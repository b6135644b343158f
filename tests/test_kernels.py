"""Pallas kernel validation: shape/dtype sweeps, interpret mode on CPU,
assert_allclose against the pure-jnp oracles (ref.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (test extra)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (finalize_partials,
                                           flash_attention_carry_pallas,
                                           flash_attention_pallas,
                                           init_partials, merge_partials)
from repro.kernels.stencil import (jacobi_ksweep_pallas,
                                   jacobi_multistep_pallas,
                                   jacobi_step_pallas)


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,skv,h,kvh,hd", [
    (1, 128, 128, 4, 4, 64),      # MHA
    (2, 128, 128, 4, 2, 64),      # GQA 2:1
    (1, 256, 256, 8, 1, 32),      # MQA
    (1, 64, 256, 4, 4, 128),      # cross-shaped (Sq != Skv)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_vs_ref(b, sq, skv, h, kvh, hd, causal, dtype):
    rng = np.random.default_rng(0)
    q = _rand(rng, (b, sq, h, hd), dtype)
    k = _rand(rng, (b, skv, kvh, hd), dtype)
    v = _rand(rng, (b, skv, kvh, hd), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, blk_q=64,
                                 blk_kv=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_pallas_sliding_window(window):
    rng = np.random.default_rng(1)
    q = _rand(rng, (1, 128, 4, 32), jnp.float32)
    k = _rand(rng, (1, 128, 2, 32), jnp.float32)
    v = _rand(rng, (1, 128, 2, 32), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 blk_q=32, blk_kv=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_flash_blockwise_matches_ref_long():
    rng = np.random.default_rng(2)
    q = _rand(rng, (1, 512, 2, 64), jnp.float32)
    k = _rand(rng, (1, 512, 2, 64), jnp.float32)
    v = _rand(rng, (1, 512, 2, 64), jnp.float32)
    out = ops.flash_attention_blockwise(q, k, v, causal=True, blk_kv=128)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=3),
       st.sampled_from([32, 64]))
@settings(max_examples=12, deadline=None)
def test_flash_blockwise_property(b, kvh_mult, hd):
    """Property sweep: blockwise == dense for random GQA configurations."""
    rng = np.random.default_rng(b * 100 + kvh_mult * 10 + hd)
    kvh = kvh_mult
    h = kvh * 2
    q = _rand(rng, (b, 128, h, hd), jnp.float32)
    k = _rand(rng, (b, 128, kvh, hd), jnp.float32)
    v = _rand(rng, (b, 128, kvh, hd), jnp.float32)
    out = ops.flash_attention_blockwise(q, k, v, causal=True, blk_kv=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


# -- online-softmax merge (ring attention's combiner) ------------------------


@given(st.integers(min_value=0, max_value=2 ** 30),
       st.integers(min_value=1, max_value=3),
       st.booleans(),
       st.sampled_from([0, 37]),
       st.integers(min_value=2, max_value=5))
@settings(max_examples=20, deadline=None)
def test_online_softmax_merge_property(seed, kvh, causal, window, n_cuts):
    """Merging flash partials over an ARBITRARY kv-block split (chained
    carry AND pairwise merge_partials, any order) is bit-tolerant against
    attend_ref on the full sequence — causal, sliding-window, and GQA
    head-group cases.  This is the invariant ring attention rests on."""
    rng = np.random.default_rng(seed)
    b, sq, skv, hd = 1, 32, 96, 16
    h = kvh * 2                                     # GQA 2:1
    q = _rand(rng, (b, sq, h, hd), jnp.float32)
    k = _rand(rng, (b, skv, kvh, hd), jnp.float32)
    v = _rand(rng, (b, skv, kvh, hd), jnp.float32)
    q_offset = skv - sq                             # q at the sequence end

    cuts = sorted(set(rng.integers(1, skv, size=n_cuts).tolist()))
    bounds = [0, *cuts, skv]
    segments = list(zip(bounds[:-1], bounds[1:]))

    want = np.asarray(ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, q_offset=q_offset))

    # (a) chained carry through the segments in order
    carry = None
    # (b) independent partials, merged pairwise in REVERSED order
    partials = []
    for lo, hi in segments:
        carry = ops.flash_attention_step(
            q, k[:, lo:hi], v[:, lo:hi], carry, causal=causal,
            window=window, q_offset=q_offset, k_offset=lo)
        partials.append(ops.flash_attention_step(
            q, k[:, lo:hi], v[:, lo:hi], None, causal=causal,
            window=window, q_offset=q_offset, k_offset=lo))
    out_chain, _ = finalize_partials(*carry)
    merged = partials[-1]
    for p in reversed(partials[:-1]):
        merged = merge_partials(merged, p)
    out_merge, _ = finalize_partials(*merged)

    np.testing.assert_allclose(np.asarray(out_chain), want,
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(out_merge), want,
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_carry_pallas_matches_jnp_engine(causal):
    """The Pallas carry kernel (interpret mode) and the jnp engine produce
    the same partials for the same KV block, traced offsets included."""
    rng = np.random.default_rng(5)
    b, sq, skv, h, kvh, hd = 1, 64, 64, 4, 2, 32
    q = _rand(rng, (b, sq, h, hd), jnp.float32)
    k = _rand(rng, (b, skv, kvh, hd), jnp.float32)
    v = _rand(rng, (b, skv, kvh, hd), jnp.float32)
    m0, l0, a0 = init_partials(b, sq, h, hd)
    got = flash_attention_carry_pallas(
        q, k, v, m0, l0, a0, causal=causal, q_offset=jnp.int32(64),
        k_offset=jnp.int32(32), blk_q=32, blk_kv=32, interpret=True)
    want = ops._flash_step_jnp(q, k, v, m0, l0, a0, causal, 0,
                               jnp.int32(64), jnp.int32(32), 32)
    for g, w, nm in zip(got, want, ("m", "l", "acc")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5, err_msg=nm)


@pytest.mark.parametrize("m,n,bm,bn", [
    (66, 130, 64, 128),
    (130, 130, 64, 64),
    (258, 514, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jacobi_pallas_vs_ref(m, n, bm, bn, dtype):
    rng = np.random.default_rng(3)
    u = _rand(rng, (m, n), dtype)
    f = _rand(rng, (m, n), dtype)
    out = jacobi_step_pallas(u, f, blk_m=bm, blk_n=bn, interpret=True)
    want = ref.jacobi_step_ref(u, f)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m,n,bm", [
    (66, 130, 64),        # 66 rows padded up to two 64-row blocks
    (256, 130, 64),       # 4-block grid
    (128, 258, 16),       # 8-block grid, tiny tiles
])
@pytest.mark.parametrize("k", [1, 2, 3, 4, 8])
def test_jacobi_multistep_vs_k_unit_sweeps(m, n, bm, k):
    """The temporally-blocked kernel (k sweeps per HBM round-trip) must
    match k applications of the unit-sweep oracle exactly — the trapezoid
    plus frozen Dirichlet edges is redundant compute, not approximation."""
    rng = np.random.default_rng(7)
    u = _rand(rng, (m, n), jnp.float32)
    f = _rand(rng, (m, n), jnp.float32)
    out = jacobi_multistep_pallas(u, f, k=k, blk_m=bm, interpret=True)
    want = ref.jacobi_multistep_ref(u, f, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_jacobi_multistep_bf16():
    rng = np.random.default_rng(8)
    u = _rand(rng, (128, 130), jnp.bfloat16)
    f = _rand(rng, (128, 130), jnp.bfloat16)
    out = jacobi_multistep_pallas(u, f, k=4, blk_m=32, interpret=True)
    want = ref.jacobi_multistep_ref(u, f, 4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_jacobi_ksweep_slab_interior(k):
    """The distributed slab kernel: with a k-deep apron of true neighbour
    rows (frozen depths 0), the center must equal k unit sweeps of the
    larger grid restricted to the center rows."""
    rng = np.random.default_rng(9)
    m, n = 64, 130
    big = _rand(rng, (m + 2 * k, n), jnp.float32)
    fbig = _rand(rng, (m + 2 * k, n), jnp.float32)
    out = jacobi_ksweep_pallas(big, fbig, k, 0, 0, blk_m=32, interpret=True)
    # Oracle: k sweeps on the padded grid where EVERY row updates (the slab
    # kernel's apron rows are live neighbour rows, not Dirichlet): emulate
    # by padding the big grid with one more frozen ring per sweep.
    want = big
    for _ in range(k):
        z = jnp.zeros((1, n), jnp.float32)
        up = jnp.concatenate([z, want, z], axis=0)
        new = 0.25 * (up[:-2, 1:-1] + up[2:, 1:-1] + up[1:-1, :-2]
                      + up[1:-1, 2:] - fbig[:, 1:-1])
        want = want.at[:, 1:-1].set(new)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(want[k:-k]),
                               rtol=1e-6, atol=1e-6)


def test_jacobi_converges():
    """Sweeps reduce the residual of Laplace's equation (sanity that the
    kernel computes the right operator, not just matches the ref once)."""
    n = 66
    u = jnp.zeros((n, n), jnp.float32).at[0, :].set(1.0)
    f = jnp.zeros((n, n), jnp.float32)
    def residual(u):
        r = ref.jacobi_step_ref(u, f) - u
        return float(jnp.abs(r).max())
    r0 = residual(u)
    for _ in range(50):
        u = jacobi_step_pallas(u, f, blk_m=64, blk_n=64, interpret=True)
    assert residual(u) < r0


def test_flash_untileable_length_raises_when_kernels_on(monkeypatch):
    """With the kernels on, a length the blocks do not tile must not run
    the reference in the kernel's place."""
    monkeypatch.setenv("REPRO_PALLAS", "on")
    rng = np.random.default_rng(10)
    q = _rand(rng, (1, 200, 2, 32), jnp.float32)
    kv = _rand(rng, (1, 200, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="do not tile"):
        ops.flash_attention(q, kv, kv, causal=True)


def test_ksweep_row_block_sized_or_refused():
    """The k-sweep kernel sizes its row block to the VMEM budget, rejects
    a block that breaks the apron grid, and refuses a row too wide for
    any block instead of taking the whole shard."""
    from repro.kernels import stencil
    assert stencil._row_block(16384, 16384, 8, 4, None) % 16 == 0
    with pytest.raises(ValueError, match="multiple of"):
        stencil._row_block(64, 130, 8, 4, 24)
    with pytest.raises(ValueError, match="VMEM"):
        stencil._row_block(64, 1 << 22, 8, 4, None)
