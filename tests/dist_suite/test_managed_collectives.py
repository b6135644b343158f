"""Managed collectives vs bulk oracles on 8 devices: every op, every mode,
chunk counts, gradients through the custom-VJP rings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import managed
from repro.parallel.sharding import smap

N = 8


def run(mesh, fn, in_specs, out_specs, *args):
    return jax.jit(smap(fn, mesh, in_specs=in_specs,
                        out_specs=out_specs))(*args)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    return {
        "shard": jnp.asarray(rng.normal(size=(N * 4, 6)).astype(np.float32)),
        "full": jnp.asarray(rng.normal(size=(32, 6)).astype(np.float32)),
        "w": jnp.asarray(rng.normal(size=(6, 5)).astype(np.float32)),
    }


@pytest.mark.parametrize("mode,chunks", [("bulk", 1), ("interleaved", 1),
                                         ("interleaved", 2)])
def test_all_gather(mesh8, data, mode, chunks):
    out = run(mesh8,
              lambda a: managed.managed_all_gather(a, "x", mode, chunks),
              (P("x"),), P(None), data["shard"])
    np.testing.assert_allclose(out, data["shard"], rtol=1e-6)


@pytest.mark.parametrize("mode,chunks", [("bulk", 1), ("interleaved", 1),
                                         ("interleaved", 2)])
def test_reduce_scatter(mesh8, data, mode, chunks):
    out = run(mesh8,
              lambda a: managed.managed_reduce_scatter(a, "x", mode, chunks),
              (P(None),), P("x"), data["full"])
    np.testing.assert_allclose(out, data["full"] * N, rtol=1e-5)


@pytest.mark.parametrize("mode", ["bulk", "interleaved"])
def test_all_reduce(mesh8, data, mode):
    out = run(mesh8,
              lambda a: managed.managed_all_reduce(a, "x", mode=mode),
              (P(None),), P(None, None), data["full"])
    np.testing.assert_allclose(out, data["full"] * N, rtol=1e-5)


@pytest.mark.parametrize("mode", ["bulk", "interleaved"])
@pytest.mark.parametrize("split,concat", [(0, 0), (0, 1), (1, 0)])
def test_all_to_all(mesh8, mode, split, concat):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(N * 8, 16, 3)).astype(np.float32))
    ref = run(mesh8,
              lambda a: lax.all_to_all(a, "x", split, concat, tiled=True),
              (P("x"),), P("x"), x)
    out = run(mesh8,
              lambda a: managed.managed_all_to_all(
                  a, "x", split, concat, mode),
              (P("x"),), P("x"), x)
    np.testing.assert_allclose(out, ref, rtol=1e-6)


@pytest.mark.parametrize("mode,chunks", [("bulk", 1), ("interleaved", 1),
                                         ("interleaved", 2)])
def test_all_gather_matmul(mesh8, data, mode, chunks):
    want = data["shard"] @ data["w"]
    out = run(mesh8,
              lambda a, w: managed.all_gather_matmul(a, w, "x", mode,
                                                     chunks),
              (P("x"), P(None)), P(None), data["shard"], data["w"])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["bulk", "interleaved"])
def test_all_gather_matmul_multi(mesh8, data, mode):
    rng = np.random.default_rng(2)
    w2 = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
    outs = run(mesh8,
               lambda a, wa, wb: tuple(managed.all_gather_matmul_multi(
                   a, [wa, wb], "x", mode)),
               (P("x"), P(None), P(None)), (P(None), P(None)),
               data["shard"], data["w"], w2)
    np.testing.assert_allclose(outs[0], data["shard"] @ data["w"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs[1], data["shard"] @ w2,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["bulk", "interleaved"])
def test_matmul_reduce_scatter(mesh8, mode):
    rng = np.random.default_rng(3)
    xf = rng.normal(size=(32, 16)).astype(np.float32)
    wf = rng.normal(size=(16, 5)).astype(np.float32)
    out = run(mesh8,
              lambda a, w: managed.matmul_reduce_scatter(a, w, "x", mode),
              (P(None, "x"), P("x", None)), P("x", None),
              jnp.asarray(xf), jnp.asarray(wf))
    np.testing.assert_allclose(out, xf @ wf, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["bulk", "interleaved"])
def test_ring_grads_match_bulk(mesh8, data, mode):
    """Gradients through the custom-VJP rings equal the bulk-mode grads —
    the duality (AG<->RS, AG-mm<->mm-RS, gram ring) is exact."""
    w = data["w"]

    def loss_fn(mode):
        def f(a, w):
            y = managed.all_gather_matmul(a, w, "x", mode)
            z = managed.matmul_reduce_scatter(
                jnp.tanh(y), w[:5, :6], "x", mode)
            g = managed.managed_all_gather(z, "x", mode)
            return jnp.sum(g ** 2)
        return f

    def grads(mode):
        return run(mesh8, jax.grad(loss_fn(mode), argnums=(0, 1)),
                   (P("x"), P(None)), (P("x"), P(None)),
                   data["shard"], w)

    ga, gwa = grads("bulk")
    gb, gwb = grads("interleaved")
    np.testing.assert_allclose(ga, gb, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gwa, gwb, rtol=1e-4, atol=1e-4)


def test_all_reduce_ring_non_divisible(mesh8):
    """Forced ring mode is honored when axis 0 isn't divisible by the axis
    size: pad-and-slice, not a silent lax.psum demotion.  The decision log
    must show the interleaved schedule actually ran."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(5, 3)).astype(np.float32))
    managed.clear_decision_log()
    out = run(mesh8,
              lambda a: managed.managed_all_reduce(a, "x",
                                                   mode="interleaved"),
              (P(None),), P(None, None), x)
    np.testing.assert_allclose(out, x * N, rtol=1e-5)
    recs = [r for r in managed.decision_log() if r.op == "all_reduce"]
    assert recs and all(r.mode == "interleaved" for r in recs)


def test_all_reduce_scalar_fallback_logged(mesh8):
    """0-d operands still fall back to lax.psum — and the DecisionRecord
    says so (mode='bulk'), keeping the audit trail honest."""
    managed.clear_decision_log()
    out = run(mesh8,
              lambda a: managed.managed_all_reduce(a[0, 0], "x",
                                                   mode="interleaved"),
              (P(None),), P(), jnp.ones((1, 1), jnp.float32))
    np.testing.assert_allclose(out, float(N))
    recs = [r for r in managed.decision_log() if r.op == "all_reduce"]
    assert recs and all(r.mode == "bulk" for r in recs)


def test_bucketed_all_reduce_mixed_dtype(mesh8):
    """Regression: a bf16 leaf ordered FIRST must not drag f32 grads
    through a bf16 round-trip — buckets group by dtype."""
    from repro.core import overlap
    # values chosen to be destroyed by a bf16 cast (1 + 2^-10 etc.)
    f32 = (1.0 + np.arange(24, dtype=np.float32) / 1024.0).reshape(4, 6)
    bf16 = jnp.asarray(np.arange(8, dtype=np.float32), jnp.bfloat16)
    tree = {"a_bf16": bf16, "b_f32": jnp.asarray(f32)}

    out = run(mesh8,
              lambda t: overlap.bucketed_all_reduce(t, "x",
                                                    bucket_bytes=16),
              ({"a_bf16": P(None), "b_f32": P(None)},),
              {"a_bf16": P(None), "b_f32": P(None, None)}, tree)
    assert out["b_f32"].dtype == jnp.float32
    assert out["a_bf16"].dtype == jnp.bfloat16
    # exact: psum of identical f32 values x8 is a power-of-two scale
    np.testing.assert_array_equal(np.asarray(out["b_f32"]), f32 * N)
    np.testing.assert_allclose(
        np.asarray(out["a_bf16"], np.float32),
        np.asarray(bf16, np.float32) * N, rtol=1e-2)


def test_decision_log_records(mesh8, data):
    managed.clear_decision_log()
    run(mesh8, lambda a: managed.managed_all_gather(a, "x", "interleaved"),
        (P("x"),), P(None), data["shard"])
    log = managed.decision_log()
    assert any(r.op == "all_gather" and r.mode == "interleaved"
               for r in log)
