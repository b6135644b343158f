"""Grouped-expert GEMM — Pallas TPU kernel + jnp oracle engine.

The MoE capacity buffers are [G, C, D] groups of padded rows (G = E
experts, or E_loc x tp (expert, source-rank) groups after the EP
all_to_all); only the first ``valid[g]`` rows of each group hold real
tokens — the rest are zero padding sized by the capacity factor.  A plain
einsum burns FLOPs on every padded row; this kernel walks the groups with
a scalar-prefetched per-group valid count (from ``dispatch_indices``'
keep mask) so capacity blocks past the valid rows are skipped outright —
padded rows cost no FLOPs.

Two engines with identical math (engine-matched on the shared pattern):

  * ``grouped_expert_ffn`` with the Pallas path — grid (G, C/blk, F/blk_f);
    the valid counts ride in scalar-prefetch SMEM
    (``pltpu.PrefetchScalarGridSpec``, same mechanism as
    kernels/paged_attention.py's page tables); blocks whose first row is
    past ``valid[g]`` write zeros without touching the MXU, partial
    blocks mask rows before the dot so the zero rows contribute exact
    zeros.  d_ff is cut into tiles so the weight blocks fit VMEM, and
    the down-projection accumulates over them in f32.
  * the jnp engine — rows masked by the same predicate, then the batched
    einsum; bit-exact against the kernel when d_ff is one tile (both
    contract D in f32 with the same activation ops) including the padded
    capacity rows; over several tiles only the f32 summation order of
    the down-projection differs.

The Pallas path carries a custom VJP whose backward recomputes through
the jnp engine (the padded-row saving is a forward-schedule property;
the backward reuses the masked operands, O(G x C x D) residuals).
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _act(mlp: str, u: Array, g: Array | None) -> Array:
    """models/layers.py::activation, replicated here so the kernel layer
    does not import the model layer (same jnp primitives — engine match
    relies on it)."""
    if mlp == "swiglu":
        return jax.nn.silu(u) * g
    if mlp == "geglu":
        return jax.nn.gelu(u) * g
    if mlp == "relu2":
        r = jax.nn.relu(u)
        return r * r
    if mlp == "gelu":
        return jax.nn.gelu(u)
    raise ValueError(mlp)


def gated(mlp: str) -> bool:
    return mlp in ("swiglu", "geglu")


def _mask_rows(h: Array, valid: Array) -> Array:
    """Zero rows >= valid[g] (h: [G, C, D]; valid: [G])."""
    rows = jnp.arange(h.shape[1])
    live = rows[None, :, None] < valid[:, None, None]
    return jnp.where(live, h, jnp.zeros((), h.dtype))


# ---------------------------------------------------------------------------
# jnp engine (the oracle; also the backward of the Pallas path)
# ---------------------------------------------------------------------------


def grouped_expert_ffn_jnp(h: Array, w1: Array, w1_gate: Array | None,
                           w2: Array, valid: Array, mlp: str) -> Array:
    """h: [G, C, D] capacity groups; valid: [G] rows kept per group;
    w1 (+w1_gate): [E, D, F]; w2: [E, F, D] with G % E == 0 (group g uses
    expert g // (G/E) — the (expert, source-rank) grouping of the EP
    all_to_all).  Returns [G, C, D] in h's dtype; rows >= valid are
    exactly zero."""
    e = w1.shape[0]
    gpe = h.shape[0] // e
    hm = _mask_rows(h, valid)

    def per_expert(w):
        return jnp.repeat(w, gpe, axis=0) if gpe > 1 else w

    u = jnp.einsum("gcd,gdf->gcf", hm, per_expert(w1),
                   preferred_element_type=jnp.float32)
    if gated(mlp):
        g = jnp.einsum("gcd,gdf->gcf", hm, per_expert(w1_gate),
                       preferred_element_type=jnp.float32)
        act = _act(mlp, u, g)
    else:
        act = _act(mlp, u, None)
    out = jnp.einsum("gcf,gfd->gcd", act, per_expert(w2).astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(h.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


#: VMEM the kernel's blocks may take (v5e's default scoped VMEM limit is
#: 16 MiB; the rest is left to Mosaic's own temporaries)
VMEM_BUDGET = 12 * 1024 * 1024


def _ff_block(d: int, f: int, blk_c: int, n_w: int, itemsize: int) -> int:
    """Largest d_ff tile whose double-buffered weight, activation and
    accumulator blocks fit ``VMEM_BUDGET``: all of F, or a multiple of 128
    that divides F (the lane width a weight block must be cut at)."""
    fixed = 2 * 2 * blk_c * d * itemsize + blk_c * d * 4  # h, out, acc
    for blk in [f] + [c for c in range(f - f % 128, 0, -128) if f % c == 0]:
        if fixed + 2 * n_w * d * blk * itemsize <= VMEM_BUDGET:
            return blk
    raise ValueError(
        f"grouped expert FFN: no d_ff tile of F={f} fits "
        f"{VMEM_BUDGET} B of VMEM at D={d}, blk_c={blk_c}")


def _gemm_kernel(valid_ref, h_ref, *refs, blk_c: int, mlp: str,
                 n_f: int):
    if gated(mlp):
        w1_ref, w1g_ref, w2_ref, o_ref, acc_ref = refs
    else:
        w1_ref, w2_ref, o_ref, acc_ref = refs
        w1g_ref = None
    g = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    v = valid_ref[g]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * blk_c < v)
    def _compute():
        rows = i * blk_c + jax.lax.broadcasted_iota(jnp.int32,
                                                    (blk_c, 1), 0)
        h = jnp.where(rows < v, h_ref[...], jnp.zeros((), h_ref.dtype))
        u = jax.lax.dot_general(h, w1_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if w1g_ref is not None:
            gg = jax.lax.dot_general(h, w1g_ref[...],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            act = _act(mlp, u, gg)
        else:
            act = _act(mlp, u, None)
        # the activation is elementwise in d_ff, so each d_ff tile's
        # down-projection is a partial sum of the output
        acc_ref[...] += jax.lax.dot_general(
            act, w2_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # fully padded capacity blocks skip the MXU and write exact zeros
    @pl.when(j == n_f - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_expert_ffn_pallas(h: Array, w1: Array, w1_gate: Array | None,
                              w2: Array, valid: Array, mlp: str, *,
                              blk_c: int = 128,
                              interpret: bool = False) -> Array:
    """The Pallas engine (see module docstring).  ``valid`` may be any
    integer/float array of per-group counts; blocks wholly past the count
    are skipped via the scalar-prefetched predicate.  The grid walks
    (group, capacity block, d_ff tile); d_ff is tiled so the weight
    blocks fit VMEM (``_ff_block``)."""
    G, c, d = h.shape
    e, _, f = w1.shape
    gpe = G // e
    assert G % e == 0, (G, e)
    blk = blk_c if (c % blk_c == 0) else c
    n_w = 3 if gated(mlp) else 2
    blk_f = _ff_block(d, f, blk, n_w, h.dtype.itemsize)
    n_f = f // blk_f
    kernel = functools.partial(_gemm_kernel, blk_c=blk, mlp=mlp, n_f=n_f)

    w_in_spec = pl.BlockSpec((None, d, blk_f),
                             lambda g_, i, j, v: (g_ // gpe, 0, j))
    in_specs = [pl.BlockSpec((None, blk, d), lambda g_, i, j, v: (g_, i, 0)),
                w_in_spec]
    operands = [h, w1]
    if gated(mlp):
        in_specs.append(w_in_spec)
        operands.append(w1_gate)
    in_specs.append(pl.BlockSpec((None, blk_f, d),
                                 lambda g_, i, j, v: (g_ // gpe, j, 0)))
    operands.append(w2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # valid counts
        grid=(G, c // blk, n_f),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, blk, d),
                               lambda g_, i, j, v: (g_, i, 0)),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
        interpret=interpret,
    )(valid.astype(jnp.int32), *operands)


# ---------------------------------------------------------------------------
# Differentiable Pallas path: bwd recomputes through the jnp engine
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _pallas_ffn(operands, valid_f, mlp, interpret):
    h, w1, w1g, w2 = operands
    return grouped_expert_ffn_pallas(h, w1, w1g if w1g is not None else None,
                                     w2, valid_f, mlp, interpret=interpret)


def _pallas_ffn_fwd(operands, valid_f, mlp, interpret):
    return _pallas_ffn(operands, valid_f, mlp, interpret), \
        (operands, valid_f)


def _pallas_ffn_bwd(mlp, interpret, res, dy):
    operands, valid_f = res
    h, w1, w1g, w2 = operands

    def ref(h_, w1_, w1g_, w2_):
        return grouped_expert_ffn_jnp(h_, w1_, w1g_, w2_, valid_f, mlp)

    if w1g is None:
        _, vjp = jax.vjp(lambda a, b, c: ref(a, b, None, c), h, w1, w2)
        dh, dw1, dw2 = vjp(dy)
        dw1g = None
    else:
        _, vjp = jax.vjp(ref, h, w1, w1g, w2)
        dh, dw1, dw1g, dw2 = vjp(dy)
    return (dh, dw1, dw1g, dw2), jnp.zeros_like(valid_f)


_pallas_ffn.defvjp(_pallas_ffn_fwd, _pallas_ffn_bwd)


# ---------------------------------------------------------------------------
# Dispatch (mirrors kernels/paged_attention.py::paged_attention)
# ---------------------------------------------------------------------------


def grouped_expert_ffn(h: Array, w1: Array, w1_gate: Array | None,
                       w2: Array, valid: Array, *, mlp: str,
                       engine: str = "auto") -> Array:
    """Batched expert FFN over capacity groups with padded rows skipped:
    Pallas kernel on TPU (or REPRO_PALLAS=interpret), jnp masked einsum
    elsewhere.  ``engine`` pins an implementation for tests."""
    from repro.kernels.ops import _pallas_mode
    # valid rides as f32 through the custom VJP (counts are tiny ints —
    # exact in f32) so the cotangent is ordinary zeros, not float0
    valid_f = valid.astype(jnp.float32)
    if engine == "pallas" or (engine == "auto"
                              and _pallas_mode() in ("on", "interpret")):
        return _pallas_ffn((h, w1, w1_gate, w2), valid_f, mlp,
                           _pallas_mode() != "on")
    return grouped_expert_ffn_jnp(h, w1, w1_gate, w2, valid_f, mlp)
