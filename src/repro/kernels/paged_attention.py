"""Paged decode attention — Pallas TPU kernel + jnp oracle engine.

The serving runtime (repro/serve) stores each sequence's KV cache as a
chain of fixed-size PAGES drawn from a shared pool ([n_pages, page, KV,
hd] per layer) instead of a contiguous [B, S_max, KV, hd] slab; a per-slot
page table maps logical block i of slot b to pool page ``table[b, i]``.
Decode attention then has to gather K/V *through the page table* — the
classic vLLM paged-attention shape.

Two engines with identical math:

  * ``paged_attention_pallas`` — the table rides in scalar-prefetch SMEM
    (``pltpu.PrefetchScalarGridSpec``): the k/v BlockSpec index maps read
    ``table[b, i]`` to pick which pool page the next grid step DMAs, so
    the gather costs nothing beyond the page loads themselves.  The
    (m, l, acc) online-softmax state accumulates across the page grid in
    VMEM scratch exactly like kernels/flash_attention.py.
  * ``paged_attention_partials_jnp`` — a lax.scan over table columns that
    computes one flash partial per page and folds it with the
    ``merge_partials`` LSE combinator (the same combinator the ring
    attention and the distributed tests use).  It additionally supports a
    traced ``pool_offset`` for pools sharded over mesh axes: pages owned
    by other ranks contribute an empty partial, and the caller LSE-merges
    across the mesh (flash-decoding, distributed — see
    models/attention.py::attention_decode_paged).

Per-slot queries are single tokens (q: [B, H, hd]); ``lens[b]`` is the
number of valid cache positions of slot b (0 = nothing to attend — the
finalize guard returns zeros).  Sliding windows mask ``kpos <
lens - window`` so SWA layers can keep their full page chain.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, finalize_partials,
                                           init_partials, merge_partials)

Array = jax.Array


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page: int, n_pages_max: int,
                  window: int, scale: float, groups: int):
    """Online-softmax accumulation over one slot's page chain.  Grid is
    (B, n_pages_max) with pages innermost; the k/v refs already hold pool
    page ``table[b, i]`` (the index maps did the gather)."""
    i = pl.program_id(1)
    b = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                # [H, hd]
    # Flatten the page to 2-D rows (pos, kv head) -> pos * KV + kv so both
    # products are plain 2-D MXU dots; a q head scores only the columns
    # of its own kv head (GQA: head h reads kv head h // groups).
    kvh = k_ref.shape[1]
    k = k_ref[...].astype(jnp.float32).reshape(page * kvh, -1)
    v = v_ref[...].astype(jnp.float32).reshape(page * kvh, -1)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [H, page * KV]

    valid_len = len_ref[b]
    col = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    head = lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    kpos = i * page + col // kvh
    mask = (kpos < valid_len) & (col % kvh == head // groups)
    if window > 0:
        mask &= kpos >= valid_len - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]                               # [H, 1]
    l_prev = l_scr[...]
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    p = jnp.where(mask, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [H, hd]
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(i == n_pages_max - 1)
    def _finish():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q: Array, k_pages: Array, v_pages: Array,
                           table: Array, lens: Array, *, window: int = 0,
                           interpret: bool = False) -> Array:
    """q: [B, H, hd]; k_pages, v_pages: [n_pages, page, KV, hd];
    table: [B, n_pages_max] int32 pool page ids (unused entries may hold
    any in-range id — their positions are masked by ``lens``);
    lens: [B] int32 valid lengths.  Returns [B, H, hd] in q's dtype."""
    b, h, hd = q.shape
    n_pool, page, kvh, _ = k_pages.shape
    n_pages_max = table.shape[1]
    groups = h // kvh
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _paged_kernel, page=page, n_pages_max=n_pages_max, window=window,
        scale=scale, groups=groups)

    kv_spec = pl.BlockSpec(
        (None, page, kvh, hd),
        lambda b_, i, tbl, ln: (tbl[b_, i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # table, lens
        grid=(b, n_pages_max),
        in_specs=[
            pl.BlockSpec((None, h, hd), lambda b_, i, tbl, ln: (b_, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((None, h, hd),
                               lambda b_, i, tbl, ln: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), lens.astype(jnp.int32), q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# jnp engine: per-page partials merged with the shared LSE combinators
# ---------------------------------------------------------------------------


def paged_attention_partials_jnp(q: Array, k_pages: Array, v_pages: Array,
                                 table: Array, lens: Array, *,
                                 window: int = 0, pool_offset=0
                                 ) -> tuple[Array, Array, Array]:
    """Flash partials of ``q`` [B, H, hd] against the page chains in a
    (possibly rank-local) pool.  ``pool_offset`` (may be a traced scalar —
    mesh ranks derive it from their cache rank) converts the table's
    GLOBAL page ids to local pool indices: entries outside the local pool
    contribute an empty partial, so partials from all ranks LSE-merge to
    the full attention.  Returns (m, l, acc) in the public
    [B, 1, H] / [B, 1, H, hd] carry layout of kernels/flash_attention.py.
    """
    b, h, hd = q.shape
    n_loc, page, kvh, _ = k_pages.shape
    groups = h // kvh
    n_pages_max = table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # grouped GQA layout (q head h = kv*G + g, matching the kernels'
    # h // G mapping): accumulate in f32 WITHOUT materialising a
    # group-expanded copy of the pages — same trick as attention_decode
    qg = (q.astype(jnp.float32) * scale).reshape(b, kvh, groups, hd)

    def body(carry, i):
        pid = table[:, i].astype(jnp.int32) - pool_offset        # [B]
        owned = (pid >= 0) & (pid < n_loc)
        safe = jnp.clip(pid, 0, n_loc - 1)
        kb = k_pages[safe]                         # [B, page, KV, hd]
        vb = v_pages[safe]
        logits = jnp.einsum("bkgd,bskd->bkgs", qg, kb,
                            preferred_element_type=jnp.float32)
        kpos = i * page + jnp.arange(page)                       # [page]
        valid = owned[:, None] & (kpos[None, :] < lens[:, None])
        if window > 0:
            valid &= kpos[None, :] >= lens[:, None] - window
        vmask = valid[:, None, None, :]            # [B, 1, 1, page]
        logits = jnp.where(vmask, logits, NEG_INF)
        m_i = jnp.max(logits, axis=-1)                      # [B, KV, G]
        p_i = jnp.exp(logits - m_i[..., None])
        p_i = jnp.where(vmask, p_i, 0.0)
        l_i = jnp.sum(p_i, axis=-1)
        acc_i = jnp.einsum("bkgs,bskd->bkgd", p_i.astype(vb.dtype), vb,
                           preferred_element_type=jnp.float32)
        part = (m_i.reshape(b, 1, h), l_i.reshape(b, 1, h),
                acc_i.astype(jnp.float32).reshape(b, 1, h, hd))
        return merge_partials(carry, part), None

    carry = init_partials(b, 1, h, hd)
    carry, _ = lax.scan(body, carry, jnp.arange(n_pages_max))
    return carry


def paged_attention_jnp(q: Array, k_pages: Array, v_pages: Array,
                        table: Array, lens: Array, *,
                        window: int = 0) -> Array:
    """Self-contained jnp paged attention (the kernel's oracle)."""
    m, l, acc = paged_attention_partials_jnp(
        q, k_pages, v_pages, table, lens, window=window)
    out, _ = finalize_partials(m, l, acc, out_dtype=q.dtype)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Dispatch (mirrors kernels/ops.py::flash_attention)
# ---------------------------------------------------------------------------


def paged_kernel_enabled() -> bool:
    from repro.kernels.ops import _pallas_mode
    return _pallas_mode() in ("on", "interpret")


def paged_attention(q: Array, k_pages: Array, v_pages: Array, table: Array,
                    lens: Array, *, window: int = 0,
                    engine: str = "auto") -> Array:
    """Decode attention through a page table: Pallas kernel on TPU (or
    REPRO_PALLAS=interpret), jnp page-scan elsewhere.  ``engine`` pins an
    implementation for tests."""
    from repro.kernels.ops import _pallas_mode
    if engine == "pallas" or (engine == "auto"
                              and _pallas_mode() in ("on", "interpret")):
        return paged_attention_pallas(
            q, k_pages, v_pages, table, lens, window=window,
            interpret=(_pallas_mode() != "on"))
    return paged_attention_jnp(q, k_pages, v_pages, table, lens,
                               window=window)
