"""Blockwise (flash) attention — Pallas TPU kernel.

Online-softmax attention that never materialises the [Sq, Skv] logits.
Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv dimension iterated
innermost; running max / sum / output accumulators live in VMEM scratch and
persist across kv steps (the standard TPU flash pattern).  GQA is handled
in the k/v BlockSpec index maps (head h reads kv head h * KV // H) — no
head-expansion copies.  Causal + sliding-window masking is applied
per-block; fully-masked blocks still run (grid is static) but their
contribution is zero.

Two kernel entry points:

  * ``flash_attention_pallas``       — self-contained attention over a full
    KV operand (init -> accumulate -> normalise in one pallas_call).
  * ``flash_attention_carry_pallas`` — the STREAMED variant for ring
    attention (managed.managed_ring_attention): the (m, l, acc) online-
    softmax state is carried IN and OUT instead of initialised/normalised,
    so one call consumes one KV block as it arrives off the ring and the
    next call continues where it left off.  q/k global offsets are traced
    SMEM scalars (they depend on lax.axis_index inside shard_map).
    ``merge_partials``/``finalize_partials`` are the LSE-merge combinators
    shared by this kernel, the pure-jnp engine (kernels/ops.py), and the
    distributed tests — merging partials over ANY kv split is exact up to
    float reduction order.

Both kernels take the [B, S, H, hd] operands of the model code and run
on a head-major [B, H, S, hd] copy, so every block is a (blk, hd) tile in
the last two dims, as the TPU lowering requires.

VMEM budget per step (bf16, blk_q = blk_kv = 512, hd = 256):
q/k/v blocks 3 * 512*256*2 = 768 KB + f32 accumulators 512*256*4 = 512 KB
— inside v5e's 16 MiB default scoped VMEM with double buffering; block
sizes are MXU-aligned multiples of 128 (tuned in ops.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, q_offset: int, blk_q: int,
                  blk_kv: int, n_kv_blocks: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)               # [blk_q, hd]
    k = k_ref[...].astype(jnp.float32)               # [blk_kv, hd]
    v = v_ref[...].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [blk_q, blk_kv]

    qpos = q_offset + qi * blk_q + lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_kv), 0)
    kpos = ki * blk_kv + lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_kv), 1)
    mask = jnp.ones((blk_q, blk_kv), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]                              # [blk_q, 1]
    l_prev = l_scr[...]
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    p = jnp.where(mask, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: Array, k: Array, v: Array, *,
                           causal: bool = True, window: int = 0,
                           q_offset: int = 0, blk_q: int = 128,
                           blk_kv: int = 128,
                           interpret: bool = False) -> Array:
    """q: [B, Sq, H, hd]; k, v: [B, Skv, KV, hd] -> [B, Sq, H, hd]."""
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    blk_q = min(blk_q, sq)
    blk_kv = min(blk_kv, skv)
    assert sq % blk_q == 0 and skv % blk_kv == 0, (sq, skv, blk_q, blk_kv)
    nq = sq // blk_q
    nk = skv // blk_kv
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, q_offset=q_offset,
        blk_q=blk_q, blk_kv=blk_kv, n_kv_blocks=nk, scale=scale)

    # Head-major layout [B, H, S, hd]: the kernel's blocks are then
    # (blk, hd) tiles in the last two dims, which Mosaic tiles as
    # (sublane, lane).  A [B, S, H, hd] block would squeeze H in the
    # second-minor position, which the TPU lowering refuses.
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    grid = (b, h, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, blk_q, hd),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((None, None, blk_kv, hd),
                         lambda b_, h_, qi, ki, kvh=kvh, h=h:
                         (b_, h_ * kvh // h, ki, 0)),
            pl.BlockSpec((None, None, blk_kv, hd),
                         lambda b_, h_, qi, ki, kvh=kvh, h=h:
                         (b_, h_ * kvh // h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, blk_q, hd),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Online-softmax partials: init / merge / finalize
#
# Public carry layout (matches q): m, l: [B, Sq, H] f32; acc: [B, Sq, H, hd]
# f32.  ``out = acc / l`` and ``lse = m + log(l)`` only at finalize — every
# intermediate stays unnormalised so partials from disjoint KV ranges
# combine with one LSE merge.
# ---------------------------------------------------------------------------


def init_partials(b: int, sq: int, h: int, hd: int
                  ) -> tuple[Array, Array, Array]:
    """Empty carry: max = -inf (finite sentinel), sum = 0, acc = 0."""
    m = jnp.full((b, sq, h), NEG_INF, jnp.float32)
    l = jnp.zeros((b, sq, h), jnp.float32)
    acc = jnp.zeros((b, sq, h, hd), jnp.float32)
    return m, l, acc


def merge_partials(p1: tuple[Array, Array, Array],
                   p2: tuple[Array, Array, Array]
                   ) -> tuple[Array, Array, Array]:
    """LSE-merge two flash partials over disjoint KV ranges.  Commutative
    and associative up to float rounding; an empty carry (init_partials)
    is the identity."""
    m1, l1, a1 = p1
    m2, l2, a2 = p2
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)
    w2 = jnp.exp(m2 - m)
    l = w1 * l1 + w2 * l2
    acc = w1[..., None] * a1 + w2[..., None] * a2
    return m, l, acc


def finalize_partials(m: Array, l: Array, acc: Array,
                      out_dtype=jnp.float32) -> tuple[Array, Array]:
    """(m, l, acc) -> (out [B, Sq, H, hd], lse [B, Sq, H])."""
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(out_dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


# ---------------------------------------------------------------------------
# Carry-in / carry-out kernel (ring-attention step)
# ---------------------------------------------------------------------------


def _flash_kernel_carry(off_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                        m_out, l_out, acc_out, m_scr, l_scr, acc_scr, *,
                        causal: bool, window: int, blk_q: int, blk_kv: int,
                        n_kv_blocks: int, scale: float):
    """Same online-softmax update as _flash_kernel, but the running state
    enters/leaves through refs instead of being initialised/normalised, and
    the q/k global offsets come from SMEM (traced per-rank values)."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_offset = off_ref[0, 0]
    k_offset = off_ref[0, 1]

    @pl.when(ki == 0)
    def _load_carry():
        m_scr[...] = m_in[...]
        l_scr[...] = l_in[...]
        acc_scr[...] = acc_in[...]

    q = q_ref[...].astype(jnp.float32)               # [blk_q, hd]
    k = k_ref[...].astype(jnp.float32)               # [blk_kv, hd]
    v = v_ref[...].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [blk_q, blk_kv]

    qpos = q_offset + qi * blk_q + lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_kv), 0)
    kpos = k_offset + ki * blk_kv + lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_kv), 1)
    mask = jnp.ones((blk_q, blk_kv), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]                              # [blk_q, 1]
    l_prev = l_scr[...]
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    p = jnp.where(mask, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _store_carry():
        m_out[...] = m_scr[...]
        l_out[...] = l_scr[...]
        acc_out[...] = acc_scr[...]


def flash_attention_carry_pallas(q: Array, k: Array, v: Array,
                                 m: Array, l: Array, acc: Array, *,
                                 causal: bool = True, window: int = 0,
                                 q_offset=0, k_offset=0,
                                 blk_q: int = 128, blk_kv: int = 128,
                                 interpret: bool = False
                                 ) -> tuple[Array, Array, Array]:
    """One streamed flash step: fold the KV block [B, Skv, KV, hd] into the
    carry (m, l, acc) for q [B, Sq, H, hd].  ``q_offset``/``k_offset`` are
    the GLOBAL positions of q[0]/k[0] and may be traced int32 scalars
    (ring ranks derive them from lax.axis_index) — they ride in SMEM."""
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    blk_q = min(blk_q, sq)
    blk_kv = min(blk_kv, skv)
    assert sq % blk_q == 0 and skv % blk_kv == 0, (sq, skv, blk_q, blk_kv)
    nq = sq // blk_q
    nk = skv // blk_kv
    scale = 1.0 / math.sqrt(hd)

    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)]).reshape(1, 2)
    # head-major layout, as in flash_attention_pallas; m and l get a
    # trailing unit dim so their blocks are 2-D (blk_q, 1) tiles
    qt, kt, vt, acc_t = (x.transpose(0, 2, 1, 3) for x in (q, k, v, acc))
    m4 = m.transpose(0, 2, 1)[..., None]             # [B, H, Sq, 1]
    l4 = l.transpose(0, 2, 1)[..., None]

    kernel = functools.partial(
        _flash_kernel_carry, causal=causal, window=window,
        blk_q=blk_q, blk_kv=blk_kv, n_kv_blocks=nk, scale=scale)

    grid = (b, h, nq, nk)
    kv_spec = pl.BlockSpec((None, None, blk_kv, hd),
                           lambda b_, h_, qi, ki, kvh=kvh, h=h:
                           (b_, h_ * kvh // h, ki, 0))
    ml_spec = pl.BlockSpec((None, None, blk_q, 1),
                           lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    acc_spec = pl.BlockSpec((None, None, blk_q, hd),
                            lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    m_new, l_new, acc_new = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (q_offset, k_offset)
            acc_spec,
            kv_spec,
            kv_spec,
            ml_spec, ml_spec, acc_spec,
        ],
        out_specs=[ml_spec, ml_spec, acc_spec],
        out_shape=[
            jax.ShapeDtypeStruct(m4.shape, jnp.float32),
            jax.ShapeDtypeStruct(l4.shape, jnp.float32),
            jax.ShapeDtypeStruct(acc_t.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(offs, qt, kt, vt, m4, l4, acc_t)
    return (m_new[..., 0].transpose(0, 2, 1), l_new[..., 0].transpose(0, 2, 1),
            acc_new.transpose(0, 2, 1, 3))
