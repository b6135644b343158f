"""Jit'd kernel entry points + dispatch policy.

``flash_attention`` picks the best implementation for the runtime:
  * Pallas TPU kernel (flash_attention.py) on TPU backends, or when
    REPRO_PALLAS=interpret forces interpret-mode execution (CPU tests);
  * blockwise pure-jnp flash (same online-softmax math, lax.scan over kv
    blocks — memory O(Sq * blk)) for long sequences elsewhere, including
    the 512-device CPU dry-run where the [Sq, Skv] logits of a 32k prefill
    would be terabytes;
  * the dense reference for small shapes.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ref
from repro.kernels.flash_attention import (finalize_partials,  # noqa: F401
                                           flash_attention_carry_pallas,
                                           flash_attention_pallas,
                                           init_partials, merge_partials)
from repro.kernels.stencil import jacobi_step_pallas  # noqa: F401 (re-export)

Array = jax.Array

#: sequences at or above this use a blockwise implementation
DENSE_MAX_SEQ = 2048


def _pallas_mode() -> str:
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env in ("interpret", "on", "off"):
        return env
    return "on" if jax.default_backend() == "tpu" else "off"


def _kernel_tiles(mode: str, kernel: str, sq: int, skv: int, blk_q: int,
                  blk_kv: int) -> bool:
    """Whether the flash kernels run for these lengths.  With the kernels
    on, a length the blocks do not tile is an error: the reference would
    run in the kernel's place unseen."""
    if mode not in ("on", "interpret"):
        return False
    if sq % min(blk_q, sq) == 0 and skv % min(blk_kv, skv) == 0:
        return True
    if mode == "on":
        raise ValueError(
            f"{kernel}: Sq={sq}, Skv={skv} do not tile into "
            f"blk_q={blk_q}, blk_kv={blk_kv} blocks")
    return False


def flash_attention_applicable(q: Array, k: Array, v: Array) -> bool:
    """attend() fast-path predicate: True when any blockwise impl should
    replace the dense reference."""
    return (q.ndim == 4 and k.ndim == 4
            and q.shape[1] * k.shape[1] >= DENSE_MAX_SEQ * DENSE_MAX_SEQ
            or _pallas_mode() in ("on", "interpret"))


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "blk_q", "blk_kv"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, q_offset: int = 0, blk_q: int = 128,
                    blk_kv: int = 128) -> Array:
    return _flash_vjp(q, k, v, causal, window, q_offset, blk_q, blk_kv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, window, q_offset, blk_q, blk_kv):
    """Flash attention with a FLASH backward: fwd saves only (out, lse);
    bwd recomputes probabilities block-by-block.  Without this, scanning
    the online softmax saves a stacked f32 [nk, ..., Sq, blk] probability
    tensor per attention — measured as the top HBM/residual offender in
    every train cell."""
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, blk_q,
                             blk_kv)
    return out


def _flash_fwd_impl(q, k, v, causal, window, q_offset, blk_q, blk_kv):
    mode = _pallas_mode()
    sq, skv = q.shape[1], k.shape[1]
    if _kernel_tiles(mode, "flash_attention", sq, skv, blk_q, blk_kv):
        out = flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            blk_q=blk_q, blk_kv=blk_kv, interpret=(mode == "interpret"))
        # lse recomputed blockwise for the bwd residual (cheap: no V pass);
        # a production TPU build would emit it from the fwd kernel.
        lse = _lse_blockwise(q, k, causal, window, q_offset,
                             max(blk_kv, 512))
        return out, lse
    if sq * skv > DENSE_MAX_SEQ * DENSE_MAX_SEQ:
        return _blockwise_fwd(q, k, v, causal, window, q_offset,
                              max(blk_kv, 512))
    out = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)
    lse = _lse_blockwise(q, k, causal, window, q_offset, k.shape[1])
    return out, lse


def _flash_fwd_rule(q, k, v, causal, window, q_offset, blk_q, blk_kv):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_offset, blk_q,
                               blk_kv)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, q_offset, blk_q, blk_kv, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_blockwise(q, k, v, out, lse, dout, causal, window,
                                q_offset, max(blk_kv, 512))


_flash_vjp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _pad_kv(k, v, blk):
    skv = k.shape[1]
    if skv % blk:
        pad = blk - skv % blk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return k, v, skv


def _blk_mask(sq, blk, ki, qpos, skv_valid, causal, window):
    kpos = ki * blk + jnp.arange(blk)
    mask = jnp.broadcast_to((kpos < skv_valid)[None, :], (sq, blk))
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= qpos[:, None] - kpos[None, :] < window
    return mask


def _blockwise_fwd(q, k, v, causal, window, q_offset, blk_kv):
    """Online-softmax forward returning (out, lse)."""
    b, sq, h, hd = q.shape
    k, v, skv_valid = _pad_kv(k, v, min(blk_kv, k.shape[1]))
    skv = k.shape[1]
    blk = min(blk_kv, skv)
    nk = skv // blk
    kvh = k.shape[2]
    groups = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(b, sq, kvh, groups, hd).astype(jnp.float32) * scale
    qf = qf.transpose(0, 2, 3, 1, 4)                 # [b,kvh,g,sq,hd]
    qpos = q_offset + jnp.arange(sq)

    def body(carry, ki):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(k, ki * blk, blk, axis=1)
        vs = lax.dynamic_slice_in_dim(v, ki * blk, blk, axis=1)
        logits = jnp.einsum("bkgqd,bskd->bkgqs", qf,
                            ks.astype(jnp.float32))
        mask = _blk_mask(sq, blk, ki, qpos, skv_valid, causal, window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vs.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, groups, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, groups, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, groups, sq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))         # [b,kvh,g,sq]
    return out, lse


def _lse_blockwise(q, k, causal, window, q_offset, blk_kv):
    """LSE only (no V pass) — residual for kernels without an lse output."""
    b, sq, h, hd = q.shape
    k2, _, skv_valid = _pad_kv(k, k, min(blk_kv, k.shape[1]))
    skv = k2.shape[1]
    blk = min(blk_kv, skv)
    nk = skv // blk
    kvh = k.shape[2]
    groups = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(b, sq, kvh, groups, hd).astype(jnp.float32) * scale
    qf = qf.transpose(0, 2, 3, 1, 4)
    qpos = q_offset + jnp.arange(sq)

    def body(carry, ki):
        m, l = carry
        ks = lax.dynamic_slice_in_dim(k2, ki * blk, blk, axis=1)
        logits = jnp.einsum("bkgqd,bskd->bkgqs", qf,
                            ks.astype(jnp.float32))
        mask = _blk_mask(sq, blk, ki, qpos, skv_valid, causal, window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        l_new = jnp.exp(m - m_new) * l + jnp.sum(
            jnp.where(mask[None, None, None],
                      jnp.exp(logits - m_new[..., None]), 0.0), axis=-1)
        return (m_new, l_new), None

    m0 = jnp.full((b, kvh, groups, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, groups, sq), jnp.float32)
    (m, l), _ = lax.scan(body, (m0, l0), jnp.arange(nk))
    return m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_bwd_blockwise(q, k, v, out, lse, dout, causal, window, q_offset,
                         blk_kv):
    """Flash backward: recompute p per kv block from (q, k, lse); memory
    stays O(block), matching the fwd."""
    b, sq, h, hd = q.shape
    k, v, skv_valid = _pad_kv(k, v, min(blk_kv, k.shape[1]))
    skv = k.shape[1]
    blk = min(blk_kv, skv)
    nk = skv // blk
    kvh = k.shape[2]
    groups = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(b, sq, kvh, groups, hd).astype(jnp.float32)
    qf = qf.transpose(0, 2, 3, 1, 4)                 # [b,kvh,g,sq,hd]
    do = dout.reshape(b, sq, kvh, groups, hd).astype(jnp.float32)
    do = do.transpose(0, 2, 3, 1, 4)
    of = out.reshape(b, sq, kvh, groups, hd).astype(jnp.float32)
    of = of.transpose(0, 2, 3, 1, 4)
    dsum = jnp.sum(do * of, axis=-1)                 # [b,kvh,g,sq]
    qpos = q_offset + jnp.arange(sq)

    def body(dq_acc, ki):
        ks = lax.dynamic_slice_in_dim(k, ki * blk, blk, axis=1) \
            .astype(jnp.float32)
        vs = lax.dynamic_slice_in_dim(v, ki * blk, blk, axis=1) \
            .astype(jnp.float32)
        logits = jnp.einsum("bkgqd,bskd->bkgqs", qf * scale, ks)
        mask = _blk_mask(sq, blk, ki, qpos, skv_valid, causal, window)
        p = jnp.where(mask[None, None, None],
                      jnp.exp(logits - lse[..., None]), 0.0)
        dv_blk = jnp.einsum("bkgqs,bkgqd->bskd", p, do)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", do, vs)
        ds = p * (dp - dsum[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bkgqs,bskd->bkgqd", ds, ks)
        dk_blk = jnp.einsum("bkgqs,bkgqd->bskd", ds, qf)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, kvh, groups, sq, hd), jnp.float32)
    dq, (dk_blks, dv_blks) = lax.scan(body, dq0, jnp.arange(nk))
    dq = dq.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)
    dk = dk_blks.transpose(1, 0, 2, 3, 4).reshape(b, skv, kvh, hd)
    dv = dv_blks.transpose(1, 0, 2, 3, 4).reshape(b, skv, kvh, hd)
    dk = dk[:, :skv_valid].astype(k.dtype)
    dv = dv[:, :skv_valid].astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Streamed flash steps (ring attention) — carry in/out, traced offsets
# ---------------------------------------------------------------------------


def _step_mask(sq, blk, ki, q_offset, k_offset, skv_valid, causal, window):
    """Mask for one kv sub-block when BOTH q and k sit at global offsets
    (which may be traced scalars — ring ranks derive them from
    lax.axis_index).  ``skv_valid`` masks the zero-padding of ragged kv."""
    qpos = q_offset + jnp.arange(sq)
    kloc = ki * blk + jnp.arange(blk)
    kpos = k_offset + kloc
    mask = jnp.broadcast_to((kloc < skv_valid)[None, :], (sq, blk))
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= qpos[:, None] - kpos[None, :] < window
    return mask


def _to_grouped(x, kvh, groups):
    """[B, Sq, H(, hd)] -> [b, kvh, g, sq(, hd)] (internal GQA layout)."""
    b, sq = x.shape[:2]
    if x.ndim == 3:
        return x.reshape(b, sq, kvh, groups).transpose(0, 2, 3, 1)
    hd = x.shape[-1]
    return x.reshape(b, sq, kvh, groups, hd).transpose(0, 2, 3, 1, 4)


def _from_grouped(x):
    """[b, kvh, g, sq(, hd)] -> [B, Sq, H(, hd)]."""
    b, kvh, g, sq = x.shape[:4]
    if x.ndim == 4:
        return x.transpose(0, 3, 1, 2).reshape(b, sq, kvh * g)
    return x.transpose(0, 3, 1, 2, 4).reshape(b, sq, kvh * g, x.shape[-1])


def _flash_step_jnp(q, k, v, m, l, acc, causal, window, q_offset, k_offset,
                    blk_kv):
    """Pure-jnp carry step (lax.scan over kv sub-blocks) — the attend_ref-
    family engine behind flash_attention_step where Pallas can't lower."""
    b, sq, h, hd = q.shape
    k, v, skv_valid = _pad_kv(k, v, min(blk_kv, k.shape[1]))
    skv = k.shape[1]
    blk = min(blk_kv, skv)
    nk = skv // blk
    kvh = k.shape[2]
    groups = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qf = _to_grouped(q.astype(jnp.float32) * scale, kvh, groups)

    mi = _to_grouped(m, kvh, groups)
    li = _to_grouped(l, kvh, groups)
    ai = _to_grouped(acc, kvh, groups)

    def body(carry, ki):
        mc, lc, ac = carry
        ks = lax.dynamic_slice_in_dim(k, ki * blk, blk, axis=1)
        vs = lax.dynamic_slice_in_dim(v, ki * blk, blk, axis=1)
        logits = jnp.einsum("bkgqd,bskd->bkgqs", qf,
                            ks.astype(jnp.float32))
        mask = _step_mask(sq, blk, ki, q_offset, k_offset, skv_valid,
                          causal, window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(mc, m_cur)
        alpha = jnp.exp(mc - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l_new = alpha * lc + jnp.sum(p, axis=-1)
        a_new = ac * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vs.astype(jnp.float32))
        return (m_new, l_new, a_new), None

    (mi, li, ai), _ = lax.scan(body, (mi, li, ai), jnp.arange(nk))
    return _from_grouped(mi), _from_grouped(li), _from_grouped(ai)


def flash_attention_step(q: Array, k: Array, v: Array,
                         carry: tuple[Array, Array, Array] | None = None, *,
                         causal: bool = True, window: int = 0,
                         q_offset=0, k_offset=0, blk_q: int = 128,
                         blk_kv: int = 128) -> tuple[Array, Array, Array]:
    """Fold one KV block into an online-softmax carry (m, l, acc — the
    public [B, Sq, H(, hd)] layout of kernels/flash_attention.py).

    This is the per-arrival work item of ring attention: each ring step
    calls it on the KV block that just landed while the next block is in
    flight.  ``q_offset``/``k_offset`` may be traced int32 scalars.
    Dispatch mirrors ``flash_attention``: Pallas carry kernel on TPU (or
    REPRO_PALLAS=interpret), jnp blockwise scan elsewhere."""
    b, sq, h, hd = q.shape
    if carry is None:
        carry = init_partials(b, sq, h, hd)
    m, l, acc = carry
    mode = _pallas_mode()
    if _kernel_tiles(mode, "flash_attention_step", sq, k.shape[1], blk_q,
                     blk_kv):
        return flash_attention_carry_pallas(
            q, k, v, m, l, acc, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset, blk_q=blk_q,
            blk_kv=blk_kv, interpret=(mode == "interpret"))
    return _flash_step_jnp(q, k, v, m, l, acc, causal, window, q_offset,
                           k_offset, max(blk_kv, 512))


def flash_attention_bwd_block(q: Array, k: Array, v: Array, dout: Array,
                              lse: Array, dsum: Array, *, causal: bool,
                              window: int = 0, q_offset=0, k_offset=0,
                              blk_kv: int = 512
                              ) -> tuple[Array, Array, Array]:
    """Backward of one streamed flash step, recomputing p from (q, k, lse).

    q, dout: [B, Sq, H, hd]; k, v: [B, Skv, KV, hd];
    lse, dsum: [B, Sq, H] (dsum = sum(dout * out, -1), computed once by the
    caller — it is block-independent).  Returns f32 (dq_contrib, dk, dv) so
    ring ranks can accumulate across steps without dtype round-trips.
    Offsets may be traced; memory stays O(Sq * blk) via the inner scan."""
    b, sq, h, hd = q.shape
    k, v, skv_valid = _pad_kv(k, v, min(blk_kv, k.shape[1]))
    skv = k.shape[1]
    blk = min(blk_kv, skv)
    nk = skv // blk
    kvh = k.shape[2]
    groups = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qf = _to_grouped(q.astype(jnp.float32), kvh, groups)
    do = _to_grouped(dout.astype(jnp.float32), kvh, groups)
    lse_g = _to_grouped(lse, kvh, groups)
    dsum_g = _to_grouped(dsum, kvh, groups)

    def body(dq_acc, ki):
        ks = lax.dynamic_slice_in_dim(k, ki * blk, blk, axis=1) \
            .astype(jnp.float32)
        vs = lax.dynamic_slice_in_dim(v, ki * blk, blk, axis=1) \
            .astype(jnp.float32)
        logits = jnp.einsum("bkgqd,bskd->bkgqs", qf * scale, ks)
        mask = _step_mask(sq, blk, ki, q_offset, k_offset, skv_valid,
                          causal, window)
        p = jnp.where(mask[None, None, None],
                      jnp.exp(logits - lse_g[..., None]), 0.0)
        dv_blk = jnp.einsum("bkgqs,bkgqd->bskd", p, do)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", do, vs)
        ds = p * (dp - dsum_g[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bkgqs,bskd->bkgqd", ds, ks)
        dk_blk = jnp.einsum("bkgqs,bkgqd->bskd", ds, qf)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, kvh, groups, sq, hd), jnp.float32)
    dq, (dk_blks, dv_blks) = lax.scan(body, dq0, jnp.arange(nk))
    dq = _from_grouped(dq)
    dk = dk_blks.transpose(1, 0, 2, 3, 4).reshape(b, skv, kvh, hd)
    dv = dv_blks.transpose(1, 0, 2, 3, 4).reshape(b, skv, kvh, hd)
    return dq, dk[:, :skv_valid], dv[:, :skv_valid]


def flash_attention_blockwise(q: Array, k: Array, v: Array, *,
                              causal: bool = True, window: int = 0,
                              q_offset: int = 0, blk_kv: int = 512) -> Array:
    """Online-softmax flash in pure jnp (lax.scan over kv blocks).  Same
    math as the Pallas kernel; used where Pallas can't lower (CPU dry-run)
    and as the kernel's second oracle for long shapes."""
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    blk = min(blk_kv, skv)
    if skv % blk:
        # ragged kv (e.g. whisper's 1500 frames): pad and mask
        pad = blk - skv % blk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    skv_valid = skv
    skv = k.shape[1]
    nk = skv // blk
    groups = h // kvh
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(b, sq, kvh, groups, hd).astype(jnp.float32) * scale
    qpos = q_offset + jnp.arange(sq)

    def body(carry, ki):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(k, ki * blk, blk, axis=1) \
            .astype(jnp.float32)
        vs = lax.dynamic_slice_in_dim(v, ki * blk, blk, axis=1) \
            .astype(jnp.float32)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, ks)
        kpos = ki * blk + jnp.arange(blk)
        mask = jnp.broadcast_to((kpos < skv_valid)[None, :], (sq, blk))
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window > 0:
            mask &= qpos[:, None] - kpos[None, :] < window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd",
                                                      p, vs)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, groups, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, groups, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, groups, sq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)
