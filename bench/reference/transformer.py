"""Plain float32 reference of the dense GQA decoder the phi4-mini
configurations describe, independent of the program under test.

It follows the published block: RMSNorm, attention with grouped kv heads
and rotary positions, residual, RMSNorm, a SiLU-gated MLP, residual; a
final RMSNorm and the output head tied to the embedding.  Departures from
the published model are the program's and are listed in its
configuration file: full-width rotary positions with plain theta (no
partial rotary factor, no LongRoPE scaling), and query heads padded with
zero heads to the program's multiple.  Weights come from
``bench/reference/weights.py`` in that layout; each matrix product runs at
``Precision.HIGHEST``, so float32 means float32 on the TPU too.

``quant`` selects the control: every matrix product of the layers and the
head, forward and backward, is computed from operands rounded to int8
(per row or column along the contraction) or to fp8 e4m3 (per tensor),
the precision below bfloat16 that a later change might be tempted to
serve or train in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32


def plain(x):
    """``x`` replicated over the mesh it lives on, if any, so that the
    reference's plain indexing needs no sharding of its own."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    s = jax.typeof(x).sharding
    if getattr(s, "mesh", None) is None or s.mesh.empty:
        return x
    return jax.sharding.reshard(x, NamedSharding(s.mesh, P()))


def rounded(x, quant: str | None, axis: int | None = None):
    """``x`` rounded to the control's precision and scaled back: int8 with
    one scale per slice along ``axis`` (per tensor when None), or fp8
    e4m3 with one scale per tensor."""
    if quant is None:
        return x
    if quant == "int8":
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.max(jnp.abs(x)) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def mm(a, b, quant: str | None = None):
    """a [..., K] @ b [K, N] in float32; for a control, from operands
    rounded to its precision, forward and backward."""
    if quant is None:
        return jnp.matmul(a, b, precision=HIGHEST)
    return _qmm(a, b, quant)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qmm(a, b, quant):
    return jnp.matmul(rounded(a, quant, -1), rounded(b, quant, 0),
                      precision=HIGHEST)


def _qmm_fwd(a, b, quant):
    return _qmm(a, b, quant), (a, b)


def _qmm_bwd(quant, res, g):
    a, b = res
    ga = jnp.matmul(rounded(g, quant, -1), rounded(b, quant, 1).T,
                    precision=HIGHEST)
    a2, g2 = a.reshape(-1, a.shape[-1]), g.reshape(-1, g.shape[-1])
    gb = jnp.matmul(rounded(a2, quant, 0).T, rounded(g2, quant, 0),
                    precision=HIGHEST)
    return ga, gb


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def rms_norm(x, offset, eps: float):
    """RMSNorm with scale 1 + offset (the stored layout of the weights)."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + offset)


def rope(x, pos, theta: float):
    """Rotary positions on [B, T, H, hd]: the first half of each head is
    rotated against the second half by angle pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, p, c: dict, quant: str | None = None):
    """One decoder block on x [B, T, D] (float32)."""
    b, t, d = x.shape
    hp, k = c["padded_heads"], c["num_key_value_heads"]
    hd = d // c["num_attention_heads"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(t)
    h = rms_norm(x, p["ln1"], eps)
    q = mm(h, p["w_q"], quant).reshape(b, t, hp, hd)
    kk, v = jnp.split(mm(h, p["w_kv"], quant), 2, axis=-1)
    kk, v = kk.reshape(b, t, k, hd), v.reshape(b, t, k, hd)
    q, kk = rope(q, pos, theta), rope(kk, pos, theta)
    qg = q.reshape(b, t, k, hp // k, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, kk,
                   precision=HIGHEST) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", a, v, precision=HIGHEST)
    x = x + mm(o.reshape(b, t, hp * hd), p["w_o"], quant)
    h = rms_norm(x, p["ln2"], eps)
    y = jax.nn.silu(mm(h, p["w_up"], quant)) * mm(h, p["w_gate"], quant)
    return x + mm(y, p["w_down"], quant)


def hidden(w, tokens, c: dict, quant: str | None = None,
           remat: bool = False):
    """Final hidden states [B, T, D] of ``tokens`` [B, T], layer by layer
    (each layer's weights cast to float32 as it is reached)."""
    w = jax.tree.map(plain, w)
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    def body(x, p):
        p = jax.tree.map(lambda a: a.astype(F32), p)
        return layer(x, p, c, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, w["layers"])
    return rms_norm(x, w["final_ln"].astype(F32), c["rms_norm_eps"])


def head(w, h, quant: str | None = None):
    """Logits of hidden states h [..., D] over the (tied) vocabulary."""
    return mm(h, w["embed"].astype(F32).T, quant)


# ---------------------------------------------------------------------------
# Serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cj", "quant"))
def served_gaps(w, tokens, at, served, cj: str, quant: str | None = None):
    """tokens [1, T]: a prompt and its served tokens, padded; at [N]: the
    positions whose next token was served; served [N]: those tokens.

    Returns (gap [N], control gap [N]): how far the reference's logit of
    each served token lies below its best logit, and, when ``quant`` is
    given, the same for the token the lower precision puts first."""
    import json
    c = json.loads(cj)
    w = jax.tree.map(plain, w)
    h = hidden(w, tokens, c)[0]
    hs = jnp.take(h, at, axis=0)
    lg = head(w, hs)
    best = jnp.max(lg, axis=-1)
    gap = best - jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
    if quant is None:
        return gap, jnp.zeros_like(gap)
    hq = jnp.take(hidden(w, tokens, c, quant)[0], at, axis=0)
    pick = jnp.argmax(head(w, hq, quant), axis=-1)
    ctl = best - jnp.take_along_axis(lg, pick[:, None], axis=1)[:, 0]
    return gap, ctl


# ---------------------------------------------------------------------------
# Training: loss, gradients and AdamW
# ---------------------------------------------------------------------------


def loss(w, tokens, labels, c: dict, quant: str | None = None):
    """Mean next-token cross-entropy over the labelled positions (a label
    below 0 marks a position left out)."""
    h = hidden(w, tokens, c, quant, remat=True)
    lg = head(w, h, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    valid = labels >= 0
    tgt = jnp.take_along_axis(lg, jnp.maximum(labels, 0)[..., None],
                              axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - tgt, 0.0)) / jnp.sum(valid)


def lr_at(step: int, o: dict) -> float:
    """Linear warm-up, then a cosine from lr down to a tenth of it."""
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    frac = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    return o["lr"] * warm * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi
                                                               * frac)))


def global_norm(tree) -> float:
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree.leaves(tree))))


def clip_scale(gnorm: float, o: dict) -> float:
    return min(1.0, o["clip_norm"] / max(gnorm, 1e-12))
