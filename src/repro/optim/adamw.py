"""Sharded AdamW + gradient clipping + LR schedule.

Optimizer states inherit the parameter sharding exactly (every update is
elementwise), so FSDP/ZeRO-3 state sharding falls out of the param layout.
``moment_dtype`` comes from the arch config (bf16 moments for the 100B+
archs — DESIGN.md §3.1 memory posture).  The global-norm clip is the only
cross-shard operation; its scalar crosses the mesh through MDMP managed
reductions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import managed

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    moment_dtype: str = "float32"


def adamw_init(params: Any, cfg: AdamWConfig) -> dict:
    dt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros_like(p, dtype=dt)   # keeps p's sharding
    return {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def cosine_schedule(step: Array, cfg: AdamWConfig) -> Array:
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return cfg.lr * warm * (0.1 + 0.9 * cos)


def global_norm(grads: Any, axes: Sequence[str] = ()) -> Array:
    """Global L2 norm of a (sharded) grad tree; partial sums-of-squares are
    psum'd across ``axes`` so every shard agrees on the clip factor."""
    ssq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(grads))
    for ax in axes:
        ssq = managed.managed_all_reduce(ssq, ax)
    return jnp.sqrt(ssq)


def adamw_update(params: Any, grads: Any, state: dict, cfg: AdamWConfig,
                 *, psum_axes: Sequence[str] = (),
                 gnorm: Array | None = None) -> tuple[Any, dict, dict]:
    """One AdamW step.  ``gnorm`` may be precomputed (the train step builds
    a replication-aware norm).  Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    lr = cosine_schedule(step, cfg)
    if gnorm is None:
        gnorm = global_norm(grads, psum_axes)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12)) \
        if cfg.clip_norm > 0 else jnp.float32(1.0)

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    mdt = jnp.dtype(cfg.moment_dtype)

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu32 = mu.astype(jnp.float32) * b1 + (1 - b1) * g
        nu32 = nu.astype(jnp.float32) * b2 + (1 - b2) * g * g
        mhat = mu32 / bc1
        nhat = nu32 / bc2
        delta = mhat / (jnp.sqrt(nhat) + cfg.eps)
        p32 = p.astype(jnp.float32)
        p_new = p32 - lr * (delta + cfg.weight_decay * p32)
        return p_new.astype(p.dtype), mu32.astype(mdt), nu32.astype(mdt)

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    out = [upd(p, g, m, n) for p, g, m, n in
           zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_p = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_mu = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_nu = jax.tree.unflatten(tdef, [o[2] for o in out])
    new_state = {"mu": new_mu, "nu": new_nu, "step": step}
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}
