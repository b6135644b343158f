"""Model factory: ModelConfig -> parameter specs, init, and the three
entry points (all called INSIDE shard_map over the production mesh):

  * ``loss_sp(params, batch)``           training loss (SP flow)
  * ``prefill_sp(params, batch)``        prefill -> (last-token logits, cache)
  * ``decode_step(params, cache, ...)``  one-token decode (TP-2D flow)

Parameters are stored in ONE layout shared by train and serve
(DESIGN.md §3.1); decode contracts FSDP dims in place instead of gathering.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import managed
from repro.models import attention, layers, moe, ssm, transformer
from repro.parallel.sharding import (LOGICAL_RULES, MeshCtx, ParamSpec,
                                     pad_to_multiple)

Array = jax.Array
PS = ParamSpec


#: SSM leaves that start from a constant instead of zero (A in [1, e],
#: dt_bias ~ softplus-inv, unit skip)
_CONST_INIT = {"a_log": 0.5, "dt_bias": 0.1, "d_skip": 1.0}


@lru_cache(maxsize=256)
def _leaf_init(shape: tuple[int, ...], logical: tuple[str, ...], dtype,
               const: float, sharding):
    """Jitted initializer of one parameter leaf, placed by ``sharding``;
    cached so models of one shape compile their leaves once."""
    def one(k):
        if len([l for l in logical if l != "layers"]) <= 1:
            # norm scales / per-head scalars: constants
            return jnp.full(shape, const, dtype)
        scale = 1.0 / math.sqrt(max(shape[-2], 1))        # fan-in
        return (jax.random.normal(k, shape, jnp.float32)
                * scale).astype(dtype)
    return jax.jit(one, out_shardings=sharding)


def _gated_mult(cfg: ModelConfig) -> int:
    return 2 if layers.gated(cfg.mlp) else 1


class Model:
    def __init__(self, cfg: ModelConfig, ctx: MeshCtx):
        self.cfg = cfg
        self.ctx = ctx
        assert cfg.padded_heads % max(ctx.tp, 1) == 0 or cfg.n_heads == 0, \
            (cfg.name, cfg.padded_heads, ctx.tp)

    # ------------------------------------------------------------------
    # Parameter specs
    # ------------------------------------------------------------------

    def _attn_specs(self, cross: bool = False) -> dict:
        cfg = self.cfg
        hd = cfg.head_dim
        hp = cfg.padded_heads
        kvp = attention.padded_kv_heads(cfg)
        sfx = "_x" if cross else ""
        d = cfg.d_model
        specs = {
            f"w_q{sfx}": PS((d, hp * hd), ("embed", "heads")),
            f"w_kv{sfx}": PS((d, 2 * kvp * hd), ("embed", "null")),
            f"w_o{sfx}": PS((hp * hd, d), ("heads", "embed")),
        }
        return specs

    def _mlp_specs(self) -> dict:
        cfg = self.cfg
        d, ff = cfg.d_model, cfg.padded_ff
        specs = {
            "w_up": PS((d, ff), ("embed", "ff")),
            "w_down": PS((ff, d), ("ff", "embed")),
        }
        if _gated_mult(cfg) == 2:
            # separate gate matrix: a fused [up|gate] would split on the
            # LOCAL shard and misalign with the global column order
            # (breaks elastic resume across mesh shapes)
            specs["w_gate"] = PS((d, ff), ("embed", "ff"))
        return specs

    def _moe_specs(self) -> dict:
        cfg = self.cfg
        e = cfg.moe
        d, f = cfg.d_model, e.d_ff_expert
        ep = (e.impl == "ep_a2a" or
              (e.impl == "auto" and e.n_experts % self.ctx.tp == 0))
        e_ax = "experts" if ep else "null"
        f_ax = "expert_ff" if ep else "ff"
        specs = {
            "w_router": PS((d, e.n_experts), ("embed_nofsdp", "null")),
            "w1": PS((e.n_experts, d, f), (e_ax, "embed", f_ax)),
            "w2": PS((e.n_experts, f, d), (e_ax, f_ax, "embed")),
        }
        if _gated_mult(cfg) == 2:
            specs["w1_gate"] = PS((e.n_experts, d, f),
                                  (e_ax, "embed", f_ax))
        return specs

    def _ssm_specs(self) -> dict:
        cfg = self.cfg
        s = cfg.ssm
        d = cfg.d_model
        h = cfg.ssm_heads
        di = h * s.headdim
        n = s.d_state
        return {
            "w_z": PS((d, di), ("embed", "inner")),
            "w_x": PS((d, di), ("embed", "inner")),
            "w_bc": PS((d, 2 * n), ("embed", "null")),
            "w_dt": PS((d, h), ("embed", "ssm_heads")),
            "conv_x": PS((s.d_conv, di), ("conv", "inner")),
            "conv_bc": PS((s.d_conv, 2 * n), ("conv", "null")),
            "a_log": PS((h,), ("ssm_heads",)),
            "dt_bias": PS((h,), ("ssm_heads",)),
            "d_skip": PS((h,), ("ssm_heads",)),
            "norm_w": PS((di,), ("inner",)),
            "w_out": PS((di, d), ("inner", "embed")),
        }

    def _layer_specs(self) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        ln = lambda: PS((d,), ("embed_nofsdp",))
        if cfg.family == "ssm":
            return {"ln1": ln(), **self._ssm_specs()}
        specs = {"ln1": ln(), "ln2": ln(), **self._attn_specs()}
        if cfg.family == "moe":
            specs.update(self._moe_specs())
        else:
            specs.update(self._mlp_specs())
        if cfg.family == "hybrid":
            specs["ssm"] = self._ssm_specs()
        if cfg.encoder is not None:
            specs["ln_x"] = ln()
            specs.update(self._attn_specs(cross=True))
        return specs

    def param_specs(self) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        v = cfg.padded_vocab
        specs: dict[str, Any] = {
            "embed": PS((v, d), ("vocab", "embed")),
            "final_ln": PS((d,), ("embed_nofsdp",)),
        }
        if not cfg.tie_embeddings:
            specs["unembed"] = PS((d, v), ("embed", "vocab"))
        layer = self._layer_specs()
        if self.scan_layers:
            specs["layers"] = jax.tree.map(
                lambda s: PS((cfg.n_layers,) + s.shape,
                             ("layers",) + s.logical),
                layer, is_leaf=lambda x: isinstance(x, PS))
        else:
            specs["layers"] = [jax.tree.map(lambda s: s, layer,
                                            is_leaf=lambda x: isinstance(x, PS))
                               for _ in range(cfg.n_layers)]
        if cfg.encoder is not None:
            enc_layer = {"ln1": PS((d,), ("embed_nofsdp",)),
                         "ln2": PS((d,), ("embed_nofsdp",)),
                         **self._attn_specs(), **self._mlp_specs()}
            specs["encoder"] = {
                "layers": jax.tree.map(
                    lambda s: PS((cfg.encoder.n_layers,) + s.shape,
                                 ("layers",) + s.logical),
                    enc_layer, is_leaf=lambda x: isinstance(x, PS)),
                "final_ln": PS((d,), ("embed_nofsdp",)),
            }
        if cfg.vision is not None:
            specs["vision_adapter"] = PS((d, d), ("embed_nofsdp", "null"))
        return specs

    @property
    def scan_layers(self) -> bool:
        return self.cfg.family != "hybrid"

    # ------------------------------------------------------------------
    # Init: seeded random weights, built straight into their shardings
    # ------------------------------------------------------------------

    def init(self, key: Array, shardings: Any = None) -> dict:
        """Seeded random parameters.  Each leaf is made by its own jitted
        program whose output lands in its sharding (``shardings``: a tree
        of ``jax.sharding.Sharding`` matching ``param_specs()``; default
        placement when None), so no full copy of the weights is ever held
        on one device or on the host, and the float32 draw of a leaf is a
        temporary of that leaf's program alone."""
        cfg = self.cfg
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_specs(), is_leaf=lambda x: isinstance(x, PS))
        keys = jax.random.split(key, len(flat))
        shards = (treedef.flatten_up_to(shardings) if shardings is not None
                  else [None] * len(flat))
        dtype = jnp.dtype(cfg.dtype)
        out = []
        for k, (path, spec), sh in zip(keys, flat, shards):
            const = _CONST_INIT.get(getattr(path[-1], "key", None), 0.0)
            out.append(_leaf_init(spec.shape, spec.logical, dtype, const,
                                  sh)(k))
        return jax.tree.unflatten(treedef, out)

    # ------------------------------------------------------------------
    # Forward (SP flow)
    # ------------------------------------------------------------------

    def _assemble_input_sp(self, params: dict, batch: dict) -> Array:
        """Embed tokens (and splice modality-stub embeddings)."""
        cfg, ctx = self.cfg, self.ctx
        x = layers.embed_sp(batch["tokens"], params["embed"], cfg, ctx)
        if cfg.vision is not None and "patches" in batch:
            # splice projected patch embeddings into positions [0, P)
            patches = batch["patches"]                    # [B, P, D]
            b, s_loc, d = x.shape
            s = batch["tokens"].shape[1]
            pad = jnp.zeros((b, s - patches.shape[1], d), x.dtype)
            patch_full = jnp.concatenate(
                [jnp.dot(patches, params["vision_adapter"]).astype(x.dtype),
                 pad], axis=1)
            r = lax.axis_index("model")
            mine = lax.dynamic_slice_in_dim(patch_full, r * s_loc, s_loc,
                                            axis=1)
            pos = r * s_loc + jnp.arange(s_loc)
            is_patch = (pos < patches.shape[1])[None, :, None]
            x = jnp.where(is_patch, mine, x)
        return x

    def _encoder_sp(self, params: dict, frames: Array) -> Array:
        """Whisper encoder on stub frame embeddings [B, F, D] ->
        enc_out [B, F_loc, D]."""
        cfg, ctx = self.cfg, self.ctx
        b, f, d = frames.shape
        pos = jnp.arange(f)
        x = frames + _sinusoidal(pos, d)[None].astype(frames.dtype)
        # pad frames to a TP multiple, then shard over 'model' (SP)
        f_pad = pad_to_multiple(f, ctx.tp)
        if f_pad != f:
            x = jnp.pad(x, ((0, 0), (0, f_pad - f), (0, 0)))
        r = lax.axis_index("model")
        f_loc = f_pad // ctx.tp
        x = lax.dynamic_slice_in_dim(x, r * f_loc, f_loc, axis=1)
        x, _, _, _ = transformer.stack_sp(
            x, params["encoder"]["layers"], cfg, ctx, causal=False)
        return layers.rms_norm(x, params["encoder"]["final_ln"],
                               cfg.norm_eps)

    def loss_sp(self, params: dict, batch: dict) -> tuple[Array, dict]:
        """Training loss.  batch: tokens [B_loc, S], labels [B_loc, S]
        (+ frames/patches stubs).  Returns (loss, metrics)."""
        cfg, ctx = self.cfg, self.ctx
        x = self._assemble_input_sp(params, batch)
        enc_out = None
        if cfg.encoder is not None:
            enc_out = self._encoder_sp(params, batch["frames"])
        x, aux, _, _ = transformer.stack_sp(
            x, params["layers"], cfg, ctx, causal=True, enc_out=enc_out)
        x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
        unembed = self._unembed(params)
        loss_sum, count = layers.lm_loss_sp(x, unembed, batch["labels"],
                                            cfg, ctx)
        axes = ctx.all_axes
        total = loss_sum
        cnt = count
        for ax in axes:
            total = managed.managed_all_reduce(total, ax)
            cnt = managed.managed_all_reduce(cnt, ax)
        loss = total / jnp.maximum(cnt, 1.0)
        if cfg.moe is not None:
            # aux is a local-token mean: average it across ranks (expert_tp
            # computes it on replicated tokens — the pmean is then a no-op
            # on the model axis; ep_a2a tokens are fully sharded).
            n_dev = 1
            for ax in axes:
                aux = managed.managed_all_reduce(aux, ax)
                n_dev *= ctx.axis_sizes[ax]
            loss = loss + 0.01 * (aux / n_dev) / cfg.n_layers
        return loss, {"loss": loss, "tokens": cnt}

    def _unembed(self, params: dict) -> Array:
        if self.cfg.tie_embeddings:
            # embed: [V_loc(model), D_loc(data)] -> unembed [D_loc, V_loc]
            return params["embed"].T
        return params["unembed"]

    # ------------------------------------------------------------------
    # Prefill (SP flow, collects cache in prefill layout)
    # ------------------------------------------------------------------

    def prefill_sp(self, params: dict, batch: dict) -> tuple[Array, Any]:
        """Prefill: returns (logits of the LAST position [B, V_loc(model)],
        cache in prefill layout).  Dry-run cells lower this as-is."""
        cfg, ctx = self.cfg, self.ctx
        x = self._assemble_input_sp(params, batch)
        enc_out = None
        if cfg.encoder is not None:
            enc_out = self._encoder_sp(params, batch["frames"])
        x, _, kvs, states = transformer.stack_sp(
            x, params["layers"], cfg, ctx, causal=True, collect_kv=True,
            enc_out=enc_out, remat=False)
        x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
        # The final global position lives on the LAST model rank's shard:
        # masked psum broadcasts its hidden state to every rank.
        last_loc = x[:, -1, :].astype(jnp.float32)          # [B_loc, D]
        is_last = (lax.axis_index("model") == ctx.tp - 1).astype(jnp.float32)
        last = managed.managed_all_reduce(last_loc * is_last, "model")
        w = self._unembed(params)
        from repro.core.overlap import fsdp_gather
        wg = fsdp_gather(w, "data", axis=0, mode=ctx.mdmp_mode)
        logits = jnp.dot(last, wg.astype(jnp.float32))      # [B, V_loc(mdl)]
        cache = {"kv": kvs, "ssm": states, "enc_out": enc_out}
        return logits, cache

    # ------------------------------------------------------------------
    # Decode (TP-2D flow)
    # ------------------------------------------------------------------

    def decode_step(self, params: dict, cache: Any, token: Array,
                    pos: Array) -> tuple[Array, Any]:
        """One greedy decode step.  token: [B] int32 (replicated);
        pos: [] int32.  Returns (next_token [B], new cache)."""
        cfg, ctx = self.cfg, self.ctx
        # embed_decode contracts vocab over 'model' and returns the
        # decode-layout [B, D_loc(data)] residual directly.
        x = layers.embed_decode(token, params["embed"], cfg, ctx)
        d_loc = cfg.d_model // ctx.dp
        r_d = lax.axis_index("data")

        stacked = params["layers"]
        x, new_cache = transformer.stack_decode(x, stacked, cache, pos,
                                                cfg, ctx)
        ln = lax.dynamic_slice_in_dim(params["final_ln"], r_d * d_loc,
                                      d_loc, axis=0)
        x = layers.rms_norm_sharded(x, ln, cfg.norm_eps, "data")
        if cfg.tie_embeddings:
            # embed [V_loc(model), D_loc(data)]: logits = x @ embed.T
            logits = managed.managed_all_reduce(
                jnp.dot(x, params["embed"].T), "data", mode=ctx.mdmp_mode)
        else:
            logits = layers.logits_decode(x, params["unembed"], ctx)
        nxt = layers.greedy_sample(logits, ctx)
        return nxt, new_cache

    def decode_step_paged(self, params: dict, cache: Any, table: Array,
                          token: Array, pos: Array, active: Array
                          ) -> tuple[Array, Any]:
        """One greedy decode step against the PAGED cache (the serving
        runtime's flow).  token: [B] int32 (replicated); table: [B, Pmax]
        int32 global page ids; pos: [B] int32 per-slot positions; active:
        [B] bool.  Returns (next_token [B], new cache) — outputs of
        inactive slots are garbage the engine discards, and their cache
        state does not advance."""
        cfg, ctx = self.cfg, self.ctx
        x = layers.embed_decode(token, params["embed"], cfg, ctx)
        d_loc = cfg.d_model // ctx.dp
        r_d = lax.axis_index("data")

        x, new_cache = transformer.stack_decode_paged(
            x, params["layers"], cache, table, pos, active, cfg, ctx)
        ln = lax.dynamic_slice_in_dim(params["final_ln"], r_d * d_loc,
                                      d_loc, axis=0)
        x = layers.rms_norm_sharded(x, ln, cfg.norm_eps, "data")
        if cfg.tie_embeddings:
            logits = managed.managed_all_reduce(
                jnp.dot(x, params["embed"].T), "data", mode=ctx.mdmp_mode)
        else:
            logits = layers.logits_decode(x, params["unembed"], ctx)
        nxt = layers.greedy_sample(logits, ctx)
        return nxt, new_cache

    # ------------------------------------------------------------------
    # Decode-cache construction (decode layout; used by serve + dry-run)
    # ------------------------------------------------------------------

    def decode_cache_specs(self, shape: ShapeConfig) -> tuple[Any, Any]:
        """Returns (ShapeDtypeStruct pytree, PartitionSpec pytree) for the
        decode-layout cache of this (arch, shape) cell."""
        cfg, ctx = self.cfg, self.ctx
        b = shape.global_batch                   # replicated in decode flow
        n_sh = attention.cache_shards(ctx)
        sax = (("pod", "data", "model") if ctx.has_pod else
               ("data", "model"))
        dt = jnp.dtype(cfg.dtype)
        kvp = attention.padded_kv_heads(cfg) if cfg.n_heads else 0
        hd = cfg.head_dim if cfg.n_heads else 0

        def kv_entry(s_total):
            s_pad = pad_to_multiple(s_total, n_sh)
            shp = (b, s_pad, kvp, hd)
            spec = P(None, sax, None, None)
            return (jax.ShapeDtypeStruct(shp, dt), spec)

        def ssm_entry():
            s = cfg.ssm
            h_loc_total = cfg.ssm_heads          # global; sharded by model
            di = cfg.ssm_heads * s.headdim
            hshp = (b, h_loc_total, s.headdim, s.d_state)
            hspec = P(None, "model", None, None)
            cshp = (b, s.d_conv - 1, di + 2 * s.d_state)
            # conv channels: x-part sharded over model, bc replicated —
            # stored separately to shard cleanly
            cx = (jax.ShapeDtypeStruct((b, s.d_conv - 1, di), dt),
                  P(None, None, "model"))
            cbc = (jax.ShapeDtypeStruct((b, s.d_conv - 1, 2 * s.d_state),
                                        dt), P(None, None, None))
            return ((jax.ShapeDtypeStruct(hshp, jnp.float32), hspec),
                    cx, cbc)

        def layer_entry(i):
            entry = {}
            if cfg.family != "ssm" and cfg.n_heads:
                w = transformer.layer_window(cfg, i)
                s_total = min(shape.seq_len, w) if w else shape.seq_len
                s_total = max(s_total, n_sh)
                entry["k"] = kv_entry(s_total)
                entry["v"] = kv_entry(s_total)
            if cfg.family in ("ssm", "hybrid"):
                h_e, cx, cbc = ssm_entry()
                entry["ssm_h"] = h_e
                entry["ssm_conv_x"] = cx
                entry["ssm_conv_bc"] = cbc
            if cfg.encoder is not None:
                f = pad_to_multiple(cfg.encoder.n_frames, n_sh)
                entry["xk"] = kv_entry(f)
                entry["xv"] = kv_entry(f)
            return entry

        if self.scan_layers:
            entry = layer_entry(0)
            out_sds = jax.tree.map(
                lambda e: jax.ShapeDtypeStruct(
                    (cfg.n_layers,) + e[0].shape, e[0].dtype),
                entry, is_leaf=lambda x: isinstance(x, tuple))
            out_specs = jax.tree.map(
                lambda e: P(None, *e[1]), entry,
                is_leaf=lambda x: isinstance(x, tuple))
            return out_sds, out_specs
        sds, specs = [], []
        for i in range(cfg.n_layers):
            e = layer_entry(i)
            sds.append(jax.tree.map(lambda t: t[0], e,
                                    is_leaf=lambda x: isinstance(x, tuple)))
            specs.append(jax.tree.map(lambda t: t[1], e,
                                      is_leaf=lambda x: isinstance(x, tuple)))
        return sds, specs


    # ------------------------------------------------------------------
    # Paged-cache construction (serving runtime; repro/serve)
    # ------------------------------------------------------------------

    def paged_cache_specs(self, slots: int, n_pages: int, page_size: int
                          ) -> tuple[Any, Any]:
        """Returns (ShapeDtypeStruct pytree, PartitionSpec pytree) for the
        paged serving cache: per-layer page POOLS [n_pages, page, KV, hd]
        (the page dim sharded over the cache axes — rank r owns global
        page ids [r*Np_loc, (r+1)*Np_loc)) plus slot-indexed SSM states.
        Unlike ``decode_cache_specs`` nothing scales with max_seq: memory
        is pages actually allocated, and completed sequences recycle their
        pages through the free list (serve/kv_cache.py)."""
        cfg, ctx = self.cfg, self.ctx
        n_sh = attention.cache_shards(ctx)
        assert n_pages % n_sh == 0, (n_pages, n_sh)
        assert cfg.encoder is None and cfg.vision is None, \
            "paged serving supports token-only decoders"
        sax = (("pod", "data", "model") if ctx.has_pod else
               ("data", "model"))
        dt = jnp.dtype(cfg.dtype)
        kvp = attention.padded_kv_heads(cfg) if cfg.n_heads else 0
        hd = cfg.head_dim if cfg.n_heads else 0

        def pool_entry():
            shp = (n_pages, page_size, kvp, hd)
            return (jax.ShapeDtypeStruct(shp, dt), P(sax, None, None, None))

        def ssm_entry():
            s = cfg.ssm
            di = cfg.ssm_heads * s.headdim
            hshp = (slots, cfg.ssm_heads, s.headdim, s.d_state)
            cx = (jax.ShapeDtypeStruct((slots, s.d_conv - 1, di), dt),
                  P(None, None, "model"))
            cbc = (jax.ShapeDtypeStruct((slots, s.d_conv - 1,
                                         2 * s.d_state), dt),
                   P(None, None, None))
            return ((jax.ShapeDtypeStruct(hshp, jnp.float32),
                     P(None, "model", None, None)), cx, cbc)

        def layer_entry(i):
            entry = {}
            if cfg.family != "ssm" and cfg.n_heads:
                entry["kp"] = pool_entry()
                entry["vp"] = pool_entry()
            if cfg.family in ("ssm", "hybrid"):
                h_e, cx, cbc = ssm_entry()
                entry["ssm_h"] = h_e
                entry["ssm_conv_x"] = cx
                entry["ssm_conv_bc"] = cbc
            return entry

        if self.scan_layers:
            entry = layer_entry(0)
            out_sds = jax.tree.map(
                lambda e: jax.ShapeDtypeStruct(
                    (cfg.n_layers,) + e[0].shape, e[0].dtype),
                entry, is_leaf=lambda x: isinstance(x, tuple))
            out_specs = jax.tree.map(
                lambda e: P(None, *e[1]), entry,
                is_leaf=lambda x: isinstance(x, tuple))
            return out_sds, out_specs
        sds, specs = [], []
        for i in range(cfg.n_layers):
            e = layer_entry(i)
            sds.append(jax.tree.map(lambda t: t[0], e,
                                    is_leaf=lambda x: isinstance(x, tuple)))
            specs.append(jax.tree.map(lambda t: t[1], e,
                                      is_leaf=lambda x: isinstance(x, tuple)))
        return sds, specs


def _sinusoidal(positions: Array, d: int) -> Array:
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
