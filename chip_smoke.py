"""Bring-up check: MDMP's serve, train and halo paths on TPU.

    python3 chip_smoke.py             # one chip: serve, train, halo phases
    python3 chip_smoke.py --chips 4   # four chips: the halo path only

Run it from the root of a checkout; it needs nothing but the checkout and
makes its weights and data from seeds.  Every phase goes through the
entry points a user calls (``launch.serve.build_engine``, ``TrainLoop``,
``halo.jacobi_solve``) with the Pallas kernels compiled, checks its
results, and checks that each kernel agrees with its jnp engine at the
phase's shapes.  The times printed are smoke timings of single runs, not
benchmark numbers.

It exits non-zero before any phase when JAX finds no TPU or when
``REPRO_PALLAS`` is set to anything but ``on``, and non-zero after any
failed check.  The last line of stdout is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: the model of the serve and train phases
ARCH = "phi4-mini-3.8b"
#: float32 grid of the halo phase: 16384^2 * 4 B = 1 GiB per array
HALO_N = 16384
HALO_ITERS = 32
#: share of device memory the compiled train step may claim when the
#: train phase picks its depth (the rest is runtime slack)
TRAIN_MEM_SHARE = 0.95

# Tolerances of the kernel checks, each against the kernel's jnp engine.
#: bf16 outputs: half an ulp of bf16 is 2^-9 relative, and the engines sum
#: the softmax in different block orders in f32
ATTN_TOL = 2e-2
#: the stencil kernel and its jnp engine apply the same f32 operations in
#: the same order; the bound only leaves room for a fused multiply-add
STENCIL_TOL = 1e-5


class SmokeFailure(AssertionError):
    """A phase's result is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    """The TPU devices, or exit non-zero before any phase."""
    mode = os.environ.get("REPRO_PALLAS")
    if mode not in (None, "on"):
        sys.exit(f"chip_smoke: REPRO_PALLAS={mode!r} would run the "
                 f"kernels' references; unset it or set it to 'on'")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX found "
                 f"{len(devices)}")
    return devices[:n_chips]


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _has_kernel(compiled_text: str, what: str) -> None:
    check("tpu_custom_call" in compiled_text,
          f"{what}: no tpu_custom_call in the compiled HLO, so a Pallas "
          f"kernel fell back to a reference")


def _max_abs_diff(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# Serve phase
# ---------------------------------------------------------------------------


def _serve_requests(engine, prompts, new_tokens: int) -> list[np.ndarray]:
    rids = [engine.submit(p, new_tokens) for p in prompts]
    out = engine.run()
    return [out[r] for r in rids]


def check_paged_kernel(engine, seed: int) -> float:
    """Paged attention kernel vs its jnp engine at the engine's pool
    shape, with random pages and page chains."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_attention as paged
    from repro.kernels.ops import _pallas_mode
    pool = jax.tree.leaves(engine.cache)[0]        # [L, Np, page, KV, hd]
    _, n_pages, page, kvh, hd = pool.shape
    cfg = engine.model.cfg
    slots, pmax = engine.pt.table.shape
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k1, (slots, cfg.padded_heads, hd), pool.dtype)
    kp = jax.random.normal(k2, (n_pages, page, kvh, hd), pool.dtype)
    vp = jax.random.normal(k3, (n_pages, page, kvh, hd), pool.dtype)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(n_pages)[:slots * pmax]
                        .reshape(slots, pmax).astype(np.int32))
    lens = jnp.asarray(rng.integers(1, pmax * page + 1, size=slots)
                       .astype(np.int32))
    got = jax.jit(lambda *a: paged.paged_attention_pallas(
        *a, interpret=_pallas_mode() != "on"))(q, kp, vp, table, lens)
    want = jax.jit(paged.paged_attention_jnp)(q, kp, vp, table, lens)
    err = _max_abs_diff(got, want)
    check(err <= ATTN_TOL, f"paged attention kernel vs jnp: max |diff| "
          f"{err} > {ATTN_TOL}")
    return err


def serve_phase(cfg, *, slots: int = 4, n_requests: int = 8,
                prompt_lens: tuple[int, int] = (64, 256),
                new_tokens: int = 32, seed: int = 0,
                on_tpu: bool = True) -> dict:
    """Serve ``n_requests`` seeded prompts twice through ``ServeEngine``
    (built as ``launch.serve`` builds it) and check the tokens."""
    import jax
    from repro.launch.serve import build_engine
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in rng.integers(lo, hi + 1, size=n_requests)]
    t0 = time.perf_counter()
    engine = build_engine(cfg, seed=seed, slots=slots,
                          max_seq=hi + new_tokens)
    jax.block_until_ready(engine.params)
    res = {"init_s": time.perf_counter() - t0}
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.cache))
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if stats is not None:
        # one copy of the weights and the page pool, plus small change
        held = stats["bytes_in_use"]
        check(held <= 1.05 * (param_bytes + cache_bytes) + (64 << 20),
              f"serve: {held} B in use after init, weights are "
              f"{param_bytes} B and the page pool {cache_bytes} B")
    res.update(param_bytes=param_bytes, cache_bytes=cache_bytes)

    t0 = time.perf_counter()
    first = _serve_requests(engine, prompts, new_tokens)
    res["run1_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = _serve_requests(engine, prompts, new_tokens)
    res["run2_s"] = time.perf_counter() - t0
    for i, (a, b) in enumerate(zip(first, second)):
        check(a.shape == (new_tokens,),
              f"serve: request {i} returned {a.shape[0]} tokens")
        check(bool(((a >= 0) & (a < cfg.vocab_size)).all()),
              f"serve: request {i} returned out-of-vocab tokens")
        check(np.array_equal(a, b),
              f"serve: request {i} differs between two runs")
    chunk = engine.scheduler.chunk
    res["chunk"] = chunk
    res["tokens"] = int(sum(len(p) for p in prompts)) + n_requests * new_tokens
    if on_tpu:
        t0 = time.perf_counter()
        text = engine.lower_quantum(chunk).compile().as_text()
        res["quantum_compile_s"] = time.perf_counter() - t0
        _has_kernel(text, "serve quantum")
    res["paged_kernel_err"] = check_paged_kernel(engine, seed)
    res["peak_bytes"] = peak_bytes(dev)
    return res


# ---------------------------------------------------------------------------
# Train phase
# ---------------------------------------------------------------------------


def _train_parts(cfg, steps: int):
    import jax
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import MeshCtx
    from repro.train.train_loop import build_train_step
    mesh = jax.make_mesh((jax.device_count(), 1), ("data", "model"))
    model = Model(cfg, MeshCtx.from_mesh(mesh))
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=steps,
                          moment_dtype=cfg.moment_dtype)
    step_fn, pshard, bshard = build_train_step(model, opt_cfg, mesh)
    return model, opt_cfg, step_fn, pshard, bshard


def _abstract_state(model, opt_cfg, pshard, bshard, seq: int, batch: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.parallel.sharding import ParamSpec
    dt = jnp.dtype(model.cfg.dtype)
    mdt = jnp.dtype(opt_cfg.moment_dtype)
    sds = lambda d: (lambda s, sh: jax.ShapeDtypeStruct(s.shape, d,
                                                        sharding=sh))
    is_ps = lambda x: isinstance(x, ParamSpec)
    params = jax.tree.map(sds(dt), model.param_specs(), pshard,
                          is_leaf=is_ps)
    mom = jax.tree.map(sds(mdt), model.param_specs(), pshard, is_leaf=is_ps)
    mesh = jax.tree.leaves(pshard)[0].mesh
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(
        mesh, PartitionSpec()))
    batch_sds = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                         sharding=bshard[k])
                 for k in ("tokens", "labels")}
    return params, {"mu": mom, "nu": mom, "step": step}, batch_sds


def fit_train_depth(cfg, *, seq: int, batch: int, limit: int) -> int:
    """The deepest cut of ``cfg`` whose compiled train step (weights,
    grads, AdamW moments, activations and temporaries, as the compiler
    counts them) fits ``TRAIN_MEM_SHARE`` of the chip's ``limit`` bytes.
    The search starts where the 12 bytes per parameter of weights, grads
    and moments alone would fill three quarters of the chip."""
    from repro.models.model import Model
    from repro.parallel.sharding import MeshCtx, ParamSpec
    import jax

    def n_params(n_layers: int) -> int:
        m = Model(dataclasses.replace(cfg, n_layers=n_layers),
                  MeshCtx({"data": 1, "model": 1}))
        return sum(math.prod(s.shape) for s in jax.tree.leaves(
            m.param_specs(), is_leaf=lambda x: isinstance(x, ParamSpec)))

    per_layer = n_params(2) - n_params(1)
    base = n_params(1) - per_layer
    top = int((0.75 * limit / 12 - base) // per_layer)
    for n_layers in range(min(top, cfg.n_layers), 0, -1):
        model, opt_cfg, step_fn, pshard, bshard = _train_parts(
            dataclasses.replace(cfg, n_layers=n_layers), steps=1)
        ma = step_fn.lower(*_abstract_state(
            model, opt_cfg, pshard, bshard, seq, batch)).compile() \
            .memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        log(f"train depth {n_layers}: compiled step needs {need} B of "
            f"{limit} B")
        if need <= TRAIN_MEM_SHARE * limit:
            return n_layers
    raise SmokeFailure(f"train: no depth of {cfg.name} fits {limit} B")


def check_flash_kernel(cfg, *, seq: int, batch: int, seed: int) -> float:
    """Flash forward kernel vs the blockwise jnp engine at the train
    phase's attention shape."""
    import jax
    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import padded_kv_heads
    hd, dt = cfg.head_dim, cfg.dtype
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k1, (batch, seq, cfg.padded_heads, hd), dt)
    k = jax.random.normal(k2, (batch, seq, padded_kv_heads(cfg), hd), dt)
    v = jax.random.normal(k3, (batch, seq, padded_kv_heads(cfg), hd), dt)
    got = jax.jit(lambda *a: flash_attention_pallas(
        *a, causal=True, interpret=ops._pallas_mode() != "on"))(q, k, v)
    want = jax.jit(lambda *a: ops.flash_attention_blockwise(
        *a, causal=True))(q, k, v)
    err = _max_abs_diff(got, want)
    check(err <= ATTN_TOL, f"flash kernel vs jnp: max |diff| {err} > "
          f"{ATTN_TOL}")
    return err


def train_phase(cfg, *, seq: int = 2048, batch: int = 1, steps: int = 3,
                seed: int = 0, on_tpu: bool = True) -> dict:
    """``steps`` TrainLoop steps of ``cfg`` on seeded synthetic tokens;
    the losses must be finite."""
    import jax
    from repro.data.pipeline import DataConfig, SyntheticLMData
    from repro.train.train_loop import TrainLoop, TrainLoopConfig
    model, opt_cfg, step_fn, pshard, bshard = _train_parts(cfg, steps)
    data = SyntheticLMData(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=seq, global_batch=batch,
                                      seed=seed))
    loop = TrainLoop(step_fn, model, opt_cfg, data,
                     TrainLoopConfig(total_steps=steps, ckpt_every=0,
                                     ckpt_dir=str(ROOT / "results"),
                                     max_retries=0),
                     pshard, bshard)
    t0 = time.perf_counter()
    params, opt, _ = loop.init_state(seed)
    jax.block_until_ready(params)
    res = {"init_s": time.perf_counter() - t0}
    if on_tpu:
        batch0 = {k: jax.device_put(v, bshard[k])
                  for k, v in data.global_batch_at(0).items()}
        t0 = time.perf_counter()
        text = step_fn.lower(params, opt, batch0).compile().as_text()
        res["step_compile_s"] = time.perf_counter() - t0
        _has_kernel(text, "train step")
    t0 = time.perf_counter()
    out = loop.run(params, opt, 0)
    res["run_s"] = time.perf_counter() - t0
    losses = [h["loss"] for h in out["history"]]
    check(len(losses) == steps and all(map(math.isfinite, losses)),
          f"train: losses {losses}")
    res["losses"] = losses
    res["step_s"] = [h["time_s"] for h in out["history"]]
    del params, opt, out
    res["flash_kernel_err"] = check_flash_kernel(cfg, seq=seq, batch=batch,
                                                 seed=seed)
    res["peak_bytes"] = peak_bytes(jax.devices()[0])
    return res


# ---------------------------------------------------------------------------
# Halo phase (the paper's Jacobi workload)
# ---------------------------------------------------------------------------


def _halo_solver(mesh, iters: int, mode: str, **kw):
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.core import halo
    from repro.parallel.sharding import smap
    rows = P("x", None)
    return jax.jit(smap(
        lambda u, f: halo.jacobi_solve(u, f, "x", iters, mode, **kw),
        mesh, in_specs=(rows, rows), out_specs=rows))


def _halo_grid(mesh, n: int, seed: int):
    """Seeded u0 and f, [n, n] float32 each, made on the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P("x", None))
    make = jax.jit(lambda key: tuple(
        jax.random.normal(k, (n, n), jnp.float32)
        for k in jax.random.split(key)), out_shardings=(sh, sh))
    return make(jax.random.key(seed))


def halo_k(mesh, n: int) -> int:
    """The aggregation factor the managed runtime picks for this grid."""
    from repro.core import cost_model, managed
    ndev = mesh.devices.size
    return cost_model.decide_halo_aggregation(
        n // ndev, n, ndev, dtype_bytes=4, hw=managed.get_config().hw).k


def check_stencil_kernel(u, f, k: int) -> float:
    """k-sweep stencil kernel vs its jnp engine on the grid padded with
    k-row Dirichlet aprons."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import _pallas_mode
    from repro.kernels.stencil import jacobi_ksweep_pallas, ksweep_trapezoid

    def pad(x):
        return jnp.pad(x, ((k, k), (0, 0)))

    got = jax.jit(lambda a, b: jacobi_ksweep_pallas(
        pad(a), pad(b), k, k, k, interpret=_pallas_mode() != "on"))(u, f)
    want = jax.jit(lambda a, b: ksweep_trapezoid(
        pad(a), pad(b), k, k, k)[k:-k])(u, f)
    err = _max_abs_diff(got, want)
    check(err <= STENCIL_TOL, f"stencil kernel vs jnp: max |diff| {err} > "
          f"{STENCIL_TOL}")
    return err


def halo_phase(mesh, *, n: int = HALO_N, iters: int = HALO_ITERS,
               seed: int = 0, on_tpu: bool = True) -> dict:
    """Managed aggregated Jacobi (k from decide_halo_aggregation, Pallas
    engine) against the bulk schedule on one mesh."""
    import jax
    u, f = _halo_grid(mesh, n, seed)
    k = halo_k(mesh, n)
    res = {"k": k, "n": n, "iters": iters}
    agg = _halo_solver(mesh, iters, "aggregated", k=k, engine="pallas")
    if on_tpu:
        _has_kernel(agg.lower(u, f).compile().as_text(), "aggregated halo")
    got, res["aggregated_first_s"] = _timed(agg, u, f)
    _, res["aggregated_s"] = _timed(agg, u, f)
    bulk = _halo_solver(mesh, iters, "bulk")
    want, res["bulk_first_s"] = _timed(bulk, u, f)
    _, res["bulk_s"] = _timed(bulk, u, f)
    res["agg_vs_bulk_err"] = err = _max_abs_diff(got, want)
    check(err <= STENCIL_TOL, f"halo: aggregated vs bulk max |diff| {err} "
          f"> {STENCIL_TOL}")
    del got, want
    if mesh.devices.size == 1:
        res["stencil_kernel_err"] = check_stencil_kernel(u, f, k)
    res["peak_bytes"] = peak_bytes(mesh.devices.flat[0])
    return res


def halo_mesh_phase(devices, *, n: int = HALO_N, iters: int = HALO_ITERS,
                    seed: int = 0, on_tpu: bool = True) -> dict:
    """The halo path across all ``devices`` (rows on one mesh axis):
    bulk and managed aggregated, each against the one-device solve."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    one = Mesh(np.array(devices[:1]), ("x",))
    mesh = Mesh(np.array(devices), ("x",))
    u1, f1 = _halo_grid(one, n, seed)
    ref, res_ref_s = _timed(_halo_solver(one, iters, "bulk"), u1, f1)
    sh = NamedSharding(mesh, P("x", None))
    u, f = jax.device_put(u1, sh), jax.device_put(f1, sh)
    del u1, f1
    owners = {s.device for s in u.addressable_shards}
    check(len(owners) == len(devices),
          f"halo mesh: the grid sits on {len(owners)} devices, not "
          f"{len(devices)}")
    k = halo_k(mesh, n)
    res = {"k": k, "n": n, "iters": iters, "one_device_bulk_s": res_ref_s}
    for mode, kw in (("bulk", {}),
                     ("aggregated", {"k": k, "engine": "pallas"})):
        fn = _halo_solver(mesh, iters, mode, **kw)
        if on_tpu and mode == "aggregated":
            _has_kernel(fn.lower(u, f).compile().as_text(),
                        "aggregated halo")
        got, res[f"{mode}_first_s"] = _timed(fn, u, f)
        _, res[f"{mode}_s"] = _timed(fn, u, f)
        err = _max_abs_diff(jax.device_put(got, devices[0]), ref)
        res[f"{mode}_vs_one_device_err"] = err
        check(err <= STENCIL_TOL, f"halo mesh: {mode} vs one-device solve "
              f"max |diff| {err} > {STENCIL_TOL}")
    res["peak_bytes"] = [peak_bytes(d) for d in devices]
    return res


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the halo path across four chips only")
    args = ap.parse_args()
    devices = require_tpu(args.chips)

    import jax
    from repro import configs
    from repro.core import managed
    from repro.launch.compile_cache import use_compile_cache
    from jax.sharding import Mesh

    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"hardware model: {managed.get_config().hw}")
    log(f"compile cache: {use_compile_cache()}")
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    log(f"device memory limit: {limit} B")

    if args.chips == 4:
        res = halo_mesh_phase(devices)
        log(f"halo x4 (smoke timings): {json.dumps(res)}")
    else:
        cfg = configs.get_config(ARCH)
        res = serve_phase(cfg)
        log(f"serve {ARCH} {cfg.n_layers} layers (smoke timings): "
            f"{json.dumps(res)}")
        check(res["peak_bytes"] is None or limit is None
              or res["peak_bytes"] < limit, "serve: peak over device memory")
        gc.collect()

        cfg = configs.get_config(ARCH)
        depth = fit_train_depth(cfg, seq=2048, batch=1, limit=limit)
        log(f"train {ARCH}: published widths, depth cut "
            f"{cfg.n_layers} -> {depth} layers so weights, grads and AdamW "
            f"state fit one chip; seq 2048, batch 1")
        res = train_phase(dataclasses.replace(cfg, n_layers=depth))
        log(f"train (smoke timings): {json.dumps(res)}")
        gc.collect()

        res = halo_phase(Mesh(np.array(devices[:1]), ("x",)))
        log(f"halo x1 (smoke timings): {json.dumps(res)}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
