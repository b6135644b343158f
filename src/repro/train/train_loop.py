"""Fault-tolerant training loop + the shard_map train step builder.

The train step is ONE shard_map over the full mesh: every collective in
forward, backward, and optimizer is an MDMP managed op.  Gradient flow:

  * FSDP-sharded params: the fsdp_gather transpose reduce-scatters each
    layer's gradient inside the backward scan step — MDMP's as-ready
    "send on last write" (core/overlap.py);
  * replicated params (+ the pod axis): explicit psums over exactly the
    mesh axes absent from each param's PartitionSpec, with optional int8
    error-feedback compression on the thin cross-pod link.

Fault tolerance (DESIGN.md §4): periodic async checkpoints, automatic
restore-and-retry on step failure (with injectable faults for tests),
straggler detection via step-time EWMA, elastic resume on a different mesh.
"""

from __future__ import annotations

import dataclasses
import json
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import checkpoint as ckpt_lib
from repro.core import managed, overlap
from repro.core import tuner as tuner_lib
from repro.core.faults import FaultPlan
from repro.data.pipeline import SyntheticLMData
from repro.models import layers as model_layers
from repro.models import transformer
from repro.models.model import Model
from repro.obs.calibrate import Recalibrator
from repro.obs.tracer import get_tracer
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.parallel import compression
from repro.parallel import pipeline as pipe
from repro.parallel.sharding import MeshCtx, ParamSpec, smap, spec_pspecs

Array = jax.Array


# ---------------------------------------------------------------------------
# Gradient post-processing: reduce over the axes a param is NOT sharded on
# ---------------------------------------------------------------------------


def _missing_axes(pspec: P, all_axes: tuple[str, ...]) -> tuple[str, ...]:
    present: set[str] = set()
    for entry in pspec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            present.update(entry)
        else:
            present.add(entry)
    return tuple(ax for ax in all_axes if ax not in present)


def sync_grads(grads: Any, spec_tree: Any, ctx: MeshCtx, *,
               compress_pod: bool = False, error_state: Any = None
               ) -> tuple[Any, Any]:
    """psum each grad over the mesh axes absent from its PartitionSpec.
    FSDP/TP-sharded dims were already reduced by collective transposes.
    The pod-axis reduction optionally uses int8 error-feedback compression
    (the thin inter-pod pipe)."""
    pspecs = spec_pspecs(spec_tree)
    flat_g, tdef = jax.tree.flatten(grads)
    flat_s = jax.tree.leaves(pspecs)
    flat_err = (jax.tree.leaves(error_state)
                if error_state is not None else [None] * len(flat_g))
    out_g, out_err = [], []
    for g, ps, err in zip(flat_g, flat_s, flat_err):
        axes = _missing_axes(ps, ctx.all_axes)
        for ax in axes:
            if ax == "pod" and compress_pod and g.size > 4096:
                g, err = compression.compressed_psum(g, ax, err)
            else:
                g = managed.managed_all_reduce(g, ax)
        out_g.append(g)
        out_err.append(err if err is not None else jnp.zeros((), g.dtype))
    return (jax.tree.unflatten(tdef, out_g),
            jax.tree.unflatten(tdef, out_err))


def _replication_factor(pspec: P, ctx: MeshCtx) -> int:
    n = 1
    for ax in _missing_axes(pspec, ctx.all_axes):
        n *= ctx.axis_sizes[ax]
    return n


# ---------------------------------------------------------------------------
# Train step builder
# ---------------------------------------------------------------------------


def build_train_step(model: Model, opt_cfg: AdamWConfig, mesh: Mesh, *,
                     compress_pod: bool = False, donate: bool = True,
                     pipeline: str = "none",
                     pipe_microbatches: int | None = None,
                     global_batch: int | None = None,
                     seq_len: int | None = None
                     ) -> tuple[Callable, Any, Any]:
    """Returns (jitted step, param NamedShardings, batch NamedShardings).

    step(params, opt_state, batch) -> (params, opt_state, metrics)

    ``pipeline`` turns the pod axis into pipeline STAGES instead of
    hierarchical DP: "gpipe" | "1f1b" | "interleaved" pin a schedule,
    "auto" lets the managed runtime pick (cost model + decision log,
    ``managed.resolve_pipeline_schedule``); the batch then replicates
    across pods and streams through the stages as ``pipe_microbatches``
    microbatches (default: the decision's M).  ``global_batch``/
    ``seq_len`` feed the cost model's compute/bytes estimates.
    """
    cfg = model.cfg
    ctx = model.ctx
    spec_tree = model.param_specs()
    pspecs = spec_pspecs(spec_tree)
    use_pipe = pipeline != "none"
    if use_pipe:
        assert ctx.has_pod, (
            f"pipeline={pipeline!r} needs a 'pod' mesh axis (stages); "
            f"got axes {tuple(ctx.axis_sizes)}")
    batch_axes = ("data",) if use_pipe else ctx.batch_axes
    batch_pspec = {"tokens": P(batch_axes, None),
                   "labels": P(batch_axes, None)}
    if cfg.encoder is not None:
        batch_pspec["frames"] = P(batch_axes, None, None)
    if cfg.vision is not None:
        batch_pspec["patches"] = P(batch_axes, None, None)
    accum = max(1, cfg.accum_steps)

    n_devices = 1
    for n in ctx.axis_sizes.values():
        n_devices *= n

    sched = None
    if use_pipe:
        assert model.scan_layers and cfg.moe is None \
            and cfg.encoder is None and cfg.vision is None and accum == 1, \
            "pipeline training needs a uniform scanned decoder stack"
        n_stage = ctx.pods
        # cost-model inputs: one rank's full-batch forward compute
        # (~2 flops/param/token over its layer share) and the boundary
        # activation block
        gb = global_batch if global_batch is not None else 8
        sl = seq_len if seq_len is not None else 128
        b_loc = max(1, gb // max(1, ctx.dp))
        tokens_loc = b_loc * sl
        batch_fwd_s = (2.0 * cfg.param_count() / n_stage * tokens_loc
                       / managed.get_config().hw.peak_flops)
        batch_bytes = (b_loc * (sl // max(1, ctx.tp)) * cfg.d_model
                       * jnp.dtype(cfg.dtype).itemsize)
        # M must tile the local batch: restrict the candidates (and any
        # explicit M) to divisors of b_loc up front, not at trace time
        cand_micro = tuple(m for m in (1, 2, 4, 8, 16, 32, 64)
                           if b_loc % m == 0)
        if pipe_microbatches is not None:
            assert b_loc % pipe_microbatches == 0, (
                f"--microbatches {pipe_microbatches} must divide the "
                f"local batch {b_loc}")
        decision = managed.resolve_pipeline_schedule(
            "pod", n_stage, batch_fwd_s, batch_bytes,
            n_layers=cfg.n_layers, candidate_micro=cand_micro,
            mode=ctx.mdmp_mode,
            schedule=None if pipeline == "auto" else pipeline,
            n_micro=pipe_microbatches)
        sched = pipe.build_schedule(decision.schedule, decision.n_micro,
                                    n_stage, decision.virtual)

    def pipe_loss_and_grads(params, batch):
        """Loss + grads through the managed pipeline over the pod axis.
        Grads come back per-stage partial (each rank only differentiates
        its own chunks); sync_grads' pod psum assembles the full tree."""
        n_virtual = sched.n_stage * sched.virtual
        m = sched.n_micro
        tokens, labels_b = batch["tokens"], batch["labels"]
        b_loc, sl = tokens.shape
        assert b_loc % m == 0, (b_loc, m)
        toks = tokens.reshape(m, b_loc // m, sl)
        labels_s = labels_b.reshape(m, b_loc // m, sl)
        proto = jax.ShapeDtypeStruct(
            (b_loc // m, sl // max(1, ctx.tp), cfg.d_model),
            jnp.dtype(cfg.dtype))

        def chunk_fn(p, q, mb, x):
            x = lax.cond(
                q == 0,
                lambda op: model._assemble_input_sp(
                    p, {"tokens": toks[mb]}).astype(op.dtype),
                lambda op: op, x)
            cp, per = pipe.slice_chunk_params(p["layers"], cfg.n_layers,
                                              n_virtual, q)

            def layer_fn(xc, lp):
                y, _, _, _ = transformer.block_sp(
                    xc, lp, cfg, ctx, causal=True,
                    window=cfg.sliding_window, collect_kv=False)
                return y

            return pipe.masked_chunk_apply(layer_fn, cp, per, x)

        def loss_fn(p, y, mb):
            x = model_layers.rms_norm(y, p["final_ln"], cfg.norm_eps)
            loss_sum, count = model_layers.lm_loss_sp(
                x, model._unembed(p), labels_s[mb], cfg, ctx)
            for ax in ("data", "model"):
                if ax in ctx.axis_sizes:
                    loss_sum = managed.managed_all_reduce(loss_sum, ax)
                    count = managed.managed_all_reduce(count, ax)
            return loss_sum / jnp.maximum(count, 1.0)

        # the loss psums over data+model replicate it there; the backward
        # seed divides their product away (same correction as micro())
        n_md = ctx.dp * ctx.tp
        return pipe.pipeline_value_and_grad(
            chunk_fn, loss_fn, params, proto, sched, "pod", mean=True,
            grad_seed_scale=1.0 / n_md, reduce_grads=False)

    def body(params, opt_state, batch):
        def micro(p, mb):
            # The psum'd loss is REPLICATED on every rank; shard_map
            # transposes then accumulate each rank's cotangent, so the raw
            # grad is n_devices x too large.  Differentiate loss/N and
            # report the true loss via aux.
            loss, metrics = model.loss_sp(p, mb)
            return loss / n_devices, loss

        if use_pipe:
            loss, grads = pipe_loss_and_grads(params, batch)
        elif accum > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape(accum, b // accum, *x.shape[1:])
            stacked = jax.tree.map(split, batch)
            mb0 = jax.tree.map(lambda x: x[0], stacked)
            (_, loss0), g0 = jax.value_and_grad(micro, has_aux=True)(
                params, mb0)

            def acc_body(carry, mb):
                loss_a, g_a = carry
                (_, l), g = jax.value_and_grad(micro, has_aux=True)(
                    params, mb)
                return (loss_a + l,
                        jax.tree.map(jnp.add, g_a, g)), None

            rest = jax.tree.map(lambda x: x[1:], stacked)
            (loss_sum, grads), _ = lax.scan(acc_body, (loss0, g0), rest)
            loss = loss_sum / accum
            grads = jax.tree.map(lambda g: g / accum, grads)
        else:
            (_, loss), grads = jax.value_and_grad(micro, has_aux=True)(
                params, batch)

        grads, _ = sync_grads(grads, spec_tree, ctx,
                              compress_pod=compress_pod)
        # replication-aware global grad norm
        flat_g = jax.tree.leaves(grads)
        flat_s = jax.tree.leaves(pspecs)
        ssq = jnp.float32(0.0)
        for g, ps in zip(flat_g, flat_s):
            rep = _replication_factor(ps, ctx)
            ssq = ssq + jnp.sum(jnp.square(g.astype(jnp.float32))) / rep
        for ax in ctx.all_axes:
            ssq = managed.managed_all_reduce(ssq, ax)
        gnorm = jnp.sqrt(ssq)

        params2, opt2, metrics = adamw_update(
            params, grads, opt_state, opt_cfg, gnorm=gnorm)
        metrics["loss"] = loss
        return params2, opt2, metrics

    opt_pspecs = {"mu": pspecs, "nu": pspecs, "step": P()}
    out_metrics_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
    sharded = smap(body, mesh,
                   in_specs=(pspecs, opt_pspecs, batch_pspec),
                   out_specs=(pspecs, opt_pspecs, out_metrics_spec))
    jitted = jax.jit(sharded, donate_argnums=(0, 1) if donate else ())

    param_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    batch_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   batch_pspec)
    return jitted, param_shardings, batch_shardings


# ---------------------------------------------------------------------------
# Fault-tolerant loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_every: int = 25                # 0: take no checkpoints
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    log_every: int = 10
    max_retries: int = 3
    straggler_factor: float = 3.0       # step > factor * EWMA -> straggler
    ewma: float = 0.9
    managed_cadence: bool = False       # Young/Daly-chosen ckpt interval
    mtbf_s: float = 1800.0              # assumed mean time between failures


class TrainLoop:
    """Drives (step fn, data, checkpoints) with restart-on-failure.

    ``fault_hook(step)`` (tests) may raise to simulate a node failure, and
    ``fault_plan`` injects the deterministic fault taxonomy of
    core/faults.py; the loop restores the latest readable checkpoint and
    retries.  Step times feed a straggler detector (on real pods this
    triggers re-balancing / host replacement; here it logs and counts).

    With ``managed_cadence`` the checkpoint interval is a managed knob:
    ``managed.resolve_checkpoint`` re-resolves the Young/Daly optimum
    between steps from the EWMA step time and checkpoint/metrics.py's
    measured write bandwidth / snapshot cost, logging each pick as a
    ``DecisionRecord(op="ckpt_interval")``.  A ``tuner`` persists the
    winner (it rides along inside the checkpoint's ``extra``), and on an
    elastic resume — checkpoint written on a different mesh — every
    persisted tuner winner is replayed onto the new topology in one
    ``tuner.replan_for_mesh`` pass (``self.replayed`` keeps the trail).
    """

    def __init__(self, step_fn: Callable, model: Model, opt_cfg: AdamWConfig,
                 data: SyntheticLMData, loop_cfg: TrainLoopConfig,
                 param_shardings: Any, batch_shardings: Any,
                 fault_hook: Callable[[int], None] | None = None, *,
                 tuner: tuner_lib.ScheduleTuner | None = None,
                 fault_plan: FaultPlan | None = None):
        self.step_fn = step_fn
        self.model = model
        self.opt_cfg = opt_cfg
        self.data = data
        self.cfg = loop_cfg
        self.param_shardings = param_shardings
        self.batch_shardings = batch_shardings
        self.tuner = tuner
        self.fault_plan = fault_plan
        hooks = [h for h in (
            fault_hook,
            fault_plan.train_hook(ckpt_dir=loop_cfg.ckpt_dir)
            if fault_plan is not None else None) if h is not None]
        self.fault_hook = (
            (lambda step: [h(step) for h in hooks]) if hooks else None)
        self.ckpt_metrics = ckpt_lib.CheckpointMetrics()
        self.mgr = ckpt_lib.CheckpointManager(loop_cfg.ckpt_dir,
                                              keep=loop_cfg.keep,
                                              metrics=self.ckpt_metrics)
        self.ckpt_interval = max(1, loop_cfg.ckpt_every)
        # the step-time EWMA + the cadence re-resolution trigger, now the
        # shared obs.Recalibrator policy (warmup=1: resolve from the very
        # first post-warmup measurement, then on >25% sustained drift —
        # exactly the trigger the loop used to hand-roll inline)
        self.recal = Recalibrator(threshold=0.25, warmup=1,
                                  alpha=loop_cfg.ewma)
        self.ckpt_decisions: list = []       # CheckpointDecision trail
        self.replayed: list[dict] = []       # elastic replan records
        self._resolved_step_s: float | None = None
        self._mesh_axis = "mesh"
        self._mesh_size = 1
        for n in model.ctx.axis_sizes.values():
            self._mesh_size *= int(n)
        self.stragglers: list[int] = []
        self.restarts = 0
        self.history: list[dict] = []

    # -- state management ----------------------------------------------------

    def init_state(self, seed: int = 0) -> tuple[Any, Any, int]:
        params = self.model.init(jax.random.key(seed), self.param_shardings)
        opt = adamw_init(params, self.opt_cfg)
        # the step returns its counter replicated over the mesh; start it
        # there, or the second step compiles the whole program again
        mesh = jax.tree.leaves(self.param_shardings)[0].mesh
        opt["step"] = jax.device_put(opt["step"], NamedSharding(mesh, P()))
        return params, opt, 0

    def resume_or_init(self, seed: int = 0) -> tuple[Any, Any, int]:
        params, opt, _ = self.init_state(seed)
        like = {"params": params, "opt": opt}
        t0 = time.monotonic()
        hit = ckpt_lib.restore_latest(
            self.cfg.ckpt_dir, like,
            shardings={"params": self.param_shardings,
                       "opt": {"mu": self.param_shardings,
                               "nu": self.param_shardings,
                               "step": None}})
        if hit is None:
            return params, opt, 0
        tree, extra, ck_step = hit
        self.ckpt_metrics.note_restore(ck_step, time.monotonic() - t0)
        step = int(extra.get("step", ck_step))
        if "data" in extra:
            # the data pipeline resumes WITH the model: dropping its state
            # used to replay batches the optimizer had already consumed
            self.data, _ = SyntheticLMData.resume(self.data.cfg,
                                                  extra["data"])
        if self.tuner is not None and "tuner" in extra:
            self.tuner.load_entries(extra["tuner"])
            mesh_now = self._mesh_dict()
            mesh_then = {k: int(v)
                         for k, v in extra.get("mesh", mesh_now).items()}
            if mesh_then != mesh_now:
                # elastic resume: N-way winners replayed onto M ranks
                sizes = dict(mesh_now)
                sizes[self._mesh_axis] = self._mesh_size
                self.replayed += tuner_lib.replan_for_mesh(
                    self.tuner, sizes,
                    step_s=self._resolved_step_s or 0.1,
                    mtbf_s=self.cfg.mtbf_s)
        return tree["params"], tree["opt"], step

    def _mesh_dict(self) -> dict[str, int]:
        return {k: int(v) for k, v in self.model.ctx.axis_sizes.items()}

    def _batch(self, step: int) -> Any:
        g = self.data.global_batch_at(step)
        return {k: jax.device_put(v, self.batch_shardings[k])
                if k in self.batch_shardings else v for k, v in g.items()}

    # -- managed checkpoint cadence -------------------------------------------

    def _resolve_cadence(self, step_s: float, snapshot_bytes: int) -> None:
        """Re-resolve the Young/Daly interval from live measurements: the
        EWMA step time plus checkpoint/metrics.py's measured write
        bandwidth, snapshot cost and restore time.  Logged as a
        DecisionRecord(op="ckpt_interval"); the winner persists via the
        tuner (riding along inside the next checkpoint)."""
        m = self.ckpt_metrics
        d = managed.resolve_checkpoint(
            self._mesh_axis, step_s, snapshot_bytes,
            mtbf_s=self.cfg.mtbf_s,
            measured_write_bw=m.write_bw_estimate(),
            measured_ckpt_cost_s=m.ckpt_cost_s_estimate(),
            measured_restore_s=m.restore_s_estimate())
        self.ckpt_interval = max(1, int(d.interval))
        self.ckpt_decisions.append(d)
        self._resolved_step_s = step_s
        self.recal.rebase(step_s)
        # re-meter the async drain's D2H chunking to the current step time
        self.mgr.drain_chunk_bytes = overlap.drain_chunk_bytes(
            step_s, d.write_bw)
        if self.tuner is not None:
            entry = self.tuner.decide_ckpt(
                self._mesh_axis, self._mesh_size, snapshot_bytes, step_s,
                mtbf_s=self.cfg.mtbf_s, write_bw=m.write_bw_estimate(),
                ckpt_cost_s=m.ckpt_cost_s_estimate(),
                restore_s=m.restore_s_estimate())
            cost = m.ckpt_cost_s_estimate()
            if cost is not None:
                # realized overhead of the cadence we actually ran
                tau = self.ckpt_interval * step_s
                overhead = (cost / tau
                            + (0.5 * tau + (m.restore_s_estimate() or 0.0))
                            / self.cfg.mtbf_s)
                self.tuner.record(entry.key, d.mode, self.ckpt_interval,
                                  overhead)

    def _save(self, step: int, params: Any, opt: Any) -> None:
        extra = {"step": step, "data": self.data.state_dict(step),
                 "mesh": self._mesh_dict()}
        if self.tuner is not None:
            extra["tuner"] = json.loads(self.tuner.to_json())
        self.mgr.save_async(step, {"params": params, "opt": opt},
                            extra=extra)

    # -- the loop --------------------------------------------------------------

    def run(self, params: Any, opt: Any, start_step: int = 0) -> dict:
        cfg = self.cfg
        tr = get_tracer()
        step = start_step
        retries = 0
        warmup_until = start_step + 2
        last_saved = start_step
        steps_executed = 0
        wall_t0 = time.monotonic()
        snapshot_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves({"params": params, "opt": opt})
            if hasattr(leaf, "size"))
        while step < cfg.total_steps:
            with tr.span("train.batch", track="compute", step=step):
                batch = self._batch(step)
            t0 = time.monotonic()
            try:
                if self.fault_hook is not None:
                    self.fault_hook(step)
                with tr.span("train.step", track="compute", step=step):
                    with tr.span("train.dispatch", track="compute"):
                        params, opt, metrics = self.step_fn(params, opt,
                                                            batch)
                    # float() blocks on the device — the span measures
                    # the realized step, not the dispatch
                    with tr.span("train.sync", track="compute"):
                        loss = float(metrics["loss"])
                if not np.isfinite(loss):
                    raise FloatingPointError(f"non-finite loss at {step}")
            except Exception as e:          # noqa: BLE001 — restart path
                retries += 1
                self.restarts += 1
                if retries > cfg.max_retries:
                    raise
                self.mgr.wait()
                params, opt, step = self.resume_or_init()
                last_saved = step
                # the EWMA window must restart: the first post-restore
                # steps re-compile/re-warm, and judging them against the
                # pre-fault EWMA flags every recovery as a straggler
                warmup_until = step + 2
                continue
            retries = 0
            steps_executed += 1
            dt = time.monotonic() - t0
            in_warmup = step < warmup_until
            ewma_t = self.recal.value
            if (not in_warmup and ewma_t is not None
                    and dt > cfg.straggler_factor * ewma_t):
                self.stragglers.append(step)
            if not in_warmup:
                # (re)compile steps feed neither EWMA nor straggler
                self.recal.note(dt)
            self.history.append({"step": step, "loss": loss,
                                 "time_s": dt})
            if cfg.managed_cadence and self.recal.should_retune():
                self._resolve_cadence(self.recal.value, snapshot_bytes)
            step += 1
            if cfg.ckpt_every > 0 and (step - last_saved >= self.ckpt_interval
                                       or step == cfg.total_steps):
                # scale = the train seconds this cadence amortizes one
                # checkpoint over, so dur/scale is the measured overhead
                # fraction — the unit resolve_checkpoint predicts
                with tr.span("ckpt.save", op="ckpt_interval",
                             axis=self._mesh_axis, track="ckpt",
                             nbytes=snapshot_bytes,
                             scale=self.ckpt_interval
                             * max(self.recal.value or dt, 1e-9)):
                    self._save(step, params, opt)
                last_saved = step
        self.mgr.wait()
        return {"params": params, "opt": opt, "step": step,
                "history": self.history, "stragglers": self.stragglers,
                "restarts": self.restarts,
                "steps_executed": steps_executed,
                "wall_s": time.monotonic() - wall_t0,
                "ckpt_interval": self.ckpt_interval,
                "replayed": self.replayed}
