"""Managed collectives — MDMP's send/recv directives, TPU-native.

The paper lets the user *declare* communication (``#pragma send/recv``) and
has the runtime decide when/how to execute it, intermingling messages with
the computation that produces or consumes the data.  The JAX/TPU analogue
implemented here: every collective a model needs is expressed through a
``managed_*`` entry point that can execute in two modes:

  * ``bulk``         — exactly the unmanaged ``jax.lax`` collective.  This is
                       the paper-faithful "MDMP disabled at compile time"
                       path and the numerical oracle for every test.
  * ``interleaved``  — a chunked ``lax.ppermute`` ring schedule in which each
                       ring step's DMA overlaps the adjacent step's compute
                       (for the fused *_matmul variants the compute is fused
                       into the ring, which is the paper's "send each piece
                       as soon as its last write occurs" at tile granularity).
  * ``auto``         — the manager decides per call site using the alpha-beta
                       cost model (and shape-derived compute estimates), and
                       logs the decision (the paper's managed-runtime role).

All functions must be called inside ``shard_map`` (they use collective axis
names).  Interleaved outputs are numerically identical to bulk outputs up to
floating-point reduction order; tests assert allclose against bulk.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cost_model
from repro.core.cost_model import HardwareModel
from repro.obs.tracer import dispatch_span

Array = jax.Array


# ---------------------------------------------------------------------------
# Global MDMP configuration + decision log (the managed-runtime audit trail)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MDMPConfig:
    """Process-wide MDMP behaviour.  ``mode='auto'`` lets the cost model pick
    per call site; forcing ``bulk`` reproduces the unmanaged baseline,
    forcing ``interleaved`` reproduces the paper's always-intermingle mode.
    """
    mode: str = "auto"                # auto | bulk | interleaved
    chunks: int | None = None         # override ring sub-chunking
    hw: HardwareModel = dataclasses.field(
        default_factory=cost_model.device_hardware)
    log_decisions: bool = True
    # quantized FSDP weight gathering (fp8 payload, bf16 master weights,
    # exact-dtype gradient reduce-scatter) — §Perf round 3
    fsdp_gather_dtype: str | None = None


_STATE = threading.local()


def get_config() -> MDMPConfig:
    cfg = getattr(_STATE, "config", None)
    if cfg is None:
        cfg = MDMPConfig()
        _STATE.config = cfg
    return cfg


class use_config:
    """``with mdmp.use_config(MDMPConfig(mode='bulk')): ...``"""

    def __init__(self, config: MDMPConfig):
        self._new = config

    def __enter__(self) -> MDMPConfig:
        self._old = getattr(_STATE, "config", None)
        _STATE.config = self._new
        return self._new

    def __exit__(self, *exc: Any) -> None:
        _STATE.config = self._old


@dataclasses.dataclass(frozen=True)
class DecisionRecord:
    op: str
    axis: str
    nbytes: int
    mode: str
    chunks: int
    predicted_bulk_s: float
    predicted_interleaved_s: float
    #: monotonic log time (``time.perf_counter``), stamped by
    #: ``log_decision`` — the join key that places the decision instant
    #: on the trace timeline next to its measured spans.  Excluded from
    #: equality so decision-trail comparisons stay timestamp-free.
    t: float | None = dataclasses.field(default=None, compare=False)


#: Every DecisionRecord ``op`` the managed runtime may emit — ONE registry
#: so the program planner, the trail printers, and the CI greps can
#: enumerate them instead of guessing free strings.  Subsystem resolvers
#: first, then the generic managed-collective call sites, then the joint
#: planner's summary record.
DECISION_OPS = frozenset({
    # subsystem resolvers (resolve_*)
    "halo_aggregation", "attention_schedule", "pipeline_schedule",
    "serve_schedule", "preempt_policy", "ckpt_interval", "moe_dispatch",
    # generic managed collectives (_resolve call sites)
    "all_gather", "reduce_scatter", "all_reduce", "all_to_all",
    "all_gather_matmul", "all_gather_matmul_multi", "gram_ag_ring",
    "matmul_reduce_scatter", "ring_attention", "expert_stream",
    # the whole-program planner (plan/planner.py) summary record
    "program_plan",
    # the static verifier's preflight audit record (repro.analysis):
    # axis carries the linted graph name, chunks the diagnostic count,
    # nbytes the error count — suppressed warnings land in the trail
    "lint",
})

_DECISION_LOG: list[DecisionRecord] = []


def log_decision(rec: DecisionRecord) -> None:
    """Append to the audit trail, enforcing the op-name registry (a typo'd
    op would silently escape every trail grep and the planner's lowering)."""
    assert rec.op in DECISION_OPS, (
        f"unregistered DecisionRecord op {rec.op!r}; add it to "
        f"managed.DECISION_OPS")
    if rec.t is None:
        # stamp log time here (not in the resolver) so every emission
        # site gets trace-timeline placement for free
        object.__setattr__(rec, "t", time.perf_counter())
    _DECISION_LOG.append(rec)


def decision_log() -> list[DecisionRecord]:
    return list(_DECISION_LOG)


def clear_decision_log() -> None:
    _DECISION_LOG.clear()


class capture_decisions:
    """``with managed.capture_decisions() as cap: ...`` — scoped view of
    the decisions logged inside the block, WITHOUT clearing or copying
    the global trail (``_DECISION_LOG`` only ever grows; tests and the
    trace exporter need "the records of THIS run", not "all records
    since import").  ``cap.records`` is live: it re-slices the trail on
    every access, so it is valid both inside the block and after exit
    (where it is pinned to the block's extent)."""

    def __init__(self) -> None:
        self._start = 0
        self._end: int | None = None

    def __enter__(self) -> "capture_decisions":
        self._start = len(_DECISION_LOG)
        self._end = None
        return self

    def __exit__(self, *exc: Any) -> None:
        self._end = len(_DECISION_LOG)

    @property
    def records(self) -> list[DecisionRecord]:
        return list(_DECISION_LOG[self._start:self._end])


# ---------------------------------------------------------------------------
# Program-plan override (the MDMP compiler's hook into every resolver)
# ---------------------------------------------------------------------------
#
# plan/planner.py emits a ProgramPlan whose knobs must win over each
# subsystem's LOCAL resolution.  The plan is installed on the same
# thread-local as MDMPConfig and consulted by every resolve_* entry point
# and by _resolve for the generic collectives.  Precedence, most-binding
# first: explicit caller knob (schedule=/k=/n_micro=/chunks=...) >
# program-plan knob > ambient mode (cfg.mode / ctx.mdmp_mode "auto") >
# cost-model auto.  The plan object is duck-typed (anything with
# ``knob_for(op, axis) -> dict | None``) so this module never imports
# plan/ (no import cycle).


def install_plan(plan: Any | None) -> None:
    """Install (or clear, with None) the active ProgramPlan for this
    thread.  Planner-chosen knobs win over local resolution wherever the
    caller did not pin an explicit knob."""
    _STATE.plan = plan


def active_plan() -> Any | None:
    return getattr(_STATE, "plan", None)


class use_plan:
    """``with managed.use_plan(program_plan): ...`` — scoped install."""

    def __init__(self, plan: Any | None):
        self._new = plan

    def __enter__(self) -> Any | None:
        self._old = getattr(_STATE, "plan", None)
        _STATE.plan = self._new
        return self._new

    def __exit__(self, *exc: Any) -> None:
        _STATE.plan = self._old


def _plan_knob(op: str, axis_name: str) -> dict | None:
    """The active plan's knob for (op, axis), or None when no plan is
    installed / the plan has no opinion on this call site."""
    plan = active_plan()
    if plan is None:
        return None
    return plan.knob_for(op, axis_name)


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _axis_size(axis_name: str) -> int:
    return lax.psum(1, axis_name)


def _nbytes(x: Array) -> int:
    return int(x.size * x.dtype.itemsize)


def _resolve(op: str, axis_name: str, x: Array, mode: str | None,
             chunks: int | None, collective: str,
             compute_time_s: float = 0.0) -> tuple[str, int]:
    """Resolve mode/chunks for a call site and log the decision."""
    cfg = get_config()
    pk = _plan_knob(op, axis_name)
    if pk is not None and mode in (None, "auto") and chunks is None:
        # the program plan binds this call site; an explicit caller
        # mode/chunks would have pinned the knob above it
        mode = pk.get("mode") or mode
        chunks = pk.get("chunks")
    mode = mode or cfg.mode
    n = _axis_size(axis_name)
    decision = cost_model.decide(
        _nbytes(x), n, compute_time_s=compute_time_s, hw=cfg.hw,
        collective=collective,
        force_mode=None if mode == "auto" else mode)
    eff_chunks = chunks if chunks is not None else (
        cfg.chunks if cfg.chunks is not None else decision.chunks)
    eff_mode = decision.mode if mode == "auto" else mode
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op=op, axis=axis_name, nbytes=_nbytes(x), mode=eff_mode,
            chunks=eff_chunks,
            predicted_bulk_s=decision.bulk_time_s,
            predicted_interleaved_s=decision.interleaved_time_s))
    return eff_mode, max(1, int(eff_chunks))


def resolve_halo_aggregation(axis_name: str, axis_size: int,
                             rows_local: int, cols: int, *,
                             dtype_bytes: int = 4,
                             candidate_k: Sequence[int] = (1, 2, 4, 8),
                             mode: str | None = None,
                             k: int | None = None
                             ) -> cost_model.HaloAggregationDecision:
    """The managed-runtime entry for the aggregation knob: pick how many
    stencil sweeps each halo exchange should carry (k=1 = bulk) and log the
    decision.  Called OUTSIDE shard_map at planning time — ``axis_size`` is
    the static mesh extent, and the chosen k feeds
    ``halo.jacobi_solve(mode="aggregated", k=...)``.

    ``mode="bulk"`` (or a global MDMPConfig forcing bulk) pins k=1 — the
    paper-faithful unmanaged baseline; ``k`` pins an explicit sweep count
    (the tuner's measured override).  The DecisionRecord reuses ``chunks``
    to carry k and the predicted fields to carry seconds-per-sweep.
    """
    cfg = get_config()
    pk_plan = _plan_knob("halo_aggregation", axis_name)
    if pk_plan is not None and mode in (None, "auto") and k is None:
        k = pk_plan.get("chunks")
    eff_mode = mode or cfg.mode
    force_k = 1 if eff_mode == "bulk" else k
    decision = cost_model.decide_halo_aggregation(
        rows_local, cols, axis_size, dtype_bytes=dtype_bytes, hw=cfg.hw,
        candidate_k=candidate_k, force_k=force_k)
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op="halo_aggregation", axis=axis_name,
            nbytes=2 * decision.k * cols * dtype_bytes,
            mode=decision.mode, chunks=decision.k,
            predicted_bulk_s=decision.bulk_sweep_s,
            predicted_interleaved_s=decision.aggregated_sweep_s))
    return decision


def _ring_perm(n: int, shift: int = 1) -> list[tuple[int, int]]:
    return [(i, (i + shift) % n) for i in range(n)]


def _split(x: Array, chunks: int, axis: int = 0) -> list[Array]:
    if chunks <= 1 or x.shape[axis] % chunks != 0:
        return [x]
    return list(jnp.split(x, chunks, axis=axis))


def _ppermute_chunked(x: Array, axis_name: str, perm, chunks: int) -> Array:
    """One ring step as ``chunks`` independent collective-permutes (the
    finer-grained messages of the paper; XLA may overlap them)."""
    pieces = _split(x, chunks)
    moved = [lax.ppermute(p, axis_name, perm) for p in pieces]
    return moved[0] if len(moved) == 1 else jnp.concatenate(moved, axis=0)


# ---------------------------------------------------------------------------
# managed_all_gather
#
# Every managed collective carries a custom VJP implementing its exact
# mathematical dual as another managed collective (AG <-> RS, AR <-> AR,
# A2A <-> reverse A2A, AG-matmul <-> matmul-RS...).  This matters twice:
#  (1) memory — differentiating through the ring fori_loops would save the
#      per-step carries (O(ring_steps x operand) residuals per call);
#  (2) schedule — the backward pass stays an MDMP-interleaved ring instead
#      of whatever the loop transpose produces.
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def managed_all_gather(x: Array, axis_name: str, mode: str | None = None,
                       chunks: int | None = None) -> Array:
    """All-gather ``x`` (tiled along axis 0) across ``axis_name``."""
    return _managed_all_gather_impl(x, axis_name, mode, chunks)


def _managed_all_gather_impl(x, axis_name, mode, chunks):
    n = _axis_size(axis_name)
    if n == 1:
        return x
    eff_mode, c = _resolve("all_gather", axis_name, x, mode, chunks,
                           "all_gather")
    if eff_mode == "bulk":
        return lax.all_gather(x, axis_name, tiled=True)
    return _ring_all_gather(x, axis_name, n, c)


def _ag_fwd(x, axis_name, mode, chunks):
    return _managed_all_gather_impl(x, axis_name, mode, chunks), None


def _ag_bwd(axis_name, mode, chunks, _, dy):
    return (_managed_reduce_scatter_impl(dy, axis_name, mode, chunks),)


managed_all_gather.defvjp(_ag_fwd, _ag_bwd)


def _ring_all_gather(x: Array, axis_name: str, n: int, chunks: int) -> Array:
    idx = lax.axis_index(axis_name)
    m = x.shape[0]
    perm = _ring_perm(n)
    out = jnp.zeros((n * m,) + x.shape[1:], x.dtype)
    out = lax.dynamic_update_slice_in_dim(out, x, idx * m, axis=0)

    def body(s, carry):
        out, buf = carry
        buf = _ppermute_chunked(buf, axis_name, perm, chunks)
        src = (idx - s) % n          # buf now holds rank (idx - s)'s shard
        out = lax.dynamic_update_slice_in_dim(out, buf, src * m, axis=0)
        return out, buf

    out, _ = lax.fori_loop(1, n, body, (out, x))
    return out


# ---------------------------------------------------------------------------
# managed_reduce_scatter
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def managed_reduce_scatter(x: Array, axis_name: str,
                           mode: str | None = None,
                           chunks: int | None = None) -> Array:
    """Sum-reduce ``x`` across ``axis_name``, scattering blocks of axis 0
    (tiled): rank i receives ``sum_r x_r[i*m:(i+1)*m]``."""
    return _managed_reduce_scatter_impl(x, axis_name, mode, chunks)


def _managed_reduce_scatter_impl(x, axis_name, mode, chunks):
    n = _axis_size(axis_name)
    if n == 1:
        return x
    eff_mode, c = _resolve("reduce_scatter", axis_name, x, mode, chunks,
                           "reduce_scatter")
    if eff_mode == "bulk":
        return lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
    return _ring_reduce_scatter(x, axis_name, n, c)


def _rs_fwd(x, axis_name, mode, chunks):
    return _managed_reduce_scatter_impl(x, axis_name, mode, chunks), None


def _rs_bwd(axis_name, mode, chunks, _, dy):
    return (_managed_all_gather_impl(dy, axis_name, mode, chunks),)


managed_reduce_scatter.defvjp(_rs_fwd, _rs_bwd)


def _ring_reduce_scatter(x: Array, axis_name: str, n: int,
                         chunks: int) -> Array:
    idx = lax.axis_index(axis_name)
    assert x.shape[0] % n == 0, (
        f"reduce_scatter axis 0 ({x.shape[0]}) not divisible by {n}")
    m = x.shape[0] // n
    blocks = x.reshape((n, m) + x.shape[1:])
    perm = _ring_perm(n)

    # Block b starts at rank (b+1) and accumulates along the ring; at step s
    # rank i receives the partial of block (i-1-s) and adds its own share.
    send = lax.dynamic_index_in_dim(blocks, (idx - 1) % n, axis=0,
                                    keepdims=False)

    def body(s, buf):
        incoming = _ppermute_chunked(buf, axis_name, perm, chunks)
        blk = (idx - 1 - s) % n
        mine = lax.dynamic_index_in_dim(blocks, blk, axis=0, keepdims=False)
        return incoming + mine

    return lax.fori_loop(1, n, body, send)


# ---------------------------------------------------------------------------
# managed_all_reduce (psum)
# ---------------------------------------------------------------------------


def managed_all_reduce(x: Array, axis_name: str, *, mode: str | None = None,
                       chunks: int | None = None) -> Array:
    """Sum ``x`` across ``axis_name`` (all ranks receive the sum).
    The ring path composes the custom-VJP'd RS/AG, so its transpose is a
    flat-memory ring as well.  A non-divisible leading axis no longer
    silently demotes a forced ring to ``lax.psum``: the operand is
    zero-padded to a multiple of the axis size and sliced back after the
    AG (exact — the pad rows reduce to zero).  The one remaining psum
    fallback (0-d operands) is logged as ``mode='bulk'`` in the
    DecisionRecord so the audit trail shows the demotion."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    scalar = x.ndim == 0
    eff_mode, c = _resolve("all_reduce", axis_name, x,
                           "bulk" if scalar else mode, chunks, "all_reduce")
    if eff_mode == "bulk" or scalar:
        return lax.psum(x, axis_name)
    rows = x.shape[0]
    if rows % n != 0:
        pad = n - rows % n
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    scattered = managed_reduce_scatter(x, axis_name, eff_mode, c)
    full = managed_all_gather(scattered, axis_name, eff_mode, c)
    return full[:rows] if rows != full.shape[0] else full


# ---------------------------------------------------------------------------
# managed_all_to_all
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def managed_all_to_all(x: Array, axis_name: str, split_axis: int = 0,
                       concat_axis: int = 0, mode: str | None = None,
                       chunks: int | None = None) -> Array:
    """All-to-all: block j of ``x`` (along split_axis) goes to rank j; the
    received blocks concatenate along ``concat_axis`` in rank order."""
    return _managed_all_to_all_impl(x, axis_name, split_axis, concat_axis,
                                    mode, chunks)


def _managed_all_to_all_impl(x, axis_name, split_axis, concat_axis, mode,
                             chunks):
    n = _axis_size(axis_name)
    if n == 1:
        return x
    eff_mode, _ = _resolve("all_to_all", axis_name, x, mode, chunks,
                           "all_to_all")
    if eff_mode == "bulk":
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    return _ring_all_to_all(x, axis_name, n, split_axis, concat_axis)


def _a2a_fwd(x, axis_name, split_axis, concat_axis, mode, chunks):
    return _managed_all_to_all_impl(x, axis_name, split_axis, concat_axis,
                                    mode, chunks), None


def _a2a_bwd(axis_name, split_axis, concat_axis, mode, chunks, _, dy):
    # transpose of an all-to-all is the reverse all-to-all
    return (_managed_all_to_all_impl(dy, axis_name, concat_axis, split_axis,
                                     mode, chunks),)


managed_all_to_all.defvjp(_a2a_fwd, _a2a_bwd)


def _ring_all_to_all(x: Array, axis_name: str, n: int, split_axis: int,
                     concat_axis: int) -> Array:
    idx = lax.axis_index(axis_name)
    assert x.shape[split_axis] % n == 0
    blocks = jnp.split(x, n, axis=split_axis)     # blocks[j] -> rank j

    # Every shifted permute is independent (all source from x): the n-1
    # fine-grained messages can all be in flight at once.
    out_shape = list(blocks[0].shape)
    # received blocks stack along concat_axis in SOURCE-rank order; the
    # placement stride is the block's own concat-axis extent
    stride = out_shape[concat_axis]
    out = jnp.zeros([s if d != concat_axis else s * n
                     for d, s in enumerate(out_shape)], x.dtype)
    # My own block stays put: out[block idx] = blocks[idx] (dynamic).
    own = _dyn_block(jnp.stack(blocks), idx)
    out = lax.dynamic_update_slice_in_dim(out, own, idx * stride,
                                          axis=concat_axis)
    for s in range(1, n):
        perm = _ring_perm(n, shift=s)
        # send blocks[(idx+s) % n] to rank idx+s; receive from idx-s.
        tosend = _dyn_block(jnp.stack(blocks), (idx + s) % n)
        got = lax.ppermute(tosend, axis_name, perm)
        src = (idx - s) % n
        out = lax.dynamic_update_slice_in_dim(out, got, src * stride,
                                              axis=concat_axis)
    return out


def _dyn_block(stacked: Array, i) -> Array:
    return lax.dynamic_index_in_dim(stacked, i, axis=0, keepdims=False)


# ---------------------------------------------------------------------------
# Fused ring collectives — communication intermingled with the compute that
# produces/consumes it (the paper's Figure 3 strategy, tile-granular).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def all_gather_matmul(x: Array, w: Array, axis_name: str,
                      mode: str | None = None, chunks: int | None = None,
                      precision=None) -> Array:
    """``all_gather(x, axis) @ w`` with the gather interleaved into the
    matmul:  each ring step multiplies the block that just arrived while the
    next block is in flight.  x: [m_local, k] (sharded on axis 0 over
    ``axis_name``), w: [k, f] (replicated or TP-sharded on f).
    Returns [m_local * n, f].

    VJP (the MDMP duality): dx = matmul_reduce_scatter(dy, w^T);
    dw = gram ring (re-gather x, accumulate x_blk^T dy_blk).
    """
    return _ag_matmul_impl(x, w, axis_name, mode, chunks, precision)


def _ag_matmul_impl(x, w, axis_name, mode, chunks, precision):
    n = _axis_size(axis_name)
    if n == 1:
        return jnp.dot(x, w, precision=precision)
    flops = 2.0 * x.shape[0] * n * x.shape[1] * w.shape[1]
    compute_s = flops / get_config().hw.peak_flops
    eff_mode, c = _resolve("all_gather_matmul", axis_name, x, mode, chunks,
                           "all_gather", compute_time_s=compute_s)
    if eff_mode == "bulk":
        xg = lax.all_gather(x, axis_name, tiled=True)
        return jnp.dot(xg, w, precision=precision)

    idx = lax.axis_index(axis_name)
    m = x.shape[0]
    perm = _ring_perm(n)
    out = jnp.zeros((n * m, w.shape[1]),
                    jnp.result_type(x.dtype, w.dtype))
    out = lax.dynamic_update_slice_in_dim(
        out, jnp.dot(x, w, precision=precision).astype(out.dtype),
        idx * m, axis=0)

    def body(s, carry):
        out, buf = carry
        buf = _ppermute_chunked(buf, axis_name, perm, c)
        src = (idx - s) % n
        blockprod = jnp.dot(buf, w, precision=precision).astype(out.dtype)
        out = lax.dynamic_update_slice_in_dim(out, blockprod, src * m, axis=0)
        return out, buf

    out, _ = lax.fori_loop(1, n, body, (out, x))
    return out


def _gram_ag_ring(a: Array, b: Array, axis_name: str, mode, chunks,
                  precision) -> Array:
    """``all_gather(a, axis)^T @ b`` with the gather interleaved into the
    accumulation: dw-style gram for the ring VJPs.  a: [m_loc, p] sharded
    on axis 0; b: [n*m_loc, q] full rows.  Returns [p, q] (per-rank
    partial — the w shard's gradient needs no further reduction because
    each rank's w shard only saw its own output columns)."""
    n = _axis_size(axis_name)
    if n == 1:
        return jnp.dot(a.T, b, precision=precision)
    eff_mode, c = _resolve("gram_ag_ring", axis_name, a, mode, chunks,
                           "all_gather")
    if eff_mode == "bulk":
        ag = lax.all_gather(a, axis_name, tiled=True)
        return jnp.dot(ag.T, b, precision=precision)

    idx = lax.axis_index(axis_name)
    m = a.shape[0]
    perm = _ring_perm(n)

    def block(buf, src):
        rows = lax.dynamic_slice_in_dim(b, src * m, m, axis=0)
        return jnp.dot(buf.T, rows, precision=precision)

    acc = block(a, idx).astype(jnp.float32)

    def body(s, carry):
        acc, buf = carry
        buf = _ppermute_chunked(buf, axis_name, perm, c)
        src = (idx - s) % n
        return acc + block(buf, src).astype(jnp.float32), buf

    acc, _ = lax.fori_loop(1, n, body, (acc, a))
    return acc.astype(jnp.result_type(a.dtype, b.dtype))


def _agmm_fwd(x, w, axis_name, mode, chunks, precision):
    y = _ag_matmul_impl(x, w, axis_name, mode, chunks, precision)
    return y, (x, w)


def _agmm_bwd(axis_name, mode, chunks, precision, res, dy):
    x, w = res
    dx = _mmrs_impl(dy, w.T, axis_name, mode, chunks, precision)
    dw = _gram_ag_ring(x, dy, axis_name, mode, chunks, precision)
    return dx.astype(x.dtype), dw.astype(w.dtype)


all_gather_matmul.defvjp(_agmm_fwd, _agmm_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def all_gather_matmul_multi(x: Array, ws: Sequence[Array], axis_name: str,
                            mode: str | None = None,
                            chunks: int | None = None,
                            precision=None) -> list[Array]:
    """Like all_gather_matmul but multiplies each arriving block by SEVERAL
    weight matrices in the same ring (fused QKV / fused z,x|B,C|dt
    projections, whose outputs have different shardings and therefore can't
    live in one matrix).  One gather ring, len(ws) matmuls per step."""
    return _ag_matmul_multi_impl(x, ws, axis_name, mode, chunks, precision)


def _ag_matmul_multi_impl(x, ws, axis_name, mode, chunks, precision):
    n = _axis_size(axis_name)
    if n == 1:
        return [jnp.dot(x, w, precision=precision) for w in ws]
    total_cols = sum(w.shape[1] for w in ws)
    flops = 2.0 * x.shape[0] * n * x.shape[1] * total_cols
    compute_s = flops / get_config().hw.peak_flops
    eff_mode, c = _resolve("all_gather_matmul_multi", axis_name, x, mode,
                           chunks, "all_gather", compute_time_s=compute_s)
    if eff_mode == "bulk":
        xg = lax.all_gather(x, axis_name, tiled=True)
        return [jnp.dot(xg, w, precision=precision) for w in ws]

    idx = lax.axis_index(axis_name)
    m = x.shape[0]
    perm = _ring_perm(n)
    outs = tuple(
        jnp.zeros((n * m, w.shape[1]), jnp.result_type(x.dtype, w.dtype))
        for w in ws)

    def place(outs, buf, src):
        return tuple(
            lax.dynamic_update_slice_in_dim(
                o, jnp.dot(buf, w, precision=precision).astype(o.dtype),
                src * m, axis=0)
            for o, w in zip(outs, ws))

    outs = place(outs, x, idx)

    def body(s, carry):
        outs, buf = carry
        buf = _ppermute_chunked(buf, axis_name, perm, c)
        src = (idx - s) % n
        return place(outs, buf, src), buf

    (outs, _) = lax.fori_loop(1, n, body, (outs, x))
    return list(outs)


def _agmm_multi_fwd(x, ws, axis_name, mode, chunks, precision):
    ys = _ag_matmul_multi_impl(x, ws, axis_name, mode, chunks, precision)
    return ys, (x, tuple(ws))


def _agmm_multi_bwd(axis_name, mode, chunks, precision, res, dys):
    x, ws = res
    dx = None
    dws = []
    for w, dy in zip(ws, dys):
        d = _mmrs_impl(dy, w.T, axis_name, mode, chunks, precision)
        dx = d if dx is None else dx + d
        dws.append(_gram_ag_ring(x, dy, axis_name, mode, chunks,
                                 precision).astype(w.dtype))
    return dx.astype(x.dtype), list(dws)


all_gather_matmul_multi.defvjp(_agmm_multi_fwd, _agmm_multi_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def matmul_reduce_scatter(x: Array, w: Array, axis_name: str,
                          mode: str | None = None, chunks: int | None = None,
                          precision=None) -> Array:
    """``reduce_scatter(x @ w, axis)`` with the matmul interleaved into the
    reduction ring: each step computes only the output block about to be
    sent — the paper's "send data as soon as it has been computed".
    x: [M, k_local] with M divisible by axis size, w: [k_local, d]
    (both sharded on the contracting dim over ``axis_name``).
    Returns [M // n, d] (rank i holds block i of rows).

    VJP (duality): dx = all_gather_matmul(dy, w^T);
    dw = gram ring over dy (x^T @ AG(dy)).
    """
    return _mmrs_impl(x, w, axis_name, mode, chunks, precision)


def _mmrs_impl(x, w, axis_name, mode, chunks, precision):
    n = _axis_size(axis_name)
    if n == 1:
        return jnp.dot(x, w, precision=precision)
    flops = 2.0 * x.shape[0] * x.shape[1] * w.shape[1]
    compute_s = flops / get_config().hw.peak_flops
    eff_mode, c = _resolve("matmul_reduce_scatter", axis_name, x, mode,
                           chunks, "reduce_scatter",
                           compute_time_s=compute_s)
    if eff_mode == "bulk":
        y = jnp.dot(x, w, precision=precision)
        return lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                tiled=True)

    idx = lax.axis_index(axis_name)
    assert x.shape[0] % n == 0
    m = x.shape[0] // n
    perm = _ring_perm(n)
    acc_dtype = jnp.result_type(x.dtype, w.dtype)

    def block_prod(b):
        rows = lax.dynamic_slice_in_dim(x, b * m, m, axis=0)
        return jnp.dot(rows, w, precision=precision).astype(acc_dtype)

    send = block_prod((idx - 1) % n)

    def body(s, buf):
        incoming = _ppermute_chunked(buf, axis_name, perm, c)
        blk = (idx - 1 - s) % n
        return incoming + block_prod(blk)

    return lax.fori_loop(1, n, body, send)


def _mmrs_fwd(x, w, axis_name, mode, chunks, precision):
    y = _mmrs_impl(x, w, axis_name, mode, chunks, precision)
    return y, (x, w)


def _mmrs_bwd(axis_name, mode, chunks, precision, res, dy):
    x, w = res
    dx = _ag_matmul_impl(dy, w.T, axis_name, mode, chunks, precision)
    # dw = x^T @ AG(dy): gram ring over dy blocks against x rows
    dw = _gram_ag_ring(dy, x, axis_name, mode, chunks, precision).T
    return dx.astype(x.dtype), dw.astype(w.dtype)


matmul_reduce_scatter.defvjp(_mmrs_fwd, _mmrs_bwd)


# ---------------------------------------------------------------------------
# Managed ring attention (context parallelism)
#
# The paper's Figure-3 strategy mapped onto attention: q stays sequence-
# sharded, KV blocks rotate around the ring via ppermute while the flash
# kernel consumes the block that already arrived, merging partials with the
# online-softmax (m, l, acc) carry.  Activation memory is O(S_loc); the
# per-step transfer hides under the per-block flash once compute dominates
# the link.  ``mode='bulk'`` is the oracle: all-gather the KV and run ONE
# flash call (identical math, bulk communication).  Causal masks skip
# fully-masked future blocks (lax.cond per step — the permute stays
# outside the cond so every rank still participates in the collective).
#
# The custom VJP re-streams the backward ring: dq accumulates locally as
# KV blocks pass by again, while each block's (dk, dv) accumulator rotates
# WITH it, collecting every rank's contribution before arriving back home
# after a full cycle.  Residuals are only (q, k, v, out, lse) — O(S_loc),
# never the gathered sequence.
# ---------------------------------------------------------------------------


def _block_visible(q_off, k_off, sq: int, skv: int, causal: bool,
                   window: int):
    """Whether ANY (qpos, kpos) pair of the block survives the mask.
    Offsets may be traced (ring ranks derive them from axis_index)."""
    vis = jnp.bool_(True)
    if causal:
        vis &= k_off <= q_off + sq - 1
    if window > 0:
        vis &= (q_off - (k_off + skv - 1)) < window
    return vis


def _ring_attn_resolve(q, k, axis_name, causal, mode):
    n = _axis_size(axis_name)
    b, s_loc, h, hd = q.shape
    compute_s = ((0.5 if causal else 1.0) * n
                 * cost_model.attention_flash_step_s(
                     b, s_loc, h, hd, get_config().hw))
    eff_mode, _ = _resolve("ring_attention", axis_name, k, mode, None,
                           "all_gather", compute_time_s=compute_s)
    return eff_mode, n


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def managed_ring_attention(q: Array, k: Array, v: Array, axis_name: str,
                           causal: bool = True, window: int = 0,
                           mode: str | None = None) -> Array:
    """Sequence-sharded attention with KV streamed around ``axis_name``.

    q: [B, S_loc, H, hd]; k, v: [B, S_loc, KV, hd] — every rank holds its
    own sequence block of q AND kv (GQA via head grouping, KV <= H).
    Global positions are rank-derived: q[0] sits at ``axis_index * S_loc``.
    Returns [B, S_loc, H, hd] in q's dtype, allclose to flash attention
    over the all-gathered KV (the ``mode='bulk'`` fallback).
    """
    with dispatch_span("attention.ring", q, op="ring_attention",
                       axis=axis_name, nbytes=2 * _nbytes(k),
                       buffer="kv_blocks"):
        out, _ = _ring_attention_fwd_impl(q, k, v, axis_name, causal,
                                          window, mode)
        return out


def _ring_attention_fwd_impl(q, k, v, axis_name, causal, window, mode):
    from repro.kernels import ops as kernel_ops
    from repro.kernels.flash_attention import finalize_partials
    b, s_loc, h, hd = q.shape
    eff_mode, n = _ring_attn_resolve(q, k, axis_name, causal, mode)
    if n == 1:
        carry = kernel_ops.flash_attention_step(q, k, v, causal=causal,
                                                window=window)
        out, lse = finalize_partials(*carry, out_dtype=q.dtype)
        return out, lse
    idx = lax.axis_index(axis_name)
    # Positions only matter under a mask; keeping q_off literal 0 otherwise
    # avoids a dead axis_index chain in the bulk branch (XLA's SPMD
    # partitioner rejects a partition-id it cannot place).
    q_off = idx * s_loc if (causal or window > 0) else 0

    if eff_mode == "bulk":
        kg = lax.all_gather(k, axis_name, axis=1, tiled=True)
        vg = lax.all_gather(v, axis_name, axis=1, tiled=True)
        carry = kernel_ops.flash_attention_step(
            q, kg, vg, causal=causal, window=window, q_offset=q_off,
            k_offset=0)
        out, lse = finalize_partials(*carry, out_dtype=q.dtype)
        return out, lse

    perm = _ring_perm(n)
    from repro.kernels.flash_attention import init_partials
    m0, l0, acc0 = init_partials(b, s_loc, h, hd)

    def attend_block(carry, kb, vb, k_off):
        mc, lc, ac = carry
        return lax.cond(
            _block_visible(q_off, k_off, s_loc, s_loc, causal, window),
            lambda op: kernel_ops.flash_attention_step(
                q, op[3], op[4], (op[0], op[1], op[2]), causal=causal,
                window=window, q_offset=q_off, k_offset=k_off),
            lambda op: (op[0], op[1], op[2]),
            (mc, lc, ac, kb, vb))

    def body(s, carry):
        mc, lc, ac, kb, vb = carry
        # issue the permute FIRST: the transfer of block s+1 overlaps the
        # flash consuming block s (the MDMP intermingling).
        kb_next = lax.ppermute(kb, axis_name, perm)
        vb_next = lax.ppermute(vb, axis_name, perm)
        src = jnp.mod(idx - s, n)
        mc, lc, ac = attend_block((mc, lc, ac), kb, vb, src * s_loc)
        return mc, lc, ac, kb_next, vb_next

    mc, lc, ac, kb, vb = lax.fori_loop(0, n - 1, body,
                                       (m0, l0, acc0, k, v))
    src = jnp.mod(idx - (n - 1), n)
    mc, lc, ac = attend_block((mc, lc, ac), kb, vb, src * s_loc)
    out, lse = finalize_partials(mc, lc, ac, out_dtype=q.dtype)
    return out, lse


def _ring_attn_fwd(q, k, v, axis_name, causal, window, mode):
    out, lse = _ring_attention_fwd_impl(q, k, v, axis_name, causal, window,
                                        mode)
    return out, (q, k, v, out, lse)


def _ring_attn_bwd(axis_name, causal, window, mode, res, dy):
    from repro.kernels import ops as kernel_ops
    q, k, v, out, lse = res
    b, s_loc, h, hd = q.shape
    eff_mode, n = _ring_attn_resolve(q, k, axis_name, causal, mode)
    dsum = jnp.sum(dy.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)

    if n == 1:
        dq, dk, dv = kernel_ops.flash_attention_bwd_block(
            q, k, v, dy, lse, dsum, causal=causal, window=window)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    idx = lax.axis_index(axis_name)
    q_off = idx * s_loc if (causal or window > 0) else 0

    if eff_mode == "bulk":
        kg = lax.all_gather(k, axis_name, axis=1, tiled=True)
        vg = lax.all_gather(v, axis_name, axis=1, tiled=True)
        dq, dk_full, dv_full = kernel_ops.flash_attention_bwd_block(
            q, kg, vg, dy, lse, dsum, causal=causal, window=window,
            q_offset=q_off, k_offset=0)
        # each rank computed its q-rows' contribution to EVERY kv position;
        # the transpose of the seq all-gather sums + scatters them home
        dk = lax.psum_scatter(dk_full, axis_name, scatter_dimension=1,
                              tiled=True)
        dv = lax.psum_scatter(dv_full, axis_name, scatter_dimension=1,
                              tiled=True)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    perm = _ring_perm(n)

    def bwd_block(carry, s):
        dq, kb, vb, dkb, dvb = carry
        src = jnp.mod(idx - s, n)
        k_off = src * s_loc

        def compute(op):
            dq_c, kb_c, vb_c, dkb_c, dvb_c = op
            dq_i, dk_i, dv_i = kernel_ops.flash_attention_bwd_block(
                q, kb_c, vb_c, dy, lse, dsum, causal=causal, window=window,
                q_offset=q_off, k_offset=k_off)
            return dq_c + dq_i, kb_c, vb_c, dkb_c + dk_i, dvb_c + dv_i

        return lax.cond(
            _block_visible(q_off, k_off, s_loc, s_loc, causal, window),
            compute, lambda op: op, (dq, kb, vb, dkb, dvb))

    def body(s, carry):
        carry = bwd_block(carry, s)
        dq, kb, vb, dkb, dvb = carry
        # (dk, dv) accumulators travel WITH their block: after the full
        # cycle every rank has contributed and the sums are back home.
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        dkb = lax.ppermute(dkb, axis_name, perm)
        dvb = lax.ppermute(dvb, axis_name, perm)
        return dq, kb, vb, dkb, dvb

    init = (jnp.zeros(q.shape, jnp.float32), k, v,
            jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    dq, _, _, dk, dv = lax.fori_loop(0, n, body, init)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


managed_ring_attention.defvjp(_ring_attn_fwd, _ring_attn_bwd)


def resolve_attention_schedule(axis_name: str, axis_size: int, batch: int,
                               s_local: int, heads: int, kv_heads: int,
                               head_dim: int, d_model: int, *,
                               dtype_bytes: int = 2, causal: bool = True,
                               mode: str | None = None,
                               schedule: str | None = None
                               ) -> cost_model.AttentionScheduleDecision:
    """The managed-runtime entry for the three-way attention schedule
    (bulk sequence-gather vs ulysses a2a vs ring streaming) — the analogue
    of ``resolve_halo_aggregation`` for the transformer path.  Called at
    trace/plan time with static shapes; the chosen schedule feeds
    ``models/attention.py`` dispatch and lands in the decision log.

    ``mode='bulk'`` pins the paper-faithful unmanaged baseline;
    ``mode='interleaved'`` pins the always-stream schedule (ring);
    ``schedule`` pins an explicit choice (the tuner's measured winner).
    """
    cfg = get_config()
    pk = _plan_knob("attention_schedule", axis_name)
    if pk is not None and schedule is None and mode in (None, "auto"):
        schedule = pk.get("mode")
    eff_mode = mode or cfg.mode
    force = {"bulk": "bulk", "interleaved": "ring"}.get(eff_mode, schedule)
    decision = cost_model.decide_attention_schedule(
        batch, s_local, heads, kv_heads, head_dim, d_model, axis_size,
        dtype_bytes=dtype_bytes, causal=causal, hw=cfg.hw,
        force_schedule=force)
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op="attention_schedule", axis=axis_name,
            nbytes=2 * batch * s_local * kv_heads * head_dim * dtype_bytes,
            mode=decision.schedule, chunks=max(1, axis_size),
            predicted_bulk_s=decision.bulk_s,
            predicted_interleaved_s=decision.chosen_s))
    return decision


def resolve_pipeline_schedule(axis_name: str, axis_size: int,
                              batch_fwd_s: float, batch_bytes: float, *,
                              n_layers: int | None = None,
                              stash_cap_bytes: float | None = None,
                              candidate_micro: Sequence[int] = (4, 8, 16,
                                                                32),
                              candidate_virtual: Sequence[int] = (2,),
                              overlap_budget: float = 1.0,
                              mode: str | None = None,
                              schedule: str | None = None,
                              n_micro: int | None = None,
                              virtual: int | None = None
                              ) -> cost_model.PipelineScheduleDecision:
    """The managed-runtime entry for the pipeline-schedule knob (gpipe vs
    1f1b vs interleaved, plus the microbatch count M and virtual chunk
    factor v) — the analogue of ``resolve_halo_aggregation`` for the
    pipeline-parallel training loop.  Called at build time with static
    shapes; the chosen (schedule, M, v) feeds
    ``parallel/pipeline.build_schedule`` and lands in the decision log.

    ``mode='bulk'`` pins gpipe (the unmanaged forward-then-backward
    baseline); ``mode='interleaved'`` pins 1f1b (the always-intermingle
    schedule); ``schedule``/``n_micro``/``virtual`` pin an explicit
    choice (the tuner's measured winner).  ``overlap_budget`` is the
    instrumented readiness of the stage boundary
    (``instrument.analyze_region``) — how much of a tick's compute can
    hide the handoff bytes.  The DecisionRecord reuses ``chunks`` to
    carry the microbatch count M."""
    cfg = get_config()
    pk = _plan_knob("pipeline_schedule", axis_name)
    if pk is not None and schedule is None and n_micro is None and \
            mode in (None, "auto"):
        schedule = pk.get("mode")
        n_micro = pk.get("chunks")
        if virtual is None:
            virtual = pk.get("virtual")
    eff_mode = mode or cfg.mode
    # an EXPLICIT schedule wins over the ambient mode (same precedence as
    # cfg.attn_impl vs mdmp_mode): mode only maps to a schedule when none
    # was requested
    force = schedule if schedule is not None else \
        {"bulk": "gpipe", "interleaved": "1f1b"}.get(eff_mode)
    decision = cost_model.decide_pipeline_schedule(
        axis_size, batch_fwd_s, batch_bytes, n_layers=n_layers,
        stash_cap_bytes=stash_cap_bytes,
        candidate_micro=candidate_micro,
        candidate_virtual=candidate_virtual, hw=cfg.hw,
        overlap_budget=overlap_budget, force_schedule=force,
        force_micro=n_micro, force_virtual=virtual)
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op="pipeline_schedule", axis=axis_name,
            nbytes=int(batch_bytes / max(1, decision.n_micro)),
            mode=decision.schedule, chunks=decision.n_micro,
            predicted_bulk_s=decision.bulk_s,
            predicted_interleaved_s=decision.chosen_s))
    return decision


def resolve_serve_schedule(axis_name: str, batch_slots: int,
                           mean_prompt: float, mean_new: float,
                           n_params: float, *, dtype_bytes: int = 2,
                           max_prompt: float | None = None,
                           measured_step_s: float | None = None,
                           measured_dispatch_s: float | None = None,
                           ttft_budget_s: float | None = None,
                           mode: str | None = None,
                           schedule: str | None = None,
                           chunk: int | None = None
                           ) -> cost_model.ServeScheduleDecision:
    """The managed-runtime entry for the serving schedule (static waves vs
    continuous batching, plus the scheduling-quantum C) — the analogue of
    ``resolve_halo_aggregation`` for the serving runtime.  Called between
    engine quanta with host-side statistics; the chosen (mode, C) feeds
    ``serve/scheduler.py`` and lands in the decision log.

    ``mode='bulk'`` pins static waves (the paper-faithful unmanaged
    baseline, = the seed Generator); ``mode='interleaved'`` pins
    continuous batching; ``schedule``/``chunk`` pin an explicit choice
    (the tuner's measured winner).  Measured step/dispatch seconds from
    ``serve/metrics.py`` override the modeled roofline terms — the
    iteration-(k)->(k+1) correction.  The DecisionRecord reuses ``chunks``
    to carry C and the predicted fields to carry seconds-per-token."""
    cfg = get_config()
    pk = _plan_knob("serve_schedule", axis_name)
    if pk is not None and schedule is None and chunk is None and \
            mode in (None, "auto"):
        schedule = pk.get("mode")
        chunk = pk.get("chunks")
    eff_mode = mode or cfg.mode
    force = {"bulk": "static", "interleaved": "continuous"}.get(eff_mode,
                                                                schedule)
    decision = cost_model.decide_serve_schedule(
        n_params, batch_slots, mean_prompt, mean_new,
        max_prompt=max_prompt, dtype_bytes=dtype_bytes, hw=cfg.hw,
        measured_step_s=measured_step_s,
        measured_dispatch_s=measured_dispatch_s,
        ttft_budget_s=ttft_budget_s, force_mode=force, force_chunk=chunk)
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op="serve_schedule", axis=axis_name,
            nbytes=int(n_params) * dtype_bytes,
            mode=decision.mode, chunks=decision.chunk,
            predicted_bulk_s=1.0 / max(decision.static_tok_s, 1e-30),
            predicted_interleaved_s=1.0 / max(decision.chosen_tok_s,
                                              1e-30)))
    return decision


def resolve_preempt(axis_name: str, victim_pages: int, page_bytes: int,
                    replay_tokens: int, n_params: float, *,
                    batch_slots: int = 1, dtype_bytes: int = 2,
                    measured_step_s: float | None = None,
                    measured_pcie_bw: float | None = None,
                    chunk_bytes: int | None = None,
                    wait_s: float | None = None,
                    allow_swap: bool = True,
                    mode: str | None = None,
                    policy: str | None = None
                    ) -> cost_model.PreemptDecision:
    """The managed-runtime entry for the serving preemption knob (swap a
    victim's KV pages to host vs drop-and-recompute its prefill vs
    head-of-line wait) — the overload analogue of
    ``resolve_serve_schedule``.  Called by the engine on every
    pool-exhaustion event with the victim's geometry and the instrumented
    queue statistics; the chosen policy drives the eviction and lands in
    the decision log.

    ``mode='bulk'`` pins drop-and-recompute (the unmanaged baseline — no
    host state, every eviction re-earns its KV by replay);
    ``mode='interleaved'`` pins swap (the chunk-metered transfer path);
    an explicit ``policy`` (the tuner's measured winner or a
    ``--preempt`` pin) wins over the ambient mode.  Measured step
    seconds and swap bandwidth from ``serve/metrics.py`` override the
    modeled terms — the iteration-(k)->(k+1) correction.  The
    DecisionRecord reuses ``chunks`` to carry the victim's page count
    and the predicted fields to carry recompute-vs-chosen seconds."""
    cfg = get_config()
    pk = _plan_knob("preempt_policy", axis_name)
    if pk is not None and policy is None and mode in (None, "auto"):
        policy = pk.get("mode")
    eff_mode = mode or cfg.mode
    force = policy if policy is not None else \
        {"bulk": "recompute", "interleaved": "swap"}.get(eff_mode)
    decision = cost_model.decide_preempt(
        victim_pages, page_bytes, replay_tokens, n_params,
        step_s=measured_step_s, batch_slots=batch_slots,
        dtype_bytes=dtype_bytes, pcie_bw=measured_pcie_bw,
        chunk_bytes=chunk_bytes, wait_s=wait_s, allow_swap=allow_swap,
        hw=cfg.hw, force_policy=force)
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op="preempt_policy", axis=axis_name,
            nbytes=decision.swap_bytes,
            mode=decision.policy, chunks=decision.victim_pages,
            predicted_bulk_s=decision.recompute_s,
            predicted_interleaved_s=decision.chosen_s))
    return decision


def resolve_checkpoint(axis_name: str, step_s: float, snapshot_bytes: int,
                       *, mtbf_s: float = 1800.0,
                       measured_write_bw: float | None = None,
                       measured_ckpt_cost_s: float | None = None,
                       measured_restore_s: float | None = None,
                       mode: str | None = None,
                       interval: int | None = None
                       ) -> cost_model.CheckpointDecision:
    """The managed-runtime entry for the checkpoint-cadence knob (the
    Young/Daly interval) — the analogue of ``resolve_serve_schedule`` for
    the fault-tolerance path.  Called by ``TrainLoop`` between steps with
    the EWMA step time and checkpoint/metrics.py's measured write
    bandwidth / per-checkpoint cost; the chosen interval drives the next
    ``save_async`` and lands in the decision log.

    ``mode='bulk'`` pins the fixed ``ckpt_every=25`` baseline (the
    unmanaged cadence every prior PR shipped); an explicit ``interval``
    wins over the ambient mode (same precedence as every other managed
    knob).  The DecisionRecord reuses ``chunks`` to carry the interval
    and the predicted fields to carry overhead fractions (fixed vs
    chosen)."""
    cfg = get_config()
    pk = _plan_knob("ckpt_interval", axis_name)
    if pk is not None and interval is None and mode in (None, "auto"):
        interval = pk.get("chunks")
    eff_mode = mode or cfg.mode
    force = interval if interval is not None else (
        cost_model.CKPT_FIXED_INTERVAL if eff_mode == "bulk" else None)
    decision = cost_model.decide_checkpoint(
        step_s, snapshot_bytes, mtbf_s=mtbf_s,
        write_bw=measured_write_bw,
        ckpt_cost_s=measured_ckpt_cost_s,
        restore_s=measured_restore_s, hw=cfg.hw, force_interval=force)
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op="ckpt_interval", axis=axis_name,
            nbytes=int(snapshot_bytes),
            mode=decision.mode, chunks=decision.interval,
            predicted_bulk_s=decision.fixed_overhead,
            predicted_interleaved_s=decision.chosen_overhead))
    return decision


# ---------------------------------------------------------------------------
# Managed expert dispatch (expert parallelism)
#
# The paper's Figure-3 strategy mapped onto MoE token routing: the [E, C,
# D] capacity buffers are the declared communication, and instead of one
# bulk all_to_all each way around the expert FFN, the ring streams one
# rank-block at a time — the NEXT block's ppermute is issued before the
# current block's expert FFN runs, and each of the g capacity chunks'
# results returns home with its own permute as soon as it is computed.
# Equivalent math to a2a -> ffn -> reverse a2a (the bulk oracle); the
# wire hides under the FFN once compute dominates the link.  Plain
# autodiff streams the backward ring (every op is a linear permute, a
# dynamic slice/update, or the expert_fn the caller differentiates).
# ---------------------------------------------------------------------------


def managed_expert_stream(buffers: Array, counts: Array, axis_name: str,
                          expert_fn, *, g: int = 1) -> Array:
    """Stream expert-capacity buffers around ``axis_name``.

    buffers: [E, C, D] capacity rows of THIS rank's tokens (expert-major,
    experts sharded E_loc = E/n per rank); counts: [E] int32 valid-row
    counts (rows past the count are zero padding); ``expert_fn(block,
    valid)`` applies this rank's LOCAL experts to an [E_loc, c, D] block
    (c = C/g) with per-expert valid counts [E_loc].  Returns [E, C, D]:
    row-block e holds the processed rows of expert e for MY tokens —
    exactly ``managed_all_to_all -> ffn -> reverse managed_all_to_all``.
    """
    n = _axis_size(axis_name)
    e, c, d = buffers.shape
    if n == 1:
        return expert_fn(buffers, counts)
    assert e % n == 0, (e, n)
    eff_g = g if (g >= 1 and c % max(1, g) == 0) else 1
    cs = c // eff_g
    e_loc = e // n
    idx = lax.axis_index(axis_name)
    blocks = buffers.reshape(n, e_loc, c, d)
    cnt_blocks = counts.reshape(n, e_loc)

    _resolve("expert_stream", axis_name, buffers, "interleaved", eff_g,
             "all_to_all")
    with dispatch_span("moe.expert_stream", buffers, op="expert_stream",
                       axis=axis_name, nbytes=_nbytes(buffers),
                       chunks=eff_g, buffer="expert_buffers"):
        return _expert_stream_body(blocks, cnt_blocks, axis_name,
                                   expert_fn, n, eff_g, cs, idx)


def _expert_stream_body(blocks, cnt_blocks, axis_name, expert_fn, n,
                        eff_g, cs, idx):
    _, e_loc, c, d = blocks.shape
    e = n * e_loc

    out = None
    cur = _dyn_block(blocks, idx)
    cur_cnt = _dyn_block(cnt_blocks, idx)
    for s in range(n):
        if s + 1 < n:
            # issue the NEXT block's transfer before this block's FFN
            # (the MDMP intermingling)
            perm_fwd = _ring_perm(n, shift=s + 1)
            send_to = jnp.mod(idx + s + 1, n)
            nxt = lax.ppermute(_dyn_block(blocks, send_to), axis_name,
                               perm_fwd)
            nxt_cnt = lax.ppermute(_dyn_block(cnt_blocks, send_to),
                                   axis_name, perm_fwd)
        rets = []
        for j in range(eff_g):
            vj = jnp.clip(cur_cnt - j * cs, 0, cs)
            yj = expert_fn(cur[:, j * cs:(j + 1) * cs], vj)
            if s > 0:
                # the chunk's result returns to its source rank while the
                # next chunk's FFN runs
                yj = lax.ppermute(yj, axis_name, _ring_perm(n, shift=-s))
            rets.append(yj)
        y = rets[0] if len(rets) == 1 else jnp.concatenate(rets, axis=1)
        if out is None:
            out = jnp.zeros((e, c, d), y.dtype)
        # what arrived in the return permute: rank idx+s's experts' output
        # on MY capacity rows
        src_e = jnp.mod(idx + s, n) * e_loc
        out = lax.dynamic_update_slice_in_dim(out, y, src_e, axis=0)
        if s + 1 < n:
            cur, cur_cnt = nxt, nxt_cnt
    return out


def resolve_moe_dispatch(axis_name: str, axis_size: int, tokens_local: int,
                         d_model: int, n_experts: int, top_k: int,
                         d_ff_expert: int, *, mults: int = 3,
                         dtype_bytes: int = 2,
                         capacity_factor: float = 1.25,
                         measured_imbalance: float | None = None,
                         measured_drop_rate: float | None = None,
                         measured_occupancy: float | None = None,
                         layout: str = "ep_a2a",
                         mode: str | None = None,
                         schedule: str | None = None,
                         g: int | None = None,
                         capacity_factor_override: float | None = None
                         ) -> cost_model.MoEDispatchDecision:
    """The managed-runtime entry for the MoE dispatch knob (bulk a2a vs
    chunked-stream vs dense-fallback, plus the capacity factor) — the
    analogue of ``resolve_attention_schedule`` for expert parallelism.
    Called at trace/plan time with static shapes; the chosen (schedule,
    g, capacity_factor) feeds ``models/moe.py`` dispatch and lands in
    the decision log.  ``measured_*`` come from
    ``instrument.capture_routing`` — the runtime routing counters that
    re-resolve the schedule and the capacity online.

    ``mode='bulk'`` pins the paper-faithful unmanaged baseline;
    ``mode='interleaved'`` pins the always-stream schedule; an explicit
    ``schedule`` (the tuner's measured winner, or a pinned
    cfg.moe.dispatch) wins over the ambient mode.  The DecisionRecord
    reuses ``chunks`` to carry the stream chunk count g."""
    cfg = get_config()
    pk = _plan_knob("moe_dispatch", axis_name)
    if pk is not None and schedule is None and g is None and \
            mode in (None, "auto"):
        schedule = pk.get("mode")
        g = pk.get("chunks")
        if capacity_factor_override is None:
            capacity_factor_override = pk.get("capacity_factor")
    eff_mode = mode or cfg.mode
    force = schedule if schedule is not None else \
        {"bulk": "bulk", "interleaved": "stream"}.get(eff_mode)
    decision = cost_model.decide_moe_dispatch(
        tokens_local, d_model, n_experts, top_k, d_ff_expert, axis_size,
        mults=mults, dtype_bytes=dtype_bytes,
        capacity_factor=capacity_factor,
        measured_imbalance=measured_imbalance,
        measured_drop_rate=measured_drop_rate,
        measured_occupancy=measured_occupancy, hw=cfg.hw, layout=layout,
        force_schedule=force, force_g=g,
        force_capacity_factor=capacity_factor_override)
    if cfg.log_decisions:
        log_decision(DecisionRecord(
            op="moe_dispatch", axis=axis_name, nbytes=decision.a2a_bytes,
            mode=decision.schedule, chunks=decision.g,
            predicted_bulk_s=decision.bulk_s,
            predicted_interleaved_s=decision.chosen_s))
    return decision


# ---------------------------------------------------------------------------
# Convenience: sequence-parallel psum replacement
# ---------------------------------------------------------------------------


def managed_psum_scatter_gather(x: Array, axis_name: str, *,
                                mode: str | None = None) -> Array:
    """psum expressed as RS+AG so the two halves can straddle compute
    (Megatron-SP style); numerically identical to psum."""
    return managed_all_gather(
        managed_reduce_scatter(x, axis_name, mode=mode), axis_name,
        mode=mode)
