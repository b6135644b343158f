"""Trace-time data-access instrumentation — the paper's read/write tracking.

MDMP instruments every read and write of communicated data inside a
communication region, and uses the counts from iteration k to schedule
iteration k+1 ("launch the communication of that data once it is ready").

On TPU the schedule is static, so the *same information* is extracted at
trace time by walking the jaxpr of the region: for each tracked operand we
count consuming equations (reads), producing equations along its def-use
chain (writes), and the program depth at which the last write / first read
occurs.  ``readiness`` — how early a send operand is fully produced, or how
late a receive operand is first consumed — is exactly what the managed
scheduler needs to know how much compute is available to hide the message.

This costs nothing at runtime (the paper's Table 1 shows its runtime
counters cost ~10-20x on STREAM; the trace-time equivalent is free), which
we report as a TPU-model advantage in EXPERIMENTS.md §Paper-repro.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore


@dataclasses.dataclass
class AccessRecord:
    """Read/write profile of one tracked operand inside a region."""
    label: str
    reads: int = 0
    writes: int = 0
    first_read_depth: int | None = None
    last_write_depth: int | None = None

    def readiness(self, total_depth: int) -> float:
        """For send operands: fraction of the region's program that runs
        *before* the operand is fully produced (0 = ready immediately,
        1 = ready only at the end — no overlap opportunity)."""
        if total_depth <= 0 or self.last_write_depth is None:
            return 0.0
        return self.last_write_depth / total_depth

    def consumption_slack(self, total_depth: int) -> float:
        """For recv operands: fraction of the region that runs before the
        first read (1 = consumed only at the end — maximal overlap)."""
        if total_depth <= 0 or self.first_read_depth is None:
            return 1.0
        return self.first_read_depth / total_depth


#: jaxpr primitives that move bytes across a mesh axis.  ``psum`` carries
#: its axes under ``axes`` (names, unlike reduce_sum's int dims); the rest
#: under ``axis_name`` (a bare name or a tuple of names).
COLLECTIVE_PRIMS = {"psum", "all_gather", "all_to_all", "ppermute",
                    "reduce_scatter", "psum_scatter"}


@dataclasses.dataclass(frozen=True)
class CollectiveRecord:
    """One collective equation extracted from the region's jaxpr — the
    planner needs the mesh axis and the payload bytes to serialise link
    contention across ops sharing that axis.  ``trips`` is the static
    trip count of the enclosing ``scan`` nest (a ppermute inside a ring
    body executes ``length`` times per region run but is ONE logical
    site); ``source`` is the user-code ``file:line`` the equation traces
    to, for the static verifier's diagnostics."""
    primitive: str                 # jaxpr primitive name ("psum", ...)
    axis: str                      # mesh axis the bytes cross
    nbytes: int                    # payload bytes (sum of array operands)
    depth: int                     # program depth of the equation
    trips: int = 1                 # executions per region run (scan nest)
    source: str = ""               # user-frame "file:line" provenance


@dataclasses.dataclass
class RegionReport:
    records: dict[str, AccessRecord]
    total_eqns: int
    collectives: list[CollectiveRecord] = dataclasses.field(
        default_factory=list)

    def overlap_budget(self, label: str) -> float:
        """Fraction of the region's equations available to overlap the
        communication of ``label`` (sends: after last write; recvs: before
        first read)."""
        rec = self.records[label]
        if rec.writes > 0:
            return 1.0 - rec.readiness(self.total_eqns)
        return rec.consumption_slack(self.total_eqns)

    def collective_bytes_by_axis(self) -> dict[str, int]:
        """Total extracted payload bytes per mesh axis (one logical site
        inside a scanned ring body contributes ``nbytes * trips`` — the
        bytes a full region run actually moves)."""
        out: dict[str, int] = {}
        for c in self.collectives:
            out[c.axis] = out.get(c.axis, 0) + c.nbytes * max(1, c.trips)
        return out


def _collective_axes(eqn) -> tuple[str, ...]:
    """Mesh axis names of one collective eqn (normalised to a tuple)."""
    ax = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if isinstance(ax, str):
        return (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _source_of(eqn) -> str:
    """Repo-relative ``file:line`` of the user frame this eqn traces to
    (empty when source info is unavailable — e.g. synthetic jaxprs)."""
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return ""
    fn = frame.file_name
    for marker in ("src/repro/", "tests/", "benchmarks/", "examples/"):
        i = fn.find(marker)
        if i >= 0:
            fn = fn[i:]
            break
    return f"{fn}:{frame.start_line}"


def _walk(jaxpr: jcore.Jaxpr, tracked: dict[Any, str],
          records: dict[str, AccessRecord], depth0: int,
          collectives: list[CollectiveRecord] | None = None,
          trips: int = 1) -> int:
    """Walk eqns, propagating tracked vars through aliasing ops; returns the
    depth after this jaxpr.  When ``collectives`` is given, every collective
    eqn (psum / all_gather / all_to_all / ppermute / reduce_scatter) is
    recorded ONCE per logical site with its mesh axis name, payload bytes,
    and ``trips`` — the product of enclosing static scan lengths (a ring
    body's ppermute runs ``length`` times per region execution)."""
    depth = depth0
    alias_prims = {"convert_element_type", "reshape", "transpose",
                   "squeeze", "broadcast_in_dim", "copy", "pjit",
                   "custom_jvp_call", "custom_vjp_call", "remat",
                   "checkpoint",
                   # jax >= 0.4 names the staged-out custom-derivative
                   # call sites *_jaxpr; the fwd body rides in fun_jaxpr
                   "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"}
    def _raw(p):
        return p.jaxpr if isinstance(p, jcore.ClosedJaxpr) else (
            p if isinstance(p, jcore.Jaxpr) else None)

    for eqn in jaxpr.eqns:
        depth += 1
        if collectives is not None and \
                eqn.primitive.name in COLLECTIVE_PRIMS:
            nbytes = sum(
                int(v.aval.size) * v.aval.dtype.itemsize
                for v in eqn.invars
                if not isinstance(v, jcore.Literal)
                and getattr(v.aval, "shape", None) is not None)
            for ax in _collective_axes(eqn):
                collectives.append(CollectiveRecord(
                    primitive=eqn.primitive.name, axis=ax,
                    nbytes=nbytes, depth=depth, trips=trips,
                    source=_source_of(eqn)))
        # (sub-jaxpr, outer operands aligned to its invars, trip multiplier).
        # while's two jaxprs bind DIFFERENT operand subsets (cond_consts +
        # carry vs body_consts + carry); cond's first invar is the branch
        # index, bound by no branch; everything else binds eqn.invars
        # positionally.  A scan body executes ``length`` times — its
        # collectives are one logical site each with that trip count
        # (while trip counts are dynamic: the multiplier stays 1).
        sub_trips = trips
        if eqn.primitive.name == "scan":
            sub_trips = trips * max(1, int(eqn.params.get("length", 1)))
        sub_jaxprs = []
        if eqn.primitive.name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            ops = list(eqn.invars)
            carry = ops[cn + bn:]
            sub_jaxprs.append((_raw(eqn.params["cond_jaxpr"]),
                               ops[:cn] + carry))
            sub_jaxprs.append((_raw(eqn.params["body_jaxpr"]),
                               ops[cn:cn + bn] + carry))
        else:
            default_ops = list(eqn.invars)
            if eqn.primitive.name == "cond":
                default_ops = default_ops[1:]
            for param in eqn.params.values():
                if _raw(param) is not None:
                    sub_jaxprs.append((_raw(param), default_ops))
                elif isinstance(param, (tuple, list)):
                    # cond carries its branches as a tuple of ClosedJaxprs
                    for p in param:
                        if _raw(p) is not None:
                            sub_jaxprs.append((_raw(p), default_ops))
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                continue
            label = tracked.get(v)
            if label is not None:
                rec = records[label]
                rec.reads += 1
                if rec.first_read_depth is None:
                    rec.first_read_depth = depth
        # Writes: an eqn that *produces* a tracked value.  We propagate
        # tracking through pure aliasing ops and in-place-style updates
        # (dynamic_update_slice, add into accumulators is NOT aliasing).
        if eqn.primitive.name in alias_prims or \
                eqn.primitive.name == "dynamic_update_slice":
            for vin in eqn.invars:
                if not isinstance(vin, jcore.Literal) and vin in tracked:
                    label = tracked[vin]
                    for vout in eqn.outvars:
                        tracked[vout] = label
                    rec = records[label]
                    rec.writes += 1
                    rec.last_write_depth = depth
                    break
        # Recurse into sub-jaxprs (scan/while/cond/pjit bodies): map tracked
        # outer vars to inner binders positionally.  Binders pair with the
        # UNFILTERED operand list — a Literal operand still consumes its
        # binder position (that binder is literal-bound and simply never
        # tracked); filtering literals out first would slide every later
        # binder onto the wrong outer operand.  Only ``sub.invars`` bind
        # eqn operands: constvars are closure constants (ClosedJaxpr
        # consts), and zipping them in front would slide every scan
        # carry/xs binder onto the wrong outer operand.
        for sub, operands in sub_jaxprs:
            inner_tracked = dict()
            for inner_v, outer_v in zip(list(sub.invars), operands):
                if isinstance(outer_v, jcore.Literal):
                    continue
                if outer_v in tracked:
                    inner_tracked[inner_v] = tracked[outer_v]
            # collective extraction must see EVERY sub-jaxpr (a shard_map
            # body's collectives exist whether or not a tracked operand
            # threads into it); access tracking still needs inner binders.
            if inner_tracked or collectives is not None:
                depth = _walk(sub, {**tracked, **inner_tracked}, records,
                              depth, collectives, sub_trips)
    return depth


# ---------------------------------------------------------------------------
# MoE routing statistics — the data-dependent communication counters
# ---------------------------------------------------------------------------
#
# Unlike halo/attention/pipeline traffic, MoE dispatch bytes are decided by
# a ROUTER at runtime: the trace-time jaxpr walk above cannot see them.
# This is exactly the case where the paper's runtime read/write counters
# earn their keep, so the routing path gets true runtime instrumentation:
# ``moe_routing_stats`` is traceable (cheap — one histogram per layer) and
# ``capture_routing`` records host-side summaries that feed the
# iteration-(k)->(k+1) capacity/schedule re-resolution
# (cost_model.decide_moe_dispatch's measured_* inputs).


@dataclasses.dataclass
class RoutingRecord:
    """Host-side routing profile of one MoE dispatch call site."""
    label: str
    n_experts: int
    capacity: int
    tokens: int
    top_k: int
    histogram: np.ndarray          # [E] routed (t, k) assignments
    drop_rate: float               # fraction of assignments over capacity
    occupancy: float               # kept rows / (E * C) buffer slots
    imbalance: float               # max expert load / mean expert load


def moe_routing_stats(top_idx, n_experts: int, capacity: int) -> dict:
    """Routing statistics from a router's top-k expert ids [T, K]
    (traceable — returns jnp values usable inside jit):

      histogram [E]   assignments per expert,
      drop_rate []    fraction of (t, k) assignments past capacity,
      occupancy []    realised buffer occupancy (kept / E*C),
      imbalance []    max load / mean load (feeds the capacity-factor
                      re-resolution: cf >= imbalance drops nothing).
    """
    flat = top_idx.reshape(-1)
    # scatter-add histogram: O(T*K), not the O(T*K*E) one-hot blow-up
    hist = jnp.zeros(n_experts, jnp.float32).at[flat].add(1.0)
    kept = jnp.minimum(hist, float(capacity))
    total = jnp.maximum(jnp.float32(flat.shape[0]), 1.0)
    mean_load = jnp.maximum(jnp.mean(hist), 1e-9)
    return {
        "histogram": hist,
        "drop_rate": 1.0 - jnp.sum(kept) / total,
        "occupancy": jnp.sum(kept) / float(n_experts * capacity),
        "imbalance": jnp.max(hist) / mean_load,
    }


_ROUTING_LOG: list[RoutingRecord] = []


def capture_routing(label: str, top_idx, n_experts: int,
                    capacity: int) -> RoutingRecord:
    """Summarise CONCRETE routed ids and append to the routing log (the
    runtime counter readout: benchmarks/tuners call this on a sampled
    batch between steps, then hand ``imbalance``/``drop_rate`` back to
    ``managed.resolve_moe_dispatch``)."""
    t, k = np.asarray(top_idx).shape
    stats = jax.tree.map(np.asarray,
                         moe_routing_stats(jnp.asarray(top_idx), n_experts,
                                           capacity))
    rec = RoutingRecord(
        label=label, n_experts=n_experts, capacity=capacity, tokens=t,
        top_k=k, histogram=stats["histogram"],
        drop_rate=float(stats["drop_rate"]),
        occupancy=float(stats["occupancy"]),
        imbalance=float(stats["imbalance"]))
    _ROUTING_LOG.append(rec)
    return rec


def routing_log() -> list[RoutingRecord]:
    return list(_ROUTING_LOG)


def clear_routing_log() -> None:
    _ROUTING_LOG.clear()


def analyze_region(fn: Callable, *example_args: Any,
                   tracked_args: Sequence[int | str] | None = None,
                   labels: Sequence[str] | None = None) -> RegionReport:
    """Trace ``fn`` and produce read/write records for the tracked inputs.

    ``tracked_args``: indices into the flattened argument list (default:
    all array arguments).  ``labels``: names for the report.
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    jaxpr = closed.jaxpr
    flat_invars = list(jaxpr.invars)
    if tracked_args is None:
        tracked_args = list(range(len(flat_invars)))
    if labels is None:
        labels = [f"arg{i}" for i in tracked_args]

    tracked: dict[Any, str] = {}
    records: dict[str, AccessRecord] = {}
    for i, label in zip(tracked_args, labels):
        tracked[flat_invars[i]] = label
        records[label] = AccessRecord(label=label)

    collectives: list[CollectiveRecord] = []
    total = _walk(jaxpr, tracked, records, 0, collectives)
    return RegionReport(records=records, total_eqns=total,
                        collectives=collectives)
