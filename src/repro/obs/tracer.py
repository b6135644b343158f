"""mdmptrace — the zero-dependency span tracer (the SEVENTH managed
subsystem's sensor).

MDMP's contract is "implement communications optimally using information
provided by the user and data collected from instrumenting the code" —
this module is the *collecting* half at runtime granularity: the serving
runtime's per-quantum phases, train steps, checkpoint snapshot/drain/
commit, swap-outs and swap-ins, planner resolution and lint preflight
open a :class:`Span` under the ambient tracer, and the exporters
(``obs/export.py``) and the calibration ledger (``obs/calibrate.py``)
consume the resulting event stream.

One span API, two sinks.  While a ``jax.profiler`` session records
(``jax.profiler.trace`` around the run, or the benchmark's ``--trace 1``),
every ``span()`` — the shared disabled one included — also opens a
``jax.profiler.TraceAnnotation`` of the same name, so the program's
phases sit on the device trace's clock, nested as the code nests.  The
annotation carries the name and the scalar attrs of
:data:`ANNOTATED_ATTRS` only.  There is no other switch: with no
profiler session a span pays one ``TraceAnnotation.is_enabled()`` check.

Design constraints, in order:

1.  **Disabled is free.**  The default ambient tracer is a shared
    :data:`NULL` singleton whose ``span()`` returns one reusable no-op
    context manager while no profiler records — no allocation beyond the
    kwargs dict at the call site, no clock reads, no list growth.
2.  **Bounded.**  Events land in a ``deque(maxlen=capacity)`` ring so a
    week-long serve run cannot OOM the host; the drop count is kept.
3.  **Thread-correct.**  Span nesting is tracked per thread (the
    checkpoint writer thread emits drain/commit spans concurrently with
    the train loop), and the ambient tracer itself is installed on a
    thread-local exactly like ``managed.use_config`` — but with a
    process-wide default so worker threads spawned *after*
    ``install_tracer`` inherit it.

Spans carry free-form ``attrs``; the conventional keys the rest of the
repo reads are ``op`` (a ``managed.DECISION_OPS`` name — the calibration
join key), ``axis`` (mesh axis -> a per-axis comm track in the Chrome
export), ``nbytes``, ``scale`` (how many predicted units the span
covers: tokens for serve quanta, sweeps for halo, train-seconds for the
checkpoint cadence), ``buffer``/``reads``/``writes`` (measured
in-flight windows and accesses for mdmplint pass 4), and ``track`` (an
explicit export track override).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed interval on the monotonic clock.  ``t0`` is seconds on
    ``time.perf_counter`` (shared origin across threads), ``dur`` its
    length, ``depth`` the nesting depth *within its thread* at open time
    (0 = top level)."""

    name: str
    t0: float
    dur: float
    depth: int
    tid: int
    attrs: dict[str, Any]

    @property
    def t1(self) -> float:
        return self.t0 + self.dur


#: the span attrs a profiler annotation carries beside the name (scalars
#: a trace viewer shows); every other attr stays on the ring's Span only
ANNOTATED_ATTRS = ("chunk", "quantum", "step")

#: lazily-resolved ``jax.profiler.TraceAnnotation`` (False when jax is
#: unavailable) — one import, not one per span
_ANNOTATION_CLS: Any = None


def _annotation(name: str, attrs: dict[str, Any]) -> Any:
    """A profiler annotation for a span while a ``jax.profiler`` session
    records, else None (one ``is_enabled()`` check)."""
    global _ANNOTATION_CLS
    cls = _ANNOTATION_CLS
    if cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:     # no jax, no annotations
            cls = False
        _ANNOTATION_CLS = cls
    if not cls or not cls.is_enabled():
        return None
    return cls(name, **{k: attrs[k] for k in ANNOTATED_ATTRS if k in attrs})


class _NullSpan:
    """The reusable no-op context manager the disabled path hands out."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _AnnotatedNullSpan:
    """The disabled tracer's span while a profiler records: the profiler
    annotation alone, nothing on the ring."""

    __slots__ = ("_ann",)

    def __init__(self, ann: Any):
        self._ann = ann

    def __enter__(self) -> None:
        self._ann.__enter__()
        return None

    def __exit__(self, *exc: Any) -> bool:
        self._ann.__exit__(*exc)
        return False


class NullTracer:
    """The disabled tracer: every query is empty; a span is a no-op, or
    a profiler annotation while a profiler session records.  ONE shared
    instance (:data:`NULL`) serves the whole process."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, **attrs: Any) -> _NullSpan | _AnnotatedNullSpan:
        ann = _annotation(name, attrs)
        return _NULL_SPAN if ann is None else _AnnotatedNullSpan(ann)

    def spans(self) -> list[Span]:
        return []


NULL = NullTracer()


class _SpanCtx:
    """The live context manager: clocks on enter/exit, ring append on
    exit, and the profiler annotation while a profiler records.  A plain
    class (not ``contextlib.contextmanager``) keeps the per-span overhead
    to a few attribute writes and two clock reads."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "_SpanCtx":
        stack = self._tracer._stack()
        self._depth = len(stack)
        stack.append(self)
        self._ann = _annotation(self._name, self._attrs)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tracer
        tr._stack().pop()
        tr._events.append(Span(
            name=self._name, t0=self._t0, dur=t1 - self._t0,
            depth=self._depth, tid=threading.get_ident(),
            attrs=self._attrs))
        tr.n_spans += 1
        return False

    def note(self, **attrs: Any) -> None:
        """Attach attrs discovered mid-span (e.g. bytes counted while
        draining) — must be called before ``__exit__``."""
        self._attrs.update(attrs)


class Tracer:
    """The live tracer: a bounded ring of :class:`Span` events with
    per-thread nesting stacks."""

    enabled = True

    def __init__(self, capacity: int = 65536):
        self.capacity = int(capacity)
        self._events: deque[Span] = deque(maxlen=self.capacity)
        self._local = threading.local()
        self.t_origin = time.perf_counter()
        self.n_spans = 0              # total ever opened (ring may drop)

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = []
            self._local.stack = st
        return st

    # -- recording -----------------------------------------------------------

    def span(self, name: str, **attrs: Any) -> _SpanCtx:
        """``with tracer.span("serve.quantum", op="serve_schedule",
        axis="serve", nbytes=..., scale=tokens): ...``"""
        return _SpanCtx(self, name, attrs)

    # -- queries -------------------------------------------------------------

    def spans(self) -> list[Span]:
        return list(self._events)

    @property
    def dropped(self) -> int:
        """Spans the ring evicted (0 unless the run outgrew capacity)."""
        return max(0, self.n_spans - len(self._events))

    def clear(self) -> None:
        self._events.clear()
        self.n_spans = 0


# ---------------------------------------------------------------------------
# Ambient tracer — thread-local override over a process-wide default, the
# same shape as managed.use_config / managed.install_plan.
# ---------------------------------------------------------------------------

_STATE = threading.local()
_DEFAULT: NullTracer | Tracer = NULL


def get_tracer() -> NullTracer | Tracer:
    """The ambient tracer for this thread (:data:`NULL` unless one was
    installed) — the ONE call every instrumentation site makes."""
    tr = getattr(_STATE, "tracer", None)
    return tr if tr is not None else _DEFAULT


def install_tracer(tracer: NullTracer | Tracer | None) -> None:
    """Install (or clear, with None) the process-wide default tracer —
    the launcher entry point.  Worker threads (the checkpoint writer)
    see it without any per-thread setup."""
    global _DEFAULT
    _DEFAULT = tracer if tracer is not None else NULL


class use_tracer:
    """``with obs.use_tracer(Tracer()) as tr: ...`` — scoped, this
    thread only (tests; the launchers use :func:`install_tracer`)."""

    def __init__(self, tracer: NullTracer | Tracer | None):
        self._new = tracer if tracer is not None else NULL

    def __enter__(self) -> NullTracer | Tracer:
        self._old = getattr(_STATE, "tracer", None)
        _STATE.tracer = self._new
        return self._new

    def __exit__(self, *exc: Any) -> None:
        _STATE.tracer = self._old


def dispatch_span(name: str, operand: Any = None, **attrs: Any):
    """A span at a possibly-jit-traced dispatch boundary (halo solves,
    ring attention, expert streams, pipeline scans).

    When ``operand`` is an abstract jax tracer the body is being TRACED,
    not run: the span still lands (it marks the dispatch in the timeline
    and measures trace/lower time) but is tagged ``jit=True`` so the
    calibration ledger excludes it from measured ratios.  When the
    operand is concrete (eager execution) the span is a real runtime
    measurement.  The jax import is lazy and optional — the tracer core
    stays dependency-free."""
    tr = get_tracer()
    if not tr.enabled:
        return _NULL_SPAN
    if operand is not None:
        global _JAX_TRACER_CLS
        if _JAX_TRACER_CLS is None:
            try:
                from jax.core import Tracer as _JaxTracer
                _JAX_TRACER_CLS = _JaxTracer
            except Exception:   # noqa: BLE001 — no jax, no tagging
                _JAX_TRACER_CLS = ()
        if isinstance(operand, _JAX_TRACER_CLS):
            attrs["jit"] = True
    return tr.span(name, **attrs)


#: lazily-resolved jax.core.Tracer (() when jax is unavailable) — one
#: import, not one per span
_JAX_TRACER_CLS: Any = None
