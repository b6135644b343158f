"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer readers need: device-busy intervals, Pallas kernel time,
collective time and the part of it no compute hides, and idle gaps
labelled by the harness's own host spans.

All times are nanoseconds on the profiler's clock, on which host spans
(``jax.profiler.TraceAnnotation``) and device operations are aligned.  The
measured window is the host span ``bench.window`` that the harness opens
when it starts the trace and closes before it stops it.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Iterable

WINDOW_SPAN = "bench.window"
#: host spans the harness opens; gaps are labelled by these
HOST_PREFIX = "bench."
#: device operations that move data between chips
COLLECTIVE = re.compile(
    r"(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"send|recv)")
#: a device line holding one event per executed operation
OPS_LINE = "XLA Ops"
#: an op event's name is its HLO text: "%name.N = type{layout} op(...)"
HLO = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = (\(?[a-z0-9]+\[[^\]]*\])")
PALLAS = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def result(self) -> str:
        """The HLO result type an op event's name states
        ("%op.3 = bf16[24,32,128]{...} ..." -> "bf16[24,32,128]")."""
        m = HLO.match(self.name)
        return m.group(2) if m else ""

    @property
    def op(self) -> str:
        """The HLO instruction's own name without its instance number."""
        m = HLO.match(self.name)
        return m.group(1) if m else self.name

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE.match(self.op))

    @property
    def pallas(self) -> bool:
        """A Pallas kernel: an HLO custom call to ``tpu_custom_call``."""
        return PALLAS in self.name


@dataclasses.dataclass
class Trace:
    window: tuple[int, int]
    host: list[Event]              # harness host spans
    devices: list[list[Event]]     # per device: its operations, by start

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------


def _device_index(plane_name: str) -> int | None:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def from_profile(pd, devices: Iterable[int] | None = None) -> Trace:
    """A ``jax.profiler.ProfileData`` as a ``Trace``: the ``bench.*`` spans
    of the host plane, and the operations of each TPU plane's
    ``XLA Ops`` line."""
    host: list[Event] = []
    dev: dict[int, list[Event]] = {}
    want = None if devices is None else set(devices)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append(Event(ev.name, int(ev.start_ns),
                                          int(ev.end_ns)))
            continue
        idx = _device_index(plane.name)
        if idx is None or (want is not None and idx not in want):
            continue
        lines = list(plane.lines)
        ops = [l for l in lines if l.name == OPS_LINE]
        if not ops:
            continue
        dev[idx] = sorted(
            (Event(ev.name, int(ev.start_ns), int(ev.end_ns))
             for ev in ops[0].events), key=lambda e: e.start)
    wins = [e for e in host if e.name == WINDOW_SPAN]
    if not wins:
        raise ValueError("trace holds no bench.window span")
    window = (wins[0].start, wins[0].end)
    return Trace(window=window,
                 host=[e for e in host if e.name != WINDOW_SPAN],
                 devices=[dev[i] for i in sorted(dev)])


def load(path: str, devices: Iterable[int] | None = None) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), devices)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: list[tuple[int, int]], b: list[tuple[int, int]]
             ) -> list[tuple[int, int]]:
    """a minus b, both unions (sorted, disjoint)."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------


def in_window(tr: Trace, events: list[Event]) -> list[Event]:
    lo, hi = tr.window
    return [e for e in events if e.end > lo and e.start < hi]


def busy(tr: Trace, device: int) -> list[tuple[int, int]]:
    lo, hi = tr.window
    return clip(union((e.start, e.end) for e in tr.devices[device]), lo, hi)


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.devices:
        return 0.0
    return sum(length(busy(tr, d)) for d in range(len(tr.devices))) \
        / len(tr.devices) / 1e9


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / (tr.window_ns / 1e9)


def kernel_events(tr: Trace, result: str, device: int | None = None
                  ) -> list[Event]:
    """The Pallas kernel calls whose result type is ``result`` (a kernel's
    stable mark while the program names none: the HLO names its custom
    call after the enclosing function)."""
    devs = range(len(tr.devices)) if device is None else [device]
    return [e for d in devs for e in in_window(tr, tr.devices[d])
            if e.pallas and e.result == result]


def kernel_s(tr: Trace, result: str, device: int | None = None) -> float:
    """Device seconds of those kernel calls in the window, summed over
    the devices (or on ``device``)."""
    lo, hi = tr.window
    return sum(min(e.end, hi) - max(e.start, lo)
               for e in kernel_events(tr, result, device)) / 1e9


def exposed_collective_s(tr: Trace, device: int = 0) -> tuple[float, float]:
    """(collective seconds, seconds in which a collective ran on the
    device and no other operation did)."""
    lo, hi = tr.window
    evs = in_window(tr, tr.devices[device])
    coll = clip(union((e.start, e.end) for e in evs if e.collective),
                lo, hi)
    comp = clip(union((e.start, e.end) for e in evs if not e.collective),
                lo, hi)
    return length(coll) / 1e9, length(subtract(coll, comp)) / 1e9


def idle_gaps(tr: Trace, device: int = 0) -> list[tuple[int, int]]:
    lo, hi = tr.window
    return subtract([(lo, hi)], busy(tr, device))


def label(tr: Trace, t: int) -> str:
    """The innermost harness span open at ``t``, or ``none``."""
    open_ = [e for e in tr.host if e.start <= t < e.end]
    if not open_:
        return "none"
    return min(open_, key=lambda e: e.dur).name


def breakdown(tr: Trace, top: int = 10, device: int = 0) -> dict:
    """The device operations that took most time on ``device`` (by name,
    the trailing instance number dropped) and the longest idle gaps by the
    host span open in them, each as [name, seconds]."""
    lo, hi = tr.window
    per_op: dict[str, int] = defaultdict(int)
    for e in in_window(tr, tr.devices[device]):
        per_op[op_key(e)] += min(e.end, hi) - max(e.start, lo)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr, device), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[label(tr, (s + e) // 2), (e - s) / 1e9]
                          for s, e in gaps]}


def op_key(e: Event) -> str:
    """An op's name without its instance number, with its result type:
    "fusion bf16[24,8192]", "tpu_custom_call bf16[24,32,128]"."""
    if not e.result:
        return re.sub(r"\.\d+$", "", e.name)[:80]
    op = "tpu_custom_call" if e.pallas else e.op
    return f"{op} {e.result}"[:80]
