"""Shared pieces of the on-chip benchmark: the cell table, the chip check,
the compile cache, timing, tracing, and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``   sizes of the configuration as run;
* ``bench/traffic/<traffic>.json``  parameters of the traffic mix, whose
  ``kind`` names the driver ``bench/drivers/<kind>.py`` that runs it;
* ``bench/layer_metrics/<metric>.py`` one reader per per-layer metric.

So a new cell is new files plus entries, and no edit of these.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = BENCH / ".jax_cache"
#: traces of ``--trace 1`` runs, reduced and deleted within the run
TRACE_DIR = BENCH / ".trace"

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_start_s() -> float:
    """Wall-clock time at which this process started (Linux /proc), so
    that set-up counts the interpreter and every import."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell table
# ---------------------------------------------------------------------------


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """Whether ``cell`` reports the per-layer ``metric``: the cells it
    lists, or, where it lists none, every cell that reports the metric it
    moves (the contract allows such an entry)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = benchmark(root)
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(work)})")
    w = work[name]
    cfg_entry = next(c for c in bm["configs"] if c["name"] == w["config"])
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=load_json(root / cfg_entry["file"]),
                traffic_name=w["traffic"],
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return load_module(BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def layer_reader(metric: str) -> Callable:
    return load_module(BENCH / "layer_metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_")).read


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} "
                         f"in bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# The chip and JAX
# ---------------------------------------------------------------------------


def check_environment() -> None:
    """Refuse a run whose kernels would not be the compiled ones."""
    mode = os.environ.get("REPRO_PALLAS")
    if mode not in (None, "on"):
        raise NoChip(f"bench: REPRO_PALLAS={mode!r} would run the kernels' "
                     f"references; unset it or set it to 'on'")


def use_compile_cache(cache_dir: Path = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    caching every program, so that only a cell's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    """The first ``n`` accelerator devices; never the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("bench: JAX found no accelerator (platform 'cpu'); "
                     "the benchmark runs on the chip only")
    if len(devices) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX found "
                     f"{len(devices)}")
    return devices[:n]


def peak_bytes(devices) -> int | None:
    peaks_ = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


class CompileCounter:
    """Counts JAX compile requests (persistent-cache hits included) while
    ``armed``: the window should hold none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.total = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.total += 1
            if self.armed:
                self.count += 1


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class Run:
    """What a driver fills in: set-up time, end-to-end metrics, counters
    for the per-layer readers, the checks that decide ``correct``, and the
    traced window."""

    def __init__(self, cell: Cell, *, seed: int, seconds: float,
                 trace: bool, devices=None, t_start: float | None = None):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_start = time.time() if t_start is None else t_start
        self.setup_s: float | None = None
        self.metrics: dict[str, tuple[float, str]] = {}
        self.counters: dict[str, Any] = {}
        self.checks: list[Check] = []
        self.attempted = 0
        self.failed = 0
        self.trace_path: str | None = None
        self.reduced = None
        self.peak_bytes: int | None = None
        self.peaks: dict | None = None
        #: the control (or a planted fault) in the program's place:
        #: ``bench/readings.py`` sets it, the benchmark's runs never do
        self.control: str | bool | None = None
        self._annotation = None

    # -- timing ------------------------------------------------------------

    def setup_done(self) -> None:
        """The first timed operation starts now."""
        self.setup_s = time.time() - self.t_start

    def metric(self, name: str, value: float, unit: str) -> None:
        self.metrics[name] = (float(value), unit)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append(Check(name, float(value), float(limit)))

    # -- tracing -----------------------------------------------------------

    def start_trace(self) -> None:
        if not self.trace or self._annotation is not None:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()

    def stop_trace(self) -> None:
        if self._annotation is None:
            return
        import jax
        self._annotation.__exit__(None, None, None)
        self._annotation = "done"
        jax.profiler.stop_trace()
        found = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        self.trace_path = str(found[-1]) if found else None

    @property
    def tracing(self) -> bool:
        return self._annotation is not None and self._annotation != "done"


@contextlib.contextmanager
def span(name: str):
    """A host span on the profiler's clock, named for the idle-gap labels
    (next to free when no trace is being taken)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def rng(seed: int, *salt: int):
    """A numpy generator for one purpose of one seed."""
    import numpy as np
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def jax_key(seed: int, *salt: int):
    """A JAX key from every bit of ``seed`` (a plain ``jax.random.key``
    keeps only 32 of them)."""
    import jax
    import numpy as np
    a, b = np.random.SeedSequence([int(seed), *salt]).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a)), int(b))


def quantile(xs: list[float], q: float) -> float:
    """The q-quantile of xs by linear interpolation between order
    statistics (numpy's default), for tails over all requests."""
    s = sorted(xs)
    if not s:
        return float("nan")
    pos = q * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
