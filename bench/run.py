"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from the root of a checkout.  It makes its inputs and weights from
``--seed``, warms up every shape its window uses (that is set-up), then
measures for ``--seconds`` seconds, checks what the window produced
against a plain reference, and prints one JSON line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the profiler traces the end of the window and the metrics
are the cell's per-layer metrics, read by ``bench/layer_metrics/<name>.py``.
``checks`` holds each number compared with its limit; they are also the
last lines on stderr.

It exits non-zero without printing a result when JAX finds no accelerator
or fewer chips than the cell asks for, or when ``REPRO_PALLAS`` is set to
anything but ``on``.  JAX's compile cache is kept in ``bench/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def per_layer(r: harness.Run) -> dict:
    """Each per-layer metric of the cell that its reader finds; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in r.cell.per_layer:
        value = harness.layer_reader(m["name"])(r)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(r: harness.Run) -> dict:
    dev = r.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(r.devices), "memory_peak_bytes": r.peak_bytes}
    if r.trace:
        metrics = per_layer(r)
        if r.reduced is not None:
            from bench import trace_reduce
            device["busy_s"] = trace_reduce.busy_s(r.reduced)
            device["window_s"] = r.reduced.window_ns / 1e9
    else:
        metrics = {name: {"value": v, "unit": u}
                   for name, (v, u) in r.metrics.items()}
        metrics["setup_s"] = {"value": r.setup_s, "unit": "s"}
        wanted = [m["name"] for m in r.cell.end_to_end]
        metrics = {k: metrics[k] for k in wanted if k in metrics}
    out = {"correct": bool(r.checks) and all(c.ok for c in r.checks)
           and r.failed == 0,
           "attempted": r.attempted, "failed": r.failed,
           "metrics": metrics, "device": device}
    if r.trace and r.reduced is not None and r.reduced.devices:
        from bench import trace_reduce
        out["breakdown"] = trace_reduce.breakdown(r.reduced)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in r.checks}
    return out


def execute(r: harness.Run) -> dict:
    """Drive the cell's traffic through its driver, reduce the trace, and
    return the result line (the chip check is the caller's)."""
    harness.driver(r.traffic["kind"]).run(r)
    if r.trace_path is not None:
        from bench import trace_reduce
        r.reduced = trace_reduce.load(
            r.trace_path, [d.id for d in r.devices])
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    return result(r)


def main(argv=None) -> int:
    t_start = harness.process_start_s()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.check_environment()
    harness.use_compile_cache()
    devices = harness.require_chips(cell.chips)
    r = harness.Run(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices,
                    t_start=t_start)
    r.peaks = harness.peaks(devices[0].device_kind)
    out = execute(r)
    for name, c in out["checks"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
