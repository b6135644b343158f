"""Readings behind the limits of ``correct``: one cell over many seeds in
one process, with the program and with its control.

    python3 bench/readings.py --workload <name> --seconds <s> \\
        --control <fp8|int8|half_batch|bf16> [--control-only] \\
        --seeds <n> [<n> ...]

For each seed it runs the cell as ``bench/run.py`` does (no trace) and
prints one JSON line with the numbers compared and, where the control
runs, the control's.  Serving reads the control beside the program in the
same run (the lower precision's first choice at each served position);
training and the Jacobi solve put the control in the program's place, so
their control seeds run again (``half_batch`` plants the training fault
instead; the Jacobi control is always the bfloat16 reference).  Run it on
the chip; the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, run as bench_run  # noqa: E402


def one(cell_name: str, seed: int, seconds: float, devices, control):
    cell = harness.load_cell(cell_name)
    r = harness.Run(cell, seed=seed, seconds=seconds, trace=False,
                    devices=devices)
    r.peaks = harness.peaks(devices[0].device_kind)
    r.control = control
    out = bench_run.execute(r)
    extra = {k: r.counters[k] for k in ("control_gap", "program_gap",
                                         "losses", "ref_losses",
                                         "left_out", "checked_tokens")
             if k in r.counters}
    line = {"seed": seed, "control": control, "correct": out["correct"],
            "checks": out["checks"], "metrics": out["metrics"], **extra}
    print(json.dumps(line), flush=True)
    del r, out
    gc.collect()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control-only", action="store_true",
                    help="run only the control (or a planted fault such "
                    "as train's half_batch) on each seed")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.check_environment()
    harness.use_compile_cache()
    devices = harness.require_chips(cell.chips)
    kind = cell.traffic["kind"]
    for i, seed in enumerate(args.seeds):
        # serving reads its control in the same run as the program
        ctl = args.control if kind == "serve" else None
        if not args.control_only or kind == "serve":
            one(args.workload, seed, args.seconds, devices, ctl)
        if kind != "serve" and args.control and i < args.control_seeds:
            one(args.workload, seed, args.seconds, devices,
                True if kind == "halo" else args.control)


if __name__ == "__main__":
    main()
