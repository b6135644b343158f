"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (benchmarks/paper_tables.py), plus the
measured multi-device microbenchmarks (subprocess, 8 forced host devices)
and the §Roofline table from the dry-run artifact.  Output: CSV lines
``name,us_per_call,derived``.
"""

from __future__ import annotations

import os
import subprocess
import sys


def main() -> None:
    # The measured microbenches run on 8 forced CPU host devices in their
    # own process.  Start it before this process touches JAX, and pin it
    # to the CPU, so that on an accelerator host the chip stays free for
    # this process alone.
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), os.path.join(here, ".."),
         env.get("PYTHONPATH", "")])
    child = subprocess.Popen(
        [sys.executable, os.path.join(here, "measured.py"), "--child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)

    from benchmarks import paper_tables, roofline

    rows = paper_tables.all_tables()
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")

    try:
        out, err = child.communicate(timeout=1800)
    except subprocess.TimeoutExpired:
        child.kill()
        out, err = child.communicate()
    if child.returncode != 0:
        print(f"measured_suite,0.00,ERROR: {err[-400:]}")
    else:
        for line in out.splitlines():
            if line.count(",") >= 2:
                print(line)

    # roofline table (requires the dry-run artifact)
    path = os.path.join(here, "..", "results", "dryrun.json")
    if os.path.exists(path):
        for mesh in ("16x16", "2x16x16"):
            rows = roofline.report(path, mesh=mesh)
            for name, us, derived in roofline.rows_as_csv(rows):
                print(f"{name},{us:.2f},{derived}")
    else:
        print("roofline,0.00,SKIPPED (run repro.launch.dryrun first)")


if __name__ == "__main__":
    main()
