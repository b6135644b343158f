"""The comparison that decides ``correct`` separates the program from its
control: the reference put in the program's place and computed in the
precision below the configuration's.  At sizes a CPU test run holds the
limits of the cells' own sizes do not apply, so each number the control
reads must lie at least three times over the program's; the readings at
the cells' own sizes, and the limits set from them, are in PERF.md."""

import pytest

from bench.tests import tiny


def readings(out):
    return {k: v["value"] for k, v in out["checks"].items()}


@pytest.mark.parametrize("name,control", [
    ("halo-x1", True), ("serve-chat", "fp8"), ("train-2k", "fp8")])
def test_control_reads_three_times_the_program(name, control):
    prog = readings(tiny.drive(name))
    ctl = readings(tiny.drive(name, control=control))
    assert any(ctl[k] >= 3 * prog[k] and ctl[k] > 1e-3 for k in prog), \
        (prog, ctl)
