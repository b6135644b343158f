"""``bench/run.py`` prints no result and exits non-zero where there is no
accelerator, or where the kernels would not be the compiled ones."""

import os
import subprocess
import sys

from bench import harness


def run(env_extra, cwd=harness.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "halo-x1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_non_zero_on_the_cpu():
    out = run({})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_refuses_interpreted_kernels():
    out = run({"REPRO_PALLAS": "interpret"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "REPRO_PALLAS" in out.stderr
