"""chip_smoke.py on the CPU: its phase functions at reduced configs with
the Pallas kernels interpreted, and its refusal to run anywhere but a
TPU."""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")


def test_serve_phase_reduced(smoke, interpret):
    cfg = configs.get_reduced("phi4-mini-3.8b")
    res = smoke.serve_phase(cfg, n_requests=3, prompt_lens=(4, 12),
                            new_tokens=4, on_tpu=False)
    assert res["tokens"] > 3 * 4
    assert res["paged_kernel_err"] <= smoke.ATTN_TOL


def test_train_phase_reduced(smoke, interpret):
    cfg = configs.get_reduced("phi4-mini-3.8b")
    res = smoke.train_phase(cfg, seq=64, batch=2, steps=2, on_tpu=False)
    assert len(res["losses"]) == 2
    assert all(np.isfinite(res["losses"]))
    assert res["flash_kernel_err"] <= smoke.ATTN_TOL


def test_halo_phase_reduced(smoke, interpret):
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    res = smoke.halo_phase(mesh, n=64, iters=12, on_tpu=False)
    assert res["k"] >= 1
    assert res["agg_vs_bulk_err"] <= smoke.STENCIL_TOL
    assert res["stencil_kernel_err"] <= smoke.STENCIL_TOL


def test_halo_mesh_phase_one_device(smoke, interpret):
    """The four-chip path's code at a one-device mesh."""
    res = smoke.halo_mesh_phase(jax.devices()[:1], n=64, iters=12,
                                on_tpu=False)
    assert res["aggregated_vs_one_device_err"] <= smoke.STENCIL_TOL


def test_fit_train_depth_cuts_to_the_limit(smoke, monkeypatch):
    """The depth search walks down from its estimate until the compiled
    step fits the limit (compiled for the CPU here)."""
    cfg = dataclasses.replace(configs.get_reduced("phi4-mini-3.8b"),
                              n_layers=8)
    deep = smoke.fit_train_depth(cfg, seq=64, batch=2, limit=1 << 40)
    assert deep == 8
    with pytest.raises(smoke.SmokeFailure):
        smoke.fit_train_depth(cfg, seq=64, batch=2, limit=1 << 10)


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"JAX_PLATFORMS": "cpu",
                                  "REPRO_PALLAS": "off"}])
def test_refuses_without_tpu_before_any_phase(env):
    full = {k: v for k, v in os.environ.items() if k != "REPRO_PALLAS"}
    full.update(env)
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=full, capture_output=True, text=True,
                          timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""           # no phase ran, no result printed
    assert "chip_smoke:" in proc.stderr
