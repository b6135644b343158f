"""A run whose timed path is broken underneath comes out not correct:
once for each fault its cell can have.  The chip check is left out and
the rest of a run is driven at sizes a CPU test run holds."""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp

from bench import harness
from bench.tests import tiny


def test_halo_state_unchanged(monkeypatch):
    from repro.core import halo
    monkeypatch.setattr(halo, "jacobi_solve", lambda u0, f, *a, **k: u0)
    assert not tiny.drive("halo-x1")["correct"]


def test_halo_answer_altered(monkeypatch):
    from repro.core import halo
    orig = halo.jacobi_solve
    monkeypatch.setattr(halo, "jacobi_solve", lambda *a, **k:
                        orig(*a, **k).at[3, 5].add(1.0))
    assert not tiny.drive("halo-x1")["correct"]


def test_halo_exchange_left_out():
    """Four host devices need a process of their own."""
    code = textwrap.dedent("""
        import jax.numpy as jnp
        from repro.core import halo
        from bench.tests import tiny
        ok = tiny.drive("halo-x1", chips=4)
        halo.halo_exchange = lambda x, axis, *, halo=1, periodic=False: (
            jnp.zeros((halo,) + x.shape[1:], x.dtype),) * 2
        bad = tiny.drive("halo-x1", chips=4)
        print(ok["correct"], bad["correct"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT),
                                           str(harness.ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=harness.ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-2:] == ["True", "False"], out.stdout


def test_serve_token_altered(monkeypatch):
    from repro.models import layers
    orig = layers.greedy_sample
    monkeypatch.setattr(layers, "greedy_sample", lambda lg, ctx: (
        orig(lg, ctx) + 1) % lg.shape[-1])
    assert not tiny.drive("serve-chat")["correct"]


def test_train_state_unchanged(monkeypatch):
    from repro.train import train_loop
    monkeypatch.setattr(train_loop, "adamw_update",
                        lambda p, g, s, cfg, **kw: (p, s, {
                            "grad_norm": kw["gnorm"], "lr": 0.0 * kw["gnorm"]}))
    assert not tiny.drive("train-2k")["correct"]


def test_train_half_batch_left_out(monkeypatch):
    from repro.models.model import Model
    orig = Model.loss_sp

    def half(self, params, batch):
        lbl = batch["labels"]
        pos = jnp.arange(lbl.shape[1])[None, :]
        return orig(self, params, dict(batch, labels=jnp.where(
            pos < lbl.shape[1] // 2, lbl, -1)))

    monkeypatch.setattr(Model, "loss_sp", half)
    out = tiny.drive("train-2k")
    assert not out["correct"], out["checks"]
