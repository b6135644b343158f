"""End-to-end behaviour tests (single device): training decreases loss,
the fault-tolerant loop restarts from checkpoints, resume is bit-exact,
stragglers are detected."""

import dataclasses
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.sharding import MeshCtx
from repro.train.train_loop import (TrainLoop, TrainLoopConfig,
                                    build_train_step)


def _setup(arch="granite-34b", seq=64, batch=4, lr=1e-3):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ctx = MeshCtx.from_mesh(mesh, mdmp_mode="bulk")
    cfg = configs.get_reduced(arch)
    model = Model(cfg, ctx)
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=5, total_steps=200)
    step_fn, pshard, bshard = build_train_step(model, opt_cfg, mesh)
    data = SyntheticLMData(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=seq, global_batch=batch))
    return model, opt_cfg, step_fn, pshard, bshard, data


def test_loss_decreases():
    model, opt_cfg, step_fn, pshard, bshard, data = _setup()
    params = jax.tree.map(jax.device_put, model.init(jax.random.key(0)),
                          pshard)
    opt = adamw_init(params, opt_cfg)
    losses = []
    for step in range(10):
        batch = {k: jax.device_put(v, bshard[k])
                 for k, v in data.global_batch_at(step).items()}
        params, opt, m = step_fn(params, opt, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_train_loop_fault_recovery(tmp_path):
    model, opt_cfg, step_fn, pshard, bshard, data = _setup()
    loop_cfg = TrainLoopConfig(total_steps=12, ckpt_every=4,
                               ckpt_dir=str(tmp_path / "ckpt"),
                               max_retries=3)
    boom = {"armed": True}

    def fault(step):
        if step == 6 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected node failure")

    loop = TrainLoop(step_fn, model, opt_cfg, data, loop_cfg, pshard,
                     bshard, fault_hook=fault)
    params, opt, s0 = loop.init_state()
    out = loop.run(params, opt, s0)
    assert out["step"] == 12
    assert out["restarts"] == 1
    assert all(np.isfinite(h["loss"]) for h in out["history"])


def test_resume_bit_exact(tmp_path):
    """Interrupted-and-resumed training must equal the uninterrupted run."""
    model, opt_cfg, step_fn, pshard, bshard, data = _setup()

    def run(total, ckpt_dir, resume=False):
        loop = TrainLoop(step_fn, model, opt_cfg, data,
                         TrainLoopConfig(total_steps=total, ckpt_every=4,
                                         ckpt_dir=ckpt_dir),
                         pshard, bshard)
        if resume:
            params, opt, s0 = loop.resume_or_init()
        else:
            params, opt, s0 = loop.init_state()
        return loop.run(params, opt, s0)

    full = run(8, str(tmp_path / "a"))
    _ = run(4, str(tmp_path / "b"))
    resumed = run(8, str(tmp_path / "b"), resume=True)
    for (k1, a), (k2, b) in zip(
            jax.tree_util.tree_flatten_with_path(full["params"])[0],
            jax.tree_util.tree_flatten_with_path(resumed["params"])[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(k1))


def test_straggler_detection():
    model, opt_cfg, step_fn, pshard, bshard, data = _setup()
    import time

    def slow(step):
        if step == 8:
            time.sleep(8.0)   # >> factor x EWMA even under CPU contention

    loop = TrainLoop(step_fn, model, opt_cfg, data,
                     TrainLoopConfig(total_steps=10, ckpt_every=100,
                                     ckpt_dir="/tmp/_nockpt",
                                     straggler_factor=2.0),
                     pshard, bshard, fault_hook=slow)
    params, opt, s0 = loop.init_state()
    out = loop.run(params, opt, s0)
    assert 8 in out["stragglers"]


def test_compile_cache_dir(monkeypatch):
    """The persistent compilation cache stays where JAX_COMPILATION_CACHE_DIR
    says; without it, it goes to the checkout's fixed .jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.use_compile_cache()
        assert got.endswith(".jax_cache")
        assert os.path.dirname(got) == os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_train_loop_spans(tmp_path):
    """Each step opens train.batch, then train.step holding the dispatch
    and the sync on the loss, in that order."""
    from repro.obs import Tracer, use_tracer
    model, opt_cfg, step_fn, pshard, bshard, data = _setup()
    loop = TrainLoop(step_fn, model, opt_cfg, data,
                     TrainLoopConfig(total_steps=2, ckpt_every=0,
                                     ckpt_dir=str(tmp_path / "ckpt")),
                     pshard, bshard)
    params, opt, s0 = loop.init_state()
    tr = Tracer()
    with use_tracer(tr):
        loop.run(params, opt, s0)
    spans = sorted(tr.spans(), key=lambda s: s.t0)
    assert [(s.name, s.depth) for s in spans] == [
        ("train.batch", 0), ("train.step", 0), ("train.dispatch", 1),
        ("train.sync", 1)] * 2
    assert [s.attrs["step"] for s in spans if s.name == "train.batch"] \
        == [0, 1]
