"""Pipeline parallelism over the pod axis: schedule correctness on 8
devices — forward GPipe vs the sequential stack (incl. the uneven
stage-partition regression), loss AND grads of all three managed
schedules vs the sequential oracle, the full train-step integration, and
the auto-schedule decision trail."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import managed
from repro.parallel import pipeline
from repro.parallel.sharding import smap


def _layer_fn(x, w):
    return jnp.tanh(x @ w)


def test_forward_pipeline_matches_sequential_uneven_stages(mesh8):
    """8-stage GPipe forward over 4 microbatches with n_layers=12 (NOT a
    multiple of 8: stages 0-3 get 2 layers, 4-7 get 1) == the sequential
    stack — the seed's stage_layer_slice dropped the remainder layers."""
    rng = np.random.default_rng(0)
    d, n_layers = 16, 12
    ws = rng.normal(size=(n_layers, d, d)).astype(np.float32) * 0.3
    xs = rng.normal(size=(4, 8, d)).astype(np.float32)   # [M, B, D]

    def stage_fn_factory(n_stage):
        def stage_fn(x, params):
            chunk, per = params
            return pipeline.masked_chunk_apply(_layer_fn, chunk, per, x)
        return stage_fn

    def run(ws_all, mbs):
        sid = jax.lax.axis_index("x")
        chunk, per = pipeline.slice_chunk_params(ws_all, n_layers, 8, sid)
        out = pipeline.pipeline_apply(stage_fn_factory(8), (chunk, per),
                                      mbs, "x")
        return pipeline.select_last_stage(out, "x")

    got = jax.jit(smap(run, mesh8,
                       in_specs=(P(None), P(None)),
                       out_specs=P(None)))(jnp.asarray(ws),
                                           jnp.asarray(xs))
    want = xs
    for layer in range(n_layers):
        want = np.tanh(want @ ws[layer])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=1e-6)


def _train_problem():
    rng = np.random.default_rng(1)
    n_layers, d, m, b = 16, 16, 8, 4
    ws = jnp.asarray(rng.normal(size=(n_layers, d, d)).astype(np.float32)
                     * 0.25)
    xs = jnp.asarray(rng.normal(size=(m, b, d)).astype(np.float32))
    tg = jnp.asarray(rng.normal(size=(m, b, d)).astype(np.float32))
    return n_layers, d, m, b, ws, xs, tg


def test_training_schedules_match_sequential_oracle(mesh8):
    """gpipe == 1f1b == interleaved == sequential autodiff for loss AND
    grads, 8 stages, backward flowing through the pipeline."""
    n_layers, d, m, b, ws, xs, tg = _train_problem()

    def oracle(p):
        losses = []
        for mb in range(m):
            x = xs[mb]
            for i in range(n_layers):
                x = _layer_fn(x, p[i])
            losses.append(jnp.mean((x - tg[mb]) ** 2))
        return jnp.mean(jnp.stack(losses))

    want_loss, want_g = jax.value_and_grad(oracle)(ws)

    for name, v in (("gpipe", 1), ("1f1b", 1), ("interleaved", 2)):
        sched = pipeline.build_schedule(name, m, 8, v)
        n_virtual = 8 * sched.virtual

        def run(p, sched=sched, n_virtual=n_virtual):
            def chunk_fn(pp, q, mb, x):
                x = jnp.where(q == 0, xs[mb], x)
                cp, per = pipeline.slice_chunk_params(pp, n_layers,
                                                      n_virtual, q)
                return pipeline.masked_chunk_apply(_layer_fn, cp, per, x)

            def loss_fn(pp, y, mb):
                return jnp.mean((y - tg[mb]) ** 2)

            return pipeline.pipeline_value_and_grad(
                chunk_fn, loss_fn, p,
                jax.ShapeDtypeStruct((b, d), np.float32), sched, "x")

        loss, grads = jax.jit(smap(run, mesh8, in_specs=(P(None),),
                                   out_specs=(P(), P(None))))(ws)
        np.testing.assert_allclose(float(loss), float(want_loss),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grads), np.asarray(want_g),
                                   rtol=3e-4, atol=1e-6)


def test_train_step_pipeline_matches_dp_baseline(mesh222):
    """build_train_step with the pod axis as 1f1b pipeline stages produces
    the same loss and updated params as the hierarchical-DP baseline on
    the same global batch (mean-of-microbatch-means == global token
    mean)."""
    from repro import configs
    from repro.data.pipeline import DataConfig, SyntheticLMData
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.parallel.sharding import MeshCtx
    from repro.train.train_loop import build_train_step

    cfg = configs.get_reduced("granite-34b")
    data = SyntheticLMData(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=32, global_batch=8))
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=4)

    def one_step(pipe_mode):
        ctx = MeshCtx.from_mesh(mesh222, mdmp_mode="auto")
        model = Model(cfg, ctx)
        step, pshard, bshard = build_train_step(
            model, opt_cfg, mesh222, pipeline=pipe_mode,
            pipe_microbatches=None if pipe_mode == "none" else 2,
            global_batch=8, seq_len=32)
        params = model.init(jax.random.key(0))
        params = jax.tree.map(jax.device_put, params, pshard)
        opt = adamw_init(params, opt_cfg)
        batch = {k: jax.device_put(v, bshard[k])
                 for k, v in data.global_batch_at(0).items() if k in bshard}
        p2, _, metrics = step(params, opt, batch)
        return float(metrics["loss"]), jax.tree.leaves(p2)

    loss_dp, leaves_dp = one_step("none")
    for sched in ("gpipe", "1f1b"):
        loss_pp, leaves_pp = one_step(sched)
        # bf16 params: grads agree to accumulation-order rounding, so the
        # single AdamW step may flip sign on near-zero coordinates
        assert abs(loss_pp - loss_dp) < 1e-4 * max(1.0, abs(loss_dp))
        for a, b in zip(leaves_pp, leaves_dp):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=3 * float(opt_cfg.lr))


def test_auto_schedule_decision_trail(mesh8):
    """pipeline='auto' resolution on 8 stages lands a pipeline_schedule
    DecisionRecord whose choice builds a valid timetable."""
    managed.clear_decision_log()
    d = managed.resolve_pipeline_schedule("x", 8, 1e-4, 1 << 20,
                                          n_layers=16)
    recs = [r for r in managed.decision_log()
            if r.op == "pipeline_schedule"]
    assert recs and recs[-1].mode == d.schedule
    assert recs[-1].chunks == d.n_micro
    sched = pipeline.build_schedule(d.schedule, d.n_micro, 8, d.virtual)
    assert sched.ticks > 0
