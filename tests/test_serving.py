"""Serving runtime validation (tier-1, single device).

Three layers of oracles:
  * kernel — paged-attention Pallas (interpret) == jnp page-scan engine
    == dense reference on randomized page tables;
  * cache — paged greedy decode == the contiguous-cache Generator
    (same tokens, per family);
  * scheduler — continuous batching == one-request-at-a-time decoding,
    and it finishes mixed-length queues in strictly fewer quanta than
    static waves (the deterministic form of the throughput win).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ShapeConfig
from repro.core import managed
from repro.kernels import paged_attention as paged
from repro.kernels import ref
from repro.models.model import Model
from repro.parallel.sharding import MeshCtx, infer_shardings
from repro.serve.engine import ServeEngine
from repro.serve.kv_cache import PagedCacheConfig, PageTable
from repro.train.serve_loop import Generator


# ---------------------------------------------------------------------------
# Kernel: pallas == jnp == dense on randomized page tables
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,kvh,hd,page,pmax,lens", [
    pytest.param(8, 2, 32, 8, 5, None, id="8-2-32-8-5"),      # GQA 4:1
    pytest.param(4, 4, 16, 4, 7, None, id="4-4-16-4-7"),      # MHA, small
    pytest.param(8, 1, 64, 16, 3, None, id="8-1-64-16-3"),    # MQA
    # the kernel walks blocks of 16 pages of 8 rows: 37 pages are three
    # blocks, the last partly live; slots empty, ending on a block
    # boundary, full (lens == page * pmax), and one row past a boundary
    pytest.param(8, 2, 32, 8, 37, None, id="blocks-8-2-32-8-37"),
    pytest.param(8, 2, 32, 8, 37, (0, 256, 296, 129),
                 id="edges-8-2-32-8-37"),
    # blocks of 32 pages of 4 rows; 70 is not a multiple of 32
    pytest.param(4, 4, 16, 4, 70, (0, 128, 280, 131),
                 id="edges-4-4-16-4-70"),
])
# window 150 starts the walk inside a later block of the wide tables
@pytest.mark.parametrize("window", [0, 9, 150])
def test_paged_attention_pallas_vs_jnp(h, kvh, hd, page, pmax, lens,
                                       window):
    rng = np.random.default_rng(h * 100 + page + window)
    b = 3 if lens is None else len(lens)
    npool = max(32, b * pmax)
    q = jnp.asarray(rng.normal(size=(b, h, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(npool, page, kvh, hd))
                     .astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(npool, page, kvh, hd))
                     .astype(np.float32))
    table = jnp.asarray(rng.permutation(npool)[:b * pmax]
                        .reshape(b, pmax).astype(np.int32))
    if lens is None:
        lens = rng.integers(0, page * pmax + 1, size=b)
    lens = jnp.asarray(np.asarray(lens, np.int32))
    o_jnp = paged.paged_attention_jnp(q, kp, vp, table, lens,
                                      window=window)
    o_pal = paged.paged_attention_pallas(q, kp, vp, table, lens,
                                         window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_jnp),
                               rtol=2e-5, atol=2e-5)
    # dense oracle per slot: gather the page chain contiguously
    for i in range(b):
        n = int(lens[i])
        if n == 0:
            np.testing.assert_array_equal(np.asarray(o_jnp[i]), 0.0)
            continue
        kc = np.concatenate([np.asarray(kp[int(table[i, j])])
                             for j in range(pmax)])[:n]
        vc = np.concatenate([np.asarray(vp[int(table[i, j])])
                             for j in range(pmax)])[:n]
        lo = max(0, n - window) if window else 0
        want = ref.flash_attention_ref(
            q[i:i + 1, None], jnp.asarray(kc[lo:])[None],
            jnp.asarray(vc[lo:])[None], causal=False)
        np.testing.assert_allclose(np.asarray(want)[0, 0],
                                   np.asarray(o_jnp[i]), rtol=2e-5,
                                   atol=2e-5)


def test_paged_attention_is_one_kernel_call():
    """At serve-chat's shapes the kernel is one pallas_call whose result,
    returned as is, has q's shape and dtype: the benchmark finds the
    kernel in a device trace by that result type."""
    sds = jax.ShapeDtypeStruct
    q = sds((24, 32, 128), jnp.bfloat16)
    pool = sds((3072, 8, 8, 128), jnp.bfloat16)
    closed = jax.make_jaxpr(paged.paged_attention_pallas)(
        q, pool, pool, sds((24, 128), jnp.int32), sds((24,), jnp.int32))
    calls = [e for e in closed.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    out, = calls[0].outvars
    assert closed.jaxpr.outvars == [out]
    assert (out.aval.shape, out.aval.dtype) == (q.shape, q.dtype)


def test_paged_partials_shard_merge():
    """Partials over disjoint pool shards LSE-merge to the full result —
    the distributed flash-decoding contract of attention_decode_paged."""
    from repro.kernels.flash_attention import (finalize_partials,
                                               merge_partials)
    rng = np.random.default_rng(3)
    b, h, kvh, hd, page, pmax, npool = 2, 4, 2, 16, 4, 6, 16
    q = jnp.asarray(rng.normal(size=(b, h, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(npool, page, kvh, hd))
                     .astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(npool, page, kvh, hd))
                     .astype(np.float32))
    table = jnp.asarray(rng.permutation(npool)[:b * pmax]
                        .reshape(b, pmax).astype(np.int32))
    lens = jnp.asarray(np.array([17, 23], np.int32))
    full = paged.paged_attention_jnp(q, kp, vp, table, lens)
    parts = [paged.paged_attention_partials_jnp(
        q, kp[o:o + 4], vp[o:o + 4], table, lens, pool_offset=o)
        for o in (0, 4, 8, 12)]
    acc = parts[0]
    for p in parts[1:]:
        acc = merge_partials(acc, p)
    out, _ = finalize_partials(*acc, out_dtype=q.dtype)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Engine: paged == contiguous oracle; continuous == sequential oracle
# ---------------------------------------------------------------------------


def _build(arch):
    cfg = dataclasses.replace(configs.get_reduced(arch), dtype="float32")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ctx = MeshCtx.from_mesh(mesh, mdmp_mode="bulk")
    model = Model(cfg, ctx)
    params = jax.tree.map(
        lambda a, s: jax.device_put(np.asarray(a), s),
        model.init(jax.random.key(0)),
        infer_shardings(model.param_specs(), mesh))
    return cfg, mesh, model, params


@pytest.mark.parametrize("arch", ["granite-34b", "mamba2-130m",
                                  "hymba-1-5b", "moonshot-v1-16b-a3b"])
def test_paged_generator_matches_contiguous(arch):
    """Generator(engine='paged') greedy-decodes the SAME tokens as the
    contiguous-cache oracle (dense / ssm / hybrid-with-SWA / moe
    families — moe_block_decode behind the serving engine had no
    coverage before PR 5)."""
    cfg, mesh, model, params = _build(arch)
    shape = ShapeConfig("serve", seq_len=32, global_batch=2, kind="decode")
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size - 1, size=(2, 5)) \
        .astype(np.int32)
    want = Generator(model, mesh, shape, params).generate(prompts, n_new=6)
    got = Generator(model, mesh, shape, params, engine="paged",
                    page_size=4).generate(prompts, n_new=6)
    np.testing.assert_array_equal(got, want)


def test_continuous_batching_matches_sequential_oracle():
    """Mixed-length queue through 2 continuously-batched slots decodes
    every request to the same tokens as one-request-at-a-time, in
    strictly fewer quanta than static waves, reusing freed pages."""
    cfg, mesh, model, params = _build("granite-34b")
    shape = ShapeConfig("serve", seq_len=32, global_batch=1, kind="decode")
    gen = Generator(model, mesh, shape, params)
    rng = np.random.default_rng(1)
    plens = [4, 9, 3, 7, 5, 2]
    prompts = [rng.integers(0, cfg.vocab_size - 1, size=p)
               .astype(np.int32) for p in plens]
    oracle = [gen.generate(p[None], n_new=6)[0] for p in prompts]

    def run(schedule):
        eng = ServeEngine(model, mesh, params, slots=2, max_seq=32,
                          page_size=4, schedule=schedule, chunk=4)
        rids = [eng.submit(p, 6) for p in prompts]
        res = eng.run()
        return [res[r] for r in rids], eng

    got_c, eng_c = run("continuous")
    got_s, eng_s = run("static")
    for want, gc, gs in zip(oracle, got_c, got_s):
        np.testing.assert_array_equal(gc, want)
        np.testing.assert_array_equal(gs, want)
    # the deterministic throughput win: fewer dispatched quanta for the
    # same work (static waves pad to the wave's longest request)
    assert len(eng_c.metrics.quanta) < len(eng_s.metrics.quanta), (
        eng_c.metrics.summary(), eng_s.metrics.summary())
    assert eng_c.metrics.occupancy() > eng_s.metrics.occupancy()
    # paging: the pool never had to hold all 6 requests at once
    assert eng_c.pt.high_water <= 2 * eng_c.cache_cfg.max_pages_per_seq
    assert eng_c.pt.free_pages == eng_c.cache_cfg.n_pages  # all released


def test_paged_engine_uses_pallas_kernel(monkeypatch):
    """REPRO_PALLAS=interpret routes decode attention through the Pallas
    paged kernel inside the full engine (single-shard pool fast path)."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    cfg, mesh, model, params = _build("granite-34b")
    shape = ShapeConfig("serve", seq_len=16, global_batch=1, kind="decode")
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, cfg.vocab_size - 1, size=(1, 4)) \
        .astype(np.int32)
    got = Generator(model, mesh, shape, params, engine="paged",
                    page_size=4).generate(prompts, n_new=3)
    monkeypatch.delenv("REPRO_PALLAS")
    want = Generator(model, mesh, shape, params, engine="paged",
                     page_size=4).generate(prompts, n_new=3)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# The serving runtime's spans and warm-up counter
# ---------------------------------------------------------------------------


def _span_engine(first_chunk=1, retuned_chunk=2):
    """A two-slot engine whose first decision takes ``first_chunk`` and
    every retune ``retuned_chunk`` (the measured correction pinned, so
    the test does not rest on host timing), with four queued requests."""
    cfg, mesh, model, params = _build("granite-34b")
    eng = ServeEngine(model, mesh, params, slots=2, max_seq=32,
                      page_size=4)
    sch = eng.scheduler
    decide = sch.decide

    def pinned(*a, **kw):
        first = sch.chunk is None
        decide(*a, **kw)
        sch.mode = "continuous"
        sch.chunk = first_chunk if first else retuned_chunk

    sch.decide = pinned
    rng = np.random.default_rng(3)
    for plen in (4, 3, 5, 2):
        eng.submit(rng.integers(0, cfg.vocab_size - 1, size=plen), 6)
    return eng


def test_engine_spans_nest_on_the_profiler_clock(tmp_path):
    """With no tracer installed, a profiler session alone puts the
    engine's phases in the trace, each quantum's dispatch inside it."""
    import glob
    from jax.profiler import ProfileData
    eng = _span_engine(first_chunk=2, retuned_chunk=2)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    evs: dict[str, list] = {}
    stats: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    evs.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns))
                    if ev.name == "serve.dispatch":
                        stats.setdefault(ev.name, []).append(
                            dict(ev.stats))
    quanta, dispatches = evs["serve.quantum"], evs["serve.dispatch"]
    assert len(quanta) == len(dispatches) == len(eng.metrics.quanta)
    for d0, d1 in dispatches:
        assert any(q0 <= d0 and d1 <= q1 for q0, q1 in quanta)
    # the profiler alone records, so the dispatch carries its page counts
    for st in stats["serve.dispatch"]:
        assert 0 < st["live_pages"] <= st["table_pages"] == 2 * 8
    for name in ("serve.decide", "serve.warmup", "serve.admit",
                 "serve.plan", "serve.upload", "serve.readback",
                 "serve.complete", "serve.retune"):
        assert name in evs, name


def test_engine_spans_in_order_and_warmups_counted():
    """The live tracer sees every quantum's phases in order, and the
    metrics note one warm-up at entry and one after the retune that
    changed C."""
    from repro.obs import Tracer, use_tracer
    eng = _span_engine(first_chunk=1, retuned_chunk=2)
    tr = Tracer()
    with use_tracer(tr):
        eng.run()
    assert [c for _, c in eng.metrics.warmups] == [1, 2]
    spans = sorted(tr.spans(), key=lambda s: s.t0)
    top = [s.name for s in spans if s.depth == 0]
    n = len(eng.metrics.quanta)
    assert top[:2] == ["serve.decide", "serve.warmup"]
    phases = [t for t in top[2:] if t != "serve.warmup"]
    assert phases == ["serve.admit", "serve.plan", "serve.quantum",
                      "serve.complete", "serve.retune"] * n
    # one retune moved C from 1 to 2, and the second warm-up follows it
    moved = [(s.attrs["chunk_before"], s.attrs["chunk"]) for s in spans
             if s.name == "serve.retune"
             and s.attrs["chunk_before"] != s.attrs["chunk"]]
    assert moved == [(1, 2)]
    assert top[top.index("serve.warmup", 2) - 1] == "serve.retune"
    decide = spans[0]
    assert decide.attrs == {"track": "serve", "chunk": 1,
                            "source": "prior"}
    children = [s.name for s in spans if s.depth == 1
                and s.name.startswith(("serve.upload", "serve.dispatch",
                                       "serve.readback"))]
    assert children == ["serve.upload", "serve.dispatch",
                        "serve.readback"] * n
    assert len(eng.results) == 4


def test_dispatch_span_counts_live_pages(monkeypatch):
    """Each quantum's ``serve.dispatch`` span carries the pages its first
    step walks per layer (the plan's live contexts) and the table's
    width times the slots; an untraced run does not count them."""
    from repro.obs import Tracer, use_tracer
    from repro.serve import engine as engine_mod
    eng = _span_engine(first_chunk=2, retuned_chunk=2)
    plans = []
    plan_quantum = eng.scheduler.plan_quantum

    def keep(*a, **kw):
        plans.append(plan_quantum(*a, **kw))
        return plans[-1]

    eng.scheduler.plan_quantum = keep
    tr = Tracer()
    with use_tracer(tr):
        eng.run()
    page = eng.cache_cfg.page_size
    want = [sum(-(-(int(p) + 1) // page)
                for p, n in zip(pl.pos, pl.steps) if n > 0)
            for pl in plans if pl.steps.sum() > 0]
    got = [s.attrs for s in tr.spans() if s.name == "serve.dispatch"]
    assert [a["live_pages"] for a in got] == want
    assert max(want) > 2            # some slot's context outgrew a page
    assert {a["table_pages"] for a in got} == {2 * 8}
    # untraced: the counts are never computed
    eng = _span_engine(first_chunk=2, retuned_chunk=2)

    def refuse(*a, **kw):
        raise AssertionError("page counts computed in an untraced run")

    monkeypatch.setattr(engine_mod, "live_page_range", refuse)
    eng.run()
    assert len(eng.results) == 4


# ---------------------------------------------------------------------------
# The managed decision + bookkeeping units
# ---------------------------------------------------------------------------


def test_decide_serve_schedule_model():
    from repro.core import cost_model as cm
    # mixed lengths: continuous wins; uniform: static never loses
    d = cm.decide_serve_schedule(1e8, 8, 64, 32, max_prompt=256)
    assert d.mode == "continuous" and d.predicted_speedup > 1.0
    assert f"{d.mode}:{d.chunk}" in d.tok_s
    du = cm.decide_serve_schedule(1e8, 8, 64, 32, max_prompt=64)
    assert du.static_tok_s >= max(
        v for k, v in du.tok_s.items() if k.startswith("continuous"))
    # pinning
    df = cm.decide_serve_schedule(1e8, 8, 64, 32, max_prompt=256,
                                  force_mode="static", force_chunk=5)
    assert (df.mode, df.chunk) == ("static", 5)
    # TTFT budget drops big quanta
    db = cm.decide_serve_schedule(1e8, 8, 64, 32, max_prompt=256,
                                  measured_step_s=1e-3,
                                  ttft_budget_s=0.08)
    assert db.ttft_s <= 0.08 or db.chunk == 1


def test_resolve_serve_schedule_trail_and_tuner(tmp_path):
    from repro.core.tuner import ScheduleTuner
    managed.clear_decision_log()
    d = managed.resolve_serve_schedule("serve", 8, 64, 32, 1e8,
                                       max_prompt=256)
    rec = managed.decision_log()[-1]
    assert rec.op == "serve_schedule"
    assert rec.mode == d.mode and rec.chunks == d.chunk
    # bulk mode pins the unmanaged baseline (static waves)
    with managed.use_config(managed.MDMPConfig(mode="bulk")):
        db = managed.resolve_serve_schedule("serve", 8, 64, 32, 1e8,
                                            max_prompt=256)
    assert db.mode == "static"
    # tuner: model seed, measured override, persistence, sweep
    path = str(tmp_path / "tuner.json")
    t = ScheduleTuner(path=path)
    e = t.decide_serve(8, 64, 32, int(1e8), max_prompt=256)
    assert t.next_trial(e.key) == ScheduleTuner.SERVE_CANDIDATES[0]
    t.record(e.key, "continuous", 8, 1e-4)
    t.record(e.key, "static", 8, 5e-4)
    assert (t.entries[e.key].mode, t.entries[e.key].chunks) == \
        ("continuous", 8)
    t.save()
    t2 = ScheduleTuner(path=path)
    assert t2.entries[e.key].mode == "continuous"


def test_comm_region_serve_declaration():
    from repro.core.region import CommRegion
    region = CommRegion("serving", axis_sizes={"data": 2})
    region.serve("batching", axis="data", batch_slots=8, mean_prompt=64,
                 mean_new=32, max_prompt=256, n_params=int(1e8),
                 dtype=jnp.bfloat16)
    plan = region.plan(lambda x: x + 1, np.zeros(4, np.float32))
    assert plan.mode_for("batching") in ("static", "continuous")
    assert plan.chunks_for("batching") >= 1


def test_page_table_free_list():
    cfg = PagedCacheConfig(slots=2, page_size=4, n_pages=6,
                           max_pages_per_seq=3)
    pt = PageTable(cfg)
    pt.ensure(0, 9)                     # 3 pages
    pt.ensure(1, 1)                     # 1 page
    assert pt.pages_held(0) == 3 and pt.pages_held(1) == 1
    assert pt.free_pages == 2
    assert sorted(pt.table[0].tolist()) == [0, 1, 2]
    pt.release(0)
    assert pt.free_pages == 5
    pt.ensure(1, 12)                    # grows to 3, reuses freed pages
    assert pt.pages_held(1) == 3 and pt.free_pages == 3
    assert pt.high_water == 4
    assert not pt.can_fit(16) and pt.can_fit(12)
