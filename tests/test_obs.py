"""mdmptrace — the observability subsystem (repro.obs): the metrics
registry primitives, the span tracer (nesting, bounded ring, thread
correctness, disabled-is-free), the Chrome-trace export golden schema,
the predicted-vs-measured calibration ledger (perfect run -> ratio 1.0,
2x skew flagged, jit-trace spans excluded, covering attribution), the
Recalibrator warmup/drift policy, capture_decisions scoping, and the
metrics classes' migration onto the shared registry."""

import json
import threading
import time

import pytest

from repro import obs
from repro.core import managed
from repro.obs.calibrate import (CalibrationLedger, Recalibrator,
                                 chosen_predicted_s, cover_with)
from repro.obs.export import (measured_windows, to_chrome_trace,
                              trace_tracks)
from repro.obs.registry import (Counter, Ewma, Extremum, Histogram,
                                MetricsRegistry)
from repro.obs.tracer import (NULL, Span, Tracer, dispatch_span,
                              get_tracer, use_tracer)


def _rec(op="halo_aggregation", axis="x", *, mode="interleaved",
         bulk=2e-3, inter=1e-3, nbytes=1024, chunks=4):
    return managed.DecisionRecord(
        op=op, axis=axis, nbytes=nbytes, mode=mode, chunks=chunks,
        predicted_bulk_s=bulk, predicted_interleaved_s=inter)


def _span(name, t0, dur, **attrs):
    return Span(name=name, t0=t0, dur=dur, depth=0, tid=0, attrs=attrs)


# -- registry primitives ----------------------------------------------------


def test_counter_gauge():
    c = Counter()
    assert c.value == 0
    c.add(3)
    c.add(2.5)
    c.add()
    assert c.value == 6.5


def test_extremum_min_max_and_empty():
    lo = Extremum(kind="min")
    hi = Extremum(kind="max")
    assert lo.value is None and hi.value is None
    for v in (3.0, 1.0, 2.0):
        lo.observe(v)
        hi.observe(v)
    assert lo.value == 1.0 and hi.value == 3.0 and lo.count == 3


def test_ewma_update_and_drift():
    e = Ewma(alpha=0.5)
    assert e.value is None and e.drift_frac(1.0) == 0.0
    e.update(1.0)
    assert e.drift_frac(None) == float("inf")   # no baseline trips
    assert e.value == 1.0
    e.update(3.0)
    assert e.value == pytest.approx(2.0)
    assert e.drift_frac(1.0) == pytest.approx(1.0)
    assert e.drift_frac(2.0) == pytest.approx(0.0)


def test_histogram_percentiles():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    assert h.mean == pytest.approx(50.5)
    assert h.percentile(0.5) == pytest.approx(50.0)
    assert h.percentile(0.99) == pytest.approx(99.0)
    assert h.median == h.percentile(0.5)


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    c1 = reg.counter("a.b")
    assert reg.counter("a.b") is c1        # same name -> same metric
    with pytest.raises(AssertionError):
        reg.histogram("a.b")               # name reuse across kinds
    reg.extremum("m", kind="min").observe(2.0)
    snap = reg.snapshot()
    assert snap["m"]["value"] == 2.0 and "a.b" in snap


# -- tracer -----------------------------------------------------------------


def test_span_nesting_depth_and_order():
    tr = Tracer()
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    spans = tr.spans()
    # inner closes first; depths reflect nesting within the thread
    assert [(s.name, s.depth) for s in spans] == [("inner", 1),
                                                  ("outer", 0)]
    inner, outer = spans
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
    assert outer.attrs == {"k": 1}


def test_ring_bounded_and_drop_count():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    assert len(tr.spans()) == 4 and tr.n_spans == 10 and tr.dropped == 6
    assert [s.attrs["i"] for s in tr.spans()] == [6, 7, 8, 9]


def test_disabled_is_free_shared_noop():
    assert get_tracer() is NULL
    a = NULL.span("x", big=list(range(100)))
    b = NULL.span("y")
    assert a is b                          # ONE reusable no-op object
    with a:
        pass
    assert NULL.spans() == [] and dispatch_span("z") is a


def _profiled_host_events(logdir):
    """name -> [(start_ns, end_ns, stats)] of a trace's host events."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def test_spans_annotate_the_profiler_only_while_it_records(tmp_path):
    jax = pytest.importorskip("jax")
    tr = Tracer()
    with NULL.span("serve.quantum", chunk=2), tr.span("serve.dispatch"):
        pass
    # no profiler session: the disabled span is the shared no-op
    assert NULL.span("serve.quantum") is NULL.span("x")
    with jax.profiler.trace(str(tmp_path)):
        with NULL.span("serve.quantum", chunk=2, quantum=7,
                       op="serve_schedule") as sp:
            assert sp is None
            with tr.span("serve.dispatch", step=3):
                pass
    evs = _profiled_host_events(tmp_path)
    (q0, q1, qstats), = evs["serve.quantum"]
    (d0, d1, dstats), = evs["serve.dispatch"]
    assert q0 <= d0 <= d1 <= q1
    # the annotation carries the scalar attrs it names, and no others
    assert qstats == {"chunk": 2, "quantum": 7} and dstats == {"step": 3}
    # the live tracer's ring records with or without the profiler
    assert [s.name for s in tr.spans()] == ["serve.dispatch"] * 2


def test_use_tracer_scoped_and_note():
    tr = Tracer()
    with use_tracer(tr):
        assert get_tracer() is tr
        with tr.span("s") as sp:
            sp.note(nbytes=42)
    assert get_tracer() is NULL
    assert tr.spans()[0].attrs["nbytes"] == 42


def test_tracer_thread_correct_depths():
    tr = Tracer()

    def worker():
        with tr.span("w.outer"):
            with tr.span("w.inner"):
                time.sleep(0.001)

    with tr.span("main.outer"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    d = {s.name: s.depth for s in tr.spans()}
    # the worker's nesting starts at 0 in ITS thread, regardless of the
    # main thread's open span
    assert d == {"main.outer": 0, "w.outer": 0, "w.inner": 1}
    tids = {s.name: s.tid for s in tr.spans()}
    assert tids["w.inner"] != tids["main.outer"]


def test_dispatch_span_tags_jit_trace_time():
    jax = pytest.importorskip("jax")
    tr = Tracer()
    with use_tracer(tr):

        @jax.jit
        def f(x):
            with dispatch_span("inside", x, op="halo_aggregation"):
                return x + 1

        f(1.0)
        with dispatch_span("eager", 2.0, op="halo_aggregation"):
            pass
    tagged = {s.name: s.attrs.get("jit") for s in tr.spans()}
    assert tagged == {"inside": True, "eager": None}


# -- Chrome-trace export golden schema --------------------------------------


def test_chrome_trace_schema():
    tr = Tracer()
    with use_tracer(tr):
        with tr.span("train.step", track="compute", step=0):
            with tr.span("halo.solve", op="halo_aggregation", axis="x",
                         nbytes=64, scale=10):
                pass
    rec = _rec()
    managed.log_decision(rec)
    doc = to_chrome_trace(tr, [rec])
    json.loads(json.dumps(doc))            # round-trips as plain JSON

    events = doc["traceEvents"]
    tracks = trace_tracks(doc)
    assert set(tracks.values()) >= {"decisions", "compute", "comm:x"}
    assert tracks[0] == "decisions"

    metas = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert events[: len(metas)] == metas   # metadata first
    assert {e["name"] for e in xs} == {"train.step", "halo.solve"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and "args" in e
    # the comm span landed on its axis track with its attrs as args
    halo = next(e for e in xs if e["name"] == "halo.solve")
    assert tracks[halo["tid"]] == "comm:x"
    assert halo["args"]["nbytes"] == 64 and halo["args"]["depth"] == 1
    # nesting invariant survives the us conversion
    step = next(e for e in xs if e["name"] == "train.step")
    assert step["ts"] <= halo["ts"]
    assert halo["ts"] + halo["dur"] <= step["ts"] + step["dur"] + 1e-6

    # the only instants are the decisions, on their own track
    dec = [e for e in instants if e["tid"] == 0]
    assert dec == instants
    assert len(dec) == 1 and dec[0]["name"] == "decision:halo_aggregation"
    assert dec[0]["args"]["predicted_bulk_s"] == rec.predicted_bulk_s
    assert dec[0]["args"]["predicted_interleaved_s"] \
        == rec.predicted_interleaved_s
    assert doc["otherData"]["n_decisions"] == 1


def test_measured_windows_from_spans():
    spans = [
        _span("swap", 10.0, 1.0, buffer="kv"),
        _span("quantum", 10.25, 0.5, reads="kv", writes=["logits"]),
    ]
    inflight, accesses = measured_windows(spans)
    assert inflight == [("kv", 0.0, 1.0, "swap")]
    assert ("kv", pytest.approx(0.5), "read", "quantum") in [
        (b, t, a, l) for b, t, a, l in accesses]
    assert [a for a in accesses if a[0] == "logits"][0][2] == "write"


# -- calibration ledger -----------------------------------------------------


def test_chosen_prediction_bulk_vs_interleaved():
    assert chosen_predicted_s(_rec(op="fsdp_gather", mode="bulk")) == 2e-3
    assert chosen_predicted_s(
        _rec(op="fsdp_gather", mode="interleaved")) == 1e-3
    # resolver ops store the CHOSEN prediction in interleaved_s
    assert chosen_predicted_s(
        _rec(op="serve_schedule", mode="static")) == 1e-3


def test_calibration_perfect_run_ratio_one():
    led = CalibrationLedger()
    led.correlate([_span("halo.solve", 0.0, 1e-2,
                         op="halo_aggregation", axis="x", scale=10)],
                  [_rec()])               # predicted 1e-3/unit, 10 units
    assert led.coverage() == 1.0
    assert led.ratios()[("halo_aggregation", "x")] \
        == pytest.approx(1.0, rel=1e-6)
    assert led.miscalibrated() == {}
    assert "MISCALIBRATED" not in led.report()


def test_calibration_2x_skew_flagged_with_term():
    led = CalibrationLedger()
    led.correlate([_span("halo.solve", 0.0, 2e-2,
                         op="halo_aggregation", axis="x", scale=10)],
                  [_rec()])
    assert led.miscalibrated()[("halo_aggregation", "x")] \
        == pytest.approx(2.0)
    rep = led.report()
    assert "MISCALIBRATED(+100%)" in rep
    assert "decide_halo_aggregation" in rep   # names the model term


def test_calibration_skips_jit_spans_and_counts_uncorrelated():
    led = CalibrationLedger()
    led.correlate([_span("halo.solve", 0.0, 1e-6,
                         op="halo_aggregation", axis="x", jit=True)],
                  [_rec(), _rec(op="moe_dispatch", axis="ep")])
    assert led.samples == [] and len(led.uncorrelated) == 2
    assert led.coverage() == 0.0
    assert "uncorrelated: 2" in led.report()


def test_calibration_covering_span_counts_coverage_not_ratio():
    spans = [_span("train.step", 0.0, 1e-2, track="compute")]
    assert cover_with(spans, "train.step", ["moe_dispatch"]) == 1
    led = CalibrationLedger()
    led.correlate(spans, [_rec(op="moe_dispatch", axis="ep")])
    assert led.coverage() == 1.0
    assert not led.samples[0].attributed
    assert led.ratios() == {}              # no per-op ratio claimed
    assert "COVERED" in led.report()


def test_recalibrator_warmup_then_drift():
    r = Recalibrator(threshold=0.25, warmup=3)
    assert not r.should_retune()
    r.note(1.0)
    r.note(1.0)
    assert not r.should_retune()           # below warmup
    r.note(1.0)
    assert r.should_retune()               # warmup one-shot
    r.rebase()
    assert r.baseline == pytest.approx(1.0) and not r.should_retune()
    for _ in range(40):
        r.note(1.2)                        # +20% sustained: inside band
    assert not r.should_retune()
    for _ in range(40):
        r.note(1.5)                        # +50% sustained: fires
    assert r.should_retune()


# -- decision capture -------------------------------------------------------


def test_capture_decisions_scoped_and_stamped():
    managed.log_decision(_rec(op="halo_aggregation"))
    with managed.capture_decisions() as cap:
        managed.log_decision(_rec(op="moe_dispatch", axis="ep"))
    managed.log_decision(_rec(op="serve_schedule", axis="serve"))
    assert [r.op for r in cap.records] == ["moe_dispatch"]
    assert cap.records[0].t is not None    # stamped for the timeline


# -- metrics migration onto the shared registry -----------------------------


def test_serve_metrics_on_shared_registry():
    from repro.serve.metrics import ServeMetrics
    reg = MetricsRegistry()
    m = ServeMetrics(registry=reg)
    assert m.step_s_estimate() is None
    m.note_quantum(0.8, chunk=8, useful_steps=12, slots=2)
    m.note_quantum(1.6, chunk=8, useful_steps=12, slots=2)
    assert m.step_s_estimate() == pytest.approx(0.1)   # running min
    m.note_swap(nbytes=256, seconds=0.5)
    assert m.swap_bytes == 256 and m.swap_s == 0.5
    snap = reg.snapshot()
    assert snap["serve.swap_bytes"] == 256
    assert snap["serve.step_s"]["value"] == pytest.approx(0.1)


def test_checkpoint_metrics_on_shared_registry():
    from repro.checkpoint.metrics import CheckpointMetrics
    reg = MetricsRegistry()
    m = CheckpointMetrics(registry=reg)
    m.note_save(step=1, nbytes=1000, snapshot_s=0.1, drain_s=0.5,
                write_s=0.5)
    m.note_save(step=2, nbytes=1000, snapshot_s=0.3, drain_s=1.0,
                write_s=1.0)
    assert m.write_bw_estimate() == pytest.approx(1000.0)  # best rate
    assert m.ckpt_cost_s_estimate() == pytest.approx(0.6)  # best cost
    assert reg.snapshot()["ckpt.write_bw"]["value"] \
        == pytest.approx(1000.0)


# -- trace -> mdmplint pass 4 ----------------------------------------------


def test_attach_trace_flips_overlap_diagnostic():
    from repro.analysis import attach_trace, check_overlap
    from repro.analysis.graph import CommGraph
    g = CommGraph(name="t", axis_sizes={})
    assert check_overlap(g) == []          # declared story: no race
    spans = [
        _span("serve.swap_out", 0.0, 1.0, buffer="kv_pages"),
        _span("serve.quantum", 0.25, 0.5, reads="kv_pages"),
    ]
    g2 = attach_trace(g, spans)
    codes = [d.code for d in check_overlap(g2)]
    assert codes == ["MDMP401"]            # the measured story races
    assert check_overlap(g) == []          # original graph untouched
    # racing writes escalate to MDMP402
    g3 = attach_trace(g, [
        _span("serve.swap_in", 0.0, 1.0, buffer="kv_pages"),
        _span("decode", 0.25, 0.5, writes="kv_pages"),
    ])
    assert [d.code for d in check_overlap(g3)] == ["MDMP402"]
