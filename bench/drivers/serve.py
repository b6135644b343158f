"""Driver of the ``serve`` traffic kind: open-loop requests through the
program's serving engine.

Set-up builds the engine as ``launch.serve.build_engine`` does (a
(data, model) mesh over the cell's chips, ``Model``, ``ServeEngine``) but
with the benchmark's own seeded weights, and warms up the quantum of every
C that the managed serve schedule can choose.

The window: a generator thread submits each request of the mix through
``ServeEngine.submit`` when it is due, and the main thread calls
``ServeEngine.run`` whenever work is queued.  Requests due in the window
are served to completion.  A harness subclass of ``ServeMetrics``, passed
as the engine's ``metrics``, notes the moment the host holds each token.
The time per output token is taken over every stretch of
``STRETCH_TOKENS`` successive tokens of every request, so that each
reading spans several steps, and its 95th percentile is the end-to-end
metric; a request that fails is a miss.  Time to first token runs from
each request's due time; with some 15 requests in a window its tail is
logged, not bound.

``correct``: once all is served, a sample of finished requests drawn from
the seed, the longest among them, is run through the float32 reference
(prompt and served tokens together), and the widest gap by which a served
token's logit lies below the reference's best logit is compared with its
limit.
"""

from __future__ import annotations

import collections
import inspect
import json
import threading
import time

import numpy as np

from bench import gen, harness
from bench.model import model_config
from bench.harness import Run, span

#: widest gap, in logits, of a served token below the reference's best:
#: sound runs read up to 0.094 over 27 seeds, the fp8 control 0.78 and
#: more (PERF.md, section 2)
MAX_LOGIT_GAP = 0.3

#: tokens of one reading of the time per output token: 8 steps of about
#: 75 ms on one v5e span some 0.6 s, well over the host clock's error
STRETCH_TOKENS = 8


def schedule_chunks() -> tuple[int, ...]:
    """Every quantum C the managed serve schedule can choose."""
    from repro.core import cost_model
    return tuple(inspect.signature(cost_model.decide_serve_schedule)
                 .parameters["candidate_chunks"].default)


def make_metrics():
    import jax
    from repro.serve.metrics import ServeMetrics

    class Metrics(ServeMetrics):
        """The engine's metrics, noting when the host holds each token
        of each request, and each quantum with the slot positions it
        advanced."""

        def __init__(self):
            super().__init__()
            self.lock = threading.Lock()
            self.first: dict[int, float] = {}
            self.done: dict[int, float] = {}
            self.held: dict[int, list[float]] = {}
            self.log: list[tuple] = []
            self.pending_pos = None
            self.on_quantum = None
            self.capture = False
            self._host = None

        def on_submit(self, rid, n_prompt, n_new):
            with self.lock:
                super().on_submit(rid, n_prompt, n_new)

        def rebase_pending(self):
            with self.lock:
                super().rebase_pending()

        def on_first_token(self, rid):
            self.first.setdefault(rid, time.perf_counter())
            super().on_first_token(rid)

        def on_generated(self, rid, n=1):
            self.held.setdefault(rid, []).extend([time.perf_counter()] * n)
            super().on_generated(rid, n)

        def on_done(self, rid):
            self.done[rid] = time.perf_counter()
            super().on_done(rid)

        def note_quantum(self, wall_s, chunk, useful_steps, slots):
            super().note_quantum(wall_s, chunk, useful_steps, slots)
            self.log.append((time.perf_counter(), wall_s, chunk,
                             useful_steps, self.pending_pos))
            self.pending_pos = None
            if self.on_quantum is not None:
                self.on_quantum()
            if self.capture:
                # the host's work from one quantum's result to the next
                # dispatch, for the idle-gap labels
                self._host = jax.profiler.TraceAnnotation(
                    "bench.serve.between_quanta")
                self._host.__enter__()

        def end_host_span(self):
            if self._host is not None:
                self._host.__exit__(None, None, None)
                self._host = None

    return Metrics()


def build(r: Run):
    import jax
    from repro.models.model import Model
    from repro.parallel.sharding import MeshCtx, infer_shardings
    from repro.serve.engine import ServeEngine
    from bench.reference import weights

    c = r.cfg
    mc = model_config(c)
    mesh = jax.make_mesh((len(r.devices), 1), ("data", "model"),
                         devices=r.devices)
    model = Model(mc, MeshCtx.from_mesh(mesh))
    params = weights.make(harness.jax_key(r.seed, 3), c,
                          infer_shardings(model.param_specs(), mesh))
    metrics = make_metrics()
    engine = ServeEngine(model, mesh, params, slots=c["serve"]["slots"],
                         max_seq=c["serve"]["max_seq"],
                         page_size=c["serve"]["page_size"], metrics=metrics)
    return engine, params, metrics


def instrument(engine, metrics, capture: bool) -> None:
    """Wrap each compiled quantum in a host span and note the slots'
    positions and steps before each dispatch."""
    import jax
    sch = engine.scheduler

    metrics.capture = capture

    def wrap(fn, chunk):
        def call(*args):
            metrics.end_host_span()
            metrics.pending_pos = [
                (rs.consumed, min(chunk, rs.req.total_steps - rs.consumed))
                for rs in sch.active.values()]
            with jax.profiler.TraceAnnotation("bench.serve.dispatch"):
                return fn(*args)
        return call

    engine._steps = {c: wrap(fn, c) for c, fn in engine._steps.items()}


class Arrivals(threading.Thread):
    """Submits each request when it is due; notes how late it ran."""

    def __init__(self, engine, reqs, t0: float, wake: threading.Event):
        super().__init__(name="bench-arrivals", daemon=True)
        self.engine, self.reqs, self.t0, self.wake = engine, reqs, t0, wake
        self.rid: dict[int, int] = {}
        self.late: list[float] = []
        self.failed: list[int] = []
        self.error: BaseException | None = None

    def run(self) -> None:
        from repro.serve.scheduler import RequestRejected
        try:
            for i, q in enumerate(self.reqs):
                wait = self.t0 + q.due_s - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.late.append(time.perf_counter() - self.t0 - q.due_s)
                try:
                    self.rid[i] = self.engine.submit(q.prompt, q.max_new)
                except RequestRejected:
                    self.failed.append(i)
                self.wake.set()
        except BaseException as e:   # noqa: BLE001 - re-raised by the caller
            self.error = e
            self.wake.set()


def window(engine, metrics, reqs, on_quantum=None):
    """Serve ``reqs`` open loop: a thread submits each when due, this
    thread runs the engine whenever work is queued, until every request
    is finished.  Returns (t0, t1, arrivals)."""
    sch = engine.scheduler
    wake = threading.Event()
    t0 = time.perf_counter()
    arrivals = Arrivals(engine, reqs, t0, wake)
    metrics.on_quantum = (lambda: on_quantum(t0)) if on_quantum else None
    arrivals.start()
    while True:
        wake.clear()
        if sch.has_work():
            with span("bench.serve.run"):
                engine.run()
                metrics.end_host_span()
            continue
        if not arrivals.is_alive():
            if sch.has_work():
                continue
            break
        if on_quantum is not None:
            on_quantum(t0)
        with span("bench.serve.idle"):
            wake.wait(0.01)
    arrivals.join()
    t1 = time.perf_counter()
    metrics.on_quantum = None
    if arrivals.error is not None:
        raise arrivals.error
    return t0, t1, arrivals


def stretches(held: list[float], tokens: int = STRETCH_TOKENS
              ) -> list[float]:
    """Seconds per token over each stretch of one request's output: its
    gaps split into as many even stretches of at least ``tokens`` as
    they hold (one, where they hold fewer); ``held[i]`` is when the host
    held token i."""
    gaps = len(held) - 1
    if gaps < 1:
        return []
    m = max(1, gaps // tokens)
    b = [round(i * gaps / m) for i in range(m + 1)]
    return [(held[b[i + 1]] - held[b[i]]) / (b[i + 1] - b[i])
            for i in range(m)]


def latencies(engine, metrics, reqs, t0, arrivals, vocab: int):
    """(ttft list, per-request tpot list, per-stretch tpot list, failed
    request indices); a failed request counts as an infinite time in
    each."""
    ttft, tpot, tpot_stretch = [], [], []
    failed = set(arrivals.failed)
    for i, q in enumerate(reqs):
        rid = arrivals.rid.get(i)
        out = engine.results.get(rid) if rid is not None else None
        if out is None or len(out) != q.max_new or \
                not np.all((out >= 0) & (out < vocab)):
            failed.add(i)
            continue
        first, done = metrics.first[rid], metrics.done[rid]
        ttft.append(first - t0 - q.due_s)
        tpot.append((done - first) / max(1, q.max_new - 1))
        tpot_stretch += stretches(metrics.held[rid][-q.max_new:])
    for xs in (ttft, tpot, tpot_stretch):
        xs += [float("inf")] * len(failed)
    return ttft, tpot, tpot_stretch, failed


def kv_filled(quanta, slots: int, max_seq: int) -> float:
    """Mean share of the page pool's positions that live contexts fill
    after each of ``quanta``."""
    if not quanta:
        return float("nan")
    return sum(sum(pos + n for pos, n in q[4]) for q in quanta) / (
        len(quanta) * slots * max_seq)


def warm(engine, metrics, capture: bool) -> None:
    """Compile (or load) the quantum of every C the schedule can choose."""
    with span("bench.serve.warmup"):
        for c in schedule_chunks():
            engine.warmup(c)
    instrument(engine, metrics, capture)


def run(r: Run) -> None:
    engine, params, metrics = build(r)
    warm(engine, metrics, capture=r.trace)
    mix = r.traffic
    reqs = gen.serve_requests(mix, r.seed, r.seconds, r.cfg["vocab_size"])
    counter = harness.CompileCounter()
    trace_from = r.seconds - float(mix.get("trace_seconds", r.seconds))

    def boundary(t0):
        now = time.perf_counter() - t0
        if r.trace and not r.tracing and r.trace_path is None \
                and trace_from <= now < r.seconds:
            r.start_trace()
            r.counters["trace_q0"] = len(metrics.log)
        elif r.tracing and now >= r.seconds:
            r.stop_trace()
            r.counters["trace_q1"] = len(metrics.log)

    r.setup_done()
    counter.armed = True
    t0, t1, arrivals = window(engine, metrics, reqs, boundary)
    counter.armed = False
    if r.tracing:
        r.stop_trace()
        r.counters["trace_q1"] = len(metrics.log)
    r.peak_bytes = harness.peak_bytes(r.devices)
    ttft, tpot, tpot_stretch, failed = latencies(
        engine, metrics, reqs, t0, arrivals, r.cfg["vocab_size"])
    r.attempted, r.failed = len(reqs), len(failed)
    r.metric("serve_tpot_p95_ms",
             1e3 * harness.quantile(tpot_stretch, 0.95), "ms")
    # the quanta whose results the host held inside the window
    window_q1 = sum(1 for q in metrics.log if q[0] - t0 <= r.seconds)
    kv = kv_filled(metrics.log[:window_q1], engine.slots,
                   r.cfg["serve"]["max_seq"])
    r.counters.update(
        compiles_in_window=counter.count, late_s=arrivals.late,
        quanta=metrics.log, window_q1=window_q1, served_s=t1 - t0,
        requests=len(reqs), slots=engine.slots,
        chunk=engine.scheduler.chunk)
    chunks = collections.Counter(q[2] for q in metrics.log)
    harness.log(
        f"serve: {len(reqs)} requests in {t1 - t0:.1f} s, "
        f"{len(metrics.log)} quanta ({window_q1} in the window), by C "
        f"{dict(sorted(chunks.items()))}, last C={engine.scheduler.chunk}; "
        f"ttft p50 {harness.quantile(ttft, 0.5):.3f} s p95 "
        f"{harness.quantile(ttft, 0.95):.3f} s; tpot per request p95 "
        f"{1e3 * harness.quantile(tpot, 0.95):.2f} ms; tpot over "
        f"{len(tpot_stretch)} stretches p50 "
        f"{1e3 * harness.quantile(tpot_stretch, 0.5):.2f} ms p95 "
        f"{1e3 * harness.quantile(tpot_stretch, 0.95):.2f} ms; "
        f"page pool filled {100 * kv:.1f}% in the window")
    verify(r, engine, params, reqs, arrivals.rid, failed)


def sample(r: Run, reqs, rids, failed) -> list[int]:
    """Finished requests to check: the longest, and others drawn from the
    seed, up to the mix's ``check_requests``."""
    done = [i for i in rids if i not in failed]
    if not done:
        return []
    longest = max(done, key=lambda i: len(reqs[i].prompt) + reqs[i].max_new)
    rest = [i for i in done if i != longest]
    g = harness.rng(r.seed, 5)
    k = min(len(rest), int(r.traffic["check_requests"]) - 1)
    return [longest] + list(g.choice(rest, size=k, replace=False))


def gaps(r: Run, params, tokens_of, quant: str | None = None):
    """Reference gaps of each (prompt, served) pair, one request at a
    time, padded to the cell's longest sequence and output (one compiled
    program for every run of the cell)."""
    import jax.numpy as jnp
    from bench.reference import transformer
    c = r.cfg
    t_pad = c["serve"]["max_seq"]
    n_pad = r.traffic["output"]["max"]
    cj = json.dumps(c, sort_keys=True)
    prog, ctl = [], []
    for prompt, served in tokens_of:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks = np.zeros((1, t_pad), np.int32)
        toks[0, :len(seq)] = seq
        at = np.zeros(n_pad, np.int32)
        at[:len(served)] = np.arange(len(prompt) - 1, len(seq))
        want = np.zeros(n_pad, np.int32)
        want[:len(served)] = served
        g, gc = transformer.served_gaps(params, jnp.asarray(toks),
                                        jnp.asarray(at), jnp.asarray(want),
                                        cj=cj, quant=quant)
        prog.append(np.asarray(g)[:len(served)])
        ctl.append(np.asarray(gc)[:len(served)])
    return prog, ctl


def verify(r: Run, engine, params, reqs, rids, failed) -> None:
    import jax
    picked = sample(r, reqs, rids, failed)
    tokens_of = [(reqs[i].prompt, engine.results[rids[i]]) for i in picked]
    # the program's state goes before the reference runs
    engine.cache = None
    jax.clear_caches()
    quant = r.control
    prog, ctl = gaps(r, params, tokens_of, quant)
    # the control puts the lower precision's first choice in the served
    # tokens' place
    read = ctl if quant else prog
    worst = float(max((g.max() for g in read), default=float("inf")))
    r.counters["checked_tokens"] = int(sum(len(g) for g in read))
    r.counters["program_gap"] = float(max((g.max() for g in prog),
                                          default=float("inf")))
    if quant:
        r.counters["control_gap"] = worst
    r.check("serve_max_logit_gap", worst, MAX_LOGIT_GAP)
