"""Serving engine — the jitted step loop over the paged cache.

One dispatched call advances every decode slot by up to C tokens
(``lax.scan`` over ``Model.decode_step_paged``): slots still inside their
prompt consume prompt tokens (chunked prefill), slots past it feed their
own last sample back (decode).  C — the scheduling quantum — is the
managed knob: it amortises the per-dispatch overhead (the alpha of this
decision) against scheduling granularity (admission + retirement only
happen at quantum boundaries), and is chosen by
``managed.resolve_serve_schedule`` from the serve cost model, then
corrected online from serve/metrics.py's measured step latencies —
MDMP's iteration-(k)->(k+1) loop on the serving path.

The cache is the paged pool of serve/kv_cache.py: per-layer page pools
sharded over the cache axes, one host-side page table, pages recycled
through the free list as requests retire.  Works for every token-only
decoder family (dense / moe / ssm / hybrid — SSM state is slot-indexed
and masked, so "paging" degenerates to slot reuse there).

Overload is a managed condition, not a crash.  Admission is OPTIMISTIC
(watermark mode commits only the prompt's pages; decode growth claims
pages on demand), and when the pool exhausts mid-decode
(``PagePoolExhausted``) the engine preempts: pick a victim, then either
SWAP its page chain to host (D2H in ``overlap.drain_chunk_bytes``-metered
row slices, restored on re-admission) or DROP it for prefill-replay
(``scheduler.continuation`` — the drain() idiom), or stall the growing
slot one quantum — whichever ``managed.resolve_preempt`` prices cheapest
from the measured step seconds and PCIe bandwidth.  Greedy decoding makes
both eviction paths token-equal to the no-overload run.  The ``burst``
and ``pool_squeeze`` fault kinds drive this machinery deterministically
under test.
"""

from __future__ import annotations

import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cost_model, managed, overlap
from repro.core.faults import FaultPlan
from repro.models.model import Model
from repro.obs.calibrate import Recalibrator
from repro.obs.tracer import get_tracer
from repro.parallel.sharding import smap, spec_pspecs
from repro.serve.kv_cache import (PagedCacheConfig, PagePoolExhausted,
                                  PageTable)
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import (Request, RequestRejected, ServeScheduler)

Array = jax.Array


def build_paged_step(model: Model, mesh: Mesh, cache_pspecs: Any,
                     chunk: int):
    """Jitted quantum: (params, cache, table [B, Pmax], tokens [B, C],
    n_in [B], pos0 [B], steps [B]) -> (sampled [B, C], new cache).

    Inner scan step t feeds slot b ``tokens[b, t]`` while t < n_in[b]
    (prompt/chain seed) and its own previous sample afterwards; slots
    with t >= steps[b] are inactive (no cache write, no position
    advance)."""
    pspecs = spec_pspecs(model.param_specs())

    def body(params, cache, table, tokens, n_in, pos0, steps):
        def inner(carry, xs):
            cache_c, pos, last = carry
            t, tok_col = xs
            tok = jnp.where(t < n_in, tok_col, last)
            act = t < steps
            nxt, cache_c = model.decode_step_paged(
                params, cache_c, table, tok, pos, act)
            pos = pos + act.astype(jnp.int32)
            last = jnp.where(act, nxt, last)
            return (cache_c, pos, last), nxt

        init = (cache, pos0, tokens[:, 0])
        (cache, _, _), outs = lax.scan(
            inner, init, (jnp.arange(chunk), tokens.T))
        return outs.T, cache

    sharded = smap(
        body, mesh,
        in_specs=(pspecs, cache_pspecs, P(None, None), P(None, None),
                  P(None), P(None), P(None)),
        out_specs=(P(None, None), cache_pspecs))
    return jax.jit(sharded, donate_argnums=(1,))


class ServeEngine:
    """Continuous-batching serving loop over the paged cache."""

    def __init__(self, model: Model, mesh: Mesh, params: Any, *,
                 slots: int = 4, max_seq: int = 256, page_size: int = 8,
                 n_pages: int | None = None, schedule: str = "auto",
                 chunk: int | None = None,
                 metrics: ServeMetrics | None = None, tuner: Any = None,
                 fault_plan: FaultPlan | None = None,
                 admission: str = "watermark", watermark: int = 0,
                 preempt: str = "auto",
                 slo_ttft_s: float | None = None,
                 max_queue: int | None = None, burst_new: int = 8):
        from repro.models import attention
        assert preempt in ("auto", "swap", "recompute", "none"), preempt
        self.model = model
        self.mesh = mesh
        self.params = params
        self.slots = slots
        n_sh = attention.cache_shards(model.ctx)
        pages_per_seq = max(1, math.ceil(max_seq / page_size))
        if n_pages is None:
            n_pages = slots * pages_per_seq
        n_pages = ((n_pages + n_sh - 1) // n_sh) * n_sh
        self.cache_cfg = PagedCacheConfig(
            slots=slots, page_size=page_size, n_pages=n_pages,
            max_pages_per_seq=pages_per_seq)
        self.pt = PageTable(self.cache_cfg)
        self.metrics = metrics or ServeMetrics()
        self._n_params = model.cfg.param_count()
        self._dtype_bytes = jnp.dtype(model.cfg.dtype).itemsize
        self.scheduler = ServeScheduler(
            slots, schedule=schedule, chunk=chunk, tuner=tuner,
            cache_cfg=self.cache_cfg, admission=admission,
            watermark=watermark, slo_ttft_s=slo_ttft_s,
            max_queue=max_queue,
            model_step_s=cost_model.serve_step_time(
                self._n_params, slots, dtype_bytes=self._dtype_bytes))
        self._schedule = schedule
        self._preempt = preempt
        self._burst_new = int(burst_new)
        # KV state is pageable for attention-cache families; SSM slot
        # state is not a page chain, so those evict by recompute only
        self._swappable = model.cfg.family in ("dense", "moe")
        self._cache_sds, self._cache_pspecs = model.paged_cache_specs(
            slots, n_pages, page_size)
        # bytes per pool page, summed across pool leaves (each leaf is
        # layer-stacked, so a page's footprint spans every layer)
        self._page_bytes = sum(
            int(np.prod(s.shape)) // s.shape[ax]
            * np.dtype(s.dtype).itemsize
            for s, ax in zip(jax.tree.leaves(self._cache_sds),
                             self._pool_page_axes())
            if ax is not None)
        self._steps: dict[int, Any] = {}      # chunk -> jitted quantum
        self._rid = 0
        # the online-correction trigger (obs.Recalibrator): fire once as
        # soon as 3 quanta are measured (the historical warmup retune),
        # then again whenever the per-step seconds drift >25% off the
        # value the schedule was last resolved against
        self.recal = Recalibrator(threshold=0.25, warmup=3)
        self._variant_q0 = 0      # quanta index of the variant's window
        self.fault_plan = fault_plan
        self._quantum_idx = 0     # lifetime quantum counter (fault clock)
        self.results: dict[int, np.ndarray] = {}
        #: rid -> (n_pages, host page rows per pool leaf, consumed,
        #: last_out, generated) for swapped-out victims awaiting re-admit
        self._swapped: dict[int, tuple] = {}
        #: rid -> tokens generated before a recompute eviction (stitched
        #: in front of the continuation's output at retirement)
        self._gen_prefix: dict[int, list[int]] = {}
        #: rids evicted since the last dispatched quantum; admission holds
        #: them at the queue head so eviction cannot chase re-admission
        self._hold: set[int] = set()
        self.cache = self._empty_cache()

    # -- device state --------------------------------------------------------

    def _empty_cache(self) -> Any:
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._cache_pspecs)
        return jax.tree.map(
            lambda sds, sh: jax.device_put(
                jnp.zeros(sds.shape, sds.dtype), sh),
            self._cache_sds, shardings)

    def _step_fn(self, chunk: int):
        fn = self._steps.get(chunk)
        if fn is None:
            fn = build_paged_step(self.model, self.mesh,
                                  self._cache_pspecs, chunk)
            self._steps[chunk] = fn
        return fn

    def _idle_args(self, chunk: int) -> tuple:
        """Quantum arguments of a zero-step quantum (touches no state)."""
        zeros = jnp.asarray(np.zeros(self.slots, np.int32))
        return (self.params, self.cache, jnp.asarray(self.pt.table),
                jnp.zeros((self.slots, chunk), jnp.int32),
                jnp.asarray(np.ones(self.slots, np.int32)), zeros, zeros)

    def warmup(self, chunk: int) -> None:
        """Compile the quantum function outside the measured loop: one
        idle quantum of ``chunk`` steps, noted in ``metrics.warmups``."""
        self.metrics.note_warmup(chunk)
        with get_tracer().span("serve.warmup", track="serve", chunk=chunk):
            out, self.cache = self._step_fn(chunk)(*self._idle_args(chunk))
            jax.block_until_ready(out)

    def lower_quantum(self, chunk: int) -> Any:
        """The quantum of ``chunk`` steps, lowered (``jax.stages.Lowered``)
        for inspecting what it compiles to."""
        return self._step_fn(chunk).lower(*self._idle_args(chunk))

    # -- queue ---------------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int,
               ttft_slo_s: float | None = None) -> int:
        rid = self._rid
        self._rid += 1
        req = Request(rid=rid, prompt=np.asarray(prompt, np.int32).ravel(),
                      max_new=int(max_new), ttft_slo_s=ttft_slo_s)
        self.submit_request(req)
        return rid

    def submit_request(self, req: Request) -> None:
        """Submit a pre-built request, preserving its rid — the failover
        path: a drained replica's requests re-admit here with their
        generated prefix folded into the prompt.  Infeasible requests
        raise the typed ``RequestRejected`` and shed ones ``RequestShed``
        (scheduler.submit) — the rid is consumed either way."""
        self._rid = max(self._rid, req.rid + 1)
        self.scheduler.submit(req, self.metrics)

    def drain(self) -> list[tuple[Request, list[int]]]:
        """Evacuate a dead replica: free every in-flight request's page
        chain and hand back [(request, generated_prefix)] rebuilt for a
        survivor (scheduler.drain).  Finished requests retire into
        ``self.results``; the caller stitches prefix + survivor output
        for the rest.  Swapped-out host state is dropped — the original
        request is still queued and replays from scratch elsewhere."""
        out = self.scheduler.drain(self.pt, self.results)
        self._swapped.clear()
        self.scheduler.restore_pages.clear()
        for rid, pre in list(self._gen_prefix.items()):
            if rid in self.results:
                self.results[rid] = np.concatenate(
                    [np.asarray(pre, np.int32), self.results[rid]])
                del self._gen_prefix[rid]
        return [(req, self._gen_prefix.pop(req.rid, []) + prefix)
                for req, prefix in out]

    # -- overload faults -----------------------------------------------------

    def _inject_burst(self, n: int) -> None:
        """A ``burst@q:n`` event: n synthetic arrivals at this quantum
        boundary, prompts seeded from the quantum index so the flood is
        identical across runs.  Shed/rejected arrivals are recorded by
        admission control and dropped — overload degrades, never kills."""
        rng = np.random.default_rng(0xB0 + 997 * self._quantum_idx)
        for _ in range(max(0, n)):
            plen = int(rng.integers(4, 17))
            prompt = rng.integers(1, 1000, size=plen).astype(np.int32)
            try:
                self.submit(prompt, self._burst_new)
            except RequestRejected:
                pass

    def _apply_overload_events(self) -> None:
        if self.fault_plan is None:
            return
        for ev in self.fault_plan.serve_overload(self._quantum_idx):
            if ev.kind == "burst":
                self._inject_burst(int(ev.arg))
            else:                             # pool_squeeze@q:frac
                self.pt.squeeze(float(ev.arg))

    # -- preemption (the optimistic-admission backstop) ----------------------

    def _pool_page_axes(self) -> list[int | None]:
        """Per cache leaf: the axis indexed by pool page ids, or None for
        non-pool state (SSM slot state).  Pool leaves are [Np, page, KV,
        hd] or, layer-stacked, [L, Np, page, KV, hd]."""
        npg = self.cache_cfg.n_pages
        pg = self.cache_cfg.page_size
        axes: list[int | None] = []
        for leaf in jax.tree.leaves(self._cache_sds):
            shp = tuple(leaf.shape)
            if len(shp) == 4 and shp[0] == npg and shp[1] == pg:
                axes.append(0)
            elif len(shp) == 5 and shp[1] == npg and shp[2] == pg:
                axes.append(1)
            else:
                axes.append(None)
        return axes

    def _swap_chunk_rows(self, row_bytes: int) -> int:
        """Rows per metered transfer slice: the checkpoint drain's chunk
        meter applied to eviction traffic."""
        step = self.scheduler.step_s_hint(self.metrics) or 1e-3
        bw = self.metrics.swap_bw_estimate() or cost_model.PCIE_BW
        cb = overlap.drain_chunk_bytes(step, bw)
        return max(1, cb // max(1, row_bytes))

    def _swap_out(self, slot: int) -> None:
        """Evict ``slot`` by draining its resident KV pages to host in
        row-sliced chunks; the original request requeues at the front and
        restores (``_swap_in``) once admission finds its pages again."""
        sch, pt = self.scheduler, self.pt
        rs = sch.active[slot]
        keep = pt.cfg.pages_needed(rs.consumed)
        ids = np.asarray(pt.chain(slot)[:keep], np.int32)
        axes = self._pool_page_axes()
        leaves = jax.tree.leaves(self.cache)
        t0 = time.perf_counter()
        host: list[np.ndarray | None] = []
        nbytes = 0
        with get_tracer().span("serve.swap_out", op="preempt_policy",
                               axis="serve", track="serve",
                               buffer="kv_pages", slot=slot) as sp:
            for leaf, ax in zip(leaves, axes):
                if ax is None:
                    host.append(None)
                    continue
                row_bytes = (int(np.prod(leaf.shape)) // leaf.shape[ax]
                             * leaf.dtype.itemsize)
                rpc = self._swap_chunk_rows(row_bytes)
                # the pool's page axis is sharded: name the (replicated)
                # sharding of the gathered rows, which jax cannot infer
                rep = NamedSharding(self.mesh, P())
                pre = (slice(None),) * ax
                parts = [np.asarray(leaf.at[pre + (ids[i:i + rpc],)].get(
                             out_sharding=rep))
                         for i in range(0, len(ids), rpc)]
                empty = leaf.shape[:ax] + (0,) + leaf.shape[ax + 1:]
                rows = (np.concatenate(parts, axis=ax) if parts else
                        np.zeros(empty, leaf.dtype))
                host.append(rows)
                nbytes += rows.nbytes
            if sp is not None:
                sp.note(nbytes=nbytes)
        self.metrics.note_swap(nbytes, time.perf_counter() - t0)
        rs = sch.preempt(slot, pt)
        self._swapped[rs.req.rid] = (len(ids), host, rs.consumed,
                                     rs.last_out, list(rs.generated))
        sch.restore_pages[rs.req.rid] = keep
        sch.requeue_front(rs.req)
        self._hold.add(rs.req.rid)
        self.metrics.on_preempt(rs.req.rid, "swap")

    def _swap_in(self, rs) -> None:
        """Restore a swapped victim into its new slot: reallocate a page
        chain for its consumed positions and push the host rows back
        (H2D, same chunk meter), then resume decoding mid-chain."""
        data = self._swapped.pop(rs.req.rid, None)
        if data is None:
            return
        n_ids, host, consumed, last_out, generated = data
        pt = self.pt
        pt.ensure(rs.slot, consumed)
        new_ids = np.asarray(pt.chain(rs.slot)[:n_ids], np.int32)
        leaves, treedef = jax.tree.flatten(self.cache)
        pleaves = jax.tree.leaves(self._cache_pspecs)
        axes = self._pool_page_axes()
        t0 = time.perf_counter()
        nbytes = 0
        out_leaves = []
        with get_tracer().span("serve.swap_in", op="preempt_policy",
                               axis="serve", track="serve",
                               buffer="kv_pages", slot=rs.slot) as sp:
            for leaf, ps, rows, ax in zip(leaves, pleaves, host, axes):
                if rows is None or ax is None or not len(new_ids):
                    out_leaves.append(leaf)
                    continue
                row_bytes = (int(np.prod(leaf.shape)) // leaf.shape[ax]
                             * leaf.dtype.itemsize)
                rpc = self._swap_chunk_rows(row_bytes)
                pre = (slice(None),) * ax
                home = NamedSharding(self.mesh, ps)
                for i in range(0, len(new_ids), rpc):
                    leaf = leaf.at[pre + (new_ids[i:i + rpc],)].set(
                        jnp.asarray(rows[pre + (slice(i, i + rpc),)]),
                        out_sharding=home)
                out_leaves.append(leaf)
                nbytes += rows.nbytes
            self.cache = jax.tree.unflatten(treedef, out_leaves)
            jax.block_until_ready(self.cache)
            if sp is not None:
                sp.note(nbytes=nbytes)
        self.metrics.note_swap(nbytes, time.perf_counter() - t0)
        rs.consumed = consumed
        rs.last_out = last_out
        rs.generated = list(generated)
        self.scheduler.restore_pages.pop(rs.req.rid, None)

    def _drop_recompute(self, slot: int) -> None:
        """Evict ``slot`` by releasing its pages outright; the request
        requeues as a prompt+generated continuation whose prefill REPLAYS
        the lost KV (greedy decoding keeps the token chain bit-equal)."""
        sch = self.scheduler
        with get_tracer().span("serve.recompute_evict",
                               op="preempt_policy", axis="serve",
                               track="serve", buffer="kv_pages",
                               slot=slot):
            rs = sch.preempt(slot, self.pt)
            rid = rs.req.rid
            cont = sch.continuation(rs)
            if cont is None:                  # already finished: retire
                self._retire(rid, rs.generated)
                return
            if rs.generated:
                self._gen_prefix[rid] = (self._gen_prefix.get(rid, [])
                                         + list(rs.generated))
            sch.requeue_front(cont)
            self._hold.add(rid)
        self.metrics.on_preempt(rid, "recompute")

    def _retire(self, rid: int, generated: list[int]) -> None:
        pre = self._gen_prefix.pop(rid, [])
        self.results[rid] = np.asarray(list(pre) + list(generated),
                                       np.int32)

    def _cap_to_resident(self, plan, stalled: list[int]) -> int:
        """The WAIT policy: clamp each stalled slot's quantum steps to
        the positions its already-allocated chain can hold.  Returns the
        batch's total steps after clamping."""
        for s in stalled:
            rs = self.scheduler.active[s]
            fit = (self.pt.pages_held(s) * self.cache_cfg.page_size
                   - rs.consumed)
            plan.steps[s] = max(0, min(int(plan.steps[s]), fit))
        return int(plan.steps.sum())

    def _handle_exhaustion(self, plan, stalled: list[int]) -> bool:
        """React to ``PagePoolExhausted`` on this quantum's page growth.
        Returns True when a victim was evicted (the caller re-admits and
        re-plans), False when ``plan.steps`` were capped in place and the
        clamped quantum should dispatch (wait)."""
        sch, pt = self.scheduler, self.pt
        can_wait = self._cap_to_resident(plan, stalled) > 0
        if self._preempt == "none":
            # the unmanaged baseline: no eviction machinery — stall while
            # anything progresses, die when nothing can
            if not can_wait:
                raise RuntimeError(
                    "serve queue stalled: page pool exhausted and "
                    f"preemption is disabled ({self.cache_cfg})")
            return False
        victim = sch.select_victim(pt, prefer_not=stalled[0])
        if victim is None or len(sch.active) == 1:
            # no victim — or evicting the SOLE slot, which can never
            # help: its continuation needs at least the pages it holds
            # now, so eviction would only trade a stall for a thrash
            if can_wait:
                return False
            raise RuntimeError(
                "serve queue stalled: page pool exhausted with no "
                f"evictable victim ({self.cache_cfg})")
        vrs = sch.active[victim]
        victim_pages = pt.pages_held(victim)
        step = sch.step_s_hint(self.metrics)
        # soonest a retirement frees pages naturally — only meaningful
        # when the clamped batch still progresses toward one
        wait_s = None
        if can_wait and step is not None:
            rem = [rs.req.total_steps - rs.consumed
                   for s, rs in sch.active.items() if s not in stalled]
            if rem:
                wait_s = min(rem) * step
        policy = None if self._preempt == "auto" else self._preempt
        if policy is None and sch.tuner is not None:
            entry = sch.tuner.decide_preempt(
                sch.axis_name, self.slots, self._page_bytes,
                self._n_params, victim_pages=victim_pages,
                replay_tokens=vrs.consumed,
                dtype_str=self.model.cfg.dtype,
                dtype_bytes=self._dtype_bytes, step_s=step)
            self._preempt_key = entry.key
            if len(entry.measured_s) >= 2:
                policy = entry.mode
        d = managed.resolve_preempt(
            sch.axis_name, victim_pages, self._page_bytes, vrs.consumed,
            self._n_params, batch_slots=self.slots,
            dtype_bytes=self._dtype_bytes, measured_step_s=step,
            measured_pcie_bw=self.metrics.swap_bw_estimate(),
            wait_s=wait_s, allow_swap=self._swappable, policy=policy)
        if d.policy == "wait":
            return False
        t0 = time.perf_counter()
        if d.policy == "swap":
            self._swap_out(victim)
        else:
            self._drop_recompute(victim)
        if sch.tuner is not None and getattr(self, "_preempt_key", None):
            # feed the measured eviction cost back (the replay part of a
            # recompute is charged from the measured step rate)
            cost = time.perf_counter() - t0
            if d.policy == "recompute" and step is not None:
                cost += vrs.consumed * step
            sch.tuner.record(self._preempt_key, d.policy, 1, cost)
        return True

    # -- the step loop -------------------------------------------------------

    def run(self) -> dict[int, np.ndarray]:
        """Serve the queue to completion; returns rid -> generated tokens.
        The schedule decision (and any online correction) is visible in
        ``managed.decision_log()`` as ``op="serve_schedule"`` records,
        and every pool-exhaustion event as ``op="preempt_policy"``."""
        sch = self.scheduler
        if not sch.has_work() and not (
                self.fault_plan and self.fault_plan.unfired()):
            return {}
        tr = get_tracer()
        with tr.span("serve.decide", track="serve") as sp:
            sch.decide(self._n_params, self._dtype_bytes,
                       dtype_str=self.model.cfg.dtype)
            if sp is not None:
                sp.note(chunk=sch.chunk, source=sch.decided_from)
        if sch.chunk is None:       # queue was empty (pure fault drive)
            return self.results
        self.warmup(sch.chunk)
        # compilation is over: TTFT measures serving from here, and the
        # running variant's measurement window starts empty
        self.metrics.rebase_pending()
        self._variant_q0 = len(self.metrics.quanta)
        results = self.results
        while sch.has_work():
            self._apply_overload_events()
            with tr.span("serve.admit", track="serve"):
                for rs in sch.admit(self.pt, hold=self._hold):
                    if rs.req.rid in self._swapped:
                        self._swap_in(rs)
            with tr.span("serve.plan", track="serve"):
                plan = sch.plan_quantum(sch.chunk)
                if int(plan.steps.sum()) == 0:
                    # admit() ran just above with an empty batch and
                    # still produced nothing: the head request can never
                    # fit
                    raise RuntimeError(
                        "serve queue stalled: request exceeds the page "
                        f"pool ({self.cache_cfg})")
                stalled = []
                for slot in sorted(sch.active):
                    rs = sch.active[slot]
                    try:
                        self.pt.ensure(slot,
                                       rs.consumed + int(plan.steps[slot]))
                    except PagePoolExhausted:
                        stalled.append(slot)
                evicted = bool(stalled) and self._handle_exhaustion(
                    plan, stalled)
            if evicted:
                continue              # victim evicted: re-admit, re-plan
            if int(plan.steps.sum()) == 0:
                continue              # whole batch stalled this quantum
            if self.fault_plan is not None:
                # the fault clock ticks on dispatched quanta; a
                # replica_death here leaves finished work in self.results
                # and in-flight state intact for drain()
                self.fault_plan.serve_quantum(self._quantum_idx)
            self._quantum_idx += 1
            useful = int(plan.steps.sum())
            t0 = time.perf_counter()
            # scale = useful slot-steps: dur/scale is measured seconds
            # per token, the unit resolve_serve_schedule predicts
            with tr.span(
                    "serve.quantum", op="serve_schedule", axis="serve",
                    track="serve", chunk=plan.chunk, scale=useful,
                    quantum=self._quantum_idx - 1, reads="kv_pages"):
                with tr.span("serve.upload", track="serve"):
                    args = (jnp.asarray(self.pt.table),
                            jnp.asarray(plan.tokens),
                            jnp.asarray(plan.n_in), jnp.asarray(plan.pos),
                            jnp.asarray(plan.steps))
                with tr.span("serve.dispatch", track="serve"):
                    out, self.cache = self._step_fn(plan.chunk)(
                        self.params, self.cache, *args)
                with tr.span("serve.readback", track="serve"):
                    out_np = np.asarray(out)
            wall = time.perf_counter() - t0
            self._hold.clear()    # a quantum dispatched: evictees may
            # re-enter admission on the next planning round
            with tr.span("serve.complete", track="serve"):
                self.metrics.note_quantum(wall, plan.chunk, useful,
                                          self.slots)
                self.recal.note(wall / max(1, plan.chunk))
                for rs in sch.complete_quantum(plan, out_np, self.pt,
                                               self.metrics):
                    self._retire(rs.req.rid, rs.generated)
            prev = (sch.mode, sch.chunk)
            with tr.span("serve.retune", track="serve") as sp:
                self._maybe_retune()
                if sp is not None:
                    sp.note(chunk_before=prev[1], chunk=sch.chunk,
                            step_s=self.metrics.step_s_estimate())
            if sch.has_work() and (sch.mode, sch.chunk) != prev:
                # the correction changed the schedule: compile the new
                # quantum OUTSIDE the measured loop, keep the compile out
                # of still-queued requests' TTFT, and start a fresh
                # measurement window for the new variant
                self.warmup(sch.chunk)
                self.metrics.rebase_pending()
                self._variant_q0 = len(self.metrics.quanta)
        return results

    def _maybe_retune(self) -> None:
        """The iteration-(k)->(k+1) correction: once enough quanta are
        measured, re-resolve the schedule with the observed step/dispatch
        seconds, and feed the running variant's measured seconds-per-token
        to the tuner (so a persisted winner survives restarts).  The
        variant is only credited with quanta from its OWN measurement
        window (``_variant_q0``) — cumulative throughput would attribute
        the previous variant's behaviour to the current one."""
        sch = self.scheduler
        tok_s = self.metrics.useful_tokens_per_s(since=self._variant_q0)
        if sch.tuner is not None and sch.tuner_key and tok_s > 0:
            sch.tuner.record(sch.tuner_key, sch.mode, sch.chunk,
                             1.0 / tok_s)
        if self._schedule != "auto" or not self.recal.should_retune():
            return
        sch.decide(self._n_params, self._dtype_bytes,
                   dtype_str=self.model.cfg.dtype,
                   measured_step_s=self.metrics.step_s_estimate(),
                   measured_dispatch_s=self.metrics.dispatch_s_estimate())
        # rebase on the measurement EWMA at resolve time; the next
        # retune needs a further >threshold sustained drift from here
        self.recal.rebase()
