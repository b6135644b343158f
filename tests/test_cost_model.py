"""Property tests (hypothesis) for the MDMP cost model — the decision
engine's invariants must hold for ANY workload."""

import math

import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (test extra)")
from hypothesis import given, settings, strategies as st

from repro.core import cost_model as cm


sizes = st.integers(min_value=1, max_value=64)
nbytes = st.floats(min_value=1.0, max_value=1e12, allow_nan=False)
compute = st.floats(min_value=0.0, max_value=10.0, allow_nan=False)


@given(nbytes=nbytes, n=sizes)
@settings(max_examples=200, deadline=None)
def test_no_compute_never_interleaves(nbytes, n):
    """With zero fusable compute, chunking buys nothing — the manager must
    keep the bulk schedule (no free lunch from latency alone)."""
    d = cm.decide(nbytes, n, compute_time_s=0.0)
    assert d.mode == "bulk"


@given(nbytes=nbytes, n=st.integers(min_value=2, max_value=64),
       compute=st.floats(min_value=1e-6, max_value=10.0))
@settings(max_examples=200, deadline=None)
def test_interleaved_never_predicted_worse_than_chosen(nbytes, n, compute):
    """decide() must never pick a schedule it predicts to be slower than
    bulk."""
    d = cm.decide(nbytes, n, compute_time_s=compute)
    assert d.interleaved_time_s <= d.bulk_time_s * (1 + 1e-9)


@given(nbytes=nbytes, n=sizes, compute=compute)
@settings(max_examples=200, deadline=None)
def test_times_positive_and_monotone_in_bytes(nbytes, n, compute):
    d1 = cm.decide(nbytes, n, compute_time_s=compute)
    d2 = cm.decide(nbytes * 2, n, compute_time_s=compute)
    assert d1.comm_time_s >= 0
    assert d2.comm_time_s >= d1.comm_time_s


@given(n=st.integers(min_value=2, max_value=64),
       nbytes=st.floats(min_value=1e3, max_value=1e9))
@settings(max_examples=100, deadline=None)
def test_ring_identities(n, nbytes):
    """AR = RS + AG(shard) for ring algorithms."""
    hw = cm.TPU_V5E
    ar = cm.ring_all_reduce_time(nbytes, n, hw)
    rs = cm.ring_reduce_scatter_time(nbytes, n, hw)
    ag = cm.ring_all_gather_time(nbytes / n, n, hw)
    assert ar == pytest.approx(rs + ag, rel=1e-9)


@given(delay=st.floats(min_value=0.0, max_value=1e6),
       n=st.integers(min_value=2, max_value=4096))
@settings(max_examples=100, deadline=None)
def test_pingpong_fine_never_beats_bulk_without_overlap(delay, n):
    """On a machine with no async progression (the paper's HELIOS), bulk
    always wins — Fig 5b's HELIOS curve."""
    bulk, fine = cm.pingpong_times(n, delay, cm.HELIOS_BULLX)
    assert fine >= bulk - 1e-12


def test_crossover_ordering_matches_paper():
    """Qualitative reproduction of Fig 5b/6b: element-granular messaging
    never crosses at realistic constants (documented discrepancy,
    EXPERIMENTS.md §Paper-repro), tile-granular crossover exists on
    machines with async progression and not on HELIOS."""
    for hw in (cm.HECTOR_XE6, cm.JUQUEEN_BGQ, cm.TPU_V5E):
        assert math.isfinite(
            cm.crossover_compute_chunked(1 << 20, 8, hw=hw))
    assert math.isinf(
        cm.crossover_compute_chunked(1 << 20, 8, hw=cm.HELIOS_BULLX))


def test_roofline_terms():
    t = cm.roofline(hlo_flops=197e12, hlo_bytes=819e9,
                    collective_bytes=50e9, n_chips=1)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.collective_s == pytest.approx(1.0)
    assert t.dominant in ("compute", "memory", "collective")


def test_selective_pingpong_model():
    """Fig 6a: sending fewer elements shrinks the MPI/MDMP gap."""
    hw = cm.HECTOR_XE6
    gaps = []
    for sent in (1024, 128, 16):
        bulk, fine = cm.pingpong_times(1024, 0.0, hw, sent_elements=sent)
        gaps.append(fine - bulk)
    assert gaps[0] > gaps[1] > gaps[2]


# -- halo aggregation (the managed message-aggregation knob) -----------------


@given(rows=st.integers(min_value=8, max_value=4096),
       cols=st.integers(min_value=16, max_value=4096),
       k=st.integers(min_value=1, max_value=32))
@settings(max_examples=200, deadline=None)
def test_halo_sweep_time_positive(rows, cols, k):
    t = cm.halo_sweep_time(k, rows, cols)
    assert t > 0 and math.isfinite(t)


@given(rows=st.integers(min_value=8, max_value=4096),
       cols=st.integers(min_value=16, max_value=4096))
@settings(max_examples=200, deadline=None)
def test_halo_decision_never_worse_than_bulk(rows, cols):
    """The manager must never pick a k it predicts to be slower than the
    bulk (k=1) schedule."""
    d = cm.decide_halo_aggregation(rows, cols, 8)
    assert d.aggregated_sweep_s <= d.bulk_sweep_s * (1 + 1e-9)
    assert d.k in d.per_sweep_s
    assert d.per_sweep_s[d.k] == min(d.per_sweep_s.values())


# -- attention schedule (bulk gather vs ulysses a2a vs ring streaming) -------


@given(batch=st.integers(min_value=1, max_value=32),
       s_local=st.sampled_from([128, 1024, 8192, 65536]),
       heads=st.sampled_from([8, 32, 128]),
       n=st.sampled_from([2, 4, 8, 16]),
       causal=st.booleans())
@settings(max_examples=100, deadline=None)
def test_attention_decision_is_argmin(batch, s_local, heads, n, causal):
    """decide_attention_schedule must pick the schedule it predicts to be
    fastest, and every modeled time must be positive and finite."""
    d = cm.decide_attention_schedule(batch, s_local, heads, max(1, heads // 4),
                                     128, heads * 128, n, causal=causal)
    assert d.schedule in ("bulk", "ulysses", "ring")
    assert set(d.times_s) == {"bulk", "ulysses", "ring"}
    for t in d.times_s.values():
        assert t > 0 and math.isfinite(t)
    assert d.chosen_s <= min(d.times_s.values()) * (1 + 1e-9)


@given(n=st.sampled_from([4, 8, 16]))
@settings(max_examples=20, deadline=None)
def test_attention_ring_wins_long_context(n):
    """Long-context prefill is the ring's home turf: per-step KV transfer
    hides under per-block flash, while the bulk schedule's sequence gather
    grows with S — the crossover the PR 2 tentpole is built on."""
    d = cm.decide_attention_schedule(1, 65536 // n, 32, 8, 128, 4096, n,
                                     causal=False)
    assert d.schedule == "ring"
    assert d.times_s["ring"] < d.times_s["bulk"]


def test_attention_force_schedule():
    for s in ("bulk", "ulysses", "ring"):
        d = cm.decide_attention_schedule(1, 1024, 32, 8, 128, 4096, 8,
                                         force_schedule=s)
        assert d.schedule == s


def test_attention_tiny_seq_prefers_gather():
    """At tiny sequence lengths the per-step alpha of the ring dominates;
    the manager must keep a gather-style schedule."""
    d = cm.decide_attention_schedule(1, 64, 8, 8, 64, 512, 8, causal=True)
    assert d.times_s["ring"] >= min(d.times_s["bulk"],
                                    d.times_s["ulysses"]) * (1 - 1e-9)


def test_halo_aggregation_prefers_deep_halos_when_latency_dominates():
    """Small local blocks on a high-alpha machine: per-message latency
    dominates, so the manager must aggregate (k > 1) — the MatlabMPI /
    MDMP latency-dominance regime."""
    d = cm.decide_halo_aggregation(128, 514, 8, hw=cm.TPU_V5E)
    assert d.k > 1
    assert d.mode == "aggregated"
    assert d.predicted_speedup > 1.0


def test_halo_aggregation_force_bulk():
    d = cm.decide_halo_aggregation(128, 514, 8, force_k=1)
    assert d.k == 1 and d.mode == "bulk"
    assert d.aggregated_sweep_s == pytest.approx(d.bulk_sweep_s)


def test_halo_aggregation_respects_block_height():
    """k can never exceed the local block (the ghost trapezoid would
    swallow the whole shard)."""
    d = cm.decide_halo_aggregation(4, 514, 8, candidate_k=(1, 2, 4, 8))
    assert d.k <= 4


def test_halo_terms_structure():
    """alpha amortises k x; halo bytes per sweep stay constant; redundant
    flops grow with k."""
    c1, m1, f1 = cm.halo_sweep_terms(1, 256, 514)
    c8, m8, f8 = cm.halo_sweep_terms(8, 256, 514)
    hw = cm.TPU_V5E
    assert c1 - c8 == pytest.approx(2 * hw.alpha_s * (1 - 1 / 8))
    assert m8 < m1                     # k x fewer HBM round-trips
    assert f8 > f1                     # ghost trapezoid is extra compute


def test_hardware_by_device_kind():
    """The managed layer prices a chip by its device kind; a kind missing
    from the table is an error, not another chip's constants."""
    assert cm.hardware_for("TPU v5 lite") is cm.TPU_V5E
    with pytest.raises(KeyError, match="no HardwareModel"):
        cm.hardware_for("TPU v9 imaginary")
    # the CPU backend rehearses the production target
    assert cm.device_hardware() is cm.DEFAULT_HW
