"""mdmplint — the static communication verifier (repro.analysis):
graph construction from the three truth sources, all five pass
families positive + negative, the lint corpus golden codes, the
launcher preflight modes, declaration-time axis validation
(UnknownAxisError / MDMP001), scan-body collective extraction with
trip counts, and the permutation bijection/ring properties of every
permute the repo constructs."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro import analysis
from repro.analysis.graph import (BufferAccess, CommGraph, InFlight,
                                  PermuteSite, WaitEdge, _KnobTable)
from repro.core import cost_model, instrument, managed
from repro.core.managed import _ring_perm
from repro.core.region import CommRegion, UnknownAxisError
from repro.parallel import smap
from repro.plan import CommOp, lower_collectives, train_geometry

CORPUS = os.path.join(os.path.dirname(__file__), "lint_corpus")


def _codes(diags):
    return sorted({d.code for d in diags})


# -- satellite: declaration-time axis validation ----------------------------


def test_typo_axis_raises_at_declaration():
    region = CommRegion("r", axis_sizes={"model": 4, "data": 2})
    with pytest.raises(UnknownAxisError) as ei:
        region.send("grads", axis="modle", shape=(16,),
                    dtype=jnp.float32)
    assert ei.value.axis == "modle"
    assert "MDMP001" in str(ei.value)
    assert region._specs == []          # nothing half-declared


def test_typo_axis_raises_for_subsystem_declarations():
    region = CommRegion("r", axis_sizes={"x": 8})
    with pytest.raises(UnknownAxisError):
        region.halo("h", axis="y", rows_local=32, cols=64,
                    dtype=jnp.float32)
    with pytest.raises(UnknownAxisError):
        region.moe("m", axis="pod", tokens_local=64, d_model=8,
                   n_experts=4, top_k=1, d_ff_expert=16,
                   dtype=jnp.float32)


def test_valid_declaration_captures_site():
    region = CommRegion("r", axis_sizes={"model": 4})
    region.send("kv", axis="model", shape=(16,), dtype=jnp.float32)
    spec = region._specs[0]
    assert spec.site is not None
    assert spec.site[0].endswith("test_analysis.py")
    ops = analysis.from_ops("r", axis_sizes=region.axis_sizes,
                            declared=region.lower()).declared
    assert ops[0].meta["site"][0].endswith("test_analysis.py")


# -- satellite: scan-body collective extraction with trip counts ------------


def test_scan_body_ppermute_extracted_once_with_trips():
    """A ring ppermute inside ``lax.scan`` must surface exactly once per
    logical site, carrying the scan's trip count — not dropped, not
    double-counted."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("x", "y"))
    LEN = 5

    def body(a):
        def step(carry, _):
            carry = lax.ppermute(carry, "x", [(0, 0)])
            return carry, carry.sum()
        out, sums = lax.scan(step, a, None, length=LEN)
        return out.sum() + sums.sum()

    f = smap(body, mesh, in_specs=(P("x"),), out_specs=P())
    rep = instrument.analyze_region(f, jnp.ones((4, 2), jnp.float32))
    perms = [c for c in rep.collectives if c.primitive == "ppermute"]
    assert len(perms) == 1              # one logical site
    assert perms[0].trips == LEN        # executed LEN times
    assert perms[0].nbytes == 4 * 2 * 4
    # bytes-by-axis prices the trips (the drift pass compares this
    # against declarations)
    assert rep.collective_bytes_by_axis()["x"] == LEN * 4 * 2 * 4
    # provenance survives into the lowered comm-IR op
    ops = lower_collectives(perms, {"x": 1})
    assert ops[0].meta["trips"] == LEN
    assert ops[0].meta["source"].endswith(
        f"test_analysis.py:{body.__code__.co_firstlineno + 2}")


def test_scan_carry_binders_align_with_closure_consts():
    """A scanned body that CLOSES OVER a constant: the sub-jaxpr gains
    constvars, and the carry/xs binder alignment must not slide (the
    binder-misalignment class) — the tracked operand's accesses inside
    the loop still resolve."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("x", "y"))
    big = jnp.arange(8, dtype=jnp.float32)        # becomes a const

    def body(a):
        def step(carry, _):
            carry = lax.ppermute(carry + big[:4].sum(), "x", [(0, 0)])
            return carry, ()
        out, _ = lax.scan(step, a, None, length=3)
        return out.sum()

    f = smap(body, mesh, in_specs=(P(None),), out_specs=P())
    rep = instrument.analyze_region(f, jnp.ones((4,), jnp.float32))
    perms = [c for c in rep.collectives if c.primitive == "ppermute"]
    assert len(perms) == 1 and perms[0].trips == 3


# -- satellite: bijection / ring-closure properties of repo permutes --------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _check_perm_properties(n: int, shift: int):
    perm = _ring_perm(n, shift)
    srcs, dsts = [a for a, _ in perm], [b for _, b in perm]
    # bijection on the axis
    assert sorted(srcs) == list(range(n))
    assert sorted(dsts) == list(range(n))
    # returns home after axis_size applications
    f = {a: b for a, b in perm}
    for start in range(n):
        i = start
        for _ in range(n):
            i = f[i]
        assert i == start
    # the analyzer agrees
    g = CommGraph("perm", {"ax": n})
    g.permutes = [PermuteSite("p", "ax", n, tuple(perm),
                              ring=(np.gcd(abs(shift) % n or n, n) == 1))]
    assert analysis.run_all(g) == []


def _perm_cases():
    """Every permutation family the repo constructs: ring attention
    fwd/bwd (shift +-1), pipeline fwd/bwd ticks (shift +-1 on the stage
    axis, incl. interleaved chunk wraps riding the same ring), and MoE
    stream chunks (shift s+1 forward, -s return)."""
    for n in range(1, 13):
        yield n, 1                      # ring attention kv / pipeline fwd
        yield n, -1                     # dk-dv ring / pipeline bwd
        for s in range(1, n):           # MoE stream forward shifts
            yield n, s


if HAVE_HYPOTHESIS:
    @given(n=st.integers(min_value=1, max_value=64),
           shift=st.integers(min_value=-64, max_value=64))
    @settings(max_examples=200, deadline=None)
    def test_ring_perm_properties(n, shift):
        _check_perm_properties(n, shift)
else:
    def test_ring_perm_properties():
        # deterministic sweep fallback: hypothesis is not installed in
        # this environment (and nothing may be pip-installed)
        for n, shift in _perm_cases():
            _check_perm_properties(n, shift)
        rng = np.random.default_rng(0)
        for _ in range(100):
            n = int(rng.integers(1, 65))
            _check_perm_properties(n, int(rng.integers(-64, 65)))


def test_moe_stream_pairs_compose_to_identity():
    """Expert-stream step s sends forward with shift s (cumulative) and
    returns results with shift -s — each pair must compose to the
    identity, and the analyzer's pair check must agree."""
    for n in range(2, 9):
        for s in range(1, n):
            fwd = {a: b for a, b in _ring_perm(n, s)}
            ret = {a: b for a, b in _ring_perm(n, -s)}
            assert all(ret[fwd[i]] == i for i in range(n))
    g = CommGraph("moe", {"model": 8})
    g.permutes = [PermuteSite(f"s{s}", "model", 8,
                              tuple(_ring_perm(8, s)), pair=(s, -s))
                  for s in range(1, 8)]
    assert analysis.run_all(g) == []
    g.permutes = [PermuteSite("bad", "model", 8,
                              tuple(_ring_perm(8, 2)), pair=(2, -3))]
    assert _codes(analysis.run_all(g)) == ["MDMP202"]


def test_pipeline_tick_perms_are_inverse_rings():
    s = 6
    fwd = {a: b for a, b in _ring_perm(s, 1)}     # act handoff
    bwd = {a: b for a, b in _ring_perm(s, -1)}    # grad handoff
    assert all(bwd[fwd[i]] == i for i in range(s))
    g = CommGraph("pipe", {"pod": s})
    g.permutes = analysis.derive_permutes(
        [CommOp(kind="pipeline", label="p", op_name="pipeline_schedule",
                axis="pod", axis_size=s, nbytes=1,
                meta={"n_layers": 12})], {"pod": s})
    assert {p.label for p in g.permutes} == {"p.fwd_tick", "p.bwd_tick"}
    assert analysis.run_all(g) == []


# -- the five pass families: positive + negative ----------------------------


def test_pass_axes():
    ok = CommOp(kind="all_reduce", label="g", op_name="all_reduce",
                axis="data", axis_size=2, nbytes=8)
    bad = CommOp(kind="all_reduce", label="g2", op_name="all_reduce",
                 axis="dta", axis_size=2, nbytes=8)
    assert analysis.check_axes(
        CommGraph("t", {"data": 2}, declared=[ok])) == []
    diags = analysis.check_axes(
        CommGraph("t", {"data": 2}, declared=[ok, bad]))
    assert _codes(diags) == ["MDMP001"] and diags[0].label == "g2"


def test_pass_drift():
    decl = [CommOp(kind="all_gather", label="kv", op_name="all_gather",
                   axis="model", axis_size=4, nbytes=1000)]
    traced_ok = [CommOp(kind="collective", label="ag#0",
                        op_name="all_gather", axis="model", axis_size=4,
                        nbytes=1000, meta={"trips": 2})]
    g = CommGraph("t", {"model": 4}, declared=decl, traced=traced_ok)
    assert analysis.check_drift(g) == []
    # trips push the traced bytes past the 4x tolerance -> MDMP102
    g.traced = [CommOp(kind="collective", label="ag#0",
                       op_name="all_gather", axis="model", axis_size=4,
                       nbytes=1000, meta={"trips": 9})]
    assert _codes(analysis.check_drift(g)) == ["MDMP102"]
    # traffic on an undeclared axis -> MDMP101
    g.traced.append(CommOp(kind="collective", label="ps#1",
                           op_name="all_reduce", axis="data",
                           axis_size=2, nbytes=64))
    assert "MDMP101" in _codes(analysis.check_drift(g))
    # declared axis with no traced traffic -> MDMP103 (warning)
    g2 = CommGraph("t", {"model": 4, "data": 2},
                   declared=decl + [CommOp(
                       kind="all_reduce", label="gr",
                       op_name="all_reduce", axis="data", axis_size=2,
                       nbytes=64)],
                   traced=traced_ok)
    d = analysis.check_drift(g2)
    assert _codes(d) == ["MDMP103"]
    assert all(x.severity == "warning" for x in d)
    # direct-collective family mismatch -> MDMP104 (warning)
    g3 = CommGraph("t", {"model": 4},
                   declared=[CommOp(kind="all_to_all", label="a2a",
                                    op_name="all_to_all", axis="model",
                                    axis_size=4, nbytes=1000)],
                   traced=traced_ok)
    assert _codes(analysis.check_drift(g3)) == ["MDMP104"]
    # no trace at all -> nothing to drift against
    assert analysis.check_drift(
        CommGraph("t", {"model": 4}, declared=decl)) == []


def test_pass_permutes():
    g = CommGraph("t", {"model": 4})
    g.permutes = [PermuteSite("ok", "model", 4,
                              tuple(_ring_perm(4)), ring=True)]
    assert analysis.check_permutes(g) == []
    g.permutes = [PermuteSite("dup", "model", 4,
                              ((0, 1), (1, 1), (2, 3), (3, 0)))]
    assert _codes(analysis.check_permutes(g)) == ["MDMP201"]
    g.permutes = [PermuteSite("swap", "model", 4,
                              ((0, 1), (1, 0), (2, 3), (3, 2)),
                              ring=True)]
    assert _codes(analysis.check_permutes(g)) == ["MDMP202"]
    # shift-2 ring on even n splits into two orbits -> not a full cycle
    g.permutes = [PermuteSite("even", "model", 4,
                              tuple(_ring_perm(4, 2)), ring=True)]
    assert _codes(analysis.check_permutes(g)) == ["MDMP202"]


def test_pass_ordering():
    a = CommOp(kind="all_gather", label="a", op_name="all_gather",
               axis="model", axis_size=4, nbytes=8, window=(0.0, 0.5))
    b = CommOp(kind="all_gather", label="b", op_name="all_gather",
               axis="model", axis_size=4, nbytes=8, window=(0.2, 0.7))
    g = CommGraph("t", {"model": 4}, declared=[a, b])
    assert analysis.check_ordering(g) == []       # serialized, acyclic
    # b's wire serializes after a, but a waits on b -> deadlock
    g.waits = [WaitEdge("b", "a", "a gates on b's arrival")]
    d = analysis.check_ordering(g)
    assert _codes(d) == ["MDMP301"] and "a" in d[0].message
    # pure wait cycle with no windows at all
    g2 = CommGraph("t", {"model": 4})
    g2.waits = [WaitEdge("x", "y"), WaitEdge("y", "z"),
                WaitEdge("z", "x")]
    assert _codes(analysis.check_ordering(g2)) == ["MDMP301"]


def test_pass_overlap():
    g = CommGraph("t", {"x": 8})
    g.inflight = [InFlight("ghost", 0.1, 0.5, "halo.xfer")]
    g.accesses = [BufferAccess("ghost", 0.7, "read", "sweep")]
    assert analysis.check_overlap(g) == []        # read after landing
    g.accesses = [BufferAccess("ghost", 0.3, "read", "sweep")]
    assert _codes(analysis.check_overlap(g)) == ["MDMP401"]
    g.accesses = [BufferAccess("ghost", 0.3, "write", "sweep")]
    assert _codes(analysis.check_overlap(g)) == ["MDMP402"]
    # two overlapping in-flight claims on one buffer (donation hazard)
    g.accesses = []
    g.inflight.append(InFlight("ghost", 0.4, 0.9, "halo.xfer2"))
    assert _codes(analysis.check_overlap(g)) == ["MDMP402"]


def test_pass_feasibility():
    moe = CommOp(kind="moe", label="m", op_name="moe_dispatch",
                 axis="model", axis_size=4, nbytes=1,
                 meta={"tokens_local": 64, "top_k": 2, "n_experts": 4,
                       "capacity_factor": 1.0})    # capacity C = 32
    pipe = CommOp(kind="pipeline", label="p", op_name="pipeline_schedule",
                  axis="pod", axis_size=2, nbytes=1,
                  meta={"local_batch": 8, "n_layers": 4,
                        "batch_bytes": 1 << 30})
    halo = CommOp(kind="halo", label="h", op_name="halo_aggregation",
                  axis="x", axis_size=4, nbytes=1,
                  meta={"rows_local": 16, "cols": 64})
    sizes = {"model": 4, "pod": 2, "x": 4}
    good = _KnobTable({"moe_dispatch|model": {"mode": "stream",
                                              "chunks": 4},
                       "pipeline_schedule|pod": {"mode": "1f1b",
                                                 "chunks": 4},
                       "halo_aggregation|x": {"mode": "aggregated",
                                              "chunks": 8}})
    g = CommGraph("t", sizes, declared=[moe, pipe, halo], plan=good,
                  stash_cap_bytes=1 << 40)
    assert analysis.check_feasibility(g) == []
    bad = _KnobTable({"moe_dispatch|model": {"mode": "stream",
                                             "chunks": 5},
                      "pipeline_schedule|pod": {"mode": "interleaved",
                                                "chunks": 3,
                                                "virtual": 2},
                      "halo_aggregation|x": {"mode": "aggregated",
                                             "chunks": 64}})
    g.plan = bad
    g.stash_cap_bytes = 1 << 20
    codes = [d.code for d in analysis.check_feasibility(g)]
    assert codes.count("MDMP501") == 1            # 32 % 5 != 0
    assert codes.count("MDMP502") == 2            # 8 % 3, 3 % S=2
    assert codes.count("MDMP503") == 1            # stash over 1MB cap
    assert codes.count("MDMP504") == 1            # k=64 > 16 rows
    # no plan -> feasibility has nothing to check
    g.plan = None
    assert analysis.check_feasibility(g) == []


# -- the golden corpus -------------------------------------------------------


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(CORPUS, "*.json"))), ids=os.path.basename)
def test_lint_corpus_golden_codes(path):
    """Every deliberately-broken corpus config yields EXACTLY its golden
    diagnostic codes (and clean.json yields none)."""
    with open(path) as f:
        case = json.load(f)
    graph = analysis.from_corpus(case)
    diags = analysis.run_all(graph)
    assert _codes(diags) == sorted(set(case["expect"]))
    assert analysis.exit_code(diags) == (
        1 if any(analysis.CODES[c][0] == "error"
                 for c in case["expect"]) else 0)


def test_lint_cli_on_corpus(capsys):
    from repro.launch import lint
    rc = lint.main(["--case",
                    os.path.join(CORPUS, "nondivisor_g.json"), "-v"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "MDMP501" in out and "fix      |" in out
    rc = lint.main(["--case", os.path.join(CORPUS, "clean.json")])
    assert rc == 0
    assert "clean (0 diagnostics)" in capsys.readouterr().out


def test_lint_cli_train_geometry(capsys):
    """The launcher-preflight path: geometry-only lint of a pipelined
    train config (no devices needed) comes back clean."""
    from repro.launch import lint
    rc = lint.main(["--target", "train", "--arch", "granite-34b",
                    "--reduced", "--mesh", "2x2x2", "--pipeline", "1f1b",
                    "--batch", "8", "--seq", "32"])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


# -- preflight modes ---------------------------------------------------------


def _broken_graph():
    g = CommGraph("broken", {"model": 4})
    g.permutes = [PermuteSite("dup", "model", 4,
                              ((0, 1), (1, 1), (2, 3), (3, 0)))]
    return g


def test_preflight_off_and_warn_and_strict():
    assert analysis.preflight(_broken_graph(), "off",
                              out=lambda s: None) == []
    managed.clear_decision_log()
    diags = analysis.preflight(_broken_graph(), "warn",
                               out=lambda s: None)
    assert _codes(diags) == ["MDMP201"]
    recs = [r for r in managed.decision_log() if r.op == "lint"]
    assert len(recs) == 1
    assert recs[0].chunks == 1 and recs[0].nbytes == 1   # 1 diag, 1 err
    with pytest.raises(SystemExit):
        analysis.preflight(_broken_graph(), "strict",
                           out=lambda s: None)
    # strict on a clean graph does not raise
    clean = CommGraph("clean", {"model": 4})
    assert analysis.preflight(clean, "strict", out=lambda s: None) == []


def test_strict_renders_side_by_side():
    lines = []
    decl = [CommOp(kind="all_gather", label="kv", op_name="all_gather",
                   axis="model", axis_size=4, nbytes=100,
                   meta={"site": ("src/repro/x.py", 7)})]
    traced = [CommOp(kind="collective", label="ag#0",
                     op_name="all_gather", axis="model", axis_size=4,
                     nbytes=100, meta={"trips": 99,
                                       "source": "src/repro/x.py:52"})]
    g = CommGraph("t", {"model": 4}, declared=decl, traced=traced)
    with pytest.raises(SystemExit):
        analysis.preflight(g, "strict", out=lines.append)
    text = "\n".join(lines)
    assert "declared |" in text and "traced   |" in text
    assert "src/repro/x.py:52" in text      # file:line provenance


# -- graph construction from a real region + trace --------------------------


def test_graph_from_region_trace_and_plan():
    """End-to-end over the three truth sources: declare, trace, plan —
    an undeclared collective in the trace surfaces as MDMP101 with its
    eqn provenance."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("x", "y"))
    region = CommRegion("r", axis_sizes={"x": 1, "y": 1})
    region.send("gathered", axis="x", shape=(4, 2), dtype=jnp.float32)

    def body(a, b):
        g = lax.all_gather(a, "x", tiled=True)
        s = lax.psum(b, "y")                 # never declared
        return g.sum() + s.sum()

    f = smap(body, mesh, in_specs=(P("x"), P(None)), out_specs=P())
    rep = instrument.analyze_region(f, jnp.ones((4, 2), jnp.float32),
                                    jnp.ones((3,), jnp.float32))
    graph = analysis.from_ops(
        "r", axis_sizes=region.axis_sizes, declared=region.lower(),
        traced=lower_collectives(rep.collectives, region.axis_sizes))
    diags = analysis.run_all(graph)
    undecl = [d for d in diags if d.code == "MDMP101"]
    assert len(undecl) == 1 and undecl[0].axis == "y"
    assert "test_analysis.py" in str(undecl[0].site)


def test_train_geometry_matches_launcher_shapes():
    from repro import configs
    cfg = configs.get_reduced("granite-34b")
    hw = managed.get_config().hw
    geo = train_geometry(cfg, mesh_axes={"pod": 2, "data": 2,
                                         "model": 2},
                         batch=8, seq=32, hw=hw, pipeline="1f1b")
    assert geo["pipeline"]["local_batch"] == 4     # 8 // dp=2
    assert geo["pipeline"]["candidate_micro"] == (1, 2, 4)
    assert geo["grad_bytes"] == int(cfg.param_count()) * 4
    from repro.plan import lower_train_ops
    ops = lower_train_ops(mesh_axes=geo["mesh_axes"],
                          grad_bytes=geo["grad_bytes"],
                          pipeline=geo["pipeline"],
                          attention=geo["attention"], moe=geo["moe"])
    assert {o.kind for o in ops} >= {"pipeline", "all_reduce"}
