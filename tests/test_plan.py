"""Whole-program communication planner (repro.plan): comm-IR lowering,
joint pricing under shared constraints, the coordinate-descent search,
plan-override precedence in the managed resolvers, and persistence
through the ScheduleTuner."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import cost_model, instrument, managed
from repro.core.region import CommRegion
from repro.core.tuner import ScheduleTuner, replan_program_plans
from repro.parallel import smap
from repro.plan import (CommOp, candidates_for, crosscheck_collectives,
                        lower_collectives, plan_program)
from repro.plan.planner import ProgramPlan, contention_sets, joint_cost


# -- the conflict geometry: two subsystems contending on one axis -----------
#
# Attention's ring streaming owns a huge flash-compute hide (the pooled
# overlap donor); the MoE stream's local pick buys back almost nothing
# under the SHARED account but pays ~steps*(2+g) dispatch alphas.  The
# joint pass should back the MoE off to bulk while keeping the ring.

N_AXIS = 8


def conflict_ops():
    att = CommOp(kind="attention", label="conflict.attention",
                 op_name="attention_schedule", axis="model",
                 axis_size=N_AXIS,
                 nbytes=2 * 4 * 2048 * 2 * 128 * 2, dtype_bytes=2,
                 phase="fwd", window=(0.0, 0.6),
                 meta={"batch": 4, "s_local": 2048, "heads": 32,
                       "kv_heads": 2, "head_dim": 128, "d_model": 4096,
                       "causal": True})
    cap = cost_model.moe_capacity(1024, 2, 16, 1.25)
    moe = CommOp(kind="moe", label="conflict.moe",
                 op_name="moe_dispatch", axis="model", axis_size=N_AXIS,
                 nbytes=16 * cap * 2048 * 2, dtype_bytes=2,
                 phase="fwd", window=(0.1, 0.7),
                 meta={"tokens_local": 1024, "d_model": 2048,
                       "n_experts": 16, "top_k": 2, "d_ff_expert": 512,
                       "capacity_factor": 1.25, "mults": 3})
    return [att, moe]


# -- satellite 1: the DecisionRecord op-name registry ------------------------


def test_registry_rejects_unknown_op():
    with pytest.raises(AssertionError):
        managed.log_decision(managed.DecisionRecord(
            op="not_a_registered_op", axis="x", nbytes=0, mode="bulk",
            chunks=1, predicted_bulk_s=0.0, predicted_interleaved_s=0.0))


def test_every_subsystem_op_is_registered():
    """Exercise every resolver entry point and assert each logged op name
    is in the central registry."""
    managed.clear_decision_log()
    managed.resolve_halo_aggregation("x", 4, 256, 256)
    managed.resolve_attention_schedule("model", 4, 2, 128, 8, 8, 64, 512)
    managed.resolve_pipeline_schedule("pod", 2, 1e-3, 1 << 20)
    managed.resolve_moe_dispatch("model", 4, 256, 128, 8, 2, 256)
    managed.resolve_serve_schedule("serve", 4, 16.0, 16.0, 1e8)
    managed.resolve_preempt("serve", 2, 1 << 16, 16, 1e8)
    managed.resolve_checkpoint("host", 0.1, 1 << 24)
    plan_program(conflict_ops())
    log = managed.decision_log()
    assert {r.op for r in log} >= {
        "halo_aggregation", "attention_schedule", "pipeline_schedule",
        "moe_dispatch", "serve_schedule", "preempt_policy",
        "ckpt_interval", "program_plan"}
    for r in log:
        assert r.op in managed.DECISION_OPS, r.op
    managed.clear_decision_log()


# -- satellite 2: collective extraction records axis + bytes -----------------


def test_instrument_extracts_two_axes():
    """A jaxpr with two collectives on DIFFERENT mesh axes: the walk must
    record each one's axis name and payload bytes."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("x", "y"))

    def body(a, b):
        g = lax.all_gather(a, "x", tiled=True)
        s = lax.psum(b, "y")
        return g.sum() + s.sum()

    f = smap(body, mesh, in_specs=(P("x"), P(None)), out_specs=P())
    rep = instrument.analyze_region(f, jnp.ones((4, 2), jnp.float32),
                                    jnp.ones((3,), jnp.float32))
    got = {(c.primitive, c.axis): c.nbytes for c in rep.collectives}
    assert got[("all_gather", "x")] == 4 * 2 * 4
    assert got[("psum", "y")] == 3 * 4
    by_axis = rep.collective_bytes_by_axis()
    assert by_axis["x"] == 32 and by_axis["y"] == 12


# -- IR lowering --------------------------------------------------------------


def test_lower_region_and_windows():
    region = CommRegion("r", axis_sizes={"model": 4})
    region.attention("attn", axis="model", batch=2, s_local=256, heads=8,
                     kv_heads=8, head_dim=64, d_model=512,
                     dtype=jnp.bfloat16)
    region.moe("moe", axis="model", tokens_local=512, d_model=512,
               n_experts=8, top_k=2, d_ff_expert=256, dtype=jnp.bfloat16)
    ops = region.lower()
    assert [o.op_name for o in ops] == ["attention_schedule",
                                        "moe_dispatch"]
    assert all(o.axis == "model" and o.axis_size == 4 and o.nbytes > 0
               for o in ops)
    # default windows overlap -> one contention set
    assert contention_sets(ops) == [[0, 1]]
    for o in ops:
        o2 = CommOp.from_dict(o.to_dict())
        assert o2 == o


def test_lower_collectives_and_crosscheck():
    recs = [instrument.CollectiveRecord("all_gather", "x", 4096, 2),
            instrument.CollectiveRecord("psum", "y", 1024, 5)]
    ops = lower_collectives(recs, {"x": 4, "y": 2}, max_depth=8)
    assert {(o.op_name, o.axis) for o in ops} == {("all_gather", "x"),
                                                  ("all_reduce", "y")}
    # declared ops on axis "x" only; the traced psum on "y" must surface
    # as a discrepancy note
    rep = instrument.RegionReport(records={}, total_eqns=8,
                                  collectives=recs)
    notes = crosscheck_collectives([ops[0]], rep)
    assert any("y" in n for n in notes)


# -- satellite 3 (modeled half): the joint pass beats local concatenation ----


def test_planner_coordinates_conflicting_regions():
    managed.clear_decision_log()
    plan = plan_program(conflict_ops())
    assert plan.coordinated, plan.summary()
    # the coordinated joint cost strictly beats BOTH the local picks under
    # shared constraints and the concatenation of local plans
    assert plan.joint_cost_s < plan.local_joint_cost_s
    assert plan.joint_cost_s < plan.local_solo_sum_s
    moe = next(c for c in plan.choices if c.op.op_name == "moe_dispatch")
    att = next(c for c in plan.choices
               if c.op.op_name == "attention_schedule")
    # locally the MoE streams; jointly it backs off to bulk because the
    # ring attention is the pooled overlap donor
    assert moe.local_knob["mode"] == "stream"
    assert moe.knob["mode"] == "bulk"
    assert att.knob["mode"] == "ring"
    # the trail: one DecisionRecord per op plus the program_plan summary
    log = managed.decision_log()
    summary = [r for r in log if r.op == "program_plan"]
    assert len(summary) == 1 and summary[0].mode == "coordinated"
    assert summary[0].chunks == 2
    assert {r.op for r in log} >= {"attention_schedule", "moe_dispatch"}
    managed.clear_decision_log()


def test_joint_cost_singleton_matches_solo():
    """A one-op program prices identically under joint and solo rules —
    the shared-constraint model degrades gracefully."""
    op = conflict_ops()[1]
    cands = candidates_for(op)
    hw = managed.get_config().hw
    for c in cands:
        assert joint_cost([op], [c], hw=hw) == pytest.approx(
            c.solo_s(hw.alpha_s), rel=1e-12)


def test_disjoint_windows_no_contention():
    """Ops on the same axis with DISJOINT windows (or different axes)
    never share an account: the planner keeps both local picks."""
    a, b = conflict_ops()
    b2 = CommOp.from_dict({**b.to_dict(), "window": [0.7, 1.0]})
    assert contention_sets([a, b2]) == [[0], [1]]
    plan = plan_program([a, b2], log=False)
    assert not plan.coordinated
    assert plan.joint_cost_s == pytest.approx(plan.local_solo_sum_s,
                                              rel=1e-9)


def test_stash_cap_forces_feasible_plan():
    """An infeasible pooled-stash assignment prices to inf, so the
    search lands on a feasible one."""
    op = CommOp(kind="pipeline", label="p", op_name="pipeline_schedule",
                axis="pod", axis_size=4, nbytes=1 << 20, phase="step",
                window=(0.0, 1.0),
                meta={"n_layers": 8, "batch_fwd_s": 1e-3,
                      "batch_bytes": float(1 << 20),
                      "candidate_micro": (4, 8)})
    plan = plan_program([op], stash_cap_bytes=1 << 30, log=False)
    assert plan.joint_cost_s < float("inf")
    chosen = plan.choices[0].knob
    assert chosen["mode"] in ("gpipe", "1f1b", "interleaved")


# -- plan-override precedence in the managed resolvers -----------------------


def test_resolvers_prefer_installed_plan():
    plan = plan_program(conflict_ops(), log=False)
    with managed.use_plan(plan):
        d = managed.resolve_moe_dispatch("model", N_AXIS, 1024, 2048, 16,
                                         2, 512, dtype_bytes=2)
        assert d.schedule == plan.knob_for("moe_dispatch",
                                           "model")["mode"]
        a = managed.resolve_attention_schedule(
            "model", N_AXIS, 4, 2048, 32, 2, 128, 4096, dtype_bytes=2)
        assert a.schedule == "ring"
        # an explicit caller pin still wins over the plan
        d2 = managed.resolve_moe_dispatch("model", N_AXIS, 1024, 2048,
                                          16, 2, 512, dtype_bytes=2,
                                          schedule="stream")
        assert d2.schedule == "stream"
        # the plan has no opinion on other axes -> local resolution
        d3 = managed.resolve_moe_dispatch("other", N_AXIS, 1024, 2048,
                                          16, 2, 512, dtype_bytes=2)
        assert d3.schedule in ("bulk", "stream", "dense")
    assert managed.active_plan() is None


def test_no_plan_behaviour_unchanged():
    """Without an installed plan the resolvers answer exactly as before
    the planner existed (local behaviour is the default)."""
    before = managed.resolve_moe_dispatch("model", N_AXIS, 1024, 2048,
                                          16, 2, 512, dtype_bytes=2)
    assert managed.active_plan() is None
    after = managed.resolve_moe_dispatch("model", N_AXIS, 1024, 2048,
                                         16, 2, 512, dtype_bytes=2)
    assert (before.schedule, before.g) == (after.schedule, after.g)


# -- persistence: the tuner stores and re-plans program plans ----------------


def test_tuner_roundtrip_and_replan(tmp_path):
    plan = plan_program(conflict_ops(), log=False)
    t = ScheduleTuner()
    t.store_program_plan(plan)
    key = ScheduleTuner.program_plan_key(plan.signature, plan.topology)
    assert key in t.program_plans
    path = tmp_path / "tuner.json"
    t.save(str(path))
    t2 = ScheduleTuner()
    t2.load(str(path))
    got = t2.get_program_plan(plan.signature, plan.topology)
    assert isinstance(got, ProgramPlan)
    assert got.knobs == plan.knobs
    assert got.joint_cost_s == pytest.approx(plan.joint_cost_s)

    # topology change: the stored plan re-prices on the new mesh and the
    # replay trail reports it as a program_plan record
    recs = replan_program_plans(t2, {"model": 4})
    assert recs and all(r["op"] == "program_plan" for r in recs)
    new_keys = [k for k in t2.program_plans if "model4" in k]
    assert new_keys, list(t2.program_plans)


def test_program_plan_serialization_roundtrip():
    plan = plan_program(conflict_ops(), log=False)
    d = plan.to_dict()
    back = ProgramPlan.from_dict(d)
    assert back.signature == plan.signature
    assert back.topology == plan.topology
    assert back.knobs == plan.knobs
    assert back.coordinated == plan.coordinated
    assert [c.knob for c in back.choices] == [c.knob for c in plan.choices]
    assert back.knob_for("moe_dispatch", "model") == \
        plan.knob_for("moe_dispatch", "model")
