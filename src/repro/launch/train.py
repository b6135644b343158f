"""Training driver CLI.

    PYTHONPATH=src python -m repro.launch.train --arch granite-34b \
        --reduced --steps 20 [--mdmp-mode auto|bulk|interleaved] [--resume]

Full (non-reduced) configs need a real TPU slice; on this host use
--reduced (the same code path at toy scale).
"""

from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import MeshCtx
from repro.train.train_loop import TrainLoop, TrainLoopConfig, \
    build_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mdmp-mode", default="auto",
                    choices=["auto", "bulk", "interleaved"])
    ap.add_argument("--pipeline", default="none",
                    choices=["none", "gpipe", "1f1b", "interleaved",
                             "auto"],
                    help="run the pod axis as pipeline stages (auto = "
                         "managed schedule decision)")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="pipeline microbatch count M (default: the "
                         "cost model's pick)")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["bulk", "stream", "dense", "auto"],
                    help="MoE expert-dispatch schedule (auto = managed "
                         "cost-model decision, logged per layer)")
    ap.add_argument("--plan", default="local",
                    choices=["local", "program", "auto"],
                    help="communication planning scope: 'local' keeps "
                         "per-subsystem resolution; 'program'/'auto' run "
                         "the whole-program planner (repro.plan) over the "
                         "step's comm set and install the coordinated "
                         "ProgramPlan before tracing")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 (data x model) or 2x2x2 "
                         "(pod x data x model); default = all devices "
                         "on data")
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-pod", action="store_true")
    ap.add_argument("--ckpt-every", default=None,
                    help="checkpoint interval in steps, or 'auto' for "
                         "the managed Young/Daly cadence (re-resolved "
                         "online from measured step time + write bw)")
    ap.add_argument("--mtbf", type=float, default=1800.0,
                    help="assumed mean time between failures, seconds "
                         "(feeds the Young/Daly cadence)")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault injection spec, e.g. "
                         "'transient@6;slow@9:0.5;corrupt@14' "
                         "(core/faults.py grammar)")
    ap.add_argument("--verify", default="warn",
                    choices=["off", "warn", "strict"],
                    help="static-verifier preflight (repro.analysis): "
                         "'warn' prints findings and logs a "
                         "DecisionRecord(op=\"lint\"); 'strict' exits "
                         "non-zero on any error with the declared/"
                         "traced side-by-side")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record every hot path to a Chrome-trace JSON "
                         "(open in ui.perfetto.dev), print the "
                         "predicted-vs-measured calibration report, and "
                         "embed the calibration ledger in the file")
    args = ap.parse_args()
    use_compile_cache()

    if args.trace:
        # install before anything resolves so planner/lint/step spans
        # and decision timestamps all land on one ring
        from repro import obs
        obs.install_tracer(obs.Tracer())

    import dataclasses
    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    if args.moe_dispatch is not None:
        if cfg.moe is None:
            ap.error(f"--moe-dispatch set but {args.arch} has no MoE "
                     "layers")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, dispatch=args.moe_dispatch))
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = (("pod", "data", "model") if len(dims) == 3
                else ("data", "model"))
    elif args.pipeline != "none":
        dims = (jax.device_count(), 1, 1)
        axes = ("pod", "data", "model")
    else:
        dims = (jax.device_count(), 1)
        axes = ("data", "model")
    if args.pipeline != "none" and "pod" not in axes:
        ap.error("--pipeline needs a pod axis: pass a 3-axis --mesh "
                 "like 2x2x2 (pod x data x model)")
    mesh = jax.make_mesh(dims, axes)
    ctx = MeshCtx.from_mesh(mesh, mdmp_mode=args.mdmp_mode)
    model = Model(cfg, ctx)
    print(f"arch={args.arch} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dims} mdmp={args.mdmp_mode} pipeline={args.pipeline}")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                          total_steps=args.steps,
                          moment_dtype=cfg.moment_dtype)
    from repro.core import managed as managed_lib
    from repro.core.tuner import ScheduleTuner
    managed_lib.clear_decision_log()
    tuner = ScheduleTuner()
    prog = None
    if args.plan != "local" or args.verify != "off":
        # Lower this step's communication set to comm-IR ops once —
        # the whole-program planner (--plan) and the static-verifier
        # preflight (--verify) both consume it, so the linted program
        # is exactly the planned one.
        from repro.plan import (lower_train_ops, plan_program,
                                train_geometry)
        hw = managed_lib.get_config().hw
        geo = train_geometry(cfg, mesh_axes=dict(ctx.axis_sizes),
                             batch=args.batch, seq=args.seq, hw=hw,
                             pipeline=args.pipeline)
        ops = lower_train_ops(
            mesh_axes=geo["mesh_axes"], grad_bytes=geo["grad_bytes"],
            pipeline=geo["pipeline"], attention=geo["attention"],
            moe=geo["moe"])
        prog = plan_program(ops, hw=hw,
                            notes=[f"launch.train {args.arch}"])
    if args.plan != "local":
        # Whole-program pass: price the JOINT schedule and install the
        # plan so every resolve_* call below prefers the coordinated
        # knob.
        kind = "coordinated" if prog.coordinated else "local"
        print(f"decision program_plan({kind} ops={len(prog.choices)} "
              f"topo={prog.topology} "
              f"local-concat={prog.local_solo_sum_s * 1e6:.1f}us "
              f"joint={prog.joint_cost_s * 1e6:.1f}us)")
        for line in prog.summary().splitlines()[1:]:
            print(f"  trail{line}")
        tuner.store_program_plan(prog)
        managed_lib.install_plan(prog)
    if args.verify != "off":
        # Static-verifier preflight: drift/permute/deadlock/race/
        # feasibility passes over the lowered comm set under the knobs
        # this launch will actually run (forced flags override the
        # plan's picks, so strict mode catches the clamp BEFORE the
        # executor silently degrades it).
        from repro import analysis
        key = "pipeline_schedule|pod"
        if args.microbatches is not None:
            knob = dict(prog.knobs.get(key)
                        or {"mode": args.pipeline, "virtual": 2})
            knob["chunks"] = args.microbatches
            if args.pipeline not in ("none", "auto"):
                knob["mode"] = args.pipeline
            prog.knobs[key] = knob
        elif args.pipeline not in ("none", "auto") and key in prog.knobs:
            prog.knobs[key] = dict(prog.knobs[key],
                                   mode=args.pipeline)
        graph = analysis.from_ops(
            f"train:{args.arch}", axis_sizes=dict(ctx.axis_sizes),
            declared=ops, plan=prog, hw=hw)
        analysis.preflight(graph, args.verify)
    step_fn, pshard, bshard = build_train_step(
        model, opt_cfg, mesh, compress_pod=args.compress_pod,
        pipeline=args.pipeline, pipe_microbatches=args.microbatches,
        global_batch=args.batch, seq_len=args.seq)
    for rec in managed_lib.decision_log():
        if rec.op == "pipeline_schedule":
            print(f"decision pipeline_schedule({rec.mode} M={rec.chunks} "
                  f"axis={rec.axis} handoff={rec.nbytes/1e3:.1f}kB "
                  f"bulk={rec.predicted_bulk_s*1e3:.2f}ms "
                  f"chosen={rec.predicted_interleaved_s*1e3:.2f}ms)")
    data = SyntheticLMData(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=args.seq,
                                      global_batch=args.batch))
    managed_cadence = args.ckpt_every == "auto"
    ckpt_every = (max(5, args.steps // 4)
                  if args.ckpt_every in (None, "auto")
                  else int(args.ckpt_every))
    from repro.core.faults import FaultPlan
    fault_plan = (FaultPlan.parse(args.fault_plan)
                  if args.fault_plan else None)
    loop = TrainLoop(step_fn, model, opt_cfg, data,
                     TrainLoopConfig(total_steps=args.steps,
                                     ckpt_every=ckpt_every,
                                     ckpt_dir=args.ckpt,
                                     managed_cadence=managed_cadence,
                                     mtbf_s=args.mtbf),
                     pshard, bshard, tuner=tuner,
                     fault_plan=fault_plan)
    params, opt, s0 = (loop.resume_or_init() if args.resume
                       else loop.init_state())
    out = loop.run(params, opt, s0)
    for rec in managed_lib.decision_log():
        if rec.op == "ckpt_interval":
            print(f"decision ckpt_interval({rec.mode} N={rec.chunks} "
                  f"axis={rec.axis} snap={rec.nbytes/1e6:.1f}MB "
                  f"fixed_ovh={rec.predicted_bulk_s:.4f} "
                  f"chosen_ovh={rec.predicted_interleaved_s:.4f})")
    for r in out["replayed"]:
        print(f"replan {r['op']}: {r['mode']}:{r['chunks']} "
              f"{r['axis']}{r['old_n']} -> {r['axis']}{r['new_n']}")
    if fault_plan is not None:
        left = fault_plan.unfired()
        print(f"faults injected={len(fault_plan.events) - len(left)} "
              f"unfired={len(left)} restarts={out['restarts']} "
              f"steps_executed={out['steps_executed']}")
    if args.moe_dispatch is not None:
        # the dispatch decision fires at trace time (first step); print
        # the unique trail entries the managed runtime logged
        seen = set()
        for rec in managed_lib.decision_log():
            key = (rec.op, rec.mode, rec.chunks, rec.nbytes)
            if rec.op == "moe_dispatch" and key not in seen:
                seen.add(key)
                print(f"decision moe_dispatch({rec.mode} g={rec.chunks} "
                      f"axis={rec.axis} a2a={rec.nbytes/1e3:.1f}kB "
                      f"bulk={rec.predicted_bulk_s*1e3:.3f}ms "
                      f"chosen={rec.predicted_interleaved_s*1e3:.3f}ms)")
    for h in out["history"][:: max(1, len(out["history"]) // 10)]:
        print(f"  step {h['step']:4d} loss {h['loss']:.4f} "
              f"{h['time_s']:.2f}s")
    print(f"done at step {out['step']}, final loss "
          f"{out['history'][-1]['loss']:.4f}")
    if args.trace:
        from repro import obs
        tr = obs.get_tracer()
        decisions = managed_lib.decision_log()
        # jit-interior decisions (attention/halo/MoE/pipeline modes) have
        # no host-side span of their own — the train.step span covers the
        # XLA program they were compiled into
        obs.cover_with(tr.spans(), "train.step",
                       (r.op for r in decisions))
        led = obs.CalibrationLedger()
        led.correlate(tr.spans(), decisions)
        print(led.report())
        obs.write_chrome_trace(
            args.trace, tr, decisions,
            other_data={"run": f"train:{args.arch}",
                        "calibration": led.snapshot()})
        print(f"trace: {args.trace} ({tr.n_spans} spans, "
              f"{len(decisions)} decisions, "
              f"coverage {led.coverage() * 100:.0f}%)")


if __name__ == "__main__":
    main()
