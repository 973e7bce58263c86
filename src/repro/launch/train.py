"""End-to-end pipeline-parallel training driver.

CPU quickstart (8 virtual devices, reduced model):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.train \\
        --arch llama3.2-1b --reduced --data 2 --stages 2 --tensor 2 \\
        --steps 200 --batch 8 --seq 128

On real hardware drop ``--reduced`` and size the mesh to the pod
(``--data 16 --stages 8 --tensor 2`` etc.).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import (checkpoint_meta, layout_dict,
                              reshard_checkpoint, restore_checkpoint,
                              save_checkpoint)
from repro.configs import resolve_config
from repro.data import SyntheticLM
from repro.data.synthetic import shard_batch
from repro.optim import AdamW, warmup_cosine
from repro.pipeline import runtime as RT
from repro.pipeline import stage as ST


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-test widths (d_model 256, <= 4 heads, "
                         "vocab <= 1024)")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth; without --reduced every width stays as "
                         "published")
    ap.add_argument("--d-model", type=int, default=0,
                    help="with --reduced: the reduced d_model")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--stages", type=int, default=0)
    ap.add_argument("--tensor", type=int, default=0)
    ap.add_argument("--virtual", type=int, default=0,
                    help="1F1B-I virtual stages (chunks) per device; "
                         "needs --microbatches >= stages")
    ap.add_argument("--schedule", default="",
                    help="pipeline op order: auto | gpipe | 1f1b | dapple"
                         " | zb-h1 | zb-h2 | zb-auto | 1f1b-interleaved |"
                         " 1f1b-interleaved-memlean (memlean needs"
                         " --microbatches %% stages == 0); backward order"
                         " is executed as first-class ticks")
    ap.add_argument("--runtime", default="", choices=("", "ticks", "stream"),
                    help="training executor: ticks (synchronous tick grid,"
                         " both rings shift every tick) | stream (compiled"
                         " instruction streams — ring collectives only at"
                         " scheduled SEND slots, so W/idle slots overlap"
                         " compute with no barrier)")
    ap.add_argument("--grad-sync", default="",
                    choices=("", "auto", "end", "overlap", "2bw"),
                    help="data-parallel gradient sync placement: end"
                         " (trailing full-pytree psum) | overlap (AR"
                         " bucket ops scheduled into the pipeline drain,"
                         " executed inside the tick scan; needs"
                         " --runtime stream) | 2bw (PipeDream-2BW"
                         " double-buffered weights: step k's grads apply"
                         " at step k+1, so the AR never blocks the next"
                         " step's warmup — sync-free steady state,"
                         " stale-by-one) | auto (overlap iff the"
                         " stream runtime is active)")
    ap.add_argument("--ar-groups", type=int, default=1,
                    help="with overlapped grad sync: split each per-"
                         "(device, chunk) AR bucket into N per-layer-"
                         "group buckets released as each group's W"
                         " retires mid-drain (earlier release, lower"
                         " exposed sync; layers per chunk must divide"
                         " evenly)")
    ap.add_argument("--mem-limit", type=int, default=0,
                    help="zb-auto only: peak-live cap (resident micro-batch"
                         " residuals per device). 0 = unbounded, the fully"
                         " bubble-free order at an M-deep residual stash;"
                         " stages (=1F1B window) reproduces zb-h1,"
                         " ~2*stages reproduces zb-h2")
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--remat", default="stage")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt", default="",
                    help="checkpoint base path; saves the FULL training "
                         "state {params, opt} + step + stage layout")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="with --ckpt: also save every N steps (the "
                         "survive loop; 0 = only at exit)")
    ap.add_argument("--resume", default="",
                    help="checkpoint base path to resume from; a layout "
                         "mismatch (different stages/virtual) reshards "
                         "the checkpoint on the host first "
                         "(repro.checkpoint.reshard)")
    ap.add_argument("--die-at", type=int, default=0,
                    help="fault injection: exit(17) after completing N "
                         "steps, WITHOUT saving — resume restarts from "
                         "the last --ckpt-every boundary")
    ap.add_argument("--losses-out", default="",
                    help="write {start, losses} JSON here (harness "
                         "cross-process loss comparison)")
    ap.add_argument("--drift-every", type=int, default=0,
                    help="update the drift monitor every N steps from "
                         "live block-proxy timings (0 = off)")
    ap.add_argument("--drift-threshold", type=float, default=0.25,
                    help="max per-stage relative share error before the "
                         "monitor triggers a replan")
    ap.add_argument("--drift-inject", default="",
                    help="comma-separated per-stage slowdown factors "
                         "multiplied into the measured vector "
                         "(deterministic drift for tests/CI)")
    ap.add_argument("--replan-budget", type=float, default=5.0,
                    help="seconds the drift-triggered re-search may "
                         "spend before returning the incumbent")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default="",
                    help="write a profiler trace of steps 1 to the end "
                         "(step 0 compiles) under DIR: device ops carry the "
                         "train step's scopes, the host the data layer's "
                         "spans (repro.core.trace)")
    ap.add_argument("--auto-plan", action="store_true",
                    help="let the BaPipe explorer pick stages/tensor/M")
    ap.add_argument("--auto-plan3d", action="store_true",
                    help="search the per-stage (DP, TP) degree space over "
                         "a homogeneous device pool (--pool chips) and "
                         "adopt the best UNIFORM executable plan; non-"
                         "uniform winners are reported analytically")
    ap.add_argument("--pool", type=int, default=0,
                    help="device-pool size for --auto-plan3d "
                         "(default: jax.device_count())")
    ap.add_argument("--cluster", default="",
                    help="comma-separated per-stage device names for "
                         "--auto-plan on a heterogeneous pod "
                         "(tpu_v5e|v100|vcu118|vcu129); fixes the stage "
                         "count to the list length and ranks candidates "
                         "by the scheduled heterogeneous makespan of the "
                         "per-device cost vector")
    args = ap.parse_args(argv)

    try:
        cfg = resolve_config(args.arch, reduced=args.reduced,
                             layers=args.layers, d_model=args.d_model,
                             seq=args.seq)
    except ValueError as e:
        ap.error(str(e))
    if args.stages:
        cfg = dataclasses.replace(cfg, stages=args.stages)
    if args.tensor:
        cfg = dataclasses.replace(cfg, tensor=args.tensor)
    if args.virtual:
        cfg = dataclasses.replace(cfg, virtual=args.virtual)
    if args.schedule:
        cfg = dataclasses.replace(cfg, schedule=args.schedule)
    if args.runtime:
        cfg = dataclasses.replace(cfg, runtime=args.runtime)
    if args.mem_limit:
        if not args.auto_plan:
            from repro.core.schedplan import canonical_name
            sched = cfg.schedule if cfg.schedule not in ("auto", "") \
                else "1f1b"
            if canonical_name(sched) != "zb-auto":
                ap.error(f"--mem-limit only applies to --schedule zb-auto "
                         f"(or --auto-plan); got --schedule {sched}")
        cfg = dataclasses.replace(cfg, mem_limit=args.mem_limit)
    if args.cluster and not args.auto_plan:
        ap.error("--cluster only applies to --auto-plan")
    if args.auto_plan and args.auto_plan3d:
        ap.error("--auto-plan and --auto-plan3d are mutually exclusive")
    if args.auto_plan3d:
        from repro.core.autoplan import auto_plan3d
        plan_ = auto_plan3d(cfg, global_batch=args.batch, seq_len=args.seq,
                            n_devices=args.pool or jax.device_count(),
                            mem_limit=args.mem_limit or None)
        cfg = plan_.apply(cfg)
        args.data = plan_.data_axis
        args.microbatches = plan_.n_microbatches
        widths = "x".join(str(w) for w in plan_.stage_widths)
        print(f"auto-plan3d: stages={plan_.stages} data={plan_.data_axis} "
              f"tensor={plan_.tensor} M={plan_.n_microbatches} "
              f"sched={plan_.schedule} widths={widths} "
              f"(predicted {plan_.predicted_step_time*1e3:.2f} ms/step, "
              f"{plan_.predicted_speedup_over_dp:.2f}x over best "
              f"pipeline-only)")
    if args.auto_plan:
        from repro.core.autoplan import auto_plan
        devices = None
        if args.cluster:
            from repro.core.hardware import TPU_V5E, V100, VCU118, VCU129
            catalogue = {d.name: d for d in (TPU_V5E, V100, VCU118, VCU129)}
            try:
                devices = [catalogue[s.strip()]
                           for s in args.cluster.split(",")]
            except KeyError as e:
                ap.error(f"unknown device {e.args[0]!r} in --cluster "
                         f"(know: {', '.join(sorted(catalogue))})")
        plan_ = auto_plan(cfg, global_batch=args.batch, seq_len=args.seq,
                          model_axis=cfg.stages * cfg.tensor,
                          data_axis=args.data, devices=devices,
                          mem_limit=args.mem_limit or None)
        cfg = plan_.apply(cfg)
        args.microbatches = plan_.n_microbatches
        print(f"auto-plan: stages={plan_.stages} tensor={plan_.tensor} "
              f"M={plan_.n_microbatches} sched={plan_.schedule} "
              f"V={plan_.virtual} "
              f"(predicted {plan_.predicted_step_time*1e3:.2f} ms/step)")
    need = args.data * cfg.stages * cfg.tensor
    if need > jax.device_count():
        ap.error(f"mesh needs {need} devices, have {jax.device_count()} "
                 f"{jax.devices()[0].platform} devices (CPU: set XLA_FLAGS="
                 f"--xla_force_host_platform_device_count={need}; TPU: run "
                 f"on a host with {need} chips)")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((args.data, cfg.stages, cfg.tensor),
                     ("data", "stage", "tensor"))
    plan = ST.plan_stages(cfg)
    print(f"arch={cfg.arch_id} layers={cfg.n_layers} d={cfg.d_model} "
          f"mesh=data{args.data} x stage{cfg.stages} x tensor{cfg.tensor}"
          + (f" x virtual{cfg.virtual}" if cfg.virtual > 1 else ""))

    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(args.seed), plan)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.1f}M")
    opt = AdamW(lr=warmup_cosine(args.lr, 20, args.steps), weight_decay=0.01)
    opt_state = opt.init(params)
    pcfg = RT.PipelineConfig(n_microbatches=args.microbatches,
                             schedule=cfg.schedule, remat=args.remat,
                             mem_limit=cfg.mem_limit, runtime=cfg.runtime,
                             grad_sync=args.grad_sync or "auto",
                             ar_groups=args.ar_groups)
    step_fn, specs = RT.make_train_step(cfg, mesh, plan, pcfg, optimizer=opt)
    if args.grad_sync == "2bw":
        # the wrapped state (inner/pending/primed) is what gets stepped,
        # checkpointed, and resumed
        opt_state = RT.init_2bw_state(opt_state, params)

    layout = layout_dict(plan, cfg.n_layers)

    def _save(path, step_done, params, opt_state):
        save_checkpoint(path, dict(params=params, opt=opt_state),
                        step=step_done,
                        extra=dict(layout=layout, arch=cfg.arch_id))

    # the state lives on the mesh in the step's own layout from the start,
    # so step 1 does not compile again for differently placed inputs
    p_sh, o_sh = RT.state_shardings(mesh, specs, opt_state)
    start_step = 0
    if args.resume:
        meta = checkpoint_meta(args.resume)
        src_layout = (meta.get("extra") or {}).get("layout")
        resume_path = args.resume
        if src_layout and any(
                src_layout.get(k) != layout[k]
                for k in ("stages", "virtual", "layers_per_stage",
                          "n_layers_padded")):
            resume_path = f"{args.resume}.to{plan.n_stages}v{plan.virtual}"
            reshard_checkpoint(args.resume, resume_path, plan)
            print(f"resharded checkpoint: stages"
                  f"{src_layout['stages']} x virtual"
                  f"{src_layout.get('virtual', 1)} -> stages"
                  f"{plan.n_stages} x virtual{plan.virtual}")
        state = restore_checkpoint(resume_path,
                                   dict(params=params, opt=opt_state),
                                   shardings=dict(params=p_sh, opt=o_sh))
        params, opt_state = state["params"], state["opt"]
        start_step = int(checkpoint_meta(resume_path)["step"])
        print(f"resumed from {resume_path} at step {start_step}")
    else:
        params, opt_state = jax.device_put((params, opt_state), (p_sh, o_sh))

    monitor, inject, replanned = None, None, False
    if args.drift_every:
        from repro.core import profiler as PF
        planned = PF.planned_stage_costs(cfg, plan, seq=args.seq)
        monitor = PF.DriftMonitor(planned=tuple(planned),
                                  threshold=args.drift_threshold)
        if args.drift_inject:
            inject = [float(x) for x in args.drift_inject.split(",")]
            if len(inject) != plan.n_stages:
                ap.error(f"--drift-inject needs {plan.n_stages} factors, "
                         f"got {len(inject)}")

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed)
    bspec = dict(tokens=NamedSharding(mesh, P(("data",), None)),
                 labels=NamedSharding(mesh, P(("data",), None)))
    def _dump_losses():
        if args.losses_out:
            import json
            with open(args.losses_out, "w") as f:
                json.dump(dict(start=start_step, losses=losses), f)

    def _end_trace():
        if tracing:
            jax.profiler.stop_trace()
            print(f"trace of steps {start_step + 1} to {step} written "
                  f"under {args.trace_dir}", flush=True)

    losses, step_s, tracing = [], [], False
    for step in range(start_step, args.steps):
        if args.trace_dir and step == start_step + 1:
            jax.profiler.start_trace(args.trace_dir)
            tracing = True
        # a step's time is its batch build, the step and the loss read
        t0 = time.perf_counter()
        batch = shard_batch(data.batch(step), bspec)
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((args.batch, 64, cfg.d_model))
        if cfg.family == "vlm":
            batch["pos3"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, None],
                (3, args.batch, args.seq)).astype(jnp.int32)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))   # waits for the step
        step_s.append(time.perf_counter() - t0)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"({step_s[-1]:.4f} s)", flush=True)
        if monitor is not None and not replanned \
                and (step + 1) % args.drift_every == 0:
            from repro.core import profiler as PF
            measured = PF.measure_stage_times(cfg, plan,
                                              seq=min(args.seq, 64), iters=2)
            if measured is None:
                # proxy timing unavailable: no live signal, no drift
                measured = list(monitor.planned)
            if inject:
                measured = [m * f for m, f in zip(measured, inject)]
            drift = monitor.update(measured)
            if monitor.should_replan():
                from repro.core.autoplan import AutoPlan, replan
                incumbent = AutoPlan(
                    stages=cfg.stages, tensor=cfg.tensor,
                    n_microbatches=args.microbatches,
                    schedule=cfg.schedule,
                    predicted_step_time=float("inf"),
                    predicted_speedup_over_dp=1.0, virtual=cfg.virtual,
                    mem_limit=cfg.mem_limit, data_axis=args.data)
                new = replan(cfg, incumbent, budget_s=args.replan_budget,
                             global_batch=args.batch, seq_len=args.seq,
                             slowdown=list(monitor.slowdown()))
                if new is incumbent:
                    print(f"drift {drift:.2f} at step {step}: replan kept "
                          f"the incumbent plan", flush=True)
                else:
                    print(f"drift {drift:.2f} at step {step}: replan -> "
                          f"stages={new.stages} tensor={new.tensor} "
                          f"M={new.n_microbatches} sched={new.schedule} "
                          f"V={new.virtual} (predicted "
                          f"{new.predicted_step_time * 1e3:.2f} ms/step); "
                          f"restart with --resume to adopt", flush=True)
                replanned = True
        if args.ckpt and args.ckpt_every \
                and (step + 1) % args.ckpt_every == 0:
            _save(args.ckpt, step + 1, params, opt_state)
        if args.die_at and step + 1 >= args.die_at:
            _end_trace()
            _dump_losses()
            print(f"fault injection: dying after step {step + 1}",
                  flush=True)
            raise SystemExit(17)
    _end_trace()
    n = min(10, max(1, len(losses)))
    print(f"first-{n} mean loss {sum(losses[:n])/n:.4f} -> "
          f"last-{n} mean loss {sum(losses[-n:])/n:.4f}")
    if len(step_s) > 1:
        # the first step compiles; the rest are steady state
        steady = sorted(step_s[1:])[len(step_s[1:]) // 2]
        print(f"timing: first step {step_s[0]:.3f} s (compile included), "
              f"steady step median {steady:.4f} s over {len(step_s) - 1} "
              f"steps ({args.batch * args.seq / steady:.0f} tok/s)")
    _dump_losses()
    if args.ckpt:
        _save(args.ckpt, args.steps, params, opt_state)
        print(f"saved checkpoint to {args.ckpt}.npz")
    return losses


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
