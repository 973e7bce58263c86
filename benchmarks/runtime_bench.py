"""Wall-clock benchmark of the pipeline runtimes per schedule.

Runs the instruction-stream runtime (``runtime='stream'``) on 8 fake CPU
devices for each schedule, measures the per-step wall-clock, and checks
the measured ranking against ``simulate_costs`` fed the MEASURED per-op
durations — the planning→execution conformance claim: the simulator's
timing model, built from what the ops actually cost on this host, must
predict the order the runtimes realise.

Per-op durations are measured on a single-device stage proxy exactly as
the runtime executes them (structural stage-remat — every backward op
re-runs the stage forward under ``jax.vjp``):

* ``F``  — the stage forward;
* two-op ``B``      — recompute + full vjp (params and input);
* zero-bubble ``B`` — recompute + input-only vjp;
* zero-bubble ``W`` — recompute + params-only vjp.

So the zero-bubble family pays the recompute twice (once in B, once in
W): on hardware where W hides in drain bubbles that is the price of a
shorter critical path, and the simulator sees the same inflated costs —
measured and simulated rankings must still agree.

Usage::

    python benchmarks/runtime_bench.py [--assert-ranking] [--data D]

Prints one ``schedule,sim_makespan,measured_ms`` row per schedule plus
the two rankings.  ``--assert-ranking`` exits nonzero when a pair the
simulator separates by more than ``SIM_TIE`` is measured in the opposite
order by more than ``MEAS_SLACK`` — the CI conformance gate.

``--data D`` (D > 1) switches to the grad-sync report: a (D data x S
stage) mesh, each schedule stepped under ``grad_sync='end'`` and
``'overlap'`` on the stream runtime, one row per schedule with the
measured wall-clock of both paths next to the simulator's predicted
exposed/hidden sync split (``simulate_costs`` fed the measured per-op
durations and the measured data-fabric AR cost).  The ranking gate then
compares the OVERLAPPED measurements against the overlapped sim
makespans, and flags any schedule whose overlap path measures slower
than its own sync-at-end path beyond noise.
"""
import argparse
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SCHEDULES = ("1f1b", "dapple", "zb-h1", "zb-auto")
SIM_TIE = 0.05     # sim gap below 5% is a tie: no ordering required
MEAS_SLACK = 1.10  # measured may violate a sim ordering by <= 10% noise
# overlap-vs-end is a gross-regression gate only: on fake CPU devices
# the AR bucket is a memcpy (nothing to hide) while the per-slot gate
# costs real dispatch overhead, so small measured losses are expected —
# the gate exists to catch the overlap path recompiling or serializing
OVERLAP_SLACK = 1.25


def _stage_proxy(cfg, mesh, plan):
    """One stage's forward as the runtime applies it, on a single
    micro-batch — the timing unit of every schedule op."""
    import jax
    import jax.numpy as jnp
    from repro.pipeline import runtime as RT
    from repro.pipeline import stage as ST

    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # [Lps, ...] stage 0
    smeta = jax.tree.map(lambda a: a[0], ST.stacked_meta(cfg, plan))
    mb, T = 1, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (mb, T, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (mb, T))

    def fwd(lp_, x_):
        y, a, _ = RT.apply_stage(cfg, lp_, smeta, x_, pos=pos, cache=None)
        return y, a

    return fwd, lp, x


def _time(fn, *args, iters=20):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)          # compile outside the timed region
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def measure_op_durations(cfg, mesh, plan):
    """(t_f, t_full, t_dx, t_dw): the four op costs of the runtime's
    structural stage-remat execution, measured on this host."""
    import jax

    fwd, lp, x = _stage_proxy(cfg, mesh, plan)
    ones = lambda t: jax.tree.map(lambda a: a.astype(float) * 0 + 1, t)

    @jax.jit
    def f_op(lp_, x_):
        return fwd(lp_, x_)[0]

    @jax.jit
    def b_full(lp_, x_):                # two-op backward: recompute + vjp
        (y, a), vjp = jax.vjp(lambda l, xx: fwd(l, xx), lp_, x_)
        return vjp((ones(y), 1.0))

    @jax.jit
    def b_dx(lp_, x_):                  # zb B: recompute + input-only vjp
        (y, a), vjp = jax.vjp(lambda xx: fwd(lp_, xx), x_)
        return vjp((ones(y), 1.0))

    @jax.jit
    def b_dw(lp_, x_):                  # zb W: recompute + params-only vjp
        (y, a), vjp = jax.vjp(lambda l: fwd(l, x_), lp_)
        return vjp((ones(y), 1.0))

    return (_time(f_op, lp, x), _time(b_full, lp, x),
            _time(b_dx, lp, x), _time(b_dw, lp, x))


def _measured_costs(M, S, sched, t_f, t_full, t_dx, t_dw):
    from repro.core import schedplan as SP
    if SP.build_schedule(sched, M, S, 1).has_w:
        b = t_dx + t_dw
        return SP.StageCosts.uniform_costs(S, t_f, b, w_frac=t_dw / b)
    return SP.StageCosts.uniform_costs(S, t_f, t_full)


def sim_makespans(M, S, t_f, t_full, t_dx, t_dw):
    """simulate_costs under the measured durations, per schedule."""
    from repro.core.simulator import simulate_costs
    out = {}
    for sched in SCHEDULES:
        costs = _measured_costs(M, S, sched, t_f, t_full, t_dx, t_dw)
        out[sched] = simulate_costs(sched, M, S, costs).makespan
    return out


def sim_grad_sync(M, S, t_f, t_full, t_dx, t_dw, ar):
    """Per schedule: (base, overlapped, sequential) makespans under the
    measured op durations and the measured AR bucket cost — the
    simulator replaying the AR-op plan on the shared data fabric."""
    from repro.core.simulator import simulate_costs
    out = {}
    for sched in SCHEDULES:
        costs = _measured_costs(M, S, sched, t_f, t_full, t_dx, t_dw)
        base = simulate_costs(sched, M, S, costs).makespan
        ov = simulate_costs(sched, M, S, costs, ar=ar,
                            grad_sync=True).makespan
        out[sched] = (base, ov, base + S * ar)
    return out


def measure_ar_duration(mesh, n_elems, dp):
    """Measured cost of one AR bucket on the data fabric: the chunked
    ``psum_scatter`` + ``all_gather`` exactly as the stream runtime
    executes an AR slot, over a flat bucket of ``n_elems`` floats."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    x = jnp.zeros((n_elems + (-n_elems) % dp,), jnp.float32)

    def rs_ag(v):
        red = lax.psum_scatter(v, "data", scatter_dimension=0, tiled=True)
        return lax.all_gather(red, "data", axis=0, tiled=True)

    # RS+AG leaves the value replicated over data, but the vma checker
    # can't infer that through psum_scatter — disable it
    f = jax.jit(jax.shard_map(rs_ag, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False))
    return _time(f, x)


def measured_walltimes(cfg, mesh, plan, M, runtime="stream", steps=10,
                       dp=1, grad_sync="auto", ar_groups=1):
    """Per-schedule best wall-clock of the jitted train step."""
    import jax
    import numpy as np
    from repro.pipeline import runtime as RT
    from repro.pipeline import stage as ST

    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    kt, kl = jax.random.split(jax.random.PRNGKey(3))
    B, T = M * dp, 64
    batch = dict(tokens=jax.random.randint(kt, (B, T), 0, cfg.vocab),
                 labels=jax.random.randint(kl, (B, T), 0, cfg.vocab))
    out = {}
    for sched in SCHEDULES:
        pcfg = RT.PipelineConfig(n_microbatches=M, schedule=sched,
                                 runtime=runtime, grad_sync=grad_sync,
                                 ar_groups=ar_groups)
        step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
        loss, grads = step(params, batch)          # compile + sanity
        assert np.isfinite(float(loss)), (sched, float(loss))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss, grads = step(params, batch)
            jax.block_until_ready(loss)
            best = min(best, (time.perf_counter() - t0) / steps)
        out[sched] = best
    return out


def check_ranking(sim, meas):
    """Every pair the simulator separates by > SIM_TIE must be measured
    in the same order (up to MEAS_SLACK noise).  Returns violations."""
    bad = []
    names = list(sim)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            lo, hi = (a, b) if sim[a] <= sim[b] else (b, a)
            if sim[hi] - sim[lo] <= SIM_TIE * sim[hi]:
                continue                           # sim tie: no constraint
            if meas[lo] > meas[hi] * MEAS_SLACK:
                bad.append((lo, hi, sim[lo], sim[hi], meas[lo], meas[hi]))
    return bad


def grad_sync_report(args, cfg, mesh, plan, M, S, dp,
                     t_f, t_full, t_dx, t_dw):
    """The ``--data`` mode: measured 'end' vs 'overlap' wall-clock per
    schedule next to the simulator's exposed/hidden sync split."""
    import jax
    import numpy as np
    from repro.pipeline import stage as ST

    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    n_elems = sum(int(np.prod(a.shape[1:]))
                  for a in jax.tree.leaves(params["layers"]))
    ar = measure_ar_duration(mesh, n_elems, dp)
    print(f"# AR bucket ({n_elems} floats, dp={dp}): {ar*1e3:.3f} ms")

    sim = sim_grad_sync(M, S, t_f, t_full, t_dx, t_dw, ar)
    end = measured_walltimes(cfg, mesh, plan, M, dp=dp, grad_sync="end")
    ov = measured_walltimes(cfg, mesh, plan, M, dp=dp, grad_sync="overlap")

    print("schedule,sim_exposed_ms,sim_hidden_ms,"
          "end_ms,overlap_ms,measured_saved_ms")
    for sched in SCHEDULES:
        base, sov, seq = sim[sched]
        exposed, hidden = sov - base, seq - sov
        print(f"{sched},{exposed*1e3:.3f},{hidden*1e3:.3f},"
              f"{end[sched]*1e3:.3f},{ov[sched]*1e3:.3f},"
              f"{(end[sched] - ov[sched])*1e3:.3f}")

    if getattr(args, "ar_groups", 1) > 1:
        _grouped_ar_report(args, cfg, mesh, plan, M, S, dp,
                           t_f, t_full, t_dx, t_dw, ar, sim, ov)

    sim_ov = {s: v[1] for s, v in sim.items()}
    rank = lambda d: ",".join(sorted(d, key=d.get))
    print(f"# sim ranking (overlapped):      {rank(sim_ov)}")
    print(f"# measured ranking (overlapped): {rank(ov)}")
    bad = check_ranking(sim_ov, ov)
    for (lo, hi, slo, shi, mlo, mhi) in bad:
        print(f"# RANKING VIOLATION: sim says {lo} < {hi} "
              f"({slo*1e3:.2f} < {shi*1e3:.2f} ms) but measured "
              f"{mlo*1e3:.2f} > {mhi*1e3:.2f} ms")
    # the overlap path must never cost GROSSLY more than its own
    # sync-at-end path (see OVERLAP_SLACK: fake-device collectives are
    # free, so we gate on gross regression, not on realized savings)
    for sched in SCHEDULES:
        if ov[sched] > end[sched] * OVERLAP_SLACK:
            bad.append((sched, "end", sim_ov[sched], sim[sched][2],
                        ov[sched], end[sched]))
            print(f"# OVERLAP REGRESSION: {sched} overlap "
                  f"{ov[sched]*1e3:.2f} ms > end "
                  f"{end[sched]*1e3:.2f} ms * {OVERLAP_SLACK}")
    if not bad:
        print("# RANKING OK")
    if args.assert_ranking and bad:
        sys.exit(1)
    return sim, ov


def _grouped_ar_report(args, cfg, mesh, plan, M, S, dp,
                       t_f, t_full, t_dx, t_dw, ar, sim, ov):
    """The ``--ar-groups`` satellite report: split each device's AR
    bucket into G per-layer-group buckets released as each group's W
    retires mid-drain.  Shows the closed-form exposed-sync drop
    (``eval_grad_sync(groups=G)``) next to the measured wall-clock of
    the grouped overlap path, and gates on the drop being monotone."""
    from repro.core import schedplan as SP
    from repro.core.schedules import eval_grad_sync

    G = args.ar_groups
    ovg = measured_walltimes(cfg, mesh, plan, M, dp=dp,
                             grad_sync="overlap", ar_groups=G)
    print(f"schedule,sim_exposed_g1_ms,sim_exposed_g{G}_ms,"
          f"overlap_g1_ms,overlap_g{G}_ms")
    for sched in SCHEDULES:
        if SP.build_schedule(sched, M, S, 1).has_w:
            b = t_dx + t_dw
            wf = t_dw / b
        else:
            b, wf = t_full, 0.5
        e1 = eval_grad_sync(sched, M, S, t_f, b, ar, w_frac=wf).exposed
        eg = eval_grad_sync(sched, M, S, t_f, b, ar, w_frac=wf,
                            groups=G).exposed
        assert eg <= e1 + 1e-12, (sched, e1, eg)
        print(f"{sched},{e1*1e3:.3f},{eg*1e3:.3f},"
              f"{ov[sched]*1e3:.3f},{ovg[sched]*1e3:.3f}")
    print(f"# GROUPED-AR OK: exposed(G={G}) <= exposed(1) "
          f"for all schedules")


def tp_report(args):
    """The ``--tp`` dry-run gate: uniform tp=2 plans executed on the
    real ``tensor`` axis by BOTH runtimes — losses and gradients must
    be bit-equal across ticks/stream (the 3D planner's uniform
    candidates are executable), with the per-runtime wall-clock
    reported."""
    import dataclasses
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.pipeline import runtime as RT
    from repro.pipeline import stage as ST

    S, M, tp = args.stages, args.microbatches, 2
    assert jax.device_count() >= S * tp, \
        f"--tp needs {S * tp} devices, have {jax.device_count()}"
    cfg = get_config("llama3.2-1b").reduced(n_layers=args.layers,
                                            d_model=128)
    cfg = dataclasses.replace(cfg, stages=S, tensor=tp)
    mesh = make_mesh((1, S, tp), ("data", "stage", "tensor"))
    plan = ST.plan_stages(cfg)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    kt, kl = jax.random.split(jax.random.PRNGKey(3))
    batch = dict(tokens=jax.random.randint(kt, (M, 64), 0, cfg.vocab),
                 labels=jax.random.randint(kl, (M, 64), 0, cfg.vocab))
    bad = False
    print("schedule,ticks_ms,stream_ms,bitequal")
    for sched in ("1f1b", "zb-h1"):
        outs, times = {}, {}
        for runtime in ("ticks", "stream"):
            pcfg = RT.PipelineConfig(n_microbatches=M, schedule=sched,
                                     runtime=runtime)
            step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
            loss, grads = step(params, batch)
            assert np.isfinite(float(loss)), (sched, runtime, float(loss))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(5):
                    loss, grads = step(params, batch)
                jax.block_until_ready(loss)
                best = min(best, (time.perf_counter() - t0) / 5)
            outs[runtime] = (float(loss), jax.tree.map(np.asarray, grads))
            times[runtime] = best
        (lt, gt), (ls, gs) = outs["ticks"], outs["stream"]
        ok = ls == lt
        if ok:
            try:
                jax.tree.map(
                    lambda a, b: np.testing.assert_array_equal(a, b),
                    gs, gt)
            except AssertionError:
                ok = False
        print(f"{sched},{times['ticks']*1e3:.3f},"
              f"{times['stream']*1e3:.3f},{'yes' if ok else 'NO'}")
        bad |= not ok
    if bad:
        print("# TP DRY-RUN FAILED: ticks/stream mismatch")
        sys.exit(1)
    print("# TP DRY-RUN OK")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--runtime", default="stream",
                    choices=("ticks", "stream"))
    ap.add_argument("--data", type=int, default=1,
                    help="data-parallel degree; > 1 switches to the "
                         "grad_sync 'end' vs 'overlap' exposed-sync "
                         "report (stream runtime only)")
    ap.add_argument("--assert-ranking", action="store_true")
    ap.add_argument("--tp", action="store_true",
                    help="tp=2 dry-run gate: execute uniform-TP plans "
                         "on the real tensor axis under both runtimes "
                         "and require bit-equal losses/gradients")
    ap.add_argument("--ar-groups", type=int, default=1,
                    help="with --data > 1: also report the per-layer-"
                         "group AR bucket split (G buckets per device "
                         "released as each group's W retires) — closed-"
                         "form exposed-sync drop + measured wall-clock")
    args = ap.parse_args(argv)

    if args.tp:
        # bit-equality across differently structured programs needs
        # single-threaded contractions (see tests/harness_pipe.py);
        # set before the first jax import locks the backend
        if "--xla_cpu_multi_thread_eigen" not in os.environ["XLA_FLAGS"]:
            os.environ["XLA_FLAGS"] += " --xla_cpu_multi_thread_eigen=false"
        return tp_report(args)

    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.pipeline import stage as ST

    S, M, dp = args.stages, args.microbatches, args.data
    assert jax.device_count() >= dp * S, \
        f"need {dp * S} devices (XLA_FLAGS fake-device mesh), " \
        f"have {jax.device_count()}"
    assert dp == 1 or args.runtime == "stream", \
        "--data > 1 overlaps the sync in-schedule: stream runtime only"
    cfg = get_config("llama3.2-1b").reduced(n_layers=args.layers,
                                            d_model=128)
    cfg = dataclasses.replace(cfg, stages=S, tensor=1)
    mesh = make_mesh((dp, S, 1), ("data", "stage", "tensor"))
    plan = ST.plan_stages(cfg)

    t_f, t_full, t_dx, t_dw = measure_op_durations(cfg, mesh, plan)
    print(f"# op durations (ms): F={t_f*1e3:.3f} B_full={t_full*1e3:.3f} "
          f"B_dx={t_dx*1e3:.3f} W_dw={t_dw*1e3:.3f}")

    if dp > 1:
        return grad_sync_report(args, cfg, mesh, plan, M, S, dp,
                                t_f, t_full, t_dx, t_dw)

    sim = sim_makespans(M, S, t_f, t_full, t_dx, t_dw)
    meas = measured_walltimes(cfg, mesh, plan, M, runtime=args.runtime)

    print("schedule,sim_makespan_ms,measured_ms")
    for sched in SCHEDULES:
        print(f"{sched},{sim[sched]*1e3:.3f},{meas[sched]*1e3:.3f}")
    rank = lambda d: ",".join(sorted(d, key=d.get))
    print(f"# sim ranking:      {rank(sim)}")
    print(f"# measured ranking: {rank(meas)}")
    bad = check_ranking(sim, meas)
    for (lo, hi, slo, shi, mlo, mhi) in bad:
        print(f"# RANKING VIOLATION: sim says {lo} < {hi} "
              f"({slo*1e3:.2f} < {shi*1e3:.2f} ms) but measured "
              f"{mlo*1e3:.2f} > {mhi*1e3:.2f} ms")
    if not bad:
        print("# RANKING OK")
    if args.assert_ranking and bad:
        sys.exit(1)
    return sim, meas


if __name__ == "__main__":
    main()
