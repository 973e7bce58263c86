"""Multi-device pipeline checks, run as a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests must not set the
flag in-process: the main pytest process keeps 1 device).

Usage: python tests/harness_pipe.py <mode> [arch]
Prints 'OK <metric>' on success, raises otherwise.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
if "--xla_cpu_multi_thread_eigen" not in os.environ["XLA_FLAGS"]:
    # The bit-equality gates here compare gradients across differently
    # structured programs (ticks vs stream, grad_sync end vs overlap).
    # XLA:CPU's multi-threaded Eigen backend picks reduction split
    # points per module, so an unrelated program difference (e.g. the
    # set of trailing all-reduces) can reassociate backward sums at the
    # ulp level; single-threaded contractions make the comparison sound.
    os.environ["XLA_FLAGS"] += " --xla_cpu_multi_thread_eigen=false"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.models import model as M
from repro.pipeline import runtime as RT
from repro.pipeline import stage as ST

TOL = 5e-5


def _mesh(data, stages, tensor, pod=0):
    from repro.launch.mesh import make_mesh
    shape = ((pod,) if pod else ()) + (data, stages, tensor)
    axes = (("pod",) if pod else ()) + ("data", "stage", "tensor")
    return make_mesh(shape, axes)


def _setup(arch, stages, tensor, fsdp=False, aux0=True):
    cfg = get_config(arch).reduced(n_layers=4, d_model=128)
    changes = dict(stages=stages, tensor=tensor, fsdp=fsdp)
    if cfg.moe is not None and aux0:
        changes["moe"] = dataclasses.replace(cfg.moe, router_aux_weight=0.0,
                                             capacity_factor=8.0)
    if cfg.family == "audio":
        changes["n_enc_layers"] = 2
    cfg = dataclasses.replace(cfg, **changes)
    plan = ST.plan_stages(cfg)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    return cfg, plan, params


def _batch(cfg, B, T):
    kt, kl, kf = jax.random.split(jax.random.PRNGKey(3), 3)
    b = dict(tokens=jax.random.randint(kt, (B, T), 0, cfg.vocab),
             labels=jax.random.randint(kl, (B, T), 0, cfg.vocab))
    if cfg.family == "audio":
        b["frames"] = jax.random.normal(kf, (B, 16, cfg.d_model))
    if cfg.family == "vlm":
        b["pos3"] = jnp.broadcast_to(jnp.arange(T)[None, None],
                                     (3, B, T)).astype(jnp.int32)
    return b


def _ref_params(cfg, params, plan=None):
    if plan is not None:
        unstack = lambda a: ST.unstack_chunks(a, plan)[:cfg.n_layers]
    else:
        unstack = lambda a: a.reshape((-1,) + a.shape[2:])[:cfg.n_layers]
    rp = dict(embed=params["embed"],
              layers=jax.tree.map(unstack, params["layers"]),
              final_norm=params["final_norm"])
    if "head" in params:
        rp["head"] = params["head"]
    return rp


def train_equivalence(arch, stages=2, tensor=2, fsdp=False, pod=0,
                      pod_role="data"):
    data = 8 // (stages * tensor * max(1, pod)) or 1
    cfg, plan, params = _setup(arch, stages, tensor, fsdp)
    mesh = _mesh(data, stages, tensor, pod)
    pcfg = RT.PipelineConfig(n_microbatches=2, pod_role=pod_role)
    step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
    batch = _batch(cfg, 8, 32)
    loss, grads = step(params, batch)
    rp = _ref_params(cfg, params)
    ref_loss = M.loss_fn(cfg, rp, batch)
    ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
    assert abs(float(loss) - float(ref_loss)) < 1e-4, \
        (float(loss), float(ref_loss))
    gp = jax.tree.map(
        lambda a: np.asarray(a).reshape((-1,) + a.shape[2:])[:cfg.n_layers],
        grads["layers"])
    gr = jax.tree.map(np.asarray, ref_grads["layers"])
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)),
        gp, gr)
    worst = max(jax.tree.leaves(errs))
    assert worst < 1e-4, worst
    emb = float(np.max(np.abs(np.asarray(grads["embed"])
                              - np.asarray(ref_grads["embed"]))))
    assert emb < 1e-4 * (np.abs(np.asarray(ref_grads["embed"])).max() + 1), emb
    print(f"OK gerr={worst:.2e}")


def serve_equivalence(arch, stages=2, tensor=2):
    data = 8 // (stages * tensor)
    cfg, plan, params = _setup(arch, stages, tensor)
    mesh = _mesh(data, stages, tensor)
    B, steps, maxlen = 8, 4, 16
    pcfg = RT.PipelineConfig(n_microbatches=2)
    serve, _, cspecs, _ = RT.make_serve_step(cfg, mesh, plan, pcfg,
                                             max_len=maxlen, global_batch=B)
    cache = jax.jit(lambda: RT.init_pipeline_cache(cfg, plan, B, maxlen),
                    out_shardings=jax.tree.map(
                        lambda s: NamedSharding(mesh, s), cspecs))()
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, steps), 0, cfg.vocab)
    got = []
    for t in range(steps):
        b = dict(tokens=toks[:, t:t + 1])
        if cfg.family == "vlm":
            b["pos3"] = jnp.full((3, B, 1), t, jnp.int32)
        lg, cache = serve(params, cache, b)
        got.append(np.asarray(lg[:, 0]))
    rp = _ref_params(cfg, params)
    rcache = M.init_cache(cfg, B, max_len=maxlen)
    errs = []
    for t in range(steps):
        b = dict(tokens=toks[:, t:t + 1])
        if cfg.family == "vlm":
            b["pos3"] = jnp.full((3, B, 1), t, jnp.int32)
        lg, rcache = M.decode_step(cfg, rp, b, rcache)
        errs.append(float(np.max(np.abs(got[t] - np.asarray(lg[:, 0])))))
    assert max(errs) < TOL, errs
    print(f"OK maxerr={max(errs):.2e}")


def train_loss_decreases(arch):
    from repro.launch.train import main as train_main
    losses = train_main(["--arch", arch, "--reduced", "--layers", "2",
                         "--d-model", "128", "--data", "2", "--stages", "2",
                         "--tensor", "2", "--steps", "60", "--batch", "8",
                         "--seq", "64", "--lr", "6e-3", "--log-every", "30"])
    first, last = sum(losses[:10]) / 10, sum(losses[-10:]) / 10
    assert last < first - 0.3, (first, last)
    print(f"OK loss {first:.3f}->{last:.3f}")


def serve_driver(arch):
    from repro.launch.serve import main as serve_main
    base = ["--arch", arch, "--reduced", "--data", "2", "--stages", "2",
            "--tensor", "2", "--batch", "8", "--prompt-len", "16",
            "--gen", "8"]
    toks = serve_main(base).tokens
    assert toks.shape == (8, 8)
    # interleaved prefill + donated restack handoff must not change tokens
    toks_v2 = serve_main(base + ["--virtual", "2"]).tokens
    assert (toks_v2 == toks).all()
    print("OK")


def moe_ep_data(arch="deepseek-v3-671b"):
    train_equivalence(arch, stages=2, tensor=2)


def interleaved_equivalence(arch="llama3.2-1b", stages=2, tensor=2,
                            virtual=2, microbatches=2, schedule="auto",
                            fsdp=0):
    """1F1B-I: V>1 chunked pipeline loss/grads must equal both the V=1
    pipeline and the single-device reference — for every ring schedule
    (streaming and memory-lean) and with fsdp sharding of the chunked
    [S, V, Lc] parameters."""
    import dataclasses as _dc
    data = 8 // (stages * tensor) or 1
    cfg = get_config(arch).reduced(n_layers=stages * virtual, d_model=128)
    cfg = _dc.replace(cfg, stages=stages, tensor=tensor, virtual=virtual,
                      fsdp=bool(fsdp))
    mesh = _mesh(data, stages, tensor)
    plan = ST.plan_stages(cfg)
    assert plan.virtual == virtual and plan.layers_per_stage == 1
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule=schedule)
    step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
    batch = _batch(cfg, 8, 32)
    loss, grads = step(params, batch)

    # single-device reference
    rp = _ref_params(cfg, params, plan)
    ref_loss = M.loss_fn(cfg, rp, batch)
    ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
    assert abs(float(loss) - float(ref_loss)) < 1e-4, \
        (float(loss), float(ref_loss))
    gp = jax.tree.map(
        lambda a: np.asarray(ST.unstack_chunks(a, plan))[:cfg.n_layers],
        grads["layers"])
    gr = jax.tree.map(np.asarray, ref_grads["layers"])
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)),
        gp, gr)
    worst = max(jax.tree.leaves(errs))
    assert worst < 1e-4, worst

    # V=1 pipeline on the same weights (re-stacked contiguously)
    cfg1 = _dc.replace(cfg, virtual=1, fsdp=False)
    plan1 = ST.plan_stages(cfg1)
    params1 = dict(rp)
    params1["layers"] = jax.tree.map(
        lambda a: ST._stack_chunks(a, plan1), rp["layers"])
    step1, _ = RT.make_train_step(cfg1, mesh, plan1,
                                  RT.PipelineConfig(
                                      n_microbatches=microbatches))
    loss1, _ = step1(params1, batch)
    assert abs(float(loss) - float(loss1)) < 1e-4, \
        (float(loss), float(loss1))
    print(f"OK gerr={worst:.2e}")


def schedule_equivalence(arch="llama3.2-1b", stages=2, tensor=2,
                         microbatches=4, *schedules):
    """First-class backward ticks: every ring schedule — including the
    early-backward ``dapple`` and the zero-bubble family ``zb_h1`` /
    ``zb_h2`` / ``zb_auto`` (split input-/weight-gradient ticks) — must
    produce loss/grads equal to the single-device reference (and hence
    to each other / to gpipe).  A ``name:K`` schedule runs zb_auto under
    a peak-live cap of K (the PipelineConfig.mem_limit knob).  Runs
    several schedules in one subprocess so the reference is computed
    once."""
    schedules = schedules or ("gpipe", "dapple", "zb_h1", "zb_h2",
                              "zb_auto")
    data = 8 // (stages * tensor) or 1
    cfg, plan, params = _setup(arch, stages, tensor)
    mesh = _mesh(data, stages, tensor)
    batch = _batch(cfg, 8, 32)
    rp = _ref_params(cfg, params)
    ref_loss = float(M.loss_fn(cfg, rp, batch))
    ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
    gr = jax.tree.map(np.asarray, ref_grads["layers"])
    worsts = {}
    for sched in schedules:
        name, _, cap = str(sched).partition(":")
        pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule=name,
                                 mem_limit=int(cap) if cap else 0)
        step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
        loss, grads = step(params, batch)
        assert abs(float(loss) - ref_loss) < 1e-4, (sched, float(loss),
                                                    ref_loss)
        gp = jax.tree.map(
            lambda a: np.asarray(a).reshape((-1,) + a.shape[2:])
            [:cfg.n_layers], grads["layers"])
        errs = jax.tree.map(
            lambda a, b: float(np.max(np.abs(a - b))
                               / (np.max(np.abs(b)) + 1e-9)), gp, gr)
        worst = max(jax.tree.leaves(errs))
        assert worst < 1e-4, (sched, worst)
        emb = float(np.max(np.abs(np.asarray(grads["embed"])
                                  - np.asarray(ref_grads["embed"]))))
        assert emb < 1e-4 * (np.abs(np.asarray(ref_grads["embed"])).max()
                             + 1), (sched, emb)
        worsts[sched] = worst
    print("OK " + " ".join(f"{k}={v:.2e}" for k, v in worsts.items()))


def stream_equivalence(arch="llama3.2-1b", stages=2, tensor=1,
                       microbatches=4, *schedules):
    """runtime='stream' (gated instruction-stream rings) must produce
    loss/grads BIT-EQUAL to runtime='ticks' — the compiled op sequence
    and every data path are identical; the gated rings skip only slots
    whose carries are dead — and grad-equal to the single-device
    reference, for every ring builder."""
    import dataclasses as _dc
    schedules = schedules or ("gpipe", "1f1b", "dapple", "zb-h1", "zb-h2",
                              "zb-auto", "1f1b-interleaved",
                              "1f1b-interleaved-memlean")
    data = 8 // (stages * tensor) or 1
    mesh = _mesh(data, stages, tensor)
    worsts = {}
    for sched in schedules:
        V = 2 if "interleaved" in str(sched) else 1
        cfg = get_config(arch).reduced(n_layers=max(4, stages * V),
                                       d_model=128)
        cfg = _dc.replace(cfg, stages=stages, tensor=tensor, virtual=V)
        plan = ST.plan_stages(cfg)
        params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
        batch = _batch(cfg, 8, 32)
        rp = _ref_params(cfg, params, plan)
        ref_loss = float(M.loss_fn(cfg, rp, batch))
        ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
        gr = jax.tree.map(np.asarray, ref_grads["layers"])
        outs = {}
        for runtime in ("ticks", "stream"):
            pcfg = RT.PipelineConfig(n_microbatches=microbatches,
                                     schedule=str(sched), runtime=runtime)
            step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
            loss, grads = step(params, batch)
            assert abs(float(loss) - ref_loss) < 1e-4, \
                (sched, runtime, float(loss), ref_loss)
            outs[runtime] = (float(loss), jax.tree.map(np.asarray, grads))
        lt, gt = outs["ticks"]
        ls, gs = outs["stream"]
        assert ls == lt, (sched, ls, lt)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     gs, gt)
        gp = jax.tree.map(
            lambda a: np.asarray(ST.unstack_chunks(a, plan))[:cfg.n_layers],
            gs["layers"])
        errs = jax.tree.map(
            lambda a, b: float(np.max(np.abs(a - b))
                               / (np.max(np.abs(b)) + 1e-9)), gp, gr)
        worst = max(jax.tree.leaves(errs))
        assert worst < 1e-4, (sched, worst)
        worsts[str(sched)] = worst
    print("OK " + " ".join(f"{k}={v:.2e}" for k, v in worsts.items()))


def dp_overlap(arch="llama3.2-1b", stages=4, tensor=1,
               microbatches=4, *schedules):
    """Bubble-filling gradient sync: under ``runtime='stream'`` with
    DP>1, ``grad_sync='overlap'`` (AR bucket ops scheduled into the
    pipeline drain, executed inside the tick scan) must produce
    loss/grads BIT-EQUAL to ``grad_sync='end'`` (the trailing
    full-pytree psum it replaces) — the data-axis sum is the same
    single reduction, only its placement moves — and grad-equal to the
    single-device reference, for every ring builder."""
    import dataclasses as _dc
    schedules = schedules or ("gpipe", "1f1b", "dapple", "zb-h1", "zb-h2",
                              "zb-auto", "1f1b-interleaved",
                              "1f1b-interleaved-memlean")
    data = 8 // (stages * tensor) or 1
    assert data > 1, "dp_overlap needs a data axis: use stages*tensor <= 4"
    mesh = _mesh(data, stages, tensor)
    worsts = {}
    for sched in schedules:
        V = 2 if "interleaved" in str(sched) else 1
        cfg = get_config(arch).reduced(n_layers=max(4, stages * V),
                                       d_model=128)
        cfg = _dc.replace(cfg, stages=stages, tensor=tensor, virtual=V)
        plan = ST.plan_stages(cfg)
        params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
        batch = _batch(cfg, 8, 32)
        rp = _ref_params(cfg, params, plan)
        ref_loss = float(M.loss_fn(cfg, rp, batch))
        ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
        gr = jax.tree.map(np.asarray, ref_grads["layers"])
        outs = {}
        for gsync in ("end", "overlap"):
            pcfg = RT.PipelineConfig(n_microbatches=microbatches,
                                     schedule=str(sched), runtime="stream",
                                     grad_sync=gsync)
            step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
            loss, grads = step(params, batch)
            assert abs(float(loss) - ref_loss) < 1e-4, \
                (sched, gsync, float(loss), ref_loss)
            outs[gsync] = (float(loss), jax.tree.map(np.asarray, grads))
        le, ge = outs["end"]
        lo, go = outs["overlap"]
        assert lo == le, (sched, lo, le)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     go, ge)
        gp = jax.tree.map(
            lambda a: np.asarray(ST.unstack_chunks(a, plan))[:cfg.n_layers],
            go["layers"])
        errs = jax.tree.map(
            lambda a, b: float(np.max(np.abs(a - b))
                               / (np.max(np.abs(b)) + 1e-9)), gp, gr)
        worst = max(jax.tree.leaves(errs))
        assert worst < 1e-4, (sched, worst)
        worsts[str(sched)] = worst
    print("OK " + " ".join(f"{k}={v:.2e}" for k, v in worsts.items()))


def tp_equivalence(arch="llama3.2-1b", stages=2, microbatches=4,
                   *schedules):
    """Uniform-TP execution on the real ``tensor`` axis: a tp=2 plan run
    under BOTH runtimes and the bubble-light ring builders must produce
    grads equal to the single-device reference (and ticks == stream
    bit-equal) — the 3D planner's uniform (dp, tp) candidates are
    executable plans, not just analytic entries."""
    stream_equivalence(arch, stages, 2, microbatches,
                       *(schedules or ("1f1b", "zb-h1")))


def two_bw(arch="llama3.2-1b", stages=2, microbatches=2, steps=4,
           schedule="1f1b"):
    """PipeDream-2BW double-buffered weights: ``grad_sync='2bw'`` must
    apply stale-by-one exactly — step 0 applies its own gradients
    (warmup), step k >= 1 applies step k-1's.  Pinned by replaying the
    run's OWN recorded gradient snapshots (``pending``) through the
    optimizer on the host with the one-step lag and requiring the
    parameter trajectory to match tightly; the grads themselves must
    match the synchronous ``grad_sync='end'`` step, and the trajectory
    must DIFFER from applying each step's fresh grads (the staleness is
    pinned semantics, not noise)."""
    from repro.optim import AdamW
    data = 8 // stages or 1
    assert data > 1, "two_bw needs a data axis"
    cfg, plan, params = _setup(arch, stages, 1)
    mesh = _mesh(data, stages, 1)
    opt = AdamW(lr=1e-2)

    def batch_k(k):
        kt, kl = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(3),
                                                     k))
        return dict(tokens=jax.random.randint(kt, (8, 32), 0, cfg.vocab),
                    labels=jax.random.randint(kl, (8, 32), 0, cfg.vocab))

    pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule=schedule,
                             runtime="stream", grad_sync="2bw")
    step2, _ = RT.make_train_step(cfg, mesh, plan, pcfg, optimizer=opt)
    p2 = jax.tree.map(lambda a: a.copy(), params)
    st = RT.init_2bw_state(opt.init(p2), p2)
    traj, pendings, losses2 = [], [], []
    host = lambda t: jax.tree.map(np.array, t)   # copy off donated buffers
    for k in range(steps):
        p2, st, m = step2(p2, st, batch_k(k))
        losses2.append(float(m["loss"]))
        traj.append(host(p2))
        pendings.append(host(st["pending"]))

    # grads must equal the synchronous step's grads at the same params
    gstep, _ = RT.make_train_step(cfg, mesh, plan, RT.PipelineConfig(
        n_microbatches=microbatches, schedule=schedule, runtime="stream",
        grad_sync="end"))
    loss0, g0 = gstep(params, batch_k(0))
    assert abs(float(loss0) - losses2[0]) < 1e-5, (float(loss0), losses2[0])
    gerr = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                           / (np.max(np.abs(np.asarray(b))) + 1e-9)),
        pendings[0], g0)))
    assert gerr < 1e-4, gerr

    # host replay with the one-step lag must reproduce the trajectory
    pr, opt_ref = params, opt.init(params)
    perr = 0.0
    for k in range(steps):
        apply_g = pendings[0] if k == 0 else pendings[k - 1]
        pr, opt_ref = opt.update(pr, apply_g, opt_ref)
        perr = max(perr, max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                               / (np.max(np.abs(np.asarray(b))) + 1e-9)),
            traj[k], pr))))
    assert perr < 1e-6, perr

    # ...and the NON-stale replay (fresh grads each step) must diverge
    ps, opt_s = params, opt.init(params)
    for k in range(steps):
        ps, opt_s = opt.update(ps, pendings[k], opt_s)
    diverged = any(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) > 1e-6
        for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(traj[-1])))
    assert steps < 2 or diverged, "2bw trajectory identical to synchronous"
    print(f"OK gerr={gerr:.2e} perr={perr:.2e} stale-by-one pinned")


def ar_groups(arch="llama3.2-1b", stages=2, groups=2, microbatches=2,
              *schedules):
    """Satellite: per-layer-group AR buckets (``ar_groups=G``, released
    as each group's W retires mid-drain) must be a pure scheduling
    change — loss/grads BIT-EQUAL to the one-bucket overlapped sync;
    every element is still reduced exactly once."""
    import dataclasses as _dc
    schedules = schedules or ("1f1b", "zb-h1")
    data = 8 // stages or 1
    assert data > 1, "ar_groups needs a data axis"
    mesh = _mesh(data, stages, 1)
    worsts = {}
    for sched in schedules:
        # each per-stage chunk must split into `groups` layer groups
        cfg = get_config(arch).reduced(n_layers=max(2, int(groups)) * stages,
                                       d_model=128)
        cfg = _dc.replace(cfg, stages=stages, tensor=1)
        plan = ST.plan_stages(cfg)
        params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
        batch = _batch(cfg, 8, 32)
        rp = _ref_params(cfg, params, plan)
        ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
        gr = jax.tree.map(np.asarray, ref_grads["layers"])
        outs = {}
        for g in (1, int(groups)):
            pcfg = RT.PipelineConfig(n_microbatches=microbatches,
                                     schedule=str(sched), runtime="stream",
                                     grad_sync="overlap", ar_groups=g)
            step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
            loss, grads = step(params, batch)
            outs[g] = (float(loss), jax.tree.map(np.asarray, grads))
        l1, g1 = outs[1]
        lg, gg = outs[int(groups)]
        assert lg == l1, (sched, lg, l1)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     gg, g1)
        gp = jax.tree.map(
            lambda a: np.asarray(ST.unstack_chunks(a, plan))[:cfg.n_layers],
            gg["layers"])
        errs = jax.tree.map(
            lambda a, b: float(np.max(np.abs(a - b))
                               / (np.max(np.abs(b)) + 1e-9)), gp, gr)
        worst = max(jax.tree.leaves(errs))
        assert worst < 1e-4, (sched, worst)
        worsts[str(sched)] = worst
    print("OK " + " ".join(f"{k}={v:.2e}" for k, v in worsts.items()))


def pos3_ring(arch="qwen2-vl-7b", stages=4, tensor=1, virtual=1,
              microbatches=4, schedule="auto"):
    """Regression for the latent pos3 defect: per-micro-batch DISTINCT
    M-RoPE positions must reach the stage that holds the micro-batch
    (they ride the ppermute ring), not stage 0's micro-batch index."""
    import dataclasses as _dc
    data = 8 // (stages * tensor) or 1
    cfg = get_config(arch).reduced(n_layers=max(4, stages * virtual),
                                   d_model=128)
    cfg = _dc.replace(cfg, stages=stages, tensor=tensor, virtual=virtual)
    assert cfg.family == "vlm", "pos3 regression needs an M-RoPE arch"
    mesh = _mesh(data, stages, tensor)
    plan = ST.plan_stages(cfg)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule=schedule)
    step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
    B, T = 8, 32
    batch = _batch(cfg, B, T)
    # distinct positions per batch row => distinct per micro-batch
    batch["pos3"] = jax.random.randint(jax.random.PRNGKey(7), (3, B, T),
                                       0, T).astype(jnp.int32)
    loss, grads = step(params, batch)
    rp = _ref_params(cfg, params, plan)
    ref_loss = M.loss_fn(cfg, rp, batch)
    assert abs(float(loss) - float(ref_loss)) < 1e-4, \
        (float(loss), float(ref_loss))
    ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
    gp = jax.tree.map(
        lambda a: np.asarray(ST.unstack_chunks(a, plan))[:cfg.n_layers],
        grads["layers"])
    gr = jax.tree.map(np.asarray, ref_grads["layers"])
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)),
        gp, gr)
    worst = max(jax.tree.leaves(errs))
    assert worst < 1e-4, worst
    print(f"OK gerr={worst:.2e}")


def prefill_equivalence(arch="llama3.2-1b", stages=2, tensor=2, virtual=2,
                        microbatches=2, schedule="auto"):
    """Interleaved (V>1) pipelined prefill must match the single-device
    reference — run in two segments so the second consumes the KV cache
    the first wrote through the chunked [V, Lc, ...] layout."""
    import dataclasses as _dc
    data = 8 // (stages * tensor) or 1
    cfg = get_config(arch).reduced(n_layers=stages * virtual, d_model=128)
    cfg = _dc.replace(cfg, stages=stages, tensor=tensor, virtual=virtual)
    mesh = _mesh(data, stages, tensor)
    plan = ST.plan_stages(cfg)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    B, P1, P2, maxlen = 8, 8, 8, 32
    pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule=schedule)
    pre1, _, cspecs, _ = RT.make_serve_step(cfg, mesh, plan, pcfg,
                                            max_len=maxlen, global_batch=B,
                                            q_len=P1)
    pre2, _, _, _ = RT.make_serve_step(cfg, mesh, plan, pcfg,
                                       max_len=maxlen, global_batch=B,
                                       q_len=P2)
    cache = jax.jit(lambda: RT.init_pipeline_cache(cfg, plan, B, maxlen),
                    out_shardings=jax.tree.map(
                        lambda s: NamedSharding(mesh, s), cspecs))()
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, P1 + P2), 0,
                              cfg.vocab)
    lg1, cache = pre1(params, cache, dict(tokens=toks[:, :P1]))
    lg2, cache = pre2(params, cache, dict(tokens=toks[:, P1:]))
    rp = _ref_params(cfg, params, plan)
    rcache = M.init_cache(cfg, B, max_len=maxlen)
    rlg1, rcache = M.decode_step(cfg, rp, dict(tokens=toks[:, :P1]), rcache)
    rlg2, rcache = M.decode_step(cfg, rp, dict(tokens=toks[:, P1:]), rcache)
    e1 = float(np.max(np.abs(np.asarray(lg1[:, 0]) - np.asarray(rlg1[:, -1]))))
    e2 = float(np.max(np.abs(np.asarray(lg2[:, 0]) - np.asarray(rlg2[:, -1]))))
    assert max(e1, e2) < TOL, (e1, e2)
    print(f"OK maxerr={max(e1, e2):.2e}")




def interleaved_decode(arch="llama3.2-1b", stages=2, tensor=2, virtual=2,
                       microbatches=2):
    """One-token pipelined decode on an interleaved (V > 1) plan matches
    the single-device reference — the former NotImplementedError is gone;
    decode ticks replay the same compiled table as prefill."""
    import dataclasses as _dc
    data = 8 // (stages * tensor) or 1
    cfg = get_config(arch).reduced(n_layers=stages * virtual, d_model=128)
    cfg = _dc.replace(cfg, stages=stages, tensor=tensor, virtual=virtual)
    mesh = _mesh(data, stages, tensor)
    plan = ST.plan_stages(cfg)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    B, P1, steps, maxlen = 8, 8, 4, 32
    pcfg = RT.PipelineConfig(n_microbatches=microbatches)
    prefill, _, cspecs, _ = RT.make_serve_step(cfg, mesh, plan, pcfg,
                                               max_len=maxlen,
                                               global_batch=B, q_len=P1)
    serve, _, _, _ = RT.make_serve_step(cfg, mesh, plan, pcfg,
                                        max_len=maxlen, global_batch=B,
                                        q_len=1)
    cache = jax.jit(lambda: RT.init_pipeline_cache(cfg, plan, B, maxlen),
                    out_shardings=jax.tree.map(
                        lambda s: NamedSharding(mesh, s), cspecs))()
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, P1 + steps), 0,
                              cfg.vocab)
    lg, cache = prefill(params, cache, dict(tokens=toks[:, :P1]))
    got = [np.asarray(lg[:, 0])]
    for t in range(steps):
        lg, cache = serve(params, cache, dict(tokens=toks[:, P1 + t:P1 + t + 1]))
        got.append(np.asarray(lg[:, 0]))
    rp = _ref_params(cfg, params, plan)
    rcache = M.init_cache(cfg, B, max_len=maxlen)
    rlg, rcache = M.decode_step(cfg, rp, dict(tokens=toks[:, :P1]), rcache)
    errs = [float(np.max(np.abs(got[0] - np.asarray(rlg[:, -1]))))]
    for t in range(steps):
        rlg, rcache = M.decode_step(
            cfg, rp, dict(tokens=toks[:, P1 + t:P1 + t + 1]), rcache)
        errs.append(float(np.max(np.abs(got[t + 1] - np.asarray(rlg[:, 0])))))
    assert max(errs) < TOL, errs
    print(f"OK maxerr={max(errs):.2e}")


def serve_continuous(arch="llama3.2-1b", stages=2, tensor=2, virtual=1):
    """Continuous batching on the pipelined serve step: overlapping
    requests at staggered arrivals, admitted into cache slots and run as
    mixed chunked-prefill + decode steps, must produce tokens
    bit-identical to each request's solo single-device reference."""
    import copy
    import dataclasses as _dc
    from repro.core import serve_sched as SS
    data = 8 // (stages * tensor) or 1
    if virtual > 1:
        cfg = get_config(arch).reduced(n_layers=stages * virtual, d_model=128)
        cfg = _dc.replace(cfg, stages=stages, tensor=tensor, virtual=virtual)
        plan = ST.plan_stages(cfg)
    else:
        cfg, plan, _ = _setup(arch, stages, tensor)
    mesh = _mesh(data, stages, tensor)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    n_slots, chunk, maxlen = 8, 4, 32
    pcfg = RT.PipelineConfig(n_microbatches=2)
    step, _, cspecs, _ = RT.make_serve_step(cfg, mesh, plan, pcfg,
                                            max_len=maxlen,
                                            global_batch=n_slots,
                                            q_len=chunk)
    cache = jax.jit(lambda: RT.init_pipeline_cache(cfg, plan, n_slots,
                                                   maxlen),
                    out_shardings=jax.tree.map(
                        lambda s: NamedSharding(mesh, s), cspecs))()
    rng = np.random.default_rng(7)
    reqs = [SS.Request(rid=i, prompt=rng.integers(
                0, cfg.vocab, size=pl).tolist(), max_new=4, arrival=a)
            for i, (pl, a) in enumerate([(9, 0), (6, 1), (11, 3), (5, 6)])]

    rp = _ref_params(cfg, params, plan if virtual > 1 else None)

    def solo(req):
        rcache = M.init_cache(cfg, 1, max_len=maxlen)
        lg, rcache = M.decode_step(cfg, rp,
                                   dict(tokens=jnp.asarray([req.prompt])),
                                   rcache)
        t = int(np.asarray(lg[0, -1, :cfg.vocab]).argmax())
        out = [t]
        for _ in range(req.max_new - 1):
            lg, rcache = M.decode_step(cfg, rp,
                                       dict(tokens=jnp.asarray([[t]])),
                                       rcache)
            t = int(np.asarray(lg[0, 0, :cfg.vocab]).argmax())
            out.append(t)
        return out

    refs = {r.rid: solo(r) for r in reqs}
    eng = SS.ContinuousEngine(cfg, step, params, cache, n_slots=n_slots,
                              chunk=chunk)
    done = eng.run(copy.deepcopy(reqs))
    assert len(done) == len(reqs)
    for r in done:
        assert r.generated == refs[r.rid], (r.rid, r.generated, refs[r.rid])
    kinds = [tuple(w.kind for w in sp.work) for sp in eng.step_log]
    assert any("prefill" in k and "decode" in k for k in kinds), kinds
    print(f"OK steps={eng.steps_run} reqs={len(done)} bitident=True")


def elastic_resume(arch="llama3.2-1b"):
    """Kill-and-resume across a device-count change (the survive loop):
    train on an 8-stage pipeline with periodic checkpoints, die mid-run
    via fault injection (exit 17, losing the unsaved tail), then resume
    the SAME job on HALF the devices — 4 stages with 2 virtual chunks
    each, so the checkpoint is host-resharded 8x(V=1) -> 4x(V=2) on
    restore.  The resumed loss trajectory must be BIT-EQUAL to the
    uninterrupted 8-stage reference (deterministic data by step index,
    the optimizer's saved step counter drives the LR schedule, and the
    reshard moves real-layer weights/moments bit-for-bit)."""
    import tempfile
    from repro.launch.train import main as train_main
    d = tempfile.mkdtemp()
    ck = os.path.join(d, "ck")
    common = ["--arch", str(arch), "--reduced", "--layers", "8",
              "--d-model", "64", "--data", "1", "--tensor", "1",
              "--microbatches", "8", "--steps", "12", "--batch", "8",
              "--seq", "32", "--log-every", "100", "--seed", "3"]
    ref = train_main(common + ["--stages", "8"])
    try:
        train_main(common + ["--stages", "8", "--ckpt", ck,
                             "--ckpt-every", "4", "--die-at", "9"])
        raise AssertionError("fault injection did not kill the run")
    except SystemExit as e:
        assert e.code == 17, e.code
    res = train_main(common + ["--stages", "4", "--virtual", "2",
                               "--schedule", "1f1b-interleaved",
                               "--resume", ck])
    # died after step 9, last save at step 8 -> resume covers steps 8..11
    assert len(res) == 4, len(res)
    errs = [abs(a - b) for a, b in zip(res, ref[8:])]
    assert max(errs) == 0.0, (errs, res, ref[8:])
    print(f"OK resumed 8->4(V=2) bit-equal over {len(res)} steps")


def elastic_drift(arch="llama3.2-1b"):
    """Injected cost skew must trip the drift monitor mid-run and
    produce a budget-bounded replan recommendation (the train.py side of
    the elastic loop; plan quality is pinned against the simulator in
    tests/test_drift_replan.py)."""
    import contextlib
    import io
    from repro.launch.train import main as train_main
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        train_main(["--arch", str(arch), "--reduced", "--layers", "8",
                    "--d-model", "64", "--data", "1", "--stages", "4",
                    "--tensor", "1", "--microbatches", "4", "--steps", "8",
                    "--batch", "4", "--seq", "32", "--log-every", "100",
                    "--drift-every", "2", "--drift-inject", "4,1,1,1",
                    "--drift-threshold", "0.25", "--replan-budget", "20"])
    text = out.getvalue()
    sys.stdout.write(text)
    assert "replan" in text, text
    print("OK drift-triggered replan fired")


def pod_stage_equivalence():
    import dataclasses as _dc
    cfg = get_config("llama3.2-1b").reduced(n_layers=4, d_model=128)
    cfg = _dc.replace(cfg, stages=2, tensor=2)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 1, 2, 2), ("pod", "data", "stage", "tensor"))
    plan = ST.plan_stages(cfg, n_stages=4)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    pcfg = RT.PipelineConfig(n_microbatches=2, pod_role="stage")
    step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
    batch = _batch(cfg, 8, 32)
    loss, grads = step(params, batch)
    rp = _ref_params(cfg, params)
    ref_loss = M.loss_fn(cfg, rp, batch)
    assert abs(float(loss) - float(ref_loss)) < 1e-4
    ref_grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(rp)
    gp = jax.tree.map(
        lambda a: np.asarray(a).reshape((-1,) + a.shape[2:])[:cfg.n_layers],
        grads["layers"])
    gr = jax.tree.map(np.asarray, ref_grads["layers"])
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)),
        gp, gr)
    worst = max(jax.tree.leaves(errs))
    assert worst < 1e-4, worst
    print(f"OK gerr={worst:.2e}")


def gated_serve(arch):
    import dataclasses as _dc
    tp = 1 if arch in ("mamba2-2.7b", "hymba-1.5b") else 2
    cfg, plan, params = _setup(arch, 2, tp)
    mesh = _mesh(8 // (2 * tp), 2, tp)
    B, steps, maxlen = 8, 4, 16
    pcfg = RT.PipelineConfig(n_microbatches=2, gate_ticks=True)
    serve, _, cspecs, _ = RT.make_serve_step(cfg, mesh, plan, pcfg,
                                             max_len=maxlen, global_batch=B)
    cache = jax.jit(lambda: RT.init_pipeline_cache(cfg, plan, B, maxlen),
                    out_shardings=jax.tree.map(
                        lambda s: NamedSharding(mesh, s), cspecs))()
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, steps), 0, cfg.vocab)
    got = []
    for t in range(steps):
        lg, cache = serve(params, cache, dict(tokens=toks[:, t:t + 1]))
        got.append(np.asarray(lg[:, 0]))
    rp = _ref_params(cfg, params)
    rcache = M.init_cache(cfg, B, max_len=maxlen)
    errs = []
    for t in range(steps):
        lg, rcache = M.decode_step(cfg, rp, dict(tokens=toks[:, t:t + 1]),
                                   rcache)
        errs.append(float(np.max(np.abs(got[t] - np.asarray(lg[:, 0])))))
    assert max(errs) < TOL, errs
    print(f"OK maxerr={max(errs):.2e}")


def split_head(cases_json, arch="llama3.2-1b", microbatches=4):
    """The vocab-split loss head: for each case of ``cases_json`` (a
    JSON object of name: [stages, tensor, virtual, schedule, runtime,
    tied]), the pipeline's loss and the gradient of every leaf (the
    layers, the tied table or embedding and head, the final norm)
    against the single-device reference.  Prints ``OK`` and a JSON
    object of per-case errors (relative to each leaf's largest
    reference entry), or of the error a case raised."""
    import json
    out = {}
    for name, (S, tp, V, sched, runtime, tied) in json.loads(
            cases_json).items():
        try:
            cfg = get_config(arch).reduced(n_layers=S * V, d_model=64)
            cfg = dataclasses.replace(cfg, stages=S, tensor=tp, virtual=V,
                                      tie_embeddings=tied)
            plan = ST.plan_stages(cfg)
            params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
            # a final norm away from its initial value, so its gradient
            # is not the one of a constant scale
            params["final_norm"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(1), params["final_norm"].shape)
            mesh = _mesh(1, S, tp)
            pcfg = RT.PipelineConfig(n_microbatches=microbatches,
                                     schedule=sched, runtime=runtime)
            step, _ = RT.make_train_step(cfg, mesh, plan, pcfg)
            batch = _batch(cfg, 8, 16)
            loss, grads = step(params, batch)
            rp = _ref_params(cfg, params, plan)
            ref_loss, ref = jax.value_and_grad(
                lambda p: M.loss_fn(cfg, p, batch))(rp)
            got = dict(grads, layers=jax.tree.map(
                lambda a: ST.unstack_chunks(a, plan)[:cfg.n_layers],
                grads["layers"]))

            def rel(a, b):
                a, b = np.asarray(a), np.asarray(b)
                return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            errs = {k: max(jax.tree.leaves(jax.tree.map(rel, got[k],
                                                          ref[k])))
                    for k in ref}
            out[name] = dict(errs, loss=abs(float(loss) - float(ref_loss)),
                             leaves=sorted(ref))
        except Exception:           # reported per case by the test
            import traceback
            out[name] = dict(error=traceback.format_exc()[-3000:])
    print("OK " + json.dumps(out))


def scoped_hlo(out_path, arch="qwen3-1.7b", stages=4, microbatches=4):
    """Compile the 1F1B ticks train step with AdamW on ``stages`` devices,
    write its HLO text to ``out_path`` and print its static counters.
    The vocabulary is 1152 rows, so a stage's share of the head (288
    rows) is no other dimension of the step."""
    import json
    from repro.optim import AdamW
    cfg, plan, params = _setup(arch, stages, 1)
    cfg = dataclasses.replace(cfg, vocab=1152)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    mesh = _mesh(1, stages, 1)
    pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule="1f1b",
                             runtime="ticks")
    opt = AdamW(lr=1e-3)
    step, _ = RT.make_train_step(cfg, mesh, plan, pcfg, optimizer=opt)
    batch = _batch(cfg, microbatches, 16)
    txt = step.lower(params, opt.init(params), batch).compile().as_text()
    with open(out_path, "w") as f:
        f.write(txt)
    counters = RT.train_step_counters(cfg, mesh, plan, pcfg, batch)
    print("OK " + json.dumps(dict(counters, vocab=cfg.vocab,
                                  microbatches=microbatches)))


if __name__ == "__main__":
    mode = sys.argv[1]
    args = [int(a) if a.lstrip("-").isdigit() else a for a in sys.argv[2:]]
    {"train_equivalence": train_equivalence,
     "serve_equivalence": serve_equivalence,
     "train_loss_decreases": train_loss_decreases,
     "serve_driver": serve_driver,
     "moe_ep_data": moe_ep_data,
     "pod_stage_equivalence": pod_stage_equivalence,
     "gated_serve": gated_serve,
     "interleaved_equivalence": interleaved_equivalence,
     "schedule_equivalence": schedule_equivalence,
     "stream_equivalence": stream_equivalence,
     "dp_overlap": dp_overlap,
     "tp_equivalence": tp_equivalence,
     "two_bw": two_bw,
     "ar_groups": ar_groups,
     "pos3_ring": pos3_ring,
     "prefill_equivalence": prefill_equivalence,
     "interleaved_decode": interleaved_decode,
     "serve_continuous": serve_continuous,
     "elastic_resume": elastic_resume,
     "elastic_drift": elastic_drift,
     "scoped_hlo": scoped_hlo,
     "split_head": split_head,
     }[mode](*args)
