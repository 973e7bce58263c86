"""BaPipe distributed runtime: intra-batch pipeline parallelism as
``shard_map`` + ``lax.scan`` + ``lax.ppermute`` on a
("pod",) ("data", "stage", "tensor") mesh.

Execution model (per device, SPMD):

* layer parameters arrive stacked ``[1, Lps, ...]`` (stage-sharded);
* TRAINING runs ONE tick scan over the schedule's full mixed F/B(/W) op
  table: backward ops are first-class ticks.  ``make_train_step`` builds
  the op table with the schedule-plan IR (:mod:`repro.core.schedplan`)
  and compiles it with :func:`repro.core.schedplan.lower_to_ticks` into
  per-device per-tick lookup arrays (op kind, micro-batch, chunk,
  stash/inbox slots); the scan body ``lax.switch``es on the op kind.
  There is NO autodiff of the scan — gradients are assembled manually:

  - an F tick applies the stage block and saves its *input* into a
    statically allocated residual stash (slot count == the schedule's
    peak-live row, by construction);
  - a B tick re-runs the stage forward from the stashed residual under
    ``jax.vjp`` and applies it to the cotangent arriving on the
    *backward* ppermute ring (stage s -> s-1), accumulating layer grads
    and sending the input-cotangent upstream.  On the last virtual stage
    the cotangent is seeded by that micro-batch's loss head
    (final-norm + logits + xent).  With one stage the head runs inside
    the seed tick.  With more, it is split over the stages: at the tick
    the last stage finishes the micro-batch's forward, every stage runs
    the head on its ``ceil(vocab / S)`` rows of the (replicated) table,
    the softmax reduced over the stages, and the head's y-cotangent
    waits in a register-allocated dy stash for the seed tick, which is
    then an ordinary ring B — so the last stage no longer holds every
    lock-step tick for the whole head;
  - a W tick (zero-bubble schedules) re-runs the forward once more and
    applies the stashed cotangent to the *parameters* only — the
    input-gradient B tick earlier propagated the error without paying
    for weight grads on the critical path;
  - the two rings shift every tick; arrivals the consuming op is not
    ready for are parked in statically allocated inbox slots.

* stage 0 injects micro-batches (and collects the injection cotangents
  that feed the embedding backward); the per-micro-batch losses are
  summed into the same global scalar as before and ``psum``-broadcast;
* gradients are then ``psum``'d over exactly the axes each leaf is
  replicated on (data/pod for everything; +stage for embed/head/norm) —
  the paper's "orthogonal to data parallelism", literally.  Under
  ``runtime='stream'`` the DATA-axis share of that sync is instead
  compiled INTO the schedule (``PipelineConfig.grad_sync``): the plan
  carries one AR op per (device, chunk) gradient bucket, scheduled into
  the drain right after the bucket's last B/W tick (stage N-1 retires
  first and syncs earliest, stage 0 last), and the scan executes each AR
  slot as a chunked ``psum_scatter`` + ``all_gather`` over ``data`` —
  retired buckets sync while later micro-batches are still in B/W, so
  the all-reduce hides in the pipeline bubble.  The trailing psum then
  skips ``data`` for the layer grads; embed/head/norm grads and
  fsdp-sharded leaves keep the full trailing sync.

``PipelineConfig.schedule`` selects the executed order — ``gpipe``,
``1f1b`` / ``dapple`` (early backward), ``zb-h1`` (zero-bubble split
backward), ``1f1b-interleaved`` (streaming chunk passes, the ``auto``
default for V > 1) or ``1f1b-interleaved-memlean`` (Megatron groups of
S; its every ring return is consumed the tick it arrives).  Interleaved
1F1B (plan.virtual = V > 1) stacks parameters ``[1, V, Lc, ...]`` — V
non-contiguous layer chunks per device, chunk v of device n being
virtual stage v*S + n — and both rings loop the daisy chain V times.

Because B ticks recompute the stage from its stashed input, the residual
footprint IS the schedule's features-memory row (1F1B's S - n instead of
GPipe's M) — ``remat='stage'`` semantics are structural now.
``remat='full'`` additionally rematerialises per layer inside the B-tick
recompute.

SERVING (``make_serve_step``) still runs the forward-only lowering
(:func:`repro.core.schedplan.lower_to_ring`): one tick scan over
``M*V + S - 1`` forward elements with the stage-0 return buffer rules the
ring lowering emits.  Micro-batch positions (``pos3``, VLM M-RoPE) ride
the serve ring alongside the activation; the train scan instead indexes
the per-micro-batch position table by the tick's micro-batch, so stage s
always applies the positions of the micro-batch it actually holds.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import schedplan as SP
from repro.core import trace as TR
from repro.models import layers as LYR
from repro.models import model as M
from repro.pipeline import stage as ST


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_microbatches: int = 4
    schedule: str = "auto"          # schedplan name: auto | gpipe | 1f1b |
                                    # dapple | zb-h1 | zb-h2 | zb-auto |
                                    # 1f1b-interleaved |
                                    # 1f1b-interleaved-memlean
    mem_limit: int = 0              # zb-auto peak-live cap (resident
                                    # micro-batch residuals per device);
                                    # 0 = unbounded (fully bubble-free
                                    # order, M-deep residual stash)
    remat: str = "stage"            # none | stage | stage_save_moe | full.
                                    # Training recomputes each stage from
                                    # its stashed input at the B tick, so
                                    # 'none'/'stage'/'stage_save_moe' all
                                    # behave as structural stage-remat
                                    # (MoE all_to_alls DO re-run in the
                                    # B-tick recompute); 'full' adds
                                    # per-layer remat inside it
    runtime: str = "ticks"          # ticks | stream.  'ticks' replays the
                                    # tick grid with both rings shifting
                                    # every tick (two full-pytree ppermutes
                                    # per tick, even on idle/W ticks);
                                    # 'stream' executes the compiled
                                    # instruction streams
                                    # (schedplan.lower_to_instructions):
                                    # ring collectives fire ONLY at slots
                                    # where some device SENDs, so ops take
                                    # their actual durations and W/idle
                                    # slots run communication-free
    grad_sync: str = "auto"         # auto | end | overlap | 2bw.
                                    # 'overlap' compiles the data-axis
                                    # gradient all-reduce into the
                                    # schedule as AR bucket ops executed
                                    # inside the tick scan (stream
                                    # runtime only — the AR slots ride
                                    # the instruction stream); 'end'
                                    # keeps the trailing full-pytree
                                    # psum; 'auto' overlaps iff
                                    # runtime='stream'.  '2bw' is
                                    # PipeDream-2BW double-buffered
                                    # weights: step k's (fully synced)
                                    # gradients are applied at step k+1,
                                    # so the collective has a whole step
                                    # of slack — sync-free steady state
                                    # at a pinned one-step staleness
                                    # (both runtimes; needs an optimizer
                                    # and the 2bw-wrapped opt state,
                                    # :func:`init_2bw_state`)
    ar_groups: int = 1              # grad_sync='overlap': split each
                                    # (device, chunk) gradient bucket
                                    # into this many per-layer-group AR
                                    # sub-buckets (layers per chunk must
                                    # divide evenly); 1 = one bucket
    pod_role: str = "data"          # data | stage  (stage = pipeline over DCN)
    unroll: bool = False            # fully unroll ALL scans (roofline mode)
    gate_ticks: bool = False        # serve: lax.cond-skip invalid ticks so
                                    # devices neither compute nor stream
                                    # weights during fill/drain (real TPUs
                                    # take one conditional branch)
    tick_unroll: int = 0            # >0: unroll factor for the tick scan
                                    # only (two-point roofline differencing);
                                    # inner scans are then fully unrolled

    @property
    def inner_unroll(self) -> bool:
        return self.unroll or self.tick_unroll > 0

    @property
    def tick_scan_unroll(self):
        if self.unroll:
            return True
        return self.tick_unroll if self.tick_unroll > 0 else 1


def _batch_axes(mesh: Mesh, pcfg: PipelineConfig) -> tuple[str, ...]:
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if pcfg.pod_role == "stage":
        axes = tuple(a for a in axes if a != "pod")
    return axes


def _stage_axes(mesh: Mesh, pcfg: PipelineConfig):
    if pcfg.pod_role == "stage" and "pod" in mesh.axis_names:
        return ("pod", "stage")
    return "stage"


def _n_stages(mesh: Mesh, pcfg: PipelineConfig) -> int:
    s = mesh.shape["stage"]
    if pcfg.pod_role == "stage" and "pod" in mesh.axis_names:
        s *= mesh.shape["pod"]
    return s


# ---------------------------------------------------------------------------
# Per-stage block apply (scan over the stage's layers).
# ---------------------------------------------------------------------------

def _gather_fsdp(lp: dict, fsdp_dims: dict, axis: str) -> dict:
    def g(path, leaf):
        name = getattr(path[-1], "key", None)
        dim = fsdp_dims.get(name)
        if dim is None:
            return leaf
        return lax.all_gather(leaf, axis, axis=dim, tiled=True)
    return jax.tree_util.tree_map_with_path(g, lp)


def apply_stage(cfg: ArchConfig, stage_params, stage_meta, x, *,
                pos, pos3=None, cache=None, tp_axis=None, tp_index=None,
                dp_axis=None, dp_index=None, n_dp=1,
                fsdp_axis=None, fsdp_dims=None, remat="stage",
                unroll=False):
    """Scan this stage's Lps layers over activation pytree ``x``.

    ``x`` is the raw hidden state [mb,T,d], or for audio a dict
    {h_enc, h_dec}.  Padded (inactive) layer slots pass through unchanged.
    Returns (x', aux, new_cache)."""

    def layer_body(carry, inp):
        xc, aux = carry
        lp, ml, cl = inp
        if fsdp_axis is not None and fsdp_dims:
            lp = _gather_fsdp(lp, fsdp_dims, fsdp_axis)
        blk_x = dict(h_enc=xc["h_enc"], h_dec=xc["h_dec"]) \
            if isinstance(xc, dict) else xc
        y, new_cl, a = M.block_apply(cfg, lp, blk_x, ml, pos=pos, pos3=pos3,
                                     cache_l=cl, tp_axis=tp_axis,
                                     tp_index=tp_index, dp_axis=dp_axis,
                                     dp_index=dp_index, n_dp=n_dp)
        act = ml["active"]
        y = jax.tree.map(lambda new, old: jnp.where(act, new, old), y, blk_x)
        if new_cl is not None:
            new_cl = jax.tree.map(lambda new, old: jnp.where(act, new, old),
                                  new_cl, cl)
        return (y, aux + jnp.where(act, a, 0.0)), new_cl

    body = jax.checkpoint(layer_body) if remat == "full" else layer_body
    (x, aux), new_cache = lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stage_params, stage_meta, cache),
        unroll=unroll)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Micro-batch preparation (embedding etc., data-parallel, outside the pipe).
# ---------------------------------------------------------------------------

def _prepare_microbatches(cfg: ArchConfig, params, batch, M_: int, tp_index):
    """Returns (inj [M, ...] pytree of per-microbatch injected carries,
    pos [mb,T], pos3 [M,3,mb,T] or None)."""
    if cfg.family == "vlm" and "embeds" in batch:
        x_all = batch["embeds"]
    else:
        x_all = M.embed_tokens(cfg, params["embed"], batch["tokens"],
                               "tensor", tp_index)
    B_loc, T = x_all.shape[0], x_all.shape[1]
    assert B_loc % M_ == 0, f"local batch {B_loc} not divisible by M={M_}"
    mb = B_loc // M_
    pos = jnp.broadcast_to(jnp.arange(T)[None], (mb, T))
    if cfg.family == "audio":
        x_all = x_all + M.sinusoid_pos(
            jnp.broadcast_to(jnp.arange(T)[None], (B_loc, T)), cfg.d_model,
            x_all.dtype)
        frames = batch["frames"].astype(x_all.dtype)
        Sf = frames.shape[1]
        enc_pos = jnp.broadcast_to(jnp.arange(Sf)[None], (B_loc, Sf))
        h_enc = frames + M.sinusoid_pos(enc_pos, cfg.d_model, x_all.dtype)
        inj = dict(h_dec=x_all.reshape(M_, mb, T, -1),
                   h_enc=h_enc.reshape(M_, mb, Sf, -1))
    else:
        inj = x_all.reshape(M_, mb, T, -1)
    pos3 = None
    if batch.get("pos3") is not None:
        pos3 = jnp.moveaxis(batch["pos3"].reshape(3, M_, mb, T), 1, 0)
    return inj, pos, pos3, mb, T


def _hidden_of(y):
    return y["h_dec"] if isinstance(y, dict) else y


def _ring_tables(lowering: SP.RingLowering) -> dict:
    """The lowering's per-element lookup arrays as device constants: the
    per-tick (micro-batch, chunk, fresh/direct/park/collect) assignment of
    the compiled schedule, indexed by ``e = tick - stage`` in the scan."""
    return dict(
        m=jnp.asarray(lowering.m_of_e, jnp.int32),
        v=jnp.asarray(lowering.v_of_e, jnp.int32),
        fresh=jnp.asarray(lowering.fresh, bool),
        direct=jnp.asarray(lowering.direct, bool),
        park=jnp.asarray(lowering.park, bool),
        collect=jnp.asarray(lowering.collect, bool))


def _tick_tables(lo: SP.TickLowering) -> dict:
    """The tick lowering's per-device per-tick lookup tables as flat
    device constants, indexed by ``stage_idx * n_ticks + t``: op kind,
    micro-batch, chunk, and the stash/inbox slots of the mixed F/B(/W)
    schedule; and the split head's per-tick tables, indexed by ``t``."""
    def flat(rows, dt=jnp.int32):
        return jnp.asarray([x for row in rows for x in row], dt)
    return dict(
        kind=flat(lo.kind), m=flat(lo.m), v=flat(lo.v),
        xw=flat(lo.xw), xr=flat(lo.xr),
        fsrc=flat(lo.fsrc), fr=flat(lo.fr), fpark=flat(lo.fpark),
        bsrc=flat(lo.bsrc), br=flat(lo.br), bpark=flat(lo.bpark),
        cw=flat(lo.cw), cr=flat(lo.cr), dinj=flat(lo.dinj, bool),
        dyr=flat(lo.dyr),
        # the split head's tables: per tick, the same on every device
        head=jnp.asarray(lo.head, bool), head_m=jnp.asarray(lo.head_m,
                                                            jnp.int32),
        dyw=jnp.asarray(lo.dyw, jnp.int32))


def _stream_tables(instr: SP.InstrLowering) -> dict:
    """The instruction lowering's tables: the tick tables plus the two
    global per-slot comm gates — ``fsend[t]``/``bsend[t]`` is True iff
    some device SENDs on that ring at slot ``t``.  Both are functions of
    the slot counter alone (identical on every device), so the gated
    ring collectives stay uniform across the mesh."""
    return dict(_tick_tables(instr.ticks),
                fsend=jnp.asarray(instr.fsend, bool),
                bsend=jnp.asarray(instr.bsend, bool),
                aron=jnp.asarray(instr.arsync, bool))


def _buf_read(buf, slot):
    """Read pytree slot ``buf[slot]`` of a leading-dim buffer pytree."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, slot, 0, keepdims=False), buf)


def _buf_write(buf, slot, val, do):
    """Write ``val`` into pytree slot ``buf[slot]`` where ``do`` (else
    keep the old slot content)."""
    def w(a, x):
        old = lax.dynamic_index_in_dim(a, slot, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            a, jnp.where(do, x, old), slot, 0)
    return jax.tree.map(w, buf, val)


def _at(table: jnp.ndarray, idx):
    return lax.dynamic_index_in_dim(table, idx, 0, keepdims=False)


def _shard_retbuf(cfg: ArchConfig, S: int, stage_ax) -> bool:
    """The stage-0 return buffer can be feature-sharded over the stage
    axis: requires a single plain axis name (pod_role='stage' fuses two
    axes — psum_scatter's tiled layout wants one) and a feature dim that
    splits evenly.  Every injected leaf's last dim is ``d_model``."""
    return isinstance(stage_ax, str) and S > 1 and cfg.d_model % S == 0


def _retbuf_init(inj, S: int, sharded: bool):
    """Zero-initialised stage-0 return buffer matching ``inj``'s [M, ...]
    layout.  Unsharded it is a FULL copy of ``inj`` on every device (the
    scan carry is SPMD-uniform, so write-masking to stage 0 does not
    shrink it); sharded each device holds 1/S of the feature dim and the
    buffer is reassembled by ``all_gather`` only at the ticks stage 0
    actually reads a parked return."""
    if not sharded:
        return jax.tree.map(jnp.zeros_like, inj)
    return jax.tree.map(
        lambda q: jnp.zeros(q.shape[:-1] + (q.shape[-1] // S,), q.dtype),
        inj)


def _ring_ingest(tab: dict, MV: int, S: int, stage_idx, t, inj, x_cur,
                 retbuf, *, stage_ax=None, sharded: bool = False):
    """Stage-0 ring ingestion for one tick of the compiled schedule: park
    the arriving ring return (when the schedule buffers; stage 0 only),
    then select this tick's stage-0 source — fresh injection (chunk-0
    pass), the ring return straight off the ppermute carry (``direct``),
    or the parked return.  ``retbuf`` is None for schedules that consume
    every return the tick it arrives.

    When ``sharded``, the return buffer holds 1/S of every feature dim
    per device: parking scatters stage 0's arrival over the stage axis
    (``psum_scatter`` of a stage-0-masked contribution), reading gathers
    it back.  Both collectives are gated by predicates that depend on
    the tick alone — uniform across the mesh, so the branches are safe
    (cf. ``gate_ticks``) and non-park ticks pay nothing.
    Returns (retbuf, x_in)."""
    if retbuf is not None:
        e_arr = t - S
        eacl = jnp.clip(e_arr, 0, MV - 1)
        want_park = (e_arr >= 0) & _at(tab["park"], eacl)
        slot = _at(tab["m"], eacl)
        if sharded:
            def park_scatter(rb):
                def park1(rb_l, c):
                    contrib = jnp.where(stage_idx == 0, c,
                                        jnp.zeros_like(c))
                    sh = lax.psum_scatter(contrib, stage_ax,
                                          scatter_dimension=c.ndim - 1,
                                          tiled=True)
                    return lax.dynamic_update_index_in_dim(rb_l, sh,
                                                           slot, 0)
                return jax.tree.map(park1, rb, x_cur)

            retbuf = lax.cond(want_park, park_scatter, lambda rb: rb,
                              retbuf)
        else:
            do_park = want_park & (stage_idx == 0)

            def park(rb, c):
                old = lax.dynamic_index_in_dim(rb, slot, 0, keepdims=False)
                return lax.dynamic_update_index_in_dim(
                    rb, jnp.where(do_park, c, old), slot, 0)

            retbuf = jax.tree.map(park, retbuf, x_cur)
    e0 = jnp.clip(t, 0, MV - 1)
    m0 = _at(tab["m"], e0)
    is_fresh = _at(tab["fresh"], e0)
    if retbuf is not None:
        take_direct = _at(tab["direct"], e0)
        if sharded:
            def read_gather(rb):
                def gather1(rb_l):
                    sl = lax.dynamic_index_in_dim(rb_l, m0, 0,
                                                  keepdims=False)
                    return lax.all_gather(sl, stage_ax, axis=sl.ndim - 1,
                                          tiled=True)
                return jax.tree.map(gather1, rb)

            parked = lax.cond(
                ~is_fresh & ~take_direct, read_gather,
                lambda rb: jax.tree.map(jnp.zeros_like, x_cur), retbuf)
            src = jax.tree.map(
                lambda q, pk, c: jnp.where(
                    is_fresh,
                    lax.dynamic_index_in_dim(q, m0, 0, keepdims=False),
                    jnp.where(take_direct, c, pk)),
                inj, parked, x_cur)
        else:
            src = jax.tree.map(
                lambda q, rb, c: jnp.where(
                    is_fresh,
                    lax.dynamic_index_in_dim(q, m0, 0, keepdims=False),
                    jnp.where(take_direct, c,
                              lax.dynamic_index_in_dim(rb, m0, 0,
                                                       keepdims=False))),
                inj, retbuf, x_cur)
    else:
        src = jax.tree.map(
            lambda q, c: jnp.where(
                is_fresh,
                lax.dynamic_index_in_dim(q, m0, 0, keepdims=False),
                c),
            inj, x_cur)
    x_in = jax.tree.map(
        lambda s_, c: jnp.where(stage_idx == 0, s_, c), src, x_cur)
    return retbuf, x_in


# ---------------------------------------------------------------------------
# Training step factory.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _TrainSchedule:
    """The train step's compiled schedule: the op table, its tick lowering
    (and instruction streams under ``runtime='stream'``), and the layer
    leaves the in-scan gradient sync covers."""
    specs: Any
    plan_ir: SP.SchedPlan
    lowering: SP.TickLowering
    instr: Optional[SP.InstrLowering]
    ar_mask: Any
    overlap_sync: bool


def _train_schedule(cfg: ArchConfig, mesh: Mesh, plan: ST.StagePlan,
                    pcfg: PipelineConfig, param_dtype) -> _TrainSchedule:
    """Validate ``pcfg`` and lower the schedule the train step runs; no
    compile.  :func:`make_train_step` executes it and
    :func:`train_step_counters` counts it."""
    shape_params = jax.eval_shape(
        lambda k: ST.init_stacked_params(cfg, k, plan, param_dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    mesh_axes = tuple(mesh.axis_names)
    stage_ax = _stage_axes(mesh, pcfg)
    S = _n_stages(mesh, pcfg)
    assert plan.n_stages == S, \
        f"stage plan ({plan.n_stages}) != mesh pipeline depth ({S}); " \
        f"with pod_role='stage' build the plan with n_stages=pod*stages"
    V = plan.virtual
    specs = ST.param_specs(cfg, shape_params, stage_axis=stage_ax,
                           fsdp_axis="data" if cfg.fsdp else None,
                           tensor_size=mesh.shape["tensor"], virtual=V)
    M_ = pcfg.n_microbatches
    # compile the schedule's FULL mixed F/B(/W) op table and lower it to
    # per-device per-tick lookup arrays: backward ops are first-class
    # ticks, executed by the same scan as the forwards
    if pcfg.runtime not in ("ticks", "stream"):
        raise ValueError(f"unknown runtime {pcfg.runtime!r}: "
                         f"expected ticks | stream")
    if pcfg.grad_sync not in ("auto", "end", "overlap", "2bw"):
        raise ValueError(f"unknown grad_sync {pcfg.grad_sync!r}: "
                         f"expected auto | end | overlap | 2bw")
    if pcfg.grad_sync == "overlap" and pcfg.runtime != "stream":
        raise ValueError("grad_sync='overlap' requires runtime='stream' "
                         "(the tick replay has no AR slots)")
    if pcfg.ar_groups < 1:
        raise ValueError(f"ar_groups must be >= 1, got {pcfg.ar_groups}")
    if pcfg.ar_groups > 1 and not (
            pcfg.runtime == "stream"
            and pcfg.grad_sync in ("auto", "overlap")):
        raise ValueError("ar_groups > 1 splits the OVERLAPPED AR buckets; "
                         "it requires runtime='stream' with "
                         "grad_sync='overlap' (or 'auto')")
    dp_size = mesh.shape.get("data", 1)
    # layer-grad leaves the in-scan AR covers: replicated over data
    # (fsdp-sharded leaves keep the trailing sync)
    ar_mask = jax.tree.map(
        lambda s: "data" in ST.grad_sync_axes(s, mesh_axes),
        specs["layers"])
    overlap_sync = (pcfg.grad_sync == "overlap"
                    or (pcfg.grad_sync == "auto"
                        and pcfg.runtime == "stream"))
    overlap_sync = (overlap_sync and dp_size > 1
                    and any(jax.tree.leaves(ar_mask)))
    ar_groups = pcfg.ar_groups if overlap_sync else 1
    sched = SP.resolve_ring_schedule(pcfg.schedule, V)
    ml = (pcfg.mem_limit or None) if sched == "zb-auto" else None
    plan_ir = SP.build_schedule(sched, M_, S, V, mem_limit=ml,
                                grad_sync=ar_groups if overlap_sync
                                else False)
    instr = (SP.lower_to_instructions(plan_ir)
             if pcfg.runtime == "stream" else None)
    lowering = instr.ticks if instr else SP.lower_to_ticks(plan_ir)
    if pcfg.remat not in ("none", "stage", "stage_save_moe", "full"):
        raise ValueError(
            f"unknown remat {pcfg.remat!r}: expected none | stage | "
            f"stage_save_moe | full (the first three are equivalent under "
            f"first-class backward ticks — B recomputes the stage from "
            f"its stashed input)")
    return _TrainSchedule(specs=specs, plan_ir=plan_ir, lowering=lowering,
                          instr=instr, ar_mask=ar_mask,
                          overlap_sync=overlap_sync)


def train_step_counters(cfg: ArchConfig, mesh: Mesh, plan: ST.StagePlan,
                        pcfg: PipelineConfig, batch: dict, *,
                        param_dtype=jnp.float32) -> dict:
    """Static counts of one :func:`make_train_step` step, from the same
    schedule and lowering, with no compile.  ``batch`` holds the step's
    global batch (arrays or ``ShapeDtypeStruct``s).

    ``ticks``: slots of the tick scan.  ``send_bytes_per_step``: bytes one
    chip puts on the forward and backward rings in one step — under
    ``runtime='ticks'`` both rings shift every tick, under ``'stream'``
    only at the slots some device sends on them; one stage sends nothing.

    ``head_split``: the chips that share one micro-batch's loss head (its
    vocabulary split over the stages and the tensor axis).
    ``head_bytes_per_step``: bytes of the head's collectives one chip
    takes part in per step — with more than one stage, the psums of the
    last stage's output and of its cotangent, and with any split the
    softmax's per-token max, sums and their cotangents."""
    ts = _train_schedule(cfg, mesh, plan, pcfg, param_dtype)
    n_ticks = ts.lowering.n_ticks
    S = _n_stages(mesh, pcfg)
    if S == 1:
        shifts = 0
    elif ts.instr is not None:
        shifts = sum(ts.instr.fsend) + sum(ts.instr.bsend)
    else:
        shifts = 2 * n_ticks
    # one ring element: a micro-batch's hidden state [mb, T, d] (and the
    # encoder's [mb, Sf, d] for audio) in the embedding's dtype
    B, T = batch["labels"].shape[:2]
    mb = B // math.prod(mesh.shape[a] for a in _batch_axes(mesh, pcfg)) \
        // pcfg.n_microbatches
    rows = T + (batch["frames"].shape[1] if cfg.family == "audio" else 0)
    payload = mb * rows * cfg.d_model * jnp.dtype(param_dtype).itemsize
    head_split = S * mesh.shape["tensor"]
    # one head: the pmax, and the two psums and their two transposes, of
    # [mb, T] f32; the y and dy psums of the decoder's [mb, T, d]
    head_bytes = 5 * mb * T * 4 if head_split > 1 else 0
    if S > 1:
        head_bytes += 2 * mb * T * cfg.d_model * jnp.dtype(
            param_dtype).itemsize
    return dict(ticks=n_ticks, send_bytes_per_step=shifts * payload,
                head_split=head_split,
                head_bytes_per_step=pcfg.n_microbatches * head_bytes)


def _scoped(name: str, fn):
    """``fn`` with its ops under device scope ``name``."""
    def run(*a):
        with jax.named_scope(name):
            return fn(*a)
    return run


def make_train_step(cfg: ArchConfig, mesh: Mesh, plan: ST.StagePlan,
                    pcfg: PipelineConfig, *, optimizer=None,
                    param_dtype=jnp.float32):
    """Build the jitted pipeline train step.

    Returns (step_fn, specs): without an optimizer ``step_fn(params, batch)
    -> (loss, grads)``; with one ``step_fn(params, opt_state, batch) ->
    (params, opt_state, metrics)``.  Its ops carry the device scopes of
    :mod:`repro.core.trace`.

    The loss head: with one stage it runs in the seed B tick.  With
    more than one, every stage runs its share of the vocabulary (split
    over the stage and tensor axes) in one uniform ``lax.cond`` at the
    tick the lowering's ``head`` table marks, and the seed B reads its
    cotangent from the dy stash (:class:`repro.core.schedplan.
    TickLowering`); each stage accumulates only its rows of the table's
    gradient."""
    ts = _train_schedule(cfg, mesh, plan, pcfg, param_dtype)
    specs, plan_ir, lowering, instr = (ts.specs, ts.plan_ir, ts.lowering,
                                       ts.instr)
    ar_mask, overlap_sync = ts.ar_mask, ts.overlap_sync
    mesh_axes = tuple(mesh.axis_names)
    batch_axes = _batch_axes(mesh, pcfg)
    stage_ax = _stage_axes(mesh, pcfg)
    S = _n_stages(mesh, pcfg)
    V = plan.virtual
    M_ = pcfg.n_microbatches
    two_bw = pcfg.grad_sync == "2bw"
    if two_bw and optimizer is None:
        raise ValueError("grad_sync='2bw' double-buffers the weight "
                         "update and needs an optimizer")
    dp_size = mesh.shape.get("data", 1)
    has_w = lowering.has_w
    fsdp_dims = ST.fsdp_scan_dims(specs, virtual=V) if cfg.fsdp else {}
    ep_dp_axis = "data" if (cfg.moe and cfg.moe.ep_data) else None
    ep_n_dp = mesh.shape["data"] if ep_dp_axis else 1
    n_batch_shards = math.prod(mesh.shape[a] for a in batch_axes) or 1
    # the loss head splits its vocabulary over the stages (and the tensor
    # axis) wherever there is more than one stage; one stage keeps it in
    # the seed B tick
    split_head = S > 1

    def batch_spec_for(keys):
        spec = {}
        for k in keys:
            if k in ("tokens", "labels"):
                spec[k] = P(batch_axes, None)
            elif k in ("embeds", "frames"):
                spec[k] = P(batch_axes, None, None)
            elif k == "pos3":
                spec[k] = P(None, batch_axes, None)
        return spec

    tp_size = mesh.shape["tensor"]

    def global_loss_and_grads(params, batch):
        """One pass over the compiled mixed F/B(/W) tick table, producing
        this device's LOCAL loss term and its gradient contributions —
        no autodiff of the scan; every backward is an explicit tick."""
        stage_idx = lax.axis_index(stage_ax)
        tp_index = lax.axis_index("tensor")
        smeta = ST.stacked_meta(cfg, plan)
        smeta_local = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, stage_idx, 0, keepdims=False),
            smeta)
        lp_local = jax.tree.map(lambda a: a[0], params["layers"])

        # micro-batch preparation under vjp: the injection cotangents the
        # scan accumulates (stage 0's chunk-0 B ticks) drive the
        # embedding backward after the scan
        @functools.partial(_scoped, TR.PIPE_EMBED)
        def prep(embed):
            inj, pos, pos3, mb, T = _prepare_microbatches(
                cfg, dict(params, embed=embed), batch, M_, tp_index)
            return inj, (pos, pos3, mb, T)

        inj, prep_vjp, (pos, pos3, mb, T) = jax.vjp(
            prep, params["embed"], has_aux=True)
        labels_mb = batch["labels"].reshape(M_, mb, -1)
        fn_p = params["final_norm"]
        head_p = params.get("head", params["embed"])
        tab = _stream_tables(instr) if instr else _tick_tables(lowering)
        nT = lowering.n_ticks
        # d(global loss)/d(per-micro-batch ce) == d/d(per-op aux): the
        # seed every B tick's vjp is driven by
        ct_scale = jnp.float32(1.0 / (M_ * n_batch_shards * tp_size))

        def stage_f(lp_t, sm_t, x, m):
            """Forward of one stage chunk on micro-batch m: the function
            every F tick applies and every B/W tick re-runs under vjp
            from the stashed residual (remat='stage' structurally)."""
            p3 = None if pos3 is None else lax.dynamic_index_in_dim(
                pos3, m, 0, keepdims=False)
            y, a, _ = apply_stage(
                cfg, lp_t, sm_t, x, pos=pos, pos3=p3, cache=None,
                tp_axis="tensor", tp_index=tp_index,
                dp_axis=ep_dp_axis, n_dp=ep_n_dp,
                fsdp_axis="data" if cfg.fsdp else None,
                fsdp_dims=fsdp_dims,
                remat="full" if pcfg.remat == "full" else "none",
                unroll=pcfg.inner_unroll)
            return y, a

        @functools.partial(_scoped, TR.PIPE_HEAD)
        def head_loss(fn_param, head_param, y, m):
            """Per-micro-batch loss head (final norm + logits + xent):
            seeds the backward of the last virtual stage."""
            h = LYR.rms_norm(_hidden_of(y), fn_param, cfg.norm_eps)
            labels_m = lax.dynamic_index_in_dim(labels_mb, m, 0,
                                                keepdims=False)
            return M.logits_and_xent(cfg,
                                     {"head": head_param,
                                      "embed": head_param}, h, labels_m,
                                     "tensor", tp_index)

        if split_head:
            # this stage's ceil(rows/S) rows of the (tensor-local) table;
            # the last share starts early enough to fit and masks the
            # rows its neighbour owns
            n_rows = head_p.shape[0]
            share = -(-n_rows // S)
            own0 = stage_idx * share
            row_start = jnp.minimum(own0, n_rows - share)
            head_rows = lax.dynamic_slice_in_dim(head_p, row_start, share, 0)
            head_axes = ((stage_ax if isinstance(stage_ax, tuple)
                          else (stage_ax,)) + ("tensor",))

            @functools.partial(_scoped, TR.PIPE_HEAD)
            def split_xent(h, rows_p, m):
                """Micro-batch m's cross-entropy over this stage's share
                of the vocabulary, its softmax reduced over the stages
                (and the tensor axis)."""
                labels_m = lax.dynamic_index_in_dim(labels_mb, m, 0,
                                                    keepdims=False)
                return M.logits_and_xent(
                    cfg, {"embed": rows_p}, h, labels_m, head_axes,
                    row0=tp_index * n_rows + row_start,
                    skip=own0 - row_start)

        zero_pay = jax.tree.map(lambda q: jnp.zeros_like(q[0]), inj)

        def buf0(k):
            if not k:
                return None
            return jax.tree.map(
                lambda z: jnp.zeros((k,) + z.shape, z.dtype), zero_pay)

        carry0 = dict(
            fwd=zero_pay, bwd=zero_pay,
            xs=buf0(lowering.n_x),      # residual stash == peak-live row
            fin=buf0(lowering.n_f),     # parked forward arrivals
            bin=buf0(lowering.n_b),     # parked backward arrivals
            ct=buf0(lowering.n_c),      # zb: cotangents alive B -> W
            # split head: y-cotangents alive head tick -> B_SEED
            dys=(jnp.zeros((lowering.n_dy,) + _hidden_of(zero_pay).shape,
                           _hidden_of(zero_pay).dtype)
                 if split_head else None),
            dinj=jax.tree.map(jnp.zeros_like, inj),
            dlp=jax.tree.map(jnp.zeros_like, lp_local),
            dfn=jnp.zeros_like(fn_p),
            # split head: only this stage's rows of the table's gradient
            dhd=jnp.zeros_like(head_rows if split_head else head_p),
            ce=jnp.zeros((), jnp.float32), aux=jnp.zeros((), jnp.float32))

        def tick(carry, t):
            idx = stage_idx * nT + t
            g = lambda name: _at(tab[name], idx)
            m_t, v_t = g("m"), g("v")
            # park this tick's ring arrivals the consumer isn't ready for
            if carry["fin"] is not None:
                sl = g("fpark")
                carry = dict(carry, fin=_buf_write(
                    carry["fin"], jnp.maximum(sl, 0), carry["fwd"], sl >= 0))
            if carry["bin"] is not None:
                sl = g("bpark")
                carry = dict(carry, bin=_buf_write(
                    carry["bin"], jnp.maximum(sl, 0), carry["bwd"], sl >= 0))
            if V > 1:
                pick = lambda a: lax.dynamic_index_in_dim(a, v_t, 0,
                                                          keepdims=False)
                lp_t = jax.tree.map(pick, lp_local)
                sm_t = jax.tree.map(pick, smeta_local)
            else:
                lp_t, sm_t = lp_local, smeta_local

            def read_res(c):
                return _buf_read(c["xs"], jnp.maximum(g("xr"), 0))

            def acc_dlp(acc, dlp):
                if V > 1:
                    def upd(a, d):
                        cur = lax.dynamic_index_in_dim(a, v_t, 0,
                                                       keepdims=False)
                        return lax.dynamic_update_index_in_dim(
                            a, cur + d, v_t, 0)
                    return jax.tree.map(upd, acc, dlp)
                return jax.tree.map(lambda a, d: a + d, acc, dlp)

            def idle_fn(c):
                return c

            def f_fn(c):
                fsrc = g("fsrc")
                fresh = jax.tree.map(
                    lambda q: lax.dynamic_index_in_dim(q, m_t, 0,
                                                       keepdims=False), inj)
                if c["fin"] is not None:
                    inbox = _buf_read(c["fin"], jnp.maximum(g("fr"), 0))
                    x_in = jax.tree.map(
                        lambda fq, cq, bq: jnp.where(
                            fsrc == 0, fq, jnp.where(fsrc == 2, bq, cq)),
                        fresh, c["fwd"], inbox)
                else:
                    x_in = jax.tree.map(
                        lambda fq, cq: jnp.where(fsrc == 0, fq, cq),
                        fresh, c["fwd"])
                y, a = stage_f(lp_t, sm_t, x_in, m_t)
                return dict(c, fwd=y, aux=c["aux"] + a,
                            xs=_buf_write(c["xs"], g("xw"), x_in, True))

            def b_ct(c):
                if c["bin"] is not None:
                    inbox = _buf_read(c["bin"], jnp.maximum(g("br"), 0))
                    return jax.tree.map(
                        lambda cq, bq: jnp.where(g("bsrc") == 2, bq, cq),
                        c["bwd"], inbox)
                return c["bwd"]

            def b_ring_fn(c, ctan=None):
                x_res = read_res(c)
                if ctan is None:
                    ctan = b_ct(c)
                if has_w:
                    # zb: input-gradient only; stash the cotangent for W
                    _, vjp = jax.vjp(
                        lambda xx: stage_f(lp_t, sm_t, xx, m_t), x_res)
                    (dx,) = vjp((ctan, ct_scale))
                    c = dict(c, ct=_buf_write(
                        c["ct"], jnp.maximum(g("cw"), 0), ctan,
                        g("cw") >= 0))
                else:
                    _, vjp = jax.vjp(
                        lambda lp, xx: stage_f(lp, sm_t, xx, m_t),
                        lp_t, x_res)
                    dlp, dx = vjp((ctan, ct_scale))
                    c = dict(c, dlp=acc_dlp(c["dlp"], dlp))
                return dict(c, bwd=dx, dinj=_buf_write(
                    c["dinj"], m_t, dx, g("dinj")))

            def b_seed_fn(c):
                x_res = read_res(c)
                if has_w:
                    # zb: vjp the stage (input grad) and the loss head
                    # separately — the head's y-cotangent is stashed so
                    # the seed's W tick is an ordinary w_fn, and the
                    # head/final-norm grads (outside the pipeline
                    # stages) land here without a second head pass
                    (y, _), svjp = jax.vjp(
                        lambda xx: stage_f(lp_t, sm_t, xx, m_t), x_res)
                    ce_m, hvjp = jax.vjp(
                        lambda fnp, hdp, yy: head_loss(fnp, hdp, yy, m_t),
                        fn_p, head_p, y)
                    dfn_d, dhd_d, dy = hvjp(ct_scale)
                    (dx,) = svjp((dy, ct_scale))
                    c = dict(c, dfn=c["dfn"] + dfn_d, dhd=c["dhd"] + dhd_d,
                             ct=_buf_write(c["ct"], jnp.maximum(g("cw"), 0),
                                           dy, g("cw") >= 0))
                else:
                    def fl(lp, fnp, hdp, xx):
                        y, a = stage_f(lp, sm_t, xx, m_t)
                        return head_loss(fnp, hdp, y, m_t), a
                    (ce_m, _), vjp = jax.vjp(fl, lp_t, fn_p, head_p, x_res)
                    dlp, dfn_d, dhd_d, dx = vjp((ct_scale, ct_scale))
                    c = dict(c, dlp=acc_dlp(c["dlp"], dlp),
                             dfn=c["dfn"] + dfn_d, dhd=c["dhd"] + dhd_d)
                return dict(c, bwd=dx, ce=c["ce"] + ce_m,
                            dinj=_buf_write(c["dinj"], m_t, dx, g("dinj")))

            def b_split_seed_fn(c):
                """The seed B once the head is split: a ring B whose
                cotangent is the split head's, from the dy stash."""
                dy = _buf_read(c["dys"], jnp.maximum(g("dyr"), 0))
                ctan = (dict(zero_pay, h_dec=dy)
                        if isinstance(zero_pay, dict) else dy)
                return b_ring_fn(c, ctan)

            def head_fn(c):
                """The split head on micro-batch ``head_m[t]``, run by
                every stage: the last stage's output y reaches all by one
                psum, each takes its share of the vocabulary, and the
                y-cotangent goes into the dy stash; the loss and the
                final norm's gradient are counted on the last stage."""
                m_h = jnp.maximum(_at(tab["head_m"], t), 0)
                last = stage_idx == S - 1
                y = _hidden_of(c["fwd"])
                y = lax.psum(jnp.where(last, y, jnp.zeros_like(y)), stage_ax)
                h, nvjp = jax.vjp(
                    lambda yy, fnp: LYR.rms_norm(yy, fnp, cfg.norm_eps),
                    y, fn_p)
                ce_m, xvjp = jax.vjp(
                    lambda hh, rp: split_xent(hh, rp, m_h), h, head_rows)
                # each stage's copy of the loss carries 1/S of its seed:
                # the vjp of a psum is a psum over the same stages
                dh, drows = xvjp(ct_scale / S)
                dy, dfn_d = nvjp(lax.psum(dh, stage_ax))
                return dict(
                    c, dys=_buf_write(c["dys"], _at(tab["dyw"], t), dy, True),
                    dhd=c["dhd"] + drows,
                    dfn=c["dfn"] + jnp.where(last, dfn_d, 0.0),
                    ce=c["ce"] + jnp.where(last, ce_m, 0.0))

            def w_fn(c):
                x_res = read_res(c)
                ctan = _buf_read(c["ct"], jnp.maximum(g("cr"), 0))
                _, vjp = jax.vjp(
                    lambda lp: stage_f(lp, sm_t, x_res, m_t), lp_t)
                (dlp,) = vjp((ctan, ct_scale))
                return dict(c, dlp=acc_dlp(c["dlp"], dlp))

            branches = [idle_fn, _scoped(TR.PIPE_F, f_fn),
                        _scoped(TR.PIPE_B, b_ring_fn),
                        _scoped(TR.PIPE_B, b_split_seed_fn if split_head
                                else b_seed_fn)]
            if has_w:
                branches.append(_scoped(TR.PIPE_W, w_fn))
            kind_t = g("kind")
            if plan_ir.has_grad_sync:
                # AR slots execute below, outside the switch; the
                # compute branch for them is idle
                kind_t = jnp.where(kind_t == SP.TICK_AR, SP.TICK_IDLE,
                                   kind_t)
            carry = lax.switch(jnp.clip(kind_t, 0, len(branches) - 1),
                               branches, carry)
            if split_head:
                # the predicate depends on the tick alone, so every
                # device enters the cond and its collectives line up
                with jax.named_scope(TR.PIPE_HEAD):
                    carry = lax.cond(_at(tab["head"], t), head_fn,
                                     lambda c: c, carry)
            if plan_ir.has_grad_sync:
                n_groups = plan_ir.grad_sync_groups or 1

                def ar_fn(c):
                    """One AR slot: reduce-scatter + all-gather this
                    device's retired chunk-``v_t`` layer-grad bucket over
                    ``data``.  The gate (``aron[t]``) depends on the slot
                    counter alone, so every device enters the cond;
                    within one data group all members share a stage ->
                    identical tables -> they sync the same bucket
                    together.  Groups whose device holds no AR here
                    compute a discarded sum (masked write-back).  With
                    ``ar_groups > 1`` the AR op's ``m`` field is the
                    layer-group index: each slot syncs only rows
                    ``[g * Lc/G, (g+1) * Lc/G)`` of the chunk's grads —
                    every element still reduced exactly once, so the
                    result stays bit-equal to the one-bucket sync."""
                    arw = g("kind") == SP.TICK_AR
                    g_t = g("m")        # AR ops carry the group index
                    dlp_leaves, treedef = jax.tree.flatten(c["dlp"])
                    masks = jax.tree.leaves(ar_mask)
                    chunks = [
                        (i, lax.dynamic_index_in_dim(a, v_t, 0,
                                                     keepdims=False)
                            if V > 1 else a)
                        for i, (a, el) in enumerate(zip(dlp_leaves,
                                                        masks)) if el]
                    slices = []
                    for i, ch in chunks:
                        if n_groups > 1:
                            rows = ch.shape[0]
                            if rows % n_groups:
                                raise ValueError(
                                    f"ar_groups={n_groups} must divide "
                                    f"the {rows} layers per chunk "
                                    f"(leaf {i})")
                            rg = rows // n_groups
                            sl = lax.dynamic_slice_in_dim(
                                ch, g_t * rg, rg, 0)
                        else:
                            sl = ch
                        slices.append((i, ch, sl))
                    # pack per dtype (concat cannot mix), one RS+AG over
                    # data per dtype, unpack; dp=2's single addition per
                    # element keeps the result bit-equal to the trailing
                    # psum it replaces
                    by_dt: dict = {}
                    for i, ch, sl in slices:
                        by_dt.setdefault(sl.dtype, []).append((i, ch, sl))
                    out = dict(enumerate(dlp_leaves))
                    for dt, group in by_dt.items():
                        flat = jnp.concatenate(
                            [sl.reshape(-1) for _, _, sl in group])
                        pad = (-flat.size) % dp_size
                        if pad:
                            flat = jnp.concatenate(
                                [flat, jnp.zeros((pad,), dt)])
                        red = lax.psum_scatter(flat, "data",
                                               scatter_dimension=0,
                                               tiled=True)
                        full = lax.all_gather(red, "data", axis=0,
                                              tiled=True)
                        off = 0
                        for i, ch, sl in group:
                            new = full[off:off + sl.size].reshape(
                                sl.shape)
                            off += sl.size
                            new = jnp.where(arw, new, sl)
                            if n_groups > 1:
                                rg = ch.shape[0] // n_groups
                                new = lax.dynamic_update_slice_in_dim(
                                    ch, new, g_t * rg, 0)
                            out[i] = (lax.dynamic_update_index_in_dim(
                                dlp_leaves[i], new, v_t, 0)
                                if V > 1 else new)
                    return dict(c, dlp=jax.tree.unflatten(
                        treedef, [out[i]
                                  for i in range(len(dlp_leaves))]))

                with jax.named_scope(TR.PIPE_AR):
                    carry = lax.cond(_at(tab["aron"], t), ar_fn,
                                     lambda c: c, carry)
            perm_f = [(i, (i + 1) % S) for i in range(S)]
            perm_b = [(i, (i - 1) % S) for i in range(S)]
            shift_f = lambda tr: jax.tree.map(
                lambda a: lax.ppermute(a, stage_ax, perm_f), tr)
            shift_b = lambda tr: jax.tree.map(
                lambda a: lax.ppermute(a, stage_ax, perm_b), tr)
            with jax.named_scope(TR.PIPE_SEND):
                if instr is not None:
                    # stream runtime: a ring shifts ONLY at slots where
                    # some device SENDs on it.  Every value travels
                    # exactly one hop at its producer's slot (arrival is
                    # always the next slot in the compiled tables), so
                    # slots without a scheduled SEND carry only dead data
                    # — skipping the collective is exact, and W/idle
                    # slots run with no barrier at all.  The gate is a
                    # function of the slot counter alone (uniform across
                    # devices), so the collective inside the cond is safe
                    # (cf. the gate_ticks serve path).
                    fwd = lax.cond(_at(tab["fsend"], t), shift_f,
                                   lambda tr: tr, carry["fwd"])
                    bwd = lax.cond(_at(tab["bsend"], t), shift_b,
                                   lambda tr: tr, carry["bwd"])
                else:
                    # tick runtime: both rings shift every tick
                    fwd = shift_f(carry["fwd"])
                    bwd = shift_b(carry["bwd"])
            return dict(carry, fwd=fwd, bwd=bwd), None

        out, _ = lax.scan(tick, carry0, jnp.arange(nT),
                          unroll=pcfg.tick_scan_unroll)
        # Per-device LOCAL term of the global loss: global = psum(local).
        # ce/aux accumulated only where the table placed the ops, so no
        # stage masking is needed; tensor replication is divided out.
        local = (out["ce"] + out["aux"]) / M_ / (n_batch_shards * tp_size)
        with jax.named_scope(TR.PIPE_EMBED):
            (d_embed,) = prep_vjp(out["dinj"])
        grads = dict(embed=d_embed,
                     layers=jax.tree.map(lambda a: a[None], out["dlp"]),
                     final_norm=out["dfn"])
        add_dhd = lambda full: full + out["dhd"]
        if split_head:
            # this stage's rows into the full-shape gradient; pipe.sync's
            # psum over the stages assembles the table's
            def add_dhd(full):
                cur = lax.dynamic_slice_in_dim(full, row_start, share, 0)
                return lax.dynamic_update_slice_in_dim(
                    full, cur + out["dhd"], row_start, 0)
        if "head" in params:
            grads["head"] = (add_dhd(jnp.zeros_like(head_p)) if split_head
                             else out["dhd"])
        else:
            grads["embed"] = add_dhd(grads["embed"])
        return local, grads

    def sharded_step(params, batch):
        local, grads = global_loss_and_grads(params, batch)

        def sync(g, s, layer):
            axes = ST.grad_sync_axes(s, mesh_axes)
            if "data" in axes:
                # the data-axis sync is its own reduction, split from
                # the other replication axes: the AR-op schedule
                # replaces exactly this psum (for layer grads) with the
                # in-scan bucket collectives, and performing the data
                # sum separately for EVERY leaf in BOTH paths keeps the
                # two programs' collective structure — and hence the
                # reduction order of the remaining axes — identical
                if not (layer and plan_ir.has_grad_sync):
                    g = lax.psum(g, "data")
                axes = tuple(a for a in axes if a != "data")
            return lax.psum(g, axes) if axes else g

        with jax.named_scope(TR.PIPE_SYNC):
            loss = lax.psum(local, mesh_axes)
            grads = {
                k: jax.tree.map(
                    functools.partial(sync, layer=(k == "layers")),
                    grads[k], specs[k])
                for k in grads}
        return loss, grads

    _built: dict = {}

    def fn(params, batch):
        keys = tuple(sorted(batch))
        if keys not in _built:
            _built[keys] = jax.shard_map(
                sharded_step, mesh=mesh,
                in_specs=(specs, batch_spec_for(keys)),
                out_specs=(P(), specs), check_vma=False)
        return _built[keys](params, batch)

    if optimizer is None:
        return jax.jit(fn), specs

    opt_update = optimizer.make_update(specs, mesh)

    if two_bw:
        def train_step(params, opt_state, batch):
            # PipeDream-2BW double-buffered weights: compute step k's
            # grads as usual, but APPLY the stashed step k-1 grads —
            # the pending collective result isn't consumed until the
            # next call, giving it a full step of slack (sync-free
            # steady state).  Step 0 applies its own grads (warmup:
            # nothing is pending), so the trajectory is the synchronous
            # one shifted by exactly one step from step 1 on.
            loss, grads = fn(params, batch)
            primed = opt_state["primed"]
            apply_g = jax.tree.map(
                lambda p, g: jnp.where(primed, p, g),
                opt_state["pending"], grads)
            with jax.named_scope(TR.OPT_UPDATE):
                params, inner = opt_update(params, apply_g,
                                           opt_state["inner"])
            new_state = dict(inner=inner, pending=grads,
                             primed=jnp.ones((), jnp.bool_))
            return params, new_state, dict(loss=loss)
    else:
        def train_step(params, opt_state, batch):
            loss, grads = fn(params, batch)
            with jax.named_scope(TR.OPT_UPDATE):
                params, opt_state = opt_update(params, grads, opt_state)
            return params, opt_state, dict(loss=loss)

    # JAX's persistent compilation cache leaves op metadata, the scopes
    # among it, out of its key, and an executable it loads keeps the
    # metadata it was compiled with; the step's name (its HLO module's)
    # is in the key, so a change to the scopes alone renames the step
    return jax.jit(train_step, donate_argnums=(0, 1)), specs


def init_2bw_state(opt_state, params):
    """Wrap an optimizer state for ``grad_sync='2bw'`` double-buffered
    weights: ``pending`` holds the previous step's gradients (zeros
    until the first step), ``primed`` flips True after step 0 so the
    warmup step applies its own gradients instead of the zero
    buffer."""
    return dict(inner=opt_state,
                pending=jax.tree.map(jnp.zeros_like, params),
                primed=jnp.zeros((), jnp.bool_))


def state_shardings(mesh: Mesh, specs, opt_state=None):
    """``NamedSharding`` pytrees for the training state, from the param
    specs :func:`make_train_step` returns — the ``shardings`` argument
    :func:`repro.checkpoint.restore_checkpoint` wants so a resumed
    state lands directly on the mesh in the step function's layout.

    Returns the param sharding tree alone, or — given an ``opt_state``
    skeleton — ``(param_shardings, opt_shardings)`` where any opt-state
    entry whose tree structure mirrors the params (AdamW's ``m``/``v``
    moments, SGD's momentum, the 2bw ``pending`` gradient buffer)
    inherits the param shardings; nested wrappers (the 2bw
    ``inner``/``pending``/``primed`` dict) recurse, and everything else
    (step counters, flags) is replicated."""
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    if opt_state is None:
        return param_sh
    rep = NamedSharding(mesh, P())
    pstruct = jax.tree.structure(specs)

    def mirror(sub):
        if jax.tree.structure(sub) == pstruct:
            return param_sh
        if isinstance(sub, dict):
            return {k: mirror(v) for k, v in sub.items()}
        return jax.tree.map(lambda _: rep, sub)

    return param_sh, {k: mirror(v) for k, v in opt_state.items()}


# ---------------------------------------------------------------------------
# Serving: pipelined decode (and prefill).
# ---------------------------------------------------------------------------

def cache_specs(cfg: ArchConfig, cache_shapes, batch_axes, *,
                b_sharded: bool, stage_axis="stage", virtual: int = 1):
    """Stage-sharded cache specs: every leaf is [S, Lps, B, ...] — or
    [S, V, Lc, B, ...] for an interleaved (virtual > 1) plan, which shifts
    the positional dims right by one.  Attention K/V caches additionally
    shard their head dim over tensor."""
    off = 0 if virtual == 1 else 1
    def leaf(path, l):
        name = getattr(path[-1], "key", None)
        if name == "len":
            # per-slot offsets [S, (V,) Lc, B]: trailing axis is the slot
            spec = [stage_axis] + [None] * (l.ndim - 1)
            if b_sharded and l.ndim >= 3:
                spec[-1] = batch_axes
            return P(*spec)
        spec = [stage_axis, None] + [None] * (l.ndim - 2)
        if b_sharded and l.ndim >= 3 + off:
            spec[2 + off] = batch_axes
        if name in ("k", "v", "xk", "xv") and l.ndim >= 6 + off:
            spec[4 + off] = "tensor"   # [S, (V,) Lps, B, len, heads, hd]
        return P(*spec)
    return jax.tree_util.tree_map_with_path(leaf, cache_shapes)


def init_pipeline_cache(cfg: ArchConfig, plan: ST.StagePlan, batch: int,
                        max_len: int, *, dtype=jnp.float32, enc_len: int = 0):
    """Global cache [S, Lps, B, ...] (call under jit with sharding, or use
    eval_shape for the dry run).

    When n_kv_heads doesn't divide the tensor axis, the cache carries
    ``tensor`` head slots (one per device) — the inherent duplication of
    serving few-KV-head models under tensor parallelism."""
    tp = plan.tensor
    nkv = cfg.n_kv_heads
    if cfg.attn_kind == "gqa" and nkv % tp != 0:
        nh_l = max(1, cfg.n_heads // tp)
        g = cfg.n_heads // nkv
        nkv = tp * max(1, nh_l // g)
    pad_cfg = dataclasses.replace(cfg, n_layers=plan.n_layers_padded,
                                  n_kv_heads=nkv)
    c = M.init_cache(pad_cfg, batch, max_len, tp=1, dtype=dtype,
                     enc_len=enc_len)
    return jax.tree.map(lambda a: ST._stack_chunks(a, plan), c)


def _is_kv_len(path) -> bool:
    """True only for the ``kv`` subtree's ``len`` offset leaves — scoped so
    an unrelated cache field that happens to be named ``len`` (e.g. in a
    future ssm/audio extension) is never silently bumped."""
    keys = [getattr(p, "key", None) for p in path]
    return bool(keys) and keys[-1] == "len" and "kv" in keys[:-1]


def _restore_len(c_new, c_old):
    """Copy kv 'len' offsets back from c_old into c_new."""
    def pick(path, new, old):
        return old if _is_kv_len(path) else new
    return jax.tree_util.tree_map_with_path(pick, c_new, c_old)


def _advance_len(cache, adv):
    """Advance the kv 'len' offsets by ``adv`` — a scalar (uniform step) or
    a per-slot [B] vector (mixed prefill/decode: each request advances by
    its own valid-token count), broadcast over the trailing slot axis of
    the [Lc, B] / [V, Lc, B] leaves."""
    def bump(path, leaf):
        return leaf + adv if _is_kv_len(path) else leaf
    return jax.tree_util.tree_map_with_path(bump, cache)


def make_serve_step(cfg: ArchConfig, mesh: Mesh, plan: ST.StagePlan,
                    pcfg: PipelineConfig, *, batch_sharded: bool = True,
                    param_dtype=jnp.float32, cache_dtype=jnp.float32,
                    max_len: int = 0, global_batch: int = 0, q_len: int = 1,
                    enc_len: int = 0):
    """Build the jitted pipelined decode/prefill step:
    ``serve_step(params, cache, batch) -> (last_logits, cache)``.

    ``q_len=1`` is one-token decode; ``q_len=seq`` is prefill (KV/SSM cache
    populated, logits returned for the last position).  Micro-batches split
    the batch dimension; the per-stage cache is [Lps, B_loc, ...] and each
    tick dynamic-slices its micro-batch rows.  Cache ``len`` offsets are
    per-slot [B] vectors, frozen during the tick scan (each row is
    processed exactly once per step) and advanced once at the end.

    Continuous batching: the batch may carry ``n_valid`` [B] int32 — each
    slot then holds the first ``n_valid`` columns of its row as real
    tokens (``0`` = idle slot, ``1`` = decode, up to ``q_len`` = chunked
    prefill) and advances its cache offset by exactly that count.  Rows
    start at their own per-slot offsets, the returned ``[B, 1, vocab]``
    logits are gathered at each slot's last valid column, and garbage
    written by padding columns is causally masked and later overwritten,
    so mixed prefill chunks and decode ticks share one compiled step.
    Attention families only (ssm/hybrid/audio recurrent state has no
    per-token offsets to mask padding with).

    Interleaved (``plan.virtual`` = V > 1) plans replay the same compiled
    schedule table as training (cache leaves are [V, Lc, B, ...]; each
    tick chunk-indexes them).  For prefill the V-times-smaller flush
    bubble pays directly; one-token decode rides the same table — each
    extra ring lap adds S hops to the token's critical path, so it is a
    throughput-over-latency trade the serving scheduler opts into (e.g.
    to keep one parameter layout for both phases).
    """
    V = plan.virtual
    shape_params = jax.eval_shape(
        lambda k: ST.init_stacked_params(cfg, k, plan, param_dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    mesh_axes = tuple(mesh.axis_names)
    batch_axes = _batch_axes(mesh, pcfg)
    stage_ax = _stage_axes(mesh, pcfg)
    S = _n_stages(mesh, pcfg)
    assert plan.n_stages == S, \
        f"stage plan ({plan.n_stages}) != mesh pipeline depth ({S})"
    specs = ST.param_specs(cfg, shape_params, stage_axis=stage_ax,
                           fsdp_axis="data" if cfg.fsdp else None,
                           tensor_size=mesh.shape["tensor"], virtual=V)
    M_ = pcfg.n_microbatches
    sched = SP.resolve_ring_schedule(pcfg.schedule, V)
    ml = (pcfg.mem_limit or None) if sched == "zb-auto" else None
    lowering = SP.lower_to_ring(SP.build_schedule(sched, M_, S, V,
                                                  mem_limit=ml))
    fsdp_dims = ST.fsdp_scan_dims(specs, virtual=V) if cfg.fsdp else {}
    ep_dp_axis = "data" if (cfg.moe and cfg.moe.ep_data) else None
    ep_n_dp = mesh.shape["data"] if ep_dp_axis else 1

    cache_shapes = jax.eval_shape(
        functools.partial(init_pipeline_cache, cfg, plan, global_batch,
                          max_len, dtype=cache_dtype, enc_len=enc_len))
    cspecs = cache_specs(cfg, cache_shapes, batch_axes,
                         b_sharded=batch_sharded, stage_axis=stage_ax,
                         virtual=V)
    b_ax = batch_axes if batch_sharded else None

    def batch_spec_for(keys):
        sp = {}
        for kk in keys:
            if kk == "tokens":
                sp[kk] = P(b_ax, None)
            elif kk == "n_valid":
                sp[kk] = P(b_ax)
            elif kk == "pos3":
                sp[kk] = P(None, b_ax, None)
            else:
                raise ValueError(f"unknown serve batch key {kk!r}")
        return sp

    tab = _ring_tables(lowering)
    MV = M_ * V
    use_retbuf = lowering.needs_retbuf
    retbuf_sharded = use_retbuf and _shard_retbuf(cfg, S, stage_ax)

    def sharded_decode(params, cache, batch):
        stage_idx = lax.axis_index(stage_ax)
        tp_index = lax.axis_index("tensor")
        smeta = ST.stacked_meta(cfg, plan)
        smeta_local = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, stage_idx, 0, keepdims=False),
            smeta)
        lp_local = jax.tree.map(lambda a: a[0], params["layers"])
        cache_local = jax.tree.map(lambda a: a[0], cache)

        x_all = M.embed_tokens(cfg, params["embed"], batch["tokens"],
                               "tensor", tp_index)           # [B_loc,q,d]
        B_loc = x_all.shape[0]
        assert B_loc % M_ == 0
        mb = B_loc // M_
        # per-slot cache offsets -> per-row positions, sliced per micro-batch
        cur_len = jnp.asarray(M._cache_len(cache_local), jnp.int32)
        if cur_len.ndim == 0:
            cur_len = jnp.broadcast_to(cur_len, (B_loc,))
        pos_all = cur_len[:, None] + jnp.arange(q_len, dtype=jnp.int32)[None]
        if cfg.family == "audio":
            x_all = x_all + M.sinusoid_pos(pos_all, cfg.d_model, x_all.dtype)
        inj = x_all.reshape(M_, mb, q_len, -1)
        if cfg.family == "audio":
            # decode consumes the cross K/V cache; h_enc is vestigial
            inj = dict(h_dec=inj,
                       h_enc=jnp.zeros((M_, mb, 1, cfg.d_model), x_all.dtype))
        pos_mb = pos_all.reshape(M_, mb, q_len)
        pos3 = None
        if batch.get("pos3") is not None:
            pos3 = jnp.moveaxis(batch["pos3"].reshape(3, M_, mb, q_len), 1, 0)

        def tick(carry, t):
            if use_retbuf:
                x_cur, cache_l, outbuf, retbuf = carry
            else:
                x_cur, cache_l, outbuf = carry
                retbuf = None
            retbuf, x_in = _ring_ingest(tab, MV, S, stage_idx, t,
                                        inj, x_cur, retbuf,
                                        stage_ax=stage_ax,
                                        sharded=retbuf_sharded)
            # element (micro-batch, chunk) this stage works on at tick t
            e_idx = t - stage_idx
            valid = (e_idx >= 0) & (e_idx < MV)
            ecl = jnp.clip(e_idx, 0, MV - 1)
            mc = _at(tab["m"], ecl)
            if V > 1:
                chunk = _at(tab["v"], ecl)
                lp_t = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, chunk, 0,
                                                       keepdims=False),
                    lp_local)
                sm_t = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, chunk, 0,
                                                       keepdims=False),
                    smeta_local)
                cache_chunk = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, chunk, 0,
                                                       keepdims=False),
                    cache_l)
            else:
                lp_t, sm_t, cache_chunk = lp_local, smeta_local, cache_l
            # slice this micro-batch's cache rows ([Lc, B_loc, ...] leaves;
            # 'len' counters are [Lc] and pass through whole)
            c_mb = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, mc * mb, mb, 1)
                if a.ndim >= 2 else a, cache_chunk)
            p3 = None if pos3 is None else pos3[mc]
            pos_t = pos_mb[mc]

            def _run(args):
                x_in, c_mb = args
                y, _, c_new = apply_stage(
                    cfg, lp_t, sm_t, x_in, pos=pos_t, pos3=p3,
                    cache=c_mb, tp_axis="tensor", tp_index=tp_index,
                    dp_axis=ep_dp_axis, n_dp=ep_n_dp,
                    fsdp_axis="data" if cfg.fsdp else None,
                    fsdp_dims=fsdp_dims, remat="none",
                    unroll=pcfg.inner_unroll)
                return y, c_new

            if pcfg.gate_ticks:
                # validity is uniform across (data, tensor) for a fixed
                # (stage, tick), so collectives inside the branch are safe
                y, c_new = lax.cond(valid, _run, lambda a: a, (x_in, c_mb))
            else:
                y, c_new = _run((x_in, c_mb))
            # write back only when this tick was valid for this stage;
            # freeze 'len' counters (all micro-batches share the offset)
            c_new = jax.tree.map(
                lambda new, old: jnp.where(valid, new, old), c_new, c_mb)
            c_new = _restore_len(c_new, c_mb)
            cache_chunk = jax.tree.map(
                lambda full, blk: lax.dynamic_update_slice_in_dim(
                    full, blk.astype(full.dtype), mc * mb, 1)
                if full.ndim >= 2 else blk, cache_chunk, c_new)
            if V > 1:
                cache_l = jax.tree.map(
                    lambda full, blk: lax.dynamic_update_index_in_dim(
                        full, blk.astype(full.dtype), chunk, 0),
                    cache_l, cache_chunk)
            else:
                cache_l = cache_chunk
            # last stage emits the final (chunk V-1) last-position hidden
            out_e = t - (S - 1)
            oecl = jnp.clip(out_e, 0, MV - 1)
            oc = _at(tab["m"], oecl)
            do_collect = ((out_e >= 0) & _at(tab["collect"], oecl)
                          & (stage_idx == S - 1))
            curo = lax.dynamic_index_in_dim(outbuf, oc, 0, keepdims=False)
            wr = jnp.where(do_collect, _hidden_of(y), curo)
            outbuf = lax.dynamic_update_index_in_dim(outbuf, wr, oc, 0)
            perm = [(i, (i + 1) % S) for i in range(S)]
            x_next = jax.tree.map(lambda a: lax.ppermute(a, stage_ax, perm), y)
            if use_retbuf:
                return (x_next, cache_l, outbuf, retbuf), None
            return (x_next, cache_l, outbuf), None

        x0 = jax.tree.map(lambda q: jnp.zeros_like(q[0]), inj)
        outbuf0 = jnp.zeros((M_, mb, q_len, cfg.d_model), x_all.dtype)
        carry0 = (x0, cache_local, outbuf0)
        if use_retbuf:
            carry0 = carry0 + (_retbuf_init(inj, S, retbuf_sharded),)
        carry_out, _ = lax.scan(
            tick, carry0, jnp.arange(lowering.n_ticks),
            unroll=pcfg.tick_scan_unroll)
        cache_local, outbuf = carry_out[1], carry_out[2]
        nv = batch.get("n_valid")
        adv = q_len if nv is None else nv.astype(jnp.int32)
        cache_local = _advance_len(cache_local, adv)

        # gather each slot's last *valid* column (uniform steps: column -1)
        hidden = outbuf.reshape(B_loc, q_len, -1)
        if nv is None:
            hidden = hidden[:, -1:]
        else:
            col = jnp.clip(nv.astype(jnp.int32), 1, q_len) - 1
            hidden = jnp.take_along_axis(hidden, col[:, None, None], axis=1)
        h = LYR.rms_norm(hidden, params["final_norm"], cfg.norm_eps)
        table = params.get("head", params["embed"])
        logits = (h @ table.T).astype(jnp.float32)
        # broadcast real logits from the last stage to every stage
        on_last = (stage_idx == S - 1).astype(logits.dtype)
        logits = lax.psum(logits * on_last, stage_ax)
        new_cache = jax.tree.map(lambda a: a[None], cache_local)
        return logits, new_cache

    out_specs = (P(b_ax, None, "tensor"), cspecs)
    _built: dict = {}

    def fn(params, cache, batch):
        keys = tuple(sorted(batch))
        if "n_valid" in keys and cfg.family in ("ssm", "hybrid", "audio"):
            raise ValueError(
                f"continuous batching (n_valid) needs per-token cache "
                f"offsets to mask padding columns; the {cfg.family} "
                f"family carries recurrent ssm/conv (or cross-attention) "
                f"state that padding tokens would pollute — serve it "
                f"with uniform steps instead")
        if keys not in _built:
            _built[keys] = jax.shard_map(
                sharded_decode, mesh=mesh,
                in_specs=(specs, cspecs, batch_spec_for(keys)),
                out_specs=out_specs, check_vma=False)
        return _built[keys](params, cache, batch)

    return jax.jit(fn, donate_argnums=(1,)), specs, cspecs, cache_shapes
