"""Pipelined serving driver: batched prefill + greedy decode.

CPU quickstart:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.serve \\
        --arch llama3.2-1b --reduced --data 2 --stages 2 --tensor 2 \\
        --batch 8 --prompt-len 32 --gen 16

``--virtual V`` (V > 1) runs the *prefill* phase on an interleaved
1F1B-I plan — prefill is throughput-bound, so the V-times-smaller flush
bubble pays — then restacks the V-chunk parameters and the chunk-stacked
[S, V, Lc, ...] prefill cache contiguously for the decode loop, whose
plan stays V=1.  The restack runs as ONE jitted call that *donates* the
prefill copies: the contiguous buffers are built in place of the chunked
ones, so the handoff never holds params+cache twice (the old eager
restack had a transient 2x residency spike).

Timing discipline: both jitted steps are AOT-compiled (``.lower(...)
.compile()``) before any timed region and every phase is fenced with
``block_until_ready`` — compile time, prefill throughput, and
steady-state decode throughput are reported separately instead of the
first decode step's compile silently landing inside the decode loop.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import resolve_config
from repro.pipeline import runtime as RT
from repro.pipeline import stage as ST


class Served(NamedTuple):
    prompt: np.ndarray           # [batch, prompt_len] token ids
    tokens: np.ndarray           # [batch, gen] greedy tokens
    prefill_logits: np.ndarray   # [batch, vocab] at the last prompt position


def main(argv=None) -> Served:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-test widths (d_model 256, <= 4 heads, "
                         "vocab <= 1024)")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth; without --reduced every width stays as "
                         "published")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--stages", type=int, default=0)
    ap.add_argument("--tensor", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--virtual", type=int, default=0,
                    help="interleave the PREFILL over V chunks/device "
                         "(decode always runs the contiguous V=1 plan)")
    ap.add_argument("--schedule", default="auto",
                    help="prefill op order (schedplan name); memlean needs "
                         "--microbatches %% stages == 0")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        cfg = resolve_config(args.arch, reduced=args.reduced,
                             layers=args.layers)
    except ValueError as e:
        ap.error(str(e))
    if args.stages:
        cfg = dataclasses.replace(cfg, stages=args.stages)
    if args.tensor:
        cfg = dataclasses.replace(cfg, tensor=args.tensor)
    if args.virtual:
        cfg = dataclasses.replace(cfg, virtual=args.virtual)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((args.data, cfg.stages, cfg.tensor),
                     ("data", "stage", "tensor"))
    # decode always runs the contiguous plan; prefill may interleave
    plan = ST.plan_stages(cfg, virtual=1)
    plan_p = ST.plan_stages(cfg) if cfg.virtual > 1 else plan
    params_p = ST.init_stacked_params(cfg, jax.random.PRNGKey(args.seed),
                                      plan_p)
    max_len = args.prompt_len + args.gen
    pcfg = RT.PipelineConfig(n_microbatches=args.microbatches,
                             schedule=args.schedule)
    pcfg1 = RT.PipelineConfig(n_microbatches=args.microbatches)

    prefill, _, cspecs_p, _ = RT.make_serve_step(
        cfg, mesh, plan_p, pcfg, max_len=max_len, global_batch=args.batch,
        q_len=args.prompt_len)
    decode, dspecs, cspecs, _ = RT.make_serve_step(
        cfg, mesh, plan, pcfg1, max_len=max_len, global_batch=args.batch,
        q_len=1)
    cache = jax.jit(
        lambda: RT.init_pipeline_cache(cfg, plan_p, args.batch, max_len),
        out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   cspecs_p))()

    prompt = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)
    tok0 = jnp.zeros((args.batch, 1), jnp.int32)

    # ---- warm-up: compile every phase before any timed region ------------
    t0 = time.perf_counter()
    prefill_x = prefill.lower(params_p, cache, dict(tokens=prompt)).compile()
    restack_x = None
    if cfg.virtual > 1:
        # one donated jitted call re-folds the V-chunked params AND the
        # chunk-stacked [S, V, Lc, ...] prefill cache to the contiguous
        # [S, Lps, ...] decode layout in place of the prefill buffers
        shard = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree)

        def _restack(p, c):
            p2 = ST.restack_params(p, plan_p, plan, cfg.n_layers)
            c2 = jax.tree.map(
                lambda a: ST.restack_layers(a, plan_p, plan, cfg.n_layers), c)
            return p2, c2

        restack_x = jax.jit(
            _restack, donate_argnums=(0, 1),
            out_shardings=(shard(dspecs), shard(cspecs)))
        params_shapes, cache_shapes = jax.eval_shape(_restack, params_p,
                                                     cache)
        import warnings
        with warnings.catch_warnings():
            # the chunked->contiguous layout change blocks in-place
            # aliasing for the re-folded leaves; those are instead freed
            # by the `del params_p` right after the handoff call
            # (tests/test_serve_sched.py pins both halves)
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            restack_x = restack_x.lower(params_p, cache).compile()
        decode_x = decode.lower(
            jax.tree.map(lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
                params_shapes, dspecs),
            jax.tree.map(lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
                cache_shapes, cspecs),
            dict(tokens=tok0)).compile()
    else:
        decode_x = decode.lower(params_p, cache, dict(tokens=tok0)).compile()
    t_compile = time.perf_counter() - t0

    # ---- prefill ----------------------------------------------------------
    t0 = time.perf_counter()
    logits, cache = prefill_x(params_p, cache, dict(tokens=prompt))
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    # ---- prefill -> decode handoff (donating restack) ---------------------
    t0 = time.perf_counter()
    if cfg.virtual > 1:
        params, cache = restack_x(params_p, cache)
        # drop the last reference to the prefill-layout copies: the
        # layout-changing leaves cannot be aliased by the donation, so
        # they stay resident until this name dies
        del params_p
        jax.block_until_ready(params)
    else:
        params = params_p
    t_handoff = time.perf_counter() - t0

    prefill_logits = np.asarray(logits[:, 0, :cfg.vocab])
    next_tok = jnp.argmax(logits[:, 0, :cfg.vocab], axis=-1).astype(jnp.int32)
    generated = [np.asarray(next_tok)]

    # ---- steady-state decode (everything below is compiled + fenced) ------
    jax.block_until_ready(next_tok)
    t0 = time.perf_counter()
    for _ in range(args.gen - 1):
        logits, cache = decode_x(params, cache, dict(tokens=next_tok[:, None]))
        next_tok = jnp.argmax(logits[:, 0, :cfg.vocab],
                              axis=-1).astype(jnp.int32)
        generated.append(np.asarray(next_tok))
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0
    toks = np.stack(generated, 1)
    pre_toks = args.batch * args.prompt_len
    dec_toks = (args.gen - 1) * args.batch
    print(f"compile: {t_compile*1e3:.1f}ms (excluded from all phases)")
    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill*1e3:.1f}ms "
          f"({pre_toks / max(t_prefill, 1e-9):.0f} tok/s)")
    if cfg.virtual > 1:
        print(f"handoff: V={cfg.virtual} restack (donated) in "
              f"{t_handoff*1e3:.1f}ms")
    print(f"decode:  {args.gen - 1} steps x batch {args.batch} in "
          f"{t_decode*1e3:.1f}ms "
          f"({dec_toks / max(t_decode, 1e-9):.0f} tok/s steady-state)")
    print("sample generations (first 3 rows):")
    for row in toks[:3]:
        print("  ", row.tolist())
    return Served(np.asarray(prompt), toks, prefill_logits)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
