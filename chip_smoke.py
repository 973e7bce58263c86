#!/usr/bin/env python3
"""Chip smoke test: BaPipe's trainer and server on a TPU at the published
widths of Qwen3-1.7B, driven through the normal entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-stage pipeline on four chips

One chip:
  * train 4 layers (stages 1, batch 8 x 1024 tokens, 8 micro-batches, AdamW)
    for 6 steps through ``repro.launch.train.main``: every loss finite, the
    last below the first, and the step-0 loss equal to the plain
    ``repro.models.model.loss_fn`` on the same seed's params and batch;
  * serve all 28 layers (batch 8, 1024-token prompts, 16 new tokens)
    through ``repro.launch.serve.main``: the prefill logits at the last
    prompt position equal the plain ``model.forward``'s.

Four chips (``--chips 4``, nothing else runs): one optimizer-free step of
the 4-layer model as 4 stages (one layer per chip) under the ``ticks`` and
the ``stream`` runtime, each compared with the same params and batch as
one stage on one of those chips; then 3 AdamW steps on 4 stages through
``train.main``.

Every phase that is compared runs at ``default_matmul_precision("highest")``,
so the pipeline and its reference agree to float32 rounding: the tolerances
below only absorb summation order (the 4-stage optimizer steps run at the
default precision).  Weights are random, from ``--seed``.
The script runs in one process (a chip belongs to one process) and
exits non-zero, printing no result, when JAX finds no TPU.  Its last
line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-1.7b"
TRAIN = dict(layers=4, batch=8, seq=1024, microbatches=8, steps=6)
SERVE = dict(batch=8, prompt_len=1024, gen=16)
# |pipeline - reference| / |reference| bounds, at "highest" precision
LOSS_RTOL = 1e-4         # a scalar mean over 8192 tokens
LOGITS_RTOL = 1e-3       # max |diff| over max |reference logit|
GRAD_NORM_RTOL = 1e-3    # per-leaf gradient norms


def _devices(count):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r}")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} chips; JAX found {len(devs)}")
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)
    return devs


def _bytes_in_use(d):
    return d.memory_stats()["bytes_in_use"]


def _peak(devs, what):
    stats = [d.memory_stats() or {} for d in devs]
    print(f"{what}: peak_bytes_in_use "
          f"{[s.get('peak_bytes_in_use') for s in stats]}", flush=True)


def _model_args(layers):
    return ["--arch", ARCH, "--layers", str(layers), "--stages", "1",
            "--tensor", "1", "--data", "1"]


def _plain_params(cfg, plan, seed):
    """The entry points' initial params (``init_stacked_params`` at
    ``PRNGKey(seed)``) in the plain model's [L, ...] layout."""
    import jax
    from repro.pipeline import stage as ST

    def build():
        p = ST.init_stacked_params(cfg, jax.random.PRNGKey(seed), plan)
        p["layers"] = jax.tree.map(
            lambda a: ST.unstack_chunks(a, plan)[:cfg.n_layers], p["layers"])
        p["embed"] = p["embed"][:cfg.vocab]
        return p
    return jax.jit(build)()


def _rel(got, want):
    return abs(got - want) / max(abs(want), 1e-30)


def train_phase(devs, seed):
    import jax
    import numpy as np
    from repro.configs import resolve_config
    from repro.data import SyntheticLM
    from repro.launch import train
    from repro.models import model as M
    from repro.pipeline import stage as ST

    t = TRAIN
    losses = train.main(_model_args(t["layers"]) + [
        "--batch", str(t["batch"]), "--seq", str(t["seq"]),
        "--microbatches", str(t["microbatches"]),
        "--steps", str(t["steps"]), "--log-every", "1",
        "--seed", str(seed)])
    _peak(devs, "train")
    assert len(losses) == t["steps"], losses
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    cfg = dataclasses.replace(resolve_config(ARCH, layers=t["layers"]),
                              stages=1, tensor=1)
    params = _plain_params(cfg, ST.plan_stages(cfg), seed)
    batch = SyntheticLM(vocab=cfg.vocab, seq_len=t["seq"],
                        global_batch=t["batch"], seed=seed).batch(0)
    ref = float(jax.jit(lambda p, b: M.loss_fn(cfg, p, b))(params, batch))
    err = _rel(losses[0], ref)
    print(f"train: step-0 loss {losses[0]:.6f} reference {ref:.6f} "
          f"rel err {err:.2e} (bound {LOSS_RTOL:g}); "
          f"losses {[round(x, 4) for x in losses]}", flush=True)
    assert err <= LOSS_RTOL, (losses[0], ref)


def serve_phase(devs, seed):
    import jax
    import numpy as np
    from repro.configs import resolve_config
    from repro.launch import serve
    from repro.models import model as M
    from repro.pipeline import stage as ST

    s = SERVE
    out = serve.main(["--arch", ARCH, "--stages", "1", "--tensor", "1",
                      "--batch", str(s["batch"]),
                      "--prompt-len", str(s["prompt_len"]),
                      "--gen", str(s["gen"]), "--seed", str(seed)])
    _peak(devs, "serve")
    assert out.tokens.shape == (s["batch"], s["gen"]), out.tokens.shape
    assert np.all(np.isfinite(out.prefill_logits))

    cfg = dataclasses.replace(resolve_config(ARCH), stages=1, tensor=1)
    params = _plain_params(cfg, ST.plan_stages(cfg), seed)

    @jax.jit
    def last_logits(p, tokens):
        h, _, _ = M.forward(cfg, p, dict(tokens=tokens))
        return h[:, -1] @ p["embed"].T

    ref = np.asarray(last_logits(params, out.prompt))
    err = float(np.max(np.abs(out.prefill_logits - ref))
                / np.max(np.abs(ref)))
    print(f"serve: prefill logits {out.prefill_logits.shape} rel err "
          f"{err:.2e} (bound {LOGITS_RTOL:g}); max |ref| "
          f"{np.max(np.abs(ref)):.4f}", flush=True)
    assert err <= LOGITS_RTOL, err


def pipeline4_phase(devs, seed):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import resolve_config
    from repro.data import SyntheticLM
    from repro.launch import train
    from repro.launch.mesh import make_mesh
    from repro.pipeline import runtime as RT
    from repro.pipeline import stage as ST

    t = TRAIN
    S = len(devs)
    cfg = dataclasses.replace(resolve_config(ARCH, layers=t["layers"]),
                              stages=S, tensor=1)
    host_batch = SyntheticLM(vocab=cfg.vocab, seq_len=t["seq"],
                             global_batch=t["batch"], seed=seed).batch(0)

    def leaf_norms(grads):
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        return {jax.tree_util.keystr(k):
                float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
                for k, v in flat}

    def run(cfg, devices, runtime):
        mesh = make_mesh((1, cfg.stages, 1), ("data", "stage", "tensor"),
                         devices=devices)
        plan = ST.plan_stages(cfg)
        pcfg = RT.PipelineConfig(n_microbatches=t["microbatches"],
                                 runtime=runtime)
        step, specs = RT.make_train_step(cfg, mesh, plan, pcfg)
        params = jax.jit(
            lambda: ST.init_stacked_params(cfg, jax.random.PRNGKey(seed),
                                           plan),
            out_shardings=RT.state_shardings(mesh, specs))()
        bsh = NamedSharding(mesh, P(("data",), None))
        batch = {k: jax.device_put(v, bsh) for k, v in host_batch.items()}
        loss, grads = step(params, batch)
        jax.block_until_ready(grads)
        in_use = [_bytes_in_use(d) for d in devices]
        # the layers' stage axis: layer s lives on chip s only
        wq = params["layers"]["attn"]["wq"]
        owners = sorted((sh.index[0].start or 0, sh.device.id)
                        for sh in wq.addressable_shards)
        # grads come back in the params' stage layout [S, Lps, ...]
        grads["layers"] = jax.tree.map(
            lambda a: np.asarray(a).reshape((-1,) + a.shape[2:]),
            grads["layers"])
        return float(loss), leaf_norms(grads), in_use, owners

    results = {}
    with jax.default_matmul_precision("highest"):
        for runtime in ("ticks", "stream"):
            loss, norms, in_use, owners = run(cfg, devs[:S], runtime)
            print(f"pipeline {S} stages ({runtime}): loss {loss:.6f}; "
                  f"bytes_in_use per chip {in_use}; wq stage slice -> "
                  f"chip {owners}", flush=True)
            assert len({d for _, d in owners}) == S, owners
            assert max(in_use) <= 1.5 * (sum(in_use) / S), in_use
            results[runtime] = (loss, norms)
        ref_loss, ref_norms = run(dataclasses.replace(cfg, stages=1),
                                  devs[:1], "ticks")[:2]
    for runtime, (loss, norms) in results.items():
        lerr = _rel(loss, ref_loss)
        gerr = max(_rel(norms[k], ref_norms[k]) for k in ref_norms)
        print(f"pipeline {runtime} vs one chip: loss {loss:.6f} vs "
              f"{ref_loss:.6f} rel err {lerr:.2e} (bound {LOSS_RTOL:g}); "
              f"worst grad-norm rel err {gerr:.2e} "
              f"(bound {GRAD_NORM_RTOL:g})", flush=True)
        assert lerr <= LOSS_RTOL, (runtime, loss, ref_loss)
        assert gerr <= GRAD_NORM_RTOL, (runtime, gerr)

    # the optimizer steps run at the default precision, as users train
    losses = train.main(["--arch", ARCH, "--layers", str(t["layers"]),
                         "--stages", str(S), "--tensor", "1", "--data", "1",
                         "--batch", str(t["batch"]), "--seq", str(t["seq"]),
                         "--microbatches", str(t["microbatches"]),
                         "--steps", "3", "--log-every", "1",
                         "--seed", str(seed)])
    _peak(devs, "pipeline train")
    assert np.all(np.isfinite(losses)), losses


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = _devices(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache() or os.environ["JAX_COMPILATION_CACHE_DIR"]
    print(f"compile cache: {cache}", flush=True)
    if args.chips == 4:
        pipeline4_phase(devs[:4], args.seed)
    else:
        with jax.default_matmul_precision("highest"):
            train_phase(devs[:1], args.seed)
            serve_phase(devs[:1], args.seed)
    print(json.dumps(dict(ok=True, device=dict(
        platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs)))))


if __name__ == "__main__":
    main()
