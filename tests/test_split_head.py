"""The loss head split over the pipeline's stages.

With more than one stage, every stage runs its share of each
micro-batch's vocabulary at the tick the last stage finishes that
micro-batch's forward, and the last stage's seed B reads the head's
cotangent from a stash.  The loss and the gradient of every leaf (the
layers, the tied table or the embedding and the untied head, the final
norm) must equal the single-device reference, for the tick and stream
runtimes, the zero-bubble split backward, interleaved chunks, a
vocabulary the stages do not divide, an untied head and a tensor axis.

The pipeline cases compile in one subprocess on 8 virtual CPU devices
(``tests/harness_pipe.py split_head``; the main pytest process keeps its
single device)."""
import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh

from benchmarks.chip.scopes import scope_of
from repro.configs import get_config
from repro.core import trace as TR
from repro.pipeline import runtime as RT
from repro.pipeline import stage as ST

HARNESS = os.path.join(os.path.dirname(__file__), "harness_pipe.py")
TOL = 1e-4
CASES = {
    # name: (stages, tensor, virtual, schedule, runtime, tied)
    "s2-1f1b": (2, 1, 1, "1f1b", "ticks", True),
    "s4-1f1b": (4, 1, 1, "1f1b", "ticks", True),
    "s2-stream": (2, 1, 1, "1f1b", "stream", True),
    "s4-stream": (4, 1, 1, "1f1b", "stream", True),
    "s2-zb-h1": (2, 1, 1, "zb-h1", "ticks", True),
    "s4-zb-h1": (4, 1, 1, "zb-h1", "ticks", True),
    "s2-interleaved-v2": (2, 1, 2, "1f1b-interleaved", "ticks", True),
    "s4-interleaved-v2": (4, 1, 2, "1f1b-interleaved", "ticks", True),
    # 1024 vocabulary rows over 3 stages: the last share overlaps its
    # neighbour's by 2 rows
    "s3-vocab-not-divisible": (3, 1, 1, "1f1b", "ticks", True),
    "s4-untied": (4, 1, 1, "1f1b", "ticks", False),
    # split over the stage and the tensor axes together
    "s2-tensor2": (2, 2, 1, "1f1b", "ticks", True),
}


@pytest.fixture(scope="module")
def split_runs():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, HARNESS, "split_head",
                        json.dumps(CASES)], env=env, capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.split("OK ", 1)[1])


@pytest.mark.parametrize("case", sorted(CASES))
def test_split_head_matches_the_single_device_reference(split_runs, case):
    got = split_runs[case]
    assert "error" not in got, got.get("error")
    tied = CASES[case][-1]
    assert got["leaves"] == (["embed", "final_norm", "layers"] if tied else
                             ["embed", "final_norm", "head", "layers"])
    assert got["loss"] < TOL, got
    for leaf in got["leaves"]:
        assert got[leaf] < TOL, (leaf, got)


def _counters(stages, tensor, runtime="ticks", microbatches=8, batch=8,
              seq=32):
    cfg = get_config("qwen3-1.7b").reduced(n_layers=4, d_model=64)
    cfg = dataclasses.replace(cfg, stages=stages, tensor=tensor)
    mesh = AbstractMesh((1, stages, tensor), ("data", "stage", "tensor"))
    pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule="1f1b",
                             runtime=runtime)
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    c = RT.train_step_counters(cfg, mesh, ST.plan_stages(cfg), pcfg,
                               dict(tokens=rows, labels=rows))
    mb = batch // microbatches
    # per micro-batch: the last stage's output and its cotangent, f32
    # [mb, seq, d]; the softmax's max, two sums and their two cotangents,
    # f32 [mb, seq]
    hidden, token = mb * seq * cfg.d_model * 4, mb * seq * 4
    return c, microbatches, hidden, token


@pytest.mark.parametrize("stages,tensor,runtime,split,per_head", [
    (4, 1, "ticks", 4, lambda h, t: 2 * h + 5 * t),
    (4, 1, "stream", 4, lambda h, t: 2 * h + 5 * t),
    (2, 2, "ticks", 4, lambda h, t: 2 * h + 5 * t),
    (1, 2, "ticks", 2, lambda h, t: 5 * t),
    (1, 1, "ticks", 1, lambda h, t: 0),
])
def test_counters_give_the_head_split_and_its_bytes(stages, tensor, runtime,
                                                    split, per_head):
    c, M, hidden, token = _counters(stages, tensor, runtime)
    assert c["head_split"] == split
    assert c["head_bytes_per_step"] == M * per_head(hidden, token)


OP = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\(.*?op_name=\"([^\"]*)\"",
                re.M)


def test_one_stage_keeps_the_head_in_the_seed_tick():
    """With one stage nothing is split: every op of the head, its
    reductions over the (one-wide) tensor axis among them, runs inside
    the seed B tick, and the head has no conditional of its own."""
    from repro.launch.mesh import make_mesh
    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64)
    cfg = dataclasses.replace(cfg, stages=1, tensor=1)
    plan = ST.plan_stages(cfg)
    mesh = make_mesh((1, 1, 1), ("data", "stage", "tensor"))
    step, _ = RT.make_train_step(cfg, mesh, plan,
                                 RT.PipelineConfig(n_microbatches=2))
    params = jax.eval_shape(
        lambda k: ST.init_stacked_params(cfg, k, plan),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    rows = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = step.lower(params, dict(tokens=rows, labels=rows)) \
        .compile().as_text()
    head = [(op, name) for op, name in OP.findall(text)
            if scope_of(name) == TR.PIPE_HEAD]
    assert any(op in ("dot", "convolution") for op, _ in head)
    assert all("/pipe.B/" in name for _, name in head), head
    assert "conditional" not in {op for op, _ in head}
