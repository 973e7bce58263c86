"""The program's names in a profiler trace: the device scopes on the
compiled train step's ops, the ring-byte counter against the compiled
collectives, the data layer's spans, and ``train.py --trace-dir``.

The four-stage step compiles in a subprocess on 8 virtual CPU devices
(the main pytest process keeps its single device)."""
import dataclasses
import glob
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from benchmarks.chip.scopes import scope_of
from repro.configs import get_config
from repro.core import schedplan as SP
from repro.core import trace as TR
from repro.data.synthetic import SyntheticLM, shard_batch
from repro.pipeline import runtime as RT
from repro.pipeline import stage as ST

HARNESS = os.path.join(os.path.dirname(__file__), "harness_pipe.py")
# result shape, opcode and op_name of one HLO instruction
INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\("
                   r".*?op_name=\"([^\"]*)\"", re.M)
TRIP = re.compile(r"known_trip_count\":\{\"n\":\"(\d+)\"")
# an all-reduce, its result one array or a tuple of them, and its op_name
ALL_REDUCE = re.compile(r"^\s*(?:ROOT )?%\S+ = (\([^)]*\)|\w+\[[\d,]*\]\S*) "
                        r"all-reduce\(.*?op_name=\"([^\"]*)\"", re.M)
ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
ITEMSIZE = dict(f32=4, bf16=2, s32=4, f16=2, u32=4)


@pytest.fixture(scope="module")
def four_stage_hlo(tmp_path_factory):
    """(HLO text, counters) of the compiled 4-stage 1F1B ticks step with
    AdamW."""
    path = tmp_path_factory.mktemp("hlo") / "step.hlo"
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, HARNESS, "scoped_hlo", str(path)],
                       env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-4000:]
    return path.read_text(), json.loads(r.stdout.split("OK ", 1)[1])


@pytest.fixture(scope="module")
def four_stage(four_stage_hlo):
    """(instructions, trip counts, counters) of that step."""
    text, counters = four_stage_hlo
    instrs = [(dt, tuple(int(d) for d in dims.split(",") if d), op, name)
              for dt, dims, op, name in INSTR.findall(text)]
    return instrs, [int(n) for n in TRIP.findall(text)], counters


@pytest.mark.parametrize("scope", [TR.PIPE_F, TR.PIPE_B, TR.PIPE_HEAD,
                                   TR.PIPE_SEND, TR.PIPE_EMBED,
                                   TR.PIPE_SYNC, TR.OPT_UPDATE])
def test_compiled_step_carries_each_scope(four_stage, scope):
    instrs, _, _ = four_stage
    assert any(scope_of(name) == scope for *_, name in instrs)


def test_every_ring_send_is_under_pipe_send(four_stage):
    instrs, _, _ = four_stage
    sends = [name for _, _, op, name in instrs
             if op.startswith("collective-permute")]
    assert len(sends) >= 2
    assert {scope_of(n) for n in sends} == {TR.PIPE_SEND}


def test_the_heads_logits_matmuls_are_under_pipe_head(four_stage):
    """The tied head's logits matmul forward ([mb*T, rows]) and its
    weight gradient ([rows, d]), each stage's share of the vocabulary's
    rows in the split head."""
    instrs, _, counters = four_stage
    rows = -(-counters["vocab"] // counters["head_split"])
    dots = [(shape, name) for _, shape, op, name in instrs
            if op in ("dot", "convolution") and rows in shape]
    assert len(dots) >= 2
    assert {scope_of(n) for _, n in dots} == {TR.PIPE_HEAD}
    assert any("transpose(jvp(pipe.head))" in n for _, n in dots)
    assert any("transpose" not in n for _, n in dots)


def test_adamw_ops_are_under_opt_update(four_stage):
    """AdamW's square root of the second moment is the only ``sqrt`` in
    the step (the norms take ``rsqrt``): each is under ``opt.update``."""
    instrs, _, _ = four_stage
    roots = [name for *_, name in instrs if name.endswith("/sqrt")]
    assert roots
    assert {scope_of(n) for n in roots} == {TR.OPT_UPDATE}


def test_send_bytes_equal_the_compiled_permutes_times_the_trip_count(
        four_stage):
    instrs, trips, counters = four_stage
    per_tick = sum(ITEMSIZE[dt] * int(np.prod(shape))
                   for dt, shape, op, _ in instrs
                   if op.startswith("collective-permute"))
    assert trips == [counters["ticks"]]
    assert counters["send_bytes_per_step"] == per_tick * counters["ticks"]


def test_head_bytes_equal_the_compiled_head_reductions_per_micro_batch(
        four_stage_hlo):
    """The all-reduces under ``pipe.head`` run once per micro-batch (the
    head's cond), and move the counted bytes."""
    text, counters = four_stage_hlo
    arrays = [ARRAY.findall(result) for result, name
              in ALL_REDUCE.findall(text) if scope_of(name) == TR.PIPE_HEAD]
    per_head = sum(ITEMSIZE[dt] * math.prod(int(d) for d in dims.split(",")
                                            if d)
                   for reduce in arrays for dt, dims in reduce)
    assert per_head > 0
    assert counters["head_bytes_per_step"] == (counters["microbatches"]
                                               * per_head)


def _counters(runtime, stages=4, microbatches=8, batch=8, seq=32):
    cfg = get_config("qwen3-1.7b").reduced(n_layers=4, d_model=64)
    cfg = dataclasses.replace(cfg, stages=stages, tensor=1)
    mesh = AbstractMesh((1, stages, 1), ("data", "stage", "tensor"))
    pcfg = RT.PipelineConfig(n_microbatches=microbatches, schedule="1f1b",
                             runtime=runtime)
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    c = RT.train_step_counters(cfg, mesh, ST.plan_stages(cfg), pcfg,
                               dict(tokens=rows, labels=rows))
    payload = batch // microbatches * seq * cfg.d_model * 4
    return c, payload, SP.build_schedule("1f1b", microbatches, stages, 1)


def test_stream_counts_only_the_scheduled_sends():
    ticks, payload, plan_ir = _counters("ticks")
    stream, _, _ = _counters("stream")
    instr = SP.lower_to_instructions(plan_ir)
    assert ticks["ticks"] == instr.ticks.n_ticks
    assert ticks["send_bytes_per_step"] == 2 * instr.ticks.n_ticks * payload
    assert stream["send_bytes_per_step"] == payload * (
        sum(instr.fsend) + sum(instr.bsend))
    assert 0 < stream["send_bytes_per_step"] < ticks["send_bytes_per_step"]


def test_one_stage_puts_nothing_on_a_ring():
    c, _, _ = _counters("ticks", stages=1, microbatches=2)
    assert c["ticks"] > 0 and c["send_bytes_per_step"] == 0


def _unspanned_batch(d: SyntheticLM, step: int) -> dict:
    """``SyntheticLM.batch`` as it was before its spans."""
    rng = np.random.default_rng((d.seed, step))
    B, T = d.global_batch, d.seq_len + 1
    toks = np.empty((B, T), np.int64)
    toks[:, 0] = rng.integers(0, d.vocab, size=B)
    branch = rng.integers(0, d.branch, size=(B, T))
    noise_mask = rng.random((B, T)) < d.noise
    noise_tok = rng.integers(0, d.vocab, size=(B, T))
    for t in range(1, T):
        nxt = d.table[toks[:, t - 1], branch[:, t]]
        toks[:, t] = np.where(noise_mask[:, t], noise_tok[:, t], nxt)
    return dict(tokens=toks[:, :-1].astype(np.int32),
                labels=toks[:, 1:].astype(np.int32))


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_data_spans_leave_batches_as_they_were(seed):
    from jax.sharding import NamedSharding, PartitionSpec as P
    d = SyntheticLM(vocab=300, seq_len=40, global_batch=3, seed=seed)
    mesh = jax.make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    for step in (0, 1, 5):
        want = _unspanned_batch(d, step)
        got = shard_batch(d.batch(step), dict(tokens=sh, labels=sh))
        for k in ("tokens", "labels"):
            assert got[k].dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_train_trace_dir_writes_a_trace_of_the_steady_steps(tmp_path,
                                                            capsys):
    from repro.launch import train
    losses = train.main(["--arch", "qwen3-1.7b", "--reduced", "--layers",
                         "2", "--d-model", "64", "--stages", "1",
                         "--tensor", "1", "--microbatches", "1",
                         "--steps", "3", "--batch", "2", "--seq", "16",
                         "--log-every", "1", "--trace-dir", str(tmp_path)])
    assert len(losses) == 3
    assert "trace of steps 1 to 2 written" in capsys.readouterr().out
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
