"""Device time by the program's scopes and idle time by its spans
(``scopes.py``): on synthetic traces, on a real CPU profile of a tiny
scoped train step, and through the five readers that use them."""
from __future__ import annotations

import dataclasses
import glob
import types

import pytest

from benchmarks.chip import cells, scopes
from benchmarks.chip.tests.bench_tiny import HERE

STEP = "jit(train_step)/shard_map/while/body/closed_call/cond/branch_3_fun"


def _pd(planes):
    ev = lambda n, a, d, st=None: types.SimpleNamespace(
        name=n, start_ns=a, duration_ns=d, stats=list((st or {}).items()))
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p, lines=[
            types.SimpleNamespace(name=ln, events=[ev(*e) for e in es])
            for ln, es in lines.items()])
        for p, lines in planes.items()])


def _op(name, a, d, op_name):
    """An op event; its ``op_name`` goes to the plane's metadata."""
    OP_NAMES.setdefault(name, op_name)
    return (name, a, d)


OP_NAMES: dict = {}


@pytest.mark.parametrize("op_name,scope", [
    (f"{STEP}/pipe.B/transpose(jvp(pipe.head))/dot_general", "pipe.head"),
    (f"{STEP}/pipe.B/jvp(pipe.head)/dot_general", "pipe.head"),
    (f"{STEP}/pipe.B/transpose(jvp(closed_call))/mul", "pipe.B"),
    ("jit(train_step)/opt.update/sqrt", "opt.update"),
    ("jit(train_step)/transpose(jvp(pipe.embed))/scatter-add", "pipe.embed"),
    ("jit(f)/pipe.send/ppermute;jit(f)/pipe.F/add", "pipe.send"),
    ("jit(train_step)/shard_map/while", "other"),
    ("", "other")])
def test_scope_is_the_innermost_once_wrappers_are_off(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_hlo_text_maps_module_and_op_to_scope():
    text = (
        "HloModule jit_train_step, is_scheduled=true\n\n"
        "%fused (p: f32[4]) -> f32[4] {\n"
        "  %p = f32[4]{0} parameter(0)\n"
        '  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name='
        '"jit(train_step)/opt.update/mul" stack_frame_id=3}\n}\n\n'
        "ENTRY %main (a: f32[4]) -> f32[4] {\n"
        '  %a = f32[4]{0} parameter(0), metadata={op_name="a"}\n'
        '  %ppermute.16 = f32[1,16,64]{2,1,0} collective-permute(%a), '
        'channel_id=1, source_target_pairs={{0,1}}, metadata={op_name='
        f'"{STEP}/pipe.send/ppermute" stack_frame_id=441}}\n'
        '  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused,'
        ' metadata={op_name="jit(train_step)/pipe.B/jvp(pipe.head)/exp"}\n'
        "  %copy.1 = f32[4]{0} copy(f32[4]{0} %a)\n"
        "  %reshape.7 = f32[2,2]{1,0} reshape(%fusion.3)\n"
        "  %fusion.9 = (f32[4]{0}, f32[2]{0}) fusion(%copy.1), calls=%fused,"
        ' metadata={op_name="jit(train_step)/pipe.F/add"}\n'
        "  ROOT %convert.2 = bf16[4]{0} convert(%a)\n"
        "}\n")
    assert scopes.hlo_scopes(text) == {
        ("jit_train_step", "p"): "opt.update",
        ("jit_train_step", "mul.1"): "opt.update",
        ("jit_train_step", "a"): "other",
        ("jit_train_step", "ppermute.16"): "pipe.send",
        ("jit_train_step", "fusion.3"): "pipe.head",
        # ops XLA made take the scope next to them along the data flow:
        # the operand's, else a user's, past ops outside every scope
        ("jit_train_step", "reshape.7"): "pipe.head",
        ("jit_train_step", "copy.1"): "pipe.F",
        ("jit_train_step", "fusion.9"): "pipe.F",
        ("jit_train_step", "convert.2"): "other"}


def _synthetic():
    """Chip 0 runs a loop whose body computes, sends and runs the head;
    chip 1 computes and sends.  The host builds a batch inside the
    benchmark's input span, then puts it.  Returns the trace and its op
    names."""
    pd = _pd({
        "/device:TPU:0": {"XLA Ops": [
            _op("while.2", 100, 800, "jit(train_step)/shard_map/while"),
            _op("fusion.1", 100, 200, f"{STEP}/pipe.F/dot_general"),
            _op("collective-permute-done", 300, 400,
                f"{STEP}/pipe.send/ppermute"),
            _op("fusion.9", 750, 100,
                f"{STEP}/pipe.B/transpose(jvp(pipe.head))/dot_general"),
            _op("fusion.7", 950, 30, "jit(train_step)/opt.update/sqrt")]},
        "/device:TPU:1": {"XLA Ops": [
            _op("fusion.1", 100, 300, f"{STEP}/pipe.F/dot_general"),
            _op("collective-permute-done", 400, 500,
                f"{STEP}/pipe.send/ppermute")]},
        "/host:CPU": {"python": [("bench.window", 0, 1000),
                                 ("bench.input", 0, 100),
                                 ("data.build", 10, 60),
                                 ("data.put", 70, 20),
                                 ("bench.step", 100, 880),
                                 ("other", 0, 5)]}})
    return pd, {p: dict(OP_NAMES) for p in ("/device:TPU:0",
                                             "/device:TPU:1")}


def test_scopes_and_other_add_up_to_busy_time():
    ops, spans = scopes.events(*_synthetic())
    assert sorted(ops) == [0, 1]
    assert {n for n, _, _ in spans} == {"bench.window", "bench.input",
                                        "data.build", "data.put",
                                        "bench.step"}
    r = scopes.reduce(ops, spans, [0, 1])
    c0, c1 = r["scope_s_per_chip"]
    # chip 0: the loop's own time, [700, 750) and [850, 900), is under no
    # scope; the head's op inside a B tick goes to the head
    assert c0 == pytest.approx({"pipe.F": 200e-9, "pipe.send": 400e-9,
                                "pipe.head": 100e-9, "other": 100e-9,
                                "opt.update": 30e-9})
    assert c1 == pytest.approx({"pipe.F": 300e-9, "pipe.send": 500e-9})
    for per_chip, busy in zip(r["scope_s_per_chip"], r["busy_s_per_chip"]):
        assert sum(per_chip.values()) == pytest.approx(busy, rel=1e-12)
    assert r["busy_s_per_chip"] == pytest.approx([830e-9, 800e-9])
    assert r["device_scopes"]["pipe.send"] == pytest.approx(450e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["program_spans"] == pytest.approx({"data.build": 60e-9,
                                                "data.put": 20e-9})


def test_idle_gaps_go_to_the_innermost_host_span():
    ops, spans = scopes.events(*_synthetic())
    r = scopes.reduce(ops, spans, [0, 1])
    gaps = r["idle_gaps_program"]
    # both chips idle in [0, 100): build 60, put 20, the input span's
    # own 20; chip 0 idle in [900, 950) and [980, 1000) under the step
    # span up to 980 and no span after; chip 1 idle in [900, 1000)
    assert gaps["data.build"] == pytest.approx(60e-9)
    assert gaps["data.put"] == pytest.approx(20e-9)
    assert gaps["bench.input"] == pytest.approx(20e-9)
    assert gaps["bench.step"] == pytest.approx((50e-9 + 80e-9) / 2)
    assert gaps["host.other"] == pytest.approx((20e-9 + 20e-9) / 2)
    idle = [r["window_s"] - b for b in r["busy_s_per_chip"]]
    assert sum(gaps.values()) == pytest.approx(sum(idle) / 2)


def test_cpu_op_events_map_through_the_hlo_text():
    pd = _pd({"/host:CPU": {"tf_XLAPjRtCpuClient/1": [
        ("dot.2", 10, 50, dict(hlo_op="dot.2", hlo_module="jit_train_step",
                               device_ordinal=0)),
        ("fusion", 70, 20, dict(hlo_op="fusion", hlo_module="jit_other",
                                device_ordinal=0))],
        "python": [("bench.window", 0, 100)]}})
    ops, spans = scopes.events(pd, hlo={("jit_train_step", "dot.2"):
                                        "pipe.head"})
    assert ops == {0: [("pipe.head", 10, 60), ("other", 70, 90)]}
    assert spans == [("bench.window", 0, 100)]


def test_trace_names_come_from_metadata_the_trace_file_keeps(tmp_path):
    """The ``tf_op`` stat of each device event's metadata, as a string or
    as a reference to a stat metadata's name, with its ``:type`` taken
    off; and each module's HLO proto in the metadata plane."""
    space_cls, hlo_cls = scopes._messages()
    hlo = hlo_cls()
    mod = hlo.hlo_module
    mod.name = "jit_train_step"
    comp = mod.computations.add()
    for i, (name, op_name, operands) in enumerate([
            ("fusion.4", f"{STEP}/pipe.B/jvp(pipe.head)/dot", []),
            ("reshape.5", "", [0]), ("param.6", "params", [])]):
        ins = comp.instructions.add(name=name, id=i)
        ins.metadata.op_name = op_name
        ins.operand_ids.extend(operands)
    space = space_cls()
    for name in ("/device:TPU:0", "/host:CPU"):
        p = space.planes.add(name=name)
        for k, v in ((1, "hlo_category"), (2, "tf_op"), (3, f"{STEP}/x:")):
            e = p.stat_metadata.add(key=k)
            e.value.name = v
        e = p.event_metadata.add(key=7)
        e.value.name = "%fusion.1 = f32[8] fusion(...)"
        e.value.stats.add(metadata_id=1, str_value="loop fusion")
        e.value.stats.add(metadata_id=2,
                          str_value="jit(s)/pipe.B/jvp(pipe.head)/dot:")
        e = p.event_metadata.add(key=8)
        e.value.name = "%copy.2 = f32[8] copy(...)"
        e.value.stats.add(metadata_id=2, ref_value=3)
        p.event_metadata.add(key=9).value.name = "%copy-start = (...)"
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata.add(key=1).value.name = "Hlo Proto"
    e = meta.event_metadata.add(key=1)
    e.value.name = "jit_train_step(123)"
    e.value.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    names, scoped = scopes.trace_names(str(path))
    assert names == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(...)": "jit(s)/pipe.B/jvp(pipe.head)/dot",
        "%copy.2 = f32[8] copy(...)": f"{STEP}/x"}}
    assert scoped == {("jit_train_step", "fusion.4"): "pipe.head",
                      ("jit_train_step", "reshape.5"): "pipe.head",
                      ("jit_train_step", "param.6"): "other"}


def test_tpu_ops_without_a_name_are_scoped_by_their_module():
    pd = _pd({"/device:TPU:0": {
        "XLA Modules": [("jit_train_step(123)", 0, 100)],
        "XLA Ops": [("%fusion.1 = f32[8] fusion(...)", 0, 10),
                    ("%reshape.5 = f32[8] reshape(...)", 10, 10),
                    ("%copy-start = (...)", 20, 10)]}})
    ops, _ = scopes.events(
        pd, {"/device:TPU:0": {"%fusion.1 = f32[8] fusion(...)":
                               f"{STEP}/pipe.F/dot"}},
        {("jit_train_step", "reshape.5"): "pipe.head"})
    assert ops == {0: [("pipe.F", 0, 10), ("pipe.head", 10, 20),
                       ("other", 20, 30)]}


def test_the_window_span_is_needed():
    with pytest.raises(ValueError):
        scopes.reduce({0: []}, [("data.build", 0, 1)], [0])


def _reduction(send=(0.4, 0.5), head=(0.0, 0.12), opt=(0.02, 0.03)):
    chips = [{"pipe.send": s, "pipe.head": h, "opt.update": o, "other": 0.1}
             for s, h, o in zip(send, head, opt)]
    return dict(cell="qwen3-train-pp4", window_s=1.0,
                scope_s_per_chip=[{k: v for k, v in c.items() if v}
                                  for c in chips],
                program_spans={"data.build": 0.011})


@dataclasses.dataclass
class _Run:
    steps: int = 2
    chips: int = 2
    trace: dict | None = dataclasses.field(default_factory=dict)


def _readers():
    return {n: cells.reader(f"{HERE}/metrics/{n}.py")
            for n in ("head_ms.train", "optimizer_ms.train",
                      "send_share_max", "send_ms_per_gb",
                      "input_build_ms.train")}


def test_readers_read_the_scoped_window(monkeypatch):
    monkeypatch.setattr(scopes, "of_run", lambda run: _reduction())
    rd = {n: m.read(_Run()) for n, m in _readers().items()}
    assert rd["head_ms.train"] == pytest.approx(60.0)
    assert rd["optimizer_ms.train"] == pytest.approx(15.0)
    assert rd["send_share_max"] == pytest.approx(50.0)
    assert rd["input_build_ms.train"] == pytest.approx(5.5)
    # 22 ticks x 2 rings x one 1 x 1024 x 2048 f32 micro-batch a step
    gb = 22 * 2 * 1024 * 2048 * 4 * 2 / 1e9
    assert rd["send_ms_per_gb"] == pytest.approx(1e3 * 0.5 / gb)


def test_readers_of_a_program_without_scopes_read_nothing(monkeypatch):
    plain = dict(_reduction(), scope_s_per_chip=[{"other": 0.9}] * 2,
                 program_spans={})
    monkeypatch.setattr(scopes, "of_run", lambda run: plain)
    assert all(m.read(_Run()) is None for m in _readers().values())
    monkeypatch.setattr(scopes, "of_run", lambda run: None)
    assert all(m.read(_Run(trace=None)) is None
               for m in _readers().values())


def test_untraced_runs_have_no_scopes():
    assert scopes.of_run(_Run(trace=None)) is None


def test_a_real_cpu_profile_maps_ops_to_scopes(tmp_path, monkeypatch):
    """A tiny scoped train step, compiled and profiled on the CPU: its op
    events carry ``hlo_op``/``hlo_module``, mapped through its HLO."""
    import jax
    from jax.profiler import ProfileData
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.data.synthetic import SyntheticLM, shard_batch
    from repro.launch.mesh import make_mesh
    from repro.optim import AdamW
    from repro.pipeline import runtime as RT
    from repro.pipeline import stage as ST
    cfg = dataclasses.replace(
        get_config("qwen3-1.7b").reduced(n_layers=1, d_model=32),
        stages=1, tensor=1)
    plan = ST.plan_stages(cfg)
    mesh = make_mesh((1, 1, 1), ("data", "stage", "tensor"))
    opt = AdamW(lr=1e-3)
    step, _ = RT.make_train_step(
        cfg, mesh, plan, RT.PipelineConfig(n_microbatches=2,
                                           schedule="1f1b"), optimizer=opt)
    params = ST.init_stacked_params(cfg, jax.random.PRNGKey(0), plan)
    state = opt.init(params)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=8, global_batch=2, seed=1)
    sh = NamedSharding(mesh, P(("data",), None))
    bs = dict(tokens=sh, labels=sh)
    b = shard_batch(data.batch(0), bs)
    compiled = step.lower(params, state, b).compile()
    hlo = scopes.hlo_scopes(compiled.as_text())
    params, state, m = compiled(params, state, b)
    float(m["loss"])
    trace_dir = tmp_path / "qwen3-train-1chip"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.input"):
            b = shard_batch(data.batch(1), bs)
        params, state, m = compiled(params, state, b)
        float(m["loss"])
    jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    ops, spans = scopes.events(ProfileData.from_file(path), hlo=hlo)
    r = scopes.reduce(ops, spans, [0])
    got = r["scope_s_per_chip"][0]
    for s in ("pipe.F", "pipe.B", "pipe.head", "pipe.embed", "opt.update"):
        assert got.get(s, 0) > 0, (s, got)
    assert sum(got.values()) == pytest.approx(r["busy_s_per_chip"][0],
                                              rel=1e-9)
    assert set(r["program_spans"]) == {"data.build", "data.put"}
    # the readers find the newest trace and the cell it was taken for
    monkeypatch.setattr(scopes, "TRACES", str(tmp_path))
    assert scopes.newest_trace() == path
    assert scopes.of_run(_Run(chips=1))["cell"] == "qwen3-train-1chip"
