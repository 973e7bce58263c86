"""From a profiler trace to device time by the program's own scopes, and
host time by its own spans.

The program names the work of its train step with ``jax.named_scope``s
(``pipe.F``, ``pipe.B``, ``pipe.W``, ``pipe.head``, ``pipe.send``,
``pipe.ar``, ``pipe.embed``, ``pipe.sync``, ``opt.update``) and its data
layer with host spans (``data.build``, ``data.put``).  A device op's
scope is the innermost component of its HLO ``op_name`` that begins
with ``pipe.`` or ``opt.``, once wrappers such as ``jvp(...)`` and
``transpose(...)`` are taken off; an op under none is ``other``.  An op
XLA made itself (a layout reshape, a convert) has no ``op_name``: it
takes the scope of the nearest op with one along the data flow of its
computation, operands first.

Where the names come from: a TPU op event is named by its HLO text
alone; the trace keeps the op's ``op_name`` as the ``tf_op`` stat of the
event's metadata and each module's HLO as a proto in the
``/host:metadata`` plane, neither of which ``jax.profiler.ProfileData``
shows, so :func:`trace_names` reads them from the ``.xplane.pb`` file.
An XLA:CPU op event carries ``hlo_module`` and ``hlo_op``, mapped
through the compiled module's HLO text (:func:`hlo_scopes`).

Each moment of a chip's busy time inside ``bench.window`` goes to the
innermost op running then, as in ``trace_reduce``, and so to that op's
scope: the scopes and ``other`` add up to the busy time.  Each moment of
a chip's idle time goes to the innermost host span open then (``data.*``
or ``bench.*``), else to ``host.other``.

Nothing of the program is imported: the names are written out here.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import sys
from collections import defaultdict, deque

from benchmarks.chip import trace_reduce as TR

SCOPE_PREFIXES = ("pipe.", "opt.")
PROGRAM_SPAN = "data."
OTHER = "other"
HOST_OTHER = "host.other"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
WRAPPED = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")
MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*? ([\w-]+)\(")
OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
TRACES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".bench_traces")


def scope_of(op_name: str) -> str:
    """``jit(f)/while/body/pipe.B/transpose(jvp(pipe.head))/dot`` ->
    ``pipe.head``; ``other`` where no component is a scope.  Of an op
    XLA merged from several (``a;b``) the first name counts."""
    found = OTHER
    for part in op_name.split(";", 1)[0].split("/"):
        m = WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = WRAPPED.match(part)
        if part.startswith(SCOPE_PREFIXES):
            found = part
    return found


def graph_scopes(instrs: dict) -> dict:
    """``{op: scope}`` of one module from ``{op: (op_name, [operands])}``:
    an op with an ``op_name`` has its scope; one without takes that of
    the nearest op in a scope, found breadth first over operands, then
    users, through ops without an ``op_name``."""
    users = defaultdict(list)
    for op, (_, operands) in instrs.items():
        for o in operands:
            users[o].append(op)
    out = {op: scope_of(name) for op, (name, _) in instrs.items() if name}
    for op, (name, _) in instrs.items():
        if name:
            continue
        seen, todo, found = {op}, deque([op]), OTHER
        while todo:
            cur = todo.popleft()
            if cur != op and out.get(cur, OTHER) != OTHER:
                found = out[cur]
                break
            if cur != op and instrs.get(cur, ("",))[0]:
                continue
            for nxt in instrs.get(cur, ("", []))[1] + users[cur]:
                if nxt not in seen:
                    seen.add(nxt)
                    todo.append(nxt)
        out[op] = found
    return out


def _operands(line: str, start: int) -> list[str]:
    """The ``%names`` inside the parentheses that open at ``start``."""
    depth = 0
    for i in range(start, len(line)):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        if depth == 0:
            return re.findall(r"%([^\s,()]+)", line[start:i])
    return []


def hlo_scopes(hlo_text: str) -> dict:
    """``{(module, op): scope}`` for every instruction of the compiled
    HLO text (``compiled.as_text()``)."""
    m = MODULE.search(hlo_text)
    module = m.group(1) if m else ""
    instrs = {}
    for line in hlo_text.splitlines():
        i = INSTR.match(line)
        if i:
            name = OP_NAME.search(line)
            instrs[i.group(1)] = (name.group(1) if name else "",
                                  _operands(line, i.end() - 1))
    return {(module, op): s for op, s in graph_scopes(instrs).items()}


@functools.cache
def _messages():
    """Message classes for the parts of the profiler's ``XSpace``
    (``tsl/profiler/protobuf/xplane.proto``) and of XLA's ``HloProto``
    (``xla/service/hlo.proto``) read here, with their field numbers; the
    parser skips the rest.  A ``map<int64, M>`` is read as its wire form,
    repeated ``{key = 1, value = 2}`` entries."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_trace_subset.proto", package="bench_trace",
        syntax="proto3")

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, typ in fields:
            rep, typ = typ.startswith("*"), typ.lstrip("*")
            f = m.field.add(name=fname, number=num,
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if typ in ("int64", "uint64", "string", "bytes"):
                f.type = getattr(F, "TYPE_" + typ.upper())
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, ".bench_trace." + typ
    msg("Stat", ("metadata_id", 1, "int64"), ("str_value", 5, "string"),
        ("bytes_value", 6, "bytes"), ("ref_value", 7, "uint64"))
    msg("EventMeta", ("name", 2, "string"), ("stats", 5, "*Stat"))
    msg("StatMeta", ("name", 2, "string"))
    msg("EventMetaEntry", ("key", 1, "int64"), ("value", 2, "EventMeta"))
    msg("StatMetaEntry", ("key", 1, "int64"), ("value", 2, "StatMeta"))
    msg("Plane", ("name", 2, "string"),
        ("event_metadata", 4, "*EventMetaEntry"),
        ("stat_metadata", 5, "*StatMetaEntry"))
    msg("Space", ("planes", 1, "*Plane"))
    msg("OpMetadata", ("op_name", 2, "string"))
    msg("Instr", ("name", 1, "string"), ("metadata", 7, "OpMetadata"),
        ("id", 35, "int64"), ("operand_ids", 36, "*int64"))
    msg("Computation", ("instructions", 2, "*Instr"))
    msg("Module", ("name", 1, "string"), ("computations", 3, "*Computation"))
    msg("Hlo", ("hlo_module", 1, "Module"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    get = lambda n: message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_trace." + n))
    return get("Space"), get("Hlo")


def trace_names(path: str) -> tuple[dict, dict]:
    """From the ``.xplane.pb`` at ``path``: ``{plane: {event name:
    op_name}}``, the ``tf_op`` stat of each device plane's event metadata
    (``<op_name>:<type>``; JAX's ops have no type), and ``{(module, op):
    scope}`` of every module whose HLO proto the trace holds."""
    space_cls, hlo_cls = _messages()
    with open(path, "rb") as f:
        space = space_cls.FromString(f.read())
    names, hlo = {}, {}
    for p in space.planes:
        stat = {e.key: e.value.name for e in p.stat_metadata}
        if TR.DEVICE_PLANE.match(p.name):
            mine = names.setdefault(p.name, {})
            for e in p.event_metadata:
                for st in e.value.stats:
                    if stat.get(st.metadata_id) == "tf_op":
                        v = st.str_value or stat.get(st.ref_value, "")
                        mine[e.value.name] = v.rsplit(":", 1)[0]
        elif p.name == METADATA_PLANE:
            for e in p.event_metadata:
                for st in e.value.stats:
                    if st.bytes_value:
                        hlo.update(_proto_scopes(
                            hlo_cls.FromString(st.bytes_value).hlo_module))
    return names, hlo


def _proto_scopes(module) -> dict:
    by_id, instrs = {}, {}
    for c in module.computations:
        for i in c.instructions:
            by_id[i.id] = i.name
    for c in module.computations:
        for i in c.instructions:
            instrs[i.name] = (i.metadata.op_name,
                              [by_id[o] for o in i.operand_ids if o in by_id])
    return {(module.name, op): s for op, s in graph_scopes(instrs).items()}


def events(pd, names: dict | None = None,
           hlo: dict | None = None) -> tuple[dict, list]:
    """(``{chip: [(scope, start_ns, end_ns)]}``, ``[(span, start_ns,
    end_ns)]``) from a loaded trace: the ops of TPU planes (scoped by
    their ``op_name`` in ``names``, else by ``hlo`` under the module that
    runs them) or XLA:CPU's op events (chip = ``device_ordinal``; scoped
    by ``hlo``); and the host spans of the program (``data.*``) and the
    benchmark (``bench.*``).  ``names`` and ``hlo`` as
    :func:`trace_names` and :func:`hlo_scopes` give them."""
    names, hlo = names or {}, hlo or {}
    ops: dict = defaultdict(list)
    spans = []
    for plane in pd.planes:
        m = TR.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.name.split("(", 1)[0])
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else []))
            starts = [a for a, _ in mods]
            named = {n: scope_of(v)
                     for n, v in names.get(plane.name, {}).items()}

            def scope(e):
                if e.name in named:
                    return named[e.name]
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][1] if i >= 0 else ""
                return hlo.get((mod, TR.short(e.name)), OTHER)
            if TR.OPS_LINE in lines:
                ops[int(m.group(1))].extend(
                    (scope(e), e.start_ns, e.start_ns + e.duration_ns)
                    for e in lines[TR.OPS_LINE].events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((PROGRAM_SPAN, TR.SPAN_PREFIX)):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
                    continue
                st = dict(e.stats)
                if "hlo_op" in st:
                    ops[int(st.get("device_ordinal", 0))].append(
                        (hlo.get((str(st.get("hlo_module")),
                                  str(st["hlo_op"])), OTHER),
                         e.start_ns, e.start_ns + e.duration_ns))
    return dict(ops), spans


def _overlaps(pieces, gap_list) -> dict:
    """Seconds of ``gap_list`` under each named piece; pieces and gaps
    are disjoint and sorted."""
    out: dict = defaultdict(float)
    i = 0
    for ga, gb in gap_list:
        covered = 0.0
        while i < len(pieces) and pieces[i][2] <= ga:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][1] < gb:
            n, a, b = pieces[j]
            ov = min(b, gb) - max(a, ga)
            if ov > 0:
                out[n] += ov * 1e-9
                covered += ov
            j += 1
        if gb - ga - covered > 0:
            out[HOST_OTHER] += (gb - ga - covered) * 1e-9
    return dict(out)


def reduce(ops: dict, spans: list, chips: list[int]) -> dict:
    """Per chip, the busy seconds inside ``bench.window`` by scope
    (``scope_s_per_chip``) and the idle seconds by innermost host span;
    the mean over chips of both (``device_scopes``,
    ``idle_gaps_program``); and the seconds of each program span inside
    the window (``program_spans``)."""
    win = [(a, b) for n, a, b in spans if n == TR.WINDOW]
    if not win:
        raise ValueError(f"trace holds no {TR.WINDOW!r} span")
    lo, hi = win[0]
    host = [s for s in spans if s[0] != TR.WINDOW]
    pieces = [(n, max(a, lo), min(b, hi)) for n, a, b in TR.innermost(host)
              if min(b, hi) > max(a, lo)]
    per_chip, busy_s = [], []
    scopes: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    for c in chips:
        evs = ops.get(c, [])
        mine: dict = defaultdict(float)
        for n, a, b in TR.innermost(evs):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                mine[n] += (b - a) * 1e-9
        per_chip.append(dict(mine))
        busy = TR.merge([(a, b) for _, a, b in evs], lo, hi)
        busy_s.append(sum(b - a for a, b in busy) * 1e-9)
        for n, s in mine.items():
            scopes[n] += s / len(chips)
        for n, s in _overlaps(pieces, TR.gaps(busy, lo, hi)).items():
            gaps[n] += s / len(chips)
    prog: dict = defaultdict(float)
    for n, a, b in spans:
        if n.startswith(PROGRAM_SPAN) and min(b, hi) > max(a, lo):
            prog[n] += (min(b, hi) - max(a, lo)) * 1e-9
    order = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
    return dict(window_s=(hi - lo) * 1e-9, busy_s_per_chip=busy_s,
                scope_s_per_chip=per_chip, device_scopes=order(scopes),
                idle_gaps_program=order(gaps), program_spans=dict(prog))


def newest_trace() -> str | None:
    """The newest ``.xplane.pb`` under ``TRACES``: the trace of the run
    whose readers are reading (``run.py`` traces each cell into
    ``TRACES/<cell>/`` and removes it once the readers are done)."""
    files = glob.glob(os.path.join(TRACES, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=1)
def _analyse(path: str, mtime: float, chips: int) -> dict | None:
    from jax.profiler import ProfileData
    ops, spans = events(ProfileData.from_file(path), *trace_names(path))
    if not ops:
        return None
    r = reduce(ops, spans, sorted(ops)[:chips])
    print(f"scopes: device_scopes {r['device_scopes']}; "
          f"idle_gaps_program {r['idle_gaps_program']}; per chip "
          f"busy_s {r['busy_s_per_chip']} scope_s {r['scope_s_per_chip']}"
          f"; program spans {r['program_spans']}", file=sys.stderr)
    return r


def of_run(run) -> dict | None:
    """The scope reduction of the traced window a metric's reader is
    called for, read once per trace; ``None`` without a trace.
    ``run.py``'s traced branch keeps only ``trace_reduce``'s reduction on
    ``run.trace``, so the readers read the trace file it leaves on disk
    while they run."""
    if run.trace is None:
        return None
    path = newest_trace()
    if path is None:
        return None
    r = _analyse(path, os.path.getmtime(path), run.chips)
    if r is None:
        return None
    return dict(r, cell=os.path.relpath(path, TRACES).split(os.sep)[0])


def scope_ms_max(run, scope: str) -> float | None:
    """The largest per-chip device milliseconds per step under ``scope``;
    ``None`` where no op of the trace carries it."""
    r = of_run(run)
    if r is None or run.steps == 0:
        return None
    s = [c.get(scope) for c in r["scope_s_per_chip"]]
    if all(v is None for v in s):
        return None
    return 1e3 * max(v or 0.0 for v in s) / run.steps
