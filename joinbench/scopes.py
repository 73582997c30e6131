"""The program's own names in a profiler trace: device self time per
layer scope, and host time per entry span.

    python3 -m joinbench.scopes <file.xplane.pb> [--chips <n>]
    python3 -m joinbench.scopes --workload <cell> --seed <n> --seconds <s> --keep <dir>

The join step names its stages with ``jax.named_scope``: ``partition``,
``shuffle`` and ``join``, and under ``join`` the local join's
``prepare``, ``sort``, ``scan``, ``compact`` and ``expand``. Each
instruction carries the path in its ``op_name`` metadata. A TPU trace
holds each program's optimised HLO (an ``HloProto`` in its metadata
plane), which ``jax.profiler.ProfileData`` does not expose, so
``hlo_graph`` reads it from the file's protobuf wire format; a device
operation's event is named after its instruction. Instructions the
compiler makes itself (X64 splits and combines, relayout loops, pads to
a kernel's tiling) carry no op_name: ``attribute`` gives such an
instruction the layer of the nearest named instruction it feeds, else
of the nearest that feeds it, else its caller's. The entry,
``distributed_inner_join``, wraps each host step in a
``TraceAnnotation``: ``entry.prepare``, ``entry.resolve`` (holding
``entry.compile`` on a program-cache miss), ``entry.dispatch``,
``entry.fetch_overflow`` (arguments ``out_rows``, the result's static
capacity, and ``rung``) and ``entry.finish``.

``reduce`` sums, over the harness's window: each device's self time by
layer (the innermost of ``partition``, ``shuffle`` and
``join/<stage>``; ``join`` for join work outside a stage; ``(none)``
for work under no layer scope), from the instructions' own op_names and
with the attribution; host self time per entry span; each device's idle
time inside entry spans and outside them; and the mean ``out_rows``.
The ``--workload`` form makes one traced run of the cell through
``run.run``, keeps its trace in ``--keep`` and prints the run's result
line, then the reduction; its call stack differs from
``joinbench.run``'s, which the Pallas kernels' compile-cache key sees,
so it compiles the program once more.

The harness's per-layer readers cannot read these yet: ``trace.load``
keeps neither the HLO nor the host spans once the window is reduced
(PERF.md, Open questions).
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import shutil
import sys

from joinbench import trace

LAYERS = ("partition", "shuffle", "join")
STAGES = ("prepare", "sort", "scan", "compact", "expand")
NO_LAYER = "(none)"
HLO_PROTO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
ENTRY = "entry."
FETCH_SPAN = "entry.fetch_overflow"


def layer(op_name: str) -> str:
    """The innermost layer scope of an instruction's ``op_name``; its
    last component is the primitive, not a scope."""
    parts = op_name.split("/")[:-1]
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in STAGES and i > 0 and parts[i - 1] == "join":
            return f"join/{parts[i]}"
        if parts[i] in LAYERS:
            return parts[i]
    return NO_LAYER


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, bytes for anything length-delimited."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            step = 8 if kind == 1 else 4
            value, i = buf[i:i + step], i + step
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace")
        yield key >> 3, value


def _group(message: bytes) -> dict:
    """``{field number: [values]}`` of one protobuf message."""
    fields = collections.defaultdict(list)
    for f, v in _fields(message):
        fields[f].append(v)
    return fields


def _packed(values) -> list:
    """The integers of a repeated varint field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def _hlo_modules(space: bytes):
    """The ``HloModuleProto`` of each program whose optimised HLO the
    trace's metadata plane holds (as the ``Hlo Proto`` stat, an
    ``HloProto`` whose field 1 is the module).

    The wire format, of ``tsl/profiler/protobuf/xplane.proto``: an
    XSpace holds planes (1); a plane its name (2) and event metadata
    (4) and stat metadata (5), both maps keyed by id (value: field 2);
    an event metadata its stats (5); a stat metadata its name (2); a
    stat its metadata id (1) and, here, bytes (6)."""
    for plane in _group(space)[1]:
        fields = _group(plane)
        if not fields[2] or fields[2][0].decode() != METADATA_PLANE:
            continue
        names = {}
        for entry in fields[5]:
            meta = _group(_group(entry)[2][0])
            names[meta[1][0] if meta[1] else 0] = meta[2][0].decode()
        for entry in fields[4]:
            for stat in _group(_group(entry)[2][0])[5]:
                stat = _group(stat)
                if stat[6] and names.get(stat[1][0]) == HLO_PROTO_STAT:
                    yield _group(stat[6][0])[1][0]


def hlo_graph(module: bytes) -> dict:
    """``{instruction: (computation id, operands, called computation
    ids, op_name)}`` of one ``HloModuleProto``: computations (3), each
    with instructions (2) and an id (5); an instruction's name (1),
    metadata (7, whose op_name is 2), id (35), operand ids (36) and
    called computation ids (38)."""
    rows = []
    for comp in _group(module)[3]:
        comp = _group(comp)
        comp_id = comp[5][0] if comp[5] else None
        for ins in comp[2]:
            ins = _group(ins)
            meta = _group(ins[7][0]) if ins[7] else {}
            rows.append((ins[35][0], ins[1][0].decode(), comp_id,
                         _packed(ins[36]), _packed(ins[38]),
                         meta[2][0].decode() if meta.get(2) else ""))
    names = {r[0]: r[1] for r in rows}
    return {name: (comp_id, [names[o] for o in operands if o in names],
                   called, op_name)
            for _, name, comp_id, operands, called, op_name in rows}


def attribute(graph: dict) -> dict:
    """``{instruction: layer}`` for every instruction of the graph. An
    instruction whose own op_name names a layer has that layer. One the
    compiler made without one (an X64 split or combine, a relayout
    loop, a pad for a kernel's tiling) takes the layer of the nearest
    named instruction it feeds, else of the nearest that feeds it, in
    its own computation; else its caller's (a while, conditional or
    fusion); else none."""
    users = collections.defaultdict(list)
    caller = {}
    for name, (_, operands, called, _) in graph.items():
        for o in operands:
            users[o].append(name)
        for c in called:
            caller[c] = name
    own = {n: layer(g[3]) for n, g in graph.items()}
    memo = {}

    def nearest(name, neighbours):
        seen, queue = {name}, collections.deque(neighbours(name))
        while queue:
            n = queue.popleft()
            if n in seen:
                continue
            seen.add(n)
            if own[n] != NO_LAYER:
                return own[n]
            queue.extend(neighbours(n))
        return None

    def of(name, depth=0):
        if name not in memo:
            found = own[name] if own[name] != NO_LAYER else (
                nearest(name, lambda n: users[n])
                or nearest(name, lambda n: graph[n][1]))
            host = caller.get(graph[name][0])
            if found is None and host is not None and depth < 64:
                found = of(host, depth + 1)
            memo[name] = found or NO_LAYER
        return memo[name]

    return {name: of(name) for name in graph}


@dataclasses.dataclass
class Scoped:
    trace: trace.Trace         # the harness's own reading of the file
    op_names: dict             # instruction -> its own op_name
    layers: dict               # instruction -> layer, by ``attribute``
    entry: list                # [(Event, stats)] of the entry's spans


def load(path: str) -> Scoped:
    """The trace as ``trace.load`` reads it, plus each instruction's
    op_name and layer from the programs' HLO, and the entry's host
    spans with their arguments, from the thread that ran the window."""
    from jax.profiler import ProfileData

    entry = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for ln in plane.lines:
            events = list(ln.events)
            if not any(e.name == trace.WINDOW_SPAN for e in events):
                continue
            for e in events:
                if e.name.startswith(ENTRY):
                    start = float(e.start_ns)
                    entry.append((trace.Event(
                        e.name, "", start, start + float(e.duration_ns)),
                        dict(e.stats)))
    with open(path, "rb") as f:
        space = f.read()
    op_names, layers = {}, {}
    for module in _hlo_modules(space):
        graph = hlo_graph(module)
        op_names.update((n, g[3]) for n, g in graph.items())
        layers.update(attribute(graph))
    return Scoped(trace.load(path), op_names, layers, entry)


def overlap_ns(intervals, spans) -> float:
    """Length of the part of ``intervals`` that ``spans`` cover."""
    cover = trace.clip(spans, float("-inf"), float("inf"))
    return sum(max(0.0, min(e, ce) - max(s, cs))
               for s, e in intervals for cs, ce in cover)


def reduce(scoped: Scoped, lo: float, hi: float) -> dict:
    """Per-call numbers of the window ``[lo, hi]``, per device: self
    time by the layer scope of each operation's own op_name
    (``by_scope``) and with the operations that have none attributed
    through the HLO (``by_layer``), busy time, and idle time inside
    entry spans and outside them; host self time per entry span; and
    the mean ``out_rows``."""
    tr = scoped.trace
    calls = sum(1 for h in tr.host
                if h.name == trace.CALL_SPAN and lo <= h.start_ns < hi)
    per = max(calls, 1)
    entry = [(e, st) for e, st in scoped.entry if lo <= e.start_ns < hi]
    spans = [(e.start_ns, e.end_ns) for e, _ in entry]
    by_scope, by_layer, idle_in, idle_out, busy = {}, {}, {}, {}, {}
    for dev, ops in tr.devices.items():
        scoped_t, layer_t = collections.Counter(), collections.Counter()
        for o, d in trace.self_times(ops, lo, hi):
            if d <= 0:
                continue
            name = o.name.partition(" ")[0]
            scoped_t[layer(scoped.op_names.get(name, ""))] += d
            layer_t[scoped.layers.get(name, NO_LAYER)] += d
        by_scope[dev] = {k: v / per / 1e6
                         for k, v in sorted(scoped_t.items())}
        by_layer[dev] = {k: v / per / 1e6
                         for k, v in sorted(layer_t.items())}
        intervals = [(o.start_ns, o.end_ns) for o in ops]
        busy[dev] = trace.union_ns(intervals, lo, hi) / per / 1e6
        idle = trace.gaps(intervals, lo, hi)
        inside = overlap_ns(idle, spans)
        idle_in[dev] = inside / per / 1e6
        idle_out[dev] = (sum(e - s for s, e in idle) - inside) / per / 1e6
    host = collections.Counter()
    for e, d in trace.self_times([e for e, _ in entry], lo, hi):
        host[e.name] += d
    out_rows = [st["out_rows"] for e, st in entry
                if e.name == FETCH_SPAN and "out_rows" in st]
    return {
        "calls": calls,
        "device_ms_per_call_by_scope": by_scope,
        "device_ms_per_call_by_layer": by_layer,
        "device_busy_ms_per_call": busy,
        "device_idle_ms_per_call_in_entry": idle_in,
        "device_idle_ms_per_call_elsewhere": idle_out,
        "host_ms_per_call_by_entry_span": {
            k: v / per / 1e6 for k, v in sorted(host.items())},
        "out_rows": (sum(out_rows) / len(out_rows)) if out_rows else None,
    }


def reduce_file(path: str, chips: int | None = None) -> dict | None:
    """``reduce`` over the harness's window span of a kept trace, on
    devices ``0 .. chips-1`` (every device where ``chips`` is None)."""
    scoped = load(path)
    if chips is not None:
        scoped.trace.devices = {k: v for k, v in
                                scoped.trace.devices.items() if k < chips}
    span = trace.window(scoped.trace)
    return None if span is None else reduce(scoped, *span)


@contextlib.contextmanager
def keeping_trace(keep_dir: str):
    """While entered, every traced window of ``run.run`` leaves a copy
    of its trace in ``keep_dir`` (``window.<n>.xplane.pb``)."""
    from joinbench import run

    real = run.trace_window
    kept = []

    def trace_window(run_window):
        out, tmp, path = real(run_window)
        if path is not None:
            os.makedirs(keep_dir, exist_ok=True)
            kept.append(os.path.join(keep_dir,
                                     f"window.{len(kept)}.xplane.pb"))
            shutil.copy(path, kept[-1])
        return out, tmp, path

    run.trace_window = trace_window
    try:
        yield kept
    finally:
        run.trace_window = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path", nargs="?")
    ap.add_argument("--chips", type=int)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    if args.workload:
        from joinbench import run

        cell = run.load_cell(args.workload)
        with keeping_trace(args.keep) as kept:
            result = run.run(cell, args.seed, args.seconds, trace=True)
        print(json.dumps({"workload": cell.name, "seed": args.seed,
                          **result}), flush=True)
        paths, chips = kept, cell.chips
    else:
        paths, chips = [args.path], args.chips
    for path in paths:
        print(json.dumps({"trace": path,
                          **(reduce_file(path, chips) or {})}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
