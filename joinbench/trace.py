"""Reduction of a profiler trace (``.xplane.pb``) to per-device numbers.

``load`` reads the trace with ``jax.profiler.ProfileData``: the device
operations of each TPU (the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane) and the spans of the host thread that ran the window, where the
harness's ``TraceAnnotation``s lie; both are on one clock.
``summarize`` reduces them over the traced window: the union of each
device's busy intervals, device self time by operation category and by
operation name, and the idle gaps, each labelled with the innermost
host span that covers it.

``python3 -m joinbench.trace <file.xplane.pb>`` prints the planes, lines
and most frequent events of a trace, to look at one by hand.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "joinbench.window"
CALL_SPAN = "joinbench.call"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    category: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    devices: dict          # device ordinal -> [Event] of its operations
    host: list             # [Event] of the host thread that ran the window


@dataclasses.dataclass
class Summary:
    window_ns: float
    calls: int                 # harness calls that began in the window
    busy_ns: dict              # device -> union of busy intervals
    category_ns: dict          # device -> {category: device self time}
    name_ns: dict              # device -> {operation name: self time}
    gaps: list                 # [(length_ns, host label)], longest first


HLO = re.compile(r"^%?(?P<name>[^\s=]+) = ")


def parse_op(text: str):
    """``(name, category)`` of a device operation. On a TPU the event's
    name is the HLO instruction's text (``%sort.3 = (s32[..], ..)
    sort(...)``): the category is its opcode, a Pallas kernel's is
    ``tpu_custom_call`` (its ``custom_call_target``), and an async
    collective's ``-start``/``-done`` halves count as the collective.
    Elsewhere the name alone (``sort.12``) gives the opcode."""
    m = HLO.match(text)
    if m is None:
        name, opcode = text, re.sub(r"\.\d+$", "", text)
    else:
        name, rest = m.group("name"), text[m.end():]
        if rest.startswith("("):          # a tuple shape
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            rest = rest[i + 1:]
        else:
            rest = rest.partition(" ")[2]
        opcode = rest.strip().partition("(")[0]
    opcode = re.sub(r"-(start|done|update)$", "", opcode)
    target = re.search(r'custom_call_target="([^"]+)"', text)
    if opcode == "custom-call" and target:
        return f"{name} {target.group(1)}", target.group(1)
    kind = re.search(r"kind=(k\w+)", text)
    label = f"{name} {opcode}" + (f" {kind.group(1)}" if kind else "")
    return (label if m else name), opcode


def _events(line, ops: bool):
    out = []
    for e in line.events:
        name, cat = parse_op(e.name) if ops else (e.name, "")
        start = float(e.start_ns)
        out.append(Event(name, cat, start, start + float(e.duration_ns)))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            devices[int(m.group(1))] = (_events(ops[0], True) if ops
                                        else [])
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                if any(e.name == WINDOW_SPAN for e in ln.events):
                    host = _events(ln, False)
    return Trace(devices, host)


def self_times(ops, lo: float, hi: float):
    """``[(event, self time)]`` within ``[lo, hi]``: each operation's
    time less that of the operations nested in it (a ``while`` or
    ``conditional`` lists its body's operations inside its own span)."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.end_ns))
    own = [max(0.0, min(o.end_ns, hi) - max(o.start_ns, lo)) for o in ops]
    stack = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= o.start_ns:
            stack.pop()
        if stack and o.end_ns <= ops[stack[-1]].end_ns:
            parent = stack[-1]
            own[parent] -= max(0.0, min(o.end_ns, hi) - max(o.start_ns, lo))
        stack.append(i)
    return [(o, max(t, 0.0)) for o, t in zip(ops, own)]


def clip(intervals, lo: float, hi: float):
    """Sorted, merged intervals cut to ``[lo, hi]``."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def union_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float):
    """The idle stretches of ``[lo, hi]`` between busy intervals."""
    out, t = [], lo
    for s, e in clip(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def host_label(host, t: float) -> str:
    """The innermost host span that covers time ``t``."""
    covering = [h for h in host if h.start_ns <= t < h.end_ns]
    if not covering:
        return "no host span"
    return min(covering, key=lambda h: h.end_ns - h.start_ns).name


def window(trace: Trace, span: str = WINDOW_SPAN):
    """``(start, end)`` of the harness's window span, or None."""
    for h in trace.host:
        if h.name == span:
            return h.start_ns, h.end_ns
    return None


def summarize(trace: Trace, lo: float, hi: float,
              top_gaps: int = 10) -> Summary:
    busy, cat, names, all_gaps = {}, {}, {}, []
    for dev, ops in trace.devices.items():
        spans = [(o.start_ns, o.end_ns) for o in ops]
        busy[dev] = union_ns(spans, lo, hi)
        by_cat = collections.Counter()
        by_name = collections.Counter()
        for o, d in self_times(ops, lo, hi):
            if d > 0:
                by_cat[o.category] += d
                by_name[o.name] += d
        cat[dev], names[dev] = dict(by_cat), dict(by_name)
        if dev == min(trace.devices):
            longest = sorted(gaps(spans, lo, hi), key=lambda g: g[0] - g[1])
            all_gaps = [(e - s, host_label(trace.host, (s + e) / 2))
                        for s, e in longest[:top_gaps]]
    calls = sum(1 for h in trace.host
                if h.name == CALL_SPAN and lo <= h.start_ns < hi)
    return Summary(hi - lo, calls, busy, cat, names, all_gaps)


def per_device_mean(values: dict) -> float | None:
    return sum(values.values()) / len(values) if values else None


def category_ns(summary: Summary, categories) -> dict:
    """Device time of the given categories, per device."""
    return {dev: sum(t for c, t in cats.items() if c in categories)
            for dev, cats in summary.category_ns.items()}


def top_ops(summary: Summary, n: int = 10):
    """``[[name, seconds]]`` of the operations with most device time,
    averaged over the devices."""
    total = collections.Counter()
    for by_name in summary.name_ns.values():
        total.update(by_name)
    k = max(len(summary.name_ns), 1)
    return [[name, t / k / 1e9] for name, t in total.most_common(n)]


def describe(path: str, out=sys.stdout, per_line: int = 12) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}", file=out)
        for ln in plane.lines:
            evs = list(ln.events)
            print(f"  line {ln.name!r}: {len(evs)} events", file=out)
            seen = collections.Counter(e.name for e in evs)
            for name, n in seen.most_common(per_line):
                e = next(e for e in evs if e.name == name)
                stats = {k: str(v)[:120] for k, v in dict(e.stats).items()}
                print(f"    {n:6d} x {name!r} start {e.start_ns:.0f} "
                      f"dur {e.duration_ns:.0f} stats {stats}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
