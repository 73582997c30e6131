"""Telemetry — one observability layer for the whole join pipeline.

The reference paper's analysis lives and dies on per-stage accounting
(partition vs. all-to-all vs. local join, wire bytes vs. the ICI
roofline — SURVEY.md §5 "Tracing", docs/ROOFLINE.md). Before this
subsystem the repo's instrumentation was fragmented: ``out_of_core.py``
kept a hand-rolled phase dict, ``utils/benchmarking.py`` its own timer,
each driver assembled its own JSON, and the failure-semantics layer's
``RetryReport``/``JoinManifest``/``BootstrapError`` were three more
disjoint sinks. Everything now flows through ONE process-global
session with three parts (docs/OBSERVABILITY.md is the contract):

- :mod:`.spans` — hierarchical host-side span timer with honest sync
  semantics (fetch ONE scalar at span close, per ``benchmarking.py``'s
  protocol; never bare ``block_until_ready``), emitting both a sink
  event and a ``jax.profiler.TraceAnnotation`` so host spans line up
  with the device planes of an XLA profile. Stages inside the compiled
  step are ``jax.named_scope``s, not spans: always on, metadata only;
- :mod:`.metrics` — device-side counters that travel as an auxiliary
  ``Metrics`` pytree OUTPUT of the compiled SPMD join step (no host
  callbacks inside jit), cross-rank aggregated with one
  ``Communicator.all_gather`` of the summary vector at step end;
- :mod:`.export` — the :class:`~.export.TelemetrySink`: JSONL event
  log + Chrome-trace (Perfetto-loadable) file per rank, rank-0 merged
  summary.

On top of the recording layer sit the READERS — :mod:`.analyze`
(cross-rank diagnosis: skew Gini, stragglers, overflow headroom, wire
efficiency, retry cost, with knob recommendations; every driver's
``--diagnose``) and :mod:`.baselines` (deterministic counter
signatures + the ``compare`` perf gate of ``run_tier1.sh perfgate``).
Both are device-free: they consume the files, never the session.

The hard contract: **telemetry OFF is the seed hot path** — no extra
aux outputs, no recompilation, no sink writes. Every function in this
module is a no-op until :func:`configure` activates a session, except
:func:`span`, which still enters a ``TraceAnnotation`` (one activity
check while no profiler runs); ``make_join_step`` only emits the aux
``Metrics`` output when explicitly asked (``with_metrics=True``) or
when a session is active at build time via ``make_distributed_join``'s
``with_metrics=None`` resolution. The step's stage scopes are on
either way: they change instruction metadata, never instructions.
Tested by ``tests/test_telemetry.py`` (treedef/program-count parity
with the seed plus counter-vs-pandas-oracle checks).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from distributed_join_tpu.telemetry.export import TelemetrySink
from distributed_join_tpu.telemetry.metrics import Metrics, MetricsTape
from distributed_join_tpu.telemetry import spans as _spans

__all__ = [
    "Metrics", "MetricsTape", "TelemetrySink",
    "configure", "configure_from_args", "counter_add",
    "current_trace", "emit_metrics", "enabled", "event", "finalize",
    "maybe_start_xla_trace", "note", "request_scope", "session", "sink",
    "span", "span_complete", "stage_profile", "summary",
]

_active: Optional[TelemetrySink] = None


def enabled() -> bool:
    """Whether a telemetry session is active. With no session nothing
    reaches the sink, events/counters are no-ops, spans reach only a
    running profiler, and the join step compiles the seed program."""
    return _active is not None


def sink() -> Optional[TelemetrySink]:
    return _active


def configure(out_dir: str, *, trace: bool = False,
              rank: Optional[int] = None) -> TelemetrySink:
    """Activate a telemetry session writing under ``out_dir``
    (events JSONL + Chrome trace per rank, summary on rank 0).
    ``trace`` additionally arms a full XLA device profile — started
    lazily by :func:`maybe_start_xla_trace` because
    ``jax.profiler.start_trace`` initializes the backend, which must
    not happen before the drivers' ``--platform`` handling / multi-host
    bootstrap. Reconfiguring finalizes the previous session."""
    global _active
    if _active is not None:
        finalize()
    if rank is None:
        # Env-based before backend init (bootstrap.process_id probes
        # the env fallback without initializing a backend).
        from distributed_join_tpu.parallel.bootstrap import process_id

        rank = process_id()
    _active = TelemetrySink(out_dir, rank=rank, xla_trace=trace)
    return _active


def configure_from_args(args) -> bool:
    """Driver seam: activate from ``--telemetry[=DIR]`` / ``--trace``
    / ``--diagnose`` / ``--history`` / ``--explain`` /
    ``--stage-profile`` flags (see ``benchmarks.add_telemetry_args``).
    Any of them alone implies telemetry at the default directory (all
    need a session — diagnosis reads its files, a history entry wants
    the counter signature, explain.json and stageprofile.json land
    beside diagnosis.json). Returns whether a session was
    configured."""
    out_dir = getattr(args, "telemetry", None)
    trace = bool(getattr(args, "trace", False))
    if out_dir is None and (trace or getattr(args, "diagnose", False)
                            or getattr(args, "history", None)
                            or getattr(args, "explain", False)
                            or getattr(args, "stage_profile", None)):
        out_dir = "telemetry"
    if out_dir is None:
        return False
    configure(out_dir, trace=trace)
    return True


def maybe_start_xla_trace() -> None:
    """Start the XLA device profile for a ``--trace`` session, once,
    AFTER platform selection/bootstrap (drivers call this from
    ``apply_platform``; bench.py after backend init). Safe to call any
    time: no-op without an armed session."""
    if _active is not None:
        _active.maybe_start_xla_trace()


def refresh_rank() -> None:
    """Re-resolve the process rank and rebind the sink's files to it.
    Sessions are configured before the multi-host handshake, when only
    the env fallback rank is visible; drivers call this (via
    ``apply_platform``/bench.py, alongside :func:`maybe_start_xla_trace`)
    once the runtime is authoritative. No-op without a session or when
    the rank is unchanged."""
    if _active is not None:
        from distributed_join_tpu.parallel.bootstrap import process_id

        _active.rebind_rank(process_id())


def finalize() -> Optional[dict]:
    """Close the session: stop an XLA trace, write the Chrome trace
    (and rank-0 summary), close the JSONL log. Returns the final
    summary dict (None when no session was active). Idempotent."""
    global _active
    if _active is None:
        return None
    s = _active
    _active = None
    return s.close()


@contextlib.contextmanager
def session(out_dir: str, *, trace: bool = False, rank: Optional[int] = None):
    """Scoped session for tests/scripts: ``with telemetry.session(d)
    as sink: ...`` — configured on entry, finalized on exit."""
    s = configure(out_dir, trace=trace, rank=rank)
    try:
        yield s
    finally:
        if _active is s:
            finalize()


class _ProfilerSpan:
    """:func:`span` with no session: a ``TraceAnnotation`` alone, so
    the span still lands in any running profiler trace (payload as its
    arguments) and costs one activity check when none runs. Yields
    None, like an inactive span always has."""

    __slots__ = ("_annotation",)

    def __init__(self, name: str, payload: dict):
        self._annotation = jax.profiler.TraceAnnotation(name, **payload)

    def __enter__(self):
        self._annotation.__enter__()
        _spans.annotations().append(self._annotation)
        return None

    def __exit__(self, *exc):
        _spans.annotations().pop()
        return self._annotation.__exit__(*exc)


def span(name: str, **payload):
    """Hierarchical host span context manager. With a session active
    it records a sink event and yields a handle supporting
    ``note(**kv)`` and ``sync_on(scalar)`` — the scalar is fetched
    (ONE value to host) at span close so the span honestly covers
    device completion; see :mod:`.spans` for the sync-semantics
    contract. Either way the span enters a
    ``jax.profiler.TraceAnnotation`` carrying ``payload`` as its
    arguments; without a session that is all it does, and it yields
    None. Host code only: a stage inside traced code is a
    ``jax.named_scope``."""
    if _active is None:
        return _ProfilerSpan(name, payload)
    return _spans.span_scope(_active, name, payload or None)


def note(**kv) -> None:
    """Attach arguments to the innermost open :func:`span` of this
    thread, for what becomes known inside it: its ``TraceAnnotation``
    carries them into a running profiler's trace, and with a session
    its sink event carries them too. No-op outside any span."""
    anns = _spans.annotations()
    if anns:
        anns[-1].set_metadata(**kv)
    stack = _spans._stack()
    if _active is not None and stack:
        stack[-1].note(**kv)


def span_complete(name: str, t0_perf: float, dur_s: float, **payload) -> None:
    """Record an already-measured interval as a completed span (the
    ``utils.benchmarking.measure`` seam: the timing definition lives
    there, the record lands here). ``t0_perf`` is a
    ``time.perf_counter()`` stamp."""
    if _active is not None:
        _active.span_event(name, t0_perf, dur_s, payload=payload or None)


@contextlib.contextmanager
def request_scope(request_id: Optional[str],
                  trace: Optional[dict] = None):
    """Tag every event/span recorded inside the scope with a serving
    request id (the correlation key of docs/OBSERVABILITY.md "Live
    service metrics") and — when ``trace`` carries a ``telemetry/
    tracectx.py`` context — with ``(trace_id, span_id,
    parent_span_id)``, the cross-process causal key of
    docs/OBSERVABILITY.md "Distributed tracing". Tags land in the
    per-rank JSONL records, the Chrome-trace args, and — because the
    sink tags are sink-global, not thread-local — in events a
    request's watchdog/staging worker threads emit too. No-op when
    telemetry is off or both tags are None; nests (the previous tags
    are restored on exit)."""
    s = _active
    if s is None or (request_id is None and trace is None):
        yield
        return
    prev = s.set_request_id(request_id) if request_id is not None \
        else None
    prev_trace = s.set_trace(trace) if trace is not None else None
    try:
        yield
    finally:
        # the session may have been finalized mid-request; restoring
        # on the captured sink is still safe (a closed sink just holds
        # the tag, it records nothing)
        if trace is not None:
            s.set_trace(prev_trace)
        if request_id is not None:
            s.set_request_id(prev)


def current_trace() -> Optional[dict]:
    """The trace context installed by the innermost active
    :func:`request_scope` (None when telemetry is off or no scope set
    one). Flight-recorder dumps and history writers read it so
    postmortem artifacts carry the causal key of the request that was
    active when they were cut."""
    if _active is None:
        return None
    return _active.current_trace()


def event(name: str, **payload) -> None:
    """Record an instant event (retry attempts, manifest writes,
    bootstrap backoff, batch completion...)."""
    if _active is not None:
        _active.event(name, payload=payload or None)


def counter_add(name: str, value) -> None:
    """Accumulate a host-side counter (e.g. the out-of-core phase
    seconds); appears under ``counters`` in the summary."""
    if _active is not None:
        _active.counter_add(name, value)


def emit_metrics(metrics: Metrics) -> Optional[dict]:
    """Fetch a device :class:`Metrics` pytree to host (the one
    deliberate transfer — after the timed region) and fold it into the
    session summary + event log. Returns the host-side dict."""
    if metrics is None:
        return None
    d = metrics.to_dict()
    if _active is not None:
        _active.set_metrics(d)
        _active.event("metrics", payload={"reduced": d["reduced"]})
    return d


def stage_profile(record: dict) -> None:
    """Render a stage-profile record (``stageprof.StageProfile.
    as_record()``) into the session's Chrome trace as a dedicated
    Perfetto track with counter flow links (no-op when telemetry is
    off)."""
    if _active is not None:
        _active.add_stage_profile(record)


def summary() -> Optional[dict]:
    """The JSON-shaped session summary drivers embed in their records
    (``benchmarks.report``): counters, span totals, device metrics,
    event/file locations. None when telemetry is off."""
    if _active is None:
        return None
    return _active.summary()
