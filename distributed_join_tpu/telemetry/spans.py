"""Hierarchical span timer with honest sync semantics.

A span measures a host-visible interval (generate, trace, compile,
dispatch, fetch, an out-of-core batch stage...). Two rules keep the
numbers honest in this environment (the same protocol as
``utils/benchmarking.py``, whose docstring explains why):

1. **Sync by fetching ONE scalar, never bare ``block_until_ready``.**
   Pulling one scalar to the host is the completion signal that
   orders the host clock after the device work on every backend. A span that should cover device completion registers that
   scalar via ``sp.sync_on(scalar)`` and the fetch happens at span
   close, inside the measured interval.
2. **Spans are host code; stages inside traced code are scopes.**
   The whole partition->shuffle->join pipeline is ONE compiled
   program, and a host timer around a stage inside ``jit`` would time
   tracing. So the step names its stages with ``jax.named_scope``
   (always on: the names ride the instructions' ``op_name`` metadata
   into every XLA profile and change no instruction), and a span is
   only ever entered by code that runs on the host. Each span enters
   a ``jax.profiler.TraceAnnotation`` (its payload as arguments), so
   it lands in a running profiler's trace on the device planes'
   clock; with a session active it is also recorded in the sink.
   Device-side *values* travel via :mod:`.metrics`, never host
   callbacks.

Span nesting is tracked per thread (the out-of-core staging/fetch
workers each get their own stack); the sink records the full
slash-joined path so hierarchy survives into the JSONL log, and the
Chrome trace nests "X" events by time per (rank, thread) track.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Optional


def fetch_one_scalar(x):
    """Force completion of the program that produced ``x`` by pulling
    exactly one scalar to the host (the honest sync — see module
    docstring). ``x`` may be any array; non-scalars are reduced to
    their first element ON DEVICE so only one value crosses."""
    import numpy as np

    if getattr(x, "ndim", 0):
        x = x.ravel()[0]
    v = np.asarray(x)
    try:
        return v.item()
    except ValueError:  # pragma: no cover - non-numeric scalar
        return None


_tls = threading.local()


def annotations() -> list:
    """This thread's open spans' ``TraceAnnotation``s, innermost
    last (what ``telemetry.note`` attaches arguments to)."""
    s = getattr(_tls, "annotations", None)
    if s is None:
        s = _tls.annotations = []
    return s


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class Span:
    """The handle a span context yields: attach payload with
    ``note(**kv)``; register the completion scalar with
    ``sync_on(scalar)`` (fetched at close)."""

    __slots__ = ("name", "path", "payload", "t0", "_sync")

    def __init__(self, name: str, path: str, payload: Optional[dict]):
        self.name = name
        self.path = path
        self.payload = dict(payload) if payload else {}
        self.t0 = 0.0
        self._sync = None

    def note(self, **kv) -> None:
        self.payload.update(kv)

    def sync_on(self, scalar) -> None:
        self._sync = scalar


@contextmanager
def span_scope(sink, name: str, payload: Optional[dict] = None):
    """The active-session span implementation behind
    ``telemetry.span`` (a bare ``TraceAnnotation`` when off)."""
    import jax

    stack = _stack()
    path = "/".join([*(s.name for s in stack), name])
    sp = Span(name, path, payload)
    stack.append(sp)
    err = None
    try:
        with jax.profiler.TraceAnnotation(name, **sp.payload) as ann:
            annotations().append(ann)
            sp.t0 = time.perf_counter()
            try:
                yield sp
                if sp._sync is not None:
                    sp.payload["sync_value"] = fetch_one_scalar(sp._sync)
            except BaseException as exc:
                err = exc
                raise
            finally:
                annotations().pop()
    finally:
        dur = time.perf_counter() - sp.t0
        stack.pop()
        if err is not None:
            sp.payload["error"] = f"{type(err).__name__}: {err}"
        sink.span_event(name, sp.t0, dur, path=sp.path,
                        payload=sp.payload or None)
