"""Shared hang watchdog — bounded execution for host-side blocking calls.

A distributed join has three places where "it never came back" is a
live failure mode the retry machinery cannot see: backend/bootstrap
init (PJRT client init can block when another process holds the
chip), the out-of-core batch loop's per-batch scalar fetch
(a deadlocked collective never sequences), and a whole benchmark run
wedged inside any of the above. PR 1 solved the first with
``bootstrap.call_with_deadline``; this module is that watchdog
PROMOTED to a shared, domain-neutral seam (the bootstrap keeps its
``BootstrapError`` wrapper on top):

- :func:`call_with_deadline` runs ``fn()`` on a watchdog worker thread
  and raises a structured :class:`HangError` on timeout, emitting
  ``watchdog_armed`` / ``watchdog_timeout`` telemetry events
  (no-ops without a session — docs/OBSERVABILITY.md). The timed-out
  worker thread cannot be killed (CPython), but it IS detached from
  ``concurrent.futures``' atexit join so a wedged call can no longer
  hang interpreter shutdown.
- :func:`resolve_guard_deadline` is the one resolution of the
  benchmark-level deadline (``--guard-deadline-s`` flag, then the
  ``DJTPU_GUARD_DEADLINE_S`` env var, default None = unguarded —
  exactly the pre-existing behavior).
- :func:`shutdown_bounded` is the bounded worker-pool teardown the
  out-of-core error path uses instead of an unbounded atexit join
  (docs/FAILURE_SEMANTICS.md "Hang watchdog").
"""

from __future__ import annotations

import os
import time
import warnings
from typing import Callable, Optional

ENV_GUARD_DEADLINE = "DJTPU_GUARD_DEADLINE_S"

# How long the bounded teardown waits for a worker thread before
# declaring it wedged and detaching it from the atexit join.
DEFAULT_SHUTDOWN_TIMEOUT_S = 10.0


class HangError(RuntimeError):
    """A watchdogged call did not complete within its deadline — the
    structured form of "it hung". Distinct from an *error* return: the
    underlying work may still be running on its (now detached) worker
    thread, so the caller must treat any shared state it touches as
    poisoned."""

    def __init__(self, message: str, *, what: str = "guarded call",
                 deadline_s: Optional[float] = None):
        super().__init__(message)
        self.what = what
        self.deadline_s = deadline_s

    def record(self) -> dict:
        """JSON-shaped failure record (the watchdog analog of
        ``BootstrapError.record()``)."""
        return {
            "error": "HangError",
            "what": self.what,
            "deadline_s": self.deadline_s,
            "message": str(self),
        }


def _detach_from_atexit(thread) -> None:
    """Best-effort: remove ``thread`` from concurrent.futures' atexit
    join table so a wedged worker cannot hang interpreter shutdown.
    (CPython keeps the registry in a private dict; if the internals
    move, the worst case is the old behavior — a blocked exit.)"""
    try:
        from concurrent.futures import thread as _cft

        _cft._threads_queues.pop(thread, None)
    except Exception:  # pragma: no cover - interpreter-internal drift
        pass


def call_with_deadline(fn: Callable, deadline_s: float,
                       what: str = "guarded call"):
    """Run ``fn()`` under a watchdog thread; raise :class:`HangError`
    if it does not complete within ``deadline_s`` seconds.

    Exceptions raised by ``fn`` propagate unchanged (the watchdog
    bounds TIME, it does not reinterpret failures — callers with a
    domain error, like ``bootstrap.call_with_deadline``, wrap on top).
    On timeout the worker thread stays blocked inside ``fn`` — it is
    detached from the atexit join, and the caller decides whether the
    process can continue at all (a wedged thread may hold backend
    locks; benchmark drivers hard-exit after writing their record).
    """
    import concurrent.futures

    from distributed_join_tpu import telemetry

    telemetry.event("watchdog_armed", what=what,
                    deadline_s=float(deadline_s))
    ex = concurrent.futures.ThreadPoolExecutor(
        1, thread_name_prefix=f"watchdog-{what[:24]}")
    fut = ex.submit(fn)
    try:
        result = fut.result(timeout=deadline_s)
    except concurrent.futures.TimeoutError:
        for t in list(getattr(ex, "_threads", ())):
            _detach_from_atexit(t)
        telemetry.event("watchdog_timeout", what=what,
                        deadline_s=float(deadline_s))
        raise HangError(
            f"{what} did not complete within {deadline_s:g}s",
            what=what, deadline_s=float(deadline_s),
        ) from None
    finally:
        # On fn-raised exceptions the worker has already returned
        # (the raise IS its result), so the idle thread must be
        # released here too — only the timeout path above leaves its
        # (wedged, detached) worker behind.
        if not fut.cancelled() and fut.done():
            ex.shutdown(wait=False)
    return result


def resolve_guard_deadline(args=None) -> Optional[float]:
    """The benchmark-run guard deadline: ``--guard-deadline-s`` when
    the driver passed one, else ``DJTPU_GUARD_DEADLINE_S``, else None
    (unguarded — the historical behavior; a deadline is opt-in because
    a legitimate SF-100 run can take hours)."""
    flag = getattr(args, "guard_deadline_s", None) if args is not None \
        else None
    if flag is not None:
        return float(flag) if flag > 0 else None
    env = os.environ.get(ENV_GUARD_DEADLINE, "")
    if not env:
        return None
    val = float(env)
    return val if val > 0 else None


def shutdown_bounded(executor, what: str,
                     timeout_s: float = DEFAULT_SHUTDOWN_TIMEOUT_S) -> bool:
    """Shut an executor down with a BOUNDED join of its workers.

    ``ThreadPoolExecutor.shutdown(wait=False)`` does not join, but
    concurrent.futures' atexit hook joins every pool thread forever —
    so a worker wedged in a dead backend call turns "the run failed"
    into "the interpreter never exits" (the orphaned-worker risk noted
    in the out-of-core error path). This helper joins each worker for
    its slice of ``timeout_s``; a thread still alive after that is
    reported (``worker_shutdown_timeout`` telemetry event + warning)
    and detached from the atexit join so exit proceeds. Returns True
    when every worker exited cleanly."""
    from distributed_join_tpu import telemetry

    executor.shutdown(wait=False, cancel_futures=True)
    threads = list(getattr(executor, "_threads", ()))
    deadline = time.monotonic() + timeout_s
    clean = True
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
        if t.is_alive():
            clean = False
            _detach_from_atexit(t)
            telemetry.event("worker_shutdown_timeout", pool=what,
                            thread=t.name, timeout_s=float(timeout_s))
            warnings.warn(
                f"{what} worker {t.name!r} did not exit within "
                f"{timeout_s:g}s — detached from interpreter-exit "
                "join; treat its outputs as abandoned",
                stacklevel=2,
            )
    return clean
