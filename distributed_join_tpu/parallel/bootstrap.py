"""Multi-host bootstrap — the reference's MPI control plane, TPU-native.

The reference launches one OS process per GPU under ``mpirun``; MPI is
pure control plane (rank/size, ``ncclGetUniqueId`` broadcast, barriers)
while NCCL/UCX own the data plane (SURVEY.md §3.3). The 1:1 TPU mapping:

- control plane: ``jax.distributed.initialize(coordinator_address,
  num_processes, process_id)`` — a TCP/DCN handshake with a coordinator
  replaces ``MPI_Bcast`` of the NCCL id;
- data plane: unchanged — after initialization ``jax.devices()`` spans
  every process's chips, the 1-D rank mesh covers the whole slice, and
  the SAME compiled ``shard_map`` program runs on it, XLA routing
  collectives over ICI within a host and DCN across hosts.

Nothing else in the framework changes for multi-host: the
``Communicator`` is already built on the global ``jax.devices()`` view.

Configuration comes from flags or the ``DJTPU_*`` environment variables
(set by ``scripts/launch_multiprocess.py``, the framework's ``mpirun``
equivalent):

  DJTPU_COORDINATOR    host:port of process 0 (the coordinator)
  DJTPU_NUM_PROCESSES  total process count
  DJTPU_PROCESS_ID     this process's id in [0, num_processes)

For a no-TPU validation path (the reference cannot do this at all —
its multi-rank tests need real GPUs under mpirun, SURVEY.md §4), set
``DJTPU_CPU_DEVICES_PER_PROCESS=k``: each process presents ``k``
virtual CPU devices and cross-process collectives run over the gloo CPU
backend — verified working in this environment (2 procs x 4 devices).
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

ENV_COORDINATOR = "DJTPU_COORDINATOR"
ENV_NUM_PROCESSES = "DJTPU_NUM_PROCESSES"
ENV_PROCESS_ID = "DJTPU_PROCESS_ID"
ENV_CPU_DEVICES = "DJTPU_CPU_DEVICES_PER_PROCESS"
# Failure-semantics knobs (docs/FAILURE_SEMANTICS.md): overall
# handshake deadline, attempt count, and first-retry backoff.
ENV_BOOTSTRAP_DEADLINE = "DJTPU_BOOTSTRAP_DEADLINE"
ENV_BOOTSTRAP_RETRIES = "DJTPU_BOOTSTRAP_RETRIES"
ENV_BOOTSTRAP_BACKOFF = "DJTPU_BOOTSTRAP_BACKOFF"

DEFAULT_DEADLINE_S = 300.0
DEFAULT_RETRIES = 3
DEFAULT_BACKOFF_S = 2.0


class BootstrapError(RuntimeError):
    """The distributed handshake (or backend init) failed or hung —
    an environment outage, not a join/benchmark result. Carries the
    full per-attempt trail so every driver can emit a machine-readable
    failure record instead of a bare traceback (generalizes bench.py's
    round-5 ad-hoc ``_BackendInitError``)."""

    def __init__(self, message: str, *, phase: str = "bootstrap",
                 attempts=None, deadline_s: Optional[float] = None,
                 coordinator: Optional[str] = None):
        super().__init__(message)
        self.phase = phase
        self.attempts = attempts or []
        self.deadline_s = deadline_s
        self.coordinator = coordinator

    def record(self) -> dict:
        """The JSON-shaped failure record drivers embed in their
        output (docs/FAILURE_SEMANTICS.md "Bootstrap failures")."""
        return {
            "error": "BootstrapError",
            "phase": self.phase,
            "message": str(self),
            "coordinator": self.coordinator,
            "deadline_s": self.deadline_s,
            "attempts": self.attempts,
        }


def call_with_deadline(fn: Callable, deadline_s: float,
                       what: str = "backend init"):
    """Run ``fn()`` under the shared hang watchdog
    (:mod:`..watchdog` — promoted there from this module in PR 5) and
    turn BOTH failure modes of a dead environment — an exception
    ("UNAVAILABLE") and a hang inside PJRT client init (a chip another
    process holds) — into a structured :class:`BootstrapError`.
    The caller decides whether a timed-out worker thread forces a hard
    exit (the watchdog detaches it from the atexit join, but it may
    still hold backend locks; see bench.py)."""
    from distributed_join_tpu.parallel.watchdog import (
        HangError,
        call_with_deadline as _guarded,
    )

    try:
        return _guarded(fn, deadline_s, what=what)
    except HangError:
        raise BootstrapError(
            f"{what} did not complete within {deadline_s:g}s "
            "(is the chip held by another process?)",
            phase=what, deadline_s=deadline_s,
            attempts=[{"attempt": 0, "elapsed_s": deadline_s,
                       "error": f"timeout after {deadline_s:g}s"}],
        ) from None
    except Exception as exc:
        raise BootstrapError(
            f"{what} failed: {type(exc).__name__}: {exc}",
            phase=what, deadline_s=deadline_s,
            attempts=[{"attempt": 0, "elapsed_s": None,
                       "error": f"{type(exc).__name__}: {exc}"}],
        ) from exc


def _connect(coordinator_address: str, num_processes: int,
             process_id: int) -> None:
    """The raw handshake — one ``jax.distributed.initialize`` attempt.
    Split out so :func:`initialize`'s retry loop (and tests) can
    substitute it."""
    import jax

    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except Exception:
        # jax sets its global client/service state BEFORE the TCP
        # connect; left in place, every retry would die instantly with
        # "distributed.initialize should only be called once" instead
        # of re-attempting the handshake. Best-effort teardown (the
        # half-initialized client may itself fail to shut down). Only
        # reachable on toolchains where a failed handshake raises —
        # this environment's XLA LOG(FATAL)s on a connect timeout,
        # which no in-process retry can survive.
        try:
            jax.distributed.shutdown()
        except Exception:
            pass
        raise


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    cpu_devices_per_process: Optional[int] = None,
    *,
    deadline_s: Optional[float] = None,
    max_retries: Optional[int] = None,
    backoff_s: Optional[float] = None,
    connect: Optional[Callable] = None,
    sleep: Callable[[float], None] = time.sleep,
) -> None:
    """Join the distributed runtime. Call BEFORE any other jax use —
    like ``MPI_Init``, this must precede every collective/device call.

    ``cpu_devices_per_process`` switches to the virtual-CPU data plane
    (gloo): multi-host semantics without TPU hardware.

    Failure semantics: the TCP/DCN handshake replaces the reference's
    ``MPI_Bcast`` of the NCCL id and fails in its ways — a coordinator
    that is not up yet (workers race it at launch), a transient
    connect refusal, or a hung endpoint. Attempts retry with
    exponential backoff (``max_retries`` attempts, first retry after
    ``backoff_s``, doubling) under an overall ``deadline_s``; the env
    knobs ``DJTPU_BOOTSTRAP_DEADLINE`` / ``DJTPU_BOOTSTRAP_RETRIES`` /
    ``DJTPU_BOOTSTRAP_BACKOFF`` configure launched processes.
    Exhaustion raises :class:`BootstrapError` carrying the full
    per-attempt trail, which drivers embed as a machine-readable
    failure record in their JSON output. ``connect``/``sleep`` are
    injectable for tests.
    """
    import jax

    from distributed_join_tpu.parallel.faults import retry_with_backoff

    deadline_s = (float(os.environ.get(ENV_BOOTSTRAP_DEADLINE,
                                       DEFAULT_DEADLINE_S))
                  if deadline_s is None else deadline_s)
    max_retries = (int(os.environ.get(ENV_BOOTSTRAP_RETRIES,
                                      DEFAULT_RETRIES))
                   if max_retries is None else max_retries)
    backoff_s = (float(os.environ.get(ENV_BOOTSTRAP_BACKOFF,
                                      DEFAULT_BACKOFF_S))
                 if backoff_s is None else backoff_s)

    # Record the identity for process_id()/is_coordinator() even when
    # this is called directly (one invocation per host) rather than via
    # the tpu-launch env.
    os.environ[ENV_NUM_PROCESSES] = str(num_processes)
    os.environ[ENV_PROCESS_ID] = str(process_id)

    if cpu_devices_per_process is not None:
        # OVERRIDE any inherited device-count flag (e.g. a test harness
        # parent sets 8): each launched process must present exactly
        # cpu_devices_per_process devices or the global mesh is wrong.
        flags = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(
            "--xla_force_host_platform_device_count="
            f"{cpu_devices_per_process}"
        )
        os.environ["XLA_FLAGS"] = " ".join(flags)
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
        # Cross-process CPU collectives need an explicit transport.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    do_connect = connect if connect is not None else _connect
    t0 = time.monotonic()

    def _on_retry(attempt, exc, delay):
        # Telemetry is a no-op without a session; with one, the
        # handshake's backoff trail streams into the event log as it
        # happens (docs/OBSERVABILITY.md) — the same per-attempt data
        # BootstrapError.record() carries on exhaustion.
        from distributed_join_tpu import telemetry

        telemetry.event(
            "bootstrap_retry", attempt=attempt,
            coordinator=coordinator_address, backoff_s=delay,
            error=f"{type(exc).__name__}: {exc}",
        )

    def _bounded_connect():
        # retry_with_backoff's deadline check only runs BETWEEN
        # attempts; a hung endpoint (TCP accepted, handshake never
        # completing) must be bounded too, so each attempt runs under
        # call_with_deadline's watchdog holding the REMAINING
        # deadline (its timed-out worker thread stays hung — the
        # caller decides whether to force a hard exit). INVARIANT: a
        # timed-out attempt burned that whole remainder, so the retry
        # loop's deadline check stops before launching another attempt
        # — a hung handshake is never retried (a retry would race the
        # still-blocked worker thread on jax's global state).
        remaining = max(0.0, deadline_s - (time.monotonic() - t0))
        return call_with_deadline(
            lambda: do_connect(coordinator_address, num_processes,
                               process_id),
            remaining, what="handshake",
        )

    from distributed_join_tpu import telemetry

    try:
        _, attempts = retry_with_backoff(
            _bounded_connect,
            max_attempts=max(1, max_retries),
            backoff_s=backoff_s,
            deadline_s=deadline_s,
            sleep=sleep,
            on_retry=_on_retry,
        )
        telemetry.event("bootstrap_ok",
                        coordinator=coordinator_address,
                        process_id=process_id, attempts=len(attempts))
    except BootstrapError as exc:
        # Every connect outcome — hang or error — reaches here wrapped
        # by call_with_deadline; fill in the handshake identity, the
        # CONFIGURED deadline (not the last attempt's remainder), and
        # the retry loop's full per-attempt trail.
        exc.coordinator = exc.coordinator or coordinator_address
        exc.deadline_s = deadline_s
        exc.attempts = getattr(exc, "_retry_attempts", None) or exc.attempts
        telemetry.event("bootstrap_failed", **exc.record())
        raise


def maybe_initialize_from_env() -> bool:
    """Initialize iff the ``DJTPU_*`` launch env is present; returns
    whether it did. Drivers call this first thing, so a single-process
    run (no env) is untouched and a launched run joins its slice."""
    coord = os.environ.get(ENV_COORDINATOR)
    if not coord:
        return False
    nproc = int(os.environ[ENV_NUM_PROCESSES])
    pid = int(os.environ[ENV_PROCESS_ID])
    cpu = os.environ.get(ENV_CPU_DEVICES)
    initialize(coord, nproc, pid,
               cpu_devices_per_process=int(cpu) if cpu else None)
    return True


def process_id() -> int:
    """This process's id (0 when not launched distributed) — the
    reference's ``rank`` for rank-0-only printing.

    Once a backend exists, ``jax.process_index()`` is authoritative —
    a user may have called ``jax.distributed.initialize`` themselves
    (or relied on TPU-pod auto-detection) without any ``DJTPU_*`` env,
    and every host believing it is rank 0 would duplicate reports and
    race on ``--json-output``. The env is only a pre-initialization
    fallback; probing it must not itself initialize a backend."""
    from jax._src import xla_bridge

    if getattr(xla_bridge, "_backends", None):
        import jax

        return jax.process_index()
    return int(os.environ.get(ENV_PROCESS_ID, "0"))


def is_coordinator() -> bool:
    return process_id() == 0
