"""Failure-semantics layer: fault injection, plan validation, retry
policy, and progress manifests.

The reference sizes receive buffers exactly and "can't overflow"
(SURVEY.md §2); the TPU port replaces that with static shapes plus an
``auto_retry`` ladder, a multi-host TCP handshake, and a host-side
out-of-core batch loop — three failure surfaces the rest of the code
merely documents. This module makes them *exercisable* and *recoverable*:

- :class:`FaultInjectingCommunicator` wraps any ``Communicator`` and
  deterministically injects failures — forced overflow flags (a
  capacity squeeze as the retry ladder sees it), rank-inconsistent
  ragged-plan count gathers, delayed or failed dispatches — so every
  branch of ``distributed_inner_join``'s ladder, the skew-capacity
  jump, the compression bits-widening path, and the out-of-core batch
  retry can be driven from tier-1 CPU tests.
- :func:`validate_ragged_plan` is the debug-mode cross-rank
  consistency check for the exact-size shuffle's size/offset vectors
  (``parallel/shuffle.ragged_plan``): inconsistent vectors silently
  corrupt (emulation) or hang (TPU hardware op) — validation turns
  them into a loud :class:`PlanValidationError` at the cost of one
  extra small all-gather. Enable with :func:`validate_plans` or
  ``DJTPU_VALIDATE_PLANS=1``; the gate is trace-time, so it must be
  on when the program is *traced*, not merely when it runs.
- :func:`retry_with_backoff` is the generic transient-failure loop
  (used by ``bootstrap.initialize``'s handshake retry; see
  :class:`distributed_join_tpu.parallel.bootstrap.BootstrapError`).
- :class:`CapacityLadder` + :class:`RetryReport` make the
  overflow-escalation policy a first-class, reportable object shared
  by ``distributed_inner_join``, the benchmark drivers, and
  ``bench.py`` (previously an inline loop whose decisions evaporated).
- :class:`JoinManifest` is the on-disk per-batch progress record that
  makes ``out_of_core.batched_join_host`` resumable: a killed SF-100
  run restarts from the first incomplete batch and reproduces the
  uninterrupted total bit-exactly.

When a telemetry session is active (docs/OBSERVABILITY.md), ladder
attempts and manifest writes also stream into the session's event log
(``retry_attempt`` / ``manifest_batch`` / ``manifest_failure``), so
the retry trail survives even a run killed before its RetryReport
could be assembled.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from contextlib import contextmanager
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from distributed_join_tpu.ops.join import JoinResult
from distributed_join_tpu.parallel.communicator import Communicator


class FaultInjectedError(RuntimeError):
    """An injected (not organic) failure — raised by
    :class:`FaultInjectingCommunicator` on a scheduled dispatch fault
    so recovery paths can be driven deterministically."""


class PlanValidationError(RuntimeError):
    """A ragged transfer plan failed cross-rank consistency checks.
    Raised from :func:`check_plan_violations` (violations are RECORDED
    by the in-program callback, which also trips the overflow flag —
    raising inside the compiled program would poison the backend's
    dispatch stream for the whole process, turning a diagnosable fault
    into an undiagnosable one)."""


@dataclasses.dataclass
class FaultPlan:
    """Deterministic failure schedule for
    :class:`FaultInjectingCommunicator`. All counters are cumulative
    over the wrapper's lifetime, so one plan describes one scripted
    outage scenario.

    - ``overflow_programs``: the first N programs compiled through
      ``spmd`` report ``JoinResult.overflow`` = True regardless of the
      data — indistinguishable, to the ``auto_retry`` ladder, from a
      genuine capacity squeeze (every retry recompiles, so program
      index == ladder attempt).
    - ``fail_dispatches``: the first N invocations of any compiled
      program raise :class:`FaultInjectedError` at dispatch (a
      transient launch/collective failure).
    - ``fail_after_dispatches``: every invocation AFTER the first N
      raises — a persistent outage, the "killed mid-run" scenario for
      out-of-core resume tests.
    - ``drop_dispatches``: EXACT 1-based dispatch ordinals that raise,
      everything else unaffected — a surgical single-message drop.
      The replication fencing tests use it to make one holder miss
      precisely the ``append`` delta prep (dispatch #2 after the
      register prep) while register and later probes stay healthy.
    - ``dispatch_delay_s``: sleep before each dispatch (a slow/
      congested interconnect; drives deadline paths).
      ``delay_after_dispatches`` defers the delay: the first N
      dispatches run at full speed and every LATER one sleeps — a
      replica that serves healthily and then wedges mid-soak, the
      scripted hang the fleet chaos slice injects (None/0 = delay
      from the first dispatch, the historical behavior).
    - ``corrupt_plan_gathers``: the first N 1-D int32 all-gathers (the
      ragged plan's count exchange) come back rank-INCONSISTENTLY
      perturbed: each rank adds its own rank index to row
      ``seed % n_ranks`` of its gathered view, so every rank plans
      from a different count matrix — exactly the corruption
      :func:`validate_ragged_plan` exists to catch.
    - ``corrupt_mode`` + ``corrupt_collectives``: DATA corruption —
      the adversary the wire-integrity digests
      (parallel/integrity.py) exist for, injected at the collective
      seams so the join sees exactly what a corrupting transport
      would deliver. The first N eligible collectives (counted at
      trace time, cumulative over the wrapper's lifetime — every
      retry recompiles, so a retry ladder can outlast a finite
      budget) are perturbed on rank ``corrupt_rank`` (default
      ``seed % n_ranks``):

      * ``"bit_flip"`` — one bit of one element of a received data
        block flips (seed-addressed; padded blocks via
        ``all_to_all``, ragged buffers via ``ragged_all_to_all``);
      * ``"row_truncate"`` / ``"row_duplicate"`` — a received row
        count drops/gains 1, so the receiver silently loses a real
        row or adopts a garbage one. Padded mode perturbs the target
        rank's received count vector; ragged mode perturbs one entry
        of the gathered count MATRIX identically on every rank — a
        consistent lie that sails through
        :func:`validate_ragged_plan` (whose whole check is
        cross-rank consistency) and is caught only by the digests;
      * ``"misroute"`` — rows land at the wrong rank: the target
        sender's blocks rotate one destination over (padded: the
        received block axis rolls; ragged: two entries of its
        ``input_offsets`` swap).
    """

    seed: int = 0
    overflow_programs: int = 0
    fail_dispatches: int = 0
    fail_after_dispatches: Optional[int] = None
    drop_dispatches: tuple = ()
    dispatch_delay_s: float = 0.0
    delay_after_dispatches: Optional[int] = None
    corrupt_plan_gathers: int = 0
    corrupt_mode: Optional[str] = None
    corrupt_collectives: int = 0
    corrupt_rank: Optional[int] = None


def plan_from_record(record: dict) -> FaultPlan:
    """Rebuild a :class:`FaultPlan` from its JSON-shaped record (the
    inverse of ``dataclasses.asdict``, unknown keys refused loudly) —
    the seam behind the daemon's ``--fault-plan`` flag, which lets the
    fleet chaos harness script a replica's outage from the command
    line instead of patching code."""
    known = {f.name for f in dataclasses.fields(FaultPlan)}
    unknown = set(record) - known
    if unknown:
        raise ValueError(
            f"unknown FaultPlan field(s) {sorted(unknown)}; "
            f"known: {sorted(known)}")
    record = dict(record)
    if record.get("drop_dispatches") is not None:
        record["drop_dispatches"] = tuple(record["drop_dispatches"])
    return FaultPlan(**record)


CORRUPTION_MODES = ("bit_flip", "row_truncate", "row_duplicate",
                    "misroute")


class FaultInjectingCommunicator(Communicator):
    """A ``Communicator`` decorator that injects scheduled faults.

    Collective semantics are delegated verbatim to the wrapped backend;
    injection happens at the wrapper's OWN seams (program build,
    program dispatch, the plan-count gather), so the orchestrator and
    shuffle code run unmodified — what they see is indistinguishable
    from the real failure. Unknown attributes (``device_put_sharded``,
    ``mesh``, ...) delegate to the wrapped communicator.
    """

    def __init__(self, inner: Communicator, plan: FaultPlan):
        self._inner = inner
        self.plan = plan
        if (plan.corrupt_mode is not None
                and plan.corrupt_mode not in CORRUPTION_MODES):
            raise ValueError(
                f"unknown corrupt_mode {plan.corrupt_mode!r}; "
                f"pick one of {CORRUPTION_MODES}"
            )
        self.name = f"faulty({inner.name})"
        self._programs_built = 0
        self._dispatches = 0
        self._plan_gathers = 0
        self._corruptions = 0

    # -- delegation ---------------------------------------------------

    @property
    def n_ranks(self) -> int:
        return self._inner.n_ranks

    def all_to_all(self, x):
        return self._corrupt_exchanged(self._inner.all_to_all(x))

    def ppermute_all_to_all(self, x):
        return self._corrupt_exchanged(
            self._inner.ppermute_all_to_all(x))

    # -- hierarchical tier seams (two-level shuffle) ------------------
    # Explicit wrappers, not __getattr__ delegation: delegation would
    # hand back the INNER communicator's bound methods and the
    # corruption seams would silently never see hierarchical traffic.

    @property
    def n_slices(self) -> int:
        return self._inner.n_slices

    @property
    def chips_per_slice(self) -> int:
        return self._inner.chips_per_slice

    def all_to_all_chip(self, x):
        """The intra-slice (ICI) hop — delivered CLEAN by design: the
        chaos model targets the new cross-slice transport (the DCN
        tier is the long, lossy haul the wire-integrity digests exist
        for); ICI corruption is already exercised through the flat
        all_to_all seam every non-hierarchical config routes."""
        return self._inner.all_to_all_chip(x)

    def all_to_all_slice(self, x):
        """The cross-slice (DCN) exchange seam: the same corruption
        modes as the flat data plane (bit_flip / misroute roll /
        count slip on a 1-D int32 vector), injected on what a
        corrupting DCN transport would deliver. The received block's
        leading axis is the source-slice axis, so a misroute roll
        mis-attributes whole slices — rows that hash elsewhere enter
        the local join, exactly the adversary the end-to-end pair
        digests catch."""
        return self._corrupt_exchanged(
            self._inner.all_to_all_slice(x))

    def axis_index(self):
        return self._inner.axis_index()

    def pvary(self, x):
        return self._inner.pvary(x)

    def all_gather_replicated(self, x):
        return self._inner.all_gather_replicated(x)

    def psum(self, x):
        return self._inner.psum(x)

    def finalize(self) -> None:
        self._inner.finalize()

    def __getattr__(self, name):
        # Only reached for attributes not defined on the wrapper
        # (device_put_sharded, mesh, axis_name, ...).
        return getattr(self._inner, name)

    # -- injection seams ----------------------------------------------

    def _corrupt_rank(self) -> int:
        """The rank whose traffic the corruption modes hit (static)."""
        t = self.plan.corrupt_rank
        return (self.plan.seed if t is None else t) % self.n_ranks

    def _corrupt_budget(self) -> bool:
        """Trace-time budget: True for the first
        ``corrupt_collectives`` eligible collectives over the
        wrapper's lifetime (retries recompile, so a finite budget
        exhausts and a retried program runs clean)."""
        if (self.plan.corrupt_mode is None
                or self._corruptions >= self.plan.corrupt_collectives):
            return False
        self._corruptions += 1
        return True

    def rearm_corruption(self) -> None:
        """Reset the corruption budget so the NEXT traced program
        carries the schedule again. The drivers' verification seam
        (``benchmarks.collect_integrity``) calls this before tracing
        its verified step: the budget was spent corrupting the timed
        program traced earlier, and without rearming, the separate
        verification program would trace clean and bless benchmark
        numbers the corruption already touched."""
        self._corruptions = 0

    def _corrupt_exchanged(self, y):
        """Corruption of an all_to_all/ppermute result as the receiver
        sees it. 1-D int32 (n,) vectors are the padded shuffle's count
        exchange (truncate/duplicate seam: the target rank's received
        count for one sender slips by 1); >=2-D blocks are the data
        plane (bit_flip / misroute seams)."""
        mode = self.plan.corrupt_mode
        if mode is None:
            return y
        n = self.n_ranks
        # The count exchange: the padded shuffle's (n,) vector, or the
        # hierarchical route's (slices, chips)-nested view of the same
        # n counts (shuffle._hier_route — an int32 block of exactly n
        # entries on the tier seams).
        is_counts = (y.dtype == jnp.int32 and y.size == n
                     and y.ndim in (1, 2))
        if (mode in ("row_truncate", "row_duplicate") and is_counts
                and self._corrupt_budget()):
            j = (self.plan.seed // n) % n
            delta = jnp.int32(-1 if mode == "row_truncate" else 1)
            active = (self.axis_index()
                      == jnp.int32(self._corrupt_rank()))
            flat = y.reshape(-1)
            flat = flat.at[j].add(delta * active.astype(jnp.int32))
            return jnp.maximum(flat, 0).reshape(y.shape)
        if mode == "bit_flip" and y.ndim >= 2 \
                and self._corrupt_budget():
            active = (self.axis_index()
                      == jnp.int32(self._corrupt_rank()))
            return _flip_one_bit(y, self.plan.seed, active)
        if mode == "misroute" and y.ndim >= 2 and n > 1 \
                and self._corrupt_budget():
            # The received sender-block axis rotates by one on the
            # target rank: every block is attributed to the wrong
            # source — rows that hash elsewhere enter the local join.
            active = (self.axis_index()
                      == jnp.int32(self._corrupt_rank()))
            return jnp.where(active, jnp.roll(y, 1, axis=0), y)
        return y

    def ragged_all_to_all(self, operand, output, input_offsets,
                          send_sizes, output_offsets, recv_sizes):
        mode = self.plan.corrupt_mode
        n = self.n_ranks
        if mode == "misroute" and n > 1 and self._corrupt_budget():
            # The target SENDER reads two destinations' rows from each
            # other's bucket offsets — its rows land at wrong ranks.
            d1 = self.plan.seed % n
            d2 = (d1 + 1 + (self.plan.seed // n) % (n - 1)) % n
            swapped = input_offsets.at[d1].set(
                input_offsets[d2]).at[d2].set(input_offsets[d1])
            active = (self.axis_index()
                      == jnp.int32(self._corrupt_rank()))
            input_offsets = jnp.where(active, swapped, input_offsets)
        out = self._inner.ragged_all_to_all(
            operand, output, input_offsets, send_sizes,
            output_offsets, recv_sizes,
        )
        if mode == "bit_flip" and self._corrupt_budget():
            active = (self.axis_index()
                      == jnp.int32(self._corrupt_rank()))
            out = _flip_one_bit(out, self.plan.seed, active)
        return out

    def all_gather(self, x):
        g = self._inner.all_gather(x)
        if (self.plan.corrupt_mode in ("row_truncate", "row_duplicate")
                and x.ndim == 1 and x.dtype == jnp.int32
                and x.shape[0] == self.n_ranks
                and self._corrupt_budget()):
            # The ragged plan's count-matrix gather, perturbed
            # IDENTICALLY on every rank: a consistent lie about how
            # many rows the target sender routes to one destination.
            # validate_ragged_plan only checks cross-rank consistency,
            # so this sails through it — the wire digests are the only
            # layer that can catch it (sender digests commit to the
            # TRUE local counts before any exchange).
            n = self.n_ranks
            row = self._corrupt_rank()
            col = (self.plan.seed // n) % n
            delta = -1 if self.plan.corrupt_mode == "row_truncate" \
                else 1
            g2 = g.reshape(n, n).at[row, col].add(jnp.int32(delta))
            g = jnp.maximum(g2, 0).reshape(g.shape)
        if (x.ndim == 1 and x.dtype == jnp.int32
                and x.shape[0] == self.n_ranks
                and self._plan_gathers < self.plan.corrupt_plan_gathers):
            # The ragged plan's count-vector gather (shuffle.py
            # _ragged_plan_matrices). Perturb rank-dependently: every
            # rank sees a DIFFERENT count matrix, the defining
            # property of a corrupted/racing metadata exchange. Rank 0
            # adds 0 — inconsistency, not a uniform shift.
            self._plan_gathers += 1
            n = self.n_ranks
            row = self.plan.seed % n
            me = self.axis_index()
            g2 = g.reshape(n, n)
            g2 = g2.at[row].add(me.astype(g2.dtype))
            g = g2.reshape(g.shape)
        return g

    def spmd(self, fn: Callable, *, sharded_out=None) -> Callable:
        idx = self._programs_built
        self._programs_built += 1
        inject_overflow = idx < self.plan.overflow_programs

        def wrapped(*args):
            out = fn(*args)
            if inject_overflow:
                if isinstance(out, JoinResult):
                    out = dataclasses.replace(
                        out, overflow=out.overflow | jnp.bool_(True)
                    )
                elif (isinstance(out, tuple) and out
                      and isinstance(out[0], JoinResult)):
                    # The telemetry-instrumented step returns
                    # (JoinResult, Metrics); the squeeze must look the
                    # same to the ladder either way.
                    out = (dataclasses.replace(
                        out[0],
                        overflow=out[0].overflow | jnp.bool_(True),
                    ),) + out[1:]
            return out

        compiled = self._inner.spmd(wrapped, sharded_out=sharded_out)

        def dispatch(*args, **kwargs):
            self._dispatches += 1
            if self.plan.dispatch_delay_s and self._dispatches > (
                    self.plan.delay_after_dispatches or 0):
                time.sleep(self.plan.dispatch_delay_s)
            if self._dispatches <= self.plan.fail_dispatches:
                raise FaultInjectedError(
                    f"injected dispatch failure #{self._dispatches} "
                    f"(fail_dispatches={self.plan.fail_dispatches})"
                )
            if self._dispatches in (self.plan.drop_dispatches or ()):
                raise FaultInjectedError(
                    f"injected dispatch drop #{self._dispatches} "
                    f"(drop_dispatches={self.plan.drop_dispatches})"
                )
            after = self.plan.fail_after_dispatches
            if after is not None and self._dispatches > after:
                raise FaultInjectedError(
                    f"injected persistent outage: dispatch "
                    f"#{self._dispatches} > fail_after_dispatches={after}"
                )
            return compiled(*args, **kwargs)

        return dispatch


def _flip_one_bit(block, seed: int, active):
    """One bit of one (seed-addressed) element of ``block`` flips where
    ``active`` (a traced bool — the corrupt rank predicate) holds: the
    minimal in-flight payload corruption. Integer dtypes (and f32, via
    bitcast) flip a real bit; f64 — whose bitcast the TPU x64 rewriter
    can't lower — degrades to an additive nudge, which serves the same
    adversarial purpose."""
    dt = block.dtype
    if jnp.issubdtype(dt, jnp.floating):
        if dt == jnp.float32:
            u = jax.lax.bitcast_convert_type(block, jnp.uint32)
            return jax.lax.bitcast_convert_type(
                _flip_one_bit(u, seed, active), dt)
        flat = block.reshape(-1)
        idx = seed % flat.shape[0]
        bumped = flat.at[idx].add(jnp.where(active, dt.type(1.0),
                                            dt.type(0.0)))
        return bumped.reshape(block.shape)
    flat = block.reshape(-1)
    idx = seed % flat.shape[0]
    nbits = dt.itemsize * 8
    bit = (seed // max(flat.shape[0], 1)) % nbits
    # left_shift wraps into the sign bit for bit == nbits-1 — exactly a
    # bit flip there too (two's complement).
    mask = jnp.left_shift(jnp.asarray(1, dt), bit)
    flipped = flat.at[idx].set(
        jnp.where(active, flat[idx] ^ mask, flat[idx]))
    return flipped.reshape(block.shape)


# -- ragged-plan validation -------------------------------------------

_PLAN_VALIDATION: Optional[bool] = None  # None -> env decides


def plan_validation_enabled() -> bool:
    """Whether :func:`validate_ragged_plan` should be woven into newly
    TRACED ragged shuffles (already-compiled programs are unaffected)."""
    if _PLAN_VALIDATION is not None:
        return _PLAN_VALIDATION
    return os.environ.get("DJTPU_VALIDATE_PLANS", "") not in ("", "0")


@contextmanager
def validate_plans(enabled: bool = True):
    """Force plan validation on (or off) for programs traced inside the
    context — the test/debug switch; production uses the
    ``DJTPU_VALIDATE_PLANS`` env var."""
    global _PLAN_VALIDATION
    prev = _PLAN_VALIDATION
    _PLAN_VALIDATION = enabled
    try:
        yield
    finally:
        _PLAN_VALIDATION = prev


_plan_violations: list = []


def plan_violations() -> list:
    """Messages recorded by validation callbacks since the last
    :func:`check_plan_violations` (newest last)."""
    return list(_plan_violations)


def clear_plan_violations() -> None:
    """Drop recorded violations. The record list is process-global, so
    a harness that is about to attribute violations to ONE program
    (``distributed_inner_join`` does, before each attempt) must clear
    leftovers from earlier programs whose caller never checked —
    otherwise a stale message fails a healthy join."""
    _plan_violations.clear()


def check_plan_violations(clear: bool = True) -> None:
    """Raise :class:`PlanValidationError` if any validated program
    observed an inconsistent plan. Call after CONSUMING the program's
    outputs (the callback runs with the program; consuming any output
    array sequences after it). ``distributed_inner_join`` calls this
    for you after every attempt when validation is enabled."""
    if not _plan_violations:
        return
    msg = "; ".join(_plan_violations)
    if clear:
        _plan_violations.clear()
    raise PlanValidationError(msg)


def _plan_check_host(ok, where="shuffle_ragged"):
    import numpy as np

    if bool(ok):
        return np.int32(0)
    import warnings

    msg = (
        f"ragged plan inconsistent across ranks in {where}: "
        "send/recv/offset vectors disagree — the exchange would "
        "corrupt rows (emulation) or hang (TPU hardware op). "
        "A rank computed its plan from a different count matrix; "
        "suspect a corrupted/raced metadata all-gather."
    )
    # Record + warn + trip the overflow flag (the returned 1), never
    # raise: an exception inside a backend callback poisons the
    # process-wide dispatch stream. check_plan_violations() is the
    # raise point.
    _plan_violations.append(msg)
    warnings.warn(msg, stacklevel=2)
    return np.int32(1)


def validate_ragged_plan(comm: Communicator, send_sizes, recv_sizes,
                         output_offsets, out_capacity: int,
                         where: str = "shuffle_ragged"):
    """Cross-rank consistency check of a ragged transfer plan.

    Every rank all-gathers its (send_sizes, recv_sizes,
    output_offsets) triple and re-derives what its peers must hold;
    ``lax.ragged_all_to_all``'s contract requires these vectors to be
    MUTUALLY consistent across ranks, and nothing on the data path
    checks it. Verified invariants (identical math on every rank):

    - transpose consistency: rank j's ``send_sizes[i]`` equals rank
      i's ``recv_sizes[j]`` — what one side sends the other expects;
    - bounds: sizes non-negative, every write interval
      ``[offset, offset + send)`` within ``out_capacity``;
    - receiver packing: on each receiver, sender blocks are disjoint
      and orderly — offsets non-decreasing in sender rank with at
      least the clamped received rows between consecutive offsets.

    Returns an int32 scalar token (0 = consistent, 1 = violated) that
    the caller must fold into a live output so the check cannot be
    dead-code-eliminated — the ragged shuffle ORs it into its overflow
    flag, so a corrupted plan also reads as "do not trust this
    result". On violation the embedded host callback records the
    message (see :func:`check_plan_violations`, the raise point) and
    emits a warning; it deliberately does NOT raise in-program — a
    callback exception poisons the backend's process-wide dispatch
    stream. Cost: one (n, 3n) int32 all-gather plus O(n^2) scalar
    math — debug mode only.
    """
    n = comm.n_ranks
    me = comm.axis_index()
    mine = jnp.stack([
        send_sizes.astype(jnp.int32),
        recv_sizes.astype(jnp.int32),
        output_offsets.astype(jnp.int32),
    ], axis=0)                                       # (3, n) — 2-D, so
    # FaultPlan.corrupt_plan_gathers' 1-D predicate never corrupts the
    # validation gather itself.
    g = comm.all_gather(mine.reshape(1, 3 * n)).reshape(n, 3, n)
    g_send, g_recv, g_off = g[:, 0, :], g[:, 1, :], g[:, 2, :]

    ok = jnp.bool_(True)
    # transpose consistency: G_send[j, i] == G_recv[i, j]
    ok = ok & jnp.all(g_send == g_recv.T)
    # bounds
    ok = ok & jnp.all(g_send >= 0) & jnp.all(g_recv >= 0)
    ok = ok & jnp.all(g_off >= 0)
    # Bounds apply only to senders that actually transfer: offsets are
    # the UNclamped receiver-side prefix starts, so a sender squeezed
    # out entirely by a capacity clamp legitimately carries
    # start > out_capacity with send == 0 — a zero-size transfer at
    # any offset is valid, and flagging it would turn every
    # recoverable overflow into a phantom "corrupted plan".
    ok = ok & jnp.all(jnp.where(g_send > 0,
                                g_off + g_send <= out_capacity, True))
    # receiver packing: per receiver i, sender offsets non-decreasing
    # with at least the received rows between consecutive blocks.
    # g_off[j, i] is where sender j's block starts on receiver i.
    off_r = g_off.T                                  # (receiver, sender)
    recv_r = g_recv                                  # (receiver, sender)
    gap_ok = off_r[:, 1:] >= off_r[:, :-1] + recv_r[:, :-1]
    ok = ok & jnp.all(gap_ok)
    # A locally-consistent view can still differ from a peer's; the
    # transpose check above catches that, but only if the gathers
    # themselves delivered each rank's true vectors — psum the verdict
    # so ONE unhappy rank fails everyone deterministically.
    n_bad = comm.psum((~ok).astype(jnp.int32))
    ok_global = n_bad == 0
    from functools import partial

    tok = jax.pure_callback(
        partial(_plan_check_host, where=where),
        jax.ShapeDtypeStruct((), jnp.int32),
        ok_global,
    )
    return tok


# -- retry with backoff (bootstrap + generic transient failures) ------


def retry_with_backoff(
    fn: Callable,
    *,
    max_attempts: int = 3,
    backoff_s: float = 1.0,
    backoff_factor: float = 2.0,
    deadline_s: Optional[float] = None,
    retry_on=(Exception,),
    sleep: Callable[[float], None] = time.sleep,
    clock: Callable[[], float] = time.monotonic,
    on_retry: Optional[Callable] = None,
):
    """Call ``fn()`` with exponential backoff on failure.

    Returns ``(result, attempts)`` where ``attempts`` is a list of
    per-attempt records ``{"attempt", "elapsed_s", "error"}`` (error is
    None on the success entry) — the machine-readable trail
    :class:`distributed_join_tpu.parallel.bootstrap.BootstrapError`
    embeds in driver JSON output. Raises the LAST error (unwrapped,
    with the trail attached as ``exc._retry_attempts``) when attempts
    or the deadline run out; callers wrap it in their domain error.
    ``sleep``/``clock`` are injectable for tests.
    """
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")
    t0 = clock()
    attempts = []
    delay = backoff_s
    last = None
    for attempt in range(max_attempts):
        ta = clock()
        try:
            result = fn()
            attempts.append({"attempt": attempt,
                             "elapsed_s": clock() - ta, "error": None})
            return result, attempts
        except retry_on as exc:  # noqa: PERF203 - retry loop
            last = exc
            attempts.append({
                "attempt": attempt,
                "elapsed_s": clock() - ta,
                "error": f"{type(exc).__name__}: {exc}",
            })
            out_of_time = (
                deadline_s is not None
                and clock() - t0 + delay > deadline_s
            )
            if attempt == max_attempts - 1 or out_of_time:
                break
            if on_retry is not None:
                on_retry(attempt, exc, delay)
            sleep(delay)
            delay *= backoff_factor
    last._retry_attempts = attempts
    raise last


# -- the auto_retry capacity ladder, reified --------------------------


@dataclasses.dataclass(frozen=True)
class RetryAttempt:
    """One rung of the ladder: the sizing that ran and what happened.
    ``action`` is what produced this attempt's sizing ("initial",
    "widen_compression_bits", "double_capacities", or
    "retry_integrity" — a same-sizing rerun after a wire-integrity
    mismatch). ``integrity_ok`` is the digest verdict when the attempt
    was verified (None: verification off, or skipped on overflow)."""

    attempt: int
    action: str
    overflow: Optional[bool]           # None: never ran (ladder abandoned)
    shuffle_capacity_factor: float
    out_capacity_factor: float
    out_rows_per_rank: Optional[int]
    compression_bits: Optional[int]
    hh_build_capacity: Optional[int]
    hh_probe_capacity: Optional[int]
    hh_out_capacity: Optional[int]
    integrity_ok: Optional[bool] = None

    def as_record(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class RetryReport:
    """The full retry trail of one ``auto_retry`` join — which
    capacities doubled, why, per attempt. Attached host-side to the
    ``JoinResult`` of :func:`..distributed_join.distributed_inner_join`
    (as ``res.retry_report``) and embedded in benchmark driver JSON."""

    attempts: tuple

    @property
    def n_attempts(self) -> int:
        return len(self.attempts)

    @property
    def resolved(self) -> Optional[bool]:
        """True when the final attempt ran clean, False when it still
        overflowed, None when nothing ran."""
        if not self.attempts:
            return None
        last = self.attempts[-1].overflow
        return None if last is None else not last

    def as_record(self) -> Optional[dict]:
        """JSON-shaped record (None when the join ran once, clean,
        from rung 0 — so drivers can emit ``"retry": null`` for the
        common case). A tuner-seeded ladder (``base_rung > 0``) keeps
        its record even for a single clean attempt: the rung label
        and its sizing ARE the information the workload-history store
        persists for the next pre-size."""
        if self.n_attempts <= 1 and self.resolved and (
                not self.attempts or self.attempts[0].attempt == 0):
            return None
        return {
            "n_attempts": self.n_attempts,
            "resolved": self.resolved,
            "attempts": [a.as_record() for a in self.attempts],
        }


class CapacityLadder:
    """The overflow-escalation policy behind ``auto_retry``, extracted
    so every harness (``distributed_inner_join``, the benchmark
    drivers, ``bench.py``) escalates identically and the decisions are
    reportable.

    Policy (unchanged from the historical inline loop):

    1. compression first — a codec-width overflow is indistinguishable
       from a capacity overflow on the flag, and widening bits is the
       CHEAP axis (at most 3 bits-only recompiles 4->8->16->32) versus
       inflating every buffer up to 8x for nothing (review r4);
    2. then double every capacity a retry can relieve: the shuffle and
       output factors, ``out_rows_per_rank`` when set (it supersedes
       the factor), and — when the skew path is on — the HH capacities,
       which JUMP straight to full local probe coverage rather than
       creeping, because one retry must cover ANY skew (alpha >= 1.4
       puts ~90% of probe rows in the HH set).
    """

    def __init__(self, *, shuffle_capacity_factor: float,
                 out_capacity_factor: float,
                 out_rows_per_rank: Optional[int] = None,
                 compression_bits: Optional[int] = None,
                 skew: bool = False,
                 hh_build_capacity: Optional[int] = None,
                 hh_probe_capacity: Optional[int] = None,
                 hh_out_capacity: Optional[int] = None,
                 local_probe_rows: Optional[int] = None,
                 base_rung: int = 0):
        self.shuffle_f = shuffle_capacity_factor
        self.out_f = out_capacity_factor
        self.out_rows = out_rows_per_rank
        self.bits = compression_bits
        self.skew = skew
        self.hh_build = hh_build_capacity
        self.hh_probe = hh_probe_capacity
        self.hh_out = hh_out_capacity
        self.p_local = local_probe_rows
        # Rung-label offset for a history-pre-sized ladder (the
        # autotuner, planning/tuner.py): a warm run starting at the
        # sizing a cold run escalated to carries the SAME absolute
        # rung label, so its program signature equals the executable
        # already resident in the cache — the zero-retrace contract.
        self.base_rung = base_rung
        self._action = ("initial" if base_rung == 0
                        else "tuned_presize")
        self._attempts: list = []

    @property
    def next_rung(self) -> int:
        """Absolute rung label of the attempt about to run."""
        return self.base_rung + len(self._attempts)

    def seed_rung(self, rung: int) -> None:
        """Start the ladder at an absolute rung label (the autotuner
        pre-sized the knobs to a rung a previous run escalated to;
        the sizing itself was already applied to the ladder's
        construction kwargs). No-op for rung 0."""
        if rung:
            self.base_rung = int(rung)
            self._action = "tuned_presize"

    def sizing(self) -> dict:
        """Keyword arguments for ``make_join_step`` /
        ``make_distributed_join`` at the current rung."""
        return dict(
            shuffle_capacity_factor=self.shuffle_f,
            out_capacity_factor=self.out_f,
            out_rows_per_rank=self.out_rows,
            compression_bits=self.bits,
            hh_build_capacity=self.hh_build,
            hh_probe_capacity=self.hh_probe,
            hh_out_capacity=self.hh_out,
        )

    def note(self, overflow: Optional[bool],
             integrity_ok: Optional[bool] = None) -> None:
        """Record the outcome of running the current rung. The attempt
        also lands in the telemetry event log (the RetryReport's
        per-attempt record, streamed as it happens — a killed run
        keeps the trail its report would have carried)."""
        att = RetryAttempt(
            attempt=self.base_rung + len(self._attempts),
            action=self._action,
            overflow=overflow,
            shuffle_capacity_factor=self.shuffle_f,
            out_capacity_factor=self.out_f,
            out_rows_per_rank=self.out_rows,
            compression_bits=self.bits,
            hh_build_capacity=self.hh_build,
            hh_probe_capacity=self.hh_probe,
            hh_out_capacity=self.hh_out,
            integrity_ok=integrity_ok,
        )
        self._attempts.append(att)
        from distributed_join_tpu import telemetry

        telemetry.event("retry_attempt", **att.as_record())

    def escalate(self) -> str:
        """Advance one rung; returns the action taken."""
        if self.bits is not None and self.bits < 32:
            self.bits = min(self.bits * 2, 32)
            self._action = "widen_compression_bits"
            return self._action
        self.shuffle_f *= 2.0
        self.out_f *= 2.0
        if self.out_rows is not None:
            self.out_rows *= 2
        if self.skew:
            if self.hh_build is not None:
                self.hh_build *= 2
            if self.hh_probe is not None:
                self.hh_probe = (max(self.hh_probe * 2, self.p_local)
                                 if self.p_local else self.hh_probe * 2)
            if self.hh_out is not None:
                self.hh_out = (max(self.hh_out * 2, self.p_local)
                               if self.p_local else self.hh_out * 2)
        self._action = "double_capacities"
        return self._action

    def hold(self, action: str = "retry_integrity") -> str:
        """Advance to a rung with the SAME sizing — the retry that
        answers a wire-integrity mismatch (corruption is transient;
        the capacities were right) rather than an overflow. The rerun
        still recompiles, so a finite injected corruption budget
        (FaultPlan.corrupt_collectives) exhausts across holds."""
        self._action = action
        return self._action

    def report(self) -> RetryReport:
        return RetryReport(attempts=tuple(self._attempts))


# -- out-of-core progress manifest ------------------------------------


class ManifestMismatchError(RuntimeError):
    """An existing manifest describes a DIFFERENT run (batch count,
    capacities, or per-batch row counts changed) — resuming against it
    would silently merge unrelated partial results."""


class JoinManifest:
    """Durable per-batch progress for the out-of-core join loop.

    One JSON file, rewritten atomically (tmp + ``os.replace``) after
    every batch completes, holding the run's config fingerprint, each
    completed batch's exact match total + overflow flag, and a bounded
    failure log. A killed run resumes from the first incomplete batch;
    matching keys land in the same batch on both sides, so batch totals
    are independent and the resumed sum is bit-exact (the acceptance
    contract of this layer). Format documented in
    docs/FAILURE_SEMANTICS.md.
    """

    VERSION = 1
    MAX_FAILURES = 50

    def __init__(self, path: str, config: dict,
                 on_mismatch: str = "raise"):
        """Load (and verify) an existing manifest at ``path`` or start
        a fresh one. ``on_mismatch``: "raise" (default) or "restart" —
        discard the stale manifest and start over."""
        self.path = path
        self.config = config
        self._data = {"version": self.VERSION, "config": config,
                      "batches": {}, "failures": []}
        if os.path.exists(path):
            with open(path) as f:
                existing = json.load(f)
            if (existing.get("version") != self.VERSION
                    or existing.get("config") != config):
                if on_mismatch == "restart":
                    self._write()
                else:
                    raise ManifestMismatchError(
                        f"manifest {path} was written by a different "
                        f"run config; refusing to resume against it "
                        f"(have {existing.get('config')!r}, "
                        f"want {config!r}). Delete the file or pass "
                        "a fresh manifest path to start over."
                    )
            else:
                self._data = existing
        else:
            self._write()

    @property
    def completed(self) -> dict:
        """{batch_id (int): {"total": int, "overflow": bool}}"""
        return {int(k): v for k, v in self._data["batches"].items()}

    @property
    def failures(self) -> list:
        return list(self._data["failures"])

    def record_batch(self, batch: int, total: int,
                     overflow: bool) -> None:
        self._data["batches"][str(batch)] = {
            "total": int(total), "overflow": bool(overflow),
        }
        self._write()
        from distributed_join_tpu import telemetry

        telemetry.event("manifest_batch", path=self.path,
                        batch=int(batch), total=int(total),
                        overflow=bool(overflow))

    def record_failure(self, batch: int, error: str,
                       attempt: int) -> None:
        log = self._data["failures"]
        log.append({"batch": int(batch), "attempt": int(attempt),
                    "error": error})
        del log[:-self.MAX_FAILURES]
        self._write()
        from distributed_join_tpu import telemetry

        telemetry.event("manifest_failure", path=self.path,
                        batch=int(batch), attempt=int(attempt),
                        error=error)

    def _write(self) -> None:
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._data, f, indent=1)
        os.replace(tmp, self.path)


def batch_config_fingerprint(build_batches: Sequence, probe_batches:
                             Sequence, n_ranks: int, key,
                             bcap: int, pcap: int) -> dict:
    """The identity a manifest binds to: resuming only makes sense
    against the SAME batching of the same tables, and per-batch row
    counts are a cheap, order-sensitive witness of that."""
    return {
        "n_batches": len(build_batches),
        "n_ranks": int(n_ranks),
        "key": list(key) if isinstance(key, (list, tuple)) else key,
        "build_capacity": int(bcap),
        "probe_capacity": int(pcap),
        "build_rows": [int(next(iter(b.values())).shape[0])
                       for b in build_batches],
        "probe_rows": [int(next(iter(b.values())).shape[0])
                       for b in probe_batches],
    }
