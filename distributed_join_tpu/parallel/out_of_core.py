"""Out-of-core key-range batching — tables bigger than HBM.

The reference's over-decomposition has a second job beyond pipelining:
only ``1/k`` of the *shuffled* data is resident at once (SURVEY.md §5
"Long-context"). But its inputs still live wholly in device memory, and
so do ours inside one compiled step. For tables that exceed HBM
entirely (TPC-H SF-100 lineitem is ~600M rows), this module batches the
*key space* on the host: rows are split into ``n_batches`` by key hash
(the same Murmur3 finalizer the device kernels use — numpy mirror
below), and each co-partitioned batch pair runs through the compiled
distributed join independently. Matching keys share a hash, hence a
batch, so batch joins are independent and their totals sum.

This is the framework's answer to the reference's "tables larger than
per-chip HBM" axis; the host loop costs one H2D transfer per batch,
overlapped with device compute by :func:`batched_join_host`'s staging
thread.
"""

from __future__ import annotations

import time
import warnings
from typing import Callable, Optional, Tuple

import numpy as np

from distributed_join_tpu import telemetry
from distributed_join_tpu.parallel.communicator import Communicator
from distributed_join_tpu.table import Table


def fmix64_np(x: np.ndarray) -> np.ndarray:
    """numpy mirror of ops.hashing.fmix64 (same constants) so host-side
    batching agrees with device-side bucket routing."""
    k = x.astype(np.uint64)
    k ^= k >> np.uint64(33)
    k *= np.uint64(0xFF51AFD7ED558CCD)
    k ^= k >> np.uint64(33)
    k *= np.uint64(0xC4CEB9FE1A85EC53)
    k ^= k >> np.uint64(33)
    return k


def hash_combine_np(seed: np.ndarray, h: np.ndarray) -> np.ndarray:
    """numpy mirror of ops.hashing.hash_combine."""
    magic = np.uint64(0x9E3779B97F4A7C15)
    return seed ^ (
        h + magic + (seed << np.uint64(6)) + (seed >> np.uint64(2))
    )


def fmix32_np(x: np.ndarray) -> np.ndarray:
    """numpy mirror of ops.hashing.fmix32."""
    h = x.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def _hash_one_np(col: np.ndarray) -> np.ndarray:
    """numpy mirror of ops.hashing._hash_one's per-dtype dispatch.

    Bit-exact with the device hash for integer and float32 keys. For
    float64 the mirror follows the same arithmetic decomposition, but
    np.log2/np.exp2 and XLA's may differ in the last ulp near powers of
    two, so cross-layer agreement is NOT guaranteed for f64 — batch
    correctness only needs host-internal consistency (both sides of a
    key batch by the same host hash), which always holds."""
    dt = col.dtype
    if dt in (np.dtype(np.int64), np.dtype(np.uint64)):
        return fmix64_np(col)
    if dt in (np.dtype(t) for t in
              (np.int32, np.uint32, np.int16, np.uint16, np.int8, np.uint8)):
        return fmix32_np(col).astype(np.uint64)
    if dt == np.dtype(np.float64):
        # Mirrors the device's arithmetic f64 decomposition (hashing.py
        # _hash_one): |x| = m * 2**e with m in [1, 2), mantissa scaled
        # to 52 bits; sign folded into the exponent hash.
        a = np.abs(col)
        with np.errstate(divide="ignore"):
            e = np.where(a > 0, np.floor(np.log2(a)), 0.0)
        m = np.where(a > 0, a / np.exp2(e), 0.0)
        mi = (m * (2.0 ** 52)).astype(np.int64).astype(np.uint64)
        ebits = e.astype(np.int32) ^ (col < 0).astype(np.int32) << 30
        return hash_combine_np(fmix64_np(mi), fmix32_np(ebits).astype(np.uint64))
    if dt == np.dtype(np.float32):
        return fmix32_np(col.view(np.uint32)).astype(np.uint64)
    raise TypeError(f"unhashable key dtype {dt}")


def hash_columns_np(cols) -> np.ndarray:
    """numpy mirror of ops.hashing.hash_columns (composite keys batch by
    the combined hash); per-dtype dispatch matches the device exactly."""
    acc = _hash_one_np(cols[0])
    for c in cols[1:]:
        acc = hash_combine_np(acc, _hash_one_np(c))
    return acc


def key_batch_ids(keys, n_batches: int) -> np.ndarray:
    """Batch id per row; ``keys`` is one array or a list of composite
    key columns. Uses the UPPER hash bits so batching composes with the
    device kernels' ``hash % n_buckets`` routing (lower bits): the two
    partitions stay independent, and every key pair that joins lands in
    the same batch on both sides."""
    cols = keys if isinstance(keys, (list, tuple)) else [keys]
    h = hash_columns_np([np.asarray(c) for c in cols])
    # Re-mix before taking the upper bits: 32-bit key dtypes hash via
    # fmix32 widened to uint64, whose top 32 bits are all zero — without
    # this pass every row of such a column would land in batch 0.
    h = fmix64_np(h)
    return ((h >> np.uint64(40)) % np.uint64(n_batches)).astype(np.int64)


def _host_columns(table: Table) -> dict:
    mask = np.asarray(table.valid)
    return {n: np.asarray(c)[mask] for n, c in table.columns.items()}


def _pad_host(cols: dict, cap: int) -> Table:
    """Host columns -> fixed-capacity HOST Table (prefix-valid). Leaves
    stay numpy so the subsequent ``device_put_sharded`` performs the one
    and only H2D transfer, sharded — materializing on the default
    device here would bounce every batch through one chip's HBM."""
    m = next(iter(cols.values())).shape[0]
    out = {}
    for name, c in cols.items():
        buf = np.zeros((cap,) + c.shape[1:], dtype=c.dtype)
        buf[:m] = c
        out[name] = buf
    return Table(out, np.arange(cap) < m)


def batched_join_host(
    build_batches,
    probe_batches,
    comm: Communicator,
    key: str = "key",
    warmup: bool = True,
    stats: Optional[dict] = None,
    on_batch_result: Optional[Callable] = None,
    manifest_path: Optional[str] = None,
    batch_retries: int = 0,
    batch_retry_backoff_s: float = 1.0,
    on_batch_failure: str = "raise",
    verify_integrity: bool = False,
    batch_deadline_s: Optional[float] = None,
    **join_opts,
) -> Tuple[int, bool]:
    """Join pre-binned HOST batches (lists of numpy column dicts, e.g.
    from :func:`..utils.tpch_host.generate_tpch_host_batches`) with
    one-batch-ahead H2D staging; returns (total_matches, any_overflow).

    Failure semantics (docs/FAILURE_SEMANTICS.md):

    - ``manifest_path``: a per-batch progress manifest
      (:class:`..faults.JoinManifest`) written atomically after each
      batch's exact total is known. A killed run re-invoked with the
      same arguments resumes from the first incomplete batch — batches
      are key-disjoint, so the resumed sum is bit-exact with the
      uninterrupted run. A manifest written by a DIFFERENT batching
      (row counts, capacities, key, rank count) is refused.
    - ``batch_retries``: per-batch dispatch retries before a batch is
      declared failed (transient launch/collective failures), with
      exponential backoff starting at ``batch_retry_backoff_s`` —
      back-to-back re-dispatches would burn the whole budget inside a
      still-live transient outage.
    - ``on_batch_failure``: "raise" (default) propagates a batch's
      final failure; "continue" degrades gracefully — the batch id is
      recorded in ``stats['failed_batches']`` (and the manifest's
      failure log) and the join returns PARTIAL totals over the
      batches that did complete, instead of crashing an hours-long
      out-of-core run on one bad batch.
    - ``stats`` additionally receives ``resumed_batches`` (ids skipped
      via the manifest) and ``failed_batches``.
    - ``verify_integrity``: each batch's join carries the in-graph
      wire digests (parallel/integrity.py; the compiled program's aux
      Metrics block) and is verified at its settle point. A mismatch
      is a batch failure under the same contract as a dispatch
      failure: ``"raise"`` propagates the
      :class:`..integrity.IntegrityError`; ``"continue"`` abandons the
      batch (its corrupt total is NOT counted) and records it in
      ``failed_batches`` + the manifest's failure log — never folded
      silently into the returned total.
    - ``batch_deadline_s``: bound each batch's result fetch under the
      shared hang watchdog (:mod:`..watchdog`): a deadlocked
      collective (or a wedged backend) surfaces as a structured
      ``HangError`` batch failure — same degradation contract —
      instead of blocking the loop forever. Worker teardown on the
      error path is also bounded (``watchdog.shutdown_bounded``), so
      an orphaned stage/fetch worker reports a
      ``worker_shutdown_timeout`` event rather than hanging the
      interpreter at exit.

    This is the out-of-core hot path (VERDICT r1 weak #5: the r1 loop
    was fully serial). The pipeline, per loop iteration:

      1. batch b's join is DISPATCHED (async under JAX);
      2. batch b+1's pad + H2D transfer starts on the staging thread;
      3. batch b's RESULT leaves the device on a second worker thread
         (round 5 — the D2H side of VERDICT r4 weak #2): when
         ``on_batch_result`` is given it runs there, in batch order,
         overlapping batch b+1's compute the same way the staging
         thread overlaps H2D. Backpressure: before dispatching batch
         b+1 the loop waits for batch b-1's fetch (or, with no
         consumer, fetches b-1's match count) — batch b+2 cannot
         stage until b-1 has finished and its buffers are freeable,
         which bounds device residency at ~3 batches of inputs + ~2
         output blocks regardless of ``n_batches`` (without
         backpressure, a fast host would stage EVERY batch while
         batch 0 still computes and OOM at exactly the scale this
         path exists for). Size ``n_batches`` so three batches of
         inputs and two output blocks fit HBM.

    The reference overlaps comm/compute with CUDA streams + helper
    threads (SURVEY.md §2 "Over-decomposition"); here a single staging
    THREAD does the same job: measured phase timings showed
    ``jax.device_put`` of host batches is effectively synchronous (at
    SF-10 the phase sums equaled the elapsed time — zero overlap), so
    batch b+1's pad+transfer runs on a worker thread while this thread
    waits on batch b-1's result. numpy copies and the transfer both
    release the GIL, so the overlap is real even on a 1-CPU host.

    Every batch runs through ONE compiled join (capacities = max batch
    rows, rank-rounded), so there is exactly one XLA compile.

    Timing note: with ``warmup`` the already-staged batch 0 is reused
    as the measured loop's first input, so its H2D falls outside
    ``stats['elapsed_s']`` — an undercount of at most 1/n_batches of
    the staging cost (vs double-staging batch 0, which overcounted).
    """
    from distributed_join_tpu.parallel.distributed_join import (
        make_distributed_join,
    )
    from distributed_join_tpu.parallel.faults import (
        JoinManifest,
        batch_config_fingerprint,
    )

    if len(build_batches) != len(probe_batches):
        raise ValueError("build/probe batch counts differ")
    if on_batch_failure not in ("raise", "continue"):
        raise ValueError(
            f"on_batch_failure must be 'raise' or 'continue', "
            f"got {on_batch_failure!r}"
        )
    n_batches = len(build_batches)
    n = comm.n_ranks

    def _cap(batches):
        c = max(next(iter(b.values())).shape[0] for b in batches)
        return max(-(-c // n) * n, n)

    bcap, pcap = _cap(build_batches), _cap(probe_batches)

    manifest = None
    completed: dict = {}
    if manifest_path is not None:
        manifest = JoinManifest(
            manifest_path,
            batch_config_fingerprint(build_batches, probe_batches,
                                     n, key, bcap, pcap),
        )
        # An overflowed batch's recorded TOTAL is exact, but its
        # materialized rows were truncated — and the natural resume
        # after an overflowing run is "re-invoke with bigger
        # capacities against the same manifest" (join sizing options
        # are deliberately NOT in the fingerprint). So overflowed
        # entries count as incomplete and re-run; record_batch
        # overwrites them.
        completed = {b: v for b, v in manifest.completed.items()
                     if not v["overflow"]}
        if completed and on_batch_result is not None:
            warnings.warn(
                "resuming from a manifest: on_batch_result will not "
                f"be called for already-completed batches "
                f"{sorted(completed)} — the consumer's stream covers "
                "only batches run in THIS invocation, though the "
                "returned total covers all of them",
                stacklevel=2,
            )
    # Batches still to run, in order; capacities stay computed over ALL
    # batches so a resumed run compiles the identical program.
    pending = [b for b in range(n_batches) if b not in completed]
    failed: set = set()

    # fetch_s: time actually spent pulling results (on the fetch
    # worker when a consumer is installed — HIDDEN behind compute);
    # fetch_wait_s: time the MAIN loop blocked on a fetch — the
    # UNHIDDEN remainder, the number that shows whether the overlap
    # worked. Only the fetch worker writes fetch_s; only the main
    # thread writes the others — no lock needed. The same increments
    # flow into the telemetry session (``out_of_core.<key>`` counters
    # + per-batch spans, docs/OBSERVABILITY.md) with the JSON keys
    # preserved verbatim — `stats` consumers never notice telemetry.
    phase = {"pad_s": 0.0, "put_s": 0.0, "dispatch_s": 0.0,
             "fetch_s": 0.0, "fetch_wait_s": 0.0}

    def _phase_add(key, dt):
        phase[key] += dt
        telemetry.counter_add("out_of_core." + key, dt)

    def stage(b):
        with telemetry.span("stage", batch=b):
            t0 = time.perf_counter()
            bt = _pad_host(build_batches[b], bcap)
            pt = _pad_host(probe_batches[b], pcap)
            t1 = time.perf_counter()
            out = comm.device_put_sharded((bt, pt))
            _phase_add("pad_s", t1 - t0)
            _phase_add("put_s", time.perf_counter() - t1)
        return out

    from concurrent.futures import ThreadPoolExecutor

    # with_metrics=False: the per-batch dispatch loop stays the seed
    # program even under an active telemetry session — out-of-core
    # observability is host-side by design (phase counters, per-batch
    # spans/events above), and an aux device block nobody fetches
    # would still be computed every batch. verify_integrity is the
    # exception: its digests ARE fetched, at each batch's settle.
    fn = make_distributed_join(comm, key=key, with_metrics=False,
                               with_integrity=verify_integrity,
                               **join_opts)
    pool = ThreadPoolExecutor(max_workers=1)
    fetch_pool = ThreadPoolExecutor(max_workers=1)

    # {pending index -> IntegrityReport} verified on the fetch worker
    # (reused by _settle so each batch's digests are checked once).
    verified_reports: dict = {}

    def _fetch(i, b, res):
        # Runs ON the fetch worker, in batch order (1 worker). The
        # consumer's D2H pulls overlap the NEXT batch's device compute
        # — mirror image of the staging thread. numpy materialization
        # and the transfer both release the GIL.
        if verify_integrity and getattr(res, "telemetry", None) is not None:
            # Verify BEFORE the consumer sees a single row: a wire-
            # corrupted batch must be abandoned, not persisted by a
            # materializing consumer and only flagged at settle. The
            # overflow fetch + digest transfer ride this worker, so
            # the overlap the fetch thread exists for is preserved.
            # (Overflowed batches skip the check — clamped rows
            # mismatch by design and the flag already demands a
            # retry; the consumer contract for flagged batches is
            # unchanged.)
            if not bool(res.overflow):
                from distributed_join_tpu.parallel import integrity

                rep = integrity.verify_digests(res.telemetry)
                verified_reports[i] = rep
                if not rep.ok:
                    telemetry.event(
                        "batch_integrity_mismatch", batch=b,
                        mismatches=len(rep.mismatches))
                    return  # _settle fails the batch under contract
        with telemetry.span("fetch", batch=b):
            tf = time.perf_counter()
            on_batch_result(b, res)
            _phase_add("fetch_s", time.perf_counter() - tf)

    # Per-batch remaining FAILED-attempt budget: one pool of
    # batch_retries + 1, shared between the warmup dispatch and the
    # measured loop so neither double-charges (or double-logs).
    # Successes are free — the measured loop re-dispatching a batch
    # warmup already ran clean is by design, not a retry.
    tries_left: dict = {}

    def _dispatch(b, bt, pt):
        """fn(bt, pt) under batch ``b``'s failure budget, with
        exponential backoff between attempts (faults.retry_with_backoff
        — the same transient-failure loop as the bootstrap handshake);
        returns the JoinResult or None when the batch is abandoned
        (on_batch_failure='continue', budget exhausted). Failures are
        appended to the manifest's forensic log."""
        from distributed_join_tpu.parallel.faults import (
            retry_with_backoff,
        )

        last = None
        res = None
        tries_left.setdefault(b, batch_retries + 1)
        budget = tries_left[b]
        if budget > 0:
            try:
                res, attempts = retry_with_backoff(
                    lambda: fn(bt, pt), max_attempts=budget,
                    backoff_s=batch_retry_backoff_s,
                )
            except Exception as exc:  # noqa: BLE001 - retry seam
                last = exc
                attempts = getattr(exc, "_retry_attempts", [])
            # Only FAILED attempts charge the budget + forensic log.
            fails = [a for a in attempts if a["error"] is not None]
            tries_left[b] -= len(fails)
            if manifest is not None:
                base = batch_retries + 1 - budget
                for k, a in enumerate(fails):
                    manifest.record_failure(b, a["error"], base + k)
            if res is not None:
                return res
        if on_batch_failure == "continue":
            failed.add(b)
            return None
        # last=None (budget pre-exhausted, e.g. by warmup) only
        # happens under 'continue', which returned above.
        raise last

    def _fetch_scalars(i):
        """The one host sync per batch: total + overflow flag, under
        the hang watchdog when a batch deadline is configured (a
        deadlocked collective never sequences this fetch — HangError
        is a batch failure, not an eternity)."""
        if batch_deadline_s is None:
            return int(totals[i]), bool(overflows[i])
        from distributed_join_tpu.parallel.watchdog import (
            call_with_deadline,
        )

        return call_with_deadline(
            lambda: (int(totals[i]), bool(overflows[i])),
            batch_deadline_s,
            what=f"out-of-core batch {pending[i]} result fetch",
        )

    def _settle(i):
        """Force pending[i]'s total to host (the device sync), verify
        its wire digests when asked, and persist its manifest record.
        A failure HERE (result fetch, hang, integrity mismatch) is a
        batch failure too — same degradation contract as dispatch."""
        if totals[i] is None or isinstance(totals[i], int):
            return
        b = pending[i]
        try:
            totals[i], overflows[i] = _fetch_scalars(i)
            if (verify_integrity and not overflows[i]
                    and metrics_refs[i] is not None):
                from distributed_join_tpu.parallel import integrity

                rep = verified_reports.get(i)
                if rep is None:
                    rep = integrity.verify_digests(metrics_refs[i])
                if not rep.ok:
                    # A corrupt batch total must never fold into the
                    # returned sum — surface or abandon, per contract.
                    raise integrity.IntegrityError(rep)
        except Exception as exc:  # noqa: BLE001 - degradation seam
            if manifest is not None:
                manifest.record_failure(
                    b, f"{type(exc).__name__}: {exc}", batch_retries)
            if on_batch_failure != "continue":
                raise
            totals[i], overflows[i] = None, None
            failed.add(b)
            telemetry.event("batch_failed", batch=b,
                            error=f"{type(exc).__name__}: {exc}")
            return
        telemetry.event("batch_complete", batch=b, total=totals[i],
                        overflow=overflows[i])
        if manifest is not None:
            manifest.record_batch(b, totals[i], overflows[i])

    nxt = None
    if warmup and pending:
        nxt = stage(pending[0])
        # Compile + run under the same per-batch retry/degradation
        # contract as the measured loop (a transient failure here must
        # not crash a run that opted into batch_retries / 'continue');
        # result discarded, the staged inputs are reused as the
        # measured loop's first batch. The attempt budget is SHARED
        # with the measured loop: a batch warmup exhausts is failed
        # here and not re-dispatched.
        res = _dispatch(pending[0], *nxt)
        if res is not None:
            try:
                if batch_deadline_s is None:
                    int(res.total)
                else:
                    from distributed_join_tpu.parallel.watchdog import (
                        call_with_deadline,
                    )

                    call_with_deadline(
                        lambda: int(res.total), batch_deadline_s,
                        what="out-of-core warmup result fetch",
                    )
            except Exception as exc:  # noqa: BLE001 - degradation seam
                # Same contract as _settle: an async device failure
                # that only surfaces at the scalar fetch is a batch
                # failure, not a run crash, when the caller opted into
                # 'continue'. The measured loop re-dispatches the
                # batch and clears it from `failed` on recovery.
                if manifest is not None:
                    manifest.record_failure(
                        pending[0], f"{type(exc).__name__}: {exc}",
                        batch_retries)
                if on_batch_failure != "continue":
                    raise
                failed.add(pending[0])

    # Warmup staged the first pending batch before t0: reset the phase
    # counters so the breakdown covers exactly the [t0, end) window it
    # is reported against (otherwise pad_s/put_s over-count by one
    # batch). The telemetry counters are NOT reset — a session covers
    # the whole run, warmup included; the event below marks where the
    # measured window begins so the two accountings reconcile.
    for k_ in phase:
        phase[k_] = 0.0
    telemetry.event("out_of_core_measured_window",
                    n_batches=n_batches, pending=len(pending),
                    resumed=sorted(completed))
    t0 = time.perf_counter()
    fut = None
    if pending:
        fut = (pool.submit(lambda: nxt) if nxt is not None
               else pool.submit(stage, pending[0]))
    # All four lists are positionally aligned with `pending`;
    # totals[i] is a device scalar until _settle(i) fetches it, None
    # for a failed/abandoned batch. metrics_refs holds only the small
    # aux Metrics block (verify_integrity) — never the output table,
    # so backpressure still bounds device residency.
    totals, overflows, fetch_futs, metrics_refs = [], [], [], []
    try:
        for i, b in enumerate(pending):
            bt, pt = fut.result()
            td = time.perf_counter()
            res = _dispatch(b, bt, pt)
            _phase_add("dispatch_s", time.perf_counter() - td)
            if res is not None:
                # A batch marked failed at the warmup FETCH (dispatch
                # succeeded, async failure at the scalar sync) that
                # this dispatch just recovered must not stay in the
                # failure record — its total is counted.
                failed.discard(b)
            totals.append(res.total if res is not None else None)
            overflows.append(res.overflow if res is not None else None)
            metrics_refs.append(
                getattr(res, "telemetry", None)
                if res is not None else None
            )
            fetch_futs.append(
                fetch_pool.submit(_fetch, i, b, res)
                if (on_batch_result is not None and res is not None)
                else None
            )
            if i + 1 < len(pending):
                # Stage the next batch on the worker thread,
                # overlapping both this batch's device work and the
                # backpressure wait.
                fut = pool.submit(stage, pending[i + 1])
                if i >= 1:
                    # Backpressure (see docstring): batch i-1 must be
                    # done before a third batch's buffers exist.
                    tf = time.perf_counter()
                    if fetch_futs[i - 1] is not None:
                        # In-order consumption: i-1's consumer must
                        # have returned before i+1 dispatches.
                        fetch_futs[i - 1].result()
                    # The DEVICE sync cannot be delegated to the
                    # consumer — one that merely reduces (or keeps
                    # device references) returns before i-1's join
                    # finished, which would let the staging worker
                    # race ahead and OOM (review r5). A scalar fetch
                    # (utils/benchmarking.py's sync). The
                    # manifest record rides the same sync point, so
                    # durability costs no extra synchronization.
                    _settle(i - 1)
                    _phase_add("fetch_wait_s",
                               time.perf_counter() - tf)
        tf = time.perf_counter()
        for f in fetch_futs:
            if f is not None:
                f.result()  # drain (+ surface consumer exceptions)
        for i in range(len(pending)):
            _settle(i)
        total = sum(t for t in totals if t is not None)
        overflow = any(bool(o) for o in overflows if o is not None)
        _phase_add("fetch_wait_s", time.perf_counter() - tf)
    finally:
        # Also on error: an orphaned worker (wedged in a dead backend
        # put/fetch) would hang the interpreter at exit via
        # ThreadPoolExecutor's atexit join. Bounded teardown instead:
        # join each worker briefly, then report a
        # worker_shutdown_timeout event and detach it from the atexit
        # join (watchdog.shutdown_bounded).
        from distributed_join_tpu.parallel.watchdog import (
            shutdown_bounded,
        )

        shutdown_bounded(pool, "out_of_core.stage")
        shutdown_bounded(fetch_pool, "out_of_core.fetch")
    # Fold in the batches a prior (killed) run already completed —
    # totals only: overflowed entries were filtered back into
    # `pending` above, so `completed` carries no overflow.
    total += sum(v["total"] for v in completed.values())
    if failed and stats is None:
        warnings.warn(
            f"on_batch_failure='continue': batches {sorted(failed)} "
            "were abandoned and the returned total is PARTIAL — pass "
            "a stats dict to receive failed_batches programmatically",
            stacklevel=2,
        )
    if stats is not None:
        stats["elapsed_s"] = time.perf_counter() - t0
        stats["build_capacity"] = bcap
        stats["probe_capacity"] = pcap
        stats["resumed_batches"] = sorted(completed)
        stats["failed_batches"] = sorted(failed)
        stats.update(phase)
    return total, overflow


def keyrange_batched_join(
    build: Table,
    probe: Table,
    comm: Communicator,
    key: str = "key",
    n_batches: int = 4,
    on_batch_result: Optional[Callable] = None,
    warmup: bool = True,
    stats: Optional[dict] = None,
    manifest_path: Optional[str] = None,
    batch_retries: int = 0,
    batch_retry_backoff_s: float = 1.0,
    on_batch_failure: str = "raise",
    verify_integrity: bool = False,
    batch_deadline_s: Optional[float] = None,
    **join_opts,
) -> Tuple[int, bool]:
    """Join arbitrarily large host-resident tables in ``n_batches``
    device-sized pieces; returns (total_matches, any_overflow).
    ``manifest_path``/``batch_retries``/``on_batch_failure``/
    ``verify_integrity``/``batch_deadline_s`` are the checkpoint/
    resume + per-batch recovery/verification knobs of
    :func:`batched_join_host` (binning is deterministic — the same
    tables and ``n_batches`` always rebuild the same batches, which is
    what makes resuming against the manifest sound).

    ``on_batch_result(batch_index, JoinResult)`` can materialize or
    reduce each batch's output; it runs on a dedicated fetch worker
    thread, in batch order, overlapped with the next batch's compute
    (round 5 — see :func:`batched_join_host`), with at most two
    batches' outputs alive at once.
    ``warmup`` runs (and discards) batch 0 once first so the 30-100s
    remote XLA compile stays out of the measured loop; ``stats`` (if a
    dict) receives ``elapsed_s`` — the post-warmup batch-loop wall time
    including H2D staging, the honest out-of-core figure a caller
    should report instead of timing around this whole call.
    Implementation: bins the host copies of ``build``/``probe`` into
    per-batch column blocks and delegates to :func:`batched_join_host`
    (one compile, one-ahead staged H2D, per-batch backpressure).
    """
    keys = [key] if isinstance(key, str) else list(key)
    hb, hp = _host_columns(build), _host_columns(probe)
    bb = key_batch_ids([hb[k] for k in keys], n_batches)
    pb = key_batch_ids([hp[k] for k in keys], n_batches)

    def _bin(cols, ids):
        # Column-at-a-time, releasing each source column as it is
        # binned: peak host overhead is one column plus the index
        # arrays (int32 = half a column-width in total — but only
        # below 2^31 rows; a silent int32 wrap would route rows into
        # wrong batches with wrong data), not a second full copy of
        # the dataset (this path exists for near-RAM tables). The
        # batch masks are resolved to index arrays ONCE, not per
        # (column, batch).
        idx_dt = np.int32 if len(ids) < 2**31 else np.int64
        idx = [np.flatnonzero(ids == b).astype(idx_dt)
               for b in range(n_batches)]
        out = [{} for _ in range(n_batches)]
        for nm in list(cols):
            c = cols.pop(nm)
            for b in range(n_batches):
                out[b][nm] = c[idx[b]]
        return out

    return batched_join_host(
        _bin(hb, bb), _bin(hp, pb), comm, key=key,
        warmup=warmup, stats=stats, on_batch_result=on_batch_result,
        manifest_path=manifest_path, batch_retries=batch_retries,
        batch_retry_backoff_s=batch_retry_backoff_s,
        on_batch_failure=on_batch_failure,
        verify_integrity=verify_integrity,
        batch_deadline_s=batch_deadline_s,
        **join_opts,
    )
