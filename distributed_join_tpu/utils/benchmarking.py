"""Shared benchmark timing discipline.

Per-call timing measures dispatch and one host round trip along with
the work, so the protocol is:

  1. chain K *dependent* iterations of the measured computation inside
     ONE compiled program (``lax.fori_loop``), perturbing the inputs
     with the loop counter so XLA can neither hoist loop-invariant work
     nor dead-code-eliminate outputs;
  2. run it once for warmup/compile;
  3. time one more call, fetching a single scalar to force completion,
     and divide by K.

The reference times with ``MPI_Barrier`` + chrono around the measured
region (SURVEY.md §5 "Tracing"); the fetch-one-scalar protocol is the
same barrier discipline expressed in XLA terms.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def measure(fn: Callable, fetch: Callable, iters: int,
            name: str = "timed") -> float:
    """THE timing definition — every profile script, driver, and
    bench.py routes through here: warm up ``fn`` (compiles + runs),
    then time ONE more call; returns seconds per iteration.
    ``fetch(result)`` must force completion by pulling at least one
    scalar to the host (see the module docstring). The measured interval is recorded as a
    completed telemetry span (``telemetry.span_complete``, a no-op
    without an active session) so driver JSON records and Chrome
    traces share this one definition."""
    from distributed_join_tpu import telemetry

    fetch(fn())
    t0 = time.perf_counter()
    fetch(fn())
    dt = time.perf_counter() - t0
    telemetry.span_complete(name, t0, dt, iters=iters,
                            per_iter_s=dt / iters)
    return dt / iters


def measure_chained(name: str, make_body: Callable, *args,
                    iters: int = 8) -> float:
    """Time one primitive with the chained-loop protocol:
    ``make_body(i, *args) -> scalar`` is run ``iters`` dependent times
    inside a single jitted ``fori_loop`` (the loop counter perturbed by
    the carry so nothing hoists), then handed to :func:`measure` (one
    timing codepath, not two). Prints and returns seconds per
    iteration. Used by the scripts/profile_*.py microbenchmarks."""

    def looped(*args):
        def body(i, acc):
            return acc + make_body(i + acc % 2, *args).astype(jnp.int64)

        return lax.fori_loop(0, iters, body, jnp.int64(0))

    fn = jax.jit(looped)
    dt = measure(lambda: fn(*args), lambda r: int(r), iters, name=name)
    print(f"{name:52s} {dt * 1e3:9.1f} ms", flush=True)
    return dt


def consume_all_columns(table) -> "jnp.ndarray":
    """Reduce EVERY output column into one int64 scalar so no part of
    the result materialization can be dead-code-eliminated.

    This matters: an earlier guard consumed a single payload column,
    and XLA silently deleted the key and build-payload gathers AND the
    whole build-side sort from the timed program — the "join" being
    measured materialized one column. The reference's cudf::inner_join
    materializes every output column inside the timed region; honest
    parity requires consuming them all.
    """
    acc = jnp.int64(0)
    for c in table.columns.values():
        if jnp.issubdtype(c.dtype, jnp.floating):
            c = lax.convert_element_type(c, jnp.int32)
        if c.ndim > 1:  # string columns: every byte, not just byte 0
            c = jnp.sum(
                c.reshape((c.shape[0], -1)).astype(jnp.int32), axis=1
            )
        acc = acc + jnp.sum(
            jnp.where(table.valid, c.astype(jnp.int64), 0)
        )
    return acc


def timed_join_throughput(
    comm,
    step: Callable,
    build,
    probe,
    iters: int,
    key: str = "key",
):
    """Time ``iters`` chained join steps; returns
    ``(sec_per_join, total_matches_per_join, overflow)``.

    The loop-variance tricks live here, in one place:
    - both sides' key columns are shifted by the loop counter (the shift
      preserves hit/miss structure — the generator's miss keys occupy a
      disjoint range that shifts rigidly with the hits — but makes every
      hash/sort/shuffle stage loop-variant so nothing hoists);
    - EVERY output column is reduced into the carry so no part of the
      result materialization can be dead-code-eliminated (see
      consume_all_columns);
    - the per-rank carry is initialized from sharded data (a literal
      zero is unvarying in shard_map's vma tracking and is rejected as
      a carry init for a varying accumulator);
    - the DCE-guard psum happens once AFTER the loop so no collective
      is billed to the throughput number beyond the join's own.
    """
    from distributed_join_tpu.table import Table

    # For a composite key, shifting ONLY the first column preserves
    # tuple-equality structure (tuples equal iff shifted tuples equal)
    # while still making every downstream stage loop-variant.
    shift_key = key if isinstance(key, str) else key[0]
    key_dtype = probe.columns[shift_key].dtype

    def looped(build, probe):
        def body(i, acc):
            shift = (
                i if jnp.issubdtype(key_dtype, jnp.integer)
                else lax.convert_element_type(i, key_dtype)
            )
            bcols = dict(build.columns)
            bcols[shift_key] = bcols[shift_key] + shift
            pcols = dict(probe.columns)
            pcols[shift_key] = pcols[shift_key] + shift
            res = step(Table(bcols, build.valid), Table(pcols, probe.valid))
            consumed = consume_all_columns(res.table)
            return (
                acc[0] + res.total.astype(jnp.int64),
                acc[1] | res.overflow,
                acc[2] + consumed,
            )

        # Any probe column works for the varying all-zero init.
        first_col = next(iter(probe.columns.values()))
        vzero = (first_col[0] * 0).astype(jnp.int64)
        total, overflow, consumed = lax.fori_loop(
            0, iters, body, (jnp.int64(0), jnp.bool_(False), vzero)
        )
        return total, overflow, comm.psum(consumed)

    fn = comm.spmd(looped, sharded_out=(True, True, True))

    state = {}

    def fetch(res):
        state["total"], state["overflow"] = int(res[0]), bool(res[1])

    sec = measure(lambda: fn(build, probe), fetch, iters,
                  name="timed_join")
    return sec, state["total"] // iters, state["overflow"]
