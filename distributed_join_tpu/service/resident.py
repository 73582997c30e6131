"""Resident build tables — register once, serve probe-only joins.

Every request through the full pipeline re-partitions, re-shuffles,
and re-sorts BOTH tables, yet real serving traffic overwhelmingly
joins many small probes against a few large, slowly-changing build
tables (ROADMAP item 4) — ~2/3 of each request's work recomputes a
result that hasn't changed. This module makes that work resident:

- :func:`make_resident_prep_step` runs the expensive build-side 2/3
  ONCE — hash-partition into ``n_ranks`` buckets, all-to-all shuffle,
  key-sort the received rows into a valid-prefix **sorted run** — and
  the result stays on-device as one row-sharded :class:`~..table.
  Table` under a named handle with a monotonic **generation** stamp;
- :func:`~..parallel.distributed_join.make_probe_join_step` then
  serves repeat joins as probe-only programs: partition + shuffle +
  sort the probe side only, merge each batch against the resident
  run. Programs are cached under a :class:`ResidentSignature`
  extended with ``(handle, generation)`` — a warm probe-only repeat
  is a zero-trace dict-lookup dispatch, exactly like the full join's
  warm path (docs/SERVICE.md);
- streaming ingestion closes the loop LSM-style: :meth:`
  ResidentTableRegistry.append` lands a delta as a small sorted run
  (same prep program at the delta's slot shape), and a **maintenance
  pass** merges pending runs into the resident shards — a merge of
  PRE-sorted runs, the structure docs/ROOFLINE.md §8 names as the
  only regime where run-length effects pay ("the run-length effect
  pays only when data ARRIVES pre-bucketed"). XLA exposes no
  dedicated merge primitive, so the pass is expressed as concat +
  ``lax.sort`` over the two runs (§6: sort cost is run-length
  dominated, and a concat of two sorted runs is exactly two runs); a
  future Pallas merge-path kernel slots in behind the same step
  factory. Each generation bump evicts ONLY the probe-only cache
  entries compiled against the old build image
  (``JoinProgramCache.evict(reason="generation")``).

Integrity: registration and every append/merge are conservation-
checked — the global valid-row count AND an order-invariant key-hash
sum must survive the prep/merge program exactly, or the operation
refuses loudly (:class:`ResidentError`) and the handle is left
untouched (a failed merge poisons the handle instead of blessing
wrong rows — the chaos suite grades exactly this). Out-of-core paging
of resident shards larger than HBM is explicitly deferred to the
``out_of_core`` manifest machinery (ROADMAP item 4's tail).
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
import json
import math
import threading
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from distributed_join_tpu import telemetry
from distributed_join_tpu.ops.hashing import hash_columns
from distributed_join_tpu.ops.join import _dtype_sentinel_max
from distributed_join_tpu.ops.partition import radix_hash_partition
from distributed_join_tpu.parallel.distributed_join import (
    DEFAULT_SHUFFLE_CAPACITY_FACTOR,
    JOIN_METRICS_SHARDED_OUT,
    JOIN_SHARDED_OUT,
    _batch_shuffle,
    make_probe_join_step,
    resolve_join_ladder,
)
# ONE canonicalizer set for every cache signature: resident and
# full-join programs share the JoinProgramCache, and a drifted
# canonical form would silently fork the keyspace.
from distributed_join_tpu.service.programs import _canon, _schema_of
from distributed_join_tpu.table import Table

RESIDENT_SCHEMA_VERSION = 1

# (Table row-sharded, rows psummed+replicated, digest replicated,
# overflow replicated) — the merge program's output spec; the prep
# program additionally returns the INPUT conservation pair (rows_in,
# digest_in) so the host check never materializes table shards
# (np.asarray on non-addressable shards crashes multi-controller).
MERGE_SHARDED_OUT = (False, True, True, True)
PREP_SHARDED_OUT = (False, True, True, True, True, True)

# make_probe_join_step's own keyword surface, defaults filled — the
# probe-only signature's option basis, derived from the function
# itself so a new knob can never alias two programs (the
# JoinSignature discipline, docs/SERVICE.md).
_PROBE_STEP_DEFAULTS = {
    name: p.default
    for name, p in inspect.signature(
        make_probe_join_step).parameters.items()
    if p.default is not inspect.Parameter.empty
}

# Sizing keys the CapacityLadder resolves that the probe-only step
# actually takes (the hh_* skew capacities are not part of the
# probe-only program).
_PROBE_SIZING_KEYS = (
    "shuffle_capacity_factor", "out_capacity_factor",
    "out_rows_per_rank", "compression_bits",
)


class ResidentError(RuntimeError):
    """A resident-table operation refused loudly: unknown/poisoned
    handle, schema mismatch, capacity overflow, or a conservation
    check failing on a prep/merge pass. Never a wrong answer."""


class StaleGenerationError(ResidentError):
    """Generation fence (docs/FAILURE_SEMANTICS.md, replication
    contract): this holder's image is at a LOWER generation than the
    fleet directory requires — it missed an ``append`` fan-out. A
    stale image would serve rows that silently exclude the missed
    delta, so probe-only work is refused loudly instead; the router
    treats the refusal as a failover signal and retries the request
    on an up-to-date holder."""


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m




@dataclasses.dataclass(frozen=True)
class ResidentSignature:
    """Canonical identity of one resident-subsystem program.

    ``kind`` is ``"prep"`` (build/delta preparation), ``"merge"``
    (the LSM maintenance pass), or ``"probe_join"`` (the serving
    path). Probe-join signatures additionally bind the HANDLE and its
    GENERATION, so the program cache, tuner, and history store all
    key the resident image a program was compiled against — a
    generation bump can never serve a stale executable (and the old
    generation's entries are evicted eagerly on top).
    """

    kind: str
    n_ranks: int
    build_schema: tuple
    build_capacity: int
    probe_schema: Optional[tuple]
    probe_capacity: Optional[int]
    handle: Optional[str]
    generation: Optional[int]
    options: tuple

    def canonical(self) -> dict:
        return dataclasses.asdict(self)

    def digest(self) -> str:
        return hashlib.sha256(
            json.dumps(self.canonical(), sort_keys=True,
                       default=str).encode()
        ).hexdigest()


# -- the in-program sorted-run machinery -------------------------------


def _key_sorted_prefix(table: Table, keys: Sequence[str]) -> Table:
    """Sort a (possibly interleaved-validity) table so its valid rows
    form a key-sorted prefix — the resident run layout. One
    value-carrying ``lax.sort``: keys (invalid rows masked to the
    dtype's max) + a validity tag as sort keys, every payload column
    riding as a value lane (ROOFLINE §1: value operands are ~free).
    The tag breaks sentinel collisions: a VALID row whose key equals
    the sentinel still sorts before every invalid row."""
    ops = []
    for k in keys:
        c = table.columns[k]
        ops.append(jnp.where(table.valid, c,
                             _dtype_sentinel_max(c.dtype)))
    tag = jnp.where(table.valid, jnp.int8(0), jnp.int8(1))
    payload = [n for n in table.column_names if n not in keys]
    vals = [table.columns[n] for n in payload]
    sorted_ = lax.sort((*ops, tag, *vals), num_keys=len(keys) + 1)
    cols = {k: sorted_[i] for i, k in enumerate(keys)}
    for i, n in enumerate(payload):
        cols[n] = sorted_[len(keys) + 1 + i]
    # Preserve the source column order (schema identity is
    # name-sorted anyway; this keeps to_pandas views stable).
    cols = {n: cols[n] for n in table.column_names}
    return Table(cols, sorted_[len(keys)] == jnp.int8(0))


def _run_accounting(comm, table: Table, keys: Sequence[str]):
    """(rows, key_digest): global valid-row count and order-invariant
    key-hash sum — the conservation pair every prep/merge pass must
    carry through exactly (a dropped, duplicated, or value-corrupted
    key row moves one of them)."""
    h = hash_columns([table.columns[k] for k in keys])
    digest = jnp.sum(jnp.where(table.valid, h, jnp.uint64(0)))
    rows = jnp.sum(table.valid.astype(jnp.int64))
    return comm.psum(rows), comm.psum(digest)


def make_resident_prep_step(comm, key="key",
                            resident_rows_per_rank: int = 0,
                            shuffle_capacity_factor: float =
                            DEFAULT_SHUFFLE_CAPACITY_FACTOR,
                            shuffle: str = "padded"):
    """The register/append preparation program: hash-partition the
    build-side shard into ``n_ranks`` buckets, shuffle, and key-sort
    the received rows into a valid-prefix run of
    ``resident_rows_per_rank`` capacity. Returns ``step(build_local)
    -> (resident_local, rows, key_digest, rows_in, digest_in,
    overflow)`` for ``comm.spmd(..., sharded_out=PREP_SHARDED_OUT)``
    — the INPUT pair is measured before the shuffle and the OUTPUT
    pair after the sort, so the caller's host-side equality check
    brackets exactly the data movement (and needs no table
    materialization — multi-controller safe)."""
    n = comm.n_ranks
    if shuffle not in ("padded", "ppermute"):
        raise ValueError(
            f"resident prep supports the padded/ppermute shuffles, "
            f"not {shuffle!r}")
    keys = [key] if isinstance(key, str) else list(key)

    def step(build_local: Table):
        for name, c in build_local.columns.items():
            if c.ndim != 1:
                raise TypeError(
                    f"resident column {name!r} is {c.ndim}-D; "
                    "resident tables cover scalar columns")
        b_rows = build_local.capacity
        in_rows, in_digest = _run_accounting(comm, build_local, keys)
        if n == 1:
            recv, ovf = build_local, jnp.bool_(False)
        else:
            b_cap = _round_up(
                int(math.ceil(b_rows / n * shuffle_capacity_factor)),
                8)
            with jax.named_scope("partition"):
                pt = radix_hash_partition(build_local, keys, n)
            with jax.named_scope("shuffle"):
                recv, ovf = _batch_shuffle(comm, pt, 0, n, b_cap,
                                           mode=shuffle)
        if recv.capacity > resident_rows_per_rank:
            raise ValueError(
                f"resident capacity {resident_rows_per_rank} below "
                f"the shuffle receive block {recv.capacity}")
        with jax.named_scope("sort"):
            run = _key_sorted_prefix(
                recv.pad_to(resident_rows_per_rank), keys)
        rows, digest = _run_accounting(comm, run, keys)
        overflow = comm.psum(ovf.astype(jnp.int32)) > 0
        return run, rows, digest, in_rows, in_digest, overflow

    return step


def make_run_merge_step(comm, key="key"):
    """The LSM maintenance pass: merge one pending sorted run into
    the resident base run. ``step(base_local, run_local) ->
    (merged_local, rows, key_digest, overflow)`` — concat + one
    value-carrying sort over the two PRE-sorted runs (ROOFLINE §6/§8:
    the pre-bucketed regime), sliced back to the base capacity.
    Overflow fires when valid rows exceed the base capacity (rows
    would be silently lost otherwise — the caller refuses instead)."""
    keys = [key] if isinstance(key, str) else list(key)

    def step(base_local: Table, run_local: Table):
        base_cap = base_local.capacity
        merged = Table(
            {
                n: jnp.concatenate([base_local.columns[n],
                                    run_local.columns[n]])
                for n in base_local.column_names
            },
            jnp.concatenate([base_local.valid, run_local.valid]),
        )
        with jax.named_scope("merge_sort"):
            sorted_ = _key_sorted_prefix(merged, keys)
        valid_total = jnp.sum(sorted_.valid.astype(jnp.int32))
        ovf = valid_total > base_cap
        out = Table(
            {n: c[:base_cap] for n, c in sorted_.columns.items()},
            sorted_.valid[:base_cap],
        )
        rows, digest = _run_accounting(comm, out, keys)
        overflow = comm.psum(ovf.astype(jnp.int32)) > 0
        return out, rows, digest, overflow

    return step


# -- the registry ------------------------------------------------------


class ResidentTable:
    """One registered build table's host-side handle: the resident
    on-device image plus its LSM state. Mutated only by the registry
    (under the owning service's exec lock)."""

    def __init__(self, name: str, keys: tuple, table: Table,
                 rows: int, key_digest: int, capacity_per_rank: int,
                 row_bytes: int, n_ranks: int):
        self.name = name
        self.keys = keys
        self.table = table              # device Table, row-sharded
        self.rows = rows                # global valid rows
        self.key_digest = key_digest    # order-invariant key-hash sum
        self.capacity_per_rank = capacity_per_rank
        self.row_bytes = row_bytes
        self.n_ranks = n_ranks
        self.generation = 1
        self.pending_runs: list = []    # (device Table, rows, digest)
        self.poisoned: Optional[str] = None
        self.joins_served = 0
        self.warm_joins = 0             # probe-only joins, 0 traces
        self.appends = 0
        self.merges = 0
        # Probe-only signatures cached against the CURRENT generation
        # (evicted on bump so only dependent entries churn).
        self.cached_sigs: set = set()
        # Wire-layer metadata (the daemon stashes the generator spec
        # and the base key column here so probe specs can draw hit
        # keys without regenerating the whole build per request).
        self.wire_spec: Optional[dict] = None
        self.wire_build_keys = None

    @property
    def bytes_resident(self) -> int:
        total = self.capacity_per_rank * self.n_ranks * self.row_bytes
        for run, _, _ in self.pending_runs:
            total += run.capacity * self.row_bytes
        return total

    def stats(self) -> dict:
        return {
            "rows": self.rows,
            "generation": self.generation,
            "capacity_per_rank": self.capacity_per_rank,
            "bytes_resident": self.bytes_resident,
            "pending_runs": len(self.pending_runs),
            "joins_served": self.joins_served,
            "warm_joins": self.warm_joins,
            "appends": self.appends,
            "merges": self.merges,
            "poisoned": self.poisoned,
            "key": list(self.keys),
        }


class ResidentTableRegistry:
    """Named resident build tables over one communicator's mesh.

    Thread-compatibility contract: like :class:`~.programs.
    JoinProgramCache`, the registry itself does not lock — the owning
    :class:`~.server.JoinService` serializes every mutating call on
    its exec lock (one mesh runs one program at a time anyway);
    library users on one thread need nothing.
    """

    def __init__(self, comm, cache=None, *, max_tables: int = 8,
                 capacity_factor: float = 1.5,
                 shuffle_capacity_factor: float =
                 DEFAULT_SHUFFLE_CAPACITY_FACTOR,
                 delta_slot_rows: int = 1024,
                 maintain_runs: int = 4,
                 prep_retries: int = 2):
        self.comm = comm
        self.cache = cache          # JoinProgramCache or None
        self.max_tables = int(max_tables)
        self.capacity_factor = float(capacity_factor)
        self.shuffle_capacity_factor = float(shuffle_capacity_factor)
        self.delta_slot_rows = int(delta_slot_rows)
        self.maintain_runs = int(maintain_runs)
        self.prep_retries = int(prep_retries)
        self._tables: dict = {}
        self._local_programs: dict = {}   # cache=None fallback tier
        self.registered = 0
        self.dropped = 0
        self.refused = 0
        self._lock = threading.Lock()     # protects the name table
                                          # only; programs/devices are
                                          # serialized by the caller

    # -- lookup --------------------------------------------------------

    def __contains__(self, name: str) -> bool:
        return name in self._tables

    def names(self):
        return sorted(self._tables)

    def peek(self, name: str) -> Optional[ResidentTable]:
        """The handle if registered (poisoned or not), else None —
        the bookkeeping view (:meth:`get` is the serving view and
        refuses poisoned handles loudly)."""
        return self._tables.get(name)

    def get(self, name: str) -> ResidentTable:
        handle = self._tables.get(name)
        if handle is None:
            self.refused += 1
            raise ResidentError(
                f"no resident table {name!r} (registered: "
                f"{self.names() or 'none'})")
        if handle.poisoned:
            self.refused += 1
            raise ResidentError(
                f"resident table {name!r} is poisoned "
                f"({handle.poisoned}); drop and re-register")
        return handle

    def stats(self) -> dict:
        tables = {n: h.stats() for n, h in sorted(self._tables.items())}
        return {
            "count": len(self._tables),
            "max_tables": self.max_tables,
            "bytes_resident": sum(h.bytes_resident
                                  for h in self._tables.values()),
            "generation_max": max(
                (h.generation for h in self._tables.values()),
                default=0),
            "probe_joins": sum(h.joins_served
                               for h in self._tables.values()),
            "warm_probe_joins": sum(h.warm_joins
                                    for h in self._tables.values()),
            "registered": self.registered,
            "dropped": self.dropped,
            "refused": self.refused,
            "tables": tables,
        }

    # -- program admission --------------------------------------------

    def _program(self, sig: ResidentSignature, builder,
                 example_args=None, with_aux: bool = False):
        """(program, hit) through the shared JoinProgramCache when the
        registry has one (LRU + disk tiers + trace accounting), else a
        plain local dict (library use)."""
        if self.cache is not None:
            return self.cache.get_keyed(sig, builder,
                                        example_args=example_args,
                                        with_aux=with_aux)
        entry = self._local_programs.get(sig)
        if entry is not None:
            return entry, True
        from distributed_join_tpu.service.programs import CachedProgram

        entry = CachedProgram(sig, builder(), with_aux, "trace")
        self._local_programs[sig] = entry
        return entry, False

    def _prep_program(self, schema: tuple, capacity: int, keys: tuple,
                      resident_rows: int, factor: float):
        sig = ResidentSignature(
            kind="prep", n_ranks=self.comm.n_ranks,
            build_schema=schema, build_capacity=capacity,
            probe_schema=None, probe_capacity=None,
            handle=None, generation=None,
            options=(("resident_rows_per_rank", resident_rows),
                     ("shuffle_capacity_factor", factor)),
        )

        def build():
            step = make_resident_prep_step(
                self.comm, key=list(keys),
                resident_rows_per_rank=resident_rows,
                shuffle_capacity_factor=factor)
            return self.comm.spmd(step, sharded_out=PREP_SHARDED_OUT)

        fn, _ = self._program(sig, build)
        return fn, sig

    def _evict_program(self, sig: ResidentSignature) -> None:
        """Drop a resident-subsystem program whose run failed a
        conservation check: corruption is woven at TRACE time (the
        same discipline as the full join's integrity eviction), so a
        clean re-run must re-trace, never reuse the tainted
        executable."""
        if self.cache is not None:
            self.cache.evict(sig, reason="integrity")
        else:
            self._local_programs.pop(sig, None)

    def _merge_program(self, schema: tuple, base_cap: int,
                       run_cap: int, keys: tuple):
        sig = ResidentSignature(
            kind="merge", n_ranks=self.comm.n_ranks,
            build_schema=schema, build_capacity=base_cap,
            probe_schema=schema, probe_capacity=run_cap,
            handle=None, generation=None, options=(),
        )

        def build():
            step = make_run_merge_step(self.comm, key=list(keys))
            return self.comm.spmd(step,
                                  sharded_out=MERGE_SHARDED_OUT)

        fn, _ = self._program(sig, build)
        return fn, sig

    # -- registration / ingestion -------------------------------------

    def _validate(self, table: Table, keys: tuple) -> None:
        for k in keys:
            if k not in table.columns:
                self.refused += 1
                raise ResidentError(f"key column {k!r} missing from "
                                    "the build table")
            if not jnp.issubdtype(table.columns[k].dtype, jnp.integer):
                self.refused += 1
                raise ResidentError(
                    f"resident key {k!r} must be an integer column "
                    f"(got {table.columns[k].dtype}); string/float "
                    "keys go through the full join")
        for name, c in table.columns.items():
            if c.ndim != 1 or name.endswith("#len"):
                self.refused += 1
                raise ResidentError(
                    f"resident column {name!r} is not a scalar "
                    "column; 2-D/string payloads go through the "
                    "full join")

    def _prep(self, table: Table, keys: tuple, resident_rows: int):
        """Run the prep program (escalating the shuffle factor on
        overflow, the ladder discipline) and conservation-check the
        result against the INPUT pair the program measured before the
        shuffle — replicated scalars only, so no table shard is ever
        materialized host-side (multi-controller safe). Returns
        (run_table, rows, digest, capacity_per_rank)."""
        n = self.comm.n_ranks
        padded = table.pad_to(_round_up(table.capacity, n))
        if hasattr(self.comm, "device_put_sharded"):
            padded = self.comm.device_put_sharded(padded)
        factor = self.shuffle_capacity_factor
        schema = _schema_of(padded)
        for attempt in range(self.prep_retries + 1):
            b_cap = _round_up(int(math.ceil(
                padded.capacity / n / n * factor)), 8) if n > 1 else 0
            rows_needed = max(n * b_cap, padded.capacity // n)
            cap = max(resident_rows, _round_up(rows_needed, 8))
            fn, sig = self._prep_program(schema, padded.capacity,
                                         keys, cap, factor)
            run, rows, digest, rows_in, digest_in, overflow = \
                fn(padded)
            if not bool(overflow):
                rows, expected_rows = int(rows), int(rows_in)
                digest = int(np.asarray(digest))
                expected_digest = int(np.asarray(digest_in))
                if rows != expected_rows or digest != expected_digest:
                    self.refused += 1
                    self._evict_program(sig)
                    raise ResidentError(
                        "prep conservation check failed: "
                        f"{rows} rows / digest {digest:#x} out vs "
                        f"{expected_rows} rows / digest "
                        f"{expected_digest:#x} in — refusing to "
                        "bless a corrupt resident image")
                return run, rows, digest, cap
            factor *= 2.0
        self.refused += 1
        raise ResidentError(
            f"prep shuffle overflowed after {self.prep_retries + 1} "
            f"factor escalations (final {factor:g}); the key "
            "distribution is too skewed for resident registration")

    def register(self, name: str, build: Table, key="key", *,
                 replace: bool = False) -> ResidentTable:
        """Run the build-side 2/3 once and hold the result resident
        under ``name``. Refuses an existing name unless ``replace``."""
        keys = (key,) if isinstance(key, str) else tuple(key)
        if self.comm.n_ranks > 1 \
                and getattr(self.comm, "n_slices", 1) > 1:
            # The prep step (and every probe-only join after it)
            # routes flat GLOBAL collectives — on a multi-slice mesh
            # that drags intra-slice traffic across DCN. Hierarchical
            # resident serving is a named ROADMAP leftover; refuse at
            # the registration chokepoint, never mis-route.
            self.refused += 1
            raise ResidentError(
                "resident tables are served by flat global "
                "collectives; hierarchical (multi-slice) probe-only "
                "serving is not implemented yet — register on a flat "
                "1-D communicator")
        if name in self._tables and not replace:
            self.refused += 1
            raise ResidentError(
                f"resident table {name!r} already exists "
                "(pass replace=True to re-register)")
        if name not in self._tables \
                and len(self._tables) >= self.max_tables:
            self.refused += 1
            raise ResidentError(
                f"{len(self._tables)} resident tables already held "
                f"(max_tables={self.max_tables}); drop one first")
        self._validate(build, keys)
        n = self.comm.n_ranks
        b_local = _round_up(build.capacity, n) // n
        # Headroom for future deltas: capacity_factor over the local
        # rows, floored at what one shuffle receive block needs.
        resident_rows = _round_up(
            int(math.ceil(b_local * self.capacity_factor)), 8)
        run, rows, digest, cap = self._prep(build, keys, resident_rows)
        row_bytes = sum(
            np.dtype(c.dtype).itemsize for c in build.columns.values())
        handle = ResidentTable(name, keys, run, rows, digest, cap,
                               row_bytes, n)
        old = self._tables.get(name)
        with self._lock:
            self._tables[name] = handle
        if old is not None:
            self._evict_generation(old)
        self.registered += 1
        telemetry.event("resident_register", table=name, rows=rows,
                        capacity_per_rank=cap,
                        bytes=handle.bytes_resident)
        return handle

    def append(self, name: str, delta: Table, *,
               maintain: Optional[bool] = None) -> ResidentTable:
        """Land ``delta`` as a small sorted run on ``name``'s LSM
        queue and bump the generation (the visible image changed).
        ``maintain=None`` merges when the queue reaches
        ``maintain_runs``; True forces a merge now; False only
        queues. Joins always see appended rows — the serving path
        merges any pending queue before dispatch (merge-on-read)."""
        handle = self.get(name)
        keys = handle.keys
        self._validate(delta, keys)
        if _schema_of(delta) != _schema_of(handle.table):
            self.refused += 1
            raise ResidentError(
                f"delta schema does not match resident table "
                f"{name!r} — refusing the append")
        # Fixed delta slot so repeat appends share one prep program
        # (and one merge program) regardless of the delta's exact
        # row count.
        n = self.comm.n_ranks
        slot = _round_up(max(delta.capacity, n), self.delta_slot_rows)
        run, rows, digest, _ = self._prep(
            delta.pad_to(slot), keys,
            _round_up(max(slot // n, 8), 8))
        handle.pending_runs.append((run, rows, digest))
        handle.appends += 1
        self._bump_generation(handle)
        telemetry.event("resident_append", table=name,
                        delta_rows=rows, generation=handle.generation,
                        pending_runs=len(handle.pending_runs))
        if maintain or (maintain is None and
                        len(handle.pending_runs) >= self.maintain_runs):
            self.maintain(name)
        return handle

    def maintain(self, name: str) -> int:
        """Merge every pending sorted run into ``name``'s resident
        base (one merge dispatch per run; warm after the first).
        Returns the number of runs merged. A failed conservation
        check or a capacity overflow POISONS the handle — the base
        image may be half-merged, and serving it would be a guess."""
        handle = self.get(name)
        merged = 0
        while handle.pending_runs:
            run, run_rows, run_digest = handle.pending_runs[0]
            fn, msig = self._merge_program(
                _schema_of(handle.table), handle.capacity_per_rank,
                run.capacity, handle.keys)
            out, rows, digest, overflow = fn(handle.table, run)
            if bool(overflow):
                handle.poisoned = (
                    f"maintenance overflow: merged rows exceed the "
                    f"resident capacity {handle.capacity_per_rank}"
                    "/rank")
                self.refused += 1
                raise ResidentError(
                    f"resident table {name!r}: {handle.poisoned} — "
                    "re-register with a larger capacity_factor")
            rows = int(rows)
            digest = int(np.asarray(digest))
            want_rows = handle.rows + run_rows
            want_digest = (handle.key_digest + run_digest) % (1 << 64)
            if rows != want_rows or digest != want_digest:
                handle.poisoned = (
                    f"merge conservation check failed ({rows} rows / "
                    f"digest {digest:#x} vs expected {want_rows} / "
                    f"{want_digest:#x})")
                self.refused += 1
                self._evict_program(msig)
                raise ResidentError(
                    f"resident table {name!r}: {handle.poisoned} — "
                    "refusing to bless a corrupt merge")
            handle.table = out
            handle.rows = rows
            handle.key_digest = digest
            handle.pending_runs.pop(0)
            handle.merges += 1
            merged += 1
        if merged:
            telemetry.event("resident_maintain", table=name,
                            runs_merged=merged, rows=handle.rows,
                            generation=handle.generation)
        return merged

    def drop(self, name: str) -> None:
        handle = self._tables.get(name)
        if handle is None:
            self.refused += 1
            raise ResidentError(f"no resident table {name!r}")
        with self._lock:
            del self._tables[name]
        self._evict_generation(handle)
        self.dropped += 1
        telemetry.event("resident_drop", table=name)

    def _bump_generation(self, handle: ResidentTable) -> None:
        self._evict_generation(handle)
        handle.generation += 1

    def _evict_generation(self, handle: ResidentTable) -> None:
        """Invalidate exactly the probe-only entries compiled against
        ``handle``'s current image (other handles' and other
        generations' entries are untouched)."""
        if self.cache is not None:
            for sig in handle.cached_sigs:
                self.cache.evict(sig, reason="generation")
        else:
            # The cache-less local tier leaks otherwise: old-
            # generation signatures can never match again (the sig
            # embeds the generation) but would stay resident forever.
            for sig in handle.cached_sigs:
                self._local_programs.pop(sig, None)
        handle.cached_sigs = set()

    # -- the serving path ---------------------------------------------

    # NOTE on generation keying: today's probe-only programs close
    # over NO image-derived values (schema, capacity_per_rank, and
    # every option are generation-invariant), so binding the
    # generation into the cache key costs one re-trace per append per
    # probe shape that strictly-shape keying would avoid. The keying
    # is kept anyway as the conservative contract (docs/SERVICE.md):
    # the moment a future program DOES bake image state in (e.g.
    # sizing derived from handle.rows), strict keying is what stops a
    # stale executable from silently serving — and the eager
    # `evict(reason="generation")` keeps the churn visible.
    def probe_signature(self, handle: ResidentTable, probe: Table,
                        opts: dict) -> ResidentSignature:
        merged = {**_PROBE_STEP_DEFAULTS, **opts}
        unknown = set(merged) - set(_PROBE_STEP_DEFAULTS)
        if unknown:
            raise TypeError(
                f"unknown probe-only option(s) {sorted(unknown)}; "
                "the signature covers make_probe_join_step's keywords")
        return ResidentSignature(
            kind="probe_join", n_ranks=self.comm.n_ranks,
            build_schema=_schema_of(handle.table),
            build_capacity=handle.capacity_per_rank * self.comm.n_ranks,
            probe_schema=_schema_of(probe),
            probe_capacity=probe.capacity,
            handle=handle.name, generation=handle.generation,
            options=tuple(sorted(
                (name, _canon(v)) for name, v in merged.items())),
        )

    def workload_signature(self, name: str, probe: Table,
                           opts: dict) -> str:
        """The rung-stable workload identity of a probe-only join —
        GENERATION-FREE on purpose: appends move the data, not the
        workload, so the tuner's per-signature sizing history
        survives delta merges (the program cache still keys the
        generation; see :meth:`probe_signature`). ``with_metrics`` is
        stripped: callers hold it at different layers (the service
        passes it through opts, the registry takes it as a keyword),
        and the history's writer and reader must never key apart."""
        basis = json.dumps(
            {"handle": name, "n_ranks": self.comm.n_ranks,
             "probe": _schema_of(probe),
             "probe_capacity": probe.capacity,
             "opts": sorted((k, repr(v)) for k, v in opts.items()
                            if k != "with_metrics")},
            sort_keys=True, default=str)
        return "res-" + hashlib.sha256(basis.encode()).hexdigest()[:13]

    def join(self, name: str, probe: Table, *, auto_retry: int = 2,
             tuner=None, with_metrics=None, explain: bool = False,
             verify_integrity: bool = False, **opts):
        """One probe-only join against resident table ``name``: merge
        any pending delta runs first (so every join sees every
        append), then partition/shuffle/sort the probe side only and
        merge against the resident runs, through the program cache —
        the warm repeat is a dict lookup + dispatch with zero traces.
        Returns the :class:`~..ops.join.JoinResult` with the usual
        host-side ``retry_report`` (probe-side capacity ladder) and a
        ``resident`` record attribute.

        ``verify_integrity``: in-graph wire digests over the
        probe-side shuffle (``make_probe_join_step(with_integrity=)``)
        verified host-side before returning — the full join's
        integrity contract on the probe-only dispatch. A mismatch is
        the RETRYABLE ``retry_integrity`` rung: the tainted probe-only
        program is evicted and the SAME sizing re-traced (transport
        corruption is transient, capacities are innocent) up to the
        ``auto_retry`` budget, then
        :class:`~..parallel.integrity.IntegrityError` instead of
        corrupt rows. A verified clean result carries
        ``res.integrity_report``."""
        handle = self.get(name)
        # The workload signature is hashed FIRST — on the unpadded
        # probe and unmutated opts, the exact basis JoinService keys
        # its history entries on (resident_join hashes before
        # dispatch) — so the tuner's lookup and the store's writer
        # can never key apart.
        wsig = self.workload_signature(name, probe, opts)
        if opts.pop("skew_threshold", None) is not None or any(
                opts.get(k) is not None for k in
                ("hh_build_capacity", "hh_probe_capacity",
                 "hh_out_capacity")):
            self.refused += 1
            raise ResidentError(
                "the skew sidecar is not part of the probe-only "
                "program; run skewed workloads through the full join")
        opts.pop("hh_slots", None)
        if self.maintain(name):
            handle = self.get(name)
        if with_metrics is None:
            with_metrics = telemetry.enabled()
        n = self.comm.n_ranks
        probe = probe.pad_to(_round_up(probe.capacity, n))
        if hasattr(self.comm, "device_put_sharded"):
            probe = self.comm.device_put_sharded(probe)

        tuned = None
        if tuner is not None:
            tuned = tuner.resolve_resident(
                self.comm, handle.capacity_per_rank, probe,
                signature=wsig, opts=opts)
            opts = tuned.apply(opts)
        ladder = resolve_join_ladder(
            handle.table, probe, n, opts,
            n_slices=getattr(self.comm, "n_slices", 1))
        if tuned is not None:
            ladder.seed_rung(tuned.rung)
        key_opt = list(handle.keys) if len(handle.keys) > 1 \
            else handle.keys[0]
        from distributed_join_tpu.parallel import integrity

        with_aux = bool(with_metrics or verify_integrity)
        for attempt in range(auto_retry + 1):
            rung = ladder.base_rung + attempt
            sizing = {k: v for k, v in ladder.sizing().items()
                      if k in _PROBE_SIZING_KEYS}
            step_opts = dict(opts, key=key_opt,
                             with_metrics=with_metrics,
                             with_integrity=verify_integrity,
                             metrics_static={"retry_attempt_max": rung},
                             **sizing)
            sig = self.probe_signature(handle, probe, step_opts)

            def build(step_opts=step_opts):
                step = make_probe_join_step(self.comm, **step_opts)
                sharded = (JOIN_METRICS_SHARDED_OUT if with_aux
                           else JOIN_SHARDED_OUT)
                return self.comm.spmd(step, sharded_out=sharded)

            fn, hit = self._program(
                sig, build, example_args=(handle.table, probe),
                with_aux=with_aux)
            handle.cached_sigs.add(sig)
            with telemetry.span("resident_join", table=name,
                                generation=handle.generation) as sp:
                res = fn(handle.table, probe)
                if sp is not None:
                    sp.sync_on(res.total)
            overflow = bool(res.overflow)
            report = None
            if verify_integrity and not overflow:
                # Overflow attempts skip verification (clamped rows
                # mismatch by design; the overflow rung handles it) —
                # the full join's discipline, verbatim.
                report = integrity.verify_join_result(res)
            ladder.note(overflow,
                        integrity_ok=None if report is None
                        else report.ok)
            failed = overflow or (report is not None
                                  and not report.ok)
            if attempt == auto_retry or not failed:
                if report is not None and not report.ok:
                    # Budget exhausted on a wire mismatch: the
                    # resident program is as tainted as a retried one
                    # — evict it, then refuse loudly rather than
                    # handing corrupt rows to a probe-only caller.
                    self._evict_program(sig)
                    handle.cached_sigs.discard(sig)
                    raise integrity.IntegrityError(report)
                handle.joins_served += 1
                if hit:
                    handle.warm_joins += 1
                object.__setattr__(res, "retry_report",
                                   ladder.report())
                if report is not None:
                    object.__setattr__(res, "integrity_report",
                                       report)
                object.__setattr__(res, "resident", {
                    "table": name,
                    "generation": handle.generation,
                    "rows": handle.rows,
                    "warm": bool(hit),
                })
                if tuned is not None:
                    object.__setattr__(res, "tuned",
                                       tuned.as_record())
                if explain:
                    from distributed_join_tpu import planning

                    object.__setattr__(res, "plan", planning.
                                       build_probe_plan(
                        self.comm, handle.table, probe,
                        key=key_opt, digest=sig.digest(),
                        with_metrics=with_metrics,
                        **dict(opts, **sizing)))
                telemetry.emit_metrics(getattr(res, "telemetry",
                                               None))
                return res
            if overflow:
                ladder.escalate()
            else:
                # Integrity mismatch: rerun the SAME sizing — the
                # rows were wrong, not too many. The tainted entry is
                # evicted first so the rerun re-traces (injected
                # corruption is woven at trace time; a resident
                # executable that delivered corrupt rows must not
                # keep serving).
                self._evict_program(sig)
                handle.cached_sigs.discard(sig)
                ladder.hold("retry_integrity")
        raise AssertionError("unreachable")
