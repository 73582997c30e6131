"""Radix hash partition — the TPU equivalent of ``cudf::hash_partition``.

The reference's partition step (SURVEY.md §2 "Hash partition step") is a
Murmur3 radix scatter on GPU. Scatters are a poor fit for the TPU memory
system, so the TPU-native formulation is sort-based (SURVEY.md §7 step 1):

    hash -> bucket id -> ONE stable sort by bucket that carries every
    1-D column as a value operand -> offsets

The sort moves the data: its keys are the bucket id (and the optional
within-bucket order), its values every 1-D column and last a row-index
iota, the row permutation ``order`` (XLA drops it on the CPU where
nothing reads it; the TPU compiler makes a stable sort by adding such an
index lane anyway, and reuses this one). Each bucket's rows
then lie contiguously in every sorted column, so ``to_padded`` packs a
destination's ``(capacity,)`` lane block with one ``dynamic_slice`` per
bucket — a copy at HBM rate — and no per-row gather. The reason is
docs/ROOFLINE.md §1: XLA's TPU gather is a serialized per-element loop
(~9 ns per i32 element, ~21 ns per i64, whatever the index order), while
a sort moves its value operands almost for free. On the four-chip
(TPU v5e) 50M x 50M join the old formulation — a sort of
(bucket, row index), then one gather per 32-bit half of each column
through ``order[offset + lane]`` — spent 1.9 s of a 2.66 s call in those
gathers; carrying the int64 key and payload through the sort instead
costs about 20 ms per column and side at 12.5M rows a chip. Columns that are not 1-D (fixed-width string bytes,
``(n, W) uint8``) cannot be sort operands; they alone are still gathered
through ``order``.

The result is exactly what the reference's all-to-all needs: rows
grouped by destination bucket plus a per-bucket offset/count vector
(the reference exchanges the same counts in its metadata all-to-all,
SURVEY.md §2 "Size-exchange helper"). Overflow (a bucket larger than
the static capacity) is reported per call so the caller can re-run with
a bigger pad or trigger the skew path.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from distributed_join_tpu.ops.hashing import bucket_ids
from distributed_join_tpu.table import Table


# Below this many buckets, offsets come from a compare-and-sum over
# the rows instead of a binary search: it costs rows x buckets
# compares, so it is kept to few buckets.
_COMPARE_ALL_BUCKETS = 64


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PartitionedTable:
    """A table's rows grouped by bucket: the 1-D columns in
    bucket-sorted order (values of the partition's sort), the rest
    reached through the bucket-sorted row permutation. Invalid rows
    sort after every real bucket.

    Attributes:
      source:  the original (unsorted) table.
      order:   (capacity,) int32 row permutation, bucket-sorted.
      offsets: (n_buckets + 1,) int32; bucket b occupies positions
               ``offsets[b] : offsets[b+1]`` of ``order`` and of every
               sorted column.
      counts:  (n_buckets,) int32 == diff(offsets).
      sorted_columns: name -> the 1-D column ``name`` of ``source`` in
               bucket-sorted order, produced by the sort itself (no
               gather).
    """

    source: Table
    order: jax.Array
    offsets: jax.Array
    counts: jax.Array
    sorted_columns: Mapping[str, jax.Array]

    @property
    def n_buckets(self) -> int:
        return self.counts.shape[0]

    @property
    def gathered_columns(self) -> int:
        """Columns packed by a gather through ``order`` (the ones the
        sort could not carry: not 1-D)."""
        return len(self.source.columns) - len(self.sorted_columns)

    @property
    def table(self) -> Table:
        """The bucket-sorted table: the sorted columns as they are, a
        gather through ``order`` for the others. Valid rows form a
        prefix (every invalid row sorts last)."""
        cols = {
            n: self.sorted_columns[n] if n in self.sorted_columns
            else c[self.order]
            for n, c in self.source.columns.items()
        }
        lane = jnp.arange(self.source.capacity, dtype=jnp.int32)
        return Table(cols, lane < jnp.sum(self.counts))

    def to_padded(self, capacity: int, bucket_start: int = 0,
                  n_buckets: int | None = None):
        """Dense (n_buckets, capacity) layout for fixed-shape all-to-all.

        ``bucket_start``/``n_buckets`` select a contiguous bucket range —
        the over-decomposition path shuffles one batch (= one range of
        n_ranks buckets) at a time, exactly like the reference's batched
        pipeline (SURVEY.md §2 "Over-decomposition").

        A sorted column's block for bucket ``b`` is one contiguous
        ``dynamic_slice`` of ``capacity`` rows at ``offsets[b]``, taken
        from the column padded at its end by ``capacity`` rows so that
        no start is clamped (a clamped start would shift a bucket near
        the table's end). Lanes past a bucket's count hold whatever
        follows it (the next buckets' rows, invalid rows, the pad):
        ``row_valid`` masks them. Columns that are not 1-D are gathered
        through ``order`` instead.

        Returns (padded_columns: dict name -> (n_buckets, capacity) array,
        counts clipped to capacity, overflow: bool scalar — True iff some
        selected bucket exceeded the capacity and rows were dropped,
        row_valid: (n_buckets, capacity) bool mask).
        """
        nb = self.n_buckets if n_buckets is None else n_buckets
        offs = self.offsets[bucket_start : bucket_start + nb]
        counts = self.counts[bucket_start : bucket_start + nb]
        lane = jnp.arange(capacity, dtype=jnp.int32)
        row_valid = lane[None, :] < counts[:, None]
        idx = None
        padded = {}
        for n, c in self.source.columns.items():
            if n in self.sorted_columns:
                s = jnp.pad(self.sorted_columns[n], (0, capacity))
                padded[n] = jnp.stack([
                    jax.lax.dynamic_slice_in_dim(s, offs[j], capacity)
                    for j in range(nb)
                ])
                continue
            if idx is None:
                # Compose the bucket-slot -> sorted-position ->
                # source-row maps so the column is gathered once,
                # straight into its padded layout.
                pos = jnp.clip(offs[:, None] + lane[None, :], 0,
                               self.source.capacity - 1)
                idx = self.order[pos]
            padded[n] = c[idx]
        overflow = jnp.any(counts > capacity)
        return padded, jnp.minimum(counts, capacity), overflow, row_valid


def radix_hash_partition(
    table: Table, key_cols: Sequence[str], n_buckets: int,
    order_within: str | None = None, sub_buckets: int = 1,
) -> PartitionedTable:
    """Partition ``table`` into ``n_buckets`` by hash of ``key_cols``.

    ``order_within`` names a 1-D integer column; when given, rows
    within each bucket additionally sort by it DESCENDING. The
    variable-width string wire (parallel/shuffle.shuffle_ragged's
    ``varwidth``) relies on this: with rows ordered by byte length
    desc, the rows still alive at u32 word-plane ``w`` form a PREFIX
    of every bucket, so each plane ships as one ragged slice.

    ``sub_buckets`` > 1 partitions at FINE granularity: the result has
    ``n_buckets * sub_buckets`` buckets, fine id ``coarse *
    sub_buckets + seg`` with ``seg`` drawn from the hash bits above
    the coarse modulus (ops/hashing.bucket_ids). The coarse routing is
    unchanged — fine buckets of one coarse bucket are contiguous —
    so the segmented-sort pipeline's sub-bucket ordering rides the
    SAME partition sort the flat pipeline already pays for (the
    zero-added-routing-cost contract of docs/ROOFLINE.md §9).
    Incompatible with ``order_within`` (the ragged varwidth wire and
    the segmented layout are disjoint modes by contract)."""
    if sub_buckets > 1 and order_within is not None:
        raise ValueError(
            "sub_buckets and order_within are mutually exclusive: the "
            "within-bucket order slot is either the segment id or the "
            "varwidth length, never both")
    b = bucket_ids([table.columns[c] for c in key_cols], n_buckets,
                   sub_buckets=sub_buckets)
    n_buckets = n_buckets * max(int(sub_buckets), 1)
    # Padding rows get bucket n_buckets so they sort after every real bucket.
    b = jnp.where(table.valid, b, jnp.int32(n_buckets))
    # One stable sort: 32-bit keys (bucket id, the optional order), the
    # 1-D columns and an int32 row index as values — NOT jnp.argsort,
    # whose x64-mode int64 iota would double that lane on TPU (emulated
    # 64-bit).
    keys = [b]
    if order_within is not None:
        oc = table.columns[order_within]
        if oc.ndim != 1 or not jnp.issubdtype(oc.dtype, jnp.integer):
            raise TypeError(
                f"order_within column {order_within!r} must be a 1-D "
                f"integer column, got ndim={oc.ndim} dtype={oc.dtype}"
            )
        keys.append(-oc.astype(jnp.int32))
    carried = [name for name, c in table.columns.items() if c.ndim == 1]
    out = jax.lax.sort(
        (*keys, *(table.columns[name] for name in carried),
         jnp.arange(b.shape[0], dtype=jnp.int32)),
        num_keys=len(keys), is_stable=True,
    )
    order = out[-1]
    sorted_columns = dict(zip(carried, out[len(keys):-1]))
    # Bucket starts. For a few buckets, count the rows below each id in
    # one compare-and-sum pass (no gather); for many, binary-search
    # (its gathers touch n_buckets + 1 elements a step).
    offsets = jnp.searchsorted(
        out[0], jnp.arange(n_buckets + 1, dtype=jnp.int32),
        side="left",
        method="compare_all" if n_buckets < _COMPARE_ALL_BUCKETS
        else "scan",
    ).astype(jnp.int32)
    counts = jnp.diff(offsets)
    return PartitionedTable(table, order, offsets, counts, sorted_columns)


def unpad(padded_columns, counts, capacity: int) -> Table:
    """Inverse-ish of ``to_padded`` after a shuffle: flatten a
    (n_src, capacity) block received from n_src peers into a flat Table
    whose validity mask marks the first counts[s] rows of each stripe."""
    lane = jnp.arange(capacity, dtype=jnp.int32)
    valid = (lane[None, :] < counts[:, None]).reshape(-1)
    # Flatten only the (src, lane) dims; trailing dims (e.g. the byte
    # axis of fixed-width string columns) ride along.
    cols = {
        n: c.reshape((-1,) + c.shape[2:]) for n, c in padded_columns.items()
    }
    return Table(cols, valid)
