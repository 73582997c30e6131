"""Heavy-hitter (skew) handling — BASELINE config 3.

Hash partitioning routes every row of one key to one rank, so a
Zipf-skewed key column (alpha=1.5 concentrates a large fraction of all
rows on a handful of keys) both overloads one rank and — under this
framework's static-shape padded shuffle — forces every (rank, bucket)
pad up to the hottest bucket's size (SURVEY.md §7 hard part #2). The
reference has no skew machinery (its exact-size buffers merely survive
skew without balancing it); this framework does better with the classic
PRPD scheme (partial redistribution / partial duplication), designed
here for static shapes:

  1. detect heavy-hitter keys on device: each rank counts its probe-side
     key runs (one sort + searchsorted), takes its local top-K, and the
     K-slot candidate lists are all-gathered and aggregated; keys whose
     (approximate) global count exceeds ``threshold`` become the
     replicated HH set — a fixed K-slot array, identically computed on
     every rank;
  2. probe rows with HH keys are EXCLUDED from the shuffle and stay on
     their generating rank — they are balanced by construction (the
     generator hashes nothing), and the hot bucket disappears from the
     padded all-to-all;
  3. build rows with HH keys are broadcast (all-gather of a fixed-slot
     block) to every rank — the build side of a hot key is small (unique
     or low-multiplicity build keys), so duplication is cheap;
  4. each rank joins its local HH probe rows against the replicated HH
     build block; results concatenate with the normal path's.

Every row of a key takes exactly one path (the HH set is replicated and
consistent), so no match is lost or duplicated. Detection is
approximate — a key spread thin below every rank's local top-K can be
missed — but classification consistency, not accuracy, is what
correctness needs; a missed moderately-hot key just pays normal-path
padding.

Where the caller sets no ``skew_threshold``, ``distributed_inner_join``
decides from the input (:func:`resolve`): a small detection program
samples each rank's probe keys, estimates the fullest shuffle bucket
with and without the heavy keys, and counts the heavy keys' build rows.
The path turns on only where the heavy keys would overflow the padded
shuffle and diverting them relieves it; its blocks are then sized from
what was measured.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from distributed_join_tpu.ops.join import _dtype_sentinel_max
from distributed_join_tpu.parallel.communicator import Communicator, _pvary
from distributed_join_tpu.table import Table

# The entry's resolution (:func:`resolve`).
RESOLVE_SAMPLE_ROWS = 1 << 15  # probe rows a rank samples
RESOLVE_THRESHOLD = 0.001      # the driver's measured sweep default
RESOLVE_SLACK = 1.25           # an HH block's headroom over its estimate
RESOLVE_GRID = 8               # HH blocks round up to 1/8 of rank rows


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HeavyHitters:
    """A fixed-K replicated set of heavy keys. Invalid slots hold the
    key dtype's max sentinel and are masked by ``slot_valid``."""

    keys: jax.Array        # (K,) key dtype
    counts: jax.Array      # (K,) int64 approximate global counts; when
    #                        sampled detection ran (the default), these
    #                        are sampled tallies scaled by ``sample`` —
    #                        ESTIMATES, not exact tallies.
    slot_valid: jax.Array  # (K,) bool


def _zipf_partial_sum(alpha: float, n: int) -> float:
    """sum_{r=1..n} r^-alpha — exact head, midpoint-integral tail
    (relative error < 1e-6 for the n up to 1e8 seen here)."""
    import numpy as np

    m = min(n, 1_000_000)
    s = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -alpha))
    if n > m:
        if abs(alpha - 1.0) < 1e-9:
            s += math.log((n + 0.5) / (m + 0.5))
        else:
            s += (
                (m + 0.5) ** (1.0 - alpha) - (n + 0.5) ** (1.0 - alpha)
            ) / (alpha - 1.0)
    return s


def zipf_top_k_mass(alpha: float, n_keys: int, k: int) -> float:
    """Expected fraction of Zipf(``alpha``) draws over ``n_keys`` keys
    that land on the ``k`` most probable keys — the capacity model
    behind the driver's skew auto-policy (round 5): with the HH set
    sized ``k`` slots, ~this fraction of each rank's probe rows takes
    the HH path, so the HH probe/output blocks can be PRE-sized from a
    known alpha instead of overflowing into an auto_retry recompile
    (alpha >= 1.4 puts ~90% of rows in the top-64; the old p_rows/8
    default overflowed by design)."""
    if n_keys <= 0 or k <= 0:
        return 0.0
    return _zipf_partial_sum(alpha, min(k, n_keys)) / _zipf_partial_sum(
        alpha, n_keys
    )


def local_top_keys(keys: jax.Array, valid: jax.Array, k: int):
    """Per-shard top-``k`` keys by frequency: (keys, counts), padded
    slots carrying count 0. ONE single-operand sort; run lengths come
    from forward/backward scans over the change marks (round 1's two
    ``method="sort"`` searchsorteds re-sorted the shard twice more —
    measured 40x the sort itself at 10M rows on v5e). Always returns
    ``k`` slots even when the shard has fewer rows (extra slots pad)."""
    n = keys.shape[0]
    k_eff = min(k, n)  # lax.top_k rejects k > array length
    sentinel = _dtype_sentinel_max(keys.dtype)
    sk = lax.sort((jnp.where(valid, keys, sentinel),))[0]
    n_valid = jnp.sum(valid.astype(jnp.int32))
    iota = jnp.arange(n, dtype=jnp.int32)
    changed = sk != jnp.concatenate([sk[:1], sk[:-1]])
    changed = changed & (iota > 0)
    first = changed | (iota == 0)
    # next run start after i (exclusive) via a reverse cummin.
    nxt_incl = jnp.flip(lax.cummin(jnp.flip(
        jnp.where(changed, iota, jnp.int32(n))
    )))
    nxt = jnp.concatenate([nxt_incl[1:], jnp.full((1,), n, jnp.int32)])
    # Valid rows sort before the sentinel block; clamping the run end to
    # n_valid counts only real rows (a real key == sentinel undercounts
    # into the padding block — harmless for approximate detection).
    run = jnp.minimum(nxt, n_valid) - iota
    score = jnp.where(first & (iota < n_valid), run, 0)
    top_counts, top_idx = lax.top_k(score, k_eff)
    top_keys = jnp.where(top_counts > 0, sk[top_idx], sentinel)
    if k_eff < k:
        pad = k - k_eff
        top_keys = jnp.concatenate(
            [top_keys, jnp.full((pad,), sentinel, dtype=top_keys.dtype)]
        )
        top_counts = jnp.concatenate(
            [top_counts, jnp.zeros((pad,), dtype=top_counts.dtype)]
        )
    return top_keys, top_counts


def _sample_index(m: int, n: int) -> jax.Array:
    """``m`` quasi-random row positions of ``n``. The odd multiplier
    cycles positions through all residues of every power-of-two
    period: near-uniform coverage of any periodic layout. Collisions
    (gcd(C, n) > 1) repeat a few rows — harmless for approximate
    counting."""
    return ((jnp.arange(m, dtype=jnp.int64) * jnp.int64(2654435761))
            % jnp.int64(n)).astype(jnp.int32)


def global_heavy_hitters(
    comm: Communicator,
    keys: jax.Array,
    valid: jax.Array,
    k: int,
    threshold,
    sample: int = 16,
) -> HeavyHitters:
    """Replicated global top-``k`` keys with aggregated count >
    ``threshold`` (a traced or static int). Aggregation is exact over
    the union of per-rank candidate lists (a key missing from some
    rank's list undercounts — see module docstring).

    ``sample``: detection runs on a 1/``sample`` quasi-random subset —
    a heavy key (count > threshold, typically >=0.1% of rows) appears
    hundreds of times in the sample, so detection stays robust while
    the top-keys sort drops from the full shard to n/sample rows (the
    sort WAS ~half the skew path's fixed overhead at 10M uniform
    rows). Rows are picked by a multiplicative index mix, NOT a fixed
    stride — ``keys[::16]`` would deterministically miss a heavy key
    living only at positions != 0 mod 16 (periodic layouts from
    round-robin re-partitions do exist; review r4). Counts and
    threshold are compared in sampled units; reported counts are
    scaled back up. Small shards (n < 64*k*sample) disable sampling.
    Classification CONSISTENCY across sides/ranks — what correctness
    needs — is unaffected: it comes from the replicated HH set, not
    from who sampled what."""
    n = keys.shape[0]
    if sample > 1 and n >= 64 * k * sample:
        idx = _sample_index(n // sample, n)
        keys_d = keys[idx]
        valid_d = valid[idx]
        # A threshold below ``sample`` truncates to 0, which would make
        # EVERY sampled key (count >= 1) HH-eligible and divert up to k
        # arbitrary keys to the HH path — clamp to 1 sampled occurrence.
        thr = jnp.maximum(threshold // sample, 1)
    else:
        sample = 1
        keys_d, valid_d, thr = keys, valid, threshold
    lk, lc = local_top_keys(keys_d, valid_d, k)
    gk = comm.all_gather(lk)                      # (n*k,)
    gc = comm.all_gather(lc)                      # (n*k,) int32
    nk = gk.shape[0]
    sentinel = _dtype_sentinel_max(keys.dtype)
    eq = gk[:, None] == gk[None, :]
    # int64 accumulation: per-rank counts fit int32, but a globally hot
    # key summed over many ranks can pass 2^31 — an int32 wrap here
    # would silently demote the hottest key to the normal path.
    tot = jnp.sum(
        jnp.where(eq, gc[None, :].astype(jnp.int64), 0), axis=1
    )
    iota = jnp.arange(nk)
    dup = jnp.any(eq & (iota[None, :] < iota[:, None]), axis=1)
    real = gk != sentinel
    score = jnp.where(real & ~dup, tot, 0)
    top_counts, top_idx = lax.top_k(score, k)
    slot_valid = top_counts > thr
    hh_keys = jnp.where(slot_valid, gk[top_idx], sentinel)
    return HeavyHitters(hh_keys, top_counts * sample, slot_valid)


# Above this K, mark_heavy's unrolled compare chain would bloat the
# program; the rolled fori_loop costs ~100s of us of device-loop
# overhead PER SLOT (docs/ROOFLINE.md §6), so unrolling is the fast
# path for the default K=64.
_MARK_UNROLL_MAX = 512


def mark_heavy(keys: jax.Array, hh: HeavyHitters) -> jax.Array:
    """Row-wise bool: key is in the HH set. K elementwise passes — no
    (rows, K) materialization (which would be GBs at 10M rows)."""
    K = hh.keys.shape[0]
    if K <= _MARK_UNROLL_MAX:
        acc = keys != keys
        for j in range(K):
            acc = acc | ((keys == hh.keys[j]) & hh.slot_valid[j])
        return acc

    def body(j, acc):
        hk = lax.dynamic_index_in_dim(hh.keys, j, keepdims=False)
        hv = lax.dynamic_index_in_dim(hh.slot_valid, j, keepdims=False)
        return acc | ((keys == hk) & hv)

    # Init derived from `keys` (all-False, same shape) so the carry is
    # rank-varying under shard_map's vma tracking, like the body output.
    return lax.fori_loop(0, K, body, keys != keys)


def extract_prefix(table: Table, sel: jax.Array, capacity: int,
                   kernel_config=None):
    """Stable-compact rows where ``sel`` into a static-capacity Table;
    returns (extracted, count, overflow). Where ``capacity`` is at
    least the table's row count, the block is the table itself with
    ``sel`` as its validity (padded to ``capacity``): the join reads a
    mask as it reads a prefix, so nothing is sorted or gathered.

    On TPU the selected row INDICES are packed by the streaming
    log-shift compaction kernel (ops/compact_planes.py — the round-3
    VERDICT's named fix for the HH path re-sorting the full probe),
    then one composed row gather materializes the block at ``capacity``
    rows, so the cost scales with ``capacity``, not the table. Off-TPU
    (and under the interpreter) a 32-bit sort does the same job — the
    kernel carry chain is slow to interpret."""
    from distributed_join_tpu.ops.kernel_config import resolve

    n = sel.shape[0]
    count = jnp.sum(sel.astype(jnp.int32))
    if capacity >= n:
        return (Table(table.columns, sel).pad_to(capacity), count,
                jnp.bool_(False))
    cfg = resolve(kernel_config)
    use_kernel, interpret = cfg.expand_enabled()
    lane = jnp.arange(capacity, dtype=jnp.int32)
    if use_kernel and not interpret and n >= 2 * capacity:
        from distributed_join_tpu.ops.compact_pallas import stream_compact
        from distributed_join_tpu.ops.compact_planes import (
            plane_stream_compact,
        )

        compact = (
            plane_stream_compact
            if cfg.use_plane_compact(interpret) else stream_compact
        )
        pos = jnp.cumsum(sel.astype(jnp.int32)) - 1
        iota64 = jnp.arange(n, dtype=jnp.uint64)
        kw = {"block": cfg.block} if cfg.block else {}
        (packed_idx,) = compact(sel, pos, [iota64], capacity,
                                interpret=interpret, name="hh_extract",
                                **kw)
        # Slots >= the survivor count are UNDEFINED in the kernel's
        # contract — clamp before gathering; `valid` masks them.
        idx = jnp.clip(packed_idx.astype(jnp.int32), 0, n - 1)
    else:
        # 32-bit stable sort (jnp.argsort under x64 would carry int64
        # lanes).
        _, order = lax.sort(
            ((~sel).astype(jnp.int8), jnp.arange(n, dtype=jnp.int32)),
            num_keys=1, is_stable=True,
        )
        idx = order[jnp.minimum(lane, n - 1)]
    cols = {name: c[idx] for name, c in table.columns.items()}
    valid = lane < jnp.minimum(count, capacity)
    return Table(cols, valid), count, count > capacity


def broadcast_heavy_build(
    comm: Communicator, build: Table, is_hh: jax.Array, capacity: int,
    kernel_config=None,
):
    """All-gather each rank's HH build rows (fixed ``capacity`` slots)
    into one replicated Table of n_ranks*capacity rows."""
    local, count, overflow = extract_prefix(
        build, is_hh & build.valid, capacity,
        kernel_config=kernel_config)
    cols = {n: comm.all_gather(c) for n, c in local.columns.items()}
    valid = comm.all_gather(local.valid)
    return Table(cols, valid), comm.psum(overflow.astype(jnp.int32)) > 0


# -- the entry's resolution from the input ----------------------------


class _AxisGather:
    """The one collective :func:`global_heavy_hitters` asks of a
    communicator, on a mesh axis: the detection program runs beside
    the join's programs, outside any communicator wrapper that counts
    or perturbs them."""

    def __init__(self, axis_name):
        self.axis_name = axis_name

    def all_gather(self, x):
        return lax.all_gather(x, self.axis_name, axis=0, tiled=True)


@dataclasses.dataclass(frozen=True)
class Resolution:
    """What the detection program measured, in probe rows of the
    fullest rank, and the join options resolved from it (empty where
    the path stays off)."""

    on: bool
    bucket_capacity: int       # the padded shuffle's per-bucket rows
    fullest_bucket: int        # estimated rows of the fullest bucket
    hh_probe_rows: int         # estimated heavy-key probe rows
    options: dict

    def as_args(self) -> dict:
        """The ``entry.skew_resolve`` span's arguments."""
        return {"on": int(self.on),
                "hh_probe_rows": self.hh_probe_rows,
                "fullest_bucket": self.fullest_bucket,
                "bucket_capacity": self.bucket_capacity,
                **{k: self.options.get(k, 0) for k in (
                    "hh_probe_capacity", "hh_build_capacity",
                    "hh_out_capacity")}}


@functools.lru_cache(maxsize=32)
def _detection_program(mesh, axis_name, keys: tuple, n_buckets: int,
                       bucket_capacity: int, sample_rows: int,
                       hh_slots: int, threshold: int):
    """One SPMD program over ``mesh`` (cached per static sizing; jit
    caches per shape): ``(build, probe)`` -> a replicated int64 vector
    ``[on, fullest, hh_probe, hh_out, hh_build,
    max_build_rows_per_key]``: the fullest bucket and the heavy probe
    and output rows in sampled rows of the fullest rank, the heavy
    build rows of the fullest rank."""
    from jax.sharding import PartitionSpec as P

    from distributed_join_tpu.ops.hashing import hash_columns
    from distributed_join_tpu.utils.strings import prepare_string_key_join

    def pmax(x):
        # A TPU all-reduces 64-bit integers by sum alone: the maximum
        # goes over 32 bits, saturated (counts of one rank fit).
        x = jnp.minimum(x, jnp.iinfo(jnp.int32).max).astype(jnp.int32)
        return lax.pmax(x, axis_name).astype(jnp.int64)

    def step(build, probe):
        # String keys hash as the step hashes them: packed words.
        build, probe, keys_eff, *_ = prepare_string_key_join(
            build, probe, list(keys), None, None)
        p_rows = probe.capacity
        cols = [probe.columns[k] for k in keys_eff]
        valid = probe.valid
        if sample_rows < p_rows:
            idx = _sample_index(sample_rows, p_rows)
            cols = [c[idx] for c in cols]
            valid = valid[idx]
        h = hash_columns(cols)
        hh = global_heavy_hitters(_AxisGather(axis_name), h, valid,
                                  hh_slots, threshold=threshold,
                                  sample=1)
        # (rows, slots) and (rows, buckets) over the sample only.
        slot = ((h[:, None] == hh.keys[None, :]) & hh.slot_valid[None, :]
                & valid[:, None])
        heavy = jnp.any(slot, axis=1)
        dest = (h % jnp.uint64(n_buckets)).astype(jnp.int32)
        to = dest[:, None] == jnp.arange(n_buckets, dtype=jnp.int32)
        fullest = pmax(jnp.max(jnp.sum(to & valid[:, None], axis=0)))
        rest = pmax(jnp.max(jnp.sum(to & (valid & ~heavy)[:, None],
                                    axis=0)))
        # The heavy keys overflow the shuffle, and diverting them
        # relieves it (sampled rows against the capacity, scaled).
        cap = jnp.int64(bucket_capacity) * sample_rows
        on = ((pmax(jnp.sum(hh.slot_valid)) > 0)
              & (fullest * p_rows > cap) & (rest * p_rows <= cap))

        def count_build(_):
            bh = hash_columns([build.columns[k] for k in keys_eff])
            hit = ((bh[:, None] == hh.keys[None, :])
                   & hh.slot_valid[None, :] & build.valid[:, None])
            return jnp.sum(hit, axis=0, dtype=jnp.int64)

        def no_count(_):
            return _pvary(jnp.zeros((hh_slots,), jnp.int64), axis_name)

        # Only a resolution that turns on reads the whole build side.
        local_b = lax.cond(on, count_build, no_count, None)
        per_key = lax.psum(local_b, axis_name)
        out = jnp.sum(jnp.where(slot, per_key[None, :], 0))
        return jnp.stack([
            on.astype(jnp.int64), fullest, pmax(jnp.sum(heavy)),
            pmax(out), pmax(jnp.sum(local_b)), jnp.max(per_key),
        ])

    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=P(axis_name), out_specs=P()))


def resolve(comm: Communicator, build: Table, probe: Table, key, *,
            n_buckets: int, bucket_capacity: int, hh_slots: int,
            hh_build_floor: int) -> Resolution:
    """Decide the skew path from the input, for row-sharded global
    tables over ``comm``'s mesh (runs the detection program and reads
    its result). ``n_buckets`` and ``bucket_capacity`` are the padded
    shuffle's buckets and per-bucket rows; ``hh_build_floor`` is the
    smallest HH build block.

    On, the options set ``skew_threshold`` (:data:`RESOLVE_THRESHOLD`)
    and the three HH blocks, each from what was measured with
    :data:`RESOLVE_SLACK` of headroom, and rounded up so that tables of
    one shape and distribution resolve alike: the probe and output
    blocks to eighths of a rank's probe rows (the probe block at most
    all of them; the output block at most the probe block times the
    most build rows of one heavy key), the build block to a power of
    two. An estimate that falls short costs an overflow flag (the
    ladder's to relieve), never a wrong row."""
    import numpy as np

    n = comm.n_ranks
    p_rows = probe.capacity // n
    m = min(RESOLVE_SAMPLE_ROWS, p_rows)
    keys = (key,) if isinstance(key, str) else tuple(key)
    threshold = int(RESOLVE_THRESHOLD * p_rows) * m // p_rows
    fn = _detection_program(comm.mesh, comm.axis_name, keys, n_buckets,
                            bucket_capacity, m, hh_slots, threshold)
    on, fullest, hh_probe, hh_out, hh_build, per_key = (
        int(v) for v in np.asarray(fn(build, probe)))

    def rows(sampled):
        return -(-sampled * p_rows // m)

    unit = -(-p_rows // RESOLVE_GRID)
    unit += -unit % 8

    def grid(x):
        return max(1, -(-math.ceil(x) // unit)) * unit

    options = {}
    if on:
        probe_cap = min(grid(rows(hh_probe) * RESOLVE_SLACK), p_rows)
        options = {
            "skew_threshold": RESOLVE_THRESHOLD,
            "hh_probe_capacity": probe_cap,
            "hh_out_capacity": min(grid(rows(hh_out) * RESOLVE_SLACK),
                                   grid(probe_cap * per_key)),
            "hh_build_capacity": max(
                hh_build_floor,
                1 << max(0, math.ceil(hh_build * RESOLVE_SLACK) - 1
                         ).bit_length()),
        }
    return Resolution(bool(on), bucket_capacity, rows(fullest),
                      rows(hh_probe), options)
