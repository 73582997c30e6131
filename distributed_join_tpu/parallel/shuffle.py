"""Two-phase all-to-all table shuffle.

The reference's shuffle (SURVEY.md §2 "All-to-all shuffle of a cuDF
table", §3.1) is: exchange per-bucket row counts (metadata all-to-all),
allocate exact-size receive buffers, then per column per peer post
send/recv of the bucket slice. XLA has no dynamic receive sizes, so the
TPU formulation pads each bucket to a static per-destination capacity:

  phase 1: ``all_to_all`` of the (n_ranks,) int32 count vector;
  phase 2: ``all_to_all`` of each column laid out (n_ranks, capacity).

The received block flattens into a validity-masked Table (padding rows
carry the mask, not a sentinel). Overflow — a bucket bigger than the
static capacity — is detected on device and reported so the caller can
retry with a larger pad or engage the skew path (BASELINE config 3).

Bandwidth note: padding inflates bytes on the wire by ~1/load-factor.
For uniform keys capacity_factor ~1.2-1.5 keeps that small; the skew
path exists precisely because one hot bucket would otherwise set the pad
for everyone (SURVEY.md §7 hard part #2).

:func:`shuffle_ragged` removes the pad bytes entirely — the reference's
exact-size exchange (counts first, then exactly ``count`` rows per
peer), expressed with ``lax.ragged_all_to_all``. Phase 1 becomes an
``all_gather`` of each rank's count vector: the full (n, n) count
matrix is what lets every rank compute, consistently and without more
communication, its send/recv sizes, where its blocks land in every
receiver's buffer, and a deterministic clamp when a receiver's static
output capacity would overflow. The hardware op only exists on TPU
(XLA:CPU has no ragged-all-to-all thunk), so non-TPU backends run a
bit-identical emulation — see ``Communicator.ragged_all_to_all``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from distributed_join_tpu.ops.partition import PartitionedTable, unpad
from distributed_join_tpu.parallel.communicator import Communicator
from distributed_join_tpu.table import Table


def shuffle_padded(
    comm: Communicator, padded_columns, counts: jax.Array, capacity: int,
    via: str = "all_to_all", tape=None, digest_tape=None,
) -> Tuple[Table, jax.Array]:
    """Shuffle a pre-padded (n_ranks, capacity) block; returns the
    received rows as a masked Table plus the received counts.

    ``via='ppermute'`` moves the data blocks over a chain of
    collective-permutes instead of one grouped all-to-all — same
    bytes and result, but an async-schedulable lowering (see
    Communicator.ppermute_all_to_all / docs/OVERLAP.md).

    ``tape`` (a ``telemetry.MetricsTape`` view, or None) receives the
    wire accounting: ``rows_shuffled``/``rows_received`` are ACTUAL
    rows (the count vectors), ``wire_bytes`` the data-plane bytes the
    collective moves — for this padded layout the full static
    ``n_ranks x capacity`` block per column, pad included, because
    that IS what rides the wire (the ~1/load-factor inflation the
    module docstring describes, now measurable per run). Metadata
    (the count exchange) is not billed; see docs/OBSERVABILITY.md.

    ``digest_tape`` (a ``MetricsTape`` view, or None) receives the
    wire-integrity digests (parallel/integrity.py): per destination
    the digest of the rows this rank ROUTED there (computed on the
    pre-exchange block) and per source the digest of the rows it
    BELIEVES it received (the post-exchange block under its own —
    possibly corrupted — received counts); the host-side pair check
    is ``integrity.verify_digests``."""
    a2a = (
        comm.ppermute_all_to_all if via == "ppermute" else comm.all_to_all
    )
    recv_counts = comm.all_to_all(counts)
    recv_cols = {n: a2a(c) for n, c in padded_columns.items()}
    if digest_tape is not None:
        from distributed_join_tpu.parallel import integrity

        integrity.record_pair_digests(
            digest_tape,
            integrity.padded_block_digests(padded_columns, counts),
            integrity.padded_block_digests(recv_cols, recv_counts),
        )
    if tape is not None:
        tape.add("rows_shuffled", jnp.sum(counts.astype(jnp.int64)))
        tape.add("rows_received",
                 jnp.sum(recv_counts.astype(jnp.int64)))
        tape.add("wire_bytes",
                 sum(c.size * c.dtype.itemsize
                     for c in padded_columns.values()))
    return unpad(recv_cols, recv_counts, capacity), recv_counts


def shuffle_padded_compressed(
    comm: Communicator, padded_columns, counts: jax.Array, capacity: int,
    bits: int, block: int = 256, via: str = "all_to_all", tape=None,
    digest_tape=None,
) -> Tuple[Table, jax.Array, jax.Array]:
    """Padded shuffle with the FoR+bitpack codec on the wire.

    The reference's ``--compression`` path: compress each partition
    buffer before the all-to-all, decompress after (SURVEY.md §2
    "nvcomp compression"). Here every integer column's per-destination
    block is encoded row-wise (one frame stream per destination, so
    the all-to-all's leading-axis redistribution never splits a codec
    block across destinations), the uint32 word + int64 frame planes
    ride the collective, and receivers decode.

    Static shapes force the codec's compile-time ``bits`` contract
    (ops/compression.py): a block whose frame-of-reference residual
    exceeds ``bits`` cannot pack losslessly, so the returned
    ``compression_overflow`` flag fires and the caller must retry
    wider (``distributed_inner_join``'s auto_retry doubles bits up to
    32) — rows are never silently corrupted. Measured economics:
    break-even wire bandwidth is ~5-7 GB/s
    (results/compression_for_bitpack.json) — below ICI, so this is an
    opt-in for slow (DCN-class) links, off by default.

    Returns ``(received table, received counts, compression_overflow)``.
    """
    from distributed_join_tpu.ops.compression import (
        Packed,
        for_bitpack_decode,
        for_bitpack_encode,
    )

    a2a = (
        comm.ppermute_all_to_all if via == "ppermute" else comm.all_to_all
    )
    recv_counts = comm.all_to_all(counts)
    n_ranks = counts.shape[0]
    lane = jnp.arange(capacity, dtype=jnp.int32)
    row_valid = lane[None, :] < counts[:, None]
    c_ovf = jnp.bool_(False)
    recv_cols = {}
    # Wire accounting (static — every buffer here is capacity-shaped):
    # raw_bytes is what the UNcompressed padded shuffle would move,
    # sent_bytes what actually rides; the difference is the codec's
    # saving at this bits width (negative = expansion, reportable).
    raw_bytes = 0
    sent_bytes = 0
    for name, col in padded_columns.items():
        compressible = _codec_eligible(name, col)
        raw_bytes += col.size * col.dtype.itemsize
        if not compressible:
            # uint8 string payload planes etc. ride raw.
            sent_bytes += col.size * col.dtype.itemsize
            recv_cols[name] = a2a(col)
            continue

        # Padding slots hold whatever follows the bucket in the sorted
        # column (the next buckets' rows, invalid rows, the slice pad's
        # zeros) — a block mixing 0 with large-magnitude real values
        # would blow the residual span for data whose REAL residuals
        # are tiny. Fill them with the bucket's last valid row instead
        # (residual 0 against a real frame, the codec's own padding
        # trick).
        fill = col[jnp.arange(n_ranks), jnp.maximum(counts - 1, 0)]
        col = jnp.where(row_valid, col, fill[:, None])

        def _enc(row):
            p = for_bitpack_encode(row, bits, block)
            return p.words, p.frames, p.overflow

        words, frames, ovf = jax.vmap(_enc)(col)
        c_ovf = c_ovf | jnp.any(ovf)
        sent_bytes += (words.size * words.dtype.itemsize
                       + frames.size * frames.dtype.itemsize)
        rwords, rframes = a2a(words), a2a(frames)

        def _dec(w, f, dt=col.dtype):
            return for_bitpack_decode(
                Packed(w, f, None, None, n=capacity, bits=bits,
                       block=block),
                dtype=dt,
            )

        recv_cols[name] = jax.vmap(_dec)(rwords, rframes)
    if digest_tape is not None:
        # Sender digests run on the ORIGINAL block (valid slots are
        # untouched by the codec's pad-fill trick); receiver digests
        # on the decoded block. A lossy encode (residual wider than
        # ``bits``) would mismatch, but it also fires c_ovf — and
        # verification is only consulted on non-overflowed results.
        from distributed_join_tpu.parallel import integrity

        integrity.record_pair_digests(
            digest_tape,
            integrity.padded_block_digests(padded_columns, counts),
            integrity.padded_block_digests(recv_cols, recv_counts),
        )
    if tape is not None:
        tape.add("rows_shuffled", jnp.sum(counts.astype(jnp.int64)))
        tape.add("rows_received",
                 jnp.sum(recv_counts.astype(jnp.int64)))
        tape.add("wire_bytes", sent_bytes)
        tape.add("wire_bytes_saved", raw_bytes - sent_bytes)
    return unpad(recv_cols, recv_counts, capacity), recv_counts, c_ovf


def shuffle_segmented(
    comm: Communicator, padded_fine, fine_counts: jax.Array,
    seg_cap: int, segments: int, via: str = "all_to_all",
    tape=None, digest_tape=None,
):
    """Padded shuffle of a FINE-partitioned block for the
    segmented-sort pipeline (ops/segmented.py, docs/ROOFLINE.md §9):
    ``padded_fine`` holds ``(n_ranks * segments, seg_cap, ...)``
    blocks (destination-major, segment-minor — the contiguous layout
    ``radix_hash_partition(sub_buckets=)``'s fine ordering yields) and
    ``fine_counts`` the matching ``(n_ranks * segments,)`` int32
    counts.

    Each destination's ``segments * seg_cap`` slots ride the wire as
    ONE block — the same collectives, byte-for-byte, as a flat padded
    shuffle of capacity ``segments * seg_cap`` — plus the fine count
    matrix as the (unbilled) metadata exchange, so the receiver can
    mask every (source, segment) prefix. ``via`` selects all_to_all /
    ppermute / hierarchical routing; the hierarchical route moves both
    phases RAW (the DCN codec's pad-fill framing assumes one valid
    prefix per destination block, which the fine layout breaks — the
    caller refuses that combination loudly).

    Returns ``(recv_cols, recv_counts)``: column arrays shaped
    ``(n_src, segments, seg_cap, ...)`` and the received fine counts
    ``(n_src, segments)``. Overflow stays the caller's ``to_padded``
    verdict (a fine bucket exceeding ``seg_cap``).

    ``tape`` billing mirrors :func:`shuffle_padded`: ``wire_bytes`` is
    the full static block (pad included — that IS what rides),
    rows are the fine-count sums. ``digest_tape`` records the same
    per-(src, dst) pair digests as the flat shuffles, computed under
    the fine-count mask (integrity.masked_block_digests) — coarse
    per-peer channels, so ``verify_digests`` reads them unchanged.
    """
    n = comm.n_ranks
    s = segments
    hier = via == "hierarchical" and comm.n_slices > 1
    if hier:
        def route(x):
            return _hier_route(comm, x)

        route_meta = route
    else:
        route = (comm.ppermute_all_to_all if via == "ppermute"
                 else comm.all_to_all)
        route_meta = comm.all_to_all
    recv_counts = route_meta(fine_counts.reshape(n, s))
    recv_cols = {}
    block_bytes = 0
    for name, col in padded_fine.items():
        block = col.reshape((n, s * seg_cap) + col.shape[2:])
        block_bytes += block.size * block.dtype.itemsize
        recv = route(block)
        recv_cols[name] = recv.reshape((n, s, seg_cap)
                                       + col.shape[2:])
    # Hierarchical routing moves every block TWICE (intra-slice ICI
    # hop, then the raw cross-slice DCN hop) — bill both tiers, with
    # the per-tier counters the exact wire gate and the DCN telemetry
    # read, exactly like shuffle_hierarchical's raw path.
    wire_bytes = 2 * block_bytes if hier else block_bytes
    if digest_tape is not None:
        from distributed_join_tpu.parallel import integrity

        lane = jnp.arange(seg_cap, dtype=jnp.int32)
        sent_mask = (lane[None, :]
                     < fine_counts[:, None]).reshape(n, s * seg_cap)
        recv_mask = (lane[None, None, :]
                     < recv_counts[:, :, None]).reshape(n, s * seg_cap)
        integrity.record_pair_digests(
            digest_tape,
            integrity.masked_block_digests(
                {nm: c.reshape((n, s * seg_cap) + c.shape[2:])
                 for nm, c in padded_fine.items()}, sent_mask),
            integrity.masked_block_digests(
                {nm: c.reshape((n, s * seg_cap) + c.shape[3:])
                 for nm, c in recv_cols.items()}, recv_mask),
        )
    if tape is not None:
        tape.add("rows_shuffled",
                 jnp.sum(fine_counts.astype(jnp.int64)))
        tape.add("rows_received",
                 jnp.sum(recv_counts.astype(jnp.int64)))
        tape.add("wire_bytes", wire_bytes)
        if hier:
            tape.add("wire_bytes_ici", block_bytes)
            tape.add("wire_bytes_dcn", block_bytes)
    return recv_cols, recv_counts


def _hier_route(comm: Communicator, x: jax.Array) -> jax.Array:
    """Two-level routing of an ``(n_ranks, ...)`` destination-major
    block: intra-slice all-to-all over ICI, then cross-slice exchange
    over DCN. Returns the ``(n_ranks, ...)`` block received, leading
    axis in SENDER-rank order — the same contract as one global
    ``all_to_all`` of the block, in two tier-local hops.

    Algebra (s slices x c chips, rank r = (r//c, r%c), docs/
    HIERARCHY.md): phase 1 regroups the n = s*c destination blocks by
    destination CHIP — block j of the intra-slice exchange carries
    ``[dest (t, j) for every slice t]`` — so after the ICI hop, chip j
    holds (from each of its c slice-mates) everything destined to chip
    j of ANY slice, indexed ``(src_chip, dest_slice)``. Phase 2
    transposes to destination-slice-major and exchanges over the slice
    axis; the received ``(src_slice, src_chip)`` nesting IS flat
    sender-rank order (slice-major), so one reshape finishes."""
    s, c = comm.n_slices, comm.chips_per_slice
    z = comm.all_to_all_slice(_hier_phase1(comm, x))
    return z.reshape((s * c,) + x.shape[1:])


def _hier_phase1(comm: Communicator, x: jax.Array) -> jax.Array:
    """Phase 1 of :func:`_hier_route` alone: the intra-slice ICI hop,
    returning the ``(dest_slice, src_chip, ...)`` block phase 2
    exchanges — split out so the DCN codec can encode exactly the
    cross-slice payload and nothing else."""
    s, c = comm.n_slices, comm.chips_per_slice
    tail = x.shape[1:]
    y = x.reshape((s, c) + tail).swapaxes(0, 1)
    y = comm.all_to_all_chip(y)
    return y.swapaxes(0, 1)


def shuffle_hierarchical(
    comm: Communicator, padded_columns, counts: jax.Array,
    capacity: int, dcn_bits: Optional[int] = None, block: int = 256,
    tape=None, digest_tape=None,
) -> Tuple[Table, jax.Array, jax.Array]:
    """Two-level shuffle of a pre-padded ``(n_ranks, capacity)`` block
    over a hierarchical ``(slice, chip)`` mesh: slice-local buckets
    ride one intra-slice all-to-all over ICI (fast, always raw), the
    remote buckets then cross slices over DCN — with the FoR+bitpack
    codec from :func:`shuffle_padded_compressed` applied ONLY to that
    cross-slice payload when ``dcn_bits`` is set (the codec's measured
    ~5-7 GB/s break-even sits ABOVE DCN and BELOW ICI, so compression
    flips from NO-GO to win exactly at the tier that needs it —
    docs/ROOFLINE.md, docs/HIERARCHY.md).

    Returns ``(received table, received counts, codec_overflow)`` —
    the received block is bit-identical to :func:`shuffle_padded` of
    the same input (sender-rank order), and ``codec_overflow`` fires
    when a cross-slice residual exceeds ``dcn_bits`` (the caller's
    ladder widens bits, exactly like the compressed flat shuffle;
    always False with the codec off).

    ``tape`` gains the per-tier wire accounting next to the usual
    totals: ``wire_bytes_ici`` (phase 1 — the full static block, pad
    included, exactly what rides ICI), ``wire_bytes_dcn`` (phase 2 —
    codec planes when on, the full block otherwise) and
    ``wire_bytes_saved`` (the codec's saving vs shipping phase 2
    raw); ``wire_bytes`` stays the two-tier sum so every existing
    efficiency indicator keeps reading. ``digest_tape`` records the
    same per-(src, dst) pair digests as the flat padded shuffle —
    end-to-end over both hops, so a corrupted EITHER tier (including
    a misrouted cross-slice exchange) fails verification.
    """
    s, c = comm.n_slices, comm.chips_per_slice
    n = s * c
    if counts.shape[0] != n:
        raise ValueError(
            f"hierarchical shuffle needs {n} destination buckets, "
            f"got {counts.shape[0]}")
    recv_counts = _hier_route(comm, counts)
    lane = jnp.arange(capacity, dtype=jnp.int32)
    row_valid = lane[None, :] < counts[:, None]
    c_ovf = jnp.bool_(False)
    recv_cols = {}
    ici_bytes = 0
    dcn_raw = 0
    dcn_sent = 0
    for name, col in padded_columns.items():
        col_bytes = col.size * col.dtype.itemsize
        ici_bytes += col_bytes
        dcn_raw += col_bytes
        compressible = dcn_bits is not None and _codec_eligible(name,
                                                                col)
        if not compressible:
            dcn_sent += col_bytes
            recv_cols[name] = _hier_route(comm, col)
            continue
        # Same pad-fill trick as shuffle_padded_compressed: padding
        # slots hold clipped-gather garbage whose span would blow the
        # residual width; fill each bucket's pad with its last valid
        # row BEFORE routing (phase 1 moves rows verbatim, so the
        # fill arrives intact at the codec seam).
        fill = col[jnp.arange(n), jnp.maximum(counts - 1, 0)]
        col = jnp.where(row_valid, col, fill[:, None])
        staged = _hier_phase1(comm, col)      # (s, c, capacity)
        from distributed_join_tpu.ops.compression import (
            Packed,
            for_bitpack_decode,
            for_bitpack_encode,
        )

        # One frame stream PER DESTINATION SLICE (rows flattened
        # chip-major), so the slice exchange never splits a codec
        # block across destinations — the flat compressed shuffle's
        # per-destination discipline, one tier up.
        flat = staged.reshape(s, c * capacity)

        def _enc(row):
            p = for_bitpack_encode(row, dcn_bits, block)
            return p.words, p.frames, p.overflow

        words, frames, ovf = jax.vmap(_enc)(flat)
        c_ovf = c_ovf | jnp.any(ovf)
        dcn_sent += (words.size * words.dtype.itemsize
                     + frames.size * frames.dtype.itemsize)
        rwords = comm.all_to_all_slice(words)
        rframes = comm.all_to_all_slice(frames)

        def _dec(w, f, dt=col.dtype):
            return for_bitpack_decode(
                Packed(w, f, None, None, n=c * capacity,
                       bits=dcn_bits, block=block),
                dtype=dt,
            )

        decoded = jax.vmap(_dec)(rwords, rframes)
        recv_cols[name] = decoded.reshape(n, capacity)
    if digest_tape is not None:
        # End-to-end pair digests across both hops (sender commitment
        # on the pre-routing block, receiver belief on the assembled
        # sender-order block) — the same verify_digests contract as
        # the flat shuffles, so a corruption on EITHER tier mismatches.
        from distributed_join_tpu.parallel import integrity

        integrity.record_pair_digests(
            digest_tape,
            integrity.padded_block_digests(padded_columns, counts),
            integrity.padded_block_digests(recv_cols, recv_counts),
        )
    if tape is not None:
        tape.add("rows_shuffled", jnp.sum(counts.astype(jnp.int64)))
        tape.add("rows_received",
                 jnp.sum(recv_counts.astype(jnp.int64)))
        tape.add("wire_bytes", ici_bytes + dcn_sent)
        tape.add("wire_bytes_ici", ici_bytes)
        tape.add("wire_bytes_dcn", dcn_sent)
        if dcn_bits is not None:
            tape.add("wire_bytes_saved", dcn_raw - dcn_sent)
    return (unpad(recv_cols, recv_counts, capacity), recv_counts,
            c_ovf)


def _codec_eligible(name: str, col) -> bool:
    """The FoR+bitpack wire's column eligibility — one rule shared by
    the flat compressed shuffle and the hierarchical DCN tier: 2-D
    integer columns of >= 4-byte lanes, excluding the packed
    string-key word columns (big-endian byte packs whose per-block
    spans exceed any packable width — they would overflow at every
    bits, so they ride raw by construction)."""
    from distributed_join_tpu.utils.strings import _WORD_PREFIX

    return (
        col.ndim == 2
        and jnp.issubdtype(col.dtype, jnp.integer)
        and col.dtype.itemsize >= 4
        and not name.startswith(_WORD_PREFIX)
    )


def ragged_plan(comm: Communicator, counts: jax.Array, out_capacity: int,
                capacity_per_bucket: int | None = None):
    """Phase 1 of the exact-size shuffle: from each rank's (n,) count
    vector, build the consistent transfer plan every rank needs.

    Returns ``(send_sizes, recv_sizes, output_offsets, total_recv,
    overflow)`` where entry i of ``output_offsets`` is where THIS
    rank's block starts in rank i's output buffer. Sizes are clamped
    deterministically (identically on every rank, from the shared
    count matrix) so no write can pass ``out_capacity``; any clamping
    raises the overflow flag on the affected receiver.

    ``capacity_per_bucket`` unifies the capacity CONTRACT with the
    padded shuffle (VERDICT r2 weak #4): when given, the overflow flag
    also fires whenever any single (sender, destination) bucket
    exceeds it — exactly the padded mode's condition — even though the
    pooled buffer could still hold the rows. Rows are still
    transferred whenever they fit (no behavior change on the data
    path, extra varwidth columns included — ADVICE r5); only the flag
    is conservative, so ``auto_retry`` fires under the same conditions
    in every shuffle mode instead of one mode silently accepting a
    layout another would reject.
    """
    send_sizes, recv_sizes, output_offsets, total_recv, overflow, _, _ = \
        _ragged_plan_matrices(comm, counts, out_capacity,
                              capacity_per_bucket)
    return send_sizes, recv_sizes, output_offsets, total_recv, overflow


def _ragged_plan_matrices(comm, counts, out_capacity,
                          capacity_per_bucket=None):
    """ragged_plan + the full (start, allowed) matrices the
    variable-width plane exchange needs, + the receiver-local ACTUAL
    row-clamp flag (distinct from the possibly-conservative overflow
    flag: a ``capacity_per_bucket`` trip fires the flag without
    clamping a single row, and data-destructive recovery like the
    extra-varwidth zeroing must key on the clamp, not the flag —
    ADVICE r5)."""
    n = comm.n_ranks
    me = comm.axis_index()
    # Full count matrix: M[j, i] = rows rank j sends to rank i.
    M = comm.all_gather(counts).reshape(n, n)
    # Receiver-side packing: rank i's buffer concatenates blocks from
    # senders in rank order. start[j, i] = exclusive prefix down col i.
    start = jnp.cumsum(M, axis=0) - M
    allowed = jnp.clip(out_capacity - start, 0, M)
    row_clamped = jnp.any(allowed[:, me] < M[:, me])
    overflow = row_clamped
    if capacity_per_bucket is not None:
        overflow = overflow | jnp.any(M > capacity_per_bucket)
    send_sizes = comm.pvary(allowed[me, :].astype(jnp.int32))
    recv_sizes = comm.pvary(allowed[:, me].astype(jnp.int32))
    output_offsets = comm.pvary(start[me, :].astype(jnp.int32))
    total_recv = jnp.sum(recv_sizes)
    return (send_sizes, recv_sizes, output_offsets, total_recv,
            comm.pvary(overflow), (start, allowed),
            comm.pvary(row_clamped))


def shuffle_ragged(
    comm: Communicator,
    pt: PartitionedTable,
    out_capacity: int,
    bucket_start: int = 0,
    capacity_per_bucket: int | None = None,
    varwidth=None,
    tape=None,
    digest_tape=None,
) -> Tuple[Table, jax.Array]:
    """Exact-size shuffle of ``n_ranks`` buckets starting at
    ``bucket_start``: wire bytes = actual rows, not padded capacity.

    Returns (received table with a valid-prefix mask, overflow flag).
    The received rows pack contiguously in sender-rank order; rows a
    clamped transfer dropped are reported via the flag, never silently
    presented as success.

    ``varwidth`` names 2-D uint8 string column(s) — a name or a
    sequence of names — to ship BYTE-exactly (the reference's
    offsets+chars children exchange, SURVEY.md §2): each of a column's
    width/4 u32 word-planes ships as its own ragged slice of exactly
    ``ceil(len/4)`` words per row, so wire bytes for the column drop
    from ``rows * max_len`` to ``sum(ceil(len/4) * 4)``. The
    plane-prefix layout requires each bucket's rows ordered by THAT
    column's "<name>#len" companion DESCENDING:

    - the FIRST name's order is the caller's contract
      (radix_hash_partition's ``order_within``) — its planes land
      row-aligned at the receiver and reconstruction is free (the
      skipped tail slots stay zero, which IS the fixed-width
      zero-padded representation);
    - every FURTHER column is sorted into its own per-bucket
      length-descending order on the sender (a within-bucket
      permutation; bucket offsets are unchanged) and un-permuted at
      the receiver, which reconstructs the identical permutation from
      the received "#len" companion — the same stable
      (bucket, len desc) sort on both sides, no extra wire bytes
      (round 5; VERDICT r4 weak #5 lifted the one-column limit).
      Under an ACTUALLY clamped transfer the dropped rows differ
      between the row exchange (bucket tail) and a resorted column
      (shortest rows), so per-row alignment of the extra columns
      cannot hold — they are delivered ALL-ZERO on the clamping
      receiver (never silently misaligned; the flag demands a retry).
      A flag-only trip of the conservative ``capacity_per_bucket``
      contract clamps nothing, so the columns arrive intact
      (ADVICE r5: zeroing on the flag destroyed correctly delivered
      data).

    Debug mode (``faults.validate_plans()`` / ``DJTPU_VALIDATE_PLANS``
    at trace time): the transfer plan is cross-rank validated before
    the exchange — see :func:`..faults.validate_ragged_plan`.

    ``tape`` (``telemetry.MetricsTape`` view, or None): wire
    accounting from the PLAN vectors — ``rows_shuffled`` =
    sum(send_sizes) (the clamped plan totals, i.e. rows actually
    transferred), ``rows_received`` = total_recv, ``wire_bytes`` =
    fixed-width row bytes x rows sent plus the varwidth columns'
    exact u32-plane prefix bytes, with the bytes the byte-exact wire
    avoided (vs shipping those columns fixed-width for the same rows)
    in ``wire_bytes_saved``.
    """
    n = comm.n_ranks
    vw = ((varwidth,) if isinstance(varwidth, str)
          else tuple(varwidth or ()))
    counts = pt.counts[bucket_start : bucket_start + n].astype(jnp.int32)
    offsets = pt.offsets[bucket_start : bucket_start + n].astype(jnp.int32)
    (send_sizes, recv_sizes, output_offsets, total_recv, overflow,
     (start, allowed), row_clamped) = _ragged_plan_matrices(
        comm, counts, out_capacity,
        capacity_per_bucket=capacity_per_bucket,
    )
    from distributed_join_tpu.parallel import faults

    if faults.plan_validation_enabled():
        # Inconsistent vectors silently corrupt (emulation) or hang
        # (TPU hardware op); the validation token must stay live in an
        # output or XLA dead-code-eliminates the check.
        tok = faults.validate_ragged_plan(
            comm, send_sizes, recv_sizes, output_offsets, out_capacity,
        )
        overflow = overflow | comm.pvary(tok > 0)
    if tape is not None:
        rows_sent = jnp.sum(send_sizes.astype(jnp.int64))
        row_bytes = sum(
            int(c.size // c.shape[0]) * c.dtype.itemsize
            for name, c in pt.source.columns.items() if name not in vw
        )
        tape.add("rows_shuffled", rows_sent)
        tape.add("rows_received", total_recv.astype(jnp.int64))
        tape.add("wire_bytes", rows_sent * row_bytes)
    # The bucket-sorted layout the input offsets point into (no
    # padding, unlike to_padded): the partition's sort carried the 1-D
    # columns; only the 2-D ones are gathered. The varwidth columns go
    # LAST: the extra ones need their received "#len" companion to
    # reconstruct the sender-side permutation.
    sorted_table = pt.table
    out_cols = {}
    for name, col in sorted_table.columns.items():
        if name in vw:
            continue
        out = jnp.zeros((out_capacity,) + col.shape[1:], col.dtype)
        out_cols[name] = comm.ragged_all_to_all(
            col, out, offsets, send_sizes, output_offsets, recv_sizes
        )
    sorted_vw = varwidth_sort_plan(pt, vw)
    for i, name in enumerate(vw):
        if i == 0:
            # Partition-ordered by this column's len (caller contract).
            out_cols[name] = _varwidth_exchange(
                comm, sorted_table.columns[name],
                sorted_table.columns[name + "#len"],
                offsets, counts, start, allowed, out_capacity,
                tape=tape,
            )
            continue
        col_s, lens_s = sorted_vw[name]
        raw = _varwidth_exchange(
            comm, col_s, lens_s, offsets, counts, start,
            allowed, out_capacity, tape=tape,
        )
        unsorted = _receiver_unsort(
            comm, raw, out_cols[name + "#len"], start, total_recv
        )
        # Under an actual clamp the row exchange drops each bucket's
        # partition-order tail while this length-sorted column drops
        # its SHORTEST rows — different row sets, so the unsort would
        # attach surviving rows to other rows' bytes. Deliver the
        # column EMPTY on this receiver instead (all-zero bytes): the
        # flag already demands a retry, and a caller peeking at
        # partial results must never read silently misaligned strings
        # (review r5). Keyed on the receiver-local ROW CLAMP, not the
        # overflow flag: a conservative capacity_per_bucket trip
        # clamps nothing and must leave delivered data intact
        # (ADVICE r5 / ragged_plan's contract).
        out_cols[name] = jnp.where(row_clamped, 0, unsorted)
    if digest_tape is not None:
        # Wire-integrity digests (parallel/integrity.py). Sender side:
        # per-destination segment sums over the bucket-sorted layout's
        # TRUE local counts — committed before any plan/count exchange
        # could lie. Receiver side: segment sums over the assembled
        # output under the boundaries the receiver PLANS with
        # (start/allowed derive from the gathered count matrix), so a
        # consistently corrupted metadata exchange — which
        # validate_ragged_plan cannot see — still disagrees with the
        # sender's commitment. Rows align across columns here: the row
        # exchange packs in partition order per sender block and the
        # extra varwidth columns were just unsorted back to it. An
        # ACTUAL clamp breaks alignment by design, but it also raises
        # the overflow flag, and verification is only consulted on
        # non-overflowed results.
        from distributed_join_tpu.parallel import integrity

        me = comm.axis_index()
        rd_sent = integrity.row_digests(sorted_table.columns)
        rd_recv = integrity.row_digests(out_cols)
        integrity.record_pair_digests(
            digest_tape,
            integrity.segment_digests(rd_sent, offsets, counts),
            integrity.segment_digests(rd_recv, start[:, me],
                                      allowed[:, me]),
        )
    valid = jnp.arange(out_capacity, dtype=jnp.int32) < total_recv
    return Table(out_cols, valid), overflow


def varwidth_sort_plan(pt: PartitionedTable, names) -> dict:
    """Length-sorted layouts for every varwidth column BEYOND the
    first: {name: (col[perm], lens[perm])} with perm the within-bucket
    length-descending permutation. Batch-independent (the permutation
    covers all k*n buckets at once), so the sort happens ONCE per join
    step here and memoizes on the PartitionedTable — the per-batch
    shuffle_ragged calls reuse it instead of re-sorting k times
    (review r5).

    Cache lifetime contract (ADVICE r5): entries memoize on the
    PartitionedTable instance via ``object.__setattr__`` and key on
    nothing else, so a ``pt`` must not outlive the data its ``order``/
    ``source`` refer to — in practice, the trace (or eager call
    sequence) it was built in; ``radix_hash_partition`` returns a
    fresh ``pt`` per step, which upholds this. What is cached also
    differs by caller mode: under TRACING the gathered wide column is
    cached too (a trace-local intermediate the k per-batch shuffles
    share — composing bucket order with the length permutation gathers
    it once instead of twice per use); for EAGER callers only the
    cheap int32 composed permutation and sorted lengths are cached and
    the wide gather re-runs per call, because caching it would pin a
    full-width sorted copy of every extra string column in memory for
    the pt's lifetime."""
    names = tuple(names or ())[1:]
    if not names:
        return {}
    cache = getattr(pt, "_varwidth_sort_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(pt, "_varwidth_sort_cache", cache)
    out = {}
    for name in names:
        ent = cache.get(name)
        if ent is None:
            lens_sorted = pt.source.columns[name + "#len"][pt.order]
            perm = _within_bucket_len_order(pt.offsets, lens_sorted)
            order2 = pt.order[perm]
            lens2 = lens_sorted[perm]
            tracing = isinstance(order2, jax.core.Tracer)
            col2 = pt.source.columns[name][order2] if tracing else None
            cache[name] = (order2, lens2, col2)
            ent = cache[name]
        order2, lens2, col2 = ent
        if col2 is None:
            col2 = pt.source.columns[name][order2]
        out[name] = (col2, lens2)
    return out


def _within_bucket_len_order(all_offsets, lens):
    """Permutation putting each bucket's rows in length-DESCENDING
    order, buckets staying in place (stable sort keyed on
    (bucket, -len) — bucket blocks are contiguous, so only rows within
    a bucket move)."""
    from jax import lax

    rows = lens.shape[0]
    idx = jnp.arange(rows, dtype=jnp.int32)
    bid = (
        jnp.searchsorted(
            all_offsets.astype(jnp.int32), idx, side="right"
        ).astype(jnp.int32) - 1
    )
    _, _, perm = lax.sort(
        (bid, -lens.astype(jnp.int32), idx), num_keys=2, is_stable=True
    )
    return perm


def _receiver_unsort(comm, raw, recv_lens, start, total_recv):
    """Undo the sender's within-bucket length sort: the receiver holds
    the same lengths (the '#len' companion rode the ROW exchange, in
    partition order, per sender block), so the identical stable
    (block, len desc) sort reconstructs the sender's permutation with
    zero extra wire bytes. ``raw``'s row i (block-major, len-desc
    within each sender block) belongs at row ``perm[i]``."""
    from jax import lax

    me = comm.axis_index()
    out_capacity = raw.shape[0]
    idx = jnp.arange(out_capacity, dtype=jnp.int32)
    rb = (
        jnp.searchsorted(start[:, me], idx, side="right").astype(
            jnp.int32
        ) - 1
    )
    valid = idx < total_recv
    # Invalid tail rows take key -1: below any real length, so they
    # sort after their block's real rows and consume raw's zero tail.
    key_len = jnp.where(valid, recv_lens.astype(jnp.int32), -1)
    _, _, perm = lax.sort(
        (rb, -key_len, idx), num_keys=2, is_stable=True
    )
    return jnp.zeros_like(raw).at[perm].set(raw)


def _varwidth_exchange(comm, col, lens, offsets, counts, start, allowed,
                       out_capacity: int, tape=None):
    """Byte-exact exchange of one bucket-sorted (rows, L) uint8 column
    whose buckets are ordered by ``lens`` descending. Plane ``w`` of
    the u32 view is alive for exactly the first
    ``k[b, w] = #(len > 4w)`` rows of each bucket."""
    from jax import lax

    n = comm.n_ranks
    me = comm.axis_index()
    rows, L = col.shape
    assert L % 4 == 0, f"varwidth column width {L} must be 4-aligned"
    W = L // 4
    w32 = lax.bitcast_convert_type(
        col.reshape(rows, W, 4), jnp.uint32
    )                                                   # (rows, W)
    # k[b, w]: rows of bucket b alive at plane w — a prefix count,
    # read off a cumulative sum at the bucket boundaries.
    alive = (
        lens[:, None].astype(jnp.int32)
        > (4 * jnp.arange(W, dtype=jnp.int32))[None, :]
    )
    cs = jnp.concatenate(
        [jnp.zeros((1, W), jnp.int32),
         jnp.cumsum(alive.astype(jnp.int32), axis=0)]
    )                                                   # (rows+1, W)
    ends = jnp.minimum(offsets + counts, rows)
    k = cs[ends] - cs[jnp.minimum(offsets, rows)]       # (n, W)
    # Row-level clamping drops each bucket's TAIL — the shortest rows,
    # whose plane contributions are also the tail of every plane
    # prefix — so min(k, allowed_rows) keeps sender/receiver plans
    # consistent with the row exchange.
    gk = comm.all_gather(k).reshape(n, n, W)
    k_allowed = jnp.minimum(gk, allowed[:, :, None])
    if tape is not None:
        # Exact prefix bytes on the wire for this column vs shipping
        # the same (clamped) rows at fixed width — the byte-exact
        # wire's whole point, now a counter.
        exact = 4 * jnp.sum(k_allowed[me].astype(jnp.int64))
        fixed = jnp.sum(allowed[me].astype(jnp.int64)) * L
        tape.add("wire_bytes", exact)
        tape.add("varwidth_bytes", exact)
        tape.add("wire_bytes_saved", fixed - exact)
    out_planes = []
    for w in range(W):
        out = jnp.zeros((out_capacity,), jnp.uint32)
        out_planes.append(comm.ragged_all_to_all(
            w32[:, w], out, offsets,
            comm.pvary(k_allowed[me, :, w].astype(jnp.int32)),
            comm.pvary(start[me, :].astype(jnp.int32)),
            comm.pvary(k_allowed[:, me, w].astype(jnp.int32)),
        ))
    out32 = jnp.stack(out_planes, axis=1)               # (out_cap, W)
    return lax.bitcast_convert_type(out32, jnp.uint8).reshape(
        out_capacity, L
    )


def shuffle_partitioned(
    comm: Communicator, pt: PartitionedTable, capacity: int
) -> Tuple[Table, jax.Array]:
    """Shuffle a table already partitioned into exactly n_ranks buckets.

    Returns (received table, overflow flag). The received table holds
    every row of the global table whose key hashes to this rank, padding
    masked off.
    """
    if pt.n_buckets != comm.n_ranks:
        raise ValueError(
            f"partitioned into {pt.n_buckets} buckets but {comm.n_ranks} ranks"
        )
    padded, counts, overflow, _ = pt.to_padded(capacity)
    table, _ = shuffle_padded(comm, padded, counts, capacity)
    return table, overflow
