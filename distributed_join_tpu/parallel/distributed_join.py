"""The distributed inner-join orchestrator.

TPU re-design of the reference's ``distributed_inner_join``
(SURVEY.md §2/§3.1): partition both tables -> all-to-all shuffle ->
local join, with over-decomposition batching. Two deliberate departures
from the reference's shape:

- The whole pipeline is ONE compiled SPMD program (``jit(shard_map)``).
  The reference hand-pipelines comm of batch b+1 against the join of
  batch b on CUDA streams with helper threads. Round 2 MEASURED what
  XLA does with the unrolled batch loop on the v5e toolchain: the
  all-to-alls lower as SYNCHRONOUS HLO ops scheduled back to back —
  no async start/done pairs, no comm/compute interleaving (the
  compiled-schedule artifacts and what explicit overlap would take
  are in docs/OVERLAP.md). Over-decomposition here therefore buys
  memory capping, not overlap.
- That second purpose — capping resident shuffled data at 1/k of the
  table (the reference's answer to tables bigger than device memory,
  SURVEY.md §5 "Long-context") — is preserved: each batch materializes
  only its own shuffle buffers and join output block. The overlap that
  IS real and measured lives in the host staging thread of
  parallel/out_of_core.py.

Bucket arithmetic: with n ranks and over-decomposition factor k, rows
hash into ``bucket = h % (k*n)``; ``dest = bucket % n`` and
``batch = bucket // n``, so a sorted-by-bucket layout is batch-major and
each batch's n destination buckets are contiguous — one partition sort
serves all k batches. Matching keys share h, hence share (dest, batch):
batches join independently.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from distributed_join_tpu import telemetry
from distributed_join_tpu.ops.join import (
    JOIN_TYPES,
    JoinResult,
    patch_string_lengths,
    sort_merge_inner_join,
)
from distributed_join_tpu.ops.partition import radix_hash_partition
from distributed_join_tpu.parallel.communicator import Communicator
from distributed_join_tpu.parallel.shuffle import (
    shuffle_hierarchical,
    shuffle_padded,
    shuffle_padded_compressed,
    shuffle_ragged,
)
from distributed_join_tpu.table import Table


DEFAULT_SHUFFLE_CAPACITY_FACTOR = 1.6
DEFAULT_OUT_CAPACITY_FACTOR = 1.2
DEFAULT_HH_SLOTS = 64
HH_BUILD_SLOTS_PER_HH = 32  # default hh_build_capacity = slots * this
SHUFFLE_MODES = ("padded", "ragged", "ppermute", "hierarchical")
SORT_MODES = ("flat", "segmented")
# Residual width the hierarchical DCN codec starts at when the caller
# set dcn_codec on/auto but no compression_bits — the flat driver's
# own --compression-bits default; the ladder widens it on overflow.
DEFAULT_DCN_CODEC_BITS = 16

# The one sharded_out spec for a JoinResult: table row-sharded, the
# psummed total/overflow replicated.
JOIN_SHARDED_OUT = JoinResult(table=False, total=True, overflow=True)
# The metrics-emitting step returns (JoinResult, Metrics); the Metrics
# block is replicated by construction (one in-program all_gather).
JOIN_METRICS_SHARDED_OUT = (JOIN_SHARDED_OUT, True)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _bucket_capacity(rows: int, n_buckets: int, factor: float) -> int:
    """Rows of one (sender, destination) bucket of the padded shuffle:
    a rank's ``rows`` over the buckets, times the capacity factor."""
    return _round_up(int(math.ceil(rows / n_buckets * factor)), 8)


def _record_partition(tape, pt, capacity: int) -> None:
    """One side's partition counters (docs/OBSERVABILITY.md), where a
    tape is given: valid rows partitioned, the tightest
    per-(sender, destination)-bucket headroom under the shuffle
    capacity contract (how close this sizing came to an overflow), and
    the columns still packed by a gather (static)."""
    if tape is not None:
        tape.add("rows_partitioned",
                 jnp.sum(pt.counts.astype(jnp.int64)))
        tape.record_min("overflow_margin_min",
                        jnp.int64(capacity)
                        - jnp.max(pt.counts).astype(jnp.int64))
        tape.add("gathered_columns", pt.gathered_columns)


def _varwidth_cols(table: Table) -> list:
    """ALL 2-D uint8 columns with a '<name>#len' companion and
    4-aligned width — the columns the ragged shuffle ships
    byte-exactly (round 5 lifted the old one-per-table limit: the
    first rides the partition's order_within; further ones are
    within-bucket length-sorted by the shuffle itself, see
    shuffle.shuffle_ragged)."""
    return [
        name for name, c in table.columns.items()
        if (c.ndim == 2 and c.dtype == jnp.uint8
            and c.shape[1] % 4 == 0
            and name + "#len" in table.columns)
    ]


def _batch_shuffle(comm, pt, batch: int, n_ranks: int, capacity: int,
                   mode: str = "padded",
                   compression_bits: Optional[int] = None,
                   varwidth=None, tape=None, digest_tape=None,
                   dcn_codec_on: bool = False):
    if mode == "hierarchical":
        padded, counts, overflow, _ = pt.to_padded(
            capacity, bucket_start=batch * n_ranks, n_buckets=n_ranks
        )
        if comm.n_slices == 1:
            # Degenerate hierarchy: one slice = one ICI domain = the
            # flat padded path, byte-identically (no phase-2 identity
            # hop, no codec — there is no cross-slice payload to
            # compress). Lowering-locked in tests/test_hierarchy.py.
            table, _ = shuffle_padded(comm, padded, counts, capacity,
                                      tape=tape,
                                      digest_tape=digest_tape)
            return table, overflow
        dcn_bits = ((compression_bits or DEFAULT_DCN_CODEC_BITS)
                    if dcn_codec_on else None)
        table, _, c_ovf = shuffle_hierarchical(
            comm, padded, counts, capacity, dcn_bits=dcn_bits,
            tape=tape, digest_tape=digest_tape)
        return table, overflow | c_ovf
    if mode == "ragged":
        # Exact-size exchange: receive buffer = the same total rows the
        # padded layout would flatten to, but wire bytes = actual rows.
        # capacity_per_bucket aligns the overflow contract with padded
        # mode: auto_retry fires under identical conditions.
        return shuffle_ragged(
            comm, pt, n_ranks * capacity, bucket_start=batch * n_ranks,
            capacity_per_bucket=capacity, varwidth=varwidth, tape=tape,
            digest_tape=digest_tape,
        )
    padded, counts, overflow, _ = pt.to_padded(
        capacity, bucket_start=batch * n_ranks, n_buckets=n_ranks
    )
    via = "ppermute" if mode == "ppermute" else "all_to_all"
    if compression_bits is not None:
        table, _, c_ovf = shuffle_padded_compressed(
            comm, padded, counts, capacity, bits=compression_bits,
            via=via, tape=tape, digest_tape=digest_tape,
        )
        return table, overflow | c_ovf
    table, _ = shuffle_padded(comm, padded, counts, capacity, via=via,
                              tape=tape, digest_tape=digest_tape)
    return table, overflow


def _batch_shuffle_segmented(comm, pt, batch: int, n_ranks: int,
                             segments: int, seg_cap: int, mode: str,
                             tape=None, digest_tape=None):
    """One batch of the segmented-sort exchange: the fine-partitioned
    table pads per (destination, segment) fine bucket and rides the
    wire as one block per destination (parallel/shuffle.
    shuffle_segmented). Returns ``(recv_cols (n, s, seg_cap, ...),
    recv_fine_counts (n, s), overflow)`` — overflow fires when any
    fine bucket exceeds ``seg_cap``, the flat capacity contract one
    level down (the same ladder escalation relieves it)."""
    from distributed_join_tpu.parallel.shuffle import shuffle_segmented

    padded, counts, overflow, _ = pt.to_padded(
        seg_cap, bucket_start=batch * n_ranks * segments,
        n_buckets=n_ranks * segments,
    )
    if mode == "hierarchical" and comm.n_slices == 1:
        # Degenerate hierarchy: one slice = the flat padded route,
        # exactly like _batch_shuffle's degenerate branch.
        mode = "padded"
    via = {"padded": "all_to_all", "ppermute": "ppermute",
           "hierarchical": "hierarchical"}[mode]
    recv_cols, recv_counts = shuffle_segmented(
        comm, padded, counts, seg_cap, segments, via=via,
        tape=tape, digest_tape=digest_tape,
    )
    return recv_cols, recv_counts, overflow


def make_join_step(
    comm: Communicator,
    key: str = "key",
    join_type: str = "inner",
    over_decomposition: int = 1,
    shuffle_capacity_factor: float = DEFAULT_SHUFFLE_CAPACITY_FACTOR,
    out_capacity_factor: float = DEFAULT_OUT_CAPACITY_FACTOR,
    out_rows_per_rank: Optional[int] = None,
    build_payload: Optional[Sequence[str]] = None,
    probe_payload: Optional[Sequence[str]] = None,
    skew_threshold: Optional[float] = None,
    hh_slots: int = DEFAULT_HH_SLOTS,
    hh_build_capacity: Optional[int] = None,
    hh_probe_capacity: Optional[int] = None,
    hh_out_capacity: Optional[int] = None,
    shuffle: str = "padded",
    compression_bits: Optional[int] = None,
    dcn_codec: str = "auto",
    sort_mode: str = "flat",
    sort_segments: Optional[int] = None,
    aggregate=None,
    kernel_config=None,
    with_metrics: bool = False,
    with_integrity: bool = False,
    metrics_static: Optional[dict] = None,
):
    """The raw per-rank join step (partition -> shuffle -> local join).

    ``join_type`` (docs/QUERY.md): ``inner`` (default — the exact seed
    program, byte-for-byte) or one of ``left``/``right``/``full_outer``
    /``semi``/``anti`` (ops.join.JOIN_TYPES). Probe is the preserved
    ("left") side. Typed emission is purely local to each bucket's
    sort-merge — hash partitioning already co-locates every key's rows
    from both sides — so all shuffle modes and over-decomposition
    compose unchanged. Three shapes refuse by name: the skew sidecar
    (broadcast heavy-hitter build rows would emit unmatched once per
    rank), aggregate pushdown (no NULL-row emission in the fused
    reduction), and ``sort_mode='segmented'``.

    ``sort_mode`` ("flat"/"segmented"): "flat" is the exact existing
    pipeline, byte-for-byte. "segmented" is the segmented-sort path
    (ops/segmented.py, docs/ROOFLINE.md §9): sub-bucket hash bits ride
    the sender's existing partition sort as extra key bits, the padded
    wire carries static per-(source, segment) fine blocks, and the
    receiver sorts all segments as one batched short-run ``lax.sort``
    (the §6 run-length regime) with the scan/compact/expand stages
    batched per segment — each segment owns its share of the output
    capacity and the shared ladder relieves any segment overflow.
    ``sort_segments`` overrides the segment count per (batch, rank)
    receive (default: ``ops.segmented.resolve_sort_segments`` from the
    table shapes — THE shared resolution the plan mirrors). The result
    is the same row multiset as the flat path (graded bit-exact in
    tests/test_sortpath.py); row order is segment-major.
    Unsupported combinations refuse loudly, never fall back: the
    ragged exchange (dynamic boundaries — no static segments), the
    compressed wire and the hierarchical DCN codec (the codec's
    per-destination frame streams assume one valid prefix per block),
    aggregate pushdown (the fused reduction rides the flat sorts),
    and ``kernel_config`` (it tunes the flat Pallas pipeline the
    batched XLA formulation never runs). A one-segment resolution or
    a single-bucket (n*k == 1) mesh lowers to the flat path.

    ``aggregate`` (an :class:`~..ops.aggregate.AggregateSpec`, or
    None): the FUSED join+aggregate pipeline (docs/AGGREGATION.md).
    The step then reduces in the merged/compacted domain — segment
    scans ride the join's own sorts — and NEVER runs the output
    row-gathers that dominate materialization (docs/ROOFLINE.md
    §1-§3): only the columns the reduction reads are partitioned and
    shuffled, the local result is a per-group PARTIALS block of
    ``ops.aggregate.resolve_groups_capacity`` rows, and the returned
    ``JoinResult.table`` holds finalized per-group aggregates (group
    keys, aggregate outputs, carries; ``.valid`` marks real groups)
    instead of joined rows. ``total`` stays the row count the
    materializing join WOULD have produced — free from the run
    algebra, and the oracle/accounting anchor. Group keys equal to the
    join keys ("key mode") are co-located by hash partitioning, so
    per-rank partials are final — no second exchange; probe-side
    group-bys ("probe mode") exchange only the tiny per-group partials
    (one groups-sized padded collective — hierarchical routing on a
    multi-slice mesh — billed under the ``partials.*`` counters), so
    wire bytes collapse from O(output rows) to O(groups). A partials
    block too small for the distinct groups raises the overflow flag
    (rows are dropped loudly, never wrong sums); the ladder's
    out-capacity escalation grows the derived block. Shapes the fused
    pipeline cannot cover (the skew sidecar, string/2-D keys, explicit
    payload lists, build-side group-bys...) refuse with a named
    :class:`~..ops.aggregate.AggregatePushdownUnsupported` — callers
    fall back to the materializing join.

    ``shuffle``: "padded" (capacity-padded all_to_all, the default),
    "ragged" (exact-size ``lax.ragged_all_to_all`` — wire bytes equal
    actual rows), "ppermute" (padded blocks over a collective-permute
    chain whose lowering the scheduler can overlap with compute;
    docs/OVERLAP.md), or "hierarchical" (the two-level ICI/DCN
    shuffle over a multi-slice mesh: slice-local buckets ride one
    intra-slice all-to-all, remote buckets cross slices — with the
    FoR+bitpack codec on exactly that slow tier when ``dcn_codec``
    resolves on; docs/HIERARCHY.md). On a one-slice communicator the
    hierarchical mode lowers byte-identically to "padded" (the
    degenerate hierarchy, lowering-locked).

    ``dcn_codec`` ("off"/"auto"/"on", hierarchical mode only): the
    cross-slice codec knob. "auto" (default) resolves statically
    against the cost model — on exactly when the configured DCN
    bandwidth sits below the codec's measured ~5-7 GB/s break-even
    (planning.cost.resolve_dcn_codec). The residual width is
    ``compression_bits`` (default 16 when unset); a cross-slice
    residual overflow raises the overflow flag and the ladder widens
    bits, exactly like the flat compressed shuffle.

    ``compression_bits``: when set, integer columns ride the padded/
    ppermute shuffle FoR+bitpacked at this width (the reference's
    ``--compression`` / nvcomp path; shuffle.shuffle_padded_compressed).
    A residual wider than ``bits`` raises the overflow flag —
    ``auto_retry`` widens up to 32 — never corrupts rows. Opt-in only:
    measured break-even wire bandwidth (~5-7 GB/s,
    results/compression_for_bitpack.json) is below ICI.

    ONE capacity contract across all modes: the unit of capacity is
    the per-(sender, destination) bucket,
    ``ceil(rows/(k*n)) * shuffle_capacity_factor``, and the overflow
    flag fires whenever any bucket exceeds it — so ``auto_retry``
    fires under identical conditions whichever mode is selected.
    Ragged mode's receive buffer additionally pools to
    ``n_ranks x capacity`` and clamps deterministically at the pooled
    bound (rows a clamp drops are always flagged); its flag is
    CONSERVATIVE relative to what its pooling could physically hold —
    the price of mode-independent retry semantics. The ragged hardware
    op exists only on TPU; other backends transparently run the
    bit-identical emulation (Communicator.ragged_all_to_all).

    Returns ``step(build_local, probe_local) -> JoinResult`` meant to run
    inside ``comm.spmd`` (collectives are unresolved outside it). Exposed
    separately from :func:`make_distributed_join` so harnesses can wrap
    extra structure around the step before compiling — e.g. ``bench.py``
    chains K dependent steps in one ``lax.fori_loop`` for honest timing
    (``utils/benchmarking.py``).

    Static capacities (the XLA dynamic-shape answer, SURVEY.md §7):
    - shuffle pad per (batch, destination) bucket =
      ceil(local_rows / (k * n)) * shuffle_capacity_factor;
    - join output block per batch = probe rows per batch *
      out_capacity_factor (or out_rows_per_rank / k if given).
    Overflow of either capacity is reported, never silently dropped
    rows presented as success.

    Skew handling (BASELINE config 3; :mod:`..parallel.skew`): pass
    ``skew_threshold`` — a key becomes a heavy hitter when its global
    probe count exceeds ``skew_threshold * local_probe_rows``. HH probe
    rows skip the shuffle and stay local, compacted into an
    ``hh_probe_capacity`` block (default 1/8 of local probe rows —
    streaming-kernel packed on TPU, so the HH join's cost scales with
    the block, not the full probe; round-3 VERDICT #2); HH build rows
    are broadcast (``hh_build_capacity`` slots per rank, default
    ``hh_slots * 32``) and joined locally into an extra output block
    of ``hh_out_capacity`` rows (default 1/4 of local probe rows).
    Heavy-hitter mass above these (Zipf alpha >= ~1.4 puts ~90% of
    probe rows in the top keys) overflows and is caught by the flag /
    ``auto_retry`` doubling; size them explicitly for known-heavy
    workloads (``distributed_inner_join`` sizes them from the input
    when the caller sets no ``skew_threshold``). The HH join's Pallas
    kernels carry an ``hh_`` prefix (``hh_join_scan_f``,
    ``hh_compact_planes``, ``hh_expand_b8``, ...), and the HH probe and
    build blocks are compacted by ``hh_extract``.

    Telemetry (docs/OBSERVABILITY.md): ``with_metrics=True`` makes the
    step return ``(JoinResult, telemetry.Metrics)`` — device-side
    counters (rows partitioned/shuffled, wire bytes, per-bucket
    overflow margin, match count) accumulated on a
    :class:`~..telemetry.metrics.MetricsTape` and cross-rank gathered
    once at step end; ``metrics_static`` merges caller-known constants
    (e.g. the retry attempt index) into the same vector. The default
    (``False``) compiles the exact seed program — no aux output, no
    extra collective (tests/test_telemetry.py locks the treedef and
    program count). The stages are ``jax.named_scope``s
    (`partition`/`shuffle`/`join`, `skew`; the local join nests
    `prepare`/`sort`/`scan`/`compact`/`expand` under `join`), always
    on: they name every device op in an XLA profile through its
    ``op_name`` metadata and change no instruction.

    Wire integrity (docs/FAILURE_SEMANTICS.md "Integrity contract"):
    ``with_integrity=True`` additionally computes order-invariant
    per-(src-rank, dst-rank) payload digests inside each shuffle
    (parallel/integrity.py) and ships them in the SAME aux Metrics
    block — the step returns ``(JoinResult, Metrics)`` exactly as
    ``with_metrics`` does, with no further collective (the digests
    ride the step-end all_gather). Verify host-side with
    ``integrity.verify_digests``; with both switches off this is still
    the exact seed program.
    """
    n = comm.n_ranks
    k = over_decomposition
    if k < 1:
        raise ValueError("over_decomposition must be >= 1")
    if join_type not in JOIN_TYPES:
        raise ValueError(
            f"unknown join_type {join_type!r}; expected one of "
            f"{JOIN_TYPES}")
    if join_type != "inner":
        # The typed variants ride the same hash partitioning — every
        # key's build AND probe rows land in one bucket, so unmatched
        # rows are locally visible — but three shapes would break that
        # locality (or double-emit) and refuse by name, mirroring the
        # aggregate-pushdown discipline:
        if skew_threshold is not None:
            raise ValueError(
                f"join_type={join_type!r} does not combine with the "
                "skew sidecar: broadcast heavy-hitter build rows are "
                "replicated on every rank, so an unmatched heavy "
                "build row would emit once PER RANK — run typed joins "
                "without skew_threshold")
        if aggregate is not None:
            raise ValueError(
                f"join_type={join_type!r} does not combine with "
                "aggregate pushdown: the fused reduction counts "
                "matches in the merged domain and has no NULL-row "
                "emission — aggregate over a materialized typed join "
                "instead")
        if sort_mode == "segmented":
            raise ValueError(
                f"join_type={join_type!r} is not part of the "
                "segmented-sort path (the batched short-run "
                "formulation emits matches only) — use "
                "sort_mode='flat'")
    if shuffle not in SHUFFLE_MODES:
        # Validate for EVERY config — the single-rank path never
        # reaches the shuffle, and a typo'd mode must not silently
        # report success.
        raise ValueError(f"unknown shuffle mode {shuffle!r}")
    if compression_bits is not None and shuffle == "ragged":
        raise ValueError(
            "compression applies to the padded/ppermute shuffles; the "
            "ragged exchange already sends exact rows (combining the "
            "two is unimplemented)"
        )
    from distributed_join_tpu.planning.cost import resolve_dcn_codec

    if shuffle == "hierarchical":
        if compression_bits is not None and dcn_codec == "off":
            raise ValueError(
                "dcn_codec='off' contradicts compression_bits="
                f"{compression_bits}: the hierarchical mode's codec "
                "rides ONLY the cross-slice tier (the flat-tier codec "
                "is a measured NO-GO on ICI) — drop the bits or the "
                "knob")
        dcn_on = resolve_dcn_codec(dcn_codec)
    else:
        # The knob is hierarchical-only; still validate the VALUE so
        # a typo'd config fails loudly everywhere.
        resolve_dcn_codec(dcn_codec)
        dcn_on = False
        if n > 1 and getattr(comm, "n_slices", 1) > 1:
            raise ValueError(
                f"shuffle {shuffle!r} routes one GLOBAL collective "
                "over a multi-slice mesh, dragging intra-slice "
                "traffic across DCN — use shuffle='hierarchical' "
                "(or a flat 1-D communicator)")
    nb = k * n

    if sort_mode not in SORT_MODES:
        raise ValueError(
            f"unknown sort_mode {sort_mode!r}; pick one of {SORT_MODES}")
    if sort_segments is not None and int(sort_segments) < 1:
        raise ValueError("sort_segments must be >= 1")
    if sort_mode == "flat" and sort_segments is not None:
        raise ValueError(
            "sort_segments applies to sort_mode='segmented' only — "
            "the flat pipeline never reads it, and silently ignoring "
            "it would cache one byte-identical program per value "
            "(the signature binds every keyword); drop the knob or "
            "pass sort_mode='segmented'")
    if sort_mode == "segmented":
        if shuffle == "ragged":
            raise ValueError(
                "sort_mode='segmented' needs static per-(source, "
                "segment) receive boundaries; the ragged exchange "
                "packs exact-size blocks whose boundaries only exist "
                "at run time — use shuffle='padded'/'ppermute' (or "
                "sort_mode='flat')")
        if compression_bits is not None:
            raise ValueError(
                "sort_mode='segmented' does not combine with the "
                "compressed wire: the codec's per-destination frame "
                "streams assume one valid prefix per block, which "
                "the fine-bucket layout breaks — drop "
                "compression_bits (or use sort_mode='flat')")
        if (shuffle == "hierarchical" and dcn_on
                and getattr(comm, "n_slices", 1) > 1):
            # Topology-gated like resolve_dcn_bits: one slice has no
            # cross-slice payload, so the degenerate hierarchy is
            # codec-free and segments fine.
            raise ValueError(
                "sort_mode='segmented' does not combine with the "
                "hierarchical DCN codec (same per-block framing "
                "problem as compression_bits) — pass dcn_codec='off' "
                "(or sort_mode='flat')")
        if kernel_config is not None:
            raise ValueError(
                "sort_mode='segmented' ignores kernel_config (the "
                "knob tunes the flat Pallas expand/compact pipeline; "
                "the segmented path is the batched XLA formulation) "
                "— drop the knob (silently ignoring it would cache "
                "one byte-identical program per value)")

    keys = [key] if isinstance(key, str) else list(key)

    if aggregate is not None:
        from distributed_join_tpu.ops import aggregate as agg_ops

        if not isinstance(aggregate, agg_ops.AggregateSpec):
            raise TypeError(
                "aggregate must be an ops.aggregate.AggregateSpec "
                f"(got {type(aggregate).__name__}); build one with "
                "AggregateSpec.of(group_by, aggs, ...)")
        if sort_mode == "segmented":
            raise agg_ops.AggregatePushdownUnsupported(
                "aggregate pushdown unsupported under "
                "sort_mode='segmented': the fused reduction rides "
                "the flat pipeline's own sorts — run aggregates with "
                "sort_mode='flat'")
        if skew_threshold is not None:
            raise agg_ops.AggregatePushdownUnsupported(
                "aggregate pushdown unsupported: the skew sidecar "
                "joins heavy hitters through a separate output block "
                "the fused reduction does not cover — run skewed "
                "workloads through the materializing join")
        if build_payload is not None or probe_payload is not None:
            raise agg_ops.AggregatePushdownUnsupported(
                "aggregate pushdown unsupported: explicit payload "
                "lists conflict with the pushdown's own wire-column "
                "resolution (ops.aggregate.wire_columns resolves "
                "exactly the columns the reduction reads)")
        if kernel_config is not None:
            raise agg_ops.AggregatePushdownUnsupported(
                "aggregate pushdown unsupported: kernel_config tunes "
                "the materializing expand/compact gathers the fused "
                "reduction never runs — drop the knob (silently "
                "ignoring it would cache one program per value)")
        return _make_join_agg_step(
            comm, aggregate, keys=keys, k=k,
            shuffle_capacity_factor=shuffle_capacity_factor,
            out_capacity_factor=out_capacity_factor,
            out_rows_per_rank=out_rows_per_rank,
            shuffle=shuffle, compression_bits=compression_bits,
            dcn_on=dcn_on, with_metrics=with_metrics,
            with_integrity=with_integrity,
            metrics_static=metrics_static)

    def step(build_local: Table, probe_local: Table):
        # The integrity digests ride the same Metrics slot, so either
        # switch materializes the tape (and the aux output).
        tape = telemetry.MetricsTape() if (with_metrics
                                           or with_integrity) else None
        if tape is not None:
            for mname, mval in (metrics_static or {}).items():
                tape.add(mname, int(mval))
        for kname in keys:
            bdt = build_local.columns[kname].dtype
            pdt = probe_local.columns[kname].dtype
            if bdt != pdt:
                # Hash routing is dtype-dependent: a mismatch would
                # shuffle equal keys apart and silently lose matches.
                raise TypeError(
                    f"key {kname!r} dtype mismatch: build {bdt} vs probe {pdt}"
                )

        # String keys: pack 2-D byte key columns into uint64 words ONCE,
        # before hashing/partitioning — every stage downstream (hash,
        # partition sort, shuffle, local join) then sees an ordinary
        # composite scalar key, and the key bytes ride the wire packed
        # (not duplicated; the build side's dead '#len' companion is
        # dropped before the shuffle). Reconstructed exactly on exit.
        from distributed_join_tpu.utils.strings import (
            prepare_string_key_join,
        )

        (build_local, probe_local, keys_eff, bpay, ppay,
         str_spec) = prepare_string_key_join(
            build_local, probe_local, keys, build_payload,
            probe_payload,
        )
        sk_names = tuple(
            nm for _, wns, _ in str_spec for nm in wns
        )
        b_rows, p_rows = build_local.capacity, probe_local.capacity
        b_cap = _bucket_capacity(b_rows, nb, shuffle_capacity_factor)
        p_cap = _bucket_capacity(p_rows, nb, shuffle_capacity_factor)
        if out_rows_per_rank is not None:
            out_cap = _round_up(int(math.ceil(out_rows_per_rank / k)), 8)
        else:
            # received probe rows per batch can reach n * p_cap
            out_cap = _round_up(
                int(math.ceil(p_rows / k * out_capacity_factor)), 8
            )

        parts = []
        total = jnp.int64(0)
        overflow = jnp.bool_(False)

        if skew_threshold is not None:
            from distributed_join_tpu.ops.hashing import hash_columns
            from distributed_join_tpu.parallel import skew

            with jax.named_scope("skew"):
                # Detect/mark heavy hitters on the uint64 key-tuple
                # hash: classification only needs to be CONSISTENT
                # across sides and ranks (hash collisions merely
                # over-classify a key as heavy, which stays correct —
                # the HH join matches on the real composite key).
                bh = hash_columns(
                    [build_local.columns[k] for k in keys_eff])
                ph = hash_columns(
                    [probe_local.columns[k] for k in keys_eff])
                hh = skew.global_heavy_hitters(
                    comm,
                    ph,
                    probe_local.valid,
                    hh_slots,
                    threshold=jnp.int32(int(skew_threshold * p_rows)),
                )
                is_hh_b = skew.mark_heavy(bh, hh)
                is_hh_p = skew.mark_heavy(ph, hh)
                hh_build, ovf_hb = skew.broadcast_heavy_build(
                    comm, build_local, is_hh_b,
                    hh_build_capacity or hh_slots * HH_BUILD_SLOTS_PER_HH,
                    kernel_config=kernel_config,
                )
                # HH probe rows stay local, COMPACTED into a
                # right-sized block first (round-3 VERDICT #2:
                # narrowing validity on the full-capacity arrays made
                # the HH join re-sort all p_rows to join a
                # typically-tiny subset — the whole HH path then cost
                # ~90% of the join even with zero heavy keys).
                # Overflowing the block raises the flag; auto_retry
                # doubles it like every other capacity.
                hh_probe_cap = _round_up(
                    hh_probe_capacity or max(p_rows // 8, 1024), 8
                )
                hh_probe, _, ovf_hp = skew.extract_prefix(
                    probe_local, probe_local.valid & is_hh_p,
                    hh_probe_cap, kernel_config=kernel_config,
                )
                hh_res = sort_merge_inner_join(
                    hh_build, hh_probe, keys_eff,
                    hh_out_capacity or max(p_rows // 4, 1024),
                    build_payload=bpay, probe_payload=ppay,
                    kernel_config=kernel_config,
                    _internal=sk_names, kernel_prefix="hh_",
                )
                parts.append(hh_res.table)
                total = total + hh_res.total.astype(jnp.int64)
                overflow = overflow | ovf_hb | ovf_hp | hh_res.overflow
                if tape is not None:
                    tape.add("skew.hh_matches",
                             hh_res.total.astype(jnp.int64))
                # The normal path sees neither side's HH rows.
                build_local = Table(build_local.columns,
                                    build_local.valid & ~is_hh_b)
                probe_local = Table(probe_local.columns,
                                    probe_local.valid & ~is_hh_p)

        seg = 1
        if sort_mode == "segmented" and nb > 1:
            from distributed_join_tpu.ops import segmented as seg_ops

            # THE shared resolution (plan mirrors it): one segment
            # count for both sides — segments must be the same hash
            # classes on build and probe or matches would cross them.
            seg = seg_ops.resolve_sort_segments(
                sort_segments, max(b_rows, p_rows), n, k,
                shuffle_capacity_factor)
        if nb == 1:
            # Single rank, single batch: the partition is one all-rows
            # bucket and the shuffle is an identity — both pure row
            # permutations. Skip them entirely (the join handles masked
            # validity natively); this is the reference's 1-rank path,
            # which also partitions into nranks=1 buckets and joins.
            with jax.named_scope("join"):
                res = sort_merge_inner_join(
                    build_local, probe_local, keys_eff, out_cap,
                    build_payload=bpay, probe_payload=ppay,
                    kernel_config=kernel_config, join_type=join_type,
                    _internal=sk_names,
                )
            parts.append(res.table)
            total = total + res.total.astype(jnp.int64)
            overflow = overflow | res.overflow
        elif seg > 1:
            # The segmented-sort pipeline (ops/segmented.py,
            # docs/ROOFLINE.md §9): fine partition (sub-bucket bits on
            # the SAME partition sort), per-segment padded wire,
            # batched short-run sorts + per-segment merge at the
            # receiver. One resolution level below the flat contract:
            # capacities are per fine bucket / per segment, and every
            # overflow folds into the same shared flag the ladder
            # relieves.
            b_cap_s = seg_ops.segment_capacity(
                b_rows, n, k, seg, shuffle_capacity_factor)
            p_cap_s = seg_ops.segment_capacity(
                p_rows, n, k, seg, shuffle_capacity_factor)
            out_cap_s = seg_ops.segmented_out_capacity(
                p_rows, k, seg, out_capacity_factor, out_rows_per_rank)
            with jax.named_scope("partition"):
                ptb = radix_hash_partition(build_local, keys_eff, nb,
                                           sub_buckets=seg)
                ptp = radix_hash_partition(probe_local, keys_eff, nb,
                                           sub_buckets=seg)
            tb = tape.scoped("build") if tape is not None else None
            tp = tape.scoped("probe") if tape is not None else None
            dtb = tape.scoped("build.integrity") if with_integrity \
                else None
            dtp = tape.scoped("probe.integrity") if with_integrity \
                else None
            if tape is not None:
                # The static segmentation rides the metrics block so
                # EXPLAIN's segment-count prediction grades against a
                # device-reported value, like the wire bytes.
                tape.add("sort_segments", seg)
            # Headroom under the FINE capacity contract.
            _record_partition(tb, ptb, b_cap_s)
            _record_partition(tp, ptp, p_cap_s)
            for b in range(k):
                with jax.named_scope("shuffle"):
                    rb_cols, rb_counts, ovf_b = \
                        _batch_shuffle_segmented(
                            comm, ptb, b, n, seg, b_cap_s, shuffle,
                            tape=tb, digest_tape=dtb)
                    rp_cols, rp_counts, ovf_p = \
                        _batch_shuffle_segmented(
                            comm, ptp, b, n, seg, p_cap_s, shuffle,
                            tape=tp, digest_tape=dtp)
                with jax.named_scope("join"):
                    bcols, bval = seg_ops.runs_from_blocks(
                        rb_cols, rb_counts)
                    pcols, pval = seg_ops.runs_from_blocks(
                        rp_cols, rp_counts)
                    table, t_batch, ovf_j = \
                        seg_ops.batched_sort_merge_inner_join(
                            bcols, bval, pcols, pval, keys_eff,
                            out_cap_s, build_payload=bpay,
                            probe_payload=ppay, _internal=sk_names)
                parts.append(table)
                total = total + t_batch
                overflow = overflow | ovf_b | ovf_p | ovf_j
        else:
            # Byte-exact string wire (ragged mode): order each bucket
            # by the FIRST string column's length desc so its u32
            # planes ship as ragged prefixes (shuffle_ragged's
            # varwidth); further string columns are length-ordered
            # within the shuffle itself.
            vb = _varwidth_cols(build_local) if shuffle == "ragged" \
                else []
            vp = _varwidth_cols(probe_local) if shuffle == "ragged" \
                else []
            with jax.named_scope("partition"):
                ptb = radix_hash_partition(
                    build_local, keys_eff, nb,
                    order_within=vb[0] + "#len" if vb else None)
                ptp = radix_hash_partition(
                    probe_local, keys_eff, nb,
                    order_within=vp[0] + "#len" if vp else None)
            tb = tape.scoped("build") if tape is not None else None
            tp = tape.scoped("probe") if tape is not None else None
            dtb = tape.scoped("build.integrity") if with_integrity \
                else None
            dtp = tape.scoped("probe.integrity") if with_integrity \
                else None
            _record_partition(tb, ptb, b_cap)
            _record_partition(tp, ptp, p_cap)
            for b in range(k):
                with jax.named_scope("shuffle"):
                    recv_build, ovf_b = _batch_shuffle(
                        comm, ptb, b, n, b_cap, mode=shuffle,
                        compression_bits=compression_bits, varwidth=vb,
                        tape=tb, digest_tape=dtb,
                        dcn_codec_on=dcn_on)
                    recv_probe, ovf_p = _batch_shuffle(
                        comm, ptp, b, n, p_cap, mode=shuffle,
                        compression_bits=compression_bits, varwidth=vp,
                        tape=tp, digest_tape=dtp,
                        dcn_codec_on=dcn_on)
                with jax.named_scope("join"):
                    res = sort_merge_inner_join(
                        recv_build, recv_probe, keys_eff, out_cap,
                        build_payload=bpay, probe_payload=ppay,
                        kernel_config=kernel_config, join_type=join_type,
                        _internal=sk_names,
                    )
                parts.append(res.table)
                total = total + res.total.astype(jnp.int64)
                overflow = overflow | ovf_b | ovf_p | res.overflow
        out = Table(
            {
                name: jnp.concatenate([t.columns[name] for t in parts])
                for name in parts[0].column_names
            },
            jnp.concatenate([t.valid for t in parts]),
        )
        if str_spec:
            from distributed_join_tpu.utils.strings import (
                rebuild_string_keys,
            )

            out = patch_string_lengths(
                rebuild_string_keys(out, str_spec, keys), keys,
                join_type)
        if tape is not None:
            # Local (pre-psum) match count: the gathered per-rank
            # vector sums to the global total, giving per-rank match
            # distribution for free.
            tape.add("matches", total)
            metrics = tape.gathered(comm)
        total = comm.psum(total)
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        result = JoinResult(out, total=total, overflow=overflow)
        return (result, metrics) if tape is not None else result

    return step


def _make_join_agg_step(comm, spec, *, keys, k,
                        shuffle_capacity_factor, out_capacity_factor,
                        out_rows_per_rank, shuffle, compression_bits,
                        dcn_on, with_metrics, with_integrity,
                        metrics_static):
    """The FUSED join+aggregate step (``make_join_step(aggregate=)``;
    docs/AGGREGATION.md): partition + shuffle ONLY the columns the
    reduction reads, reduce each batch in the merged domain with
    :func:`~..ops.aggregate.local_join_aggregate` (segment scans ride
    the join's own sorts — zero output gathers), then settle the
    per-group partials: key mode is final per rank (hash co-location),
    probe mode pays one cross-batch combine plus the groups-sized
    cross-rank partials exchange (padded; hierarchical routing on a
    multi-slice mesh; billed under the ``partials.*`` counters).
    Returns the same ``step(build, probe) -> JoinResult`` shape as the
    materializing step — ``table`` holds finalized groups, ``total``
    the would-be join row count, ``overflow`` any shuffle-bucket or
    partial-groups capacity trip (rows dropped loudly, never wrong
    sums)."""
    from distributed_join_tpu.ops import aggregate as agg_ops

    n = comm.n_ranks
    nb = k * n
    partials_mode = "hierarchical" if shuffle == "hierarchical" \
        else "padded"

    def step(build_local: Table, probe_local: Table):
        tape = telemetry.MetricsTape() if (with_metrics
                                           or with_integrity) else None
        if tape is not None:
            for mname, mval in (metrics_static or {}).items():
                tape.add(mname, int(mval))
        for kname in keys:
            bc = build_local.columns[kname]
            pc = probe_local.columns[kname]
            if bc.ndim != 1:
                raise agg_ops.AggregatePushdownUnsupported(
                    f"aggregate pushdown unsupported: join key "
                    f"{kname!r} is a 2-D (string) column; the fused "
                    "reduction covers scalar keys — run string-key "
                    "workloads through the materializing join")
            if bc.dtype != pc.dtype:
                raise TypeError(
                    f"key {kname!r} dtype mismatch: build {bc.dtype} "
                    f"vs probe {pc.dtype}")
        bschema = agg_ops.table_schema(build_local)
        pschema = agg_ops.table_schema(probe_local)
        mode = agg_ops.resolve_agg_mode(spec, keys, bschema, pschema)
        wire_b, wire_p = agg_ops.wire_columns(spec, mode, keys,
                                              bschema, pschema)
        build_w = build_local.select(wire_b)
        probe_w = probe_local.select(wire_p)
        lanes_schema = agg_ops.partial_lane_schema(spec, bschema,
                                                   pschema)
        group_names = list(keys) if mode == "key" \
            else list(spec.group_keys)

        b_rows, p_rows = build_w.capacity, probe_w.capacity
        # Capacity arithmetic VERBATIM from the materializing step —
        # planning.build_plan mirrors it, and the ladder's escalation
        # relieves the same contract.
        b_cap = _round_up(
            int(math.ceil(b_rows / nb * shuffle_capacity_factor)), 8)
        p_cap = _round_up(
            int(math.ceil(p_rows / nb * shuffle_capacity_factor)), 8)
        if out_rows_per_rank is not None:
            out_cap = _round_up(
                int(math.ceil(out_rows_per_rank / k)), 8)
        else:
            out_cap = _round_up(
                int(math.ceil(p_rows / k * out_capacity_factor)), 8)
        groups_cap = agg_ops.resolve_groups_capacity(spec, out_cap)

        total = jnp.int64(0)
        overflow = jnp.bool_(False)
        parts = []
        if nb == 1:
            with jax.named_scope("join_agg"):
                partials, t, _g, ovf = agg_ops.local_join_aggregate(
                    build_w, probe_w, keys, spec, mode, groups_cap)
            parts.append(partials)
            total = total + t
            overflow = overflow | ovf
        else:
            with jax.named_scope("partition"):
                ptb = radix_hash_partition(build_w, keys, nb)
                ptp = radix_hash_partition(probe_w, keys, nb)
            tb = tape.scoped("build") if tape is not None else None
            tp = tape.scoped("probe") if tape is not None else None
            dtb = tape.scoped("build.integrity") if with_integrity \
                else None
            dtp = tape.scoped("probe.integrity") if with_integrity \
                else None
            _record_partition(tb, ptb, b_cap)
            _record_partition(tp, ptp, p_cap)
            for b in range(k):
                with jax.named_scope("shuffle"):
                    recv_build, ovf_b = _batch_shuffle(
                        comm, ptb, b, n, b_cap, mode=shuffle,
                        compression_bits=compression_bits,
                        tape=tb, digest_tape=dtb, dcn_codec_on=dcn_on)
                    recv_probe, ovf_p = _batch_shuffle(
                        comm, ptp, b, n, p_cap, mode=shuffle,
                        compression_bits=compression_bits,
                        tape=tp, digest_tape=dtp, dcn_codec_on=dcn_on)
                with jax.named_scope("join_agg"):
                    partials, t, _g, ovf_j = \
                        agg_ops.local_join_aggregate(
                            recv_build, recv_probe, keys, spec, mode,
                            groups_cap)
                parts.append(partials)
                total = total + t
                overflow = overflow | ovf_b | ovf_p | ovf_j
        if mode in ("probe", "build"):
            # Key mode needs NEITHER settle pass: a key lives in
            # exactly one (batch, rank) by the bucket arithmetic, so
            # per-batch per-rank partials are disjoint final groups.
            # Probe and build mode share both settle passes — the
            # regroup/exchange machinery is side-agnostic once the
            # partials table exists.
            if len(parts) > 1:
                # Non-key groups recur across batches — one combine
                # (concat + regroup sort at groups size) settles them.
                with jax.named_scope("agg_combine"):
                    combined, _g, ovf_c = agg_ops.combine_partials(
                        parts, spec, group_names, lanes_schema,
                        groups_cap)
                overflow = overflow | ovf_c
                parts = [combined]
            if n > 1:
                # The partials-only exchange: wire bytes are
                # O(groups), not O(output rows). Per-destination
                # capacity = the full partials block, so a SEND bucket
                # can never overflow (a rank holds at most groups_cap
                # valid partials); the post-exchange combine's flag
                # fires if one rank receives more distinct groups than
                # its block holds.
                with jax.named_scope("partials_exchange"):
                    ptg = radix_hash_partition(parts[0], group_names,
                                               n)
                    tg = tape.scoped("partials") if tape is not None \
                        else None
                    dtg = tape.scoped("partials.integrity") \
                        if with_integrity else None
                    recv, ovf_x = _batch_shuffle(
                        comm, ptg, 0, n, groups_cap,
                        mode=partials_mode, tape=tg, digest_tape=dtg)
                    combined, _g, ovf_c = agg_ops.combine_partials(
                        [recv], spec, group_names, lanes_schema,
                        groups_cap)
                overflow = overflow | ovf_x | ovf_c
                parts = [combined]
        finals = [agg_ops.finalize_groups(p, spec, group_names)
                  for p in parts]
        out = Table(
            {name: jnp.concatenate([t_.columns[name] for t_ in finals])
             for name in finals[0].column_names},
            jnp.concatenate([t_.valid for t_ in finals]),
        )
        if tape is not None:
            tape.add("matches", total)
            # Per-rank FINAL groups emitted (the gathered vector sums
            # to the global group count — every group lives on exactly
            # one rank after the settle passes above).
            tape.add("agg.groups",
                     jnp.sum(out.valid.astype(jnp.int64)))
            metrics = tape.gathered(comm)
        total = comm.psum(total)
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        result = JoinResult(out, total=total, overflow=overflow)
        return (result, metrics) if tape is not None else result

    return step


def resolve_probe_capacities(p_local: int, n: int, k: int,
                             shuffle_capacity_factor: float,
                             out_capacity_factor: float,
                             out_rows_per_rank: Optional[int]):
    """THE one probe-side capacity resolution of the probe-only
    program: ``(p_cap per (sender, destination) bucket, out_cap per
    batch)`` — shared by :func:`make_probe_join_step` and
    :func:`..planning.plan.build_probe_plan` so a probe-only EXPLAIN
    and the dispatched program can never drift apart (the
    resolve_join_ladder discipline, applied to the probe side)."""
    nb = k * n
    p_cap = _round_up(
        int(math.ceil(p_local / nb * shuffle_capacity_factor)), 8)
    if out_rows_per_rank is not None:
        out_cap = _round_up(
            int(math.ceil(int(out_rows_per_rank) / k)), 8)
    else:
        out_cap = _round_up(
            int(math.ceil(p_local / k * out_capacity_factor)), 8)
    return p_cap, out_cap


def make_probe_join_step(
    comm: Communicator,
    key: str = "key",
    over_decomposition: int = 1,
    shuffle_capacity_factor: float = DEFAULT_SHUFFLE_CAPACITY_FACTOR,
    out_capacity_factor: float = DEFAULT_OUT_CAPACITY_FACTOR,
    out_rows_per_rank: Optional[int] = None,
    build_payload: Optional[Sequence[str]] = None,
    probe_payload: Optional[Sequence[str]] = None,
    shuffle: str = "padded",
    compression_bits: Optional[int] = None,
    sort_mode: str = "flat",
    aggregate=None,
    kernel_config=None,
    with_metrics: bool = False,
    with_integrity: bool = False,
    metrics_static: Optional[dict] = None,
):
    """The PROBE-ONLY join step against a resident build image
    (service/resident.py; ROADMAP item 4).

    ``sort_mode``: "flat" only. The segmented-sort path needs BOTH
    sides segmented into the same hash classes, and a resident image
    is one flat key-sorted run registered before any probe's segment
    count exists — segment-aligned resident images are a named
    leftover, so "segmented" refuses loudly here instead of silently
    serving the flat program.

    ``aggregate`` (an :class:`~..ops.aggregate.AggregateSpec`, or
    None): the fused join+aggregate pipeline on the probe-only
    dispatch — partition + shuffle only the probe columns the
    reduction reads, reduce each batch against the full resident
    shard in the merged domain, and return per-group aggregates with
    zero materialization gathers (docs/AGGREGATION.md). Key-mode
    co-location holds by the registration hash ((h % kn) % n ==
    h % n); probe mode exchanges only the groups-sized partials. The
    same refusal contract as ``make_join_step(aggregate=)``.

    ``with_integrity=True`` weaves the wire-integrity digests
    (parallel/integrity.py) into the probe-side shuffle exactly as
    the full join does — per-(src, dst) payload digests riding the
    aux Metrics block (the step then returns ``(JoinResult,
    Metrics)``), verified host-side with
    ``integrity.verify_join_result``. The build side has no wire to
    digest: its image moved at registration, where the conservation
    check (service/resident.py) already guards it.

    ``step(resident_local, probe_local) -> JoinResult`` where
    ``resident_local`` is one rank's shard of a registered build table
    that ALREADY went through the expensive 2/3 of the pipeline —
    hash-partitioned to this rank, shuffled, key-sorted into a
    valid-prefix run (resident.make_resident_prep_step). Only the
    probe side is partitioned, shuffled, and sorted here; each batch
    merges against the full resident shard. Hash routing guarantees
    co-location at ANY over-decomposition k: a key's destination rank
    is ``h % n`` whether buckets were computed mod ``n``
    (registration, k=1) or mod ``k*n`` (this step) — ``(h % kn) % n
    == h % n`` — so matching keys always meet, and each probe row
    rides exactly one batch.

    The capacity contract mirrors :func:`make_join_step` on the probe
    side verbatim (same per-bucket arithmetic, same overflow flag →
    the same ``CapacityLadder`` escalates it); the build side has no
    capacities to size — its image is fixed at registration. The skew
    sidecar and 2-D (string) columns are not part of the probe-only
    program (resident registration refuses them up front); pass those
    workloads through the full join.
    """
    n = comm.n_ranks
    k = over_decomposition
    if k < 1:
        raise ValueError("over_decomposition must be >= 1")
    if shuffle not in ("padded", "ragged", "ppermute"):
        raise ValueError(f"unknown shuffle mode {shuffle!r}")
    if sort_mode not in SORT_MODES:
        raise ValueError(
            f"unknown sort_mode {sort_mode!r}; pick one of {SORT_MODES}")
    if sort_mode != "flat":
        raise ValueError(
            "sort_mode='segmented' is not part of the probe-only "
            "program: the resident build image is one flat key-sorted "
            "run registered before the probe's segment count is known, "
            "and segments must be the SAME hash classes on both sides "
            "— segment-aligned resident images are unimplemented; "
            "serve resident joins with sort_mode='flat'")
    if compression_bits is not None and shuffle == "ragged":
        raise ValueError(
            "compression applies to the padded/ppermute shuffles; the "
            "ragged exchange already sends exact rows (combining the "
            "two is unimplemented)"
        )
    if n > 1 and getattr(comm, "n_slices", 1) > 1:
        # The same guard make_join_step applies to its flat modes:
        # the probe-only program routes one GLOBAL collective, which
        # on a multi-slice mesh drags intra-slice traffic across DCN.
        # Hierarchical probe-only serving is a named ROADMAP leftover
        # — refuse loudly instead of silently mis-routing.
        raise ValueError(
            "probe-only joins route one GLOBAL collective over the "
            "mesh; a multi-slice topology would drag intra-slice "
            "traffic across DCN, and hierarchical probe-only serving "
            "is not implemented yet — register resident tables on a "
            "flat 1-D communicator")
    nb = k * n
    keys = [key] if isinstance(key, str) else list(key)

    if aggregate is not None:
        from distributed_join_tpu.ops import aggregate as agg_ops

        if not isinstance(aggregate, agg_ops.AggregateSpec):
            raise TypeError(
                "aggregate must be an ops.aggregate.AggregateSpec "
                f"(got {type(aggregate).__name__})")
        if build_payload is not None or probe_payload is not None:
            raise agg_ops.AggregatePushdownUnsupported(
                "aggregate pushdown unsupported: explicit payload "
                "lists conflict with the pushdown's own wire-column "
                "resolution")
        if kernel_config is not None:
            raise agg_ops.AggregatePushdownUnsupported(
                "aggregate pushdown unsupported: kernel_config tunes "
                "the materializing expand/compact gathers the fused "
                "reduction never runs — drop the knob")
        return _make_probe_agg_step(
            comm, aggregate, keys=keys, k=k,
            shuffle_capacity_factor=shuffle_capacity_factor,
            out_capacity_factor=out_capacity_factor,
            out_rows_per_rank=out_rows_per_rank,
            shuffle=shuffle, compression_bits=compression_bits,
            with_metrics=with_metrics, with_integrity=with_integrity,
            metrics_static=metrics_static)

    def step(resident_local: Table, probe_local: Table):
        # The integrity digests ride the same Metrics slot, so either
        # switch materializes the tape (and the aux output) — the
        # full join's contract, verbatim.
        tape = telemetry.MetricsTape() if (with_metrics
                                           or with_integrity) else None
        if tape is not None:
            for mname, mval in (metrics_static or {}).items():
                tape.add(mname, int(mval))
        for t, side in ((resident_local, "resident"),
                        (probe_local, "probe")):
            for name, c in t.columns.items():
                if c.ndim != 1:
                    raise TypeError(
                        f"{side} column {name!r} is {c.ndim}-D; the "
                        "probe-only program covers scalar columns "
                        "(register 2-D/string workloads through the "
                        "full join)")
        for kname in keys:
            bdt = resident_local.columns[kname].dtype
            pdt = probe_local.columns[kname].dtype
            if bdt != pdt:
                raise TypeError(
                    f"key {kname!r} dtype mismatch: resident {bdt} "
                    f"vs probe {pdt}")

        p_cap, out_cap = resolve_probe_capacities(
            probe_local.capacity, n, k, shuffle_capacity_factor,
            out_capacity_factor, out_rows_per_rank)

        if tape is not None:
            tape.add("resident.rows",
                     jnp.sum(resident_local.valid.astype(jnp.int64)))

        parts = []
        total = jnp.int64(0)
        overflow = jnp.bool_(False)
        if nb == 1:
            with jax.named_scope("join"):
                res = sort_merge_inner_join(
                    resident_local, probe_local, keys, out_cap,
                    build_payload=build_payload,
                    probe_payload=probe_payload,
                    kernel_config=kernel_config,
                )
            parts.append(res.table)
            total = total + res.total.astype(jnp.int64)
            overflow = overflow | res.overflow
        else:
            with jax.named_scope("partition"):
                ptp = radix_hash_partition(probe_local, keys, nb)
            tp = tape.scoped("probe") if tape is not None else None
            dtp = tape.scoped("probe.integrity") if with_integrity \
                else None
            _record_partition(tp, ptp, p_cap)
            for b in range(k):
                with jax.named_scope("shuffle"):
                    recv_probe, ovf_p = _batch_shuffle(
                        comm, ptp, b, n, p_cap, mode=shuffle,
                        compression_bits=compression_bits, tape=tp,
                        digest_tape=dtp)
                with jax.named_scope("join"):
                    res = sort_merge_inner_join(
                        resident_local, recv_probe, keys, out_cap,
                        build_payload=build_payload,
                        probe_payload=probe_payload,
                        kernel_config=kernel_config,
                    )
                parts.append(res.table)
                total = total + res.total.astype(jnp.int64)
                overflow = overflow | ovf_p | res.overflow
        out = Table(
            {
                name: jnp.concatenate([t.columns[name] for t in parts])
                for name in parts[0].column_names
            },
            jnp.concatenate([t.valid for t in parts]),
        )
        if tape is not None:
            tape.add("matches", total)
            metrics = tape.gathered(comm)
        total = comm.psum(total)
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        result = JoinResult(out, total=total, overflow=overflow)
        return (result, metrics) if tape is not None else result

    return step


def _make_probe_agg_step(comm, spec, *, keys, k,
                         shuffle_capacity_factor, out_capacity_factor,
                         out_rows_per_rank, shuffle, compression_bits,
                         with_metrics, with_integrity, metrics_static):
    """The PROBE-ONLY fused join+aggregate step
    (``make_probe_join_step(aggregate=)``): the resident build image
    is already hash-co-located, so only the probe's needed columns
    partition + shuffle, each batch reduces against the full resident
    shard, and the partials settle exactly as in the full fused step
    (key mode final per rank; probe mode one cross-batch combine plus
    the groups-sized padded partials exchange)."""
    from distributed_join_tpu.ops import aggregate as agg_ops

    n = comm.n_ranks
    nb = k * n
    # Probe-only refuses shuffle="hierarchical" today, so this always
    # resolves to "padded" — kept as the full fused step's expression
    # so the two pipelines cannot route partials apart if the
    # probe-only path ever learns the two-level exchange.
    partials_mode = "hierarchical" if shuffle == "hierarchical" \
        else "padded"

    def step(resident_local: Table, probe_local: Table):
        tape = telemetry.MetricsTape() if (with_metrics
                                           or with_integrity) else None
        if tape is not None:
            for mname, mval in (metrics_static or {}).items():
                tape.add(mname, int(mval))
        for t, side in ((resident_local, "resident"),
                        (probe_local, "probe")):
            for name, c in t.columns.items():
                if c.ndim != 1:
                    raise TypeError(
                        f"{side} column {name!r} is {c.ndim}-D; the "
                        "probe-only program covers scalar columns")
        for kname in keys:
            bdt = resident_local.columns[kname].dtype
            pdt = probe_local.columns[kname].dtype
            if bdt != pdt:
                raise TypeError(
                    f"key {kname!r} dtype mismatch: resident {bdt} "
                    f"vs probe {pdt}")
        bschema = agg_ops.table_schema(resident_local)
        pschema = agg_ops.table_schema(probe_local)
        mode = agg_ops.resolve_agg_mode(spec, keys, bschema, pschema)
        if mode == "build":
            raise agg_ops.AggregatePushdownUnsupported(
                "group keys live on the RESIDENT (build) side; the "
                "probe-only program keeps the build shards pinned and "
                "only exchanges probe rows, so build-keyed group-bys "
                "ride make_join_step(aggregate=) instead")
        wire_b, wire_p = agg_ops.wire_columns(spec, mode, keys,
                                              bschema, pschema)
        resident_w = resident_local.select(wire_b)
        probe_w = probe_local.select(wire_p)
        lanes_schema = agg_ops.partial_lane_schema(spec, bschema,
                                                   pschema)
        group_names = list(keys) if mode == "key" \
            else list(spec.group_keys)

        p_cap, out_cap = resolve_probe_capacities(
            probe_w.capacity, n, k, shuffle_capacity_factor,
            out_capacity_factor, out_rows_per_rank)
        groups_cap = agg_ops.resolve_groups_capacity(spec, out_cap)
        if tape is not None:
            tape.add("resident.rows",
                     jnp.sum(resident_local.valid.astype(jnp.int64)))

        total = jnp.int64(0)
        overflow = jnp.bool_(False)
        parts = []
        if nb == 1:
            with jax.named_scope("join_agg"):
                partials, t, _g, ovf = agg_ops.local_join_aggregate(
                    resident_w, probe_w, keys, spec, mode, groups_cap)
            parts.append(partials)
            total = total + t
            overflow = overflow | ovf
        else:
            with jax.named_scope("partition"):
                ptp = radix_hash_partition(probe_w, keys, nb)
            tp = tape.scoped("probe") if tape is not None else None
            dtp = tape.scoped("probe.integrity") if with_integrity \
                else None
            _record_partition(tp, ptp, p_cap)
            for b in range(k):
                with jax.named_scope("shuffle"):
                    recv_probe, ovf_p = _batch_shuffle(
                        comm, ptp, b, n, p_cap, mode=shuffle,
                        compression_bits=compression_bits, tape=tp,
                        digest_tape=dtp)
                with jax.named_scope("join_agg"):
                    partials, t, _g, ovf_j = \
                        agg_ops.local_join_aggregate(
                            resident_w, recv_probe, keys, spec, mode,
                            groups_cap)
                parts.append(partials)
                total = total + t
                overflow = overflow | ovf_p | ovf_j
        if mode == "probe":
            if len(parts) > 1:
                with jax.named_scope("agg_combine"):
                    combined, _g, ovf_c = agg_ops.combine_partials(
                        parts, spec, group_names, lanes_schema,
                        groups_cap)
                overflow = overflow | ovf_c
                parts = [combined]
            if n > 1:
                with jax.named_scope("partials_exchange"):
                    ptg = radix_hash_partition(parts[0], group_names,
                                               n)
                    tg = tape.scoped("partials") if tape is not None \
                        else None
                    dtg = tape.scoped("partials.integrity") \
                        if with_integrity else None
                    recv, ovf_x = _batch_shuffle(
                        comm, ptg, 0, n, groups_cap,
                        mode=partials_mode, tape=tg, digest_tape=dtg)
                    combined, _g, ovf_c = agg_ops.combine_partials(
                        [recv], spec, group_names, lanes_schema,
                        groups_cap)
                overflow = overflow | ovf_x | ovf_c
                parts = [combined]
        finals = [agg_ops.finalize_groups(p, spec, group_names)
                  for p in parts]
        out = Table(
            {name: jnp.concatenate([t_.columns[name] for t_ in finals])
             for name in finals[0].column_names},
            jnp.concatenate([t_.valid for t_ in finals]),
        )
        if tape is not None:
            tape.add("matches", total)
            tape.add("agg.groups",
                     jnp.sum(out.valid.astype(jnp.int64)))
            metrics = tape.gathered(comm)
        total = comm.psum(total)
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        result = JoinResult(out, total=total, overflow=overflow)
        return (result, metrics) if tape is not None else result

    return step


def make_distributed_join(comm: Communicator, with_metrics=None,
                          with_integrity: bool = False, **opts):
    """Compile a distributed inner join over ``comm``'s ranks.

    Returns a jitted ``fn(build: Table, probe: Table) -> JoinResult``
    taking row-sharded global Tables (capacity divisible by n_ranks) and
    returning a row-sharded result Table plus a replicated global match
    count and overflow flag. See :func:`make_join_step` for options.

    ``with_metrics=None`` (default) resolves from the global telemetry
    state: with a session active the compiled program additionally
    emits the device-metrics block and the result carries it as a
    host-side ``res.telemetry`` attribute (a ``telemetry.Metrics``;
    like ``retry_report``, not a pytree field — the call signature and
    the JoinResult pytree are unchanged either way). With telemetry
    off this is exactly the seed program.

    ``with_integrity=True`` weaves the wire-integrity digests into the
    same aux Metrics block (``res.telemetry`` then always exists, even
    with telemetry off) — verify with ``integrity.verify_join_result``
    or use :func:`distributed_inner_join`'s ``verify_integrity``.
    """
    if with_metrics is None:
        with_metrics = telemetry.enabled()
    step = make_join_step(comm, with_metrics=with_metrics,
                          with_integrity=with_integrity, **opts)
    if not (with_metrics or with_integrity):
        return comm.spmd(step, sharded_out=JOIN_SHARDED_OUT)
    compiled = comm.spmd(step, sharded_out=JOIN_METRICS_SHARDED_OUT)

    def fn(build: Table, probe: Table) -> JoinResult:
        res, metrics = compiled(build, probe)
        object.__setattr__(res, "telemetry", metrics)
        return res

    return fn


def resolve_join_ladder(build, probe, n_ranks: int, opts: dict,
                        n_slices: int = 1):
    """THE one resolution of ``distributed_inner_join``'s capacity
    contract: pop the sizing knobs from ``opts`` (mutated — what
    remains goes to ``make_join_step`` verbatim), resolve the skew
    defaults exactly as the step would, and return the
    :class:`..faults.CapacityLadder` at its initial rung.

    Shared with :func:`..planning.explain_join` so an EXPLAIN's plan
    resolves the identical sizing a real call would run — the two
    can never drift apart."""
    from distributed_join_tpu.parallel.faults import CapacityLadder

    shuffle_f = opts.pop("shuffle_capacity_factor",
                         DEFAULT_SHUFFLE_CAPACITY_FACTOR)
    out_f = opts.pop("out_capacity_factor", DEFAULT_OUT_CAPACITY_FACTOR)
    # Resolve the HH capacities here so retries can double them too —
    # overflow can originate in the skew path as well as the shuffle.
    skew_on = opts.get("skew_threshold") is not None
    hh_build_cap = opts.pop("hh_build_capacity", None)
    hh_probe_cap = opts.pop("hh_probe_capacity", None)
    hh_out_cap = opts.pop("hh_out_capacity", None)
    if skew_on:
        hh_build_cap = hh_build_cap or (
            opts.get("hh_slots", DEFAULT_HH_SLOTS) * HH_BUILD_SLOTS_PER_HH
        )
        hh_probe_cap = hh_probe_cap or max(
            probe.capacity // (8 * n_ranks), 1024)
        hh_out_cap = hh_out_cap or max(
            probe.capacity // (4 * n_ranks), 1024)
    out_rows = opts.pop("out_rows_per_rank", None)
    comp_bits = opts.pop("compression_bits", None)
    if opts.get("shuffle") == "hierarchical" and comp_bits is None:
        # The hierarchical DCN codec defaults its residual width when
        # the caller set only the knob; resolving it HERE (not just
        # inside the step) hands the bits to the ladder, so a
        # cross-slice residual overflow escalates by widening bits —
        # the cheap axis — instead of uselessly doubling capacities.
        # Topology-gated: one slice has no cross-slice payload (the
        # degenerate path routes flat raw padded), so arming bits
        # there would burn the first rung widening a no-op knob.
        from distributed_join_tpu.planning.cost import (
            resolve_dcn_bits,
        )

        comp_bits = resolve_dcn_bits(
            opts.get("dcn_codec", "auto"), None, n_slices=n_slices)
    # The escalation policy — compression bits widen first (the cheap
    # axis), then every capacity doubles with the skew capacities
    # jumping straight to full local probe coverage — lives in
    # CapacityLadder so drivers escalate identically and the
    # decisions survive as a RetryReport.
    return CapacityLadder(
        shuffle_capacity_factor=shuffle_f,
        out_capacity_factor=out_f,
        out_rows_per_rank=out_rows,
        compression_bits=comp_bits,
        skew=skew_on,
        hh_build_capacity=hh_build_cap,
        hh_probe_capacity=hh_probe_cap,
        hh_out_capacity=hh_out_cap,
        local_probe_rows=probe.capacity // n_ranks,
    )


def _resolve_skew(comm, build, probe, key, opts: dict) -> None:
    """The skew path, resolved from the input (mutates ``opts``).

    An explicit ``skew_threshold`` keeps its meaning, except 0, which
    forces the plain path. Where the caller set none, on a mesh of
    more than one rank and for the shapes the skew sidecar takes (an
    inner join, the flat sort, no aggregate), a detection program
    decides (:func:`..skew.resolve`) under the ``entry.skew_resolve``
    span, whose arguments say what it found; turned on, it sets the
    threshold and the HH blocks the caller left unset. Off, ``opts``
    stays as given, so the program is the one the caller's options
    name."""
    threshold = opts.get("skew_threshold")
    if threshold == 0:
        opts.pop("skew_threshold")
        return
    n = comm.n_ranks
    if (threshold is not None or n == 1
            or opts.get("join_type", "inner") != "inner"
            or opts.get("sort_mode", "flat") != "flat"
            or opts.get("aggregate") is not None):
        return
    from distributed_join_tpu.parallel import skew

    nb = opts.get("over_decomposition", 1) * n
    hh_slots = opts.get("hh_slots", DEFAULT_HH_SLOTS)
    factor = opts.get("shuffle_capacity_factor",
                      DEFAULT_SHUFFLE_CAPACITY_FACTOR)
    with telemetry.span("entry.skew_resolve"):
        res = skew.resolve(
            comm, build, probe, key, n_buckets=nb,
            bucket_capacity=_bucket_capacity(probe.capacity // n, nb,
                                             factor),
            hh_slots=hh_slots,
            hh_build_floor=hh_slots * HH_BUILD_SLOTS_PER_HH)
        telemetry.note(**res.as_args())
    for name, value in res.options.items():
        if opts.get(name) is None:
            opts[name] = value


def distributed_inner_join(
    build: Table,
    probe: Table,
    comm: Communicator,
    key: str = "key",
    auto_retry: int = 0,
    verify_integrity: bool = False,
    program_cache=None,
    explain: bool = False,
    tuner=None,
    **opts,
) -> JoinResult:
    """One-shot convenience: pad to rank-divisible capacity, shard the
    inputs over the mesh, compile and run. For benchmarking, build the
    function once with :func:`make_distributed_join` instead.

    ``auto_retry``: on overflow (a static capacity too small for the
    data), recompile with escalated capacities up to this many times —
    the policy lives in :class:`..faults.CapacityLadder` (compression
    bits widen first, then every capacity doubles, with the skew
    capacities jumping to full local probe coverage). The reference
    sizes receive buffers exactly and can't overflow (SURVEY.md §2);
    static shapes can, so they get an escape hatch instead of a wrong
    answer. The returned result carries the full escalation trail as a
    host-side ``retry_report`` attribute (:class:`..faults.RetryReport`
    — which capacities doubled, why, per attempt), which the benchmark
    drivers embed in their JSON records.

    Skew (BASELINE config 3): where ``opts`` set no ``skew_threshold``,
    an inner join with the flat sort and no aggregate on a mesh of more
    than one rank first runs a small detection program
    (:func:`..skew.resolve`, span ``entry.skew_resolve``). It turns the
    heavy-hitter path on only where the heavy keys would overflow the
    padded shuffle, with blocks sized from what it measured; otherwise
    the program is the one the options name. ``skew_threshold=0``
    forces the plain path; any other value keeps its meaning
    (:func:`make_join_step`).

    ``verify_integrity``: compute in-graph wire digests
    (parallel/integrity.py) and verify every (src, dst) pair host-side
    before returning. A mismatch is a RETRYABLE rung distinct from
    overflow — the ladder re-runs the SAME sizing (``retry_integrity``
    in the report; transport corruption is transient, capacities are
    innocent) up to the ``auto_retry`` budget, and raises
    :class:`..integrity.IntegrityError` instead of returning corrupt
    rows when the budget runs out. A verified clean result carries the
    report as ``res.integrity_report``. Verification is skipped on an
    overflowed attempt (clamped rows mismatch by design; the overflow
    rung handles it).

    ``program_cache``: a :class:`..service.programs.JoinProgramCache`.
    When given, every attempt resolves its executable THROUGH the
    cache instead of building a fresh closure — a repeat query, or a
    retry rung whose exact sizing (the ladder's ``sizing()`` plus the
    attempt index) was seen before, dispatches the resident program
    with zero new traces (the serving warm path, docs/SERVICE.md).
    Exception: an integrity-mismatch rung EVICTS the attempt's entry
    before the same-sizing rerun — injected corruption is woven at
    trace time, so only a re-trace is guaranteed to face a fresh
    schedule, and a possibly-tainted resident program must not keep
    serving. Default None: build per call, the historical behavior.

    ``explain``: attach the fully-resolved :class:`..planning.JoinPlan`
    of the attempt that produced the result (final ladder rung) as a
    host-side ``res.plan`` attribute — capacities, wire-byte and
    wall-time predictions, and the canonical signature digest, which
    equals the program cache's key for the same call
    (docs/OBSERVABILITY.md "Explain & cost model"). Plan construction
    is pure host arithmetic — no extra traces or compiles; use
    :func:`..planning.explain_join` for the plan WITHOUT running.

    ``tuner``: a :class:`..planning.tuner.JoinTuner`. When given, the
    call's workload signature is looked up in the tuner's history
    table BEFORE the ladder resolves: a repeat workload whose ladder
    previously escalated starts at the final rung it resolved to —
    sizing AND rung label, so with a ``program_cache`` the dispatch
    is the already-resident executable (zero new traces, zero
    escalations) — and evidence-backed structural knobs (PRPD skew,
    ragged wire) fill in when the caller left them unset. No history
    for the signature = the exact static (tuner-off) resolution. The
    verdict is attached host-side as ``res.tuned``
    (``TunedConfig.as_record()``); the ladder still guards every
    run, so a lying history costs recompiles, never wrong rows.
    """
    from distributed_join_tpu.parallel import faults, integrity

    if program_cache is not None and program_cache.comm is not comm:
        # The cache compiles over ITS communicator's mesh; silently
        # running this join on a different mesh would be a wrong-shard
        # answer, not a slow one.
        raise ValueError(
            "program_cache was built for a different communicator")

    n = comm.n_ranks

    # Host spans (telemetry.span: a TraceAnnotation, plus a sink event
    # with a session) split each call on the profiler's clock:
    # prepare (-> skew_resolve) -> resolve (-> compile on a
    # program-cache miss) -> dispatch -> fetch_overflow -> finish.
    with telemetry.span("entry.prepare"):
        tuned = None
        if tuner is not None:
            # Resolved on the UNPADDED tables and pre-tuned opts — the
            # same basis JoinService keys its history entries on, so
            # the signature the tuner reads is the one the store wrote.
            tuned = tuner.resolve(comm, build, probe, key=key,
                                  with_integrity=verify_integrity,
                                  opts=opts)
            opts = tuned.apply(opts)

        build = build.pad_to(_round_up(build.capacity, n))
        probe = probe.pad_to(_round_up(probe.capacity, n))
        if hasattr(comm, "device_put_sharded"):
            build, probe = comm.device_put_sharded((build, probe))
    _resolve_skew(comm, build, probe, key, opts)

    with telemetry.span("entry.resolve"):
        ladder = resolve_join_ladder(
            build, probe, n, opts, n_slices=getattr(comm, "n_slices", 1))
        if tuned is not None:
            ladder.seed_rung(tuned.rung)
    last_sig = None
    for attempt in range(auto_retry + 1):
        # The rung label is ABSOLUTE (ladder.base_rung + attempt): a
        # tuner-pre-sized first attempt carries the same label — hence
        # the same program signature — as the executable the cold
        # run's escalation already traced at this sizing.
        rung = ladder.base_rung + attempt
        with telemetry.span("entry.resolve"):
            if program_cache is not None:
                fn, _ = program_cache.get(
                    build, probe, key=key,
                    with_integrity=verify_integrity,
                    metrics_static={"retry_attempt_max": rung},
                    **ladder.sizing(), **opts)
                last_sig = fn.signature
            else:
                fn = make_distributed_join(
                    comm, key=key, with_integrity=verify_integrity,
                    metrics_static={"retry_attempt_max": rung},
                    **ladder.sizing(), **opts)
        if faults.plan_validation_enabled():
            # The violation record is process-global; drop leftovers
            # from earlier unchecked programs so what check() raises
            # below belongs to THIS attempt.
            faults.clear_plan_violations()
        with telemetry.span("entry.dispatch"):
            res = fn(build, probe)
        # out_rows is the program's own output sizing, read from the
        # result's static shape: nothing is fetched for it.
        with telemetry.span("entry.fetch_overflow",
                            out_rows=res.table.capacity, rung=rung):
            overflow = bool(res.overflow)
        with telemetry.span("entry.finish"):
            if faults.plan_validation_enabled():
                # The flag fetch above sequenced the validation callbacks;
                # surface a recorded inconsistency as the loud error it is
                # rather than retrying a corrupted-metadata exchange.
                faults.check_plan_violations()
            report = None
            if verify_integrity and not overflow:
                # Overflow attempts skip verification: a clamp drops rows
                # by design and the overflow rung already forces a retry.
                report = integrity.verify_join_result(res)
            ladder.note(overflow,
                        integrity_ok=None if report is None else report.ok)
            failed = overflow or (report is not None and not report.ok)
            if attempt == auto_retry or not failed:
                # retry_report is host-side metadata, not a pytree field:
                # JoinResult traces through shard_map, and the report only
                # exists outside the compiled program.
                object.__setattr__(res, "retry_report", ladder.report())
                if tuned is not None:
                    object.__setattr__(res, "tuned", tuned.as_record())
                if explain:
                    # Host arithmetic only (no trace/compile): the plan of
                    # the attempt that produced THIS result — its digest is
                    # the program cache's key for the same sizing.
                    from distributed_join_tpu import planning

                    object.__setattr__(res, "plan", planning.build_plan(
                        comm, build, probe, key=key,
                        with_integrity=verify_integrity,
                        metrics_static={"retry_attempt_max": rung},
                        **ladder.sizing(), **opts))
                if report is not None:
                    object.__setattr__(res, "integrity_report", report)
                # Fold the device metrics of the FINAL attempt into the
                # telemetry session (one host fetch, after the retry loop
                # settled — the flag fetch above already synced).
                telemetry.emit_metrics(getattr(res, "telemetry", None))
                if report is not None and not report.ok:
                    # Never hand corrupt rows back as a result. The
                    # budget-exhausted rung is as tainted as a retried
                    # one: a resident (or persisted) program that failed
                    # verification must not serve the next same-signature
                    # request.
                    if program_cache is not None and last_sig is not None:
                        program_cache.evict(last_sig)
                    raise integrity.IntegrityError(report)
                return res
            if overflow:
                ladder.escalate()
            else:
                # Integrity mismatch: rerun the SAME sizing — the rows
                # were wrong, not too many. Every retry recompiles (a
                # cached entry for this rung is evicted first), so a
                # deterministic injected corruption budget (FaultPlan)
                # exhausts and the rerun can verify clean.
                if program_cache is not None and last_sig is not None:
                    program_cache.evict(last_sig)
                ladder.hold("retry_integrity")
    raise AssertionError("unreachable")
