"""Per-partition sort-merge inner join.

The reference's local join step delegates to ``cudf::hash_join`` —
build a GPU hash table on the smaller side, probe with the larger
(SURVEY.md §2 "Local join step"). Hash tables need random scatter/gather
and data-dependent probing loops, which map badly onto the TPU's vector
units; the TPU-native formulation (SURVEY.md §7 step 1) is sort-merge.

Round 2 profiling on v5e (scripts/profile_*.py, measured with the
chained-loop protocol) established the cost model this implementation
is built around:

- ``lax.sort`` VALUE operands are nearly free: +4 extra int64 operands
  on a 20M-row sort cost +23 ms on a 137 ms sort. Sorts are the cheap
  way to MOVE data.
- random gathers/scatters cost ~10-20 ns per processed element
  regardless of index locality (sorted vs random indices: no
  difference), and a 64-bit scatter is catastrophic (emulated: 2.5 s
  vs 90 ms for int32 at 7.5M elements).
- a row gather from a 2-D (rows, k) pack costs the same as from a 1-D
  array for k = 1..4: packing columns amortizes gathers to one per
  dtype group instead of one per column.

One more measured fact shaped the final design: a benchmark that
consumes only part of the output lets XLA dead-code-eliminate the rest
(an early guard consumed one column and silently deleted half the
join); all variant comparisons below were re-run with every output
column consumed (utils/benchmarking.py consume_all_columns). Under
honest consumption, the scatter-based expansion cost 486 ms of a
1050 ms 10Mx10M join — so the expansion was moved out of the merged
domain entirely. The structure — THREE sorts that carry all values,
ONE small int32 scatter, one packed row-gather per dtype group:

  1. build-side sort: build keys + validity tag + all 1-D build payload
     columns ride one nb-row sort. Valid build rows land in a key-sorted
     prefix whose order matches their merge rank below (both orders are
     (key, within-key-arbitrary) over valid rows; see the no-stability
     note in the code).
  2. merged sort: concatenated (build, probe) keys + side tag; probe's
     1-D payload columns ride. Builds sort before probes of an equal
     key (tag 0 < 1), padding sinks (tag 2 plus key sentinel).
  3. scans recover, for every probe position, its run of matching build
     ranks [lo, lo+cnt) — cumsum of the build indicator and a cummax
     broadcast of run-start values; no gathers, no searchsorted (a v5e
     binary search is ~25 random-gather rounds — measured 3.8 s at 10M
     queries in round 1).
  4. run-record compaction sort: one record per matching probe, keyed
     by its first output slot, with every probe-side output value plus
     the run geometry riding; the records land in a dense
     output-ordered prefix.
  5. ONE int32 scatter (out_capacity operand, unique slots) posts each
     record's index at its first output slot; a cummax broadcasts it
     down the run; then one packed row-gather per dtype group pulls
     probe-side values from the records and build-side values from the
     step-1 sorted prefix at the in-run build rank.

Output capacity is static (XLA constraint); the true match count and
an overflow flag are returned alongside. Duplicate keys on either side
are fully supported (runs x runs expansion). Null/padding rows never
match. Composite (multi-column) keys are extra key operands of the same
sorts. 2-D columns (fixed-width strings, utils/strings.py) cannot ride
``lax.sort`` (rank-1 operands only), so their row indices are carried
instead and they pay one 2-D row-gather per column — the same cost
shape as round 1 for exactly the columns that need it.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence

import jax

import jax.numpy as jnp
from jax import lax

from distributed_join_tpu.ops.kernel_config import (
    KernelConfig,
    resolve as resolve_kernel_config,
)
from distributed_join_tpu.table import Table


def _dtype_sentinel_max(dt):
    # Typed scalar, not a weak Python number: uint64's max overflows
    # the default int64 weak-type path inside where()/full().
    if jnp.issubdtype(dt, jnp.integer):
        return jnp.asarray(jnp.iinfo(dt).max, dtype=dt)
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.asarray(jnp.inf, dtype=dt)
    raise TypeError(f"unsupported key dtype {dt}")


# A plain int, NOT jnp.int32(...): a module-level device constant would
# initialize the XLA backend at import time, which breaks the multi-host
# bootstrap contract (jax.distributed.initialize must run first).
_I32_MAX = 2**31 - 1

# The join-type family (docs/QUERY.md). Orientation: PROBE is the
# preserved ("left") side, BUILD the other — matching the build/probe
# naming everywhere else in the repo. Outer variants append bool
# validity columns (BUILD_VALID / PROBE_VALID) marking which side of
# each output row carries real values; NULL payloads are zeroed.
JOIN_TYPES = ("inner", "left", "right", "full_outer", "semi", "anti")
OUTER_TYPES = ("left", "right", "full_outer")
BUILD_VALID = "build#valid"   # emitted by left / full_outer
PROBE_VALID = "probe#valid"   # emitted by right / full_outer

def _holds_i32_exactly(dt) -> bool:
    """Can dt round-trip any NON-NEGATIVE int32 value (for riding the
    int32 run-geometry lanes in the key dtype's gather pack)? f32's
    24-bit mantissa cannot."""
    if jnp.issubdtype(dt, jnp.integer):
        return jnp.iinfo(dt).bits >= 32
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.finfo(dt).nmant >= 31
    return False


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class JoinResult:
    table: Table          # static capacity; .valid marks real result rows
    total: jax.Array      # true number of matches (may exceed capacity)
    overflow: jax.Array   # bool: total > capacity, rows were truncated
    # Results returned by parallel.distributed_join.distributed_inner_join
    # additionally carry a host-side `retry_report` attribute
    # (parallel/faults.RetryReport: the auto_retry escalation trail).
    # It is NOT a pytree field — JoinResult traces through shard_map,
    # and the report only exists outside the compiled program.


def patch_string_lengths(table: Table, keys, join_type: str) -> Table:
    """Recompute '<key>#len' companions from the rebuilt key BYTES on
    rows whose probe side is absent (right/full_outer): the companion
    rides as ordinary probe payload, so an unmatched-build row gets a
    NULL-zeroed length even though its key bytes are exact. The
    encoding is zero-padded with no interior NULs (utils/strings), so
    the byte count recovers the true length. No-op for other types."""
    if join_type not in ("right", "full_outer"):
        return table
    from distributed_join_tpu.utils.strings import LEN_SUFFIX

    cols = dict(table.columns)
    pm = cols[PROBE_VALID]
    changed = False
    for k in keys:
        ln = k + LEN_SUFFIX
        if ln in cols and cols[k].ndim == 2:
            from_bytes = jnp.sum(
                (cols[k] != 0).astype(cols[ln].dtype), axis=1
            )
            cols[ln] = jnp.where(pm, cols[ln], from_bytes)
            changed = True
    return Table(cols, table.valid) if changed else table


def _to_u64_lane(c: jax.Array):
    """Bit-exact uint64 encoding of a column, or None if impossible on
    TPU (f64: the x64 bitcast rewrite is unimplemented there)."""
    dt = c.dtype
    if dt in (jnp.int64, jnp.uint64):
        return c.astype(jnp.uint64)  # two's-complement wrap: same bits
    if jnp.issubdtype(dt, jnp.integer) and jnp.iinfo(dt).bits <= 32:
        # zero-extend the BIT PATTERN (astype of signed would
        # sign-extend and change the upper lanes)
        unsigned = jnp.dtype(f"uint{jnp.iinfo(dt).bits}")
        return c.astype(unsigned).astype(jnp.uint64)
    if dt == jnp.float32:
        return lax.bitcast_convert_type(c, jnp.uint32).astype(jnp.uint64)
    return None


def _from_u64_lane(c64: jax.Array, dt):
    if dt in (jnp.int64, jnp.uint64):
        return c64.astype(dt)
    if jnp.issubdtype(dt, jnp.integer):
        unsigned = jnp.dtype(f"uint{jnp.iinfo(dt).bits}")
        return c64.astype(unsigned).astype(dt)
    if dt == jnp.float32:
        return lax.bitcast_convert_type(
            c64.astype(jnp.uint32), jnp.float32
        )
    raise TypeError(dt)


def _expand_records(S, recs: dict, out_capacity: int, j, total, cfg,
                    kernel_prefix: str = ""):
    """Broadcast each record's values down its output run (the XLA
    join path's expansion; the kernel pipeline's lives in
    _join_kernel_path with the fused build-side materialization).

    Returns ``(out_vals, start_b)``: the expanded record values and
    each slot's run-start output slot (the caller derives the build
    rank from the expanded ``__lo`` and start_b, then gathers).

    XLA formulation: one unique-slot int32 scatter + cummax gives each
    slot its record index; packed row-gathers per dtype group pull the
    values; start_b is a second cummax over the raw marks.

    The Pallas record-expand (``cfg.expand``; non-f64 columns only)
    replaces all three with the streaming one-hot-matmul kernel of
    ops/expand_pallas.py, which computes only the output blocks below
    ``total`` (the slots the records cover; the rest are masked by the
    caller). This path is reached on TPU only when
    _kernel_path_ok rejected the full pipeline (f64 columns route to
    the scatter below instead; oversized blocks still benefit here).
    """
    use_pallas, interpret = cfg.expand_enabled()
    if use_pallas and interpret and getattr(
        jax.typeof(S), "vma", None
    ):
        # The Mosaic lowering works under shard_map on real TPU
        # (compile-checked: tpu_custom_call in the mesh module); only
        # the INTERPRETER trips shard_map's vma checks, so the CPU
        # test mesh falls back to the XLA path.
        use_pallas = False
    if use_pallas:
        from distributed_join_tpu.ops.expand_pallas import expand_gather

        lanes = {nm: _to_u64_lane(c) for nm, c in recs.items()}
        if all(v is not None for v in lanes.values()):
            names = list(lanes)
            rec_outs, start_b = expand_gather(
                S, [lanes[nm] for nm in names], out_capacity, total,
                block=cfg.block, interpret=interpret,
                name_prefix=kernel_prefix,
            )
            out_vals = {
                nm: _from_u64_lane(rec_outs[i], recs[nm].dtype)
                for i, nm in enumerate(names)
            }
            return out_vals, start_b

    raw = jnp.zeros((out_capacity,), jnp.int32).at[S].set(
        j + 1, mode="drop", unique_indices=True
    )
    ridx = jnp.maximum(lax.cummax(raw) - 1, 0)
    out_vals = _grouped_row_gather(recs, ridx)
    # The run's first slot is where its raw mark landed — cheaper as an
    # out-domain cummax than as another ridden sort lane.
    start_b = lax.cummax(jnp.where(raw > 0, j, 0))
    return out_vals, start_b


def _chunked_rank_gather(lanes_u64, idx: jax.Array):
    """Gather uint64 lanes at ``idx`` through uint32 HALF-PLANES — the
    kernel fallback's rank gather (ROADMAP item 2b; ROOFLINE §7's
    named residual large-N cost). The measured economics (§1): XLA's
    TPU gather is a serialized per-element loop whose cost tracks the
    element WIDTH — 7.5M i64 gathers run 161-205 ms while i32 runs
    70 ms — and a packed (rows, k<=4) row gather is flat in k. So
    splitting each u64 lane into (lo32, hi32) and gathering the
    (rows, 2k) u32 pack in one pass moves the same bytes at the
    narrow-element rate; the halves recombine with cheap elementwise
    shifts, bit-exactly."""
    planes = []
    for c in lanes_u64:
        planes.append((c & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
        planes.append((c >> jnp.uint64(32)).astype(jnp.uint32))
    if len(planes) == 2:
        lo, hi = planes[0][idx], planes[1][idx]
        return [lo.astype(jnp.uint64)
                | (hi.astype(jnp.uint64) << jnp.uint64(32))]
    packed = jnp.stack(planes, axis=1)
    rows = packed[idx]
    out = []
    for i in range(len(lanes_u64)):
        lo = rows[:, 2 * i].astype(jnp.uint64)
        hi = rows[:, 2 * i + 1].astype(jnp.uint64)
        out.append(lo | (hi << jnp.uint64(32)))
    return out


def _grouped_row_gather(cols: dict, idx: jax.Array) -> dict:
    """Gather rows ``idx`` from every 1-D column, one packed 2-D gather
    per dtype group (columns of a dtype are stacked, gathered once,
    unstacked — flat in column count per the profile)."""
    groups: dict = {}
    for name, c in cols.items():
        groups.setdefault(c.dtype, []).append(name)
    out = {}
    for dt, names in groups.items():
        if len(names) == 1:
            out[names[0]] = cols[names[0]][idx]
        else:
            pack = jnp.stack([cols[n] for n in names], axis=1)
            rows = pack[idx]
            for j, n in enumerate(names):
                out[n] = rows[:, j]
    return out


def _u64_lane_ok(dt) -> bool:
    """Static form of _to_u64_lane's dtype dispatch (no tracing)."""
    if dt in (jnp.int64, jnp.uint64) or dt == jnp.float32:
        return True
    return jnp.issubdtype(dt, jnp.integer) and jnp.iinfo(dt).bits <= 32


def _kernel_path_ok(build, probe, keys, b1d, p1d, nb, npr,
                    out_capacity, cfg):
    """Choose between the fused-kernel pipeline (merged sort -> fused
    scans -> stream compactions -> expand kernel; TPU) and the XLA
    pipeline (everything below; CPU tests, f64 columns, empty sides,
    merged domains past int32). Returns (use, interpret).

    Round-4: the old f32-exact (2^24) rank limits are gone entirely —
    first the gate stopped disqualifying the whole path (they had
    silently dropped config 2's spec-scale joins onto the XLA path, a
    3-4x cliff measured at the boundary, results/scale_curve_r4.json),
    then the fused-build kernel's rank arithmetic went block-relative
    (expand_pallas._expand_kernel_b8), removing the limit at the
    source. Only int32 domain bounds remain."""
    use, interpret = cfg.expand_enabled()
    if not use:
        return False, False
    if interpret and getattr(
        jax.typeof(build.columns[keys[0]]), "vma", None
    ):
        # shard_map's interpreter trips on pallas_call vma checks; the
        # CPU test mesh runs the XLA pipeline instead (real-TPU
        # shard_map compiles the kernels fine).
        return False, False
    if not (0 < nb and npr > 0 and nb + npr < 2**31 - 2
            and out_capacity < 2**31 - 2):
        _warn_xla_fallback(interpret, f"sides of {nb} + {npr} rows "
                           f"into {out_capacity} output slots")
        return False, False
    dts = (
        [build.columns[k].dtype for k in keys]
        + [build.columns[nm].dtype for nm in b1d]
        + [probe.columns[nm].dtype for nm in p1d]
    )
    bad = sorted({str(dt) for dt in dts if not _u64_lane_ok(dt)})
    if bad:
        _warn_xla_fallback(interpret, f"column dtypes {bad}")
        return False, False
    return True, interpret


def _warn_xla_fallback(interpret: bool, why: str) -> None:
    """The kernel pipeline was asked for but the data rules it out:
    say so (once per reason), except under the test interpreter."""
    if not interpret:
        warnings.warn(f"join runs the XLA pipeline, not the Pallas "
                      f"kernels: {why}", stacklevel=3)




def _join_kernel_path(build, probe, keys, b1d, b2d, p1d, p2d,
                      build_payload, probe_payload, out_capacity,
                      interpret, cfg, kernel_prefix="") -> JoinResult:
    """The TPU pipeline: ONE value-carrying merged sort, the fused
    scan kernel (ops/scan_pallas.py — including the MATCHED-build
    machinery), two streaming compactions (ops/compact_pallas.py: the
    run-record block and the matched-dense build pack), and the expand
    kernel with its two-window build materialization
    (ops/expand_pallas.py). Ranks are matched-build ranks (lo_m), so
    the window bound holds by construction — unmatched build keys
    never enter the pack; build_windows_ok + lax.cond stay as
    belt-and-braces (the fallback is also exact over the pack)."""
    from distributed_join_tpu.ops.compact_pallas import stream_compact
    from distributed_join_tpu.ops.compact_planes import (
        plane_stream_compact,
    )
    from distributed_join_tpu.ops.expand_pallas import (
        build_windows_ok,
        expand_gather,
    )
    from distributed_join_tpu.ops.scan_pallas import join_scans

    # log-shift plane compaction (default): measured 54 vs 101 ms for
    # the 20M->7.5M 4-lane record block on v5e
    # (scripts/profile_r3_compact.py); cfg.compact='mxu' restores the
    # one-hot matmul kernel. Config is resolved at TRACE time.
    if cfg.use_plane_compact(interpret):
        stream_compact = plane_stream_compact  # noqa: F811
        compact_name = kernel_prefix + "compact_planes"
    else:
        compact_name = kernel_prefix + "compact_mxu"

    nb, npr = build.capacity, probe.capacity
    n = nb + npr
    bvalid, pvalid = build.valid, probe.valid

    with jax.named_scope("prepare"):
        # merged sort: keys + tag as sort keys; BOTH sides' 1-D payloads
        # (and 2-D columns' row indices) ride as values — value operands
        # are nearly free, and this subsumes the XLA path's separate
        # build-side sort.
        m_ops = []
        for k in keys:
            b, p = build.columns[k], probe.columns[k]
            sentinel = _dtype_sentinel_max(b.dtype)
            m_ops.append(jnp.concatenate([
                jnp.where(bvalid, b, sentinel),
                jnp.where(pvalid, p, sentinel),
            ]))
        tag = jnp.concatenate([
            jnp.where(bvalid, jnp.int8(0), jnp.int8(2)),
            jnp.where(pvalid, jnp.int8(1), jnp.int8(2)),
        ])
        # Value lanes: a build row never needs a probe value and vice
        # versa, so same-dtype (probe, build) column PAIRS share one
        # physical sort lane (build rows carry the build value, probe rows
        # the probe value) — each extra i64 lane costs ~6 ms on a 20M-row
        # sort. The 2-D columns' per-side row indices are such a pair by
        # construction.
        pcols = [(nm, probe.columns[nm]) for nm in p1d]
        bcols = [(nm, build.columns[nm]) for nm in b1d]
        if p2d:
            pcols.append(("__prow", jnp.arange(npr, dtype=jnp.int32)))
        if b2d:
            bcols.append(("__browidx", jnp.arange(nb, dtype=jnp.int32)))
        m_vals = []
        mv_names = []   # [(probe_name | None, build_name | None)]
        bq = list(bcols)
        for pnm, pc in pcols:
            mate = next(
                (t for t in bq if t[1].dtype == pc.dtype), None
            )
            if mate is not None:
                bq.remove(mate)
                bnm, bc = mate
                m_vals.append(jnp.concatenate([bc, pc]))
                mv_names.append((pnm, bnm))
            else:
                m_vals.append(jnp.concatenate(
                    [jnp.zeros((nb,), dtype=pc.dtype), pc]
                ))
                mv_names.append((pnm, None))
        for bnm, bc in bq:
            m_vals.append(jnp.concatenate(
                [bc, jnp.zeros((npr,), dtype=bc.dtype)]
            ))
            mv_names.append((None, bnm))

    with jax.named_scope("sort"):
        sorted_m = lax.sort(
            (*m_ops, tag, *m_vals), num_keys=len(keys) + 1
        )
        skeys = sorted_m[:len(keys)]
        stag = sorted_m[len(keys)]
        svals = {}
        for (pnm, bnm), c in zip(mv_names, sorted_m[len(keys) + 1:]):
            if pnm is not None:
                svals[("p", pnm)] = c
            if bnm is not None:
                svals[("b", bnm)] = c

    with jax.named_scope("scan"):
        iota = jnp.arange(n, dtype=jnp.int32)
        changed = jnp.zeros((n,), dtype=bool)
        for sk in skeys:
            prev = jnp.concatenate([sk[:1], sk[:-1]])
            changed = changed | (sk != prev)
        first = changed | (iota == 0)

        sc = join_scans(stag, first, interpret=interpret,
                        name_prefix=kernel_prefix)
        cnt = sc["cnt"]
        # start_out is int32: past 2**31 total matches it wraps, S is no
        # longer sorted, and searchsorted/build_windows_ok below operate on
        # garbage. Under x64 that run is covered by the overflow contract —
        # the int64 `total` still fires `overflow`, flagging every payload
        # row untrustworthy (with x64 disabled the sum itself wraps; the
        # documented caveat warned about in sort_merge_inner_join) — and
        # cannot read out of bounds either way: the expand kernel's window
        # offsets are clipped before every DMA.
        total = jnp.sum(cnt.astype(jnp.int64))
        rec_total = sc["rec_pos"][-1] + 1
        is_probe = stag == jnp.int8(1)
        is_rec = is_probe & (cnt > 0)

    with jax.named_scope("compact"):
        # record compaction: one record per matching probe, in start_out
        # order (rec_pos is monotone over merged order, which IS start_out
        # order), carrying S, the probe-side output values, and lo_m.
        rec_lanes = {"__S": _to_u64_lane(sc["start_out"])}
        for i, sk in enumerate(skeys):
            rec_lanes[f"__key{i}"] = _to_u64_lane(sk)
        for nm in p1d:
            rec_lanes[nm] = _to_u64_lane(svals[("p", nm)])
        rec_lanes["__lo"] = _to_u64_lane(sc["lo_m"])
        if p2d:
            rec_lanes["__prow"] = _to_u64_lane(svals[("p", "__prow")])
        rec_names = list(rec_lanes)
        compacted = dict(zip(rec_names, stream_compact(
            is_rec, sc["rec_pos"], [rec_lanes[nm] for nm in rec_names],
            out_capacity, interpret=interpret, name=compact_name,
        )))
        kept = jnp.minimum(rec_total, jnp.int32(out_capacity))
        j = jnp.arange(out_capacity, dtype=jnp.int32)
        S = jnp.where(j < kept, compacted["__S"].astype(jnp.int32),
                      jnp.int32(_I32_MAX))
        # Slots past the survivor count are UNDEFINED in stream_compact's
        # output; the window checker and the kernel's w2 lookups read
        # lo[r0+1] across that boundary, so zero them like the sort-based
        # path's _prefix padding did (garbage there would spuriously fail
        # build_windows_ok and force the slow fallback).
        lo_rec = jnp.where(
            j < kept, compacted["__lo"].astype(jnp.int32), 0
        )
        compacted["__lo"] = _to_u64_lane(lo_rec)

        # matched-build pack: dense, key-ordered, gap-free by construction.
        pack_names = list(b1d) + (["__browidx"] if b2d else [])
        pack_lanes = [
            _to_u64_lane(svals[("b", nm)]) for nm in pack_names
        ]
        matched = sc["matched"] != 0
        pack = stream_compact(
            matched, sc["mb_pos"], pack_lanes, nb, interpret=interpret,
            name=compact_name,
        ) if pack_names else []

    with jax.named_scope("expand"):
        rec_value_names = [
            nm for nm in rec_names if nm not in ("__S", "__lo")
        ]
        cols_list = [compacted[nm] for nm in rec_value_names]

        if pack_names:
            def _kernel(_):
                return expand_gather(
                    S, cols_list, out_capacity, total, block=cfg.block,
                    interpret=interpret, lo=lo_rec, build_cols=pack,
                    window=cfg.window, name_prefix=kernel_prefix,
                )

            def _fallback(_):
                outs2, sb2 = expand_gather(
                    S, cols_list + [compacted["__lo"]], out_capacity,
                    total, block=cfg.block, interpret=interpret,
                    name_prefix=kernel_prefix,
                )
                rank2 = outs2[-1].astype(jnp.int32) + (j - sb2)
                safe = jnp.clip(rank2, 0, max(nb - 1, 0))
                bouts2 = _chunked_rank_gather(pack, safe)
                return outs2[:-1], sb2, rank2, bouts2

            rec_outs, start_b, _rank, build_outs = lax.cond(
                build_windows_ok(S, lo_rec, out_capacity,
                                 block=cfg.block, window=cfg.window),
                _kernel, _fallback, None,
            )
            build_vals_u64 = dict(zip(pack_names, build_outs))
        else:
            rec_outs, start_b = expand_gather(
                S, cols_list, out_capacity, total, block=cfg.block,
                interpret=interpret, name_prefix=kernel_prefix,
            )
            build_vals_u64 = {}
        rec_vals_u64 = dict(zip(rec_value_names, rec_outs))

        out_cols = {}
        for i, k in enumerate(keys):
            out_cols[k] = _from_u64_lane(
                rec_vals_u64[f"__key{i}"], build.columns[k].dtype
            )
        for nm in b1d:
            out_cols[nm] = _from_u64_lane(
                build_vals_u64[nm], build.columns[nm].dtype
            )
        if b2d:
            bidx = _from_u64_lane(
                build_vals_u64["__browidx"], jnp.int32
            )
            bidx = jnp.clip(bidx, 0, max(nb - 1, 0))
            for nm in b2d:
                out_cols[nm] = build.columns[nm][bidx]
        for nm in p1d:
            out_cols[nm] = _from_u64_lane(
                rec_vals_u64[nm], probe.columns[nm].dtype
            )
        if p2d:
            # __prow is the PER-SIDE probe row index (it shares a lane
            # with __browidx), so no -nb rebase.
            prow = _from_u64_lane(rec_vals_u64["__prow"], jnp.int32)
            p = jnp.clip(prow, 0, max(npr - 1, 0))
            for nm in p2d:
                out_cols[nm] = probe.columns[nm][p]
        out_cols = {
            nm: out_cols[nm]
            for nm in [*keys, *build_payload, *probe_payload]
        }
        return JoinResult(
            Table(out_cols, j < total),
            total=total,
            overflow=total > out_capacity,
        )


def sort_merge_inner_join(
    build: Table,
    probe: Table,
    key,
    out_capacity: int,
    build_payload: Optional[Sequence[str]] = None,
    probe_payload: Optional[Sequence[str]] = None,
    kernel_config: Optional["KernelConfig"] = None,
    join_type: str = "inner",
    _internal: Sequence[str] = (),
    kernel_prefix: str = "",
) -> JoinResult:
    """Join ``build`` and ``probe`` on equality of ``key`` — a
    column name or a sequence of names (composite key). A key column
    may be a fixed-width 2-D uint8 byte column (utils/strings.py):
    it joins on lexicographic equality of the zero-padded bytes via
    packed big-endian uint64 words, the same composite-key machinery
    as scalar keys (SURVEY.md §2 string children; §7 step 7).

    ``join_type`` selects the variant (docs/QUERY.md): ``inner``
    (default — the seed program, unchanged), ``left`` (every valid
    probe row survives; unmatched rows carry zeroed build payloads and
    a False ``build#valid``), ``right`` (every valid build row
    survives; unmatched rows carry zeroed probe payloads and a False
    ``probe#valid``), ``full_outer`` (both), ``semi`` (probe rows with
    at least one build match, once each), ``anti`` (probe rows with no
    build match). Semi/anti emit keys + probe payloads only — an
    explicit non-empty ``build_payload`` is refused. All variants are
    the SAME merged-domain sort/scan/compact/expand with a different
    per-position emission count; unmatched builds are already visible
    at merge time as key runs containing zero probe rows.

    Output columns: the key column(s), then build payloads, then probe
    payloads, then any validity columns. Payload names must not
    collide.

    ``kernel_config`` (ops/kernel_config.KernelConfig) selects the
    Pallas kernel paths; None reads the DJTPU_* env fallbacks.
    ``kernel_prefix`` goes before the names of the Pallas kernels it
    runs (``join_scan_r``, ``compact_planes``, ``expand_b8``, ...), so
    that a profile tells this join's kernels from another's.
    """
    if join_type not in JOIN_TYPES:
        raise ValueError(
            f"unknown join_type {join_type!r}; expected one of "
            f"{JOIN_TYPES}"
        )
    cfg = resolve_kernel_config(kernel_config)
    keys = [key] if isinstance(key, str) else list(key)
    # String keys: pack 2-D byte key columns into uint64 word columns
    # and recurse with the scalar composite key; the byte column is
    # reconstructed exactly from the output words. This runs BEFORE
    # payload defaulting: the companion "<key>#len" columns exist on
    # both sides and the probe's copy wins (keys-from-probe).
    from distributed_join_tpu.utils.strings import check_key_ndim

    check_key_ndim(build, probe, keys)
    if any(build.columns[k].ndim == 2 for k in keys):
        from distributed_join_tpu.utils.strings import (
            prepare_string_key_join,
            rebuild_string_keys,
        )

        b2, p2, keys2, bp, pp, spec = prepare_string_key_join(
            build, probe, keys, build_payload, probe_payload
        )
        allowed = tuple(
            nm for _, wns, _ in spec for nm in wns
        )
        res = sort_merge_inner_join(
            b2, p2, keys2, out_capacity,
            build_payload=bp, probe_payload=pp,
            kernel_config=kernel_config, join_type=join_type,
            _internal=allowed, kernel_prefix=kernel_prefix,
        )
        out = patch_string_lengths(
            rebuild_string_keys(res.table, spec, keys), keys, join_type
        )
        return JoinResult(out, total=res.total, overflow=res.overflow)

    if join_type in ("semi", "anti"):
        if build_payload:
            raise ValueError(
                f"join_type={join_type!r} emits probe rows only; an "
                "explicit build_payload cannot be honored — drop it "
                "or use a left join with the build#valid column"
            )
        build_payload = []
    if build_payload is None:
        build_payload = [n for n in build.column_names if n not in keys]
    if probe_payload is None:
        probe_payload = [n for n in probe.column_names if n not in keys]
    clash = set(build_payload) & set(probe_payload)
    if clash:
        raise ValueError(f"payload name collision: {sorted(clash)}")
    if join_type in OUTER_TYPES:
        taken = set(keys) | set(build_payload) | set(probe_payload)
        emitted = [
            nm for nm in (
                (BUILD_VALID,) if join_type == "left"
                else (PROBE_VALID,) if join_type == "right"
                else (BUILD_VALID, PROBE_VALID)
            ) if nm in taken
        ]
        if emitted:
            raise ValueError(
                f"column(s) {emitted} collide with the outer-join "
                "validity columns"
            )
    # Internal record lanes (__S, __key{i}, __lo, __prow, __browidx)
    # share one dict namespace with user column names; a payload named
    # '__S' would silently overwrite a geometry lane and corrupt the
    # join output. Only the EXACT packed word names injected by the
    # string-key branch above (threaded through ``_internal``) are
    # exempt — any other dunder, including unused __sk-pattern names,
    # is rejected (split_string_keys also refuses to overwrite one).
    reserved = [
        nm for nm in (*keys, *build_payload, *probe_payload)
        if nm.startswith("__") and nm not in _internal
    ]
    if reserved:
        raise ValueError(
            "column names starting with '__' are reserved for "
            f"internal join lanes: {sorted(set(reserved))}"
        )

    for k in keys:
        bdt = build.columns[k].dtype
        pdt = probe.columns[k].dtype
        if bdt != pdt:
            # Sort order is dtype-dependent; a silent mismatch would
            # route equal keys apart and drop matches.
            raise TypeError(
                f"key dtype mismatch: build {bdt} vs probe {pdt}"
            )

    b1d = [n for n in build_payload if build.columns[n].ndim == 1]
    b2d = [n for n in build_payload if build.columns[n].ndim > 1]
    p1d = [n for n in probe_payload if probe.columns[n].ndim == 1]
    p2d = [n for n in probe_payload if probe.columns[n].ndim > 1]

    nb = build.capacity
    npr = probe.capacity
    n = nb + npr
    bvalid, pvalid = build.valid, probe.valid

    if not jax.config.x64_enabled:
        warnings.warn(
            "JAX x64 is disabled: join match totals are int32 and the "
            "overflow flag is unreliable past 2**31 matches per shard",
            stacklevel=2,
        )

    use_kernel, interpret = _kernel_path_ok(
        build, probe, keys, b1d, p1d, nb, npr, out_capacity, cfg
    )
    if join_type != "inner":
        # The fused kernel pipeline is inner-only (its scans drop
        # zero-count probes and unmatched builds by construction); the
        # typed variants run the XLA formulation below, whose emission
        # count generalizes per position.
        use_kernel = False
    if use_kernel:
        return _join_kernel_path(
            build, probe, keys, b1d, b2d, p1d, p2d, build_payload,
            probe_payload, out_capacity, interpret, cfg, kernel_prefix,
        )

    with jax.named_scope("prepare"):
        # -- 1. build-side sort: keys + tag + 1-D payloads (+ row index for
        #    2-D columns). Valid rows compact to a key-sorted prefix whose
        #    order agrees with the merge ranks of step 3: both sort valid
        #    builds by (key, original position).
        b_ops = []
        for k in keys:
            c = build.columns[k]
            b_ops.append(jnp.where(bvalid, c, _dtype_sentinel_max(c.dtype)))
        btag = jnp.where(bvalid, jnp.int8(0), jnp.int8(1))
        b_vals = [build.columns[nm] for nm in b1d]
        if b2d:
            b_vals.append(jnp.arange(nb, dtype=jnp.int32))

    with jax.named_scope("sort"):
        # No stability needed anywhere: equal-key valid builds are
        # interchangeable — a probe's build-rank window [lo, lo+cnt) covers
        # the ENTIRE equal-key run, so any within-key order yields the same
        # output multiset (lo = #builds with smaller keys in both sorts).
        sorted_b = lax.sort(
            (*b_ops, btag, *b_vals), num_keys=len(keys) + 1
        )
        sb_payload = dict(zip(b1d, sorted_b[len(keys) + 1:]))
        sb_rowidx = sorted_b[-1] if b2d else None

    with jax.named_scope("prepare"):
        # -- 2. merged sort: keys + side tag; probe 1-D values (incl. the
        #    output copy of each key column, which IS the key operand) ride.
        #    Invalid rows are masked to the key dtype's max so they land in
        #    the final runs; a real key equal to the sentinel still joins
        #    exactly — the tag, not the key value, drives all counting.
        m_ops = []
        for k in keys:
            b, p = build.columns[k], probe.columns[k]
            sentinel = _dtype_sentinel_max(b.dtype)
            m_ops.append(jnp.concatenate([
                jnp.where(bvalid, b, sentinel),
                jnp.where(pvalid, p, sentinel),
            ]))
        tag = jnp.concatenate([
            jnp.where(bvalid, jnp.int8(0), jnp.int8(2)),
            jnp.where(pvalid, jnp.int8(1), jnp.int8(2)),
        ])
        m_vals = []
        for nm in p1d:
            c = probe.columns[nm]
            m_vals.append(jnp.concatenate(
                [jnp.zeros((nb,), dtype=c.dtype), c]
            ))
        if p2d:
            m_vals.append(jnp.arange(n, dtype=jnp.int32))  # merged row index

    with jax.named_scope("sort"):
        sorted_m = lax.sort(
            (*m_ops, tag, *m_vals), num_keys=len(keys) + 1
        )
        skeys = sorted_m[:len(keys)]
        stag = sorted_m[len(keys)]
        sp_payload = dict(zip(p1d, sorted_m[len(keys) + 1:]))
        sp_rowidx = sorted_m[-1] if p2d else None

    with jax.named_scope("scan"):
        # -- 3. runs and counts via scans (all int32 lanes; every per-run
        #    quantity is broadcast down its run with a cummax of values that
        #    are globally non-decreasing).
        is_build = stag == jnp.int8(0)
        is_probe = stag == jnp.int8(1)
        f_incl = jnp.cumsum(is_build.astype(jnp.int32))   # valid builds <= pos
        b_before = f_incl - is_build.astype(jnp.int32)    # valid builds <  pos
        iota = jnp.arange(n, dtype=jnp.int32)
        changed = jnp.zeros((n,), dtype=bool)
        for sk in skeys:
            prev = jnp.concatenate([sk[:1], sk[:-1]])
            changed = changed | (sk != prev)
        first = changed | (iota == 0)
        # Build rank of each run's first element, broadcast down the run:
        # b_before is non-decreasing, so a cummax of its run-start samples
        # holds each run's start value until the next run begins.
        lo = lax.cummax(jnp.where(first, b_before, 0))
        # Builds sort before probes of an equal key (tag order), so for a
        # probe at position i every matching build lies in [run_start, i)
        # and cnt = b_before[i] - lo[i].
        cnt = jnp.where(is_probe, b_before - lo, 0)

        #    `total` must be int64: duplicate-heavy joins (hot keys on both
        #    sides) can exceed 2^31 matches per shard, and an int32 wrap
        #    would turn it negative and defeat the overflow contract. The
        #    cumsum itself stays int32 — a 64-bit cumsum lowers to an
        #    emulated-u32-pair reduce-window that blows TPU scoped VMEM at
        #    10M+ rows (verified on v5e). If csum wraps, total >= 2^31 >>
        #    out_capacity, so overflow fires and the (garbage) payload rows
        #    are already flagged untrustworthy. (The x64 warning for this
        #    contract is issued once by sort_merge_inner_join.)
        if join_type == "inner":
            csum = jnp.cumsum(cnt)
            total = jnp.sum(cnt.astype(jnp.int64))
            start_out = csum - cnt        # first output slot of each run
            is_rec = is_probe & (cnt > 0)
        else:
            # Typed emission (docs/QUERY.md): each merged position emits
            # ``emit`` output rows instead of ``cnt``. Probe rows emit
            # their match count (padded to 1 for left/full_outer, collapsed
            # to a presence bit for semi, an absence bit for anti); for
            # right/full_outer an UNMATCHED build row — a key run holding
            # zero probe rows — emits itself once with the probe payloads
            # NULL-zeroed (the merged sort already planted zeros there).
            if join_type in ("right", "full_outer"):
                p_incl = jnp.cumsum(is_probe.astype(jnp.int32))
                # Probes before the run start, broadcast down the run
                # (non-decreasing, so cummax of run-start samples holds).
                p_before = lax.cummax(jnp.where(
                    first, p_incl - is_probe.astype(jnp.int32), 0
                ))
                # Probes THROUGH the run end, broadcast backwards: p_incl
                # sampled at run-last positions is non-decreasing, so a
                # reversed cummin over a max-filled lane carries each
                # run's last sample back to its start.
                run_last = jnp.concatenate(
                    [first[1:], jnp.ones((1,), dtype=bool)]
                )
                p_thru = jnp.flip(lax.cummin(jnp.flip(
                    jnp.where(run_last, p_incl, _I32_MAX)
                )))
                b_unmatched = is_build & ((p_thru - p_before) == 0)
            if join_type == "left":
                emit = jnp.where(is_probe, jnp.maximum(cnt, 1), 0)
            elif join_type == "semi":
                emit = (is_probe & (cnt > 0)).astype(jnp.int32)
            elif join_type == "anti":
                emit = (is_probe & (cnt == 0)).astype(jnp.int32)
            elif join_type == "right":
                emit = cnt + b_unmatched.astype(jnp.int32)
            else:  # full_outer
                emit = (jnp.where(is_probe, jnp.maximum(cnt, 1), 0)
                        + b_unmatched.astype(jnp.int32))
            csum = jnp.cumsum(emit)
            total = jnp.sum(emit.astype(jnp.int64))
            start_out = csum - emit
            is_rec = emit > 0

    with jax.named_scope("compact"):
        # -- 4. run-record compaction sort: one record per probe row with
        #    matches, keyed by its first output slot (strictly increasing
        #    over such probes, so keys are unique). EVERYTHING an output
        #    slot will need rides as value operands: the probe's key and
        #    payload values, lo, start_out, the 2-D row index. This moves
        #    the expansion out of the 20M merged domain: the scatter below
        #    has an out_capacity operand instead of n, and the probe-side
        #    output gather reads the compact records directly. (The
        #    scatter-only expansion this replaces measured 486 ms of a
        #    1050 ms join at 10M x 10M — sorts move values almost for free,
        #    scatters pay per operand element.)
        rkey = jnp.where(is_rec, start_out, _I32_MAX)
        kdt = skeys[0].dtype
        geom_dt = kdt if _holds_i32_exactly(kdt) else jnp.int32
        rec_cols = {f"__key{i}": sk for i, sk in enumerate(skeys)}
        for nm in p1d:
            rec_cols[nm] = sp_payload[nm]
        if join_type == "inner":
            rec_cols["__lo"] = lo.astype(geom_dt)
        else:
            # An unmatched-build record (right/full_outer) gathers its OWN
            # payload: its rank in the step-1 sorted valid prefix is
            # b_before. Side-presence flags ride as int8 lanes so each
            # output row knows which side carries real values.
            rec_cols["__lo"] = jnp.where(
                is_build, b_before, lo
            ).astype(geom_dt)
            rec_cols["__bm"] = jnp.where(
                is_build, jnp.int8(1), (cnt > 0).astype(jnp.int8)
            )
            rec_cols["__pm"] = is_probe.astype(jnp.int8)
        if p2d:
            rec_cols["__prow"] = sp_rowidx
        rec_names = list(rec_cols)
        sorted_r = lax.sort(
            (rkey, *[rec_cols[nm] for nm in rec_names]), num_keys=1
        )

        #    Records beyond out_capacity could only start at overflow slots.
        def _prefix(a, fill):
            if n >= out_capacity:
                return a[:out_capacity]
            pad = jnp.full((out_capacity - n,), fill, dtype=a.dtype)
            return jnp.concatenate([a, pad])

        S = _prefix(sorted_r[0], _I32_MAX)
        recs = {
            nm: _prefix(c, jnp.zeros((), c.dtype))
            for nm, c in zip(rec_names, sorted_r[1:])
        }

    with jax.named_scope("expand"):
        # -- 5. expansion: ONE small scatter + cummax + packed row gathers
        #    (XLA primitives), or the Pallas record-expand kernel where it
        #    applies (see _expand_records); the build side is an XLA packed
        #    row gather at the derived rank. The fused kernel pipeline with
        #    its in-kernel build materialization lives in
        #    _join_kernel_path; this path serves CPU, f64 columns, and
        #    blocks past the f32-exact rank range.
        j = jnp.arange(out_capacity, dtype=jnp.int32)
        out_vals, start_b = _expand_records(S, recs, out_capacity, j,
                                            total, cfg, kernel_prefix)
        bm = pm = None
        if join_type != "inner":
            bm = out_vals.pop("__bm") != 0
            pm = out_vals.pop("__pm") != 0
        lo_b = out_vals.pop("__lo").astype(jnp.int32)
        build_rank = lo_b + (j - start_b)
        safe_rank = jnp.clip(build_rank, 0, max(nb - 1, 0))
        build_vals = _grouped_row_gather(sb_payload, safe_rank)
        if b2d:
            build_vals["__browidx"] = sb_rowidx[safe_rank]

        out_cols = {}
        for i, k in enumerate(keys):
            out_cols[k] = out_vals.pop(f"__key{i}")
        for nm in b1d:
            # Unmatched probe rows (left/full_outer) derive a garbage rank
            # (lo of an unrelated run) — NULL-zero their build values.
            out_cols[nm] = (build_vals[nm] if bm is None else jnp.where(
                bm, build_vals[nm], jnp.zeros_like(build_vals[nm])))
        if b2d:
            bidx = build_vals["__browidx"]
            for nm in b2d:
                rows = build.columns[nm][bidx]
                out_cols[nm] = (rows if bm is None else jnp.where(
                    bm[:, None], rows, jnp.zeros_like(rows)))
        for nm in p1d:
            out_cols[nm] = out_vals.pop(nm)
        if p2d:
            p = jnp.clip(out_vals.pop("__prow") - nb, 0, max(npr - 1, 0))
            for nm in p2d:
                rows = probe.columns[nm][p]
                out_cols[nm] = (rows if pm is None else jnp.where(
                    pm[:, None], rows, jnp.zeros_like(rows)))
        # Column order: keys, build payloads, probe payloads, validity.
        order = [*keys, *build_payload, *probe_payload]
        if join_type in ("left", "full_outer"):
            out_cols[BUILD_VALID] = bm
            order.append(BUILD_VALID)
        if join_type in ("right", "full_outer"):
            out_cols[PROBE_VALID] = pm
            order.append(PROBE_VALID)
        out_cols = {nm: out_cols[nm] for nm in order}

        out_valid = j < total
        return JoinResult(
            Table(out_cols, out_valid),
            total=total,
            overflow=total > out_capacity,
        )
