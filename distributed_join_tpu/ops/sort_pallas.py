"""Pallas alternating-orientation merge sort (EXPERIMENTAL — not wired
into the production join; committed as the measured falsification
artifact for the round-2 radix-sort estimate, docs/ROOFLINE.md §6:
it lands at ~168 ms vs lax.sort's 166 ms at 20M, parity not victory,
so ops/join.py keeps lax.sort).

The reference's local join delegates sorting/hashing to cuDF GPU
kernels (SURVEY.md §2 "Local join step"); this framework's equivalent
hot primitive is the 20M-row value-carrying merged sort at the heart
of ops/join.py. Round-3 measurements (scripts/profile_r3_sort.py,
v5e) put ``lax.sort`` at 166 ms for the bench operand set
(i64 key + i8 tag + i64 value at 20M rows) — 44% of the whole join —
while the SAME data sorts in 24-38 ms when split into independent
runs ((8192, 2048): 24 ms; (512, 32768): 38 ms). XLA's flat sort pays
~100 HBM round-trip equivalents; batched runs + a bandwidth-optimal
merge tree does the same job in ~10.

Design (everything is u32 "planes"):

- Records are decomposed into 32-bit planes: order-preserving planes
  for the compare keys (sign-flip for signed ints, monotone bit
  transform for f32, hi/lo split for 64-bit), bit-preserving planes
  for the values. All kernel data movement is plain u32 vector ops —
  no bf16 chunking, no matmuls, exact by construction.
- Run sort: the padded array is reshaped to (runs, T) and run-sorted
  by ONE batched ``lax.sort`` (is_stable=False) — per-run sorting is
  where XLA's sort is already fast.
- Alternating orientation: odd-index segments are stored DESCENDING,
  so every merge pair [A asc, B desc] is a contiguous bitonic
  sequence and the kernel never materializes a reversal (Mosaic has
  no ``rev`` lowering — probed on v5e).
- Merge levels: each level halves the segment count. Output tiles of
  T elements are independent: a merge-path diagonal search (26-step
  vectorized binary search in XLA, ~n/T tiny queries per level) finds
  how many A-elements land in each tile; the Pallas kernel DMAs the
  A- and B-windows at element-granular offsets (128-aligned DMA + a
  3-roll in-VMEM flat shift), builds the bitonic tile
  [A-part asc | B-part desc] with one select, and sorts it with
  log2(T) XOR-partner compare-exchange stages: row-space stages
  (stride >= 128) as 4-D reshape min/max, lane-space stages
  (stride < 128) as paired ``pltpu.roll`` +- s with a lane-bit
  select. Direction per tile follows the segment parity at the next
  level.
- Ceil merge tree: a segment whose sibling is virtual passes through
  a level untouched (its tiles become q=0 "copy" tiles — the same
  kernel, zero special cases); its required orientation is deferred
  to the level where it first merges. The physical buffer never
  exceeds n_pad + 2T slack (no power-of-two blowup).

Correctness does NOT depend on data distribution: bitonic networks
are data-independent, and ties need no stability (ops/join.py's
within-key order contract — equal (key, tag) rows are
interchangeable).
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax

import jax.numpy as jnp
import numpy as np
from jax import lax

_SENT = jnp.uint32(0xFFFFFFFF)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def split_u64(c: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(hi, lo) u32 planes of a uint64-convertible column."""
    u = c.astype(jnp.uint64)
    return (u >> jnp.uint64(32)).astype(jnp.uint32), u.astype(jnp.uint32)


def merge_u64(hi: jax.Array, lo: jax.Array) -> jax.Array:
    return (hi.astype(jnp.uint64) << jnp.uint64(32)) | \
        lo.astype(jnp.uint64)


# ---------------------------------------------------------------------------
# dtype <-> u32 plane codecs


def key_to_planes(c: jax.Array) -> list[jax.Array]:
    """Order-preserving u32 planes (most-significant first): unsigned
    lexicographic comparison of the planes == the dtype's ordering."""
    dt = c.dtype
    if dt == jnp.uint32:
        return [c]
    if dt == jnp.int32:
        return [(c.astype(jnp.uint32)) ^ jnp.uint32(0x80000000)]
    if jnp.issubdtype(dt, jnp.integer) and jnp.iinfo(dt).bits <= 16:
        lo = jnp.iinfo(dt).min
        return [(c.astype(jnp.int32) - lo).astype(jnp.uint32)]
    if dt == jnp.uint64:
        return [(c >> jnp.uint64(32)).astype(jnp.uint32),
                c.astype(jnp.uint32)]
    if dt == jnp.int64:
        u = c.astype(jnp.uint64) ^ (jnp.uint64(1) << jnp.uint64(63))
        return [(u >> jnp.uint64(32)).astype(jnp.uint32),
                u.astype(jnp.uint32)]
    if dt == jnp.float32:
        b = lax.bitcast_convert_type(c, jnp.uint32)
        # monotone IEEE-754 transform: negatives reversed, sign flipped
        return [jnp.where(b >> 31 != 0, ~b, b | jnp.uint32(0x80000000))]
    raise TypeError(f"unsupported key dtype {dt}")


def planes_to_key(planes: list[jax.Array], dt) -> jax.Array:
    if dt == jnp.uint32:
        return planes[0]
    if dt == jnp.int32:
        return (planes[0] ^ jnp.uint32(0x80000000)).astype(jnp.int32)
    if jnp.issubdtype(dt, jnp.integer) and jnp.iinfo(dt).bits <= 16:
        lo = jnp.iinfo(dt).min
        return (planes[0].astype(jnp.int32) + lo).astype(dt)
    if dt == jnp.uint64:
        return (planes[0].astype(jnp.uint64) << jnp.uint64(32)) | \
            planes[1].astype(jnp.uint64)
    if dt == jnp.int64:
        u = (planes[0].astype(jnp.uint64) << jnp.uint64(32)) | \
            planes[1].astype(jnp.uint64)
        return (u ^ (jnp.uint64(1) << jnp.uint64(63))).astype(jnp.int64)
    if dt == jnp.float32:
        b = planes[0]
        b = jnp.where(
            b >> 31 != 0, b & jnp.uint32(0x7FFFFFFF), ~b
        )
        return lax.bitcast_convert_type(b, jnp.float32)
    raise TypeError(dt)


def val_to_planes(c: jax.Array) -> list[jax.Array]:
    """Bit-preserving u32 planes (values only ride, never compared)."""
    dt = c.dtype
    if dt in (jnp.int64, jnp.uint64):
        u = c.astype(jnp.uint64)
        return [(u >> jnp.uint64(32)).astype(jnp.uint32),
                u.astype(jnp.uint32)]
    if dt == jnp.float32:
        return [lax.bitcast_convert_type(c, jnp.uint32)]
    if jnp.issubdtype(dt, jnp.integer) and jnp.iinfo(dt).bits <= 32:
        bits = jnp.iinfo(dt).bits
        unsigned = jnp.dtype(f"uint{bits}")
        return [c.astype(unsigned).astype(jnp.uint32)]
    raise TypeError(f"unsupported value dtype {dt}")


def planes_to_val(planes: list[jax.Array], dt) -> jax.Array:
    if dt in (jnp.int64, jnp.uint64):
        u = (planes[0].astype(jnp.uint64) << jnp.uint64(32)) | \
            planes[1].astype(jnp.uint64)
        return u.astype(dt)
    if dt == jnp.float32:
        return lax.bitcast_convert_type(planes[0], jnp.float32)
    if jnp.issubdtype(dt, jnp.integer) and jnp.iinfo(dt).bits <= 32:
        bits = jnp.iinfo(dt).bits
        unsigned = jnp.dtype(f"uint{bits}")
        return planes[0].astype(unsigned).astype(dt)
    raise TypeError(dt)


def planes_ok(dt, is_key: bool) -> bool:
    try:
        (key_to_planes if is_key else val_to_planes)(
            jnp.zeros((1,), dt)
        )
        return True
    except TypeError:
        return False
    except Exception:
        # abstract tracing never runs device code; any other failure
        # means unsupported
        return False


# ---------------------------------------------------------------------------
# ceil merge tree orientation (0 = ascending, 1 = descending)


def _tree_counts(nruns: int) -> list[int]:
    counts = [nruns]
    while counts[-1] > 1:
        counts.append((counts[-1] + 1) // 2)
    return counts


def _orient(j: int, level: int, counts: list[int]) -> int:
    # A segment whose sibling is virtual keeps its orientation until
    # the level where it first merges; orientation there is its index
    # parity (even = asc = the "A" side).
    while level < len(counts) - 1:
        if (j ^ 1) < counts[level]:
            return j & 1
        j >>= 1
        level += 1
    return 0


# ---------------------------------------------------------------------------
# merge-path diagonal search (XLA; tiny query counts)


def _diag_search(stacked, nk, qa0, qla, qb0, qlb, qd,
                 iters: int = 32):
    """For each query: #A-elements among the first qd outputs of
    merge(A asc, B desc-stored), ties taking A first. Fixed-step
    binary search; ONE fused gather per step (per-gather-op overhead
    of ~tens of us dominated a per-plane formulation — measured
    11.5 ms/level before fusing, scripts/profile_r3_psort_parts.py).
    ``stacked``: (P, size) u32 with the nk key planes first."""
    size = stacked.shape[1]
    cat = stacked[:nk].reshape(-1)
    nq = qd.shape[0]
    lo = jnp.maximum(jnp.int32(0), qd - qlb)
    hi = jnp.minimum(qd, qla)

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        ai = jnp.clip(qa0 + mid, 0, size - 1)
        bi_asc = qd - 1 - mid
        b_hi = bi_asc >= qlb      # virtual +inf: take more A
        b_lo = bi_asc < 0         # virtual -inf: stop
        bp = jnp.clip(qb0 + qlb - 1 - bi_asc, 0, size - 1)
        # one gather for all planes x both sides
        plane_off = (
            jnp.arange(nk, dtype=ai.dtype)[:, None]
            * jnp.asarray(size, ai.dtype)
        )
        vals = cat[jnp.concatenate(
            [(ai[None, :] + plane_off).reshape(-1),
             (bp[None, :] + plane_off).reshape(-1)]
        )]
        a_planes = vals[:nk * nq].reshape(nk, nq)
        b_planes = vals[nk * nq:].reshape(nk, nq)
        # P(mid): A[mid] <= B_asc[qd-1-mid]  (lexicographic)
        le = jnp.ones(mid.shape, bool)
        for j in range(nk - 1, -1, -1):
            a = a_planes[j]
            b = b_planes[j]
            le = (a < b) | ((a == b) & le)
        P = (le | b_hi) & ~b_lo
        lo2 = jnp.where(active & P, mid + 1, lo)
        hi2 = jnp.where(active & ~P, mid, hi)
        return lo2, hi2

    # Unrolled on purpose: a lax.fori_loop pays ~100s of us of
    # per-iteration device-loop overhead on this toolchain, which at
    # 32 iterations x O(10) levels dwarfed the actual gather work.
    lohi = (lo, hi)
    for _ in range(iters):
        lohi = body(None, lohi)
    return lohi[0]


# ---------------------------------------------------------------------------
# the merge-tile kernel


def _flat_shift(x, delta, rows):
    """y[f] = x_flat[f + delta] for delta in (-nrows*128, nrows*128),
    returning the first ``rows`` rows of the shifted view. Rolls wrap,
    so positions whose source falls outside the buffer read garbage —
    callers only consume in-window positions."""
    from jax.experimental.pallas import tpu as pltpu

    nr = x.shape[0]
    if isinstance(delta, int):
        # static path: multiples of 128 are a single row roll; other
        # static shifts still save the dynamic-mod arithmetic
        dl = delta % 128
        dr = (delta - dl) // 128
        x2 = pltpu.roll(x, (-dr) % nr, 0) if dr % nr else x
        if dl == 0:
            return x2[:rows]
        rl = pltpu.roll(x2, (-dl) % 128, 1)
        rup = pltpu.roll(rl, nr - 1, 0)
        lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
        return jnp.where(lane + dl >= 128, rup, rl)[:rows]
    # Bitwise/single-primitive arithmetic only: composite jnp ops
    # (floor_divide, mod) on scalars derived from SMEM reads insert
    # `pvary` under shard_map tracing, which Mosaic cannot lower
    # (found by the chipless v5e:2x4 AOT compile). x & 127 == x mod
    # 128 for any two's-complement int; >> is an arithmetic shift.
    dl = delta & 127                   # in [0, 128)
    dr = (delta - dl) >> 7             # signed row part
    # row part: x2[r] = x[r + dr]; (-dr) mod nr via one lax.rem on a
    # non-negative operand (dr in (-nr, nr))
    x2 = pltpu.roll(x, lax.rem(2 * nr - dr, nr), 0)
    # lane part: y[f] = x2[f + dl], dl in [0, 128)
    rl = pltpu.roll(x2, (128 - dl) & 127, 1)    # rl[r,c]=x2[r,(c+dl)%128]
    rup = pltpu.roll(rl, nr - 1, 0)             # rl[r+1, .]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    y = jnp.where(lane + dl >= 128, rup, rl)
    return y[:rows]


def _lex_le(a_keys, b_keys):
    le = jnp.ones(a_keys[0].shape, bool)
    for a, b in zip(reversed(a_keys), reversed(b_keys)):
        le = (a < b) | ((a == b) & le)
    return le


def _merge_tile_kernel(abase_ref, aoff_ref, bbase_ref, boff_ref,
                       p_ref, dir_ref, *refs, tile: int, nplanes: int,
                       nkeys: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P = nplanes
    R = tile // 128
    RA = R + 16          # 8-row-aligned window + shift slop
    in_ref, out_ref, scrA, scrB, sem = refs

    t = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = t % 2
    aoff = aoff_ref[t]            # a0 - abase*128
    boff = boff_ref[t]
    p = p_ref[t]
    dirb = dir_ref[t] != 0

    # Row-dim DMA offsets must be 8-row aligned on this toolchain
    # (unaligned ones fault); the residue rides the in-VMEM flat
    # shift, whose row roll wraps modulo the window so any in-window
    # distance is reachable. The planes travel as ONE stacked
    # (P, rows, 128) array (2 DMAs per tile, not 2P), and the windows
    # are DOUBLE-BUFFERED: tile t+1's copies are issued before tile
    # t's compute so the per-tile DMA wait overlaps (the synchronous
    # wait was most of the ~20 us/tile overhead, as in
    # ops/compact_planes.py).
    def copies(tt, sl):
        ca = pltpu.make_async_copy(
            in_ref.at[:, pl.ds(abase_ref[tt], RA), :], scrA.at[sl],
            sem.at[sl, 0],
        )
        cb = pltpu.make_async_copy(
            in_ref.at[:, pl.ds(bbase_ref[tt], RA), :], scrB.at[sl],
            sem.at[sl, 1],
        )
        return ca, cb

    @pl.when(t == 0)
    def _():
        ca, cb = copies(0, 0)
        ca.start()
        cb.start()

    @pl.when(t + 1 < nt)
    def _():
        ca, cb = copies(t + 1, (t + 1) % 2)
        ca.start()
        cb.start()

    ca, cb = copies(t, slot)
    ca.wait()
    cb.wait()

    # assemble the bitonic tile [A-part asc | B-part desc]
    delta_b = boff - p
    row_i = lax.broadcasted_iota(jnp.int32, (R, 128), 0)
    lane_i = lax.broadcasted_iota(jnp.int32, (R, 128), 1)
    flat = row_i * 128 + lane_i
    from_a = flat < p
    planes = []
    for i in range(P):
        ya = _flat_shift(scrA[slot, i], aoff, R)
        yb = _flat_shift(scrB[slot, i], delta_b, R)
        planes.append(jnp.where(from_a, ya, yb))

    # XOR-partner compare-exchange network, log2(tile) stages
    s = tile // 2
    while s >= 128:
        k = s // 128
        g = R // (2 * k)
        halves = [x.reshape(g, 2, k, 128) for x in planes]
        a_keys = [x[:, 0] for x in halves[:nkeys]]
        b_keys = [x[:, 1] for x in halves[:nkeys]]
        le = _lex_le(a_keys, b_keys)           # (g, k, 128)
        keep = le ^ dirb                        # top gets smaller iff asc
        news = []
        for x in halves:
            a = x[:, 0]
            b = x[:, 1]
            lo2 = jnp.where(keep, a, b)
            hi2 = jnp.where(keep, b, a)
            news.append(
                jnp.concatenate(
                    [lo2[:, None], hi2[:, None]], axis=1
                ).reshape(R, 128)
            )
        planes = news
        s //= 2
    while s >= 1:
        bit = (lane_i & s) != 0
        partners = [
            jnp.where(bit, pltpu.roll(x, s, 1),
                      pltpu.roll(x, 128 - s, 1))
            for x in planes
        ]
        le_sp = _lex_le(planes[:nkeys], partners[:nkeys])
        eqs = jnp.ones((R, 128), bool)
        for a, b in zip(planes[:nkeys], partners[:nkeys]):
            eqs = eqs & (a == b)
        lt_sp = le_sp & ~eqs
        kmin = (~bit) ^ dirb
        # pure logic (a bool-valued select would hit Mosaic's
        # unsupported i8->i1 truncation)
        keep_self = (kmin & le_sp) | (~kmin & ~lt_sp)
        planes = [
            jnp.where(keep_self, x, px)
            for x, px in zip(planes, partners)
        ]
        s //= 2

    for i in range(P):
        out_ref[i, ...] = planes[i]


def _merge_level(stacked, a0, b0, p, dirs,
                 tile: int, nkeys: int, interpret: bool):
    """One merge level over the stacked (P, size) planes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, size = stacked.shape
    R = tile // 128
    rows = size // 128
    ntiles = a0.shape[0]

    ins3d = stacked.reshape(P, rows, 128)
    vma = getattr(jax.typeof(ins3d), "vma", None)

    def sds(shape, dt):
        if vma is not None:
            return jax.ShapeDtypeStruct(shape, dt, vma=vma)
        return jax.ShapeDtypeStruct(shape, dt)

    in_specs = (
        [pl.BlockSpec(memory_space=pltpu.SMEM)] * 6
        + [pl.BlockSpec(memory_space=pl.ANY)]
    )
    out_specs = pl.BlockSpec((P, R, 128), lambda t: (0, t, 0))
    # Row-dim DMA offsets must be 8-row aligned (unaligned dynamic
    # windows fault on this toolchain): bases are rounded down to 8
    # rows and the residue moves into the in-VMEM flat shift. Slack
    # tiles at the buffer tail clamp their base (their content is
    # all-sentinel, so a shifted window is indistinguishable); real
    # tiles never clamp (a0 <= n_pad and 2*tile slack >= window).
    RA = R + 16
    bound = rows - RA
    abase = jnp.minimum((a0 // 1024) * 8, bound)
    aoff = a0 - abase * 128
    bbase = jnp.minimum((b0 // 1024) * 8, bound)
    boff = b0 - bbase * 128
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(
                _merge_tile_kernel, tile=tile, nplanes=P, nkeys=nkeys
            ),
            name="sort_merge_tile",
            grid=(ntiles,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=sds((P, ntiles * R, 128), jnp.uint32),
            scratch_shapes=[
                pltpu.VMEM((2, P, RA, 128), jnp.uint32),
                pltpu.VMEM((2, P, RA, 128), jnp.uint32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
            interpret=interpret,
        )(abase, aoff, bbase, boff, p, dirs, ins3d)
    return out.reshape(P, -1)[:, :size]


# ---------------------------------------------------------------------------
# the sort


def merge_sort_planes(planes: Sequence[jax.Array], num_keys: int,
                      tile: int = 32768, run_mult: int = 4,
                      interpret: bool = False):
    """Sort u32 planes by the first ``num_keys`` planes (unsigned
    lexicographic, most-significant plane first). Returns the planes
    in sorted row order. Non-stable. The all-ones key tuple must be
    reserved by the caller (it is the padding sentinel; rows carrying
    it may be permuted with the padding)."""
    assert tile >= 1024 and tile % 128 == 0 and (tile & (tile - 1)) == 0
    planes = list(planes)
    P = len(planes)
    nk = num_keys
    assert 0 < nk <= P
    n = planes[0].shape[0]
    if n == 0:
        return planes

    # Initial runs are run_mult tiles long: the batched lax.sort's
    # per-element cost grows slowly with run length while every
    # extra doubling saves one full merge level (measured:
    # (2048,8192)=30ms vs (512,32768)=38ms vs flat 20M=166ms).
    m0 = run_mult * tile
    n_pad = _round_up(n, m0)
    nruns = n_pad // m0
    if nruns == 1:
        srt = lax.sort(tuple(planes), num_keys=nk, is_stable=False)
        return list(srt)

    slack = 2 * tile
    size = n_pad + slack

    def pad(x, fill):
        return jnp.concatenate(
            [x, jnp.full((size - n,), fill, jnp.uint32)]
        )

    planes = [
        pad(x, 0xFFFFFFFF if i < nk else 0)
        for i, x in enumerate(planes)
    ]

    # run sort (batched; this is where lax.sort is fast), then flip
    # the runs that must start out descending
    counts = _tree_counts(nruns)
    runs2d = [x[:n_pad].reshape(nruns, m0) for x in planes]
    runs2d = list(lax.sort(tuple(runs2d), dimension=1, num_keys=nk,
                           is_stable=False))
    desc0 = np.array(
        [_orient(j, 0, counts) for j in range(nruns)], dtype=bool
    )
    if desc0.any():
        dm = jnp.asarray(desc0)[:, None]
        runs2d = [jnp.where(dm, x[:, ::-1], x) for x in runs2d]
    planes = [
        jnp.concatenate([x.reshape(-1), pl_[n_pad:]])
        for x, pl_ in zip(runs2d, planes)
    ]
    # One stacked (P, size) array between levels: the kernel moves
    # all planes with 2 DMAs per tile instead of 2P.
    stacked = jnp.stack(planes)

    # merge levels
    seg_starts = [j * m0 for j in range(nruns)]
    seg_lens = [m0] * nruns
    level = 0
    while len(seg_starts) > 1:
        level += 1
        nseg = len(seg_starts)
        pa_s, pa_l, pb_s, pb_l, po_s = [], [], [], [], []
        for j in range(0, nseg, 2):
            if j + 1 < nseg:
                pa_s.append(seg_starts[j])
                pa_l.append(seg_lens[j])
                pb_s.append(seg_starts[j + 1])
                pb_l.append(seg_lens[j + 1])
            else:
                pa_s.append(seg_starts[j])
                pa_l.append(seg_lens[j])
                pb_s.append(seg_starts[j])
                pb_l.append(0)
            po_s.append(seg_starts[j])
        # slack pass-through (keeps the sentinel tail valid as the
        # next level's input)
        pa_s.append(n_pad)
        pa_l.append(slack)
        pb_s.append(n_pad)
        pb_l.append(0)
        po_s.append(n_pad)

        npair = len(pa_s)
        pa_s_np = np.asarray(pa_s, np.int64)
        pa_l_np = np.asarray(pa_l, np.int64)
        pb_s_np = np.asarray(pb_s, np.int64)
        pb_l_np = np.asarray(pb_l, np.int64)
        po_l_np = pa_l_np + pb_l_np
        ntiles_p = po_l_np // tile

        # one search query per tile boundary per pair (trivial
        # endpoints included — they converge instantly)
        nq = ntiles_p + 1
        qpair = np.repeat(np.arange(npair), nq)
        qt = np.concatenate([np.arange(c) for c in nq])
        qd = (qt * tile).astype(np.int64)
        qd = np.minimum(qd, po_l_np[qpair])
        # search range is at most min(lenA, lenB) wide
        max_rng = int(min(pa_l_np.max(), pb_l_np.max() or 1))
        iters = max(1, math.ceil(math.log2(max_rng + 1)) + 1)
        bnd = _diag_search(
            stacked, nk,
            jnp.asarray(pa_s_np[qpair], jnp.int32),
            jnp.asarray(pa_l_np[qpair], jnp.int32),
            jnp.asarray(pb_s_np[qpair], jnp.int32),
            jnp.asarray(pb_l_np[qpair], jnp.int32),
            jnp.asarray(qd, jnp.int32),
            iters=iters,
        )

        # per-tile kernel arrays
        qstart = np.concatenate([[0], np.cumsum(nq)])
        tpair = np.repeat(np.arange(npair), ntiles_p)
        tloc = np.concatenate([np.arange(c) for c in ntiles_p])

        dirs_np = np.zeros(len(tpair), np.int32)
        real = pb_l_np[tpair] > 0
        # output segment index at this level == pair index; its
        # orientation comes from the ceil tree. Pass-throughs keep
        # their current orientation.
        for i, pj in enumerate(tpair):
            if pj == npair - 1:
                dirs_np[i] = 0          # slack: ascending sentinels
            elif real[i]:
                dirs_np[i] = _orient(int(pj), level, counts)
            else:
                # deferred: same orientation it already has
                dirs_np[i] = _orient(2 * int(pj), level - 1, counts)

        # The diagonal search ranks ascending. A DESCENDING output
        # segment lays its tiles largest-first, so physical tile t
        # takes the ascending-ranked block ntiles-1-t (each tile then
        # sorts descending internally). Pass-through tiles are pure
        # copies and keep the identity mapping whatever their stored
        # orientation.
        tloc_eff = np.where(
            real & (dirs_np == 1), ntiles_p[tpair] - 1 - tloc, tloc
        )
        bndS_idx = qstart[tpair] + tloc_eff
        aS = bnd[jnp.asarray(bndS_idx, jnp.int32)]
        aE = bnd[jnp.asarray(bndS_idx + 1, jnp.int32)]
        a0 = jnp.asarray(pa_s_np[tpair], jnp.int32) + aS
        pT = aE - aS
        d1 = jnp.asarray((tloc_eff + 1) * tile, jnp.int32)
        bE = d1 - aE
        b0 = jnp.asarray(pb_s_np[tpair] + pb_l_np[tpair],
                         jnp.int32) - bE
        b0 = jnp.maximum(b0, 0)

        stacked = _merge_level(
            stacked,
            a0.astype(jnp.int32),
            b0.astype(jnp.int32),
            pT.astype(jnp.int32),
            jnp.asarray(dirs_np),
            tile, nk, interpret,
        )

        seg_starts = po_s[:-1]
        seg_lens = list(po_l_np[:-1])
    return [stacked[i][:n] for i in range(P)]


def pallas_merged_sort(operands: Sequence[jax.Array], num_keys: int,
                       tile: int = 32768, run_mult: int = 4,
                       interpret: bool = False):
    """Drop-in for ``lax.sort(operands, num_keys=...)`` (non-stable):
    first ``num_keys`` operands are compare keys, the rest ride.
    Caller must ensure the all-max key tuple either cannot occur or
    marks rows whose order against padding is immaterial (ops/join.py:
    sentinel rows are tag-2 invalid rows)."""
    operands = list(operands)
    planes = []
    spec = []          # (operand index, is_key, dtype, plane count)
    for i, c in enumerate(operands):
        is_key = i < num_keys
        ps = key_to_planes(c) if is_key else val_to_planes(c)
        spec.append((i, is_key, c.dtype, len(ps)))
        planes.extend(ps)
    nk = sum(cnt for _, k, _, cnt in spec if k)
    srt = merge_sort_planes(planes, nk, tile=tile, run_mult=run_mult,
                            interpret=interpret)
    out = []
    pos = 0
    for i, is_key, dt, cnt in spec:
        sub = srt[pos:pos + cnt]
        pos += cnt
        out.append(
            planes_to_key(sub, dt) if is_key else planes_to_val(sub, dt)
        )
    return tuple(out)
