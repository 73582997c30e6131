"""Fused join scans: every per-position scan the sort-merge join needs,
in two streaming Pallas passes over the merged-sorted domain.

The XLA formulation (ops/join.py step 3) chains ~5 full-length
cumsum/cummax ops at ~2 ns/element each (~70 ms at 20M rows), and the
matched-build machinery the universal kernel build path needs (below)
would add a REVERSED cumsum+cummax (~+60 ms) — each XLA scan is its own
HBM round trip. Both passes here are bandwidth-bound streaming kernels:
big (8, L) int32 tiles, in-VMEM log-shift prefix scans (pltpu.roll —
Mosaic has no cumsum primitive), and a few SMEM scalar carries across
sequential grid blocks.

Pass R (reverse grid order, suffix scans): a build row is MATCHED iff
its run still has a probe after it — builds precede probes of the same
run, so at a build position "probes after me in my run" is the whole
run's probe count. With ``P[i]`` = suffix probe count and ``NR[i]`` =
``P`` at the next run start strictly after i (a reverse EXCLUSIVE
cummax of ``first ? P : 0`` — P decreases forward, so the max picks the
nearest run start), ``matched[i] = is_build[i] & (P[i] - NR[i] > 0)``.
Matched-ness is what makes the expand kernel's two-window build scheme
universal: ``lo'`` (the matched-build prefix rank) advances between
records EXACTLY by the previous record's run length, never by unmatched
keys (ops/expand_pallas.py's gap hazard), so the window proof holds on
the matched-dense pack by construction.

Pass F (forward, prefix scans): build counts, run-start broadcasts (a
cummax of values sampled at run starts — the values are globally
non-decreasing), match counts per probe, output-slot prefix, record
positions, matched-build positions:

    b_before  = cumsum(is_build) - is_build
    lo_raw    = cummax(first ? b_before : 0)
    cnt       = is_probe ? b_before - lo_raw : 0
    start_out = cumsum(cnt) - cnt
    rec_pos   = cumsum(is_probe & cnt > 0) - 1
    mb_before = cumsum(matched) - matched
    lo_m      = cummax(first ? mb_before : 0)
    mb_pos    = cumsum(matched) - 1

``rec_pos``/``mb_pos`` feed ops/compact_pallas.stream_compact (the
record block and the matched-build pack); ``lo_m`` rides the records
into the expand kernel; ``start_out`` is the record key; ``cnt`` is
summed (in int64, outside) for the overflow contract.

int32 throughout (the join's documented >2^31-matches contract lives in
the OUTSIDE int64 sum of cnt). All scans here are over 0/1 indicators
or their prefix counts, so int32 is exact up to 2^31 rows per shard.
"""

from __future__ import annotations

import jax

import jax.numpy as jnp

from distributed_join_tpu.ops.expand_pallas import _round_up

# (8, _LANES) int32 tiles: one grid block covers 8*_LANES elements.
# Big blocks amortize per-iteration overhead (the pass is bandwidth
# bound); (8, 8192) = 256 KB per array comfortably fits several arrays
# in VMEM.
_LANES = 8192


def _tile_scan(x, op, identity, reverse=False):
    """Inclusive prefix (or suffix) scan over the row-major flattened
    (8, L) tile: log-shift lane scans, then the 8 row totals are
    scanned and broadcast back. ~log2(L)+3 pltpu.roll ops."""
    from jax.experimental.pallas import tpu as pltpu

    L = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < L:
        if reverse:
            # left rotation = roll by L - s (pltpu.roll rejects
            # negative shifts)
            sh = pltpu.roll(x, L - s, 1)
            x = op(x, jnp.where(lane < L - s, sh, identity))
        else:
            sh = pltpu.roll(x, s, 1)
            x = op(x, jnp.where(lane >= s, sh, identity))
        s *= 2
    # Row totals live at the last (first, if reverse) lane; scan the 8
    # rows the same way along the sublane axis, EXCLUSIVE, and fold in.
    row = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    tot = x[:, L - 1 : L] if not reverse else x[:, 0:1]
    s = 1
    while s < 8:
        if reverse:
            sh = pltpu.roll(tot, 8 - s, 0)
            tot = op(tot, jnp.where(row < 8 - s, sh, identity))
        else:
            sh = pltpu.roll(tot, s, 0)
            tot = op(tot, jnp.where(row >= s, sh, identity))
        s *= 2
    # exclusive across rows: shift by one row
    if reverse:
        excl = jnp.where(row < 7, pltpu.roll(tot, 7, 0), identity)
    else:
        excl = jnp.where(row >= 1, pltpu.roll(tot, 1, 0), identity)
    return op(x, excl)


def _scan_r_kernel(tag_ref, first_ref, matched_ref, p_carry, nr_carry):
    """Reverse pass: matched-build flags. Carries: suffix probe total
    (p_carry) and the masked reverse-cummax carrier (nr_carry)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        p_carry[0] = 0
        nr_carry[0] = 0

    tag = tag_ref[...]
    first = first_ref[...]
    is_p = (tag == 1).astype(jnp.int32)
    is_b = tag == 0
    add = jnp.add
    # P: inclusive suffix probe count (carry = probes right of block)
    P = _tile_scan(is_p, add, 0, reverse=True) + p_carry[0]
    # NR: EXCLUSIVE reverse cummax of (first ? P : 0) — shift the
    # masked values one position left before the scan so each element
    # sees only run starts strictly after it.
    masked = jnp.where(first, P, 0)
    L = masked.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, masked.shape, 0)
    # element (r, l) takes (r, l+1); row boundary takes (r+1, 0);
    # the tile's last element takes the carry.
    nxt = pltpu.roll(masked, L - 1, 1)
    from_next_row = pltpu.roll(masked[:, 0:1], 7, 0)
    nxt = jnp.where(lane == L - 1, from_next_row, nxt)
    nxt = jnp.where((lane == L - 1) & (row == 7), nr_carry[0], nxt)
    NR = _tile_scan(nxt, jnp.maximum, 0, reverse=True)
    NR = jnp.maximum(NR, nr_carry[0])
    matched_ref[...] = (is_b & (P - NR > 0)).astype(jnp.int32)

    p_carry[0] = P[0, 0]
    # new carrier: max of (first ? P : 0) over this block and right
    nr_carry[0] = jnp.maximum(
        jnp.max(jnp.where(first, P, 0)), nr_carry[0]
    )


def _scan_f_kernel(tag_ref, first_ref, matched_ref, cnt_ref, so_ref,
                   lom_ref, rpos_ref, mpos_ref, carry):
    """Forward pass. carry layout (SMEM (8,) int32):
    [0] b_incl, [1] csum, [2] rec count, [3] mb count,
    [4] lo_raw carrier, [5] lo_m carrier."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for t in range(6):
            carry[t] = 0

    tag = tag_ref[...]
    first = first_ref[...] != 0
    matched = matched_ref[...] != 0
    is_b = (tag == 0).astype(jnp.int32)
    is_p = tag == 1
    add = jnp.add

    b_incl = _tile_scan(is_b, add, 0) + carry[0]
    b_before = b_incl - is_b
    lo_raw = jnp.maximum(
        _tile_scan(jnp.where(first, b_before, 0), jnp.maximum, 0),
        carry[4],
    )
    cnt = jnp.where(is_p, b_before - lo_raw, 0)
    csum = _tile_scan(cnt, add, 0) + carry[1]
    so = csum - cnt
    is_rec = (is_p & (cnt > 0)).astype(jnp.int32)
    rpos = _tile_scan(is_rec, add, 0) + carry[2] - 1
    mb = matched.astype(jnp.int32)
    mb_incl = _tile_scan(mb, add, 0) + carry[3]
    mb_before = mb_incl - mb
    lo_m = jnp.maximum(
        _tile_scan(jnp.where(first, mb_before, 0), jnp.maximum, 0),
        carry[5],
    )

    cnt_ref[...] = cnt
    so_ref[...] = so
    lom_ref[...] = lo_m
    rpos_ref[...] = rpos
    mpos_ref[...] = mb_incl - 1

    L = tag.shape[1]
    carry[0] = b_incl[7, L - 1]
    carry[1] = csum[7, L - 1]
    carry[2] = rpos[7, L - 1] + 1
    carry[3] = mb_incl[7, L - 1]
    carry[4] = lo_raw[7, L - 1]
    carry[5] = lo_m[7, L - 1]


def join_scans(tag: jax.Array, first: jax.Array,
               interpret: bool = False):
    """All merged-domain scans of the sort-merge join, fused.

    tag:   (n,) int8 — 0 build, 1 probe, 2 padding (ops/join.py step 2).
    first: (n,) bool — run starts (key-change positions; [0] True).

    Returns a dict of (n,) int32 arrays: ``cnt`` (matches per probe
    row), ``start_out`` (first output slot of the probe's run),
    ``lo_m`` (matched-build rank of the run start), ``rec_pos``
    (cumsum(is_rec)-1), ``matched`` (0/1 matched-build flag),
    ``mb_pos`` (cumsum(matched)-1). Totals are the last elements + 1
    of the *_pos arrays (position scans cover every element).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = tag.shape[0]
    L = _LANES if n >= 8 * _LANES else max(128, _round_up(n, 8 * 128) // 8)
    blk = 8 * L
    n_pad = _round_up(max(n, 1), blk)
    nblocks = n_pad // blk

    tag_i = tag.astype(jnp.int32)
    first_i = first.astype(jnp.int32)
    if n_pad > n:
        pad = n_pad - n
        tag_i = jnp.concatenate(
            [tag_i, jnp.full((pad,), 2, jnp.int32)]
        )
        # padding opens its own "run" so it can never read run state
        # from real rows (it has no probes/builds either way)
        first_i = jnp.concatenate(
            [first_i, jnp.ones((1,), jnp.int32),
             jnp.zeros((pad - 1,), jnp.int32)]
            if pad > 1
            else [first_i, jnp.ones((1,), jnp.int32)]
        )
    tag2 = tag_i.reshape(n_pad // L, L)
    first2 = first_i.reshape(n_pad // L, L)

    spec = pl.BlockSpec((8, L), lambda i: (i, 0))
    rspec = pl.BlockSpec((8, L), lambda i: (nblocks - 1 - i, 0))
    vma = getattr(jax.typeof(tag2), "vma", None)

    def _shape():
        if vma is not None:
            return jax.ShapeDtypeStruct(
                (n_pad // L, L), jnp.int32, vma=vma
            )
        return jax.ShapeDtypeStruct((n_pad // L, L), jnp.int32)

    with jax.enable_x64(False):
        matched2 = pl.pallas_call(
            _scan_r_kernel,
            grid=(nblocks,),
            in_specs=[rspec, rspec],
            out_specs=rspec,
            scratch_shapes=[
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
            out_shape=_shape(),
            interpret=interpret,
        )(tag2, first2)

        outs = pl.pallas_call(
            _scan_f_kernel,
            grid=(nblocks,),
            in_specs=[spec, spec, spec],
            out_specs=[spec] * 5,
            scratch_shapes=[pltpu.SMEM((8,), jnp.int32)],
            out_shape=[_shape() for _ in range(5)],
            interpret=interpret,
        )(tag2, first2, matched2)

    cnt, so, lo_m, rpos, mpos = [o.reshape(n_pad)[:n] for o in outs]
    matched = matched2.reshape(n_pad)[:n]
    return {
        "cnt": cnt,
        "start_out": so,
        "lo_m": lo_m,
        "rec_pos": rpos,
        "matched": matched,
        "mb_pos": mpos,
    }


def join_scans_reference(tag: jax.Array, first: jax.Array):
    """XLA reference (the scan chain spelled out), for tests and as the
    CPU fallback shape of the same quantities."""
    from jax import lax

    is_b = tag == jnp.int8(0)
    is_p = tag == jnp.int8(1)
    f_incl = jnp.cumsum(is_b.astype(jnp.int32))
    b_before = f_incl - is_b.astype(jnp.int32)
    lo_raw = lax.cummax(jnp.where(first, b_before, 0))
    cnt = jnp.where(is_p, b_before - lo_raw, 0)
    csum = jnp.cumsum(cnt)
    so = csum - cnt
    is_rec = is_p & (cnt > 0)
    rpos = jnp.cumsum(is_rec.astype(jnp.int32)) - 1
    # matched: reversed scans
    P = jnp.flip(jnp.cumsum(jnp.flip(is_p.astype(jnp.int32))))
    maskedP = jnp.where(first, P, 0)
    nxt = jnp.concatenate([maskedP[1:], jnp.zeros((1,), jnp.int32)])
    NR = jnp.flip(lax.cummax(jnp.flip(nxt)))
    matched = (is_b & (P - NR > 0)).astype(jnp.int32)
    mb_incl = jnp.cumsum(matched)
    mb_before = mb_incl - matched
    lo_m = lax.cummax(jnp.where(first, mb_before, 0))
    return {
        "cnt": cnt,
        "start_out": so,
        "lo_m": lo_m,
        "rec_pos": rpos,
        "matched": matched,
        "mb_pos": mb_incl - 1,
    }
