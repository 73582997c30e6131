"""Pallas log-shift record expansion (u32 planes). EXPERIMENTAL — not
wired into the production join: the fused build side is blocked by
duplicate-key rank revisits (proof sketch below); ops/join.py uses
the MXU expand kernel (ops/expand_pallas.py) instead.

Same job as ops/expand_pallas.expand_gather — broadcast each record's
values down its output run, plus the fused build-side materialization
— but built from shift networks instead of one-hot MXU matmuls:

- PUSH: each record in the block's window moves UP to its (clamped)
  run-start slot ``max(S[r]-blockstart,0)``. Displacements
  ``d[e] = target[e]-e`` are >=0 and non-decreasing (run starts are
  strictly increasing), so the same collision-free bit-by-bit shift
  network as ops/compact_planes.py applies, with an alive-priority
  select (records whose run starts beyond the block ride dead).
- FILL: a Hillis-Steele "last placed record" scan broadcasts each
  record down its run: log2(B) conditional-take stages.
- PULL (build side): after the fill, every output slot knows its
  build rank ``rank[j] = lo[j] + (j - start_b[j])`` pointwise, and
  ``out[j] = W[pidx[j]]`` is computed by bit-decomposing
  ``q[j] = j + 2048 - pidx[j]`` into log2 conditional pulls.

  **KNOWN LIMITATION — build side is only correct for non-repeating
  rank sequences.** Bit-decomposed pulls compose as
  ``y[j] = y0[j - q[j]]`` only when every intermediate position's q
  agrees on the processed bits; duplicate probe keys make ``rank``
  revisit earlier pack windows (q jumps), and the composition breaks
  (regression-tested as xfail). The join therefore keeps
  ops/expand_pallas.py's one-hot window gather for the fused build
  materialization; this module's record expand + fill (which ARE
  dup-safe — the push network is MSB-first and needs only monotone
  run starts) serve the no-build-cols call sites.

Every op is a u32 roll/select — no _F32_EXACT range limits, no bf16
chunking, no MXU.
"""

from __future__ import annotations

import functools

import jax

import jax.numpy as jnp
from jax import lax

from distributed_join_tpu.ops.sort_pallas import (
    _flat_shift,
    _round_up,
    merge_u64,
    split_u64,
)

_I32_MAX = 2**31 - 1


def _expand_kernel(r0_ref, roff_ref, bb_ref, boff_ref, *refs,
                   block: int, nrec: int, nbuild: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    RB = block // 128
    RW = RB + 16
    has_build = nbuild > 0
    if has_build:
        rec_ref, b_ref, out_ref, scrR, scrB, sem = refs
    else:
        rec_ref, out_ref, scrR, sem = refs
        b_ref = scrB = None

    t = pl.program_id(0)
    bs = t * block
    rbase = r0_ref[t]
    roff = roff_ref[t]

    cr = pltpu.make_async_copy(
        rec_ref.at[:, pl.ds(rbase, RW), :], scrR, sem.at[0]
    )
    cr.start()
    if has_build:
        cb = pltpu.make_async_copy(
            b_ref.at[:, pl.ds(bb_ref[t], RW), :], scrB, sem.at[1]
        )
        cb.start()
    cr.wait()
    if has_build:
        cb.wait()

    row_i = lax.broadcasted_iota(jnp.int32, (RB, 128), 0)
    lane_i = lax.broadcasted_iota(jnp.int32, (RB, 128), 1)
    flat = row_i * 128 + lane_i

    # window planes, record e at flat position e; plane 0 is S
    planes = [_flat_shift(scrR[i], roff, RB) for i in range(nrec)]
    S_loc = planes[0].astype(jnp.int32) - bs
    alive = (S_loc < block).astype(jnp.uint32)   # sentinels are huge
    target = jnp.maximum(S_loc, 0)
    d = jnp.where(alive != 0, target - flat, 0).astype(jnp.uint32)

    # PUSH records up to their run-start slots — MSB-FIRST. Expansion
    # displacements only satisfy monotonicity (NOT the compaction
    # network's d[i]-d[j] <= i-j), and LSB-first partial positions can
    # collide (e.g. d = [.., 3, 6] at adjacent records). MSB-first is
    # collision-free for any non-decreasing d: a mover at stage b
    # landing on an alive stayer would need the stayer's remaining
    # low bits to reach 2^b, which contradicts low < 2^b.
    s = block // 2
    while s >= 1:
        d_sh = _flat_shift(d, -s, RB)
        alive_sh = _flat_shift(alive, -s, RB)
        take = (
            ((d_sh & s) != 0) & (alive_sh != 0) & (flat - s >= 0)
        )
        moved_away = ((d & s) != 0) & (alive != 0)
        alive = jnp.where(
            take, jnp.uint32(1),
            jnp.where(moved_away, jnp.uint32(0), alive),
        )
        d = jnp.where(take, d_sh, d)
        planes = [
            jnp.where(take, _flat_shift(x, -s, RB), x) for x in planes
        ]
        s //= 2

    # FILL each run downward from its start (take from BELOW)
    s = 1
    while s < block:
        has_sh = _flat_shift(alive, -s, RB)
        take = (alive == 0) & (has_sh != 0) & (flat - s >= 0)
        planes = [
            jnp.where(take, _flat_shift(x, -s, RB), x) for x in planes
        ]
        alive = jnp.where(take, jnp.uint32(1), alive)
        s *= 2

    outs = list(planes)          # S plane doubles as start_b
    if has_build:
        start_b = planes[0].astype(jnp.int32)
        lo = planes[1].astype(jnp.int32)
        rank = lo + (bs + flat - start_b)
        pidx = jnp.clip(rank - (bb_ref[t] * 128), 0, RW * 128 - 1)
        # q >= 1: pidx <= boff + flat (delta-rank <= 1/slot) and
        # boff < 2048 by the window-base choice below
        q = (flat + 2048 - pidx).astype(jnp.uint32)
        # The pull composes modularly over the FULL RW-row window:
        # intermediate positions j - (partial bits of q) go negative
        # and wrap; slicing to RB rows mid-chain would change the
        # modulus and corrupt the composition. Slice only at the end.
        qw = jnp.concatenate(
            [q, jnp.zeros((RW - RB, 128), jnp.uint32)], axis=0
        )
        bplanes = [_flat_shift(scrB[i], 2048, RW) for i in range(nbuild)]
        s = 1
        while s < 2 * block:
            bit = (qw & s) != 0
            bplanes = [
                jnp.where(bit, _flat_shift(x, -s, RW), x)
                for x in bplanes
            ]
            s *= 2
        outs = outs + [x[:RB] for x in bplanes]

    for i, x in enumerate(outs):
        out_ref[i, ...] = x


def expand_pull(S: jax.Array, cols, out_capacity: int,
                block: int = 32768, interpret: bool = False,
                lo=None, build_cols=None):
    """Drop-in for ops/expand_pallas.expand_gather (uint64 columns).

    Without build_cols: returns (rec_outs, start_b).
    With lo+build_cols: returns (rec_outs, start_b, rank, build_outs)
    (rank is a placeholder zero array, as in the fused MXU kernel).
    Slots j >= the covered range (no record with S <= j) are
    undefined; callers mask by the match count.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert block >= 2048 and block % 128 == 0
    RB = block // 128
    RW = RB + 16
    m = S.shape[0]
    out_pad = _round_up(out_capacity, block)
    nblk = out_pad // block

    # record planes: [S, (lo), *split(cols)]
    rec_planes = [S.astype(jnp.uint32)]
    if build_cols is not None:
        rec_planes.append(lo.astype(jnp.uint32))
    for c in cols:
        rec_planes.extend(split_u64(c))
    nrec = len(rec_planes)

    m_pad = _round_up(m, 128) + RW * 128
    def padr(x, fill):
        return jnp.concatenate(
            [x, jnp.full((m_pad - m,), fill, jnp.uint32)]
        )
    rec_planes = [
        padr(x, _I32_MAX if i == 0 else 0)
        for i, x in enumerate(rec_planes)
    ]
    rec3d = jnp.stack(rec_planes).reshape(nrec, m_pad // 128, 128)

    starts = jnp.arange(nblk, dtype=jnp.int32) * block
    r0 = jnp.maximum(
        jnp.searchsorted(S, starts, side="right").astype(jnp.int32) - 1,
        0,
    )
    rbase = jnp.minimum((r0 // 1024) * 8, m_pad // 128 - RW)
    roff = r0 - rbase * 128

    nbuild = 0
    bb = boff = jnp.zeros((nblk,), jnp.int32)
    args = [rbase, roff, bb, boff, rec3d]
    if build_cols is not None:
        bplanes = []
        for c in build_cols:
            bplanes.extend(split_u64(c))
        nbuild = len(bplanes)
        nb = build_cols[0].shape[0]
        nb_pad = _round_up(nb, 128) + RW * 128
        bplanes = [
            jnp.concatenate(
                [x, jnp.zeros((nb_pad - nb,), jnp.uint32)]
            )
            for x in bplanes
        ]
        b3d = jnp.stack(bplanes).reshape(nbuild, nb_pad // 128, 128)
        # build rank at each block start (w1 formula of the MXU
        # kernel): lo[r0] + (blockstart - S[r0])
        s_r0 = jnp.where(S[r0] == _I32_MAX, starts, S[r0].astype(jnp.int32))
        b0 = jnp.clip(lo[r0].astype(jnp.int32) + (starts - s_r0),
                      0, nb_pad - 1)
        # the pull buffer is pre-shifted by +2048, so the window base
        # sits up to 2048 elements before b0 (boff in [1024, 2048)
        # unless clipped at the array start)
        bb = jnp.clip((b0 - 1024) // 1024 * 8, 0,
                      nb_pad // 128 - RW)
        boff = b0 - bb * 128
        args = [rbase, roff, bb, boff, rec3d, b3d]

    nout = nrec + nbuild
    vma = getattr(jax.typeof(rec3d), "vma", None)
    out_sds = (
        jax.ShapeDtypeStruct((nout, out_pad // 128, 128), jnp.uint32,
                             vma=vma)
        if vma is not None else
        jax.ShapeDtypeStruct((nout, out_pad // 128, 128), jnp.uint32)
    )
    scratch = [pltpu.VMEM((nrec, RW, 128), jnp.uint32)]
    if build_cols is not None:
        scratch.append(pltpu.VMEM((nbuild, RW, 128), jnp.uint32))
    scratch.append(pltpu.SemaphoreType.DMA((2,)))
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(
                _expand_kernel, block=block, nrec=nrec, nbuild=nbuild
            ),
            name="expand_planes",
            grid=(nblk,),
            in_specs=(
                [pl.BlockSpec(memory_space=pltpu.SMEM)] * 4
                + [pl.BlockSpec(memory_space=pl.ANY)]
                * (2 if build_cols is not None else 1)
            ),
            out_specs=pl.BlockSpec(
                (nout, RB, 128), lambda t: (0, t, 0)
            ),
            out_shape=out_sds,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*args)
    flat_out = out.reshape(nout, -1)[:, :out_capacity]

    start_b = flat_out[0].astype(jnp.int32)
    idx = 1 + (1 if build_cols is not None else 0)
    rec_outs = []
    for _ in cols:
        rec_outs.append(merge_u64(flat_out[idx], flat_out[idx + 1]))
        idx += 2
    if build_cols is None:
        return rec_outs, start_b
    build_outs = []
    for _ in build_cols:
        build_outs.append(merge_u64(flat_out[idx], flat_out[idx + 1]))
        idx += 2
    zero = start_b * 0
    return rec_outs, start_b, zero, build_outs
