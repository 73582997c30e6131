"""Pallas log-shift streaming compaction (u32 planes).

Same contract as ops/compact_pallas.stream_compact — order-preserving
``out[pos[e]] = cols[e] where mask[e]`` with positions
``pos = cumsum(mask) - 1`` — but the in-block routing is a monotone
LOG-SHIFT network instead of a one-hot MXU matmul. Round-3 ablation
(scripts/profile_r3_pipeline.py) put the two matmul-routed
compactions at 116 ms of the 360 ms bench join; the matmul costs
~ck*B MACs per element, while shifting costs log2(B) select passes.

Why shifts suffice: an element's in-block displacement
``d[e] = e_local - local_rank[e]`` equals the number of dead elements
before it in the block, which is NON-DECREASING in e. Moving every
survivor down by the set bits of its d (LSB to MSB) can never collide
two survivors: partial positions ``e - (d mod 2^{b+1})`` stay
strictly increasing (d monotone and d[i]-d[j] <= i-j), and equality
would require all elements between to be dead. Dead slots are
don't-care lanes that arriving survivors overwrite; a survivor only
"arrives" when its own bit is set (priority select on the riding
alive plane).

Block output windows are element-granular. DMA row offsets must be
8-row (1024-element) aligned on this toolchain, so each block writes
an aligned superset window whose partial leading chunk reproduces the
previous block's tail (carry), exactly like ops/compact_pallas.py —
except the carry is read from the PREVIOUS grid step's stage scratch
(double-buffered slots), which also lets each step's output DMA
overlap the next step's compute: the per-step DMA wait was ~20 us of
dead time per block in the matmul kernel.

All data moves as a single stacked (P+2, rows, 128) u32 array
(2 DMAs per block, not 2 per plane): [alive, d, *value planes].
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


def _compact_kernel(base8_ref, q_ref, *refs, block: int, nplanes: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P2 = nplanes + 2
    RB = block // 128
    RS = RB + 8                    # stage rows: q < 1024 head + block
    in_ref, out_ref, stage, sem = refs

    t = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = t % 2
    # base8/q are precomputed OUTSIDE: floor-divides on SMEM-read
    # scalars insert `pvary` under shard_map tracing, which Mosaic
    # cannot lower.
    base8 = base8_ref[t]
    q = q_ref[t]

    data = in_ref[...]             # (P2, RB, 128) auto-pipelined block
    alive = data[0]
    d = data[1]

    row_i = lax.broadcasted_iota(jnp.int32, (RB, 128), 0)
    lane_i = lax.broadcasted_iota(jnp.int32, (RB, 128), 1)
    flat = row_i * 128 + lane_i

    planes = [data[i] for i in range(P2)]
    s = 1
    while s < block:
        # survivors whose displacement has bit s move down by s
        d_sh = _flat_shift(d, s, RB)
        alive_sh = _flat_shift(alive, s, RB)
        take = (
            ((d_sh & s) != 0) & (alive_sh != 0) & (flat + s < block)
        )
        moved_away = ((d & s) != 0) & (alive != 0)
        new_planes = []
        for i, x in enumerate(planes):
            x_sh = _flat_shift(x, s, RB)
            if i == 0:
                stay = jnp.where(moved_away, jnp.uint32(0), x)
                new_planes.append(jnp.where(take, x_sh, stay))
            else:
                new_planes.append(jnp.where(take, x_sh, x))
        planes = new_planes
        alive = planes[0]
        d = planes[1]
        s *= 2

    # place survivors at stage flat [q, q+cnt); head rows reproduce
    # the previous block's partial tail chunk (carry from the other
    # slot's stage, still untouched thanks to double buffering)
    srow_i = lax.broadcasted_iota(jnp.int32, (RS, 128), 0)
    slane_i = lax.broadcasted_iota(jnp.int32, (RS, 128), 1)
    sflat = srow_i * 128 + slane_i

    prev_base8 = base8_ref[jnp.maximum(t - 1, 0)]
    carry_row = base8 - prev_base8       # within prev stage (RS rows)

    for i in range(P2):
        xs = jnp.concatenate(
            [planes[i],
             jnp.zeros((RS - RB, 128), jnp.uint32)], axis=0
        )
        y = _flat_shift(xs, -q, RS)      # y[f] = compacted[f - q]
        prev = _flat_shift(
            stage[1 - slot, i], carry_row * 128, RS
        )
        y = jnp.where(sflat < q, prev, y)
        stage[slot, i] = y

    @pl.when(t > 0)
    def _():
        # the previous step's out-DMA (lagged one step for overlap)
        # must land before this step's overlapping window starts
        pltpu.make_async_copy(
            stage.at[1 - slot, pl.ds(2, nplanes)],
            out_ref.at[:, pl.ds(prev_base8, RS), :],
            sem.at[1 - slot],
        ).wait()

    # only the value planes go to HBM: the alive/d planes (0-1) exist
    # for the shift network and the carry chain, and writing them
    # would be 2/(P+2) dead output bandwidth
    cp = pltpu.make_async_copy(
        stage.at[slot, pl.ds(2, nplanes)],
        out_ref.at[:, pl.ds(base8, RS), :],
        sem.at[slot],
    )
    cp.start()

    @pl.when(t == nt - 1)
    def _():
        cp.wait()


def plane_compact_stacked(stacked: jax.Array, mask: jax.Array,
                          pos: jax.Array, capacity: int,
                          block: int = 32768,
                          interpret: bool = False,
                          name: str = "compact_planes"):
    """Compact P u32 planes (stacked (P, n)) to ``capacity`` slots.

    mask: (n,) bool survivors; pos: (n,) int32 == cumsum(mask)-1.
    Returns (P, capacity); slots >= the survivor count are undefined.
    ``name`` is the kernel's name, which a profile reads.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, n = stacked.shape
    P2 = P + 2
    RB = block // 128
    RS = RB + 8
    n_pad = _round_up(max(n, 1), block)
    nblocks = n_pad // block

    keep = mask & (pos < capacity)
    alive = keep.astype(jnp.uint32)
    e_local = (
        jnp.arange(n, dtype=jnp.int32) % block
    )
    keep_i = alive.astype(jnp.int32)
    counts = jnp.sum(
        keep_i.reshape(nblocks, -1)
        if n == n_pad else
        jnp.concatenate(
            [keep_i, jnp.zeros((n_pad - n,), jnp.int32)]
        ).reshape(nblocks, -1),
        axis=1,
    )
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts, dtype=jnp.int32)]
    )                                               # (nblocks+1,)
    base8s = (offs[:-1] // 1024) * 8
    qs = offs[:-1] - base8s * 128
    # broadcast+reshape, NOT jnp.repeat: repeat of a traced vector can
    # lower to a TPU gather (~21 ns/element — catastrophic at 20M)
    offs_bcast = jnp.broadcast_to(
        offs[:-1, None], (nblocks, block)
    ).reshape(-1)
    pos_local = pos - offs_bcast[:n]
    ddisp = jnp.where(keep, e_local - pos_local, 0).astype(jnp.uint32)

    def pad(x):
        if n == n_pad:
            return x
        return jnp.concatenate(
            [x, jnp.zeros((n_pad - x.shape[0],), x.dtype)]
        )

    full = jnp.concatenate([
        pad(alive)[None, :], pad(ddisp)[None, :],
        jnp.concatenate(
            [stacked,
             jnp.zeros((P, n_pad - n), jnp.uint32)], axis=1
        ) if n != n_pad else stacked,
    ])                                              # (P2, n_pad)
    ins3d = full.reshape(P2, nblocks * RB, 128)

    out_rows = _round_up(capacity, 1024) // 128 + RS + 8
    vma = getattr(jax.typeof(ins3d), "vma", None)
    out_sds = (
        jax.ShapeDtypeStruct((P, out_rows, 128), jnp.uint32, vma=vma)
        if vma is not None else
        jax.ShapeDtypeStruct((P, out_rows, 128), jnp.uint32)
    )
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(
                _compact_kernel, block=block, nplanes=P
            ),
            name=name,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((P2, RB, 128), lambda t: (0, t, 0)),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=out_sds,
            scratch_shapes=[
                pltpu.VMEM((2, P2, RS, 128), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
        )(base8s, qs, ins3d)
    return out.reshape(P, -1)[:, :capacity]


def plane_stream_compact(mask, pos, cols, capacity: int,
                         block: int = 32768, interpret: bool = False,
                         name: str = "compact_planes"):
    """Drop-in for ops/compact_pallas.stream_compact: uint64 columns
    in, uint64 columns (length ``capacity``) out."""
    planes = []
    for c in cols:
        planes.extend(split_u64(c))
    stacked = jnp.stack(planes)
    outp = plane_compact_stacked(
        stacked, mask, pos.astype(jnp.int32), capacity,
        block=block, interpret=interpret, name=name,
    )
    return [
        merge_u64(outp[2 * i], outp[2 * i + 1])
        for i in range(len(cols))
    ]
