"""Pallas streaming compaction: ``out[pos[e]] = cols[e] where mask[e]``
as one sequential pass, replacing sorts whose only job is to move a
masked subset into a dense prefix.

This is the standalone primitive for the join core's two
order-preserving compactions (the run-record block and the
matched-build pack); the ops/join.py integration lands with the
matched-rank pipeline (the scan restructuring that makes the kernel
build path gap-free by construction). The XLA
formulations are a value-carrying sort (~150 ms at 20M rows — sorts
move values almost for free but the comparison network itself is the
cost) or a scatter (~12 ns per element). Compaction is neither a sort
nor random access: target positions ``pos = cumsum(mask) - 1`` are
NON-DECREASING, so each input block of B elements lands in one
contiguous ≤B-wide output window, and the whole operation is a
streaming merge of matmul-selected blocks:

- grid over INPUT blocks of ``B`` elements (plain BlockSpec tiling —
  input movement is fully sequential);
- in-VMEM, the block's elements are routed to their in-window slots by
  a one-hot MXU matmul (``values_block @ onehot^T`` — the same
  bit-exact 0/1-matmul selection as ops/expand_pallas.py), built from
  the block-local positions ``pos[e] - 128*floor(offset_i/128)``;
- the (ck, B+chunk) stage is DMA'd to HBM at the block's 128-aligned
  output offset. Consecutive windows OVERLAP (a window starts mid-128
  wherever the previous block's elements ended); the partial leading
  lane-chunk is reproduced from a persistent (ck, 128) carry scratch —
  grid iterations run sequentially on a TPU core, so the carry and the
  overlapping writes are ordered by construction;
- per-block output offsets (exclusive cumsum of per-block survivor
  counts, divided/remaindered by the 128-lane tile) are tiny host-side
  arrays prefetched through SMEM.

int64 columns ride as 8-bit bfloat16 chunks (expand_pallas._split_rows8
— bf16 holds 0..255 exactly, and one-pass bf16 matmuls beat ~6-pass
f32-HIGHEST even with 8/3 more chunk rows; the selected values come
back exact because every output slot sums exactly one nonzero
product). Elements whose position reaches ``capacity`` are
dropped (the caller sized the output; positions are monotone so the
kept set is a prefix). Output slots at and beyond the survivor count
are UNDEFINED — callers mask them (the join's validity contract).
"""

from __future__ import annotations

import functools

import jax

import jax.numpy as jnp

from distributed_join_tpu.ops.expand_pallas import (
    _default_block,
    _default_chunk,
    _merge_rows8,
    _round_up,
    _split_rows8,
)


def _compact_kernel(base_ref, q_ref, pm_ref, v_ref, out_hbm, stage,
                    pend, sem, *, block: int, chunk: int, ck: int,
                    w: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = block
    i = pl.program_id(0)
    base = base_ref[i]           # floor(out_offset / 128)
    nxt = base_ref[i + 1]
    q = q_ref[i]                 # out_offset - 128*base, in [0, 128)
    posb = pm_ref[0:1, :]        # (1, b) global target positions
    maskb = pm_ref[1:2, :]       # (1, b) 0/1 survivor mask
    spos = jnp.where(maskb != 0, posb - base * 128, -1)
    # ONE (w, b) one-hot and ONE matmul per block: a chunked loop of
    # (ck, chunk) matmuls measured 5x slower — 175K tiny MXU
    # dispatches of per-call overhead, not FLOPs, dominated.
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (w, b), 0)
    oh = (spos == iota_w).astype(jnp.bfloat16)           # (w, b)
    # bf16 x bf16 -> f32 accumulate at the MXU's native one-pass rate
    # (vs ~6 emulation passes for f32 Precision.HIGHEST); exact because
    # the 8-bit chunk values and the 0/1 one-hot are both
    # bf16-representable and each output slot sums ONE nonzero term.
    stage[...] = jax.lax.dot_general(
        v_ref[...], oh,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # Reproduce the previous blocks' elements living in this window's
    # partial leading 128-lane chunk (the write below would otherwise
    # zero them). Stale carry lanes at and beyond q are masked off; at
    # i == 0, q == 0 masks the (uninitialized) whole carry.
    lane = jax.lax.broadcasted_iota(jnp.int32, (ck, 128), 1)
    stage[:, 0:128] = stage[:, 0:128] + jnp.where(
        lane < q, pend[...], 0.0
    )
    dma = pltpu.make_async_copy(
        stage, out_hbm.at[:, pl.ds(base * 128, w)], sem
    )
    dma.start()
    # Next block's carry: the (possibly partial) 128-chunk its window
    # starts inside — a 128-aligned in-VMEM slice, safe to read while
    # the DMA streams the same scratch out.
    m = nxt - base
    pend[...] = stage[:, pl.ds(m * 128, 128)]
    dma.wait()


def stream_compact(mask: jax.Array, pos: jax.Array, cols, capacity: int,
                   block: int | None = None, interpret: bool = False,
                   name: str = "compact_mxu"):
    """Order-preserving masked compaction of k uint64 columns.

    mask: (n,) bool — survivors.
    pos:  (n,) int32 == cumsum(mask) - 1 (the caller usually has this
          scan already); only read where mask is set.
    cols: k 1-D uint64 arrays of length n.
    capacity: static output length; survivors with pos >= capacity are
          dropped (a suffix, by monotonicity).

    Returns k uint64 arrays of length ``capacity``; slots >= the
    survivor count are undefined. ``name`` is the kernel's name, which
    a profile reads.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if block is None:
        block = _default_block()
    chunk = _default_chunk(block)
    w = block + max(chunk, 128)
    assert w % chunk == 0 and w % 128 == 0, (w, chunk)

    k = len(cols)
    n = mask.shape[0]
    n_pad = _round_up(max(n, 1), block)
    nblocks = n_pad // block

    keep = mask & (pos < capacity)
    keep_i = keep.astype(jnp.int32)
    rows = _split_rows8(cols)
    ck = _round_up(len(rows), 16)   # bf16 sublane tile
    if n_pad > n:
        pad = n_pad - n
        keep_i = jnp.concatenate([keep_i, jnp.zeros((pad,), jnp.int32)])
        pos = jnp.concatenate([pos, jnp.zeros((pad,), pos.dtype)])
        rows = [
            jnp.concatenate([r, jnp.zeros((pad,), jnp.bfloat16)])
            for r in rows
        ]
    vT = jnp.stack(
        rows + [jnp.zeros_like(rows[0])] * (ck - len(rows)), axis=0
    )                                                    # (ck, n_pad)
    pm = jnp.stack([pos.astype(jnp.int32), keep_i], axis=0)  # (2, n_pad)

    counts = keep_i.reshape(nblocks, block).sum(axis=1)
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)]
    )                                                    # (nblocks+1,)
    base = offs // 128
    q = offs[:-1] - base[:-1] * 128

    out_pad = _round_up(capacity, 128) + w
    vma = getattr(jax.typeof(vT), "vma", None)
    out_shape = (
        jax.ShapeDtypeStruct((ck, out_pad), jnp.float32, vma=vma)
        if vma is not None
        else jax.ShapeDtypeStruct((ck, out_pad), jnp.float32)
    )
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(
                _compact_kernel, block=block, chunk=chunk, ck=ck, w=w
            ),
            name=name,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((2, block), lambda i: (0, i)),
                pl.BlockSpec((ck, block), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((ck, w), jnp.float32),
                pltpu.VMEM((ck, 128), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
            out_shape=out_shape,
            interpret=interpret,
        )(base, q, pm, vT)
    return [c[:capacity] for c in _merge_rows8(out, k)]


def stream_compact_reference(mask, pos, cols, capacity: int):
    """XLA reference (one int32-indexed scatter per column), for tests
    and as the CPU fallback."""
    idx = jnp.where(mask, pos, capacity)  # capacity == dropped
    outs = []
    for c in cols:
        # No unique_indices hint: every dropped element maps to the
        # same out-of-bounds index `capacity`, so the indices are NOT
        # unique and claiming so would be undefined behavior.
        outs.append(
            jnp.zeros((capacity,), c.dtype).at[idx].set(c, mode="drop")
        )
    return outs
