"""Pallas expand-gather: the join's output expansion AND build-side
materialization as one streaming kernel.

The join core (ops/join.py) turns compact run records into output rows
with scatter + cummax + a packed row-gather — measured at ~300 ms of a
~900 ms honest 10Mx10M join (docs/ROOFLINE.md). All three are random-
access primitives that XLA executes at ~10-20 ns/element. But the
access pattern is NOT random: record start-slots ``S`` are sorted, so
the records covering one block of output rows are a CONTIGUOUS window,
and expansion is a streaming merge. This kernel exploits that:

- grid over output blocks of ``B`` rows; a scalar-prefetched per-block
  record offset (one tiny searchsorted outside) selects a 2B-record
  window — since every record covers at least one output row, <= B+1
  records cover a block, and a down-aligned 2B window always contains
  them;
- the window is DMA'd into VMEM at a dynamic offset (block-aligned so
  Mosaic can prove tiling divisibility); record values live TRANSPOSED
  as (lanes, m) so the windowed dimension is the 128-tiled one;
- in-VMEM, chunked comparisons of output positions against the
  window's start-slots isolate each row's covering record as a one-hot
  column (cmp minus left-shifted cmp);
- the "gather" is then ``values_window @ onehot^T`` on the MXU — the
  TPU-native trick for data-dependent selection: a one-hot f32 matmul
  copies exactly one element per output, bit-exactly, because every
  partial product is 0 or the element itself.

int64 value columns ride as 22-bit f32 chunks (f32 holds integers
<= 2^24 exactly; split/recombined OUTSIDE the kernel with cheap
elementwise ops), so arbitrary 64-bit payloads survive the float
matmul without loss.

Build-side materialization (round 2, second pass): the join's last
random access was the build-rank output gather (~180 ms at 10Mx10M —
one XLA gather of the key-sorted build pack at
``rank = lo[rec] + (j - S[rec])``). Those ranks are NOT random either.
Records tile the output contiguously (``S[r+1] = S[r] + cnt[r]``) and
``lo`` is non-decreasing over records (it is a prefix count of build
rows in merged key order), which bounds the ranks any B-row output
block can touch by TWO windows over the build pack:

- the block's STRADDLING record r0 (the unique record whose run covers
  the block start) contributes the contiguous range
  ``[lo[r0] + (i*B - S[r0]), +B)``;
- every later record r covering the block has ``lo[r] >= lo[r0+1]``,
  and — WHEN every build key between two in-block records' keys also
  has probe matches — the middle records' runs lie inside the block so
  their total length bounds the increase of ``lo`` across them by B,
  pinning all non-straddler ranks inside ``[lo[r0+1], lo[r0+1] + 2B)``.

The parenthetical is a DATA property, not a theorem: build keys with
zero probe matches advance ``lo`` without producing records, so a gap
of unmatched builds between two matched keys whose output rows share a
block pushes later ranks past window 2. The join's kernel pipeline
(ops/join.py _join_kernel_path) therefore feeds MATCHED-build ranks
(``lo_m`` from ops/scan_pallas.py, over the matched-dense pack from
ops/compact_pallas.py): unmatched keys never enter the pack, ``lo_m``
advances between records by exactly the previous record's run length,
and the bound holds by construction. :func:`build_windows_ok` still
checks the exact per-block condition OUTSIDE the kernel as
belt-and-braces — ``lo`` is non-decreasing over records, so the
largest in-block ``lo`` is just ``lo[r0[i+1]]`` and the check is
O(out/B) gathers — and the caller `lax.cond`s to an exact XLA-gather
fallback if it ever fails.

So the build-mode kernel (_expand_kernel_b8) DMAs two build windows
(w1w/w2w wide per _window_widths, offsets 128-aligned outside) and
selects each row's build values with a second one-hot matmul against
``rank``, computed in-kernel from two f32 aux rows (``lo - S`` and
``S``) that ride the record window; rows choose window 1 iff their run
started at or before the block start (``S_j <= i*B``), which makes the
two selections disjoint and exact. Its value matmuls run on 8-bit
bfloat16 chunk rows (_split_rows8 — one native MXU pass instead of
f32-HIGHEST's ~6 emulation passes), and its record window is
128-aligned and only ``w1w`` wide (the f32 S aux row replaces the
non-build kernel's 1-D int32 S array, whose DMA tiling forces
1024-aligned offsets and hence 2B windows).

Everything the kernels touch moves sequentially (record windows,
build windows, output blocks); the join's output path has no
per-element random access left. ``expand_gather_reference`` is the XLA
formulation used for correctness tests and as a CPU fallback.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax

import jax.numpy as jnp
from jax import lax

# f32 holds integers up to 2^24 exactly. Round 4 made the build-mode
# kernel's rank arithmetic BLOCK-RELATIVE (hi/lo-split i32 aux rows),
# so the fused build path no longer has a 2^24 limit; the constant
# remains for the NON-build kernel's S-lane choice (s_u64_lane).
_F32_EXACT = 1 << 24


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _default_block() -> int:
    import os

    return int(os.environ.get("DJTPU_PALLAS_BLOCK", "1024"))


def _default_chunk(block: int) -> int:
    """Shared by the kernel, the compaction kernel, and
    build_windows_ok — window geometry and its validity check MUST
    parse the same knobs identically or the checker would validate a
    different geometry than the kernel DMAs."""
    import os

    chunk = min(int(os.environ.get("DJTPU_PALLAS_CHUNK", "256")), block)
    assert block % chunk == 0, (block, chunk)
    # 128-compatibility keeps every _window_widths result an exact
    # multiple of chunk (the widths round to lcm(chunk, 128)); e.g.
    # chunk=96 would make the window loops slice past the VMEM buffers.
    assert chunk % 128 == 0 or 128 % chunk == 0, chunk
    return chunk


def _window_widths(block: int, chunk: int,
                   window: int | None = None):
    """Build-window VMEM widths, rounded so the chunked compare loop
    and the 128-lane tile divide them exactly.

    Window 2's bound is B (not the naive 2B): middle records' runs
    tile the block, so ``lo[r] - lo[r0+1]`` across them is at most the
    coverage consumed before the last record r1 starts, and r1's own
    in-block rank extent is at most the coverage that remains —
    ``(lo[r1] - lo[r0+1]) + extent(r1) <= (S[r1] - blockstart) +
    (blockend - S[r1]) = B``. build_windows_ok checks exactly this
    quantity per block.

    ``window`` (default: ``block``) DECOUPLES the build-window width
    from the output block size (ROADMAP item 2a; ROOFLINE §8):
    ``results/build_window_blocks_r4.json`` showed that widening the
    windows by growing ``block`` scales every VMEM buffer in the
    kernel and hits the 16M scoped-vmem wall — a wider ``window``
    grows ONLY the two build windows (and relaxes exactly the
    ``build_windows_ok`` bound that forces the gather fallback on
    gap-heavy data), while the record window stays block-sized
    (<= B+1 records ever cover a block, whatever the build windows
    hold)."""
    lane = max(chunk, 128)
    w1w = _round_up((window or block) + 128, lane)
    return w1w, w1w


def build_windows_ok(S: jax.Array, lo: jax.Array, out_capacity: int,
                     block: int | None = None,
                     window: int | None = None) -> jax.Array:
    """Exact per-run-of-blocks validity of the two-window build scheme.

    Window 2 of output block i covers ranks
    ``[align128(lo[r0[i]+1]), +w2w)``. The largest rank any
    non-straddler row in the block can need is EXACTLY
    ``lo[r1] + (blockend - S[r1]) - 1`` with ``r1 = r0[i+1]``: ``lo``
    is non-decreasing over records, middle records' maxima
    ``lo[r] + cnt[r] - 1 = lo[r+1] - 1 < lo[r1]``, and r1's in-block
    extent is capped by the block end. On matched-rank data this is
    always <= ``lo[r0+1] + B - 1`` (_window_widths); build keys with
    zero probe matches advance ``lo`` without emitting records and
    break it — a DATA property the kernel cannot bound a priori.
    Returns a traced bool: True iff every block's needs fit, i.e. the
    kernel path is exact; ops/join.py conds to the XLA gather
    otherwise.
    """
    if block is None:
        block = _default_block()
    _, w2w = _window_widths(block, _default_chunk(block),
                            window=window)
    m = S.shape[0]
    out_pad = _round_up(out_capacity, block)
    nblk = out_pad // block
    starts = jnp.arange(nblk + 1, dtype=jnp.int32) * block
    r0 = jnp.maximum(
        jnp.searchsorted(S, starts, side="right").astype(jnp.int32) - 1,
        0,
    )
    lo_i = lo.astype(jnp.int32)
    nxt = jnp.minimum(r0[:-1] + 1, m - 1)
    w2 = lo_i[nxt]
    r1 = r0[1:]
    # The final block's real slots end at out_capacity, not at its
    # padded end starts[nblk]; the padded tail holds no records, so
    # using the raw padded end would count phantom ranks into hi and
    # spuriously force the exact-but-slower XLA fallback.
    ends = jnp.minimum(starts[1:], jnp.int32(out_capacity))
    hi = lo_i[r1] + (ends - S[r1])  # > any non-straddler rank
    # Two masks against spurious flags on blocks without window-2
    # reads: (a) no real record after the straddler (S[r0+1] is a
    # sentinel and lo is zeroed padding there — every
    # out_capacity > total run would otherwise fall back); (b) the
    # straddler covers the whole block (r1 == r0, and a giant run's
    # blockend - S[r1] would read as a huge gap).
    has_w2 = (S[nxt] != jnp.int32(2**31 - 1)) & (S[r1] > starts[:-1])
    return ~jnp.any(has_w2 & (hi > w2 + (w2w - 128)))


def _split_rows(cols_u64: Sequence[jax.Array]):
    """k 1-D uint64 columns -> list of 3k 1-D f32 rows of exact 22-bit
    chunks (c0s, then c1s, then c2s)."""
    rows = []
    for shift, mask in ((0, 0x3FFFFF), (22, 0x3FFFFF), (44, 0xFFFFF)):
        for c in cols_u64:
            rows.append(
                ((c >> jnp.uint64(shift)) & jnp.uint64(mask)).astype(
                    jnp.float32
                )
            )
    return rows


def _merge_rows(rows_f32: jax.Array, k: int):
    """(3k, n) f32 -> list of k 1-D uint64 columns."""
    out = []
    for i in range(k):
        c0 = rows_f32[i].astype(jnp.uint64)
        c1 = rows_f32[k + i].astype(jnp.uint64)
        c2 = rows_f32[2 * k + i].astype(jnp.uint64)
        out.append(c0 | (c1 << jnp.uint64(22)) | (c2 << jnp.uint64(44)))
    return out


def _split_rows8(cols_u64):
    """k 1-D uint64 columns -> 8k 1-D bfloat16 rows of exact 8-bit
    chunks (byte b of every column grouped together). bf16's 8-bit
    mantissa holds 0..255 exactly, which lets the one-hot matmuls run
    at the MXU's native bf16 rate (one pass) instead of
    Precision.HIGHEST's ~6-pass f32 emulation."""
    rows = []
    for shift in range(0, 64, 8):
        for c in cols_u64:
            rows.append(
                ((c >> jnp.uint64(shift)) & jnp.uint64(0xFF)).astype(
                    jnp.bfloat16
                )
            )
    return rows


def _merge_rows8(rows_f32: jax.Array, k: int):
    """(8k, n) f32 (byte chunks, post-matmul) -> k uint64 columns."""
    out = []
    for i in range(k):
        acc = jnp.zeros(rows_f32.shape[1:], jnp.uint64)
        for b in range(8):
            acc = acc | (
                rows_f32[b * k + i].astype(jnp.uint64)
                << jnp.uint64(8 * b)
            )
        out.append(acc)
    return out


def _expand_kernel(r0b_ref, ib_ref, s_hbm, v_hbm, out_ref, s_vmem,
                   v_vmem, sem_s, sem_v, *, block: int, chunk: int,
                   ck: int, srow: int):
    """Per-output-block body, record expansion only (the build path
    runs _expand_kernel_b8); see module docstring for the scheme.

    Mosaic constraints shaping this code:
    - dynamic DMA offsets must be PROVABLY divisible by the tiling
      (1024 for 1-D int32): the window start is down-aligned to a
      block multiple and passed pre-divided, so the prover sees
      ``x * block``;
    - the windowed dimension must be the 128-tiled LANE dimension:
      values arrive transposed as (lane_rows, m);
    - a full (block, 2*block) comparison matrix would blow VMEM at
      block=1024 (8 MB per temporary), so the window is processed in
      ``chunk``-wide slices, each one MXU matmul into the accumulator.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = block
    i = pl.program_id(0)
    w = r0b_ref[i] * b  # provably block-aligned
    dma_s = pltpu.make_async_copy(s_hbm.at[pl.ds(w, 2 * b)], s_vmem, sem_s)
    dma_v = pltpu.make_async_copy(
        v_hbm.at[:, pl.ds(w, 2 * b)], v_vmem, sem_v
    )
    dma_s.start()
    dma_v.start()
    dma_s.wait()
    dma_v.wait()

    # Global output position of each row in this block, as a COLUMN
    # (broadcasted_iota emits 2-D directly; Mosaic cannot reshape a
    # 1-D vector into the sublane dimension). The absolute block start
    # comes from SMEM, not i*b: under output tiling (ADVICE r4 — the
    # monolithic (ck, out_pad) f32 buffer OOMs at spec-scale
    # capacities, same class the build path fixed in round 4) this
    # invocation covers blocks [tile_start, ...) of the global output.
    j = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0) + ib_ref[i]
    s_win = s_vmem[...]
    acc = jnp.zeros((ck, b), jnp.float32)
    for t in range(0, 2 * b, chunk):
        # Record r covers j iff S[r] <= j and S[r+1] > j; the element
        # past the window counts as "not started", which is exact (the
        # last covering record sits strictly inside the window).
        sl = s_win[t : t + chunk]
        cmp_a = (sl[None, :] <= j).astype(jnp.float32)      # (b, chunk)
        if t + chunk < 2 * b:
            sl_b = s_win[t + 1 : t + chunk + 1]
            cmp_b = (sl_b[None, :] <= j).astype(jnp.float32)
        else:
            sl_b = s_win[t + 1 : t + chunk]
            cmp_b = jnp.pad(
                (sl_b[None, :] <= j).astype(jnp.float32),
                ((0, 0), (0, 1)),
            )
        onehot = cmp_a - cmp_b                              # {0,1}
        # (ck, chunk) x (b, chunk) contracting chunk -> (ck, b); the
        # transposed contraction avoids materializing onehot^T.
        # Precision.HIGHEST: the default lets the MXU run this at bf16
        # (8-bit mantissa), silently truncating the 22-bit chunks.
        acc = acc + jax.lax.dot_general(
            v_vmem[:, t : t + chunk], onehot,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    out_ref[...] = acc


def _expand_kernel_b8(*refs, block: int, chunk: int, ck8: int,
                      ckb8: int, wr: int, w1w: int, w2w: int):
    """Build-mode kernel, v3: 8-bit bf16 chunk rows for every value
    matmul (one MXU pass instead of ~6 f32-HIGHEST emulation passes),
    record windows 128-aligned (width b+chunk-slack instead of 2b — the
    v2 1-D int32 S array forced 1024-aligned offsets; here the record
    start-slots ride an f32 aux row, exact below 2^24 which the build
    path already guarantees), and no aux outputs (the caller's cond
    interface takes placeholders — rank and start_b are only consumed
    in-kernel)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (r0a_ref, w1a_ref, w2a_ref, ib_ref, v8_hbm, aux_hbm, bv_hbm,
     out_ref, v8_vmem, aux_vmem, b1_vmem, b2_vmem, sem_v, sem_a,
     sem_b1, sem_b2) = refs
    b = block
    i = pl.program_id(0)
    wro = r0a_ref[i] * 128  # 128-aligned record-window offset
    dma_v = pltpu.make_async_copy(
        v8_hbm.at[:, pl.ds(wro, wr)], v8_vmem, sem_v
    )
    dma_a = pltpu.make_async_copy(
        aux_hbm.at[:, pl.ds(wro, wr)], aux_vmem, sem_a
    )
    o1 = w1a_ref[i] * 128
    o2 = w2a_ref[i] * 128
    dma_b1 = pltpu.make_async_copy(
        bv_hbm.at[:, pl.ds(o1, w1w)], b1_vmem, sem_b1
    )
    dma_b2 = pltpu.make_async_copy(
        bv_hbm.at[:, pl.ds(o2, w2w)], b2_vmem, sem_b2
    )
    dma_v.start()
    dma_a.start()
    dma_b1.start()
    dma_b2.start()
    dma_v.wait()
    dma_a.wait()

    # BLOCK-RELATIVE arithmetic throughout (round 4): all f32-lane
    # values are clipped relative offsets bounded by +-2^20, so nb and
    # out_capacity past 2^24 stay exact. CL must exceed every window
    # width and the block, and survive f32 exactly.
    CL = jnp.int32(1 << 20)
    # Absolute output-block start from SMEM (NOT i*b): under output
    # tiling this invocation covers blocks [tile_start, ...) of the
    # global output, and everything else in the kernel is already
    # block-relative.
    ib = ib_ref[i]
    jloc = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    jlocf = jloc.astype(jnp.float32)
    # 1-D row extractions: Mosaic can sublane-broadcast a slice of a
    # 1-D vector but rejects the same broadcast from a 2-D row slice
    # ("Invalid input layout" on vector.broadcast).
    c_hi, c_lo = aux_vmem[0], aux_vmem[1]    # (wr,) f32 lo - S halves
    s_hi, s_lo = aux_vmem[2], aux_vmem[3]    # (wr,) f32 S halves

    def _dec(hi_row, lo_row, t0, t1):
        return (
            hi_row[t0:t1].astype(jnp.int32) * jnp.int32(65536)
            + lo_row[t0:t1].astype(jnp.int32)
        )

    acc = jnp.zeros((ck8, b), jnp.float32)
    c1_col = jnp.zeros((b, 1), jnp.float32)
    c2_col = jnp.zeros((b, 1), jnp.float32)
    srel_col = jnp.zeros((b, 1), jnp.float32)
    d1 = ib - o1
    d2 = ib - o2
    for t in range(0, wr, chunk):
        s_rel = jnp.clip(
            _dec(s_hi, s_lo, t, t + chunk) - ib, -CL, CL
        ).astype(jnp.float32)
        cmp_a = (s_rel[None, :] <= jlocf).astype(jnp.float32)
        if t + chunk < wr:
            s_rel_b = jnp.clip(
                _dec(s_hi, s_lo, t + 1, t + chunk + 1) - ib, -CL, CL
            ).astype(jnp.float32)
            cmp_b = (s_rel_b[None, :] <= jlocf).astype(jnp.float32)
        else:
            s_rel_b = jnp.clip(
                _dec(s_hi, s_lo, t + 1, t + chunk) - ib, -CL, CL
            ).astype(jnp.float32)
            cmp_b = jnp.pad(
                (s_rel_b[None, :] <= jlocf).astype(jnp.float32),
                ((0, 0), (0, 1)),
            )
        onehot = cmp_a - cmp_b
        acc = acc + jax.lax.dot_general(
            v8_vmem[:, t : t + chunk], onehot.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        c = _dec(c_hi, c_lo, t, t + chunk)
        # c + ib - o{1,2} == (rank of this record's run at the block
        # start) - window base: in (-b, window) for every record the
        # onehot can select, so the clip never distorts a selected
        # value.
        c1v = jnp.clip(c + d1, -CL, CL).astype(jnp.float32)
        c2v = jnp.clip(c + d2, -CL, CL).astype(jnp.float32)
        c1_col = c1_col + jnp.sum(
            onehot * c1v[None, :], axis=1, keepdims=True)
        c2_col = c2_col + jnp.sum(
            onehot * c2v[None, :], axis=1, keepdims=True)
        srel_col = srel_col + jnp.sum(
            onehot * s_rel[None, :], axis=1, keepdims=True)
    out_ref[0:ck8, :] = acc

    dma_b1.wait()
    dma_b2.wait()
    # rank - o1 == jloc + (lo - S + ib - o1); window choice: the run
    # started at or before the block start iff S - ib <= 0.
    is_w1 = srel_col.astype(jnp.int32) <= 0
    local1 = jloc + c1_col.astype(jnp.int32)
    local2 = jloc + c2_col.astype(jnp.int32)
    accb = jnp.zeros((ckb8, b), jnp.float32)
    iota_ch = jax.lax.broadcasted_iota(jnp.int32, (b, chunk), 1)
    # f32 where + cast: producing bf16 straight from the i1 mask needs
    # an unsupported (8,128)->(16,128) replicating relayout in Mosaic.
    for t in range(0, w1w, chunk):
        oh = jnp.where(
            is_w1 & (local1 == t + iota_ch), 1.0, 0.0
        ).astype(jnp.bfloat16)
        accb = accb + jax.lax.dot_general(
            b1_vmem[:, t : t + chunk], oh,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    for t in range(0, w2w, chunk):
        oh = jnp.where(
            (~is_w1) & (local2 == t + iota_ch), 1.0, 0.0
        ).astype(jnp.bfloat16)
        accb = accb + jax.lax.dot_general(
            b2_vmem[:, t : t + chunk], oh,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    out_ref[ck8 : ck8 + ckb8, :] = accb


# Per-tile budget for the build-mode kernel's f32 chunk-row output
# (~32 B per u64 lane per output row — 4x the value width). One
# monolithic buffer OOM'd HBM at a 60M-row output capacity
# (16.1G/15.75G, round 4); tiling the output bounds the footprint at
# any capacity.
_FUSED_TILE_BYTES = 2 << 30


def _tiled_output_launch(n_blocks, block, tile_bytes, launch, merge):
    """Shared output-tiling driver for both expand wrappers: run
    ``launch(q, qb, ib_arr) -> raw f32 (rows, qb*block)`` once per
    HBM-budget tile of output blocks, ``merge(out) -> pytree of 1-D
    arrays`` per tile, and concatenate the merged pieces.

    Two invariants live ONLY here (review r5 — they were hand-copied
    in both wrappers before):
    - tile sizing: ceil-divide ``tile_bytes`` into the
      ``_FUSED_TILE_BYTES`` budget, never more tiles than blocks;
    - serialization: each tile's absolute block starts ``ib_arr``
      carry a ``dep`` tied to the previous tile's output through an
      optimization_barrier — a plain ``x * 0`` would be algebraically
      folded to a constant, severing the ordering that lets buffer
      assignment reuse the f32 space across tiles.
    """
    n_tiles = min(max(1, -(-tile_bytes // _FUSED_TILE_BYTES)), n_blocks)
    tile_blocks = -(-n_blocks // n_tiles)
    pieces = []
    dep = jnp.int32(0)
    for q in range(0, n_blocks, tile_blocks):
        qb = min(tile_blocks, n_blocks - q)
        ib_arr = (
            jnp.arange(qb, dtype=jnp.int32) + jnp.int32(q)
        ) * block + dep
        out = launch(q, qb, ib_arr)
        pieces.append(merge(out))
        dep = lax.optimization_barrier(
            (jnp.int32(0), out[0, 0])
        )[0]
    if len(pieces) == 1:
        return pieces[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs), *pieces
    )


def _expand_gather_b8(S, cols, out_capacity, block, interpret, lo,
                      build_cols, window=None, name_prefix=""):
    """v3 build-mode wrapper; see _expand_kernel_b8."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk = _default_chunk(block)
    w1w, w2w = _window_widths(block, chunk, window=window)
    # The kernel clips every block-relative quantity to +-CL = 2^20
    # (see _expand_kernel_b8).  Those quantities are bounded by a few
    # blocks plus one window width; `block` is user-configurable
    # (DJTPU_PALLAS_BLOCK / --kernel-block / cfg.block), so an
    # oversized block must fail loudly here, not corrupt ranks via a
    # distorting clip (ADVICE r4).
    if not 3 * block + max(w1w, w2w) < (1 << 20):
        raise ValueError(
            f"kernel block {block} too large: 3*block + window "
            f"({3 * block + max(w1w, w2w)}) must stay below the "
            f"2^20 block-relative clip bound"
        )
    # Record window: BLOCK-sized regardless of the build-window width
    # (<= B+1 records ever cover a B-row block) — b+128 coverage,
    # 128-aligned, chunk-mult. This is the decoupling: a wider
    # `window` grows only the b1/b2 build windows below.
    wr = _window_widths(block, chunk)[0]
    k = len(cols)
    kb = len(build_cols)
    m = S.shape[0]

    rows8 = _split_rows8(cols)
    ck8 = _round_up(len(rows8), 16)
    is_real = S != jnp.int32(2**31 - 1)
    # aux rows carry the i32 quantities (lo - S) and S split into
    # EXACT hi/lo 16-bit halves riding f32 lanes (hi arithmetic-
    # shifted keeps the sign; v == hi*65536 + (v & 0xFFFF) for any
    # two's-complement i32). The kernel reconstructs in i32 and works
    # BLOCK-RELATIVE, so no absolute rank/start ever needs f32
    # exactness — this is what lifts the old 2^24 limit on nb and
    # out_capacity (round 4; the sentinel S = 2^31-1 reconstructs
    # exactly and clips to "never covers").
    contrib_i = jnp.where(is_real, lo - S, 0)
    s_i = jnp.where(is_real, S, jnp.int32(2**31 - 1))

    def _hi(v):
        return lax.shift_right_arithmetic(
            v, jnp.int32(16)).astype(jnp.float32)

    def _lo16(v):
        return (v & jnp.int32(0xFFFF)).astype(jnp.float32)

    aux = [_hi(contrib_i), _lo16(contrib_i), _hi(s_i), _lo16(s_i)]
    out_pad = _round_up(out_capacity, block)
    pad_cols = out_pad + wr + 128 - m
    if pad_cols > 0:
        S = jnp.concatenate(
            [S, jnp.full((pad_cols,), 2**31 - 1, jnp.int32)]
        )
        rows8 = [
            jnp.concatenate([r, jnp.zeros((pad_cols,), jnp.bfloat16)])
            for r in rows8
        ]
        sent_hi = float((2**31 - 1) >> 16)
        sent_lo = float((2**31 - 1) & 0xFFFF)
        aux = [
            jnp.concatenate(
                [aux[0], jnp.zeros((pad_cols,), jnp.float32)]
            ),
            jnp.concatenate(
                [aux[1], jnp.zeros((pad_cols,), jnp.float32)]
            ),
            jnp.concatenate(
                [aux[2], jnp.full((pad_cols,), sent_hi, jnp.float32)]
            ),
            jnp.concatenate(
                [aux[3], jnp.full((pad_cols,), sent_lo, jnp.float32)]
            ),
        ]
    v8T = jnp.stack(
        rows8 + [jnp.zeros_like(rows8[0])] * (ck8 - len(rows8)), axis=0
    )
    auxT = jnp.stack(
        aux + [jnp.zeros_like(aux[0])] * 4, axis=0
    )                                            # (8, m_pad) f32

    starts = jnp.arange(out_pad // block, dtype=jnp.int32) * block
    r0 = jnp.maximum(
        jnp.searchsorted(S, starts, side="right").astype(jnp.int32) - 1,
        0,
    )
    r0a = r0 // 128

    brows8 = _split_rows8(build_cols)
    ckb8 = _round_up(len(brows8), 16)
    nb = build_cols[0].shape[0]
    nb_pad = _round_up(max(nb, 1), 128) + w2w
    bpad = nb_pad - nb
    brows8 = [
        jnp.concatenate([r, jnp.zeros((bpad,), jnp.bfloat16)])
        for r in brows8
    ]
    bv8T = jnp.stack(
        brows8 + [jnp.zeros_like(brows8[0])] * (ckb8 - len(brows8)),
        axis=0,
    )
    omax = _round_up(max(nb, 1), 128) // 128
    lo_pad = jnp.concatenate(
        [lo, jnp.zeros((max(S.shape[0] - lo.shape[0], 0),), lo.dtype)]
    )
    s_r0 = jnp.where(S[r0] == 2**31 - 1, starts, S[r0])
    w1 = lo_pad[r0] + (starts - s_r0)
    w1a = jnp.clip(w1, 0, omax * 128) // 128
    w2 = lo_pad[jnp.minimum(r0 + 1, S.shape[0] - 1)]
    w2a = jnp.clip(w2, 0, omax * 128) // 128

    vma = getattr(jax.typeof(v8T), "vma", None)

    # Output TILING (round 4): the f32 chunk-row output costs ~32 B
    # per u64 lane per output row; at spec-scale capacities one
    # monolithic buffer exceeds HBM (fused_build_hbm_bytes). The
    # kernel is block-relative with absolute block starts from SMEM,
    # so the SAME compiled kernel covers any output range — run it
    # per tile and concatenate the merged u64 pieces
    # (_tiled_output_launch owns the tile sizing + serialization).
    def _launch(q, qb, ib_arr):
        sl = slice(q, q + qb)
        out_shape = (
            jax.ShapeDtypeStruct((ck8 + ckb8, qb * block),
                                 jnp.float32, vma=vma)
            if vma is not None
            else jax.ShapeDtypeStruct((ck8 + ckb8, qb * block),
                                      jnp.float32)
        )
        # x64 scoped off around the pallas_call ONLY: Mosaic fails to
        # legalize with global x64, but the u64 merge must see real
        # 64-bit types or it silently truncates to u32.
        with jax.enable_x64(False):
            return pl.pallas_call(
                functools.partial(
                    _expand_kernel_b8, block=block, chunk=chunk,
                    ck8=ck8, ckb8=ckb8, wr=wr, w1w=w1w, w2w=w2w,
                ),
                name=f"{name_prefix}expand_b8",
                grid=(qb,),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((ck8 + ckb8, block),
                                       lambda i: (0, i)),
                scratch_shapes=[
                    pltpu.VMEM((ck8, wr), jnp.bfloat16),
                    pltpu.VMEM((8, wr), jnp.float32),
                    pltpu.VMEM((ckb8, w1w), jnp.bfloat16),
                    pltpu.VMEM((ckb8, w2w), jnp.bfloat16),
                    pltpu.SemaphoreType.DMA(()),
                    pltpu.SemaphoreType.DMA(()),
                    pltpu.SemaphoreType.DMA(()),
                    pltpu.SemaphoreType.DMA(()),
                ],
                out_shape=out_shape,
                interpret=interpret,
            )(r0a[sl], w1a[sl], w2a[sl], ib_arr, v8T, auxT, bv8T)

    rec_full, build_full = _tiled_output_launch(
        out_pad // block, block, (ck8 + ckb8) * 4 * out_pad, _launch,
        lambda out: (_merge_rows8(out, k), _merge_rows8(out[ck8:], kb)),
    )
    rec_outs = [c[:out_capacity] for c in rec_full]
    build_outs = [c[:out_capacity] for c in build_full]
    # start_b/rank placeholders (consumed in-kernel only); derived from
    # S so they carry the same vma as the cond's other branch under
    # shard_map.
    zero = S[:out_capacity] * 0
    return rec_outs, zero, zero, build_outs


def expand_gather(S: jax.Array, cols: Sequence[jax.Array],
                  out_capacity: int, block: int | None = None,
                  interpret: bool = False,
                  lo: Optional[jax.Array] = None,
                  build_cols: Optional[Sequence[jax.Array]] = None,
                  window: int | None = None, name_prefix: str = ""):
    """For each output slot j in [0, out_capacity): find the covering
    record r = max{r : S[r] <= j} and return each column's value at r,
    plus the run-start slot ``start_b[j] = S[r]``.

    S: (m,) int32, sorted ascending, unique among real records, with
       INT32_MAX sentinels after them; S[0] == 0 whenever any real
       record exists (the first record starts at slot 0).
    cols: k 1-D uint64 arrays of length m.

    With ``lo`` ((m,) int32, the build rank of each record's run start,
    non-decreasing over real records) and ``build_cols`` (kb 1-D uint64
    arrays over the key-sorted build pack), the kernel also
    materializes each output row's build values at
    ``rank = lo[r] + (j - S[r])`` via the two-window scheme (module
    docstring).

    Returns ``(rec_outs, start_b)`` — or, on the build path,
    ``(rec_outs, start_b, rank, build_outs)`` — where rec_outs /
    build_outs are lists of uint64 arrays of length out_capacity.
    start_b is the run's first output slot per row (int32). On the
    BUILD path start_b and rank are ZERO PLACEHOLDERS: both quantities
    are consumed inside the kernel and exist in the return value only
    so the caller's lax.cond branches (kernel vs XLA-gather fallback)
    have matching pytrees. Values at slots >= the true total are
    garbage (masked by the caller).

    ``block`` must be a multiple of 1024 on real TPUs (the 1-D int32
    DMA tiling; the kernel proves window offsets divisible by it);
    interpret mode accepts any block with block % chunk == 0 (the
    chunked loops; _window_widths handles the 128-lane rounding).

    ``name_prefix`` goes before the kernel's name (``expand_b8`` on the
    build path, ``expand`` otherwise), which a profile reads.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if block is None:
        block = _default_block()
    build = build_cols is not None
    if build:
        assert lo is not None and len(build_cols) > 0
        # v3 path: bf16 8-bit chunk matmuls, 128-aligned record
        # windows, placeholder start_b/rank (consumed in-kernel only —
        # callers on the build path never read them). Rank/start
        # arithmetic is BLOCK-RELATIVE i32 (round 4) — no 2^24 limit.
        # ``window`` (build path only) widens the two build windows
        # independently of the block (_window_widths).
        return _expand_gather_b8(
            S, cols, out_capacity, block, interpret, lo, build_cols,
            window=window, name_prefix=name_prefix,
        )
    k = len(cols)
    m = S.shape[0]
    rows = _split_rows(cols)                         # 3k rows of (m,)
    s_u64_lane = out_capacity >= _F32_EXACT
    if s_u64_lane:
        # start_b values can exceed f32's exact-integer range; ride S
        # as a full 22-bit-chunked u64 lane instead of one f32 row.
        rows.extend(
            _split_rows([S.astype(jnp.uint32).astype(jnp.uint64)])
        )
        srow = len(rows) - 3  # chunk0 row; merged below
    else:
        # start_b comes from one f32 S row (replaces the u64 S lane
        # callers used to append; exact below 2^24).
        srow = len(rows)
        rows.append(
            jnp.where(
                S != jnp.int32(2**31 - 1), S.astype(jnp.float32), 0.0
            )
        )
    ck = _round_up(len(rows), 8)                     # f32 sublane tile
    out_pad = _round_up(out_capacity, block)
    pad_cols = out_pad + 2 * block - m
    if pad_cols > 0:
        S = jnp.concatenate(
            [S, jnp.full((pad_cols,), 2**31 - 1, jnp.int32)]
        )
        rows = [
            jnp.concatenate([r, jnp.zeros((pad_cols,), jnp.float32)])
            for r in rows
        ]
    vT = jnp.stack(
        rows + [jnp.zeros_like(rows[0])] * (ck - len(rows)), axis=0
    )                                                # (ck, m_pad)

    # Per-output-block record offset. A record's start slot is >= its
    # index (each earlier record covers >= 1 slot), so r0[i] <= i*block
    # and the [r0b*block, r0b*block + 2*block) windows stay in-bounds.
    starts = jnp.arange(out_pad // block, dtype=jnp.int32) * block
    r0 = jnp.maximum(
        jnp.searchsorted(S, starts, side="right").astype(jnp.int32) - 1,
        0,
    )
    r0b = r0 // block

    # Under shard_map with vma checking, the out_shape must carry how
    # the output varies over mesh axes — same as the inputs.
    vma = getattr(jax.typeof(vT), "vma", None)
    # Output TILING (ADVICE r4): same scheme as the build wrapper — a
    # monolithic (ck, out_pad) f32 buffer exceeds HBM at spec-scale
    # capacities, and this wrapper serves the lax.cond fallback branch
    # whose gate now admits out_capacity up to 2^31-2. The kernel
    # takes absolute block starts from SMEM, so one compiled kernel
    # covers any output range (_tiled_output_launch owns the tile
    # sizing + serialization). Merging to u64 happens PER TILE:
    # concatenating raw f32 pieces would keep every tile alive at
    # once — the exact monolithic footprint tiling exists to avoid.
    chunk = _default_chunk(block)

    def _launch(q, qb, ib_arr):
        out_shape = (
            jax.ShapeDtypeStruct((ck, qb * block), jnp.float32, vma=vma)
            if vma is not None
            else jax.ShapeDtypeStruct((ck, qb * block), jnp.float32)
        )
        # Global x64 breaks Mosaic legalization ("failed to legalize
        # func.return" — i64 index plumbing); every type here is
        # explicit i32/f32, so scope x64 off around the kernel. The
        # offsets ride a plain SMEM input + manual DMA because
        # PrefetchScalarGridSpec also fails to legalize with this
        # toolchain.
        with jax.enable_x64(False):
            return pl.pallas_call(
                functools.partial(
                    _expand_kernel, block=block, chunk=chunk,
                    ck=ck, srow=srow,
                ),
                name=f"{name_prefix}expand",
                grid=(qb,),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((ck, block), lambda i: (0, i)),
                scratch_shapes=[
                    pltpu.VMEM((2 * block,), jnp.int32),
                    pltpu.VMEM((ck, 2 * block), jnp.float32),
                    pltpu.SemaphoreType.DMA(()),
                    pltpu.SemaphoreType.DMA(()),
                ],
                out_shape=out_shape,
                interpret=interpret,
            )(r0b[q : q + qb], ib_arr, S, vT)

    def _merge(out):
        if s_u64_lane:
            sb = _merge_rows(out[srow : srow + 3], 1)[0].astype(
                jnp.int32
            )
        else:
            sb = out[srow].astype(jnp.int32)
        return _merge_rows(out, k), sb

    rec_full, sb_full = _tiled_output_launch(
        out_pad // block, block, ck * 4 * out_pad, _launch, _merge
    )
    rec_outs = [c[:out_capacity] for c in rec_full]
    start_b = sb_full[:out_capacity]
    return rec_outs, start_b


def expand_gather_reference(S: jax.Array, cols: Sequence[jax.Array],
                            out_capacity: int):
    """XLA reference (the ops/join.py formulation: one scatter + cummax
    + row gather), for correctness tests and as a CPU fallback."""
    r = jnp.arange(S.shape[0], dtype=jnp.int32)
    raw = jnp.zeros((out_capacity,), jnp.int32).at[S].set(
        r + 1, mode="drop", unique_indices=True
    )
    ridx = jnp.clip(lax.cummax(raw) - 1, 0, S.shape[0] - 1)
    return [c[ridx] for c in cols]
