"""Pallas expand-gather kernel vs the XLA reference (interpret mode —
runs the real kernel logic on CPU, no TPU needed)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_join_tpu.ops.expand_pallas import (
    _merge_rows,
    _split_rows,
    build_windows_ok,
    expand_gather,
    expand_gather_reference,
)


# Live-slot counts around the block edges: the kernels compute only
# the blocks that start below ``live``, and every slot below it must
# match the reference. Values past the capacity are clipped.
LIVE = {
    "none": lambda block, cap: 0,
    "one": lambda block, cap: 1,
    "block-1": lambda block, cap: block - 1,
    "block": lambda block, cap: block,
    "block+1": lambda block, cap: block + 1,
    "mid": lambda block, cap: cap // 2 + 3,
    "all": lambda block, cap: cap,
}


def _make_records(rng, n_records, out_capacity, k):
    """Random run lengths covering [0, total); sentinel tail."""
    lens = rng.integers(1, 7, size=n_records)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    total = int(np.cumsum(lens)[-1])
    m = n_records + 13  # some sentinel rows
    S = np.full((m,), 2**31 - 1, np.int32)
    S[:n_records] = starts
    cols = [jnp.asarray(rng.integers(0, 1 << 63, size=(m,), dtype=np.uint64))
            for _ in range(k)]
    return jnp.asarray(S), cols, min(total, out_capacity)


def test_chunk_roundtrip():
    rng = np.random.default_rng(0)
    cols = [jnp.asarray(rng.integers(0, 1 << 64, size=(257,), dtype=np.uint64))
            for _ in range(3)]
    back = _merge_rows(jnp.stack(_split_rows(cols)), 3)
    for a, b in zip(back, cols):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("n_records,out_cap,k", [
    (50, 256, 1),
    (200, 1024, 3),
    (1000, 2048, 2),
])
def test_expand_matches_reference(n_records, out_cap, k, live):
    rng = np.random.default_rng(n_records)
    S, cols, total = _make_records(rng, n_records, out_cap, k)
    n = LIVE[live](128, out_cap)
    got, start_b = expand_gather(S, cols, out_cap, jnp.int32(n),
                                 block=128, interpret=True)
    want = expand_gather_reference(S, cols, out_cap)
    # only slots below live are defined (the rest are not computed and
    # masked downstream); both implementations agree there
    n = min(n, out_cap)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(g)[:n], np.asarray(w)[:n]
        )
    want_sb = expand_gather_reference(
        S, [S.astype(jnp.uint32).astype(jnp.uint64)], out_cap
    )[0]
    np.testing.assert_array_equal(
        np.asarray(start_b)[:n], np.asarray(want_sb)[:n]
    )


def _make_join_records(rng, key_specs, out_cap, kb=1):
    """Records exactly as the join produces them: per key (in sorted
    order) with c builds and p probes, p records of run length c, all
    sharing lo = (builds of earlier keys). p == 0 keys advance lo
    WITHOUT emitting records (unmatched-build gaps — the case the
    window proof does not cover; build_windows_ok must flag them).
    Returns (S, lo, rec cols, build cols, expected rank per slot,
    total)."""
    S_list, lo_list = [], []
    lo = 0
    slot = 0
    for c, p in key_specs:
        for _ in range(p):
            S_list.append(slot)
            lo_list.append(lo)
            slot += c
        lo += c
    nb = max(lo, 1)
    total = slot
    m = len(S_list) + 7
    S = np.full((m,), 2**31 - 1, np.int32)
    S[: len(S_list)] = S_list
    lo_arr = np.zeros((m,), np.int32)
    lo_arr[: len(lo_list)] = lo_list
    cols = [
        jnp.asarray(rng.integers(0, 1 << 63, size=(m,), dtype=np.uint64))
    ]
    bcols = [
        jnp.asarray(rng.integers(0, 1 << 63, size=(nb,), dtype=np.uint64))
        for _ in range(kb)
    ]
    # oracle rank per output slot: each record fills its run
    rank = np.zeros((total,), np.int64)
    ends = S_list[1:] + [total]
    for (s, l), e in zip(zip(S_list, lo_list), ends):
        rank[s:e] = l + np.arange(e - s)
    return (
        jnp.asarray(S),
        jnp.asarray(lo_arr),
        cols,
        bcols,
        rank,
        min(total, out_cap),
    )


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("key_specs,out_cap,block", [
    # small uniform runs
    ([(2, 3)] * 40 + [(1, 1)] * 30, 4096, 256),
    # one huge build run (c >> block) straddling many blocks
    ([(3, 2)] * 10, None, 256),
    # alternating huge/small, multiple records per key
    ([(700, 2), (1, 5), (300, 3), (2, 2)], None, 256),
    # run starting exactly at a block boundary
    ([(256, 1), (256, 2), (1, 7)], None, 256),
    # single key, single giant record
    ([(2000, 1)], None, 256),
    # small unmatched gaps (lo advances without records) that still
    # fit window 2's slack
    ([(2, 2), (2, 0), (2, 2)] * 15, None, 256),
])
def test_expand_build_windows_match_oracle(key_specs, out_cap, block,
                                          live):
    import zlib

    from distributed_join_tpu.ops.expand_pallas import build_windows_ok

    rng = np.random.default_rng(zlib.crc32(str(key_specs).encode()))
    if out_cap is None:
        out_cap = sum(c * p for c, p in key_specs)
    S, lo, cols, bcols, rank_want, total = _make_join_records(
        rng, key_specs, out_cap, kb=2
    )
    # the kernel's contract: exact whenever the checker passes
    assert bool(build_windows_ok(S, lo, out_cap, block=block))
    n = LIVE[live](block, out_cap)
    rec_outs, _sb, _rank, build_outs = expand_gather(
        S, cols, out_cap, jnp.int32(n), block=block, interpret=True,
        lo=lo, build_cols=bcols,
    )
    n = min(n, out_cap)
    want_rec = expand_gather_reference(S, cols, out_cap)
    np.testing.assert_array_equal(
        np.asarray(rec_outs[0])[:n], np.asarray(want_rec[0])[:n]
    )
    # rank/start_b are in-kernel quantities now (placeholder outputs);
    # the build values below being exact implies the ranks were (the
    # oracle's ranks stop at the total).
    nb = min(n, total)
    for bo, bc in zip(build_outs, bcols):
        np.testing.assert_array_equal(
            np.asarray(bo)[:nb],
            np.asarray(bc)[rank_want[:nb]],
        )


def test_window_checker_flags_gap_data():
    """The code-review repro: a large unmatched-build key between two
    matched keys whose output rows share a block. The checker must
    refuse the kernel path (ops/join.py then conds to the XLA
    gather)."""
    from distributed_join_tpu.ops.expand_pallas import build_windows_ok

    rng = np.random.default_rng(42)
    key_specs = [(1, 1), (1, 1), (5000, 0), (1, 1)]
    out_cap = 8
    S, lo, cols, bcols, rank_want, total = _make_join_records(
        rng, key_specs, out_cap
    )
    assert not bool(build_windows_ok(S, lo, out_cap, block=256))


@pytest.mark.slow
def test_join_level_gap_data_falls_back_exact(monkeypatch):
    """Join-level oracle on data with mostly-unmatched build keys
    (sparse probe hits over a wide key domain): the cond must route to
    the exact XLA gather and the result must still match pandas."""
    monkeypatch.setenv("DJTPU_PALLAS_EXPAND", "1")
    import pandas as pd

    from distributed_join_tpu.ops.join import sort_merge_inner_join
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    build, probe = generate_build_probe_tables(
        seed=13, build_nrows=60_000, probe_nrows=4_000,
        rand_max=120_000, selectivity=0.2,
    )
    res = sort_merge_inner_join(build, probe, "key", 16_384)
    merged = build.to_pandas().merge(probe.to_pandas(), on="key")
    assert int(res.total) == len(merged)
    got = res.table.to_pandas().sort_values(
        ["key", "build_payload", "probe_payload"]).reset_index(drop=True)
    want = merged.sort_values(
        ["key", "build_payload", "probe_payload"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got[want.columns], want)


@pytest.mark.slow
@pytest.mark.parametrize("selectivity,out_cap", [
    (0.6, 40_000),
    (0.02, 40_000),   # ~2% of the output filled, as on TPC-H Q3
], ids=["dense", "sparse"])
def test_join_kernel_path_fallback_branch_exact(monkeypatch, selectivity,
                                                out_cap):
    """Force build_windows_ok False so the lax.cond in
    _join_kernel_path takes the XLA-gather fallback branch (the
    matched-rank pipeline makes the checker pass by construction, so
    nothing else covers that closure) and compare against pandas; the
    sparse case leaves most of the expand grid dead."""
    monkeypatch.setenv("DJTPU_PALLAS_EXPAND", "1")
    import jax.numpy as jnp
    import pandas as pd

    from distributed_join_tpu.ops import expand_pallas
    from distributed_join_tpu.ops.join import sort_merge_inner_join
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    monkeypatch.setattr(
        expand_pallas, "build_windows_ok",
        lambda *a, **k: jnp.bool_(False),
    )
    build, probe = generate_build_probe_tables(
        seed=21, build_nrows=3000, probe_nrows=5000,
        rand_max=1024, selectivity=selectivity,
    )
    res = sort_merge_inner_join(build, probe, "key", out_cap)
    merged = build.to_pandas().merge(probe.to_pandas(), on="key")
    assert int(res.total) == len(merged) > 0
    got = res.table.to_pandas().sort_values(
        ["key", "build_payload", "probe_payload"]).reset_index(drop=True)
    want = merged.sort_values(
        ["key", "build_payload", "probe_payload"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got[want.columns], want)


def test_expand_truncated_overflow_build_path():
    """out_cap smaller than the total: kept records still tile the
    prefix; every slot below out_cap must be exact."""
    rng = np.random.default_rng(99)
    key_specs = [(5, 3)] * 50 + [(900, 1), (2, 4)] * 3
    total_full = sum(c * p for c, p in key_specs)
    out_cap = total_full // 2
    S, lo, cols, bcols, rank_want, total = _make_join_records(
        rng, key_specs, out_cap
    )
    # truncate records to those starting below out_cap (join's _prefix)
    keep = np.asarray(S) < out_cap
    m = int(keep.sum())
    S_t = np.where(np.arange(S.shape[0]) < m, np.asarray(S), 2**31 - 1)
    lo_t = np.where(np.arange(S.shape[0]) < m, np.asarray(lo), 0)
    # live is the match total, past the capacity: every slot is live
    rec_outs, _sb, _rank, build_outs = expand_gather(
        jnp.asarray(S_t), cols, out_cap, jnp.int64(total_full),
        block=256, interpret=True, lo=jnp.asarray(lo_t),
        build_cols=bcols,
    )
    np.testing.assert_array_equal(
        np.asarray(build_outs[0]),
        np.asarray(bcols[0])[rank_want[:out_cap]],
    )


def test_expand_empty():
    S = jnp.full((16,), 2**31 - 1, jnp.int32)
    cols = [jnp.zeros((16,), jnp.uint64)]
    out, _ = expand_gather(S, cols, 64, jnp.int32(0), block=64,
                           interpret=True)
    assert out[0].shape == (64,)


@pytest.mark.slow
@pytest.mark.parametrize("selectivity,out_cap", [
    (0.5, 32768),
    (0.02, 32768),   # ~2% of the output filled, as on TPC-H Q3
], ids=["dense", "sparse"])
def test_join_level_pallas_path_matches_oracle(monkeypatch, selectivity,
                                               out_cap):
    """The join-level wiring of the kernel (u64 lane encode/decode per
    dtype, the __lo geometry lane, start_b riding as the S lane) — CPU
    CI otherwise never takes this path (use_pallas defaults off there).
    The sparse case leaves most of the expand grid dead."""
    monkeypatch.setenv("DJTPU_PALLAS_EXPAND", "1")
    from distributed_join_tpu.ops.join import sort_merge_inner_join
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    build, probe = generate_build_probe_tables(
        seed=5, build_nrows=2048, probe_nrows=4096,
        rand_max=512, selectivity=selectivity,
    )
    # mixed payload dtypes to exercise the lane round-trips
    build = type(build)(
        {**build.columns,
         "b32": build.columns["build_payload"].astype(jnp.int32) - 7,
         "bf32": (build.columns["build_payload"] % 97).astype(jnp.float32)},
        build.valid,
    )
    res = sort_merge_inner_join(build, probe, "key", out_cap)
    bp, pp = build.to_pandas(), probe.to_pandas()
    merged = bp.merge(pp, on="key")
    assert int(res.total) == len(merged) > 0
    got = res.table.to_pandas().sort_values(
        ["key", "build_payload", "probe_payload"]).reset_index(drop=True)
    want = merged.sort_values(
        ["key", "build_payload", "probe_payload"]).reset_index(drop=True)
    import pandas as pd
    pd.testing.assert_frame_equal(got[want.columns], want)


@pytest.mark.parametrize("live", [336, 300, 200, 0],
                         ids=["all", "in_tile_2", "tile_2_dead",
                              "none"])
def test_build_path_output_tiling_exact(monkeypatch, live):
    """Force the tiled output path (per-tile f32 budget shrunk so the
    small test splits into several tiles) and require bit-exactness vs
    the monolithic run — the spec-scale OOM fix must not change a
    single value (round 4). Two one-block tiles; the live bound falls
    at their end, inside the second, or before it (a whole tile
    dead)."""
    import zlib

    import distributed_join_tpu.ops.expand_pallas as E

    key_specs = [(64, 3), (32, 1), (16, 7)]
    rng = np.random.default_rng(zlib.crc32(b"tiling"))
    out_cap = sum(c * p for c, p in key_specs)
    S, lo, cols, bcols, rank_want, total = _make_join_records(
        rng, key_specs, out_cap, kb=2
    )
    assert bool(build_windows_ok(S, lo, out_cap, block=256))
    whole = expand_gather(S, cols, out_cap, jnp.int32(live), block=256,
                          interpret=True, lo=lo, build_cols=bcols)
    monkeypatch.setattr(E, "_FUSED_TILE_BYTES", 256 * 64)  # few blocks
    tiled = expand_gather(S, cols, out_cap, jnp.int32(live), block=256,
                          interpret=True, lo=lo, build_cols=bcols)
    for a, b in zip(whole[0] + whole[3], tiled[0] + tiled[3]):
        np.testing.assert_array_equal(np.asarray(a)[:live],
                                      np.asarray(b)[:live])
    for bo, bc in zip(tiled[3], bcols):
        np.testing.assert_array_equal(
            np.asarray(bo)[:live], np.asarray(bc)[rank_want[:live]])


@pytest.mark.parametrize("tile_bytes", [128 * 32, 128 * 32 * 4],
                         ids=["1_block_tiles", "4_block_tiles"])
@pytest.mark.parametrize("live", [2048, 178, 100],
                         ids=["all", "in_tile_2", "tiles_dead"])
def test_nonbuild_path_output_tiling_exact(monkeypatch, live,
                                           tile_bytes):
    """Same exactness contract for the NON-build wrapper (the lax.cond
    fallback branch): its gate admits out_capacity up to 2^31-2, so it
    needs the same output tiling (ADVICE r4) — and tiling must not
    change a value or a start_b below the live bound, which falls at
    the end, inside the second 128-slot block, or inside the first
    (every later tile dead)."""
    import distributed_join_tpu.ops.expand_pallas as E

    rng = np.random.default_rng(7)
    S, cols, total = _make_records(rng, 900, 2048, 2)
    n = jnp.int32(live)
    whole, whole_sb = expand_gather(S, cols, 2048, n, block=128,
                                    interpret=True)
    want = expand_gather_reference(S, cols, 2048)
    for a, b in zip(whole, want):
        np.testing.assert_array_equal(np.asarray(a)[:live],
                                      np.asarray(b)[:live])
    monkeypatch.setattr(E, "_FUSED_TILE_BYTES", tile_bytes)
    tiled, tiled_sb = expand_gather(S, cols, 2048, n, block=128,
                                    interpret=True)
    for a, b in zip(whole, tiled):
        np.testing.assert_array_equal(np.asarray(a)[:live],
                                      np.asarray(b)[:live])
    np.testing.assert_array_equal(
        np.asarray(whole_sb)[:live], np.asarray(tiled_sb)[:live]
    )
    # Also cover the u64-S-lane start_b branch under tiling (real
    # trigger is out_capacity >= 2^24 — force it instead).
    monkeypatch.setattr(E, "_F32_EXACT", 1)
    tiled64, tiled64_sb = expand_gather(S, cols, 2048, n, block=128,
                                        interpret=True)
    for a, b in zip(whole, tiled64):
        np.testing.assert_array_equal(np.asarray(a)[:live],
                                      np.asarray(b)[:live])
    np.testing.assert_array_equal(
        np.asarray(whole_sb)[:live], np.asarray(tiled64_sb)[:live]
    )


@pytest.mark.parametrize("live", [0, 129, 700, 1024])
@pytest.mark.parametrize("build", [True, False],
                         ids=["build", "nonbuild"])
def test_dead_blocks_are_not_computed(monkeypatch, build, live):
    """The kernels compute exactly the blocks that start below
    ``live``: the Pallas interpreter fills an output it never writes
    with NaN, so every dead block's raw f32 rows read NaN and every
    live block's none."""
    import zlib

    import distributed_join_tpu.ops.expand_pallas as E

    block, out_cap = 128, 1024
    raw = []
    for name in ("_merge_rows8", "_merge_rows"):
        merge = getattr(E, name)
        monkeypatch.setattr(
            E, name,
            lambda out, k, _m=merge: raw.append(out) or _m(out, k))
    rng = np.random.default_rng(zlib.crc32(b"dead"))
    if build:
        S, lo, cols, bcols, _, _ = _make_join_records(
            rng, [(3, 2)] * 200, out_cap)
        expand_gather(S, cols, out_cap, jnp.int32(live), block=block,
                      interpret=True, lo=lo, build_cols=bcols)
    else:
        S, cols, _ = _make_records(rng, 400, out_cap, 1)
        expand_gather(S, cols, out_cap, jnp.int32(live), block=block,
                      interpret=True)
    out = np.asarray(raw[0])
    assert out.shape[1] == out_cap
    n_live = -(-live // block) * block
    assert not np.isnan(out[:, :n_live]).any()
    assert np.isnan(out[:, n_live:]).all()
