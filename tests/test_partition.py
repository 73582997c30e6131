import jax.numpy as jnp
import numpy as np
import pytest

from distributed_join_tpu.ops.hashing import bucket_ids
from distributed_join_tpu.ops.partition import radix_hash_partition, unpad
from distributed_join_tpu.table import Table


def _mk(keys, valid=None):
    keys = jnp.asarray(keys, dtype=jnp.int64)
    cols = {"key": keys, "payload": jnp.arange(keys.shape[0], dtype=jnp.int64)}
    if valid is None:
        return Table.from_dense(cols)
    return Table(cols, jnp.asarray(valid))


def test_partition_groups_rows_by_bucket():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1000, size=500)
    t = _mk(keys)
    nb = 8
    pt = radix_hash_partition(t, ["key"], nb)
    want_b = np.asarray(bucket_ids([t.columns["key"]], nb))
    got_keys = np.asarray(pt.table.columns["key"])
    got_b = np.asarray(bucket_ids([pt.table.columns["key"]], nb))
    offsets = np.asarray(pt.offsets)
    counts = np.asarray(pt.counts)
    assert counts.sum() == 500
    assert (np.diff(offsets) == counts).all()
    # each bucket slice contains exactly the rows hashing to it
    for b in range(nb):
        sl = got_b[offsets[b] : offsets[b + 1]]
        assert (sl == b).all()
    # multiset of keys preserved
    assert sorted(got_keys.tolist()) == sorted(keys.tolist())


def test_partition_is_stable_and_respects_validity():
    keys = [5, 5, 5, 5, 5, 5]
    t = _mk(keys, valid=[True, False, True, True, False, True])
    pt = radix_hash_partition(t, ["key"], 4)
    assert int(np.asarray(pt.counts).sum()) == 4
    # valid rows keep original relative order (stable sort), padding last
    pay = np.asarray(pt.table.columns["payload"])
    v = np.asarray(pt.table.valid)
    assert list(pay[v]) == [0, 2, 3, 5]
    assert not v[4:].any()


def test_to_padded_unpad_roundtrip():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 50, size=200)
    t = _mk(keys)
    nb = 4
    pt = radix_hash_partition(t, ["key"], nb)
    cap = int(np.asarray(pt.counts).max()) + 3
    padded, counts, overflow, _ = pt.to_padded(cap)
    assert not bool(overflow)
    flat = unpad(padded, counts, cap)
    got = flat.to_pandas()
    assert len(got) == 200
    assert sorted(got["key"].tolist()) == sorted(keys.tolist())


def test_to_padded_overflow_flag():
    t = _mk([7] * 100)  # all rows in one bucket
    pt = radix_hash_partition(t, ["key"], 4)
    _, counts, overflow, _ = pt.to_padded(16)
    assert bool(overflow)
    assert np.asarray(counts).max() == 16


def test_to_padded_bucket_range():
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 1000, size=300)
    t = _mk(keys)
    pt = radix_hash_partition(t, ["key"], 8)  # k=2 batches of 4 ranks
    cap = 128
    rows = []
    for batch in range(2):
        padded, counts, ovf, _ = pt.to_padded(cap, bucket_start=batch * 4, n_buckets=4)
        assert not bool(ovf)
        flat = unpad(padded, counts, cap)
        rows.append(flat.to_pandas())
    import pandas as pd

    both = pd.concat(rows)
    assert len(both) == 300
    assert sorted(both["key"].tolist()) == sorted(keys.tolist())


# -- the sort carries the columns; to_padded packs by slices -----------


def _gather_oracle(t, order, offsets, counts, capacity, bucket_start, nb):
    """The formulation the sort-carried columns replaced: each bucket's
    lanes gathered from the source through ``order[clip(offset + lane)]``
    (numpy). Returns (padded columns, clipped counts, overflow,
    row_valid)."""
    offs = offsets[bucket_start: bucket_start + nb]
    cnt = counts[bucket_start: bucket_start + nb]
    lane = np.arange(capacity)
    idx = order[np.clip(offs[:, None] + lane[None, :], 0,
                        order.shape[0] - 1)]
    cols = {n: np.asarray(c)[idx] for n, c in t.columns.items()}
    return (cols, np.minimum(cnt, capacity), bool((cnt > capacity).any()),
            lane[None, :] < cnt[:, None])


def _mixed_table(rows, seed, masked=False, strings=False):
    """int64 key, int64/float64/float32/int32/bool payloads, optional
    validity holes and a fixed-width (rows, 8) uint8 string column with
    its length companion."""
    rng = np.random.default_rng(seed)
    cols = {
        "key": jnp.asarray(rng.integers(0, rows // 3 + 1, rows),
                           jnp.int64),
        "pay_i64": jnp.asarray(rng.integers(-2**62, 2**62, rows),
                               jnp.int64),
        "pay_f64": jnp.asarray(rng.standard_normal(rows), jnp.float64),
        "pay_f32": jnp.asarray(rng.standard_normal(rows), jnp.float32),
        "pay_i32": jnp.arange(rows, dtype=jnp.int32),
        "flag": jnp.asarray(rng.integers(0, 2, rows).astype(bool)),
    }
    if strings:
        lens = rng.integers(0, 9, rows)
        raw = rng.integers(1, 256, (rows, 8)).astype(np.uint8)
        raw[np.arange(8)[None, :] >= lens[:, None]] = 0
        cols["name"] = jnp.asarray(raw)
        cols["name#len"] = jnp.asarray(lens, jnp.int32)
    valid = (rng.random(rows) < 0.7) if masked else np.ones(rows, bool)
    return Table(cols, jnp.asarray(valid))


def _reference_order(t, n_buckets, sub_buckets=1, order_within=None):
    """Stable (bucket, -order_within) argsort of the rows, invalid rows
    last — the permutation the partition's sort must produce."""
    b = np.asarray(bucket_ids([t.columns["key"]], n_buckets,
                              sub_buckets=sub_buckets))
    nbt = n_buckets * sub_buckets
    b = np.where(np.asarray(t.valid), b, nbt)
    keys = (b,) if order_within is None else (
        -np.asarray(t.columns[order_within]).astype(np.int64), b)
    order = np.lexsort(keys)  # last key is primary; lexsort is stable
    offsets = np.searchsorted(b[order], np.arange(nbt + 1), side="left")
    return order, offsets, np.diff(offsets)


# (rows, n_buckets, sub_buckets, order_within, masked, strings,
#  capacity: "fit" | "overflow", [(bucket_start, n_buckets), ...])
PACK_CASES = {
    "one_bucket": (64, 1, 1, None, False, False, "fit", [(0, 1)]),
    "four_buckets": (333, 4, 1, None, False, False, "fit",
                     [(0, 4), (1, 2), (3, 1)]),
    "eight_buckets_batches": (1000, 8, 1, None, False, False, "fit",
                              [(0, 4), (4, 4), (0, 8), (7, 1)]),
    "masked": (517, 4, 1, None, True, False, "fit", [(0, 4), (2, 2)]),
    "overflow": (500, 4, 1, None, False, False, "overflow",
                 [(0, 4), (2, 2)]),
    "masked_overflow": (400, 8, 1, None, True, False, "overflow",
                        [(0, 8), (4, 4)]),
    "string_column": (300, 4, 1, None, True, True, "fit", [(0, 4)]),
    "sub_buckets": (777, 4, 2, None, False, False, "fit",
                    [(0, 8), (4, 4), (6, 2)]),
    "order_within": (400, 4, 1, "name#len", True, True, "fit",
                     [(0, 4), (1, 3)]),
}


@pytest.mark.parametrize("case", list(PACK_CASES), ids=list(PACK_CASES))
def test_to_padded_valid_lanes_match_gather_formulation(case):
    (rows, nb, sub, ow, masked, strings, cap_mode,
     ranges) = PACK_CASES[case]
    t = _mixed_table(rows, seed=rows + nb, masked=masked, strings=strings)
    pt = radix_hash_partition(t, ["key"], nb, order_within=ow,
                              sub_buckets=sub)
    order, offsets, counts = _reference_order(t, nb, sub, ow)
    np.testing.assert_array_equal(np.asarray(pt.order), order)
    np.testing.assert_array_equal(np.asarray(pt.offsets), offsets)
    np.testing.assert_array_equal(np.asarray(pt.counts), counts)
    # The sorted view: every column in bucket order, valid rows first.
    view = pt.table
    for n, c in t.columns.items():
        np.testing.assert_array_equal(np.asarray(view.columns[n]),
                                      np.asarray(c)[order], err_msg=n)
    np.testing.assert_array_equal(np.asarray(view.valid),
                                  np.asarray(t.valid)[order])
    cap = int(counts.max()) + 5 if cap_mode == "fit" \
        else max(int(counts.max()) // 2, 1)
    if cap_mode == "fit" and not masked:
        # The last bucket's lane block runs past the table's end: a
        # clamped slice start would shift its rows.
        assert offsets[-2] + cap > rows
    for start, k in ranges:
        padded, got_counts, ovf, row_valid = pt.to_padded(
            cap, bucket_start=start, n_buckets=k)
        want, want_counts, want_ovf, want_valid = _gather_oracle(
            t, order, offsets, counts, cap, start, k)
        np.testing.assert_array_equal(np.asarray(got_counts), want_counts)
        assert bool(ovf) == want_ovf == (cap_mode == "overflow"
                                         and bool((counts[start:start + k]
                                                   > cap).any()))
        np.testing.assert_array_equal(np.asarray(row_valid), want_valid)
        assert set(padded) == set(want)
        for n, col in padded.items():
            got = np.asarray(col)
            assert got.shape == want[n].shape and got.dtype == want[n].dtype
            np.testing.assert_array_equal(got[want_valid],
                                          want[n][want_valid],
                                          err_msg=f"{case} {n} {start}")


@pytest.mark.parametrize("strings", [False, True],
                         ids=["scalar_columns", "string_column"])
def test_gathered_columns_counts_what_the_sort_cannot_carry(strings):
    """Only columns that are not 1-D are packed by a gather; the
    partition step records that count on each side's metrics tape."""
    from distributed_join_tpu.parallel.communicator import make_communicator
    from distributed_join_tpu.parallel.distributed_join import (
        make_distributed_join,
    )

    want = 1 if strings else 0
    rows = 512
    t = _mixed_table(rows, seed=3, strings=strings)
    pt = radix_hash_partition(t, ["key"], 4)
    assert pt.gathered_columns == want
    assert set(pt.sorted_columns) == {
        n for n, c in t.columns.items() if c.ndim == 1}

    comm = make_communicator("tpu", n_ranks=4)
    probe = _mixed_table(rows, seed=4, strings=strings)
    build = Table({"key": t.columns["key"],
                   "build_pay": t.columns["pay_i64"]}, t.valid)
    res = make_distributed_join(comm, key="key", with_metrics=True,
                                out_capacity_factor=4.0)(build, probe)
    assert not bool(res.overflow)
    per_rank = res.telemetry.to_dict()["per_rank"]
    assert per_rank["build.gathered_columns"] == [0] * 4
    assert per_rank["probe.gathered_columns"] == [want] * 4


def test_flat_padded_step_packs_without_gathers():
    """The four-rank flat padded join step, compiled: the partition's
    sort carries the int64 key and payload, and no gather is left under
    the partition or shuffle scopes."""
    import re

    from distributed_join_tpu.parallel.communicator import make_communicator
    from distributed_join_tpu.parallel.distributed_join import (
        make_distributed_join,
    )
    from distributed_join_tpu.service.programs import abstract_join_tables

    comm = make_communicator("tpu", n_ranks=4)
    build, probe = abstract_join_tables(comm, 4096)
    text = make_distributed_join(comm, key="key").lower(
        build, probe).compile().as_text()
    instr = re.compile(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z0-9\-]*)\(")
    sorts = 0
    for line in text.splitlines():
        m = instr.match(line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not (m and op_name):
            continue
        scopes = op_name.group(1).split("/")
        if m.group(2) == "gather":
            assert not {"partition", "shuffle"} & set(scopes), line[:300]
        if m.group(2) == "sort" and "partition" in scopes:
            sorts += 1
            # bucket id, then key and payload (then, where kept, the
            # row index)
            lanes = re.findall(r"\b([a-z]+\d+)\[", m.group(1))
            assert lanes[:3] == ["s32", "s64", "s64"], m.group(1)
    assert sorts == 2  # one per side
