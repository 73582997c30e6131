"""Distributed shuffle + end-to-end distributed join on 8 virtual devices.

The oracle strategy mirrors the reference's (SURVEY.md §3.4): run the
distributed join, gather the sharded result, compare against a
single-process pandas join of the full tables, sort-normalized.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from distributed_join_tpu.ops.hashing import bucket_ids
from distributed_join_tpu.ops.partition import radix_hash_partition
from distributed_join_tpu.parallel.communicator import (
    LocalCommunicator,
    TpuCommunicator,
    make_communicator,
)
from distributed_join_tpu.parallel.distributed_join import (
    distributed_inner_join,
    make_distributed_join,
)
from distributed_join_tpu.parallel.shuffle import shuffle_partitioned
from distributed_join_tpu.table import Table
from distributed_join_tpu.utils.generators import (
    generate_build_probe_tables,
    generate_zipf_probe_table,
)


def _normalize(df):
    cols = sorted(df.columns)
    return df[cols].sort_values(cols).reset_index(drop=True).astype("int64")


@pytest.fixture(scope="module")
def comm8():
    assert len(jax.devices()) >= 8, "conftest should provide 8 virtual devices"
    return TpuCommunicator(n_ranks=8)


def test_communicator_factory():
    assert make_communicator("local").n_ranks == 1
    assert make_communicator("tpu", n_ranks=8).n_ranks == 8
    with pytest.raises(ValueError, match="tpu"):
        make_communicator("nccl")
    with pytest.raises(ValueError, match="unknown"):
        make_communicator("smoke-signals")


def test_shuffle_routes_every_row_to_its_hash_owner(comm8):
    n = comm8.n_ranks
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 10_000, size=1024)
    t = Table.from_dense(
        {"key": jnp.asarray(keys, dtype=jnp.int64),
         "payload": jnp.arange(1024, dtype=jnp.int64)}
    )

    def per_rank(t_local):
        pt = radix_hash_partition(t_local, ["key"], n)
        recv, ovf = shuffle_partitioned(comm8, pt, capacity=64)
        return recv, comm8.psum(ovf.astype(jnp.int32)) > 0

    fn = comm8.spmd(per_rank, sharded_out=(False, True))
    t_sharded = comm8.device_put_sharded(t)
    recv, ovf = fn(t_sharded)
    assert not bool(np.asarray(ovf).any())
    # gather: recv is the globally sharded received table; per-rank block r
    # must contain exactly the rows with bucket_ids == r
    rkeys = np.asarray(recv.columns["key"]).reshape(n, -1)
    rvalid = np.asarray(recv.valid).reshape(n, -1)
    want_b = np.asarray(bucket_ids([t.columns["key"]], n))
    for r in range(n):
        got = sorted(rkeys[r][rvalid[r]].tolist())
        want = sorted(keys[want_b == r].tolist())
        assert got == want


def _run_and_check(build, probe, comm, **opts):
    res = distributed_inner_join(build, probe, comm, **opts)
    assert not bool(res.overflow), "capacity overflow in test config"
    got = _normalize(res.table.to_pandas())
    want = _normalize(build.to_pandas().merge(probe.to_pandas(), on="key"))
    assert int(res.total) == len(want)
    pd.testing.assert_frame_equal(got, want)


def test_distributed_join_matches_oracle(comm8):
    build, probe = generate_build_probe_tables(
        seed=11, build_nrows=4096, probe_nrows=8192, rand_max=2048,
        selectivity=0.5,
    )
    _run_and_check(build, probe, comm8, out_capacity_factor=3.0)


def test_distributed_join_local_single_rank():
    build, probe = generate_build_probe_tables(
        seed=12, build_nrows=1000, probe_nrows=2000, rand_max=700,
        selectivity=0.3,
    )
    _run_and_check(build, probe, LocalCommunicator(), out_capacity_factor=3.0)


def test_distributed_join_over_decomposition(comm8):
    build, probe = generate_build_probe_tables(
        seed=13, build_nrows=4096, probe_nrows=4096, rand_max=4096,
        selectivity=0.7,
    )
    _run_and_check(
        build, probe, comm8, over_decomposition=3, out_capacity_factor=3.0
    )


def test_distributed_join_ppermute_shuffle(comm8):
    # the collective-permute-chained shuffle must be bit-equivalent to
    # the grouped all-to-all (same blocks, async-schedulable lowering)
    build, probe = generate_build_probe_tables(
        seed=21, build_nrows=4096, probe_nrows=8192, rand_max=2048,
        selectivity=0.5,
    )
    _run_and_check(
        build, probe, comm8, shuffle="ppermute", out_capacity_factor=3.0
    )


def test_distributed_join_ppermute_over_decomposition(comm8):
    build, probe = generate_build_probe_tables(
        seed=22, build_nrows=4096, probe_nrows=4096, rand_max=4096,
        selectivity=0.7,
    )
    _run_and_check(
        build, probe, comm8, shuffle="ppermute", over_decomposition=2,
        out_capacity_factor=3.0,
    )


def test_distributed_join_uneven_input_padding(comm8):
    # capacity not divisible by 8 exercises the pad_div path
    build, probe = generate_build_probe_tables(
        seed=14, build_nrows=1000, probe_nrows=2007, rand_max=500,
        selectivity=0.5,
    )
    _run_and_check(build, probe, comm8, out_capacity_factor=4.0)


def test_distributed_join_zipf_skew(comm8):
    key = jax.random.PRNGKey(15)
    build, _ = generate_build_probe_tables(
        seed=15, build_nrows=4096, probe_nrows=1, rand_max=4096,
        unique_build_keys=True,
    )
    probe = generate_zipf_probe_table(
        key, nrows=4096, alpha=1.5, rand_max=4096
    )
    # Zipf concentrates rows on few keys -> few buckets; need a fat pad.
    _run_and_check(
        build, probe, comm8,
        shuffle_capacity_factor=9.0, out_capacity_factor=3.0,
    )


def test_distributed_join_overflow_reported(comm8):
    # every probe row has the same key -> one bucket overflows a tight pad
    build = Table.from_dense(
        {"key": jnp.arange(64, dtype=jnp.int64),
         "build_payload": jnp.arange(64, dtype=jnp.int64)}
    )
    probe = Table.from_dense(
        {"key": jnp.zeros(1024, dtype=jnp.int64),
         "probe_payload": jnp.arange(1024, dtype=jnp.int64)}
    )
    res = distributed_inner_join(
        build, probe, comm8, shuffle_capacity_factor=1.0,
        skew_threshold=0,  # the plain path: no heavy-hitter sidecar
    )
    assert bool(res.overflow)


def test_distributed_join_hot_key_resolves_the_skew_path(comm8):
    # the same one-key probe with no skew knob: the entry sees the hot
    # key, keeps its rows local and the padded shuffle fits
    build = Table.from_dense(
        {"key": jnp.arange(64, dtype=jnp.int64),
         "build_payload": jnp.arange(64, dtype=jnp.int64)}
    )
    probe = Table.from_dense(
        {"key": jnp.zeros(1024, dtype=jnp.int64),
         "probe_payload": jnp.arange(1024, dtype=jnp.int64)}
    )
    res = distributed_inner_join(
        build, probe, comm8, shuffle_capacity_factor=1.0
    )
    assert not bool(res.overflow)
    assert int(res.total) == 1024
    got = res.table.to_pandas()
    assert (got["key"] == 0).all() and (got["build_payload"] == 0).all()
    assert sorted(got["probe_payload"]) == list(range(1024))


@pytest.mark.parametrize("out_capacity_factor", [3.0, 0.05],
                         ids=["sparse", "overflow"])
def test_expand_counters_count_the_live_blocks(comm8,
                                               out_capacity_factor):
    """The metrics tape's expand pair, per rank: the blocks below the
    rank's match count (capped by its output capacity), which the
    expand kernels compute, and the static grid over that capacity."""
    from distributed_join_tpu.ops.kernel_config import KernelConfig

    block = 128
    build, probe = generate_build_probe_tables(
        seed=11, build_nrows=4096, probe_nrows=8192, selectivity=0.3)
    res = make_distributed_join(
        comm8, key="key", with_metrics=True,
        out_capacity_factor=out_capacity_factor,
        kernel_config=KernelConfig(block=block))(build, probe)
    out_cap = res.table.capacity // 8
    per_rank = res.telemetry.to_dict()["per_rank"]
    live = [-(-min(m, out_cap) // block) for m in per_rank["matches"]]
    grid = [-(-out_cap // block)] * 8
    assert per_rank["expand.live_blocks"] == live
    assert per_rank["expand.grid_blocks"] == grid
    if bool(res.overflow):
        assert live == grid
    else:
        assert 0 < sum(live) < sum(grid)
    assert bool(res.overflow) == (out_capacity_factor < 1)
