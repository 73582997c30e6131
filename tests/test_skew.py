"""Skew-path tests (BASELINE config 3): heavy-hitter detection,
classification consistency, and end-to-end Zipf joins vs the pandas
oracle on the 8-virtual-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_join_tpu as dj
from distributed_join_tpu.parallel.skew import (
    HeavyHitters,
    global_heavy_hitters,
    local_top_keys,
    mark_heavy,
)
from distributed_join_tpu.table import Table
from distributed_join_tpu.utils.generators import (
    generate_build_table,
    generate_zipf_probe_table,
)


def test_local_top_keys():
    keys = jnp.array([5, 5, 5, 9, 9, 2, 5, 9, 7, 7], dtype=jnp.int64)
    valid = jnp.ones(10, dtype=bool).at[8].set(False)  # one 7 invalid
    top_keys, top_counts = local_top_keys(keys, valid, k=3)
    got = dict(zip(np.asarray(top_keys).tolist(),
                   np.asarray(top_counts).tolist()))
    assert got[5] == 4 and got[9] == 3
    # third slot: 2 or 7, each count 1
    assert sorted(got.values(), reverse=True)[:2] == [4, 3]


def test_local_top_keys_ignores_invalid_runs():
    keys = jnp.array([3, 3, 3, 3, 1], dtype=jnp.int64)
    valid = jnp.array([True, False, False, False, True])
    top_keys, top_counts = local_top_keys(keys, valid, k=2)
    got = dict(zip(np.asarray(top_keys).tolist(),
                   np.asarray(top_counts).tolist()))
    assert got.get(3) == 1  # invalid duplicates not counted


def test_global_heavy_hitters_detects_planted_key():
    comm = dj.make_communicator("tpu", n_ranks=8)
    n_local = 128
    rows = 8 * n_local

    # Key 77 on ~half of all rows (spread over all ranks); rest unique.
    base = jnp.arange(rows, dtype=jnp.int64) + 1000
    hot = jnp.where(jnp.arange(rows) % 2 == 0, 77, base)

    def step(keys):
        hh = global_heavy_hitters(
            comm, keys, jnp.ones_like(keys, dtype=bool), k=8,
            threshold=jnp.int32(n_local // 2),
        )
        # all_gather results are replicated in value but shard_map
        # cannot statically infer that, so return them per-rank
        # (sharded out-spec concatenates the identical copies).
        return hh.keys, hh.counts, hh.slot_valid, mark_heavy(keys, hh)

    fn = comm.spmd(step, sharded_out=False)
    hk, hc, hv, is_hh = fn(hot)
    hk, hc, hv = np.asarray(hk), np.asarray(hc), np.asarray(hv)
    k = 8
    # Every rank computed the identical HH set.
    assert (hk.reshape(8, k) == hk[:k]).all()
    assert hv[0] and hk[0] == 77 and hc[0] == rows // 2
    assert hv[:k].sum() == 1  # nothing else crosses the threshold
    np.testing.assert_array_equal(
        np.asarray(is_hh), np.asarray(hot) == 77
    )


def _oracle(build, probe):
    return len(build.to_pandas().merge(probe.to_pandas(), on="key"))


@pytest.mark.slow
@pytest.mark.parametrize("over_decomposition", [1, 2])
def test_zipf_join_with_skew_handling(over_decomposition):
    comm = dj.make_communicator("tpu", n_ranks=8)
    rows, rand_max = 16384, 4096
    build = generate_build_table(
        jax.random.PRNGKey(0), 4096, rand_max, unique_keys=True
    )
    probe = generate_zipf_probe_table(
        jax.random.PRNGKey(1), rows, alpha=1.5, rand_max=rand_max
    )
    # alpha=1.5 puts ~90% of probe rows in the heavy hitters — beyond
    # the probe/8 and probe/4 default HH blocks, so rely on the
    # documented auto_retry contract: one skew retry jumps the HH
    # probe/out capacities straight to full local probe coverage.
    res = dj.distributed_inner_join(
        build, probe, comm,
        skew_threshold=0.05,
        hh_slots=32,
        out_capacity_factor=2.0,
        over_decomposition=over_decomposition,
        auto_retry=1,
    )
    assert not bool(res.overflow)
    assert int(res.total) == _oracle(build, probe)


@pytest.mark.slow
def test_zipf_skew_relieves_shuffle_padding():
    """The point of the skew path: a hot key that overflows the padded
    shuffle at a tight capacity factor must fit once HH rows bypass it."""
    comm = dj.make_communicator("tpu", n_ranks=8)
    rows, rand_max = 8192, 2048
    build = generate_build_table(
        jax.random.PRNGKey(0), 2048, rand_max, unique_keys=True
    )
    probe = generate_zipf_probe_table(
        jax.random.PRNGKey(1), rows, alpha=1.5, rand_max=rand_max
    )
    naive = dj.distributed_inner_join(
        build, probe, comm, shuffle_capacity_factor=1.3,
        out_capacity_factor=2.0, skew_threshold=0,  # the plain path
    )
    assert bool(naive.overflow)  # Zipf breaks naive padding

    skewed = dj.distributed_inner_join(
        build, probe, comm, shuffle_capacity_factor=1.3,
        out_capacity_factor=2.0, skew_threshold=0.05, hh_slots=32,
        auto_retry=1,  # HH output block; the SHUFFLE must fit as-is
    )
    assert not bool(skewed.overflow)
    assert int(skewed.total) == _oracle(build, probe)


@pytest.mark.slow
def test_auto_retry_recovers_from_overflow():
    comm = dj.make_communicator("tpu", n_ranks=8)
    rows, rand_max = 8192, 2048
    build = generate_build_table(
        jax.random.PRNGKey(0), 2048, rand_max, unique_keys=True
    )
    probe = generate_zipf_probe_table(
        jax.random.PRNGKey(1), rows, alpha=1.5, rand_max=rand_max
    )
    res = dj.distributed_inner_join(
        build, probe, comm, shuffle_capacity_factor=1.1,
        out_capacity_factor=1.2, auto_retry=4,
        skew_threshold=0,  # the plain path, so the ladder relieves it
    )
    assert not bool(res.overflow)
    assert int(res.total) == _oracle(build, probe)


def test_skew_path_agrees_with_plain_path_uniform():
    """With uniform keys (no real skew) the HH machinery must be a
    correctness no-op."""
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    comm = dj.make_communicator("tpu", n_ranks=8)
    build, probe = generate_build_probe_tables(
        seed=7, build_nrows=4096, probe_nrows=8192, selectivity=0.5
    )
    plain = dj.distributed_inner_join(
        build, probe, comm, out_capacity_factor=3.0
    )
    skewed = dj.distributed_inner_join(
        build, probe, comm, out_capacity_factor=3.0, skew_threshold=0.1
    )
    assert int(plain.total) == int(skewed.total) == _oracle(build, probe)
    assert not bool(skewed.overflow)


def test_hh_slots_exceeding_local_rows():
    """hh_slots larger than a shard must clamp, not crash (the default
    64 slots vs tiny smoke tables)."""
    comm = dj.make_communicator("tpu", n_ranks=8)
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    build, probe = generate_build_probe_tables(
        seed=3, build_nrows=128, probe_nrows=256, selectivity=0.5
    )
    res = dj.distributed_inner_join(
        build, probe, comm, skew_threshold=0.5, hh_slots=64,
        out_capacity_factor=4.0, shuffle_capacity_factor=4.0,
    )
    assert int(res.total) == _oracle(build, probe)


def test_sampled_detection_sees_periodic_heavy_key():
    """Detection samples 1/16 of rows via a multiplicative index mix —
    a heavy key living ONLY at odd positions (period-2 layout; a fixed
    [::16] stride would see positions 0 mod 16 only and miss it or
    16x-overcount it) must still be detected (review r4)."""
    import jax.numpy as jnp

    from distributed_join_tpu.parallel import skew
    import distributed_join_tpu as dj

    comm = dj.make_communicator("local")
    n = 1 << 17  # big enough that sampling engages (64*k*sample)
    keys = jnp.arange(n, dtype=jnp.int64)
    hot = jnp.where(jnp.arange(n) % 2 == 1, jnp.int64(7), keys)
    hh = skew.global_heavy_hitters(
        comm, hot, jnp.ones(n, bool), 64,
        threshold=jnp.int32(n // 10), sample=16,
    )
    import numpy as np
    ks = np.asarray(hh.keys)[np.asarray(hh.slot_valid)]
    assert 7 in ks.tolist()
    # and the scaled count estimate is in the right ballpark (half n)
    cnt = int(np.asarray(hh.counts)[np.asarray(hh.keys) == 7][0])
    assert n // 4 < cnt < n, cnt


# -- the entry resolves the skew path from the input -------------------


def _zipf_tables(seed, rows_per_rank, n_ranks=4):
    """The benchmark's Zipf alpha=1.5 foreign-key tables (unique build
    keys over [0, rows), probe keys Zipf over the same keys)."""
    from joinbench import run
    from joinbench.data import zipf

    rows = rows_per_rank * n_ranks
    cfg = {"build_rows": rows, "probe_rows": rows, "rand_max": rows,
           "zipf_alpha": 1.5, "key_dtype": "int64",
           "payload_dtype": "int64"}
    kd = run.seed_key_data(seed, 0)
    t = jax.jit(lambda kd: zipf.tables(cfg, jax.random.wrap_key_data(kd))
                )(kd)
    return tuple(Table(dict(t[s]["columns"]), t[s]["valid"])
                 for s in ("build", "probe"))


def _uniform_tables(rows=16384):
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    return generate_build_probe_tables(
        seed=7, build_nrows=rows, probe_nrows=rows, selectivity=0.3)


def _resolved(comm, build, probe, **opts):
    """The options the entry hands on after its skew resolution."""
    from distributed_join_tpu.parallel.distributed_join import (
        _resolve_skew,
    )

    build, probe = comm.device_put_sharded((build, probe))
    _resolve_skew(comm, build, probe, "key", opts)
    return opts


def _digest_matches_reference(res, build, probe):
    from joinbench import oracle

    names = ("key", ["build_payload"], ["probe_payload"])
    got = oracle.to_ints(oracle.digest(
        {k: np.asarray(v) for k, v in res.table.columns.items()},
        np.asarray(res.table.valid), *names, np))
    side = [{k: np.asarray(v)[np.asarray(t.valid)]
             for k, v in t.columns.items()} for t in (build, probe)]
    want = oracle.reference(*side, *names)
    return oracle.mismatches(got, want)


def test_entry_resolves_skew_on_for_zipf_keys():
    """No knob: Zipf alpha=1.5 probe keys against unique build keys
    would overflow the padded shuffle; the entry turns the skew path
    on, sized from the data, and the answer is the reference's."""
    comm = dj.make_communicator("tpu", n_ranks=4)
    build, probe = _zipf_tables(11, 10_000)
    plain = dj.distributed_inner_join(build, probe, comm, skew_threshold=0)
    assert bool(plain.overflow)
    res = dj.distributed_inner_join(build, probe, comm)
    assert not bool(res.overflow)
    assert _digest_matches_reference(res, build, probe) == []
    (attempt,) = res.retry_report.attempts
    # About 88% of the probe rows are heavy: the probe block is the
    # rank's rows, and each heavy probe row matches one build row.
    assert attempt.hh_probe_capacity == 10_000
    assert 10_000 <= attempt.hh_out_capacity < 11_000
    assert attempt.hh_build_capacity == 2048


@pytest.mark.parametrize("rows_per_rank", [10_000, 50_000],
                         ids=["whole", "sampled"])
def test_two_seeds_resolve_to_the_same_sizing(rows_per_rank):
    """Tables of one shape and distribution resolve alike, sampled or
    read whole, so one warmed program serves both."""
    comm = dj.make_communicator("tpu", n_ranks=4)
    a = _resolved(comm, *_zipf_tables(3, rows_per_rank))
    b = _resolved(comm, *_zipf_tables(2**40 + 5, rows_per_rank))
    assert a == b and a["skew_threshold"] > 0


def test_uniform_keys_resolve_off_to_the_plain_program():
    """Uniform keys resolve off, and what runs is the program of the
    explicit off setting: the same lowering, one program-cache entry."""
    from distributed_join_tpu.parallel.distributed_join import (
        make_distributed_join,
        resolve_join_ladder,
    )
    from distributed_join_tpu.service.programs import JoinProgramCache

    comm = dj.make_communicator("tpu", n_ranks=4)
    build, probe = _uniform_tables()
    auto = _resolved(comm, build, probe)
    off = _resolved(comm, build, probe, skew_threshold=0)
    assert auto == off == {}

    def lowered(opts):
        b, p = comm.device_put_sharded((build, probe))
        ladder = resolve_join_ladder(b, p, comm.n_ranks, dict(opts))
        return make_distributed_join(comm, key="key", **ladder.sizing(),
                                     **opts).lower(b, p).as_text()

    assert lowered(auto) == lowered(off)
    cache = JoinProgramCache(comm)
    for opts in ({}, {"skew_threshold": 0}):
        dj.distributed_inner_join(build, probe, comm, program_cache=cache,
                                  **opts)
    assert cache.traces == 1


@pytest.mark.parametrize("make_comm", [
    lambda: dj.make_communicator("tpu", n_ranks=1),
    lambda: dj.make_communicator("local"),
], ids=["tpu", "local"])
def test_one_rank_runs_no_prepass(make_comm, monkeypatch):
    from distributed_join_tpu.parallel import skew

    def no_prepass(*a, **k):
        raise AssertionError("the detection program ran")

    monkeypatch.setattr(skew, "resolve", no_prepass)
    comm = make_comm()
    build, probe = _zipf_tables(5, 16_000, n_ranks=1)
    res = dj.distributed_inner_join(build, probe, comm,
                                    out_capacity_factor=1.5)
    assert not bool(res.overflow)
    assert _digest_matches_reference(res, build, probe) == []


def _agg_spec():
    from distributed_join_tpu.ops.aggregate import AggregateSpec

    return AggregateSpec.of("key", [("count", None, "n")])


@pytest.mark.parametrize("opts", [
    {"join_type": "left"},
    {"aggregate": "count"},
    {"sort_mode": "segmented"},
], ids=["left", "aggregate", "segmented"])
def test_unsupported_shapes_skip_the_prepass(opts, monkeypatch):
    """Outer joins, aggregates and the segmented sort on Zipf keys run
    as before: no detection program, no new refusal."""
    from distributed_join_tpu.parallel import skew

    def no_prepass(*a, **k):
        raise AssertionError("the detection program ran")

    monkeypatch.setattr(skew, "resolve", no_prepass)
    if "aggregate" in opts:
        opts = {"aggregate": _agg_spec()}
    comm = dj.make_communicator("tpu", n_ranks=4)
    build, probe = _zipf_tables(9, 4_000)
    dj.distributed_inner_join(build, probe, comm, auto_retry=3,
                              out_capacity_factor=2.0, **opts)


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_skew_resolve_span_carries_what_it_found(tmp_path):
    """The host reads the detection program's result under
    ``entry.skew_resolve``, whose arguments say whether the path turned
    on, the heavy probe rows and the blocks' sizes."""
    import glob

    from jax.profiler import ProfileData

    comm = dj.make_communicator("tpu", n_ranks=4)
    zipf_tables = _zipf_tables(11, 10_000)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for build, probe in (zipf_tables, _uniform_tables()):
            dj.distributed_inner_join(build, probe, comm)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    args = [dict(e.stats) for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events if e.name == "entry.skew_resolve"]
    assert [a["on"] for a in args] == [1, 0]
    on, off = args
    assert 8_000 < on["hh_probe_rows"] <= 10_000
    assert on["hh_probe_capacity"] == 10_000
    assert on["hh_build_capacity"] == 2048
    assert on["fullest_bucket"] > on["bucket_capacity"]
    assert off["fullest_bucket"] <= off["bucket_capacity"]
    assert off["hh_probe_capacity"] == off["hh_out_capacity"] == 0
