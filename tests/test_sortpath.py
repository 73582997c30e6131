"""Segmented-sort join pipeline (docs/ROOFLINE.md §9; ISSUE 14).

Acceptance bars: the segmented path is bit-exact (full-content
multiset) against BOTH the flat path and the pandas oracle across
padded/ppermute/hierarchical, k>1, skew, string keys, and every
segment-boundary edge case (empty segments, single segment = flat
parity, non-dividing counts); unsupported combinations refuse with
named reasons; the segmented wire-byte and segment-count predictions
are EXACT vs the device counters with plan digest == program-cache
key; and the round-4 kernel-path cliff stays locked (the
``_kernel_path_ok`` eligibility arithmetic across the 2^24 boundary).
The two ROADMAP-item-2 satellites ride along: the fused-build expand's
window width decoupled from block size, and the fallback's rank gather
chunked onto u32 half-planes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from distributed_join_tpu import planning
from distributed_join_tpu.ops.segmented import (
    MIN_SEGMENT_CAPACITY,
    SEGMENT_TARGET_RUN,
    resolve_sort_segments,
    segment_capacity,
)
from distributed_join_tpu.parallel.communicator import (
    HierarchicalTpuCommunicator,
    TpuCommunicator,
)
from distributed_join_tpu.parallel.distributed_join import (
    JOIN_METRICS_SHARDED_OUT,
    JOIN_SHARDED_OUT,
    distributed_inner_join,
    make_join_step,
    make_probe_join_step,
)
from distributed_join_tpu.table import Table
from distributed_join_tpu.utils.generators import (
    generate_build_probe_tables,
)

pytestmark = pytest.mark.sortpath


@pytest.fixture(scope="module")
def comm8():
    assert len(jax.devices()) >= 8
    return TpuCommunicator(n_ranks=8)


@pytest.fixture(scope="module")
def tables8(comm8):
    build, probe = generate_build_probe_tables(
        seed=7, build_nrows=4096, probe_nrows=8192, rand_max=2000,
        selectivity=0.5)
    return comm8.device_put_sharded((build, probe))


def _normalize(df):
    cols = sorted(df.columns)
    return (df[cols].sort_values(cols).reset_index(drop=True)
            .astype("int64"))


def _run(comm, build, probe, key="key", **opts):
    step = make_join_step(comm, key=key,
                          **{"out_capacity_factor": 4.0, **opts})
    fn = comm.spmd(step, sharded_out=JOIN_SHARDED_OUT)
    res = fn(build, probe)
    return res


def _frames(res):
    return _normalize(res.table.to_pandas())


def _oracle(build, probe, key="key"):
    keys = [key] if isinstance(key, str) else list(key)
    return _normalize(
        build.to_pandas().merge(probe.to_pandas(), on=keys))


# -- segment-count resolution (THE shared owner) ----------------------


def test_resolve_sort_segments_explicit_and_invalid():
    assert resolve_sort_segments(5, 10**6, 8, 1, 1.6) == 5
    assert resolve_sort_segments(1, 10**6, 8, 1, 1.6) == 1
    with pytest.raises(ValueError, match="sort_segments"):
        resolve_sort_segments(0, 10**6, 8, 1, 1.6)


def test_resolve_sort_segments_auto_targets_run_length():
    # Small shapes stay flat (run already under the target)...
    assert resolve_sort_segments(None, 1000, 8, 1, 1.6) == 1
    # ...spec-scale shapes segment until the run fits the §6 regime.
    s = resolve_sort_segments(None, 2_500_000, 8, 1, 1.6)
    assert s > 1
    run = 8 * segment_capacity(2_500_000, 8, 1, s, 1.6)
    assert run <= SEGMENT_TARGET_RUN
    # ...and never below the fine-bucket floor.
    assert segment_capacity(2_500_000, 8, 1, s, 1.6) \
        >= MIN_SEGMENT_CAPACITY


# -- multiset exactness vs flat and the pandas oracle -----------------


@pytest.mark.parametrize("opts", [
    dict(sort_segments=4),
    dict(sort_segments=4, shuffle="ppermute"),
    dict(sort_segments=4, over_decomposition=2,
         shuffle_capacity_factor=3.0),
    dict(sort_segments=3),                      # non-power-of-two
    dict(sort_segments=16, shuffle_capacity_factor=4.0),
])
def test_segmented_matches_flat_and_oracle(comm8, tables8, opts):
    build, probe = tables8
    flat = _run(comm8, build, probe,
                **{k: v for k, v in opts.items()
                   if k not in ("sort_segments",)})
    seg = _run(comm8, build, probe, sort_mode="segmented", **opts)
    assert not bool(flat.overflow) and not bool(seg.overflow)
    assert int(seg.total) == int(flat.total)
    want = _oracle(build, probe)
    pd.testing.assert_frame_equal(_frames(seg), want)
    pd.testing.assert_frame_equal(_frames(flat), want)


def test_segmented_duplicate_heavy_keys(comm8):
    build, probe = generate_build_probe_tables(
        seed=11, build_nrows=2048, probe_nrows=4096, rand_max=64,
        selectivity=0.8)
    build, probe = comm8.device_put_sharded((build, probe))
    seg = _run(comm8, build, probe, sort_mode="segmented",
               sort_segments=4, shuffle_capacity_factor=6.0,
               out_capacity_factor=200.0)
    assert not bool(seg.overflow)
    pd.testing.assert_frame_equal(_frames(seg),
                                  _oracle(build, probe))


def test_segmented_skew_sidecar(comm8, tables8):
    build, probe = tables8
    seg = _run(comm8, build, probe, sort_mode="segmented",
               sort_segments=4, skew_threshold=0.01)
    assert not bool(seg.overflow)
    pd.testing.assert_frame_equal(_frames(seg),
                                  _oracle(build, probe))


def test_segmented_hierarchical_mesh(comm8, tables8):
    hcomm = HierarchicalTpuCommunicator(n_slices=2, n_ranks=8)
    build, probe = tables8
    seg = _run(hcomm, build, probe, shuffle="hierarchical",
               dcn_codec="off", sort_mode="segmented",
               sort_segments=4)
    assert not bool(seg.overflow)
    pd.testing.assert_frame_equal(_frames(seg),
                                  _oracle(build, probe))
    # The two-tier wire accounting must stay EXACT vs the plan —
    # both hops billed, per-tier counters included (the flat
    # hierarchical discipline, one resolution level down).
    opts = dict(shuffle="hierarchical", dcn_codec="off",
                sort_mode="segmented", sort_segments=4,
                out_capacity_factor=4.0)
    plan = planning.build_plan(hcomm, build, probe,
                               with_metrics=True, **opts)
    step = make_join_step(hcomm, with_metrics=True, **opts)
    _, metrics = hcomm.spmd(
        step, sharded_out=JOIN_METRICS_SHARDED_OUT)(build, probe)
    red = metrics.to_dict()["reduced"]
    for side in ("build", "probe"):
        assert plan.wire[side]["bytes_per_rank"] * 8 \
            == red[f"{side}.wire_bytes"], side
        assert plan.wire[side]["ici_bytes_per_rank"] * 8 \
            == red[f"{side}.wire_bytes_ici"], side
        assert plan.wire[side]["dcn_bytes_per_rank"] * 8 \
            == red[f"{side}.wire_bytes_dcn"], side


def test_segmented_string_key(comm8):
    from distributed_join_tpu.utils.strings import encode_int_strings

    build, probe = generate_build_probe_tables(
        seed=9, build_nrows=2048, probe_nrows=4096, rand_max=1500,
        selectivity=0.5)

    def stringify(t):
        ids = np.asarray(t.columns["key"])
        b, l = encode_int_strings(ids, prefix="itm-", digits=8)
        cols = {k: v for k, v in t.columns.items() if k != "key"}
        cols["skey"] = b
        cols["skey#len"] = l
        return Table(cols, t.valid)

    build, probe = stringify(build), stringify(probe)
    build, probe = comm8.device_put_sharded((build, probe))
    flat = _run(comm8, build, probe, key="skey")
    seg = _run(comm8, build, probe, key="skey", sort_mode="segmented",
               sort_segments=4, shuffle_capacity_factor=3.0)
    assert not bool(seg.overflow)
    assert int(seg.total) == int(flat.total)

    def norm(res):
        df = res.table.to_pandas()
        cols = sorted(df.columns)
        return df[cols].sort_values(cols).reset_index(drop=True)

    pd.testing.assert_frame_equal(norm(seg), norm(flat))


# -- segment-boundary edge cases --------------------------------------


def test_empty_segments_on_sparse_key_domain(comm8):
    # 16 distinct keys into 8 ranks x 8 segments = 64 fine classes:
    # most (source, segment) fine buckets are EMPTY on every source.
    build, probe = generate_build_probe_tables(
        seed=3, build_nrows=1024, probe_nrows=1024, rand_max=16,
        selectivity=1.0)
    build, probe = comm8.device_put_sharded((build, probe))
    # A rank's couple of surviving keys can land in ONE segment, so
    # the per-segment output block needs the whole rank's fan-out.
    # Sparse domains concentrate: a fine bucket holds WHOLE keys, so
    # both the per-fine-bucket and per-segment-output contracts need
    # key-granular headroom here.
    seg = _run(comm8, build, probe, sort_mode="segmented",
               sort_segments=8, shuffle_capacity_factor=40.0,
               out_capacity_factor=1600.0)
    assert not bool(seg.overflow)
    pd.testing.assert_frame_equal(_frames(seg),
                                  _oracle(build, probe))


def test_single_segment_lowers_byte_identical_to_flat(comm8, tables8):
    """sort_segments=1 (and a one-segment auto resolution) IS the flat
    program — lowering-locked, not just result-equal (the
    degenerate-hierarchy discipline)."""
    build, probe = tables8

    def lowered(**opts):
        step = make_join_step(comm8, out_capacity_factor=4.0, **opts)
        return comm8.spmd(step, sharded_out=JOIN_SHARDED_OUT).lower(
            build, probe).as_text()

    assert lowered(sort_mode="segmented", sort_segments=1) \
        == lowered()
    # The auto resolution at this small shape is one segment too.
    assert lowered(sort_mode="segmented") == lowered()


def test_segment_count_not_dividing_capacity(comm8, tables8):
    # p_local=1024, 3 segments: 1024/(8*3) rounds up per fine bucket
    # — nothing divides anything, capacities round per fine bucket.
    build, probe = tables8
    seg = _run(comm8, build, probe, sort_mode="segmented",
               sort_segments=3)
    assert not bool(seg.overflow)
    pd.testing.assert_frame_equal(_frames(seg),
                                  _oracle(build, probe))


def test_segmented_overflow_ladder_recovers(comm8, tables8):
    build, probe = tables8
    # Deliberately tiny per-segment blocks: the fine buckets overflow,
    # the flag fires (rows dropped LOUDLY), and the ladder escalates
    # back to oracle-exact.
    res = _run(comm8, build, probe, sort_mode="segmented",
               sort_segments=16, shuffle_capacity_factor=0.4)
    assert bool(res.overflow)
    res2 = distributed_inner_join(
        build, probe, comm8, auto_retry=6, sort_mode="segmented",
        sort_segments=16, shuffle_capacity_factor=0.4,
        out_capacity_factor=4.0)
    assert not bool(res2.overflow)
    assert res2.retry_report.n_attempts > 1
    pd.testing.assert_frame_equal(_frames(res2),
                                  _oracle(build, probe))


# -- refusal contract -------------------------------------------------


def test_refusals_are_named_never_silent(comm8):
    with pytest.raises(ValueError, match="static"):
        make_join_step(comm8, sort_mode="segmented", shuffle="ragged")
    with pytest.raises(ValueError, match="codec"):
        make_join_step(comm8, sort_mode="segmented",
                       compression_bits=16)
    with pytest.raises(ValueError, match="kernel_config"):
        make_join_step(comm8, sort_mode="segmented",
                       kernel_config=object())
    with pytest.raises(ValueError, match="sort_mode"):
        make_join_step(comm8, sort_mode="sometimes")
    with pytest.raises(ValueError, match="sort_segments"):
        make_join_step(comm8, sort_mode="segmented", sort_segments=0)
    from distributed_join_tpu.ops import aggregate as agg_ops

    with pytest.raises(agg_ops.AggregatePushdownUnsupported,
                       match="segmented"):
        make_join_step(
            comm8, sort_mode="segmented",
            aggregate=agg_ops.AggregateSpec.of(
                ["key"], [("count", None, "n")]))
    with pytest.raises(ValueError, match="resident"):
        make_probe_join_step(comm8, sort_mode="segmented")
    hcomm = HierarchicalTpuCommunicator(n_slices=2, n_ranks=8)
    with pytest.raises(ValueError, match="DCN codec"):
        make_join_step(hcomm, sort_mode="segmented",
                       shuffle="hierarchical", dcn_codec="on")


def test_plan_mirrors_refusals(comm8, tables8):
    build, probe = tables8
    with pytest.raises(ValueError, match="static"):
        planning.build_plan(comm8, build, probe,
                            sort_mode="segmented", shuffle="ragged")
    with pytest.raises(ValueError, match="codec"):
        planning.build_plan(comm8, build, probe,
                            sort_mode="segmented",
                            compression_bits=16)
    with pytest.raises(ValueError, match="sort_mode"):
        planning.build_plan(comm8, build, probe,
                            sort_mode="sometimes")


# -- plan == program: exact wire, segment count, digest ---------------


def test_segmented_plan_wire_and_digest_exact(comm8, tables8):
    from distributed_join_tpu.service.programs import JoinProgramCache

    build, probe = tables8
    opts = dict(sort_mode="segmented", sort_segments=4,
                out_capacity_factor=4.0)
    plan = planning.build_plan(comm8, build, probe, with_metrics=True,
                               **opts)
    assert plan.capacities["sort_segments"] == 4
    # One level down: per-bucket capacity == segments x per-segment.
    assert plan.capacities["shuffle_build_per_bucket"] == \
        4 * plan.capacities["shuffle_build_per_segment"]
    step = make_join_step(comm8, with_metrics=True, **opts)
    _, metrics = comm8.spmd(
        step, sharded_out=JOIN_METRICS_SHARDED_OUT)(build, probe)
    red = metrics.to_dict()["reduced"]
    for side in ("build", "probe"):
        assert plan.wire[side]["bytes_per_rank"] * 8 \
            == red[f"{side}.wire_bytes"], side
    # Segment-count prediction vs the device-reported static stamp
    # (the counter sums the per-rank constant across 8 ranks).
    assert red["sort_segments"] == 4 * 8
    # Plan digest == program-cache key (the EXPLAIN contract).
    cache = JoinProgramCache(comm8)
    fn, _ = cache.get(build, probe, with_metrics=True, **opts)
    assert fn.signature.digest() == plan.digest
    # The cost model prices the batched short-run sort below the flat
    # superlinear rate (the new refittable constant).
    flat_plan = planning.build_plan(comm8, build, probe,
                                    with_metrics=True,
                                    out_capacity_factor=4.0)
    assert plan.cost["stages"]["join"] \
        < flat_plan.cost["stages"]["join"]
    assert "sort_run_ns_per_elem" in plan.cost["model"]


def test_sort_run_constant_refits_only_from_segmented_profiles():
    """The per-mode attribution discipline (the DCN precedent): a
    SEGMENTED profile's join ratio refits sort_run_ns_per_elem and
    nothing else; a FLAT profile — no batched short-run sort ever ran
    — refits the other join constants and never touches it."""
    from distributed_join_tpu.planning.cost import (
        CostModel,
        calibrate_from_stage_profile,
    )

    base = CostModel()

    def prof(segs, ratio):
        return {
            "kind": "stageprofile", "platform": "tpu",
            "overflow": False, "sort_segments": segs,
            "stages": {"join": {"ran": True, "wall_s": 0.1 * ratio,
                                "predicted_s": 0.1}},
        }

    seg_model, seg_report = calibrate_from_stage_profile(prof(8, 2.0))
    assert seg_report["calibrated"]
    assert seg_report["sort_run_scale"] == pytest.approx(2.0)
    assert seg_model.sort_run_ns_per_elem \
        == pytest.approx(base.sort_run_ns_per_elem * 2.0)
    # ...and the segmented evidence never refits the flat-owned join
    # constants.
    assert seg_model.scan_ns_per_elem == base.scan_ns_per_elem

    flat_model, flat_report = calibrate_from_stage_profile(
        prof(1, 3.0))
    assert flat_report["calibrated"]
    assert flat_report["sort_run_scale"] is None
    assert flat_model.sort_run_ns_per_elem \
        == base.sort_run_ns_per_elem
    assert flat_model.scan_ns_per_elem \
        == pytest.approx(base.scan_ns_per_elem * 3.0)
    assert "sort_run_ns_per_elem" not in flat_report["refit"]["join"]


def test_segmented_integrity_digests_verify_clean(comm8, tables8):
    from distributed_join_tpu.parallel import integrity

    build, probe = tables8
    step = make_join_step(comm8, sort_mode="segmented",
                          sort_segments=4, out_capacity_factor=4.0,
                          with_integrity=True)
    _, metrics = comm8.spmd(
        step, sharded_out=JOIN_METRICS_SHARDED_OUT)(build, probe)
    assert integrity.verify_digests(metrics).ok


# -- the round-4 kernel-path cliff guard (satellite) ------------------


def test_kernel_path_eligibility_locked_across_2e24():
    """Regression guard for the round-4 path cliff: the fused-kernel
    eligibility arithmetic (`_kernel_path_ok`) must NOT change across
    the 16,777,216-row boundary the old f32-exact gate bisected to —
    a future refactor silently re-dropping spec-scale joins onto the
    XLA path is exactly the 3-4x cliff ROOFLINE §7 measured. Shape
    metadata only (int8 keys), no 16M-row arrays materialized."""
    from distributed_join_tpu.ops.join import _kernel_path_ok
    from distributed_join_tpu.ops.kernel_config import KernelConfig

    class _Shape:
        def __init__(self, n):
            self.columns = {"key": jax.ShapeDtypeStruct((n,),
                                                        jnp.int8)}
            self.capacity = n
            self.valid = None

    cfg = KernelConfig(expand="pallas")  # force-enabled; CPU=interpret
    boundary = 16_777_216
    verdicts = {}
    for n in (boundary - 8, boundary, boundary + 8, 2 * boundary):
        use, _ = _kernel_path_ok(_Shape(n), _Shape(n), ["key"],
                                 [], [], n, n, n, cfg)
        verdicts[n] = use
    # Eligible on BOTH sides of the boundary — the gate has no 2^24
    # clause left; only the int32 domain bound may disqualify.
    assert all(verdicts.values()), verdicts
    big = 2**30 + 8
    use, _ = _kernel_path_ok(_Shape(big), _Shape(big), ["key"],
                             [], [], big, big, big, cfg)
    assert not use, "int32 merged-domain bound must still gate"


# -- expand window decoupling + chunked rank gather (satellites) ------


def test_chunked_rank_gather_bit_exact():
    from distributed_join_tpu.ops.join import _chunked_rank_gather

    rng = np.random.default_rng(1)
    lanes = [jnp.asarray(rng.integers(0, 2**64, size=5000,
                                      dtype=np.uint64))
             for _ in range(3)]
    idx = jnp.asarray(rng.integers(0, 5000, size=2000,
                                   dtype=np.int32))
    for got, lane in zip(_chunked_rank_gather(lanes, idx), lanes):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(lane)[np.asarray(idx)])
    # single-lane fast path
    got1 = _chunked_rank_gather(lanes[:1], idx)[0]
    np.testing.assert_array_equal(
        np.asarray(got1), np.asarray(lanes[0])[np.asarray(idx)])


def test_expand_window_decouples_from_block():
    """ROADMAP item 2a: a wider `window` (a) relaxes exactly the
    build_windows_ok bound that forces the gather fallback on
    gap-heavy data, and (b) keeps the kernel exact — without touching
    the block size (whose scaling hits the scoped-vmem wall)."""
    from test_expand_pallas import _make_join_records

    from distributed_join_tpu.ops.expand_pallas import (
        build_windows_ok,
        expand_gather,
        expand_gather_reference,
    )

    rng = np.random.default_rng(2)
    # Huge unmatched-build gaps between matched keys: the classic
    # window-2 breaker.
    key_specs = [(2, 2), (900, 0), (2, 2)] * 3
    out_cap = 4096
    S, lo, cols, bcols, rank_want, total = _make_join_records(
        rng, key_specs, out_cap, kb=2)
    assert not bool(build_windows_ok(S, lo, out_cap, block=256))
    assert bool(build_windows_ok(S, lo, out_cap, block=256,
                                 window=4096))
    rec_outs, _sb, _rank, build_outs = expand_gather(
        S, cols, out_cap, jnp.int32(total), block=256, interpret=True,
        lo=lo, build_cols=bcols, window=4096)
    want_rec = expand_gather_reference(S, cols, out_cap)
    np.testing.assert_array_equal(
        np.asarray(rec_outs[0])[:total],
        np.asarray(want_rec[0])[:total])
    for bo, bc in zip(build_outs, bcols):
        np.testing.assert_array_equal(
            np.asarray(bo)[:total], np.asarray(bc)[rank_want[:total]])


def test_kernel_config_window_field():
    import dataclasses

    from distributed_join_tpu.ops.kernel_config import KernelConfig

    cfg = KernelConfig(window=2048)
    assert cfg.window == 2048
    with pytest.raises(ValueError, match="window"):
        KernelConfig(window=0)
    # repr participates in the program-cache signature: two windows
    # must never alias one entry.
    assert repr(cfg) != repr(dataclasses.replace(cfg, window=4096))


# -- tuner: sort_mode as a structural knob from stage history ---------


def _trend_entry(sig, join_share):
    other = (1.0 - join_share) / 2
    return {
        "kind": "request", "signature": sig, "outcome": "ok",
        "wall_s": 1.0, "rung": 0, "n_attempts": 1,
        "resolved_knobs": {"shuffle_capacity_factor": 1.6},
        "stages": {"wall_s": {"partition": other, "shuffle": other,
                              "join": join_share}},
    }


def test_tuner_fills_sort_mode_from_stage_history():
    from distributed_join_tpu.planning.tuner import JoinTuner

    tuner = JoinTuner(min_entries=1)
    tuner.observe_entry(_trend_entry("sig1", 0.8))
    geometry = {"nb": 8, "n_ranks": 8, "b_local": 2_500_000,
                "p_local": 2_500_000,
                "row_bytes": {"build": 16, "probe": 16}}
    cfg = tuner.recommend("sig1", user_opts={},
                          side_geometry=geometry)
    assert cfg.structural.get("sort_mode") == "segmented"
    assert cfg.basis["sort_mode"]["segments"] > 1
    # Caller's explicit choice is never overridden...
    cfg2 = tuner.recommend("sig1", user_opts={"sort_mode": "flat"},
                           side_geometry=geometry)
    assert "sort_mode" not in cfg2.structural
    # ...ragged / compressed / aggregate workloads never get it...
    for bad in ({"shuffle": "ragged"}, {"compression_bits": 16},
                {"aggregate": object()}):
        cfg3 = tuner.recommend("sig1", user_opts=bad,
                               side_geometry=geometry)
        assert "sort_mode" not in cfg3.structural, bad
    # ...and a shape whose resolution is one segment stays flat.
    small = dict(geometry, b_local=1000, p_local=1000)
    cfg4 = tuner.recommend("sig1", user_opts={}, side_geometry=small)
    assert "sort_mode" not in cfg4.structural
    # A sort-light trend never flips the knob.
    tuner2 = JoinTuner(min_entries=1)
    tuner2.observe_entry(_trend_entry("sig2", 0.2))
    cfg5 = tuner2.recommend("sig2", user_opts={},
                            side_geometry=geometry)
    assert "sort_mode" not in cfg5.structural
    # A hierarchical multi-slice workload whose DCN codec resolves ON
    # (the "auto" default) refuses segmented — the fill must not
    # produce a config the step errors on...
    hgeom = dict(geometry, n_slices=2)
    cfg6 = tuner.recommend("sig1",
                           user_opts={"shuffle": "hierarchical"},
                           side_geometry=hgeom)
    assert "sort_mode" not in cfg6.structural
    # ...but with the codec explicitly off the combination compiles
    # and the evidence-backed fill applies.
    cfg7 = tuner.recommend("sig1",
                           user_opts={"shuffle": "hierarchical",
                                      "dcn_codec": "off"},
                           side_geometry=hgeom)
    assert cfg7.structural.get("sort_mode") == "segmented"


def test_resolve_sort_mode_auto_compiles():
    """--sort-mode auto must pick a config that RUNS: ragged and a
    codec-armed hierarchical mesh resolve flat; a plain padded
    spec-scale shape resolves segmented (docs/ROOFLINE.md §9)."""
    import argparse

    from distributed_join_tpu.benchmarks import resolve_sort_mode

    args = argparse.Namespace(sort_mode="auto", sort_segments=None)
    big = 2_500_000
    assert resolve_sort_mode(args, 8, 1, big, big, 1.6,
                             "padded") == "segmented"
    assert resolve_sort_mode(args, 8, 1, big, big, 1.6,
                             "ragged") == "flat"
    assert resolve_sort_mode(args, 8, 1, big, big, 1.6,
                             "hierarchical", n_slices=2,
                             dcn_codec="auto") == "flat"
    assert resolve_sort_mode(args, 8, 1, big, big, 1.6,
                             "hierarchical", n_slices=2,
                             dcn_codec="off") == "segmented"
    assert resolve_sort_mode(args, 8, 1, 1000, 1000, 1.6,
                             "padded") == "flat"


def test_flat_mode_refuses_sort_segments(comm8, tables8):
    """sort_segments under flat must refuse loudly — the flat
    pipeline never reads it, and silently ignoring it would cache
    one byte-identical program per value (the kernel_config
    rationale, symmetrically)."""
    build, probe = tables8
    with pytest.raises(ValueError, match="sort_segments applies"):
        make_join_step(comm8, sort_segments=4)
    with pytest.raises(ValueError, match="sort_segments applies"):
        planning.build_plan(comm8, build, probe, sort_segments=4)


def test_service_serves_segmented_over_wire(comm8):
    """The daemon path: sort_mode/sort_segments ride the wire query
    spec (_WIRE_JOIN_OPTS) — a segmented wire request runs the
    segmented program (never a silent flat fallback) and a warm
    repeat is a zero-trace dispatch."""
    from distributed_join_tpu.service.server import (
        _WIRE_JOIN_OPTS,
        JoinService,
        ServiceConfig,
        _join_opts_from_spec,
    )

    assert "sort_mode" in _WIRE_JOIN_OPTS
    assert "sort_segments" in _WIRE_JOIN_OPTS
    opts = _join_opts_from_spec(
        {"sort_mode": "segmented", "sort_segments": 4, "seed": 3})
    assert opts == {"sort_mode": "segmented", "sort_segments": 4}
    build, probe = generate_build_probe_tables(
        seed=29, build_nrows=2048, probe_nrows=2048, rand_max=1024,
        selectivity=0.5)
    service = JoinService(comm8, ServiceConfig())
    res = service.join(build, probe, out_capacity_factor=3.0,
                       shuffle_capacity_factor=3.0, **opts)
    want = len(build.to_pandas().merge(probe.to_pandas(), on="key"))
    assert int(res.total) == want
    warm = service.join(build, probe, out_capacity_factor=3.0,
                        shuffle_capacity_factor=3.0, **opts)
    assert int(warm.total) == want
    assert warm.new_traces == 0


# -- serving: warm segmented repeats are zero-trace -------------------


def test_segmented_program_serves_warm(comm8, tables8):
    from distributed_join_tpu.service.programs import JoinProgramCache

    build, probe = tables8
    cache = JoinProgramCache(comm8)
    opts = dict(sort_mode="segmented", sort_segments=4,
                out_capacity_factor=4.0)
    fn1, _ = cache.get(build, probe, **opts)
    r1 = fn1(build, probe)
    traces = cache.traces
    fn2, _ = cache.get(build, probe, **opts)
    r2 = fn2(build, probe)
    assert cache.traces == traces, "warm repeat re-traced"
    assert int(r1.total) == int(r2.total)
    # flat and segmented key DISTINCT entries (sort_mode is part of
    # the signature by construction).
    fn3, _ = cache.get(build, probe, out_capacity_factor=4.0)
    assert fn3.signature != fn1.signature
