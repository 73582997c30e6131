"""EXPLAIN / plan introspection / cost model (distributed_join_tpu/
planning) on the 8-virtual-device CPU mesh.

Four contracts (docs/OBSERVABILITY.md "Explain & cost model"):

- **Determinism.** The same query spec yields a byte-identical
  explain artifact — no timestamps, no float jitter.
- **Plan == cache key.** A plan's digest equals the program cache's
  signature digest for the join it predicts, on both the dry-run
  surface (``explain_join``) and the attached-result surface
  (``distributed_inner_join(explain=True)``).
- **Padded wire bytes are EXACT.** For the static-block shuffle modes
  (padded, compressed) the predicted wire bytes equal the measured
  device counter to the byte, across over-decomposition, compression
  and skew configs — the CI gate, not a dashboard estimate.
- **Dry-run costs nothing.** The service ``explain`` op (and
  ``explain_join`` generally) traces and compiles NOTHING.
"""

import json
import subprocess
import sys

import pytest

import jax

from distributed_join_tpu import planning, telemetry
from distributed_join_tpu.parallel.communicator import TpuCommunicator
from distributed_join_tpu.parallel.distributed_join import (
    JOIN_METRICS_SHARDED_OUT,
    distributed_inner_join,
    make_join_step,
)
from distributed_join_tpu.service.programs import JoinProgramCache
from distributed_join_tpu.telemetry import analyze, history
from distributed_join_tpu.utils.generators import (
    generate_build_probe_tables,
)

pytestmark = pytest.mark.explain


@pytest.fixture(autouse=True)
def _no_leaked_session():
    telemetry.finalize()
    yield
    telemetry.finalize()


@pytest.fixture(scope="module")
def comm():
    return TpuCommunicator(n_ranks=8)


@pytest.fixture(scope="module")
def tables():
    return generate_build_probe_tables(
        seed=42, build_nrows=1024, probe_nrows=1024, selectivity=0.3)


# -- determinism ------------------------------------------------------


def test_explain_record_is_byte_deterministic(comm, tables):
    b, p = tables
    docs = [
        json.dumps(
            planning.explain_join(
                b, p, comm, out_capacity_factor=3.0).explain_record(),
            indent=1, sort_keys=True)
        for _ in range(2)
    ]
    assert docs[0] == docs[1]
    # and it round-trips as the schema-checked artifact kind
    doc = json.loads(docs[0])
    assert doc["kind"] == "explain"
    assert doc["plan"]["pipeline"] == "join"


def test_exchange_plan_deterministic_and_valid():
    d1 = planning.build_exchange_plan(8, 1 << 20)
    d2 = planning.build_exchange_plan(8, 1 << 20)
    assert json.dumps(d1, sort_keys=True) == json.dumps(d2,
                                                       sort_keys=True)
    assert d1["plan"]["pipeline"] == "all_to_all"
    assert d1["plan"]["wire"]["bytes_total"] == 8 * (1 << 20)


# -- plan == cache key ------------------------------------------------


def test_plan_digest_equals_cache_key_dry_run(comm, tables):
    b, p = tables
    cache = JoinProgramCache(comm)
    plan = planning.explain_join(b, p, comm, out_capacity_factor=3.0)
    # the signature the first ladder rung would key under (the same
    # resolution distributed_inner_join applies)
    sig = cache.signature(
        b, p, key="key", with_integrity=False,
        metrics_static={"retry_attempt_max": 0},
        shuffle_capacity_factor=1.6, out_capacity_factor=3.0,
        out_rows_per_rank=None, compression_bits=None,
        hh_build_capacity=None, hh_probe_capacity=None,
        hh_out_capacity=None)
    assert plan.digest == sig.digest()


def test_inner_join_explain_attaches_plan_matching_cache(comm, tables):
    b, p = tables
    cache = JoinProgramCache(comm)
    res = distributed_inner_join(b, p, comm, key="key",
                                 out_capacity_factor=3.0,
                                 program_cache=cache, explain=True)
    assert int(res.total) > 0
    plan = res.plan
    # exactly one resident entry — its key IS the plan digest
    (sig,) = list(cache._entries)
    assert plan.digest == sig.digest()
    # and a dry-run explain of the same call agrees
    dry = planning.explain_join(b, p, comm, out_capacity_factor=3.0)
    assert dry.digest == plan.digest
    # the cache-hit prediction now says resident
    assert cache.predict_hit(plan.digest)["resident"]


# -- exact wire-byte prediction (the CI gate's contract) --------------


@pytest.mark.parametrize("opts", [
    {},
    {"over_decomposition": 2},
    {"compression_bits": 16},
    {"skew_threshold": 0.01},
], ids=["padded", "overdecomp", "compressed", "skew"])
def test_padded_wire_bytes_exact(comm, tables, opts):
    b, p = tables
    step_opts = dict(key="key", out_capacity_factor=3.0,
                     with_metrics=True, **opts)
    step = make_join_step(comm, **step_opts)
    _, metrics = comm.spmd(
        step, sharded_out=JOIN_METRICS_SHARDED_OUT)(b, p)
    red = metrics.to_dict()["reduced"]
    plan = planning.build_plan(comm, b, p, **step_opts)
    assert plan.wire["exact"]
    assert plan.wire["build"]["bytes_total"] == red["build.wire_bytes"]
    assert plan.wire["probe"]["bytes_total"] == red["probe.wire_bytes"]
    if not opts:
        # Rows are an ESTIMATE in general (a clamped bucket undercounts
        # and raises overflow; skew routes HH rows around the shuffle)
        # — but the clamp-free dense base case lands exactly.
        assert (plan.wire["build"]["rows_estimate"]
                == red["build.rows_shuffled"])


def test_ragged_plan_is_estimate(comm, tables):
    b, p = tables
    plan = planning.build_plan(comm, b, p, key="key", shuffle="ragged",
                               out_capacity_factor=3.0)
    assert not plan.wire["exact"]
    assert plan.wire["build"]["bytes_total"] > 0


def test_single_rank_plan_has_no_wire():
    comm1 = TpuCommunicator(n_ranks=1)
    b, p = generate_build_probe_tables(
        seed=7, build_nrows=256, probe_nrows=256, selectivity=0.5)
    plan = planning.explain_join(b, p, comm1, out_capacity_factor=3.0)
    assert plan.wire["build"]["bytes_total"] == 0
    assert plan.cost["stages"]["shuffle"] == 0.0
    assert plan.cost["total_s"] > 0


# -- grading (EXPLAIN ANALYZE) ----------------------------------------


def _graded(comm, tables, **opts):
    b, p = tables
    step_opts = dict(key="key", out_capacity_factor=3.0,
                     with_metrics=True, **opts)
    step = make_join_step(comm, **step_opts)
    _, metrics = comm.spmd(
        step, sharded_out=JOIN_METRICS_SHARDED_OUT)(b, p)
    plan = planning.build_plan(comm, b, p, **step_opts)
    return plan.explain_record(), metrics.to_dict()


def test_grade_explain_match_and_mismatch(comm, tables):
    doc, metrics = _graded(comm, tables)
    grade = analyze.grade_explain(
        doc, metrics, {"elapsed_per_join_s": 0.5})
    assert grade["wire"]["build"]["match"]
    assert grade["wire"]["probe"]["match"]
    assert grade["wall"]["ratio"] > 0
    # corrupt the prediction: the grade must say MISMATCH
    doc_bad = json.loads(json.dumps(doc))
    doc_bad["plan"]["wire"]["build"]["bytes_total"] += 8
    grade_bad = analyze.grade_explain(doc_bad, metrics, None)
    assert not grade_bad["wire"]["build"]["match"]


def test_analyze_explain_cli_gate(comm, tables, tmp_path):
    doc, metrics = _graded(comm, tables)
    record = {"telemetry": {"metrics": metrics},
              "elapsed_per_join_s": 0.25}
    epath = tmp_path / "explain.json"
    rpath = tmp_path / "record.json"
    epath.write_text(json.dumps(doc))
    rpath.write_text(json.dumps(record))
    rc = analyze.main(["explain", str(epath), "--record", str(rpath),
                       "--gate-wire-bytes"])
    assert rc == 0
    # a drifted prediction fails the gate with exit 2
    doc["plan"]["wire"]["probe"]["bytes_total"] += 8
    epath.write_text(json.dumps(doc))
    rc = analyze.main(["explain", str(epath), "--record", str(rpath),
                       "--gate-wire-bytes"])
    assert rc == 2
    # an estimate-only plan refuses the gate (exit 1), never passes it
    doc["plan"]["wire"]["exact"] = False
    epath.write_text(json.dumps(doc))
    rc = analyze.main(["explain", str(epath), "--record", str(rpath),
                       "--gate-wire-bytes"])
    assert rc == 1


def test_grade_explain_estimate_plan_labels_not_mismatch(comm, tables):
    # ISSUE 10 satellite: a ragged (estimate-only) plan grades rows/
    # wall normally and labels wire bytes ESTIMATE — an exact-equality
    # MATCH/MISMATCH verdict on an upper bound would read every run
    # as a failure.
    doc, metrics = _graded(comm, tables, shuffle="ragged")
    grade = analyze.grade_explain(
        doc, metrics, {"elapsed_per_join_s": 0.5})
    assert grade["wire_exact"] is False
    for side in ("build", "probe"):
        d = grade["wire"][side]
        assert d["estimate"] is True
        assert "match" not in d
        assert d["error_ratio"] is not None
    assert grade["rows"]["build"]["measured_rows"] > 0
    assert grade["wall"]["ratio"] > 0
    text = analyze.format_explain_grade(grade)
    assert "ESTIMATE" in text
    assert "MISMATCH" not in text


def test_analyze_explain_no_gate_grades_estimate_plans(comm, tables,
                                                       tmp_path):
    # --no-gate overrides --gate-wire-bytes (for wrappers that pass
    # the gate unconditionally): the estimate-only refusal becomes a
    # normal graded exit 0.
    doc, metrics = _graded(comm, tables, shuffle="ragged")
    record = {"telemetry": {"metrics": metrics},
              "elapsed_per_join_s": 0.25}
    epath = tmp_path / "explain.json"
    rpath = tmp_path / "record.json"
    epath.write_text(json.dumps(doc))
    rpath.write_text(json.dumps(record))
    rc = analyze.main(["explain", str(epath), "--record", str(rpath),
                       "--gate-wire-bytes"])
    assert rc == 1    # the gated refusal, unchanged
    rc = analyze.main(["explain", str(epath), "--record", str(rpath),
                       "--gate-wire-bytes", "--no-gate"])
    assert rc == 0
    rc = analyze.main(["explain", str(epath), "--record", str(rpath)])
    assert rc == 0


def test_analyze_check_validates_explain_artifacts(comm, tables,
                                                   tmp_path):
    b, p = tables
    doc = planning.explain_join(
        b, p, comm, out_capacity_factor=3.0).explain_record()
    good = tmp_path / "explain.json"
    good.write_text(json.dumps(doc))
    assert analyze.check_file(str(good)) == []
    # kind-stamp recognition under any name
    other = tmp_path / "whatever.json"
    other.write_text(json.dumps(doc))
    assert analyze.check_file(str(other)) == []
    bad = tmp_path / "explain.bad.json"
    broken = json.loads(json.dumps(doc))
    del broken["cost"]
    del broken["plan"]["signature_digest"]
    bad.write_text(json.dumps(broken))
    problems = analyze.check_file(str(bad))
    assert any("cost" in pr for pr in problems)
    assert any("signature_digest" in pr for pr in problems)


# -- service explain op -----------------------------------------------


def test_service_explain_zero_traces_and_cache_verdict(comm):
    from distributed_join_tpu.service.server import (
        JoinService,
        ServiceConfig,
    )

    svc = JoinService(comm, ServiceConfig(auto_retry=1))
    b, p = generate_build_probe_tables(
        seed=9, build_nrows=512, probe_nrows=512, selectivity=0.5)
    ab, ap = planning.abstract_tables(512, 512)
    # Dry run BEFORE anything is resident: would_trace, zero traces.
    out = svc.explain(ab, ap, out_capacity_factor=3.0)
    assert svc.cache.traces == 0
    assert out["cache"] == {"resident": False, "persisted": False,
                            "would_trace": True}
    res = svc.join(b, p, out_capacity_factor=3.0)
    assert int(res.total) > 0
    traces = svc.cache.traces
    out2 = svc.explain(ab, ap, out_capacity_factor=3.0)
    assert svc.cache.traces == traces          # still zero NEW traces
    assert out2["cache"]["resident"]
    assert out2["plan"]["signature_digest"] == \
        out["plan"]["signature_digest"]
    assert out2["cost"]["total_s"] > 0
    # the op shows up in live metrics like any other
    assert "explain" in svc.live.latency_by_op()
    # and a FAILING dry run is visible to operators too
    with pytest.raises(ValueError):
        svc.explain(ab, ap, shuffle="bogus")
    snap = svc.live.snapshot()
    assert snap["ops"]["explain"]["outcomes"].get("failed") == 1
    # with_metrics is FORWARDED, not dropped: a metrics-instrumented
    # join keys a different program, and explain must track it
    res_m = svc.join(b, p, with_metrics=True, out_capacity_factor=3.0)
    assert res_m.telemetry is not None
    out_m = svc.explain(ab, ap, with_metrics=True,
                        out_capacity_factor=3.0)
    assert out_m["cache"]["resident"]
    assert (out_m["plan"]["signature_digest"]
            != out2["plan"]["signature_digest"])


def test_service_history_carries_prediction_and_plan_digest(
        comm, tmp_path):
    from distributed_join_tpu.service.server import (
        JoinService,
        ServiceConfig,
    )

    svc = JoinService(comm, ServiceConfig(
        auto_retry=1, history_dir=str(tmp_path)))
    b, p = generate_build_probe_tables(
        seed=9, build_nrows=512, probe_nrows=512, selectivity=0.5)
    svc.join(b, p, out_capacity_factor=3.0)
    entries, malformed = history.load_history(str(tmp_path))
    assert malformed == 0 and len(entries) == 1
    pred = entries[0]["prediction"]
    assert pred and pred["predicted_wall_s"] > 0
    assert pred["wall_ratio"] > 0
    # the flight record carries the plan digest next to the coarser
    # workload signature
    rec = svc.recorder.snapshot()["records"][-1]
    assert rec["plan_digest"] and len(rec["plan_digest"]) == 16
    assert rec["signature"]


# -- history prediction-band drift ------------------------------------


def _hist_entry(sig, wall, predicted):
    return {
        "kind": "request", "signature": sig, "op": "join",
        "outcome": "served", "wall_s": wall,
        "prediction": history.prediction_block(wall, predicted),
    }


def test_history_flags_prediction_band_drift():
    band = planning.DEFAULT_PREDICTION_BAND
    inside = [_hist_entry("aaaa", 0.010, 0.009) for _ in range(3)]
    outside = [_hist_entry("bbbb", 0.010 * band * 2, 0.010)]
    summ = history.summarize(inside + outside)
    sa = summ["signatures"]["aaaa"]["prediction"]
    sb = summ["signatures"]["bbbb"]["prediction"]
    assert sa["n"] == 3 and not sa["drift"]
    assert sb["drift"]
    text = history.format_summary(summ)
    assert "OUTSIDE prediction band" in text
    assert "cost model" in text


def test_run_entry_grades_explain_block():
    entry = history.run_entry(record={
        "benchmark": "distributed_join", "n_ranks": 8,
        "build_table_nrows": 1024, "probe_table_nrows": 1024,
        "elapsed_per_join_s": 0.02,
        "explain": {"plan_digest": "ff" * 32,
                    "predicted_wall_s": 0.01},
    })
    assert entry["prediction"]["predicted_wall_s"] == 0.01
    assert entry["prediction"]["wall_ratio"] == 2.0
    # no explain block -> no prediction, unchanged behavior
    entry2 = history.run_entry(record={"benchmark": "x",
                                       "elapsed_per_join_s": 0.02})
    assert entry2["prediction"] is None


# -- cache counters + live metrics surfaces (satellites) --------------


def test_cache_eviction_and_disk_counters(comm, tables, tmp_path):
    b, p = tables
    cache = JoinProgramCache(comm, persist_dir=str(tmp_path))
    fn, hit = cache.get(b, p, key="key", out_capacity_factor=3.0)
    assert not hit
    st = cache.stats()
    assert st["integrity_evictions"] == 0
    assert st["occupancy"] is None            # unbounded
    assert cache.evict(fn.signature)          # default reason counted
    assert cache.stats()["integrity_evictions"] == 1
    # persisted blobs (when the AOT tier engaged) are counted too
    assert st["disk_persists"] == st["disk_persists"]  # key exists
    assert "disk_load_failures" in st


def test_live_metrics_per_op_quantiles_and_prometheus():
    from distributed_join_tpu.telemetry.live import LiveMetrics

    live = LiveMetrics()
    for ms in (1, 2, 3, 50):
        live.record_request("join", "served", latency_s=ms / 1e3)
    live.record_request("batch", "served", latency_s=0.2)
    by_op = live.latency_by_op()
    assert set(by_op) == {"join", "batch"}
    assert by_op["join"]["p50_s"] <= by_op["join"]["p99_s"]
    prom = live.to_prometheus()
    assert 'djtpu_request_latency_quantile_seconds{op="join",' \
           'quantile="0.5"}' in prom
    assert 'quantile="0.99"' in prom


def test_stats_wire_op_carries_cache_and_quantiles(comm):
    from distributed_join_tpu.service.server import (
        JoinService,
        ServiceConfig,
        ServiceClient,
        start_daemon,
    )

    svc = JoinService(comm, ServiceConfig(max_programs=16))
    server, port = start_daemon(svc, "127.0.0.1", 0)
    try:
        client = ServiceClient("127.0.0.1", port)
        resp = client.send({"op": "join", "build_nrows": 512,
                            "probe_nrows": 512, "seed": 3,
                            "out_capacity_factor": 3.0})
        assert resp["ok"], resp
        st = client.send({"op": "stats"})
        assert st["cache"]["occupancy"] == round(
            st["cache"]["entries"] / 16, 4)
        for key in ("integrity_evictions", "disk_persists",
                    "disk_load_failures"):
            assert key in st["cache"]
        assert "join" in st["latency_by_op"]
        exp = client.send({"op": "explain", "build_nrows": 512,
                           "probe_nrows": 512,
                           "out_capacity_factor": 3.0})
        assert exp["ok"] and exp["plan"]["signature_digest"]
        assert exp["cache"]["resident"]
        prom = client.send({"op": "metrics",
                            "format": "prometheus"})["prometheus"]
        assert "djtpu_program_cache_occupancy" in prom
        assert "djtpu_program_cache_integrity_evictions" in prom
        client.send({"op": "shutdown"})
        client.close()
    finally:
        server.server_close()


# -- the --watch console shows per-op quantiles -----------------------


def test_watch_console_renders_per_op_quantiles(comm):
    import io

    from distributed_join_tpu.service.server import (
        JoinService,
        ServiceConfig,
        start_daemon,
        watch,
    )

    svc = JoinService(comm, ServiceConfig())
    b, p = generate_build_probe_tables(
        seed=3, build_nrows=512, probe_nrows=512, selectivity=0.5)
    svc.join(b, p, out_capacity_factor=3.0)
    server, port = start_daemon(svc, "127.0.0.1", 0)
    try:
        out = io.StringIO()
        rc = watch("127.0.0.1", port, interval_s=0.01, count=1,
                   out=out)
        assert rc == 0
        line = out.getvalue()
        assert "join[" in line          # the per-op quantile segment
    finally:
        server.shutdown()
        server.server_close()


# -- drivers: --explain flag plumbing ---------------------------------


def test_driver_explain_flag_forwarded_by_launcher():
    from distributed_join_tpu.benchmarks import (
        extract_forwarded_flags,
    )

    class A:
        telemetry = None
        trace = False
        diagnose = False
        history = None
        explain = True
        verify_integrity = False
        chaos_seed = None
        guard_deadline_s = None

    a = A()
    extra = extract_forwarded_flags(a, ["prog"])
    assert "--explain" in extra
    assert a.explain is False


@pytest.mark.slow
def test_driver_explain_end_to_end(tmp_path):
    """Full driver --explain run in a subprocess (slow lane): the
    artifact schema-checks and the padded wire-byte gate passes."""
    tel = tmp_path / "tel"
    record = tmp_path / "record.json"
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PATH": "/usr/bin:/bin"}
    rc = subprocess.run(
        [sys.executable, "-m",
         "distributed_join_tpu.benchmarks.distributed_join",
         "--platform", "cpu", "--n-ranks", "8",
         "--build-table-nrows", "1024", "--probe-table-nrows", "1024",
         "--iterations", "1", "--out-capacity-factor", "3.0",
         "--telemetry", str(tel), "--explain",
         "--json-output", str(record)],
        env=env, capture_output=True, text=True, timeout=600)
    assert rc.returncode == 0, rc.stderr[-2000:]
    assert analyze.check_file(str(tel / "explain.json")) == []
    assert analyze.main(["explain", str(tel / "explain.json"),
                         "--record", str(record),
                         "--gate-wire-bytes"]) == 0
