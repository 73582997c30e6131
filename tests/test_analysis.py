"""Run analysis & perf-regression layer (telemetry/analyze.py +
telemetry/baselines.py) on the 8-virtual-device CPU mesh.

The contract under test (ISSUE 3, docs/OBSERVABILITY.md "Diagnosis &
baselines"):

- a deterministic SKEWED run (``--duplicate-build-keys`` over a tiny
  key domain) must produce a skew diagnosis with a concrete knob
  recommendation; the balanced default run must not;
- the counter signature round-trips through the baseline registry and
  ``compare`` exits non-zero on drift (and on banded wall-time
  regression when both sides carry a timing);
- pre-``schema_version: 2`` records load without crashing
  (``benchmarks.load_record`` stamps them v1);
- every artifact (summary/diagnosis/trace/events/baseline) passes the
  ``check`` shape validation the perfgate lane runs;
- ``bench.py`` without a TPU exits non-zero with a failure record and
  no proxy measurement.
"""

import json
import os

import pytest

from distributed_join_tpu import telemetry
from distributed_join_tpu.benchmarks import load_record
from distributed_join_tpu.telemetry import analyze, baselines

pytestmark = pytest.mark.analysis


@pytest.fixture(autouse=True)
def _no_leaked_session():
    telemetry.finalize()
    yield
    telemetry.finalize()


def _drive(tel_dir: str, extra):
    """One join-driver run with a telemetry session into ``tel_dir``;
    returns the (stamped) record. Shares program shapes with
    test_telemetry's acceptance run so the compile cache is warm."""
    from distributed_join_tpu.benchmarks import distributed_join as drv

    record_path = os.path.join(tel_dir, "record.json")
    args = drv.parse_args([
        "--build-table-nrows", "8000", "--probe-table-nrows", "8000",
        "--communicator", "tpu", "--iterations", "1",
        "--shuffle", "ragged", "--telemetry", tel_dir,
        "--json-output", record_path,
    ] + extra)
    assert telemetry.configure_from_args(args)
    try:
        record = drv.run(args)
    finally:
        telemetry.finalize()
    return record


@pytest.fixture(scope="module")
def balanced_run(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tel_balanced"))
    record = _drive(d, ["--out-capacity-factor", "3.0"])
    return d, record


@pytest.fixture(scope="module")
def skewed_run(tmp_path_factory):
    # 32 distinct keys drawn WITH replacement: every key is ~250-fold
    # duplicated on the build side, so hash routing concentrates
    # receives and matches on whichever ranks own the hot buckets —
    # the --duplicate-build-keys skew shape of the acceptance
    # criterion. out factor 200 covers the hottest rank's ~170k
    # matches without tripping the overflow flag.
    d = str(tmp_path_factory.mktemp("tel_skewed"))
    record = _drive(d, ["--out-capacity-factor", "200.0",
                        "--shuffle-capacity-factor", "4.0",
                        "--rand-max", "32", "--duplicate-build-keys"])
    return d, record


# -- indicator math ---------------------------------------------------


def test_gini_and_imbalance():
    assert analyze.gini([5, 5, 5, 5]) == pytest.approx(0.0)
    assert analyze.gini([0, 0, 0, 4]) == pytest.approx(0.75)
    assert analyze.gini([1]) is None          # undefined for n < 2
    assert analyze.gini([0, 0]) is None       # undefined for sum 0
    assert analyze.imbalance([1, 1, 2]) == pytest.approx(1.5)
    assert analyze.imbalance([]) is None


def test_counter_signature_source_shapes():
    m = {"n_ranks": 2, "per_rank": {"matches": [3, 4]},
         "reduced": {"matches": 7, "build.wire_bytes": 96}}
    want = {"signature_version": baselines.SIGNATURE_SCHEMA_VERSION,
            "n_ranks": 2,
            "counters": {"build.wire_bytes": 96, "matches": 7}}
    assert baselines.counter_signature(m) == want
    assert baselines.counter_signature({"metrics": m}) == want
    assert baselines.counter_signature(
        {"telemetry": {"metrics": m}}) == want
    assert baselines.counter_signature({"counter_signature": want}) == want
    assert baselines.counter_signature({"value": None}) is None
    assert baselines.counter_signature(None) is None


def test_wall_time_of():
    assert baselines.wall_time_of({"elapsed_per_join_s": 1.5}) == 1.5
    assert baselines.wall_time_of(
        {"elapsed_per_exchange_s": 0.2}) == 0.2
    assert baselines.wall_time_of({"value": 3.0}) is None
    assert baselines.wall_time_of(None) is None


# -- load_record: v1 tolerance ----------------------------------------


def test_load_record_stamps_v1(tmp_path):
    p = tmp_path / "old.json"
    p.write_text(json.dumps({"metric": "join throughput",
                             "value": 12.3}))
    rec = load_record(str(p))
    assert rec["schema_version"] == 1
    assert rec["rank"] == 0
    # dict passthrough does not mutate the caller's object
    src = {"benchmark": "x"}
    rec2 = load_record(src)
    assert rec2["schema_version"] == 1 and "schema_version" not in src
    # v2 records keep their stamp
    assert load_record({"schema_version": 2,
                        "rank": 3})["schema_version"] == 2


def test_load_record_on_committed_v1_results():
    """Every committed pre-v2 results/*.json must load (the analysis
    layer reads the historical trajectory)."""
    import glob

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sorted(glob.glob(os.path.join(root, "results", "*.json")))
    assert paths
    for path in paths:
        rec = load_record(path)
        assert rec["schema_version"] >= 1


# -- diagnose: skewed vs balanced -------------------------------------


def test_balanced_run_is_clean(balanced_run):
    d, record = balanced_run
    diag = analyze.diagnose_run(d, record=record)
    assert diag["status"] == "ok"
    skew = diag["indicators"]["key_skew"]
    assert skew["status"] == "ok"
    assert all(c["gini"] < analyze.SKEW_GINI_WARN
               for c in skew["counters"].values())
    assert diag["recommendations"] == []
    # ragged wire at 16 B/row is the ideal payload exactly
    wire = diag["indicators"]["wire_efficiency"]
    assert wire["sides"]["build"]["efficiency"] == pytest.approx(1.0)
    assert os.path.exists(os.path.join(d, "diagnosis.json"))


def test_skewed_run_diagnosed_with_knob_recommendation(
        skewed_run, capsys):
    """ISSUE 3 acceptance: a --duplicate-build-keys skew run, run
    through the CLI, reports a skew diagnosis with a concrete knob."""
    d, record = skewed_run
    assert not record["overflow"]
    rc = analyze.main(["diagnose", d, "--record",
                       os.path.join(d, "record.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "--skew-threshold" in out      # the concrete knob
    diag = json.load(open(os.path.join(d, "diagnosis.json")))
    assert diag["status"] == "warn"
    skew = diag["indicators"]["key_skew"]
    assert skew["status"] == "warn"
    assert skew["counters"]["matches"]["gini"] > analyze.SKEW_GINI_WARN
    recs = {r["id"]: r for r in diag["recommendations"]}
    assert "skew_enable_prpd" in recs
    assert recs["skew_enable_prpd"]["module"] == "parallel/skew.py"
    assert any("--skew-threshold" in f
               for f in recs["skew_enable_prpd"]["flags"])


def test_diagnosis_artifacts_pass_schema_check(balanced_run):
    d, _ = balanced_run
    analyze.diagnose_run(d)
    for name in ("summary.json", "diagnosis.json", "trace.rank0.json",
                 "events.rank0.jsonl"):
        assert analyze.check_file(os.path.join(d, name)) == [], name
    # Chrome trace shape, explicitly (Perfetto-loadable)
    trace = json.load(open(os.path.join(d, "trace.rank0.json")))
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    assert all({"name", "ph", "ts", "pid"} <= set(e)
               for e in trace["traceEvents"])


def test_check_flags_malformed_artifacts(tmp_path):
    bad_summary = tmp_path / "summary.json"
    bad_summary.write_text(json.dumps({"rank": 0}))
    assert any("telemetry_format_version" in p
               for p in analyze.check_file(str(bad_summary)))
    bad_kind = tmp_path / "events.rank0.jsonl"
    bad_kind.write_text('{"kind": "event", "name": "a"}\n'
                        '{"kind": "bogus"}\n')
    assert analyze.check_file(str(bad_kind))
    assert analyze.main(["check", str(bad_summary)]) == 1


def test_check_tolerates_torn_final_line_only(tmp_path):
    """A torn FINAL event line is the advertised killed-run artifact
    (export.py streams; a kill can land mid-write) — `check` must
    pass it. Torn lines anywhere else are corruption and fail."""
    killed = tmp_path / "events.rank0.jsonl"
    killed.write_text('{"kind": "event", "name": "a"}\n'
                      '{"kind": "span", "name": "b", "dur_us')
    assert analyze.check_file(str(killed)) == []
    corrupt = tmp_path / "events.rank1.jsonl"
    corrupt.write_text('{"kind": "span", "name": "b", "dur_us\n'
                       '{"kind": "event", "name": "a"}\n')
    assert any("line 1" in p for p in analyze.check_file(str(corrupt)))


def test_check_accepts_chrome_trace_array_form(tmp_path):
    """Chrome's JSON Array Format (a bare list of events) is as valid
    as the Object Format the sink writes."""
    arr = tmp_path / "trace.rank0.json"
    arr.write_text(json.dumps(
        [{"name": "x", "ph": "X", "ts": 0, "pid": 0, "dur": 1}]))
    assert analyze.check_file(str(arr)) == []
    arr.write_text(json.dumps([{"ph": "X"}]))
    assert analyze.check_file(str(arr))
    assert analyze.main(["check", str(arr)]) == 1


def test_baseline_path_forms(tmp_path):
    bdir = str(tmp_path)
    assert baselines.baseline_path("foo", bdir) \
        == os.path.join(bdir, "foo.json")
    # a registry name typed WITH the extension resolves identically
    assert baselines.baseline_path("foo.json", bdir) \
        == os.path.join(bdir, "foo.json")
    # an explicit path (separator or existing file) passes through
    p = tmp_path / "explicit.json"
    assert baselines.baseline_path(str(p), bdir) == str(p)


def test_load_run_tolerates_torn_log(tmp_path):
    d = tmp_path / "run"
    d.mkdir()
    (d / "events.rank0.jsonl").write_text(
        '{"kind": "event", "name": "session_start", "ts_us": 1.0}\n'
        '{"kind": "span", "name": "timed_join", "ts_us": 2.0, "dur_us')
    run = analyze.load_run(str(d))
    assert run.malformed_lines == 1
    assert len(run.events) == 1
    diag = analyze.diagnose(run)     # sparse run must not crash
    assert diag["indicators"]["key_skew"]["status"] == "unknown"
    assert diag["signature"] is None


# -- baselines: round-trip, drift, wall band --------------------------


def test_baseline_roundtrip_and_drift(balanced_run, skewed_run,
                                      tmp_path):
    bdir = str(tmp_path / "baselines")
    d_bal, rec_bal = balanced_run
    d_skew, _ = skewed_run
    path = baselines.write_baseline("cpu_mesh_test", rec_bal,
                                    baseline_dir=bdir, record=rec_bal)
    assert analyze.check_file(path) == []
    base = baselines.load_baseline("cpu_mesh_test", bdir)
    assert base["wall_time_s"] is None      # CPU wall never gated
    assert base["config"]["build_table_nrows"] == 8000

    same = baselines.compare(base, rec_bal, record=rec_bal)
    assert same.ok and not same.drifted and same.wall is None

    drifted = baselines.compare(base, load_record(
        os.path.join(d_skew, "record.json")))
    assert not drifted.ok
    assert "matches" in drifted.drifted
    assert drifted.drifted["matches"]["baseline"] \
        != drifted.drifted["matches"]["current"]
    assert "DRIFT matches" in drifted.format()

    # New counters the baseline predates are reported, not failed.
    sig = baselines.counter_signature(rec_bal)
    sig["counters"]["brand.new_counter"] = 1
    fwd = baselines.compare(base, sig, record=rec_bal)
    assert fwd.ok and fwd.extra == ["brand.new_counter"]
    # A counter the baseline has but the run lost IS a failure.
    sig2 = baselines.counter_signature(rec_bal)
    del sig2["counters"]["matches"]
    assert not baselines.compare(base, sig2).ok


def test_wall_time_noise_band(balanced_run, tmp_path):
    _, rec = balanced_run
    bdir = str(tmp_path / "bl")
    base = json.load(open(baselines.write_baseline(
        "hw", rec, baseline_dir=bdir, record=rec)))
    base["wall_time_s"] = 1.0
    within = dict(rec, elapsed_per_join_s=1.2)
    beyond = dict(rec, elapsed_per_join_s=1.3)
    assert baselines.compare(base, rec, record=within).ok
    slow = baselines.compare(base, rec, record=beyond)
    assert not slow.ok and slow.signature_ok
    assert slow.wall["regressed"] and "REGRESSED" in slow.format()
    # wider explicit band clears it
    assert baselines.compare(base, rec, record=beyond,
                             noise_band=0.5).ok


def test_compare_cli_exit_codes(balanced_run, tmp_path):
    d_bal, _ = balanced_run
    bdir = str(tmp_path / "bl")
    rec_path = os.path.join(d_bal, "record.json")
    assert analyze.main(["compare", rec_path, "--baseline", "gate",
                         "--baseline-dir", bdir, "--write"]) == 0
    assert analyze.main(["compare", rec_path, "--baseline", "gate",
                         "--baseline-dir", bdir]) == 0
    # comparing the run DIRECTORY (summary.json signature) also passes
    assert analyze.main(["compare", d_bal, "--baseline", "gate",
                         "--baseline-dir", bdir,
                         "--record", rec_path]) == 0
    # missing baseline is a usage error (1), not a drift (2)
    assert analyze.main(["compare", rec_path, "--baseline", "nope",
                         "--baseline-dir", bdir]) == 1
    # drift: doctor the baseline
    base = json.load(open(os.path.join(bdir, "gate.json")))
    base["signature"]["counters"]["matches"] += 1
    with open(os.path.join(bdir, "gate.json"), "w") as f:
        json.dump(base, f)
    assert analyze.main(["compare", rec_path, "--baseline", "gate",
                         "--baseline-dir", bdir]) == 2


# -- driver --diagnose end-to-end -------------------------------------


def test_driver_diagnose_flag_writes_diagnosis(tmp_path, capsys):
    """`--diagnose` through the real driver main() (run_guarded):
    diagnosis.json lands in the session dir and the report prints."""
    from distributed_join_tpu.benchmarks import distributed_join as drv

    d = str(tmp_path / "tel")
    rc = drv.main([
        "--build-table-nrows", "8000", "--probe-table-nrows", "8000",
        "--communicator", "tpu", "--iterations", "1",
        "--shuffle", "ragged", "--out-capacity-factor", "3.0",
        "--telemetry", d, "--diagnose",
    ])
    assert rc == 0
    assert not telemetry.enabled()      # run_guarded finalized it
    diag = json.load(open(os.path.join(d, "diagnosis.json")))
    assert diag["schema_version"] == analyze.DIAGNOSIS_SCHEMA_VERSION
    assert diag["signature"]["counters"]["matches"] > 0
    # run_guarded forwarded the run's record, so the record-dependent
    # wire indicator resolved (16 B/row ragged = ideal payload)
    wire = diag["indicators"]["wire_efficiency"]
    assert wire["shuffle_mode"] == "ragged"
    assert wire["sides"]["build"]["efficiency"] == pytest.approx(1.0)
    assert "key skew" in capsys.readouterr().out


def test_diagnose_alone_implies_telemetry(tmp_path, monkeypatch):
    from distributed_join_tpu.benchmarks import distributed_join as drv

    monkeypatch.chdir(tmp_path)   # the default dir is ./telemetry
    args = drv.parse_args(["--diagnose"])
    assert telemetry.configure_from_args(args)
    assert telemetry.sink().dir == "telemetry"
    telemetry.finalize()


# -- launcher forwarding ----------------------------------------------


def test_launch_forwards_telemetry_flags():
    from distributed_join_tpu.benchmarks import launch

    args = launch.parse_args([
        "--num-processes", "2", "--telemetry", "teldir", "--diagnose",
        "--", "tpu-distributed-join", "--iterations", "1",
    ])
    assert args.command[:3] == ["tpu-distributed-join",
                                "--iterations", "1"]
    assert "--telemetry" in args.command and "teldir" in args.command
    assert "--diagnose" in args.command
    # the launcher itself must not open a session for these
    assert args.telemetry is None and not args.diagnose
    assert not telemetry.configure_from_args(args)

    # explicit child flags win; nothing is forwarded twice
    args2 = launch.parse_args([
        "--num-processes", "2", "--telemetry", "parentdir",
        "--", "drv", "--telemetry", "childdir",
    ])
    assert args2.command.count("--telemetry") == 1
    assert "parentdir" not in args2.command


def test_launch_forwards_robustness_flags():
    """PR 5's robustness flags ride the same forwarding table as the
    telemetry flags — the launcher used to silently drop them."""
    from distributed_join_tpu.benchmarks import launch

    args = launch.parse_args([
        "--num-processes", "2", "--verify-integrity",
        "--chaos-seed", "7", "--guard-deadline-s", "30",
        "--", "tpu-distributed-join", "--iterations", "1",
    ])
    cmd = args.command
    assert "--verify-integrity" in cmd
    assert cmd[cmd.index("--chaos-seed") + 1] == "7"
    assert cmd[cmd.index("--guard-deadline-s") + 1] == "30.0"
    # ... and are stripped from the launcher itself: its own
    # spawn-and-reap loop must stay unguarded and chaos-free
    assert not args.verify_integrity
    assert args.chaos_seed is None
    # 0 (not None): the 0 sentinel also blocks the
    # DJTPU_GUARD_DEADLINE_S env fallback from guarding the launcher
    assert args.guard_deadline_s == 0

    # explicit child flags win; nothing forwards twice
    args2 = launch.parse_args([
        "--num-processes", "2", "--chaos-seed", "7",
        "--", "drv", "--chaos-seed", "9",
    ])
    assert args2.command.count("--chaos-seed") == 1
    assert "7" not in args2.command


# -- bench.py without a chip -----------------------------------------


def test_bench_without_tpu_fails_with_record():
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "bench.py"], cwd=root,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["value"] is None and rec["vs_baseline"] is None
    assert "proxy" not in rec
    assert rec["bootstrap"]["error"] == "BootstrapError"
    assert "no TPU" in rec["error"]
    assert rec["schema_version"] == 2


# -- workload history (ISSUE 7) ---------------------------------------


def _fake_history(tmp_path):
    from distributed_join_tpu.telemetry import history

    store = history.WorkloadHistory(str(tmp_path))
    store.append(history.request_entry(
        request_id="req-000001", op="join", signature="sig-a",
        outcome="served", wall_s=0.5, new_traces=2))
    store.append(history.request_entry(
        request_id="req-000002", op="join", signature="sig-a",
        outcome="served", wall_s=0.1,
        retry_record={"attempts": [
            {"attempt": 0, "action": "initial", "overflow": True,
             "out_capacity_factor": 3.0},
            {"attempt": 1, "action": "double_capacities",
             "overflow": False, "out_capacity_factor": 6.0},
        ]}))
    store.append(history.request_entry(
        request_id="req-000003", op="batch", signature="sig-b",
        outcome="failed", wall_s=0.2, error="ValueError: nope"))
    return store


def test_history_summarize_trends(tmp_path):
    from distributed_join_tpu.telemetry import history

    store = _fake_history(tmp_path)
    entries, malformed = history.load_history(str(tmp_path))
    assert malformed == 0 and len(entries) == 3
    summary = history.summarize(entries)
    assert summary["n_signatures"] == 2
    a = summary["signatures"]["sig-a"]
    assert a["entries"] == 2
    assert a["escalations"] == 1
    assert a["resolved_knobs_last"] == {"out_capacity_factor": 6.0}
    assert a["wall"]["p50_s"] == 0.5 and a["wall"]["last_s"] == 0.1
    b = summary["signatures"]["sig-b"]
    assert b["outcomes"] == {"failed": 1}
    text = history.format_summary(summary, path=store.path)
    assert "2 signature(s)" in text and "sig-a" in text

    # torn final line tolerated, like the event logs
    with open(store.path, "a") as f:
        f.write('{"torn": ')
    entries2, malformed2 = history.load_history(store.path)
    assert len(entries2) == 3 and malformed2 == 1


def test_history_cli_and_artifact_checks(tmp_path, capsys):
    """`analyze history` summarizes the store (human + --json), and
    `analyze check` understands history.jsonl and flightrecorder.json
    artifacts — the CI lane's validation."""
    from distributed_join_tpu.telemetry import live

    store = _fake_history(tmp_path)
    assert analyze.main(["history", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "2 signature(s)" in out
    assert analyze.main(["history", store.path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["n_entries"] == 3

    assert analyze.main(["check", store.path]) == 0
    capsys.readouterr()
    # a history line missing its required keys fails the check
    bad = tmp_path / "bad" / "history.jsonl"
    bad.parent.mkdir()
    bad.write_text('{"kind": "request"}\n{"also": "bad"}\n')
    assert analyze.main(["check", str(bad)]) == 1
    capsys.readouterr()

    fr = live.FlightRecorder(capacity=4)
    fr.record(request_id="req-1", op="join", outcome="hang",
              signature="sig-a", elapsed_s=0.75)
    path = fr.dump(str(tmp_path / "flightrecorder.json"), "poisoned")
    assert analyze.check_file(path) == []
    assert analyze.main(["check", path]) == 0
    capsys.readouterr()
    doc = json.load(open(path))
    del doc["reason"]
    doc["records"].append({"no": "ids"})
    broken = tmp_path / "broken_flightrecorder.json"
    broken.write_text(json.dumps(doc))
    problems = analyze.check_file(str(broken))
    assert any("reason" in p for p in problems)
    assert any("records[1]" in p for p in problems)


def test_history_cli_tenant_filter(tmp_path, capsys):
    """`analyze history --tenant` summarizes one tenant's slice of
    the store: a named tenant selects its stamped entries (trend
    keys stay ``tenant/signature``), the default-tenant name selects
    the un-stamped (pre-tenancy) entries."""
    from distributed_join_tpu.telemetry import history

    store = history.WorkloadHistory(str(tmp_path))
    store.append(history.request_entry(
        request_id="req-000001", op="join", signature="sig-a",
        outcome="served", wall_s=0.1, tenant="acme"))
    store.append(history.request_entry(
        request_id="req-000002", op="join", signature="sig-a",
        outcome="served", wall_s=0.2))

    assert analyze.main(["history", store.path, "--tenant", "acme",
                         "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["tenant"] == "acme"
    assert summary["n_entries"] == 1
    assert list(summary["signatures"]) == ["acme/sig-a"]

    assert analyze.main(["history", store.path, "--tenant",
                         history.DEFAULT_TENANT, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["n_entries"] == 1
    assert list(summary["signatures"]) == ["sig-a"]

    # An un-stamped store filtered to a tenant nobody stamped is
    # empty, not an error.
    assert analyze.main(["history", store.path, "--tenant", "ghost",
                         "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["n_entries"] == 0


def test_run_entry_from_driver_record(tmp_path):
    """The drivers' --history flag appends a run-shaped entry whose
    workload hash is stable across repeats and whose counter signature
    comes from the record's telemetry block."""
    from distributed_join_tpu.benchmarks import maybe_history
    from distributed_join_tpu.telemetry import history

    record = {
        "benchmark": "distributed_join", "n_ranks": 8,
        "build_table_nrows": 8000, "probe_table_nrows": 8000,
        "shuffle": "ragged", "elapsed_per_join_s": 0.25,
        "matches_per_join": 123,
        "retry": None,
        "telemetry": {"metrics": {
            "n_ranks": 8,
            "per_rank": {"matches": [15] * 8},
            "reduced": {"matches": 120},
        }},
    }
    e1 = history.run_entry(record=record)
    e2 = history.run_entry(record=dict(record, elapsed_per_join_s=0.5))
    assert e1["kind"] == "run"
    assert e1["signature"] == e2["signature"]      # same workload
    assert e1["wall_s"] == 0.25 and e2["wall_s"] == 0.5
    assert e1["counter_signature"]["counters"]["matches"] == 120

    # the end-of-run hook appends on rank 0 (best-effort, never raises)
    path = str(tmp_path / "h.jsonl")

    class A:
        history = path

    maybe_history(A(), summary=None, record=record)
    entries, _ = history.load_history(path)
    assert len(entries) == 1 and entries[0]["signature"] == \
        e1["signature"]


def test_launch_forwards_history_flag():
    """The new observability flag rides the shared forwarding table —
    tpu-launch must not silently drop it (the PR 6 fix pattern)."""
    from distributed_join_tpu.benchmarks import launch

    args = launch.parse_args([
        "--num-processes", "2", "--history", "store.jsonl",
        "--", "tpu-distributed-join", "--iterations", "1",
    ])
    cmd = args.command
    assert cmd[cmd.index("--history") + 1] == "store.jsonl"
    # ... and is stripped from the launcher itself (no session, no
    # launcher-level history entry)
    assert args.history is None
    assert not telemetry.configure_from_args(args)

    # explicit child flags win; nothing forwards twice
    args2 = launch.parse_args([
        "--num-processes", "2", "--history", "parent.jsonl",
        "--", "drv", "--history", "child.jsonl",
    ])
    assert args2.command.count("--history") == 1
    assert "parent.jsonl" not in args2.command


def test_history_file_contract_and_wall_extraction(tmp_path):
    """--history FILE must write THAT file (never silently become a
    directory), and run_entry's wall number follows wall_time_of —
    all_to_all's elapsed_per_exchange_s counts, bench.py's rate-shaped
    'value' never does."""
    from distributed_join_tpu.telemetry import history

    path = str(tmp_path / "runs.log")        # no .jsonl suffix
    store = history.WorkloadHistory(path)
    store.append(history.run_entry(record={"benchmark": "demo"}))
    store.append(history.run_entry(record={"benchmark": "demo2"}))
    assert os.path.isfile(path)
    entries, _ = history.load_history(path)
    assert len(entries) == 2
    # `analyze check` validates the store under ANY filename (content
    # sniff on the per-line kind stamp)
    assert analyze.check_file(path) == []

    e = history.run_entry(record={"benchmark": "all_to_all",
                                  "elapsed_per_exchange_s": 0.125})
    assert e["wall_s"] == 0.125
    e2 = history.run_entry(record={"benchmark": "bench",
                                   "value": 68.4})
    assert e2["wall_s"] is None              # a rate, not a time


def test_failed_run_history_entry(tmp_path, capsys):
    """A run that dies under run_guarded must land a FAILED history
    entry carrying the failure record's identity and error — never a
    bogus healthy entry hashed from an empty workload."""
    import pytest as _pytest

    from distributed_join_tpu import benchmarks
    from distributed_join_tpu.telemetry import history

    path = str(tmp_path / "h.jsonl")

    class A:
        telemetry = str(tmp_path / "tel")
        trace = False
        diagnose = False
        history = path
        guard_deadline_s = 0
        json_output = None
        # driver-args workload identity, back-filled into the failure
        # record so the failed run files under the same signature as
        # its healthy runs
        build_table_nrows = 8000
        shuffle = "ragged"

    def boom(args):
        raise ValueError("nope")

    # arrange the back-fill's precondition explicitly: it only reads
    # n_ranks from an ALREADY-initialized backend (order-independent)
    import jax

    jax.device_count()
    with _pytest.raises(ValueError):
        benchmarks.run_guarded(boom, A(), benchmark="demo")
    capsys.readouterr()
    entries, _ = history.load_history(path)
    assert len(entries) == 1
    e = entries[0]
    assert e["outcome"] == "failed"
    assert "ValueError" in e["error"]
    wl = dict(e["workload"])
    # n_ranks is back-filled from the already-initialized backend so
    # the failure hashes to the same signature as healthy runs
    assert wl.pop("n_ranks", None) is not None
    assert wl == {"benchmark": "demo",
                  "build_table_nrows": 8000,
                  "shuffle": "ragged"}


def test_hang_failure_lands_history_entry(tmp_path, monkeypatch,
                                          capsys):
    """The HangError hard-exit path must still append the failure's
    history entry before os._exit — a hang-prone workload is exactly
    the trend the store exists to show."""
    import os as _os
    import time

    import pytest as _pytest

    from distributed_join_tpu import benchmarks
    from distributed_join_tpu.telemetry import history

    path = str(tmp_path / "h.jsonl")

    class Exited(Exception):
        pass

    def fake_exit(code):
        raise Exited(str(code))

    monkeypatch.setattr(_os, "_exit", fake_exit)

    class A:
        telemetry = str(tmp_path / "tel")
        trace = False
        diagnose = False
        history = path
        guard_deadline_s = 0.2
        json_output = None
        build_table_nrows = 4096

    def sleepy(args):
        time.sleep(3.0)

    with _pytest.raises(Exited):
        benchmarks.run_guarded(sleepy, A(), benchmark="demo")
    capsys.readouterr()
    entries, _ = history.load_history(path)
    assert entries                  # (the fake exit lets the finally
    #                                 run too; production exits first)
    assert all(e["outcome"] == "failed" for e in entries)
    assert "HangError" in entries[0]["error"]
    assert entries[0]["workload"]["build_table_nrows"] == 4096
    time.sleep(3.0)                 # drain the detached worker
