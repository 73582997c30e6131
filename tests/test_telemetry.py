"""Telemetry subsystem (distributed_join_tpu/telemetry/) on the
8-virtual-device CPU mesh.

Two contracts (docs/OBSERVABILITY.md):

- **Off = seed.** With no telemetry session, the compiled join step's
  output treedef and compiled-program count are identical to the seed
  — no silent aux outputs, no recompiles, no attribute leakage.
- **On = honest.** With a session active, the device-side counters
  that ride the compiled step as an aux ``Metrics`` pytree match
  pandas-oracle ground truth (rows shuffled, wire bytes, match
  count), span events land in the JSONL log, and the Chrome trace is
  Perfetto-loadable JSON carrying the run's host spans.

Stages inside the compiled step are ``jax.named_scope``s, on in both
modes: the partition/shuffle/join names ride the instructions'
``op_name`` metadata, and the entry's host spans reach a profiler trace
whether or not a session is active.
"""

import glob
import json
import math
import re

import pytest

import jax

import distributed_join_tpu as dj
from distributed_join_tpu import telemetry
from distributed_join_tpu.ops.join import JoinResult
from distributed_join_tpu.parallel.communicator import TpuCommunicator
from distributed_join_tpu.parallel.distributed_join import (
    make_distributed_join,
)
from distributed_join_tpu.parallel.out_of_core import keyrange_batched_join
from distributed_join_tpu.utils.generators import (
    generate_build_probe_tables,
)

pytestmark = pytest.mark.telemetry

# int64 key + int64 payload: the generators' fixed row layout.
ROW_BYTES = 16


@pytest.fixture(autouse=True)
def _no_leaked_session():
    """Telemetry state is process-global; a test that dies mid-session
    must not flip every later test into the instrumented path."""
    telemetry.finalize()
    yield
    telemetry.finalize()


class CountingComm(TpuCommunicator):
    """Counts compiled SPMD programs — the observable behind the
    'telemetry off compiles exactly the seed program set' contract."""

    def __init__(self, n_ranks: int = 8):
        super().__init__(n_ranks=n_ranks)
        self.programs_built = 0

    def spmd(self, fn, *, sharded_out=None):
        self.programs_built += 1
        return super().spmd(fn, sharded_out=sharded_out)


def _tables():
    return generate_build_probe_tables(
        seed=11, build_nrows=512, probe_nrows=1024, rand_max=256,
        selectivity=0.5,
    )


def _oracle(build, probe) -> int:
    return len(build.to_pandas().merge(probe.to_pandas(), on="key"))


def _layer_scopes(hlo_text: str) -> set:
    """The layer scopes (partition, shuffle, join) that instructions'
    op_names carry: every component but the last, which is the
    primitive."""
    return {scope
            for op_name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for scope in op_name.split("/")[:-1]
            if scope in ("partition", "shuffle", "join")}


# -- telemetry OFF: the seed hot path, bit for bit --------------------


def test_off_path_treedef_and_program_count(tmp_path):
    """No session: one compiled program, a bare JoinResult output
    (same treedef as the instrumented mode's result — the aux Metrics
    block must never leak into the JoinResult pytree), no telemetry
    attribute, and no recompile on the second call."""
    assert not telemetry.enabled()
    b, p = _tables()
    want = _oracle(b, p)

    comm = CountingComm()
    fn = make_distributed_join(comm, key="key", out_capacity_factor=4.0)
    res_off = fn(b, p)
    assert comm.programs_built == 1
    assert type(res_off) is JoinResult
    assert not hasattr(res_off, "telemetry")
    assert int(res_off.total) == want
    # Second call: the jit cache must be hit, not re-traced.
    fn(b, p)
    assert comm.programs_built == 1
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1

    # Same join with a session active: result carries the metrics as a
    # HOST-side attribute; the JoinResult pytree itself is unchanged.
    with telemetry.session(str(tmp_path / "tel")):
        comm_on = CountingComm()
        fn_on = make_distributed_join(comm_on, key="key",
                                      out_capacity_factor=4.0)
        res_on = fn_on(b, p)
        assert comm_on.programs_built == 1
        assert hasattr(res_on, "telemetry")
        assert int(res_on.total) == want
        assert (jax.tree_util.tree_structure(res_off)
                == jax.tree_util.tree_structure(res_on))


def test_explicit_with_metrics_false_wins_over_session(tmp_path):
    """An active session must not leak into callers that pinned the
    seed program (e.g. the out-of-core batch loop)."""
    b, p = _tables()
    with telemetry.session(str(tmp_path / "tel")):
        fn = make_distributed_join(CountingComm(), key="key",
                                   with_metrics=False,
                                   out_capacity_factor=4.0)
        res = fn(b, p)
        assert not hasattr(res, "telemetry")


def test_stage_scopes_on_four_devices():
    """No session: the four-rank step's instructions carry the
    partition, shuffle and join scopes all the same."""
    assert not telemetry.enabled()
    b, p = _tables()
    fn = make_distributed_join(TpuCommunicator(n_ranks=4), key="key",
                               out_capacity_factor=4.0)
    assert _layer_scopes(fn.lower(b, p).compile().as_text()) == {
        "partition", "shuffle", "join"}


ENTRY_SPANS = ("entry.prepare", "entry.resolve", "entry.dispatch",
               "entry.fetch_overflow", "entry.finish")


def _host_spans(trace_dir: str):
    """``[(name, start_ns, end_ns, stats)]`` of the host threads'
    events in the profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_entry_spans_reach_a_profiler_trace(tmp_path):
    """No session: a profiler trace of two calls holds the entry's five
    host spans inside the caller's annotation, one compile span (the
    program cache's miss) with the signature digest, and the program's
    own output sizing as ``out_rows``."""
    from distributed_join_tpu.service.programs import JoinProgramCache

    assert not telemetry.enabled()
    b, p = _tables()
    comm = TpuCommunicator(n_ranks=4)
    cache = JoinProgramCache(comm)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("caller"):
                res = dj.distributed_inner_join(
                    b, p, comm, program_cache=cache,
                    out_capacity_factor=4.0)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    callers = [s for s in spans if s[0] == "caller"]
    assert len(callers) == 2
    for name in ENTRY_SPANS + ("entry.compile",):
        found = [s for s in spans if s[0] == name]
        assert found, name
        assert all(any(c[1] <= s[1] and s[2] <= c[2] for c in callers)
                   for s in found), name
    fetches = [s[3] for s in spans if s[0] == "entry.fetch_overflow"]
    assert [{k: st[k] for k in ("out_rows", "rung")} for st in fetches] \
        == [{"out_rows": res.table.capacity, "rung": 0}] * 2
    (compile_span,) = [s for s in spans if s[0] == "entry.compile"]
    assert len(compile_span[3]["digest"]) == 12
    assert int(res.total) == _oracle(b, p)


def test_span_without_session_fetches_nothing(monkeypatch):
    """No session: a span yields no handle and fetches no scalar, and
    the entry's spans build no program beyond the one join."""
    from distributed_join_tpu.service.programs import JoinProgramCache
    from distributed_join_tpu.telemetry import spans

    assert not telemetry.enabled()

    def no_fetch(x):
        raise AssertionError("a span fetched a scalar")

    monkeypatch.setattr(spans, "fetch_one_scalar", no_fetch)
    with telemetry.span("host.work", rows=3) as sp:
        assert sp is None
    b, p = _tables()
    comm = CountingComm(n_ranks=4)
    cache = JoinProgramCache(comm)
    for _ in range(2):
        res = dj.distributed_inner_join(b, p, comm, program_cache=cache,
                                        out_capacity_factor=4.0)
    assert comm.programs_built == 1 and cache.traces == 1
    assert int(res.total) == _oracle(b, p)


# -- telemetry ON: counters vs. pandas-oracle ground truth ------------


def test_ragged_metrics_match_oracle(tmp_path):
    """Exact-size shuffle: rows shuffled = valid rows, wire bytes =
    rows x fixed row bytes, matches = the pandas join size."""
    b, p = _tables()
    want = _oracle(b, p)
    with telemetry.session(str(tmp_path / "tel")):
        comm = dj.make_communicator("tpu", n_ranks=8)
        res = dj.distributed_inner_join(
            b, p, comm, shuffle="ragged", out_capacity_factor=4.0,
        )
        assert int(res.total) == want
        m = res.telemetry.to_dict()
        summ = telemetry.summary()
    r = m["reduced"]
    assert r["matches"] == want
    assert r["build.rows_partitioned"] == 512
    assert r["build.rows_shuffled"] == 512
    assert r["build.rows_received"] == 512
    assert r["probe.rows_shuffled"] == 1024
    assert r["build.wire_bytes"] == 512 * ROW_BYTES
    assert r["probe.wire_bytes"] == 1024 * ROW_BYTES
    assert r["build.overflow_margin_min"] >= 0
    assert r["retry_attempt_max"] == 0
    # per-rank matches sum to the global total (gathered pre-psum)
    assert sum(m["per_rank"]["matches"]) == want
    # distributed_inner_join folded the same block into the session
    assert summ["metrics"]["reduced"] == r


def test_padded_metrics_wire_bytes_are_static_capacity(tmp_path):
    """Padded mode bills the full static block per column — the
    ~1/load-factor wire inflation the shuffle docstring describes —
    while rows_shuffled stays the actual row count."""
    b, p = _tables()
    n, factor = 8, 2.0
    with telemetry.session(str(tmp_path / "tel")):
        comm = dj.make_communicator("tpu", n_ranks=8)
        res = dj.distributed_inner_join(
            b, p, comm, shuffle="padded",
            shuffle_capacity_factor=factor, out_capacity_factor=4.0,
        )
        m = res.telemetry.to_dict()
    r = m["reduced"]
    assert r["build.rows_shuffled"] == 512
    assert r["probe.rows_shuffled"] == 1024

    def padded_bytes(rows):
        cap = math.ceil(rows / n / n * factor)
        cap += (-cap) % 8  # _round_up(., 8)
        return n * (n * cap) * ROW_BYTES  # all ranks x padded block

    assert r["build.wire_bytes"] == padded_bytes(512)
    assert r["probe.wire_bytes"] == padded_bytes(1024)


def test_retry_ladder_events_and_attempt_metric(tmp_path):
    """An injected capacity squeeze: the final attempt's metrics carry
    the retry attempt index, and each ladder rung streamed a
    retry_attempt event into the JSONL log as it happened."""
    from distributed_join_tpu.parallel.faults import (
        FaultInjectingCommunicator,
        FaultPlan,
    )

    b, p = _tables()
    with telemetry.session(str(tmp_path / "tel")) as sink:
        comm = FaultInjectingCommunicator(
            dj.make_communicator("tpu", n_ranks=8),
            FaultPlan(overflow_programs=1),
        )
        res = dj.distributed_inner_join(
            b, p, comm, auto_retry=2, out_capacity_factor=4.0,
        )
        assert not bool(res.overflow)
        assert res.telemetry.to_dict()["reduced"]["retry_attempt_max"] == 1
        events_path = sink.events_path
    events = [json.loads(line) for line in open(events_path)]
    attempts = [e["payload"] for e in events
                if e["name"] == "retry_attempt"]
    assert [a["overflow"] for a in attempts] == [True, False]
    assert attempts[1]["action"] == "double_capacities"


def test_out_of_core_phase_counters_and_events(tmp_path):
    """The out-of-core phase dict keeps its JSON keys verbatim while
    the same increments land as out_of_core.* telemetry counters, and
    every settled batch leaves a batch_complete event."""
    b, p = _tables()
    stats = {}
    with telemetry.session(str(tmp_path / "tel")) as sink:
        comm = dj.make_communicator("tpu", n_ranks=8)
        total, overflow = keyrange_batched_join(
            b, p, comm, n_batches=2, stats=stats,
            out_capacity_factor=4.0, shuffle_capacity_factor=3.0,
        )
        events_path = sink.events_path
        summ = telemetry.summary()
    assert total == _oracle(b, p) and not overflow
    # JSON keys preserved for downstream BENCH parsing
    for key in ("pad_s", "put_s", "dispatch_s", "fetch_s",
                "fetch_wait_s", "elapsed_s"):
        assert key in stats
    assert {"out_of_core.pad_s", "out_of_core.put_s",
            "out_of_core.dispatch_s"} <= set(summ["counters"])
    events = [json.loads(line) for line in open(events_path)]
    done = [e["payload"]["batch"] for e in events
            if e["name"] == "batch_complete"]
    assert sorted(done) == [0, 1]


# -- the acceptance run: driver --telemetry end-to-end ----------------


def test_join_driver_telemetry_acceptance(tmp_path):
    """ISSUE 2 acceptance: one --telemetry join-driver run on the CPU
    mesh produces a JSONL event log, a Perfetto-loadable Chrome trace
    of host spans, a program whose instructions carry the
    partition/shuffle/join scopes, and a JSON record whose embedded
    counters match the pandas oracle."""
    from distributed_join_tpu.benchmarks import (
        distributed_join as dj_driver,
    )

    tel_dir = str(tmp_path / "tel")
    args = dj_driver.parse_args([
        "--build-table-nrows", "8000", "--probe-table-nrows", "8000",
        "--communicator", "tpu", "--iterations", "1",
        "--out-capacity-factor", "3.0", "--shuffle", "ragged",
        "--telemetry", tel_dir,
    ])
    assert telemetry.configure_from_args(args)
    try:
        record = dj_driver.run(args)
    finally:
        telemetry.finalize()

    tables = generate_build_probe_tables(
        seed=42, build_nrows=8000, probe_nrows=8000, selectivity=0.3,
        unique_build_keys=True,
    )
    want = _oracle(*tables)
    assert record["schema_version"] == 2
    assert record["rank"] == 0
    assert record["matches_per_join"] == want

    tel = record["telemetry"]
    red = tel["metrics"]["reduced"]
    assert red["matches"] == want
    assert red["build.rows_shuffled"] == 8000
    assert red["probe.rows_shuffled"] == 8000
    assert red["build.wire_bytes"] == 8000 * ROW_BYTES
    assert red["probe.wire_bytes"] == 8000 * ROW_BYTES

    # JSONL event log: one JSON object per line, metrics event present.
    events = [json.loads(line) for line in open(tel["events_path"])]
    assert any(e["name"] == "metrics" for e in events)
    assert any(e["kind"] == "span" for e in events)

    # Chrome trace: Perfetto-loadable shape with the host spans; the
    # stages are scopes of the compiled step, not trace-time spans.
    trace = json.load(open(tel["trace_path"]))
    assert isinstance(trace["traceEvents"], list)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "collect_metrics" in names
    assert not {"partition", "shuffle", "join"} & names
    step = make_distributed_join(TpuCommunicator(n_ranks=8), key="key",
                                 shuffle="ragged", out_capacity_factor=3.0)
    assert {"partition", "shuffle", "join"} <= _layer_scopes(
        step.lower(*tables).compile().as_text())
    complete = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert complete and all(
        {"name", "ts", "dur", "pid", "tid"} <= set(e) for e in complete
    )


def test_driver_record_off_mode_unchanged():
    """Without --telemetry the record gains only the schema stamp —
    no telemetry block, and the run is the seed path."""
    from distributed_join_tpu.benchmarks import (
        distributed_join as dj_driver,
    )

    assert not telemetry.enabled()
    args = dj_driver.parse_args([
        "--build-table-nrows", "4096", "--probe-table-nrows", "4096",
        "--communicator", "tpu", "--iterations", "1",
        "--out-capacity-factor", "3.0",
    ])
    record = dj_driver.run(args)
    assert record["schema_version"] == 2
    assert record["rank"] == 0
    assert "telemetry" not in record


# -- live-observability plumbing (ISSUE 7) ----------------------------


def test_counter_track_events_in_chrome_trace(tmp_path):
    """Host counters must land in the Chrome trace as counter-track
    ("ph": "C") events carrying the RUNNING total — so Perfetto plots
    rows/bytes over time instead of the counters existing only as one
    final summary number."""
    d = str(tmp_path / "tel")
    with telemetry.session(d, rank=0) as sink:
        telemetry.counter_add("demo.rows", 5)
        telemetry.counter_add("demo.rows", 7)
        telemetry.counter_add("demo.bytes", 100)
        trace_path = sink.trace_path
    trace = json.load(open(trace_path))
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    rows = [e["args"]["value"] for e in counters
            if e["name"] == "demo.rows"]
    assert rows == [5, 12]                     # cumulative series
    assert [e["args"]["value"] for e in counters
            if e["name"] == "demo.bytes"] == [100]
    # still a valid Chrome trace per the analyze shape check
    from distributed_join_tpu.telemetry.analyze import check_file

    assert check_file(trace_path) == []


def test_request_scope_tags_events_and_spans(tmp_path):
    """Everything recorded inside telemetry.request_scope carries the
    request id — in the JSONL record AND the trace args — including
    events emitted from another thread (the watchdog-worker case);
    records outside the scope stay untagged."""
    import threading

    d = str(tmp_path / "tel")
    with telemetry.session(d, rank=0) as sink:
        telemetry.event("before")
        with telemetry.request_scope("req-000042"):
            telemetry.event("inside")
            with telemetry.span("request_stage"):
                pass
            t = threading.Thread(
                target=lambda: telemetry.event("from_worker"))
            t.start()
            t.join()
        telemetry.event("after")
        events_path, trace_path = sink.events_path, sink.trace_path
    by_name = {}
    for line in open(events_path):
        ev = json.loads(line)
        by_name[ev["name"]] = ev
    assert by_name["inside"]["request_id"] == "req-000042"
    assert by_name["from_worker"]["request_id"] == "req-000042"
    assert by_name["request_stage"]["request_id"] == "req-000042"
    assert "request_id" not in by_name["before"]
    assert "request_id" not in by_name["after"]
    trace = json.load(open(trace_path))
    args_by_name = {e["name"]: e.get("args", {})
                    for e in trace["traceEvents"]}
    assert args_by_name["inside"]["request_id"] == "req-000042"
    assert args_by_name["request_stage"]["request_id"] == "req-000042"
    assert "request_id" not in args_by_name["before"]


def test_request_scope_noop_when_off():
    assert not telemetry.enabled()
    with telemetry.request_scope("req-1"):
        telemetry.event("ignored")          # must not raise


def test_payload_request_id_wins_over_scope(tmp_path):
    """An event fired concurrently with another request's scope (the
    admission-rejection case — emitted outside the exec lock) carries
    ITS OWN payload request_id, never the scope's tag."""
    d = str(tmp_path / "tel")
    with telemetry.session(d, rank=0) as sink:
        with telemetry.request_scope("req-A"):
            # request B's rejection, stamped explicitly by admission
            telemetry.event("request_rejected", request_id="req-B")
            telemetry.event("scoped_event")
        events_path = sink.events_path
    by_name = {json.loads(l)["name"]: json.loads(l)
               for l in open(events_path)}
    assert by_name["request_rejected"]["request_id"] == "req-B"
    assert by_name["scoped_event"]["request_id"] == "req-A"
