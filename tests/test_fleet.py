"""The fault-tolerant serving fleet (distributed_join_tpu/service/
fleet.py) on the 8-virtual-device CPU mesh.

Replica-failure semantics (docs/FLEET.md, ISSUE 15):

- **Affinity.** The router hashes the SAME canonical
  workload-signature digest the program cache and tuner key on —
  computed over abstract tables from the wire spec, it must equal the
  digest a replica computes over the real tables — and repeats land
  on one replica.
- **Kill.** SIGKILL (here: the in-process analog, a closed listening
  socket) mid-traffic: the repeat fails over to the next affine
  replica within the bounded retry budget and answers pandas-oracle
  exact; the dead replica is drained and replaced.
- **Hang.** A FaultPlan dispatch delay blows the replica's watchdog
  deadline: the HangError surfaces to the router, the poisoned
  replica is drained + replaced, and the follow-up repeat dispatches
  WARM on the replacement (zero new programs, persist-dir locked).
- **Corrupt.** The integrity rung refuses loudly THROUGH the router
  (the IntegrityError passes to the client untouched) and the fleet
  never returns wrong rows; the replica is not drained (its
  corruption budget is spent) and keeps serving oracle-exact.
- **Shedding.** Admission at the router (inflight bound + the
  p95/QPS policy over probed LiveMetrics snapshots) sheds with a
  structured AdmissionError — never an unbounded queue — and the
  fleet gauges ride the Prometheus exposition.

In-process replicas run over DISJOINT device subsets of the one CPU
runtime (2 replicas x 2 devices); the subprocess path is exercised by
the ``fleet`` lane's smoke and the ``chaos --fleet`` soak.
"""

import json
import socketserver
import threading
import time

import pytest

from distributed_join_tpu.parallel.faults import (
    FaultInjectingCommunicator,
    FaultPlan,
)
from distributed_join_tpu.service import fleet as fleet_mod
from distributed_join_tpu.service.fleet import (
    FleetConfig,
    FleetRouter,
    affine_replica,
    affinity_key,
    in_process_fleet_factory,
    start_router_daemon,
)
from distributed_join_tpu.service.server import (
    ServiceClient,
    ServiceConfig,
)

pytestmark = pytest.mark.fleet

# One canonical wire query for every fleet test: ONE compiled program
# shape per replica slot, shared through the persistent XLA cache.
Q = {"op": "join", "build_nrows": 1024, "probe_nrows": 1024,
     "seed": 5, "selectivity": 0.4, "rand_max": 512,
     "out_capacity_factor": 3.0}


def oracle_matches(spec) -> int:
    from distributed_join_tpu.service.server import _tables_from_spec

    build, probe = _tables_from_spec(spec)
    return len(build.to_pandas().merge(probe.to_pandas(), on="key"))


def make_fleet(tmp_path, *, comm_wrap=None, service_config=None,
               probe_interval_s=0.2, **cfg_overrides):
    cfg = FleetConfig(
        n_replicas=2, replica_ranks=2,
        probe_interval_s=probe_interval_s,
        suspect_strikes=1, retry_budget=2,
        **cfg_overrides)
    factory = in_process_fleet_factory(
        2, 2, service_config=service_config, comm_wrap=comm_wrap,
        persist_dir=str(tmp_path / "programs"))
    router = FleetRouter(factory, cfg)
    router.start()
    server, port = start_router_daemon(router)
    client = ServiceClient("127.0.0.1", port)
    return router, server, client


def teardown_fleet(router, server, client):
    client.close()
    server.shutdown()
    server.server_close()
    router.stop()


# -- affinity ----------------------------------------------------------


def test_affinity_key_matches_replica_side_signature():
    """The router-side hash (abstract tables from the wire spec) IS
    the digest a replica computes over the real generated tables —
    the 'repeat workloads land where their executable is resident'
    contract cannot drift between the two sides."""
    from distributed_join_tpu.planning.tuner import workload_signature
    from distributed_join_tpu.service.server import (
        _join_opts_from_spec,
        _tables_from_spec,
    )

    spec = dict(Q)
    build, probe = _tables_from_spec(spec)

    class Stub:
        n_ranks = 2
        n_slices = 1

    replica_side = workload_signature(
        Stub(), build, probe, with_metrics=False,
        **_join_opts_from_spec(spec))
    assert affinity_key(spec, replica_ranks=2) == replica_side


def test_affinity_key_deterministic_and_spec_sensitive():
    assert affinity_key(Q, 2) == affinity_key(dict(Q), 2)
    other = {**Q, "build_nrows": 2048}
    assert affinity_key(other, 2) != affinity_key(Q, 2)
    # Table-management ops co-locate by handle name.
    reg = {"op": "register", "name": "dim", "rows": 512}
    join = {"op": "join", "table": "dim", "probe_nrows": 256}
    assert affinity_key(reg, 2) == affinity_key(join, 2)
    assert affinity_key(reg, 2) != affinity_key(
        {"op": "register", "name": "dim2", "rows": 512}, 2)
    # affine_replica is the ring start everyone (router + chaos
    # harness) derives from the key.
    assert affine_replica(Q, 2, 2) == int(
        affinity_key(Q, 2)[:8], 16) % 2


# -- fake replicas: the state machine without a mesh -------------------


class FakeReplica:
    """A wire-protocol replica with a pluggable handler — the state
    machine and shedding tests without any jax."""

    def __init__(self, handler):
        outer = self

        class H(socketserver.StreamRequestHandler):
            def handle(self):
                for raw in self.rfile:
                    line = raw.strip()
                    if not line:
                        continue
                    resp = outer.handler(json.loads(line))
                    self.wfile.write(
                        (json.dumps(resp) + "\n").encode())
                    self.wfile.flush()

        class S(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self.handler = handler
        self.server = S(("127.0.0.1", 0), H)
        self.host, self.port = ("127.0.0.1",
                                self.server.server_address[1])
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        self._dead = False

    def alive(self):
        return not self._dead

    def kill(self):
        if not self._dead:
            self._dead = True
            self.server.shutdown()
            self.server.server_close()

    def stop(self, timeout_s=10.0):  # noqa: ARG002 - backend API
        self.kill()


def _ok_handler(req):
    op = req.get("op")
    if op == "stats":
        return {"ok": True, "poisoned": None, "draining": None,
                "qps_60s": 0.0, "latency": {}}
    if op == "drain":
        return {"ok": True, "op": "drain", "drained": True}
    return {"ok": True, "op": op, "matches": 7, "new_traces": 0,
            "overflow": False, "request_id": req.get("request_id")}


def test_probe_drains_poisoned_replica_and_replaces():
    """stats showing ``poisoned`` -> drained within one probe
    interval -> replaced at generation 1 (the factory hands back a
    healthy fake); the drain is flight-recorded with a replica
    stamp."""
    poisoned = {"flag": False}

    def sick_handler(req):
        resp = _ok_handler(req)
        if req.get("op") == "stats" and poisoned["flag"]:
            resp["poisoned"] = "request req-x blew its deadline"
        return resp

    def factory(index, generation):
        if index == 0 and generation == 0:
            return FakeReplica(sick_handler)
        return FakeReplica(_ok_handler)

    cfg = FleetConfig(n_replicas=2, replica_ranks=2,
                      probe_interval_s=0.1)
    router = FleetRouter(factory, cfg)
    router.start()
    try:
        poisoned["flag"] = True
        t0 = time.monotonic()
        assert router.wait_replaced(0, timeout_s=10.0)
        rep = router.replicas[0]
        assert rep.generation == 1
        assert rep.state == "healthy"
        assert rep.drained_at is not None
        assert rep.drained_at - t0 <= 5 * cfg.probe_interval_s + 1.0
        assert router.stats()["drains_total"] == 1
        assert router.stats()["replaced_total"] == 1
        recs = router.recorder.snapshot()["records"]
        drains = [r for r in recs if r["op"] == "drain_replica"]
        assert drains and drains[0]["replica"]["index"] == 0
    finally:
        router.stop()


def test_dead_connection_strikes_to_drain_and_failover():
    """A torn connection mid-request: strike -> drained (strikes
    bound 1) -> the request fails over to the sibling and serves;
    failovers_total counts it."""
    def factory(index, generation):
        return FakeReplica(_ok_handler)

    cfg = FleetConfig(n_replicas=2, replica_ranks=2,
                      probe_interval_s=30.0, suspect_strikes=1,
                      retry_budget=2, retry_backoff_s=0.01,
                      respawn=False)
    router = FleetRouter(factory, cfg)
    router.start()
    try:
        victim = affine_replica(Q, 2, 2)
        router.replicas[victim].backend.kill()
        resp = router.dispatch(dict(Q))
        assert resp["ok"] and resp["matches"] == 7
        assert resp["fleet"]["replica"] == 1 - victim
        assert resp["fleet"]["attempts"] == 2
        assert router.replicas[victim].state == "drained"
        assert router.stats()["failovers_total"] == 1
    finally:
        router.stop()


def test_admission_sheds_structured_never_queues():
    """No admittable replica (inflight bound 0) -> a structured
    AdmissionError response with ``shed: true``, immediately — and
    the p95 policy sheds from the probed stats snapshot alone."""
    def factory(index, generation):
        return FakeReplica(_ok_handler)

    cfg = FleetConfig(n_replicas=2, replica_ranks=2,
                      probe_interval_s=30.0,
                      max_inflight_per_replica=0)
    router = FleetRouter(factory, cfg)
    router.start()
    try:
        resp = router.dispatch(dict(Q))
        assert not resp["ok"]
        assert resp["error"] == "AdmissionError" and resp["shed"]
        assert router.stats()["shed_total"] == 1

        # p95-driven: bounds read from the replicas' own probed
        # LiveMetrics snapshots.
        router.config.max_inflight_per_replica = 4
        router.config.shed_p95_s = 0.5
        for rep in router.replicas:
            rep.last_stats = {"qps_60s": 1.0,
                              "latency": {"p95_s": 2.0}}
        resp = router.dispatch(dict(Q))
        assert not resp["ok"] and resp["shed"]
        router.config.shed_p95_s = None
        resp = router.dispatch(dict(Q))
        assert resp["ok"]
    finally:
        router.stop()


def test_duplicate_request_id_parks_never_dispatches_concurrently():
    """The duplicate-dispatch fence: a resend of an id still in
    flight PARKS until the original settles, then serves (the
    reconnect-and-resend client whose first answer was lost must get
    one) — the two dispatches never overlap on a replica — and a
    duplicate still blocked past the request deadline is refused
    with a structured error."""
    release = threading.Event()
    concurrency = {"now": 0, "max": 0}
    lock = threading.Lock()

    def slow_handler(req):
        if req.get("op") == "join":
            with lock:
                concurrency["now"] += 1
                concurrency["max"] = max(concurrency["max"],
                                         concurrency["now"])
            release.wait(timeout=10.0)
            with lock:
                concurrency["now"] -= 1
        return _ok_handler(req)

    def factory(index, generation):
        return FakeReplica(slow_handler)

    cfg = FleetConfig(n_replicas=2, replica_ranks=2,
                      probe_interval_s=30.0,
                      request_deadline_s=30.0)
    router = FleetRouter(factory, cfg)
    router.start()
    try:
        out = {}

        def send(slot):
            out[slot] = router.dispatch(
                {**Q, "request_id": "dup-1"})

        def wait_registered():
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                with router._lock:
                    if "dup-1" in router._inflight_ids:
                        return
                time.sleep(0.01)
            raise AssertionError("original never registered")

        t1 = threading.Thread(target=send, args=("first",))
        t1.start()
        wait_registered()
        t2 = threading.Thread(target=send, args=("dup",))
        t2.start()
        time.sleep(0.3)
        assert "dup" not in out, "the duplicate must park, not race"
        release.set()
        t1.join(timeout=10.0)
        t2.join(timeout=10.0)
        assert out["first"]["ok"] and out["dup"]["ok"]
        assert concurrency["max"] == 1, \
            "duplicate id dispatched concurrently with the original"

        # Past the request deadline the parked duplicate refuses.
        # The deadline shrinks only once the original is BLOCKED
        # inside the replica (so the original itself captured the
        # long deadline and stays in flight past the fence window).
        release.clear()
        t3 = threading.Thread(target=send, args=("slow",))
        t3.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with lock:
                if concurrency["now"] == 1:
                    break
            time.sleep(0.01)
        router.config.request_deadline_s = 0.3
        late = router.dispatch({**Q, "request_id": "dup-1"})
        release.set()
        t3.join(timeout=10.0)
        assert not late["ok"] and late["error"] == "FleetError"
        assert "still in flight" in late["message"]
    finally:
        release.set()
        router.stop()


# -- multi-tenant admission: quotas, priority shed, autoscaling --------


def test_tenant_quota_refusals_structured_and_named():
    """Each per-tenant bound refuses with a structured
    QuotaExceededError NAMING the bound crossed (QPS bucket,
    inflight cap, p95), stamped with ``shed`` + the tenant — and an
    UNSTAMPED request is byte-identical to the pre-tenant contract
    (no ``tenant`` key anywhere)."""
    def factory(index, generation):
        return FakeReplica(_ok_handler)

    cfg = FleetConfig(
        n_replicas=2, replica_ranks=2, probe_interval_s=30.0,
        tenants={"b": {"qps": 0.001, "burst_s": 1.0},
                 "c": {"max_inflight": 0},
                 "d": {"shed_p95_s": 0.5}})
    router = FleetRouter(factory, cfg)
    router.start()
    try:
        # QPS bucket: holds max(qps*burst, 1) = 1 token — the first
        # request spends it, the back-to-back repeat refuses.
        first = router.dispatch({**Q, "tenant": "b"})
        assert first["ok"], first
        second = router.dispatch({**Q, "tenant": "b"})
        assert not second["ok"]
        assert second["error"] == "QuotaExceededError"
        assert second["shed"] and second["tenant"] == "b"
        assert "QPS quota" in second["message"]

        # Inflight cap.
        capped = router.dispatch({**Q, "tenant": "c"})
        assert capped["error"] == "QuotaExceededError"
        assert "max_inflight" in capped["message"]

        # Per-tenant p95 bound, read from the probed snapshots the
        # global shed policy uses.
        for rep in router.replicas:
            rep.last_stats = {"qps_60s": 1.0,
                              "latency": {"p95_s": 2.0}}
        slow = router.dispatch({**Q, "tenant": "d"})
        assert slow["error"] == "QuotaExceededError"
        assert "p95" in slow["message"]

        st = router.stats()["tenants"]
        assert st["b"]["quota_sheds"] == 1
        assert st["c"]["quota_sheds"] == 1
        assert st["d"]["quota_sheds"] == 1
        assert st["b"]["shed"] == 1 and st["b"]["inflight"] == 0

        # The default tenant rides the legacy contract untouched.
        legacy = router.dispatch(dict(Q))
        assert legacy["ok"] and "tenant" not in legacy
        assert set(router.stats()["tenants"]) == {"b", "c", "d"}
    finally:
        router.stop()


def test_priority_shed_order_low_yields_first():
    """Under the SAME fleet pressure the low-priority tenant's
    per-replica headroom (its priority share of the fleet inflight
    bound) runs out first: bronze sheds with ShedError naming the
    priority bound while gold — and the pressure gone — both
    serve."""
    def factory(index, generation):
        return FakeReplica(_ok_handler)

    cfg = FleetConfig(
        n_replicas=2, replica_ranks=2, probe_interval_s=30.0,
        max_inflight_per_replica=2,
        tenants={"low": {"priority": 1}, "high": {"priority": 2}})
    router = FleetRouter(factory, cfg)
    router.start()
    try:
        with router._lock:
            for rep in router.replicas:
                rep.inflight += 1
        low = router.dispatch({**Q, "tenant": "low"})
        high = router.dispatch({**Q, "tenant": "high"})
        with router._lock:
            for rep in router.replicas:
                rep.inflight = max(rep.inflight - 1, 0)
        assert not low["ok"] and low["error"] == "ShedError"
        assert low["shed"] and low["tenant"] == "low"
        assert "priority" in low["message"]
        assert high["ok"], \
            "the high-priority tenant must ride the SAME pressure"
        assert router.stats()["tenants"]["low"][
            "priority_sheds"] == 1
        relieved = router.dispatch({**Q, "tenant": "low"})
        assert relieved["ok"], relieved
    finally:
        router.stop()


def test_autoscaler_spawns_warm_verified_then_drains_idle(tmp_path):
    """The signature-level control loop: sustained probed QPS over
    the up bound spawns replica 2 — pre-warm gated on a replay of
    the hottest retained spec with ZERO new traces BEFORE rotation —
    and a sustained idle fleet drains it back, never below the base
    replica count. The fleet_autoscale record passes analyze."""
    from distributed_join_tpu.telemetry.analyze import check_file

    def factory(index, generation):
        return FakeReplica(_ok_handler)

    cfg = FleetConfig(
        n_replicas=2, replica_ranks=2, probe_interval_s=30.0,
        autoscale=True, autoscale_max_replicas=3,
        autoscale_up_qps=0.5, autoscale_interval_s=0.05,
        autoscale_sustain=2, autoscale_down_qps=0.1,
        autoscale_idle_s=0.3)
    router = FleetRouter(factory, cfg)
    router.start()
    try:
        served = router.dispatch(dict(Q))  # retains the hot spec
        assert served["ok"]
        with router._lock:
            for rep in router.replicas:
                rep.last_stats = {"qps_60s": 5.0,
                                  "latency": {"p95_s": 0.01}}
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with router._lock:
                if router.autoscale_spawns_total >= 1:
                    break
            time.sleep(0.02)
        record = router.autoscale_record()
        spawns = [e for e in record["events"]
                  if e["action"] == "spawn"]
        assert spawns, record["events"]
        ev = spawns[0]
        assert ev["replica"] == 2
        assert ev["warm_verified"] and ev["new_traces"] == 0
        assert ev["signature"] == affinity_key(Q, 2)
        with router._lock:
            scaled = [r for r in router.replicas if r.index == 2]
        assert scaled and scaled[0].state == "healthy"
        assert router.stats()["autoscale"]["spawns_total"] == 1
        # No runaway: at the max, sustained heat spawns nothing.
        time.sleep(0.3)
        assert router.autoscale_spawns_total == 1

        # Idle: QPS under the down bound + nothing in flight,
        # sustained past autoscale_idle_s, drains the SCALED replica
        # only — the base fleet never shrinks.
        with router._lock:
            for rep in router.replicas:
                rep.last_stats = {"qps_60s": 0.0, "latency": {}}
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            record = router.autoscale_record()
            if any(e["action"] == "drain"
                   for e in record["events"]):
                break
            time.sleep(0.02)
        assert [e["action"] for e in record["events"]].count(
            "drain") == 1
        with router._lock:
            live = [r.index for r in router.replicas
                    if r.state in ("healthy", "suspect")]
        assert sorted(live) == [0, 1], \
            "only the scaled-up replica drains"
        time.sleep(0.5)
        assert router.autoscale_drains_total == 1, \
            "the base fleet must never shrink below n_replicas"

        out = tmp_path / "autoscale.json"
        out.write_text(json.dumps(record))
        assert check_file(str(out)) == []
    finally:
        router.stop()


# -- real replicas over disjoint device subsets ------------------------


def test_kill_failover_oracle_exact_and_replacement_warm(tmp_path):
    """The full kill story end to end: affinity holds warm, the
    killed affine replica's repeat fails over oracle-exact within
    the budget, the slot is drained + replaced, and the replacement
    serves the repeat signature WARM (zero new traces via its slot's
    persist dir). History lines carry validated replica stamps and
    the fleet gauges ride Prometheus."""
    # probe_interval 10s: the dead replica must be discovered by the
    # REQUEST path (strike -> drain -> failover), not raced away by
    # the prober — failovers_total is then deterministic.
    router, server, client = make_fleet(
        tmp_path, history_dir=str(tmp_path / "hist"),
        probe_interval_s=10.0)
    try:
        expected = oracle_matches(Q)
        cold = client.send(Q)
        warm = client.send(Q)
        assert cold["ok"] and warm["ok"]
        assert cold["matches"] == warm["matches"] == expected
        assert warm["fleet"]["replica"] == cold["fleet"]["replica"]
        assert warm["new_traces"] == 0

        victim = router.replicas[cold["fleet"]["replica"]]
        victim.backend.kill()
        failover = client.send(Q)
        assert failover["ok"], failover
        assert failover["matches"] == expected
        assert failover["fleet"]["replica"] != victim.index
        assert failover["fleet"]["attempts"] <= \
            router.config.retry_budget + 1

        assert router.wait_replaced(victim.index, timeout_s=60.0)
        direct = ServiceClient(*victim.addr())
        try:
            replay = direct.send(Q)
        finally:
            direct.close()
        assert replay["ok"] and replay["matches"] == expected
        slot = tmp_path / "programs" / f"r{victim.index}"
        assert replay["new_traces"] == 0, (
            "replacement must load its slot's persisted programs",
            replay["cache"],
            sorted(p.name for p in slot.iterdir())
            if slot.is_dir() else "missing slot dir")

        stats = router.stats()
        assert stats["healthy"] == 2
        assert stats["replaced_total"] == 1
        assert stats["failovers_total"] >= 1
        prom = router.prometheus_metrics()
        for gauge in ("djtpu_fleet_replicas 2",
                      "djtpu_fleet_healthy 2",
                      "djtpu_fleet_drained 0",
                      "djtpu_fleet_failovers_total",
                      "djtpu_fleet_shed_total",
                      "djtpu_fleet_replaced_total 1"):
            assert gauge in prom, (gauge, prom)
    finally:
        teardown_fleet(router, server, client)

    from distributed_join_tpu.telemetry.analyze import check_file

    hist = tmp_path / "hist" / "history.jsonl"
    assert check_file(str(hist)) == []
    entries = [json.loads(ln) for ln in
               hist.read_text().splitlines()]
    stamped = [e for e in entries if e.get("replica")]
    assert stamped, "router history must stamp serving replicas"
    assert {"index", "generation"} <= set(stamped[0]["replica"])


def test_hang_drains_replaces_and_followup_is_warm(tmp_path):
    """FaultPlan dispatch delay -> the replica's watchdog deadline
    fires -> HangError surfaces through the router -> drain +
    replace; the hung request itself fails over and serves, and the
    replacement serves the repeat signature warm."""
    victim_index = affine_replica(Q, 2, 2)

    def wrap(index, generation, comm):
        if index == victim_index and generation == 0:
            # Delay-free for the first 2 dispatches (cold trace +
            # warm repeat — the per-request deadline must cover the
            # real cold compile), then a 30s stall against the 8s
            # deadline.
            return FaultInjectingCommunicator(
                comm, FaultPlan(dispatch_delay_s=30.0,
                                delay_after_dispatches=2))
        return comm

    router, server, client = make_fleet(
        tmp_path, comm_wrap=wrap,
        service_config=ServiceConfig(request_deadline_s=8.0))
    try:
        expected = oracle_matches(Q)
        cold = client.send(Q)
        warm = client.send(Q)
        assert cold["ok"] and warm["ok"]
        assert cold["fleet"]["replica"] == victim_index
        assert warm["new_traces"] == 0

        hung = client.send(Q)  # 3rd dispatch on the victim: hangs
        assert hung["ok"], hung
        assert hung["matches"] == expected
        assert hung["fleet"]["replica"] != victim_index
        assert hung["fleet"]["failovers"] >= 1

        assert router.wait_replaced(victim_index, timeout_s=60.0)
        rep = router.replicas[victim_index]
        assert rep.generation == 1
        # The hang surfaces on whichever path wins the race: the
        # request path (HangError through the router) or the 0.2s
        # prober seeing the watchdog-poisoned replica ("probe saw
        # poisoned: ... did not complete within ..."). Both reasons
        # are the watchdog deadline talking; either proves the drain
        # was FOR the hang.
        reason = rep.drained_reason or ""
        assert ("hang" in reason.lower()
                or "did not complete" in reason), reason

        # The replacement serves the repeat signature. (The
        # ZERO-TRACE warm replacement is a shared-persist-dir
        # property: a fault-WRAPPED comm's spmd returns a plain
        # callable, so the in-process victim never persisted — the
        # subprocess smoke and the chaos --fleet hang soak lock the
        # zero-trace gate where the persist dir is really shared.)
        direct = ServiceClient(*rep.addr())
        try:
            replay = direct.send(Q)
        finally:
            direct.close()
        assert replay["ok"] and replay["matches"] == expected
    finally:
        teardown_fleet(router, server, client)
        # Drain the detached watchdog worker before the suite moves
        # on: it is still sleeping toward (then RUNNING) the delayed
        # dispatch, and it must not overlap the interpreter's exit
        # (the _poison_drill smoke does the same).
        for t in threading.enumerate():
            if t.name.startswith("watchdog-request"):
                t.join(timeout=120.0)


def test_corrupt_refuses_loudly_through_router_never_wrong_rows(
        tmp_path):
    """An armed corruption mode + --verify-integrity semantics with
    no retry budget: the IntegrityError passes THROUGH the router to
    the client (a refusal, never wrong rows), the replica is NOT
    drained (its trace-time budget is spent), and the repeat serves
    oracle-exact."""
    victim_index = affine_replica(Q, 2, 2)

    def wrap(index, generation, comm):
        if index == victim_index and generation == 0:
            return FaultInjectingCommunicator(
                comm, FaultPlan(seed=7, corrupt_mode="bit_flip",
                                corrupt_collectives=1))
        return comm

    router, server, client = make_fleet(
        tmp_path, comm_wrap=wrap,
        service_config=ServiceConfig(verify_integrity=True,
                                     auto_retry=0))
    try:
        expected = oracle_matches(Q)
        first = client.send(Q)
        assert not first["ok"], \
            "the corrupted exchange must refuse, not answer"
        assert first["error"] == "IntegrityError", first
        # A client-level refusal is NOT a replica fault: no drain.
        assert router.replicas[victim_index].state != "drained"
        # Budget spent at trace time: the re-trace serves clean, and
        # the answer is oracle-exact — the fleet never returned a
        # wrong row in between.
        second = client.send(Q)
        assert second["ok"], second
        assert second["matches"] == expected
        assert second["fleet"]["replica"] == victim_index
        assert router.stats()["drains_total"] == 0
    finally:
        teardown_fleet(router, server, client)


def test_program_cache_is_tenant_free_history_is_not(tmp_path):
    """The shared program cache stays SHARED across tenants: the
    compiled executable is keyed by workload signature alone
    (tenant-free by construction), so tenant beta's first request
    for alpha's signature is a warm cache hit on the SAME affine
    replica — while the router's history stamps each entry with its
    tenant and the tuner trend table keys ``tenant/signature``."""
    router, server, client = make_fleet(
        tmp_path, history_dir=str(tmp_path / "hist"),
        probe_interval_s=10.0)
    try:
        cold = client.send({**Q, "tenant": "alpha"})
        assert cold["ok"], cold
        warm = client.send({**Q, "tenant": "beta"})
        assert warm["ok"], warm
        assert warm["fleet"]["replica"] == cold["fleet"]["replica"], \
            "affinity must ignore the tenant stamp"
        assert warm["new_traces"] == 0, \
            "tenant beta must hit alpha's compiled executable"
    finally:
        teardown_fleet(router, server, client)

    from distributed_join_tpu.telemetry import history as hist_mod
    from distributed_join_tpu.telemetry.analyze import check_file

    hist = tmp_path / "hist" / "history.jsonl"
    assert check_file(str(hist)) == []
    entries = [json.loads(ln) for ln in
               hist.read_text().splitlines()]
    reqs = [e for e in entries if e.get("kind") == "request"]
    assert {e.get("tenant") for e in reqs} == {"alpha", "beta"}
    # The trend namespace: same signature, one row per tenant.
    sig = fleet_mod.affinity_key(Q, 2)
    assert hist_mod.tenant_key(sig, "alpha") == f"alpha/{sig}"
    assert hist_mod.tenant_key(sig, None) == sig
    trends = hist_mod.trends_of(reqs)
    assert f"alpha/{sig}" in trends and f"beta/{sig}" in trends


def test_tenant_artifact_schemas(tmp_path):
    """`analyze check` recognizes the three tenancy artifact kinds
    by their stamps and flags gutted ones."""
    from distributed_join_tpu.telemetry.analyze import check_file

    docs = {
        "soak.json": {
            "kind": "fleet_tenant_soak", "schema_version": 1,
            "harness_seed": 7, "slice": "tenants", "victim": 1,
            "replica_ranks": 2, "trials": 4,
            "verdicts": {"ok": 4},
            "noisy": {"sent": 40, "quota_shed": 33},
            "quiet": {"trials": 4, "shed_responses": 0},
            "failures": 0},
        "autoscale.json": {
            "kind": "fleet_autoscale", "schema_version": 1,
            "enabled": True, "spawns_total": 1, "drains_total": 0,
            "replicas": 3,
            "events": [{"action": "spawn", "replica": 2,
                        "reason": "sustained load",
                        "warm_verified": True, "new_traces": 0}]},
        "smoke.json": {
            "kind": "fleet_tenant_smoke", "n_ranks": 2,
            "replicas": 2,
            "counter_signature": {"signature_version": 1,
                                  "n_ranks": 2,
                                  "counters": {"replicas": 2}},
            "tenants": {"gold": {}}, "autoscale": {}},
    }
    gut = {"fleet_tenant_soak": "noisy",
           "fleet_autoscale": "events",
           "fleet_tenant_smoke": "counter_signature"}
    for name, doc in docs.items():
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        assert check_file(str(p)) == [], name
        gutted = dict(doc)
        gutted.pop(gut[doc["kind"]])
        bad = tmp_path / ("bad_" + name)
        bad.write_text(json.dumps(gutted))
        assert check_file(str(bad)), \
            f"a gutted {doc['kind']} artifact must be flagged"


def test_fleet_soak_artifact_schema():
    """`analyze check` recognizes the fleet_soak artifact kind by
    its stamp (any filename)."""
    import tempfile

    from distributed_join_tpu.telemetry.analyze import check_file

    doc = {"kind": "fleet_soak", "schema_version": 1,
           "harness_seed": 42, "slice": "fleet", "fault": "kill",
           "victim": 0, "replica_ranks": 2, "trials": 20,
           "verdicts": {"ok": 19, "recovered": 1}, "answered": 20,
           "failures": 0,
           "drain_replace": {"required": True, "drained": True,
                             "replaced": True}}
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(doc, f)
        path = f.name
    assert check_file(path) == []
    bad = dict(doc)
    bad.pop("verdicts")
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(bad, f)
        bad_path = f.name
    assert check_file(bad_path), \
        "a verdict-less fleet_soak artifact must be flagged"


def test_fleet_module_exports():
    """The pieces the chaos harness and the lane scripts reach for."""
    assert callable(fleet_mod.process_fleet_factory)
    assert callable(fleet_mod.run_fleet_smoke)
    assert hasattr(fleet_mod, "main")


@pytest.mark.parametrize("platform,cfg_kw,refused", [
    ("tpu", dict(n_replicas=2), True),
    ("default", dict(n_replicas=1, autoscale=True,
                     autoscale_max_replicas=3), True),
    ("tpu", dict(n_replicas=1), False),
    ("cpu", dict(n_replicas=4), False),
])
def test_process_fleet_refuses_two_processes_per_chip(platform, cfg_kw,
                                                      refused):
    """A chip belongs to one process: a process fleet that may hold two
    replica processes on a chip platform refuses before spawning."""
    cfg = fleet_mod.FleetConfig(**cfg_kw)
    if refused:
        with pytest.raises(fleet_mod.ChipSharingError,
                           match="one process per chip"):
            fleet_mod.process_fleet_factory(cfg, platform=platform)
    else:
        assert callable(fleet_mod.process_fleet_factory(
            cfg, platform=platform))
