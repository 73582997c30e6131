#!/usr/bin/env bash
# The one blessed test entrypoint (builders + CI invoke this, nothing
# else), encoding the ROADMAP.md tier-1 command VERBATIM plus a fast
# failure-semantics smoke lane.
#
#   scripts/run_tier1.sh            # full tier-1 (ROADMAP verbatim)
#   scripts/run_tier1.sh faults     # fast lane: -m faults smoke only
#   scripts/run_tier1.sh telemetry  # fast lane: -m telemetry smoke only
#   scripts/run_tier1.sh analysis   # fast lane: -m 'analysis or
#                                   # explain' suites + an --explain
#                                   # driver smoke whose padded-mode
#                                   # wire-byte prediction is gated
#                                   # EXACTLY vs measured counters
#   scripts/run_tier1.sh perfgate   # deterministic CPU-mesh join vs.
#                                   # the committed counter-signature
#                                   # baseline + artifact schema check
#                                   # + wire-contract drift gate
#   scripts/run_tier1.sh lint       # joinlint, all three checkers:
#                                   # AST SPMD-hazard + concurrency
#                                   # rules (DJL001-010), wire-protocol
#                                   # contract vs results/contracts/
#                                   # wire_ops.json, jaxpr collective-
#                                   # schedule check vs
#                                   # results/schedules/ goldens
#   scripts/run_tier1.sh chaos      # fixed-seed ~20-trial chaos soak
#                                   # (faults x configs, pandas-oracle
#                                   # verified, wire digests on) +
#                                   # -m chaos unit suite
#   scripts/run_tier1.sh service    # join-as-a-service: -m service
#                                   # unit suite + the daemon smoke
#                                   # (warm second query = zero new
#                                   # traces, batched 16-way beats 16
#                                   # sequential warm calls, live
#                                   # metrics quantiles, poison drill)
#                                   # + schema checks over the flight
#                                   # recorder and workload-history
#                                   # artifacts, on the CPU mesh
#   scripts/run_tier1.sh stageprof  # stage-segmented profiling: -m
#                                   # stageprof suite + a deterministic
#                                   # CPU-mesh --stage-profile driver
#                                   # smoke — stageprofile.json schema-
#                                   # checked, `analyze stages` renders
#                                   # it, the padded per-stage wire-
#                                   # byte split gated EXACTLY vs the
#                                   # Metrics counters, and the
#                                   # stage-sum >= monolithic floor
#                                   # (noise-robust min walls) gated
#   scripts/run_tier1.sh resident   # resident build tables: -m
#                                   # resident suite (probe-only
#                                   # oracle correctness, LSM delta
#                                   # merges, conservation chaos
#                                   # slice) + the daemon smoke's
#                                   # resident A/B with the strict
#                                   # wall gate (warm probe-only must
#                                   # beat the warm cold full join
#                                   # and add zero traces) + the
#                                   # resident_smoke counter-
#                                   # signature gate
#   scripts/run_tier1.sh hier       # hierarchical ICI/DCN shuffle:
#                                   # -m hier suite + a deterministic
#                                   # nested-mesh (2x4) driver smoke —
#                                   # per-tier wire bytes gated
#                                   # EXACTLY vs the device counters
#                                   # (analyze explain
#                                   # --gate-wire-bytes), the codec-on
#                                   # cross-slice bytes strictly below
#                                   # the flat wire, and the counter
#                                   # signature (matches included)
#                                   # gated vs results/baselines/
#                                   # hier_smoke.json
#   scripts/run_tier1.sh agg        # aggregation pushdown: -m agg
#                                   # suite + a deterministic CPU-mesh
#                                   # driver A/B smoke on the
#                                   # duplicate-key high-fan-out shape
#                                   # — pandas-oracle equality on BOTH
#                                   # sides, zero warm pushdown
#                                   # traces, pushdown strictly faster
#                                   # than materialize-then-host-
#                                   # group-by, counter signature
#                                   # gated vs results/baselines/
#                                   # agg_smoke.json — plus the tpch
#                                   # driver's --agg mode (oracle-
#                                   # graded in-driver)
#   scripts/run_tier1.sh query      # multi-operator query plans
#                                   # (docs/QUERY.md): -m query suite
#                                   # (join-type family edge cases,
#                                   # plan validation/refusals, ONE-
#                                   # program compile lock, service
#                                   # query op) + a deterministic
#                                   # CPU-mesh Q3 driver smoke —
#                                   # whole-query pandas-oracle
#                                   # equality, zero warm traces, ONE
#                                   # traced program, the exact per-
#                                   # operator wire-byte prediction
#                                   # (analyze explain
#                                   # --gate-wire-bytes on the
#                                   # queryplan artifact), and the
#                                   # merged per-operator counter
#                                   # signature gated vs results/
#                                   # baselines/query_smoke.json
#   scripts/run_tier1.sh sortpath   # segmented-sort join pipeline:
#                                   # -m sortpath suite + a
#                                   # deterministic CPU-mesh
#                                   # segmented-vs-flat driver smoke —
#                                   # pandas-oracle equality on BOTH
#                                   # modes, full-content multiset
#                                   # equality, zero warm traces, the
#                                   # exact segmented wire-byte
#                                   # prediction (analyze explain
#                                   # --gate-wire-bytes), and the
#                                   # counter signature gated vs
#                                   # results/baselines/
#                                   # sortpath_smoke.json
#   scripts/run_tier1.sh fleet      # fault-tolerant serving fleet:
#                                   # -m fleet suite (affinity, state
#                                   # machine, kill/hang/corrupt
#                                   # matrix over disjoint-device
#                                   # in-process replicas, shedding,
#                                   # drain semantics) + the
#                                   # deterministic 2-replica
#                                   # subprocess fleet smoke with one
#                                   # SCRIPTED replica kill (oracle
#                                   # equality + drain/replace
#                                   # observed + bounded retry count +
#                                   # zero-trace warm replacement,
#                                   # counter signature gated vs
#                                   # results/baselines/
#                                   # fleet_smoke.json) + the chaos
#                                   # --fleet 20-trial soak (one
#                                   # replica faulted mid-soak, every
#                                   # non-refused answer pandas-
#                                   # oracle-graded) + the two-tenant
#                                   # smoke (quota refusal, priority
#                                   # shed order, warm-verified
#                                   # autoscale spawn) + the chaos
#                                   # --tenants soak (noisy tenant
#                                   # flooded at 5x quota, quiet
#                                   # tenant oracle-exact with zero
#                                   # sheds, replica killed mid-soak)
#   scripts/run_tier1.sh fleet_ha   # durable resident state + router
#                                   # HA (docs/FLEET.md "Replication
#                                   # & HA"): tests/test_fleet_ha.py
#                                   # (manifest/directory schemas,
#                                   # generation fencing via a
#                                   # surgically dropped append,
#                                   # NoHolderError refusal, rebuild-
#                                   # from-manifest, lease fencing,
#                                   # router takeover with request-id-
#                                   # fenced resend) + the --ha-smoke
#                                   # subprocess protocol (K=2
#                                   # replicated register, warm
#                                   # zero-trace serving, holder
#                                   # SIGKILL -> bounded failover ->
#                                   # rebuilt image's fenced ZERO-
#                                   # trace replay, primary router
#                                   # crash -> standby takeover ->
#                                   # idempotent resend, counter
#                                   # signature gated vs results/
#                                   # baselines/fleet_ha_smoke.json,
#                                   # manifest + directory artifacts
#                                   # schema-checked) + the chaos
#                                   # --fleet-fault resident-kill
#                                   # soak (primary HOLDER killed
#                                   # mid-soak: zero wrong rows,
#                                   # failover within budget, rebuild
#                                   # + fenced zero-trace replay)
#   scripts/run_tier1.sh tracing    # fleet-wide distributed tracing
#                                   # (docs/OBSERVABILITY.md
#                                   # "Distributed tracing"):
#                                   # tests/test_tracing.py (trace-
#                                   # context mint/child/wire
#                                   # adoption, sink stamping,
#                                   # request-scope restore, fleet
#                                   # timeline assembly + critical
#                                   # path on synthetic streams,
#                                   # tracing-off parity) + the
#                                   # --tracing-smoke subprocess
#                                   # protocol (2 replicas with per-
#                                   # slot telemetry dirs, scripted
#                                   # SIGKILL -> the failed attempt
#                                   # and the failover retry share
#                                   # ONE trace_id in the flight
#                                   # ring AND the merged Perfetto
#                                   # fleet timeline; both timeline
#                                   # artifacts schema-checked;
#                                   # counter signature gated vs
#                                   # results/baselines/
#                                   # tracing_smoke.json) + `analyze
#                                   # timeline` over the smoke's
#                                   # per-process session dirs
#   scripts/run_tier1.sh tuner      # autotuner: -m tuner suite + a
#                                   # cold/warm driver A/B (warm run
#                                   # must start at the escalated
#                                   # rung: zero ladder escalations)
#                                   # + a service-level zero-trace
#                                   # warm gate + `analyze tune`
#                                   # schema check. Tuner-off stays
#                                   # the exact current path (the
#                                   # lint/perfgate lanes keep the
#                                   # schedule-golden and baseline
#                                   # byte-identity gates)
#
# Notes:
# - the persistent XLA compile cache lives where
#   JAX_COMPILATION_CACHE_DIR says, else in the checkout's .jax_cache/
#   (distributed_join_tpu/device.py); a cold cache pays ~8-device
#   compiles for every shard_map program, a warm one replays them.
set -u
cd "$(dirname "$0")/.."

lane="${1:-tier1}"
case "$lane" in
  tier1)
    # ROADMAP.md "Tier-1 verify", verbatim.
    set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
    ;;
  faults|smoke)
    # Failure-semantics smoke: the injected-fault retry ladder, plan
    # validation, bootstrap backoff, and manifest-resume tests only.
    exec timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m faults --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    ;;
  telemetry)
    # Observability smoke: telemetry-off seed parity (treedef +
    # program count), device-counter oracle checks, span/Chrome-trace
    # export, the driver --telemetry acceptance run.
    exec timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m telemetry --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    ;;
  analysis)
    # Run-analysis smoke: skew/balanced diagnosis, baseline
    # round-trip + drift detection, CLI exit codes, bench proxy —
    # plus the explain suite and an end-to-end --explain smoke whose
    # padded-mode wire-byte prediction is gated EXACTLY against the
    # measured device counters (docs/OBSERVABILITY.md "Explain &
    # cost model").
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m 'analysis or explain' \
      --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_explain.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 \
      --build-table-nrows 8000 --probe-table-nrows 8000 \
      --iterations 1 --out-capacity-factor 3.0 \
      --telemetry "$tmp/tel" --explain \
      --json-output "$tmp/record.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tel/explain.json"
    # The hard gate: padded-mode predicted wire bytes must EXACTLY
    # equal the measured Metrics counters (exit 2 on any drift).
    python -m distributed_join_tpu.telemetry.analyze explain \
      "$tmp/tel/explain.json" --record "$tmp/record.json" \
      --gate-wire-bytes
    exit $?
    ;;
  perfgate)
    # The perf gate (docs/OBSERVABILITY.md "Diagnosis & baselines"):
    # one small DETERMINISTIC join on the 8-virtual-device CPU mesh,
    # its counter signature compared exactly against the committed
    # baseline (results/baselines/cpu_mesh_smoke.json — re-baseline
    # intentional changes with `analyze compare ... --write`), plus a
    # shape check of every artifact the run produced. Wall time is
    # never gated here: CPU-mesh timings measure emulation, not perf.
    set -e
    tmp="$(mktemp -d /tmp/djtpu_perfgate.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    # Wire-protocol contract drift gates perf too (a routing or
    # resend-policy change moves counters): the static wire_ops.json
    # check first — pure ast, milliseconds, fails fast
    # (docs/STATIC_ANALYSIS.md "Level 3").
    timeout -k 10 60 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.analysis.lint --contracts-only
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 \
      --build-table-nrows 8000 --probe-table-nrows 8000 \
      --iterations 1 --shuffle ragged --out-capacity-factor 3.0 \
      --telemetry "$tmp/tel" --diagnose --explain --stage-profile 1 \
      --json-output "$tmp/record.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tel/summary.json" "$tmp/tel/diagnosis.json" \
      "$tmp/tel/explain.json" "$tmp/tel/stageprofile.json" \
      "$tmp/tel/trace.rank0.json" "$tmp/tel/events.rank0.jsonl"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/record.json" --baseline cpu_mesh_smoke
    # The service smoke's counter signature is part of the same gate
    # (docs/SERVICE.md): the final micro-batched join's device
    # counters are deterministic on the CPU mesh, and a changed
    # partitioner/wire/batching seam moves them. --smoke-no-wall-gate
    # keeps this lane's "wall time is never gated here" contract —
    # the strict batched-beats-sequential gate lives in the service
    # lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.server --smoke \
      --smoke-no-wall-gate --platform cpu --n-ranks 8 \
      --telemetry "$tmp/svc_tel" \
      --json-output "$tmp/service_smoke.json"
    # no exec: the EXIT trap must still clean $tmp
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/service_smoke.json" --baseline service_smoke
    # The resident A/B sub-record of the same smoke gates its own
    # deterministic counter signature (docs/SERVICE.md "Resident
    # build tables"): register -> probe-only matches, the pandas-
    # oracle match count after 2 LSM delta merges, the generation
    # stamp, and the zero warm-trace count.
    python - "$tmp" <<'PY'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/service_smoke.json"))
json.dump(rec["resident_drill"],
          open(f"{sys.argv[1]}/resident_drill.json", "w"), indent=1)
PY
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/resident_drill.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/resident_drill.json" --baseline resident_smoke
    # The hierarchical shuffle's counter signature is part of the
    # same gate (docs/HIERARCHY.md): the deterministic 2x4 nested-
    # mesh join's per-tier wire bytes (ici/dcn, codec savings) and
    # match count — a changed router, codec, or tier split moves
    # them. The per-tier EXACT gate itself lives in the hier lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 --slices 2 --shuffle hierarchical \
      --build-table-nrows 8000 --probe-table-nrows 8000 \
      --iterations 1 --out-capacity-factor 3.0 \
      --telemetry "$tmp/hier_tel" \
      --json-output "$tmp/hier_record.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/hier_record.json" --baseline hier_smoke
    # The aggregation-pushdown A/B's counter signature is part of the
    # same gate (docs/AGGREGATION.md): a deterministic duplicate-key
    # fan-out join's pushdown counters (wire-column-restricted bytes,
    # matches, agg.groups) — a changed reduction, wire-column
    # resolution, or partials exchange moves them. The strict
    # speedup/oracle gates live in the agg lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 \
      --build-table-nrows 16000 --probe-table-nrows 16000 \
      --duplicate-build-keys --rand-max 1000 \
      --iterations 1 --out-capacity-factor 30 --agg-ab 1 \
      --json-output "$tmp/agg_record.json"
    python - "$tmp" <<'PY'
import json, sys
ab = json.load(open(f"{sys.argv[1]}/agg_record.json"))["agg_ab"]
json.dump(ab, open(f"{sys.argv[1]}/agg_smoke.json", "w"), indent=1)
PY
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/agg_smoke.json" --baseline agg_smoke
    # The segmented-sort A/B's counter signature is part of the same
    # gate (docs/ROOFLINE.md §9): a deterministic segmented join's
    # device counters (fine-bucket wire bytes, segment stamp,
    # matches) — a changed sub-bucket router, fine padding, or
    # batched join seam moves them. The strict oracle/trace gates
    # live in the sortpath lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 \
      --build-table-nrows 8000 --probe-table-nrows 8000 \
      --iterations 1 --out-capacity-factor 3.0 \
      --sort-ab 1 --sort-segments 8 \
      --json-output "$tmp/sort_record.json"
    python - "$tmp" <<'PY'
import json, sys
ab = json.load(open(f"{sys.argv[1]}/sort_record.json"))["sort_ab"]
json.dump(ab, open(f"{sys.argv[1]}/sortpath_smoke.json", "w"),
          indent=1)
PY
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/sortpath_smoke.json" --baseline sortpath_smoke
    # The query-plan smoke's counter signature is part of the same
    # gate (docs/QUERY.md): the canonical Q3 plan compiled as ONE
    # SPMD program, every operator's counters under an op-id prefix
    # — a changed re-shard seam, wire-column restriction, fused-
    # aggregate exchange, or capacity rung in ANY operator moves
    # them. The oracle/trace/wire-exact gates live in the query
    # lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.tpch_join \
      --platform cpu --n-ranks 8 --query q3 --scale-factor 0.01 \
      --iterations 1 --json-output "$tmp/query_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/query_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/query_smoke.json" --baseline query_smoke
    # The fleet smoke's counter signature is part of the same gate
    # (docs/FLEET.md): the scripted-kill protocol's deterministic
    # match + trace counters — a changed router, affinity hash,
    # failover loop, or persist-dir distribution tier moves them.
    # The drain-latency / shed gates live in the fleet lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --smoke \
      --platform cpu --replica-ranks 2 \
      --json-output "$tmp/fleet_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/fleet_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/fleet_smoke.json" --baseline fleet_smoke
    # The tenant smoke's record is schema-gated here (kind
    # fleet_tenant_smoke: quota refusal, priority shed order,
    # warm-verified autoscale spawn — docs/FLEET.md "Multi-tenancy
    # & autoscaling"); its behavior gates live in the fleet lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --tenant-smoke \
      --platform cpu --replica-ranks 2 \
      --json-output "$tmp/tenant_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tenant_smoke.json"
    # The HA smoke's counter signature is part of the same gate
    # (docs/FLEET.md "Replication & HA"): the scripted holder-kill +
    # router-takeover protocol's deterministic match/trace/generation
    # counters — a changed fan-out, fence, manifest replay, or lease
    # protocol moves them. The latency/ordering gates live in the
    # fleet_ha lane.
    timeout -k 10 900 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --ha-smoke \
      --platform cpu --replica-ranks 2 \
      --json-output "$tmp/fleet_ha_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/fleet_ha_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/fleet_ha_smoke.json" --baseline fleet_ha_smoke
    # The tracing smoke's counter signature is part of the same gate
    # (docs/OBSERVABILITY.md "Distributed tracing"): the scripted-
    # kill protocol's deterministic one-trace failover continuity
    # (the failed attempt and the winning retry share ONE trace_id)
    # plus the merged fleet-timeline process census — a changed
    # trace-context mint/attach/adopt seam, flight-ring stamping, or
    # timeline assembler moves them. The hop/critical-path shape
    # gates live in the tracing lane.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --tracing-smoke \
      --platform cpu --replica-ranks 2 \
      --json-output "$tmp/tracing_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tracing_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/tracing_smoke.json" --baseline tracing_smoke
    exit $?
    ;;
  agg)
    # Aggregation pushdown (docs/AGGREGATION.md). 1. the -m agg unit
    # suite (oracle exactness across shuffle modes/ranks/batching,
    # exact wire accounting incl. the partials exchange, refusal
    # contract, overflow ladder, warm serving, corruption chaos
    # slice); 2. a deterministic CPU-mesh driver A/B smoke on the
    # duplicate-key high-fan-out shape — where materialization
    # actually hurts — gating oracle equality on BOTH sides, zero
    # warm pushdown traces, a strict pushdown-beats-materialize wall
    # win, and the agg_smoke counter signature; 3. the tpch driver's
    # Q3/Q10-shaped --agg mode (oracle-graded in-driver — divergence
    # exits nonzero).
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m agg --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_agg.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 \
      --build-table-nrows 16000 --probe-table-nrows 16000 \
      --duplicate-build-keys --rand-max 1000 \
      --iterations 1 --out-capacity-factor 30 --agg-ab 3 \
      --json-output "$tmp/record.json"
    python - "$tmp" <<'PY'
import json, sys
ab = json.load(open(f"{sys.argv[1]}/record.json"))["agg_ab"]
json.dump(ab, open(f"{sys.argv[1]}/agg_smoke.json", "w"), indent=1)
assert ab.get("skipped") is None, ab
assert ab["oracle_equal_pushdown"] and ab["oracle_equal_materialize"], ab
assert ab["warm_pushdown_new_traces"] == 0, ab
assert ab["pushdown_speedup"] and ab["pushdown_speedup"] > 1.0, ab
print(f"agg A/B: pushdown x{ab['pushdown_speedup']:.2f} vs "
      f"materialize+host-group-by, {ab['groups']} groups, "
      f"{ab['matches']} would-be join rows, 0 warm traces")
PY
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/agg_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/agg_smoke.json" --baseline agg_smoke
    # no exec: the EXIT trap must still clean $tmp
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.tpch_join \
      --platform cpu --n-ranks 8 --scale-factor 0.01 --q3-filters \
      --agg --iterations 1 --out-capacity-factor 3.0 \
      --json-output "$tmp/tpch_agg.json"
    python - "$tmp" <<'PY'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/tpch_agg.json"))
agg = rec["aggregate"]
assert rec["agg"] and agg["oracle_equal"], rec
print(f"tpch --agg: {agg['groups']} groups oracle-exact, "
      f"{rec['matches_per_join']} would-be join rows fused away")
PY
    ;;
  query)
    # Multi-operator query plans (docs/QUERY.md). 1. the -m query
    # unit suite (the six-way join-type family vs the pandas oracle
    # incl. empty-build/all-unmatched/dup-heavy-overflow/string-key
    # edges, plan normalization + the refusal matrix, the ONE-
    # program compile lock, digest-keyed warm serving, the service
    # `query` wire op and its counters); 2. a deterministic CPU-mesh
    # Q3 driver smoke: whole-query pandas-oracle equality (the
    # driver itself exits nonzero on divergence), ONE traced
    # program, zero warm traces, the queryplan artifact schema-
    # checked, its per-operator padded wire-byte prediction gated
    # EXACTLY (analyze explain --gate-wire-bytes), and the merged
    # per-operator counter signature gated vs the committed
    # query_smoke baseline. Wall time is never gated on the CPU
    # mesh (emulation, not perf).
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m query --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_query.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.tpch_join \
      --platform cpu --n-ranks 8 --query q3 --scale-factor 0.01 \
      --iterations 1 --explain --telemetry "$tmp/tel" \
      --json-output "$tmp/query_smoke.json"
    python - "$tmp" <<'PY'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/query_smoke.json"))
assert rec["oracle_equal"], rec
assert rec["warm_new_traces"] == 0, rec
assert rec["programs_traced"] == 1, rec
assert rec["retry_attempts"] == 0, rec
assert rec["wire_exact"], rec["wire"]
assert rec["n_operators"] == 3, rec
print(f"query smoke: q3 as ONE program, {rec['groups']} groups "
      f"oracle-exact, 0 warm traces, wire bytes exact over "
      f"{len(rec['wire'])} operators")
PY
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/query_smoke.json" "$tmp/tel/explain.json"
    python -m distributed_join_tpu.telemetry.analyze explain \
      "$tmp/tel/explain.json" --record "$tmp/query_smoke.json" \
      --gate-wire-bytes
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/query_smoke.json" --baseline query_smoke
    ;;
  sortpath)
    # Segmented-sort join pipeline (docs/ROOFLINE.md §9). 1. the
    # -m sortpath unit suite (segmented-vs-flat-vs-oracle multiset
    # exactness across shuffle modes/k/skew/string keys, segment
    # edge cases, refusal contract, plan==program digest + wire
    # exactness, the 2^24 kernel-path guard, expand window
    # decoupling, chunked fallback gather, tuner policy); 2. a
    # deterministic CPU-mesh driver smoke: the SEGMENTED program is
    # the timed mode, its padded wire-byte prediction gated EXACTLY
    # (analyze explain --gate-wire-bytes), and the --sort-ab record
    # must be oracle-clean on both modes, multiset-equal, zero warm
    # traces, wire-exact — its counter signature is the
    # sortpath_smoke baseline the perfgate lane also gates. Wall
    # time is never gated on the CPU mesh (emulation, not perf;
    # the segmented-vs-flat chip number is not measured yet).
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m sortpath --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_sortpath.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 \
      --build-table-nrows 8000 --probe-table-nrows 8000 \
      --iterations 1 --out-capacity-factor 3.0 \
      --sort-mode segmented --sort-segments 8 \
      --telemetry "$tmp/tel" --explain --sort-ab 2 \
      --json-output "$tmp/record.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tel/explain.json"
    # The hard gate: the SEGMENTED program's predicted wire bytes
    # must EXACTLY equal the measured device counters.
    python -m distributed_join_tpu.telemetry.analyze explain \
      "$tmp/tel/explain.json" --record "$tmp/record.json" \
      --gate-wire-bytes
    python - "$tmp" <<'PY'
import json, sys
ab = json.load(open(f"{sys.argv[1]}/record.json"))["sort_ab"]
json.dump(ab, open(f"{sys.argv[1]}/sortpath_smoke.json", "w"),
          indent=1)
assert ab.get("skipped") is None, ab
assert ab["oracle_equal_flat"] and ab["oracle_equal_segmented"], ab
assert ab["multiset_equal"], ab
assert ab["warm_new_traces"] == 0, ab
assert ab["wire_exact"], ab
print(f"sort A/B: {ab['sort_segments']} segments, "
      f"{ab['matches']} matches, oracle-exact both modes, "
      f"0 warm traces, wire exact "
      f"(segmented x{ab['segmented_speedup']:.2f} on the CPU mesh — "
      "not a perf gate)")
PY
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/sortpath_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/sortpath_smoke.json" --baseline sortpath_smoke
    exit $?
    ;;
  lint)
    # Static analysis (docs/STATIC_ANALYSIS.md), all three checkers:
    # level-1 AST rules DJL001-010 (SPMD hazards + concurrency lint)
    # over the production tree (exit nonzero on any finding not in
    # the committed suppressions), level-3 wire-protocol contract
    # check against results/contracts/wire_ops.json (op-table
    # cross-checks, Prometheus/doc gauge parity, artifact-kind
    # registry; re-baseline with `analysis.lint --update-contracts`),
    # and level-2 jaxpr collective-schedule check of all 14 program
    # families against results/schedules/ (re-baseline intentional
    # schedule changes with `analysis.lint --update-schedules`).
    # DJTPU_VALIDATE_PLANS is cleared: the gate checks the SHIPPING
    # trace, and the debug seam's callback would (correctly) fail the
    # telemetry-off no-callback invariant.
    exec timeout -k 10 600 env -u DJTPU_VALIDATE_PLANS \
      JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.analysis.lint
    ;;
  chaos)
    # Chaos smoke (docs/FAILURE_SEMANTICS.md "Integrity contract"):
    # the -m chaos unit suite, then a fixed-seed 20-trial soak on the
    # 8-virtual-device CPU mesh — randomized fault schedules
    # (including every corruption mode) x join configs, every trial
    # graded against the pandas oracle with wire digests on. Exit 1 =
    # a trial returned wrong rows silently or hung (minimal-repro
    # JSON written under /tmp); replay one trial with
    # `python -m distributed_join_tpu.parallel.chaos --seed 42
    # --trial K`.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m chaos --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    exec timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.parallel.chaos \
      --trials 20 --seed 42 --repro-out /tmp/djtpu_chaos_repro.json
    ;;
  service)
    # Join-as-a-service (docs/SERVICE.md): the -m service unit suite
    # (cache-key discipline, warm-path program-count locks, retry-rung
    # reuse, batching isolation, daemon protocol, live observability),
    # then the daemon smoke through the real TCP loop — a warm second
    # query must add zero traces, a 16-way micro-batch must beat 16
    # sequential warm calls on wall clock, the `metrics` op must
    # return non-degenerate latency quantiles over the warm traffic,
    # and the poison drill must dump a schema-valid flight recorder.
    # The observability artifacts (flightrecorder.json + the workload
    # history store) are schema-checked and the history store must
    # summarize >= 2 distinct workload signatures (ISSUE 7 acceptance).
    # The smoke's record carries the counter signature the perfgate
    # lane gates against results/baselines/service_smoke.json.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m service --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_service.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.server --smoke \
      --platform cpu --n-ranks 8 \
      --history-dir "$tmp/history" \
      --flight-recorder-path "$tmp/flightrecorder.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/flightrecorder.json" "$tmp/history/history.jsonl"
    python -m distributed_join_tpu.telemetry.analyze history \
      "$tmp/history"
    python -m distributed_join_tpu.telemetry.analyze history \
      "$tmp/history" --json | python -c '
import json, sys
s = json.load(sys.stdin)
assert s["n_signatures"] >= 2, s
print("history store:", s["n_entries"], "entries,",
      s["n_signatures"], "signatures")'
    exit $?
    ;;
  stageprof)
    # Stage-segmented profiling (docs/OBSERVABILITY.md "Stage
    # profiling"): the -m stageprof unit suite, then a deterministic
    # CPU-mesh driver run with --stage-profile. The artifact is
    # schema-checked, `analyze stages` must render it, the padded
    # per-stage wire bytes must EXACTLY equal the monolithic Metrics
    # counters, the stage set must match cost.predict's keys 1:1, and
    # the segmented sum must dominate the monolithic wall on the
    # noise-robust minimum walls.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m stageprof --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_stageprof.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 \
      --build-table-nrows 8000 --probe-table-nrows 8000 \
      --iterations 1 --out-capacity-factor 3.0 \
      --telemetry "$tmp/tel" --stage-profile 3 \
      --json-output "$tmp/record.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tel/stageprofile.json"
    python -m distributed_join_tpu.telemetry.analyze stages \
      "$tmp/tel/stageprofile.json"
    python - "$tmp" <<'PY'
import json, sys
tmp = sys.argv[1]
prof = json.load(open(f"{tmp}/tel/stageprofile.json"))
rec = json.load(open(f"{tmp}/record.json"))
red = rec["telemetry"]["metrics"]["reduced"]
sh = prof["stages"]["shuffle"]["counters"]
for side in ("build", "probe"):
    assert sh[f"{side}.wire_bytes"] == red[f"{side}.wire_bytes"], \
        (side, sh, red)
assert set(prof["stages"]) == {"partition", "shuffle", "join", "skew"}
assert prof["stages"]["join"]["counters"]["matches"] == red["matches"]
# 5% noise allowance: on the emulated mesh the two mins are a
# near-tie and scheduler jitter can flip the sign of a sub-ms gap
# (same allowance as tests/test_stageprof.py's min-wall gate).
assert prof["sum_of_stages_min_s"] >= \
    0.95 * prof["monolithic"]["wall_min_s"], \
    (prof["sum_of_stages_min_s"], prof["monolithic"])
print("stageprof gate: per-stage wire bytes exact, stage set matches "
      "cost.predict,",
      f"overlap credit {prof['overlap']['credit_s']:.4f}s "
      f"({prof['overlap']['fraction']})")
PY
    exit $?
    ;;
  hier)
    # Hierarchical two-level ICI/DCN shuffle (docs/HIERARCHY.md).
    # 1. the -m hier unit suite (oracle exactness incl. skew/string
    #    keys, per-tier wire exactness, degenerate-hierarchy lowering
    #    locks, DCN-seam chaos, probe-only integrity rungs);
    # 2. a deterministic nested-mesh (2x4) driver smoke: the per-tier
    #    wire-byte split must EXACTLY match the device counters
    #    (analyze explain --gate-wire-bytes now gates each tier), and
    #    the counter signature — matches included, i.e. the join's
    #    answer — is gated against results/baselines/hier_smoke.json;
    # 3. a 6-trial fixed-seed hierarchical chaos slice (cross-slice
    #    corruption seam included) must survive clean.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m hier --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_hier.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.benchmarks.distributed_join \
      --platform cpu --n-ranks 8 --slices 2 --shuffle hierarchical \
      --build-table-nrows 8000 --probe-table-nrows 8000 \
      --iterations 1 --out-capacity-factor 3.0 \
      --telemetry "$tmp/tel" --explain \
      --json-output "$tmp/record.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tel/explain.json"
    python -m distributed_join_tpu.telemetry.analyze explain \
      "$tmp/tel/explain.json" --record "$tmp/record.json" \
      --gate-wire-bytes
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/record.json" --baseline hier_smoke
    # no exec: the EXIT trap must still clean $tmp
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.parallel.chaos \
      --hier-slice 6 --seed 42 \
      --repro-out /tmp/djtpu_hier_chaos_repro
    ;;
  fleet)
    # Fault-tolerant serving fleet (docs/FLEET.md). 1. the -m fleet
    # unit suite (signature-affinity routing == the replica-side
    # digest, replica state machine over fake wire replicas, the
    # kill/hang/corrupt failure matrix over disjoint-device
    # in-process replicas, structured shedding, duplicate-id fence);
    # 2. the subprocess fleet smoke: 2 tpu-join-service replicas
    # sharing one persist dir behind the router, one SCRIPTED SIGKILL
    # mid-traffic — failover answers pandas-oracle-exact within the
    # bounded retry budget, the killed replica is drained within one
    # probe interval and replaced, the replacement serves the repeat
    # signature with ZERO new traces, and a synthetic-overload burst
    # sheds with structured errors; its counter signature is gated
    # against results/baselines/fleet_smoke.json; the router-side
    # history store (replica-stamped) is schema-checked; 3. the
    # chaos --fleet soak: >= 20 trials, one replica faulted mid-soak.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m fleet --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_fleet.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --smoke \
      --platform cpu --replica-ranks 2 \
      --history-dir "$tmp/history" \
      --json-output "$tmp/fleet_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/fleet_smoke.json" "$tmp/history/history.jsonl"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/fleet_smoke.json" --baseline fleet_smoke
    # The acceptance soak (>= 20 trials, fixed seed): one replica
    # killed/hung/corrupted mid-soak, every non-refused answer
    # graded against the pandas oracle, drain+replace and the
    # zero-trace warm replacement gated inside the harness.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.parallel.chaos \
      --fleet 20 --seed 42 \
      --json-output "$tmp/fleet_soak.json" \
      --repro-out /tmp/djtpu_fleet_repro
    # no exec: the EXIT trap must still clean $tmp
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/fleet_soak.json"
    # 4. the two-tenant smoke (docs/FLEET.md "Multi-tenancy &
    # autoscaling"): a noisy low-priority tenant is quota-refused
    # (QuotaExceededError naming the bound) and priority-shed
    # (ShedError) under the SAME pressure the quiet tenant rides
    # served and oracle-exact, and the signature-level autoscaler
    # spawns a replica that serves the hot signature WARM (zero new
    # traces) before entering rotation.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --tenant-smoke \
      --platform cpu --replica-ranks 2 \
      --history-dir "$tmp/tenant_history" \
      --json-output "$tmp/tenant_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tenant_smoke.json" "$tmp/tenant_history/history.jsonl"
    # 5. the multi-tenant chaos soak: the noisy tenant floods at 5x
    # its quota while the quiet tenant's oracle-graded joins run,
    # one replica SIGKILLed mid-soak — quiet answers exact with
    # ZERO sheds, the noisy tenant is the one refused, history
    # entries and trend keys stay tenant-namespaced, and the
    # replacement serves the quiet tenant's signature warm.
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.parallel.chaos \
      --tenants 4 --seed 42 \
      --json-output "$tmp/tenant_soak.json" \
      --repro-out /tmp/djtpu_tenant_repro
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tenant_soak.json"
    ;;
  fleet_ha)
    # Durable replicated resident state + router HA (docs/FLEET.md
    # "Replication & HA"). 1. tests/test_fleet_ha.py: manifest +
    # directory artifact schemas, generation fencing (a FaultPlan-
    # dropped append fences EXACTLY the holder that missed it —
    # StaleGenerationError on fenced work, honest old-generation
    # serving without the fence), structured NoHolderError refusal,
    # rebuild-from-manifest to the acked generation, lease fencing
    # (live lease not stealable, expired lease stolen, fenced-out
    # renew refused), router takeover (standby adopts the directory,
    # request-id-fenced resend — no loss, no double-execution).
    # 2. the --ha-smoke subprocess protocol: K=2 replicated register
    # -> manifest/directory on disk -> warm zero-trace serving ->
    # holder SIGKILL -> failover within the bounded budget -> the
    # replacement rebuilds from the manifest and answers the FENCED
    # replay with zero new traces -> primary router crash -> standby
    # takeover -> the client's resend answers identically with zero
    # new traces; counter signature gated vs results/baselines/
    # fleet_ha_smoke.json; the manifest and router-directory
    # artifacts are schema-checked. 3. the chaos resident-kill soak:
    # the table's PRIMARY HOLDER killed mid-soak — zero wrong rows,
    # failover within budget, rebuild + fenced zero-trace replay
    # gated inside the harness.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/test_fleet_ha.py -q --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_fleet_ha.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 900 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --ha-smoke \
      --platform cpu --replica-ranks 2 \
      --persist-dir "$tmp/ha" \
      --json-output "$tmp/fleet_ha_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/fleet_ha_smoke.json" \
      "$tmp"/ha/coord/tables/*.manifest.json \
      "$tmp/ha/coord/router_directory.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/fleet_ha_smoke.json" --baseline fleet_ha_smoke
    timeout -k 10 900 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.parallel.chaos \
      --fleet 10 --fleet-fault resident-kill --seed 42 \
      --json-output "$tmp/fleet_ha_soak.json" \
      --repro-out /tmp/djtpu_fleet_ha_repro
    # no exec: the EXIT trap must still clean $tmp
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/fleet_ha_soak.json"
    ;;
  tracing)
    # Fleet-wide distributed tracing (docs/OBSERVABILITY.md
    # "Distributed tracing"). 1. tests/test_tracing.py: trace-context
    # minting/capping, wire attach (copy semantics) + receiver-side
    # adoption (child_of_wire), sink event stamping, request_scope
    # save/restore, fleet timeline assembly on synthetic per-process
    # streams (clock anchoring, cross-process hops, critical path,
    # Perfetto export), and tracing-off parity (no trace fields, no
    # extra events). 2. the --tracing-smoke subprocess protocol: 2
    # replicas each with its OWN telemetry session dir, cold/warm
    # serving under client-minted trace contexts, then one scripted
    # SIGKILL of the affine replica — the router's failed dispatch
    # attempt and the winning failover retry must share ONE trace_id
    # in the flight ring AND in the merged timeline; the three
    # per-process JSONL streams assemble into ONE Perfetto fleet
    # timeline whose focus trace spans both surviving processes with
    # >= 1 cross-process hop and a non-empty critical path; both
    # timeline artifacts are schema-checked and the counter
    # signature is gated vs results/baselines/tracing_smoke.json.
    # 3. `analyze timeline` renders the merged causal report from
    # the smoke's kept session dirs.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/test_tracing.py -q --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_tracing.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.fleet --tracing-smoke \
      --platform cpu --replica-ranks 2 \
      --persist-dir "$tmp/work" \
      --json-output "$tmp/tracing_smoke.json"
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/tracing_smoke.json" \
      "$tmp/work/telemetry/fleet_timeline.json"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/tracing_smoke.json" --baseline tracing_smoke
    python -m distributed_join_tpu.telemetry.analyze timeline \
      "$tmp/work/telemetry/router" \
      "$tmp/work/telemetry/replica0" \
      "$tmp/work/telemetry/replica1" \
      --out "$tmp/timeline"
    ;;
  tuner)
    # History-driven autotuner (docs/OBSERVABILITY.md "Autotuner").
    # 1. the -m tuner unit suite (zero-trace warm locks via
    #    CountingComm, poisoned-history chaos slice, compaction,
    #    calibration, CLI schema);
    # 2. driver cold/warm A/B on an overflow-prone workload: the cold
    #    run pays the ladder and records the rung, the warm tuned
    #    re-run must dispatch with ZERO ladder escalations;
    # 3. a service-level warm gate: the tuned second request must add
    #    zero new traces AND zero escalations (CountingComm-locked);
    # 4. `analyze tune --json` output schema-checked.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m tuner --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_tuner.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    for phase in cold warm; do
      timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m distributed_join_tpu.benchmarks.distributed_join \
        --platform cpu --n-ranks 8 \
        --build-table-nrows 8000 --probe-table-nrows 8000 \
        --iterations 1 --out-capacity-factor 0.1 --auto-retry 6 \
        --auto-tune --history "$tmp/history.jsonl" \
        --telemetry "$tmp/tel_$phase" \
        --json-output "$tmp/$phase.json"
    done
    python - "$tmp" <<'PY'
import json, sys
tmp = sys.argv[1]
cold = json.load(open(f"{tmp}/cold.json"))
warm = json.load(open(f"{tmp}/warm.json"))
def escalations(rec):
    return sum(1 for a in (rec.get("retry") or {}).get("attempts", [])
               if a.get("overflow"))
assert escalations(cold) >= 1, "cold run never escalated: the A/B tested nothing"
assert escalations(warm) == 0, f"warm tuned run escalated: {warm.get('retry')}"
assert warm["tuned"]["source"] == "history", warm["tuned"]
assert warm["tuned"]["rung"] >= 1, warm["tuned"]
print(f"tuner A/B: cold {escalations(cold)} escalation(s) -> warm 0 "
      f"(pre-sized at rung {warm['tuned']['rung']})")
PY
    # Service-level zero-trace warm gate: the tuned repeat must be a
    # pure dict-lookup dispatch (no new SPMD programs built at all).
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python - "$tmp" <<'PY'
import sys
from distributed_join_tpu.benchmarks import force_cpu_platform
force_cpu_platform(8)
from distributed_join_tpu.parallel.communicator import TpuCommunicator
from distributed_join_tpu.service.server import JoinService, ServiceConfig
from distributed_join_tpu.utils.generators import generate_build_probe_tables

class CountingComm(TpuCommunicator):
    def __init__(self):
        super().__init__(n_ranks=8)
        self.programs_built = 0
    def spmd(self, fn, *, sharded_out=None):
        self.programs_built += 1
        return super().spmd(fn, sharded_out=sharded_out)

comm = CountingComm()
svc = JoinService(comm, ServiceConfig(
    auto_retry=6, auto_tune=True, history_dir=sys.argv[1] + "/svc_hist"))
b, p = generate_build_probe_tables(
    seed=11, build_nrows=512, probe_nrows=1024, rand_max=256,
    selectivity=0.5)
r1 = svc.join(b, p, out_capacity_factor=0.1)
assert r1.retry_report.n_attempts > 1, "cold service run never escalated"
built = comm.programs_built
r2 = svc.join(b, p, out_capacity_factor=0.1)
assert r2.new_traces == 0 and comm.programs_built == built, \
    f"warm tuned request traced: {r2.new_traces}"
assert r2.retry_report.n_attempts == 1, "warm tuned request escalated"
assert int(r1.total) == int(r2.total)
print(f"service warm gate: cold {r1.retry_report.n_attempts} attempt(s) "
      f"-> warm 1 attempt, 0 new traces")
PY
    # analyze tune: dry-run the tuner over the A/B history; the JSON
    # output must carry the documented schema.
    python -m distributed_join_tpu.telemetry.analyze tune \
      "$tmp/history.jsonl"
    python -m distributed_join_tpu.telemetry.analyze tune \
      "$tmp/history.jsonl" --json | python -c '
import json, sys
doc = json.load(sys.stdin)
assert doc["kind"] == "tune" and doc["schema_version"] == 1, doc
assert doc["n_signatures"] >= 1, doc
sig = next(iter(doc["signatures"].values()))
for key in ("source", "rung", "knobs", "delta", "basis"):
    assert key in sig, (key, sig)
assert sig["source"] == "history" and sig["delta"], sig
print("analyze tune schema: OK,", doc["n_signatures"], "signature(s)")'
    exit $?
    ;;
  resident)
    # Resident build tables (docs/SERVICE.md "Resident build
    # tables"). 1. the -m resident unit suite (probe-only oracle
    # correctness, LSM merges, generation eviction, conservation-
    # check chaos slice, wire ops); 2. the daemon smoke WITH the
    # strict wall gate — the warm probe-only join must beat the warm
    # cold full join on the min wall and add zero traces; 3. the
    # resident drill sub-record is schema-checked and its
    # deterministic counter signature gated against
    # results/baselines/resident_smoke.json; history entries must
    # carry validated resident stamps.
    set -e
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/ -q -m resident --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
    tmp="$(mktemp -d /tmp/djtpu_resident.XXXXXX)"
    trap 'rm -rf "$tmp"' EXIT
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python -m distributed_join_tpu.service.server --smoke \
      --platform cpu --n-ranks 8 \
      --history-dir "$tmp/history" \
      --flight-recorder-path "$tmp/flightrecorder.json" \
      --json-output "$tmp/smoke.json"
    python - "$tmp" <<'PY'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/smoke.json"))
drill = rec["resident_drill"]
json.dump(drill, open(f"{sys.argv[1]}/resident_drill.json", "w"),
          indent=1)
assert drill["probe_only_speedup"] and drill["probe_only_speedup"] > 1
assert drill["counter_signature"]["counters"][
    "warm_probe_new_traces"] == 0
print(f"resident drill: probe-only x{drill['probe_only_speedup']:.2f}"
      f" vs cold, generation {drill['resident']['generation']}, "
      f"{drill['resident']['merges']} LSM merge(s), 0 warm traces")
PY
    python -m distributed_join_tpu.telemetry.analyze check \
      "$tmp/resident_drill.json" "$tmp/history/history.jsonl"
    python -m distributed_join_tpu.telemetry.analyze compare \
      "$tmp/resident_drill.json" --baseline resident_smoke
    exit $?
    ;;
  *)
    echo "usage: $0 [tier1|faults|telemetry|analysis|perfgate|lint|chaos|service|stageprof|tuner|resident|hier|agg|sortpath|fleet|fleet_ha]" >&2
    exit 2
    ;;
esac
