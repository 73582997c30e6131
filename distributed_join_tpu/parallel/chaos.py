"""Seeded chaos-soak harness: randomized fault schedules x join
configs, every trial verified against the host pandas oracle.

``python -m distributed_join_tpu.parallel.chaos --trials 25 --seed 42``
runs 25 deterministic trials on the 8-virtual-device CPU mesh. Each
trial derives its OWN rng from ``(seed, trial_index)``, draws a join
config (padded / ragged / skew / out-of-core) and a fault schedule
(nothing, capacity squeezes, transient dispatch failures, or one of
the :data:`..faults.CORRUPTION_MODES` data corruptions), runs the join
with ``verify_integrity=True``, and grades the outcome against ground
truth computed with pandas on the host:

- ``ok`` / ``recovered`` — the result is oracle-exact (full content
  comparison via the order-invariant table digest, not just the match
  count — a flipped payload byte with an intact key count would fool
  a count oracle); ``recovered`` means the retry ladder worked for it;
- ``detected`` — the run refused to return corrupt rows: a structured
  ``IntegrityError`` / ``PlanValidationError`` / ``FaultInjectedError``
  surfaced. For a corrupting schedule this is a PASS — the acceptance
  bar is "injected corruption is detected and survived, never silently
  joined";
- ``FAILED:silent_corruption`` — a trial RETURNED rows that disagree
  with the oracle: the one unforgivable outcome;
- ``FAILED:hang`` — the trial blew its watchdog deadline
  (:mod:`..watchdog`); ``FAILED:crash`` — an unstructured error, or
  any error on a fault-free trial.

Every failure writes a minimal-repro JSON (``--repro-out``) holding
the harness seed, the trial index, the exact config + fault plan, and
the replay command — ``--trial K`` reruns exactly trial K of a seed,
bit-for-bit (generators, schedule draws, and fault addressing are all
keyed on the trial rng).

The CI smoke lane (``scripts/run_tier1.sh chaos``) runs a fixed-seed
~20-trial soak; exit code 0 = every trial survived, 1 = at least one
failure (repro files written), 2 = bad usage.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import sys
import time
from typing import Optional

from distributed_join_tpu.parallel.faults import (
    CORRUPTION_MODES,
    FaultInjectedError,
    FaultInjectingCommunicator,
    FaultPlan,
    PlanValidationError,
    retry_with_backoff,
)

CONFIGS = ("padded", "ragged", "skew", "out_of_core")

# Discrete, small parameter sets: trials stay fast and programs repeat
# across trials, so the persistent XLA cache absorbs most compiles.
# rand_max stays >= 256 so key duplication can't organically overflow
# a 3x-sized output block — organic overflow on a fault-free trial
# would read as a phantom harness failure (the retry budget still
# absorbs moderate skew).
_BUILD_ROWS = (512, 1024)
_PROBE_ROWS = (1024, 2048)
_RAND_MAX = (256, 700, 5000)
_SELECTIVITY = (0.3, 0.5)


def random_fault_plan(rng: random.Random, *, corruption: bool = True,
                      dispatch_failures: bool = True) -> FaultPlan:
    """One seeded fault schedule. ``corruption=False`` restricts to
    the recoverable faults (squeezes/transients) — the knob behind the
    soak's ``--no-corruption`` control arm and the drivers'
    ``--chaos-seed`` smoke wrap."""
    kinds = ["none", "overflow"]
    if dispatch_failures:
        kinds.append("transient_dispatch")
    if corruption:
        kinds += ["corruption", "corruption"]  # corruption-heavy soak
    kind = rng.choice(kinds)
    seed = rng.randrange(1 << 16)
    if kind == "overflow":
        return FaultPlan(seed=seed,
                         overflow_programs=rng.choice((1, 2)))
    if kind == "transient_dispatch":
        return FaultPlan(seed=seed, fail_dispatches=1)
    if kind == "corruption":
        return FaultPlan(
            seed=seed,
            corrupt_mode=rng.choice(CORRUPTION_MODES),
            corrupt_collectives=rng.choice((1, 2)),
        )
    return FaultPlan(seed=seed)


def _trial_rng(seed: int, trial: int) -> random.Random:
    """Deterministic ACROSS processes: integer-mixed seeding (tuple/
    str seeds route through PYTHONHASHSEED-randomized hashing)."""
    return random.Random(seed * 1_000_003 + trial)


def fault_label(plan: FaultPlan) -> str:
    if plan.corrupt_mode is not None:
        return plan.corrupt_mode
    if plan.overflow_programs:
        return "overflow"
    if plan.fail_dispatches or plan.fail_after_dispatches is not None:
        return "transient_dispatch"
    return "none"


def wrap_communicator(comm, seed: int,
                      plan: Optional[FaultPlan] = None):
    """Driver seam (``--chaos-seed N``): wrap a communicator in a
    seeded fault schedule. Corruption modes are INCLUDED — pair with
    ``--verify-integrity`` (the drivers do) so a corrupted run is
    detected, not reported as a clean benchmark number."""
    if plan is None:
        plan = random_fault_plan(_trial_rng(seed, 0),
                                 dispatch_failures=False)
    return FaultInjectingCommunicator(comm, plan)


def _plan_record(plan: FaultPlan) -> dict:
    return {k: v for k, v in dataclasses.asdict(plan).items()
            if v not in (None, 0, 0.0)}


def _oracle_frame(build, probe):
    """Ground truth on the host: the merged pandas frame (the
    reference implementation this repo reproduces is, at trial scale,
    exactly a pandas inner join)."""
    return build.to_pandas().merge(probe.to_pandas(), on="key")


def _content_digest(columns: dict) -> int:
    from distributed_join_tpu.parallel.integrity import table_digest_np

    return table_digest_np(columns)


def _result_columns(res_table) -> dict:
    """Valid rows of a (possibly device) result table as host numpy."""
    import numpy as np

    valid = np.asarray(res_table.valid)
    return {n: np.asarray(c)[valid]
            for n, c in res_table.columns.items()}


def _frame_columns(frame, names) -> dict:
    import numpy as np

    return {n: np.asarray(frame[n].to_numpy()) for n in names}


@dataclasses.dataclass
class TrialOutcome:
    verdict: str
    error: Optional[str] = None
    expected_total: Optional[int] = None
    got_total: Optional[int] = None
    retries: int = 0

    @property
    def failed(self) -> bool:
        return self.verdict.startswith("FAILED")


def _grade_result(got_cols, got_total, oracle_cols, oracle_total,
                  corrupting: bool, retries: int) -> TrialOutcome:
    content_ok = (
        got_total == oracle_total
        and _content_digest(got_cols) == _content_digest(oracle_cols)
    )
    if content_ok:
        return TrialOutcome("recovered" if retries else "ok",
                            expected_total=oracle_total,
                            got_total=got_total, retries=retries)
    return TrialOutcome(
        "FAILED:silent_corruption" if corrupting
        else "FAILED:wrong_result",
        expected_total=oracle_total, got_total=got_total,
        retries=retries,
    )


def run_trial(harness_seed: int, trial: int, n_ranks: int = 8,
              corruption: bool = True,
              deadline_s: Optional[float] = 300.0) -> dict:
    """Run one trial; returns its JSON-shaped record. Deterministic in
    (harness_seed, trial): the config draw, the generators, and the
    fault schedule all derive from the trial rng."""
    from distributed_join_tpu.parallel.watchdog import (
        HangError,
        call_with_deadline,
    )

    rng = _trial_rng(harness_seed, trial)
    config = {
        "mode": CONFIGS[trial % len(CONFIGS)],
        "build_rows": rng.choice(_BUILD_ROWS),
        "probe_rows": rng.choice(_PROBE_ROWS),
        "rand_max": rng.choice(_RAND_MAX),
        "selectivity": rng.choice(_SELECTIVITY),
        "table_seed": rng.randrange(1 << 16),
    }
    plan = random_fault_plan(rng, corruption=corruption)
    # Corrupting schedules sometimes run with NO retry budget — the
    # IntegrityError raise path must soak too. Every other schedule
    # keeps budget to absorb injected squeezes + moderate skew.
    config["auto_retry"] = (
        rng.choice((0, 2)) if plan.corrupt_mode is not None else 3
    )
    record = {
        "trial": trial,
        "config": config,
        "fault": fault_label(plan),
        "fault_plan": _plan_record(plan),
    }
    t0 = time.perf_counter()
    try:
        if deadline_s is not None:
            out = call_with_deadline(
                lambda: _run_trial_body(config, plan, n_ranks),
                deadline_s, what=f"chaos trial {trial}",
            )
        else:
            out = _run_trial_body(config, plan, n_ranks)
    except HangError as exc:
        out = TrialOutcome("FAILED:hang", error=str(exc))
    except Exception as exc:  # noqa: BLE001 — grading seam
        # Anything the trial body did not convert to a structured
        # refusal is a crash VERDICT, not a soak abort: the trial is
        # graded FAILED, its repro JSON is written, and the remaining
        # trials still run.
        out = TrialOutcome(
            "FAILED:crash", error=f"{type(exc).__name__}: {exc}")
    record.update(dataclasses.asdict(out))
    record["verdict"] = out.verdict
    record["elapsed_s"] = round(time.perf_counter() - t0, 3)
    from distributed_join_tpu import telemetry

    telemetry.event("chaos_trial", trial=trial,
                    verdict=out.verdict, mode=config["mode"])
    return record


def _run_trial_body(config, plan: FaultPlan, n_ranks: int
                    ) -> TrialOutcome:
    import distributed_join_tpu as dj
    from distributed_join_tpu.parallel import integrity
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    build, probe = generate_build_probe_tables(
        seed=config["table_seed"],
        build_nrows=config["build_rows"],
        probe_nrows=config["probe_rows"],
        rand_max=config["rand_max"],
        selectivity=config["selectivity"],
    )
    oracle = _oracle_frame(build, probe)
    oracle_total = len(oracle)
    out_names = ["key", "build_payload", "probe_payload"]
    oracle_cols = _frame_columns(oracle, out_names)
    corrupting = plan.corrupt_mode is not None

    comm = FaultInjectingCommunicator(
        dj.make_communicator("tpu", n_ranks=n_ranks), plan)
    mode = config["mode"]
    injected = fault_label(plan) != "none"

    def loud(kind: str, detail: Optional[str] = None) -> TrialOutcome:
        # A structured refusal (IntegrityError, a still-flagged
        # overflow, a surfaced injected fault) PASSES a trial whose
        # schedule injected something — corruption detected, never
        # silently joined. On a fault-free trial the same outcome is
        # a harness catch: a false alarm or an organic failure.
        return TrialOutcome(
            "detected" if injected else f"FAILED:{kind}",
            error=detail or kind, expected_total=oracle_total)

    try:
        if mode == "out_of_core":
            return _run_out_of_core(build, probe, comm, oracle_cols,
                                    oracle_total, corrupting, loud,
                                    plan)
        join_opts = dict(
            out_capacity_factor=3.0,
            shuffle_capacity_factor=3.0,
            shuffle="ragged" if mode == "ragged" else "padded",
        )
        if mode == "skew":
            join_opts["skew_threshold"] = 0.05

        def attempt():
            return dj.distributed_inner_join(
                build, probe, comm,
                auto_retry=config["auto_retry"],
                verify_integrity=True, **join_opts,
            )

        # Driver-level transient retry (the drivers' own contract):
        # injected dispatch failures are retried a couple of times
        # before counting as a loud structured failure.
        res, _ = retry_with_backoff(
            attempt, max_attempts=3, backoff_s=0.01,
            retry_on=(FaultInjectedError,),
        )
        retries = res.retry_report.n_attempts - 1
        if bool(res.overflow):
            return loud("overflow_after_ladder")
        return _grade_result(
            _result_columns(res.table), int(res.total),
            oracle_cols, oracle_total, corrupting, retries,
        )
    except integrity.IntegrityError as exc:
        return loud("false_integrity_alarm", f"IntegrityError: {exc}")
    except (PlanValidationError, FaultInjectedError) as exc:
        return loud("structured_error",
                    f"{type(exc).__name__}: {exc}")


def _run_out_of_core(build, probe, comm, oracle_cols, oracle_total,
                     corrupting: bool, loud,
                     plan: FaultPlan) -> TrialOutcome:
    import numpy as np

    from distributed_join_tpu.parallel.out_of_core import (
        keyrange_batched_join,
    )

    fetched = []

    def consumer(_b, res):
        fetched.append(_result_columns(res.table))

    total, overflow = keyrange_batched_join(
        build, probe, comm, n_batches=3, warmup=False,
        batch_retries=2, batch_retry_backoff_s=0.01,
        verify_integrity=True, on_batch_result=consumer,
        out_capacity_factor=3.0, shuffle_capacity_factor=3.0,
    )
    if overflow:
        return loud("overflow_flagged")
    got = {
        n: np.concatenate([c[n] for c in fetched])
        for n in (fetched[0] if fetched else {})
    }
    # A clean finish over an injected transient necessarily consumed a
    # batch retry — grade it "recovered" so the verdict histogram
    # reflects the retry machinery (the batch loop doesn't surface
    # attempt counts).
    retries = 1 if plan.fail_dispatches else 0
    return _grade_result(got, int(total), oracle_cols, oracle_total,
                         corrupting, retries=retries)


# -- the tuner slice (poisoned-history grading) -----------------------


def poisoned_history_entry(signature: str, *,
                           shuffle_capacity_factor: float = 0.4,
                           out_capacity_factor: float = 0.2,
                           rung: int = 1) -> dict:
    """A history line CLAIMING a workload resolved at a rung whose
    capacities are far too small — the lying-history adversary the
    tuner slice feeds the autotuner. Shaped like a real request entry
    (escalations recorded, resolved knobs at the bogus sizing) so the
    trend aggregation adopts it exactly as it would a genuine one."""
    return {
        "schema_version": 1,
        "kind": "request",
        "request_id": "poisoned",
        "op": "join",
        "signature": signature,
        "outcome": "served",
        "wall_s": 0.01,
        "new_traces": 1,
        "cache_hits": 0,
        "matches": 1,
        "retry": {"n_attempts": 2, "escalations": 1,
                  "integrity_retries": 0},
        "resolved_knobs": {
            "shuffle_capacity_factor": shuffle_capacity_factor,
            "out_capacity_factor": out_capacity_factor,
        },
        "rung": rung,
        "tuned": None,
        "error": None,
    }


def run_tuner_trial(harness_seed: int, trial: int,
                    n_ranks: int = 8,
                    deadline_s: Optional[float] = 300.0) -> dict:
    """One poisoned-history trial: seed a temp history store with
    capacities claiming a too-small rung for EXACTLY the workload
    signature the tuner will compute, run the join with the tuner
    armed, and grade:

    - the result must be pandas-oracle-exact (the retry ladder
      catches the mis-size — a self-tuned config must never trade
      correctness for speed);
    - the post-run history must record the ESCALATED rung with
      capacities strictly above the poisoned claim (the tuner
      *learns* the corrected rung for the next run).
    """
    import tempfile

    import distributed_join_tpu as dj
    from distributed_join_tpu.parallel.watchdog import (
        HangError,
        call_with_deadline,
    )
    from distributed_join_tpu.planning.tuner import (
        JoinTuner,
        workload_signature,
    )
    from distributed_join_tpu.telemetry import history as tel_history
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    rng = _trial_rng(harness_seed, 1_000 + trial)
    config = {
        "mode": ("padded", "ragged", "skew")[trial % 3],
        "build_rows": rng.choice(_BUILD_ROWS),
        "probe_rows": rng.choice(_PROBE_ROWS),
        "rand_max": rng.choice(_RAND_MAX),
        "selectivity": rng.choice(_SELECTIVITY),
        "table_seed": rng.randrange(1 << 16),
    }
    # Recoverable faults only: the poisoned store is this slice's
    # adversary; corruption grading is the main soak's job.
    plan = random_fault_plan(rng, corruption=False)
    record = {
        "trial": trial,
        "config": config,
        "fault": fault_label(plan),
        "fault_plan": _plan_record(plan),
        "poisoned_history": True,
    }
    t0 = time.perf_counter()

    def body():
        build, probe = generate_build_probe_tables(
            seed=config["table_seed"],
            build_nrows=config["build_rows"],
            probe_nrows=config["probe_rows"],
            rand_max=config["rand_max"],
            selectivity=config["selectivity"],
        )
        oracle = _oracle_frame(build, probe)
        out_names = ["key", "build_payload", "probe_payload"]
        oracle_cols = _frame_columns(oracle, out_names)
        comm = FaultInjectingCommunicator(
            dj.make_communicator("tpu", n_ranks=n_ranks), plan)
        join_opts = dict(
            shuffle="ragged" if config["mode"] == "ragged"
            else "padded",
        )
        if config["mode"] == "skew":
            join_opts["skew_threshold"] = 0.05
        # The poison must land on EXACTLY the signature the tuner
        # will look up (same function, same tables, same opts).
        sig = workload_signature(comm, build, probe, key="key",
                                 with_integrity=True, **join_opts)
        poison = poisoned_history_entry(sig)
        with tempfile.TemporaryDirectory() as tmp:
            store = tel_history.WorkloadHistory(
                tmp + "/history.jsonl")
            store.append(poison)
            store.close()
            tuner = JoinTuner(store.path)

            def attempt():
                return dj.distributed_inner_join(
                    build, probe, comm, auto_retry=6,
                    verify_integrity=True, tuner=tuner,
                    **join_opts)

            res, _ = retry_with_backoff(
                attempt, max_attempts=3, backoff_s=0.01,
                retry_on=(FaultInjectedError,),
            )
            if bool(res.overflow):
                return TrialOutcome(
                    "FAILED:overflow_after_ladder",
                    expected_total=len(oracle)), None
            # Grade content against the oracle, THEN verify the
            # corrected rung landed back in the store.
            out = _grade_result(
                _result_columns(res.table), int(res.total),
                oracle_cols, len(oracle), corrupting=False,
                retries=res.retry_report.n_attempts - 1,
            )
            store2 = tel_history.WorkloadHistory(store.path)
            store2.append(tel_history.request_entry(
                request_id=f"tuner-trial-{trial}", op="join",
                signature=sig, outcome="served",
                wall_s=time.perf_counter() - t0,
                retry_record=res.retry_report.as_record(),
                tuned=getattr(res, "tuned", None)))
            store2.close()
            entries, _ = tel_history.load_history(store.path)
            trend = tel_history.trends_of(entries)[sig].as_dict()
            learned = trend["resolved_knobs_last"] or {}
            corrected = (
                (trend["resolved_rung_last"] or 0)
                > poison["rung"]
                and learned.get("out_capacity_factor", 0)
                > poison["resolved_knobs"]["out_capacity_factor"]
            )
            tuned_rec = getattr(res, "tuned", None) or {}
            presized = (tuned_rec.get("source") == "history")
            return out, {
                "tuner_presized": presized,
                "tuner_corrected": corrected,
                "learned_rung": trend["resolved_rung_last"],
                "learned_knobs": learned,
            }

    try:
        if deadline_s is not None:
            out, tuner_verdict = call_with_deadline(
                body, deadline_s, what=f"tuner trial {trial}")
        else:
            out, tuner_verdict = body()
    except HangError as exc:
        out, tuner_verdict = TrialOutcome("FAILED:hang",
                                          error=str(exc)), None
    except Exception as exc:  # noqa: BLE001 — grading seam
        out, tuner_verdict = TrialOutcome(
            "FAILED:crash", error=f"{type(exc).__name__}: {exc}"), None
    if tuner_verdict is not None:
        record.update(tuner_verdict)
        if not out.failed and not (tuner_verdict["tuner_presized"]
                                   and tuner_verdict["tuner_corrected"]):
            # Oracle-clean but the loop didn't close: either the
            # poisoned sizing never applied (the slice tested
            # nothing) or the corrected rung never landed — both are
            # harness failures, loudly.
            out = TrialOutcome(
                "FAILED:tuner_loop_open",
                error=str(tuner_verdict),
                expected_total=out.expected_total,
                got_total=out.got_total, retries=out.retries)
    record.update(dataclasses.asdict(out))
    record["verdict"] = out.verdict
    record["elapsed_s"] = round(time.perf_counter() - t0, 3)
    return record


def run_hier_trial(harness_seed: int, trial: int, n_ranks: int = 8,
                   deadline_s: Optional[float] = 300.0) -> dict:
    """One hierarchical-shuffle chaos trial: a two-level
    ``--shuffle hierarchical`` join over a faked multi-slice mesh,
    with the fault schedule injected at the communicator seams —
    including the new cross-slice (DCN) exchange
    (``FaultInjectingCommunicator.all_to_all_slice``) — graded
    against the pandas oracle with wire digests on. Deterministic in
    ``(harness_seed, trial)`` like every other trial."""
    from distributed_join_tpu.parallel.watchdog import (
        HangError,
        call_with_deadline,
    )

    rng = _trial_rng(harness_seed, 10_000 + trial)
    slices = rng.choice([s for s in (2, 4) if n_ranks % s == 0]
                        or [1])
    config = {
        "mode": "hierarchical",
        "n_slices": slices,
        "dcn_codec": rng.choice(("auto", "on", "off")),
        "build_rows": rng.choice(_BUILD_ROWS),
        "probe_rows": rng.choice(_PROBE_ROWS),
        "rand_max": rng.choice(_RAND_MAX),
        "selectivity": rng.choice(_SELECTIVITY),
        "table_seed": rng.randrange(1 << 16),
        "auto_retry": 3,
    }
    plan = random_fault_plan(rng, corruption=True)
    record = {
        "trial": trial,
        "config": config,
        "fault": fault_label(plan),
        "fault_plan": _plan_record(plan),
    }
    t0 = time.perf_counter()
    try:
        body = lambda: _run_hier_trial_body(config, plan, n_ranks)  # noqa: E731
        out = (call_with_deadline(body, deadline_s,
                                  what=f"hier chaos trial {trial}")
               if deadline_s is not None else body())
    except HangError as exc:
        out = TrialOutcome("FAILED:hang", error=str(exc))
    except Exception as exc:  # noqa: BLE001 — grading seam
        out = TrialOutcome(
            "FAILED:crash", error=f"{type(exc).__name__}: {exc}")
    record.update(dataclasses.asdict(out))
    record["verdict"] = out.verdict
    record["elapsed_s"] = round(time.perf_counter() - t0, 3)
    return record


def _run_hier_trial_body(config, plan: FaultPlan, n_ranks: int
                         ) -> TrialOutcome:
    import distributed_join_tpu as dj
    from distributed_join_tpu.parallel import integrity
    from distributed_join_tpu.parallel.communicator import (
        HierarchicalTpuCommunicator,
    )
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    build, probe = generate_build_probe_tables(
        seed=config["table_seed"],
        build_nrows=config["build_rows"],
        probe_nrows=config["probe_rows"],
        rand_max=config["rand_max"],
        selectivity=config["selectivity"],
    )
    oracle = _oracle_frame(build, probe)
    oracle_total = len(oracle)
    oracle_cols = _frame_columns(
        oracle, ["key", "build_payload", "probe_payload"])
    corrupting = plan.corrupt_mode is not None
    injected = fault_label(plan) != "none"

    comm = FaultInjectingCommunicator(
        HierarchicalTpuCommunicator(n_slices=config["n_slices"],
                                    n_ranks=n_ranks), plan)

    def loud(kind: str, detail: Optional[str] = None) -> TrialOutcome:
        return TrialOutcome(
            "detected" if injected else f"FAILED:{kind}",
            error=detail or kind, expected_total=oracle_total)

    join_opts = dict(
        out_capacity_factor=3.0,
        shuffle_capacity_factor=3.0,
        shuffle="hierarchical",
        dcn_codec=config["dcn_codec"],
    )
    try:
        def attempt():
            return dj.distributed_inner_join(
                build, probe, comm,
                auto_retry=config["auto_retry"],
                verify_integrity=True, **join_opts,
            )

        res, _ = retry_with_backoff(
            attempt, max_attempts=3, backoff_s=0.01,
            retry_on=(FaultInjectedError,),
        )
        retries = res.retry_report.n_attempts - 1
        if bool(res.overflow):
            return loud("overflow_after_ladder")
        return _grade_result(
            _result_columns(res.table), int(res.total),
            oracle_cols, oracle_total, corrupting, retries,
        )
    except integrity.IntegrityError as exc:
        return loud("false_integrity_alarm", f"IntegrityError: {exc}")
    except (PlanValidationError, FaultInjectedError) as exc:
        return loud("structured_error",
                    f"{type(exc).__name__}: {exc}")


def hier_slice(seed: int, trials: int, n_ranks: int = 8,
               deadline_s: Optional[float] = 300.0,
               repro_out: Optional[str] = None) -> dict:
    """The --hier-slice soak: N hierarchical-shuffle trials; exit
    contract mirrors the main soak (0 failures = pass)."""
    records, failures = [], []
    for k in range(trials):
        rec = run_hier_trial(seed, k, n_ranks=n_ranks,
                             deadline_s=deadline_s)
        records.append(rec)
        print(f"hier trial {k:3d} [{rec['config']['n_slices']}x"
              f"{n_ranks // rec['config']['n_slices']} "
              f"codec={rec['config']['dcn_codec']:4s}] "
              f"fault={rec['fault']:17s} -> {rec['verdict']} "
              f"({rec['elapsed_s']}s)", flush=True)
        if rec["verdict"].startswith("FAILED"):
            failures.append(rec)
            if repro_out:
                path = f"{repro_out}_hier_{seed}_{k}.json"
                with open(path, "w") as f:
                    json.dump({**rec, "harness_seed": seed}, f,
                              indent=2)
                print(f"  repro written: {path}", flush=True)
    verdicts: dict = {}
    for rec in records:
        verdicts[rec["verdict"]] = verdicts.get(rec["verdict"], 0) + 1
    return {
        "harness_seed": seed,
        "slice": "hierarchical_shuffle",
        "n_ranks": n_ranks,
        "trials": len(records),
        "verdicts": verdicts,
        "failures": len(failures),
        "records": records,
    }


def tuner_slice(seed: int, trials: int, n_ranks: int = 8,
                deadline_s: Optional[float] = 300.0,
                repro_out: Optional[str] = None) -> dict:
    """The --tuner-slice soak: N poisoned-history trials; exit
    contract mirrors the main soak (0 failures = pass)."""
    records, failures = [], []
    for k in range(trials):
        rec = run_tuner_trial(seed, k, n_ranks=n_ranks,
                              deadline_s=deadline_s)
        records.append(rec)
        print(f"tuner trial {k:3d} [{rec['config']['mode']:7s}] "
              f"fault={rec['fault']:17s} -> {rec['verdict']} "
              f"(presized={rec.get('tuner_presized')}, "
              f"corrected={rec.get('tuner_corrected')}, "
              f"{rec['elapsed_s']}s)", flush=True)
        if rec["verdict"].startswith("FAILED"):
            failures.append(rec)
            if repro_out:
                path = f"{repro_out}_tuner_{seed}_{k}.json"
                with open(path, "w") as f:
                    json.dump({**rec, "harness_seed": seed}, f,
                              indent=2)
                print(f"  repro written: {path}", flush=True)
    verdicts: dict = {}
    for rec in records:
        verdicts[rec["verdict"]] = verdicts.get(rec["verdict"], 0) + 1
    return {
        "harness_seed": seed,
        "slice": "tuner_poisoned_history",
        "n_ranks": n_ranks,
        "trials": len(records),
        "verdicts": verdicts,
        "failures": len(failures),
        "records": records,
    }


# -- the fleet slice (kill/hang/corrupt one replica mid-soak) ---------


def _fleet_trial_spec(seed: int, trial: int) -> dict:
    """One trial's wire join spec, deterministic in (seed, trial).
    Small discrete shape sets so the fleet shares few compiled
    programs (the shared persist dir absorbs repeats); rand_max stays
    >= 256 for the usual organic-overflow reason."""
    trng = _trial_rng(seed, 555_100 + trial)
    return {
        "op": "join",
        "build_nrows": trng.choice((512, 1024)),
        "probe_nrows": 1024,
        "rand_max": 512,
        "selectivity": trng.choice((0.3, 0.5)),
        "seed": trng.randrange(1 << 16),
        "out_capacity_factor": 3.0,
    }


def fleet_slice(seed: int, trials: int, *, replica_ranks: int = 2,
                fault: Optional[str] = None,
                repro_out: Optional[str] = None) -> dict:
    """The ``--fleet`` soak (docs/FLEET.md): a 2-replica subprocess
    fleet behind the signature-affinity router, N seeded join trials
    through the router's TCP wire, ONE replica faulted mid-soak —
    ``kill`` (SIGKILL at the midpoint trial), ``hang``
    (``FaultPlan.dispatch_delay_s`` armed after a few dispatches via
    ``--fault-plan``, turning into a replica-side HangError + poison),
    or ``corrupt`` (a corruption-mode plan + ``--verify-integrity
    --auto-retry 0``, so the integrity rung refuses loudly through
    the router; the victim is excluded from the shared persist dir so
    its corrupted trace can never enter the distribution tier).

    Gates (the ISSUE 15 acceptance bar):

    - every non-refused answer grades pandas-oracle-clean (a wrong
      match count through the router is ``FAILED:wrong_result`` —
      the one unforgivable outcome);
    - a refusal is only a PASS when it is structured AND the schedule
      injected something (kill/hang failovers absorb transparently;
      the corrupt victim's IntegrityError passes through);
    - for kill/hang: the faulted replica is DRAINED within one probe
      interval (+ scheduling slack) of the fault surfacing and
      REPLACED (healthy at a higher generation), and the
      post-replacement repeat of a PRE-FAULT workload signature
      dispatches with ZERO new traces (the shared persist dir is the
      distribution tier);
    - no request is lost: served + structured refusals == trials
      (the router answers everything; the failover budget bounds the
      retries behind each answer).
    """
    import tempfile

    from distributed_join_tpu.service import fleet as fleet_mod
    from distributed_join_tpu.service.server import (
        ServiceClient,
        _tables_from_spec,
    )

    rng = _trial_rng(seed, 555_000)
    fault = fault or rng.choice(("kill", "hang", "corrupt"))
    # The victim is the replica AFFINE to the workload that will face
    # the fault (the router's routing is deterministic given the
    # spec) — an rng-drawn index could land on a replica the whole
    # soak never routes to. hang/corrupt arm a FaultPlan at spawn, so
    # trial 0's replica faces it; the kill lands right before the
    # MIDPOINT trial's dispatch, so THAT trial's replica is the
    # victim — the router's first post-kill attempt is then
    # guaranteed to hit the dead backend, producing the failed-
    # attempt/failover-retry pair the trace-continuity gate walks.
    trial0 = _fleet_trial_spec(seed, 0)
    victim = fleet_mod.affine_replica(
        _fleet_trial_spec(seed, trials // 2) if fault == "kill"
        else trial0,
        replica_ranks, 2)
    workdir = tempfile.mkdtemp(prefix="djtpu_fleet_soak_")
    cfg = fleet_mod.FleetConfig(
        n_replicas=2,
        replica_ranks=replica_ranks,
        persist_dir=os.path.join(workdir, "programs"),
        history_dir=os.path.join(workdir, "history"),
        probe_interval_s=0.5,
        suspect_strikes=2,
        retry_budget=2,
        request_deadline_s=120.0,
    )
    # Per-INDEX flight-recorder paths in the soak's workdir: a shared
    # path would let the sibling's stop-time dump clobber the
    # victim's postmortem (respawned generations default to the
    # persist dir — still inside the workdir, never the cwd).
    overrides: dict = {
        i: {"extra_args": ["--flight-recorder-path",
                           os.path.join(workdir,
                                        f"replica{i}_fr.json")]}
        for i in (0, 1)
    }
    if fault == "hang":
        overrides[victim]["fault_plan"] = {
            "seed": seed % (1 << 16),
            "dispatch_delay_s": 30.0,
            "delay_after_dispatches": 3}
        # The per-request watchdog must cover the victim's cold
        # compiles (its first dispatches are delay-free) but trip
        # well inside the 30s injected stall.
        overrides[victim]["extra_args"] += ["--guard-deadline-s",
                                            "10.0"]
    elif fault == "corrupt":
        overrides[victim]["fault_plan"] = {
            "seed": seed % (1 << 16),
            "corrupt_mode": rng.choice(CORRUPTION_MODES),
            "corrupt_collectives": 1}
        overrides[victim]["extra_args"] += ["--verify-integrity",
                                            "--auto-retry", "0"]
        overrides[victim]["persist"] = False
    router = fleet_mod.FleetRouter(
        fleet_mod.process_fleet_factory(
            cfg, platform="cpu", replica_overrides=overrides), cfg)
    router.start()
    server, port = fleet_mod.start_router_daemon(router)
    client = ServiceClient("127.0.0.1", port)
    kill_at = trials // 2

    def refusal_injected(k: int, err: str) -> bool:
        """Whether a structured refusal is attributable to THE armed
        fault — anything else (a spurious shed, a bogus overflow
        refusal from the healthy sibling) must grade FAILED even on a
        faulted soak, or the acceptance gate would mask regressions.
        corrupt injects exactly an IntegrityError; hang surfaces as
        HangError/poisoned (raw, or folded into the router's
        failover-exhausted FleetError message); a post-kill refusal
        must chain from the dead replica's connection (a spurious
        shed from the healthy sibling is a failure even then)."""
        if fault == "kill":
            return k >= kill_at and ("connection" in err
                                     or "FleetError" in err)
        if fault == "corrupt":
            return "IntegrityError" in err
        return any(tag in err for tag in ("Hang", "hang", "poisoned"))

    records, failures = [], []
    pre_fault_spec = None
    fault_seen_at: Optional[float] = None
    kill_send_at: Optional[float] = None

    def grade(resp, expected, k: int) -> TrialOutcome:
        if resp.get("ok"):
            if resp.get("overflow"):
                return TrialOutcome("FAILED:overflow",
                                    expected_total=expected)
            got = resp.get("matches")
            failovers = (resp.get("fleet") or {}).get("failovers", 0)
            if got == expected:
                return TrialOutcome(
                    "recovered" if failovers else "ok",
                    expected_total=expected, got_total=got,
                    retries=failovers)
            return TrialOutcome(
                "FAILED:wrong_result", expected_total=expected,
                got_total=got, retries=failovers)
        err = f"{resp.get('error')}: {resp.get('message')}"
        return TrialOutcome(
            "detected" if refusal_injected(k, err)
            else "FAILED:refused",
            error=err, expected_total=expected)

    try:
        for k in range(trials):
            spec = _fleet_trial_spec(seed, k)
            if pre_fault_spec is None:
                pre_fault_spec = dict(spec)
            build, probe = _tables_from_spec(spec)
            expected = len(_oracle_frame(build, probe))
            if fault == "kill" and k == kill_at:
                # SIGKILL right before THIS dispatch (the oracle is
                # already computed): the victim is this trial's
                # affine replica, so the router's next attempt dials
                # the dead backend unless the 0.5s prober wins the
                # microsecond race (detected below via drained_at
                # and excused by the trace-continuity gate).
                router.replicas[victim].backend.kill()
                fault_seen_at = time.monotonic()
            t_send = time.monotonic()
            if fault == "kill" and k == kill_at:
                kill_send_at = t_send
            t0 = time.perf_counter()
            try:
                resp = client.send(spec)
            except (OSError, ValueError) as exc:
                # The ROUTER must never die under a replica fault.
                resp = {"ok": False, "error": "RouterLost",
                        "message": f"{type(exc).__name__}: {exc}"}
            t_resp = time.monotonic()
            out = grade(resp, expected, k)
            rep = router.replicas[victim]
            if fault in ("hang", "corrupt") \
                    and fault_seen_at is None \
                    and (rep.drained_at or 0) >= t_send:
                # The armed fault surfaced during THIS trial: it
                # became OBSERVABLE when the replica's HangError
                # answer (its own watchdog deadline — the in-flight
                # request 'deadlines out' by design) reached the
                # router, which is no later than our response. The
                # drain-latency gate measures from there.
                fault_seen_at = min(t_resp, rep.drained_at)
            rec = {"trial": k, "spec": spec, "fault": fault,
                   **dataclasses.asdict(out),
                   "verdict": out.verdict,
                   "elapsed_s": round(time.perf_counter() - t0, 3)}
            records.append(rec)
            print(f"fleet trial {k:3d} fault={fault:7s} -> "
                  f"{rec['verdict']} ({rec['elapsed_s']}s)",
                  flush=True)
            if out.failed:
                failures.append(rec)
                if repro_out:
                    path = f"{repro_out}_fleet_{seed}_{k}.json"
                    with open(path, "w") as f:
                        json.dump({**rec, "harness_seed": seed,
                                   "replay": "python -m distributed_"
                                   "join_tpu.parallel.chaos --fleet "
                                   f"{trials} --seed {seed}"},
                                  f, indent=2)
                    print(f"  repro written: {path}", flush=True)

        drain_replace = {"required": fault in ("kill", "hang")}
        post_replacement_new_traces = None
        trace_continuity = {"required": False}
        if fault in ("kill", "hang"):
            rep = router.replicas[victim]
            replaced = router.wait_replaced(
                victim, timeout_s=cfg.spawn_timeout_s)
            drained_after_s = (
                (rep.drained_at - fault_seen_at)
                if rep.drained_at is not None
                and fault_seen_at is not None else None)
            within = (drained_after_s is not None
                      and drained_after_s
                      <= 3 * cfg.probe_interval_s + 5.0)
            drain_replace.update(
                drained=rep.drained_at is not None,
                drained_after_s=(round(drained_after_s, 3)
                                 if drained_after_s is not None
                                 else None),
                drained_within_probe_interval=within,
                replaced=replaced,
                generation=rep.generation)
            if not (replaced and within):
                failures.append({"gate": "drain_replace",
                                 **drain_replace})
            # Zero-trace warm repeat of a PRE-FAULT signature on the
            # replacement (the shared persist dir at work) — only
            # when a replacement is actually up; dialing the dead
            # backend's old port would crash the harness instead of
            # recording the gate failure above.
            if replaced:
                try:
                    direct = ServiceClient(*rep.addr(),
                                           timeout_s=120.0)
                    try:
                        replay = direct.send(dict(pre_fault_spec))
                    finally:
                        direct.close()
                except (OSError, ValueError) as exc:
                    replay = {"ok": False, "error": "RouterLost",
                              "message":
                                  f"{type(exc).__name__}: {exc}"}
                post_replacement_new_traces = replay.get(
                    "new_traces")
                if not replay.get("ok") \
                        or replay.get("new_traces") != 0:
                    failures.append({
                        "gate": "post_replacement_warm",
                        "response": {kk: replay.get(kk) for kk in
                                     ("ok", "error", "message",
                                      "new_traces", "matches")}})
        # Distributed-tracing continuity through the kill
        # (docs/OBSERVABILITY.md "Distributed tracing"): every
        # failed join-dispatch attempt the flight ring recorded must
        # share its trace_id with the SAME request's final served
        # record — the victim hop and the winning failover retry are
        # ONE causal trace, never two. The scripted SIGKILL
        # guarantees at least one mid-soak failover, so an empty
        # attempt ring here means trace stamping broke, not a quiet
        # soak.
        if fault == "kill":
            ring = router.recorder.snapshot()["records"]
            failed_attempts = [
                r for r in ring
                if r.get("outcome") == "attempt_failed"
                and r.get("op") == "join"
                and (r.get("trace") or {}).get("trace_id")]
            served_by_rid = {
                r.get("request_id"): r for r in ring
                if r.get("outcome") == "served"}
            broken = []
            for r in failed_attempts:
                final = served_by_rid.get(r.get("request_id"))
                f_tid = (r.get("trace") or {}).get("trace_id")
                s_tid = ((final or {}).get("trace")
                         or {}).get("trace_id")
                if final is None or f_tid != s_tid:
                    broken.append({"request_id": r.get("request_id"),
                                   "attempt_trace": f_tid,
                                   "served_trace": s_tid})
            # The prober can (rarely) drain the freshly killed victim
            # in the microseconds between the SIGKILL and the
            # midpoint dispatch — then the router routes straight to
            # the sibling and no attempt ever fails. Observable as
            # drained_at preceding the dispatch; excused, because
            # there was no failover whose continuity COULD be graded.
            rep = router.replicas[victim]
            prober_won = bool(
                not failed_attempts
                and rep.drained_at is not None
                and kill_send_at is not None
                and rep.drained_at <= kill_send_at)
            trace_continuity = {
                "required": True,
                "failed_attempts": len(failed_attempts),
                "broken": broken,
                "prober_won_race": prober_won,
            }
            if broken or (not failed_attempts and not prober_won):
                failures.append({"gate": "trace_continuity",
                                 **trace_continuity})
    finally:
        client.close()
        server.shutdown()
        server.server_close()
        router.stop()

    verdicts: dict = {}
    for rec in records:
        verdicts[rec["verdict"]] = verdicts.get(rec["verdict"], 0) + 1
    answered = sum(1 for r in records
                   if not r["verdict"].startswith("FAILED"))
    if failures:
        # Keep the workdir: the per-replica flight dumps and the
        # shared program dir ARE the postmortem.
        print(f"fleet soak artifacts kept at {workdir}", flush=True)
    else:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "kind": "fleet_soak",
        "schema_version": 1,
        "harness_seed": seed,
        "slice": "fleet",
        "fault": fault,
        "victim": victim,
        "replica_ranks": replica_ranks,
        "trials": len(records),
        "verdicts": verdicts,
        "answered": answered,
        "failures": len(failures),
        "failure_records": failures,
        "drain_replace": drain_replace,
        "post_replacement_new_traces": post_replacement_new_traces,
        "trace_continuity": trace_continuity,
        "fleet_stats": router.stats(),
        "records": records,
    }


# -- the multi-tenant fleet slice -------------------------------------


def fleet_tenant_slice(seed: int, trials: int, *,
                       replica_ranks: int = 2,
                       repro_out: Optional[str] = None) -> dict:
    """The ``--tenants`` soak (docs/FLEET.md "Multi-tenancy &
    autoscaling"): a 2-replica subprocess fleet with two configured
    tenants — ``noisy`` (QPS-quota'd, priority 1) FLOODS at ~5x its
    quota while ``quiet`` (no quota, priority 2) runs oracle-graded
    joins; one replica is SIGKILLed at the midpoint trial.

    Gates (the ISSUE 20 acceptance bar):

    - the quiet tenant's every answer grades pandas-oracle-EXACT and
      its shed count is ZERO (router-side and response-side): the
      noisy tenant's flood is shed, never the quiet tenant;
    - the noisy tenant IS shed — structured ``QuotaExceededError`` /
      ``ShedError`` refusals naming the bound, with the router's
      per-tenant shed counters agreeing;
    - tenant isolation in the tuner namespace: every router history
      entry carries its sender's tenant stamp and the trend table
      keys stay ``tenant/signature``-namespaced — the noisy flood
      never moves the quiet tenant's knobs;
    - the killed replica is drained and REPLACED, and the
      replacement serves a pre-kill quiet signature with ZERO new
      traces (the shared persist dir is the distribution tier — the
      same warm contract the autoscaler's rotation gate enforces);
      the router's ``fleet_autoscale`` record stays well-formed with
      the control loop live the whole soak.
    """
    import tempfile

    from distributed_join_tpu.service import fleet as fleet_mod
    from distributed_join_tpu.service.server import (
        ServiceClient,
        _tables_from_spec,
    )
    from distributed_join_tpu.telemetry import history as tel_history

    noisy_qps = 2.0
    flood_per_trial = 10  # ~5x the one-second bucket capacity
    workdir = tempfile.mkdtemp(prefix="djtpu_tenant_soak_")
    cfg = fleet_mod.FleetConfig(
        n_replicas=2,
        replica_ranks=replica_ranks,
        persist_dir=os.path.join(workdir, "programs"),
        history_dir=os.path.join(workdir, "history"),
        probe_interval_s=0.5,
        suspect_strikes=2,
        retry_budget=2,
        request_deadline_s=120.0,
        tenants={
            "noisy": {"qps": noisy_qps, "burst_s": 1.0,
                      "priority": 1},
            "quiet": {"priority": 2},
        },
        # The control loop runs the whole soak (its record must stay
        # well-formed under fault); the up bound is out of reach so
        # the scripted kill's respawn is the one lifecycle event.
        autoscale=True,
        autoscale_up_qps=1e9,
        autoscale_interval_s=0.5,
    )
    overrides: dict = {
        i: {"extra_args": ["--flight-recorder-path",
                           os.path.join(workdir,
                                        f"replica{i}_fr.json")]}
        for i in (0, 1)
    }
    kill_at = trials // 2
    victim = fleet_mod.affine_replica(
        _fleet_trial_spec(seed, kill_at), replica_ranks, 2)
    router = fleet_mod.FleetRouter(
        fleet_mod.process_fleet_factory(
            cfg, platform="cpu", replica_overrides=overrides), cfg)
    router.start()
    server, port = fleet_mod.start_router_daemon(router)
    client = ServiceClient("127.0.0.1", port)

    records, failures = [], []
    noisy_counts = {"sent": 0, "ok": 0, "quota_shed": 0,
                    "priority_shed": 0, "excused": 0, "other": 0}
    quiet_shed_responses = 0
    pre_kill_spec = None
    killed = False

    def send(spec):
        try:
            return client.send(spec)
        except (OSError, ValueError) as exc:
            return {"ok": False, "error": "RouterLost",
                    "message": f"{type(exc).__name__}: {exc}"}

    try:
        for k in range(trials):
            spec = _fleet_trial_spec(seed, k)
            build, probe = _tables_from_spec(spec)
            expected = len(_oracle_frame(build, probe))
            if k == kill_at:
                router.replicas[victim].backend.kill()
                killed = True
            # The noisy flood rides FIRST each round: back-to-back
            # sends far over the bucket — the quiet trial right
            # after must be untouched by it.
            for j in range(flood_per_trial):
                nresp = send({**_fleet_trial_spec(seed, k),
                              "tenant": "noisy",
                              "request_id":
                                  f"noisy-{seed}-{k}-{j}"})
                noisy_counts["sent"] += 1
                if nresp.get("ok"):
                    noisy_counts["ok"] += 1
                elif nresp.get("error") == "QuotaExceededError":
                    noisy_counts["quota_shed"] += 1
                elif nresp.get("error") == "ShedError":
                    noisy_counts["priority_shed"] += 1
                elif killed and nresp.get("error") in (
                        "FleetError", "AdmissionError"):
                    # An ADMITTED noisy request can land on the dead
                    # backend before the prober drains it — that is
                    # the scripted kill, not a quota bug.
                    noisy_counts["excused"] += 1
                else:
                    noisy_counts["other"] += 1
                    failures.append({"gate": "noisy_outcome",
                                     "trial": k, "flood": j,
                                     "error": nresp.get("error"),
                                     "message":
                                         nresp.get("message")})
            t0 = time.perf_counter()
            resp = send({**spec, "tenant": "quiet",
                         "request_id": f"quiet-{seed}-{k}"})
            if resp.get("shed"):
                quiet_shed_responses += 1
            got = resp.get("matches")
            failovers = (resp.get("fleet") or {}).get("failovers",
                                                      0)
            if resp.get("ok") and got == expected:
                verdict = "recovered" if failovers else "ok"
            elif resp.get("ok"):
                verdict = "FAILED:wrong_result"
            else:
                verdict = "FAILED:refused"
            rec = {"trial": k, "spec": spec, "verdict": verdict,
                   "expected_total": expected, "got_total": got,
                   "retries": failovers,
                   "error": (None if resp.get("ok") else
                             f"{resp.get('error')}: "
                             f"{resp.get('message')}"),
                   "elapsed_s": round(time.perf_counter() - t0, 3)}
            records.append(rec)
            print(f"tenant trial {k:3d} -> {verdict} "
                  f"({rec['elapsed_s']}s)", flush=True)
            if verdict.startswith("FAILED"):
                failures.append(rec)
                if repro_out:
                    path = f"{repro_out}_tenant_{seed}_{k}.json"
                    with open(path, "w") as f:
                        json.dump({**rec, "harness_seed": seed,
                                   "replay": "python -m distributed"
                                   "_join_tpu.parallel.chaos "
                                   f"--tenants {trials} --seed "
                                   f"{seed}"}, f, indent=2)
                    print(f"  repro written: {path}", flush=True)
            if not verdict.startswith("FAILED") \
                    and k < kill_at:
                pre_kill_spec = dict(spec)

        st = router.stats()
        tenants_st = st.get("tenants") or {}
        quiet_st = tenants_st.get("quiet") or {}
        noisy_st = tenants_st.get("noisy") or {}
        # Gate: the quiet tenant was NEVER shed — zero shed answers
        # on the wire AND a zero router-side shed counter.
        if quiet_shed_responses \
                or (quiet_st.get("shed") or 0) != 0:
            failures.append({
                "gate": "quiet_never_shed",
                "shed_responses": quiet_shed_responses,
                "router_shed": quiet_st.get("shed")})
        # Gate: the noisy tenant WAS shed, with the router's counter
        # agreeing that sheds happened.
        if noisy_counts["quota_shed"] == 0 \
                or (noisy_st.get("shed") or 0) == 0:
            failures.append({
                "gate": "noisy_shed",
                "counts": dict(noisy_counts),
                "router_shed": noisy_st.get("shed")})
        # Gate: tenant isolation in the tuner namespace — every
        # history entry stamped with its sender's tenant, every
        # trend key tenant/signature-namespaced.
        entries, _ = tel_history.load_history(
            cfg.history_dir)
        request_entries = [e for e in entries
                           if e.get("kind") == "request"]
        unstamped = [e for e in request_entries
                     if e.get("tenant") not in ("noisy", "quiet")]
        trend_keys = list(tel_history.trends_of(request_entries))
        bare = [key for key in trend_keys if "/" not in key]
        if unstamped or bare:
            failures.append({
                "gate": "tenant_namespace",
                "unstamped_entries": len(unstamped),
                "bare_trend_keys": bare})
        # Gate: drain + replace + the warm contract on the
        # replacement (the autoscaler's own rotation gate).
        rep = router.replicas[victim]
        replaced = router.wait_replaced(
            victim, timeout_s=cfg.spawn_timeout_s)
        drain_replace = {"required": True,
                         "drained": rep.drained_at is not None,
                         "replaced": replaced,
                         "generation": rep.generation}
        post_replacement_new_traces = None
        if not replaced:
            failures.append({"gate": "drain_replace",
                             **drain_replace})
        elif pre_kill_spec is not None:
            try:
                direct = ServiceClient(*rep.addr(),
                                       timeout_s=120.0)
                try:
                    replay = direct.send(
                        {**pre_kill_spec, "tenant": "quiet"})
                finally:
                    direct.close()
            except (OSError, ValueError) as exc:
                replay = {"ok": False, "error": "RouterLost",
                          "message":
                              f"{type(exc).__name__}: {exc}"}
            post_replacement_new_traces = replay.get("new_traces")
            if not replay.get("ok") \
                    or replay.get("new_traces") != 0:
                failures.append({
                    "gate": "post_replacement_warm",
                    "response": {kk: replay.get(kk) for kk in
                                 ("ok", "error", "message",
                                  "new_traces", "matches")}})
        autoscale = router.autoscale_record()
        from distributed_join_tpu.telemetry.analyze import (
            check_file,
        )

        as_path = os.path.join(workdir, "fleet_autoscale.json")
        with open(as_path, "w") as f:
            json.dump(autoscale, f, indent=2)
        as_problems = check_file(as_path)
        if as_problems:
            failures.append({"gate": "autoscale_record",
                             "problems": as_problems})
    finally:
        client.close()
        server.shutdown()
        server.server_close()
        router.stop()

    verdicts: dict = {}
    for rec in records:
        verdicts[rec["verdict"]] = verdicts.get(rec["verdict"],
                                                0) + 1
    if failures:
        print(f"tenant soak artifacts kept at {workdir}",
              flush=True)
    else:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "kind": "fleet_tenant_soak",
        "schema_version": 1,
        "harness_seed": seed,
        "slice": "tenants",
        "victim": victim,
        "replica_ranks": replica_ranks,
        "trials": len(records),
        "verdicts": verdicts,
        "noisy": {"quota_qps": noisy_qps,
                  "flood_per_trial": flood_per_trial,
                  **noisy_counts,
                  "router_shed": noisy_st.get("shed"),
                  "quota_sheds": noisy_st.get("quota_sheds"),
                  "priority_sheds": noisy_st.get("priority_sheds")},
        "quiet": {"trials": len(records),
                  "shed_responses": quiet_shed_responses,
                  "router_shed": quiet_st.get("shed") or 0},
        "failures": len(failures),
        "failure_records": failures,
        "drain_replace": drain_replace,
        "post_replacement_new_traces": post_replacement_new_traces,
        "autoscale": {"enabled": autoscale.get("enabled"),
                      "spawns_total":
                          autoscale.get("spawns_total"),
                      "drains_total":
                          autoscale.get("drains_total")},
        "fleet_stats": st,
        "records": records,
    }


# -- the resident-kill fleet slice ------------------------------------


def _resident_trial_spec(seed: int, trial: int, table: str) -> dict:
    """One probe-only trial spec, deterministic in (seed, trial).
    The table name pins the signature ring slot, so every trial
    ring-starts at the table's primary holder — the victim faces
    ALL the traffic."""
    trng = _trial_rng(seed, 555_200 + trial)
    return {
        "op": "join",
        "table": table,
        "probe_nrows": trng.choice((512, 1024)),
        "rand_max": 4096,
        "selectivity": trng.choice((0.3, 0.5)),
        "seed": trng.randrange(1 << 16),
        "out_capacity_factor": 3.0,
    }


def fleet_resident_slice(seed: int, trials: int, *,
                         replica_ranks: int = 2,
                         repro_out: Optional[str] = None) -> dict:
    """The ``--fleet-fault resident-kill`` soak (docs/FLEET.md
    "Replication & HA"): a K=2 fleet holds ONE resident table
    (register + one append, so generation fencing is live), N
    probe-only trials ring-start at the table's PRIMARY holder, and
    that holder is SIGKILLed at the midpoint.

    Gates:

    - **zero wrong rows from any holder** — every non-refused answer
      must equal the pandas oracle over register + delta (stale or
      rebuilt images either serve the full image or refuse; a wrong
      match count is the unforgivable ``FAILED:wrong_result``);
    - **failover within the retry budget** — post-kill probe-only
      trials are answered by the surviving holder within
      ``retry_budget + 1`` attempts (a refusal only passes when
      attributable to the kill);
    - **rebuild** — the replacement walks ``rebuilding -> serving``
      at the directory generation by replaying the durable manifest,
      and a generation-FENCED replay of a pre-fault probe-only
      signature on it answers oracle-exact with ZERO new traces (the
      shared persist dir hands the rebuilt holder its warm program).
    """
    import tempfile

    import pandas as pd

    from distributed_join_tpu.service import fleet as fleet_mod
    from distributed_join_tpu.service.server import (
        ServiceClient,
        _build_from_spec,
        _probe_from_spec,
    )

    table = "soak_residents"
    reg = {"op": "register", "name": table, "rows": 2048,
           "seed": seed % 9973 + 11, "rand_max": 4096,
           "unique_keys": True}
    delta = {"op": "append", "name": table, "rows": 256,
             "seed": seed % 9973 + 13, "rand_max": 4096}
    victim = fleet_mod.affine_replica({"op": "join", "table": table},
                                      replica_ranks, 2)
    workdir = tempfile.mkdtemp(prefix="djtpu_fleet_resident_soak_")
    cfg = fleet_mod.FleetConfig(
        n_replicas=2,
        replica_ranks=replica_ranks,
        persist_dir=os.path.join(workdir, "programs"),
        history_dir=os.path.join(workdir, "history"),
        coord_dir=os.path.join(workdir, "coord"),
        table_replication=2,
        probe_interval_s=0.5,
        suspect_strikes=2,
        retry_budget=2,
        request_deadline_s=120.0,
    )
    overrides: dict = {
        i: {"extra_args": ["--flight-recorder-path",
                           os.path.join(workdir,
                                        f"replica{i}_fr.json")]}
        for i in (0, 1)
    }
    router = fleet_mod.FleetRouter(
        fleet_mod.process_fleet_factory(
            cfg, platform="cpu", replica_overrides=overrides), cfg)
    router.start()
    server, port = fleet_mod.start_router_daemon(router)
    client = ServiceClient("127.0.0.1", port)
    kill_at = trials // 2

    # The resident oracle: register + delta, concatenated once.
    base = _build_from_spec(reg)
    build_df = pd.concat(
        [base.to_pandas(), _build_from_spec(delta).to_pandas()],
        ignore_index=True)

    class _Stub:
        wire_spec = {k: reg[k] for k in
                     ("rows", "seed", "rand_max", "unique_keys")}
        wire_build_keys = base.columns["key"]

    def expected_matches(spec: dict) -> int:
        probe = _probe_from_spec(spec, _Stub)
        return len(build_df.merge(probe.to_pandas(), on="key"))

    def refusal_injected(k: int, err: str) -> bool:
        return k >= kill_at and ("connection" in err
                                 or "FleetError" in err
                                 or "StaleGeneration" in err)

    records, failures = [], []
    pre_fault_spec = None
    generation = None
    try:
        r = client.send(reg)
        if not r.get("ok"):
            raise RuntimeError(f"soak register failed: {r}")
        a = client.send(delta)
        if not a.get("ok"):
            raise RuntimeError(f"soak append failed: {a}")
        generation = int(a.get("generation", 0))

        for k in range(trials):
            spec = _resident_trial_spec(seed, k, table)
            if pre_fault_spec is None:
                pre_fault_spec = dict(spec)
            if k == kill_at:
                router.replicas[victim].backend.kill()
            expected = expected_matches(spec)
            t0 = time.perf_counter()
            try:
                resp = client.send(spec)
            except (OSError, ValueError) as exc:
                resp = {"ok": False, "error": "RouterLost",
                        "message": f"{type(exc).__name__}: {exc}"}
            if resp.get("ok"):
                got = resp.get("matches")
                fl = resp.get("fleet") or {}
                if resp.get("overflow"):
                    out = TrialOutcome("FAILED:overflow",
                                       expected_total=expected)
                elif got != expected:
                    # The unforgivable outcome: a holder served rows
                    # that exclude the delta (or worse).
                    out = TrialOutcome("FAILED:wrong_result",
                                       expected_total=expected,
                                       got_total=got,
                                       retries=fl.get("failovers",
                                                      0))
                elif fl.get("attempts", 1) > cfg.retry_budget + 1:
                    out = TrialOutcome(
                        "FAILED:budget",
                        expected_total=expected, got_total=got,
                        retries=fl.get("failovers", 0))
                else:
                    out = TrialOutcome(
                        "recovered" if fl.get("failovers") else "ok",
                        expected_total=expected, got_total=got,
                        retries=fl.get("failovers", 0))
            else:
                err = (f"{resp.get('error')}: "
                       f"{resp.get('message')}")
                out = TrialOutcome(
                    "detected" if refusal_injected(k, err)
                    else "FAILED:refused",
                    error=err, expected_total=expected)
            rec = {"trial": k, "spec": spec,
                   "fault": "resident-kill",
                   **dataclasses.asdict(out),
                   "verdict": out.verdict,
                   "elapsed_s": round(time.perf_counter() - t0, 3)}
            records.append(rec)
            print(f"resident trial {k:3d} -> {rec['verdict']} "
                  f"({rec['elapsed_s']}s)", flush=True)
            if out.failed:
                failures.append(rec)
                if repro_out:
                    path = f"{repro_out}_resident_{seed}_{k}.json"
                    with open(path, "w") as f:
                        json.dump({**rec, "harness_seed": seed,
                                   "replay": "python -m distributed_"
                                   "join_tpu.parallel.chaos --fleet "
                                   f"{trials} --fleet-fault "
                                   "resident-kill "
                                   f"--seed {seed}"},
                                  f, indent=2)
                    print(f"  repro written: {path}", flush=True)

        # -- the rebuild gate -----------------------------------------
        drain_replace = {"required": True}
        rebuild: dict = {"required": True}
        replaced = router.wait_replaced(victim,
                                       timeout_s=cfg.spawn_timeout_s)
        drain_replace.update(
            replaced=replaced,
            generation=router.replicas[victim].generation)
        if not replaced:
            failures.append({"gate": "drain_replace",
                             **drain_replace})
        holder = None
        deadline = time.monotonic() + cfg.spawn_timeout_s
        while time.monotonic() < deadline:
            holder = (router.stats()["tables"]
                      .get(table, {}).get("holders", {})
                      .get(str(victim)))
            if holder and holder["state"] == "serving":
                break
            time.sleep(0.2)
        rebuild.update(holder=holder,
                       rebuilds_total=router.stats()
                       ["rebuilds_total"])
        if not (holder and holder["state"] == "serving"
                and holder["generation"] == generation):
            failures.append({"gate": "rebuild_serving", **rebuild})
        elif replaced:
            # The FENCED replay of a pre-fault signature on the
            # rebuilt image: oracle-exact, correct generation, zero
            # new traces.
            try:
                direct = ServiceClient(
                    *router.replicas[victim].addr(),
                    timeout_s=120.0)
                try:
                    replay = direct.send(
                        {**pre_fault_spec,
                         "min_generation": generation})
                finally:
                    direct.close()
            except (OSError, ValueError) as exc:
                replay = {"ok": False, "error": "RouterLost",
                          "message": f"{type(exc).__name__}: {exc}"}
            rebuild["replay"] = {kk: replay.get(kk) for kk in
                                 ("ok", "error", "message",
                                  "new_traces", "matches")}
            rebuild["replay"]["generation"] = \
                (replay.get("resident") or {}).get("generation")
            if (not replay.get("ok")
                    or replay.get("new_traces") != 0
                    or replay.get("matches")
                    != expected_matches(pre_fault_spec)
                    or rebuild["replay"]["generation"]
                    != generation):
                failures.append({"gate": "rebuilt_replay_warm",
                                 **rebuild})
    finally:
        client.close()
        server.shutdown()
        server.server_close()
        router.stop()

    verdicts: dict = {}
    for rec in records:
        verdicts[rec["verdict"]] = verdicts.get(rec["verdict"],
                                                0) + 1
    if failures:
        print(f"resident soak artifacts kept at {workdir}",
              flush=True)
    else:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "kind": "fleet_soak",
        "schema_version": 1,
        "harness_seed": seed,
        "slice": "fleet_resident",
        "fault": "resident-kill",
        "victim": victim,
        "table": table,
        "generation": generation,
        "replica_ranks": replica_ranks,
        "trials": len(records),
        "verdicts": verdicts,
        "failures": len(failures),
        "failure_records": failures,
        "drain_replace": drain_replace,
        "rebuild": rebuild,
        "fleet_stats": router.stats(),
        "records": records,
    }


# -- the soak loop ----------------------------------------------------


def soak(seed: int, trials: int, n_ranks: int = 8,
         corruption: bool = True, only_trial: Optional[int] = None,
         deadline_s: Optional[float] = 300.0,
         repro_out: Optional[str] = None) -> dict:
    """Run the soak (or one replayed trial); returns the summary
    record and writes a minimal-repro JSON per failed trial."""
    indices = ([only_trial] if only_trial is not None
               else list(range(trials)))
    records, failures = [], []
    for k in indices:
        rec = run_trial(seed, k, n_ranks=n_ranks,
                        corruption=corruption, deadline_s=deadline_s)
        records.append(rec)
        line = (f"trial {k:3d} [{rec['config']['mode']:11s}] "
                f"fault={rec['fault']:17s} -> {rec['verdict']} "
                f"({rec['elapsed_s']}s)")
        print(line, flush=True)
        if rec["verdict"].startswith("FAILED"):
            failures.append(rec)
            if repro_out:
                path = repro_out.replace(
                    ".json", f"_{seed}_{k}.json") if repro_out.endswith(
                    ".json") else f"{repro_out}_{seed}_{k}.json"
                repro = dict(rec)
                repro["harness_seed"] = seed
                repro["replay"] = (
                    "python -m distributed_join_tpu.parallel.chaos "
                    f"--seed {seed} --trial {k}"
                )
                with open(path, "w") as f:
                    json.dump(repro, f, indent=2)
                print(f"  repro written: {path}", flush=True)
    verdicts: dict = {}
    for rec in records:
        verdicts[rec["verdict"]] = verdicts.get(rec["verdict"], 0) + 1
    return {
        "harness_seed": seed,
        "n_ranks": n_ranks,
        "trials": len(records),
        "verdicts": verdicts,
        "failures": len(failures),
        "records": records,
    }


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--trials", type=int, default=25)
    p.add_argument("--seed", type=int, default=42,
                   help="harness seed; every trial derives its own "
                        "rng from (seed, trial) so any trial replays "
                        "exactly")
    p.add_argument("--trial", type=int, default=None,
                   help="replay ONE trial of this seed (the repro "
                        "workflow)")
    p.add_argument("--n-ranks", type=int, default=8)
    p.add_argument("--no-corruption", action="store_true",
                   help="restrict schedules to recoverable faults "
                        "(squeezes/transients) — the control arm")
    p.add_argument("--hier-slice", type=int, default=None,
                   metavar="N",
                   help="instead of the main soak: N hierarchical-"
                        "shuffle trials (--shuffle hierarchical over "
                        "a faked multi-slice mesh, fault schedules "
                        "including the cross-slice DCN exchange seam, "
                        "pandas-oracle graded with wire digests on)")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="instead of the main soak: N join trials "
                        "through a 2-replica subprocess fleet behind "
                        "the signature-affinity router "
                        "(service/fleet.py), ONE replica killed/"
                        "hung/corrupted mid-soak — every non-refused "
                        "answer pandas-oracle-graded, drain+replace "
                        "and the zero-trace warm replacement gated "
                        "(docs/FLEET.md)")
    p.add_argument("--fleet-fault", default=None,
                   choices=("kill", "hang", "corrupt",
                            "resident-kill"),
                   help="pin the fleet soak's fault (default: drawn "
                        "from the harness seed); resident-kill runs "
                        "the REPLICATED-table slice instead (K=2 "
                        "holders, the table's primary holder "
                        "SIGKILLed mid-soak, manifest rebuild + "
                        "fenced zero-trace replay gated)")
    p.add_argument("--replica-ranks", type=int, default=2,
                   help="mesh size of each fleet replica")
    p.add_argument("--tenants", type=int, default=None, metavar="N",
                   help="instead of the main soak: N oracle-graded "
                        "quiet-tenant trials through a 2-replica "
                        "fleet while a noisy tenant floods at ~5x "
                        "its QPS quota and one replica is killed "
                        "mid-soak — the quiet tenant must stay "
                        "exact with ZERO sheds, the noisy tenant "
                        "must be quota-shed, the tuner namespace "
                        "must stay tenant-isolated, and the "
                        "replacement must serve warm (docs/FLEET.md "
                        "\"Multi-tenancy & autoscaling\")")
    p.add_argument("--tuner-slice", type=int, default=None,
                   metavar="N",
                   help="instead of the main soak: N poisoned-history "
                        "autotuner trials (a history file claims a "
                        "too-small rung; every trial must still grade "
                        "oracle-clean via the retry ladder, and the "
                        "post-run history must record the escalated "
                        "rung)")
    p.add_argument("--trial-deadline-s", type=float, default=300.0,
                   help="hang watchdog per trial (0 disables)")
    p.add_argument("--repro-out", default="chaos_repro.json",
                   help="minimal-repro JSON path stem for failed "
                        "trials")
    p.add_argument("--json-output", default=None,
                   help="write the full soak summary record here")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.trials < 1 or args.n_ranks < 2:
        print("chaos: need --trials >= 1 and --n-ranks >= 2",
              file=sys.stderr)
        return 2
    # The soak is a CPU-mesh harness by design (deterministic,
    # hardware-free); reuse the shared platform forcing + the
    # persistent compile cache so repeat soaks replay their programs.
    from distributed_join_tpu import device
    from distributed_join_tpu.benchmarks import force_cpu_platform

    force_cpu_platform(args.n_ranks)
    import jax

    device.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.5)

    if args.tenants:
        summary = fleet_tenant_slice(
            args.seed, args.tenants,
            replica_ranks=args.replica_ranks,
            repro_out=args.repro_out)
    elif args.fleet and args.fleet_fault == "resident-kill":
        summary = fleet_resident_slice(
            args.seed, args.fleet,
            replica_ranks=args.replica_ranks,
            repro_out=args.repro_out)
    elif args.fleet:
        summary = fleet_slice(args.seed, args.fleet,
                              replica_ranks=args.replica_ranks,
                              fault=args.fleet_fault,
                              repro_out=args.repro_out)
    elif args.hier_slice:
        summary = hier_slice(args.seed, args.hier_slice,
                             n_ranks=args.n_ranks,
                             deadline_s=(args.trial_deadline_s
                                         or None),
                             repro_out=args.repro_out)
    elif args.tuner_slice:
        summary = tuner_slice(args.seed, args.tuner_slice,
                              n_ranks=args.n_ranks,
                              deadline_s=(args.trial_deadline_s
                                          or None),
                              repro_out=args.repro_out)
    else:
        summary = soak(
            args.seed, args.trials, n_ranks=args.n_ranks,
            corruption=not args.no_corruption,
            only_trial=args.trial,
            deadline_s=(args.trial_deadline_s or None),
            repro_out=args.repro_out,
        )
    print(json.dumps({k: v for k, v in summary.items()
                      if k != "records"}))
    if args.json_output:
        with open(args.json_output, "w") as f:
            json.dump(summary, f, indent=2)
    return 1 if summary["failures"] else 0


if __name__ == "__main__":
    sys.exit(main())
