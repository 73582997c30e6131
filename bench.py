"""Headline benchmark — one JSON line for the driver.

Measures the flagship pipeline (radix hash-partition -> shuffle ->
sort-merge inner join) end-to-end on the available device(s) and prints

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Protocol mirrors the reference's ``benchmark/distributed_join`` driver
(SURVEY.md §3.1): generate outside the measured region, warmup, then a
timed region reporting ``(build_nrows + probe_nrows) / elapsed-per-join``
rows/sec. The timing discipline (chained dependent iterations in one
compiled loop; see distributed_join_tpu/utils/benchmarking.py) is shared
with benchmark/distributed_join.py.

``vs_baseline`` is value / 125 M rows/s/chip — the BASELINE.json north
star (>= 1 B rows/s aggregate on 8 v5e chips) divided per chip; there
are no reference-published numbers (BASELINE.md).

Output sizing (round-2 weak #5 / round-3 #8): the join is measured
under BOTH capacity stories and both appear in the one JSON line —

- ``value``: output block sized from the known match count + 25% slack
  (mirrors the reference's exactly-sized cudf::inner_join allocation;
  comparable with BENCH_r01..r03).
- ``value_capacity_contract``: output block sized by the flag driver's
  general contract, ``out_capacity_factor`` (1.2) x probe rows — what a
  user who does NOT know the match count pays.

Observability: ``--telemetry [DIR]`` / ``--trace`` / ``--diagnose``
activate the shared telemetry session (docs/OBSERVABILITY.md); the
record carries ``schema_version``/``rank`` always, and the session
summary under ``"telemetry"`` only when a session is active (key
present iff telemetry is on — the same presence contract as
``benchmarks.report``). Flagless invocation changes nothing else
about the record or the run.

Outage: when backend init fails or hangs, the record carries
``value: null`` and the bootstrap failure, and the exit code is 1. No
other device stands in for the chip.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

import jax

# Backend-init deadline: jax.devices() can HANG inside PJRT client
# init (a chip another process holds) rather than raise
# "UNAVAILABLE" — bootstrap.call_with_deadline's watchdog
# turns either failure mode into a structured BootstrapError whose
# record lands in the JSON line (the failure-semantics layer that
# generalized this script's round-5 ad-hoc _BackendInitError;
# docs/FAILURE_SEMANTICS.md).
_INIT_TIMEOUT_S = float(os.environ.get("DJTPU_BENCH_INIT_TIMEOUT", 300))
# Overflow escape hatch: the measured join sizes its output from the
# known match count; a drifted generator/selectivity would overflow.
# Instead of dying on an assert, escalate via the shared
# CapacityLadder and RECORD the trail — automation sees the retry in
# the JSON, not a crash.
_AUTO_RETRY = int(os.environ.get("DJTPU_BENCH_AUTO_RETRY", 2))


def _init_devices():
    """The chip's devices, or a BootstrapError: an init that fails or
    hangs, or a default device that is not a TPU."""
    from distributed_join_tpu.parallel.bootstrap import (
        BootstrapError,
        call_with_deadline,
    )

    devs = call_with_deadline(jax.devices, _INIT_TIMEOUT_S,
                              what="backend init")
    if devs[0].platform != "tpu":
        raise BootstrapError(
            f"no TPU: the default device is {devs[0].platform!r}",
            phase="backend init", deadline_s=_INIT_TIMEOUT_S)
    return devs


# The headline protocol; it must not change between rounds.
BUILD_NROWS = 10_000_000
PROBE_NROWS = BUILD_NROWS
SELECTIVITY = 0.3
# Matches at the default (seed, sizes, selectivity): 5,994,493 — probe
# hits are size-biased draws of build keys (~2 matches/hit), scaling
# ~linearly with rows (0.6/row). The output block is sized to matches
# + 25% slack, mirroring the reference's exactly-sized output
# allocation (cudf inner_join); the overflow flag plus the assert
# below still guard the estimate.
EXPECTED_MATCHES = int(0.6 * BUILD_NROWS)
OUT_SLACK = 1.25
ITERS = 8
BASELINE_M_ROWS_PER_SEC_PER_CHIP = 125.0


def main(argv=None) -> int:
    # Backend init (jax.devices()) is the first thing that can fail.
    # Every failure — an outage, an overflow, a code bug — leaves a
    # parseable one-line JSON record and exits 1.
    import argparse

    from distributed_join_tpu import telemetry
    from distributed_join_tpu.benchmarks import (
        add_telemetry_args,
        stamp_record,
    )

    from distributed_join_tpu.benchmarks import add_robustness_args

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--shuffle",
                   choices=["padded", "ragged", "ppermute",
                            "hierarchical"],
                   default="padded",
                   help="shuffle mode of the measured join "
                        "(hierarchical = two-level ICI/DCN over "
                        "--slices; docs/HIERARCHY.md)")
    p.add_argument("--slices", type=int, default=None,
                   help="hierarchical-mesh slice count (must divide "
                        "the device count; needs --shuffle "
                        "hierarchical)")
    p.add_argument("--dcn-codec", choices=["off", "auto", "on"],
                   default="auto",
                   help="cross-slice FoR+bitpack codec knob of "
                        "--shuffle hierarchical")
    add_telemetry_args(p)
    add_robustness_args(p)
    args = p.parse_args(argv)
    from distributed_join_tpu import device

    device.enable_compile_cache()
    telemetry.configure_from_args(args)
    result = None
    try:
        result = _run(args)
        return 0
    except Exception as exc:  # noqa: BLE001 — record, then re-signal
        from distributed_join_tpu.parallel.bootstrap import BootstrapError

        is_outage = isinstance(exc, BootstrapError)
        record = stamp_record({
            "metric": "join throughput",
            "value": None,
            "unit": "M rows/sec/chip",
            "vs_baseline": None,
            "error": f"{type(exc).__name__}: {exc}",
            "bootstrap": exc.record() if is_outage else None,
            "traceback": traceback.format_exc().splitlines()[-3:],
        })
        print(json.dumps(record), flush=True)
        # A hung init thread would block normal interpreter exit; the
        # record is already flushed, so leave hard (after flushing the
        # telemetry files — finally won't run past os._exit).
        # Non-outage failures (overflow, a code bug) DID leave join
        # telemetry behind — exactly the run --diagnose is for — so
        # they get the diagnosis run_guarded's finally would have
        # given them; an outage has nothing to read.
        from distributed_join_tpu.benchmarks import (
            maybe_diagnose,
            maybe_history,
        )

        summ = telemetry.finalize()
        if not is_outage:
            maybe_diagnose(args, summ, record=record)
        # --history gets the failure entry BEFORE the hard exit
        # (os._exit skips the finally below) — a failing headline
        # workload is exactly the trend the store exists to show.
        maybe_history(args, summ, record=record)
        os._exit(1)
    finally:
        from distributed_join_tpu.benchmarks import (
            maybe_diagnose,
            maybe_history,
        )

        summ = telemetry.finalize()
        maybe_diagnose(args, summ, record=result)
        # --history: the headline run feeds the same per-workload
        # store the drivers and the join service write (its identity
        # keys ride the record; telemetry/history.run_entry).
        maybe_history(args, summ, record=result)


def _run(args=None) -> dict:
    from distributed_join_tpu.benchmarks import maybe_chaos_communicator
    from distributed_join_tpu.parallel.communicator import (
        LocalCommunicator,
        TpuCommunicator,
    )
    from distributed_join_tpu.parallel.distributed_join import make_join_step
    from distributed_join_tpu.utils.benchmarking import timed_join_throughput
    from distributed_join_tpu.utils.generators import generate_build_probe_tables

    from distributed_join_tpu import telemetry

    n_dev = len(_init_devices())
    # Rank was env-resolved at configure time; rebind now that the
    # backend is authoritative. --trace: the XLA device profile can
    # only start once the backend is up (the line above).
    telemetry.refresh_rank()
    telemetry.maybe_start_xla_trace()
    shuffle_mode = getattr(args, "shuffle", "padded") or "padded"
    slices = getattr(args, "slices", None)
    if (slices or 1) > 1 and shuffle_mode != "hierarchical":
        raise SystemExit(
            f"--slices {slices} needs --shuffle hierarchical (a "
            "global collective over a multi-slice mesh drags "
            "intra-slice traffic across DCN)")
    if (slices or 1) > 1:
        from distributed_join_tpu.parallel.communicator import (
            HierarchicalTpuCommunicator,
        )

        comm = HierarchicalTpuCommunicator(n_slices=slices,
                                           n_ranks=n_dev)
    else:
        comm = (LocalCommunicator() if n_dev == 1
                else TpuCommunicator(n_ranks=n_dev))
    if args is not None:
        comm = maybe_chaos_communicator(comm, args)

    build, probe = generate_build_probe_tables(
        seed=42,
        build_nrows=BUILD_NROWS,
        probe_nrows=PROBE_NROWS,
        selectivity=SELECTIVITY,
    )
    build, probe = comm.device_put_sharded((build, probe))
    jax.block_until_ready((build, probe))

    from distributed_join_tpu.parallel.distributed_join import (
        DEFAULT_OUT_CAPACITY_FACTOR,
        DEFAULT_SHUFFLE_CAPACITY_FACTOR,
    )
    from distributed_join_tpu.parallel.faults import CapacityLadder

    # --auto-tune: pre-size both measured ladders from this protocol's
    # own history (capacity knobs only — benchmarks.tuned_driver_record
    # documents the driver-path contract). The workload identity keys
    # ride the record so the end-of-run --history entry files under
    # the same signature the lookup used.
    # --sort-mode: the headline bench A/Bs the flat default against
    # the segmented-sort pipeline on real chips (ROOFLINE §9). auto =
    # the shared resolution's verdict at this shape.
    sort_mode = getattr(args, "sort_mode", None) or "flat"
    if sort_mode == "auto":
        from distributed_join_tpu.benchmarks import resolve_sort_mode
        from distributed_join_tpu.parallel.distributed_join import (
            DEFAULT_SHUFFLE_CAPACITY_FACTOR as _DSCF,
        )

        sort_mode = resolve_sort_mode(
            args, n_dev, 1, BUILD_NROWS // max(n_dev, 1),
            PROBE_NROWS // max(n_dev, 1), _DSCF, shuffle_mode,
            n_slices=slices or 1,
            dcn_codec=getattr(args, "dcn_codec", "auto") or "auto")
    workload = {k: v for k, v in {
        "benchmark": "bench",
        "n_ranks": n_dev,
        "build_table_nrows": BUILD_NROWS,
        "probe_table_nrows": PROBE_NROWS,
        "selectivity": SELECTIVITY,
        "shuffle": (shuffle_mode if shuffle_mode != "padded"
                    else None),
        "slices": slices if (slices or 1) > 1 else None,
        "dcn_codec": ((getattr(args, "dcn_codec", "auto") or "auto")
                      if shuffle_mode == "hierarchical" else None),
        "sort_mode": sort_mode if sort_mode != "flat" else None,
        "sort_segments": (getattr(args, "sort_segments", None)
                          if sort_mode != "flat" else None),
    }.items() if v is not None}
    tuned_sizing, tuned_rung, tuned_rec = {}, 0, None
    if args is not None:
        from distributed_join_tpu.benchmarks import (
            resolve_tuner,
            tuned_driver_record,
        )

        tuner = resolve_tuner(args)
        if tuner is not None:
            tuned_sizing, tuned_rung, tuned_rec = tuned_driver_record(
                tuner, workload)

    # Hierarchical mode arms the DCN codec bits on the ladder (the
    # cross-slice tier is a requested codec; a residual overflow must
    # widen bits, not double capacities) — the driver's discipline.
    dcn_bits = None
    if shuffle_mode == "hierarchical":
        from distributed_join_tpu.planning.cost import (
            resolve_dcn_bits,
        )

        dcn_bits = resolve_dcn_bits(
            getattr(args, "dcn_codec", "auto") or "auto",
            None, n_slices=slices or 1)
    join_base = dict(key="key", over_decomposition=1,
                     shuffle=shuffle_mode,
                     dcn_codec=getattr(args, "dcn_codec", "auto")
                     or "auto")
    if sort_mode != "flat":
        join_base["sort_mode"] = sort_mode
        if getattr(args, "sort_segments", None):
            join_base["sort_segments"] = args.sort_segments

    def measure(out_rows_per_rank=None):
        # Overflow escalates instead of crashing (faults.CapacityLadder
        # — the same policy as auto_retry); attempts are returned for
        # the JSON record so a retried headline is never silent.
        # The match-sized variant keeps its exactly-sized output
        # (out_rows_per_rank param wins over tuned history).
        ladder = CapacityLadder(
            shuffle_capacity_factor=tuned_sizing.get(
                "shuffle_capacity_factor",
                DEFAULT_SHUFFLE_CAPACITY_FACTOR),
            out_capacity_factor=tuned_sizing.get(
                "out_capacity_factor", DEFAULT_OUT_CAPACITY_FACTOR),
            out_rows_per_rank=(
                out_rows_per_rank if out_rows_per_rank is not None
                else tuned_sizing.get("out_rows_per_rank")),
            compression_bits=tuned_sizing.get("compression_bits",
                                              dcn_bits),
            base_rung=tuned_rung,
        )
        for attempt in range(_AUTO_RETRY + 1):
            sizing = {k: v for k, v in ladder.sizing().items()
                      if v is not None}
            step = make_join_step(comm, **join_base, **sizing)
            per_join, total, overflow = timed_join_throughput(
                comm, step, build, probe, ITERS
            )
            ladder.note(bool(overflow))
            if not overflow:
                break
            if attempt < _AUTO_RETRY:
                ladder.escalate()
        if total <= 0 or overflow:
            # The escalation trail must still reach the JSON error
            # record main() emits — an opaque assert would lose
            # exactly the history this layer exists to provide. The
            # two causes get distinct diagnoses: zero matches points
            # at the generator, not capacities.
            reason = ("join overflowed after ladder exhaustion"
                      if overflow else
                      "join produced zero matches (generator drift?)")
            raise RuntimeError(
                reason + ": " + json.dumps(
                    {"total": int(total), "overflow": bool(overflow),
                     "retry": ladder.report().as_record()}
                )
            )
        rows_per_sec = (BUILD_NROWS + PROBE_NROWS) / per_join
        return (rows_per_sec / 1e6 / n_dev,
                ladder.report().as_record(), ladder.sizing())

    m_rows_per_chip, retry_match, sizing_match = measure(
        out_rows_per_rank=int(EXPECTED_MATCHES * OUT_SLACK / n_dev)
    )
    # Same join under the flag driver's general capacity contract
    # (distributed_join.DEFAULT_OUT_CAPACITY_FACTOR over probe rows) —
    # no match-count oracle.
    m_rows_contract, retry_contract, _ = measure()

    # --verify-integrity: one untimed digest-verified step after the
    # timed regions (benchmarks.collect_integrity); a wire mismatch
    # raises IntegrityError instead of shipping a headline number
    # computed from corrupt rows.
    integ = None
    if args is not None and getattr(args, "verify_integrity", False):
        from distributed_join_tpu.benchmarks import collect_integrity

        integ = collect_integrity(
            comm, build, probe,
            dict(join_base, out_capacity_factor=3.0),
        )

    # --explain: the headline protocol's resolved plan + roofline
    # prediction for the match-sized measurement's SETTLED ladder rung
    # (an escalated headline must not be graded against the
    # first-rung plan — that would charge the cost model with rung
    # mismatch). Pure host arithmetic after the timed runs.
    explain_rec = None
    if args is not None and getattr(args, "explain", False):
        from distributed_join_tpu import planning
        from distributed_join_tpu.benchmarks import (
            explain_summary,
            write_explain,
        )

        doc = planning.build_plan(
            comm, build, probe, with_metrics=False,
            **join_base, **sizing_match,
        ).explain_record()
        write_explain(args, doc)
        explain_rec = explain_summary(doc)

    # --stage-profile: stage-segmented profiling of the match-sized
    # protocol's settled sizing (untimed side pass after both timed
    # regions; telemetry/stageprof.py).
    stage_rec = None
    if args is not None and getattr(args, "stage_profile", None):
        from distributed_join_tpu.benchmarks import maybe_stage_profile

        stage_rec = maybe_stage_profile(
            args, comm, build, probe,
            dict(join_base, **sizing_match))
    from distributed_join_tpu.benchmarks import stamp_record

    record = stamp_record({
        "metric": "join throughput",
        "value": round(m_rows_per_chip, 3),
        "unit": "M rows/sec/chip",
        "vs_baseline": round(
            m_rows_per_chip / BASELINE_M_ROWS_PER_SEC_PER_CHIP, 4
        ),
        "value_capacity_contract": round(m_rows_contract, 3),
        # workload identity (telemetry/history.WORKLOAD_KEYS) so a
        # --history entry files this run under a stable signature
        **workload,
        "tuned": tuned_rec,
        "out_rows": {
            "match_sized": int(EXPECTED_MATCHES * OUT_SLACK),
            "contract": "out_capacity_factor=1.2 x probe rows",
        },
        "retry": {
            "match_sized": retry_match,
            "capacity_contract": retry_contract,
        },
        "integrity": integ,
        "explain": explain_rec,
        "stage_profile": stage_rec,
    })
    print(json.dumps(record))
    return record


if __name__ == "__main__":
    sys.exit(main())
