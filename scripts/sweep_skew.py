"""Config-3 skew-path characterization (VERDICT r3 #5).

Two measurements:

1. ON-CHIP (1 rank): the heavy-hitter machinery's IN-JOIN cost —
   detection (sort+top_k+fori passes) + the extra HH join block —
   swept over skew_threshold / hh_slots at Zipf alpha in {1.1, 1.5}
   and uniform keys (the overhead paid when no skew exists).
2. CPU 8-device mesh: the MEMORY win — the minimum
   shuffle_capacity_factor at which each mode (naive padded vs skew)
   first completes without overflow at Zipf 1.5. The skew path's
   purpose is relieving the one-hot-bucket-pads-everyone blowup
   (SURVEY.md §7 hard part #2); this sweep quantifies it.

Writes results/config3_sweep_skew.json.

Run: PYTHONPATH=/root/repo:$PYTHONPATH python scripts/sweep_skew.py
(on the chip for part 1; rerun with --platform cpu for part 2)
"""

from __future__ import annotations

import argparse
import json

from distributed_join_tpu.benchmarks import add_platform_arg, apply_platform


def on_chip_overhead(report):
    import jax

    import distributed_join_tpu as dj
    from distributed_join_tpu.parallel.distributed_join import (
        make_join_step,
    )
    from distributed_join_tpu.utils.benchmarking import (
        consume_all_columns,
        measure_chained,
    )
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
        generate_zipf_probe_table,
    )

    comm = dj.make_communicator("local")
    rows = 10_000_000
    build, _ = generate_build_probe_tables(
        seed=31, build_nrows=rows, probe_nrows=1, rand_max=rows,
        unique_build_keys=True,
    )
    cases = {"uniform": None, "zipf1.1": 1.1, "zipf1.5": 1.5}
    out = {}
    for nm, alpha in cases.items():
        if alpha is None:
            # selectivity=0: pure uniform draws over [0, rand_max) —
            # matches come from natural collisions with the unique
            # build keys. (selectivity=0.5 with this 1-row generator
            # build made HALF the probe share ONE key: the r3 sweep's
            # "uniform" case was secretly a 50%-mass heavy hitter,
            # discovered when the honest overflow flag fired on it.)
            _, probe = generate_build_probe_tables(
                seed=32, build_nrows=1, probe_nrows=rows,
                rand_max=rows, selectivity=0.0,
            )
        else:
            probe = generate_zipf_probe_table(
                jax.random.PRNGKey(33), nrows=rows, alpha=alpha,
                rand_max=rows,
            )
        jax.block_until_ready((build.columns, probe.columns))
        entry = {}
        for label, opts in {
            "naive": {},
            # DEFAULT capacities (hh_probe=p/8, hh_out=p/4): the cost a
            # user pays for leaving skew handling on — the r4 target
            # (<=20% at uniform; results/skew_overhead_uniform_r4.json)
            "skew_default_caps": {"skew_threshold": 0.001,
                                  "hh_slots": 64, "_default_caps": True},
            "skew_t0.001_s64": {"skew_threshold": 0.001, "hh_slots": 64},
            "skew_t0.001_s256": {"skew_threshold": 0.001,
                                 "hh_slots": 256},
            "skew_t0.01_s64": {"skew_threshold": 0.01, "hh_slots": 64},
        }.items():
            opts = dict(opts)
            caps = {} if opts.pop("_default_caps", False) else {
                "hh_probe_capacity": int(rows * 1.1),
                "hh_out_capacity": int(rows * 1.2),
            }
            step = make_join_step(
                comm, key="key", out_rows_per_rank=int(rows * 1.4),
                **caps, **opts,
            )

            def body(i, b, p):
                bt = type(b)(
                    {k: (c + i.astype(c.dtype) - i.astype(c.dtype)
                         if k == "key" else c)
                     for k, c in b.columns.items()}, b.valid)
                res = step(bt, p)
                return consume_all_columns(res.table) + res.total

            sec = measure_chained(f"{nm}/{label}", body, build, probe)
            entry[label] = round(sec * 1e3, 1)
            # Default caps MAY overflow under heavy Zipf (the HH block
            # is probe/8; auto_retry's jump-to-full-probe is the
            # documented remedy) — record the flag so the table reads
            # honestly, but only where it is informative: the explicit
            # fat-caps labels never overflow, and the check costs an
            # extra compile+run of the 10M join (review r4). (jit: an
            # eager 10M join would run op-by-op.)
            if label in ("naive", "skew_default_caps"):
                entry[label + "_overflow"] = bool(jax.jit(
                    lambda b, p: step(b, p).overflow)(build, probe))
        out[nm] = entry
    report["on_chip_ms_per_join_10M"] = out


def mesh_capacity_crossover(report):
    import jax

    import distributed_join_tpu as dj
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
        generate_zipf_probe_table,
    )

    comm = dj.make_communicator("tpu", n_ranks=8)
    rows = 262144
    build, _ = generate_build_probe_tables(
        seed=41, build_nrows=rows, probe_nrows=1, rand_max=rows,
        unique_build_keys=True,
    )
    probe = generate_zipf_probe_table(
        jax.random.PRNGKey(42), nrows=rows, alpha=1.5, rand_max=rows
    )
    want = len(build.to_pandas().merge(probe.to_pandas(), on="key"))

    factors = [1.0, 1.5, 2.0, 3.0, 4.0, 6.0, 9.0, 13.0, 20.0]
    out = {"rows": rows, "alpha": 1.5, "oracle_matches": want}
    for label, opts in {
        "naive": {},
        "skew_t0.002_s128": {"skew_threshold": 0.002, "hh_slots": 128,
                             "hh_probe_capacity": rows,
                             "hh_out_capacity": rows * 2},
    }.items():
        min_ok = None
        for f in factors:
            res = dj.distributed_inner_join(
                build, probe, comm, shuffle_capacity_factor=f,
                out_capacity_factor=3.0, **opts,
            )
            ok = (not bool(res.overflow)) and int(res.total) == want
            if ok:
                min_ok = f
                break
        out[label] = {"min_shuffle_capacity_factor": min_ok}
        print(label, "min factor:", min_ok, flush=True)
    report["mesh_8dev_zipf15_capacity"] = out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--skip-chip", action="store_true")
    p.add_argument("--skip-mesh", action="store_true")
    add_platform_arg(p)
    args = p.parse_args()

    report = {}
    path = "results/config3_sweep_skew.json"
    try:
        with open(path) as f:
            report = json.load(f)
    except FileNotFoundError:
        pass

    if args.platform == "cpu":
        apply_platform("cpu", 8)
        if not args.skip_mesh:
            mesh_capacity_crossover(report)
    else:
        if not args.skip_chip:
            on_chip_overhead(report)

    print(json.dumps(report, indent=2))
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


if __name__ == "__main__":
    main()
