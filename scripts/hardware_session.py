"""Turnkey hardware-session pack (VERDICT r3 #7).

Point this at a REAL multi-chip TPU slice and it runs, in one command,
every measurement that needs more than one chip:

  1. all-to-all shuffle bandwidth over ICI (GB/s — BASELINE metric 2);
  2. config 2 at spec scale (100M rows, 8 ranks) — padded shuffle;
  3. the shuffle-mode decision: padded vs ragged vs ppermute wall
     clocks on identical data (docs/OVERLAP.md's open question);
  4. config 3 (Zipf alpha=1.5, 100M rows), skew path ON vs naive;
  5. config 4 (TPC-H SF-100 lineitem x orders, out-of-core batches).

Artifacts land in results/hw_<n>chips_*.json plus a paste-ready
results/HARDWARE_SESSION.md table for BASELINE.md.

Usage (real slice):      PYTHONPATH=. python scripts/hardware_session.py
Plumbing check (no TPU): PYTHONPATH=. python scripts/hardware_session.py --smoke

--smoke runs the identical command matrix on the 8-virtual-device CPU
mesh at ~1/100 scale — it validates every flag path end-to-end, not
performance.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
RESULTS = ROOT / "results"


def sh(args, outfile):
    cmd = [sys.executable, "-m"] + args + ["--json-output", str(outfile)]
    print("==", " ".join(cmd), flush=True)
    subprocess.run(cmd, check=True, cwd=ROOT)
    return json.loads(pathlib.Path(outfile).read_text())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-mesh plumbing check at ~1/100 scale")
    ap.add_argument("--n-ranks", type=int, default=None,
                    help="override rank count (default: all devices)")
    args = ap.parse_args()

    smoke = args.smoke
    plat = ["--platform", "cpu", "--n-ranks", "8"] if smoke else (
        ["--n-ranks", str(args.n_ranks)] if args.n_ranks else []
    )
    if smoke:
        n = 8
    elif args.n_ranks:
        n = args.n_ranks
    else:
        # Count devices in a THROWAWAY subprocess: initializing the
        # TPU backend here would hold the device lock for this
        # process's lifetime and every child benchmark would fail to
        # acquire the chips (review r4).
        n = int(subprocess.run(
            [sys.executable, "-c",
             "import jax; print(len(jax.devices()))"],
            capture_output=True, text=True, check=True,
        ).stdout.strip().splitlines()[-1])
    tag = "smoke" if smoke else f"hw_{n}chips"
    rows = 1_000_000 if smoke else 50_000_000   # per side (2 sides = spec 100M)
    rows -= rows % n
    iters = 1 if smoke else 4
    RESULTS.mkdir(exist_ok=True)
    records = {}

    # 1. all-to-all GB/s (the reference's benchmark/all_to_all).
    records["all_to_all"] = sh(
        ["distributed_join_tpu.benchmarks.all_to_all"] + plat +
        ["--iterations", "10"],
        RESULTS / f"{tag}_all_to_all.json")

    # 2. config 2 at spec scale, padded shuffle.
    base = ["distributed_join_tpu.benchmarks.distributed_join"] + plat + [
        "--build-table-nrows", str(rows), "--probe-table-nrows", str(rows),
        "--iterations", str(iters)]
    records["config2_padded"] = sh(
        base, RESULTS / f"{tag}_config2_padded.json")

    # 3. shuffle-mode decision on identical data.
    for mode in ("ragged", "ppermute"):
        records[f"config2_{mode}"] = sh(
            base + ["--shuffle", mode],
            RESULTS / f"{tag}_config2_{mode}.json")

    # 4. config 3: Zipf skew, HH path on vs naive.
    zipf = base + ["--zipf-alpha", "1.5"]
    records["config3_skew"] = sh(
        zipf + ["--skew-threshold", "0.001",
                "--hh-probe-capacity", str(rows),
                "--hh-out-capacity", str(rows)],
        RESULTS / f"{tag}_config3_skew.json")
    # --skew-threshold 0 forces the naive path (round 5's auto-policy
    # would otherwise default the skew machinery ON for --zipf-alpha).
    records["config3_naive"] = sh(
        zipf + ["--skew-threshold", "0",
                "--shuffle-capacity-factor", "8.0"],
        RESULTS / f"{tag}_config3_naive.json")

    # 5. config 4: TPC-H out-of-core (SF-100 real; SF-1 smoke).
    sf = 1 if smoke else 100
    batches = 2 if smoke else 24
    tp = ["distributed_join_tpu.benchmarks.tpch_join",
          "--scale-factor", str(sf), "--host-generator",
          "--batches", str(batches)]
    if smoke:
        tp += ["--platform", "cpu"]
    records["config4_tpch"] = sh(tp, RESULTS / f"{tag}_config4_tpch.json")

    # 6. The BENCH protocol (bench.py's dual-capacity one-line JSON) so
    # a hardware session also produces the driver-comparable headline
    # number (VERDICT r4 weak #7). bench.py sizes its mesh from
    # jax.devices() and refuses anything but a TPU, so --smoke skips it.
    if not smoke:
        records["bench"] = _bench(RESULTS / f"{tag}_bench.json")

    # Paste-ready BASELINE.md rows.
    md = [f"# Hardware session ({tag})", "",
          "| measurement | value | artifact |", "|---|---|---|"]
    a2a = records["all_to_all"]
    md.append(f"| all-to-all off-chip bandwidth | "
              f"{a2a.get('aggregate_offchip_gb_per_sec', '?')} GB/s | "
              f"{tag}_all_to_all.json |")
    for k in ("config2_padded", "config2_ragged", "config2_ppermute",
              "config3_skew", "config3_naive"):
        r = records[k]
        md.append(
            f"| {k} | {r['m_rows_per_sec_per_rank']:.2f} M rows/s/chip "
            f"({r['elapsed_per_join_s']:.3f} s/join, overflow="
            f"{r['overflow']}) | {tag}_{k}.json |")
    r = records["config4_tpch"]
    md.append(f"| config4 TPC-H SF-{sf} | "
              f"{r.get('rows_per_sec', 0) / 1e6:.2f} M rows/s | "
              f"{tag}_config4_tpch.json |")
    b = records.get("bench")
    if b is not None:
        md.append(f"| BENCH protocol (match-sized / contract) | "
                  f"{b.get('value')} / "
                  f"{b.get('value_capacity_contract')} "
                  f"{b.get('unit', '')} | {tag}_bench.json |")
    md.append("")
    md.append("Shuffle-mode decision: compare config2_padded vs _ragged "
              "vs _ppermute elapsed — the fastest mode on real ICI "
              "closes docs/OVERLAP.md's open question.")
    (RESULTS / "HARDWARE_SESSION.md").write_text("\n".join(md) + "\n")
    print(f"\nwrote results/HARDWARE_SESSION.md + {tag}_*.json", flush=True)


def _bench(out_path) -> dict:
    print("== bench.py", flush=True)
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench.py")],
        capture_output=True, text=True, cwd=ROOT,
    )
    json_lines = [ln for ln in p.stdout.splitlines()
                  if ln.strip().startswith("{")]
    if not json_lines:
        raise SystemExit(
            f"bench.py produced no JSON (rc={p.returncode}):\n"
            + p.stderr[-2000:]
        )
    rec = json.loads(json_lines[-1])
    out_path.write_text(json.dumps(rec, indent=2) + "\n")
    if rec.get("value") is None:
        # THIS session exists to capture the number, so a missing
        # value fails the session like every other stage (sh() uses
        # check=True).
        raise SystemExit(
            "bench.py produced an error record instead of a "
            f"measurement: {rec.get('error')}"
        )
    return rec


if __name__ == "__main__":
    main()
