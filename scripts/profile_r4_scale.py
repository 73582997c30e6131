"""Round-4: WHERE does the 10M->50M throughput falloff go?

VERDICT r3 next #1: the headline 68 M rows/s/chip at 10M+10M rows
collapses to ~17.6 M (driver contract) / 28.6 M (match-sized output)
at 50M+50M — config 2's scale. This script measures, on the real v5e:

1. the end-to-end local join at N per side across the 2^24 boundary
   (SCALES_M; OUT = 0.75*N, mirroring bench.py's sizing), and
2. the substitution ablation (fake one stage, read its in-program cost
   off the delta — scripts/profile_r3_pipeline.py protocol) at
   ABLATE_AT_M — NOTE this protocol over-attributes at scale (a faked
   sort feeds degenerate data to the data-dependent expand; see the
   results file's ablation_caveat), and
3. lax.sort alone at the merged-operand shapes (2N elements), since
   ROOFLINE.md §6 shows sort cost is run-length, not element, bound.

Writes results/scale_curve_r4.json.

Run: PYTHONPATH=/root/repo:$PYTHONPATH python scripts/profile_r4_scale.py
"""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
from jax import lax

import distributed_join_tpu  # noqa: F401
from distributed_join_tpu.ops import join as J
from distributed_join_tpu.utils.benchmarking import (
    consume_all_columns,
    measure_chained,
)
from distributed_join_tpu.utils.generators import generate_build_probe_tables

# The committed results/scale_curve_r4.json was assembled from several
# runs of this script (the initial [10,20,35,50] curve, the knee
# bisection around 2^24, and the post-fix re-measurement); this
# default reproduces the full curve in one run.
SCALES_M = [10, 13, 16, 20, 35, 50]
ABLATE_AT_M = [20]
OUT_FRac = 0.75


def run_join(n_rows: int, out_rows: int, label: str, iters: int = 4,
             fake_sort=False, fake_compact=False, fake_expand=False):
    import distributed_join_tpu.ops.compact_pallas as C
    import distributed_join_tpu.ops.expand_pallas as E

    orig_sort = lax.sort
    orig_compact = C.stream_compact
    orig_expand = E.expand_gather
    orig_windows = E.build_windows_ok
    if fake_sort or fake_compact or fake_expand:
        # Pin the lax.cond to the kernel expand ONLY in fake-stage
        # variants: a faked upstream stage feeds the window check
        # garbage and would flip the branch, changing what the delta
        # measures. The PLAIN runs keep the real predicate so full_s
        # is the program production runs (review r4).
        E.build_windows_ok = lambda *a, **k: jnp.bool_(True)

    if fake_sort:
        def fsort(operands, dimension=-1, is_stable=True, num_keys=1):
            return tuple(jnp.roll(o, 1) for o in operands)
        J.lax = type(lax)("fakelax")
        for a in dir(lax):
            if not a.startswith("_"):
                try:
                    setattr(J.lax, a, getattr(lax, a))
                except Exception:
                    pass
        J.lax.sort = fsort
    if fake_compact:
        def fcompact(mask, pos, cols, capacity, block=None,
                     interpret=False):
            return [c[:capacity] if c.shape[0] >= capacity
                    else jnp.pad(c, (0, capacity - c.shape[0]))
                    for c in cols]
        C.stream_compact = fcompact
    if fake_expand:
        def fexpand(Sarr, cols, out_capacity, live, interpret=False,
                    lo=None, build_cols=None, **_kw):
            outs = [c[:out_capacity] for c in cols]
            sb = jnp.arange(out_capacity, dtype=jnp.int32)
            if build_cols is not None:
                bouts = [c[:out_capacity] for c in build_cols]
                return outs, sb, sb, bouts
            return outs, sb
        E.expand_gather = fexpand

    try:
        build, probe = generate_build_probe_tables(
            seed=42, build_nrows=n_rows, probe_nrows=n_rows,
            selectivity=0.3)
        jax.block_until_ready((build.columns, probe.columns))

        def jbody(i, b, p):
            bt = type(b)(
                {nm: (c + i.astype(c.dtype) - i.astype(c.dtype)
                      if nm == "key" else c)
                 for nm, c in b.columns.items()}, b.valid)
            res = J.sort_merge_inner_join(bt, p, "key", out_rows)
            return consume_all_columns(res.table) + res.total

        return measure_chained(label, jbody, build, probe, iters=iters)
    finally:
        J.lax = lax
        C.stream_compact = orig_compact
        E.expand_gather = orig_expand
        E.build_windows_ok = orig_windows
        assert lax.sort is orig_sort


def run_sort(n_elems: int, label: str, iters: int = 4):
    k = jnp.arange(n_elems, dtype=jnp.int64) * 2654435761 % (1 << 40)
    t = (jnp.arange(n_elems, dtype=jnp.int32) % 2).astype(jnp.int8)
    v = jnp.arange(n_elems, dtype=jnp.int64)
    jax.block_until_ready((k, t, v))

    def body(i, k, t, v):
        ks, ts, vs = lax.sort(
            (k + i.astype(jnp.int64), t, v), num_keys=1, is_stable=True)
        return ks[0] + vs[-1] + ts[0].astype(jnp.int64)

    return measure_chained(label, body, k, t, v, iters=iters)


def main():
    out = {"scales_m": SCALES_M, "full_s": {}, "sort_s": {},
           "ablation": {}}
    for m in SCALES_M:
        n = m * 1_000_000
        dt = run_join(n, int(n * OUT_FRac), f"full join {m}M+{m}M")
        out["full_s"][str(m)] = dt
        out.setdefault("m_rows_per_s", {})[str(m)] = 2 * n / dt / 1e6
    for m in SCALES_M:
        dt = run_sort(2 * m * 1_000_000,
                      f"lax.sort {2*m}M (i64,i8,i64)")
        out["sort_s"][str(m)] = dt
    for m in ABLATE_AT_M:
        n = m * 1_000_000
        o = int(n * OUT_FRac)
        full = out["full_s"][str(m)]
        nosort = run_join(n, o, f"  {m}M - fake merged sort",
                          fake_sort=True)
        nocomp = run_join(n, o, f"  {m}M - fake stream_compact",
                          fake_compact=True)
        noexp = run_join(n, o, f"  {m}M - fake expand",
                         fake_expand=True)
        out["ablation"][str(m)] = {
            "full_s": full,
            "sort_cost_s": full - nosort,
            "compact_cost_s": full - nocomp,
            "expand_cost_s": full - noexp,
            "residual_s": nosort + nocomp + noexp - 2 * full,
        }
        print(f"{m}M: sort {1e3*(full-nosort):.0f} ms, compact "
              f"{1e3*(full-nocomp):.0f} ms, expand "
              f"{1e3*(full-noexp):.0f} ms, residual "
              f"{1e3*(out['ablation'][str(m)]['residual_s']):.0f} ms",
              flush=True)
    p = pathlib.Path(__file__).resolve().parent.parent / "results" / \
        "scale_curve_r4.json"
    p.write_text(json.dumps(out, indent=2))
    print("wrote", p)


if __name__ == "__main__":
    main()
