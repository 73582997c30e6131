"""Round-3 substitution ablation of the KERNEL-path join at the bench
shape: replace one stage at a time with a shape-preserving fake and
read each stage's true in-program cost off the deltas.

Stages: merged sort | join_scans | record compact | build-pack compact
| expand(+build windows).

Run: PYTHONPATH=/root/repo:$PYTHONPATH python scripts/profile_r3_pipeline.py
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

import distributed_join_tpu  # noqa: F401
from distributed_join_tpu.ops import join as J
from distributed_join_tpu.utils.benchmarking import measure_chained
from distributed_join_tpu.utils.generators import generate_build_probe_tables

N = 10_000_000
OUT = 7_500_000


def run_variant(name, fake_sort=False, fake_scans=False,
                fake_compact=False, fake_expand=False):
    """Monkeypatch one stage of the kernel path with a cheap fake and
    time the full join. The fakes keep shapes/dtypes identical so the
    rest of the program is unchanged."""
    import distributed_join_tpu.ops.compact_pallas as C
    import distributed_join_tpu.ops.expand_pallas as E
    import distributed_join_tpu.ops.scan_pallas as S

    orig_sort = lax.sort
    orig_scans = S.join_scans
    orig_compact = C.stream_compact
    orig_expand = E.expand_gather
    orig_windows = E.build_windows_ok

    # Pin the lax.cond branch to the kernel expand in EVERY variant:
    # with a faked upstream stage the window check would see garbage
    # and flip to the XLA-gather fallback, changing what is measured.
    E.build_windows_ok = lambda *a, **k: jnp.bool_(True)

    if fake_sort:
        def fsort(operands, dimension=-1, is_stable=True, num_keys=1):
            # roll instead of sort: same shapes, trivially cheap
            return tuple(jnp.roll(o, 1) for o in operands)
        J.lax = type(lax)("fakelax")
        for a in dir(lax):
            if not a.startswith("_"):
                try:
                    setattr(J.lax, a, getattr(lax, a))
                except Exception:
                    pass
        J.lax.sort = fsort
    if fake_scans:
        def fscans(stag, first, interpret=False):
            n = stag.shape[0]
            z = jnp.zeros((n,), jnp.int32)
            io = jnp.arange(n, dtype=jnp.int32)
            return {"cnt": z + (stag == 1).astype(jnp.int32),
                    "start_out": io, "lo_m": z, "rec_pos": io,
                    "matched": (stag == 0).astype(jnp.int32),
                    "mb_pos": io}
        S.join_scans = fscans
        J.__dict__  # keep flake quiet
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
            seed=42, build_nrows=N, probe_nrows=N, selectivity=0.3)
        jax.block_until_ready((build.columns, probe.columns))
        from distributed_join_tpu.utils.benchmarking import (
            consume_all_columns,
        )

        def jbody(i, b, p):
            bt = type(b)(
                {nm: (c + i.astype(c.dtype) - i.astype(c.dtype)
                      if nm == "key" else c)
                 for nm, c in b.columns.items()}, b.valid)
            res = J.sort_merge_inner_join(bt, p, "key", OUT)
            return consume_all_columns(res.table) + res.total

        return measure_chained(name, jbody, build, probe)
    finally:
        J.lax = lax
        S.join_scans = orig_scans
        C.stream_compact = orig_compact
        E.expand_gather = orig_expand
        E.build_windows_ok = orig_windows
        assert lax.sort is orig_sort


def main():
    full = run_variant("full join (kernel path)")
    nosort = run_variant("  - fake merged sort", fake_sort=True)
    noscan = run_variant("  - fake join_scans", fake_scans=True)
    nocomp = run_variant("  - fake stream_compact x2", fake_compact=True)
    noexp = run_variant("  - fake expand_gather", fake_expand=True)
    print(f"sort cost     ~ {1e3 * (full - nosort):7.1f} ms")
    print(f"scans cost    ~ {1e3 * (full - noscan):7.1f} ms")
    print(f"compact cost  ~ {1e3 * (full - nocomp):7.1f} ms")
    print(f"expand cost   ~ {1e3 * (full - noexp):7.1f} ms")


if __name__ == "__main__":
    main()
