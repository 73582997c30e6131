"""Piecewise timing of the single-chip join path on the real device.

Times each stage of ops/join.py's merged-sort core in isolation so the
optimization target is measured, not guessed (VERDICT round 1, weak #1:
"no profile exists to even localize the time").

Uses the chained-fori_loop protocol from utils/benchmarking.py: each
primitive is run ITERS dependent times inside one compiled loop,
perturbed by the loop counter, reduced to one scalar.

Run: PYTHONPATH=/root/repo:$PYTHONPATH python scripts/profile_join.py
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

import distributed_join_tpu  # noqa: F401
from distributed_join_tpu.utils.benchmarking import (  # noqa: E402
    measure_chained as timeit,
)
from distributed_join_tpu.ops.join import sort_merge_inner_join
from distributed_join_tpu.table import Table
from distributed_join_tpu.utils.generators import generate_build_probe_tables

N = 10_000_000
OUT_CAP = 7_500_000


def main():
    build, probe = generate_build_probe_tables(
        seed=42, build_nrows=N, probe_nrows=N, selectivity=0.3
    )
    bk = build.columns["key"]
    pk = probe.columns["key"]
    n = 2 * N
    key64 = jnp.concatenate([bk, pk])
    key32 = (key64 & 0xFFFFFFFF).astype(jnp.uint32)
    tag = jnp.concatenate(
        [jnp.zeros((N,), jnp.int8), jnp.ones((N,), jnp.int8)]
    )
    idx = jnp.arange(n, dtype=jnp.int32)
    perm = jax.random.permutation(jax.random.PRNGKey(0), n).astype(jnp.int32)
    sl = perm[:OUT_CAP] % N
    jax.block_until_ready((key64, key32, tag, idx, perm, sl))

    timeit("sort 20M (i64 key, i8 tag, i32 idx)",
           lambda i, a, t, x: lax.sort((a + i, t, x), num_keys=2)[2][0],
           key64, tag, idx)
    timeit("sort 20M (i64+i8 two keys, i32 idx)",
           lambda i, a, t, x: lax.sort((a + i, t, x), num_keys=2)[2][0],
           key64, tag, idx)
    timeit("sort 20M (u32 key, i8 tag, i32 idx)",
           lambda i, a, t, x: lax.sort(
               (a + i.astype(jnp.uint32), t, x), num_keys=2)[2][0],
           key32, tag, idx)
    timeit("sort 20M (u32 key, i32 idx)",
           lambda i, a, x: lax.sort(
               (a + i.astype(jnp.uint32), x), num_keys=1)[1][0],
           key32, idx)
    timeit("sort 20M (i64 key alone)",
           lambda i, a: lax.sort((a + i,), num_keys=1)[0][0], key64)
    timeit("sort 10M (i64, i8, i32)",
           lambda i, a, t, x: lax.sort(
               (a[:N] + i, t[:N], x[:N]), num_keys=2)[2][0],
           key64, tag, idx)
    timeit("cumsum 20M i32",
           lambda i, x: jnp.cumsum(x + i)[-1], idx)
    timeit("cummax 20M i32",
           lambda i, x: lax.cummax(x + i)[-1], idx)
    timeit("scatter-max 20M->7.5M",
           lambda i, s, v: jnp.zeros((OUT_CAP,), jnp.int32)
           .at[(s + i) % OUT_CAP].max(v, mode="drop")[0],
           perm, idx)
    timeit("gather 7.5M from 10M (i64 col)",
           lambda i, c, s: c[(s + i) % N][0], bk, sl)
    timeit("gather 7.5M from 10M (i32 col)",
           lambda i, c, s: c[(s + i) % N][0], idx[:N], sl)
    timeit("gather 20M from 20M (i64, random idx)",
           lambda i, c, s: c[(s + i) % n][0], key64, perm)

    def full(i, b, p):
        bcols = dict(b.columns)
        bcols["key"] = bcols["key"] + i
        pcols = dict(p.columns)
        pcols["key"] = pcols["key"] + i
        res = sort_merge_inner_join(
            Table(bcols, b.valid), Table(pcols, p.valid), "key", OUT_CAP
        )
        return res.total + jnp.sum(
            jnp.where(res.table.valid,
                      res.table.columns["probe_payload"], 0)
        ).astype(jnp.int64)

    timeit("sort_merge_inner_join full", full, build, probe)


if __name__ == "__main__":
    main()
