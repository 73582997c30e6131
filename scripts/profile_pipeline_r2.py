"""Cumulative-prefix profile of the kernel-path join pipeline at the
headline bench shape (10M x 10M, selectivity 0.3, out 7.5M).

Each timed program runs the pipeline up to stage k and consumes every
live array (sum of bitcasts), with 4 chained dependent iterations.
Differences between consecutive prefixes approximate per-stage cost
(XLA may fuse/DCE differently per prefix — read deltas as estimates).

Run: PYTHONPATH=/root/repo python scripts/profile_pipeline_r2.py
"""

import time

import jax
import jax.numpy as jnp
from jax import lax

import distributed_join_tpu  # noqa: F401
from distributed_join_tpu.ops import join as J
from distributed_join_tpu.ops.compact_pallas import stream_compact
from distributed_join_tpu.ops.expand_pallas import expand_gather
from distributed_join_tpu.ops.scan_pallas import join_scans
from distributed_join_tpu.utils.generators import (
    generate_build_probe_tables,
)

N = 10_000_000
OUT = 7_500_000


def pipeline(build, probe, upto: int, salt):
    nb = build.capacity
    npr = probe.capacity
    n = nb + npr
    bk = build.columns["key"] + salt
    pk = probe.columns["key"] + salt
    sent = J._dtype_sentinel_max(bk.dtype)
    mkey = jnp.concatenate([
        jnp.where(build.valid, bk, sent),
        jnp.where(probe.valid, pk, sent),
    ])
    tag = jnp.concatenate([
        jnp.where(build.valid, jnp.int8(0), jnp.int8(2)),
        jnp.where(probe.valid, jnp.int8(1), jnp.int8(2)),
    ])
    pay = jnp.concatenate([
        build.columns["build_payload"], probe.columns["probe_payload"]
    ])
    live = []
    skey, stag, spay = lax.sort((mkey, tag, pay), num_keys=2)
    live = [skey, stag.astype(jnp.int32), spay]
    if upto >= 2:
        prev = jnp.concatenate([skey[:1], skey[:-1]])
        first = (skey != prev) | (jnp.arange(n, dtype=jnp.int32) == 0)
        sc = join_scans(stag, first)
        live = [skey, spay] + [sc[k] for k in
                               ("cnt", "start_out", "lo_m", "rec_pos",
                                "matched", "mb_pos")]
    if upto >= 3:
        is_rec = (stag == 1) & (sc["cnt"] > 0)
        lanes = [J._to_u64_lane(sc["start_out"]),
                 J._to_u64_lane(skey),
                 J._to_u64_lane(spay),
                 J._to_u64_lane(sc["lo_m"])]
        comp = stream_compact(is_rec, sc["rec_pos"], lanes, OUT)
        kept = jnp.minimum(sc["rec_pos"][-1] + 1, jnp.int32(OUT))
        jj = jnp.arange(OUT, dtype=jnp.int32)
        S = jnp.where(jj < kept, comp[0].astype(jnp.int32),
                      jnp.int32(2**31 - 1))
        lo_rec = jnp.where(jj < kept, comp[1 + 1 + 1].astype(jnp.int32),
                           0)
        live = [skey, spay, S, lo_rec, comp[1], comp[2],
                sc["matched"], sc["mb_pos"]]
    if upto >= 4:
        matched = sc["matched"] != 0
        pack = stream_compact(matched, sc["mb_pos"],
                              [J._to_u64_lane(spay)], nb)
        live = [S, lo_rec, comp[1], comp[2], pack[0]]
    if upto >= 5:
        cols_list = [comp[1], comp[2]]
        rec_outs, start_b, rank, bouts = expand_gather(
            S, cols_list, OUT, jnp.sum(sc["cnt"]), lo=lo_rec,
            build_cols=pack,
        )
        live = [rec_outs[0], rec_outs[1], start_b, rank, bouts[0]]
    acc = jnp.int64(0)
    for a in live:
        if a.dtype == jnp.uint64 or a.dtype == jnp.int64:
            acc += jnp.sum(lax.bitcast_convert_type(a, jnp.int64))
        else:
            acc += jnp.sum(a.astype(jnp.int64))
    return acc


def timed(build, probe, upto):
    def looped(b, p):
        def it(i, acc):
            return acc + pipeline(b, p, upto, (acc % 2).astype(
                b.columns["key"].dtype))
        return lax.fori_loop(0, 4, it, jnp.int64(0))

    f = jax.jit(looped)
    v = int(f(build, probe))
    t0 = time.perf_counter()
    v = int(f(build, probe))
    t1 = time.perf_counter()
    return (t1 - t0) / 4 * 1000


def main():
    build, probe = generate_build_probe_tables(
        seed=42, build_nrows=N, probe_nrows=N, selectivity=0.3,
    )
    jax.block_until_ready((build, probe))
    names = {2: "sort + fused scans", 4: "+ both compacts",
             5: "+ expand/windows"}
    prevt = 0.0
    for k in sorted(names):
        t = timed(build, probe, k)
        print(f"{names[k]:20s} cumulative {t:7.1f} ms   "
              f"delta {t - prevt:7.1f} ms", flush=True)
        prevt = t


if __name__ == "__main__":
    main()
