"""Chip smoke: the one-shot join and the served join on a real TPU.

Runs, in ONE process (a chip belongs to one process at a time):

1. device check — the default device must be a TPU; no CPU branch;
2. BASELINE config 1 at spec size — a 10M x 10M uniform int64 join
   (seed 42, selectivity 0.3, the ``bench.py`` protocol) through
   ``distributed_inner_join`` on ``make_communicator("tpu", n_ranks=1)``,
   graded against a numpy oracle (match count + an order-independent
   checksum of every output column), ``overflow`` false;
3. kernel check — the compiled join step holds ``tpu_custom_call``
   (the Pallas pipeline ran, not the XLA fallback);
4. served path — a ``JoinService`` daemon on a thread of this process
   answers a cold join, a warm repeat (``new_traces: 0``) and a join
   of another signature over the line-JSON wire, each graded.

``--chips 4`` runs only the multi-chip path: config 2's per-rank shape
(50M x 50M over a 4-rank mesh) with the padded and the ragged shuffle,
each graded against the oracle and against the other.

The last stdout line is ``{"ok": true, "device": {...}}``; any failed
phase exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

SEED = 42
SELECTIVITY = 0.3
MAIN_ROWS = 10_000_000          # BASELINE config 1, per side
SERVED_ROWS = 1_000_000
MULTI_ROWS = 50_000_000         # config 2's per-rank shape x 4 ranks
MULTI_RANKS = 4
AUTO_RETRY = 2


class PhaseFailed(RuntimeError):
    pass


def check(ok: bool, phase: str, reason: str) -> None:
    if not ok:
        raise PhaseFailed(f"{phase}: {reason}")


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


# -- host oracle (numpy only; none of the package's ops) --------------


def _mix(v):
    import numpy as np

    x = v.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    return x ^ (x >> np.uint64(29))


def _wsum(weights, values) -> int:
    import numpy as np

    return int(np.sum(weights.astype(np.uint64) * _mix(values),
                      dtype=np.uint64))


def _counts_in(keys, other):
    """For each entry of ``keys``: how many rows of ``other`` hold it."""
    import numpy as np

    uniq, cnt = np.unique(other, return_counts=True)
    idx = np.searchsorted(uniq, keys)
    idx_c = np.minimum(idx, len(uniq) - 1)
    hit = (idx < len(uniq)) & (uniq[idx_c] == keys)
    return np.where(hit, cnt[idx_c], 0)


def host_table(t) -> dict:
    import numpy as np

    valid = np.asarray(t.valid)
    return {nm: np.asarray(c)[valid] for nm, c in t.columns.items()}


def oracle(build: dict, probe: dict) -> dict:
    """Inner join of ``build`` and ``probe`` on ``key``, as a match count
    and one order-independent checksum per output column, computed from
    per-key multiplicities without materializing the join."""
    import numpy as np

    cb = _counts_in(probe["key"], build["key"])   # matches per probe row
    cp = _counts_in(build["key"], probe["key"])   # matches per build row
    return {
        "matches": int(np.sum(cb, dtype=np.int64)),
        "key": _wsum(cb, probe["key"]),
        "build_payload": _wsum(cp, build["build_payload"]),
        "probe_payload": _wsum(cb, probe["probe_payload"]),
    }


def answer(res) -> dict:
    """The same digest of what the system returned."""
    import numpy as np

    out = host_table(res.table)
    one = np.ones(len(out["key"]), np.uint64)
    return {"matches": int(res.total),
            **{nm: _wsum(one, out[nm])
               for nm in ("key", "build_payload", "probe_payload")}}


# -- phases -----------------------------------------------------------


def device_check():
    import jax

    from distributed_join_tpu import device

    cache_dir = device.enable_compile_cache()
    devs = jax.devices()
    say(phase="device", jax=jax.__version__, platform=devs[0].platform,
        device_kind=devs[0].device_kind, count=len(devs),
        compile_cache_dir=cache_dir)
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: the default device is "
            f"{devs[0].platform!r} ({devs[0].device_kind})")
    check(device.on_tpu(), "device", "device.on_tpu() is False on a TPU")
    return devs


def make_tables(rows: int, seed: int = SEED):
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    return generate_build_probe_tables(
        seed=seed, build_nrows=rows, probe_nrows=rows,
        selectivity=SELECTIVITY)


def run_join(comm, build, probe, phase: str, **opts):
    """One production call, cold then warm, through a program cache;
    returns (result, compiled program text, record)."""
    import jax

    from distributed_join_tpu.parallel.distributed_join import (
        distributed_inner_join,
        resolve_join_ladder,
    )
    from distributed_join_tpu.service.programs import JoinProgramCache

    cache = JoinProgramCache(comm)
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = distributed_inner_join(build, probe, comm, key="key",
                                     auto_retry=AUTO_RETRY,
                                     program_cache=cache, **opts)
        jax.block_until_ready(res)
        walls.append(time.perf_counter() - t0)
    n_att = res.retry_report.n_attempts
    check(cache.traces == n_att, phase,
          f"warm call traced again ({cache.traces} traces for "
          f"{n_att} ladder attempt(s))")
    # Re-resolve the settled rung to fetch its program from the cache
    # (a hit proves the sizing is the one that ran), then lower it
    # again for its text; jit's caches make that a lookup.
    ladder = resolve_join_ladder(build, probe, comm.n_ranks, dict(opts))
    for _ in range(n_att - 1):
        ladder.escalate()
    entry, hit = cache.get(
        build, probe, key="key",
        metrics_static={"retry_attempt_max": ladder.base_rung + n_att - 1},
        **ladder.sizing(), **opts)
    check(hit, phase, "settled program not found in the program cache")
    t0 = time.perf_counter()
    compiled = entry.raw.lower(build, probe).compile()
    recompile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    rec = {"cold_wall_s": walls[0], "warm_wall_s": walls[1],
           "recompile_s": recompile, "ladder_attempts": n_att,
           "overflow": bool(res.overflow)}
    if mem is not None:
        rec["memory"] = {k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")}
    return res, compiled.as_text(), rec


def grade(phase: str, got: dict, want: dict) -> None:
    check(got == want, phase, f"answer {got} != oracle {want}")


def main_phase():
    import jax

    from distributed_join_tpu import make_communicator

    comm = make_communicator("tpu", n_ranks=1)
    build, probe = make_tables(MAIN_ROWS)
    build, probe = comm.device_put_sharded((build, probe))
    jax.block_until_ready((build, probe))
    want = oracle(host_table(build), host_table(probe))
    res, text, rec = run_join(comm, build, probe, "main")
    check(not rec["overflow"], "main", "overflow is set")
    got = answer(res)
    grade("main", got, want)
    kernels = text.count("tpu_custom_call")
    say(phase="main", rows=[MAIN_ROWS, MAIN_ROWS], oracle=want,
        tpu_custom_calls=kernels, **rec)
    check(kernels > 0, "kernel",
          "the compiled join step holds no tpu_custom_call: the XLA "
          "fallback ran, not the Pallas pipeline")
    say(phase="kernel", tpu_custom_calls=kernels, ok=True)
    return comm


def served_phase(comm):
    from distributed_join_tpu.service.server import (
        JoinService,
        ServiceClient,
        start_daemon,
    )
    from distributed_join_tpu.utils.generators import (
        generate_build_probe_tables,
    )

    requests = [
        ("cold", {"seed": SEED, "build_nrows": SERVED_ROWS,
                  "probe_nrows": SERVED_ROWS}),
        ("warm", {"seed": SEED, "build_nrows": SERVED_ROWS,
                  "probe_nrows": SERVED_ROWS}),
        ("other_signature", {"seed": 7, "build_nrows": SERVED_ROWS,
                             "probe_nrows": SERVED_ROWS // 2}),
    ]
    service = JoinService(comm)
    server, port = start_daemon(service, "127.0.0.1", 0)
    client = ServiceClient("127.0.0.1", port)
    try:
        for what, spec in requests:
            b, p = generate_build_probe_tables(
                seed=spec["seed"], build_nrows=spec["build_nrows"],
                probe_nrows=spec["probe_nrows"],
                selectivity=SELECTIVITY)
            want = oracle(host_table(b), host_table(p))["matches"]
            resp = client.send({"op": "join", "selectivity": SELECTIVITY,
                                **spec})
            check(bool(resp.get("ok")), "served", f"{what}: {resp}")
            say(phase="served", request=what, matches=resp["matches"],
                oracle_matches=want, new_traces=resp["new_traces"],
                overflow=resp["overflow"], elapsed_s=resp["elapsed_s"])
            check(resp["matches"] == want and not resp["overflow"],
                  "served", f"{what}: {resp['matches']} matches, "
                  f"oracle {want}, overflow {resp['overflow']}")
            if what == "warm":
                check(resp["new_traces"] == 0, "served",
                      f"warm repeat traced {resp['new_traces']} programs")
            elif resp["new_traces"] == 0:
                raise PhaseFailed(f"served: {what} request traced nothing")
    finally:
        client.close()
        server.shutdown()
        server.server_close()


def multi_chip_phase():
    import jax

    from distributed_join_tpu.parallel.communicator import TpuCommunicator

    check(len(jax.devices()) >= MULTI_RANKS, "mesh",
          f"{len(jax.devices())} devices, need {MULTI_RANKS}")
    comm = TpuCommunicator(n_ranks=MULTI_RANKS)
    mesh_ids = {d.id for d in comm.mesh.devices.flat}
    check(len(mesh_ids) == MULTI_RANKS, "mesh",
          f"the mesh spans devices {sorted(mesh_ids)}")
    build, probe = make_tables(MULTI_ROWS)
    build, probe = comm.device_put_sharded((build, probe))
    jax.block_until_ready((build, probe))
    want = oracle(host_table(build), host_table(probe))
    answers = {}
    for shuffle in ("padded", "ragged"):
        res, text, rec = run_join(comm, build, probe, shuffle,
                                  shuffle=shuffle)
        shard_devs = {s.device.id for s in
                      res.table.columns["key"].addressable_shards}
        check(shard_devs == mesh_ids, shuffle,
              f"result shards live on devices {sorted(shard_devs)}")
        check(not rec["overflow"], shuffle, "overflow is set")
        answers[shuffle] = answer(res)
        grade(shuffle, answers[shuffle], want)
        ragged_ops = text.count("ragged-all-to-all")
        say(phase=f"multi_chip_{shuffle}", rows=[MULTI_ROWS, MULTI_ROWS],
            n_ranks=MULTI_RANKS, oracle=want,
            ragged_all_to_all_ops=ragged_ops,
            all_to_all_ops=text.count("all-to-all") - ragged_ops, **rec)
        if shuffle == "ragged":
            check(ragged_ops > 0, shuffle,
                  "the ragged program holds no ragged-all-to-all")
        del res
    check(answers["padded"] == answers["ragged"], "multi_chip",
          "padded and ragged answers differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MULTI_RANKS),
                    default=1,
                    help=f"{MULTI_RANKS}: run only the multi-chip path")
    args = ap.parse_args(argv)
    devs = device_check()
    try:
        if args.chips == MULTI_RANKS:
            multi_chip_phase()
        else:
            comm = main_phase()
            served_phase(comm)
    except PhaseFailed as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
