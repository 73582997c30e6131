"""All-to-all shuffle microbenchmark — mirrors the reference's
``benchmark/all_to_all`` executable (SURVEY.md §3.2).

The reference allocates fixed-size send/recv buffers per peer, loops
``comm->send/recv`` to all peers + waitall, and reports GB/s — isolating
the communication layer entirely. Here the isolated layer is the
``Communicator.all_to_all`` collective (XLA ``AllToAll`` over ICI on a
real slice; the host-platform emulation on the CPU fake backend), timed
with the chained-loop protocol so per-call dispatch latency doesn't
pollute the number.

Bandwidth definition: per-rank egress — each rank sends
``(n_ranks - 1) / n_ranks`` of its buffer off-chip per iteration (the
diagonal block stays local), and we report aggregate off-chip GB/s =
``n_ranks * egress_bytes / t``. The reference's count-everything variant
(as if the local copy were traffic) is also printed for comparability.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax import lax

from distributed_join_tpu.benchmarks import (
    add_platform_arg,
    add_robustness_args,
    add_telemetry_args,
    apply_platform,
    maybe_chaos_communicator,
    report,
)
from distributed_join_tpu.parallel.communicator import make_communicator
from distributed_join_tpu.utils.benchmarking import measure


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--buffer-size", type=int, default=64 * 1024 * 1024,
                   help="bytes in each rank's send buffer (split across "
                        "peers), reference-style fixed-size exchange")
    p.add_argument("--communicator", default="tpu")
    p.add_argument("--n-ranks", type=int, default=None)
    p.add_argument("--iterations", type=int, default=20,
                   help="chained exchanges in the timed compiled loop")
    p.add_argument("--json-output", default=None)
    add_platform_arg(p)
    add_telemetry_args(p)
    add_robustness_args(p)
    return p.parse_args(argv)


def _verified_exchange(comm, x, n: int, per_rank: int):
    """One digest-verified exchange of the benchmark buffer (untimed,
    after the timed loop): per-(src,dst) digests of the sent and
    received blocks ride one step-end all_gather on the MetricsTape,
    exactly the join shuffles' integrity channel
    (parallel/integrity.py) applied to the raw microbenchmark wire.
    Raises IntegrityError on any pair mismatch."""
    import jax.numpy as jnp

    from distributed_join_tpu.parallel import integrity
    from distributed_join_tpu.telemetry import MetricsTape

    # Chaos smoke: the timed loop's trace spent the corruption budget;
    # rearm so THIS trace faces the same schedule (the same hazard
    # benchmarks.collect_integrity guards against).
    rearm = getattr(comm, "rearm_corruption", None)
    if rearm is not None:
        rearm()

    def exchange(buf):
        buf = buf.reshape(n, per_rank)
        full = jnp.full((n,), per_rank, jnp.int32)
        sent = integrity.padded_block_digests({"buf": buf}, full)
        recv_buf = comm.all_to_all(buf)
        recv = integrity.padded_block_digests({"buf": recv_buf}, full)
        t = MetricsTape()
        integrity.record_pair_digests(
            t.scoped("wire.integrity"), sent, recv)
        return t.gathered(comm)

    metrics = comm.spmd(exchange, sharded_out=True)(x)
    rep = integrity.verify_digests(metrics)
    if not rep.ok:
        raise integrity.IntegrityError(rep)
    return rep.as_record()


def run(args) -> dict:
    if args.auto_tune is not None:
        # One fixed-size exchange has no join knobs to tune.
        raise SystemExit(
            "--auto-tune applies to the join drivers; the all_to_all "
            "microbenchmark has no capacity contract to pre-size")
    if getattr(args, "stage_profile", None):
        raise SystemExit(
            "--stage-profile needs the multi-stage join pipeline; "
            "this microbenchmark IS one shuffle stage — its timed "
            "wall already answers per-stage timing")
    if getattr(args, "sort_mode", None) not in (None, "flat"):
        raise SystemExit(
            "--sort-mode selects the join's LOCAL sort pipeline; "
            "this microbenchmark has no local sort")
    apply_platform(args.platform, args.n_ranks)
    comm = maybe_chaos_communicator(
        make_communicator(args.communicator, n_ranks=args.n_ranks),
        args,
    )
    n = comm.n_ranks
    if n < 2:
        raise SystemExit(
            "all_to_all needs >= 2 ranks (on one real chip, force the CPU "
            "fake backend: XLA_FLAGS=--xla_force_host_platform_device_count=8"
            " with jax.config jax_platforms=cpu)"
        )
    elems = args.buffer_size // 4  # float32 lanes
    elems -= elems % n
    per_rank = elems // n

    x = jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems)
    x = comm.device_put_sharded(x)
    jax.block_until_ready(x)
    iters = args.iterations

    def looped(x):
        x = x.reshape(n, per_rank)

        def body(i, carry):
            # The +i makes each exchange depend on the loop counter and
            # the previous result, so XLA cannot collapse the chain.
            return comm.all_to_all(carry + jnp.float32(1)) + i
        y = lax.fori_loop(0, iters, body, x)
        return comm.psum(jnp.sum(y))

    fn = comm.spmd(looped, sharded_out=True)

    state = {}

    def fetch(res):
        state["checksum"] = float(res)

    sec = measure(lambda: fn(x), fetch, iters, name="all_to_all")

    # --verify-integrity: one untimed digest-verified exchange of the
    # same buffer — the timed loop above stays the seed program.
    integ = None
    if args.verify_integrity:
        integ = _verified_exchange(comm, x, n, per_rank)

    bytes_per_rank = elems * 4
    egress = bytes_per_rank * (n - 1) / n

    # --explain: the microbenchmark's reduced plan — one fixed-size
    # exchange's exact wire bytes + the spec-derived ICI prediction
    # (planning.build_exchange_plan; no join pipeline here).
    explain_rec = None
    if args.explain:
        from distributed_join_tpu import planning
        from distributed_join_tpu.benchmarks import (
            explain_summary,
            write_explain,
        )

        doc = planning.build_exchange_plan(n, bytes_per_rank)
        write_explain(args, doc)
        explain_rec = explain_summary(doc)

    record = {
        "benchmark": "all_to_all",
        "communicator": comm.name,
        "n_ranks": n,
        "buffer_bytes_per_rank": bytes_per_rank,
        "integrity": integ,
        "explain": explain_rec,
        "chaos_seed": args.chaos_seed,
        "elapsed_per_exchange_s": sec,
        "aggregate_offchip_gb_per_sec": n * egress / sec / 1e9,
        "aggregate_gb_per_sec_incl_local": n * bytes_per_rank / sec / 1e9,
    }
    report(
        f"all-to-all: {n} ranks x {bytes_per_rank / 1e6:.1f} MB in "
        f"{sec * 1e3:.3f} ms -> "
        f"{record['aggregate_offchip_gb_per_sec']:.2f} GB/s off-chip "
        f"({record['aggregate_gb_per_sec_incl_local']:.2f} GB/s incl. "
        f"local block)",
        record, args.json_output,
    )
    return record


def main(argv=None):
    from distributed_join_tpu.benchmarks import run_guarded

    return run_guarded(run, parse_args(argv), benchmark="all_to_all")


if __name__ == "__main__":
    main()
