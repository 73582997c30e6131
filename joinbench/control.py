"""The control of the comparison that decides ``correct``.

    python3 -m joinbench.control --workload <cell> --seconds <s> --seeds <n> [<n> ...]

The configurations state an exact equi-join. The control breaks that
guarantee the way a faster join would be tempted to: the plain
reference, put in the program's place, matches rows on a 32-bit
fingerprint of the key instead of the key, so that keys whose
fingerprints collide join too. For each seed the control is a whole run
of the cell (``run.run``) with that join in place of
``distributed_inner_join``: the same tables, calls, window and check as
a run of the program. A comparison that is sound comes out not correct;
each run's result line is printed, ``checks`` last.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import sys

import numpy as np

FINGERPRINT_BITS = 32

Rows = collections.namedtuple("Rows", "columns valid")
Answer = collections.namedtuple("Answer", "table total overflow")


def fingerprint(keys):
    from joinbench import oracle

    u = keys.astype(np.int64).astype(np.uint64)
    shift = np.uint64(64 - FINGERPRINT_BITS)
    return (oracle.splitmix(u, np) >> shift).astype(np.int64)


def fingerprint_join(cfg: dict):
    """A join with the program's signature that pairs every valid build
    row with every valid probe row of equal key fingerprint, on the host,
    and hands the rows back on the device."""
    import jax

    from joinbench import run

    def join(build, probe, comm, *, key, **kw):
        b, p = run.host_side(build), run.host_side(probe)
        fb, fp = fingerprint(b[key]), fingerprint(p[key])
        order = np.argsort(fb, kind="stable")
        lo = np.searchsorted(fb[order], fp, "left")
        n = np.searchsorted(fb[order], fp, "right") - lo
        pi = np.repeat(np.arange(len(fp)), n)
        start = np.repeat(lo - (np.cumsum(n) - n), n)
        bi = order[start + np.arange(len(pi))]
        cols = {key: b[key][bi],
                **{c: b[c][bi] for c in cfg["build_payloads"]},
                **{c: p[c][pi] for c in cfg["probe_payloads"]}}
        return Answer(Rows(jax.device_put(cols),
                           jax.device_put(np.ones(len(pi), bool))),
                      jax.device_put(np.int64(len(pi))),
                      jax.device_put(np.bool_(False)))

    return join


@contextlib.contextmanager
def in_place_of_the_program(cfg: dict):
    from distributed_join_tpu.parallel import distributed_join as dj

    real = dj.distributed_inner_join
    dj.distributed_inner_join = fingerprint_join(cfg)
    try:
        yield
    finally:
        dj.distributed_inner_join = real


def control_run(cell, seed: int, seconds: float,
                require_tpu: bool = True) -> dict:
    """A whole run of ``cell`` with the control in the program's place."""
    from joinbench import run

    with in_place_of_the_program(cell.config):
        return run.run(cell, seed, seconds, trace=False,
                       require_tpu=require_tpu)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from joinbench import run

    cell = run.load_cell(args.workload)
    for seed in args.seeds:
        r = control_run(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
