"""Compile a cell's join program for a described v5e, without the chip.

    JAX_PLATFORMS=cpu python3 -m joinbench.compile_check --workload <cell> [--set key=value ...]

Lowers the program that ``distributed_inner_join`` runs at its first
rung, at the cell's real shapes, for chips that are described and not
attached, and prints the compiler's memory analysis per chip and the
number of Pallas kernels (``tpu_custom_call``) in the program. Nothing
runs, so this gives no time and no answer; it shows whether the chip's
compiler takes the program and whether it fits the chip's memory.
``--set`` overrides a number of the cell's config, to size a cut.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_join_tpu import device
    from distributed_join_tpu.parallel.communicator import TpuCommunicator
    from distributed_join_tpu.parallel.distributed_join import (
        make_distributed_join,
        resolve_join_ladder,
    )
    from distributed_join_tpu.table import Table
    from joinbench import data, run, work

    cell = run.load_cell(args.workload)
    cfg = dict(cell.config)
    for kv in args.set:
        k, v = kv.split("=", 1)
        cfg[k] = json.loads(v)
    # The kernels' dispatch asks the default device, which is the CPU
    # here; the program is built for the described chip.
    device.on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:cell.chips]), ("ranks",))
    comm = TpuCommunicator(mesh=mesh)
    sharding = NamedSharding(mesh, P("ranks"))
    gen = data.generator(cfg["generator"])
    shapes = jax.eval_shape(
        lambda kd: gen.tables(cfg, jax.random.wrap_key_data(kd)),
        jax.ShapeDtypeStruct((2,), np.uint32))
    sides = []
    for s in ("build", "probe"):
        cols = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
                for k, v in shapes[s]["columns"].items()}
        valid = jax.ShapeDtypeStruct(shapes[s]["valid"].shape, bool,
                                     sharding=sharding)
        sides.append(Table(cols, valid))
    build, probe = sides
    opts = {}
    ladder = resolve_join_ladder(build, probe, comm.n_ranks, opts)
    fn = make_distributed_join(comm, key=cfg["key"],
                               metrics_static={"retry_attempt_max": 0},
                               **ladder.sizing(), **opts)
    t0 = time.monotonic()
    compiled = fn.lower(build, probe).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "workload": cell.name, "config": {k: cfg[k] for k in cfg
                                          if isinstance(cfg[k], (int, float))},
        "compile_s": time.monotonic() - t0,
        "build_rows": build.capacity, "probe_rows": probe.capacity,
        "input_bytes": work.input_bytes(
            (t.columns, t.valid) for t in sides),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "memory_per_chip": {k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")},
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
