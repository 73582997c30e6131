"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 -m joinbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's tables on the device from the seed (one jitted
call per table set), loads the join's program from the persistent
compile cache (or compiles it, in a checkout's first run) and makes one
warm call. The window then calls the program's entry,
``distributed_inner_join``, with its defaults, back to back for
``--seconds`` seconds, each call ending when its result is ready. After
the window the answers are compared with the plain reference
(``oracle.py``, numpy on the host): every call's match count, and the
full digest of ``checked_calls`` calls drawn from the seed.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The last line of standard output is one JSON object; the
numbers compared, each with its limit, are the last lines of standard
error and the last key of that object. A run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

START = time.monotonic()  # before JAX is imported: set-up starts here

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _for_cell(metrics, name: str):
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name, cell["chips"], config, traffic,
                _for_cell(bench["end_to_end"], name),
                _for_cell(bench["per_layer"], name))


class CompileCounter:
    """Counts executables compiled or loaded while it is entered."""

    def __init__(self):
        self.count = 0

    def _on_duration(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def seed_key_data(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words of threefry key data from any whole seed."""
    ss = np.random.SeedSequence([seed % 2**64, stream])
    return ss.generate_state(2, np.uint32)


def make_table_sets(cell: Cell, comm, seed: int):
    """``table_sets`` (build, probe) pairs from the seed, row-sharded
    over the cell's chips, each made in one jitted call."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_join_tpu.table import Table
    from joinbench import data

    gen = data.generator(cell.config["generator"])
    sharding = NamedSharding(comm.mesh, P(comm.axis_name))
    make = jax.jit(lambda kd: gen.tables(cell.config,
                                         jax.random.wrap_key_data(kd)),
                   out_shardings=sharding)
    sets = []
    for i in range(cell.traffic["table_sets"]):
        t = make(seed_key_data(seed, i))
        sets.append(tuple(Table(dict(t[s]["columns"]), t[s]["valid"])
                          for s in ("build", "probe")))
    jax.block_until_ready(sets)
    return sets


def join(comm, cache, key, build, probe):
    """One call of the program's entry with its defaults."""
    from distributed_join_tpu.parallel import distributed_join as dj

    return dj.distributed_inner_join(build, probe, comm, key=key,
                                     program_cache=cache)


def host_side(table) -> dict:
    valid = np.asarray(table.valid)
    return {nm: np.asarray(c)[valid] for nm, c in table.columns.items()}


def reference_digests(cell: Cell, sets, used) -> dict:
    """The reference's digest of each table set in ``used``, on the
    host, one thread a set (numpy's sorts and ufuncs release the GIL)."""
    from joinbench import oracle

    cfg = cell.config

    def one(i):
        build, probe = (host_side(t) for t in sets[i])
        return oracle.reference(build, probe, cfg["key"],
                                cfg["build_payloads"], cfg["probe_payloads"])

    with concurrent.futures.ThreadPoolExecutor(len(used)) as ex:
        futures = {i: ex.submit(one, i) for i in sorted(used)}
        return {i: f.result() for i, f in futures.items()}


def answer_digest(cell: Cell, res) -> dict:
    """The oracle's digest of one answer, computed on the device."""
    import jax
    import jax.numpy as jnp

    from joinbench import oracle

    cfg = cell.config
    names = {cfg["key"], *cfg["build_payloads"], *cfg["probe_payloads"]}
    if set(res.table.columns) != names:
        return {"columns": sorted(res.table.columns)}
    fn = jax.jit(lambda cols, valid: oracle.digest(
        cols, valid, cfg["key"], cfg["build_payloads"],
        cfg["probe_payloads"], jnp))
    return oracle.to_ints(fn(dict(res.table.columns), res.table.valid))


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Call:
    table_set: int
    total: object = None       # device scalar until fetched
    overflow: object = None
    error: str | None = None
    seconds: float = 0.0


def closed_loop(cell, comm, cache, sets, seconds, seed):
    """Calls back to back until ``seconds`` have passed; returns the
    calls, the kept (checked) results by call index, and the elapsed
    seconds from the first call's start to the last call's end."""
    import jax

    key = cell.config["key"]
    n_sets = len(sets)
    keep = cell.traffic["checked_calls"]
    rng = np.random.default_rng(seed_key_data(seed, 1 << 20))
    calls, kept = [], {}
    t0 = time.monotonic()
    while True:
        i = len(calls)
        call = Call((i + 1) % n_sets)
        start = time.monotonic()
        with jax.profiler.TraceAnnotation("joinbench.call"):
            try:
                res = join(comm, cache, key, *sets[call.table_set])
                jax.block_until_ready(res)
                call.total, call.overflow = res.total, res.overflow
            except Exception:  # a call that raises is a failed call
                call.error = traceback.format_exc(limit=4)
                res = None
        call.seconds = time.monotonic() - start
        calls.append(call)
        if res is not None:
            # Reservoir sampling: every call is kept with equal chance.
            slot = i if i < keep else int(rng.integers(0, i + 1))
            if slot < keep:
                kept[slot] = (i, res)
        del res
        elapsed = time.monotonic() - t0
        if elapsed >= seconds:
            return calls, dict(kept.values()), elapsed


def trace_window(run_window):
    """Runs ``run_window`` under the profiler; returns its result and
    the trace's path."""
    import jax

    tmp = tempfile.TemporaryDirectory(prefix="joinbench-trace-")
    # Host spans come from TraceMe only: the Python tracer would trace
    # every Python call of the entry and inflate the idle share.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp.name, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("joinbench.window"):
            out = run_window()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp.name, "**", "*.xplane.pb"),
                      recursive=True)
    return out, tmp, files[0] if files else None


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True) -> dict:
    import jax

    from distributed_join_tpu import device, make_communicator
    from distributed_join_tpu.service.programs import JoinProgramCache
    from joinbench import oracle, peaks, work
    from joinbench import trace as tr
    from joinbench.layers import LayerInput, reader

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: the default device is "
                     f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < cell.chips:
        raise NoChip(f"{len(devices)} devices, the cell needs {cell.chips}")
    if cell.traffic["callers"] != 1 or cell.traffic["loop"] != "closed":
        raise ValueError("the load loop runs one caller in a closed loop")
    kind = devices[0].device_kind
    chip = peaks.peaks(kind) if require_tpu else None
    device.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = cell.config

    comm = make_communicator("tpu", n_ranks=cell.chips)
    sets = make_table_sets(cell, comm, seed)
    cache = JoinProgramCache(comm)
    warm = join(comm, cache, cfg["key"], *sets[0])
    jax.block_until_ready(warm)
    row_bytes = work.output_row_bytes(
        c.dtype for c in warm.table.columns.values())
    del warm
    # Set-up's objects leave the collector's view, so that a collection
    # in the window scans only what the window made.
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - START

    def window():
        with CompileCounter() as counter:
            out = closed_loop(cell, comm, cache, sets, seconds, seed)
        return out, counter.count

    tmp = path = None
    gc_before = [g["collections"] for g in gc.get_stats()]
    if trace:
        ((calls, kept, elapsed), compiles), tmp, path = trace_window(window)
    else:
        (calls, kept, elapsed), compiles = window()
    gc_in_window = [g["collections"] - n
                    for g, n in zip(gc.get_stats(), gc_before)]
    gc.unfreeze()
    mesh_devices = list(comm.mesh.devices.flat)
    peak = peak_bytes(mesh_devices)

    # -- correctness: after the window, with the peak read ------------
    for c in calls:
        if c.error is None:
            c.total, c.overflow = int(c.total), bool(c.overflow)
    got = {i: answer_digest(cell, res) for i, res in kept.items()}
    del kept
    used = {c.table_set for c in calls}
    want = reference_digests(cell, sets, used)
    bad = set()
    gap = 0
    for i, c in enumerate(calls):
        if c.error is not None or c.overflow:
            bad.add(i)
            continue
        d = abs(c.total - want[c.table_set]["matches"])
        gap = max(gap, d)
        if d:
            bad.add(i)
    digest_bad = {i for i, d in got.items()
                  if oracle.mismatches(d, want[calls[i].table_set])}
    bad |= digest_bad
    checks = {
        "raised_calls": [sum(c.error is not None for c in calls), 0],
        "overflowed_calls": [sum(bool(c.overflow) for c in calls), 0],
        "match_count_gap": [gap, 0],
        "digest_mismatches": [len(digest_bad), 0],
    }
    correct = bool(calls) and bool(got) and not bad and all(
        v <= lim for v, lim in checks.values())

    # -- metrics ------------------------------------------------------
    done = [c for c in calls if c.error is None]
    rows = sum(t.capacity for t in sets[0])
    result = {"correct": correct, "attempted": len(calls),
              "failed": len(bad), "metrics": {}}
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": peak or 0}
    if not trace:
        values = {"join_mrows_per_s_chip":
                  rows * len(done) / elapsed / cell.chips / 1e6,
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        summary = None
        if path is not None:
            loaded = tr.load(path)
            # Only the cell's chips: a host may hold more than it uses.
            ids = {d.id for d in mesh_devices}
            loaded.devices = {k: v for k, v in loaded.devices.items()
                              if k in ids}
            span = tr.window(loaded)
            if loaded.devices and span is not None:
                summary = tr.summarize(loaded, *span)
        tmp.cleanup()
        matches = np.mean([want[c.table_set]["matches"] for c in done]
                          ) if done else 0.0
        in_bytes = work.input_bytes(
            (t.columns, t.valid) for t in sets[0])
        inp = LayerInput(
            summary=summary,
            calls=summary.calls if summary is not None else len(calls),
            chips=cell.chips, compiles_in_window=compiles,
            peak_bytes=peak,
            bytes_per_call=work.hbm_bytes_per_call(in_bytes, row_bytes,
                                                   matches),
            peaks=chip)
        for m in cell.per_layer:
            v = reader(m["name"])(inp)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        if summary is not None:
            dev["busy_s"] = tr.per_device_mean(summary.busy_ns) / 1e9
            dev["window_s"] = summary.window_ns / 1e9
            result["breakdown"] = {
                "device_ops": tr.top_ops(summary),
                "idle_gaps": [[label, ns / 1e9]
                              for ns, label in summary.gaps]}
    walls = sorted(c.seconds for c in calls)
    result["call_s"] = {"min": walls[0], "median": walls[len(walls) // 2],
                        "max": walls[-1],
                        "slowest_index": max(range(len(calls)),
                                             key=lambda i: calls[i].seconds),
                        "gc_collections_by_generation": gc_in_window}
    result["device"] = dev
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"joinbench: {exc}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
