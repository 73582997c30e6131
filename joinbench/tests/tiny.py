"""Cells cut to a size that a CPU test holds.

The four-chip cell is not in ``BENCHMARK.json`` yet (its chip
measurement is an open question in PERF.md); its files are, and the
tests build it from them.
"""

import copy
import json
import os

from joinbench import run

TINY_ROWS = {"uniform_int64_10m": 6_000, "uniform_int64_100m_8chip": 8_000}
TINY_SCALE = 0.004   # 6,000 orders
PENDING = {"uniform100m.oneshot.4chip": ("uniform_int64_100m_8chip", 4)}


def load(name: str) -> run.Cell:
    if name not in PENDING:
        return run.load_cell(name)
    config, chips = PENDING[name]
    with open(os.path.join(run.HERE, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(run.HERE, "traffic", "oneshot_closed.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = [{"name": "shuffle.collective_ms_per_call", "unit": "ms"},
              *bench["per_layer"]]
    return run.Cell(name, chips, cfg, traffic, bench["end_to_end"], layers)


def tiny_cell(name: str) -> run.Cell:
    cell = load(name)
    cfg = copy.deepcopy(cell.config)
    if cfg["generator"] == "uniform":
        rows = TINY_ROWS[cfg["name"]]
        cfg.update(build_rows=rows, probe_rows=rows, rand_max=rows)
    else:
        cfg["scale_factor"] = TINY_SCALE
    cell.config = cfg
    return cell
