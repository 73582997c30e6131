"""Each cell end to end on the CPU at a tiny size, through the harness's
own functions (the four-chip cell on four virtual devices), and the
command's refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from joinbench import run
from joinbench.layers import HERE as LAYERS
from joinbench.tests.tiny import PENDING, tiny_cell

ROOT = run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS + list(PENDING))
@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_cell_runs_correct_on_cpu(name, traced):
    cell = tiny_cell(name)
    r = run.run(cell, seed=2**31 + 12345, seconds=0.5, trace=traced,
                require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["device"]["count"] >= cell.chips
    if traced:
        # Off the chip only the counter is there to read.
        assert r["metrics"] == {"entry.compiles_in_window":
                                {"value": 0, "unit": "count"}}
    else:
        assert set(r["metrics"]) == {"join_mrows_per_s_chip", "setup_s"}
        assert r["metrics"]["join_mrows_per_s_chip"]["value"] > 0


def test_same_seed_same_tables_under_any_sharding():
    import numpy as np

    from distributed_join_tpu import make_communicator

    cell = tiny_cell("uniform100m.oneshot.4chip")
    one = run.make_table_sets(cell, make_communicator("tpu", n_ranks=1), 9)
    four = run.make_table_sets(cell, make_communicator("tpu", n_ranks=4), 9)
    for a, b in zip(one, four):
        for ta, tb in zip(a, b):
            for name in ta.columns:
                np.testing.assert_array_equal(np.asarray(ta.columns[name]),
                                              np.asarray(tb.columns[name]))
    other = run.make_table_sets(cell, make_communicator("tpu", n_ranks=1),
                                2**40 + 9)
    assert not np.array_equal(np.asarray(one[0][1].columns["key"]),
                              np.asarray(other[0][1].columns["key"]))


def test_every_name_in_benchmark_json_has_its_files():
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(LAYERS, m["name"] + ".py"))
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in CELLS:
        cell = run.load_cell(w)
        assert cell.chips == cell.config["chips"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "joinbench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
