"""A run with the timed path broken underneath comes out not correct:
one test for each fault the cells can have. The harness's look for a
chip is skipped; the rest of a run is the real one."""

import dataclasses

import jax.numpy as jnp
import pytest

from distributed_join_tpu.parallel import distributed_join as dj
from distributed_join_tpu.parallel.communicator import TpuCommunicator
from distributed_join_tpu.table import Table
from joinbench import run
from joinbench.tests.tiny import tiny_cell

REAL = dj.distributed_inner_join


def run_cell(name):
    return run.run(tiny_cell(name), seed=77, seconds=0.3, trace=False,
                   require_tpu=False)


def test_sound_run_is_correct():
    assert run_cell("uniform10m.oneshot")["correct"]


def test_stale_result(monkeypatch):
    """Each call returns the answer of the call before it."""
    last = {}

    def stale(build, probe, comm, **kw):
        res = REAL(build, probe, comm, **kw)
        out = last.get("res", res)
        last["res"] = res
        return out

    monkeypatch.setattr(dj, "distributed_inner_join", stale)
    r = run_cell("uniform10m.oneshot")
    assert not r["correct"]
    assert r["checks"]["match_count_gap"]["value"] > 0


@pytest.mark.parametrize("name", ["uniform10m.oneshot", "tpch_q3.oneshot"])
def test_half_of_the_batch_left_out(monkeypatch, name):
    def half(build, probe, comm, **kw):
        keep = jnp.arange(probe.capacity) < probe.capacity // 2
        return REAL(build, Table(probe.columns, probe.valid & keep), comm,
                    **kw)

    monkeypatch.setattr(dj, "distributed_inner_join", half)
    r = run_cell(name)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]


def test_exchange_between_chips_left_out(monkeypatch):
    monkeypatch.setattr(TpuCommunicator, "all_to_all", lambda self, x: x)
    r = run_cell("uniform100m.oneshot.4chip")
    assert not r["correct"]


@pytest.mark.parametrize("name", ["uniform10m.oneshot", "tpch_q3.oneshot",
                                  "uniform100m.oneshot.4chip"])
def test_answer_altered_where_produced(monkeypatch, name):
    """One probe payload of one output row is off by one."""
    def altered(build, probe, comm, **kw):
        res = REAL(build, probe, comm, **kw)
        cfg = tiny_cell(name).config
        col = cfg["probe_payloads"][-1]
        cols = dict(res.table.columns)
        first = jnp.argmax(res.table.valid)
        cols[col] = cols[col].at[first].add(1)
        return dataclasses.replace(res, table=Table(cols, res.table.valid))

    monkeypatch.setattr(dj, "distributed_inner_join", altered)
    r = run_cell(name)
    assert not r["correct"]
    assert r["checks"]["digest_mismatches"]["value"] >= 1
    assert r["checks"]["match_count_gap"]["value"] == 0
