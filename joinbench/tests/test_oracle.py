"""The oracle against a brute-force pandas merge, for every config's
generator, and the faults its digest must catch."""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from distributed_join_tpu import make_communicator
from joinbench import oracle, run
from joinbench.tests.tiny import tiny_cell

CELLS = ["uniform10m.oneshot", "tpch_q3.oneshot",
         "uniform100m.oneshot.4chip"]   # every config's generator


def brute_force(cfg, build, probe):
    """Every output row of the join, materialised by pandas."""
    key = cfg["key"]
    out = pd.DataFrame(build).merge(pd.DataFrame(probe), on=key)
    return {c: out[c].to_numpy() for c in out.columns}


def names(cfg):
    return cfg["key"], cfg["build_payloads"], cfg["probe_payloads"]


@pytest.fixture(scope="module", params=CELLS)
def joined(request):
    """A cell's tables, the join that pandas makes of them, and the
    reference's digest."""
    cell = tiny_cell(request.param)
    sets = run.make_table_sets(
        run.Cell(cell.name, 1, cell.config, {"table_sets": 1}, [], []),
        make_communicator("tpu", n_ranks=1), 5)
    build, probe = (run.host_side(t) for t in sets[0])
    out = brute_force(cell.config, build, probe)
    return cell.config, out, run.reference_digests(cell, sets, [0])[0]


def np_digest(cfg, out):
    n = len(out[cfg["key"]])
    return oracle.to_ints(oracle.digest(out, np.ones(n, bool),
                                        *names(cfg), np))


def test_reference_equals_brute_force(joined):
    cfg, out, want = joined
    assert len(out[cfg["key"]]) > 0
    assert want == np_digest(cfg, out)


def test_device_digest_equals_host_digest(joined):
    cfg, out, _ = joined
    n = len(out[cfg["key"]])
    valid = np.arange(n + 3) < n      # with invalid padding rows
    padded = {c: np.concatenate([v, v[:3]]) for c, v in out.items()}
    got = oracle.to_ints(oracle.digest(
        {c: jnp.asarray(v) for c, v in padded.items()}, jnp.asarray(valid),
        *names(cfg), jnp))
    assert got == np_digest(cfg, out)


def test_catches_a_dropped_row(joined):
    cfg, out, want = joined
    dropped = {c: v[1:] for c, v in out.items()}
    assert "matches" in oracle.mismatches(np_digest(cfg, dropped), want)


def test_catches_a_swapped_payload(joined):
    """Two rows of different keys trade one build payload: every column
    holds the same values, only their pairing changed."""
    cfg, out, want = joined
    keys = out[cfg["key"]]
    col = cfg["build_payloads"][0]
    j = int(np.argmax((keys != keys[0]) & (out[col] != out[col][0])))
    assert out[col][0] != out[col][j]
    swapped = dict(out)
    swapped[col] = out[col].copy()
    swapped[col][[0, j]] = swapped[col][[j, 0]]
    assert oracle.mismatches(np_digest(cfg, swapped), want) == ["rows"]


def reference(build, build_valid, probe, probe_valid):
    return oracle.reference({c: v[build_valid] for c, v in build.items()},
                            {c: v[probe_valid] for c, v in probe.items()},
                            "k", ["b"], ["p"])


def test_duplicate_keys_on_both_sides_and_invalid_rows():
    build = {"k": np.array([1, 1, 2, 3, 3]), "b": np.array([10, 11, 12, 13, 14])}
    bv = np.array([True, True, True, True, False])
    probe = {"k": np.array([1, 1, 1, 3, 4, 2]), "p": np.arange(6)}
    pv = np.array([True, True, True, True, True, False])
    cfg = {"key": "k", "build_payloads": ["b"], "probe_payloads": ["p"]}
    out = brute_force(cfg, {c: v[bv] for c, v in build.items()},
                      {c: v[pv] for c, v in probe.items()})
    assert len(out["k"]) == 7
    assert reference(build, bv, probe, pv) == np_digest(cfg, out)


def test_no_valid_rows():
    build = {"k": np.array([1, 2]), "b": np.array([1, 2])}
    probe = {"k": np.array([1]), "p": np.array([1])}
    got = reference(build, np.zeros(2, bool), probe, np.ones(1, bool))
    assert got["matches"] == 0 and got["rows"] == 0
