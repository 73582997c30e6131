"""The control (an equi-join on a fingerprint of the key, in the
program's place) comes out not correct through a whole run of the
harness. At a size a test run holds, 32-bit fingerprints would not
collide, so the test narrows them to 12 bits."""

import numpy as np
import pytest

from joinbench import control
from joinbench.tests.tiny import tiny_cell


@pytest.mark.parametrize("name", ["uniform10m.oneshot", "tpch_q3.oneshot"])
def test_control_run_is_not_correct(monkeypatch, name):
    monkeypatch.setattr(control, "FINGERPRINT_BITS", 12)
    r = control.control_run(tiny_cell(name), seed=2**31 + 3, seconds=0.3,
                            require_tpu=False)
    assert not r["correct"]
    assert r["checks"]["match_count_gap"]["value"] > 0
    assert r["checks"]["digest_mismatches"]["value"] >= 1


def test_control_at_full_width_is_the_exact_join_without_collisions():
    """With no two keys' fingerprints equal, the control is exact: what
    fails it is the collisions, not the harness around it."""
    r = control.control_run(tiny_cell("uniform10m.oneshot"), seed=5,
                            seconds=0.3, require_tpu=False)
    assert r["correct"], r["checks"]


def test_fingerprint_keeps_equal_keys_equal():
    k = np.array([5, 5, 2**40, -3])
    f = control.fingerprint(k)
    assert f[0] == f[1] and (f >= 0).all() and (f < 2**32).all()
