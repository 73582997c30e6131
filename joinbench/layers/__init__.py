"""Per-layer metrics: one reader each, in ``<metric name>.py``.

A reader is ``read(inp: LayerInput) -> float | None``. It returns None
where it finds nothing to read, and the harness then leaves the metric
out of the result line; it never returns 0 for a share of a peak.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class LayerInput:
    summary: object            # trace.Summary of the window, or None
    calls: int                 # join calls in the traced window
    chips: int
    compiles_in_window: int
    peak_bytes: int | None     # on the fullest chip
    bytes_per_call: float      # work.hbm_bytes_per_call, all chips
    peaks: dict | None         # peaks.peaks(device_kind); None off-chip


def reader(metric: str):
    path = os.path.join(HERE, metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "joinbench.layers._" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
