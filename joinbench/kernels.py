"""Device time of the program's Pallas kernels, told apart by name.

Each ``pallas_call`` of the local join passes ``name=``, and the chip's
compiler names the kernel's instruction after it (``join_scan_r.1``,
``expand_b8.1``), which the trace's operation label starts with
(``trace.parse_op``: ``"join_scan_r.1 tpu_custom_call"``). A program
whose kernels carry no such names shows none of them, and a reader then
finds nothing to read.
"""

from __future__ import annotations

import re

KERNEL_CATEGORY = "tpu_custom_call"


def kernel_name(label: str) -> str | None:
    """The kernel a Pallas operation's label names (its instruction
    name without the compiler's ``.N`` suffix); None for any other
    operation."""
    name, _, category = label.partition(" ")
    if category != KERNEL_CATEGORY:
        return None
    return re.sub(r"\.\d+$", "", name)


def ms_per_call(inp, kernels) -> float | None:
    """Device self time of the named kernels per join call, on the
    slowest chip; None where the trace holds none of them."""
    if inp.summary is None or not inp.calls:
        return None
    t = max((sum(ns for label, ns in by_name.items()
                 if kernel_name(label) in kernels)
             for by_name in inp.summary.name_ns.values()), default=0)
    return t / inp.calls / 1e6 if t > 0 else None
