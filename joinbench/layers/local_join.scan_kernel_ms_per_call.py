"""Device time of the local join's scan kernels (``join_scan_r`` and
``join_scan_f``: run boundaries, match counts, output offsets) per join
call, on the slowest chip, from the trace."""

from joinbench import kernels


def read(inp):
    return kernels.ms_per_call(inp, {"join_scan_r", "join_scan_f"})
