"""Device time of the local join's stream-compaction kernels
(``compact_planes``, or ``compact_mxu`` where configured: the
run-record block and the matched-build pack) per join call, on the
slowest chip, from the trace."""

from joinbench import kernels


def read(inp):
    return kernels.ms_per_call(inp, {"compact_planes", "compact_mxu"})
