"""Device time of the local join's expand kernels (``expand_b8`` with
the build windows, ``expand`` on the fallback branch, or
``expand_planes``), which write the output rows over the output's
static capacity, per join call, on the slowest chip, from the trace."""

from joinbench import kernels


def read(inp):
    return kernels.ms_per_call(inp, {"expand_b8", "expand",
                                     "expand_planes"})
