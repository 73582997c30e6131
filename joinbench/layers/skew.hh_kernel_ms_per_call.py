"""Device time of the heavy-hitter path's Pallas kernels (every kernel
named ``hh_*``: the heavy-key join's ``hh_join_scan_*``,
``hh_compact_*`` and ``hh_expand*``, and ``hh_extract``, which compacts
the heavy keys' rows) per join call, on the slowest chip, from the
trace. A program that runs no skew path, or names no kernel so, shows
none of them."""

from joinbench import kernels

PREFIX = "hh_"


def read(inp):
    if inp.summary is None:
        return None
    names = {name for by_name in inp.summary.name_ns.values()
             for label in by_name
             if (name := kernels.kernel_name(label) or "").startswith(PREFIX)}
    return kernels.ms_per_call(inp, names) if names else None
