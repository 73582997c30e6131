"""Device time of the shuffle's collectives (all-to-all, ragged
all-to-all, collective-permute) per join call, on the slowest chip,
from the trace."""

from joinbench import trace

SHUFFLE = {"all-to-all", "ragged-all-to-all", "collective-permute"}


def read(inp):
    if inp.summary is None or not inp.calls:
        return None
    t = max(trace.category_ns(inp.summary, SHUFFLE).values(), default=0)
    return t / inp.calls / 1e6 if t > 0 else None
