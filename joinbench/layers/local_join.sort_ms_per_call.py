"""Device time of XLA sort operations per join call, on the slowest
chip, from the trace."""

from joinbench import trace


def read(inp):
    if inp.summary is None or not inp.calls:
        return None
    t = max(trace.category_ns(inp.summary, {"sort"}).values(), default=0)
    return t / inp.calls / 1e6 if t > 0 else None
