"""Share of the traced window in which no operation ran on the device,
mean over chips: 1 - union of busy intervals / window."""

from joinbench import trace


def read(inp):
    if inp.summary is None or not inp.summary.window_ns:
        return None
    busy = trace.per_device_mean(inp.summary.busy_ns)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / inp.summary.window_ns)
