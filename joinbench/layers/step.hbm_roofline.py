"""Share of the HBM peak that the whole join step reaches: the bytes any
join must move (``joinbench/work.py``) per chip and call, over the peak
bandwidth, over the device's busy time per call (mean over chips)."""

from joinbench import trace


def read(inp):
    if inp.summary is None or not inp.calls or inp.peaks is None:
        return None
    busy = trace.per_device_mean(inp.summary.busy_ns)
    if not busy:
        return None
    least_s = inp.bytes_per_call / inp.chips / inp.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (busy / 1e9 / inp.calls)
