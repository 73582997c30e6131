"""The trace reduction and the per-layer readers, on synthetic events,
and one real (CPU) profiler trace read for its shape."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from joinbench import trace, work
from joinbench.layers import LayerInput, reader

V5E = {"hbm_bytes_per_s": 819e9}


def ev(name, start, end):
    return trace.Event(*trace.parse_op(name), start, end)


def synthetic():
    """Two devices over a window [0, 1000) ns holding two calls."""
    d0 = [ev("sort.1", 100, 300), ev("fusion.2", 250, 400),   # overlap
          ev('%custom-call.3 = u32[8]{0} custom-call(u32[8]{0} %x), '
             'custom_call_target="tpu_custom_call"', 500, 600),
          ev("sort.4", 900, 1100)]
    d1 = [ev("all-to-all.5", 0, 100), ev("sort.6", 150, 350),
          ev("collective-permute-start.7", 400, 500)]
    host = [trace.Event(trace.WINDOW_SPAN, "", 0, 1000),
            trace.Event(trace.CALL_SPAN, "", 0, 450),
            trace.Event(trace.CALL_SPAN, "", 450, 1000),
            trace.Event("PjitFunction(step)", "", 440, 520)]
    return trace.Trace({0: d0, 1: d1}, host)


TPU_OPS = [
    ('%sort.3 = (s32[20000000]{0:T(1024)}, u32[20000000]{0:T(1024)}) '
     'sort(s32[20000000]{0:T(1024)} %a, u32[20000000]{0:T(1024)} %b), '
     'dimensions={0}, is_stable=true', "sort.3 sort", "sort"),
    ('%custom-call.12 = u32[12000000]{0:T(1024)} custom-call(u32[20000000]'
     '{0:T(1024)} %x), custom_call_target="tpu_custom_call", '
     'backend_config={...}', "custom-call.12 tpu_custom_call",
     "tpu_custom_call"),
    ('%custom-call = u32[10000000]{0:T(1024)} custom-call(s64[10000000]'
     '{0:T(1024)} %k), custom_call_target="X64SplitHigh"',
     "custom-call X64SplitHigh", "X64SplitHigh"),
    ('%fusion.203 = s32[11720]{0:T(1024)S(1)} fusion(s32[12000000]'
     '{0:T(1024)S(1)} %g), kind=kCustom, calls=%fused_computation.7',
     "fusion.203 fusion kCustom", "fusion"),
    ('%all-to-all-start.2 = ((u32[4,100]{1,0}), u32[4,100]{1,0}) '
     'all-to-all-start(u32[4,100]{1,0} %p), replica_groups={{0,1,2,3}}',
     "all-to-all-start.2 all-to-all", "all-to-all"),
    ("sort.12", "sort.12", "sort"),
    ("collective-permute-done.9", "collective-permute-done.9",
     "collective-permute"),
]


@pytest.mark.parametrize("text,name,category", TPU_OPS)
def test_operation_names_and_categories(text, name, category):
    assert trace.parse_op(text) == (name, category)


def test_self_time_excludes_nested_operations():
    ops = [ev("conditional.1", 0, 100), ev("sort.2", 10, 60),
           ev("fusion.3", 60, 90), ev("copy.4", 100, 120)]
    own = {o.name: t for o, t in trace.self_times(ops, 0, 115)}
    assert own == {"conditional.1": 20, "sort.2": 50, "fusion.3": 30,
                   "copy.4": 15}


def test_union_merges_overlaps_and_clips_to_window():
    spans = [(100, 300), (250, 400), (500, 600), (900, 1100)]
    assert trace.union_ns(spans, 0, 1000) == 300 + 100 + 100
    assert trace.gaps(spans, 0, 1000) == [(0, 100), (400, 500), (600, 900)]


def test_summary_per_device_and_category():
    s = trace.summarize(synthetic(), 0, 1000)
    assert s.window_ns == 1000 and s.calls == 2
    assert s.busy_ns == {0: 500, 1: 400}
    assert s.category_ns[0]["sort"] == 200 + 100      # clipped at 1000
    assert s.category_ns[1]["collective-permute"] == 100
    # device 0's gaps, longest first, labelled by the innermost span
    assert s.gaps == [(300, trace.CALL_SPAN), (100, trace.CALL_SPAN),
                      (100, "PjitFunction(step)")]


def test_readers_divide_by_calls_and_take_the_slowest_chip():
    s = trace.summarize(synthetic(), 0, 1000)
    inp = LayerInput(summary=s, calls=2, chips=2, compiles_in_window=0,
                     peak_bytes=3 * 2**30, bytes_per_call=0.0, peaks=V5E)
    assert reader("local_join.sort_ms_per_call")(inp) == 300 / 2 / 1e6
    assert reader("local_join.kernel_ms_per_call")(inp) == 100 / 2 / 1e6
    assert reader("shuffle.collective_ms_per_call")(inp) == 200 / 2 / 1e6
    assert reader("device.idle_pct")(inp) == pytest.approx(55.0)
    assert reader("device.peak_hbm_gib")(inp) == 3.0
    assert reader("entry.compiles_in_window")(inp) == 0


def test_readers_find_nothing_without_a_trace():
    inp = LayerInput(summary=None, calls=3, chips=1, compiles_in_window=1,
                     peak_bytes=None, bytes_per_call=1.0, peaks=None)
    for m in ("local_join.sort_ms_per_call", "step.hbm_roofline",
              "device.idle_pct", "device.peak_hbm_gib"):
        assert reader(m)(inp) is None
    s = trace.summarize(trace.Trace({0: [ev("fusion.1", 0, 10)]}, []), 0, 10)
    inp.summary = s
    assert reader("shuffle.collective_ms_per_call")(inp) is None


def test_roofline_from_known_bytes_and_time():
    # 819 MB per call over two chips: 0.5 ms per chip at the peak;
    # each chip is busy 4 ms over two calls, 2 ms a call: 25%.
    ops = {0: [ev("fusion.1", 0, 4_000_000)],
           1: [ev("fusion.2", 0, 4_000_000)]}
    s = trace.summarize(trace.Trace(ops, []), 0, 5_000_000)
    inp = LayerInput(summary=s, calls=2, chips=2, compiles_in_window=0,
                     peak_bytes=None, bytes_per_call=819e6, peaks=V5E)
    assert reader("step.hbm_roofline")(inp) == pytest.approx(25.0)


def test_bytes_a_join_must_move():
    cols = {"k": jnp.zeros(10, jnp.int64), "p": jnp.zeros(10, jnp.int32)}
    sides = [(cols, jnp.zeros(10, bool)), (cols, jnp.zeros(10, bool))]
    assert work.input_bytes(sides) == 2 * (80 + 40 + 10)
    row = work.output_row_bytes([jnp.dtype("int64"), jnp.dtype("int32")])
    assert row == 12
    assert work.hbm_bytes_per_call(260, row, 5) == 320


def test_reads_a_real_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(1000)[::-1]
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    t = trace.load(path)
    lo, hi = trace.window(t)
    assert hi > lo
    assert t.devices == {}      # the CPU has no device plane
    s = trace.summarize(t, lo, hi)
    assert s.calls == 2 and s.busy_ns == {}
