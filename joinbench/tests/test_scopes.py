"""The reduction by the program's own names (``joinbench/scopes.py``) and
the kernel readers (``joinbench/kernels.py``), on synthetic events, and
on one real (CPU) profiler trace of a traced run for its host spans."""

import pytest

from joinbench import kernels, run, scopes, trace
from joinbench.layers import LayerInput, reader
from joinbench.tests.tiny import tiny_cell


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/join/sort/sort", "join/sort"),
    ("jit(step)/join/scan/join_scan_r/pallas_call", "join/scan"),
    ("jit(step)/join/expand/cond/branch_1_fun/expand_b8/pallas_call",
     "join/expand"),
    ("jit(step)/shard_map/join/prepare/concatenate", "join/prepare"),
    ("jit(step)/shard_map/partition/sort", "partition"),
    ("jit(step)/shard_map/shuffle/all_to_all", "shuffle"),
    ("jit(step)/shard_map/join/concatenate", "join"),
    ("jit(step)/shard_map/psum_invariant", scopes.NO_LAYER),
    ("build_local.columns['key']", scopes.NO_LAYER),
    ("", scopes.NO_LAYER),
])
def test_layer_is_the_innermost_scope(op_name, want):
    assert scopes.layer(op_name) == want


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def message(*fields) -> bytes:
    """A protobuf message of ``(field number, int | str | bytes)``."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def instruction(ident, name, operands=(), called=(), op_name=None):
    fields = [(1, name), (35, ident)]
    fields += [(36, o) for o in operands] + [(38, c) for c in called]
    if op_name is not None:
        fields.append((7, message((2, op_name))))
    return (2, message(*fields))


# An entry computation (1) and a while body (2), as the compiler leaves
# them: named instructions, and ones it made without an op_name.
MODULE = message(
    (3, message((1, "main"), (5, 1),
                instruction(10, "x.1"),
                instruction(11, "custom-call.3", [10]),      # X64 split
                instruction(12, "sort.30", [11],
                            op_name="jit(step)/join/sort/sort"),
                instruction(13, "while.4", [12], called=[2]),
                instruction(14, "join_scan_r.1", [13], op_name=(
                    "jit(step)/join/scan/join_scan_r/pallas_call")),
                instruction(15, "custom-call.8", [14]))),    # X64 combine
    (3, message((1, "body.4"), (5, 2),
                instruction(20, "dynamic-update-slice_fusion.2"))),
)


def test_hlo_graph_reads_the_module_proto():
    graph = scopes.hlo_graph(MODULE)
    assert graph["sort.30"] == (1, ["custom-call.3"], [],
                                "jit(step)/join/sort/sort")
    assert graph["while.4"] == (1, ["sort.30"], [2], "")
    assert graph["dynamic-update-slice_fusion.2"] == (2, [], [], "")


def test_attribute_follows_the_data_flow():
    """Unnamed instructions take the layer of what they feed, else of
    what feeds them, else their caller's."""
    assert scopes.attribute(scopes.hlo_graph(MODULE)) == {
        "x.1": "join/sort", "custom-call.3": "join/sort",
        "sort.30": "join/sort", "while.4": "join/scan",
        "join_scan_r.1": "join/scan", "custom-call.8": "join/scan",
        "dynamic-update-slice_fusion.2": "join/scan"}


def test_hlo_modules_from_the_metadata_plane():
    """The module rides in the metadata plane as the ``Hlo Proto``
    stat's bytes (an HloProto whose field 1 is the module)."""
    stat = message((1, 7), (6, message((1, MODULE))))
    plane = message(
        (2, scopes.METADATA_PLANE),
        (4, message((1, 1), (2, message((1, 1), (2, "jit_step(1)"),
                                        (5, stat))))),
        (5, message((1, 7), (2, message((1, 7),
                                        (2, scopes.HLO_PROTO_STAT))))))
    device = message((2, "/device:TPU:0"))
    space = message((1, device), (1, plane))
    assert list(scopes._hlo_modules(space)) == [MODULE]


def ev(name, start, end):
    return trace.Event(*trace.parse_op(name), start, end)


# One operation of each kind as a TPU trace names it (the event's name
# is the instruction's text), with its op_name from the program's HLO.
TPU_OPS = [
    ("sort.30", "jit(step)/join/sort/sort"),
    ('%join_scan_r.1 = s32[2448,8192]{1,0:T(8,128)S(1)} custom-call('
     's32[2448,8192]{1,0:T(8,128)S(1)} %reshape.86), '
     'custom_call_target="tpu_custom_call"',
     "jit(step)/join/scan/join_scan_r/pallas_call"),
    ("fusion.7", "jit(step)/join/expand/gather"),
    ("fusion.8", "jit(step)/shard_map/partition/add"),
    ("all-to-all.9", "jit(step)/shard_map/shuffle/all_to_all"),
    ("copy.10", ""),                  # made by the compiler
]


def synthetic():
    """Two calls in a window [0, 1000) ns on one device; the entry's
    spans of each call, one with a program-cache miss inside."""
    names = [trace.parse_op(t)[0].partition(" ")[0] for t, _ in TPU_OPS]
    d0 = [ev(TPU_OPS[0][0], 100, 200),      # join/sort: 100
          ev(TPU_OPS[1][0], 200, 260),      # join/scan: 60
          ev(TPU_OPS[2][0], 260, 300),      # join/expand: 40
          ev(TPU_OPS[3][0], 600, 700),      # partition: 100
          ev(TPU_OPS[4][0], 700, 750),      # shuffle: 50
          ev(TPU_OPS[5][0], 750, 760)]      # (none), fed to shuffle: 10
    host = [trace.Event(trace.WINDOW_SPAN, "", 0, 1000),
            trace.Event(trace.CALL_SPAN, "", 0, 500),
            trace.Event(trace.CALL_SPAN, "", 500, 1000)]
    entry = [
        (trace.Event("entry.prepare", "", 0, 20), {}),
        (trace.Event("entry.resolve", "", 20, 80), {}),
        (trace.Event("entry.compile", "", 30, 70), {"digest": "abc"}),
        (trace.Event("entry.dispatch", "", 80, 90), {}),
        (trace.Event(scopes.FETCH_SPAN, "", 90, 320),
         {"out_rows": 100, "rung": 0}),
        (trace.Event("entry.finish", "", 320, 330), {}),
        (trace.Event("entry.dispatch", "", 500, 510), {}),
        (trace.Event(scopes.FETCH_SPAN, "", 510, 790),
         {"out_rows": 300, "rung": 0}),
    ]
    op_names = dict(zip(names, (o for _, o in TPU_OPS)))
    layers = {n: scopes.layer(o) for n, o in op_names.items()}
    layers["copy.10"] = "shuffle"
    return scopes.Scoped(trace.Trace({0: d0}, host), op_names, layers,
                         entry)


def test_reduce_by_layer_entry_span_and_idle():
    r = scopes.reduce(synthetic(), 0, 1000)
    assert r["calls"] == 2
    by_scope = {"join/sort": 50e-6, "join/scan": 30e-6,
                "join/expand": 20e-6, "partition": 50e-6,
                "shuffle": 25e-6, scopes.NO_LAYER: 5e-6}
    assert r["device_ms_per_call_by_scope"][0] == pytest.approx(by_scope)
    by_scope["shuffle"] += by_scope.pop(scopes.NO_LAYER)
    assert r["device_ms_per_call_by_layer"][0] == pytest.approx(by_scope)
    assert r["device_busy_ms_per_call"][0] == pytest.approx(180e-6)
    # Idle [0,100) [300,600) [760,1000); the entry spans cover [0,330)
    # and [500,790) of it: 100 + 30 + 100 + 30 of 640 ns.
    assert r["device_idle_ms_per_call_in_entry"][0] == \
        pytest.approx(130e-6)
    assert r["device_idle_ms_per_call_elsewhere"][0] == \
        pytest.approx(190e-6)
    # The compile span's time is its own, not its parent's.
    assert r["host_ms_per_call_by_entry_span"] == pytest.approx({
        "entry.prepare": 10e-6, "entry.resolve": 10e-6,
        "entry.compile": 20e-6, "entry.dispatch": 10e-6,
        "entry.fetch_overflow": 255e-6, "entry.finish": 5e-6})
    assert r["out_rows"] == 200


def test_reduce_leaves_out_what_lies_outside_the_window():
    r = scopes.reduce(synthetic(), 0, 500)
    assert r["calls"] == 1
    assert "partition" not in r["device_ms_per_call_by_layer"][0]
    assert r["out_rows"] == 100


def kernel_input(labels_ns, calls=2):
    s = trace.Summary(1000, calls, {0: 1}, {}, {0: dict(labels_ns)}, [])
    return LayerInput(s, calls, 1, 0, None, 0.0, None)


@pytest.mark.parametrize("metric,ms", [
    ("local_join.scan_kernel_ms_per_call", 0.3),
    ("local_join.compact_kernel_ms_per_call", 0.5),
    ("local_join.expand_kernel_ms_per_call", 1.5),
])
def test_kernel_readers(metric, ms):
    inp = kernel_input({"join_scan_r.1 tpu_custom_call": 2e5,
                        "join_scan_f.1 tpu_custom_call": 4e5,
                        "compact_planes.2 tpu_custom_call": 4e5,
                        "compact_planes.3 tpu_custom_call": 6e5,
                        "expand_b8.1 tpu_custom_call": 3e6,
                        "sort.30 sort": 9e6})
    assert reader(metric)(inp) == pytest.approx(ms)


@pytest.mark.parametrize("metric", [
    "local_join.scan_kernel_ms_per_call",
    "local_join.compact_kernel_ms_per_call",
    "local_join.expand_kernel_ms_per_call",
])
def test_kernel_readers_find_nothing_in_unnamed_kernels(metric):
    """Kernels named after their enclosing function (a program without
    kernel names) give no reading, and no zero."""
    inp = kernel_input({"branch_1_fun.1 tpu_custom_call": 3e6,
                        "step.5 tpu_custom_call": 1e5,
                        "expand.3 fusion kLoop": 1e5})
    assert reader(metric)(inp) is None
    assert reader(metric)(LayerInput(None, 0, 1, 0, None, 0.0, None)) \
        is None


def test_kernel_name():
    assert kernels.kernel_name("expand_b8.12 tpu_custom_call") == \
        "expand_b8"
    assert kernels.kernel_name("expand tpu_custom_call") == "expand"
    assert kernels.kernel_name("expand.3 fusion kLoop") is None


def test_a_kept_cpu_trace_holds_the_entry_spans(tmp_path):
    """A traced run of the harness on the CPU, its trace kept: the
    entry's spans lie in the window, and ``out_rows`` is the program's
    output capacity. (A CPU trace has no TPU plane, so no device
    numbers.)"""
    cell = tiny_cell("uniform10m.oneshot")
    with scopes.keeping_trace(str(tmp_path)) as kept:
        r = run.run(cell, seed=2**33 + 5, seconds=0.5, trace=True,
                    require_tpu=False)
    assert r["correct"]
    (path,) = kept
    red = scopes.reduce_file(path, chips=1)
    assert red["calls"] >= 1
    assert red["device_ms_per_call_by_layer"] == {}
    assert red["device_ms_per_call_by_scope"] == {}
    assert {"entry.prepare", "entry.resolve", "entry.dispatch",
            "entry.fetch_overflow", "entry.finish"} <= set(
        red["host_ms_per_call_by_entry_span"])
    # 6,000 probe rows a side, the default 1.2x output sizing.
    assert red["out_rows"] == 7200
