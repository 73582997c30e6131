"""The main path's Pallas kernels, compiled for a described v5e chip.

Interpret-mode tests cannot see what the chip's compiler refuses: VMEM
limits, tiling, unaligned slices. These tests compile each kernel with
``interpret=False`` at the shapes the single-chip 10M x 10M join (BASELINE
config 1) hands it — a 20M-row merged domain and the default 1.2x output
capacity of 12M slots — and the four-chip ragged exchange at config
2's 12.5M rows per rank, for chips that are described, not attached
(an ahead-of-time compile against a described TPU topology). The whole
one-chip join step,
compiled at a small size, shows the stage scopes and kernel names that
an XLA profile of the chip reads. Nothing runs.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and the test runner's workers all
import this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import distributed_join_tpu  # noqa: F401  (x64 on, as in the join)
from distributed_join_tpu.ops.compact_pallas import stream_compact
from distributed_join_tpu.ops.compact_planes import plane_stream_compact
from distributed_join_tpu.ops.expand_pallas import expand_gather
from distributed_join_tpu.ops.scan_pallas import join_scans

MERGED = 20_000_000      # build + probe rows of one 10M x 10M rank
BUILD = 10_000_000       # the matched-build pack's capacity
OUT = 12_000_000         # DEFAULT_OUT_CAPACITY_FACTOR (1.2) x 10M
RANK_ROWS = 12_500_000   # config 2's 50M rows over four chips
RECV = 20_000_000        # 1.6 (shuffle capacity factor) x RANK_ROWS


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # conftest turns the persistent cache on; an entry compiled for a
    # described chip cannot be read back here.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(one_chip, n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_join_scans(one_chip):
    _compile(lambda tag, first: join_scans(tag, first),
             _spec(one_chip, MERGED, jnp.int8),
             _spec(one_chip, MERGED, jnp.bool_))


@pytest.mark.parametrize("compact", [plane_stream_compact,
                                     stream_compact],
                         ids=["plane", "mxu"])
@pytest.mark.parametrize("lanes,capacity", [(4, OUT), (1, BUILD)],
                         ids=["records", "build_pack"])
def test_stream_compact(one_chip, compact, lanes, capacity):
    _compile(lambda mask, pos, *cols: compact(mask, pos, list(cols),
                                              capacity),
             _spec(one_chip, MERGED, jnp.bool_),
             _spec(one_chip, MERGED, jnp.int32),
             *[_spec(one_chip, MERGED, jnp.uint64)] * lanes)


def _live(one_chip):
    """The traced live-slot count (the join's int64 match total)."""
    return jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)


def test_expand_gather_fused_build(one_chip):
    _compile(lambda S, live, lo, b, *cols: expand_gather(
                 S, list(cols), OUT, live, lo=lo, build_cols=[b]),
             _spec(one_chip, OUT, jnp.int32),
             _live(one_chip),
             _spec(one_chip, OUT, jnp.int32),
             _spec(one_chip, BUILD, jnp.uint64),
             *[_spec(one_chip, OUT, jnp.uint64)] * 2)


def test_expand_gather_plain(one_chip):
    _compile(lambda S, live, *cols: expand_gather(S, list(cols), OUT,
                                                  live),
             _spec(one_chip, OUT, jnp.int32),
             _live(one_chip),
             *[_spec(one_chip, OUT, jnp.uint64)] * 3)


def test_ragged_exchange_of_64bit_keys(topo, monkeypatch):
    """The four-chip ragged shuffle of one int64 column: the hardware
    ragged-all-to-all, and no lane-padded copy (a (rows, 2) uint32
    layout once needed 64x the column's bytes and ran out of HBM)."""
    import numpy as np
    from jax.sharding import Mesh

    from distributed_join_tpu import device
    from distributed_join_tpu.parallel.communicator import TpuCommunicator

    monkeypatch.setattr(device, "on_tpu", lambda: True)
    comm = TpuCommunicator(mesh=Mesh(np.array(topo.devices), ("ranks",)))
    n = comm.n_ranks
    rows = jax.ShapeDtypeStruct((n * RANK_ROWS,), jnp.int64,
                                sharding=NamedSharding(comm.mesh,
                                                       P("ranks")))
    share = RANK_ROWS // n

    def step(col):
        sizes = comm.pvary(jnp.full((n,), share, jnp.int32))
        offs = comm.pvary(jnp.arange(n, dtype=jnp.int32) * share)
        out = comm.pvary(jnp.zeros((RECV,), jnp.int64))
        return comm.ragged_all_to_all(col, out, offs, sizes, offs, sizes)

    compiled = comm.spmd(step).lower(rows).compile()
    assert "ragged-all-to-all" in compiled.as_text()
    column = RECV * 8
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * column


# -- the one-chip join step: stage scopes and kernel names -------------

JOIN_ROWS = 1024
STAGES = ("prepare", "sort", "scan", "compact", "expand")
KERNELS = {"join_scan_r", "join_scan_f", "compact_planes", "expand",
           "expand_b8"}
INSTRUCTION = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9\-]*)\(")


def _join_step_hlo(topo, monkeypatch, scopes=True, **opts):
    """The optimised HLO of the one-chip join step (the Pallas kernel
    pipeline, as on the chip); ``scopes=False`` traces it with every
    ``jax.named_scope`` turned into a no-op. ``opts`` go to the step."""
    import contextlib

    import numpy as np
    from jax.sharding import Mesh

    from distributed_join_tpu import device
    from distributed_join_tpu.parallel.communicator import TpuCommunicator
    from distributed_join_tpu.parallel.distributed_join import (
        make_distributed_join,
    )
    from distributed_join_tpu.service.programs import abstract_join_tables

    monkeypatch.setattr(device, "on_tpu", lambda: True)
    if not scopes:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    comm = TpuCommunicator(mesh=Mesh(np.array(topo.devices[:1]),
                                     ("ranks",)))
    build, probe = abstract_join_tables(comm, JOIN_ROWS)
    fn = make_distributed_join(comm, key="key", **opts)
    return fn.lower(build, probe).compile().as_text()


@pytest.fixture(scope="module")
def join_hlo(topo):
    with pytest.MonkeyPatch.context() as mp:
        return _join_step_hlo(topo, mp)


def _instructions(text):
    """``[(name, shape, opcode, line)]`` of every HLO instruction."""
    return [(*m.groups(), line) for line in text.splitlines()
            if (m := INSTRUCTION.match(line))]


def test_join_step_names_its_stages_and_kernels(join_hlo):
    """Every sort, Pallas kernel and concatenate of the local join
    carries a ``join/<stage>`` scope in its op_name, and each kernel
    instruction is named after its kernel."""
    kernels = set()
    seen = set()
    for name, _, opcode, line in _instructions(join_hlo):
        kernel = 'custom_call_target="tpu_custom_call"' in line
        if not (opcode in ("sort", "concatenate") or kernel):
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        assert op_name, line[:200]
        stages = [s for s in STAGES if f"/join/{s}/" in op_name.group(1)]
        assert stages, op_name.group(1)
        seen.update(stages)
        if kernel:
            kernels.add(re.sub(r"\.\d+$", "", name))
    assert kernels == KERNELS
    assert {"sort", "scan", "compact", "expand"} <= seen


def _kernels(text):
    return {re.sub(r"\.\d+$", "", name)
            for name, _, _, line in _instructions(text)
            if 'custom_call_target="tpu_custom_call"' in line}


def test_skew_step_names_the_heavy_hitter_kernels(topo, monkeypatch):
    """The skew step's heavy-key join runs its kernels under an ``hh_``
    prefix and compacts the heavy rows with ``hh_extract``; the normal
    path keeps its names, which ``local_join.*_kernel_ms_per_call``
    read. Blocks smaller than half the rows, so the compaction kernel
    runs."""
    text = _join_step_hlo(topo, monkeypatch, skew_threshold=0.01,
                          hh_probe_capacity=JOIN_ROWS // 4,
                          hh_build_capacity=JOIN_ROWS // 8,
                          hh_out_capacity=JOIN_ROWS // 2)
    hh = {"hh_" + k for k in KERNELS} | {"hh_extract"}
    assert _kernels(text) == KERNELS | hh


def test_scopes_leave_the_instructions_alone(topo, join_hlo, monkeypatch):
    """Scopes are metadata: the optimised program has the same
    instructions (names, shapes, opcodes, order) with and without
    them."""
    plain = _join_step_hlo(topo, monkeypatch, scopes=False)
    with_scopes = [i[:3] for i in _instructions(join_hlo)]
    assert with_scopes == [i[:3] for i in _instructions(plain)]
    assert len(with_scopes) > 1000
