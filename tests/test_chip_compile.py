"""The main path's Pallas kernels, compiled for a described v5e chip.

Interpret-mode tests cannot see what the chip's compiler refuses: VMEM
limits, tiling, unaligned slices. These tests compile each kernel with
``interpret=False`` at the shapes the single-chip 10M x 10M join (BASELINE
config 1) hands it — a 20M-row merged domain and the default 1.2x output
capacity of 12M slots — and the four-chip ragged exchange at config
2's 12.5M rows per rank, for chips that are described, not attached
(on-chip-measurement guide, section 2). Nothing runs.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and the test runner's workers all
import this file.
"""

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


def test_expand_gather_fused_build(one_chip):
    _compile(lambda S, lo, b, *cols: expand_gather(
                 S, list(cols), OUT, lo=lo, build_cols=[b]),
             _spec(one_chip, OUT, jnp.int32),
             _spec(one_chip, OUT, jnp.int32),
             _spec(one_chip, BUILD, jnp.uint64),
             *[_spec(one_chip, OUT, jnp.uint64)] * 2)


def test_expand_gather_plain(one_chip):
    _compile(lambda S, *cols: expand_gather(S, list(cols), OUT),
             _spec(one_chip, OUT, jnp.int32),
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
