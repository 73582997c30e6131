"""Test harness: 8 virtual CPU devices, no TPU required.

The reference can only test multi-rank behavior with real GPUs under
mpirun (SURVEY.md §4). JAX lets us do better: forcing the host platform
to present 8 virtual devices runs the *identical* shard_map/collective
program with real all-to-all semantics on CPU.

The platform is flipped via ``jax.config`` as well as the environment,
so the tests run on the CPU even where jax was imported first.
XLA_FLAGS is read at backend-creation time, so mutating it here
(before the first ``jax.devices()``) works.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The seeded tables of the capacity-margin and corruption tests
# (test_join_types, test_sortpath, test_faults, test_chaos,
# test_fleet) were sized under the pre-0.5 threefry stream; JAX 0.5
# made the partitionable stream the default, which draws other keys
# from the same seeds and tips those margins. Pin the stream the
# margins were sized for; the drivers and chip_smoke use the default.
jax.config.update("jax_threefry_partitionable", False)

# Persistent compilation cache: most of the suite's wall time is XLA
# compiling the same 8-device shard_map programs run after run. First
# run populates, repeat runs replay. JAX_COMPILATION_CACHE_DIR places
# it; unset, it is the checkout's .jax_cache/ (distributed_join_tpu.
# device). Safe to delete the dir at any time.
from distributed_join_tpu.device import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
