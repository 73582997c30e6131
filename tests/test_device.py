"""The device facts every entry point shares (distributed_join_tpu.device)
and the entry points that must refuse to run without a chip."""

import os
import shutil
import subprocess
import sys

import jax

from distributed_join_tpu import device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, cwd=ROOT, **env):
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else code_or_argv)
    return subprocess.run(argv, cwd=cwd, capture_output=True, text=True,
                          timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_on_tpu_is_false_on_the_cpu_mesh():
    assert device.on_tpu() is False


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    assert device.DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    try:
        assert device.enable_compile_cache() == device.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == \
            device.DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_env_places_the_cache(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets no other
    directory and the compiled entries land there."""
    where = tmp_path / "cache"
    p = _run(
        "import jax, jax.numpy as jnp\n"
        "from distributed_join_tpu import device\n"
        "d = device.enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
        "print(d, jax.config.jax_compilation_cache_dir)\n",
        JAX_COMPILATION_CACHE_DIR=str(where))
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(where), str(where)]
    assert any(where.iterdir())


def test_kernel_program_text_is_the_same_from_any_checkout(tmp_path):
    """The Pallas kernels' Mosaic payloads carry source locations that
    JAX's cache key does not strip; the helper strips the checkout's
    path from them, so a copy elsewhere keys the same cache entries."""
    shutil.copytree(os.path.join(ROOT, "distributed_join_tpu"),
                    tmp_path / "distributed_join_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = (
        "import hashlib, jax, jax.numpy as jnp\n"
        "from distributed_join_tpu import device\n"
        "from distributed_join_tpu.ops.scan_pallas import join_scans\n"
        "device.enable_compile_cache()\n"
        "spec = [jax.ShapeDtypeStruct((4096,), d)"
        " for d in (jnp.int8, jnp.bool_)]\n"
        "low = jax.jit(join_scans).trace(*spec)"
        ".lower(lowering_platforms=('tpu',))\n"
        "text = low.as_text()\n"
        "assert 'tpu_custom_call' in text\n"
        "print(hashlib.sha256(text.encode()).hexdigest())\n")
    here, there = _run(code), _run(code, cwd=tmp_path)
    assert here.returncode == 0, here.stderr
    assert there.returncode == 0, there.stderr
    assert here.stdout == there.stdout


def test_chip_smoke_refuses_without_a_tpu():
    p = _run([sys.executable, "chip_smoke.py"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_launcher_parent_never_initializes_a_backend():
    """tpu-launch spawns the processes that own the chips; a parent
    holding a backend would take the chip from its children."""
    p = _run(
        "import sys\n"
        "from distributed_join_tpu.benchmarks import launch\n"
        "rc = launch.main(['--num-processes', '2', '--', sys.executable,"
        " '-c', 'pass'])\n"
        "from jax._src import xla_bridge\n"
        "print(rc, len(xla_bridge._backends))\n")
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == ["0", "0"]

