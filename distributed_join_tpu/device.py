"""The two facts about the device every entry point shares.

- :func:`on_tpu` — the ONE test of "this process runs on a TPU" that
  the kernel dispatch (``ops/kernel_config.py``) and the hardware
  ragged all-to-all (``parallel/communicator.py``) both read. It asks
  the default device for its platform, not the backend for its name,
  so a plug-in that names its backend differently still counts as the
  chip it drives.
- :func:`enable_compile_cache` — JAX's persistent compilation cache,
  placed from outside: ``JAX_COMPILATION_CACHE_DIR`` when it is set
  (JAX reads it at import; nothing else is set), else a fixed
  directory inside the checkout — never a temp name, a pid or a time.
  It also strips the checkout's path from the source locations that
  the Pallas kernels carry inside their serialized Mosaic modules:
  JAX's key drops the program's own debug info but not theirs, so
  without it every checkout path (a driver's clone, a ``git archive``
  copy) keys its own entries and never reads the chip's warm cache.
"""

from __future__ import annotations

import os
import re

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def on_tpu() -> bool:
    """Whether the default device is a TPU (initializes the backend)."""
    return jax.devices()[0].platform == "tpu"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory (see the module docstring for where it lives)."""
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
