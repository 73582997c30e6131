"""Table generators, one module each, named by a config's ``generator``.

A generator module has ``tables(cfg, key) -> {"build": side, "probe":
side}``, each side ``{"columns": {name: array}, "valid": bool array}``,
traceable under ``jit`` with shapes fixed by ``cfg`` alone, so that
every seed gives the same shapes.
"""

from __future__ import annotations

import importlib


def generator(name: str):
    return importlib.import_module(f"joinbench.data.{name}")
