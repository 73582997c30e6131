"""Device-side metrics: counters that ride the compiled SPMD step.

Host callbacks inside ``jit`` are forbidden on this path (they poison
the dispatch stream — see
``faults.validate_ragged_plan``'s design notes for the one debug-mode
exception). Instead, hot-path values are accumulated as traced scalars
on a :class:`MetricsTape` while the step TRACES, stacked into one
int64 summary vector, cross-rank aggregated with a single
``Communicator.all_gather`` at step end, and returned as an auxiliary
:class:`Metrics` pytree OUTPUT of the compiled program. The host
fetches the whole (n_ranks, n_metrics) block with one transfer, after
the timed region (``telemetry.emit_metrics``).

Metric names use dotted scopes (``build.rows_shuffled``,
``probe.wire_bytes``); the reduction across ranks is SUM unless the
name ends in ``_min``/``_max`` (e.g. ``build.overflow_margin_min``,
the tightest per-bucket headroom seen on any rank — summing margins
would be meaningless). Units and the full metric catalog live in
docs/OBSERVABILITY.md.

Telemetry-off contract: ``make_join_step(with_metrics=False)`` (the
default) never constructs a tape, so the compiled program, its output
treedef, and its program count are bit-identical to the seed
(tests/test_telemetry.py locks this).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Metrics:
    """The aux output pytree: ``values[r, i]`` is metric ``names[i]``
    on rank ``r`` (already all-gathered, so every rank holds the full
    block). ``names`` is static treedef metadata — two programs with
    different metric sets have different treedefs, loudly."""

    names: tuple
    values: jax.Array  # (n_ranks, n_metrics) int64

    def to_dict(self) -> dict:
        """Host-side summary (ONE device transfer): per-rank values
        plus the per-metric cross-rank reduction (sum, or min/max by
        name suffix). Wire-integrity digest lanes (``*.integrity.*``,
        parallel/integrity.py) are per-(rank, peer) checksums — no
        cross-rank reduction is meaningful, so they appear only in
        ``per_rank`` (where ``verify_digests`` reads them)."""
        import numpy as np

        vals = np.asarray(self.values)
        per_rank = {n: [int(v) for v in vals[:, i]]
                    for i, n in enumerate(self.names)}
        reduced = {}
        for n, v in per_rank.items():
            if ".integrity." in n:
                continue
            if n.endswith("_min"):
                reduced[n] = min(v)
            elif n.endswith("_max"):
                reduced[n] = max(v)
            else:
                reduced[n] = sum(v)
        return {"n_ranks": int(vals.shape[0]), "per_rank": per_rank,
                "reduced": reduced}


jax.tree_util.register_dataclass(
    Metrics, data_fields=["values"], meta_fields=["names"]
)


class MetricsTape:
    """Trace-time accumulator. Values may be Python ints (static —
    e.g. padded-mode wire bytes, the retry attempt index) or traced
    scalars (ragged send totals, match counts); both fold into the
    same int64 summary vector. ``scoped("build")`` returns a view
    writing ``build.``-prefixed names into the SAME storage, so the
    shuffle layer stays ignorant of which side it is moving."""

    def __init__(self, _store: Optional[dict] = None, _prefix: str = ""):
        self._store = {} if _store is None else _store
        self._prefix = _prefix

    def scoped(self, prefix: str) -> "MetricsTape":
        return MetricsTape(self._store, f"{self._prefix}{prefix}.")

    def add(self, name: str, value) -> None:
        """Sum-accumulate ``value`` into ``name`` (per rank)."""
        key = self._prefix + name
        prev = self._store.get(key)
        self._store[key] = value if prev is None else prev + value

    def record_min(self, name: str, value) -> None:
        """Keep the minimum seen; ``name`` must end in ``_min`` so the
        cross-rank reduction minimizes too."""
        key = self._prefix + name
        prev = self._store.get(key)
        self._store[key] = (
            value if prev is None else jnp.minimum(prev, value)
        )

    def gathered(self, comm) -> Metrics:
        """Step-end aggregation: stack the per-rank summary vector and
        all_gather it once — the only collective telemetry adds to the
        program."""
        names = tuple(sorted(self._store))
        vec = jnp.stack([
            jnp.asarray(self._store[n]).astype(jnp.int64).reshape(())
            for n in names
        ])
        g = comm.all_gather_replicated(comm.pvary(vec)[None, :])
        return Metrics(names=names, values=g)
