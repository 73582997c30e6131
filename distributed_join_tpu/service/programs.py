"""The join program cache — compiled executables keyed by signature.

``make_distributed_join`` builds a fresh closure and a fresh ``jax.jit``
wrapper per call, so every query — and every rung of the capacity retry
ladder — re-traces and re-compiles before a single row moves
(``distributed_join.py``: "Every retry recompiles"). The serving answer
is the :class:`JoinProgramCache`: a canonical :class:`JoinSignature`
over everything that determines the compiled program (table schemas and
capacities, key columns, shuffle mode, over-decomposition, the full
capacity contract including the ladder rung's sizing, skew policy,
compression bits, telemetry/integrity switches) maps to ONE resident
executable. A repeat query is a dict lookup and a dispatch; a retry
rung whose sizing was seen before reuses its executable instead of
paying trace + compile again.

Two storage tiers:

- **memory** (always): signature -> :class:`CachedProgram`. A hit adds
  zero traces and zero compiles (tests/test_service.py locks the
  program count).
- **disk** (opt-in, ``persist_dir=``): the AOT path that
  ``scripts/check_overlap.py --aot-tpu`` proves out —
  ``jit(...).lower(...).compile()`` then
  ``jax.experimental.serialize_executable`` — writes each executable
  next to its canonical signature, so a RESTARTED server skips even
  the first trace. Executables are backend- and topology-bound; a blob
  that fails to load (new jaxlib, different mesh) silently falls back
  to a fresh trace — persistence is an optimization, never a
  correctness dependency.

The chipless AOT helper (:func:`aot_compile_chipless`) lives here too:
compiling the full join for a TPU topology this host does not have is
the same lower-and-compile path the persistence tier uses, and
``scripts/check_overlap.py`` is a thin wrapper over it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
import json
import os
import pickle
from typing import Callable, Optional

from distributed_join_tpu import telemetry
from distributed_join_tpu.parallel.communicator import Communicator
from distributed_join_tpu.parallel.distributed_join import (
    JOIN_METRICS_SHARDED_OUT,
    JOIN_SHARDED_OUT,
    make_join_step,
)

# Every make_join_step option participates in the signature, at its
# default when the caller did not pass it — derived from the function
# signature itself so a new knob can never silently alias two distinct
# programs to one cache entry.
_STEP_DEFAULTS = {
    name: p.default
    for name, p in inspect.signature(make_join_step).parameters.items()
    if p.default is not inspect.Parameter.empty
}

PROGRAM_SUFFIX = ".joinprog"


def _canon(v):
    """Hashable, JSON-stable form of one option value."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, dict):
        return tuple(sorted((str(k), _canon(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_canon(x) for x in v)
    # KernelConfig and friends: flat frozen dataclasses whose repr is
    # total — good enough to DISTINGUISH programs, which is all a
    # cache key must do.
    return repr(v)


def spec_digest(doc) -> str:
    """Stable content digest of a JSON-ish document (dicts, lists,
    scalars), through the same canonicalizer the program-cache
    signatures use. Table manifests (docs/FLEET.md) stamp their
    register spec / delta payloads with this so a rebuilt holder can
    prove it replayed the same rows the original held."""
    canon = _canon(doc)
    return hashlib.sha256(
        json.dumps(canon, sort_keys=True, default=str).encode()
    ).hexdigest()


def atomic_write_json(path: str, doc) -> str:
    """Crash-safe JSON write: tmp file + ``os.replace`` so a reader
    never observes a torn document. The same discipline the program
    persist tier and the join manifest use; table manifests and the
    router directory (service/fleet.py) share it."""
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def _schema_of(table) -> tuple:
    """(name, dtype, trailing-dims) triples, name-sorted — the aval
    identity of a Table (or a Table of ShapeDtypeStructs) minus the
    shared row capacity, which is carried separately."""
    return tuple(sorted(
        (name, str(c.dtype), tuple(int(d) for d in c.shape[1:]))
        for name, c in table.columns.items()
    ))


@dataclasses.dataclass(frozen=True)
class JoinSignature:
    """The canonical identity of one compiled join program.

    Two calls with equal signatures compile to the same executable;
    two calls that could compile differently MUST differ somewhere in
    here. ``options`` is the name-sorted tuple of every
    ``make_join_step`` option (defaults filled in), so telemetry
    on/off, integrity on/off, shuffle mode, ladder-rung sizing, skew
    capacities and compression bits all key distinct entries.
    """

    n_ranks: int
    build_schema: tuple
    build_capacity: int
    probe_schema: tuple
    probe_capacity: int
    options: tuple
    # Slow-tier topology (hierarchical shuffle, docs/HIERARCHY.md):
    # two slice-splits of the same rank count compile DIFFERENT
    # routing programs, so the split is part of the program identity.
    # 1 = flat mesh.
    n_slices: int = 1

    @classmethod
    def of(cls, comm: Communicator, build, probe,
           **opts) -> "JoinSignature":
        unknown = set(opts) - set(_STEP_DEFAULTS)
        if unknown:
            raise TypeError(
                f"unknown join option(s) {sorted(unknown)}; the "
                "signature covers make_join_step's keywords"
            )
        merged = {**_STEP_DEFAULTS, **opts}
        return cls(
            n_ranks=comm.n_ranks,
            build_schema=_schema_of(build),
            build_capacity=int(
                next(iter(build.columns.values())).shape[0]),
            probe_schema=_schema_of(probe),
            probe_capacity=int(
                next(iter(probe.columns.values())).shape[0]),
            options=tuple(sorted(
                (name, _canon(v)) for name, v in merged.items()
            )),
            n_slices=int(getattr(comm, "n_slices", 1)),
        )

    def canonical(self) -> dict:
        """JSON-shaped form (what the on-disk blob binds to)."""
        return dataclasses.asdict(self)

    def digest(self) -> str:
        return hashlib.sha256(
            json.dumps(self.canonical(), sort_keys=True,
                       default=str).encode()
        ).hexdigest()


@dataclasses.dataclass
class CachedProgram:
    """One resident executable plus the call convention around it.

    ``raw`` is the dispatchable program — a ``jax.jit`` callable
    (trace tier), a ``jax.stages.Compiled`` (AOT tier), or a
    deserialized loaded executable (disk tier). ``with_aux`` marks the
    ``(JoinResult, Metrics)`` aux convention of the metrics/integrity
    programs; the wrapper re-attaches the host-side ``telemetry``
    attribute exactly as ``make_distributed_join`` does, so callers
    cannot tell a cached program from a fresh one.
    """

    signature: JoinSignature
    raw: Callable
    with_aux: bool
    source: str                  # "trace" | "disk"
    persisted: bool = False

    def __call__(self, *args):
        out = self.raw(*args)
        if not self.with_aux:
            return out
        res, metrics = out
        object.__setattr__(res, "telemetry", metrics)
        return res


class JoinProgramCache:
    """Executable cache for one communicator's mesh.

    The cache is keyed on :class:`JoinSignature` — never on table
    CONTENTS — so any stream of same-shaped queries shares one
    program. ``with_metrics=None`` resolves from the telemetry session
    exactly like ``make_distributed_join``, which means a session flip
    mid-stream keys a separate (instrumented) entry rather than
    silently reusing the seed program.

    Not thread-safe by itself; :class:`..server.JoinService` serializes
    access (one mesh executes one program at a time anyway).
    """

    def __init__(self, comm: Communicator,
                 persist_dir: Optional[str] = None,
                 max_entries: Optional[int] = None):
        from collections import OrderedDict

        self.comm = comm
        self.persist_dir = persist_dir
        # None = unbounded (library use); a long-lived server MUST
        # bound it — the wire lets every request choose its own table
        # shape, and each distinct shape is a resident executable.
        self.max_entries = max_entries
        self._entries = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.traces = 0
        self.disk_loads = 0
        self.disk_load_failures = 0
        self.disk_persists = 0
        self.lru_evictions = 0
        self.integrity_evictions = 0
        self.generation_evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> dict:
        """Operator-facing occupancy + tier counters (the ``stats``
        wire op and Prometheus exposition surface every field —
        PR 6's LRU bound and disk tier are invisible otherwise)."""
        return {
            "entries": len(self._entries),
            "max_entries": self.max_entries,
            "occupancy": (round(len(self._entries) / self.max_entries,
                                4)
                          if self.max_entries else None),
            "hits": self.hits,
            "misses": self.misses,
            "traces": self.traces,
            "disk_loads": self.disk_loads,
            "disk_load_failures": self.disk_load_failures,
            "disk_persists": self.disk_persists,
            "lru_evictions": self.lru_evictions,
            "integrity_evictions": self.integrity_evictions,
            "generation_evictions": self.generation_evictions,
        }

    def signature(self, build, probe, with_metrics=None,
                  **opts) -> JoinSignature:
        """The signature :meth:`get` would key this call under (the
        ``with_metrics=None`` session resolution applied)."""
        if with_metrics is None:
            with_metrics = telemetry.enabled()
        return JoinSignature.of(self.comm, build, probe,
                                with_metrics=with_metrics, **opts)

    def get(self, build, probe, with_metrics=None, **opts):
        """Return ``(program, hit)`` for this build/probe shape and
        option set — tracing and compiling only on a cold miss."""
        if with_metrics is None:
            with_metrics = telemetry.enabled()
        sig = JoinSignature.of(self.comm, build, probe,
                               with_metrics=with_metrics, **opts)
        entry = self._entries.get(sig)
        if entry is not None:
            self.hits += 1
            self._entries.move_to_end(sig)
            return entry, True
        self.misses += 1
        entry = self._load_persisted(sig)
        if entry is None:
            entry = self._build(sig, build, probe,
                                dict(opts, with_metrics=with_metrics))
        self._admit_entry(sig, entry)
        return entry, False

    def _admit_entry(self, sig, entry) -> None:
        self._entries[sig] = entry
        if self.max_entries is not None \
                and len(self._entries) > self.max_entries:
            # Least-recently-USED memory eviction; the disk blob (if
            # any) stays, so a re-miss reloads instead of re-tracing.
            old_sig, _ = self._entries.popitem(last=False)
            self.lru_evictions += 1
            telemetry.event("program_cache_lru_evict",
                            digest=old_sig.digest()[:12],
                            entries=len(self._entries))

    def get_keyed(self, sig, builder, *, example_args=None,
                  with_aux: bool = False):
        """Generic admission for programs that are not a plain
        (build, probe) join — the resident prep/merge/probe-only
        programs of :mod:`..service.resident`. ``sig`` is any frozen
        signature object with ``digest()``/``canonical()`` (and a
        name-sorted ``options`` tuple when the program carries an aux
        Metrics output); ``builder()`` returns the dispatchable
        program on a cold miss. Same memory LRU + disk-AOT tiers and
        the same hit/miss/trace counters as :meth:`get`;
        ``example_args`` (the program's real call arguments) arms the
        persist tier's lower+compile."""
        entry = self._entries.get(sig)
        if entry is not None:
            self.hits += 1
            self._entries.move_to_end(sig)
            return entry, True
        self.misses += 1
        entry = self._load_persisted(sig)
        if entry is None:
            raw = builder()
            self.traces += 1
            telemetry.event("program_cache_trace",
                            digest=sig.digest()[:12],
                            entries=len(self._entries) + 1)
            persisted = False
            if self.persist_dir is not None and hasattr(raw, "lower") \
                    and example_args is not None:
                try:
                    compiled = self._aot_compile(raw, *example_args)
                    persisted = self._persist(sig, compiled)
                    raw = compiled
                except Exception as exc:  # pragma: no cover - backend-dependent
                    telemetry.event("program_cache_persist_failed",
                                    digest=sig.digest()[:12],
                                    error=f"{type(exc).__name__}: "
                                          f"{exc}")
            entry = CachedProgram(sig, raw, with_aux, "trace",
                                  persisted=persisted)
        self._admit_entry(sig, entry)
        return entry, False

    def predict_hit(self, digest: str) -> dict:
        """Cache-hit prediction for a plan digest (the ``explain``
        wire op's dry-run verdict): would this signature dispatch a
        resident executable, rehydrate a persisted blob, or pay a
        fresh trace? Read-only — never loads or traces."""
        # Snapshot before iterating: the explain wire op calls this
        # WITHOUT the service exec lock, and a concurrent join may be
        # inserting/evicting entries (dict-changed-size mid-iteration
        # otherwise; a momentarily stale verdict is fine, a crash not).
        resident = any(sig.digest() == digest
                       for sig in list(self._entries))
        persisted = bool(
            self.persist_dir is not None
            and os.path.exists(os.path.join(
                self.persist_dir, digest + PROGRAM_SUFFIX)))
        return {
            "resident": resident,
            "persisted": persisted,
            "would_trace": not (resident or persisted),
        }

    def evict(self, signature: JoinSignature,
              reason: str = "integrity") -> bool:
        """Drop one entry (memory AND its disk blob). The integrity
        retry rung uses this: a wire-corruption verdict taints the
        resident program — injected corruption is woven at trace time,
        so only a RE-trace is guaranteed to face a fresh schedule —
        and the corrupt-adjacent blob must not be reloaded either.
        ``reason="integrity"`` (the only production caller) is counted
        so operators can see taint-driven churn in ``stats``."""
        dropped = self._entries.pop(signature, None) is not None
        if self.persist_dir is not None:
            try:
                os.unlink(self._blob_path(signature))
                dropped = True
            except OSError:
                pass
        if dropped and reason == "integrity":
            self.integrity_evictions += 1
        elif dropped and reason == "generation":
            # A resident table's generation bump taints exactly the
            # probe-only entries compiled against the old build image
            # (service/resident.py) — counted so operators can see
            # delta-driven churn next to integrity churn.
            self.generation_evictions += 1
        return dropped

    def clear(self) -> None:
        self._entries.clear()

    # -- build + persistence tiers ------------------------------------

    def _build(self, sig: JoinSignature, build, probe,
               opts: dict) -> CachedProgram:
        with_aux = bool(opts.get("with_metrics")
                        or opts.get("with_integrity"))
        sharded_out = (JOIN_METRICS_SHARDED_OUT if with_aux
                       else JOIN_SHARDED_OUT)
        step = make_join_step(self.comm, **opts)
        raw = self.comm.spmd(step, sharded_out=sharded_out)
        self.traces += 1
        telemetry.event("program_cache_trace", digest=sig.digest()[:12],
                        entries=len(self._entries) + 1)
        persisted = False
        if self.persist_dir is not None and hasattr(raw, "lower"):
            # The AOT tier: lower+compile now (the jit wrapper would
            # have paid the same compile on first dispatch) and keep
            # the Compiled as the dispatch target so it can also be
            # serialized. Wrapped communicators whose spmd returns a
            # plain callable (fault injection) skip this tier.
            try:
                compiled = self._aot_compile(raw, build, probe)
                persisted = self._persist(sig, compiled)
                raw = compiled
            except Exception as exc:  # pragma: no cover - backend-dependent
                telemetry.event("program_cache_persist_failed",
                                digest=sig.digest()[:12],
                                error=f"{type(exc).__name__}: {exc}")
        return CachedProgram(sig, raw, with_aux, "trace",
                             persisted=persisted)

    def _devices(self):
        """The mesh a blob must load onto (None: every device). A
        replica on a subset of the host's devices would otherwise get
        the executable loaded across all of them."""
        mesh = getattr(self.comm, "mesh", None)
        return None if mesh is None else list(mesh.devices.flat)

    def _blob_path(self, sig: JoinSignature) -> str:
        return os.path.join(self.persist_dir,
                            sig.digest() + PROGRAM_SUFFIX)

    @staticmethod
    def _aot_compile(raw, *example_args):
        """Lower+compile for the persistence tier with jax's OWN
        persistent compilation cache bypassed: an executable
        rehydrated from that cache serializes into a blob whose CPU
        object symbols are missing (observed on jaxlib 0.4.37 —
        deserialize fails with "Symbols not found"), so a
        self-contained blob needs a real compile."""
        import jax

        # jax memoizes the "is the persistent cache used" decision at
        # first compile (compilation_cache._cache_checked), so merely
        # clearing the config flag is not enough once any compile ran
        # warm — reset the cache module around this one compile, then
        # reset again so later compiles re-initialize with the
        # restored settings. Private API by necessity; if it moves,
        # the AttributeError lands in _build's persist guard and the
        # entry simply stays memory-tier.
        from jax._src import compilation_cache

        prev = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            compilation_cache.reset_cache()
            return raw.lower(*example_args).compile()
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
            compilation_cache.reset_cache()

    def _persist(self, sig: JoinSignature, compiled) -> bool:
        import jax
        from jax.experimental import serialize_executable

        blob = serialize_executable.serialize(compiled)
        # Loadability check NOW, not at restart: a blob that cannot
        # deserialize is a silent trace-per-restart, the exact cost
        # this tier exists to remove.
        serialize_executable.deserialize_and_load(
            *blob, execution_devices=self._devices())
        os.makedirs(self.persist_dir, exist_ok=True)
        path = self._blob_path(sig)
        payload = {
            "signature": sig.canonical(),
            "backend": jax.default_backend(),
            "program": blob,
        }
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            pickle.dump(payload, f)
        os.replace(tmp, path)
        self.disk_persists += 1
        return True

    def _load_persisted(self, sig: JoinSignature):
        if self.persist_dir is None:
            return None
        path = self._blob_path(sig)
        if not os.path.exists(path):
            return None
        import jax
        from jax.experimental import serialize_executable

        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
            if (payload.get("signature") != sig.canonical()
                    or payload.get("backend") != jax.default_backend()):
                # A foreign/stale blob degrading to a miss is still a
                # disk-tier event operators should see.
                self.disk_load_failures += 1
                return None
            raw = serialize_executable.deserialize_and_load(
                *payload["program"], execution_devices=self._devices())
        except Exception as exc:
            # A stale blob (jaxlib bump, different device topology) is
            # a cache miss, not an outage.
            self.disk_load_failures += 1
            telemetry.event("program_cache_load_failed", path=path,
                            error=f"{type(exc).__name__}: {exc}")
            return None
        self.disk_loads += 1
        telemetry.event("program_cache_disk_load",
                        digest=sig.digest()[:12])
        with_aux = bool(dict(sig.options).get("with_metrics")
                        or dict(sig.options).get("with_integrity"))
        return CachedProgram(sig, raw, with_aux, "disk", persisted=True)


# -- chipless AOT (the check_overlap --aot-tpu path, factored) ---------


AOT_TOPOLOGY = "v5e:2x4"


def chipless_tpu_communicator(topology: str = AOT_TOPOLOGY):
    """A :class:`TpuCommunicator` over a CHIPLESS AOT topology — the
    terminal compiler needs device descriptions, not devices, so the
    full 8-rank join compiles (and serializes) on any host."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from distributed_join_tpu.parallel.communicator import (
        TpuCommunicator,
    )

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=topology)
    devs = np.array(topo.devices)
    mesh = Mesh(devs.reshape(devs.size), ("ranks",))
    return TpuCommunicator(mesh=mesh)


def abstract_join_tables(comm, rows: int, payload: str = "payload"):
    """Abstract (ShapeDtypeStruct) build/probe Tables for AOT lowering:
    the int64 key + int64 payload layout of the generators, row-sharded
    over ``comm``'s mesh. ``rows`` is the GLOBAL row count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_join_tpu.table import Table

    sh = NamedSharding(comm.mesh, P(comm.axis_name))

    def tbl(payload_name):
        return Table(
            {"key": jax.ShapeDtypeStruct((rows,), jnp.int64,
                                         sharding=sh),
             payload_name: jax.ShapeDtypeStruct((rows,), jnp.int64,
                                                sharding=sh)},
            jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=sh),
        )

    return tbl("build_" + payload), tbl("probe_" + payload)


def aot_compile_chipless(shuffle: str = "padded",
                         rows_per_rank: int = 65536,
                         over_decomposition: int = 2,
                         out_capacity_factor: float = 3.0,
                         topology: str = AOT_TOPOLOGY,
                         **opts):
    """Lower + compile the full distributed join for a chipless TPU
    topology and return the ``jax.stages.Compiled`` (``.as_text()`` is
    the scheduled HLO). This is the persistence tier's lower-and-
    compile path pointed at hardware the host does not have —
    ``scripts/check_overlap.py --aot-tpu`` is a thin wrapper."""
    from distributed_join_tpu.parallel.distributed_join import (
        make_distributed_join,
    )

    comm = chipless_tpu_communicator(topology)
    build, probe = abstract_join_tables(
        comm, rows_per_rank * comm.n_ranks)
    fn = make_distributed_join(
        comm, key="key", over_decomposition=over_decomposition,
        out_capacity_factor=out_capacity_factor, shuffle=shuffle,
        **opts)
    return fn.lower(build, probe).compile()
