"""Level-2 joinlint: the jaxpr collective-schedule checker.

The AST rules see syntax; this module sees the TRUTH the compiler will
schedule. Under the 8-virtual-device CPU mesh it traces the key
compiled programs — the full program family: the three shuffle modes,
the join step with and without metrics, the skew path, the typed
joins (left/full_outer/anti), the segmented sort, the hierarchical
2×4 mesh, aggregate pushdown in key and probe mode, the probe-only
resident dispatch, and the Q3 multi-operator query plan — with
abstract inputs (trace only, never compiled or run) and extracts each
jaxpr's ordered sequence of collective primitives. Three checks:

1. **golden schedule** — the sequence must equal the committed fixture
   in ``results/schedules/<program>.json``. Any reordering, any added
   or dropped collective fails loudly; intentional changes regenerate
   with ``analysis.lint --update-schedules`` and the diff shows up in
   review (the same workflow as the counter-signature baselines,
   telemetry/baselines.py).
2. **no host callbacks in a telemetry-off program** — unconditional,
   regen cannot bless it: the telemetry-off join is the seed hot path
   and a callback primitive in it means the parity contract
   (docs/OBSERVABILITY.md) is broken. This is also exactly what
   ``faults.validate_plans`` weaves in, so tracing under plan
   validation makes this check fire — the test for both.
3. **no cond-divergent collectives** — a ``lax.cond`` whose branches
   carry different collective sequences lets a data-dependent
   predicate (worse: a rank-varying one) steer ranks into different
   collective programs. SPMD requires the sequence to be identical on
   every rank; branch-divergent collectives are how that fails at the
   trace level. Branches with IDENTICAL collective subsequences pass.

Caveat recorded in each golden: the CPU mesh has no ragged-all-to-all
thunk, so ``shuffle='ragged'`` traces through the all-gather emulation
(``Communicator._ragged_emulate``) — the golden captures the CPU-mesh
schedule, which is the program every tier-1 test runs. A hardware
trace would show ``ragged_all_to_all`` primitives instead.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional

SCHEDULE_SCHEMA_VERSION = 1
DEFAULT_SCHEDULE_DIR = os.path.join("results", "schedules")
N_RANKS = 8
ROWS = 256  # global rows per side: 32/rank on the 8-device mesh

# Primitive names that ARE collectives (exact, or versioned suffixes).
COLLECTIVE_PRIMS = (
    "all_to_all", "all_gather", "ragged_all_to_all", "ppermute",
    "psum", "pbroadcast", "reduce_scatter", "collective_permute",
    "pmin", "pmax",
)


def is_collective_prim(name: str) -> bool:
    return any(name == p or name.startswith(p + "_")
               for p in COLLECTIVE_PRIMS)


def is_callback_prim(name: str) -> bool:
    return "callback" in name or name == "outside_call"


@dataclasses.dataclass
class ProgramSchedule:
    """One traced program's schedule facts."""

    program: str
    n_ranks: int
    telemetry_off: bool
    collectives: List[str]
    host_callbacks: List[str]
    cond_divergence: List[str]

    def golden(self) -> dict:
        return {
            "schema_version": SCHEDULE_SCHEMA_VERSION,
            "program": self.program,
            "n_ranks": self.n_ranks,
            "telemetry_off": self.telemetry_off,
            "collectives": self.collectives,
            "host_callbacks": self.host_callbacks,
        }


# -- jaxpr walking ----------------------------------------------------


def _subjaxprs(eqn):
    """Inner jaxprs of one eqn (pjit/shard_map/scan/while/cond/...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, Jaxpr):
                out.append(x)
    return out


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _walk_eqns(sub)


def collective_sequence(jaxpr) -> List[str]:
    """Ordered collective primitive names of a (possibly nested)
    jaxpr. Trace order is program order for collectives: XLA may
    overlap them with compute but never reorders collectives against
    each other without an explicit schedule pass."""
    # An invariant-typed all_gather is the same all-gather on the wire.
    return [e.primitive.name.removesuffix("_invariant")
            for e in _walk_eqns(jaxpr)
            if is_collective_prim(e.primitive.name)]


def callback_sequence(jaxpr) -> List[str]:
    return [e.primitive.name for e in _walk_eqns(jaxpr)
            if is_callback_prim(e.primitive.name)]


def cond_divergences(jaxpr) -> List[str]:
    """cond eqns whose branches carry different collective
    sequences (see module docstring, check 3)."""
    bad = []
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name != "cond":
            continue
        branches = eqn.params.get("branches", ())
        seqs = []
        for br in branches:
            from jax.extend.core import ClosedJaxpr

            j = br.jaxpr if isinstance(br, ClosedJaxpr) else br
            seqs.append(tuple(collective_sequence(j)))
        if len(set(seqs)) > 1:
            bad.append(
                "cond with branch-divergent collective sequences: "
                + " vs ".join(repr(list(s)) for s in seqs)
            )
    return bad


# -- the key programs -------------------------------------------------


def _abstract_table(cols):
    """An abstract (never-allocated) global Table: ``cols`` is
    (name, dtype) pairs, every column ROWS long plus the bool valid
    mask."""
    import jax
    import jax.numpy as jnp

    from distributed_join_tpu.table import Table

    c = {name: jax.ShapeDtypeStruct((ROWS,), dt) for name, dt in cols}
    return Table(c, jax.ShapeDtypeStruct((ROWS,), jnp.bool_))


def _abstract_tables():
    import jax.numpy as jnp

    def side(payload_name):
        return _abstract_table((("key", jnp.int64),
                                (payload_name, jnp.int32)))

    return side("build_payload"), side("probe_payload")


def _abstract_tpch_q3_tables():
    """Minimal abstract customer/orders/lineitem triple for the Q3
    plan, matching utils/tpch.py's unified key names and dtypes
    (int64 keys/prices, int32 dates)."""
    import jax.numpy as jnp

    customer = _abstract_table((("custkey", jnp.int64),
                                ("c_acctbal", jnp.int64)))
    orders = _abstract_table((("custkey", jnp.int64),
                              ("orderkey", jnp.int64),
                              ("o_orderdate", jnp.int32)))
    lineitem = _abstract_table((("orderkey", jnp.int64),
                                ("l_extendedprice", jnp.int64)))
    return customer, orders, lineitem


def key_programs(comm=None) -> Dict[str, dict]:
    """name -> {fn, args, telemetry_off} for every program the checker
    guards. Building the step functions is cheap; nothing traces until
    :func:`trace_program`."""
    from distributed_join_tpu.parallel.communicator import (
        TpuCommunicator,
    )
    from distributed_join_tpu.parallel.distributed_join import (
        JOIN_METRICS_SHARDED_OUT,
        JOIN_SHARDED_OUT,
        make_join_step,
    )

    comm = comm if comm is not None else TpuCommunicator(n_ranks=N_RANKS)
    build, probe = _abstract_tables()
    args = (build, probe)
    payloads = dict(build_payload=["build_payload"],
                    probe_payload=["probe_payload"])

    def spmd(step, metrics=False):
        return comm.spmd(step, sharded_out=(
            JOIN_METRICS_SHARDED_OUT if metrics else JOIN_SHARDED_OUT))

    progs = {}
    for mode in ("padded", "ragged", "ppermute"):
        progs[f"join_step_{mode}"] = {
            "fn": spmd(make_join_step(comm, shuffle=mode, **payloads)),
            "args": args, "telemetry_off": True,
        }
    progs["join_step_metrics"] = {
        "fn": spmd(make_join_step(comm, with_metrics=True, **payloads),
                   metrics=True),
        "args": args, "telemetry_off": False,
    }
    progs["join_step_skew"] = {
        "fn": spmd(make_join_step(comm, skew_threshold=0.2, **payloads)),
        "args": args, "telemetry_off": True,
    }
    # The typed-join family (docs/JOIN_TYPES.md): same shuffle spine,
    # different settle programs — left/full_outer emit the unmatched
    # sides, anti emits only build rows with no probe match.
    for join_type in ("left", "full_outer", "anti"):
        # Anti emits probe rows only — a build payload cannot be
        # honored and make_join_step refuses it loudly.
        pl = (dict(probe_payload=["probe_payload"])
              if join_type == "anti" else payloads)
        progs[f"join_step_{join_type}"] = {
            "fn": spmd(make_join_step(comm, join_type=join_type,
                                      **pl)),
            "args": args, "telemetry_off": True,
        }
    # Segmented local sort (docs/ROOFLINE.md §9): hash classes sorted
    # per segment — the CI sort lane's sort_segments=8 configuration.
    progs["join_step_segmented"] = {
        "fn": spmd(make_join_step(comm, sort_mode="segmented",
                                  sort_segments=8, **payloads)),
        "args": args, "telemetry_off": True,
    }
    # Aggregate pushdown (docs/AGGREGATION.md), both fused settle
    # paths: key mode (group == join key, co-located by the shuffle)
    # and probe mode (probe-side group column, partials exchanged).
    # No explicit payload kwargs: the spec resolves wire columns.
    from distributed_join_tpu.ops.aggregate import AggregateSpec

    agg_key = AggregateSpec.of(
        "key", [("sum", "probe_payload", "probe_sum"),
                ("count", None, "n_rows")])
    progs["join_step_agg_key"] = {
        "fn": spmd(make_join_step(comm, aggregate=agg_key)),
        "args": args, "telemetry_off": True,
    }
    agg_probe = AggregateSpec.of(
        "probe_payload", [("sum", "build_payload", "build_sum"),
                          ("count", None, "n_rows")])
    progs["join_step_agg_probe"] = {
        "fn": spmd(make_join_step(comm, aggregate=agg_probe)),
        "args": args, "telemetry_off": True,
    }
    # Probe-only dispatch against a resident build image
    # (service/resident.py): the build side arrives pre-prepped
    # (key-sorted valid-prefix, same columns), only the probe side
    # shuffles.
    from distributed_join_tpu.parallel.distributed_join import (
        make_probe_join_step,
    )

    progs["probe_join_step"] = {
        "fn": comm.spmd(
            make_probe_join_step(comm,
                                 build_payload=["build_payload"],
                                 probe_payload=["probe_payload"]),
            sharded_out=JOIN_SHARDED_OUT),
        "args": args, "telemetry_off": True,
    }
    # Hierarchical 2×4 (slice, chip) mesh (docs/HIERARCHY.md): the
    # same join step lowered over the two-axis communicator — the
    # scale-out schedule the DCN seams route through.
    from distributed_join_tpu.parallel.communicator import (
        HierarchicalTpuCommunicator,
    )

    hier = HierarchicalTpuCommunicator(n_slices=2, n_ranks=N_RANKS)
    progs["join_step_hier_2x4"] = {
        "fn": hier.spmd(make_join_step(hier, shuffle="hierarchical",
                                       **payloads),
                        sharded_out=JOIN_SHARDED_OUT),
        "args": args, "telemetry_off": True,
    }
    # The Q3 multi-operator query plan (docs/QUERY.md): two chained
    # joins + the fused group-by as ONE compiled program.
    from distributed_join_tpu.parallel.query_exec import (
        make_query_step,
        query_sharded_out,
    )
    from distributed_join_tpu.planning.query import tpch_query_plan

    q3 = tpch_query_plan("q3")
    progs["query_plan_q3"] = {
        "fn": comm.spmd(make_query_step(comm, q3),
                        sharded_out=query_sharded_out(q3)),
        "args": _abstract_tpch_q3_tables(), "telemetry_off": True,
    }
    return progs


def trace_program(name: str, prog: dict) -> ProgramSchedule:
    """Trace one program (abstract inputs — no compile, no execute)
    and extract its schedule facts."""
    import jax

    closed = jax.make_jaxpr(prog["fn"])(*prog["args"])
    return ProgramSchedule(
        program=name,
        n_ranks=N_RANKS,
        telemetry_off=bool(prog["telemetry_off"]),
        collectives=collective_sequence(closed.jaxpr),
        host_callbacks=callback_sequence(closed.jaxpr),
        cond_divergence=cond_divergences(closed.jaxpr),
    )


# -- golden registry + the check --------------------------------------


def golden_path(name: str, schedule_dir: Optional[str] = None) -> str:
    return os.path.join(schedule_dir or DEFAULT_SCHEDULE_DIR,
                        f"{name}.json")


def write_golden(sched: ProgramSchedule,
                 schedule_dir: Optional[str] = None) -> str:
    d = schedule_dir or DEFAULT_SCHEDULE_DIR
    os.makedirs(d, exist_ok=True)
    path = golden_path(sched.program, d)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(sched.golden(), f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def _diff_sequences(want: List[str], got: List[str]) -> str:
    """A readable first-divergence diff of two collective sequences
    (docs/STATIC_ANALYSIS.md "reading a schedule diff")."""
    n = min(len(want), len(got))
    for i in range(n):
        if want[i] != got[i]:
            return (f"first divergence at position {i}: committed "
                    f"{want[i]!r} vs traced {got[i]!r} "
                    f"(committed has {len(want)} collectives, "
                    f"traced {len(got)})")
    return (f"committed has {len(want)} collectives, traced has "
            f"{len(got)}; the first {n} agree — a collective was "
            + ("dropped" if len(got) < len(want) else "added")
            + " at the tail")


def check_program(sched: ProgramSchedule,
                  schedule_dir: Optional[str] = None) -> List[str]:
    """Violations for one traced program: the two unconditional
    invariants plus the golden comparison."""
    violations = []
    if sched.telemetry_off and sched.host_callbacks:
        violations.append(
            f"{sched.program}: host callback primitive(s) "
            f"{sched.host_callbacks} in a TELEMETRY-OFF program — the "
            "seed hot path must carry no callbacks "
            "(docs/OBSERVABILITY.md parity contract; if this is the "
            "plan-validation debug seam, trace without "
            "DJTPU_VALIDATE_PLANS)"
        )
    for msg in sched.cond_divergence:
        violations.append(f"{sched.program}: {msg}")
    path = golden_path(sched.program, schedule_dir)
    if not os.path.exists(path):
        violations.append(
            f"{sched.program}: no committed golden schedule at {path} "
            "— run `python -m distributed_join_tpu.analysis.lint "
            "--update-schedules` and commit the result"
        )
        return violations
    with open(path) as f:
        golden = json.load(f)
    if golden.get("schema_version") != SCHEDULE_SCHEMA_VERSION:
        violations.append(
            f"{sched.program}: golden schema_version "
            f"{golden.get('schema_version')} != "
            f"{SCHEDULE_SCHEMA_VERSION} — regenerate with "
            "--update-schedules"
        )
        return violations
    if golden.get("n_ranks") != sched.n_ranks:
        violations.append(
            f"{sched.program}: golden n_ranks {golden.get('n_ranks')} "
            f"!= traced {sched.n_ranks}"
        )
    want = list(golden.get("collectives", []))
    if want != sched.collectives:
        violations.append(
            f"{sched.program}: collective schedule drifted from "
            f"{path}: " + _diff_sequences(want, sched.collectives)
        )
    if list(golden.get("host_callbacks", [])) != sched.host_callbacks:
        violations.append(
            f"{sched.program}: host-callback set drifted: committed "
            f"{golden.get('host_callbacks')} vs traced "
            f"{sched.host_callbacks}"
        )
    return violations


def check_schedules(schedule_dir: Optional[str] = None,
                    update: bool = False,
                    programs: Optional[Dict[str, dict]] = None):
    """Trace every key program and check (or, with ``update``,
    rewrite) its golden. Returns ``(violations, schedules)``; the CLI
    exit gate is ``not violations``. Requires >= 8 devices (the CLI
    and tests force the 8-virtual-device CPU mesh first)."""
    progs = programs if programs is not None else key_programs()
    violations: List[str] = []
    schedules: List[ProgramSchedule] = []
    for name, prog in progs.items():
        sched = trace_program(name, prog)
        schedules.append(sched)
        if update:
            write_golden(sched, schedule_dir)
        vs = check_program(sched, schedule_dir)
        if update:
            # The golden was just rewritten, so only the unconditional
            # invariants can still fire — regen must not bless a
            # callback in the seed hot path or a divergent cond.
            vs = [v for v in vs
                  if "host callback" in v or "cond with" in v]
        violations.extend(vs)
    return violations, schedules
