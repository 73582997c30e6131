"""Stage-segmented profiling: measured per-stage walls + overlap credit.

Every measured number in the stack before this module was whole-join
granularity: the roofline cost model (``planning/cost.py``) predicts
per-STAGE wall seconds, but history/EXPLAIN grading could only compare
whole-join walls — so ``calibrate_from_history`` can refit one global
scale and nothing more, and docs/OVERLAP.md §1's overlap question
(do ppermute's async pairs beat padded's synchronous all-to-alls?) was
answered from HLO structure, never wall clocks.

This harness closes the gap by running the SAME join twice:

1. **Segmented**: the pipeline is split at exactly the boundaries the
   cost model predicts over — ``partition`` (hash + bucket sort + the
   padded/sorted-layout gathers; ``cost.predict`` bills the
   materialization gathers here, so the segment does too, even though
   the monolithic program nests ``to_padded`` under its shuffle span),
   ``shuffle`` (the pure collective exchange + codec), ``join`` (the
   merged sort / scans / compaction / expand) — each compiled as its
   own SPMD program whose shapes and capacities come from THE shared
   ladder resolution (``distributed_join.resolve_join_ladder`` via
   ``planning.build_plan``), so segment capacities provably match the
   monolithic plan; per-stage device counters (a ``MetricsTape`` per
   segment) ride each program. Stages are timed back to back with a
   fetch-one-scalar barrier between them (the honest sync of
   ``utils/benchmarking.py``), N repeats, median.
2. **Monolithic**: ONE ``make_join_step`` program — the exact seed hot
   path (``with_metrics=False``), the program the drivers time — run
   with the same repeat/median protocol.

The delta ``sum(stage walls) - monolithic wall`` IS the measured
overlap/fusion credit: work the compiler hides across stage boundaries
that the segmented run must pay serially. Per shuffle mode this
answers OVERLAP.md §1 with wall clocks; per-stage ICI utilization
(measured off-chip bytes / stage wall vs the spec bandwidth) lands
next to it, and ``planning.cost.calibrate_from_stage_profile`` refits
INDIVIDUAL constants (sort, ICI bandwidth, ...) from the per-stage
ratios instead of one global scale.

The timed hot path is untouched: profiling runs only as an extra
untimed-side pass after the drivers' timed region (the
``collect_join_metrics`` pattern), and with ``--stage-profile`` off no
code here ever runs — program byte-parity is test-locked.

Scope (loud refusals, never silent wrong numbers): the skew sidecar,
string (2-D uint8) keys, and ragged-mode varwidth columns are not
stage-segmentable yet — ``profile_join_stages`` raises a ValueError
naming the unsupported feature.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

STAGE_PROFILE_SCHEMA_VERSION = 1

# The stage keys — 1:1 with planning.cost.predict's ``stages`` dict
# (the acceptance contract: grading needs the two keyed identically).
STAGE_KEYS = ("partition", "shuffle", "join", "skew")


def _round_s(x: float) -> float:
    return round(float(x), 9)


def _median(vals):
    s = sorted(vals)
    return s[len(s) // 2] if s else 0.0


@dataclasses.dataclass
class StageProfile:
    """One profiled run: per-stage walls/counters, the monolithic
    wall, and the derived overlap credit. ``as_record()`` is the
    ``stageprofile.json`` artifact (kind-stamped, schema-checked by
    ``analyze check``); ``summary()`` the compact block drivers embed
    in their JSON record (and ``history.run_entry`` persists)."""

    plan_digest: str
    shuffle: str
    n_ranks: int
    over_decomposition: int
    repeats: int
    platform: str
    overflow: bool
    stages: dict                 # name -> stage dict (see _stage_entry)
    monolithic_walls_s: list
    cost: dict                   # the plan's cost prediction (model incl.)
    # Segmented-sort mode (docs/ROOFLINE.md §9): the plan-resolved
    # static segment count the profiled programs ran with (1 = flat).
    sort_segments: int = 1

    @property
    def monolithic_wall_s(self) -> float:
        return _median(self.monolithic_walls_s)

    @property
    def sum_of_stages_s(self) -> float:
        return sum(s["wall_s"] for s in self.stages.values())

    @property
    def sum_of_stages_min_s(self) -> float:
        """Noise-robust floor: sum of per-stage MINIMUM walls. Timing
        noise only ever inflates a wall, so the min across repeats is
        the honest best-case estimate — the consistency invariant
        (segments do strictly more work than the fused program, hence
        sum-of-stages >= monolithic) is gated on mins, while the
        headline overlap credit reports medians."""
        return sum(s["wall_min_s"] for s in self.stages.values())

    @property
    def monolithic_wall_min_s(self) -> float:
        return min(self.monolithic_walls_s) \
            if self.monolithic_walls_s else 0.0

    @property
    def overlap(self) -> dict:
        total = self.sum_of_stages_s
        credit = total - self.monolithic_wall_s
        return {
            "credit_s": _round_s(credit),
            "fraction": (_round_s(credit / total) if total > 0
                         else None),
            "note": ("sum-of-segments minus monolithic wall: work the "
                     "compiler overlaps/fuses across stage boundaries "
                     "that the segmented run pays serially"),
        }

    def as_record(self) -> dict:
        return {
            "schema_version": STAGE_PROFILE_SCHEMA_VERSION,
            "kind": "stageprofile",
            "pipeline": "join",
            "plan_digest": self.plan_digest,
            "shuffle": self.shuffle,
            "n_ranks": self.n_ranks,
            "over_decomposition": self.over_decomposition,
            "repeats": self.repeats,
            "platform": self.platform,
            "overflow": self.overflow,
            "sort_segments": self.sort_segments,
            "stages": {k: dict(v) for k, v in self.stages.items()},
            "sum_of_stages_s": _round_s(self.sum_of_stages_s),
            "sum_of_stages_min_s": _round_s(self.sum_of_stages_min_s),
            "monolithic": {
                "wall_s": _round_s(self.monolithic_wall_s),
                "wall_min_s": _round_s(self.monolithic_wall_min_s),
                "walls_s": [_round_s(w)
                            for w in self.monolithic_walls_s],
            },
            "overlap": self.overlap,
            "cost_model": self.cost.get("model"),
            "predicted_total_s": self.cost.get("total_s"),
        }

    def summary(self) -> dict:
        """The compact per-record block (history's ``stages`` seam)."""
        return {
            "plan_digest": self.plan_digest,
            "shuffle": self.shuffle,
            "repeats": self.repeats,
            "platform": self.platform,
            "overflow": self.overflow,
            "wall_s": {k: v["wall_s"] for k, v in self.stages.items()},
            "ratio": {k: v["ratio"] for k, v in self.stages.items()
                      if v.get("ratio") is not None},
            "sum_of_stages_s": _round_s(self.sum_of_stages_s),
            "monolithic_wall_s": _round_s(self.monolithic_wall_s),
            "overlap_fraction": self.overlap["fraction"],
        }

    def format(self) -> str:
        return format_stage_record(self.as_record())


def format_stage_record(record: dict, worst_stage: Optional[str] = None,
                        worst_constants=None) -> str:
    """THE one human rendering of a stage-profile record — shared by
    the drivers' ``--stage-profile`` printout (via
    :meth:`StageProfile.format`) and ``analyze stages`` (which adds
    the worst-mispredicted line from its grade), so the two surfaces
    cannot drift apart."""
    stages = record.get("stages") or {}
    lines = [
        f"stage profile {str(record.get('plan_digest'))[:16]}: "
        f"{record.get('shuffle')} shuffle, "
        f"{record.get('n_ranks')} rank(s) x "
        f"k={record.get('over_decomposition')}, "
        f"{record.get('repeats')} repeat(s), "
        f"platform={record.get('platform')}"
        + ("  [OVERFLOW — walls belong to a clamped run]"
           if record.get("overflow") else ""),
        f"  {'stage':<10} {'measured':>12} {'predicted':>12} "
        f"{'ratio':>9}",
    ]
    ordered = [s for s in STAGE_KEYS if s in stages] + \
        sorted(s for s in stages if s not in STAGE_KEYS)
    for name in ordered:
        s = stages[name]
        if not s.get("ran"):
            lines.append(f"  {name:<10} {'-':>12} "
                         f"{s.get('predicted_s')!s:>12} {'-':>9}")
            continue
        ratio = (f"x{s['ratio']:.3g}" if s.get("ratio") is not None
                 else "-")
        lines.append(f"  {name:<10} {s['wall_s']:>12.6f} "
                     f"{s['predicted_s']:>12.6f} {ratio:>9}")
    ov = record.get("overlap") or {}
    mono = (record.get("monolithic") or {}).get("wall_s")
    if record.get("sum_of_stages_s") is not None and mono is not None:
        lines.append(
            f"  sum-of-stages {record['sum_of_stages_s']:.6f}s vs "
            f"monolithic {mono:.6f}s -> overlap credit "
            f"{ov.get('credit_s'):.6f}s"
            + (f" ({ov['fraction']:.1%} of segmented work hidden)"
               if ov.get("fraction") is not None else ""))
    ici = (stages.get("shuffle") or {}).get("ici")
    if ici:
        lines.append(
            f"  shuffle wire: {ici['offchip_bytes_per_rank']} "
            f"off-chip B/rank at "
            f"{ici['measured_gb_per_s']:.4g} GB/s = "
            f"{ici['ici_utilization']:.2%} of spec "
            f"{ici['spec_gb_per_s']:.3g} GB/s"
            + ("" if record.get("platform") == "tpu" else
               "  (non-TPU platform: utilization vs the v5e spec "
               "is not meaningful)"))
    if worst_stage:
        lines.append(
            f"  worst-mispredicted stage: {worst_stage} -> refit "
            "constants " + ", ".join(worst_constants or ())
            + " (planning.cost.calibrate_from_stage_profile)")
    return "\n".join(lines)


def _stage_entry(ran: bool, walls, counters: Optional[dict],
                 predicted_s: float) -> dict:
    wall = _median(walls) if ran else 0.0
    return {
        "ran": bool(ran),
        "wall_s": _round_s(wall),
        "wall_min_s": _round_s(min(walls) if ran and walls else 0.0),
        "walls_s": [_round_s(w) for w in (walls or [])],
        "counters": {k: int(v) for k, v in
                     sorted((counters or {}).items())},
        "predicted_s": predicted_s,
        "ratio": (_round_s(wall / predicted_s)
                  if ran and predicted_s else None),
    }


def profile_join_stages(comm, build, probe, key="key", repeats: int = 3,
                        cost_model=None, **opts) -> StageProfile:
    """Profile one join workload stage by stage (see module docstring).

    ``opts`` are ``distributed_inner_join``-shaped options (sizing
    factors included); the capacity contract resolves through the SAME
    ``resolve_join_ladder`` path every real call uses, via
    ``planning.build_plan`` — the returned profile's ``plan_digest``
    equals the monolithic seed program's signature digest (and the
    driver's ``explain.json`` digest for the same run).

    Runs ``3 + k-dependent`` extra compiled programs (three segments +
    one monolithic step); intended as an untimed side pass AFTER any
    timed region, never inside one.
    """
    import jax
    import jax.numpy as jnp

    from distributed_join_tpu import planning, telemetry
    from distributed_join_tpu.ops.join import sort_merge_inner_join
    from distributed_join_tpu.ops.partition import (
        PartitionedTable,
        radix_hash_partition,
    )
    from distributed_join_tpu.parallel.distributed_join import (
        JOIN_SHARDED_OUT,
        _record_partition,
        _round_up,
        _varwidth_cols,
        make_join_step,
        resolve_join_ladder,
    )
    from distributed_join_tpu.parallel.shuffle import (
        shuffle_hierarchical,
        shuffle_padded,
        shuffle_padded_compressed,
        shuffle_ragged,
    )
    from distributed_join_tpu.table import Table
    from distributed_join_tpu.telemetry.spans import fetch_one_scalar

    if repeats < 1:
        raise ValueError("repeats must be >= 1")
    opts = dict(opts)
    if opts.get("skew_threshold") is not None:
        raise ValueError(
            "stage profiling does not support the skew sidecar yet — "
            "profile with skew off (the skew stage is reported 0.0, "
            "matching cost.predict's key set)")
    keys = [key] if isinstance(key, str) else list(key)
    for kname in keys:
        if build.columns[kname].ndim != 1:
            raise ValueError(
                f"stage profiling does not support string (2-D) key "
                f"{kname!r} yet — profile the integer-key form")

    n = comm.n_ranks
    build = build.pad_to(_round_up(build.capacity, n))
    probe = probe.pad_to(_round_up(probe.capacity, n))
    if hasattr(comm, "device_put_sharded"):
        # Multi-controller (tpu-launch) callers hand tables the driver
        # ALREADY placed as global arrays spanning non-addressable
        # devices — re-placing would fetch them to host, which jax
        # forbids across processes. Anything else (host values, or
        # single-process device arrays) goes through the normal put.
        already_global = jax.process_count() > 1 and all(
            isinstance(c, jax.Array) and not c.is_fully_addressable
            for t in (build, probe) for c in t.columns.values())
        if not already_global:
            build, probe = comm.device_put_sharded((build, probe))

    # THE shared resolution: the capacity contract resolves through
    # resolve_join_ladder — the same seam distributed_inner_join and
    # explain_join use (sizing knobs pop out of opts here) — and the
    # plan's capacity arithmetic is make_join_step's verbatim; segment
    # shapes below read b_cap/p_cap/out_cap FROM the plan, so they
    # cannot drift from what the monolithic program compiles.
    ladder = resolve_join_ladder(build, probe, n, opts,
                                 n_slices=getattr(comm, "n_slices", 1))
    sizing = ladder.sizing()
    plan = planning.build_plan(comm, build, probe, key=key,
                               with_metrics=False,
                               cost_model=cost_model, **sizing, **opts)
    mode = plan.shuffle
    k = plan.over_decomposition
    nb = n * k
    b_cap = plan.capacities["shuffle_build_per_bucket"]
    p_cap = plan.capacities["shuffle_probe_per_bucket"]
    out_cap = plan.capacities["out_rows_per_batch"]
    comp_bits = plan.compression_bits
    kc = opts.get("kernel_config")
    bpay, ppay = opts.get("build_payload"), opts.get("probe_payload")
    if mode == "ragged" and (_varwidth_cols(build)
                             or _varwidth_cols(probe)):
        raise ValueError(
            "stage profiling does not support ragged-mode varwidth "
            "(byte-exact string) columns yet — profile with "
            "shuffle='padded' or drop the string columns")
    via = "ppermute" if mode == "ppermute" else "all_to_all"
    single = nb == 1
    # Hierarchical mode: the shuffle segment routes the two tiers
    # exactly as the monolithic step — shuffle_hierarchical with the
    # plan's resolved dcn codec (the per-tier wire counters then gate
    # exactly, like the flat padded bytes). One-slice degenerates to
    # the flat padded segment, mirroring _batch_shuffle.
    hier = (mode == "hierarchical"
            and getattr(comm, "n_slices", 1) > 1)
    dcn_bits = None
    if mode == "hierarchical":
        from distributed_join_tpu.planning.cost import (
            resolve_dcn_bits,
        )

        dcn_bits = resolve_dcn_bits(
            plan.resolved_options.get("dcn_codec") or "auto",
            comp_bits, n_slices=getattr(comm, "n_slices", 1))

    # Segmented-sort mode (sort_mode="segmented", docs/ROOFLINE.md
    # §9): the plan's shared resolution says how many sub-buckets the
    # partition sorts and what the fine capacities are — the three
    # stage programs below then mirror the monolithic segmented step
    # exactly (fine partition / per-segment padded wire / batched
    # short-run join), so the per-stage wire counters still gate
    # EXACTLY and the join-stage wall attributes the sort-mode delta.
    sort_seg = int(plan.capacities.get("sort_segments") or 1)
    seg_b_cap = plan.capacities.get("shuffle_build_per_segment")
    seg_p_cap = plan.capacities.get("shuffle_probe_per_segment")
    seg_out_cap = plan.capacities.get("out_rows_per_segment")

    # -- segment programs ---------------------------------------------

    def seg_partition_segmented(build_local, probe_local):
        tape = telemetry.MetricsTape()
        ptb = radix_hash_partition(build_local, keys, nb,
                                   sub_buckets=sort_seg)
        ptp = radix_hash_partition(probe_local, keys, nb,
                                   sub_buckets=sort_seg)
        tape.add("sort_segments", sort_seg)
        _record_partition(tape.scoped("build"), ptb, seg_b_cap)
        _record_partition(tape.scoped("probe"), ptp, seg_p_cap)
        out = {}
        overflow = jnp.bool_(False)
        for side, pt, cap in (("build", ptb, seg_b_cap),
                              ("probe", ptp, seg_p_cap)):
            for b in range(k):
                padded, counts, ovf, _ = pt.to_padded(
                    cap, bucket_start=b * n * sort_seg,
                    n_buckets=n * sort_seg)
                out[f"{side}.b{b}.counts"] = counts
                for cname, c in padded.items():
                    out[f"{side}.b{b}.col.{cname}"] = c
                overflow = overflow | ovf
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        return out, overflow, tape.gathered(comm)

    def seg_shuffle_segmented(payload):
        from distributed_join_tpu.parallel.shuffle import (
            shuffle_segmented,
        )

        tape = telemetry.MetricsTape()
        out = {}
        seg_via = ("hierarchical" if hier
                   else ("ppermute" if mode == "ppermute"
                         else "all_to_all"))
        for side, cap in (("build", seg_b_cap), ("probe", seg_p_cap)):
            t = tape.scoped(side)
            for b in range(k):
                prefix = f"{side}.b{b}.col."
                padded = {cname[len(prefix):]: c
                          for cname, c in payload.items()
                          if cname.startswith(prefix)}
                counts = payload[f"{side}.b{b}.counts"]
                recv_cols, recv_counts = shuffle_segmented(
                    comm, padded, counts, cap, sort_seg, via=seg_via,
                    tape=t)
                out[f"{side}.b{b}.counts"] = recv_counts
                for cname, c in recv_cols.items():
                    out[f"{side}.b{b}.col.{cname}"] = c
        overflow = comm.psum(jnp.int32(0)) > 0
        return out, overflow, tape.gathered(comm)

    def seg_join_segmented(payload):
        from distributed_join_tpu.ops.segmented import (
            batched_sort_merge_inner_join,
            runs_from_blocks,
        )

        tape = telemetry.MetricsTape()
        parts = []
        total = jnp.int64(0)
        overflow = jnp.bool_(False)
        for b in range(k):
            seg_tables = []
            for side in ("build", "probe"):
                prefix = f"{side}.b{b}.col."
                cols = {cname[len(prefix):]: c
                        for cname, c in payload.items()
                        if cname.startswith(prefix)}
                seg_tables.append(runs_from_blocks(
                    cols, payload[f"{side}.b{b}.counts"]))
            (bcols, bval), (pcols, pval) = seg_tables
            table, t_batch, ovf = batched_sort_merge_inner_join(
                bcols, bval, pcols, pval, keys, seg_out_cap,
                build_payload=bpay, probe_payload=ppay)
            parts.append(table)
            total = total + t_batch
            overflow = overflow | ovf
        out = Table(
            {name: jnp.concatenate([t.columns[name] for t in parts])
             for name in parts[0].column_names},
            jnp.concatenate([t.valid for t in parts]),
        )
        tape.add("matches", total)
        metrics = tape.gathered(comm)
        total = comm.psum(total)
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        return ({"col." + nm: c for nm, c in out.columns.items()}
                | {"valid": out.valid}, total, overflow, metrics)

    def seg_partition(build_local, probe_local):
        tape = telemetry.MetricsTape()
        ptb = radix_hash_partition(build_local, keys, nb)
        ptp = radix_hash_partition(probe_local, keys, nb)
        _record_partition(tape.scoped("build"), ptb, b_cap)
        _record_partition(tape.scoped("probe"), ptp, p_cap)
        out = {}
        overflow = jnp.bool_(False)
        for side, pt, cap in (("build", ptb, b_cap),
                              ("probe", ptp, p_cap)):
            if mode == "ragged":
                # The sorted layout (the partition sort's own output;
                # a gather for 2-D columns) is partition work per the
                # cost model, as is to_padded's packing below.
                st = pt.table
                for cname, c in st.columns.items():
                    out[f"{side}.col.{cname}"] = c
                out[f"{side}.valid"] = st.valid
                # offsets truncated to (nb,) — shard_map needs a
                # rank-divisible leading dim, and shuffle_ragged only
                # reads the first nb boundaries.
                out[f"{side}.offsets"] = pt.offsets[:nb]
                out[f"{side}.counts"] = pt.counts
                overflow = overflow | jnp.any(pt.counts > cap)
            else:
                for b in range(k):
                    padded, counts, ovf, _ = pt.to_padded(
                        cap, bucket_start=b * n, n_buckets=n)
                    out[f"{side}.b{b}.counts"] = counts
                    for cname, c in padded.items():
                        out[f"{side}.b{b}.col.{cname}"] = c
                    overflow = overflow | ovf
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        return out, overflow, tape.gathered(comm)

    def seg_shuffle(payload):
        tape = telemetry.MetricsTape()
        out = {}
        overflow = jnp.bool_(False)
        for side, cap in (("build", b_cap), ("probe", p_cap)):
            t = tape.scoped(side)
            if mode == "ragged":
                cols = {cname[len(f"{side}.col."):]: c
                        for cname, c in payload.items()
                        if cname.startswith(f"{side}.col.")}
                rows = payload[f"{side}.valid"].shape[0]
                pt = PartitionedTable(
                    source=Table(cols, payload[f"{side}.valid"]),
                    order=jnp.arange(rows, dtype=jnp.int32),
                    offsets=payload[f"{side}.offsets"],
                    counts=payload[f"{side}.counts"],
                    sorted_columns={cname: c for cname, c in cols.items()
                                    if c.ndim == 1},
                )
                for b in range(k):
                    recv, ovf = shuffle_ragged(
                        comm, pt, n * cap, bucket_start=b * n,
                        capacity_per_bucket=cap, tape=t)
                    overflow = overflow | ovf
                    out[f"{side}.b{b}.valid"] = recv.valid
                    for cname, c in recv.columns.items():
                        out[f"{side}.b{b}.col.{cname}"] = c
                continue
            for b in range(k):
                prefix = f"{side}.b{b}.col."
                padded = {cname[len(prefix):]: c
                          for cname, c in payload.items()
                          if cname.startswith(prefix)}
                counts = payload[f"{side}.b{b}.counts"]
                if hier:
                    recv, _, c_ovf = shuffle_hierarchical(
                        comm, padded, counts, cap,
                        dcn_bits=dcn_bits, tape=t)
                    overflow = overflow | c_ovf
                elif comp_bits is not None and mode != "hierarchical":
                    recv, _, c_ovf = shuffle_padded_compressed(
                        comm, padded, counts, cap, bits=comp_bits,
                        via=via, tape=t)
                    overflow = overflow | c_ovf
                else:
                    recv, _ = shuffle_padded(comm, padded, counts,
                                             cap, via=via, tape=t)
                out[f"{side}.b{b}.valid"] = recv.valid
                for cname, c in recv.columns.items():
                    out[f"{side}.b{b}.col.{cname}"] = c
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        return out, overflow, tape.gathered(comm)

    def _batch_table(payload, side, b):
        prefix = f"{side}.b{b}.col."
        cols = {cname[len(prefix):]: c
                for cname, c in payload.items()
                if cname.startswith(prefix)}
        return Table(cols, payload[f"{side}.b{b}.valid"])

    def seg_join(payload):
        tape = telemetry.MetricsTape()
        parts = []
        total = jnp.int64(0)
        overflow = jnp.bool_(False)
        for b in range(k):
            res = sort_merge_inner_join(
                _batch_table(payload, "build", b),
                _batch_table(payload, "probe", b),
                keys, out_cap, build_payload=bpay,
                probe_payload=ppay, kernel_config=kc)
            parts.append(res.table)
            total = total + res.total.astype(jnp.int64)
            overflow = overflow | res.overflow
        out = Table(
            {name: jnp.concatenate([t.columns[name] for t in parts])
             for name in parts[0].column_names},
            jnp.concatenate([t.valid for t in parts]),
        )
        tape.add("matches", total)
        metrics = tape.gathered(comm)
        total = comm.psum(total)
        overflow = comm.psum(overflow.astype(jnp.int32)) > 0
        return ({"col." + nm: c for nm, c in out.columns.items()}
                | {"valid": out.valid}, total, overflow, metrics)

    def seg_join_single(build_local, probe_local):
        tape = telemetry.MetricsTape()
        res = sort_merge_inner_join(
            build_local, probe_local, keys, out_cap,
            build_payload=bpay, probe_payload=ppay, kernel_config=kc)
        tape.add("matches", res.total.astype(jnp.int64))
        metrics = tape.gathered(comm)
        total = comm.psum(res.total.astype(jnp.int64))
        overflow = comm.psum(res.overflow.astype(jnp.int32)) > 0
        return ({"col." + nm: c for nm, c in res.table.columns.items()}
                | {"valid": res.table.valid}, total, overflow, metrics)

    # -- compile + warmup chain (barriered handoff) -------------------

    aux_out = (False, True, True)        # payload sharded, rest replicated
    overflow_seen = False
    seg_metrics: dict = {}
    if single:
        fn_join = comm.spmd(seg_join_single,
                            sharded_out=(False, True, True, True))
        j_out = fn_join(build, probe)
        fetch_one_scalar(j_out[1])
        overflow_seen = overflow_seen or bool(j_out[2])
        seg_metrics["join"] = j_out[3].to_dict()["reduced"]
        chain = [("join", fn_join, (build, probe), 1)]
    else:
        part_fn = (seg_partition_segmented if sort_seg > 1
                   else seg_partition)
        shuf_fn = (seg_shuffle_segmented if sort_seg > 1
                   else seg_shuffle)
        join_fn = seg_join_segmented if sort_seg > 1 else seg_join
        fn_part = comm.spmd(part_fn, sharded_out=aux_out)
        fn_shuf = comm.spmd(shuf_fn, sharded_out=aux_out)
        fn_join = comm.spmd(join_fn,
                            sharded_out=(False, True, True, True))
        a_out = fn_part(build, probe)
        fetch_one_scalar(a_out[1])
        b_out = fn_shuf(a_out[0])
        fetch_one_scalar(b_out[1])
        j_out = fn_join(b_out[0])
        fetch_one_scalar(j_out[1])
        overflow_seen = any(bool(o) for o in
                            (a_out[1], b_out[1], j_out[2]))
        seg_metrics["partition"] = a_out[2].to_dict()["reduced"]
        seg_metrics["shuffle"] = b_out[2].to_dict()["reduced"]
        seg_metrics["join"] = j_out[3].to_dict()["reduced"]
        chain = [("partition", fn_part, (build, probe), 1),
                 ("shuffle", fn_shuf, (a_out[0],), 1),
                 ("join", fn_join, (b_out[0],), 1)]

    # The monolithic comparator: the exact seed hot path the drivers
    # time (with_metrics=False — its signature IS plan.digest),
    # compiled from the ladder's resolved sizing, so the program
    # provably matches the segment capacities.
    mono_step = make_join_step(comm, key=key, **sizing, **opts)
    fn_mono = comm.spmd(mono_step, sharded_out=JOIN_SHARDED_OUT)
    warm = fn_mono(build, probe)
    fetch_one_scalar(warm.total)
    overflow_seen = overflow_seen or bool(warm.overflow)

    # -- the timed repeats (fetch-one-scalar barrier between stages) --

    walls: dict = {name: [] for name, *_ in chain}
    mono_walls = []
    for _ in range(repeats):
        for name, fn, fargs, sync_idx in chain:
            t0 = time.perf_counter()
            res = fn(*fargs)
            fetch_one_scalar(res[sync_idx])
            dt = time.perf_counter() - t0
            walls[name].append(dt)
            telemetry.span_complete(f"stage_profile.{name}", t0, dt)
        t0 = time.perf_counter()
        res = fn_mono(build, probe)
        fetch_one_scalar(res.total)
        dt = time.perf_counter() - t0
        mono_walls.append(dt)
        telemetry.span_complete("stage_profile.monolithic", t0, dt)

    # -- assemble ------------------------------------------------------

    predicted = plan.cost["stages"]
    stages = {}
    for name in STAGE_KEYS:
        ran = name in walls
        stages[name] = _stage_entry(
            ran, walls.get(name), seg_metrics.get(name),
            predicted.get(name, 0.0))
    # Per-stage ICI utilization: measured off-chip bytes over the
    # shuffle wall vs the spec bandwidth the cost model carries.
    sh = stages["shuffle"]
    if sh["ran"] and sh["wall_s"] > 0:
        wire_total = sum(sh["counters"].get(f"{s}.wire_bytes", 0)
                         for s in ("build", "probe"))
        offchip = int(wire_total / n * (n - 1) / n)
        spec = float(plan.cost["model"]["ici_bytes_per_s"])
        bw = offchip / sh["wall_s"]
        sh["ici"] = {
            "wire_bytes_per_rank": int(wire_total / n),
            "offchip_bytes_per_rank": offchip,
            "measured_gb_per_s": _round_s(bw / 1e9),
            "spec_gb_per_s": _round_s(spec / 1e9),
            "ici_utilization": _round_s(bw / spec),
        }

    return StageProfile(
        plan_digest=plan.digest,
        shuffle=mode,
        n_ranks=n,
        over_decomposition=k,
        repeats=repeats,
        platform=jax.default_backend(),
        overflow=overflow_seen,
        stages=stages,
        monolithic_walls_s=mono_walls,
        cost=plan.cost,
        sort_segments=sort_seg,
    )


# -- query-chain profiling (per-OPERATOR walls) ------------------------


@dataclasses.dataclass
class QueryStageProfile:
    """One profiled multi-operator query: per-OPERATOR walls (each
    operator compiled as its own barriered SPMD program), the
    monolithic ``make_query_step`` wall (the exact program
    ``distributed_query`` dispatches), and the derived cross-operator
    overlap credit. The segmentation boundary here is the OPERATOR —
    the same resolution ``explain_query`` prices (one ``cost.predict``
    verdict per op), so predicted-vs-measured grading joins on op_id
    exactly like the join-stage profile joins on stage name.

    ``as_record()`` is the ``query_stageprofile.json`` artifact (its
    own kind — ``analyze check``'s ``stageprofile`` contract requires
    the four join-stage keys, which do not apply here); ``summary()``
    is shaped for ``history.stages_block`` with op_ids as the stage
    keys, so per-operator walls flow into history trends unchanged."""

    plan_digest: str
    n_ranks: int
    n_operators: int
    repeats: int
    platform: str
    overflow: bool
    operators: dict              # op_id -> stage dict (_stage_entry)
    order: list                  # op_ids in plan order
    monolithic_walls_s: list
    predicted_total_s: Optional[float]
    cost_model: Optional[dict] = None

    @property
    def monolithic_wall_s(self) -> float:
        return _median(self.monolithic_walls_s)

    @property
    def monolithic_wall_min_s(self) -> float:
        return min(self.monolithic_walls_s) \
            if self.monolithic_walls_s else 0.0

    @property
    def sum_of_operators_s(self) -> float:
        return sum(s["wall_s"] for s in self.operators.values())

    @property
    def overlap(self) -> dict:
        total = self.sum_of_operators_s
        credit = total - self.monolithic_wall_s
        return {
            "credit_s": _round_s(credit),
            "fraction": (_round_s(credit / total) if total > 0
                         else None),
            "note": ("sum-of-operators minus monolithic wall: "
                     "scheduling XLA hides across operator boundaries "
                     "that the per-op programs pay serially"),
        }

    def as_record(self) -> dict:
        return {
            "schema_version": STAGE_PROFILE_SCHEMA_VERSION,
            "kind": "query_stageprofile",
            "pipeline": "query",
            "plan_digest": self.plan_digest,
            "n_ranks": self.n_ranks,
            "n_operators": self.n_operators,
            "repeats": self.repeats,
            "platform": self.platform,
            "overflow": self.overflow,
            "order": list(self.order),
            "operators": {k: dict(v)
                          for k, v in self.operators.items()},
            "sum_of_operators_s": _round_s(self.sum_of_operators_s),
            "monolithic": {
                "wall_s": _round_s(self.monolithic_wall_s),
                "wall_min_s": _round_s(self.monolithic_wall_min_s),
                "walls_s": [_round_s(w)
                            for w in self.monolithic_walls_s],
            },
            "overlap": self.overlap,
            "cost_model": self.cost_model,
            "predicted_total_s": self.predicted_total_s,
        }

    def summary(self) -> dict:
        """The compact per-record block — ``history.stages_block``
        reads ``wall_s``/``ratio`` dicts without caring that the keys
        are op_ids instead of join-stage names, so query records'
        per-operator walls land in ``analyze history`` trends through
        the existing seam."""
        return {
            "plan_digest": self.plan_digest,
            "pipeline": "query",
            "repeats": self.repeats,
            "platform": self.platform,
            "overflow": self.overflow,
            "wall_s": {k: v["wall_s"]
                       for k, v in self.operators.items()},
            "ratio": {k: v["ratio"] for k, v in self.operators.items()
                      if v.get("ratio") is not None},
            "sum_of_stages_s": _round_s(self.sum_of_operators_s),
            "monolithic_wall_s": _round_s(self.monolithic_wall_s),
            "overlap_fraction": self.overlap["fraction"],
        }

    def format(self) -> str:
        return format_query_stage_record(self.as_record())


def format_query_stage_record(record: dict) -> str:
    """THE one human rendering of a query stage-profile record —
    shared by the driver's ``--query --stage-profile`` printout and
    ``analyze``'s query_stageprofile surfaces."""
    ops = record.get("operators") or {}
    lines = [
        f"query stage profile {str(record.get('plan_digest'))[:16]}: "
        f"{record.get('n_operators')} operator(s), "
        f"{record.get('n_ranks')} rank(s), "
        f"{record.get('repeats')} repeat(s), "
        f"platform={record.get('platform')}"
        + ("  [OVERFLOW — walls belong to a clamped run]"
           if record.get("overflow") else ""),
        f"  {'operator':<14} {'measured':>12} {'predicted':>12} "
        f"{'ratio':>9}",
    ]
    order = [o for o in (record.get("order") or []) if o in ops] + \
        sorted(o for o in ops if o not in (record.get("order") or []))
    for name in order:
        s = ops[name]
        if not s.get("ran"):
            lines.append(f"  {name:<14} {'-':>12} "
                         f"{s.get('predicted_s')!s:>12} {'-':>9}")
            continue
        ratio = (f"x{s['ratio']:.3g}" if s.get("ratio") is not None
                 else "-")
        pred = s.get("predicted_s")
        pred_txt = f"{pred:>12.6f}" if pred else f"{'-':>12}"
        lines.append(f"  {name:<14} {s['wall_s']:>12.6f} "
                     f"{pred_txt} {ratio:>9}")
    ov = record.get("overlap") or {}
    mono = (record.get("monolithic") or {}).get("wall_s")
    if record.get("sum_of_operators_s") is not None \
            and mono is not None:
        lines.append(
            f"  sum-of-operators {record['sum_of_operators_s']:.6f}s "
            f"vs monolithic {mono:.6f}s -> overlap credit "
            f"{ov.get('credit_s'):.6f}s"
            + (f" ({ov['fraction']:.1%} of per-op work hidden)"
               if ov.get("fraction") is not None else ""))
    return "\n".join(lines)


def profile_query_stages(comm, plan, tables, repeats: int = 3,
                         cost_model=None,
                         **defaults) -> QueryStageProfile:
    """Profile one multi-operator :class:`~..planning.query.QueryPlan`
    operator by operator.

    Each operator compiles as its OWN ``make_join_step`` program (the
    exact per-op step ``make_query_step`` chains, via the shared
    ``_op_steps`` seam — same keys, join type, fused aggregate, and
    per-op options), dispatched against the intermediates the warm
    chain produced, with a fetch-one-scalar barrier and N-repeat
    median per op. The monolithic comparator is the ONE
    ``make_query_step`` program ``distributed_query`` times — so
    ``sum(op walls) - monolithic wall`` is the measured cross-operator
    overlap credit. Per-op predictions come from ``explain_query``'s
    ``cost.predict`` verdicts at the same defaults, joining measured
    to predicted at the op_id resolution.

    ``defaults`` are ``distributed_query``-shaped executor defaults
    (per-op plan options win, exactly as in execution). Intended as an
    untimed side pass AFTER any timed region, never inside one.
    """
    import jax

    from distributed_join_tpu import telemetry
    from distributed_join_tpu.parallel.distributed_join import (
        JOIN_SHARDED_OUT,
        _round_up,
    )
    from distributed_join_tpu.parallel.query_exec import (
        _op_steps,
        make_query_step,
        query_sharded_out,
    )
    from distributed_join_tpu.planning.query import explain_query
    from distributed_join_tpu.telemetry.spans import fetch_one_scalar

    if repeats < 1:
        raise ValueError("repeats must be >= 1")
    defaults = dict(defaults)

    # Predictions first (no tracing): one cost.predict verdict per
    # operator at the SAME defaults the profiled programs compile with.
    doc = explain_query(plan, comm, dict(tables),
                        cost_model=cost_model, defaults=defaults,
                        orders=False)
    predicted = {o["id"]: ((o.get("cost") or {}).get("total_s"))
                 for o in doc.get("operators") or []}

    n = comm.n_ranks
    missing = [name for name in plan.tables if name not in tables]
    if missing:
        raise ValueError(
            f"plan references base tables {missing} not supplied "
            f"(have {sorted(tables)})")
    padded = {
        name: tables[name].pad_to(
            _round_up(tables[name].capacity, n))
        for name in plan.tables
    }
    if hasattr(comm, "device_put_sharded"):
        padded = comm.device_put_sharded(padded)

    # -- per-operator programs (the _op_steps seam) -------------------

    steps = _op_steps(comm, plan, defaults, False, None)
    op_fns = [comm.spmd(s, sharded_out=JOIN_SHARDED_OUT)
              for s in steps]

    # Warm chain: run each op program once, threading intermediates
    # exactly as make_query_step's env does — the captured per-op
    # inputs are what the timed repeats re-dispatch.
    overflow_seen = False
    env = dict(padded)
    op_inputs = []
    for op, fn in zip(plan.ops, op_fns):
        fargs = (env[op.build], env[op.probe])
        res = fn(*fargs)
        fetch_one_scalar(res.total)
        overflow_seen = overflow_seen or bool(res.overflow)
        env[op.op_id] = res.table
        op_inputs.append((op.op_id, fn, fargs))

    # The monolithic comparator: the exact program distributed_query
    # dispatches (with_metrics=False — the seed hot path).
    mono_step = make_query_step(comm, plan, defaults=defaults)
    fn_mono = comm.spmd(
        mono_step, sharded_out=query_sharded_out(plan, False))
    margs = tuple(padded[name] for name in plan.tables)
    warm = fn_mono(*margs)
    fetch_one_scalar(warm.total)
    overflow_seen = overflow_seen or bool(warm.overflow)

    # -- timed repeats (fetch-one-scalar barrier per op) --------------

    walls: dict = {op_id: [] for op_id, *_ in op_inputs}
    mono_walls = []
    for _ in range(repeats):
        for op_id, fn, fargs in op_inputs:
            t0 = time.perf_counter()
            res = fn(*fargs)
            fetch_one_scalar(res.total)
            dt = time.perf_counter() - t0
            walls[op_id].append(dt)
            telemetry.span_complete(f"query_profile.{op_id}", t0, dt)
        t0 = time.perf_counter()
        res = fn_mono(*margs)
        fetch_one_scalar(res.total)
        dt = time.perf_counter() - t0
        mono_walls.append(dt)
        telemetry.span_complete("query_profile.monolithic", t0, dt)

    operators = {
        op_id: _stage_entry(True, walls[op_id], None,
                            predicted.get(op_id) or 0.0)
        for op_id, *_ in op_inputs
    }
    return QueryStageProfile(
        plan_digest=doc.get("digest") or plan.digest(),
        n_ranks=n,
        n_operators=len(plan.ops),
        repeats=repeats,
        platform=jax.default_backend(),
        overflow=overflow_seen,
        operators=operators,
        order=[op.op_id for op in plan.ops],
        monolithic_walls_s=mono_walls,
        predicted_total_s=doc.get("total_s"),
    )


