"""The roofline cost model — ROOFLINE.md's measured numbers, in code.

``docs/ROOFLINE.md`` §1 measured what every primitive of the join
pipeline costs on a real v5e (sorts ~7 ns/element with value lanes
riding ~free, random gathers a serialized ~10-21 ns/element loop,
scans ~2 ns/element, the Pallas expand ~11-16 ns/output-row), and §6-8
refined the per-stage split (sort 138 ms / compaction 116 ms / expand
80 ms of a 20M-element 360 ms join). Until this module those numbers
lived only in a doc; :class:`CostModel` makes them an executable
predictor over a :class:`~.plan.JoinPlan`: per-stage wall seconds and
the derived rows/s, before anything traces or compiles.

Honesty contract:

- Every constant is either MEASURED (named row of ROOFLINE.md §1/§6,
  chip wall clocks) or SPEC-DERIVED (the ICI bandwidth — this
  environment exposes one chip, so the all-to-all has never been
  measured on real ICI; ROADMAP item 1's hardware session is the
  calibration path). ``CostModel.provenance`` says which is which.
- Predictions model the **v5e roofline**, not whatever backend the
  process happens to run on. On the 8-virtual-device CPU mesh the
  predicted WALL is deliberately wrong (emulation measures the host);
  the predicted WIRE BYTES are exact in padded/compressed modes and
  are gated in CI. ``analyze explain`` grades both against measured
  counters after a run, and the workload-history store records the
  error per workload signature so the autotuner (ROADMAP item 5)
  learns where this model lies.

Everything here is plain host arithmetic — no jax import, no device
touch, deterministic to the byte (the explain artifact is
byte-identical across runs of the same query spec).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

COST_MODEL_VERSION = 1

# Prediction band for grading: a measured wall within
# [predicted / BAND, predicted * BAND] is "inside the model"; outside
# means the model (or the machine) moved for that workload. Wide on
# purpose — the model is a v5e roofline, and §5-8 of ROOFLINE.md show
# real implementations landing within ~1.5-4x of primitive floors.
DEFAULT_PREDICTION_BAND = 4.0


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Per-primitive costs (ns/element unless noted), v5e.

    Measured constants cite their ROOFLINE.md row; spec-derived ones
    say so. Replace any field and re-run ``predict`` — the explain
    artifact embeds the constants used, so a graded run is always
    attributable to one concrete model.
    """

    # lax.sort, 20M x (i64 key + small lanes): 139-168 ms (§1, §6).
    sort_ns_per_elem: float = 7.0
    # batched short-run lax.sort — the §6 run-length regime the
    # segmented-sort pipeline rides (ops/segmented.py, §9): the same
    # 20M x (i64, i8, i64) operands sort in 24-45 ms as independent
    # runs ((8192, 2048): 24 ms; (512, 32768): 38 ms) => ~1.2-2.2
    # ns/elem; the conservative midpoint ships until the first
    # real-chip segmented stage profile refits it
    # (calibrate_from_stage_profile).
    sort_run_ns_per_elem: float = 1.9
    # each extra i64 value lane on a 139 ms sort: +6 ms (§1).
    sort_lane_ns_per_elem: float = 0.3
    # cumsum/cummax 20M i32: 30-43 ms (§1).
    scan_ns_per_elem: float = 2.0
    # random gather, any index order: 161-205 ms / 7.5M i64 (§1).
    gather_ns_per_elem: float = 21.0
    # packed (rows, k<=4) row gather: ~110 ms / 7.5M rows (§1 fact 3).
    row_gather_ns_per_row: float = 14.7
    # log-shift plane compaction: 116 ms / 20M merged elements (§6).
    compact_ns_per_elem: float = 5.8
    # Pallas streaming expand: ~80 ms / 7.5M output rows (§6).
    expand_ns_per_out_row: float = 10.7
    # sequential HBM stream (§1 fact 2's contrast case).
    hbm_bytes_per_s: float = 8.0e11
    # FoR+bitpack codec encode/decode: 26/28 GB/s (BASELINE.md row 18).
    codec_bytes_per_s: float = 2.6e10
    # SPEC-DERIVED: v5e ICI per-chip all-to-all egress. Never measured
    # here (one-chip environment; BASELINE.md row 17) — recalibrate
    # from the first real `tpu-all-to-all` session (ROADMAP item 1).
    ici_bytes_per_s: float = 4.5e10
    # SPEC-DERIVED: per-chip cross-slice (DCN) egress of a multi-slice
    # v5e pod — ~25 GB/s NIC per 8-chip host, ~3 GB/s per chip. Never
    # measured here (no multi-slice allocation yet); flagged
    # uncalibrated until the first multi-slice chip run refits it
    # through the same calibrate_from_stage_profile seam as ICI
    # (ROADMAP item 5). Sits BELOW the
    # codec's ~5-7 GB/s break-even — the whole reason the FoR+bitpack
    # wire flips from NO-GO to win on the cross-slice tier
    # (docs/HIERARCHY.md).
    dcn_bytes_per_s: float = 3.0e9
    # per-collective dispatch/sync overhead (spec-derived order).
    collective_latency_s: float = 2.0e-5
    # v5e HBM per chip, for the footprint verdict (16 GiB).
    hbm_capacity_bytes: int = 16 * 1024**3
    # Set by calibrate_from_history: the global measured/predicted
    # wall scale this model was refit with (None = the as-shipped
    # ROOFLINE.md constants).
    calibrated_scale: Optional[float] = None
    # Set by calibrate_from_stage_profile: ((stage, scale), ...) of
    # the PER-STAGE measured/predicted ratios the stage-owned
    # constants were refit with (None = no stage-level refit). A tuple
    # (not a dict) so the frozen dataclass stays hashable.
    calibrated_stage_scales: Optional[tuple] = None

    @property
    def provenance(self) -> dict:
        return {
            "measured": [
                "sort_ns_per_elem", "sort_run_ns_per_elem",
                "sort_lane_ns_per_elem",
                "scan_ns_per_elem", "gather_ns_per_elem",
                "row_gather_ns_per_row", "compact_ns_per_elem",
                "expand_ns_per_out_row", "hbm_bytes_per_s",
                "codec_bytes_per_s",
            ],
            "spec_derived": [
                "ici_bytes_per_s", "dcn_bytes_per_s",
                "collective_latency_s", "hbm_capacity_bytes",
            ],
            "source": "docs/ROOFLINE.md §1/§6; BASELINE.md"
                      + ("" if self.calibrated_scale is None else
                         f"; calibrated x{self.calibrated_scale:g} "
                         "from measured history")
                      + ("" if self.calibrated_stage_scales is None
                         else "; stage-calibrated "
                         + " ".join(f"{s}=x{v:g}" for s, v in
                                    self.calibrated_stage_scales)
                         + " from a stage profile"),
        }

    def as_record(self) -> dict:
        rec = dataclasses.asdict(self)
        rec["model_version"] = COST_MODEL_VERSION
        rec["provenance"] = self.provenance
        return rec


DEFAULT_COST_MODEL = CostModel()

# The FoR+bitpack codec's measured break-even wire bandwidth
# (results/compression_for_bitpack.json, docs/ROOFLINE.md: ~5-7 GB/s;
# the conservative upper edge): below it, encode+decode time is
# cheaper than the bytes it removes. ICI (~45 GB/s) sits far above —
# codec NO-GO; DCN (~3 GB/s) sits below — codec win. This one number
# is what the hierarchical shuffle's `dcn_codec="auto"` resolves
# against.
CODEC_BREAK_EVEN_BYTES_PER_S = 7.0e9

DCN_CODEC_KNOBS = ("off", "auto", "on")


def resolve_dcn_codec(knob: str,
                      model: Optional[CostModel] = None) -> bool:
    """Resolve the hierarchical shuffle's ``dcn_codec`` knob to a
    static on/off: ``"auto"`` turns the codec on exactly when the
    configured cross-slice bandwidth sits below the codec's measured
    break-even. Deterministic host arithmetic — the verdict is baked
    into the compiled program (and its signature carries the knob, so
    a changed model constant cannot silently alias two programs)."""
    if knob not in DCN_CODEC_KNOBS:
        raise ValueError(
            f"unknown dcn_codec {knob!r}; pick one of "
            f"{DCN_CODEC_KNOBS}")
    if knob == "auto":
        m = model or DEFAULT_COST_MODEL
        return m.dcn_bytes_per_s < CODEC_BREAK_EVEN_BYTES_PER_S
    return knob == "on"


def resolve_dcn_bits(knob: str, compression_bits: Optional[int] = None,
                     *, n_slices: int,
                     model: Optional[CostModel] = None) -> Optional[int]:
    """THE one resolution of the hierarchical shuffle's cross-slice
    residual width, shared by the capacity ladder, the drivers, and
    the stage profiler: the caller's bits (defaulting to
    ``DEFAULT_DCN_CODEC_BITS``) exactly when the codec resolves on
    AND the mesh has a cross-slice tier to ride. One slice has no DCN
    payload — the degenerate hierarchy routes the flat raw padded
    path — so bits resolve to ``None`` there (armed bits would burn
    the first retry rung widening a knob the program ignores). The
    knob VALUE is validated regardless, so a typo fails loudly on
    every topology."""
    on = resolve_dcn_codec(knob, model)
    if n_slices <= 1 or not on:
        return None
    from distributed_join_tpu.parallel.distributed_join import (
        DEFAULT_DCN_CODEC_BITS,
    )

    return compression_bits or DEFAULT_DCN_CODEC_BITS


def _round_s(x: float) -> float:
    """Deterministic second-rounding for the artifact (9 digits keeps
    ns resolution without float repr jitter)."""
    return round(float(x), 9)


def predict(plan, model: Optional[CostModel] = None) -> dict:
    """Per-stage predicted wall seconds (per rank — the pipeline is
    symmetric, so per-rank == critical path) for one join step plus
    the derived throughput. ``plan`` is a :class:`~.plan.JoinPlan`.

    Stage decomposition mirrors make_join_step: partition (one
    bucket sort per side + the to_padded gathers), shuffle (wire
    bytes over ICI + per-collective latency + codec time when
    compression is on), join per batch (merged sort + scans +
    compaction over the merged domain, expand over the output block),
    and the skew sidecar when the PRPD path is on.
    """
    m = model or DEFAULT_COST_MODEL
    n = plan.n_ranks
    k = plan.over_decomposition
    ns = 1e-9
    # Probe-only plans (resident build tables, plan.build_probe_plan):
    # the build side is an on-device image prepared at registration —
    # no per-request build partition work or wire bytes — while the
    # join stage still merges each batch against the full resident
    # shard (capacities["resident_rows_per_rank"]).
    probe_only = bool(getattr(plan, "probe_only", False))
    # Aggregation pushdown (pipeline "join_agg", docs/AGGREGATION.md):
    # the fused pipeline reduces in the merged domain and NEVER runs
    # the output expand/gathers that dominate materialization
    # (ROOFLINE §1-§3) — the expand constant drops out of the join
    # stage entirely, and the shuffle stage gains the groups-sized
    # partials exchange (plan.wire["partials"], probe mode only).
    fused_agg = getattr(plan, "pipeline", "join") in (
        "join_agg", "probe_join_agg")
    wire_sides = ("build", "probe", "partials") if fused_agg \
        else ("build", "probe")

    b_local = plan.build.rows_local
    p_local = plan.probe.rows_local
    b_cols = max(len(plan.build.columns), 1)
    p_cols = max(len(plan.probe.columns), 1)

    single = plan.n_buckets == 1
    # Rows materialized into the shuffle layout per side (k batches of
    # the n x cap padded block; ragged ships the same rows unpadded).
    b_shipped = 0 if single else k * n * plan.capacities["shuffle_build_per_bucket"]
    p_shipped = 0 if single else k * n * plan.capacities["shuffle_probe_per_bucket"]

    # -- partition: bucket sort (2 int32 lanes) + one composed gather
    # per column into the padded/ragged layout.
    if single:
        partition_s = 0.0
    elif probe_only:
        partition_s = ns * (
            p_local * m.sort_ns_per_elem
            + p_shipped * m.row_gather_ns_per_row * _col_groups(p_cols)
        )
    else:
        partition_s = ns * (
            (b_local + p_local) * m.sort_ns_per_elem
            + b_shipped * m.row_gather_ns_per_row * _col_groups(b_cols)
            + p_shipped * m.row_gather_ns_per_row * _col_groups(p_cols)
        )

    # -- shuffle: off-chip bytes at ICI bandwidth + dispatch latency.
    shuffle_tiers = None
    if single:
        shuffle_s = 0.0
    elif (plan.shuffle == "hierarchical"
          and getattr(plan, "n_slices", 1) > 1):
        # Two sequential tiers (phase 2 consumes phase 1's output, so
        # the honest price is the SUM; both appear separately in the
        # record so `analyze stages`/the tuner can see which tier
        # dominates). Off-chip fractions: (c-1)/c of the ICI block
        # leaves the chip within a slice, (s-1)/s of the DCN block
        # crosses slices.
        s_ = getattr(plan, "n_slices", 1)
        c_ = max(n // s_, 1)
        ici_rank = sum((plan.wire.get(side) or {})
                       .get("ici_bytes_per_rank", 0)
                       for side in wire_sides)
        dcn_rank = sum((plan.wire.get(side) or {})
                       .get("dcn_bytes_per_rank", 0)
                       for side in wire_sides)
        ici_s = (ici_rank * (c_ - 1) / c_) / m.ici_bytes_per_s
        dcn_s = (dcn_rank * (s_ - 1) / s_) / m.dcn_bytes_per_s
        codec_s = 0.0
        raw = sum((plan.wire.get(side) or {})
                  .get("dcn_raw_bytes_per_rank", 0)
                  for side in wire_sides)
        if raw:
            # encode + decode of the raw cross-slice block bytes.
            codec_s = 2.0 * raw / m.codec_bytes_per_s
        shuffle_s = (ici_s + dcn_s + codec_s
                     + plan.wire["collectives_per_step"]
                     * m.collective_latency_s)
        shuffle_tiers = {"ici_s": _round_s(ici_s),
                         "dcn_s": _round_s(dcn_s),
                         "codec_s": _round_s(codec_s)}
    else:
        wire_rank = sum((plan.wire.get(side) or {})
                        .get("bytes_per_rank", 0)
                        for side in wire_sides)
        offchip = wire_rank * (n - 1) / n
        shuffle_s = (offchip / m.ici_bytes_per_s
                     + plan.wire["collectives_per_step"]
                     * m.collective_latency_s)
        if plan.compression_bits is not None:
            # encode + decode of the raw (uncompressed) block bytes.
            raw = (plan.wire["build"].get("raw_bytes_per_rank", 0)
                   + plan.wire["probe"].get("raw_bytes_per_rank", 0))
            shuffle_s += 2.0 * raw / m.codec_bytes_per_s

    # -- local join, per batch: sort both received sides into the
    # merged domain, scans + compaction over it, expand the output.
    if single:
        merged = b_local + p_local
        out_total = plan.capacities["out_rows_per_batch"]
        batches = 1
    elif probe_only:
        merged = (plan.capacities.get("resident_rows_per_rank",
                                      b_local)
                  + n * plan.capacities["shuffle_probe_per_bucket"])
        out_total = plan.capacities["out_rows_per_batch"]
        batches = k
    else:
        merged = (n * plan.capacities["shuffle_build_per_bucket"]
                  + n * plan.capacities["shuffle_probe_per_bucket"])
        out_total = plan.capacities["out_rows_per_batch"]
        batches = k
    if fused_agg:
        # Zero materialization: no record expand, no output gathers —
        # the segmented scans and the groups-sized compaction ride the
        # scan/compact constants over the merged domain.
        out_total = 0
    # Segmented-sort mode (capacities["sort_segments"] > 1): the
    # merged + record sorts run as batched short runs at the §6
    # run-length rate instead of the flat superlinear rate — the whole
    # point of the pipeline (ROOFLINE §9); scans/compaction/expand
    # costs are unchanged (same total elements, batched).
    sort_c = (m.sort_run_ns_per_elem
              if (plan.capacities.get("sort_segments") or 1) > 1
              else m.sort_ns_per_elem)
    join_s = batches * ns * (
        merged * (sort_c
                  + m.sort_lane_ns_per_elem * 2
                  + m.scan_ns_per_elem
                  + m.compact_ns_per_elem)
        + out_total * m.expand_ns_per_out_row
    )

    # -- skew sidecar: HH detection scans the probe keys; the HH join
    # runs over the compacted HH blocks.
    skew_s = 0.0
    if plan.skew is not None:
        hh_rows = (plan.capacities.get("hh_build") or 0) * n \
            + (plan.capacities.get("hh_probe") or 0)
        skew_s = ns * (
            (b_local + p_local) * m.scan_ns_per_elem
            + hh_rows * m.sort_ns_per_elem
            + (plan.capacities.get("hh_out") or 0)
            * m.expand_ns_per_out_row
        )

    total = partition_s + shuffle_s + join_s + skew_s
    rows = plan.build.rows_global + plan.probe.rows_global
    out = {
        "model": m.as_record(),
        "platform": "tpu-v5e-roofline",
        "stages": {
            "partition": _round_s(partition_s),
            "shuffle": _round_s(shuffle_s),
            "join": _round_s(join_s),
            "skew": _round_s(skew_s),
        },
        "total_s": _round_s(total),
        "predicted_rows_per_sec": _round_s(rows / total) if total else None,
        "predicted_m_rows_per_sec_per_rank": (
            _round_s(rows / total / 1e6 / n) if total else None),
    }
    if shuffle_tiers is not None:
        # Sibling of "stages" (never inside it — the stage set is 1:1
        # with STAGE_CONSTANTS/stageprof.STAGE_KEYS by contract): the
        # per-tier split of the shuffle price, so `analyze explain`
        # and the tuner can see which tier dominates.
        out["shuffle_tiers"] = shuffle_tiers
    return out


def _col_groups(n_cols: int) -> float:
    """ROOFLINE §1 fact 3: a packed row gather is flat in k for k <= 4
    columns, so materialization pays one gather per group of 4."""
    return max((n_cols + 3) // 4, 1)


# The per-stage cost constants a calibration scale applies to:
# time-per-element constants scale WITH the measured/predicted ratio,
# bandwidth constants scale AGAINST it (wall = bytes / bandwidth).
_TIME_CONSTANTS = (
    "sort_ns_per_elem", "sort_lane_ns_per_elem", "scan_ns_per_elem",
    "gather_ns_per_elem", "row_gather_ns_per_row",
    "compact_ns_per_elem", "expand_ns_per_out_row",
    "collective_latency_s",
)
_BANDWIDTH_CONSTANTS = ("hbm_bytes_per_s", "codec_bytes_per_s",
                        "ici_bytes_per_s", "dcn_bytes_per_s")


def calibrate_from_history(entries, model: Optional[CostModel] = None,
                           *, min_entries: int = 3,
                           platform: Optional[str] = "tpu"):
    """Refit the cost model from a workload-history store's
    measured/predicted wall ratios (``prediction.wall_ratio`` per
    entry, ``telemetry/history.py``) — the calibration seam ROADMAP
    item 1's hardware session feeds.

    Honesty contract: per-run entries carry ONE total-wall ratio, so
    the only fit the data supports is a single multiplicative
    correction applied uniformly — time constants scale with the
    median ratio, bandwidth constants against it. Separating
    per-stage error needs per-stage measurements: that is
    :func:`calibrate_from_stage_profile`, fed by a ``--stage-profile``
    run (``telemetry/stageprof.py``). Only entries measured on ``platform`` count (default
    "tpu": CPU-mesh walls measure emulation, and a model refit from
    them would be confidently wrong about the chip — the exact
    failure mode the provenance block exists to prevent); pass
    ``platform=None`` to calibrate against whatever was measured
    (testing only).

    Returns ``(model_or_None, report)``: None with
    ``report["calibrated"] = False`` when fewer than ``min_entries``
    eligible entries exist — an uncalibratable store refuses loudly
    instead of shipping a model refit from noise.
    """
    base = model or DEFAULT_COST_MODEL
    ratios = []
    for e in entries or []:
        pred = e.get("prediction")
        if not isinstance(pred, dict) or not pred.get("wall_ratio"):
            continue
        if e.get("outcome") not in ("ok", "served", "recovered"):
            continue
        if platform is not None and e.get("platform") != platform:
            continue
        ratios.append(float(pred["wall_ratio"]))
    report = {
        "platform": platform,
        "n_eligible": len(ratios),
        "min_entries": min_entries,
        "base_calibrated_scale": base.calibrated_scale,
    }
    if len(ratios) < min_entries:
        report.update(
            calibrated=False,
            reason=(f"need >= {min_entries} measured "
                    f"{platform or 'any'}-platform entries with a "
                    f"wall ratio, have {len(ratios)}"))
        return None, report
    ratios.sort()
    scale = ratios[len(ratios) // 2]
    fields = {k: getattr(base, k) * scale for k in _TIME_CONSTANTS}
    fields.update({k: getattr(base, k) / scale
                   for k in _BANDWIDTH_CONSTANTS})
    calibrated = dataclasses.replace(
        base, calibrated_scale=round(scale, 6), **fields)
    report.update(
        calibrated=True,
        scale=round(scale, 6),
        ratio_min=round(ratios[0], 4),
        ratio_median=round(scale, 4),
        ratio_max=round(ratios[-1], 4),
    )
    return calibrated, report


# Which constants each pipeline stage OWNS for the per-constant refit
# (calibrate_from_stage_profile). Honesty note: sort_ns_per_elem
# appears in both the partition and join predictions; the partition
# stage — a pure bucket sort + materialization gather — owns it, and
# the join stage's merged-sort share of any error is absorbed into the
# join-owned constants. hbm_bytes_per_s feeds no stage prediction and
# is never refit here.
STAGE_CONSTANTS = {
    "partition": {
        "time": ("sort_ns_per_elem", "gather_ns_per_elem",
                 "row_gather_ns_per_row"),
        "bandwidth": (),
    },
    "shuffle": {
        "time": ("collective_latency_s",),
        "bandwidth": ("ici_bytes_per_s", "dcn_bytes_per_s",
                      "codec_bytes_per_s"),
    },
    "join": {
        "time": ("sort_run_ns_per_elem", "sort_lane_ns_per_elem",
                 "scan_ns_per_elem", "compact_ns_per_elem",
                 "expand_ns_per_out_row"),
        "bandwidth": (),
    },
}


def calibrate_from_stage_profile(profiles,
                                 model: Optional[CostModel] = None,
                                 *, min_profiles: int = 1,
                                 platform: Optional[str] = "tpu"):
    """Refit INDIVIDUAL cost constants from stage-segmented profiles
    (``telemetry/stageprof.py``'s ``stageprofile.json`` records) — the
    per-constant seam ``calibrate_from_history`` cannot provide: a
    history entry carries one total-wall ratio, while a stage profile
    carries one measured/predicted ratio PER stage, so the sort
    constants (partition stage), the ICI bandwidth + collective
    latency (shuffle stage), and the merge/compact/expand constants
    (join stage) refit independently (:data:`STAGE_CONSTANTS` is the
    ownership map).

    ``profiles`` is one record dict or a sequence of them. Per stage
    the median measured/predicted ratio over eligible profiles becomes
    that stage's scale: stage-owned time constants multiply by it,
    bandwidth constants divide. Eligibility mirrors
    ``calibrate_from_history``: overflowed profiles never count, and
    only ``platform``-stamped profiles do (default "tpu" — a CPU-mesh
    stage wall measures emulation; pass ``platform=None`` to calibrate
    against whatever was measured, testing only).

    Returns ``(model_or_None, report)``; fewer than ``min_profiles``
    eligible profiles refuses loudly (``report["calibrated"] =
    False``) instead of shipping a model refit from noise.
    """
    base = model or DEFAULT_COST_MODEL
    if isinstance(profiles, dict):
        profiles = [profiles]
    ratios: dict = {}
    dcn_ratios: list = []
    sort_run_ratios: list = []
    eligible = 0
    for p in profiles or []:
        if not isinstance(p, dict) or p.get("kind") != "stageprofile":
            continue
        if p.get("overflow"):
            continue
        if platform is not None and p.get("platform") != platform:
            continue
        counted = False
        for stage, info in (p.get("stages") or {}).items():
            if stage not in STAGE_CONSTANTS:
                continue
            if not isinstance(info, dict) or not info.get("ran"):
                continue
            pred, wall = info.get("predicted_s"), info.get("wall_s")
            if pred and wall:
                r = float(wall) / float(pred)
                # Per-tier attribution cuts BOTH ways: a flat
                # profile's shuffle ratio carries zero DCN evidence
                # (scaling the spec constant from it could silently
                # cross the codec break-even), and a DCN-carrying
                # profile's shuffle wall is dominated by the slow
                # tier — folding its ratio into ici/codec would
                # corrupt the fast-tier constants with DCN error.
                # Each shuffle ratio refits exactly one tier.
                if stage == "shuffle" and any(
                        (info.get("counters") or {}).get(
                            f"{s}.wire_bytes_dcn")
                        for s in ("build", "probe")):
                    dcn_ratios.append(r)
                elif stage == "join" and (
                        p.get("sort_segments") or 1) > 1:
                    # Same discipline for the sort modes: a SEGMENTED
                    # profile's join wall is dominated by the batched
                    # short-run sort, so its ratio refits ONLY
                    # sort_run_ns_per_elem — and a flat profile (no
                    # batched sort ever ran) must never touch it.
                    sort_run_ratios.append(r)
                else:
                    ratios.setdefault(stage, []).append(r)
                counted = True
        if counted:
            eligible += 1
    report = {
        "platform": platform,
        "n_eligible": eligible,
        "min_profiles": min_profiles,
    }
    if eligible < min_profiles:
        report.update(
            calibrated=False,
            reason=(f"need >= {min_profiles} non-overflowed "
                    f"{platform or 'any'}-platform stage profiles "
                    f"with per-stage ratios, have {eligible}"))
        return None, report
    fields: dict = {}
    scales: dict = {}
    refit: dict = {}
    for stage, rs in sorted(ratios.items()):
        rs.sort()
        scale = round(rs[len(rs) // 2], 6)
        scales[stage] = scale
        owned = STAGE_CONSTANTS[stage]
        fit_time = list(owned["time"])
        if stage == "join":
            # sort_run_ns_per_elem refits ONLY from segmented
            # profiles (their own median below) — a flat join ratio
            # carries zero batched-short-run-sort evidence.
            fit_time.remove("sort_run_ns_per_elem")
        for k in fit_time:
            fields[k] = getattr(base, k) * scale
        fit_bw = list(owned["bandwidth"])
        if stage == "shuffle":
            # dcn_bytes_per_s refits ONLY from profiles whose shuffle
            # stage actually moved cross-slice bytes — a flat
            # profile's ratio carries zero DCN evidence, and scaling
            # the uncalibrated spec constant from it could silently
            # cross the codec break-even. Hierarchical profiles get
            # their own median below.
            fit_bw.remove("dcn_bytes_per_s")
        for k in fit_bw:
            fields[k] = getattr(base, k) / scale
        refit[stage] = fit_time + fit_bw
    sort_run_scale = None
    if sort_run_ratios:
        sort_run_ratios.sort()
        sort_run_scale = round(
            sort_run_ratios[len(sort_run_ratios) // 2], 6)
        fields["sort_run_ns_per_elem"] = \
            base.sort_run_ns_per_elem * sort_run_scale
        refit.setdefault("join", []).append("sort_run_ns_per_elem")
        scales.setdefault("join", sort_run_scale)
    dcn_scale = None
    if dcn_ratios:
        dcn_ratios.sort()
        dcn_scale = round(dcn_ratios[len(dcn_ratios) // 2], 6)
        fields["dcn_bytes_per_s"] = base.dcn_bytes_per_s / dcn_scale
        refit.setdefault("shuffle", []).append("dcn_bytes_per_s")
    calibrated = dataclasses.replace(
        base,
        calibrated_stage_scales=tuple(sorted(scales.items())),
        **fields)
    report.update(
        calibrated=True,
        stage_scales=scales,
        dcn_scale=dcn_scale,
        sort_run_scale=sort_run_scale,
        refit=refit,
        # the stage the shipped model mispredicts hardest (log scale:
        # x4 optimistic and x0.25 pessimistic are equally wrong)
        worst_stage=max(scales, key=lambda s: abs(math.log(scales[s]))),
        unfit_stages=[s for s in STAGE_CONSTANTS if s not in scales],
    )
    return calibrated, report


def predict_exchange(n_ranks: int, bytes_per_rank: int,
                     model: Optional[CostModel] = None) -> dict:
    """The all_to_all microbenchmark's reduced prediction: one
    fixed-size exchange (`tpu-all-to-all`'s --explain)."""
    m = model or DEFAULT_COST_MODEL
    offchip = bytes_per_rank * (n_ranks - 1) / n_ranks
    total = offchip / m.ici_bytes_per_s + m.collective_latency_s
    return {
        "model": m.as_record(),
        "platform": "tpu-v5e-roofline",
        "stages": {"all_to_all": _round_s(total)},
        "total_s": _round_s(total),
        "predicted_aggregate_offchip_gb_per_sec": _round_s(
            n_ranks * offchip / total / 1e9),
    }
