"""TPC-H ``orders`` (build) and ``lineitem`` (probe) for the Q3 join on
``orderkey``, with Q3's date predicates as validity masks, carrying the
columns that Q3 selects and returns.

After the program's ``utils/tpch.py`` (copied, so that no change to the
program changes the data), which follows dbgen's join structure:

- ``orders``: ``1.5M x SF`` rows; order keys are dbgen's sparse keys
  (8 used out of every 32); ``o_custkey`` uniform over the customer keys
  ``1 .. 150,000 x SF`` that are not multiples of 3 (dbgen leaves a third
  of the customers without orders); ``o_orderdate`` uniform over the
  2406 days 1992-01-01 .. 1998-08-02; ``o_shippriority`` 0, as dbgen
  writes it.
- ``lineitem``: 1 to 7 lines per order; ``l_shipdate`` trails the order
  date by 1 to 121 days; ``l_extendedprice`` in cents; ``l_discount`` in
  percent 0..10.

One departure, so that every seed gives the same shapes and the same
work: the lines per order are ``1 + (i mod 7)`` over the orders ``i``,
dealt to the orders in an order drawn from the seed. Each order's count
is still uniform over 1..7, and the total is fixed by the scale.

Q3 (``DATE = 1995-03-15``, day ``cutoff_day`` of the range): orders
placed before it join lineitems shipped after it. Q3 selects
``l_orderkey``, ``o_orderdate`` and ``o_shippriority``, sums
``l_extendedprice * (1 - l_discount)``, and filters on ``o_custkey`` (the
customer leg), ``o_orderdate`` and ``l_shipdate``: those are the columns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ORDERS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 150_000
DATE_RANGE_DAYS = 2406
MAX_SHIP_LAG_DAYS = 121
MAX_LINES_PER_ORDER = 7


def n_orders(cfg: dict) -> int:
    return int(ORDERS_PER_SF * cfg["scale_factor"])


def n_lines(n: int) -> int:
    """Sum of ``1 + (i mod 7)`` over ``i < n``."""
    full, rest = divmod(n, MAX_LINES_PER_ORDER)
    per_cycle = MAX_LINES_PER_ORDER * (MAX_LINES_PER_ORDER + 1) // 2
    return full * per_cycle + rest * (rest + 1) // 2


def tables(cfg: dict, key) -> dict:
    n = n_orders(cfg)
    total = n_lines(n)
    cutoff = cfg["cutoff_day"]
    ko, kl = jax.random.split(key)
    k_date, k_cust = jax.random.split(ko)
    i = jnp.arange(n, dtype=jnp.int64)
    orderkey = (i // 8) * 32 + (i % 8) + 1
    orderdate = jax.random.randint(k_date, (n,), 0, DATE_RANGE_DAYS,
                                   dtype=jnp.int32)
    # The r-th customer key that is not a multiple of 3.
    r = jax.random.randint(k_cust, (n,), 0,
                           int(CUSTOMERS_PER_SF * cfg["scale_factor"])
                           * 2 // 3, dtype=jnp.int32)
    custkey = 3 * (r // 2) + 1 + r % 2
    k_deal, k_ship, k_ext, k_disc = jax.random.split(kl, 4)
    deal = jax.random.permutation(k_deal, n)
    counts = (1 + deal % MAX_LINES_PER_ORDER).astype(jnp.int32)
    l_orderkey = jnp.repeat(orderkey, counts, total_repeat_length=total)
    l_orderdate = jnp.repeat(orderdate, counts, total_repeat_length=total)
    shipdate = l_orderdate + jax.random.randint(
        k_ship, (total,), 1, MAX_SHIP_LAG_DAYS + 1, dtype=jnp.int32)
    return {
        "build": {"columns": {"orderkey": orderkey,
                              "o_custkey": custkey,
                              "o_orderdate": orderdate,
                              "o_shippriority": jnp.zeros((n,), jnp.int32)},
                  "valid": orderdate < cutoff},
        "probe": {"columns": {
            "orderkey": l_orderkey,
            "l_shipdate": shipdate,
            "l_extendedprice": jax.random.randint(
                k_ext, (total,), 90_000, 10_500_000, dtype=jnp.int64),
            "l_discount": jax.random.randint(k_disc, (total,), 0, 11,
                                             dtype=jnp.int32)},
            "valid": shipdate > cutoff},
    }
