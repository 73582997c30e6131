"""A dimension table with unique int64 keys (build) joined by a fact
table whose keys follow a power law over the same keys (probe): the
Zipf-skewed foreign-key join of the reference benchmark's skew
configuration, as this repository's port of its driver draws it
(``--zipf-alpha``: a unique-key build table, Zipf probe keys).

Build side: the uniform generator's unique-key build
(``joinbench.data.uniform``): each key of ``[0, rand_max)`` once, in an
order drawn from the seed. Probe keys: a continuous Pareto truncated to
``[1, rand_max + 1)``, ``x = u ** (-1 / (alpha - 1))`` with ``u``
uniform in ``[(rand_max + 1) ** -(alpha - 1), 1)``, and key
``floor(x) - 1``, so that ``P(key >= k) ~ (k + 1) ** -(alpha - 1)`` and
no key is clipped onto the top of the range. ``u`` is made from 53
random bits in float64 arithmetic (a TPU cannot bitcast to float64).
Every payload is its row id.

``build_rows``, ``probe_rows`` and ``rand_max`` are multiplied by the
config's ``scale_factor`` (1 where absent).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from joinbench.data import uniform


def scaled(cfg: dict, name: str) -> int:
    return int(cfg[name] * cfg.get("scale_factor", 1))


def zipf_keys(key, rows: int, alpha: float, rand_max: int, dtype):
    bits = jax.random.bits(key, (rows,), jnp.uint64) >> jnp.uint64(11)
    lo = float(rand_max + 1) ** -(alpha - 1.0)
    u = lo + bits.astype(jnp.int64).astype(jnp.float64) * (
        (1.0 - lo) * 2.0 ** -53)
    x = jnp.exp(jnp.log(u) * (-1.0 / (alpha - 1.0)))
    # The clip only guards float rounding at the range's two ends.
    k = jnp.clip(jnp.floor(x).astype(jnp.int64) - 1, 0, rand_max - 1)
    return k.astype(dtype)


def tables(cfg: dict, key) -> dict:
    nb, npr = scaled(cfg, "build_rows"), scaled(cfg, "probe_rows")
    rand_max = scaled(cfg, "rand_max")
    kb, kp = jax.random.split(key)
    build = uniform.tables(
        dict(cfg, build_rows=nb, probe_rows=8, rand_max=rand_max,
             unique_build_keys=True, selectivity=1.0), kb)["build"]
    pay = jnp.dtype(cfg["payload_dtype"])
    probe_keys = zipf_keys(kp, npr, cfg["zipf_alpha"], rand_max,
                           jnp.dtype(cfg["key_dtype"]))
    return {
        "build": build,
        "probe": {"columns": {"key": probe_keys,
                              "probe_payload": jnp.arange(npr, dtype=pay)},
                  "valid": jnp.ones((npr,), bool)},
    }
