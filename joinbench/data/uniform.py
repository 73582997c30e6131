"""Uniform int64 build/probe tables, after the reference benchmark's
generator (``generate_build_probe_tables`` in distributed-join's
``src/generate_table.cuh``; copied from the program's
``utils/generators.py`` so that no change to the program changes the
data).

Build keys are drawn from ``[0, rand_max)``: each key once, in an order
drawn from the seed, where the config asks for ``unique_build_keys`` (the
reference benchmark's default), and uniformly with replacement otherwise.
Each probe key is, with probability ``selectivity``, the key of a
uniformly picked build row (a guaranteed hit) and otherwise uniform in
``[rand_max, 2 rand_max)`` (a guaranteed miss). Every payload is its row
id.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tables(cfg: dict, key) -> dict:
    nb, npr = cfg["build_rows"], cfg["probe_rows"]
    rand_max = cfg["rand_max"]
    dtype = jnp.dtype(cfg["key_dtype"])
    pay = jnp.dtype(cfg["payload_dtype"])
    kb, kp = jax.random.split(key)
    if cfg["unique_build_keys"]:
        build_keys = jax.random.permutation(
            kb, jnp.arange(rand_max, dtype=jnp.int64))[:nb].astype(dtype)
    else:
        build_keys = jax.random.randint(kb, (nb,), 0, rand_max,
                                        dtype=jnp.int64).astype(dtype)
    k_sel, k_pick, k_miss = jax.random.split(kp, 3)
    pick = jax.random.randint(k_pick, (npr,), 0, nb)
    miss = jax.random.randint(k_miss, (npr,), rand_max, 2 * rand_max,
                              dtype=jnp.int64).astype(dtype)
    hit = jax.random.uniform(k_sel, (npr,)) < cfg["selectivity"]
    probe_keys = jnp.where(hit, build_keys[pick], miss)
    return {
        "build": {"columns": {"key": build_keys,
                              "build_payload": jnp.arange(nb, dtype=pay)},
                  "valid": jnp.ones((nb,), bool)},
        "probe": {"columns": {"key": probe_keys,
                              "probe_payload": jnp.arange(npr, dtype=pay)},
                  "valid": jnp.ones((npr,), bool)},
    }
