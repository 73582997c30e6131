"""The plain reference of an exact inner equi-join, and the digest that
compares a join's answer with it.

Generalised from the numpy oracle of the program's ``chip_smoke.py``: it
works from per-key multiplicities and never materialises the join, and
it uses nothing of the program.

A digest of a set of output rows ``(key, build payloads, probe
payloads)`` holds:

- ``matches``: the number of rows;
- ``rows``: the sum over rows of ``F(key) * G(build payloads) *
  H(probe payloads)`` modulo 2**64, where ``F``, ``G`` and ``H`` are odd
  64-bit hashes. It depends on which payloads share a row, so a payload
  moved to another row, a dropped row or an altered value changes it;
- one sum of a 64-bit hash per output column, which says which column
  went wrong.

Because ``G`` and ``H`` enter as a product, the reference sums ``G`` per
build key and needs no pairing of rows: the sum over the rows of key
``k`` is ``F(k) * sum(G over build rows of k) * sum(H over probe rows of
k)``, exact in arithmetic modulo 2**64.

``digest`` takes ``numpy`` or ``jax.numpy`` as ``xp``: the harness digests
the program's answer on the device with the same function, in the same
modular arithmetic, that the tests apply to a join materialised by
pandas. ``reference`` computes the digest from the inputs with numpy
on the host, after the window.
"""

from __future__ import annotations

import zlib

import numpy as np

GOLDEN = 0x9E3779B97F4A7C15
MASK = (1 << 64) - 1


def splitmix(x, xp):
    """The splitmix64 finaliser, elementwise on a uint64 array."""
    z = x + xp.uint64(GOLDEN)
    z = (z ^ (z >> xp.uint64(30))) * xp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> xp.uint64(27))) * xp.uint64(0x94D049BB133111EB)
    return z ^ (z >> xp.uint64(31))


def _salt(name: str) -> int:
    return (zlib.crc32(name.encode()) * GOLDEN) & MASK


def column_hash(name: str, values, xp):
    """Hash of each value of a column; the column's name salts it."""
    u = values.astype(xp.int64).astype(xp.uint64)
    return splitmix(u ^ xp.uint64(_salt(name)), xp)


def row_hash(names, hashes: dict, n: int, xp):
    """Odd hash of each row from the column hashes of the named columns
    (1 where none)."""
    acc = xp.zeros((n,), xp.uint64)
    for name in sorted(names):
        acc = splitmix(acc ^ hashes[name], xp)
    return acc | xp.uint64(1)


def _total(x, xp):
    return xp.sum(x, dtype=xp.uint64)


def digest(columns, valid, key: str, build_names, probe_names, xp) -> dict:
    """Digest of the valid rows of an answer (see the module docstring).
    Returns arrays of ``xp``; ``to_ints`` makes Python ints of them."""
    n = valid.shape[0]
    zero = xp.uint64(0)
    hs = {nm: column_hash(nm, columns[nm], xp)
          for nm in (key, *build_names, *probe_names)}
    f = hs[key] | xp.uint64(1)
    g = row_hash(build_names, hs, n, xp)
    h = row_hash(probe_names, hs, n, xp)
    out = {"matches": xp.sum(valid.astype(xp.int64)),
           "rows": _total(xp.where(valid, f * g * h, zero), xp)}
    for nm, hv in hs.items():
        out[nm] = _total(xp.where(valid, hv, zero), xp)
    return out


def to_ints(d: dict) -> dict:
    return {k: int(v) for k, v in d.items()}


def reference(build: dict, probe: dict, key: str, build_names,
              probe_names) -> dict:
    """Digest of the exact inner join of the valid rows ``build`` and
    ``probe`` (dicts of host arrays) on ``key``.

    Both sides are sorted on the key; every sum is over a whole side, so
    its order does not matter."""
    mb, mp = build[key], probe[key]
    out = {"matches": 0, "rows": 0, key: 0}
    out.update({nm: 0 for nm in (*build_names, *probe_names)})
    if len(mb) == 0 or len(mp) == 0:
        return out
    # Build side: per distinct key, its row count and the sums of the
    # row hash and of each payload's column hash.
    order = np.argsort(mb)
    sb = mb[order]
    starts = np.flatnonzero(np.r_[True, sb[1:] != sb[:-1]])
    uniq = sb[starts]
    cnt_b = np.diff(np.r_[starts, len(sb)]).astype(np.uint64)

    def per_key(h):
        return np.add.reduceat(h[order], starts)

    hb = {nm: column_hash(nm, build[nm], np) for nm in build_names}
    sum_g = per_key(row_hash(build_names, hb, len(sb), np))
    # Probe side, in the order of its compared key.
    porder = np.argsort(mp)
    hp = {nm: column_hash(nm, probe[nm][porder], np)
          for nm in (key, *probe_names)}
    pos = np.minimum(np.searchsorted(uniq, mp[porder]), len(uniq) - 1)
    hit = uniq[pos] == mp[porder]
    per_probe = np.where(hit, cnt_b[pos], np.uint64(0))
    per_key_probes = np.bincount(pos[hit], minlength=len(uniq)).astype(
        np.uint64)
    rows = np.where(hit, (hp[key] | np.uint64(1)) * sum_g[pos]
                    * row_hash(probe_names, hp, len(pos), np), np.uint64(0))
    out["matches"] = int(per_probe.sum(dtype=np.uint64))
    out["rows"] = int(_total(rows, np))
    for nm, h in hp.items():
        out[nm] = int(_total(per_probe * h, np))
    for nm, h in hb.items():
        out[nm] = int(_total(per_key_probes * per_key(h), np))
    return out


def mismatches(got: dict, want: dict) -> list[str]:
    """Fields of two digests that differ (a field missing from either
    counts)."""
    return sorted(k for k in set(got) | set(want)
                  if got.get(k) != want.get(k))
