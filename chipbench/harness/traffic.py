"""One general generator for every traffic mix file under ``traffic/``.

A mix file lists query ``templates``, used in turn (the paper's
alternating Q1/Q2), and either an open loop (``rate_per_s``) or a closed
loop (``clients``). Each template names its aggregate and one filter per
key column:

* ``{"eq": true}``: equality on a value uniform over the key's domain;
* ``{"all": true}``: the range covering the whole domain;
* ``{"span": [a, b]}``: a range of ``a`` to ``b`` values at a uniform
  start. The spans of a template cycle through every size from ``a`` to
  ``b`` once per ``b - a + 1`` uses, in an order drawn from the seed, so
  every seed gets the same sizes.

Open-loop arrivals are a Poisson process conditioned on its count: the
``rate * seconds`` gaps are the same stratified exponential quantiles
for every seed, in an order drawn from the seed. So runs with different
seeds do the same amount of work in the same window, and differ only in
order and in the key values drawn.

A mix may also carry a ``writes`` block: refreshes of ``generator``
due at the times ``refresh_at_s`` on the window's clock, each all its
rows at once (:func:`write_stream`), drawn on the seed stream
``stream``; and every ``fresh_read_every``-th read aimed at a written
row (:func:`aim_at_rows`), so that read-your-writes shows in the
answers. Such a mix may not read with ``select``: a select's rows are
read back through the replica tables captured before the window, which
flushes replace.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Mix:
    """``n`` queries as arrays: query ``i`` uses template
    ``templates[tmpl[i]]`` and filters key column ``c`` to
    ``[lo[c][i], hi[c][i])``."""

    templates: list[dict]
    tmpl: np.ndarray
    lo: dict[str, np.ndarray]
    hi: dict[str, np.ndarray]

    def __len__(self) -> int:
        return int(self.tmpl.shape[0])

    def agg(self, i: int) -> str:
        return self.templates[self.tmpl[i]]["agg"]


def _spans(a: int, b: int, n: int, rng: np.random.Generator) -> np.ndarray:
    cycles = -(-n // (b - a + 1))
    return np.concatenate(
        [rng.permutation(np.arange(a, b + 1)) for _ in range(cycles)]
    )[:n]


def queries(templates: list[dict], dom: dict[str, int], n: int, seed) -> Mix:
    """The ``n`` queries drawn from ``seed``, templates used in turn."""
    rng = np.random.default_rng(seed)
    tmpl = np.arange(n) % len(templates)
    cols = sorted({c for t in templates for c in t["filters"]})
    lo = {c: np.zeros(n, np.int64) for c in cols}
    hi = {c: np.zeros(n, np.int64) for c in cols}
    for t_idx, t in enumerate(templates):
        at = np.nonzero(tmpl == t_idx)[0]
        for col, f in t["filters"].items():
            d = dom[col]
            if f.get("eq"):
                v = rng.integers(0, d, at.size)
                lo[col][at], hi[col][at] = v, v + 1
            elif f.get("all"):
                lo[col][at], hi[col][at] = 0, d
            elif "span" in f:
                span = _spans(int(f["span"][0]), int(f["span"][1]), at.size, rng)
                start = rng.integers(0, np.maximum(1, d - span))
                lo[col][at], hi[col][at] = start, start + span
            else:
                raise ValueError(f"unknown filter {f!r} on {col!r} in template {t['name']!r}")
    return Mix(list(templates), tmpl, lo, hi)


def arrivals(rate_per_s: float, seconds: float, seed) -> np.ndarray:
    """Due times in ``[0, seconds)`` of ``round(rate * seconds)`` reads."""
    n = int(round(rate_per_s * seconds))
    if n < 1:
        raise ValueError(f"rate {rate_per_s}/s gives no read in {seconds} s")
    u = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = np.random.default_rng(seed).permutation(-np.log1p(-u))
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps)[:n]


def check_mix(mix_cfg: dict) -> None:
    """Refuse a mix the harness cannot check: writes beside selects."""
    if "writes" in mix_cfg and any(t["agg"] == "select" for t in mix_cfg["templates"]):
        raise ValueError(
            f"traffic {mix_cfg['name']!r} mixes writes with select templates: select rows "
            "are read back through tables captured before the window, which flushes replace"
        )


def write_stream(writes: dict, rows_per_refresh: int) -> tuple[np.ndarray, np.ndarray]:
    """``(due_s, ends)`` of a mix's writes: ``rows_per_refresh`` rows due
    at each of ``refresh_at_s``, in time order, and the end row
    (exclusive) of each refresh."""
    at = np.sort(np.asarray(writes["refresh_at_s"], float))
    if at.size < 1 or rows_per_refresh < 1:
        raise ValueError(f"writes {writes!r} insert no row")
    return np.repeat(at, rows_per_refresh), np.arange(1, at.size + 1) * rows_per_refresh


def aim_at_rows(mix: Mix, dom: dict[str, int], key_cols: dict, every: int, seed) -> np.ndarray:
    """Aim every ``every``-th query of ``mix`` (in place) at a row of
    ``key_cols`` drawn from ``seed``: each equality takes the row's
    value, and each span keeps its size and moves to a start, drawn
    from ``seed``, at which it covers the row's value and stays inside
    the key's domain ``dom``. Returns the
    aimed queries' indices. The work a query does is unchanged: the
    same template, the same span sizes."""
    rng = np.random.default_rng(seed)
    at = np.arange(every - 1, len(mix), every)
    row = rng.integers(0, len(next(iter(key_cols.values()))), at.size)
    for t_idx, t in enumerate(mix.templates):
        sel = at[mix.tmpl[at] == t_idx]
        r = row[mix.tmpl[at] == t_idx]
        for col, f in t["filters"].items():
            v = np.asarray(key_cols[col], np.int64)[r]
            if f.get("eq"):
                mix.lo[col][sel], mix.hi[col][sel] = v, v + 1
            elif "span" in f:
                span = mix.hi[col][sel] - mix.lo[col][sel]
                start = np.clip(v - rng.integers(0, span), 0, dom[col] - span)
                mix.lo[col][sel], mix.hi[col][sel] = start, start + span
    return at


def to_queries(mix: Mix) -> list:
    """The program's ``Query`` objects for every query of ``mix``. Filter
    objects are immutable, so equal filters are built once and shared."""
    from repro.core import Eq, Query, Range

    eq_cache: dict[int, object] = {}
    range_cache: dict[tuple[int, int], object] = {}

    def eq(v):
        f = eq_cache.get(v)
        if f is None:
            f = eq_cache[v] = Eq(v)
        return f

    def rng(a, b):
        f = range_cache.get((a, b))
        if f is None:
            f = range_cache[(a, b)] = Range(a, b)
        return f

    out: list = [None] * len(mix)
    for t_idx, t in enumerate(mix.templates):
        at = np.nonzero(mix.tmpl == t_idx)[0]
        cols = []
        for c, f in t["filters"].items():
            lo = mix.lo[c][at].tolist()
            if f.get("eq"):
                cols.append((c, list(map(eq, lo))))
            else:
                cols.append((c, list(map(rng, lo, mix.hi[c][at].tolist()))))
        agg, vcol = t["agg"], t.get("value_col")
        for j, i in enumerate(at.tolist()):
            out[i] = Query(filters={c: fs[j] for c, fs in cols}, agg=agg, value_col=vcol)
    return out
