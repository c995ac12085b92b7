"""The plain reference: the same reads answered by filtering the
generated columns directly, in float64, with nothing of the program.

For each template, an index sorts the row ids by the template's
equality columns once; a query's candidate rows are its bucket, and
every filter is then applied to them. A template with no equality
filter scans every row. Sums are taken in float64 over the matched
rows in row order, counts exactly, and selected rows are returned as
their full contents (key columns, then value columns), sorted.

Where the mix writes, the reference holds the loaded rows followed by
the written rows that were acknowledged, in order, and each read sees
exactly the rows below its own ``visible`` count: the loaded rows plus
the written rows acknowledged before its batch went out
(read-your-writes). :func:`fingerprint` and :func:`device_fingerprint`
check that every replica holds exactly the reference's rows once the
window is over, in its host table and in the arrays reads are served
from.

``Reference.answers(..., control=True)`` is the control: the reference
computed one step below the precision the configuration states: sums folded in bfloat16 (stated:
float32) and selected rows with their values in float32 (stated: exact
float64). Its answers must fail the comparison.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np


@dataclasses.dataclass
class Answer:
    """What a read says: its aggregate, its matched count, and for a
    select the matched rows' contents as a sorted ``(m, k)`` array."""

    value: float
    matched: int
    rows: np.ndarray | None = None


class Reference:
    def __init__(self, key_cols: dict, value_cols: dict, dom: dict[str, int]):
        self.key_cols = {c: np.asarray(v, np.int64) for c, v in key_cols.items()}
        self.value_cols = {c: np.asarray(v, np.float64) for c, v in value_cols.items()}
        self.dom = dom
        self._index: dict[tuple[str, ...], tuple[np.ndarray, np.ndarray]] = {}

    def _composite(self, cols: tuple[str, ...], values: list) -> np.ndarray:
        key = np.zeros(np.shape(values[0]), np.int64)
        for c, v in zip(cols, values):
            key = key * self.dom[c] + np.asarray(v, np.int64)
        return key

    def _bucket_index(self, cols: tuple[str, ...]):
        if cols not in self._index:
            key = self._composite(cols, [self.key_cols[c] for c in cols])
            order = np.argsort(key)
            self._index[cols] = (key[order], order)
        return self._index[cols]

    def matches(self, mix, idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Every (position in ``idx``, row id) pair where the row matches
        query ``mix[idx[pos]]``, ordered by position, then row id."""
        pos_parts, row_parts = [], []
        n_all = next(iter(self.key_cols.values())).shape[0]
        for t_idx, t in enumerate(mix.templates):
            sel = np.nonzero(mix.tmpl[idx] == t_idx)[0]
            if sel.size == 0:
                continue
            q = idx[sel]
            eq_cols = tuple(c for c, f in t["filters"].items() if f.get("eq"))
            if eq_cols:
                keys, order = self._bucket_index(eq_cols)
                probe = self._composite(eq_cols, [mix.lo[c][q] for c in eq_cols])
                start = np.searchsorted(keys, probe, side="left")
                sizes = np.searchsorted(keys, probe, side="right") - start
            else:
                order = np.arange(n_all)
                start = np.zeros(q.size, np.int64)
                sizes = np.full(q.size, n_all)
            owner = np.repeat(np.arange(q.size), sizes)
            first = np.repeat(np.cumsum(sizes) - sizes, sizes)
            rows = order[np.repeat(start, sizes) + np.arange(owner.size) - first]
            keep = np.ones(rows.size, bool)
            for c in t["filters"]:
                v = self.key_cols[c][rows]
                keep &= (v >= mix.lo[c][q][owner]) & (v < mix.hi[c][q][owner])
            pos_parts.append(sel[owner[keep]])
            row_parts.append(rows[keep])
        pos = np.concatenate(pos_parts) if pos_parts else np.zeros(0, np.int64)
        rows = np.concatenate(row_parts) if row_parts else np.zeros(0, np.int64)
        o = np.lexsort((rows, pos))
        return pos[o], rows[o]

    def row_contents(self, rows: np.ndarray, value_cast=np.float64) -> np.ndarray:
        """``(m, k)`` float64 contents of ``rows``, sorted lexicographically."""
        cols = [self.key_cols[c][rows].astype(np.float64) for c in sorted(self.key_cols)]
        cols += [
            self.value_cols[c][rows].astype(value_cast).astype(np.float64)
            for c in sorted(self.value_cols)
        ]
        return sort_rows(np.stack(cols, axis=1))

    def answers(self, mix, idx: np.ndarray, *, control: bool = False,
                visible: np.ndarray | None = None) -> list[Answer]:
        """The reference's answer to each query ``mix[idx[j]]``, over
        the rows below ``visible[j]`` (default: every row); with
        ``control``, the control's (see the module doc)."""
        pos, rows = self.matches(mix, idx)
        if visible is not None:
            seen = rows < np.asarray(visible, np.int64)[pos]
            pos, rows = pos[seen], rows[seen]
        counts = np.bincount(pos, minlength=idx.size)
        bounds = np.concatenate([[0], np.cumsum(counts)])
        tmpl = mix.tmpl[idx]
        sums = np.zeros(idx.size)
        for t_idx, t in enumerate(mix.templates):
            if t["agg"] == "sum" and not control:
                at = tmpl[pos] == t_idx
                sums += np.bincount(
                    pos[at], weights=self.value_cols[t["value_col"]][rows[at]],
                    minlength=idx.size,
                )
        out = []
        for j, (t_idx, m) in enumerate(zip(tmpl.tolist(), counts.tolist())):
            t = mix.templates[t_idx]
            if t["agg"] == "sum":
                if control:
                    v = self.value_cols[t["value_col"]][rows[bounds[j]:bounds[j + 1]]]
                    out.append(Answer(_bf16_fold(v), m))
                else:
                    out.append(Answer(float(sums[j]), m))
            elif t["agg"] == "count":
                out.append(Answer(float(m), m))
            else:
                cast = np.float32 if control else np.float64
                r = rows[bounds[j]:bounds[j + 1]]
                out.append(Answer(float(m), m, self.row_contents(r, cast)))
        return out


_ROUND = [np.uint64(m) for m in (0x9E3779B97F4A7C15, 0xBF58476D1CE4E5B9, 0x94D049BB133111EB,
                                  0xD6E8FEB86659FD93, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
                                  0xFF51AFD7ED558CCD, 0xC4CEB9FE1A85EC53)]


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer, lane-wise (uint64 arithmetic wraps)."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _digest(cols: list) -> tuple[int, int]:
    """``(rows, digest)`` of the rows whose columns are the int64 arrays
    ``cols``, independent of row order: the sum modulo 2**64 of one
    64-bit hash per row. Each column folds into the row's hash by an
    xor, an odd multiplier and a shift, and splitmix64's finalizer
    mixes the result; two row sets that differ in a row differ in the
    digest but for a chance of about 2**-64."""
    n = cols[0].shape[0] if cols else 0
    h = np.zeros(n, np.uint64)
    with np.errstate(over="ignore"):
        for k, c in enumerate(cols):
            h ^= np.asarray(c, np.int64).view(np.uint64)
            h *= _ROUND[k % len(_ROUND)]
            h ^= h >> np.uint64(29)
        return n, int(np.sum(_mix64(h), dtype=np.uint64))


def fingerprint(key_cols: dict, value_cols: dict) -> tuple[int, int]:
    """``(rows, digest)`` of a host row set over every key column and
    every value column in hundredths (order keys, status and priorities
    are whole, prices have two decimals, so each is an exact integer
    there)."""
    cols = [np.asarray(key_cols[c], np.int64) for c in sorted(key_cols)]
    cols += [np.rint(np.asarray(value_cols[c], np.float64) * 100).astype(np.int64)
             for c in sorted(value_cols)]
    return _digest(cols)


def device_fingerprint(key_cols: dict, value_cols: dict, ones=None) -> tuple[int, int]:
    """``(rows, digest)`` of a row set as a device-resident replica
    holds it: every key column whole, every value column as the bits of
    its float32, and the count lane (``ones``: 1.0 on every row, which
    is what the reference holds)."""
    cols = [np.asarray(key_cols[c], np.int64) for c in sorted(key_cols)]
    n = cols[0].shape[0] if cols else 0
    f32 = [np.asarray(value_cols[c], np.float32) for c in sorted(value_cols)]
    f32.append(np.ones(n, np.float32) if ones is None else np.asarray(ones, np.float32))
    return _digest(cols + [v.view(np.int32).astype(np.int64) for v in f32])


def _bf16_fold(values: np.ndarray) -> float:
    """Sequential sum in bfloat16 arithmetic."""
    bf16 = ml_dtypes.bfloat16
    acc = bf16(0.0)
    for v in values:
        acc = bf16(acc + bf16(v))
    return float(acc)


def sort_rows(rows: np.ndarray) -> np.ndarray:
    if rows.shape[0] == 0:
        return rows
    return rows[np.lexsort(rows.T[::-1])]


@dataclasses.dataclass
class Comparison:
    """The numbers compared, each with its limit. A mix that writes adds
    two: ``writes_failed``, the written rows due in the window that were
    not acknowledged (never sent, or their write raised), and
    ``replica_mismatches``, the replicas whose rows after the window
    differ from the reference's in number or content, in the host table
    or in the device-resident arrays."""

    compared: int = 0
    failed: int = 0
    count_mismatches: int = 0
    row_mismatches: int = 0
    sum_rel_gap: float = 0.0
    writes_failed: int | None = None
    replica_mismatches: int | None = None

    def numbers(self, limits: dict) -> dict:
        out = {
            "failed": {"value": self.failed, "limit": 0},
            "count_mismatches": {"value": self.count_mismatches, "limit": 0},
            "row_mismatches": {"value": self.row_mismatches, "limit": 0},
        }
        if self.writes_failed is not None:
            out["writes_failed"] = {"value": self.writes_failed, "limit": 0}
            out["replica_mismatches"] = {"value": self.replica_mismatches, "limit": 0}
        out["sum_rel_gap"] = {"value": self.sum_rel_gap, "limit": limits["sum_rel_gap"]}
        return out

    def correct(self, limits: dict) -> bool:
        return all(n["value"] <= n["limit"] for n in self.numbers(limits).values())


def compare(got: list, want: list[Answer], aggs: list[str]) -> Comparison:
    """Compare answers. ``got[i]`` is an :class:`Answer`, or None for a
    read that did not complete: refused, shed, timed out or never
    answered. The configuration states no shedding, so each such read
    is failed."""
    c = Comparison()
    for g, w, agg in zip(got, want, aggs):
        if g is None:
            c.failed += 1
            continue
        c.compared += 1
        if g.matched != w.matched or (agg == "count" and g.value != w.value):
            c.count_mismatches += 1
        if agg == "select":
            if g.rows is None or g.rows.shape != w.rows.shape or not np.array_equal(g.rows, w.rows):
                c.row_mismatches += 1
        elif agg == "sum":
            gap = abs(g.value - w.value) / max(abs(w.value), 1.0)
            if not gap <= c.sum_rel_gap:  # a NaN answer reads as infinitely far
                c.sum_rel_gap = gap if gap == gap else float("inf")
    return c
