"""Materialized per-slab aggregate views — O(slabs touched) reads.

Each device-resident replica can carry a *view*: per-block partial
sums of its resident value tile (one float32 partial per
``DEVICE_BLOCK_N`` row block per value row, in the replica's own sort
order — ``repro.kernels.block_agg``). A view-eligible range aggregate
is then answered as

    interior blocks   → stored partials (one lookup each)
    boundary blocks   → one masked rescan per window edge
    accumulation      → sequential float32 fold in ascending block
                        order (``np.cumsum``)

instead of the fused O(N) device stream — the materialized-view / CQRS
pattern applied to heterogeneous layouts: every replica's view is
sorted its own way, so the Cost Evaluator ranks view hits exactly as
it ranks layouts (a capped row estimate, ``VIEW_ROWS_CAP``).

**Eligibility** (:func:`query_view_eligible`): sum/count aggregates
whose filters are fully consumed by the slab walk on this replica's
layout — an equality prefix plus at most one range, nothing filtered
after the prefix opens. For those queries residual matching equals
slab membership equals a row-index window per sorted run, so the
answer is a pure function of the windows and the stored partials.
"select" and residual-filtered queries keep the fused path.

**Maintenance** mirrors the table's storage moves, and rides the same
engine events that invalidate the per-replica result cache (flush,
compaction, node failure/recovery, migration, read repair — views are
maintained where cache entries are dropped, one invalidation path):

* flush — ``SortedTable.merge_run`` extends the partials O(run)
  (:func:`extend_views_state`: only blocks at/after the append point
  are refolded) and appends the run's packed keys to the per-run
  window index;
* compaction / rebuild — ``compact_runs`` and recovery re-place the
  arrays, so the view is rebuilt whole (:func:`build_views_state`);
  views are *derived* state: any corruption heals by rebuilding from
  the resident arrays (``scrub_column_family`` verifies this —
  :func:`verify_views`);
* migration — vnode tables are rebuilt by log replay, so a fresh
  view rides along; untouched vnodes keep their tables and therefore
  their views byte-for-byte.

The view state lives inside the table's ``_device`` dict under
``"views"``::

    {"block_sums": np.float32[V_pad, n_blocks],   # stored partials
     "block_n": int,                              # DEVICE_BLOCK_N
     "n_rows": int,                               # rows covered
     "run_packed": [np.int64[...], ...]}          # per-run sorted keys

``run_packed`` mirrors the device run stack (``run_starts``): slab
windows per run come from two host ``searchsorted`` calls on each
run's sorted packed keys — O(R log n) per query, R = resident runs.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "VIEW_AGGS",
    "VIEW_ROWS_CAP",
    "query_view_eligible",
    "view_eligible_matrix",
    "build_views_state",
    "extend_views_state",
    "verify_views",
    "serve_view_many",
]

VIEW_AGGS = ("sum", "count")

# Planning-time row estimate for a view hit: at most two boundary
# blocks are rescanned per (query, run) — interior work is O(1) per
# block. The Cost Evaluator feeds min(estimated_rows, cap) through the
# same Eq 1-2 cost polynomial, so a view-serving replica outranks a
# full scan exactly when the scan would stream more than the cap.
# Shared verbatim by the scalar and batched cost paths (parity).
VIEW_ROWS_CAP = 2 * 8192  # 2 * kernels.DEVICE_BLOCK_N (import-cycle-free)


def query_view_eligible(query, layout) -> bool:
    """True when ``query`` is answerable from ``layout``'s view alone:
    a sum/count whose filters form an equality prefix plus at most one
    range in this layout — the slab walk consumes every filter, no
    residual predicate remains, and matched rows == slab rows == the
    row-index window. Filters *after* the prefix opens keep the query
    on the fused path (value-column residency is the caller's check)."""
    if query.agg not in VIEW_AGGS:
        return False
    open_range = False
    for col in layout:
        f = query.filters.get(col)
        if open_range:
            if f is not None:
                return False
        elif f is None or not f.is_equality:
            open_range = True
    return True


def view_eligible_matrix(layouts, queries) -> np.ndarray:
    """bool[R, Q] view-eligibility per (replica layout, query) — the
    batched planning twin of :func:`query_view_eligible` (same walk per
    cell, so scalar and batched routing agree bit-for-bit). Callers
    short-circuit select-only batches *before* calling this (the
    aggregate planning arrays must not be walked for them)."""
    out = np.zeros((len(layouts), len(queries)), dtype=bool)
    for k, layout in enumerate(layouts):
        for j, q in enumerate(queries):
            out[k, j] = query_view_eligible(q, layout)
    return out


def _table_block_sums(state, *, use_pallas: bool = True) -> np.ndarray:
    from repro.kernels import DEVICE_BLOCK_N, block_sums, block_sums_ref

    fn = block_sums if use_pallas else block_sums_ref
    return np.asarray(fn(state["values_tile"], block_n=DEVICE_BLOCK_N))


def build_views_state(state, packed, *, use_pallas: bool = True) -> dict:
    """Fresh view over a device state holding one sorted run (CREATE,
    recovery, post-compaction): fold the whole value tile into
    per-block partials and index the single run's packed keys."""
    from repro.kernels import DEVICE_BLOCK_N

    n = int(state["n_rows"])
    return {
        "block_sums": _table_block_sums(state, use_pallas=use_pallas),
        "block_n": DEVICE_BLOCK_N,
        "n_rows": n,
        "run_packed": [np.asarray(packed, np.int64)[:n].copy()],
    }


def extend_views_state(
    views, state, run_packed, n_old: int, *, use_pallas: bool = True
) -> dict:
    """O(run) view extension for a flush append: the run's rows landed
    at ``[n_old, n_rows)`` in the resident arrays, so only blocks from
    ``n_old // block_n`` on changed — refold those, keep the earlier
    partials, and append the run's sorted packed keys as a new window
    index. Returns a fresh dict (the pre-merge table keeps its view)."""
    from repro.kernels import DEVICE_BLOCK_N, block_sums, block_sums_ref

    bn = int(views["block_n"])
    n_new = int(state["n_rows"])
    if n_old <= 0:
        # appending to an empty base collapses to a fresh single-run
        # build (device_state_append keeps it single-run too)
        return build_views_state(state, run_packed, use_pallas=use_pallas)
    b0 = n_old // bn
    fn = block_sums if use_pallas else block_sums_ref
    tail = np.asarray(fn(state["values_tile"][:, b0 * bn :], block_n=bn))
    return {
        "block_sums": np.concatenate(
            [views["block_sums"][:, :b0], tail], axis=1
        ),
        "block_n": DEVICE_BLOCK_N,
        "n_rows": n_new,
        "run_packed": list(views["run_packed"])
        + [np.asarray(run_packed, np.int64).copy()],
    }


def verify_views(table, *, use_pallas: bool = True) -> bool:
    """True when the table's stored view partials still match a fresh
    fold of the resident arrays (views are derived state — the arrays
    are ground truth, so a corrupted partial is healed by rebuild, not
    repair). Missing or shape-drifted view state also fails."""
    state = getattr(table, "_device", None)
    if state is None or "views" not in state:
        return False
    vs = state["views"]
    if int(vs["n_rows"]) != int(state["n_rows"]):
        return False
    if sum(p.shape[0] for p in vs["run_packed"]) != int(state["n_rows"]):
        return False
    fresh = _table_block_sums(state, use_pallas=use_pallas)
    stored = np.asarray(vs["block_sums"])
    return stored.shape == fresh.shape and bool(
        np.array_equal(stored, fresh)
    )


def _run_windows(vs, bounds) -> tuple[np.ndarray, np.ndarray]:
    """Global row-index windows int64[Q, R] (lo inclusive, hi
    exclusive) of each query's slab in each resident run
    (``slab_windows`` over the run's sorted packed keys; ``bounds``
    comes from ``slab_bounds_many``)."""
    from repro.core.table import slab_windows

    n_q = bounds.shape[0]
    runs = vs["run_packed"]
    wlo = np.empty((n_q, len(runs)), np.int64)
    whi = np.empty((n_q, len(runs)), np.int64)
    start = 0
    for r, p in enumerate(runs):
        lo, hi = slab_windows(p, bounds)
        wlo[:, r] = start + lo
        whi[:, r] = start + hi
        start += int(p.shape[0])
    return wlo, whi


def serve_view_many(table, queries, *, trace=None, view_stats=None) -> list:
    """Answer a batch of view-eligible queries from the table's view:
    per query, locate its per-run row windows (host searchsorted),
    classify touched blocks as interior (all real rows covered → use
    the stored partial) or boundary (one masked rescan), and fold the
    partials sequentially in float32, ascending block order — bits
    equal to the fused full-scan launch (see ``kernels.block_agg``).

    ``trace`` records one ``view.serve`` span, with a ``view.boundary``
    child around the boundary rescan when there is one; ``view_stats``
    (a dict, or None) accumulates ``hits`` (queries answered) and
    ``boundary_rows`` (rows streamed through boundary rescans — the
    honest residual scan cost a view hit still pays)."""
    from repro.core.table import ScanResult, slab_bounds_many
    from repro.kernels import boundary_block_sums

    state = table._device
    vs = state["views"]
    bn = int(vs["block_n"])
    n_rows = int(state["n_rows"])
    queries = list(queries)
    sp = (
        trace.child("view.serve", queries=len(queries))
        if trace is not None
        else None
    )
    bounds = slab_bounds_many(queries, table.layout, table.schema)
    wlo, whi = _run_windows(vs, bounds)
    lens = np.maximum(whi - wlo, 0)
    matched = lens.sum(axis=1)

    value_rows = state["value_rows"]
    block_sums = vs["block_sums"]
    # plans[i]: ordered per-block partial sources for sum query i —
    # ("s", block) stored partial, ("b", pair_idx) boundary rescan
    plans: dict[int, list] = {}
    pair_sel: list[int] = []
    pair_block: list[int] = []
    pair_q: list[int] = []
    boundary_rows = 0
    for i, q in enumerate(queries):
        if q.agg != "sum":
            continue
        windows = [
            (int(wlo[i, r]), int(whi[i, r]))
            for r in range(lens.shape[1])
            if lens[i, r] > 0
        ]
        cov: dict[int, int] = {}
        for a, b in windows:  # disjoint (runs partition the row space)
            for blk in range(a // bn, (b - 1) // bn + 1):
                lo = max(a, blk * bn)
                hi = min(b, (blk + 1) * bn)
                cov[blk] = cov.get(blk, 0) + (hi - lo)
        plan: list = []
        vrow = value_rows[q.value_col]
        for blk in sorted(cov):
            real = min((blk + 1) * bn, n_rows) - blk * bn
            if cov[blk] == real:
                plan.append(("s", blk))
            else:
                plan.append(("b", len(pair_sel)))
                pair_sel.append(vrow)
                pair_block.append(blk)
                pair_q.append(i)
                boundary_rows += real
        plans[i] = plan

    bvals = np.empty(0, np.float32)
    if pair_sel:
        n_w = wlo.shape[1]
        p_lo = np.zeros((len(pair_sel), n_w), np.int64)
        p_hi = np.zeros((len(pair_sel), n_w), np.int64)
        for p, i in enumerate(pair_q):
            p_lo[p] = wlo[i]
            p_hi[p] = np.maximum(whi[i], wlo[i])  # empty slots: lo == hi
        bs = (
            sp.child("view.boundary", pairs=len(pair_sel))
            if sp is not None
            else None
        )
        bvals = np.asarray(
            boundary_block_sums(
                state["values_tile"], pair_sel, pair_block, p_lo, p_hi,
                block_n=bn,
            )
        )
        if bs is not None:
            bs.end()

    out: list[ScanResult] = []
    for i, q in enumerate(queries):
        m = int(matched[i])
        if q.agg == "count":
            out.append(ScanResult(float(m), m, m))
            continue
        plan = plans[i]
        if not plan:
            out.append(ScanResult(0.0, m, m))
            continue
        parts = np.array(
            [
                block_sums[value_rows[q.value_col], ref] if kind == "s"
                else bvals[ref]
                for kind, ref in plan
            ],
            np.float32,
        )
        # np.cumsum is a strictly sequential fold (unlike np.sum's
        # pairwise tree) — the fused kernel's block-order accumulation
        acc = np.cumsum(parts, dtype=np.float32)[-1]
        out.append(ScanResult(float(acc), m, m))

    if view_stats is not None:
        view_stats["hits"] = view_stats.get("hits", 0) + len(queries)
        view_stats["boundary_rows"] = (
            view_stats.get("boundary_rows", 0) + boundary_rows
        )
    if sp is not None:
        sp.end(boundary_rows=boundary_rows, boundary_blocks=len(pair_sel))
    return out
