"""Public jit'd wrappers around the Pallas kernels.

``scan_agg`` executes a located slab scan (the engine's read path on
device); ``ecdf_hist`` refreshes Cost-Evaluator statistics. Both take the
same arguments as their ``ref.py`` oracles and dispatch to Pallas
(interpret-mode on CPU, compiled on TPU).

``table_execute_device_many`` is the batched read fast path: one *fused
locate+scan* launch (``slab_locate`` module) answers a whole sum/count/
select query group against a replica's device-resident columns — slab
location happens inside the scan predicate (no host searchsorted, no
host sync between locate and scan), counts accumulate in int32 lanes
(exact to 2**31 rows), and "select" queries get their matched row
indices from a second prefix-sum compaction launch sized by the first's
counts. ``table_slab_locate_many`` exposes the standalone vectorized
binary search behind ``SortedTable.slab_many``; ``device_state_append``
extends a resident table's arrays with a merged write run in place of a
full re-upload. Key columns up to 60 bits are packed into two int32
lanes; wider columns raise a precise error naming the column.

``table_scan_device_many`` (PR 2) remains as the slab-mask row-streaming
launch over host-located slabs — the benchmark baseline the fused path
is measured against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .ecdf_hist import ecdf_hist_pallas
from .merge_runs import merge_rank_batched, merge_run_positions
from .scan_agg import (
    WIDE_LANE_BITS,
    scan_agg_batched_pallas,
    scan_agg_batched_qgrid_pallas,
    scan_agg_pallas,
)
from .slab_locate import (
    residual_membership_batched,
    scan_agg_locate_batched,
    select_compact_batched,
    slab_locate_batched,
)

__all__ = [
    "scan_agg",
    "scan_agg_batched",
    "scan_agg_locate_batched",
    "slab_locate_batched",
    "select_compact_batched",
    "ecdf_hist",
    "scan_agg_ref",
    "scan_agg_batched_ref",
    "scan_agg_locate_batched_ref",
    "slab_locate_batched_ref",
    "select_compact_batched_ref",
    "merge_rank_batched",
    "merge_run_positions",
    "merge_run_positions_ref",
    "ecdf_hist_ref",
    "device_key_plan",
    "build_device_state",
    "device_state_append",
    "merge_device_runs",
    "table_scan_device",
    "table_scan_device_many",
    "table_execute_device_many",
    "table_slab_locate_many",
]

scan_agg_ref = ref.scan_agg_ref
scan_agg_batched_ref = ref.scan_agg_batched_ref
scan_agg_locate_batched_ref = ref.scan_agg_locate_batched_ref
slab_locate_batched_ref = ref.slab_locate_batched_ref
select_compact_batched_ref = ref.select_compact_batched_ref
merge_run_positions_ref = ref.merge_run_positions_ref
ecdf_hist_ref = ref.ecdf_hist_ref

# Keys and filter bounds live in int32 lanes on device; one lane holds a
# ≤30-bit column (its exclusive global bound 2**bits must still fit), a
# lane *pair* holds up to 60 bits split as (value >> 30, value & mask).
MAX_DEVICE_COL_BITS = 2 * WIDE_LANE_BITS
_LANE_MASK = (1 << WIDE_LANE_BITS) - 1


def scan_agg(keys, values, col_lo, col_hi, slab, *, block_n: int = 2048, use_pallas: bool = True):
    """(sum, count) over the slab with residual predicates. Arrays may be
    numpy or jax; returns a float32[2] jax array."""
    keys = jnp.asarray(keys, jnp.int32)
    values = jnp.asarray(values, jnp.float32)
    col_lo = jnp.asarray(col_lo, jnp.int32)
    col_hi = jnp.asarray(col_hi, jnp.int32)
    slab = jnp.asarray(slab, jnp.int32)
    if not use_pallas:
        return ref.scan_agg_ref(keys, values, col_lo, col_hi, slab)
    return scan_agg_pallas(keys, values, col_lo, col_hi, slab, block_n=block_n)


def ecdf_hist(col, *, n_bins: int, bin_width: int, block_n: int = 512, use_pallas: bool = True):
    """float32[n_bins] bin counts. The kernel path takes at most 4096
    bins and raises beyond that — callers with wider tables bin on the
    host themselves (``ColumnStats.merge_values``)."""
    col = jnp.asarray(col, jnp.int32)
    if not use_pallas:
        return ref.ecdf_hist_ref(col, n_bins=n_bins, bin_width=bin_width)
    return ecdf_hist_pallas(col, n_bins=n_bins, bin_width=bin_width, block_n=block_n)


def scan_agg_batched(
    keys,
    values,
    col_lo,
    col_hi,
    slabs,
    value_sel=None,
    *,
    col_parts: tuple[int, ...] | None = None,
    block_n: int = 2048,
    use_pallas: bool = True,
    grid: str = "rows_outer",
):
    """Per-query (sum, count) for a query batch sharing one replica's
    columns. Arrays may be numpy or jax; returns float32[Q, 2].

    ``grid="rows_outer"`` (default) is the row-streaming launch: key and
    value tiles are fetched from HBM once per batch, per-query
    accumulators are revisited at every row step. ``values`` may be a
    (V, N) tile with ``value_sel`` routing each query to its row, and
    ``col_parts`` marks wide (two-lane) key columns.

    ``grid="queries_outer"`` dispatches the legacy PR 1 grid (queries ×
    row blocks, row axis fastest; key traffic scales with Q). It only
    supports a single value row and narrow columns — kept as the
    benchmark baseline for the perf trajectory.
    """
    keys = jnp.asarray(keys, jnp.int32)
    values = jnp.asarray(values, jnp.float32)
    col_lo = jnp.asarray(col_lo, jnp.int32)
    col_hi = jnp.asarray(col_hi, jnp.int32)
    slabs = jnp.asarray(slabs, jnp.int32)
    if grid == "queries_outer":
        if values.ndim != 1:
            raise ValueError("queries_outer grid supports a single value row")
        if value_sel is not None or (col_parts and any(p != 1 for p in col_parts)):
            raise ValueError(
                "queries_outer grid supports neither value selectors nor wide columns"
            )
        if not use_pallas:
            return ref.scan_agg_batched_ref(keys, values, col_lo, col_hi, slabs)
        return scan_agg_batched_qgrid_pallas(
            keys, values, col_lo, col_hi, slabs, block_n=block_n
        )
    if grid != "rows_outer":
        raise ValueError(f"unknown grid {grid!r}")
    if value_sel is not None:
        value_sel = jnp.asarray(value_sel, jnp.int32)
    if not use_pallas:
        return ref.scan_agg_batched_ref(
            keys, values, col_lo, col_hi, slabs, value_sel=value_sel, col_parts=col_parts
        )
    return scan_agg_batched_pallas(
        keys, values, col_lo, col_hi, slabs, value_sel,
        col_parts=col_parts, block_n=block_n,
    )


# -- device-resident table scans ---------------------------------------------


def device_key_plan(table) -> tuple[int, ...]:
    """Lane count (1 or 2) per layout column for the device scan path.

    Raises a precise ``ValueError`` naming the offending column when a
    key column exceeds the two-lane budget (> 60 bits) — wider schemas
    are served by the numpy engine.
    """
    parts = []
    for c in table.layout:
        bits = table.schema.bits[c]
        if bits <= WIDE_LANE_BITS:
            parts.append(1)
        elif bits <= MAX_DEVICE_COL_BITS:
            parts.append(2)
        else:
            raise ValueError(
                f"device scan path: key column {c!r} needs {bits} bits, more "
                f"than the {MAX_DEVICE_COL_BITS}-bit two-lane budget "
                f"(2 × {WIDE_LANE_BITS}-bit int32 lanes); use "
                "SortedTable.execute/execute_many (numpy) for this schema"
            )
    return tuple(parts)


def _expand_key_cols(
    key_cols, layout, col_parts: tuple[int, ...], n: int
) -> np.ndarray:
    """int32[K_ex, n] key lanes in layout order: narrow columns as one
    lane, wide columns as (value >> 30, value & mask) pairs whose
    lexicographic order equals the numeric order."""
    rows: list[np.ndarray] = []
    for c, parts in zip(layout, col_parts):
        v = np.asarray(key_cols[c], np.int64)
        if parts == 1:
            rows.append(v.astype(np.int32))
        else:
            rows.append((v >> WIDE_LANE_BITS).astype(np.int32))
            rows.append((v & _LANE_MASK).astype(np.int32))
    return np.stack(rows) if rows else np.zeros((0, n), np.int32)


def _expand_key_planes(table, col_parts: tuple[int, ...]) -> np.ndarray:
    return _expand_key_cols(table.key_cols, table.layout, col_parts, len(table))


def _expand_bounds(
    bounds: np.ndarray, col_parts: tuple[int, ...]
) -> tuple[np.ndarray, np.ndarray]:
    """Split int64[Q, K, 2] per-column bounds into int32[Q, K_ex] lane
    bounds. An exclusive upper bound splits the same way — comparing the
    lane pair lexicographically against (hi >> 30, hi & mask) is exactly
    ``value < hi``."""
    los: list[np.ndarray] = []
    his: list[np.ndarray] = []
    for j, parts in enumerate(col_parts):
        lo, hi = bounds[:, j, 0], bounds[:, j, 1]
        if parts == 1:
            los.append(lo.astype(np.int32))
            his.append(hi.astype(np.int32))
        else:
            los.append((lo >> WIDE_LANE_BITS).astype(np.int32))
            los.append((lo & _LANE_MASK).astype(np.int32))
            his.append((hi >> WIDE_LANE_BITS).astype(np.int32))
            his.append((hi & _LANE_MASK).astype(np.int32))
    return np.stack(los, axis=1), np.stack(his, axis=1)


# Row-axis padding granularity of the resident arrays AND the device
# read path's kernel block size: jit-time pads are no-ops for every
# block_n that divides it, so the per-batch work is O(Q), not O(N).
# 8192 rows × 8 int32 lanes ≈ 256 KB per key tile — comfortably VMEM-
# sized with double buffering, and measured ~2-3× faster than 2048 in
# interpret mode (fewer grid steps amortize the per-step overhead).
DEVICE_BLOCK_N = 8192


# The fused kernel accumulates matched/slab counts in int32 lanes and
# addresses rows with int32 indices, so the device path is exact up to
# the int32 range (the old float32 count lane capped it at 2**24).
MAX_DEVICE_ROWS = (1 << 31) - DEVICE_BLOCK_N

# Count exactness bound of the LEGACY float32 count lane still used by
# table_scan_device_many (rowgrid/qgrid scan_agg kernels); the fused
# path is unaffected. Guarded at that entry point, not at placement.
FLOAT32_EXACT_ROWS = 1 << 24


# The select compaction kernel's (Q_pad, width) int32 output block
# stays VMEM-resident across every grid step, so it must be bounded in
# BOTH dimensions: per query (width, pow-2 of the batch's largest match
# count) and as a whole. Queries matching more than MAX_WIDTH rows take
# the membership-mask fallback (device mask + per-query sized
# flatnonzero, so only the indices reach host — still zero host
# searchsorted and zero residual scans); the rest launch in chunks of
# at most MAX_ELEMS // width queries (~4 MB of output block per
# launch, comfortably VMEM-sized next to the key tiles).
SELECT_COMPACT_MAX_WIDTH = 1 << 16
SELECT_COMPACT_MAX_ELEMS = 1 << 20


def _check_device_rows(n: int) -> None:
    if n >= MAX_DEVICE_ROWS:
        raise ValueError(
            f"device scan path: {n} rows exceeds the int32 row-index/"
            f"count budget ({MAX_DEVICE_ROWS}); use the numpy engine "
            "for tables this large"
        )


def build_device_state(table, value_cols=None) -> dict:
    """Materialize a table's device-resident arrays: expanded int32 key
    lanes and a float32 value tile (one row per value column + a ones
    row for counts), both pre-padded to the kernel's sublane/block
    granularity so repeated batches ship only O(Q) bounds/selector
    data — no per-call stack or pad of the N-sized columns.
    ``SortedTable.place_on_device`` stores the result; host-only tables
    build it ephemerally per call, passing ``value_cols`` to materialize
    only the batch's columns.

    A fresh build holds one sorted run (``n_runs == 1``, device row
    order == host row order, ``row_map is None``);
    :func:`device_state_append` extends it with merged write runs."""
    col_parts = device_key_plan(table)
    n = len(table)
    _check_device_rows(n)
    n_pad = -(-max(n, 1) // DEVICE_BLOCK_N) * DEVICE_BLOCK_N
    keys = _expand_key_planes(table, col_parts)
    k_ex = keys.shape[0]
    k_pad = max(8, -(-k_ex // 8) * 8)
    keys_p = np.zeros((k_pad, n_pad), np.int32)
    keys_p[:k_ex, :n] = keys
    if value_cols is None:
        vnames = list(table.value_cols)
    else:
        wanted = set(value_cols)
        vnames = [c for c in table.value_cols if c in wanted]
    n_value_rows = len(vnames) + 1  # + ones row
    v_pad = max(8, -(-n_value_rows // 8) * 8)
    tile = np.zeros((v_pad, n_pad), np.float32)
    for i, c in enumerate(vnames):
        tile[i, :n] = np.asarray(table.value_cols[c], np.float32)
    tile[len(vnames), :n] = 1.0  # padded rows stay 0 and are window-masked
    return {
        "col_parts": col_parts,
        "keys": jnp.asarray(keys_p),
        "values_tile": jnp.asarray(tile),
        "value_rows": {c: i for i, c in enumerate(vnames)},
        "ones_row": len(vnames),
        "n_value_rows": n_value_rows,
        "n_rows": n,
        "n_runs": 1,
        # start offset of each resident run (run 0 = the sorted base);
        # device_state_append extends it, merge_device_runs resets it
        "run_starts": (0,),
        # device row -> host row translation for "select"; None == identity
        "row_map": None,
    }


def device_state_append(state, table, run_key_cols, run_value_cols, positions) -> dict:
    """Incrementally extend a device-resident column cache with a merged
    write run (LSM append): the run's rows land right after the existing
    rows in the resident arrays — two O(run) device updates, no
    re-upload of the N-sized columns. Device row order then differs from
    the host (fully merged) order; only "select" observes row order, and
    ``row_map`` translates emitted device row indices back to host row
    indices. Maintaining ``row_map`` is the host cost floor: an O(N)
    arange + searchsorted per append (plus an O(N) gather once runs
    chain), and ``n_runs`` grows until ``place_on_device(rebuild=True)``
    collapses the runs — see the ROADMAP "compaction policy" open item
    for the automatic threshold that would bound both. Aggregate and slab-row counts are order-
    independent (the fused kernel decides slab membership by key), so
    they stay exact across appends.

    ``table`` is the *merged* table (for layout/schema), ``run_key_cols``
    / ``run_value_cols`` the run already sorted in table layout order,
    and ``positions`` the ``np.searchsorted`` merge positions of the run
    into the previous packed column. Returns a new state dict; the input
    state (still referenced by the pre-merge table) is untouched."""
    col_parts = state["col_parts"]
    positions = np.asarray(positions, np.int64)
    m = int(positions.shape[0])
    if m == 0:
        # an empty run must not cost a run: growing n_runs/row_map here
        # would permanently kick the table off the single-run fast paths
        # (device slab_many, the no-gather select) for no rows at all
        return dict(state)
    n_old = state["n_rows"]
    n_new = n_old + m
    _check_device_rows(n_new)
    keys = state["keys"]
    tile = state["values_tile"]
    cap = keys.shape[1]
    if n_new > cap:
        new_cap = -(-n_new // DEVICE_BLOCK_N) * DEVICE_BLOCK_N
        keys = jnp.pad(keys, ((0, 0), (0, new_cap - cap)))
        tile = jnp.pad(tile, ((0, 0), (0, new_cap - cap)))
    run_lanes = _expand_key_cols(run_key_cols, table.layout, col_parts, m)
    k_block = np.zeros((keys.shape[0], m), np.int32)
    k_block[: run_lanes.shape[0]] = run_lanes
    v_block = np.zeros((tile.shape[0], m), np.float32)
    for c, i in state["value_rows"].items():
        v_block[i] = np.asarray(run_value_cols[c], np.float32)
    v_block[state["ones_row"]] = 1.0
    keys = jax.lax.dynamic_update_slice(keys, jnp.asarray(k_block), (0, n_old))
    tile = jax.lax.dynamic_update_slice(tile, jnp.asarray(v_block), (0, n_old))
    new = dict(state)
    if n_old == 0:
        # appending to an empty base (a freshly-split partition that
        # owns no CREATE-time rows): the sorted run IS the base run —
        # device row order equals host order, so the table keeps the
        # single-run fast paths instead of paying a phantom run
        new.update(
            keys=keys, values_tile=tile, n_rows=n_new,
            n_runs=1, run_starts=(0,), row_map=None,
        )
        return new
    # host index of old row i after the merge: i + |{j : positions[j] <= i}|;
    # run row j (sorted order) lands at positions[j] + j (np.insert layout)
    old_to_merged = np.arange(n_old, dtype=np.int64) + np.searchsorted(
        positions, np.arange(n_old, dtype=np.int64), side="right"
    )
    rm = state["row_map"]
    base = old_to_merged if rm is None else old_to_merged[rm]
    row_map = np.concatenate([base, positions + np.arange(m, dtype=np.int64)])
    new.update(
        keys=keys,
        values_tile=tile,
        n_rows=n_new,
        n_runs=state.get("n_runs", 1) + 1,
        run_starts=tuple(state.get("run_starts", (0,))) + (n_old,),
        row_map=row_map,
    )
    return new


def merge_device_runs(
    state, *, block_n: int = DEVICE_BLOCK_N, use_pallas: bool = True
) -> dict:
    """Collapse a state's appended runs into one sorted run on device
    (automatic compaction's storage move): the k-way merge-path kernel
    (``merge_run_positions``) computes every row's merged position, and
    one scatter per resident array reorders keys and value tile — the
    N-sized columns never round-trip to host. The merge tie rule equals
    the host ``merge_run`` order, so afterwards device row order ==
    host row order: ``row_map`` collapses to identity (``None``),
    ``n_runs`` to 1, and the single-run fast paths (device ``slab_many``,
    the no-gather select) apply again. Returns a new state dict; the
    input state is untouched."""
    if state.get("n_runs", 1) <= 1:
        return dict(state)
    n = state["n_rows"]
    pos = jnp.asarray(
        merge_run_positions(
            state["keys"], state["run_starts"], n,
            n_lanes=sum(state["col_parts"]), block_n=block_n,
            use_pallas=use_pallas,
        )
    )
    keys = state["keys"]
    tile = state["values_tile"]
    merged_keys = jnp.zeros_like(keys).at[:, pos].set(keys[:, :n])
    merged_tile = jnp.zeros_like(tile).at[:, pos].set(tile[:, :n])
    new = dict(state)
    new.update(
        keys=merged_keys,
        values_tile=merged_tile,
        n_runs=1,
        run_starts=(0,),
        row_map=None,
    )
    return new


def table_scan_device(table, query, *, use_pallas: bool = True) -> tuple[float, float]:
    """Device-side execution of ``SortedTable.execute`` (sum/count aggs):
    slab via packed-key searchsorted, then the batched scan kernel at
    Q = 1. Used by the serving/data layers when tables are resident as
    jax arrays."""
    (out,) = table_scan_device_many(table, [query], use_pallas=use_pallas)
    return out


def table_scan_device_many(
    table,
    queries,
    *,
    slabs: np.ndarray | None = None,
    block_n: int = 2048,
    use_pallas: bool = True,
    grid: str = "rows_outer",
) -> list[tuple[float, float]]:
    """Batched ``table_scan_device``: all queries against one replica in
    a single row-streaming launch. Returns ``[(value, count)]`` per query
    in batch order.

    Heterogeneous groups ride together: "sum" queries over any mix of
    value columns and "count" queries share the launch — each distinct
    value column becomes one row of the value tile, counts select a ones
    row, and a per-query selector routes the aggregation. ``slabs``
    accepts precomputed ``slab_many`` output so callers that already
    located the slabs (``SortedTable.execute_many``) skip the second
    searchsorted. ``grid="queries_outer"`` dispatches the legacy PR 1
    grid (uniform-agg, narrow-key batches only) for benchmarking.
    """
    queries = list(queries)
    if not queries:
        return []
    # this legacy entry point accumulates counts in a float32 lane,
    # exact only to 2**24 — the fused int32 path has no such cap
    if table.n_rows > FLOAT32_EXACT_ROWS:
        raise ValueError(
            f"table has {table.n_rows} rows but the float32 count lane of "
            f"table_scan_device_many is exact only to {FLOAT32_EXACT_ROWS} "
            "matches; use table_execute_device_many (int32 counts)"
        )
    for q in queries:
        if q.agg not in ("sum", "count"):
            raise ValueError(f"device path supports sum/count aggs, got {q.agg!r}")
        if q.agg == "sum" and q.value_col is None:
            raise ValueError("sum aggregation requires value_col")
    state = getattr(table, "_device", None)
    if state is None:  # host table: materialize only this batch's columns
        state = build_device_state(
            table, value_cols={q.value_col for q in queries if q.agg == "sum"}
        )
    elif state.get("n_runs", 1) > 1:
        raise ValueError(
            "device state holds appended write runs (device row order is "
            "not sorted); row-slab scans need a single sorted run — use "
            "table_execute_device_many or place_on_device(rebuild=True)"
        )
    col_parts: tuple[int, ...] = state["col_parts"]
    if slabs is None:
        slabs = table.slab_many(queries)

    # the resident value tile already holds every value column + the
    # ones row; the per-query selector routes each aggregation to its row
    value_rows: dict[str, int] = state["value_rows"]
    values = state["values_tile"]
    sel = np.array(
        [
            value_rows[q.value_col] if q.agg == "sum" else state["ones_row"]
            for q in queries
        ],
        np.int32,
    )

    names = list(table.layout)
    bounds = np.array(
        [[q.filter_bounds(table.schema, c) for c in names] for q in queries],
        np.int64,
    )  # (Q, K, 2) — lo inclusive, hi exclusive
    lo, hi = _expand_bounds(bounds, col_parts)
    slabs32 = np.asarray(slabs, np.int64).astype(np.int32)

    if grid == "queries_outer":
        if len(set(sel)) > 1 or any(p != 1 for p in col_parts):
            raise ValueError(
                "queries_outer grid requires a uniform-agg, narrow-key batch"
            )
    elif grid != "rows_outer":
        raise ValueError(f"unknown grid {grid!r}")
    if not use_pallas:  # one oracle covers both grids
        out = np.asarray(
            ref.scan_agg_batched_ref(
                state["keys"], jnp.asarray(values), jnp.asarray(lo, jnp.int32),
                jnp.asarray(hi, jnp.int32), jnp.asarray(slabs32),
                jnp.asarray(sel), col_parts=col_parts,
            )
        )
    elif grid == "queries_outer":
        out = np.asarray(
            scan_agg_batched_qgrid_pallas(
                state["keys"], values[int(sel[0])], lo, hi, slabs32,
                block_n=block_n,
            )
        )
    else:
        out = np.asarray(
            scan_agg_batched_pallas(
                state["keys"], values, lo, hi, slabs32, sel,
                col_parts=col_parts, block_n=block_n,
                n_vals=state["n_value_rows"],
            )
        )
    return [
        (float(s) if q.agg == "sum" else float(c), float(c))
        for q, (s, c) in zip(queries, out)
    ]


# -- fused device read path ---------------------------------------------------


def _device_query_bounds(table, queries, col_parts, n_rows):
    """Host-side O(Q·K) operand prep for the device read kernels: the
    residual per-lane bounds (exclusive hi), the slab key lane bounds
    (inclusive hi, from the same walk ``slab_bounds_many`` packs), and
    the per-query [start, stop) row windows. Empty queries are encoded
    as an impossible slab key (hi lanes = −1) and a (0, 0) window.
    Raises exactly where the host walk raises (out-of-domain bounds on a
    nonempty query); performs zero searchsorted calls."""
    from repro.core.table import _slab_col_bounds

    names = list(table.layout)
    # the slab walk first: it owns bound validation, so the device path
    # raises (or not) exactly like the scalar host walk
    los, his, nonempty = _slab_col_bounds(queries, names, table.schema)
    slab_lo, slab_hi = _expand_bounds(np.stack([los, his], axis=2), col_parts)
    slab_lo[~nonempty] = 0
    slab_hi[~nonempty] = -1
    bounds = np.array(
        [[q.filter_bounds(table.schema, c) for c in names] for q in queries],
        np.int64,
    )  # (Q, K, 2) — lo inclusive, hi exclusive
    res_lo, res_hi = _expand_bounds(bounds, col_parts)
    limits = np.zeros((len(queries), 2), np.int64)
    limits[:, 1] = np.where(nonempty, n_rows, 0)
    return res_lo, res_hi, slab_lo, slab_hi, limits


def table_slab_locate_many(
    table, queries, *, block_n: int = DEVICE_BLOCK_N, use_pallas: bool = True
) -> np.ndarray:
    """Device-side ``SortedTable.slab_many``: int64[Q, 2] row slabs from
    the vectorized binary-search kernel (:func:`slab_locate_batched`)
    over the resident key lanes. Requires the resident arrays to hold a
    single sorted run — with appended write runs device row order is not
    the table order and ranks would be meaningless."""
    queries = list(queries)
    state = getattr(table, "_device", None)
    if state is None:
        raise ValueError("table_slab_locate_many needs a device-resident table")
    if state.get("n_runs", 1) > 1:
        raise ValueError(
            "device state holds appended write runs; slab ranks need a "
            "single sorted run — use place_on_device(rebuild=True)"
        )
    col_parts = state["col_parts"]
    _, _, slab_lo, slab_hi, limits = _device_query_bounds(
        table, queries, col_parts, state["n_rows"]
    )
    fn = slab_locate_batched if use_pallas else ref.slab_locate_batched_ref
    kw = {"block_n": block_n} if use_pallas else {}
    out = fn(
        state["keys"], jnp.asarray(slab_lo), jnp.asarray(slab_hi),
        jnp.asarray(limits, jnp.int32), n_lanes=sum(col_parts), **kw,
    )
    return np.asarray(out).astype(np.int64)


def table_execute_device_many(
    table, queries, *, block_n: int = DEVICE_BLOCK_N, use_pallas: bool = True,
    trace=None,
) -> list:
    """Serve a sum/count/select batch entirely from a table's resident
    device arrays: one fused locate+scan launch computes every query's
    aggregate, matched count and slab row count (``rows_scanned``), and
    — only when the batch contains selects with matches — one prefix-sum
    compaction launch emits the matched row indices (two-pass: the
    fused counts size its output). Returns ``list[ScanResult]`` in batch
    order, equal to the numpy engine's results (counts/rows exactly,
    sums to float32 accumulation).

    The only host↔device syncs are the result fetches; no host
    searchsorted and no numpy residual scan run at any batch
    composition. On append-structured states (after ``merge_insert`` on
    a resident table) ``row_map`` translates select indices back to
    host row order.

    ``trace`` (an open ``repro.obs.Span``, or None) wraps each device
    launch *wall* — launch plus the ``np.asarray`` result fetch, i.e.
    including the host sync — as ``kernel.scan_launch`` /
    ``kernel.select_compact`` child spans."""
    from repro.core.table import ScanResult

    queries = list(queries)
    if not queries:
        return []
    state = getattr(table, "_device", None)
    if state is None:
        raise ValueError("table_execute_device_many needs a device-resident table")
    value_rows: dict[str, int] = state["value_rows"]
    for q in queries:
        if q.agg not in ("sum", "count", "select"):
            raise ValueError(
                f"device path supports sum/count/select aggs, got {q.agg!r}"
            )
        if q.agg == "sum":
            if q.value_col is None:
                raise ValueError("sum aggregation requires value_col")
            if q.value_col not in value_rows:
                raise KeyError(q.value_col)
    col_parts = state["col_parts"]
    res_lo, res_hi, slab_lo, slab_hi, limits = _device_query_bounds(
        table, queries, col_parts, state["n_rows"]
    )
    sel = np.array(
        [
            value_rows[q.value_col] if q.agg == "sum" else state["ones_row"]
            for q in queries
        ],
        np.int32,
    )
    ks = (
        trace.child(
            "kernel.scan_launch", queries=len(queries),
            n_rows=int(state["n_rows"]), fused=bool(use_pallas),
        )
        if trace is not None
        else None
    )
    if use_pallas:
        sums, matched, slab_rows = scan_agg_locate_batched(
            state["keys"], state["values_tile"], res_lo, res_hi, slab_lo,
            slab_hi, limits, sel, col_parts=col_parts,
            n_vals=state["n_value_rows"], block_n=block_n,
        )
    else:
        sums, matched, slab_rows = ref.scan_agg_locate_batched_ref(
            state["keys"], state["values_tile"], jnp.asarray(res_lo),
            jnp.asarray(res_hi), jnp.asarray(slab_lo), jnp.asarray(slab_hi),
            jnp.asarray(limits, jnp.int32), jnp.asarray(sel),
            col_parts=col_parts,
        )
    sums = np.asarray(sums)
    matched = np.asarray(matched, np.int64)
    slab_rows = np.asarray(slab_rows, np.int64)
    if ks is not None:
        ks.end()

    sel_idx = [i for i, q in enumerate(queries) if q.agg == "select"]
    selected: dict[int, np.ndarray] = {}
    rm = state["row_map"]

    def _host_rows(dev_rows: np.ndarray) -> np.ndarray:
        rows = dev_rows.astype(np.int64)
        if rm is not None:
            # appended runs: translate device row order to host
            # (merged) order; numpy emits ascending indices
            rows = np.sort(rm[rows])
        return rows

    wide = [i for i in sel_idx if int(matched[i]) > SELECT_COMPACT_MAX_WIDTH]
    if wide:
        # too many matches for a VMEM-resident compaction output: build
        # the membership mask on device, pull back only the indices
        wmask = residual_membership_batched(
            state["keys"], res_lo[wide], res_hi[wide], limits[wide],
            col_parts=col_parts,
        )
        for j, i in enumerate(wide):
            rows = jnp.flatnonzero(wmask[j], size=int(matched[i]))
            selected[i] = _host_rows(np.asarray(rows))
        sel_idx = [i for i in sel_idx if int(matched[i]) <= SELECT_COMPACT_MAX_WIDTH]
    kc = (
        trace.child("kernel.select_compact", queries=len(sel_idx))
        if trace is not None and sel_idx
        else None
    )
    if sel_idx:
        mmax = int(matched[sel_idx].max())
        if mmax == 0:
            for i in sel_idx:
                selected[i] = np.empty(0, np.int64)
        else:
            width = 128
            while width < mmax:  # pow-2 lanes bucket the jit cache
                width *= 2
            # bound the whole output block, not just its width: chunk the
            # batch so Q_pad * width stays inside the element budget
            q_chunk = max(8, (SELECT_COMPACT_MAX_ELEMS // width) // 8 * 8)
            for s in range(0, len(sel_idx), q_chunk):
                chunk = sel_idx[s : s + q_chunk]
                if use_pallas:
                    idx = select_compact_batched(
                        state["keys"], res_lo[chunk], res_hi[chunk],
                        limits[chunk], col_parts=col_parts, out_width=width,
                        block_n=block_n,
                    )
                else:
                    idx = ref.select_compact_batched_ref(
                        state["keys"], jnp.asarray(res_lo[chunk]),
                        jnp.asarray(res_hi[chunk]),
                        jnp.asarray(limits[chunk], jnp.int32),
                        col_parts=col_parts, out_width=width,
                    )
                idx = np.asarray(idx)
                for j, i in enumerate(chunk):
                    selected[i] = _host_rows(idx[j, : int(matched[i])])
    if kc is not None:
        kc.end()

    out = []
    for i, q in enumerate(queries):
        value = float(sums[i]) if q.agg == "sum" else float(matched[i])
        out.append(
            ScanResult(value, int(slab_rows[i]), int(matched[i]), selected.get(i))
        )
    return out
