"""SortedTable — the SSTable analogue (paper §3.1, Fig 2).

A table holds columnar data sorted lexicographically by a *layout*
(permutation of the clustering key columns). A query with an equality
prefix and one range filter touches a *contiguous slab* of rows: Cassandra
"traverses from the lower bound and terminates at the first key exceeding
the end boundary" — here the slab is located with two binary searches on
the packed composite key, then scanned with residual predicates.

The slab size IS the paper's ``Row(r, q)`` ground truth; ``execute``
returns it alongside the query result so the cost model can be validated
against reality (tests + Fig 4 benches).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import numpy as np

from .keys import KeySchema, _field_shifts, pack_columns, pack_tuple
from .workload import Query

__all__ = [
    "SortedTable",
    "ScanResult",
    "slab_bounds_for",
    "slab_bounds_many",
    "merge_partial_scans",
]


@dataclasses.dataclass(frozen=True)
class ScanResult:
    """Result of executing a query on one replica's table.

    Frozen: the engine's result cache hands the same object to every
    hit, so field mutation would corrupt later reads (the ``selected``
    array's *buffer* is additionally write-protected when cached)."""

    value: float  # aggregate value ("select" reports match count here too)
    rows_scanned: int  # slab size — rows streamed from storage (paper Row())
    rows_matched: int  # rows passing all residual predicates
    selected: np.ndarray | None = None  # row indices for agg == "select"


def merge_partial_scans(
    partials: Sequence[tuple[ScanResult, int]], agg: str
) -> ScanResult:
    """Merge per-partition partial scan results into one ``ScanResult``
    (the gather half of a partitioned ``read_many``).

    ``partials`` is ``[(result, row_offset)]`` in ring order; partitions
    hold disjoint row sets, so sums, match counts and slab row counts
    simply add (partial sums accumulate in ring order, so the float
    result is deterministic). For ``agg == "select"`` each partition's
    local row indices shift by that partition's global row offset and
    concatenate — the merged index space is "partitions in ring order,
    each in its serving replica's serialization order", the P-partition
    analogue of a single replica's row order. Offsets are applied into
    fresh arrays: a partial may be a shared (frozen) result-cache entry.
    """
    if len(partials) == 1 and agg != "select":
        return partials[0][0]
    value = sum(float(r.value) for r, _ in partials)
    scanned = sum(int(r.rows_scanned) for r, _ in partials)
    matched = sum(int(r.rows_matched) for r, _ in partials)
    if agg != "select":
        return ScanResult(value, scanned, matched)
    chunks = [
        r.selected.astype(np.int64, copy=True) + off
        for r, off in partials
        if r.selected is not None and r.selected.size
    ]
    selected = (
        np.concatenate(chunks) if chunks else np.empty(0, np.int64)
    )
    return ScanResult(float(matched), scanned, matched, selected=selected)


def slab_bounds_for(
    query: Query, layout: Sequence[str], schema: KeySchema
) -> tuple[int, int]:
    """Packed-key [lo, hi) bounds of the contiguous slab a query touches.

    Walk the layout: keys with equality filters extend the fixed prefix;
    the first non-equality key contributes its range and terminates the
    prefix (everything after it is residual-filtered during the scan, so
    its slab bounds are the full per-column domain).
    """
    los: list[int] = []
    his: list[int] = []
    open_range = False
    for col in layout:
        if open_range:
            lo_c, hi_c = 0, schema.max_value(col) + 1
        else:
            lo_c, hi_c = query.filter_bounds(schema, col)
            if hi_c <= lo_c:
                # degenerate (empty) filter range: the query matches no
                # row — return an empty slab instead of packing hi_c - 1
                # (< lo_c), which would raise.
                return 0, 0
            if not query.is_equality_on(col):
                open_range = True
        los.append(lo_c)
        his.append(hi_c - 1)  # inclusive upper value per field
    lo = pack_tuple(los, layout, schema)
    hi = pack_tuple(his, layout, schema) + 1  # exclusive
    return lo, hi


def _slab_col_bounds(
    queries: Sequence[Query], layout: Sequence[str], schema: KeySchema
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-column slab bounds for a query batch: ``(los, his, nonempty)``
    with ``los``/``his`` int64[Q, K] (his *inclusive*) and ``nonempty``
    a bool[Q] mask of queries whose filter ranges are all non-degenerate.

    This is the layout walk shared by :func:`slab_bounds_many` (which
    packs the columns into composite keys for the host searchsorted) and
    the device locate path (which ships them as int32 key lanes to the
    Pallas kernels — ``repro.kernels``). Validation is deferred and
    masked exactly like the scalar walk: only nonempty queries may raise
    on out-of-domain bounds.
    """
    schema.check_layout(layout)
    n_q, n_k = len(queries), len(layout)
    los = np.zeros((n_q, n_k), dtype=np.int64)
    his = np.zeros((n_q, n_k), dtype=np.int64)
    nonempty = np.ones(n_q, dtype=bool)
    open_range = np.zeros(n_q, dtype=bool)
    for j, col in enumerate(layout):
        full_lo, full_hi = 0, schema.max_value(col) + 1
        for i, q in enumerate(queries):
            if open_range[i] or not nonempty[i]:
                # open prefix — or a query already known empty, whose
                # remaining filters must not be evaluated (the scalar
                # walk returns before reaching them)
                lo_c, hi_c = full_lo, full_hi
            else:
                f = q.filters.get(col)
                if f is None:  # global range filter opens the prefix
                    lo_c, hi_c = full_lo, full_hi
                    open_range[i] = True
                elif f.is_equality:
                    lo_c = f.value
                    hi_c = lo_c + 1
                else:
                    lo_c, hi_c = f.start, f.end
                    if hi_c <= lo_c:
                        nonempty[i] = False
                        lo_c, hi_c = full_lo, full_hi  # placeholder; masked below
                    else:
                        open_range[i] = True
            los[i, j] = lo_c
            his[i, j] = hi_c - 1  # inclusive upper value per field
    # validation is deferred and masked: the scalar walk returns (empty
    # slab) on a degenerate range before pack_tuple ever checks the
    # other columns, so only nonempty queries may raise here
    for j, col in enumerate(layout):
        bad = nonempty & ((los[:, j] < 0) | (his[:, j] > schema.max_value(col)))
        if bad.any():
            raise ValueError(
                f"query {int(np.argmax(bad))} bounds out of range for column {col!r}"
            )
    return los, his, nonempty


def slab_bounds_many(
    queries: Sequence[Query], layout: Sequence[str], schema: KeySchema
) -> np.ndarray:
    """Packed-key [lo, hi] slab bounds for a query batch: int64[Q, 2].

    Same walk as :func:`slab_bounds_for` but with the per-column bounds
    gathered into ``int64[Q, K]`` arrays (:func:`_slab_col_bounds`) and
    packed with one vectorized shift-or per column. Unlike the scalar
    function the upper bound is returned *inclusive* — a 63-bit schema
    packs its maximum key to ``2**63 − 1``, and the scalar ``+ 1`` would
    wrap int64 (``slab_many`` compensates with ``side="right"``, an
    exact equivalent). Queries with a degenerate (empty) filter range
    get ``lo = 0, hi = −1``.
    """
    return pack_slab_bounds(*_slab_col_bounds(queries, layout, schema), layout, schema)


def pack_slab_bounds(
    los: np.ndarray, his: np.ndarray, nonempty: np.ndarray,
    layout: Sequence[str], schema: KeySchema,
) -> np.ndarray:
    """Pack :func:`_slab_col_bounds`'s per-column bounds into the
    int64[Q, 2] composite-key bounds of :func:`slab_bounds_many`."""
    # MSB-first packing, same field shifts as keys.pack_tuple
    sh = np.asarray(_field_shifts(schema, layout), dtype=np.int64)
    out = np.empty((los.shape[0], 2), dtype=np.int64)
    out[:, 0] = ((los << sh).sum(axis=1)) * nonempty
    out[:, 1] = np.where(nonempty, (his << sh).sum(axis=1), -1)
    return out


def slab_windows(packed: np.ndarray, bounds: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Row windows ``(lo, hi)``, each int[Q], ``[lo, hi)``, of packed-key
    slab bounds (:func:`slab_bounds_many`) in one sorted run: two
    vectorized searchsorteds. The inclusive upper key with
    ``side="right"`` equals the scalar ``(hi + 1, side="left")`` without
    the int64 wrap at 63-bit schemas, and matches the fused kernel's
    ``<=`` rank."""
    lo = np.searchsorted(packed, bounds[:, 0], side="left")
    hi = np.searchsorted(packed, bounds[:, 1], side="right")
    return lo, hi


@dataclasses.dataclass
class SortedTable:
    layout: tuple[str, ...]
    schema: KeySchema
    key_cols: dict[str, np.ndarray]  # sorted, int64
    value_cols: dict[str, np.ndarray]  # sorted alongside
    packed: np.ndarray  # int64, ascending
    # device-resident column cache (repro.kernels.build_device_state) —
    # populated by place_on_device(); never part of table identity
    _device: dict | None = dataclasses.field(default=None, repr=False, compare=False)
    # multiset content digest sealed at CREATE/recovery and *extended*
    # (never recomputed from memory) by each flush — see
    # ``storage.content_digest``; scrub recomputes from the arrays and
    # compares to detect at-rest bit flips. Not table identity — two
    # equal tables may differ only in whether a digest was sealed
    stored_digest: int | None = dataclasses.field(default=None, repr=False, compare=False)

    # -- construction ------------------------------------------------------

    @classmethod
    def from_columns(
        cls,
        key_cols: Mapping[str, np.ndarray],
        value_cols: Mapping[str, np.ndarray],
        layout: Sequence[str],
        schema: KeySchema | None = None,
    ) -> "SortedTable":
        if schema is None:
            schema = KeySchema.for_columns(key_cols)
        layout = tuple(layout)
        packed = pack_columns(key_cols, layout, schema)
        order = np.argsort(packed, kind="stable")
        return cls(
            layout=layout,
            schema=schema,
            key_cols={c: np.asarray(v)[order].astype(np.int64) for c, v in key_cols.items()},
            value_cols={c: np.asarray(v)[order] for c, v in value_cols.items()},
            packed=packed[order],
        )

    def __len__(self) -> int:
        return int(self.packed.shape[0])

    @property
    def n_rows(self) -> int:
        return len(self)

    def resorted(self, layout: Sequence[str]) -> "SortedTable":
        """Same dataset, different serialization — the HR recovery path
        (rebuild a lost replica by re-sorting a survivor, paper §4)."""
        return SortedTable.from_columns(self.key_cols, self.value_cols, layout, self.schema)

    # -- content checksums (scrub) ------------------------------------------

    def content_digest(self) -> int:
        """Order/layout-independent multiset digest of the key + value
        columns (see ``storage.content_digest``): every replica of the
        same row set agrees on it regardless of serialization."""
        from .storage.memtable import content_digest

        return content_digest(self.key_cols, self.value_cols)

    def seal_checksum(self) -> "SortedTable":
        """Record the current content digest in ``stored_digest``. The
        engine seals at CREATE and recovery; a flush *extends* the seal
        with the run's digest instead (``combine_digests``), so the
        sealed value always derives from the durable history — merging
        on top of a corrupted array can't launder the corruption into a
        fresh seal. Returns ``self`` for chaining."""
        self.stored_digest = self.content_digest()
        return self

    def verify_checksum(self) -> bool:
        """True when the sealed digest still matches the content (or no
        digest was ever sealed — nothing to verify against)."""
        return self.stored_digest is None or self.content_digest() == self.stored_digest

    # -- device residency ----------------------------------------------------

    def place_on_device(self, *, rebuild: bool = False) -> "SortedTable":
        """Materialize the columns as device-resident jax arrays (int32
        key lanes — wide columns split into two — plus float32 value
        rows). Afterwards ``execute``/``execute_many`` answer sum, count
        AND select queries entirely on device (fused locate+scan, plus
        index compaction for selects) and ``slab_many`` locates slabs
        with the Pallas binary-search kernel instead of host
        searchsorted. Raises ``ValueError`` naming the offending column
        if a key column exceeds the device path's two-lane 60-bit budget.

        Placement is *incremental*: ``merge_insert`` appends each merged
        write run to the already-resident arrays, so a resident table is
        NOT re-uploaded after writes. Calling ``place_on_device()`` on a
        table that is already resident is a no-op; pass ``rebuild=True``
        to force a fresh, fully-sorted re-upload (collapses appended
        runs and restores device row order == host row order). Returns
        ``self`` for chaining."""
        from repro.kernels import build_device_state

        if self._device is None or rebuild:
            self._device = build_device_state(self)
        return self

    def evict_from_device(self) -> None:
        """Drop the device-resident cache; reads fall back to numpy."""
        self._device = None

    @property
    def device_resident(self) -> bool:
        return self._device is not None

    # -- materialized per-slab views ----------------------------------------

    def build_views(self, *, use_pallas: bool = True, trace=None) -> "SortedTable":
        """(Re)build the materialized per-slab aggregate view over the
        resident arrays (``repro.core.storage.views``): per-block
        float32 partial sums of the value tile plus the per-run packed
        key index. Views are *derived* state — this is also the heal
        path when scrub finds a corrupted partial. Requires device
        residency. Returns ``self`` for chaining."""
        from .storage.views import build_views_state

        if self._device is None:
            raise ValueError("build_views requires a device-resident table")
        vb = (
            trace.child("view.build", rows=len(self))
            if trace is not None
            else None
        )
        self._device["views"] = build_views_state(
            self._device, self.packed, use_pallas=use_pallas
        )
        if vb is not None:
            vb.end()
        return self

    @property
    def has_views(self) -> bool:
        return self._device is not None and "views" in self._device

    def _view_eligible(self, query: Query) -> bool:
        """Queries the view answers bit-identically to the fused scan:
        sum/count whose filters the slab walk fully consumes on this
        layout (no residual predicate), with the view built and sum
        value columns resident."""
        from .storage.views import query_view_eligible

        return (
            self.has_views
            and query_view_eligible(query, self.layout)
            and (
                query.agg != "sum"
                or query.value_col in self._device["value_rows"]
            )
        )

    def _device_eligible(self, query: Query) -> bool:
        """Queries the device path answers end-to-end: sum/count
        aggregations and "select" row emission (fused locate+scan plus
        prefix-sum index compaction). Sums need their value column
        resident; unknown aggregations keep the numpy path (which
        raises, same as a host table)."""
        return (
            self._device is not None
            and query.agg in ("sum", "count", "select")
            and (query.agg != "sum" or query.value_col in self.value_cols)
        )

    # -- writes (LSM-style bulk merge) --------------------------------------

    def merge_insert(
        self, key_cols: Mapping[str, np.ndarray], value_cols: Mapping[str, np.ndarray]
    ) -> "SortedTable":
        """Merge an unsorted write batch: sort it into a run in this
        table's own layout, then :meth:`merge_run` it.

        The per-replica sort order is this table's own layout, mirroring
        Cassandra's per-replica LSM write path: HR costs the same writes
        as TR because every replica sorts exactly one copy (Table 1).
        The engine's memtable path produces the run itself (one sort for
        a whole commit group) and calls :meth:`merge_run` directly.
        """
        from .storage.memtable import sort_run

        return self.merge_run(sort_run(key_cols, value_cols, self.layout, self.schema))

    def merge_run(self, run, *, trace=None) -> "SortedTable":
        """Merge one presorted run (memtable flush → SSTable merge).

        ``run`` carries ``key_cols``/``value_cols``/``packed`` already
        sorted by this table's layout (``repro.core.storage.SortedRun``).
        Ties merge new-rows-first: a freshly written row lands *before*
        equal existing rows, and rows within the run keep arrival order
        — the order every layer above (``row_map`` bookkeeping, the
        device k-way merge kernel) reproduces.

        The hot path is GIL-friendly by construction: the dominant
        O(N log N) step is an in-place ``np.sort`` on a concatenated
        packed-key buffer (numpy's sort releases the GIL; its stable
        sort is adaptive, so two sorted runs merge in ~O(N)), and the
        columns are placed by precomputed destination scatters — no
        ``np.argsort`` and no ``np.insert`` on the base-sized arrays,
        which held the GIL and kept ``write(parallel=True)`` at
        break-even (``benchmarks/write_queue.py`` records the overlap).

        If this table is device-resident, the run is *appended* to the
        resident arrays (``repro.kernels.device_state_append``) instead
        of re-uploading the whole table: the returned table is
        immediately resident, with a ``row_map`` translating device row
        order (base rows then appended runs) back to the merged host
        order for "select". Automatic compaction (or
        ``place_on_device(rebuild=True)``) collapses the run stack.
        """
        new_packed = np.asarray(run.packed)
        m = int(new_packed.shape[0])
        n_old = len(self)
        # merge positions of the new run into the existing rows
        pos = np.searchsorted(self.packed, new_packed, side="left")
        if m == 0:
            kc = {c: v.copy() for c, v in self.key_cols.items()}
            vc = {c: np.asarray(v).copy() for c, v in self.value_cols.items()}
            merged = SortedTable(self.layout, self.schema, kc, vc, self.packed.copy())
        else:
            # destination rows reproduce np.insert semantics exactly:
            # run row j lands at pos[j] + j, old row i shifts past the
            # new rows at-or-before it (ties: new rows first)
            dest_new = pos + np.arange(m, dtype=np.int64)
            shift = np.searchsorted(new_packed, self.packed, side="right")
            dest_old = np.arange(n_old, dtype=np.int64) + shift
            merged_packed = np.concatenate([self.packed, new_packed])
            merged_packed.sort(kind="stable")

            def _scatter(old: np.ndarray, new: np.ndarray) -> np.ndarray:
                out = np.empty(n_old + m, dtype=old.dtype)
                out[dest_old] = old
                out[dest_new] = new
                return out

            kc = {c: _scatter(self.key_cols[c], run.key_cols[c]) for c in self.key_cols}
            vc = {
                c: _scatter(np.asarray(self.value_cols[c]), np.asarray(run.value_cols[c]))
                for c in self.value_cols
            }
            merged = SortedTable(self.layout, self.schema, kc, vc, merged_packed)
        if self._device is not None:
            from repro.kernels import device_state_append

            merged._device = device_state_append(
                self._device, merged, run.key_cols, run.value_cols, pos
            )
            if "views" in self._device and m > 0:
                # extend the materialized view O(run): only blocks at or
                # after the append point refold (storage.views)
                from .storage.views import extend_views_state

                vb = (
                    trace.child("view.build", rows=m, incremental=True)
                    if trace is not None
                    else None
                )
                merged._device["views"] = extend_views_state(
                    self._device["views"], merged._device, new_packed, n_old
                )
                if vb is not None:
                    vb.end()
        return merged

    def compact_runs(self, *, use_pallas: bool = True) -> "SortedTable":
        """Collapse appended device runs into one sorted run *on device*
        via the Pallas k-way merge kernel
        (``repro.kernels.merge_device_runs``) — unlike
        ``place_on_device(rebuild=True)`` nothing is re-uploaded. After
        compaction device row order equals host row order again
        (``row_map`` is identity), so the single-run fast paths apply.
        No-op on host tables and single-run states. Returns ``self``."""
        if self._device is not None and self._device.get("n_runs", 1) > 1:
            from repro.kernels import merge_device_runs

            had_views = "views" in self._device
            self._device = merge_device_runs(self._device, use_pallas=use_pallas)
            if had_views:
                # compaction permuted the resident arrays into one
                # sorted run: rebuild the view whole (per-run partials
                # cannot be permuted cheaper than refolding)
                self.build_views(use_pallas=use_pallas)
        return self

    # -- reads ---------------------------------------------------------------

    def slab(self, query: Query) -> tuple[int, int]:
        """Row index range [lo_idx, hi_idx) the query must stream."""
        lo_key, hi_key = slab_bounds_for(query, self.layout, self.schema)
        lo = int(np.searchsorted(self.packed, lo_key, side="left"))
        # search for the inclusive upper key with side="right": a 63-bit
        # schema's exclusive bound is 2**63, which does not fit int64 and
        # would be float-cast (losing low bits) by searchsorted
        hi = int(np.searchsorted(self.packed, hi_key - 1, side="right"))
        return lo, hi

    def slab_many(self, queries: Sequence[Query]) -> np.ndarray:
        """Row index slabs ``int64[Q, 2]`` for a query batch.

        On a device-resident table holding a single sorted run, the
        ranks come from the Pallas vectorized binary-search kernel
        (``repro.kernels.table_slab_locate_many``) — no host
        searchsorted. Otherwise (host tables, or resident arrays with
        appended write runs, whose device row order is no longer
        sorted) one vectorized ``np.searchsorted`` over the packed
        bound array replaces 2·Q per-query binary searches; that numpy
        path stays the oracle the kernel is property-tested against.
        """
        queries = list(queries)
        if queries and self._device is not None and self._device.get("n_runs", 1) == 1:
            from repro.kernels import table_slab_locate_many

            return table_slab_locate_many(self, queries)
        lo, hi = slab_windows(
            self.packed, slab_bounds_many(queries, self.layout, self.schema)
        )
        return np.stack([lo, hi], axis=1).astype(np.int64)

    def execute(self, query: Query) -> ScanResult:
        """Stream the slab, apply residual predicates, aggregate.

        Device-resident tables answer eligible queries (sum, count,
        select) with the fused locate+scan launch at Q = 1 — no host
        searchsorted, no numpy scan — so a scalar loop and
        ``execute_many`` compute per-query results identically; numpy is
        the reference engine and the path for host tables.
        """
        if self._view_eligible(query):
            from .storage.views import serve_view_many

            return serve_view_many(self, [query])[0]
        if self._device_eligible(query):
            from repro.kernels import table_execute_device_many

            return table_execute_device_many(self, [query])[0]
        lo, hi = self.slab(query)
        return self._scan_slab(query, lo, hi)

    def execute_many(
        self, queries: Sequence[Query], *, trace=None, view_stats=None,
        select_stats=None,
    ) -> list[ScanResult]:
        """Batched ``execute``.

        On a device-resident table every eligible query (sum, count AND
        select) is served by ``repro.kernels.table_execute_device_many``:
        one fused locate+scan launch answers the whole group — slab
        membership is decided against the packed slab key bounds inside
        the scan predicate, so no host searchsorted runs and no host
        sync separates locate from scan — plus one compaction launch
        when the group contains selects with matches. Host tables (and
        ineligible aggregations) locate slabs with one vectorized
        searchsorted and run the numpy residual scan. Either way result
        ``i`` equals ``execute(queries[i])``, which routes per query the
        same way.

        ``trace`` (an open :class:`repro.obs.Span`, or None) records
        the device launches as ``kernel.scan_launch`` /
        ``kernel.select_compact`` children, view hits as ``view.serve``
        and the numpy fallback as ``engine.host_scan`` — the deepest
        tier of the read-path span tree.

        When the table carries a materialized view
        (:meth:`build_views`), view-eligible queries (sum/count fully
        consumed by the slab walk) are answered from the stored
        per-block partials — O(blocks touched) instead of the O(N)
        fused stream, bit-identical by construction — and
        ``view_stats`` (a dict, or None) receives their ``hits`` /
        ``boundary_rows`` tallies for the engine's counters, as
        ``select_stats`` receives the device select compaction's
        ``sparse_launches`` / ``full_launches``.
        """
        queries = list(queries)
        if not queries:
            return []
        results: list[ScanResult | None] = [None] * len(queries)
        view_idx = [i for i, q in enumerate(queries) if self._view_eligible(q)]
        if view_idx:
            from .storage.views import serve_view_many

            out = serve_view_many(
                self, [queries[i] for i in view_idx], trace=trace,
                view_stats=view_stats,
            )
            for i, r in zip(view_idx, out):
                results[i] = r
        dev_idx = [
            i
            for i, q in enumerate(queries)
            if results[i] is None and self._device_eligible(q)
        ]
        if dev_idx:
            from repro.kernels import table_execute_device_many

            out = table_execute_device_many(
                self, [queries[i] for i in dev_idx], trace=trace,
                select_stats=select_stats,
            )
            for i, r in zip(dev_idx, out):
                results[i] = r
        host_idx = [i for i in range(len(queries)) if results[i] is None]
        if host_idx:
            hs = (
                trace.child("engine.host_scan", queries=len(host_idx))
                if trace is not None
                else None
            )
            sub = [queries[i] for i in host_idx]
            slabs = self.slab_many(sub)
            for j, i in enumerate(host_idx):
                results[i] = self._scan_slab(sub[j], int(slabs[j, 0]), int(slabs[j, 1]))
            if hs is not None:
                hs.end()
        return results  # type: ignore[return-value]

    def _scan_slab(self, query: Query, lo: int, hi: int) -> ScanResult:
        n = hi - lo
        if n <= 0:
            return ScanResult(0.0, 0, 0, np.empty(0, np.int64) if query.agg == "select" else None)
        mask = np.ones(n, dtype=bool)
        for col in self.layout:
            lo_c, hi_c = query.filter_bounds(self.schema, col)
            v = self.key_cols[col][lo:hi]
            mask &= (v >= lo_c) & (v < hi_c)
        matched = int(mask.sum())
        if query.agg == "count":
            return ScanResult(float(matched), n, matched)
        if query.agg == "sum":
            if query.value_col is None:
                raise ValueError("sum aggregation requires value_col")
            vals = self.value_cols[query.value_col][lo:hi]
            return ScanResult(float(np.sum(vals * mask)), n, matched)
        if query.agg == "select":
            idx = np.nonzero(mask)[0] + lo
            return ScanResult(float(matched), n, matched, selected=idx)
        raise ValueError(f"unknown agg {query.agg!r}")

    # -- identity ------------------------------------------------------------

    def dataset_fingerprint(self) -> str:
        """Order-independent content hash: replicas of the same dataset have
        equal fingerprints regardless of serialization (HR invariant).

        Rows are brought to a canonical order (lexicographic over sorted
        column names, value columns as tiebreakers) and hashed exactly.
        """
        import hashlib

        canon = tuple(sorted(self.key_cols))
        packed = pack_columns(self.key_cols, canon, self.schema)
        vnames = tuple(sorted(self.value_cols))
        tiebreak = [
            np.asarray(self.value_cols[c], dtype=np.float64) for c in reversed(vnames)
        ]
        order = np.lexsort(tuple(tiebreak) + (packed,))
        md = hashlib.md5()
        md.update(packed[order].tobytes())
        for c in vnames:
            md.update(c.encode())
            md.update(np.asarray(self.value_cols[c], dtype=np.float64)[order].tobytes())
        return md.hexdigest()
