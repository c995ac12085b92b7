"""HR Engine — the shim layer of paper §4, simulated-cluster edition.

Five modules, mapped 1:1 onto the paper's Figure 3:

  Request Agency    → ``HREngine.read`` / ``read_many`` / ``write``
                      (client API)
  Replica Generator → ``create_column_family`` (runs HRCA at CREATE, then
                      places replicas on nodes via hash(replica_id, pk))
  Cost Evaluator    → ``CostModel`` over live ``TableStats``
  Request Scheduler → cheapest-replica routing w/ tie round-robin (load
                      balance) and optional straggler hedging
  Write Scheduler   → commit log → per-replica memtable → flushed sorted
                      runs; each replica sorts through its own LSM-style
                      merge path (Table 1: HR write speed == TR write
                      speed)
  Recovery          → rebuild lost replicas by replaying the shared
                      commit log (default; bit-identical to re-sorting a
                      survivor, which remains available — §4 "leverage
                      the LSM-Tree write process"; §5.4)

Nodes are simulated (this container is one host), but every byte of the
data path is real: tables, scans, sorts and stats are actual arrays, so
rows_scanned/latency numbers in benchmarks are measurements, not models.

Token-ring partitioning (``create_column_family(partitions=P)``)
----------------------------------------------------------------
A production keyspace is split the way Cassandra's ring splits it
(``repro.core.ring``): rows map to one of ``P`` contiguous token ranges
of the canonical packed key space, and each partition owns a full
heterogeneous replica set of just its rows, its own commit log,
memtables and compaction policy. ``write`` routes rows to the owning
partitions' logs; ``read_many`` scatters each query to the partitions
its slab bounds intersect (pure host arithmetic against the ring's
start tokens), executes per ``(partition, replica)`` group — device
partitions via the fused Pallas launch — and gathers partial
aggregates (sum/count add up; select indices concatenate into the
global "partitions in ring order" index space). ``fail_node`` loses
only the partition replicas the node hosted and ``recover_node``
rebuilds each from its own partition log. ``P = 1`` (the default) is
bit-identical to the unpartitioned engine — same placement, same
routing draws, same results.

Batched reads (``read_many``)
-----------------------------
Production traffic arrives in batches; ``read_many`` amortizes the
scheduler and the storage scan across a whole batch while preserving the
sequential semantics exactly:

* **Cost estimation** is vectorized over all (query, replica) pairs
  (``estimate_rows_many``); the float64 expressions match the scalar
  path bit-for-bit, so the cost matrix equals Q×R scalar calls.
* **Tie-break**: per query (in batch order) the cheapest live replicas
  within the same relative tolerance as ``read`` form the tie set, and
  one round-robin counter draw is consumed per query — a ``read_many``
  over a batch picks exactly the replicas a sequential ``read`` loop
  would.
* **Execution** groups queries by chosen replica and answers each group
  with one ``SortedTable.execute_many`` (single vectorized searchsorted
  over packed slab bounds); per-query results/rows_scanned are identical
  to ``execute``. Group wall time is attributed evenly across the
  group's executed queries (× node slowdown). For a *device-resident*
  column family (``create_column_family(device_resident=True)``) each
  group is answered by one FUSED locate+scan Pallas launch
  (``repro.kernels.table_execute_device_many``): slab location happens
  inside the scan predicate (zero host ``searchsorted`` calls, no host
  sync between locate and scan), the replica's columns stream through
  VMEM once per group regardless of group size, and mixed
  sum/count/select groups share the launch ("select" row indices come
  from a second prefix-sum compaction launch sized by the first's
  int32 match counts). The scalar ``read`` path routes through the
  same kernel at Q = 1, so batched and sequential results stay
  identical; numpy remains the reference engine and the path for host
  tables.
* **Result cache**: each replica keeps a ``(packed slab bounds, agg,
  value col, filters) → ScanResult`` cache shared by both paths,
  invalidated by ``write``/``fail_node``/``recover_node``; hit/miss
  counters live on ``HREngine.stats``.
* **Hedging**: with ``hedge=True``, queries whose chosen node is a
  straggler (slowdown > ``hedge_ratio``) are duplicated — grouped per
  alternate replica (the next-cheapest on a *different* node, as in
  ``read``) — and the faster copy wins per query.

Durable write path (``write``)
------------------------------
Every write runs Cassandra's commit-log → memtable → sorted-run
pipeline (``repro.core.storage``): the batch is appended to the column
family's layout-agnostic :class:`CommitLog` (one shared record stream —
record 0 is the CREATE-time base dataset), staged into each live
replica's :class:`Memtable`, and flushed as an immutable sorted run in
that replica's own key layout via ``SortedTable.merge_run``. With
``memtable_rows > 0`` flushes are deferred until the staging threshold
(group commit: one sort + one merge per group instead of one per
write); reads flush a replica's pending rows before consulting it or
its result cache, so staged-but-unflushed writes can never serve stale
aggregates. On device-resident column families each flush appends a run
to the resident arrays and the :class:`CompactionPolicy` collapses the
run stack on device (Pallas k-way merge, ``merge_device_runs``) once
appended rows outgrow the base — no manual
``place_on_device(rebuild=True)``. Flushes and compactions invalidate
the affected replica's result-cache entries; counters for log records,
staged rows, flushes and compactions ride on :attr:`HREngine.stats`.

Availability layer (hints · consistency · detection · scrub)
------------------------------------------------------------
Cassandra's availability machinery, fitted to the simulated cluster:

* **Hinted handoff** — ``fail_node(node, transient=True)`` models an
  outage that loses memory but not disk: the node's tables survive and
  every hosted partition replica opens a *hint*, the LSN watermark its
  table was flushed through (``Partition.hints``/``flushed_lsn`` — an
  LSN range against the partition's own commit log, never a data
  copy). ``node_up`` then replays only ``[watermark, next_lsn)`` and
  merges that tail into the surviving table — healing a short outage
  costs O(missed writes), not O(dataset) — falling back to a full
  rebuild whenever the tail is gone (a checkpoint collapsed it, or the
  loss was durable). ``recover_node`` keeps the full-rebuild semantics
  for durable losses.
* **Tunable read consistency** — ``read``/``read_many`` accept
  ``consistency="ONE" | "QUORUM" | "ALL"``. Beyond ONE, each query
  also executes on the next cost-ranked replicas up to k (RF//2 + 1
  for QUORUM, RF for ALL) and the k results' *digests* are compared —
  crc32 over the canonical (layout-independent) ``ScanResult``
  encoding: the aggregate value (float32-quantized for sums, whose
  float64 totals differ across layouts only by summation-order noise
  far below one float32 ulp), the matched-row count, and for selects
  the sorted canonical packed keys of the selected rows. A mismatch
  (``digest_mismatches``) triggers **read repair**: minority replicas
  are rebuilt from the partition log — the ground truth — and the
  majority answer is returned (``read_repairs``); with no majority
  every consulted replica is rebuilt and the query re-executes.
* **Failure detection + graceful degradation** — pass
  ``failure_detector=FailureDetector()`` (``repro.ft.detector``; any
  object with ``record``/``record_failure``/``cost_factor`` works) and
  every executed replica-group scan feeds it. Nodes whose phi crosses
  the suspect threshold get their ranking costs *multiplied* by the
  detector's cost factor — soft avoidance, Cassandra's dynamic-snitch
  badness rule, never hard exclusion. When a scan raises (an injected
  fault: ``Node.read_fault_budget``), the planner retries the affected
  queries on the next-ranked untried replica (bounded by the replica
  count, ``read_retries``), recording the failure with the detector.
* **Checksums + scrub** — flushed runs carry crc32 (verified before
  merging) and the engine seals a content crc32 on every table it
  installs; ``scrub_column_family`` re-verifies every live replica and
  heals corrupt ones from the partition log (``scrub_repairs``).

``ft/chaos.py`` drives all of it: a seeded schedule of crash /
torn-log-tail / run-corruption / slow-node / flush-abort events whose
acceptance property is that after detector-driven repair, reads are
row-identical to a no-fault oracle engine fed the same writes.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Mapping, Sequence

import numpy as np

from ..obs import MetricsRegistry
from .cost_model import (
    CostModel,
    LinearCostFunction,
    estimate_rows,
    estimate_rows_many,
    precompute_query_stats,
)
from .ecdf import TableStats
from .hrca import HRCAResult, exhaustive_search, hrca, initial_state
from .keys import KeySchema, pack_columns
from .ring import Partition, ReplicaHandle, TokenHistogram, TokenRing, place_replica
from .storage import CommitLog, CompactionPolicy, Memtable, compact_table
from .storage.memtable import combine_digests, sort_run
from .storage.views import (
    VIEW_AGGS,
    VIEW_ROWS_CAP,
    query_view_eligible,
    verify_views,
    view_eligible_matrix,
)
from .table import ScanResult, SortedTable, merge_partial_scans, slab_bounds_many
from .workload import Query, Workload

__all__ = [
    "Node",
    "ReplicaHandle",
    "ColumnFamily",
    "ReadReport",
    "HREngine",
    "ONE",
    "QUORUM",
    "ALL",
    "CONSISTENCY_LEVELS",
    "TransientFault",
    "TransientReadError",
    "TransientFlushError",
    "CorruptRunError",
    "DeadlineExceeded",
    "ENGINE_COUNTERS",
    "FAULT_COUNTERS",
    "REPAIR_COUNTERS",
    "VIEW_COUNTERS",
    "SELECT_COUNTERS",
]

#: Tunable read consistency levels (Cassandra's CL, read side): how
#: many cost-ranked replicas must answer — and digest-agree — before a
#: result is returned. ONE trusts the single cheapest replica (the
#: historical behavior and the default).
ONE = "ONE"
QUORUM = "QUORUM"
ALL = "ALL"
CONSISTENCY_LEVELS = (ONE, QUORUM, ALL)


class TransientFault(RuntimeError):
    """A scan or flush raised in a retryable way (injected fault / chaos
    event). Carries the faulting node id; the read planner fails over
    to the next-ranked replica, writers retry the flush."""

    def __init__(self, node_id: int, what: str) -> None:
        super().__init__(f"transient {what} fault on node {node_id}")
        self.node_id = node_id


class TransientReadError(TransientFault):
    def __init__(self, node_id: int) -> None:
        super().__init__(node_id, "read")


class TransientFlushError(TransientFault):
    def __init__(self, node_id: int) -> None:
        super().__init__(node_id, "flush")


class CorruptRunError(RuntimeError):
    """A flushed run failed its crc32 verification before merging."""


class DeadlineExceeded(RuntimeError):
    """A read's latency budget (``deadline_s``) was spent before the
    answer was complete. Raised instead of continuing to scan, retry or
    digest-read past the budget: a request that cannot answer in time
    is shed *explicitly*, never served silently slow. Deliberately not
    a :class:`TransientFault` — failover must not swallow it."""

    def __init__(self, budget_s: float | None = None) -> None:
        what = (
            "read deadline budget spent before the answer completed"
            if budget_s is None
            else f"read deadline budget of {budget_s * 1e3:.3f} ms spent "
            "before the answer completed"
        )
        super().__init__(what)
        self.budget_s = budget_s


#: Registry counters every engine registers at construction, in the
#: names the ``stats`` dict view exposes them under. The counter-
#: coverage audit (tests/test_obs.py) walks this inventory against
#: ``HREngine.metrics.catalog()`` — a counter added to the engine but
#: not listed here (or vice versa) fails the audit.
ENGINE_COUNTERS = (
    "result_cache_hits",
    "result_cache_misses",
    "commitlog_auto_checkpoints",
    "memtable_flushes",
    "compactions",
    "partition_splits",
    "partition_merges",
    "rebalance_rows_moved",
    "empty_partition_skips",
    "hints_queued",
    "hint_replays",
    "hint_rows_replayed",
    "hint_fallbacks",
    "digest_mismatches",
    "read_repairs",
    "read_retries",
    "scrub_checks",
    "scrub_repairs",
    "deadline_exceeded",
    "read_faults",
    "flush_faults",
    "corrupt_runs",
    "flush_wall_seconds",
    "view_hits",
    "view_boundary_rows",
    "view_rebuilds",
    "select_sparse_launches",
    "select_full_launches",
)

#: Typed refusal/fault → the registry counter that records it. Every
#: exception type the engine raises (or survives via failover) must
#: appear here; the audit test raises each one and asserts its counter
#: moved.
FAULT_COUNTERS: dict[str, str] = {
    "DeadlineExceeded": "deadline_exceeded",
    "TransientReadError": "read_faults",
    "TransientFlushError": "flush_faults",
    "CorruptRunError": "corrupt_runs",
}

#: Repair paths named by the PR-9 audit satellite: each must increment
#: its registry counter whenever the path runs.
REPAIR_COUNTERS = (
    "hint_replays",
    "hint_fallbacks",
    "read_repairs",
    "scrub_repairs",
)

#: Materialized per-slab aggregate views (PR-10): queries the view path
#: answered, window-edge rows its boundary rescan touched, and full
#: partial rebuilds (create excluded; compaction / migration / recovery
#: / scrub heal included). Audited like the repair inventory — each
#: name must resolve in the registry catalog and move when its path
#: runs.
VIEW_COUNTERS = (
    "view_hits",
    "view_boundary_rows",
    "view_rebuilds",
)

#: Device select compaction launches, by grid: ``sparse`` read
#: only the row blocks holding each query's slab window (one sorted
#: run, narrow slabs), ``full`` streamed every row block (appended
#: write runs, or slabs covering much of the table). Audited like
#: ``VIEW_COUNTERS``.
SELECT_COUNTERS = (
    "select_sparse_launches",
    "select_full_launches",
)


def _deadline_at(deadline_s: float | None) -> float | None:
    """Absolute ``perf_counter`` cutoff for a per-call latency budget
    (None = unbounded). A zero/negative budget yields an already-spent
    cutoff, so the caller sheds before doing any work."""
    if deadline_s is None:
        return None
    return time.perf_counter() + deadline_s


def _deadline_spent(deadline_at: float | None) -> bool:
    return deadline_at is not None and time.perf_counter() >= deadline_at


@dataclasses.dataclass
class Node:
    node_id: int
    alive: bool = True
    slowdown: float = 1.0  # >1 = straggler (ft.straggler injects this)
    tables: dict[tuple[str, int], SortedTable] = dataclasses.field(default_factory=dict)
    # injected fault budgets (chaos harness): each >0 count makes the
    # next scan / flush on this node raise a TransientFault, modeling a
    # slow-failing or flapping node rather than a clean death
    read_fault_budget: int = 0
    flush_fault_budget: int = 0

    def bytes_stored(self) -> int:
        total = 0
        for t in self.tables.values():
            total += t.packed.nbytes
            total += sum(c.nbytes for c in t.key_cols.values())
            total += sum(np.asarray(c).nbytes for c in t.value_cols.values())
        return total


@dataclasses.dataclass
class ColumnFamily:
    """One keyspace: a token ring over the canonical packed key space
    and one :class:`repro.core.ring.Partition` per ring range, each
    holding a full heterogeneous replica set of that range's rows with
    its own commit log, memtables, compaction policy and round-robin
    counter. ``slot_layouts`` (the HRCA/TR/explicit choice) is shared
    by every partition — a partition's slot-``s`` replica always
    serializes in ``slot_layouts[s]``, under global replica id
    ``vnode_id * RF + s`` (``vnode_id`` is the partition's stable
    virtual-node identity; equal to its ring position until the first
    split/merge/rebalance renumbers the ring). ``stats`` and the cost
    model keep the CF-global selectivities (the P = 1 planner's view
    and the rebuild fallback); partitioned planning ranks each
    partition's replicas with ``Partition.stats`` — that slice's own
    selectivities.

    ``replicas``/``commitlog``/``memtables``/``compaction``/
    ``rr_counter`` are flat compatibility views (the single-partition
    forms every pre-ring caller used); code that routes per partition
    goes through ``partitions`` directly."""

    name: str
    schema: KeySchema
    key_names: tuple[str, ...]
    value_names: tuple[str, ...]
    slot_layouts: tuple[tuple[str, ...], ...]
    ring: TokenRing
    partitions: list[Partition]
    stats: TableStats
    cost_model: CostModel
    hrca_result: HRCAResult | None = None
    # replica tables held as device-resident jax arrays: reads route
    # through the batched Pallas scan, and every table produced by the
    # write/recovery paths is re-placed on device
    device_resident: bool = False
    # group-commit staging threshold (0 = write-through: every write
    # flushes); the per-partition durable state lives on ``partitions``
    memtable_rows: int = 0
    # materialized per-slab aggregate views (storage.views): every
    # replica table carries per-block partial sums in its own sort
    # order; view-eligible aggregates are served O(blocks touched) and
    # the Cost Evaluator caps their row estimate at VIEW_ROWS_CAP.
    # Requires device_resident
    views: bool = False
    # observed-token histogram (P > 1 only): fed by CREATE and every
    # write, read by the rebalance drift trigger and the histogram
    # boundary proposal
    token_hist: TokenHistogram | None = None
    # next unused virtual-node id — vnode ids are never reused, so
    # migrated partitions' replica ids can never collide with live ones
    next_vnode: int = 0

    @property
    def replication_factor(self) -> int:
        return len(self.slot_layouts)

    @property
    def replicas(self) -> list[ReplicaHandle]:
        """All replica handles, flat in global-replica-id order
        (partition-major, so index == ``replica_id``)."""
        return [r for part in self.partitions for r in part.replicas]

    @property
    def commitlog(self) -> CommitLog | None:
        """Partition 0's log — THE column-family log when P == 1."""
        return self.partitions[0].commitlog

    @property
    def memtables(self) -> dict[int, Memtable]:
        """Flat ``replica_id → Memtable`` view across partitions (read
        the partitions directly to mutate)."""
        return {
            rid: mt for part in self.partitions for rid, mt in part.memtables.items()
        }

    @property
    def compaction(self) -> CompactionPolicy | None:
        return self.partitions[0].compaction

    @property
    def rr_counter(self) -> "itertools.count":
        return self.partitions[0].rr_counter

    @rr_counter.setter
    def rr_counter(self, counter: "itertools.count") -> None:
        self.partitions[0].rr_counter = counter


@dataclasses.dataclass
class ReadReport:
    replica_id: int
    node_id: int
    estimated_rows: float
    estimated_cost: float
    wall_seconds: float  # measured scan time × node slowdown
    rows_scanned: int
    hedged: bool = False


_Ranked = tuple[float, float, ReplicaHandle]  # (est_cost, est_rows, handle)


def _tie_threshold(best_cost: float) -> float:
    """Cost at or under which a replica counts as tied with the best.
    Shared by ``read`` and ``read_many`` — batched/sequential routing
    parity depends on both using the identical float expression. The
    margin is on |cost| so the threshold is ≥ best_cost even when a
    fitted cost function goes negative (negative intercept): the tie
    set always contains the cheapest replica."""
    return best_cost + abs(best_cost) * 1e-9 + 1e-12


def _schedule_picks(cost_mat: np.ndarray, counter) -> tuple[np.ndarray, np.ndarray]:
    """Request Scheduler core, shared by the single-partition and
    partitioned planners (one copy, so their routing semantics cannot
    drift): per query (column of the ``(replicas, queries)`` cost
    matrix) the within-tolerance ties are exactly the first tie_count
    entries of the column's stable ascending order — the same tie list
    a scalar ``read`` builds — and one round-robin draw is consumed per
    query in batch order. Returns ``(order, picks)``: the stable cost
    order and the picked replica row per query."""
    order = np.argsort(cost_mat, axis=0, kind="stable")  # (R, Q)
    sorted_costs = np.take_along_axis(cost_mat, order, axis=0)
    thresh = _tie_threshold(sorted_costs[0])  # elementwise over queries
    tie_counts = (sorted_costs <= thresh[None, :]).sum(axis=0)
    n_q = cost_mat.shape[1]
    draws = np.fromiter(
        (next(counter) for _ in range(n_q)), dtype=np.int64, count=n_q
    )
    return order, order[draws % tie_counts, np.arange(n_q)]


def _group_by_pick(picks: np.ndarray, qidx: list[int]) -> dict[int, list[int]]:
    """Group global query indices (``qidx[j]`` is column ``j``'s) by
    their picked replica row; one batched scan serves each group."""
    groups: dict[int, list[int]] = {}
    for j, qi in enumerate(qidx):
        groups.setdefault(int(picks[j]), []).append(qi)
    return groups


def _result_digest(
    scan: ScanResult,
    table: SortedTable,
    key_names: tuple[str, ...],
    schema: KeySchema,
) -> int:
    """Layout-independent digest of a ``ScanResult`` — what QUORUM/ALL
    reads compare across replicas (the digest-read half of Cassandra's
    read path). crc32 over:

    * ``rows_matched`` (int64) — exact and identical across layouts;
    * the aggregate value quantized to float32 — sum totals differ
      across serializations only by float summation order (~1e-15
      relative), far below float32 resolution, while a corrupted
      exponent/high bit shifts the total by orders of magnitude;
    * for selects, the *canonical* packed keys of the selected rows,
      sorted — each replica reports its own serialization order, but
      the selected row set (and hence its sorted canonical key multiset)
      is layout-independent.

    ``rows_scanned`` is deliberately excluded: it is a property of the
    serving layout, not of the answer.
    """
    h = zlib.crc32(np.int64(scan.rows_matched).tobytes())
    with np.errstate(over="ignore"):  # corrupt totals may exceed float32
        h = zlib.crc32(np.float32(scan.value).tobytes(), h)
    if scan.selected is not None and np.asarray(scan.selected).size:
        sel = np.asarray(scan.selected)
        keys = pack_columns(
            {c: table.key_cols[c][sel] for c in key_names}, key_names, schema
        )
        h = zlib.crc32(np.ascontiguousarray(np.sort(keys)), h)
    return h


class HREngine:
    """Simulated-cluster HR engine (Request Agency facade).

    ``result_cache`` (default on) keeps a per-replica map
    ``(agg, value col, filter signature) → ScanResult`` fed by both
    ``read`` and ``read_many``. The packed slab bounds are a pure
    function of (filters, layout, schema) and each replica has its own
    map, so the filter signature alone identifies the slab — keying on
    it avoids re-running the ``slab_bounds_many`` walk just to build
    keys. Writes and node recovery invalidate the affected replicas'
    entries, each per-replica map is bounded in entries
    (``result_cache_max_entries``, FIFO eviction) and in retained
    select-index bytes, and hit/miss counters are exposed on
    :attr:`stats`.
    """

    def __init__(
        self,
        n_nodes: int = 6,
        *,
        result_cache: bool = True,
        result_cache_max_entries: int = 4096,
        parallel_writes: bool = False,
        memtable_rows: int = 0,
        compaction: CompactionPolicy | None = None,
        commitlog_checkpoint_records: int = 256,
        rebalance_imbalance: float = 0.0,
        failure_detector=None,
        checksums: bool = True,
        read_retry_limit: int | None = None,
        metrics: MetricsRegistry | None = None,
        scan_timer=None,
    ) -> None:
        if n_nodes < 1:
            raise ValueError("need at least one node")
        if result_cache and result_cache_max_entries < 1:
            raise ValueError(
                "result_cache_max_entries must be >= 1; pass "
                "result_cache=False to disable caching"
            )
        if memtable_rows < 0:
            raise ValueError("memtable_rows must be >= 0 (0 = write-through)")
        if commitlog_checkpoint_records < 0:
            raise ValueError(
                "commitlog_checkpoint_records must be >= 0 (0 = no "
                "automatic checkpointing)"
            )
        self.nodes = [Node(node_id=i) for i in range(n_nodes)]
        self.column_families: dict[str, ColumnFamily] = {}
        self._cache_enabled = result_cache
        self._cache_max = result_cache_max_entries
        # operational counters live on the metrics registry (repro.obs);
        # the legacy ``stats`` dict is a read-through view and
        # ``reset_stats()`` is one registry reset. The handles bound
        # below keep the hot-path cost at one attribute load + float add.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        for _name in ENGINE_COUNTERS:
            self.metrics.counter(_name)
        self._cache_hits = self.metrics.counter("result_cache_hits")
        self._cache_misses = self.metrics.counter("result_cache_misses")
        self._result_cache: dict[tuple[str, int], dict] = {}
        # running total of selected-array bytes per replica map, so the
        # byte budget doesn't rescan the map on every store
        self._cache_sel_bytes: dict[tuple[str, int], int] = {}
        self.parallel_writes = parallel_writes
        # write-path defaults inherited by create_column_family
        self.memtable_rows = memtable_rows
        self.compaction = compaction if compaction is not None else CompactionPolicy()
        # auto-checkpoint trigger: collapse a partition's commit log
        # after a flush once more than this many records accumulated
        # since its last snapshot (0 disables; checkpoint_commitlog
        # stays as the manual form)
        self.commitlog_checkpoint_records = commitlog_checkpoint_records
        # skew-drift auto-rebalance: after a write-path flush, any P > 1
        # column family whose token histogram puts more than
        # ``rebalance_imbalance`` × the mean row mass in one partition
        # is rebalanced in place (0 disables; ``rebalance`` stays as
        # the manual form)
        if rebalance_imbalance < 0:
            raise ValueError("rebalance_imbalance must be >= 0 (0 = manual only)")
        self.rebalance_imbalance = rebalance_imbalance
        # availability layer: optional accrual failure detector (duck-
        # typed — record/record_failure/cost_factor; see ft.detector),
        # content checksums on installed tables (scrub's witness), and
        # the failover bound for transient read faults (None = one
        # attempt per live replica)
        self.failure_detector = failure_detector
        self.checksums = bool(checksums)
        # scan-wall clock: the walls fed to the failure detector (and
        # attributed to ReadReports) come from this zero-arg callable.
        # ``time.perf_counter`` by default; a deterministic counter
        # (e.g. repro.obs.TickClock) makes detector state — and hence
        # replica routing — a pure function of the operation sequence,
        # which the chaos byte-identical-trace property requires
        self._scan_timer = scan_timer if scan_timer is not None else time.perf_counter
        # the limit counts ATTEMPTS (first try included), so anything
        # below 1 is nonsense: 0 used to slip through both retry loops
        # as "zero attempts allowed", turning the first transient fault
        # into an immediate unanswerable-query RuntimeError
        if read_retry_limit is not None and read_retry_limit < 1:
            raise ValueError(
                "read_retry_limit must be >= 1 (attempts, first try "
                "included; None = one attempt per live replica), got "
                f"{read_retry_limit}"
            )
        self.read_retry_limit = read_retry_limit
        self._hints_queued = self.metrics.counter("hints_queued")
        self._hint_replays = self.metrics.counter("hint_replays")
        self._hint_rows_replayed = self.metrics.counter("hint_rows_replayed")
        self._hint_fallbacks = self.metrics.counter("hint_fallbacks")
        self._digest_mismatches = self.metrics.counter("digest_mismatches")
        self._read_repairs = self.metrics.counter("read_repairs")
        self._read_retries = self.metrics.counter("read_retries")
        self._scrub_checks = self.metrics.counter("scrub_checks")
        self._scrub_repairs = self.metrics.counter("scrub_repairs")
        self._flushes = self.metrics.counter("memtable_flushes")
        self._compactions = self.metrics.counter("compactions")
        self._auto_checkpoints = self.metrics.counter("commitlog_auto_checkpoints")
        # migration observability (satellite counters)
        self._partition_splits = self.metrics.counter("partition_splits")
        self._partition_merges = self.metrics.counter("partition_merges")
        self._rebalance_rows_moved = self.metrics.counter("rebalance_rows_moved")
        self._empty_partition_skips = self.metrics.counter("empty_partition_skips")
        # typed refusals/faults (FAULT_COUNTERS): raised-or-survived
        # exceptions, each visible in the registry at the raise site
        self._deadline_exceeded = self.metrics.counter("deadline_exceeded")
        self._read_faults = self.metrics.counter("read_faults")
        self._flush_faults = self.metrics.counter("flush_faults")
        self._corrupt_runs = self.metrics.counter("corrupt_runs")
        # cumulative seconds spent in memtable flushes (incl. the ones
        # a read barrier triggers, which are write-path cost and NOT
        # attributed to any ReadReport.wall_seconds)
        self._flush_wall = self.metrics.counter("flush_wall_seconds")
        # materialized per-slab aggregate views: queries answered from
        # block partials, window-edge rows the boundary rescan touched,
        # and full view rebuilds (create / compaction / migration /
        # scrub heal — incremental flush extensions are NOT rebuilds)
        self._view_hits = self.metrics.counter("view_hits")
        self._view_boundary_rows = self.metrics.counter("view_boundary_rows")
        self._view_rebuilds = self.metrics.counter("view_rebuilds")
        # device select compactions: block-sparse and full-stream launches
        self._select_sparse = self.metrics.counter("select_sparse_launches")
        self._select_full = self.metrics.counter("select_full_launches")
        self._pool: ThreadPoolExecutor | None = None

    @property
    def _executor(self) -> ThreadPoolExecutor:
        """Shared flush thread pool, created lazily on first parallel
        flush — a per-flush pool's startup cost would eat into the
        overlap ``benchmarks/write_queue.py`` measures."""
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=8)
        return self._pool

    def __getstate__(self) -> dict:
        # thread pools hold locks/threads and cannot be (deep)copied or
        # pickled; drop it — it is recreated lazily on first use
        state = self.__dict__.copy()
        state["_pool"] = None
        return state

    # -- result cache --------------------------------------------------------

    @property
    def stats(self) -> dict:
        """Operational counters: per-replica read result cache plus the
        durable write path (log records/rows, currently staged rows,
        memtable flushes and automatic compactions).

        A read-through view: counter-backed keys come from
        :attr:`metrics` (see ``ENGINE_COUNTERS``), structural keys
        (log records, staged rows, open hints, cache occupancy) are
        computed live from the storage structures they describe —
        they are state, not events, so ``reset_stats`` leaves them."""
        parts = [p for cf in self.column_families.values() for p in cf.partitions]
        return {
            "result_cache_hits": int(self._cache_hits.value),
            "result_cache_misses": int(self._cache_misses.value),
            "result_cache_entries": sum(
                len(c) for c in self._result_cache.values()
            ),
            "result_cache_select_bytes": sum(self._cache_sel_bytes.values()),
            "partitions": len(parts),
            "commitlog_records": sum(
                len(p.commitlog) for p in parts if p.commitlog is not None
            ),
            "commitlog_rows": sum(
                p.commitlog.n_rows for p in parts if p.commitlog is not None
            ),
            "commitlog_auto_checkpoints": int(self._auto_checkpoints.value),
            "staged_rows": sum(
                mt.n_staged for p in parts for mt in p.memtables.values()
            ),
            "memtable_flushes": int(self._flushes.value),
            "compactions": int(self._compactions.value),
            # ring-migration observability: boundary insertions/removals
            # and the rows whose partition ownership a migration rebuilt
            "partition_splits": int(self._partition_splits.value),
            "partition_merges": int(self._partition_merges.value),
            "rebalance_rows_moved": int(self._rebalance_rows_moved.value),
            # (partition, query) launches the scatter path skipped
            # because the partition provably held no rows in the slab
            "empty_partition_skips": int(self._empty_partition_skips.value),
            # availability layer: writes that accrued a hint for a
            # transiently-down replica; node-up heals served from the
            # hinted tail vs. full-rebuild fallbacks; digest reads;
            # failover retries; scrub activity
            "hints_open": sum(len(p.hints) for p in parts),
            "hints_queued": int(self._hints_queued.value),
            "hint_replays": int(self._hint_replays.value),
            "hint_rows_replayed": int(self._hint_rows_replayed.value),
            "hint_fallbacks": int(self._hint_fallbacks.value),
            "digest_mismatches": int(self._digest_mismatches.value),
            "read_repairs": int(self._read_repairs.value),
            "read_retries": int(self._read_retries.value),
            "scrub_checks": int(self._scrub_checks.value),
            "scrub_repairs": int(self._scrub_repairs.value),
            # typed refusals and faults survived via failover
            # (FAULT_COUNTERS)
            "deadline_exceeded": int(self._deadline_exceeded.value),
            "read_faults": int(self._read_faults.value),
            "flush_faults": int(self._flush_faults.value),
            "corrupt_runs": int(self._corrupt_runs.value),
            # cumulative wall of ALL flushes. Flushes inside write()
            # (write-through or threshold-crossing) also count toward
            # that write's returned wall — don't sum the two. The
            # counter exists because read-barrier flushes appear in
            # neither write()'s return nor any ReadReport.wall_seconds;
            # here is the only place that time is visible
            "flush_wall_seconds": self._flush_wall.value,
            # materialized aggregate views: view-routed answers, edge
            # rows the boundary rescan touched, full rebuilds
            "view_hits": int(self._view_hits.value),
            "view_boundary_rows": int(self._view_boundary_rows.value),
            "view_rebuilds": int(self._view_rebuilds.value),
            # device select compaction launches, by grid
            "select_sparse_launches": int(self._select_sparse.value),
            "select_full_launches": int(self._select_full.value),
        }

    def reset_stats(self) -> None:
        """Zero every registry-backed counter in place (benchmarks used
        to re-construct engines just to get clean counters). Structural
        ``stats`` keys — log records, staged rows, open hints, cache
        occupancy — describe live state and are untouched."""
        self.metrics.reset()

    def _check_deadline(
        self, deadline_at: float | None, budget_s: float | None
    ) -> None:
        """Raise :class:`DeadlineExceeded` once the budget is spent.
        Called before each unit of *required* work (a replica-group
        scan, a failover retry, a digest read); optional work (hedges)
        is skipped instead of raising — the primary answer stands.
        Every raise is visible as the ``deadline_exceeded`` counter."""
        if deadline_at is not None and time.perf_counter() >= deadline_at:
            self._deadline_exceeded.inc()
            raise DeadlineExceeded(budget_s)

    @staticmethod
    def _cache_keys(queries: list[Query]) -> list:
        """One key per query: aggregation + value column + filter
        signature. The cache is per-replica, so layout (and with it the
        packed slab bounds, a pure function of the filters) is implicit
        — no bounds walk on the hot path just to build keys."""
        return [
            (q.agg, q.value_col, tuple(sorted(q.filters.items())))
            for q in queries
        ]

    # a select's cached index array may be arbitrarily large; entries
    # past the per-entry byte size are served but never cached, and each
    # replica map evicts FIFO until its retained selected-array bytes
    # fit the map budget — so worst-case memory is bounded per replica
    # by min(max_entries × entry cap, map budget), not by table size
    _CACHE_MAX_SELECT_BYTES = 1 << 20
    _CACHE_MAX_MAP_BYTES = 64 << 20

    def _cache_store(self, map_key, cache: dict, key, result: ScanResult) -> None:
        """Cache hits hand out the same ScanResult object, so a select's
        index array is frozen on the way in — a caller mutating it would
        otherwise corrupt every later hit. Each per-replica map is
        bounded in entries (``result_cache_max_entries``, FIFO) and in
        selected-array bytes: workloads of all-distinct (select)
        queries must not grow memory without bound."""
        nb = 0 if result.selected is None else int(result.selected.nbytes)
        if nb > self._CACHE_MAX_SELECT_BYTES or nb > self._CACHE_MAX_MAP_BYTES:
            # uncacheable either way: over the per-entry cap, or (only
            # reachable when the budgets are tuned so a single entry can
            # exceed the whole map budget) it would leave the map over
            # budget even after the eviction loop emptied it
            return
        if result.selected is not None:
            result.selected.setflags(write=False)
        total = self._cache_sel_bytes.get(map_key, 0)
        old = cache.pop(key, None)
        if old is not None and old.selected is not None:
            total -= old.selected.nbytes
        while cache and (
            len(cache) >= self._cache_max
            or total + nb > self._CACHE_MAX_MAP_BYTES
        ):
            evicted = cache.pop(next(iter(cache)))
            if evicted.selected is not None:
                total -= evicted.selected.nbytes
        cache[key] = result
        self._cache_sel_bytes[map_key] = total + nb

    def _invalidate_result_cache(
        self,
        cf_name: str,
        node_id: int | None = None,
        replica_id: int | None = None,
    ) -> None:
        cf = self.column_families[cf_name]
        for r in cf.replicas:
            if node_id is not None and r.node_id != node_id:
                continue
            if replica_id is not None and r.replica_id != replica_id:
                continue
            self._result_cache.pop((cf_name, r.replica_id), None)
            self._cache_sel_bytes.pop((cf_name, r.replica_id), None)

    # -- Replica Generator ---------------------------------------------------

    def _place(self, replica_id: int, cf_name: str) -> int:
        """Replica placement hash(replica_id, cf) → node. Successive
        replicas land on distinct nodes when possible (Cassandra ring);
        with global replica ids (``partition_id * RF + slot``)
        successive partitions stagger around the node ring too.

        Delegates to ``repro.core.ring.place_replica`` — crc32, not
        ``hash``: the builtin is salted per process (PYTHONHASHSEED),
        which made placement — and every benchmark downstream of it —
        differ between runs.
        """
        return place_replica(cf_name, replica_id, len(self.nodes))

    def create_column_family(
        self,
        name: str,
        key_cols: Mapping[str, np.ndarray],
        value_cols: Mapping[str, np.ndarray],
        *,
        replication_factor: int = 3,
        mechanism: str = "HR",
        workload: Workload | None = None,
        schema: KeySchema | None = None,
        cost_fns: dict[int, LinearCostFunction] | None = None,
        hrca_kwargs: dict | None = None,
        layouts: Sequence[Sequence[str]] | None = None,
        device_resident: bool = False,
        views: bool = False,
        memtable_rows: int | None = None,
        compaction: CompactionPolicy | None = None,
        partitions: int = 1,
        partition_balance: str = "equal",
    ) -> ColumnFamily:
        """CREATE COLUMN FAMILY: choose replica structures, build tables.

        mechanism:
          "HR" — layouts from HRCA over ``workload`` (paper).
          "TR" — the single best expert layout, identical on all replicas
                 (the paper's baseline: "approximate optimal structure
                 that an expert can give"); exhaustive for ≤5 keys, else
                 single-replica annealing + greedy polish.
        Explicit ``layouts`` override both (tests / ablations).

        With ``device_resident=True`` every replica table is placed on
        device at creation: ``read``/``read_many`` then answer sum,
        count and select queries with the fused locate+scan Pallas
        launch instead of the numpy engine, writes *append* to the
        resident arrays (incremental placement — no re-upload), and
        recovery re-places rebuilt tables. Raises if the schema exceeds
        the device path's per-column two-lane budget.

        ``views=True`` (requires ``device_resident``) additionally
        materializes per-slab aggregate views on every replica table:
        per-block partial sums of the value tile in that replica's own
        sort order (``repro.core.storage.views``). View-eligible sum and
        count queries — slab filters forming a prefix of the layout —
        are then answered from the stored partials plus a rescan of at
        most the two window-edge blocks, O(blocks touched) instead of
        O(N), bit-identical to the fused full scan. Views extend
        incrementally at flush, rebuild at compaction and migration,
        and are treated as derived state everywhere else (scrub heals a
        corrupted view by rebuilding it from the resident arrays).

        ``memtable_rows`` (default: the engine's) is the group-commit
        staging threshold — 0 means write-through, every ``write``
        flushes. ``compaction`` (default: the engine's policy) bounds
        the device run stack; pass an explicit ``CompactionPolicy`` to
        tune its thresholds. The CREATE-time dataset is committed as
        record 0 of the column family's shared commit log, so replaying
        the log alone rebuilds any replica.

        ``partitions`` splits the keyspace Cassandra-style: a token
        ring over the canonical packed key range assigns every row to
        one of ``P`` contiguous token ranges, each owning a full
        heterogeneous replica set of just its rows, its own commit log,
        memtables and compaction policy (``repro.core.ring``). Reads
        scatter over the partitions a query's slab can touch and gather
        partial aggregates on the host; writes route rows to the owning
        partitions' logs. ``partitions=1`` (default) is bit-identical
        to the unpartitioned engine.

        ``partition_balance`` picks the initial boundaries: ``"equal"``
        (default) splits the key *space* evenly — the historical,
        skew-oblivious form; ``"tokens"`` places the boundaries at
        exact quantiles of the CREATE dataset's observed tokens, so a
        Zipf-skewed keyspace starts balanced in *rows* instead
        (``TokenRing.from_tokens``; ``rebalance`` applies the same
        boundaries to a live column family). Either way each partition
        carries its own ``TableStats`` (P > 1) and the planner ranks
        its replicas with that partition's selectivities; with
        ``mechanism="HR"`` the HRCA search itself optimizes the
        row-fraction-weighted blend of per-partition cost models.
        """
        if name in self.column_families:
            raise ValueError(f"column family {name!r} exists")
        if views and not device_resident:
            raise ValueError(
                "views=True requires device_resident=True (views are "
                "per-block partials of the resident value tile)"
            )
        if schema is None:
            schema = KeySchema.for_columns(key_cols)
        key_names = tuple(key_cols)
        stats = TableStats.from_columns(key_cols, schema)
        model = CostModel(stats=stats, cost_fns=dict(cost_fns or {}))
        n = replication_factor
        hrca_result: HRCAResult | None = None

        # ring + per-partition stats come BEFORE the layout choice: the
        # HR search over a partitioned CF optimizes against each
        # partition's own selectivities, not the CF-global blend
        value_names = tuple(value_cols)
        policy = compaction if compaction is not None else self.compaction
        tokens = token_hist = None
        part_stats: list[TableStats | None]
        if partitions == 1:
            ring = TokenRing.build(schema, key_names, 1)
            owner_masks: list = [None]  # whole dataset, no slicing copies
            part_stats = [None]
        else:
            kc_arr = {c: np.asarray(key_cols[c]) for c in key_names}
            tokens = pack_columns(kc_arr, key_names, schema)
            if partition_balance == "equal":
                ring = TokenRing.build(schema, key_names, partitions)
            elif partition_balance == "tokens":
                ring = TokenRing.from_tokens(schema, key_names, tokens, partitions)
            else:
                raise ValueError(
                    f"unknown partition_balance {partition_balance!r} "
                    "(expected 'equal' or 'tokens')"
                )
            pids = ring.partition_of_tokens(tokens)
            owner_masks = [pids == pid for pid in range(partitions)]
            part_stats = [
                TableStats.from_columns(
                    {c: kc_arr[c][mask] for c in key_names}, schema
                )
                for mask in owner_masks
            ]
            token_hist = TokenHistogram.build(ring.total_bits)
            token_hist.add_tokens(tokens, device=device_resident)

        if layouts is not None:
            chosen = tuple(tuple(a) for a in layouts)
            if len(chosen) != n:
                raise ValueError("len(layouts) != replication_factor")
        elif mechanism == "TR":
            if workload is None:
                chosen = tuple(tuple(key_names) for _ in range(n))
            else:
                best = self._expert_layout(model, workload, key_names)
                chosen = tuple(best for _ in range(n))
        elif mechanism == "HR":
            if workload is None:
                raise ValueError("HR mechanism needs a workload for HRCA")
            kw = dict(hrca_kwargs or {})
            if partitions == 1:
                hrca_model = model
            else:
                # per-partition models weighted by row fraction — the
                # shared layout set is optimized for what each
                # partition actually serves (see hrca._MemoCost)
                hrca_model = [
                    (
                        float(ps.n_rows),
                        CostModel(stats=ps, cost_fns=dict(cost_fns or {})),
                    )
                    for ps in part_stats
                ]
            hrca_result = hrca(hrca_model, workload, initial_state(key_names, n), **kw)
            chosen = hrca_result.layouts
        else:
            raise ValueError(f"unknown mechanism {mechanism!r}")

        parts: list[Partition] = []
        for pid, mask in enumerate(owner_masks):
            if mask is None:
                kc_p, vc_p = key_cols, value_cols
            else:
                kc_p = {c: np.asarray(key_cols[c])[mask] for c in key_names}
                vc_p = {c: np.asarray(value_cols[c])[mask] for c in value_names}
            handles: list[ReplicaHandle] = []
            memtables: dict[int, Memtable] = {}
            part_digest: int | None = None  # layout-independent: one
            for slot, layout in enumerate(chosen):  # digest per partition
                rid = pid * n + slot
                table = SortedTable.from_columns(kc_p, vc_p, layout, schema)
                if device_resident:
                    table.place_on_device()
                    if views:
                        table.build_views()
                if self.checksums:
                    if part_digest is None:
                        part_digest = table.seal_checksum().stored_digest
                    else:
                        table.stored_digest = part_digest
                node_id = self._place(rid, name)
                self.nodes[node_id].tables[(name, rid)] = table
                handles.append(
                    ReplicaHandle(rid, tuple(layout), node_id, partition_id=pid)
                )
                memtables[rid] = Memtable(layout, schema, key_names, value_names)
            log = CommitLog(key_names=key_names, value_names=value_names)
            log.append(kc_p, vc_p)  # record 0: the rows this partition owns
            lo, hi = ring.token_range(pid)
            part = Partition(
                partition_id=pid,
                token_lo=lo,
                token_hi=hi,
                replicas=handles,
                commitlog=log,
                memtables=memtables,
                compaction=policy,
                vnode_id=pid,  # birth identity == ring position at CREATE
                stats=part_stats[pid],
                # every replica table holds exactly record 0 — complete
                # through the log's current tail
                flushed_lsn={r.replica_id: log.next_lsn for r in handles},
            )
            if tokens is not None:
                part.observe_tokens(tokens[owner_masks[pid]])
            parts.append(part)

        cf = ColumnFamily(
            name=name,
            schema=schema,
            key_names=key_names,
            value_names=value_names,
            slot_layouts=tuple(tuple(a) for a in chosen),
            ring=ring,
            partitions=parts,
            stats=stats,
            cost_model=model,
            hrca_result=hrca_result,
            device_resident=device_resident,
            views=views,
            memtable_rows=(
                self.memtable_rows if memtable_rows is None else memtable_rows
            ),
            token_hist=token_hist,
            next_vnode=partitions,
        )
        self.column_families[name] = cf
        return cf

    @staticmethod
    def _expert_layout(
        model: CostModel, workload: Workload, key_names: tuple[str, ...]
    ) -> tuple[str, ...]:
        if len(key_names) <= 5:
            state, _ = exhaustive_search(model, workload, key_names, 1)
            return state[0]
        res = hrca(
            model, workload, initial_state(key_names, 1), greedy_descent=True, k_max=2000
        )
        return res.layouts[0]

    # -- Cost Evaluator / Request Scheduler -----------------------------------

    def _table(self, cf: ColumnFamily, r: ReplicaHandle) -> SortedTable:
        return self.nodes[r.node_id].tables[(cf.name, r.replica_id)]

    def _ranked_replicas(self, cf: ColumnFamily, query: Query) -> list[_Ranked]:
        """Replicas on live nodes ranked by estimated cost (Eq 2–3),
        multiplied by the failure detector's per-node cost factor when
        one is attached (suspected nodes are down-ranked, not excluded)."""
        det = self.failure_detector
        ranked: list[_Ranked] = []
        for r in cf.replicas:
            if not self.nodes[r.node_id].alive:
                continue
            rows = estimate_rows(cf.stats, r.layout, query)
            cost = cf.cost_model.cost_fn(len(r.layout))(rows)
            if (
                cf.views
                and query.agg in VIEW_AGGS
                and query_view_eligible(query, r.layout)
            ):
                # view term (Eq 1–2 refined): a view-eligible aggregate
                # touches at most the two window-edge blocks, so its
                # row estimate is capped — the planner learns that a
                # view hit beats a full scan regardless of selectivity
                cost = cf.cost_model.cost_fn(len(r.layout))(
                    min(rows, float(VIEW_ROWS_CAP))
                )
            if det is not None:
                cost *= det.cost_factor(r.node_id)
            ranked.append((cost, rows, r))
        if not ranked:
            raise RuntimeError(f"no live replica for {cf.name!r}")
        ranked.sort(key=lambda t: t[0])
        return ranked

    def _live_cost_factors(self, live: list[ReplicaHandle]) -> np.ndarray | None:
        """Per-live-replica detector cost factors (None when no detector
        is attached — the cost matrices then stay bit-identical to the
        detector-free engine)."""
        det = self.failure_detector
        if det is None:
            return None
        return np.array([det.cost_factor(r.node_id) for r in live], dtype=np.float64)

    def read(
        self,
        cf_name: str,
        query: Query,
        *,
        hedge: bool = False,
        hedge_ratio: float = 2.0,
        consistency: str = ONE,
        deadline_s: float | None = None,
        trace=None,
    ) -> tuple[ScanResult, ReadReport]:
        """Route to the cheapest live replica; ties broken round-robin
        (load balance). With ``hedge=True`` a read landing on a straggler
        node (slowdown > hedge_ratio) is duplicated on the next-cheapest
        replica on a *different* node; the faster copy wins.
        ``consistency`` beyond ``ONE`` adds digest reads on the next
        cost-ranked replicas with read repair on mismatch (module
        docstring, availability layer).

        ``deadline_s`` is a latency *budget* for this call: required
        work (the primary scan, failover retries, digest reads) checks
        the remaining budget before launching and raises
        :class:`DeadlineExceeded` once it is spent — the request is shed
        explicitly instead of served late; optional work (the hedge
        duplicate) is silently skipped when no budget remains. ``None``
        (default) is unbounded; a non-positive budget sheds before any
        scan.

        The common case (single partition, ``consistency=ONE``) runs a
        scalar fast path: one ``_ranked_replicas`` pass instead of the
        batched planner's full cost/order matrices — same costs, same
        tie rule, same RR counter, so routing stays identical to
        ``read_many`` at Q = 1 (parity-tested) at a fraction of the
        per-call planning cost. Partitioned CFs and higher consistency
        levels delegate to the batched planner at Q = 1.

        ``trace`` (an open :class:`repro.obs.Span`, or None) hangs this
        call's span subtree under the caller's — ``engine.read`` for
        the scalar fast path (see the taxonomy in
        :mod:`repro.obs.trace`). Tracing disabled (None) costs one
        ``is None`` test per stage.
        """
        if consistency not in CONSISTENCY_LEVELS:
            raise ValueError(
                f"consistency must be one of {CONSISTENCY_LEVELS}, "
                f"got {consistency!r}"
            )
        cf = self.column_families[cf_name]
        if cf.ring.n_partitions > 1 or consistency != ONE:
            return self.read_many(
                cf_name,
                [query],
                hedge=hedge,
                hedge_ratio=hedge_ratio,
                consistency=consistency,
                deadline_s=deadline_s,
                trace=trace,
            )[0]
        deadline = _deadline_at(deadline_s)
        self._check_deadline(deadline, deadline_s)
        span = (
            trace.child("engine.read", cf=cf_name, level=consistency)
            if trace is not None
            else None
        )
        try:
            ranked = self._ranked_replicas(cf, query)
            best_cost = ranked[0][0]
            ties = [t for t in ranked if t[0] <= _tie_threshold(best_cost)]
            entry = ties[next(cf.rr_counter) % len(ties)]

            # same failover semantics as _run_groups: a transient fault
            # advances to the next-ranked untried replica, bounded by the
            # live count (or read_retry_limit)
            limit = len(ranked) if self.read_retry_limit is None else self.read_retry_limit
            tried: set[int] = set()
            while True:
                tried.add(entry[2].replica_id)
                try:
                    result, report = self._execute_scalar(
                        cf, entry, query, hedged=False, trace=span,
                        retry=bool(tried - {entry[2].replica_id}),
                    )
                    break
                except TransientFault:
                    self._read_retries.inc()
                    self._check_deadline(deadline, deadline_s)
                    entry = next(
                        (t for t in ranked if t[2].replica_id not in tried), None
                    )
                    if entry is None or len(tried) >= limit:
                        raise RuntimeError(
                            f"no live replica answered query 0 of {cf.name!r} "
                            f"after {len(tried)} attempts"
                        ) from None

            if (
                hedge
                and len(ranked) > 1
                and self.nodes[report.node_id].slowdown > hedge_ratio
                and not _deadline_spent(deadline)  # hedging is optional work
            ):
                alt = next(
                    (t for t in ranked if t[2].node_id != report.node_id), None
                )
                if alt is not None:
                    try:
                        r2, rep2 = self._execute_scalar(
                            cf, alt, query, hedged=True, trace=span
                        )
                    except TransientFault:
                        pass  # best-effort duplicate; the primary stands
                    else:
                        # ties go to the hedge — cache hits serve at zero
                        # attributed wall on both sides (see _execute_group)
                        if rep2.wall_seconds <= report.wall_seconds:
                            return r2, rep2
            return result, report
        finally:
            if span is not None:
                span.end()

    def _execute_scalar(
        self, cf: ColumnFamily, entry: _Ranked, query: Query, *,
        hedged: bool, trace=None, retry: bool = False,
    ) -> tuple[ScanResult, ReadReport]:
        """Execute one query on one replica through the shared
        cache/fault/detector path (``_scan_with_cache``)."""
        est_cost, est_rows, r = entry
        g = (
            trace.child(
                "engine.group_scan", replica=r.replica_id, node=r.node_id,
                queries=1, hedged=hedged, retry=retry,
            )
            if trace is not None
            else None
        )
        try:
            scans, walls = self._scan_with_cache(cf, r, [query], trace=g)
        except TransientFault as e:
            if g is not None:
                g.end(error=type(e).__name__)
            raise
        if g is not None:
            g.end(rows=int(scans[0].rows_scanned))
        return scans[0], ReadReport(
            replica_id=r.replica_id,
            node_id=r.node_id,
            estimated_rows=est_rows,
            estimated_cost=est_cost,
            wall_seconds=walls[0],
            rows_scanned=scans[0].rows_scanned,
            hedged=hedged,
        )

    def read_many(
        self,
        cf_name: str,
        queries: Sequence[Query],
        *,
        hedge: bool = False,
        hedge_ratio: float = 2.0,
        consistency: str = ONE,
        deadline_s: float | None = None,
        trace=None,
    ) -> list[tuple[ScanResult, ReadReport]]:
        """Batched ``read``: one scheduler pass and one grouped storage
        scan for the whole batch (see module docstring for semantics).

        Returns per-query ``(ScanResult, ReadReport)`` in batch order;
        results and routing decisions are identical to calling ``read``
        on each query in sequence. ``consistency="QUORUM"``/``"ALL"``
        additionally executes every query on the next cost-ranked
        replicas up to the level's k, compares layout-independent result
        digests and repairs divergent replicas from the commit log
        (read repair); the returned result is always the digest-majority
        answer. ``deadline_s`` bounds the whole batch's latency budget:
        required work (replica-group scans, failover retries, digest
        reads) raises :class:`DeadlineExceeded` once the budget is
        spent, while optional work (hedge duplicates) is silently
        skipped — the call either answers within budget or fails
        loudly, never silently slow.

        ``trace`` (an open :class:`repro.obs.Span`, or None) hangs an
        ``engine.read_many`` subtree — planning, per-(partition,
        replica) group scans down to the kernel launches, digest pass,
        gather — under the caller's span; see the stage taxonomy in
        :mod:`repro.obs.trace`. ``None`` (default) keeps the hot path
        untraced at the cost of one ``is None`` test per stage.
        """
        if consistency not in CONSISTENCY_LEVELS:
            raise ValueError(
                f"unknown consistency {consistency!r} "
                f"(expected one of {CONSISTENCY_LEVELS})"
            )
        cf = self.column_families[cf_name]
        queries = list(queries)
        if not queries:
            return []
        deadline = _deadline_at(deadline_s)
        self._check_deadline(deadline, deadline_s)
        span = (
            trace.child(
                "engine.read_many", cf=cf_name, queries=len(queries),
                level=consistency,
            )
            if trace is not None
            else None
        )
        try:
            if cf.ring.n_partitions > 1:
                return self._read_many_partitioned(
                    cf,
                    queries,
                    hedge=hedge,
                    hedge_ratio=hedge_ratio,
                    consistency=consistency,
                    deadline_at=deadline,
                    budget_s=deadline_s,
                    trace=span,
                )
            live = [r for r in cf.replicas if self.nodes[r.node_id].alive]
            if not live:
                raise RuntimeError(f"no live replica for {cf_name!r}")
            n_q = len(queries)

            # vectorized Cost Evaluator: Eq (1)-(2) over all (replica,
            # query); per-column selectivities are extracted once and
            # shared by all replica layouts
            plan = span.child("engine.plan") if span is not None else None
            pre = precompute_query_stats(cf.stats, queries, cf.key_names)
            rows_mat = np.stack(
                [estimate_rows_many(cf.stats, r.layout, queries, pre) for r in live]
            )
            cost_mat = np.stack(
                [
                    cf.cost_model.cost_fn(len(r.layout)).many(rows_mat[k])
                    for k, r in enumerate(live)
                ]
            )
            # view term: cap eligible (replica, query) row estimates.
            # The any() guard doubles as the all-select fast path — a
            # batch with no sum/count never walks the eligibility arrays
            if cf.views and any(q.agg in VIEW_AGGS for q in queries):
                elig = view_eligible_matrix([r.layout for r in live], queries)
                if elig.any():
                    capped = np.minimum(rows_mat, float(VIEW_ROWS_CAP))
                    cost_mat = np.where(
                        elig,
                        np.stack(
                            [
                                cf.cost_model.cost_fn(len(r.layout)).many(
                                    capped[k]
                                )
                                for k, r in enumerate(live)
                            ]
                        ),
                        cost_mat,
                    )
            factors = self._live_cost_factors(live)
            if factors is not None:
                cost_mat = cost_mat * factors[:, None]

            # Request Scheduler: per-query cheapest replica, RR tie-break
            # (one draw per query in batch order, so a batch matches a
            # sequential read loop); then one batched scan per chosen group,
            # with bounded failover onto the next-ranked replica when a scan
            # raises a transient fault
            order_mat, picks = _schedule_picks(cost_mat, cf.rr_counter)
            if plan is not None:
                plan.end(replicas=len(live))
            all_q = list(range(n_q))
            results: list[ScanResult | None] = [None] * n_q
            reports: list[ReadReport | None] = [None] * n_q
            self._run_groups(
                cf, live, order_mat, picks, all_q, queries, rows_mat, cost_mat,
                results, reports, deadline_at=deadline, budget_s=deadline_s,
                trace=span,
            )

            if hedge and len(live) > 1 and not _deadline_spent(deadline):
                # duplicate straggler-bound queries onto the next-cheapest
                # replica on a different node (same alternate ``read`` picks);
                # hedges are best-effort duplicates — a faulting hedge is
                # dropped, never failed over (the primary result stands)
                for k, qidx in self._hedge_groups(
                    live, order_mat, picks, all_q, hedge_ratio
                ).items():
                    try:
                        self._execute_group(
                            cf, live[k], qidx, queries, rows_mat[k], cost_mat[k],
                            results, reports, hedged=True, trace=span,
                        )
                    except TransientFault:
                        continue

            if consistency != ONE:
                self._consistency_pass(
                    cf, cf.partitions[0], live, order_mat, picks, all_q,
                    queries, results, reports, consistency,
                    deadline_at=deadline, budget_s=deadline_s, trace=span,
                )

            return list(zip(results, reports))  # type: ignore[arg-type]
        finally:
            if span is not None:
                span.end()

    def _run_groups(
        self,
        cf: ColumnFamily,
        live: list[ReplicaHandle],
        order: np.ndarray,
        picks: np.ndarray,
        qidx: list[int],
        queries: list[Query],
        rows_live: np.ndarray,
        cost_live: np.ndarray,
        results: list,
        reports: list,
        *,
        deadline_at: float | None = None,
        budget_s: float | None = None,
        trace=None,
    ) -> None:
        """Primary grouped execution with bounded failover: queries
        whose group raises a :class:`TransientFault` advance to the
        next replica in their cost order that was not yet tried
        (``read_retries`` counts each re-routed query), up to
        ``read_retry_limit`` attempts per query (default: one per live
        replica). Scheduler column ``j`` of ``order`` corresponds to
        global query index ``qidx[j]``. A spent ``deadline_at`` budget
        raises :class:`DeadlineExceeded` before the next group scan."""
        col_of = {qi: j for j, qi in enumerate(qidx)}
        limit = (
            len(live) if self.read_retry_limit is None else self.read_retry_limit
        )
        tried: dict[int, set[int]] = {qi: set() for qi in qidx}
        queue = [(k, sub, False) for k, sub in _group_by_pick(picks, qidx).items()]
        while queue:
            self._check_deadline(deadline_at, budget_s)
            k, sub, is_retry = queue.pop(0)
            for qi in sub:
                tried[qi].add(k)
            try:
                self._execute_group(
                    cf, live[k], sub, queries, rows_live[k], cost_live[k],
                    results, reports, hedged=False, trace=trace,
                    retry=is_retry,
                )
            except TransientFault:
                self._read_retries.inc(len(sub))
                retry: dict[int, list[int]] = {}
                for qi in sub:
                    nxt = (
                        next(
                            (
                                int(x)
                                for x in order[:, col_of[qi]]
                                if int(x) not in tried[qi]
                            ),
                            None,
                        )
                        if len(tried[qi]) < limit
                        else None
                    )
                    if nxt is None:
                        raise RuntimeError(
                            f"no live replica answered query {qi} of "
                            f"{cf.name!r} after {len(tried[qi])} attempts"
                        )
                    retry.setdefault(nxt, []).append(qi)
                queue.extend((k2, sub2, True) for k2, sub2 in retry.items())

    def _scan_with_cache(
        self, cf: ColumnFamily, r: ReplicaHandle, group: list[Query],
        *, trace=None,
    ) -> tuple[list[ScanResult], list[float]]:
        """Core scan for one replica's query group: read-barrier flush,
        injected-fault check, result cache, one ``execute_many`` for
        the misses, failure-detector feed. Returns per-query
        ``(scans, walls)`` aligned with ``group``; cache hits carry
        zero attributed wall. Raises :class:`TransientReadError` /
        :class:`TransientFlushError` *before* producing any result, so
        a faulting group is retried whole."""
        self._ensure_flushed(cf, r, trace=trace)  # may raise TransientFlushError
        table = self._table(cf, r)
        cp = trace.child("engine.cache_probe") if trace is not None else None
        cache = ckeys = None
        if self._cache_enabled:
            cache = self._result_cache.setdefault((cf.name, r.replica_id), {})
            ckeys = self._cache_keys(group)
        hit_j = set() if cache is None else {j for j, k in enumerate(ckeys) if k in cache}
        miss_j = [j for j in range(len(group)) if j not in hit_j]
        if cp is not None:
            cp.end(hits=len(hit_j), misses=len(miss_j))
        node = self.nodes[r.node_id]
        if miss_j and node.read_fault_budget > 0:
            node.read_fault_budget -= 1
            if self.failure_detector is not None:
                self.failure_detector.record_failure(node.node_id)
            self._read_faults.inc()
            raise TransientReadError(node.node_id)
        sc = (
            trace.child("engine.scan", queries=len(miss_j))
            if trace is not None and miss_j
            else None
        )
        vstats = {"hits": 0, "boundary_rows": 0} if cf.views else None
        sstats = {"sparse_launches": 0, "full_launches": 0}
        t0 = self._scan_timer()
        miss_scans = (
            table.execute_many(
                [group[j] for j in miss_j], trace=sc, view_stats=vstats,
                select_stats=sstats,
            )
            if miss_j
            else []
        )
        wall = (self._scan_timer() - t0) * node.slowdown
        if sc is not None:
            sc.end(rows=int(sum(s.rows_scanned for s in miss_scans)))
        if vstats is not None and vstats["hits"]:
            self._view_hits.inc(vstats["hits"])
            self._view_boundary_rows.inc(vstats["boundary_rows"])
        if sstats["sparse_launches"]:
            self._select_sparse.inc(sstats["sparse_launches"])
        if sstats["full_launches"]:
            self._select_full.inc(sstats["full_launches"])
        if miss_j and self.failure_detector is not None:
            # one latency sample per executed group — cache hits are
            # not operations the node performed
            self.failure_detector.record(node.node_id, wall)
        per_q_wall = wall / len(miss_j) if miss_j else 0.0
        scans: list[ScanResult | None] = [None] * len(group)
        walls = [0.0] * len(group)
        # read the hits out BEFORE storing misses: a store can FIFO-evict
        # a key that was a hit when hit_j was computed
        for j in hit_j:
            scans[j] = cache[ckeys[j]]
        for j, sr in zip(miss_j, miss_scans):
            scans[j] = sr
            walls[j] = per_q_wall
            if cache is not None:
                self._cache_store((cf.name, r.replica_id), cache, ckeys[j], sr)
        if cache is not None:
            self._cache_hits.inc(len(hit_j))
            self._cache_misses.inc(len(miss_j))
        return scans, walls  # type: ignore[return-value]

    def _execute_group(
        self,
        cf: ColumnFamily,
        r: ReplicaHandle,
        qidx: list[int],
        queries: list[Query],
        est_rows: np.ndarray,
        est_costs: np.ndarray,
        results: list,
        reports: list,
        *,
        hedged: bool,
        trace=None,
        retry: bool = False,
    ) -> None:
        """Run one replica's query group via ``execute_many``; measured
        wall time (× node slowdown) is split evenly across the queries
        that actually executed — result-cache hits are served at zero
        attributed wall. Hedged runs only replace a query's primary
        result when at least as fast (ties — e.g. both served from
        cache at zero wall — go to the hedge: the duplicate answered
        first or simultaneously, which is what ``hedged`` reports)."""
        group = [queries[i] for i in qidx]
        g = (
            trace.child(
                "engine.group_scan", replica=r.replica_id, node=r.node_id,
                queries=len(qidx), hedged=hedged, retry=retry,
            )
            if trace is not None
            else None
        )
        try:
            scans, walls = self._scan_with_cache(cf, r, group, trace=g)
        except TransientFault as e:
            if g is not None:
                g.end(error=type(e).__name__)
            raise
        if g is not None:
            g.end(rows=int(sum(sr.rows_scanned for sr in scans)))
        for j, i in enumerate(qidx):
            sr = scans[j]
            if hedged and not (
                reports[i] is None or walls[j] <= reports[i].wall_seconds
            ):
                continue
            results[i] = sr
            reports[i] = ReadReport(
                replica_id=r.replica_id,
                node_id=r.node_id,
                estimated_rows=float(est_rows[i]),
                estimated_cost=float(est_costs[i]),
                wall_seconds=walls[j],
                rows_scanned=sr.rows_scanned,
                hedged=hedged,
            )

    # -- tunable consistency (digest reads + read repair) ---------------------

    @staticmethod
    def _consistency_k(consistency: str, rf: int) -> int:
        """Replicas that must answer at a consistency level (read k)."""
        if consistency == ONE:
            return 1
        if consistency == QUORUM:
            return rf // 2 + 1
        if consistency == ALL:
            return rf
        raise ValueError(
            f"unknown consistency {consistency!r} "
            f"(expected one of {CONSISTENCY_LEVELS})"
        )

    def _consistency_pass(
        self,
        cf: ColumnFamily,
        part: Partition,
        live: list[ReplicaHandle],
        order: np.ndarray,
        picks: np.ndarray,
        qidx: list[int],
        queries: list[Query],
        results: list,
        reports: list,
        consistency: str,
        *,
        deadline_at: float | None = None,
        budget_s: float | None = None,
        trace=None,
    ) -> None:
        """Digest reads: execute each query on the next cost-ranked
        replicas until k distinct replicas (primary included) answered,
        compare the layout-independent digests, and on mismatch repair
        divergent replicas from the partition log. Majority digest wins
        (the returned result is re-pointed at a majority replica when
        the primary was the outlier); with no majority — e.g. a 1–1
        split at k = 2 — the log is the ground truth: every consulted
        replica is rebuilt and the query re-executes on the primary."""
        k = self._consistency_k(consistency, len(part.replicas))
        if k <= 1:
            return
        if len(live) < k:
            raise RuntimeError(
                f"consistency {consistency} needs {k} live replicas of "
                f"partition {part.partition_id} of {cf.name!r}, "
                f"have {len(live)}"
            )
        dg = (
            trace.child("engine.digest", level=consistency, k=k)
            if trace is not None
            else None
        )
        col_of = {qi: j for j, qi in enumerate(qidx)}
        row_of_rid = {r.replica_id: i for i, r in enumerate(live)}
        # alternates: per query the k-1 cheapest ranked replicas other
        # than the one that served the primary (hedging may have moved
        # it off picks[j])
        consulted: dict[int, set[int]] = {}
        alt_groups: dict[int, list[int]] = {}
        for j, qi in enumerate(qidx):
            primary_row = row_of_rid.get(reports[qi].replica_id)
            consulted[qi] = {primary_row} if primary_row is not None else set()
            chosen: list[int] = []
            for x in order[:, j]:
                x = int(x)
                if x in consulted[qi]:
                    continue
                chosen.append(x)
                if len(chosen) >= k - 1:
                    break
            for x in chosen:
                alt_groups.setdefault(x, []).append(qi)
        # execute the digest reads, failing over like the primary pass
        alt_scans: dict[int, list[tuple[ReplicaHandle, ScanResult]]] = {}
        queue = list(alt_groups.items())
        while queue:
            # digest reads are REQUIRED work at QUORUM/ALL — a spent
            # budget sheds the whole call rather than quietly answering
            # at a weaker level than the caller asked for
            self._check_deadline(deadline_at, budget_s)
            x, sub = queue.pop(0)
            for qi in sub:
                consulted[qi].add(x)
            try:
                scans, _walls = self._scan_with_cache(
                    cf, live[x], [queries[qi] for qi in sub], trace=dg
                )
            except TransientFault:
                self._read_retries.inc(len(sub))
                retry: dict[int, list[int]] = {}
                for qi in sub:
                    nxt = next(
                        (
                            int(y)
                            for y in order[:, col_of[qi]]
                            if int(y) not in consulted[qi]
                        ),
                        None,
                    )
                    if nxt is None:
                        raise RuntimeError(
                            f"consistency {consistency}: fewer than {k} live "
                            f"replicas answered for {cf.name!r}"
                        )
                    retry.setdefault(nxt, []).append(qi)
                queue.extend(retry.items())
                continue
            for qi, sr in zip(sub, scans):
                alt_scans.setdefault(qi, []).append((live[x], sr))

        repaired: set[int] = set()  # replica ids healed earlier in this pass

        def _fresh(h: ReplicaHandle, qi: int, sr: ScanResult) -> ScanResult:
            # a scan taken before this pass repaired its replica is
            # stale evidence — re-read (the repair invalidated the cache)
            if h.replica_id not in repaired:
                return sr
            return self._scan_with_cache(cf, h, [queries[qi]], trace=dg)[0][0]

        handle_of_rid = {r.replica_id: r for r in part.replicas}
        for qi in qidx:
            alts = alt_scans.get(qi)
            if not alts:
                continue
            prim = handle_of_rid[reports[qi].replica_id]
            entries = [(prim, _fresh(prim, qi, results[qi]))] + [
                (h, _fresh(h, qi, sr)) for h, sr in alts
            ]
            digs = [
                _result_digest(sr, self._table(cf, h), cf.key_names, cf.schema)
                for h, sr in entries
            ]
            if len(set(digs)) == 1:
                if entries[0][1] is not results[qi]:
                    results[qi] = entries[0][1]  # refreshed primary
                continue
            self._digest_mismatches.inc()
            counts: dict[int, int] = {}
            for d in digs:
                counts[d] = counts.get(d, 0) + 1
            best_d, best_n = max(counts.items(), key=lambda t: t[1])
            if best_n * 2 > len(digs):
                # majority wins: heal the minority from the log and
                # answer from a majority replica
                for (h, _sr), d in zip(entries, digs):
                    if d != best_d:
                        rp = (
                            dg.child("engine.read_repair", replica=h.replica_id)
                            if dg is not None
                            else None
                        )
                        self._repair_replica(cf, part, h)
                        repaired.add(h.replica_id)
                        self._read_repairs.inc()
                        if rp is not None:
                            rp.end()
                win, win_scan = next(
                    e for e, d in zip(entries, digs) if d == best_d
                )
                results[qi] = win_scan
                reports[qi] = dataclasses.replace(
                    reports[qi],
                    replica_id=win.replica_id,
                    node_id=win.node_id,
                    rows_scanned=win_scan.rows_scanned,
                )
            else:
                # no majority: rebuild every consulted replica from the
                # log (the ground truth) and re-execute on the primary
                for h, _sr in entries:
                    rp = (
                        dg.child("engine.read_repair", replica=h.replica_id)
                        if dg is not None
                        else None
                    )
                    self._repair_replica(cf, part, h)
                    repaired.add(h.replica_id)
                    self._read_repairs.inc()
                    if rp is not None:
                        rp.end()
                scan = self._scan_with_cache(cf, prim, [queries[qi]], trace=dg)[0][0]
                results[qi] = scan
                reports[qi] = dataclasses.replace(
                    reports[qi], rows_scanned=scan.rows_scanned
                )
        if dg is not None:
            dg.end()

    def _hedge_groups(
        self,
        live: list[ReplicaHandle],
        order: np.ndarray,
        picks: np.ndarray,
        qidx: list[int],
        hedge_ratio: float,
    ) -> dict[int, list[int]]:
        """Queries whose picked node is a straggler (slowdown >
        ``hedge_ratio``), grouped by the next-cheapest replica on a
        *different* node — the same alternate a scalar ``read`` hedges
        to. Shared by both planners; ``qidx[j]`` is the global query
        index of scheduler column ``j``."""
        groups: dict[int, list[int]] = {}
        for j, qi in enumerate(qidx):
            pick_node = live[int(picks[j])].node_id
            if self.nodes[pick_node].slowdown <= hedge_ratio:
                continue
            alt = next(
                (int(k) for k in order[:, j] if live[int(k)].node_id != pick_node),
                -1,
            )
            if alt >= 0:
                groups.setdefault(alt, []).append(qi)
        return groups

    # -- partitioned scatter-gather read path ---------------------------------

    def _partition_row_offsets(self, cf: ColumnFamily) -> np.ndarray:
        """Global row offset of each partition in the cross-partition
        select index space (partitions concatenated in ring order).
        Built from the partition logs' row counts — append-only system,
        so log rows == table rows for any fully-flushed live replica —
        which keeps the offsets independent of staging state."""
        rows = np.array(
            [part.n_rows_committed for part in cf.partitions], dtype=np.int64
        )
        offsets = np.zeros(len(rows), dtype=np.int64)
        np.cumsum(rows[:-1], out=offsets[1:])
        return offsets

    def _read_many_partitioned(
        self,
        cf: ColumnFamily,
        queries: list[Query],
        *,
        hedge: bool,
        hedge_ratio: float,
        consistency: str = ONE,
        deadline_at: float | None = None,
        budget_s: float | None = None,
        trace=None,
    ) -> list[tuple[ScanResult, ReadReport]]:
        """Scatter-gather ``read_many`` over a partitioned column family.

        **Scatter** (host, pure arithmetic): each query's canonical slab
        bounds — the ``slab_bounds_many`` walk over ``key_names``, the
        same packing the ring's tokens use — are intersected with the
        ring's contiguous token ranges, giving a contiguous partition
        span per query (an equality filter on the leading canonical key
        pins one partition; an open scan fans out to all). A
        ``(partition, query)`` pair whose slab is disjoint from the
        partition's observed committed-token range
        (``Partition.may_contain`` — append-only writes keep the
        extrema monotone, so the test is never stale) is dropped before
        grouping: no device launch and no result-cache probe for a
        partition that provably contributes zero rows. Per surviving
        partition the Cost Evaluator ranks that partition's *live*
        replicas with the partition's own ``TableStats`` (its slice's
        selectivities — the CF-global stats are only the fallback), the
        RR tie-break draws from the partition's own counter, and each
        ``(partition, replica)`` group runs the ordinary grouped
        execution — device-resident partitions answer with the fused
        locate+scan launch, and the per-replica result cache applies
        per partition replica.

        **Gather** (host): per query, sum/count partial aggregates add
        up across its partitions in ring order, and select indices
        concatenate after each partition's local row indices (already
        host-ordered via the table's ``row_map``) are offset into the
        global index space — partitions in ring order, each in its
        chosen replica's serialization order (``merge_partial_scans``).
        The merged report carries the first executing partition's
        routing choice and the summed wall/rows_scanned; a query all of
        whose partitions were skipped gets a synthetic empty result and
        a placeholder report (``replica_id == node_id == -1`` — no
        replica was consulted).
        """
        n_q = len(queries)
        ring = cf.ring
        sc = trace.child("engine.scatter") if trace is not None else None
        bounds = slab_bounds_many(queries, cf.key_names, cf.schema)
        p_lo, p_hi = ring.span_partitions(bounds)

        touched: dict[int, list[int]] = {}
        for qi in range(n_q):
            for pid in range(int(p_lo[qi]), int(p_hi[qi]) + 1):
                part = cf.partitions[pid]
                if not part.may_contain(int(bounds[qi, 0]), int(bounds[qi, 1])):
                    self._empty_partition_skips.inc()
                    continue
                touched.setdefault(pid, []).append(qi)
        if sc is not None:
            sc.end(partitions=len(touched))

        rf = cf.replication_factor
        n_slots = len(cf.slot_layouts)
        partials: dict[int, tuple[list, list]] = {}
        for pid in sorted(touched):
            self._check_deadline(deadline_at, budget_s)
            ps = (
                trace.child("engine.partition", partition=pid)
                if trace is not None
                else None
            )
            part = cf.partitions[pid]
            qidx = touched[pid]
            live = [r for r in part.replicas if self.nodes[r.node_id].alive]
            if not live:
                raise RuntimeError(
                    f"no live replica for partition {pid} of {cf.name!r}"
                )
            # this partition's replica ranking, from ITS OWN stats: the
            # same vectorized Eq 1-2 as the single-partition path, but
            # the selectivities describe the partition's row slice
            group = [queries[i] for i in qidx]
            rows_sub, cost_sub = cf.cost_model.rank_matrices(
                cf.slot_layouts, group, stats=part.stats
            )
            # view term: same cap as the single-partition planner, per
            # (slot layout, group query); the any() guard keeps
            # all-select batches off the eligibility arrays
            if cf.views and any(q.agg in VIEW_AGGS for q in group):
                elig = view_eligible_matrix(cf.slot_layouts, group)
                if elig.any():
                    capped = np.minimum(rows_sub, float(VIEW_ROWS_CAP))
                    cost_sub = np.where(
                        elig,
                        np.stack(
                            [
                                cf.cost_model.cost_fn(len(lay)).many(capped[s])
                                for s, lay in enumerate(cf.slot_layouts)
                            ]
                        ),
                        cost_sub,
                    )
            # scatter the group estimates back to full batch width —
            # _execute_group indexes them by global query index
            rows_mat = np.zeros((n_slots, n_q))
            cost_mat = np.zeros((n_slots, n_q))
            rows_mat[:, qidx] = rows_sub
            cost_mat[:, qidx] = cost_sub
            slots = [r.replica_id - part.vnode_id * rf for r in live]
            factors = self._live_cost_factors(live)
            if factors is not None:
                # penalize suspected nodes' rows in place so ranking,
                # failover order and reported est_cost all agree
                for k, s in enumerate(slots):
                    cost_mat[s] *= factors[k]
            sub_cost = cost_mat[np.asarray(slots)][:, qidx]  # (live, group)
            order, picks = _schedule_picks(sub_cost, part.rr_counter)

            res_p: list[ScanResult | None] = [None] * n_q
            rep_p: list[ReadReport | None] = [None] * n_q
            rows_live = rows_mat[np.asarray(slots)]
            cost_live = cost_mat[np.asarray(slots)]
            self._run_groups(
                cf, live, order, picks, qidx, queries, rows_live, cost_live,
                res_p, rep_p, deadline_at=deadline_at, budget_s=budget_s,
                trace=ps,
            )
            if hedge and len(live) > 1 and not _deadline_spent(deadline_at):
                for k, sub in self._hedge_groups(
                    live, order, picks, qidx, hedge_ratio
                ).items():
                    try:
                        self._execute_group(
                            cf, live[k], sub, queries, rows_live[k],
                            cost_live[k], res_p, rep_p, hedged=True, trace=ps,
                        )
                    except TransientFault:
                        continue  # best-effort duplicate
            if consistency != ONE:
                self._consistency_pass(
                    cf, part, live, order, picks, qidx, queries,
                    res_p, rep_p, consistency,
                    deadline_at=deadline_at, budget_s=budget_s, trace=ps,
                )
            if ps is not None:
                ps.end()
            partials[pid] = (res_p, rep_p)

        # gather: merge each query's per-partition partials in ring order
        ga = trace.child("engine.gather") if trace is not None else None
        offsets = self._partition_row_offsets(cf)
        out: list[tuple[ScanResult, ReadReport]] = []
        for qi in range(n_q):
            scans = []
            reps: list[ReadReport] = []
            for pid in range(int(p_lo[qi]), int(p_hi[qi]) + 1):
                if pid not in partials or partials[pid][0][qi] is None:
                    continue  # skipped: provably no rows in this slab
                scans.append((partials[pid][0][qi], int(offsets[pid])))
                reps.append(partials[pid][1][qi])
            if not scans:
                # every candidate partition was skipped — the query
                # provably matches nothing; synthesize the empty result
                # without consulting any replica
                empty_sel = (
                    np.empty(0, dtype=np.int64)
                    if queries[qi].agg == "select"
                    else None
                )
                out.append(
                    (
                        ScanResult(0.0, 0, 0, empty_sel),
                        ReadReport(
                            replica_id=-1,
                            node_id=-1,
                            estimated_rows=0.0,
                            estimated_cost=0.0,
                            wall_seconds=0.0,
                            rows_scanned=0,
                        ),
                    )
                )
                continue
            merged = merge_partial_scans(scans, queries[qi].agg)
            first = reps[0]
            out.append(
                (
                    merged,
                    ReadReport(
                        replica_id=first.replica_id,
                        node_id=first.node_id,
                        estimated_rows=first.estimated_rows,
                        estimated_cost=first.estimated_cost,
                        wall_seconds=sum(r.wall_seconds for r in reps),
                        rows_scanned=merged.rows_scanned,
                        hedged=any(r.hedged for r in reps),
                    ),
                )
            )
        if ga is not None:
            ga.end()
        return out

    # -- ring migration (vnode split / merge / rebalance) ---------------------

    def partition_imbalance(self, cf_name: str) -> float:
        """Max/mean committed-row imbalance across the ring (1.0 =
        perfectly balanced). The exact form of the histogram drift
        signal — ``rebalance`` reports it before/after."""
        cf = self.column_families[cf_name]
        rows = np.array(
            [p.n_rows_committed for p in cf.partitions], dtype=np.float64
        )
        total = rows.sum()
        if total <= 0:
            return 1.0
        return float(rows.max() / (total / rows.size))

    def split_partition(
        self, cf_name: str, partition_id: int, token: int | None = None
    ) -> int:
        """Online split: cut one partition's token range in two at
        ``token`` (rows with canonical token ≥ ``token`` move to the
        right child). Default cut: the partition's median committed
        token — the boundary that halves its *rows*, not its key range.
        Both children are new vnodes built by replaying token-sliced
        copies of the parent's commit log (see ``_reshard``); every
        other partition is untouched. Returns the cut token."""
        cf = self.column_families[cf_name]
        part = cf.partitions[partition_id]
        if token is None:
            kc, _ = part.commitlog.replay_columns()
            toks = np.sort(pack_columns(kc, cf.key_names, cf.schema))
            if toks.size:
                token = int(toks[toks.size // 2])
            else:
                token = (part.token_lo + part.token_hi + 1) // 2
            # a median equal to the range start cannot form a boundary
            # (the left child would own nothing of the cut); nudge right
            token = max(token, part.token_lo + 1)
        token = int(token)
        if not part.token_lo < token <= part.token_hi:
            raise ValueError(
                f"split token {token} outside partition {partition_id}'s "
                f"range ({part.token_lo}, {part.token_hi}]"
            )
        self._reshard(cf, sorted(cf.ring.starts + (token,)))
        return token

    def merge_partitions(self, cf_name: str, partition_id: int) -> None:
        """Online merge: fuse ring-adjacent partitions ``partition_id``
        and ``partition_id + 1`` into one new vnode whose commit log is
        the two logs concatenated in ring order (see ``_reshard``).
        Every other partition is untouched."""
        cf = self.column_families[cf_name]
        if partition_id + 1 >= cf.ring.n_partitions:
            raise ValueError(
                f"partition {partition_id} has no right neighbor to merge with"
            )
        starts = list(cf.ring.starts)
        del starts[partition_id + 1]
        self._reshard(cf, starts)

    def rebalance(
        self,
        cf_name: str,
        *,
        partitions: int | None = None,
        exact: bool = True,
    ) -> dict:
        """Load-aware rebalancing: move the ring boundaries to the
        observed row-count quantiles, so each partition owns ~1/P of
        the committed rows (Cassandra's vnode reassignment, done as one
        ring-wide reshard). ``partitions`` changes the partition count
        (default: keep P). ``exact=True`` (default) takes quantiles of
        the exact committed tokens replayed from the partition logs —
        what the ≤ 1.25× imbalance target needs; ``exact=False`` uses
        the column family's token *histogram* proposal instead (cheaper,
        resolution = one histogram bin). Partitions whose range is
        unchanged keep all state; the rest migrate by log slicing +
        replay (``_reshard``). Returns an info dict with the imbalance
        before/after and the rows moved. No-op (zero rows moved) when
        the boundaries come out unchanged.

        The engine's ``rebalance_imbalance`` knob arms an automatic
        form: after a write-path flush, a P > 1 column family whose
        histogram drift exceeds the threshold rebalances itself.
        """
        cf = self.column_families[cf_name]
        P = cf.ring.n_partitions if partitions is None else int(partitions)
        before = self.partition_imbalance(cf_name)
        if exact or cf.token_hist is None:
            toks = np.concatenate(
                [
                    pack_columns(
                        p.commitlog.replay_columns()[0], cf.key_names, cf.schema
                    )
                    for p in cf.partitions
                ]
            )
            new_ring = TokenRing.from_tokens(cf.schema, cf.key_names, toks, P)
        else:
            new_ring = TokenRing.from_histogram(
                cf.schema, cf.key_names, cf.token_hist, P
            )
        moved = 0
        if new_ring.starts != cf.ring.starts:
            moved = self._reshard(cf, new_ring.starts)
        return {
            "partitions": P,
            "imbalance_before": before,
            "imbalance_after": self.partition_imbalance(cf_name),
            "rows_moved": moved,
        }

    def _reshard(self, cf: ColumnFamily, new_starts: Sequence[int]) -> int:
        """Rebuild the ring around new boundaries; returns rows moved.

        The migration contract (documented in ``repro.core.ring``):

        * a partition whose inclusive ``[lo, hi]`` range appears
          unchanged in the new ring is KEPT — same vnode, same log,
          same tables, memtables, stats, caches and RR counter; only
          its ``partition_id`` (ring position) is renumbered;
        * every other new range becomes a fresh vnode whose commit log
          is the token-sliced concatenation (ring order, fresh LSNs) of
          the overlapping old partitions' logs, and whose replica
          tables are built by replaying that log — the exact
          ``recover_node(source="log")`` path, so post-migration
          log-replay recovery is bit-identical to a surviving peer by
          construction. Staged-but-unflushed rows ride along for free:
          they are already log records, so the replay includes them and
          the fresh memtables start empty;
        * only migrated replica ids lose node tables and result-cache
          entries; a replica placed on a dead node is simply not
          installed (``recover_node`` rebuilds it from the new log).

        Counters: every boundary present in the new ring but not the
        old is a split, every boundary dropped is a merge, and the
        committed rows of all rebuilt partitions count as moved.
        """
        new_ring = cf.ring.with_starts(new_starts)
        old_parts = list(cf.partitions)
        old_ranges = [(p.token_lo, p.token_hi) for p in old_parts]
        old_by_range = dict(zip(old_ranges, old_parts))
        new_ranges = [
            new_ring.token_range(pid) for pid in range(new_ring.n_partitions)
        ]
        rf = cf.replication_factor

        new_parts: list[Partition] = []
        rows_moved = 0
        for pid, (nlo, nhi) in enumerate(new_ranges):
            kept = old_by_range.get((nlo, nhi))
            if kept is not None:
                kept.partition_id = pid
                for r in kept.replicas:
                    r.partition_id = pid
                new_parts.append(kept)
                continue
            overlap = [
                p
                for p, (olo, ohi) in zip(old_parts, old_ranges)
                if not (ohi < nlo or olo > nhi)
            ]

            def in_range(kc, _lo=nlo, _hi=nhi):
                t = pack_columns(kc, cf.key_names, cf.schema)
                return (t >= _lo) & (t <= _hi)

            log = CommitLog.concatenated(
                [p.commitlog.sliced(in_range) for p in overlap]
            )
            kc, vc = log.replay_columns()
            toks = pack_columns(kc, cf.key_names, cf.schema)
            # stats: pure-union merges add histograms bin-wise (exact —
            # disjoint row sets); a range cut inside an old partition
            # recomputes from the replayed slice
            if (
                len(overlap) > 1
                and overlap[0].token_lo == nlo
                and overlap[-1].token_hi == nhi
                and all(p.stats is not None for p in overlap)
            ):
                stats_p = overlap[0].stats
                for p in overlap[1:]:
                    stats_p = stats_p.merged_with(p.stats)
            else:
                stats_p = TableStats.from_columns(kc, cf.schema)
            vnode = cf.next_vnode
            cf.next_vnode += 1
            handles: list[ReplicaHandle] = []
            memtables: dict[int, Memtable] = {}
            flushed_lsn: dict[int, int] = {}
            for slot, layout in enumerate(cf.slot_layouts):
                rid = vnode * rf + slot
                node_id = self._place(rid, cf.name)
                if self.nodes[node_id].alive:
                    table = SortedTable.from_columns(kc, vc, layout, cf.schema)
                    if cf.device_resident:
                        table.place_on_device()
                    # a resharded vnode's views are re-derived over its
                    # sliced rows; untouched (kept) vnodes keep theirs
                    self._ensure_views(cf, table)
                    if self.checksums:
                        table.seal_checksum()
                    self.nodes[node_id].tables[(cf.name, rid)] = table
                    # rebuilt from the new log's full replay, so the
                    # watermark starts at its tail; replicas on dead
                    # nodes get theirs when recovery installs them
                    flushed_lsn[rid] = log.next_lsn
                handles.append(
                    ReplicaHandle(rid, tuple(layout), node_id, partition_id=pid)
                )
                memtables[rid] = Memtable(
                    layout, cf.schema, cf.key_names, cf.value_names
                )
            part = Partition(
                partition_id=pid,
                token_lo=nlo,
                token_hi=nhi,
                replicas=handles,
                commitlog=log,
                memtables=memtables,
                compaction=overlap[0].compaction if overlap else cf.compaction,
                vnode_id=vnode,
                stats=stats_p,
                flushed_lsn=flushed_lsn,
            )
            part.observe_tokens(toks)
            new_parts.append(part)
            rows_moved += log.n_rows

        # retire the migrated old partitions: their replica ids vanish,
        # so their node tables and result-cache entries (ONLY theirs —
        # kept partitions' caches stay warm) go with them
        kept_ids = {id(p) for p in new_parts}
        for part in old_parts:
            if id(part) in kept_ids:
                continue
            for r in part.replicas:
                self.nodes[r.node_id].tables.pop((cf.name, r.replica_id), None)
                self._result_cache.pop((cf.name, r.replica_id), None)
                self._cache_sel_bytes.pop((cf.name, r.replica_id), None)
            part.memtables.clear()

        old_set = set(cf.ring.starts)
        new_set = set(new_ring.starts)
        self._partition_splits.inc(len(new_set - old_set))
        self._partition_merges.inc(len(old_set - new_set))
        self._rebalance_rows_moved.inc(rows_moved)
        cf.ring = new_ring
        cf.partitions = new_parts
        return rows_moved

    # -- Write Scheduler (commit log → memtable → sorted runs) ----------------

    def write(
        self,
        cf_name: str,
        key_cols: Mapping[str, np.ndarray],
        value_cols: Mapping[str, np.ndarray],
        *,
        parallel: bool | None = None,
        flush: bool | None = None,
        trace=None,
    ) -> float:
        """Commit a batch write through the durable path and refresh
        stats; returns wall seconds. The batch is (1) appended to the
        column family's shared commit log — the layout-agnostic
        durability record any replica can be rebuilt from — then (2)
        staged into each live replica's memtable, and (3) flushed as one
        sorted run per replica when the staging threshold is reached
        (``memtable_rows``; 0 = write-through, so every write flushes).
        ``flush`` forces (True) or defers (False) step 3 explicitly.
        Matches §5.3: per-replica flush cost is one sort regardless of
        layout, so HR writes cost the same as TR (Table 1).

        *Group commit falls out of the staging*: with a threshold set, g
        writes of b rows flush as one sort + one merge of g×b rows —
        the amortization ``benchmarks/write_queue.py`` measures. The
        per-replica flushes remain independent and ``parallel=True``
        (default: the engine's ``parallel_writes`` flag) overlaps them
        on a thread pool; the merge hot path now runs through
        GIL-releasing ``np.sort`` + scatters (``SortedTable.merge_run``),
        and the same benchmark re-measures the overlap honestly.

        Deferred rows are never stale-served: reads flush a replica's
        pending rows (invalidating its cached results) before touching
        it. On a device-resident column family each flush *appends* its
        run to the replica's resident arrays and the column family's
        ``CompactionPolicy`` collapses the run stack on device once it
        outgrows the base — nothing is re-uploaded either way.

        On a partitioned column family the batch is first split by the
        token ring (one vectorized pack + partition lookup): each owning
        partition's sub-batch becomes one record in *that partition's*
        commit log and stages into that partition's live replicas only —
        a node hosting no replica of a row's partition never sees the
        row.
        """
        cf = self.column_families[cf_name]
        if parallel is None:
            parallel = self.parallel_writes
        t0 = time.perf_counter()
        w = (
            trace.child(
                "engine.write", cf=cf_name,
                rows=int(len(next(iter(key_cols.values())))) if key_cols else 0,
            )
            if trace is not None
            else None
        )
        if cf.ring.n_partitions == 1:
            routed = [(cf.partitions[0], key_cols, value_cols, None)]
        else:
            kc_arr = {c: np.asarray(key_cols[c]) for c in cf.key_names}
            tokens = cf.ring.tokens(kc_arr, cf.schema)
            pids = cf.ring.partition_of_tokens(tokens)
            if cf.token_hist is not None:
                cf.token_hist.add_tokens(tokens, device=cf.device_resident)
            routed = []
            for pid in np.unique(pids):
                mask = pids == pid
                routed.append(
                    (
                        cf.partitions[int(pid)],
                        {c: kc_arr[c][mask] for c in cf.key_names},
                        {
                            c: np.asarray(value_cols[c])[mask]
                            for c in cf.value_names
                        },
                        tokens[mask],
                    )
                )
        # missed writes on dead nodes are repaired by Recovery (the log
        # has every record; dead replicas neither stage nor flush). The
        # record's columns are the log's own immutable copies, so every
        # memtable stages them by reference — one copy per write, not RF.
        # A dead replica with an open hint just grows its hinted tail —
        # the hint is an LSN watermark into this same log, never a copy
        la = w.child("engine.log_append") if w is not None else None
        recs = []
        for part, kc_p, _vc_p, _toks_p in routed:
            part.commitlog.append(kc_p, _vc_p)
            recs.append(part.commitlog.tail)
        if la is not None:
            la.end(partitions=len(routed))
        ms = w.child("engine.memtable_stage") if w is not None else None
        for (part, kc_p, vc_p, toks_p), rec in zip(routed, recs):
            for r in part.replicas:
                if self.nodes[r.node_id].alive:
                    part.memtables[r.replica_id].stage(
                        rec.key_cols, rec.value_cols, copy=False
                    )
                elif r.replica_id in part.hints:
                    self._hints_queued.inc()
            if toks_p is not None:
                part.observe_tokens(toks_p)
            if part.stats is not None:
                # incremental per-partition selectivities: the routed
                # sub-batch folds into exactly the partition it joined
                part.stats.merge_rows(rec.key_cols, device=cf.device_resident)
        cf.stats.merge_rows(key_cols, device=cf.device_resident)
        if ms is not None:
            ms.end()
        # the threshold check spans ALL live replicas, not just this
        # write's routed partitions: rows staged earlier in a partition
        # the current key mix never touches again must still flush once
        # over the group-commit threshold
        live = [r for r in cf.replicas if self.nodes[r.node_id].alive]
        if flush is None:
            flush = cf.memtable_rows <= 0 or any(
                self._memtable(cf, r).n_staged >= cf.memtable_rows
                for r in live
            )
        if flush:
            self._flush_replicas(cf, live, parallel=parallel, trace=w)
            # skew-drift trigger: when the observed-token histogram says
            # one partition's row mass drifted past the threshold × mean,
            # rebalance in place (boundaries to observed quantiles).
            # Post-flush only — migration replays logs, so rebalancing a
            # freshly flushed CF never races staged state
            if (
                self.rebalance_imbalance > 0
                and cf.ring.n_partitions > 1
                and cf.token_hist is not None
                and cf.token_hist.imbalance(cf.ring.starts)
                > self.rebalance_imbalance
            ):
                self.rebalance(cf_name)
        if w is not None:
            w.end()
        return time.perf_counter() - t0

    def _flush_replicas(
        self, cf: ColumnFamily, replicas: Sequence[ReplicaHandle], *,
        parallel: bool = False, trace=None,
    ) -> None:
        """Flush the given replicas' staged rows: one sorted run per
        replica (in its own layout), merged via ``merge_run``, result
        cache invalidated, then the compaction policy applied to the
        merged table. ``parallel`` overlaps the independent per-replica
        merges on a thread pool (``engine.flush`` spans are emitted per
        replica either way; CPython's atomic int/list ops keep the
        shared tracer consistent under the pool)."""
        pending = [
            r
            for r in replicas
            if self.nodes[r.node_id].alive and self._memtable(cf, r).n_staged
        ]
        if not pending:
            return
        t0 = time.perf_counter()

        def _flush(r: ReplicaHandle) -> tuple[ReplicaHandle, SortedTable]:
            # peek, don't drain: the memtable is cleared only after the
            # merged table is installed below, so an exception here (or
            # in a sibling thread) never loses committed rows — the
            # staged buffers and the old table both survive a retry
            node = self.nodes[r.node_id]
            fs = (
                trace.child(
                    "engine.flush", replica=r.replica_id, node=r.node_id,
                    rows=int(self._memtable(cf, r).n_staged),
                )
                if trace is not None
                else None
            )
            try:
                if node.flush_fault_budget > 0:
                    node.flush_fault_budget -= 1
                    if self.failure_detector is not None:
                        self.failure_detector.record_failure(node.node_id)
                    self._flush_faults.inc()
                    raise TransientFlushError(node.node_id)
                run = self._memtable(cf, r).peek_run()
                if self.checksums and not run.verify():
                    self._corrupt_runs.inc()
                    raise CorruptRunError(
                        f"flush of {cf.name!r} replica {r.replica_id}: sorted "
                        f"run failed its checksum"
                    )
                table = node.tables[(cf.name, r.replica_id)]
                fm = fs.child("engine.flush_merge") if fs is not None else None
                merged = table.merge_run(run, trace=fm)
                if fm is not None:
                    fm.end()
                if self.checksums:
                    # extend the seal with the run's digest — O(run), and
                    # derived from durable history, never from the (possibly
                    # corrupted) base arrays: a bit flip in the base stays
                    # detectable by scrub after any number of flushes
                    if table.stored_digest is not None:
                        merged.stored_digest = combine_digests(
                            table.stored_digest, run.digest
                        )
                    else:
                        merged.seal_checksum()
            except Exception as e:
                if fs is not None:
                    fs.end(error=type(e).__name__)
                raise
            if fs is not None:
                fs.end()
            return r, merged

        if parallel and len(pending) > 1:
            merged_tables = list(self._executor.map(_flush, pending))
        else:
            merged_tables = [_flush(r) for r in pending]
        for r, merged in merged_tables:
            if cf.device_resident and not merged.device_resident:
                merged.place_on_device()
            self._ensure_views(cf, merged)
            self.nodes[r.node_id].tables[(cf.name, r.replica_id)] = merged
            self._memtable(cf, r).clear()
            self._flushes.inc()
            part = cf.partitions[r.partition_id]
            if part.commitlog is not None:
                # hinted-handoff watermark: this replica's table now
                # reflects every log record below the tail
                part.flushed_lsn[r.replica_id] = part.commitlog.next_lsn
            self._invalidate_result_cache(cf.name, replica_id=r.replica_id)
            policy = part.compaction
            if policy is not None:
                tc = trace.tracer.now() if trace is not None else 0.0
                if compact_table(merged, policy):
                    # content unchanged by compaction, so the sealed
                    # multiset digest carries over as-is
                    self._compactions.inc()
                    if merged.has_views:
                        # compact_runs re-derived the per-block partials
                        # over the collapsed run stack (full rebuild —
                        # block boundaries moved with the row order)
                        self._view_rebuilds.inc()
                    self._invalidate_result_cache(cf.name, replica_id=r.replica_id)
                    if trace is not None:
                        # retroactive span: only compactions that ran
                        # appear in the tree, with an honest wall
                        trace.child(
                            "engine.compaction", t=tc, replica=r.replica_id
                        ).end()
        # count-based auto-checkpoint: once a flushed partition's log
        # has accumulated more than the engine's record threshold since
        # its last snapshot AND the partition is fully drained (every
        # replica flushed through the tail — the documented safety
        # condition of CommitLog.checkpoint), collapse its history.
        # Deferred while any hint is open: a checkpoint re-LSNs the
        # record the hint watermark points into, forcing node_up onto
        # the full-rebuild fallback — cheaper to wait the outage out
        k = self.commitlog_checkpoint_records
        if k:
            for pid in sorted({r.partition_id for r, _ in merged_tables}):
                part = cf.partitions[pid]
                log = part.commitlog
                if (
                    log is not None
                    and log.should_checkpoint(k)
                    and not part.hints
                    and not any(mt.n_staged for mt in part.memtables.values())
                ):
                    log.checkpoint()
                    # every drained replica is flushed through the new
                    # snapshot record by construction
                    for rid in list(part.flushed_lsn):
                        part.flushed_lsn[rid] = log.next_lsn
                    self._auto_checkpoints.inc()
        self._flush_wall.inc(time.perf_counter() - t0)

    def _ensure_views(
        self, cf: ColumnFamily, table: SortedTable, *, count: bool = True
    ) -> None:
        """Materialize a views CF's per-block partials on ``table`` if
        absent (full rebuild from the resident arrays, counted under
        ``view_rebuilds`` unless ``count=False``).

        Views are *derived* state, so every site that rebuilds or
        replaces a replica table — flush fallback, migration reshard,
        log-replay recovery, node_up heal, scrub repair — funnels
        through here right where it already invalidates the result
        cache: the two caches share one invalidation discipline (stale
        content never outlives the table swap that produced it).
        Tables that already carry views (the incremental ``merge_run``
        extension, or ``compact_runs``' own rebuild) are left alone."""
        if not cf.views or table.has_views:
            return
        if not table.device_resident:
            table.place_on_device()
        table.build_views()
        if count:
            self._view_rebuilds.inc()

    def _memtable(self, cf: ColumnFamily, r: ReplicaHandle) -> Memtable:
        return cf.partitions[r.partition_id].memtables[r.replica_id]

    def _ensure_flushed(
        self, cf: ColumnFamily, r: ReplicaHandle, *, trace=None
    ) -> None:
        """Flush one replica's pending staged rows (read barrier)."""
        mt = cf.partitions[r.partition_id].memtables.get(r.replica_id)
        if mt is not None and mt.n_staged:
            if trace is None:
                self._flush_replicas(cf, [r])
            else:
                fb = trace.child("engine.flush_barrier", rows=int(mt.n_staged))
                self._flush_replicas(cf, [r], trace=fb)
                fb.end()

    def flush_memtables(self, cf_name: str, *, parallel: bool | None = None) -> None:
        """Drain every live replica's memtable (group-commit flush)."""
        cf = self.column_families[cf_name]
        if parallel is None:
            parallel = self.parallel_writes
        live = [r for r in cf.replicas if self.nodes[r.node_id].alive]
        self._flush_replicas(cf, live, parallel=parallel)

    def checkpoint_commitlog(self, cf_name: str) -> int:
        """Collapse every partition's commit log into one snapshot
        record, bounding log memory and replay-recovery cost at
        O(current rows) instead of O(rows ever written). Flushes every
        live replica first so no record still backs staged-only rows;
        log-replay recovery is unchanged (the snapshot replays to the
        identical dataset). Returns the highest snapshot LSN (the only
        one when ``partitions == 1``). The count-based automatic
        trigger (``commitlog_checkpoint_records``) fires the same
        collapse per partition after a flush."""
        cf = self.column_families[cf_name]
        self.flush_memtables(cf_name)
        top = 0
        for part in cf.partitions:
            top = max(top, part.commitlog.checkpoint())
            # every flushed replica is complete through the snapshot —
            # advance the hinted-handoff watermarks past it so a later
            # short outage still heals by tail replay
            for rid in list(part.flushed_lsn):
                part.flushed_lsn[rid] = part.commitlog.next_lsn
        return top

    # -- Recovery ----------------------------------------------------------------

    def fail_node(self, node_id: int, *, transient: bool = False) -> None:
        """Take a node down. The default models *node loss*: the node's
        disk (every partition replica it hosted, across all column
        families) and memtables are gone; partitions the node held no
        replica of are untouched; the per-partition commit logs are the
        durable copy ``recover_node`` rebuilds from.

        ``transient=True`` models a *short outage* (process restart,
        network partition): the replica tables survive on disk, only
        the staged memtable rows are lost — and those are already log
        records. Each hosted partition opens a **hint**: the replica's
        flushed-LSN watermark, recording exactly where its table's
        knowledge of the log ends. Writes committed during the outage
        just grow the log past the watermark; ``node_up`` replays only
        that tail (hinted handoff — O(missed writes), not O(dataset)).

        Failing a node that is already down is an explicit no-op — the
        first failure's hints keep their (older, still correct)
        watermarks. An out-of-range ``node_id`` raises ``ValueError``.
        """
        if not 0 <= node_id < len(self.nodes):
            raise ValueError(
                f"unknown node {node_id} (cluster has {len(self.nodes)})"
            )
        node = self.nodes[node_id]
        if not node.alive:
            return  # already down; earlier hints/loss state stands
        node.alive = False
        for cf_name, cf in self.column_families.items():
            for part in cf.partitions:
                for r in part.replicas:
                    if r.node_id != node_id:
                        continue
                    rid = r.replica_id
                    if transient:
                        if part.commitlog is not None:
                            # hint = LSN watermark into the shared log,
                            # never a data copy
                            part.hints[rid] = part.flushed_lsn.get(rid, 0)
                    else:
                        part.hints.pop(rid, None)
                        part.flushed_lsn.pop(rid, None)
                    if rid in part.memtables:
                        # the memtable dies with its node either way; the
                        # commit log is the durable copy every staged row
                        # replays from
                        part.memtables[rid].clear()
            self._invalidate_result_cache(cf_name, node_id=node_id)
        if not transient:
            node.tables = {}  # disk lost

    # -- replica rebuild/install helpers (recovery, read repair, scrub) ------

    def _rebuild_replica_table(
        self,
        cf: ColumnFamily,
        part: Partition,
        r: ReplicaHandle,
        *,
        source: str = "log",
    ) -> SortedTable:
        """Rebuild one partition replica's full table in its own layout:
        replay the owning partition's commit log (``source="log"``, the
        ground truth) or re-sort a surviving live peer
        (``source="survivor"``, also the fallback when the partition has
        no log)."""
        log = part.commitlog
        if source == "log" and log is not None and len(log):
            kc, vc = log.replay_columns()
            rebuilt = SortedTable.from_columns(kc, vc, r.layout, cf.schema)
        else:
            survivor = next(
                (
                    s
                    for s in part.replicas
                    if s.replica_id != r.replica_id
                    and self.nodes[s.node_id].alive
                    and (cf.name, s.replica_id) in self.nodes[s.node_id].tables
                ),
                None,
            )
            if survivor is None:
                raise RuntimeError(
                    f"data loss: no survivor for {cf.name!r} partition "
                    f"{part.partition_id} replica {r.replica_id}"
                )
            self._ensure_flushed(cf, survivor)  # staged rows too
            src = self.nodes[survivor.node_id].tables[
                (cf.name, survivor.replica_id)
            ]
            rebuilt = src.resorted(r.layout)
        if cf.device_resident:
            rebuilt.place_on_device()
        self._ensure_views(cf, rebuilt)
        if self.checksums:
            rebuilt.seal_checksum()
        return rebuilt

    def _install_rebuilt(
        self,
        cf: ColumnFamily,
        part: Partition,
        r: ReplicaHandle,
        table: SortedTable,
    ) -> None:
        """Install a fully rebuilt replica table: fresh memtable (a full
        rebuild IS flushed state), hint discharged, watermark at the log
        tail, stale cached results dropped."""
        rid = r.replica_id
        self.nodes[r.node_id].tables[(cf.name, rid)] = table
        part.memtables[rid] = Memtable(
            r.layout, cf.schema, cf.key_names, cf.value_names
        )
        part.hints.pop(rid, None)
        if part.commitlog is not None:
            part.flushed_lsn[rid] = part.commitlog.next_lsn
        self._invalidate_result_cache(cf.name, replica_id=rid)

    def _repair_replica(
        self, cf: ColumnFamily, part: Partition, r: ReplicaHandle
    ) -> None:
        """Heal one *live* replica in place from the partition log — the
        read-repair / scrub action. Only this replica's table, memtable
        and cached results are replaced; the caller bumps the counter
        that names the trigger (``read_repairs`` / ``scrub_repairs``)."""
        self._install_rebuilt(
            cf, part, r, self._rebuild_replica_table(cf, part, r)
        )

    def node_up(self, node_id: int) -> float:
        """Bring a transiently failed node back, healing each hosted
        partition replica by **hinted handoff**: replay only the log
        tail past the hint watermark and merge it into the surviving
        table — one sorted run of exactly the missed rows. A partition
        that committed nothing during the outage costs nothing (the
        common case that makes short outages cheap). Falls back to the
        full ``recover_node`` rebuild — counted in ``hint_fallbacks`` —
        when the table is gone (durable failure), the watermark predates
        a checkpoint collapse (``CommitLog.can_replay_from``), or no
        hint was recorded. Returns wall seconds; bringing up a live node
        is a no-op returning 0.0."""
        if not 0 <= node_id < len(self.nodes):
            raise ValueError(
                f"unknown node {node_id} (cluster has {len(self.nodes)})"
            )
        node = self.nodes[node_id]
        if node.alive:
            return 0.0
        t0 = time.perf_counter()
        node.alive = True
        for cf_name in self.column_families:
            self._invalidate_result_cache(cf_name, node_id=node_id)
        for cf in self.column_families.values():
            for part in cf.partitions:
                for r in part.replicas:
                    if r.node_id != node_id:
                        continue
                    rid = r.replica_id
                    log = part.commitlog
                    table = node.tables.get((cf.name, rid))
                    hint = part.hints.pop(rid, None)
                    if (
                        table is None
                        or hint is None
                        or log is None
                        or not log.can_replay_from(hint)
                    ):
                        self._hint_fallbacks.inc()
                        self._install_rebuilt(
                            cf, part, r, self._rebuild_replica_table(cf, part, r)
                        )
                        continue
                    kc, vc = log.replay_columns(start_lsn=hint)
                    n_rows = next(iter(kc.values())).shape[0] if kc else 0
                    if n_rows:
                        run = sort_run(kc, vc, r.layout, cf.schema)
                        merged = table.merge_run(run)
                        if cf.device_resident and not merged.device_resident:
                            merged.place_on_device()
                        self._ensure_views(cf, merged)
                        if self.checksums:
                            if table.stored_digest is not None:
                                merged.stored_digest = combine_digests(
                                    table.stored_digest, run.digest
                                )
                            else:
                                merged.seal_checksum()
                        node.tables[(cf.name, rid)] = merged
                        self._hint_replays.inc()
                        self._hint_rows_replayed.inc(n_rows)
                    # zero missed rows: the surviving table is already
                    # complete — no merge, no re-seal, no device work
                    part.flushed_lsn[rid] = log.next_lsn
                    part.memtables[rid] = Memtable(
                        r.layout, cf.schema, cf.key_names, cf.value_names
                    )
        return time.perf_counter() - t0

    def recover_node(self, node_id: int, *, source: str = "log") -> float:
        """Rebuild every replica the node hosted, in that replica's own
        heterogeneous layout. Returns wall seconds (§5.4 bench);
        recovering a node that is already live is a no-op returning 0.0
        (its tables are intact — use ``node_up`` for hinted heal after
        a transient failure, or ``scrub_column_family`` to audit).

        Recovery is partition-aware: only the partition replicas the
        node actually hosted are rebuilt, each from *its own
        partition's* state — the other partitions (and their logs) are
        never touched.

        ``source="log"`` (default) replays the owning partition's
        commit log: the layout-agnostic record stream — that
        partition's base rows plus every committed write it owns,
        including ones the dead node missed and rows that were
        staged-but-unflushed anywhere when the node died — is sorted
        into the lost replica's layout. The result is the same dataset
        and serialization the surviving-peer path produces
        (bit-identical packed keys and key columns; value columns too
        whenever composite keys are unique — the tie order among
        duplicate full keys is the only degree of freedom).

        ``source="survivor"`` keeps the original path: stream a
        surviving replica of the same partition and re-sort it (same
        row slice, different serialization). It is also the fallback
        for partitions without a commit log.
        """
        if source not in ("log", "survivor"):
            raise ValueError(f"unknown recovery source {source!r}")
        if not 0 <= node_id < len(self.nodes):
            raise ValueError(
                f"unknown node {node_id} (cluster has {len(self.nodes)})"
            )
        node = self.nodes[node_id]
        if node.alive:
            return 0.0
        t0 = time.perf_counter()
        node.alive = True
        for cf_name in self.column_families:
            self._invalidate_result_cache(cf_name, node_id=node_id)
        for cf in self.column_families.values():
            for part in cf.partitions:
                for r in part.replicas:
                    if r.node_id != node_id:
                        continue
                    self._install_rebuilt(
                        cf,
                        part,
                        r,
                        self._rebuild_replica_table(cf, part, r, source=source),
                    )
        return time.perf_counter() - t0

    def scrub_column_family(self, cf_name: str, *, repair: bool = True) -> dict:
        """Audit every live replica's content checksum (sealed at
        install time) against its arrays and heal mismatches from the
        partition log. The anti-entropy sweep of the availability layer:
        silent corruption that digest reads have not yet tripped over is
        found and repaired here. Returns
        ``{"replicas_checked", "corrupt", "repaired"}``; with
        ``repair=False`` corruption is only reported. Replicas without a
        sealed checksum (``checksums=False`` engines) verify trivially.

        On a views CF the sweep also audits the *derived* per-block
        partials against a fresh recompute from the (just-verified)
        resident arrays: a corrupted or missing view is healed by
        rebuild — no log replay, the base arrays are the ground truth —
        and counted under both ``scrub_repairs`` and ``view_rebuilds``.
        """
        cf = self.column_families[cf_name]
        checked = 0
        corrupt: list[int] = []
        repaired = 0
        for part in cf.partitions:
            for r in part.replicas:
                node = self.nodes[r.node_id]
                if not node.alive:
                    continue
                table = node.tables.get((cf.name, r.replica_id))
                if table is None:
                    continue
                checked += 1
                self._scrub_checks.inc()
                if table.verify_checksum():
                    if cf.views and (
                        not table.has_views or not verify_views(table)
                    ):
                        # base arrays verified, derived partials did
                        # not: heal from the arrays themselves — one
                        # kernel pass, no log replay
                        corrupt.append(r.replica_id)
                        if repair:
                            table.build_views()
                            self._view_rebuilds.inc()
                            self._scrub_repairs.inc()
                            self._invalidate_result_cache(
                                cf.name, replica_id=r.replica_id
                            )
                            repaired += 1
                    continue
                corrupt.append(r.replica_id)
                if repair:
                    self._repair_replica(cf, part, r)
                    self._scrub_repairs.inc()
                    repaired += 1
        return {
            "replicas_checked": checked,
            "corrupt": corrupt,
            "repaired": repaired,
        }

    # -- introspection -------------------------------------------------------------

    def layouts(self, cf_name: str) -> tuple[tuple[str, ...], ...]:
        """Per-replica layouts, flat in global replica-id order (every
        partition serializes slot ``s`` as ``slot_layouts[s]``, so a
        P-partition CF repeats the RF slot layouts P times)."""
        return tuple(r.layout for r in self.column_families[cf_name].replicas)

    def total_bytes(self) -> int:
        return sum(n.bytes_stored() for n in self.nodes)
