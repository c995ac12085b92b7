"""Observability layer: metrics registry, span trees, exports, and the
coverage/determinism contracts.

Four properties anchor this file:

1. **Counter coverage** — every typed refusal (``RetryAfter`` kinds,
   ``DeadlineExceeded``), engine fault, degradation rung, and repair
   path named in the audit inventories (``ENGINE_COUNTERS`` /
   ``FAULT_COUNTERS`` / ``REPAIR_COUNTERS`` on the engine,
   ``FRONTDOOR_COUNTERS`` / ``REFUSAL_COUNTERS`` / ``RUNG_COUNTERS``
   on the front door) resolves to a live registry metric, and the
   provokable ones actually increment.
2. **Span-tree integrity** — parent/child links are consistent, no
   span is orphaned from its tracer's roots, timestamps are monotone
   under a ``TickClock``, and success paths close every span.
3. **Acceptance** — a traced QUORUM request through the front door
   over a device-resident column family yields ONE tree from
   ``frontdoor.request`` down to ``kernel.scan_launch``, whose
   frontdoor stage walls sum to the client-observed latency.
4. **Determinism** — two runs of the same seeded chaos schedule with
   ``TickClock`` tracers export byte-identical JSON-lines dumps.
"""

import io

import numpy as np
import pytest

from repro.core import (
    DeadlineExceeded,
    Eq,
    HREngine,
    QUORUM,
    Query,
    TransientFault,
)
from repro.core.engine import (
    ENGINE_COUNTERS,
    FAULT_COUNTERS,
    REPAIR_COUNTERS,
    SELECT_COUNTERS,
    VIEW_COUNTERS,
)
from repro.core.tpch import generate_simulation
from repro.ft.chaos import ChaosHarness
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    SlowQueryLog,
    TickClock,
    Tracer,
    dump_jsonl,
    format_tree,
    load_jsonl,
    span_to_line,
    stage_totals,
    walk,
)
from repro.obs.__main__ import main as obs_main
from repro.serving.frontdoor import (
    FRONTDOOR_COUNTERS,
    FrontDoor,
    REFUSAL_COUNTERS,
    Request,
    RUNG_COUNTERS,
)

LAYOUTS = [("k0", "k1", "k2"), ("k1", "k2", "k0"), ("k2", "k0", "k1")]
_CF = "cf"


def _engine(n_rows=512, *, device_resident=False, partitions=1, views=False, **kw):
    kc, vc, schema = generate_simulation(n_rows, 3, seed=0)
    kw.setdefault("result_cache", False)
    eng = HREngine(n_nodes=6, **kw)
    eng.create_column_family(
        _CF, kc, vc, replication_factor=3, layouts=LAYOUTS, schema=schema,
        partitions=partitions, device_resident=device_resident, views=views,
    )
    return eng, schema


# -- metrics primitives ------------------------------------------------------


class TestMetrics:
    def test_counter_inc_reset(self):
        c = Counter("c")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        c.reset()
        assert c.value == 0.0

    def test_gauge_set_and_high_water(self):
        g = Gauge("g")
        g.set(4.0)
        g.max(2.0)
        assert g.value == 4.0
        g.max(9.0)
        assert g.value == 9.0

    def test_histogram_quantiles_bracket_the_data(self):
        h = Histogram("h")
        data = np.random.default_rng(0).uniform(1e-4, 1e-1, 2000)
        for v in data:
            h.observe(float(v))
        snap = h.snapshot()
        assert snap["count"] == 2000
        assert snap["sum"] == pytest.approx(float(data.sum()), rel=1e-9)
        assert snap["max"] == pytest.approx(float(data.max()))
        # log-bucketed quantiles are bucket upper bounds: conservative
        # (>= the true quantile) but within one bucket (~9%) of it
        for q, name in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            true = float(np.quantile(data, q))
            assert true <= snap[name] <= min(true * 1.15, snap["max"])

    def test_histogram_nonpositive_goes_to_zero_bucket(self):
        h = Histogram("h")
        h.observe(0.0)
        h.observe(-1.0)
        assert h.count == 2
        assert h.quantile(0.5) == 0.0

    def test_registry_get_or_create_and_kind_conflict(self):
        reg = MetricsRegistry()
        c = reg.counter("x")
        assert reg.counter("x") is c
        with pytest.raises(TypeError):
            reg.gauge("x")
        assert "x" in reg
        assert reg.catalog() == ("x",)

    def test_registry_as_dict_explodes_histograms(self):
        reg = MetricsRegistry()
        reg.counter("a").inc(2)
        reg.histogram("lat").observe(0.5)
        d = reg.as_dict()
        assert d["a"] == 2.0
        assert {"lat.count", "lat.p50", "lat.p95", "lat.p99"} <= set(d)

    def test_registry_reset_keeps_handles_live(self):
        reg = MetricsRegistry()
        c = reg.counter("a")
        c.inc(5)
        reg.reset()
        assert c.value == 0.0
        c.inc()
        assert reg.value("a") == 1.0


# -- counter coverage audits -------------------------------------------------


class TestCounterCoverage:
    def test_engine_inventories_resolve_in_registry(self):
        eng, _ = _engine(64)
        cat = set(eng.metrics.catalog())
        for name in ENGINE_COUNTERS:
            assert name in cat, f"ENGINE_COUNTERS[{name!r}] not registered"
        for exc_name, counter in FAULT_COUNTERS.items():
            assert counter in cat, f"{exc_name} has no registry counter"
            assert counter in ENGINE_COUNTERS
        for counter in REPAIR_COUNTERS:
            assert counter in cat
            assert counter in ENGINE_COUNTERS
        for counter in VIEW_COUNTERS:
            assert counter in cat, f"VIEW_COUNTERS[{counter!r}] not registered"
            assert counter in ENGINE_COUNTERS
        for counter in SELECT_COUNTERS:
            assert counter in cat, f"SELECT_COUNTERS[{counter!r}] not registered"
            assert counter in ENGINE_COUNTERS
        # the stats view exposes every engine counter
        stats = eng.stats
        for name in ENGINE_COUNTERS:
            assert name in stats

    def test_frontdoor_inventories_resolve_in_registry(self):
        eng, _ = _engine(64)
        fd = FrontDoor(eng)
        cat = set(fd.metrics.catalog())
        for name in FRONTDOOR_COUNTERS:
            assert name in cat
        for counter in (*REFUSAL_COUNTERS.values(), *RUNG_COUNTERS.values()):
            assert counter in cat
            assert counter in FRONTDOOR_COUNTERS
        # exact public stats key set: the pre-registry dict, unchanged
        assert set(fd.stats) == set(FRONTDOOR_COUNTERS) | {"max_queue_depth"}

    def test_select_counters_move_with_their_path(self):
        """A select on a single sorted run compacts over its slab window
        (``select_sparse_launches``); once a flush appends a write run,
        device row order is no longer sorted and the compaction streams
        every block (``select_full_launches``)."""
        kc, vc, schema = generate_simulation(512, 3, seed=0)
        eng = HREngine(n_nodes=6, result_cache=False)
        eng.create_column_family(
            _CF, kc, vc, replication_factor=3, layouts=LAYOUTS, schema=schema,
            device_resident=True, memtable_rows=0,
        )
        q = Query({"k0": Eq(int(kc["k0"][0])), "k1": Eq(int(kc["k1"][0]))}, agg="select")

        def counts():
            return tuple(eng.stats[c] for c in SELECT_COUNTERS)

        (r, _), = eng.read_many(_CF, [q])
        assert r.rows_matched > 0
        assert counts() == (1, 0)
        eng.write(_CF, {c: v[:5] for c, v in kc.items()}, {c: v[:5] for c, v in vc.items()})
        (r2, _), = eng.read_many(_CF, [q])
        assert r2.rows_matched > r.rows_matched
        assert counts() == (1, 1)

    def test_deadline_exceeded_increments_counter(self):
        eng, schema = _engine(256)
        q = Query({"k0": Eq(1)})
        before = eng.stats["deadline_exceeded"]
        with pytest.raises(DeadlineExceeded):
            eng.read_many(_CF, [q], deadline_s=0.0)
        assert eng.stats["deadline_exceeded"] == before + 1

    def test_read_fault_increments_counter(self):
        eng, _ = _engine(256)
        # every node faults its first scan: all three replicas raise
        # TransientReadError, each incrementing the counter before the
        # failover gives up
        for node in eng.nodes:
            node.read_fault_budget = 1
        with pytest.raises(RuntimeError, match="no live replica answered"):
            eng.read(_CF, Query({"k0": Eq(1)}))
        assert eng.stats["read_faults"] == 3
        assert eng.stats["read_retries"] == 3

    def test_flush_fault_increments_counter(self):
        eng, _ = _engine(256)
        for node in eng.nodes:
            node.flush_fault_budget = 99
        kc = {c: np.array([1], np.int64) for c in ("k0", "k1", "k2")}
        vc = {"metric": np.array([0.5])}
        with pytest.raises(TransientFault):
            eng.write(_CF, kc, vc)
        assert eng.stats["flush_faults"] >= 1

    def test_frontdoor_refusals_increment_their_counters(self):
        eng, schema = _engine(256)
        # queue bound: max_queue arrivals at t=0 fill it, the rest refuse
        fd = FrontDoor(eng, max_batch=4, max_queue=4, shed_fill=1.0)
        reqs = [Request(_CF, Query({"k0": Eq(i % 4)})) for i in range(7)]
        fd.serve(reqs)
        assert fd.stats["rejected_queue_full"] == 3
        # token bucket: burst of 2 at one instant, third arrival refused
        fd = FrontDoor(eng, rate=10.0, burst=2.0)
        fd.serve([Request(_CF, Query({"k0": Eq(0)})) for _ in range(3)])
        assert fd.stats["rejected_throttle"] == 1
        # deadline rung: a budget of zero is spent on arrival
        fd = FrontDoor(eng, max_wait=1e-3)
        resps = fd.serve([Request(_CF, Query({"k0": Eq(0)}), deadline_s=0.0)])
        assert resps[0].status == "deadline"
        assert fd.stats["shed_deadline"] == 1

    def test_reset_stats_on_engine_and_frontdoor(self):
        eng, _ = _engine(256, result_cache=True)
        eng.read(_CF, Query({"k0": Eq(1)}))
        assert eng.stats["result_cache_misses"] > 0
        eng.reset_stats()
        assert eng.stats["result_cache_misses"] == 0
        assert eng.stats["result_cache_hits"] == 0

        fd = FrontDoor(eng)
        fd.serve([Request(_CF, Query({"k0": Eq(1)}))])
        assert fd.stats["submitted"] == 1
        assert fd.stats["max_queue_depth"] == 1
        fd.reset_stats()
        assert fd.stats["submitted"] == 0
        assert fd.stats["max_queue_depth"] == 0


# -- span trees --------------------------------------------------------------


def _assert_tree_integrity(tracer):
    """Parent links, unique ids, no orphans, closed spans."""
    seen = []
    for root in tracer.roots:
        assert root.parent_id is None
        for s in walk(root):
            seen.append(s.span_id)
            for c in s.children:
                assert c.parent_id == s.span_id
                assert c.t_start >= s.t_start
    assert len(seen) == len(set(seen)), "span ids must be unique"
    assert len(seen) == tracer.spans_started, "orphaned spans exist"


class TestSpanTrees:
    def test_traced_read_many_integrity_and_monotone_ticks(self):
        eng, _ = _engine(512, partitions=4)
        tracer = Tracer(clock=TickClock())
        root = tracer.root("test.root")
        qs = [Query({"k0": Eq(i)}) for i in range(6)]
        eng.read_many(_CF, qs, consistency=QUORUM, trace=root)
        root.end()
        _assert_tree_integrity(tracer)
        for s in walk(root):
            assert s.t_end is not None, f"{s.name} left open on success path"
            assert s.t_end >= s.t_start
        names = {s.name for s in walk(root)}
        # the partitioned path ranks replicas per partition, so no
        # top-level engine.plan appears (the acceptance test covers it)
        assert {"engine.read_many", "engine.scatter", "engine.partition",
                "engine.group_scan", "engine.gather"} <= names

    def test_traced_write_path_reaches_flush(self):
        eng, _ = _engine(256)
        tracer = Tracer(clock=TickClock())
        root = tracer.root("test.root")
        kc = {c: np.arange(4, dtype=np.int64) for c in ("k0", "k1", "k2")}
        vc = {"metric": np.ones(4)}
        eng.write(_CF, kc, vc, trace=root)
        root.end()
        _assert_tree_integrity(tracer)
        names = {s.name for s in walk(root)}
        assert {"engine.write", "engine.log_append", "engine.memtable_stage",
                "engine.flush", "engine.flush_merge"} <= names

    def test_error_spans_carry_error_attr(self):
        eng, _ = _engine(256)
        for node in eng.nodes:
            node.read_fault_budget = 1
        tracer = Tracer(clock=TickClock())
        root = tracer.root("test.root")
        with pytest.raises(RuntimeError):
            eng.read_many(_CF, [Query({"k0": Eq(1)})], trace=root)
        root.end()
        # the faulting group scans record which exception killed them,
        # and the finally still closes the read_many span
        rm = root.find("engine.read_many")
        assert rm is not None and rm.t_end is not None
        errs = [s.attrs.get("error") for s in root.find_all("engine.group_scan")]
        assert "TransientReadError" in errs


# -- acceptance: one tree, kernel depth, walls sum to latency ----------------


class TestFrontDoorAcceptance:
    def test_single_quorum_request_one_tree_to_kernel_depth(self):
        eng, _ = _engine(256, device_resident=True)
        fd = FrontDoor(eng, max_batch=4, max_wait=1e-4, tracer=Tracer())
        resps = fd.serve(
            [Request(_CF, Query({"k0": Eq(3)}), consistency=QUORUM)]
        )
        assert resps[0].ok
        roots = fd.tracer.roots
        # ONE tree: the sole group member parents the engine subtree
        # under its own service span, no frontdoor.batch root appears
        assert [r.name for r in roots] == ["frontdoor.request"]
        root = roots[0]
        names = [s.name for s in walk(root)]
        assert "kernel.scan_launch" in names
        assert "engine.read_many" in names
        assert "engine.digest" in names
        # frontdoor stage walls (virtual clock) sum to the latency the
        # client observed, exactly the decomposition the tree promises
        q = root.find("frontdoor.queue")
        s = root.find("frontdoor.service")
        total = q.wall + s.wall
        assert total == pytest.approx(resps[0].latency_s, rel=1e-9)
        assert root.wall == pytest.approx(resps[0].latency_s, rel=1e-9)
        assert root.attrs["status"] == "ok"
        # the completed tree landed in the slow-query log
        entries = fd.slow_log.entries()
        assert len(entries) == 1
        assert entries[0][1] is root

    def test_multi_request_batch_links_members_to_batch_root(self):
        eng, _ = _engine(512)
        fd = FrontDoor(eng, max_batch=4, max_wait=1e-3, tracer=Tracer())
        reqs = [
            Request(_CF, Query({"k0": Eq(i)}), arrival_s=i * 1e-5)
            for i in range(3)
        ]
        resps = fd.serve(reqs)
        assert all(r.ok for r in resps)
        roots = fd.tracer.roots
        batch_roots = [r for r in roots if r.name == "frontdoor.batch"]
        req_roots = [r for r in roots if r.name == "frontdoor.request"]
        assert len(batch_roots) == 1 and len(req_roots) == 3
        bid = batch_roots[0].span_id
        for r in req_roots:
            svc = r.find("frontdoor.service")
            assert svc is not None and svc.attrs["batch"] == bid
        assert batch_roots[0].find("engine.read_many") is not None
        _assert_tree_integrity(fd.tracer)


# -- exports -----------------------------------------------------------------


class TestExport:
    def _tree(self):
        tr = Tracer(clock=TickClock())
        root = tr.root("a")
        root.child("b").end()
        root.end()
        return root

    def test_slow_query_log_keeps_k_slowest(self):
        log = SlowQueryLog(2)
        spans = []
        for i, lat in enumerate((0.3, 0.1, 0.5, 0.2)):
            s = self._tree()
            spans.append(s)
            log.offer(s, latency=lat)
        got = log.entries()
        assert [lat for lat, _ in got] == [0.5, 0.3]

    def test_jsonl_round_trip_and_determinism(self):
        root = self._tree()
        line = span_to_line(root, latency=1.5)
        assert line == span_to_line(root, latency=1.5)
        buf = io.StringIO()
        n = dump_jsonl([(1.5, root), root], buf)
        assert n == 2
        docs = load_jsonl(io.StringIO(buf.getvalue()))
        assert docs[0]["latency"] == 1.5
        assert docs[0]["tree"]["name"] == "a"
        assert docs[1]["name"] == "a"

    def test_load_jsonl_rejects_malformed(self):
        with pytest.raises(ValueError, match="line 1"):
            load_jsonl(io.StringIO("{not json\n"))
        with pytest.raises(ValueError):
            load_jsonl(io.StringIO('{"no_name": 1}\n'))

    def test_stage_totals_and_format_tree(self):
        root = self._tree()
        totals = stage_totals([root])
        assert totals["a"]["count"] == 1
        assert "a" in format_tree(root, unit="ticks")

    def test_report_cli(self, tmp_path):
        out = tmp_path / "t.jsonl"
        dump_jsonl([self._tree()], str(out))
        assert obs_main([str(out), "--unit", "ticks"]) == 0
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert obs_main([str(empty)]) == 1
        bad = tmp_path / "bad.jsonl"
        bad.write_text("{nope\n")
        assert obs_main([str(bad)]) == 1


# -- determinism: byte-identical chaos traces --------------------------------


class TestChaosTraceDeterminism:
    def _run(self):
        tracer = Tracer(clock=TickClock())
        harness = ChaosHarness(
            seed=3, n_steps=8, n_rows=400, write_rows=40, n_probes=3,
            probe_every=3, memtable_rows=120, tracer=tracer,
        )
        report = harness.run()
        assert report.ok, report.failures
        buf = io.StringIO()
        dump_jsonl(tracer.roots, buf)
        return buf.getvalue()

    def test_same_seed_same_bytes(self):
        a = self._run()
        b = self._run()
        assert a, "traced chaos run exported no span trees"
        assert a == b, "same seeded schedule must export identical traces"


# -- materialized views: span stages + counters ------------------------------


class TestViewObservability:
    def test_view_serve_span_and_counters(self):
        eng, _ = _engine(512, device_resident=True, views=True)
        tracer = Tracer(clock=TickClock())
        root = tracer.root("test.root")
        # no filters → view-eligible on every layout
        q = Query({}, agg="sum", value_col="metric")
        eng.read_many(_CF, [q], trace=root)
        root.end()
        _assert_tree_integrity(tracer)
        sv = root.find("view.serve")
        assert sv is not None and sv.t_end is not None
        assert sv.attrs["queries"] == 1
        assert "boundary_rows" in sv.attrs
        assert eng.stats["view_hits"] == 1
        assert eng.stats["view_boundary_rows"] == sv.attrs["boundary_rows"]

    def test_view_build_span_on_flush(self):
        eng, _ = _engine(512, device_resident=True, views=True)
        tracer = Tracer(clock=TickClock())
        root = tracer.root("test.root")
        kc = {c: np.arange(6, dtype=np.int64) for c in ("k0", "k1", "k2")}
        vc = {"metric": np.ones(6)}
        eng.write(_CF, kc, vc, trace=root)
        root.end()
        _assert_tree_integrity(tracer)
        builds = root.find_all("view.build")
        assert builds, "write-through flush must record view.build spans"
        for s in builds:
            assert s.attrs.get("incremental") is True
            assert s.attrs["rows"] == 6
        # incremental extensions are NOT rebuilds
        assert eng.stats["view_rebuilds"] == 0

    def test_non_view_engine_emits_no_view_stages(self):
        eng, _ = _engine(512, device_resident=True)
        tracer = Tracer(clock=TickClock())
        root = tracer.root("test.root")
        eng.read_many(_CF, [Query({}, agg="sum", value_col="metric")],
                      trace=root)
        root.end()
        assert root.find("view.serve") is None
        assert eng.stats["view_hits"] == 0

    @pytest.mark.parametrize(
        "query, boundary",
        [
            # one slab inside the single row block: a partly covered
            # block, rescanned at its boundary
            (Query({"k0": Eq(3)}, agg="sum", value_col="metric"), True),
            # every row: the block is wholly covered, its partial serves
            (Query({}, agg="sum", value_col="metric"), False),
        ],
    )
    def test_view_boundary_span_only_when_blocks_are_rescanned(
        self, query, boundary
    ):
        eng, _ = _engine(512, device_resident=True, views=True)
        tracer = Tracer()
        root = tracer.root("test.root")
        eng.read_many(_CF, [query], trace=root)
        root.end()
        _assert_tree_integrity(tracer)
        sv = root.find("view.serve")
        assert (sv.attrs["boundary_blocks"] > 0) is boundary
        bs = sv.find_all("view.boundary")
        assert len(bs) == int(boundary)
        assert root.find_all("view.boundary") == bs
        for b in bs:
            assert b.parent_id == sv.span_id
            assert b.attrs["pairs"] == sv.attrs["boundary_blocks"]
            assert b.t_end is not None and 0 < b.wall <= sv.wall


# -- the profiler's clock ----------------------------------------------------


def _host_events(trace_dir) -> dict[str, list[float]]:
    """``{name: [seconds, ...]}`` of the ``test.*`` events on the host
    planes of the profiler trace written under ``trace_dir``."""
    import jax

    (path,) = trace_dir.glob("**/*.xplane.pb")
    out: dict[str, list[float]] = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("test."):
                    out.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
    return out


class TestProfilerClock:
    def test_wall_clock_spans_reach_the_profiler_host_trace(self, tmp_path):
        import threading
        import time

        import jax

        wall, ticks = Tracer(), Tracer(clock=TickClock())
        nap = 5e-3
        jax.profiler.start_trace(str(tmp_path))
        try:
            root = wall.root("test.root")
            child = root.child("test.child")
            time.sleep(nap)
            child.end()
            # closed out of order: the outer span ends first
            outer = root.child("test.outer")
            inner = outer.child("test.inner")
            time.sleep(nap)
            outer.end()
            time.sleep(nap)
            inner.end()
            # ended on another thread, as a flush-pool span is
            pooled = root.child("test.pooled")
            time.sleep(nap)
            t = threading.Thread(target=pooled.end)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
            # virtual-clock and tick-clock spans write no event
            root.child("test.virtual", t=1.0).end(t=2.0)
            ticks.root("test.tick").child("test.tick_child").end()
            root.end()
        finally:
            jax.profiler.stop_trace()
        events = _host_events(tmp_path)
        spans = {s.name: s for s in walk(root)}
        assert set(events) == set(spans) - {"test.virtual"}
        for name, durations in events.items():
            (secs,) = durations
            w = spans[name].wall
            # the event brackets the span's own clock reads
            assert w - 1e-6 <= secs <= 1.5 * w + 1e-3, (name, secs, w)
        assert events["test.inner"][0] > events["test.outer"][0] + nap / 2
