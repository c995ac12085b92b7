"""Smoke run of the store's device read path on one TPU chip.

Builds the paper's deployment — TPC-H ``orders`` at scale factor 5
(7.5 M rows), clustering keys (custkey, orderdate, clerk), RF = 3
heterogeneous layouts chosen by HRCA over the Q1/Q2 workload, every
replica device-resident with materialized views — and drives it through
the normal entry points: ``read_many``, the ``FrontDoor`` at ONE and
QUORUM, a view-served wide-slab batch, a ``select`` batch, and a write
with flush and compaction followed by more reads. Every answer is
checked against the host numpy engine built from the same seeded data.

    python chip_smoke.py [--scale-factor 5] [--seed 0]

It refuses to run anywhere but a TPU. Per-phase wall times and compile
counts are smoke timings of one cold run, not metrics. The last line of
standard output is one JSON object naming the device; the exit code is
non-zero if any phase failed.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

# Device sums accumulate in float32 (ROADMAP R4 makes them exact); the
# numpy reference sums in float64. A device sum may differ from the
# reference by this fraction of the reference's magnitude.
SUM_RTOL = 1e-4

CF = "orders"


def require_tpu():
    """The first JAX device, or exit non-zero before anything is built:
    with JAX_PLATFORMS unset JAX falls back to the CPU when the TPU does
    not initialise, and a CPU run would prove nothing here."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found (JAX platform is {dev.platform!r}); "
            "this script only runs on a TPU"
        )
    return dev


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


class Smoke:
    """Phase runner: times each phase, counts the backend compiles in
    it, and collects failed checks (any failure fails the run)."""

    def __init__(self, dev):
        import jax

        self.dev = dev
        self.failures: list[str] = []
        self.compiles = 0

        def on_event(name, secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            print(f"FAIL: {what}", flush=True)

    def phase(self, name, fn, *args):
        c0, t0 = self.compiles, time.perf_counter()
        out = fn(*args)
        print(
            f"phase {name}: {time.perf_counter() - t0:.3f} s wall, "
            f"{self.compiles - c0} compiles (smoke timing, not a metric)",
            flush=True,
        )
        return out


def build(sf: float, seed: int):
    """The device engine under test and its numpy reference twin, same
    data and same layouts."""
    from repro.core import HREngine
    from repro.core.storage import CompactionPolicy
    from repro.core.tpch import generate_orders, orders_schema, q1_q2_workload

    kc, vc = generate_orders(sf, seed=seed)
    n = len(kc["custkey"])
    wl = q1_q2_workload(500, seed=seed + 1, n_rows=n)
    eng = HREngine(n_nodes=6, result_cache=False)
    # appended_frac=0 compacts at every flush that leaves more than one
    # run: the write phase exercises the on-device k-way merge
    eng.create_column_family(
        CF, kc, vc, replication_factor=3, mechanism="HR", workload=wl,
        schema=orders_schema(), hrca_kwargs={"k_max": 2500, "seed": seed},
        device_resident=True, views=True,
        compaction=CompactionPolicy(appended_frac=0.0),
    )
    ref = HREngine(n_nodes=6, result_cache=False)
    ref.create_column_family(
        CF, kc, vc, replication_factor=3, layouts=eng.layouts(CF),
        schema=orders_schema(),
    )
    return eng, ref, n


def tables(eng):
    cf = eng.column_families[CF]
    return {r.replica_id: eng.nodes[r.node_id].tables[(CF, r.replica_id)] for r in cf.replicas}


def check_residency(s: Smoke, eng, n: int) -> int:
    total = 0
    for rid, t in tables(eng).items():
        st = t._device
        s.check(st is not None and t.has_views, f"replica {rid} is not resident with views")
        for name in ("keys", "values_tile"):
            arr = st[name]
            total += arr.nbytes
            s.check(arr.devices() == {s.dev}, f"replica {rid} {name} is on {arr.devices()}")
    print(f"resident bytes: {total} over 3 replicas of {n} rows "
          f"({total / (3 * n):.2f} B per row per replica)", flush=True)
    return total


def compare(s: Smoke, label: str, eng, ref, queries, got) -> float:
    """Each answer against the numpy reference on the replica layout that
    served it: counts, rows scanned and select row sets exactly, sums
    within SUM_RTOL. Returns the largest relative sum error seen."""
    ref_tables = tables(ref)
    worst = 0.0
    for i, (q, (res, rep)) in enumerate(zip(queries, got)):
        want = ref_tables[rep.replica_id].execute(q)
        where = f"{label} query {i} ({q.agg}) on replica {rep.replica_id}"
        s.check(res.rows_matched == want.rows_matched,
                f"{where}: matched {res.rows_matched} != {want.rows_matched}")
        s.check(res.rows_scanned == want.rows_scanned,
                f"{where}: scanned {res.rows_scanned} != {want.rows_scanned}")
        if q.agg == "select":
            s.check(np.array_equal(np.sort(res.selected), np.sort(want.selected)),
                    f"{where}: selected rows differ")
        elif q.agg == "count":
            s.check(res.value == want.value, f"{where}: count {res.value} != {want.value}")
        else:
            err = abs(res.value - want.value) / max(abs(want.value), 1.0)
            worst = max(worst, err)
            s.check(err <= SUM_RTOL, f"{where}: sum {res.value} vs {want.value} (rel {err:.3g})")
    print(f"{label}: {len(queries)} answers checked, max relative sum error "
          f"{worst:.3g} (bound {SUM_RTOL})", flush=True)
    return worst


def view_queries(eng, n: int, seed: int, count: int = 96):
    """Wide-slab sum/count ranges on each replica's leading column: an
    equality-free slab the views answer from block partials."""
    from repro.core import Query, Range
    from repro.core.tpch import N_DATES, n_clerks, n_custkey

    domain = {"custkey": n_custkey(n), "orderdate": N_DATES, "clerk": n_clerks(n)}
    leads = [layout[0] for layout in eng.layouts(CF)]
    rng = np.random.default_rng(seed + 2)
    out = []
    for i in range(count):
        c = leads[i % len(leads)]
        d = domain[c]
        lo = int(rng.integers(0, d // 4))
        hi = int(rng.integers(d // 2, d + 1))
        out.append(Query(filters={c: Range(lo, hi)},
                         agg="sum" if i % 2 == 0 else "count", value_col="totalprice"))
    return out


def select_queries(n: int, seed: int, count: int = 48):
    """Selects of a few rows (Q1/Q2 shapes) and of ~1500 rows (one
    clerk), so the compaction output spans several 128-slot tiles."""
    from repro.core import Eq, Query, Range
    from repro.core.tpch import N_DATES, n_clerks, n_custkey

    rng = np.random.default_rng(seed + 3)
    nck, ncl = n_custkey(n), n_clerks(n)
    out = []
    for i in range(count):
        kind = i % 3
        if kind == 0:
            f = {"custkey": Eq(int(rng.integers(0, nck))),
                 "orderdate": Range(int(rng.integers(0, N_DATES // 2)), N_DATES)}
        elif kind == 1:
            f = {"orderdate": Eq(int(rng.integers(0, N_DATES))),
                 "clerk": Eq(int(rng.integers(0, ncl)))}
        else:
            f = {"clerk": Eq(int(rng.integers(0, ncl)))}
        out.append(Query(filters=f, agg="select"))
    return out


def check_views_match_fused(s: Smoke, eng, queries, got) -> None:
    """View-served answers equal the fused full scan over the same
    resident arrays bit for bit (and fused answers equal themselves)."""
    from repro.kernels import table_execute_device_many

    by_rid: dict[int, list[int]] = {}
    for i, (_res, rep) in enumerate(got):
        by_rid.setdefault(rep.replica_id, []).append(i)
    tabs = tables(eng)
    for rid, idx in by_rid.items():
        fused = table_execute_device_many(tabs[rid], [queries[i] for i in idx])
        for i, f in zip(idx, fused):
            v = got[i][0].value
            s.check(np.float32(v) == np.float32(f.value),
                    f"view answer {v!r} != fused {f.value!r} (query {i}, replica {rid})")
    print(f"views vs fused scan: {len(queries)} answers compared bit for bit", flush=True)


def run_reads(s: Smoke, label: str, eng, ref, n: int, seed: int) -> None:
    from repro.core.tpch import q1_q2_workload

    # the planner sends Q1 and Q2 to the replica whose layout makes them
    # view-eligible; the fused-scan comparison below then runs each
    # replica's group (more queries than one launch carries) through
    # the fused kernel too
    batch = list(q1_q2_workload(512, seed=seed + 4, n_rows=n).queries)
    got = s.phase(f"{label}.read_many", eng.read_many, CF, batch)
    compare(s, f"{label} read_many", eng, ref, batch, got)
    s.phase(f"{label}.fused", check_views_match_fused, s, eng, batch, got)

    vq = view_queries(eng, n, seed)
    hits0 = eng.stats["view_hits"]
    got = s.phase(f"{label}.views", eng.read_many, CF, vq)
    s.check(eng.stats["view_hits"] - hits0 == len(vq),
            f"{label}: views answered {eng.stats['view_hits'] - hits0} of {len(vq)}")
    compare(s, f"{label} views", eng, ref, vq, got)
    check_views_match_fused(s, eng, vq, got)

    sq = select_queries(n, seed)
    got = s.phase(f"{label}.select", eng.read_many, CF, sq)
    compare(s, f"{label} select", eng, ref, sq, got)


def run_frontdoor(s: Smoke, eng, ref, n: int, seed: int) -> None:
    """Requests through the front door: Q1/Q2 sums at ONE, counts at
    QUORUM. QUORUM compares layout-independent digests across replicas;
    float32 sums taken in different row orders can differ in the last
    bit, so the QUORUM requests are counts, whose answers are exact."""
    from repro.core import QUORUM, Query
    from repro.core.tpch import q1_q2_workload
    from repro.serving.frontdoor import FrontDoor, Request

    qs = list(q1_q2_workload(300, seed=seed + 5, n_rows=n).queries)
    reqs = []
    for i, q in enumerate(qs):
        if i % 3 == 2:
            q = Query(filters=q.filters, agg="count")
        reqs.append(Request(CF, q, arrival_s=i * 1e-4,
                            consistency=QUORUM if q.agg == "count" else "ONE"))
    # long batching window and a queue that holds every request: nothing
    # is shed or degraded, so every answer is checked
    fd = FrontDoor(eng, max_batch=64, max_wait=30.0, max_queue=len(reqs), shed_fill=1.0)
    resp = s.phase("frontdoor.serve", fd.serve, reqs)
    s.check(all(r.ok for r in resp), "front door refused requests: "
            + str(sorted({r.status for r in resp if not r.ok})))
    s.check(all(r.consistency_used == q.consistency for r, q in zip(resp, reqs)),
            "front door degraded a request's consistency")
    ok = [(q.query, (r.result, r.report)) for q, r in zip(reqs, resp) if r.ok]
    compare(s, "frontdoor", eng, ref, [q for q, _ in ok], [g for _, g in ok])
    print(f"frontdoor: {len(resp)} requests, {sum(r.consistency == QUORUM for r in reqs)} "
          f"at QUORUM, batches={fd.stats['batches']}", flush=True)


def run_write(s: Smoke, eng, ref, n: int, seed: int, rows: int = 4096) -> None:
    """One write through the durable path on both engines. Write-through
    flushes every replica; the compaction policy then merges the run
    stack on device (merge_rank), views rebuild, and the column stats
    refresh through the ecdf_hist kernel."""
    from repro.core.tpch import N_DATES, n_clerks, n_custkey

    rng = np.random.default_rng(seed + 6)
    kc = {"custkey": rng.integers(0, n_custkey(n), rows),
          "orderdate": rng.integers(0, N_DATES, rows),
          "clerk": rng.integers(0, n_clerks(n), rows)}
    vc = {"totalprice": np.round(rng.uniform(857.71, 555285.16, rows), 2),
          "shippriority": rng.integers(0, 5, rows).astype(np.float64)}
    before = eng.stats
    s.phase("write", eng.write, CF, kc, vc)
    ref.write(CF, kc, vc)
    after = eng.stats
    s.check(after["memtable_flushes"] - before["memtable_flushes"] == 3, "write did not flush 3 replicas")
    s.check(after["compactions"] - before["compactions"] == 3, "write did not compact 3 replicas")
    for rid, t in tables(eng).items():
        s.check(t._device["n_runs"] == 1 and t._device["n_rows"] == n + rows,
                f"replica {rid} after compaction: {t._device['n_runs']} runs, {t._device['n_rows']} rows")
    dev_stats = eng.column_families[CF].stats.columns
    ref_stats = ref.column_families[CF].stats.columns
    for c in dev_stats:
        s.check(np.array_equal(dev_stats[c].counts, ref_stats[c].counts),
                f"column stats for {c} differ from the numpy bincount")


def run_traced(s: Smoke, eng, n: int, seed: int) -> None:
    from repro.core.tpch import q1_q2_workload
    from repro.obs import Tracer, stage_totals

    batch = (
        list(q1_q2_workload(64, seed=seed + 7, n_rows=n).queries)
        + view_queries(eng, n, seed, 16)
        + select_queries(n, seed, 6)
    )
    tracer = Tracer()
    root = tracer.root("smoke.read_many")
    eng.read_many(CF, batch, trace=root)
    root.end()
    stages = stage_totals(tracer.roots)
    for name in ("kernel.scan_launch", "view.serve"):
        s.check(name in stages, f"traced batch has no {name} span")
    s.check("engine.host_scan" not in stages, "traced batch ran the host numpy scan")
    print("traced stages: " + ", ".join(f"{k}={v['count']}" for k, v in stages.items()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale-factor", type=float, default=5.0,
                    help="TPC-H scale factor of orders (1.5 M rows each)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    import jax

    print(f"device: {dev.device_kind}, {len(jax.devices())} device(s); jax "
          f"{jax.__version__}, jaxlib {_version('jaxlib')}, libtpu {_version('libtpu')}",
          flush=True)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)

    s = Smoke(dev)
    eng, ref, n = s.phase("build", build, args.scale_factor, args.seed)
    print(f"orders: {n} rows, layouts {[list(a) for a in eng.layouts(CF)]}", flush=True)
    check_residency(s, eng, n)
    run_reads(s, "cold", eng, ref, n, args.seed)
    run_frontdoor(s, eng, ref, n, args.seed)
    run_write(s, eng, ref, n, args.seed)
    run_reads(s, "after_write", eng, ref, n, args.seed)
    run_traced(s, eng, n, args.seed)
    if s.failures:
        print(f"chip_smoke: {len(s.failures)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
