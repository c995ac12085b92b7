"""Batched serving: model decode batches AND batched HR reads.

Default mode prefills a prompt batch and decodes with a KV cache using
the smoke-size StarCoder2 config on CPU; under a TPU mesh the same entry
point runs the sequence-parallel decode path (seq-sharded KV with
cross-chip flash-decoding). Run:

    PYTHONPATH=src python examples/serve_batch.py [--arch hymba-1.5b]

``--hr`` serves a batch of TPC-H-style queries through the HR engine's
batched read path instead: one ``read_many`` call ranks replicas for
the whole batch (vectorized cost model), groups queries per chosen
replica, and answers each group with a single vectorized slab scan —
compare its queries/sec against the sequential ``read`` loop:

    PYTHONPATH=src python examples/serve_batch.py --hr --batch 64

``--frontdoor`` goes one layer up: an *open-loop* Poisson arrival
stream (requests carry deadlines, priorities, and mixed consistency)
is pushed through the serving front door, which coalesces arrivals
into dynamic ``read_many`` batches and sheds/degrades under pressure.
Prints client-observed p50/p99 (queue wait included) and the refusal
breakdown against the closed-loop ``read_many`` capacity:

    PYTHONPATH=src python examples/serve_batch.py --frontdoor --load 2

``--views`` contrasts the materialized per-slab aggregate views against
the fused full-scan engine on the same wide-slab aggregate batch: two
device-resident twins of the orders table (one with views, one
without) answer an identical batch of range-sum/count queries, the
answers are asserted bit-identical, and the traced pass prints each
engine's per-stage wall breakdown — the views engine's time lands in
``view.serve`` (stored block partials + boundary rescans) where the
fused engine's lands in the full-table scan stages:

    PYTHONPATH=src python examples/serve_batch.py --views --batch 64

``--trace`` attaches a :class:`repro.obs.Tracer` to the front door:
every request grows a ``frontdoor.request`` span tree (admission →
queue → service, with the engine's plan/scan/digest subtree below),
and the demo prints the per-stage wall breakdown plus the slowest
request's full tree — where an overloaded request's time actually
went. ``--trace-out out.jsonl`` additionally dumps the K slowest
trees as JSON-lines for the offline report CLI:

    PYTHONPATH=src python examples/serve_batch.py --frontdoor --trace \\
        --trace-out /tmp/serve.jsonl
    PYTHONPATH=src python -m repro.obs /tmp/serve.jsonl
"""

import argparse
import itertools
import time


def run_model(args) -> None:
    from repro.configs.registry import ARCHS, get_smoke
    from repro.launch.serve import serve_batch

    if args.arch not in ARCHS:
        raise SystemExit(
            f"unknown --arch {args.arch!r}; choices: {', '.join(sorted(ARCHS))}"
        )
    cfg = get_smoke(args.arch)
    print(f"serving {cfg.name}: batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    out = serve_batch(cfg, batch_size=args.batch, prompt_len=args.prompt_len,
                      gen_tokens=args.gen)
    print(f"prefill: {out['prefill_s']*1e3:.1f} ms")
    print(f"decode:  {out['decode_tok_s']:.1f} tok/s "
          f"({out['decode_s']*1e3:.1f} ms for {args.gen} steps)")
    print(f"sample continuation (greedy): {out['tokens'][0].tolist()}")


def run_hr(args) -> None:
    from repro.core import HREngine
    from repro.core.tpch import generate_orders, orders_schema, q1_q2_workload

    n_rows = args.rows
    print(f"HR batched read demo: {n_rows} orders rows, batch={args.batch}")
    kc, vc = generate_orders(1.0, seed=0, rows_per_sf=n_rows)
    wl = q1_q2_workload(args.batch, seed=1, n_rows=n_rows)
    # no result cache: the demo times the scheduling+scan paths, and the
    # sequential loop would otherwise pre-warm the batch's cache entries
    eng = HREngine(n_nodes=6, result_cache=False)
    eng.create_column_family(
        "orders", kc, vc, replication_factor=3, mechanism="HR", workload=wl,
        schema=orders_schema(), hrca_kwargs={"k_max": 2500, "seed": 0},
    )
    print(f"replica layouts: {[list(a) for a in eng.layouts('orders')]}")

    cf = eng.column_families["orders"]
    cf.rr_counter = itertools.count()  # same tie-break draws for both paths
    t0 = time.perf_counter()
    seq = [eng.read("orders", q) for q in wl.queries]
    t_seq = time.perf_counter() - t0
    cf.rr_counter = itertools.count()
    t0 = time.perf_counter()
    bat = eng.read_many("orders", wl.queries)
    t_bat = time.perf_counter() - t0

    assert all(rb.value == rs.value for (rs, _), (rb, _) in zip(seq, bat))
    total = sum(r.value for r, _ in bat)
    per_replica: dict[int, int] = {}
    for _, rep in bat:
        per_replica[rep.replica_id] = per_replica.get(rep.replica_id, 0) + 1
    print(f"sequential: {args.batch / t_seq:,.0f} q/s ({t_seq*1e3:.1f} ms)")
    print(f"read_many:  {args.batch / t_bat:,.0f} q/s ({t_bat*1e3:.1f} ms) "
          f"— {t_seq / t_bat:.1f}x")
    print(f"routing: {per_replica} (queries per replica), Σvalue={total:,.0f}")


def run_views(args) -> None:
    import numpy as np

    from repro.core import HREngine, Query, Range
    from repro.core.tpch import generate_orders, n_custkey, orders_schema
    from repro.obs import Tracer, stage_totals

    n_rows = args.rows
    print(f"materialized-view demo: {n_rows} orders rows, batch={args.batch}")
    kc, vc = generate_orders(1.0, seed=0, rows_per_sf=n_rows)
    # explicit rotated layouts so replica 0 leads with custkey: the
    # wide-slab custkey ranges below are view-eligible there, and the
    # planner's capped view cost routes them to it
    layouts = [
        ("custkey", "orderdate", "clerk"),
        ("orderdate", "clerk", "custkey"),
        ("clerk", "custkey", "orderdate"),
    ]

    def build(views: bool) -> HREngine:
        eng = HREngine(n_nodes=6, result_cache=False)
        eng.create_column_family(
            "orders", kc, vc, replication_factor=3, layouts=layouts,
            schema=orders_schema(), device_resident=True, views=views,
        )
        return eng

    ev, ef = build(True), build(False)

    # wide-slab eligible aggregates: each range covers most of custkey,
    # so the fused engine streams most of the table per query while the
    # view path folds stored block partials + at most two boundary blocks
    rng = np.random.default_rng(2)
    nck = n_custkey(n_rows)
    queries = [
        Query(
            filters={"custkey": Range(int(rng.integers(0, nck // 4)),
                                      int(rng.integers(nck // 2, nck + 1)))},
            agg="sum" if i % 2 == 0 else "count",
            value_col="totalprice",
        )
        for i in range(args.batch)
    ]

    # warm-up pass doubles as the correctness bar: view-routed answers
    # must be bit-identical to the full-scan engine's
    rv = ev.read_many("orders", queries)
    rf = ef.read_many("orders", queries)
    assert all(a.value == b.value for (a, _), (b, _) in zip(rv, rf))
    print(f"bit-identity: {args.batch}/{args.batch} answers match the "
          f"full-scan engine exactly")

    t0 = time.perf_counter()
    ev.read_many("orders", queries)
    t_vw = time.perf_counter() - t0
    t0 = time.perf_counter()
    ef.read_many("orders", queries)
    t_fu = time.perf_counter() - t0
    print(f"full scan:  {args.batch / t_fu:,.0f} q/s ({t_fu*1e3:.1f} ms)")
    print(f"views:      {args.batch / t_vw:,.0f} q/s ({t_vw*1e3:.1f} ms) "
          f"— {t_fu / t_vw:.1f}x")

    # traced pass: the stage-total tables show WHERE each engine spends
    # the batch — view.serve on the views engine vs the full-table scan
    # stages (engine.scan / kernel launches) on the fused one
    for label, eng in (("views engine", ev), ("full-scan engine", ef)):
        tracer = Tracer()
        root = tracer.root("demo.read_many")
        eng.read_many("orders", queries, trace=root)
        root.end()
        print(f"\nper-stage wall breakdown ({label}):")
        for name, row in stage_totals(tracer.roots).items():
            print(f"  {name:<22} n={row['count']:>5}  "
                  f"total={row['total'] * 1e3:>10,.2f} ms")
    s = ev.stats
    print(f"\nview counters: view_hits={s['view_hits']} "
          f"view_boundary_rows={s['view_boundary_rows']} "
          f"view_rebuilds={s['view_rebuilds']}")


def run_frontdoor(args) -> None:
    import numpy as np

    from repro.core import HREngine, QUORUM
    from repro.core.tpch import generate_orders, orders_schema, q1_q2_workload
    from repro.serving.frontdoor import FrontDoor, Request

    n_rows = args.rows
    print(f"front-door serving demo: {n_rows} orders rows, "
          f"{args.requests} requests at {args.load:g}x capacity")
    kc, vc = generate_orders(1.0, seed=0, rows_per_sf=n_rows)
    wl = q1_q2_workload(args.requests, seed=1, n_rows=n_rows)
    eng = HREngine(n_nodes=6, result_cache=False)
    eng.create_column_family(
        "orders", kc, vc, replication_factor=3, mechanism="HR", workload=wl,
        schema=orders_schema(), hrca_kwargs={"k_max": 2500, "seed": 0},
    )
    queries = list(wl.queries)

    # closed-loop capacity: back-to-back full read_many batches — the
    # baseline the open-loop offered load is expressed against
    t0 = time.perf_counter()
    for i in range(0, len(queries), args.batch):
        eng.read_many("orders", queries[i : i + args.batch])
    t_closed = time.perf_counter() - t0
    closed_qps = len(queries) / t_closed
    print(f"closed-loop read_many: {closed_qps:,.0f} q/s "
          f"({t_closed * 1e3:.1f} ms)")

    rng = np.random.default_rng(2)
    rate = args.load * closed_qps
    arrivals = np.cumsum(rng.exponential(1.0 / rate, len(queries)))
    reqs = [
        Request(
            "orders", q, arrival_s=float(arrivals[i]),
            deadline_s=args.deadline * 1e-3,
            priority=int(rng.integers(0, 3)),
            consistency=QUORUM if rng.random() < 0.25 else "ONE",
        )
        for i, q in enumerate(queries)
    ]
    tracer = None
    if args.trace or args.trace_out:
        from repro.obs import Tracer

        tracer = Tracer()
    fd = FrontDoor(
        eng, max_batch=args.batch, max_wait=2e-3, max_queue=256,
        tracer=tracer,
    )
    resps = fd.serve(reqs)
    s = fd.stats

    ok = [r for r in resps if r.ok]
    if ok:
        lat = np.asarray([r.latency_s for r in ok])
        p50, p99 = np.percentile(lat, 50) * 1e3, np.percentile(lat, 99) * 1e3
        print(f"open-loop through front door: {len(ok)}/{len(reqs)} ok, "
              f"p50={p50:.2f} ms p99={p99:.2f} ms (queue wait included)")
    else:
        print(f"open-loop through front door: 0/{len(reqs)} ok")
    print(f"refusals: shed_overload={s['shed_overload']} "
          f"shed_deadline={s['shed_deadline']} "
          f"rejected_queue_full={s['rejected_queue_full']}")
    print(f"degradation: consistency_degraded={s['consistency_degraded']} "
          f"hedged_batches={s['hedged_batches']} "
          f"degrade_recoveries={s['degrade_recoveries']}")
    print(f"batches={s['batches']} max_queue_depth={s['max_queue_depth']}")

    if tracer is not None:
        from repro.obs import dump_jsonl, format_tree, stage_totals

        print("\nper-stage wall breakdown (all request trees):")
        for name, row in stage_totals(tracer.roots).items():
            print(f"  {name:<22} n={row['count']:>5}  "
                  f"total={row['total'] * 1e3:>10,.2f} ms")
        slowest = fd.slow_log.entries()
        if slowest:
            lat, tree = slowest[0]
            print(f"\nslowest request ({lat * 1e3:.2f} ms):")
            print(format_tree(tree, unit="ms"))
        if args.trace_out:
            n = dump_jsonl(slowest, args.trace_out)
            print(f"\nwrote {n} slowest span trees to {args.trace_out} "
                  f"(render with: python -m repro.obs {args.trace_out})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hr", action="store_true",
                    help="serve a query batch via HREngine.read_many")
    ap.add_argument("--frontdoor", action="store_true",
                    help="open-loop arrivals through the serving front door")
    ap.add_argument("--views", action="store_true",
                    help="materialized per-slab aggregate views vs the "
                         "fused full scan, with traced stage breakdowns")
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--batch", type=int, default=None,
                    help="default: 4 (model mode), 64 (--hr/--frontdoor)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--rows", type=int, default=120_000,
                    help="orders rows for --hr/--frontdoor mode")
    ap.add_argument("--requests", type=int, default=400,
                    help="open-loop request count (--frontdoor)")
    ap.add_argument("--load", type=float, default=2.0,
                    help="offered load as a multiple of closed-loop capacity")
    ap.add_argument("--deadline", type=float, default=50.0,
                    help="per-request deadline in ms (--frontdoor)")
    ap.add_argument("--trace", action="store_true",
                    help="trace every request through the front door and "
                         "print the stage breakdown + slowest tree")
    ap.add_argument("--trace-out", default=None, metavar="OUT.jsonl",
                    help="dump the slowest span trees as JSON-lines "
                         "(implies tracing; render with python -m repro.obs)")
    args = ap.parse_args()
    if args.batch is None:
        args.batch = 64 if (args.hr or args.frontdoor or args.views) else 4
    if args.views:
        run_views(args)
    elif args.frontdoor:
        run_frontdoor(args)
    elif args.hr:
        run_hr(args)
    else:
        run_model(args)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
