"""Benchmark runner — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and optionally writes them
to --csv). Default sizes finish on CPU in a few minutes; --full uses
paper-scale row counts; --smoke runs every registered benchmark at toy
scale (the pre-merge gate, see scripts/ci.sh).
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes (slow)")
    ap.add_argument(
        "--smoke", action="store_true",
        help="toy-scale pass over every registered benchmark (CI gate)",
    )
    ap.add_argument("--csv", default=None)
    ap.add_argument("--json", default=None)
    ap.add_argument(
        "--only", default=None,
        help=(
            "comma list: fig4,fig5a,fig5b,fig5c,table1,recovery,hrca,"
            "kernels,batched,views,write_queue,partitioned,availability,"
            "serving"
        ),
    )
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        availability,
        batched_read,
        fig4_cost_model,
        fig5a_datasize,
        fig5b_repfactor,
        fig5c_clustering,
        hrca_convergence,
        kernel_bench,
        partitioned_read,
        recovery_bench,
        serving_latency,
        table1_write,
        write_queue,
    )
    from .common import ROWS, flush_csv

    full, smoke = args.full, args.smoke
    results = {}
    print("name,us_per_call,derived")

    def want(k):
        return only is None or k in only

    def size(full_size, default_size, smoke_size):
        return full_size if full else (smoke_size if smoke else default_size)

    if want("fig4"):
        results["fig4"] = fig4_cost_model.run(n_rows=size(1_000_000, 200_000, 20_000))
    if want("fig5a"):
        results["fig5a"] = fig5a_datasize.run(
            rows_per_sf=size(1_500_000, 40_000, 5_000),
            n_queries=size(500, 60, 10),
        )
    if want("fig5b"):
        results["fig5b"] = fig5b_repfactor.run(n_rows=size(10_000_000, 200_000, 20_000))
    if want("fig5c"):
        results["fig5c"] = fig5c_clustering.run(n_rows=size(10_000_000, 200_000, 20_000))
    if want("table1"):
        results["table1"] = table1_write.run(
            total_rows=size(
                (40_000_000, 80_000_000, 120_000_000),
                (40_000, 80_000, 120_000),
                (5_000, 10_000),
            )
        )
    if want("recovery"):
        # smoke numbers feed the regression gate (log-replay + resort
        # rows/sec), same as write_queue below — see scripts/bench_gate.py
        results["recovery"] = recovery_bench.run(n_rows=size(18_000_000, 300_000, 30_000))
    if want("hrca"):
        results["hrca"] = hrca_convergence.run(n_rows=size(1_000_000, 200_000, 20_000))
    if want("kernels"):
        results["kernels"] = kernel_bench.run()
    if want("batched"):
        # smoke exercises the device kernels too (tiny batches, no JSON);
        # extra timing repeats + best-of-N keep the CI regression gate's
        # toy-scale queries/sec out of scheduler-jitter territory
        results["batched"] = batched_read.run(
            n_rows=size(1_500_000, 120_000, 20_000),
            batch_sizes=(8, 16) if smoke else (16, 64, 256),
            device=smoke,
            repeats=11 if smoke else 3,
            best=smoke,
        )
    if want("views"):
        # materialized per-slab views vs the fused full scan on
        # wide-slab eligible aggregates; the smoke views_qps and the
        # views_over_fused_speedup ratio feed the regression gate (the
        # tentpole acceptance: view routing must hold its O(blocks
        # touched) advantage, bit-identical answers asserted in-bench)
        # smoke keeps the full 120k rows: the view advantage IS the
        # O(N) vs O(blocks) gap, and at toy row counts the fused scan
        # is too cheap for the gated >=5x speedup ratio to be stable
        results["views"] = batched_read.run_views(
            n_rows=size(1_500_000, 120_000, 120_000),
            batch_sizes=(8, 16) if smoke else (16, 64, 256),
            repeats=11 if smoke else 3,
            best=smoke,
        )
    if want("partitioned"):
        # q/s vs partition count at fixed dataset size; the smoke
        # p{P}_qps keys feed the regression gate (best-of-N, same
        # jitter rationale as the batched gate). The Zipf --skew
        # section also runs at smoke scale so p{P}_skew_qps (the
        # post-rebalance drain on a vnode ring) is gated too.
        results["partitioned"] = partitioned_read.run(
            n_rows=size(2_000_000, 200_000, 20_000),
            batch=size(256, 64, 16),
            n_batches=size(8, 4, 3),
            partition_counts=(1, 2, 4) if smoke else (1, 2, 4, 8),
            repeats=11 if smoke else 3,
            best=smoke,
            skew=1.3,
            skew_partitions=4 if smoke else 8,
        )
    if want("availability"):
        # hinted-handoff heal vs full log replay, and the QUORUM read
        # tax; the four throughput keys feed the regression gate while
        # hint_speedup / quorum_over_one stay descriptive
        results["availability"] = availability.run(
            n_rows=size(1_000_000, 120_000, 20_000),
            outage_rows=size(20_000, 2_000, 500),
            n_queries=size(64, 16, 8),
            repeats=11 if smoke else 5,
        )
    if want("serving"):
        # open-loop front-door latency vs offered load; the smoke
        # passthrough/direct q/s and per-load p99 keys feed the
        # regression gate (p99 gated lower-is-better, see bench_gate)
        results["serving"] = serving_latency.run(
            n_rows=size(1_000_000, 120_000, 20_000),
            batch=size(64, 64, 16),
            n_requests=size(2_000, 400, 120),
            loads=(0.25, 2.0) if smoke else (0.25, 1.0, 2.0),
            repeats=11 if smoke else 5,
            best=smoke,
        )
    if want("write_queue"):
        results["write_queue"] = write_queue.run(
            n_rows=size(1_000_000, 60_000, 8_000),
            n_batches=size(32, 16, 6),
            batch_rows=size(20_000, 2_000, 400),
        )

    import os

    if args.csv:
        os.makedirs(os.path.dirname(args.csv) or ".", exist_ok=True)
        flush_csv(args.csv)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, default=str)
    print(f"\n[benchmarks] {len(ROWS)} rows emitted", file=sys.stderr)


if __name__ == "__main__":
    main()
