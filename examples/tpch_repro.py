"""Reproduce the paper's TPC-H experiment (Fig 5a/5d) end to end.

Generates the `orders` table at several scale factors, runs 100 Q1/Q2
instances against TR (expert layout) and HR (HRCA layouts), and prints
the latency/row-scan gains. Paper claim: 1–2 orders of magnitude at
SF 5. Run:

    PYTHONPATH=src:. python examples/tpch_repro.py [--rows-per-sf 150000]
"""

import argparse

from benchmarks.fig5a_datasize import run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-per-sf", type=int, default=60_000,
                    help="1_500_000 reproduces the paper's SF scaling exactly")
    ap.add_argument("--queries", type=int, default=100)
    args = ap.parse_args()

    print("== TPC-H orders: TR vs HR (paper Fig 5a/5d) ==")
    results = run(rows_per_sf=args.rows_per_sf, n_queries=args.queries)
    print(f"\n{'SF':>3s} {'TRdef rows':>11s} {'TRexp rows':>11s} {'HR rows':>9s} "
          f"{'gain(def)':>10s} {'gain(exp)':>10s}")
    for sf, r in results.items():
        print(f"{sf:>3d} {r['tr_defined_rows']:>11.0f} {r['tr_expert_rows']:>11.1f} "
              f"{r['hr_rows']:>9.1f} {r['gain_rows']:>9.0f}x {r['gain_vs_expert']:>9.1f}x")
    last = results[max(results)]
    print(f"\nexpert TR layout: {last['tr_expert_layout']}")
    print(f"HR layouts: {last['hr_layouts']}")
    print(f"paper claim C1 (1–2 orders of magnitude vs the declared order): "
          f"measured {last['gain_rows']:.0f}x rows")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
