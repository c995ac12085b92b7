"""Quickstart: heterogeneous replicas in 60 seconds.

Builds a 3-replica column family over a simulated multi-dimensional
dataset, lets HRCA pick the replica layouts for a query workload, and
compares rows-scanned / latency against the best single ("traditional")
layout an expert could pick. Run:

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import HREngine, random_workload
from repro.core.tpch import generate_simulation


def main() -> None:
    print("== Heterogeneous Replica quickstart ==")
    kc, vc, schema = generate_simulation(n_rows=200_000, n_keys=4, seed=0)
    rng = np.random.default_rng(1)
    workload = random_workload(rng, schema, list(kc), n_queries=40, value_col="metric")

    engine = HREngine(n_nodes=6)
    engine.create_column_family(
        "tr", kc, vc, replication_factor=3, mechanism="TR",
        workload=workload, schema=schema,
    )
    cf = engine.create_column_family(
        "hr", kc, vc, replication_factor=3, mechanism="HR",
        workload=workload, schema=schema, hrca_kwargs={"k_max": 2000, "seed": 0},
    )
    print("TR layout  (all replicas):", engine.layouts("tr")[0])
    print("HR layouts (per replica): ", *engine.layouts("hr"))
    print(f"HRCA: cost {cf.hrca_result.initial_cost:.0f} → {cf.hrca_result.cost:.0f} "
          f"in {cf.hrca_result.wall_seconds:.2f}s")

    totals = {"tr": [0.0, 0], "hr": [0.0, 0]}
    for q in workload.queries:
        for mech in ("tr", "hr"):
            res, rep = engine.read(mech, q)
            totals[mech][0] += rep.wall_seconds
            totals[mech][1] += rep.rows_scanned
    n = len(workload)
    print(f"\n{'':14s}{'avg latency':>14s}{'avg rows scanned':>18s}")
    for mech in ("tr", "hr"):
        print(f"{mech.upper():14s}{totals[mech][0]/n*1e6:>11.0f} us{totals[mech][1]/n:>18.0f}")
    print(f"\nHR gain: {totals['tr'][1]/max(totals['hr'][1],1):.1f}x fewer rows, "
          f"{totals['tr'][0]/max(totals['hr'][0],1e-12):.1f}x faster")

    # recovery: same dataset, different serialization
    victim = cf.replicas[0].node_id
    engine.fail_node(victim)
    secs = engine.recover_node(victim)
    print(f"node {victim} failed and recovered (replica re-sorted) in {secs*1e3:.0f} ms")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
