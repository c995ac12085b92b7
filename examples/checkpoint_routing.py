"""HR checkpoint-replica routing: restore queries pick the cheapest
manifest serialization (paper §2 applied to checkpoint I/O).

Saves a model checkpoint with 3 replica manifests in different
(stack, layer, kind) orders, then costs three restore patterns — full,
layer-range (warm partial restart), by-kind (optimizer-less eval
restore) — on the best vs worst replica. Run:

    PYTHONPATH=src python examples/checkpoint_routing.py
"""

import tempfile

import jax

from repro.checkpoint.layouts import CheckpointRouter
from repro.checkpoint.manager import save_checkpoint
from repro.configs import get_smoke
from repro.core import Eq, Query, Range
from repro.models import lm


def main() -> None:
    cfg = get_smoke("yi-34b")
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 100, {"params": params}, n_chunks=8, replicas=3)
        router = CheckpointRouter(d, 100)
        print("replica manifest layouts:", *router.layouts, sep="\n  ")

        cases = {
            "full restore": Query(filters={}),
            "layer range [0,2)": Query(filters={"layer": Range(0, 2)}),
            "single kind": Query(filters={"kind_id": Eq(0)}),
            "kind 0 of layer 0": Query(filters={"layer": Eq(0), "kind_id": Eq(0)}),
        }
        print(f"\n{'restore query':>22s} {'best span':>10s} {'worst span':>11s} "
              f"{'needed':>7s} {'replica':>8s}")
        for name, q in cases.items():
            best = router.plan(q)
            worst = router.worst_plan(q)
            print(f"{name:>22s} {best.files_span:>10d} {worst.files_span:>11d} "
                  f"{best.files_needed:>7d} {best.replica:>8d}")
        print("\nspan = contiguous files streamed; the Request Scheduler picks")
        print("the replica whose serialization makes the query's span minimal.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
