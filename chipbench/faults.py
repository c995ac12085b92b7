"""Run a cell with a fault planted in the program's write path, on
several seeds, in one process.

    python3 chipbench/faults.py --workload <name> --fault <fault> --seconds <s> --seeds <n>,<n>,...

Each seed is a whole run of the cell (set-up, window, check) with the
program broken underneath the timed path; a sound check reports every
one of them not correct. One JSON line per seed, with the numbers
compared. The faults (:data:`FAULTS`), each a function that plants
itself through ``setattr(target, name, value)`` (``monkeypatch.setattr``
in the tests, plain ``setattr`` here):

- ``barrier``: the read barrier skipped, so reads answer from a replica
  as it was before the rows staged since its last flush;
- ``append``: the device append writes the run's first row with every
  value off by one, while the host table is right;
- ``views``: the view extension indexes the appended run one row off;
- ``drop_row``: the first replica to flush loses one row of each run it
  flushes, though the write was acknowledged;
- ``unstaged``: writes acknowledged but never staged.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))


def barrier(set_attr) -> None:
    from repro.core.engine import HREngine

    set_attr(HREngine, "_ensure_flushed", lambda self, cf, r, trace=None: None)


def append(set_attr) -> None:
    import repro.kernels as kernels

    real = kernels.device_state_append

    def device_state_append(state, table, run_key_cols, run_value_cols, positions):
        bad = {c: np.asarray(v, np.float64).copy() for c, v in run_value_cols.items()}
        for v in bad.values():
            v[:1] += 1.0
        return real(state, table, run_key_cols, bad, positions)

    set_attr(kernels, "device_state_append", device_state_append)


def views(set_attr) -> None:
    from repro.core.storage import views as views_mod

    real = views_mod.extend_views_state

    def extend_views_state(views, state, run_packed, n_old, **kw):
        out = real(views, state, run_packed, n_old, **kw)
        runs = list(out["run_packed"])
        runs[-1] = np.roll(runs[-1], 1)
        return dict(out, run_packed=runs)

    set_attr(views_mod, "extend_views_state", extend_views_state)


def drop_row(set_attr) -> None:
    from repro.core.storage import memtable

    real = memtable.Memtable.peek_run
    victim = []

    def peek_run(self):
        run = real(self)
        if not victim:
            victim.append(self)
        if self is not victim[0] or run is None or len(run) == 0:
            return run
        kc = {c: v[1:] for c, v in run.key_cols.items()}
        vc = {c: v[1:] for c, v in run.value_cols.items()}
        packed = run.packed[1:]
        return dataclasses.replace(run, key_cols=kc, value_cols=vc, packed=packed,
                                   crc=memtable.run_crc32(packed, kc, vc))

    set_attr(memtable.Memtable, "peek_run", peek_run)


def unstaged(set_attr) -> None:
    from repro.core.storage import memtable

    set_attr(memtable.Memtable, "stage", lambda self, *a, **k: None)


FAULTS = {f.__name__: f for f in (barrier, append, views, drop_row, unstaged)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import run

    FAULTS[args.fault](setattr)
    for seed in args.seeds.split(","):
        res = run.main(
            ["--workload", args.workload, "--seed", seed, "--seconds", str(args.seconds),
             "--trace", "0"],
            started=time.perf_counter(),
        )
        print(json.dumps({"fault": args.fault, "workload": args.workload, "seed": int(seed),
                          "correct": res["correct"], "attempted": res["attempted"],
                          "failed": res["failed"], "compared": res["compared"]}), flush=True)


if __name__ == "__main__":
    main()
