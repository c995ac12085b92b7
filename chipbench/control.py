"""Run a cell's control on several seeds, in one process.

    python3 chipbench/control.py --workload <name> --seconds <s> --seeds <n>,<n>,...

Each seed is a whole run of the cell (set-up, window, check), but the
answers compared are the control's: the plain reference computed one
precision step below what the configuration states (sums folded in
bfloat16, selected rows' values in float32), put in the program's place
for every read the program answered. The control has to come out not
correct. Each run also prints the program's own numbers, the sound
readings the limits are set above. One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds.split(","):
        res = run.main(
            ["--workload", args.workload, "--seed", seed, "--seconds", str(args.seconds),
             "--trace", "0"],
            control=True, started=time.perf_counter(),
        )
        print(json.dumps({"control": args.workload, "seed": int(seed),
                          "correct": res["correct"], "attempted": res["attempted"],
                          "control_compared": res["compared"],
                          "program_compared": res["program_compared"]}), flush=True)


if __name__ == "__main__":
    main()
