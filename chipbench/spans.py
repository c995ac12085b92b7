"""Run one cell traced, as ``run.py --trace 1`` does, and say what the
program was doing while the device sat idle.

    python3 chipbench/spans.py --workload <name> --seed <n> --seconds <s>

It prints what ``run.py`` prints, then reads the program's own spans
from the same profiler trace (``harness.program_trace``) and adds, on
standard error and as one more JSON line on standard output:

- ``engine.read_many``'s total on the two clocks: the program's span
  total (``perf_counter``) and its profiler annotations;
- per read, each engine, view and kernel stage, and the front door's
  own time from the annotations beside ``frontdoor.self_us_per_read``'s
  reading; ``device.idle_in_engine_pct`` from the annotations beside its
  reading;
- per call, each write-path stage the window ran (:data:`WRITE_PATH`):
  the write itself, and the flush a read barrier or a write makes, with
  its host merge, view extension and compaction;
- for each of the ten longest idle gaps of chip 0, the chain of program
  spans that covers its middle, innermost first, or ``front door`` where
  only ``client.serve`` covers it. A gap inside a flush is named by the
  flush's spans.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

import run  # noqa: E402
from harness import devtrace, program_trace  # noqa: E402

WRITE_PATH = ("engine.write", "engine.flush_barrier", "engine.flush", "engine.flush_merge",
              "view.build", "engine.compaction")


def traced_run(argv, **kw):
    """``run.main`` with ``--trace 1``, keeping what it reads: its
    :class:`run.Run` and the program's annotations in the window."""
    kept: dict = {}
    load, Run = devtrace.load, run.Run

    def load_keeping(trace_dir, platform="TPU"):
        trace = load(trace_dir, platform)
        kept["program"] = program_trace.load(trace_dir, trace.window)
        return trace

    class RunKept(Run):
        def __init__(self, *args):
            super().__init__(*args)
            kept["run"] = self

    devtrace.load, run.Run = load_keeping, RunKept
    try:
        result = run.main([*argv, "--trace", "1"], **kw)
    finally:
        devtrace.load, run.Run = load, Run
    return result, kept["run"], kept["program"]


def report(result: dict, r, program) -> dict:
    t, stages, reads = r.trace, r.stages or {}, r.reads
    rm = program_trace.intervals(program, "engine.read_many")
    serve = program_trace.intervals(t.labels, program_trace.SERVE)
    span_s = stages.get("engine.read_many", {}).get("total", 0.0)
    ann_s = sum(e - s for s, e in rm) * 1e-9
    per_read = {
        name: s["total"] * 1e6 / reads
        for name, s in stages.items()
        if name.startswith(program_trace.PROGRAM) and not name.startswith("frontdoor.")
    }
    write_path = {
        name: {"count": stages[name]["count"], "total_s": stages[name]["total"],
               "ms_per_call": stages[name]["total"] * 1e3 / stages[name]["count"]}
        for name in WRITE_PATH if stages.get(name, {}).get("count")
    }
    front_door_us = program_trace.covered_s(serve, rm) * 1e6 / reads
    idle_in_engine = 100.0 * program_trace.idle_in_s(t, rm) / t.window_s
    gaps = [
        [program_trace.chain(program, t.labels, (s + e) / 2), (e - s) * 1e-9]
        for s, e in program_trace.idle_gaps_at(t, 10)
    ]
    metrics = result["metrics"]
    out = {
        "engine.read_many_s": {"spans": span_s, "annotations": ann_s},
        "us_per_read": per_read,
        "frontdoor.self_us_per_read": {
            "annotations": front_door_us,
            "reader": _reading(metrics, "frontdoor.self_us_per_read."),
        },
        "device.idle_in_engine_pct": {
            "annotations": idle_in_engine,
            "reader": _reading(metrics, "device.idle_in_engine_pct."),
        },
        "write_path": write_path,
        "idle_gaps": gaps,
    }
    gap = (ann_s - span_s) / span_s * 100 if span_s else float("nan")

    def say(msg: str) -> None:
        print(f"chipbench spans: {msg}", file=sys.stderr)

    say(f"engine.read_many {span_s:.6f} s by its spans, {ann_s:.6f} s by its "
        f"profiler annotations ({gap:+.3f} %)")
    say("us per read: " + ", ".join(f"{k} {v:.3f}" for k, v in per_read.items()))
    if write_path:
        say("write path, ms per call: " + ", ".join(
            f"{k} {v['ms_per_call']:.3f} x {v['count']}" for k, v in write_path.items()))
    say(f"front door {front_door_us:.3f} us per read by the annotations, reader "
        f"{out['frontdoor.self_us_per_read']['reader']}; idle in engine {idle_in_engine:.4f} % "
        f"by the annotations, reader {out['device.idle_in_engine_pct']['reader']}")
    for k, (name, secs) in enumerate(gaps, 1):
        say(f"idle gap {k}: {secs * 1e3:.3f} ms in {name}")
    print(json.dumps({"spans": out}), flush=True)
    return out


def _reading(metrics: dict, prefix: str):
    return next((m["value"] for k, m in metrics.items() if k.startswith(prefix)), None)


def main(argv=None) -> dict:
    result, r, program = traced_run(sys.argv[1:] if argv is None else argv)
    return report(result, r, program)


if __name__ == "__main__":
    try:
        main()
    except run.NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
