"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell's configuration and traffic mix; both
are files under ``chipbench/`` found by name. The run builds the
deployment from ``--seed`` on the chip, warms every shape it will use,
then drives the program's front door from a single-threaded client for
``--seconds`` and checks every answer against the plain reference. A
mix that writes also inserts its rows through ``HREngine.write`` in the
same loop; after the window every replica is flushed and checked to
hold exactly the reference's rows.

With ``--trace 0`` it reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace and the
program's spans over the same window. The last line of standard output
is one JSON object; the numbers compared and their limits are also the
last lines of standard error. It exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
from contextlib import nullcontext  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from harness import client, deploy, devtrace, reference, spec, stats, tpch, traffic, warmup  # noqa: E402

class NoChip(SystemExit):
    pass


class Unread(SystemExit):
    """A per-layer metric that the cell lists found nothing to read."""


def require_chip(chips: int):
    """The devices, or exit non-zero: only a TPU with enough chips counts."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (JAX platform is {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def load_peaks(bench_dir: Path, kind: str) -> dict:
    table = spec.load_json(bench_dir / "peaks.json")
    if kind not in table["devices"]:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


class JaxEvents:
    """JAX's monitoring events that mean work a warm window should not
    do, each with the host time it was recorded at: tracing a function,
    lowering it, compiling it, and looking it up in the persistent
    compilation cache. A shape that set-up missed shows here even where
    the cache spares its compile."""

    WATCHED = ("/jax/core/compile/", "/jax/compilation_cache/cache_")

    def __init__(self, clock=time.perf_counter):
        import jax

        self.clock = clock
        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on(self, name, **_kw):
        if name.startswith(self.WATCHED):
            self.events.append((self.clock(), name.rsplit("/", 1)[-1]))

    def _on_duration(self, name, _secs, **_kw):
        self._on(name)

    def between(self, t0: float, t1: float = math.inf) -> dict[str, int]:
        out: dict[str, int] = {}
        for t, name in self.events:
            if t0 <= t < t1:
                out[name] = out.get(name, 0) + 1
        return out


def program_answers(window, tables: dict) -> list:
    """Each answered read's answer in the reference's terms, else None.
    A select's row indices are read back into row contents from the
    replica that served them."""
    out: list = []
    for k in range(window.n):
        if window.status[k] != client.OK:
            out.append(None)
            continue
        rows = None
        sel = window.selected[k]
        if sel is not None:
            t = tables[int(window.replica[k])]
            cols = [t.key_cols[c][sel].astype(np.float64) for c in sorted(t.key_cols)]
            cols += [np.asarray(t.value_cols[c], np.float64)[sel] for c in sorted(t.value_cols)]
            rows = reference.sort_rows(np.stack(cols, axis=1))
        out.append(reference.Answer(float(window.value[k]), int(window.matched[k]), rows))
    return out


class Run:
    """What a per-layer reader may read: the window's reads, the
    counters (the front door's under their own names, the engine's
    ``stats`` over the window under ``engine.<name>``), the program's
    span totals, the device trace, the resident shapes and the chip's
    peaks."""

    def __init__(self, reads, counters, stages, trace, shapes, peaks):
        self.reads = reads
        self.counters = counters
        self.stages = stages
        self.trace = trace
        self.shapes = shapes
        self.peaks = peaks

    def span_us_per_read(self, name: str):
        s = (self.stages or {}).get(name)
        if not s or not s["count"] or not self.reads:
            return None
        return s["total"] * 1e6 / self.reads

    def span_ms_per_call(self, name: str):
        s = (self.stages or {}).get(name)
        return s["total"] * 1e3 / s["count"] if s and s["count"] else None


def engine_window_counters(before: dict, after: dict) -> dict:
    """The engine's numeric ``stats`` over the window, as
    ``engine.<name>``: what each counter gained."""
    return {f"engine.{k}": after[k] - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float))}


def main(argv=None, *, chip_required: bool = True, rows: int | None = None,
         control: bool = False, root: Path = ROOT, started: float | None = None) -> dict:
    """One run; returns the result it prints. The keyword arguments are
    for tests and the control: ``chip_required=False`` skips the look for
    a TPU (and the compile cache), ``rows`` shrinks the table, ``control``
    puts the reference one precision step down in the program's place,
    ``root`` is a checkout holding ``BENCHMARK.json`` and the data
    files under ``chipbench/``, and ``started`` is when set-up began
    (default: when this module was loaded)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_START if started is None else started

    bench_dir = root / BENCH_DIR.name
    cell = spec.Cell(spec.load_benchmark(root), args.workload, bench_dir)
    import jax

    devs = require_chip(cell.chips) if chip_required else jax.devices()
    dev = devs[0]
    peaks = load_peaks(bench_dir, dev.device_kind) if chip_required else None
    if chip_required:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax_events = JaxEvents()

    cfg, mix_cfg = cell.config, cell.traffic
    traffic.check_mix(mix_cfg)
    seed = args.seed
    n_rows, kc, vc = deploy.make_data(cfg, seed, rows)
    dom = tpch.domains(n_rows)
    eng, cf = deploy.build_engine(cfg, kc, vc, dom)
    shapes = deploy.resident_shapes(eng, cf)
    tables = deploy.replica_tables(eng, cf)
    max_batch = int(cfg["frontdoor"]["max_batch"])
    timeout_s = cfg["guarantees"]["read_timeout_ms"] / 1000.0
    templates = mix_cfg["templates"]

    if mix_cfg["loop"] == "open":
        due = traffic.arrivals(mix_cfg["rate_per_s"], args.seconds, [seed, 2])
        n_reads = len(due)
    else:
        n_reads = int(mix_cfg["pool"])
    mix = traffic.queries(templates, dom, n_reads, [seed, 1])
    wcfg = mix_cfg.get("writes")
    if wcfg is not None:
        write_due, ends = traffic.write_stream(wcfg, tpch.rf1_rows(n_rows))
        wk, wv = deploy.make_writes(wcfg, n_rows, write_due.size, seed)
        # reads aimed at written rows see them only by read-your-writes
        aimed = np.zeros(len(mix), bool)
        aimed[traffic.aim_at_rows(mix, dom, wk, int(wcfg["fresh_read_every"]),
                                  [seed, wcfg["stream"], 2])] = True
    queries = traffic.to_queries(mix)

    warmup.warm(eng, cf, templates, dom, max_batch, timeout_s, seed)
    if wcfg is not None:
        warmup.warm_writes(eng, cf, ends, max_batch)

    tracer = None
    if args.trace:
        from repro.obs import Tracer

        tracer = Tracer()
    fd = client.make_frontdoor(eng, max_batch, tracer=tracer)
    writes = None
    if wcfg is not None:
        writes = client.Writes(eng, cf, wk, wv, write_due, tracer=tracer)
    client.settle()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    try:
        if args.trace:
            jax.profiler.start_trace(trace_dir, profiler_options=devtrace.profiler_options())
        window = jax.profiler.TraceAnnotation(devtrace.WINDOW) if args.trace else nullcontext()
        stats_before = eng.stats
        t_window = time.perf_counter()
        with window, client.GcPauses() as gcp:
            if mix_cfg["loop"] == "open":
                win = client.open_loop(fd, cf, queries, due, max_batch=max_batch,
                                       timeout_s=timeout_s, trace=bool(args.trace),
                                       writes=writes)
            else:
                win = client.closed_loop(fd, cf, queries, clients=int(mix_cfg["clients"]),
                                         seconds=args.seconds, timeout_s=timeout_s,
                                         trace=bool(args.trace), writes=writes)
        if args.trace:
            jax.profiler.stop_trace()
        window_events = jax_events.between(t_window, time.perf_counter())
        engine_counters = engine_window_counters(stats_before, eng.stats)
        mem = dev.memory_stats() or {}
        memory_peak = int(mem.get("peak_bytes_in_use", 0))
        if writes is not None:
            # every acknowledged write on every replica: flush what is
            # still staged, then read each replica's rows back, from its
            # host table and from the resident arrays reads are served from
            eng.flush_memtables(cf)
            replica_prints = {
                rid: (reference.fingerprint(t.key_cols, t.value_cols),
                      reference.device_fingerprint(*deploy.device_rows(t)))
                for rid, t in deploy.replica_tables(eng, cf).items()
            }

        dtrace = devtrace.load(trace_dir, dev.platform.upper()) if args.trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    got = program_answers(win, tables)
    counters = dict(fd.stats)
    counters.update(engine_counters)
    stages = None
    if tracer is not None:
        from repro.obs import stage_totals

        stages = stage_totals(tracer.roots)
        tracer.clear()
    if writes is not None:
        write_lat, write_failed = writes.latency_s, writes.failed | np.isnan(writes.latency_s)
        write_calls, write_errors = writes.calls, writes.errors
    del eng, fd, tables, tracer, writes
    gc.unfreeze()
    gc.collect()

    # the check, once the program's state is gone
    t_check = time.perf_counter()
    n = win.n
    idx = np.arange(n) % len(mix)
    visible = None
    if wcfg is not None:
        # the loaded rows, then the acknowledged writes in order; a read
        # sees the loaded rows and the writes acknowledged before its batch
        acked = ~write_failed
        kc = {c: np.concatenate([kc[c], wk[c][acked]]) for c in kc}
        vc = {c: np.concatenate([vc[c], wv[c][acked]]) for c in vc}
        visible = n_rows + np.concatenate([[0], np.cumsum(acked)])[win.watermark]
    ref = reference.Reference(kc, vc, dom)
    want = ref.answers(mix, idx, visible=visible)
    aggs = [mix.agg(i) for i in idx]
    missing = win.status == client.MISSING
    refused = win.status == client.REFUSED
    cmp = reference.compare(got, want, aggs)
    if wcfg is not None:
        ref_print = (reference.fingerprint(kc, vc), reference.device_fingerprint(kc, vc))
        cmp.writes_failed = int(np.sum(write_failed))
        cmp.replica_mismatches = sum(p != ref_print for p in replica_prints.values())
    program_cmp = None
    if control:
        # the control puts the reference one precision step down in the
        # program's place, for every read the program answered
        program_cmp = cmp
        low = ref.answers(mix, idx, control=True, visible=visible)
        got = [None if g is None else w for g, w in zip(got, low)]
        cmp = dataclasses.replace(reference.compare(got, want, aggs),
                                  writes_failed=program_cmp.writes_failed,
                                  replica_mismatches=program_cmp.replica_mismatches)
    check_s = time.perf_counter() - t_check
    limits = cfg["limits"]
    correct = cmp.correct(limits)

    fail_mask = missing | refused | np.isnan(win.latency_s)
    lat_ok = np.where(fail_mask, 0.0, win.latency_s)
    result_metrics = {}
    if not args.trace:
        for m in cell.end_to_end:
            name = m["name"]
            if name == "setup_s":
                v = setup_s
            elif name.startswith("read_p"):
                p = float(name[len("read_p"):].split("_")[0])
                v = stats.percentile(lat_ok, fail_mask, p, timeout_s) * 1e3
            elif name == "read_qps":
                v = stats.rate(int(np.sum(~fail_mask)), win.length_s)
            else:
                raise KeyError(f"no measurement for end-to-end metric {name!r}")
            result_metrics[name] = {"value": v, "unit": m["unit"]}
    else:
        run = Run(n, counters, stages, dtrace, shapes, peaks)
        for m in cell.per_layer:
            v = spec.load_metric_reader(m["name"], bench_dir)(run)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    unread = [m["name"] for m in cell.per_layer if args.trace and m["name"] not in result_metrics]

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": memory_peak}
    attempted, failed = int(n), int(np.sum(fail_mask))
    if wcfg is not None:
        attempted += int(write_lat.size)
        failed += int(np.sum(write_failed))
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": result_metrics,
              "device": device}
    if dtrace is not None:
        device["busy_s"] = dtrace.busy_s
        device["window_s"] = dtrace.window_s
        result["breakdown"] = {"device_ops": dtrace.top_ops(10), "idle_gaps": dtrace.idle_gaps(10)}
    numbers = cmp.numbers(limits)
    if program_cmp is not None:
        result["program_compared"] = _numbers(program_cmp.numbers(limits))
    result["compared"] = _numbers(numbers)

    late = win.lateness_s
    print(f"chipbench: {cell.name} seed {seed}: {n} reads in {win.length_s:.3f} s, "
          f"{win.batches} batches, {int(np.sum(missing))} missing, {int(np.sum(refused))} refused; "
          f"setup {setup_s:.3f} s; check {check_s:.3f} s", file=sys.stderr)
    print(f"chipbench: generator lateness p50 {np.median(late) * 1e6 if late.size else 0:.1f} us, "
          f"max {late.max() * 1e6 if late.size else 0:.1f} us over {late.size} waits; "
          f"JAX compile events in window {window_events or 'none'}; {gcp.summary()}", file=sys.stderr)
    if wcfg is not None:
        ok_lat = write_lat[~write_failed]
        rows = {r: f"{h[0]}/{d[0]}{'' if (h, d) == ref_print else ' differs'}"
                for r, (h, d) in replica_prints.items()}
        print(f"chipbench: writes: {write_lat.size} rows due in {write_calls} calls, "
              f"{int(np.sum(write_failed))} failed {write_errors[:3]}; latency p50 "
              f"{np.median(ok_lat) * 1e3 if ok_lat.size else float('nan'):.1f} ms, max "
              f"{ok_lat.max() * 1e3 if ok_lat.size else float('nan'):.1f} ms; in the window "
              f"{engine_counters.get('engine.memtable_flushes', 0)} flushes, "
              f"{engine_counters.get('engine.compactions', 0)} compactions; "
              f"{int(np.sum(aimed[idx] & (win.watermark > 0)))} reads aimed at written rows "
              f"after a write; replica rows host/device {rows} "
              f"against {ref_print[0][0]}", file=sys.stderr)
    for start, secs, size in win.slowest(5):
        t0 = win.t0 + start
        print(f"chipbench: slow batch at {start:.3f} s: {size} reads, {secs * 1e3:.1f} ms, "
              f"JAX compile events {jax_events.between(t0, t0 + secs) or 'none'}", file=sys.stderr)
    if unread:
        # the cell lists these metrics, so the code they read has left
        # the path or been renamed: the run fails rather than go silent
        print(f"chipbench: per-layer metrics {unread} found nothing to read in {cell.name}, "
              "which lists them", file=sys.stderr)
    for k, v in numbers.items():
        print(f"compared {k}: {_finite(v['value'])} limit {v['limit']}", file=sys.stderr)
    if unread:
        raise Unread(1)
    print(json.dumps(result), flush=True)
    return result


def _numbers(numbers: dict) -> dict:
    return {k: {"value": _finite(v["value"]), "limit": v["limit"]} for k, v in numbers.items()}


def _finite(v):
    """A compared number as JSON can carry it: an answer infinitely far
    off (or NaN) reads as 1e300."""
    return v if isinstance(v, int) or math.isfinite(v) else 1e300


if __name__ == "__main__":
    try:
        main()
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
