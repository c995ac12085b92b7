"""The program's spans on the profiler's clock: the readers that split
the front door's time and the device's idle time by layer, on a
synthetic trace; the annotations read back from a trace recorded on the
CPU; and ``spans.py`` over a whole small run."""

import json
import shutil

import pytest

import run
import spans
from harness import devtrace, program_trace, spec

MS = 1e6  # ns


def synthetic():
    """Window 0-10 ms. Chip 0 busy 1-4 ms and 6-7 ms; ``client.serve``
    0.6-4.5 and 5.5-9.6 ms; ``engine.read_many`` 0.8-4.2 and 6-9.4 ms,
    holding all of the busy time; ``view.serve`` 7.5-8.5 ms."""
    ops = [[("%a.1 = f32[8] fusion()", 1 * MS, 3 * MS), ("%b.1 = f32[8] copy()", 2.5 * MS, 4 * MS),
            ("%c.1 = f32[8] fusion()", 6 * MS, 7 * MS)]]
    labels = [("chipbench.window", 0.0, 10 * MS), ("client.serve", 0.6 * MS, 4.5 * MS),
              ("client.idle", 4.5 * MS, 5.5 * MS), ("client.serve", 5.5 * MS, 9.6 * MS)]
    program = [("engine.read_many", 0.8 * MS, 4.2 * MS), ("engine.read_many", 6 * MS, 9.4 * MS),
               ("view.serve", 7.5 * MS, 8.5 * MS)]
    return devtrace.DeviceTrace((0.0, 10 * MS), ops, labels), program


class _Run:
    def __init__(self, trace, stages, reads):
        self.trace, self.stages, self.reads = trace, stages, reads


def synthetic_run(reads=10):
    trace, program = synthetic()
    total = sum(e - s for n, s, e in program if n == "engine.read_many") * 1e-9
    return _Run(trace, {"engine.read_many": {"count": 2, "total": total}}, reads), program


def test_front_door_time_is_serve_less_read_many():
    r, program = synthetic_run()
    # serve 3.9 + 4.1 ms, read_many 3.4 + 3.4 ms: 1.2 ms over 10 reads
    for name in ("frontdoor.self_us_per_read.r80", "frontdoor.self_us_per_read.closed"):
        assert spec.load_metric_reader(name)(r) == pytest.approx(120.0)
    # the same seconds, taken from the annotations themselves
    rm = program_trace.intervals(program, "engine.read_many")
    serve = program_trace.intervals(r.trace.labels, "client.serve")
    assert program_trace.covered_s(serve, rm) == pytest.approx(1.2e-3)


def test_idle_in_engine_from_the_readers_and_from_the_annotations():
    r, program = synthetic_run()
    # read_many 0.8-4.2 less busy 1-4: 0.4 ms; 6-9.4 less busy 6-7: 2.4 ms
    want = 100 * 2.8e-3 / 10e-3
    for name in ("device.idle_in_engine_pct.r80", "device.idle_in_engine_pct.closed"):
        got = spec.load_metric_reader(name)(r)
        assert got == pytest.approx(want)
        assert got <= spec.load_metric_reader("device.idle_pct.r80")(r)
    rm = program_trace.intervals(program, "engine.read_many")
    assert 100 * program_trace.idle_in_s(r.trace, rm) / r.trace.window_s == pytest.approx(want)


@pytest.mark.parametrize("stages", [None, {}, {"engine.read_many": {"count": 0, "total": 0.0}}])
def test_readers_find_nothing_without_read_many_spans(stages):
    r, _ = synthetic_run()
    r.stages = stages
    for name in ("frontdoor.self_us_per_read.r80", "device.idle_in_engine_pct.r80"):
        assert spec.load_metric_reader(name)(r) is None


def test_readers_find_nothing_without_a_trace():
    r, _ = synthetic_run()
    r.trace = None
    for name in ("frontdoor.self_us_per_read.r80", "device.idle_in_engine_pct.r80"):
        assert spec.load_metric_reader(name)(r) is None


def test_covered_with_and_without_busy_time():
    busy = [(1 * MS, 3 * MS), (2 * MS, 4 * MS)]
    assert program_trace.covered_s([(0, 2 * MS), (1 * MS, 5 * MS)]) == pytest.approx(5e-3)
    assert program_trace.covered_s([(0, 2 * MS), (1 * MS, 5 * MS)], busy) == pytest.approx(2e-3)
    assert program_trace.covered_s([]) == 0.0


def test_idle_gaps_named_by_the_program_spans_covering_them():
    trace, program = synthetic()
    gaps = program_trace.idle_gaps_at(trace, 10)
    # the same gaps, in the same order, as DeviceTrace.idle_gaps
    assert [pytest.approx((e - s) * 1e-9) for s, e in gaps] == [g[1] for g in trace.idle_gaps(10)]
    chains = [program_trace.chain(program, trace.labels, (s + e) / 2) for s, e in gaps]
    # 7-10 ms (mid 8.5: view.serve inside read_many), 4-6 ms (mid 5:
    # client.idle), 0-1 ms (mid 0.5: before the first serve)
    assert chains == ["view.serve < engine.read_many", "client.idle", "chipbench.window"]
    # inside client.serve but outside every program span
    assert program_trace.chain(program, trace.labels, 9.5 * MS) == "front door"
    assert program_trace.chain(program, [], 20 * MS) == "none"


def test_load_reads_program_annotations_from_a_recorded_cpu_trace(tmp_path):
    import jax

    from repro.obs import Tracer

    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tracer.root("engine.before_window").end()
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            root = tracer.root("engine.read_many")
            plan = root.child("engine.plan")
            sum(range(10_000))
            plan.end()
            with jax.profiler.TraceAnnotation("client.serve"):
                root.child("view.serve").child("view.boundary").end()
            tracer.root("frontdoor.request", t=0.0).end(t=1.0)  # virtual clock
            root.end()
    finally:
        jax.profiler.stop_trace()
    trace = devtrace.load(str(tmp_path), "CPU")
    # the harness's labels are as before: the program's spans stay out
    assert sorted(n for n, _, _ in trace.labels) == ["chipbench.window", "client.serve"]
    program = program_trace.load(str(tmp_path), trace.window)
    names = [n for n, _, _ in program]
    # view.serve is never ended, so it writes no event
    assert sorted(names) == ["engine.plan", "engine.read_many", "view.boundary"]
    assert names[0] == "engine.read_many"  # ordered by start
    (rm,) = program_trace.intervals(program, "engine.read_many")
    (pl,) = program_trace.intervals(program, "engine.plan")
    assert rm[0] <= pl[0] < pl[1] <= rm[1]
    assert plan.wall - 1e-6 <= (pl[1] - pl[0]) * 1e-9 <= plan.wall + 1e-3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the closed-loop pool cut to what the CPU serves."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(spec.BENCH_DIR / d, root / "chipbench" / d)
    shutil.copy(spec.BENCH_DIR / "peaks.json", root / "chipbench")
    f = root / "chipbench" / "traffic" / "q1q2-closed64.json"
    f.write_text(json.dumps(dict(json.loads(f.read_text()), pool=256)))
    f = root / "chipbench" / "traffic" / "q1q2-newsales-closed64.json"
    f.write_text(json.dumps(dict(json.loads(f.read_text()), pool=256)))
    return root


def test_spans_report_on_a_small_traced_run(root, capsys):
    argv = ["--workload", "orders-hr.q1q2.closed64", "--seed", str(2**31 + 5), "--seconds", "1"]
    result, r, program = spans.traced_run(argv, chip_required=False, rows=20_000, root=root)
    assert result["correct"] and isinstance(r, run.Run)
    out = spans.report(result, r, program)
    clocks = out["engine.read_many_s"]
    # the two clocks time the same intervals
    assert clocks["annotations"] == pytest.approx(clocks["spans"], rel=0.02)
    assert out["us_per_read"]["view.serve"] > 0 and "view.boundary" in out["us_per_read"]
    fd = out["frontdoor.self_us_per_read"]
    assert fd["reader"] == result["metrics"]["frontdoor.self_us_per_read.closed"]["value"]
    assert fd["annotations"] == pytest.approx(fd["reader"], rel=0.05)
    idle = out["device.idle_in_engine_pct"]
    assert 0 < idle["reader"] <= result["metrics"]["device.idle_pct.closed"]["value"]
    _out, err = capsys.readouterr()
    assert "engine.read_many" in err and json.loads(_out.strip().splitlines()[-1]) == {"spans": out}


def test_spans_report_the_write_path(root, capsys):
    argv = ["--workload", "orders-hr.q1q2.newsales.closed64", "--seed", str(2**31 + 6),
            "--seconds", "1"]
    result, r, program = spans.traced_run(argv, chip_required=False, rows=20_000, root=root)
    assert result["correct"]
    out = spans.report(result, r, program)
    wp = out["write_path"]
    assert wp["engine.write"]["count"] == 1
    for name in ("engine.flush_barrier", "engine.flush", "engine.flush_merge", "view.build"):
        assert wp[name]["count"] > 0 and wp[name]["ms_per_call"] > 0
    assert wp["engine.flush_merge"]["count"] == wp["engine.flush"]["count"]
    # the write and flush spans reach the profiler's clock too
    assert program_trace.intervals(program, "engine.flush_merge")
    _out, err = capsys.readouterr()
    assert "write path, ms per call: engine.write" in err
