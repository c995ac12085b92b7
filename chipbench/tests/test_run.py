"""Whole runs at a small size on the CPU, the look for a chip skipped:
a sound run is correct, and each fault the cells can have, planted in
the program under the timed path, makes ``correct`` come out false, as
does the control."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from harness import spec

ROWS = 20_000
RATE = 40.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding BENCHMARK.json and the data files, with the
    open-loop mixes slowed to what the CPU serves in interpret mode."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(spec.BENCH_DIR / d, root / "chipbench" / d)
    shutil.copy(spec.BENCH_DIR / "peaks.json", root / "chipbench")
    for f in (root / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        if t["loop"] == "open":
            t["rate_per_s"] = RATE
        else:
            t["pool"] = 256  # small enough that the window sends reads again
        f.write_text(json.dumps(t))
    return root


def go(root, workload, seed=2**31 + 11, trace=0, **kw):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "1", "--trace", str(trace)]
    return run.main(argv, chip_required=False, rows=ROWS, root=root, **kw)


CELLS = ["orders-hr.q1q2.r80", "orders-tr.q1q2.r80", "orders-hr.q1q2sel.r80"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(root, workload, capsys):
    res = go(root, workload)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == RATE
    names = {m["name"] for m in spec.Cell(spec.load_benchmark(root), workload).end_to_end}
    assert set(res["metrics"]) == names
    assert list(res)[-1] == "compared"
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == json.loads(json.dumps(res))
    assert err.strip().splitlines()[-1].startswith("compared sum_rel_gap")


def test_closed_loop_traced_run_reads_its_per_layer_metrics(root):
    res = go(root, "orders-hr.q1q2.closed64", trace=1)
    assert res["correct"]
    assert res["attempted"] % 64 == 0 and res["attempted"] > 256
    assert {"engine.plan_us_per_read.closed", "view.serve_us_per_read.closed"} <= set(res["metrics"])
    assert "busy_s" in res["device"] and res["device"]["window_s"] > 0


def _broken(monkeypatch, change):
    """Wrap ``HREngine.read_many`` so ``change(queries, answers)`` alters
    what the engine returns to the front door."""
    from repro.core.engine import HREngine

    real = HREngine.read_many

    def read_many(self, cf_name, queries, **kw):
        return change(list(queries), real(self, cf_name, queries, **kw))

    monkeypatch.setattr(HREngine, "read_many", read_many)


def _alter(sr):
    if sr.selected is not None:
        return dataclasses.replace(sr, selected=sr.selected[:-1], rows_matched=sr.rows_matched - 1)
    return dataclasses.replace(sr, value=sr.value * 1.001 + 1.0)


@pytest.mark.parametrize("workload", ["orders-hr.q1q2.r80", "orders-hr.q1q2sel.r80"])
def test_half_of_each_batch_left_out(root, workload, monkeypatch):
    _broken(monkeypatch, lambda qs, out: out[: len(out) // 2])
    res = go(root, workload)
    assert not res["correct"] and res["compared"]["failed"]["value"] > 0


@pytest.mark.parametrize("workload", ["orders-hr.q1q2.r80", "orders-hr.q1q2.closed64"])
def test_half_of_each_batch_refused_by_the_front_door(root, workload, monkeypatch):
    """Refused, shed or timed-out reads are failed: the configuration
    states no shedding, so a front door that drops reads is not correct."""
    from repro.serving.frontdoor import FrontDoor, Response

    real = FrontDoor.serve
    served = [0]  # a batch may hold one read: refuse every other read of the window

    def serve(self, requests, **kw):
        out = []
        for r in real(self, requests, **kw):
            served[0] += 1
            out.append(Response(status="shed", error="planted") if served[0] % 2 else r)
        return out

    monkeypatch.setattr(FrontDoor, "serve", serve)
    res = go(root, workload)
    assert not res["correct"]
    assert res["compared"]["failed"]["value"] == res["failed"] >= res["attempted"] // 2


@pytest.mark.parametrize("workload", ["orders-hr.q1q2.r80", "orders-tr.q1q2.r80", "orders-hr.q1q2sel.r80"])
def test_an_answer_altered_where_it_is_produced(root, workload, monkeypatch):
    def change(qs, out):
        hit = [i for i, (sr, _rep) in enumerate(out) if sr.rows_matched > 0]
        for i in hit[:1]:
            out[i] = (_alter(out[i][0]), out[i][1])
        return out

    _broken(monkeypatch, change)
    res = go(root, workload)
    assert not res["correct"]


def test_a_stale_answer_repeated(root, monkeypatch):
    last = {}

    def change(qs, out):
        prev = last.get("out")
        last["out"] = out
        if prev is None:
            return out
        return [prev[i % len(prev)] for i in range(len(out))]

    _broken(monkeypatch, change)
    res = go(root, "orders-hr.q1q2.r80")
    assert not res["correct"]


@pytest.mark.parametrize("workload", ["orders-hr.q1q2.r80", "orders-hr.q1q2sel.r80"])
def test_the_control_is_not_correct(root, workload):
    res = go(root, workload, control=True)
    assert not res["correct"]
    assert res["program_compared"]["sum_rel_gap"]["value"] <= res["program_compared"]["sum_rel_gap"]["limit"]


def test_a_listed_metric_that_finds_nothing_fails_the_run(root, monkeypatch, capsys):
    """A kernel renamed or taken off the path leaves its roofline with
    nothing to read; the run exits non-zero instead of going silent."""
    real = spec.load_metric_reader

    def load(name, bench_dir=spec.BENCH_DIR):
        return (lambda run: None) if name == "view.serve_us_per_read.r80" else real(name, bench_dir)

    monkeypatch.setattr(spec, "load_metric_reader", load)
    with pytest.raises(SystemExit) as e:
        go(root, "orders-hr.q1q2.r80", trace=1)
    assert e.value.code != 0
    out, err = capsys.readouterr()
    assert out.strip() == "" and "view.serve_us_per_read.r80" in err
    assert err.strip().splitlines()[-1].startswith("compared sum_rel_gap")


def test_no_tpu_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload", "orders-hr.q1q2.r80",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
