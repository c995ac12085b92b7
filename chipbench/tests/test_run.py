"""Whole runs at a small size on the CPU, the look for a chip skipped:
a sound run is correct, and each fault the cells can have, planted in
the program under the timed path, makes ``correct`` come out false, as
does the control. The cell that writes is checked for read-your-writes
and for every acknowledged write on every replica."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

import faults
import run
from harness import spec

ROWS = 20_000
RATE = 40.0
RF1 = ROWS // 1000  # one TPC-H RF1 against ROWS orders
WRITES = "orders-hr.q1q2.newsales.closed64"


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


@pytest.mark.parametrize("workload", ["orders-hr.q1q2.r80", "orders-hr.q1q2sel.r80", WRITES])
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


@pytest.mark.parametrize("workload", ["orders-hr.q1q2.r80", "orders-tr.q1q2.r80", "orders-hr.q1q2sel.r80",
                                      WRITES])
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


def test_writes_sound_run_is_correct(root, capsys):
    res = go(root, WRITES)
    assert res["correct"] and res["failed"] == 0
    c = res["compared"]
    assert c["writes_failed"]["value"] == 0 and c["replica_mismatches"]["value"] == 0
    names = {m["name"] for m in spec.Cell(spec.load_benchmark(root), WRITES).end_to_end}
    assert set(res["metrics"]) == names == {"read_qps", "setup_s"}
    assert res["attempted"] % 64 == RF1 % 64
    _out, err = capsys.readouterr()
    # the RF1 sent in one call as the window opens, flushed by the read
    # barrier in the window, and every shape the window reached warmed
    assert f"{RF1} rows due in 1 calls, 0 failed" in err
    assert "in the window 0 flushes" not in err and " 0 reads aimed at written rows" not in err
    assert "JAX compile events in window none" in err
    assert err.strip().splitlines()[-1].startswith("compared sum_rel_gap")


def test_writes_traced_run_reads_its_per_layer_metrics(root):
    res = go(root, WRITES, trace=1)
    assert res["correct"]
    listed = {m["name"] for m in spec.Cell(spec.load_benchmark(root), WRITES).per_layer}
    assert set(res["metrics"]) == listed
    assert res["metrics"]["engine.flushes_per_s.newsales"]["value"] > 0


@pytest.mark.parametrize("fault, number, value", [
    ("barrier", "count_mismatches", None),  # stale reads of the written rows
    ("append", "replica_mismatches", 3),  # every replica, the last in the final flush
    ("views", "count_mismatches", None),
    ("drop_row", "replica_mismatches", 1),
    ("unstaged", "replica_mismatches", 3),
])
def test_a_planted_write_path_fault_is_not_correct(root, monkeypatch, fault, number, value):
    faults.FAULTS[fault](monkeypatch.setattr)
    res = go(root, WRITES)
    assert not res["correct"]
    got = res["compared"][number]["value"]
    assert got > 0 if value is None else got == value
    assert res["compared"]["writes_failed"]["value"] == 0


def test_the_control_is_not_correct_in_the_cell_that_writes(root):
    res = go(root, WRITES, control=True)
    assert not res["correct"] and res["compared"]["sum_rel_gap"]["value"] > 1e-4
    program = res["program_compared"]
    assert all(v["value"] <= v["limit"] for v in program.values())


def test_the_open_loop_sends_writes_before_its_reads(root, tmp_path, capsys):
    """An open-loop mix given a writes block of three refreshes: the
    configuration has no write path of its own, so every write flushes
    as it is made."""
    checkout = tmp_path / "checkout"
    shutil.copytree(root, checkout)
    f = checkout / "chipbench" / "traffic" / "q1q2-hr-r80.json"
    t = json.loads(f.read_text())
    t["writes"] = {"generator": "tpch.orders.rf1", "refresh_at_s": [0.6, 0.0, 0.3], "stream": 5,
                   "fresh_read_every": 4}
    f.write_text(json.dumps(t))
    res = go(checkout, "orders-hr.q1q2.r80")
    assert res["correct"] and res["attempted"] == RATE + 3 * RF1
    assert res["compared"]["replica_mismatches"]["value"] == 0
    _out, err = capsys.readouterr()
    assert f"{3 * RF1} rows due in 3 calls, 0 failed" in err
    assert "JAX compile events in window none" in err


def test_a_mix_with_writes_and_selects_is_refused(root, tmp_path):
    mixed = tmp_path / "checkout"
    shutil.copytree(root, mixed)
    sel = mixed / "chipbench" / "traffic" / "q1q2sel-hr-r80.json"
    t = json.loads(sel.read_text())
    t["writes"] = {"generator": "tpch.orders.rf1", "refresh_at_s": [0.0], "stream": 5,
                   "fresh_read_every": 8}
    sel.write_text(json.dumps(t))
    with pytest.raises(ValueError, match="select"):
        go(mixed, "orders-hr.q1q2sel.r80")


def test_no_tpu_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload", "orders-hr.q1q2.r80",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
