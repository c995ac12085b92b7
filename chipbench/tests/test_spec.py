"""Every name in BENCHMARK.json resolves to a file of its own."""

import re

import numpy as np
import pytest

from harness import spec, tpch

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_config_traffic_and_metrics(w):
    cell = spec.Cell(BENCH, w["name"])
    assert cell.config["name"] == w["config"]
    assert cell.traffic["name"] == w["traffic"]
    assert cell.traffic["loop"] in ("open", "closed")
    if cell.traffic["loop"] == "open":
        assert cell.traffic["rate_per_s"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(spec.load_metric_reader(m["name"]))
        assert m["moves"] in names


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_configuration_as_run(c):
    cfg = spec.load_json(spec.ROOT / c["file"])
    assert cfg["name"] == c["name"]
    assert cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"] == []
    assert cfg["limits"]["sum_rel_gap"] > 0
    g = cfg["guarantees"]
    assert g["read_consistency"] == "ONE" and g["result_cache"] is False
    _, values = tpch.generate_orders(64, 1)
    assert cfg["schema"]["values"] == list(values) == list(cfg["schema"]["value_encoding"])


def test_orders_columns_follow_dbgen():
    keys, values = tpch.generate_orders(200_000, 3)
    assert values["orderkey"][:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 32, 33]
    assert np.all(values["shippriority"] == 0)
    assert set(np.unique(values["orderpriority"])) == {1, 2, 3, 4, 5}
    share = [np.mean(values["orderstatus"] == tpch.ORDER_STATUS[s]) for s in "FOP"]
    assert share == pytest.approx([0.486, 0.487, 0.026], abs=0.01)  # TPC-H's own shares
    late = keys["orderdate"] > tpch.CURRENT_DAY
    assert np.all(values["orderstatus"][late] == tpch.ORDER_STATUS["O"])


def test_names_and_files_follow_the_contract():
    entries = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert 0 < m.get("bound", 0.01) <= 0.25
    assert BENCH["command"] == ["python3", "chipbench/run.py"]


def test_missing_names_are_errors():
    with pytest.raises(KeyError):
        spec.Cell(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.load_metric_reader("no-such-metric")


def test_every_per_layer_metric_lists_its_cells():
    for m in BENCH["per_layer"]:
        assert m["workloads"], m["name"]
    bench = dict(BENCH)
    bench["per_layer"] = [{"name": "device.idle_pct.r80", "moves": "read_p50_ms"}]
    with pytest.raises(KeyError):
        spec.Cell(bench, "orders-hr.q1q2.r80")


@pytest.mark.parametrize("name", ["device.idle_pct", "engine.plan_us_per_read", "view.serve_us_per_read"])
def test_a_closed_loop_reader_is_its_open_loop_twin(name):
    class Run:
        class trace:
            window_s, busy_s = 10.0, 2.5

        stages = {"engine.plan": {"count": 3, "total": 0.5}, "view.serve": {"count": 2, "total": 0.25}}
        reads = 1000

        def span_us_per_read(self, stage):
            return self.stages[stage]["total"] * 1e6 / self.reads

    closed = spec.load_metric_reader(f"{name}.closed")(Run())
    assert closed == spec.load_metric_reader(f"{name}.r80")(Run()) is not None


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12
