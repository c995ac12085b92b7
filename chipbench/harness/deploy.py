"""Build the system under test from a configuration file."""

from __future__ import annotations

import numpy as np

from . import tpch, traffic


def make_data(config: dict, seed: int, rows: int | None = None):
    """``(rows, key_cols, value_cols)`` of the configuration's table."""
    if config["generator"] != "tpch.orders":
        raise ValueError(f"unknown generator {config['generator']!r}")
    rows = tpch.n_rows(config["scale_factor"]) if rows is None else rows
    kc, vc = tpch.generate_orders(rows, seed)
    return rows, kc, vc


def make_writes(writes: dict, loaded_rows: int, rows: int, seed: int):
    """``(key_cols, value_cols)`` of the ``rows`` rows a mix's
    ``writes`` block inserts, drawn from its own seed stream."""
    if writes["generator"] != "tpch.orders.rf1":
        raise ValueError(f"unknown write generator {writes['generator']!r}")
    return tpch.new_orders(loaded_rows, rows, [seed, writes["stream"], 1])


def layout_workload(config: dict, dom: dict[str, int]):
    """The workload HRCA (or TR's expert search) chooses layouts for."""
    from repro.core.workload import Workload

    w = config["layout_workload"]
    mix = traffic.queries(w["templates"], dom, w["instances"], w["seed"])
    return Workload(traffic.to_queries(mix))


def build_engine(config: dict, key_cols: dict, value_cols: dict, dom: dict[str, int]):
    """The engine with the configuration's column family created and
    placed on the device, and the column family's name."""
    from repro.core import HREngine
    from repro.core.keys import KeySchema

    g = config["guarantees"]
    if g["read_consistency"] != "ONE":
        raise ValueError("the harness sends reads at consistency ONE only")
    eng = HREngine(n_nodes=config["n_nodes"], result_cache=g["result_cache"])
    schema = config["schema"]
    cf = schema["table"]
    write_path = {}
    if "writes" in config:
        from repro.core.storage import CompactionPolicy

        w = config["writes"]
        write_path = {"memtable_rows": int(w["memtable_rows"]),
                      "compaction": CompactionPolicy(**w["compaction"])}
    eng.create_column_family(
        cf,
        {c: key_cols[c] for c in schema["clustering_keys"]},
        {c: value_cols[c] for c in schema["values"]},
        replication_factor=config["replication_factor"],
        mechanism=config["mechanism"],
        workload=layout_workload(config, dom),
        schema=KeySchema(dict(schema["key_bits"])),
        hrca_kwargs=dict(config.get("hrca", {})),
        device_resident=config["device_resident"],
        views=config["views"],
        **write_path,
    )
    return eng, cf


def replica_tables(eng, cf_name: str) -> dict:
    """``{replica_id: SortedTable}`` of a column family."""
    cf = eng.column_families[cf_name]
    return {
        r.replica_id: eng.nodes[r.node_id].tables[(cf_name, r.replica_id)]
        for r in cf.replicas
    }


def device_rows(table) -> tuple[dict, dict, np.ndarray]:
    """``(key_cols, value_cols, ones)``: the rows a device-resident
    replica serves reads from, read back from its resident arrays: the
    first ``n_rows`` columns of the key lanes (one int32 lane per
    layout column) and of the float32 value tile, whose count lane is
    ``ones``."""
    st = table._device
    if any(p != 1 for p in st["col_parts"]):
        raise ValueError(f"key lanes {st['col_parts']}: the check reads one lane per column")
    n = int(st["n_rows"])
    keys = np.asarray(st["keys"])[:, :n]
    tile = np.asarray(st["values_tile"])[:, :n]
    key_cols = {c: keys[i] for i, c in enumerate(table.layout)}
    value_cols = {c: tile[i] for c, i in st["value_rows"].items()}
    return key_cols, value_cols, tile[st["ones_row"]]


def resident_shapes(eng, cf_name: str) -> list[dict]:
    """Shapes and dtypes of each replica's resident key and value arrays."""
    out = []
    for rid, t in sorted(replica_tables(eng, cf_name).items()):
        st = t._device
        out.append({
            "replica": rid,
            "layout": list(t.layout),
            "keys": list(st["keys"].shape),
            "keys_itemsize": st["keys"].dtype.itemsize,
            "values": list(st["values_tile"].shape),
            "values_itemsize": st["values_tile"].dtype.itemsize,
        })
    return out
