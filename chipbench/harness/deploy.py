"""Build the system under test from a configuration file."""

from __future__ import annotations

from . import tpch, traffic


def make_data(config: dict, seed: int, rows: int | None = None):
    """``(rows, key_cols, value_cols)`` of the configuration's table."""
    if config["generator"] != "tpch.orders":
        raise ValueError(f"unknown generator {config['generator']!r}")
    rows = tpch.n_rows(config["scale_factor"]) if rows is None else rows
    kc, vc = tpch.generate_orders(rows, seed)
    return rows, kc, vc


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
    )
    return eng, cf


def replica_tables(eng, cf_name: str) -> dict:
    """``{replica_id: SortedTable}`` of a column family."""
    cf = eng.column_families[cf_name]
    return {
        r.replica_id: eng.nodes[r.node_id].tables[(cf_name, r.replica_id)]
        for r in cf.replicas
    }


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
