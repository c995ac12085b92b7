"""TPC-H ``orders`` as the paper uses it (arXiv:1810.01037 §5), made from
a seed: clustering keys (custkey, orderdate, clerk), and as value
columns every other column of ``orders`` that the store can hold, which
keeps numeric columns only: orderkey, orderstatus, totalprice,
orderpriority and shippriority. ``o_comment`` is text and is not held.

TPC-H ratios set the key domains: about 10 orders per customer, 1500
orders per clerk and 2406 order dates, at 1.5 M rows per scale factor.
The other columns follow TPC-H's dbgen (specification 4.2.3): sparse
order keys, the status an order's lines give it, a priority of five,
and a ship priority of 0. This is the benchmark's own copy of the
generator, so that a change to the program cannot change the data a
cell runs on.

:func:`new_orders` makes the rows of TPC-H's New Sales refresh
function (RF1, specification 2.5.2), :func:`rf1_rows` of them a
refresh: orders drawn as the loader draws them, under order keys that
no loaded order holds.
"""

from __future__ import annotations

import numpy as np

ROWS_PER_SF = 1_500_000
N_DATES = 2406  # 1992-01-01 .. 1998-08-02
ORDERS_PER_CUSTOMER = 10
ORDERS_PER_CLERK = 1500
TOTALPRICE_RANGE = (857.71, 555285.16)
CURRENT_DAY = 1263  # CURRENTDATE, 1995-06-17, as a day of the order dates
LINES_PER_ORDER = (1, 7)
SHIP_DAYS = 121  # a line ships 1..121 days after its order
ORDER_STATUS = {"F": 0.0, "O": 1.0, "P": 2.0}
ORDER_PRIORITIES = 5  # "1-URGENT" .. "5-LOW", held as 1..5
SHIPPRIORITY = 0.0


def n_rows(scale_factor: float) -> int:
    return int(scale_factor * ROWS_PER_SF)


def rf1_rows(loaded_rows: int) -> int:
    """Orders one RF1 inserts (specification 2.5.2): SF x 1500 against
    the SF x 1,500,000 orders loaded, a thousandth of the table."""
    return max(1, loaded_rows // 1000)


def domains(rows: int) -> dict[str, int]:
    """Number of distinct values of each clustering key at ``rows``."""
    return {
        "custkey": max(1024, rows // ORDERS_PER_CUSTOMER),
        "orderdate": N_DATES,
        "clerk": max(32, rows // ORDERS_PER_CLERK),
    }


def order_keys(rows: int, slot: int = 0) -> np.ndarray:
    """dbgen's sparse keys (``mk_sparse``): order ``i`` (from 1) gets
    ``(((i >> 3) << 2 | slot) << 3) | (i & 7)``, so of every 32 keys
    the loader (slot 0) uses the first 8 and leaves slots 1-3 of each
    32 for refresh data."""
    i = np.arange(1, rows + 1, dtype=np.int64)
    return (((i >> 3) << 2 | slot) << 3 | (i & 7)).astype(np.float64)


def order_status(orderdate: np.ndarray, rng) -> np.ndarray:
    """F where every line of the order has shipped by CURRENTDATE, O
    where none has, P otherwise. Lines ship independently, so an order
    of ``L`` lines is all open with chance ``p**L`` and all shipped with
    ``(1 - p)**L``, ``p`` being one line's chance to ship after
    CURRENTDATE."""
    lines = rng.integers(LINES_PER_ORDER[0], LINES_PER_ORDER[1] + 1, orderdate.size)
    p = np.clip(orderdate + SHIP_DAYS - CURRENT_DAY, 0, SHIP_DAYS) / SHIP_DAYS
    all_open, all_shipped = p**lines, (1.0 - p) ** lines
    u = rng.random(orderdate.size)
    return np.where(
        u < all_open, ORDER_STATUS["O"],
        np.where(u < all_open + all_shipped, ORDER_STATUS["F"], ORDER_STATUS["P"]),
    )


def _draw_orders(rows: int, dom: dict[str, int], orderkey: np.ndarray, rng) -> tuple[dict, dict]:
    key_cols = {c: rng.integers(0, dom[c], rows, dtype=np.int64) for c in dom}
    lo, hi = TOTALPRICE_RANGE
    value_cols = {
        "orderkey": orderkey,
        "orderstatus": order_status(key_cols["orderdate"], rng),
        "totalprice": np.round(rng.uniform(lo, hi, rows), 2),
        "orderpriority": rng.integers(1, ORDER_PRIORITIES + 1, rows).astype(np.float64),
        "shippriority": np.full(rows, SHIPPRIORITY),
    }
    return key_cols, value_cols


def generate_orders(rows: int, seed: int) -> tuple[dict, dict]:
    """``(key_cols, value_cols)``: int64 keys uniform over their domains
    and float64 values, all drawn in bulk from ``seed``."""
    return _draw_orders(rows, domains(rows), order_keys(rows), np.random.default_rng(seed))


def new_orders(loaded_rows: int, rows: int, seed) -> tuple[dict, dict]:
    """The first ``rows`` orders of RF1 against a table loaded with
    ``loaded_rows`` orders: keys and values drawn as the loader draws
    them (over the loaded table's key domains), from ``seed``. Their
    order keys are dbgen's sparse slot 1 (:func:`order_keys`), which no
    loaded order holds, as RF1 requires."""
    return _draw_orders(rows, domains(loaded_rows), order_keys(rows, slot=1),
                        np.random.default_rng(seed))
