"""Warm every program shape the window can use, before it starts.

Each replica serves groups of up to ``max_batch`` reads, which the
kernels pad to a power of two of at least 8 queries. So every replica
runs groups of each template at every such size, through the table's
own ``execute_many`` (views, fused locate+scan, select compaction), and
the view's boundary kernel runs at every pair count a group can make
(up to two boundary blocks per read).
"""

from __future__ import annotations

import numpy as np

from . import client, deploy, traffic


def group_sizes(max_batch: int) -> list[int]:
    sizes, s = [], 8
    while s < max_batch:
        sizes.append(s)
        s *= 2
    return sizes + [max_batch]


def warm_tables(eng, cf: str, templates: list[dict], dom: dict, max_batch: int, seed) -> int:
    """Runs every (replica, template, group size); returns the launches made."""
    from repro.kernels import DEVICE_BLOCK_N, boundary_block_sums

    sizes = group_sizes(max_batch)
    mix = traffic.queries(templates, dom, len(templates) * max_batch, seed)
    qs = traffic.to_queries(mix)
    by_template = [
        [q for q, t in zip(qs, mix.tmpl) if t == k] for k in range(len(templates))
    ]
    calls = 0
    for table in deploy.replica_tables(eng, cf).values():
        for group in by_template:
            for s in sizes:
                table.execute_many(group[:s])
                calls += 1
        if table.has_views:
            tile = table._device["values_tile"]
            n_win = len(table._device["views"]["run_packed"])
            pairs = 8
            while pairs <= 2 * max_batch:
                zeros = np.zeros((pairs, n_win), np.int64)
                boundary_block_sums(
                    tile, np.zeros(pairs, np.int32), np.zeros(pairs, np.int32),
                    zeros, zeros, block_n=DEVICE_BLOCK_N,
                )
                calls += 1
                pairs *= 2
    return calls


def warm(eng, cf: str, templates: list[dict], dom: dict, max_batch: int,
         timeout_s: float, seed: int) -> None:
    """Every table shape, then the served path itself: a few front-door
    batches of assorted sizes, from queries the window does not use."""
    warm_tables(eng, cf, templates, dom, max_batch, [seed, 3])
    fd = client.make_frontdoor(eng, max_batch)
    qs = traffic.to_queries(traffic.queries(templates, dom, 8 * max_batch, [seed, 4]))
    for s in group_sizes(max_batch) + [1, 3, 5]:
        fd.serve(client.requests(cf, qs[:s], [0.0] * s, timeout_s))
        qs = qs[s:] + qs[:s]
