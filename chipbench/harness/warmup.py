"""Warm every program shape the window can use, before it starts.

Each replica serves groups of up to ``max_batch`` reads, which the
kernels pad to a power of two of at least 8 queries. So every replica
runs groups of each template at every such size, through the table's
own ``execute_many`` (views, fused locate+scan, select compaction), and
the view's boundary kernel runs at every pair count a group can make
(up to two boundary blocks per read).

Where the mix writes, :func:`warm_writes` also runs the programs the
window's write calls and read-barrier flushes reach, on throwaway copies of
each replica's resident state: the table neither grows nor changes.
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


def _spans(ends: np.ndarray):
    """Every ``(before, after)`` pair of written-row counts that one
    write call or one replica flush can span: a call sends every
    refresh due, and a flush takes every row staged since the last
    one, so both ends are refresh ends (or 0)."""
    e = [0] + [int(x) for x in ends]
    return [(a, b) for i, a in enumerate(e) for b in e[i + 1:]]


def warm_writes(eng, cf: str, ends: np.ndarray, max_batch: int) -> int:
    """Run the programs that the window's writes and flushes use,
    without growing the table: each write call's statistics update (on
    copies of the column family's statistics), each flush's append to a
    replica's resident arrays and its view extension, for every way the
    window's refreshes can fall into calls and flushes (on throwaway
    states of every capacity the window can reach), and the boundary
    kernel at every run count and capacity the window can reach.
    ``ends`` are the refreshes' end rows (``traffic.write_stream``).
    Returns the calls made."""
    import copy

    import jax.numpy as jnp

    from repro.core.storage.views import extend_views_state
    from repro.kernels import DEVICE_BLOCK_N, boundary_block_sums, device_state_append

    bn = DEVICE_BLOCK_N
    family = eng.column_families[cf]
    calls = 0
    stats = [copy.deepcopy(s) for s in [family.stats] + [p.stats for p in family.partitions]
             if s is not None]
    flushes = _spans(ends)
    for size in sorted({b - a for a, b in flushes}):
        zeros = {c: np.zeros(size, np.int64) for c in family.key_names}
        for st in stats:
            st.merge_rows(zeros, device=True)
            calls += 1

    seen: set = set()
    for table in deploy.replica_tables(eng, cf).values():
        st = table._device
        keys, tile = st["keys"], st["values_tile"]
        sig = (keys.shape, keys.dtype, tile.shape, tile.dtype)
        if sig in seen:
            continue
        seen.add(sig)
        n0, cap0 = int(st["n_rows"]), int(keys.shape[1])

        def cap(n: int) -> int:
            return max(cap0, -(-n // bn) * bn)

        widths = {}

        def state(width: int, n_rows: int) -> dict:
            if width not in widths:
                widths[width] = (keys, tile) if width == cap0 else (
                    jnp.zeros((keys.shape[0], width), keys.dtype),
                    jnp.zeros((tile.shape[0], width), tile.dtype))
            k, t = widths[width]
            return dict(st, keys=k, values_tile=t, n_rows=n_rows)

        appends, extends = set(), set()
        for a, b in flushes:
            n_old, m = n0 + a, b - a
            if (cap(n_old), cap(n0 + b), m) not in appends:
                appends.add((cap(n_old), cap(n0 + b), m))
                run_keys = {c: np.zeros(m, np.int64) for c in table.key_cols}
                run_values = {c: np.zeros(m, np.float64) for c in table.value_cols}
                device_state_append(state(cap(n_old), n_old), table, run_keys, run_values,
                                    np.full(m, n_old, np.int64))
                calls += 1
            if (cap(n0 + b), n_old // bn) not in extends:
                extends.add((cap(n0 + b), n_old // bn))
                extend_views_state(st["views"], state(cap(n0 + b), n0 + b),
                                   np.zeros(m, np.int64), n_old)
                calls += 1
        for width in sorted({cap(n0 + b) for _, b in flushes} | {cap0}):
            t = state(width, n0)["values_tile"]
            for n_win in range(1, len(ends) + 2):
                pairs = 8
                while pairs <= 2 * max_batch:
                    zeros = np.zeros((pairs, n_win), np.int64)
                    boundary_block_sums(
                        t, np.zeros(pairs, np.int32), np.zeros(pairs, np.int32),
                        zeros, zeros, block_n=bn,
                    )
                    calls += 1
                    pairs *= 2
    return calls
