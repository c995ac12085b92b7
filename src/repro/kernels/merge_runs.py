"""Pallas TPU kernel: k-way merge of device-resident sorted runs.

After memtable flushes a device-resident replica holds a *stack* of
sorted runs (base + appended) in its resident arrays; compaction must
collapse them into one sorted run **on device** — no host re-upload of
the N-sized columns. A gather-driven merge is hostile to the TPU vector
unit, so (like the binary search in ``slab_locate``) the merge is
vectorized into its branch-free *merge-path rank* form: a row's merged
position is its within-run position plus its rank in every other run,

    merged_pos(e ∈ run r) = local_pos(e)
                          + |{rows j in runs before r : key_j <  key_e}|
                          + |{rows j in runs after  r : key_j <= key_e}|

two masked popcounts per element, evaluated for a whole probe block
while the key lanes stream through VMEM (the same row-block grid as the
scan kernels). Runs are contiguous in device order — run r's
predecessors occupy ``[0, start_r)`` and its successors ``[end_r, N)``
— so the k-way merge needs exactly one strict-rank window and one
inclusive-rank window per element, independent of the run count.

The tie rule (strict below for earlier runs, at-or-below for later
runs, arrival order within a run) is precisely the host merge order of
``SortedTable.merge_run`` — a freshly written row lands *before* equal
existing rows — so the computed permutation equals the incremental
``row_map`` and the compacted device order equals the host row order
(``row_map`` collapses to identity; property-tested).

Work is O(M · N) popcounts for M appended rows: only appended rows are
probed, and the base rows' positions follow from the appended rows'
strict ranks in the sorted base (``merge_run_positions``), so the
N-sized base never becomes a probe set. The numpy/lexsort oracle lives
in ``ref.merge_run_positions_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .scan_agg import _pad_to, launch_query_chunks, query_chunk
from .slab_locate import _lex_tuple_ge, _lex_tuple_le

__all__ = [
    "merge_rank_kernel",
    "merge_rank_batched",
    "merge_run_positions",
]


def merge_rank_kernel(
    n_lanes, row_off, lim_lt_ref, lim_le_ref, probes_ref, keys_ref, out_ref
):
    """One row-block step: every probe counts the rows of its strict
    window lying lexicographically below its key tuple (lane 0) and the
    rows of its inclusive window at-or-below it (lane 1). ``row_off``
    (static) is the grid's starting block — probe sets whose windows
    live in a suffix of the rows skip the prefix blocks entirely."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]  # (K_pad, block_n) int32 key lanes
    probes = probes_ref[...]  # (Q_pad, K_pad) int32 probe key tuples
    lim_lt = lim_lt_ref[...]  # (Q_pad, 2) strict-rank row window
    lim_le = lim_le_ref[...]  # (Q_pad, 2) inclusive-rank row window

    block_n = keys.shape[1]
    ridx = (i + row_off) * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1
    )
    in_lt = (ridx >= lim_lt[:, 0:1]) & (ridx < lim_lt[:, 1:2])
    in_le = (ridx >= lim_le[:, 0:1]) & (ridx < lim_le[:, 1:2])

    below = in_lt & ~_lex_tuple_ge(keys, probes, n_lanes)
    at_or_below = in_le & _lex_tuple_le(keys, probes, n_lanes)
    cnt_lt = jnp.sum(below.astype(jnp.int32), axis=1, keepdims=True)
    cnt_le = jnp.sum(at_or_below.astype(jnp.int32), axis=1, keepdims=True)
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = (
        out_ref[...]
        + jnp.where(lane_idx == 0, cnt_lt, 0)
        + jnp.where(lane_idx == 1, cnt_le, 0)
    )


@functools.partial(
    jax.jit, static_argnames=("n_lanes", "row_off", "block_n", "interpret")
)
def _merge_rank_call(
    keys, probes, lim_lt, lim_le, *, n_lanes, row_off, block_n, interpret
):
    N = keys.shape[1]
    Q = probes.shape[0]
    K_pad = max(8, -(-keys.shape[0] // 8) * 8)
    Q_pad = max(8, -(-Q // 8) * 8)
    N_pad = -(-max(N, 1) // block_n) * block_n

    keys_p = _pad_to(_pad_to(keys.astype(jnp.int32), N_pad, 1, 0), K_pad, 0, 0)
    probes_p = _pad_to(_pad_to(probes.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    # padded probes carry (0, 0) windows and count nothing
    lt_p = _pad_to(lim_lt.astype(jnp.int32), Q_pad, 0, 0)
    le_p = _pad_to(lim_le.astype(jnp.int32), Q_pad, 0, 0)

    n_blocks = N_pad // block_n - row_off
    out = pl.pallas_call(
        functools.partial(merge_rank_kernel, n_lanes, row_off),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((Q_pad, 2), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, 2), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
            pl.BlockSpec((K_pad, block_n), lambda i, _off=row_off: (0, i + _off)),
        ],
        out_specs=pl.BlockSpec((Q_pad, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Q_pad, 128), jnp.int32),
        interpret=interpret,
    )(lt_p, le_p, probes_p, keys_p)
    return out[:Q, :2]


def merge_rank_batched(
    keys: jax.Array,  # int32[K_ex(+pad), N] — key lanes, device row order
    probes: jax.Array,  # int32[Q, n_lanes] — probe key tuples
    lim_lt: jax.Array,  # int32[Q, 2] — strict-rank row window per probe
    lim_le: jax.Array,  # int32[Q, 2] — inclusive-rank row window per probe
    *,
    n_lanes: int,
    row_start: int = 0,
    block_n: int = 2048,
    max_q: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """int32[Q, 2] = per probe, (strict rank in its lt window, inclusive
    rank in its le window). ``row_start`` drops whole leading row blocks
    from the stream when every window lies at or past it. Launches
    carry at most :func:`query_chunk` probes (lowered by ``max_q``)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    keys = jnp.asarray(keys, jnp.int32)
    probes = np.asarray(probes, np.int32)
    if not 0 < n_lanes <= keys.shape[0]:
        raise ValueError(f"n_lanes {n_lanes} out of range for {keys.shape[0]} key lanes")
    if probes.shape[1] < n_lanes:
        raise ValueError(f"probes carry {probes.shape[1]} lanes, need {n_lanes}")
    (out,) = launch_query_chunks(
        lambda *ops: (
            _merge_rank_call(
                keys, *ops, n_lanes=n_lanes, row_off=row_start // block_n,
                block_n=block_n, interpret=interpret,
            ),
        ),
        (probes, lim_lt, lim_le),
        max_q=query_chunk(block_n, max_q),
    )
    return out


def merge_run_positions(
    keys: jax.Array,  # int32[K_ex(+pad), N(+pad)] — resident key lanes
    run_starts,  # sequence of run start offsets (run 0 = base at 0)
    n_rows: int,
    *,
    n_lanes: int,
    block_n: int = 2048,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> np.ndarray:
    """int64[n_rows] merged position of every device row — the k-way
    merge permutation (see module docstring for the tie rule). Two rank
    launches, both probed by the appended rows only: one over every row
    (strict rank in the base, inclusive rank in the later runs), one
    streamed from the base boundary onward (strict rank in the earlier
    appended runs). Base rows need no probe: the base is sorted, so an
    appended row with strict base rank ``lb`` precedes exactly the base
    rows ``i >= lb``, and base row ``i`` moves down by the number of
    appended rows with ``lb <= i``."""
    starts = np.asarray(tuple(run_starts) + (n_rows,), dtype=np.int64)
    n_runs = len(starts) - 1
    if n_runs <= 1:
        return np.arange(n_rows, dtype=np.int64)
    if not use_pallas:
        from . import ref

        return ref.merge_run_positions_ref(keys, run_starts, n_rows, n_lanes=n_lanes)
    base_end = int(starts[1])
    m = n_rows - base_end
    run_lens = np.diff(starts)[1:]  # appended runs only
    run_start = np.repeat(starts[1:-1], run_lens)  # each probe's run start

    # launch 1: strict rank in the base [0, base_end), inclusive rank in
    # the successor runs [end_r, n_rows)
    probes = np.asarray(keys[:n_lanes, base_end:n_rows]).T
    lim_base = np.zeros((m, 2), np.int64)
    lim_base[:, 1] = base_end
    lim_after = np.empty((m, 2), np.int64)
    lim_after[:, 0] = np.repeat(starts[2:], run_lens)
    lim_after[:, 1] = n_rows
    ranks = np.asarray(
        merge_rank_batched(
            keys, probes, lim_base, lim_after, n_lanes=n_lanes, block_n=block_n,
            interpret=interpret,
        ),
        np.int64,
    )
    lb = ranks[:, 0]
    # launch 2: strict rank in the earlier appended runs [base_end,
    # start_r), streamed from the base boundary's block onward
    lim_before = np.empty((m, 2), np.int64)
    lim_before[:, 0] = base_end
    lim_before[:, 1] = run_start
    before = np.asarray(
        merge_rank_batched(
            keys, probes, lim_before, np.zeros((m, 2), np.int64),
            n_lanes=n_lanes, row_start=base_end, block_n=block_n,
            interpret=interpret,
        ),
        np.int64,
    )[:, 0]
    local = np.arange(m, dtype=np.int64) - (run_start - base_end)
    pos_app = local + lb + before + ranks[:, 1]
    base_rows = np.arange(base_end, dtype=np.int64)
    pos_base = base_rows + np.searchsorted(np.sort(lb), base_rows, side="right")
    return np.concatenate([pos_base, pos_app])
