"""Pallas TPU kernels: device slab location, fused locate+scan, "select".

Together with ``scan_agg`` these put the *entire* read path of a
device-resident replica on the accelerator — after PR 2 the scan itself
ran on device but every batch still round-tripped to host numpy for slab
location (``np.searchsorted`` over the packed key column), for "select"
aggregations, and for re-placement after writes. The three kernels here
remove those host hops.

``slab_locate_batched``
    The device replacement for the host ``searchsorted`` in
    ``SortedTable.slab_many``. A gather-per-probe binary search is
    hostile to the TPU vector unit, so the binary search is vectorized
    into its branch-free *rank* form over the sorted key lanes: for a
    query whose packed slab bounds are ``[lo, hi]`` (inclusive),

        lo_idx = |{rows r : key(r) <  lo  (lex)}|
        hi_idx = |{rows r : key(r) <= hi  (lex)}|

    two masked popcounts the VPU evaluates for every query of the batch
    while the key lanes stream through VMEM once (the same row-block
    grid as the scan kernel). On a sorted column these ranks equal
    ``np.searchsorted(packed, lo, "left")`` / ``(packed, hi, "right")``
    exactly (property-tested against that oracle). The output is a
    device array that feeds ``scan_agg_batched``'s ``slabs`` operand
    directly — a locate→scan device pipeline with no host sync.

``scan_agg_locate_batched``
    The fused form used by the batched read fast path. Because rows are
    compared against the packed slab bounds *by key*, a row's slab
    membership ("would the sorted scan stream it") is decided inside the
    scan predicate itself — the locate disappears into the scan and one
    launch returns, per query, the masked float32 aggregate **and** the
    int32 matched/slab-row counts. Counts ride an int32 output (exact to
    2**31), which is what lifts the old float32 2**24-row device cap —
    and because slab membership is a per-row key predicate, the counts
    stay correct even when the resident arrays hold appended (unsorted)
    write runs.

``select_compact_batched``
    Device "select": emit the matched row indices by block-local
    prefix-sum compaction. Two passes: the fused kernel counts matches
    (sizing the output), then this kernel walks the row blocks keeping a
    per-query running base in a VMEM-resident carry accumulator; each
    128-row sub-chunk computes an exclusive prefix sum of its match mask
    (a triangular-ones matmul) and places row indices at ``base +
    local`` of a pre-sized ``(Q, out_width)`` output in one-hot form —
    no cumsum and no dynamic scatter, both of which Mosaic refuses.

Every wrapper launches at most :func:`query_chunk` queries at a time,
the VMEM bound of the ``(Q_pad, block_n)`` per-step intermediates.

Lane layout, ``col_parts`` (wide two-lane columns) and padding
conventions are shared with ``scan_agg`` — lexicographic comparison
over the lane sequence equals numeric order on the packed key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .scan_agg import _lex_ge, _lex_lt, _pad_to, launch_query_chunks, query_chunk

__all__ = [
    "slab_locate_kernel",
    "slab_locate_batched",
    "scan_agg_locate_kernel",
    "scan_agg_locate_batched",
    "select_compact_kernel",
    "select_compact_batched",
    "residual_membership_batched",
]


def _lex_tuple_ge(keys, bounds, n_lanes):
    """(Q, block_n) mask: key lane tuple >= per-query bound tuple,
    lexicographic over the first ``n_lanes`` lanes (MSB lane first, so
    it equals numeric order on the packed composite key)."""
    acc = None
    for lane in reversed(range(n_lanes)):
        k = keys[lane : lane + 1, :]  # (1, block_n)
        b = bounds[:, lane : lane + 1]  # (Q, 1)
        acc = (k >= b) if acc is None else (k > b) | ((k == b) & acc)
    return acc


def _lex_tuple_le(keys, bounds, n_lanes):
    acc = None
    for lane in reversed(range(n_lanes)):
        k = keys[lane : lane + 1, :]
        b = bounds[:, lane : lane + 1]
        acc = (k <= b) if acc is None else (k < b) | ((k == b) & acc)
    return acc


def _residual_pred(keys, lo, hi, col_parts, base):
    """AND the per-column residual range predicate ([lo, hi) per logical
    column, wide columns as lexicographic lane pairs) onto ``base``."""
    pred = base
    lane = 0
    for parts in col_parts:
        if parts == 1:
            k = keys[lane : lane + 1, :]
            pred &= (k >= lo[:, lane : lane + 1]) & (k < hi[:, lane : lane + 1])
        else:
            kh = keys[lane : lane + 1, :]
            kl = keys[lane + 1 : lane + 2, :]
            pred &= _lex_ge(kh, kl, lo[:, lane : lane + 1], lo[:, lane + 1 : lane + 2])
            pred &= _lex_lt(kh, kl, hi[:, lane : lane + 1], hi[:, lane + 1 : lane + 2])
        lane += parts
    return pred


def _row_window(limits, block_n, i):
    """(Q, block_n) row-validity mask for grid step ``i``: row index in
    the query's [start, stop) window. Padded queries carry (0, 0)."""
    ridx = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    return ridx, (ridx >= limits[:, 0:1]) & (ridx < limits[:, 1:2])


def residual_membership_batched(
    keys: jax.Array,  # int32[K_ex(+pad), N]
    res_lo: jax.Array,  # int32[Q, K_ex] residual bounds, inclusive
    res_hi: jax.Array,  # int32[Q, K_ex] residual bounds, EXCLUSIVE
    limits: jax.Array,  # int32[Q, 2] row window
    *,
    col_parts: tuple[int, ...] | None = None,
) -> jax.Array:
    """bool[Q, N] device membership mask — the kernels' own residual
    predicate evaluated whole-array. This is the wide-select fallback:
    when a compaction output block cannot stay VMEM-sized, callers take
    this mask and pull back only the matched indices via per-query
    ``jnp.flatnonzero(mask[j], size=count)`` (counts come from the fused
    pass), never the mask itself."""
    keys = jnp.asarray(keys, jnp.int32)
    res_lo = jnp.asarray(res_lo, jnp.int32)
    res_hi = jnp.asarray(res_hi, jnp.int32)
    limits = jnp.asarray(limits, jnp.int32)
    Q, K_ex = res_lo.shape
    if col_parts is None:
        col_parts = (1,) * K_ex
    col_parts = tuple(int(p) for p in col_parts)
    if sum(col_parts) != K_ex or not all(p in (1, 2) for p in col_parts):
        raise ValueError(f"col_parts {col_parts} does not tile {K_ex} bound lanes")
    ridx = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :]
    valid = (ridx >= limits[:, 0:1]) & (ridx < limits[:, 1:2])
    return _residual_pred(keys, res_lo, res_hi, col_parts, valid)


# -- rank-form binary search --------------------------------------------------


def slab_locate_kernel(n_lanes, limits_ref, keys_ref, lo_ref, hi_ref, out_ref):
    """One row-block step: every query counts the window rows lying
    strictly below its lower slab key (lane 0) and at-or-below its upper
    slab key (lane 1) — the two searchsorted ranks."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]
    _, valid = _row_window(limits_ref[...], keys.shape[1], i)

    below = valid & ~_lex_tuple_ge(keys, lo, n_lanes)
    at_or_below = valid & _lex_tuple_le(keys, hi, n_lanes)
    cnt_lo = jnp.sum(below.astype(jnp.int32), axis=1, keepdims=True)
    cnt_hi = jnp.sum(at_or_below.astype(jnp.int32), axis=1, keepdims=True)
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = (
        out_ref[...]
        + jnp.where(lane_idx == 0, cnt_lo, 0)
        + jnp.where(lane_idx == 1, cnt_hi, 0)
    )


@functools.partial(jax.jit, static_argnames=("n_lanes", "block_n", "interpret"))
def _slab_locate_call(keys, slab_lo, slab_hi, limits, *, n_lanes, block_n, interpret):
    N = keys.shape[1]
    Q = slab_lo.shape[0]
    K_pad = max(8, -(-keys.shape[0] // 8) * 8)
    Q_pad = max(8, -(-Q // 8) * 8)
    N_pad = -(-max(N, 1) // block_n) * block_n

    keys_p = _pad_to(_pad_to(keys.astype(jnp.int32), N_pad, 1, 0), K_pad, 0, 0)
    lo_p = _pad_to(_pad_to(slab_lo.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    hi_p = _pad_to(_pad_to(slab_hi.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    lim_p = _pad_to(limits.astype(jnp.int32), Q_pad, 0, 0)

    out = pl.pallas_call(
        functools.partial(slab_locate_kernel, n_lanes),
        grid=(N_pad // block_n,),
        in_specs=[
            pl.BlockSpec((Q_pad, 2), lambda i: (0, 0)),
            pl.BlockSpec((K_pad, block_n), lambda i: (0, i)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Q_pad, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Q_pad, 128), jnp.int32),
        interpret=interpret,
    )(lim_p, keys_p, lo_p, hi_p)
    return out[:Q, :2]


def slab_locate_batched(
    keys: jax.Array,  # int32[K_ex(+pad), N] — key lanes
    slab_lo: jax.Array,  # int32[Q, K_ex] — lower slab key, per lane (inclusive)
    slab_hi: jax.Array,  # int32[Q, K_ex] — upper slab key, per lane (INCLUSIVE)
    limits: jax.Array,  # int32[Q, 2] — [start, stop) row window (usually [0, N))
    *,
    n_lanes: int | None = None,
    block_n: int = 2048,
    max_q: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """int32[Q, 2] = (lo_idx, hi_idx) row slabs — the vectorized binary
    search. On a sorted key column this equals ``searchsorted(packed,
    lo, "left")`` / ``searchsorted(packed, hi, "right")``. An empty
    query is encoded as ``slab_lo = 0``-lanes, ``slab_hi = -1``-lanes
    (or a ``(0, 0)`` window) and yields ``(0, 0)``. Launches carry at
    most :func:`query_chunk` queries (lowered by ``max_q``)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    keys = jnp.asarray(keys, jnp.int32)
    slab_lo = np.asarray(slab_lo, np.int32)
    K_ex = slab_lo.shape[1]
    if n_lanes is None:
        n_lanes = K_ex
    if not 0 < n_lanes <= keys.shape[0]:
        raise ValueError(f"n_lanes {n_lanes} out of range for {keys.shape[0]} key lanes")
    (out,) = launch_query_chunks(
        lambda *ops: (
            _slab_locate_call(
                keys, *ops, n_lanes=n_lanes, block_n=block_n, interpret=interpret
            ),
        ),
        (slab_lo, np.asarray(slab_hi, np.int32), np.asarray(limits, np.int32)),
        max_q=query_chunk(block_n, max_q),
    )
    return out


# -- fused locate + scan ------------------------------------------------------


def scan_agg_locate_kernel(
    col_parts,
    n_vals,
    limits_ref,
    sel_ref,
    keys_ref,
    vals_ref,
    res_lo_ref,
    res_hi_ref,
    slab_lo_ref,
    slab_hi_ref,
    out_f_ref,
    out_i_ref,
):
    """One row-block step serving every query: float32 masked aggregate
    (out_f lane 0) plus int32 matched count (out_i lane 0) and slab row
    count (out_i lane 1). Slab membership is the lexicographic key-range
    test, so no row-index slab input exists at all."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_f_ref[...] = jnp.zeros_like(out_f_ref)
        out_i_ref[...] = jnp.zeros_like(out_i_ref)

    keys = keys_ref[...]
    vals = vals_ref[...]
    sel = sel_ref[...]
    _, valid = _row_window(limits_ref[...], keys.shape[1], i)

    n_lanes = sum(col_parts)
    slab_ok = (
        valid
        & _lex_tuple_ge(keys, slab_lo_ref[...], n_lanes)
        & _lex_tuple_le(keys, slab_hi_ref[...], n_lanes)
    )
    matched = _residual_pred(keys, res_lo_ref[...], res_hi_ref[...], col_parts, valid)

    fmask = matched.astype(jnp.float32)
    vq = jnp.zeros(fmask.shape, jnp.float32)
    for v in range(n_vals):
        vq += jnp.where(sel == v, vals[v : v + 1, :], 0.0)
    part_sum = jnp.sum(vq * fmask, axis=1, keepdims=True)
    cnt = jnp.sum(matched.astype(jnp.int32), axis=1, keepdims=True)
    slab_cnt = jnp.sum(slab_ok.astype(jnp.int32), axis=1, keepdims=True)

    lane_f = jax.lax.broadcasted_iota(jnp.int32, out_f_ref.shape, 1)
    out_f_ref[...] = out_f_ref[...] + jnp.where(lane_f == 0, part_sum, 0.0)
    lane_i = jax.lax.broadcasted_iota(jnp.int32, out_i_ref.shape, 1)
    out_i_ref[...] = (
        out_i_ref[...]
        + jnp.where(lane_i == 0, cnt, 0)
        + jnp.where(lane_i == 1, slab_cnt, 0)
    )


@functools.partial(
    jax.jit, static_argnames=("col_parts", "n_vals", "block_n", "interpret")
)
def _fused_call(
    keys,
    values,
    res_lo,
    res_hi,
    slab_lo,
    slab_hi,
    limits,
    value_sel,
    *,
    col_parts,
    n_vals,
    block_n,
    interpret,
):
    N = keys.shape[1]
    Q = res_lo.shape[0]
    K_pad = max(8, -(-keys.shape[0] // 8) * 8)
    V_pad = max(8, -(-values.shape[0] // 8) * 8)
    Q_pad = max(8, -(-Q // 8) * 8)
    N_pad = -(-max(N, 1) // block_n) * block_n

    keys_p = _pad_to(_pad_to(keys.astype(jnp.int32), N_pad, 1, 0), K_pad, 0, 0)
    vals_p = _pad_to(_pad_to(values.astype(jnp.float32), N_pad, 1, 0.0), V_pad, 0, 0.0)
    res_lo_p = _pad_to(_pad_to(res_lo.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    res_hi_p = _pad_to(_pad_to(res_hi.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    slab_lo_p = _pad_to(_pad_to(slab_lo.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    slab_hi_p = _pad_to(_pad_to(slab_hi.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    lim_p = _pad_to(limits.astype(jnp.int32), Q_pad, 0, 0)
    sel_p = _pad_to(value_sel.astype(jnp.int32)[:, None], Q_pad, 0, 0)

    kernel = functools.partial(scan_agg_locate_kernel, col_parts, n_vals)
    out_f, out_i = pl.pallas_call(
        kernel,
        grid=(N_pad // block_n,),
        in_specs=[
            pl.BlockSpec((Q_pad, 2), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((K_pad, block_n), lambda i: (0, i)),
            pl.BlockSpec((V_pad, block_n), lambda i: (0, i)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((Q_pad, 128), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, 128), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Q_pad, 128), jnp.float32),
            jax.ShapeDtypeStruct((Q_pad, 128), jnp.int32),
        ),
        interpret=interpret,
    )(lim_p, sel_p, keys_p, vals_p, res_lo_p, res_hi_p, slab_lo_p, slab_hi_p)
    return out_f[:Q, 0], out_i[:Q, 0], out_i[:Q, 1]


def scan_agg_locate_batched(
    keys: jax.Array,  # int32[K_ex(+pad), N]
    values: jax.Array,  # float32[N] or float32[V(+pad), N]
    res_lo: jax.Array,  # int32[Q, K_ex] residual bounds, inclusive
    res_hi: jax.Array,  # int32[Q, K_ex] residual bounds, EXCLUSIVE
    slab_lo: jax.Array,  # int32[Q, K_ex] slab key, inclusive
    slab_hi: jax.Array,  # int32[Q, K_ex] slab key, INCLUSIVE
    limits: jax.Array,  # int32[Q, 2] row window ([0, N) for live queries)
    value_sel: jax.Array | None = None,  # int32[Q]
    *,
    col_parts: tuple[int, ...] | None = None,
    n_vals: int | None = None,
    block_n: int = 2048,
    max_q: int | None = None,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fused locate+scan: ``(sum f32[Q], matched i32[Q], slab_rows
    i32[Q])``, columns streamed from HBM once per launch of at most
    :func:`query_chunk` queries (lowered by ``max_q``). ``slab_rows``
    is the number of rows a sorted scan of the slab would stream (==
    ``hi_idx - lo_idx`` of :func:`slab_locate_batched`); matched/sum
    use the residual per-column predicate only, which the slab provably
    contains."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    values = jnp.asarray(values, jnp.float32)
    if values.ndim == 1:
        values = values[None, :]
    keys = jnp.asarray(keys, jnp.int32)
    res_lo = np.asarray(res_lo, np.int32)
    Q, K_ex = res_lo.shape
    if value_sel is None:
        value_sel = np.zeros(Q, np.int32)
    if col_parts is None:
        col_parts = (1,) * K_ex
    col_parts = tuple(int(p) for p in col_parts)
    if sum(col_parts) != K_ex or not all(p in (1, 2) for p in col_parts):
        raise ValueError(f"col_parts {col_parts} does not tile {K_ex} bound lanes")
    if K_ex > keys.shape[0]:
        raise ValueError(f"bounds cover {K_ex} lanes but keys carry {keys.shape[0]}")
    if n_vals is None:
        n_vals = int(values.shape[0])
    if not 0 < n_vals <= values.shape[0]:
        raise ValueError(f"n_vals {n_vals} out of range for {values.shape[0]} rows")
    return launch_query_chunks(
        functools.partial(
            _fused_call,
            keys,
            values,
            col_parts=col_parts,
            n_vals=n_vals,
            block_n=block_n,
            interpret=interpret,
        ),
        (res_lo, res_hi, slab_lo, slab_hi, limits, value_sel),
        max_q=query_chunk(block_n, max_q),
    )


# -- "select": block-local prefix-sum compaction ------------------------------

# rows per select sub-chunk, and output slots per scatter tile: one lane
# width, so the prefix-sum matmul is (Q, 128) x (128, 128)
_SUB = 128


def select_compact_kernel(
    col_parts, limits_ref, keys_ref, res_lo_ref, res_hi_ref, out_ref, carry_ref
):
    """One row-block step of the two-pass select: the carry accumulator
    (lane 0) holds each query's match count over earlier blocks; this
    block's matches land at ``carry + exclusive-prefix-sum`` of the
    match mask.

    The block is walked in 128-row sub-chunks. A sub-chunk's exclusive
    prefix sum is one MXU matmul of its 0/1 match mask against a
    strictly upper-triangular ones matrix (exact: 0/1 operands, sums of
    at most 128). The scatter is its one-hot form: for each 128-slot
    output tile the sub-chunk can reach, ``(pos == slot) & matched``
    selects each matched row's index into its slot, summed over rows —
    every matched row owns its slot, so the sum is exact. Sub-chunks
    without matches skip the scatter."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        carry_ref[...] = jnp.zeros_like(carry_ref)

    block_n = keys_ref.shape[1]
    n_tiles = out_ref.shape[1] // _SUB
    limits = limits_ref[...]
    res_lo = res_lo_ref[...]
    res_hi = res_hi_ref[...]
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 1)
    ).astype(jnp.float32)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _SUB), 2)

    def sub_chunk(c, base):  # base: (Q, 1) matches before this sub-chunk
        start = pl.multiple_of(c * _SUB, _SUB)
        keys = keys_ref[:, pl.ds(start, _SUB)]
        ridx = i * block_n + start + jax.lax.broadcasted_iota(jnp.int32, (1, _SUB), 1)
        valid = (ridx >= limits[:, 0:1]) & (ridx < limits[:, 1:2])
        matched = _residual_pred(keys, res_lo, res_hi, col_parts, valid)
        cnt = jnp.sum(matched.astype(jnp.int32), axis=1, keepdims=True)

        @pl.when(jnp.max(cnt) > 0)
        def _scatter():
            local = jnp.dot(
                matched.astype(jnp.float32), tri, preferred_element_type=jnp.float32
            ).astype(jnp.int32)
            pos = jnp.where(matched, base + local, -1)
            # output tiles this sub-chunk's matches reach, over all queries
            t_lo = jnp.min(jnp.where(cnt > 0, base, 1 << 30)) // _SUB
            t_hi = jnp.max(jnp.where(cnt > 0, base + cnt - 1, -1)) // _SUB
            rows = ridx[:, :, None]

            def tile(t, carry):
                s0 = pl.multiple_of(t * _SUB, _SUB)
                hit = pos[:, :, None] == s0 + slot  # (Q, rows, slots)
                out_ref[:, pl.ds(s0, _SUB)] += jnp.sum(jnp.where(hit, rows, 0), axis=1)
                return carry

            jax.lax.fori_loop(t_lo, jnp.minimum(t_hi, n_tiles - 1) + 1, tile, 0)

        return base + cnt

    base = jax.lax.fori_loop(0, block_n // _SUB, sub_chunk, carry_ref[:, 0:1])
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, carry_ref.shape, 1)
    carry_ref[...] = jnp.where(lane_idx == 0, base, 0)


@functools.partial(
    jax.jit, static_argnames=("col_parts", "out_width", "block_n", "interpret")
)
def _select_call(keys, res_lo, res_hi, limits, *, col_parts, out_width, block_n, interpret):
    N = keys.shape[1]
    Q = res_lo.shape[0]
    K_pad = max(8, -(-keys.shape[0] // 8) * 8)
    Q_pad = max(8, -(-Q // 8) * 8)
    N_pad = -(-max(N, 1) // block_n) * block_n

    keys_p = _pad_to(_pad_to(keys.astype(jnp.int32), N_pad, 1, 0), K_pad, 0, 0)
    lo_p = _pad_to(_pad_to(res_lo.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    hi_p = _pad_to(_pad_to(res_hi.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    lim_p = _pad_to(limits.astype(jnp.int32), Q_pad, 0, 0)

    kernel = functools.partial(select_compact_kernel, col_parts)
    out, _carry = pl.pallas_call(
        kernel,
        grid=(N_pad // block_n,),
        in_specs=[
            pl.BlockSpec((Q_pad, 2), lambda i: (0, 0)),
            pl.BlockSpec((K_pad, block_n), lambda i: (0, i)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((Q_pad, out_width), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, 128), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Q_pad, out_width), jnp.int32),
            jax.ShapeDtypeStruct((Q_pad, 128), jnp.int32),
        ),
        interpret=interpret,
    )(lim_p, keys_p, lo_p, hi_p)
    return out[:Q]


def select_compact_batched(
    keys: jax.Array,  # int32[K_ex(+pad), N]
    res_lo: jax.Array,  # int32[Q, K_ex] residual bounds, inclusive
    res_hi: jax.Array,  # int32[Q, K_ex] residual bounds, EXCLUSIVE
    limits: jax.Array,  # int32[Q, 2] row window
    *,
    col_parts: tuple[int, ...] | None = None,
    out_width: int = 128,
    block_n: int = 2048,
    max_q: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """int32[Q, out_width]: per query, its matched row indices compacted
    to the front (slots past the match count stay 0 — callers slice with
    the counts from the fused pass). ``out_width`` must cover the
    largest match count in the batch; it and ``block_n`` are multiples
    of 128. Launches carry at most :func:`query_chunk` queries (lowered
    by ``max_q``)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if out_width % _SUB or block_n % _SUB:
        raise ValueError(
            f"out_width {out_width} and block_n {block_n} must be multiples of {_SUB}"
        )
    keys = jnp.asarray(keys, jnp.int32)
    res_lo = np.asarray(res_lo, np.int32)
    K_ex = res_lo.shape[1]
    if col_parts is None:
        col_parts = (1,) * K_ex
    col_parts = tuple(int(p) for p in col_parts)
    if sum(col_parts) != K_ex or not all(p in (1, 2) for p in col_parts):
        raise ValueError(f"col_parts {col_parts} does not tile {K_ex} bound lanes")
    (out,) = launch_query_chunks(
        lambda *ops: (
            _select_call(
                keys, *ops, col_parts=col_parts, out_width=out_width,
                block_n=block_n, interpret=interpret,
            ),
        ),
        (res_lo, res_hi, limits),
        max_q=query_chunk(block_n, max_q),
    )
    return out
