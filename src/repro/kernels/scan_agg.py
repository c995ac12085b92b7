"""Pallas TPU kernel: predicated slab scan + aggregate.

This is the paper's hot loop — the SSTable scan of Fig 2 — adapted to the
TPU memory hierarchy. The storage layout is *columnar* with rows along
the 128-lane axis (keys: int32[K, N]), so one VMEM tile holds a block of
rows for every clustering key and the residual predicate evaluates as a
vectorized compare + AND-reduce over the (tiny) K sublane axis; the
aggregation is a masked reduction feeding per-query scalar accumulators.

HBM→VMEM traffic is exactly rows × row_bytes, which is what Eq (1) of the
paper counts — the kernel makes Row() the literal unit of memory cost.

Row-streaming grid (the PR 2 batched form)
------------------------------------------
``scan_agg_batched_pallas`` serves a whole query batch with one kernel
launch over a replica's device-resident columns, given host-located
row slabs. (The engine's ``read_many`` device path now routes through
the FUSED locate+scan variant in ``slab_locate.py``, which decides slab
membership inside the predicate; this kernel is kept as the pre-fusion
baseline and general slab-mask scan.) Row blocks are the **outer**
(and only) grid axis: each
key/value tile is fetched from HBM exactly once per batch and every
query's accumulator is *revisited* at every row step — the accumulators
live in a single (Q_pad, 128) output block whose index map is constant
across the grid, so it stays resident in VMEM for the whole launch (the
standard Pallas reduction pattern). HBM traffic is therefore
``N × (K_ex + V) × 4`` bytes regardless of Q — the paper's "pay the
serialization cost once, amortize across queries" applied to HBM instead
of disk. Block shapes:

  keys   (K_ex_pad, block_n) — key *lanes* (wide columns occupy two)
  values (V_pad, block_n)    — one sublane per distinct value column
                               (+ a ones row for counts)
  lo/hi  (Q_pad, K_ex_pad)   — per-query per-lane bounds, resident
  slabs  (Q_pad, 2)          — per-query [lo, hi) row slabs, resident
  sel    (Q_pad, 1)          — per-query value-row selector, resident
  out    (Q_pad, 128)        — lane 0: Σ value·mask, lane 1: Σ mask

Mixed aggregations ride in one launch: a "count" query selects the ones
value row, a "sum" query selects its value column's row.

Wide keys: a column wider than 30 bits ships as two int32 lanes
(hi = v >> 30, lo = v & (2^30−1)); ``col_parts`` marks how many lanes
each logical column occupies and the predicate compares lane pairs
lexicographically, which equals the numeric order on the int64 value.

The legacy queries-outer grid (grid = (queries, row blocks), row axis
fastest, key tiles re-fetched per query so HBM key traffic scales with
Q) is kept as ``scan_agg_batched_qgrid_pallas`` for the perf trajectory
benchmark (`benchmarks/batched_read.py --device`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "WIDE_LANE_BITS",
    "scan_agg_rowstream_kernel",
    "scan_agg_batched_pallas",
    "scan_agg_qgrid_kernel",
    "scan_agg_batched_qgrid_pallas",
    "scan_agg_pallas",
]

# A key lane is an int32; columns wider than this many bits are split
# into (hi, lo) lane pairs compared lexicographically.
WIDE_LANE_BITS = 30


def _lex_ge(h, l, bh, bl):
    """(h, l) >= (bh, bl) lexicographically (== numeric >= on the
    recombined value when l, bl < 2**WIDE_LANE_BITS)."""
    return (h > bh) | ((h == bh) & (l >= bl))


def _lex_lt(h, l, bh, bl):
    return (h < bh) | ((h == bh) & (l < bl))


def scan_agg_rowstream_kernel(
    col_parts, n_vals, slabs_ref, sel_ref, keys_ref, vals_ref, lo_ref, hi_ref, out_ref
):
    """One row-block grid step serving *every* query in the batch.

    ``col_parts`` (static) lists the lane count (1 or 2) of each logical
    key column; ``n_vals`` (static) is the number of live value rows.
    The output block's index map is constant, so ``out_ref`` is the same
    VMEM-resident accumulator at every step — initialized at step 0,
    accumulated into at every step (revisited-accumulator pattern).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]  # (K_ex_pad, block_n) int32 key lanes
    vals = vals_ref[...]  # (V_pad, block_n) float32 value rows
    lo = lo_ref[...]  # (Q_pad, K_ex_pad) int32, inclusive (per lane)
    hi = hi_ref[...]  # (Q_pad, K_ex_pad) int32, exclusive (per lane)
    slabs = slabs_ref[...]  # (Q_pad, 2) int32
    sel = sel_ref[...]  # (Q_pad, 1) int32 value-row selector

    block_n = keys.shape[1]
    row0 = i * block_n
    ridx = row0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    # (Q_pad, block_n); padded queries carry slab (0, 0) → all-false
    pred = (ridx >= slabs[:, 0:1]) & (ridx < slabs[:, 1:2])

    lane = 0
    for parts in col_parts:  # static unroll over logical key columns
        if parts == 1:
            k = keys[lane : lane + 1, :]  # (1, block_n)
            pred &= (k >= lo[:, lane : lane + 1]) & (k < hi[:, lane : lane + 1])
        else:  # wide column: (hi, lo) lane pair, lexicographic range
            kh = keys[lane : lane + 1, :]
            kl = keys[lane + 1 : lane + 2, :]
            pred &= _lex_ge(kh, kl, lo[:, lane : lane + 1], lo[:, lane + 1 : lane + 2])
            pred &= _lex_lt(kh, kl, hi[:, lane : lane + 1], hi[:, lane + 1 : lane + 2])
        lane += parts

    fmask = pred.astype(jnp.float32)  # (Q_pad, block_n)
    # per-query value row: one masked pass per live value row (n_vals is
    # tiny — the distinct value columns of the batch plus a ones row)
    vq = jnp.zeros(fmask.shape, jnp.float32)
    for v in range(n_vals):
        vq += jnp.where(sel == v, vals[v : v + 1, :], 0.0)

    part_sum = jnp.sum(vq * fmask, axis=1, keepdims=True)  # (Q_pad, 1)
    part_cnt = jnp.sum(fmask, axis=1, keepdims=True)
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    upd = jnp.where(lane_idx == 0, part_sum, 0.0) + jnp.where(
        lane_idx == 1, part_cnt, 0.0
    )
    out_ref[...] = out_ref[...] + upd


def _pad_to(x: jax.Array, size: int, axis: int, fill) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


# VMEM budget of the row-streaming kernels: each grid step holds several
# (Q_pad, block_n) predicate/mask intermediates, so queries per launch
# scale inversely with the row block. Compiled for TPU v5e at 8 key
# lanes, the fused locate+scan kernel fits 128 queries at block_n = 8192
# and runs out of VMEM at 256; Q_pad * block_n <= 2**20 keeps every
# row-streaming kernel inside that bound.
QUERY_BLOCK_ELEMS = 1 << 20


def query_chunk(block_n: int, max_q: int | None = None) -> int:
    """Most queries one launch of a ``(Q_pad, block_n)`` row-streaming
    kernel may carry: the VMEM budget, optionally lowered by ``max_q``
    (a multiple of 8, at least 8)."""
    cap = max(8, QUERY_BLOCK_ELEMS // block_n // 8 * 8)
    if max_q is not None:
        cap = max(8, min(cap, -(-max_q // 8) * 8))
    return cap


def query_bucket(n: int, max_q: int) -> int:
    """Queries a launch of ``n`` is padded to: a power of two, at least
    8, at most ``max_q``."""
    return min(max_q, max(8, 1 << max(n - 1, 0).bit_length()))


def launch_query_chunks(call, operands, *, max_q: int) -> tuple[np.ndarray, ...]:
    """Run ``call`` over the query axis (axis 0 of every operand) in
    launches of at most ``max_q`` queries. Each launch is zero-padded on
    the host to a power-of-two query count (at least 8), so a cold run
    compiles a handful of programs, not one per group size; zero rows
    carry an empty ``(0, 0)`` row window and count nothing. Returns the
    outputs of ``call`` (a tuple of arrays with a leading query axis)
    sliced back and concatenated on the host."""
    operands = [np.asarray(x) for x in operands]
    n_q = operands[0].shape[0]
    parts = []
    for s in range(0, max(n_q, 1), max_q):
        n = min(max_q, n_q - s)
        bucket = query_bucket(n, max_q)
        chunk = [
            np.pad(x[s : s + n], [(0, bucket - n)] + [(0, 0)] * (x.ndim - 1))
            for x in operands
        ]
        parts.append([np.asarray(o)[:n] for o in call(*chunk)])
    return tuple(np.concatenate(outs, axis=0) for outs in zip(*parts))


@functools.partial(
    jax.jit, static_argnames=("col_parts", "n_vals", "block_n", "interpret")
)
def _rowstream_call(
    keys: jax.Array,  # int32[K_ex(+pad), N] — key lanes, replica order
    values: jax.Array,  # float32[V(+pad), N] — value rows (ones row for counts)
    col_lo: jax.Array,  # int32[Q, K_ex] inclusive per-query/lane bounds
    col_hi: jax.Array,  # int32[Q, K_ex] exclusive per-query/lane bounds
    slabs: jax.Array,  # int32[Q, 2] — per-query [lo, hi) row slabs
    value_sel: jax.Array,  # int32[Q] — per-query value-row index
    *,
    col_parts: tuple[int, ...],
    n_vals: int,  # live value rows (the selector's range)
    block_n: int,
    interpret: bool,
) -> jax.Array:
    N = keys.shape[1]
    Q = col_lo.shape[0]
    K_pad = max(8, -(-keys.shape[0] // 8) * 8)
    V_pad = max(8, -(-values.shape[0] // 8) * 8)
    Q_pad = max(8, -(-Q // 8) * 8)
    N_pad = -(-max(N, 1) // block_n) * block_n

    # for device-resident tables these pads are no-ops: build_device_state
    # pre-pads keys/values to the same granularity, so the N-sized arrays
    # pass through untouched and only the O(Q) operands are prepared here
    keys_p = _pad_to(_pad_to(keys.astype(jnp.int32), N_pad, 1, 0), K_pad, 0, 0)
    vals_p = _pad_to(_pad_to(values.astype(jnp.float32), N_pad, 1, 0.0), V_pad, 0, 0.0)
    # padded key lanes are never referenced (col_parts covers only the
    # real lanes); padded queries get empty slabs and all-zero bounds
    lo_p = _pad_to(_pad_to(col_lo.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    hi_p = _pad_to(_pad_to(col_hi.astype(jnp.int32), K_pad, 1, 0), Q_pad, 0, 0)
    slabs_p = _pad_to(slabs.astype(jnp.int32), Q_pad, 0, 0)
    sel_p = _pad_to(value_sel.astype(jnp.int32)[:, None], Q_pad, 0, 0)

    grid = (N_pad // block_n,)
    kernel = functools.partial(scan_agg_rowstream_kernel, col_parts, n_vals)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q_pad, 2), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((K_pad, block_n), lambda i: (0, i)),
            pl.BlockSpec((V_pad, block_n), lambda i: (0, i)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
            pl.BlockSpec((Q_pad, K_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Q_pad, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Q_pad, 128), jnp.float32),
        interpret=interpret,
    )(slabs_p, sel_p, keys_p, vals_p, lo_p, hi_p)
    return out[:Q, :2]


def scan_agg_batched_pallas(
    keys: jax.Array,  # int32[K_ex, N]
    values: jax.Array,  # float32[N] or float32[V, N]
    col_lo: jax.Array,  # int32[Q, K_ex]
    col_hi: jax.Array,  # int32[Q, K_ex]
    slabs: jax.Array,  # int32[Q, 2]
    value_sel: jax.Array | None = None,  # int32[Q], default all zeros
    *,
    col_parts: tuple[int, ...] | None = None,
    n_vals: int | None = None,
    block_n: int = 2048,
    max_q: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """Returns float32[Q, 2]: per query, (masked sum of values, count).

    One row-streaming launch serves the whole batch (see module
    docstring); batches larger than :func:`query_chunk` (lowered by
    ``max_q``) are chunked so the per-step blocks stay within VMEM —
    each chunk still streams the columns exactly once. ``keys``/``values``
    may carry pre-padded sublane rows beyond the ``col_parts`` lanes /
    ``n_vals`` live value rows (the device-resident layout); padded rows
    are never referenced.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    values = jnp.asarray(values, jnp.float32)
    if values.ndim == 1:
        values = values[None, :]
    keys = jnp.asarray(keys, jnp.int32)
    col_lo = np.asarray(col_lo, np.int32)
    col_hi = np.asarray(col_hi, np.int32)
    slabs = np.asarray(slabs, np.int32)
    Q, K_ex = col_lo.shape
    if value_sel is None:
        value_sel = np.zeros(Q, np.int32)
    else:
        value_sel = np.asarray(value_sel, np.int32)
    if col_parts is None:
        col_parts = (1,) * K_ex
    col_parts = tuple(int(p) for p in col_parts)
    if sum(col_parts) != K_ex or not all(p in (1, 2) for p in col_parts):
        raise ValueError(f"col_parts {col_parts} does not tile {K_ex} bound lanes")
    if K_ex > keys.shape[0]:
        raise ValueError(
            f"bounds cover {K_ex} lanes but keys carry {keys.shape[0]}"
        )
    if n_vals is None:
        n_vals = int(values.shape[0])
    if not 0 < n_vals <= values.shape[0]:
        raise ValueError(f"n_vals {n_vals} out of range for {values.shape[0]} rows")
    (out,) = launch_query_chunks(
        lambda *ops: (
            _rowstream_call(
                keys, values, *ops, col_parts=col_parts, n_vals=n_vals,
                block_n=block_n, interpret=interpret,
            ),
        ),
        (col_lo, col_hi, slabs, value_sel),
        max_q=query_chunk(block_n, max_q),
    )
    return out


# -- legacy queries-outer grid (kept for the perf trajectory bench) ----------


def scan_agg_qgrid_kernel(slabs_ref, keys_ref, vals_ref, lo_ref, hi_ref, out_ref):
    """One (query, row block) grid step. A query's (1, 128) output block
    stays resident across its row blocks (row axis iterates fastest).
    Bounds arrive pre-transposed as (K_pad, Q) so the per-query column is
    a (K_pad, 1) slice that broadcasts against the keys tile."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]  # (K_pad, block_n) int32
    vals = vals_ref[...]  # (1, block_n) float32
    lo = lo_ref[...]  # (K_pad, 1) int32, inclusive — this query's column
    hi = hi_ref[...]  # (K_pad, 1) int32, exclusive

    block_n = keys.shape[1]
    row0 = i * block_n
    ridx = row0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    slab_lo = slabs_ref[0, 0]
    slab_hi = slabs_ref[0, 1]
    in_slab = (ridx >= slab_lo) & (ridx < slab_hi)  # (1, block_n)

    col_ok = (keys >= lo) & (keys < hi)  # (K_pad, block_n)
    pred = jnp.all(col_ok, axis=0, keepdims=True) & in_slab  # (1, block_n)

    fmask = pred.astype(vals.dtype)
    part_sum = jnp.sum(vals * fmask)
    part_cnt = jnp.sum(fmask)

    acc = out_ref[...]
    upd = jnp.zeros_like(acc)
    upd = upd.at[0, 0].set(part_sum)
    upd = upd.at[0, 1].set(part_cnt)
    out_ref[...] = acc + upd


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def scan_agg_batched_qgrid_pallas(
    keys: jax.Array,  # int32[K, N] — columnar clustering keys, replica order
    values: jax.Array,  # float32[N]
    col_lo: jax.Array,  # int32[Q, K] inclusive per-query/column lower bounds
    col_hi: jax.Array,  # int32[Q, K] exclusive per-query/column upper bounds
    slabs: jax.Array,  # int32[Q, 2] — per-query [lo, hi) row slabs
    *,
    block_n: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """The PR 1 grid: (queries, row blocks), row axis fastest. Each
    query's output block stays resident while it scans, but key tiles
    are re-fetched per query — HBM key traffic scales with Q. Superseded
    by the row-streaming grid; kept as the benchmark baseline."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    K, N = keys.shape
    Q = col_lo.shape[0]
    K_pad = max(8, -(-K // 8) * 8)
    N_pad = -(-max(N, 1) // block_n) * block_n

    keys_p = _pad_to(_pad_to(keys.astype(jnp.int32), N_pad, 1, 0), K_pad, 0, 0)
    vals_p = _pad_to(values.astype(jnp.float32)[None, :], N_pad, 1, 0.0)
    # transpose bounds to (K_pad, Q): per-query column slices broadcast
    # against the keys tile. Padded K rows get always-true bounds; padded
    # N rows are killed by the slab mask (row index ≥ N ≥ slab hi).
    lo_p = _pad_to(col_lo.astype(jnp.int32).T, K_pad, 0, jnp.iinfo(jnp.int32).min)
    hi_p = _pad_to(col_hi.astype(jnp.int32).T, K_pad, 0, jnp.iinfo(jnp.int32).max)
    slabs_p = slabs.astype(jnp.int32)

    grid = (Q, N_pad // block_n)
    out = pl.pallas_call(
        scan_agg_qgrid_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda q, i: (q, 0)),
            pl.BlockSpec((K_pad, block_n), lambda q, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda q, i: (0, i)),
            pl.BlockSpec((K_pad, 1), lambda q, i: (0, q)),
            pl.BlockSpec((K_pad, 1), lambda q, i: (0, q)),
        ],
        out_specs=pl.BlockSpec((1, 128), lambda q, i: (q, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, 128), jnp.float32),
        interpret=interpret,
    )(slabs_p, keys_p, vals_p, lo_p, hi_p)
    return out[:, :2]


def scan_agg_pallas(
    keys: jax.Array,  # int32[K, N]
    values: jax.Array,  # float32[N]
    col_lo: jax.Array,  # int32[K] inclusive per-column lower bounds
    col_hi: jax.Array,  # int32[K] exclusive per-column upper bounds
    slab: jax.Array,  # int32[2] = [lo, hi) row slab
    *,
    block_n: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns float32[2] = (masked sum of values, matched row count).

    The Q = 1 case of :func:`scan_agg_batched_pallas`.
    """
    col_lo = jnp.asarray(col_lo)
    col_hi = jnp.asarray(col_hi)
    slab = jnp.asarray(slab)
    out = scan_agg_batched_pallas(
        keys, values, col_lo[None, :], col_hi[None, :], slab[None, :],
        block_n=block_n, interpret=interpret,
    )
    return out[0]
