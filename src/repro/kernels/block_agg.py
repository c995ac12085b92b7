"""Pallas per-block partial aggregates — the device half of the
materialized per-slab views (``repro.core.storage.views``).

``block_sums`` folds a replica's resident value tile into one float32
partial sum per ``block_n`` row block, in that replica's own sort
order. The view serve path then answers a range aggregate as
interior-blocks-from-partials plus boundary-block rescans instead of
an O(N) stream — O(blocks touched) work.

Bit-identity contract
=====================

The fused full-scan kernel (``scan_agg_locate_kernel``) accumulates
per row block: ``part = jnp.sum(vq * fmask, axis=1)`` over a
``(·, block_n)`` tile, added into the float32 output lane in ascending
block order. The view path must reproduce those bits exactly, so every
reduction here is the *same shape family* — a minor-axis ``jnp.sum``
over a ``(rows-padded-to-8, block_n)`` tile:

* an **interior** block (every real row inside the query's row-window
  union) contributes its stored ``block_sums`` column — elementwise
  the tile values times an all-ones mask, bitwise the fused product
  (value pads are 0.0);
* a **boundary** block recomputes ``jnp.sum(vals * window_mask,
  axis=1)`` via :func:`boundary_block_sums` — the fused per-block
  partial restricted to one block;
* the host then folds the touched blocks' partials sequentially in
  float32, ascending block order (``np.cumsum`` — strictly
  sequential, unlike numpy's pairwise ``np.sum``). Untouched blocks
  contribute exactly 0.0 in the fused scan, and adding 0.0 is the
  float32 identity, so skipping them preserves the accumulator bits.

(The one tolerated divergence is the sign of zero: the fused kernel's
``vq`` accumulation can turn a stored ``-0.0`` into ``+0.0``. IEEE
``==`` treats them equal, which is what the bit-identity property
tests assert.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .scan_agg import _pad_to

__all__ = [
    "block_sums",
    "block_sums_kernel",
    "boundary_block_kernel",
    "boundary_block_sums",
]


def block_sums_kernel(vals_ref, out_ref):
    """One row-block step: fold this block's value tile into its output
    column. The accumulator block is revisited every step (same idiom
    as the fused scan kernel's query lanes); lane ``i`` of the output
    receives block ``i``'s partial, pads stay 0."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    part = jnp.sum(vals_ref[...], axis=1, keepdims=True)  # (V_pad, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = out_ref[...] + jnp.where(lane == i, part, 0.0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _block_sums_call(values, *, block_n, interpret):
    V, N = values.shape
    V_pad = max(8, -(-V // 8) * 8)
    N_pad = -(-max(N, 1) // block_n) * block_n
    n_blocks = N_pad // block_n
    B_pad = max(128, -(-n_blocks // 128) * 128)
    vals_p = _pad_to(_pad_to(values.astype(jnp.float32), N_pad, 1, 0.0), V_pad, 0, 0.0)
    out = pl.pallas_call(
        block_sums_kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((V_pad, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((V_pad, B_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((V_pad, B_pad), jnp.float32),
        interpret=interpret,
    )(vals_p)
    return out[:V, :n_blocks]


def block_sums(
    values: jax.Array,  # float32[V, N] value tile (device row order)
    *,
    block_n: int,
    interpret: bool | None = None,
) -> jax.Array:
    """float32[V, ceil(N / block_n)] per-block partial sums, one row
    per value row of the tile (rows past N are zero pads and contribute
    +0.0). Each column's bits equal the fused scan kernel's per-block
    partial for a query whose window covers the whole block."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _block_sums_call(
        jnp.asarray(values, jnp.float32), block_n=block_n, interpret=interpret
    )


def boundary_block_kernel(n_win, blocks_ref, vals_ref, win_ref, out_ref):
    """One (query, block) pair per grid step: the pair's row block of
    the value tile (chosen by the scalar-prefetched block index) times
    its window-union mask, reduced per value row exactly as the fused
    kernel reduces ``vq * fmask``. Lane 0 of each output row carries
    that value row's partial."""
    p = pl.program_id(0)
    block_n = vals_ref.shape[1]
    cols = blocks_ref[p] * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1
    )
    win = win_ref[0]  # (8, W_pad): sublane 0 window starts, 1 stops
    inw = jnp.zeros((1, block_n), jnp.bool_)
    for w in range(n_win):
        inw |= (cols >= win[0:1, w : w + 1]) & (cols < win[1:2, w : w + 1])
    part = jnp.sum(vals_ref[...] * inw.astype(jnp.float32), axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(lane == 0, part, 0.0)


@functools.partial(jax.jit, static_argnames=("n_win", "block_n", "interpret"))
def _boundary_call(values, sel, blocks, win, *, n_win, block_n, interpret):
    V, N = values.shape
    V_pad = max(8, -(-V // 8) * 8)
    vals_p = _pad_to(values.astype(jnp.float32), V_pad, 0, 0.0)
    P = blocks.shape[0]
    out = pl.pallas_call(
        functools.partial(boundary_block_kernel, n_win),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(P,),
            in_specs=[
                pl.BlockSpec((V_pad, block_n), lambda p, blk: (0, blk[p])),
                pl.BlockSpec((1,) + win.shape[1:], lambda p, blk: (p, 0, 0)),
            ],
            out_specs=pl.BlockSpec((V_pad, 128), lambda p, blk: (p, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((P * V_pad, 128), jnp.float32),
        interpret=interpret,
    )(blocks, vals_p, win)
    return out.reshape(P, V_pad, 128)[jnp.arange(P), sel, 0]


def boundary_block_sums(
    values: jax.Array,  # float32[V, N_cap] resident value tile
    sel,  # int[P] value-row selector per (query, block) pair
    blocks,  # int[P] block index per pair
    win_lo,  # int[P, W] window starts (global row idx, inclusive)
    win_hi,  # int[P, W] window stops (global row idx, exclusive)
    *,
    block_n: int,
    interpret: bool | None = None,
) -> np.ndarray:
    """float32[P] masked partial sums of boundary blocks: pair ``p``
    gets ``sum(values[sel[p], rows of block blocks[p] inside any
    [win_lo[p, w], win_hi[p, w]) window])`` — the fused kernel's
    per-block ``jnp.sum(vq * fmask, axis=1)`` restricted to one block,
    computed by a Pallas kernel with the same ``(rows, block_n)``
    reduction so the bits match on every backend. Empty window slots
    are encoded ``lo >= hi``; pairs are padded to a power of two (empty
    windows) so a cold run compiles few programs."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    sel = np.asarray(sel, np.int32)
    win_lo = np.asarray(win_lo, np.int32)
    win_hi = np.asarray(win_hi, np.int32)
    P, n_win = win_lo.shape
    P_pad = max(8, 1 << max(P - 1, 0).bit_length())
    W_pad = max(128, -(-n_win // 128) * 128)
    win = np.zeros((P_pad, 8, W_pad), np.int32)  # pad pairs: lo == hi == 0
    win[:P, 0, :n_win] = win_lo
    win[:P, 1, :n_win] = win_hi
    out = _boundary_call(
        jnp.asarray(values, jnp.float32),
        np.pad(sel, (0, P_pad - P)),
        np.pad(np.asarray(blocks, np.int32), (0, P_pad - P)),
        win, n_win=n_win, block_n=block_n, interpret=interpret,
    )
    return np.asarray(out)[:P]
