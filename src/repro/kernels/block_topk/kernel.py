"""Pallas TPU kernel: tiled top-k over a large score vector.

The JASS min-heap has no TPU analogue; the idiomatic replacement for top-k
over millions of accumulators is a two-stage select: per-tile top-k entirely
in VMEM, then a small XLA merge over ``num_tiles * k`` finalists. This
kernel is stage 1; the ``ops`` wrapper fuses stage 2 with ``lax.top_k`` over
the finalists.

Each grid cell holds 8 query rows of one score tile (an f32 sublane tile)
and runs the row-wise repeated-max selection of
:func:`repro.kernels.impact_scatter_topk.kernel.select_topk_rows` — Mosaic
has no ``top_k`` lowering — in ``lax.top_k``'s order (value descending,
lowest index first among ties). Each tile is read from HBM exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.impact_scatter_topk.kernel import ROWS, select_topk_rows


def _topk_kernel_batched(scores_ref, out_s_ref, out_i_ref, *, k: int):
    i = pl.program_id(1)
    tile = scores_ref[...]  # f32[ROWS, T] — ROWS queries, one tile
    t = tile.shape[1]
    gid = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + i * t
    s, idx = select_topk_rows(tile, gid, k)
    out_s_ref[...] = s
    out_i_ref[...] = idx


def block_topk_batched_kernel(
    scores: jax.Array,  # f32[B, n], B % 8 == 0, n % tile == 0
    *,
    k: int,
    tile: int = 8192,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Stage-1 select for a whole query batch: grid over (row group, tile).

    Returns ``(scores, ids)`` finalists of shape ``[n_tiles, B, k]``.
    """
    b, n = scores.shape
    assert n % tile == 0 and k <= tile and b % ROWS == 0, (b, n, tile, k)
    n_tiles = n // tile
    out = pl.BlockSpec((None, ROWS, k), lambda q, i: (i, q, 0))
    s, i = pl.pallas_call(
        functools.partial(_topk_kernel_batched, k=k),
        grid=(b // ROWS, n_tiles),
        in_specs=[pl.BlockSpec((ROWS, tile), lambda q, i: (q, i))],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, b, k), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, b, k), jnp.int32),
        ],
        interpret=interpret,
    )(scores)
    return s, i
