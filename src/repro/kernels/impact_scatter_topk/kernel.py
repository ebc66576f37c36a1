"""Pallas TPU kernel: fused SAAT scatter-add → per-block top-k selection.

The unfused SAAT pipeline writes the full ``[B, n_docs]`` accumulator to HBM
(``impact_scatter_batched``) and immediately reads it back for top-k — twice
the accumulator's worth of HBM traffic for a result that is only ``k`` entries
wide. This kernel fuses the selection into the scatter: the query's
accumulator lives in VMEM scratch, is filled tile by tile exactly as in
``impact_scatter``, and after the LAST tile the kernel selects each
accumulator block's ``k`` best candidates (ids globalized to document space,
scores f32). What crosses the HBM boundary is the candidate pool
``[B, n_blocks, k]`` — never the accumulator.

Selection is a repeated row-wise max with a lowest-index argmax (Mosaic has
no ``top_k`` lowering): ``k`` passes over 8 accumulator blocks at a time,
each taking the largest untaken value and, among equal values, the lowest
doc — exactly ``lax.top_k``'s order, ``-inf`` ties included. When ``k``
equals the block width the block is emitted whole, in doc order.

Rank safety of the two-stage select: a block of ``block_d`` documents can
contribute at most ``min(k, block_d)`` entries to the global top-k, so keeping
``min(k, block_d)`` candidates per block loses nothing; the caller's merge
pass over the pool (``repro.core.topk.tiled_topk``) recovers the exact global
top-k, bit-identical in ids to ``lax.top_k`` over the dense accumulator
(ties resolve block-major → ascending doc id, the same order).

Padded documents (``gid >= n_live``) are masked to ``-inf`` *inside* the
kernel, before selection, so the candidate pool replicates the unfused
engine's ``_mask_pad_docs`` + ``topk`` semantics. The index lifecycle's
tombstone bitmap rides the same gate: an optional ``[n_blocks, block_d]``
i32 live input (nonzero = live, i32 because Mosaic has no bool VMEM tiles)
is ANDed into the pad mask at selection time, so deleted documents score
``-inf`` without touching the accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.impact_scatter.kernel import accumulate_tile

ROWS = 8  # accumulator blocks selected per pass: one f32 sublane tile


def select_topk_rows(x: jax.Array, ids: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Row-wise top-k of ``x [R, W]`` in ``lax.top_k`` order, built for Mosaic.

    Each of ``k`` passes takes the largest untaken value per row and, among
    equal values, the lowest column (so ``-inf`` entries come out in column
    order once the finite ones are exhausted). Returns ``(values [R, k],
    ids[row, column] [R, k])``. Requires ``k <= W``.
    """
    R, W = x.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)

    def one(i, carry):
        taken, vals, out_ids = carry
        avail = taken == 0
        m = jnp.max(jnp.where(avail, x, -jnp.inf), axis=1, keepdims=True)
        pos = jnp.min(jnp.where(avail & (x == m), col, W), axis=1, keepdims=True)
        sel = col == pos
        picked = jnp.sum(jnp.where(sel, ids, 0), axis=1, keepdims=True)
        taken = jnp.where(sel, 1, taken)
        vals = jnp.where(kcol == i, m, vals)
        out_ids = jnp.where(kcol == i, picked, out_ids)
        return taken, vals, out_ids

    init = (
        jnp.zeros((R, W), jnp.int32),
        jnp.full((R, k), -jnp.inf, jnp.float32),
        jnp.zeros((R, k), jnp.int32),
    )
    _, vals, out_ids = jax.lax.fori_loop(0, k, one, init)
    return vals, out_ids


def _scatter_topk_kernel(
    docs_ref,
    vals_ref,
    *rest,
    block_d: int,
    n_tiles: int,
    n_live: int,
    has_live: bool = False,
):
    # `rest` unpacks to (live_ref?, out_s_ref, out_i_ref, acc_ref): the live
    # bitmap is an optional trailing input, so the no-mask launch traces the
    # exact same kernel it always has.
    if has_live:
        live_ref, out_s_ref, out_i_ref, acc_ref = rest
    else:
        live_ref = None
        out_s_ref, out_i_ref, acc_ref = rest
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate_tile(docs_ref[...], vals_ref[...], acc_ref, block_d=block_d)

    @pl.when(t == n_tiles - 1)
    def _select():
        n_blocks, k = out_s_ref.shape

        def rows(g, carry):
            r0 = pl.multiple_of(g * ROWS, ROWS)
            sl = pl.ds(r0, ROWS)
            gid = (
                jax.lax.broadcasted_iota(jnp.int32, (ROWS, block_d), 0) + r0
            ) * block_d + jax.lax.broadcasted_iota(jnp.int32, (ROWS, block_d), 1)
            mask = gid < n_live
            if live_ref is not None:
                mask = mask & (live_ref[sl, :] != 0)
            scores = jnp.where(mask, acc_ref[sl, :], -jnp.inf)
            if k == block_d:  # the whole block survives: emit it in doc order
                s, i = scores, gid
            else:
                s, i = select_topk_rows(scores, gid, k)
            out_s_ref[sl, :] = s
            out_i_ref[sl, :] = i
            return carry

        jax.lax.fori_loop(0, n_blocks // ROWS, rows, 0)


def impact_scatter_topk_batched_kernel(
    doc_ids: jax.Array,
    vals: jax.Array,
    *,
    n_docs: int,
    n_live: int,
    k: int,
    block_d: int = 512,
    tile_p: int = 512,
    live: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Batched fused scatter → per-block top-k: grid over (query, tile).

    Args:
      doc_ids: i32[B, P] sorted per row, P % tile_p == 0, values in [0, n_docs).
      vals: f32[B, P], at most one nonzero per (row, doc).
      n_docs: accumulator length; must be % (8 * block_d) == 0.
      n_live: real document count; ids >= n_live are masked to -inf.
      k: candidates kept per accumulator block; must be <= block_d.
      live: optional i32[n_docs] tombstone bitmap shared by the whole batch.

    Returns:
      (cand_scores f32[B, n_blocks, k], cand_ids i32[B, n_blocks, k]) — the
      only arrays that leave VMEM; the accumulator never reaches HBM.
    """
    B, P = doc_ids.shape
    assert P % tile_p == 0, (P, tile_p)
    assert n_docs % (ROWS * block_d) == 0, (n_docs, block_d)
    assert 0 < k <= block_d, (k, block_d)
    n_tiles = P // tile_p
    n_blocks = n_docs // block_d

    tile = pl.BlockSpec((None, None, 1, tile_p), lambda b, t: (b, t, 0, 0))
    in_specs = [tile, tile]
    inputs = [
        doc_ids.reshape(B, n_tiles, 1, tile_p),
        vals.astype(jnp.float32).reshape(B, n_tiles, 1, tile_p),
    ]
    if live is not None:
        assert live.shape == (n_docs,), (live.shape, n_docs)
        in_specs.append(pl.BlockSpec((n_blocks, block_d), lambda b, t: (0, 0)))
        inputs.append(live.astype(jnp.int32).reshape(n_blocks, block_d))

    cand = pl.BlockSpec((None, n_blocks, k), lambda b, t: (b, 0, 0))
    out_s, out_i = pl.pallas_call(
        functools.partial(
            _scatter_topk_kernel, block_d=block_d, n_tiles=n_tiles,
            n_live=n_live, has_live=live is not None,
        ),
        grid=(B, n_tiles),
        in_specs=in_specs,
        out_specs=[cand, cand],
        out_shape=[
            jax.ShapeDtypeStruct((B, n_blocks, k), jnp.float32),
            jax.ShapeDtypeStruct((B, n_blocks, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((n_blocks, block_d), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    return out_s, out_i
