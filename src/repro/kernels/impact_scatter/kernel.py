"""Pallas TPU kernel: SAAT accumulator scatter-add as a VMEM one-hot.

TPUs have no fast random scatter; the idiomatic translation is a *one-hot
contraction*: for a VMEM tile of postings ``(doc_ids[TP], vals[TP])`` and an
accumulator block of ``BD`` documents, the partial update is

    acc[blk, :] += sum_t where(iota[BD, TP] == doc_ids - blk * BD, vals, 0)

The grid is (query, posting tile); the query's whole ``[n_blocks, BD]``
accumulator stays resident in VMEM across the tile axis (output revisiting),
so HBM traffic is one read of the postings plus one write of the
accumulator. The ops wrapper sorts postings by doc id, so a tile only spans
the blocks between its smallest and largest doc — the kernel loops over
exactly those blocks (``fori_loop`` with data-dependent bounds) instead of
visiting every (block, tile) pair.

Exactness: the wrapper has already folded each document's postings into ONE
nonzero value (:func:`repro.kernels.common.canonical_doc_sums`), so every
accumulator cell receives at most one nonzero addend — the select-and-sum is
exact in any order, and the accumulator is bit-identical to XLA's scatter of
the same values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def accumulate_tile(docs, vals, acc_ref, *, block_d: int) -> None:
    """Add one doc-sorted posting tile ``(1, TP)`` into ``acc_ref [nb, BD]``."""
    tp = docs.shape[1]
    lo = jnp.min(docs) // block_d
    hi = jnp.max(docs) // block_d

    def one_block(blk, carry):
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_d, tp), 0) + blk * block_d
        part = jnp.sum(jnp.where(rows == docs, vals, 0.0), axis=1)  # f32[BD]
        acc_ref[pl.ds(blk, 1), :] += part[None, :]
        return carry

    jax.lax.fori_loop(lo, hi + 1, one_block, 0)


def _scatter_kernel(docs_ref, vals_ref, acc_ref, *, block_d: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate_tile(docs_ref[...], vals_ref[...], acc_ref, block_d=block_d)


def impact_scatter_batched_kernel(
    doc_ids: jax.Array,
    vals: jax.Array,
    *,
    n_docs: int,
    block_d: int = 512,
    tile_p: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Batched scatter-add: grid over (query, posting tile).

    Args:
      doc_ids: i32[B, P] sorted per row, P % tile_p == 0, values in [0, n_docs).
      vals: f32[B, P], at most one nonzero per (row, doc).
      n_docs: accumulator length; must be % block_d == 0.

    Returns:
      f32[B, n_docs] accumulators.
    """
    B, P = doc_ids.shape
    assert P % tile_p == 0, (P, tile_p)
    assert n_docs % block_d == 0, (n_docs, block_d)
    n_tiles = P // tile_p
    n_blocks = n_docs // block_d
    # a unit axis makes each (query, tile) block's last two dims equal the
    # array's, which Mosaic accepts for any tile width
    tile = pl.BlockSpec((None, None, 1, tile_p), lambda b, t: (b, t, 0, 0))
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, block_d=block_d),
        grid=(B, n_tiles),
        in_specs=[tile, tile],
        out_specs=pl.BlockSpec((None, n_blocks, block_d), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_blocks, block_d), jnp.float32),
        interpret=interpret,
    )(
        doc_ids.reshape(B, n_tiles, 1, tile_p),
        vals.astype(jnp.float32).reshape(B, n_tiles, 1, tile_p),
    )
    return out.reshape(B, n_docs)
