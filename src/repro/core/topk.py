"""Top-k utilities shared by SAAT / DAAT / exhaustive evaluation and recsys.

On TPU there is no min-heap: full ``jax.lax.top_k`` over the accumulator (or a
tiled two-stage variant for very large candidate sets — see
``repro.kernels.block_topk`` for the Pallas version) replaces the heap +
accumulator-page machinery of JASS.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)


def _order_key(x: jax.Array) -> jax.Array:
    """i32 key ordered as f32's total order, the order ``lax.top_k`` compares in."""
    i = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def topk(scores: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Top-k scores and indices (descending), equal scores in ascending index
    order, on every backend. Static k.

    ``lax.top_k`` documents that tie order, but the TPU compiler lowers a
    wide top-k (k=1000 of 262,144) to sorts that compare values only and are
    not stable, so which of several equal scores survives is left open there.
    Only the values (which no tie order changes) are taken from
    ``lax.top_k``; the positions are fixed here: every score above the k-th
    value, then the lowest-index scores equal to it, found by a second
    top-k over distinct keys, and ordered by (score desc, index asc).
    """
    n = scores.shape[-1]
    k = min(k, n)
    vals, _ = jax.lax.top_k(scores, k)
    key = _order_key(scores)
    kth = _order_key(vals[..., -1:])
    above, tied = key > kth, key == kth
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    take = above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= need))
    iota = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    # exactly k positions are taken; their keys are distinct, so any top-k
    # returns them, in ascending position order
    _, pos = jax.lax.top_k(jnp.where(take, n - iota, -1), k)
    sel = jnp.take_along_axis(key, pos, axis=-1)
    _, ids = jax.lax.sort((~sel, pos), dimension=scores.ndim - 1, num_keys=2)
    return vals, ids


def tiled_topk(scores: jax.Array, k: int, num_tiles: int) -> Tuple[jax.Array, jax.Array]:
    """Two-stage top-k: per-tile top-k then merge.

    For ``n`` candidates this reduces the sort working set from ``n`` to
    ``num_tiles * k`` — the pattern used for the recsys ``retrieval_cand``
    shape (1M candidates), for sharded document scoring, and for merging the
    fused scatter→top-k kernel's per-block candidate pools.

    Ragged inputs are handled rather than rejected: when ``n`` is not a
    multiple of ``num_tiles`` the tail tile is padded with ``NEG_INF`` (pad
    slots sort behind every real entry, including real ``-inf`` ties, because
    they sit at the highest flat positions), and ``k`` larger than the tile
    size is clamped per tile. Both cases stay rank-safe: a tile can contribute
    at most ``min(k, tile)`` entries to the global top-k, and a clamped ``k``
    keeps whole tiles. Like :func:`topk`, the output width is ``min(k, n)``.
    """
    n = scores.shape[-1]
    tile = -(-n // num_tiles)  # ceil: tail tile may be partial
    n_pad = tile * num_tiles
    if n_pad != n:
        pad = jnp.full(scores.shape[:-1] + (n_pad - n,), NEG_INF, scores.dtype)
        scores = jnp.concatenate([scores, pad], axis=-1)
    k_out = min(k, n)
    k_tile = min(k_out, tile)  # clamped k keeps whole tiles -> merge stays exact
    tiles = scores.reshape(scores.shape[:-1] + (num_tiles, tile))
    s, i = topk(tiles, k_tile)  # [..., num_tiles, k_tile]
    base = (jnp.arange(num_tiles, dtype=jnp.int32) * tile)[:, None]
    gids = i.astype(jnp.int32) + base
    flat_s = s.reshape(scores.shape[:-1] + (num_tiles * k_tile,))
    flat_i = gids.reshape(scores.shape[:-1] + (num_tiles * k_tile,))
    ms, mi = topk(flat_s, k_out)
    return ms, jnp.take_along_axis(flat_i, mi, axis=-1)


def merge_topk(
    scores_a: jax.Array,
    ids_a: jax.Array,
    scores_b: jax.Array,
    ids_b: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Merge two top-k pools (e.g. incremental DAAT chunks) into one."""
    s = jnp.concatenate([scores_a, scores_b], axis=-1)
    i = jnp.concatenate([ids_a, ids_b], axis=-1)
    ms, mi = topk(s, k)
    return ms, jnp.take_along_axis(i, mi, axis=-1)


def merge_pools_by_id(
    scores_a: jax.Array,
    ids_a: jax.Array,
    scores_b: jax.Array,
    ids_b: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Merge two candidate pools with ties canonicalized to doc-id order.

    The host-local analogue of :func:`canonical_topk_merge` — no collective,
    just two pools whose ids live in one global doc-id space. This is the
    ``IndexHandle`` merge boundary: the main index's top-k pool and the delta
    segment's top-k pool (delta-local ids already mapped to global ids) join
    here, and the result must be bit-identical to a top-k over a single
    accumulator covering both.

    Same tie argument as :func:`canonical_topk_merge`: after the stable
    id-ascending reorder, position order *is* id order, and ``lax.top_k``
    breaks equal-score ties toward the lower input position — so tied
    candidates surface in ascending-id order exactly as a dense-accumulator
    top-k would, regardless of which pool contributed them. Pad sentinels
    (``-inf`` score) lose to every finite candidate; positions holding
    ``-inf`` carry no id guarantee.

    Precondition: a live document appears in at most one pool (an updated doc
    is tombstoned in main, so its stale main entry scores ``-inf`` and loses).
    """
    s = jnp.concatenate([scores_a, scores_b], axis=-1)
    i = jnp.concatenate([ids_a, ids_b], axis=-1).astype(jnp.int32)
    order = jnp.argsort(i, axis=-1)  # jnp.argsort is stable
    s = jnp.take_along_axis(s, order, axis=-1)
    i = jnp.take_along_axis(i, order, axis=-1)
    ms, mi = topk(s, k)
    return ms, jnp.take_along_axis(i, mi, axis=-1)


def sharded_topk_merge(
    local_scores: jax.Array, local_ids: jax.Array, k: int, axis_name: str
) -> Tuple[jax.Array, jax.Array]:
    """Distributed top-k: all-gather per-shard top-k pools and re-select.

    Used inside ``shard_map`` when documents are sharded across the ``model``
    mesh axis: each chip computes top-k over its local shard (with globalized
    doc ids), then the k-sized pools — not the accumulators — cross the ICI.
    Communication = ``shards * k * 8`` bytes instead of ``n_docs * 4``.

    Ties break by *pool position* (rank-major), which is NOT the unsharded
    engines' tie order once pad sentinels enter the pool: a sentinel
    ``(NEG_INF, INT32_MAX)`` from an early rank outranks a real ``-inf``
    document from a later rank. Serve paths that promise bit-identity to the
    unsharded oracle must use :func:`canonical_topk_merge` instead.
    """
    gs = jax.lax.all_gather(local_scores, axis_name, axis=-1, tiled=True)
    gi = jax.lax.all_gather(local_ids, axis_name, axis=-1, tiled=True)
    ms, mi = topk(gs, k)
    return ms, jnp.take_along_axis(gi, mi, axis=-1)


def canonical_topk_merge(
    local_scores: jax.Array,
    local_ids: jax.Array,
    k: int,
    axis_name,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed top-k with ties canonicalized to global-doc-id order.

    The cross-shard/cross-host merge boundary: per-rank candidate pools are
    all-gathered over ``axis_name`` (a mesh axis name or a tuple of names —
    the pod case gathers over ``("pod", "model")`` at once), the pooled
    candidates are stably reordered by global doc id ascending, and the
    global top-k is re-selected with :func:`tiled_topk` (one tile per rank,
    so the sort working set stays ``ranks * k``).

    Why the reorder makes the result layout-invariant: ``lax.top_k`` breaks
    equal-score ties toward the lower input position, both per tile and in
    the tile-merge. After the id-ascending reorder, position order *is* id
    order — within a tile directly, and across tiles because each tile is a
    contiguous id range — so tied candidates surface in ascending-id order
    no matter how many ranks contributed them. That is exactly the unsharded
    engines' tie order (a top-k over the accumulator breaks ties toward the
    lower doc id), and it demotes pad sentinels (``INT32_MAX``) behind every
    real ``-inf`` document. 1 rank, 8 ranks, ragged or empty shards: one
    merged answer, bit-identical to the unsharded oracle.
    """
    gs = jax.lax.all_gather(local_scores, axis_name, axis=-1, tiled=True)
    gi = jax.lax.all_gather(local_ids, axis_name, axis=-1, tiled=True)
    order = jnp.argsort(gi, axis=-1)  # jnp.argsort is stable
    gs = jnp.take_along_axis(gs, order, axis=-1)
    gi = jnp.take_along_axis(gi, order, axis=-1)
    n_ranks = max(gs.shape[-1] // local_scores.shape[-1], 1)
    ms, mi = tiled_topk(gs, k, num_tiles=n_ranks)
    return ms, jnp.take_along_axis(gi, mi, axis=-1)
