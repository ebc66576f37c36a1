"""jit'd wrappers around the fused scatter→top-k Pallas kernel.

The full fused SAAT selection is kernel + merge: the kernel emits per-block
candidate pools ``[B, n_blocks * k]`` (the only arrays that touch HBM), the
merge pass (``repro.core.topk.tiled_topk`` over the pool) recovers the exact
global top-k. Results are bit-identical in doc ids — including ``-inf`` tie
order — to ``top_k`` over the dense ``impact_scatter`` accumulator, and
bit-identical in scores (both accumulate the same canonical per-document
sums, :func:`repro.kernels.common.canonical_doc_sums`).

Like ``impact_scatter``'s wrappers: the doc-sort and fold, padding, and
interpret-mode selection are handled here so one call site serves CPU tests
and TPU deployments.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import KernelContract, ShapeCase
from repro.core.topk import tiled_topk
from repro.kernels.common import (
    interpret_default,
    pad_axis,
    round_up,
    sorted_posting_tiles,
)
from repro.kernels.impact_scatter_topk.kernel import (
    ROWS,
    impact_scatter_topk_batched_kernel,
)


def _merge_pool(
    cand_s: jax.Array, cand_i: jax.Array, k_out: int
) -> tuple[jax.Array, jax.Array]:
    """Exact global top-k over per-block candidate pools ``[..., nb, kb]``.

    ``tiled_topk`` with one tile per block is rank-safe here by construction
    (each tile IS a block's full candidate set), and its flat positional ids
    map back through ``cand_i`` to document ids.
    """
    nb, kb = cand_s.shape[-2:]
    flat_s = cand_s.reshape(cand_s.shape[:-2] + (nb * kb,))
    flat_i = cand_i.reshape(cand_i.shape[:-2] + (nb * kb,))
    ms, mpos = tiled_topk(flat_s, k_out, num_tiles=nb)
    return ms, jnp.take_along_axis(flat_i, mpos, axis=-1)


@partial(
    jax.jit,
    static_argnames=("n_docs", "k", "n_live", "block_d", "tile_p", "max_run", "interpret"),
)
def impact_scatter_topk_batched(
    doc_ids: jax.Array,
    contribs: jax.Array,
    n_docs: int,
    k: int,
    *,
    n_live: int | None = None,
    live: jax.Array | None = None,
    block_d: int = 512,
    tile_p: int = 512,
    max_run: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched fused top-k: the batched SAAT engine's ``fused_topk`` hot path.

    Equivalent to ``top_k(mask(impact_scatter(doc_ids, contribs, n_docs)), k)``
    with ids >= ``n_live`` masked to ``-inf`` — but the dense accumulator
    never leaves VMEM. One kernel launch grids over (query, tile). Returns
    ``([B, min(k, n_docs)]`` score/id pairs. ``live`` is the optional
    tombstone bitmap (i32/bool, length <= the padded accumulator; nonzero =
    live) shared by the whole batch, ANDed into the pad mask at in-kernel
    selection time. ``max_run`` is as in ``impact_scatter_batched``.
    """
    if interpret is None:
        interpret = interpret_default()
    if n_live is None:
        n_live = n_docs
    n_docs_pad = round_up(max(n_docs, block_d), ROWS * block_d)
    k_out = min(k, n_docs)
    k_blk = min(k_out, block_d)  # a block holds at most block_d of the top-k
    docs, c, _ = sorted_posting_tiles(doc_ids, contribs, tile_p, max_run)
    if live is not None:
        live = pad_axis(live.astype(jnp.int32), 0, n_docs_pad)[:n_docs_pad]
    cand_s, cand_i = impact_scatter_topk_batched_kernel(
        docs,
        c,
        n_docs=n_docs_pad,
        n_live=min(n_live, n_docs),
        k=k_blk,
        block_d=block_d,
        tile_p=tile_p,
        live=live,
        interpret=interpret,
    )
    return _merge_pool(cand_s, cand_i, k_out)


@partial(
    jax.jit,
    static_argnames=("n_docs", "k", "n_live", "block_d", "tile_p", "max_run", "interpret"),
)
def impact_scatter_topk(
    doc_ids: jax.Array,
    contribs: jax.Array,
    n_docs: int,
    k: int,
    *,
    n_live: int | None = None,
    live: jax.Array | None = None,
    block_d: int = 512,
    tile_p: int = 512,
    max_run: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused top-k of the scatter accumulator for one query (B = 1)."""
    s, i = impact_scatter_topk_batched(
        doc_ids[None], contribs[None], n_docs, k, n_live=n_live, live=live,
        block_d=block_d, tile_p=tile_p, max_run=max_run, interpret=interpret,
    )
    return s[0], i[0]


def _contract_call(dims):
    """Trace target for the static checker: abstract inputs, sweep tiling."""
    sds = jax.ShapeDtypeStruct
    kw = dict(
        n_docs=dims["n_docs"], k=dims["k"], block_d=dims["block_d"],
        tile_p=dims["tile_p"], interpret=True,
    )
    live_sds = sds((dims["n_docs"],), jnp.int32) if dims.get("live") else None
    if "batch" in dims:
        shape = (dims["batch"], dims["n_postings"])
        qargs = (sds(shape, jnp.int32), sds(shape, jnp.float32))
        if live_sds is not None:
            fn = lambda d, c, l: impact_scatter_topk_batched(d, c, live=l, **kw)
            return fn, qargs + (live_sds,)
        return partial(impact_scatter_topk_batched, **kw), qargs
    shape = (dims["n_postings"],)
    qargs = (sds(shape, jnp.int32), sds(shape, jnp.float32))
    if live_sds is not None:
        fn = lambda d, c, l: impact_scatter_topk(d, c, live=l, **kw)
        return fn, qargs + (live_sds,)
    return partial(impact_scatter_topk, **kw), qargs


# Single source of truth for the sweep shapes in tests/test_kernels.py and
# the checker's trace grid: k from 1 to beyond block_d, ragged doc counts,
# and the tombstone-bitmap (live-masked) variants of both layouts.
CONTRACT = KernelContract(
    name="impact_scatter_topk",
    description="fused scatter -> per-block top-k candidate pool (SAAT fused_topk)",
    make_call=_contract_call,
    shape_grid=(
        ShapeCase("k1", dict(n_postings=128, n_docs=512, k=1, block_d=256, tile_p=128)),
        ShapeCase("k10_ragged", dict(n_postings=1000, n_docs=1000, k=10, block_d=256, tile_p=128)),
        ShapeCase("k300", dict(n_postings=4096, n_docs=512, k=300, block_d=256, tile_p=128)),
        ShapeCase("live_ragged", dict(n_postings=1000, n_docs=1000, k=10, block_d=256, tile_p=128, live=1)),
        ShapeCase("b1", dict(batch=1, n_postings=1000, n_docs=700, k=13, block_d=256, tile_p=128)),
        ShapeCase("b3_ragged", dict(batch=3, n_postings=1000, n_docs=700, k=13, block_d=256, tile_p=128)),
        ShapeCase("b8", dict(batch=8, n_postings=1000, n_docs=700, k=13, block_d=256, tile_p=128)),
        ShapeCase("b3_live", dict(batch=3, n_postings=1000, n_docs=700, k=13, block_d=256, tile_p=128, live=1)),
    ),
)
