"""jit'd wrapper around the impact-scatter Pallas kernel.

Handles the doc-sort + canonical per-document fold (which make the kernel's
one-hot exact and let each tile visit only the blocks it spans), padding,
and interpret-mode selection so the same call site works on CPU tests and
TPU deployments.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import KernelContract, ShapeCase
from repro.kernels.common import interpret_default, round_up, sorted_posting_tiles
from repro.kernels.impact_scatter.kernel import impact_scatter_batched_kernel


@partial(
    jax.jit,
    static_argnames=("n_docs", "block_d", "tile_p", "max_run", "interpret"),
)
def impact_scatter_batched(
    doc_ids: jax.Array,
    contribs: jax.Array,
    n_docs: int,
    *,
    block_d: int = 512,
    tile_p: int = 512,
    max_run: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """acc[b, d] = sum of contribs[b] with doc_ids[b] == d, natively batched.

    The whole batch runs as ONE kernel launch with a grid axis over queries —
    the batched SAAT engine's hot loop. Each document's contributions are
    summed in the canonical order of
    :func:`repro.kernels.common.canonical_doc_sums` (stable doc sort, then a
    segmented doubling scan); ``max_run`` bounds how many postings one
    document can have in a row (the engines pass the query width; ``None``
    assumes the whole row).
    """
    if interpret is None:
        interpret = interpret_default()
    n_docs_pad = round_up(max(n_docs, block_d), block_d)
    docs, c, _ = sorted_posting_tiles(doc_ids, contribs, tile_p, max_run)
    acc = impact_scatter_batched_kernel(
        docs, c, n_docs=n_docs_pad, block_d=block_d, tile_p=tile_p,
        interpret=interpret,
    )
    return acc[:, :n_docs]


@partial(
    jax.jit,
    static_argnames=("n_docs", "block_d", "tile_p", "max_run", "interpret"),
)
def impact_scatter(
    doc_ids: jax.Array,
    contribs: jax.Array,
    n_docs: int,
    *,
    block_d: int = 512,
    tile_p: int = 512,
    max_run: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """acc[d] = sum of contribs with doc_id == d: the batched op at B = 1."""
    return impact_scatter_batched(
        doc_ids[None], contribs[None], n_docs, block_d=block_d, tile_p=tile_p,
        max_run=max_run, interpret=interpret,
    )[0]


def _contract_call(dims):
    """Trace target for the static checker: abstract inputs, sweep tiling."""
    sds = jax.ShapeDtypeStruct
    kw = dict(
        n_docs=dims["n_docs"], block_d=dims["block_d"], tile_p=dims["tile_p"],
        interpret=True,
    )
    if "batch" in dims:
        shape = (dims["batch"], dims["n_postings"])
        return partial(impact_scatter_batched, **kw), (
            sds(shape, jnp.int32), sds(shape, jnp.float32))
    shape = (dims["n_postings"],)
    return partial(impact_scatter, **kw), (sds(shape, jnp.int32), sds(shape, jnp.float32))


# The single source of truth for the interpret-mode sweep shapes in
# tests/test_kernels.py AND the static checker's trace grid: ragged
# (non-divisible pre-pad) posting/doc counts included on purpose.
CONTRACT = KernelContract(
    name="impact_scatter",
    description="batch-gridded scatter-add accumulator (SAAT hot loop)",
    make_call=_contract_call,
    shape_grid=(
        ShapeCase("single_tile", dict(n_postings=128, n_docs=512, block_d=256, tile_p=128)),
        ShapeCase("ragged", dict(n_postings=1000, n_docs=1000, block_d=256, tile_p=128)),
        ShapeCase("multi_tile", dict(n_postings=4096, n_docs=512, block_d=256, tile_p=128)),
        ShapeCase("b1", dict(batch=1, n_postings=128, n_docs=700, block_d=256, tile_p=128)),
        ShapeCase("b3_ragged", dict(batch=3, n_postings=1000, n_docs=700, block_d=256, tile_p=128)),
        ShapeCase("b8", dict(batch=8, n_postings=1000, n_docs=700, block_d=256, tile_p=128)),
    ),
)
