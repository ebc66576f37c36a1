"""jit'd wrapper around the match-and-accumulate scorer kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import KernelContract, ShapeCase
from repro.kernels.common import interpret_default, next_pow2, pad_axis, round_up
from repro.kernels.sparse_score.kernel import sparse_score_batched_kernel


@partial(jax.jit, static_argnames=("block_d", "interpret"))
def sparse_score_batched(
    doc_terms: jax.Array,
    doc_weights: jax.Array,
    q_terms: jax.Array,
    q_weights: jax.Array,
    *,
    block_d: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-query scores for ``doc_terms [B, N, Tmax]`` vs queries ``[B, Lq]``.

    One (query, doc-block)-gridded launch — the DAAT phase-2 chunk scorer.
    Doc rows pad to the block multiple and the term axis to whole 128-lane
    tiles, or to a power of two when narrower (sentinel term -1, weight 0:
    both add exactly ``+0.0``); query slots with weight ``<= 0`` are zeroed.
    f32[B, N].
    """
    if interpret is None:
        interpret = interpret_default()
    n, tmax = doc_terms.shape[1:]
    dt = pad_axis(doc_terms.astype(jnp.int32), 1, block_d, fill=-1)
    dw = pad_axis(doc_weights.astype(jnp.float32), 1, block_d, fill=0.0)
    lanes = next_pow2(tmax) if tmax < 128 else round_up(tmax, 128)
    dt = pad_axis(dt, 2, lanes, fill=-1)
    dw = pad_axis(dw, 2, lanes, fill=0.0)
    qw = jnp.where(q_weights > 0, q_weights.astype(jnp.float32), 0.0)
    scores = sparse_score_batched_kernel(
        dt, dw, q_terms, qw, block_d=block_d, interpret=interpret
    )
    return scores[:, :n]


@partial(jax.jit, static_argnames=("block_d", "interpret"))
def sparse_score(
    doc_terms: jax.Array,
    doc_weights: jax.Array,
    q_terms: jax.Array,
    q_weights: jax.Array,
    *,
    block_d: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Scores for N docs vs one query via the batched op at B = 1. f32[N]."""
    return sparse_score_batched(
        doc_terms[None], doc_weights[None], q_terms[None], q_weights[None],
        block_d=block_d, interpret=interpret,
    )[0]


def _contract_call(dims):
    """Trace target for the static checker: abstract inputs, sweep tiling."""
    sds = jax.ShapeDtypeStruct
    n, tmax, lq = dims["n"], dims["tmax"], dims["lq"]
    kw = dict(block_d=dims["block_d"], interpret=True)
    if "batch" in dims:
        b = dims["batch"]
        return partial(sparse_score_batched, **kw), (
            sds((b, n, tmax), jnp.int32), sds((b, n, tmax), jnp.float32),
            sds((b, lq), jnp.int32), sds((b, lq), jnp.float32))
    return partial(sparse_score, **kw), (
        sds((n, tmax), jnp.int32), sds((n, tmax), jnp.float32),
        sds((lq,), jnp.int32), sds((lq,), jnp.float32))


# Single source of truth for the sweep shapes in tests/test_kernels.py and
# the checker's trace grid: doc counts ragged vs the block and sub-lane Lq.
CONTRACT = KernelContract(
    name="sparse_score",
    description="match-and-accumulate sparse scorer (DAAT chunk scoring)",
    make_call=_contract_call,
    shape_grid=(
        ShapeCase("small", dict(n=100, tmax=16, lq=8, block_d=128)),
        ShapeCase("aligned", dict(n=512, tmax=64, lq=32, block_d=128)),
        ShapeCase("ragged", dict(n=130, tmax=7, lq=3, block_d=128)),
        ShapeCase("b1", dict(batch=1, n=100, tmax=16, lq=8, block_d=128)),
        ShapeCase("b3_ragged", dict(batch=3, n=130, tmax=7, lq=3, block_d=128)),
        ShapeCase("b4_aligned", dict(batch=4, n=512, tmax=64, lq=32, block_d=128)),
    ),
)
