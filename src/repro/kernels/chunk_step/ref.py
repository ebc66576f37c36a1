"""Pure-jnp oracle for the fused chunk-step kernel.

This is the batched DAAT engine's phase-2 while-body, verbatim: the exact
selection (``lax.top_k`` over the masked ub row), the exact ``score_blocks``
gather-reduce through a dense query vector (summed in the fixed order of
``tree_sum_last``), and the exact ``merge_topk`` pool+candidates
concatenation. The fused kernel must be indistinguishable from this function
in doc ids, scores, theta, and the processed bitmap (bitwise) — which is
exactly the engine-level ``fused_chunk`` parity contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import tree_sum_last


def chunk_step_batched_ref(
    doc_terms: jax.Array,  # i32[n_docs_pad, Tmax] (pad slot term = n_terms)
    doc_weights: jax.Array,  # f32[n_docs_pad, Tmax]
    q_terms: jax.Array,  # i32[B, Lq]
    q_weights: jax.Array,  # f32[B, Lq] (slots with weight <= 0 are padding)
    ub: jax.Array,  # f32[B, n_blocks]
    processed: jax.Array,  # bool[B, n_blocks]
    pool_s: jax.Array,  # f32[B, k]
    pool_i: jax.Array,  # i32[B, k]
    theta: jax.Array,  # f32[B]
    *,
    block_budget: int,
    block_size: int,
    n_live: int,
    n_terms: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One jnp chunk step; returns ``(pool_s, pool_i, theta, processed)``."""
    B = q_terms.shape[0]
    k = pool_s.shape[-1]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]

    # dense query vectors over V+1 slots — repro.core.daat.query_vectors
    safe = jnp.where(q_weights > 0, q_terms, n_terms)
    qvec = jnp.zeros((B, n_terms + 1), jnp.float32)
    qvec = qvec.at[rows, safe].add(q_weights.astype(jnp.float32))
    qvec = qvec.at[:, n_terms].set(0.0)

    rub = jnp.where(processed, -jnp.inf, ub)
    ub_c, b_c = jax.lax.top_k(rub, block_budget)  # [B, budget]
    live = ub_c > theta[:, None]

    # score_blocks: gather the doc-major rows, reduce against qvec
    docs = b_c[..., :, None] * block_size + jnp.arange(block_size, dtype=jnp.int32)
    terms = doc_terms[docs]  # [B, budget, bs, Tmax]
    w = doc_weights[docs]
    qv = qvec[rows[..., None, None], terms]
    s_c = tree_sum_last(qv * w)
    s_c = jnp.where(docs < n_live, s_c, -jnp.inf)
    s_c = jnp.where(live[..., None], s_c, -jnp.inf)

    # merge_topk: pool first, candidates after — the tie order the kernel keeps
    all_s = jnp.concatenate([pool_s, s_c.reshape(B, -1)], axis=-1)
    all_i = jnp.concatenate([pool_i, docs.reshape(B, -1).astype(jnp.int32)], axis=-1)
    ms, mpos = jax.lax.top_k(all_s, k)
    new_i = jnp.take_along_axis(all_i, mpos, axis=-1)
    new_theta = ms[:, k - 1]
    new_processed = processed.at[rows, b_c].set(processed[rows, b_c] | live)
    return ms, new_i, new_theta, new_processed


def chunk_step_multi_batched_ref(
    doc_terms: jax.Array,
    doc_weights: jax.Array,
    q_terms: jax.Array,
    q_weights: jax.Array,
    ub: jax.Array,
    processed: jax.Array,
    pool_s: jax.Array,
    pool_i: jax.Array,
    theta: jax.Array,
    trips_left: jax.Array,  # i32[B] per-row trip budget
    *,
    trips_per_launch: int,
    block_budget: int,
    block_size: int,
    n_live: int,
    n_terms: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Multi-trip oracle: ``trips_per_launch`` sequential single-trip steps.

    Per-row gating mirrors the engine's while-loop semantics exactly — a row
    advances on trip ``t`` iff ``t < trips_left[row]`` AND it is not yet
    rank-safe; frozen rows keep their state bit-for-bit. Returns the final
    state plus ``trips_done[B]``, the per-row count of trips that advanced.
    """
    trips_done = jnp.zeros(trips_left.shape, jnp.int32)
    for t in range(trips_per_launch):
        rub = jnp.where(processed, -jnp.inf, ub)
        act = (t < trips_left) & (jnp.max(rub, axis=-1) > theta)
        ns, ni, nth, npr = chunk_step_batched_ref(
            doc_terms, doc_weights, q_terms, q_weights,
            ub, processed, pool_s, pool_i, theta,
            block_budget=block_budget, block_size=block_size,
            n_live=n_live, n_terms=n_terms,
        )
        pool_s = jnp.where(act[:, None], ns, pool_s)
        pool_i = jnp.where(act[:, None], ni, pool_i)
        theta = jnp.where(act, nth, theta)
        processed = jnp.where(act[:, None], npr, processed)
        trips_done = trips_done + act.astype(jnp.int32)
    return pool_s, pool_i, theta, processed, trips_done
