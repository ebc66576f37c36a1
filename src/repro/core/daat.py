"""Vectorized Block-Max document-at-a-time (DAAT) evaluation.

CPU MaxScore / WAND / BMW walk doc-ordered postings and use per-term (and
per-block) score upper bounds to *skip* documents that cannot enter the top-k.
Per-document pivoting is meaningless on a 128-lane vector unit, so the TPU
adaptation works at document-*block* granularity — which is also exactly where
Block-Max WAND gets its skipping power:

  phase 0   upper bound for every block in one scatter-add over the per-term
            block-max lists (``ub[b] = sum_t qw_t * blockmax[t, b]``)
  phase 1   score the ``est_blocks`` highest-ub blocks exactly -> threshold
            theta = k-th best score
  phase 2   *skip* every block with ``ub <= theta``; score survivors in
            chunks of ``block_budget`` inside a ``lax.while_loop`` until
            rank-safe (``exact=True``) or for one chunk (approximate).

The while_loop trip count is data-dependent: with BM25-like skewed weights few
blocks survive and the loop exits immediately; with "wacky" learned weights
the bounds are loose, almost nothing is skippable, and the loop degenerates
toward exhaustive scoring — reproducing both the paper's DAAT slowdown *and*
its unpredictable tail latency, structurally, on TPU. ``WorkStats`` exposes
the survivor counts that quantify the collapse (benchmarks Table 1 / §4.2).

Batched while_loop semantics
----------------------------
Like the SAAT engine (PR 1), DAAT now has a *natively batched* formulation,
``daat_search_batched``: the whole ``[B, Lq]`` query batch is ONE executable —
one batched block-upper-bound scatter (``ub[b_q, blk]``), one batched phase-1
scoring pass, and a SINGLE ``lax.while_loop`` whose state carries every
query's (pool, processed-set, theta, chunk-count) side by side. Each query's
threshold dynamics stay *independent*:

  * the loop condition is ``any(active)`` where ``active[q]`` is exactly the
    per-query condition the single-query loop would evaluate
    (``max remaining ub > theta AND chunks < max_chunks``);
  * the body computes one batched chunk step, then per-query ``where`` masks
    keep every *inactive* query's state frozen — a query that became
    rank-safe idles (its rows ride along untouched) while stragglers keep
    scoring.

This replicates ``jax.vmap``-of-``while_loop`` semantics by construction, so
``daat_search_batched`` is bit-identical to the ``daat_search_vmap`` oracle —
but the batch executes as one program (one scatter, one top-k, one scorer per
iteration) instead of B interleaved vmapped programs. Tail latency remains
data-dependent *by design*: the batch runs until its SLOWEST query is done
(max over per-query trip counts), which is precisely the paper's DAAT
tail-latency mechanism, now measured per batch. ``WorkStats`` is still
per-query: survivor counts, scored-block counts, trip counts, and rank-safety
flags are carried through the masked loop unchanged.

``daat_search_vmap`` (the historical ``blockmax_search``, kept as an alias)
remains the parity oracle and benchmark baseline
(``benchmarks/side_daat_vs_saat_batched.py``).

Kernel-backed phase 2 (``use_kernels=True``)
--------------------------------------------
The batched engine can route its hot inner ops through the batch-gridded
Pallas kernels instead of jnp:

  * block upper bounds — ``block_prune_csr_batched`` walks the per-term
    block-max lists and accumulates the bound slot by slot (one launch,
    phase 0);
  * chunk selection — ``block_topk_batched`` replaces ``lax.top_k`` over the
    remaining-ub vector (phase 1 seeding and every phase-2 iteration);
  * chunk scoring — ``sparse_score_batched`` match-and-accumulate replaces
    the jnp gather-reduce ``score_blocks``.

The jnp path is kept verbatim as the parity oracle: doc ids, scores and
``WorkStats`` must match exactly (every sum runs in the same fixed order in
XLA and in the kernels — see :mod:`repro.kernels.common`). All
threshold/merge/masking logic is shared between the modes — ``use_kernels``
swaps only HOW the same numbers are produced.

Fused chunk step (``use_kernels=True, fused_chunk=True``)
---------------------------------------------------------
The split kernel mode still pays three launches per while_loop trip, with the
``[B, budget, bs]`` chunk-score tensor and the selection finalists
round-tripping HBM between them — exactly the per-trip traffic a
skipping-hostile (wacky-weight) workload multiplies by its trip count.
``fused_chunk=True`` routes the WHOLE phase-2 body through ONE batch-gridded
Pallas kernel (``repro.kernels.chunk_step``):

  * the chunk state — pool scores/ids, theta, the candidate score tile, and
    the per-query processed-bitmap row — stays in VMEM scratch across the
    doc-block revisiting loop;
  * the selected blocks' doc-major rows are pulled from the HBM store with
    double-buffered async-copy DMAs, so block ``j+1``'s ``[bs, Tmax]`` rows
    prefetch while block ``j`` is being scored;
  * only the updated per-query state (pool, theta, processed) crosses the
    HBM boundary per trip — the candidate output.

Phase 0/1 still run the split kernels (they execute once per query, not once
per trip). The jnp body remains the parity oracle: the fused kernel evaluates
the numerically identical expressions in the same order, so doc ids, theta,
and ``WorkStats`` are bit-identical across all three modes.

Multi-trip launches (``fused_chunk=True, trips_per_launch=N``)
--------------------------------------------------------------
The fused mode still exits to XLA on every while_loop trip — one launch plus
a pool/theta/processed HBM round-trip per trip, multiplied by exactly the
trip counts that explode under wacky weights. ``trips_per_launch=N`` runs up
to N trip bodies inside ONE ``chunk_step`` launch: the engine hands the
kernel a scalar-prefetched per-row trip budget
(``min(max_chunks - chunks, N)``; 0 for already-finished rows), the state
revolves in VMEM across the in-kernel trip loop with a per-trip early exit
(a rank-safe row skips the remaining trips' DMAs and compute), and the
while_loop advances ``chunks`` by the kernel's reported per-row
``trips_done``. Each row's trip sequence is independent of the others, so
the final pool/theta/processed AND the per-query trip counts are
bit-identical to ``trips_per_launch=1`` — a launch is just a window of T
consecutive trips, and a query's launch count drops to
``ceil(chunks / trips_per_launch)``. Approximate mode (``exact=False``)
clamps the budget to one trip so its single gated step stays flag-invariant.

CSR-native phase 0 (``use_kernels=True``)
-----------------------------------------
Kernel-mode phase 0 used to densify the per-(query, slot) block-max lists to
a ``[B, Lq, n_blocks]`` matrix — ``Lq`` x the footprint of the CSR lists it
expands — just to feed ``block_prune_batched``'s MXU contraction. The
``block_prune_csr`` kernel walks the CSR lists directly: the engine
scalar-prefetches the per-slot list offsets/counts
(:func:`csr_blockmax_offsets`), the kernel DMAs each slot's window out of
the HBM-resident ``bm_block``/``bm_weight`` arrays, densifies it into a
``[Lq, n_blocks]`` VMEM tile, and runs the SAME ``[1, Lq] x [Lq, NB]`` dot —
so ``ub`` (and therefore ids and ``WorkStats``) is bit-identical while the
dense intermediate never exists in the jaxpr.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.impact_index import ImpactIndex, query_vector
from repro.core.topk import merge_topk, topk


class WorkStats(NamedTuple):
    """Per-query DAAT work metrics — the paper's skipping-collapse evidence."""

    n_survivors: jax.Array  # i32[...] blocks with ub > theta after phase 1
    blocks_scored: jax.Array  # i32[...] total blocks actually scored
    chunks: jax.Array  # i32[...] while_loop trip count (tail-latency proxy)
    rank_safe: jax.Array  # bool[...] all survivors were scored


class DaatResult(NamedTuple):
    scores: jax.Array  # f32[..., k]
    doc_ids: jax.Array  # i32[..., k]
    n_survivors: jax.Array  # i32[...] blocks with ub > theta after phase 1
    blocks_scored: jax.Array  # i32[...] total blocks actually scored
    chunks: jax.Array  # i32[...] while_loop trip count (tail-latency proxy)
    rank_safe: jax.Array  # bool[...] all survivors were scored

    @property
    def stats(self) -> WorkStats:
        return WorkStats(self.n_survivors, self.blocks_scored, self.chunks, self.rank_safe)


class DaatPlan(NamedTuple):
    """Batched phase-0 output: per-query dense vectors the scorer consumes.

    Fields carry an optional leading query-batch dim (``[Lq]`` or ``[B, Lq]``
    inputs); single-query plans are the rank-1 case.
    """

    ub: jax.Array  # f32[..., n_blocks] additive block upper bounds
    qvec: jax.Array  # f32[..., n_terms + 1] dense query vector (pad slot 0)


def max_blocks_per_term(index: ImpactIndex) -> int:
    """Static bound on per-term block-max list length (safety: must not clip).

    ``build_impact_index`` records this as ``index.max_bm`` so DAAT serving
    setup never blocks on a device sync (mirroring ``max_segs`` for SAAT);
    the reduction below only runs for indexes assembled by hand without the
    metadata. Clamped to >= 1 so a zero-posting corpus (every doc
    tombstoned, then compacted) still yields an indexable bound — the padded
    slot has block count 0 and never survives pruning.
    """
    if index.max_bm > 0:
        return int(index.max_bm)
    return max(1, int(jax.device_get(index.term_bm_count.max())))


def query_vectors(index: ImpactIndex, q_terms: jax.Array, q_weights: jax.Array) -> jax.Array:
    """Dense query vectors over V+1 slots: ``[Lq]`` or ``[B, Lq]`` inputs.

    The batched (rank-2) case is ONE scatter over ``[B, V+1]`` (duplicate
    query terms sum, pad slot forced to 0), not B vmapped scatters.
    """
    if q_terms.ndim == 1:
        return query_vector(index, q_terms, q_weights)
    n_terms = index.n_terms
    safe = jnp.where(q_weights > 0, q_terms, n_terms)
    qvec = jnp.zeros(q_terms.shape[:-1] + (n_terms + 1,), jnp.float32)
    rows = jnp.arange(q_terms.shape[0], dtype=jnp.int32)[:, None]
    qvec = qvec.at[rows, safe].add(q_weights.astype(jnp.float32))
    return qvec.at[..., n_terms].set(0.0)


def _gather_blockmax_lists(
    index: ImpactIndex, q_terms: jax.Array, q_weights: jax.Array, max_bm_per_term: int
) -> Tuple[jax.Array, jax.Array]:
    """Clamp-and-gather the per-slot block-max lists (shared by the jnp and
    kernel phase-0 paths — ONE copy of the sentinel/clamp logic).

    Returns ``(blocks i32[..., Lq, M], w f32[..., Lq, M])`` with raw block
    maxima (query weight NOT applied) and invalid slots zeroed; pad /
    zero-weight query slots map to the sentinel term's empty list.
    """
    base, cnt = csr_blockmax_offsets(index, q_terms, q_weights, max_bm_per_term)
    offs = jnp.arange(max_bm_per_term, dtype=jnp.int32)
    idx = base[..., :, None] + offs
    valid = offs < cnt[..., :, None]
    idx = jnp.where(valid, idx, 0)
    blocks = jnp.where(valid, index.bm_block[idx], 0)
    w = jnp.where(valid, index.bm_weight[idx], 0.0)
    return blocks, w


def csr_blockmax_offsets(
    index: ImpactIndex, q_terms: jax.Array, q_weights: jax.Array, max_bm_per_term: int
) -> Tuple[jax.Array, jax.Array]:
    """Scalar-prefetch operands for the CSR-native prune kernel.

    The same sentinel/clamp logic as :func:`_gather_blockmax_lists` — pad /
    zero-weight query slots map to the sentinel term's empty list, counts
    clamp to the static per-term bound — but only the ``(base, cnt)``
    ``i32[..., Lq]`` window descriptors are materialized; the lists
    themselves stay in HBM for the kernel to DMA.
    """
    t = jnp.where(q_weights > 0, q_terms, index.n_terms)
    base = index.term_bm_start[t].astype(jnp.int32)
    cnt = jnp.minimum(index.term_bm_count[t], max_bm_per_term).astype(jnp.int32)
    return base, cnt


def block_upper_bounds(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    max_bm_per_term: int,
) -> jax.Array:
    """BMW-style additive upper bound for every document block.

    ``[Lq]`` inputs give ``f32[n_blocks]``; ``[B, Lq]`` inputs give
    ``f32[B, n_blocks]``. The bound accumulates slot by slot
    (``ub += qw_l * blockmax_l``): within one slot's list a block appears at
    most once, so every scatter has distinct indices and the sum order is
    fixed — the bits the ``block_prune_csr`` kernel reproduces.
    Ranks above 2 are not supported (the row-index scatter is rank-2).
    """
    blocks, w = _gather_blockmax_lists(index, q_terms, q_weights, max_bm_per_term)
    w = w * q_weights[..., :, None].astype(jnp.float32)
    ub = jnp.zeros(blocks.shape[:-2] + (index.n_blocks,), jnp.float32)
    for l in range(blocks.shape[-2]):
        if blocks.ndim == 2:
            ub = ub.at[blocks[l]].add(w[l])
        else:
            rows = jnp.arange(blocks.shape[0], dtype=jnp.int32)[:, None]
            ub = ub.at[rows, blocks[:, l]].add(w[:, l])
    return ub


def daat_plan(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    max_bm_per_term: int,
) -> DaatPlan:
    """Phase 0 for a whole batch: block upper bounds + dense query vectors."""
    return DaatPlan(
        ub=block_upper_bounds(index, q_terms, q_weights, max_bm_per_term),
        qvec=query_vectors(index, q_terms, q_weights),
    )


def score_blocks(
    index: ImpactIndex,
    qvec: jax.Array,
    block_ids: jax.Array,
    live_mask: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact scores for whole blocks of documents via the doc-major store.

    ``qvec[V+1], block_ids[nb]`` returns
    ``(scores[nb, block_size], doc_ids[nb, block_size])``; the batched case
    ``qvec[B, V+1], block_ids[B, nb]`` returns ``[B, nb, block_size]`` pairs.
    Padded documents are masked to -inf, as are documents whose slot in the
    optional ``live_mask`` (i32/bool ``[n_docs_pad]`` lifecycle tombstone
    bitmap; nonzero = live) is 0 — masking happens at selection, never inside
    the score sum, so surviving docs' f32 scores are bit-identical with or
    without the mask. The inner op is a gather of query weights by term id +
    a weighted row reduction in the fixed pairwise order of
    :func:`repro.kernels.common.tree_sum_last` — the ``sparse_score`` and
    ``chunk_step`` kernels compute the same bits with VMEM-tiled blocks.
    """
    bs = index.block_size
    docs = block_ids[..., :, None] * bs + jnp.arange(bs, dtype=jnp.int32)
    terms = index.doc_terms[docs]  # [..., nb, bs, Tmax]
    w = index.doc_weights[docs]
    if qvec.ndim == 1:
        qv = qvec[terms]
    else:
        rows = jnp.arange(qvec.shape[0], dtype=jnp.int32)[:, None, None, None]
        qv = qvec[rows, terms]
    from repro.kernels.common import tree_sum_last

    scores = tree_sum_last(qv * w)  # the kernels' fixed sum order
    scores = jnp.where(docs < index.n_docs, scores, -jnp.inf)
    if live_mask is not None:
        scores = jnp.where(live_mask[docs] != 0, scores, -jnp.inf)
    return scores, docs


def _dense_blockmax_rows(
    index: ImpactIndex, q_terms: jax.Array, q_weights: jax.Array, max_bm_per_term: int
) -> jax.Array:
    """Densify the per-(query, slot) block-max lists: ``f32[B, Lq, n_blocks]``.

    Raw block maxima (query weight NOT applied) — the ``[Lq, NB]`` layout the
    ``block_prune`` kernel contracts against ``q_weights`` on the MXU.
    Pad / zero-weight slots densify to empty rows, so they contribute exactly
    0 to the bound, mirroring :func:`block_upper_bounds`.

    Cost note: the dense layout is ``Lq`` x larger than the CSR lists it
    expands, which is why kernel-mode phase 0 no longer uses it — the
    CSR-native ``block_prune_csr`` kernel walks the lists directly and the
    analysis lane asserts this intermediate never appears in the traced
    search. Kept as the dense ``block_prune`` kernel's input builder for its
    oracle tests.
    """
    blocks, w = _gather_blockmax_lists(index, q_terms, q_weights, max_bm_per_term)
    B, Lq = q_terms.shape
    rows = jnp.zeros((B, Lq, index.n_blocks), jnp.float32)
    b_ix = jnp.arange(B, dtype=jnp.int32)[:, None, None]
    l_ix = jnp.arange(Lq, dtype=jnp.int32)[None, :, None]
    return rows.at[b_ix, l_ix, blocks].add(w)


def _score_blocks_kernel_batched(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    block_ids: jax.Array,
    live_mask: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Kernel-backed :func:`score_blocks`: one ``sparse_score_batched`` launch.

    Gathers the selected blocks' doc-major rows (exactly as the jnp scorer
    does) and hands the ``[B, nb * block_size, Tmax]`` tile to the
    match-and-accumulate kernel; padded and tombstoned documents mask to
    ``-inf`` outside the kernel, matching the jnp path (selection-time
    masking, never inside the score sum).
    """
    from repro.kernels.sparse_score import ops as score_ops

    bs = index.block_size
    docs = block_ids[..., :, None] * bs + jnp.arange(bs, dtype=jnp.int32)  # [B, nb, bs]
    B = docs.shape[0]
    flat = docs.reshape(B, -1)
    dt = index.doc_terms[flat]  # [B, nb*bs, Tmax]
    dw = index.doc_weights[flat]
    # the engine defines qw <= 0 slots as padding; the kernel sums raw weights
    qw = jnp.where(q_weights > 0, q_weights.astype(jnp.float32), 0.0)
    scores = score_ops.sparse_score_batched(dt, dw, q_terms, qw)
    scores = jnp.where(flat < index.n_docs, scores, -jnp.inf)
    if live_mask is not None:
        scores = jnp.where(live_mask[flat] != 0, scores, -jnp.inf)
    return scores.reshape(docs.shape), docs


def _mask_dead_blocks(
    index: ImpactIndex, ub: jax.Array, live_mask: jax.Array
) -> jax.Array:
    """``ub -> -inf`` for blocks whose every document is tombstoned.

    Applied identically in every mode right after phase 0 (the
    ``block_prune_csr`` kernel itself is untouched — a stale-high bound over
    a partially-dead block is still a valid upper bound, and uniform
    post-phase-0 masking keeps ``WorkStats`` mode-identical): a fully-dead
    block can never contribute a candidate, so dropping it from selection
    keeps survivor counts meaningful and lets ``rank_safe`` converge without
    scoring blocks that only contain ``-inf``.
    """
    bs = index.block_size
    blk_live = live_mask.reshape(index.n_blocks, bs).max(axis=-1)
    return jnp.where(blk_live != 0, ub, -jnp.inf)


def _resolve_daat_shapes(
    index: ImpactIndex, k: int, est_blocks: int, block_budget: int, max_chunks: int | None
) -> Tuple[int, int, int]:
    n_blocks = index.n_blocks
    est_blocks = min(est_blocks, n_blocks)
    block_budget = min(block_budget, n_blocks)
    if max_chunks is None:
        max_chunks = -(-n_blocks // block_budget)  # ceil: worst case scores all
    if k > est_blocks * index.block_size:
        raise ValueError(
            f"k={k} exceeds the phase-1 pool (est_blocks={est_blocks} * "
            f"block_size={index.block_size}); raise est_blocks"
        )
    return est_blocks, block_budget, max_chunks


@partial(
    jax.jit,
    static_argnames=("k", "est_blocks", "block_budget", "max_bm_per_term", "exact", "max_chunks"),
)
def daat_search_vmap(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    *,
    k: int,
    est_blocks: int,
    block_budget: int,
    max_bm_per_term: int,
    exact: bool = True,
    max_chunks: int | None = None,
    live_mask: jax.Array | None = None,
) -> DaatResult:
    """Legacy ``jax.vmap(one-query)`` block-max DAAT — the parity oracle.

    ``q_terms/q_weights: [B, Lq]``. Semantically identical to
    :func:`daat_search_batched`; kept so the batched engine can be validated
    bit-for-bit on doc ids and raced in the side benchmarks. ``live_mask``
    (optional ``[n_docs_pad]`` tombstone bitmap, shared by the batch) masks
    deleted docs to ``-inf`` and drops fully-dead blocks from selection.
    """
    n_blocks = index.n_blocks
    est_blocks, block_budget, max_chunks = _resolve_daat_shapes(
        index, k, est_blocks, block_budget, max_chunks
    )

    def one(qt, qw):
        qvec = query_vector(index, qt, qw)
        ub = block_upper_bounds(index, qt, qw, max_bm_per_term)
        if live_mask is not None:
            ub = _mask_dead_blocks(index, ub, live_mask)

        # ---- phase 1: seed the top-k pool from the most promising blocks ----
        _, b1 = topk(ub, est_blocks)
        s1, d1 = score_blocks(index, qvec, b1, live_mask)
        pool_s, pool_i = topk(s1.reshape(-1), k)
        pool_i = d1.reshape(-1)[pool_i].astype(jnp.int32)
        theta = pool_s[k - 1]
        processed = jnp.zeros((n_blocks,), jnp.bool_).at[b1].set(True)
        survivors0 = jnp.sum((ub > theta) & ~processed).astype(jnp.int32)

        # ---- phase 2: chunked scoring of surviving blocks ----
        def remaining_ub(processed, theta):
            return jnp.where(processed, -jnp.inf, ub)

        def cond(state):
            pool_s, pool_i, processed, theta, chunks = state
            more = jnp.max(remaining_ub(processed, theta)) > theta
            return more & (chunks < max_chunks)

        def body(state):
            pool_s, pool_i, processed, theta, chunks = state
            rub = remaining_ub(processed, theta)
            ub_c, b_c = topk(rub, block_budget)
            live = ub_c > theta  # only these can change the top-k
            s_c, d_c = score_blocks(index, qvec, b_c, live_mask)
            s_c = jnp.where(live[:, None], s_c, -jnp.inf)
            pool_s, pool_i = merge_topk(
                pool_s, pool_i, s_c.reshape(-1), d_c.reshape(-1).astype(jnp.int32), k
            )
            theta = pool_s[k - 1]
            processed = processed.at[b_c].set(processed[b_c] | live)
            return pool_s, pool_i, processed, theta, chunks + 1

        state = (pool_s, pool_i, processed, theta, jnp.int32(0))
        if exact:
            pool_s, pool_i, processed, theta, chunks = jax.lax.while_loop(cond, body, state)
        else:
            pool_s, pool_i, processed, theta, chunks = jax.lax.cond(
                cond(state), body, lambda s: s, state
            )
        blocks_scored = jnp.sum(processed).astype(jnp.int32)
        rank_safe = jnp.max(remaining_ub(processed, theta)) <= theta
        return DaatResult(pool_s, pool_i, survivors0, blocks_scored, chunks, rank_safe)

    return jax.vmap(one)(q_terms, q_weights)


# Historical name, kept for existing callers (benchmarks, wacky reports).
blockmax_search = daat_search_vmap


# The full static surface of the batched engine: everything here forks the
# compile cache. repro.analysis.hot_path keys executables on exactly this
# tuple, so keep it in sync with the jit decorator below (it IS the decorator
# argument).
DAAT_STATICS = (
    "k", "est_blocks", "block_budget", "max_bm_per_term", "exact", "max_chunks",
    "use_kernels", "fused_chunk", "trips_per_launch",
)


@partial(jax.jit, static_argnames=DAAT_STATICS)
def daat_search_batched(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    *,
    k: int,
    est_blocks: int,
    block_budget: int,
    max_bm_per_term: int,
    exact: bool = True,
    max_chunks: int | None = None,
    use_kernels: bool = False,
    fused_chunk: bool = False,
    trips_per_launch: int = 1,
    live_mask: jax.Array | None = None,
) -> DaatResult:
    """Natively batched block-max DAAT top-k. ``q_terms/q_weights: [B, Lq]``.

    One executable per (k, est_blocks, block_budget, exact) configuration for
    the whole batch: a single phase-0 scatter, a single phase-1 scoring pass,
    and a single ``lax.while_loop`` with per-query masked state (see module
    docstring for the batched-loop semantics). Bit-identical doc ids and
    :class:`WorkStats` to :func:`daat_search_vmap`.

    ``use_kernels=True`` routes phase 0's upper bounds through the CSR-native
    ``block_prune_csr`` kernel, chunk selection through
    ``block_topk_batched``, and chunk scoring through
    ``sparse_score_batched``; ``fused_chunk=True`` (kernel mode only)
    additionally collapses every phase-2 trip's select+score+merge into the
    single VMEM-resident ``chunk_step`` kernel, and ``trips_per_launch=N``
    (fused mode only) runs up to N trips per launch inside that kernel (see
    module docstring); the jnp formulation stays the parity oracle for every
    combination.

    ``live_mask`` (optional i32/bool ``[n_docs_pad]`` lifecycle tombstone
    bitmap; nonzero = live, shared by the batch) threads through every mode:
    fully-dead blocks drop out of selection right after phase 0
    (:func:`_mask_dead_blocks`), and dead docs mask to ``-inf`` at
    selection time — via the jnp/kernel scorers' gather or the fused
    ``chunk_step`` kernel's DMA'd live rows — so ids, theta, and
    ``WorkStats`` stay bit-identical across all kernel modes for any mask.
    """
    if q_terms.ndim != 2:
        raise ValueError(f"expected [B, Lq] query batch, got shape {q_terms.shape}")
    if fused_chunk and not use_kernels:
        raise ValueError(
            "fused_chunk fuses the kernel-mode chunk step; pass use_kernels=True"
        )
    if trips_per_launch < 1:
        raise ValueError(f"trips_per_launch={trips_per_launch} must be >= 1")
    if trips_per_launch > 1 and not fused_chunk:
        raise ValueError(
            "trips_per_launch > 1 batches trips inside the fused chunk_step "
            "kernel; pass use_kernels=True, fused_chunk=True"
        )
    n_blocks = index.n_blocks
    est_blocks, block_budget, max_chunks = _resolve_daat_shapes(
        index, k, est_blocks, block_budget, max_chunks
    )
    B = q_terms.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]

    if use_kernels:
        from repro.kernels.block_prune_csr import ops as prune_ops
        from repro.kernels.block_topk import ops as topk_ops

        # CSR-native phase 0: only the [B, Lq] window descriptors cross to
        # the kernel; the dense [B, Lq, n_blocks] block-max intermediate the
        # old block_prune_batched path densified never exists (the analysis
        # lane asserts its absence from this jaxpr). ub stays bit-identical.
        base, cnt = csr_blockmax_offsets(index, q_terms, q_weights, max_bm_per_term)
        ub, _ = prune_ops.block_prune_csr_batched(
            index.bm_block, index.bm_weight, base, cnt,
            q_weights.astype(jnp.float32),
            jnp.full((B,), -jnp.inf, jnp.float32),  # no threshold yet: pure ub pass
            n_blocks=n_blocks, max_bm_per_term=max_bm_per_term,
        )
        qvec = None  # the kernel scorer consumes (q_terms, q_weights) directly

        def _select(scores_vec, n):  # noqa: ANN001 — chunk/phase-1 block select
            return topk_ops.block_topk_batched(scores_vec, n)

        def _score(block_ids):
            return _score_blocks_kernel_batched(
                index, q_terms, q_weights, block_ids, live_mask
            )

    else:
        plan = daat_plan(index, q_terms, q_weights, max_bm_per_term)
        ub, qvec = plan.ub, plan.qvec  # [B, n_blocks], [B, V+1]

        def _select(scores_vec, n):
            return topk(scores_vec, n)

        def _score(block_ids):
            return score_blocks(index, qvec, block_ids, live_mask)

    if live_mask is not None:
        ub = _mask_dead_blocks(index, ub, live_mask)

    # ---- phase 1: seed every query's top-k pool in one batched pass ----
    _, b1 = _select(ub, est_blocks)  # [B, est_blocks]
    s1, d1 = _score(b1)  # [B, est_blocks, bs]
    pool_s, pool_i = topk(s1.reshape(B, -1), k)
    pool_i = jnp.take_along_axis(d1.reshape(B, -1), pool_i, axis=-1).astype(jnp.int32)
    theta = pool_s[:, k - 1]  # [B]
    processed = jnp.zeros((B, n_blocks), jnp.bool_).at[rows, b1].set(True)
    survivors0 = jnp.sum((ub > theta[:, None]) & ~processed, axis=-1).astype(jnp.int32)

    # ---- phase 2: one while_loop, per-query state advances independently ----
    def remaining_ub(processed):
        return jnp.where(processed, -jnp.inf, ub)

    def active_rows(state):
        pool_s, pool_i, processed, theta, chunks = state
        more = jnp.max(remaining_ub(processed), axis=-1) > theta
        return more & (chunks < max_chunks)  # bool[B]

    def cond(state):
        return jnp.any(active_rows(state))

    # approximate mode applies the body ONCE outside the while_loop, so its
    # launch must stay a single gated trip for flag-invariant results
    trip_cap = trips_per_launch if exact else 1
    multi_body = None

    if fused_chunk:
        from repro.kernels.chunk_step import ops as chunk_ops

        # the engine defines qw <= 0 slots as padding; the kernel sums raw
        # weights (same contract as _score_blocks_kernel_batched)
        qw_raw = jnp.where(q_weights > 0, q_weights.astype(jnp.float32), 0.0)

        def _chunk_step(pool_s, pool_i, processed, theta):
            """ONE kernel launch: select+score+merge, state VMEM-resident."""
            return chunk_ops.chunk_step_batched(
                index.doc_terms, index.doc_weights, q_terms, qw_raw,
                ub, processed, pool_s, pool_i, theta,
                block_budget=block_budget,
                block_size=index.block_size,
                n_live=index.n_docs,
                live=live_mask,
            )

        if trip_cap > 1:

            def multi_body(state):
                """Up to ``trip_cap`` trips in ONE launch; state stays in VMEM.

                The per-row scalar-prefetched budget folds the engine's
                ``chunks < max_chunks`` bound into the kernel (a row never
                overruns it) and zeroes out inactive rows, so the kernel's
                in-kernel gating reproduces the per-trip loop's active
                condition trip by trip — final state AND per-query trip
                counts are bit-identical to ``trips_per_launch=1``.
                """
                pool_s, pool_i, processed, theta, chunks = state
                act = active_rows(state)
                trips_left = jnp.where(
                    act, jnp.minimum(max_chunks - chunks, trip_cap), 0
                ).astype(jnp.int32)
                new_s, new_i, new_theta, new_processed, trips_done = (
                    chunk_ops.chunk_step_multi_batched(
                        index.doc_terms, index.doc_weights, q_terms, qw_raw,
                        ub, processed, pool_s, pool_i, theta, trips_left,
                        trips_per_launch=trip_cap,
                        block_budget=block_budget,
                        block_size=index.block_size,
                        n_live=index.n_docs,
                        live=live_mask,
                    )
                )
                # the kernel freezes trips_left == 0 rows itself; the masks
                # keep the inactive-row guarantee structural regardless
                pool_s = jnp.where(act[:, None], new_s, pool_s)
                pool_i = jnp.where(act[:, None], new_i, pool_i)
                processed = jnp.where(act[:, None], new_processed, processed)
                theta = jnp.where(act, new_theta, theta)
                chunks = chunks + jnp.where(act, trips_done, 0)
                return pool_s, pool_i, processed, theta, chunks

    else:

        def _chunk_step(pool_s, pool_i, processed, theta):
            """Split chunk step: selection, scoring, and merge round-trip HBM."""
            rub = remaining_ub(processed)
            ub_c, b_c = _select(rub, block_budget)  # [B, budget]
            live = ub_c > theta[:, None]  # only these can change the top-k
            s_c, d_c = _score(b_c)  # [B, budget, bs]
            s_c = jnp.where(live[..., None], s_c, -jnp.inf)
            new_s, new_i = merge_topk(
                pool_s, pool_i, s_c.reshape(B, -1), d_c.reshape(B, -1).astype(jnp.int32), k
            )
            new_theta = new_s[:, k - 1]
            new_processed = processed.at[rows, b_c].set(
                processed[rows, b_c] | live
            )
            return new_s, new_i, new_theta, new_processed

    def body(state):
        pool_s, pool_i, processed, theta, chunks = state
        act = active_rows(state)  # finished queries idle below
        new_s, new_i, new_theta, new_processed = _chunk_step(
            pool_s, pool_i, processed, theta
        )
        # per-query masking: inactive rows keep their state bit-for-bit
        pool_s = jnp.where(act[:, None], new_s, pool_s)
        pool_i = jnp.where(act[:, None], new_i, pool_i)
        processed = jnp.where(act[:, None], new_processed, processed)
        theta = jnp.where(act, new_theta, theta)
        chunks = chunks + act.astype(jnp.int32)
        return pool_s, pool_i, processed, theta, chunks

    if multi_body is not None:
        body = multi_body

    state = (pool_s, pool_i, processed, theta, jnp.zeros((B,), jnp.int32))
    if exact:
        pool_s, pool_i, processed, theta, chunks = jax.lax.while_loop(cond, body, state)
    else:
        # approximate mode: at most one chunk step, per-query gated
        new_state = body(state)
        pool_s, pool_i, processed, theta, chunks = new_state
    blocks_scored = jnp.sum(processed, axis=-1).astype(jnp.int32)
    rank_safe = jnp.max(remaining_ub(processed), axis=-1) <= theta
    return DaatResult(pool_s, pool_i, survivors0, blocks_scored, chunks, rank_safe)
