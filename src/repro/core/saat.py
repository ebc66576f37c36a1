"""Anytime score-at-a-time (SAAT) query evaluation — the JASS analogue.

JASS processes impact-ordered posting segments in decreasing order of score
*contribution* (segment impact x query weight) and stops after ``rho``
postings, yielding an approximate top-k whose cost — and therefore latency —
is bounded by construction.

TPU adaptation (DESIGN.md §2): ``rho`` becomes a *static tensor shape*. The
plan step orders candidate segments by contribution; the execute step maps the
first ``rho`` posting slots onto (segment, offset) pairs with a vectorized
``searchsorted`` over the segment-length prefix sum, gathers doc ids, and
scatter-adds contributions into a dense accumulator. Every query therefore
executes the *identical* instruction stream — the strongest possible form of
the paper's "SAAT has predictable latency" claim, and simultaneously the
straggler-mitigation primitive for multi-pod serving.

The engine is *natively batched*: a ``[B, Lq]`` query batch runs one batched
argsort in the planner, one histogram-based batched ``searchsorted`` in the
posting gather, and one batch-aware scatter — a single executable per
(k, rho) configuration, not ``B`` vmapped single-query programs. ``saat_search_vmap`` keeps the original
``jax.vmap(one-query)`` formulation as a parity oracle and benchmark baseline
(``benchmarks/side_batched_vs_vmap.py``).

The scatter is the hot loop; ``scatter_impl='pallas'`` routes it to the
one-hot-matmul Pallas kernel (``repro.kernels.impact_scatter``), which for the
batched engine grids over (query, doc-block, posting-tile).

``fused_topk=True`` goes one step further and fuses the top-k selection INTO
the scatter kernel (``repro.kernels.impact_scatter_topk``): each accumulator
block's revisiting loop ends by emitting its per-block top-k candidates, so
only the ``[B, n_blocks * k]`` candidate pool — never the ``[B, n_docs]``
accumulator — crosses the HBM boundary; a final ``tiled_topk`` merge over the
pool recovers the exact global top-k. The fused path is rank-safe by
construction (a block contributes at most ``min(k, block_d)`` finalists) and
bit-identical in doc ids to the unfused engine; ``scatter_impl`` is ignored
when it is set (the fused kernel IS the scatter).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.impact_index import ImpactIndex
from repro.core.topk import topk


class SaatPlan(NamedTuple):
    """Per-query segment schedule, ordered by decreasing contribution.

    All fields carry the query batch dims in front (``[..., n_cand]``);
    single-query plans are simply the rank-1 case.
    """

    starts: jax.Array  # i32[..., n_cand] posting-store offsets
    contribs: jax.Array  # f32[..., n_cand] per-posting score contribution
    cum_len: jax.Array  # i32[..., n_cand] inclusive prefix sum of segment lengths
    total_postings: jax.Array  # i32[...] total candidate postings


class SaatResult(NamedTuple):
    scores: jax.Array  # f32[..., k]
    doc_ids: jax.Array  # i32[..., k]
    postings_processed: jax.Array  # i32[...]
    total_postings: jax.Array  # i32[...]


def max_segments_per_term(index: ImpactIndex) -> int:
    """Static bound for plan shapes (index-build-time constant).

    ``build_impact_index`` records this as ``index.max_segs`` so the serving
    hot path never blocks on a device sync; the reduction below only runs for
    indexes assembled by hand without the metadata. Clamped to >= 1: a
    corpus with zero postings (all docs tombstoned then compacted away) has
    no segments at all, and a 0-width plan axis cannot be indexed — the one
    padded slot carries segment count 0 and is masked everywhere.
    """
    if index.max_segs > 0:
        return int(index.max_segs)
    return max(1, int(jax.device_get(index.term_seg_count.max())))


def saat_plan(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    max_segs_per_term: int,
) -> SaatPlan:
    """Build the contribution-ordered segment schedule.

    Shape-polymorphic over leading batch dims: ``[Lq]`` inputs give a
    single-query plan, ``[B, Lq]`` a batched plan whose JASS ordering is ONE
    batched argsort over ``[B, n_cand]`` rather than B independent sorts.
    """
    n_terms = index.n_terms
    t = jnp.where(q_weights > 0, q_terms, n_terms)  # pad slot has no segments
    base = index.term_seg_start[t]  # [..., Lq]
    cnt = jnp.minimum(index.term_seg_count[t], max_segs_per_term)  # [..., Lq]
    offs = jnp.arange(max_segs_per_term, dtype=jnp.int32)
    j = base[..., :, None] + offs  # [..., Lq, M]
    valid = offs < cnt[..., :, None]
    j = jnp.where(valid, j, 0)
    contrib = index.seg_weight[j] * q_weights[..., :, None].astype(jnp.float32)
    contrib = jnp.where(valid, contrib, -jnp.inf)
    lens = jnp.where(valid, index.seg_len[j], 0)
    starts = jnp.where(valid, index.seg_start[j], 0)

    flat_shape = contrib.shape[:-2] + (contrib.shape[-2] * contrib.shape[-1],)
    flat_c = contrib.reshape(flat_shape)
    order = jnp.argsort(-flat_c, axis=-1)  # decreasing contribution (JASS order)
    starts = jnp.take_along_axis(starts.reshape(flat_shape), order, axis=-1)
    lens = jnp.take_along_axis(lens.reshape(flat_shape), order, axis=-1)
    sorted_c = jnp.take_along_axis(flat_c, order, axis=-1)
    contribs = jnp.where(jnp.isfinite(sorted_c), sorted_c, 0.0)
    cum = jnp.cumsum(lens, axis=-1, dtype=jnp.int32)
    return SaatPlan(
        starts=starts, contribs=contribs, cum_len=cum, total_postings=cum[..., -1]
    )


def _batched_searchsorted_slots(cum: jax.Array, rho: int) -> jax.Array:
    """Row-wise ``searchsorted(cum[b], arange(rho), side='right')`` without vmap.

    Because the queries are the *sorted* slot ids ``0..rho-1``, the binary
    search collapses to a counting argument: ``j[b, p] = #{i : cum[b, i] <= p}``
    is the prefix sum of a histogram of ``cum`` values. One batched
    ``[B, n_cand]`` scatter-add plus one batched ``[B, rho]`` cumsum —
    integer ops only, so bit-identical to ``jnp.searchsorted``.
    """
    B = cum.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    bins = jnp.clip(cum, 0, rho)  # bin rho collects entries past the budget
    hist = jnp.zeros((B, rho + 1), jnp.int32).at[rows, bins].add(1)
    return jnp.cumsum(hist[:, :rho], axis=-1)


def _gather_postings(
    index: ImpactIndex, plan: SaatPlan, rho: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Map posting slots [0, rho) -> (doc_id, contribution, n_processed)."""
    p = jnp.arange(rho, dtype=jnp.int32)
    j = jnp.searchsorted(plan.cum_len, p, side="right").astype(jnp.int32)
    j = jnp.minimum(j, plan.cum_len.shape[0] - 1)
    prev = jnp.where(j > 0, plan.cum_len[jnp.maximum(j - 1, 0)], 0)
    offset = p - prev
    pidx = plan.starts[j] + offset
    valid = p < plan.total_postings
    docs = index.doc_ids[jnp.where(valid, pidx, 0)]
    contribs = jnp.where(valid, plan.contribs[j], 0.0)
    docs = jnp.where(valid, docs, 0)
    n_processed = jnp.minimum(plan.total_postings, rho).astype(jnp.int32)
    return docs, contribs, n_processed


def _gather_postings_batched(
    index: ImpactIndex, plan: SaatPlan, rho: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched slot -> posting map: one histogram searchsorted over [B, rho]."""
    B, n_cand = plan.cum_len.shape
    p = jnp.broadcast_to(jnp.arange(rho, dtype=jnp.int32), (B, rho))
    j = _batched_searchsorted_slots(plan.cum_len, rho)
    j = jnp.minimum(j, n_cand - 1)
    prev_cum = jnp.take_along_axis(plan.cum_len, jnp.maximum(j - 1, 0), axis=-1)
    prev = jnp.where(j > 0, prev_cum, 0)
    offset = p - prev
    pidx = jnp.take_along_axis(plan.starts, j, axis=-1) + offset
    valid = p < plan.total_postings[:, None]
    docs = index.doc_ids[jnp.where(valid, pidx, 0)]
    contribs = jnp.where(valid, jnp.take_along_axis(plan.contribs, j, axis=-1), 0.0)
    docs = jnp.where(valid, docs, 0)
    n_processed = jnp.minimum(plan.total_postings, rho).astype(jnp.int32)
    return docs, contribs, n_processed


def _accumulate(
    index: ImpactIndex, docs, contribs, scatter_impl: str, max_run: int
) -> jax.Array:
    """Single-query scatter: the batched one at B = 1 (same sums, same bits)."""
    return _accumulate_batched(index, docs[None], contribs[None], scatter_impl, max_run)[0]


def _accumulate_batched(
    index: ImpactIndex,
    docs: jax.Array,
    contribs: jax.Array,
    scatter_impl: str,
    max_run: int,
) -> jax.Array:
    """Batch-aware scatter: ``docs/contribs [B, rho]`` -> ``acc [B, n_docs_pad]``.

    ``"jnp"`` is the plain scatter-add oracle (addend order left to the
    backend). ``"sort"`` and ``"pallas"`` first fold each document's
    postings into one value in the canonical order of
    :func:`repro.kernels.common.canonical_doc_sums` (stable doc sort, then a
    segmented doubling scan bounded by ``max_run``, the query width), so the
    accumulator they build — by XLA scatter or by the one-hot kernel — is
    the same bits on every backend, and equal to the fused kernel's.
    """
    n_docs_pad = index.doc_terms.shape[0]
    B = docs.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    if scatter_impl == "jnp":
        return jnp.zeros((B, n_docs_pad), jnp.float32).at[rows, docs].add(contribs)
    if scatter_impl == "sort":
        from repro.kernels.common import canonical_doc_sums

        sd, sums = canonical_doc_sums(docs, contribs, max_run)  # one nonzero per doc
        return jnp.zeros((B, n_docs_pad), jnp.float32).at[rows, sd].add(sums)
    if scatter_impl == "pallas":
        from repro.kernels.impact_scatter import ops as scatter_ops

        return scatter_ops.impact_scatter_batched(docs, contribs, n_docs_pad, max_run=max_run)
    raise ValueError(f"unknown scatter_impl {scatter_impl!r}")


def _mask_pad_docs(
    index: ImpactIndex, acc: jax.Array, live_mask: jax.Array | None = None
) -> jax.Array:
    n_docs_pad = acc.shape[-1]
    live = jnp.arange(n_docs_pad, dtype=jnp.int32) < index.n_docs
    if live_mask is not None:
        live = live & (live_mask != 0)
    return jnp.where(live, acc, -jnp.inf)


def _fused_scatter_topk_batched(
    index: ImpactIndex,
    docs: jax.Array,
    contribs: jax.Array,
    k: int,
    max_run: int,
    live_mask: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter + pad-mask + top-k in ONE kernel: HBM sees only candidates."""
    from repro.kernels.impact_scatter_topk import ops as fused_ops

    n_docs_pad = index.doc_terms.shape[0]
    return fused_ops.impact_scatter_topk_batched(
        docs, contribs, n_docs_pad, k, n_live=index.n_docs, live=live_mask,
        max_run=max_run,
    )


# The full static surface of the batched engine: everything here forks the
# compile cache. repro.analysis.hot_path keys executables on exactly this
# tuple, so keep it in sync with the jit decorator below (it IS the decorator
# argument).
SAAT_STATICS = ("k", "rho", "max_segs_per_term", "scatter_impl", "fused_topk")


@partial(jax.jit, static_argnames=SAAT_STATICS)
def saat_search(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    *,
    k: int,
    rho: int,
    max_segs_per_term: int,
    scatter_impl: str = "jnp",
    fused_topk: bool = False,
    live_mask: jax.Array | None = None,
) -> SaatResult:
    """Natively batched anytime SAAT top-k. ``q_terms/q_weights: [B, Lq]``.

    ``rho`` is the JASS posting budget. Exact (rank-safe) evaluation = any
    ``rho >= index.n_postings`` (the executor stops at the query's own total).

    The whole batch is one executable per (k, rho, scatter_impl): the planner
    runs one batched argsort, the gather one batched binary search, and the
    scatter one batch-aware kernel launch — no per-query vmapped programs.

    ``fused_topk=True`` replaces scatter-then-select with the fused
    ``impact_scatter_topk`` kernel: the accumulator never materializes in HBM
    and doc ids stay bit-identical to the unfused path. ``scatter_impl`` is
    ignored in that mode (the fused Pallas kernel IS the scatter).

    ``live_mask`` is the index lifecycle's tombstone gate: an i32/bool
    ``[n_docs_pad]`` bitmap (nonzero = live) ANDed into the same candidate
    mask that already demotes pad docs, so tombstoned docs score ``-inf``
    with zero index rebuild. The accumulation itself is untouched — dead
    docs' postings still scatter, they just can never surface — which keeps
    per-doc f32 sums bit-identical to a rebuilt index (posting order
    restricted to any surviving doc is unchanged by other docs' removal).
    """
    if q_terms.ndim != 2:
        raise ValueError(f"expected [B, Lq] query batch, got shape {q_terms.shape}")
    plan = saat_plan(index, q_terms, q_weights, max_segs_per_term)
    docs, contribs, n_proc = _gather_postings_batched(index, plan, rho)
    if fused_topk:
        scores, ids = _fused_scatter_topk_batched(
            index, docs, contribs, k, q_terms.shape[-1], live_mask
        )
    else:
        acc = _accumulate_batched(index, docs, contribs, scatter_impl, q_terms.shape[-1])
        scores, ids = topk(_mask_pad_docs(index, acc, live_mask), k)
    return SaatResult(scores, ids.astype(jnp.int32), n_proc, plan.total_postings)


@partial(jax.jit, static_argnames=("k", "rho", "max_segs_per_term", "scatter_impl"))
def saat_search_vmap(
    index: ImpactIndex,
    q_terms: jax.Array,
    q_weights: jax.Array,
    *,
    k: int,
    rho: int,
    max_segs_per_term: int,
    scatter_impl: str = "jnp",
    live_mask: jax.Array | None = None,
) -> SaatResult:
    """Legacy ``jax.vmap(one-query)`` SAAT — parity oracle / benchmark baseline.

    Semantically identical to :func:`saat_search` (including the tombstone
    ``live_mask``, shared across the batch); kept so the batched engine can be
    validated bit-for-bit on doc ids and raced in
    ``benchmarks/side_batched_vs_vmap.py``.
    """

    def one(qt, qw):
        plan = saat_plan(index, qt, qw, max_segs_per_term)
        docs, contribs, n_proc = _gather_postings(index, plan, rho)
        acc = _accumulate(index, docs, contribs, scatter_impl, qt.shape[-1])
        scores, ids = topk(_mask_pad_docs(index, acc, live_mask), k)
        return SaatResult(scores, ids.astype(jnp.int32), n_proc, plan.total_postings)

    return jax.vmap(one)(q_terms, q_weights)


def posting_reach(index: ImpactIndex) -> np.ndarray:
    """``reach[w - 1]``: the most postings a query of ``w`` distinct terms
    can touch — the sum of the ``w`` longest posting lists (for a stacked
    index, the largest over its shards). Host int64, one entry per term
    slot; reads the per-term posting counts back from the device once.
    """
    counts = np.asarray(jax.device_get(index.term_post_count), dtype=np.int64)
    reach = np.cumsum(-np.sort(-counts, axis=-1), axis=-1)
    return reach.max(axis=0) if reach.ndim == 2 else reach


def exact_rho(index: ImpactIndex) -> int:
    """A rho that guarantees rank-safe evaluation for any query."""
    return index.n_postings
