"""Impact-ordered inverted index — TPU-native JASS analogue.

The CPU JASS index stores, per term, postings grouped into equal-impact
segments ordered by descending impact, compressed with Group-Elias SIMD codes.
The TPU adaptation keeps the *logical* structure (term -> impact segments ->
doc ids) but lays everything out as flat, aligned ``int32``/``float32`` arrays
so query evaluation is pure gather / one-hot-matmul / top-k — no pointer
chasing, no bit unpacking (see DESIGN.md §2 for why compression is dropped).

Structures built here:
  * posting store     ``doc_ids[P]`` ordered by (term, impact desc, doc asc)
  * segment table     ``seg_{term,weight,start,len}[S]`` (term-impact runs)
  * per-term CSR      over segments and over raw postings
  * block-max table   per (term, doc-block) max weight, CSR by term — the
                      structure Block-Max WAND skips with
  * doc-major store   padded ``doc_terms/doc_weights[n_docs, Tmax]`` used by
                      the vectorized block scorer and the exhaustive evaluator

Everything is a registered-dataclass pytree: arrays are leaves, integer
metadata is static (so ``jax.jit`` treats block sizes etc. as compile-time
constants).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import QuantConfig, dequantize, quantize


def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    if x.shape[0] >= n:
        return x[:n]
    pad = np.full((n - x.shape[0],) + x.shape[1:], fill, dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# The doc-major store's width is a whole number of 128-lane tiles. Mosaic
# DMAs a block of the store's rows (the fused DAAT chunk step) only when its
# width is a whole lane tile, and a TPU lays a narrower i32/f32 array out
# padded to 128 lanes in HBM anyway, so the rounding costs the chip no bytes.
DOC_STORE_LANES = 128


def _store_width(max_doc_terms: int) -> int:
    return _round_up(max(max_doc_terms, 1), DOC_STORE_LANES)


# Static (non-array) fields of ImpactIndex. The single source of truth for
# the pytree registration AND for every consumer that splits an index into
# (data, meta) — e.g. repro.serving.sharded — so a new metadata field cannot
# silently be treated as an array leaf somewhere.
META_FIELDS = (
    "n_docs",
    "n_terms",
    "n_blocks",
    "block_size",
    "max_doc_terms",
    "scale",
    "bits",
    "max_segs",
    "max_bm",
)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "doc_ids",
        "seg_term",
        "seg_weight",
        "seg_start",
        "seg_len",
        "term_seg_start",
        "term_seg_count",
        "term_post_count",
        "term_max_weight",
        "bm_block",
        "bm_weight",
        "term_bm_start",
        "term_bm_count",
        "doc_terms",
        "doc_weights",
        "doc_n_terms",
        "doc_weight_sum",
    ],
    meta_fields=list(META_FIELDS),
)
@dataclasses.dataclass(frozen=True)
class ImpactIndex:
    """Impact-ordered index over a corpus of sparse vectors (see module doc)."""

    # --- posting store (impact order) ---
    doc_ids: jax.Array  # i32[P]
    # --- segment table ---
    seg_term: jax.Array  # i32[S]
    seg_weight: jax.Array  # f32[S] dequantized impact
    seg_start: jax.Array  # i32[S]
    seg_len: jax.Array  # i32[S]
    # --- per-term CSR ---
    term_seg_start: jax.Array  # i32[V+1]
    term_seg_count: jax.Array  # i32[V+1]
    term_post_count: jax.Array  # i32[V+1]
    term_max_weight: jax.Array  # f32[V+1]
    # --- block-max structure ---
    bm_block: jax.Array  # i32[NB]
    bm_weight: jax.Array  # f32[NB]
    term_bm_start: jax.Array  # i32[V+1]
    term_bm_count: jax.Array  # i32[V+1]
    # --- doc-major store ---
    doc_terms: jax.Array  # i32[n_docs_pad, Tmax] (pad slot = V)
    doc_weights: jax.Array  # f32[n_docs_pad, Tmax]
    doc_n_terms: jax.Array  # i32[n_docs_pad]
    doc_weight_sum: jax.Array  # f32[n_docs_pad] quantized-impact sum (overflow analysis)
    # --- static metadata ---
    n_docs: int
    n_terms: int
    n_blocks: int
    block_size: int
    max_doc_terms: int
    scale: float
    bits: int
    # Largest per-term segment count, computed at build time. Static plan
    # bound for SAAT; 0 = unknown (abstract/hand-rolled indexes), in which
    # case ``max_segments_per_term`` falls back to a device sync.
    max_segs: int = 0
    # Largest per-term block-max list length, computed at build time. Static
    # bound for the DAAT block-upper-bound gather; 0 = unknown, in which case
    # ``max_blocks_per_term`` falls back to a device sync.
    max_bm: int = 0

    @property
    def n_postings(self) -> int:
        return int(self.doc_ids.shape[0])

    @property
    def n_segments(self) -> int:
        return int(self.seg_term.shape[0])

    def nbytes(self) -> int:
        """Uncompressed index size (posting store + tables), bytes."""
        total = 0
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if hasattr(v, "nbytes"):
                total += int(v.nbytes)
        return total

    def posting_store_nbytes(self) -> int:
        """Size of the inverted-file part only (Table 1 'Index Size' analogue)."""
        parts = [
            self.doc_ids,
            self.seg_term,
            self.seg_weight,
            self.seg_start,
            self.seg_len,
            self.bm_block,
            self.bm_weight,
        ]
        return int(sum(p.nbytes for p in parts))


def build_impact_index(
    doc_idx: np.ndarray,
    term_idx: np.ndarray,
    weights: np.ndarray,
    n_docs: int,
    n_terms: int,
    *,
    quant: QuantConfig = QuantConfig(bits=8),
    block_size: int = 128,
    pad_postings_to: int = 128,
    max_doc_terms: int | None = None,
    quant_max_weight: float | None = None,
) -> ImpactIndex:
    """Build an :class:`ImpactIndex` from COO postings (host-side, numpy).

    Args:
      doc_idx/term_idx/weights: parallel COO arrays, one entry per posting
        (one (doc, term) pair with positive weight).
      n_docs, n_terms: corpus dimensions.
      quant: impact quantization config.
      block_size: document-block size for the block-max (BMW) structure.
      pad_postings_to: pad the posting store to this multiple (TPU alignment).
      max_doc_terms: longest document the doc-major store keeps (defaults
        to the longest doc; longer ones are truncated). The store's width is
        this rounded up to a multiple of ``DOC_STORE_LANES`` slots.
    """
    doc_idx = np.asarray(doc_idx, dtype=np.int64)
    term_idx = np.asarray(term_idx, dtype=np.int64)
    weights = np.asarray(weights, dtype=np.float64)
    keep = weights > 0
    doc_idx, term_idx, weights = doc_idx[keep], term_idx[keep], weights[keep]
    if doc_idx.size == 0:
        # Degenerate but legal: a shard whose doc range holds no postings
        # (short final shard of an uneven split, aggressively filtered
        # corpus). Every CSR count is zero, so the engines touch nothing —
        # but the posting/segment/block-max stores still get padded rows so
        # no zero-length array ever reaches a jitted gather.
        _, scale = quantize(weights, quant, max_weight=quant_max_weight)
        max_doc_terms = _store_width(max_doc_terms or 1)
        n_docs_pad = _round_up(max(n_docs, 1), block_size)
        zc = np.zeros(n_terms + 1, dtype=np.int32)
        return ImpactIndex(
            doc_ids=jnp.zeros(max(pad_postings_to, 1), dtype=jnp.int32),
            seg_term=jnp.full(1, n_terms, dtype=jnp.int32),
            seg_weight=jnp.zeros(1, dtype=jnp.float32),
            seg_start=jnp.zeros(1, dtype=jnp.int32),
            seg_len=jnp.zeros(1, dtype=jnp.int32),
            term_seg_start=jnp.asarray(zc),
            term_seg_count=jnp.asarray(zc),
            term_post_count=jnp.asarray(zc),
            term_max_weight=jnp.zeros(n_terms + 1, dtype=jnp.float32),
            bm_block=jnp.zeros(1, dtype=jnp.int32),
            bm_weight=jnp.zeros(1, dtype=jnp.float32),
            term_bm_start=jnp.asarray(zc),
            term_bm_count=jnp.asarray(zc),
            doc_terms=jnp.full((n_docs_pad, max_doc_terms), n_terms, dtype=jnp.int32),
            doc_weights=jnp.zeros((n_docs_pad, max_doc_terms), dtype=jnp.float32),
            doc_n_terms=jnp.zeros(n_docs_pad, dtype=jnp.int32),
            doc_weight_sum=jnp.zeros(n_docs_pad, dtype=jnp.float32),
            n_docs=int(n_docs),
            n_terms=int(n_terms),
            n_blocks=int(n_docs_pad // block_size),
            block_size=int(block_size),
            max_doc_terms=int(max_doc_terms),
            scale=float(scale),
            bits=int(quant.bits),
            max_segs=0,
            max_bm=0,
        )

    # -- deduplicate (doc, term) pairs by summing weights (bag-of-words) --
    key = doc_idx * n_terms + term_idx
    order = np.argsort(key, kind="stable")
    key, doc_idx, term_idx, weights = key[order], doc_idx[order], term_idx[order], weights[order]
    uk, inv = np.unique(key, return_inverse=True)
    if uk.size != key.size:
        w = np.zeros(uk.size, dtype=np.float64)
        np.add.at(w, inv, weights)
        doc_idx = (uk // n_terms).astype(np.int64)
        term_idx = (uk % n_terms).astype(np.int64)
        weights = w

    # -- quantize to impacts (a caller-supplied max keeps SHARDED indexes on
    # one shared impact grid so cross-shard score merges are exact) --
    q, scale = quantize(weights, quant, max_weight=quant_max_weight)
    deq = dequantize(q, scale, quant).astype(np.float32)

    # -- posting order: (term asc, impact desc, doc asc) --
    order = np.lexsort((doc_idx, -q, term_idx))
    t_s, q_s, d_s, w_s = term_idx[order], q[order], doc_idx[order], deq[order]
    P = t_s.size

    # -- segment runs of equal (term, impact) --
    seg_break = np.empty(P, dtype=bool)
    seg_break[0] = True
    seg_break[1:] = (t_s[1:] != t_s[:-1]) | (q_s[1:] != q_s[:-1])
    seg_start = np.flatnonzero(seg_break)
    seg_end = np.append(seg_start[1:], P)
    seg_len = (seg_end - seg_start).astype(np.int32)
    seg_term = t_s[seg_start].astype(np.int32)
    seg_weight = w_s[seg_start].astype(np.float32)
    S = seg_start.size

    # -- per-term CSR over segments / postings (V+1 rows: last = pad slot) --
    term_seg_count = np.zeros(n_terms + 1, dtype=np.int32)
    np.add.at(term_seg_count, seg_term, 1)
    term_seg_start = np.zeros(n_terms + 1, dtype=np.int32)
    term_seg_start[1:] = np.cumsum(term_seg_count)[:-1]
    term_post_count = np.zeros(n_terms + 1, dtype=np.int32)
    np.add.at(term_post_count, t_s.astype(np.int64), 1)
    term_max_weight = np.zeros(n_terms + 1, dtype=np.float32)
    np.maximum.at(term_max_weight, t_s.astype(np.int64), w_s)

    # -- block-max: per (term, block) max dequantized weight --
    n_blocks = _round_up(n_docs, block_size) // block_size
    blk = (d_s // block_size).astype(np.int64)
    tb_key = t_s * n_blocks + blk
    ub_key, ub_inv = np.unique(tb_key, return_inverse=True)
    bm_weight = np.zeros(ub_key.size, dtype=np.float32)
    np.maximum.at(bm_weight, ub_inv, w_s)
    bm_term = (ub_key // n_blocks).astype(np.int64)
    bm_block = (ub_key % n_blocks).astype(np.int32)
    term_bm_count = np.zeros(n_terms + 1, dtype=np.int32)
    np.add.at(term_bm_count, bm_term, 1)
    term_bm_start = np.zeros(n_terms + 1, dtype=np.int32)
    term_bm_start[1:] = np.cumsum(term_bm_count)[:-1]

    # -- doc-major store --
    d_order = np.lexsort((t_s, d_s))
    dd, tt, ww, qq = d_s[d_order], t_s[d_order], w_s[d_order], q_s[d_order]
    doc_n = np.zeros(n_docs, dtype=np.int32)
    np.add.at(doc_n, dd, 1)
    if max_doc_terms is None:
        max_doc_terms = int(doc_n.max())
    max_doc_terms = max(1, max_doc_terms)
    width = _store_width(max_doc_terms)
    n_docs_pad = _round_up(max(n_docs, 1), block_size)
    doc_terms = np.full((n_docs_pad, width), n_terms, dtype=np.int32)
    doc_weights = np.zeros((n_docs_pad, width), dtype=np.float32)
    # position of each posting within its doc
    doc_offsets = np.zeros(n_docs + 1, dtype=np.int64)
    doc_offsets[1:] = np.cumsum(doc_n)
    within = np.arange(dd.size, dtype=np.int64) - doc_offsets[dd]
    ok = within < max_doc_terms  # truncate over-long docs (counted, rare)
    doc_terms[dd[ok], within[ok]] = tt[ok]
    doc_weights[dd[ok], within[ok]] = ww[ok]
    doc_weight_sum = np.zeros(n_docs_pad, dtype=np.float32)
    np.add.at(doc_weight_sum, dd, qq.astype(np.float32))

    # -- pad posting store --
    P_pad = _round_up(P, pad_postings_to)
    doc_ids_arr = _pad_to(d_s.astype(np.int32), P_pad, 0)

    return ImpactIndex(
        doc_ids=jnp.asarray(doc_ids_arr),
        seg_term=jnp.asarray(seg_term),
        seg_weight=jnp.asarray(seg_weight),
        seg_start=jnp.asarray(seg_start.astype(np.int32)),
        seg_len=jnp.asarray(seg_len),
        term_seg_start=jnp.asarray(term_seg_start),
        term_seg_count=jnp.asarray(term_seg_count),
        term_post_count=jnp.asarray(term_post_count),
        term_max_weight=jnp.asarray(term_max_weight),
        bm_block=jnp.asarray(bm_block),
        bm_weight=jnp.asarray(bm_weight),
        term_bm_start=jnp.asarray(term_bm_start),
        term_bm_count=jnp.asarray(term_bm_count),
        doc_terms=jnp.asarray(doc_terms),
        doc_weights=jnp.asarray(doc_weights),
        doc_n_terms=jnp.asarray(_pad_to(doc_n, n_docs_pad, 0)),
        doc_weight_sum=jnp.asarray(doc_weight_sum),
        n_docs=int(n_docs),
        n_terms=int(n_terms),
        n_blocks=int(n_blocks),
        block_size=int(block_size),
        max_doc_terms=int(width),
        scale=float(scale),
        bits=int(quant.bits),
        max_segs=int(term_seg_count.max()),
        max_bm=int(term_bm_count.max()),
    )


def extract_doc_coo(
    index: ImpactIndex, live: np.ndarray | None = None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Recover host-side COO postings from the doc-major store.

    The index-lifecycle compactor's read path: returns
    ``(doc_idx, term_idx, weights)`` over the real (non-pad) documents, with
    weights on the index's dequantized impact grid. ``live`` (optional bool/i32
    ``[>= n_docs]`` bitmap; nonzero = live) drops tombstoned documents
    entirely.

    Round-trip caveat: the doc-major store truncates documents longer than
    ``max_doc_terms`` at build time, so extraction only recovers what the
    store kept. Lifecycle rebuilds that must be lossless should build with
    the default ``max_doc_terms=None`` (no truncation).
    """
    dt = np.asarray(jax.device_get(index.doc_terms))[: index.n_docs]
    dw = np.asarray(jax.device_get(index.doc_weights))[: index.n_docs]
    keep = (dt != index.n_terms) & (dw > 0)
    if live is not None:
        keep &= np.asarray(live)[: index.n_docs].astype(bool)[:, None]
    d, slot = np.nonzero(keep)
    return d.astype(np.int64), dt[d, slot].astype(np.int64), dw[d, slot].astype(np.float64)


def query_vector(index: ImpactIndex, q_terms: jax.Array, q_weights: jax.Array) -> jax.Array:
    """Dense query vector over V+1 slots (pad slot stays 0)."""
    qvec = jnp.zeros(index.n_terms + 1, dtype=jnp.float32)
    safe = jnp.where(q_weights > 0, q_terms, index.n_terms)
    return qvec.at[safe].add(q_weights.astype(jnp.float32)).at[index.n_terms].set(0.0)


def pad_queries(
    term_lists: list[np.ndarray],
    weight_lists: list[np.ndarray],
    max_q_terms: int,
    n_terms: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad ragged host-side queries to ``[B, max_q_terms]`` arrays."""
    B = len(term_lists)
    qt = np.full((B, max_q_terms), n_terms, dtype=np.int32)
    qw = np.zeros((B, max_q_terms), dtype=np.float32)
    truncated = 0
    for i, (t, w) in enumerate(zip(term_lists, weight_lists)):
        t = np.asarray(t, dtype=np.int32)
        w = np.asarray(w, dtype=np.float32)
        if t.size > max_q_terms:  # keep the highest-weight terms
            top = np.argsort(-w)[:max_q_terms]
            t, w = t[top], w[top]
            truncated += 1
        qt[i, : t.size] = t
        qw[i, : w.size] = w
    return qt, qw
