"""The plain reference that decides ``correct``: numpy only.

It re-does the serving semantics from the encoded collection that the
benchmark generated itself, and takes nothing that the program made:

* impacts: each posting's weight quantized on one global scale to
  ``ceil(min(w / max_w, 1) * 255)`` in ``[1, 255]``, and scored as that impact's
  float32 value ``impact * max_w / 255``, where ``max_w`` is the grid's top
  that the configuration states (or the largest weight where it states
  none);
* a term's postings in equal-impact segments, highest impact first, docs
  ascending inside a segment;
* a query takes its segments in JASS order: contribution (impact value times
  query weight, float32) descending, ties in query-slot then segment order.
  A budget of ``rho`` postings cuts that order (anytime SAAT); no budget
  scores every posting (exhaustive, which exact DAAT has to return);
* each document's score is the float64 sum of the contributions it got.

The control is the same postings summed in bfloat16, the precision below
the float32 the program scores in: :meth:`Reference.scores` with
``control=True``.

:class:`Gaps` holds served answers against the reference's scores and
keeps the numbers that decide ``correct``.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

IMPACT_LEVELS = 255  # 8-bit impacts; 0 is "no posting"


class Reference:
    """Per-term impact-ordered postings of the collection, on the host.

    ``terms`` (optional) keeps only the postings of those terms: each term's
    posting count is still taken over every posting. ``max_weight``
    (optional) is the top of the impact grid. The generator
    gives one posting per (doc, term), which this class relies on.
    """

    def __init__(self, doc_idx, term_idx, weights, n_docs: int, n_terms: int, terms=None,
                 max_weight: float | None = None):
        doc = np.asarray(doc_idx, dtype=np.int64)
        term = np.asarray(term_idx, dtype=np.int64)
        w = np.asarray(weights, dtype=np.float64)
        keep = w > 0
        if max_weight is None:
            max_weight = float(w[keep].max()) if keep.any() else 1.0
        max_w = max(max_weight, 1e-12)
        self.term_postings = np.bincount(term[keep], minlength=n_terms + 1)
        if terms is not None:
            keep &= np.isin(term, np.asarray(terms, dtype=np.int64))
        doc, term, w = doc[keep], term[keep], w[keep]
        q = np.clip(np.ceil(np.clip(w / max_w, 0.0, 1.0) * IMPACT_LEVELS), 1, IMPACT_LEVELS)
        q = q.astype(np.int32)
        order = np.lexsort((doc, -q, term))
        doc, term, q = doc[order], term[order], q[order]
        brk = np.ones(doc.size, dtype=bool)
        brk[1:] = (term[1:] != term[:-1]) | (q[1:] != q[:-1])
        self.seg_start = np.flatnonzero(brk)
        self.seg_len = np.diff(np.append(self.seg_start, doc.size))
        seg_term = term[self.seg_start]
        self.seg_value = (q[self.seg_start].astype(np.float64) * (max_w / IMPACT_LEVELS)).astype(
            np.float32
        )
        self.term_seg_count = np.bincount(seg_term, minlength=n_terms + 1)
        self.term_seg_start = np.concatenate([[0], np.cumsum(self.term_seg_count)[:-1]])
        self.doc = doc
        self.n_docs, self.n_terms = int(n_docs), int(n_terms)

    def total_postings(self, qt, qw) -> int:
        """How many postings the query's live terms hold (no budget)."""
        qt, qw = np.asarray(qt), np.asarray(qw)
        live = (qt != self.n_terms) & (qw > 0)
        return int(self.term_postings[qt[live]].sum())

    def _postings(self, qt, qw, rho):
        """(docs, contributions) of one query, in JASS order, cut at ``rho``."""
        segs, contrib = [], []
        for t, w in zip(np.asarray(qt), np.asarray(qw, dtype=np.float32)):
            if t == self.n_terms or w <= 0:
                continue
            s0, c = self.term_seg_start[t], self.term_seg_count[t]
            segs.append(np.arange(s0, s0 + c))
            contrib.append(self.seg_value[s0 : s0 + c] * np.float32(w))
        segs = np.concatenate(segs) if segs else np.zeros(0, np.int64)
        if segs.size == 0:  # no live term has postings
            return np.zeros(0, np.int64), np.zeros(0, np.float32)
        contrib = np.concatenate(contrib)
        order = np.argsort(-contrib, kind="stable")
        segs, contrib = segs[order], contrib[order]
        take = self.seg_len[segs]
        if rho is not None:
            before = np.cumsum(take) - take
            take = np.clip(rho - before, 0, take)
        ends = np.cumsum(take)
        pos = np.arange(ends[-1]) - np.repeat(ends - take, take)
        docs = self.doc[np.repeat(self.seg_start[segs], take) + pos]
        return docs, np.repeat(contrib, take)

    def scores(self, qt, qw, rho: int | None = None, control: bool = False) -> np.ndarray:
        """f64[n_docs]: one query's score for every document.

        ``control=True`` rounds each contribution and each running sum to
        bfloat16, in the order the postings come.
        """
        docs, contrib = self._postings(qt, qw, rho)
        if not control:
            return np.bincount(docs, contrib.astype(np.float64), minlength=self.n_docs)
        return _bf16_sums(docs, contrib, self.n_docs)


def _bf16_sums(docs, contrib, n_docs: int) -> np.ndarray:
    bf16 = ml_dtypes.bfloat16
    c = contrib.astype(bf16).astype(np.float32)
    order = np.argsort(docs, kind="stable")  # keeps the posting order per doc
    docs, c = docs[order], c[order]
    first = np.ones(docs.size, dtype=bool)
    first[1:] = docs[1:] != docs[:-1]
    starts = np.flatnonzero(first)
    rank = np.arange(docs.size) - np.repeat(starts, np.diff(np.append(starts, docs.size)))
    acc = np.zeros(n_docs, dtype=np.float32)
    for j in range(int(rank.max()) + 1 if rank.size else 0):
        sel = rank == j  # at most one posting per doc at each rank
        acc[docs[sel]] = (acc[docs[sel]] + c[sel]).astype(bf16).astype(np.float32)
    return acc.astype(np.float64)


def top_k(scores: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """The k best (scores, ids) of one row, equal scores lowest id first."""
    cand = np.argpartition(-scores, k - 1)[:k]
    cut = scores[cand].min()
    cand = np.flatnonzero(scores >= cut)  # every doc tied at the cut
    ids = cand[np.lexsort((cand, -scores[cand]))][:k]
    return scores[ids], ids


@dataclasses.dataclass
class Gaps:
    """The comparison of served answers with the reference, over many rows.

    ``topk_gap``: largest distance between a served score and the
    reference's score at the same rank. ``id_gap``: largest distance
    between a served score and the reference's score of the doc served with
    it. Both relative to the row's best reference score. ``bad_ids``:
    served ids outside the collection or repeated within a row.
    """

    topk_gap: float = 0.0
    id_gap: float = 0.0
    bad_ids: int = 0
    rows: int = 0

    def add(self, ref: np.ndarray, scores: np.ndarray, ids: np.ndarray) -> None:
        k = scores.shape[0]
        top = -np.sort(np.partition(-ref, k - 1)[:k])
        scale = max(float(top[0]), 1e-30)
        s = np.asarray(scores, dtype=np.float64)
        live = np.isfinite(s)  # -inf: fewer than k docs reached, stands for 0
        gap = np.max(np.abs(np.where(live, s, 0.0) - top)) / scale
        self.topk_gap = max(self.topk_gap, float(gap))
        got = np.asarray(ids, dtype=np.int64)[live]
        valid = (got >= 0) & (got < ref.shape[0])
        self.bad_ids += int((~valid).sum()) + (got.size - np.unique(got).size)
        if valid.any():
            gap = np.max(np.abs(ref[got[valid]] - s[live][valid])) / scale
            self.id_gap = max(self.id_gap, float(gap))
        self.rows += 1
