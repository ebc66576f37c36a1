"""The benchmark's own copy of the corpus, treatment and BM25 generators.

A frozen yardstick: the concept-latent synthetic corpus (vocabulary
mismatch between queries and their one relevant passage), the six
retrieval-model treatments, and BM25 weights (k1=0.82, b=0.68), after the
program's ``repro.data.synthetic``, ``repro.models.treatments`` and
``repro.models.bm25``, with the per-passage loop drawn as whole arrays (the
same distributions, other draws). A configuration sets the corpus
parameters that give its source's widths (terms per passage and per
query). The program's copies may change; this one does not, so every later
run of the benchmark serves the same collection and queries for the same
seed. ``perfbench/tests`` pins its output by a checksum of its own.

Host-side numpy only; imports nothing of the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class CorpusConfig:
    n_docs: int = 20000
    n_queries: int = 200
    n_concepts: int = 2000
    terms_per_concept: int = 24
    n_stopwords: int = 64
    concepts_per_doc: float = 6.0  # Poisson mean (>=1 enforced)
    terms_per_doc_concept: float = 4.0  # surface terms drawn per (doc, concept)
    stopwords_per_doc: float = 6.0
    concepts_per_query: float = 2.0
    terms_per_query_concept: float = 1.3
    stopwords_per_query: float = 0.8
    concept_zipf: float = 1.1  # popularity skew across concepts
    term_zipf: float = 1.2  # skew across surface forms within a concept
    max_tf: int = 8
    seed: int = 0

    @property
    def n_surface_terms(self) -> int:
        return self.n_stopwords + self.n_concepts * self.terms_per_concept


@dataclasses.dataclass(frozen=True)
class Corpus:
    """Base (pre-treatment) corpus: docs/queries over the surface vocabulary."""

    config: CorpusConfig
    # documents, CSR over a ragged (term, tf) representation
    doc_offsets: np.ndarray  # i64[n_docs + 1]
    doc_terms: np.ndarray  # i32[nnz] surface term ids
    doc_tfs: np.ndarray  # i32[nnz]
    doc_concepts: list  # list of i32 arrays (latent, used by expansion models)
    doc_concept_strengths: list  # list of f32 arrays: how central each concept is
    # queries (ragged)
    query_terms: list  # list of i32 arrays
    query_concepts: list  # list of i32 arrays (latent)
    qrels: np.ndarray  # i32[n_queries] focus (relevant) doc per query

    @property
    def n_docs(self) -> int:
        return len(self.doc_offsets) - 1

    @property
    def n_queries(self) -> int:
        return len(self.query_terms)

    def doc(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        lo, hi = self.doc_offsets[i], self.doc_offsets[i + 1]
        return self.doc_terms[lo:hi], self.doc_tfs[lo:hi]

    def coo(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(doc_idx, term_idx, tf) postings."""
        doc_idx = np.repeat(
            np.arange(self.n_docs, dtype=np.int64), np.diff(self.doc_offsets)
        )
        return doc_idx, self.doc_terms.astype(np.int64), self.doc_tfs.astype(np.float64)


def _zipf_probs(n: int, alpha: float) -> np.ndarray:
    p = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), alpha)
    return p / p.sum()


def _sample_counts(rng, mean: float, n: int, minimum: int = 0) -> np.ndarray:
    return np.maximum(rng.poisson(mean, n), minimum)


def _draw(rng, p: np.ndarray, n: int) -> np.ndarray:
    """``n`` independent draws of an index with probabilities ``p``."""
    cdf = np.cumsum(p)
    return np.minimum(np.searchsorted(cdf / cdf[-1], rng.random(n), side="right"), p.size - 1)


def _distinct_draws(rng, p: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Row ``i`` gets ``sizes[i]`` distinct indices, drawn one after another
    with probabilities ``p`` renormalized over what is left: numpy's
    ``choice(replace=False, p=p)``, taken as draws with replacement whose
    repeats are skipped. Returns the rows' indices concatenated, each row in
    the order drawn."""
    n = sizes.size
    tries = 4 * sizes + 8
    row = np.repeat(np.arange(n), tries)
    cand = _draw(rng, p, row.size)
    _, first_at = np.unique(row * p.size + cand, return_index=True)
    first = np.zeros(row.size, dtype=bool)
    first[first_at] = True
    seen = np.cumsum(first)
    row_start = np.concatenate([[0], np.cumsum(tries)[:-1]])
    rank = seen - 1 - (seen - first)[row_start][row]
    keep = first & (rank < sizes[row])
    got = np.bincount(row[keep], minlength=n)
    rows = np.split(cand[keep], np.cumsum(got)[:-1])
    for i in np.flatnonzero(got < sizes):  # rare: too many repeats
        rows[i] = rng.choice(p.size, size=sizes[i], replace=False, p=p)
    return np.concatenate(rows) if rows else np.zeros(0, np.int64)


def generate_corpus(cfg: CorpusConfig) -> Corpus:
    """Generate the base corpus (host-side numpy; offline data prep)."""
    rng = np.random.default_rng(cfg.seed)
    concept_p = _zipf_probs(cfg.n_concepts, cfg.concept_zipf)
    term_p = _zipf_probs(cfg.terms_per_concept, cfg.term_zipf)

    def concept_term(concepts: np.ndarray, forms: np.ndarray) -> np.ndarray:
        return cfg.n_stopwords + concepts * cfg.terms_per_concept + forms

    # ---------------- documents ----------------
    n_con = _sample_counts(rng, cfg.concepts_per_doc, cfg.n_docs, minimum=1)
    cs = _distinct_draws(rng, concept_p, n_con)
    con_doc = np.repeat(np.arange(cfg.n_docs, dtype=np.int64), n_con)
    con_pos = np.arange(cs.size) - np.repeat(np.cumsum(n_con) - n_con, n_con)
    # concept centrality: a doc is "about" its first concepts (geometric
    # decay); central concepts get more surface terms and higher tfs, and
    # queries about this doc target its central concepts — the relevance
    # signal learned weights can exploit but BM25 only sees through tf.
    strength = 0.6 ** con_pos.astype(np.float64)
    k = np.maximum(rng.poisson(cfg.terms_per_doc_concept * strength), 1)
    reps = np.repeat(cs, k)
    forms = _draw(rng, term_p, reps.size)
    n_stop = rng.poisson(cfg.stopwords_per_doc, cfg.n_docs)
    doc = np.concatenate([np.repeat(con_doc, k),
                          np.repeat(np.arange(cfg.n_docs, dtype=np.int64), n_stop)])
    terms = np.concatenate([concept_term(reps, forms),
                            rng.integers(0, cfg.n_stopwords, int(n_stop.sum()))])
    # heavy-tailed tf (centrality-boosted): BM25's within-term weight
    # variance (and hence block-max skipping headroom) comes from here
    str_all = np.concatenate([np.repeat(strength, k), np.ones(int(n_stop.sum()))])
    tfs = (1 + np.floor(rng.exponential(0.9 + 2.0 * str_all))).clip(1, cfg.max_tf)
    # merge duplicate surface terms of a doc
    key, inv = np.unique(doc * cfg.n_surface_terms + terms, return_inverse=True)
    tf = np.bincount(inv, weights=tfs, minlength=key.size)
    doc_offsets = np.zeros(cfg.n_docs + 1, dtype=np.int64)
    doc_offsets[1:] = np.cumsum(np.bincount(key // cfg.n_surface_terms, minlength=cfg.n_docs))
    doc_terms = (key % cfg.n_surface_terms).astype(np.int32)
    doc_tfs = tf.clip(1, cfg.max_tf * 4).astype(np.int32)
    splits = np.cumsum(n_con)[:-1]
    doc_concepts = np.split(cs.astype(np.int32), splits)
    doc_strengths = np.split(strength.astype(np.float32), splits)

    # ---------------- queries ----------------
    query_terms: list[np.ndarray] = []
    query_concepts: list[np.ndarray] = []
    qrels = np.zeros(cfg.n_queries, dtype=np.int32)
    for qi in range(cfg.n_queries):
        d = int(rng.integers(0, cfg.n_docs))
        qrels[qi] = d
        m = min(max(int(rng.poisson(cfg.concepts_per_query)), 1), doc_concepts[d].size)
        # queries target the doc's central concepts
        p = doc_strengths[d].astype(np.float64) ** 2
        p = p / p.sum()
        cs = rng.choice(doc_concepts[d], size=m, replace=False, p=p)
        query_concepts.append(cs.astype(np.int32))
        k = _sample_counts(rng, cfg.terms_per_query_concept, m, minimum=1)
        reps = np.repeat(cs, k)
        # independent surface-form resampling => vocabulary mismatch
        forms = rng.choice(cfg.terms_per_concept, size=reps.size, p=term_p)
        terms = concept_term(reps, forms)
        n_stop = max(int(rng.poisson(cfg.stopwords_per_query)), 0)
        stops = rng.integers(0, cfg.n_stopwords, n_stop)
        terms = np.unique(np.concatenate([terms, stops]))
        query_terms.append(terms.astype(np.int32))

    return Corpus(
        config=cfg,
        doc_offsets=doc_offsets,
        doc_terms=doc_terms,
        doc_tfs=doc_tfs,
        doc_concepts=doc_concepts,
        doc_concept_strengths=doc_strengths,
        query_terms=query_terms,
        query_concepts=query_concepts,
        qrels=qrels,
    )


# ---- BM25 (paper baseline rows; Pyserini parameters) ----


@dataclasses.dataclass(frozen=True)
class BM25Params:
    k1: float = 0.82
    b: float = 0.68


def bm25_weights(
    doc_idx: np.ndarray,
    term_idx: np.ndarray,
    tf: np.ndarray,
    n_docs: int,
    n_terms: int,
    params: BM25Params = BM25Params(),
) -> np.ndarray:
    """Per-posting BM25 weight w_{d,t} for COO postings."""
    doc_idx = np.asarray(doc_idx, dtype=np.int64)
    term_idx = np.asarray(term_idx, dtype=np.int64)
    tf = np.asarray(tf, dtype=np.float64)
    # document lengths (in tokens, tf-weighted) and df
    dl = np.bincount(doc_idx, weights=tf, minlength=n_docs)
    avdl = dl.mean() if n_docs else 1.0
    df = np.bincount(term_idx, minlength=n_terms).astype(np.float64)
    idf = np.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))
    k1, b = params.k1, params.b
    denom = tf + k1 * (1.0 - b + b * (dl[doc_idx] / max(avdl, 1e-9)))
    return (idf[term_idx] * tf * (k1 + 1.0) / denom).astype(np.float64)


# ---- the six treatments (paper section 3.1, Tables 1 and 2) ----

MODEL_NAMES = (
    "bm25",
    "bm25-t5",
    "deepimpact",
    "unicoil-t5",
    "unicoil-tilde",
    "spladev2",
)


@dataclasses.dataclass(frozen=True)
class ModelProfile:
    """Treatment knobs + the paper's Table 2 targets (for reporting)."""

    name: str
    doc_expansion_forms: int  # forms added per doc concept (doc2query/TILDE/MLM)
    query_expansion_forms: int  # forms added per query concept (SPLADE only)
    learned_weights: bool  # transformer-assigned (flat) vs BM25 weights
    query_weights: bool  # learned query-side weights
    subword_frac: float  # 0 = surface vocab; else subword vocab fraction
    subwords_per_term: int  # 1 = plain hash, 2 = split effect (SPLADE)
    stopword_doc_weight: float  # learned weight mass on stopwords in docs
    stopword_query_terms: int  # stopword tokens injected into queries
    weight_flatness: float  # in (0, 1]; higher = flatter ("wackier")
    weight_scale: float  # scales total mass (Table 2 "total terms")
    table2_targets: dict


PROFILES: dict[str, ModelProfile] = {
    "bm25": ModelProfile(
        name="bm25",
        doc_expansion_forms=0,
        query_expansion_forms=0,
        learned_weights=False,
        query_weights=False,
        subword_frac=0.0,
        subwords_per_term=1,
        stopword_doc_weight=0.0,
        stopword_query_terms=0,
        weight_flatness=0.0,
        weight_scale=1.0,
        table2_targets={"doc_unique": 30.1, "q_unique": 5.8, "doc_total": 39.8, "rr10": 0.187},
    ),
    "bm25-t5": ModelProfile(
        name="bm25-t5",
        doc_expansion_forms=4,
        query_expansion_forms=0,
        learned_weights=False,
        query_weights=False,
        subword_frac=0.0,
        subwords_per_term=1,
        stopword_doc_weight=0.0,
        stopword_query_terms=0,
        weight_flatness=0.0,
        weight_scale=1.0,
        table2_targets={"doc_unique": 51.1, "q_unique": 5.8, "doc_total": 224.7, "rr10": 0.277},
    ),
    "deepimpact": ModelProfile(
        name="deepimpact",
        doc_expansion_forms=6,
        query_expansion_forms=0,
        learned_weights=True,
        query_weights=False,
        subword_frac=0.0,
        subwords_per_term=1,
        stopword_doc_weight=0.18,
        stopword_query_terms=0,
        weight_flatness=0.55,
        weight_scale=24.0,
        table2_targets={"doc_unique": 71.1, "q_unique": 4.2, "doc_total": 4010.0, "rr10": 0.325},
    ),
    "unicoil-t5": ModelProfile(
        name="unicoil-t5",
        doc_expansion_forms=6,
        query_expansion_forms=0,
        learned_weights=True,
        query_weights=True,
        subword_frac=1.0,
        subwords_per_term=1,
        stopword_doc_weight=0.22,
        stopword_query_terms=0,
        weight_flatness=0.62,
        weight_scale=30.0,
        table2_targets={"doc_unique": 66.4, "q_unique": 6.6, "doc_total": 5032.3, "rr10": 0.352},
    ),
    "unicoil-tilde": ModelProfile(
        name="unicoil-tilde",
        doc_expansion_forms=11,
        query_expansion_forms=0,
        learned_weights=True,
        query_weights=True,
        subword_frac=1.0,
        subwords_per_term=1,
        stopword_doc_weight=0.22,
        stopword_query_terms=0,
        weight_flatness=0.62,
        weight_scale=30.0,
        table2_targets={"doc_unique": 107.6, "q_unique": 6.5, "doc_total": 8260.8, "rr10": 0.350},
    ),
    "spladev2": ModelProfile(
        name="spladev2",
        doc_expansion_forms=16,
        query_expansion_forms=5,
        learned_weights=True,
        query_weights=True,
        # frac=1.0: SPLADE's BERT vocab is the SAME size as uniCOIL's (paper
        # Table 2: 28131 vs 27678); a shrunken vocab over-collides subwords
        # and was measured to cost ~3 RR@10 points
        subword_frac=1.0,
        subwords_per_term=2,
        stopword_doc_weight=0.35,
        stopword_query_terms=4,
        weight_flatness=0.78,
        weight_scale=36.0,
        table2_targets={"doc_unique": 229.4, "q_unique": 25.0, "doc_total": 10794.8, "rr10": 0.369},
    ),
}


@dataclasses.dataclass(frozen=True)
class EncodedCollection:
    """A (model x corpus) encoding, ready for ``build_impact_index``."""

    name: str
    doc_idx: np.ndarray  # i64[nnz]
    term_idx: np.ndarray  # i64[nnz]
    weights: np.ndarray  # f64[nnz]
    query_terms: list  # list of i32 arrays
    query_weights: list  # list of f32 arrays
    n_terms: int
    profile: ModelProfile

    @property
    def n_postings(self) -> int:
        return int(self.doc_idx.size)


# --------------------------------------------------------------------------
# building blocks
# --------------------------------------------------------------------------


class _StrengthLookup:
    """O(log n) per-posting concept-centrality lookup over (doc, concept)."""

    def __init__(self, corpus: Corpus):
        cfg = corpus.config
        docs = np.repeat(
            np.arange(corpus.n_docs, dtype=np.int64),
            [c.size for c in corpus.doc_concepts],
        )
        cons = np.concatenate(corpus.doc_concepts).astype(np.int64)
        strs = np.concatenate(corpus.doc_concept_strengths).astype(np.float64)
        keys = docs * cfg.n_concepts + cons
        order = np.argsort(keys)
        self._keys = keys[order]
        self._strs = strs[order]
        self._cfg = cfg

    def concept_of(self, term_idx: np.ndarray) -> np.ndarray:
        cfg = self._cfg
        return np.where(
            term_idx >= cfg.n_stopwords,
            (term_idx - cfg.n_stopwords) // cfg.terms_per_concept,
            -1,
        )

    def __call__(self, doc_idx: np.ndarray, term_idx: np.ndarray) -> np.ndarray:
        """Per-posting strength in [0, 1]; stopwords/unknown get 0.1."""
        cfg = self._cfg
        con = self.concept_of(term_idx)
        keys = doc_idx.astype(np.int64) * cfg.n_concepts + con
        pos = np.searchsorted(self._keys, keys).clip(0, self._keys.size - 1)
        hit = (self._keys[pos] == keys) & (con >= 0)
        return np.where(hit, self._strs[pos], 0.1)


def _expand_docs(corpus: Corpus, forms_per_concept: int):
    """doc2query/TILDE/MLM-style document expansion.

    For every (doc, concept) pair, append the concept's ``forms_per_concept``
    most *query-popular* surface forms (what a seq2seq trained on queries
    predicts) with tf=1. Returns extra COO (doc, term, tf) postings.
    """
    cfg = corpus.config
    docs = np.repeat(
        np.arange(corpus.n_docs, dtype=np.int64),
        [c.size for c in corpus.doc_concepts],
    )
    cons = np.concatenate(corpus.doc_concepts).astype(np.int64)
    doc_rep = np.repeat(docs, forms_per_concept)
    con_rep = np.repeat(cons, forms_per_concept)
    form = np.tile(np.arange(forms_per_concept, dtype=np.int64), cons.size)
    terms = cfg.n_stopwords + con_rep * cfg.terms_per_concept + form
    tfs = np.ones(terms.size, dtype=np.float64)
    return doc_rep, terms, tfs


def _learned_weights(
    term_idx: np.ndarray,
    tf: np.ndarray,
    strength: np.ndarray,
    n_stopwords: int,
    profile: ModelProfile,
    rng,
) -> np.ndarray:
    """Transformer-style "wacky" impact weights.

    signal      concept centrality (the relevance signal tf/idf only proxies)
    flat floor  learned weights cluster in a narrow band -> loose block-max
                bounds -> DAAT skipping collapses (paper §4.2)
    stopwords   non-trivial learned mass ("and": 225 in the paper's example)
    """
    tf = np.asarray(tf, dtype=np.float64)
    signal = (0.3 + 0.7 * strength) * (0.75 + 0.25 * np.log1p(tf) / np.log1p(8.0))
    noise = rng.lognormal(0.0, 0.2, term_idx.size)
    flat = profile.weight_flatness
    w = ((1.0 - flat) * signal + flat * (0.55 + 0.2 * rng.random(term_idx.size))) * noise
    stop = term_idx < n_stopwords
    w = np.where(stop, profile.stopword_doc_weight * (0.5 + rng.random(term_idx.size)), w)
    return np.maximum(w, 1e-3) * profile.weight_scale


def _subword_vocab_size(profile: ModelProfile, n_surface: int) -> int:
    return max(2048, int(profile.subword_frac * n_surface))


def _subword_map(terms: np.ndarray, vocab: int, copies: int, n_stopwords: int) -> np.ndarray:
    """Hash surface terms onto a BERT-like subword vocabulary.

    Many-to-one collisions reproduce the paper's subword conflation ("and" vs
    "##rogen"); ``copies=2`` splits a term into two subwords (SPLADE docs).
    Stopwords map to a reserved low range so their identity (and wacky query
    mass) is preserved. Output shape: [copies * len(terms)].
    """
    terms = np.asarray(terms, dtype=np.int64)
    outs = []
    for c in range(copies):
        h = (terms * 2654435761 + 97 + 1013904223 * c) % (vocab - n_stopwords)
        mapped = np.where(terms < n_stopwords, terms, n_stopwords + h)
        outs.append(mapped)
    return np.concatenate(outs)


def _dedup_coo(doc_idx, term_idx, weights, n_terms: int):
    """Merge repeated (doc, term) postings, summing their weights."""
    key = doc_idx.astype(np.int64) * n_terms + term_idx
    uk, inv = np.unique(key, return_inverse=True)
    w = np.bincount(inv, weights=weights, minlength=uk.size)
    return (uk // n_terms).astype(np.int64), (uk % n_terms).astype(np.int64), w


# --------------------------------------------------------------------------
# the treatment itself
# --------------------------------------------------------------------------


def apply_treatment(corpus: Corpus, model: str, seed: int = 0) -> EncodedCollection:
    """Encode the base corpus under one of the six retrieval models."""
    if model not in PROFILES:
        raise ValueError(f"unknown model {model!r}; choose from {MODEL_NAMES}")
    profile = PROFILES[model]
    cfg = corpus.config
    rng = np.random.default_rng(seed * 1009 + list(PROFILES).index(model))
    lookup = _StrengthLookup(corpus)

    doc_idx, term_idx, tf = corpus.coo()
    if profile.doc_expansion_forms > 0:
        ed, et, etf = _expand_docs(corpus, profile.doc_expansion_forms)
        doc_idx = np.concatenate([doc_idx, ed])
        term_idx = np.concatenate([term_idx, et])
        tf = np.concatenate([tf, etf])
        doc_idx, term_idx, tf = _dedup_coo(doc_idx, term_idx, tf, cfg.n_surface_terms)

    # learned weights are computed on the *surface* postings (where concept
    # identity is known), then optionally mapped to subwords
    if profile.learned_weights:
        strength = lookup(doc_idx, term_idx)
        weights = _learned_weights(term_idx, tf, strength, cfg.n_stopwords, profile, rng)
    else:
        weights = None  # BM25 computed after (optional) vocab mapping

    n_terms = cfg.n_surface_terms
    if profile.subword_frac:
        n_terms = _subword_vocab_size(profile, cfg.n_surface_terms)
        copies = profile.subwords_per_term
        mapped = _subword_map(term_idx, n_terms, copies, cfg.n_stopwords)
        doc_idx = np.tile(doc_idx, copies)
        tf = np.tile(tf, copies)
        if weights is not None:
            weights = np.tile(weights / copies, copies)
        term_idx = mapped
        if weights is not None:
            doc_idx, term_idx, weights = _dedup_coo(doc_idx, term_idx, weights, n_terms)
        else:
            doc_idx, term_idx, tf = _dedup_coo(doc_idx, term_idx, tf, n_terms)

    if weights is None:
        weights = bm25_weights(doc_idx, term_idx, tf, corpus.n_docs, n_terms)

    # ---------------- queries ----------------
    q_terms_out, q_weights_out = [], []
    for qi in range(corpus.n_queries):
        terms = corpus.query_terms[qi].astype(np.int64)
        d_focus = int(corpus.qrels[qi])
        cs = corpus.query_concepts[qi].astype(np.int64)
        kind = np.zeros(terms.size, dtype=np.int64)  # 0=content, 1=expansion, 2=stop
        kind[terms < cfg.n_stopwords] = 2
        if profile.query_expansion_forms > 0:  # SPLADE-style query expansion
            reps = np.repeat(cs, profile.query_expansion_forms)
            form = np.tile(np.arange(profile.query_expansion_forms, dtype=np.int64), cs.size)
            exp = cfg.n_stopwords + reps * cfg.terms_per_concept + form
            terms = np.concatenate([terms, exp])
            kind = np.concatenate([kind, np.ones(exp.size, dtype=np.int64)])
        if profile.stopword_query_terms > 0:
            stops = rng.integers(0, cfg.n_stopwords, profile.stopword_query_terms)
            terms = np.concatenate([terms, stops])
            kind = np.concatenate([kind, np.full(stops.size, 2, dtype=np.int64)])
        if profile.query_weights:
            # learned query weights track term informativeness for this query
            strength = lookup(np.full(terms.size, d_focus, dtype=np.int64), terms)
            base = 0.25 + 0.75 * strength
            base = np.where(kind == 1, 0.6 * base, base)  # expansion discount
            base = np.where(kind == 2, 0.12, base)  # stopword down-weight
            qw = base * (0.85 + 0.3 * rng.random(terms.size)) * profile.weight_scale * 0.6
        else:
            qw = np.ones(terms.size, dtype=np.float64)
        if profile.subword_frac:
            terms = _subword_map(terms, n_terms, 1, cfg.n_stopwords)
        # dedup (max weight wins, SPLADE max-pool semantics)
        ut = np.unique(terms)
        w = np.zeros(ut.size, dtype=np.float64)
        pos = np.searchsorted(ut, terms)
        np.maximum.at(w, pos, qw)
        q_terms_out.append(ut.astype(np.int32))
        q_weights_out.append(w.astype(np.float32))

    return EncodedCollection(
        name=model,
        doc_idx=doc_idx,
        term_idx=term_idx,
        weights=weights,
        query_terms=q_terms_out,
        query_weights=q_weights_out,
        n_terms=int(n_terms),
        profile=profile,
    )
