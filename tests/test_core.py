"""Core retrieval invariants: quantization, index, SAAT/DAAT/exhaustive."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    QuantConfig,
    blockmax_search,
    build_impact_index,
    dequantize,
    exact_rho,
    exhaustive_search,
    quantization_error,
    quantize,
    saat_search,
)
from repro.core.daat import max_blocks_per_term
from repro.core.saat import max_segments_per_term
from repro.core.topk import merge_topk, tiled_topk, topk
from repro.metrics.ir_metrics import mrr_at_k, rank_overlap


# ---------------------------------------------------------------- quantization


def test_quantize_roundtrip_bound():
    rng = np.random.default_rng(0)
    w = rng.gamma(2.0, 3.0, 10000)
    for bits in (4, 8, 12):
        err = quantization_error(w, QuantConfig(bits=bits))
        assert err["bound_ok"], err


def test_quantize_zero_reserved():
    q, _ = quantize(np.array([0.0, -1.0, 0.5, 2.0]), QuantConfig(bits=8))
    assert q[0] == 0 and q[1] == 0 and q[2] >= 1 and q[3] >= 1


def test_quantize_monotone():
    w = np.linspace(0.01, 10, 1000)
    q, _ = quantize(w, QuantConfig(bits=8))
    assert (np.diff(q) >= 0).all()


def test_log_scheme_roundtrip():
    w = np.exp(np.random.default_rng(1).normal(0, 2, 1000))
    cfg = QuantConfig(bits=8, scheme="log")
    q, scale = quantize(w, cfg)
    deq = dequantize(q, scale, cfg)
    # log-scheme relative error stays bounded
    rel = np.abs(deq - w) / w
    assert np.median(rel) < 0.2


# ---------------------------------------------------------------- index


def test_index_invariants(bm25_index, bm25_collection):
    idx = bm25_index
    # segments ordered by (term, impact desc)
    seg_term = np.asarray(idx.seg_term)
    seg_w = np.asarray(idx.seg_weight)
    same_term = seg_term[1:] == seg_term[:-1]
    assert (seg_w[1:][same_term] <= seg_w[:-1][same_term] + 1e-6).all()
    # CSR covers all postings
    assert int(np.asarray(idx.term_post_count).sum()) == len(bm25_collection.doc_idx)
    # doc-major nnz matches
    assert int(np.asarray(idx.doc_n_terms).sum()) == len(bm25_collection.doc_idx)
    # block-max >= every posting weight in that (term, block)
    assert float(np.asarray(idx.term_max_weight).max()) > 0


def test_doc_store_width_is_whole_lane_tiles(tiny_corpus, splade_collection):
    enc = splade_collection
    for cap, width in ((1, 128), (128, 128), (129, 256), (300, 384), (640, 640)):
        idx = build_impact_index(
            enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms,
            max_doc_terms=cap,
        )
        assert idx.max_doc_terms == idx.doc_terms.shape[1] == width


@pytest.mark.parametrize("width", [1, 2, 7, 128, 384, 640, 768, 1024])
def test_tree_sum_last_equals_zero_padded_halving(width):
    """The fold is the pairwise halving of the width zero-padded to a power of
    two, bit for bit, without building the padding."""
    from repro.kernels.common import next_pow2, tree_sum_last

    rng = np.random.default_rng(width)
    x = (rng.standard_normal((3, width)) * 10.0 ** rng.integers(-3, 4, (3, width)))
    x = x.astype(np.float32)
    want = np.zeros((3, next_pow2(width)), np.float32)
    want[:, :width] = x
    while want.shape[1] > 1:
        h = want.shape[1] // 2
        want = want[:, :h] + want[:, h:]
    got = np.asarray(tree_sum_last(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want[:, 0])


def test_index_size_accounting(bm25_index):
    assert bm25_index.posting_store_nbytes() < bm25_index.nbytes()


def test_build_empty_corpus_index_is_inert():
    """A shard whose COO range holds no postings must still build and serve.

    All engines see zeroed CSR counts plus padded (never zero-length) stores,
    so every search returns all-zero scores instead of crashing."""
    z = np.zeros(0)
    idx = build_impact_index(z, z, z, 4, 5)
    assert idx.n_postings >= 1  # padded posting store: no zero-length gathers
    assert idx.seg_term.shape[0] >= 1 and idx.bm_block.shape[0] >= 1
    assert int(np.asarray(idx.term_post_count).sum()) == 0
    assert idx.max_segs == 0 and idx.max_bm == 0
    qt = jnp.asarray([[0, 2]], jnp.int32)
    qw = jnp.ones((1, 2), jnp.float32)
    ex = exhaustive_search(idx, qt, qw, k=3)
    assert np.all(np.asarray(ex.scores) == 0.0)
    sa = saat_search(idx, qt, qw, k=3, rho=idx.n_postings, max_segs_per_term=1)
    assert np.all(np.asarray(sa.scores) == 0.0)


# ---------------------------------------------------------------- evaluation


def test_saat_exact_equals_exhaustive(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    qt, qw = jnp.asarray(qt), jnp.asarray(qw)
    k = 10
    ex = exhaustive_search(bm25_index, qt, qw, k=k)
    sa = saat_search(
        bm25_index, qt, qw, k=k, rho=exact_rho(bm25_index),
        max_segs_per_term=max_segments_per_term(bm25_index),
    )
    np.testing.assert_allclose(np.asarray(sa.scores), np.asarray(ex.scores), rtol=1e-4, atol=1e-4)
    assert rank_overlap(np.asarray(sa.doc_ids), np.asarray(ex.doc_ids), k) > 0.99


def test_saat_scatter_impls_agree(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    qt, qw = jnp.asarray(qt[:8]), jnp.asarray(qw[:8])
    ms = max_segments_per_term(bm25_index)
    res = {}
    for impl in ("jnp", "sort", "pallas"):
        r = saat_search(bm25_index, qt, qw, k=10, rho=5000, max_segs_per_term=ms, scatter_impl=impl)
        res[impl] = np.asarray(r.scores)
    np.testing.assert_allclose(res["jnp"], res["sort"], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(res["jnp"], res["pallas"], rtol=1e-3, atol=1e-3)


def test_saat_monotone_in_rho(bm25_index, bm25_queries, tiny_corpus):
    """More postings budget -> effectiveness never degrades (on average)."""
    qt, qw = bm25_queries
    qt, qw = jnp.asarray(qt), jnp.asarray(qw)
    ms = max_segments_per_term(bm25_index)
    mrrs = []
    for rho in (200, 2000, exact_rho(bm25_index)):
        r = saat_search(bm25_index, qt, qw, k=10, rho=rho, max_segs_per_term=ms)
        mrrs.append(mrr_at_k(np.asarray(r.doc_ids), tiny_corpus.qrels, 10))
    assert mrrs[0] <= mrrs[1] + 0.02 and mrrs[1] <= mrrs[2] + 0.02, mrrs


def test_saat_postings_budget_respected(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    r = saat_search(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10, rho=500,
        max_segs_per_term=max_segments_per_term(bm25_index),
    )
    assert int(np.asarray(r.postings_processed).max()) <= 500


def test_daat_rank_safe_equals_exhaustive(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    qt, qw = jnp.asarray(qt), jnp.asarray(qw)
    ex = exhaustive_search(bm25_index, qt, qw, k=10)
    da = blockmax_search(
        bm25_index, qt, qw, k=10, est_blocks=2, block_budget=2,
        max_bm_per_term=max_blocks_per_term(bm25_index), exact=True,
    )
    assert bool(np.asarray(da.rank_safe).all())
    np.testing.assert_allclose(np.asarray(da.scores), np.asarray(ex.scores), rtol=1e-4, atol=1e-4)


def test_daat_skipping_happens_on_bm25(bm25_index, bm25_queries):
    """BM25's skewed weights must leave some blocks skippable."""
    qt, qw = bm25_queries
    da = blockmax_search(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10, est_blocks=1, block_budget=1,
        max_bm_per_term=max_blocks_per_term(bm25_index), exact=True,
    )
    scored = np.asarray(da.blocks_scored)
    assert (scored < bm25_index.n_blocks).any()


# ---------------------------------------------------------------- top-k utils


def test_tiled_topk_matches_full():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=4096), jnp.float32)
    s1, i1 = topk(x, 50)
    s2, i2 = tiled_topk(x, 50, num_tiles=8)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2))
    np.testing.assert_allclose(np.asarray(x)[np.asarray(i2)], np.asarray(s1))


def test_tiled_topk_k_equals_pool_size():
    """k == n must return the whole pool, exactly sorted, with a valid
    permutation of indices — including when the pool is ragged across tiles
    (k is clamped per tile, so every tile contributes all of its entries)."""
    rng = np.random.default_rng(3)
    for n, num_tiles in ((96, 8), (100, 8), (7, 3)):  # even, ragged, tiny
        x = jnp.asarray(rng.normal(size=n), jnp.float32)
        s, i = tiled_topk(x, n, num_tiles=num_tiles)
        assert s.shape == (n,) and i.shape == (n,)
        np.testing.assert_allclose(np.asarray(s), np.sort(np.asarray(x))[::-1])
        # indices are a permutation of the pool and consistent with scores
        assert sorted(np.asarray(i).tolist()) == list(range(n))
        np.testing.assert_allclose(np.asarray(x)[np.asarray(i)], np.asarray(s))
    # k beyond the pool clamps to n (mirrors topk())
    x = jnp.asarray(rng.normal(size=16), jnp.float32)
    s, i = tiled_topk(x, 50, num_tiles=4)
    assert s.shape == (16,)


def _ties_last_first_top_k(real):
    """``lax.top_k`` with its ties reversed: the highest index first, as an
    unstable sort may return them."""

    def top_k(x, k):
        n = x.shape[-1]
        s, i = real(x[..., ::-1], k)
        return s, (n - 1 - i).astype(i.dtype)

    return top_k


@pytest.mark.parametrize("shape,k", [((4096,), 1000), ((3, 700), 64), ((2, 5, 48), 48)])
def test_topk_ties_lowest_index_first_whatever_lax_top_k_does(monkeypatch, shape, k):
    """Equal scores come back in ascending index order even when the
    backend's ``lax.top_k`` orders ties otherwise (as the TPU compiler's
    unstable sort lowering of a wide top-k does)."""
    rng = np.random.default_rng(5)
    x = rng.integers(0, 6, size=shape).astype(np.float32)  # heavy ties
    x[..., ::11] = -np.inf
    monkeypatch.setattr(jax.lax, "top_k", _ties_last_first_top_k(jax.lax.top_k))
    s, i = topk(jnp.asarray(x), k)
    want_i = np.argsort(-x, axis=-1, kind="stable")[..., :k]
    np.testing.assert_array_equal(np.asarray(i), want_i)
    np.testing.assert_array_equal(np.asarray(s), np.take_along_axis(x, want_i, axis=-1))
    # the two-stage merge inherits the order
    ts, ti = tiled_topk(jnp.asarray(x), k, num_tiles=4)
    np.testing.assert_array_equal(np.asarray(ti), want_i)


def test_merge_topk():
    sa = jnp.asarray([9.0, 5.0, 1.0])
    ia = jnp.asarray([1, 2, 3], jnp.int32)
    sb = jnp.asarray([7.0, 6.0, 0.5])
    ib = jnp.asarray([4, 5, 6], jnp.int32)
    ms, mi = merge_topk(sa, ia, sb, ib, 4)
    np.testing.assert_allclose(np.asarray(ms), [9.0, 7.0, 6.0, 5.0])
    np.testing.assert_array_equal(np.asarray(mi), [1, 4, 5, 2])
