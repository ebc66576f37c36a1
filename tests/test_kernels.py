"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle.

The whole module carries the ``kernels`` marker so CI can run the
interpret-mode sweeps as a standalone matrix entry — a kernel regression
fails in an attributable job instead of somewhere inside the full tier-1 run.

Sweep shapes come from each package's ``CONTRACT.shape_grid`` (see
``src/repro/analysis/README.md``): the static checker traces exactly the
shapes these tests execute, so adding a ``ShapeCase`` grows both gates at
once and the lists can never drift apart.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_prune.ops import CONTRACT as BLOCK_PRUNE_CONTRACT
from repro.kernels.block_prune.ops import block_prune, block_prune_batched
from repro.kernels.block_prune.ref import block_prune_batched_ref, block_prune_ref
from repro.kernels.block_topk.ops import CONTRACT as BLOCK_TOPK_CONTRACT
from repro.kernels.block_topk.ops import block_topk, block_topk_batched
from repro.kernels.block_topk.ref import block_topk_batched_ref, block_topk_ref
from repro.kernels.impact_scatter.ops import CONTRACT as SCATTER_CONTRACT
from repro.kernels.impact_scatter.ops import impact_scatter, impact_scatter_batched
from repro.kernels.impact_scatter.ref import impact_scatter_batched_ref, impact_scatter_ref
from repro.kernels.impact_scatter_topk.ops import CONTRACT as SCATTER_TOPK_CONTRACT
from repro.kernels.impact_scatter_topk.ops import (
    impact_scatter_topk,
    impact_scatter_topk_batched,
)
from repro.kernels.impact_scatter_topk.ref import (
    impact_scatter_topk_batched_ref,
    impact_scatter_topk_ref,
)
from repro.kernels.sparse_score.ops import CONTRACT as SPARSE_SCORE_CONTRACT
from repro.kernels.sparse_score.ops import sparse_score, sparse_score_batched
from repro.kernels.sparse_score.ref import sparse_score_batched_ref, sparse_score_ref

pytestmark = pytest.mark.kernels


@pytest.mark.parametrize("n_postings", SCATTER_CONTRACT.sweep_values("n_postings", exclude=("batch",)))
@pytest.mark.parametrize("n_docs", SCATTER_CONTRACT.sweep_values("n_docs", exclude=("batch",)))
@pytest.mark.parametrize("presorted", [True, False])
def test_impact_scatter_sweep(n_postings, n_docs, presorted):
    rng = np.random.default_rng(n_postings + n_docs)
    docs = jnp.asarray(rng.integers(0, n_docs, n_postings), jnp.int32)
    if presorted:  # already doc-ordered input: the wrapper's sort is a no-op
        docs = jnp.sort(docs)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, n_postings), jnp.float32)
    got = impact_scatter(docs, contribs, n_docs, block_d=256, tile_p=128, interpret=True)
    want = impact_scatter_ref(docs, contribs, n_docs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_impact_scatter_dtypes(dtype):
    rng = np.random.default_rng(0)
    docs = jnp.asarray(rng.integers(0, 300, 512), jnp.int32)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, 512), dtype)
    got = impact_scatter(docs, contribs, 300, interpret=True)
    want = impact_scatter_ref(docs, contribs.astype(jnp.float32), 300)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_impact_scatter_zero_contrib_padding():
    docs = jnp.zeros(256, jnp.int32)
    contribs = jnp.zeros(256, jnp.float32)
    got = impact_scatter(docs, contribs, 128, interpret=True)
    assert float(jnp.abs(got).max()) == 0.0


@pytest.mark.parametrize("batch", SCATTER_CONTRACT.sweep_values("batch"))
@pytest.mark.parametrize("n_postings", SCATTER_CONTRACT.sweep_values("n_postings", require=("batch",)))
@pytest.mark.parametrize("presorted", [True, False])
def test_impact_scatter_batched_sweep(batch, n_postings, presorted):
    (n_docs,) = SCATTER_CONTRACT.sweep_values("n_docs", require=("batch",))
    rng = np.random.default_rng(batch * 1000 + n_postings)
    docs = jnp.asarray(rng.integers(0, n_docs, (batch, n_postings)), jnp.int32)
    if presorted:
        docs = jnp.sort(docs, axis=-1)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, (batch, n_postings)), jnp.float32)
    got = impact_scatter_batched(
        docs, contribs, n_docs, block_d=256, tile_p=128, interpret=True
    )
    want = impact_scatter_batched_ref(docs, contribs, n_docs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_impact_scatter_batched_matches_per_query_kernel():
    """Batched kernel rows == the single-query kernel run row by row."""
    rng = np.random.default_rng(7)
    B, P, D = 4, 512, 600
    docs = jnp.asarray(rng.integers(0, D, (B, P)), jnp.int32)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, (B, P)), jnp.float32)
    got = impact_scatter_batched(docs, contribs, D, block_d=256, tile_p=128, interpret=True)
    for b in range(B):
        row = impact_scatter(docs[b], contribs[b], D, block_d=256, tile_p=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(row), rtol=1e-5, atol=1e-5)


def test_impact_scatter_batched_rows_independent():
    """A hot row must not leak into its neighbors' accumulators."""
    B, P, D = 3, 256, 512
    docs = jnp.zeros((B, P), jnp.int32)
    contribs = jnp.zeros((B, P), jnp.float32)
    contribs = contribs.at[1, :].set(1.0)
    got = impact_scatter_batched(docs, contribs, D, block_d=256, tile_p=128, interpret=True)
    assert float(jnp.abs(got[0]).max()) == 0.0 and float(jnp.abs(got[2]).max()) == 0.0
    assert float(got[1, 0]) == float(P)


# ---------------------------------------------------------------------------
# impact_scatter_topk: fused scatter → per-block top-k
# ---------------------------------------------------------------------------


def _fused_parity(docs, contribs, n_docs, k, *, n_live=None, block_d=256, tile_p=128):
    """Fused op (interpret) vs the dense scatter+mask+topk oracle."""
    n_live = n_docs if n_live is None else n_live
    if docs.ndim == 1:
        got = impact_scatter_topk(
            docs, contribs, n_docs, k, n_live=n_live,
            block_d=block_d, tile_p=tile_p, interpret=True,
        )
        want = impact_scatter_topk_ref(docs, contribs, n_docs, n_live, k)
    else:
        got = impact_scatter_topk_batched(
            docs, contribs, n_docs, k, n_live=n_live,
            block_d=block_d, tile_p=tile_p, interpret=True,
        )
        want = impact_scatter_topk_batched_ref(docs, contribs, n_docs, n_live, k)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-5)
    return got


@pytest.mark.parametrize("n_postings", SCATTER_TOPK_CONTRACT.sweep_values("n_postings", exclude=("batch",)))
@pytest.mark.parametrize("n_docs", SCATTER_TOPK_CONTRACT.sweep_values("n_docs", exclude=("batch",)))
@pytest.mark.parametrize("k", SCATTER_TOPK_CONTRACT.sweep_values("k", exclude=("batch",)))
def test_impact_scatter_topk_sweep(n_postings, n_docs, k):
    rng = np.random.default_rng(n_postings + n_docs + k)
    docs = jnp.asarray(rng.integers(0, n_docs, n_postings), jnp.int32)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, n_postings), jnp.float32)
    _fused_parity(docs, contribs, n_docs, k)


@pytest.mark.parametrize("batch", SCATTER_TOPK_CONTRACT.sweep_values("batch"))
@pytest.mark.parametrize("presorted", [True, False])
def test_impact_scatter_topk_batched_sweep(batch, presorted):
    """Non-divisible n_docs/tile_p shapes, doc-ordered input or not."""
    # 700 % 256 != 0, 1000 % 128 != 0 (the contract's ragged batched case)
    (n_docs,) = SCATTER_TOPK_CONTRACT.sweep_values("n_docs", require=("batch",))
    (n_postings,) = SCATTER_TOPK_CONTRACT.sweep_values("n_postings", require=("batch",))
    (k,) = SCATTER_TOPK_CONTRACT.sweep_values("k", require=("batch",))
    rng = np.random.default_rng(batch * 1000)
    docs = jnp.asarray(rng.integers(0, n_docs, (batch, n_postings)), jnp.int32)
    if presorted:
        docs = jnp.sort(docs, axis=-1)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, (batch, n_postings)), jnp.float32)
    got = impact_scatter_topk_batched(
        docs, contribs, n_docs, k, block_d=256, tile_p=128, interpret=True,
    )
    want = impact_scatter_topk_batched_ref(docs, contribs, n_docs, n_docs, k)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-5)


def test_impact_scatter_topk_all_postings_one_doc():
    """Degenerate hot doc: every posting lands on doc 7 of one block."""
    P, D = 512, 640
    docs = jnp.full((P,), 7, jnp.int32)
    contribs = jnp.asarray(np.random.default_rng(0).gamma(2.0, 1.0, P), jnp.float32)
    s, i = _fused_parity(docs, contribs, D, 5)
    assert int(np.asarray(i)[0]) == 7
    np.testing.assert_allclose(float(np.asarray(s)[0]), float(contribs.sum()), rtol=1e-5)


def test_impact_scatter_topk_all_zero_contribs():
    """All-zero contributions: ties resolve to ascending doc ids, scores 0."""
    docs = jnp.asarray(np.random.default_rng(1).integers(0, 500, 256), jnp.int32)
    contribs = jnp.zeros((256,), jnp.float32)
    s, i = _fused_parity(docs, contribs, 500, 8)
    np.testing.assert_allclose(np.asarray(s), 0.0)
    np.testing.assert_array_equal(np.asarray(i), np.arange(8))


def test_impact_scatter_topk_k_exceeds_block_survivors():
    """k larger than a block's surviving (live) candidates: -inf fill ranks.

    n_live=40 leaves one partial block of live docs; k=64 must surface all 40
    live docs then ascending masked ids, bit-identical to the dense oracle.
    """
    rng = np.random.default_rng(2)
    docs = jnp.asarray(rng.integers(0, 40, 256), jnp.int32)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, 256), jnp.float32)
    s, i = _fused_parity(docs, contribs, 512, 64, n_live=40)
    assert bool(np.isfinite(np.asarray(s)[:40]).all())
    assert bool(np.isneginf(np.asarray(s)[40:]).all())


def test_impact_scatter_topk_batch_of_one():
    rng = np.random.default_rng(3)
    docs = jnp.asarray(rng.integers(0, 300, (1, 384)), jnp.int32)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, (1, 384)), jnp.float32)
    got = _fused_parity(docs, contribs, 300, 9)
    assert got[0].shape == (1, 9)


def test_impact_scatter_topk_batched_matches_per_query_kernel():
    """Batched kernel rows == the single-query fused kernel run row by row."""
    rng = np.random.default_rng(7)
    B, P, D = 4, 512, 600
    docs = jnp.asarray(rng.integers(0, D, (B, P)), jnp.int32)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, (B, P)), jnp.float32)
    gs, gi = impact_scatter_topk_batched(
        docs, contribs, D, 11, block_d=256, tile_p=128, interpret=True
    )
    for b in range(B):
        rs, ri = impact_scatter_topk(
            docs[b], contribs[b], D, 11, block_d=256, tile_p=128, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(gi[b]), np.asarray(ri))
        np.testing.assert_allclose(np.asarray(gs[b]), np.asarray(rs), rtol=1e-5, atol=1e-5)


def test_impact_scatter_topk_matches_unfused_pallas_bitwise():
    """Same accumulation kernel -> fused scores are BIT-equal to unfused."""
    rng = np.random.default_rng(11)
    docs = jnp.asarray(rng.integers(0, 700, (2, 512)), jnp.int32)
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, (2, 512)), jnp.float32)
    acc = impact_scatter_batched(docs, contribs, 700, block_d=256, tile_p=128, interpret=True)
    ds, di = jax.lax.top_k(acc, 15)
    fs, fi = impact_scatter_topk_batched(
        docs, contribs, 700, 15, block_d=256, tile_p=128, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(di))
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(ds))


def test_impact_scatter_topk_single_query_bitwise_with_duplicate_docs():
    """Fused and unfused single-query wrappers share ONE canonical fold
    (``sorted_posting_tiles``), so heavy doc-id duplication — where any
    backend-chosen addend order would perturb the f32 accumulation — still
    yields BIT-equal scores."""
    rng = np.random.default_rng(17)
    docs = jnp.asarray(rng.integers(0, 10, 512), jnp.int32)  # ~51 postings/doc
    contribs = jnp.asarray(rng.gamma(2.0, 1.0, 512), jnp.float32)
    acc = impact_scatter(docs, contribs, 300, block_d=256, tile_p=128, interpret=True)
    ds, di = jax.lax.top_k(acc, 12)
    fs, fi = impact_scatter_topk(
        docs, contribs, 300, 12, block_d=256, tile_p=128, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(di))
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(ds))


@pytest.mark.parametrize("n,k,tile", BLOCK_TOPK_CONTRACT.sweep("n", "k", "tile", exclude=("batch",)))
def test_block_topk_sweep(n, k, tile):
    rng = np.random.default_rng(n + k)
    scores = jnp.asarray(rng.normal(size=n), jnp.float32)
    s, i = block_topk(scores, k, tile=tile, interpret=True)
    rs, ri = block_topk_ref(scores, min(k, n))
    np.testing.assert_allclose(np.asarray(s)[: min(k, n)], np.asarray(rs), rtol=1e-6)
    # ids must point at the same scores (ties may permute)
    np.testing.assert_allclose(
        np.asarray(scores)[np.asarray(i)[: min(k, n)]], np.asarray(rs), rtol=1e-6
    )


def test_block_topk_with_neg_inf():
    scores = jnp.asarray([1.0, -jnp.inf, 3.0, -jnp.inf, 2.0], jnp.float32)
    s, i = block_topk(scores, 3, tile=128, interpret=True)
    np.testing.assert_allclose(np.asarray(s), [3.0, 2.0, 1.0])


@pytest.mark.parametrize("lq,nb", BLOCK_PRUNE_CONTRACT.sweep("lq", "nb", exclude=("batch",)))
def test_block_prune_sweep(lq, nb):
    rng = np.random.default_rng(lq * nb)
    bm = jnp.asarray(rng.gamma(1.0, 1.0, (lq, nb)) * (rng.random((lq, nb)) > 0.3), jnp.float32)
    qw = jnp.asarray(rng.gamma(1.0, 1.0, lq), jnp.float32)
    theta = jnp.float32(np.quantile(np.asarray(bm).sum(0), 0.7))
    ub, mask = block_prune(bm, qw, theta, block_nb=256, interpret=True)
    rub, rmask = block_prune_ref(bm, qw, theta)
    np.testing.assert_allclose(np.asarray(ub), np.asarray(rub), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(rmask))


@pytest.mark.parametrize("batch,n,k,tile", BLOCK_TOPK_CONTRACT.sweep("batch", "n", "k", "tile"))
def test_block_topk_batched_sweep(batch, n, k, tile):
    """Non-divisible n/tile shapes; per-row finalists must match the oracle."""
    rng = np.random.default_rng(batch * 10 + n + k)
    scores = jnp.asarray(rng.normal(size=(batch, n)), jnp.float32)
    s, i = block_topk_batched(scores, k, tile=tile, interpret=True)
    rs, ri = block_topk_batched_ref(scores, min(k, n))
    ke = min(k, n)
    np.testing.assert_allclose(np.asarray(s)[:, :ke], np.asarray(rs), rtol=1e-6)
    np.testing.assert_allclose(  # ids must point at the same scores (ties may permute)
        np.take_along_axis(np.asarray(scores), np.asarray(i)[:, :ke], axis=-1),
        np.asarray(rs), rtol=1e-6,
    )


def test_block_topk_batched_matches_per_row_kernel():
    rng = np.random.default_rng(3)
    scores = jnp.asarray(rng.normal(size=(4, 600)), jnp.float32)
    s, i = block_topk_batched(scores, 9, tile=128, interpret=True)
    for b in range(4):
        rs, ri = block_topk(scores[b], 9, tile=128, interpret=True)
        np.testing.assert_allclose(np.asarray(s[b]), np.asarray(rs), rtol=1e-6)


def test_block_topk_batched_k_exceeds_n_pads():
    scores = jnp.asarray(np.random.default_rng(1).normal(size=(2, 40)), jnp.float32)
    s, i = block_topk_batched(scores, 50, tile=128, interpret=True)
    assert s.shape == (2, 50)
    assert bool(np.isneginf(np.asarray(s)[:, 40:]).all())


@pytest.mark.parametrize("batch,lq,nb", BLOCK_PRUNE_CONTRACT.sweep("batch", "lq", "nb"))
def test_block_prune_batched_sweep(batch, lq, nb):
    """Non-divisible block counts; each row pruned against its own theta."""
    rng = np.random.default_rng(batch * 100 + lq * nb)
    bm = jnp.asarray(
        rng.gamma(1.0, 1.0, (batch, lq, nb)) * (rng.random((batch, lq, nb)) > 0.3), jnp.float32
    )
    qw = jnp.asarray(rng.gamma(1.0, 1.0, (batch, lq)), jnp.float32)
    theta = jnp.asarray(np.quantile(np.asarray(bm).sum(1), 0.7, axis=-1), jnp.float32)
    ub, mask = block_prune_batched(bm, qw, theta, block_nb=256, interpret=True)
    rub, rmask = block_prune_batched_ref(bm, qw, theta)
    np.testing.assert_allclose(np.asarray(ub), np.asarray(rub), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(rmask))


def test_block_prune_batched_matches_per_row_kernel():
    rng = np.random.default_rng(9)
    B, lq, nb = 3, 6, 130
    bm = jnp.asarray(rng.gamma(1.0, 1.0, (B, lq, nb)), jnp.float32)
    qw = jnp.asarray(rng.gamma(1.0, 1.0, (B, lq)), jnp.float32)
    theta = jnp.asarray(rng.gamma(2.0, 2.0, B), jnp.float32)
    ub, mask = block_prune_batched(bm, qw, theta, block_nb=128, interpret=True)
    for b in range(B):
        rub, rmask = block_prune(bm[b], qw[b], theta[b], block_nb=128, interpret=True)
        np.testing.assert_allclose(np.asarray(ub[b]), np.asarray(rub), rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(mask[b]), np.asarray(rmask))


def test_block_prune_batched_degenerate_all_and_none_pruned():
    """theta below every ub keeps all nonempty blocks; theta above kills all."""
    rng = np.random.default_rng(4)
    B, lq, nb = 2, 4, 260  # non-divisible by the 128 tile
    bm = jnp.asarray(rng.gamma(1.0, 1.0, (B, lq, nb)) + 0.1, jnp.float32)
    qw = jnp.asarray(np.ones((B, lq)), jnp.float32)
    ub_ref, _ = block_prune_batched_ref(bm, qw, jnp.zeros((B,), jnp.float32))
    lo = jnp.full((B,), -1.0, jnp.float32)
    hi = jnp.asarray(np.asarray(ub_ref).max(-1) + 1.0, jnp.float32)
    _, mask_none = block_prune_batched(bm, qw, lo, block_nb=128, interpret=True)
    _, mask_all = block_prune_batched(bm, qw, hi, block_nb=128, interpret=True)
    assert bool(np.asarray(mask_none).all())  # none pruned: every block survives
    assert not bool(np.asarray(mask_all).any())  # all pruned: nothing survives
    # rows see only their own theta: mixing lo/hi prunes exactly one row
    mixed = jnp.asarray([float(lo[0]), float(hi[1])], jnp.float32)
    _, mask_mix = block_prune_batched(bm, qw, mixed, block_nb=128, interpret=True)
    assert bool(np.asarray(mask_mix)[0].all()) and not bool(np.asarray(mask_mix)[1].any())


def test_block_prune_batched_empty_blocks_never_survive():
    """ub == 0 blocks (no query term present) stay dead even with theta < 0."""
    bm = jnp.zeros((2, 3, 140), jnp.float32)
    qw = jnp.ones((2, 3), jnp.float32)
    theta = jnp.full((2,), -5.0, jnp.float32)
    _, mask = block_prune_batched(bm, qw, theta, block_nb=128, interpret=True)
    assert not bool(np.asarray(mask).any())


@pytest.mark.parametrize("n,tmax,lq", SPARSE_SCORE_CONTRACT.sweep("n", "tmax", "lq", exclude=("batch",)))
def test_sparse_score_sweep(n, tmax, lq):
    rng = np.random.default_rng(n + tmax + lq)
    V = 500
    dt = jnp.asarray(rng.integers(0, V, (n, tmax)), jnp.int32)
    dw = jnp.asarray(rng.gamma(1.0, 1.0, (n, tmax)), jnp.float32)
    qt = jnp.asarray(rng.choice(V, lq, replace=False), jnp.int32)
    qw = jnp.asarray(rng.gamma(1.0, 1.0, lq), jnp.float32)
    got = sparse_score(dt, dw, qt, qw, block_d=128, interpret=True)
    want = sparse_score_ref(dt, dw, qt, qw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_sparse_score_duplicate_query_terms():
    """Duplicate query terms must both contribute (sum semantics)."""
    dt = jnp.asarray([[3, 5]], jnp.int32)
    dw = jnp.asarray([[2.0, 1.0]], jnp.float32)
    qt = jnp.asarray([3, 3], jnp.int32)
    qw = jnp.asarray([1.0, 0.5], jnp.float32)
    got = sparse_score(dt, dw, qt, qw, interpret=True)
    want = sparse_score_ref(dt, dw, qt, qw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got), [3.0])


@pytest.mark.parametrize("batch,n,tmax,lq", SPARSE_SCORE_CONTRACT.sweep("batch", "n", "tmax", "lq"))
def test_sparse_score_batched_sweep(batch, n, tmax, lq):
    """Each query scores its own doc rows; non-divisible doc counts pad."""
    rng = np.random.default_rng(batch + n + tmax + lq)
    V = 500
    dt = jnp.asarray(rng.integers(0, V, (batch, n, tmax)), jnp.int32)
    dw = jnp.asarray(rng.gamma(1.0, 1.0, (batch, n, tmax)), jnp.float32)
    qt = jnp.asarray(rng.integers(0, V, (batch, lq)), jnp.int32)
    qw = jnp.asarray(rng.gamma(1.0, 1.0, (batch, lq)), jnp.float32)
    got = sparse_score_batched(dt, dw, qt, qw, block_d=128, interpret=True)
    want = sparse_score_batched_ref(dt, dw, qt, qw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_sparse_score_batched_matches_per_query_kernel():
    rng = np.random.default_rng(13)
    B, n, tmax, lq = 3, 200, 9, 5
    dt = jnp.asarray(rng.integers(0, 300, (B, n, tmax)), jnp.int32)
    dw = jnp.asarray(rng.gamma(1.0, 1.0, (B, n, tmax)), jnp.float32)
    qt = jnp.asarray(rng.integers(0, 300, (B, lq)), jnp.int32)
    qw = jnp.asarray(rng.gamma(1.0, 1.0, (B, lq)), jnp.float32)
    got = sparse_score_batched(dt, dw, qt, qw, block_d=64, interpret=True)
    for b in range(B):
        row = sparse_score(dt[b], dw[b], qt[b], qw[b], block_d=64, interpret=True)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(row), rtol=1e-5, atol=1e-5)


def test_sparse_score_batched_rows_independent():
    """Query b must never score with query c's weights."""
    dt = jnp.asarray(np.full((2, 64, 2), 3), jnp.int32)
    dw = jnp.asarray(np.ones((2, 64, 2)), jnp.float32)
    qt = jnp.asarray([[3, 4], [9, 9]], jnp.int32)  # row 1 matches nothing
    qw = jnp.asarray([[1.0, 1.0], [1.0, 1.0]], jnp.float32)
    got = np.asarray(sparse_score_batched(dt, dw, qt, qw, block_d=64, interpret=True))
    np.testing.assert_allclose(got[0], 2.0)  # two slots of term 3, weight 1 each
    np.testing.assert_allclose(got[1], 0.0)
