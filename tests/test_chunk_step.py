"""Fused DAAT chunk-step kernel: interpret-mode sweeps + properties.

The ``chunk_step`` kernel replaces the batched engine's phase-2 while-body
(select + score + merge) with ONE VMEM-resident pass, so the bar is the
strictest in the repo: doc ids, theta, the processed bitmap, AND the pool
scores must be **bitwise** identical to the jnp body (``chunk_step_batched_ref``
— the engine formulation, verbatim), per trip and end-to-end. The module
carries the ``kernels`` marker so a regression fails in the standalone CI
kernels entry by name.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_impact_index, pad_queries
from repro.core.daat import (
    daat_plan,
    daat_search_batched,
    max_blocks_per_term,
    score_blocks,
)
from repro.core.topk import topk
from repro.kernels.chunk_step.ops import (
    CONTRACT,
    chunk_step_batched,
    chunk_step_multi_batched,
)
from repro.kernels.chunk_step.ref import (
    chunk_step_batched_ref,
    chunk_step_multi_batched_ref,
)

pytestmark = pytest.mark.kernels


# --------------------------------------------------------------------------
# state construction helpers
# --------------------------------------------------------------------------

_INDEX_CACHE: dict = {}


def _tiny_index(seed=0, n_docs=220, n_terms=40, n_postings=1500, block_size=32):
    """Session-cached tiny index (220 docs / bs=32 -> 7 blocks, non-divisible
    by any power-of-two budget — the shapes the sweeps need)."""
    key = (seed, n_docs, n_terms, n_postings, block_size)
    if key not in _INDEX_CACHE:
        rng = np.random.default_rng(seed)
        d = rng.integers(0, n_docs, n_postings)
        t = rng.integers(0, n_terms, n_postings)
        w = rng.gamma(2.0, 1.0, n_postings)
        _INDEX_CACHE[key] = build_impact_index(
            d, t, w, n_docs, n_terms, block_size=block_size
        )
    return _INDEX_CACHE[key]


def _phase1_state(idx, qt, qw, *, k, est_blocks=2):
    """Reproduce the engine's phase-1 seeding: the state a chunk step takes."""
    mb = max_blocks_per_term(idx)
    plan = daat_plan(idx, qt, qw, mb)
    ub = plan.ub
    B = qt.shape[0]
    _, b1 = topk(ub, est_blocks)
    s1, d1 = score_blocks(idx, plan.qvec, b1)
    pool_s, pool_i = topk(s1.reshape(B, -1), k)
    pool_i = jnp.take_along_axis(d1.reshape(B, -1), pool_i, axis=-1).astype(jnp.int32)
    theta = pool_s[:, k - 1]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    processed = jnp.zeros((B, idx.n_blocks), jnp.bool_).at[rows, b1].set(True)
    return ub, processed, pool_s, pool_i, theta


def _random_queries(idx, rng, B, Lq):
    qt = rng.integers(0, idx.n_terms, (B, Lq)).astype(np.int32)
    qw = rng.gamma(1.0, 1.0, (B, Lq)).astype(np.float32)
    return jnp.asarray(qt), jnp.asarray(qw)


def _assert_step_bitwise(idx, qt, qw, state, *, budget):
    """Kernel vs the jnp body: EVERYTHING bitwise, scores included."""
    ub, processed, pool_s, pool_i, theta = state
    qw_raw = jnp.where(qw > 0, qw, 0.0)
    got = chunk_step_batched(
        idx.doc_terms, idx.doc_weights, qt, qw_raw,
        ub, processed, pool_s, pool_i, theta,
        block_budget=budget, block_size=idx.block_size, n_live=idx.n_docs,
    )
    want = chunk_step_batched_ref(
        idx.doc_terms, idx.doc_weights, qt, qw,
        ub, processed, pool_s, pool_i, theta,
        block_budget=budget, block_size=idx.block_size, n_live=idx.n_docs,
        n_terms=idx.n_terms,
    )
    for name, g, r in zip(("pool_s", "pool_i", "theta", "processed"), got, want):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(r), err_msg=f"chunk step {name} diverged (bitwise)"
        )
    return got


def _assert_multi_step_bitwise(idx, qt, qw, state, trips_left, *, budget, trips):
    """Multi-trip kernel vs its jnp oracle: all five outputs bitwise."""
    ub, processed, pool_s, pool_i, theta = state
    qw_raw = jnp.where(qw > 0, qw, 0.0)
    tl = jnp.asarray(trips_left, jnp.int32)
    got = chunk_step_multi_batched(
        idx.doc_terms, idx.doc_weights, qt, qw_raw,
        ub, processed, pool_s, pool_i, theta, tl,
        trips_per_launch=trips, block_budget=budget,
        block_size=idx.block_size, n_live=idx.n_docs,
    )
    want = chunk_step_multi_batched_ref(
        idx.doc_terms, idx.doc_weights, qt, qw,
        ub, processed, pool_s, pool_i, theta, tl,
        trips_per_launch=trips, block_budget=budget,
        block_size=idx.block_size, n_live=idx.n_docs, n_terms=idx.n_terms,
    )
    names = ("pool_s", "pool_i", "theta", "processed", "trips_done")
    for name, g, r in zip(names, got, want):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(r),
            err_msg=f"multi-trip chunk step {name} diverged (bitwise)",
        )
    return got


# --------------------------------------------------------------------------
# interpret-mode degenerate sweeps (op vs jnp body)
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dims", [c.dims for c in CONTRACT.shape_grid],
    ids=[c.name for c in CONTRACT.shape_grid],
)
def test_chunk_step_sweep(dims):
    """Executes the CONTRACT's exact shape grid (what the checker traces):
    the full B x budget x k cross on the 7-block index — budget 3 is
    non-divisible, 7 == n_blocks — plus the ragged bs=24 degenerate and the
    multi-trip cases (``trips`` dim present: the scalar-prefetched launch
    with heterogeneous per-row trip budgets, including a zero-budget row)."""
    idx = _tiny_index(n_docs=dims["n_docs"], block_size=dims["block_size"])
    rng = np.random.default_rng(dims["B"] * 100 + dims["budget"] * 10 + dims["k"])
    qt, qw = _random_queries(idx, rng, dims["B"], dims["lq"])
    state = _phase1_state(idx, qt, qw, k=dims["k"])
    if "trips" in dims:
        trips = dims["trips"]
        # heterogeneous budgets spanning 0..trips exercise the per-row gate
        trips_left = np.arange(dims["B"], dtype=np.int32) % (trips + 1)
        _assert_multi_step_bitwise(
            idx, qt, qw, state, trips_left, budget=dims["budget"], trips=trips
        )
    else:
        _assert_step_bitwise(idx, qt, qw, state, budget=dims["budget"])


def test_multi_trip_matches_sequential_single_trips():
    """One multi-trip launch == the same trips applied one launch at a time
    (the exact equivalence the engine's trips_per_launch routing relies on)."""
    idx = _tiny_index()
    rng = np.random.default_rng(11)
    qt, qw = _random_queries(idx, rng, 3, 5)
    state = _phase1_state(idx, qt, qw, k=4)
    trips = 4
    got = _assert_multi_step_bitwise(
        idx, qt, qw, state, np.full(3, trips, np.int32), budget=2, trips=trips
    )
    ub = state[0]
    qw_raw = jnp.where(qw > 0, qw, 0.0)
    _, processed, pool_s, pool_i, theta = state
    for _ in range(trips):
        rub = jnp.where(processed, -jnp.inf, ub)
        act = jnp.max(rub, axis=-1, initial=-jnp.inf) > theta
        step = chunk_step_batched(
            idx.doc_terms, idx.doc_weights, qt, qw_raw,
            ub, processed, pool_s, pool_i, theta,
            block_budget=2, block_size=idx.block_size, n_live=idx.n_docs,
        )
        m = act[:, None]
        pool_s = jnp.where(m, step[0], pool_s)
        pool_i = jnp.where(m, step[1], pool_i)
        theta = jnp.where(act, step[2], theta)
        processed = jnp.where(m, step[3], processed)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(pool_s))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(pool_i))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(theta))
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(processed))


def test_multi_trip_early_exit_counts_trips():
    """trips_done stops where a row goes rank-safe or its budget ends; a
    zero-budget row rides through the launch bit-for-bit untouched."""
    idx = _tiny_index()
    rng = np.random.default_rng(12)
    qt, qw = _random_queries(idx, rng, 3, 5)
    ub, processed, pool_s, pool_i, theta = _phase1_state(idx, qt, qw, k=4)
    trips_left = np.array([0, 2, 8], np.int32)
    got = _assert_multi_step_bitwise(
        idx, qt, qw, (ub, processed, pool_s, pool_i, theta), trips_left,
        budget=3, trips=8,
    )
    trips_done = np.asarray(got[4])
    assert trips_done[0] == 0
    assert trips_done[1] <= 2
    # the 7-block index at budget 3 is fully scored in <= 3 trips: row 2's
    # in-kernel early exit must fire well before its 8-trip budget
    assert trips_done[2] < 8
    np.testing.assert_array_equal(np.asarray(got[0])[0], np.asarray(pool_s)[0])
    np.testing.assert_array_equal(np.asarray(got[3])[0], np.asarray(processed)[0])


def test_multi_trip_validates_budget():
    idx = _tiny_index()
    rng = np.random.default_rng(13)
    qt, qw = _random_queries(idx, rng, 2, 4)
    ub, processed, pool_s, pool_i, theta = _phase1_state(idx, qt, qw, k=3)
    with pytest.raises(ValueError, match="trips_per_launch"):
        chunk_step_multi_batched(
            idx.doc_terms, idx.doc_weights, qt, qw,
            ub, processed, pool_s, pool_i, theta,
            jnp.ones((2,), jnp.int32),
            trips_per_launch=0, block_budget=2,
            block_size=idx.block_size, n_live=idx.n_docs,
        )


def test_chunk_step_all_pruned_trip():
    """theta above every remaining ub: nothing is live, the whole state must
    ride through the kernel bit-for-bit unchanged."""
    idx = _tiny_index()
    rng = np.random.default_rng(1)
    qt, qw = _random_queries(idx, rng, 3, 5)
    ub, processed, pool_s, pool_i, _ = _phase1_state(idx, qt, qw, k=4)
    theta = jnp.full((3,), float(jnp.max(ub)) + 1.0, jnp.float32)
    got = _assert_step_bitwise(
        idx, qt, qw, (ub, processed, pool_s, pool_i, theta), budget=3
    )
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(pool_s))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(pool_i))
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(processed))


def test_chunk_step_single_active_row():
    """Rows whose blocks are all processed idle; the one live row advances."""
    idx = _tiny_index()
    rng = np.random.default_rng(2)
    qt, qw = _random_queries(idx, rng, 3, 5)
    ub, processed, pool_s, pool_i, theta = _phase1_state(idx, qt, qw, k=4)
    processed = processed.at[1:, :].set(True)  # only row 0 has work left
    got = _assert_step_bitwise(
        idx, qt, qw, (ub, processed, pool_s, pool_i, theta), budget=2
    )
    np.testing.assert_array_equal(np.asarray(got[0])[1:], np.asarray(pool_s)[1:])
    np.testing.assert_array_equal(np.asarray(got[3])[1:], np.asarray(processed)[1:])
    assert bool((np.asarray(got[3])[0] >= np.asarray(processed)[0]).all())


def test_chunk_step_duplicate_and_zero_weight_terms():
    """Dup query terms sum, zero-weight slots vanish, all-pad rows idle."""
    idx = _tiny_index()
    rng = np.random.default_rng(3)
    qt, qw = (np.array(a) for a in _random_queries(idx, rng, 4, 6))
    qt[:, 1] = qt[:, 0]
    qw[:, 2] = 0.0
    qt[2], qw[2] = idx.n_terms, 0.0  # all-pad row
    qt, qw = jnp.asarray(qt), jnp.asarray(qw)
    state = _phase1_state(idx, qt, qw, k=4)
    _assert_step_bitwise(idx, qt, qw, state, budget=3)


def test_chunk_step_k_at_pool_boundary():
    """k equal to the whole merged width boundary cases: the k-th slot (the
    new theta) comes from the last candidate rank, where an off-by-one in the
    merge shows up first."""
    idx = _tiny_index()
    rng = np.random.default_rng(4)
    qt, qw = _random_queries(idx, rng, 2, 5)
    # k == est_blocks * block_size: the pool exactly at phase-1 capacity
    k = 2 * idx.block_size
    state = _phase1_state(idx, qt, qw, k=k, est_blocks=2)
    _assert_step_bitwise(idx, qt, qw, state, budget=3)


def test_chunk_step_budget_exceeding_blocks_rejected():
    idx = _tiny_index()
    rng = np.random.default_rng(6)
    qt, qw = _random_queries(idx, rng, 2, 4)
    ub, processed, pool_s, pool_i, theta = _phase1_state(idx, qt, qw, k=3)
    with pytest.raises(ValueError, match="n_blocks"):
        chunk_step_batched(
            idx.doc_terms, idx.doc_weights, qt, qw,
            ub, processed, pool_s, pool_i, theta,
            block_budget=idx.n_blocks + 1, block_size=idx.block_size,
            n_live=idx.n_docs,
        )


# --------------------------------------------------------------------------
# engine-level golden parity: fused chunk step vs the jnp oracle
# --------------------------------------------------------------------------


def _assert_engine_parity(index, qt, qw, **kw):
    """fused == split kernels (bitwise) == jnp oracle (ids/stats/scores)."""
    kw.setdefault("max_bm_per_term", max_blocks_per_term(index))
    j = daat_search_batched(index, qt, qw, use_kernels=False, **kw)
    s = daat_search_batched(index, qt, qw, use_kernels=True, **kw)
    f = daat_search_batched(index, qt, qw, use_kernels=True, fused_chunk=True, **kw)
    # the fusion is invisible next to the split kernel mode — bitwise
    np.testing.assert_array_equal(np.asarray(f.doc_ids), np.asarray(s.doc_ids))
    np.testing.assert_array_equal(np.asarray(f.scores), np.asarray(s.scores))
    # and indistinguishable from the jnp oracle in ids + WorkStats
    np.testing.assert_array_equal(np.asarray(f.doc_ids), np.asarray(j.doc_ids))
    np.testing.assert_allclose(
        np.asarray(f.scores), np.asarray(j.scores), rtol=1e-5, atol=1e-6
    )
    for field in ("n_survivors", "blocks_scored", "chunks", "rank_safe"):
        np.testing.assert_array_equal(
            np.asarray(getattr(f.stats, field)),
            np.asarray(getattr(j.stats, field)),
            err_msg=f"WorkStats.{field} diverged between fused and jnp phase 2",
        )
    return f


@pytest.mark.parametrize("exact", [True, False])
def test_engine_fused_chunk_parity(bm25_index, bm25_queries, exact):
    qt, qw = bm25_queries
    _assert_engine_parity(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw),
        k=10, est_blocks=2, block_budget=2, exact=exact,
    )


def test_engine_fused_chunk_parity_on_three_tile_store(tiny_corpus, splade_collection):
    """A doc-major store three 128-lane tiles wide (not a power of two): the
    kernels' fixed-order row sum folds the tiles exactly as the jnp scorer
    does, so scores are bitwise equal too."""
    enc = splade_collection
    idx = build_impact_index(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms,
        max_doc_terms=300,
    )
    assert idx.doc_terms.shape[1] == 384
    qt, qw = pad_queries(enc.query_terms[:4], enc.query_weights[:4], 16, enc.n_terms)
    f = _assert_engine_parity(
        idx, jnp.asarray(qt), jnp.asarray(qw), k=10, est_blocks=1, block_budget=1,
        exact=True,
    )
    j = daat_search_batched(
        idx, jnp.asarray(qt), jnp.asarray(qw), k=10, est_blocks=1, block_budget=1,
        exact=True, max_bm_per_term=max_blocks_per_term(idx),
    )
    np.testing.assert_array_equal(np.asarray(f.scores), np.asarray(j.scores))


def test_engine_fused_chunk_ragged_batch(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    qt, qw = np.array(qt[:8]), np.array(qw[:8])
    for i in range(qt.shape[0]):
        keep = max(1, qt.shape[1] - i)
        qw[i, keep:] = 0.0
        qt[i, keep:] = bm25_index.n_terms
    _assert_engine_parity(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw),
        k=10, est_blocks=2, block_budget=1, exact=True,
    )


def test_engine_fused_chunk_k_exceeds_n_docs():
    idx = _tiny_index(seed=7, n_docs=50, n_terms=30, n_postings=400, block_size=32)
    rng = np.random.default_rng(8)
    qt = jnp.asarray(rng.integers(0, 30, (3, 4)).astype(np.int32))
    qw = jnp.asarray(rng.gamma(1.0, 1.0, (3, 4)).astype(np.float32))
    f = _assert_engine_parity(
        idx, qt, qw, k=60, est_blocks=idx.n_blocks, block_budget=1, exact=True,
    )
    assert bool(np.isneginf(np.asarray(f.scores)[:, 50:]).all())


def test_engine_fused_chunk_max_chunks_cap(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    f = _assert_engine_parity(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw),
        k=10, est_blocks=1, block_budget=1, exact=True, max_chunks=1,
    )
    assert int(np.asarray(f.chunks).max()) <= 1


@pytest.mark.parametrize("trips", [2, 3, 8])
def test_engine_multi_trip_parity(bm25_index, bm25_queries, trips):
    """trips_per_launch is invisible: ids/scores/WorkStats bitwise vs the
    per-trip fused mode (which itself is pinned to the jnp oracle above)."""
    qt, qw = bm25_queries
    kw = dict(
        k=10, est_blocks=2, block_budget=2, exact=True,
        max_bm_per_term=max_blocks_per_term(bm25_index),
        use_kernels=True, fused_chunk=True,
    )
    f = daat_search_batched(bm25_index, jnp.asarray(qt), jnp.asarray(qw), **kw)
    m = daat_search_batched(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw),
        trips_per_launch=trips, **kw,
    )
    np.testing.assert_array_equal(np.asarray(m.doc_ids), np.asarray(f.doc_ids))
    np.testing.assert_array_equal(np.asarray(m.scores), np.asarray(f.scores))
    for field in ("n_survivors", "blocks_scored", "chunks", "rank_safe"):
        np.testing.assert_array_equal(
            np.asarray(getattr(m.stats, field)),
            np.asarray(getattr(f.stats, field)),
            err_msg=f"WorkStats.{field} diverged under trips_per_launch={trips}",
        )


def test_engine_multi_trip_anytime_flag_invariant(bm25_index, bm25_queries):
    """exact=False clamps the trip batching to 1: the anytime budget is
    enforced per trip, so trips_per_launch must not change anything."""
    qt, qw = bm25_queries
    kw = dict(
        k=10, est_blocks=2, block_budget=2, exact=False,
        max_bm_per_term=max_blocks_per_term(bm25_index),
        use_kernels=True, fused_chunk=True,
    )
    a = daat_search_batched(bm25_index, jnp.asarray(qt), jnp.asarray(qw), **kw)
    b = daat_search_batched(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw), trips_per_launch=4, **kw
    )
    np.testing.assert_array_equal(np.asarray(b.doc_ids), np.asarray(a.doc_ids))
    np.testing.assert_array_equal(np.asarray(b.scores), np.asarray(a.scores))
    np.testing.assert_array_equal(
        np.asarray(b.stats.chunks), np.asarray(a.stats.chunks)
    )


def test_engine_multi_trip_requires_fused_chunk(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    with pytest.raises(ValueError, match="fused_chunk"):
        daat_search_batched(
            bm25_index, jnp.asarray(qt[:2]), jnp.asarray(qw[:2]),
            k=5, est_blocks=2, block_budget=2,
            max_bm_per_term=max_blocks_per_term(bm25_index),
            use_kernels=True, fused_chunk=False, trips_per_launch=2,
        )


def test_engine_fused_chunk_requires_kernels(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    with pytest.raises(ValueError, match="use_kernels"):
        daat_search_batched(
            bm25_index, jnp.asarray(qt[:2]), jnp.asarray(qw[:2]),
            k=5, est_blocks=2, block_budget=2,
            max_bm_per_term=max_blocks_per_term(bm25_index),
            use_kernels=False, fused_chunk=True,
        )


def test_sharded_fused_chunk_serve_matches_exhaustive(
    tiny_corpus, bm25_collection, bm25_index, bm25_queries
):
    """Doc-sharded DAAT with the fused chunk step on every rank == oracle."""
    import jax

    from repro.core import exhaustive_search
    from repro.serving import make_sharded_serve_step, shard_corpus, stack_indexes

    enc = bm25_collection
    qt, qw = bm25_queries
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms, 2
    )
    stacked = stack_indexes(shards)
    serve, _, _ = make_sharded_serve_step(
        mesh,
        k=10,
        rho_per_shard=0,  # unused by the daat engine
        max_segs_per_term=0,
        docs_per_shard=dps,
        engine="daat",
        daat_est_blocks=2,
        daat_block_budget=2,
        max_bm_per_term=stacked.max_bm,
        daat_use_kernels=True,
        daat_fused_chunk=True,
    )
    with mesh:
        ss, si = serve(stacked, jnp.asarray(qt[:8]), jnp.asarray(qw[:8]))
    ex = exhaustive_search(bm25_index, jnp.asarray(qt[:8]), jnp.asarray(qw[:8]), k=10)
    np.testing.assert_allclose(
        np.asarray(ss), np.asarray(ex.scores), rtol=1e-4, atol=1e-4
    )
    assert (np.asarray(si) == np.asarray(ex.doc_ids)).mean() > 0.8


def test_sharded_fused_chunk_requires_kernels():
    import jax

    from repro.serving import make_sharded_serve_step

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="daat_use_kernels"):
        make_sharded_serve_step(
            mesh, k=5, rho_per_shard=0, max_segs_per_term=0, docs_per_shard=100,
            engine="daat", max_bm_per_term=3,
            daat_use_kernels=False, daat_fused_chunk=True,
        )


# --------------------------------------------------------------------------
# hypothesis property (skipped — not the whole module — without hypothesis)
# --------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    _settings = settings(
        max_examples=15,
        deadline=None,
        suppress_health_check=[HealthCheck.function_scoped_fixture, HealthCheck.too_slow],
    )
    _HYPOTHESIS = True
except ImportError:  # deterministic sweeps above still run
    _HYPOTHESIS = False

    def given(**kw):  # noqa: D103 - placeholder so decorators below parse
        return lambda f: pytest.mark.skip(reason="hypothesis not installed")(f)

    def _settings(f):
        return f

    class st:  # noqa: D101
        integers = sampled_from = staticmethod(lambda *a, **k: None)


@_settings
@given(
    seed=st.integers(0, 2**31 - 1),
    B=st.sampled_from([1, 2, 4]),
    budget=st.sampled_from([1, 2, 3, 7]),
    k=st.sampled_from([1, 4]),
    processed_frac=st.sampled_from([0.0, 0.5, 1.0]),
)
def test_prop_chunk_step_bitwise(seed, B, budget, k, processed_frac):
    """Any reachable (and some unreachable) chunk state: kernel == jnp body,
    bitwise, for ids, theta, pool scores, and the processed bitmap."""
    idx = _tiny_index()
    rng = np.random.default_rng(seed)
    qt, qw = _random_queries(idx, rng, B, 5)
    ub, processed, pool_s, pool_i, theta = _phase1_state(idx, qt, qw, k=k)
    # random extra processed blocks model a mid-loop trip (phase 1 marks
    # processed_frac=0's baseline; 1.0 drives the all-pruned degenerate)
    extra = jnp.asarray(rng.random((B, idx.n_blocks)) < processed_frac)
    processed = processed | extra
    _assert_step_bitwise(
        idx, qt, qw, (ub, processed, pool_s, pool_i, theta), budget=budget
    )
