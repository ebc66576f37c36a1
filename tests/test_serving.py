"""Serving layer: anytime server, deadline->rho control, doc-sharded search."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exhaustive_search
from repro.core.saat import max_segments_per_term
from repro.metrics.latency import summarize_latencies
from repro.serving import (
    AnytimeServer,
    ServingConfig,
    make_sharded_serve_step,
    run_query_stream,
    shard_corpus,
    stack_indexes,
)


def test_server_exact_matches_exhaustive(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    srv = AnytimeServer(bm25_index, ServingConfig(k=10, rho_ladder=(10**9,), batch_size=8))
    scores, ids = run_query_stream(srv, qt, qw)
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(scores, np.asarray(ex.scores), rtol=1e-4, atol=1e-4)


def test_server_ladder_capped_at_exact(bm25_index):
    srv = AnytimeServer(bm25_index, ServingConfig(rho_ladder=(100, 10**9)))
    assert srv.rho_ladder[-1] == bm25_index.n_postings
    assert srv.rho_ladder[0] == 100


def test_bucketed_exact_rung_is_bounded_and_exact(bm25_index, bm25_queries):
    """The exact level is served at the executable's reach — the sum of its
    width's longest posting lists, not the whole store — and serving at it
    still equals exhaustive scoring (and an uncapped dispatch, bitwise)."""
    qt, qw = bm25_queries
    L = qt.shape[1]
    cfg = dict(k=10, rho_ladder=(10**9,), batch_size=8)
    srv = AnytimeServer(bm25_index, ServingConfig(**cfg, lq_buckets=(L,)))
    top = srv.rho_ladder[-1]
    assert top == bm25_index.n_postings
    longest = np.sort(np.asarray(bm25_index.term_post_count))[::-1]
    reach = int(longest[:L].sum())
    assert srv.served_rho(top, L) == reach < bm25_index.n_postings
    assert srv.executable_key(L, 8, top)[2] == reach
    scores, ids = run_query_stream(srv, qt, qw)
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(scores, np.asarray(ex.scores), rtol=1e-4, atol=1e-4)
    full = srv.engine_fn(top)(jnp.asarray(qt), jnp.asarray(qw))
    np.testing.assert_array_equal(ids, np.asarray(full.doc_ids))
    np.testing.assert_array_equal(scores, np.asarray(full.scores))


@pytest.mark.parametrize("lq_buckets", [None, (4,)])
def test_repeated_query_terms_stay_rank_safe_at_exact_rung(bm25_index, lq_buckets):
    """A query that repeats its longest term would touch more postings than
    its width's reach; the server merges repeats into one slot (weights
    summed), so the exact level still processes every posting and scores the
    query as the sparse vector exhaustive scoring sees."""
    counts = np.asarray(bm25_index.term_post_count)
    t0, t1 = (int(t) for t in np.argsort(-counts)[:2])
    n = bm25_index.n_terms
    qt = np.array([[t0, t0, t0, t1], [t1, n, t1, t0]], np.int32)
    qw = np.array([[0.5, 0.25, 1.0, 2.0], [1.0, 0.0, 0.5, 0.75]], np.float32)
    srv = AnytimeServer(
        bm25_index, ServingConfig(k=10, rho_ladder=(10**9,), lq_buckets=lq_buckets)
    )
    assert srv.served_rho(srv.rho_ladder[-1], 4) < 3 * int(counts[t0]) + int(counts[t1])
    res = srv.search_batch(jnp.asarray(qt), jnp.asarray(qw))
    np.testing.assert_array_equal(np.asarray(res.postings_processed), np.asarray(res.total_postings))
    merged_t = np.array([[t0, n, n, t1], [t1, n, n, t0]], np.int32)
    merged_w = np.array([[1.75, 0, 0, 2.0], [1.5, 0, 0, 0.75]], np.float32)
    want = srv.search_batch(jnp.asarray(merged_t), jnp.asarray(merged_w))
    np.testing.assert_array_equal(np.asarray(res.doc_ids), np.asarray(want.doc_ids))
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(want.scores))
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(
        np.asarray(res.scores), np.asarray(ex.scores), rtol=1e-5, atol=1e-5
    )


def test_deadline_controller_picks_rho(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    srv = AnytimeServer(
        bm25_index,
        ServingConfig(k=10, rho_ladder=(100, 1000, 10000), batch_size=8, deadline_ms=10.0),
    )
    srv.warmup(jnp.asarray(qt[:8]), jnp.asarray(qw[:8]))
    # an impossible deadline must select the smallest rho
    srv.cfg = ServingConfig(k=10, rho_ladder=(100, 1000, 10000), batch_size=8, deadline_ms=1e-9)
    assert srv.pick_rho() == srv.rho_ladder[0]
    # an infinite deadline must select the largest
    srv.cfg = ServingConfig(k=10, rho_ladder=(100, 1000, 10000), batch_size=8, deadline_ms=1e9)
    assert srv.pick_rho() == srv.rho_ladder[-1]


def test_latency_stats():
    s = summarize_latencies([1.0] * 98 + [10.0, 100.0])
    assert s.p50_ms == 1.0
    assert s.max_ms == 100.0
    assert s.tail_ratio > 5


@pytest.mark.serving
def test_search_batch_rejects_off_ladder_rho(bm25_index, bm25_queries):
    """rho=0 (or any off-ladder budget) must raise, not silently fall
    through to the deadline controller (the old `rho or pick_rho()` bug)."""
    qt, qw = bm25_queries
    srv = AnytimeServer(bm25_index, ServingConfig(k=5, rho_ladder=(100, 1000)))
    with pytest.raises(ValueError, match="ladder"):
        srv.search_batch(jnp.asarray(qt[:2]), jnp.asarray(qw[:2]), rho=0)
    with pytest.raises(ValueError, match="ladder"):
        srv.search_batch(jnp.asarray(qt[:2]), jnp.asarray(qw[:2]), rho=777)
    # a real ladder level is honored verbatim
    srv.search_batch(jnp.asarray(qt[:2]), jnp.asarray(qw[:2]), rho=100)
    assert (srv.dispatch_log[-1].rho, srv.dispatch_log[-1].batch) == (100, 2)


@pytest.mark.serving
def test_pick_rho_never_treats_uncalibrated_as_free(bm25_index):
    """An unmeasured level must not look free under a tight deadline."""
    srv = AnytimeServer(
        bm25_index, ServingConfig(rho_ladder=(100, 1000, 10**9), deadline_ms=1.0)
    )
    # nothing calibrated: fall back to the SMALLEST uncalibrated level, never
    # the 10M-posting one the old `pred == 0.0 -> fits` logic selected
    assert srv.pick_rho() == srv.rho_ladder[0]
    # calibrate only the smallest level, cheap enough to fit 1 ms
    srv._cost.us_per_mpost[srv.rho_ladder[0]] = 1.0
    srv._cost.last_update_s[srv.rho_ladder[0]] = 0.0
    # largest CALIBRATED fitting level wins over larger uncalibrated ones
    # (the never-measured exact level stays ineligible however cheap the
    # nearest-level extrapolation makes it look)
    assert srv.pick_rho() == srv.rho_ladder[0]
    # once the big level is measured as cheap, it becomes eligible
    srv._cost.us_per_mpost[srv.rho_ladder[-1]] = 1e-6
    assert srv.pick_rho() == srv.rho_ladder[-1]


@pytest.mark.serving
def test_pick_rho_deadline_override(bm25_index, bm25_queries):
    """The admission queue passes per-batch remaining budgets."""
    qt, qw = bm25_queries
    srv = AnytimeServer(bm25_index, ServingConfig(rho_ladder=(100, 1000, 10000)))
    srv.warmup(jnp.asarray(qt[:4]), jnp.asarray(qw[:4]))
    assert srv.pick_rho() == srv.rho_ladder[-1]  # cfg deadline None -> max
    assert srv.pick_rho(deadline_ms=1e-12) == srv.rho_ladder[0]
    assert srv.pick_rho(deadline_ms=1e9) == srv.rho_ladder[-1]
    assert srv.pick_rho(deadline_ms=None) == srv.rho_ladder[-1]


@pytest.mark.serving
def test_run_query_stream_ragged_final_batch(bm25_index, bm25_queries):
    """N % batch_size != 0: the padded-with-repeats tail must be dropped and
    the kept rows must equal serving everything in one batch."""
    qt, qw = bm25_queries
    N, bs = 10, 4  # final batch holds 2 real + 2 repeated rows
    srv = AnytimeServer(bm25_index, ServingConfig(k=10, rho_ladder=(10**9,), batch_size=bs))
    scores, ids = run_query_stream(srv, qt[:N], qw[:N])
    assert scores.shape == (N, 10) and ids.shape == (N, 10)
    one = srv.search_batch(jnp.asarray(qt[:N]), jnp.asarray(qw[:N]))
    np.testing.assert_array_equal(ids, np.asarray(one.doc_ids))
    np.testing.assert_array_equal(scores, np.asarray(one.scores))
    # the repeated pad rows were served but never reported
    assert [d.batch for d in srv.dispatch_log] == [bs, bs, bs, N]  # then the direct call


@pytest.mark.serving
def test_cost_model_ema_convergence_and_interpolation():
    from repro.metrics.latency import SimulatedClock
    from repro.serving.scheduler import _CostModel

    clock = SimulatedClock()
    m = _CostModel({}, alpha=0.5, clock=clock)
    assert m.predict_us(1_000_000) is None and not m.is_calibrated(1_000_000)
    # EMA converges to a shifted steady state
    m.update(1_000_000, 100.0)  # 100 us / Mpost
    assert m.predict_us(1_000_000) == pytest.approx(100.0)
    for _ in range(40):
        clock.advance(1.0)
        m.update(1_000_000, 300.0)
    assert m.predict_us(1_000_000) == pytest.approx(300.0, rel=1e-3)
    assert m.last_update_s[1_000_000] == pytest.approx(40.0)
    # one calibrated level: above it, clamp to that level's RATE; below it,
    # floor at the level's measured TOTAL — small batches still pay the full
    # launch/dispatch overhead, so rate-scaling 300 us down to 150 us was a
    # systematic under-prediction that admitted infeasible work
    assert m.predict_us(2_000_000) == pytest.approx(600.0, rel=1e-3)
    assert m.predict_us(500_000) == pytest.approx(300.0, rel=1e-3)
    # two calibrated levels: in-between rho interpolates TOTAL cost between
    # the bracketing levels instead of scaling the nearest level's rate —
    # the old rule predicted 8 * 500 = 4000 us for 8M, jumping wildly at the
    # nearest-level boundary; the interpolant is continuous across the ladder
    m.update(10_000_000, 5000.0)  # total 5000 us at 10M
    lo, hi = 300.0, 5000.0  # calibrated totals at 1M and 10M
    assert m.predict_us(8_000_000) == pytest.approx(lo + (hi - lo) * 7 / 9, rel=1e-3)
    assert m.predict_us(1_200_000) == pytest.approx(lo + (hi - lo) * 0.2 / 9, rel=1e-3)
    # calibrated levels predict exactly themselves (interpolant hits knots)
    assert m.predict_us(10_000_000) == pytest.approx(5000.0, rel=1e-3)
    # beyond the top level: clamp to the top level's rate
    assert m.predict_us(20_000_000) == pytest.approx(10_000.0, rel=1e-3)


@pytest.mark.serving
def test_cost_model_low_end_floors_at_boundary_total(bm25_index):
    """Seeding ONLY a high-rho level must not make small-rho work look
    fractionally cheap: a 100k-posting batch pays the same launch/dispatch
    overhead as the measured 5M-posting one, so its prediction floors at the
    boundary level's measured total instead of rate-scaling through the
    origin (the old rule predicted 5000 * 0.1/5 = 100 us and over-admitted)."""
    from repro.serving.scheduler import _CostModel

    m = _CostModel({}, alpha=0.5)
    m.update(5_000_000, 5000.0)  # measured 5000 us total at 5M postings
    # every rho at or below the only calibrated level predicts its total
    assert m.predict_us(5_000_000) == pytest.approx(5000.0)
    assert m.predict_us(1_000_000) == pytest.approx(5000.0)
    assert m.predict_us(100_000) == pytest.approx(5000.0)
    # above it still extrapolates by rate
    assert m.predict_us(10_000_000) == pytest.approx(10_000.0)

    # end to end: with only the big level measured as slow, a deadline that
    # the old origin-scaled estimate called feasible for the small level now
    # correctly falls back to the smallest rung instead of "fitting" rho=100
    srv = AnytimeServer(
        bm25_index, ServingConfig(rho_ladder=(100, 1000, 10**9), deadline_ms=1.0)
    )
    srv._cost.us_per_mpost[srv.rho_ladder[-1]] = 1e9  # seconds total: nothing fits
    srv._cost.last_update_s[srv.rho_ladder[-1]] = 0.0
    assert srv._cost.predict_us(100) == pytest.approx(
        srv._cost.predict_us(srv.rho_ladder[-1])
    )
    assert srv.pick_rho() == srv.rho_ladder[0]


def test_server_rejects_multi_trip_without_fused_chunk(bm25_index):
    """daat_trips_per_launch > 1 batches trips inside the fused kernel."""
    with pytest.raises(ValueError, match="daat_fused_chunk"):
        AnytimeServer(
            bm25_index,
            ServingConfig(engine="daat", daat_use_kernels=True, daat_trips_per_launch=4),
        )
    with pytest.raises(ValueError, match="daat_trips_per_launch"):
        AnytimeServer(
            bm25_index, ServingConfig(engine="daat", daat_trips_per_launch=0)
        )


def test_sharded_daat_rejects_multi_trip_without_fused_chunk(bm25_index):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="daat_fused_chunk"):
        make_sharded_serve_step(
            mesh, k=5, rho_per_shard=0, max_segs_per_term=0, docs_per_shard=100,
            engine="daat", max_bm_per_term=4, daat_use_kernels=True,
            daat_trips_per_launch=2,
        )
    with pytest.raises(ValueError, match="daat_trips_per_launch"):
        make_sharded_serve_step(
            mesh, k=5, rho_per_shard=0, max_segs_per_term=0, docs_per_shard=100,
            engine="daat", max_bm_per_term=4, daat_trips_per_launch=0,
        )


class _ScriptedClock:
    """Clock whose now() returns a scripted sequence (pads with the last)."""

    def __init__(self, times):
        self.times = list(times)
        self.i = 0

    def now(self) -> float:
        t = self.times[min(self.i, len(self.times) - 1)]
        self.i += 1
        return t


@pytest.mark.serving
def test_predict_service_ms_is_shape_keyed_not_linear_in_b(bm25_index, bm25_queries):
    """B=8 and B=32 flushes observing different wall times must produce
    different, NON-linear-in-B predictions (a batch is one executable; the
    old per-query EMA x n_queries over-predicted every large-shape flush)."""
    qt, qw = bm25_queries
    L = qt.shape[1]
    # scripted service times: the B=8 batch takes 10 ms, the B=32 batch 16
    # ms. A SAAT search_batch reads the clock exactly five times (start, the
    # ends of its prep, dispatch and wait, the cost-model calibration stamp)
    # — the script covers two calls, each spending its time in the wait.
    clock = _ScriptedClock(
        [0.0, 0.0, 0.0, 0.010, 0.010] + [0.010, 0.010, 0.010, 0.026, 0.026]
    )
    srv = AnytimeServer(
        bm25_index,
        ServingConfig(k=5, rho_ladder=(10**9,), lq_buckets=(L,)),
        clock=clock,
    )
    reps8 = np.resize(np.arange(qt.shape[0]), 8)
    reps32 = np.resize(np.arange(qt.shape[0]), 32)
    srv.search_batch(jnp.asarray(qt[reps8]), jnp.asarray(qw[reps8]))
    srv.search_batch(jnp.asarray(qt[reps32]), jnp.asarray(qw[reps32]))
    p8 = srv.predict_service_ms(8, L)
    p32 = srv.predict_service_ms(32, L)
    assert p8 == pytest.approx(10.0)
    assert p32 == pytest.approx(16.0)  # observed, NOT 4 * p8 = 40 ms
    assert [d.wait_ms for d in srv.dispatch_log] == pytest.approx([10.0, 16.0])
    assert p32 != pytest.approx(4 * p8)
    # nearest-shape fallback: a smaller unseen shape borrows the closest
    # executable's time unscaled (over-predicts, safe) ...
    assert srv.predict_service_ms(6, L) == pytest.approx(p8)
    # ... a LARGER unseen shape ratio-scales up (a conservative upper bound:
    # under-predicting an unmeasured big executable means late flushes)
    assert srv.predict_service_ms(40, L) == pytest.approx(p32 * 40 / 32)
    # an unseen bucket has no shapes: SAAT falls back to the rho model
    assert srv.predict_service_ms(8, L + 7) >= 0.0


@pytest.mark.serving
def test_observe_bucket_ms_ema_is_per_shape_and_per_rho():
    """EMAs for different shapes — and different rho levels — never mix:
    every SAAT ladder level is its own executable with its own wall time."""

    class _Srv(AnytimeServer):  # bypass engine setup; only the EMA matters
        def __init__(self):
            self.cfg = ServingConfig()
            self.rho_ladder = (100, 1000)
            self._bucket_ms = {}
            self._bucket_conf = {}

    srv = _Srv()
    srv._observe_bucket_ms(4, 8, 10.0, rho=1000)
    srv._observe_bucket_ms(4, 32, 16.0, rho=1000)
    srv._observe_bucket_ms(4, 8, 10.0, rho=1000)
    srv._observe_bucket_ms(4, 8, 2.0, rho=100)  # small budget, small time
    assert srv._bucket_ms[("saat", 4, 8, 1000)] == pytest.approx(10.0)
    assert srv._bucket_ms[("saat", 4, 32, 1000)] == pytest.approx(16.0)
    assert srv._bucket_ms[("saat", 4, 8, 100)] == pytest.approx(2.0)
    # default rho resolves to pick_rho() (= full ladder without a deadline)
    srv._observe_bucket_ms(4, 8, 10.0)
    assert srv._bucket_ms[("saat", 4, 8, 1000)] == pytest.approx(10.0)
    # predictions read the lane they were asked about, never a neighbor level
    assert srv.predict_service_ms(8, 4, rho=100) == pytest.approx(2.0)
    assert srv.predict_service_ms(8, 4, rho=1000) == pytest.approx(10.0)


def test_server_daat_engine_matches_exhaustive(bm25_index, bm25_queries):
    """engine='daat' serves the batched Block-Max engine, rank-safe."""
    qt, qw = bm25_queries
    srv = AnytimeServer(
        bm25_index,
        ServingConfig(k=10, batch_size=8, engine="daat", daat_est_blocks=2, daat_block_budget=2),
    )
    srv.warmup(jnp.asarray(qt[:8]), jnp.asarray(qw[:8]))
    scores, ids = run_query_stream(srv, qt, qw)
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(scores, np.asarray(ex.scores), rtol=1e-4, atol=1e-4)
    assert srv.stats().p50_ms > 0


def test_server_rejects_unknown_engine(bm25_index):
    with pytest.raises(ValueError, match="engine"):
        AnytimeServer(bm25_index, ServingConfig(engine="bmw"))


def test_server_rejects_fused_chunk_without_kernels(bm25_index):
    """daat_fused_chunk fuses the KERNEL chunk step; jnp mode has no fusion."""
    with pytest.raises(ValueError, match="daat_use_kernels"):
        AnytimeServer(
            bm25_index, ServingConfig(engine="daat", daat_fused_chunk=True)
        )


def test_daat_engine_rejects_explicit_rho(bm25_index, bm25_queries):
    """A SAAT budget passed to the daat engine is a caller bug, not a no-op."""
    qt, qw = bm25_queries
    srv = AnytimeServer(
        bm25_index,
        ServingConfig(k=10, engine="daat", daat_est_blocks=2, daat_block_budget=2),
    )
    with pytest.raises(ValueError, match="rho"):
        srv.search_batch(jnp.asarray(qt[:4]), jnp.asarray(qw[:4]), rho=100)


@pytest.mark.parametrize("n_shards", [1, 4])
def test_sharded_serve_matches_exhaustive(tiny_corpus, bm25_collection, bm25_index, bm25_queries, n_shards):
    """Doc-sharded SAAT with k-merge == global exhaustive oracle (1-dev mesh)."""
    enc = bm25_collection
    qt, qw = bm25_queries
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms, n_shards
    )
    stacked = stack_indexes(shards)
    # rho is a STATIC shape: it must cover the shard's postings for rank
    # safety but stay small (a huge literal materializes [rho]-sized arrays
    # per vmapped query)
    rho_exact = max(s.n_postings for s in shards)
    serve, _, _ = make_sharded_serve_step(
        mesh,
        k=10,
        rho_per_shard=rho_exact,
        max_segs_per_term=max(max_segments_per_term(s) for s in shards),
        docs_per_shard=dps,
    )
    with mesh:
        ss, si = serve(stacked, jnp.asarray(qt), jnp.asarray(qw))
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ex.scores), rtol=1e-4, atol=1e-4)
    assert (np.asarray(si) == np.asarray(ex.doc_ids)).mean() > 0.95  # ties may permute


@pytest.mark.parametrize("n_shards", [1, 2])
def test_sharded_daat_serve_matches_exhaustive(
    tiny_corpus, bm25_collection, bm25_index, bm25_queries, n_shards
):
    """Doc-sharded batched DAAT with k-merge == global exhaustive oracle."""
    enc = bm25_collection
    qt, qw = bm25_queries
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms, n_shards
    )
    stacked = stack_indexes(shards)
    assert stacked.max_bm == max(s.max_bm for s in shards)  # build-time bound survives stacking
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
    )
    with mesh:
        ss, si = serve(stacked, jnp.asarray(qt), jnp.asarray(qw))
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ex.scores), rtol=1e-4, atol=1e-4)
    # DAAT's incremental merge permutes ties more than a single top-k pass,
    # so demand only majority id agreement on top of the exact score parity
    assert (np.asarray(si) == np.asarray(ex.doc_ids)).mean() > 0.8


def test_sharded_daat_requires_static_bm_bound(bm25_index):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="max_bm_per_term"):
        make_sharded_serve_step(
            mesh, k=5, rho_per_shard=0, max_segs_per_term=0, docs_per_shard=100,
            engine="daat",
        )


def test_sharded_rho_budget_is_per_shard(tiny_corpus, bm25_collection):
    """A small per-shard budget bounds work identically on every shard."""
    enc = bm25_collection
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms, 2
    )
    stacked = stack_indexes(shards)
    serve, _, _ = make_sharded_serve_step(
        mesh, k=5, rho_per_shard=50,
        max_segs_per_term=max(max_segments_per_term(s) for s in shards),
        docs_per_shard=dps,
    )
    qt = jnp.asarray(np.array([[1, 2, 3]], dtype=np.int32))
    qw = jnp.asarray(np.ones((1, 3), np.float32))
    with mesh:
        ss, si = serve(stacked, qt, qw)
    assert ss.shape == (1, 5) and si.shape == (1, 5)


# ------------------------------------------------------------------------
# sharded-path correctness regressions: pad-doc leak, metadata threading,
# degenerate shard layouts
# ------------------------------------------------------------------------

_I32_MAX = np.iinfo(np.int32).max


def _hand_coo(postings):
    """postings: [(doc, term, weight), ...] -> parallel COO arrays."""
    d = np.array([p[0] for p in postings], dtype=np.int64)
    t = np.array([p[1] for p in postings], dtype=np.int64)
    w = np.array([p[2] for p in postings], dtype=np.float64)
    return d, t, w


def test_sharded_pad_docs_never_alias_real_ids():
    """k > live docs per shard: pad docs (score 0.0) used to survive the
    local top-k and globalize into the NEXT shard's real-id range. They must
    come out as explicit (-inf, INT32_MAX) sentinels instead."""
    from repro.core import build_impact_index

    # 5 docs, one distinct term each, descending weights; 3 shards of 2 =>
    # the final shard is short (1 live doc) AND every shard has fewer live
    # docs than k
    d, t, w = _hand_coo([(i, i, 5.0 - i) for i in range(5)])
    n_docs, n_terms, k = 5, 6, 8
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(d, t, w, n_docs, n_terms, 3)
    stacked = stack_indexes(shards)
    serve, _, _ = make_sharded_serve_step(
        mesh,
        k=k,
        rho_per_shard=max(s.n_postings for s in shards),
        max_segs_per_term=max(max_segments_per_term(s) for s in shards),
        docs_per_shard=dps,
        n_docs_total=n_docs,
    )
    qt = jnp.asarray(np.arange(5, dtype=np.int32)[None, :])
    qw = jnp.ones((1, 5), jnp.float32)
    with mesh:
        ss, si = serve(stacked, qt, qw)
    ss, si = np.asarray(ss)[0], np.asarray(si)[0]
    oracle = build_impact_index(d, t, w, n_docs, n_terms)
    ex = exhaustive_search(oracle, qt, qw, k=n_docs)
    # the live prefix matches the unsharded oracle doc-for-doc ...
    np.testing.assert_allclose(ss[:n_docs], np.asarray(ex.scores)[0], rtol=1e-4, atol=1e-4)
    assert si[:n_docs].tolist() == np.asarray(ex.doc_ids)[0].tolist()
    # ... and the k - n_docs overflow slots are sentinels, NOT aliased docs
    assert np.all(si[n_docs:] == _I32_MAX)
    assert np.all(np.isneginf(ss[n_docs:]))
    assert len(set(si[:n_docs].tolist())) == n_docs  # no duplicate real ids


def test_sharded_meta_threads_real_build_constants(
    tiny_corpus, bm25_collection, bm25_index, bm25_queries
):
    """block_size=64 + non-unit quant scale: the per-shard indexes rebuilt
    inside the shard_map must carry the REAL build constants (the old
    hardcoded 128/1.0/8 mis-mapped block ids to doc ranges and broke the
    sharded DAAT engine on non-default corpora)."""
    enc = bm25_collection
    qt, qw = bm25_queries
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms, 2,
        block_size=64,
    )
    stacked = stack_indexes(shards)
    assert stacked.block_size == 64  # precondition: non-default build
    assert stacked.scale != 1.0  # precondition: non-unit quant scale
    serve, _, _ = make_sharded_serve_step(
        mesh,
        k=10,
        rho_per_shard=0,
        max_segs_per_term=0,
        docs_per_shard=dps,
        engine="daat",
        daat_est_blocks=2,
        daat_block_budget=2,
        max_bm_per_term=stacked.max_bm,
        n_docs_total=tiny_corpus.n_docs,
    )
    with mesh:
        ss, _ = serve(stacked, jnp.asarray(qt), jnp.asarray(qw))
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ex.scores), rtol=1e-4, atol=1e-4)


def test_sharded_short_final_shard_matches_exhaustive(
    tiny_corpus, bm25_collection, bm25_index, bm25_queries
):
    """n_shards not dividing n_docs: the short final shard's out-of-corpus
    tail is masked via n_docs_total and results match the unsharded oracle."""
    enc = bm25_collection
    qt, qw = bm25_queries
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms, 3
    )
    assert 3 * dps > tiny_corpus.n_docs  # precondition: final shard is short
    stacked = stack_indexes(shards)
    serve, _, _ = make_sharded_serve_step(
        mesh,
        k=10,
        rho_per_shard=max(s.n_postings for s in shards),
        max_segs_per_term=max(max_segments_per_term(s) for s in shards),
        docs_per_shard=dps,
        n_docs_total=tiny_corpus.n_docs,
    )
    with mesh:
        ss, si = serve(stacked, jnp.asarray(qt), jnp.asarray(qw))
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ex.scores), rtol=1e-4, atol=1e-4)
    assert np.asarray(si).max() < tiny_corpus.n_docs  # no out-of-corpus ids
    assert (np.asarray(si) == np.asarray(ex.doc_ids)).mean() > 0.95


def test_sharded_empty_shard_serves(tiny_corpus):
    """A shard whose COO mask is empty must build, stack, and serve — and the
    merge must match the unsharded oracle."""
    from repro.core import build_impact_index

    # postings only in docs 0..1; 2 shards of 2 => shard 1 is empty
    d, t, w = _hand_coo([(0, 0, 2.0), (0, 1, 1.0), (1, 2, 3.0)])
    n_docs, n_terms = 4, 5
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(d, t, w, n_docs, n_terms, 2)
    assert shards[1].max_segs == 0  # precondition: second shard IS empty
    stacked = stack_indexes(shards)
    serve, _, _ = make_sharded_serve_step(
        mesh,
        k=n_docs,
        rho_per_shard=max(s.n_postings for s in shards),
        max_segs_per_term=max(1, max(max_segments_per_term(s) for s in shards)),
        docs_per_shard=dps,
        n_docs_total=n_docs,
    )
    qt = jnp.asarray(np.array([[0, 2]], dtype=np.int32))
    qw = jnp.ones((1, 2), jnp.float32)
    with mesh:
        ss, si = serve(stacked, qt, qw)
    ss, si = np.asarray(ss)[0], np.asarray(si)[0]
    oracle = build_impact_index(d, t, w, n_docs, n_terms)
    ex = exhaustive_search(oracle, qt, qw, k=n_docs)
    np.testing.assert_allclose(ss, np.asarray(ex.scores)[0], rtol=1e-4, atol=1e-4)
    assert si[0] == 1 and si[1] == 0  # scored docs lead; zero-score docs trail
    assert set(si.tolist()) == set(range(n_docs))
