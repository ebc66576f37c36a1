"""Continuous-batching admission queue + Lq-bucketed serving suite.

Everything here is deterministic: time is a ``SimulatedClock`` the tests
advance explicitly, arrival schedules come from seeded numpy RNGs, and the
hypothesis properties run under the derandomized ``serving-ci`` profile in
CI. The two core claims pinned by this file:

  * **Bucketing is invisible**: serving through the (B, Lq-bucket) grid is
    bit-identical in doc ids AND scores to padding at max Lq, both engines.
  * **The queue is lossless and on time**: every submitted request completes
    exactly once, order is FIFO within a bucket (modulo DAAT's declared
    within-flush survivor sort), and no batch flushes after its oldest
    request's deadline minus the predicted service time.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exhaustive_search
from repro.metrics.latency import Clock, HybridClock, SimulatedClock, SystemClock
from repro.serving import (
    AdmissionQueue,
    AnytimeServer,
    ServingConfig,
    SurvivorPredictor,
    bucket_for,
    effective_lq,
    make_bucketed_serve_step,
    normalize_buckets,
    pad_to_width,
    shard_corpus,
    stack_indexes,
)
from repro.serving.queue import replay_arrivals

pytestmark = pytest.mark.serving

EXACT = (10**9,)  # rho ladder that caps to the index's exact level


# --------------------------------------------------------------------------
# clocks + bucketing helpers
# --------------------------------------------------------------------------


def test_simulated_clock_semantics():
    c = SimulatedClock(1.5)
    assert c.now() == 1.5
    assert c.advance(0.25) == 1.75
    assert c.advance_to(1.0) == 1.75  # never backwards
    assert c.advance_to(2.0) == 2.0
    with pytest.raises(ValueError):
        c.advance(-0.1)
    assert isinstance(c, Clock) and isinstance(SystemClock(), Clock)


def test_system_clock_monotonic():
    c = SystemClock()
    a = c.now()
    assert c.now() >= a


def test_hybrid_clock_accrues_real_work():
    import time

    c = HybridClock(5.0)
    assert c.now() >= 5.0
    t0 = c.now()
    time.sleep(0.01)  # real work between calls must advance simulated time
    assert c.now() - t0 >= 0.009
    t1 = c.advance_to(100.0)
    assert t1 >= 100.0 and c.advance_to(0.0) >= 100.0  # never backwards
    assert isinstance(c, SimulatedClock)  # accepted by replay_arrivals


def test_bucket_helpers():
    assert normalize_buckets([8, 4, 8]) == (4, 8)
    with pytest.raises(ValueError):
        normalize_buckets([0, 4])
    assert bucket_for(3, (4, 8)) == 4
    assert bucket_for(4, (4, 8)) == 4
    assert bucket_for(5, (4, 8)) == 8
    # overflow rounds up to a multiple of the top bucket (bounded grid)
    assert bucket_for(9, (4, 8)) == 16
    assert bucket_for(17, (4, 8)) == 24


def test_effective_lq_and_pad(bm25_index):
    n_terms = bm25_index.n_terms
    qt = np.array([[1, n_terms, 3, n_terms], [2, 4, n_terms, n_terms]], np.int32)
    qw = np.array([[1.0, 0.0, 2.0, 0.0], [1.0, 0.5, 0.0, 0.0]], np.float32)
    assert effective_lq(qt, qw, n_terms) == 3  # interior pad never sliced
    t, w = pad_to_width(qt, qw, 6, n_terms)
    assert t.shape == (2, 6) and np.all(t[:, 4:] == n_terms) and np.all(w[:, 4:] == 0)
    t2, w2 = pad_to_width(t, w, 3, n_terms)  # dead columns may be sliced
    assert t2.shape == (2, 3)
    with pytest.raises(ValueError, match="live"):
        pad_to_width(qt, qw, 2, n_terms)  # would drop column 2's live term


# --------------------------------------------------------------------------
# bucketed serving == max-Lq pad, bit-identical (deterministic versions)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["saat", "daat"])
def test_bucketed_serving_bit_identical(bm25_index, bm25_queries, engine):
    qt, qw = bm25_queries
    L = qt.shape[1]
    kw = dict(k=10, rho_ladder=EXACT, daat_est_blocks=2, daat_block_budget=2, engine=engine)
    ref = AnytimeServer(bm25_index, ServingConfig(**kw))
    buk = AnytimeServer(bm25_index, ServingConfig(**kw, lq_buckets=(2, 4, L)))
    for lo, w in [(0, L), (4, 3), (8, 2), (12, 1)]:  # mixed widths incl. truncated
        bt, bw = qt[lo : lo + 8, :w], qw[lo : lo + 8, :w]
        r1 = ref.search_batch(jnp.asarray(bt), jnp.asarray(bw))
        r2 = buk.search_batch(jnp.asarray(bt), jnp.asarray(bw))
        assert np.array_equal(np.asarray(r1.doc_ids), np.asarray(r2.doc_ids))
        assert np.array_equal(np.asarray(r1.scores), np.asarray(r2.scores))


def test_bucketed_server_serves_smaller_executables(bm25_index, bm25_queries):
    """Short-query traffic really lands on a narrow bucket, not max Lq."""
    qt, qw = bm25_queries
    srv = AnytimeServer(
        bm25_index, ServingConfig(k=5, rho_ladder=EXACT, lq_buckets=(2, qt.shape[1]))
    )
    srv.search_batch(jnp.asarray(qt[:4, :2]), jnp.asarray(qw[:4, :2]))
    top = srv.rho_ladder[-1]
    assert ("saat", 2, 4, top) in srv._bucket_ms  # narrow bucket was exercised
    srv.search_batch(jnp.asarray(qt[:4]), jnp.asarray(qw[:4]))
    assert ("saat", qt.shape[1], 4, top) in srv._bucket_ms


def test_warmup_calibrates_every_bucket_from_a_wide_sample(bm25_index, bm25_queries):
    """A full-width calibration sample must still warm the NARROW buckets
    (slice to shape; which live terms survive is irrelevant to compilation)."""
    qt, qw = bm25_queries
    L = qt.shape[1]
    srv = AnytimeServer(
        bm25_index, ServingConfig(k=5, rho_ladder=EXACT, lq_buckets=(2, 4, L))
    )
    srv.warmup(jnp.asarray(qt[:4]), jnp.asarray(qw[:4]), batch_sizes=(4,))
    assert {b for (_, b, _, _) in srv._bucket_ms} == {2, 4, L}


def test_bucketed_sharded_serve_matches_exhaustive(tiny_corpus, bm25_collection, bm25_index, bm25_queries):
    import jax

    from repro.core.saat import max_segments_per_term

    enc = bm25_collection
    qt, qw = bm25_queries
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shards, dps = shard_corpus(
        enc.doc_idx, enc.term_idx, enc.weights, tiny_corpus.n_docs, enc.n_terms, 2
    )
    stacked = stack_indexes(shards)
    serve, _, _ = make_bucketed_serve_step(
        mesh,
        lq_buckets=(2, qt.shape[1]),
        n_terms=enc.n_terms,
        k=10,
        rho_per_shard=max(s.n_postings for s in shards),
        max_segs_per_term=max(max_segments_per_term(s) for s in shards),
        docs_per_shard=dps,
    )
    with mesh:
        ss, si = serve(stacked, jnp.asarray(qt), jnp.asarray(qw))
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ex.scores), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# admission queue mechanics
# --------------------------------------------------------------------------


def _queue_server(index, L, *, engine="saat", clock=None, buckets=None, **cfg_kw):
    cfg = ServingConfig(
        k=10,
        rho_ladder=EXACT,
        engine=engine,
        daat_est_blocks=2,
        daat_block_budget=2,
        lq_buckets=buckets if buckets is not None else (2, 4, L),
        **cfg_kw,
    )
    return AnytimeServer(index, cfg, clock=clock or SimulatedClock())


def test_queue_requires_width_grid(bm25_index):
    srv = AnytimeServer(bm25_index, ServingConfig(rho_ladder=EXACT), clock=SimulatedClock())
    with pytest.raises(ValueError, match="lq_buckets"):
        AdmissionQueue(srv, batch_shapes=(4,))
    AdmissionQueue(srv, batch_shapes=(4,), max_lq=8)  # explicit width grid is enough


def test_queue_rejects_bad_submissions(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    srv = _queue_server(bm25_index, qt.shape[1])
    q = AdmissionQueue(srv, batch_shapes=(4,))
    with pytest.raises(ValueError, match="deadline"):
        q.submit(qt[0], qw[0], deadline_ms=0.0)
    with pytest.raises(ValueError, match="shape"):
        q.submit(qt[0], qw[0][:2], deadline_ms=5.0)
    with pytest.raises(ValueError, match="batch_shapes"):
        AdmissionQueue(srv, batch_shapes=())


def test_queue_flushes_when_full(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock)
    # same effective width -> same bucket lane for all four
    t3, w3 = np.array([1, 2, 3], np.int32), np.ones(3, np.float32)
    rids = [q.submit(t3, w3, deadline_ms=100.0) for _ in range(4)]
    # the 4th admission fills the largest shape -> immediate flush, no time passed
    comps = q.take_completions()
    assert sorted(c.rid for c in comps) == rids and q.pending() == 0
    assert q.flush_log[-1].reason == "full" and q.flush_log[-1].batch_shape == 4
    assert not q.flush_log[-1].violation


def test_queue_deadline_flush_uses_smallest_covering_shape(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2, 8), clock=clock)
    q.submit(np.array([1, 2], np.int32), np.ones(2, np.float32), deadline_ms=10.0)
    assert q.poll() == []  # not due yet
    due = q.next_due()
    assert due == pytest.approx(0.010)  # uncalibrated predicted service = 0
    clock.advance_to(due)
    comps = q.poll()
    assert len(comps) == 1 and comps[0].batch_shape == 2  # padded to smallest shape
    assert q.flush_log[-1].reason == "deadline" and not q.flush_log[-1].violation
    assert comps[0].wait_ms == pytest.approx(10.0)


def test_queue_partitions_by_bucket(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2,), clock=clock)
    q.submit(np.array([1], np.int32), np.ones(1, np.float32), deadline_ms=50.0)  # bucket 2
    q.submit(np.array([1, 2, 3], np.int32), np.ones(3, np.float32), deadline_ms=50.0)  # bucket 4
    assert q.pending() == 2  # different lanes: no cross-bucket coalescing
    comps = q.drain()
    assert {c.bucket for c in comps} == {2, 4}
    assert all(f.reason == "drain" for f in q.flush_log)


def test_queue_completions_match_direct_serving(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock)
    for i in range(6):
        clock.advance(0.001)
        q.submit(qt[i], qw[i], deadline_ms=20.0)
    comps = {c.rid: c for c in q.drain()}
    ref = AnytimeServer(bm25_index, ServingConfig(k=10, rho_ladder=EXACT))
    direct = ref.search_batch(jnp.asarray(qt[:6]), jnp.asarray(qw[:6]))
    for i in range(6):
        assert np.array_equal(comps[i].doc_ids, np.asarray(direct.doc_ids)[i])
        assert np.array_equal(comps[i].scores, np.asarray(direct.scores)[i])
        # SAAT completions record the ladder level actually served
        assert comps[i].rho == srv.rho_ladder[-1]


# --------------------------------------------------------------------------
# the simulated-clock serving harness (acceptance test)
# --------------------------------------------------------------------------


def _mixed_lq_requests(qt, qw, n, rng):
    """Sample n requests with mixed widths from the padded query matrix."""
    L = qt.shape[1]
    widths = rng.choice([1, 2, 3, L], size=n, p=[0.2, 0.3, 0.2, 0.3])
    picks = rng.integers(0, qt.shape[0], size=n)
    return [np.asarray(qt[q, :w]) for q, w in zip(picks, widths)], [
        np.asarray(qw[q, :w]) for q, w in zip(picks, widths)
    ]


def test_queue_poisson_stream_500_requests(bm25_index, bm25_queries):
    """>=500 Poisson arrivals, mixed Lq, simulated clock: the tentpole claim.

    Asserts zero deadline-policy violations, zero dropped/duplicated/
    reordered-beyond-policy requests, and doc ids bit-identical to serving
    the same requests directly via ``search_batch`` at max rho with max-Lq
    padding (no bucketing).
    """
    qt, qw = bm25_queries
    L = qt.shape[1]
    N = 500
    rng = np.random.default_rng(7)
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, L, clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(4, 16), clock=clock)

    terms, weights = _mixed_lq_requests(qt, qw, N, rng)
    arrivals = np.cumsum(rng.exponential(0.002, size=N))  # ~500 qps
    deadlines = rng.uniform(20.0, 60.0, size=N)
    comps = replay_arrivals(q, arrivals.tolist(), terms, weights, deadlines.tolist())

    # lossless: every rid exactly once
    assert sorted(c.rid for c in comps) == list(range(N))
    assert q.n_submitted == q.n_completed == N
    # on time: no flush after (oldest deadline - predicted service - safety)
    assert q.n_violations == 0
    assert all(f.reason in ("full", "deadline") for f in q.flush_log)
    # ordered within policy: SAAT keeps FIFO per bucket
    per_bucket: dict = {}
    for c in comps:
        per_bucket.setdefault(c.bucket, []).append(c.rid)
    for bucket, rids in per_bucket.items():
        assert rids == sorted(rids), f"bucket {bucket} completions reordered"
    # every completion waited no longer than its own deadline
    for c in comps:
        assert c.flush_s <= c.deadline_s + 1e-9

    # bit-identical to direct max-rho serving with max-Lq padding
    ref = AnytimeServer(bm25_index, ServingConfig(k=10, rho_ladder=EXACT))
    rt = np.full((N, L), bm25_index.n_terms, np.int32)
    rw = np.zeros((N, L), np.float32)
    for i, (t, w) in enumerate(zip(terms, weights)):
        rt[i, : len(t)], rw[i, : len(w)] = t, w
    by_rid = sorted(comps, key=lambda c: c.rid)
    for lo in range(0, N, 100):
        direct = ref.search_batch(jnp.asarray(rt[lo : lo + 100]), jnp.asarray(rw[lo : lo + 100]))
        ids = np.asarray(direct.doc_ids)
        for i in range(100):
            assert np.array_equal(by_rid[lo + i].doc_ids, ids[i])


def test_queue_daat_straggler_coscheduling(bm25_index, bm25_queries):
    """DAAT queue: survivor predictor learns, batches stay FIFO-prefix sets."""
    qt, qw = bm25_queries
    L = qt.shape[1]
    N = 80
    rng = np.random.default_rng(11)
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, L, engine="daat", clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(4, 8), clock=clock)
    terms, weights = _mixed_lq_requests(qt, qw, N, rng)
    arrivals = np.cumsum(rng.exponential(0.001, size=N))
    comps = replay_arrivals(q, arrivals.tolist(), terms, weights, [30.0] * N)

    assert sorted(c.rid for c in comps) == list(range(N))
    assert q.n_violations == 0
    # WorkStats history reached the predictor
    assert q.survivors._by_lq and q.survivors.predict(2) >= 0.0
    # policy boundary: a flush may permute rids internally (survivor sort)
    # but always consumes a contiguous FIFO prefix of its bucket lane
    seen: dict = {}
    for f in q.flush_log:
        lane = seen.setdefault(f.bucket, [])
        assert min(f.rids) > (max(lane) if lane else -1)
        lane.extend(f.rids)
    # and ids still match direct unbucketed daat serving
    ref = AnytimeServer(
        bm25_index,
        ServingConfig(k=10, engine="daat", daat_est_blocks=2, daat_block_budget=2),
    )
    rt = np.full((N, L), bm25_index.n_terms, np.int32)
    rw = np.zeros((N, L), np.float32)
    for i, (t, w) in enumerate(zip(terms, weights)):
        rt[i, : len(t)], rw[i, : len(w)] = t, w
    direct = ref.search_batch(jnp.asarray(rt), jnp.asarray(rw))
    ids = np.asarray(direct.doc_ids)
    for c in comps:
        assert np.array_equal(c.doc_ids, ids[c.rid])


def test_queue_separates_infeasible_from_violation(bm25_index, bm25_queries):
    """A deadline unmeetable at ADMISSION is infeasibility, not a policy
    violation; a missed-but-meetable due instant is a violation."""
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    # make service expensive in the model's eyes: 50 ms predicted per flush
    srv._bucket_ms[("saat", 4, 2, srv.rho_ladder[-1])] = 50.0  # whole-batch wall ms at shape 2
    q = AdmissionQueue(srv, batch_shapes=(2,), clock=clock)
    t3, w3 = np.array([1, 2, 3], np.int32), np.ones(3, np.float32)
    # infeasible: 10 ms budget < 50 ms predicted -> due is before arrival
    q.submit(t3, w3, deadline_ms=10.0)
    q.poll()
    assert q.flush_log[-1].infeasible and not q.flush_log[-1].violation
    # violation: 100 ms budget is meetable (due = +50 ms) but we poll late
    q.submit(t3, w3, deadline_ms=100.0)
    clock.advance(0.080)  # overslept past the 50 ms due instant
    q.poll()
    assert q.flush_log[-1].violation and not q.flush_log[-1].infeasible
    assert q.n_violations == 1 and q.n_infeasible == 1


# --------------------------------------------------------------------------
# degrade-instead-of-violate: the anytime SLO autopilot
# --------------------------------------------------------------------------


def _overload_server(index, *, clock):
    """SAAT server with a scripted per-(shape, rho) service model.

    Ladder has three levels; only the smallest and the full budget are
    *calibrated* (directly measured) — the middle level exists but was never
    timed, so the degrade policy must never pick it on faith. Both fixed
    levels sit below the exact level of a width-4 bucket.
    """
    cfg = ServingConfig(k=10, rho_ladder=(100, 250) + EXACT, lq_buckets=(4,))
    srv = AnytimeServer(index, cfg, clock=clock)
    assert len(srv.rho_ladder) == 3, srv.rho_ladder
    small, full = srv.rho_ladder[0], srv.rho_ladder[-1]
    srv._bucket_ms.update(
        {
            ("saat", 4, 2, full): 20.0,  # whole-flush wall ms
            ("saat", 4, 4, full): 60.0,
            ("saat", 4, 2, small): 5.0,
            ("saat", 4, 4, small): 15.0,
        }
    )
    return srv, small, full


def _overload_schedule():
    """Three requests, 100 ms deadlines, arrival rate sized so full-rho
    service cannot meet them: the third arrival (t=75ms) jumps the covering
    shape from 2 to 4, moving the due instant (oldest deadline - predicted
    service) from t=80ms back to t=40ms — already in the past, but after the
    oldest ARRIVAL (t=0), so missing it is a scheduling violation rather
    than admission infeasibility. 25 ms remain; full rho needs 60."""
    t3, w3 = np.array([1, 2, 3], np.int32), np.ones(3, np.float32)
    return [0.0, 0.070, 0.075], [t3] * 3, [w3] * 3, [100.0] * 3


def test_overload_replay_violates_without_degradation(bm25_index):
    clock = SimulatedClock()
    srv, small, full = _overload_server(bm25_index, clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock)
    arrivals, ts, ws, dl = _overload_schedule()
    comps = replay_arrivals(q, arrivals, ts, ws, dl)
    assert q.n_violations >= 1 and q.n_degraded == 0
    # every flush records the budget actually served (the full ladder level)
    assert [f.rho for f in q.flush_log] == [full] * len(q.flush_log)
    # at max rho, queue-served ids stay bit-identical to direct serving
    ref = AnytimeServer(
        bm25_index, ServingConfig(k=10, rho_ladder=(100, 250) + EXACT, lq_buckets=(4,))
    )
    direct = ref.search_batch(jnp.asarray(ts[0][None, :]), jnp.asarray(ws[0][None, :]))
    direct_ids = np.asarray(direct.doc_ids)[0]
    for c in comps:
        assert c.rho == full
        assert np.array_equal(c.doc_ids, direct_ids)


def test_overload_replay_degrades_instead_of_violating(bm25_index):
    clock = SimulatedClock()
    srv, small, full = _overload_server(bm25_index, clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock, degrade_rho=True)
    arrivals, ts, ws, dl = _overload_schedule()
    comps = replay_arrivals(q, arrivals, ts, ws, dl)
    # the identical overload produces ZERO violations: the overloaded flush
    # served the largest calibrated budget that still fit (the small level)
    assert q.n_violations == 0
    assert q.n_degraded >= 1
    assert all(f.rho == small for f in q.flush_log if f.rho != full)
    assert any(f.rho == small for f in q.flush_log)
    # every completion met its deadline and audits the budget it was served
    for c in comps:
        assert c.flush_s <= c.deadline_s + 1e-9
        assert c.rho in (small, full)
    # degraded ids match direct serving at the SAME degraded budget
    ref = AnytimeServer(
        bm25_index, ServingConfig(k=10, rho_ladder=(100, 250) + EXACT, lq_buckets=(4,))
    )
    direct = ref.search_batch(
        jnp.asarray(ts[0][None, :]), jnp.asarray(ws[0][None, :]), rho=small
    )
    direct_ids = np.asarray(direct.doc_ids)[0]
    for c in comps:
        if c.rho == small:
            assert np.array_equal(c.doc_ids, direct_ids)


def test_pick_degraded_rho_prefers_largest_calibrated_fit(bm25_index):
    clock = SimulatedClock()
    srv, small, full = _overload_server(bm25_index, clock=clock)
    mid = srv.rho_ladder[1]
    assert srv.pick_degraded_rho(4, 4, 100.0) == full  # everything fits
    assert srv.pick_degraded_rho(4, 4, 25.0) == small  # only small fits
    # the uncalibrated middle level is never picked on faith, even though
    # its (interpolated) cost-model guess might fit
    assert mid not in (srv.pick_degraded_rho(4, 4, b) for b in (1.0, 25.0, 100.0))
    # nothing fits -> the smallest calibrated level is the least-late choice
    assert srv.pick_degraded_rho(4, 4, 1.0) == small
    # nothing calibrated at all -> defer to pick_rho's deadline logic
    cold = AnytimeServer(
        bm25_index,
        ServingConfig(k=10, rho_ladder=(100, 250) + EXACT, lq_buckets=(4,)),
        clock=SimulatedClock(),
    )
    assert cold.pick_degraded_rho(4, 4, 25.0) == cold.pick_rho(deadline_ms=25.0)


def test_degrade_rho_policy_validation(bm25_index, bm25_queries):
    qt, _ = bm25_queries
    clock = SimulatedClock()
    saat = _queue_server(bm25_index, qt.shape[1], clock=clock)
    with pytest.raises(ValueError, match="at most one"):
        AdmissionQueue(saat, clock=clock, dynamic_rho=True, degrade_rho=True)
    daat = _queue_server(bm25_index, qt.shape[1], engine="daat", clock=clock)
    with pytest.raises(ValueError, match="rho"):
        AdmissionQueue(daat, clock=clock, degrade_rho=True)


# --------------------------------------------------------------------------
# the effectiveness harness, wired to real serving
# --------------------------------------------------------------------------


def test_rho_effectiveness_sweep_reports_per_level_loss(
    tiny_corpus, bm25_index, bm25_queries
):
    from repro.metrics.ir_metrics import (
        cheapest_rho_within_loss,
        mrr_at_k,
        rho_effectiveness_sweep,
    )

    qt, qw = bm25_queries
    qrels = np.asarray(tiny_corpus.qrels)
    srv = AnytimeServer(
        bm25_index,
        ServingConfig(k=20, rho_ladder=(200, 1000) + EXACT, batch_size=8),
        clock=SimulatedClock(),
    )
    rows = rho_effectiveness_sweep(srv, qt, qw, qrels, recall_k=20)
    assert [r["rho"] for r in rows] == list(srv.rho_ladder)
    # the exhaustive level anchors the loss scale at exactly zero
    assert rows[-1]["exact"] and rows[-1]["loss_mrr"] == 0.0
    assert all(r["loss_mrr"] >= 0.0 and r["loss_recall"] >= 0.0 for r in rows)
    # exact-level metrics equal the rank-safe exhaustive oracle's
    ex = exhaustive_search(bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=20)
    assert rows[-1]["mrr"] == pytest.approx(mrr_at_k(np.asarray(ex.doc_ids), qrels, 10))
    # the 3%-tolerance selector always finds a level (exhaustive qualifies)
    best = cheapest_rho_within_loss(rows, max_loss=0.03)
    assert best in srv.rho_ladder


def _replay_server(index, L, *, clock):
    """Single-bucket SAAT server with a scripted per-(shape, rho) model."""
    cfg = ServingConfig(k=10, rho_ladder=(200, 1000) + EXACT, lq_buckets=(L,))
    srv = AnytimeServer(index, cfg, clock=clock)
    small, full = srv.rho_ladder[0], srv.rho_ladder[-1]
    srv._bucket_ms.update(
        {
            ("saat", L, 2, full): 20.0,
            ("saat", L, 4, full): 60.0,
            ("saat", L, 2, small): 5.0,
            ("saat", L, 4, small): 15.0,
        }
    )
    return srv, small, full


def test_replay_effectiveness_accounts_per_served_rho(
    tiny_corpus, bm25_index, bm25_queries
):
    """Two bursts through a degrading queue: the loose-deadline burst serves
    the full budget, the tight one degrades — and the report groups
    effectiveness by the rho each request was ACTUALLY served at."""
    from repro.metrics.ir_metrics import replay_effectiveness

    qt, qw = bm25_queries
    L = qt.shape[1]
    qrels = np.asarray(tiny_corpus.qrels)[:8]
    clock = SimulatedClock()
    srv, small, full = _replay_server(bm25_index, L, clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock, degrade_rho=True)
    # burst A (t=0..3ms, 200 ms deadlines): fills to shape 4 and fits the
    # full budget. burst B (t=50..53ms, 30 ms deadlines): the third arrival
    # jumps the covering shape to 4, whose predicted full-rho service no
    # longer fits the remaining ~28 ms -> that flush degrades to the small
    # level; the straggler then flushes alone, on time, at full rho.
    arrivals = [0.0, 0.001, 0.002, 0.003, 0.050, 0.051, 0.052, 0.053]
    deadlines = [200.0] * 4 + [30.0] * 4
    rep = replay_effectiveness(
        q,
        arrivals,
        [qt[i] for i in range(8)],
        [qw[i] for i in range(8)],
        deadlines,
        qrels,
        recall_k=10,
    )
    assert rep["n_requests"] == 8
    assert rep["violations"] == 0
    assert rep["degraded_flushes"] == 1
    assert {(g["rho"], g["n_queries"]) for g in rep["by_rho"]} == {(small, 3), (full, 5)}
    for g in rep["by_rho"] + [rep["overall"]]:
        assert 0.0 <= g["mrr"] <= 1.0 and 0.0 <= g["recall"] <= 1.0
    assert "p99_ms" in rep["wait_ms"]


def test_effectiveness_surface_shifts_traffic_down_the_ladder(
    tiny_corpus, bm25_index, bm25_queries
):
    """Tightening the deadline moves served traffic down the rho ladder;
    every deadline point gets a FRESH queue so rows are independent."""
    from repro.metrics.ir_metrics import effectiveness_surface

    qt, qw = bm25_queries
    L = qt.shape[1]
    qrels = np.asarray(tiny_corpus.qrels)[:4]
    _, small, full = _replay_server(bm25_index, L, clock=SimulatedClock())

    def factory(deadline_ms):
        clock = SimulatedClock()
        srv, _, _ = _replay_server(bm25_index, L, clock=clock)
        return AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock, degrade_rho=True)

    arrivals = [0.0, 0.001, 0.002, 0.003]
    rows = effectiveness_surface(
        factory,
        [200.0, 30.0],
        arrivals,
        [qt[i] for i in range(4)],
        [qw[i] for i in range(4)],
        qrels,
        recall_k=10,
    )
    assert [r["deadline_ms"] for r in rows] == [200.0, 30.0]
    loose, tight = rows
    assert loose["degraded_flushes"] == 0 and loose["violations"] == 0
    assert tight["degraded_flushes"] >= 1 and tight["violations"] == 0
    # the loose deadline serves everything at the full budget; tightening it
    # pushes part of the traffic down the ladder
    assert {g["rho"] for g in loose["by_rho"]} == {full}
    assert small in {g["rho"] for g in tight["by_rho"]}


def test_flush_pads_with_inert_sentinel_rows(bm25_index, bm25_queries):
    """A short flush pads with all-sentinel rows (pad term ids, zero weights)
    — never by repeating the last real request, which burned DAAT while_loop
    work on a duplicate's survivors — and only the n_real rows ever reach the
    SurvivorPredictor or the per-request accounting."""
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], engine="daat", clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(4,), clock=clock)
    captured = {}
    real_search = srv.search_batch

    def spy(qt_, qw_, rho=None):
        captured["qt"], captured["qw"] = np.asarray(qt_), np.asarray(qw_)
        return real_search(qt_, qw_, rho=rho)

    srv.search_batch = spy
    observed: list = []
    real_observe = q.survivors.observe
    q.survivors.observe = lambda lq, s: (observed.append((lq, s)), real_observe(lq, s))[1]
    t3, w3 = np.array([1, 2, 3], np.int32), np.ones(3, np.float32)
    q.submit(t3, w3, deadline_ms=10.0)
    comps = q.drain()
    assert len(comps) == 1 and captured["qt"].shape[0] == 4
    n_terms = bm25_index.n_terms
    # rows past n_real are inert sentinels, not copies of the last request
    assert np.all(captured["qt"][1:] == n_terms) and np.all(captured["qw"][1:] == 0.0)
    # only the single real request reached the survivor predictor
    assert len(observed) == 1 and q.flush_log[-1].n_real == 1
    # the service-time EMA is keyed by the flushed executable shape
    assert ("daat", 4, 4, None) in srv._bucket_ms
    # and the real row's results are untouched by the sentinel pads
    ref = AnytimeServer(
        bm25_index,
        ServingConfig(k=10, engine="daat", daat_est_blocks=2, daat_block_budget=2),
    )
    rt, rw = pad_to_width(t3[None, :], w3[None, :], 4, n_terms)
    direct = ref.search_batch(jnp.asarray(rt), jnp.asarray(rw))
    assert np.array_equal(comps[0].doc_ids, np.asarray(direct.doc_ids)[0])
    assert np.array_equal(comps[0].scores, np.asarray(direct.scores)[0])


def test_survivor_predictor_ema():
    p = SurvivorPredictor(alpha=0.5)
    assert p.predict(3) == 0.0  # cold start
    p.observe(3, 10.0)
    assert p.predict(3) == 10.0
    p.observe(3, 20.0)
    assert p.predict(3) == pytest.approx(15.0)
    assert p.predict(7) == pytest.approx(15.0)  # nearest observed key (3)
    p.observe(7, 100.0)
    assert p.predict(7) == 100.0


def test_survivor_predictor_nearest_key_beats_global():
    """Unseen Lq under a bimodal stream: the nearest observed key predicts,
    not the global EMA (which describes NO query in a bimodal mix)."""
    p = SurvivorPredictor(alpha=0.2)
    p.observe(2, 5.0)
    p.observe(30, 400.0)
    # global EMA is 0.8*5 + 0.2*400 = 84 — wrong for BOTH modes
    assert p._global == pytest.approx(84.0)
    assert p.predict(3) == pytest.approx(5.0)  # nearest is 2
    assert p.predict(28) == pytest.approx(400.0)  # nearest is 30
    assert p.predict(16) == pytest.approx(5.0)  # tie |2-16|==|30-16| -> smaller
    assert p.predict(2) == pytest.approx(5.0)  # exact keys still exact


def test_queue_bimodal_lq_coschedules_with_neighbor(bm25_index, bm25_queries):
    """DAAT survivor sort under a bimodal stream: an UNSEEN Lq rides with its
    neighboring mode instead of the global EMA. With history at Lq 4 (cheap)
    and Lq 30 (expensive), a first-ever Lq-3 request must tie with the Lq-4
    mode — stable FIFO keeps it first — where the old global fallback
    predicted 84 survivors and bumped it behind the cheap Lq-4 request."""
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], engine="daat", clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2,), clock=clock)
    q.survivors.observe(4, 5.0)
    q.survivors.observe(30, 400.0)
    assert q.survivors._global == pytest.approx(84.0)  # describes no mode
    captured = {}
    real_search = srv.search_batch

    def spy(qt_, qw_, rho=None):
        captured["qt"] = np.asarray(qt_)
        return real_search(qt_, qw_, rho=rho)

    srv.search_batch = spy
    n_terms = bm25_index.n_terms
    # both requests land in bucket 4 (same lane): Lq 3 first, then Lq 4
    q.submit(np.array([1, 2, 3], np.int32), np.ones(3, np.float32), deadline_ms=50.0)
    q.submit(np.array([4, 5, 6, 7], np.int32), np.ones(4, np.float32), deadline_ms=50.0)
    q.drain()
    # nearest-key predicts Lq 3 ~ Lq 4: tie -> FIFO keeps the Lq-3 row first
    assert captured["qt"].shape[0] == 2
    assert int((captured["qt"][0] != n_terms).sum()) == 3
    assert int((captured["qt"][1] != n_terms).sum()) == 4


def test_queue_max_wait_flushes_deadline_less_traffic(bm25_index, bm25_queries):
    """The starvation bug: a non-full bucket of deadline-less requests was
    never due (next_due() = None) and sat until drain(). max_wait_s bounds
    the wait at oldest-arrival + max_wait, pinned on a simulated clock."""
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    t3, w3 = np.array([1, 2, 3], np.int32), np.ones(3, np.float32)

    # without the age bound the request starves: nothing is ever due
    starved = AdmissionQueue(srv, batch_shapes=(4,), clock=clock)
    starved.submit(t3, w3, deadline_ms=None)
    assert starved.next_due() is None
    clock.advance(3600.0)
    assert starved.poll() == [] and starved.pending() == 1

    bounded = AdmissionQueue(srv, batch_shapes=(4,), clock=clock, max_wait_s=0.05)
    t0 = clock.now()
    bounded.submit(t3, w3, deadline_ms=None)
    assert bounded.next_due() == pytest.approx(t0 + 0.05)
    clock.advance(0.049)
    assert bounded.poll() == []  # age bound not reached yet
    clock.advance_to(t0 + 0.05)
    comps = bounded.poll()
    assert len(comps) == 1 and comps[0].wait_ms == pytest.approx(50.0)
    assert bounded.flush_log[-1].reason == "deadline"
    assert not bounded.flush_log[-1].violation  # inf deadline is never late


def test_queue_max_wait_coexists_with_deadlines(bm25_index, bm25_queries):
    """An earlier hard deadline still wins over the age bound, and the age
    bound still wins over a distant deadline."""
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(4,), clock=clock, max_wait_s=1.0)
    t3, w3 = np.array([1, 2, 3], np.int32), np.ones(3, np.float32)
    t0 = clock.now()
    q.submit(t3, w3, deadline_ms=10.0)  # deadline due at +10 ms beats +1 s age
    assert q.next_due() == pytest.approx(t0 + 0.010)
    clock.advance_to(q.next_due())
    assert len(q.poll()) == 1
    t1 = clock.now()
    q.submit(t3, w3, deadline_ms=60_000.0)  # distant deadline: age bound wins
    assert q.next_due() == pytest.approx(t1 + 1.0)
    with pytest.raises(ValueError, match="max_wait_s"):
        AdmissionQueue(srv, batch_shapes=(4,), clock=clock, max_wait_s=-0.1)


def test_queue_drain_final_partial_flush_accounting(bm25_index, bm25_queries):
    """drain()'s ragged last batch: the full flush happens on admission, the
    remainder pads with sentinels, and ONLY real rows reach the survivor
    predictor / per-request accounting."""
    qt, qw = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], engine="daat", clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock)
    observed: list = []
    real_observe = q.survivors.observe
    q.survivors.observe = lambda lq, s: (observed.append((lq, s)), real_observe(lq, s))[1]
    t3, w3 = np.array([1, 2, 3], np.int32), np.ones(3, np.float32)
    rids = [q.submit(t3, w3, deadline_ms=None) for _ in range(7)]
    comps = q.take_completions()  # the 4-wide full flush fired on admission
    assert len(comps) == 4 and q.pending() == 3
    comps += q.drain()  # ragged remainder: 3 real rows in the 4-wide shape
    assert sorted(c.rid for c in comps) == rids
    last = q.flush_log[-1]
    assert last.reason == "drain" and last.n_real == 3 and last.batch_shape == 4
    # 4 real rows from the full flush + 3 from the drain, never the sentinel
    assert len(observed) == 7
    assert q.n_submitted == q.n_completed == 7


def test_replay_arrivals_requires_simulated_clock(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    srv = _queue_server(bm25_index, qt.shape[1], clock=SystemClock())
    q = AdmissionQueue(srv, batch_shapes=(2,))
    with pytest.raises(TypeError, match="SimulatedClock"):
        replay_arrivals(q, [0.0], [qt[0]], [qw[0]], [5.0])


# --------------------------------------------------------------------------
# hypothesis properties (skipped — not the whole module — without hypothesis)
# --------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    _settings = settings(
        max_examples=15,
        deadline=None,
        suppress_health_check=[HealthCheck.function_scoped_fixture, HealthCheck.too_slow],
    )
    _HYPOTHESIS = True
except ImportError:  # deterministic suite above still runs
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
    engine=st.sampled_from(["saat", "daat"]),
    width=st.sampled_from([1, 2, 3, 4]),
)
def test_prop_bucketed_bit_identical(bm25_index, bm25_queries, seed, engine, width):
    """(a) bucketed serving == unbucketed max-Lq pad, both engines."""
    qt, qw = bm25_queries
    L = qt.shape[1]
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, qt.shape[0], size=4)
    bt, bw = np.asarray(qt[rows, :width]), np.asarray(qw[rows, :width])
    # reference at max-Lq padding
    rt, rw = pad_to_width(bt, bw, L, bm25_index.n_terms)
    kw = dict(k=10, rho_ladder=EXACT, daat_est_blocks=2, daat_block_budget=2, engine=engine)
    ref = AnytimeServer(bm25_index, ServingConfig(**kw))
    buk = AnytimeServer(bm25_index, ServingConfig(**kw, lq_buckets=(2, 4, L)))
    r1 = ref.search_batch(jnp.asarray(rt), jnp.asarray(rw))
    r2 = buk.search_batch(jnp.asarray(bt), jnp.asarray(bw))
    assert np.array_equal(np.asarray(r1.doc_ids), np.asarray(r2.doc_ids))
    assert np.array_equal(np.asarray(r1.scores), np.asarray(r2.scores))


@_settings
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(1, 40),
    qps=st.sampled_from([200.0, 1000.0, 5000.0]),
)
def test_prop_queue_lossless_and_on_time(bm25_index, bm25_queries, seed, n, qps):
    """(b) no drops, no duplicates, no flush past the oldest deadline."""
    qt, qw = bm25_queries
    rng = np.random.default_rng(seed)
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(4, 8), clock=clock)
    terms, weights = _mixed_lq_requests(qt, qw, n, rng)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=n))
    deadlines = rng.uniform(5.0, 50.0, size=n)
    comps = replay_arrivals(q, arrivals.tolist(), terms, weights, deadlines.tolist())
    assert sorted(c.rid for c in comps) == list(range(n))
    assert q.n_violations == 0
    for f in q.flush_log:
        assert f.flush_s <= f.oldest_deadline_s + 1e-9


# --------------------------------------------------------------------------
# regression: flush-time clock semantics
# --------------------------------------------------------------------------


def test_queue_poll_rereads_clock_between_buckets(bm25_index):
    """Regression: ``poll()`` captured ``now`` once, so a bucket whose
    deadline expired DURING an earlier bucket's flush (real service time on a
    hybrid clock) waited for the next driver wakeup instead of flushing in
    the same poll. The clock must be re-read per bucket iteration."""
    clock = HybridClock(0.0)
    srv = _queue_server(bm25_index, 16, clock=clock, buckets=(4, 16))
    q = AdmissionQueue(srv, batch_shapes=(2,), clock=clock)

    orig = srv.search_batch

    def search_and_accrue(qt, qw, rho=None):
        res = orig(qt, qw, rho=rho)
        clock.advance(10.0)  # this flush's service time, in simulated seconds
        return res

    srv.search_batch = search_and_accrue

    # bucket 4: due almost immediately; bucket 16: due only after the first
    # flush's 10 s of service time has accrued
    q.submit(np.array([1, 2], np.int32), np.ones(2, np.float32), deadline_ms=5.0)
    q.submit(np.arange(1, 8, dtype=np.int32), np.ones(7, np.float32), deadline_ms=5000.0)
    clock.advance(0.006)
    assert clock.now() < q._due_instant(16)  # not yet due at poll entry

    comps = q.poll()  # ONE poll must serve both
    assert sorted(c.rid for c in comps) == [0, 1]
    assert [f.bucket for f in q.flush_log] == [4, 16]
    assert all(f.reason == "deadline" for f in q.flush_log)


def test_queue_overfull_lane_predicts_chunked_launches(bm25_index):
    """Regression: a lane holding more than the largest batch shape drains as
    ceil(n/shape) launches, but ``_due_instant`` predicted ONE launch — the
    lane flushed too late and every chunk after the first mis-accounted as a
    violation. Seed the lane directly (``submit`` auto-flushes full lanes,
    so an overfull lane only arises between poll wakeups)."""
    from repro.serving.queue import _Request

    clock = SimulatedClock()
    srv = _queue_server(bm25_index, 4, clock=clock, buckets=(4,))
    q = AdmissionQueue(srv, batch_shapes=(2, 4), clock=clock)
    rho = srv.pick_rho()
    pred_ms = 500.0
    srv._observe_bucket_ms(4, 4, pred_ms, rho=rho)
    assert srv.predict_service_ms(4, 4) == pytest.approx(pred_ms)

    now = clock.now()
    deadline = now + 2 * pred_ms / 1e3 + 0.010  # meetable only as 2 launches
    for _ in range(7):  # ceil(7/4) = 2 launches
        q._pending[4].append(
            _Request(
                rid=q._next_rid,
                q_terms=np.array([1, 2, 3], np.int32),
                q_weights=np.ones(3, np.float32),
                arrival_s=now,
                deadline_s=deadline,
                lq_eff=3,
                bucket=4,
            )
        )
        q._next_rid += 1
        q.n_submitted += 1

    # the due instant must reserve BOTH launches' predicted service
    assert q.next_due() == pytest.approx(deadline - 2 * pred_ms / 1e3)
    clock.advance_to(q.next_due())
    comps = q.poll()
    assert len(comps) == 7 and q.pending() == 0
    recs = q.flush_log[-2:]
    assert [r.n_real for r in recs] == [4, 3]
    assert all(r.reason == "deadline" for r in recs)
    assert not any(r.violation or r.infeasible for r in recs)


def test_replay_effectiveness_empty_schedule(bm25_index, bm25_queries):
    """Regression: a replay that completes nothing (empty schedule) must
    return a well-formed all-zero report, not crash in np.stack([])."""
    from repro.metrics.ir_metrics import replay_effectiveness

    qt, _ = bm25_queries
    clock = SimulatedClock()
    srv = _queue_server(bm25_index, qt.shape[1], clock=clock)
    q = AdmissionQueue(srv, batch_shapes=(2,), clock=clock)
    rep = replay_effectiveness(q, [], [], [], [], np.zeros(0, np.int64), recall_k=10)
    assert rep["n_requests"] == 0 and rep["by_rho"] == []
    assert rep["violations"] == 0 and rep["infeasible"] == 0
    assert rep["overall"]["mrr"] == 0.0 and rep["overall"]["recall"] == 0.0
    assert rep["wait_ms"]["p99_ms"] == 0.0
