"""The frozen pieces of the benchmark: generator, traffic, reference, trace
reduction and roofline arithmetic."""
import hashlib

import jax
import numpy as np
import pytest

from perfbench import collection, harness, roofline, trace, traffic
from perfbench.reference import Reference, top_k

# sha256 of the copied generator's output (documents, weights, qrels and
# queries) at 512 passages: a change to the yardstick shows here.
PINNED = {
    ("spladev2", 0): "ebb18b69a3cd3ba13152da949222e6ab47661175cca6e1b9e7f521329b5021c4",
    ("spladev2", 2**31 + 5): "799efc9d2760782a497938cf512fde3d1666c370778128de7a4962bdd0a0d6c3",
    ("bm25", 0): "079574661c64197b2263535fe857ea0f769a192e75b6182cee75d1768aeb3381",
    ("bm25", 2**31 + 5): "07d88dae157f4fed088a39acfc4fb4799204a11e40f0ed0d97d2c7e1e26f9e68",
}


def _collection(model, seed, n_docs=512, n_queries=32, n_concepts=200):
    cfg = collection.CorpusConfig(n_docs=n_docs, n_queries=n_queries, n_concepts=n_concepts,
                                  seed=seed)
    corpus = collection.generate_corpus(cfg)
    return corpus, collection.apply_treatment(corpus, model, seed=seed)


@pytest.mark.parametrize("model,seed", sorted(PINNED))
def test_generator_output_is_pinned(model, seed):
    corpus, enc = _collection(model, seed)
    h = hashlib.sha256()
    for a in (enc.doc_idx, enc.term_idx, enc.weights, corpus.qrels, *enc.query_terms,
              *enc.query_weights):
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(str(enc.n_terms).encode())
    assert h.hexdigest() == PINNED[(model, seed)]


def test_schedule_and_lanes_do_not_depend_on_the_seed(saat_cfg, open_trf):
    t5, lanes5 = traffic.arrivals(open_trf, 5.0)
    t9, lanes9 = traffic.arrivals(open_trf, 9.0)
    # the seed reaches neither; a longer window only extends the schedule
    assert np.array_equal(t9[: t5.size], t5) and np.array_equal(lanes9[: t5.size], lanes5)
    assert t5.max() < 5.0 <= t9.max() and 600 < t5.size < 900  # 150 queries/s
    widths = []
    for seed in (1, 2**31 + 11):
        _, enc = harness.generate(dict(saat_cfg, n_docs=512), seed)
        qids = traffic.fill(lanes5, traffic.lane_pools(enc.query_weights, open_trf["lanes"]))
        widths.append(np.array([enc.query_terms[q].size for q in qids]))
    lo = np.array([0] + open_trf["lanes"])[lanes5]
    hi = np.array(open_trf["lanes"])[lanes5]
    for w in widths:  # every seed fills each request from the request's own lane
        assert np.all((w > lo) & (w <= hi))
    assert not np.array_equal(widths[0], widths[1])


def test_every_seed_runs_the_same_executables(saat_cfg, open_trf):
    def statics(index):
        leaves, treedef = jax.tree_util.tree_flatten(index)
        return treedef, [(a.shape, a.dtype) for a in leaves]

    first = harness.setup(saat_cfg, open_trf, seed=1)
    compiles = harness._compile_counter()
    compiles["on"] = True
    second = harness.setup(saat_cfg, open_trf, seed=2**31 + 11)
    compiles["on"] = False
    # the index's extents and static fields (scale, plan bounds) and so every
    # executable of the warm-up are the seed's no more: the second seed
    # compiles nothing
    assert statics(first[2]) == statics(second[2])
    assert compiles["n"] == 0
    assert first[2].n_postings == saat_cfg["capacity"]["postings"]


def test_a_collection_past_the_capacity_fails_setup(saat_cfg, open_trf):
    tight = dict(saat_cfg["capacity"], segments=1000)
    with pytest.raises(harness.SetupError, match="seg_term of"):
        harness.setup(dict(saat_cfg, capacity=tight), open_trf, seed=3)
    tight = dict(saat_cfg["capacity"], doc_terms=128)
    with pytest.raises(harness.SetupError, match="a passage of"):
        harness.setup(dict(saat_cfg, capacity=tight), open_trf, seed=3)


def test_budget_past_a_lanes_reach_fails_setup(saat_cfg, open_trf):
    with pytest.raises(harness.SetupError, match="reach of lane 32"):
        harness.setup(dict(saat_cfg, rho=10**7), open_trf, seed=3)


def test_reference_matches_dense_scoring():
    corpus, enc = _collection("spladev2", 4, n_docs=300)
    ref = Reference(enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms)
    w = enc.weights
    impact = np.clip(np.ceil(w / w.max() * 255), 1, 255)
    value = (impact * (w.max() / 255)).astype(np.float32)
    dense = np.zeros((corpus.n_docs, enc.n_terms + 1), np.float32)
    dense[enc.doc_idx, enc.term_idx] = value
    for qt, qw in zip(enc.query_terms[:8], enc.query_weights[:8]):
        contrib = dense[:, qt] * qw.astype(np.float32)  # f32, as the engines take them
        got = ref.scores(qt, qw)
        np.testing.assert_allclose(got, contrib.astype(np.float64).sum(1), rtol=1e-12)
        assert ref.total_postings(qt, qw) == np.count_nonzero(contrib)
        # a budget takes that many postings, the highest contributions first
        budget = max(np.count_nonzero(contrib) // 3, 1)
        best = np.sort(contrib[contrib > 0])[::-1][:budget].astype(np.float64).sum()
        assert ref.scores(qt, qw, budget).sum() == pytest.approx(best, rel=1e-12)
        s, ids = top_k(got, 5)
        assert list(s) == sorted(s, reverse=True) and np.allclose(got[ids], s)


def test_trace_reduction_on_a_hand_made_trace():
    ms = 1_000_000
    spans = [("bench.window", 0, 100 * ms), ("bench.poll", 5 * ms, 60 * ms),
             ("bench.search_batch", 10 * ms, 60 * ms), ("bench.sleep", 60 * ms, 95 * ms)]
    ops = [("fusion.1", -5 * ms, 20 * ms), ("while.3", 30 * ms, 50 * ms),
           ("fusion.1", 30 * ms, 40 * ms), ("kernel", 40 * ms, 50 * ms),
           ("fusion.2", 98 * ms, 120 * ms)]
    got = trace.reduce([ops], spans)
    # busy: [0,20] + [30,50] + [98,100] = 42 ms of a 100 ms window
    assert got.busy_s == pytest.approx(0.042) and got.window_s == pytest.approx(0.1)
    assert got.idle_share == pytest.approx(0.58)
    # the while loop's event holds its body's two ops and counts only through them
    assert got.device_ops[0] == ["fusion.1", pytest.approx(0.030)]
    assert [n for n, _ in got.device_ops] == ["fusion.1", "kernel", "fusion.2"]
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %x)") == "fusion.12"
    assert got.idle_gaps[0] == ["bench.sleep", pytest.approx(0.048)]  # [50, 98]
    assert got.idle_gaps[1] == ["bench.search_batch", pytest.approx(0.010)]  # [20, 30]
    run = harness.Run(engine="saat", k=10, rho=1, setup_s=0, setup_phases={},
                      window_s=0.1, attempted=0, completed=0,
                      peaks=roofline.peaks("TPU v5 lite"), trace=got)
    run.saat_bytes = roofline.saat_step_bytes(postings=1000, rows=2, width_slots=30, k=10,
                                              posting_bytes=4)
    assert run.saat_bytes == 4000 + 240 + 160
    share = harness.load_metric("saat_step_roofline")(run)
    assert share == pytest.approx(100 * 4400 / 819e9 / 0.042)
    assert harness.load_metric("device_idle_share.steady")(run) == pytest.approx(58.0)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
