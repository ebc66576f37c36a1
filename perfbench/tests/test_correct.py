"""``correct`` on the CPU at a tiny size: a sound run passes, and the
control and each fault a cell can have fail. The chip check is skipped: the
tests drive ``harness.run`` under the command line's look for a TPU."""
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import control, harness, roofline
from perfbench.reference import Reference
from repro.serving import AnytimeServer

PEAKS = roofline.peaks("TPU v5 lite")


def _run(cfg, trf, seed=3, seconds=1.5):
    return harness.run(cfg, trf, seed=seed, seconds=seconds, traced=False, t_start=0.0,
                       peaks=PEAKS)


@pytest.mark.parametrize("grid_top", [48.0, 20.0])
def test_sound_run_is_correct(grid_top, saat_cfg, open_trf):
    # under a grid top of 20 the largest weights take the top impact in the
    # program and in the reference alike
    cfg = dict(saat_cfg, capacity=dict(saat_cfg["capacity"], max_weight=grid_top))
    out = _run(cfg, open_trf)
    assert out.correct, out.checks
    assert out.checks["off_budget"] == (0, 0) and out.run.completed == out.run.attempted
    assert out.checks["topk_gap"][0] < 1e-6 and out.run.flush_rows.max() > 1


def _control_engine(cfg, seed, monkeypatch):
    """The reference in bfloat16 in the program's place."""
    corpus, enc = harness.generate(cfg, seed)
    ref = Reference(enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms,
                    max_weight=cfg["capacity"]["max_weight"])
    real = AnytimeServer.search_batch

    def search_batch(self, q_terms, q_weights, rho=None):
        res = real(self, q_terms, q_weights, rho=rho)  # keeps the server's own records
        rows = [control.answers(ref, t, w, cfg.get("rho"), self.cfg.k)
                for t, w in zip(np.asarray(q_terms), np.asarray(q_weights))]
        return res._replace(scores=jnp.asarray(np.stack([s for s, _ in rows]), jnp.float32),
                            doc_ids=jnp.asarray(np.stack([i for _, i in rows]), jnp.int32))

    monkeypatch.setattr(AnytimeServer, "search_batch", search_batch)


def test_control_is_not_correct(saat_cfg, open_trf, monkeypatch):
    _control_engine(saat_cfg, 3, monkeypatch)
    out = _run(saat_cfg, open_trf)
    assert not out.correct
    assert out.checks["topk_gap"][0] > 1e-3 and out.checks["bad_ids"][0] == 0


def _fault(kind):
    def alter(scores, ids):
        if kind == "answer_altered":  # one id of every row moved to its neighbour
            return scores, ids.at[:, 0].set((ids[:, 0] + 1) % 1024)
        # half of the rows left out: every odd row gets the even row's answer
        src = jnp.arange(ids.shape[0]) // 2 * 2
        return scores[src], ids[src]
    return alter


@pytest.mark.parametrize("kind", ["answer_altered", "half_rows_left_out"])
def test_faults_are_not_correct(kind, saat_cfg, open_trf, monkeypatch):
    real, alter = AnytimeServer.search_batch, _fault(kind)

    def search_batch(self, q_terms, q_weights, rho=None):
        res = real(self, q_terms, q_weights, rho=rho)
        scores, ids = alter(res.scores, res.doc_ids)
        return res._replace(scores=scores, doc_ids=ids)

    monkeypatch.setattr(AnytimeServer, "search_batch", search_batch)
    out = _run(saat_cfg, open_trf)
    assert not out.correct, out.checks
