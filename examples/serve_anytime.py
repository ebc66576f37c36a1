"""Anytime serving under a latency deadline (paper §4.3 + tail-latency story).

Runs the same query stream at several deadlines; the controller picks the
posting budget rho per batch, trading effectiveness for bounded latency.

    PYTHONPATH=src python examples/serve_anytime.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import build_impact_index, pad_queries
from repro.data.synthetic import CorpusConfig, generate_corpus
from repro.metrics.ir_metrics import mrr_at_k
from repro.models.treatments import apply_treatment
from repro.serving import AnytimeServer, ServingConfig, run_query_stream


def main():
    corpus = generate_corpus(CorpusConfig(n_docs=4000, n_queries=120))
    enc = apply_treatment(corpus, "spladev2")  # the wackiest treatment
    index = build_impact_index(enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms)
    max_q = max(len(t) for t in enc.query_terms)
    qt, qw = pad_queries(enc.query_terms, enc.query_weights, max_q, enc.n_terms)
    print(f"spladev2 index: {index.n_postings:,} postings over {corpus.n_docs} docs")

    ladder = tuple(
        sorted({max(index.n_postings // f, 1000) for f in (100, 20, 4, 1)})
    )
    for deadline in (None, 50.0, 5.0):
        srv = AnytimeServer(
            index,
            ServingConfig(k=100, rho_ladder=ladder, batch_size=16, deadline_ms=deadline),
        )
        srv.warmup(jnp.asarray(qt[:16]), jnp.asarray(qw[:16]))
        srv.reset_stats()
        _, ids = run_query_stream(srv, qt, qw)
        stats = srv.stats()
        rho_used = int(np.median([d.rho for d in srv.dispatch_log])) if srv.dispatch_log else 0
        print(
            f"deadline={str(deadline):>6} ms | median rho={rho_used:>9,} | "
            f"RR@10={mrr_at_k(ids, corpus.qrels, 10):.3f} | "
            f"p50={stats.p50_ms:.1f}ms p99={stats.p99_ms:.1f}ms "
            f"tail-ratio={stats.tail_ratio:.2f}"
        )
    print("smaller deadlines -> smaller budgets -> bounded latency, graceful "
          "effectiveness loss (the paper's anytime tradeoff).")


if __name__ == "__main__":
    main()
