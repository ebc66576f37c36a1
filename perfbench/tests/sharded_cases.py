"""Runs of a doc-sharded cell on four virtual CPU devices, for
``test_sharded.py``, which starts this in a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python perfbench/tests/sharded_cases.py <case> [<case> ...]

Each case is one ``harness.run`` at a tiny size (4 x 256 passages, lanes 8
and 16, flushes of 8, kernels in interpret mode), with the timed path sound
or broken underneath, and every case shares one set-up. ``pool_left_out``
rebuilds the pod step with chip 0's pool kept out of the k-merge and warms
it before its window. Prints one JSON line per case: ``correct``, the
checks, and whether every leaf of the stacked index had one shard on each
device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import control, harness, roofline  # noqa: E402
from perfbench.reference import Reference  # noqa: E402
from repro.serving import PodServer  # noqa: E402
from repro.serving import sharded  # noqa: E402

N_SHARDS = 4
CFG = dict(
    name="tiny-bm25-4x256", treatment="bm25", shards=N_SHARDS, n_docs=4 * 256, n_queries=96,
    corpus={"concepts_per_doc": 16, "terms_per_doc_concept": 5, "stopwords_per_doc": 9.4,
            "max_tf": 2, "concepts_per_query": 3.15, "terms_per_query_concept": 1.5},
    # per shard: 3% to 7% over the most of four seeds
    capacity={"postings": 8448, "segments": 6144, "block_max": 3584, "doc_terms": 128,
              "max_weight": 13.5},
    serving={"engine": "daat", "daat_exact": True, "daat_use_kernels": True,
             "daat_fused_chunk": True, "daat_trips_per_launch": 4, "daat_est_blocks": 8,
             "daat_block_budget": 16},
    limits={"topk_gap": 1e-4, "id_gap": 1e-4},
)
TRF = dict(loop="open", k=10, lanes=[8, 16], lane_shares=[0.82, 0.18], stream_seed=5,
           rate_qps=40.0, batch_shapes=[8], max_wait_s=0.0, deadline_ms=None,
           degrade_rho=False, check_sample=1000)
SEED = 3
CASES = ("sound", "answer_altered", "half_rows_left_out", "control", "shard_offset_shifted",
         "pool_left_out")


def one_shard_per_device(stack) -> bool:
    for x in jax.tree.leaves(stack):
        homes = {s.device for s in x.addressable_shards}
        rows = {s.data.shape[0] for s in x.addressable_shards}
        if len(homes) != N_SHARDS or rows != {1}:
            return False
    return True


def _swap_shards(server) -> None:
    """Shards 1 and 2 trade places on the mesh: each one's docs then take
    the other's global ids, as a shard offset gone wrong would give them."""
    host = jax.tree.map(lambda x: np.asarray(x)[[0, 2, 1, 3]], server.index)
    server.index = jax.block_until_ready(sharded.place_index_stack(host, server.mesh))


def _merge_without_chip_0(server, warm) -> None:
    """Rebuild the pod step with chip 0's pool kept out of the k-merge, and
    warm it, as set-up would, before the window."""
    merge = sharded.canonical_topk_merge

    def merge_without_0(scores, ids, k, axis_name):
        out = jax.lax.axis_index("model") == 0
        return merge(jnp.where(out, -jnp.inf, scores),
                     jnp.where(out, jnp.iinfo(jnp.int32).max, ids), k, axis_name)

    sharded.canonical_topk_merge = merge_without_0
    server._steps.clear()
    server._jitted.clear()
    warm()


def _alter_answers(case: str):
    """What the server hands the queue, broken after the step."""
    real = PodServer.search_batch
    if case == "control":
        corpus, enc = harness.generate(CFG, SEED)
        ref = Reference(enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms,
                        max_weight=CFG["capacity"]["max_weight"])

    def search_batch(self, q_terms, q_weights, rho=None):
        # on the host, in numpy: the shared set-up warmed no jnp op of these
        res = real(self, q_terms, q_weights, rho=rho)  # keeps the server's own records
        scores, ids = np.asarray(res.scores), np.asarray(res.doc_ids)
        if case == "answer_altered":  # one id of every row moved to its neighbour
            ids = ids.copy()
            ids[:, 0] = (ids[:, 0] + 1) % CFG["n_docs"]
        elif case == "half_rows_left_out":  # every odd row gets the even row's answer
            src = np.arange(ids.shape[0]) // 2 * 2
            scores, ids = scores[src], ids[src]
        else:  # control: the reference in bfloat16 in the program's place
            rows = [control.answers(ref, t, w, None, self.cfg.k)
                    for t, w in zip(np.asarray(q_terms), np.asarray(q_weights))]
            scores = np.stack([s for s, _ in rows]).astype(np.float32)
            ids = np.stack([i for _, i in rows]).astype(np.int32)
        return res.__class__(scores=jnp.asarray(scores), doc_ids=jnp.asarray(ids))

    return search_batch


def main(cases) -> int:
    if len(jax.devices()) < N_SHARDS:
        raise SystemExit(f"needs {N_SHARDS} devices: set "
                         f"XLA_FLAGS=--xla_force_host_platform_device_count={N_SHARDS}")
    unknown = set(cases) - set(CASES)
    if unknown:
        raise SystemExit(f"unknown cases {sorted(unknown)}; known: {CASES}")
    out = harness.setup(CFG, TRF, SEED)
    server, placed = out[3], one_shard_per_device(out[2])
    harness.setup = lambda cfg, trf, seed: out  # every case's run shares it

    def warm():
        enc, pools = out[0], out[4]
        for li, lane in enumerate(TRF["lanes"]):
            qt, qw = harness._padded(enc, np.resize(pools[li], 8), lane)
            jax.block_until_ready(server.search_batch(qt, qw).doc_ids)

    real_search, real_merge, real_index = (PodServer.search_batch,
                                           sharded.canonical_topk_merge, server.index)
    for i, case in enumerate(cases):
        if case in ("answer_altered", "half_rows_left_out", "control"):
            PodServer.search_batch = _alter_answers(case)
        elif case == "shard_offset_shifted":
            _swap_shards(server)
        elif case == "pool_left_out":
            _merge_without_chip_0(server, warm)
        try:
            res = harness.run(CFG, TRF, seed=SEED, seconds=1.5, traced=False,
                              t_start=time.perf_counter(), peaks=roofline.peaks("TPU v5 lite"))
        finally:
            PodServer.search_batch, sharded.canonical_topk_merge = real_search, real_merge
            server.index = real_index
            if case == "pool_left_out" and i + 1 < len(cases):
                server._steps.clear()
                server._jitted.clear()
                warm()
        print(json.dumps({
            "case": case, "correct": res.correct, "placed": placed,
            "checks": {n: list(v) for n, v in res.checks.items()},
            "attempted": res.run.attempted, "completed": res.run.completed,
            "peak_per_chip": res.device["memory_peak_bytes_per_chip"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
