"""The phases of ``chip_smoke.py``: one pass over the serving main path.

A smoke check, not a benchmark: it proves that the serving path compiles and
runs on the chip at a corpus size users would call real, and that every
kernel mode returns its oracle's doc ids there. Each phase prints one line;
the wall times it prints are single runs, not measurements.

1. ``generate`` — from ``--seed``, one base corpus of ``n_docs`` passages and
   two treatments at the paper's Table 2 shapes
   (:data:`repro.models.treatments.PROFILES`): ``spladev2`` (the wacky case)
   and ``bm25`` (the skewed control).
2. ``index`` — both impact indexes built and placed on the device.
3. ``serve`` — batches of ``k=10`` and ``k=1000`` queries through
   :class:`~repro.serving.AnytimeServer` in four modes: SAAT
   ``scatter_impl="sort"`` (oracle) vs ``fused_topk=True``, and DAAT jnp
   (oracle) vs ``use_kernels + fused_chunk + trips_per_launch``. Each kernel
   mode's ids must equal its oracle's (the slow jnp DAAT oracle serves the
   first rows of the first batch only; a row's answer never depends on the
   rest of its batch), and every kernel-mode answer must pass
   :class:`HostReference`, exhaustive scoring on the host that shares no
   code with the device path. On a TPU each kernel mode's executable must
   contain the Mosaic kernel (``tpu_custom_call``).
4. ``queue`` — a short seeded-Poisson replay through
   :class:`~repro.serving.queue.AdmissionQueue` on a ``HybridClock``, as
   ``python -m repro.launch.serve --queue`` runs it.

No phase catches another's failure: an assertion or error ends the run.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh

from repro.core import build_impact_index, pad_queries
from repro.core.saat import posting_reach
from repro.data.synthetic import CorpusConfig, generate_corpus
from repro.metrics.latency import HybridClock
from repro.models.treatments import apply_treatment
from repro.serving import AnytimeServer, ServingConfig
from repro.serving.bucketing import merge_repeated_terms
from repro.serving.queue import AdmissionQueue, replay_arrivals
from repro.serving.sharded import (
    make_bucketed_serve_step,
    place_index_stack,
    shard_corpus,
    stack_indexes,
)

# The deployment chip_smoke.py serves on one chip, and the widths
# tests/test_tpu_compile.py compiles the kernels at. 262,144 passages is one
# chip's share of an MS MARCO-size collection split over a 32-chip slice
# (8.8M / 32 ~ 275k); the doc-major widths are the stores the generator's
# longest spladev2 / bm25 passages round up to (whole 128-lane tiles).
DEPLOYMENT = dict(
    treatments=("spladev2", "bm25"),
    n_docs=262_144,
    n_queries=256,
    batch=32,
    n_batches=2,
    daat_oracle_rows=8,
    lq_buckets=(8, 16, 40),
    lq=40,
    rho=262_144,
    ks=(10, 1000),
    block_size=128,
    n_blocks=262_144 // 128,
    est_blocks=8,
    block_budget=16,
    trips_per_launch=4,
    tmax=(128, 768),
    n_bm=20_000_000,  # block-max cells: ~19.2M in the spladev2 index
    queue_requests=16,
    queue_qps=400.0,
    queue_deadline_ms=2000.0,
    # two lanes, one flush shape: each (lane, shape) is a compile at the
    # exact budget, the slowest compiles of the run
    queue_lq_buckets=(16, 40),
    queue_shapes=(32,),
)

# The same phases at a size the CPU test runs in interpret mode.
TINY = dict(
    DEPLOYMENT,
    n_docs=1024,
    n_queries=48,
    batch=8,
    n_batches=1,
    daat_oracle_rows=4,
    lq_buckets=(8, 40),
    rho=2048,
    ks=(10, 200),
    n_blocks=1024 // 128,
    queue_requests=12,
    queue_shapes=(8,),
)

# ``chip_smoke.py --chips 4``: the same corpus split over a one-host mesh of
# four devices, one shard per chip, against the unsharded oracle. The mesh,
# the placement and the cross-chip merge are what only four chips show; the
# kernels at the wacky widths are the one-chip run's, so the sharded check
# takes the bm25 treatment, whose index builds in seconds on the host.
SHARDED = dict(DEPLOYMENT, treatments=("bm25",), n_shards=4, ks=(10,))
TINY_SHARDED = dict(TINY, treatments=("bm25",), n_shards=4, ks=(10,))


_T0 = time.perf_counter()


def _log(phase: str, **fields) -> None:
    fields["elapsed_s"] = f"{time.perf_counter() - _T0:.1f}"
    print(f"[smoke] {phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _device_bytes_in_use() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats.get("bytes_in_use", 0))


def _modes(cfg: dict, k: int) -> dict[str, tuple[ServingConfig, ServingConfig]]:
    """(oracle, kernel) serving configs per engine."""
    saat = dict(k=k, rho_ladder=(cfg["rho"],), lq_buckets=cfg["lq_buckets"])
    daat = dict(
        k=k, engine="daat", lq_buckets=cfg["lq_buckets"],
        daat_est_blocks=cfg["est_blocks"], daat_block_budget=cfg["block_budget"],
    )
    return {
        "saat": (
            ServingConfig(**saat, scatter_impl="sort"),
            ServingConfig(**saat, fused_topk=True),
        ),
        "daat": (
            ServingConfig(**daat),
            ServingConfig(
                **daat, daat_use_kernels=True, daat_fused_chunk=True,
                daat_trips_per_launch=cfg["trips_per_launch"],
            ),
        ),
    }


def _executable_text(server: AnytimeServer, qt, qw, rho) -> str:
    """Compiled text of the executable ``search_batch`` just ran."""
    qt, qw, bucket = server._bucketize(qt, qw)
    fn = server.engine_fn(server.served_rho(rho, bucket))  # a partial of a jitted engine
    return fn.func.lower(*fn.args, qt, qw, **fn.keywords).compile().as_text()


# Host float64 sums against the device's f32 ones: at most 40 addends of
# 8-bit impacts times query weights, far inside these tolerances.
_RTOL, _ATOL = 1e-5, 1e-5


class HostReference:
    """The serving semantics re-done on the host: numpy over the posting store.

    A query's postings are taken in JASS order — its segments by
    contribution (``impact * query weight``, f32) descending, ties in slot
    then segment order — up to a budget of ``rho`` postings (all of them
    when ``rho`` is None: exhaustive scoring), and summed per document by a
    float64 ``bincount``. No plan, sort network, kernel or jitted code of
    the serving path runs here, so it witnesses that path rather than
    repeating it. :meth:`check` accepts an answer when it is a valid top-k:
    its scores are the k largest reference scores, each returned doc really
    has its score, and no doc appears twice. Which of several docs tied at
    the k-th score an engine keeps is left open.
    """

    def __init__(self, index):
        def get(a):
            return np.asarray(jax.device_get(a))

        self.doc_ids = get(index.doc_ids)
        self.seg_weight = get(index.seg_weight)
        self.seg_start = get(index.seg_start).astype(np.int64)
        self.seg_len = get(index.seg_len).astype(np.int64)
        self.term_seg_start = get(index.term_seg_start)
        self.term_seg_count = get(index.term_seg_count)
        self.n_docs, self.n_terms = index.n_docs, index.n_terms

    def scores(self, qt: np.ndarray, qw: np.ndarray, rho: int | None = None) -> np.ndarray:
        """f64[B, n_docs]: each query's score for every document."""
        out = np.zeros((qt.shape[0], self.n_docs))
        for b in range(qt.shape[0]):
            segs, contrib = [], []
            for t, w in zip(qt[b], qw[b]):
                if t == self.n_terms or w <= 0:
                    continue
                s0, c = self.term_seg_start[t], self.term_seg_count[t]
                segs.append(np.arange(s0, s0 + c))
                contrib.append(self.seg_weight[s0 : s0 + c] * np.float32(w))
            if not segs:
                continue
            segs, contrib = np.concatenate(segs), np.concatenate(contrib)
            order = np.argsort(-contrib, kind="stable")
            segs, contrib = segs[order], contrib[order]
            take = self.seg_len[segs]
            if rho is not None:  # the budget cuts the plan after rho postings
                before = np.cumsum(take) - take
                take = np.clip(rho - before, 0, take)
            ends = np.cumsum(take)
            pos = np.arange(ends[-1]) - np.repeat(ends - take, take)
            docs = self.doc_ids[np.repeat(self.seg_start[segs], take) + pos]
            w = np.repeat(contrib.astype(np.float64), take)
            out[b] = np.bincount(docs, w, minlength=self.n_docs)
        return out

    @staticmethod
    def check(ref: np.ndarray, scores: np.ndarray, ids: np.ndarray) -> bool:
        """Is ``(scores, ids) [B, k]`` a valid top-k of ``ref [B, n_docs]``?

        ``-inf`` slots (fewer than k docs reached) stand for score 0.
        """
        k = scores.shape[1]
        for r in range(scores.shape[0]):
            top = -np.sort(np.partition(-ref[r], k - 1)[:k])
            s = scores[r].astype(np.float64)
            live = np.isfinite(s)
            got = ids[r][live]
            if not (
                np.allclose(np.where(live, s, 0.0), top, rtol=_RTOL, atol=_ATOL)
                and np.all((got >= 0) & (got < ref.shape[1]))
                and np.allclose(ref[r][got], s[live], rtol=_RTOL, atol=_ATOL)
                and np.unique(got).size == got.size
            ):
                return False
        return True


def _serve(name: str, index, cfg: dict, qt: np.ndarray, qw: np.ndarray) -> None:
    on_tpu = jax.devices()[0].platform == "tpu"
    B = cfg["batch"]
    batches = [
        (qt[i * B : (i + 1) * B], qw[i * B : (i + 1) * B]) for i in range(cfg["n_batches"])
    ]
    host = HostReference(index)
    refs = {  # SAAT at its posting budget; DAAT is exact
        engine: [host.scores(bt, bw, rho) for bt, bw in batches]
        for engine, rho in (("saat", cfg["rho"]), ("daat", None))
    }
    for k in cfg["ks"]:
        for engine, (oracle_cfg, kernel_cfg) in _modes(cfg, k).items():
            ids = {}
            for label, scfg in (("oracle", oracle_cfg), ("kernel", kernel_cfg)):
                server = AnytimeServer(index, scfg)
                rho = cfg["rho"] if engine == "saat" else None
                served = batches
                if (engine, label) == ("daat", "oracle"):
                    # by far the slowest mode: the first rows of one batch
                    r = cfg["daat_oracle_rows"]
                    served = [(batches[0][0][:r], batches[0][1][:r])]
                walls, buckets, out = [], [], []
                for bt, bw in served:
                    t0 = time.perf_counter()
                    res = server.search_batch(jnp.asarray(bt), jnp.asarray(bw), rho=rho)
                    ids_b = np.asarray(res.doc_ids)  # blocks until the batch is done
                    walls.append(time.perf_counter() - t0)
                    buckets.append(server._bucketize(bt, bw)[2])
                    out.append((ids_b, np.asarray(res.scores)))
                ids[label] = out
                extra = {}
                if label == "kernel":
                    extra["host_witness"] = all(
                        HostReference.check(ref, s, i)
                        for ref, (i, s) in zip(refs[engine], out)
                    )
                    if on_tpu:
                        text = _executable_text(server, jnp.asarray(batches[0][0]),
                                                jnp.asarray(batches[0][1]), rho)
                        extra["tpu_custom_call"] = "tpu_custom_call" in text
                # a batch whose Lq bucket is new to the server compiles first
                _log(
                    "serve", treatment=name, engine=engine, mode=label, k=k,
                    rows=",".join(str(bt.shape[0]) for bt, _ in served),
                    lq_buckets=",".join(map(str, buckets)),
                    batch_s_incl_new_bucket_compile=",".join(f"{w:.4f}" for w in walls),
                    **extra,
                )
                assert extra.get("host_witness", True), (
                    f"{name} {engine} k={k}: kernel answer is not the exhaustive top-k"
                )
                assert extra.get("tpu_custom_call", True), (
                    f"{name} {engine} k={k}: no Mosaic kernel in the executable"
                )
            for b, ((oi, os_), (ki, ks_)) in enumerate(zip(ids["oracle"], ids["kernel"])):
                r = oi.shape[0]  # the kernel batch's first r rows
                same_ids = np.array_equal(oi, ki[:r])
                same_scores = np.array_equal(os_, ks_[:r])
                _log(
                    "parity", treatment=name, engine=engine, k=k, batch=b, rows=r,
                    ids_equal=same_ids, scores_equal=same_scores,
                )
                assert same_ids, f"{name} {engine} k={k} batch {b}: kernel ids != oracle ids"


def _queue(index, cfg: dict, qt: np.ndarray, qw: np.ndarray, seed: int) -> None:
    """A short arrival replay, as ``python -m repro.launch.serve --queue`` runs it.

    The ladder is the exact level alone: each flush serves rank-safe
    answers at the reach of its Lq bucket, which must fit the device at the
    largest flush shape.
    """
    clock = HybridClock()
    scfg = ServingConfig(
        k=10, rho_ladder=(index.n_postings,), lq_buckets=cfg["queue_lq_buckets"],
        fused_topk=True,
    )
    server = AnytimeServer(index, scfg, clock=clock)
    queue = AdmissionQueue(server, batch_shapes=cfg["queue_shapes"], clock=clock)
    t0 = time.perf_counter()
    server.warmup(jnp.asarray(qt[:8]), jnp.asarray(qw[:8]), batch_sizes=cfg["queue_shapes"])
    warm_s = time.perf_counter() - t0
    server.reset_stats()
    rng = np.random.default_rng(seed)
    n = cfg["queue_requests"]
    arrivals = np.cumsum(rng.exponential(1.0 / cfg["queue_qps"], size=n))
    order = rng.integers(0, qt.shape[0], size=n)
    completions = replay_arrivals(
        queue, arrivals.tolist(), [qt[i] for i in order], [qw[i] for i in order],
        [cfg["queue_deadline_ms"]] * n,
    )
    assert len(completions) == n == queue.n_completed, (len(completions), n)
    assert sorted(c.rid for c in completions) == list(range(n))
    # queue-served ids equal direct serving of the same queries
    first = sorted(completions, key=lambda c: c.rid)[: min(cfg["queue_shapes"])]
    rows = order[[c.rid for c in first]]
    direct = server.search_batch(jnp.asarray(qt[rows]), jnp.asarray(qw[rows]))
    same_ids = np.array_equal(np.stack([c.doc_ids for c in first]), np.asarray(direct.doc_ids))
    _log(
        "queue", requests=n, completed=queue.n_completed, flushes=len(queue.flush_log),
        violations=queue.n_violations,
        exact_rho_served=",".join(
            f"{b}:{server.served_rho(server.rho_ladder[-1], b)}" for b in server.lq_buckets
        ),
        warmup_s=f"{warm_s:.3f}", ids_equal_direct=same_ids,
    )
    assert same_ids, "queue-served ids != direct serving ids"


def _generate(cfg: dict, seed: int):
    t0 = time.perf_counter()
    corpus = generate_corpus(
        CorpusConfig(n_docs=cfg["n_docs"], n_queries=cfg["n_queries"], seed=seed)
    )
    encs = {m: apply_treatment(corpus, m, seed=seed) for m in cfg["treatments"]}
    _log(
        "generate", n_docs=corpus.n_docs, n_queries=corpus.n_queries,
        seconds=f"{time.perf_counter() - t0:.1f}",
        **{f"{m}_postings": e.n_postings for m, e in encs.items()},
    )
    return corpus, encs


def _queries(enc, cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """The treatment's queries padded to ``cfg["lq"]``, each term once."""
    qt, qw = pad_queries(enc.query_terms, enc.query_weights, cfg["lq"], enc.n_terms)
    return merge_repeated_terms(qt, qw, enc.n_terms)


def _build(enc, n_docs: int, device):
    index = build_impact_index(enc.doc_idx, enc.term_idx, enc.weights, n_docs, enc.n_terms)
    return jax.block_until_ready(jax.device_put(index, device))


def run(cfg: dict, seed: int) -> None:
    """All four phases; raises on the first failure."""
    corpus, encs = _generate(cfg, seed)
    for name, enc in encs.items():
        t0 = time.perf_counter()
        index = _build(enc, corpus.n_docs, jax.devices()[0])
        _log(
            "index", treatment=name, n_docs=index.n_docs, n_postings=index.n_postings,
            doc_major_width=index.max_doc_terms, index_bytes=index.nbytes(),
            device_bytes_in_use=_device_bytes_in_use(),
            seconds=f"{time.perf_counter() - t0:.1f}",
        )
        qt, qw = _queries(enc, cfg)
        _serve(name, index, cfg, qt, qw)
        if name == cfg["treatments"][-1]:
            _queue(index, cfg, qt, qw, seed)
        del index


def _sharded_modes(cfg: dict, k: int, stack) -> dict:
    """(unsharded oracle config, sharded step kwargs) per engine, both exact.

    DAAT first: it is the half whose cross-chip run matters most, and it
    must not wait behind the other.
    """
    lq = cfg["lq_buckets"][-1]
    return {
        "daat": (
            ServingConfig(k=k, engine="daat", lq_buckets=cfg["lq_buckets"],
                          daat_est_blocks=cfg["est_blocks"],
                          daat_block_budget=cfg["block_budget"]),
            dict(
                engine="daat", rho_per_shard=0, daat_est_blocks=cfg["est_blocks"],
                daat_block_budget=cfg["block_budget"], daat_use_kernels=True,
                daat_fused_chunk=True, daat_trips_per_launch=cfg["trips_per_launch"],
            ),
        ),
        "saat": (
            ServingConfig(k=k, rho_ladder=(cfg["rho"],), lq_buckets=cfg["lq_buckets"],
                          scatter_impl="sort"),
            # exact on every shard: the widest bucket's reach on the fullest one
            dict(fused_topk=True, rho_per_shard=int(posting_reach(stack)[lq - 1])),
        ),
    }


def _same_above_kth(scores, ids, want_s, want_i) -> bool:
    """Bit-equal scores, and the same ids wherever a score is above the row's
    k-th score (which of the docs tied at the k-th score a pool keeps is the
    engine's choice)."""
    if not np.array_equal(scores, want_s):
        return False
    above = scores > scores[:, -1:]
    return all(
        set(ids[r][above[r]]) == set(want_i[r][above[r]]) for r in range(len(ids))
    )


def run_sharded(cfg: dict, seed: int) -> None:
    """The doc-sharded path on a one-host mesh of ``n_shards`` devices.

    The stacked index is placed one shard per device (checked through each
    array's ``addressable_shards``), one batch is served through
    :func:`~repro.serving.sharded.make_bucketed_serve_step` in DAAT
    ``fused_chunk`` and SAAT ``fused_topk`` modes at their exact budgets, and
    each answer is held to the unsharded oracle on the same corpus and to
    :class:`HostReference`. SAAT must equal the oracle bit for bit. DAAT
    must equal it in every score and in every id above the k-th score: a
    DAAT pool keeps, of the docs tied at the k-th score, those its traversal
    reaches first, and sharding changes the traversal, so there the host
    witness checks that each returned doc really has that score.
    """
    n = cfg["n_shards"]
    devices = jax.devices()[:n]
    assert len(devices) == n, f"{n} shards need {n} devices, JAX sees {len(jax.devices())}"
    mesh = Mesh(np.array(devices).reshape(1, n), ("data", "model"))
    corpus, encs = _generate(cfg, seed)
    B = cfg["batch"]
    for name, enc in encs.items():
        t0 = time.perf_counter()
        full = _build(enc, corpus.n_docs, devices[0])
        shards, docs_per_shard = shard_corpus(
            enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms, n
        )
        stack = jax.block_until_ready(place_index_stack(stack_indexes(shards), mesh))
        for x in jax.tree.leaves(stack):
            homes = {s.device for s in x.addressable_shards}
            rows = {s.data.shape[0] for s in x.addressable_shards}
            assert len(homes) == n and rows == {1}, (x.shape, homes, rows)
        _log(
            "shard", treatment=name, n_shards=n, docs_per_shard=docs_per_shard,
            shard_postings=",".join(str(s.n_postings) for s in shards),
            one_shard_per_device=True,
            device_bytes_in_use=",".join(
                str(d.memory_stats()["bytes_in_use"]) if d.memory_stats() else "-"
                for d in devices
            ),
            seconds=f"{time.perf_counter() - t0:.1f}",
        )
        qt, qw = _queries(enc, cfg)
        qt, qw = qt[:B], qw[:B]
        ref = HostReference(full).scores(qt, qw)
        for k in cfg["ks"]:
            for engine, (oracle_cfg, step_kw) in _sharded_modes(cfg, k, stack).items():
                oracle = AnytimeServer(full, oracle_cfg)
                rho = oracle.rho_ladder[-1] if engine == "saat" else None
                want = oracle.search_batch(jnp.asarray(qt), jnp.asarray(qw), rho=rho)
                serve, _, _ = make_bucketed_serve_step(
                    mesh, lq_buckets=cfg["lq_buckets"], n_terms=enc.n_terms, k=k,
                    max_segs_per_term=stack.max_segs, max_bm_per_term=stack.max_bm,
                    docs_per_shard=docs_per_shard, n_docs_total=corpus.n_docs,
                    **step_kw,
                )
                t0 = time.perf_counter()
                scores, ids = serve(stack, qt, qw)
                scores, ids = np.asarray(scores), np.asarray(ids)
                wall = time.perf_counter() - t0
                want_s, want_i = np.asarray(want.scores), np.asarray(want.doc_ids)
                same_ids = np.array_equal(ids, want_i)
                same_above = _same_above_kth(scores, ids, want_s, want_i)
                witness = HostReference.check(ref, scores, ids)
                _log(
                    "sharded", treatment=name, engine=engine, k=k,
                    batch_s_incl_compile=f"{wall:.3f}", ids_equal_unsharded=same_ids,
                    ids_equal_above_kth=same_above,
                    scores_equal=np.array_equal(scores, want_s), host_witness=witness,
                )
                exact = same_ids if engine == "saat" else same_above
                assert exact and witness, f"{name} {engine} k={k}: sharded != unsharded oracle"
        del full, stack
