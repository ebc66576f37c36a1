"""One run of one cell: set up, warm up, measure, check.

Set-up generates the seed's collection with the benchmark's own generator
(:mod:`perfbench.collection`), builds the program's impact index from it,
fits the index to the capacity its configuration states, places it on the
chip and warms up every executable the cell's traffic uses: its engine, its
k, its lanes and its batch shapes, twice each. Every array of the index has
the configuration's extent whatever the seed, so every seed runs the same
executables. Then the window: the fixed arrival schedule of the traffic
file, submitted one request at a time through the program's
``AdmissionQueue`` and polled back. A request's latency runs from the
instant it was due to be sent until the ``poll`` that returns its
``Completion``.

After the window the program is freed and a sample of the served answers,
drawn from the seed, is held against :class:`perfbench.reference.Reference`.

The host spans ``bench.window``, ``bench.submit``, ``bench.poll``,
``bench.sleep`` and ``bench.search_batch`` go around the benchmark's calls
into the program, so a trace names what the host did in each idle gap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

from perfbench import collection, reference, trace as tracing, traffic as traffic_mod

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# The server appends its exact level to every rho ladder and serves the top
# level when no deadline is set. Under a deadline that a calibrated level
# meets, it serves the largest calibrated level, and the configured rho is
# the only level that set-up warms and calibrates. So the steady SAAT cells
# serve exactly the configured rho; every flush is checked for it.
_RHO_PICK_DEADLINE_MS = 3.6e6


def init_jax():
    """JAX's compilation cache at a fixed path: ``$JAX_COMPILATION_CACHE_DIR``
    where that is set, else ``.jax_cache`` at the root of the checkout. Every
    executable is kept, so a second run of a cell compiles nothing."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.devices()


class SetupError(RuntimeError):
    """The cell cannot run as its files state it."""


@dataclasses.dataclass
class Run:
    """What one run measured: the metric readers read this."""

    engine: str
    k: int
    rho: int | None
    setup_s: float
    setup_phases: dict
    window_s: float  # host clock: window start to the last answer
    attempted: int
    completed: int
    peaks: dict
    latencies_ms: np.ndarray = None  # due instant to completion
    lags_ms: np.ndarray = None  # submit instant minus due instant
    waits_ms: np.ndarray = None  # Completion.wait_ms
    flush_rows: np.ndarray = None  # FlushRecord.n_real of each flush
    search_ms: np.ndarray = None  # host ms of each search_batch call
    saat_bytes: float | None = None  # least bytes the served SAAT rows moved
    trace: tracing.TraceSummary | None = None


@dataclasses.dataclass
class Outcome:
    run: Run
    checks: dict  # name -> (value, limit)
    device: dict

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def _log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_config(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def load_metric(name: str):
    """The reader ``perfbench/metrics/<name>.py``: ``read(run) -> float | None``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _padded(enc, ids, width: int):
    qt = np.full((len(ids), width), enc.n_terms, dtype=np.int32)
    qw = np.zeros((len(ids), width), dtype=np.float32)
    for r, i in enumerate(ids):
        t, w = enc.query_terms[i], enc.query_weights[i]
        qt[r, : t.size], qw[r, : w.size] = t, w
    return qt, qw


def _serving_config(cfg: dict, trf: dict):
    from repro.serving import ServingConfig

    kw = dict(cfg["serving"], k=int(trf["k"]), lq_buckets=tuple(trf["lanes"]))
    if kw.get("engine", "saat") == "saat":
        kw.update(rho_ladder=(int(cfg["rho"]),), deadline_ms=_RHO_PICK_DEADLINE_MS)
    return ServingConfig(**kw)


def check_budget(server, rho: int, lanes) -> None:
    """Every lane serves the configured rho: the SAAT ``[B, rho]`` shapes and
    device work are then the same for every seed."""
    for lane in lanes:
        got = server.served_rho(rho, lane)
        if got != rho:
            raise SetupError(
                f"rho={rho} is past the reach of lane {lane} ({got} postings) on this "
                "seed's collection: the configuration's rho must be at or below the "
                "narrowest lane's reach for every seed"
            )


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _compile_counter():
    """Counts executables compiled or fetched from the cache while ``on``."""
    import jax

    state = {"on": False, "n": 0}

    def listener(event, duration_secs, **kwargs):
        if state["on"] and event == _COMPILE_EVENT:
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return state


def generate(cfg: dict, seed: int):
    """The seed's collection under the configuration's corpus parameters.

    Where the configuration states a ``query_seed``, its queries come from
    that fixed stream instead: the same concept model, drawn over a corpus
    of ``n_queries`` passages of their own. Every seed then serves the same
    queries over its own passages, so a cell whose work follows the queries
    (exact DAAT) does the same work for every seed. No passage of the
    seed's collection was drawn for those queries, so ``qrels`` are -1."""

    def draw(n_docs: int, n_queries: int, s: int):
        corpus = collection.generate_corpus(collection.CorpusConfig(
            n_docs=n_docs, n_queries=n_queries, seed=s, **cfg["corpus"]))
        return corpus, collection.apply_treatment(corpus, cfg["treatment"], seed=s)

    if "query_seed" not in cfg:
        return draw(cfg["n_docs"], cfg["n_queries"], seed)
    corpus, enc = draw(cfg["n_docs"], 0, seed)
    qc, qe = draw(cfg["n_queries"], cfg["n_queries"], int(cfg["query_seed"]))
    corpus = dataclasses.replace(corpus, query_terms=qc.query_terms,
                                 query_concepts=qc.query_concepts,
                                 qrels=np.full(qc.n_queries, -1, np.int32))
    return corpus, dataclasses.replace(enc, query_terms=qe.query_terms,
                                       query_weights=qe.query_weights)


# The index arrays whose extent follows the collection: (field, capacity key, fill).
_RAGGED = (
    ("doc_ids", "postings", 0),
    ("seg_term", "segments", None),  # the pad term slot
    ("seg_weight", "segments", 0),
    ("seg_start", "segments", 0),
    ("seg_len", "segments", 0),
    ("bm_block", "block_max", 0),
    ("bm_weight", "block_max", 0),
)


def _over(what: str, got, cap) -> SetupError:
    return SetupError(f"this seed's collection has {what} {got}, over the configuration's "
                      f"capacity {cap}: raise it in the configuration's file")


def _on_host():
    """Build on the host's CPU device, where the process has one."""
    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def _check_longest(enc, n_docs: int, capacity: dict) -> None:
    longest = int(np.bincount(enc.doc_idx, minlength=n_docs).max())
    if longest > capacity["doc_terms"]:
        raise _over("a passage of", longest, capacity["doc_terms"])


def fit_index(index, capacity: dict):
    """One index fitted to ``capacity``: the posting, segment and block-max
    stores padded past their ends (the per-term tables never reach the
    padding) and the plan bounds at their ceilings: every segment count a
    term can have, and every block."""
    arrays = {}
    for field, key, fill in _RAGGED:
        a = np.asarray(getattr(index, field))
        if a.shape[0] > capacity[key]:
            raise _over(f"{field} of", a.shape[0], capacity[key])
        pad = np.full(capacity[key] - a.shape[0], index.n_terms if fill is None else fill, a.dtype)
        arrays[field] = np.concatenate([a, pad])
    return dataclasses.replace(index, **arrays, max_segs=(1 << index.bits) - 1,
                               max_bm=index.n_blocks)


def build_index(enc, n_docs: int, capacity: dict):
    """The program's index of the collection, fitted to ``capacity`` on the
    host (:func:`fit_index`): impacts on one grid up to ``max_weight`` (a
    larger weight takes the top impact, as the reference's does) and the
    doc-major store ``doc_terms`` wide. Its arrays and static fields are
    then the same for every seed, and so are the executables that take it."""
    from repro.core import build_impact_index

    _check_longest(enc, n_docs, capacity)
    with _on_host():  # built as host arrays, padded and placed once
        index = build_impact_index(enc.doc_idx, enc.term_idx, enc.weights, n_docs, enc.n_terms,
                                   max_doc_terms=capacity["doc_terms"],
                                   quant_max_weight=capacity["max_weight"])
    return fit_index(index, capacity)


def build_stack(enc, n_docs: int, capacity: dict, n_shards: int):
    """The collection split into ``n_shards`` equal doc ranges, each shard's
    index on the one grid up to ``max_weight`` and fitted to the per-shard
    ``capacity`` as :func:`build_index` fits the one index, then stacked on
    a leading shard axis on the host. Returns (stack, docs per shard)."""
    from repro.serving.sharded import shard_corpus, stack_indexes

    _check_longest(enc, n_docs, capacity)
    with _on_host():
        shards, docs_per_shard = shard_corpus(
            enc.doc_idx, enc.term_idx, enc.weights, n_docs, enc.n_terms, n_shards,
            max_doc_terms=capacity["doc_terms"], quant_max_weight=capacity["max_weight"])
        stack = stack_indexes([fit_index(s, capacity) for s in shards])
    return stack, docs_per_shard


def setup(cfg: dict, trf: dict, seed: int):
    """Collection, index on the chips, server, warm executables. A
    configuration with ``"shards": S`` over 1 serves S equal doc ranges,
    one per chip of a one-host mesh, through the program's ``PodServer``
    and its cross-chip k-merge; its ``capacity`` is then per shard."""
    import jax

    phases = {}
    t = time.perf_counter()
    corpus, enc = generate(cfg, seed)
    phases["generate_s"] = time.perf_counter() - t
    n_shards = int(cfg.get("shards", 1))
    t = time.perf_counter()
    if n_shards > 1:
        index, docs_per_shard = build_stack(enc, corpus.n_docs, cfg["capacity"], n_shards)
    else:
        index = build_index(enc, corpus.n_docs, cfg["capacity"])
    phases["index_build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if n_shards > 1:
        from jax.sharding import Mesh
        from repro.serving import PodServer
        from repro.serving.sharded import place_index_stack

        devices = jax.devices()[:n_shards]
        if len(devices) < n_shards:
            raise SetupError(f"{n_shards} shards need {n_shards} chips, JAX sees {len(devices)}")
        mesh = Mesh(np.array(devices).reshape(1, n_shards), ("pod", "model"))
        index = jax.block_until_ready(place_index_stack(index, mesh))
        make_server = functools.partial(PodServer, mesh, docs_per_shard=docs_per_shard,
                                        n_docs_total=corpus.n_docs)
    else:
        from repro.serving import AnytimeServer

        index = jax.block_until_ready(jax.device_put(index, jax.devices()[0]))
        make_server = AnytimeServer
    phases["placement_s"] = time.perf_counter() - t
    _log(f"index: shards={n_shards} n_docs={index.n_docs} doc_ids={tuple(index.doc_ids.shape)} "
         f"bytes={index.nbytes()}")

    t = time.perf_counter()
    lanes = list(trf["lanes"])
    server = make_server(index, _serving_config(cfg, trf))
    engine = server.cfg.engine
    rho = int(cfg["rho"]) if engine == "saat" else None
    if rho is not None:
        check_budget(server, rho, lanes)
    pools = traffic_mod.lane_pools(enc.query_weights, lanes)
    shapes = trf["batch_shapes"]
    for li, lane in enumerate(lanes):
        qt, qw = _padded(enc, np.resize(pools[li], max(shapes)), lane)
        for b in shapes:
            for _ in range(2):
                jax.block_until_ready(server.search_batch(qt[:b], qw[:b], rho=rho).doc_ids)
    server.reset_stats()
    phases["warmup_s"] = time.perf_counter() - t
    return enc, corpus, index, server, pools, rho, phases


@contextlib.contextmanager
def _timed_search(server, log: list):
    """Route the server's ``search_batch`` through a host-clock span for the
    block: the admission queue's flushes call it."""
    import jax

    inner = server.search_batch

    def search_batch(q_terms, q_weights, rho=None):
        with jax.profiler.TraceAnnotation("bench.search_batch"):
            t = time.perf_counter()
            res = inner(q_terms, q_weights, rho=rho)
        log.append((time.perf_counter() - t) * 1e3)
        return res

    server.search_batch = search_batch
    try:
        yield
    finally:
        del server.search_batch


def drive_open(server, enc, pools, trf: dict, seconds: float):
    """The open loop over the fixed schedule. Returns per-request records and
    the served answers by request number."""
    import jax
    from repro.serving.queue import AdmissionQueue

    t_due, lane_idx = traffic_mod.arrivals(trf, seconds)
    qids = traffic_mod.fill(lane_idx, pools)
    n = t_due.size
    search_ms: list = []
    queue = AdmissionQueue(
        server, batch_shapes=trf["batch_shapes"], max_wait_s=trf["max_wait_s"],
        degrade_rho=trf["degrade_rho"],
    )
    deadline = trf["deadline_ms"]
    due = np.empty(n)
    sent = np.empty(n)
    done = np.full(n, np.nan)
    waits = np.full(n, np.nan)
    answers: dict = {}
    by_rid: dict = {}
    ann = jax.profiler.TraceAnnotation
    i = returned = 0
    with _timed_search(server, search_ms), ann(tracing.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            while i < n and t0 + t_due[i] <= now:
                q = qids[i]
                with ann("bench.submit"):
                    s = time.perf_counter()
                    rid = queue.submit(enc.query_terms[q], enc.query_weights[q], deadline)
                by_rid[rid] = i
                due[i], sent[i] = t0 + t_due[i], s
                i += 1
                now = time.perf_counter()
            # a submit that fills a lane flushes it, and its completions
            # wait for the next poll
            comps = []
            if queue.pending() or queue.n_completed > returned:
                with ann("bench.poll"):
                    # after the last arrival, lanes that never come due drain
                    last = i >= n and queue.next_due() is None
                    comps = queue.drain() if last else queue.poll()
                t = time.perf_counter()
                returned += len(comps)
                for c in comps:
                    j = by_rid[c.rid]
                    done[j], waits[j] = t, c.wait_ms
                    answers[j] = (c.scores, c.doc_ids)
            if comps:
                continue
            if i >= n and not queue.pending():
                break
            due_next = queue.next_due()
            wake = min(t0 + t_due[i] if i < n else np.inf,
                       np.inf if due_next is None else due_next)
            dt = wake - time.perf_counter()
            if dt > 0:
                with ann("bench.sleep"):
                    time.sleep(dt)
        t_end = time.perf_counter()
    return dict(
        queue=queue, t0=t0, t_end=t_end, due=due, sent=sent, done=done, waits=waits,
        answers=answers, qids=qids, search_ms=np.asarray(search_ms),
    )


def run(cfg: dict, trf: dict, *, seed: int, seconds: float, traced: bool, t_start: float,
        peaks: dict) -> Outcome:
    """One run: set-up, window, check. ``t_start``: the process's start on
    the host clock, from which ``setup_s`` counts."""
    import jax

    compiles = _compile_counter()
    enc, corpus, index, server, pools, rho, phases = setup(cfg, trf, seed)
    engine = server.cfg.engine
    setup_s = time.perf_counter() - t_start
    _log("setup: " + " ".join(f"{k}={v:.3f}" for k, v in phases.items()))
    compiles["on"] = True
    with tracing.capture(traced) as captured:
        w = drive_open(server, enc, pools, trf, seconds)
    compiles["on"] = False
    if compiles["n"]:
        raise SetupError(f"{compiles['n']} executables were compiled inside the window")
    used = jax.devices()[: int(cfg.get("shards", 1))]
    peak = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used]
    device = {
        "platform": used[0].platform, "kind": used[0].device_kind, "count": len(jax.devices()),
        "memory_peak_bytes": max(peak), "memory_peak_bytes_per_chip": peak,
    }
    run_rec = Run(
        engine=engine, k=int(trf["k"]), rho=rho, setup_s=setup_s,
        setup_phases=phases, window_s=w["t_end"] - w["t0"], attempted=0, completed=0,
        peaks=peaks, search_ms=w["search_ms"],
    )
    if captured.path:
        try:
            run_rec.trace = tracing.reduce(*tracing.read_xplane(captured.path))
        finally:
            tracing.discard(captured)
        device["busy_s"] = run_rec.trace.busy_s
        device["window_s"] = run_rec.trace.window_s

    queue = w["queue"]
    ok = ~np.isnan(w["done"])
    run_rec.attempted, run_rec.completed = int(w["due"].size), int(ok.sum())
    run_rec.latencies_ms = (w["done"][ok] - w["due"][ok]) * 1e3
    run_rec.lags_ms = (w["sent"] - w["due"]) * 1e3
    run_rec.waits_ms = w["waits"][ok]
    run_rec.flush_rows = np.array([f.n_real for f in queue.flush_log])
    if run_rec.completed:
        _log("latency: " + " ".join(
            f"p{q}={np.percentile(run_rec.latencies_ms, q):.3f}ms" for q in (50, 95, 99)))
    off_budget = 0 if rho is None else sum(1 for f in queue.flush_log if f.rho != rho)
    pick = check_picks(seed, np.flatnonzero(ok), int(trf["check_sample"]))
    sample = [(int(w["qids"][j]), *w["answers"][j]) for j in pick]
    served_qids = w["qids"][ok]
    _log(f"window: {run_rec.window_s:.3f}s attempted={run_rec.attempted} "
         f"completed={run_rec.completed}")
    _log_flushes(queue.flush_log, w["search_ms"], w["t0"])
    del queue

    posting_bytes = int(np.dtype(index.doc_ids.dtype).itemsize)
    del w, server, index
    gc.collect()

    t = time.perf_counter()
    ref = reference.Reference(
        enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms,
        max_weight=cfg["capacity"]["max_weight"],
        terms=np.unique(np.concatenate([enc.query_terms[q] for q, _, _ in sample])),
    )
    if engine == "saat":
        run_rec.saat_bytes = _saat_bytes(ref, enc, served_qids, rho, run_rec.k, posting_bytes)
    gaps = reference.Gaps()
    for q, scores, ids in sample:
        gaps.add(ref.scores(enc.query_terms[q], enc.query_weights[q], rho), scores, ids)
    _log(f"check: {gaps.rows} answers against the reference in {time.perf_counter() - t:.3f}s")
    limits = cfg["limits"]
    checks = {
        "topk_gap": (gaps.topk_gap, float(limits["topk_gap"])),
        "id_gap": (gaps.id_gap, float(limits["id_gap"])),
        "bad_ids": (gaps.bad_ids, 0),
        "unanswered": (run_rec.attempted - run_rec.completed, 0),
    }
    if rho is not None:
        checks["off_budget"] = (off_budget, 0)
    return Outcome(run=run_rec, checks=checks, device=device)


def _log_flushes(flush_log, search_ms: np.ndarray, t0: float) -> None:
    """Flushes by batch shape, and the longest five by their start in the
    window: a stall or a run of large-shape flushes shows here."""
    shapes, counts = np.unique([f.batch_shape for f in flush_log], return_counts=True)
    longest = np.argsort(search_ms)[::-1][:5]
    _log("flushes: " + " ".join(f"B{b}={c}" for b, c in zip(shapes, counts))
         + " longest: " + " ".join(
             f"{search_ms[j]:.0f}ms@{flush_log[j].flush_s - t0:.1f}s/B{flush_log[j].batch_shape}"
             for j in longest))


def check_picks(seed: int, candidates: np.ndarray, size: int) -> np.ndarray:
    """Which served answers the reference checks: drawn from the seed."""
    rng = np.random.default_rng([seed, 7])
    return rng.choice(candidates, size=min(size, candidates.size), replace=False)


def _saat_bytes(ref, enc, served_qids, rho: int, k: int, posting_bytes: int) -> float:
    """Least bytes of every SAAT row served in the window (see
    :func:`perfbench.roofline.saat_step_bytes`)."""
    from perfbench.roofline import saat_step_bytes

    qids, times = np.unique(served_qids, return_counts=True)
    need = [min(ref.total_postings(enc.query_terms[q], enc.query_weights[q]), rho) for q in qids]
    width = [enc.query_terms[q].size for q in qids]
    return saat_step_bytes(int(np.dot(need, times)), int(times.sum()),
                           int(np.dot(width, times)), k, posting_bytes)
