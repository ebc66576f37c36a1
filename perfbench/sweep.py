#!/usr/bin/env python3
"""The measurements that set a cell's fixed numbers; not part of a run.

    python3 perfbench/sweep.py knee --workload <cell> --seed <n> --rates 8,12,16 --seconds 12 [--refine 3]
    python3 perfbench/sweep.py rho --config <config> --seed <n> --rhos 262144,524288 --queries 512
    python3 perfbench/sweep.py extents --config <config> --seeds 1,2,3 --queries 20000 --lanes 8,16

``knee`` (on the chip) sets the cell up once and drives its open loop at
each offered rate for ``--seconds``, in the order given, up to the first
that it does not sustain: one JSON line per rate with the
latency median and tail, how late the generator ran, the median latency
of the window's first and last thirds, the executables compiled or fetched
inside the window (there should be none) and the flushes slower than 100 ms
(start in the window, ms, lane, shape, real rows). The knee is the highest
rate at which the last third is no slower than the first (the backlog does
not grow: slower means by more than a fifth, and its last answer came
within a quarter of the window after it); the cell's traffic file runs
at four fifths of it. ``--refine n`` then halves n times the gap between
the highest sustained rate and the next one up, and a last line gives the
knee. A rate may
repeat: the windows of one process then show what a seed does not change.

``rho`` (host only) generates the configuration's collection and gives, for
each budget, RR@10 on the synthetic qrels of the plain reference at that
budget against exhaustive scoring, with each lane's reach: the most
postings a query of that width can touch. A configuration's rho is at or
below the narrowest lane's reach.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# a rate whose last third's median latency is more than this many times its
# first third's has a growing backlog
_GROWN = 1.2
# nor one whose last answer came more than this share of the window after it
_DRAIN = 0.25
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def knee(args) -> None:
    import jax

    from perfbench import harness, traffic
    from perfbench.stats import percentile

    harness.init_jax()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("knee: needs a TPU")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = harness.load_config(cell["config"])
    trf = traffic.load(ROOT / "perfbench" / "traffic" / f"{cell['traffic']}.json")
    enc, _, _, server, pools, _, phases = harness.setup(cfg, trf, args.seed)
    print(json.dumps({"setup_phases": phases}), flush=True)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((event, secs)) if "compile" in event else None)
    lines = {}

    def drive(rate):
        compiles.clear()
        w = harness.drive_open(server, enc, pools, dict(trf, rate_qps=rate), args.seconds)
        lat = (w["done"] - w["due"]) * 1e3
        third = len(lat) // 3
        flush = w["search_ms"]
        log = w["queue"].flush_log
        slow = [(round(f.flush_s - w["t0"], 3), round(ms, 1), f.bucket, f.batch_shape, f.n_real)
                for f, ms in zip(log, flush) if ms > 100]
        line = {
            "compile_events": len(compiles), "compile_s": sum(s for _, s in compiles),
            "slow_flushes": slow[:20],
            "latency_p99_ms": percentile(lat, 99), "latency_max_ms": float(np.max(lat)),
            "flush_ms.max": float(flush.max()),
            "flushes_over_100ms": int(np.count_nonzero(flush > 100)),
            "rate_qps": rate, "attempted": int(lat.size),
            "completed": int(np.count_nonzero(~np.isnan(lat))),
            "latency_p50_ms": percentile(lat, 50), "latency_p95_ms": percentile(lat, 95),
            "generator_lag_ms.p95": percentile((w["sent"] - w["due"]) * 1e3, 95),
            "first_third_p50_ms": percentile(lat[:third], 50),
            "last_third_p50_ms": percentile(lat[-third:], 50),
            "rows_per_flush": float(np.mean([f.n_real for f in w["queue"].flush_log])),
            "flush_ms.p50": percentile(w["search_ms"], 50),
            "drain_s": w["t_end"] - w["t0"] - args.seconds,
        }
        print(json.dumps(line), flush=True)
        lines[rate] = line

    def sustained(rate):
        line = lines[rate]
        return (line["last_third_p50_ms"] <= _GROWN * line["first_third_p50_ms"]
                and line["drain_s"] <= _DRAIN * args.seconds)

    for rate in (float(r) for r in args.rates.split(",")):
        drive(rate)
        if not sustained(rate):  # a higher rate only drains longer
            break
    for _ in range(args.refine):  # halve the gap between the sustained and the rest
        ok = [r for r in lines if sustained(r)]
        over = [r for r in lines if not sustained(r) and (not ok or r > max(ok))]
        if not ok or not over:
            break
        drive(round((max(ok) + min(over)) / 2, 2))
    ok = [r for r in lines if sustained(r)]
    print(json.dumps({"knee_qps": max(ok) if ok else None, "grown": _GROWN, "drain": _DRAIN}),
          flush=True)


def rho(args) -> None:
    from perfbench import harness, traffic
    from perfbench.reference import Reference, top_k

    cfg = harness.load_config(args.config)
    corpus, enc = harness.generate(cfg, args.seed)
    lanes = [int(x) for x in args.lanes.split(",")]
    pools = traffic.lane_pools(enc.query_weights, lanes)
    qids = sorted(q for pool in pools for q in pool)[: args.queries]
    ref = Reference(enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms,
                    max_weight=cfg["capacity"]["max_weight"], terms=np.unique(np.concatenate([enc.query_terms[q] for q in qids])))
    reach = np.cumsum(-np.sort(-ref.term_postings))
    need = [ref.total_postings(enc.query_terms[q], enc.query_weights[q]) for q in qids]

    def rr10(budget):
        out = []
        for q in qids:
            _, ids = top_k(ref.scores(enc.query_terms[q], enc.query_weights[q], budget), 10)
            hit = np.flatnonzero(ids == corpus.qrels[q])
            out.append(1.0 / (hit[0] + 1) if hit.size else 0.0)
        return float(np.mean(out))

    exact = rr10(None)
    line = {"config": args.config, "seed": args.seed, "queries": len(qids),
            "lane_reach": {str(w): int(reach[w - 1]) for w in lanes},
            "query_postings_p50": float(np.percentile(need, 50)),
            "query_postings_p95": float(np.percentile(need, 95)),
            "rr10_exact": exact, "rr10": {}}
    for budget in (int(r) for r in args.rhos.split(",")):
        v = rr10(budget)
        line["rr10"][str(budget)] = {"rr10": v, "loss": (exact - v) / exact}
    print(json.dumps(line), flush=True)


def extents(args) -> None:
    """Widths, lane shares and index extents of the configuration's
    collection on each seed: what its ``corpus`` and ``capacity`` are set
    from. The impact grid's top is the configuration's ``max_weight`` where
    it states one, else 1.25 times the largest weight of the seeds."""
    from perfbench import harness

    cfg = harness.load_config(args.config)
    lanes = [int(x) for x in args.lanes.split(",")]
    n_shards = int(cfg.get("shards", 1))
    seeds = [int(s) for s in args.seeds.split(",")]
    gen = {s: harness.generate(dict(cfg, n_queries=args.queries), s) for s in seeds}
    top = cfg.get("capacity", {}).get("max_weight")
    if top is None:
        top = 1.25 * max(float(enc.weights.max()) for _, enc in gen.values())
    for seed in seeds:
        corpus, enc = gen.pop(seed)
        widths = np.array([t.size for t in enc.query_terms])
        lane = np.searchsorted(lanes, widths)
        print(json.dumps({
            "config": args.config, "seed": seed, "n_docs": corpus.n_docs,
            "collection_postings": enc.n_postings, "doc_unique": enc.n_postings / corpus.n_docs,
            "query_unique": float(widths.mean()), "queries": int(widths.size),
            "lane_shares": {str(w): float(np.mean(lane == i)) for i, w in enumerate(lanes)},
            "wider_share": float(np.mean(lane == len(lanes))),
            "longest_passage": int(np.bincount(enc.doc_idx).max()),
            "max_weight": float(enc.weights.max()), "grid_top": top,
            **_index_extents(enc, corpus.n_docs, n_shards, top),
        }), flush=True)


def _index_extents(enc, n_docs: int, n_shards: int, top: float) -> dict:
    """The most postings, segments and block-max entries of one index (or
    shard), on the grid up to ``top``."""
    from repro.serving.sharded import shard_corpus

    shards, _ = shard_corpus(enc.doc_idx, enc.term_idx, enc.weights, n_docs, enc.n_terms,
                             n_shards, quant_max_weight=top)
    return {key: max(int(getattr(ix, field).shape[0]) for ix in shards)
            for key, field in (("postings", "doc_ids"), ("segments", "seg_term"),
                               ("block_max", "bm_block"))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    k = sub.add_parser("knee")
    k.add_argument("--workload", required=True)
    k.add_argument("--seed", type=int, required=True)
    k.add_argument("--rates", required=True)
    k.add_argument("--seconds", type=float, default=12.0)
    k.add_argument("--refine", type=int, default=0)
    r = sub.add_parser("rho")
    r.add_argument("--config", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--rhos", required=True)
    r.add_argument("--lanes", default="32,64")
    r.add_argument("--queries", type=int, default=512)
    e = sub.add_parser("extents")
    e.add_argument("--config", required=True)
    e.add_argument("--seeds", required=True)
    e.add_argument("--queries", type=int, default=20000)
    e.add_argument("--lanes", default="8,16")
    args = ap.parse_args(argv)
    {"knee": knee, "rho": rho, "extents": extents}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
