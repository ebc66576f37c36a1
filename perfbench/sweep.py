#!/usr/bin/env python3
"""The measurements that set a cell's fixed numbers; not part of a run.

    python3 perfbench/sweep.py knee --workload <cell> --seed <n> --rates 8,12,16 --seconds 12
    python3 perfbench/sweep.py rho --config <config> --seed <n> --rhos 262144,524288 --queries 512

``knee`` (on the chip) sets the cell up once and drives its open loop at
each offered rate for ``--seconds``: one JSON line per rate with the
latency median and tail, how late the generator ran, the median latency
of the window's first and last thirds, the executables compiled or fetched
inside the window (there should be none) and the flushes slower than 100 ms
(start in the window, ms, lane, shape, real rows). The knee is the highest
rate at which the last third is no slower than the first (the backlog does
not grow); the cell's traffic file runs at four fifths of it. A rate may
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
    for rate in (float(r) for r in args.rates.split(",")):
        compiles.clear()
        w = harness.drive_open(server, enc, pools, dict(trf, rate_qps=rate), args.seconds)
        lat = (w["done"] - w["due"]) * 1e3
        third = len(lat) // 3
        flush = w["search_ms"]
        log = w["queue"].flush_log
        slow = [(round(f.flush_s - w["t0"], 3), round(ms, 1), f.bucket, f.batch_shape, f.n_real)
                for f, ms in zip(log, flush) if ms > 100]
        print(json.dumps({
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
        }), flush=True)


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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    k = sub.add_parser("knee")
    k.add_argument("--workload", required=True)
    k.add_argument("--seed", type=int, required=True)
    k.add_argument("--rates", required=True)
    k.add_argument("--seconds", type=float, default=12.0)
    r = sub.add_parser("rho")
    r.add_argument("--config", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--rhos", required=True)
    r.add_argument("--lanes", default="32,64")
    r.add_argument("--queries", type=int, default=512)
    args = ap.parse_args(argv)
    {"knee": knee, "rho": rho}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
