#!/usr/bin/env python3
"""The control: the plain reference in the program's place, in bfloat16.

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 --seconds 30

For each seed it draws the requests a run of the cell checks (the same
schedule, pools and sample), answers each with the reference's scores
summed in bfloat16 (the precision below the program's float32), and holds
those answers against the float64 reference as a run holds the program's.
It prints one JSON line per seed with the compared numbers and the
configuration's limits: the control has to fail them. Host only; run it at
the cell's own size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from perfbench import harness, traffic  # noqa: E402
from perfbench.reference import Gaps, Reference, top_k  # noqa: E402


def answers(ref: Reference, qt, qw, rho, k: int):
    """The control's top-k (scores, ids) for one query row."""
    return top_k(ref.scores(qt, qw, rho, control=True), k)


def sampled_queries(trf: dict, enc, seed: int, seconds: float):
    """Query ids of the answers a run of this cell checks."""
    _, lanes = traffic.arrivals(trf, seconds)
    qids = traffic.fill(lanes, traffic.lane_pools(enc.query_weights, trf["lanes"]))
    return qids[harness.check_picks(seed, np.arange(qids.size), int(trf["check_sample"]))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = harness.load_config(cell["config"])
    trf = traffic.load(ROOT / "perfbench" / "traffic" / f"{cell['traffic']}.json")
    rho = cfg.get("rho") if cfg["serving"].get("engine", "saat") == "saat" else None
    for seed in (int(s) for s in args.seeds.split(",")):
        corpus, enc = harness.generate(cfg, seed)
        qids = sampled_queries(trf, enc, seed, args.seconds)
        ref = Reference(enc.doc_idx, enc.term_idx, enc.weights, corpus.n_docs, enc.n_terms,
                        max_weight=cfg["capacity"]["max_weight"],
                        terms=np.unique(np.concatenate([enc.query_terms[q] for q in qids])))
        gaps = Gaps()
        for q in qids:
            qt, qw = enc.query_terms[q], enc.query_weights[q]
            gaps.add(ref.scores(qt, qw, rho), *answers(ref, qt, qw, rho, int(trf["k"])))
        print(json.dumps({
            "workload": args.workload, "seed": seed, "rows": gaps.rows,
            "topk_gap": gaps.topk_gap, "id_gap": gaps.id_gap, "bad_ids": gaps.bad_ids,
            "limits": cfg["limits"],
            "fails": gaps.topk_gap > cfg["limits"]["topk_gap"]
            or gaps.id_gap > cfg["limits"]["id_gap"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
