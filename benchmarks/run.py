"""Run every paper-table/figure benchmark + the roofline aggregation.

    PYTHONPATH=src python -m benchmarks.run            # CSV to stdout (as before)
    PYTHONPATH=src python -m benchmarks.run --json     # + BENCH_<name>.json each
    PYTHONPATH=src python -m benchmarks.run --json --out-dir results/

JSON mode wraps each benchmark's ``run()`` rows in a machine-readable record:
the module's UPPERCASE config constants (so a result can never be read apart
from the knobs that produced it), wall time, and ``parity_asserted`` — True
when the module bitwise-compares engine results *before* timing them
(``PARITY_ASSERTED`` tag), i.e. the speed numbers are provably not from a
wrong-answer fast path.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _module_config(mod) -> dict:
    """The benchmark's UPPERCASE constants, JSON-ready (tuples -> lists)."""
    out = {}
    for k, v in vars(mod).items():
        if not k.isupper() or k.startswith("_") or k == "PARITY_ASSERTED":
            continue
        if isinstance(v, (list, tuple)):
            v = list(v)
            if not all(isinstance(x, (int, float, str, bool)) for x in v):
                continue
        elif not isinstance(v, (int, float, str, bool)):
            continue
        out[k] = v
    return out


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="python -m benchmarks.run")
    p.add_argument("--json", action="store_true",
                   help="also write BENCH_<name>.json per benchmark")
    p.add_argument("--out-dir", default=".", metavar="DIR",
                   help="directory for the JSON records (default: cwd)")
    p.add_argument("--only", action="append", metavar="NAME",
                   help="run only the named benchmark(s)")
    args = p.parse_args(argv)

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    from benchmarks import (
        fig1_rho_tradeoff,
        fig2_tail_latency,
        fig3_pareto,
        roofline,
        side_batched_vs_vmap,
        side_blockmax_vs_exhaustive,
        side_bucketed_vs_padded,
        side_daat_vs_saat_batched,
        side_degrade_vs_violate,
        side_delta_vs_rebuild,
        side_fused_chunk_vs_split,
        side_fused_vs_unfused,
        side_pod_merge,
        table1_models_systems,
        table2_term_stats,
    )

    benches = [
        ("table2_term_stats", table2_term_stats),
        ("table1_models_systems", table1_models_systems),
        ("fig1_rho_tradeoff", fig1_rho_tradeoff),
        ("fig2_tail_latency", fig2_tail_latency),
        ("fig3_pareto", fig3_pareto),
        ("side_blockmax_vs_exhaustive", side_blockmax_vs_exhaustive),
        ("side_batched_vs_vmap", side_batched_vs_vmap),
        ("side_daat_vs_saat_batched", side_daat_vs_saat_batched),
        ("side_fused_vs_unfused", side_fused_vs_unfused),
        ("side_fused_chunk_vs_split", side_fused_chunk_vs_split),
        ("side_bucketed_vs_padded", side_bucketed_vs_padded),
        ("side_degrade_vs_violate", side_degrade_vs_violate),
        ("side_delta_vs_rebuild", side_delta_vs_rebuild),
        ("side_pod_merge", side_pod_merge),
        ("roofline", roofline),
    ]
    if args.only:
        known = {name for name, _ in benches}
        unknown = sorted(set(args.only) - known)
        if unknown:
            p.error(f"unknown benchmark(s) {unknown}; have {sorted(known)}")
        benches = [(n, m) for n, m in benches if n in args.only]

    out_dir = Path(args.out_dir)
    if args.json:
        out_dir.mkdir(parents=True, exist_ok=True)

    t_all = time.time()
    failures = 0
    for name, mod in benches:
        t0 = time.time()
        try:
            if args.json:
                from benchmarks.common import print_csv

                rows = mod.run()
                print_csv(name, rows)
                record = {
                    "name": name,
                    "config": _module_config(mod),
                    "parity_asserted": bool(getattr(mod, "PARITY_ASSERTED", False)),
                    "elapsed_s": round(time.time() - t0, 3),
                    "rows": rows,
                }
                path = out_dir / f"BENCH_{name}.json"
                path.write_text(json.dumps(record, indent=2) + "\n")
                print(f"-- wrote {path}", flush=True)
            else:
                mod.main()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"!! {name} FAILED: {type(e).__name__}: {e}\n", flush=True)
        print(f"-- {name} took {time.time() - t0:.1f}s\n", flush=True)
    print(f"== all benchmarks done in {time.time() - t_all:.1f}s, failures={failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
