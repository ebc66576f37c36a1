#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration and its traffic
are found by name: ``BENCHMARK.json`` names the cell's configuration
(``perfbench/configs/<config>.json``) and traffic mix
(``perfbench/traffic/<traffic>.json``), and each metric is read by
``perfbench/metrics/<metric>.py``.

The run sets up (generates the seed's collection, builds and places the
index, warms up every executable the cell uses), measures for ``--seconds``
seconds, checks a sample of the served answers against the plain reference,
and prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit. A
line before it splits ``setup_s`` into its phases.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for. JAX's compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` where
that is set, else ``.jax_cache`` at the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _seed(text: str) -> int:
    seed = int(text)
    if seed < 0:
        raise argparse.ArgumentTypeError("--seed is a whole number >= 0")
    return seed


def cell_metrics(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or with
    ``traced`` its per-layer ones (those that list it, or without a list
    those whose end-to-end metric the cell reports)."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=_seed, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        ap.error(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from perfbench import harness, roofline, traffic

    devices = harness.init_jax()
    if devices[0].platform != "tpu":
        print(f"perfbench: needs a TPU, JAX found {devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"perfbench: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    peaks = roofline.peaks(devices[0].device_kind)
    cfg = harness.load_config(cell["config"])
    trf = traffic.load(ROOT / "perfbench" / "traffic" / f"{cell['traffic']}.json")
    out = harness.run(cfg, trf, seed=args.seed, seconds=args.seconds,
                      traced=bool(args.trace), t_start=T_START, peaks=peaks)
    print(json.dumps(report(out, cell_metrics(bench, cell["name"], bool(args.trace)))))
    return 0


def report(out, wanted: list[dict]) -> dict:
    """The result line, and the phases line and the checks on the way."""
    from perfbench import harness

    run = out.run
    print(json.dumps({"setup_phases": dict(run.setup_phases, setup_s=run.setup_s)}))
    metrics = {}
    for m in wanted:
        value = harness.load_metric(m["name"])(run)
        if value is None and "moves" not in m:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {
        "correct": out.correct,
        "attempted": run.attempted,
        "failed": run.attempted - run.completed,
        "metrics": metrics,
        "device": out.device,
    }
    if run.trace is not None:
        line["breakdown"] = {"device_ops": run.trace.device_ops,
                             "idle_gaps": run.trace.idle_gaps}
    line["checks"] = {n: {"value": v, "limit": lim} for n, (v, lim) in out.checks.items()}
    for n, (v, lim) in out.checks.items():
        print(f"check {n}: {v!r} limit {lim!r}", file=sys.stderr)
    print(f"correct: {out.correct}", file=sys.stderr, flush=True)
    return line


if __name__ == "__main__":
    sys.exit(main())
