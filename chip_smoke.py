#!/usr/bin/env python3
"""Smoke check of the serving path on a TPU: ``python3 chip_smoke.py``.

Runs the phases of :mod:`repro.launch.smoke` once, in this one process, at
the deployment size (262,144 passages per treatment) on one chip, and prints
one line per phase. It is a check that the system starts and answers
correctly on the chip, not a benchmark: the times it prints are single runs.
The last line of standard output is a JSON object naming the device.

``--chips 4`` runs only the sharded path instead: the stacked index placed
one shard per device of a one-host mesh over all four chips, served by
``make_bucketed_serve_step`` and compared with the unsharded oracle.

Exits nonzero, before any work, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0, help="corpus and arrival RNG seed")
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="1: the serving phases on one chip; 4: only the sharded path",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}", file=sys.stderr
        )
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import configure_compile_cache

    print(f"[smoke] compile_cache: {configure_compile_cache()}", flush=True)
    if args.chips == 4:
        from repro.launch.smoke import SHARDED, run_sharded

        run_sharded(SHARDED, seed=args.seed)
    else:
        from repro.launch.smoke import DEPLOYMENT, run

        run(DEPLOYMENT, seed=args.seed)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
