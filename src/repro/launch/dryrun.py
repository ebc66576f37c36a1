import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count on first init); do not move them. 512 placeholder host devices back
both the 16x16 single-pod and 2x16x16 multi-pod meshes.

For every runnable cell this driver:
  1. builds the abstract step (ShapeDtypeStructs only — zero allocation),
  2. ``jax.jit(fn, in_shardings=...).lower(*args).compile()``,
  3. records ``compiled.memory_analysis()`` (fits-in-HBM proof),
     ``compiled.cost_analysis()`` (FLOPs / bytes for §Roofline), and the
     collective-op byte census parsed from the post-SPMD optimized HLO,
  4. writes one JSON per cell under ``--out`` (benchmarks/roofline.py and
     EXPERIMENTS.md §Dry-run/§Roofline read these).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, get_arch
from repro.launch import costs
from repro.launch.mesh import mesh_for, n_chips
from repro.launch.steps import build_cell_plan

# v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (1 active link assumed — conservative)


def run_cell(arch_id: str, shape_name: str, mesh_name: str) -> dict:
    spec = get_arch(arch_id)
    cell = spec.cells[shape_name]
    rec: dict = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": cell.kind,
    }
    if cell.skip is not None:
        rec["status"] = "skipped"
        rec["skip_reason"] = cell.skip
        return rec
    mesh = mesh_for(mesh_name)
    chips = n_chips(mesh)
    t0 = time.time()
    try:
        plan = build_cell_plan(spec, shape_name, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(plan.fn, in_shardings=plan.in_shardings).lower(*plan.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            if isinstance(ca, (list, tuple)):  # older JAX: one dict per device
                ca = ca[0] if ca else {}
            hlo = compiled.as_text()
        coll = costs.parse_collectives_loop_aware(hlo)
        # analytic step totals (XLA HloCostAnalysis counts loop bodies once —
        # see launch/costs.py — so the roofline numerators are analytic)
        cfg = spec.config_for(shape_name)
        dims = dict(cell.dims)
        if spec.family == "gnn":
            dims["_n_nodes"] = plan.static_meta["n_nodes"]
            dims["_n_edges"] = plan.static_meta["n_edges"]
        an = costs.analytic_costs(spec.family, cell.kind, cfg, dims)
        flops_dev_raw = float(ca.get("flops", 0.0))
        bytes_dev_raw = float(ca.get("bytes accessed", 0.0))
        coll_dev = float(coll.get("total_bytes", 0))
        compute_s = an["flops"] / chips / PEAK_FLOPS_BF16
        memory_s = an["bytes"] / chips / HBM_BW
        collective_s = coll_dev / ICI_BW
        terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
        bottleneck = max(terms, key=terms.get)
        rec.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=dict(
                argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                total_bytes=ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes,
            ),
            cost=dict(
                flops_total_analytic=an["flops"],
                bytes_total_analytic=an["bytes"],
                flops_per_device_xla_raw=flops_dev_raw,  # loop bodies counted once
                bytes_per_device_xla_raw=bytes_dev_raw,
            ),
            collectives=coll,
            model_flops=plan.model_flops,
            useful_flops_ratio=(plan.model_flops / an["flops"] if an["flops"] else None),
            roofline=dict(
                **terms,
                bottleneck=bottleneck,
                step_time_lower_bound_s=max(terms.values()),
                roofline_fraction=(
                    min(1.0, compute_s / max(max(terms.values()), 1e-30))
                ),
            ),
            static_meta=plan.static_meta,
            hlo_lines=hlo.count("\n"),
        )
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["wall_s"] = round(time.time() - t0, 2)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = sorted(ARCHS) if (args.all or args.arch is None) else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch_id in archs:
        spec = get_arch(arch_id)
        shapes = sorted(spec.cells) if (args.all or args.shape is None) else [args.shape]
        for shape in shapes:
            for mesh_name in meshes:
                rec = run_cell(arch_id, shape, mesh_name)
                fname = f"{arch_id}__{shape}__{mesh_name}.json"
                with open(os.path.join(args.out, fname), "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                if status == "ok":
                    r = rec["roofline"]
                    print(
                        f"[{status}] {arch_id}/{shape}/{mesh_name}: "
                        f"compile={rec['compile_s']}s "
                        f"mem/chip={rec['memory']['total_bytes']/2**30:.2f}GiB "
                        f"terms(c/m/x)={r['compute_s']:.2e}/{r['memory_s']:.2e}/{r['collective_s']:.2e}s "
                        f"bottleneck={r['bottleneck']}",
                        flush=True,
                    )
                elif status == "skipped":
                    print(f"[skip] {arch_id}/{shape}/{mesh_name}: {rec['skip_reason'][:80]}", flush=True)
                else:
                    failures += 1
                    print(f"[FAIL] {arch_id}/{shape}/{mesh_name}: {rec['error']}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
