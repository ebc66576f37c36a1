"""KernelContract: machine-checked invariants for the Pallas kernel packages.

Every ``kernels/*/ops.py`` exports a ``CONTRACT`` declaring the shapes its
interpret-mode sweeps exercise (the representative grid, ragged degenerates
included), the per-core VMEM budget the kernel must fit, and whether the
kernel is expected to issue async copies. :func:`check_contract` traces the
wrapped op to a jaxpr at every declared shape — no execution, no device —
and runs three passes over each ``pallas_call`` it finds:

  1. **VMEM footprint** — pipelined input/output tiles count twice (Pallas
     double-buffers blocked operands behind the grid), VMEM scratch once,
     ANY/semaphore operands not at all; failures carry the full per-operand
     breakdown so the offending tile is named, not inferred.
  2. **Grid/index-map divisibility** — every blocked dimension must divide
     its array dimension (the wrappers pre-pad; a ragged tile silently
     masks or miscompiles on device), and the block index map must stay in
     range over the whole grid, evaluated point by point.
  3. **DMA happens-before** — every ``make_async_copy`` start must be waited
     before its destination slot is read, its semaphore slot revolves, or a
     second copy starts into the same destination slot (the double-buffer /
     trip-loop revolving-buffer race classes in ``chunk_step``), and no copy
     may be left in flight at the end of the body.
  4. **Scalar prefetch** — a contract (or an individual case) that declares
     ``expect_scalar_prefetch`` must trace to a ``pallas_call`` with
     ``PrefetchScalarGridSpec`` operands; a silent fall-back to a static
     grid would drop the dynamic trip-budget / CSR-offset dispatch.

The shape grid is the single source of truth for the kernel test sweeps:
``tests/test_kernels.py`` parametrizes from ``CONTRACT.sweep(...)`` instead
of duplicating shape literals.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax

from repro.analysis import jaxpr_walk

# Per-core VMEM on current TPU generations (see the pallas guide); contracts
# may declare tighter limits but never looser ones.
VMEM_BYTES_PER_CORE = 16 * 2**20

# Cap on exhaustive index-map evaluation; beyond it the grid is corner-sampled.
_MAX_GRID_POINTS = 4096


@dataclasses.dataclass(frozen=True)
class ShapeCase:
    """One named point of a contract's shape grid.

    ``dims`` holds the op-level shape parameters (the same names the test
    sweeps use), so a case is both a trace target for the checker and a
    parametrize row for the interpret-mode tests.

    ``expect_scalar_prefetch`` overrides the contract-level default for this
    case (``None`` = inherit): a grid may mix plain cases with
    ``PrefetchScalarGridSpec`` cases (e.g. single-trip vs multi-trip
    ``chunk_step``), and the checker must know which dispatch each case is
    supposed to take.
    """

    name: str
    dims: Mapping[str, int]
    expect_scalar_prefetch: Optional[bool] = None

    def __post_init__(self):
        object.__setattr__(self, "dims", dict(self.dims))


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Declared invariants for one kernel package (exported as ``CONTRACT``).

    ``make_call(dims)`` returns ``(fn, args)`` such that ``fn(*args)`` traces
    the package's op at that shape (interpret mode, deterministic inputs).
    """

    name: str
    make_call: Callable[[Mapping[str, int]], Tuple[Callable, tuple]]
    shape_grid: Tuple[ShapeCase, ...]
    vmem_limit_bytes: int = VMEM_BYTES_PER_CORE
    expect_dma: bool = False
    expect_scalar_prefetch: bool = False
    description: str = ""

    def __post_init__(self):
        if self.vmem_limit_bytes > VMEM_BYTES_PER_CORE:
            raise ValueError(
                f"contract {self.name!r}: vmem_limit_bytes="
                f"{self.vmem_limit_bytes} exceeds the per-core budget "
                f"{VMEM_BYTES_PER_CORE}"
            )
        names = [c.name for c in self.shape_grid]
        if len(set(names)) != len(names):
            raise ValueError(f"contract {self.name!r}: duplicate case names {names}")

    def sweep(
        self, *dim_names: str, require: Sequence[str] = (), exclude: Sequence[str] = ()
    ) -> list[tuple]:
        """Shape tuples for test parametrization: one row per grid case that
        defines every requested dim (single dims flatten to scalars).

        ``require``/``exclude`` filter cases by the presence of OTHER dims —
        e.g. ``exclude=("batch",)`` selects the single-query cases.
        """
        rows = []
        for case in self.shape_grid:
            if any(n in case.dims for n in exclude):
                continue
            if not all(n in case.dims for n in require):
                continue
            if all(n in case.dims for n in dim_names):
                row = tuple(case.dims[n] for n in dim_names)
                rows.append(row[0] if len(dim_names) == 1 else row)
        return rows

    def sweep_values(
        self, dim_name: str, require: Sequence[str] = (), exclude: Sequence[str] = ()
    ) -> list[int]:
        """Deduplicated, order-preserving values of one dim across the grid."""
        return list(dict.fromkeys(self.sweep(dim_name, require=require, exclude=exclude)))


@dataclasses.dataclass(frozen=True)
class Violation:
    contract: str
    case: str
    check: str  # "vmem" | "divisibility" | "index_map" | "dma" | "scalar_prefetch" | "trace"
    message: str

    def __str__(self) -> str:
        return f"[{self.contract} / {self.case} / {self.check}] {self.message}"


# --------------------------------------------------------------------------
# the three passes
# --------------------------------------------------------------------------


def vmem_footprint(pallas_eqn) -> Tuple[int, list[tuple[str, int, str]]]:
    """(total bytes, [(operand label, counted bytes, note)]) for one launch."""
    rows: list[tuple[str, int, str]] = []
    total = 0
    for op in jaxpr_walk.kernel_operands(pallas_eqn):
        if op.space == "vmem" and op.role in ("in", "out"):
            counted = 2 * op.nbytes
            note = f"block {op.block_shape} {np.dtype(op.dtype).name} x2 (pipeline double-buffer)"
        elif op.space == "vmem":  # scratch
            counted = op.nbytes
            note = f"scratch {op.block_shape} {np.dtype(op.dtype).name}"
        else:
            counted = 0
            note = f"{op.space} (not VMEM-resident)"
        rows.append((op.label, counted, note))
        total += counted
    return total, rows


def _check_vmem(contract: KernelContract, case: ShapeCase, eqn) -> list[Violation]:
    total, rows = vmem_footprint(eqn)
    if total <= contract.vmem_limit_bytes:
        return []
    breakdown = "\n".join(
        f"    {label:<28} {counted:>12,} B  {note}" for label, counted, note in rows
    )
    return [
        Violation(
            contract.name,
            case.name,
            "vmem",
            f"per-core VMEM footprint {total:,} B exceeds the contract limit "
            f"{contract.vmem_limit_bytes:,} B; breakdown:\n{breakdown}",
        )
    ]


def _grid_points(grid: Sequence[int]) -> list[tuple[int, ...]]:
    import itertools

    dims = [int(g) for g in grid]
    n = 1
    for g in dims:
        n *= max(g, 1)
    if n <= _MAX_GRID_POINTS:
        return list(itertools.product(*[range(g) for g in dims]))
    # corner-sample: first / middle / last index per axis covers the bound
    # checks that actually fail in practice (off-by-one at either end)
    axes = [sorted({0, g // 2, g - 1}) for g in dims]
    return list(itertools.product(*axes))


def _check_blocks(contract: KernelContract, case: ShapeCase, eqn) -> list[Violation]:
    out: list[Violation] = []
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    points = _grid_points(grid) if grid else [()]
    for op in jaxpr_walk.kernel_operands(eqn):
        bm = op.block_mapping
        if bm is None or op.space != "vmem":
            continue
        block = op.block_dims
        array_shape = op.array_shape
        nblocks = []
        for d, (b, s) in enumerate(zip(block, array_shape)):
            if s % b != 0:
                out.append(
                    Violation(
                        contract.name,
                        case.name,
                        "divisibility",
                        f"{op.label}: array dim {d} ({s}) is not a multiple of "
                        f"its block dim ({b}) — the ops wrapper must pre-pad "
                        "(ragged tiles mask silently in interpret mode and "
                        "miscompile on device)",
                    )
                )
            nblocks.append(-(-s // b))
        imj = getattr(bm, "index_map_jaxpr", None)
        if imj is None:
            continue
        # scalar-prefetch operands trail the grid indices in the index-map
        # signature; the maps here never read them (`lambda b, *_: ...`), so
        # zero placeholders keep eval_jaxpr's arity happy
        n_extra = max(0, len(imj.jaxpr.invars) - len(points[0] if points else ()))
        extra = [np.int32(0)] * n_extra
        for pt in points:
            idx = jax.core.eval_jaxpr(imj.jaxpr, imj.consts, *map(np.int32, pt), *extra)
            vals = [int(v) for v in idx]
            if len(vals) != len(nblocks):
                out.append(
                    Violation(
                        contract.name,
                        case.name,
                        "index_map",
                        f"{op.label}: index map returns {len(vals)} coords for a "
                        f"rank-{len(nblocks)} block shape",
                    )
                )
                break
            bad = [
                (d, v, nb) for d, (v, nb) in enumerate(zip(vals, nblocks)) if not 0 <= v < nb
            ]
            if bad:
                d, v, nb = bad[0]
                out.append(
                    Violation(
                        contract.name,
                        case.name,
                        "index_map",
                        f"{op.label}: at grid point {pt} the index map returns "
                        f"block coord {v} on dim {d}, outside [0, {nb}) — the "
                        "tile would read/write past the padded array",
                    )
                )
                break
    return out


def _check_scalar_prefetch(
    contract: KernelContract, case: ShapeCase, eqns
) -> list[Violation]:
    expected = case.expect_scalar_prefetch
    if expected is None:
        expected = contract.expect_scalar_prefetch
    count = sum(jaxpr_walk.num_scalar_prefetch_operands(eqn) for eqn in eqns)
    if expected and count == 0:
        return [
            Violation(
                contract.name,
                case.name,
                "scalar_prefetch",
                "contract expects scalar-prefetch operands at this case but the "
                "traced pallas_call declares none (num_index_operands == 0) — "
                "the dynamic-offset/trip-budget dispatch is not being taken",
            )
        ]
    return []


def _check_dma(contract: KernelContract, case: ShapeCase, eqns) -> list[Violation]:
    out: list[Violation] = []
    starts = 0
    for eqn in eqns:
        report = jaxpr_walk.check_dma_discipline(eqn.params["jaxpr"])
        starts += report.starts
        out.extend(
            Violation(contract.name, case.name, "dma", msg) for msg in report.violations
        )
    if contract.expect_dma and starts == 0:
        out.append(
            Violation(
                contract.name,
                case.name,
                "dma",
                "contract declares expect_dma=True but the traced kernel issues "
                "no async copies — the HBM-resident operands are being copied "
                "by the pipeline instead of make_async_copy",
            )
        )
    return out


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------


def check_contract(
    contract: KernelContract, case_names: Optional[Sequence[str]] = None
) -> list[Violation]:
    """Trace + verify one contract over its shape grid. Returns violations."""
    out: list[Violation] = []
    for case in contract.shape_grid:
        if case_names is not None and case.name not in case_names:
            continue
        try:
            fn, args = contract.make_call(case.dims)
            closed = jax.make_jaxpr(fn)(*args)
        except Exception as e:  # noqa: BLE001 — a trace failure IS a finding
            out.append(
                Violation(
                    contract.name,
                    case.name,
                    "trace",
                    f"tracing failed at dims {dict(case.dims)}: {type(e).__name__}: {e}",
                )
            )
            continue
        eqns = jaxpr_walk.find_pallas_calls(closed.jaxpr)
        if not eqns:
            out.append(
                Violation(
                    contract.name,
                    case.name,
                    "trace",
                    "no pallas_call in the traced op — the kernel path is not "
                    "being exercised at these dims",
                )
            )
            continue
        for eqn in eqns:
            out.extend(_check_vmem(contract, case, eqn))
            out.extend(_check_blocks(contract, case, eqn))
        out.extend(_check_dma(contract, case, eqns))
        out.extend(_check_scalar_prefetch(contract, case, eqns))
    return out


def all_contracts() -> dict[str, KernelContract]:
    """Import every kernel package's CONTRACT (the checked-in registry)."""
    from repro.kernels.block_prune import ops as block_prune
    from repro.kernels.block_prune_csr import ops as block_prune_csr
    from repro.kernels.block_topk import ops as block_topk
    from repro.kernels.chunk_step import ops as chunk_step
    from repro.kernels.impact_scatter import ops as impact_scatter
    from repro.kernels.impact_scatter_topk import ops as impact_scatter_topk
    from repro.kernels.sparse_score import ops as sparse_score

    modules = (
        block_prune, block_prune_csr, block_topk, chunk_step, impact_scatter,
        impact_scatter_topk, sparse_score,
    )
    out: dict[str, KernelContract] = {}
    for mod in modules:
        contract = getattr(mod, "CONTRACT", None)
        if contract is None:
            raise AttributeError(
                f"{mod.__name__} exports no CONTRACT — every kernel package "
                "must declare one (see src/repro/analysis/README.md)"
            )
        out[contract.name] = contract
    return out
