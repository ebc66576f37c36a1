"""Jaxpr traversal + Pallas introspection primitives for the static checkers.

Everything the analysis subsystem knows about JAX internals lives here:
recursive equation iteration (through pjit / scan / while / cond sub-jaxprs),
``pallas_call`` discovery, memory-space classification of kernel operands,
and the DMA happens-before abstract interpretation over an unrolled kernel
body. The contract/hot-path passes above this module only consume the small
dataclasses it returns, so a JAX upgrade that moves an attribute breaks ONE
file.

Layout facts this module relies on (verified against the pinned jax):

  * a ``pallas_call`` eqn's ``params["jaxpr"]`` is the kernel body whose
    invars are ``AbstractMemoryRef``s ordered (scalar-prefetch, inputs,
    outputs, scratch) — ``PrefetchScalarGridSpec`` operands arrive FIRST, as
    SMEM refs, counted by ``grid_mapping.num_index_operands``;
    ``params["grid_mapping"]`` carries ``grid``, ``block_mappings`` (inputs +
    outputs only, scalars excluded), and the ``num_*`` operand counts; a
    ``BlockMapping`` holds the full array as ``array_aval`` and its block as
    ``block_shape`` entries ``Squeezed()`` / ``Blocked(n)`` / ``Element`` /
    ``BoundedSlice`` (read through :func:`block_dims`);
  * ``dma_start`` / ``dma_wait`` eqns share one invar layout — the flat
    ``(src_ref, *src_idx, dst_ref, *dst_idx, sem_ref, *sem_idx)`` copy
    descriptor — with constant indices appearing as ``Literal``s;
  * VMEM ref reads/writes are ``get`` / ``swap`` eqns whose first invar is
    the ref and whose remaining invars are index atoms (``swap`` interposes
    the stored value at position 1).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, Optional, Sequence, Tuple

import numpy as np

from jax._src.core import ClosedJaxpr, Jaxpr, JaxprEqn, Literal, Var


# --------------------------------------------------------------------------
# generic traversal
# --------------------------------------------------------------------------


def _param_jaxprs(value: Any) -> Iterator[Jaxpr]:
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    stack = [value]
    while stack:
        v = stack.pop()
        if isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            stack.extend(v)
        elif isinstance(v, dict):
            stack.extend(v.values())


def sub_jaxprs(eqn: JaxprEqn) -> list[Jaxpr]:
    """All sub-jaxprs of one equation (pjit body, scan/while/cond branches...)."""
    out: list[Jaxpr] = []
    for v in eqn.params.values():
        out.extend(_param_jaxprs(v))
    return out


def iter_eqns(jaxpr: Jaxpr) -> Iterator[JaxprEqn]:
    """Depth-first iteration over every equation, including nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def find_primitives(jaxpr: Jaxpr, match: Callable[[str], bool]) -> list[JaxprEqn]:
    """Equations (at any nesting depth) whose primitive name satisfies ``match``."""
    return [e for e in iter_eqns(jaxpr) if match(e.primitive.name)]


def find_pallas_calls(jaxpr: Jaxpr) -> list[JaxprEqn]:
    return find_primitives(jaxpr, lambda n: n == "pallas_call")


# --------------------------------------------------------------------------
# memory-space / size classification
# --------------------------------------------------------------------------


def is_ref(atom: Any) -> bool:
    """True for a jaxpr atom whose aval is a (memory) ref."""
    if isinstance(atom, Literal):
        return False
    return hasattr(atom.aval, "inner_aval")


def memory_space_of(aval: Any) -> str:
    """Normalized memory space of a kernel ref aval.

    Pallas leaves the default (pipelined VMEM block) space as ``None``; the
    explicit spaces stringify to ``any`` / ``vmem`` / ``smem`` /
    ``semaphore_mem`` across the jax versions we care about.
    """
    ms = getattr(aval, "memory_space", None)
    if ms is None:
        return "vmem"
    s = str(ms).lower()
    for known in ("semaphore", "smem", "vmem", "any", "hbm"):
        if known in s:
            return "hbm" if known == "any" else known
    return s


def aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    try:
        itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
    except TypeError:  # semaphore refs carry an opaque dtype: count 4 bytes
        itemsize = 4
    return int(np.prod(shape, dtype=np.int64)) * itemsize if shape else itemsize


# --------------------------------------------------------------------------
# pallas_call operand bookkeeping
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelOperand:
    """One kernel-body invar, classified for the VMEM budget pass."""

    label: str  # e.g. "scalar[0]", "in[3] args[3]", "out[0]", "scratch[1]"
    role: str  # "scalar" | "in" | "out" | "scratch"
    space: str  # normalized memory space ("vmem", "hbm", "smem", "semaphore")
    block_shape: Tuple[int, ...]  # VMEM-resident tile shape (block or scratch)
    dtype: Any
    nbytes: int  # bytes of ONE buffer instance (no pipeline multiplier)
    array_shape: Tuple[int, ...]  # full HBM array shape ("" for scratch)
    block_mapping: Any = None  # the pallas BlockMapping (inputs/outputs only)
    # per-array-dim block extent (a squeezed dim counts 1); () for scratch
    block_dims: Tuple[int, ...] = ()


def block_dims(block_mapping: Any) -> Tuple[Tuple[int, ...], Tuple[bool, ...]]:
    """``(extent per array dim, squeezed per array dim)`` of one BlockMapping."""
    from jax._src.pallas import core as pallas_core

    extents, squeezed = [], []
    for d in block_mapping.block_shape:
        if d is None or isinstance(d, pallas_core.Squeezed):
            extents.append(1)
            squeezed.append(True)
        elif isinstance(d, int):
            extents.append(d)
            squeezed.append(False)
        else:
            extents.append(int(pallas_core._get_block_dim_size(d)))
            squeezed.append(False)
    return tuple(extents), tuple(squeezed)


def _block_bytes(block_mapping: Any, dtype: Any) -> Tuple[Tuple[int, ...], int]:
    """VMEM tile shape (squeezed dims dropped) and its bytes."""
    extents, squeezed = block_dims(block_mapping)
    dims = tuple(e for e, sq in zip(extents, squeezed) if not sq)
    n = 1
    for d in dims:
        n *= d
    return dims, n * np.dtype(dtype).itemsize


def num_scalar_prefetch_operands(pallas_eqn: JaxprEqn) -> int:
    """Scalar-prefetch (``PrefetchScalarGridSpec``) operand count of one call."""
    return int(getattr(pallas_eqn.params["grid_mapping"], "num_index_operands", 0))


def kernel_operands(pallas_eqn: JaxprEqn) -> list[KernelOperand]:
    """Classify every kernel invar of one ``pallas_call`` equation."""
    gm = pallas_eqn.params["grid_mapping"]
    kernel_jaxpr: Jaxpr = pallas_eqn.params["jaxpr"]
    n_scalar = num_scalar_prefetch_operands(pallas_eqn)
    n_in = gm.num_inputs
    n_out = gm.num_outputs
    n_scratch = gm.num_scratch_operands
    invars = kernel_jaxpr.invars
    if len(invars) != n_scalar + n_in + n_out + n_scratch:
        raise ValueError(
            f"kernel jaxpr has {len(invars)} invars; grid_mapping claims "
            f"{n_scalar}+{n_in}+{n_out}+{n_scratch} "
            "(scalar-prefetch+inputs+outputs+scratch) — pallas internals "
            "changed, update jaxpr_walk.kernel_operands"
        )
    out: list[KernelOperand] = []
    mappings = list(gm.block_mappings)
    for i, var in enumerate(invars):
        aval = var.aval
        space = memory_space_of(aval)
        if i < n_scalar:
            # scalar-prefetch refs live in SMEM, carry no block mapping, and
            # cost no VMEM — but the budget/divisibility passes must still
            # see them so the invar count reconciles
            dtype = getattr(aval, "dtype", np.int32)
            shape = tuple(getattr(aval, "shape", ()))
            out.append(
                KernelOperand(
                    f"scalar[{i}]", "scalar", space, shape, dtype, aval_bytes(aval), ()
                )
            )
            continue
        i -= n_scalar
        if i < n_in + n_out:
            role = "in" if i < n_in else "out"
            idx = i if i < n_in else i - n_in
            bm = mappings[i] if i < len(mappings) else None
            origin = getattr(bm, "origin", "") if bm is not None else ""
            label = f"{role}[{idx}]" + (f" {origin}" if origin else "")
            if bm is not None:
                dtype = bm.array_aval.dtype
                block_shape, nbytes = _block_bytes(bm, dtype)
                array_shape = tuple(bm.array_aval.shape)
                extents = block_dims(bm)[0]
            else:  # defensive: fall back to the aval itself
                dtype = getattr(aval, "dtype", np.float32)
                block_shape = tuple(getattr(aval, "shape", ()))
                nbytes = aval_bytes(aval)
                array_shape = extents = block_shape
            out.append(
                KernelOperand(
                    label, role, space, block_shape, dtype, nbytes, array_shape, bm, extents
                )
            )
        else:
            j = i - n_in - n_out
            dtype = getattr(aval, "dtype", np.int32)
            shape = tuple(getattr(aval, "shape", ()))
            out.append(
                KernelOperand(
                    f"scratch[{j}]", "scratch", space, shape, dtype, aval_bytes(aval), ()
                )
            )
    return out


# --------------------------------------------------------------------------
# DMA happens-before abstract interpretation
# --------------------------------------------------------------------------


@dataclasses.dataclass
class PendingDma:
    """One in-flight async copy, keyed by its completion semaphore slot."""

    dst: Var
    dst_slot: Optional[int]  # None = statically unknown (matches any slot)
    sem: Var
    sem_idx: Optional[Tuple[int, ...]]  # None = statically unknown
    where: str  # human-readable start site


@dataclasses.dataclass
class DmaReport:
    """Result of the happens-before pass over one kernel jaxpr."""

    starts: int = 0
    waits: int = 0
    violations: list[str] = dataclasses.field(default_factory=list)


def _copy_descriptor(eqn: JaxprEqn) -> Tuple[Any, Optional[int], Any, Optional[Tuple[int, ...]]]:
    """Parse a dma_start/dma_wait invar list into (dst, slot, sem, sem_idx).

    The flat layout is ``(src_ref, *src_idx, dst_ref, *dst_idx, sem_ref,
    *sem_idx)``; groups are delimited by the ref-typed invars. Non-literal
    indices parse to ``None`` (= "unknown", matched conservatively).
    """
    groups: list[list[Any]] = []
    for v in eqn.invars:
        if is_ref(v):
            groups.append([v])
        elif groups:
            groups[-1].append(v)
    if len(groups) < 3:
        raise ValueError(
            f"{eqn.primitive.name} with {len(groups)} ref operands — expected "
            "(src, dst, sem); remote-copy layouts need a jaxpr_walk extension"
        )
    dst_ref, *dst_idx = groups[-2]
    sem_ref, *sem_idx = groups[-1]
    slot: Optional[int] = None
    for a in dst_idx:
        if isinstance(a, Literal):
            slot = int(a.val)
            break
    idx: Optional[Tuple[int, ...]]
    if all(isinstance(a, Literal) for a in sem_idx):
        idx = tuple(int(a.val) for a in sem_idx)
    else:
        idx = None
    return dst_ref, slot, sem_ref, idx


def _sem_matches(p: PendingDma, sem: Var, idx: Optional[Tuple[int, ...]]) -> bool:
    if p.sem is not sem:
        return False
    return p.sem_idx is None or idx is None or p.sem_idx == idx


def _slot_matches(pending_slot: Optional[int], access_slot: Optional[int]) -> bool:
    return pending_slot is None or access_slot is None or pending_slot == access_slot


def _access_slot(eqn: JaxprEqn) -> Optional[int]:
    """First literal index of a get/swap (the buffer-slot coordinate)."""
    start = 2 if eqn.primitive.name == "swap" else 1
    for a in eqn.invars[start:]:
        if isinstance(a, Literal):
            return int(a.val)
    return None


def check_dma_discipline(kernel_jaxpr: Jaxpr) -> DmaReport:
    """Happens-before over the unrolled kernel body.

    Flags, in program order:
      * a ``dma_start`` whose semaphore slot still has an un-waited copy in
        flight (the revolving-buffer reuse race);
      * a ``get``/``swap`` touching a destination buffer slot with a copy
        still in flight (read/write before wait);
      * a ``dma_wait`` with no matching start;
      * any copy still in flight when the body ends (start without wait).

    ``cond`` branches are analyzed independently and their in-flight sets
    merged by *intersection* (a copy waited on any path counts as waited):
    the lint gates CI, so a false "missing wait" on the epilogue-under-
    ``pl.when`` pipelining idiom would be worse than missing a race that
    only one branch closes. ``while``/``scan`` bodies are analyzed inline
    against the current in-flight set.
    """
    report = DmaReport()
    pending = _walk_dma(kernel_jaxpr, [], report)
    for p in pending:
        report.violations.append(
            f"dma_start at {p.where} is never waited on: destination "
            f"{_fmt_ref(p.dst)} slot {p.dst_slot} may still be in flight when "
            "the kernel body ends (missing make_async_copy(...).wait())"
        )
    return report


def _fmt_ref(var: Var) -> str:
    aval = var.aval
    return f"ref{getattr(aval, 'shape', '?')}@{memory_space_of(aval)}"


def _walk_dma(jaxpr: Jaxpr, pending: list[PendingDma], report: DmaReport) -> list[PendingDma]:
    pending = list(pending)
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name == "dma_start":
            dst, slot, sem, idx = _copy_descriptor(eqn)
            where = f"eqn {i} ({_fmt_ref(dst)} slot {slot}, sem idx {idx})"
            for p in pending:
                if _sem_matches(p, sem, idx):
                    report.violations.append(
                        f"dma_start at {where} reuses semaphore slot {idx} while "
                        f"the copy started at {p.where} is still in flight — "
                        "wait() must run before the slot revolves"
                    )
                elif p.dst is dst and _slot_matches(p.dst_slot, slot):
                    # different semaphore, same destination buffer slot: the
                    # trip-loop revolving-buffer race a per-trip sem rotation
                    # hides from the semaphore check above
                    report.violations.append(
                        f"dma_start at {where} overwrites destination "
                        f"{_fmt_ref(dst)} slot {slot} while the copy started at "
                        f"{p.where} is still in flight into the same slot — "
                        "the two copies race on the buffer even though their "
                        "semaphores differ; wait() the first before revolving"
                    )
            report.starts += 1
            pending.append(PendingDma(dst, slot, sem, idx, where))
        elif name == "dma_wait":
            dst, slot, sem, idx = _copy_descriptor(eqn)
            matched = [p for p in pending if _sem_matches(p, sem, idx)]
            if not matched:
                report.violations.append(
                    f"dma_wait at eqn {i} (sem idx {idx}) has no matching "
                    "dma_start on this path — wait on an idle semaphore "
                    "deadlocks on device"
                )
            else:
                pending.remove(matched[0])
            report.waits += 1
        elif name in ("get", "swap") and eqn.invars and is_ref(eqn.invars[0]):
            ref = eqn.invars[0]
            slot = _access_slot(eqn)
            for p in pending:
                if p.dst is ref and _slot_matches(p.dst_slot, slot):
                    verb = "read" if name == "get" else "overwritten"
                    report.violations.append(
                        f"{_fmt_ref(ref)} slot {slot} is {verb} at eqn {i} while "
                        f"the copy started at {p.where} is still in flight — "
                        "missing wait() before the access"
                    )
        elif name == "cond":
            branches = [b for b in sub_jaxprs(eqn)]
            if branches:
                results = [_walk_dma(b, pending, report) for b in branches]
                # intersection-by-identity: survive only if pending on EVERY path
                pending = [
                    p for p in results[0] if all(any(q is p for q in r) for r in results[1:])
                ]
        else:
            for sub in sub_jaxprs(eqn):
                pending = _walk_dma(sub, pending, report)
    return pending
