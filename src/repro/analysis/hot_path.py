"""Hot-path purity lint: the serving executables must be pure and cache-stable.

The serving stack's latency story rests on three trace-time invariants that
nothing at runtime enforces:

  * **No host syncs.** A ``callback``/``infeed``/``outfeed`` primitive inside
    a served executable stalls the device on the host every dispatch; the
    paper's predictable-latency claim dies quietly. All host I/O belongs in
    the host-side wrappers (``AnytimeServer.search_batch``'s timing,
    ``serve_bucketed``'s numpy bucketization), never under the trace.
  * **No dtype drift.** jit caches key on dtypes *and* weak-type flags. A
    caller handing i64 terms or a weak-typed python float forks the compile
    cache per call site — the admission queue's warmup grid no longer covers
    serve time and "compiled once" becomes "recompiles at p99".
  * **One executable per key.** ``AnytimeServer.executable_key`` promises a
    1:1 map from (engine statics, Lq bucket, B) to compiled programs. The
    queue's service-time EMA and the warmup grid both break if equal keys
    can retrace or distinct keys alias.

This module checks all three *statically*: it traces the exact engine
dispatch (``AnytimeServer.engine_fn``) or sharded serve step
(``make_sharded_serve_step``'s tagged fns) to a jaxpr at every
(config, Lq bucket, B) point — ``jax.make_jaxpr`` over ShapeDtypeStructs,
no arrays, no execution — and lints the result. Run via
``python -m repro.analysis.check --serving``.
"""
from __future__ import annotations

import hashlib
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_walk import iter_eqns
from repro.analysis.kernel_contracts import Violation

# Primitive names (substring match) that force a host round-trip inside a
# traced computation. "callback" covers pure_callback / io_callback /
# debug_callback; jax.debug.print traces to its own debug_print primitive;
# infeed/outfeed are the raw XLA host-transfer ops.
FORBIDDEN_PRIMITIVE_SUBSTRINGS = ("callback", "debug_print", "infeed", "outfeed")


def check_host_sync(closed_jaxpr, label: str = "<traced>", case: str = "trace"):
    """Flag host-round-trip primitives anywhere in a traced hot path."""
    out = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if any(s in name for s in FORBIDDEN_PRIMITIVE_SUBSTRINGS):
            out.append(
                Violation(
                    label, case, "host_sync",
                    f"primitive '{name}' forces a host round-trip inside the "
                    "served executable; hot paths must stay pure — move the "
                    "I/O to the host-side wrapper (search_batch / "
                    "serve_bucketed), not under the trace",
                )
            )
    return out


def check_dtype_discipline(closed_jaxpr, label: str = "<traced>", case: str = "trace"):
    """Flag compile-cache-forking dtypes at the executable boundary.

    Interface avals (invars/outvars) must be strong-typed — a weak-typed
    input means some call site passed a python scalar and the next strong
    caller retraces. f64 anywhere in the body means an x64 leak: the same
    program traced from an x64 context compiles a second, slower executable.
    """
    out = []
    jaxpr = closed_jaxpr.jaxpr
    for role, atoms in (("input", jaxpr.invars), ("output", jaxpr.outvars)):
        for i, atom in enumerate(atoms):
            aval = getattr(atom, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            if getattr(aval, "weak_type", False):
                out.append(
                    Violation(
                        label, case, "weak_type",
                        f"{role} {i} is weak-typed {aval.dtype}: a python "
                        "scalar leaked into the executable boundary and every "
                        "strong-typed caller will silently retrace — "
                        "canonicalize with jnp.asarray(x, dtype) before "
                        "dispatch",
                    )
                )
    seen: set = set()
    for eqn in iter_eqns(jaxpr):
        for atom in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(atom, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None or dt not in (jnp.float64, jnp.complex128):
                continue
            key = (eqn.primitive.name, str(dt))
            if key in seen:
                continue
            seen.add(key)
            out.append(
                Violation(
                    label, case, "f64_drift",
                    f"primitive '{eqn.primitive.name}' touches {dt}: an x64 "
                    "leak forks the compile cache (and doubles VMEM tiles) — "
                    "the hot path is an i32/f32 contract",
                )
            )
    return out


def check_no_densified_blockmax(
    closed_jaxpr,
    dense_shape: Sequence[int],
    label: str = "<traced>",
    case: str = "trace",
):
    """Flag the densified ``[B, Lq, n_blocks]`` block-max intermediate.

    Kernel-mode DAAT phase 0 walks the CSR block-max lists directly
    (``block_prune_csr``): the per-(query, slot) dense matrix must never be
    materialised — it is ``Lq`` x the footprint of the lists it expands from
    and every byte of it crosses HBM twice. Any aval of that exact shape in
    the traced search means the scatter-densify path crept back in.
    """
    out = []
    shape = tuple(int(d) for d in dense_shape)
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        for atom in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(atom, "aval", None)
            if getattr(aval, "shape", None) == shape:
                out.append(
                    Violation(
                        label, case, "dense_blockmax",
                        f"primitive '{eqn.primitive.name}' touches an aval of "
                        f"shape {shape} — the densified [B, Lq, n_blocks] "
                        "block-max intermediate is back in kernel-mode phase "
                        "0; the CSR prune kernel must consume base/cnt "
                        "windows off the index's bm lists, not scatter-dense "
                        "rows",
                    )
                )
                break
    return out


def fingerprint(closed_jaxpr) -> str:
    """Stable identity of a traced program (the executable-key invariant)."""
    return hashlib.sha1(str(closed_jaxpr).encode()).hexdigest()


def lint_trace(
    fn: Callable,
    args: Sequence,
    label: str,
    case: str,
) -> tuple[list, Optional[str]]:
    """Trace ``fn(*args)`` and run every purity check. -> (violations, fp).

    Traces TWICE and compares fingerprints: a nondeterministic trace (e.g. a
    dict-ordering or id()-dependent closure) means equal executable keys do
    not imply equal programs, which silently defeats the warmup grid.
    """
    try:
        jx = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        return (
            [Violation(label, case, "trace", f"hot path failed to trace: {e!r}")],
            None,
        )
    out = check_host_sync(jx, label, case) + check_dtype_discipline(jx, label, case)
    fp = fingerprint(jx)
    if fingerprint(jax.make_jaxpr(fn)(*args)) != fp:
        out.append(
            Violation(
                label, case, "retrace",
                "tracing the same hot path twice produced different jaxprs; "
                "the executable cache cannot be warmed for a nondeterministic "
                "trace",
            )
        )
    return out, fp


# --------------------------------------------------------------------------
# server lint: the AnytimeServer executable grid
# --------------------------------------------------------------------------


def _query_structs(B: int, lq: int):
    return (
        jax.ShapeDtypeStruct((B, lq), jnp.int32),
        jax.ShapeDtypeStruct((B, lq), jnp.float32),
    )


def lint_server(
    server,
    *,
    batch_sizes: Sequence[int] = (2, 4),
    rhos: Optional[Sequence[Optional[int]]] = None,
    label: Optional[str] = None,
    key_registry: Optional[dict] = None,
) -> list:
    """Lint every executable an :class:`AnytimeServer` can dispatch.

    Walks the full (rho-or-engine-config) x (Lq bucket) x (B) grid — the same
    grid ``warmup`` compiles and the admission queue flushes into — tracing
    ``server.engine_fn`` at each point. On top of the per-trace purity checks
    this asserts the executable-key invariant both ways: equal keys must
    fingerprint identically, distinct keys must fingerprint distinctly (a key
    that splits finer than the program means the cost model is learning two
    names for one executable).

    Pass one ``key_registry`` dict across several calls to extend the
    bijection over server *states* that never coexist in one dispatch grid —
    e.g. the same handle-backed server before and after a hot-swap
    compaction: each generation's (key, fingerprint) pairs land in the shared
    registry, so a key that fails to distinguish two generations' genuinely
    different programs (or splits one shared program in two) is a violation
    even though no single lint call sees both.
    """
    cfg = server.cfg
    if label is None:
        label = f"server:{cfg.engine}"
    if rhos is None:
        # EVERY ladder level: deadline degradation may flush any calibrated
        # rho, so each level is a dispatchable executable the key invariant
        # must cover (endpoints alone would miss a mid-ladder collision)
        rhos = [None] if cfg.engine == "daat" else list(server.rho_ladder)
    buckets = list(server.lq_buckets) if server.lq_buckets is not None else [8]
    out: list = []
    reg = key_registry if key_registry is not None else {}
    by_key: dict = reg.setdefault("by_key", {})
    by_fp: dict = reg.setdefault("by_fp", {})
    for bucket in buckets:
        for B in batch_sizes:
            for rho in dict.fromkeys(rhos):
                case = f"lq{bucket}_b{B}" + ("" if rho is None else f"_rho{rho}")
                vs, fp = lint_trace(
                    server.engine_fn(server.served_rho(rho, bucket)),
                    _query_structs(B, bucket), label, case,
                )
                out.extend(vs)
                if fp is None:
                    continue
                key = server.executable_key(bucket, B, rho)
                if key in by_key and by_key[key] != fp:
                    out.append(
                        Violation(
                            label, case, "executable_key",
                            f"executable_key {key} maps to two different "
                            "programs; equal keys must hit one compiled "
                            "executable",
                        )
                    )
                elif key not in by_key and fp in by_fp:
                    out.append(
                        Violation(
                            label, case, "executable_key",
                            f"executable_key {key} and {by_fp[fp]} name the "
                            "SAME program; the key distinguishes a config the "
                            "executable ignores, so the cost model learns two "
                            "names for one executable",
                        )
                    )
                by_key[key] = fp
                by_fp.setdefault(fp, key)
    return out


# --------------------------------------------------------------------------
# sharded serve lint: the pod-scale step behind make_bucketed_serve_step
# --------------------------------------------------------------------------


def lint_sharded_serve(
    serve,
    index_stack,
    *,
    batch_sizes: Sequence[int] = (2,),
    buckets: Optional[Sequence[int]] = None,
    label: str = "sharded",
    key_registry: Optional[dict] = None,
    live_stack=None,
) -> list:
    """Lint a (possibly bucketed) sharded/pod serve step at every bucket width.

    ``make_bucketed_serve_step``'s wrapper does host-side numpy bucketization
    and cannot be traced; its tagged ``.inner`` is the actual executable, so
    that is what gets traced — at each ``.buckets`` width, exactly the shapes
    the wrapper can dispatch.

    The step's tagged ``.statics`` dict names its compiled executable the
    same way ``AnytimeServer.executable_key`` does, so the one-executable-
    per-key bijection is asserted here too: (statics, bucket, B) keys must
    fingerprint 1:1. Pass one ``key_registry`` dict across several
    ``lint_sharded_serve`` calls and the bijection spans the whole serve
    surface — two steps whose statics differ (say, a pod mesh vs a
    single-host mesh at equal engine config) must never alias one program,
    and equal statics must never trace two. For a ``live_masked=True`` step
    pass the ``live_stack`` it will serve with; it rides as a traced operand.
    """
    inner = getattr(serve, "inner", serve)
    if buckets is None:
        tagged = getattr(serve, "buckets", None)
        if tagged is None:
            raise ValueError(
                "serve fn has no .buckets tag and no explicit buckets were "
                "given; pass buckets=(...) matching the widths it will serve"
            )
        buckets = tagged
    statics = getattr(serve, "statics", None)
    statics_key = (
        tuple(sorted(statics.items())) if isinstance(statics, dict) else None
    )
    reg = key_registry if key_registry is not None else {}
    by_key = reg.setdefault("by_key", {})
    by_fp = reg.setdefault("by_fp", {})
    out: list = []
    for bucket in buckets:
        for B in batch_sizes:
            case = f"lq{bucket}_b{B}"
            if live_stack is not None:
                fn = lambda qt, qw: inner(index_stack, qt, qw, live_stack=live_stack)  # noqa: E731
            else:
                fn = lambda qt, qw: inner(index_stack, qt, qw)  # noqa: E731
            vs, fp = lint_trace(fn, _query_structs(B, bucket), label, case)
            out.extend(vs)
            if fp is None or statics_key is None:
                continue
            key = statics_key + (int(bucket), int(B))
            if key in by_key and by_key[key] != fp:
                out.append(
                    Violation(
                        label, case, "executable_key",
                        "equal serve statics and shape traced two different "
                        "programs; the warmup grid cannot cover a "
                        "nondeterministic executable",
                    )
                )
            elif key not in by_key and fp in by_fp:
                out.append(
                    Violation(
                        label, case, "executable_key",
                        f"distinct serve statics/shape ({label}:{case} vs "
                        f"{by_fp[fp]}) name the SAME program; the key "
                        "distinguishes a config the executable ignores",
                    )
                )
            by_key[key] = fp
            by_fp.setdefault(fp, f"{label}:{case}")
    return out
