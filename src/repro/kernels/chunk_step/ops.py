"""jit'd wrapper around the fused DAAT chunk-step Pallas kernel.

Handles the engine <-> kernel interface impedance: the processed set is a
bool bitmap on the engine side but an i32 row inside the kernel (Mosaic has
no bool VMEM tiles), the block axis is padded to the 128-lane multiple
(pad lanes carry ``ub = -inf`` / ``processed = 1`` so they can never be
selected ahead of a real block — the selection breaks ``-inf`` ties toward
the lowest id, and every real block id sorts before every pad id), and
interpret-mode selection mirrors the other kernel packages so one call site
serves CPU tests and TPU deployments.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import KernelContract, ShapeCase
from repro.kernels.chunk_step.kernel import chunk_step_batched_kernel
from repro.kernels.common import interpret_default, pad_axis


def _run(doc_terms, doc_weights, q_terms, q_weights, ub, processed, pool_s,
         pool_i, theta, trips_left, *, trips, block_budget, block_size,
         n_live, live, interpret):
    """Shared engine-state plumbing for the one- and multi-trip entry points."""
    if interpret is None:
        interpret = interpret_default()
    B, nb = ub.shape
    if block_budget > nb:
        raise ValueError(
            f"block_budget={block_budget} exceeds n_blocks={nb}; the engine "
            "clamps budgets before the loop"
        )
    ubp = pad_axis(ub.astype(jnp.float32), 1, 128, fill=-jnp.inf)
    procp = pad_axis(processed.astype(jnp.int32), 1, 128, fill=1)
    if live is not None:
        live = live.astype(jnp.int32)[: nb * block_size].reshape(nb, 1, block_size)
    ps, pi, th, pr, td = chunk_step_batched_kernel(
        ubp,
        procp,
        pool_s.astype(jnp.float32),
        pool_i.astype(jnp.int32),
        theta.astype(jnp.float32),
        q_terms.astype(jnp.int32),
        jnp.where(q_weights > 0, q_weights.astype(jnp.float32), 0.0),
        doc_terms,
        doc_weights,
        trips_left,
        trips=trips,
        budget=block_budget,
        bs=block_size,
        n_live=n_live,
        live=live,
        interpret=interpret,
    )
    return ps, pi, th, pr[:, :nb].astype(jnp.bool_), td


@partial(
    jax.jit,
    static_argnames=("block_budget", "block_size", "n_live", "interpret"),
)
def chunk_step_batched(
    doc_terms: jax.Array,
    doc_weights: jax.Array,
    q_terms: jax.Array,
    q_weights: jax.Array,
    ub: jax.Array,
    processed: jax.Array,
    pool_s: jax.Array,
    pool_i: jax.Array,
    theta: jax.Array,
    *,
    block_budget: int,
    block_size: int,
    n_live: int,
    live: jax.Array | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused phase-2 chunk step over the whole ``[B, ...]`` state.

    Args mirror the engine's while-loop state plus the phase-0 products:
      doc_terms/doc_weights: the HBM doc-major store ``[n_docs_pad, Tmax]``.
      q_terms/q_weights: ``[B, Lq]``; weight-``<=0`` slots are padding.
      ub: ``f32[B, n_blocks]`` additive block upper bounds (phase 0).
      processed: ``bool[B, n_blocks]`` blocks already scored.
      pool_s/pool_i: the current top-k pool ``[B, k]``.
      theta: ``f32[B]`` current thresholds.
      live: optional i32/bool ``[n_docs_pad]`` lifecycle tombstone bitmap
        (nonzero = live), reshaped to block rows and DMA'd per selected block.

    Returns ``(pool_s, pool_i, theta, processed)`` with identical shapes and
    dtypes to the inputs — a drop-in replacement for the jnp while-body's
    select+score+merge (see :mod:`repro.kernels.chunk_step.ref`).
    """
    ps, pi, th, pr, td = _run(
        doc_terms, doc_weights, q_terms, q_weights, ub, processed, pool_s,
        pool_i, theta, jnp.ones((ub.shape[0],), jnp.int32), trips=1,
        block_budget=block_budget, block_size=block_size, n_live=n_live,
        live=live, interpret=interpret,
    )
    # a row with no remaining block above theta skips the trip in-kernel; the
    # step it skipped would only have reset theta to the pool's k-th score
    th = jnp.where(td > 0, th, ps[:, -1])
    return ps, pi, th, pr


@partial(
    jax.jit,
    static_argnames=(
        "trips_per_launch", "block_budget", "block_size", "n_live", "interpret",
    ),
)
def chunk_step_multi_batched(
    doc_terms: jax.Array,
    doc_weights: jax.Array,
    q_terms: jax.Array,
    q_weights: jax.Array,
    ub: jax.Array,
    processed: jax.Array,
    pool_s: jax.Array,
    pool_i: jax.Array,
    theta: jax.Array,
    trips_left: jax.Array,
    *,
    trips_per_launch: int,
    block_budget: int,
    block_size: int,
    n_live: int,
    live: jax.Array | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Up to ``trips_per_launch`` fused chunk steps in ONE kernel launch.

    Same engine-state interface as :func:`chunk_step_batched` plus
    ``trips_left: i32[B]`` — the per-row trip budget the kernel receives via
    scalar prefetch (the engine passes ``min(max_chunks - chunks,
    trips_per_launch)``; 0 freezes a row). Returns ``(pool_s, pool_i, theta,
    processed, trips_done)``: the state after up to ``trips_per_launch``
    sequential trips (the in-kernel early exit stops a row once rank-safe)
    and the per-row count of trips that actually advanced.
    """
    if trips_per_launch < 1:
        raise ValueError(f"trips_per_launch={trips_per_launch} must be >= 1")
    return _run(
        doc_terms, doc_weights, q_terms, q_weights, ub, processed, pool_s,
        pool_i, theta, trips_left.astype(jnp.int32), trips=trips_per_launch,
        block_budget=block_budget, block_size=block_size, n_live=n_live,
        live=live, interpret=interpret,
    )


def _contract_call(dims):
    """Trace target for the static checker: abstract engine-state inputs.

    Cases with a ``trips`` dim trace the multi-trip (scalar-prefetched)
    dispatch; the rest trace the per-trip kernel.
    """
    sds = jax.ShapeDtypeStruct
    B, k, lq = dims["B"], dims["k"], dims["lq"]
    bs, tmax = dims["block_size"], dims["tmax"]
    nb = -(-dims["n_docs"] // bs)
    ndp = nb * bs
    state = (
        sds((ndp, tmax), jnp.int32), sds((ndp, tmax), jnp.float32),  # doc store
        sds((B, lq), jnp.int32), sds((B, lq), jnp.float32),  # queries
        sds((B, nb), jnp.float32), sds((B, nb), jnp.bool_),  # ub / processed
        sds((B, k), jnp.float32), sds((B, k), jnp.int32),  # pool
        sds((B,), jnp.float32),  # theta
    )
    live_sds = sds((ndp,), jnp.int32) if dims.get("live") else None
    if "trips" in dims:
        kw = dict(
            trips_per_launch=dims["trips"], block_budget=dims["budget"],
            block_size=bs, n_live=dims["n_docs"], interpret=True,
        )
        state = state + (sds((B,), jnp.int32),)  # + trips_left
        if live_sds is not None:
            fn = lambda *a: chunk_step_multi_batched(*a[:-1], live=a[-1], **kw)
            return fn, state + (live_sds,)
        return partial(chunk_step_multi_batched, **kw), state
    kw = dict(
        block_budget=dims["budget"], block_size=bs, n_live=dims["n_docs"],
        interpret=True,
    )
    if live_sds is not None:
        fn = lambda *a: chunk_step_batched(*a[:-1], live=a[-1], **kw)
        return fn, state + (live_sds,)
    return partial(chunk_step_batched, **kw), state


# Single source of truth for the sweep shapes in tests/test_chunk_step.py and
# the checker's trace grid. expect_dma: the doc-major store MUST be pulled
# with double-buffered make_async_copy DMAs, and the checker's happens-before
# pass verifies every start is waited before its slot is read or reused —
# the race class this kernel's revolving buffers can hide.
CONTRACT = KernelContract(
    name="chunk_step",
    description="fused DAAT phase-2 chunk step (VMEM-resident select+score+merge)",
    make_call=_contract_call,
    expect_dma=True,
    # full B x budget x k cross on the 220-doc/bs=32 index (7 blocks: budget 3
    # is non-divisible, 7 == n_blocks), plus the ragged bs=24 degenerate and
    # the multi-trip (scalar-prefetched, in-kernel trip loop) cases — trips 1
    # degenerates to one gated trip, trips 4 spans the whole 7-block index at
    # budget 2, trips 3 exercises early exit headroom at the full budget
    shape_grid=tuple(
        ShapeCase(
            f"b{B}_budget{budget}_k{k}",
            dict(B=B, budget=budget, k=k, n_docs=220, block_size=32, lq=6, tmax=8),
        )
        for B in (1, 3)
        for budget in (1, 3, 7)
        for k in (1, 5)
    )
    + (
        ShapeCase(
            "ragged_bs24",  # bs not a lane multiple, 130/24 -> 6 blocks
            dict(B=2, budget=5, k=3, n_docs=130, block_size=24, lq=4, tmax=8),
        ),
    )
    + tuple(
        ShapeCase(
            f"multi_b{B}_trips{trips}_budget{budget}",
            dict(
                B=B, trips=trips, budget=budget, k=5,
                n_docs=220, block_size=32, lq=6, tmax=8,
            ),
            expect_scalar_prefetch=True,
        )
        for B, trips, budget in ((1, 1, 3), (3, 3, 7), (2, 4, 2))
    )
    + (
        ShapeCase(
            "multi_ragged_bs24",
            dict(B=2, trips=2, budget=5, k=3, n_docs=130, block_size=24, lq=4, tmax=8),
            expect_scalar_prefetch=True,
        ),
        # tombstone-bitmap (live-masked) variants: the live rows must ride the
        # same DMA discipline (third semaphore lane) the happens-before pass
        # checks for the doc store
        ShapeCase(
            "live_b2_budget3",
            dict(B=2, budget=3, k=5, n_docs=220, block_size=32, lq=6, tmax=8, live=1),
        ),
        ShapeCase(
            "multi_live_b2_trips3",
            dict(
                B=2, trips=3, budget=3, k=5,
                n_docs=220, block_size=32, lq=6, tmax=8, live=1,
            ),
            expect_scalar_prefetch=True,
        ),
    ),
)
