"""jit'd wrapper around the CSR-native block-prune kernel.

Handles the engine <-> kernel impedance: the CSR arrays are viewed as
``[rows, 128]`` (the TPU's HBM tiling) with enough trailing zero rows that
every 8-row-aligned window covering ``[base, base + M)`` is in-bounds (the
sentinel term's empty list starts at the old array end), the block axis
pads to the 128-lane multiple (pad columns densify to 0 -> ``ub = 0`` ->
never survive), and counts clamp to ``M`` defensively — the engine's
:func:`repro.core.daat.csr_blockmax_offsets` already clamps, this keeps the
op safe standalone.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import KernelContract, ShapeCase
from repro.kernels.block_prune_csr.kernel import (
    ALIGN_ROWS,
    LANES,
    block_prune_csr_batched_kernel,
)
from repro.kernels.common import interpret_default, round_up


@partial(
    jax.jit, static_argnames=("n_blocks", "max_bm_per_term", "interpret")
)
def block_prune_csr_batched(
    bm_block: jax.Array,
    bm_weight: jax.Array,
    base: jax.Array,
    cnt: jax.Array,
    q_weights: jax.Array,
    theta: jax.Array,
    *,
    n_blocks: int,
    max_bm_per_term: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched (ub, survive_mask) straight off the CSR block-max lists.

    Args:
      bm_block/bm_weight: the index's CSR block-max arrays (``i32[n_bm]`` /
        ``f32[n_bm]``), HBM-resident; the wrapper appends the window pad.
      base/cnt: ``i32[B, Lq]`` per-(query, slot) window starts and valid
        entry counts (sentinel-mapped pad slots carry an empty window) —
        see :func:`repro.core.daat.csr_blockmax_offsets`.
      q_weights: ``f32[B, Lq]`` raw query weights (``<= 0`` slots already
        map to empty windows, so they contribute exactly 0).
      theta: ``f32[B]`` per-query prune thresholds (``-inf`` = pure ub pass).

    Returns ``(ub f32[B, n_blocks], survive bool[B, n_blocks])`` —
    ``ub`` bit-identical to the engine's slot-by-slot scatter
    (:func:`repro.core.daat.block_upper_bounds`).
    """
    if interpret is None:
        interpret = interpret_default()
    m = max_bm_per_term
    if m < 1:
        raise ValueError(f"max_bm_per_term={m} must be >= 1")
    nbp = round_up(max(n_blocks, 1), LANES)
    span = ALIGN_ROWS * LANES
    # a window starts on the span boundary at or below `base` and must cover
    # base + m - 1: at most m + span - 1 entries, rounded to whole spans
    window_rows = round_up(m + span - 1, span) // LANES
    n = bm_block.shape[0]
    rows = round_up(n, span) // LANES + window_rows

    def as_rows(x, dtype):
        flat = jnp.zeros((rows * LANES,), dtype).at[:n].set(x.astype(dtype))
        return flat.reshape(rows, LANES)

    ub, mask = block_prune_csr_batched_kernel(
        as_rows(bm_block, jnp.int32),
        as_rows(bm_weight, jnp.float32),
        base.astype(jnp.int32),
        jnp.minimum(cnt.astype(jnp.int32), m),
        q_weights.astype(jnp.float32),
        jnp.asarray(theta, jnp.float32),
        window_rows=window_rows,
        nbp=nbp,
        interpret=interpret,
    )
    return ub[:, :n_blocks], mask[:, :n_blocks].astype(jnp.bool_)


def _contract_call(dims):
    """Trace target for the static checker: abstract CSR inputs."""
    sds = jax.ShapeDtypeStruct
    B, lq = dims["batch"], dims["lq"]
    n_bm = dims["n_bm"]
    fn = partial(
        block_prune_csr_batched,
        n_blocks=dims["nb"], max_bm_per_term=dims["m"], interpret=True,
    )
    args = (
        sds((n_bm,), jnp.int32), sds((n_bm,), jnp.float32),  # CSR lists
        sds((B, lq), jnp.int32), sds((B, lq), jnp.int32),  # base / cnt
        sds((B, lq), jnp.float32), sds((B,), jnp.float32),  # qw / theta
    )
    return fn, args


# Single source of truth for the sweep shapes in tests/test_kernels.py and
# the checker's trace grid. expect_dma + expect_scalar_prefetch: the CSR
# windows MUST stream in via double-buffered make_async_copy from offsets
# that only scalar prefetch can deliver — a fall-back to pipelined blocks
# would silently reintroduce the densified HBM intermediate.
CONTRACT = KernelContract(
    name="block_prune_csr",
    description="CSR-walking block upper-bound + prune (DAAT phase 0, no densify)",
    make_call=_contract_call,
    expect_dma=True,
    expect_scalar_prefetch=True,
    shape_grid=(
        ShapeCase("b1", dict(batch=1, lq=8, nb=100, m=16, n_bm=800)),
        ShapeCase("b4_wide", dict(batch=4, lq=32, nb=2048, m=64, n_bm=12000)),
        ShapeCase("b3_tiny", dict(batch=3, lq=5, nb=17, m=3, n_bm=40)),
        ShapeCase("b2_single_slot", dict(batch=2, lq=1, nb=64, m=8, n_bm=100)),
    ),
)
