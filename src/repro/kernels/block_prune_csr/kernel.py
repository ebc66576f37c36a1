"""Pallas TPU kernel: CSR-native Block-Max upper bound + threshold prune.

``block_prune_batched`` eats a densified ``[B, Lq, n_blocks]`` block-max
matrix — ``Lq`` x the footprint of the CSR lists it expands from, written to
HBM by the engine's scatter just to be re-read by the kernel. This kernel
walks the CSR block-max lists directly: the per-(query, slot) window offsets
and entry counts arrive via scalar prefetch (``PrefetchScalarGridSpec`` SMEM
operands — DMA source offsets must be known before the body runs), each
slot's window of ``bm_block``/``bm_weight`` streams HBM->VMEM with
double-buffered async copies (slot ``l+1`` prefetches while slot ``l``
densifies), and the bound accumulates in VMEM.

HBM layout: the ops wrapper views each CSR array as ``[rows, 128]``, the
TPU's tiled layout, so a window is the run of 8-row-aligned rows covering
``[base, base + cnt)``; entries outside that range are masked by position.

Parity contract: slot ``l`` adds ``q_weights[l] * blockmax[l, blk]`` to the
bound of every block on its list, slot after slot — the order of the jnp
engine's per-slot scatter (:func:`repro.core.daat.block_upper_bounds`). A
block id appears at most once per list, so each slot's densified row has one
nonzero per block and ``ub`` is bit-identical to the engine's, so engine ids
and WorkStats cannot move.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ALIGN_ROWS = 8  # HBM tile rows: window starts must sit on a multiple of 8 rows


def _prune_csr_kernel_batched(
    base_ref,  # SMEM i32[B * Lq] — window starts (flat entry offsets)
    cnt_ref,  # SMEM i32[B * Lq] — valid entry counts
    qw_ref,  # SMEM f32[B * Lq] — query weights
    theta_ref,  # SMEM f32[B] — prune thresholds
    bm_block_hbm,  # i32[rows, 128] — stays in HBM, DMA'd per slot
    bm_weight_hbm,  # f32[rows, 128] — stays in HBM, DMA'd per slot
    ub_ref,  # out f32[1, NBp]
    mask_ref,  # out i32[1, NBp]
    blk_buf,  # VMEM i32[2, RW, 128] — double-buffered block-id windows
    w_buf,  # VMEM f32[2, RW, 128] — double-buffered block-max windows
    sems,  # DMA semaphores (slot, block/weight)
    *,
    lq: int,
):
    b = pl.program_id(0)
    nbp = ub_ref.shape[1]
    rw = blk_buf.shape[1]
    span = ALIGN_ROWS * LANES

    def row0(l):
        return pl.multiple_of(base_ref[b * lq + l] // span * ALIGN_ROWS, ALIGN_ROWS)

    def window_dma(slot, l):
        rows = pl.ds(row0(l), rw)
        return (
            pltpu.make_async_copy(bm_block_hbm.at[rows, :], blk_buf.at[slot], sems.at[slot, 0]),
            pltpu.make_async_copy(bm_weight_hbm.at[rows, :], w_buf.at[slot], sems.at[slot, 1]),
        )

    ub_ref[...] = jnp.zeros_like(ub_ref)
    for c in window_dma(0, 0):  # warm up the pipeline
        c.start()
    for l in range(lq):
        slot = l % 2
        if l + 1 < lq:  # prefetch the next slot's window while densifying
            for c in window_dma((l + 1) % 2, l + 1):
                c.start()
        for c in window_dma(slot, l):
            c.wait()
        off = base_ref[b * lq + l] - row0(l) * LANES  # window-relative start
        cnt = cnt_ref[b * lq + l]
        qw = qw_ref[b * lq + l]
        r_lo = off // LANES
        r_hi = jnp.where(cnt > 0, (off + cnt - 1) // LANES + 1, r_lo)

        def block_chunk(ci, carry, slot=slot, off=off, cnt=cnt, qw=qw, r_lo=r_lo, r_hi=r_hi):
            blocks = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) + ci * LANES

            def window_row(r, acc):
                pos = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) + r * LANES
                valid = (pos >= off) & (pos < off + cnt)
                blk = blk_buf[slot, pl.ds(r, 1), :]
                w = w_buf[slot, pl.ds(r, 1), :]
                return acc + jnp.where((blocks == blk) & valid, w, 0.0)

            acc = jax.lax.fori_loop(
                r_lo, r_hi, window_row, jnp.zeros((LANES, LANES), jnp.float32)
            )
            dense = jnp.sum(acc, axis=1)[None, :]  # one nonzero per block: exact
            sl = pl.ds(pl.multiple_of(ci * LANES, LANES), LANES)
            ub_ref[:, sl] = ub_ref[:, sl] + qw * dense
            return carry

        jax.lax.fori_loop(0, nbp // LANES, block_chunk, 0)

    ub = ub_ref[...]
    mask_ref[...] = ((ub > theta_ref[b]) & (ub > 0)).astype(jnp.int32)


def block_prune_csr_batched_kernel(
    bm_block: jax.Array,  # i32[rows, 128] — padded so every window is in-bounds
    bm_weight: jax.Array,  # f32[rows, 128]
    base: jax.Array,  # i32[B, Lq]
    cnt: jax.Array,  # i32[B, Lq] (already clamped to m)
    q_weights: jax.Array,  # f32[B, Lq]
    theta: jax.Array,  # f32[B]
    *,
    window_rows: int,
    nbp: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """CSR-walking (ub, survive_mask) over doc blocks: grid over B.

    ``base``/``cnt``/``q_weights``/``theta`` ride in as scalar-prefetch
    operands; ``bm_block`` / ``bm_weight`` stay HBM-resident and are
    windowed in by DMA, ``window_rows`` rows of 128 entries per slot.
    Returns ``(ub f32[B, nbp], mask i32[B, nbp])``.
    """
    B, lq = base.shape
    row = pl.BlockSpec((None, 1, nbp), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # CSR block ids: DMA only
            pl.BlockSpec(memory_space=pl.ANY),  # CSR block maxima: DMA only
        ],
        out_specs=[row, row],
        scratch_shapes=[
            pltpu.VMEM((2, window_rows, LANES), jnp.int32),  # id windows
            pltpu.VMEM((2, window_rows, LANES), jnp.float32),  # max windows
            pltpu.SemaphoreType.DMA((2, 2)),  # (slot, block/weight)
        ],
    )
    ub, mask = pl.pallas_call(
        lambda *refs: _prune_csr_kernel_batched(*refs, lq=lq),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, nbp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, nbp), jnp.int32),
        ],
        interpret=interpret,
    )(
        base.reshape(-1), cnt.reshape(-1), q_weights.reshape(-1),
        theta.reshape(-1), bm_block, bm_weight,
    )
    return ub[:, 0, :], mask[:, 0, :]
