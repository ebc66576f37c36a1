"""Pallas TPU kernel: fused DAAT phase-2 chunk step (select+score+merge).

One while_loop trip of the batched Block-Max engine used to be THREE kernel
launches (``block_topk_batched`` selection, ``sparse_score_batched`` scoring,
then the jnp ``merge_topk``), with the ``[B, budget, bs]`` score tensor and
the remaining-ub selection finalists round-tripping HBM between them — traffic
a skipping-hostile (wacky-weight) workload multiplies by its trip count. This
kernel fuses the whole chunk step into one batch-gridded pass:

  * remaining-ub top-``budget`` block selection (repeated max with a
    lowest-index argmax over the per-query ub row, processed blocks masked to
    ``-inf``; the picks land in SMEM, where they address the DMAs);
  * live gating (``ub_c > theta`` — only these can change the top-k);
  * sparse scoring of the selected doc blocks (the ``sparse_score``
    match-and-accumulate, :func:`repro.kernels.sparse_score.kernel.score_doc_rows`);
  * candidate merge into the per-query top-k pool + the new threshold.

Chunk state — the pool scores/ids, theta, the candidate tile, and the
processed-bitmap row — lives in VMEM for the whole launch; only the updated
state (pool, theta, processed) is written back. The selected blocks'
doc-major rows are pulled from the HBM-resident store with double-buffered
``make_async_copy`` DMAs: while block ``j`` is being scored, block ``j+1``'s
``[bs, Tmax]`` term/weight rows are already in flight.

Parity contract (the engine's ``fused_chunk`` flag relies on it): the kernel
evaluates the identical expressions, in the same order, as the jnp
while-body in ``repro.core.daat`` — selection order is ``lax.top_k``'s (value
descending, ties to the lowest block id; the ``-inf`` pad lanes the ops
wrapper appends sit at the highest ids, so they never displace a real block
while ``budget <= n_blocks``), the merge is ``lax.top_k`` over the pool
followed by the candidates in block-selection order, exactly like
``merge_topk``, and non-live / padded-doc candidates mask to ``-inf`` before
the merge. Scores use the same fixed-order sums as the jnp scorer, so doc
ids, scores, theta, and the processed bitmap are bit-identical to it.

Trips per launch
----------------
A skipping-collapsed (wacky-weight) query pays one launch plus a pool/theta/
processed HBM round-trip per trip — multiplied by exactly the trip counts the
paper shows explode. The kernel therefore runs up to ``trips`` trip bodies
inside ONE launch: the per-query state initializes the output blocks once,
revolves in VMEM across trips, and crosses HBM once per *launch*. A
scalar-prefetched per-row trip budget (the engine passes ``min(max_chunks -
chunks, trips_per_launch)``, 0 for inactive rows) plus the in-kernel early
exit — each trip body runs under ``pl.when(t < budget AND max remaining ub >
theta)``, so a row that goes rank-safe mid-launch skips the remaining trips'
DMAs and compute entirely. Because each row's trip sequence never depends on
other rows, running T trip bodies in-kernel is bit-identical to T one-trip
launches; the ``trips_done`` output row lets the engine advance its
per-query chunk counts. A skipped trip leaves the state exactly where an
executed one would (no block above theta means no live candidate), so the
one-trip launch is simply ``trips=1``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_score.kernel import score_doc_rows


def _merge(pool_s, pool_i, cand, sel_blk, *, bs: int):
    """``lax.top_k`` over ``concat(pool, cand.flat)`` with the ids it implies.

    ``pool_s/pool_i [1, k]`` precede ``cand [budget, bs]`` (row-major, doc id
    ``sel_blk[j] * bs + lane``) in position order, so among equal scores the
    pool wins, then the earlier-selected block, then the lower doc.
    Returns ``(scores [1, k], ids [1, k])``.
    """
    k = pool_s.shape[1]
    budget = cand.shape[0]
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    cpos = (
        jax.lax.broadcasted_iota(jnp.int32, (budget, bs), 0) * bs
        + jax.lax.broadcasted_iota(jnp.int32, (budget, bs), 1)
    )
    n_cand = budget * bs

    def one(i, carry):
        tp, tc, out_s, out_i = carry
        ap, ac = tp == 0, tc == 0
        m = jnp.maximum(
            jnp.max(jnp.where(ap, pool_s, -jnp.inf)), jnp.max(jnp.where(ac, cand, -jnp.inf))
        )
        pp = jnp.min(jnp.where(ap & (pool_s == m), kpos, k))
        pc = jnp.min(jnp.where(ac & (cand == m), cpos, n_cand))
        from_pool = pp < k
        blk = sel_blk[jnp.minimum(pc // bs, budget - 1)]
        pid = jnp.where(
            from_pool, jnp.sum(jnp.where(kpos == pp, pool_i, 0)), blk * bs + pc % bs
        )
        tp = jnp.where(from_pool & (kpos == pp), 1, tp)
        tc = jnp.where(jnp.logical_not(from_pool) & (cpos == pc), 1, tc)
        out_s = jnp.where(kpos == i, m, out_s)
        out_i = jnp.where(kpos == i, pid, out_i)
        return tp, tc, out_s, out_i

    init = (
        jnp.zeros((1, k), jnp.int32),
        jnp.zeros((budget, bs), jnp.int32),
        jnp.full((1, k), -jnp.inf, jnp.float32),
        jnp.zeros((1, k), jnp.int32),
    )
    _, _, out_s, out_i = jax.lax.fori_loop(0, k, one, init)
    return out_s, out_i


def _trip_body(
    q0,  # first flat SMEM slot of this query's terms / weights
    ub,  # f32[1, NBp] (value, not ref — constant across trips)
    qt_ref,
    qw_ref,
    theta_ref,  # SMEM f32[1] running threshold
    dt_hbm,
    dw_hbm,
    lv_hbm,  # i32[nb, 1, bs] tombstone rows in HBM, or None
    out_s_ref,
    out_i_ref,
    out_theta_ref,
    out_proc_ref,
    dt_buf,
    dw_buf,
    lv_buf,  # VMEM (2, 1, bs) live-row double buffer, or None
    cand_ref,
    prod_ref,
    sel_blk,  # SMEM i32[budget]
    sel_ub,  # SMEM f32[budget]
    sems,
    *,
    lq: int,
    budget: int,
    bs: int,
    n_live: int,
):
    """ONE select+score+merge trip; reads and writes the state in the out refs.

    When ``lv_hbm`` is present, each selected block's ``[bs]`` tombstone row
    rides the same double-buffered DMA pipeline as its doc-major rows (third
    semaphore lane) and masks dead docs' scores to ``-inf`` before the merge
    — the in-kernel image of the jnp body's ``live_mask`` gather.
    """
    proc = out_proc_ref[...]
    theta = theta_ref[0]
    nbp = proc.shape[1]

    # ---- select: remaining-ub top-budget, lax.top_k order, into SMEM ----
    rub = jnp.where(proc != 0, -jnp.inf, ub)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, nbp), 1)
    taken = jnp.zeros((1, nbp), jnp.int32)
    for j in range(budget):
        avail = taken == 0
        m = jnp.max(jnp.where(avail, rub, -jnp.inf))
        pos = jnp.min(jnp.where(avail & (rub == m), lane, nbp))
        taken = jnp.where(lane == pos, 1, taken)
        sel_blk[j] = pos
        sel_ub[j] = m

    # ---- score: selected doc blocks, double-buffered HBM prefetch ----
    def doc_dma(slot, j):
        row0 = pl.multiple_of(sel_blk[j] * bs, bs)
        copies = (
            pltpu.make_async_copy(
                dt_hbm.at[pl.ds(row0, bs), :], dt_buf.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                dw_hbm.at[pl.ds(row0, bs), :], dw_buf.at[slot], sems.at[slot, 1]
            ),
        )
        if lv_hbm is not None:
            copies += (
                pltpu.make_async_copy(lv_hbm.at[sel_blk[j]], lv_buf.at[slot], sems.at[slot, 2]),
            )
        return copies

    for c in doc_dma(0, 0):  # warm up the pipeline
        c.start()
    for j in range(budget):
        slot = j % 2
        if j + 1 < budget:  # prefetch the next block while scoring this one
            for c in doc_dma((j + 1) % 2, j + 1):
                c.start()
        for c in doc_dma(slot, j):
            c.wait()
        s = score_doc_rows(dt_buf.at[slot], dw_buf.at[slot], qt_ref, qw_ref, q0, lq, prod_ref)
        docs = sel_blk[j] * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(docs < n_live, s, -jnp.inf)  # padded docs never rank
        if lv_hbm is not None:
            s = jnp.where(lv_buf[slot] != 0, s, -jnp.inf)  # tombstoned docs
        live = sel_ub[j] > theta  # only these can change the top-k
        cand_ref[pl.ds(j, 1), :] = jnp.where(live, s, -jnp.inf)

    # ---- merge: only a candidate above the pool's k-th score (the pool is
    # sorted descending; ties keep the pool entry) can change the pool ----
    @pl.when(jnp.max(cand_ref[...]) > jnp.min(out_s_ref[...]))
    def _merge_pool():
        s, i = _merge(out_s_ref[...], out_i_ref[...], cand_ref[...], sel_blk, bs=bs)
        out_s_ref[...] = s
        out_i_ref[...] = i

    new_theta = jnp.min(out_s_ref[...])  # the merged pool's k-th score
    theta_ref[0] = new_theta
    out_theta_ref[...] = jnp.full((1, 1), new_theta, jnp.float32)

    # ---- processed |= live-selected blocks (picks are distinct) ----
    for j in range(budget):
        hit = (lane == sel_blk[j]) & (sel_ub[j] > theta)
        proc = jnp.where(hit, 1, proc)
    out_proc_ref[...] = proc


def _chunk_step_kernel_batched(
    qt_ref,  # SMEM i32[B * Lq]
    qw_ref,  # SMEM f32[B * Lq] (pad slots zeroed)
    theta_in_ref,  # SMEM f32[B]
    trips_ref,  # SMEM i32[B] — per-row trip budget
    ub_ref,
    proc_ref,
    pool_s_ref,
    pool_i_ref,
    dt_hbm,
    dw_hbm,
    *rest,
    trips: int,
    lq: int,
    budget: int,
    bs: int,
    n_live: int,
    has_live: bool = False,
):
    """Up to ``trips`` trip bodies in ONE launch; state revolves in VMEM.

    The per-query state (pool, theta, processed) initializes the output
    blocks once and every trip reads/writes them in place — the output tile
    is VMEM-resident for the whole grid cell, so nothing crosses HBM between
    trips. Each trip runs under ``pl.when``: a row past its scalar-prefetched
    budget, or already rank-safe (``max remaining ub <= theta``), skips the
    trip's DMAs and compute entirely — the in-kernel early exit.
    """
    if has_live:
        (lv_hbm, out_s_ref, out_i_ref, out_theta_ref, out_proc_ref, out_trips_ref,
         dt_buf, dw_buf, cand_ref, prod_ref, lv_buf, theta_ref, sel_blk, sel_ub,
         sems) = rest
    else:
        (out_s_ref, out_i_ref, out_theta_ref, out_proc_ref, out_trips_ref,
         dt_buf, dw_buf, cand_ref, prod_ref, theta_ref, sel_blk, sel_ub,
         sems) = rest
        lv_hbm = lv_buf = None
    b = pl.program_id(0)
    out_s_ref[...] = pool_s_ref[...]
    out_i_ref[...] = pool_i_ref[...]
    out_proc_ref[...] = proc_ref[...]
    theta_ref[0] = theta_in_ref[b]
    out_theta_ref[...] = jnp.full((1, 1), theta_in_ref[b], jnp.float32)
    out_trips_ref[...] = jnp.zeros((1, 1), jnp.int32)
    ub = ub_ref[...]

    for t in range(trips):
        remaining = jnp.where(out_proc_ref[...] != 0, -jnp.inf, ub)
        active = (t < trips_ref[b]) & (jnp.max(remaining) > theta_ref[0])

        @pl.when(active)
        def _one_trip():
            _trip_body(
                b * lq, ub, qt_ref, qw_ref, theta_ref, dt_hbm, dw_hbm, lv_hbm,
                out_s_ref, out_i_ref, out_theta_ref, out_proc_ref,
                dt_buf, dw_buf, lv_buf, cand_ref, prod_ref, sel_blk, sel_ub, sems,
                lq=lq, budget=budget, bs=bs, n_live=n_live,
            )
            out_trips_ref[...] = out_trips_ref[...] + 1


def chunk_step_batched_kernel(
    ub: jax.Array,  # f32[B, NBp] (pad lanes = -inf)
    processed: jax.Array,  # i32[B, NBp] (pad lanes = 1)
    pool_s: jax.Array,  # f32[B, k]
    pool_i: jax.Array,  # i32[B, k]
    theta: jax.Array,  # f32[B]
    q_terms: jax.Array,  # i32[B, Lq]
    q_weights: jax.Array,  # f32[B, Lq] (pad slots already zeroed)
    doc_terms: jax.Array,  # i32[n_docs_pad, Tmax] — stays in HBM, DMA'd
    doc_weights: jax.Array,  # f32[n_docs_pad, Tmax] — stays in HBM, DMA'd
    trips_left: jax.Array,  # i32[B] — per-row trip budget (scalar-prefetched)
    *,
    trips: int,
    budget: int,
    bs: int,
    n_live: int,
    live: jax.Array | None = None,  # i32[nb, 1, bs] tombstone rows — HBM, DMA'd
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Up to ``trips`` fused chunk steps per query in ONE launch: grid over B.

    Returns ``(pool_s [B, k], pool_i [B, k], theta [B], processed [B, NBp],
    trips_done [B])`` — the state crosses the HBM boundary once per launch;
    ``trips_done[b]`` counts how many trip bodies actually ran for row ``b``.
    The ``[budget, bs]`` candidate tile and the selection never leave the
    chip's VMEM/SMEM. ``live`` (optional) is the lifecycle tombstone bitmap
    as block rows; like the doc stores it stays in HBM and only the selected
    blocks' rows are DMA'd.
    """
    B, nbp = ub.shape
    k = pool_s.shape[1]
    lq = q_terms.shape[1]
    tmax = doc_terms.shape[1]

    def row(n):  # one query's row of an [B, 1, n] state array
        return pl.BlockSpec((None, 1, n), lambda b, *_: (b, 0, 0))

    in_specs = [
        row(nbp), row(nbp), row(k), row(k),
        pl.BlockSpec(memory_space=pl.ANY),  # doc-major store: DMA only
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    args = [
        q_terms.astype(jnp.int32).reshape(-1),
        q_weights.astype(jnp.float32).reshape(-1),
        theta.astype(jnp.float32),
        trips_left.astype(jnp.int32),
        ub.reshape(B, 1, nbp),
        processed.reshape(B, 1, nbp),
        pool_s.reshape(B, 1, k),
        pool_i.reshape(B, 1, k),
        doc_terms,
        doc_weights,
    ]
    scratch = [
        pltpu.VMEM((2, bs, tmax), jnp.int32),  # double-buffered doc terms
        pltpu.VMEM((2, bs, tmax), jnp.float32),  # double-buffered doc weights
        pltpu.VMEM((budget, bs), jnp.float32),  # candidate score tile
        pltpu.VMEM((bs, tmax), jnp.float32),  # qv * w products
    ]
    n_lanes = 2
    if live is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # live rows: DMA only
        args.append(live.astype(jnp.int32))
        scratch.append(pltpu.VMEM((2, 1, bs), jnp.int32))  # live-row buffer
        n_lanes = 3  # (slot, terms/weights/live)
    scratch += [
        pltpu.SMEM((1,), jnp.float32),  # running theta
        pltpu.SMEM((budget,), jnp.int32),  # selected block ids
        pltpu.SMEM((budget,), jnp.float32),  # their upper bounds
        pltpu.SemaphoreType.DMA((2, n_lanes)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[row(k), row(k), row(1), row(nbp), row(1)],
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_step_kernel_batched,
            trips=trips, lq=lq, budget=budget, bs=bs, n_live=n_live,
            has_live=live is not None,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, nbp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    ps, pi, th, pr, td = (o[:, 0] for o in out)
    return ps, pi, th[:, 0], pr, td[:, 0]
