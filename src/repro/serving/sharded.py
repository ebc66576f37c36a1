"""Doc-sharded SAAT retrieval: the paper's serve step at pod scale.

Documents are partitioned into ``n_shards`` equal ranges over the ``model``
mesh axis; each chip owns the full impact index *of its shard* and runs the
identical rho-budgeted SAAT scan. Only the k finalists cross the ICI
(``k * 8`` bytes per shard vs ``n_docs * 4`` for accumulator exchange).
Queries batch over the data axes.

Why this is the right scale-out for the paper's technique:
  * per-chip work is rho_per_shard postings — *identical by construction*
    across chips, so corpus skew cannot create stragglers (the paper's
    predictable-latency claim, promoted to a cluster property);
  * a lost pod/chip shrinks the corpus coverage but never blocks the merge
    (elastic serving; repro.distributed.elastic).

``stack_indexes`` packs per-shard indexes into one pytree with a leading
shard axis (sharded over ``model``); ``abstract_stacked_index`` builds the
same as ShapeDtypeStructs for the multi-pod dry-run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.daat import daat_search_batched
from repro.core.impact_index import ImpactIndex, META_FIELDS as _META_FIELDS, build_impact_index
from repro.core.quantization import QuantConfig
from repro.core.saat import saat_search
from repro.core.topk import NEG_INF, canonical_topk_merge, merge_topk
from repro.distributed.sharding import mesh_axes


# --------------------------------------------------------------------------
# shard construction (host side)
# --------------------------------------------------------------------------


def shard_corpus(
    doc_idx: np.ndarray,
    term_idx: np.ndarray,
    weights: np.ndarray,
    n_docs: int,
    n_terms: int,
    n_shards: int,
    **build_kwargs,
) -> tuple[list[ImpactIndex], int]:
    """Split a COO corpus into per-shard impact indexes (equal doc ranges).

    All shards quantize against the GLOBAL max weight so their impact grids
    (and therefore merged scores) are identical to a global index's. Pass an
    explicit ``quant_max_weight`` to pin a different grid — re-sharding a
    compacted :class:`~repro.core.index_handle.IndexHandle` must reuse the
    handle's pinned grid, not re-derive one from the folded (mid-step)
    weights' max.
    """
    docs_per_shard = -(-n_docs // n_shards)
    global_max = build_kwargs.pop(
        "quant_max_weight", float(np.max(weights)) if len(weights) else 1.0
    )
    shards = []
    for s in range(n_shards):
        lo, hi = s * docs_per_shard, min((s + 1) * docs_per_shard, n_docs)
        m = (doc_idx >= lo) & (doc_idx < hi)
        shards.append(
            build_impact_index(
                doc_idx[m] - lo, term_idx[m], weights[m], docs_per_shard, n_terms,
                quant_max_weight=global_max, **build_kwargs
            )
        )
    return shards, docs_per_shard


def _pad_cat(arrs: Sequence[np.ndarray], fill) -> np.ndarray:
    n = max(a.shape[0] for a in arrs)
    out = np.full((len(arrs), n) + arrs[0].shape[1:], fill, dtype=arrs[0].dtype)
    for i, a in enumerate(arrs):
        out[i, : a.shape[0]] = a
    return out


def shard_live_stack(
    live_full: np.ndarray,
    *,
    n_shards: int,
    docs_per_shard: int,
    n_docs_pad: int,
) -> np.ndarray:
    """Slice a global live bitmap into the per-shard tombstone stack.

    ``live_full`` is the corpus-wide i32/bool bitmap over global doc ids
    (e.g. ``IndexHandle.live_mask_full()``); the result is
    ``i32[n_shards, n_docs_pad]`` — shard ``s`` holds gids
    ``[s * docs_per_shard, (s+1) * docs_per_shard)``, trailing pad slots
    (block padding, and the short final shard's tail) forced dead so a pad
    doc can never out-compete a real one inside the engines' masked scans.
    ``n_docs_pad`` is the per-shard DOC pad — the engines' accumulator
    length, i.e. ``index_stack.doc_n_terms.shape[1]`` of the stacked index
    (NOT the posting-store width).
    Partition it over the same axes as the index stack and hand it to a
    ``live_masked=True`` serve step.
    """
    if n_docs_pad < docs_per_shard:
        raise ValueError(
            f"n_docs_pad={n_docs_pad} smaller than docs_per_shard={docs_per_shard}"
        )
    live_full = np.asarray(live_full).astype(np.int32).ravel()
    out = np.zeros((n_shards, n_docs_pad), np.int32)
    for s in range(n_shards):
        lo = s * docs_per_shard
        hi = min(lo + docs_per_shard, live_full.shape[0])
        if hi > lo:
            out[s, : hi - lo] = live_full[lo:hi]
    return out


def stack_indexes(shards: list[ImpactIndex]) -> ImpactIndex:
    """Stack per-shard indexes on a new leading axis (ragged -> padded).

    Static metadata comes from shard 0 (shards are built with identical
    corpus-level constants); per-term CSR tables are padded per shard.
    """
    fields = [f.name for f in dataclasses.fields(ImpactIndex)]
    data_fields = [f for f in fields if f not in _META_FIELDS]
    stacked = {}
    for f in data_fields:
        if f in ("doc_terms", "doc_weights"):
            continue  # ragged in BOTH dims; re-padded below
        arrs = [np.asarray(jax.device_get(getattr(s, f))) for s in shards]
        fill = 0
        stacked[f] = jnp.asarray(_pad_cat(arrs, fill))
    # shard-invariant meta comes from shard 0; size-like bounds take the max
    _RAGGED_META = ("max_doc_terms", "max_segs", "max_bm")
    meta = {k: getattr(shards[0], k) for k in _META_FIELDS if k not in _RAGGED_META}
    for k in _RAGGED_META:
        meta[k] = max(getattr(s, k) for s in shards)
    # re-pad doc-major stores to a common Tmax
    tmax = meta["max_doc_terms"]
    dts = [np.asarray(jax.device_get(s.doc_terms)) for s in shards]
    dws = [np.asarray(jax.device_get(s.doc_weights)) for s in shards]
    nd = max(a.shape[0] for a in dts)
    dt = np.full((len(shards), nd, tmax), shards[0].n_terms, dtype=np.int32)
    dw = np.zeros((len(shards), nd, tmax), dtype=np.float32)
    for i, (a, b) in enumerate(zip(dts, dws)):
        dt[i, : a.shape[0], : a.shape[1]] = a
        dw[i, : b.shape[0], : b.shape[1]] = b
    stacked["doc_terms"] = jnp.asarray(dt)
    stacked["doc_weights"] = jnp.asarray(dw)
    return ImpactIndex(**stacked, **meta)


def place_index_stack(index_stack: ImpactIndex, mesh: Mesh) -> ImpactIndex:
    """Put each shard of a stacked index on its own device of ``mesh``.

    Every array's leading shard axis is partitioned over the ``model`` axis
    (replicated over the data axes) with a ``NamedSharding`` — the layout
    the serve steps' ``P("model")`` in-specs expect, so a call moves no index
    bytes between devices.
    """
    sharding = NamedSharding(mesh, P("model"))
    data = {k: jax.device_put(v, sharding) for k, v in _index_data_dict(index_stack).items()}
    meta = {k: getattr(index_stack, k) for k in _META_FIELDS}
    return ImpactIndex(**data, **meta)


def abstract_stacked_index(
    *,
    n_shards: int,
    docs_per_shard: int,
    n_terms: int,
    postings_per_shard: int,
    segments_per_shard: int,
    bm_cells_per_shard: int,
    max_doc_terms: int,
    block_size: int = 128,
) -> ImpactIndex:
    """ShapeDtypeStruct stacked index for the dry-run (no allocation)."""
    S = n_shards
    f32 = jnp.float32
    i32 = jnp.int32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    n_docs_pad = -(-docs_per_shard // block_size) * block_size
    n_blocks = n_docs_pad // block_size
    return ImpactIndex(
        doc_ids=sds((S, postings_per_shard), i32),
        seg_term=sds((S, segments_per_shard), i32),
        seg_weight=sds((S, segments_per_shard), f32),
        seg_start=sds((S, segments_per_shard), i32),
        seg_len=sds((S, segments_per_shard), i32),
        term_seg_start=sds((S, n_terms + 1), i32),
        term_seg_count=sds((S, n_terms + 1), i32),
        term_post_count=sds((S, n_terms + 1), i32),
        term_max_weight=sds((S, n_terms + 1), f32),
        bm_block=sds((S, bm_cells_per_shard), i32),
        bm_weight=sds((S, bm_cells_per_shard), f32),
        term_bm_start=sds((S, n_terms + 1), i32),
        term_bm_count=sds((S, n_terms + 1), i32),
        doc_terms=sds((S, n_docs_pad, max_doc_terms), i32),
        doc_weights=sds((S, n_docs_pad, max_doc_terms), f32),
        doc_n_terms=sds((S, n_docs_pad), i32),
        doc_weight_sum=sds((S, n_docs_pad), f32),
        n_docs=docs_per_shard,
        n_terms=n_terms,
        n_blocks=n_blocks,
        block_size=block_size,
        max_doc_terms=max_doc_terms,
        scale=1.0,
        bits=8,
    )


# --------------------------------------------------------------------------
# the sharded serve step
# --------------------------------------------------------------------------


def _validate_engine_cfg(
    engine: str,
    max_bm_per_term: int,
    daat_use_kernels: bool,
    daat_fused_chunk: bool,
    daat_trips_per_launch: int,
):
    if engine not in ("saat", "daat"):
        raise ValueError(f"unknown engine {engine!r}")
    if engine == "daat" and max_bm_per_term <= 0:
        raise ValueError("engine='daat' needs the static max_bm_per_term bound")
    if daat_fused_chunk and not daat_use_kernels:
        raise ValueError(
            "daat_fused_chunk fuses the kernel-mode chunk step; pass "
            "daat_use_kernels=True"
        )
    if daat_trips_per_launch < 1:
        raise ValueError(
            f"daat_trips_per_launch={daat_trips_per_launch} must be >= 1"
        )
    if daat_trips_per_launch > 1 and not daat_fused_chunk:
        raise ValueError(
            "daat_trips_per_launch > 1 batches trips inside the fused "
            "chunk_step kernel; pass daat_fused_chunk=True (and "
            "daat_use_kernels=True)"
        )


def _scan_local_shards(
    idx_data: dict, qt, qw, *, shard_ord0, st: dict, meta_cell: dict, live=None
):
    """Search every doc shard resident on this rank; merge their k-pools.

    Runs inside ``shard_map``. ``shard_ord0`` is this rank's flat position in
    the shard partition order (the leading shard axis is laid out
    major-to-minor along the partition spec, so consecutive flat ranks own
    consecutive shard ranges); each local shard ``j`` is global shard
    ``shard_ord0 * n_local + j``. Pad documents (block-padding slots, and —
    on a short final shard — ids past the corpus end) are demoted to
    ``(NEG_INF, INT32_MAX)`` *before* globalization so they can never alias
    a real doc id in a later shard's range. ``live`` is the optional
    per-shard tombstone stack ``i32[n_local, n_docs_pad]`` (same leading
    order as ``idx_data``'s shard rows): shard ``j``'s row
    rides the engines' ``live_mask`` paths, so deleted docs score ``-inf``
    inside the budgeted scan itself (never reaching the pool) rather than
    being filtered after the fact. Returns the rank's merged ``(scores,
    gids)`` candidate pool, ``[B, k]``.
    """
    n_local = jax.tree.leaves(idx_data)[0].shape[0]
    docs_per_shard = st["docs_per_shard"]
    pool_s = pool_i = None
    for j in range(n_local):
        local = jax.tree.map(lambda x, _j=j: x[_j], idx_data)
        index = ImpactIndex(
            **local, **_static_meta_from(local, docs_per_shard, meta_cell)
        )
        lv = live[j] if live is not None else None
        if st["engine"] == "daat":
            res = daat_search_batched(
                index,
                qt,
                qw,
                k=st["k"],
                est_blocks=st["daat_est_blocks"],
                block_budget=st["daat_block_budget"],
                max_bm_per_term=st["max_bm_per_term"],
                exact=st["daat_exact"],
                use_kernels=st["daat_use_kernels"],
                fused_chunk=st["daat_fused_chunk"],
                trips_per_launch=st["daat_trips_per_launch"],
                live_mask=lv,
            )
        else:
            res = saat_search(
                index,
                qt,
                qw,
                k=st["k"],
                rho=st["rho_per_shard"],
                max_segs_per_term=st["max_segs_per_term"],
                scatter_impl=st["scatter_impl"],
                fused_topk=st["fused_topk"],
                live_mask=lv,
            )
        shard_ord = shard_ord0 * n_local + j
        if st["n_docs_total"] is None:
            n_live = jnp.int32(docs_per_shard)
        else:
            n_live = jnp.clip(
                st["n_docs_total"] - shard_ord * docs_per_shard, 0, docs_per_shard
            ).astype(jnp.int32)
        pad = res.doc_ids >= n_live
        scores = jnp.where(pad, NEG_INF, res.scores)
        gids = jnp.where(
            pad,
            jnp.iinfo(jnp.int32).max,
            res.doc_ids + shard_ord * docs_per_shard,
        )
        if pool_s is None:
            pool_s, pool_i = scores, gids
        else:
            pool_s, pool_i = merge_topk(pool_s, pool_i, scores, gids, st["k"])
    return pool_s, pool_i


def make_sharded_serve_step(
    mesh: Mesh,
    *,
    k: int,
    rho_per_shard: int,
    max_segs_per_term: int,
    docs_per_shard: int,
    scatter_impl: str = "sort",
    fused_topk: bool = False,
    engine: str = "saat",
    daat_est_blocks: int = 8,
    daat_block_budget: int = 16,
    max_bm_per_term: int = 0,
    daat_exact: bool = True,
    daat_use_kernels: bool = False,
    daat_fused_chunk: bool = False,
    daat_trips_per_launch: int = 1,
    n_docs_total: Optional[int] = None,
    live_masked: bool = False,
):
    """Builds ``serve(index_stack, q_terms, q_weights) -> (scores, ids)``.

    Inside ``shard_map``: every model-rank runs the identical rho-budgeted
    SAAT over its local doc shard, globalizes ids by its shard offset, then
    merges finalists with a k-sized all-gather over ``model``. Data axes
    carry the query batch; each rank's local batch executes the natively
    batched engine (one plan sort / gather / scatter for the whole block),
    so the per-chip instruction stream stays identical across ranks AND
    independent of batch composition.

    ``engine="daat"`` swaps in the natively batched Block-Max engine per
    shard (``rho_per_shard`` is then unused; pass the STATIC
    ``max_bm_per_term`` bound from the stacked index's build-time metadata).
    Per-chip work becomes data-dependent — each rank loops until its own
    local batch is rank-safe — so corpus skew CAN create stragglers, which
    is exactly the contrast with SAAT the paper draws.

    ``fused_topk=True`` makes every rank's SAAT scan emit only its
    ``[B, blocks * k]`` candidate pool from VMEM (the per-shard accumulator
    never reaches HBM) before the cross-shard k-merge; ``daat_use_kernels``
    routes each rank's DAAT phase 2 through the batched Pallas kernels, and
    ``daat_fused_chunk`` collapses each rank's per-trip select+score+merge
    into the single VMEM-resident ``chunk_step`` kernel (per-trip HBM traffic
    on every rank drops to the candidate/state output only).

    ``n_docs_total`` (the UNSHARDED corpus size) bounds the live doc range of
    every shard: block-padding slots and — on a short final shard — doc ids
    past the corpus end are masked to ``(NEG_INF, INT32_MAX)`` before their
    local ids are globalized, so a pad doc can never alias a real document in
    a later shard's id range. Omitting it still masks the per-shard block
    padding (ids ``>= docs_per_shard``) but assumes every shard is full.

    ``live_masked=True`` builds the *lifecycle* variant of the step: ``serve``
    then requires a ``live_stack`` — the per-shard tombstone bitmap
    ``i32[n_shards, n_docs_pad]`` (see :func:`shard_live_stack`), laid out in
    the SAME leading shard order as the index stack and placed on the mesh
    the same way — and every rank threads its shard's row through the
    engines' ``live_mask`` paths. The flag is a
    constructor static (mirrored in ``serve.statics``) because masked and
    unmasked dispatches are genuinely different traced programs: one serve
    step is always exactly one program per batch shape, which is the
    invariant the hot-path lint keys on.
    """
    _validate_engine_cfg(
        engine, max_bm_per_term, daat_use_kernels, daat_fused_chunk,
        daat_trips_per_launch,
    )
    axes = mesh_axes(mesh)
    dp = axes.data if len(axes.data) > 1 else axes.data[0]
    idx_specs = jax.tree.map(lambda _: P("model"), _index_data_template())
    if live_masked:
        # The live stack rides with the index stack: the idx specs replicate
        # the stacked arrays onto every rank (each rank scans all local rows
        # and globalizes by its own shard_ord), so the live rows must be
        # replicated too — a partitioned spec would desynchronize live[j]
        # from idx_data[...][j].
        in_specs = (idx_specs, P(), P(dp, None), P(dp, None))
    else:
        in_specs = (idx_specs, P(dp, None), P(dp, None))
    out_specs = (P(dp, None), P(dp, None))

    # Real static metadata of the caller's index_stack (block_size, quant
    # scale/bits, seg/bm bounds). `serve()` fills it before tracing so every
    # per-shard reconstruction inside the shard_map carries the true build
    # constants instead of hardcoded defaults; a direct `sm(...)` call on a
    # bare data dict falls back to the historical defaults.
    meta_cell: dict = {}

    # Static surface of this serve step, exposed for repro.analysis.hot_path:
    # the lint traces `serve` at each (bucket, B) shape and keys executables
    # on exactly this dict plus the shape. Keep it the full closure config —
    # a knob missing here is a knob the one-executable-per-key check can't
    # see. The same dict feeds `_scan_local_shards` under the trace.
    statics = dict(
        engine=engine, k=k, rho_per_shard=rho_per_shard,
        max_segs_per_term=max_segs_per_term, docs_per_shard=docs_per_shard,
        scatter_impl=scatter_impl, fused_topk=fused_topk,
        daat_est_blocks=daat_est_blocks, daat_block_budget=daat_block_budget,
        max_bm_per_term=max_bm_per_term, daat_exact=daat_exact,
        daat_use_kernels=daat_use_kernels, daat_fused_chunk=daat_fused_chunk,
        daat_trips_per_launch=daat_trips_per_launch, n_docs_total=n_docs_total,
        live_masked=live_masked,
    )

    def body(idx_data: dict, qt, qw):
        # the block may hold SEVERAL shards when n_shards > model-axis size
        # (multiple doc ranges per chip): search each, merge locally, then
        # k-merge across chips
        rank = jax.lax.axis_index("model").astype(jnp.int32)
        pool_s, pool_i = _scan_local_shards(
            idx_data, qt, qw, shard_ord0=rank, st=statics, meta_cell=meta_cell
        )
        return canonical_topk_merge(pool_s, pool_i, k, "model")

    def body_live(idx_data: dict, live, qt, qw):
        rank = jax.lax.axis_index("model").astype(jnp.int32)
        pool_s, pool_i = _scan_local_shards(
            idx_data, qt, qw, shard_ord0=rank, st=statics, meta_cell=meta_cell,
            live=live,
        )
        return canonical_topk_merge(pool_s, pool_i, k, "model")

    sm = jax.shard_map(
        body_live if live_masked else body,
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )

    def serve(index_stack: ImpactIndex, q_terms, q_weights, live_stack=None):
        meta_cell.clear()
        meta_cell.update(
            block_size=index_stack.block_size,
            scale=index_stack.scale,
            bits=index_stack.bits,
            max_segs=index_stack.max_segs,
            max_bm=index_stack.max_bm,
        )
        data = _index_data_dict(index_stack)
        if live_masked:
            if live_stack is None:
                raise ValueError(
                    "this serve step was built live_masked=True; pass the "
                    "per-shard live_stack (see shard_live_stack)"
                )
            return sm(data, jnp.asarray(live_stack, jnp.int32), q_terms, q_weights)
        if live_stack is not None:
            raise ValueError(
                "live_stack passed to a serve step built without "
                "live_masked=True; rebuild the step with live_masked=True"
            )
        return sm(data, q_terms, q_weights)

    serve.statics = statics
    return serve, in_specs, out_specs


def make_pod_serve_step(
    mesh: Mesh,
    *,
    k: int,
    rho_per_shard: int,
    max_segs_per_term: int,
    docs_per_shard: int,
    scatter_impl: str = "sort",
    fused_topk: bool = False,
    engine: str = "saat",
    daat_est_blocks: int = 8,
    daat_block_budget: int = 16,
    max_bm_per_term: int = 0,
    daat_exact: bool = True,
    daat_use_kernels: bool = False,
    daat_fused_chunk: bool = False,
    daat_trips_per_launch: int = 1,
    n_docs_total: Optional[int] = None,
    live_masked: bool = False,
):
    """Multi-host pod serve: every host's query block, every rank's shard.

    The mesh carries a ``"pod"`` axis (one position per ingestion host) in
    the data group alongside the ``"model"`` axis; the stacked index's
    leading shard axis is partitioned over *all* mesh axes pod-major, so the
    whole pod is one document-sharded replica set. Each host contributes its
    own ``B_local`` admission block (query in_spec shards the batch over the
    data group); inside the step every rank

      1. all-gathers the query blocks over the data group — the global
         ``[hosts * B_local, Lq]`` batch, identical on every rank, so every
         query is answered by every shard;
      2. runs the engine over its local shard(s) via the shared
         ``_scan_local_shards`` (identical rho-budgeted work per rank for
         SAAT — the paper's no-straggler property, now pod-wide);
      3. joins the rank-safe cross-host k-merge: per-rank ``[B_glob, k]``
         candidate pools are gathered over ``("pod", ..., "model")`` at once
         and re-selected with the id-canonical :func:`canonical_topk_merge`
         (``tiled_topk`` over ``ranks * k`` candidates — ties and pad
         sentinels resolve identically to the unsharded oracle no matter the
         host/shard layout);
      4. hands back its own host's ``B_local`` rows, so results land on the
         host that admitted the queries.

    Returns ``(serve, in_specs, out_specs)`` like
    :func:`make_sharded_serve_step`; the caller's query batch is the
    concatenation of all hosts' blocks (``hosts * B_local`` rows, pod-major)
    — :class:`repro.serving.pod.PodServer` assembles it from one host's
    admission queue plus inert sentinel rows for the absent hosts.
    """
    _validate_engine_cfg(
        engine, max_bm_per_term, daat_use_kernels, daat_fused_chunk,
        daat_trips_per_launch,
    )
    if "pod" not in mesh.axis_names:
        raise ValueError(
            f"pod serve step needs a 'pod' mesh axis, got {mesh.axis_names}"
        )
    if "model" not in mesh.axis_names:
        raise ValueError(
            f"pod serve step needs a 'model' mesh axis, got {mesh.axis_names}"
        )
    axes = mesh_axes(mesh)
    data_axes = tuple(axes.data)  # every non-"model" axis, "pod" included
    dp = data_axes if len(data_axes) > 1 else data_axes[0]
    shard_axes = data_axes + ("model",)
    idx_specs = jax.tree.map(lambda _: P(shard_axes), _index_data_template())
    if live_masked:
        # the tombstone stack rides replicated exactly like the index stack
        # (idx specs replicate the stacked rows onto every rank), so
        # rank-local shard j always meets its own mask row live[j]
        in_specs = (idx_specs, P(), P(dp, None), P(dp, None))
    else:
        in_specs = (idx_specs, P(dp, None), P(dp, None))
    out_specs = (P(dp, None), P(dp, None))
    data_sizes = tuple(int(mesh.shape[name]) for name in data_axes)
    n_hosts = 1
    for s in data_sizes:
        n_hosts *= s
    n_model = int(mesh.shape["model"])
    meta_cell: dict = {}

    statics = dict(
        engine=engine, k=k, rho_per_shard=rho_per_shard,
        max_segs_per_term=max_segs_per_term, docs_per_shard=docs_per_shard,
        scatter_impl=scatter_impl, fused_topk=fused_topk,
        daat_est_blocks=daat_est_blocks, daat_block_budget=daat_block_budget,
        max_bm_per_term=max_bm_per_term, daat_exact=daat_exact,
        daat_use_kernels=daat_use_kernels, daat_fused_chunk=daat_fused_chunk,
        daat_trips_per_launch=daat_trips_per_launch, n_docs_total=n_docs_total,
        # pod identity: same engine statics on a different mesh is a
        # DIFFERENT executable (different collectives), and the merge fan-in
        # is the serving counter the host side reports per dispatch
        pod_axes=shard_axes, pod_hosts=n_hosts, pod_model_ranks=n_model,
        merge_fanin=n_hosts * n_model * k,
        live_masked=live_masked,
    )

    def body(idx_data: dict, qt, qw, live=None):
        # flat position of this rank's host in the data group — the same
        # major-to-minor order P(shard_axes) partitions the shard axis in,
        # so host blocks, shard ranges, and gather order all agree
        drank = jnp.int32(0)
        for name, size in zip(data_axes, data_sizes):
            drank = drank * size + jax.lax.axis_index(name).astype(jnp.int32)
        mrank = jax.lax.axis_index("model").astype(jnp.int32)
        b_local = qt.shape[0]
        qt_g = jax.lax.all_gather(qt, data_axes, axis=0, tiled=True)
        qw_g = jax.lax.all_gather(qw, data_axes, axis=0, tiled=True)
        pool_s, pool_i = _scan_local_shards(
            idx_data, qt_g, qw_g,
            shard_ord0=drank * n_model + mrank, st=statics, meta_cell=meta_cell,
            live=live,
        )
        ms, mi = canonical_topk_merge(pool_s, pool_i, k, shard_axes)
        # every rank now holds the pod-global answer; hand back the rows of
        # the host that admitted them
        ms = jax.lax.dynamic_slice_in_dim(ms, drank * b_local, b_local, axis=0)
        mi = jax.lax.dynamic_slice_in_dim(mi, drank * b_local, b_local, axis=0)
        return ms, mi

    def body_live(idx_data: dict, live, qt, qw):
        return body(idx_data, qt, qw, live=live)

    sm = jax.shard_map(
        body_live if live_masked else body,
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )

    def serve(index_stack: ImpactIndex, q_terms, q_weights, live_stack=None):
        meta_cell.clear()
        meta_cell.update(
            block_size=index_stack.block_size,
            scale=index_stack.scale,
            bits=index_stack.bits,
            max_segs=index_stack.max_segs,
            max_bm=index_stack.max_bm,
        )
        data = _index_data_dict(index_stack)
        if live_masked:
            if live_stack is None:
                raise ValueError(
                    "this pod serve step was built live_masked=True; pass "
                    "the per-shard live_stack (see shard_live_stack)"
                )
            return sm(data, jnp.asarray(live_stack, jnp.int32), q_terms, q_weights)
        if live_stack is not None:
            raise ValueError(
                "live_stack passed to a pod serve step built without "
                "live_masked=True; rebuild the step with live_masked=True"
            )
        return sm(data, q_terms, q_weights)

    serve.statics = statics
    return serve, in_specs, out_specs


def make_bucketed_serve_step(
    mesh: Mesh,
    *,
    lq_buckets: Sequence[int],
    n_terms: int,
    **kwargs,
):
    """Lq-bucketed wrapper over the sharded (or pod) serve step.

    The underlying serve step is shape-polymorphic — one executable per
    query-batch shape — so bucketing at pod scale is purely a host-side
    dispatch: pad each incoming batch to the smallest bucket covering its
    live terms and the ``(B, bucket)`` executable grid materializes lazily
    under the same shard_map. Short-query traffic stops paying long-query
    gather cost on *every rank at once*, and per-rank work stays identical
    across ranks because all ranks see the same padded batch shape. Results
    are bit-identical to padding at max Lq (trailing pad slots are inert in
    both engines).

    A mesh with a ``"pod"`` axis routes to :func:`make_pod_serve_step`
    (multi-host: query batch = concatenation of all hosts' blocks);
    otherwise the single-host :func:`make_sharded_serve_step` applies.
    """
    from repro.serving.bucketing import bucketize_batch, normalize_buckets

    buckets = normalize_buckets(lq_buckets)
    step = make_pod_serve_step if "pod" in mesh.axis_names else make_sharded_serve_step
    serve, in_specs, out_specs = step(mesh, **kwargs)
    # One program per (B, bucket) shape. Called bare, a shard_map runs op by
    # op, compiling and dispatching every primitive across the mesh on its
    # own. The stack's static meta is part of the jit key (pytree aux data).
    jitted = jax.jit(serve)

    def serve_bucketed(index_stack: ImpactIndex, q_terms, q_weights, live_stack=None):
        qt, qw, _ = bucketize_batch(
            np.asarray(q_terms), np.asarray(q_weights), buckets, n_terms
        )
        # strong i32/f32, pre-dispatch: same compile-cache invariant as
        # AnytimeServer._bucketize (see its docstring)
        return jitted(
            index_stack, jnp.asarray(qt, jnp.int32), jnp.asarray(qw, jnp.float32),
            live_stack=live_stack,
        )

    # serve_bucketed itself does host-side numpy bucketization and CANNOT be
    # traced; the lint must trace `.inner` at each `.buckets` width instead.
    serve_bucketed.inner = serve
    serve_bucketed.buckets = buckets
    serve_bucketed.statics = serve.statics
    return serve_bucketed, in_specs, out_specs


def _index_data_dict(index: ImpactIndex) -> dict:
    return {
        f.name: getattr(index, f.name)
        for f in dataclasses.fields(ImpactIndex)
        if f.name not in _META_FIELDS
    }


def _index_data_template() -> dict:
    return {
        f.name: None
        for f in dataclasses.fields(ImpactIndex)
        if f.name not in _META_FIELDS
    }


def _static_meta_from(local: dict, docs_per_shard: int, meta: dict | None = None) -> dict:
    """Static metadata for a per-shard index rebuilt inside the shard_map.

    Shape-derived fields come from the local arrays; build-time constants
    (block size, quant scale/bits, seg/bm bounds) come from the real
    ``index_stack`` via ``meta`` when :func:`make_sharded_serve_step`'s
    ``serve()`` is the entry point. The historical defaults (128/1.0/8) only
    apply to bare ``sm(data_dict, ...)`` calls that never saw a real index.
    """
    n_docs_pad, tmax = local["doc_terms"].shape
    n_terms = local["term_seg_start"].shape[0] - 1
    m = meta or {}
    block_size = int(m.get("block_size", 128))
    return dict(
        n_docs=docs_per_shard,
        n_terms=n_terms,
        n_blocks=n_docs_pad // block_size,
        block_size=block_size,
        max_doc_terms=tmax,
        scale=float(m.get("scale", 1.0)),
        bits=int(m.get("bits", 8)),
        max_segs=int(m.get("max_segs", 0)),
        max_bm=int(m.get("max_bm", 0)),
    )
