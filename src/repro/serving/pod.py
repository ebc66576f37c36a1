"""Pod-scale serving front end: per-host admission over one shared mesh.

``make_pod_serve_step`` (repro.serving.sharded) is the SPMD program: every
rank scores the pod-global query batch against its local doc shard(s) and
joins the id-canonical cross-host k-merge. This module is the *host side* of
that program:

  * :class:`PodServer` — one ingestion host's :class:`AnytimeServer`: the
    same rho ladder / cost model / service-time EMA surface the admission
    queue consumes, but every dispatch embeds the host's local ``[B]`` block
    into the pod-global ``[hosts * B]`` batch (absent hosts' rows are inert
    sentinels — see ``repro.serving.bucketing.sentinel_rows``) and runs the
    pod serve step. A single process therefore simulates any one host of a
    pod faithfully, which is exactly what the
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` CI lane exercises.
  * :class:`PodFrontEnd` — the whole pod in one object: one
    :class:`~repro.serving.queue.AdmissionQueue` per ingestion host, all
    feeding the same mesh, with merged counter export.

Serving counters (``repro.serving.counters``) are derived at scrape time
from the queues' flush logs and the servers' dispatch tallies — the traced
hot path stays pure; nothing under the shard_map ever increments a counter.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.daat import max_blocks_per_term
from repro.core.impact_index import ImpactIndex
from repro.core.index_handle import search_delta_pool
from repro.core.saat import max_segments_per_term
from repro.core.topk import merge_pools_by_id
from repro.metrics.latency import Clock
from repro.serving.bucketing import sentinel_rows
from repro.serving.counters import CounterRegistry
from repro.serving.queue import AdmissionQueue, Completion
from repro.serving.scheduler import AnytimeServer, ServingConfig, index_static_signature
from repro.serving.sharded import make_pod_serve_step


@dataclasses.dataclass(frozen=True)
class PodResult:
    """One host's block of the pod-merged answer (no per-rank WorkStats:
    the merge consumes only the k-pools, so survivor counts never leave
    their rank)."""

    scores: jax.Array  # f32[B, k]
    doc_ids: jax.Array  # i32[B, k]


def pod_hosts(mesh: Mesh) -> int:
    """Number of ingestion hosts = product of the data-group axis sizes."""
    n = 1
    for name in mesh.axis_names:
        if name != "model":
            n *= int(mesh.shape[name])
    return n


class PodServer(AnytimeServer):
    """One ingestion host's anytime server over a pod mesh.

    Inherits the whole queue-facing surface of :class:`AnytimeServer`
    (``pick_rho`` / ``predict_service_ms`` / ``pick_degraded_rho`` /
    ``search_batch`` / ``warmup`` — all keyed on the host's LOCAL batch
    shape), and reroutes the engine dispatch through the pod serve step:

      * ``rho_ladder`` caps at the *per-shard* posting count (the stacked
        index's trailing postings dim), not ``ImpactIndex.n_postings`` —
        which on a stacked index is the shard count. The top level is the
        exact budget: every shard scans all of its postings.
      * ``engine_fn(rho)`` returns a host-side wrapper, not a traceable
        engine: it pads the local block to the pod-global batch, dispatches
        the (jitted) pod step, and slices the host's rows back out. The
        traced hot path is the step's ``serve`` itself — lint it with
        ``repro.analysis.hot_path.lint_sharded_serve`` over
        ``serve_step(rho)``, never ``lint_server``.
    """

    def __init__(
        self,
        mesh: Mesh,
        index_stack: ImpactIndex,
        cfg: ServingConfig,
        *,
        docs_per_shard: int,
        n_docs_total: Optional[int] = None,
        host: int = 0,
        clock: Optional[Clock] = None,
    ):
        super().__init__(index_stack, cfg, clock)
        self.mesh = mesh
        self.n_hosts = pod_hosts(mesh)
        if not (0 <= host < self.n_hosts):
            raise ValueError(f"host={host} outside the pod's {self.n_hosts} hosts")
        self.host = int(host)
        self.docs_per_shard = int(docs_per_shard)
        self.n_docs_total = n_docs_total
        # stacked index: doc_ids is [S, postings_per_shard]; n_postings
        # (= leading dim) is the SHARD count, so rebuild the ladder against
        # the true per-shard exact budget
        exact = int(index_stack.doc_ids.shape[1])
        self.rho_ladder = tuple(sorted({min(r, exact) for r in cfg.rho_ladder} | {exact}))
        self._steps: dict[Optional[int], object] = {}
        self._jitted: dict[Optional[int], object] = {}
        self.n_pod_dispatches: dict[tuple[str, Optional[int]], int] = {}
        # index lifecycle at pod scale: a per-shard tombstone stack rides the
        # live-masked serve step; the (corpus-global) delta pool is searched
        # host-side and merged by gid AFTER the pod k-merge hands back this
        # host's rows — the delta never crosses the ICI
        self._live_stack: Optional[jax.Array] = None
        self._delta_index: Optional[ImpactIndex] = None
        self._delta_gids: Optional[jax.Array] = None

    # --------------------------- index lifecycle ---------------------------

    def set_lifecycle(
        self,
        *,
        live_stack=None,
        delta: Optional[ImpactIndex] = None,
        delta_gids=None,
        generation: Optional[int] = None,
        decay: float = 0.5,
    ):
        """Install (or clear) this host's view of the mutable corpus.

        ``live_stack`` is the per-shard tombstone bitmap
        (:func:`repro.serving.sharded.shard_live_stack`); ``delta`` +
        ``delta_gids`` the pending-docs segment with its local->gid map.
        Toggling the live mask on or off switches between the masked and
        unmasked serve-step programs, so the step cache is dropped on that
        edge (same-program updates — new mask values, a changed delta — keep
        every compiled step). A ``generation`` bump decays — never discards —
        the calibration, exactly like :meth:`AnytimeServer.swap_index`.
        """
        if (delta is None) != (delta_gids is None):
            raise ValueError("delta and delta_gids must be set (or cleared) together")
        was_masked = self._live_stack is not None
        self._live_stack = None if live_stack is None else jnp.asarray(live_stack, jnp.int32)
        if (self._live_stack is not None) != was_masked:
            self._steps.clear()
            self._jitted.clear()
        self._delta_index = delta
        self._delta_gids = None if delta_gids is None else jnp.asarray(delta_gids, jnp.int32)
        if generation is not None and generation != self.generation:
            self.generation = int(generation)
            self._decay_calibration(decay)

    def swap_stack(
        self,
        index_stack: ImpactIndex,
        *,
        live_stack=None,
        delta: Optional[ImpactIndex] = None,
        delta_gids=None,
        generation: Optional[int] = None,
        decay: float = 0.5,
        docs_per_shard: Optional[int] = None,
        n_docs_total: Optional[int] = None,
    ):
        """Hot-swap a recompacted shard stack between admission-queue flushes.

        Rebinds the stacked index and its build-time bounds, rebuilds the
        per-shard rho ladder, drops the compiled step cache (the stack's
        shapes/bounds are baked into every step), and installs the new
        lifecycle state. A compaction usually changes the shard geometry
        (docs fold out, the gid space grows), so pass the new
        ``docs_per_shard`` / ``n_docs_total`` from the re-shard alongside the
        stack. Calibration survives decayed, not discarded.
        """
        if docs_per_shard is not None:
            self.docs_per_shard = int(docs_per_shard)
        if n_docs_total is not None:
            self.n_docs_total = int(n_docs_total)
        self.index = index_stack
        self.max_segs = max_segments_per_term(index_stack)
        self.max_bm = max_blocks_per_term(index_stack)
        exact = int(index_stack.doc_ids.shape[1])
        self.rho_ladder = tuple(
            sorted({min(r, exact) for r in self.cfg.rho_ladder} | {exact})
        )
        self._steps.clear()
        self._jitted.clear()
        gen = generation if generation is not None else self.generation + 1
        self.set_lifecycle(
            live_stack=live_stack, delta=delta, delta_gids=delta_gids,
            generation=gen, decay=decay,
        )

    # ------------------------- pod step plumbing ---------------------------

    def serve_step(self, rho: Optional[int] = None):
        """The raw pod serve step for one SAAT ladder level (or DAAT).

        This is the traced hot path behind ``engine_fn`` — what the analysis
        lint matrix traces, and what carries ``.statics`` (including
        ``merge_fanin``, the pod's candidates-per-merge).
        """
        key = self._rho_key(rho)
        if key not in self._steps:
            cfg = self.cfg
            serve, _, _ = make_pod_serve_step(
                self.mesh,
                k=cfg.k,
                rho_per_shard=self.rho_ladder[-1] if key is None else key,
                max_segs_per_term=self.max_segs,
                docs_per_shard=self.docs_per_shard,
                scatter_impl=cfg.scatter_impl,
                fused_topk=cfg.fused_topk,
                engine=cfg.engine,
                daat_est_blocks=cfg.daat_est_blocks,
                daat_block_budget=cfg.daat_block_budget,
                max_bm_per_term=self.max_bm if cfg.engine == "daat" else 0,
                daat_exact=cfg.daat_exact,
                daat_use_kernels=cfg.daat_use_kernels,
                daat_fused_chunk=cfg.daat_fused_chunk,
                daat_trips_per_launch=cfg.daat_trips_per_launch,
                n_docs_total=self.n_docs_total,
                live_masked=self._live_stack is not None,
            )
            self._steps[key] = serve
            # ImpactIndex is a registered-dataclass pytree: the stack rides
            # along as an operand, so one compiled program per (B, Lq) shape
            self._jitted[key] = jax.jit(serve)
        return self._steps[key]

    def _pod_dispatch(self, qt, qw, rho: Optional[int]) -> PodResult:
        key = self._rho_key(rho)
        self.serve_step(rho)  # ensure built
        qt = np.asarray(qt, dtype=np.int32)
        qw = np.asarray(qw, dtype=np.float32)
        B, width = qt.shape
        gqt, gqw = sentinel_rows(self.n_hosts * B, width, self.index.n_terms)
        gqt[self.host * B : (self.host + 1) * B] = qt
        gqw[self.host * B : (self.host + 1) * B] = qw
        if self._live_stack is not None:
            scores, ids = self._jitted[key](
                self.index, jnp.asarray(gqt, jnp.int32), jnp.asarray(gqw, jnp.float32),
                live_stack=self._live_stack,
            )
        else:
            scores, ids = self._jitted[key](
                self.index, jnp.asarray(gqt, jnp.int32), jnp.asarray(gqw, jnp.float32)
            )
        self.n_pod_dispatches[(self.cfg.engine, key)] = (
            self.n_pod_dispatches.get((self.cfg.engine, key), 0) + 1
        )
        lo, hi = self.host * B, (self.host + 1) * B
        scores, ids = scores[lo:hi], ids[lo:hi]
        if self._delta_index is not None:
            # host-local freshness merge: the pending-docs pool is searched
            # exactly on this host (it never crosses the ICI) and merged by
            # gid with the pod answer — same canonical merge the single-host
            # IndexHandle uses, so ties still resolve ascending-gid
            ds, dlocal = search_delta_pool(
                self._delta_index, jnp.asarray(qt, jnp.int32),
                jnp.asarray(qw, jnp.float32), k=self.cfg.k,
                engine=self.cfg.engine, scatter_impl=self.cfg.scatter_impl,
                fused_topk=self.cfg.fused_topk,
            )
            dgids = self._delta_gids[dlocal]
            scores, ids = merge_pools_by_id(scores, ids, ds, dgids, self.cfg.k)
        return PodResult(scores=scores, doc_ids=ids)

    # ------------------------ AnytimeServer overrides ----------------------

    def engine_fn(self, rho: Optional[int] = None):
        if self.cfg.engine == "daat":
            return self._daat_search
        if rho is None:
            rho = self.rho_ladder[-1]

        def fn(qt, qw, _rho=rho):
            return self._pod_dispatch(qt, qw, _rho)

        return fn

    def served_rho(self, rho: Optional[int], lq_bucket: int) -> Optional[int]:
        # the pod ladder is already per shard: its top level is each shard's
        # whole posting store, the budget every rank's scan runs with
        return rho

    def _daat_search(self, q_terms, q_weights):
        return self._pod_dispatch(q_terms, q_weights, None)

    def executable_key(
        self, lq_bucket: int, batch_size: int, rho: Optional[int] = None
    ) -> tuple:
        # the pod program differs from the single-host engine at equal
        # engine statics (collectives, shard layout), and its batch is
        # hosts * B wide — fold the pod identity AND the lifecycle state's
        # static surface (mask presence, delta shapes) into the key; the
        # generation counter itself stays out for the same reason as in
        # AnytimeServer.executable_key
        base = super().executable_key(lq_bucket, batch_size, rho)
        lifecycle = (
            "live" if self._live_stack is not None else None,
            None if self._delta_index is None
            else index_static_signature(self._delta_index),
        )
        return ("pod", self.n_hosts, int(self.mesh.shape["model"]),
                self.docs_per_shard, self.n_docs_total) + lifecycle + base

    # ----------------------------- counters --------------------------------

    def export_counters(self, registry: Optional[CounterRegistry] = None) -> CounterRegistry:
        """Scrape-time serving counters for this host's dispatch path."""
        reg = registry if registry is not None else CounterRegistry()
        host = str(self.host)
        disp = reg.counter(
            "repro_pod_dispatch_total",
            "Pod serve-step dispatches by host, engine and served rho",
        )
        for (engine, rho), n in sorted(
            self.n_pod_dispatches.items(), key=lambda kv: (kv[0][0], str(kv[0][1]))
        ):
            disp.labels(host=host, engine=engine, rho="none" if rho is None else str(rho)).inc(n)
        fanin = reg.gauge(
            "repro_pod_merge_fanin",
            "Candidates entering the cross-host k-merge (ranks * k)",
        )
        for key, serve in self._steps.items():
            fanin.labels(
                host=host, rho="none" if key is None else str(key)
            ).set(serve.statics["merge_fanin"])
        return reg


class PodFrontEnd:
    """The whole pod on one process: per-host admission queues, one mesh.

    Each ingestion host gets its own :class:`PodServer` (host ``h`` embeds
    its flushes at block ``h`` of the pod batch) and its own
    :class:`AdmissionQueue` over that server — per-host admission is the
    deployment shape the paper's traffic claim needs, and simulating every
    host in one process is what lets the CI pod lane drive it end to end.
    """

    def __init__(
        self,
        mesh: Mesh,
        index_stack: ImpactIndex,
        cfg: ServingConfig,
        *,
        docs_per_shard: int,
        n_docs_total: Optional[int] = None,
        clock: Optional[Clock] = None,
        queue_kwargs: Optional[dict] = None,
    ):
        self.mesh = mesh
        self.n_hosts = pod_hosts(mesh)
        self.servers = [
            PodServer(
                mesh, index_stack, cfg,
                docs_per_shard=docs_per_shard, n_docs_total=n_docs_total,
                host=h, clock=clock,
            )
            for h in range(self.n_hosts)
        ]
        qkw = dict(queue_kwargs or {})
        self.queues = [AdmissionQueue(srv, **qkw) for srv in self.servers]

    def submit(self, host: int, q_terms, q_weights, deadline_ms: Optional[float] = None) -> int:
        return self.queues[host].submit(q_terms, q_weights, deadline_ms)

    def poll(self) -> list[tuple[int, Completion]]:
        out: list[tuple[int, Completion]] = []
        for h, q in enumerate(self.queues):
            out.extend((h, c) for c in q.poll())
        return out

    def drain(self) -> list[tuple[int, Completion]]:
        out: list[tuple[int, Completion]] = []
        for h, q in enumerate(self.queues):
            out.extend((h, c) for c in q.drain())
        return out

    def pending(self) -> int:
        return sum(q.pending() for q in self.queues)

    def set_lifecycle(self, **kwargs):
        """Install lifecycle state (tombstone stack / delta pool) on every
        host's server; see :meth:`PodServer.set_lifecycle`."""
        for srv in self.servers:
            srv.set_lifecycle(**kwargs)
        if kwargs.get("generation") is not None:
            for q in self.queues:
                q.survivors.decay(kwargs.get("decay", 0.5))

    def swap_stack(self, index_stack: ImpactIndex, **kwargs):
        """Hot-swap a recompacted shard stack on every host between flushes;
        pending requests ride (see :meth:`AdmissionQueue.swap_index` for the
        zero-loss argument — the same one applies per host queue)."""
        for srv in self.servers:
            srv.swap_stack(index_stack, **kwargs)
        for q in self.queues:
            q.survivors.decay(kwargs.get("decay", 0.5))

    def export_counters(self, registry: Optional[CounterRegistry] = None) -> CounterRegistry:
        reg = registry if registry is not None else CounterRegistry()
        for h, (srv, q) in enumerate(zip(self.servers, self.queues)):
            q.export_counters(reg, labels={"host": str(h)})
            srv.export_counters(reg)
        return reg


def warmup_pod(
    front: PodFrontEnd,
    q_terms,
    q_weights,
    *,
    batch_sizes: Optional[Sequence[int]] = None,
    repeats: int = 1,
):
    """Warm every host's executable grid (hosts share compiled programs
    only per-(host-block) — each host's embedding is a distinct operand
    layout of the SAME jitted step, so warming host 0 compiles for all)."""
    for srv in front.servers:
        srv.warmup(q_terms, q_weights, repeats=repeats, batch_sizes=batch_sizes)
