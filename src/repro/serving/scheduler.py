"""Anytime serving: batched queries, deadline -> rho control, doc sharding.

The paper's core serving claim is that SAAT's posting budget rho makes query
cost — and therefore latency — *predictable*. This module turns that into a
deadline controller: given a target latency, pick the largest rho whose
predicted cost fits. Because rho is a static tensor shape, the controller
quantizes to a ladder of pre-compiled rho levels — and because ``saat_search``
is natively batched, each level is ONE batched executable over the whole
``[B, Lq]`` query batch (single batched plan sort, gather, and scatter), not
``B`` vmapped single-query programs. Switching levels never recompiles at
serve time.

At pod scale, documents shard over the ``model`` axis: each chip runs the
identical rho-budgeted scan over its shard and ships only its k finalists
(``sharded_topk_merge``). Uniform per-chip work = no stragglers from corpus
skew — the paper's tail-latency argument, promoted to a cluster property.

The server can also run the natively batched Block-Max DAAT engine
(``engine="daat"``) so both sides of the paper's SAAT-vs-DAAT comparison are
served by one batched executable each. DAAT has no rho knob: its cost is
data-dependent (the while_loop runs until the slowest query in the batch is
rank-safe), which is exactly the tail-latency contrast the benchmarks
measure.

Two serving-layer properties make the continuous-batching admission queue
(``repro.serving.queue``) possible:

  * **Lq bucketing** (``ServingConfig.lq_buckets``): each batch is padded to
    the smallest bucket width covering its live terms instead of the stream's
    max Lq, so the executable grid is (rho-or-engine-config) x (Lq bucket)
    and short-query traffic stops paying long-query gather cost. Results are
    bit-identical to the max-Lq pad (see ``repro.serving.bucketing``).
  * **Injectable time** (``clock=``): every latency measurement and the cost
    model's calibration read a :class:`repro.metrics.latency.Clock`, so the
    queue's deadline-driven flush policy can be tested on a simulated clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.daat import daat_search_batched, max_blocks_per_term
from repro.core.impact_index import META_FIELDS, ImpactIndex
from repro.core.index_handle import IndexHandle
from repro.core.saat import max_segments_per_term, posting_reach, saat_search
from repro.metrics.latency import Clock, LatencyStats, SystemClock, summarize_latencies
from repro.serving.bucketing import (
    bucketize_batch,
    merge_repeated_terms,
    normalize_buckets,
    pad_to_width,
)

_UNSET = object()  # pick_rho sentinel: "use cfg.deadline_ms"


def index_static_signature(ix: ImpactIndex) -> tuple:
    """Hashable shape-level signature of one ``ImpactIndex`` segment.

    Meta fields plus every array field's shape — exactly the jit-visible
    surface of the index pytree (array *values* are runtime operands and do
    not fork compiled programs). Used by ``AnytimeServer.executable_key``
    and the pod front end to fold segment identity into executable keys.
    """
    meta = tuple(getattr(ix, f) for f in META_FIELDS)
    shapes = tuple(
        tuple(np.shape(getattr(ix, f.name)))
        for f in dataclasses.fields(ix)
        if f.name not in META_FIELDS
    )
    return meta + shapes


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    k: int = 1000
    rho_ladder: tuple[int, ...] = (100_000, 500_000, 1_000_000, 5_000_000, 10_000_000)
    batch_size: int = 32
    deadline_ms: Optional[float] = None  # None = always use max rho
    scatter_impl: str = "sort"
    # fuse SAAT's top-k into the scatter kernel (impact_scatter_topk): the
    # [B, n_docs] accumulator never reaches HBM; scatter_impl is then ignored
    fused_topk: bool = False
    ema_alpha: float = 0.2  # cost-model smoothing
    # engine selection: "saat" (anytime, rho ladder) or "daat" (block-max
    # pruning; data-dependent cost, no rho control)
    engine: str = "saat"
    daat_est_blocks: int = 8
    daat_block_budget: int = 16
    daat_exact: bool = True
    # route DAAT phase 2 through the batched Pallas kernels (block_prune /
    # block_topk / sparse_score); False keeps the jnp oracle formulation
    daat_use_kernels: bool = False
    # fuse every phase-2 trip's select+score+merge into the single
    # VMEM-resident chunk_step kernel (requires daat_use_kernels=True);
    # per-trip HBM traffic drops to the candidate/state output only
    daat_fused_chunk: bool = False
    # batch up to this many phase-2 trips inside ONE fused chunk_step launch
    # (requires daat_fused_chunk=True); pool/theta/processed cross HBM once
    # per launch instead of once per trip. 1 = the per-trip launch cadence.
    # Ignored (clamped to 1) when daat_exact=False: the anytime budget is
    # enforced at trip granularity.
    daat_trips_per_launch: int = 1
    # Lq bucket widths: each batch is padded to the smallest bucket covering
    # its live terms (one executable per (config, bucket) pair, bit-identical
    # results); None pads to whatever width the caller sends
    lq_buckets: Optional[tuple[int, ...]] = None


@dataclasses.dataclass(frozen=True)
class DispatchRecord:
    """One :meth:`AnytimeServer.search_batch` call, by part, in ms on the
    server's clock. The three parts tile the call; each runs under the host
    span of the same name (``serve.prep``, ``serve.dispatch``,
    ``serve.wait``)."""

    batch: int  # rows dispatched, pad rows included
    rho: Optional[int]  # ladder level served; None for the daat engine
    prep_ms: float  # merge repeated terms, bucketize, copy to the device
    dispatch_ms: float  # the engine call, which returns before the device ends
    wait_ms: float  # block_until_ready: the device finishing the batch

    @property
    def search_ms(self) -> float:
        return self.prep_ms + self.dispatch_ms + self.wait_ms


@dataclasses.dataclass
class _CostModel:
    """us per million postings, learned online per rho level.

    ``clock`` stamps each level's last calibration time so staleness is
    observable (and so calibration itself is testable on a simulated clock).
    A level is *calibrated* once it has been directly measured. Predictions
    for unmeasured levels interpolate piecewise-linearly in *total cost*
    between the two bracketing calibrated levels. Above the calibrated range
    the boundary level's per-Mpost rate extrapolates linearly; BELOW it the
    prediction floors at the boundary level's measured total — fixed
    per-call overhead does not shrink with rho, so scaling through the
    origin under-predicts small budgets (the old nearest-level-times-
    ``rho/level`` rule had the same disease across the whole ladder).
    ``predict_us`` returns ``None`` only when nothing has been measured at
    all — callers must treat that as "unknown", never as "free".
    """

    us_per_mpost: dict
    alpha: float
    clock: Clock = dataclasses.field(default_factory=SystemClock)
    last_update_s: dict = dataclasses.field(default_factory=dict)
    # per-level confidence in [0, 1]: 1.0 = the EMA is fully trusted (the
    # steady state; update() then smooths at exactly `alpha`). A hot swap
    # decays confidence instead of discarding the value — the old measurement
    # is still the best available prior for the new generation's executable,
    # but the next observations blend in faster (effective alpha rises toward
    # 1 as confidence falls) until confidence recovers.
    confidence: dict = dataclasses.field(default_factory=dict)

    def update(self, rho: int, elapsed_us: float):
        per = elapsed_us / max(rho / 1e6, 1e-9)
        conf = self.confidence.get(rho, 1.0)
        a = self.alpha + (1.0 - self.alpha) * (1.0 - conf)
        old = self.us_per_mpost.get(rho)
        self.us_per_mpost[rho] = per if old is None else (1 - a) * old + a * per
        self.confidence[rho] = 1.0 - (1.0 - conf) * (1.0 - self.alpha)
        self.last_update_s[rho] = self.clock.now()

    def decay(self, factor: float):
        """Generation bump: keep every calibrated value, shrink its trust."""
        for rho in self.us_per_mpost:
            self.confidence[rho] = self.confidence.get(rho, 1.0) * factor

    def is_calibrated(self, rho: int) -> bool:
        return rho in self.us_per_mpost

    def predict_us(self, rho: int) -> Optional[float]:
        if not self.us_per_mpost:
            return None
        levels = sorted(self.us_per_mpost)
        # below the calibrated range: floor at the boundary level's measured
        # TOTAL cost. Scaling linearly through the origin pretends the fixed
        # per-call overhead (dispatch, plan sort, top-k) shrinks with rho —
        # it doesn't, and the resulting under-prediction made pick_rho admit
        # small-rho work that blew its deadline. Over-predicting a smaller
        # rho by at most the boundary total is the safe direction.
        if rho <= levels[0]:
            return self.us_per_mpost[levels[0]] * levels[0] / 1e6
        # above it: the boundary RATE extrapolates linearly (dominated by the
        # per-posting scan, so the rate is the right asymptote)
        if rho >= levels[-1]:
            return self.us_per_mpost[levels[-1]] * rho / 1e6
        hi_ix = bisect.bisect_left(levels, rho)
        lo, hi = levels[hi_ix - 1], levels[hi_ix]
        total_lo = self.us_per_mpost[lo] * lo / 1e6
        total_hi = self.us_per_mpost[hi] * hi / 1e6
        frac = (rho - lo) / (hi - lo)
        return total_lo + frac * (total_hi - total_lo)


class AnytimeServer:
    """Batched SAAT serving over one impact index — or a mutable handle.

    Every ``search_batch`` call dispatches the natively batched engine; the
    per-rho executables are compiled once (``warmup``) and reused. The plan
    bound ``max_segs`` comes from index build-time metadata, so constructing
    a server never blocks on a device sync.

    Passing an :class:`repro.core.index_handle.IndexHandle` makes the server
    lifecycle-aware: dispatches serve (main − tombstones) ∪ delta through the
    handle's merged search (rho budgets the MAIN segment only; the delta is
    tiny and always exact), and :meth:`swap_index` hot-swaps to a freshly
    compacted main between admission-queue flushes — bumping ``generation``
    and *decaying* (never discarding) the service-time calibration.
    """

    def __init__(
        self,
        index: ImpactIndex | IndexHandle,
        cfg: ServingConfig,
        clock: Optional[Clock] = None,
    ):
        if cfg.engine not in ("saat", "daat"):
            raise ValueError(f"unknown engine {cfg.engine!r}")
        if cfg.daat_fused_chunk and not cfg.daat_use_kernels:
            raise ValueError(
                "daat_fused_chunk fuses the kernel-mode chunk step; set "
                "daat_use_kernels=True"
            )
        if cfg.daat_trips_per_launch < 1:
            raise ValueError(
                f"daat_trips_per_launch={cfg.daat_trips_per_launch} must be >= 1"
            )
        if cfg.daat_trips_per_launch > 1 and not cfg.daat_fused_chunk:
            raise ValueError(
                "daat_trips_per_launch > 1 batches trips inside the fused "
                "chunk_step kernel; set daat_fused_chunk=True (and "
                "daat_use_kernels=True)"
            )
        self.handle: Optional[IndexHandle] = None
        if isinstance(index, IndexHandle):
            self.handle = index
        else:
            self.index = index
        self.cfg = cfg
        self.clock: Clock = clock if clock is not None else SystemClock()
        self.generation = self.handle.generation if self.handle is not None else 0
        self.dispatch_log: list[DispatchRecord] = []
        self._cost = _CostModel({}, cfg.ema_alpha, clock=self.clock)
        # whole-batch wall-ms EMA keyed by (engine, Lq bucket, batch shape,
        # rho): a batch runs as ONE executable whose wall time is far from
        # linear in B (plan/gather amortize, the DAAT while_loop runs to the
        # slowest row), so the admission queue's service-time estimate is
        # learned per compiled shape — never extrapolated linearly in B.
        # rho is part of the key because each SAAT ladder level is its own
        # executable with its own wall time (that difference IS the knob the
        # degrade-instead-of-violate flush policy trades on); DAAT has no rho
        # and keys with rho=None. SAAT falls back to the per-query rho model
        # only when no shape in the (engine, bucket, rho) lane is calibrated.
        self._bucket_ms: dict[tuple[str, int, int, Optional[int]], float] = {}
        # per-key calibration confidence (1.0 = steady state; see _CostModel)
        self._bucket_conf: dict[tuple[str, int, int, Optional[int]], float] = {}
        self.lq_buckets = (
            normalize_buckets(cfg.lq_buckets) if cfg.lq_buckets is not None else None
        )
        self._bind_main_segment()

    def _bind_main_segment(self):
        """(Re)derive everything that depends on the current main segment:
        the plan bounds (build-time metadata — no device sync), the rho
        ladder cap (the exact level is the main segment's posting count) and
        the per-width reach that every dispatch's budget is capped at (see
        :meth:`served_rho`; one small read of the per-term counts). Called
        at construction and on every :meth:`swap_index`.
        """
        index = self.handle.main if self.handle is not None else self.index
        self.index = index
        self.max_segs = max_segments_per_term(index)
        self.max_bm = max_blocks_per_term(index)
        self._reach = posting_reach(index)
        exact = index.n_postings
        ladder = sorted({min(r, exact) for r in self.cfg.rho_ladder} | {exact})
        self.rho_ladder = tuple(ladder)

    def served_rho(self, rho: Optional[int], lq_bucket: int) -> Optional[int]:
        """The budget a SAAT dispatch of width ``lq_bucket`` really runs with.

        A ladder level capped at what a query of that width can touch (the
        sum of its ``lq_bucket`` longest posting lists, with each term once —
        :meth:`_bucketize` merges repeated terms): the engine stops at the
        query's own total, so the cap changes no answer, and it keeps the
        ``[B, rho]`` budget buffers of the exact level the size the
        executable's width needs rather than the store's. ``None`` (DAAT)
        passes through.
        """
        if rho is None or self.cfg.engine == "daat":
            return rho
        return min(int(rho), int(self._reach[min(int(lq_bucket), self._reach.size) - 1]))

    # -------------------------- index lifecycle ----------------------------

    def swap_index(self, handle: Optional[IndexHandle] = None, *, decay: float = 0.5):
        """Hot-swap the serving index to the handle's current main segment.

        Called between admission-queue flushes after a background
        :meth:`~repro.core.index_handle.IndexHandle.compact` (or to adopt a
        replacement handle). Rebinds the main-segment statics (plan bounds,
        rho-ladder cap) and takes the handle's ``generation``.

        Calibration survives the swap **decayed, not discarded**: every
        service-time EMA keyed by shape — and every rho cost-model level —
        keeps its value but has its confidence multiplied by ``decay``, so the
        next observation of each executable blends in faster (effective alpha
        rises toward 1 as confidence falls) while the queue's flush policy
        still has a usable prediction from the first post-swap request.
        Resetting instead would re-open the cold-start window on every
        compaction — ``predict_service_ms`` returning 0.0 makes the queue
        flush exactly at the deadline, which a warm system has no reason to
        regress to.
        """
        if handle is not None:
            self.handle = handle
        if self.handle is None:
            raise ValueError(
                "swap_index needs a handle-backed server; construct the "
                "AnytimeServer with an IndexHandle"
            )
        if not 0.0 <= decay <= 1.0:
            raise ValueError(f"decay must be in [0, 1], got {decay}")
        self._bind_main_segment()
        self.generation = self.handle.generation
        self._decay_calibration(decay)

    def _decay_calibration(self, decay: float):
        """Shrink trust in every calibrated value without discarding it
        (service-time EMAs by shape, and the per-rho cost model)."""
        if not 0.0 <= decay <= 1.0:
            raise ValueError(f"decay must be in [0, 1], got {decay}")
        for key in self._bucket_ms:
            self._bucket_conf[key] = self._bucket_conf.get(key, 1.0) * decay
        self._cost.decay(decay)

    # -------------------------- rho selection -----------------------------

    def pick_rho(self, deadline_ms=_UNSET) -> int:
        """Largest *calibrated* ladder level whose predicted cost fits.

        ``deadline_ms`` overrides ``cfg.deadline_ms`` (the admission queue
        passes each batch's remaining time budget); ``None`` means no
        deadline -> max rho. An uncalibrated level is never treated as free:
        when no calibrated level fits we fall back to the *smallest*
        uncalibrated one (measure it cheaply, let the EMA learn), and only
        then to the smallest level outright.
        """
        deadline = self.cfg.deadline_ms if deadline_ms is _UNSET else deadline_ms
        if deadline is None:
            return self.rho_ladder[-1]
        budget_us = deadline * 1e3
        calibrated_fit = [
            rho
            for rho in self.rho_ladder
            if self._cost.is_calibrated(rho) and self._cost.predict_us(rho) <= budget_us
        ]
        if calibrated_fit:
            return calibrated_fit[-1]  # ladder is sorted ascending
        uncalibrated = [r for r in self.rho_ladder if not self._cost.is_calibrated(r)]
        if uncalibrated:
            return uncalibrated[0]
        return self.rho_ladder[0]

    # ------------------------ queue-facing predictions ---------------------

    def _rho_key(self, rho: Optional[int]) -> Optional[int]:
        """Canonical rho component of the service-time key (None for DAAT)."""
        if self.cfg.engine == "daat":
            return None
        return int(rho) if rho is not None else self.pick_rho()

    def predict_service_ms(self, n_queries: int, lq_bucket: int, rho: Optional[int] = None) -> float:
        """Predicted wall time to serve an ``[n_queries, lq_bucket]`` batch.

        Prefers the per-(engine, bucket, batch-shape, rho) EMA of observed
        whole-batch wall times: a batch is ONE executable, so its cost is far
        from linear in B and the old per-query-EMA-times-``n_queries`` rule
        systematically over-predicted large-shape flushes. ``rho`` selects
        the SAAT ladder level being considered (default: whatever
        ``pick_rho()`` would serve) — each level is a distinct executable
        with its own wall time, so predictions never mix levels. When the
        exact shape is uncalibrated, the nearest calibrated shape in the same
        (engine, bucket, rho) lane stands in: unscaled when predicting a
        smaller shape (a smaller batch can only be cheaper — over-predicting
        is safe), ratio-scaled upward when predicting a LARGER shape
        (flushing early is safe; under-predicting an unmeasured big
        executable would turn the cold start into deadline violations). Once
        a shape is observed its exact key takes over. SAAT falls back to the
        rho cost model only when no shape in the lane is calibrated at all,
        and the result is 0.0 when nothing is known — the admission queue
        then flushes exactly at the deadline, which is the conservative
        policy for an unknown service time.
        """
        eng, bucket, shape = self.cfg.engine, int(lq_bucket), int(n_queries)
        rk = self._rho_key(rho)
        batch_ms = self._bucket_ms.get((eng, bucket, shape, rk))
        if batch_ms is not None:
            return batch_ms
        shapes = [
            b for (e, bk, b, r) in self._bucket_ms if e == eng and bk == bucket and r == rk
        ]
        if shapes:
            nearest = min(shapes, key=lambda b: (abs(b - shape), b))
            batch_ms = self._bucket_ms[(eng, bucket, nearest, rk)]
            if shape > nearest:  # conservative upper bound, never a late flush
                return batch_ms * shape / nearest
            return batch_ms
        if eng == "saat":
            pred_us = self._cost.predict_us(rk)
            if pred_us is not None:
                return pred_us / 1e3 * n_queries
        return 0.0

    def service_calibrated(self, lq_bucket: int, rho: Optional[int] = None) -> bool:
        """True when some batch shape in the (engine, bucket, rho) lane has
        been directly measured — i.e. ``predict_service_ms`` for that lane
        rests on an observation of THAT executable, not on a cross-level
        guess. The degraded-rho picker only trusts calibrated lanes: an
        unmeasured small-rho level must never be "picked to fit" on faith.
        """
        eng, bucket, rk = self.cfg.engine, int(lq_bucket), self._rho_key(rho)
        return any(
            e == eng and bk == bucket and r == rk for (e, bk, _b, r) in self._bucket_ms
        )

    def pick_degraded_rho(self, n_queries: int, lq_bucket: int, remaining_ms: float) -> int:
        """Largest *calibrated* ladder level whose predicted service for this
        ``[n_queries, lq_bucket]`` flush still fits in ``remaining_ms``.

        This is the queue's degrade-instead-of-violate policy: when the full
        budget would blow the oldest deadline, trade effectiveness (a smaller
        posting budget) for the SLO rather than miss it. When no calibrated
        level fits, the SMALLEST calibrated level is the least-late choice;
        with nothing calibrated at all this defers to :meth:`pick_rho`'s
        deadline logic (which probes the smallest uncalibrated level so the
        EMA can learn it).
        """
        fit = [
            rho
            for rho in self.rho_ladder
            if self.service_calibrated(lq_bucket, rho)
            and self.predict_service_ms(n_queries, lq_bucket, rho) <= remaining_ms
        ]
        if fit:
            return fit[-1]  # ladder is sorted ascending
        calibrated = [r for r in self.rho_ladder if self.service_calibrated(lq_bucket, r)]
        if calibrated:
            return calibrated[0]
        return self.pick_rho(deadline_ms=remaining_ms)

    def _observe_bucket_ms(
        self, lq_bucket: int, batch_shape: int, batch_ms: float, rho: Optional[int] = None
    ):
        key = (self.cfg.engine, int(lq_bucket), int(batch_shape), self._rho_key(rho))
        old = self._bucket_ms.get(key)
        conf = self._bucket_conf.get(key, 1.0)
        # confidence-weighted smoothing: at full confidence (no swap since the
        # last observation settled) this is exactly cfg.ema_alpha; after a
        # generation bump the decayed confidence raises the effective alpha so
        # the stale-but-kept value re-converges quickly
        a = self.cfg.ema_alpha + (1.0 - self.cfg.ema_alpha) * (1.0 - conf)
        self._bucket_ms[key] = batch_ms if old is None else (1 - a) * old + a * batch_ms
        self._bucket_conf[key] = 1.0 - (1.0 - conf) * (1.0 - self.cfg.ema_alpha)

    # ----------------------------- serving --------------------------------

    def engine_fn(self, rho: Optional[int] = None):
        """The pure engine dispatch for one executable: ``(qt, qw) -> result``.

        This is exactly what ``search_batch`` runs after host-side
        bucketization — the traced hot path, with every static baked in. The
        analysis lint (``repro.analysis.hot_path``) traces the returned
        callable at each (Lq bucket, B) shape, so serving MUST route through
        it: anything dispatched some other way is invisible to the purity
        gate.

        Handle-backed servers dispatch the handle's merged search (main with
        tombstone mask + exact delta + canonical merge); the handle's current
        segment arrays are closed over at call time, so every dispatch sees
        the latest mutations with no server-side bookkeeping.
        """
        if self.handle is not None:
            return self._handle_engine(rho)
        if self.cfg.engine == "daat":
            return functools.partial(
                daat_search_batched,
                self.index,
                k=self.cfg.k,
                est_blocks=self.cfg.daat_est_blocks,
                block_budget=self.cfg.daat_block_budget,
                max_bm_per_term=self.max_bm,
                exact=self.cfg.daat_exact,
                use_kernels=self.cfg.daat_use_kernels,
                fused_chunk=self.cfg.daat_fused_chunk,
                trips_per_launch=self.cfg.daat_trips_per_launch,
            )
        if rho is None:
            rho = self.rho_ladder[-1]
        return functools.partial(
            saat_search,
            self.index,
            k=self.cfg.k,
            rho=rho,
            max_segs_per_term=self.max_segs,
            scatter_impl=self.cfg.scatter_impl,
            fused_topk=self.cfg.fused_topk,
        )

    def _handle_engine(self, rho: Optional[int] = None):
        """Merged lifecycle dispatch: ``(qt, qw) -> HandleResult``.

        rho budgets the MAIN segment only — the delta segment is tiny and
        always searched exactly, so the anytime knob trades effectiveness
        on the bulk corpus without ever degrading freshly written docs.
        """
        cfg = self.cfg
        if cfg.engine == "daat":
            return functools.partial(
                self.handle.daat_search,
                k=cfg.k,
                est_blocks=cfg.daat_est_blocks,
                block_budget=cfg.daat_block_budget,
                exact=cfg.daat_exact,
                use_kernels=cfg.daat_use_kernels,
                fused_chunk=cfg.daat_fused_chunk,
                trips_per_launch=cfg.daat_trips_per_launch,
            )
        return functools.partial(
            self.handle.saat_search,
            k=cfg.k,
            rho=self.rho_ladder[-1] if rho is None else rho,
            scatter_impl=cfg.scatter_impl,
            fused_topk=cfg.fused_topk,
        )

    def executable_key(
        self, lq_bucket: int, batch_size: int, rho: Optional[int] = None
    ) -> tuple:
        """Hashable id of the compiled executable serving this dispatch.

        The admission queue's service-time EMA and warmup grid both assume
        **one executable per key**: equal keys must hit the same compiled
        program (never a silent retrace), distinct keys must be distinct
        programs. The tuple mirrors the engines' ``SAAT_STATICS`` /
        ``DAAT_STATICS`` jit surface plus the batch shape — plus the **index
        static signature**: the segments' meta fields and array shapes are
        part of the jit cache key (the index rides the trace as pytree
        leaves whose treedef/avals are shape-derived), so a delta growing a
        block or a compaction changing the main pad width forks the compiled
        program and must fork the key. The lifecycle ``generation`` counter
        is deliberately NOT in the key: two generations with identical
        signatures trace to the identical program (array *values* are
        runtime inputs), so folding them into one key is what keeps the
        lint's key <-> fingerprint bijection true across hot swaps. The
        analysis lint verifies the invariant by tracing every key twice.
        """
        cfg = self.cfg
        if cfg.engine == "daat":
            statics: tuple = (
                "daat", cfg.k, cfg.daat_est_blocks, cfg.daat_block_budget,
                self.max_bm, cfg.daat_exact, cfg.daat_use_kernels,
                cfg.daat_fused_chunk, cfg.daat_trips_per_launch,
            )
        else:
            rho = self.rho_ladder[-1] if rho is None else rho
            statics = (
                "saat", cfg.k, self.served_rho(rho, lq_bucket),
                self.max_segs, cfg.scatter_impl, cfg.fused_topk,
            )
        return statics + self._index_signature() + (int(lq_bucket), int(batch_size))

    def _index_signature(self) -> tuple:
        """Static (shape-level) signature of the index the dispatch closes over.

        One entry per segment: the ``ImpactIndex`` meta fields plus every
        array field's shape — exactly the jit-visible surface of the index
        pytree. Handle-backed servers contribute the main segment, a marker
        for the always-present tombstone mask, and the delta segment (or
        ``None`` when empty: the merge is skipped, a genuinely different
        program).
        """
        if self.handle is None:
            return (index_static_signature(self.index),)
        d = self.handle.delta
        return (
            index_static_signature(self.handle.main),
            "live",
            None if d is None else index_static_signature(d),
        )

    def _bucketize(self, q_terms, q_weights) -> tuple[jax.Array, jax.Array, int]:
        """Pad the batch to its Lq bucket and canonicalize dtypes.

        Dtype canonicalization is a compile-cache invariant, not a nicety: a
        caller handing i64 terms or weak-typed python-float weights would
        silently fork the jit cache per dtype and break the
        one-executable-per-key contract ``executable_key`` promises. The
        casts are host-side (pre-dispatch), so the traced hot path always
        sees ``i32/f32`` strong types — which is what the analysis lint
        asserts. A term repeated in a query row is merged into one slot
        (weights summed): a query is a sparse vector, and :meth:`served_rho`
        budgets each term once.
        """
        qt, qw = merge_repeated_terms(
            np.asarray(q_terms), np.asarray(q_weights), self.index.n_terms
        )
        if self.lq_buckets is None:
            bucket = int(qt.shape[-1])
        else:
            qt, qw, bucket = bucketize_batch(qt, qw, self.lq_buckets, self.index.n_terms)
        return jnp.asarray(qt, jnp.int32), jnp.asarray(qw, jnp.float32), bucket

    def search_batch(self, q_terms: jax.Array, q_weights: jax.Array, rho: Optional[int] = None):
        """Serve one ``[B, Lq]`` batch and wait for it; appends its
        :class:`DispatchRecord` to ``dispatch_log``."""
        if self.cfg.engine == "daat":
            if rho is not None:
                raise ValueError(
                    "rho is a SAAT posting budget; the daat engine's cost is "
                    "data-dependent and cannot honor it"
                )
        # an explicit rho must be a real ladder level: `rho or pick_rho()`
        # silently routed rho=0 (any falsy budget) to the controller
        elif rho is None:
            rho = self.pick_rho()
        elif rho not in self.rho_ladder:
            raise ValueError(
                f"rho={rho!r} is not a ladder level {self.rho_ladder}; explicit "
                "budgets must hit a pre-compiled executable"
            )
        span = jax.profiler.TraceAnnotation
        t0 = self.clock.now()  # bucketize is service cost: keep it timed
        with span("serve.prep"):
            q_terms, q_weights, bucket = self._bucketize(q_terms, q_weights)
        t1 = self.clock.now()
        with span("serve.dispatch"):
            res = self.engine_fn(self.served_rho(rho, bucket))(q_terms, q_weights)
        t2 = self.clock.now()
        with span("serve.wait"):
            jax.block_until_ready(res.scores)
        t3 = self.clock.now()
        rec = DispatchRecord(
            batch=int(q_terms.shape[0]), rho=rho, prep_ms=(t1 - t0) * 1e3,
            dispatch_ms=(t2 - t1) * 1e3, wait_ms=(t3 - t2) * 1e3,
        )
        self.dispatch_log.append(rec)
        if rho is not None:
            self._cost.update(rho, rec.search_ms / rec.batch * 1e3)
        self._observe_bucket_ms(bucket, rec.batch, rec.search_ms, rho=rho)
        return res

    def warmup(
        self,
        q_terms: jax.Array,
        q_weights: jax.Array,
        repeats: int = 2,
        batch_sizes: Optional[Sequence[int]] = None,
    ):
        """Compile + calibrate the executable grid (excluded from stats).

        The grid is (rho-or-engine-config) x (Lq bucket) x (batch size):
        every shape the admission queue can flush is compiled here, so
        serve-time never recompiles. ``batch_sizes`` defaults to the sample's
        own B; the queue passes its flushable shapes.
        """
        sizes = [int(q_terms.shape[0])] if batch_sizes is None else sorted(set(batch_sizes))
        buckets = [int(q_terms.shape[-1])] if self.lq_buckets is None else list(self.lq_buckets)
        qt_np, qw_np = np.asarray(q_terms), np.asarray(q_weights)
        for bucket in buckets:
            if bucket >= qt_np.shape[-1]:
                bt, bw = pad_to_width(qt_np, qw_np, bucket, self.index.n_terms)
            else:
                # slice regardless of live terms: warmup only needs the SHAPE
                # compiled and timed; which terms survive is irrelevant
                bt, bw = qt_np[:, :bucket], qw_np[:, :bucket]
            for B in sizes:
                reps = np.resize(np.arange(qt_np.shape[0]), B)
                qt, qw = jnp.asarray(bt[reps]), jnp.asarray(bw[reps])
                if self.cfg.engine == "daat":
                    for _ in range(repeats):
                        t0 = self.clock.now()
                        jax.block_until_ready(self.engine_fn()(qt, qw).scores)
                        batch_ms = (self.clock.now() - t0) * 1e3
                    self._observe_bucket_ms(bucket, B, batch_ms)
                    continue
                for rho in self.rho_ladder:
                    for _ in range(repeats):
                        t0 = self.clock.now()
                        res = self.engine_fn(self.served_rho(rho, bucket))(qt, qw)
                        jax.block_until_ready(res.scores)
                        batch_ms = (self.clock.now() - t0) * 1e3
                    self._cost.update(rho, batch_ms * 1e3 / B)
                    # per-rho key: each ladder level is its own executable,
                    # so its wall time must never EMA-mix with another level's
                    self._observe_bucket_ms(bucket, B, batch_ms, rho=rho)

    def stats(self) -> LatencyStats:
        """Per-query latency: each dispatch's search time over its rows, once
        for every row."""
        return summarize_latencies(
            [d.search_ms / d.batch for d in self.dispatch_log for _ in range(d.batch)]
        )

    def reset_stats(self):
        self.dispatch_log.clear()

    def export_counters(self, registry=None):
        """Scrape-time serving counters for this server's dispatch surface.

        Derived from state the server already keeps (query tallies, the
        shape-keyed service-time EMA, the rho cost model) — never touched on
        the hot path. Shares the registry conventions of
        ``AdmissionQueue.export_counters`` / ``repro.serving.counters``.
        """
        from repro.serving.counters import CounterRegistry

        reg = registry if registry is not None else CounterRegistry()
        reg.counter(
            "repro_server_queries_total", "Queries served (per-request rows)"
        ).labels(engine=self.cfg.engine).inc(sum(d.batch for d in self.dispatch_log))
        cal = reg.gauge(
            "repro_server_calibrated_shapes",
            "Directly measured (bucket, batch-shape, rho) executables",
        )
        cal.labels(engine=self.cfg.engine).set(len(self._bucket_ms))
        ema = reg.gauge(
            "repro_server_service_ms",
            "EMA whole-batch wall ms per (bucket, batch shape, rho) executable",
        )
        for (eng, bucket, shape, rho), ms in sorted(
            self._bucket_ms.items(), key=lambda kv: (kv[0][0], kv[0][1], kv[0][2], str(kv[0][3]))
        ):
            ema.labels(
                engine=eng, bucket=str(bucket), shape=str(shape),
                rho="none" if rho is None else str(rho),
            ).set(ms)
        # index lifecycle: generation is meaningful (0) even for an immutable
        # server; tombstone/delta families only exist on a handle-backed one
        reg.gauge(
            "repro_index_generation",
            "Index lifecycle generation (bumped by each hot-swapped compaction)",
        ).labels(engine=self.cfg.engine).set(self.generation)
        if self.handle is not None:
            reg.gauge(
                "repro_index_tombstones",
                "Deleted/updated docs masked -inf in the main segment",
            ).labels(engine=self.cfg.engine).set(self.handle.tombstone_count)
            reg.gauge(
                "repro_index_delta_docs",
                "Docs pending in the append-only delta segment",
            ).labels(engine=self.cfg.engine).set(self.handle.delta_docs)
        return reg


def run_query_stream(
    server: AnytimeServer,
    q_terms: np.ndarray,  # [N, Lq]
    q_weights: np.ndarray,
    *,
    batch_size: Optional[int] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Drive a query stream through the server in fixed batches.

    Returns (scores [N, k], doc_ids [N, k]). The final ragged batch is padded
    with repeats (served, then dropped) so every executable sees one shape.
    """
    bs = batch_size or server.cfg.batch_size
    N = q_terms.shape[0]
    out_s, out_i = [], []
    for lo in range(0, N, bs):
        hi = min(lo + bs, N)
        qt = q_terms[lo:hi]
        qw = q_weights[lo:hi]
        if hi - lo < bs:  # pad final batch
            pad = bs - (hi - lo)
            qt = np.concatenate([qt, np.repeat(qt[-1:], pad, 0)])
            qw = np.concatenate([qw, np.repeat(qw[-1:], pad, 0)])
        res = server.search_batch(jnp.asarray(qt), jnp.asarray(qw))
        out_s.append(np.asarray(res.scores)[: hi - lo])
        out_i.append(np.asarray(res.doc_ids)[: hi - lo])
    return np.concatenate(out_s), np.concatenate(out_i)
