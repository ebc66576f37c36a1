"""Pallas TPU kernels for the paper's compute hot spots.

  impact_scatter  SAAT accumulation: VMEM one-hot scatter-add
  impact_scatter_topk  fused SAAT scatter + per-block top-k (accumulator
                  stays in VMEM; only [B, n_blocks * k] candidates hit HBM)
  sparse_score    DAAT/exhaustive: match-and-accumulate block scoring
  block_prune_csr DAAT phase 0: block upper bounds straight off the CSR lists
  chunk_step      DAAT phase 2: fused select + score + merge per trip
  block_prune     DAAT: fused block upper-bound matmul + theta threshold
  block_topk      tiled two-stage top-k over huge accumulator/candidate sets

Each subpackage ships ``kernel.py`` (pl.pallas_call + BlockSpec VMEM tiling),
``ops.py`` (jit'd wrapper, padding, interpret-mode selection) and ``ref.py``
(pure-jnp oracle used by the sweep tests). Engine-reachable kernels compile
for a TPU v5e in ``tests/test_tpu_compile.py``.
"""
from repro.kernels.block_prune import block_prune, block_prune_batched  # noqa: F401
from repro.kernels.block_topk import block_topk, block_topk_batched  # noqa: F401
from repro.kernels.impact_scatter import impact_scatter, impact_scatter_batched  # noqa: F401
from repro.kernels.impact_scatter_topk import (  # noqa: F401
    impact_scatter_topk,
    impact_scatter_topk_batched,
)
from repro.kernels.sparse_score import sparse_score, sparse_score_batched  # noqa: F401
