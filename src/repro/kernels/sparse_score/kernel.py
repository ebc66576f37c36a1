"""Pallas TPU kernel: match-and-accumulate sparse document scoring.

The DAAT phase-2 / exhaustive hot loop is, per document d:

    score_d = sum_j w_dj * qweight(term_dj)

On CPU this is a gather through a query hash table. The TPU-native version
avoids the vocab-sized gather entirely: the (tiny) query rides in SMEM as
scalar-prefetched ``(q_terms[Lq], q_weights[Lq])`` and term matching becomes
``Lq`` elementwise compare-and-select passes over the VMEM doc tile, 8 rows
at a time:

    qv[BD, Tmax]   = sum_l where(doc_terms == q_terms[l], q_weights[l], 0)
    score[BD]      = tree_sum(qv * w)

With distinct query terms every ``qv`` cell has at most one nonzero addend,
so it equals the engine's dense-query-vector gather exactly, and the row sum
is the fixed pairwise tree of :func:`repro.kernels.common.tree_sum_last` —
the scores are bit-identical to the jnp scorer. Vocabulary size never
appears in the kernel: the same code serves the 27k-term SPLADE index and
the 3.9M-term BM25-T5 index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import tree_sum_last

ROWS = 8  # doc rows matched per pass: one sublane tile


def score_doc_rows(terms_ref, weights_ref, qt_ref, qw_ref, q0, lq: int, prod_ref):
    """Scores of the doc rows in ``terms_ref/weights_ref [BD, Tmax]``: f32[1, BD].

    ``qt_ref/qw_ref`` are flat SMEM query refs read at ``[q0, q0 + lq)``;
    ``prod_ref`` is a VMEM ``[BD, Tmax]`` f32 scratch for ``qv * w``.
    """
    bd, tmax = prod_ref.shape

    def row_group(g, carry):
        sl = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        terms = terms_ref[sl, :]

        def one_term(l, qv):
            hit = terms == qt_ref[q0 + l]
            return qv + jnp.where(hit, qw_ref[q0 + l], 0.0)

        qv = jax.lax.fori_loop(0, lq, one_term, jnp.zeros((ROWS, tmax), jnp.float32))
        prod_ref[sl, :] = qv * weights_ref[sl, :].astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, bd // ROWS, row_group, 0)
    return tree_sum_last(prod_ref[...])[None, :]


def _score_kernel_batched(qt_ref, qw_ref, dt_ref, dw_ref, out_ref, prod_ref, *, lq: int):
    q0 = pl.program_id(0) * lq
    out_ref[...] = score_doc_rows(dt_ref, dw_ref, qt_ref, qw_ref, q0, lq, prod_ref)


def sparse_score_batched_kernel(
    doc_terms: jax.Array,  # i32[B, N, Tmax]
    doc_weights: jax.Array,  # f32[B, N, Tmax]
    q_terms: jax.Array,  # i32[B, Lq]
    q_weights: jax.Array,  # f32[B, Lq] (0 on pad slots)
    *,
    block_d: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Per-query document scores for a whole batch: grid over (query, block).

    Each query scores its OWN doc tile (the DAAT phase-2 chunks differ per
    query); the query rows ride in SMEM via scalar prefetch, so the batch is
    one launch. Returns f32[B, N].
    """
    b, n, tmax = doc_terms.shape
    assert n % block_d == 0 and block_d % ROWS == 0, (n, block_d)
    lq = q_terms.shape[-1]
    doc = pl.BlockSpec((None, block_d, tmax), lambda q, i, *_: (q, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n // block_d),
        in_specs=[doc, doc],
        out_specs=pl.BlockSpec((None, 1, block_d), lambda q, i, *_: (q, 0, i)),
        scratch_shapes=[pltpu.VMEM((block_d, tmax), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_score_kernel_batched, lq=lq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        interpret=interpret,
    )(
        q_terms.astype(jnp.int32).reshape(-1),
        q_weights.astype(jnp.float32).reshape(-1),
        doc_terms,
        doc_weights,
    )
    return out[:, 0, :]
