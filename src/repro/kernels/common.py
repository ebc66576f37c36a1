"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling). On the CPU
backend — the ``JAX_PLATFORMS=cpu`` test setup — they run in interpret mode;
on any other non-TPU backend ``interpret_default()`` refuses, so a serving
process can never fall back to the interpreter without saying so.

Bit-reproducible sums
---------------------
The engines promise doc ids that are identical across every mode, which on
quantized impacts (many exact score ties) requires identical f32 *scores*.
XLA and Mosaic both leave the association order of a reduction or a
duplicate-index scatter-add unspecified, so every score sum in the engines
goes through one of two helpers built only from elementwise adds, whose
order is fixed by construction and therefore the same in XLA and in a
kernel, on the CPU and on the chip:

* :func:`tree_sum_last` — pairwise halving over the last axis (the DAAT
  doc-major scorer's sum over a document's term slots);
* :func:`canonical_doc_sums` — a segmented doubling scan over doc-sorted
  postings (the SAAT accumulator: after it, each document carries its whole
  score on exactly one posting, so any scatter of the result is exact).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    """Interpret kernels on the CPU backend only; compile them on a TPU.

    Any other backend has no Pallas TPU lowering and no business running the
    interpreter behind a caller's back, so it raises instead.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend: they "
        "compile on a TPU and are interpreted only on the CPU backend "
        "(JAX_PLATFORMS=cpu); pass interpret= explicitly to override"
    )


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pad_axis(x: jax.Array, axis: int, multiple: int, fill=0) -> jax.Array:
    """Pad one axis up to a multiple (TPU tile alignment)."""
    n = x.shape[axis]
    target = round_up(n, multiple)
    if target == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads, constant_values=fill)


def tree_sum_last(x: jax.Array) -> jax.Array:
    """Sum over the last axis by pairwise halving: ``x[..., :h] + x[..., h:]``.

    The sum is that of the width zero-padded to a power of two (adding
    ``+0.0`` is exact), but the padding is never built: the width splits
    into chunks of its largest power-of-two divisor (128 lanes for a
    doc-major store), and chunk ``i`` adds chunk ``i + h`` only where that
    chunk exists. Only elementwise adds of static, chunk-aligned slices are
    used, so the result is bit-identical wherever it is evaluated — inside a
    Pallas kernel or in XLA, on any backend. Returns ``x.shape[:-1]``.
    """
    w = x.shape[-1]
    g = w & -w
    chunks = [x[..., i * g : (i + 1) * g] for i in range(w // g)]
    while len(chunks) > 1:
        h = next_pow2(len(chunks)) // 2
        chunks = [c + chunks[i + h] if i + h < len(chunks) else c for i, c in enumerate(chunks[:h])]
    x = chunks[0]
    while x.shape[-1] > 2:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    # the last pair: any reduction order of two values is a + b
    return jnp.sum(x, axis=-1) if x.shape[-1] == 2 else x[..., 0]


def canonical_doc_sums(
    doc_ids: jax.Array, contribs: jax.Array, max_run: int
) -> tuple[jax.Array, jax.Array]:
    """Per-document score sums over postings ``[..., P]``, in a fixed order.

    One stable sort of a packed ``(doc, is-zero)`` key groups each
    document's nonzero contributions (input order kept) ahead of its zero
    ones — the key carries its iota as the only payload and the
    contributions follow by one gather, the form the TPU compiler sorts
    fastest (a third sorted operand doubles the compile time); a segmented
    doubling scan (``ceil(log2(max_run))`` rounds of shifted elementwise
    adds, never crossing a group) then leaves each group's total on its LAST
    posting and zeroes every other posting. Zero contributions are no-ops in
    any sum, so grouping them apart changes no score — it only keeps padding
    (which can be arbitrarily long) out of the runs ``max_run`` must bound.
    ``max_run`` bounds the nonzero postings one document can have in a row:
    the engines pass the query width, since a document appears at most once
    per query slot.

    Returns ``(docs, sums)`` sorted by doc id. Each document has at most one
    nonzero sum, so the dense accumulator that a scatter, a one-hot kernel
    or a top-k builds from them is the same bits everywhere.
    """
    s = contribs.astype(jnp.float32)
    key = doc_ids.astype(jnp.int32) * 2 + (s == 0).astype(jnp.int32)
    axis = key.ndim - 1
    n = key.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, key.shape, axis)
    key, perm = jax.lax.sort((key, iota), dimension=-1, num_keys=1, is_stable=True)
    s = jnp.take_along_axis(s, perm, axis=-1)
    docs = key // 2
    shift = 1
    while shift < max(int(max_run), 1) and shift < n:
        pad = [(0, 0)] * axis + [(shift, 0)]
        prev_k = jnp.pad(key, pad, constant_values=-1)[..., :n]
        prev_s = jnp.pad(s, pad)[..., :n]
        s = s + jnp.where(prev_k == key, prev_s, 0.0)
        shift *= 2
    nxt = jnp.pad(key, [(0, 0)] * axis + [(0, 1)], constant_values=-1)[..., 1:]
    return docs, jnp.where(nxt != key, s, 0.0)


def sorted_posting_tiles(
    doc_ids: jax.Array,
    contribs: jax.Array,
    tile_p: int,
    max_run: int | None = None,
) -> tuple[jax.Array, jax.Array, int]:
    """Shared preprocessing for the scatter-family kernels.

    The canonical per-document sums of :func:`canonical_doc_sums` (one
    stable sort by doc id, then a fixed-order fold), then padding to the
    posting-tile multiple (pad postings repeat the row's largest doc id with
    a ``+0.0`` contribution, so rows stay sorted and the last tile spans no
    extra accumulator blocks). Handles both the single-query ``[P]`` and batched
    ``[B, P]`` layouts.

    Returns ``(docs, sums, n_tiles)``.
    """
    run = doc_ids.shape[-1] if max_run is None else max_run
    docs, c = canonical_doc_sums(doc_ids, contribs, run)
    n = docs.shape[-1]
    pad = round_up(max(n, 1), tile_p) - n
    if pad:
        last = jnp.broadcast_to(docs[..., -1:], docs.shape[:-1] + (pad,))
        docs = jnp.concatenate([docs, last], axis=-1)
        c = jnp.concatenate([c, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)
    return docs, c, docs.shape[-1] // tile_p
