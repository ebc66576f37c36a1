"""Compile the engine-reachable Pallas kernels for a described TPU v5e.

Interpret mode accepts kernels that the TPU's compiler refuses (tiles not
aligned to the (8, 128) layout, primitives Mosaic cannot lower, more VMEM
than a core has). These tests compile every kernel the engines can reach
with ``interpret=False`` for a v5e that is described, not attached, at the
widths ``chip_smoke.py`` serves: a batch of 32 queries against a
262,144-passage index (``repro.launch.smoke.DEPLOYMENT``). Nothing runs;
the compiled executable must contain the Mosaic kernel (``tpu_custom_call``).
One case compiles the whole batched SAAT step and reads its gathers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.launch.smoke import DEPLOYMENT as D

pytestmark = pytest.mark.kernels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to the temp dir
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


i32, f32 = jnp.int32, jnp.float32
B = D["batch"]


@pytest.mark.parametrize("k", D["ks"])
def test_impact_scatter_topk_compiles(one_chip, k):
    from repro.kernels.impact_scatter_topk.ops import impact_scatter_topk_batched

    n_docs, rho = D["n_docs"], D["rho"]
    fn = lambda d, c, live: impact_scatter_topk_batched(  # noqa: E731
        d, c, n_docs, k, n_live=n_docs - 7, live=live, max_run=D["lq"], interpret=False
    )
    _compile(fn, ((B, rho), i32), ((B, rho), f32), ((n_docs,), i32), sharding=one_chip)


def test_impact_scatter_compiles(one_chip):
    from repro.kernels.impact_scatter.ops import impact_scatter_batched

    n_docs, rho = D["n_docs"], D["rho"]
    fn = lambda d, c: impact_scatter_batched(  # noqa: E731
        d, c, n_docs, max_run=D["lq"], interpret=False
    )
    _compile(fn, ((B, rho), i32), ((B, rho), f32), sharding=one_chip)


@pytest.mark.parametrize("k", [D["est_blocks"], D["block_budget"]])
def test_block_topk_compiles(one_chip, k):
    from repro.kernels.block_topk.ops import block_topk_batched

    fn = lambda s: block_topk_batched(s, k, interpret=False)  # noqa: E731
    _compile(fn, ((B, D["n_blocks"]), f32), sharding=one_chip)


@pytest.mark.parametrize("tmax", D["tmax"])
def test_sparse_score_compiles(one_chip, tmax):
    from repro.kernels.sparse_score.ops import sparse_score_batched

    n = D["est_blocks"] * D["block_size"]
    fn = lambda dt, dw, qt, qw: sparse_score_batched(dt, dw, qt, qw, interpret=False)  # noqa: E731
    _compile(
        fn, ((B, n, tmax), i32), ((B, n, tmax), f32), ((B, D["lq"]), i32),
        ((B, D["lq"]), f32), sharding=one_chip,
    )


def test_block_prune_csr_compiles(one_chip):
    from repro.kernels.block_prune_csr.ops import block_prune_csr_batched

    nb, lq = D["n_blocks"], D["lq"]
    fn = lambda blk, w, base, cnt, qw, th: block_prune_csr_batched(  # noqa: E731
        blk, w, base, cnt, qw, th, n_blocks=nb, max_bm_per_term=nb, interpret=False
    )
    _compile(
        fn, ((D["n_bm"],), i32), ((D["n_bm"],), f32), ((B, lq), i32), ((B, lq), i32),
        ((B, lq), f32), ((B,), f32), sharding=one_chip,
    )


@pytest.mark.parametrize("tmax", D["tmax"])
@pytest.mark.parametrize("k", D["ks"])
@pytest.mark.parametrize("trips", [1, D["trips_per_launch"]])
def test_chunk_step_compiles(one_chip, k, trips, tmax):
    from repro.kernels.chunk_step.ops import chunk_step_multi_batched

    nb, bs, lq = D["n_blocks"], D["block_size"], D["lq"]
    fn = lambda *a: chunk_step_multi_batched(  # noqa: E731
        *a[:-1], live=a[-1], trips_per_launch=trips, block_budget=D["block_budget"],
        block_size=bs, n_live=D["n_docs"], interpret=False,
    )
    _compile(
        fn,
        ((D["n_docs"], tmax), i32), ((D["n_docs"], tmax), f32),
        ((B, lq), i32), ((B, lq), f32),
        ((B, nb), f32), ((B, nb), jnp.bool_),
        ((B, k), f32), ((B, k), i32), ((B,), f32), ((B,), i32),
        ((D["n_docs"],), i32),
        sharding=one_chip,
    )


def _gathers(hlo_text: str) -> list[tuple[str, int, str, str]]:
    """(result dtype, result elements, operand type, op_name) of every gather
    in a compiled module, fusion bodies included."""
    import math
    import re

    types = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo_text))
    out = []
    for m in re.finditer(
        r"= (\w+)\[([\d,]*)\]\S* gather\(%([\w.\-]+),.*?(?:op_name=\"([^\"]*)\"|$)",
        hlo_text, re.M,
    ):
        n = math.prod(int(d) for d in m.group(2).split(",") if d)
        out.append((m.group(1), n, types.get(m.group(3), "?"), m.group(4) or ""))
    return out


def test_saat_step_reads_each_slot_once(one_chip, monkeypatch):
    """The batched SAAT step at deployment widths expands its plan over the
    ``B x rho`` posting slots by a scatter and a prefix sum: outside the
    select, the only gather of ``B x rho`` elements is the doc-id read of the
    posting store, under ``saat.gather``. Per-slot gathers of the plan's
    tables (``take_along_axis``) cost as much a slot as the posting read."""
    import importlib

    from repro.core.impact_index import ImpactIndex
    from repro.core.saat import saat_search

    # the engine asks the backend, which is the CPU here, whether to interpret
    fused_ops = importlib.import_module("repro.kernels.impact_scatter_topk.ops")
    monkeypatch.setattr(fused_ops, "interpret_default", lambda: False)
    n_docs, lq, rho, tmax = D["n_docs"], D["lq"], D["rho"], D["tmax"][-1]
    n_post = n_docs * 230  # SPLADEv2 passages hold about 230 terms
    n_seg, vocab, nb = n_post // 10, 30522, D["n_blocks"]
    s = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=one_chip)  # noqa: E731
    index = ImpactIndex(
        doc_ids=s((n_post,), i32), seg_term=s((n_seg,), i32), seg_weight=s((n_seg,), f32),
        seg_start=s((n_seg,), i32), seg_len=s((n_seg,), i32),
        term_seg_start=s((vocab + 1,), i32), term_seg_count=s((vocab + 1,), i32),
        term_post_count=s((vocab + 1,), i32), term_max_weight=s((vocab + 1,), f32),
        bm_block=s((D["n_bm"],), i32), bm_weight=s((D["n_bm"],), f32),
        term_bm_start=s((vocab + 1,), i32), term_bm_count=s((vocab + 1,), i32),
        doc_terms=s((n_docs, tmax), i32), doc_weights=s((n_docs, tmax), f32),
        doc_n_terms=s((n_docs,), i32), doc_weight_sum=s((n_docs,), f32),
        n_docs=n_docs, n_terms=vocab, n_blocks=nb, block_size=D["block_size"],
        max_doc_terms=tmax, scale=1.0, bits=8, max_segs=255, max_bm=nb,
    )
    text = saat_search.lower(
        index, s((B, lq), i32), s((B, lq), f32), k=D["ks"][0], rho=rho,
        max_segs_per_term=255, fused_topk=True,
    ).compile().as_text()
    assert "tpu_custom_call" in text
    slot_reads = [
        g for g in _gathers(text) if g[1] == B * rho and "saat.select" not in g[3].split("/")
    ]
    assert len(slot_reads) == 1, slot_reads
    dtype, _, operand, name = slot_reads[0]
    assert (dtype, operand) == ("s32", f"s32[{n_post}]")
    assert "saat.gather" in name.split("/") and "take_along_axis" not in name
