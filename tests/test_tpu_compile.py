"""Compile the engine-reachable Pallas kernels for a described TPU v5e.

Interpret mode accepts kernels that the TPU's compiler refuses (tiles not
aligned to the (8, 128) layout, primitives Mosaic cannot lower, more VMEM
than a core has). These tests compile every kernel the engines can reach
with ``interpret=False`` for a v5e that is described, not attached, at the
widths ``chip_smoke.py`` serves: a batch of 32 queries against a
262,144-passage index (``repro.launch.smoke.DEPLOYMENT``). Nothing runs;
the compiled executable must contain the Mosaic kernel (``tpu_custom_call``).
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
