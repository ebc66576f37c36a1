"""jit'd wrapper: two-stage top-k (Pallas per-tile select + finalist merge)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import KernelContract, ShapeCase
from repro.core.topk import topk
from repro.kernels.block_topk.kernel import block_topk_batched_kernel
from repro.kernels.common import interpret_default, pad_axis
from repro.kernels.impact_scatter_topk.kernel import ROWS


@partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def block_topk_batched(
    scores: jax.Array,
    k: int,
    *,
    tile: int = 8192,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Exact per-row top-k over ``scores [B, n]``. Returns ``([B, k], [B, k])``.

    One (row group, tile)-gridded kernel launch for stage 1, then one batched
    finalist merge — no per-query vmapped programs. Among equal scores the
    lower index comes first (:func:`repro.core.topk.topk`).
    """
    if interpret is None:
        interpret = interpret_default()
    b, n = scores.shape
    tile = min(tile, max(128, -(-n // 128) * 128))
    k_eff = min(k, n)
    s = pad_axis(scores.astype(jnp.float32), 1, tile, fill=-jnp.inf)
    s = pad_axis(s, 0, ROWS, fill=-jnp.inf)
    k_tile = min(max(k_eff, 1), tile)
    ts, ti = block_topk_batched_kernel(s, k=k_tile, tile=tile, interpret=interpret)
    # [n_tiles, B, k] -> [B, n_tiles * k]: tile-major finalists keep the
    # lower-index-first tie order through the merge
    ts = jnp.moveaxis(ts, 0, 1)[:b].reshape(b, -1)
    ti = jnp.moveaxis(ti, 0, 1)[:b].reshape(b, -1)
    fs, fi = topk(ts, k_eff)
    ids = jnp.take_along_axis(ti, fi, axis=-1)
    if k_eff < k:  # pad to requested k for shape stability
        fs = jnp.concatenate([fs, jnp.full((b, k - k_eff), -jnp.inf, fs.dtype)], axis=-1)
        ids = jnp.concatenate([ids, jnp.zeros((b, k - k_eff), ids.dtype)], axis=-1)
    return fs, ids


@partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def block_topk(
    scores: jax.Array,
    k: int,
    *,
    tile: int = 8192,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Exact top-k over a 1-D score vector of any length: the batched op at B = 1."""
    s, i = block_topk_batched(scores[None], k, tile=tile, interpret=interpret)
    return s[0], i[0]


def _contract_call(dims):
    """Trace target for the static checker: abstract inputs, sweep tiling."""
    sds = jax.ShapeDtypeStruct
    kw = dict(k=dims["k"], tile=dims["tile"], interpret=True)
    if "batch" in dims:
        return partial(block_topk_batched, **kw), (
            sds((dims["batch"], dims["n"]), jnp.float32),)
    return partial(block_topk, **kw), (sds((dims["n"],), jnp.float32),)


# Single source of truth for the sweep shapes in tests/test_kernels.py and
# the checker's trace grid: tile-ragged n, k == n, and k > tile degenerates.
CONTRACT = KernelContract(
    name="block_topk",
    description="two-stage exact top-k (per-tile select + finalist merge)",
    make_call=_contract_call,
    shape_grid=(
        ShapeCase("ragged", dict(n=1000, k=10, tile=256)),
        ShapeCase("aligned", dict(n=8192, k=100, tile=1024)),
        ShapeCase("k_is_n", dict(n=100, k=100, tile=128)),
        ShapeCase("wide_tile", dict(n=5000, k=7, tile=512)),
        ShapeCase("b1", dict(batch=1, n=1000, k=10, tile=256)),
        ShapeCase("b3_ragged", dict(batch=3, n=517, k=7, tile=128)),
        ShapeCase("b8_k_is_n", dict(batch=8, n=100, k=100, tile=128)),
    ),
)
