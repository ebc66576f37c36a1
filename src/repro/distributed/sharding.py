"""Per-arch PartitionSpec rules (DP / TP / EP / sequence / doc sharding).

One rule table maps param-leaf paths to logical layouts; logical layouts map
to mesh axes for whichever mesh is in play — so the same model code serves
the single-pod ``(data=16, model=16)`` and the multi-pod
``(pod=2, data=16, model=16)`` meshes (the ``pod`` axis joins the
data-parallel group).

Layout conventions (MaxText-style ZeRO/TP hybrid):
  * 2D weights: one dim over ``model`` (tensor parallel), the other over the
    data axes (FSDP-style param/optimizer-state sharding — this is what lets
    a 34B model's AdamW moments fit 256 chips);
  * column-parallel in (wq/wk/wv/w_gate/w_up/unembed: out-dim over model),
    row-parallel out (wo/w_down: in-dim over model) — the classic Megatron
    pairing that keeps activations model-sharded through the block with one
    reduce per projection pair;
  * MoE experts: expert axis over ``model`` (expert parallelism); dispatch
    becomes GSPMD all-to-all;
  * embeddings: vocab/row axis over ``model`` (vocab- / row-sharded tables;
    recsys tables are exactly the classic row-sharded EmbeddingBag);
  * KV caches: sequence axis over ``model`` (decode attention reduces over
    the cache; GSPMD inserts the score psum) — batch over data;
  * GNN: node/edge arrays over ALL axes flattened (the edge work dominates);
  * retrieval: documents/candidates over ``model``, queries over data —
    per-shard top-k + k-sized all-gather merge (repro.core.topk).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Axes:
    """Resolved mesh axis names."""

    data: tuple[str, ...]  # all data-parallel axes ("pod" folds in here)
    model: str = "model"

    @property
    def all(self) -> tuple[str, ...]:
        return self.data + (self.model,)


def mesh_axes(mesh: Mesh) -> Axes:
    names = mesh.axis_names
    data = tuple(n for n in names if n != "model")
    return Axes(data=data)


def _right_align(spec_tail: tuple, ndim: int) -> P:
    """Pad a trailing-dims spec with None for any leading (stack) axes."""
    pad = ndim - len(spec_tail)
    return P(*((None,) * pad + tuple(spec_tail)))


# --------------------------------------------------------------------------
# rule tables: (regex on key path, trailing-dims logical spec)
# logical tokens: "model" | "data" | None
# --------------------------------------------------------------------------

# Each rule maps a path regex to a list of candidate trailing-dim layouts,
# in preference order; the first candidate whose sharded dims are all
# divisible by their axis sizes wins (jit *input* shardings must divide
# evenly — internal constraints may be uneven, inputs may not). Non-divisible
# dims inside the winning candidate degrade to None individually.
LM_RULES: list[tuple[str, list]] = [
    # vocab over model ONLY: sharding D (the logits contraction dim) over
    # data makes SPMD emit a [tokens, vocab]-sized partial-sum all-reduce
    # per loss chunk — measured 62 GB/step on gemma3 (EXPERIMENTS.md §Perf)
    (r"embed$", [("model", None)]),  # [V, D] vocab-sharded
    (r"unembed$", [(None, "model")]),  # [D, V]
    (r"(wq|wk|wv)$", [("data", "model")]),  # column-parallel
    (r"wo$", [("model", "data")]),  # row-parallel
    # MoE (before the dense FFN rules): prefer EP on the expert axis; if E
    # doesn't divide the model axis (granite: 40 experts / 16 chips), fall
    # back to TP on the expert-ff dim
    (r"moe.*(w_gate|w_up)$", [("model", "data", None), (None, "data", "model")]),
    (r"moe.*w_down$", [("model", None, "data"), (None, "model", "data")]),
    (r"(w_gate|w_up)$", [("data", "model")]),
    (r"w_down$", [("model", "data")]),
    (r"router$", [("data", None)]),
    (r"(scale|bias)$", [()]),  # norms replicated
    (r"pos_embed$", [()]),
]

GNN_RULES: list[tuple[str, list]] = [
    (r"w1$", [(None, "model")]),
    (r"w2$", [("model", None)]),
    (r"(b1|b2)$", [()]),
]

RECSYS_RULES: list[tuple[str, list]] = [
    # rows over EVERY axis: the table grad scatter + AdamW moments then shard
    # 256/512-ways (a model-only sharded 2B-row table's dense grad would blow
    # HBM); falls back to model-only for tiny test tables
    (r"table$", [("all", None), ("model", None), ()]),
    (r"wide$", [("all",), ("model",), ()]),  # row-sharded linear weights
    (r"pos_embed$", [()]),
    (r"(wq|wk|wv)$", [(None, "model")]),
    (r"wo$", [("model", None)]),
    (r"\.w$", [("data", "model"), (None, "model"), ()]),  # MLP / cross weights
    (r"\.b$", [()]),
    (r"(scale|bias)$", [()]),
]

RULES_BY_FAMILY = {"lm": LM_RULES, "gnn": GNN_RULES, "recsys": RECSYS_RULES}


def _resolve(token, axes: Axes):
    if token == "model":
        return axes.model
    if token == "data":
        return axes.data if len(axes.data) > 1 else axes.data[0]
    if token == "all":
        return axes.data + (axes.model,)
    return None


def _axis_size(token, axes: Axes, mesh_shape: dict) -> int:
    if token == "model":
        return mesh_shape[axes.model]
    if token == "data":
        n = 1
        for a in axes.data:
            n *= mesh_shape[a]
        return n
    if token == "all":
        n = 1
        for a in axes.data + (axes.model,):
            n *= mesh_shape[a]
        return n
    return 1


def _fits(tail: tuple, shape: tuple, axes: Axes, mesh_shape: dict) -> bool:
    off = len(shape) - len(tail)
    return all(
        shape[off + i] % _axis_size(t, axes, mesh_shape) == 0 for i, t in enumerate(tail)
    )


# Leaves smaller than this keep TP ('model') sharding but drop the
# FSDP/ZeRO 'data' dim: for small weights the all-gather/partial-reduce
# traffic SPMD emits outweighs the memory saved (measured: 62 GB/step of
# all-reduce on gemma3 train_4k before this guard). Large weights (yi-34b
# 7168x7168 = 205 MB) keep both axes — there ZeRO is what makes the
# optimizer state fit at all.
FSDP_MIN_BYTES = 32 * 1024 * 1024


def spec_for_path(
    path: str, shape: tuple, rules, axes: Axes, mesh_shape: dict, nbytes: int | None = None
) -> P:
    ndim = len(shape)
    for pat, candidates in rules:
        if not re.search(pat, path):
            continue
        usable = [c for c in candidates if len(c) <= ndim]
        if not usable:
            return P()
        tail = next((c for c in usable if _fits(c, shape, axes, mesh_shape)), None)
        if tail is None:  # best candidate, degrading non-divisible dims
            tail = usable[0]
            off = ndim - len(tail)
            tail = tuple(
                t if shape[off + i] % _axis_size(t, axes, mesh_shape) == 0 else None
                for i, t in enumerate(tail)
            )
        if nbytes is not None and nbytes < FSDP_MIN_BYTES:
            tail = tuple(None if t == "data" else t for t in tail)
        return _right_align(tuple(_resolve(t, axes) for t in tail), ndim)
    return P()  # default: replicated


def normalize_path(keystr_path: str) -> str:
    """``['blocks'][0]['attn']['wq']`` -> ``.blocks.0.attn.wq``."""
    return keystr_path.replace("'", "").replace("[", ".").replace("]", "")


def param_specs(params, family: str, mesh: Mesh):
    """PartitionSpec pytree mirroring ``params`` (works on abstract trees)."""
    axes = mesh_axes(mesh)
    rules = RULES_BY_FAMILY[family]
    mesh_shape = dict(mesh.shape)

    def one(path, leaf):
        import numpy as np

        key = normalize_path(jax.tree_util.keystr(path))
        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        return spec_for_path(key, tuple(leaf.shape), rules, axes, mesh_shape, nbytes)

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params, family: str, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(params, family, mesh)
    )


# --------------------------------------------------------------------------
# batch / cache / state shardings
# --------------------------------------------------------------------------


def batch_dim_sharding(mesh: Mesh, extra_dims: int = 1) -> NamedSharding:
    """Leading dim over all data axes, rest replicated: [B, ...]."""
    axes = mesh_axes(mesh)
    return NamedSharding(mesh, P(_resolve("data", axes), *((None,) * extra_dims)))


def fully_sharded_dim(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    """Leading dim over ALL mesh axes (GNN edges, retrieval candidates)."""
    axes = mesh_axes(mesh)
    flat = axes.data + (axes.model,)
    return NamedSharding(mesh, P(flat, *((None,) * extra_dims)))


def batch_shardings(batch_specs: dict, mesh: Mesh, *, fully_shard: bool = False):
    """Shard every batch array on its leading dim (data axes, or all axes)."""

    def one(leaf):
        fn = fully_sharded_dim if fully_shard else batch_dim_sharding
        return fn(mesh, max(len(leaf.shape) - 1, 0))

    return jax.tree.map(one, batch_specs)


def cache_shardings(cache_specs, mesh: Mesh):
    """KV cache: k/v [(R,) B, T, K, hd] -> batch over data, seq over model.

    Per-dim divisibility fallback (batch=1 long-context decode cannot shard
    its batch dim; 1k-slot ring buffers shard T only when it divides).
    """
    axes = mesh_axes(mesh)
    mesh_shape = dict(mesh.shape)

    def one(path, leaf):
        nd = len(leaf.shape)
        key = jax.tree_util.keystr(path)
        tail_tok = ("data", "model") if key.endswith("['pos']") else ("data", "model", None, None)
        off = nd - len(tail_tok)
        tok = tuple(
            t if t is None or leaf.shape[off + i] % _axis_size(t, axes, mesh_shape) == 0 else None
            for i, t in enumerate(tail_tok)
        )
        tail = tuple(_resolve(t, axes) for t in tok)
        return NamedSharding(mesh, _right_align(tail, nd))

    return jax.tree_util.tree_map_with_path(one, cache_specs)


def train_state_shardings(abstract_state, family: str, mesh: Mesh):
    """TrainState shardings: opt moments mirror the param specs (ZeRO)."""
    from repro.train.trainer import TrainState
    from repro.train.optim import AdamWState

    p_shard = param_shardings(abstract_state.params, family, mesh)
    return TrainState(
        params=p_shard,
        opt=AdamWState(
            m=jax.tree.map(lambda s: s, p_shard),
            v=jax.tree.map(lambda s: s, p_shard),
            count=NamedSharding(mesh, P()),
        ),
        step=NamedSharding(mesh, P()),
    )


def constraint(x, mesh: Optional[Mesh], *spec):
    """with_sharding_constraint that no-ops without a mesh (CPU tests)."""
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# --------------------------------------------------------------------------
# ambient-mesh activation constraints (model code calls these; they no-op
# outside a `jax.set_mesh(...)` scope, so CPU unit tests are unaffected)
# --------------------------------------------------------------------------


def _get_abstract_mesh():
    """The ambient mesh set by ``jax.set_mesh(...)``, or ``None`` outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def current_axes() -> Optional[Axes]:
    m = _get_abstract_mesh()
    if m is None or m.empty or not m.axis_names:
        return None
    data = tuple(n for n in m.axis_names if n != "model")
    model = "model" if "model" in m.axis_names else None
    if model is None:
        return None
    return Axes(data=data, model=model)


def ambient_axis_size(token: str) -> int:
    """Size of a logical axis group under the ambient mesh (1 if none)."""
    m = _get_abstract_mesh()
    axes = current_axes()
    if axes is None:
        return 1
    shape = dict(m.shape)
    names = {"model": (axes.model,), "data": axes.data, "all": axes.data + (axes.model,)}[token]
    n = 1
    for a in names:
        n *= shape[a]
    return n


def act(x, *logical):
    """Constrain an activation by logical dim tokens.

    Tokens: ``"data"`` (all data axes), ``"model"``, ``"all"`` (every axis,
    flattened — GNN edge/node arrays), or None. No-op without an ambient
    mesh. Dims not divisible by their axis-group size are silently dropped
    (padded/uneven constraints trigger SPMD's involuntary-full-remat path).
    """
    axes = current_axes()
    if axes is None:
        return x

    def tok(t, dim):
        if t is None:
            return None
        if t == "all" and dim % max(ambient_axis_size("all"), 1) != 0:
            t = "data"  # degrade: 1M candidates shard 16-way, not 256-way
        if dim % max(ambient_axis_size(t), 1) != 0:
            return None
        if t == "data":
            return axes.data if len(axes.data) > 1 else axes.data[0]
        if t == "model":
            return axes.model
        if t == "all":
            return axes.data + (axes.model,)
        return None

    spec = tuple(tok(t, d) for t, d in zip(logical, x.shape))
    return jax.lax.with_sharding_constraint(x, P(*spec))
