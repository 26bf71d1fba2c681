"""Production mesh + parameter/activation sharding rules.

Single pod:  (16, 16)    -> ("data", "model")   = 256 chips (TPU v5e-256)
Multi-pod:   (2, 16, 16) -> ("pod", "data", "model") = 512 chips

Sharding strategy (DESIGN.md §5):
- batch over ("pod","data"); TP over "model"
- every large weight is 2D-sharded: its TP dim over "model" AND another dim
  over ("pod","data") (hybrid FSDP — required: mixtral-8x22b weights alone
  exceed per-replica HBM otherwise). XLA inserts the per-layer FSDP
  all-gathers, overlapped by the latency-hiding scheduler.
- embeddings P("model", None): the vocab axis over TP enables the
  distributed amortized head (models/head.py).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_train_mesh(dp: int = 1, tp: int = 1):
    """Explicit small-scale train mesh: ``("data", "model") = (dp, tp)``
    over the first ``dp*tp`` devices (the production helper above assumes a
    full pod). The data axis is pure batch parallelism: the ShardedIndex
    spans the model axis only — its leaf specs are ``P("model", ...)``, so
    its state replicates over "data" automatically and ``dp`` scales batch
    throughput without touching index placement or refresh programs."""
    n = dp * tp
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"mesh ({dp},{tp}) needs {n} devices, have {len(devs)} (CPU: "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n})"
        )
    return jax.sharding.Mesh(
        np.asarray(devs[:n]).reshape(dp, tp), ("data", "model"),
        axis_types=(AxisType.Auto, AxisType.Auto),
    )


def fsdp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# MoE expert-weight placement: "ep" (DEFAULT, §Perf iter 4) shards the
# EXPERT dim over "model" when divisible — the dispatch buffer shards
# E-wise and the memory term drops 26% on qwen3; "tp" shards the expert
# FFN hidden over "model" (used automatically when E doesn't divide the
# model axis, e.g. mixtral's 8 experts on 16 shards).
MOE_SHARDING = "ep"


def _dim_ok(dim: int, mesh, axes) -> bool:
    size = 1
    for a in axes if isinstance(axes, tuple) else (axes,):
        size *= mesh.shape[a]
    return dim % size == 0 and dim >= size


def param_spec(path_keys: list[str], shape: tuple[int, ...], mesh, cfg) -> P:
    """PartitionSpec for one parameter, identified by its pytree path.

    Stacked layer params carry a leading scan dim (never sharded). Small
    vectors replicate. Matrices: TP dim over "model", FSDP dim over
    ("pod","data") where divisible.
    """
    fa = fsdp_axes(mesh)
    name = path_keys[-1]
    if name in ("embed", "out_embed"):
        return P("model", None)
    if len(shape) <= 2 or name in ("conv",):
        return P(*([None] * len(shape)))  # norms, gates biases, convs: tiny

    lead = [None] * (len(shape) - 2)  # scan/stack dims
    d_in, d_out = shape[-2], shape[-1]

    # MoE expert weights (L, E, in, out): optional expert parallelism
    if (
        MOE_SHARDING == "ep"
        and name in ("w1", "w2", "w3")
        and len(shape) == 4
        and _dim_ok(shape[1], mesh, "model")
    ):
        in_ax = fa if (fa and _dim_ok(d_in, mesh, fa)) else None
        return P(None, "model", in_ax, None)

    tp_out = {"wq", "wk", "wv", "w1", "w3", "wx", "wz", "w_gate_branch",
              "w_in", "wdt", "wb", "wc", "w_a", "w_i"}
    tp_in = {"wo", "w2", "w_out"}
    if name in tp_out:
        out_ax = "model" if _dim_ok(d_out, mesh, "model") else None
        in_ax = fa if (fa and _dim_ok(d_in, mesh, fa)) else None
        return P(*lead, in_ax, out_ax)
    if name in tp_in:
        in_ax = "model" if _dim_ok(d_in, mesh, "model") else None
        out_ax = fa if (fa and _dim_ok(d_out, mesh, fa)) else None
        return P(*lead, in_ax, out_ax)
    if name == "router":
        in_ax = fa if (fa and _dim_ok(d_in, mesh, fa)) else None
        return P(*lead, in_ax, None)
    return P(*([None] * len(shape)))


def param_shardings(params_shapes: Any, mesh, cfg) -> Any:
    """Pytree of NamedShardings matching a params (shape) pytree."""

    def one(path, leaf):
        keys = [str(getattr(p, "key", p)) for p in path]
        return NamedSharding(mesh, param_spec(keys, leaf.shape, mesh, cfg))

    return jax.tree_util.tree_map_with_path(one, params_shapes)


def batch_spec(mesh) -> P:
    fa = fsdp_axes(mesh)
    return P(fa if fa else None)


def data_shardings(batch_shapes: Any, mesh) -> Any:
    """Batch arrays: leading (global-batch) dim over ("pod","data")."""
    fa = fsdp_axes(mesh)

    def one(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        bdim = leaf.shape[0]
        ax = fa if (fa and _dim_ok(bdim, mesh, fa)) else None
        return NamedSharding(mesh, P(ax, *([None] * (leaf.ndim - 1))))

    return jax.tree.map(one, batch_shapes)


def stacked_data_shardings(batch_shapes: Any, mesh) -> Any:
    """Fused-loop batches ``(T, global_batch, ...)``: the leading dim is
    the lax.scan axis (never sharded); the global-batch dim (axis 1) shards
    over ("pod","data") — the data-parallel training axis."""
    fa = fsdp_axes(mesh)

    def one(leaf):
        if leaf.ndim <= 1:
            return NamedSharding(mesh, P())
        ax = fa if (fa and _dim_ok(leaf.shape[1], mesh, fa)) else None
        return NamedSharding(mesh, P(None, ax, *([None] * (leaf.ndim - 2))))

    return jax.tree.map(one, batch_shapes)


def cache_shardings(cache_shapes: Any, mesh, cfg, paged: bool = False) -> Any:
    """KV/state caches: batch dim over ("pod","data") when divisible; the
    head/width dim over "model" when divisible (decode TP).

    ``paged``: the k/v leaves are the shared block pool ``(layers,
    n_blocks, KV, block_len, hd)`` — axis 1 is a *physical block id*, not
    a batch dim, and page-table gathers index it from every data row, so
    it must stay replicated over ("pod","data") (only KV-head TP applies).
    """
    fa = fsdp_axes(mesh)

    def one(path, leaf):
        keys = [str(getattr(p, "key", p)) for p in path]
        shape = leaf.shape  # leading dim = layer stack
        spec = [None] * len(shape)
        name = keys[-1]
        pool_leaf = paged and name in ("k", "v") and len(shape) == 5
        if len(shape) >= 2 and not pool_leaf:
            if fa and _dim_ok(shape[1], mesh, fa):
                spec[1] = fa  # batch
        if name in ("k", "v") and len(shape) == 5:
            # head-major dense (layers, B, KV, S, hd) / pool (layers, nb,
            # KV, bl, hd): prefer KV-head TP, else (dense only) seq TP
            if _dim_ok(shape[2], mesh, "model"):
                spec[2] = "model"
            elif not pool_leaf and _dim_ok(shape[3], mesh, "model"):
                spec[3] = "model"
        elif name == "state" and len(shape) >= 3:
            if _dim_ok(shape[2], mesh, "model"):
                spec[2] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache_shapes)
