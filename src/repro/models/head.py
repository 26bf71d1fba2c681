"""Distributed amortized LM head (shard_map over the TP-sharded vocabulary).

The output embedding is sharded ``P("model", None)``: each TP shard owns a
contiguous vocab slice, and runs the SAME estimator core as the
single-device head (:mod:`repro.core.estimators`) over its slice — an
index-backed top-k probe (sharded :class:`repro.core.mips.ShardedIndex`,
O(√(v/mp)) per query) or the dense-local scan, the stratified Algorithm-3
partial, and the lazy-Gumbel local max. This module contributes ONLY the
shard plumbing and the O(1)-per-token collectives:

* loss:   ``log Ẑ = logsumexp over shards of local log Ẑ_s`` (a pmax+psum),
          target logit via masked psum — the stratified sum of per-shard
          Algorithm-3 estimators, still exactly unbiased (Thm 3.4 per
          shard). See :func:`repro.core.estimators.combine_loss_psum`.
          Exact mode takes the pmax of the raw scores first and psums
          the exponentials (:func:`repro.core.estimators.exact_logz`), so
          it repeats the one-device arithmetic term by term.
* sample: the global argmax of per-shard lazy-Gumbel maxima IS an exact
          global sample; exactness certificates compose via a pmin
          (:func:`repro.core.estimators.combine_sample_pmax`). Collective
          payload: one (value, id) pair per shard — O(1) per token versus
          O(|V|/mp) for a full-logit gather.

The single-device head (core/amortized_head.py) is the one-shard
instantiation of the identical partials; there is deliberately no estimator
math in this file. This is the "distributed MIPS" feature of DESIGN.md §3.5.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import estimators as est
from repro.core.amortized_head import HeadConfig

__all__ = ["dist_head_loss", "dist_head_sample", "batch_axes"]


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _token_spec(mesh, t: int):
    """Batch-dim spec for (T, ...) activations: sharded over the batch axes
    when divisible, replicated otherwise (e.g. batch=1 long-context decode)."""
    ba = batch_axes(mesh)
    size = 1
    for a in ba:
        size *= mesh.shape[a]
    return ba if (ba and t % size == 0 and t >= size) else None


def _shard_geometry(cfg: HeadConfig, vp: int, mp: int):
    v_loc = vp // mp
    k_loc = max(8, cfg.k // mp)
    l_loc = max(8, cfg.l // mp)
    return v_loc, k_loc, l_loc


def _index_args(index):
    """(extra shard_map args, matching in_specs) for an optional sharded
    index: its stacked state rides through shard_map so each shard probes
    its own slice (see ShardedIndex.local_index)."""
    if index is None:
        return (), ()
    return (index.state,), (index.state_specs(),)


def dist_head_loss(
    mesh,
    emb: jax.Array,  # (Vp, d), sharded P("model", None)
    h: jax.Array,  # (T, d), sharded P(batch_axes, None)
    targets: jax.Array,  # (T,), sharded P(batch_axes)
    key: jax.Array,
    cfg: HeadConfig,
    index=None,  # optional ShardedIndex over the same (Vp, d) table
) -> jax.Array:
    """Per-token NLL, distributed. Differentiable w.r.t. emb and h."""
    cfg = cfg.resolved()
    mp = mesh.shape["model"]
    vp = emb.shape[0]
    v_loc, k_loc, l_loc = _shard_geometry(cfg, vp, mp)

    def local_fn(emb_loc, h_loc, tgt_loc, key, *idx_state):
        midx = jax.lax.axis_index("model")
        offset = midx * v_loc
        n_valid = jnp.clip(cfg.n - offset, 0, v_loc)
        key = jax.random.fold_in(key, midx)
        index_loc = index.local_index(idx_state[0]) if idx_state else None
        tgt_local = tgt_loc.astype(jnp.int32) - offset

        def one_chunk(kk, hc, tc):
            return est.loss_partials(
                kk, emb_loc, hc, tc, mode=cfg.mode, k=k_loc, l=l_loc,
                index=index_loc, n_valid=n_valid, score_dtype=cfg.score_dt,
                use_kernel=cfg.use_kernel,
                axis="model" if cfg.mode == "exact" else None,
            )

        parts = est.chunked_map(one_chunk, cfg.chunk, key, h_loc, tgt_local)
        return est.combine_loss_psum(parts, cfg.mode, "model")

    idx_args, idx_specs = _index_args(index)
    tok_ax = _token_spec(mesh, h.shape[0])
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P("model", None), P(tok_ax, None), P(tok_ax), P(),
                  *idx_specs),
        out_specs=P(tok_ax),
        check_vma=False,
    )
    return fn(emb, h, targets, key, *idx_args)


def dist_head_sample(
    mesh,
    emb: jax.Array,  # (Vp, d) P("model", None)
    h: jax.Array,  # (T, d) P(batch_axes, None)
    key: jax.Array,
    cfg: HeadConfig,
    index=None,  # optional ShardedIndex over the same (Vp, d) table
    keys: jax.Array | None = None,  # (T,) per-token typed PRNG keys
    router=None,  # optional ProbeRouter (replicated pytree; adaptive probe)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed lazy-Gumbel sampling. Returns (ids (T,), ok (T,),
    width (T,)).

    ``keys`` pins each token's randomness to its own key (each shard folds
    in its model-axis index on top, keeping per-shard draws independent):
    the serving engine derives these from (request id, position) so samples
    are invariant to batch composition and decode fusion. Raw key data is
    threaded through shard_map (typed key arrays don't cross the shard_map
    boundary on all jax versions).

    ``width`` is the per-token effective probe width under
    ``cfg.adaptive_probe`` — each shard widens independently and the global
    width is the max over shards (critical-path semantics: shards probe in
    parallel); −1 on fixed-width paths."""
    cfg = cfg.resolved()
    mp = mesh.shape["model"]
    vp = emb.shape[0]
    v_loc, k_loc, l_loc = _shard_geometry(cfg, vp, mp)
    use_keys = keys is not None
    use_router = router is not None
    if key is None:  # all randomness comes from `keys`; placeholder only
        key = jax.random.key(0)

    def local_fn(emb_loc, h_loc, key, *rest):
        midx = jax.lax.axis_index("model")
        offset = midx * v_loc
        n_valid = jnp.clip(cfg.n - offset, 0, v_loc)
        key = jax.random.fold_in(key, midx)
        t_loc = h_loc.shape[0]
        rest = list(rest)
        if use_keys:
            kd_loc = rest.pop(0)
            keys_loc = jax.vmap(jax.random.fold_in, (0, None))(
                jax.random.wrap_key_data(kd_loc), midx
            )
        else:
            keys_loc = None
        router_loc = rest.pop(0) if use_router else None
        idx_state = tuple(rest)

        width = jnp.full((t_loc,), -1, jnp.int32)
        if cfg.mode == "exact":
            loc_best, val = est.dense_gumbel_max(
                key, emb_loc, h_loc, n_valid=n_valid, keys=keys_loc
            )
            gid = loc_best + offset
            ok = jnp.ones((t_loc,), bool)
            bound = jnp.full((t_loc,), -jnp.inf)
        else:
            index_loc = index.local_index(idx_state[0]) if idx_state else None
            res = est.local_gumbel_max(
                key, emb_loc, h_loc, k=k_loc, l=l_loc, index=index_loc,
                n_valid=n_valid, c=cfg.c, keys=keys_loc,
                fused=cfg.fused_decode, adaptive=cfg.adaptive_probe,
                router=router_loc,
            )
            gid = res.index + offset
            val = res.max_val
            bound = res.bound
            ok = ~res.overflow
            if res.width is not None:
                width = res.width.astype(jnp.int32)

        gid_g, ok_g = est.combine_sample_pmax(gid, val, bound, ok, "model")
        return gid_g, ok_g, jax.lax.pmax(width, "model")

    idx_args, idx_specs = _index_args(index)
    tok_ax = _token_spec(mesh, h.shape[0])
    key_args, key_specs = (), ()
    if use_keys:
        key_args = (jax.random.key_data(keys),)
        key_specs = (P(tok_ax, None),)
    rt_args, rt_specs = (), ()
    if use_router:
        rt_args = (router,)
        rt_specs = (P(),)  # replicated: every shard routes its local probe
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P("model", None), P(tok_ax, None), P(),
                  *key_specs, *rt_specs, *idx_specs),
        out_specs=(P(tok_ax), P(tok_ax), P(tok_ax)),
        check_vma=False,
    )
    return fn(emb, h, key, *key_args, *rt_args, *idx_args)
