"""Pure-jnp oracles for every Pallas kernel (tested with assert_allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "ivf_gather_score_ref",
    "pq_lut_score_ref",
    "fused_estimator_ref",
    "flash_decode_ref",
    "topk_select_ref",
    "ivf_screen_select_ref",
    "pq_screen_select_ref",
    "rerank_select_ref",
    "tail_gather_argmax_ref",
]


def ivf_gather_score_ref(
    member_vecs: jax.Array, probe: jax.Array, q: jax.Array
) -> jax.Array:
    """(n_c,cap,d), (b,np), (b,d) -> (b, np, cap) scores."""
    gathered = member_vecs[probe]  # (b, np, cap, d)
    return jnp.einsum(
        "bpcd,bd->bpc", gathered.astype(jnp.float32), q.astype(jnp.float32)
    )


def pq_lut_score_ref(
    member_codes: jax.Array, probe: jax.Array, lut: jax.Array
) -> jax.Array:
    """(n_c,cap,m) u8, (b,np), (b,m,ksub) -> (b, np, cap) LUT sums."""
    b, n_probe = probe.shape
    cap, m = member_codes.shape[1:]
    codes = member_codes[probe].reshape(b, n_probe * cap, m)
    ct = jnp.moveaxis(codes.astype(jnp.int32), 2, 1)  # (b, m, np*cap)
    picked = jnp.take_along_axis(lut.astype(jnp.float32), ct, axis=2)
    return picked.sum(axis=1).reshape(b, n_probe, cap)


def fused_estimator_ref(
    emb: jax.Array, ids: jax.Array, h: jax.Array, log_w: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Stratified logsumexp + weighted expectation. -> (log_z (t,), (t, d))."""
    rows = emb[ids].astype(jnp.float32)  # (t, m, d)
    y = jnp.einsum("tmd,td->tm", rows, h.astype(jnp.float32)) + log_w
    log_z = jax.nn.logsumexp(y, axis=1)
    p = jnp.exp(y - log_z[:, None])
    expv = jnp.einsum("tm,tmd->td", p, rows)
    return log_z, expv


def topk_select_ref(
    scores: jax.Array, ids: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Top-k of a masked (b, pool) score/id pair, the way the fused decode
    kernels' in-VMEM extractor emits it: pools smaller than k are padded
    with (-inf, -1); -inf picks emit id -1."""
    b, pool = scores.shape
    if pool < k:
        pad = k - pool
        scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    vals, pos = jax.lax.top_k(scores, k)
    out_ids = jnp.take_along_axis(ids, pos, axis=1)
    return vals, jnp.where(jnp.isneginf(vals), -1, out_ids).astype(jnp.int32)


def ivf_screen_select_ref(
    member_vecs, member_ids, overflow_scores, overflow_ids, probe, q, k: int
) -> tuple[jax.Array, jax.Array]:
    """(n_c,cap,d), (n_c,cap), (b,o_cap), (o_cap,), (b,np), (b,d) ->
    top-k (values (b,k), ids (b,k)) of the probed pool ∪ overflow."""
    b = probe.shape[0]
    scores = ivf_gather_score_ref(member_vecs, probe, q).reshape(b, -1)
    ids = member_ids[probe].reshape(b, -1).astype(jnp.int32)
    scores = jnp.concatenate([scores, overflow_scores.astype(jnp.float32)], 1)
    o = jnp.broadcast_to(
        overflow_ids.astype(jnp.int32)[None], (b, overflow_ids.shape[0])
    )
    ids = jnp.concatenate([ids, o], 1)
    scores = jnp.where(ids >= 0, scores, -jnp.inf)
    return topk_select_ref(scores, ids, k)


def pq_screen_select_ref(
    member_codes, member_ids, coarse, overflow_scores, overflow_ids, probe,
    lut, r: int
) -> tuple[jax.Array, jax.Array]:
    """LUT screen (+ coarse centroid term) over the probed pool ∪ exact
    overflow scores -> top-r (values (b,r), ids (b,r))."""
    b = probe.shape[0]
    scores = pq_lut_score_ref(member_codes, probe, lut)
    scores = (scores + coarse.astype(jnp.float32)[..., None]).reshape(b, -1)
    ids = member_ids[probe].reshape(b, -1).astype(jnp.int32)
    scores = jnp.concatenate([scores, overflow_scores.astype(jnp.float32)], 1)
    o = jnp.broadcast_to(
        overflow_ids.astype(jnp.int32)[None], (b, overflow_ids.shape[0])
    )
    ids = jnp.concatenate([ids, o], 1)
    scores = jnp.where(ids >= 0, scores, -jnp.inf)
    return topk_select_ref(scores, ids, r)


def rerank_select_ref(db, cand, lut_vals, q, k: int):
    """Exact re-rank of (b, r) screening survivors -> top-k (values, ids)."""
    rows = db[jnp.maximum(cand, 0)].astype(jnp.float32)  # (b, r, d)
    exact = jnp.einsum("brd,bd->br", rows, q.astype(jnp.float32))
    dead = (cand < 0) | jnp.isneginf(lut_vals)
    return topk_select_ref(
        jnp.where(dead, -jnp.inf, exact), cand.astype(jnp.int32), k
    )


def tail_gather_argmax_ref(emb, pos, m_used, pert_s, s_ids, heights, h):
    """Algorithm-2 finish: perturbed argmax over S ∪ tail per token ->
    (index (t,), max_val (t,))."""
    t, m_cap = pos.shape
    rows = emb[pos].astype(jnp.float32)  # (t, m_cap, d)
    y_tail = jnp.einsum("tmd,td->tm", rows, h.astype(jnp.float32))
    live = jnp.arange(m_cap, dtype=jnp.int32)[None, :] < m_used[:, None]
    pert_t = jnp.where(live, y_tail + heights, -jnp.inf)
    pert = jnp.concatenate([pert_s.astype(jnp.float32), pert_t], axis=1)
    ids = jnp.concatenate([s_ids.astype(jnp.int32), pos.astype(jnp.int32)], 1)
    best = jnp.argmax(pert, axis=1)
    return (
        jnp.take_along_axis(ids, best[:, None], 1)[:, 0],
        jnp.take_along_axis(pert, best[:, None], 1)[:, 0],
    )


def flash_decode_ref(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, lengths: jax.Array
) -> jax.Array:
    """(B,Hq,hd), head-major (B,Hkv,S,hd) x2, (B,) -> (B,Hq,hd)."""
    b, hq, hd = q.shape
    _, hkv, s, _ = k_cache.shape
    g = hq // hkv
    qf = q.astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    # expand KV heads to query heads
    kf = jnp.repeat(kf, g, axis=1)  # (B, Hq, S, hd)
    vf = jnp.repeat(vf, g, axis=1)
    scores = jnp.einsum("bhd,bhsd->bhs", qf, kf) / (hd**0.5)
    mask = jnp.arange(s)[None, None, :] < lengths[:, None, None]
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vf)
