"""Mixture-of-Experts FFN with sort-based (MegaBlocks-style) dispatch.

Tokens' top-k expert assignments are flattened and sorted by expert id;
each assignment's rank within its expert segment maps it to a fixed-capacity
slot (static shapes — overflow rides in a trash slot and is dropped, the
standard capacity-factor semantics). Expert FFNs run as one grouped einsum
over the (E, C, d) buffer, which shards cleanly: experts over the FSDP axis
or the buffer's hidden dim over TP.

The router (an E-way softmax) is intentionally exact: E is tiny, so the
paper's sublinear machinery is inapplicable there (DESIGN.md
§Arch-applicability).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import dense_init

__all__ = ["init", "forward", "forward_dist"]

# expert placement for the distributed layer: "ep" = expert dim over the
# model axis when divisible (DEFAULT — §Perf iter 4: -26% memory term,
# HBM fit for qwen3's 128 experts), else "tp" = FFN hidden over the model
# axis. Must agree with launch.mesh.MOE_SHARDING (the storage layout) —
# launch/perf.py sets both.
DIST_MODE = "ep"


def init(key, cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (d, e)),
        "w1": dense_init(k2, (e, d, f), in_axis=-2),
        "w2": dense_init(k3, (e, f, d), in_axis=-2),
        "w3": dense_init(k4, (e, d, f), in_axis=-2),
    }


def _capacity(cfg: ArchConfig, t: int) -> int:
    c = int(cfg.capacity_factor * t * cfg.experts_per_token / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def forward_dist(
    p: dict, cfg: ArchConfig, x: jax.Array, mesh
) -> tuple[jax.Array, jax.Array]:
    """shard_map'd MoE layer (§Perf iteration 2).

    Routing and dispatch are DATA-LOCAL (each data shard routes its own
    tokens into its own capacity buffer — XLA auto-sharding otherwise
    replicates the data-dependent scatter and all-reduces multi-GB
    dispatch buffers every layer); expert FFNs are TP-local (hidden dim
    over "model"); the single cross-TP collective is a psum of the
    COMBINED (T_loc, d) output — the combine is linear, so reducing after
    it moves the psum from the (E, C, d) buffer to the (T_loc, d) output
    (Megatron row-parallel style).
    """
    from jax.sharding import PartitionSpec as P

    ba = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    t = x.shape[0]
    bsz = 1
    for a in ba:
        bsz *= mesh.shape[a]
    tok_ax = ba if (ba and t % bsz == 0 and t >= bsz) else None
    mp = mesh.shape["model"]
    use_ep = (
        DIST_MODE == "ep"
        and cfg.n_experts % mp == 0
        and cfg.n_experts >= mp
    )
    e_loc = cfg.n_experts // mp if use_ep else 0

    def local(p_loc, x_loc):
        if use_ep:
            off = jax.lax.axis_index("model") * e_loc
            out_p, aux = forward(p_loc, cfg, x_loc, expert_offset=off,
                                 n_local=e_loc)
        else:
            out_p, aux = forward(p_loc, cfg, x_loc)
        out = jax.lax.psum(out_p, "model")
        axes = ("model",) + (ba if tok_ax else ())
        aux = jax.lax.pmean(aux, axes)
        return out, aux

    if use_ep:  # experts over TP shards: full-width FFN per local expert
        p_specs = {
            "router": P(),
            "w1": P("model", None, None),
            "w3": P("model", None, None),
            "w2": P("model", None, None),
        }
    else:  # Megatron-style: FFN hidden over TP shards, all experts local
        p_specs = {
            "router": P(),
            "w1": P(None, None, "model"),
            "w3": P(None, None, "model"),
            "w2": P(None, "model", None),
        }
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(p_specs, P(tok_ax, None)),
        out_specs=(P(tok_ax, None), P()),
        check_vma=False,
    )
    return fn(p, x)


def forward(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    expert_offset: jax.Array | int = 0,
    n_local: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """x: (T, d) -> (out (T, d), aux_loss scalar).

    With ``n_local`` set (expert parallelism), only experts in
    ``[expert_offset, expert_offset + n_local)`` are computed — p's expert
    weights then carry ``n_local`` experts and the output is a PARTIAL sum
    (tokens routed elsewhere contribute zero; caller psums over the EP
    axis).
    """
    t, d = x.shape
    e, kx = cfg.n_experts, cfg.experts_per_token
    e_here = n_local or e
    dt = x.dtype
    cap = _capacity(cfg, t)

    logits = (x @ p["router"].astype(dt)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, kx)  # (T, kx)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style): E * sum_e f_e * P_e
    me = probs.mean(0)  # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (t * kx)
    aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    flat_e = idx.reshape(-1)  # (T*kx,)
    order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[order]
    tok = order // kx  # source token per sorted slot
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank = jnp.arange(t * kx) - seg_start[sorted_e]
    loc_e = sorted_e - expert_offset  # local expert coordinates
    mine = (loc_e >= 0) & (loc_e < e_here)
    keep = (rank < cap) & mine
    slot = jnp.where(keep, rank, cap)  # cap = trash slot
    loc_e = jnp.where(mine, loc_e, 0)

    buf = jnp.zeros((e_here, cap + 1, d), dt).at[loc_e, slot].set(
        jnp.where(keep[:, None], x[tok], 0)
    )

    # grouped SwiGLU over (local) experts
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(dt))
    ) * jnp.einsum("ecd,edf->ecf", buf, p["w3"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))  # (E_loc, cap+1, d)

    y_sorted = y[loc_e, slot]  # (T*kx, d); trash/foreign slots masked below
    w = (gates.reshape(-1)[order] * keep).astype(dt)
    out = jnp.zeros((t, d), dt).at[tok].add(y_sorted * w[:, None])
    return out, aux
