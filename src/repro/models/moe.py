"""Mixture-of-Experts FFN with sort-based (MegaBlocks-style) dispatch.

Tokens' top-k expert assignments are flattened and sorted by expert id;
each assignment's rank within its expert segment maps it to a slot of a
static-shaped (E, C, d) buffer. Expert FFNs run as one grouped einsum over
the buffer, which shards cleanly: experts over the FSDP axis or the
buffer's hidden dim over TP. Training takes the capacity-factor C, whose
overflow rides in a trash slot and is dropped; serving (``dropless``)
takes C = the tokens in the call, which no expert can overflow, so a
token's output never depends on its batch mates.

Two routers: an E-way softmax over the top-k (Mixtral, Qwen3-MoE), or
DeepSeek-V3's sigmoid scores with a selection bias that picks the top-k
and gates from the scores alone, renormalised and scaled. Either is
exact: E is tiny, so the paper's sublinear machinery is inapplicable
there (DESIGN.md §Arch-applicability). Shared experts run on every token
beside the routed ones.

Expert parallelism: a layer that holds ``n_local`` of the E experts routes
over all E and returns its own experts' part of the result (plus the
shared experts'); on one chip that share is the layer (configs set
``n_local_experts``), across chips the caller sums the shares.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import dense_init, mlp_init, swiglu

__all__ = ["init", "route", "forward", "forward_dist"]

# expert placement for the distributed layer: "ep" = expert dim over the
# model axis when divisible (DEFAULT — §Perf iter 4: -26% memory term,
# HBM fit for qwen3's 128 experts), else "tp" = FFN hidden over the model
# axis. Must agree with launch.mesh.MOE_SHARDING (the storage layout) —
# launch/perf.py sets both.
DIST_MODE = "ep"


def init(key, cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    held = cfg.experts_held
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": dense_init(k1, (d, e)),
        "w1": dense_init(k2, (held, d, f), in_axis=-2),
        "w2": dense_init(k3, (held, f, d), in_axis=-2),
        "w3": dense_init(k4, (held, d, f), in_axis=-2),
    }
    if cfg.router_scoring == "sigmoid":  # the selection bias
        p["bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(k5, d, cfg.n_shared_experts * f)
    return p


def route(p: dict, cfg: ArchConfig, x: jax.Array):
    """x (T, d) -> (gates (T, k) f32, expert ids (T, k), the routing
    distribution (T, E) f32 the aux loss reads)."""
    kx = cfg.experts_per_token
    if cfg.router_scoring == "softmax":
        logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, kx)
    elif cfg.router_scoring == "sigmoid":
        # float32 scores, as published: near-ties between experts are
        # common, and the chosen set must not hang on bf16 rounding
        logits = jnp.dot(x.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        sel = scores + jax.lax.stop_gradient(p["bias"])
        _, idx = jax.lax.top_k(sel, kx)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        raise ValueError(f"unknown router scoring {cfg.router_scoring!r}")
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        gates = gates * cfg.routed_scale
    return gates, idx, probs


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
    if cfg.n_local_experts or cfg.n_shared_experts:
        raise NotImplementedError(
            "forward_dist splits every routed expert over the mesh; a layer "
            "cut to one chip's share (n_local_experts), or with shared "
            "experts, runs through forward")
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
    dropless: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """x: (T, d) -> (out (T, d), aux_loss scalar).

    Only experts in ``[expert_offset, expert_offset + n_local)`` are
    computed, ``n_local`` defaulting to the experts the configuration
    holds — p's expert weights then carry ``n_local`` experts and the
    routed part of the output is a PARTIAL sum (tokens routed elsewhere
    contribute zero; across chips the caller psums over the EP axis).
    Shared experts are added in full. ``dropless`` sizes every expert's
    buffer for all T tokens, so none is dropped.
    """
    t, d = x.shape
    e, kx = cfg.n_experts, cfg.experts_per_token
    e_here = n_local or cfg.experts_held
    dt = x.dtype
    cap = t if dropless else _capacity(cfg, t)

    with jax.named_scope("moe.route"):
        gates, idx, probs = route(p, cfg, x)
        # load-balancing aux loss (Switch-style): E * sum_e f_e * P_e
        me = probs.mean(0)  # (E,)
        ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (
            t * kx)
        aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    with jax.named_scope("moe.dispatch"):
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
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(dt))
        ) * jnp.einsum("ecd,edf->ecf", buf, p["w3"].astype(dt))
        y = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))  # (E_loc, cap+1, d)

    with jax.named_scope("moe.dispatch"):
        y_sorted = y[loc_e, slot]  # (T*kx, d); trash/foreign slots masked
        w = (gates.reshape(-1)[order] * keep).astype(dt)
        out = jnp.zeros((t, d), dt).at[tok].add(y_sorted * w[:, None])
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            out = out + swiglu(x, sp["w1"], sp["w2"], sp["w3"])
    return out, aux
