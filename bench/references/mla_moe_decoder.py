"""Plain float32 reference of a DeepSeek-V3-style decoder, as Moonlight
16B-A3B publishes it (config.json ``model_type`` deepseek_v3; arXiv:2502.16982):
multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), leading dense
SwiGLU layers, then expert layers with a sigmoid router whose selection
bias picks the top-k (DeepSeek-V3, arXiv:2412.19437) beside always-on
shared experts. It imports nothing of the program under test.

Per layer, with x the RMS-normed input:

* MLA, expanded. ``c = x·W_DKV`` gives ``kv_lora_rank + qk_rope_head_dim``
  values: ``c_kv = RMSNorm(c[:r])``, and ``k_pe = RoPE(c[r:])``, one rotary
  key shared by every head. ``q = x·W_Q`` (no ``q_lora``), split per head
  into ``q_nope`` and ``q_pe = RoPE(q[nope:])``. ``[k_nope_h | v_h] =
  c_kv·W_UKV,h``. ``score = (q_nope_h·k_nope_h + q_pe_h·k_pe) /
  sqrt(nope + rope)``, causal softmax, a sum of ``v_h``, then ``W_O``.
  This is the published form; the program decodes in the absorbed form,
  and this file checks it against this one.
* MoE. ``s = sigmoid(x·W_r)`` over every routed expert (``published
  n_routed_experts``, all 64); the top-k by ``s + b``; each chosen
  expert's gate ``s_i / Σ s_chosen × routed_scaling_factor``. ``y =
  Σ g_i·E_i(x) + S(x)``, ``E_i`` and the shared ``S`` (width
  ``n_shared_experts × moe_intermediate_size``) SwiGLU. Only the experts
  this chip holds, ``[offset, offset + n_routed_experts)``, add their
  part (the program's share; the rest lie on other chips), and ``S``
  adds in full.

Departures, none of which changes the function computed with random
weights: rotate-half RoPE over the 64 rotary dims (the published code
de-interleaves each pair first, a fixed relabelling of W_Q's and W_DKV's
rotary columns); the router's weight is held in the trunk's dtype and its
scores computed in float32, as the published code does. ``n_group`` =
``topk_group`` = 1 and ``q_lora_rank`` null are the only grouping and
query paths written here.

Weights are made from the seed, stacked per kind of layer (the leading
dense layers, then the expert layers); the runner hands the same arrays
to the program in its own layout. ``logits`` runs one whole sequence at
once, every product at ``Precision.HIGHEST`` in float32, attention in
query blocks. ``quant="fp8"`` is the control of ``dense_decoder.py``:
every weight matrix but the router's rounded to e4m3 scaled per output
column, every matrix input rounded to bfloat16.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def dims(cfg: dict) -> dict:
    published = cfg.get("published", {})
    dense = cfg["first_k_dense_replace"]
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "r": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "vd": cfg["v_head_dim"], "f": cfg["intermediate_size"],
        "ef": cfg["moe_intermediate_size"],
        "shared_f": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "experts": published.get("n_routed_experts",
                                  cfg["n_routed_experts"]),
        "held": cfg["n_routed_experts"], "topk": cfg["num_experts_per_tok"],
        "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "dense_layers": dense, "moe_layers": cfg["num_hidden_layers"] - dense,
    }


def _check(cfg: dict) -> None:
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("q_lora_rank: only the direct query path is written")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("grouped routing (n_group > 1) is not written")
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("only sigmoid scores with a selection bias")


def _stack(key, layers, shape, fan_in, dtype):
    """``layers`` arrays N(0, 1/fan_in), made one layer at a time."""
    def one(k):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)
    return jax.lax.map(one, jax.random.split(key, layers))


def _attn_weights(key, n, m, dt) -> dict:
    d, h, r = m["d"], m["h"], m["r"]
    qk = m["nope"] + m["rope"]
    ks = jax.random.split(key, 4)
    return {
        "q": _stack(ks[0], n, (d, h * qk), d, dt),
        "kv_a": _stack(ks[1], n, (d, r + m["rope"]), d, dt),
        "kv_b": _stack(ks[2], n, (r, h * (m["nope"] + m["vd"])), r, dt),
        "o": _stack(ks[3], n, (h * m["vd"], d), h * m["vd"], dt),
        "kv_norm": jnp.ones((n, r), jnp.float32),
        "attn_norm": jnp.ones((n, d), jnp.float32),
        "mlp_norm": jnp.ones((n, d), jnp.float32),
    }


@functools.partial(jax.jit, static_argnames=("shape_key",))
def _init(key, shape_key):
    cfg = json.loads(shape_key)
    m = dims(cfg)
    w = cfg["synthetic_weights"]
    d, v = m["d"], m["v"]
    dt = jnp.dtype(cfg["dtype"]["trunk"])
    nd, nm, e, ef = m["dense_layers"], m["moe_layers"], m["held"], m["ef"]
    ks = jax.random.split(key, 16)
    # output head: rows cluster around centres, as the rows of a trained
    # embedding do, scaled to ``embed_row_norm``
    centres = jax.random.normal(ks[0], (w["embed_clusters"], d))
    assign = jax.random.randint(ks[1], (v,), 0, w["embed_clusters"])
    rows = centres[assign] + w["embed_noise"] * jax.random.normal(
        ks[2], (v, d))
    rows = rows / jnp.linalg.norm(rows, axis=1, keepdims=True)
    head = (rows * w["embed_row_norm"]).astype(jnp.dtype(cfg["dtype"]["head"]))
    emb = jax.random.normal(ks[3], (v, d))  # input embedding: plain rows
    embed = (emb / jnp.linalg.norm(emb, axis=1, keepdims=True)).astype(dt)
    dense = _attn_weights(ks[4], nd, m, dt)
    dense.update(gate=_stack(ks[5], nd, (d, m["f"]), d, dt),
                 up=_stack(ks[6], nd, (d, m["f"]), d, dt),
                 down=_stack(ks[7], nd, (m["f"], d), m["f"], dt))
    moe = _attn_weights(ks[8], nm, m, dt)
    moe.update(
        router=_stack(ks[9], nm, (d, m["experts"]), d, dt),
        bias=w["router_bias_std"] * jax.random.normal(
            ks[10], (nm, m["experts"]), jnp.float32),
        gate=_stack(ks[11], nm, (e, d, ef), d, dt),
        up=_stack(ks[12], nm, (e, d, ef), d, dt),
        down=_stack(ks[13], nm, (e, ef, d), ef, dt),
        shared_gate=_stack(ks[14], nm, (d, m["shared_f"]), d, dt),
        shared_up=_stack(jax.random.fold_in(ks[14], 1), nm,
                         (d, m["shared_f"]), d, dt),
        shared_down=_stack(ks[15], nm, (m["shared_f"], d), m["shared_f"],
                           dt),
    )
    return {"embed": embed, "out_embed": head, "dense": dense, "moe": moe,
            "final_norm": jnp.full((d,), w["final_norm_weight"],
                                   jnp.float32)}


def head_table(w: dict):
    """The output head's rows (the head is untied)."""
    return w["out_embed"]


SHAPE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "num_hidden_layers", "vocab_size",
              "published", "routed_scaling_factor", "norm_topk_prob",
              "rms_norm_eps", "rope_theta")


def _freeze(cfg: dict, keep: tuple) -> str:
    """The keys a traced function needs, as a hashable static argument."""
    return json.dumps({k: cfg[k] for k in keep if k in cfg}, sort_keys=True)


def init_weights(cfg: dict, key) -> dict:
    """All weights, on the device, in one jitted call from ``key``."""
    _check(cfg)
    return _init(key, _freeze(cfg, SHAPE_KEYS + ("dtype",
                                                 "synthetic_weights")))


def _w(w, quant):
    """A weight matrix (..., in, out) in float32, or, for the control,
    rounded to e4m3 per output column (reduce_precision, which the
    compiler does not fold away; the largest finite e4m3 value is 240)."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 240.0
        scale = jnp.maximum(scale, 1e-30)
        w = jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale
    return w


def _x(x, quant):
    """A matrix input, rounded to bfloat16 for the control."""
    if quant == "fp8":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _mm(x, w, quant):
    return jnp.dot(_x(x, quant), _w(w, quant), precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):  # x: (L, heads, dims), rotate-half
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, scale):
    """Causal softmax attention in query blocks. q, k: (L, H, nope + rope);
    v: (L, H, vd)."""
    L, H, _ = q.shape
    qb = min(Q_BLOCK, L)
    kpos = jnp.arange(L)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v, precision=HI)

    return jax.lax.map(block, jnp.arange(L // qb)).reshape(L, H, -1)


def mla(x, p, m, cfg, pos, quant=""):
    """One layer's latent attention, expanded, on the normed input x (L, d)."""
    L = x.shape[0]
    h, r, nope = m["h"], m["r"], m["nope"]
    theta = cfg["rope_theta"]
    q = _mm(x, p["q"], quant).reshape(L, h, nope + m["rope"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)],
                        -1)
    c = _mm(x, p["kv_a"], quant)
    c_kv = _rms(c[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(c[:, None, r:], pos, theta)  # (L, 1, rope): every head's
    kv = _mm(c_kv, p["kv_b"], quant).reshape(L, h, nope + m["vd"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (L, h, m["rope"]))], -1)
    o = _attend(q, k, kv[..., nope:], 1.0 / np.sqrt(nope + m["rope"]))
    return _mm(o.reshape(L, -1), p["o"], quant)


def _swiglu(x, gate, up, down, quant):
    g = _mm(x, gate, quant)
    return _mm(jax.nn.silu(g) * _mm(x, up, quant), down, quant)


def route(x, p, m, cfg):
    """Gates (L, experts) of the routed experts, zero where not chosen:
    sigmoid scores over every expert, top-k by score + bias, the chosen
    scores renormalised and scaled."""
    s = jax.nn.sigmoid(jnp.dot(x, p["router"].astype(jnp.float32),
                               precision=HI))
    _, idx = jax.lax.top_k(s + p["bias"], m["topk"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    g = g * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(g)


def moe(x, p, m, cfg, quant="", offset=0, shared=True):
    """One expert layer on the normed input x (L, d): the part that the
    experts ``[offset, offset + held)`` give, each run on every token and
    weighted by its gate (zero where not chosen), plus the shared
    experts' (``shared``)."""
    gates = jax.lax.dynamic_slice_in_dim(route(x, p, m, cfg), offset,
                                         m["held"], axis=1)  # (L, held)
    xq = _x(x, quant)
    hg = jnp.einsum("ld,edf->elf", xq, _w(p["gate"], quant), precision=HI)
    hu = jnp.einsum("ld,edf->elf", xq, _w(p["up"], quant), precision=HI)
    y = jnp.einsum("elf,efd->eld", _x(jax.nn.silu(hg) * hu, quant),
                   _w(p["down"], quant), precision=HI)
    out = jnp.einsum("le,eld->ld", gates, y, precision=HI)
    if shared:
        out = out + _swiglu(x, p["shared_gate"], p["shared_up"],
                            p["shared_down"], quant)
    return out


@functools.partial(jax.jit, static_argnames=("shape_key", "quant"))
def _logits(w, tokens, positions, shape_key, quant):
    cfg = json.loads(shape_key)
    m = dims(cfg)
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(mlp):
        def body(x, p):
            x = x + mla(_rms(x, p["attn_norm"], eps), p, m, cfg, pos, quant)
            b = _rms(x, p["mlp_norm"], eps)
            return x + mlp(b, p), None
        return body

    def dense(b, p):
        return _swiglu(b, p["gate"], p["up"], p["down"], quant)

    x, _ = jax.lax.scan(layer(dense), x, w["dense"])
    x, _ = jax.lax.scan(layer(lambda b, p: moe(b, p, m, cfg, quant)), x,
                        w["moe"])
    h = _rms(x[positions], w["final_norm"], eps)
    return jnp.dot(h, head_table(w).astype(jnp.float32).T, precision=HI)


def logits(w: dict, cfg: dict, tokens, positions, *, quant: str = "",
           pad_to: int = 512):
    """Logits (len(positions), vocab) float32 of the sequence ``tokens``
    at ``positions``. The sequence is padded to a multiple of ``pad_to``
    (causal: padding changes no earlier position), so few shapes compile."""
    _check(cfg)
    tokens = np.asarray(tokens, np.int32)
    lp = -(-len(tokens) // pad_to) * pad_to
    padded = np.zeros(lp, np.int32)
    padded[: len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return _logits(w, jnp.asarray(padded),
                       jnp.asarray(np.asarray(positions, np.int32)),
                       _freeze(cfg, SHAPE_KEYS), quant)
