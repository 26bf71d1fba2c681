"""Plain float32 reference of a Llama-style dense decoder (RMSNorm, rotary
position embedding with halves rotated, grouped-query attention, SwiGLU,
no bias terms), as published for Mistral 7B (arXiv:2310.06825) and the
Hugging Face ``MistralForCausalLM``. It imports nothing of the program
under test.

Weights are made here from the seed, stacked over layers under their
published names; the runner hands the same arrays to the program in its
own layout. The output head is the input embedding where the
configuration ties them, a table of its own where it does not.

``logits`` runs one whole sequence at once, every matrix product at
``Precision.HIGHEST`` in float32, causal attention in query blocks so that
the score block stays small. ``quant="fp8"`` is the control: the same
pass with every weight matrix rounded to 8-bit floats (e4m3, scaled per
output column) and every matrix input rounded to bfloat16: weight-only
fp8 serving, the precision step below the bfloat16 trunk the
configuration states.
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
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"], "h": h,
            "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h),
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def _stack(key, layers, shape, fan_in, dtype):
    """``layers`` matrices N(0, 1/fan_in), made one layer at a time."""
    def one(k):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)
    return jax.lax.map(one, jax.random.split(key, layers))


@functools.partial(jax.jit, static_argnames=("shape_key",))
def _init(key, shape_key):
    cfg = json.loads(shape_key)
    m = dims(cfg)
    w = cfg["synthetic_weights"]
    d, f, L = m["d"], m["f"], m["layers"]
    dq, dkv = m["h"] * m["hd"], m["kv"] * m["hd"]
    dt = jnp.dtype(cfg["dtype"]["trunk"])
    ks = jax.random.split(key, 11)
    # output head: rows cluster around centres, as the rows of a trained
    # embedding do, scaled to ``embed_row_norm``
    centres = jax.random.normal(ks[0], (w["embed_clusters"], d))
    assign = jax.random.randint(ks[1], (m["v"],), 0, w["embed_clusters"])
    rows = centres[assign] + w["embed_noise"] * jax.random.normal(
        ks[2], (m["v"], d))
    rows = rows / jnp.linalg.norm(rows, axis=1, keepdims=True)
    rows = rows * w["embed_row_norm"]
    head = rows.astype(jnp.dtype(cfg["dtype"]["head"]))
    if cfg["tie_word_embeddings"]:
        embed, out_embed = head, None
    else:  # the input embedding: plain normal rows, in the trunk's type
        e = jax.random.normal(ks[10], (m["v"], d))
        embed = (e / jnp.linalg.norm(e, axis=1, keepdims=True)).astype(dt)
        out_embed = head
    return {
        "embed": embed,
        "out_embed": out_embed,
        "q": _stack(ks[3], L, (d, dq), d, dt),
        "k": _stack(ks[4], L, (d, dkv), d, dt),
        "v": _stack(ks[5], L, (d, dkv), d, dt),
        "o": _stack(ks[6], L, (dq, d), dq, dt),
        "gate": _stack(ks[7], L, (d, f), d, dt),
        "up": _stack(ks[8], L, (d, f), d, dt),
        "down": _stack(ks[9], L, (f, d), f, dt),
        "attn_norm": jnp.ones((L, d), jnp.float32),
        "mlp_norm": jnp.ones((L, d), jnp.float32),
        "final_norm": jnp.full((d,), w["final_norm_weight"], jnp.float32),
    }


def head_table(w: dict):
    """The output head's rows: the tied embedding or a table of its own."""
    return w["embed"] if w["out_embed"] is None else w["out_embed"]


def _freeze(cfg: dict, keep: tuple) -> str:
    """The keys a traced function needs, as a hashable static argument."""
    return json.dumps({k: cfg[k] for k in keep if k in cfg}, sort_keys=True)


def init_weights(cfg: dict, key) -> dict:
    """All weights, on the device, in one jitted call from ``key``."""
    keep = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "head_dim", "tie_word_embeddings", "dtype", "synthetic_weights")
    return _init(key, _freeze(cfg, keep))


def _mm(x, w, quant):
    if quant == "fp8":
        # rounding by reduce_precision, an op of its own, which the
        # compiler does not fold away as it may a narrowing convert pair:
        # weights to e4m3 (4 exponent, 3 mantissa bits), scaled per
        # output column to its largest finite value, 240; inputs to bf16
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 240.0
        scale = jnp.maximum(scale, 1e-30)
        w = jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):  # x: (L, heads, hd)
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, n_rep):
    """Causal softmax attention, in query blocks. q: (L, H, hd)."""
    L, H, hd = q.shape
    k = jnp.repeat(k, n_rep, axis=1)
    v = jnp.repeat(v, n_rep, axis=1)
    qb = min(Q_BLOCK, L)
    kpos = jnp.arange(L)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / np.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(L // qb))
    return out.reshape(L, H, hd)


@functools.partial(jax.jit, static_argnames=("shape_key", "quant"))
def _logits(w, tokens, positions, shape_key, quant):
    cfg = json.loads(shape_key)
    m = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    L = tokens.shape[0]
    pos = jnp.arange(L)
    f32 = jnp.float32
    x = w["embed"][tokens].astype(f32)

    def layer(x, p):
        a = _rms(x, p["attn_norm"], eps)
        q = _mm(a, p["q"].astype(f32), quant).reshape(L, m["h"], m["hd"])
        k = _mm(a, p["k"].astype(f32), quant).reshape(L, m["kv"], m["hd"])
        v = _mm(a, p["v"].astype(f32), quant).reshape(L, m["kv"], m["hd"])
        o = _attend(_rope(q, pos, theta), _rope(k, pos, theta), v,
                    m["h"] // m["kv"])
        x = x + _mm(o.reshape(L, -1), p["o"].astype(f32), quant)
        b = _rms(x, p["mlp_norm"], eps)
        g = _mm(b, p["gate"].astype(f32), quant)
        u = _mm(b, p["up"].astype(f32), quant)
        return x + _mm(jax.nn.silu(g) * u, p["down"].astype(f32), quant), None

    stack = {n: w[n] for n in ("q", "k", "v", "o", "gate", "up", "down",
                               "attn_norm", "mlp_norm")}
    x, _ = jax.lax.scan(layer, x, stack)
    h = _rms(x[positions], w["final_norm"], eps)
    return jnp.dot(h, head_table(w).astype(f32).T, precision=HI)


def logits(w: dict, cfg: dict, tokens, positions, *, quant: str = "",
           pad_to: int = 512):
    """Logits (len(positions), vocab) float32 of the sequence ``tokens``
    at ``positions``. The sequence is padded to a multiple of ``pad_to``
    (causal: padding changes no earlier position), so few shapes compile."""
    tokens = np.asarray(tokens, np.int32)
    lp = -(-len(tokens) // pad_to) * pad_to
    padded = np.zeros(lp, np.int32)
    padded[: len(tokens)] = tokens
    keep = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "head_dim", "rms_norm_eps", "rope_theta")
    shape_key = _freeze(cfg, keep)
    with jax.default_matmul_precision("highest"):
        return _logits(w, jnp.asarray(padded),
                       jnp.asarray(np.asarray(positions, np.int32)),
                       shape_key, quant)
