"""Latent attention (MLA), the DeepSeek-V3 expert layer and the decode
kernel's latent path at smoke widths on the CPU: the absorbed decode
against the expanded form, the expert layer's shares against the plain
reference's uncut layer (``bench/references/mla_moe_decoder.py``), the
serving layer's dropless dispatch, and the kernel's GQA path against its
recorded outputs. Float32 throughout: sums in a different order agree to
1e-5. Serving through ``Server`` is in ``test_mla_serve.py``.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels.flash_decode import flash_decode
from repro.models import attention, moe
from repro.models.layers import swiglu

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import cells  # noqa: E402

HI = jax.lax.Precision.HIGHEST

@pytest.fixture(scope="module")
def ref():
    """The plain reference module (its expert layer)."""
    return cells.load_module(BENCH / "references" / "mla_moe_decoder.py",
                             "bench_reference_mla_moe_decoder")


@pytest.mark.parametrize("use_kernel", [False, True])
def test_absorbed_decode_equals_expanded(use_kernel):
    """A token decoded in the absorbed form (``q̃ = W_UK·q_nope`` against
    the cached latent rows, ``W_UV`` after) gets the expanded form's
    output over the same prefix, at ragged cache lengths; the kernel
    (interpreted) reads values as the first ``r`` latent columns."""
    cfg = get_smoke("moonlight-16b-a3b")
    p = attention.init(jax.random.key(0), cfg)
    b, l, max_seq = 3, 12, 16
    x = jax.random.normal(jax.random.key(1), (b, l, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))
    full = jax.jit(attention.mla_forward, static_argnums=1)(p, cfg, x, pos)
    lengths = jnp.array([11, 5, 1], jnp.int32)
    _, cache = jax.jit(attention.mla_prefill, static_argnums=(1, 4))(
        p, cfg, x, pos, max_seq, lengths=lengths)
    stacked = {"ckv": cache["ckv"][None]}  # a group of one layer
    xt = jnp.take_along_axis(x, lengths[:, None, None], axis=1)
    o, new = jax.jit(attention.mla_decode, static_argnums=1,
                     static_argnames="use_kernel")(
        p, cfg, xt, stacked, lengths, 0, use_kernel=use_kernel)
    want = jnp.take_along_axis(full, lengths[:, None, None], axis=1)
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)
    # the new row lands at its ring slot, every other row unchanged
    rows = np.asarray(new["ckv"][0])
    before = np.asarray(cache["ckv"])
    for i, n in enumerate(np.asarray(lengths)):
        keep = np.arange(max_seq) != n
        np.testing.assert_array_equal(rows[i, keep], before[i, keep])
        assert np.abs(rows[i, n]).sum() > 0


def _moe_cfg(experts=64, held=0):
    return get_smoke("moonlight-16b-a3b").scaled(
        d_model=32, moe_d_ff=16, n_experts=experts, experts_per_token=6,
        n_shared_experts=2, n_local_experts=held)


def test_expert_shares_add_up_to_the_uncut_layer(ref):
    """Eight chips' shares of a 64-expert layer, each routing over all 64
    and computing its own 8 experts' part, with the shared experts counted
    once, add up to the reference's uncut layer; each share is the
    reference's share of the same experts."""
    cfg = _moe_cfg()
    p = moe.init(jax.random.key(0), cfg)
    p["bias"] = 0.05 * jax.random.normal(jax.random.key(1), (64,))
    x = jax.random.normal(jax.random.key(2), (24, cfg.d_model))
    rp = {"router": p["router"], "bias": p["bias"], "gate": p["w1"],
          "up": p["w3"], "down": p["w2"], "shared_gate": p["shared"]["w1"],
          "shared_up": p["shared"]["w3"], "shared_down": p["shared"]["w2"]}
    rcfg = {"norm_topk_prob": True,
            "routed_scaling_factor": cfg.routed_scale}
    routed = {k: v for k, v in p.items() if k != "shared"}
    total = swiglu(x, p["shared"]["w1"], p["shared"]["w2"], p["shared"]["w3"])
    share_fn = jax.jit(lambda pp, off: moe.forward(
        pp, _moe_cfg(held=8), x, expert_offset=off, dropless=True)[0])
    ref_fn = jax.jit(lambda pp, off: ref.moe(
        x, pp, {"held": 8, "topk": 6}, rcfg, offset=off, shared=False))
    for i in range(8):
        sl = slice(8 * i, 8 * i + 8)
        out = share_fn(dict(routed, w1=p["w1"][sl], w2=p["w2"][sl],
                            w3=p["w3"][sl]), 8 * i)
        total = total + out
        want = ref_fn(dict(rp, gate=p["w1"][sl], up=p["w3"][sl],
                           down=p["w2"][sl]), 8 * i)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    uncut = ref.moe(x, rp, {"held": 64, "topk": 6}, rcfg)
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)


def test_selection_bias_changes_the_chosen_set():
    """The bias picks the top-k; the gates stay the unbiased scores'."""
    cfg = _moe_cfg()
    p = moe.init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(2), (64, cfg.d_model))
    g0, i0, _ = moe.route(p, cfg, x)
    p["bias"] = 0.05 * jax.random.normal(jax.random.key(1), (64,))
    g1, i1, _ = moe.route(p, cfg, x)
    changed = np.any(np.sort(i0, -1) != np.sort(i1, -1), axis=-1)
    assert 0 < changed.sum() < len(x)
    np.testing.assert_allclose(g1.sum(-1), cfg.routed_scale, rtol=1e-6)


def test_dropless_token_alone_equals_in_a_batch():
    """Serving's expert layer drops nothing: a token's output is the same
    alone and among 64 tokens that all pick the same experts, where the
    training layer's capacity factor drops most of them."""
    cfg = get_smoke("moonlight-16b-a3b")
    p = moe.init(jax.random.key(0), cfg)
    base = jax.random.normal(jax.random.key(1), (1, cfg.d_model))
    x = base + 0.01 * jax.random.normal(jax.random.key(2),
                                        (64, cfg.d_model))
    fwd = jax.jit(lambda xs: moe.forward(p, cfg, xs, dropless=True)[0])
    batch = fwd(x)
    alone = jnp.concatenate([fwd(x[i:i + 1]) for i in range(len(x))])
    np.testing.assert_allclose(batch, alone, rtol=1e-6, atol=1e-6)
    capped, _ = moe.forward(p, cfg, x)
    assert not np.allclose(capped, alone, rtol=1e-3, atol=1e-3)


def test_flash_decode_gqa_unchanged():
    """The GQA call (values of their own) gives, bit for bit, what the
    kernel gave before it learnt latent caches (outputs recorded then, in
    interpret mode, float32 and bfloat16)."""
    gold = np.load(Path(__file__).with_name("data") / "flash_decode_gqa.npz")
    rng = np.random.default_rng(0)
    q = rng.standard_normal((3, 8, 16)).astype(np.float32)
    k = rng.standard_normal((3, 2, 64, 16)).astype(np.float32)
    v = rng.standard_normal((3, 2, 64, 16)).astype(np.float32)
    lens = jnp.asarray([64, 17, 1], jnp.int32)
    for dt, key in ((jnp.float32, "out_f32"), (jnp.bfloat16, "out_bf16")):
        out = flash_decode(jnp.asarray(q, dt), jnp.asarray(k, dt),
                           jnp.asarray(v, dt), lens, s_block=32,
                           interpret=True)
        np.testing.assert_array_equal(np.asarray(out), gold[key])


def test_flash_decode_latent_values_are_key_columns():
    """A latent cache (no ``v_cache``): one KV head serves every query
    head, values are the first ``v_width`` key columns, the scale is the
    caller's; float32 against softmax attention written out."""
    rng = np.random.default_rng(1)
    b, h, s, w, vw, scale = 3, 8, 64, 128, 96, 0.2
    q = jnp.asarray(rng.standard_normal((b, h, w)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((b, 1, s, w)), jnp.float32)
    lens = jnp.asarray([64, 33, 2], jnp.int32)
    out = flash_decode(q, c, None, lens, s_block=32, interpret=True,
                       v_width=vw, scale=scale)
    sc = jnp.einsum("bhw,bsw->bhs", q, c[:, 0], precision=HI) * scale
    sc = jnp.where(jnp.arange(s)[None, None] < lens[:, None, None], sc,
                   -jnp.inf)
    want = jnp.einsum("bhs,bsv->bhv", jax.nn.softmax(sc, -1),
                      c[:, 0, :, :vw], precision=HI)
    assert out.shape == (b, h, vw)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
