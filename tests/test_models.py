"""Per-arch smoke tests (reduced configs) + decode/forward parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.transformer as T
from repro.configs import ARCHS, get, get_smoke
from repro.launch.specs import SHAPES, skip_reason
from repro.models import Model

B, L = 2, 64


def _batch(cfg, key, b=B, l=L):
    if cfg.frontend == "audio_stub":
        return {"frames": jax.random.normal(key, (b, l, cfg.d_model)),
                "labels": jax.random.randint(key, (b, l), 0, cfg.vocab)}
    if cfg.frontend == "vision_stub":
        lt = l - cfg.n_prefix_tokens
        return {"patches": jax.random.normal(key, (b, cfg.n_prefix_tokens,
                                                   cfg.d_model)),
                "tokens": jax.random.randint(key, (b, lt), 0, cfg.vocab),
                "labels": jax.random.randint(key, (b, lt), 0, cfg.vocab)}
    return {"tokens": jax.random.randint(key, (b, l), 0, cfg.vocab),
            "labels": jax.random.randint(key, (b, l), 0, cfg.vocab)}


@pytest.fixture(autouse=True)
def _no_remat(monkeypatch):
    monkeypatch.setattr(T, "REMAT", False)  # faster CPU smoke


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    m = Model(cfg)
    params = m.init(jax.random.key(1))
    batch = _batch(cfg, jax.random.key(2))
    loss, metrics = jax.jit(m.loss_fn)(params, batch, jax.random.key(3))
    assert jnp.isfinite(loss), arch
    grads = jax.grad(lambda p: m.loss_fn(p, batch, jax.random.key(3))[0])(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert bool(jnp.all(jnp.isfinite(g))), (arch, path)


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get(a).has_decode])
def test_smoke_decode_step(arch):
    cfg = get_smoke(arch)
    m = Model(cfg)
    params = m.init(jax.random.key(1))
    cache = m.init_cache(B, 128)
    ids = jnp.zeros((B,), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    nxt, ok, cache, _ = jax.jit(m.decode_step)(params, cache, ids, pos,
                                               jax.random.key(4))
    assert nxt.shape == (B,)
    assert bool(jnp.all((nxt >= 0) & (nxt < cfg.vocab))), arch


@pytest.mark.parametrize(
    "arch", ["tinyllama-1.1b", "mixtral-8x22b", "mamba2-780m",
             "recurrentgemma-9b"]
)
def test_prefill_decode_parity(arch):
    """Hidden state from step-by-step decode must match the parallel
    forward pass — validates every cache type (KV ring, SSM state, RG-LRU
    state, conv tails). capacity_factor is raised so MoE never drops:
    capacity dropping legitimately differs between batched forward
    (overflow drops) and one-token decode (never overflows)."""
    cfg = get_smoke(arch).scaled(head_mode="exact", capacity_factor=16.0)
    m = Model(cfg)
    params = m.init(jax.random.key(1))
    l = 24
    toks = jax.random.randint(jax.random.key(2), (1, l), 0, cfg.vocab)

    from repro.models import transformer
    from repro.models.layers import COMPUTE_DTYPE

    x = params["embed"][toks].astype(COMPUTE_DTYPE)
    pos_full = jnp.broadcast_to(jnp.arange(l), (1, l))
    h_full, _ = transformer.apply_trunk(params, cfg, x, pos_full)

    cache = m.init_cache(1, 64)
    hs = []
    for t in range(l):
        xt = params["embed"][toks[:, t]][:, None].astype(COMPUTE_DTYPE)
        ht, cache = transformer.apply_trunk_decode(
            params, cfg, xt, cache, jnp.array([t], jnp.int32)
        )
        hs.append(ht[:, 0])
    h_dec = jnp.stack(hs, axis=1)
    # bf16 trunk: per-step rounding accumulates. recurrentgemma's RG-LRU
    # additionally reorders float ops (associative_scan prefill vs
    # sequential decode), so it gets a little more slack.
    tol = 0.12 if arch == "recurrentgemma-9b" else 0.08
    np.testing.assert_allclose(
        np.asarray(h_full, np.float32),
        np.asarray(h_dec, np.float32),
        rtol=tol, atol=tol,
    )
    # tighter check on correlation (catches structural bugs, not rounding)
    a = np.asarray(h_full, np.float32).ravel()
    b = np.asarray(h_dec, np.float32).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.999, corr


# recurrentgemma excluded from the strict token-equality check: the RG-LRU
# prefill uses associative_scan while decode is sequential — float
# reordering at ~1e-3 can flip an argmax tie. Its cache correctness is
# covered by test_prefill_decode_parity (hidden-state corr > 0.999).
@pytest.mark.parametrize("arch", ["mixtral-8x22b", "tinyllama-1.1b",
                                  "mamba2-780m"])
def test_prefill_matches_decode_continuation(arch):
    """prefill() then decode_step() must continue exactly like pure
    decode_step() from scratch."""
    cfg = get_smoke(arch).scaled(head_mode="exact", capacity_factor=16.0)
    m = Model(cfg)
    params = m.init(jax.random.key(1))
    l = 12
    toks = jax.random.randint(jax.random.key(2), (1, l), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    # path A: prefill the prompt
    nxt_a, ok_a, pos_a, cache_a = m.prefill(params, batch, jax.random.key(7),
                                            max_seq=64)
    # path B: feed tokens one-by-one through decode_step
    cache_b = m.init_cache(1, 64)
    for t in range(l):
        nxt_b, ok_b, cache_b, _ = m.decode_step(
            params, cache_b, toks[:, t], jnp.array([t], jnp.int32),
            jax.random.fold_in(jax.random.key(9), t),
        )
    # the *next* sampled token after both paths, same key => same sample
    n_a, _, _, _ = m.decode_step(params, cache_a, nxt_a,
                                 pos_a, jax.random.key(11))
    # replicate: feed nxt_a as the continuation token in path B
    n_b, _, _, _ = m.decode_step(params, cache_b, nxt_a,
                                 jnp.array([l], jnp.int32),
                                 jax.random.key(11))
    assert int(n_a[0]) == int(n_b[0])


def test_skip_matrix_documented():
    """The 40-cell grid matches DESIGN.md §4: 8 skips, 32 runnable."""
    skips = []
    for a in ARCHS:
        cfg = get(a)
        for s in SHAPES:
            if skip_reason(cfg, s):
                skips.append((a, s))
    assert len(skips) == 8, skips
    assert ("hubert-xlarge", "decode_32k") in skips
    assert ("hubert-xlarge", "long_500k") in skips
    for a in ["qwen3-moe-30b-a3b", "stablelm-3b", "granite-8b",
              "tinyllama-1.1b", "starcoder2-3b", "paligemma-3b"]:
        assert (a, "long_500k") in skips
    for a in ["mixtral-8x22b", "mamba2-780m", "recurrentgemma-9b"]:
        assert not skip_reason(get(a), "long_500k")


def test_prefill_kernel_matches_decode_kernel():
    """Where decode runs the flash_decode kernel, a causal prompt attends
    through flash_prefill: each position, over its own prefix of the ring
    prefill builds, gets what the decode kernel computes for the token at
    that position, bit for bit — so a batched prefill and a token-by-token
    decode agree — and the result is blockwise attention's."""
    from repro.models import attention

    cfg = get_smoke("tinyllama-1.1b")
    p = attention.init(jax.random.key(0), cfg)
    b, l, max_seq = 3, 12, 16
    x = jax.random.normal(jax.random.key(1), (b, l, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))
    lengths = jnp.array([12, 5, 1], jnp.int32)
    out_ref, cache_ref = attention.prefill(p, cfg, x, pos, max_seq,
                                           lengths=lengths, use_kernel=False)
    out_k, cache_k = attention.prefill(p, cfg, x, pos, max_seq,
                                       lengths=lengths, use_kernel=True)
    for key in ("k", "v"):
        np.testing.assert_array_equal(cache_k[key], cache_ref[key])
    valid = np.arange(l)[None, :] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(
        np.asarray(out_k)[valid], np.asarray(out_ref)[valid],
        rtol=1e-5, atol=1e-5,
    )
    # token by token through the decode kernel
    cache = attention.init_cache(cfg, b, max_seq, jnp.float32)
    for t in range(l):
        o, cache = attention.decode(p, cfg, x[:, t : t + 1], cache,
                                    jnp.full((b,), t, jnp.int32),
                                    use_kernel=True)
        np.testing.assert_array_equal(np.asarray(o[:, 0])[valid[:, t]],
                                      np.asarray(out_k[:, t])[valid[:, t]])

