"""Amortized LM head: loss/grad fidelity vs exact; Table-2 mode ordering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.amortized_head import HeadConfig, head_loss, head_sample, make_index

N, D, T = 4096, 32, 24


@pytest.fixture(scope="module")
def setup():
    emb = jax.random.normal(jax.random.key(0), (N, D)) / np.sqrt(D)
    h = jax.random.normal(jax.random.key(1), (T, D)) * 2.0
    tgt = jax.random.randint(jax.random.key(2), (T,), 0, N)
    return emb, h, tgt


def test_amortized_loss_close_to_exact(setup):
    emb, h, tgt = setup
    le = head_loss(emb, h, tgt, jax.random.key(3),
                   HeadConfig(n=N, mode="exact"))
    la = head_loss(emb, h, tgt, jax.random.key(3),
                   HeadConfig(n=N, k=256, l=256, mode="amortized",
                              min_amortized_n=1))
    np.testing.assert_allclose(
        np.asarray(la.loss), np.asarray(le.loss), rtol=0.05, atol=0.05
    )


def test_amortized_grad_cosine(setup):
    emb, h, tgt = setup
    cfg_e = HeadConfig(n=N, mode="exact")
    cfg_a = HeadConfig(n=N, k=256, l=256, mode="amortized", min_amortized_n=1)

    def loss(mode_cfg, hh, ee):
        return head_loss(ee, hh, tgt, jax.random.key(4), mode_cfg).loss.sum()

    ge_h, ge_e = jax.grad(loss, argnums=(1, 2))(cfg_e, h, emb)
    ga_h, ga_e = jax.grad(loss, argnums=(1, 2))(cfg_a, h, emb)
    cos_h = float((ge_h * ga_h).sum()
                  / (jnp.linalg.norm(ge_h) * jnp.linalg.norm(ga_h)))
    cos_e = float((ge_e * ga_e).sum()
                  / (jnp.linalg.norm(ge_e) * jnp.linalg.norm(ga_e)))
    assert cos_h > 0.99, cos_h
    assert cos_e > 0.95, cos_e


def test_topk_only_is_biased_down(setup):
    """The top-k-only baseline truncates tail mass => log Z under-estimated
    => loss systematically below exact (the paper's §5 criticism)."""
    emb, h, tgt = setup
    le = head_loss(emb, h, tgt, jax.random.key(5),
                   HeadConfig(n=N, mode="exact"))
    lt = head_loss(emb, h, tgt, jax.random.key(5),
                   HeadConfig(n=N, k=64, l=64, mode="topk_only",
                              min_amortized_n=1))
    assert float(lt.loss.mean()) < float(le.loss.mean())
    # and the amortized estimator repairs the bias
    la = head_loss(emb, h, tgt, jax.random.key(5),
                   HeadConfig(n=N, k=64, l=512, mode="amortized",
                              min_amortized_n=1))
    bias_topk = abs(float(lt.loss.mean()) - float(le.loss.mean()))
    bias_amort = abs(float(la.loss.mean()) - float(le.loss.mean()))
    assert bias_amort < bias_topk / 2


def test_tiny_vocab_forces_exact():
    cfg = HeadConfig(n=504, mode="amortized").resolved()
    assert cfg.mode == "exact"


def test_head_sample_distribution(setup):
    emb, h, _ = setup
    cfg = HeadConfig(n=N, k=192, l=192, mode="amortized", min_amortized_n=1)
    hq = h[:1]
    y = np.asarray(emb @ np.asarray(hq[0]))
    p = np.exp(y - y.max())
    p /= p.sum()
    draws = 6000
    keys = jax.random.split(jax.random.key(6), draws)
    samp = jax.jit(lambda k: head_sample(emb, hq, k, cfg).index[0])
    ids = np.asarray(jax.vmap(samp)(keys))
    top = np.argsort(-p)[:10]
    obs = np.array([(ids == t).mean() for t in top])
    tol = 4 * np.sqrt(p[top] * (1 - p[top]) / draws) + 2e-3
    assert (np.abs(obs - p[top]) <= tol).all(), (obs, p[top], tol)


def test_head_with_ivf_index(setup):
    emb, h, tgt = setup
    cfg = HeadConfig(n=N, k=256, l=256, mode="amortized", mips="ivf",
                     n_probe=16, min_amortized_n=1)
    index = make_index(cfg, emb)
    out = head_loss(emb, h, tgt, jax.random.key(7), cfg, index)
    le = head_loss(emb, h, tgt, jax.random.key(7),
                   HeadConfig(n=N, mode="exact"))
    # IVF's approximate top-k only inflates variance; estimates stay close
    np.testing.assert_allclose(
        np.asarray(out.loss), np.asarray(le.loss), rtol=0.1, atol=0.1
    )


def test_padded_vocab_rows_never_contribute(setup):
    emb, h, tgt = setup
    pad = jnp.full((128, D), 100.0)  # adversarial pad rows: huge scores
    emb_p = jnp.concatenate([emb, pad])
    cfg = HeadConfig(n=N, k=128, l=128, mode="amortized", min_amortized_n=1)
    lp = head_loss(emb_p, h, tgt, jax.random.key(8), cfg)
    le = head_loss(emb, h, tgt, jax.random.key(8), cfg)
    np.testing.assert_allclose(
        np.asarray(lp.loss), np.asarray(le.loss), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("fused", [False, True])
def test_sampler_query_rounding_is_an_op(setup, fused):
    """A bf16 query reaches every scoring stage through an explicit
    ``reduce_precision`` to bf16: XLA may otherwise hand a Pallas kernel
    the unrounded f32 it came from, and the fused and unfused samplers
    then score different queries."""
    emb, h, _ = setup
    cfg = HeadConfig(n=N, mode="amortized", mips="ivf", min_amortized_n=1,
                     use_kernel=True, fused_decode=fused)
    index = make_index(cfg, emb)
    keys = jax.random.split(jax.random.key(3), T)
    text = str(jax.make_jaxpr(
        lambda hh: head_sample(emb, hh, None, cfg, index=index, keys=keys)
    )(h.astype(jnp.bfloat16)))
    assert "reduce_precision[exponent_bits=8 mantissa_bits=7]" in text
