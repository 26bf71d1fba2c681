"""Moonlight's block (latent attention, expert layers) served at smoke
widths on the CPU, prefill into the latent cache and then decode through
it, against the plain float32 reference of
``bench/references/mla_moe_decoder.py`` (published equations, expanded
MLA, every expert routed), compared on logits.

Tolerances, and why:
* float32 trunk against the reference: both compute the same function in
  float32 in a different order (absorbed against expanded attention,
  blockwise against plain softmax, a ring cache against a whole
  sequence), so logits agree to float32 rounding: 1e-4 of their spread.
* float32 trunk through ``Server``: the weights make sampling greedy in
  effect, so each served token is the reference's best, up to float32
  rounding: 1e-3 logit standard deviations. (A bfloat16 trunk at these
  widths can route a token to another expert than the float32
  reference, and one of 2 experts changed moves its logits by up to 0.8
  standard deviations, as far as the float8 control: so the benchmark's
  chip cell holds the mean gap over many positions, and its tiny cell,
  ``bench/tests/test_bench_mla.py``, serves in float32 too.)
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.transformer as T
from repro.models.model import Model
from repro.serve.server import ServeConfig, Server

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import cells  # noqa: E402

HI = jax.lax.Precision.HIGHEST

# the benchmark's configuration at smoke widths: 1 dense + 2 expert
# layers, 4 of 8 experts held, top-2, one shared expert
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "num_hidden_layers": 3, "vocab_size": 4096,
}


@pytest.fixture(autouse=True)
def _no_remat(monkeypatch):
    monkeypatch.setattr(T, "REMAT", False)


@pytest.fixture(scope="module")
def tiny():
    """(config, reference module, runner module, reference weights)."""
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                     .read_text())
    cfg.update(TINY)
    cfg["synthetic_weights"] = dict(cfg["synthetic_weights"],
                                    embed_clusters=64, router_bias_std=0.1)
    ref = cells.reference(cfg)
    runner = cells.load_module(BENCH / "runners" / "lm_serve_mla.py",
                               "bench_runner_lm_serve_mla")
    w = ref.init_weights(cfg, jax.random.key(5))
    return cfg, ref, runner, w


def _program(tiny):
    cfg, ref, runner, w = tiny
    arch = runner.arch_config(cfg, ref)
    return arch, runner.program_params(w, Model(arch))


def test_float32_trunk_matches_reference(tiny):
    """Prefill of a prompt into the latent cache, then token-by-token
    absorbed decode through it, in float32: logits at every position
    equal the reference's full expanded forward pass."""
    cfg, ref, _, w = tiny
    arch, params = _program(tiny)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 20)
    n_prompt, max_seq = 9, 32
    x = params["embed"][jnp.asarray(toks[:n_prompt])][None]
    h, part = T.apply_trunk_prefill(
        params, arch, x, jnp.arange(n_prompt)[None], max_seq=max_seq,
        lengths=jnp.asarray([n_prompt]))
    cache = T.insert_cache_slots(T.init_cache(arch, 1, max_seq, jnp.float32),
                                 part, jnp.asarray([0]))
    hs = [h[0, n_prompt - 1]]
    step = jax.jit(lambda pr, xd, c, p: T.apply_trunk_decode(pr, arch, xd,
                                                             c, p))
    for p in range(n_prompt, len(toks) - 1):
        xd = params["embed"][jnp.asarray([toks[p]])][:, None]
        hd, cache = step(params, xd, cache, jnp.asarray([p]))
        hs.append(hd[0, 0])
    got = np.asarray(jnp.dot(jnp.stack(hs), ref.head_table(w).T,
                             precision=HI))
    want = np.asarray(ref.logits(w, cfg, toks, np.arange(n_prompt - 1,
                                                         len(toks) - 1)))
    spread = want.max() - want.min()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * spread)


def test_served_tokens_match_reference(tiny, monkeypatch):
    """Through ``Server`` (batched prefill into the latent cache, fused
    absorbed decode, the amortized head), with a float32 trunk: every
    served token is the reference's best at its position, up to float32
    rounding (1e-3 logit standard deviations); and the paged latent pool
    serves the very tokens the dense latent rings do (placement is
    page-table arithmetic over the same rows)."""
    import functools

    import repro.serve.server as server_lib

    cfg, ref, _, w = tiny
    arch, params = _program(tiny)
    monkeypatch.setattr(server_lib, "Model", functools.partial(
        Model, precision_policy="f32"))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (5, 16, 11, 3)]
    served = []
    for block_len in (0, 8):
        srv = Server(arch, params, ServeConfig(
            batch_slots=3, max_seq=48, max_new_tokens=12, decode_window=4,
            prefill_chunk=16, seed=3, block_len=block_len))
        served.append({r.request_id: r.tokens for r in srv.run(prompts)})
    assert served[0] == served[1]
    for rid, toks in served[0].items():
        assert len(toks) == 12
        p = prompts[rid]
        lg = np.asarray(ref.logits(w, cfg, p + toks,
                                   len(p) - 1 + np.arange(12)))
        got = lg[np.arange(12), toks]
        assert ((lg.max(-1) - got) / lg.std(-1)).max() <= 1e-3
