"""The plain float32 dense-decoder reference against the program's own trunk:
prefill of a prompt into the cache, then token-by-token decode through
that cache, at smoke widths on the CPU.

Tolerances:
* float32 weights and activations (the program's f32 policy): both sides
  compute the same function in float32 in a different order (blockwise
  online softmax vs a plain softmax, a ring cache vs a whole sequence),
  so logits agree to float32 rounding over two layers: 1e-4 of the
  logits' spread.
* bfloat16 trunk (what the benchmark serves): the program rounds every
  activation to bfloat16 (2**-8 relative) and the reference does not, so
  the logits differ by rounding that grows through the layers; the
  argmax must agree wherever the reference's top two logits are at
  least 5% of the spread apart, and the widest gap of the program's
  argmax below the reference's best stays under 5% of the spread.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import tiny_cell
from harness import cells

HI = jax.lax.Precision.HIGHEST


def _program_logits(cfg, w, tokens, n_prompt, dtype):
    """Prefill ``tokens[:n_prompt]`` into a cache, decode the rest one
    token at a time, and return logits at positions n_prompt-1 .. end-1."""
    from repro.models import transformer

    runner = cells.runner(cells.load_cell("mistral-7b.decode-heavy"))
    ref = cells.reference(cfg)
    arch = runner.arch_config(cfg, ref)
    from repro.models.model import Model

    params = runner.program_params(w, Model(arch))
    max_seq = 256
    emb = params["embed"]
    x = emb[jnp.asarray(tokens[:n_prompt])][None].astype(dtype)
    pos = jnp.arange(n_prompt)[None]
    h, part = transformer.apply_trunk_prefill(
        params, arch, x, pos, max_seq=max_seq,
        lengths=jnp.asarray([n_prompt]))
    cache = transformer.init_cache(arch, 1, max_seq, dtype)
    cache = transformer.insert_cache_slots(cache, part, jnp.asarray([0]))
    hs = [h[0, n_prompt - 1]]
    step = jax.jit(lambda pr, xd, c, p: transformer.apply_trunk_decode(
        pr, arch, xd, c, p))
    for p in range(n_prompt, len(tokens)):
        xd = emb[jnp.asarray([tokens[p]])][:, None].astype(dtype)
        hd, cache = step(params, xd, cache, jnp.asarray([p]))
        hs.append(hd[0, 0])
    hq = jnp.stack(hs[:-1]).astype(jnp.float32)
    head = ref.head_table(w).astype(jnp.float32)
    return np.asarray(jnp.dot(hq, head.T, precision=HI))


@pytest.mark.parametrize("trunk", ["float32", "bfloat16"])
def test_reference_matches_program_prefill_then_decode(trunk):
    cfg = dict(tiny_cell("mistral-7b.decode-heavy").config)
    cfg["dtype"] = dict(cfg["dtype"], trunk=trunk)
    ref = cells.reference(cfg)
    w = ref.init_weights(cfg, jax.random.key(3))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg["vocab_size"], size=24).tolist()
    n_prompt = 10
    got = _program_logits(cfg, w, tokens, n_prompt, jnp.dtype(trunk))
    want = np.asarray(ref.logits(w, cfg, tokens,
                                 np.arange(n_prompt - 1, len(tokens) - 1)))
    spread = want.std(axis=1, keepdims=True)
    if trunk == "float32":
        assert np.max(np.abs(got - want) / spread) < 1e-4
        return
    best = want.max(axis=1)
    pick = want[np.arange(len(want)), got.argmax(axis=1)]
    gap = (best - pick) / spread[:, 0]
    top2 = np.sort(want, axis=1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) / spread[:, 0] > 0.05
    assert clear.any()
    assert (gap[clear] == 0).all()
    assert gap.max() < 0.05


def test_control_reads_wider_gaps_than_the_program():
    """The control (weights in float8_e4m3, bf16 activations) puts other
    tokens first than the float32 reference does, by more than the
    bfloat16 program does."""
    cfg = dict(tiny_cell("mistral-7b.decode-heavy").config)
    ref = cells.reference(cfg)
    w = ref.init_weights(cfg, jax.random.key(5))
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg["vocab_size"], size=200).tolist()
    pos = np.arange(8, 199)
    want = np.asarray(ref.logits(w, cfg, tokens, pos))
    ctl = np.asarray(ref.logits(w, cfg, tokens, pos, quant="fp8"))
    prog = _program_logits(cfg, w, tokens, 9, jnp.bfloat16)
    spread = want.std(axis=1)

    def widest(other):
        pick = want[np.arange(len(want)), other.argmax(axis=1)]
        return float(((want.max(axis=1) - pick) / spread).max())

    assert widest(ctl) > 3 * widest(prog)
