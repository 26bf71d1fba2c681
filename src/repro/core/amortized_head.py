"""Amortized log-linear head: the paper's algorithms as an LM softmax layer.

The softmax head of a language model is a log-linear model: features
``φ(x_i)`` are the output-embedding rows ``E_i``, parameters ``θ`` are the
final hidden state ``h``; ``y_i = h · E_i``. This module packages the
paper's estimators as a drop-in head with three modes (the three columns of
the paper's Table 2):

* ``exact``      — dense logits + logsumexp, O(n d) per token (baseline).
* ``topk_only``  — truncate the distribution to S (Vijayanarasimhan et al.
  2014 baseline; biased, fails for spread-out distributions).
* ``amortized``  — the paper: ``log Ẑ`` from Algorithm 3 over S ∪ T. The
  gradient of the surrogate loss w.r.t. (h, E) is *exactly* Algorithm 4's
  expectation estimator applied to ``f = φ``, so plain autodiff through the
  estimator gives the paper's learning method.

All estimator math lives in :mod:`repro.core.estimators` and is SHARED with
the distributed head (models/head.py): this module is the one-shard
instantiation — shard-local partials combined with the identity instead of
psum/pmax collectives. Sampling (decode) uses the lazy-Gumbel machinery of
:mod:`repro.core.gumbel` through the same shared probe.

Token-level work is chunked (:func:`repro.core.estimators.chunked_map`) so
the (tokens, k+l, d) gather never materializes at full sequence length.

Padded vocabularies: models pad ``n`` (logical vocab) up to a multiple of
256 for TP sharding. Pad rows sit at the END of the table; this head slices
``emb[:n]`` up front, so pads contribute exactly zero probability.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import estimators as est
from repro.core import mips
from repro.core.gumbel import SampleResult, default_kl

__all__ = [
    "HeadConfig", "head_loss", "head_sample", "make_index", "uses_index",
]

_MODES = ("exact", "topk_only", "amortized")
_MIPS = ("exact", "ivf", "ivfpq", "lsh")


@dataclasses.dataclass(frozen=True)
class HeadConfig:
    n: int  # logical vocab size (pad rows beyond n are never touched)
    k: int = 0  # |S|; 0 -> default_kl(n, delta)
    l: int = 0  # |T|; 0 -> same as k
    mode: str = "amortized"  # exact | topk_only | amortized
    mips: str = "exact"  # exact | ivf | ivfpq | lsh  (top-k probe index)
    n_probe: int = 8
    adaptive_probe: bool = False  # certificate-gated staged widening: probe
    #   n_probe_init clusters per token, widen geometrically (up to
    #   n_probe_max) only for tokens whose gap certificate fails
    #   (core/mips/adaptive.py); requires mips in {ivf, ivfpq}
    n_probe_init: int = 0  # 0 -> n_probe (adaptive start width)
    n_probe_max: int = 0  # 0 -> n_probe (adaptive width ceiling)
    use_kernel: bool = False
    fused_decode: bool = False  # decode: single-dispatch Pallas screen/
    #   select + tail/argmax pipeline (kernels/decode_fused.py); samples
    #   are bit-identical to use_kernel=True unfused decode
    chunk: int = 256  # token chunk for gathers
    delta: float = 1e-4
    c: float = 0.0  # assumed approximate-top-k gap (Def 3.1)
    min_amortized_n: int = 4096  # below this, amortization can't win: exact
    score_dtype: str = "f32"  # "bf16": halve candidate-gather HBM traffic
    #   (logsumexp still accumulates in f32; §Perf iteration 3b)

    def resolved(self) -> "HeadConfig":
        if self.mode not in _MODES:
            raise ValueError(
                f"unknown head mode {self.mode!r}; valid choices: {_MODES}"
            )
        if self.mips not in _MIPS:
            raise ValueError(
                f"unknown head MIPS backend {self.mips!r}; "
                f"valid choices: {_MIPS}"
            )
        if self.adaptive_probe and self.mips not in ("ivf", "ivfpq"):
            raise ValueError(
                "adaptive_probe requires a clustered MIPS backend "
                f"(ivf | ivfpq), got {self.mips!r}"
            )
        init = self.n_probe_init or self.n_probe
        maxp = self.n_probe_max or self.n_probe
        if self.adaptive_probe and init > maxp:
            raise ValueError(
                f"n_probe_init={init} exceeds n_probe_max={maxp}"
            )
        k = self.k or default_kl(self.n, self.delta, self.c)
        l = self.l or k
        mode = self.mode
        if mode != "exact" and self.n < self.min_amortized_n:
            # √n savings are nil for tiny output spaces (DESIGN.md
            # §Arch-applicability, e.g. hubert's 504-way head).
            mode = "exact"
        k = min(k, self.n // 2)
        l = min(l, self.n // 2)
        return dataclasses.replace(
            self, k=k, l=l, mode=mode, n_probe_init=init, n_probe_max=maxp
        )

    @property
    def score_dt(self):
        return jnp.bfloat16 if self.score_dtype == "bf16" else jnp.float32


class HeadLossOut(NamedTuple):
    loss: jax.Array  # (T,) per-token negative log-likelihood
    log_z: jax.Array  # (T,) partition estimates (diagnostics)


def uses_index(cfg: HeadConfig) -> bool:
    """Whether this head builds a MIPS index at all — the ONE encoding of
    the rule (exact mode or exact backend, including resolved()'s
    min_amortized_n downgrade, runs straight off ``emb``). Callers that
    prepare index inputs (e.g. the trainer's donation-safe embedding
    copy) check this first to avoid allocating for a None index."""
    cfg = cfg.resolved()
    return cfg.mode != "exact" and cfg.mips != "exact"


def make_index(
    cfg: HeadConfig, emb: jax.Array, mesh=None, axis: str = "model"
) -> mips.Index | None:
    """Build the MIPS index over the embedding rows.

    Returns a stateful :class:`repro.core.mips.Index` (a jax pytree — pass
    it through jitted steps as an argument and ``index.refresh(emb)`` it
    when the embedding drifts; see train/trainer.py), or None when the
    exact top-k path applies.

    With ``mesh`` given, builds a :class:`repro.core.mips.ShardedIndex`:
    one shard-local index per TP slice of the FULL (padded) table, laid out
    along the mesh ``axis`` for use inside the distributed head's
    ``shard_map`` (pad rows are masked at probe time via ``n_valid``).
    """
    cfg = cfg.resolved()
    if not uses_index(cfg):
        return None  # exact top-k runs directly off `emb`
    mp = mesh.shape[axis] if mesh is not None else 1
    if cfg.mips == "ivf":
        mips_cfg = mips.IVFConfig(
            n_probe=cfg.n_probe, n_probe_init=cfg.n_probe_init,
            n_probe_max=cfg.n_probe_max, use_kernel=cfg.use_kernel,
        )
    elif cfg.mips == "ivfpq":
        # quantized production index: re-rank pool sized to the PROBED k
        # (per-shard k when sharded), so the exact re-rank always covers
        # the head's candidate set with screening headroom on top
        k_loc = max(8, cfg.k // mp)
        mips_cfg = mips.PQConfig(
            n_probe=cfg.n_probe, n_probe_init=cfg.n_probe_init,
            n_probe_max=cfg.n_probe_max, use_kernel=cfg.use_kernel,
            rerank=2 * k_loc,
        )
    else:  # "lsh" (resolved() validated the choices)
        # size buckets so the union of table candidates can cover the
        # PROBED k (the default load-based cap may be smaller than k).
        # Sharded: each of the mp per-slice tables holds only n/mp rows
        # and is probed with k/mp, so caps scale down accordingly.
        base_cfg = mips.LSHConfig()
        n_loc = max(1, emb.shape[0] // mp if mesh is not None else cfg.n)
        k_loc = max(8, cfg.k // mp)
        cap_load = mips.default_bucket_cap(n_loc, base_cfg.n_bits)
        cap_k = max(8, math.ceil(2.0 * k_loc / base_cfg.n_tables / 8.0) * 8)
        mips_cfg = mips.LSHConfig(bucket_cap=max(cap_load, cap_k))
    if mesh is not None:
        return mips.build_index(mips_cfg, emb, mesh=mesh, axis=axis)
    # full-table fast path: slicing would copy, and the PQ backend keeps
    # the caller's handle as its fp re-rank rows — pass the resident
    # buffer itself whenever the vocab is unpadded
    db = emb if cfg.n == emb.shape[0] else emb[: cfg.n]
    return mips.build_index(mips_cfg, db)


def head_loss(
    emb: jax.Array,
    h: jax.Array,
    targets: jax.Array,
    key: jax.Array,
    cfg: HeadConfig,
    index: Any = None,
) -> HeadLossOut:
    """Per-token NLL ``log Z - y_target``.

    Args:
      emb: (n_rows, d) output embedding (n_rows >= cfg.n; pads at end).
      h: (T, d) final hidden states.
      targets: (T,) int32 target ids in [0, cfg.n).
    """
    cfg = cfg.resolved()
    embf = emb.astype(jnp.float32)[: cfg.n]
    h = h.astype(jnp.float32)

    def one_chunk(kk, hc, tc):
        return est.loss_partials(
            kk, embf, hc, tc, mode=cfg.mode, k=cfg.k, l=cfg.l, index=index,
            score_dtype=cfg.score_dt, use_kernel=cfg.use_kernel,
        )

    parts = est.chunked_map(
        one_chunk, cfg.chunk, key, h, targets.astype(jnp.int32)
    )
    loss, log_z = est.combine_loss(parts, cfg.mode)
    return HeadLossOut(loss, log_z)


def head_sample(
    emb: jax.Array,
    h: jax.Array,
    key: jax.Array,
    cfg: HeadConfig,
    index: Any = None,
    keys: jax.Array | None = None,
    strict: bool = False,
    strict_live: jax.Array | None = None,
    router: Any = None,
) -> SampleResult:
    """Sample next-token ids for a batch of queries h: (T, d).

    Returns SampleResult with (T,)-shaped fields. ``amortized``/``topk_only``
    both use the top-k probe; ``exact`` uses dense Gumbel-max.

    ``keys`` ((T,) typed PRNG keys) pins per-token randomness so a token's
    sample depends only on its own key, not on batch composition — required
    for the serving engine's fused-decode / single-step bit-equality.
    ``strict`` re-samples tokens whose exactness certificate failed
    (``ok=False``) with the dense exact sampler, inside a ``lax.cond`` so
    the O(n d) fallback only executes on dispatches that actually contain a
    flagged token. The fallback draws from an independent key stream (the
    failed lazy draw is discarded, not reused). ``strict_live`` ((T,) bool)
    restricts the cond's trigger to live rows — a serving batch's frozen
    slots / admission pad rows sample garbage whose failed certificates
    must not charge the whole dispatch the dense fallback.

    With ``cfg.adaptive_probe`` the probe routes through the index's
    certificate-gated staged widening (``topk_adaptive``) and the result's
    ``width`` field carries the per-token effective probe width; ``router``
    optionally predicts each token's starting stage
    (repro.models.router.ProbeRouter).
    """
    cfg = cfg.resolved()
    embf = emb.astype(jnp.float32)[: cfg.n]
    h = est.query_f32(h)
    t = h.shape[0]

    if cfg.mode == "exact":
        idx, mx = est.dense_gumbel_max(key, embf, h, keys=keys)
        return SampleResult(
            idx,
            jnp.ones((t,), bool),
            jnp.zeros((t,), jnp.int32),
            mx,
            jnp.full((t,), -jnp.inf),
            jnp.zeros((t,), bool),
        )

    res = est.local_gumbel_max(
        key, embf, h, k=cfg.k, l=cfg.l, index=index, c=cfg.c, keys=keys,
        fused=cfg.fused_decode, adaptive=cfg.adaptive_probe, router=router,
    )
    if strict:
        if keys is None:
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                key, jnp.arange(t, dtype=jnp.uint32)
            )
        fb_keys = jax.vmap(jax.random.fold_in, (0, None))(
            keys, jnp.uint32(0x5743)  # independent stream for the fallback
        )

        def fallback(_):
            exact_ids, _ = est.dense_gumbel_max(None, embf, h, keys=fb_keys)
            return jnp.where(res.ok, res.index, exact_ids)

        needs_fb = ~res.ok
        if strict_live is not None:
            needs_fb = needs_fb & strict_live
        idx = jax.lax.cond(
            jnp.any(needs_fb), fallback, lambda _: res.index, operand=None
        )
        res = res._replace(index=idx)
    return res
