"""Shard-local estimator core shared by the single-device and the
distributed (TP-sharded) amortized heads.

One copy of the paper's per-shard math lives here; the two heads differ
only in how partials are *combined*:

* :func:`topk_probe` — the MIPS candidate probe S (index-backed, sublinear)
  or a dense masked scan (the O(v_loc d) baseline);
* :func:`amortized_candidates` / :func:`topk_only_candidates` — S ∪ T with
  stratum log-weights (Algorithm 3's decomposition; the tail T is an iid
  uniform draw from the complement);
* :func:`stratified_logz` — the shard-local partial of ``log Ẑ``
  (Algorithm 3). Autodiff through it is Algorithm 4's expectation estimator
  with f = φ, so the same code serves inference and learning. An optional
  Pallas path (:mod:`repro.kernels.fused_estimator`) streams candidates
  without materializing the (t, k+l, d) gather in HBM;
* :func:`local_gumbel_max` — Algorithm 2 per shard, returning the
  exactness-certificate terms (bound, overflow) that the cross-shard
  combine re-checks against the *global* winner;
* :func:`combine_loss` / :func:`combine_loss_psum` and
  :func:`combine_sample_pmax` — the combines themselves. The single-device
  head (core/amortized_head.py) is literally the one-shard instantiation:
  identity combine instead of psum/pmax collectives (models/head.py).

Conventions: ``emb`` is the shard-LOCAL feature table ``(v_loc, d)`` and all
ids are shard-local row indices. ``n_valid`` (a scalar, possibly traced)
marks how many leading rows are real; rows at/after it (TP vocab padding)
and negative ids (index padding) get -inf stratum weight, so dead candidate
slots drop out of both the logsumexp value and its gradient.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.complement import sample_complement
from repro.core.gumbel import (
    SampleResult,
    TopK,
    TopKSampleResult,
    certificate,
    plan_tail,
    sample_fixed_b,
    topk_fixed_b,
)

__all__ = [
    "ESTIMATOR_DTYPE",
    "LossPartials",
    "topk_probe",
    "sanitize_topk",
    "amortized_candidates",
    "topk_only_candidates",
    "stratified_logz",
    "lsh_sampler_logz",
    "exact_logz",
    "target_partial",
    "loss_partials",
    "combine_loss",
    "combine_loss_psum",
    "local_gumbel_max",
    "local_gumbel_topk",
    "dense_gumbel_max",
    "combine_sample_pmax",
    "chunked_map",
    "query_f32",
]


# Estimator accumulators are ALWAYS float32, independent of the model's
# mixed-precision policy (repro/precision.py): the Algorithm-3 logsumexp
# partials, the Algorithm-2 certificate terms (S_min, bound, perturbed
# maxima), and the cross-shard combines all accumulate in this dtype, so
# approximation error stays attributable to the index (top-k gap c, tail
# draw), never to bf16 rounding. Candidate *scores* may be computed in a
# lower dtype (HeadConfig.score_dtype) — every reduction over them is
# explicitly cast up first.
ESTIMATOR_DTYPE = jnp.float32


def query_f32(h: jax.Array) -> jax.Array:
    """The sampler's queries as f32, holding exactly the values of
    ``h``'s own dtype.

    A bf16 query cast up to f32 is exact, but XLA is allowed to hand some
    consumers the f32 value the bf16 one was rounded from (it drops the
    convert pair as "excess precision") and others the rounded one. On a
    TPU v5e that split the fused decode: its tail kernel scored the
    unrounded query, the screen kernel and the unfused path the rounded
    one, and the engines' samples diverged. ``reduce_precision`` makes the
    rounding an op of its own, so every stage, XLA or Pallas, scores one
    query (a no-op for f32 input)."""
    fi = jnp.finfo(h.dtype)
    return jax.lax.reduce_precision(h.astype(jnp.float32), fi.nexp, fi.nmant)


class LossPartials(NamedTuple):
    log_z: jax.Array  # (t,) shard-local stratified partial of log Ẑ (Alg 3)
    y_t: jax.Array  # (t,) target logit where locally owned, else 0.0


# --------------------------------------------------------------------------
# candidate stats: top-k probe + tail draw
# --------------------------------------------------------------------------
def topk_probe(
    emb: jax.Array, h: jax.Array, k: int, *, index: Any = None, n_valid=None
) -> TopK:
    """Local top-k candidates S for queries ``h (t, d)``.

    Index-backed (sublinear per query) when ``index`` is given, else a dense
    masked scan of ``emb (v_loc, d)``. Slots holding ids >= n_valid (vocab
    padding) or < 0 (index padding) come back with value -inf.
    """
    if index is None:
        scores = (h @ emb.T).astype(jnp.float32)
        if n_valid is not None:
            ok = jnp.arange(emb.shape[0]) < n_valid
            scores = jnp.where(ok[None, :], scores, -jnp.inf)
        vals, ids = jax.lax.top_k(scores, k)
        return TopK(ids.astype(jnp.int32), vals)
    tk = index.topk_batch(h, k)
    ids = tk.ids.astype(jnp.int32)
    ok = ids >= 0
    if n_valid is not None:
        ok &= ids < n_valid
    return TopK(ids, jnp.where(ok, tk.values.astype(jnp.float32), -jnp.inf))


def sanitize_topk(topk: TopK, n) -> tuple[jax.Array, jax.Array]:
    """Remap dead probe slots to harmless virtual ids for complement draws.

    Index pads (-1) and vocab pads (>= n_valid) come back from the probe
    with value -inf. Feeding their raw ids into
    :func:`repro.core.complement.sample_complement` breaks its
    order-statistics bijection (a -1 sorts FIRST and shifts every tail draw
    up — the lowest rows would never be sampled). Replacing each dead slot
    with the distinct id ``n + slot`` keeps the excluded set strictly
    increasing while placing the dead entries past every possible draw, so
    they exclude nothing. Returns (sanitized ids (t, k), per-token valid
    count (t,)).
    """
    t, k = topk.ids.shape
    valid = ~jnp.isneginf(topk.values)
    virt = jnp.asarray(n, jnp.int32) + jnp.arange(k, dtype=jnp.int32)[None, :]
    return jnp.where(valid, topk.ids, virt), valid.sum(1).astype(jnp.int32)


def amortized_candidates(
    key: jax.Array, topk: TopK, n, l: int
) -> tuple[jax.Array, jax.Array]:
    """S ∪ T with stratum log-weights (Algorithm 3).

    ``n`` is the number of valid local rows (may be a traced per-shard
    scalar). Returns (ids (t, k+l), log_w (t, k+l)); dead S slots (masked
    probe results) carry -inf weight, are excluded from the complement via
    :func:`sanitize_topk`, and the tail stratum's support and weight use
    the per-token count of VALID exclusions, so the estimator stays
    unbiased under partial probe fills (sparse IVF clusters / LSH buckets).
    """
    t, k = topk.ids.shape
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(t, dtype=jnp.uint32)
    )
    ids_clean, k_valid = sanitize_topk(topk, n)
    s_sorted = jnp.sort(ids_clean, axis=1)
    n_i = jnp.asarray(n, jnp.int32)

    # tail = |complement of the VALID S slots| = n - kv elements; empty
    # tails (all-pad shards) draw in-range junk that the -inf stratum
    # weight below neutralizes
    tail = jax.vmap(
        lambda kk, ss, kv: sample_complement(kk, n_i, ss, l, n_excluded=kv)
    )(keys, s_sorted, k_valid)  # (t, l)
    n_f = jnp.asarray(n, jnp.float32)
    tail_n = n_f - k_valid.astype(jnp.float32)  # (t,)
    # an EMPTY tail stratum must weigh -inf, not log(1/l): on an all-pad
    # TP shard the partial would otherwise psum finite garbage into the
    # global log Ẑ (and its gradient)
    log_w_tail = jnp.where(
        tail_n > 0, jnp.log(jnp.maximum(tail_n, 1.0) / l), -jnp.inf
    )  # (t,)
    ids = jnp.concatenate([topk.ids, tail], axis=1)
    log_w_s = jnp.where(jnp.isneginf(topk.values), -jnp.inf, 0.0)
    log_w = jnp.concatenate(
        [log_w_s, jnp.broadcast_to(log_w_tail[:, None], (t, l))], axis=1
    )
    return ids, log_w


def topk_only_candidates(
    topk: TopK, targets: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Truncated-support candidates: S with the target's duplicate slot
    masked — the target itself enters via the combine, exactly once."""
    log_w = jnp.where(jnp.isneginf(topk.values), -jnp.inf, 0.0)
    log_w = jnp.where(topk.ids == targets[:, None], -jnp.inf, log_w)
    return topk.ids, log_w


# --------------------------------------------------------------------------
# stratified partials (Algorithm 3; gradient = Algorithm 4 with f = φ)
# --------------------------------------------------------------------------
def stratified_logz(
    emb: jax.Array,
    h: jax.Array,
    ids: jax.Array,
    log_w: jax.Array,
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """Shard-local ``log Σ_i w_i e^{y_i}`` over candidates, differentiable
    w.r.t. ``emb`` and ``h`` (∇_h = Algorithm 4's expectation estimate).

    ``use_kernel`` streams candidates through the fused Pallas estimator
    (one pass, no (t, m, d) HBM gather); its custom VJP rematerializes the
    rows in the backward pass, matching the XLA path's gradients.
    """
    ids = jnp.maximum(jax.lax.stop_gradient(ids), 0)  # -1 pads: weight -inf
    log_w = log_w.astype(ESTIMATOR_DTYPE)  # stratum weights: fp32 always
    if use_kernel:
        return _fused_logz(emb, ids, h, log_w)
    rows = emb[ids]  # (t, m, d) — differentiable gather
    y = jnp.einsum("tmd,td->tm", rows, h).astype(ESTIMATOR_DTYPE)
    return jax.nn.logsumexp(y + log_w, axis=1)


@jax.custom_vjp
def _fused_logz(emb, ids, h, log_w):
    from repro.kernels import ops as kops

    log_z, _ = kops.fused_estimator(emb, ids, h, log_w)
    return log_z


def _fused_logz_fwd(emb, ids, h, log_w):
    from repro.kernels import ops as kops

    log_z, expv = kops.fused_estimator(emb, ids, h, log_w)
    return log_z, (emb, ids, h, log_w, log_z, expv)


def _fused_logz_bwd(res, g):
    emb, ids, h, log_w, log_z, expv = res
    hf = h.astype(jnp.float32)
    y = jnp.einsum("tmd,td->tm", emb[ids].astype(jnp.float32), hf) + log_w
    p = jnp.exp(y - log_z[:, None]) * g[:, None]  # (t, m) scaled posteriors
    d_h = (g[:, None] * expv).astype(h.dtype)  # ∇_h log Ẑ = Alg-4 estimate
    d_emb = (
        jnp.zeros(emb.shape, jnp.float32)
        .at[ids]
        .add(p[..., None] * hf[:, None, :])
        .astype(emb.dtype)
    )
    d_ids = np.zeros(ids.shape, jax.dtypes.float0)
    return d_emb, d_ids, d_h, p.astype(log_w.dtype)


_fused_logz.defvjp(_fused_logz_fwd, _fused_logz_bwd)


def lsh_sampler_logz(
    index: Any, h: jax.Array, *, per_table: bool = False,
    min_bit_prob: float = 1e-7,
) -> jax.Array:
    """Spring–Shrivastava (arXiv 1703.05160) unbiased LSH-sampler estimate
    of ``log Z`` — the second estimator class behind the Algorithm-3
    interface, using :class:`repro.core.mips.LSHIndex` buckets as the
    proposal structure instead of a top-k probe + uniform tail.

    Per table ``t``, every db point ``x`` landing in the query's bucket is
    importance-weighted by its exact bucket-collision probability
    ``q1(x) = p(x)^n_bits`` (SRP per-bit agreement ``p = 1 - angle/pi``
    between the NORM-COMPLETED vectors; the query's augmented coordinate is
    0, so the scored inner product stays the raw ``h·x``)::

        Z_t = sum_{x in bucket_t(h)} e^{y_x} / q1(x),   E[Z_t] = Z

    and the estimate averages the L iid per-table estimates,
    ``Z_hat = (1/L) sum_t Z_t`` — unbiased in Z (up to fp rounding of the
    arccos collision probabilities), with across-table independence giving
    CLT/Chebyshev intervals for free (tests/test_estimator_stats.py).

    Unbiasedness REQUIRES lossless buckets: a point dropped by the padded
    bucket cap has retrieval probability below its nominal ``q1`` and
    biases Z_hat down. Build the index with ``bucket_cap >= max load``
    and check ``index.dropped_count == 0`` (the counts leaf added for
    estimator duty) before trusting the estimate.

    Args:
      index: an LSHIndex (duck-typed: needs proj / table_ids / db_aug /
        n_bits). All partials are fp32 (ESTIMATOR_DTYPE) per DESIGN.md §9.
      h: (t, d) queries.
      per_table: return the (t, L) per-table ``log Z_t`` matrix instead of
        the combined (t,) ``log Z_hat`` — the stats suite builds its
        across-table confidence intervals from these.
      min_bit_prob: floor on the per-bit collision probability. A RETRIEVED
        point's fp-rounded probability can hit exactly 0 only for
        near-antipodal pairs (a probability-~0 retrieval); the floor keeps
        the weight finite at negligible (downward) bias.

    Returns (t,) ``log Z_hat`` — or (t, L) per-table ``log Z_t`` (empty
    buckets give -inf, a legitimate ``Z_t = 0`` sample).
    """
    hf = h.astype(jnp.float32)
    tq = hf.shape[0]
    q_aug = jnp.concatenate([hf, jnp.zeros((tq, 1), jnp.float32)], axis=1)
    proj = index.proj  # (L, d+1, bits)
    n_bits = index.n_bits
    bits = jnp.einsum("bd,tdc->tbc", q_aug, proj) >= 0
    pows = (1 << jnp.arange(n_bits)).astype(jnp.int32)
    codes = jnp.tensordot(bits.astype(jnp.int32), pows, axes=1)  # (L, t)
    cand = jnp.take_along_axis(
        index.table_ids, codes[:, :, None], axis=1
    )  # (L, t, cap)
    vecs = index.db_aug[jnp.maximum(cand, 0)]  # (L, t, cap, d+1)
    # q_aug's last coordinate is 0: this IS the raw h·x, fp32 accumulated
    y = jnp.einsum("ltcd,td->ltc", vecs, q_aug).astype(ESTIMATOR_DTYPE)
    norms = jnp.linalg.norm(vecs, axis=-1) * jnp.linalg.norm(
        q_aug, axis=-1
    )[None, :, None]
    cosv = y / jnp.maximum(norms, 1e-30)
    ang = jnp.arccos(jnp.clip(cosv, -1.0, 1.0))
    p_bit = jnp.maximum(1.0 - ang / jnp.pi, min_bit_prob)
    log_q1 = n_bits * jnp.log(p_bit)  # (L, t, cap) log collision prob
    w = jnp.where(cand >= 0, y - log_q1, -jnp.inf)
    log_zt = jax.nn.logsumexp(w, axis=2)  # (L, t)
    if per_table:
        return jnp.moveaxis(log_zt, 0, 1)  # (t, L)
    n_tables = proj.shape[0]
    return jax.nn.logsumexp(log_zt, axis=0) - jnp.log(
        jnp.float32(n_tables)
    )


def exact_logz(emb: jax.Array, h: jax.Array, n_valid=None,
               axis: str | None = None) -> jax.Array:
    """Dense per-token logsumexp over the valid local rows (baseline).

    ``axis`` (a mesh axis the rows are sharded over, inside shard_map)
    makes it the logsumexp over every shard's rows: one global max first,
    so each score is exponentiated against the max a single device would
    use, and only the sum is split. Combining per-shard logsumexps instead
    puts every term through a different exp and log, and on a TPU v5e
    those differ from a one-device run by up to 1e-4."""
    scores = (h @ emb.T).astype(jnp.float32)
    if n_valid is not None:
        ok = jnp.arange(emb.shape[0]) < n_valid
        scores = jnp.where(ok[None, :], scores, -jnp.inf)
    if axis is None:
        return jax.nn.logsumexp(scores, axis=-1)
    m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(scores, axis=-1)), axis)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    total = jax.lax.psum(jnp.sum(jnp.exp(scores - m[:, None]), axis=-1),
                         axis)
    return jnp.log(total) + m


def target_partial(
    emb: jax.Array, h: jax.Array, targets: jax.Array, n_valid=None
) -> jax.Array:
    """Target logit for locally-owned targets, 0 elsewhere (psum-ready)."""
    nv = emb.shape[0] if n_valid is None else n_valid
    inside = (targets >= 0) & (targets < nv)
    rows = emb[jnp.clip(targets, 0, emb.shape[0] - 1)]
    y = jnp.einsum("td,td->t", rows, h).astype(jnp.float32)
    return jnp.where(inside, y, 0.0)


def loss_partials(
    key: jax.Array,
    emb: jax.Array,
    h: jax.Array,
    targets: jax.Array,
    *,
    mode: str,
    k: int,
    l: int,
    index: Any = None,
    n_valid=None,
    score_dtype=jnp.float32,
    use_kernel: bool = False,
    axis: str | None = None,
) -> LossPartials:
    """Shard-local loss partials for one (t, d) token block.

    The probe runs on stop-gradient queries; candidate scores are then
    RECOMPUTED through the differentiable gather so ∇(emb, h) flows through
    both strata (the Alg-4 gradient), robust to stale index values.

    ``axis`` (exact mode, inside shard_map): the mesh axis the rows are
    sharded over; ``log_z`` is then already the global logsumexp
    (:func:`exact_logz`), which :func:`combine_loss_psum` passes through.
    """
    emb_s = emb.astype(score_dtype)
    h_s = h.astype(score_dtype)
    targets = targets.astype(jnp.int32)
    if mode == "exact":
        return LossPartials(
            exact_logz(emb_s, h_s, n_valid, axis=axis),
            target_partial(emb_s, h_s, targets, n_valid),
        )
    topk = topk_probe(
        emb_s, jax.lax.stop_gradient(h_s), k, index=index, n_valid=n_valid
    )
    topk = TopK(
        jax.lax.stop_gradient(topk.ids), jax.lax.stop_gradient(topk.values)
    )
    if mode == "topk_only":
        ids, log_w = topk_only_candidates(topk, targets)
    else:  # amortized
        n = emb.shape[0] if n_valid is None else n_valid
        ids, log_w = amortized_candidates(key, topk, n, l)
    log_z = stratified_logz(emb_s, h_s, ids, log_w, use_kernel=use_kernel)
    return LossPartials(log_z, target_partial(emb_s, h_s, targets, n_valid))


# --------------------------------------------------------------------------
# combines: one-shard identity vs cross-shard collectives
# --------------------------------------------------------------------------
def combine_loss(p: LossPartials, mode: str) -> tuple[jax.Array, jax.Array]:
    """One-shard combine -> (per-token NLL, log Ẑ diagnostics)."""
    if mode == "topk_only":
        log_z = jnp.logaddexp(p.log_z, p.y_t)  # target counted exactly once
    else:
        log_z = p.log_z
    return log_z - p.y_t, log_z


def combine_loss_psum(p: LossPartials, mode: str, axis: str) -> jax.Array:
    """Cross-shard combine: global ``log Ẑ`` is the logsumexp over shards of
    the local stratified partials — the stratified sum of per-shard Alg-3
    estimators, still exactly unbiased in Z (Thm 3.4 applies per shard) —
    and the target logit enters via a masked psum (owned by exactly one
    shard). O(1) scalars per token. The pmax is a pure numerical stabilizer:
    stop_gradient keeps the combined gradient exact and avoids pmax's
    missing jvp.
    """
    sg = jax.lax.stop_gradient
    y_t_g = jax.lax.psum(p.y_t, axis)
    if mode == "exact":  # log_z spans the shards already (loss_partials)
        return p.log_z - y_t_g
    if mode == "topk_only":
        m = jnp.maximum(jax.lax.pmax(sg(p.log_z), axis), sg(y_t_g))
        z = jax.lax.psum(jnp.exp(p.log_z - m), axis) + jnp.exp(y_t_g - m)
        return m + jnp.log(z) - y_t_g
    m = jax.lax.pmax(sg(p.log_z), axis)
    lse_g = m + jnp.log(jax.lax.psum(jnp.exp(p.log_z - m), axis))
    return lse_g - y_t_g


# --------------------------------------------------------------------------
# lazy-Gumbel sampling (Algorithm 2 per shard)
# --------------------------------------------------------------------------
def local_gumbel_max(
    key: jax.Array,
    emb: jax.Array,
    h: jax.Array,
    *,
    k: int,
    l: int,
    index: Any = None,
    n_valid=None,
    c: float = 0.0,
    m_cap: int | None = None,
    keys: jax.Array | None = None,
    fused: bool = False,
    adaptive: bool = False,
    router: Any = None,
) -> SampleResult:
    """Batched lazy-Gumbel max over the local rows: per-token SampleResult
    with local ids plus the certificate terms (max_val, bound, overflow)
    that :func:`combine_sample_pmax` re-checks against the global winner.

    ``adaptive=True`` routes the probe through the index's certificate-gated
    staged widening (``topk_adaptive``, core/mips/adaptive.py) when the
    index has one: each token probes only as many clusters as its gap
    certificate needs, and the effective per-token width comes back in
    ``SampleResult.width`` (None on fixed-width paths). The Algorithm-2
    certificate below stays the sampling-exactness authority — the gap
    certificate only routes bandwidth, and widening only ever grows the
    candidate pool, so the TV-at-measured-recall machinery applies
    unchanged. ``router`` (repro.models.router.ProbeRouter, optional)
    predicts each query's starting stage.

    ``keys`` (optional, (T,) typed PRNG keys) pins each token's randomness
    explicitly instead of deriving it as ``fold_in(key, row)`` — the serving
    engine uses this to make a token's sample a function of (request,
    position) alone, independent of batch composition, so fused multi-token
    decode reproduces the single-step path bit for bit.

    ``fused=True`` routes the heavy stages through the single-dispatch
    Pallas decode pipeline (:mod:`repro.kernels.decode_fused`): the probe
    goes through the index's ``screen_select`` (gather/screen/re-rank and
    top-k selection fused, candidate pool resident in VMEM) when the index
    provides one (IVF, IVF-PQ — including their sharded per-shard
    instances), and the Algorithm-2 tail finish through
    :func:`repro.kernels.ops.tail_gather_argmax` (tail gather + perturbed
    argmax fused; the jax.random tail plan stays in XLA). Samples and
    certificate terms are BIT-IDENTICAL to ``fused=False`` with
    ``use_kernel=True`` — same keys, same floating-point programs — which
    tests/test_decode_fused.py asserts per backend."""
    t = h.shape[0]
    nv = emb.shape[0] if n_valid is None else n_valid
    if m_cap is None:
        m_cap = int(l + 6 * math.sqrt(l) + 8)
    embf = emb.astype(jnp.float32)
    hf = query_f32(h)
    width = None
    screen = getattr(index, "screen_select", None) if fused else None
    if adaptive and hasattr(index, "topk_adaptive"):
        atk = index.topk_adaptive(hf, k, c=c, fused=fused, router=router)
        # same dead-slot masking as topk_probe's index branch
        ids = atk.ids.astype(jnp.int32)
        ok = ids >= 0
        if n_valid is not None:
            ok &= ids < n_valid
        topk = TopK(ids, jnp.where(ok, atk.values.astype(jnp.float32),
                                   -jnp.inf))
        width = atk.width
    elif screen is not None:
        tk = screen(hf, k)
        # same dead-slot masking as topk_probe's index branch
        ids = tk.ids.astype(jnp.int32)
        ok = ids >= 0
        if n_valid is not None:
            ok &= ids < n_valid
        topk = TopK(ids, jnp.where(ok, tk.values.astype(jnp.float32),
                                   -jnp.inf))
    else:
        topk = topk_probe(embf, hf, k, index=index, n_valid=n_valid)
    # dead probe slots (-inf value) must not shadow real rows in the
    # sampler's complement tail draw, and the cutoff/atom-rate math must
    # use the per-token LIVE slot count (see sample_fixed_b's k_valid);
    # dead slots' -inf perturbed values already never win the argmax
    ids_clean, k_valid = sanitize_topk(topk, nv)
    if keys is None:
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            key, jnp.arange(t, dtype=jnp.uint32)
        )

    if fused:
        res = _fused_tail_argmax(
            keys, embf, hf, ids_clean, topk.values, k_valid, nv,
            l=l, m_cap=m_cap, c=c,
        )
    else:
        def one(kk, tk_ids, tk_vals, kv, hh):
            # full f32 precision, as the fused tail kernel scores: on TPU
            # the default f32 matmul rounds its inputs to bf16
            score_fn = lambda ids: jnp.dot(  # noqa: E731
                embf[jnp.minimum(ids, emb.shape[0] - 1)], hh,
                precision=jax.lax.Precision.HIGHEST,
            )
            return sample_fixed_b(
                kk, TopK(tk_ids, tk_vals), nv, score_fn, l=l, m_cap=m_cap,
                c=c, k_valid=kv,
            )

        res = jax.vmap(one)(keys, ids_clean, topk.values, k_valid, hf)
    if width is not None:
        res = res._replace(width=width.astype(jnp.int32))
    return res


def _fused_tail_argmax(
    keys: jax.Array,
    embf: jax.Array,
    hf: jax.Array,
    ids_clean: jax.Array,
    values: jax.Array,
    k_valid: jax.Array,
    nv,
    *,
    l: int,
    m_cap: int,
    c: float,
) -> SampleResult:
    """Algorithm-2 finish with the tail gather + perturbed argmax fused into
    one Pallas dispatch. The per-token randomness (Gumbel perturbations of
    S, Poisson atom count, complement positions, Exp heights) is drawn in
    XLA by :func:`repro.core.gumbel.plan_tail` with exactly the key splits
    and shapes of :func:`repro.core.gumbel.sample_fixed_b`, so the sampled
    stream is bit-identical to the unfused path; only the (t, m_cap, d)
    tail row gather — the HBM-heavy part — moves into the kernel."""
    t, k = ids_clean.shape

    def one_plan(kk, tk_ids, kv):
        k_s, k_t = jax.random.split(kk)
        g_s = jax.random.gumbel(k_s, (k,), dtype=jnp.float32)
        b = jnp.log((jnp.asarray(nv, jnp.float32) - kv) / l)
        plan = plan_tail(
            k_t, tk_ids, nv, b, jnp.float32(l), m_cap, k_valid=kv
        )
        return g_s, b, plan

    g_s, b, plan = jax.vmap(one_plan)(keys, ids_clean, k_valid)
    pert_s = values.astype(jnp.float32) + g_s  # (t, k)
    # defensive clamp, as the unfused score_fn's gather: complement draws
    # are < nv <= embf.shape[0] already, so ids are unchanged
    pos = jnp.minimum(plan.pos, embf.shape[0] - 1)

    from repro.kernels import ops as kops

    idx, max_val = kops.tail_gather_argmax(
        embf, pos, plan.m_used, pert_s, ids_clean, plan.heights, hf
    )
    ok, bound = jax.vmap(
        lambda v, bb, mv, ov: certificate(v, bb, c, mv, ov)
    )(values, b, max_val, plan.overflow)
    return SampleResult(idx, ok, plan.m_used, max_val, bound, plan.overflow)


def local_gumbel_topk(
    key: jax.Array | None,
    emb: jax.Array,
    h: jax.Array,
    *,
    num: int,
    k: int,
    l: int,
    index: Any = None,
    n_valid=None,
    c: float = 0.0,
    m_cap: int | None = None,
    keys: jax.Array | None = None,
) -> TopKSampleResult:
    """Batched lazy-Gumbel top-``num`` WITHOUT replacement over the local
    rows: :func:`local_gumbel_max`'s probe/sanitize/key discipline with
    :func:`repro.core.gumbel.topk_fixed_b` as the finish, so each token
    gets the ``num`` largest perturbed values of ONE joint Gumbel draw
    (Kool et al. 2019) plus the Algorithm-2 exactness certificate on the
    whole kept set. This is the candidate-draw primitive behind stochastic
    beam search (repro.workloads.structured): each beam expansion is one
    call, ``num`` = beam width, and the per-beam ``ok`` flag gates the
    beam's exactness.

    Returns a TopKSampleResult with leading dim t: ids/values/scores are
    (t, num) (values perturbed, descending; scores the matching raw y);
    ok/m/bound/overflow are (t,). ``keys`` ((t,) typed PRNG keys) pins
    per-token randomness as in :func:`local_gumbel_max` — beam search
    derives them from the node path so a beam's draw is independent of
    which other beams share the batch. ``key`` may be None when ``keys``
    is given.
    """
    t = h.shape[0]
    nv = emb.shape[0] if n_valid is None else n_valid
    if m_cap is None:
        m_cap = int(l + 6 * math.sqrt(l) + 8)
    embf = emb.astype(jnp.float32)
    hf = h.astype(jnp.float32)
    topk = topk_probe(embf, hf, k, index=index, n_valid=n_valid)
    ids_clean, k_valid = sanitize_topk(topk, nv)
    if keys is None:
        if key is None:
            raise ValueError("local_gumbel_topk needs key or keys")
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            key, jnp.arange(t, dtype=jnp.uint32)
        )

    def one(kk, tk_ids, tk_vals, kv, hh):
        score_fn = (
            lambda ids: embf[jnp.minimum(ids, emb.shape[0] - 1)] @ hh
        )
        return topk_fixed_b(
            kk, TopK(tk_ids, tk_vals), nv, score_fn, num=num, l=l,
            m_cap=m_cap, c=c, k_valid=kv,
        )

    return jax.vmap(one)(keys, ids_clean, topk.values, k_valid, hf)


def dense_gumbel_max(
    key: jax.Array, emb: jax.Array, h: jax.Array, n_valid=None, keys=None
) -> tuple[jax.Array, jax.Array]:
    """Exact dense Gumbel-max per token: (ids (t,), perturbed max (t,)).

    ``keys`` ((T,) typed PRNG keys) makes each token's Gumbel noise a
    function of its own key instead of the shared ``key`` — see
    :func:`local_gumbel_max`."""
    scores = (h.astype(jnp.float32) @ emb.astype(jnp.float32).T)
    if n_valid is not None:
        ok = jnp.arange(emb.shape[0]) < n_valid
        scores = jnp.where(ok[None, :], scores, -jnp.inf)
    if keys is None:
        g = jax.random.gumbel(key, scores.shape, dtype=jnp.float32)
    else:
        g = jax.vmap(
            lambda kk: jax.random.gumbel(kk, scores.shape[1:], jnp.float32)
        )(keys)
    pert = scores + g
    return jnp.argmax(pert, -1).astype(jnp.int32), jnp.max(pert, -1)


def combine_sample_pmax(
    gid: jax.Array, val: jax.Array, bound: jax.Array, ok: jax.Array, axis: str
) -> tuple[jax.Array, jax.Array]:
    """Global argmax of per-shard lazy-Gumbel maxima IS an exact global
    sample. Provably exact iff the global winner clears every shard's
    non-materialized bound (``S_min + c + B``) and no shard's static tail
    buffer overflowed — the certificates compose via a pmin. Ties break
    toward the smaller global id."""
    vmax = jax.lax.pmax(val, axis)
    cand = jnp.where(val >= vmax, gid, jnp.int32(2**30))
    gid_win = jax.lax.pmin(cand, axis)
    ok_g = jax.lax.pmin(
        (ok & (vmax >= bound)).astype(jnp.int32), axis
    ).astype(bool)
    return gid_win, ok_g


# --------------------------------------------------------------------------
# token chunking (shared by both heads)
# --------------------------------------------------------------------------
def chunked_map(fn, chunk: int, key: jax.Array, *arrays: jax.Array):
    """``lax.map(jax.checkpoint(fn))`` over token chunks.

    The (chunk, k+l, d) candidate gathers are rematerialized in the backward
    pass, so peak activation memory is O(chunk · (k+l) · d) regardless of
    sequence length. ``fn(key, *chunk_arrays)`` returns a pytree of
    (chunk, ...) outputs; the result is the same pytree with leading dim t
    (padding stripped). Each chunk gets an independent key split.
    """
    t = arrays[0].shape[0]
    ch = min(chunk, max(1, t))
    nck = -(-t // ch)
    pad = nck * ch - t

    def prep(a):
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((nck, ch) + a.shape[1:])

    xs = tuple(prep(a) for a in arrays)
    keys = jax.random.split(key, nck)
    out = jax.lax.map(
        jax.checkpoint(lambda args: fn(args[0], *args[1:])), (keys,) + xs
    )
    return jax.tree.map(
        lambda o: o.reshape((nck * ch,) + o.shape[2:])[:t], out
    )
