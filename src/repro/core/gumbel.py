"""Lazy-Gumbel sampling (paper Algorithms 1 & 2) and the TPU-native variant.

Three samplers, all exact (the first two are the paper's; the third is our
static-shape TPU adaptation):

* :func:`sample_adaptive_b`  — Algorithm 1. Cutoff ``B = M - S_min - c`` is
  data-dependent; the number of tail candidates ``m`` has ``E[m] <= n e^c/k``
  (Thm 3.2) but heavy tails, so the static buffer can overflow (flagged).
* :func:`sample_fixed_b`     — Algorithm 2. Fixed ``B`` s.t. the expected
  number of tail exceedances is ``l``; exact w.p. ``1-δ`` for
  ``k·l >= n e^c ln(1/δ)`` (Thm 3.3), and ``m < 2l`` w.h.p.
* both use the **Poissonized tail** construction (below) instead of
  Binomial + without-replacement subset sampling, which has no good
  static-shape implementation.

Poissonized lazy Gumbels
------------------------
A Gumbel variable is the max of a Poisson process with intensity
``e^{-g} dg`` on the real line (``P(max <= x) = exp(-∫_x^∞ e^-g dg)
= exp(-e^{-x})``, the Gumbel CDF). Attach an independent such process to
each of the ``N = n-k`` tail points and keep only atoms above the cutoff B:
the superposition is a Poisson process with ``K ~ Poisson(N e^{-B})`` atoms,
positions iid uniform over tail points **with replacement** (collisions are
handled for free: the per-point max over its atoms reproduces the truncated
Gumbel law exactly), and heights iid ``B + Exp(1)``. Per tail point i,
``P(no atom above x) = exp(-e^{-x})`` — exactly the Gumbel CDF — jointly
independent across points, so the construction is *distributionally
identical* to sampling a fresh Gumbel per tail point and discarding those
below B. This removes the without-replacement subset machinery of Alg 2
while keeping exactness. (Documented in DESIGN.md §3.)

Exactness certificate: every non-materialized point has unnormalized
log-prob ``y_i <= S_min + c`` (approximate-top-k gap ``c``, Def 3.1) and
Gumbel ``<= B``, so whenever the materialized winner's perturbed value is
``>= S_min + c + B`` the sample is *provably* exact; the sampler returns
this as an ``ok`` flag. Under Alg 1's cutoff the certificate holds by
construction (modulo buffer overflow); under Alg 2 it fails w.p. <= δ.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.complement import sample_complement

__all__ = [
    "TopK",
    "SampleResult",
    "TopKSampleResult",
    "TailPlan",
    "plan_tail",
    "certificate",
    "gap_certificate",
    "sample_adaptive_b",
    "sample_fixed_b",
    "topk_fixed_b",
    "gumbel_max_dense",
    "default_kl",
]


class TopK(NamedTuple):
    """Top-k set S: ids and their unnormalized log-probs (any order)."""

    ids: jax.Array  # (k,) int32
    values: jax.Array  # (k,) float32


class SampleResult(NamedTuple):
    index: jax.Array  # () int32 — the sampled element of [0, n)
    ok: jax.Array  # () bool — True => provably exact (given MIPS gap <= c)
    m: jax.Array  # () int32 — tail candidates materialized
    max_val: jax.Array  # () float32 — winning perturbed value
    bound: jax.Array  # () float32 — S_min + c + B: non-materialized points
    #                     are provably below this (distributed combining
    #                     re-checks it against the *global* winner)
    overflow: jax.Array  # () bool — static tail buffer overflowed
    width: jax.Array | None = None  # () int32 — effective probe width when
    #   the adaptive staged probe produced the top-k (None on fixed-width
    #   paths; the serving engine bins these into stats["probe_width_hist"])


def default_kl(n: int, delta: float = 1e-4, c: float = 0.0) -> int:
    """k = l satisfying Thm 3.3's ``k l >= n e^c ln(1/δ)``, rounded up to 64."""
    kl = math.sqrt(n * math.exp(c) * math.log(1.0 / delta))
    return max(64, int(math.ceil(kl / 64.0)) * 64)


def gumbel_max_dense(key: jax.Array, y: jax.Array) -> jax.Array:
    """Brute-force Gumbel-max oracle: argmax_i y_i + G_i (linear time)."""
    g = jax.random.gumbel(key, y.shape, dtype=y.dtype)
    return jnp.argmax(y + g).astype(jnp.int32)


class TailPlan(NamedTuple):
    """The data-independent part of the Poissonized tail draw: everything
    :func:`plan_tail` can decide from (key, S, n) alone — positions, heights,
    live count — before any tail score is computed. The fused decode kernel
    (:mod:`repro.kernels.decode_fused`) consumes a TailPlan directly: the
    plan stays in XLA (it is all jax.random), only the score-gather + argmax
    move into the kernel, which is what keeps the fused sampler bit-for-bit
    identical to :func:`_finish`."""

    pos: jax.Array  # (m_cap,) int32 tail positions (complement of S)
    heights: jax.Array  # (m_cap,) f32 truncated-Gumbel heights B + Exp(1)
    m_used: jax.Array  # () int32 — materialized tail candidates (<= m_cap)
    overflow: jax.Array  # () bool — Poisson draw exceeded the static buffer


def plan_tail(
    key: jax.Array,
    topk_ids: jax.Array,
    n,
    b: jax.Array,
    lam: jax.Array,
    m_cap: int,
    k_valid=None,
) -> TailPlan:
    """Draw the Poissonized tail construction for cutoff ``b`` / rate
    ``lam``: atom count (Poisson), positions (iid uniform over the
    complement of the sorted S, with replacement), heights (B + Exp(1)).
    The exact sequence of jax.random draws of the pre-refactor ``_finish``,
    so samples are reproducible across the fused/unfused split."""
    k_m, k_pos, k_h = jax.random.split(key, 3)
    with jax.named_scope("tail.draw"):
        m = jax.random.poisson(k_m, lam, dtype=jnp.int32)
        overflow = m > m_cap
        m_used = jnp.minimum(m, m_cap)
        s_sorted = jnp.sort(topk_ids).astype(jnp.int32)
        heights = b + jax.random.exponential(k_h, (m_cap,),
                                             dtype=jnp.float32)
    pos = sample_complement(
        k_pos, n, s_sorted, m_cap, n_excluded=k_valid
    )  # (m_cap,)
    return TailPlan(pos, heights, m_used, overflow)


def certificate(
    values: jax.Array,
    b: jax.Array,
    c: float,
    max_val: jax.Array,
    overflow: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Algorithm-2 exactness certificate -> (ok, bound).

    Dead S slots (value -inf: masked/padded probe results) are not real
    top-k members — S_min must bound the NON-materialized scores, so take
    the min over live slots only (all-dead => +inf bound => ok False).
    A zero-row shard (no live slots AND empty tail: s_min=+inf, b=-inf)
    holds no points at all, so nothing is non-materialized: bound=-inf,
    not NaN — a NaN would veto the GLOBAL certificate via the pmin."""
    vals = values.astype(jnp.float32)
    s_min = jnp.min(jnp.where(jnp.isneginf(vals), jnp.inf, vals))
    bound = s_min + c + b
    bound = jnp.where(jnp.isnan(bound), -jnp.inf, bound)
    ok = (max_val >= bound) & ~overflow
    return ok, bound


def gap_certificate(
    s_min: jax.Array, upper: jax.Array, c: float = 0.0
) -> jax.Array:
    """Adaptive-probe stopping rule: the candidate pool is a certified
    c-approximate top-k (Def 3.1) iff every unprobed score is provably
    <= ``s_min + c``, where ``s_min`` is the k-th best candidate found and
    ``upper`` a sound bound on anything not yet probed
    (:func:`repro.core.mips.adaptive.unprobed_bound_table`). Underfilled
    pools carry ``s_min = -inf`` and only pass once nothing is left
    unprobed (``upper = -inf``) — exhaustive coverage of a db smaller
    than k is exact by definition."""
    return upper <= s_min + c


def _finish(
    key: jax.Array,
    topk: TopK,
    n: int,
    score_fn: Callable[[jax.Array], jax.Array],
    b: jax.Array,
    lam: jax.Array,
    m_cap: int,
    c: float,
    pert_s: jax.Array,
    k_valid=None,
) -> SampleResult:
    """Shared tail materialization + argmax given cutoff b and atom rate lam."""
    plan = plan_tail(key, topk.ids, n, b, lam, m_cap, k_valid=k_valid)
    with jax.named_scope("tail.score"):
        y_tail = score_fn(plan.pos).astype(jnp.float32)  # (m_cap,)
    live = jnp.arange(m_cap, dtype=jnp.int32) < plan.m_used
    pert_t = jnp.where(live, y_tail + plan.heights, -jnp.inf)

    pert = jnp.concatenate([pert_s, pert_t])
    ids = jnp.concatenate([topk.ids.astype(jnp.int32), plan.pos])
    best = jnp.argmax(pert)
    max_val = pert[best]
    ok, bound = certificate(topk.values, b, c, max_val, plan.overflow)
    return SampleResult(
        ids[best], ok, plan.m_used, max_val, bound, plan.overflow
    )


def sample_adaptive_b(
    key: jax.Array,
    topk: TopK,
    n: int,
    score_fn: Callable[[jax.Array], jax.Array],
    *,
    m_cap: int,
    c: float = 0.0,
) -> SampleResult:
    """Algorithm 1 (adaptive cutoff). Exact whenever ``ok`` (no overflow).

    ``E[m] <= n e^c / k`` (Thm 3.2); choose ``m_cap`` a small multiple of
    ``n/k`` — overflow probability decays like ``(n e^c/k)/m_cap``.

    Args:
      score_fn: maps an int32 id array to unnormalized log-probs ``y``.
    """
    k_s, k_t = jax.random.split(key)
    k = topk.ids.shape[0]
    g_s = jax.random.gumbel(k_s, (k,), dtype=jnp.float32)
    pert_s = topk.values.astype(jnp.float32) + g_s
    m_big = jnp.max(pert_s)
    s_min = jnp.min(topk.values.astype(jnp.float32))
    b = m_big - s_min - c  # paper's B = M - S_min - c
    lam = (jnp.asarray(n, jnp.float32) - k) * jnp.exp(-b)  # tail atom rate
    return _finish(k_t, topk, n, score_fn, b, lam, m_cap, c, pert_s)


def sample_fixed_b(
    key: jax.Array,
    topk: TopK,
    n: int,
    score_fn: Callable[[jax.Array], jax.Array],
    *,
    l: int,
    m_cap: int | None = None,
    c: float = 0.0,
    k_valid=None,
) -> SampleResult:
    """Algorithm 2 (fixed cutoff): exact w.p. 1-δ for ``k l >= n e^c ln(1/δ)``.

    ``B = ln((n-k)/l)`` so the tail atom count is Poisson(l); the static
    buffer ``m_cap`` defaults to ``l + 6 sqrt(l) + 8`` (overflow < 1e-8).

    ``k_valid`` (optional, may be traced) is the number of LIVE top-k slots
    when the probe underfills (dead slots hold value -inf and sanitized
    virtual ids >= n): the true tail then has ``n - k_valid`` points, so
    the cutoff, atom rate, and complement support all use it — otherwise
    the ``k - k_valid`` largest complement ids would silently get zero
    sampling probability while the certificate still claimed exactness.
    """
    k = topk.ids.shape[0]
    kv = k if k_valid is None else k_valid
    if m_cap is None:
        m_cap = int(l + 6 * math.sqrt(l) + 8)
    k_s, k_t = jax.random.split(key)
    g_s = jax.random.gumbel(k_s, (k,), dtype=jnp.float32)
    pert_s = topk.values.astype(jnp.float32) + g_s
    # n may be a traced per-shard scalar (distributed head) — use jnp ops
    b = jnp.log((jnp.asarray(n, jnp.float32) - kv) / l)
    lam = jnp.float32(l)
    return _finish(k_t, topk, n, score_fn, b, lam, m_cap, c, pert_s,
                   k_valid=k_valid)


class TopKSampleResult(NamedTuple):
    """Perturbed top-``num`` of one lazy-Gumbel draw (best first).

    The ``num`` largest perturbed values of ONE joint Gumbel perturbation —
    i.e. Gumbel top-k sampling *without replacement* (the first num atoms
    of the Plackett–Luce process), not num independent samples. Dead output
    slots (fewer than num live candidates) carry id -1 / value -inf, the
    repo-wide pad convention."""

    ids: jax.Array  # (num,) int32 — perturbed top-num ids, -1 pads
    values: jax.Array  # (num,) f32 — perturbed values, descending
    scores: jax.Array  # (num,) f32 — the ids' UNperturbed log-probs y
    ok: jax.Array  # () bool — top-num provably exact (given MIPS gap <= c)
    m: jax.Array  # () int32 — tail candidates materialized
    bound: jax.Array  # () f32 — S_min + c + B: non-materialized points are
    #   provably below this perturbed value
    overflow: jax.Array  # () bool — static tail buffer overflowed


def topk_fixed_b(
    key: jax.Array,
    topk: TopK,
    n,
    score_fn: Callable[[jax.Array], jax.Array],
    *,
    num: int,
    l: int,
    m_cap: int | None = None,
    c: float = 0.0,
    k_valid=None,
) -> TopKSampleResult:
    """Algorithm-2 lazy Gumbels, keeping the top ``num`` perturbed values
    instead of the argmax — Gumbel top-k without replacement (Kool et al.
    2019's primitive) over the same S ∪ Poissonized-tail candidate pool.

    Key discipline, cutoff, atom rate and tail plan are IDENTICAL to
    :func:`sample_fixed_b` (same splits, same draw shapes), so with
    ``num=1`` the winning (id, value) is bit-for-bit the SampleResult of
    :func:`sample_fixed_b` — which tests/test_workloads.py asserts.

    Two deltas vs the argmax path:

    * **Tail dedup.** Tail atom positions are drawn with replacement; a
      point's true truncated Gumbel is the max over its atoms. The argmax
      never sees the smaller duplicates, but a top-num WOULD return the
      same id twice — so every non-maximal duplicate atom is masked to
      -inf (per-position max kept, in place, preserving atom order).
    * **Certificate.** Non-materialized points lie below
      ``bound = S_min + c + B``; the kept set is the true perturbed
      top-num iff the num-th best kept value clears that bound (and the
      static buffer did not overflow). When S covers the whole support
      (``k_valid == n``) the cutoff ``B = log(0) = -inf`` makes the
      certificate pass vacuously — nothing is non-materialized.
    """
    k = topk.ids.shape[0]
    kv = k if k_valid is None else k_valid
    if m_cap is None:
        m_cap = int(l + 6 * math.sqrt(l) + 8)
    k_s, k_t = jax.random.split(key)
    g_s = jax.random.gumbel(k_s, (k,), dtype=jnp.float32)
    pert_s = topk.values.astype(jnp.float32) + g_s
    b = jnp.log((jnp.asarray(n, jnp.float32) - kv) / l)
    lam = jnp.float32(l)

    plan = plan_tail(k_t, topk.ids, n, b, lam, m_cap, k_valid=k_valid)
    y_tail = score_fn(plan.pos).astype(jnp.float32)  # (m_cap,)
    live = jnp.arange(m_cap, dtype=jnp.int32) < plan.m_used
    pert_t = jnp.where(live, y_tail + plan.heights, -jnp.inf)
    # per-position max over duplicate tail atoms: stable-sort atoms by
    # (position, descending perturbed value), mark each position's first
    # (= largest, live-before-dead) occurrence, scatter the mark back so
    # atom order — and therefore argmax tie-breaking — is untouched
    order = jnp.lexsort((-pert_t, plan.pos))
    sorted_pos = plan.pos[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_pos[1:] != sorted_pos[:-1]]
    )
    keep = jnp.zeros((m_cap,), bool).at[order].set(first)
    pert_t = jnp.where(keep, pert_t, -jnp.inf)

    pert = jnp.concatenate([pert_s, pert_t])
    ids = jnp.concatenate([topk.ids.astype(jnp.int32), plan.pos])
    scores = jnp.concatenate([topk.values.astype(jnp.float32), y_tail])
    vals, pos = jax.lax.top_k(pert, num)
    out_ids = ids[pos]
    out_scores = scores[pos]
    dead = jnp.isneginf(vals)
    out_ids = jnp.where(dead, jnp.int32(-1), out_ids)
    out_scores = jnp.where(dead, -jnp.inf, out_scores)
    ok, bound = certificate(topk.values, b, c, vals[num - 1], plan.overflow)
    return TopKSampleResult(
        out_ids, vals, out_scores, ok, plan.m_used, bound, plan.overflow
    )
