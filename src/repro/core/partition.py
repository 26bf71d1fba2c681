"""Partition-function estimation (paper Algorithm 3).

``Ẑ = Σ_{i∈S} e^{y_i} + (n-k)/l · Σ_{j∈T} e^{y_j}`` with S the (approximate)
top-k set and T an iid uniform sample (with replacement, as in the paper)
from the complement. Unbiased (Thm 3.4); relative error ε w.p. 1-δ for
``k l >= (2/3) ε^{-2} n e^c ln(1/δ)``.

Everything is computed in log-space (weighted logsumexp) so that the huge
unnormalized probabilities of real LM heads never overflow; the unbiased
linear-space estimate is recovered as ``exp(log_z)`` when needed (tests).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.complement import sample_complement
from repro.core.gumbel import TopK

__all__ = ["PartitionEstimate", "partition_estimate", "stratified_logsumexp"]


class PartitionEstimate(NamedTuple):
    log_z: jax.Array  # () float32 — log of the unbiased estimate Ẑ
    tail_ids: jax.Array  # (l,) int32 — T (reused by expectation estimates)
    tail_values: jax.Array  # (l,) float32 — y over T


def stratified_logsumexp(
    y_s: jax.Array, y_t: jax.Array, log_w_tail: float | jax.Array
) -> jax.Array:
    """log( Σ_S e^{y_s} + e^{log_w_tail} Σ_T e^{y_t} ), numerically stable."""
    y_all = jnp.concatenate([y_s, y_t + log_w_tail])
    return jax.nn.logsumexp(y_all)


def partition_estimate(
    key: jax.Array,
    topk: TopK,
    n: int,
    score_fn: Callable[[jax.Array], jax.Array],
    *,
    l: int,
) -> PartitionEstimate:
    """Algorithm 3. ``score_fn`` maps ids -> unnormalized log-probs."""
    k = topk.ids.shape[0]
    with jax.named_scope("tail.draw"):
        s_sorted = jnp.sort(topk.ids).astype(jnp.int32)
    tail_ids = sample_complement(key, n, s_sorted, l)
    # y over S is RECOMPUTED through score_fn (not read from topk.values):
    # keeps Ẑ differentiable w.r.t. the parameters through both strata
    # (∇ log Ẑ = Algorithm 4 with f = φ) and robust to stale index values.
    with jax.named_scope("tail.score"):
        y_s = score_fn(topk.ids.astype(jnp.int32)).astype(jnp.float32)
        y_t = score_fn(tail_ids).astype(jnp.float32)
    log_w_tail = jnp.log((jnp.asarray(n, jnp.float32) - k) / l)
    log_z = stratified_logsumexp(y_s, y_t, log_w_tail)
    return PartitionEstimate(log_z, tail_ids, y_t)
