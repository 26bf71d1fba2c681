"""Uniform sampling from the complement of a small set S ⊂ [0, n).

The paper's Algorithms 2-4 need uniform samples from ``[1, n] \\ S`` (the
"tail"). Rejection sampling has unbounded control flow (hostile to TPU), so
we use the exact order-statistics map: if ``s_0 < s_1 < ... < s_{k-1}`` are
the sorted elements of S, then

    f(u) = u + |{j : s_j - j <= u}|      for u in [0, n-k)

is a bijection from [0, n-k) onto [0, n) \\ S. Sampling u uniformly and
mapping through f gives exact uniform samples from the complement, in
O(log k) per sample via searchsorted, with fully static shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["complement_map", "sample_complement"]


def complement_map(u: jax.Array, s_sorted: jax.Array) -> jax.Array:
    """Map u in [0, n-k) to the (u+1)-th smallest element of [0,n) \\ S.

    Args:
      u: int array of indices into the complement, any shape.
      s_sorted: (k,) strictly increasing int array (the excluded set S).

    Returns:
      int array, same shape as u, with values in [0, n) \\ S.
    """
    k = s_sorted.shape[0]
    # t_j = s_j - j is nondecreasing; rank(u) = #{j : t_j <= u}.
    t = s_sorted - jnp.arange(k, dtype=s_sorted.dtype)
    rank = jnp.searchsorted(t, u, side="right")
    return u + rank.astype(u.dtype)


def sample_complement(
    key: jax.Array, n: int, s_sorted: jax.Array, num: int, n_excluded=None
) -> jax.Array:
    """Draw ``num`` iid uniform samples (with replacement) from [0,n) \\ S.

    ``n_excluded`` overrides the count of REAL exclusions when ``s_sorted``
    carries virtual entries >= n marking dead slots (see
    ``repro.core.estimators.sanitize_topk``): those never exclude anything,
    so the complement has ``n - n_excluded`` elements, not ``n - k``. May
    be a traced scalar; clamped so an empty complement stays in-range
    (callers must weight such draws out).
    """
    k = s_sorted.shape[0] if n_excluded is None else n_excluded
    with jax.named_scope("tail.draw"):
        hi = jnp.maximum(jnp.asarray(n, jnp.int32) - k, 1)
        u = jax.random.randint(key, (num,), 0, hi, dtype=jnp.int32)
        return complement_map(u, s_sorted.astype(jnp.int32))
