"""Plain reference of the paper's log-linear model (arXiv:1707.03372):
p(i | θ) ∝ exp(θ · x_i) over a fixed feature table. It imports nothing of
the program under test.

``make_features`` synthesizes the table from the seed: unit-norm rows
clustered around ``centres`` random centres with Gaussian noise, as real
embedding tables cluster (§4.1.1); ``make_thetas`` draws query parameters
as in §4.1.2: a table row, perturbed, at the paper's temperature.

``exact_log_z`` is log Σ_i exp(θ · x_i) over all n rows, float32 at
``Precision.HIGHEST``. ``sample_pit`` judges sampled ids against the exact
distribution over all n rows (a randomized probability integral
transform, uniform on [0, 1] under exact sampling) and ``ks_uniform``
measures how far a set of such values lies from uniform. ``scores`` are
θ · x in float64 on the host; ``stratified_log_z`` recomputes
Algorithm 3's estimate from the rows an estimator chose (its top-k set S
and tail draw T) in float64:
log(Σ_S e^y + (n - k)/l · Σ_T e^y).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("n", "d", "centres", "noise"))
def make_features(key, *, n: int, d: int, centres: int, noise: float):
    k0, k1, k2 = jax.random.split(key, 3)
    c = jax.random.normal(k0, (centres, d))
    assign = jax.random.randint(k1, (n,), 0, centres)
    x = c[assign] + noise * jax.random.normal(k2, (n, d))
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("count", "noise", "temperature"))
def make_thetas(key, db, *, count: int, noise: float, temperature: float):
    k0, k1 = jax.random.split(key)
    rows = jax.random.randint(k0, (count,), 0, db.shape[0])
    th = db[rows] + noise * jax.random.normal(k1, (count, db.shape[1]))
    return th / jnp.linalg.norm(th, axis=1, keepdims=True) / temperature


@jax.jit
def exact_log_z(db, thetas):
    """(b,) log Z of each θ over every row of ``db``."""
    return jax.nn.logsumexp(jnp.dot(db, thetas.T, precision=HI), axis=0)


@jax.jit
def sample_pit(db, thetas, ids, v):
    """(b,) u = P(score > score of the sample) + v · p(sample) under
    p(i) ∝ exp(θ · x_i) over every row of ``db``, for each θ of
    ``thetas`` (b, d) with its sampled id of ``ids`` (b,) and its
    uniform ``v`` (b,). Exact sampling makes u uniform on [0, 1]."""
    s = jnp.dot(thetas, db.T, precision=HI)  # (b, n)
    lz = jax.nn.logsumexp(s, axis=1, keepdims=True)
    p = jnp.exp(s - lz)
    sx = jnp.take_along_axis(s, ids[:, None].astype(jnp.int32), axis=1)
    above = jnp.sum(jnp.where(s > sx, p, 0.0), axis=1)
    return above + v * jnp.exp(sx[:, 0] - lz[:, 0])


def ks_uniform(u: np.ndarray) -> float:
    """Kolmogorov-Smirnov distance of the values ``u`` from uniform on
    [0, 1]: the widest gap between their empirical CDF and the identity."""
    u = np.sort(np.asarray(u, np.float64))
    m = len(u)
    i = np.arange(1, m + 1)
    return float(max((i / m - u).max(), (u - (i - 1) / m).max()))


def scores(rows: np.ndarray, theta: np.ndarray) -> np.ndarray:
    """θ · x of each row, in float64 on the host."""
    return rows.astype(np.float64) @ theta.astype(np.float64)


def stratified_log_z(db_rows_s: np.ndarray, db_rows_t: np.ndarray,
                     theta: np.ndarray, n: int) -> float:
    """Algorithm 3 in float64 from the chosen rows: S (k, d) and T (l, d)."""
    ys = scores(db_rows_s, theta)
    yt = scores(db_rows_t, theta)
    k, l = len(ys), len(yt)
    m = max(ys.max(), yt.max())
    z = np.exp(ys - m).sum() + (n - k) / l * np.exp(yt - m).sum()
    return float(m + np.log(z))
