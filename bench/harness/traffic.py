"""The one traffic generator: every mix is a data file of parameters
under ``bench/traffic``, read here.

Sizes and gaps are drawn as a fixed multiset (evenly spaced quantiles of
the mix's distribution), in one fixed shuffled order for every seed: the
mix's pattern of bursts and of long and short requests. The seed draws
the token contents (and, in the runners, the weights and data). So every
seed gives the same work on the same schedule, and two seeds differ
little more than two runs of one seed do: when the seed ordered the
sizes, runs of one seed agreed to 0.1% and seeds differed by 6% in a
p95 of time per output token, since each order meets other prefill
buckets (PERF.md, Findings).

Open-loop arrivals follow the Poisson process of
``benchmarks/serve_load.py`` (exponential gaps at a fixed rate), or a
gamma process with a stated coefficient of variation for bursts.
"""
from __future__ import annotations

import math

import numpy as np
from scipy import stats


def key_int(seed: int, salt: int = 0) -> int:
    """A 31-bit integer drawn from any whole-number seed (seeds may pass
    2**32), for ``jax.random.key`` and the program's own seeds."""
    state = np.random.SeedSequence([int(seed), int(salt)]).generate_state(1)
    return int(state[0] >> 1)


def rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, gen: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from ``spec``, in the order ``gen`` draws.

    ``{"dist": "fixed", "value": v}``, ``{"dist": "uniform", "min", "max"}``
    or ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` (clipped).
    """
    q = _quantiles(n)
    dist = spec["dist"]
    if dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        vals = spec["median"] * np.exp(spec["sigma"] * stats.norm.ppf(q))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min", 1), spec.get("max", math.inf)
    vals = np.clip(np.round(vals), lo, hi).astype(np.int64)
    return gen.permutation(vals)


PATTERN_SEED = 0  # one pattern of gaps and sizes for every run seed


def arrivals(spec: dict, seconds: float,
             rate: float | None = None) -> np.ndarray:
    """Open-loop due times in [0, seconds), sorted, the same for every
    run seed.

    ``{"process": "poisson", "rate_per_s": r}`` or ``{"process": "gamma",
    "rate_per_s": r, "cv": c}``. The count is ``n = round(r * seconds)``;
    the n - 1 gaps are evenly spaced quantiles of the gap distribution,
    scaled to span (n - 1) / n of the window, in an order drawn from
    ``PATTERN_SEED``.
    """
    r = float(rate if rate is not None else spec["rate_per_s"])
    n = max(1, int(round(r * seconds)))
    q = _quantiles(n - 1)
    if spec["process"] == "poisson":
        gaps = stats.expon.ppf(q)
    elif spec["process"] == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        gaps = stats.gamma.ppf(q, shape, scale=1.0 / shape)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    if n > 1:  # n - 1 gaps spanning (n - 1) / n of the window
        gaps = gaps * (seconds * (n - 1) / n) / gaps.sum()
    order = np.random.default_rng(PATTERN_SEED).permutation(len(gaps))
    return np.concatenate([[0.0], np.cumsum(gaps[order])])


def serving(traffic: dict, seed: int, seconds: float, vocab: int,
            rate: float | None = None):
    """Prompts (lists of token ids), due times and output lengths of an
    open-loop serving mix: sizes and times the same for every seed, token
    ids drawn from ``seed``."""
    due = arrivals(traffic["arrivals"], seconds, rate)
    n = len(due)
    order = np.random.default_rng([PATTERN_SEED, 1])
    plen = lengths(traffic["prompt_len"], n, order)
    olen = lengths(traffic["output_len"], n, order)
    gen = rng(seed)
    prompts = [gen.integers(0, vocab, size=int(p)).tolist() for p in plen]
    return prompts, due, olen
