"""The traffic generator is deterministic from the seed, and every seed
gets the same work on the same schedule, with its own token contents."""
from __future__ import annotations

import numpy as np
import pytest

from bench_tiny import BENCH  # noqa: F401  (puts the harness on sys.path)
from harness import cells, traffic as tf

MIXES = ["decode-heavy"]


def _mix(name):
    return cells._json(cells.BENCH_DIR / "traffic" / f"{name}.json")


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a = tf.serving(_mix(mix), 2**33 + 1, 20.0, 32768)
    b = tf.serving(_mix(mix), 2**33 + 1, 20.0, 32768)
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2])


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_work(mix):
    t = _mix(mix)
    p1, d1, o1 = tf.serving(t, 1, 20.0, 32768)
    p2, d2, o2 = tf.serving(t, 2, 20.0, 32768)
    assert len(p1) == len(p2) == round(t["arrivals"]["rate_per_s"] * 20.0)
    assert [len(p) for p in p1] == [len(p) for p in p2]  # one order
    assert len(set(map(len, p1))) > 10  # of many sizes
    np.testing.assert_array_equal(o1, o2)
    assert p1 != p2  # the seed draws the contents
    np.testing.assert_array_equal(d1, d2)  # one schedule for every seed
    for d in (d1, d2):
        assert d[0] == 0.0 and (np.diff(d) >= 0).all() and d[-1] < 20.0


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_in_their_range(mix):
    t = _mix(mix)
    prompts, _, outs = tf.serving(t, 7, 20.0, 32768)
    lo, hi = t["prompt_len"]["min"], t["prompt_len"]["max"]
    assert all(lo <= len(p) <= hi for p in prompts)
    assert all(0 <= tok < 32768 for p in prompts for tok in p)
    assert (outs == t["output_len"]["value"]).all()


def test_lognormal_quantiles_hold_the_median():
    spec = {"dist": "lognormal", "median": 128, "sigma": 0.7, "min": 1,
            "max": 10**6}
    vals = tf.lengths(spec, 1001, np.random.default_rng(0))
    assert np.median(vals) == 128


def test_gamma_arrivals_are_burstier_than_poisson():
    pois = np.diff(tf.arrivals({"process": "poisson", "rate_per_s": 10},
                               60.0))
    gam = np.diff(tf.arrivals({"process": "gamma", "rate_per_s": 10,
                               "cv": 3.0}, 60.0))
    assert gam.std() / gam.mean() > 2 * pois.std() / pois.mean()


def test_key_int_takes_large_seeds():
    k = tf.key_int(2**40 + 3)
    assert 0 <= k < 2**31 and k == tf.key_int(2**40 + 3)
    assert k != tf.key_int(2**40 + 4)
