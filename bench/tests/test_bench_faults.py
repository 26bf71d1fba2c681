"""Whole runs of each cell at tiny size on the CPU (the harness's look
for a chip skipped), sound and with the timed path broken underneath:
a sound run must read ``correct`` true, and each fault the cell can have,
and its control, must turn it false."""
from __future__ import annotations

import jax
import pytest

from bench_tiny import tiny_cell
from harness import cells, faults

SERVING = "mistral-7b.decode-heavy"
LOGLINEAR = "loglinear-imagenet.online"


def _run(workload, **kw):
    cell = tiny_cell(workload)
    return cells.runner(cell).run(cell, seed=2**33 + 17, seconds=1.0,
                                  trace=False, devs=jax.devices(),
                                  t_start=0.0, **kw)


def test_serving_sound_run_is_correct():
    line = _run(SERVING)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "ttft_p95_ms", "tpot_p95_ms"}
    assert line["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["altered_tokens", "stale_cache"])
def test_serving_fault_is_incorrect(fault):
    with faults.FAULTS[fault]():
        line = _run(SERVING)
    assert not line["correct"], line["checks"]


def test_serving_control_is_incorrect():
    """The control (the reference with float8 weights in the program's
    place) fails the served-gap limit through the whole run."""
    line = _run(SERVING, control=True)
    assert not line["checks"]["served_gap_max"]["ok"], line["checks"]
    assert not line["correct"]


def test_loglinear_sound_run_is_correct():
    line = _run(LOGLINEAR)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"setup_s", "theta_per_s"}
    assert set(line["checks"]) == {"logz_rel_err", "logz_arith_err",
                                   "tail_score_err", "sample_ks"}


@pytest.mark.parametrize("fault,check", [
    ("altered_log_z", "logz_arith_err"),
    ("dropped_tail", "logz_arith_err"),
    ("argmax_sampler", "sample_ks"),
])
def test_loglinear_fault_is_incorrect(fault, check):
    with faults.FAULTS[fault]():
        line = _run(LOGLINEAR)
    assert not line["checks"][check]["ok"], line["checks"]
    assert not line["correct"]


def test_loglinear_control_fails_the_score_check():
    """The control (scores in three bfloat16 passes instead of float32)
    must fail the tail-score limit."""
    line = _run(LOGLINEAR, control=True)
    assert not line["checks"]["tail_score_err"]["ok"], line["checks"]
    assert not line["correct"]
