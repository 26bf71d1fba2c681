"""The trace reduction: busy time as a union of op intervals, program
and kernel times, top ops, idle gaps labelled by host spans, and the
per-layer readers built on them."""
from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from bench_tiny import BENCH  # noqa: F401
from harness import cells, trace as tr

DATA = Path(__file__).with_name("data")
D0, D1, H = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
E = tr.Event


def _events():
    ms = 1e6
    return [
        E(D0, tr.MODULES, "jit_decode_step(7)", 0, 4 * ms),
        E(D0, tr.MODULES, "jit_decode_step(7)", 10 * ms, 4 * ms),
        E(D0, tr.MODULES, "jit_prefill_step(3)", 20 * ms, 6 * ms),
        E(D0, tr.OPS, "fusion.1", 0, 2 * ms),
        E(D0, tr.OPS, "flash_decode_kernel", 1 * ms, 2 * ms),  # overlaps
        E(D0, tr.OPS, "fusion.1", 10 * ms, 4 * ms),
        E(D0, tr.OPS, "fusion.2", 20 * ms, 6 * ms),
        E(D1, tr.OPS, "fusion.1", 0, 1 * ms),
        E(H, "python", "run", 0, 30 * ms),
        E(H, "python", "PjitFunction(decode_step)", 4 * ms, 6 * ms),
    ]


def test_busy_is_the_union_averaged_over_devices():
    # device 0: [0,3) + [10,14) + [20,26) = 13 ms; device 1: 1 ms
    assert tr.busy_s(_events()) == pytest.approx((13e-3 + 1e-3) / 2)


def test_program_and_kernel_events():
    ev = _events()
    assert len(tr.module_events(ev, "jit_decode_step")) == 2
    assert len(tr.module_events(ev, "jit_prefill_step")) == 1
    kern = tr.op_events(ev, tr.kernel_match("flash_decode"))
    assert [e.name for e in kern] == ["flash_decode_kernel"]


def test_top_ops_and_idle_gaps():
    ev = _events()
    top = dict(tr.top_ops(ev))
    # fusion.1: [0,2) with flash_decode [1,3) overlapping, not nested
    assert top["fusion.1"] == pytest.approx(6e-3)
    assert top["fusion.2"] == pytest.approx(6e-3)
    gaps = dict(tr.idle_gaps(ev))
    # [3,10) is covered by the dispatch span; [14,20) only by "run"
    assert gaps == {"PjitFunction(decode_step)": pytest.approx(7e-3),
                    "run": pytest.approx(6e-3)}


def test_self_time_leaves_out_nested_ops():
    ms = 1e6
    ev = [E(D0, tr.OPS, "%while.1 = (s32[]) while(...)", 0, 10 * ms),
          E(D0, tr.OPS, "%fusion.3 = f32[8] fusion(...)", 1 * ms, 2 * ms),
          E(D0, tr.OPS, "%flash_decode.2 = f32[8] custom-call(...)",
            4 * ms, 3 * ms)]
    assert dict(tr.top_ops(ev)) == {"%while.1": pytest.approx(5e-3),
                                    "%flash_decode.2": pytest.approx(3e-3),
                                    "%fusion.3": pytest.approx(2e-3)}
    assert tr.busy_s(ev) == pytest.approx(10e-3)


def test_serving_readers():
    ctx = types.SimpleNamespace(
        events=_events(), window_s=0.03, peaks=None,
        scfg=types.SimpleNamespace(decode_window=8, batch_slots=4),
        stats={"tokens": 20, "decode_dispatches": 2}, results=[])
    read = {n: cells.metric_reader(n).read(ctx)
            for n in ("decode_step_ms", "prefill_step_ms",
                      "idle_share.serve", "decode_batch_fill")}
    assert read["decode_step_ms"] == pytest.approx(8 / 16)
    assert read["prefill_step_ms"] == pytest.approx(6.0)
    assert read["idle_share.serve"] == pytest.approx(
        100 * (1 - 7e-3 / 0.03))
    assert read["decode_batch_fill"] == pytest.approx(100 * 20 / 64)


def test_readers_find_nothing_in_a_trace_without_a_device():
    ctx = types.SimpleNamespace(events=[E(H, "python", "run", 0, 1.0)],
                                window_s=1.0, peaks=None)
    for name in ("decode_step_ms", "prefill_step_ms", "idle_share.serve",
                 "probe_ms.loglinear", "estimator_ms.loglinear",
                 "idle_share.loglinear"):
        assert cells.metric_reader(name).read(ctx) is None


@pytest.mark.parametrize("path", sorted(DATA.glob("*.json")),
                         ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    """A slice of a trace recorded on a TPU v5e, with the reduction's
    results as they were read from it."""
    rec = json.loads(path.read_text())
    ev = [tr.Event(*e) for e in rec["events"]]
    want = rec["expect"]
    assert tr.busy_s(ev) == pytest.approx(want["busy_s"], rel=1e-9)
    for module, count in want["modules"].items():
        assert len(tr.module_events(ev, module)) == count
    for kernel, count in want["kernels"].items():
        assert len(tr.op_events(ev, tr.kernel_match(kernel))) == count
    assert tr.top_ops(ev, 3) == [[n, pytest.approx(s)]
                                 for n, s in want["top_ops"]]
