"""Device time by named scope, and the per-layer readers built on it: the
name-stack reduction, the readers on hand-built events, the log-linear
programs' scopes in their compiled HLO, and a recorded chip slice."""
from __future__ import annotations

import json
import re
import types
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import tiny_cell
from harness import cells, scopes, trace as tr

DATA = Path(__file__).with_name("data")
D0, H = "/device:TPU:0", "/host:CPU"
E = tr.Event
MS = 1e6


def test_components_drop_transform_wrappers():
    stack = "jit(estimate)/vmap(tail.draw)/while/body/vmap(jit(sort))"
    assert scopes.components(stack) == ["estimate", "tail.draw", "while",
                                        "body", "sort"]
    assert scopes.in_scope(stack, "tail.draw")
    assert not scopes.in_scope(stack, "tail.score")
    assert not scopes.in_scope("jit(f)/tail.drawn/add", "tail.draw")


def _decode_events():
    """Two decode executions of 4 ms: a while op of 4 ms whose body runs
    attention (1.5 ms), mlp (1 ms) and head (1 ms) ops; a prefill
    execution whose head op must not count."""
    ev = []
    for t0 in (0.0, 10 * MS):
        ev += [E(D0, tr.MODULES, "jit_decode_step(7)", t0, 4 * MS),
               E(D0, tr.OPS, "%while.1 = (...) while(...)", t0, 4 * MS),
               E(D0, tr.OPS, "%fusion.1 = bf16[8] fusion(...)",
                 t0 + 0.2 * MS, 1.5 * MS),
               E(D0, tr.OPS, "%fusion.2", t0 + 1.8 * MS, 1 * MS),
               E(D0, tr.OPS, "%fusion.3", t0 + 2.9 * MS, 1 * MS)]
    ev += [E(D0, tr.MODULES, "jit_prefill_step(3)", 20 * MS, 6 * MS),
           E(D0, tr.OPS, "%fusion.1", 20 * MS, 6 * MS)]
    return ev


def _stacks(scoped: bool = True):
    def body(name):
        return f"jit(decode_step)/while/body/{name}/dot" if scoped else (
            "jit(decode_step)/while/body/dot")

    return {
        "jit_decode_step(7)": {"while.1": "jit(decode_step)/while",
                               "fusion.1": body("attention"),
                               "fusion.2": body("mlp"),
                               "fusion.3": body("head/vmap(tail.draw)")},
        "jit_prefill_step(3)": {"fusion.1": body("head")},
    }


def _serving_ctx(events, stacks, **kw):
    return types.SimpleNamespace(
        events=events, stacks=stacks, window_s=0.03, peaks=None, results=[],
        scfg=types.SimpleNamespace(decode_window=8, batch_slots=4), **kw)


def test_decode_readers():
    ctx = _serving_ctx(_decode_events(), _stacks())
    # per decoded position: over 2 executions x 8 tokens
    read = {n: cells.metric_reader(n).read(ctx)
            for n in ("decode_head_ms", "decode_attention_ms")}
    assert read["decode_head_ms"] == pytest.approx(2 * 1.0 / 16)
    assert read["decode_attention_ms"] == pytest.approx(2 * 1.5 / 16)
    cov = scopes.coverage(ctx, "jit_decode_step", ("attention", "mlp", "head"))
    # the while op's own time (0.5 ms of each 4) lies under no scope
    assert cov == pytest.approx(3.5 / 4)


def test_loglinear_readers():
    ev = [E(D0, tr.MODULES, "jit_loglinear_probe(1)", 0, 10 * MS),
          E(D0, tr.OPS, "%fusion.7", 0, 6 * MS),
          E(D0, tr.OPS, "%sort.1", 6 * MS, 3 * MS),
          E(D0, tr.MODULES, "jit_loglinear_estimate(2)", 10 * MS, 4 * MS),
          E(D0, tr.OPS, "%fusion.8", 10 * MS, 3 * MS),
          E(D0, tr.OPS, "%gather.1", 13 * MS, 1 * MS)]
    stacks = {
        "jit_loglinear_probe(1)": {
            "fusion.7": "jit(loglinear_probe)/ivf.members/dot_general",
            "sort.1": "jit(loglinear_probe)/ivf.select/sort"},
        # named by other ids in the metadata, and held twice: the HLO
        # that holds every op the trace shows is the one that ran
        "jit_loglinear_estimate(98)": {
            "fusion.8": "jit(loglinear_estimate)/vmap(tail.draw)/sort"},
        "jit_loglinear_estimate(99)": {
            "fusion.8": "jit(loglinear_estimate)/vmap(tail.draw)/sort",
            "gather.1": "jit(loglinear_estimate)/vmap(tail.score)/gather"},
    }
    ctx = types.SimpleNamespace(events=ev, stacks=stacks, window_s=0.02,
                                peaks=None)
    assert cells.metric_reader("probe_gather_ms.loglinear").read(ctx) == (
        pytest.approx(6.0))
    assert cells.metric_reader("tail_draw_ms.loglinear").read(ctx) == (
        pytest.approx(3.0))


def test_serving_engine_readers():
    ev = [E(H, "python3", "serve.admit", 0, 1 * MS),
          E(H, "python3", "serve.prefill", 1 * MS, 2 * MS),
          E(H, "python3", "serve.sync", 3 * MS, 10 * MS),
          E(H, "python3", "serve.emit", 12 * MS, 3 * MS),  # overlaps sync
          E(H, "python3", "serve.idle", 15 * MS, 10 * MS),
          E(H, "python3", "serve.decode", 26 * MS, 1 * MS)]
    ctx = _serving_ctx(ev, {}, stats={"prefill_tokens": 30,
                                      "prefill_positions": 128})
    busy = cells.metric_reader("host_busy_share.serve").read(ctx)
    assert busy == pytest.approx(100 * 7e-3 / 0.03)
    pad = cells.metric_reader("prefill_pad_share").read(ctx)
    assert pad == pytest.approx(100 * (1 - 30 / 128))


@pytest.mark.parametrize("stacks", ["unscoped", "none"])
def test_new_readers_find_nothing_in_a_program_without_scopes(stacks):
    """A program without the scopes, spans or counter (the parent of the
    change that added them), or a trace without its HLO, gives no value,
    and no error."""
    ctx = _serving_ctx(_decode_events(),
                       _stacks(scoped=False) if stacks == "unscoped" else {},
                       stats={"prefill_tokens": 30})
    for name in ("decode_head_ms", "decode_attention_ms",
                 "probe_gather_ms.loglinear", "tail_draw_ms.loglinear",
                 "host_busy_share.serve", "prefill_pad_share"):
        assert cells.metric_reader(name).read(ctx) is None


def test_name_stacks_from_a_trace_file(tmp_path):
    """The HLO in a trace file's metadata plane gives each instruction of
    the program that ran its name stack."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            y = jnp.tanh(x)
        with jax.named_scope("mlp"):
            return jax.vmap(lambda r: r * 2.0 + 1.0)(y)

    x = jnp.ones((8, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path / "cell-1")):  # a runner's layout
        f(x).block_until_ready()
    path = scopes.latest_trace(tmp_path)
    stacks = scopes.name_stacks(path)
    [table] = [t for name, t in stacks.items()
               if tr.base_name(name) == "jit_f"]
    got = {c for st in table.values() for c in scopes.components(st)}
    assert {"attention", "mlp"} <= got


def _hlo_scopes(compiled) -> set[str]:
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {c for n in names for c in scopes.components(n)}


@pytest.fixture(scope="module")
def loglinear_setup():
    cell = tiny_cell("loglinear-imagenet.online")
    return cells.runner(cell).build(cell, 3)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_probe_program_carries_ivf_scopes(loglinear_setup, use_kernel):
    s = loglinear_setup
    index = s.index
    if use_kernel:
        import dataclasses

        index = type(index)(dataclasses.replace(index.config,
                                                use_kernel=True), index.state)
    got = _hlo_scopes(s.probe.lower(index, s.pool, np.int32(0)).compile())
    assert {"ivf.centroids", "ivf.members", "ivf.overflow",
            "ivf.select"} <= got


def test_estimate_program_carries_tail_scopes(loglinear_setup):
    s = loglinear_setup
    j = np.int32(0)
    ids, values = s.probe(s.index, s.pool, j)
    got = _hlo_scopes(s.estimate.lower(s.db, s.pool, j, ids, values,
                                       s.base_key).compile())
    assert {"tail.draw", "tail.score"} <= got


def test_recorded_scoped_decode_slice():
    """One decode execution recorded on a TPU v5e with the named scopes,
    and the scope readers' values as they were read from it."""
    rec = json.loads((DATA / "tpu_v5e_decode_scoped.json").read_text())
    want = rec["expect"]
    ctx = types.SimpleNamespace(
        events=[tr.Event(*e) for e in rec["events"]], stacks=rec["stacks"],
        window_s=want["window_s"],
        scfg=types.SimpleNamespace(decode_window=want["decode_window"]))
    for name, value in want["readers"].items():
        assert cells.metric_reader(name).read(ctx) == pytest.approx(value)
    assert scopes.coverage(ctx, "jit_decode_step", (
        "attention", "mlp", "head")) == pytest.approx(want["coverage"])
