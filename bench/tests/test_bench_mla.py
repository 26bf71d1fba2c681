"""The latent-attention expert cell (``moonlight-16b-a3b.decode-2k``) at a
tiny size on the CPU: its runner and reference run end to end, and on
each of three seeds a sound run reads ``correct`` true while the float8
control and a planted fault read it false; the decode counts against
hand counts; the cell's new per-layer readers on a synthetic trace."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import types

import jax
import pytest

SEEDS = (1, 2, 3)

from bench_tiny import TINY_SERVER
from harness import cells, mla_counts, trace as tr  # noqa: E402

E = tr.Event
D0 = "/device:TPU:0"
MS = 1e6

CELL = "moonlight-16b-a3b.decode-2k"
TINY_MLA = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "num_hidden_layers": 2, "vocab_size": 4096,
}


def tiny_mla_cell() -> cells.Cell:
    """The cell at smoke widths with a float32 trunk (served by a float32
    program, :func:`_float32_program`) and a selection bias wide enough
    to change the chosen experts of many tokens."""
    cell = cells.load_cell(CELL)
    cfg = dict(cell.config, **TINY_MLA)
    cfg["dtype"] = dict(cfg["dtype"], trunk="float32")
    cfg["synthetic_weights"] = dict(cfg["synthetic_weights"],
                                    embed_clusters=64, router_bias_std=0.1)
    traffic = dict(cell.traffic)
    traffic.update(
        arrivals=dict(traffic["arrivals"], rate_per_s=4.0),
        prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 24},
        output_len={"dist": "fixed", "value": 24},
        server=TINY_SERVER,
    )
    # a limit of the tiny cell's own: with a float32 trunk every served
    # token is the reference's best, and sound runs read a mean gap of
    # 0.0 on seeds 1-6; the float8 control reads 0.021-0.081 there and
    # routing without the selection bias 0.020-0.250. (A bfloat16 trunk
    # at 64 wide, with 2 of 8 experts a token, now and then sends a
    # near-tie to another expert than the float32 reference does: mean
    # gaps up to 0.045 over 8 seeds, as far as the control's 0.027. The
    # chip cell's limit is set over its own seeds at full width, PERF.md)
    limits = {"check_tokens": 48, "served_gap_mean": 0.005}
    return dataclasses.replace(cell, config=cfg, traffic=traffic,
                               limits=limits)


def _run(cell, seed, **kw):
    return cells.runner(cell).run(cell, seed=seed, seconds=1.0, trace=False,
                                  devs=jax.devices(), t_start=0.0, **kw)


@contextlib.contextmanager
def unbiased_routing():
    """The router picks its top-k by the scores alone, as if the
    selection bias were zero."""
    from repro.models import moe

    real = moe.route

    def route(p, cfg, x):
        return real(dict(p, bias=jax.numpy.zeros_like(p["bias"])), cfg, x)

    moe.route = route
    try:
        yield
    finally:
        moe.route = real


FAULTS = {"unbiased_routing": unbiased_routing}


@pytest.fixture(scope="module", autouse=True)
def _float32_program():
    """``Server`` builds its model with the float32 precision policy."""
    import repro.serve.server as server_lib
    from repro.models.model import Model

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(server_lib, "Model",
                   functools.partial(Model, precision_policy="f32"))
        yield


@pytest.fixture(scope="module")
def cell():
    return tiny_mla_cell()


def test_sound_run_is_correct(cell):
    line = _run(cell, SEEDS[0])
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert set(line["checks"]) == {"served_gap_mean"}
    assert set(line["metrics"]) == {"setup_s", "ttft_p95_ms", "tpot_p95_ms"}
    assert line["compiles_in_window"] == 0
    assert line["cache_bytes"] > 0


@pytest.fixture(scope="module")
def served(cell):
    """Mean gaps of the tiny cell's served tokens, by (program, seed,
    control): the program is ``"sound"`` or a fault of ``FAULTS``, built
    once by the runner (the fault planted while it compiles); each seed's
    weights are then swapped into its server (``Server.refresh_index``)
    and the seed's traffic served, so every seed runs the same compiled
    programs."""
    from harness import traffic as tf
    from repro.models.model import Model

    runner = cells.runner(cell)
    out = {}
    for program in ("sound",) + tuple(sorted(FAULTS)):
        plant = (FAULTS[program]() if program in FAULTS
                 else contextlib.nullcontext())
        with plant:
            setup = runner.build(cell, SEEDS[0])
        arch = runner.arch_config(cell.config, setup.ref)
        for seed in SEEDS:
            w = setup.ref.init_weights(cell.config,
                                       jax.random.key(tf.key_int(seed)))
            setup.server.refresh_index(runner.program_params(w, Model(arch)))
            prompts, due, _ = tf.serving(cell.traffic, seed, 1.0,
                                         setup.m["v"])
            results = setup.server.run(prompts, arrivals=due)
            one = dataclasses.replace(setup, weights=w)
            assert all(r.status == "ok" for r in results)
            for quant in ("", "fp8")[: 1 + (program == "sound")]:
                out[program, seed, bool(quant)] = runner.compare(
                    one, prompts, results, seed, cell.limits,
                    quant=quant)["gap_mean"]
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_readings_pass(cell, served, seed):
    assert served["sound", seed, False] <= cell.limits["served_gap_mean"]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_incorrect(cell, served, seed):
    """The reference with float8 weights in the program's place fails the
    mean-gap limit."""
    assert served["sound", seed, True] > cell.limits["served_gap_mean"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_incorrect(cell, served, fault, seed):
    assert served[fault, seed, False] > cell.limits["served_gap_mean"]


def test_mla_roofline_count_by_hand():
    """Two tokens of contexts 100 and 300, 16 heads, r 512, rope 64, 27
    layers: 400 latent rows of 576 bf16 values read once, 16 queries of
    576 bf16 values and 16 latent outputs of 512 float32 per token; the
    scores 2·16·576 and the sum 2·16·512 FLOPs per position."""
    m = {"h": 16, "r": 512, "rope": 64, "layers": 27}
    flops, bytes_ = mla_counts.mla_decode_work(m, 400, 2)
    assert flops == 27 * 400 * (2 * 16 * 576 + 2 * 16 * 512)
    assert bytes_ == 27 * (400 * 576 * 2 + 2 * 16 * (576 * 2 + 512 * 4))


def test_decode_weight_bytes_are_the_reference_weights(cell):
    """What a decode position reads of the weights is every weight of
    the reference but the input embedding table, at the tiny cell's
    widths (float32 trunk) and at the chip cell's (bfloat16)."""
    for c, itemsize in ((cell, 4), (cells.load_cell(CELL), 2)):
        ref = cells.reference(c.config)
        w = jax.eval_shape(lambda k, c=c: ref.init_weights(c.config, k),
                           jax.random.key(0))
        nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(w))
        want = nbytes - w["embed"].size * w["embed"].dtype.itemsize
        got = mla_counts.decode_weight_bytes(ref.dims(c.config), itemsize)
        assert got == want


def test_decode_step_count_by_hand():
    """One layer of each kind (d 4, 2 heads, r 3, nope 2, rope 1, v 2;
    dense f 5; 8 experts of 3, 2 held, top 2, shared 3; vocab 10), two
    positions decoding three tokens at contexts summing to 7."""
    m = {"d": 4, "h": 2, "r": 3, "nope": 2, "rope": 1, "vd": 2, "f": 5,
         "ef": 3, "shared_f": 3, "experts": 8, "held": 2, "topk": 2,
         "v": 10, "layers": 2, "dense_layers": 1, "moe_layers": 1}
    attn = 4 * 2 * 3 + 4 * 4 + 3 * 2 * 4 + 2 * 2 * 4  # W_Q W_DKV W_UKV W_O
    norms = 4 * (3 + 4 + 4)
    dense = 3 * 4 * 5
    moe = 4 * 8 + 3 * 4 * 3 * 2 + 3 * 4 * 3  # router, held, shared
    weights = (2 * (2 * attn + norms) + 2 * dense + 2 * moe + 4 * 8
               + 4 * 4 + 4 * 10 * 4)  # bias, final norm, f32 head
    assert mla_counts.decode_weight_bytes(m) == weights
    flops, bytes_ = mla_counts.decode_step_work(m, 7, 3, 2)
    assert bytes_ == 2 * weights + 2 * (7 + 3) * 4 * 2 + 3 * 4 * 2
    per_token = (2 * attn + dense + 4 * 8 + 3 * 4 * 3 * (2 * 2 / 8)
                 + 3 * 4 * 3 + 10 * 4)
    assert flops == pytest.approx(
        2 * 3 * per_token + 2 * 7 * 2 * 2 * (4 + 3))


def _mla_ctx():
    """Two decode executions of 4 ms: attention 1 ms (the kernel 0.5 ms of
    it, under ``mla.attend``), route 0.25, dispatch 0.5, experts 1, shared
    0.25, head 0.5 ms; the while op's own time under no scope."""
    scoped = [("fusion.1", "attention/mla.latent/dot_general", 0.25),
              ("flash_decode.1", "attention/mla.attend/flash_decode", 0.5),
              ("fusion.2", "attention/mla.up/dot_general", 0.25),
              ("fusion.3", "mlp/moe.route/logistic", 0.25),
              ("sort.1", "mlp/moe.dispatch/sort", 0.5),
              ("fusion.4", "mlp/moe.experts/dot_general", 1.0),
              ("fusion.5", "mlp/moe.shared/dot_general", 0.25),
              ("fusion.6", "head/dot_general", 0.5)]
    ev, stacks = [], {"while.1": "jit(decode_step)/while"}
    for t0 in (0.0, 10 * MS):
        ev += [E(D0, tr.MODULES, "jit_decode_step(7)", t0, 4 * MS),
               E(D0, tr.OPS, "%while.1 = (...) while(...)", t0, 4 * MS)]
        t = t0 + 0.1 * MS
        for name, _, ms in scoped:
            ev.append(E(D0, tr.OPS, f"%{name} = f32[] fusion()", t, ms * MS))
            t += ms * MS
    for name, stack, _ in scoped:
        stacks[name] = f"jit(decode_step)/while/body/{stack}"
    # 2 executions x 8 tokens x 64 slots, every token at context 1000
    results = [types.SimpleNamespace(status="ok", prompt_len=999,
                                     tokens=[0, 0])] * 128
    return types.SimpleNamespace(
        events=ev, stacks={"jit_decode_step(7)": stacks}, window_s=0.02,
        results=results, stats={},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        scfg=types.SimpleNamespace(decode_window=8, batch_slots=64),
        m={"h": 16, "r": 512, "rope": 64, "layers": 27})


def test_new_readers_on_a_synthetic_trace():
    ctx = _mla_ctx()
    read = {n: cells.metric_reader(n).read(ctx)
            for n in ("decode_moe_ms", "moe_dispatch_ms",
                      "mla_decode_roofline", "decode_attention_ms")}
    # per decoded position: 2 executions x 8 tokens
    assert read["decode_moe_ms"] == pytest.approx(2 * 2.0 / 16)
    assert read["moe_dispatch_ms"] == pytest.approx(2 * 0.75 / 16)
    assert read["decode_attention_ms"] == pytest.approx(2 * 1.0 / 16)
    _, bytes_ = mla_counts.mla_decode_work(ctx.m, 128 * 1000, 128)
    assert read["mla_decode_roofline"] == pytest.approx(
        100 * bytes_ / 819e9 / 1e-3)


def test_decode_step_roofline_on_a_synthetic_trace(cell):
    """The whole step's least time over the decode program's 8 ms, at the
    tiny cell's widths (whose weights a 4-ms execution reads easily),
    counted in the chip cell's bfloat16."""
    ctx = _mla_ctx()
    ctx.m = cells.reference(cell.config).dims(cell.config)
    got = cells.metric_reader("decode_step_roofline.mla").read(ctx)
    flops, bytes_ = mla_counts.decode_step_work(ctx.m, 128 * 1000, 128,
                                                2 * 8)
    want = 100 * max(flops / 197e12, bytes_ / 819e9) / 8e-3
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_new_readers_find_nothing_without_their_scopes():
    """A decode program without the MLA and expert scopes (the parent's),
    or a CPU run without peaks, gives no value, and no error."""
    ctx = _mla_ctx()
    ctx.stacks = {"jit_decode_step(7)": {
        n: "jit(decode_step)/while/body/dot" for n in
        ctx.stacks["jit_decode_step(7)"]}}
    ctx.events = [e for e in ctx.events if "flash_decode" not in e.name]
    for name in ("decode_moe_ms", "moe_dispatch_ms", "mla_decode_roofline"):
        assert cells.metric_reader(name).read(ctx) is None
    ctx = _mla_ctx()
    ctx.peaks = None
    for name in ("mla_decode_roofline", "decode_step_roofline.mla"):
        assert cells.metric_reader(name).read(ctx) is None
