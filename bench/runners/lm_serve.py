"""Serving runner: an open-loop traffic mix through the program's
``repro.serve.server.Server``, a decoder configuration at its published
widths, and the served tokens compared with the plain reference.

Set-up makes the weights on the device in one jitted call from the seed
(``bench/references/<reference>.py``), builds the server (which builds
its head index on the device), and warms every prefill bucket the mix's
prompt lengths fall in plus the decode window. The window then drives
``Server.run(prompts, arrivals=due)``: each request is due at its arrival
time whether or not the server keeps up, and its time to first token
counts from then.

``correct`` needs every request served in full, and every sampled served
token to lie within the limit of the reference's best logit at its
position, in units of that position's logit standard deviation. The
configuration's weights make the head's sampling greedy in effect (see
``synthetic_weights`` in the configuration file), which is what makes
that comparison valid for a sampler. With ``control`` the served tokens
are replaced, position by position, by those that the reference computed
at the precision step below the configuration's puts first (the
control, which has to read ``correct`` false).
"""
from __future__ import annotations

import dataclasses
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells, device, peaks, report, trace as tr
from harness import traffic as tf

TRACE_DIR = cells.ROOT / ".bench_trace"


def arch_config(cfg: dict, ref):
    from repro.models.config import ArchConfig

    m = ref.dims(cfg)
    s = cfg["serve"]
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=m["layers"],
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["kv"],
        head_dim=m["hd"], d_ff=m["f"], vocab=m["v"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        head_mode=s["head_mode"], head_mips=s["head_mips"],
        head_use_kernel=bool(s["head_use_kernel"]),
        head_fused_decode=bool(s["head_fused_decode"]),
        head_n_probe=int(s["head_n_probe"]),
    )


def program_params(w: dict, model) -> dict:
    """The reference's weights in the program's parameter layout (the same
    device buffers; the program's norms hold ``weight - 1``)."""
    params = {
        "embed": w["embed"],
        "out_embed": w["out_embed"],
        "final_norm": w["final_norm"] - 1.0,
        "blocks": [{"0": {
            "norm1": w["attn_norm"] - 1.0,
            "mix": {"wq": w["q"], "wk": w["k"], "wv": w["v"], "wo": w["o"]},
            "norm2": w["mlp_norm"] - 1.0,
            "mlp": {"w1": w["gate"], "w2": w["down"], "w3": w["up"]},
        }}],
    }
    want = jax.eval_shape(model.init, jax.random.key(0))
    got_t = jax.tree.structure(params)
    if got_t != jax.tree.structure(want):
        raise RuntimeError(f"parameter layout changed: {got_t}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if a.shape != b.shape:
            raise RuntimeError(f"parameter shape {a.shape} != {b.shape}")
    return params


@dataclasses.dataclass
class Setup:
    cfg: dict
    traffic: dict
    ref: types.ModuleType
    m: dict
    weights: dict
    server: object
    new_tokens: int


def _reset_stats(server) -> None:
    keep = ("cache_bytes", "index_spill", "index_bytes")
    for k, v in server.stats.items():
        if k not in keep:
            server.stats[k] = type(v)()


def build(cell, seed: int) -> Setup:
    """Weights, server and warm-up for ``seed``; every shape the mix uses
    is compiled (or loaded from the persistent cache) on return."""
    from repro.models.model import Model
    from repro.serve.server import ServeConfig, Server, _bucket

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference(cfg)
    m = ref.dims(cfg)
    if traffic["output_len"]["dist"] != "fixed":
        raise ValueError("Server takes one max_new_tokens for all requests: "
                         "the output length must be fixed")
    new_tokens = int(traffic["output_len"]["value"])
    w = ref.init_weights(cfg, jax.random.key(tf.key_int(seed)))
    jax.block_until_ready(w)
    arch = arch_config(cfg, ref)
    params = program_params(w, Model(arch))
    sv = traffic["server"]
    scfg = ServeConfig(
        batch_slots=sv["batch_slots"], max_seq=sv["max_seq"],
        max_new_tokens=new_tokens, decode_window=sv["decode_window"],
        prefill_chunk=sv["prefill_chunk"], seed=tf.key_int(seed, 1),
    )
    server = Server(arch, params, scfg)
    jax.block_until_ready(server.index)
    # one prefill program per prompt bucket the mix can produce, and the
    # decode program: one warm request per bucket, each served alone so
    # that its bucket is its dispatch's shape (requests due while a
    # program compiles would be admitted together, at the largest bucket)
    lo = traffic["prompt_len"].get("min", 1)
    hi = min(traffic["prompt_len"]["max"], scfg.prompt_cap)
    for b in sorted({_bucket(n, scfg.prefill_chunk)
                     for n in range(lo, hi + 1)}):
        server.run([[1] * min(b, scfg.prompt_cap)])
    _reset_stats(server)
    return Setup(cfg, traffic, ref, m, w, server, new_tokens)


def serve(setup: Setup, prompts, due, *, trace: bool, log_dir: Path):
    """The measured window. Returns (results, host seconds, trace events
    or None)."""
    def go():
        with device.log_compiles():
            return setup.server.run(prompts, arrivals=due)

    if not trace:
        t0 = time.perf_counter()
        results = go()
        return results, time.perf_counter() - t0, None
    results, window_s, path = tr.capture(go, log_dir)
    return results, window_s, tr.parse(path)


def expected_len(setup: Setup, prompt_len: int) -> int:
    """Tokens a request of this prompt length must be served: the budget,
    or what fits before max_seq (the server's own length rule)."""
    max_seq = setup.server.scfg.max_seq
    return min(setup.new_tokens, max_seq - prompt_len)


def compare(setup: Setup, prompts, results, seed: int, limits: dict,
            quant: str = "") -> dict:
    """Run the reference over a seeded sample of finished requests (the
    longest among them) and return the widest gap, in logit standard
    deviations, between the reference's best logit and the served
    token's. With ``quant`` the served tokens are replaced by the argmax
    of the reference computed at that lower precision (the control)."""
    done = [r for r in results if r.status == "ok" and r.tokens]
    if not done:
        return {"gap_max": float("inf"), "positions": 0, "requests": 0}
    gen = np.random.default_rng([int(seed), 2])
    order = sorted(done, key=lambda r: -(len(prompts[r.request_id])
                                         + len(r.tokens)))
    pick = [order[0]]
    rest = [order[i] for i in gen.permutation(len(order) - 1) + 1]
    while rest and sum(len(r.tokens) for r in pick) < limits["check_tokens"]:
        pick.append(rest.pop())
    gaps = []
    n_pos = setup.new_tokens
    for r in pick:
        p = prompts[r.request_id]
        toks = list(p) + list(r.tokens)
        pos = len(p) - 1 + np.arange(len(r.tokens))
        pos_pad = np.concatenate([pos, np.full(n_pos - len(pos), pos[-1])])
        lg = setup.ref.logits(setup.weights, setup.cfg, toks, pos_pad)
        lg = lg[: len(pos)]
        if quant:
            lq = setup.ref.logits(setup.weights, setup.cfg, toks, pos_pad,
                                  quant=quant)[: len(pos)]
            served = jnp.argmax(lq, axis=-1)
        else:
            served = jnp.asarray(np.asarray(r.tokens, np.int32))
        got = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
        gap = (lg.max(-1) - got) / lg.std(-1)
        gaps.append(np.asarray(gap))
    g = np.concatenate(gaps)
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "positions": int(g.size), "requests": len(pick)}


def p95_late(values: list[float], served: list[bool], late: float) -> float:
    """95th percentile, a request not served in full counting as ``late``
    (it waited at least the whole run)."""
    return float(np.percentile([v if ok else late
                                for v, ok in zip(values, served)], 95))


def run(cell, *, seed: int, seconds: float, trace: bool, devs: list,
        t_start: float, control: bool = False):
    compiles = device.CompileLog()
    setup = build(cell, seed)
    prompts, due, _ = tf.serving(cell.traffic, seed, seconds, setup.m["v"])
    c0 = compiles.count
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    log_dir = TRACE_DIR / f"{cell.name}-{seed}"
    results, window_s, events = serve(setup, prompts, due, trace=trace,
                                      log_dir=log_dir)
    compiles_in_window = compiles.count - c0
    stats = dict(setup.server.stats)
    mem_peak = device.memory_peak_bytes(devs)

    served = [r.status == "ok" and len(r.tokens) == expected_len(
        setup, len(prompts[r.request_id])) for r in results]
    failed = served.count(False)
    e2e = {
        "setup_s": setup_s,
        "ttft_p95_ms": 1e3 * p95_late([r.ttft_s for r in results], served,
                                      window_s),
        "tpot_p95_ms": p95_late([r.itl_ms for r in results], served,
                                1e3 * window_s),
    }

    ctx = None
    if trace:
        ctx = types.SimpleNamespace(
            events=events, window_s=window_s, results=results, stats=stats,
            scfg=setup.server.scfg, m=setup.m,
            peaks=peaks.peaks(devs[0].device_kind)
            if devs[0].platform == "tpu" else None)

    # the program's state goes before the reference runs
    setup.server = None
    gc.collect()
    cmp_ = compare(setup, prompts, results, seed, cell.limits,
                   quant="fp8" if control else "")
    checks = {"served_gap_max": report.check(cmp_["gap_max"],
                                             cell.limits["served_gap_max"])}
    correct = failed == 0 and all(c["ok"] for c in checks.values())

    dev = device.info(devs)
    dev["memory_peak_bytes"] = mem_peak
    extra = {"window_wall_s": window_s, "requests_compared": cmp_["requests"],
             "positions_compared": cmp_["positions"],
             "compiles_in_window": compiles_in_window,
             "tokens_served": int(sum(len(r.tokens) for r in results)),
             "prefill_dispatches": int(stats.get("prefill_dispatches", 0)),
             "decode_dispatches": int(stats.get("decode_dispatches", 0))}
    if not trace:
        metrics = {k: e2e[k] for k in cell.end_to_end}
        return report.emit(correct=correct, attempted=len(results),
                           failed=failed, metrics=metrics, units=cell.units,
                           device=dev, checks=checks, extra=extra)
    dev["busy_s"] = tr.busy_s(events)
    dev["window_s"] = window_s
    metrics = read_per_layer(cell, ctx)
    breakdown = {"device_ops": tr.top_ops(events),
                 "idle_gaps": tr.idle_gaps(events)}
    return report.emit(correct=correct, attempted=len(results), failed=failed,
                       metrics=metrics, units=cell.units, device=dev,
                       checks=checks, breakdown=breakdown, extra=extra)


def read_per_layer(cell, ctx) -> dict:
    out = {}
    for name in cell.per_layer:
        v = cells.metric_reader(name).read(ctx)
        if v is not None:
            out[name] = v
    return out
