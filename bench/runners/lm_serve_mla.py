"""Serving runner for decoders with latent attention and expert layers
(DeepSeek-V3's block: ``bench/references/mla_moe_decoder.py``): an
open-loop traffic mix through the program's ``repro.serve.server.Server``,
and the served tokens compared with the plain reference.

It differs from ``lm_serve.py`` only in what a configuration of this
block needs: the program's ``ArchConfig`` (MLA widths, the expert layer's
held share, router, shared experts and leading dense layers) and its
parameter layout (one stack for the leading dense layers, one for the
expert layers). The measured window, the comparison with the reference,
the percentiles and the per-layer readers are ``lm_serve.py``'s own;
``correct`` holds the mean gap of the served tokens where ``lm_serve.py``
holds the widest.

Set-up warms every prefill bucket the mix's prompt lengths fall in and
the decode window in one ``Server.run``: for each bucket, in ascending
order, as many prompts as one prefill dispatch of that bucket holds. The
engine then fills one dispatch per bucket (a prompt joins the open chunk
only while it has fewer rows than its bucket's dispatch holds), so every
program is compiled, or loaded from the persistent cache, before the
window, at the cost of one request's decode rather than one per bucket.
"""
from __future__ import annotations

import gc
import time
import types

import jax

from harness import cells, device, peaks, report
from harness import trace as tr
from harness import traffic as tf

lm = cells.load_module(cells.BENCH_DIR / "runners" / "lm_serve.py",
                       "bench_runner_lm_serve")
serve, compare, p95_late = lm.serve, lm.compare, lm.p95_late
expected_len, read_per_layer = lm.expected_len, lm.read_per_layer
_reset_stats = lm._reset_stats
TRACE_DIR = lm.TRACE_DIR


def arch_config(cfg: dict, ref):
    from repro.models.config import ArchConfig

    m = ref.dims(cfg)
    s = cfg["serve"]
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the chosen experts' gates")
    return ArchConfig(
        name=cfg["name"], family="moe", n_layers=m["layers"],
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["kv"],
        head_dim=m["nope"] + m["rope"], d_ff=m["f"], vocab=m["v"],
        n_experts=m["experts"], experts_per_token=m["topk"],
        moe_d_ff=m["ef"], n_local_experts=m["held"],
        n_shared_experts=int(cfg["n_shared_experts"]),
        first_dense_layers=m["dense_layers"],
        router_scoring=cfg["scoring_func"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        attn_kind="mla", kv_lora_rank=m["r"], qk_nope_dim=m["nope"],
        qk_rope_dim=m["rope"], v_head_dim=m["vd"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        head_mode=s["head_mode"], head_mips=s["head_mips"],
        head_use_kernel=bool(s["head_use_kernel"]),
        head_fused_decode=bool(s["head_fused_decode"]),
        head_n_probe=int(s["head_n_probe"]),
    )


def _layer(p: dict) -> dict:
    """One stack of the reference's layers in the program's layout (the
    program's norms hold ``weight - 1``)."""
    out = {
        "norm1": p["attn_norm"] - 1.0,
        "mix": {"wq": p["q"], "wkv_a": p["kv_a"],
                "kv_norm": p["kv_norm"] - 1.0, "wkv_b": p["kv_b"],
                "wo": p["o"]},
        "norm2": p["mlp_norm"] - 1.0,
        "mlp": {"w1": p["gate"], "w2": p["down"], "w3": p["up"]},
    }
    if "router" in p:
        out["mlp"].update(
            router=p["router"], bias=p["bias"],
            shared={"w1": p["shared_gate"], "w2": p["shared_down"],
                    "w3": p["shared_up"]})
    return {"0": out}


def program_params(w: dict, model) -> dict:
    """The reference's weights in the program's parameter layout (the same
    device buffers): the leading dense layers' group, then the expert
    layers'."""
    params = {"embed": w["embed"], "out_embed": w["out_embed"],
              "final_norm": w["final_norm"] - 1.0,
              "blocks": [_layer(w["dense"]), _layer(w["moe"])]}
    want = jax.eval_shape(model.init, jax.random.key(0))
    got_t = jax.tree.structure(params)
    if got_t != jax.tree.structure(want):
        raise RuntimeError(f"parameter layout changed: {got_t}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if a.shape != b.shape:
            raise RuntimeError(f"parameter shape {a.shape} != {b.shape}")
    return params


def build(cell, seed: int):
    """Weights, server and warm-up for ``seed``; every shape the mix uses
    is compiled (or loaded from the persistent cache) on return."""
    from repro.models.model import Model
    from repro.serve.server import ServeConfig, Server, _bucket, _prefill_rows

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
    lo = traffic["prompt_len"].get("min", 1)
    hi = min(traffic["prompt_len"]["max"], scfg.prompt_cap)
    buckets = sorted({_bucket(n, scfg.prefill_chunk)
                      for n in range(lo, hi + 1)})
    server.run([[1] * min(b, scfg.prompt_cap) for b in buckets
                for _ in range(_prefill_rows(b, scfg.batch_slots))])
    _reset_stats(server)
    return lm.Setup(cfg, traffic, ref, m, w, server, new_tokens)


def run(cell, *, seed: int, seconds: float, trace: bool, devs: list,
        t_start: float, control: bool = False):
    compiles = device.CompileLog()
    setup = build(cell, seed)
    prompts, due, _ = tf.serving(cell.traffic, seed, seconds, setup.m["v"])
    c0 = compiles.count
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    results, window_s, events = serve(
        setup, prompts, due, trace=trace,
        log_dir=TRACE_DIR / f"{cell.name}-{seed}")
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
    # the mean gap over the compared positions: now and then a near-tie
    # among the router's scores sends a token to another expert on a
    # bfloat16 trunk, which moves that one position about as far as
    # float8 weights move many (the widest gap cannot tell them apart);
    # the mean stays an order of magnitude below the control's (PERF.md)
    checks = {"served_gap_mean": report.check(
        cmp_.get("gap_mean", float("inf")), cell.limits["served_gap_mean"])}
    correct = failed == 0 and all(c["ok"] for c in checks.values())

    dev = device.info(devs)
    dev["memory_peak_bytes"] = mem_peak
    extra = {"window_wall_s": window_s, "requests_compared": cmp_["requests"],
             "positions_compared": cmp_["positions"],
             "compiles_in_window": compiles_in_window,
             "tokens_served": int(sum(len(r.tokens) for r in results)),
             "prefill_dispatches": int(stats.get("prefill_dispatches", 0)),
             "decode_dispatches": int(stats.get("decode_dispatches", 0)),
             "cache_bytes": int(stats.get("cache_bytes", 0))}
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
