"""Serving launcher: pipelined batched-decode engine over the amortized
lazy-Gumbel sampler.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --requests 16 --new-tokens 32 --decode-window 8

:func:`serve` is the launcher without its command line: it takes the
parsed arguments (:func:`parse_args`) and returns the stats dict that
``main`` prints, plus each request's decoded tokens.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import ARCHS, get, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serve.server import ServeConfig, Server


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--head", default=None,
                    choices=[None, "exact", "topk_only", "amortized"])
    ap.add_argument("--mips", default=None,
                    choices=[None, "exact", "ivf", "ivfpq", "lsh"],
                    help="head top-k backend (ivf: stateful IVF index; "
                         "ivfpq: quantized uint8-code index with exact "
                         "re-rank; lsh: SRP theory-reference index)")
    ap.add_argument("--vocab", type=int, default=0,
                    help="override vocab size (e.g. to exercise the "
                         "amortized head on a smoke config)")
    ap.add_argument("--engine", default="pipelined",
                    choices=["pipelined", "reference"],
                    help="pipelined: batched prefill + fused decode window; "
                         "reference: one dispatch per token (comparator)")
    ap.add_argument("--decode-window", type=int, default=8,
                    help="tokens decoded per dispatch (pipelined engine)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt-length padding bucket for batched prefill")
    ap.add_argument("--overlength", default="truncate",
                    choices=["truncate", "reject"],
                    help="admission policy for prompts longer than "
                         "max_seq - new_tokens")
    ap.add_argument("--block-len", type=int, default=0,
                    help="paged KV cache: block size in positions (0: dense "
                         "slot-reserved rings). Must divide the attn ring "
                         "length min(window or max-seq, max-seq)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="paged KV cache: shared pool size in blocks "
                         "(0: auto — slots * pages-per-slot, the dense-"
                         "equivalent coverage)")
    ap.add_argument("--sched", default="fifo", choices=["fifo", "slo"],
                    help="admission scheduler: fifo (arrival order, fixed "
                         "window) or slo (priority + TTFT-deadline order, "
                         "adaptive decode window)")
    ap.add_argument("--ttft-slo", type=float, default=0.5,
                    help="slo scheduler: per-request TTFT target (seconds)")
    ap.add_argument("--strict", action="store_true",
                    help="re-sample certificate-failed tokens exactly "
                         "(in-dispatch fallback)")
    ap.add_argument("--head-use-kernel", action="store_true",
                    help="Pallas probe/estimator kernels in the head")
    ap.add_argument("--fused-decode", action="store_true",
                    help="single-dispatch fused decode step (Pallas "
                         "screen/re-rank/tail pipeline; samples are "
                         "bit-identical to the unfused kernel path)")
    ap.add_argument("--adaptive-probe", action="store_true",
                    help="certificate-gated staged probe widening: probe "
                         "n-probe-init clusters per token, widen only for "
                         "tokens whose gap certificate fails (ivf/ivfpq)")
    ap.add_argument("--n-probe-init", type=int, default=0,
                    help="adaptive probe start width (0: head n_probe)")
    ap.add_argument("--n-probe-max", type=int, default=0,
                    help="adaptive probe width ceiling (0: head n_probe)")
    ap.add_argument("--probe-router", default="",
                    help="adaptive stage router: 'fit' trains at startup, "
                         "else a router.npz path (repro.models.router)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--count-kernels", action="store_true",
                    help="report the Pallas kernel calls (tpu_custom_call) "
                         "in the compiled decode program")
    return ap.parse_args(argv)


def serve(args: argparse.Namespace, params=None) -> dict:
    """Build the model and server ``args`` describe, serve
    ``args.requests`` seeded random prompts, and return the stats dict.

    ``params`` (optional) reuses weights already on the device; they must
    be the model's params for the configuration ``args`` selects. The
    dict's ``tokens`` holds each request's decoded ids, in request order.
    """
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    if args.head:
        cfg = cfg.scaled(head_mode=args.head)
    if args.mips:
        cfg = cfg.scaled(head_mips=args.mips)
    if args.vocab:
        cfg = cfg.scaled(vocab=args.vocab)
    if args.head_use_kernel:
        cfg = cfg.scaled(head_use_kernel=True)
    if args.fused_decode:
        cfg = cfg.scaled(head_fused_decode=True)
    if args.adaptive_probe:
        cfg = cfg.scaled(
            head_adaptive_probe=True,
            head_n_probe_init=args.n_probe_init,
            head_n_probe_max=args.n_probe_max,
        )
    model = Model(cfg)
    if params is None:
        params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = [
        list(rng.integers(0, cfg.vocab, size=rng.integers(4, 12)))
        for _ in range(args.requests)
    ]
    server = Server(cfg, params, ServeConfig(
        batch_slots=args.slots, max_seq=args.max_seq,
        max_new_tokens=args.new_tokens, engine=args.engine,
        decode_window=args.decode_window, prefill_chunk=args.prefill_chunk,
        overlength=args.overlength, strict=args.strict,
        probe_router=args.probe_router,
        block_len=args.block_len, n_blocks=args.n_blocks,
        sched=args.sched, ttft_slo_s=args.ttft_slo,
    ))
    results = server.run(prompts)
    toks = sum(len(r.tokens) for r in results)
    st = server.stats
    stats = {
        "requests": len(results),
        "decoded_tokens": toks,
        "tokens_per_s": round(toks / st["wall_s"], 1),
        "prefill_tokens": st["prefill_tokens"],
        "prefill_dispatches": st["prefill_dispatches"],
        "decode_dispatches": st["decode_dispatches"],
        "ok_rate": round(st["ok"] / max(st["tokens"], 1), 4),
        "fallbacks": st["fallbacks"],
        "rejected": st["rejected"],
        "steps": st["steps"],
        "ttft_p50_ms": round(1e3 * float(np.median(
            [r.ttft_s for r in results if r.status == "ok"] or [0.0])), 2),
        "itl_p50_ms": round(float(np.median(
            [r.itl_ms for r in results if r.status == "ok"] or [0.0])), 3),
        "queue_p50_ms": round(1e3 * float(np.median(
            [r.queue_time_s for r in results if r.status == "ok"]
            or [0.0])), 2),
        "queue_depth_peak": st["queue_depth_peak"],
        "slot_occupancy_peak": st["slot_occupancy_peak"],
        "block_util_peak": round(st["block_util_peak"], 4),
        "block_stalls": st["block_stalls"],
        "cache_mb": round(st["cache_bytes"] / 1e6, 3),
        "index_mb": (
            round(server.index.memory_bytes() / 1e6, 2)
            if server.index is not None else 0.0
        ),
        "probe_width_hist": {
            str(k): v
            for k, v in sorted(st["probe_width_hist"].items())
        },
        "statuses": [r.status for r in results],
        "tokens": [list(map(int, r.tokens)) for r in results],
    }
    if args.count_kernels:
        stats["decode_kernel_calls"] = server.decode_program_text().count(
            "tpu_custom_call"
        )
    return stats


def main() -> None:
    enable_compile_cache()
    stats = serve(parse_args())
    stats.pop("tokens")
    stats.pop("statuses")
    print(json.dumps(stats, indent=1))


if __name__ == "__main__":
    main()
