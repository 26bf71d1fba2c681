"""Serving-engine throughput: old single-step loop vs the pipelined engine.

The paper's amortization argument is a *serving* argument — per-query
sublinear head cost only shows up end-to-end if the engine isn't dominated
by dispatch/host-sync overhead. This benchmark drives the same
mixed-length request batch through

* ``reference`` — one dispatch per token, prompts teacher-forced through
  the decode path (the pre-engine ``Server.run`` cost profile), and
* ``pipelined`` — chunked batched prefill + a fused ``decode_window=T``
  scan + one-deep async dispatch pipeline,

across batch-slot counts × prompt-length mixes × T, reporting tokens/s,
the prefill/decode split, and the speedup. Sample keys derive from
(request, position), so every fused row is asserted bit-identical to the
T=1 single-step engine — the speedup is pure dispatch/host-sync
amortization, not a different sampler. Match against the teacher-forced
reference loop is also reported; it is numerics-limited (prefill vs
decode trunks round bf16 differently on long prompts; see DESIGN.md §8).

  PYTHONPATH=src python -m benchmarks.serve_engine [--smoke] [--json PATH]
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

import repro.models.transformer as T

from repro.configs import get_smoke
from repro.models.model import Model
from repro.serve.server import ServeConfig, Server

ARCH = "tinyllama-1.1b"
VOCAB = 4096


def _prompts(vocab: int, n: int, lo: int, hi: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, vocab, size=int(rng.integers(lo, hi))))
            for _ in range(n)]


def _serve(cfg, params, prompts, *, engine, window, slots, new_tokens,
           max_seq):
    srv = Server(cfg, params, ServeConfig(
        batch_slots=slots, max_seq=max_seq, max_new_tokens=new_tokens,
        seed=0, engine=engine, decode_window=window,
        prefill_chunk=64,  # one length bucket -> no mid-measurement compile
    ))
    srv.run(prompts)  # warmup: compile prefill bucket + decode window
    for k in srv.stats:
        srv.stats[k] = type(srv.stats[k])()
    results = srv.run(prompts)
    st = srv.stats
    toks = sum(len(r.tokens) for r in results)
    return {
        "engine": engine,
        "decode_window": window,
        "slots": slots,
        "tokens": toks,
        "wall_s": round(st["wall_s"], 4),
        "tokens_per_s": round(toks / st["wall_s"], 1),
        "prefill_tokens": st["prefill_tokens"],
        "dispatches": st["steps"],
        "ttft_p50_ms": round(1e3 * float(np.median(
            [r.ttft_s for r in results])), 2),
        "ok_rate": round(st["ok"] / max(st["tokens"], 1), 4),
        "_tokens_by_rid": {r.request_id: r.tokens for r in results},
    }


def run(report, smoke: bool = False) -> dict:
    T.REMAT = False
    cfg = get_smoke(ARCH).scaled(vocab=VOCAB, head_mode="amortized")
    model = Model(cfg)
    params = model.init(jax.random.key(0))

    if smoke:
        grid_slots = (2,)
        windows = (8,)
        n_req, lo, hi = 8, 8, 56
        new_tokens, max_seq = 8, 128
    else:
        grid_slots = (2, 4)
        windows = (4, 8, 16)
        n_req, lo, hi = 16, 8, 60
        new_tokens, max_seq = 32, 256

    out = {"arch": cfg.name, "vocab": cfg.vocab, "rows": [], "speedup": {}}
    for slots in grid_slots:
        prompts = _prompts(cfg.vocab, n_req, lo, hi)
        base = _serve(cfg, params, prompts, engine="reference", window=1,
                      slots=slots, new_tokens=new_tokens, max_seq=max_seq)
        report(f"serve/reference/slots{slots}",
               1e6 * base["wall_s"] / base["tokens"],
               f"tok/s={base['tokens_per_s']}")
        # single-step engine: the determinism baseline — fused windows MUST
        # reproduce it bit for bit (same dispatch math, same keys)
        single = _serve(cfg, params, prompts, engine="pipelined", window=1,
                        slots=slots, new_tokens=new_tokens, max_seq=max_seq)
        single["speedup_vs_reference"] = round(
            single["tokens_per_s"] / base["tokens_per_s"], 2)
        report(f"serve/pipelined/slots{slots}/T1",
               1e6 * single["wall_s"] / single["tokens"],
               f"tok/s={single['tokens_per_s']}")
        rows = [base, single]
        for window in windows:
            eng = _serve(cfg, params, prompts, engine="pipelined",
                         window=window, slots=slots, new_tokens=new_tokens,
                         max_seq=max_seq)
            speedup = eng["tokens_per_s"] / base["tokens_per_s"]
            eng["speedup_vs_reference"] = round(speedup, 2)
            # fused window vs single-step dispatch: identical samples, so
            # the speedup is pure dispatch/host-sync amortization
            eng["tokens_identical_T1"] = (
                eng["_tokens_by_rid"] == single["_tokens_by_rid"]
            )
            assert eng["tokens_identical_T1"], (
                f"fused decode T={window} changed samples vs T=1"
            )
            # teacher-forced loop match is numerics-limited: prefill and
            # decode trunks round bf16 differently, so long prompts can
            # flip the occasional Gumbel argmax (informational only)
            eng["tokens_match_reference"] = (
                eng["_tokens_by_rid"] == base["_tokens_by_rid"]
            )
            report(f"serve/pipelined/slots{slots}/T{window}",
                   1e6 * eng["wall_s"] / eng["tokens"],
                   f"tok/s={eng['tokens_per_s']} speedup={speedup:.2f}x "
                   f"identical_T1={eng['tokens_identical_T1']} "
                   f"ref_match={eng['tokens_match_reference']}")
            rows.append(eng)
            out["speedup"][f"slots{slots}_T{window}"] = round(speedup, 2)
        for r in rows:
            r.pop("_tokens_by_rid", None)
        out["rows"].extend(rows)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid (CI: 8 requests, one window)")
    ap.add_argument("--json", default=None,
                    help="write the full result table to this path")
    args = ap.parse_args()

    def report(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    print("name,us_per_token,derived")
    out = run(report, smoke=args.smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
