#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's compared
numbers over many seeds (the lower reading), the control's and those of
planted faults (the upper reading), in one process so that set-up
compiles once.

  python3 bench/calibrate.py --workload mistral-7b.decode-heavy \\
      --seeds 101,102,103 --seconds 10 [--head-mips ivf]
  python3 bench/calibrate.py --workload loglinear-imagenet.online \\
      --seeds 201,202,203 --seconds 14 [--variants 3]

Serving cells: per seed, a window of the cell's own traffic, then the
widest gap of the served tokens below the reference's best and, at the
same positions, of the tokens that the reference computed with float8
weights puts first (the control). ``--head-mips`` serves through another
head path of the program, as a second witness. Log-linear cells: per
seed, a window with float32 scores (the configuration's precision); on
the first ``--variants`` seeds also, on the same data, one with three
bfloat16 passes (the control) and one with each fault that the cell's
numbers have to catch (``bench/harness/faults.py``).

One JSON line per seed and side on stdout.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def serving(cell, runner, seed: int, seconds: float) -> dict:
    from harness import traffic as tf

    setup = runner.build(cell, seed)
    prompts, due, _ = tf.serving(cell.traffic, seed, seconds, setup.m["v"])
    results = setup.server.run(prompts, arrivals=due)
    setup.server = None
    gc.collect()
    prog = runner.compare(setup, prompts, results, seed, cell.limits)
    ctl = runner.compare(setup, prompts, results, seed, cell.limits,
                         quant="fp8")
    return {"seed": seed, "requests": len(results),
            "program_gap_max": prog["gap_max"],
            "program_gap_mean": prog["gap_mean"],
            "control_gap_max": ctl["gap_max"],
            "control_gap_mean": ctl["gap_mean"],
            "positions": prog["positions"]}


def loglinear(cell, runner, seed: int, seconds: float,
              variants: tuple[str, ...]) -> list[dict]:
    """The program's reading for ``seed`` and, on the same table, index
    and θ, each variant's: ``"control"`` (scores in three bfloat16
    passes) or a fault of ``bench/harness/faults.py``."""
    import numpy as np

    from harness import faults

    s = runner.build(cell, seed, "f32")
    gen = np.random.default_rng([int(seed), 3])
    keep = set(gen.choice(64, size=cell.limits["check_batches"],
                          replace=False).tolist())
    rows = []
    for variant in ("",) + variants:
        plant = (faults.FAULTS[variant]() if variant in faults.FAULTS
                 else contextlib.nullcontext())
        with plant:
            if variant:
                s.probe, s.estimate = runner.make_programs(
                    s.cfg["n"], s.k, s.l, s.batch,
                    "high" if variant == "control" else "f32")
                runner.step(s, 0)  # compiles outside the window
            batches, answers, kept = runner.window(s, seconds, keep)
        cmp_ = runner.compare(s, answers, kept)
        ks = runner.sampler_ks(s, answers, seed,
                               cell.limits["check_samples"])
        rows.append({
            "seed": seed, "variant": variant or "program",
            "batches": batches, "logz_rel_err": cmp_["rel"],
            "logz_arith_err": cmp_["arith"], "tail_score_err": cmp_["score"],
            "sample_ks": ks, "thetas": cmp_["thetas"],
            "valid": cmp_["valid"]})
        del answers, kept
        gc.collect()
    return rows


LOGLINEAR_VARIANTS = ("control", "argmax_sampler", "dropped_tail")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--head-mips", default="")
    ap.add_argument("--variants", type=int, default=3)
    args = ap.parse_args(argv)
    from harness import cells, device

    cell = cells.load_cell(args.workload)
    try:
        device.require_chips(cell.chips)
    except device.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    device.enable_compile_cache(cells.ROOT)
    if args.head_mips:
        cfg = dict(cell.config)
        cfg["serve"] = dict(cfg["serve"], head_mips=args.head_mips,
                            head_use_kernel=True)
        cell = dataclasses.replace(cell, config=cfg)
    runner = cells.runner(cell)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        if cell.config["runner"] == "lm_serve":
            rows = [serving(cell, runner, seed, args.seconds)]
        else:
            variants = LOGLINEAR_VARIANTS if i < args.variants else ()
            rows = loglinear(cell, runner, seed, args.seconds, variants)
        for row in rows:
            print(json.dumps(row), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
