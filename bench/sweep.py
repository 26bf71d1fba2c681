#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: one process builds the
cell once and serves its mix at each given rate in turn.

  python3 bench/sweep.py --workload mistral-7b.decode-heavy --seed 3 \
      --seconds 20 --rates 1,2,3,4

Prints one JSON line per rate: the tails of TTFT and TPOT, the queue
wait, the tokens completed per second over the run, and the lateness of
the last request due (a backlog that grows through the window shows as
a last TTFT far above the median). The cell's rate is then fixed in its
traffic file at about four fifths of the highest rate that keeps up.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import numpy as np

    from harness import cells, device, traffic as tf

    cell = cells.load_cell(args.workload)
    try:
        device.require_chips(cell.chips)
    except device.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    device.enable_compile_cache(cells.ROOT)
    runner = cells.runner(cell)
    setup = runner.build(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        prompts, due, _ = tf.serving(cell.traffic, args.seed, args.seconds,
                                     setup.m["v"], rate=rate)
        t0 = time.perf_counter()
        res = setup.server.run(prompts, arrivals=due)
        wall = time.perf_counter() - t0
        ok = [r for r in res if r.status == "ok"]
        ttft = np.array([r.ttft_s for r in ok]) * 1e3
        tpot = np.array([r.itl_ms for r in ok])
        last = ok[int(np.argmax([due[r.request_id] for r in ok]))]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(res), "ok": len(ok),
            "ttft_p50_ms": float(np.median(ttft)),
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "tpot_p50_ms": float(np.median(tpot)),
            "tpot_p95_ms": float(np.percentile(tpot, 95)),
            "queue_p95_ms": 1e3 * float(np.percentile(
                [r.queue_time_s for r in ok], 95)),
            "last_due_ttft_ms": 1e3 * last.ttft_s,
            "tokens_per_s": sum(len(r.tokens) for r in ok) / wall,
            "wall_s": wall,
            "stats": {k: v for k, v in setup.server.stats.items()
                      if isinstance(v, (int, float))},
        }), flush=True)
        runner._reset_stats(setup.server)
    return 0


if __name__ == "__main__":
    sys.exit(main())
