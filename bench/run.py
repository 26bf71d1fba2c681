#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

  python3 bench/run.py --workload mistral-7b.decode-heavy --seed 7 \
      --seconds 30 --trace 0

Set-up (weights and data made on the device from ``--seed``, the index
built, every shape the cell's traffic uses compiled or loaded from the
persistent cache at ``<checkout>/.jax_cache``) is timed as ``setup_s``;
then the cell's traffic runs for ``--seconds``; then what the timed path
produced is compared with the plain reference. ``--trace 1`` runs the
window under the profiler and reports the cell's per-layer metrics in
place of its end-to-end ones.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for. Everything a cell needs is found by name from
``BENCHMARK.json`` (see ``bench/harness/cells.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import cells, device

    cell = cells.load_cell(args.workload)
    try:
        devs = device.require_chips(cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    device.enable_compile_cache(ROOT)
    cells.runner(cell).run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devs=devs, t_start=T_START,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
