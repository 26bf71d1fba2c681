"""Tiny versions of the benchmark's cells, for CPU tests of the harness.

The sizes are cut far below the published widths so that a CPU (with the
Pallas kernels interpreted) runs a whole cell in seconds; the harness,
the runners and the references run unchanged.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cells  # noqa: E402

TINY_LM = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 4096,
}
TINY_SERVER = {"batch_slots": 4, "max_seq": 64, "decode_window": 1,
               "prefill_chunk": 16}


def tiny_cell(workload: str) -> cells.Cell:
    cell = cells.load_cell(workload)
    cfg = dict(cell.config)
    traffic = dict(cell.traffic)
    limits = dict(cell.limits)
    if cfg["runner"] == "lm_serve":
        cfg.update(TINY_LM)
        cfg["synthetic_weights"] = dict(cfg["synthetic_weights"],
                                        embed_clusters=64)
        traffic.update(
            arrivals=dict(traffic["arrivals"], rate_per_s=4.0),
            prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.5,
                        "min": 4, "max": 24},
            output_len={"dist": "fixed", "value": 24},
            server=TINY_SERVER,
        )
        # a limit of the tiny cell's own, between its sound runs' widest
        # gap (0.0) and its float8 control's (0.137): at 64 wide and 2
        # layers the control moves the logits less than at full width
        limits.update(check_tokens=48, served_gap_max=0.06)
    else:
        cfg.update(n=4096, d=128, n_probe=8, kmeans_iters=2,
                   features={"centres": 32, "noise": 0.4})
        traffic.update(batch=4, theta_pool=64)
        # limits of the tiny cell's own: on the CPU Algorithm 3's float32
        # arithmetic reads about 1e-6 (the chip's exp reads 9e-5), and 512
        # draws spread wider from uniform than the cell's 4096 do (sound
        # runs read about 0.07; an argmax sampler 0.9; a dropped tail
        # 1.1e-3 in the arithmetic)
        limits.update(check_batches=2, check_samples=512,
                      logz_arith_err=1e-4, sample_ks=0.3)
    return dataclasses.replace(cell, config=cfg, traffic=traffic,
                               limits=limits)
