"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --steps 50 --workdir /tmp/run1

``--smoke`` uses the arch's reduced config (CPU-feasible); without it the
full config is used (TPU pod scale). ``--head`` selects the softmax mode
(the paper's Table-2 comparison). Resume is automatic from the latest
complete checkpoint in --workdir; drop a PREEMPT file there (or SIGTERM)
for a clean preempt-checkpoint-exit.
"""
from __future__ import annotations

import argparse
import json

from repro.configs import ARCHS, get, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import TrainConfig
from repro.optim.adamw import OptConfig
from repro.train.trainer import RunConfig, Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum-steps", "--accum", dest="accum_steps",
                    type=int, default=1,
                    help="gradient-accumulation microbatches per optimizer "
                         "step (batch is split; grads summed in fp32)")
    ap.add_argument("--fuse-steps", type=int, default=1,
                    help="T: optimizer steps fused into one dispatch "
                         "(lax.scan); metrics sync only at log/ckpt/refresh "
                         "boundaries")
    ap.add_argument("--precision", default="bf16", choices=["f32", "bf16"],
                    help="model compute policy (repro/precision.py): bf16 "
                         "trunk with fp32 masters/estimators (default), or "
                         "full-fp32 reference")
    ap.add_argument("--head", default=None,
                    choices=[None, "exact", "topk_only", "amortized"])
    ap.add_argument("--mips", default=None,
                    choices=[None, "exact", "ivf", "ivfpq", "lsh"],
                    help="head top-k backend (ivf: stateful, refreshed "
                         "index; ivfpq: quantized uint8-code index with "
                         "exact re-rank, ~8-16x less index HBM; lsh: SRP "
                         "theory-reference index)")
    ap.add_argument("--vocab", type=int, default=0,
                    help="override vocab size (e.g. to exercise the "
                         "amortized head on a smoke config)")
    ap.add_argument("--index-refresh-every", type=int, default=0,
                    help="R > 0: refresh the head MIPS index every R steps")
    ap.add_argument("--index-drift-threshold", type=float, default=0.0,
                    help="> 0: refresh when relative embedding drift exceeds")
    ap.add_argument("--async-refresh", action="store_true",
                    help="double-buffered index refresh: rebuild on a side "
                         "thread while stepping against the stale buffer; "
                         "atomic swap at the next fused-chunk boundary")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis size (devices used: "
                         "dp*tp; the sharded index spans the model axis "
                         "only, so dp scales batch throughput without "
                         "touching index placement)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel (model) mesh axis size")
    ap.add_argument("--sharded-ckpt", action="store_true",
                    help="per-host sharded checkpoint save/restore "
                         "(automatic on multi-process runs)")
    ap.add_argument("--adaptive-probe", action="store_true",
                    help="certificate-gated staged probe widening in the "
                         "head's MIPS queries (ivf/ivfpq)")
    ap.add_argument("--n-probe-init", type=int, default=0,
                    help="adaptive probe start width (0: head n_probe)")
    ap.add_argument("--n-probe-max", type=int, default=0,
                    help="adaptive probe width ceiling (0: head n_probe)")
    ap.add_argument("--probe-router", action="store_true",
                    help="fit the adaptive stage router on probe traces at "
                         "index-refresh boundaries; saved to "
                         "workdir/router.npz")
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    if args.head:
        cfg = cfg.scaled(head_mode=args.head)
    if args.mips:
        cfg = cfg.scaled(head_mips=args.mips)
    if args.vocab:
        cfg = cfg.scaled(vocab=args.vocab)
    if args.adaptive_probe:
        cfg = cfg.scaled(
            head_adaptive_probe=True,
            head_n_probe_init=args.n_probe_init,
            head_n_probe_max=args.n_probe_max,
        )
    mesh = None
    if args.dp * args.tp > 1:
        from repro.launch import mesh as meshlib

        mesh = meshlib.make_train_mesh(args.dp, args.tp)
    run = RunConfig(
        num_steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        ckpt_every=args.ckpt_every,
        fuse_steps=args.fuse_steps,
        index_refresh_every=args.index_refresh_every,
        index_drift_threshold=args.index_drift_threshold,
        async_refresh=args.async_refresh,
        sharded_ckpt=True if args.sharded_ckpt else None,
        fit_probe_router=args.probe_router,
        train=TrainConfig(
            opt=OptConfig(lr=args.lr, total_steps=args.steps),
            accum=args.accum_steps,
            precision=args.precision,
        ),
    )
    trainer = Trainer(cfg, run, args.workdir, mesh=mesh)
    result = trainer.train()
    result["index_refreshes"] = trainer.index_refreshes
    result["index_swaps"] = trainer.index_swaps
    print(json.dumps(result, indent=1))


if __name__ == "__main__":
    main()
