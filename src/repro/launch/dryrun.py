import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the appropriate step function (train_step /
prefill_step / serve_step) with ShapeDtypeStruct stand-ins on the
production mesh(es), compiles it, and records memory_analysis(),
cost_analysis() and the parsed collective schedule — the inputs to
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b
  PYTHONPATH=src python -m repro.launch.dryrun --shape train_4k --multi-pod both
  PYTHONPATH=src python -m repro.launch.dryrun --json out.json
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get
from repro.launch import mesh as meshlib
from repro.launch import roofline, specs, steps
from repro.models.layers import COMPUTE_DTYPE
from repro.models.model import Model, active_param_count
from repro.optim import adamw


# the chip the production meshes are built from (v5e-256 pods): its
# roofline.PEAKS row prices every cell
MODELED_KIND = "TPU v5 lite"


def _key_sds():
    return jax.eval_shape(lambda: jax.random.key(0))


def _replicated(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def lower_cell(arch: str, shape: str, mesh, tcfg: steps.TrainConfig, cfg=None):
    """Returns (lowered, n_tokens, kind)."""
    cfg = cfg or get(arch)
    model = Model(cfg, mesh)
    kind = specs.SHAPES[shape]["kind"]
    data = specs.batch_specs(cfg, shape)
    seq = specs.SHAPES[shape]["seq"]
    batch = specs.SHAPES[shape]["batch"]

    params_s = jax.eval_shape(lambda k: model.init(k), _key_sds())
    p_shard = meshlib.param_shardings(params_s, mesh, cfg)

    if kind == "train":
        if cfg.encoder_only:
            # encoder training step (per-frame CE on the small exact head)
            step = steps.make_train_step(model, tcfg)
        else:
            step = steps.make_train_step(model, tcfg)
        opt_s = jax.eval_shape(adamw.init, params_s)
        o_shard = meshlib.param_shardings(opt_s["m"], mesh, cfg)
        opt_shardings = {
            "m": o_shard,
            "v": o_shard,
            "step": NamedSharding(mesh, P()),
        }
        b_shard = meshlib.data_shardings(data["batch"], mesh)
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, opt_shardings, b_shard, None),
            out_shardings=(p_shard, opt_shardings, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(
            params_s, opt_s, data["batch"], _key_sds()
        )
        n_tokens = batch * seq
        return lowered, n_tokens, kind

    if kind == "prefill":
        if cfg.encoder_only:
            step = steps.make_encode_step(model)
            b_shard = meshlib.data_shardings(data["batch"], mesh)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_s, data["batch"])
        else:
            step = steps.make_prefill_step(model, max_seq=seq)
            b_shard = meshlib.data_shardings(data["batch"], mesh)
            jitted = jax.jit(
                step, in_shardings=(p_shard, b_shard, None)
            )
            lowered = jitted.lower(params_s, data["batch"], _key_sds())
        return lowered, batch * seq, kind

    # decode: serve_step over a seq-long cache, one new token
    serve_params_s = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, COMPUTE_DTYPE), params_s
    )
    cache_s = jax.eval_shape(
        lambda: model.init_cache(batch, seq, COMPUTE_DTYPE)
    )
    c_shard = meshlib.cache_shardings(cache_s, mesh, cfg)
    d_shard = meshlib.data_shardings(
        {"ids": data["ids"], "pos": data["pos"]}, mesh
    )
    step = steps.make_serve_step(model)
    jitted = jax.jit(
        step,
        in_shardings=(
            p_shard, c_shard, d_shard["ids"], d_shard["pos"], None,
        ),
        out_shardings=(
            d_shard["ids"], d_shard["ids"], c_shard, d_shard["pos"],
        ),
        donate_argnums=(1,),
    )
    lowered = jitted.lower(
        serve_params_s, cache_s, data["ids"], data["pos"], _key_sds()
    )
    return lowered, batch, kind


def run_cell(arch: str, shape: str, multi_pod: bool, tcfg, verbose=True,
             cfg=None):
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_dev = mesh.size
    cfg = cfg or get(arch)
    reason = specs.skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "skip", "reason": reason}
    t0 = time.time()
    try:
        with jax.default_device(jax.devices()[0]):
            lowered, n_tokens, kind = lower_cell(arch, shape, mesh, tcfg,
                                                 cfg=cfg)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        n_active = active_param_count(cfg)
        model_flops = (6 if kind == "train" else 2) * n_active * n_tokens
        rep = roofline.analyze(
            arch, shape, mesh_name, n_dev, compiled, model_flops,
            MODELED_KIND,
        )
        ms = rep.mem_stats
        out = {
            "arch": arch, "shape": shape, "mesh": mesh_name, "kind": kind,
            "device_kind": rep.device_kind, "status": "ok",
            "flops_per_device": rep.flops_per_device,
            "bytes_per_device": rep.bytes_per_device,
            "coll_bytes_per_device": rep.coll_bytes_per_device,
            "coll_detail": rep.coll_detail,
            "t_compute_ms": rep.t_compute * 1e3,
            "t_memory_ms": rep.t_memory * 1e3,
            "t_collective_ms": rep.t_collective * 1e3,
            "bottleneck": rep.bottleneck,
            "model_flops": model_flops,
            "useful_frac": rep.useful_frac,
            "mem": ms,
            "hbm_top": rep.hbm_top,
            "coll_top": rep.coll_top,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
        }
        if verbose:
            hbm = ms["args_gb"] + ms["temp_gb"]
            print(
                f"[ok] {arch:>18s} {shape:>11s} {mesh_name:>8s} "
                f"comp={out['t_compute_ms']:8.2f}ms "
                f"mem={out['t_memory_ms']:8.2f}ms "
                f"coll={out['t_collective_ms']:8.2f}ms "
                f"bn={rep.bottleneck:<10s} hbm/dev={hbm:6.2f}GB "
                f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
                flush=True,
            )
        return out
    except Exception as e:  # noqa: BLE001 — report, don't abort the sweep
        if verbose:
            print(f"[FAIL] {arch} {shape} {mesh_name}: {e}", flush=True)
            traceback.print_exc()
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "fail", "error": str(e)[:2000]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    choices=["all", *specs.SHAPES])
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--accum", type=int, default=0,
                    help="grad-accum microbatches (0 = per-arch default)")
    ap.add_argument("--json", default="", help="write results to this file")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(specs.SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod
    ]
    # per-arch default accumulation keeps the biggest models' activation +
    # MoE dispatch buffers inside HBM (see EXPERIMENTS.md §Dry-run)
    default_accum = {"mixtral-8x22b": 8, "qwen3-moe-30b-a3b": 4,
                     "granite-8b": 2, "recurrentgemma-9b": 2}

    print(f"dry-run: roofline priced at {MODELED_KIND!r} peaks "
          f"{roofline.peaks(MODELED_KIND)}", flush=True)
    results = []
    fails = 0
    for arch in archs:
        accum = args.accum or default_accum.get(arch, 1)
        tcfg = steps.TrainConfig(accum=accum)
        for shape in shapes:
            for mp in pods:
                r = run_cell(arch, shape, mp, tcfg)
                results.append(r)
                fails += r["status"] == "fail"
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    ok = sum(r["status"] == "ok" for r in results)
    skip = sum(r["status"] == "skip" for r in results)
    print(f"\ndry-run: {ok} ok / {skip} skip / {fails} FAIL "
          f"(of {len(results)} cells)")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
