"""Distributed-head probe cost: dense-local scan vs IVF-local sharded probe.

Times one jitted `dist_head_loss` (fwd+bwd) and one `dist_head_sample` step
over a (2, 4) host-device mesh for the two per-shard probe strategies, and
reports per-step collective bytes from the compiled HLO
(launch/hlo_analysis) — the dense head pays O(v_loc · d) FLOPs per shard
per token for the probe, the IVF-backed sharded index O(√v_loc · d), while
both keep the O(1)-per-token combine collectives.

The measurement needs multiple XLA devices, so ``run`` re-executes this
module in a subprocess with fake host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m benchmarks.dist_head
"""
from __future__ import annotations

import os
import subprocess
import sys

N, D, T = 32768, 64, 256
K = L = 512


def _bench_rows():
    """Runs in the multi-device process; yields (name, us, derived) rows."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from benchmarks.common import timeit
    from repro.core.amortized_head import HeadConfig, make_index
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.models.head import dist_head_loss, dist_head_sample

    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    emb = jax.random.normal(jax.random.key(0), (N, D)) / jnp.sqrt(D)
    h = jax.random.normal(jax.random.key(1), (T, D)) * 2.0
    tgt = jax.random.randint(jax.random.key(2), (T,), 0, N)
    key = jax.random.key(3)

    cfg_dense = HeadConfig(n=N, k=K, l=L, mode="amortized", min_amortized_n=1)
    cfg_ivf = dataclasses.replace(cfg_dense, mips="ivf", n_probe=16)
    index = make_index(cfg_ivf, emb, mesh=mesh)

    # per-shard probe FLOPs per token (the quantity the index amortizes;
    # CPU wall-clock under-rewards the gather-heavy IVF path vs one BLAS
    # matmul — on TPU the Pallas gather+score kernel closes that gap)
    mp = mesh.shape["model"]
    v_loc = N // mp
    ivf_state = index.local_index(
        jax.tree.map(lambda x: x[:1], index.state)
    ).state
    n_c, cap = ivf_state.n_clusters, ivf_state.cap
    o_cap = ivf_state.overflow_ids.shape[0]
    flops = {
        "dense": 2 * v_loc * D,
        "ivf": 2 * (n_c + cfg_ivf.n_probe * cap + o_cap) * D,
    }

    def variants():
        yield "dense", cfg_dense, None
        yield "ivf", cfg_ivf, index

    for name, cfg, ix in variants():
        if ix is None:
            def loss_fn(e, hh, t, k, _cfg=cfg):
                return jax.value_and_grad(
                    lambda ee: dist_head_loss(mesh, ee, hh, t, k, _cfg).sum()
                )(e)
            def samp_fn(e, hh, k, _cfg=cfg):
                return dist_head_sample(mesh, e, hh, k, _cfg)
            loss_args = (emb, h, tgt, key)
            samp_args = (emb, h, key)
        else:
            def loss_fn(i, e, hh, t, k, _cfg=cfg):
                return jax.value_and_grad(
                    lambda ee: dist_head_loss(mesh, ee, hh, t, k, _cfg,
                                              index=i).sum()
                )(e)
            def samp_fn(i, e, hh, k, _cfg=cfg):
                return dist_head_sample(mesh, e, hh, k, _cfg, index=i)
            loss_args = (index, emb, h, tgt, key)
            samp_args = (index, emb, h, key)

        loss_j = jax.jit(loss_fn)
        samp_j = jax.jit(samp_fn)
        hc = analyze_hlo(loss_j.lower(*loss_args).compile().as_text())
        t_loss = timeit(loss_j, *loss_args, iters=10)
        yield (
            f"dist_loss_{name}",
            t_loss * 1e6 / T,
            f"coll_bytes_per_tok={hc.coll_bytes / T:.0f};"
            f"probe_flops_per_tok={flops[name]}",
        )
        hs = analyze_hlo(samp_j.lower(*samp_args).compile().as_text())
        t_samp = timeit(samp_j, *samp_args, iters=10)
        yield (
            f"dist_sample_{name}",
            t_samp * 1e6 / T,
            f"coll_bytes_per_tok={hs.coll_bytes / T:.0f};"
            f"probe_flops_per_tok={flops[name]}",
        )


def main() -> None:
    for name, us, derived in _bench_rows():
        print(f"{name},{us:.1f},{derived}", flush=True)


def run(report) -> None:
    """Benchmark-suite entry: re-exec with fake host devices (jax in this
    process is already initialized single-device)."""
    env = dict(os.environ)
    # the child models the mesh on fake host devices; it must never reach
    # for an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.dist_head"],
        capture_output=True, text=True, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(f"dist_head subprocess failed:\n{out.stderr[-2000:]}")
    for line in out.stdout.strip().splitlines():
        name, us, derived = line.split(",", 2)
        report(name, float(us), derived)


if __name__ == "__main__":
    main()
