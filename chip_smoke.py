"""Smoke run of the main paths on one TPU chip, through the entry points a
user calls, at full width, with random weights and data made from --seed.

  python chip_smoke.py [--seed 0]      # one chip: serve + paper phases
  python chip_smoke.py --four-chips    # four chips: the TP-sharded head

Phases, in order, in one process:

* serve — TinyLlama-1.1B at its published widths behind the serving
  launcher (``repro.launch.serve.serve``): amortized head over a real IVF
  index of the 32000-row vocabulary, 8 requests on 4 slots, 32 new tokens,
  the bf16 trunk users serve. Run A is the pipelined engine with the
  fused Pallas decode; run B the one-token-per-dispatch reference engine
  with the unfused kernels, prompts teacher-forced through decode. Every
  request must end ``ok``, every decode program must contain Pallas
  kernels (``tpu_custom_call``), and A and B must decode the same tokens.
* paper — the paper's log-linear model at ImageNet shape (n = 1,281,167
  unit-norm features, d = 256): an on-device IVF build, then Algorithm 3's
  log Ẑ and Algorithm 2's samples for a batch of θ. Each Ẑ must lie within
  the relative error that Theorem 3.4 bounds for the k·l used (ε = 0.25),
  as ``tests/test_partition.py`` checks it.
* four-chips (``--four-chips``, only this phase) — on a (1, 4)
  ("data", "model") mesh at vocab 32000, d 2048: the sharded exact head
  loss against the single-device one (within 1e-5), the IVF ShardedIndex
  amortized loss against exact (per token, rtol = atol = 0.1, as
  ``tests/test_dist.py``), and a few TinyLlama requests
  through ``Server(..., mesh=mesh)``.

Exits non-zero, printing no result, when JAX finds no TPU. The last line of
standard output is the JSON result with the device JAX reports.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

_COMPILE_S = [0.0]


def _check(ok, what) -> None:
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


def _report(phase: str, t0: float, c0: float, **extra) -> None:
    """One line per phase: wall and compile seconds, device peak bytes."""
    mem = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": phase,
        "wall_s": time.perf_counter() - t0,
        "compile_s": _COMPILE_S[0] - c0,
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        **extra,
    }), flush=True)


# ---------------------------------------------------------------- serve
def serve_phase(seed: int, *, arch: str = "tinyllama-1.1b",
                smoke: bool = False, vocab: int = 0) -> None:
    from repro.configs import get, get_smoke
    from repro.launch import serve as launcher
    from repro.models.model import Model

    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    argv = [
        "--arch", arch, "--head", "amortized", "--mips", "ivf",
        "--requests", "8", "--slots", "4", "--new-tokens", "32",
        "--max-seq", "256", "--decode-window", "8", "--head-use-kernel",
        "--seed", str(seed), "--count-kernels",
    ] + (["--smoke"] if smoke else []) + (["--vocab", str(vocab)] if vocab
                                          else [])
    cfg = get_smoke(arch) if smoke else get(arch)
    if vocab:
        cfg = cfg.scaled(vocab=vocab)
    params = Model(cfg).init(jax.random.key(seed))

    def run(name, extra):
        st = launcher.serve(launcher.parse_args(argv + extra), params=params)
        print(json.dumps({
            "run": name, **{k: v for k, v in st.items() if k != "tokens"}
        }), flush=True)
        _check(st["requests"] == 8, st["requests"])
        _check(all(s == "ok" for s in st["statuses"]), st["statuses"])
        _check(st["index_mb"] > 0, st["index_mb"])
        _check(st["decode_kernel_calls"] > 0, "no Pallas kernel in decode")
        return st

    st_a = run("A", ["--fused-decode"])
    st_b = run("B", ["--engine", "reference"])
    a, b = st_a["tokens"], st_b["tokens"]
    first = [next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y), None)
             for ta, tb in zip(a, b)]
    print(json.dumps({"first_mismatch": first, "tokens_A": a,
                      "tokens_B": b}), flush=True)
    _check(a == b, "pipelined+fused and reference+unfused tokens differ")
    _report("serve", t0, c0, tokens_decoded=st_a["decoded_tokens"],
            ok_rate=st_a["ok_rate"])


# ---------------------------------------------------------------- paper
def paper_phase(seed: int, *, n: int | None = None, d: int | None = None,
                n_theta: int = 8) -> None:
    from repro.configs.paper_loglinear import IMAGENET
    from repro.core import mips, partition_estimate, sample_fixed_b

    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    n = n or IMAGENET.n
    d = d or IMAGENET.d
    keys = jax.random.split(jax.random.key(seed), 6)
    # clustered unit-norm features, like real embedding databases (§4.1.1)
    n_centers = 1000
    centers = jax.random.normal(keys[0], (n_centers, d))
    assign = jax.random.randint(keys[1], (n,), 0, n_centers)
    db = centers[assign] + 0.4 * jax.random.normal(keys[2], (n, d))
    db = db / jnp.linalg.norm(db, axis=1, keepdims=True)
    # θ: a database feature, perturbed, at the paper's temperature
    rows = jax.random.randint(keys[3], (n_theta,), 0, n)
    theta = db[rows] + 0.1 * jax.random.normal(keys[4], (n_theta, d))
    theta = theta / jnp.linalg.norm(theta, axis=1, keepdims=True)
    theta = theta / IMAGENET.temperature

    index = mips.build_index(mips.IVFConfig(kmeans_iters=5, n_probe=16), db)
    jax.block_until_ready(index.state)
    # k·l sized by Theorem 3.4 for relative error ε with probability 1-δ
    eps, delta = 0.25, 0.05
    k = l = math.ceil(math.sqrt((2 / 3) / eps**2 * n * math.log(1 / delta)))
    topk = index.topk_batch(theta, k)

    def one(key, ids, vals, th):
        tk = mips.TopK(ids, vals)
        score_fn = lambda i: jnp.dot(  # noqa: E731
            db[i], th, precision=jax.lax.Precision.HIGHEST
        )
        pe = partition_estimate(key, tk, n, score_fn, l=l)
        smp = sample_fixed_b(jax.random.fold_in(key, 1), tk, n, score_fn,
                             l=l)
        return pe.log_z, smp.ok

    log_z, ok = jax.jit(jax.vmap(one))(
        jax.random.split(keys[5], n_theta), topk.ids, topk.values, theta
    )
    log_z_true = jax.jit(jax.vmap(lambda th: jax.nn.logsumexp(
        jnp.dot(db, th, precision=jax.lax.Precision.HIGHEST))))(theta)
    rel = np.abs(np.expm1(np.asarray(log_z, np.float64)
                          - np.asarray(log_z_true, np.float64)))
    print(json.dumps({
        "n": n, "d": d, "k": k, "l": l,
        "n_clusters": index.state.n_clusters, "cap": index.state.cap,
        "log_z": np.asarray(log_z).tolist(),
        "log_z_true": np.asarray(log_z_true).tolist(),
        "rel_err": rel.tolist(),
        "certified_exact_share": float(np.mean(np.asarray(ok))),
    }), flush=True)
    _check(np.isfinite(np.asarray(log_z)).all(), "non-finite log Z")
    _check((rel <= eps).all(), rel)
    _report("paper", t0, c0, max_rel_err=float(rel.max()))


# ----------------------------------------------------------- four chips
def four_chip_phase(seed: int, *, arch: str = "tinyllama-1.1b",
                    smoke: bool = False, vocab: int = 0) -> None:
    from jax.sharding import AxisType

    from repro.configs import get, get_smoke
    from repro.core import mips
    from repro.core.amortized_head import HeadConfig, head_loss, make_index
    from repro.launch import mesh as meshlib
    from repro.models.head import dist_head_loss
    from repro.models.model import Model
    from repro.serve.server import ServeConfig, Server

    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_smoke(arch) if smoke else get(arch)
    if vocab:
        cfg = cfg.scaled(vocab=vocab)
    v, dm, t = cfg.vocab, cfg.d_model, 64
    ks = jax.random.split(jax.random.key(seed), 5)
    emb = jax.random.normal(ks[0], (v, dm)) / np.sqrt(dm)
    h = jax.random.normal(ks[1], (t, dm)) * 2.0
    tgt = jax.random.randint(ks[2], (t,), 0, v)

    with jax.default_matmul_precision("highest"):
        cfg_e = HeadConfig(n=v, mode="exact")
        le = jax.jit(lambda e, hh, tt: head_loss(
            e, hh, tt, ks[3], cfg_e).loss)(emb, h, tgt)
        ld = jax.jit(lambda e, hh, tt: dist_head_loss(
            mesh, e, hh, tt, ks[3], cfg_e))(emb, h, tgt)
        exact_err = float(np.max(np.abs(np.asarray(ld) - np.asarray(le))))
        np.testing.assert_allclose(np.asarray(ld), np.asarray(le),
                                   rtol=1e-5, atol=1e-5)
        cfg_i = HeadConfig(n=v, k=1024, l=1024, mode="amortized", mips="ivf",
                           n_probe=16)
        index = make_index(cfg_i, emb, mesh=mesh)
        _check(isinstance(index, mips.ShardedIndex), type(index))
        li = jax.jit(lambda ix, e, hh, tt: dist_head_loss(
            mesh, e, hh, tt, ks[4], cfg_i, index=ix))(index, emb, h, tgt)
        ivf_err = float(np.max(np.abs(np.asarray(li) - np.asarray(le))))
        np.testing.assert_allclose(np.asarray(li), np.asarray(le),
                                   rtol=0.1, atol=0.1)
    print(json.dumps({"exact_max_abs_err": exact_err,
                      "ivf_max_abs_err": ivf_err}), flush=True)

    cfg = cfg.scaled(head_mode="amortized", head_mips="ivf")
    model = Model(cfg, mesh)
    params = model.init(jax.random.key(seed))
    p_sh = meshlib.param_shardings(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params), mesh, cfg)
    params = jax.device_put(params, p_sh)
    server = Server(cfg, params, ServeConfig(
        batch_slots=4, max_seq=128, max_new_tokens=16), mesh=mesh)
    _check(isinstance(server.index, mips.ShardedIndex),
           type(server.index))
    rng = np.random.default_rng(seed)
    prompts = [list(map(int, rng.integers(0, cfg.vocab, size=8)))
               for _ in range(4)]
    results = server.run(prompts)
    _check(all(r.status == "ok" and len(r.tokens) == 16 for r in results), [
        (r.status, len(r.tokens)) for r in results])
    print(json.dumps({"served": [r.tokens for r in results]}), flush=True)
    _report("four_chips", t0, c0, exact_max_abs_err=exact_err,
            ivf_max_abs_err=ivf_err)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP-sharded head phase on 4 chips")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} chips, JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    _check(ops.INTERPRET is not True, "kernels pinned to interpret mode")
    _check(ops.resolve_interpret() is False, "kernels would be interpreted")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        serve_phase(args.seed)
        paper_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
