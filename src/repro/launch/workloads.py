"""Workloads launcher: the three estimator-core clients end to end.

  # deep-kNN over trunk activation taps (conformal credibility in JSON)
  PYTHONPATH=src python -m repro.launch.workloads dknn \
      --arch tinyllama-1.1b --mips ivf --classes 4 --train 256 --test 64

  # perturb-and-MAP structured inference (MAP / stochastic beam search)
  PYTHONPATH=src python -m repro.launch.workloads structured \
      --arch tinyllama-1.1b --mode sbs --beams 4 --horizon 8 --mips exact

  # log-Z estimator head-to-head: Algorithm 3 vs the unbiased LSH sampler
  PYTHONPATH=src python -m repro.launch.workloads estimator \
      --n 8192 --d 64 --queries 8 --tables 32 --bits 6

The dknn task is a synthetic band-classification problem: class ``c``
emits tokens from the ``c``-th vocab band, and the model's mean-pooled
activation taps (untrained: token embeddings suffice) separate the bands.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get, get_smoke
from repro.core import estimators as est
from repro.core import mips
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.workloads import dknn, structured

_MIPS = ("exact", "ivf", "ivfpq", "lsh")


def index_cfg(name: str, *, n_probe: int = 16):
    """CLI backend name -> mips config dataclass (the backend selector)."""
    if name == "exact":
        return mips.ExactConfig()
    if name == "ivf":
        return mips.IVFConfig(n_probe=n_probe)
    if name == "ivfpq":
        return mips.PQConfig(n_probe=n_probe, m_sub=4)
    if name == "lsh":
        return mips.LSHConfig()
    raise ValueError(name)


def _band_batches(cfg, n, n_classes, seq, rng, band=16):
    """Synthetic band-classification data: label c draws tokens from a
    narrow c-specific vocab band (plus 20% uniform noise). Narrow bands
    keep the mean-pooled class signal well above the within-class spread
    (separation ~ sqrt(2 * seq / band))."""
    band = min(band, cfg.vocab // n_classes)
    stride = cfg.vocab // n_classes
    labels = rng.integers(0, n_classes, size=n)
    toks = (labels[:, None] * stride + rng.integers(0, band, size=(n, seq)))
    noise = rng.integers(0, cfg.vocab, size=(n, seq))
    toks = np.where(rng.random((n, seq)) < 0.2, noise, toks)
    return jnp.asarray(toks, jnp.int32), jnp.asarray(labels, jnp.int32)


def run_dknn(args) -> dict:
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    if args.vocab:
        cfg = cfg.scaled(vocab=args.vocab)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(args.seed)

    def reps(n):
        toks, labels = _band_batches(cfg, n, args.classes, args.seq, rng)
        return model.trunk_taps(params, {"tokens": toks}), labels

    train_reps, train_labels = reps(args.train)
    cal_reps, cal_labels = reps(args.cal)
    test_reps, test_labels = reps(args.test)

    dcfg = dknn.DKNNConfig(
        n_classes=args.classes, k=args.k,
        index_cfg=index_cfg(args.mips),
    )
    state = dknn.fit(train_reps, train_labels, cal_reps, cal_labels, dcfg)
    res = dknn.classify(state, dknn.normalize_reps(test_reps), dcfg)
    acc = float(jnp.mean(res.pred == test_labels))
    return {
        "workload": "dknn",
        "mips": args.mips,
        "n_taps": int(train_reps.shape[0]),
        "classes": args.classes,
        "k": args.k,
        "accuracy": round(acc, 4),
        "credibility_mean": round(float(res.credibility.mean()), 4),
        "confidence_mean": round(float(res.confidence.mean()), 4),
        "credibility_p10": round(
            float(jnp.percentile(res.credibility, 10)), 4
        ),
        "p_value_spread": round(
            float((res.p_values.max(1) - res.p_values.min(1)).mean()), 4
        ),
    }


def run_structured(args) -> dict:
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    if args.vocab:
        cfg = cfg.scaled(vocab=args.vocab)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    index = None
    if args.mips != "exact":
        emb = model._out_embed(params)[: cfg.vocab].astype(jnp.float32)
        index = mips.build_index(index_cfg(args.mips), emb)
    bcfg = structured.BeamConfig(
        n_beams=args.beams, horizon=args.horizon,
        expand_k=args.expand_k, l=args.l, mode=args.mode,
        logz=args.logz,
    )
    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, size=args.prompt_len), jnp.int32
    )
    out = structured.search(
        model, params, prompt, jax.random.key(args.seed), bcfg, index
    )
    toks = np.asarray(out.tokens)
    return {
        "workload": "structured",
        "mode": args.mode,
        "mips": args.mips,
        "beams": args.beams,
        "horizon": args.horizon,
        "tokens": toks[np.asarray(out.live)].tolist(),
        "logp": [round(float(v), 4) for v in np.asarray(out.logp)],
        "gumbel": [round(float(v), 4) for v in np.asarray(out.gumbel)],
        "exact": np.asarray(out.exact).tolist(),
        "ok_rate": round(float(out.ok_rate), 4),
        "distinct": int(len({tuple(r) for r in toks})),
    }


def run_estimator(args) -> dict:
    """One-shot log-Z head-to-head on a synthetic clustered problem."""
    from benchmarks import common  # repo-root package, launch-time import

    db = common.clustered_db(args.n, args.d, seed=args.seed)
    h = common.random_queries(db, args.queries, seed=args.seed + 1)
    exact = est.exact_logz(db, h)

    lcfg = mips.LSHConfig(
        n_tables=args.tables, n_bits=args.bits, bucket_cap=args.n
    )
    lidx = mips.build_index(lcfg, db)
    lsh_est = est.lsh_sampler_logz(lidx, h)

    key = jax.random.key(args.seed)
    topk = est.topk_probe(db, h, args.k)
    ids, log_w = est.amortized_candidates(key, topk, args.n, args.l)
    alg3 = est.stratified_logz(db, h, ids, log_w)

    def rmse(x):
        return float(jnp.sqrt(jnp.mean((x - exact) ** 2)))

    return {
        "workload": "estimator",
        "n": args.n,
        "queries": args.queries,
        "alg3_rmse": round(rmse(alg3), 6),
        "lsh_sampler_rmse": round(rmse(lsh_est), 6),
        "lsh_tables": args.tables,
        "lsh_bits": args.bits,
        "lsh_dropped": lidx.dropped_count,
        "exact_logz_mean": round(float(exact.mean()), 4),
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    d = sub.add_parser("dknn", help="deep-kNN conformal classification")
    d.add_argument("--arch", default="tinyllama-1.1b", choices=list(ARCHS))
    d.add_argument("--smoke", action="store_true", default=True)
    d.add_argument("--full", dest="smoke", action="store_false")
    d.add_argument("--mips", default="exact", choices=list(_MIPS))
    d.add_argument("--vocab", type=int, default=0)
    d.add_argument("--classes", type=int, default=4)
    d.add_argument("--k", type=int, default=8)
    d.add_argument("--seq", type=int, default=16)
    d.add_argument("--train", type=int, default=256)
    d.add_argument("--cal", type=int, default=64)
    d.add_argument("--test", type=int, default=64)
    d.add_argument("--seed", type=int, default=0)

    s = sub.add_parser("structured", help="perturb-and-MAP beam search")
    s.add_argument("--arch", default="tinyllama-1.1b", choices=list(ARCHS))
    s.add_argument("--smoke", action="store_true", default=True)
    s.add_argument("--full", dest="smoke", action="store_false")
    s.add_argument("--mode", default="sbs", choices=["sbs", "map"])
    s.add_argument("--logz", default="exact", choices=["exact", "amortized"])
    s.add_argument("--mips", default="exact", choices=list(_MIPS))
    s.add_argument("--vocab", type=int, default=0)
    s.add_argument("--beams", type=int, default=4)
    s.add_argument("--horizon", type=int, default=8)
    s.add_argument("--expand-k", type=int, default=64)
    s.add_argument("--l", type=int, default=32)
    s.add_argument("--prompt-len", type=int, default=4)
    s.add_argument("--seed", type=int, default=0)

    e = sub.add_parser("estimator", help="log-Z estimator head-to-head")
    e.add_argument("--n", type=int, default=8192)
    e.add_argument("--d", type=int, default=64)
    e.add_argument("--queries", type=int, default=8)
    e.add_argument("--k", type=int, default=128)
    e.add_argument("--l", type=int, default=128)
    e.add_argument("--tables", type=int, default=32)
    e.add_argument("--bits", type=int, default=6)
    e.add_argument("--seed", type=int, default=0)

    args = ap.parse_args()
    out = {
        "dknn": run_dknn,
        "structured": run_structured,
        "estimator": run_estimator,
    }[args.cmd](args)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
