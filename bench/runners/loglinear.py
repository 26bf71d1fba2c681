"""Runner of the paper's log-linear model: a closed loop of θ batches
through the library's IVF top-k probe, Algorithm 3's ``partition_estimate``
and Algorithm 2's ``sample_fixed_b``, over a feature table made on the
device from the seed.

Set-up makes the table and a pool of θ on the device, builds the index on
the device, and compiles the two programs the window drives, jitted here
under stable names (``loglinear_probe``, ``loglinear_estimate``). The
window then runs batch after batch, each waiting for the previous
result on the host, for ``--seconds``.

``correct`` compares, for a seeded sample of the window's batches, each
θ's log Ẑ with the exact log Z over all n rows (within the relative error
the configuration states, from the paper's Theorem 3.4), with Algorithm 3
recomputed in float64 from the very rows the estimator chose (its
arithmetic), and the tail scores it computed with float64. Algorithm 2's
samples are judged by their distribution: for a seeded sample of the
window's draws, each sampled id's place in the exact distribution over
all n rows (a randomized probability integral transform, uniform under
exact sampling), and the Kolmogorov-Smirnov distance of those places
from uniform. ``control`` computes the scores one precision step below
the configuration's (the control, which has to read ``correct`` false).
"""
from __future__ import annotations

import dataclasses
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells, device, peaks, report, trace as tr
from harness import traffic as tf

TRACE_DIR = cells.ROOT / ".bench_trace"


@dataclasses.dataclass
class Setup:
    cfg: dict
    ref: types.ModuleType
    db: jax.Array
    index: object
    pool: jax.Array
    batch: int
    k: int
    l: int
    probe: object
    estimate: object
    base_key: jax.Array


def dot_rows(rows, t, precision: str):
    """Scores of ``rows`` against θ. ``"f32"``, what the configuration
    states, multiplies and sums in float32 (no matrix unit pass rounds
    an input); ``"high"`` is the control one step below: three bfloat16
    passes (hi·hi + hi·lo + lo·hi, as ``Precision.HIGH`` computes),
    written out so that it is the same on every backend."""
    if precision == "f32":
        return jnp.sum(rows * t, axis=-1)
    if precision != "high":
        raise ValueError(f"unknown score precision {precision!r}")
    def bf16(x):  # an op of its own: a convert pair may be folded away
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    r_hi, t_hi = bf16(rows), bf16(t)
    r_lo, t_lo = bf16(rows - r_hi), bf16(t - t_hi)

    def mm(a, b):
        return jnp.sum(a * b, axis=-1)  # exact: bf16 products fit f32

    return mm(r_hi, t_hi) + mm(r_hi, t_lo) + mm(r_lo, t_hi)


def make_programs(n: int, k: int, l: int, batch: int, precision: str):
    """The two programs of one closed-loop step, under stable names."""
    from repro.core import mips, partition_estimate, sample_fixed_b

    def loglinear_probe(index, pool, j):
        th = jax.lax.dynamic_slice_in_dim(pool, j * batch, batch, 0)
        tk = index.topk_batch(th, k)
        return tk.ids, tk.values

    def loglinear_estimate(db, pool, j, ids, values, base_key):
        th = jax.lax.dynamic_slice_in_dim(pool, j * batch, batch, 0)
        keys = jax.random.split(jax.random.fold_in(base_key, j), batch)

        def one(key, tk_ids, tk_vals, t):
            tk = mips.TopK(tk_ids, tk_vals)

            def score_fn(i):
                return dot_rows(db[i], t, precision)

            pe = partition_estimate(key, tk, n, score_fn, l=l)
            smp = sample_fixed_b(jax.random.fold_in(key, 1), tk, n,
                                 score_fn, l=l)
            return pe.log_z, pe.tail_ids, pe.tail_values, smp.index, smp.ok

        return jax.vmap(one)(keys, ids, values, th)

    return jax.jit(loglinear_probe), jax.jit(loglinear_estimate)


def build(cell, seed: int, precision: str = "f32") -> Setup:
    from repro.core import mips
    from repro.core.gumbel import default_kl

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference(cfg)
    n, d = cfg["n"], cfg["d"]
    key = jax.random.key(tf.key_int(seed))
    kf, kt, ke = jax.random.split(key, 3)
    db = ref.make_features(kf, n=n, d=d, centres=cfg["features"]["centres"],
                           noise=cfg["features"]["noise"])
    pool = ref.make_thetas(kt, db, count=traffic["theta_pool"],
                           noise=traffic["theta_noise"],
                           temperature=cfg["temperature"])
    index = mips.build_index(mips.IVFConfig(
        kmeans_iters=cfg["kmeans_iters"], n_probe=cfg["n_probe"],
        use_kernel=cfg["use_kernel"]), db)
    jax.block_until_ready((db, pool, index.state))
    k = l = default_kl(n, cfg["delta"])
    batch = traffic["batch"]
    probe, estimate = make_programs(n, k, l, batch, precision)
    s = Setup(cfg, ref, db, index, pool, batch, k, l, probe, estimate, ke)
    step(s, 0)  # compiles (or loads) both programs
    return s


def step(s: Setup, j: int):
    """One closed-loop batch: probe, estimate, wait for the answer (log Ẑ,
    the sample and its certificate, fetched in one transfer)."""
    jj = np.int32(j % (s.pool.shape[0] // s.batch))
    ids, values = s.probe(s.index, s.pool, jj)
    log_z, tail_ids, y_tail, sample, ok = s.estimate(
        s.db, s.pool, jj, ids, values, s.base_key)
    log_z, sample, ok = jax.device_get((log_z, sample, ok))
    return log_z, (ids, tail_ids, y_tail), sample, ok


def window(s: Setup, seconds: float, keep: set[int]):
    """Batches until ``seconds`` pass; returns (batches, the host-side
    answers of every batch, device outputs of the batches in ``keep``).
    Each answer carries the batch's seconds from dispatch to answer.

    The client loop runs with Python's garbage collector off, so that no
    collection stalls a batch: with it on, 3 runs of 12 held one batch
    for 0.8-1.2 s, where every other batch took 23.5-24.3 ms, and read
    1.5-2% fewer θ/s; with it off, 1 run of 12 (PERF.md, Findings). The
    loop's garbage is collected once the window has closed."""
    answers, kept = [], {}
    gc.collect()
    gc.disable()
    try:
        t = time.perf_counter()
        t_end = t + seconds
        j = 0
        while t < t_end:
            log_z, chosen, sample, ok = step(s, j)
            t, t0 = time.perf_counter(), t
            answers.append((log_z, sample, ok, t - t0))
            if j in keep:
                kept[j] = chosen
            j += 1
    finally:
        gc.enable()
    return j, answers, kept


def sampler_ks(s: Setup, answers, seed: int, count: int,
               block: int = 128) -> float:
    """Kolmogorov-Smirnov distance from uniform of the exact-distribution
    places (``ref.sample_pit``) of ``count`` draws of the window, chosen
    from the seed, the reference run in blocks of ``block`` θ."""
    nb = s.pool.shape[0] // s.batch
    draws = len(answers) * s.batch
    gen = np.random.default_rng([int(seed), 4])
    pick = np.sort(gen.choice(draws, size=min(count, draws), replace=False))
    v = gen.random(len(pick))
    j, b = pick // s.batch, pick % s.batch
    rows = (j % nb) * s.batch + b
    ids = np.array([answers[jj][1][bb] for jj, bb in zip(j, b)], np.int32)
    u = []
    for i in range(0, len(pick), block):
        sl = slice(i, i + block)
        u.append(np.asarray(s.ref.sample_pit(
            s.db, s.pool[jnp.asarray(rows[sl])], jnp.asarray(ids[sl]),
            jnp.asarray(v[sl], jnp.float32))))
    return s.ref.ks_uniform(np.concatenate(u))


def compare(s: Setup, answers, kept) -> dict:
    """Worst relative error of Ẑ against the exact Z; worst gap of log Ẑ
    from Algorithm 3 recomputed in float64 on the rows the estimator
    chose; and worst error of the tail scores the estimator computed,
    against float64."""
    n = s.cfg["n"]
    nb = s.pool.shape[0] // s.batch
    rel, arith, score = [], [], []
    valid = all(((a[1] >= 0) & (a[1] < n)).all() for a in answers)
    for j, (ids, tail_ids, y_tail) in sorted(kept.items()):
        log_z = answers[j][0]
        th = s.pool[(j % nb) * s.batch:(j % nb + 1) * s.batch]
        exact = np.asarray(s.ref.exact_log_z(s.db, th), np.float64)
        rel.extend(np.abs(np.expm1(log_z.astype(np.float64) - exact)))
        ids_h, tail_h = np.asarray(ids), np.asarray(tail_ids)
        th_h = np.asarray(th)
        valid &= bool(((ids_h >= 0) & (ids_h < n)).all())
        y_h = np.asarray(y_tail, np.float64)
        for b in range(s.batch):
            rows_s = np.asarray(s.db[jnp.asarray(ids_h[b])])
            rows_t = np.asarray(s.db[jnp.asarray(tail_h[b])])
            f64 = s.ref.stratified_log_z(rows_s, rows_t, th_h[b], n)
            arith.append(abs(float(log_z[b]) - f64))
            want = s.ref.scores(rows_t, th_h[b])
            score.append(float(np.max(np.abs(y_h[b] - want))))
    return {"rel": float(max(rel)), "arith": float(max(arith)),
            "score": float(max(score)), "valid": valid,
            "thetas": len(arith)}


def run(cell, *, seed: int, seconds: float, trace: bool, devs: list,
        t_start: float, control: bool = False):
    compiles = device.CompileLog()
    s = build(cell, seed, "high" if control else "f32")
    gen = np.random.default_rng([int(seed), 3])
    keep = set(gen.choice(64, size=cell.limits["check_batches"],
                          replace=False).tolist())
    c0 = compiles.count
    setup_s = time.perf_counter() - t_start
    if trace:
        log_dir = TRACE_DIR / f"{cell.name}-{seed}"
        (batches, answers, kept), window_s, path = tr.capture(
            lambda: window(s, seconds, keep), log_dir)
        events = tr.parse(path)
    else:
        t0 = time.perf_counter()
        batches, answers, kept = window(s, seconds, keep)
        window_s = time.perf_counter() - t0
        events = None
    compiles_in_window = compiles.count - c0
    mem_peak = device.memory_peak_bytes(devs)
    theta_per_s = batches * s.batch / window_s

    ctx = None
    if trace:
        ctx = types.SimpleNamespace(
            events=events, window_s=window_s, batches=batches,
            batch=s.batch, theta_per_s=theta_per_s,
            geometry={"n": s.cfg["n"], "d": s.cfg["d"],
                      "n_clusters": s.index.state.n_clusters,
                      "n_probe": s.cfg["n_probe"], "k": s.k, "l": s.l},
            peaks=peaks.peaks(devs[0].device_kind)
            if devs[0].platform == "tpu" else None)

    s.index = None  # the program's state goes before the reference runs
    gc.collect()
    cmp_ = compare(s, answers, kept)
    ks = sampler_ks(s, answers, seed, cell.limits["check_samples"])
    lim = cell.limits
    checks = {
        "logz_rel_err": report.check(cmp_["rel"], lim["logz_rel_err"]),
        "logz_arith_err": report.check(cmp_["arith"], lim["logz_arith_err"]),
        "tail_score_err": report.check(cmp_["score"], lim["tail_score_err"]),
        "sample_ks": report.check(ks, lim["sample_ks"]),
    }
    failed = 0 if cmp_["valid"] else 1
    correct = cmp_["valid"] and all(c["ok"] for c in checks.values())
    dev = device.info(devs)
    dev["memory_peak_bytes"] = mem_peak
    certified = float(np.mean(np.concatenate([a[2] for a in answers])))
    batch_s = [a[3] for a in answers]
    extra = {"batches": batches, "window_wall_s": window_s,
             "thetas_compared": cmp_["thetas"],
             "compiles_in_window": compiles_in_window,
             "certified_share": certified,
             "batch_ms": {q: 1e3 * float(np.quantile(batch_s, x))
                          for q, x in (("p50", 0.5), ("p99", 0.99),
                                       ("max", 1.0))}}
    if not trace:
        e2e = {"setup_s": setup_s, "theta_per_s": theta_per_s}
        metrics = {k: e2e[k] for k in cell.end_to_end}
        return report.emit(correct=correct, attempted=batches * s.batch,
                           failed=failed, metrics=metrics, units=cell.units,
                           device=dev, checks=checks, extra=extra)
    dev["busy_s"] = tr.busy_s(events)
    dev["window_s"] = window_s
    metrics = {}
    for name in cell.per_layer:
        v = cells.metric_reader(name).read(ctx)
        if v is not None:
            metrics[name] = v
    breakdown = {"device_ops": tr.top_ops(events),
                 "idle_gaps": tr.idle_gaps(events)}
    return report.emit(correct=correct, attempted=batches * s.batch,
                       failed=failed, metrics=metrics, units=cell.units,
                       device=dev, checks=checks, breakdown=breakdown,
                       extra=extra)
