"""Fused decode step economics: dispatch sites + HBM bytes/token + parity.

The decode-time head runs probe -> screen -> (re-rank) -> certificate ->
lazy-Gumbel argmax per token. Unfused, each stage is its own XLA op cluster
and every intermediate — the ``(n_probe·cap + o_cap)`` screening pool, the
``(r, d)`` re-rank gather, the ``(m_cap, d)`` tail-row gather — makes an
HBM round trip between dispatches. The fused pipeline
(:mod:`repro.kernels.decode_fused`) keeps candidate scores/ids in VMEM end
to end, emitting only the ``(k,)`` survivors (and finally two scalars per
token). This benchmark publishes three numbers per index backend:

* **parity** — fused vs unfused samples (ids, certificates, bounds) are
  asserted BITWISE identical, executing the interpret-mode kernels, for
  dense / IVF / IVF-PQ backends;
* **HLO op count** — both graphs are compiled with
  ``repro.kernels.ops.OPAQUE_STUBS`` so every Pallas site survives as one
  opaque custom-call, then ``launch.hlo_analysis.analyze_hlo`` counts
  executed top-level instruction sites (a dispatch/launch-overhead proxy,
  independent of Mosaic lowering). Asserted strictly smaller fused.
* **modeled HBM bytes/token** — an analytic per-stage model of the traffic
  that differs (intermediate round trips vs in-VMEM residency) on top of
  the shared mandatory reads, priced against the roofline HBM bandwidth
  (:data:`repro.launch.roofline.PEAKS`, the TPU v5e row). Asserted
  strictly smaller fused.

Wall-clock figures are interpret-mode CPU and indicative only.

  PYTHONPATH=src python -m benchmarks.decode_fused [--smoke] [--json PATH]
"""
from __future__ import annotations

import argparse
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import clustered_db, random_queries, timeit
from repro.core import estimators as est
from repro.core import mips
from repro.kernels import ops as kops
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import peaks

K = L = 64
N_PROBE = 8
MODELED_KIND = "TPU v5 lite"  # the roofline.PEAKS row the bound is priced at
T = 4  # decode tokens per measured dispatch


def _grids(smoke: bool):
    return (4096, 64) if smoke else (32768, 128)


def _m_cap(l: int) -> int:
    return int(l + 6 * math.sqrt(l) + 8)  # local_gumbel_max's default


def _sample_fn(fused: bool):
    @functools.partial(jax.jit, static_argnames=("fused",))
    def f(key, emb, h, index, fused=False):
        return est.local_gumbel_max(
            key, emb, h, k=K, l=L, index=index, c=0.0, fused=fused
        )

    return lambda key, emb, h, index: f(key, emb, h, index, fused=fused)


# --------------------------------------------------------------------------
# 1. bitwise parity (executes the interpret-mode kernels)
# --------------------------------------------------------------------------
def _assert_parity(emb, h, index, label: str) -> None:
    key = jax.random.key(7)
    a = _sample_fn(False)(key, emb, h, index)
    b = _sample_fn(True)(key, emb, h, index)
    for field, x, y in zip(a._fields, a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"{label}: fused decode diverged on {field}: {x} vs {y}"
        )


# --------------------------------------------------------------------------
# 2. HLO instruction sites (OPAQUE_STUBS compile, never executed)
# --------------------------------------------------------------------------
def _hlo_cost(fused: bool, emb, h, index):
    key = jax.random.key(0)
    kops.OPAQUE_STUBS = True
    try:
        fn = _sample_fn(fused)
        text = (
            jax.jit(lambda k_, e_, h_, ix: fn(k_, e_, h_, ix))
            .lower(key, emb, h, index)
            .compile()
            .as_text()
        )
    finally:
        kops.OPAQUE_STUBS = False
    return analyze_hlo(text)


# --------------------------------------------------------------------------
# 3. analytic HBM bytes/token model
# --------------------------------------------------------------------------
def _bytes_model(kind: str, *, d, n_probe, cap, o_cap, m_cap,
                 r=0, m_sub=0) -> dict:
    """Per-token HBM bytes, per stage. ``shared`` is mandatory traffic both
    paths pay (candidate/table reads); the unfused path additionally round-
    trips every inter-stage intermediate through HBM (write + read = 2x),
    while the fused path emits only each kernel's final output."""
    pool = n_probe * cap + o_cap
    shared = {
        # member payload: fp rows (IVF) or uint8 codes (IVF-PQ)
        "member_read": n_probe * cap * (m_sub if kind == "pq" else 4 * d),
        "member_ids_read": n_probe * cap * 4,
        "overflow_read": o_cap * 4 * d,
        "tail_rows_read": m_cap * 4 * d,
    }
    if kind == "pq":
        shared["rerank_rows_read"] = r * 4 * d
    unfused = {
        # screening pool (scores f32 + ids i32) written, re-read by top-k
        "pool_roundtrip": 2 * pool * 8,
        "tail_rows_roundtrip": 2 * m_cap * 4 * d,  # gather out, gemv in
        "select_out": (r if kind == "pq" else K) * 8,
    }
    fused = {"screen_out": (r if kind == "pq" else K) * 8, "tail_out": 8}
    if kind == "pq":
        unfused["rerank_rows_roundtrip"] = 2 * r * 4 * d
        unfused["rerank_out"] = K * 8
        fused["rerank_out"] = K * 8
    base = sum(shared.values())
    return {
        "shared": shared,
        "unfused_stages": unfused,
        "fused_stages": fused,
        "bytes_tok_unfused": base + sum(unfused.values()),
        "bytes_tok_fused": base + sum(fused.values()),
    }


def _backend_report(report, out, label, kind, emb, h, index, geom,
                    iters) -> None:
    _assert_parity(emb, h, index, label)
    hc_u = _hlo_cost(False, emb, h, index)
    hc_f = _hlo_cost(True, emb, h, index)
    assert hc_f.instr_count < hc_u.instr_count, (
        f"{label}: fused HLO sites {hc_f.instr_count} not < unfused "
        f"{hc_u.instr_count}"
    )
    bm = _bytes_model(kind, **geom)
    bt_u, bt_f = bm["bytes_tok_unfused"], bm["bytes_tok_fused"]
    assert bt_f < bt_u, f"{label}: modeled bytes/token {bt_f} not < {bt_u}"
    # memory-roofline decode rate bound at the modeled traffic
    hbm_bw = peaks(MODELED_KIND)["hbm_bw"]
    tok_s_u = hbm_bw / bt_u
    tok_s_f = hbm_bw / bt_f
    t_u = timeit(_sample_fn(False), jax.random.key(1), emb, h, index,
                 iters=iters, warmup=1)
    t_f = timeit(_sample_fn(True), jax.random.key(1), emb, h, index,
                 iters=iters, warmup=1)
    report(
        f"decode_fused/{label}_unfused", t_u * 1e6 / h.shape[0],
        f"hlo_sites={hc_u.instr_count} bytes_tok={bt_u} "
        f"roofline_tok_s={tok_s_u:.3e}",
    )
    report(
        f"decode_fused/{label}_fused", t_f * 1e6 / h.shape[0],
        f"hlo_sites={hc_f.instr_count} bytes_tok={bt_f} "
        f"roofline_tok_s={tok_s_f:.3e}",
    )
    out[label] = {
        "parity_bitwise": True,
        "hlo_sites_unfused": hc_u.instr_count,
        "hlo_sites_fused": hc_f.instr_count,
        "hlo_hbm_unfused": hc_u.hbm_bytes,
        "hlo_hbm_fused": hc_f.hbm_bytes,
        "bytes_tok_unfused": bt_u,
        "bytes_tok_fused": bt_f,
        "bytes_tok_reduction": round(bt_u / bt_f, 3),
        "roofline_tok_s_unfused": tok_s_u,
        "roofline_tok_s_fused": tok_s_f,
        "stages": {k: v for k, v in bm.items() if k.endswith("stages")
                   or k == "shared"},
    }


def run(report, smoke: bool = False) -> dict:
    n, d = _grids(smoke)
    iters = 2 if smoke else 5
    db = clustered_db(n, d, seed=7).astype(jnp.float32)
    h = random_queries(db, T, temperature=0.05, seed=3).astype(jnp.float32)
    m_cap = _m_cap(L)
    out: dict = {"n": n, "d": d, "k": K, "l": L, "t": T, "m_cap": m_cap}

    # dense (index=None): only the tail/argmax stage fuses — parity only
    _assert_parity(db, h, None, "dense")
    report("decode_fused/dense_parity", 0.0, "bitwise fused==unfused")
    out["dense"] = {"parity_bitwise": True}

    ivf = mips.build_index(
        mips.IVFConfig(n_probe=N_PROBE, kmeans_iters=4, use_kernel=True), db
    )
    st = ivf.state
    _backend_report(
        report, out, "ivf", "ivf", db, h, ivf,
        dict(d=d, n_probe=min(N_PROBE, st.n_clusters), cap=st.cap,
             o_cap=st.overflow_ids.shape[0], m_cap=m_cap),
        iters,
    )

    pq = mips.build_index(
        mips.PQConfig(n_probe=N_PROBE, kmeans_iters=4, pq_iters=4,
                      rerank=2 * K, use_kernel=True),
        db,
    )
    st = pq.state
    n_probe = min(N_PROBE, st.n_clusters)
    pool = n_probe * st.cap + st.overflow_ids.shape[0]
    _backend_report(
        report, out, "ivfpq", "pq", db, h, pq,
        dict(d=d, n_probe=n_probe, cap=st.cap,
             o_cap=st.overflow_ids.shape[0], m_cap=m_cap,
             r=pq._resolved_rerank(K, max(pool, K)), m_sub=st.m_sub),
        iters,
    )

    report(
        "decode_fused/acceptance", 0.0,
        f"ivf_sites {out['ivf']['hlo_sites_unfused']}->"
        f"{out['ivf']['hlo_sites_fused']} "
        f"ivfpq_sites {out['ivfpq']['hlo_sites_unfused']}->"
        f"{out['ivfpq']['hlo_sites_fused']} "
        f"bytes/tok x{out['ivf']['bytes_tok_reduction']}(ivf) "
        f"x{out['ivfpq']['bytes_tok_reduction']}(ivfpq), parity bitwise",
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI grid: vocab-4k, fewer timing iters (parity and "
                         "the fused-reduction assertions run either way)")
    ap.add_argument("--json", default=None,
                    help="write the full result table to this path")
    args = ap.parse_args()

    def report(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    print("name,us_per_token,derived")
    out = run(report, smoke=args.smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
