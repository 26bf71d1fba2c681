"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_fused, ops, ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.fused_estimator import fused_estimator
from repro.kernels.ivf_gather_score import ivf_gather_score


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n_c,cap,d,b,n_probe,d_block",
    [
        (16, 8, 256, 4, 3, 128),
        (8, 16, 128, 1, 8, 128),
        (32, 8, 512, 2, 4, 512),
        (4, 24, 384, 5, 2, 128),
    ],
)
def test_ivf_gather_score_sweep(n_c, cap, d, b, n_probe, d_block, dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    mv = jax.random.normal(k1, (n_c, cap, d), dtype=dtype)
    mids = jax.random.randint(k1, (n_c, cap), -1, n_c * cap)
    probe = jax.random.randint(k2, (b, n_probe), 0, n_c)
    q = jax.random.normal(k3, (b, d), dtype=jnp.float32)
    out, ids = ivf_gather_score(
        mv, mids, probe, q, d_block=d_block, interpret=True
    )
    want = ref.ivf_gather_score_ref(mv, probe, q)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)
    # the member-id gather rides the kernel's scalar-prefetch path: exact
    np.testing.assert_array_equal(ids, mids[probe])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,m,n,d", [(4, 24, 256, 64), (1, 64, 512, 128), (7, 16, 128, 256)])
def test_fused_estimator_sweep(t, m, n, d, dtype):
    k1, k2, k3, k4 = jax.random.split(jax.random.key(1), 4)
    emb = (jax.random.normal(k1, (n, d)) / np.sqrt(d)).astype(dtype)
    ids = jax.random.randint(k2, (t, m), 0, n)
    h = jax.random.normal(k3, (t, d), dtype=jnp.float32)
    log_w = jnp.where(jax.random.uniform(k4, (t, m)) < 0.3, -jnp.inf, 0.7)
    lz, ev = fused_estimator(emb, ids, h, log_w, interpret=True)
    lz_r, ev_r = ref.fused_estimator_ref(emb, ids, h, log_w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(lz, lz_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(ev, ev_r, rtol=tol, atol=tol)


def test_fused_estimator_all_masked_but_one():
    """Degenerate stratum weights: only one live candidate."""
    n, d = 64, 32
    emb = jax.random.normal(jax.random.key(2), (n, d))
    ids = jnp.array([[5, 6, 7, 8]], jnp.int32)
    h = jax.random.normal(jax.random.key(3), (1, d))
    log_w = jnp.array([[0.0, -jnp.inf, -jnp.inf, -jnp.inf]])
    lz, ev = fused_estimator(emb, ids, h, log_w, interpret=True)
    np.testing.assert_allclose(float(lz[0]), float(emb[5] @ h[0]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ev[0]), np.asarray(emb[5]), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,s,hd,s_block",
    [
        (2, 8, 2, 1024, 64, 256),
        (1, 4, 4, 512, 128, 512),
        (3, 16, 1, 512, 64, 128),
    ],
)
def test_flash_decode_sweep(b, hq, hkv, s, hd, s_block, dtype):
    k1, k2, k3, k4 = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(k1, (b, hq, hd), dtype=dtype)
    kc = jax.random.normal(k2, (b, hkv, s, hd), dtype=dtype)
    vc = jax.random.normal(k3, (b, hkv, s, hd), dtype=dtype)
    lens = jax.random.randint(k4, (b,), 1, s + 1)
    out = flash_decode(q, kc, vc, lens, s_block=s_block, interpret=True)
    want = ref.flash_decode_ref(q, kc, vc, lens)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)


def test_flash_decode_length_one():
    """Cache with a single valid entry -> output = v[0] exactly."""
    b, hq, hkv, s, hd = 1, 2, 1, 256, 32
    q = jax.random.normal(jax.random.key(5), (b, hq, hd))
    kc = jax.random.normal(jax.random.key(6), (b, hkv, s, hd))
    vc = jax.random.normal(jax.random.key(7), (b, hkv, s, hd))
    lens = jnp.array([1], jnp.int32)
    out = flash_decode(q, kc, vc, lens, s_block=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out[0, 0]), np.asarray(vc[0, 0, 0]), rtol=1e-5, atol=1e-6
    )


# --------------------------------------------------------------------------
# fused decode pipeline (kernels/decode_fused.py)
# --------------------------------------------------------------------------
def _ivf_pool(seed, n_c=8, cap=16, d=64, b=3, n_probe=4, o_cap=8):
    """Synthetic probe inputs honoring the pool invariant (dead ⟺ id -1)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    mv = jax.random.normal(ks[0], (n_c, cap, d), jnp.float32)
    mids = jnp.where(
        jax.random.uniform(ks[1], (n_c, cap)) < 0.15,
        -1,
        jax.random.randint(ks[1], (n_c, cap), 0, 4096),
    ).astype(jnp.int32)
    probe = jax.random.randint(ks[2], (b, n_probe), 0, n_c)
    q = jax.random.normal(ks[3], (b, d), jnp.float32)
    oid = jnp.where(
        jnp.arange(o_cap) < o_cap - 3,
        jax.random.randint(ks[4], (o_cap,), 0, 4096),
        -1,
    ).astype(jnp.int32)
    os_ = jax.random.normal(ks[5], (b, o_cap), jnp.float32)
    return mv, mids, os_, oid, probe, q


@pytest.mark.parametrize("k,d_block", [(8, 64), (24, 32), (80, 64)])
def test_ivf_screen_select(k, d_block):
    """Fused gather-score+top-k: allclose vs the einsum oracle (ids exact),
    BITWISE vs the unfused kernel composition it replaces."""
    mv, mids, os_, oid, probe, q = _ivf_pool(0)
    b = probe.shape[0]
    vals, ids = decode_fused.ivf_screen_select(
        mv, mids, os_, oid, probe, q, k=k, d_block=d_block, interpret=True
    )
    rv, ri = ref.ivf_screen_select_ref(mv, mids, os_, oid, probe, q, k)
    np.testing.assert_array_equal(ids, ri)
    np.testing.assert_allclose(vals, rv, rtol=1e-5, atol=1e-5)
    # unfused kernel path: ivf_gather_score kernel + XLA pool top-k
    s_k, i_k = ivf_gather_score(
        mv, mids, probe, q, d_block=d_block, interpret=True
    )
    pool_s = jnp.concatenate([s_k.reshape(b, -1), os_], axis=1)
    pool_i = jnp.concatenate(
        [i_k.reshape(b, -1), jnp.broadcast_to(oid, (b, oid.shape[0]))], axis=1
    )
    pool_s = jnp.where(pool_i >= 0, pool_s, -jnp.inf)
    wv, wi = ref.topk_select_ref(pool_s, pool_i, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wi))


@pytest.mark.parametrize("r", [8, 40, 200])
def test_pq_screen_select(r):
    """Fused LUT screen+top-r: bitwise vs the pq_lut_score kernel + XLA
    pool top-k composition (shared tile scorer)."""
    from repro.kernels.pq_lut_score import pq_lut_score

    n_c, cap, m_sub, ksub, b, n_probe, o_cap = 8, 16, 8, 16, 3, 4, 8
    ks = jax.random.split(jax.random.key(9), 5)
    codes = jax.random.randint(
        ks[0], (n_c, cap, m_sub), 0, ksub
    ).astype(jnp.uint8)
    _, mids, os_, oid, probe, _ = _ivf_pool(1, n_c=n_c, cap=cap)
    lut = jax.random.normal(ks[1], (b, m_sub, ksub), jnp.float32)
    coarse = jax.random.normal(ks[2], (b, n_probe), jnp.float32)
    vals, ids = decode_fused.pq_screen_select(
        codes, mids, coarse, os_, oid, probe, lut, r=r, interpret=True
    )
    rv, ri = ref.pq_screen_select_ref(
        codes, mids, coarse, os_, oid, probe, lut, r
    )
    np.testing.assert_array_equal(ids, ri)
    np.testing.assert_allclose(vals, rv, rtol=1e-5, atol=1e-5)
    s_k = pq_lut_score(codes, probe, lut, interpret=True)  # (b, np, cap)
    pool_s = (s_k + coarse[..., None]).reshape(b, -1)
    pool_s = jnp.concatenate([pool_s, os_], axis=1)
    pool_i = jnp.concatenate(
        [mids[probe].reshape(b, -1),
         jnp.broadcast_to(oid, (b, oid.shape[0]))], axis=1
    )
    pool_s = jnp.where(pool_i >= 0, pool_s, -jnp.inf)
    wv, wi = ref.topk_select_ref(pool_s, pool_i, r)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wi))


@pytest.mark.parametrize("k", [4, 16, 48])
def test_rerank_select(k):
    """Fused exact re-rank: dead candidates (-1 id or -inf screen score)
    stay dead; values bitwise vs the unfused gemv composition."""
    n, d, b, r = 256, 64, 3, 32
    ks = jax.random.split(jax.random.key(11), 4)
    db = jax.random.normal(ks[0], (n, d), jnp.float32)
    cand = jnp.where(
        jax.random.uniform(ks[1], (b, r)) < 0.2,
        -1,
        jax.random.randint(ks[1], (b, r), 0, n),
    ).astype(jnp.int32)
    lut_vals = jnp.where(cand >= 0, jax.random.normal(ks[2], (b, r)), -jnp.inf)
    q = jax.random.normal(ks[3], (b, d), jnp.float32)
    vals, ids = decode_fused.rerank_select(
        db, cand, lut_vals, q, k=k, interpret=True
    )
    rv, ri = ref.rerank_select_ref(db, cand, lut_vals, q, k)
    np.testing.assert_array_equal(ids, ri)
    np.testing.assert_allclose(vals, rv, rtol=1e-5, atol=1e-5)
    # unfused composition: XLA gather + per-token gemv + top-k
    exact = jax.vmap(lambda c, qq: db[jnp.maximum(c, 0)] @ qq)(cand, q)
    dead = (cand < 0) | jnp.isneginf(lut_vals)
    wv, wi = ref.topk_select_ref(jnp.where(dead, -jnp.inf, exact), cand, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wi))


def test_tail_gather_argmax():
    """Algorithm-2 finish: winner/value match the oracle, including tokens
    with zero live tail atoms (winner must come from S)."""
    n, d, t, k, m_cap = 512, 48, 5, 8, 16
    ks = jax.random.split(jax.random.key(13), 6)
    emb = jax.random.normal(ks[0], (n, d), jnp.float32)
    pos = jax.random.randint(ks[1], (t, m_cap), 0, n)
    m_used = jnp.array([0, m_cap, 3, 7, 1], jnp.int32)
    pert_s = jax.random.normal(ks[2], (t, k), jnp.float32).at[0, 2].set(50.0)
    s_ids = jax.random.randint(ks[3], (t, k), 0, n)
    heights = jax.random.normal(ks[4], (t, m_cap), jnp.float32)
    h = jax.random.normal(ks[5], (t, d), jnp.float32)
    idx, mx = decode_fused.tail_gather_argmax(
        emb, pos, m_used, pert_s, s_ids, heights, h, interpret=True
    )
    ri, rm = ref.tail_gather_argmax_ref(
        emb, pos, m_used, pert_s, s_ids, heights, h
    )
    np.testing.assert_array_equal(idx, ri)
    np.testing.assert_allclose(mx, rm, rtol=1e-6, atol=1e-6)
    assert int(idx[0]) == int(s_ids[0, 2])  # no live tail -> S winner
    # bitwise vs the unfused per-token gemv composition
    y_tail = jax.vmap(lambda p, hh: emb[p] @ hh)(pos, h)
    live = jnp.arange(m_cap)[None, :] < m_used[:, None]
    pert = jnp.concatenate(
        [pert_s, jnp.where(live, y_tail + heights, -jnp.inf)], axis=1
    )
    all_ids = jnp.concatenate([s_ids, pos], axis=1)
    best = jnp.argmax(pert, axis=1)
    np.testing.assert_array_equal(
        np.asarray(idx),
        np.asarray(jnp.take_along_axis(all_ids, best[:, None], 1)[:, 0]),
    )
    np.testing.assert_array_equal(
        np.asarray(mx),
        np.asarray(jnp.take_along_axis(pert, best[:, None], 1)[:, 0]),
    )


# --------------------------------------------------------------------------
# ops dispatch layer
# --------------------------------------------------------------------------
def test_resolve_interpret_is_lazy():
    """Regression (the INTERPRET-frozen-at-import bug): the default decides
    per call from the live backend, and a pin wins either way."""
    assert ops.INTERPRET is None
    assert ops.resolve_interpret() == (jax.default_backend() != "tpu")
    try:
        ops.INTERPRET = False
        assert ops.resolve_interpret() is False
        ops.INTERPRET = True
        assert ops.resolve_interpret() is True
    finally:
        ops.INTERPRET = None
    assert ops.resolve_interpret() == (jax.default_backend() != "tpu")


def test_opaque_stubs_match_real_shapes():
    """Every OPAQUE_STUBS stand-in must produce exactly the real wrapper's
    output (shape, dtype) tree, or stub-compiled HLO is meaningless."""
    import functools

    S = jax.ShapeDtypeStruct
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    cases = [
        (ops.ivf_gather_score,
         (S((8, 16, 64), f32), S((8, 16), i32), S((3, 4), i32),
          S((3, 64), f32)), {}),
        (ops.pq_lut_score,
         (S((8, 16, 8), u8), S((3, 4), i32), S((3, 8, 16), f32)), {}),
        (ops.fused_estimator,
         (S((128, 64), f32), S((3, 24), i32), S((3, 64), f32),
          S((3, 24), f32)), {}),
        (ops.flash_decode,
         (S((2, 4, 32), f32), S((2, 2, 512, 32), f32),
          S((2, 2, 512, 32), f32), S((2,), i32)), {}),
        (ops.ivf_screen_select,
         (S((8, 16, 64), f32), S((8, 16), i32), S((3, 8), f32),
          S((8,), i32), S((3, 4), i32), S((3, 64), f32)), {"k": 8}),
        (ops.pq_screen_select,
         (S((8, 16, 8), u8), S((8, 16), i32), S((3, 4), f32),
          S((3, 8), f32), S((8,), i32), S((3, 4), i32),
          S((3, 8, 16), f32)), {"r": 12}),
        (ops.rerank_select,
         (S((128, 64), f32), S((3, 12), i32), S((3, 12), f32),
          S((3, 64), f32)), {"k": 8}),
        (ops.tail_gather_argmax,
         (S((128, 64), f32), S((3, 16), i32), S((3,), i32), S((3, 8), f32),
          S((3, 8), i32), S((3, 16), f32), S((3, 64), f32)), {}),
    ]
    for fn, args, kw in cases:
        shape_of = lambda f: jax.tree.map(
            lambda x: (x.shape, str(x.dtype)),
            jax.eval_shape(functools.partial(f, **kw), *args),
        )
        real = shape_of(fn)
        try:
            ops.OPAQUE_STUBS = True
            stub = shape_of(fn)
        finally:
            ops.OPAQUE_STUBS = False
        assert stub == real, (fn.__name__, stub, real)
