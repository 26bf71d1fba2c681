"""Multi-device tests (subprocess: jax must init with fake devices BEFORE
any other test imports it — conftest deliberately does NOT set XLA_FLAGS)."""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(body: str, devices: int = 8, timeout: int = 540) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import warnings; warnings.filterwarnings("ignore")
        from jax.sharding import AxisType
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_dist_head_loss_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import mips
        from repro.core.amortized_head import HeadConfig, head_loss, make_index
        from repro.models.head import dist_head_loss

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        N, D, T = 4096, 32, 16
        emb = jax.random.normal(jax.random.key(0), (N, D)) / np.sqrt(D)
        h = jax.random.normal(jax.random.key(1), (T, D)) * 2.0
        tgt = jax.random.randint(jax.random.key(2), (T,), 0, N)

        # exact mode must agree EXACTLY (same math, different partitioning)
        cfg = HeadConfig(n=N, mode="exact")
        le = head_loss(emb, h, tgt, jax.random.key(3), cfg)
        ld = jax.jit(lambda e, hh, t: dist_head_loss(mesh, e, hh, t,
                     jax.random.key(3), cfg))(emb, h, tgt)
        np.testing.assert_allclose(np.asarray(ld), np.asarray(le.loss),
                                   rtol=1e-5, atol=1e-5)

        # amortized mode, dense-local probe: unbiased estimate close to exact
        cfg_a = HeadConfig(n=N, k=512, l=512, mode="amortized",
                           min_amortized_n=1)
        la = jax.jit(lambda e, hh, t: dist_head_loss(mesh, e, hh, t,
                     jax.random.key(4), cfg_a))(emb, h, tgt)
        np.testing.assert_allclose(np.asarray(la), np.asarray(le.loss),
                                   rtol=0.08, atol=0.08)

        # amortized mode, IVF-backed SHARDED index: each shard probes its
        # own slice sublinearly; estimate must stay close to exact
        cfg_i = HeadConfig(n=N, k=512, l=512, mode="amortized", mips="ivf",
                           n_probe=16, min_amortized_n=1)
        index = make_index(cfg_i, emb, mesh=mesh)
        assert isinstance(index, mips.ShardedIndex), type(index)
        li = jax.jit(lambda ix, e, hh, t: dist_head_loss(mesh, e, hh, t,
                     jax.random.key(4), cfg_i, index=ix))(index, emb, h, tgt)
        np.testing.assert_allclose(np.asarray(li), np.asarray(le.loss),
                                   rtol=0.1, atol=0.1)

        # gradients flow and are close to exact (dense-local and IVF-local)
        g_e = jax.grad(lambda hh: head_loss(emb, hh, tgt, jax.random.key(5),
                       cfg).loss.sum())(h)
        g_a = jax.grad(lambda hh: dist_head_loss(mesh, emb, hh, tgt,
                       jax.random.key(5), cfg_a).sum())(h)
        cos = float((g_e * g_a).sum() /
                    (jnp.linalg.norm(g_e) * jnp.linalg.norm(g_a)))
        assert cos > 0.98, cos
        g_i = jax.grad(lambda hh: dist_head_loss(mesh, emb, hh, tgt,
                       jax.random.key(5), cfg_i, index=index).sum())(h)
        cos_i = float((g_e * g_i).sum() /
                      (jnp.linalg.norm(g_e) * jnp.linalg.norm(g_i)))
        assert cos_i > 0.97, cos_i
        print("OK")
    """)
    assert "OK" in out


def test_dist_head_sample_distribution():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.amortized_head import HeadConfig, make_index
        from repro.models.head import dist_head_sample

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        N, D = 2048, 16
        emb = jax.random.normal(jax.random.key(0), (N, D)) / np.sqrt(D)
        h = jnp.broadcast_to(
            jax.random.normal(jax.random.key(1), (1, D)) * 3.0, (8, D))
        y = np.asarray(emb @ np.asarray(h[0]))
        p = np.exp(y - y.max()); p /= p.sum()
        top = np.argsort(-p)[:5]

        def check(samp, index, rounds=800):
            ids_all, oks = [], []
            for s in range(rounds):
                ids, ok, _ = samp(index, jax.random.key(s))
                ids_all.append(np.asarray(ids))
                oks.append(np.asarray(ok))
            ids = np.concatenate(ids_all)      # rounds * 8 samples
            ok_rate = np.concatenate(oks).mean()
            assert ok_rate > 0.99, ok_rate
            for t in top:
                obs = (ids == t).mean()
                se = np.sqrt(p[t] * (1 - p[t]) / len(ids))
                assert abs(obs - p[t]) < 5 * se + 2e-3, (t, obs, p[t])

        cfg = HeadConfig(n=N, k=256, l=256, mode="amortized",
                         min_amortized_n=1)
        check(jax.jit(lambda ix, k: dist_head_sample(mesh, emb, h, k, cfg)),
              None)

        # IVF-backed sharded probe: full-coverage probe (n_probe >= n_c)
        # keeps the sample distribution exact while exercising the
        # index-backed shard-local path
        cfg_i = HeadConfig(n=N, k=256, l=256, mode="amortized", mips="ivf",
                           n_probe=32, min_amortized_n=1)
        index = make_index(cfg_i, emb, mesh=mesh)
        check(jax.jit(lambda ix, k: dist_head_sample(mesh, emb, h, k, cfg_i,
                                                     index=ix)), index)
        print("OK")
    """)
    assert "OK" in out


def test_dist_trainstep_runs_and_loss_decreases():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.models.transformer as T
        T.REMAT = False
        from repro.configs import get_smoke
        from repro.launch import mesh as meshlib, steps
        from repro.models.model import Model
        from repro.optim import adamw
        from repro.data.synthetic import DataConfig, make_batch

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke("tinyllama-1.1b").scaled(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, vocab=4096,
            head_mode="amortized")
        model = Model(cfg, mesh)
        params = model.init(jax.random.key(0))
        p_sh = meshlib.param_shardings(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         params), mesh, cfg)
        params = jax.device_put(params, p_sh)
        opt = adamw.init(params)
        step = jax.jit(steps.make_train_step(
            model, steps.TrainConfig(
                opt=adamw.OptConfig(lr=1e-2, warmup_steps=2,
                                    total_steps=30))),
            donate_argnums=(0, 1))
        losses = []
        dcfg = DataConfig(batch=8, seq=32)
        for i in range(30):
            b = jax.tree.map(jnp.asarray, make_batch(cfg, dcfg, i))
            params, opt, m = step(params, opt, b, jax.random.key(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])
        print("OK", round(losses[0], 3), "->", round(losses[-1], 3))
    """)
    assert "OK" in out


def test_sharded_index_refresh_without_recompile():
    """Sharded-index lifecycle: (1) a refreshed ShardedIndex swaps into a
    compiled train step with no jit cache miss; (2) the trainer's
    drift-triggered refresh works shard-locally and recovers recall on the
    drifted embedding; (3) Server.refresh_index hot-swaps the sharded index
    without recompiling the serve step."""
    out = _run("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        import repro.models.transformer as T
        T.REMAT = False
        from repro.configs import get_smoke
        from repro.core import mips
        from repro.data.synthetic import DataConfig, make_batch
        from repro.launch import mesh as meshlib, steps
        from repro.launch.steps import TrainConfig
        from repro.models.model import Model
        from repro.optim import adamw
        from repro.optim.adamw import OptConfig
        from repro.serve.server import ServeConfig, Server
        from repro.train.trainer import RunConfig, Trainer

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke("tinyllama-1.1b").scaled(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, vocab=4096,
            head_mode="amortized", head_mips="ivf", head_k=128, head_l=128)

        # --- 1. refreshed index -> compiled train step, no cache miss ---
        model = Model(cfg, mesh)
        params = model.init(jax.random.key(0))
        p_sh = meshlib.param_shardings(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         params), mesh, cfg)
        params = jax.device_put(params, p_sh)
        index = model.make_head_index(params)
        assert isinstance(index, mips.ShardedIndex), type(index)
        opt = adamw.init(params)
        step = jax.jit(steps.make_train_step(
            model, steps.TrainConfig(opt=adamw.OptConfig(
                lr=1e-2, warmup_steps=2, total_steps=10))))
        dcfg = DataConfig(batch=8, seq=32)
        for i in range(2):
            b = jax.tree.map(jnp.asarray, make_batch(cfg, dcfg, i))
            params, opt, m = step(params, opt, b, jax.random.key(i), index)
        c0 = step._cache_size()
        index = index.refresh(model._out_embed(params))
        b = jax.tree.map(jnp.asarray, make_batch(cfg, dcfg, 2))
        params, opt, m = step(params, opt, b, jax.random.key(2), index)
        assert step._cache_size() == c0, (step._cache_size(), c0)
        assert np.isfinite(float(m["loss"]))
        print("train-swap OK", c0)

        # --- 2. trainer drift-refresh, shard-local, recall recovers ----
        run = RunConfig(num_steps=8, ckpt_every=100, log_every=100,
                        batch=4, seq=32, index_drift_threshold=0.005,
                        train=TrainConfig(opt=OptConfig(
                            lr=2e-2, warmup_steps=2, total_steps=8)))
        tr = Trainer(cfg, run, tempfile.mkdtemp(), mesh=mesh)
        stale = tr.model.make_head_index(tr.init_state()["params"])
        res = tr.train()
        assert res["status"] == "done"
        assert isinstance(tr.head_index, mips.ShardedIndex)
        assert tr.index_refreshes >= 1, "drift threshold never tripped"
        # one compile for the first (host-placed) args, at most one more
        # for the settled on-mesh layouts; refreshes add none
        assert tr.step_fn._cache_size() <= 2, tr.step_fn._cache_size()

        target = jax.eval_shape(lambda: {
            k: v for k, v in tr.init_state().items() if k != "meta"})
        state, _, _ = tr.ckpt.restore(target)
        params2 = jax.tree.map(jnp.asarray, state["params"])
        emb = tr.model._out_embed(params2)
        q = jax.random.normal(jax.random.key(42), (16, emb.shape[1])) * 2.0
        ex = np.argsort(-np.asarray(q @ emb.T), axis=1)[:, :10]
        def recall(ix):
            tk = np.asarray(ix.topk_batch(q, 10).ids)
            return np.mean([len(set(tk[i]) & set(ex[i])) / 10
                            for i in range(16)])
        r_stale, r_fresh = recall(stale), recall(tr.head_index)
        assert r_fresh >= r_stale, (r_fresh, r_stale)
        print("trainer-refresh OK", tr.index_refreshes, r_stale, r_fresh)

        # --- 3. server hot-swap without recompile -----------------------
        server = Server(cfg, params2, ServeConfig(
            batch_slots=2, max_seq=48, max_new_tokens=4), mesh=mesh)
        assert isinstance(server.index, mips.ShardedIndex)
        r1 = server.run([[1, 2, 3], [4, 5, 6, 7]])
        c1 = server.step_fn._cache_size()
        server.refresh_index(params2)
        r2 = server.run([[8, 9, 10]])
        assert server.step_fn._cache_size() == c1, (
            server.step_fn._cache_size(), c1)
        assert all(len(r.tokens) == 4 for r in r1 + r2)
        print("server-swap OK", c1)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_ivfpq_build_refresh_query():
    """Shard-local PQ build/refresh under shard_map: per-slice codebooks
    train on device inside one program, refresh keeps leaf shapes (zero-
    recompile swap), the global merge returns exact re-ranked values, and
    memory accounting stays backend-aware (codes, not the fp alias)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import mips

        mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
        n, d = 4096, 32
        k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
        centers = jax.random.normal(k1, (64, d))
        db = centers[jax.random.randint(k2, (n,), 0, 64)]
        db = db + 0.3 * jax.random.normal(k3, (n, d))
        db = db / jnp.linalg.norm(db, axis=1, keepdims=True)

        cfg = mips.PQConfig(n_probe=16, kmeans_iters=4, pq_iters=4,
                            m_sub=4, ksub=64)
        sidx = mips.build_index(cfg, db, mesh=mesh, axis="model")
        assert isinstance(sidx, mips.ShardedIndex)
        assert mips.index_spill(sidx) == 0
        # backend-aware accounting: a fraction of the exact fp table
        exact = mips.build_index(mips.ExactConfig(), db)
        assert sidx.memory_bytes() < exact.memory_bytes() / 2

        q = jax.random.normal(jax.random.key(9), (8, d))
        tk = sidx.topk_batch(q, 16)
        te = exact.topk_batch(q, 16)
        rec = np.mean([len(set(np.asarray(a).tolist())
                           & set(np.asarray(b).tolist())) / 16
                       for a, b in zip(tk.ids, te.ids)])
        assert rec > 0.8, rec
        # merged values are exact inner products of the returned rows
        scores = np.asarray(db @ q.T).T
        ids, vals = np.asarray(tk.ids), np.asarray(tk.values)
        live = ids >= 0
        np.testing.assert_allclose(
            vals[live],
            np.take_along_axis(scores, np.maximum(ids, 0), 1)[live],
            rtol=1e-4, atol=1e-4)

        db2 = db + 0.05 * jax.random.normal(jax.random.key(5), db.shape)
        db2 = db2 / jnp.linalg.norm(db2, axis=1, keepdims=True)
        r = sidx.refresh(db2)
        assert jax.tree.structure(r) == jax.tree.structure(sidx)
        query = jax.jit(lambda ix, qq: ix.topk_batch(qq, 8))
        query(sidx, q)
        c0 = query._cache_size()
        query(r, q)
        assert query._cache_size() == c0  # hot-swap: no recompile
        print("OK", rec)
    """, devices=4)
    assert "OK" in out


def test_dist_fused_decode_bitwise_parity():
    """Sharded fused decode: HeadConfig.fused_decode reproduces the unfused
    kernel path bit for bit through shard_map — each shard's local_index is
    a plain IVF/IVF-PQ instance, so the fused screen_select + tail pipeline
    rides the distributed head with no shard-specific code."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.amortized_head import HeadConfig, make_index
        from repro.models.head import dist_head_sample

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        N, D, T = 4096, 32, 8
        emb = jax.random.normal(jax.random.key(0), (N, D))
        emb = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
        h = emb[jax.random.randint(jax.random.key(1), (T,), 0, N)] / 0.05
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            jax.random.key(7), jnp.arange(T, dtype=jnp.uint32))

        for mips_kind in ("ivf", "ivfpq"):
            cfg = HeadConfig(n=N, k=128, l=128, mode="amortized",
                             mips=mips_kind, n_probe=4, use_kernel=True,
                             min_amortized_n=1)
            index = make_index(cfg, emb, mesh=mesh)
            cfg_f = dataclasses.replace(cfg, fused_decode=True)
            a = dist_head_sample(mesh, emb, h, jax.random.key(3), cfg,
                                 index=index, keys=keys)
            b = dist_head_sample(mesh, emb, h, jax.random.key(3), cfg_f,
                                 index=index, keys=keys)
            for x, y in zip(a, b):
                assert np.array_equal(np.asarray(x), np.asarray(y)), (
                    mips_kind, x, y)
            print("parity", mips_kind, "OK")
        print("OK")
    """)
    assert "OK" in out


def test_dist_adaptive_probe_parity_and_staging():
    """Sharded adaptive probe: with init == max == n_probe the adaptive
    dist_head_sample is bitwise the fixed-width one (ids AND ok), and the
    ShardedIndex degenerate topk_adaptive matches topk_batch exactly; a
    staged config reports in-schedule global widths (pmax over shards)."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.amortized_head import HeadConfig, make_index
        from repro.core.mips.adaptive import stage_widths
        from repro.models.head import dist_head_sample

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        N, D, T = 4096, 32, 8
        emb = jax.random.normal(jax.random.key(0), (N, D))
        emb = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
        h = emb[jax.random.randint(jax.random.key(1), (T,), 0, N)] / 0.05

        for mips_kind in ("ivf", "ivfpq"):
            cfg = HeadConfig(n=N, k=128, l=128, mode="amortized",
                             mips=mips_kind, n_probe=4, min_amortized_n=1)
            index = make_index(cfg, emb, mesh=mesh)

            # index-level degenerate parity on the sharded backend
            fixed = index.topk_batch(h, 64)
            atk = index.topk_adaptive(h, 64, n_probe_init=4, n_probe_max=4)
            assert np.array_equal(np.asarray(fixed.ids), np.asarray(atk.ids))
            assert np.array_equal(np.asarray(fixed.values),
                                  np.asarray(atk.values))

            # head-level degenerate parity (adaptive cfg, init == max)
            cfg_a = dataclasses.replace(
                cfg, adaptive_probe=True, n_probe_init=4, n_probe_max=4)
            ids_f, ok_f, w_f = dist_head_sample(
                mesh, emb, h, jax.random.key(3), cfg, index=index)
            ids_a, ok_a, w_a = dist_head_sample(
                mesh, emb, h, jax.random.key(3), cfg_a, index=index)
            assert np.array_equal(np.asarray(ids_f), np.asarray(ids_a))
            assert np.array_equal(np.asarray(ok_f), np.asarray(ok_a))
            assert np.all(np.asarray(w_f) == -1), w_f  # fixed: sentinel
            assert np.all(np.asarray(w_a) == 4), w_a

            # staged config: widths are pmax-combined and in-schedule
            cfg_s = dataclasses.replace(
                cfg, adaptive_probe=True, n_probe_init=2, n_probe_max=8)
            ids_s, ok_s, w_s = dist_head_sample(
                mesh, emb, h, jax.random.key(3), cfg_s, index=index)
            sched = set(stage_widths(2, 8))
            assert set(np.asarray(w_s).tolist()) <= sched, w_s
            print("adaptive parity", mips_kind, "OK")
        print("OK")
    """)
    assert "OK" in out


def test_flash_decode_kernel_on_mesh():
    """A Pallas kernel cannot be partitioned by the compiler: on a mesh the
    decode and prefill attention kernels run once per shard (KV heads over
    "model", batch over "data") and match the unsharded kernels."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.models import attention

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke("tinyllama-1.1b").scaled(n_heads=8, n_kv_heads=4)
        p = attention.init(jax.random.key(0), cfg)
        b, s = 4, 16
        ks = jax.random.split(jax.random.key(1), 4)
        x = jax.random.normal(ks[0], (b, 1, cfg.d_model), jnp.float32)
        cache = {
            n: jax.random.normal(kk, (b, cfg.n_kv_heads, s, cfg.head_dim))
            for n, kk in zip("kv", ks[1:3])
        }
        pos = jnp.array([0, 5, 15, 20], jnp.int32)
        dec = lambda m: jax.jit(lambda x, c, pp: attention.decode(
            p, cfg, x, c, pp, use_kernel=True, mesh=m))(x, cache, pos)
        (o1, c1), (o2, c2) = dec(None), dec(mesh)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=1e-5, atol=1e-5)
        xs = jax.random.normal(ks[3], (b, 6, cfg.d_model), jnp.float32)
        ps = jnp.broadcast_to(jnp.arange(6), (b, 6))
        pre = lambda m: jax.jit(lambda x: attention.prefill(
            p, cfg, x, ps, s, use_kernel=True, mesh=m))(xs)
        (o1, c1), (o2, c2) = pre(None), pre(mesh)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_compressed_allreduce_matches_psum():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import ring_allreduce_int8

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        x = jax.random.normal(jax.random.key(0), (8, 4096))

        def local(xl, key):
            flat = xl.reshape(-1)
            approx = ring_allreduce_int8(flat, "data", key)
            exact = jax.lax.psum(flat, "data")
            return approx, exact

        f = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(P("data"), P()),
            out_specs=(P(None), P(None)), check_vma=False))
        approx, exact = f(x, jax.random.key(1))
        rel = float(jnp.linalg.norm(approx - exact) /
                    jnp.linalg.norm(exact))
        assert rel < 0.04, rel  # int8 stochastic-rounding noise only
        # (max-based per-chunk scales; ~2.3% observed on gaussians)
        # unbiasedness: average error over repeats shrinks
        errs = []
        for s in range(16):
            a, e = f(x, jax.random.key(s))
            errs.append(np.asarray(a - e))
        bias = np.abs(np.mean(errs, axis=0)).mean()
        noise = np.abs(errs[0]).mean()
        assert bias < noise * 0.5, (bias, noise)
        print("OK rel", rel)
    """)
    assert "OK" in out


def test_dryrun_entry_on_tiny_mesh():
    """The dryrun cell driver end-to-end on a small mesh (lower+compile+
    roofline terms), exercising the real code path used for the report."""
    out = _run("""
        import os
        import jax
        from repro.launch import mesh as meshlib, steps
        from repro.launch.dryrun import lower_cell
        from repro.launch.hlo_analysis import analyze_hlo
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        lowered, n_tokens, kind = lower_cell(
            "stablelm-3b", "train_4k", mesh, steps.TrainConfig(accum=4))
        comp = lowered.compile()
        hc = analyze_hlo(comp.as_text())
        assert hc.flops > 1e12, hc.flops
        assert hc.coll_bytes > 0
        print("OK", f"{hc.flops:.2e}")
    """, devices=8)
    assert "OK" in out


def test_dp_tp_trainer_sharded_ckpt_async_refresh_resume():
    """End-to-end Trainer on a (2, 4) DP x TP mesh: params shard per
    launch.mesh rules, fused-chunk batches shard over "data", the head
    index spans the model axis only, checkpoints use the sharded layout,
    the async double-buffered refresh kicks and swaps on schedule, and a
    stop-and-resume restores under the mesh shardings and finishes."""
    out = _run("""
        import json, os, tempfile
        import jax, numpy as np
        import repro.models.transformer as T
        T.REMAT = False
        from repro.configs import get_smoke
        from repro.launch import mesh as meshlib
        from repro.launch.steps import TrainConfig
        from repro.optim.adamw import OptConfig
        from repro.train.trainer import RunConfig, Trainer

        mesh = meshlib.make_train_mesh(dp=2, tp=4)
        cfg = get_smoke("tinyllama-1.1b").scaled(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, vocab=4096,
            head_mode="amortized", head_mips="ivf", head_k=96, head_l=96)

        def run_cfg(steps):
            return RunConfig(
                num_steps=steps, ckpt_every=4, log_every=100, batch=8,
                seq=32, fuse_steps=2, index_refresh_every=4,
                async_refresh=True, sharded_ckpt=True,
                train=TrainConfig(opt=OptConfig(lr=1e-2, warmup_steps=2,
                                                total_steps=12)))

        wd = tempfile.mkdtemp()
        tr = Trainer(cfg, run_cfg(4), wd, mesh=mesh)
        assert tr.train()["status"] == "done"
        with open(os.path.join(wd, "ckpt_00000004", "manifest.json")) as f:
            man = json.load(f)
        assert man["sharded"] and man["complete"], man

        tr2 = Trainer(cfg, run_cfg(12), wd, mesh=mesh)
        out = tr2.train()
        assert out["status"] == "done" and out["step"] == 12
        # resume restores at 4 (the restore's rebuild IS that boundary's
        # refresh); the async schedule re-arms: kick at 8, swap at 10
        # (the kick at 12 is suppressed -- final boundary)
        assert [(e["kick"], e["swap"]) for e in tr2.refresh_events] \\
            == [(8, 10)], tr2.refresh_events
        assert tr2.index_swaps == 1
        assert tr2.head_index is not None
        # params restored UNDER the mesh shardings (not host-replicated)
        state, _, _ = tr2.ckpt.restore(
            jax.eval_shape(lambda: {k: v for k, v in tr2.init_state().items()
                                    if k != "meta"}),
            shardings=tr2._shardings)
        embed = state["params"]["embed"]
        assert len(embed.sharding.device_set) == 8, embed.sharding
        print("OK", out["step"], tr2.index_swaps)
    """, devices=8)
    assert "OK" in out
