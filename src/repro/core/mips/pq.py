"""IVF-PQ MIPS index — the compressed production index (DESIGN.md §3.6).

Same coarse geometry as the IVF index (padded clusters + always-scanned
overflow buffer, built on device in one XLA program), but the member
tables store **uint8 residual-PQ codes** instead of gathered fp row
copies: the cap-padded per-row cost drops from ``~4d·cap_factor`` bytes
(the IVF fp copy) to ``~cap_factor·(m_sub + 4)`` bytes (codes + int32
ids, both cap-padded) plus small centroid/codebook constants — an
8–16x index-HBM reduction vs even the UN-padded exact table at LM
embedding widths (11.9x measured at d=128, benchmarks/pq_index.py).

Query pipeline (three stages, all static shapes):

1. **coarse probe** — ``q @ centroidsᵀ``, top ``n_probe`` clusters (exactly
   the IVF probe);
2. **LUT screening** — one ``(m_sub, ksub)`` asymmetric-distance table per
   query (:func:`repro.core.quant.build_lut`), then every member of the
   probed clusters is scored as ``q·centroid + Σ_m lut[m, code_m]`` —
   table lookups, no per-row FLOPs in ``d``. A Pallas kernel
   (:mod:`repro.kernels.pq_lut_score`) streams the uint8 cluster tiles
   through VMEM via scalar-prefetched probe ids; the XLA path gathers.
3. **exact re-rank** — the top ``r`` LUT candidates are re-scored with
   full-precision rows gathered from the database the index was built
   over, and the final top-k comes from these EXACT scores. The returned
   ``TopK.values`` are therefore true inner products: downstream estimator
   machinery (certificates, tail strata, TV-at-measured-recall accounting)
   applies unchanged, and the only approximation is which rows reach the
   pool — measured as re-rank recall.

The fp rows used by stage 3 ride in the state pytree as ``state.db``
(re-rank must be jit-traceable and the rows must follow ``refresh``), but
are EXCLUDED from ``memory_bytes()``, which accounts the index-owned
state only (centroids + codebooks + ids + codes + overflow). On the
eager single-device path this exclusion is physical, not bookkeeping:
``build``/``refresh`` attach the CALLER's array handle (same buffer — for
the amortized head, the output-embedding table that is resident in HBM as
a model parameter regardless); the jitted build program deliberately does
not emit a db output, so no fp copy is ever materialized. Two
configurations DO hold one fp table the accounting leaves out, both
documented rather than counted: a traced sharded build materializes each
shard's slice as a co-located copy (traced outputs can't alias inputs —
noted in ``ShardedIndex.memory_bytes``), and a single-device head whose
vocab is NOT 256-divisible hands the index an ``emb[:n]`` sliced copy
(``make_index`` passes the resident buffer unsliced only when unpadded).
Either way the fp table is exact-backend-sized — still ``cap_factor``x
less than IVF's padded ``member_vecs`` copy.
The overflow buffer is scored exactly against those fp rows (it is small,
``~n/16``), so build coverage semantics match IVF: approximation comes
only from probing a subset of clusters and from LUT screening ahead of the
re-rank, never from dropped rows while ``spill_count == 0``.

``refresh`` warm-starts the coarse centroids AND the PQ codebooks from the
current state with frozen geometry (same cluster count/capacity, same
``m_sub``/``ksub``), so a refreshed index has an identical pytree
structure — the recompile-free hot-swap contract of the Index API.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.gumbel import TopK
from repro.core.mips import adaptive, base
from repro.core.mips.ivf import _cluster_radii, _geometry, _pack_ids, _pad_pool
from repro.core.quant.kmeans import assign_clusters, lloyd

__all__ = ["PQConfig", "IVFPQIndex", "PQState"]


@dataclasses.dataclass(frozen=True)
class PQConfig:
    """Build- and query-time knobs for the IVF-PQ index.

    Coarse geometry (cluster count, capacity, overflow) follows the IVF
    rules and is frozen at build, as are the PQ shapes (``m_sub``
    subspaces, ``ksub <= 256`` codewords each — one uint8 per subspace).
    """

    n_clusters: int | None = None  # None -> max(4, sqrt(n))
    cap_factor: float = 3.0  # padded capacity ≈ cap_factor · n / n_clusters
    overflow_frac: float = 1.0 / 16.0  # overflow buffer ≈ n/16 rows
    kmeans_iters: int = 10  # coarse Lloyd iterations, cold build
    refresh_iters: int = 2  # warm-started coarse iterations per refresh
    m_sub: int = 8  # PQ subspaces (d % m_sub == 0); bytes per coded row
    ksub: int = 256  # codewords per subspace (<= 256: uint8 codes)
    pq_iters: int = 8  # codebook Lloyd iterations, cold build
    pq_refresh_iters: int = 1  # warm-started codebook iterations per refresh
    rerank: int = 0  # top-r LUT candidates re-ranked exactly; 0 -> 2k
    seed: int = 0
    n_probe: int = 8  # clusters probed per query
    n_probe_init: int = 0  # adaptive probe: starting width (0 -> n_probe)
    n_probe_max: int = 0  # adaptive probe: widening ceiling (0 -> n_probe)
    anisotropic_eta: float = 0.0  # ScaNN-style codebook training: weight of
    #   the query-parallel residual component in the Lloyd objective
    #   (quant.train_codebooks); 0 -> standard (isotropic) k-means
    use_kernel: bool = False  # Pallas LUT-scoring kernel on the screen


class PQState(NamedTuple):
    centroids: jax.Array  # (n_c, d) f32 coarse quantizer
    codebooks: jax.Array  # (m_sub, ksub, d_sub) f32 residual codebooks
    member_ids: jax.Array  # (n_c, cap) i32, -1 padded
    member_codes: jax.Array  # (n_c, cap, m_sub) uint8, 0 padded
    overflow_ids: jax.Array  # (o_cap,) i32, -1 padded — scored exactly
    spill_count: jax.Array  # () i32 — rows dropped at build (0 = exact)
    rerank_spill: jax.Array  # () i32 — configured re-rank slots the probed
    #   pool can never fill (rerank > n_probe·cap + o_cap); 0 on any sane
    #   geometry. Counted by base.index_spill alongside spill_count.
    radii: jax.Array  # (n_c,) f32 — max ||x - c_j|| over rows assigned to
    #   cluster j (-inf for empty clusters): the adaptive probe's
    #   Cauchy-Schwarz bound on unprobed cluster scores (adaptive.py);
    #   sound for the EXACT re-ranked values the certificate reads
    db: jax.Array  # (n, d) fp re-rank rows: the CALLER's db handle (same
    #   buffer, eager paths) — not index-owned memory; see module doc

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def cap(self) -> int:
        return self.member_ids.shape[1]

    @property
    def m_sub(self) -> int:
        return self.codebooks.shape[0]

    @property
    def ksub(self) -> int:
        return self.codebooks.shape[1]


def _pq_geometry(n: int, d: int, cfg: PQConfig) -> tuple[int, int, int, int]:
    """Static (n_c, cap, o_cap, ksub) for a database of (n, d) rows."""
    if cfg.ksub > 256:
        raise ValueError(f"ksub={cfg.ksub} > 256 does not fit uint8 codes")
    if d % cfg.m_sub:
        raise ValueError(
            f"feature dim {d} not divisible by m_sub={cfg.m_sub}"
        )
    n_c, cap, o_cap = _geometry(n, cfg)
    return n_c, cap, o_cap, min(cfg.ksub, n)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_c", "cap", "o_cap", "m_sub", "ksub", "iters", "pq_iters", "seed",
        "anisotropic_eta",
    ),
)
def _device_build(
    db: jax.Array,
    init_cent: jax.Array | None,
    init_codebooks: jax.Array | None,
    *,
    n_c: int,
    cap: int,
    o_cap: int,
    m_sub: int,
    ksub: int,
    iters: int,
    pq_iters: int,
    seed: int,
    anisotropic_eta: float = 0.0,
) -> tuple:
    """Quantized structures of a full IVF-PQ (re)build as one XLA program:
    coarse k-means + packing + residual codebook training + encode.
    ``init_cent``/``init_codebooks`` warm-start a refresh; None cold-starts
    from seeded samples.

    Deliberately does NOT return the db: jit outputs never alias inputs,
    so returning it would materialize a full fp copy on every build and
    refresh. The eager ``build``/``refresh`` wrappers attach the CALLER's
    db handle to the state instead (a pytree reference, zero-copy) — which
    is what makes ``memory_bytes``'s exclusion of the fp rows physically
    true on the single-device path.
    """
    dbf = db.astype(jnp.float32)
    n = db.shape[0]
    if init_cent is None:
        ids = jax.random.permutation(jax.random.key(seed), n)[:n_c]
        init_cent = dbf[ids]
    cent = lloyd(dbf, init_cent.astype(jnp.float32), iters)
    assign = assign_clusters(dbf, cent)
    member_ids, overflow_ids, spill = _pack_ids(assign, n_c, cap, o_cap)

    residuals = dbf - cent[assign]  # (n, d)
    codebooks = quant.train_codebooks(
        residuals, m_sub, ksub, pq_iters, seed=seed + 1, init=init_codebooks,
        anisotropic_eta=anisotropic_eta, anchors=dbf,
    )
    codes = quant.encode(codebooks, residuals)  # (n, m_sub) uint8
    member_codes = jnp.where(
        (member_ids >= 0)[..., None], codes[jnp.maximum(member_ids, 0)], 0
    )  # (n_c, cap, m_sub)
    radii = _cluster_radii(dbf, cent, assign)
    return (
        cent, codebooks, member_ids, member_codes, overflow_ids, spill, radii
    )


@base.register_backend(PQConfig)
@jax.tree_util.register_pytree_node_class
class IVFPQIndex:
    """Stateful IVF-PQ index: frozen config + device state pytree."""

    def __init__(self, config: PQConfig, state: PQState):
        self.config = config
        self.state = state

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def build(cls, db: jax.Array, config: PQConfig | None = None):
        cfg = config or PQConfig()
        n, d = db.shape
        n_c, cap, o_cap, ksub = _pq_geometry(n, d, cfg)
        parts = _device_build(
            db, None, None, n_c=n_c, cap=cap, o_cap=o_cap, m_sub=cfg.m_sub,
            ksub=ksub, iters=cfg.kmeans_iters, pq_iters=cfg.pq_iters,
            seed=cfg.seed, anisotropic_eta=cfg.anisotropic_eta,
        )
        return cls(cfg, cls._assemble(cfg, parts, db))

    @staticmethod
    def _assemble(cfg: PQConfig, parts: tuple, db: jax.Array) -> PQState:
        """PQState from the jitted build's quantized structures + the
        CALLER's db handle. Called eagerly, ``db=db`` is a pytree
        reference to the caller's array — the same buffer, no copy — so
        an index built/refreshed over the resident embedding table adds
        no fp bytes. (Inside a trace — the sharded shard_map build — the
        passthrough necessarily materializes as a per-shard copy of the
        shard's slice; see ShardedIndex.memory_bytes's note.)"""
        (cent, codebooks, member_ids, member_codes, overflow_ids, spill,
         radii) = parts
        state = PQState(
            centroids=cent,
            codebooks=codebooks,
            member_ids=member_ids,
            member_codes=member_codes,
            overflow_ids=overflow_ids,
            spill_count=spill,
            rerank_spill=jnp.zeros((), jnp.int32),
            radii=radii,
            db=db,
        )
        return IVFPQIndex._stamp_rerank_spill(cfg, state)

    @staticmethod
    def _stamp_rerank_spill(cfg: PQConfig, state: PQState) -> PQState:
        """Static misconfiguration diagnostic: configured re-rank slots the
        probed candidate pool can never fill (the per-query pool holds
        ``n_probe·cap + o_cap`` slots). 0 on any sane geometry — the same
        contract as ``spill_count`` — and summed by ``mips.index_spill``
        so partial-fill diagnostics stay uniform across backends."""
        pool = min(cfg.n_probe, state.n_clusters) * state.cap
        pool += state.overflow_ids.shape[0]
        short = max(0, cfg.rerank - pool)
        return state._replace(
            rerank_spill=jnp.asarray(short, jnp.int32)
        )

    def refresh(self, db: jax.Array, *, iters: int | None = None) -> "IVFPQIndex":
        """Warm-started on-device rebuild over a drifted db (same n, d).

        Coarse Lloyd starts from the CURRENT centroids and codebook Lloyd
        from the CURRENT codebooks (both near-optimal for small drift, so
        ``refresh_iters``/``pq_refresh_iters`` << the cold-build counts);
        all geometry is preserved, so the returned index has the exact
        same pytree structure — safe to swap into a compiled step.
        """
        st = self.state
        parts = _device_build(
            db,
            st.centroids,
            st.codebooks,
            n_c=st.n_clusters,
            cap=st.cap,
            o_cap=st.overflow_ids.shape[0],
            m_sub=st.m_sub,
            ksub=st.ksub,
            iters=self.config.refresh_iters if iters is None else iters,
            pq_iters=self.config.pq_refresh_iters,
            seed=self.config.seed,
            anisotropic_eta=self.config.anisotropic_eta,
        )
        return IVFPQIndex(self.config, self._assemble(self.config, parts, db))

    # -------------------------------------------------------------- queries
    def _resolved_rerank(self, k: int, pool: int) -> int:
        r = self.config.rerank or 2 * k
        return min(max(r, k), pool)

    def topk(
        self, q: jax.Array, k: int, *, n_probe: int | None = None
    ) -> TopK:
        """Approximate top-k for a single query (d,)."""
        res = self.topk_batch(q[None], k, n_probe=n_probe)
        return TopK(res.ids[0], res.values[0])

    def _screen_pool(
        self, qf: jax.Array, probe: jax.Array, c_scores: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """LUT screening pool for the given probe list: (scores, ids) of
        shape (b, n_probe·cap + o_cap) — ADC member scores plus the EXACT
        overflow scores. Padded slots carry id -1; their scores are NOT yet
        masked (callers apply their own liveness mask so the fixed and
        adaptive paths share this exactly)."""
        state = self.state
        b = qf.shape[0]
        n_probe = probe.shape[1]
        dbf = state.db
        lut = quant.build_lut(state.codebooks, qf)  # (b, m, ksub)

        if self.config.use_kernel:
            from repro.kernels import ops as kops

            scores = kops.pq_lut_score(
                state.member_codes, probe, lut
            )  # (b, n_probe, cap)
        else:
            codes = state.member_codes[probe]  # (b, np, cap, m)
            scores = quant.lut_scores(
                lut, codes.reshape(b, -1, state.m_sub)
            ).reshape(b, n_probe, state.cap)
        # residual-PQ total: q·centroid + q·decode(residual code)
        scores = scores + jnp.take_along_axis(c_scores, probe, axis=1)[..., None]
        scores = scores.reshape(b, -1)
        ids = state.member_ids[probe].reshape(b, -1)  # (b, np*cap)

        # overflow buffer: small, scored EXACTLY against the fp rows
        o_ids = state.overflow_ids
        o_vecs = jnp.where(
            (o_ids >= 0)[:, None],
            dbf[jnp.maximum(o_ids, 0)].astype(jnp.float32),
            0.0,
        )
        scores = jnp.concatenate([scores, (o_vecs @ qf.T).T], axis=1)
        ids = jnp.concatenate(
            [ids, jnp.broadcast_to(o_ids, (b,) + o_ids.shape)], axis=1
        )
        return scores, ids

    def _rerank_pool(
        self, scores: jax.Array, ids: jax.Array, qf: jax.Array, k: int, r: int
    ) -> TopK:
        """Stage 3: exact re-rank of the top-r LUT candidates with fp rows."""
        dbf = self.state.db
        lut_vals, pos = jax.lax.top_k(scores, r)
        cand = jnp.take_along_axis(ids, pos, axis=1)  # (b, r)
        rows = dbf[jnp.maximum(cand, 0)].astype(jnp.float32)  # (b, r, d)
        exact = jnp.einsum(  # full f32, as the fused re-rank kernel
            "brd,bd->br", rows, qf, precision=jax.lax.Precision.HIGHEST
        )
        exact = jnp.where(
            (cand >= 0) & ~jnp.isneginf(lut_vals), exact, -jnp.inf
        )
        vals, p2 = jax.lax.top_k(exact, k)
        return TopK(jnp.take_along_axis(cand, p2, axis=1), vals)

    def topk_batch(
        self, q: jax.Array, k: int, *, n_probe: int | None = None
    ) -> TopK:
        """LUT-screened, exactly re-ranked top-k: (b, d) -> TopK[(b, k)].

        Returned values are EXACT inner products of the surviving rows
        (stage-3 re-rank), so dead slots are the only -inf entries and the
        estimator-side recall accounting needs no PQ-specific handling.
        """
        state = self.state
        n_probe = min(n_probe or self.config.n_probe, state.n_clusters)
        qf = q.astype(jnp.float32)
        c_scores = qf @ state.centroids.T  # (b, n_c)
        _, probe = jax.lax.top_k(c_scores, n_probe)  # (b, n_probe)
        scores, ids = self._screen_pool(qf, probe, c_scores)
        scores = jnp.where(ids >= 0, scores, -jnp.inf)
        scores, ids = _pad_pool(scores, ids, k)
        r = self._resolved_rerank(k, scores.shape[1])
        return self._rerank_pool(scores, ids, qf, k, r)

    def topk_adaptive(
        self,
        q: jax.Array,
        k: int,
        *,
        c: float = 0.0,
        n_probe_init: int | None = None,
        n_probe_max: int | None = None,
        fused: bool = False,
        router=None,
    ) -> "adaptive.AdaptiveTopK":
        """Certificate-gated staged probe (see ``IVFIndex.topk_adaptive``).

        The gap certificate reads the stage's EXACT re-ranked values, for
        which the centroid + radius bound is sound; LUT-screening misses
        *within* probed clusters are not the certificate's concern (they
        are the re-rank recall the benchmarks measure, unchanged from the
        fixed-width pipeline). With init == max this is one all-true-masked
        stage, bitwise identical to :meth:`topk_batch` /
        :meth:`screen_select`."""
        state = self.state
        cfg = self.config
        n_c = state.n_clusters
        w_max = min(n_probe_max or cfg.n_probe_max or cfg.n_probe, n_c)
        init = min(n_probe_init or cfg.n_probe_init or cfg.n_probe, w_max)
        widths = adaptive.stage_widths(init, w_max)
        qf = q.astype(jnp.float32)
        c_scores = qf @ state.centroids.T  # (b, n_c)
        bound_table = adaptive.unprobed_bound_table(c_scores, state.radii, qf)
        _, probe = jax.lax.top_k(c_scores, w_max)
        init_stage = (
            None if router is None
            else router.init_stage(c_scores, qf, widths)
        )

        if fused:
            from repro.kernels import ops as kops

            dbf = state.db
            coarse = jnp.take_along_axis(c_scores, probe, axis=1)
            o_ids = state.overflow_ids
            o_vecs = jnp.where(
                (o_ids >= 0)[:, None],
                dbf[jnp.maximum(o_ids, 0)].astype(jnp.float32),
                0.0,
            )
            o_scores = (o_vecs @ qf.T).T
            lut = quant.build_lut(state.codebooks, qf)
            pool = w_max * state.cap + o_ids.shape[0]
            r = self._resolved_rerank(k, max(pool, k))

            def stage_fn(w):
                lut_vals, cand = kops.pq_screen_select(
                    state.member_codes, state.member_ids, coarse, o_scores,
                    o_ids, probe, lut, r=r, probe_width=w,
                )
                return kops.rerank_select(dbf, cand, lut_vals, qf, k=k)
        else:
            scores, ids = self._screen_pool(qf, probe, c_scores)
            cap = state.cap
            slot = jnp.arange(scores.shape[1], dtype=jnp.int32)
            member_slot = slot < w_max * cap  # overflow slots always live
            pool = max(scores.shape[1], k)
            r = self._resolved_rerank(k, pool)

            def stage_fn(w):
                live = ~member_slot[None, :] | (
                    slot[None, :] < (w * cap)[:, None]
                )
                sc = jnp.where((ids >= 0) & live, scores, -jnp.inf)
                sc, sids = _pad_pool(sc, ids, k)
                tk = self._rerank_pool(sc, sids, qf, k, r)
                return tk.values, tk.ids

        return adaptive.staged_widen(
            stage_fn, bound_table, widths, k, c=c,
            no_spill=state.spill_count == 0, init_stage=init_stage,
        )

    def screen_select(
        self, q: jax.Array, k: int, *, n_probe: int | None = None
    ) -> TopK:
        """Fused query pipeline: LUT screen + pool top-r in one Pallas
        dispatch (:func:`repro.kernels.decode_fused.pq_screen_select`), then
        exact re-rank + top-k in a second
        (:func:`repro.kernels.decode_fused.rerank_select`) — neither the
        ``(b, n_probe·cap + o_cap)`` screening pool nor the ``(b, r, d)``
        re-rank gather ever reaches HBM.

        Bit-identical (ids, values) to :meth:`topk_batch` with
        ``use_kernel=True``: the LUT tile scorer is literally shared
        (:func:`repro.kernels.pq_lut_score.lut_tile_scores`), the coarse
        term and exact overflow scores use the same XLA expressions, and
        the re-rank matvec has the unfused gemv's shape. The fused decode
        head (``estimators.local_gumbel_max(fused=True)``) dispatches here.
        """
        state = self.state
        n_probe = min(n_probe or self.config.n_probe, state.n_clusters)
        b, d = q.shape
        qf = q.astype(jnp.float32)
        dbf = state.db
        c_scores = qf @ state.centroids.T  # (b, n_c)
        _, probe = jax.lax.top_k(c_scores, n_probe)  # (b, n_probe)
        lut = quant.build_lut(state.codebooks, qf)  # (b, m, ksub)
        coarse = jnp.take_along_axis(c_scores, probe, axis=1)  # (b, n_probe)
        o_ids = state.overflow_ids
        o_vecs = jnp.where(
            (o_ids >= 0)[:, None],
            dbf[jnp.maximum(o_ids, 0)].astype(jnp.float32),
            0.0,
        )
        o_scores = (o_vecs @ qf.T).T  # (b, o_cap), exact — as topk_batch
        pool = n_probe * state.cap + o_ids.shape[0]
        # unfused r is resolved over the k-padded pool; the kernel's
        # extractor reproduces the pad slots' (-inf, -1) picks on its own
        r = self._resolved_rerank(k, max(pool, k))
        from repro.kernels import ops as kops

        lut_vals, cand = kops.pq_screen_select(
            state.member_codes, state.member_ids, coarse, o_scores, o_ids,
            probe, lut, r=r,
        )
        vals, ids = kops.rerank_select(dbf, cand, lut_vals, qf, k=k)
        return TopK(ids, vals)

    def memory_bytes(self) -> int:
        """Index-OWNED device memory: centroids, codebooks, member tables,
        codes, overflow ids. Excludes ``state.db`` — on the eager
        unpadded-vocab path it IS the caller's buffer (build/refresh
        attach the handle, the jitted program emits no db output), so no
        fp bytes exist to count; the quantization win the pq benchmark
        measures is this accounting. Sharded and padded-vocab builds do
        retain one exact-backend-sized fp table the exclusion leaves out
        (see the module doc)."""
        st = self.state
        return base.state_bytes(
            (st.centroids, st.codebooks, st.member_ids, st.member_codes,
             st.overflow_ids, st.spill_count, st.rerank_spill, st.radii)
        )

    # --------------------------------------------------------------- pytree
    def tree_flatten(self):
        return (self.state,), self.config

    @classmethod
    def tree_unflatten(cls, config, children):
        return cls(config, *children)
