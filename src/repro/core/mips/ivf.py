"""IVF (k-means) MIPS index — the production index, per the paper's own
experiments (§4.1.1, following Douze et al. 2016).

TPU adaptation (DESIGN.md §3): clusters are *padded to a fixed capacity* so
the probe is two dense MXU matmuls — ``q @ centroidsᵀ`` then a gather+score
over the ``n_probe`` selected clusters — with fully static shapes. Rows that
overflow their cluster's capacity spill into an always-scanned overflow
buffer, so coverage of the database is exact while ``state.spill_count == 0``
(the build reports any drop; approximation otherwise comes only from probing
a subset of clusters, exactly as in FAISS-style IVF).

The build runs ON DEVICE as one XLA program (DESIGN.md §7): jitted Lloyd
iterations whose centroid update is a ``segment_sum``, followed by a
sort/scan packing of rows into the padded member tables — no host round-trip,
which is what keeps periodic refresh cheap during learning, where the
embedding table (the database) drifts every optimizer step. ``refresh``
warm-starts Lloyd from the previous centroids and preserves all shapes, so
a refreshed index is a drop-in replacement inside a compiled train step.
A host-side numpy build (``device_build=False``) is kept as the reference
implementation and benchmark baseline (benchmarks/index_refresh.py).

The gather+score hot loop has a Pallas kernel
(:mod:`repro.kernels.ivf_gather_score`) selected via ``use_kernel``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gumbel import TopK
from repro.core.mips import adaptive, base
from repro.core.quant.kmeans import assign_clusters as _assign_clusters
from repro.core.quant.kmeans import lloyd as _lloyd

__all__ = ["IVFConfig", "IVFIndex", "IVFState"]


def _pad_pool(
    scores: jax.Array, ids: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Pad a candidate pool narrower than k with dead slots (-inf, -1)."""
    if scores.shape[1] < k:
        pad = k - scores.shape[1]
        scores = jnp.pad(scores, ((0, 0), (0, pad)),
                         constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    return scores, ids


@dataclasses.dataclass(frozen=True)
class IVFConfig:
    """Build- and query-time knobs for the IVF index.

    Geometry (cluster count, padded capacity, overflow size) is derived
    from the database size at build time and then FROZEN: ``refresh`` keeps
    it, so the state pytree structure never changes across rebuilds.
    """

    n_clusters: int | None = None  # None -> max(4, sqrt(n))
    cap_factor: float = 3.0  # padded capacity ≈ cap_factor · n / n_clusters
    overflow_frac: float = 1.0 / 16.0  # overflow buffer ≈ n/16 rows
    kmeans_iters: int = 10  # Lloyd iterations for a cold build
    refresh_iters: int = 2  # warm-started iterations per refresh
    seed: int = 0
    n_probe: int = 8  # clusters probed per query
    n_probe_init: int = 0  # adaptive probe: starting width (0 -> n_probe)
    n_probe_max: int = 0  # adaptive probe: widening ceiling (0 -> n_probe)
    use_kernel: bool = False  # Pallas gather+score kernel on the probe
    device_build: bool = True  # False: host-numpy reference build


class IVFState(NamedTuple):
    centroids: jax.Array  # (n_c, d) f32
    member_ids: jax.Array  # (n_c, cap) i32, -1 padded
    member_vecs: jax.Array  # (n_c, cap, d) — gathered copy, 0 padded
    overflow_ids: jax.Array  # (o_cap,) i32, -1 padded
    overflow_vecs: jax.Array  # (o_cap, d)
    spill_count: jax.Array  # () i32 — rows that fit neither table (0 = exact)
    radii: jax.Array  # (n_c,) f32 — max ||x - c_j|| over rows assigned to
    #   cluster j (-inf for empty clusters): the adaptive probe's
    #   Cauchy-Schwarz bound on unprobed cluster scores (adaptive.py)

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def cap(self) -> int:
        return self.member_ids.shape[1]


def _geometry(n: int, cfg: IVFConfig) -> tuple[int, int, int]:
    """Static (n_clusters, cap, o_cap) for a database of n rows."""
    n_c = min(cfg.n_clusters or max(4, int(math.sqrt(n))), n)
    cap = max(8, int(math.ceil(cfg.cap_factor * n / n_c / 8.0)) * 8)
    o_cap = max(8, int(math.ceil(cfg.overflow_frac * n / 8.0)) * 8)
    return n_c, cap, o_cap


# --------------------------------------------------------------------------
# on-device build: jitted Lloyd k-means + sort/scan padded packing
# (the Lloyd/assignment core lives in core/quant/kmeans.py, shared with PQ
# codebook training; the host-numpy reference below stays local on purpose)
# --------------------------------------------------------------------------
def _pack_ids(
    assign: jax.Array, n_c: int, cap: int, o_cap: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Capacity-padded packing with static shapes, no host loop.

    Rows are sorted by cluster id; a row's rank within its cluster (its
    sorted position minus the cluster's start offset) selects its slot:
    rank < cap goes to ``member_ids[cluster, rank]``, the rest spill to the
    overflow buffer in sorted order. Out-of-range scatter positions use
    ``mode="drop"``, and the count of rows dropped even from the overflow
    buffer is returned as ``spill_count`` (0 on any sane geometry).

    Returns (member_ids (n_c, cap), overflow_ids (o_cap,), spill_count ()).
    Shared with the IVF-PQ build (core/mips/pq.py), which packs uint8
    codes instead of gathered fp rows into the member tables.
    """
    n = assign.shape[0]
    order = jnp.argsort(assign, stable=True).astype(jnp.int32)
    sorted_assign = assign[order]
    counts = jax.ops.segment_sum(
        jnp.ones((n,), jnp.int32), assign, num_segments=n_c
    )
    starts = jnp.cumsum(counts) - counts  # (n_c,) first sorted pos per cluster
    rank = jnp.arange(n, dtype=jnp.int32) - starts[sorted_assign]
    in_table = rank < cap

    flat_pos = jnp.where(in_table, sorted_assign * cap + rank, n_c * cap)
    member_ids = (
        jnp.full((n_c * cap,), -1, jnp.int32)
        .at[flat_pos]
        .set(order, mode="drop")
        .reshape(n_c, cap)
    )
    ovf_rank = jnp.cumsum((~in_table).astype(jnp.int32)) - 1
    ovf_pos = jnp.where(~in_table, ovf_rank, o_cap)
    overflow_ids = (
        jnp.full((o_cap,), -1, jnp.int32).at[ovf_pos].set(order, mode="drop")
    )
    n_ovf = (~in_table).sum()
    spill = jnp.maximum(n_ovf - o_cap, 0).astype(jnp.int32)
    return member_ids, overflow_ids, spill


def _pack(
    db: jax.Array, assign: jax.Array, n_c: int, cap: int, o_cap: int
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`_pack_ids` plus the gathered fp member/overflow row copies."""
    member_ids, overflow_ids, spill = _pack_ids(assign, n_c, cap, o_cap)
    member_vecs = jnp.where(
        (member_ids >= 0)[..., None], db[jnp.maximum(member_ids, 0)], 0
    ).astype(db.dtype)
    overflow_vecs = jnp.where(
        (overflow_ids >= 0)[..., None], db[jnp.maximum(overflow_ids, 0)], 0
    ).astype(db.dtype)
    return member_ids, member_vecs, overflow_ids, overflow_vecs, spill


def _cluster_radii(
    dbf: jax.Array, cent: jax.Array, assign: jax.Array
) -> jax.Array:
    """Per-cluster residual radius ``max ||x - c_j||`` over ALL rows
    assigned to j (including rows that later spill to the overflow buffer —
    a harmless overestimate, since overflow rows are scanned at every
    width). Empty clusters report -inf so they bound nothing."""
    rn = jnp.linalg.norm(dbf - cent[assign], axis=1)
    n_c = cent.shape[0]
    radii = jax.ops.segment_max(rn, assign, num_segments=n_c)
    counts = jax.ops.segment_sum(
        jnp.ones_like(assign, jnp.int32), assign, num_segments=n_c
    )
    return jnp.where(counts > 0, radii, -jnp.inf).astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("n_c", "cap", "o_cap", "iters", "seed")
)
def _device_build(
    db: jax.Array,
    init_cent: jax.Array | None,
    *,
    n_c: int,
    cap: int,
    o_cap: int,
    iters: int,
    seed: int,
) -> IVFState:
    """Full index (re)build as one XLA program: k-means + pack, no host sync.

    ``init_cent=None`` cold-starts from a seeded sample of db rows;
    passing the previous centroids warm-starts a refresh.
    """
    dbf = db.astype(jnp.float32)
    if init_cent is None:
        ids = jax.random.permutation(jax.random.key(seed), db.shape[0])[:n_c]
        init_cent = dbf[ids]
    cent = _lloyd(dbf, init_cent.astype(jnp.float32), iters)
    assign = _assign_clusters(dbf, cent)
    member_ids, member_vecs, overflow_ids, overflow_vecs, spill = _pack(
        db, assign, n_c, cap, o_cap
    )
    radii = _cluster_radii(dbf, cent, assign)
    return IVFState(
        cent, member_ids, member_vecs, overflow_ids, overflow_vecs, spill,
        radii,
    )


# --------------------------------------------------------------------------
# host reference build (numpy) — benchmark baseline + parity oracle
# --------------------------------------------------------------------------
def _host_build(
    db: jax.Array, *, n_c: int, cap: int, o_cap: int, iters: int, seed: int
) -> IVFState:
    db_np = np.asarray(db, dtype=np.float32)
    n = db_np.shape[0]
    # identical seeded init to the device path => parity given same Lloyd math
    init_ids = np.asarray(
        jax.random.permutation(jax.random.key(seed), n)[:n_c]
    )
    cent = db_np[init_ids].copy()
    for _ in range(iters):
        sq_c = (cent * cent).sum(-1)
        assign = np.argmin(sq_c[None, :] - 2.0 * (db_np @ cent.T), axis=1)
        counts = np.bincount(assign, minlength=n_c).astype(np.float32)
        sums = np.zeros_like(cent)
        np.add.at(sums, assign, db_np)
        nonempty = counts > 0
        cent[nonempty] = sums[nonempty] / counts[nonempty, None]
    sq_c = (cent * cent).sum(-1)
    assign = np.argmin(sq_c[None, :] - 2.0 * (db_np @ cent.T), axis=1)

    member_ids = np.full((n_c, cap), -1, dtype=np.int32)
    overflow_ids = np.full((o_cap,), -1, dtype=np.int32)
    counts = np.zeros(n_c, dtype=np.int64)
    n_ovf = 0
    for i in range(n):
        cl = assign[i]
        if counts[cl] < cap:
            member_ids[cl, counts[cl]] = i
            counts[cl] += 1
        else:
            if n_ovf < o_cap:
                overflow_ids[n_ovf] = i
            n_ovf += 1
    spill = max(0, n_ovf - o_cap)

    db_dt = np.asarray(db)
    member_vecs = np.where(
        (member_ids >= 0)[..., None], db_dt[np.maximum(member_ids, 0)], 0
    )
    overflow_vecs = np.where(
        (overflow_ids >= 0)[..., None], db_dt[np.maximum(overflow_ids, 0)], 0
    )
    rn = np.linalg.norm(db_np - cent[assign], axis=1)
    radii = np.full(n_c, -np.inf, dtype=np.float32)
    np.maximum.at(radii, assign, rn.astype(np.float32))
    return IVFState(
        centroids=jnp.asarray(cent),
        member_ids=jnp.asarray(member_ids),
        member_vecs=jnp.asarray(member_vecs, dtype=db.dtype),
        overflow_ids=jnp.asarray(overflow_ids),
        overflow_vecs=jnp.asarray(overflow_vecs, dtype=db.dtype),
        spill_count=jnp.asarray(spill, jnp.int32),
        radii=jnp.asarray(radii, jnp.float32),
    )


# --------------------------------------------------------------------------
# the Index
# --------------------------------------------------------------------------
@base.register_backend(IVFConfig)
@jax.tree_util.register_pytree_node_class
class IVFIndex:
    """Stateful IVF index: frozen config + device state pytree."""

    def __init__(self, config: IVFConfig, state: IVFState):
        self.config = config
        self.state = state

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def build(cls, db: jax.Array, config: IVFConfig | None = None):
        cfg = config or IVFConfig()
        n_c, cap, o_cap = _geometry(db.shape[0], cfg)
        if cfg.device_build:
            state = _device_build(
                db, None, n_c=n_c, cap=cap, o_cap=o_cap,
                iters=cfg.kmeans_iters, seed=cfg.seed,
            )
        else:
            state = _host_build(
                db, n_c=n_c, cap=cap, o_cap=o_cap,
                iters=cfg.kmeans_iters, seed=cfg.seed,
            )
        return cls(cfg, state)

    def refresh(self, db: jax.Array, *, iters: int | None = None) -> "IVFIndex":
        """Warm-started on-device rebuild over a drifted db (same n, d).

        Lloyd starts from the CURRENT centroids (they are near-optimal for
        small drift, so ``refresh_iters`` << ``kmeans_iters`` suffices) and
        the geometry is preserved, so the returned index has the exact same
        pytree structure — safe to swap into a compiled train/serve step.
        """
        st = self.state
        state = _device_build(
            db,
            st.centroids,
            n_c=st.n_clusters,
            cap=st.cap,
            o_cap=st.overflow_ids.shape[0],
            iters=self.config.refresh_iters if iters is None else iters,
            seed=self.config.seed,
        )
        return IVFIndex(self.config, state)

    # -------------------------------------------------------------- queries
    def topk(self, q: jax.Array, k: int, *, n_probe: int | None = None) -> TopK:
        """Approximate top-k for a single query (d,)."""
        res = self.topk_batch(q[None], k, n_probe=n_probe)
        return TopK(res.ids[0], res.values[0])

    def _pool_scores(
        self, qf: jax.Array, probe: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """Member + overflow candidate pool for the given probe list:
        (scores, ids) of shape (b, n_probe·cap + o_cap). Padded slots carry
        id -1; their scores are NOT yet masked (callers apply their own
        liveness mask so the fixed and adaptive paths share this exactly)."""
        state = self.state
        b = qf.shape[0]
        with jax.named_scope("ivf.members"):
            if self.config.use_kernel:
                from repro.kernels import ops as kops

                scores, ids = kops.ivf_gather_score(
                    state.member_vecs, state.member_ids, probe, qf
                )  # (b, n_probe*cap)
            else:
                ids = state.member_ids[probe].reshape(b, -1)
                vecs = state.member_vecs[probe]  # (b, n_probe, cap, d)
                scores = jnp.einsum("bpcd,bd->bpc",
                                    vecs.astype(jnp.float32), qf)
                scores = scores.reshape(b, -1)  # (b, n_probe*cap)

        with jax.named_scope("ivf.overflow"):
            o_scores = (state.overflow_vecs.astype(jnp.float32) @ qf.T).T
        with jax.named_scope("ivf.select"):
            scores = jnp.concatenate([scores, o_scores], axis=1)
            ids = jnp.concatenate(
                [
                    ids,
                    jnp.broadcast_to(
                        state.overflow_ids, (b,) + state.overflow_ids.shape
                    ),
                ],
                axis=1,
            )
        return scores, ids

    def topk_batch(
        self, q: jax.Array, k: int, *, n_probe: int | None = None
    ) -> TopK:
        """Approximate top-k for a query batch (b, d) -> TopK[(b,k), (b,k)]."""
        state = self.state
        n_probe = min(n_probe or self.config.n_probe, state.n_clusters)
        with jax.named_scope("ivf.centroids"):
            qf = q.astype(jnp.float32)
            c_scores = qf @ state.centroids.T  # (b, n_c)
            _, probe = jax.lax.top_k(c_scores, n_probe)  # (b, n_probe)
        scores, ids = self._pool_scores(qf, probe)
        with jax.named_scope("ivf.select"):
            scores = jnp.where(ids >= 0, scores, -jnp.inf)
            scores, ids = _pad_pool(scores, ids, k)
            vals, pos = jax.lax.top_k(scores, k)
            return TopK(jnp.take_along_axis(ids, pos, axis=1), vals)

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
        """Certificate-gated staged probe: start at ``n_probe_init``
        clusters, widen geometrically (per query) until the gap certificate
        (:func:`repro.core.gumbel.gap_certificate`) passes or the width
        hits ``n_probe_max``. With init == max this is one all-true-masked
        stage, bitwise identical to :meth:`topk_batch` /
        :meth:`screen_select`. ``router`` (optional,
        :class:`repro.models.router.ProbeRouter`) picks the starting stage
        per query; the certificate still gates every widening step."""
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

            o_scores = (state.overflow_vecs.astype(jnp.float32) @ qf.T).T

            def stage_fn(w):
                return kops.ivf_screen_select(
                    state.member_vecs, state.member_ids, o_scores,
                    state.overflow_ids, probe, qf, k=k, probe_width=w,
                )
        else:
            scores, ids = self._pool_scores(qf, probe)
            cap = state.cap
            slot = jnp.arange(scores.shape[1], dtype=jnp.int32)
            member_slot = slot < w_max * cap  # overflow slots always live

            def stage_fn(w):
                live = ~member_slot[None, :] | (
                    slot[None, :] < (w * cap)[:, None]
                )
                sc = jnp.where((ids >= 0) & live, scores, -jnp.inf)
                sc, sids = _pad_pool(sc, ids, k)
                vals, pos = jax.lax.top_k(sc, k)
                return vals, jnp.take_along_axis(sids, pos, axis=1)

        return adaptive.staged_widen(
            stage_fn, bound_table, widths, k, c=c,
            no_spill=state.spill_count == 0, init_stage=init_stage,
        )

    def screen_select(
        self, q: jax.Array, k: int, *, n_probe: int | None = None
    ) -> TopK:
        """Fused probe: gather+score AND in-VMEM top-k selection in one
        Pallas dispatch (:func:`repro.kernels.decode_fused.ivf_screen_select`)
        — the ``(b, n_probe·cap + o_cap)`` candidate pool never reaches HBM.

        Bit-identical (ids, values) to :meth:`topk_batch` with
        ``use_kernel=True``: same per-``d_block`` f32 accumulation order,
        same overflow scoring expression (kept in XLA glue), same
        ``lax.top_k`` tie-break. The fused decode head
        (``estimators.local_gumbel_max(fused=True)``) dispatches here.
        """
        state = self.state
        n_probe = min(n_probe or self.config.n_probe, state.n_clusters)
        qf = q.astype(jnp.float32)
        c_scores = qf @ state.centroids.T  # (b, n_c)
        _, probe = jax.lax.top_k(c_scores, n_probe)  # (b, n_probe)
        o_scores = (state.overflow_vecs.astype(jnp.float32) @ qf.T).T
        from repro.kernels import ops as kops

        vals, ids = kops.ivf_screen_select(
            state.member_vecs, state.member_ids, o_scores,
            state.overflow_ids, probe, qf, k=k,
        )
        return TopK(ids, vals)

    def memory_bytes(self) -> int:
        return base.state_bytes(self.state)

    # --------------------------------------------------------------- pytree
    def tree_flatten(self):
        return (self.state,), self.config

    @classmethod
    def tree_unflatten(cls, config, children):
        return cls(config, *children)
