"""Mesh-aware sharded MIPS indexes (DESIGN.md §3.5).

A :class:`ShardedIndex` holds one shard-LOCAL backend index per TP slice of
a model-sharded database, packed into a single jit-compatible pytree: every
backend state leaf gains a leading shard axis ``(mp, ...)`` laid out
``P(axis, None, ...)``, so ``leaf[s]`` physically lives with model shard
``s``. Inside a ``shard_map`` over the same mesh the state arrives with
leading extent 1; :meth:`ShardedIndex.local_index` peels it and
reconstitutes the plain backend Index, whose ``topk_batch`` then probes
only the shard's own rows — restoring the paper's O(√n)-per-shard
amortization where a dense head would rescan its whole vocab slice.

Builds and refreshes are shard-local:

* jit-traceable backends (IVF with ``device_build``, exact) (re)build
  INSIDE one shard_map program — the database slice never leaves its shard
  and a refresh is a single XLA program across all shards;
* host-built backends (LSH, IVF reference build) build per-slice on host,
  and the stacked state is ``device_put`` onto the mesh.

``refresh`` preserves per-shard geometry (identical leaf shapes and
shardings), so a refreshed ShardedIndex swaps into a compiled train/serve
step without recompilation — exactly like the single-device indexes.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.gumbel import TopK
from repro.core.mips import base
from repro.core.mips.adaptive import AdaptiveTopK
from repro.core.mips.exact import ExactConfig
from repro.core.mips.ivf import IVFConfig
from repro.core.mips.pq import PQConfig

__all__ = ["ShardedIndex"]


def _traceable_build(config: Any) -> bool:
    """Backends whose build/refresh can run inside a traced shard_map."""
    if isinstance(config, (ExactConfig, PQConfig)):
        return True
    return isinstance(config, IVFConfig) and config.device_build


def _leaf_spec(axis: str, x) -> P:
    return P(axis, *((None,) * (x.ndim - 1)))


def _stack_and_place(mesh, axis: str, parts):
    """Host path: stack per-shard state children and place each leaf with
    its canonical NamedSharding on the mesh."""
    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *parts
    )
    return jax.tree.map(
        lambda x: jax.device_put(
            jnp.asarray(x), NamedSharding(mesh, _leaf_spec(axis, x))
        ),
        stacked,
    )


@functools.lru_cache(maxsize=32)
def _refresh_program(config, mesh, axis: str):
    """One jitted shard-local refresh program per (config, mesh, axis).

    The trainer refreshes on a drift cadence; a per-call ``jax.jit`` over a
    fresh closure would retrace the whole k-means rebuild every time. This
    cache gives refresh the same compile-once behavior as the single-device
    ``_device_build`` (the inner jit still keys on array shapes as usual).
    """
    index_cls = base.backend_cls(config)

    def refresh_loc(db_loc, state_loc):
        children = jax.tree.map(lambda x: x[0], state_loc)
        ix = index_cls.tree_unflatten(config, children)
        new_children, _ = ix.refresh(db_loc).tree_flatten()
        return jax.tree.map(lambda x: x[None], tuple(new_children))

    def run(db, state):
        specs = jax.tree.map(lambda x: _leaf_spec(axis, x), state)
        fn = jax.shard_map(
            refresh_loc,
            mesh=mesh,
            in_specs=(P(axis, *((None,) * (db.ndim - 1))), specs),
            out_specs=specs,
            check_vma=False,
        )
        return fn(db, state)

    return jax.jit(run)


def _canonical(mesh, axis: str, state):
    """Pin every leaf to the canonical NamedSharding(mesh, P(axis, None…)).

    GSPMD may normalize equivalent specs differently between a build and a
    refresh (e.g. strip a trailing None); the placements are identical but
    the shardings compare unequal, which would miss the jit cache of any
    step the index is an argument of. An explicit device_put (a no-op data
    movement) makes build and refresh outputs bit-compatible cache keys.
    """
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, _leaf_spec(axis, x))),
        state,
    )


@jax.tree_util.register_pytree_node_class
class ShardedIndex:
    """Per-shard backend indexes over a TP-sharded database, as one pytree.

    ``state`` is the backend's ``tree_flatten`` children with a leading
    shard axis on every leaf; ``config``/``mesh``/``axis``/``n_local`` ride
    in the static treedef (meshes hash, so the index passes through ``jit``
    as a plain argument and a refresh never recompiles the step).
    """

    def __init__(self, config: Any, mesh, axis: str, n_local: int, state):
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.n_local = n_local  # database rows owned by each shard
        self.state = state

    @property
    def mp(self) -> int:
        return self.mesh.shape[self.axis]

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def build(cls, config: Any, db: jax.Array, mesh, axis: str = "model"):
        mp = mesh.shape[axis]
        n = db.shape[0]
        if n % mp:
            raise ValueError(
                f"db rows ({n}) must divide the mesh axis {axis!r} ({mp})"
            )
        n_local = n // mp
        index_cls = base.backend_cls(config)
        if _traceable_build(config):
            def build_loc(db_loc):
                children, _ = index_cls.build(db_loc, config).tree_flatten()
                return jax.tree.map(lambda x: x[None], tuple(children))

            shapes = jax.eval_shape(
                build_loc,
                jax.ShapeDtypeStruct((n_local,) + db.shape[1:], db.dtype),
            )
            out_specs = jax.tree.map(lambda s: _leaf_spec(axis, s), shapes)
            fn = jax.shard_map(
                build_loc,
                mesh=mesh,
                in_specs=(P(axis, *((None,) * (db.ndim - 1))),),
                out_specs=out_specs,
                check_vma=False,
            )
            state = _canonical(mesh, axis, jax.jit(fn)(db))
        else:
            state = cls._host_build(config, db, mesh, axis, n_local)
        return cls(config, mesh, axis, n_local, state)

    @classmethod
    def _host_build(cls, config, db, mesh, axis, n_local):
        index_cls = base.backend_cls(config)
        db_h = np.asarray(db)
        parts = [
            tuple(
                index_cls.build(
                    jnp.asarray(db_h[s * n_local : (s + 1) * n_local]), config
                ).tree_flatten()[0]
            )
            for s in range(mesh.shape[axis])
        ]
        return _stack_and_place(mesh, axis, parts)

    def refresh(self, db: jax.Array) -> "ShardedIndex":
        """Shard-local rebuild over a drifted db of the SAME (sharded)
        shape; per-shard geometry and leaf shardings are preserved, so the
        result is a drop-in swap inside a compiled step."""
        index_cls = base.backend_cls(self.config)
        if _traceable_build(self.config):
            fn = _refresh_program(self.config, self.mesh, self.axis)
            state = _canonical(self.mesh, self.axis, fn(db, self.state))
        else:
            db_h = np.asarray(db)
            parts = []
            for s in range(self.mp):
                children = jax.tree.map(lambda x: x[s], self.state)
                ix = index_cls.tree_unflatten(self.config, children)
                new = ix.refresh(
                    jnp.asarray(
                        db_h[s * self.n_local : (s + 1) * self.n_local]
                    )
                )
                parts.append(tuple(new.tree_flatten()[0]))
            state = _stack_and_place(self.mesh, self.axis, parts)
        return ShardedIndex(
            self.config, self.mesh, self.axis, self.n_local, state
        )

    # -------------------------------------------------- shard_map plumbing
    def state_specs(self):
        """PartitionSpec pytree matching ``state`` — pass both through a
        ``shard_map`` (extra arg + in_spec) to probe shard-locally."""
        return jax.tree.map(lambda x: _leaf_spec(self.axis, x), self.state)

    def local_index(self, state_loc):
        """Inside shard_map: peel the leading shard extent (1) off the
        local state and reconstitute the plain backend Index."""
        children = jax.tree.map(lambda x: x[0], state_loc)
        return base.backend_cls(self.config).tree_unflatten(
            self.config, children
        )

    # -------------------------------------------------------------- queries
    def topk_batch(self, q: jax.Array, k: int) -> TopK:
        """GLOBAL approximate top-k for replicated queries ``(b, d)``:
        per-shard probe + cross-shard merge (ids are global rows). Used by
        recall diagnostics and benchmarks; the heads instead consume
        per-shard results directly inside their own shard_map."""
        axis, n_local = self.axis, self.n_local

        def local(q_loc, state_loc):
            ix = self.local_index(state_loc)
            tk = ix.topk_batch(q_loc, k)
            off = jax.lax.axis_index(axis) * n_local
            gid = jnp.where(tk.ids >= 0, tk.ids + off, -1)
            vals = jnp.where(tk.ids >= 0, tk.values, -jnp.inf)
            av = jax.lax.all_gather(vals, axis)  # (mp, b, k)
            ag = jax.lax.all_gather(gid, axis)
            b = q_loc.shape[0]
            av = jnp.moveaxis(av, 0, 1).reshape(b, -1)
            ag = jnp.moveaxis(ag, 0, 1).reshape(b, -1)
            v, pos = jax.lax.top_k(av, k)
            return TopK(jnp.take_along_axis(ag, pos, axis=1), v)

        fn = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(), self.state_specs()),
            out_specs=TopK(P(), P()),
            check_vma=False,
        )
        return fn(q, self.state)

    def topk(self, q: jax.Array, k: int) -> TopK:
        res = self.topk_batch(q[None], k)
        return TopK(res.ids[0], res.values[0])

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
    ) -> AdaptiveTopK:
        """GLOBAL certificate-gated adaptive probe: each shard runs its own
        staged widening over its local clusters, results merge exactly like
        :meth:`topk_batch`. The reported ``width`` is the max over shards
        (shards probe in parallel, so the widest one is the critical path)
        and ``certified`` the AND — the global pool is a certified
        c-approximate top-k only if every shard's local pool is."""
        backend = base.backend_cls(self.config)
        if not hasattr(backend, "topk_adaptive"):
            raise TypeError(
                f"backend {backend.__name__} has no adaptive probe"
            )
        axis, n_local = self.axis, self.n_local

        def local(q_loc, state_loc):
            ix = self.local_index(state_loc)
            atk = ix.topk_adaptive(
                q_loc, k, c=c, n_probe_init=n_probe_init,
                n_probe_max=n_probe_max, fused=fused, router=router,
            )
            off = jax.lax.axis_index(axis) * n_local
            gid = jnp.where(atk.ids >= 0, atk.ids + off, -1)
            vals = jnp.where(atk.ids >= 0, atk.values, -jnp.inf)
            av = jax.lax.all_gather(vals, axis)  # (mp, b, k)
            ag = jax.lax.all_gather(gid, axis)
            aw = jax.lax.all_gather(atk.width, axis)  # (mp, b)
            ac = jax.lax.all_gather(atk.certified, axis)
            b = q_loc.shape[0]
            av = jnp.moveaxis(av, 0, 1).reshape(b, -1)
            ag = jnp.moveaxis(ag, 0, 1).reshape(b, -1)
            v, pos = jax.lax.top_k(av, k)
            return AdaptiveTopK(
                jnp.take_along_axis(ag, pos, axis=1), v,
                aw.max(axis=0), ac.all(axis=0),
            )

        fn = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(), self.state_specs()),
            out_specs=AdaptiveTopK(P(), P(), P(), P()),
            check_vma=False,
        )
        return fn(q, self.state)

    def memory_bytes(self) -> int:
        """Backend-accounted bytes, summed over shards. Delegating to the
        backend's own ``memory_bytes`` (on a shard-0 view — per-shard
        geometry is identical, so shards cost the same) keeps
        backend-specific accounting rules: IVF-PQ reports its quantized
        structures only. Caveat for PQ specifically: the shard_map build
        materializes each shard's fp re-rank slice as a co-located copy
        (traced outputs cannot alias inputs), so a sharded PQ index also
        holds one distributed fp table — the size of the exact backend,
        ~cap_factor x less than sharded IVF's padded member_vecs copy —
        that this accounting deliberately leaves out. Shape-only: the
        per-shard state is reconstituted from ShapeDtypeStruct views (a
        physical slice would allocate a throwaway copy of every leaf on
        each stats call)."""
        children = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), self.state
        )
        loc = base.backend_cls(self.config).tree_unflatten(
            self.config, children
        )
        return self.mp * loc.memory_bytes()

    # --------------------------------------------------------------- pytree
    def tree_flatten(self):
        return (self.state,), (self.config, self.mesh, self.axis, self.n_local)

    @classmethod
    def tree_unflatten(cls, aux, children):
        config, mesh, axis, n_local = aux
        return cls(config, mesh, axis, n_local, children[0])
