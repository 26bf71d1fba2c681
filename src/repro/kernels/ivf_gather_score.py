"""Pallas TPU kernel: IVF probed-cluster gather + score.

The IVF query hot loop scores every member of the ``n_probe`` probed
clusters against the query. The XLA path materializes the gathered
``(b, n_probe, cap, d)`` cluster copy in HBM; this kernel instead uses the
**scalar-prefetched probe ids to drive the BlockSpec index_map**, so each
grid step DMAs exactly one ``(cap, d_blk)`` cluster tile HBM→VMEM and scores
it there — the gather never exists as an HBM intermediate.

The member-*id* gather rides the same prefetch path: the ``(1, cap)`` int32
id row of the probed cluster is DMA'd alongside the vector tile and copied
to an id output, so the former separate XLA ``member_ids[probe]`` gather
(one more HBM round trip between kernel dispatches) is gone.

Grid: ``(b, n_probe, d_blocks)`` — the d axis is innermost and accumulated
into the f32 output row (init at d_blk==0), so arbitrarily large feature
dims fit in VMEM with a fixed ``(cap, d_blk)`` working set. A query's
``(n_probe, cap)`` score/id block stays resident in VMEM across its probe
steps and is written back once.

TPU tiling: every block's last two dims are either (8, 128)-aligned or the
array's own, so row-shaped operands carry a unit middle axis (ids
``(n_c, 1, cap)``, queries ``(b, 1, d)``) and scores come out lane-major
as ``(1, cap)`` rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ivf_gather_score"]


def member_tile_scores(members: jax.Array, q: jax.Array, *,
                       interpret: bool) -> jax.Array:
    """``(cap, d_blk)`` member tile · ``(1, d_blk)`` query -> ``(1, cap)``
    f32 scores. Shared with the fused decode screen
    (:mod:`repro.kernels.decode_fused`) so both paths run the same
    floating-point program — their bitwise parity rests on it.

    On the chip this is one f32 matmul on the MXU. Interpreted, it is an
    f32 multiply and lane reduction instead: XLA's CPU backend picks a
    different dot algorithm per surrounding program, while a reduction
    keeps one summation order in every program, so the parity holds in
    interpret mode too."""
    if interpret:
        prod = members.astype(jnp.float32) * q.astype(jnp.float32)
        return jnp.sum(prod, axis=1)[None, :]
    return jax.lax.dot_general(
        q.astype(jnp.float32), members.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _kernel(probe_ref, member_ref, mid_ref, q_ref, out_ref, ids_ref, *,
            interpret):
    j = pl.program_id(1)
    row = pl.ds(j, 1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[row, :] = jnp.zeros((1, out_ref.shape[1]), jnp.float32)
        ids_ref[row, :] = mid_ref[...]

    out_ref[row, :] += member_tile_scores(member_ref[...], q_ref[...],
                                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("d_block", "interpret"))
def ivf_gather_score(
    member_vecs: jax.Array,  # (n_c, cap, d)
    member_ids: jax.Array,  # (n_c, cap) int32 db row ids (-1 = dead slot)
    probe: jax.Array,  # (b, n_probe) int32 cluster ids
    q: jax.Array,  # (b, d)
    *,
    d_block: int = 512,
    interpret: bool = True,  # CPU container: interpret; False on real TPU
) -> tuple[jax.Array, jax.Array]:
    """Returns (scores, ids), both (b, n_probe, cap):
    ``scores = member_vecs[probe] · q`` and ``ids = member_ids[probe]``."""
    n_c, cap, d = member_vecs.shape
    b, n_probe = probe.shape
    d_blk = min(d_block, d)
    assert d % d_blk == 0, (d, d_blk)
    grid = (b, n_probe, d // d_blk)
    out = pl.BlockSpec((None, n_probe, cap), lambda i, j, k, probe: (i, 0, 0))

    scores, ids = pl.pallas_call(
        functools.partial(_kernel, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # cluster tiles (vectors AND ids) chosen by the prefetched
                # probe ids
                pl.BlockSpec(
                    (None, cap, d_blk),
                    lambda i, j, k, probe: (probe[i, j], 0, k),
                ),
                pl.BlockSpec(
                    (None, 1, cap), lambda i, j, k, probe: (probe[i, j], 0, 0)
                ),
                pl.BlockSpec((None, 1, d_blk), lambda i, j, k, probe: (i, 0, k)),
            ],
            # a query's whole (n_probe, cap) block, resident across its
            # probe steps
            out_specs=[out, out],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n_probe, cap), jnp.float32),
            jax.ShapeDtypeStruct((b, n_probe, cap), jnp.int32),
        ],
        interpret=interpret,
    )(
        probe.astype(jnp.int32),
        member_vecs,
        member_ids.astype(jnp.int32).reshape(n_c, 1, cap),
        q.reshape(b, 1, d),
    )
    return scores, ids
