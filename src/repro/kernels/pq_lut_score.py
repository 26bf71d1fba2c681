"""Pallas TPU kernel: IVF-PQ probed-cluster LUT accumulation.

The PQ screening hot loop sums, for every member of the ``n_probe`` probed
clusters, its ``m_sub`` codeword table entries: ``Σ_m lut[m, code_m]``.
The XLA path materializes the gathered ``(b, n_probe, cap, m_sub)`` uint8
code copy in HBM before the lookup; this kernel instead uses the **scalar-
prefetched probe ids to drive the BlockSpec index_map** (the pattern of
:mod:`repro.kernels.ivf_gather_score`), so each grid step DMAs exactly one
``(cap, m_sub)`` uint8 code tile HBM→VMEM — 8–16x less probe traffic than
the fp gather the IVF kernel moves, which is the memory-bound win of the
quantized index.

Inside the tile the lookup is phrased MXU-natively: per subspace, a
``(cap, ksub)`` one-hot of the codes matmuls the subspace's LUT row —
gathers by vector index don't vectorize on TPU, one-hot × table does. The
one-hot lives only in VMEM/registers, one subspace at a time, so peak
VMEM is ``cap·ksub`` floats regardless of ``m_sub``.

Grid: ``(b, n_probe)``; the per-query ``(m_sub, ksub)`` LUT block and the
``(n_probe, cap)`` score block stay resident across a query's probe steps,
and scores come out lane-major as one ``(1, cap)`` row per probe (the TPU
tiling rule: a block's last two dims are the array's own or (8, 128)-
aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pq_lut_score", "lut_tile_scores"]


def lut_tile_scores(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """Score one ``(cap, m_sub)`` code tile against one ``(m_sub, ksub)``
    LUT: ``out[0, c] = Σ_m lut[m, codes[c, m]]`` as a ``(1, cap)`` f32 row.

    Shared between this kernel and the fused decode screen
    (:mod:`repro.kernels.decode_fused`) so both paths are the *same
    floating-point program* — the fused/unfused bitwise-parity guarantee
    rests on it. Per subspace, the subspace's LUT row contracts a
    ``(cap, ksub)`` one-hot of the codes at full f32 precision — gathers by
    vector index don't vectorize on TPU, one-hot × table does, and each
    output sums exactly one nonzero term.
    """
    codes = codes.astype(jnp.int32)  # (cap, m_sub)
    cap = codes.shape[0]
    m_sub, ksub = lut.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (cap, ksub), 1)
    acc = jnp.zeros((1, cap), jnp.float32)
    for mi in range(m_sub):  # static unroll: one MXU pass per subspace
        onehot = (codes[:, mi:mi + 1] == cols).astype(jnp.float32)
        acc += jax.lax.dot_general(
            lut[mi:mi + 1], onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    return acc


def _kernel(probe_ref, codes_ref, lut_ref, out_ref):
    j = pl.program_id(1)
    out_ref[pl.ds(j, 1), :] = lut_tile_scores(codes_ref[...], lut_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_lut_score(
    member_codes: jax.Array,  # (n_c, cap, m_sub) uint8 residual-PQ codes
    probe: jax.Array,  # (b, n_probe) int32 cluster ids
    lut: jax.Array,  # (b, m_sub, ksub) f32 per-query codeword tables
    *,
    interpret: bool = True,  # CPU container: interpret; False on real TPU
) -> jax.Array:
    """Returns scores (b, n_probe, cap) = Σ_m lut[b, m, codes[probe, :, m]]."""
    n_c, cap, m_sub = member_codes.shape
    b, n_probe = probe.shape
    assert lut.shape[1] == m_sub, (lut.shape, m_sub)
    grid = (b, n_probe)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # code tile chosen by the prefetched probe ids
                pl.BlockSpec(
                    (None, cap, m_sub),
                    lambda i, j, probe: (probe[i, j], 0, 0),
                ),
                pl.BlockSpec(
                    (None, m_sub, lut.shape[2]),
                    lambda i, j, probe: (i, 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, n_probe, cap), lambda i, j, probe: (i, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_probe, cap), jnp.float32),
        interpret=interpret,
    )(probe.astype(jnp.int32), member_codes, lut.astype(jnp.float32))
    return out
