"""Pallas TPU kernel: fused stratified estimator (Algorithms 3 + 4).

Given per-token candidate ids (S ∪ T), strata log-weights, and the query
hidden state, computes in ONE streaming pass over candidates:

    log Ẑ  = log Σ_i w_i e^{y_i}            (Algorithm 3)
    F̂      = Σ_i (w_i e^{y_i}/Ẑ) · E_i      (Algorithm 4 with f = φ)

using a flash-attention-style online-softmax recurrence (running max M,
running sum s, running weighted row-sum v). The embedding rows are fetched
straight into VMEM by DMAs addressed by the **scalar-prefetched candidate
ids** (:mod:`repro.kernels.row_gather`) — the (tokens, k+l, d) gathered
candidate tensor never exists in HBM, which is the memory bottleneck of the
XLA path. Candidates stream in chunks of up to 128 rows: each grid step
DMAs one ``(chunk, d)`` row tile and folds it into the recurrence with two
MXU passes.

F̂ here is exactly ∇_h log Ẑ, i.e. the backward pass of the amortized head
w.r.t. the hidden state — so this kernel serves both inference-time
partition estimation and the learning path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.row_gather import gather_rows, pad_rows, stage_shape

__all__ = ["fused_estimator"]

_NEG = -1e30  # python float: jnp constants would be captured as kernel consts
_HI = jax.lax.Precision.HIGHEST
_CHUNK = 128  # candidate rows per grid step (one lane-width of scores)


def _kernel(ids_ref, emb_hbm, h_ref, logw_ref, logz_ref, expv_ref,
            stage, rows, sem, m_run, s_run, v_run):
    i = pl.program_id(0)
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        m_run[...] = jnp.full(m_run.shape, _NEG, jnp.float32)
        s_run[...] = jnp.zeros(s_run.shape, jnp.float32)
        v_run[...] = jnp.zeros_like(v_run)

    base = c * rows.shape[0]
    gather_rows(emb_hbm, lambda r: ids_ref[i, base + r], rows, stage, sem)
    tile = rows[...]  # (chunk, d) f32
    y = jax.lax.dot_general(
        h_ref[...].astype(jnp.float32), tile, (((1,), (1,)), ((), ())),
        precision=_HI, preferred_element_type=jnp.float32,
    ) + logw_ref[...]  # (1, chunk)

    m_old = m_run[...]  # (1, 1)
    m_new = jnp.maximum(m_old, jnp.max(y, axis=1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(y - m_new)
    m_run[...] = m_new
    s_run[...] = s_run[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v_run[...] = v_run[...] * corr + jnp.dot(
        p, tile, precision=_HI, preferred_element_type=jnp.float32
    )

    @pl.when(c == nc - 1)
    def _finish():
        s_fin = s_run[...]
        logz_ref[...] = m_run[...] + jnp.log(s_fin)
        expv_ref[...] = v_run[...] / s_fin


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_estimator(
    emb: jax.Array,  # (n, d)
    ids: jax.Array,  # (t, m) int32 candidate ids (S ∪ T)
    h: jax.Array,  # (t, d) queries
    log_w: jax.Array,  # (t, m) strata log-weights (0 for S, log((n-k)/l) for T)
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (log_z (t,), expectation (t, d))."""
    n, d = emb.shape
    t, m = ids.shape
    chunk = min(m, _CHUNK)
    m_pad = -(-m // chunk) * chunk
    ids = jnp.pad(ids.astype(jnp.int32), ((0, 0), (0, m_pad - m)))
    # padded candidates weigh exp(-inf) = 0
    log_w = jnp.pad(
        log_w.astype(jnp.float32), ((0, 0), (0, m_pad - m)),
        constant_values=-jnp.inf,
    )
    row = lambda i, c, ids: (i, 0, 0)
    log_z, expv = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t, m_pad // chunk),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # rows DMA'd by id
                pl.BlockSpec((None, 1, d), row),
                pl.BlockSpec((None, 1, chunk), lambda i, c, ids: (i, 0, c)),
            ],
            out_specs=[
                pl.BlockSpec((None, 1, 1), row),
                pl.BlockSpec((None, 1, d), row),
            ],
            scratch_shapes=[
                pltpu.VMEM(stage_shape(chunk, d, emb.dtype), emb.dtype),
                pltpu.VMEM((chunk, d), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, 1, d), jnp.float32),
        ],
        interpret=interpret,
    )(ids, pad_rows(emb), h.reshape(t, 1, d), log_w.reshape(t, 1, m_pad))
    return log_z[:, 0, 0], expv[:, 0]
