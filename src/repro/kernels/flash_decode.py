"""Pallas TPU kernel: flash decode attention (one query token vs KV cache).

Decode-time attention is memory-bound: one query attends over an S-long KV
cache. This kernel streams the cache in ``(s_blk, hd)`` tiles and keeps a
flash-style online softmax (running max / denominator / value accumulator)
in VMEM, so the (S,) score vector never materializes in HBM.

Per-sequence cache lengths arrive via scalar prefetch and mask the tail
tile, supporting ragged batches in serving.

TPU tiling: the cache is head-major, ``(B, Hkv, S, hd)`` — the layout the
model's serving cache is created in (models/attention.init_cache), so the
kernel reads it in place. One grid step serves a whole GQA group: the
``(G, hd)`` queries of KV head ``h`` against an ``(s_blk, hd)`` tile of
that head's cache, so every block's last two dims are the array's own or
(8, 128)-aligned.

Precision: ``q·k`` multiplies the operands in their storage dtype (bf16
products are exact in the f32 accumulator, so one MXU pass loses
nothing); ``p·v`` keeps the f32 softmax weights at full precision.

:func:`flash_prefill` is the causal prompt-side twin: a block of prompt
positions attends over the ring a token-by-token decode would hold, each
row over its own prefix, through the same per-tile step
(:func:`_online_step`) — so a position's output is the one decode
computes for it, bit for bit, and batched prefill and per-token decode
build the same cache. It streams only the tiles a query block's prefix
covers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode", "flash_prefill"]

_NEG = -1e30  # python float: jnp constants would be captured as kernel consts
_HI = jax.lax.Precision.HIGHEST


def _init_run(m_run, s_run, acc):
    m_run[...] = jnp.full(m_run.shape, _NEG, jnp.float32)
    s_run[...] = jnp.zeros(s_run.shape, jnp.float32)
    acc[...] = jnp.zeros_like(acc)


def _online_step(q, k_ref, v_ref, lens, j, m_run, s_run, acc, *, scale,
                 s_blk):
    """One ``(s_blk, hd)`` KV tile of the online softmax for query rows
    ``q`` (n, hd); row ``r`` sees the cache slots ``< lens[r]`` ((n, 1)).
    Each row's arithmetic is independent of the other rows — the decode
    and prefill kernels rest their bitwise agreement on it."""
    k = k_ref[...]  # (s_blk, hd)
    scores = jax.lax.dot_general(
        q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
        precision=_HI if k.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    ) * scale  # (n, s_blk)
    pos = j * s_blk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < lens, scores, _NEG)

    m_old = m_run[...]  # (n, 1)
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(scores - m_new)  # (n, s_blk)
    m_run[...] = m_new
    s_run[...] = s_run[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * corr + jnp.dot(
        p, v_ref[...].astype(jnp.float32), precision=_HI,
        preferred_element_type=jnp.float32,
    )


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_run, s_run, acc, *, scale,
            s_blk):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_run(m_run, s_run, acc)

    lens = jnp.full(m_run.shape, len_ref[b], jnp.int32)
    _online_step(q_ref[...], k_ref, v_ref, lens, j, m_run, s_run, acc,
                 scale=scale, s_blk=s_blk)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = acc[...] / s_run[...]


@functools.partial(
    jax.jit, static_argnames=("s_block", "interpret")
)
def flash_decode(
    q: jax.Array,  # (B, Hq, hd) — one query token per sequence
    k_cache: jax.Array,  # (B, Hkv, S, hd), head-major
    v_cache: jax.Array,  # (B, Hkv, S, hd)
    lengths: jax.Array,  # (B,) int32 valid cache lengths
    *,
    s_block: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """Returns attention output (B, Hq, hd), f32: query ``i`` attends over
    the first ``lengths[i]`` slots of its cache row."""
    b, hq, hd = q.shape
    _, hkv, s, _ = k_cache.shape
    g = hq // hkv  # GQA group size
    s_blk = min(s_block, s)
    assert s % s_blk == 0, (s, s_blk)
    scale = 1.0 / (hd**0.5)
    grid = (b, hkv, s // s_blk)
    kv_spec = pl.BlockSpec(
        (None, None, s_blk, hd), lambda i, h, j, lens: (i, h, j, 0)
    )
    q_spec = pl.BlockSpec(
        (None, None, g, hd), lambda i, h, j, lens: (i, h, 0, 0)
    )

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, s_blk=s_blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(b, hkv, g, hd), k_cache, v_cache)
    return out.reshape(b, hq, hd)


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_run, s_run, acc, *, scale,
                    s_blk, q_blk, g):
    qi = pl.program_id(2)
    j = pl.program_id(3)
    last = (qi * q_blk + q_blk - 1) // s_blk  # last tile this block needs

    @pl.when(j == 0)
    def _init():
        _init_run(m_run, s_run, acc)

    @pl.when(j <= last)
    def _step():
        # row r is query head r % g of position qi * q_blk + r // g, which
        # a decode step would see with cache length position + 1
        row = jax.lax.broadcasted_iota(jnp.int32, m_run.shape, 0)
        lens = qi * q_blk + row // g + 1
        _online_step(q_ref[...], k_ref, v_ref, lens, j, m_run, s_run, acc,
                     scale=scale, s_blk=s_blk)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        o_ref[...] = acc[...] / s_run[...]


@functools.partial(
    jax.jit, static_argnames=("s_block", "q_block", "interpret")
)
def flash_prefill(
    q: jax.Array,  # (B, L, Hq, hd) — prompt positions 0..L-1
    k_cache: jax.Array,  # (B, Hkv, S, hd), head-major ring, S >= L
    v_cache: jax.Array,  # (B, Hkv, S, hd)
    *,
    s_block: int = 512,
    q_block: int = 64,
    interpret: bool = True,
) -> jax.Array:
    """Causal prompt attention over the ring prefill builds: returns
    (B, L, Hq, hd) f32, position ``p`` attending slots ``0..p`` — the
    output :func:`flash_decode` gives the token at ``p`` with cache length
    ``p + 1``, when both use the same ``s_block``. Tiles past a query
    block's last position are neither fetched nor computed."""
    b, l, hq, hd = q.shape
    _, hkv, s, _ = k_cache.shape
    assert l <= s, (l, s)
    g = hq // hkv
    s_blk = min(s_block, s)
    assert s % s_blk == 0, (s, s_blk)
    q_blk = min(q_block, l)
    lp = -(-l // q_blk) * q_blk
    # rows (position, head-in-group), grouped per KV head
    qg = q.reshape(b, l, hkv, g, hd).transpose(0, 2, 1, 3, 4)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, lp - l), (0, 0), (0, 0)))
    qg = qg.reshape(b, hkv, lp * g, hd)

    def kv_map(i, h, qi, j):
        return (i, h, jnp.minimum(j, (qi * q_blk + q_blk - 1) // s_blk), 0)

    kv_spec = pl.BlockSpec((None, None, s_blk, hd), kv_map)
    q_spec = pl.BlockSpec(
        (None, None, q_blk * g, hd), lambda i, h, qi, j: (i, h, qi, 0)
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=1.0 / (hd**0.5),
                          s_blk=s_blk, q_blk=q_blk, g=g),
        grid=(b, hkv, lp // q_blk, s // s_blk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((q_blk * g, 1), jnp.float32),
            pltpu.VMEM((q_blk * g, 1), jnp.float32),
            pltpu.VMEM((q_blk * g, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, hkv, lp * g, hd), jnp.float32),
        interpret=interpret,
    )(qg, k_cache, v_cache)
    out = out.reshape(b, hkv, lp, g, hd)[:, :, :l]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, l, hq, hd)
