"""Compressed gradient collectives: int8 stochastic-rounding ring all-reduce.

A shard_map-level replacement for ``psum`` on the data axis: a
reduce-scatter + all-gather ring built from ``lax.ppermute`` where every
hop's payload is int8-quantized with a per-chunk fp32 scale — 4x fewer
collective bytes than fp32 psum (2x vs bf16), at the cost of quantization
noise bounded by stochastic rounding (unbiased). Accumulation happens in
fp32 *between* hops, so error grows O(sqrt(P)) not O(P).

Used by the trainer when ``TrainConfig.compress_grads`` is set; validated
against exact psum in tests/test_compress.py on a host-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["quantize", "dequantize", "ring_allreduce_int8"]


def quantize(x: jax.Array, key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """fp -> (int8, scale) with stochastic rounding (unbiased)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-30
    y = xf / scale
    noise = jax.random.uniform(key, y.shape) - 0.5
    q = jnp.clip(jnp.round(y + noise), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ring_allreduce_int8(
    x: jax.Array, axis_name: str, key: jax.Array
) -> jax.Array:
    """All-reduce (sum) of x over `axis_name` with int8-quantized hops.

    Must be called inside shard_map. x: (n,) fp array, n divisible by the
    axis size. Returns the summed result (fp32).
    """
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    n = x.shape[0]
    assert n % p == 0, (n, p)
    chunks = x.astype(jnp.float32).reshape(p, n // p)
    fwd = [(i, (i + 1) % p) for i in range(p)]

    # --- reduce-scatter: after p-1 hops, shard i holds sum of chunk (i+1)%p
    acc = chunks
    for step in range(p - 1):
        send_idx = (idx - step) % p
        payload = jnp.take(acc, send_idx, axis=0)
        kq = jax.random.fold_in(key, step)
        q, s = quantize(payload, kq)
        q_r = jax.lax.ppermute(q, axis_name, fwd)
        s_r = jax.lax.ppermute(s, axis_name, fwd)
        recv_idx = (idx - step - 1) % p
        acc = acc.at[recv_idx].add(dequantize(q_r, s_r))

    own = (idx + 1) % p  # chunk this shard fully reduced
    mine = jnp.take(acc, own, axis=0)

    # --- all-gather: quantize the reduced chunk ONCE and circulate the same
    # int8 payload (no re-quantization => no compounding error)
    out = jnp.zeros_like(chunks)
    out = out.at[own].set(mine)
    kq = jax.random.fold_in(key, 1000)
    q, s = quantize(mine, kq)
    for step in range(p - 1):
        q = jax.lax.ppermute(q, axis_name, fwd)
        s = jax.lax.ppermute(s, axis_name, fwd)
        src = (own - step - 1) % p  # chunk id that just arrived
        out = out.at[src].set(dequantize(q, s))
    return out.reshape(n)
