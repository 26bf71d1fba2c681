"""Compile every Pallas kernel for a TPU v5e at the main path's widths.

Interpret mode accepts any block shape; the chip's compiler does not. These
tests lower each kernel (flash_prefill at a prompt bucket and a full ring) with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology — no chip attached — so a block spec the
chip would refuse fails here. Widths are TinyLlama-1.1B serving with the
amortized IVF head: vocabulary 32000, d 2048, 178 clusters of capacity 544,
8 probes, k = l = 576 (tail buffer 728), 32/4 heads of 64 on a 256-long
bf16 cache, 4 slots; and the latent decode at Moonlight-16B-A3B's widths.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and test workers import every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_fused, flash_decode, fused_estimator
from repro.kernels import ivf_gather_score, pq_lut_score

N, D = 32000, 2048  # vocabulary rows, model width
N_C, CAP, O_CAP = 178, 544, 2000  # IVF geometry of 32000 rows
B, N_PROBE = 8, 8  # queries per dispatch, probed clusters
K, M_CAP = 576, 728  # |S| = |T| and the tail buffer at n = 32000
M_SUB, KSUB = 8, 256  # PQ codes
SLOTS, HQ, HKV, HD, S = 4, 32, 4, 64, 256  # decode attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip can be written to the persistent cache
    # but never read back here; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, I32, BF16, U8 = jnp.float32, jnp.int32, jnp.bfloat16, jnp.uint8


def test_ivf_gather_score_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: ivf_gather_score.ivf_gather_score(*a, interpret=False),
        ((N_C, CAP, D), F32), ((N_C, CAP), I32), ((B, N_PROBE), I32),
        ((B, D), F32),
    )


def test_pq_lut_score_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: pq_lut_score.pq_lut_score(*a, interpret=False),
        ((N_C, CAP, M_SUB), U8), ((B, N_PROBE), I32), ((B, M_SUB, KSUB), F32),
    )


def test_fused_estimator_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: fused_estimator.fused_estimator(*a, interpret=False),
        ((N, D), F32), ((B, 2 * K), I32), ((B, D), F32), ((B, 2 * K), F32),
    )


def test_flash_decode_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: flash_decode.flash_decode(*a, interpret=False),
        ((SLOTS, HQ, HD), BF16), ((SLOTS, HKV, S, HD), BF16),
        ((SLOTS, HKV, S, HD), BF16), ((SLOTS,), I32),
    )


def test_flash_decode_latent_compiles(one_chip):
    """MLA's absorbed decode at Moonlight-16B-A3B's widths: 16 query heads
    on one latent "head" 640 wide (512 + 64 zero-padded to 128s), values
    its first 512 columns, 64 slots of a 2048-long bf16 ring."""
    _compile(
        one_chip,
        lambda q, c, n: flash_decode.flash_decode(
            q, c, None, n, interpret=False, v_width=512, scale=192**-0.5),
        ((64, 16, 640), jnp.bfloat16),
        ((64, 1, 2048, 640), jnp.bfloat16),
        ((64,), jnp.int32),
    )


@pytest.mark.parametrize("length", [32, S])
def test_flash_prefill_compiles(one_chip, length):
    _compile(
        one_chip,
        lambda *a: flash_decode.flash_prefill(*a, interpret=False),
        ((SLOTS, length, HQ, HD), BF16), ((SLOTS, HKV, S, HD), BF16),
        ((SLOTS, HKV, S, HD), BF16),
    )


def test_ivf_screen_select_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: decode_fused.ivf_screen_select(*a, k=K, interpret=False),
        ((N_C, CAP, D), F32), ((N_C, CAP), I32), ((B, O_CAP), F32),
        ((O_CAP,), I32), ((B, N_PROBE), I32), ((B, D), F32),
    )


def test_pq_screen_select_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: decode_fused.pq_screen_select(
            *a, r=2 * K, interpret=False
        ),
        ((N_C, CAP, M_SUB), U8), ((N_C, CAP), I32), ((B, N_PROBE), F32),
        ((B, O_CAP), F32), ((O_CAP,), I32), ((B, N_PROBE), I32),
        ((B, M_SUB, KSUB), F32),
    )


def test_rerank_select_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: decode_fused.rerank_select(*a, k=K, interpret=False),
        ((N, D), F32), ((B, 2 * K), I32), ((B, 2 * K), F32), ((B, D), F32),
    )


def test_tail_gather_argmax_compiles(one_chip):
    _compile(
        one_chip,
        lambda *a: decode_fused.tail_gather_argmax(*a, interpret=False),
        ((N, D), F32), ((B, M_CAP), I32), ((B,), I32), ((B, K), F32),
        ((B, K), I32), ((B, M_CAP), F32), ((B, D), F32),
    )

