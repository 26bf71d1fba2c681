"""Loop-aware HLO cost model vs known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import collective_bytes, peaks


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_matmul_flops_exact():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, None, length=10)
        return c @ w

    comp = _compile(
        f,
        jax.ShapeDtypeStruct((128, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 128), jnp.float32),
    )
    cost = analyze_hlo(comp.as_text())
    expect = 2 * 128**3 * 11
    assert abs(cost.flops - expect) / expect < 1e-6
    assert 10 in [int(t) for t in cost.while_trips]


def test_nested_scan_flops():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        c, _ = jax.lax.scan(outer, x, None, length=4)
        return c

    comp = _compile(
        f,
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
    )
    cost = analyze_hlo(comp.as_text())
    expect = 2 * 64**3 * 12
    assert abs(cost.flops - expect) / expect < 1e-6


def test_batched_dot_contraction_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    comp = _compile(
        f,
        jax.ShapeDtypeStruct((4, 32, 48), jnp.float32),
        jax.ShapeDtypeStruct((4, 48, 16), jnp.float32),
    )
    cost = analyze_hlo(comp.as_text())
    expect = 2 * 4 * 32 * 48 * 16
    assert abs(cost.flops - expect) / expect < 1e-6


def test_hbm_bytes_lower_bounded_by_io():
    n = 1 << 20

    def f(x):
        return x * 2.0

    comp = _compile(f, jax.ShapeDtypeStruct((n,), jnp.float32))
    cost = analyze_hlo(comp.as_text())
    assert cost.hbm_bytes >= 2 * 4 * n  # read + write


def test_collective_bytes_regex_fallback():
    hlo = (
        "  %all-gather = f32[256,128]{1,0} all-gather(%p), channel_id=1, "
        "replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}\n"
        "  %ar = bf16[64]{0} all-reduce(%q), replica_groups={{0,1,2,3}}\n"
    )
    got = collective_bytes(hlo)
    assert got["counts"] == {"all-gather": 1, "all-reduce": 1}
    # ag operand = result/2 = 64KB; ar operand = 128B
    assert abs(got["total"] - (256 * 128 * 4 / 2 + 64 * 2)) < 1


def test_peaks_keyed_by_device_kind():
    """One table of published peaks; a kind it does not hold is an error,
    never a default."""
    v5e = peaks("TPU v5 lite")
    assert v5e["peak_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
