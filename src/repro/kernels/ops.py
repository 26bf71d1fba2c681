"""Jit'd dispatch wrappers for the Pallas kernels.

Interpret-mode resolution is LAZY: the module-level ``INTERPRET`` defaults
to ``None``, meaning "decide per call from the live backend"
(``jax.default_backend() != 'tpu'``). The old behavior froze the decision
at import time, so a launch script or test that initialized its backend
*after* importing this module (distributed init, forced host-platform
device counts, backend-flipping tests) could silently run interpreted
kernels on a real TPU. Set ``repro.kernels.ops.INTERPRET = True/False`` to
pin the mode explicitly (e.g. interpreter-on-TPU for debugging).

``OPAQUE_STUBS`` (benchmark-only, see ``benchmarks/decode_fused.py``):
when True, every wrapper returns an opaque ``jax.pure_callback`` of the
correct output shapes instead of calling its kernel. Each kernel site then
survives CPU compilation as exactly one custom-call in the optimized HLO,
which lets the dispatch-count analysis compare fused vs unfused decode
graphs *as they would dispatch on TPU* without needing Mosaic lowering.
Stubbed graphs are for HLO inspection only — never execute them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels import decode_fused as _decode_fused
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.flash_decode import flash_prefill as _flash_prefill
from repro.kernels.fused_estimator import fused_estimator as _fused_estimator
from repro.kernels.ivf_gather_score import ivf_gather_score as _ivf_gather_score
from repro.kernels.pq_lut_score import pq_lut_score as _pq_lut_score

INTERPRET: bool | None = None
OPAQUE_STUBS: bool = False

__all__ = [
    "ivf_gather_score",
    "pq_lut_score",
    "fused_estimator",
    "flash_decode",
    "flash_prefill",
    "ivf_screen_select",
    "pq_screen_select",
    "rerank_select",
    "tail_gather_argmax",
    "INTERPRET",
    "resolve_interpret",
]


def resolve_interpret() -> bool:
    """Per-call interpret decision: the pinned override if set, else
    interpret everywhere but on a real TPU backend."""
    if INTERPRET is not None:
        return INTERPRET
    return jax.default_backend() != "tpu"


def _stub(tag: str, out_shape, *args):
    """One opaque dispatch site standing in for a Pallas kernel while the
    decode-fused benchmark counts optimized-HLO instructions."""
    def cb(*_):
        return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), out_shape)

    return jax.pure_callback(cb, out_shape, *args, vmap_method="sequential")


def ivf_gather_score(
    member_vecs: jax.Array,
    member_ids: jax.Array,
    probe: jax.Array,
    q: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Returns (scores (b, np*cap), ids (b, np*cap)) for the IVF probe.

    The member-id gather rides the kernel's scalar-prefetch path (one DMA
    per probed cluster) instead of a separate XLA gather — see
    :mod:`repro.kernels.ivf_gather_score`.
    """
    b, n_probe = probe.shape
    cap = member_vecs.shape[1]
    if OPAQUE_STUBS:
        scores, ids = _stub(
            "ivf_gather_score",
            (
                jax.ShapeDtypeStruct((b, n_probe, cap), jnp.float32),
                jax.ShapeDtypeStruct((b, n_probe, cap), jnp.int32),
            ),
            member_vecs, member_ids, probe, q,
        )
    else:
        scores, ids = _ivf_gather_score(
            member_vecs, member_ids, probe, q, interpret=resolve_interpret()
        )
    return scores.reshape(b, -1), ids.reshape(b, -1)


def pq_lut_score(
    member_codes: jax.Array, probe: jax.Array, lut: jax.Array
) -> jax.Array:
    """Returns LUT screening scores (b, n_probe, cap) for the IVF-PQ probe."""
    if OPAQUE_STUBS:
        b, n_probe = probe.shape
        cap = member_codes.shape[1]
        return _stub(
            "pq_lut_score",
            jax.ShapeDtypeStruct((b, n_probe, cap), jnp.float32),
            member_codes, probe, lut,
        )
    return _pq_lut_score(member_codes, probe, lut, interpret=resolve_interpret())


def fused_estimator(emb, ids, h, log_w):
    if OPAQUE_STUBS:
        t = ids.shape[0]
        d = emb.shape[1]
        return _stub(
            "fused_estimator",
            (
                jax.ShapeDtypeStruct((t,), jnp.float32),
                jax.ShapeDtypeStruct((t, d), jnp.float32),
            ),
            emb, ids, h, log_w,
        )
    return _fused_estimator(emb, ids, h, log_w, interpret=resolve_interpret())


def flash_decode(q, k_cache, v_cache, lengths, *, s_block: int = 512):
    if OPAQUE_STUBS:
        return _stub(
            "flash_decode",
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            q, k_cache, v_cache, lengths,
        )
    return _flash_decode(
        q, k_cache, v_cache, lengths, s_block=s_block,
        interpret=resolve_interpret(),
    )


def flash_prefill(q, k_cache, v_cache, *, s_block: int = 512):
    if OPAQUE_STUBS:
        return _stub(
            "flash_prefill",
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            q, k_cache, v_cache,
        )
    return _flash_prefill(
        q, k_cache, v_cache, s_block=s_block, interpret=resolve_interpret(),
    )


# --------------------------------------------------------------------------
# fused decode step (see repro/kernels/decode_fused.py)
# --------------------------------------------------------------------------
def ivf_screen_select(
    member_vecs, member_ids, overflow_scores, overflow_ids, probe, q,
    *, k: int, probe_width=None,
) -> tuple[jax.Array, jax.Array]:
    """Fused IVF gather-score + pool top-k -> (values (b,k), ids (b,k)).

    ``probe_width`` ((b,) int32, optional): adaptive per-row live probe
    prefix — stages past it are masked inside the kernel."""
    if OPAQUE_STUBS:
        b = probe.shape[0]
        return _stub(
            "ivf_screen_select",
            (
                jax.ShapeDtypeStruct((b, k), jnp.float32),
                jax.ShapeDtypeStruct((b, k), jnp.int32),
            ),
            member_vecs, member_ids, overflow_scores, overflow_ids, probe, q,
        )
    return _decode_fused.ivf_screen_select(
        member_vecs, member_ids, overflow_scores, overflow_ids, probe, q,
        probe_width, k=k, interpret=resolve_interpret(),
    )


def pq_screen_select(
    member_codes, member_ids, coarse, overflow_scores, overflow_ids, probe,
    lut, *, r: int, probe_width=None,
) -> tuple[jax.Array, jax.Array]:
    """Fused IVF-PQ LUT screen + pool top-r -> (values (b,r), ids (b,r)).

    ``probe_width`` ((b,) int32, optional): adaptive per-row live probe
    prefix — stages past it are masked inside the kernel."""
    if OPAQUE_STUBS:
        b = probe.shape[0]
        return _stub(
            "pq_screen_select",
            (
                jax.ShapeDtypeStruct((b, r), jnp.float32),
                jax.ShapeDtypeStruct((b, r), jnp.int32),
            ),
            member_codes, member_ids, coarse, overflow_scores, overflow_ids,
            probe, lut,
        )
    return _decode_fused.pq_screen_select(
        member_codes, member_ids, coarse, overflow_scores, overflow_ids,
        probe, lut, probe_width, r=r, interpret=resolve_interpret(),
    )


def rerank_select(db, cand, lut_vals, q, *, k: int):
    """Fused exact re-rank of screening survivors -> (values, ids) (b,k)."""
    if OPAQUE_STUBS:
        b = cand.shape[0]
        return _stub(
            "rerank_select",
            (
                jax.ShapeDtypeStruct((b, k), jnp.float32),
                jax.ShapeDtypeStruct((b, k), jnp.int32),
            ),
            db, cand, lut_vals, q,
        )
    return _decode_fused.rerank_select(
        db, cand, lut_vals, q, k=k, interpret=resolve_interpret()
    )


def tail_gather_argmax(emb, pos, m_used, pert_s, s_ids, heights, h):
    """Fused lazy-Gumbel tail gather + argmax -> (index (t,), max_val (t,))."""
    if OPAQUE_STUBS:
        t = pos.shape[0]
        return _stub(
            "tail_gather_argmax",
            (
                jax.ShapeDtypeStruct((t,), jnp.int32),
                jax.ShapeDtypeStruct((t,), jnp.float32),
            ),
            emb, pos, m_used, pert_s, s_ids, heights, h,
        )
    return _decode_fused.tail_gather_argmax(
        emb, pos, m_used, pert_s, s_ids, heights, h,
        interpret=resolve_interpret(),
    )
