"""Pallas TPU kernel family: single-dispatch fused decode step.

The decode hot path used to splinter the per-token head sample across four
kernels plus XLA glue (probe gather-score → candidate pool top-k → tail
gather → Gumbel argmax), round-tripping the ``(b, n_probe·cap)`` candidate
pool and the ``(m_cap, d)`` tail gather through HBM between every stage.
This module fuses the stages into a tile pipeline of (at most) two
dispatches per token batch, keeping scores/ids in VMEM end to end:

* :func:`ivf_screen_select` — IVF fp gather-score **and** pool top-k in one
  kernel: per-probe cluster tiles are DMA'd by the scalar-prefetched probe
  ids (exactly :mod:`repro.kernels.ivf_gather_score`'s accumulation, so the
  scores are bit-identical), accumulated into a persistent
  ``(n_probe, cap)`` VMEM pool, and on the last grid step the pool +
  overflow scores are masked and reduced to the top-k — the pool never
  reaches HBM.
* :func:`pq_screen_select` — the IVF-PQ analogue: LUT screen via the shared
  :func:`repro.kernels.pq_lut_score.lut_tile_scores` tile scorer (+ coarse
  centroid term), pooled in VMEM, reduced to the top-r screening survivors.
* :func:`rerank_select` — exact re-rank of the top-r survivors: db rows are
  DMA'd by the scalar-prefetched candidate ids
  (:mod:`repro.kernels.row_gather`) into an ``(r, d)`` VMEM tile, scored
  with one f32 matvec, and reduced to the top-k — the ``(b, r, d)`` gather
  never exists in HBM.
* :func:`tail_gather_argmax` — the lazy-Gumbel finish (paper Algorithm 2):
  tail rows at the Poissonized complement positions are DMA'd into an
  ``(m_cap, d)`` VMEM tile, scored with one f32 matvec, perturbed with the
  precomputed heights, concatenated with the perturbed top-k stratum, and
  arg-maxed — returning the winning id and perturbed value (the
  certificate's ``max_val``) per token.

Bitwise parity contract
-----------------------
Every stage replicates the *same floating-point program* as the unfused
kernel path: identical tile shapes and accumulation order for the screen
(init-at-zero + per-``d_block`` f32 dot accumulate), identical one-matvec
scoring for re-rank/tail (the unfused path's per-token gemv), and a top-k
extraction whose tie-break (lower index first) matches ``jax.lax.top_k``.
All jax.random draws (Gumbel, Poisson, complement positions, Exp heights)
stay in XLA glue between dispatches, keyed identically to the unfused
path — randomness is a function of (key, shape, distribution) only, so the
fused sampler is bit-for-bit the unfused sampler. Asserted in
``tests/test_decode_fused.py`` and in ``benchmarks/decode_fused.py``.

TPU tiling: every block's last two dims are the array's own or (8, 128)-
aligned, so per-row operands carry a unit middle axis (``(b, 1, k)``) and
all in-kernel values are 2-D, lane-major rows; the extraction below finds
each pick with masked reductions instead of dynamic vector indexing.

Top-k extraction invariant: every pool construction here guarantees
``score == -inf  ⟺  slot is dead`` (dead index slots carry id -1 and are
masked; live members have finite dots/LUT sums). The extractor therefore
emits id -1 for any -inf pick, which reproduces ``lax.top_k`` +
``take_along_axis`` over a pool whose dead slots already hold id -1 — even
when the extraction loop re-picks an exhausted slot (pool smaller than k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ivf_gather_score import member_tile_scores
from repro.kernels.pq_lut_score import lut_tile_scores
from repro.kernels.row_gather import gather_rows, pad_rows, stage_shape

__all__ = [
    "ivf_screen_select",
    "pq_screen_select",
    "rerank_select",
    "tail_gather_argmax",
]

_NEG_INF = float("-inf")  # python float: jnp constants become kernel consts
_BIG = 2**31 - 1
_HI = jax.lax.Precision.HIGHEST


def _max2(x):
    return jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _min2(x):
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _sum2(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _flat_slots(pieces):
    """Pool position of every element of the 2-D ``(vals, ids)`` pieces,
    taken in order and row-major — the index of their concatenation."""
    slots, off = [], 0
    for v, _ in pieces:
        r, c = v.shape
        slots.append(
            off
            + jax.lax.broadcasted_iota(jnp.int32, (r, c), 0) * c
            + jax.lax.broadcasted_iota(jnp.int32, (r, c), 1)
        )
        off += r * c
    return slots


def _argmax_pick(pieces, slots):
    """(top value, its id, its pool slot), each (1, 1), over 2-D
    ``(vals, ids)`` pieces: the first pool slot holding the maximum —
    ``jnp.argmax``'s tie-break."""
    top = functools.reduce(jnp.maximum, [_max2(v) for v, _ in pieces])
    pos = functools.reduce(jnp.minimum, [
        _min2(jnp.where(v == top, s, _BIG)) for (v, _), s in zip(pieces, slots)
    ])
    pick = sum(
        _sum2(jnp.where(s == pos, i, 0)) for (_, i), s in zip(pieces, slots)
    )
    return top, pick, pos


def _emit_topk(pieces, vals_ref, ids_ref):
    """Reduce a pool, given as 2-D ``(vals, ids)`` pieces in pool order, to
    its top-k, written to ``(1, k)`` refs.

    Iterative argmax extraction: the first pool slot holding the maximum
    per round matches ``jax.lax.top_k``'s lower-index-first tie-break;
    extracted slots are burned to -inf. Emits id -1 for -inf picks (see
    module docstring).
    """
    k = vals_ref.shape[-1]
    ids = [i for _, i in pieces]
    slots = _flat_slots(pieces)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(n, carry):
        vals, out_v, out_i = carry
        top, pick, pos = _argmax_pick(list(zip(vals, ids)), slots)
        emit = jnp.where(top == _NEG_INF, -1, pick)
        vals = tuple(
            jnp.where(s == pos, _NEG_INF, v) for v, s in zip(vals, slots)
        )
        return (
            vals,
            jnp.where(lane == n, top, out_v),
            jnp.where(lane == n, emit, out_i),
        )

    _, out_v, out_i = jax.lax.fori_loop(
        0, k, body,
        (
            tuple(v for v, _ in pieces),
            jnp.zeros((1, k), jnp.float32),
            jnp.zeros((1, k), jnp.int32),
        ),
    )
    vals_ref[...] = out_v
    ids_ref[...] = out_i


def _pad_cols(x, n, value):
    """Right-pad the last axis of ``x`` to ``n`` with ``value``."""
    extra = n - x.shape[-1]
    if not extra:
        return x
    return jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, extra)], constant_values=value
    )


def _row_count(n: int) -> int:
    """Rows a gather kernel stages: ``n`` rounded up to 16."""
    return -(-n // 16) * 16


# --------------------------------------------------------------------------
# IVF: fused gather-score + pool top-k
# --------------------------------------------------------------------------
def _ivf_screen_kernel(
    probe_ref, width_ref, mv_ref, mid_ref, os_ref, oid_ref, q_ref,
    vals_ref, ids_ref, pool_vals, pool_ids, *, interpret,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    dk = pl.program_id(2)
    n_probe = pl.num_programs(1)
    n_dk = pl.num_programs(2)
    cap = pool_vals.shape[1]
    row = pl.ds(j, 1)

    # Stages past this row's probe width are dead: their cluster tile DMA is
    # elided by the clamped index map (block index repeats => Pallas skips
    # the re-fetch) and the MXU accumulate is skipped here. The pool rows
    # they leave uninitialized are masked out at select.
    @pl.when(j < width_ref[i])
    def _accumulate():
        @pl.when(dk == 0)
        def _init():
            pool_vals[row, :] = jnp.zeros((1, cap), jnp.float32)
            pool_ids[row, :] = mid_ref[...]

        pool_vals[row, :] += member_tile_scores(mv_ref[...], q_ref[...],
                                                interpret=interpret)

    @pl.when((j == n_probe - 1) & (dk == n_dk - 1))
    def _select():
        _select_pool(width_ref[i], pool_vals, pool_ids, os_ref, oid_ref,
                     vals_ref, ids_ref)


def _select_pool(width, pool_vals, pool_ids, os_ref, oid_ref, vals_ref,
                 ids_ref):
    """Top-k of the live probed pool rows ∪ overflow (dead ⟺ id -1)."""
    live = (
        jax.lax.broadcasted_iota(jnp.int32, pool_vals.shape, 0) < width
    )
    pids = jnp.where(live, pool_ids[...], -1)
    pvals = jnp.where(pids >= 0, pool_vals[...], _NEG_INF)
    oids = oid_ref[...]
    ovals = jnp.where(oids >= 0, os_ref[...], _NEG_INF)
    _emit_topk([(pvals, pids), (ovals, oids)], vals_ref, ids_ref)


def _clamped_probe(i, j, probe, width):
    """Probe id for (row i, stage j), clamped to the row's live width so
    dead stages re-request the previous block (Pallas skips the DMA)."""
    return probe[i, jnp.maximum(jnp.minimum(j, width[i] - 1), 0)]


@functools.partial(jax.jit, static_argnames=("k", "d_block", "interpret"))
def ivf_screen_select(
    member_vecs: jax.Array,  # (n_c, cap, d)
    member_ids: jax.Array,  # (n_c, cap) int32 (-1 = dead slot)
    overflow_scores: jax.Array,  # (b, o_cap) f32, precomputed in XLA glue
    overflow_ids: jax.Array,  # (o_cap,) int32 (-1 = dead slot)
    probe: jax.Array,  # (b, n_probe) int32 cluster ids
    q: jax.Array,  # (b, d)
    probe_width: jax.Array | None = None,  # (b,) int32 live probe prefix
    *,
    k: int,
    d_block: int = 512,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (values (b, k) f32, ids (b, k) i32): top-k of the probed
    member pool ∪ overflow, without materializing the pool in HBM.

    ``probe_width`` (adaptive probe, core/mips/adaptive.py) restricts row i
    to its first ``probe_width[i]`` probe entries: stages beyond it cost
    neither HBM reads (clamped index map) nor MXU work (``pl.when`` gate).
    ``None`` means full width, which leaves the kernel program identical to
    the fixed-width one."""
    n_c, cap, d = member_vecs.shape
    b, n_probe = probe.shape
    o_cap = overflow_ids.shape[0]
    d_blk = min(d_block, d)
    assert d % d_blk == 0, (d, d_blk)
    grid = (b, n_probe, d // d_blk)
    if probe_width is None:
        probe_width = jnp.full((b,), n_probe, jnp.int32)
    out = pl.BlockSpec((None, 1, k), lambda i, j, dk, probe, width: (i, 0, 0))

    vals, ids = pl.pallas_call(
        functools.partial(_ivf_screen_kernel, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (None, cap, d_blk),
                    lambda i, j, dk, probe, width: (
                        _clamped_probe(i, j, probe, width), 0, dk
                    ),
                ),
                pl.BlockSpec(
                    (None, 1, cap),
                    lambda i, j, dk, probe, width: (
                        _clamped_probe(i, j, probe, width), 0, 0
                    ),
                ),
                pl.BlockSpec(
                    (None, 1, o_cap), lambda i, j, dk, probe, width: (i, 0, 0)
                ),
                pl.BlockSpec((1, o_cap), lambda i, j, dk, probe, width: (0, 0)),
                pl.BlockSpec(
                    (None, 1, d_blk), lambda i, j, dk, probe, width: (i, 0, dk)
                ),
            ],
            out_specs=[out, out],
            scratch_shapes=[
                pltpu.VMEM((n_probe, cap), jnp.float32),
                pltpu.VMEM((n_probe, cap), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(
        probe.astype(jnp.int32),
        probe_width.astype(jnp.int32),
        member_vecs,
        member_ids.astype(jnp.int32).reshape(n_c, 1, cap),
        overflow_scores.astype(jnp.float32).reshape(b, 1, o_cap),
        overflow_ids.astype(jnp.int32).reshape(1, o_cap),
        q.reshape(b, 1, d),
    )
    return vals[:, 0], ids[:, 0]


# --------------------------------------------------------------------------
# IVF-PQ: fused LUT screen + pool top-r
# --------------------------------------------------------------------------
def _pq_screen_kernel(
    probe_ref, width_ref, codes_ref, mid_ref, coarse_ref, os_ref, oid_ref,
    lut_ref, vals_ref, ids_ref, pool_vals, pool_ids,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    n_probe = pl.num_programs(1)
    row = pl.ds(j, 1)

    @pl.when(j < width_ref[i])
    def _screen():
        coarse = coarse_ref[...]  # (1, n_probe)
        lane = jax.lax.broadcasted_iota(jnp.int32, coarse.shape, 1)
        c_j = jnp.max(  # exact extraction of coarse[j]
            jnp.where(lane == j, coarse, _NEG_INF), axis=1, keepdims=True
        )
        pool_vals[row, :] = lut_tile_scores(codes_ref[...], lut_ref[...]) + c_j
        pool_ids[row, :] = mid_ref[...]

    @pl.when(j == n_probe - 1)
    def _select():
        _select_pool(width_ref[i], pool_vals, pool_ids, os_ref, oid_ref,
                     vals_ref, ids_ref)


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def pq_screen_select(
    member_codes: jax.Array,  # (n_c, cap, m_sub) uint8
    member_ids: jax.Array,  # (n_c, cap) int32 (-1 = dead slot)
    coarse: jax.Array,  # (b, n_probe) f32 centroid scores of probed clusters
    overflow_scores: jax.Array,  # (b, o_cap) f32 EXACT scores (XLA glue)
    overflow_ids: jax.Array,  # (o_cap,) int32 (-1 = dead slot)
    probe: jax.Array,  # (b, n_probe) int32 cluster ids
    lut: jax.Array,  # (b, m_sub, ksub) f32 per-query codeword tables
    probe_width: jax.Array | None = None,  # (b,) int32 live probe prefix
    *,
    r: int,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (values (b, r) f32, ids (b, r) i32): top-r LUT screening
    survivors of the probed pool ∪ overflow (ADC score = LUT sum + coarse
    centroid term), without materializing the pool in HBM. ``probe_width``
    masks stages past the per-row adaptive width (see
    :func:`ivf_screen_select`); ``None`` means full width."""
    n_c, cap, m_sub = member_codes.shape
    b, n_probe = probe.shape
    o_cap = overflow_ids.shape[0]
    ksub = lut.shape[2]
    assert lut.shape[1] == m_sub, (lut.shape, m_sub)
    grid = (b, n_probe)
    if probe_width is None:
        probe_width = jnp.full((b,), n_probe, jnp.int32)
    per_row = lambda n: pl.BlockSpec(  # noqa: E731
        (None, 1, n), lambda i, j, probe, width: (i, 0, 0)
    )

    vals, ids = pl.pallas_call(
        _pq_screen_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (None, cap, m_sub),
                    lambda i, j, probe, width: (
                        _clamped_probe(i, j, probe, width), 0, 0
                    ),
                ),
                pl.BlockSpec(
                    (None, 1, cap),
                    lambda i, j, probe, width: (
                        _clamped_probe(i, j, probe, width), 0, 0
                    ),
                ),
                per_row(n_probe),
                per_row(o_cap),
                pl.BlockSpec((1, o_cap), lambda i, j, probe, width: (0, 0)),
                pl.BlockSpec(
                    (None, m_sub, ksub), lambda i, j, probe, width: (i, 0, 0)
                ),
            ],
            out_specs=[per_row(r), per_row(r)],
            scratch_shapes=[
                pltpu.VMEM((n_probe, cap), jnp.float32),
                pltpu.VMEM((n_probe, cap), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, r), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, r), jnp.int32),
        ],
        interpret=interpret,
    )(
        probe.astype(jnp.int32),
        probe_width.astype(jnp.int32),
        member_codes,
        member_ids.astype(jnp.int32).reshape(n_c, 1, cap),
        coarse.astype(jnp.float32).reshape(b, 1, n_probe),
        overflow_scores.astype(jnp.float32).reshape(b, 1, o_cap),
        overflow_ids.astype(jnp.int32).reshape(1, o_cap),
        lut.astype(jnp.float32),
    )
    return vals[:, 0], ids[:, 0]


# --------------------------------------------------------------------------
# exact re-rank of screening survivors
# --------------------------------------------------------------------------
def _row_scores(q, rows):
    """``(1, d)`` query · ``(n, d)`` rows -> ``(1, n)`` f32: the one-matvec
    scoring of the re-rank and tail kernels."""
    return jax.lax.dot_general(
        q.astype(jnp.float32), rows, (((1,), (1,)), ((), ())),
        precision=_HI, preferred_element_type=jnp.float32,
    )


def _rerank_kernel(
    cand_pref, db_hbm, cand_ref, lv_ref, q_ref, vals_ref, ids_ref,
    stage, rows, sem,
):
    i = pl.program_id(0)
    gather_rows(db_hbm, lambda r: cand_pref[i, r], rows, stage, sem)
    exact = _row_scores(q_ref[...], rows[...])  # (1, r)
    cand = cand_ref[...]
    dead = (cand < 0) | (lv_ref[...] == _NEG_INF)
    _emit_topk([(jnp.where(dead, _NEG_INF, exact), cand)], vals_ref, ids_ref)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def rerank_select(
    db: jax.Array,  # (n, d) full-precision rows
    cand: jax.Array,  # (b, r) int32 screening survivors (-1 = dead)
    lut_vals: jax.Array,  # (b, r) f32 screening scores (-inf = dead)
    q: jax.Array,  # (b, d)
    *,
    k: int,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (values (b, k) f32, ids (b, k) i32): exact re-rank of the
    top-r screening survivors, rows streamed by scalar-prefetched ids."""
    n, d = db.shape
    b, r = cand.shape
    r_pad = _row_count(r)
    # padded survivors are dead (-1 id, -inf screen score)
    cand = _pad_cols(cand.astype(jnp.int32), r_pad, -1)
    lut_vals = _pad_cols(lut_vals.astype(jnp.float32), r_pad, _NEG_INF)
    per_row = lambda n: pl.BlockSpec(  # noqa: E731
        (None, 1, n), lambda i, cand: (i, 0, 0)
    )

    vals, ids = pl.pallas_call(
        _rerank_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # rows DMA'd by id
                per_row(r_pad),
                per_row(r_pad),
                per_row(d),
            ],
            out_specs=[per_row(k), per_row(k)],
            scratch_shapes=[
                pltpu.VMEM(stage_shape(r_pad, d, db.dtype), db.dtype),
                pltpu.VMEM((r_pad, d), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(
        jnp.maximum(cand, 0),  # prefetch: valid rows only
        pad_rows(db),
        cand.reshape(b, 1, r_pad),
        lut_vals.reshape(b, 1, r_pad),
        q.reshape(b, 1, d),
    )
    return vals[:, 0], ids[:, 0]


# --------------------------------------------------------------------------
# lazy-Gumbel tail gather + perturbed argmax (Algorithm 2 finish)
# --------------------------------------------------------------------------
def _tail_kernel(
    pos_pref, mu_ref, emb_hbm, ps_ref, sid_ref, hei_ref, pos_ref, h_ref,
    idx_ref, max_ref, stage, rows, sem,
):
    i = pl.program_id(0)
    gather_rows(emb_hbm, lambda r: pos_pref[i, r], rows, stage, sem)
    # one (m_cap, d) · (d,) f32 matvec — the unfused path's per-token
    # score_fn gemv, same shape, same reduction order
    y_tail = _row_scores(h_ref[...], rows[...])  # (1, m_pad)
    live = jax.lax.broadcasted_iota(jnp.int32, y_tail.shape, 1) < mu_ref[i]
    pert_t = jnp.where(live, y_tail + hei_ref[...], _NEG_INF)
    pieces = [(ps_ref[...], sid_ref[...]), (pert_t, pos_ref[...])]
    top, pick, _ = _argmax_pick(pieces, _flat_slots(pieces))
    idx_ref[...] = pick
    max_ref[...] = top


@functools.partial(jax.jit, static_argnames=("interpret",))
def tail_gather_argmax(
    emb: jax.Array,  # (n, d) local feature table
    pos: jax.Array,  # (t, m_cap) int32 tail positions (already clamped)
    m_used: jax.Array,  # (t,) int32 live tail count
    pert_s: jax.Array,  # (t, k) f32 perturbed top-k stratum (-inf = dead)
    s_ids: jax.Array,  # (t, k) int32 sanitized top-k ids
    heights: jax.Array,  # (t, m_cap) f32 truncated-Gumbel heights B+Exp(1)
    h: jax.Array,  # (t, d) queries
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (index (t,) i32, max_val (t,) f32): the Algorithm-2 winner
    over S ∪ tail and its perturbed value (the certificate's max_val), tail
    rows streamed by scalar-prefetched positions — the (t, m_cap, d) gather
    never exists in HBM."""
    n, d = emb.shape
    t, m_cap = pos.shape
    k = s_ids.shape[1]
    m_pad = _row_count(m_cap)
    # padded tail slots sit past every m_used: never live
    pos = _pad_cols(pos.astype(jnp.int32), m_pad, 0)
    heights = _pad_cols(heights.astype(jnp.float32), m_pad, 0.0)
    per_row = lambda n: pl.BlockSpec(  # noqa: E731
        (None, 1, n), lambda i, pos, mu: (i, 0, 0)
    )

    idx, max_val = pl.pallas_call(
        _tail_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # rows DMA'd by position
                per_row(k),
                per_row(k),
                per_row(m_pad),
                per_row(m_pad),
                per_row(d),
            ],
            out_specs=[per_row(1), per_row(1)],
            scratch_shapes=[
                pltpu.VMEM(stage_shape(m_pad, d, emb.dtype), emb.dtype),
                pltpu.VMEM((m_pad, d), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((t, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        pos,
        m_used.astype(jnp.int32),
        pad_rows(emb),
        pert_s.astype(jnp.float32).reshape(t, 1, k),
        s_ids.astype(jnp.int32).reshape(t, 1, k),
        heights.reshape(t, 1, m_pad),
        pos.reshape(t, 1, m_pad),
        h.reshape(t, 1, d),
    )
    return idx[:, 0, 0], max_val[:, 0, 0]
