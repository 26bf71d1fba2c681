"""Attention: GQA with causal / sliding-window / prefix / bidirectional
masks, blockwise (flash-style) training path, and ring-buffer KV-cache
decode backed by the flash_decode Pallas kernel.

The training path streams KV in blocks with an online softmax (running max,
denominator, accumulator) inside ``lax.scan``, with an outer ``lax.map``
over query blocks — the (L, L) score matrix never materializes, which is
what lets 32k-token prefill compile within HBM budgets. Sliding-window
layers slice only the ``window + q_block`` KV span per query block, making
SWA genuinely sub-quadratic (not just masked).

Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434) is the
second attention kind (``cfg.attn_kind == "mla"``). Each position caches
one latent row ``[c_kv | k_pe]``: the RMS-normed down-projection c_kv
and one rotary key shared by every head (zero-padded to a multiple of
128 wide, the width the TPU stores it at). Training and prefill attend in
the expanded form: per-head keys ``[c_kv·W_UK,h | k_pe]`` and values
``c_kv·W_UV,h``, whose widths differ. Decode attends in the absorbed
form: the query folds ``W_UK,h`` in (``q̃_h = W_UK,h·q_nope_h``), the
scores are ``[q̃_h | q_pe_h]·[c_kv | k_pe]``, and the output ``Σ p·c_kv``
goes through ``W_UV,h`` — multi-query attention with one KV "head" as
wide as the latent row, whose values are its first ``kv_lora_rank``
columns, read by the same ``flash_decode`` kernel as GQA.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import dense_init, rms_norm, rope

_NEG = -1e30

# Blockwise-attention tile sizes (perf-tunable; see EXPERIMENTS.md §Perf:
# the K/V stream is re-read once per query block, so HBM traffic scales
# with L/Q_BLOCK — larger tiles trade score-buffer size for fewer passes).
Q_BLOCK = 512
KV_BLOCK = 512
# "bf16": store the exp'd probability blocks in bf16 between the two score
# matmuls (the dominant HBM traffic at long context; row-stat accumulators
# m/s stay f32). §Perf iteration 1.
SCORES_DTYPE = "f32"


def init(key, cfg: ArchConfig) -> dict:
    if cfg.attn_kind == "mla":
        return mla_init(key, cfg)
    d = cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, cfg.d_attn)),
        "wk": dense_init(k2, (d, cfg.d_kv)),
        "wv": dense_init(k3, (d, cfg.d_kv)),
        "wo": dense_init(k4, (cfg.d_attn, d)),
    }


def _mask(qpos, kpos, *, causal: bool, window: int, prefix: int):
    """(qb,), (kb,) -> (qb, kb) bool. True = attend."""
    q = qpos[:, None]
    k = kpos[None, :]
    ok = jnp.ones(q.shape[:1] + k.shape[1:], bool)
    if causal:
        ok = k <= q
        if prefix > 0:  # prefix-LM: bidirectional over the first `prefix`
            ok = ok | (k < prefix)
    if window > 0:
        ok = ok & (k > q - window)
    return ok


def _online_block(carry, k_blk, v_blk, q, qpos, kpos, mask_kw, scale):
    """One KV block of the online softmax. q: (B, qb, KV, G, hd).

    SCORES_DTYPE == "bf16" keeps the (qb, kb) score/probability blocks —
    the dominant HBM traffic at long context — in bf16 end to end (row
    statistics m/s and the output accumulator stay f32; the per-element
    softmax-weight error is ~2^-8, the flash-attention-style tradeoff;
    validated in tests/test_models.py::test_attention_scores_dtype).
    """
    m, s, acc = carry
    blk_dt = jnp.bfloat16 if SCORES_DTYPE == "bf16" else jnp.float32
    scores = (
        jnp.einsum(
            "bqKGd,bsKd->bKGqs", q, k_blk, preferred_element_type=blk_dt
        )
        * jnp.asarray(scale, blk_dt)
    )  # (B, KV, G, qb, kb)
    ok = _mask(qpos, kpos, **mask_kw)
    scores = jnp.where(ok[None, None, None], scores, jnp.asarray(_NEG, blk_dt))
    m_new = jnp.maximum(m, scores.max(-1).astype(jnp.float32))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None].astype(blk_dt))  # stays blk_dt
    s_new = s * corr + p.sum(-1, dtype=jnp.float32)
    upd = jnp.einsum(
        "bKGqs,bsKd->bKGqd", p, v_blk.astype(blk_dt),
        preferred_element_type=jnp.float32,
    )
    acc_new = acc * corr[..., None] + upd
    return (m_new, s_new, acc_new)


def blockwise_attention(
    q: jax.Array,  # (B, L, H, hd)
    k: jax.Array,  # (B, L, KV, hd)
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    prefix: int = 0,
    q_block: int | None = None,
    kv_block: int | None = None,
) -> jax.Array:
    q_block = q_block or Q_BLOCK
    kv_block = kv_block or KV_BLOCK
    b, l, h, hd = q.shape
    kvh = k.shape[2]
    hv = v.shape[-1]  # value width: hd, or MLA's narrower values
    g = h // kvh
    qb = min(q_block, l)
    kb = min(kv_block, l)
    assert l % qb == 0 and l % kb == 0, (l, qb, kb)
    scale = 1.0 / (hd**0.5)
    qg = q.reshape(b, l // qb, qb, kvh, g, hd)
    mask_kw = dict(causal=causal, window=window, prefix=prefix)

    span = ((window + qb + kb - 1) // kb) * kb if window > 0 else l
    use_window = 0 < window and span < l  # genuinely sub-quadratic span

    def per_qblock(args):
        qi, q_blk = args  # q_blk: (B, qb, KV, G, hd)
        qpos = qi * qb + jnp.arange(qb)
        if use_window:
            start = jnp.clip((qi + 1) * qb - span, 0, l - span)
            k_loc = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            v_loc = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            kpos0 = start
            nkb = span // kb
        else:
            k_loc, v_loc, kpos0, nkb = k, v, 0, l // kb

        m0 = jnp.full((b, kvh, g, qb), _NEG, jnp.float32)
        s0 = jnp.zeros((b, kvh, g, qb), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, qb, hv), jnp.float32)

        def body(carry, ki):
            k_blk = jax.lax.dynamic_slice_in_dim(k_loc, ki * kb, kb, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v_loc, ki * kb, kb, axis=1)
            kpos = kpos0 + ki * kb + jnp.arange(kb)
            return (
                _online_block(carry, k_blk, v_blk, q_blk, qpos, kpos, mask_kw, scale),
                None,
            )

        (m, s, acc), _ = jax.lax.scan(body, (m0, s0, a0), jnp.arange(nkb))
        out = acc / jnp.maximum(s, 1e-30)[..., None]  # (B, KV, G, qb, hd)
        return jnp.moveaxis(out, 3, 1)  # (B, qb, KV, G, hd)

    # remat per query block: the online-softmax residuals of one block are
    # recomputed during backward instead of saved for all blocks at once
    outs = jax.lax.map(
        jax.checkpoint(per_qblock), (jnp.arange(l // qb), jnp.moveaxis(qg, 1, 0))
    )
    out = jnp.moveaxis(outs, 0, 1).reshape(b, l, h, hv)
    return out.astype(q.dtype)


def forward(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, L, d)
    positions: jax.Array,  # (B, L)
    *,
    window: int | None = None,
    prefix: int = 0,
) -> jax.Array:
    """Training/prefill attention (no cache)."""
    if cfg.attn_kind == "mla":
        return mla_forward(p, cfg, x, positions)
    b, l, d = x.shape
    dt = x.dtype
    win = cfg.window if window is None else window
    q = (x @ p["wq"].astype(dt)).reshape(b, l, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    out = blockwise_attention(
        q, k, v, causal=cfg.causal and not cfg.encoder_only, window=win,
        prefix=prefix,
    )
    return out.reshape(b, l, cfg.d_attn) @ p["wo"].astype(dt)


def _ring_place(rows: dict, s_c: int, lengths, dt) -> dict:
    """Each ``(B, L, KV, w)`` array of ``rows`` as the head-major ring
    ``(B, KV, s_c, w)`` a token-by-token decode of the same prompt would
    leave (see :func:`prefill`)."""
    l = next(iter(rows.values())).shape[1]
    if lengths is not None:
        # per-row ring placement: slot j holds position
        # p = len-1 - ((len-1-j) mod s_c), the newest valid p ≡ j (mod s_c)
        j = jnp.arange(s_c)
        pj = (lengths[:, None] - 1) - ((lengths[:, None] - 1 - j[None]) % s_c)
        live = pj >= 0  # (B, s_c); rows shorter than s_c leave tail slots 0
        pc = jnp.clip(pj, 0, l - 1)[..., None, None]
        rings = {n: jnp.where(live[..., None, None],
                              jnp.take_along_axis(r, pc, axis=1),
                              0).astype(dt) for n, r in rows.items()}
    elif l <= s_c:
        rings = {n: jnp.zeros((r.shape[0], s_c) + r.shape[2:], dt)
                 .at[:, :l].set(r) for n, r in rows.items()}
    else:  # ring buffer: keep the last s_c keys at their ring slots
        kept = jnp.arange(l - s_c, l)
        slots = kept % s_c
        rings = {n: jnp.zeros((r.shape[0], s_c) + r.shape[2:], dt)
                 .at[:, slots].set(r[:, l - s_c:]) for n, r in rows.items()}
    return {n: a.transpose(0, 2, 1, 3) for n, a in rings.items()}


def prefill(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, L, d)
    positions: jax.Array,
    max_seq: int,
    *,
    window: int | None = None,
    prefix: int = 0,
    lengths: jax.Array | None = None,  # (B,) valid prompt lengths
    use_kernel: bool | None = None,  # None: kernel on TPU, XLA on CPU
    mesh=None,  # device mesh the activations are sharded over (kernel path)
) -> tuple[jax.Array, dict]:
    """Forward + KV-cache build. Returns (out, cache).

    ``lengths`` enables right-padded batched prefill (the serving engine's
    chunked admission path): row b's tokens at positions >= lengths[b] are
    pads. Pads never corrupt the cache — each ring slot j is filled from
    the newest VALID position p ≡ j (mod s_c), p < lengths[b] (exactly the
    state a token-by-token decode of the same prompt would leave), and
    slots with no valid position stay zero (masked by the decode-side
    ``lengths`` window anyway). Causality keeps pad queries from affecting
    valid outputs: pads sit strictly after every valid position.

    The cache comes out head-major, ``(B, KV, s_c, hd)`` (see init_cache).

    Where decode runs the flash_decode kernel (``use_kernel``, by default
    on the chip), a causal prompt that fits the ring attends through its
    prompt-side twin, ``flash_prefill``: each position reads only its own
    prefix of the ring built here and gets the output decode computes for
    it, bit for bit. So prefill leaves the cache a token-by-token decode
    would, and the serving engines' tokens agree. Prefix-LM, wrapping
    rings and the CPU take blockwise attention.
    """
    if cfg.attn_kind == "mla":
        return mla_prefill(p, cfg, x, positions, max_seq, lengths=lengths)
    b, l, d = x.shape
    dt = x.dtype
    win = cfg.window if window is None else window
    q = (x @ p["wq"].astype(dt)).reshape(b, l, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    s_c = min(win, max_seq) if win else max_seq
    cache = _ring_place({"k": k, "v": v}, s_c, lengths, dt)
    from repro.kernels import ops as kops

    causal = cfg.causal and not cfg.encoder_only
    if use_kernel is None:
        use_kernel = not kops.resolve_interpret()
    if use_kernel and causal and not prefix and l <= s_c:
        out = _sharded(kops.flash_prefill, mesh, q, cache["k"], cache["v"])
        out = out.astype(dt)
    else:
        out = blockwise_attention(q, k, v, causal=causal, window=win,
                                  prefix=prefix)
    return out.reshape(b, l, cfg.d_attn) @ p["wo"].astype(dt), cache


def init_cache(
    cfg: ArchConfig, batch: int, max_seq: int, dtype, window: int | None = None
) -> dict:
    """Per-slot KV rings, head-major ``(batch, KV, s_c, hd)``: each KV
    head's positions are contiguous, which is how the flash_decode kernel
    reads them, in place.

    ``window`` overrides cfg.window (griffin layers pass local_window) so
    the decode ring size matches what prefill() builds for the same layer —
    and so the ring itself enforces the sliding window at decode time."""
    win = cfg.window if window is None else window
    s_c = min(win, max_seq) if win else max_seq
    if cfg.attn_kind == "mla":  # one latent row a position, no head axis
        return {"ckv": jnp.zeros((batch, s_c, _mla_width(cfg)), dtype)}
    shape = (batch, cfg.n_kv_heads, s_c, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_pool(
    cfg: ArchConfig, n_blocks: int, block_len: int, dtype
) -> dict:
    """Shared paged KV pool: ``n_blocks`` blocks of ``block_len`` positions,
    owned by no slot — a per-slot page table (``pages``, threaded through
    :func:`decode`) maps each slot's ring pages onto physical blocks. One
    physical block id addresses the same block slice in every layer (the
    pool leaf carries the layer-stack axis), so one allocation covers the
    whole trunk. Blocks are head-major, ``(KV, block_len, hd)``, like the
    dense rings (MLA: one latent array of ``(block_len, latent)`` blocks)."""
    if cfg.attn_kind == "mla":
        return {"ckv": jnp.zeros((n_blocks, block_len, _mla_width(cfg)),
                                 dtype)}
    shape = (n_blocks, cfg.n_kv_heads, block_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_bytes_per_slot(cfg: ArchConfig, max_seq: int, dtype,
                         window: int | None = None) -> int:
    """HBM bytes ONE dense slot reserves for this layer's KV ring — the
    quantity the paged pool frees serving from (slot count × this no longer
    has to fit worst-case ``max_seq``)."""
    win = cfg.window if window is None else window
    s_c = min(win, max_seq) if win else max_seq
    per_pos = (_mla_width(cfg) if cfg.attn_kind == "mla"
               else 2 * cfg.n_kv_heads * cfg.head_dim)
    return s_c * per_pos * jnp.dtype(dtype).itemsize


def decode(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, 1, d)
    cache: dict,
    pos: jax.Array,  # (B,) int32 absolute position of the new token
    *,
    window: int | None = None,
    use_kernel: bool | None = None,  # None: kernel on TPU, XLA ref on CPU
    pages: jax.Array | None = None,  # (B, n_pages) physical block per page
    write_mask: jax.Array | None = None,  # (B,) rows allowed to write KV
    mesh=None,  # device mesh the caches are sharded over (kernel path)
) -> tuple[jax.Array, dict]:
    """Single-token decode against a per-slot KV ring OR a paged pool.

    Dense (``pages=None``): ``cache`` leaves are ``(B, KV, s_c, hd)`` rings
    owned by their slot; the new token writes ring slot ``pos % s_c``.

    Paged: ``cache`` leaves are the shared ``(n_blocks, KV, block_len,
    hd)`` pool and ``pages[b, i]`` names the physical block behind slot
    ``b``'s i-th ring page — ring placement becomes page-table arithmetic
    (page ``(pos % s_c) // block_len``, offset ``(pos % s_c) % block_len``
    with ``s_c = n_pages * block_len``). Unallocated pages carry an
    out-of-range sentinel: their writes are dropped by XLA scatter and
    their (clamped-gather) garbage is masked by ``lengths`` before the
    softmax, so the attended view is BITWISE the dense ring. ``write_mask``
    (the engine passes the slot's ``active`` flag) drops retired slots'
    writes — mandatory once blocks are recycled across requests, a no-op
    effect-wise in the dense layout where a frozen slot only ever
    overwrites its own ring row with the identical value.
    """
    b, _, d = x.shape
    dt = x.dtype
    if pages is None:
        s_c = cache["k"].shape[2]
    else:
        n_blocks, block_len = cache["k"].shape[0], cache["k"].shape[2]
        s_c = pages.shape[1] * block_len
    q = (x @ p["wq"].astype(dt)).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"].astype(dt)).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"].astype(dt)).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)
    # ring-buffer write (softmax is permutation-invariant; keys carry RoPE
    # applied at their absolute position, so slot order is irrelevant)
    slot = pos % s_c
    ar = jnp.arange(b)
    if pages is None:
        cache = {
            "k": cache["k"].at[ar, :, slot].set(k[:, 0]),
            "v": cache["v"].at[ar, :, slot].set(v[:, 0]),
        }
        k_view, v_view = cache["k"], cache["v"]
    else:
        phys = jnp.take_along_axis(
            pages, (slot // block_len)[:, None], axis=1
        )[:, 0]
        if write_mask is not None:  # retired slot: block may be reowned
            phys = jnp.where(write_mask, phys, n_blocks)  # OOB -> dropped
        off = slot % block_len
        cache = {
            "k": cache["k"].at[phys, :, off].set(k[:, 0]),
            "v": cache["v"].at[phys, :, off].set(v[:, 0]),
        }

        def view(pool):
            # gather the slot's ring view (sentinel pages clamp; masked
            # below): (B, n_pages, KV, block_len, hd) -> (B, KV, s_c, hd)
            g = pool[pages].transpose(0, 2, 1, 3, 4)
            return g.reshape(b, cfg.n_kv_heads, s_c, cfg.head_dim)

        k_view, v_view = view(cache["k"]), view(cache["v"])
    lengths = jnp.minimum(pos + 1, s_c).astype(jnp.int32)
    from repro.kernels import ops as kops

    if use_kernel is None:
        # interpret-mode Pallas on CPU would skew dry-run cost analysis;
        # the kernel is exercised explicitly by tests/test_kernels.py
        use_kernel = not kops.resolve_interpret()
    if use_kernel:
        o = _sharded(kops.flash_decode, mesh, q[:, 0], k_view, v_view,
                     lengths)
    else:
        from repro.kernels import ref as kref

        o = kref.flash_decode_ref(q[:, 0], k_view, v_view, lengths)
    out = o.astype(dt).reshape(b, 1, cfg.d_attn) @ p["wo"].astype(dt)
    return out, cache


# ------------------------------------------------------------ latent (MLA)
def mla_init(key, cfg: ArchConfig) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, h * (cfg.qk_nope_dim + cfg.qk_rope_dim))),
        "wkv_a": dense_init(k2, (d, cfg.latent_width)),  # W_DKV | W_KR
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": dense_init(k3, (r, h * (cfg.qk_nope_dim + cfg.v_head_dim))),
        "wo": dense_init(k4, (h * cfg.v_head_dim, d)),
    }


def _mla_width(cfg: ArchConfig) -> int:
    """Width of a cached latent row: ``r + rope`` rounded up to 128 with
    zeros. The TPU tiles an array's minor dim in 128s, so 576 values take
    640 in memory either way; stored 576 wide, the array is given a
    transposed layout instead and every decode dispatch copies the whole
    cache into the row-major one the kernel reads."""
    return -(-cfg.latent_width // 128) * 128


def _mla_pad(cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Latent-width rows (..., r + rope) zero-padded to the cached width."""
    pad = _mla_width(cfg) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _mla_scale(cfg: ArchConfig) -> float:
    """Softmax scale: over the query/key head width, nope + rope."""
    return 1.0 / (cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5


def _mla_in(p: dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array):
    """The projections of ``x`` (B, L, d): the query, split as ``q_nope``
    (B, L, H, nope) and RoPE'd ``q_pe`` (B, L, H, rope), and the latent
    rows (B, L, r + rope) the cache holds, ``[RMSNorm(c_kv) | RoPE(k_pe)]``."""
    b, l, _ = x.shape
    dt = x.dtype
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    q = (x @ p["wq"].astype(dt)).reshape(b, l, cfg.n_heads, -1)
    q_pe = rope(q[..., nope:], positions, cfg.rope_theta)
    c = x @ p["wkv_a"].astype(dt)
    c_kv = rms_norm(c[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(c[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return q[..., :nope], q_pe, jnp.concatenate([c_kv, k_pe], axis=-1)


def _mla_up(p: dict, cfg: ArchConfig, dt) -> tuple[jax.Array, jax.Array]:
    """W_UK (r, H, nope) and W_UV (r, H, v), the halves of W_UKV."""
    w = p["wkv_b"].astype(dt).reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., : cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def _mla_expanded(p: dict, cfg: ArchConfig, q_nope, q_pe, lat) -> jax.Array:
    """Causal attention in the expanded form over the latent rows ``lat``
    of the same sequence: per-head keys ``[c_kv·W_UK,h | k_pe]`` (nope +
    rope wide) and values ``c_kv·W_UV,h`` (v wide). Returns (B, L, d)."""
    b, l, h, _ = q_nope.shape
    dt = lat.dtype
    r = cfg.kv_lora_rank
    w_uk, w_uv = _mla_up(p, cfg, dt)
    with jax.named_scope("mla.attend"):
        k_nope = jnp.einsum("blr,rhn->blhn", lat[..., :r], w_uk)
        v = jnp.einsum("blr,rhv->blhv", lat[..., :r], w_uv)
        k_pe = jnp.broadcast_to(lat[:, :, None, r:r + cfg.qk_rope_dim],
                                (b, l, h, cfg.qk_rope_dim))
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, k_pe], axis=-1)
        out = blockwise_attention(q, k, v, causal=True)
    with jax.named_scope("mla.up"):
        return out.reshape(b, l, -1) @ p["wo"].astype(dt)


def mla_forward(p: dict, cfg: ArchConfig, x: jax.Array,
                positions: jax.Array) -> jax.Array:
    """Training attention (no cache), expanded form."""
    with jax.named_scope("mla.latent"):
        q_nope, q_pe, lat = _mla_in(p, cfg, x, positions)
    return _mla_expanded(p, cfg, q_nope, q_pe, lat)


def mla_prefill(p: dict, cfg: ArchConfig, x: jax.Array, positions,
                max_seq: int, *, lengths=None) -> tuple[jax.Array, dict]:
    """Prompt attention in the expanded form, and the latent ring
    ``{"ckv": (B, max_seq, w)}`` a token-by-token decode of the
    same prompt would leave (placement as in :func:`prefill`)."""
    with jax.named_scope("mla.latent"):
        q_nope, q_pe, lat = _mla_in(p, cfg, x, positions)
        ring = _ring_place({"ckv": _mla_pad(cfg, lat)[:, :, None]}, max_seq,
                           lengths, x.dtype)["ckv"]
        cache = {"ckv": ring[:, 0]}
    return _mla_expanded(p, cfg, q_nope, q_pe, lat), cache


def mla_decode(p: dict, cfg: ArchConfig, x: jax.Array, cache: dict,
               pos: jax.Array, layer, *, use_kernel: bool | None = None,
               pages=None, write_mask=None, mesh=None
               ) -> tuple[jax.Array, dict]:
    """One token in the absorbed form against layer ``layer`` of a latent
    cache stacked over a group of layers: ``cache["ckv"]`` holds rings
    ``(layers, B, s_c, w)`` or the paged pool ``(layers, n_blocks,
    block_len, w)``, ``w`` the padded latent width (:func:`_mla_width`;
    pages and ``write_mask`` as in :func:`decode`).
    The stack is written in place, so it can ride the layer loop whole:
    a loop that scanned over it would build a second stack as its output,
    and a serving-size latent cache leaves no room for two."""
    b = x.shape[0]
    dt = x.dtype
    r = cfg.kv_lora_rank
    ckv = cache["ckv"]
    with jax.named_scope("mla.latent"):
        q_nope, q_pe, lat = _mla_in(p, cfg, x, pos[:, None])
        lat = _mla_pad(cfg, lat)
        if pages is None:
            s_c = ckv.shape[2]
            ckv = ckv.at[layer, jnp.arange(b), pos % s_c].set(lat[:, 0])
            view = ckv[layer]
        else:
            n_blocks, block_len = ckv.shape[1], ckv.shape[2]
            s_c = pages.shape[1] * block_len
            slot = pos % s_c
            phys = jnp.take_along_axis(
                pages, (slot // block_len)[:, None], axis=1)[:, 0]
            if write_mask is not None:  # retired slot: block may be reowned
                phys = jnp.where(write_mask, phys, n_blocks)  # OOB: dropped
            ckv = ckv.at[layer, phys, slot % block_len].set(lat[:, 0])
            # the slots' ring views (sentinel pages clamp; masked below)
            view = ckv[layer][pages].reshape(b, s_c, -1)
        view = view[:, None]  # one KV "head": (B, 1, s_c, w)
        lengths = jnp.minimum(pos + 1, s_c).astype(jnp.int32)
    w_uk, w_uv = _mla_up(p, cfg, dt)
    from repro.kernels import ops as kops

    if use_kernel is None:
        use_kernel = not kops.resolve_interpret()
    scale = _mla_scale(cfg)
    with jax.named_scope("mla.attend"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
        q = _mla_pad(cfg, jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1))
        if use_kernel:
            def latent(q, k, _, lens):  # values: k's first r columns
                return kops.flash_decode(q, k, None, lens, v_width=r,
                                         scale=scale)
            o = _sharded(latent, mesh, q, view, view, lengths)
        else:
            from repro.kernels import ref as kref

            o = kref.flash_decode_ref(q, view, view[..., :r], lengths,
                                      scale=scale)
    with jax.named_scope("mla.up"):
        o = jnp.einsum("bhr,rhv->bhv", o.astype(dt), w_uv)
        out = o.reshape(b, 1, -1) @ p["wo"].astype(dt)
    return out, {"ckv": ckv}

def _sharded(kernel, mesh, q, k_view, v_view, *lengths):
    """An attention kernel — queries ``(B, ..., Hq, hd)`` against
    head-major ``(B, KV, S, hd)`` caches, optional ``(B,)`` lengths; on a
    mesh, once per shard inside ``shard_map``: the compiler cannot
    partition a Pallas kernel. Each shard holds whole GQA groups: KV heads
    (and their query heads) split over "model" when they divide it, the
    batch over the data axes when it divides them; anything else stays
    replicated."""
    if mesh is None:
        return kernel(q, k_view, v_view, *lengths)
    from jax.sharding import PartitionSpec as P

    data = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n_data = 1
    for a in data:
        n_data *= mesh.shape[a]
    bs = data if data and q.shape[0] % n_data == 0 else None
    hs = (
        "model"
        if "model" in mesh.shape and k_view.shape[1] % mesh.shape["model"] == 0
        else None
    )
    qs = P(bs, *([None] * (q.ndim - 3)), hs, None)
    kv = P(bs, hs, None, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(qs, kv, kv) + (P(bs),) * len(lengths),
        out_specs=qs, check_vma=False,
    )(q, k_view, v_view, *lengths)
