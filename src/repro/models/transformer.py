"""Trunk assembly: scan-over-layers stacks of attn / moe / ssm / rec blocks.

A layer's kind is a pair (mixer, mlp): the mixer ``attn`` (GQA or MLA, as
``cfg.attn_kind`` says), ``rec`` or ``ssm``; the MLP ``dense``, ``moe`` or
``none`` (mamba blocks). Layers are grouped into homogeneous *block
groups* (e.g. Griffin's (rec, rec, attn) period, or a leading dense layer
then the expert layers) whose parameters are stacked along a leading
layer axis and consumed by ``lax.scan`` — keeping HLO size (and therefore
512-device compile time) independent of depth. Each scan step is wrapped in
``jax.checkpoint`` so only layer-boundary activations are saved (remat).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, moe, rglru, ssm
from repro.models.config import ArchConfig
from repro.models.layers import dense_init, mlp_init, rms_norm, swiglu

__all__ = [
    "block_groups",
    "init_params",
    "apply_trunk",
    "init_cache",
    "apply_trunk_decode",
    "insert_cache_slots",
    "PagedLayout",
    "ring_len",
]

REMAT = True  # module-level knob (tests may disable for speed)


def _layer_window(cfg: ArchConfig) -> int:
    """Effective attention window for this arch's attn layers. ONE source
    of truth: prefill, decode, and cache sizing must agree, or the decode
    ring and the prefill-built cache silently disagree on shape/semantics
    (the griffin local_window bug this replaces)."""
    return cfg.local_window if cfg.layer_pattern == "griffin" else cfg.window


def ring_len(cfg: ArchConfig, max_seq: int) -> int:
    """KV ring length s_c for this arch's attn layers — the quantity a
    per-slot page table must cover (``n_pages * block_len == s_c``). Public
    because the serving allocator sizes page tables from it."""
    win = _layer_window(cfg)
    return min(win, max_seq) if win else max_seq


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Paged-pool geometry for the attention KV cache.

    ``n_blocks`` physical blocks of ``block_len`` positions are shared by
    all serving slots; a per-slot page table of ``ring_len(cfg, max_seq) //
    block_len`` entries maps ring pages onto physical blocks. Block id
    ``n_blocks`` is the OOB sentinel for unallocated pages (scatter drops
    it, gather clamps — garbage masked by decode ``lengths``). SSM/RG-LRU/
    conv state is max_seq-free and stays slot-resident (dense)."""

    block_len: int
    n_blocks: int

    def n_pages(self, cfg: ArchConfig, max_seq: int) -> int:
        s_c = ring_len(cfg, max_seq)
        if s_c % self.block_len:
            raise ValueError(
                f"block_len={self.block_len} must divide the KV ring length "
                f"s_c={s_c} (window/max_seq geometry)"
            )
        return s_c // self.block_len

    @property
    def sentinel(self) -> int:
        return self.n_blocks


def _constrain_batch(x: jax.Array, mesh):
    """Pin (B, L, d) activations to batch-over-("pod","data"), replicated
    elsewhere. Without this, XLA auto-sharding may replicate the batch
    through the layer scan (observed: 16x redundant attention work on the
    prefill cells — §Perf iteration 1)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    ba = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    size = 1
    for a in ba:
        size *= mesh.shape[a]
    ax = ba if (ba and x.shape[0] % size == 0 and x.shape[0] >= size) else None
    spec = P(ax, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def block_groups(cfg: ArchConfig
                 ) -> list[tuple[tuple[tuple[str, str], ...], int]]:
    """[(pattern, repeat)] covering the layers' (mixer, mlp) kinds."""
    kinds = list(zip(cfg.layer_kinds(), cfg.mlp_kinds()))
    if cfg.layer_pattern == "griffin":
        period = tuple(kinds[:3])
        n_full = len(kinds) // 3
        groups = [(period, n_full)]
        rem = len(kinds) - 3 * n_full
        if rem:
            groups.append((tuple(kinds[3 * n_full :]), 1))
        return groups
    groups: list[tuple[tuple[tuple[str, str], ...], int]] = []
    for kind in kinds:  # runs of one kind
        if groups and groups[-1][0] == (kind,):
            groups[-1] = (groups[-1][0], groups[-1][1] + 1)
        else:
            groups.append(((kind,), 1))
    return groups


# ----------------------------------------------------------------- init


def _init_one_layer(key, cfg: ArchConfig, kind: tuple[str, str]) -> dict:
    d = cfg.d_model
    mixer, mlp = kind
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": jnp.zeros((d,), jnp.float32)}
    if mixer == "attn":
        p["mix"] = attention.init(ks[0], cfg)
    elif mixer == "rec":
        p["mix"] = rglru.init(ks[0], cfg)
    elif mixer == "ssm":
        p["mix"] = ssm.init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if mlp == "none":
        return p  # mamba blocks: norm + mixer only, no MLP
    p["norm2"] = jnp.zeros((d,), jnp.float32)
    if mlp == "moe":
        p["mlp"] = moe.init(ks[1], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], d, cfg.d_ff)
    return p


def init_params(key, cfg: ArchConfig) -> dict:
    d, vp = cfg.d_model, cfg.vocab_padded
    k_emb, k_out, k_blocks = jax.random.split(key, 3)
    params: dict[str, Any] = {}
    if cfg.frontend != "audio_stub":  # audio stub feeds embeddings directly
        params["embed"] = dense_init(k_emb, (vp, d), in_axis=-1)
    params["out_embed"] = (
        None if cfg.tie_embeddings else dense_init(k_out, (vp, d), in_axis=-1)
    )
    params["final_norm"] = jnp.zeros((d,), jnp.float32)

    blocks = []
    gkeys = jax.random.split(k_blocks, len(block_groups(cfg)))
    for gk, (pattern, count) in zip(gkeys, block_groups(cfg)):
        stack = {}
        pkeys = jax.random.split(gk, len(pattern))
        for j, (pk, kind) in enumerate(zip(pkeys, pattern)):
            lkeys = jax.random.split(pk, count)
            stack[str(j)] = jax.vmap(
                lambda kk: _init_one_layer(kk, cfg, kind)
            )(lkeys)
        blocks.append(stack)
    params["blocks"] = blocks
    return params


# ----------------------------------------------------------------- train/prefill


def _apply_block(
    p: dict,
    cfg: ArchConfig,
    kind: tuple[str, str],
    h: jax.Array,
    positions: jax.Array,
    prefix: int,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    mixer, mlp = kind
    aux = jnp.zeros((), jnp.float32)
    x = rms_norm(h, p["norm1"], cfg.norm_eps)
    if mixer == "ssm":
        return h + ssm.forward(p["mix"], cfg, x), aux
    if mixer == "attn":
        win = _layer_window(cfg)
        mix = attention.forward(
            p["mix"], cfg, x, positions, window=win, prefix=prefix
        )
    else:  # rec
        mix = rglru.forward(p["mix"], cfg, x)
    h = h + mix
    x = rms_norm(h, p["norm2"], cfg.norm_eps)
    if mlp == "moe":
        b, l, d = x.shape
        if mesh is not None and "model" in mesh.shape:
            out, aux = moe.forward_dist(p["mlp"], cfg, x.reshape(-1, d), mesh)
        else:
            out, aux = moe.forward(p["mlp"], cfg, x.reshape(-1, d))
        out = out.reshape(b, l, d)
    else:
        out = swiglu(x, p["mlp"]["w1"], p["mlp"]["w2"], p["mlp"]["w3"])
    return h + out, aux


def apply_trunk(
    params: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, L, d) embedded input
    positions: jax.Array,  # (B, L)
    *,
    prefix: int = 0,
    mesh=None,
    return_taps: bool = False,
) -> tuple[jax.Array, jax.Array] | tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (h (B, L, d), aux_loss) — or (h, aux_loss, taps) with
    ``return_taps``.

    Taps are the scan-step boundary activations the remat machinery
    already saves: one (B, L, d) fp32 slice per block-group step (a whole
    layer-group period, e.g. Griffin's (rec, rec, attn)), plus the final
    normed output as the last row — stacked to (n_taps, B, L, d). Deep-kNN
    attribution (repro.workloads.dknn) builds one index per tap; emitting
    them as scan ys keeps HLO size depth-independent, same as the trunk
    itself.
    """
    aux0 = jnp.zeros((), jnp.float32)
    x = _constrain_batch(x, mesh)

    taps = []
    for stack, (pattern, count) in zip(params["blocks"], block_groups(cfg)):

        def body(carry, layer_p, pattern=pattern):
            h, aux = carry
            h = _constrain_batch(h, mesh)
            for j, kind in enumerate(pattern):
                h, a = _apply_block(layer_p[str(j)], cfg, kind, h, positions,
                                    prefix, mesh=mesh)
                aux = aux + a
            h = _constrain_batch(h, mesh)
            ys = h.astype(jnp.float32) if return_taps else None
            return (h, aux), ys

        if REMAT:
            body = jax.checkpoint(body)
        (x, aux0), ys = jax.lax.scan(body, (x, aux0), stack)
        if return_taps:
            taps.append(ys)  # (count, B, L, d)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_taps:
        taps.append(h.astype(jnp.float32)[None])
        return h, aux0, jnp.concatenate(taps, axis=0)
    return h, aux0


# ----------------------------------------------------------------- prefill


def _serve_mlp(p: dict, cfg: ArchConfig, mlp: str, x: jax.Array, mesh):
    """The MLP of a serving step (prefill or decode): an expert layer is
    dropless here, so a token's output never depends on its batch mates."""
    if mlp != "moe":
        return swiglu(x, p["mlp"]["w1"], p["mlp"]["w2"], p["mlp"]["w3"])
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    if mesh is not None and "model" in mesh.shape:
        out, _ = moe.forward_dist(p["mlp"], cfg, x, mesh)
    else:
        out, _ = moe.forward(p["mlp"], cfg, x, dropless=True)
    return out.reshape(shape)


def _apply_block_prefill(
    p: dict,
    cfg: ArchConfig,
    kind: tuple[str, str],
    h: jax.Array,
    positions: jax.Array,
    max_seq: int,
    prefix: int,
    mesh=None,
    lengths=None,
) -> tuple[jax.Array, dict]:
    mixer, mlp = kind
    x = rms_norm(h, p["norm1"], cfg.norm_eps)
    if mixer == "ssm":
        mix, cache = ssm.forward(p["mix"], cfg, x, return_cache=True,
                                 lengths=lengths)
        return h + mix, cache
    if mixer == "attn":
        win = _layer_window(cfg)
        with jax.named_scope("attention"):
            mix, cache = attention.prefill(
                p["mix"], cfg, x, positions, max_seq, window=win,
                prefix=prefix, lengths=lengths, mesh=mesh,
            )
    else:
        mix, cache = rglru.forward(p["mix"], cfg, x, return_cache=True,
                                   lengths=lengths)
    h = h + mix
    x = rms_norm(h, p["norm2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        out = _serve_mlp(p, cfg, mlp, x, mesh)
    return h + out, cache


def apply_trunk_prefill(
    params: dict,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    max_seq: int,
    prefix: int = 0,
    mesh=None,
    lengths=None,  # (B,) valid lengths for right-padded batched prefill
) -> tuple[jax.Array, list]:
    caches = []
    x = _constrain_batch(x, mesh)
    for stack, (pattern, count) in zip(params["blocks"], block_groups(cfg)):

        def body(h, layer_p, pattern=pattern):
            h = _constrain_batch(h, mesh)
            cs = {}
            for j, kind in enumerate(pattern):
                h, cs[str(j)] = _apply_block_prefill(
                    layer_p[str(j)], cfg, kind, h, positions, max_seq, prefix,
                    mesh=mesh, lengths=lengths,
                )
            return _constrain_batch(h, mesh), cs

        if REMAT:
            body = jax.checkpoint(body)
        x, cache = jax.lax.scan(body, x, stack)
        caches.append(cache)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return h, caches


def insert_cache_slots(
    full: list,
    part: list,
    slots: jax.Array,
    *,
    cfg: ArchConfig | None = None,
    pages: jax.Array | None = None,
) -> list:
    """Scatter a prefill-built cache ``part`` (leaves (layers, Bn, ...))
    into batch slots of a serving cache ``full`` (leaves (layers, B, ...)).

    The whole per-slot state is replaced — KV ring, SSM/RG-LRU state and
    conv tails — so a recycled slot carries nothing over from its previous
    request. Rows whose slot id is out of range (>= B) are dropped by XLA's
    scatter semantics; the engine uses slot id B for the pad rows of a
    partially-filled admission batch.

    Paged layout (``pages`` given, requires ``cfg``): attn KV leaves of
    ``full`` are the shared pool ``(layers, n_blocks, KV, block_len, hd)``;
    the prefill-built ring ``(layers, Bn, KV, s_c, hd)`` is re-cut into
    pages and scattered to each admitted row's physical blocks
    (``pages[b, i]``, sentinel ``n_blocks`` for unallocated/pad rows —
    dropped). Non-attn leaves stay slot-scattered as in the dense layout.
    """
    if pages is None:
        return jax.tree.map(
            lambda f, p: f.at[:, slots].set(p.astype(f.dtype)), full, part
        )
    if cfg is None:
        raise ValueError("paged insert_cache_slots needs cfg")
    block_len = None
    for g_full, (pattern, _) in zip(full, block_groups(cfg)):
        for j, (mixer, _) in enumerate(pattern):
            if mixer == "attn":
                block_len = jax.tree.leaves(g_full[str(j)])[0].shape[-2]
    assert block_len is not None, "paged insert on an attn-free arch"
    n_pages = pages.shape[1]

    def _scatter_attn(f, p):
        if p.ndim == 4:  # (layers, Bn, s_c, w): MLA's latent, no head axis
            lyr, bn, _, w = p.shape
            pr = p.reshape(lyr, bn, n_pages, block_len, w)
            return f.at[:, pages].set(pr.astype(f.dtype))
        # p: (layers, Bn, KV, s_c, hd) -> page-cut
        # (layers, Bn, n_pages, KV, block_len, hd) -> pool scatter
        lyr, bn, kv, _, hd = p.shape
        pr = p.reshape(lyr, bn, kv, n_pages, block_len, hd)
        return f.at[:, pages].set(pr.transpose(0, 1, 3, 2, 4, 5)
                                  .astype(f.dtype))

    out = []
    for g_full, g_part, (pattern, _) in zip(full, part, block_groups(cfg)):
        new_g = {}
        for j, (mixer, _) in enumerate(pattern):
            f, p = g_full[str(j)], g_part[str(j)]
            if mixer == "attn":
                new_g[str(j)] = jax.tree.map(_scatter_attn, f, p)
            else:
                new_g[str(j)] = jax.tree.map(
                    lambda fl, pl: fl.at[:, slots].set(pl.astype(fl.dtype)),
                    f, p,
                )
        out.append(new_g)
    return out


# ----------------------------------------------------------------- decode


def _block_cache(cfg: ArchConfig, kind: str, batch: int, max_seq: int, dtype,
                 paged: PagedLayout | None = None):
    """The decode state of one layer whose mixer is ``kind``."""
    if kind == "attn":
        if paged is not None:
            paged.n_pages(cfg, max_seq)  # validate geometry
            return attention.init_pool(
                cfg, paged.n_blocks, paged.block_len, dtype
            )
        win = _layer_window(cfg)
        return attention.init_cache(cfg, batch, max_seq, dtype, window=win)
    if kind == "ssm":
        return ssm.init_cache(cfg, batch, dtype)
    if kind == "rec":
        return rglru.init_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype,
               paged: PagedLayout | None = None) -> list:
    """Cache pytree mirroring the block-group structure (stacked).

    With ``paged`` the attn leaves become the shared block pool
    ``(count, n_blocks, KV, block_len, hd)`` — batch-free; slot -> position
    resolution happens through the page table at decode/insert time. SSM /
    RG-LRU leaves keep their dense per-slot ``(count, batch, ...)`` shape."""
    if paged is not None and not any(
        k == "attn" for k in cfg.layer_kinds()
    ):
        raise ValueError(
            "paged cache layout requires attention layers; "
            f"arch {cfg.layer_pattern!r} has none (its decode state is "
            "already max_seq-free)"
        )
    caches = []
    for pattern, count in block_groups(cfg):
        group = {}
        for j, (mixer, _) in enumerate(pattern):
            # zeros of the stacked shape made at once: a broadcast layer
            # copied would hold the whole stack twice for a moment
            one = jax.eval_shape(functools.partial(
                _block_cache, cfg, mixer, batch, max_seq, dtype, paged=paged))
            group[str(j)] = jax.tree.map(
                lambda a: jnp.zeros((count,) + a.shape, a.dtype), one
            )
        caches.append(group)
    return caches


def _apply_block_decode(
    p: dict,
    cfg: ArchConfig,
    kind: tuple[str, str],
    h: jax.Array,  # (B, 1, d)
    cache: dict,
    pos: jax.Array,  # (B,)
    mesh=None,
    pages: jax.Array | None = None,
    write_mask: jax.Array | None = None,
    layer=None,  # index into a cache stacked over the group (MLA)
) -> tuple[jax.Array, dict]:
    mixer, mlp = kind
    x = rms_norm(h, p["norm1"], cfg.norm_eps)
    if mixer == "ssm":
        mix, cache = ssm.decode(p["mix"], cfg, x, cache)
        return h + mix, cache
    if mixer == "attn":
        win = _layer_window(cfg)
        with jax.named_scope("attention"):
            if cfg.attn_kind == "mla":
                mix, cache = attention.mla_decode(
                    p["mix"], cfg, x, cache, pos, layer, pages=pages,
                    write_mask=write_mask, mesh=mesh)
            else:
                mix, cache = attention.decode(p["mix"], cfg, x, cache, pos,
                                              window=win, pages=pages,
                                              write_mask=write_mask,
                                              mesh=mesh)
    else:
        mix, cache = rglru.decode(p["mix"], cfg, x, cache)
    h = h + mix
    x = rms_norm(h, p["norm2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        out = _serve_mlp(p, cfg, mlp, x, mesh)
    return h + out, cache


def apply_trunk_decode(
    params: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, 1, d)
    caches: list,
    pos: jax.Array,  # (B,)
    mesh=None,
    pages: jax.Array | None = None,  # (B, n_pages) page table (paged cache)
    write_mask: jax.Array | None = None,  # (B,) live-slot mask for KV writes
) -> tuple[jax.Array, list]:
    new_caches = []
    x = _constrain_batch(x, mesh)
    for stack, cache, (pattern, count) in zip(
        params["blocks"], caches, block_groups(cfg)
    ):
        if cfg.attn_kind == "mla":
            # the latent cache rides the loop whole and each layer writes
            # its row in place (attention.mla_decode): scanned over, it
            # would be rebuilt as a second stack, the scan's output
            def body_mla(carry, xs, pattern=pattern):
                h, c = carry
                layer_p, i = xs
                c = dict(c)
                for j, kind in enumerate(pattern):
                    h, c[str(j)] = _apply_block_decode(
                        layer_p[str(j)], cfg, kind, h, c[str(j)], pos,
                        mesh=mesh, pages=pages, write_mask=write_mask,
                        layer=i,
                    )
                return (h, c), None

            (x, nc), _ = jax.lax.scan(body_mla, (x, cache),
                                      (stack, jnp.arange(count)))
            new_caches.append(nc)
            continue

        def body(h, xs, pattern=pattern):
            layer_p, layer_c = xs
            new_c = {}
            for j, kind in enumerate(pattern):
                h, new_c[str(j)] = _apply_block_decode(
                    layer_p[str(j)], cfg, kind, h, layer_c[str(j)], pos,
                    mesh=mesh, pages=pages, write_mask=write_mask,
                )
            return h, new_c

        x, nc = jax.lax.scan(body, x, (stack, cache))
        new_caches.append(nc)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return h, new_caches
