"""Public model API: init / loss / prefill / decode over any ArchConfig.

The LM head is the paper's amortized log-linear head (core/amortized_head
single-device; models/head.py shard_map distributed when a mesh with a
"model" axis is supplied). Modality frontends (audio/vision) are stubs per
the assignment: ``input_specs`` provides precomputed frame/patch embeddings.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import precision
from repro.core import amortized_head as ah
from repro.models import attention, head as dist_head, rglru, ssm, transformer
from repro.models.config import ArchConfig

__all__ = ["Model", "param_count", "active_param_count"]

_AUX_WEIGHT = 0.01  # MoE load-balance loss weight


def _head_cfg(cfg: ArchConfig, policy: precision.Policy) -> ah.HeadConfig:
    return ah.HeadConfig(
        n=cfg.vocab,
        k=cfg.head_k,
        l=cfg.head_l,
        mode=cfg.head_mode,
        mips=cfg.head_mips,
        delta=cfg.head_delta,
        n_probe=cfg.head_n_probe,
        adaptive_probe=cfg.head_adaptive_probe,
        n_probe_init=cfg.head_n_probe_init,
        n_probe_max=cfg.head_n_probe_max,
        use_kernel=cfg.head_use_kernel,
        fused_decode=cfg.head_fused_decode,
        score_dtype=policy.score_dtype,
    ).resolved()


class Model:
    """Stateless model bundle: methods take params explicitly.

    ``precision`` (a :class:`repro.precision.Policy` or its name) sets the
    trunk compute/activation dtype and the head's candidate-score dtype;
    master params stay fp32 and are cast at use inside each layer, and the
    head's estimator accumulators stay fp32 regardless of policy (DESIGN.md
    §9). Default is the ``bf16`` policy — identical numerics to the
    historical COMPUTE_DTYPE=bfloat16 stack.
    """

    def __init__(self, cfg: ArchConfig, mesh=None, precision_policy=None):
        self.cfg = cfg
        self.mesh = mesh  # None => single-device head path
        self.policy = precision.get_policy(precision_policy)
        self.compute_dtype = self.policy.compute_dtype
        self.head_cfg = _head_cfg(cfg, self.policy)

    # ---------------------------------------------------------------- init
    def init(self, key) -> dict:
        return transformer.init_params(key, self.cfg)

    # ---------------------------------------------------------------- embed
    def _embed_inputs(self, params, batch) -> tuple[jax.Array, jax.Array, int]:
        """Returns (x (B,L,d) compute dtype, positions (B,L), prefix)."""
        cfg = self.cfg
        if cfg.frontend == "audio_stub":
            x = batch["frames"].astype(self.compute_dtype)
            b, l, _ = x.shape
            pos = jnp.broadcast_to(jnp.arange(l), (b, l))
            return x, pos, 0
        tok_emb = params["embed"]
        if cfg.frontend == "vision_stub":
            patches = batch["patches"].astype(self.compute_dtype)
            toks = tok_emb[batch["tokens"]].astype(self.compute_dtype)
            x = jnp.concatenate([patches, toks], axis=1)
            b, l, _ = x.shape
            pos = jnp.broadcast_to(jnp.arange(l), (b, l))
            return x, pos, cfg.n_prefix_tokens
        x = tok_emb[batch["tokens"]].astype(self.compute_dtype)
        b, l, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))
        return x, pos, 0

    def _out_embed(self, params) -> jax.Array:
        return (
            params["embed"] if self.cfg.tie_embeddings else params["out_embed"]
        )

    # ---------------------------------------------------------------- index
    @property
    def head_uses_index(self) -> bool:
        """Whether make_head_index will return an index (vs None for the
        exact mode/backend — rule owned by amortized_head.uses_index)."""
        return ah.uses_index(self.head_cfg)

    def _head_mesh(self):
        """The mesh for a sharded head index, or None (single-device)."""
        if self.mesh is not None and "model" in self.mesh.shape:
            return self.mesh
        return None

    def make_head_index(self, params, db=None):
        """Build the head's stateful MIPS index over the current output
        embedding, or None when the exact path applies (exact mode/backend).

        ``db`` overrides the embedding rows to build over — the trainer
        passes a defensive copy because the PQ backend keeps its db handle
        inside the index state, which rides through the fused train step
        next to the DONATED params (XLA rejects a buffer that is both
        donated and used in one Execute(), and the donated buffer dies
        after the call regardless). Serving passes nothing and the index
        aliases the resident table directly.

        With a TP mesh, this is a :class:`repro.core.mips.ShardedIndex`:
        per-TP-slice indexes whose state rides through the distributed
        head's shard_map, so each shard probes its own vocab slice
        sublinearly instead of rescanning it.

        The returned Index is a jax pytree: thread it through the jitted
        train/serve steps as an argument and ``refresh`` it when the
        embedding drifts (train/trainer.py does this automatically).
        """
        return ah.make_index(
            self.head_cfg,
            self._out_embed(params) if db is None else db,
            mesh=self._head_mesh(),
        )

    def head_index_db(self, params) -> jax.Array:
        """The embedding rows backing the head index (for refresh/drift
        tracking): the FULL padded table when the index is sharded (each TP
        slice owns its pad rows, masked at probe time), else the
        logical-vocab slice."""
        emb = self._out_embed(params)
        if self._head_mesh() is not None or self.head_cfg.n == emb.shape[0]:
            return emb  # unsliced: refresh hands the PQ backend the
            # resident buffer itself (its fp re-rank rows alias it)
        return emb[: self.head_cfg.n]

    # ---------------------------------------------------------------- loss
    def loss_fn(self, params, batch, key, index=None) -> tuple[jax.Array, dict]:
        """Mean NLL over label positions (+ MoE aux)."""
        cfg = self.cfg
        x, pos, prefix = self._embed_inputs(params, batch)
        h, aux = transformer.apply_trunk(params, cfg, x, pos, prefix=prefix,
                                         mesh=self.mesh)
        labels = batch["labels"]
        if cfg.frontend == "vision_stub":
            h = h[:, cfg.n_prefix_tokens :]  # loss on text positions only
        b, l, d = h.shape
        h2 = h.reshape(b * l, d)
        t2 = labels.reshape(-1).astype(jnp.int32)
        if self._head_mesh() is not None:
            loss = dist_head.dist_head_loss(
                self.mesh, self._out_embed(params), h2, t2, key,
                self.head_cfg, index=index,
            )
            log_z = jnp.zeros(())  # diagnostics not returned by dist path
        else:
            out = ah.head_loss(
                self._out_embed(params), h2, t2, key, self.head_cfg,
                index=index,
            )
            loss, log_z = out.loss, out.log_z.mean()
        total = loss.mean() + _AUX_WEIGHT * aux
        return total, {"nll": loss.mean(), "aux": aux, "log_z": log_z}

    # ---------------------------------------------------------------- taps
    def trunk_taps(self, params, batch, lengths=None) -> jax.Array:
        """Mean-pooled per-tap trunk representations for deep-kNN
        attribution (repro.workloads.dknn): (n_taps, B, d) fp32.

        Taps are the block-group scan-step boundary activations plus the
        final normed output (transformer.apply_trunk ``return_taps``),
        mean-pooled over valid positions. ``lengths`` ((B,) optional)
        masks right-padded positions out of the pool; None pools over the
        full length. Rows are NOT normalized — dknn unit-normalizes so
        its MIPS probes rank by cosine."""
        cfg = self.cfg
        x, pos, prefix = self._embed_inputs(params, batch)
        _, _, taps = transformer.apply_trunk(
            params, cfg, x, pos, prefix=prefix, mesh=self.mesh,
            return_taps=True,
        )  # (n_taps, B, L, d)
        if lengths is None:
            return taps.mean(axis=2)
        ok = (
            jnp.arange(taps.shape[2])[None, :] < lengths[:, None]
        )  # (B, L)
        denom = jnp.maximum(lengths.astype(jnp.float32), 1.0)[None, :, None]
        return (taps * ok[None, :, :, None]).sum(axis=2) / denom

    # ---------------------------------------------------------------- decode
    def init_cache(self, batch: int, max_seq: int, dtype=None, paged=None):
        """``paged`` (a :class:`repro.models.transformer.PagedLayout`) swaps
        the attn KV leaves for the shared block pool; see init_cache there."""
        dtype = self.compute_dtype if dtype is None else dtype
        return transformer.init_cache(self.cfg, batch, max_seq, dtype,
                                      paged=paged)

    def decode_step(
        self, params, cache, ids: jax.Array, pos: jax.Array, key, index=None,
        *, keys=None, strict: bool = False, strict_live=None, router=None,
        pages=None, write_mask=None,
    ) -> tuple[jax.Array, jax.Array, Any, jax.Array]:
        """One serving step: (B,) last ids + (B,) positions -> next ids.

        Returns (next_ids (B,), ok (B,), new_cache, width (B,)).

        ``width`` is the per-slot effective probe width when the head runs
        the certificate-gated adaptive probe (``head_cfg.adaptive_probe``),
        −1 otherwise — the serving engine bins it into
        ``Server.stats["probe_width_hist"]``. ``router`` optionally supplies
        a :class:`repro.models.router.ProbeRouter` predicting each slot's
        starting stage.

        ``keys`` ((B,) typed PRNG keys) pins each slot's sample randomness;
        the serving engine derives them from (request id, position) so a
        token's sample is invariant to batch composition and decode fusion.
        ``strict`` re-samples certificate-failed tokens exactly (in-dispatch
        ``lax.cond`` fallback — single-device head only).

        ``pages`` ((B, n_pages) page table) switches the attn cache leaves
        to the paged-pool layout; ``write_mask`` ((B,) bool, the engine's
        ``active`` flags) drops retired slots' KV writes so recycled blocks
        are never corrupted.
        """
        cfg = self.cfg
        x = params["embed"][ids][:, None].astype(self.compute_dtype)  # (B,1,d)
        h, cache = transformer.apply_trunk_decode(params, cfg, x, cache, pos,
                                                  mesh=self.mesh, pages=pages,
                                                  write_mask=write_mask)
        hq = h[:, 0]  # (B, d)
        with jax.named_scope("head"):
            if self._head_mesh() is not None:
                if strict:
                    raise NotImplementedError(
                        "strict exact-fallback is not wired through the "
                        "distributed head; serve with strict=False on a TP "
                        "mesh"
                    )
                nxt, ok, width = dist_head.dist_head_sample(
                    self.mesh, self._out_embed(params), hq, key,
                    self.head_cfg, index=index, keys=keys, router=router,
                )
            else:
                res = ah.head_sample(
                    self._out_embed(params), hq, key, self.head_cfg,
                    index=index, keys=keys, strict=strict,
                    strict_live=strict_live, router=router,
                )
                nxt, ok = res.index, res.ok
                width = (
                    res.width.astype(jnp.int32) if res.width is not None
                    else jnp.full(nxt.shape, -1, jnp.int32)
                )
        return nxt, ok, cache, width

    def prefill(
        self, params, batch, key, max_seq: int, index=None
    ) -> tuple[jax.Array, jax.Array, jax.Array, Any]:
        """Prompt forward + cache build + first sampled token.

        Returns (next_ids (B,), ok (B,), pos (B,), cache).
        """
        cfg = self.cfg
        x, pos, prefix = self._embed_inputs(params, batch)
        b, l, _ = x.shape
        h, cache = transformer.apply_trunk_prefill(
            params, cfg, x, pos, max_seq=max_seq, prefix=prefix,
            mesh=self.mesh,
        )
        hq = h[:, -1]
        with jax.named_scope("head"):
            if self._head_mesh() is not None:
                nxt, ok, _ = dist_head.dist_head_sample(
                    self.mesh, self._out_embed(params), hq, key,
                    self.head_cfg, index=index,
                )
            else:
                res = ah.head_sample(
                    self._out_embed(params), hq, key, self.head_cfg,
                    index=index,
                )
                nxt, ok = res.index, res.ok
        return nxt, ok, jnp.full((b,), l, jnp.int32), cache

    def prefill_into_cache(
        self, params, cache, tokens: jax.Array, lengths: jax.Array,
        slots: jax.Array, keys, max_seq: int, index=None,
        strict: bool = False, strict_live=None, pages=None,
    ) -> tuple[jax.Array, jax.Array, Any]:
        """Batched chunked prefill written directly into serving-cache slots.

        One dispatch runs the full prompt forward for a right-padded
        admission batch ``tokens`` (Bn, Lp), builds each row's KV/SSM/LRU
        state as of its true ``lengths[b]``, scatters that state into
        ``cache`` at ``slots[b]`` (replacing whatever the recycled slot
        held), and samples the first output token from the last valid
        hidden state — replacing len(prompt) teacher-forced decode
        dispatches with one.

        Args:
          tokens: (Bn, Lp) int32, right-padded prompts; Lp is the engine's
            static chunk bucket (pad rows beyond the admitted count use an
            out-of-range slot id and are dropped by the scatter).
          lengths: (Bn,) true prompt lengths (>= 1).
          slots: (Bn,) serving-cache slot per row; rows with slot >= B are
            discarded (admission-batch padding).
          keys: (Bn,) per-request typed PRNG keys for the first sample.
          max_seq: the serving cache's max_seq (cache shapes must match).
          pages: optional (Bn, n_pages) physical-block table — the cache is
            the paged pool and each admitted row's KV ring is page-cut into
            its allocated blocks (sentinel entries dropped).

        Returns (next_ids (Bn,), ok (Bn,), cache).
        """
        cfg = self.cfg
        if cfg.frontend != "none":
            raise NotImplementedError(
                "prefill_into_cache serves token-LM frontends only"
            )
        x = params["embed"][tokens].astype(self.compute_dtype)  # (Bn, Lp, d)
        b, l, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))
        h, part = transformer.apply_trunk_prefill(
            params, cfg, x, pos, max_seq=max_seq, mesh=self.mesh,
            lengths=lengths,
        )
        hq = jnp.take_along_axis(
            h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]  # (Bn, d): hidden state at each row's last valid token
        with jax.named_scope("head"):
            if self._head_mesh() is not None:
                if strict:
                    raise NotImplementedError(
                        "strict exact-fallback is not wired through the "
                        "distributed head; serve with strict=False on a TP "
                        "mesh"
                    )
                nxt, ok, _ = dist_head.dist_head_sample(
                    self.mesh, self._out_embed(params), hq, None,
                    self.head_cfg, index=index, keys=keys,
                )
            else:
                res = ah.head_sample(
                    self._out_embed(params), hq, None, self.head_cfg,
                    index=index, keys=keys, strict=strict,
                    strict_live=strict_live,
                )
                nxt, ok = res.index, res.ok
        cache = transformer.insert_cache_slots(cache, part, slots, cfg=cfg,
                                               pages=pages)
        return nxt, ok, cache

    # ---------------------------------------------------------------- encoder
    def encode(self, params, batch) -> jax.Array:
        """Encoder-only (hubert): per-frame logits over the (small) vocab."""
        cfg = self.cfg
        x, pos, _ = self._embed_inputs(params, batch)
        h, _ = transformer.apply_trunk(params, cfg, x, pos, mesh=self.mesh)
        emb = self._out_embed(params)
        logits = h.astype(jnp.float32) @ emb.astype(jnp.float32).T
        return logits[..., : cfg.vocab]


# -------------------------------------------------------------------- counts
def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)  # python ints: jnp.prod would overflow int32 at >2B
    return n


def param_count(cfg: ArchConfig) -> int:
    shapes = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), jax.random.key(0)
    )
    return sum(_size(l.shape) for l in jax.tree.leaves(shapes))


def active_param_count(cfg: ArchConfig) -> int:
    """Params touched per token (MoE: routed experts only) — the
    6·N_active·D convention for MODEL_FLOPS."""
    total = param_count(cfg)
    if not cfg.is_moe:
        return total
    shapes = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), jax.random.key(0)
    )
    inactive = 0
    frac = 1.0 - cfg.experts_per_token / cfg.n_experts
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        keys = [getattr(p, "key", "") for p in path]
        if any(k in ("w1", "w2", "w3") for k in keys) and leaf.ndim == 4:
            inactive += int(frac * _size(leaf.shape))
    return total - inactive
