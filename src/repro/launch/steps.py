"""jit-able step functions: train_step / prefill_step / serve_step.

These are what the dry-run lowers and the trainer/server loops drive.
train_step supports microbatch gradient accumulation (psum once per step).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.optim import adamw

__all__ = ["TrainConfig", "make_train_step", "make_train_loop_step",
           "make_serve_step",
           "make_prefill_step", "make_encode_step", "slot_keys",
           "make_decode_loop_step",
           "make_prefill_into_cache_step"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: adamw.OptConfig = dataclasses.field(default_factory=adamw.OptConfig)
    accum: int = 1  # microbatch gradient-accumulation factor
    compress_grads: bool = False  # int8 ring all-reduce (optim/compress.py)
    precision: str = "bf16"  # model precision policy (repro/precision.py):
    #   "bf16" (default, the historical compute dtype) or "f32" (the
    #   numerics-reference / benchmark-baseline policy). Master params,
    #   gradient accumulators, and estimator partials are fp32 either way.


def _split_batch(batch: dict, accum: int) -> dict:
    """(GB, ...) -> (accum, GB/accum, ...) for lax.scan over microbatches."""

    def r(x):
        return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

    return jax.tree.map(r, batch)


def make_train_step(model: Model, tcfg: TrainConfig):
    """Returns train_step(params, opt_state, batch, key, index=None).

    ``index`` is the head's stateful MIPS index (a jax pytree, see
    core/mips) — on a TP mesh a ShardedIndex whose per-slice state rides
    into the distributed head's shard_map: it flows through as a plain
    argument, so a refreshed index never retriggers compilation. Gradients
    do not flow into it — the head only uses it for the stop-gradient
    top-k probe.

    Gradient accumulation (``tcfg.accum > 1``) scans ``accum`` microbatches
    and sums their gradients in fp32 (``precision.Policy.grad_accum_dtype``)
    regardless of the compute policy, then applies the optimizer ONCE on
    the mean — one dispatch per optimizer step either way.
    """

    def loss_for_grad(params, mb, key, index):
        loss, metrics = model.loss_fn(params, mb, key, index=index)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_for_grad, has_aux=True)

    def train_step(params, opt_state, batch, key, index=None):
        if tcfg.accum == 1:
            (loss, metrics), grads = grad_fn(params, batch, key, index)
        else:
            mbs = _split_batch(batch, tcfg.accum)
            keys = jax.random.split(key, tcfg.accum)

            def body(carry, xs):
                g_acc, l_acc = carry
                mb, kk = xs
                (l, m), g = grad_fn(params, mb, kk, index)
                # the barrier keeps XLA from folding the accumulate into the
                # gradient's own scatter-add / matmul, which would re-order
                # the sum away from the host loop's (g_acc + g) per step
                g = jax.lax.optimization_barrier(g)
                # fp32 accumulators: bf16 sums would be order-dependent at
                # the magnitudes the optimizer cares about
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g
                )
                return (g_acc, l_acc + l.astype(jnp.float32)), m

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss), ms = jax.lax.scan(body, (g0, 0.0), (mbs, keys))
            grads = jax.tree.map(lambda g: g / tcfg.accum, grads)
            loss = loss / tcfg.accum
            # per-microbatch aux metrics (nll/aux/log_z): report the mean
            metrics = jax.tree.map(
                lambda x: x.astype(jnp.float32).mean(0), ms
            )
        params, opt_state, opt_metrics = adamw.update(
            grads, opt_state, params, tcfg.opt
        )
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return params, opt_state, metrics

    return train_step


def make_train_loop_step(model: Model, tcfg: TrainConfig):
    """Fused multi-step training: ``loop_step(state, batches, steps,
    base_key, index=None) -> (state, metrics)``.

    The learning-side analogue of :func:`make_decode_loop_step`: a
    ``lax.scan`` runs ``T`` full optimizer steps (each itself an
    ``accum``-microbatch gradient-accumulation scan, see
    :func:`make_train_step`) in ONE dispatch, so step time amortizes
    dispatch + host-sync overhead ``T``-fold and the train state never
    leaves the device between optimizer steps.

    Args (shapes):
      state:   ``{"params": ..., "opt": ...}`` — jit the returned fn with
               ``donate_argnums=(0,)`` so both buffers are updated in place.
      batches: pytree with leading ``(T, GB, ...)`` — T stacked global
               batches.
      steps:   ``(T,)`` int32/uint32 global step indices. Per-step keys
               derive as ``fold_in(base_key, step)`` — the SAME derivation
               the single-step driver uses, so a fused T-window is
               bit-identical to T sequential single-step dispatches
               (asserted in tests/test_train_engine.py), invariant to how
               the trainer chunks the run (log/ckpt/refresh boundaries).
      index:   optional head MIPS index pytree; held FIXED across the
               fused window — staleness-triggered refresh is hoisted to
               fused-loop boundaries by the trainer. This frozen-window
               contract is also what makes the trainer's async
               double-buffered refresh (repro.train.refresh) safe: the
               side thread rebuilds from a snapshot while chunks keep
               dispatching against the stale buffer, and the swap is just
               a different pytree VALUE at the next dispatch — same
               treedef, same canonical shardings, so the jit cache (and
               with it this function's compiled graphs) is untouched.

    Returns the new state and per-step metrics stacked to ``(T,)`` leaves;
    the host decides when to actually sync them (every ``log_every`` steps
    in the trainer — the one-dispatch-in-flight pattern of PR 3's serving
    engine applied to learning).
    """
    step_fn = make_train_step(model, tcfg)

    def loop_step(state, batches, steps, base_key, index=None):
        def body(st, xs):
            mb, step = xs
            k = jax.random.fold_in(base_key, step)
            params, opt, metrics = step_fn(
                st["params"], st["opt"], mb, k, index
            )
            return {"params": params, "opt": opt}, metrics

        state, metrics = jax.lax.scan(body, state, (batches, steps))
        return state, metrics

    return loop_step


def make_serve_step(model: Model):
    """serve_step(params, cache, ids, pos, key, index=None)
    -> (next_ids, ok, cache, pos+1)."""

    def serve_step(params, cache, ids, pos, key, index=None):
        nxt, ok, cache, _ = model.decode_step(
            params, cache, ids, pos, key, index=index
        )
        return nxt, ok, cache, pos + 1

    return serve_step


def slot_keys(base_key, rids: jax.Array, pos: jax.Array):
    """Per-slot sample keys: ``fold_in(fold_in(base, rid), pos)``.

    Making the key a function of (request id, position) — instead of the
    host loop's step counter — is what lets the fused decode window, the
    batched prefill path, and the single-step reference loop draw
    *identical* samples for the same request: the derivation is invariant
    to batch composition, slot assignment, and dispatch fusion.
    """

    def one(r, p):
        return jax.random.fold_in(jax.random.fold_in(base_key, r), p)

    return jax.vmap(one)(rids.astype(jnp.uint32), pos.astype(jnp.uint32))


def _advance(state: dict, nxt, eos_id: int, max_seq: int):
    """Shared slot-state transition for one decoded token.

    ``state`` is the engine's device-resident per-slot record — the single
    source of truth for positions and liveness (the host only mirrors it
    from the emitted-token stream):
      ids (B,) int32    last token (frozen once inactive)
      pos (B,) int32    position of that token
      active (B,) bool  slot is decoding a live request
      budget (B,) int32 remaining new-token allowance
      rid (B,) int32    request id (keys + host bookkeeping)
    Returns (state', emitted) where emitted marks slots that produced a
    token this step. Inactive slots are frozen (ids/pos don't move) but
    their trunk still runs, so recurrent SSM/RG-LRU cache state keeps
    mutating — wasted compute whose output is never read. That is safe
    ONLY because admission replaces the slot's cache state wholesale
    (prefill_into_cache); a frozen slot must never be resumed without a
    fresh prefill.
    """
    active = state["active"]
    ids = jnp.where(active, nxt, state["ids"])
    pos = jnp.where(active, state["pos"] + 1, state["pos"])
    budget = jnp.where(active, state["budget"] - 1, state["budget"])
    eos_hit = (ids == eos_id) if eos_id >= 0 else jnp.zeros_like(active)
    done = active & (eos_hit | (budget <= 0) | (pos + 1 > max_seq - 1))
    return dict(state, ids=ids, pos=pos, budget=budget,
                active=active & ~done), active


def make_decode_loop_step(model: Model, window: int, eos_id: int,
                          max_seq: int, strict: bool = False,
                          paged: bool = False):
    """Fused multi-token decode: ``decode_loop(params, cache, state,
    base_key, n, index=None, router=None) -> (cache, state, tokens (T,B),
    ok (T,B), emitted (T,B), widths (T,B))`` with ``T = window``.

    ``n`` (a traced int32 scalar, ``1 <= n <= window``) is the number of
    tokens this dispatch decodes; rows ``n..T-1`` of the outputs are
    not emitted. The loop's trip count is an operand, so one compiled
    loop body serves every window size: the scheduler's shorter windows
    and the single-token reference loop (``engine="reference"``, ``n=1``)
    run the very program the full window runs.

    ``widths`` is the per-token effective probe width under the head's
    certificate-gated adaptive probe (−1 on fixed-width paths); the engine
    bins emitted slots' widths into ``Server.stats["probe_width_hist"]``.
    ``router`` optionally carries a ProbeRouter pytree into each step.

    A while loop decodes ``n`` tokens per dispatch with per-slot
    active masks and on-device EOS/length-budget detection — amortizing
    dispatch + host-sync overhead ``window``-fold. Slots that finish
    mid-window stop emitting (and stop perturbing their state) on device;
    the host discovers this from the emitted mask after the fact.

    With ``head_fused_decode`` set on the arch config, each scanned token
    additionally runs the head's probe → screen → re-rank → certificate →
    Gumbel-argmax as the single-dispatch Pallas pipeline
    (kernels/decode_fused.py) — inherited here through ``model.decode_step``
    with no loop-level change; per-token keys from :func:`slot_keys` keep
    the samples bit-identical either way.

    ``paged`` reads the slot page tables from ``state["pages"]`` ((B,
    n_pages) physical-block ids, sentinel for unallocated) and passes each
    slot's ``active`` flag as the KV ``write_mask`` — a retired slot's
    blocks may already belong to another request, so its (frozen, garbage)
    decode writes must be dropped on device.
    """

    def decode_loop(params, cache, state, base_key, n, index=None,
                    router=None):
        b = state["ids"].shape[0]
        outs = (
            jnp.zeros((window, b), jnp.int32),  # tokens
            jnp.zeros((window, b), bool),  # ok
            jnp.zeros((window, b), bool),  # emitted
            jnp.full((window, b), -1, jnp.int32),  # widths
        )

        def body(t, carry):
            cache, state, outs = carry
            keys = slot_keys(base_key, state["rid"], state["pos"])
            nxt, ok, cache, width = model.decode_step(
                params, cache, state["ids"], state["pos"], None, index=index,
                keys=keys, strict=strict, strict_live=state["active"],
                router=router,
                pages=state["pages"] if paged else None,
                write_mask=state["active"] if paged else None,
            )
            state, emitted = _advance(state, nxt, eos_id, max_seq)
            outs = tuple(
                o.at[t].set(v) for o, v in
                zip(outs, (state["ids"], ok, emitted, width))
            )
            return cache, state, outs

        # traced bounds: a while loop, never unrolled or specialised to n
        cache, state, (toks, oks, emitted, widths) = jax.lax.fori_loop(
            jnp.int32(0), jnp.asarray(n, jnp.int32), body,
            (cache, state, outs),
        )
        return cache, state, toks, oks, emitted, widths

    return decode_loop


def make_prefill_into_cache_step(model: Model, max_seq: int, eos_id: int,
                                 max_new_tokens: int, strict: bool = False,
                                 paged: bool = False):
    """Chunked batched prefill + slot admission: ``prefill_admit(params,
    cache, state, tokens (Bn,Lp), lengths, slots, rids, base_key,
    index=None, pages=None) -> (cache, state, first_ids, ok)``.

    Writes each admitted prompt's KV/SSM state straight into its slot's
    cache (one dispatch per admission batch instead of one per prompt
    token), samples the first output token from the last valid hidden
    state, and commits the slot records (ids/pos/active/budget/rid) on
    device. Rows with slot >= batch_slots are admission padding — their
    scatters are dropped.

    ``paged``: ``pages`` ((Bn, n_pages) physical-block ids per admitted
    row, sentinel-filled for pad rows) routes the prefill-built KV rings
    into the shared pool and is committed into ``state["pages"]`` at each
    row's slot, where the fused decode loop walks it.
    """

    def prefill_admit(params, cache, state, tokens, lengths, slots, rids,
                      base_key, index=None, pages=None):
        lengths = lengths.astype(jnp.int32)
        keys = slot_keys(base_key, rids, lengths - 1)
        nxt, ok, cache = model.prefill_into_cache(
            params, cache, tokens, lengths, slots, keys, max_seq=max_seq,
            index=index, strict=strict,
            strict_live=rids >= 0,  # admission pad rows sample garbage
            pages=pages if paged else None,
        )
        budget = jnp.full_like(lengths, max_new_tokens - 1)
        eos_hit = (nxt == eos_id) if eos_id >= 0 else jnp.zeros(
            nxt.shape, bool
        )
        alive = ~(eos_hit | (budget <= 0) | (lengths + 1 > max_seq - 1))
        new_state = {
            "ids": state["ids"].at[slots].set(nxt),
            "pos": state["pos"].at[slots].set(lengths),
            "active": state["active"].at[slots].set(alive),
            "budget": state["budget"].at[slots].set(budget),
            "rid": state["rid"].at[slots].set(rids.astype(jnp.int32)),
        }
        if paged:
            new_state["pages"] = state["pages"].at[slots].set(
                pages.astype(state["pages"].dtype)
            )
        # `alive` stays device-internal (committed into state["active"]):
        # the host re-derives liveness from the emitted tokens
        return cache, new_state, nxt, ok

    return prefill_admit


def make_prefill_step(model: Model, max_seq: int):
    """prefill_step(params, batch, key, index=None)
    -> (next_ids, ok, pos, cache)."""

    def prefill_step(params, batch, key, index=None):
        return model.prefill(params, batch, key, max_seq=max_seq, index=index)

    return prefill_step


def make_encode_step(model: Model):
    """Encoder-only archs: encode_step(params, batch) -> logits."""

    def encode_step(params, batch):
        return model.encode(params, batch)

    return encode_step
