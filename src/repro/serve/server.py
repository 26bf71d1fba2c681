"""Pipelined batched-decode engine: continuous batching over the amortized
sampler.

The serving regime is the paper's sweet spot: the output embedding (the
MIPS database) is frozen, every decoded token issues a fresh query θ = h,
and the stateful head index (core/mips) is built once at server start —
pure amortization. The index rides through the jitted steps as a pytree
argument, so a hot-swap (e.g. after a model push, via
``Server.refresh_index``) never recompiles.

Engine (``ServeConfig.engine="pipelined"``, the default):

* **Batched prefill** — admitted prompts are right-padded to a chunk
  bucket and run through ``Model.prefill_into_cache``, which writes each
  prompt's KV/SSM state directly into its slot's cache and samples the
  first output token. A 500-token prompt costs one dispatch, not 500.
  Each dispatch holds only as many rows as its bucket needs to keep the
  weight matmuls compute-bound (``_prefill_rows``), not every slot: the
  admitted prompts are cut, in scheduler order, into chunks of that many
  rows, one dispatch each, so every bucket has one prefill program.
* **Fused decode** — a ``lax.scan`` decodes ``decode_window`` tokens per
  dispatch with per-slot active masks and on-device EOS/length-budget
  detection, amortizing dispatch + host-sync cost ``T``-fold while keeping
  the lazy-Gumbel ``ok`` certificate per token.
* **Async host pipeline** — one dispatch is always kept in flight: the
  host issues window t+1 before converting window t's tokens to numpy, so
  Python bookkeeping overlaps device compute. Per-slot position/active
  state lives ON DEVICE (single source of truth); the host only mirrors it
  from the emitted-token stream.
* **Admission control** — prompts longer than ``max_seq -
  max_new_tokens`` are truncated (keep the newest tokens) or rejected at
  admission per ``ServeConfig.overlength``; they can no longer walk
  ``pos`` past the KV cache.

Sample keys derive from (request id, position) — ``launch.steps.slot_keys``
— so tokens are bit-identical between the fused engine and the single-step
reference loop (``engine="reference"``), which teacher-forces prompts one
token per dispatch with the same key discipline and is kept as the
correctness comparator and benchmark baseline.

``strict=True`` re-samples certificate-failed tokens (``ok=False``) with
the exact dense sampler inside the dispatch (``lax.cond`` — the O(n·d)
fallback only executes when a window actually contains a flagged token).

**Paged block cache** (``ServeConfig.block_len > 0``): instead of every
slot reserving a full ``max_seq``-length KV ring, attn KV lives in a
shared ``(n_blocks, block_len, ...)`` pool (models/attention.init_pool)
and each slot walks a page table committed at admission — so slot count
decouples from worst-case sequence length and concurrency is bounded by
*actual* cache use, not the worst case. Admission allocates a request's
whole-lifetime blocks up front (serve/paging.py: exhaustion is an
admission stall, never a mid-decode stall or an OOB write) and frees
them at EOS/finish. A priority + SLO-aware scheduler (serve/scheduler.py,
``ServeConfig.sched``) orders the admission queue by TTFT deadline and
picks the fused decode window per dispatch from the ITL EWMA feedback.
Tokens stay BITWISE identical to the dense layout — placement is pure
page-table arithmetic over the same ring positions, and sample keys
never see the layout.

``Server.run`` also accepts open-loop ``arrivals`` (per-request enqueue
offsets, seconds): requests become admissible only once their arrival
time passes, which is what the Poisson load benchmark
(benchmarks/serve_load.py) drives.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mips
from repro.launch import steps as steps_lib
from repro.models.config import ArchConfig
from repro.models.model import Model
from repro.serve import paging, scheduler as sched_lib

__all__ = ["ServeConfig", "Server", "RequestResult"]

_LOG = logging.getLogger("repro.serve")
# host spans of the engine loop, on the profiler's clock (the device
# trace's): one per loop phase, none per token
_span = jax.profiler.TraceAnnotation


def _warn(msg: str) -> None:
    """Single funnel for operator-facing serving diagnostics. Routed
    through ``logging`` (logger ``repro.serve``) so deployments aggregate
    them like any other log line; a stderr handler is installed lazily so
    bare scripts still see the warnings without logging config."""
    if not _LOG.handlers and not logging.getLogger().handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter("[server] %(levelname)s: %(message)s"))
        _LOG.addHandler(h)
    _LOG.warning(msg)


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 8
    max_seq: int = 512
    max_new_tokens: int = 64
    eos_id: int = -1  # -1: never stops early (synthetic workloads)
    seed: int = 0
    strict: bool = False  # exact in-dispatch re-sample when ok=False
    engine: str = "pipelined"  # pipelined | reference (single-step loop)
    decode_window: int = 8  # tokens decoded per dispatch (pipelined)
    prefill_chunk: int = 32  # prompt-length bucket granularity (pipelined)
    overlength: str = "truncate"  # truncate (keep newest) | reject
    probe_router: str = ""  # adaptive probe's learned stage router:
    #   "" disabled | "fit" train at startup on embedding-derived queries |
    #   a path to a router .npz saved by repro.models.router.save_router
    block_len: int = 0  # >0: paged KV pool with this block size (positions);
    #   0: dense slot-reserved rings (the historical layout)
    n_blocks: int = 0  # paged pool size; 0 = auto (batch_slots * pages per
    #   slot — same KV coverage as dense, for drop-in parity)
    sched: str = "fifo"  # admission scheduler: fifo | slo (serve/scheduler)
    ttft_slo_s: float = 0.5  # slo scheduler: per-request TTFT target

    @property
    def prompt_cap(self) -> int:
        """Longest admissible prompt: the length budget must leave room
        for max_new_tokens generated positions inside max_seq. Positive
        by construction — Server rejects max_new_tokens >= max_seq."""
        return self.max_seq - self.max_new_tokens

    @property
    def paged(self) -> bool:
        return self.block_len > 0


@dataclasses.dataclass
class RequestResult:
    request_id: int
    tokens: list
    ok_rate: float
    latency_s: float
    ttft_s: float = 0.0  # host-observed time to first token (from enqueue)
    itl_ms: float = 0.0  # host-observed mean inter-token latency
    queue_time_s: float = 0.0  # admission-queue wait (enqueue -> prefill
    #   dispatch) — the part of TTFT the scheduler/pool owns, as opposed
    #   to prefill compute
    prompt_len: int = 0  # admitted (possibly truncated) prompt length
    status: str = "ok"  # ok | rejected


def _bucket(n: int, chunk: int) -> int:
    """Prompt-length bucket: multiple of ``chunk``, then coarsened so the
    trunk's static tiling constraints hold (SSM chunk 128, attention
    q-block 512 must divide the padded length)."""
    out = -(-n // chunk) * chunk
    if out <= 128:
        return out
    if out <= 512:
        return -(-out // 128) * 128
    return -(-out // 512) * 512


# Positions a prefill dispatch should hold. A bf16 weight matmul on the
# TPU v5e turns compute-bound at 197 TFLOP/s / 819 GB/s ≈ 240 rows per
# weight read, rounded to 256: a smaller dispatch would wait on the
# weights, a larger one would compute padding for nothing.
_PREFILL_POSITIONS = 256


def _prefill_rows(lp: int, nslots: int, multiple: int = 1) -> int:
    """Rows of the prefill dispatch for prompt bucket ``lp``: enough for
    ``_PREFILL_POSITIONS`` positions, at least one, at most ``nslots``,
    rounded up to ``multiple`` (the mesh's batch shards, which divide
    ``nslots``). One count per bucket, so one program per bucket."""
    rows = min(nslots, max(1, -(-_PREFILL_POSITIONS // lp)))
    return min(nslots, -(-rows // multiple) * multiple)


class Server:
    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig, mesh=None):
        if scfg.engine not in ("pipelined", "reference"):
            raise ValueError(f"unknown engine {scfg.engine!r}")
        if scfg.overlength not in ("truncate", "reject"):
            raise ValueError(f"unknown overlength policy {scfg.overlength!r}")
        if scfg.decode_window < 1 or scfg.prefill_chunk < 1:
            raise ValueError("decode_window and prefill_chunk must be >= 1")
        if scfg.max_new_tokens >= scfg.max_seq:
            raise ValueError(
                f"max_new_tokens={scfg.max_new_tokens} leaves no room for "
                f"any prompt inside max_seq={scfg.max_seq}"
            )
        if scfg.strict and mesh is not None and "model" in mesh.shape:
            raise ValueError(
                "strict exact-fallback is not wired through the distributed "
                "head; serve with strict=False on a TP mesh"
            )
        if scfg.sched not in ("fifo", "slo"):
            raise ValueError(f"unknown scheduler {scfg.sched!r} (fifo | slo)")
        self.cfg = cfg
        self.scfg = scfg
        self.model = Model(cfg, mesh)
        self.params = params

        # ---- paged block pool geometry (None on the dense layout)
        self.spec: paging.PagedSpec | None = None
        self.alloc: paging.BlockAllocator | None = None
        paged_layout = None
        if scfg.paged:
            if scfg.engine != "pipelined":
                raise ValueError(
                    "paged cache layout requires engine='pipelined' (the "
                    "reference loop is the dense comparator)"
                )
            from repro.models.transformer import ring_len

            n_pages = paging.PagedSpec.from_arch(
                cfg, scfg.max_seq, scfg.block_len, 1
            ).n_pages
            n_blocks = scfg.n_blocks or scfg.batch_slots * n_pages
            self.spec = paging.PagedSpec.from_arch(
                cfg, scfg.max_seq, scfg.block_len, n_blocks
            )
            paged_layout = self.spec.layout
            # admission feasibility: the maximal admissible request must fit
            # the pool outright, or it could never be admitted (a permanent
            # stall, not a recoverable one)
            need_max = self.spec.pages_needed(scfg.prompt_cap,
                                              scfg.max_new_tokens)
            if need_max > self.spec.n_blocks:
                raise ValueError(
                    f"n_blocks={self.spec.n_blocks} cannot hold a maximal "
                    f"request (prompt_cap={scfg.prompt_cap} + "
                    f"max_new_tokens={scfg.max_new_tokens} needs {need_max} "
                    f"blocks of {scfg.block_len})"
                )
            # page-table overflow invariant: every position a request can
            # ever write ( < max_seq, enforced by admission + the device
            # done-rule) lands at page (pos % s_c) // block_len < n_pages.
            # Block exhaustion is therefore always an admission-time stall,
            # never an out-of-bounds page-table write.
            assert (scfg.prompt_cap + scfg.max_new_tokens <= scfg.max_seq
                    and self.spec.n_pages * scfg.block_len
                    == ring_len(cfg, scfg.max_seq)), (
                "page table does not cover the admissible position range"
            )
            self.alloc = paging.BlockAllocator(self.spec)
        self.sched = sched_lib.make_scheduler(scfg.sched, scfg.ttft_slo_s)
        # fused-window variants the slo scheduler may pick per dispatch
        # (compiled lazily on first use; fifo only ever uses the largest)
        self._windows = sorted({1, max(1, scfg.decode_window // 4),
                                scfg.decode_window})
        if scfg.sched == "fifo":
            self._windows = [scfg.decode_window]
        self._itl_ms = 0.0  # EWMA per-token decode wall time (slo feedback)
        # canonical shardings for the engine's device state: without a
        # fixed target, a fresh host-built state (single-device) and the
        # previous dispatch's GSPMD-placed outputs hash as different jit
        # signatures and every run would recompile the engine steps
        self._cache_sh = self._state_sh = None
        # prefill rows come in multiples of the cache's batch shards, so
        # that a short prefill still splits across them
        self._row_multiple = 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.launch import mesh as mesh_lib

            shapes = jax.eval_shape(
                lambda: self.model.init_cache(scfg.batch_slots, scfg.max_seq,
                                              paged=paged_layout)
            )
            self._cache_sh = mesh_lib.cache_shardings(shapes, mesh, cfg,
                                                      paged=scfg.paged)
            rep = NamedSharding(mesh, P())
            state_keys = ["ids", "pos", "active", "budget", "rid"]
            if scfg.paged:
                state_keys.append("pages")
            self._state_sh = {k: rep for k in state_keys}
            shards = int(np.prod([mesh.shape[a]
                                  for a in mesh_lib.fsdp_axes(mesh)]))
            if scfg.batch_slots % shards == 0:
                self._row_multiple = shards

        def _pin(cache, state):
            if self._cache_sh is None:
                return cache, state
            cache = jax.lax.with_sharding_constraint(cache, self._cache_sh)
            state = jax.lax.with_sharding_constraint(state, self._state_sh)
            return cache, state

        # the decode program: cache + per-slot state are device-resident
        # and donated through every dispatch. The token count is an
        # operand, so every window the scheduler picks — and the
        # reference loop's single token — runs this one compiled program.
        decode_core = steps_lib.make_decode_loop_step(
            self.model, scfg.decode_window, scfg.eos_id, scfg.max_seq,
            strict=scfg.strict, paged=scfg.paged,
        )

        def decode_step(params, cache, state, base_key, n, index=None,
                        router=None):
            cache, state, toks, oks, emitted, widths = decode_core(
                params, cache, state, base_key, n, index, router
            )
            cache, state = _pin(cache, state)
            return cache, state, toks, oks, emitted, widths

        self.step_fn = jax.jit(decode_step, donate_argnums=(1, 2))

        prefill_core = steps_lib.make_prefill_into_cache_step(
            self.model, scfg.max_seq, scfg.eos_id, scfg.max_new_tokens,
            strict=scfg.strict, paged=scfg.paged,
        )

        def prefill_step(params, cache, state, tokens, lengths, slots, rids,
                         base_key, index=None, pages=None):
            cache, state, nxt, ok = prefill_core(
                params, cache, state, tokens, lengths, slots, rids,
                base_key, index, pages,
            )
            cache, state = _pin(cache, state)
            return cache, state, nxt, ok

        self.prefill_fn = jax.jit(prefill_step, donate_argnums=(1, 2))
        self.cache = self.model.init_cache(scfg.batch_slots, scfg.max_seq,
                                           paged=paged_layout)
        self.key = jax.random.key(scfg.seed)
        self.stats = {
            "steps": 0, "tokens": 0, "ok": 0, "fallbacks": 0,
            "prefill_dispatches": 0, "decode_dispatches": 0,
            "prefill_tokens": 0, "rejected": 0,
            # positions the prefill programs computed: rows x the prompt
            # bucket per dispatch (prefill_tokens of them are real)
            "prefill_positions": 0,
            # adaptive probe: emitted-token counts per effective probe
            # width {width: count} — empty on fixed-width serving
            "probe_width_hist": {},
            # continuous-batching gauges (last-seen + peak): admission
            # queue depth, live-slot occupancy, block-pool utilization,
            # and admission stalls caused by an empty block free-list
            "queue_depth": 0, "queue_depth_peak": 0,
            "slot_occupancy": 0, "slot_occupancy_peak": 0,
            "block_util": 0.0, "block_util_peak": 0.0,
            "block_stalls": 0,
            # HBM bytes resident in the serving cache (pool or rings +
            # SSM/LRU state) — the denominator of the paged-concurrency win
            "cache_bytes": sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(self.cache)
            ),
        }
        # head MIPS index: built once over the frozen output embedding
        # (a ShardedIndex on a TP mesh — per-slice probe inside the
        # distributed head's shard_map)
        self.index = self.model.make_head_index(params)
        self._index_health(where="build")
        self.router = self._make_router()

        @jax.jit
        def _reset_slots(cache, mask):
            # zero a recycled slot's caches (batch is axis 1: leaves are
            # (layer_stack, B, ...)) so SSM/RG-LRU state never bleeds
            # between requests. Only the reference loop needs this — the
            # engine's prefill_into_cache replaces the slot state wholesale.
            def one(a):
                m = mask.reshape((1, -1) + (1,) * (a.ndim - 2))
                return jnp.where(m, jnp.zeros_like(a), a)

            return jax.tree.map(one, cache)

        self._reset_slots = _reset_slots

    def _fresh_state(self) -> dict:
        """The engine's per-slot device state with every slot idle."""
        nslots = self.scfg.batch_slots
        state = {
            "ids": jnp.zeros((nslots,), jnp.int32),
            "pos": jnp.zeros((nslots,), jnp.int32),
            "active": jnp.zeros((nslots,), bool),
            "budget": jnp.zeros((nslots,), jnp.int32),
            "rid": jnp.full((nslots,), -1, jnp.int32),
        }
        if self.spec is not None:
            state["pages"] = jnp.full((nslots, self.spec.n_pages),
                                      self.spec.sentinel, jnp.int32)
        return state

    def decode_program_text(self) -> str:
        """Optimized HLO of this server's decode program (both engines run
        it), compiled from shapes."""
        state = jax.eval_shape(self._fresh_state)
        n = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = self.step_fn.lower(
            self.params, self.cache, state, self.key, n, self.index,
            self.router,
        )
        return lowered.compile().as_text()

    def _index_health(self, where: str) -> None:
        """Surface index health where an operator looks: ``stats`` carries
        the index's device-HBM footprint and its coverage shortfall, and
        the two shortfall kinds warn with their own remedies (dropped rows
        vs a statically unfillable re-rank pool — mips.index_spill_parts)."""
        dropped, short = mips.index_spill_parts(self.index)
        self.stats["index_spill"] = dropped + short
        self.stats["index_bytes"] = (
            self.index.memory_bytes() if self.index is not None else 0
        )
        if dropped:  # coverage contract (DESIGN.md §3) violated
            _warn(f"index {where} dropped {dropped} rows — raise "
                  f"overflow_frac")
        if short:
            hc = self.model.head_cfg
            # one call site, remedy keyed on the probe mode: with a fixed
            # width the knob is n_probe; once width is dynamic the pool is
            # sized by the per-query effective width, so the ceiling (and
            # the certificate slack driving widening) is what to move
            knob = (
                f"at effective probe width <= {hc.n_probe_max} (adaptive; "
                f"see stats['probe_width_hist']) — lower PQConfig.rerank "
                f"or raise n_probe_max"
                if hc.adaptive_probe
                else "— lower PQConfig.rerank or raise n_probe"
            )
            _warn(f"re-rank pool short {short} slots {knob}")

    def _make_router(self):
        """Build the adaptive probe's stage router per ``scfg.probe_router``
        ("" disabled / "fit" supervised startup fit / an .npz path). The
        startup fit synthesizes queries from the embedding rows the index
        serves (scaled like serving-temperature hiddens), labels each with
        its first certificate-passing stage, and trains the tiny MLP — all
        device-side, a one-time cost."""
        spec = self.scfg.probe_router
        hc = self.model.head_cfg
        if not spec:
            return None
        if not hc.adaptive_probe or self.index is None:
            _warn("probe_router set but adaptive probe is off "
                  "(head_adaptive_probe) — router ignored")
            return None
        from repro.models import router as router_lib

        if spec != "fit":
            return router_lib.load_router(spec)
        state = getattr(self.index, "state", None)
        if state is None or not hasattr(state, "centroids"):
            _warn("probe_router='fit' needs a single-device clustered "
                  "index — router disabled")
            return None
        emb = self.model.head_index_db(self.params)
        stride = max(1, emb.shape[0] // 512)
        qs = emb[::stride][:512].astype(jnp.float32)
        qs = qs / jnp.maximum(
            jnp.linalg.norm(qs, axis=1, keepdims=True), 1e-6
        ) * 8.0  # low-temperature serving queries: peaked score profiles
        return router_lib.train_router(
            self.index, qs, hc.k, c=hc.c, seed=self.scfg.seed
        )

    def _bin_widths(self, widths: np.ndarray, mask: np.ndarray) -> None:
        """Accumulate emitted tokens' effective probe widths into
        ``stats["probe_width_hist"]`` (−1 sentinel = fixed-width path)."""
        sel = widths >= 0
        if mask is not None:
            sel &= mask
        w = widths[sel]
        if w.size == 0:
            return
        hist = self.stats["probe_width_hist"]
        vals, counts = np.unique(w, return_counts=True)
        for v, n in zip(vals.tolist(), counts.tolist()):
            hist[int(v)] = hist.get(int(v), 0) + int(n)

    def refresh_index(self, params=None) -> None:
        """Hot-swap the head index (e.g. after a params push).

        ``refresh`` preserves the index's pytree structure — per-shard
        geometry and leaf shardings included for a sharded index — so the
        jitted steps keep their compiled executables.
        """
        if params is not None:
            self.params = params
        if self.index is None:
            self.index = self.model.make_head_index(self.params)
        else:
            self.index = self.index.refresh(
                self.model.head_index_db(self.params)
            )
        self._index_health(where="refresh")

    # ------------------------------------------------------------- admission
    def _validate(self, rid: int, prompt, results: list) -> list | None:
        """Admission control (over-length / empty prompts). Returns the
        admitted (possibly truncated) prompt, or None if rejected (a
        rejected RequestResult is appended to ``results``)."""
        s = self.scfg
        prompt = list(prompt)
        if not prompt or (len(prompt) > s.prompt_cap
                          and s.overlength == "reject"):
            results.append(RequestResult(
                request_id=rid, tokens=[], ok_rate=0.0, latency_s=0.0,
                prompt_len=len(prompt), status="rejected",
            ))
            self.stats["rejected"] += 1
            return None
        if len(prompt) > s.prompt_cap:  # keep the newest context
            prompt = prompt[-s.prompt_cap:]
        return prompt

    def _intake(self, prompts, results: list, t_start: float,
                arrivals=None, priorities=None):
        """Validate + register every prompt. ``arrivals`` (per-request
        enqueue offsets from run start, seconds — the open-loop load
        model) and ``priorities`` (lower = more urgent, slo scheduler)
        default to 0. Returns (arrival-ordered [(t_enq, rid)] list,
        rid -> request record); rejected prompts land in ``results``."""
        due: list[tuple[float, int]] = []
        reqs: dict[int, dict] = {}
        for rid, prompt in enumerate(prompts):
            p = self._validate(rid, prompt, results)
            if p is None:
                continue
            t_enq = t_start + (float(arrivals[rid]) if arrivals is not None
                               else 0.0)
            reqs[rid] = {
                "rid": rid, "prompt": p, "out": [], "ok": 0, "fed": 0,
                "t_enq": t_enq, "t_admit": None,
                "t_first": None, "t_last": None,
                "priority": (int(priorities[rid]) if priorities is not None
                             else 0),
                "blocks": [],
                "pages_needed": (
                    self.spec.pages_needed(len(p), self.scfg.max_new_tokens)
                    if self.spec is not None else 0
                ),
            }
            due.append((t_enq, rid))
        due.sort()
        return due, reqs

    def _finalize(self, req: dict, results: list) -> None:
        now = time.perf_counter()
        n = len(req["out"])
        itl = 0.0
        if n > 1 and req["t_first"] is not None:
            itl = (req["t_last"] - req["t_first"]) / (n - 1) * 1e3
        results.append(RequestResult(
            request_id=req["rid"], tokens=req["out"],
            ok_rate=req["ok"] / max(n, 1),
            latency_s=now - req["t_enq"],
            ttft_s=(req["t_first"] or now) - req["t_enq"],
            itl_ms=itl,
            queue_time_s=max(0.0, (req["t_admit"] or now) - req["t_enq"]),
            prompt_len=len(req["prompt"]),
        ))
        if self.alloc is not None and req["blocks"]:
            self.alloc.free(req["blocks"])
            req["blocks"] = []

    def _mirror_done(self, req: dict) -> bool:
        """Host mirror of the device's done rule (see steps._advance):
        budget exhausted, EOS, or the next position would exceed max_seq."""
        s = self.scfg
        n = len(req["out"])
        if n >= s.max_new_tokens:
            return True
        if s.eos_id >= 0 and req["out"] and req["out"][-1] == s.eos_id:
            return True
        return len(req["prompt"]) + n > s.max_seq - 1

    # ---------------------------------------------------------------- run
    def run(self, prompts: list[list[int]], *, arrivals=None,
            priorities=None) -> list[RequestResult]:
        """Decode all prompts with continuous batching; returns one
        RequestResult per prompt (rejected ones flagged).

        ``arrivals``: optional per-request enqueue offsets (seconds from
        run start) — the open-loop load model: a request only becomes
        admissible once its arrival passes, and ``queue_time_s``/TTFT are
        measured from it. ``priorities``: optional per-request priority
        (lower = more urgent; consumed by the slo scheduler)."""
        if self.scfg.engine == "reference":
            if arrivals is not None or priorities is not None:
                raise ValueError(
                    "arrivals/priorities need the pipelined engine"
                )
            return self._run_reference(prompts)
        return self._run_engine(prompts, arrivals=arrivals,
                                priorities=priorities)

    # ------------------------------------------------------- pipelined engine
    def _gauges(self, n_queued: int, slot_req: list) -> None:
        occ = sum(r is not None for r in slot_req)
        st = self.stats
        st["queue_depth"] = n_queued
        st["queue_depth_peak"] = max(st["queue_depth_peak"], n_queued)
        st["slot_occupancy"] = occ
        st["slot_occupancy_peak"] = max(st["slot_occupancy_peak"], occ)
        if self.alloc is not None:
            st["block_util"] = self.alloc.utilization
            st["block_util_peak"] = max(st["block_util_peak"],
                                        st["block_util"])

    def _run_engine(self, prompts: list[list[int]], arrivals=None,
                    priorities=None) -> list[RequestResult]:
        s = self.scfg
        nslots = s.batch_slots
        results: list[RequestResult] = []
        t_start = time.perf_counter()
        self.key, base_key = jax.random.split(self.key)
        due, reqs = self._intake(prompts, results, t_start,
                                 arrivals, priorities)
        due = collections.deque(due)  # arrival-sorted (t_enq, rid)
        waiting: list[int] = []  # arrived, not yet admitted

        state = self._fresh_state()
        cache = self.cache
        if self._cache_sh is not None:  # one jit signature across runs
            state = jax.device_put(state, self._state_sh)
            cache = jax.device_put(cache, self._cache_sh)
        slot_req: list[int | None] = [None] * nslots
        free = list(range(nslots))
        # dispatch pipeline: FIFO of un-synced device results; one entry is
        # kept in flight so host bookkeeping overlaps device compute
        pending: collections.deque = collections.deque()

        def retire(req, slot) -> None:
            # device already froze the slot (done computed on-device in the
            # same dispatch), so any in-flight window has active=False /
            # write_mask dropping its KV writes — freeing its blocks for
            # the NEXT admission dispatch is ordered-safe
            self._finalize(req, results)
            slot_req[slot] = None
            free.append(slot)

        def rows_for(lp: int) -> int:
            return _prefill_rows(lp, nslots, self._row_multiple)

        def host_chunk(batch, slots_h):
            """Host arrays (tokens, lengths, slots, rids, pages or None) of
            one prefill dispatch: ``rows_for(lp)`` rows of the chunk's
            bucket ``lp``, pad rows at slot ``nslots`` and rid -1."""
            lp = _bucket(max(len(reqs[r]["prompt"]) for r in batch),
                         s.prefill_chunk)
            n = rows_for(lp)
            tokens = np.zeros((n, lp), np.int32)
            lengths = np.ones((n,), np.int32)
            slots = np.full((n,), nslots, np.int32)  # pad rows
            rids = np.full((n,), -1, np.int32)
            for row, (rid, slot) in enumerate(zip(batch, slots_h)):
                p = reqs[rid]["prompt"]
                tokens[row, : len(p)] = p
                lengths[row] = len(p)
                slots[row] = slot
                rids[row] = rid
            pages = None
            if self.spec is not None:
                pages = np.full((n, self.spec.n_pages), self.spec.sentinel,
                                np.int32)
                for row, rid in enumerate(batch):
                    pages[row] = paging.page_row(self.spec,
                                                 reqs[rid]["blocks"])
            return tokens, lengths, slots, rids, pages

        def admit(now):
            """Scheduler order and block allocation, then the admitted
            requests cut into prefill chunks, in scheduler order: a
            request joins the open chunk if the chunk, with it, has no
            more rows than ``rows_for`` gives for the bucket of its
            longest prompt; otherwise it opens the next chunk. A list of
            (rids, slots, host arrays) per prefill dispatch, empty if
            none was admitted."""
            free.sort()
            batch: list[int] = []
            slots_h: list[int] = []
            for rid in self.sched.order(waiting, reqs, now):
                if not free:
                    break
                req = reqs[rid]
                if self.alloc is not None:
                    if not self.alloc.can_alloc(req["pages_needed"]):
                        self.stats["block_stalls"] += 1
                        if self.sched.skip_blocked:
                            continue  # smaller requests may still fit
                        break  # fifo: strict head-of-line order
                    req["blocks"] = self.alloc.alloc(req["pages_needed"])
                batch.append(rid)
                slots_h.append(free.pop(0))
            t_admit = time.perf_counter()
            chunks: list[tuple[list[int], list[int]]] = []
            lp = 0  # the open chunk's bucket
            for rid, slot in zip(batch, slots_h):
                waiting.remove(rid)
                slot_req[slot] = rid
                reqs[rid]["t_admit"] = t_admit
                b = _bucket(len(reqs[rid]["prompt"]), s.prefill_chunk)
                if chunks and len(chunks[-1][0]) < rows_for(max(lp, b)):
                    lp = max(lp, b)
                else:
                    chunks.append(([], []))
                    lp = b
                chunks[-1][0].append(rid)
                chunks[-1][1].append(slot)
            return [(c, sl, host_chunk(c, sl)) for c, sl in chunks]

        def process(entry) -> None:
            kind = entry[0]
            with _span("serve.sync"):
                arrs = [np.asarray(a) for a in entry[1]]
            with _span("serve.emit"):
                if kind == "prefill":
                    emit_prefill(arrs, *entry[2:])
                else:
                    emit_decode(arrs, *entry[2:])

        def emit_prefill(arrs, batch, slots_h) -> None:
            nxt, ok = arrs
            now = time.perf_counter()
            for row, (rid, slot) in enumerate(zip(batch, slots_h)):
                req = reqs[rid]
                req["out"].append(int(nxt[row]))
                req["ok"] += bool(ok[row])
                req["t_first"] = req["t_last"] = now
                self.stats["tokens"] += 1
                self.stats["ok"] += bool(ok[row])
                if s.strict and not ok[row]:
                    self.stats["fallbacks"] += 1
                if self._mirror_done(req):
                    retire(req, slot)

        def emit_decode(arrs, snapshot, window, t_issue) -> None:
            toks, oks, emitted, widths = arrs
            # per-token wall EWMA — the slo scheduler's window-cost
            # estimate (includes pipeline overlap: a consistent,
            # slightly pessimistic feedback signal)
            dt_ms = (time.perf_counter() - t_issue) * 1e3 / window
            self._itl_ms = (dt_ms if self._itl_ms == 0.0
                            else 0.7 * self._itl_ms + 0.3 * dt_ms)
            self._bin_widths(widths, emitted)
            now = time.perf_counter()
            for t in range(window):
                for slot in range(nslots):
                    if not emitted[t, slot]:
                        continue
                    rid = snapshot[slot]
                    if rid is None:  # defensive: device-only slot
                        continue
                    req = reqs[rid]
                    req["out"].append(int(toks[t, slot]))
                    req["ok"] += bool(oks[t, slot])
                    req["t_last"] = now
                    self.stats["tokens"] += 1
                    self.stats["ok"] += bool(oks[t, slot])
                    if s.strict and not oks[t, slot]:
                        self.stats["fallbacks"] += 1
                    if self._mirror_done(req):
                        retire(req, slot)

        while len(results) < len(prompts):
            now = time.perf_counter()
            # 0) open-loop arrivals: requests become admissible as their
            # enqueue time passes
            while due and due[0][0] <= now:
                waiting.append(due.popleft()[1])
            self._gauges(len(waiting) + len(due), slot_req)
            # 1) streaming admission: whenever a slot AND (paged) blocks
            # free up, in scheduler order — one batched-prefill dispatch a
            # chunk
            if waiting and free:
                with _span("serve.admit") as span:
                    chunks = admit(now)
                    if chunks and _span.is_enabled():
                        span.set_metadata(rids=" ".join(
                            str(r) for c in chunks for r in c[0]))
                for batch, slots_h, host in chunks:
                    tokens, lengths, slots, rids, pages = host
                    with _span("serve.prefill"):
                        cache, state, nxt, ok = self.prefill_fn(
                            self.params, cache, state, jnp.asarray(tokens),
                            jnp.asarray(lengths), jnp.asarray(slots),
                            jnp.asarray(rids), base_key, self.index,
                            None if pages is None else jnp.asarray(pages),
                        )
                    pending.append(("prefill", (nxt, ok), batch, slots_h))
                    self.stats["prefill_dispatches"] += 1
                    self.stats["steps"] += 1
                    self.stats["prefill_tokens"] += int(
                        sum(len(reqs[r]["prompt"]) for r in batch)
                    )
                    self.stats["prefill_positions"] += tokens.size
                if chunks:
                    # re-sample: occupancy/block gauges peak right after
                    # admission fills slots, not at next loop-top (by which
                    # point a uniform wave may have retired in lockstep)
                    self._gauges(len(waiting) + len(due), slot_req)
            # 2) fused decode over the slots the host believes live, window
            # picked per dispatch (slo: shrinks under TTFT pressure)
            live = any(r is not None for r in slot_req)
            if live:
                with _span("serve.decode"):
                    window = self.sched.pick_window(
                        waiting, reqs, now, self._itl_ms, self._windows
                    )
                    t_issue = time.perf_counter()
                    cache, state, toks, oks, emitted, widths = self.step_fn(
                        self.params, cache, state, base_key,
                        np.int32(window), self.index, self.router,
                    )
                pending.append(("decode", (toks, oks, emitted, widths),
                                list(slot_req), window, t_issue))
                self.stats["decode_dispatches"] += 1
                self.stats["steps"] += 1
            # 3) sync all but the newest dispatch (double buffering)
            while len(pending) > 1:
                process(pending.popleft())
            if not live and not waiting and not pending:
                if not due:
                    break  # nothing left to dispatch: drain below
                # idle until the next open-loop arrival
                with _span("serve.idle"):
                    time.sleep(max(0.0, min(
                        due[0][0] - time.perf_counter(), 0.05
                    )))

        while pending:
            process(pending.popleft())
        self._gauges(0, slot_req)  # final sample: drained, slots retired

        self.cache = cache
        self.stats["wall_s"] = time.perf_counter() - t_start
        return sorted(results, key=lambda r: r.request_id)

    # -------------------------------------------------- reference single-step
    def _run_reference(self, prompts: list[list[int]]) -> list[RequestResult]:
        """Teacher-forced single-step loop: one dispatch per token, prompts
        fed through the decode path — the engine's decode program with a
        window of one token, its slot state set by the host each step.
        Kept as the engine's correctness comparator (same key discipline ⇒
        identical samples) and as the benchmark baseline for the
        fused/pipelined speedup."""
        s = self.scfg
        nslots = s.batch_slots
        results: list[RequestResult] = []
        t_start = time.perf_counter()
        self.key, base_key = jax.random.split(self.key)
        due, reqs = self._intake(prompts, results, t_start)
        queue = collections.deque(rid for _, rid in due)

        active: list[int | None] = [None] * nslots
        ids_h = np.zeros((nslots,), np.int32)
        pos_h = np.zeros((nslots,), np.int32)
        rids_h = np.full((nslots,), -1, np.int32)
        cache = self.cache
        if self._cache_sh is not None:  # the engine's jit signature
            cache = jax.device_put(cache, self._cache_sh)

        def admit(slot) -> None:
            if not queue:
                return
            rid = queue.popleft()
            reqs[rid]["t_admit"] = time.perf_counter()
            active[slot] = rid
            rids_h[slot] = rid
            pos_h[slot] = 0
            ids_h[slot] = 0
            mask = np.zeros((nslots,), bool)
            mask[slot] = True
            nonlocal cache
            cache = self._reset_slots(cache, jnp.asarray(mask))

        for i in range(nslots):
            admit(i)

        while any(a is not None for a in active):
            for i, rid in enumerate(active):
                if rid is None:
                    continue
                req = reqs[rid]
                if req["fed"] < len(req["prompt"]):
                    ids_h[i] = req["prompt"][req["fed"]]
                else:
                    ids_h[i] = req["out"][-1]
            live = np.asarray([a is not None for a in active])
            state = {
                "ids": jnp.asarray(ids_h), "pos": jnp.asarray(pos_h),
                "active": jnp.asarray(live),
                "budget": jnp.full((nslots,), s.max_seq, jnp.int32),
                "rid": jnp.asarray(rids_h),
            }
            cache, _, toks, oks, _, widths = self.step_fn(
                self.params, cache, state, base_key, np.int32(1), self.index,
                self.router,
            )
            nxt_h = np.asarray(toks[0])
            ok_h = np.asarray(oks[0])
            self._bin_widths(np.asarray(widths[:1]), live[None])
            self.stats["steps"] += 1
            now = time.perf_counter()
            pos_h = pos_h + live  # idle slots stay frozen, as in the engine
            for i, rid in enumerate(active):
                if rid is None:
                    continue
                req = reqs[rid]
                if req["fed"] < len(req["prompt"]):
                    req["fed"] += 1
                    if req["fed"] < len(req["prompt"]):
                        continue  # mid-prompt: sample discarded
                    # the last prompt token's sample IS the first output
                    # (the old loop dropped it and fed a spurious 0 token)
                req["out"].append(int(nxt_h[i]))
                req["ok"] += bool(ok_h[i])
                if req["t_first"] is None:
                    req["t_first"] = now
                req["t_last"] = now
                self.stats["tokens"] += 1
                self.stats["ok"] += bool(ok_h[i])
                if s.strict and not ok_h[i]:
                    self.stats["fallbacks"] += 1
                if self._mirror_done(req):
                    self._finalize(req, results)
                    active[i] = None
                    rids_h[i] = -1
                    admit(i)

        self.cache = cache
        self.stats["wall_s"] = time.perf_counter() - t_start
        return sorted(results, key=lambda r: r.request_id)
