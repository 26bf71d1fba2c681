"""Serving-engine behaviour: batched prefill + fused decode vs the
single-step reference loop, continuous-batching semantics, admission
control, and the engine's observability fields."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.transformer as T
from repro.configs import get_smoke
from repro.models.model import Model
from repro.serve.server import ServeConfig, Server, _bucket, _prefill_rows


@pytest.fixture(autouse=True)
def _no_remat(monkeypatch):
    monkeypatch.setattr(T, "REMAT", False)


def _mk(arch="tinyllama-1.1b", **scale):
    cfg = get_smoke(arch).scaled(**scale)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, params


def _prompts(cfg, n, lo=3, hi=11, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab, size=int(rng.integers(lo, hi))))
            for _ in range(n)]


def _prompts_of(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab, size=n)) for n in lengths]


# ------------------------------------------------------------ equivalence
@pytest.mark.parametrize("head,vocab", [("exact", 512), ("amortized", 4096)])
def test_engine_matches_reference_bitwise(head, vocab):
    """Fused decode (T=8) + batched prefill must sample the SAME tokens as
    the teacher-forced one-dispatch-per-token loop: keys derive from
    (request, position), so fusion/batching cannot shift randomness."""
    cfg, params = _mk(vocab=vocab, head_mode=head)
    prompts = _prompts(cfg, 5)
    outs = {}
    for eng, window in (("reference", 1), ("pipelined", 8)):
        srv = Server(cfg, params, ServeConfig(
            batch_slots=2, max_seq=64, max_new_tokens=6, seed=7,
            engine=eng, decode_window=window))
        rs = srv.run(prompts)
        assert all(len(r.tokens) == 6 for r in rs)
        outs[eng] = {r.request_id: r.tokens for r in rs}
    assert outs["reference"] == outs["pipelined"]


def test_engine_matches_reference_ssm():
    """Same equivalence through the SSD-prefill / recurrent-decode pair."""
    cfg, params = _mk("mamba2-780m")
    prompts = _prompts(cfg, 4)
    outs = {}
    for eng, window in (("reference", 1), ("pipelined", 4)):
        srv = Server(cfg, params, ServeConfig(
            batch_slots=2, max_seq=64, max_new_tokens=5, seed=3,
            engine=eng, decode_window=window))
        outs[eng] = {r.request_id: r.tokens for r in srv.run(prompts)}
    assert outs["reference"] == outs["pipelined"]


def test_decode_window_invariance_griffin():
    """Griffin's parallel-scan prefill is numerically (not bitwise) equal
    to sequential decode in bf16, so we assert the window-fusion invariant
    instead: T=1 and T=8 engines — identical prefill path — must match
    exactly, and every request still completes."""
    cfg, params = _mk("recurrentgemma-9b")
    prompts = _prompts(cfg, 4)
    outs = {}
    for window in (1, 8):
        srv = Server(cfg, params, ServeConfig(
            batch_slots=2, max_seq=64, max_new_tokens=5, seed=3,
            decode_window=window))
        rs = srv.run(prompts)
        assert all(len(r.tokens) == 5 for r in rs)
        outs[window] = {r.request_id: r.tokens for r in rs}
    assert outs[1] == outs[8]


# ------------------------------------------------- continuous batching
def test_slot_recycling_many_requests():
    """#requests >> batch_slots: every request comes back complete, in
    order, with its own tokens (slot recycling can't mix streams)."""
    cfg, params = _mk(vocab=512)
    prompts = _prompts(cfg, 9, lo=2, hi=14)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=4, seed=1,
        decode_window=4))
    rs = srv.run(prompts)
    assert [r.request_id for r in rs] == list(range(9))
    assert all(len(r.tokens) == 4 for r in rs)
    assert all(0 <= t < cfg.vocab for r in rs for t in r.tokens)
    # recycled slots must reproduce the reference loop exactly, too
    srv2 = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=4, seed=1,
        engine="reference"))
    rs2 = srv2.run(prompts)
    assert [r.tokens for r in rs] == [r.tokens for r in rs2]
    ok1 = [r.ok_rate for r in rs]
    ok2 = [r.ok_rate for r in rs2]
    assert ok1 == ok2


def test_eos_frees_slot_for_readmission():
    """EOS mid-batch finalizes the request early and the freed slot serves
    the queue; with a tiny vocab streams hit EOS fast."""
    cfg, params = _mk(vocab=32)
    eos = 7
    prompts = _prompts(cfg, 8, lo=2, hi=6, seed=5)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=48, eos_id=eos, seed=2,
        decode_window=4))
    rs = srv.run(prompts)
    assert len(rs) == 8
    for r in rs:
        assert len(r.tokens) >= 1
        if len(r.tokens) < 48:  # stopped early => must have been EOS
            assert r.tokens[-1] == eos
        assert eos not in r.tokens[:-1]  # and only at the end
    # identical early-stop behaviour in the reference loop
    srv2 = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=48, eos_id=eos, seed=2,
        engine="reference"))
    rs2 = srv2.run(prompts)
    assert [r.tokens for r in rs] == [r.tokens for r in rs2]


# ------------------------------------------------------ admission control
def test_overlength_prompt_truncated():
    """Regression: a prompt longer than max_seq - max_new_tokens used to
    walk pos past the KV cache (the done check was skipped while
    prefilling). Truncation keeps the newest context and must behave
    exactly like submitting the pre-truncated prompt."""
    cfg, params = _mk(vocab=512)
    scfg = dict(batch_slots=2, max_seq=32, max_new_tokens=8, seed=4)
    cap = 32 - 8
    long_prompt = list(np.random.default_rng(0).integers(0, 512, size=60))
    short = _prompts(cfg, 1, lo=4, hi=5)[0]
    rs = Server(cfg, params, ServeConfig(**scfg)).run([long_prompt, short])
    assert all(r.status == "ok" for r in rs)
    assert len(rs[0].tokens) == 8
    assert rs[0].prompt_len == cap
    rs_pre = Server(cfg, params, ServeConfig(**scfg)).run(
        [long_prompt[-cap:], short])
    assert rs[0].tokens == rs_pre[0].tokens
    # reference loop applies the same admission rule
    rs_ref = Server(cfg, params, ServeConfig(
        engine="reference", **scfg)).run([long_prompt, short])
    assert rs_ref[0].tokens == rs[0].tokens


def test_overlength_prompt_rejected():
    cfg, params = _mk(vocab=512)
    long_prompt = list(range(60))
    ok_prompt = [1, 2, 3]
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=32, max_new_tokens=8, overlength="reject"))
    rs = srv.run([long_prompt, ok_prompt, []])
    assert [r.status for r in rs] == ["rejected", "ok", "rejected"]
    assert rs[0].tokens == [] and rs[2].tokens == []
    assert len(rs[1].tokens) == 8
    assert srv.stats["rejected"] == 2


def test_length_budget_never_exceeds_max_seq():
    """prompt + generated tokens always fit inside max_seq, and a config
    whose token budget leaves no room for any prompt is rejected."""
    cfg, params = _mk(vocab=512)
    with pytest.raises(ValueError):  # max_new >= max_seq: unsatisfiable
        Server(cfg, params, ServeConfig(
            batch_slots=1, max_seq=16, max_new_tokens=64))
    srv = Server(cfg, params, ServeConfig(
        batch_slots=1, max_seq=16, max_new_tokens=8, overlength="truncate"))
    (r,) = srv.run([list(range(14))])  # truncated to cap = 8
    assert r.prompt_len == 8
    assert r.prompt_len + len(r.tokens) <= 16
    assert len(r.tokens) == 8


# ------------------------------------------------------ observability
def test_latency_fields_and_stats():
    cfg, params = _mk(vocab=512)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=6, decode_window=3))
    rs = srv.run(_prompts(cfg, 4))
    for r in rs:
        assert r.ttft_s > 0.0
        assert r.itl_ms >= 0.0
        assert r.latency_s >= r.ttft_s
        assert r.prompt_len >= 1
    st = srv.stats
    assert st["prefill_dispatches"] >= 1
    assert st["decode_dispatches"] >= 1
    assert st["prefill_tokens"] == sum(r.prompt_len for r in rs)
    assert st["tokens"] == sum(len(r.tokens) for r in rs)
    # fused decode: far fewer dispatches than tokens
    assert st["steps"] < st["tokens"]


def test_strict_mode_smoke():
    """strict=True re-samples flagged tokens in-dispatch; with a healthy
    index the flag rarely fires, so mostly assert it runs and matches the
    strict reference loop."""
    cfg, params = _mk(vocab=4096, head_mode="amortized", head_k=64,
                      head_l=64)
    prompts = _prompts(cfg, 3)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=4, seed=9, strict=True,
        decode_window=4))
    rs = srv.run(prompts)
    assert all(len(r.tokens) == 4 for r in rs)
    assert srv.stats["fallbacks"] == srv.stats["tokens"] - srv.stats["ok"]


def test_bucket_static_tiling():
    assert _bucket(5, 32) == 32
    assert _bucket(32, 32) == 32
    assert _bucket(130, 32) == 256  # >128: coarsened to a 128 multiple
    assert _bucket(513, 32) == 1024  # >512: coarsened to a 512 multiple
    assert _bucket(1, 1) == 1


def test_serve_config_validation():
    cfg, params = _mk(vocab=512)
    with pytest.raises(ValueError):
        Server(cfg, params, ServeConfig(engine="warp"))
    with pytest.raises(ValueError):
        Server(cfg, params, ServeConfig(overlength="explode"))
    with pytest.raises(ValueError):
        Server(cfg, params, ServeConfig(decode_window=0))


def test_prefill_positions_count_what_prefill_computes():
    """Each prefill dispatch computes rows x its prompt bucket positions,
    real tokens and padding alike."""
    cfg, params = _mk(vocab=512)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=128, max_new_tokens=4, decode_window=2,
        prefill_chunk=16))
    srv.run([[1] * 5, [2] * 20])  # admitted together: bucket 32
    assert srv.stats["prefill_dispatches"] == 1
    assert srv.stats["prefill_positions"] == 2 * 32
    srv.run([[3] * 40])  # bucket 48
    assert srv.stats["prefill_positions"] == 2 * 32 + 2 * 48
    assert srv.stats["prefill_tokens"] == 5 + 20 + 40


def test_prefill_rows_per_bucket():
    """256 positions a dispatch, one row at least, every slot at most,
    in multiples of the mesh's batch shards."""
    assert [_prefill_rows(lp, 32) for lp in (128, 256, 384)] == [2, 1, 1]
    assert _prefill_rows(32, 32) == 8
    assert _prefill_rows(16, 4) == 4
    assert _prefill_rows(1024, 32) == 1
    assert _prefill_rows(128, 32, multiple=4) == 4
    assert _prefill_rows(16, 2, multiple=2) == 2


def test_lone_long_prompt_prefills_one_row():
    """A lone 200-token prompt (bucket 256) is prefilled in one row, not
    in every slot."""
    cfg, params = _mk(vocab=512)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=4, max_seq=256, max_new_tokens=4, decode_window=2,
        prefill_chunk=16))
    (r,) = srv.run([[1] * 200])
    assert len(r.tokens) == 4
    assert srv.stats["prefill_dispatches"] == 1
    assert srv.stats["prefill_positions"] == 1 * 256


def test_short_prompts_share_one_dispatch():
    """Four 10-token prompts admitted together fit R(16) = 4 rows: one
    dispatch, no pad row."""
    cfg, params = _mk(vocab=512)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=4, max_seq=64, max_new_tokens=4, decode_window=2,
        prefill_chunk=16))
    rs = srv.run([[i + 1] * 10 for i in range(4)])
    assert all(len(r.tokens) == 4 for r in rs)
    assert srv.stats["prefill_dispatches"] == 1
    assert srv.stats["prefill_positions"] == 4 * 16


@pytest.mark.parametrize("block_len", [0, 16], ids=["dense", "paged"])
def test_prefill_chunks_match_reference_bitwise(block_len):
    """A burst wider than its buckets' row counts is cut into chunks in
    scheduler order and still samples the reference loop's tokens. Two
    waves of four (slots 4, every request the same length budget):
    [100, 10 | 120, 20] give (3, 112) and (2, 128); [200 | 30, 60, 70]
    give (1, 256) and (4, 80): four dispatches, one program a bucket."""
    cfg, params = _mk(vocab=512)
    prompts = _prompts_of(cfg, [100, 10, 120, 20, 200, 30, 60, 70])
    base = dict(batch_slots=4, max_seq=256, max_new_tokens=4, seed=3)
    ref = Server(cfg, params, ServeConfig(engine="reference", **base))
    srv = Server(cfg, params, ServeConfig(
        decode_window=2, prefill_chunk=16, block_len=block_len, **base))
    r_ref, r_srv = ref.run(prompts), srv.run(prompts)
    assert all(len(r.tokens) == 4 for r in r_srv)
    assert [r.tokens for r in r_srv] == [r.tokens for r in r_ref]
    assert srv.stats["prefill_dispatches"] == 4
    assert srv.stats["prefill_positions"] == (3 * 112 + 2 * 128 + 1 * 256
                                              + 4 * 80)
    assert srv.stats["prefill_tokens"] == sum(map(len, prompts))
    assert srv.prefill_fn._cache_size() == 4


def _program_scopes(compiled) -> set[str]:
    """Components of the compiled program's op names, transform wrappers
    (``vmap(...)``) dropped."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {re.sub(r"^(?:[\w.-]*\()+|\)+$", "", c)
            for n in names for c in n.split("/")}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_step_programs_carry_layer_scopes(program):
    """The trunk's attention and MLP and the amortized head are named in
    the compiled serving programs, where a device trace finds them."""
    cfg, params = _mk(vocab=4096, head_mode="amortized")
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=4, decode_window=2))
    state = jax.eval_shape(srv._fresh_state)
    if program == "decode":
        lowered = srv.step_fn.lower(
            srv.params, srv.cache, state, srv.key,
            jax.ShapeDtypeStruct((), jnp.int32), srv.index, srv.router)
    else:
        rows = jax.ShapeDtypeStruct((2,), jnp.int32)
        lowered = srv.prefill_fn.lower(
            srv.params, srv.cache, state,
            jax.ShapeDtypeStruct((2, 32), jnp.int32), rows, rows, rows,
            srv.key, srv.index, None)
    assert {"attention", "mlp", "head"} <= _program_scopes(lowered.compile())


def test_engine_loop_writes_host_spans(tmp_path):
    """One span per loop phase on the profiler's clock: admissions with
    their request ids, dispatches, syncs, bookkeeping and idle waits; no
    more bookkeeping spans than dispatches (none per token)."""
    from jax.profiler import ProfileData

    cfg, params = _mk(vocab=512)
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_seq=64, max_new_tokens=6, decode_window=2))
    prompts = _prompts(cfg, 3)
    srv.run(prompts[:1])  # compiles both programs
    srv.stats["prefill_dispatches"] = srv.stats["decode_dispatches"] = 0
    with jax.profiler.trace(str(tmp_path)):
        # the engine idles before the first arrival
        srv.run(prompts, arrivals=[0.2, 0.2, 0.3])
    dispatches = (srv.stats["prefill_dispatches"]
                  + srv.stats["decode_dispatches"])
    [path] = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(spans) == {"serve.admit", "serve.prefill", "serve.decode",
                          "serve.sync", "serve.emit", "serve.idle"}
    admitted = sorted(int(r) for st in spans["serve.admit"]
                      for r in str(st.get("rids", "")).split())
    assert admitted == [0, 1, 2]
    assert len(spans["serve.emit"]) == len(spans["serve.sync"]) == dispatches
    assert len(spans["serve.prefill"]) + len(spans["serve.decode"]) == (
        dispatches)
