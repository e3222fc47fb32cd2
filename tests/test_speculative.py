"""Speculative-decoding tests (serve/speculative.py + the engine's
jitted multi-slot verify step): greedy token parity with offline
generate() for EVERY drafter, zero-recompile steady state over a
64-request speculative replay, accept-rate sanity on repetitive
prompts, drafter units, and the bench CPU-fallback contract."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import (decode_step_multi, init_kv_cache,
                                           init_params, verify_step_multi)
from replicatinggpt_tpu.sample import GenerateConfig, generate
from replicatinggpt_tpu.serve import (Engine, EngineConfig, ModelDrafter,
                                      NGramDrafter, ReplayConfig, Request,
                                      SamplingParams, compile_counts,
                                      draft_config_from_preset, make_drafter,
                                      run_replay)
from replicatinggpt_tpu.serve.requests import (FINISH_LENGTH_CAP,
                                               FINISH_MAX_TOKENS)
from replicatinggpt_tpu.serve.speculative import DraftContext

CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0, dtype="float32")
DRAFT_CFG = dataclasses.replace(CFG, n_layer=1)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def draft_params():
    return init_params(jax.random.PRNGKey(1), DRAFT_CFG)


def _drafters(draft_params, pool):
    return {
        "ngram": lambda: NGramDrafter(k=4, ngram=3),
        # deliberately a BAD drafter (random init, different seed):
        # correctness must not depend on drafter quality, only speed does
        "model": lambda: ModelDrafter(draft_params, DRAFT_CFG, k=4,
                                      pool_size=pool),
    }


def _requests(n=6, greedy=True, seed=3, max_new=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        P = int(rng.integers(1, CFG.block_size // 2))
        prompt = rng.integers(0, CFG.vocab_size, (P,)).astype(np.int32)
        out.append(Request(
            id=f"r{i}", prompt=prompt,
            max_new_tokens=max_new or int(rng.integers(4, 14)),
            sampling=SamplingParams(greedy=greedy), rng_seed=i))
    return out


def _offline_greedy(params, reqs):
    return {r.id: np.asarray(generate(
        params, r.prompt[None, :], CFG,
        GenerateConfig(max_new_tokens=r.max_new_tokens, greedy=True))
    )[0].tolist() for r in reqs}


# ---------------------------------------------------------------------------
# parity: speculative greedy == offline generate, every drafter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["ngram", "model"])
def test_spec_greedy_parity_every_drafter(params, draft_params, kind):
    """Speculative drain output must be token-for-token identical to
    offline generate() at temp=0 — acceptance/rejection/bonus paths
    must all reproduce the plain greedy stream exactly."""
    reqs = _requests(6)
    want = _offline_greedy(params, reqs)
    eng = Engine(params, CFG, EngineConfig(pool_size=3, max_queue=16),
                 drafter=_drafters(draft_params, 3)[kind]())
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want


def test_spec_greedy_parity_packed_cache_layout(params):
    """verify_step_multi's packed (L,B,S,C) write/attend path must
    produce the same greedy tokens."""
    pc = dataclasses.replace(CFG, decode_cache_layout="packed")
    reqs = _requests(4)
    want = _offline_greedy(params, reqs)
    eng = Engine(params, pc, EngineConfig(pool_size=2, max_queue=8),
                 drafter=NGramDrafter(k=4))
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want


def test_spec_length_cap_edge(params):
    """A slot whose window butts against the end of the cache buffer
    must clamp its draft count (never clamp-write past seq_len) and
    still match offline greedy up to the cap."""
    P = CFG.block_size - 4
    room = CFG.block_size - P + 1
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=2),
                 drafter=NGramDrafter(k=4))
    assert eng.submit(Request(id="cap", prompt=np.ones((P,), np.int32),
                              max_new_tokens=100,
                              sampling=SamplingParams(greedy=True))) is None
    out = eng.drain()
    assert out[0].finish_reason == FINISH_LENGTH_CAP
    assert len(out[0].tokens) == room
    want = np.asarray(generate(
        params, np.ones((1, P), np.int32), CFG,
        GenerateConfig(max_new_tokens=room, greedy=True)))[0].tolist()
    assert out[0].tokens == want


def test_spec_continues_after_buffer_filling_request_finishes(params):
    """A released slot's stale frontier can sit at seq_len (a request
    that finished by filling its buffer); later speculative steps for
    OTHER slots must keep running — the window bound only constrains
    active slots (regression: the bounds check crashed every step after
    such a finish)."""
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4),
                 drafter=NGramDrafter(k=4))
    P = CFG.block_size - 2
    filler = Request(id="fill", prompt=np.ones((P,), np.int32),
                     max_new_tokens=100,
                     sampling=SamplingParams(greedy=True))
    longer = Request(id="long", prompt=np.array([3, 4], np.int32),
                     max_new_tokens=20,
                     sampling=SamplingParams(greedy=True))
    assert eng.submit(filler) is None
    assert eng.submit(longer) is None
    res = {r.id: r for r in eng.drain()}       # crashes without the fix
    assert res["fill"].finish_reason == FINISH_LENGTH_CAP
    assert len(res["long"].tokens) == 20
    want = np.asarray(generate(
        params, np.array([[3, 4]], np.int32), CFG,
        GenerateConfig(max_new_tokens=20, greedy=True)))[0].tolist()
    assert res["long"].tokens == want


def test_model_drafter_cache_stays_aligned(params):
    """With draft params == target params, greedy drafting must predict
    the target's greedy stream exactly — accept rate 1.0. This pins the
    draft-cache alignment property: the draft scan commits K/V for ALL
    k proposals, so a fully-accepted window leaves no stale position
    behind (regression: stopping the scan at k left d_k's K/V unwritten
    and degraded every later proposal after a full acceptance)."""
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8),
                 drafter=ModelDrafter(params, CFG, k=3, pool_size=2))
    for r in _requests(4, max_new=10, seed=11):
        assert eng.submit(r) is None
    eng.drain()
    assert eng.metrics_summary()["speculative"]["accept_rate"] == 1.0


def test_verify_step_multi_matches_decode_step_multi(params):
    """A W-wide verify window over already-committed tokens must score
    each position like the sequential decode steps it replaces (same
    math per row/position — the parity guarantee's foundation)."""
    B, W = 2, 3
    rng = np.random.default_rng(0)
    toks = rng.integers(0, CFG.vocab_size, (B, W + 1)).astype(np.int32)
    # sequential reference: W+1 single steps from position 0
    cache_s = init_kv_cache(CFG, B)
    seq_logits = []
    for j in range(W + 1):
        lg, cache_s = decode_step_multi(
            params, jnp.asarray(toks[:, j]),
            jnp.full((B,), j, jnp.int32), cache_s, CFG)
        seq_logits.append(np.asarray(lg))
    # one verify pass over the same window at base position 0
    cache_v = init_kv_cache(CFG, B)
    logits, cache_v = verify_step_multi(
        params, jnp.asarray(toks), jnp.zeros((B,), jnp.int32),
        jnp.full((B,), W, jnp.int32), cache_v, CFG)
    logits = np.asarray(logits)
    for j in range(W + 1):
        np.testing.assert_allclose(logits[:, j], seq_logits[j],
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cache_v["k"]),
                               np.asarray(cache_s["k"]), atol=1e-6)


# ---------------------------------------------------------------------------
# stochastic speculation: reproducible, valid, completes
# ---------------------------------------------------------------------------

def test_spec_stochastic_reproducible_and_valid(params):
    def run():
        eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=16),
                     drafter=NGramDrafter(k=3))
        reqs = [Request(id=f"s{i}", prompt=np.array([7, 7, 7, 7], np.int32),
                        max_new_tokens=10,
                        sampling=SamplingParams(temperature=0.9, top_k=12),
                        rng_seed=42 + i) for i in range(3)]
        for r in reqs:
            assert eng.submit(r) is None
        return {r.id: r.tokens for r in eng.drain()}

    a, b = run(), run()
    assert a == b                       # per-slot rng chains, seeded
    assert all(len(t) == 10 for t in a.values())
    assert all(0 <= t < CFG.vocab_size for ts in a.values() for t in ts)


# ---------------------------------------------------------------------------
# steady state: zero recompiles over a 64-request speculative replay
# ---------------------------------------------------------------------------

def test_spec_steady_state_64_requests_zero_recompiles(params):
    """64-request replay with --spec semantics: zero new programs after
    the warmup engine (CompileGuard also enforces this live from inside
    every step — a recompile raises rather than just counting)."""
    rcfg = ReplayConfig(n_requests=64, rate=5000.0, seed=0,
                        prompt_len_max=12, max_new_tokens=6, greedy=True,
                        spec="ngram", spec_k=4)
    s = run_replay(params, CFG, rcfg,
                   EngineConfig(pool_size=8, max_queue=128))
    assert s["n_completed"] == 64
    assert s["recompiles_after_warmup"] == 0
    assert s["generated_tokens"] == 64 * 6
    assert s["compile_guards"]["verify"]["compiles"] <= 1
    assert s["speculative"]["drafter"] == "ngram"
    assert s["speculative"]["k"] == 4


# ---------------------------------------------------------------------------
# accept rate + tokens/step on a repetitive trace
# ---------------------------------------------------------------------------

def test_spec_accept_rate_repetitive_prompt(params):
    """On repetitive greedy traces the n-gram drafter should accept
    most drafts: accept_rate in (0, 1] and > 0.5, mean committed
    tokens per slot-step > 1.0 (the speculative multiplier; 1.0 exactly
    is plain decode)."""
    rcfg = ReplayConfig(n_requests=12, rate=5000.0, seed=2,
                        prompt_len_min=6, prompt_len_max=12,
                        max_new_tokens=12, greedy=True,
                        prompt_mode="repeat", spec="ngram", spec_k=4)
    s = run_replay(params, CFG, rcfg,
                   EngineConfig(pool_size=4, max_queue=32))
    sp = s["speculative"]
    assert 0.0 < sp["accept_rate"] <= 1.0
    assert sp["accept_rate"] > 0.5
    assert sp["mean_tokens_per_step"] > 1.0
    assert s["counters"]["spec_accepted_tokens"] > 0
    assert sp["draft_overhead_s"]["n"] > 0


def test_spec_metrics_in_summary(params, draft_params):
    """metrics_summary/replay must report accept_rate,
    mean_tokens_per_step and draft overhead next to TTFT/tok-s."""
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8),
                 drafter=ModelDrafter(draft_params, DRAFT_CFG, k=2,
                                      pool_size=2))
    for r in _requests(3, max_new=5):
        assert eng.submit(r) is None
    res = eng.drain()
    assert all(r.finish_reason == FINISH_MAX_TOKENS for r in res)
    s = eng.metrics_summary()
    sp = s["speculative"]
    assert sp["drafter"] == "model"
    assert sp["mean_tokens_per_step"] >= 1.0
    assert "accept_rate" in sp and "draft_overhead_s" in sp
    assert s["compile_guards"]["verify"]["compiles"] <= 1
    from replicatinggpt_tpu.serve import format_summary
    s.update(n_requests=3, n_completed=3, n_rejected=0,
             generated_tokens=sum(len(r.tokens) for r in res),
             wall_s=1.0, aggregate_tokens_per_s=1.0,
             recompiles_after_warmup=0)
    assert "accept rate" in format_summary(s)


# ---------------------------------------------------------------------------
# drafter units
# ---------------------------------------------------------------------------

def test_ngram_drafter_lookup():
    d = NGramDrafter(k=3, ngram=2)
    hist = np.array([5, 6, 7, 8, 9, 5, 6], np.int32)
    ctx = DraftContext(tok=np.array([6], np.int32),
                       pos=np.array([6], np.int32),
                       active=np.array([True]), histories=[hist])
    toks, lens = d.draft(ctx)
    # trailing 2-gram [5, 6] occurred at index 0; continuation 7, 8, 9
    assert lens[0] == 3
    assert toks[0].tolist() == [7, 8, 9]
    # no earlier occurrence -> nothing proposed
    ctx2 = DraftContext(tok=np.array([4], np.int32),
                        pos=np.array([3], np.int32),
                        active=np.array([True]),
                        histories=[np.array([1, 2, 3, 4], np.int32)])
    toks2, lens2 = d.draft(ctx2)
    assert lens2[0] == 0
    # inactive slots propose nothing
    ctx3 = DraftContext(tok=np.array([6], np.int32),
                        pos=np.array([6], np.int32),
                        active=np.array([False]), histories=[None])
    assert d.draft(ctx3)[1][0] == 0


def test_make_drafter_and_draft_preset():
    assert make_drafter("off", 4, 3, 2) is None
    d = make_drafter("ngram", 5, 2, 2)
    assert isinstance(d, NGramDrafter) and d.k == 5 and d.ngram == 2
    with pytest.raises(ValueError):
        make_drafter("model", 4, 3, 2)          # params/cfg required
    with pytest.raises(ValueError):
        make_drafter("bogus", 4, 3, 2)
    big = dataclasses.replace(CFG, vocab_size=101, block_size=64)
    dc = draft_config_from_preset(big, "test-tiny")
    assert dc.vocab_size == 101 and dc.block_size == 64
    assert dc.dtype == big.dtype


def test_engine_rejects_mismatched_draft_model(params, draft_params):
    bad_cfg = dataclasses.replace(DRAFT_CFG, vocab_size=66)
    bad_params = init_params(jax.random.PRNGKey(2), bad_cfg)
    with pytest.raises(AssertionError):
        Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8),
               drafter=ModelDrafter(bad_params, bad_cfg, k=2, pool_size=2))


def test_cache_pool_positions_exposed(params):
    """CachePool.positions is the engine's live per-slot frontier —
    host data a drafter can read without any device sync."""
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8))
    prompt = np.arange(5, dtype=np.int32)
    assert eng.submit(Request(id="a", prompt=prompt, max_new_tokens=3,
                              sampling=SamplingParams(greedy=True))) is None
    eng.step()                            # admit + first decode
    slot = eng.pool.slot_of("a")
    assert eng.pool.positions[slot] == 5  # P-1 at admit, +1 per token
    eng.drain()


# ---------------------------------------------------------------------------
# serve-replay CLI with --spec
# ---------------------------------------------------------------------------

def test_serve_replay_cli_spec_smoke(capsys):
    from replicatinggpt_tpu.cli import main
    rc = main(["serve-replay", "--preset", "test-tiny", "--n-requests",
               "12", "--pool-size", "4", "--rate", "5000",
               "--request-max-new-tokens", "6", "--greedy",
               "--spec", "ngram", "--spec-k", "3",
               "--prompt-mode", "repeat"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "12 completed" in out
    assert "speculative (ngram, k=3)" in out
    assert "recompiles after warmup: 0" in out
