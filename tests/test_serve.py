"""Serving-engine tests (serve/): greedy parity with offline generate
regardless of arrival order, slot free/reuse, backpressure, deadlines,
cancellation, per-slot sampling params, and the steady-state
zero-recompile guarantee."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import init_params
from replicatinggpt_tpu.sample import GenerateConfig, generate
from replicatinggpt_tpu.serve import (CachePool, Engine, EngineConfig,
                                      ReplayConfig, Request, RequestResult,
                                      SamplingParams, Scheduler,
                                      compile_counts, run_replay)
from replicatinggpt_tpu.serve.requests import (FINISH_CANCELLED,
                                               FINISH_DEADLINE,
                                               FINISH_LENGTH_CAP,
                                               FINISH_MAX_TOKENS,
                                               REJECT_PROMPT_TOO_LONG,
                                               REJECT_QUEUE_FULL)

CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


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
# parity
# ---------------------------------------------------------------------------

def test_greedy_parity_any_arrival_order(params):
    """Engine greedy output must be token-identical to offline
    generate() per request, for a pool smaller than the request count,
    under different submission orders (continuous batching must not
    leak anything between slots)."""
    reqs = _requests(6)
    want = _offline_greedy(params, reqs)
    for order in (list(range(6)), [5, 2, 0, 4, 1, 3]):
        eng = Engine(params, CFG, EngineConfig(pool_size=3, max_queue=16))
        for i in order:
            assert eng.submit(reqs[i]) is None
        got = {r.id: r.tokens for r in eng.drain()}
        assert got == want


def test_greedy_parity_packed_cache_layout(params):
    """The packed (L,B,S,C) pooled-cache layout must produce the same
    greedy tokens through the engine (decode_step_multi's packed write
    path + chunked-prefill packed path)."""
    pc = dataclasses.replace(CFG, decode_cache_layout="packed")
    reqs = _requests(4)
    want = _offline_greedy(params, reqs)
    eng = Engine(params, pc, EngineConfig(pool_size=2, max_queue=8))
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want


def test_prefill_chunk_rounded_to_block_divisor(params):
    """A --prefill-chunk that does not divide block_size must be rounded
    down to a divisor (a non-divisor's padded final chunk would start
    past the cache buffer, where dynamic_update_slice silently CLAMPS
    and corrupts earlier K/V) — and parity must hold at the rounded
    chunk, including prompts whose final chunk is the last one in the
    buffer."""
    ecfg = EngineConfig(pool_size=2, max_queue=8, prefill_chunk=12)
    assert ecfg.chunk(CFG.block_size) == 8     # largest divisor of 32 <= 12
    assert EngineConfig(prefill_chunk=48).chunk(256) == 32
    assert EngineConfig().chunk(31) == 31      # degenerate: c | c always
    reqs = _requests(3) + [Request(
        id="edge", prompt=np.arange(CFG.block_size - 1, dtype=np.int32) % 17,
        max_new_tokens=2, sampling=SamplingParams(greedy=True))]
    want = _offline_greedy(params, reqs)
    eng = Engine(params, CFG, ecfg)
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want


def test_decode_step_multi_matches_single_row(params):
    """decode_step_multi at staggered per-slot positions must equal
    independent single-row decode_step calls (per-row independence is
    what the parity guarantee rests on)."""
    from replicatinggpt_tpu.models.gpt import (decode_step,
                                               decode_step_multi,
                                               init_kv_cache)
    B = 3
    rng = np.random.default_rng(0)
    warm = [int(x) for x in rng.integers(2, 7, (B,))]  # per-row warm length
    toks = rng.integers(0, CFG.vocab_size, (B, 8)).astype(np.int32)

    # single-row references, each warmed to its own position
    singles = []
    for b in range(B):
        cache = init_kv_cache(CFG, 1)
        for pos in range(warm[b]):
            logits, cache = decode_step(params, toks[b:b + 1, pos],
                                        jnp.int32(pos), cache, CFG)
        singles.append((logits, cache))

    # multi-slot: warm each slot by stepping all slots with per-slot pos
    cache_m = init_kv_cache(CFG, B)
    pos = np.zeros((B,), np.int32)
    logits_m = None
    for step in range(max(warm)):
        cur = np.array([toks[b, min(step, warm[b] - 1)] for b in range(B)])
        step_pos = np.minimum(step, np.array(warm) - 1).astype(np.int32)
        out, cache_m = decode_step_multi(params, jnp.asarray(cur),
                                         jnp.asarray(step_pos), cache_m, CFG)
        if logits_m is None or step == max(warm) - 1:
            logits_m = out
    # rows that reached their final position on the last step must match
    for b in range(B):
        if warm[b] == max(warm):
            np.testing.assert_allclose(np.asarray(logits_m[b]),
                                       np.asarray(singles[b][0][0]),
                                       atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# slots: free / reuse / cancellation
# ---------------------------------------------------------------------------

def test_slot_free_and_reuse_after_completion(params):
    reqs = _requests(5, max_new=6)
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=16))
    for r in reqs:
        assert eng.submit(r) is None
    max_used = 0
    results = []
    while not eng.idle:
        results.extend(eng.step())
        max_used = max(max_used, eng.pool.n_used)
    assert len(results) == 5
    assert all(r.finish_reason == FINISH_MAX_TOKENS for r in results)
    assert max_used == 2                      # pool bound respected
    assert eng.pool.n_free == 2               # everything released
    assert eng.metrics.counters["requests_admitted"] == 5


def test_cancellation_frees_slot_and_queue(params):
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=4))
    long_req = Request(id="long", prompt=np.array([1], np.int32),
                       max_new_tokens=30,
                       sampling=SamplingParams(greedy=True))
    queued = Request(id="queued", prompt=np.array([2], np.int32),
                     max_new_tokens=3, sampling=SamplingParams(greedy=True))
    assert eng.submit(long_req) is None
    assert eng.submit(queued) is None
    for _ in range(3):
        eng.step()
    assert eng.pool.slot_of("long") is not None
    assert eng.cancel("long")
    assert eng.pool.n_free == 1               # slot freed immediately
    res = {r.id: r for r in eng.drain()}
    assert set(res) == {"long", "queued"}
    assert res["long"].finish_reason == FINISH_CANCELLED
    assert len(res["long"].tokens) == 3       # partial output preserved
    assert res["queued"].finish_reason == FINISH_MAX_TOKENS
    assert len(res["queued"].tokens) == 3
    # cancelling a queued request removes it before admission
    eng2 = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=4))
    assert eng2.submit(long_req) is None
    assert eng2.submit(queued) is None
    assert eng2.cancel("queued")
    res2 = {r.id: r for r in eng2.drain()}
    assert set(res2) == {"long", "queued"}
    assert res2["queued"].finish_reason == FINISH_CANCELLED
    assert res2["queued"].tokens == []
    assert not eng2.cancel("nonexistent")


def test_cancel_admitted_request_mid_decode_and_slot_reuse(params):
    """Engine-side cancellation of an ALREADY-ADMITTED request: the slot
    frees immediately, the partial output is preserved on the terminal
    result, the freed slot serves the next request with exact greedy
    parity, and the surviving neighbor's stream is untouched — all
    without a recompile (the cancel only flips host-side state)."""
    from replicatinggpt_tpu.serve import compile_counts
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4))
    doomed = Request(id="doomed", prompt=np.array([5, 6, 7], np.int32),
                     max_new_tokens=25,
                     sampling=SamplingParams(greedy=True))
    neighbor = Request(id="neighbor", prompt=np.array([9, 10], np.int32),
                       max_new_tokens=8,
                       sampling=SamplingParams(greedy=True))
    assert eng.submit(doomed) is None
    assert eng.submit(neighbor) is None
    for _ in range(4):
        eng.step()
    assert eng.pool.slot_of("doomed") is not None
    counts = compile_counts()
    assert eng.cancel("doomed")
    assert eng.pool.slot_of("doomed") is None    # slot freed immediately
    assert eng.pool.n_free == 1
    assert not eng.cancel("doomed")              # already gone
    successor = Request(id="successor", prompt=np.array([3, 4], np.int32),
                        max_new_tokens=6,
                        sampling=SamplingParams(greedy=True))
    assert eng.submit(successor) is None
    res = {r.id: r for r in eng.drain()}
    assert res["doomed"].finish_reason == FINISH_CANCELLED
    assert len(res["doomed"].tokens) == 4        # partial output kept
    offline = _offline_greedy(params, [neighbor, successor])
    for rid in ("neighbor", "successor"):
        assert res[rid].finish_reason == FINISH_MAX_TOKENS
        assert res[rid].tokens == offline[rid]
    assert compile_counts() == counts            # cancel is host-only


def test_cancel_admitted_request_speculative_path(params):
    """The same engine-side cancel under speculative decoding: the
    drafter's slot lifecycle (on_release) stays in sync and the freed
    slot is reusable with a drafter attached."""
    from replicatinggpt_tpu.serve import NGramDrafter

    class TrackingDrafter(NGramDrafter):
        def __init__(self, k):
            super().__init__(k)
            self.released = []

        def on_release(self, slot):
            self.released.append(slot)
            super().on_release(slot)

    drafter = TrackingDrafter(k=2)
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=4),
                 drafter=drafter)
    doomed = Request(id="doomed",
                     prompt=np.array([5, 6, 5, 6, 5, 6], np.int32),
                     max_new_tokens=20,
                     sampling=SamplingParams(greedy=True))
    assert eng.submit(doomed) is None
    for _ in range(3):
        eng.step()
    slot = eng.pool.slot_of("doomed")
    assert slot is not None
    n_before = len(eng._slots[slot].tokens)
    assert n_before > 0
    assert eng.cancel("doomed")
    assert drafter.released == [slot]            # drafter told exactly once
    nxt = Request(id="next", prompt=np.array([7, 8, 7, 8], np.int32),
                  max_new_tokens=6, sampling=SamplingParams(greedy=True))
    assert eng.submit(nxt) is None
    res = {r.id: r for r in eng.drain()}
    assert res["doomed"].finish_reason == FINISH_CANCELLED
    assert len(res["doomed"].tokens) == n_before
    assert res["next"].finish_reason == FINISH_MAX_TOKENS
    assert res["next"].tokens == _offline_greedy(params, [nxt])["next"]


# ---------------------------------------------------------------------------
# admission control: backpressure, validation, deadlines, length caps
# ---------------------------------------------------------------------------

def test_backpressure_rejects_when_queue_full(params):
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=2))
    reqs = _requests(5, max_new=4)
    rejected = [r for r in (eng.submit(q) for q in reqs) if r is not None]
    # slot admission happens at step(), so submit #3..#5 hit a full queue
    assert len(rejected) == 3
    assert all(r.finish_reason == REJECT_QUEUE_FULL for r in rejected)
    assert eng.metrics.counters[REJECT_QUEUE_FULL] == 3
    accepted = eng.drain()
    assert len(accepted) == 2
    assert all(r.finish_reason == FINISH_MAX_TOKENS for r in accepted)


def test_prompt_too_long_rejected(params):
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=2))
    r = eng.submit(Request(id="big",
                           prompt=np.zeros((CFG.block_size + 1,), np.int32)))
    assert r is not None and r.finish_reason == REJECT_PROMPT_TOO_LONG


def test_deadline_expiry_queued_and_active(params):
    t = [0.0]
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=4),
                 clock=lambda: t[0])
    active = Request(id="active", prompt=np.array([1], np.int32),
                     max_new_tokens=30, deadline=5.0,
                     sampling=SamplingParams(greedy=True))
    queued = Request(id="queued", prompt=np.array([2], np.int32),
                     max_new_tokens=4, deadline=1.0,
                     sampling=SamplingParams(greedy=True))
    assert eng.submit(active) is None
    assert eng.submit(queued) is None
    eng.step()                                 # admits 'active' only
    t[0] = 2.0                                 # queued deadline passes
    finished = eng.step()
    assert [r.id for r in finished] == ["queued"]
    assert finished[0].finish_reason == FINISH_DEADLINE
    t[0] = 6.0                                 # active deadline passes
    finished = eng.step()
    assert [r.id for r in finished] == ["active"]
    assert finished[0].finish_reason == FINISH_DEADLINE
    assert eng.pool.n_free == 1
    assert 0 < len(finished[0].tokens) < 30    # partial output preserved


def test_max_new_tokens_and_context_length_cap(params):
    """A request whose budget exceeds the slot's cache room finishes
    with the length_cap reason and exactly room = S - P + 1 tokens."""
    P = CFG.block_size - 4
    room = CFG.block_size - P + 1
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=2))
    res = eng.submit(Request(id="cap",
                             prompt=np.ones((P,), np.int32),
                             max_new_tokens=100,
                             sampling=SamplingParams(greedy=True)))
    assert res is None
    out = eng.drain()
    assert out[0].finish_reason == FINISH_LENGTH_CAP
    assert len(out[0].tokens) == room


# ---------------------------------------------------------------------------
# per-slot sampling params + batched filters
# ---------------------------------------------------------------------------

def test_mixed_batch_greedy_row_unaffected_by_stochastic_neighbors(params):
    reqs = _requests(4, greedy=True, max_new=8)
    want = _offline_greedy(params, reqs)
    # neighbors with aggressive stochastic settings share the batch
    noisy = [Request(id=f"n{i}", prompt=np.array([i + 1], np.int32),
                     max_new_tokens=8,
                     sampling=SamplingParams(temperature=1.7, top_k=5,
                                             top_p=0.9), rng_seed=100 + i)
             for i in range(3)]
    eng = Engine(params, CFG, EngineConfig(pool_size=4, max_queue=16))
    for r in (noisy[0], reqs[0], noisy[1], reqs[1], reqs[2], noisy[2],
              reqs[3]):
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    for rid, toks in want.items():
        assert got[rid] == toks
    for n in noisy:                          # stochastic rows still valid
        assert len(got[n.id]) == 8
        assert all(0 <= t < CFG.vocab_size for t in got[n.id])


def test_stochastic_request_reproducible_by_seed(params):
    """A request's sampled stream is keyed by its own rng_seed — same
    seed twice gives the same tokens, independent of slot/batch."""
    def run(pool):
        eng = Engine(params, CFG, EngineConfig(pool_size=pool, max_queue=8))
        reqs = [Request(id=f"s{i}", prompt=np.array([7], np.int32),
                        max_new_tokens=10,
                        sampling=SamplingParams(temperature=0.9, top_k=12),
                        rng_seed=42 + i) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        return {r.id: r.tokens for r in eng.drain()}

    a, b = run(pool=3), run(pool=1)          # different batching, same seeds
    assert a == b


def test_batched_filters_match_scalar_filters():
    from replicatinggpt_tpu.sample.generate import (batched_top_k_filter,
                                                    batched_top_p_filter,
                                                    _top_k_filter,
                                                    _top_p_filter)
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 200)), jnp.float32)
    # per-row k: rows 0/1 filtered at different k, row 2 off (0), row 3 off (>=V)
    k = jnp.asarray([5, 50, 0, 200], jnp.int32)
    got = np.asarray(batched_top_k_filter(logits, k))
    np.testing.assert_array_equal(got[0], np.asarray(
        _top_k_filter(logits[:1], 5))[0])
    np.testing.assert_array_equal(got[1], np.asarray(
        _top_k_filter(logits[1:2], 50))[0])
    np.testing.assert_array_equal(got[2], np.asarray(logits[2]))  # passthrough
    np.testing.assert_array_equal(got[3], np.asarray(logits[3]))
    p = jnp.asarray([0.3, 0.9, 0.0, 1.0], jnp.float32)
    got = np.asarray(batched_top_p_filter(logits, p))
    np.testing.assert_array_equal(got[0], np.asarray(
        _top_p_filter(logits[:1], 0.3))[0])
    np.testing.assert_array_equal(got[1], np.asarray(
        _top_p_filter(logits[1:2], 0.9))[0])
    np.testing.assert_array_equal(got[2], np.asarray(logits[2]))
    np.testing.assert_array_equal(got[3], np.asarray(logits[3]))


def _sample_unconditionally(rngs, logits, temperature, top_k, top_p,
                            greedy):
    """The sampler as it was before it asked what its rows want: argmax,
    the three batched filters, ``vmap(categorical)``, ``where``. Kept
    here as the reference of ``sample_tokens_batched``."""
    from replicatinggpt_tpu.sample.generate import (batched_top_k_filter,
                                                    batched_top_p_filter)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    f = batched_top_p_filter(batched_top_k_filter(scaled, top_k), top_p)
    sampled = jax.vmap(jax.random.categorical)(rngs, f).astype(jnp.int32)
    return jnp.where(greedy, greedy_tok, sampled)


_B, _V = 6, 300
_SAMPLER_CASES = {
    # name: (greedy, top_k, top_p, live) per row
    "all_greedy": ([1] * 6, [0, 5, 0, 40, 0, 0], [0, .9, 0, .5, 1, 0],
                   [1] * 6),
    "sampled_top_k_only": ([0] * 6, [5, 40, 1, 299, 7, 12], [0, 1] * 3,
                           [1] * 6),
    "sampled_top_p_only": ([0] * 6, [0, 300] * 3,
                           [.3, .9, .5, .95, .1, .7], [1] * 6),
    "sampled_top_k_and_top_p": ([0] * 6, [5, 40, 0, 299, 7, 0],
                                [.3, .9, .5, 0, 1, 0], [1] * 6),
    "sampled_no_filter": ([0] * 6, [0, 300, 0, 400, 0, 0],
                          [0, 1, 0, 1.5, 0, 1], [1] * 6),
    "greedy_and_sampled_mixed": ([1, 0, 1, 0, 0, 1], [0, 5, 9, 0, 40, 0],
                                 [0, 0, .9, .8, .5, 0], [1] * 6),
    "sampled_rows_some_dead": ([0, 0, 1, 0, 0, 1], [5, 0, 0, 40, 0, 0],
                               [0, .9, 0, .5, .3, 0], [1, 1, 1, 0, 0, 0]),
    # the stale mirror: idle slots start at greedy=False and a finished
    # request's parameters stay in its slot
    "live_greedy_dead_rows_stale": ([1, 1, 0, 0, 1, 0], [0, 0, 0, 7, 0, 0],
                                    [0, 0, .9, .9, 0, .9],
                                    [1, 1, 0, 0, 1, 0]),
}


@pytest.mark.parametrize("case", sorted(_SAMPLER_CASES))
def test_sampler_matches_unconditional_composition(case):
    """``sample_tokens_batched`` runs the draw and each filter only when
    a live row asks for it; every live row still gets, bit for bit, the
    token of the unconditional composition, whatever the other rows'
    parameters say."""
    from replicatinggpt_tpu.sample.generate import sample_tokens_batched
    greedy, top_k, top_p, live = _SAMPLER_CASES[case]
    greedy, live = np.asarray(greedy, bool), np.asarray(live, bool)
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(_B, _V)) * 3.0, jnp.float32)
    temp = jnp.asarray(rng.uniform(0.5, 1.7, size=_B), jnp.float32)
    args = (logits, temp, jnp.asarray(top_k, jnp.int32),
            jnp.asarray(top_p, jnp.float32), jnp.asarray(greedy))
    for seed in range(3):
        rngs = jax.random.split(jax.random.PRNGKey(seed), _B)
        want = np.asarray(jax.jit(_sample_unconditionally)(rngs, *args))
        got = np.asarray(jax.jit(sample_tokens_batched)(
            rngs, *args, jnp.asarray(live)))
        np.testing.assert_array_equal(got[live], want[live])
        if live.all():                   # no mask = every row is live
            np.testing.assert_array_equal(
                np.asarray(sample_tokens_batched(rngs, *args)), want)
        if not (live & ~greedy).any():
            # nothing was drawn: a dead stochastic row reads the argmax
            np.testing.assert_array_equal(
                got, np.asarray(jnp.argmax(logits, axis=-1)))


@pytest.mark.parametrize("window", [1, 4])
def test_sampled_neighbour_switches_the_filters_on_and_off(params, window):
    """A greedy stream is token-identical when a sampled request
    (temperature 0.8, top_p 0.9) is admitted beside it mid-stream and
    after that request has finished; ``sample_filter_launches`` counts
    exactly the launches in between, and ``stochastic_rows`` on
    ``serve/launch`` is back at 0 once the sampled slot is released
    (its mirrors still read "not greedy": the live mask keeps them
    out)."""
    from replicatinggpt_tpu.utils.telemetry import Telemetry
    g = Request(id="g", prompt=np.array([3, 1, 4], np.int32),
                max_new_tokens=28, sampling=SamplingParams(greedy=True))
    want = _offline_greedy(params, [g])["g"]
    tel = Telemetry()
    eng = Engine(params, CFG, EngineConfig(pool_size=3, max_queue=8,
                                           decode_window=window),
                 telemetry=tel)
    assert eng.submit(g) is None
    done = []
    for _ in range(3):
        done += eng.step()
    assert eng.metrics.counters["sample_filter_launches"] == 0
    s = Request(id="s", prompt=np.array([2, 7], np.int32), max_new_tokens=5,
                sampling=SamplingParams(temperature=0.8, top_p=0.9),
                rng_seed=11)
    assert eng.submit(s) is None
    done += eng.drain()
    got = {r.id: r.tokens for r in done}
    assert got["g"] == want and len(got["s"]) == 5
    rows = [e["args"]["stochastic_rows"] for e in tel.events
            if e.get("ph") == "X" and e.get("name") == "serve/launch"]
    on = [i for i, n in enumerate(rows) if n]
    assert on and set(rows) == {0, 1}
    assert on == list(range(on[0], on[-1] + 1))       # one run of launches
    assert on[0] > 0 and on[-1] < len(rows) - 1       # g alone before, after
    # s's five tokens: a launch each at k=1; at k=4 the mixed window
    # that prefills it (3 tokens), the next (2), and one more that the
    # host, a window ahead of the device, sends before it has seen the end
    assert len(on) == {1: 5, 4: 3}[window]
    assert eng.metrics_summary()["counters"]["sample_filter_launches"] \
        == len(on)
    slot_s = int(np.flatnonzero(~eng._greedy & (eng._top_p > 0))[0])
    assert not eng._active[slot_s]                    # released, not cleaned


# ---------------------------------------------------------------------------
# steady state: zero recompiles + metrics (acceptance criterion)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# the served tree: weights cast once, at the engine's build
# ---------------------------------------------------------------------------

def _served_case(case):
    """``(cfg at bfloat16 compute, the tree handed in, engine config,
    the family's module)`` of one case."""
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.models import exaone_moe, gpt
    from replicatinggpt_tpu.quant.weights import quantize_params
    if case.startswith("exaone"):
        cfg = dataclasses.replace(
            get_config("exaone-moe-tiny").model, dtype="bfloat16",
            param_dtype="bfloat16" if "bf16" in case else "float32")
        return (cfg, exaone_moe.init_params(jax.random.PRNGKey(7), cfg),
                EngineConfig(pool_size=2, page_size=8, prefill_chunk=16,
                             prefix_cache=False), exaone_moe)
    cfg = dataclasses.replace(CFG, dtype="bfloat16", tied_head="untied"
                              not in case)
    tree = init_params(jax.random.PRNGKey(0), cfg)
    quant = "int8" if "int8" in case else "none"
    return (cfg, quantize_params(tree, quant),
            EngineConfig(pool_size=2, page_size=8, weight_quant=quant), gpt)


@pytest.mark.parametrize("case", [
    "float32-masters", "float32-masters-untied-head", "int8-weight-tree",
    "exaone-moe-tiny-bf16-params", "exaone-moe-tiny-float32-masters"])
def test_engine_serves_weights_it_cast_once(case):
    """The tree the engine's programs take holds, in the compute dtype,
    every leaf the family's entry points read only as ``astype(cd)``, cast
    ONCE at the build; the chunked prefill, a decode step's logits and a
    decode window's tokens are bit for bit those of the tree handed in
    (whose leaves the programs cast in every launch). What the programs
    read as float32 stays float32, a leaf that needs no cast is the SAME
    array, ``engine.params`` is the caller's tree, and
    ``weight_cast_bytes`` counts the copies."""
    from replicatinggpt_tpu.models.families import family
    cfg, tree, ecfg, mod = _served_case(case)
    eng = Engine(tree, cfg, ecfg)
    served = eng.served_params
    assert eng.params is tree
    flat = dict(jax.tree_util.tree_flatten_with_path(tree)[0])
    copies = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(served)[0]:
        name = path[-1].key
        if leaf is flat[path]:
            # nothing a launch would still convert from a wider float
            assert not (name in family(cfg).serve_cast_leaves
                        and leaf.dtype == jnp.float32), name
            continue
        assert (flat[path].dtype, leaf.dtype) == (jnp.float32,
                                                  jnp.bfloat16), name
        assert np.array_equal(np.asarray(leaf),
                              np.asarray(flat[path].astype(jnp.bfloat16)))
        copies += leaf.nbytes
    assert eng.metrics_summary()["weight_cast_bytes"] == copies
    if "float32" in case:
        assert copies > 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(served)[0]:
            if path[-1].key.startswith(("ln", "norm", "router")):
                assert leaf.dtype == jnp.float32 and leaf is flat[path]
    elif "bf16-params" in case:
        assert copies == 0            # every leaf the same array
    else:                             # int8 kernels, float32 scales kept
        b = served["blocks"]
        assert b["qkv_kernel"].dtype == jnp.int8
        assert b["qkv_kernel_scale"].dtype == jnp.float32
        assert b["qkv_bias"].dtype == served["wte"].dtype == jnp.bfloat16

    fam = family(cfg)
    B, psz = 2, 8
    mp = cfg.block_size // psz
    tables = jnp.asarray(np.random.default_rng(5).permutation(B * mp)
                         .astype(np.int32).reshape(B, mp))
    prompts = [np.arange(3, 3 + n, dtype=np.int32) % cfg.vocab_size
               for n in (5, 13)]
    greedy = lambda r, logits, live: (jnp.argmax(logits, -1)
                                      .astype(jnp.int32), r)
    prefill = jax.jit(lambda *a: fam.prefill_chunk_paged(*a, cfg))
    step = jax.jit(lambda *a: mod.decode_step_paged(*a, cfg)[:2])
    window = jax.jit(lambda *a: fam.decode_window_paged(
        *a, cfg, sample_fn=greedy, length=3))

    def run(params):
        cache = fam.init_paged_kv_pool(cfg, B * mp, psz, n_slots=B)
        for b, p in enumerate(prompts):
            for c in range(-(-len(p) // 8)):
                chunk = np.zeros((8,), np.int32)
                chunk[:len(p[c * 8:(c + 1) * 8])] = p[c * 8:(c + 1) * 8]
                cache = prefill(params, jnp.asarray(chunk[None]),
                                jnp.int32(c * 8), jnp.int32(len(p)),
                                tables[b], jnp.int32(b), cache)
        filled = {n: np.asarray(a).copy() for n, a in cache.items()}
        tok = jnp.asarray([p[-1] for p in prompts], jnp.int32)
        pos = jnp.asarray([len(p) - 1 for p in prompts], jnp.int32)
        live = jnp.ones((B,), bool)
        logits, cache = step(params, tok, pos, live, tables, cache)
        toks, *_ = window(
            params, tok, pos, live, jnp.full((B,), 9, jnp.int32),
            jnp.full((B,), -1, jnp.int32), tables, cache,
            jnp.stack([jax.random.PRNGKey(i) for i in range(B)]))
        return filled, np.asarray(logits), np.asarray(toks)

    pool_a, logits_a, toks_a = run(tree)
    pool_b, logits_b, toks_b = run(served)
    assert logits_a.dtype == np.float32 and np.abs(logits_a).max() > 0
    assert np.array_equal(logits_a, logits_b)
    assert np.array_equal(toks_a, toks_b)
    for n in pool_a:
        assert np.array_equal(pool_a[n], pool_b[n]), n


@pytest.mark.parametrize("preset,want", [("gpt2-large", 1_547_686_400),
                                         ("k-exaone-236b-a23b", 0)])
def test_weight_cast_bytes_of_the_published_presets(preset, want):
    """By shapes alone (``jax.eval_shape``; nothing of this size is made
    here): gpt2-large's float32 masters are served beside 1.548 GB of
    bfloat16 copies (kernels, biases, ``wte``, ``wpe``), K-EXAONE's
    bfloat16 parameters beside none."""
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.models.families import family
    from replicatinggpt_tpu.serve.engine import (served_cast_bytes,
                                                 served_tree)
    cfg = get_config(preset).model
    fam = family(cfg)
    tree = jax.eval_shape(lambda: fam.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    served = jax.eval_shape(
        lambda t: served_tree(t, fam.serve_cast_leaves, cfg.dtype), tree)
    made = sum(int(s.size) * s.dtype.itemsize
               for p, s in zip(jax.tree_util.tree_leaves(tree),
                               jax.tree_util.tree_leaves(served))
               if s.dtype != p.dtype)
    assert made == served_cast_bytes(tree, fam.serve_cast_leaves,
                                     cfg.dtype) == want


def test_steady_state_64_requests_zero_recompiles(params):
    """>= 64 requests through a pool of 8 (smaller than the request
    count): completes, reports TTFT/tok-s/occupancy, and compiles ZERO
    new programs after the warmup request."""
    ecfg = EngineConfig(pool_size=8, max_queue=64)
    warm = Engine(params, CFG, ecfg)
    warm.submit(Request(id="w", prompt=np.array([1], np.int32),
                        max_new_tokens=2,
                        sampling=SamplingParams(greedy=True)))
    warm.drain()
    baseline = compile_counts()

    eng = Engine(params, CFG, ecfg)
    reqs = _requests(64, greedy=False, seed=9, max_new=6)
    for r in reqs:
        assert eng.submit(r) is None
    results = eng.drain()
    assert compile_counts() == baseline       # zero recompiles at steady state
    assert len(results) == 64
    assert all(r.finish_reason == FINISH_MAX_TOKENS for r in results)
    s = eng.metrics_summary()
    assert s["histograms"]["ttft_s"]["n"] == 64
    assert s["histograms"]["ttft_s"]["p50"] > 0
    assert s["histograms"]["decode_tokens_per_s"]["p50"] > 0
    assert 0 < s["histograms"]["batch_fill_ratio"]["mean"] <= 1
    assert s["step_latency"]["p50_s"] > 0
    assert s["counters"]["decode_tokens"] == 64 * 6


# ---------------------------------------------------------------------------
# unit: scheduler + cache pool
# ---------------------------------------------------------------------------

def test_scheduler_bounds_and_fifo():
    sch = Scheduler(max_queue=2, block_size=8, clock=lambda: 0.0)
    a = Request(id="a", prompt=np.array([1], np.int32))
    b = Request(id="b", prompt=np.array([1], np.int32))
    c = Request(id="c", prompt=np.array([1], np.int32))
    assert sch.submit(a) is None and sch.submit(b) is None
    assert sch.submit(c) == REJECT_QUEUE_FULL
    admitted, dropped = sch.admit(n_free=1)
    assert [r.id for r, _ in admitted] == ["a"] and not dropped
    assert sch.depth == 1
    assert sch.cancel("b") and not sch.cancel("b")


def test_cache_pool_acquire_release():
    pool = CachePool(CFG, n_slots=2)
    s0, s1 = pool.acquire("a"), pool.acquire("b")
    assert {s0, s1} == {0, 1} and pool.acquire("c") is None
    assert pool.occupancy == 1.0 and pool.slot_of("b") == s1
    pool.release(s0)
    assert pool.n_free == 1 and pool.owner(s0) is None
    assert pool.acquire("c") == s0            # freed slot is reused
    pool.release(s1)
    with pytest.raises(AssertionError):
        pool.release(s1)                      # double free caught


# ---------------------------------------------------------------------------
# replay driver + CLI smoke (tier-1) and soak (slow)
# ---------------------------------------------------------------------------

def test_serve_replay_smoke(params):
    """Tiny replay through the public driver: everything completes,
    metrics summary is well-formed, zero recompiles after warmup."""
    s = run_replay(params, CFG,
                   ReplayConfig(n_requests=16, rate=2000.0, seed=0,
                                prompt_len_max=12, max_new_tokens=5,
                                greedy=True),
                   EngineConfig(pool_size=4, max_queue=32))
    assert s["n_completed"] == 16
    assert s["recompiles_after_warmup"] == 0
    assert s["generated_tokens"] == 16 * 5
    assert s["aggregate_tokens_per_s"] > 0


def test_serve_replay_cli_smoke(capsys):
    from replicatinggpt_tpu.cli import main
    rc = main(["serve-replay", "--preset", "test-tiny", "--n-requests",
               "16", "--pool-size", "4", "--rate", "2000",
               "--request-max-new-tokens", "4", "--greedy"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "16 completed" in out
    assert "recompiles after warmup: 0" in out
    assert "TTFT" in out


@pytest.mark.slow
def test_serve_replay_soak(params):
    """Longer mixed soak: 200 stochastic requests with deadlines through
    a small pool — no leaks (pool fully free), queue drained, every
    request resolved exactly once."""
    s = run_replay(params, CFG,
                   ReplayConfig(n_requests=200, rate=3000.0, seed=5,
                                prompt_len_max=16, max_new_tokens=10,
                                temperature=0.9, top_k=10),
                   EngineConfig(pool_size=6, max_queue=256))
    assert s["n_requests"] == 200
    # every request resolved exactly once (queue deep enough: no rejects)
    assert s["n_completed"] + s["n_rejected"] == 200
    assert s["recompiles_after_warmup"] == 0
    assert s["histograms"]["ttft_s"]["n"] == 200 - s["n_rejected"]
