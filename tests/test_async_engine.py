"""Async engine core (ISSUE 10) + continuous windows (ISSUE 13):
multi-token decode windows, donated device-resident step state,
double-buffered dispatch, the paged decode kernel — and the
continuous-window upgrades: admissions riding MIXED prefill+decode
windows instead of breaking to blocked k=1, deadlines/cancels landing
as on-device lifecycle masks, and the bounded k-autotuner walking
warm bucketed programs. Greedy byte-parity with offline generate()
through every async seam, zero recompiles after warmup across a
replay containing all of the above, the deterministic dispatch-count
amortization pins, and the admission-storm retention acceptance
(>= 90% of idle-trace amortization held through an admission+cancel+
deadline storm)."""

import dataclasses

import jax
import numpy as np
import pytest

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import init_params
from replicatinggpt_tpu.sample import GenerateConfig, generate
from replicatinggpt_tpu.serve import (Engine, EngineConfig, ReplayConfig,
                                      Request, SamplingParams,
                                      compile_counts, run_replay)
from replicatinggpt_tpu.serve.requests import (FINISH_CANCELLED,
                                               FINISH_DEADLINE, FINISH_EOS,
                                               FINISH_MAX_TOKENS,
                                               REJECT_BAD_REQUEST)

CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _greedy(rid, prompt, max_new=8, eos=None, seed=0):
    return Request(id=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new,
                   sampling=SamplingParams(greedy=True), rng_seed=seed,
                   eos_token_id=eos)


def _requests(n=5, seed=3, max_new=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        P = int(rng.integers(1, CFG.block_size // 2))
        prompt = rng.integers(0, CFG.vocab_size, (P,)).astype(np.int32)
        out.append(_greedy(f"r{i}", prompt,
                           max_new=max_new or int(rng.integers(4, 14))))
    return out


def _offline(params, reqs, cfg=CFG):
    # the engine caps decode at the slot's context room (length_cap);
    # mirror it so the reference compares the same number of tokens
    return {r.id: np.asarray(generate(
        params, r.prompt[None, :], cfg,
        GenerateConfig(max_new_tokens=min(
            r.max_new_tokens, cfg.block_size - int(r.prompt.size) + 1),
            greedy=True)))[0].tolist() for r in reqs}


# ---------------------------------------------------------------------------
# windowed greedy parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [2, 4, 8])
def test_windowed_greedy_parity_vs_offline(params, window):
    """Greedy output through the async window path must be
    byte-identical to offline generate() for every window size — a
    window is k steps of the SAME per-step math, not a different
    decode."""
    reqs = _requests(5)
    want = _offline(params, reqs)
    eng = Engine(params, CFG, EngineConfig(pool_size=3, max_queue=16,
                                           decode_window=window))
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want
    assert eng.idle and eng._inflight is None
    dp = eng.metrics_summary()["dispatch"]
    assert dp["window_k"] == window
    # amortization actually engaged: fewer dispatches than tokens
    assert dp["dispatches"] < eng.metrics.counters["decode_tokens"]


def test_windowed_parity_packed_layout(params):
    """Both cache layouts ride the same window program — packed
    (L, B, S, C) pages must keep parity too."""
    pc = dataclasses.replace(CFG, decode_cache_layout="packed")
    reqs = _requests(4, seed=5)
    want = _offline(params, reqs, cfg=pc)
    eng = Engine(params, pc, EngineConfig(pool_size=2, max_queue=8,
                                          decode_window=4))
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want


def test_windowed_stochastic_parity(params):
    """Sampled streams must also be window-size-invariant: the window
    body advances each slot's RNG exactly as the blocked loop does."""
    rng = np.random.default_rng(9)

    def reqs():
        return [Request(
            id=f"s{i}", prompt=rng.integers(0, 65, (4 + i,)).astype(np.int32),
            max_new_tokens=10,
            sampling=SamplingParams(temperature=0.8, top_k=12),
            rng_seed=100 + i) for i in range(3)]

    outs = []
    for window in (1, 8):
        rng = np.random.default_rng(9)
        eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8,
                                               decode_window=window))
        for r in reqs():
            assert eng.submit(r) is None
        outs.append({r.id: r.tokens for r in eng.drain()})
    assert outs[0] == outs[1]


def test_mid_window_admission_arrival(params):
    """A request arriving while a window is in flight: the engine
    admits at the next window BOUNDARY (host bookkeeping while the
    window flies, prefill riding the next mixed dispatch — no window
    break), and parity holds for both the running and the newly
    admitted stream."""
    reqs = _requests(3, seed=7, max_new=20)
    want = _offline(params, reqs)
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8,
                                           decode_window=4))
    assert eng.submit(reqs[0]) is None
    out = []
    out.extend(eng.step())            # admission boundary (mixed window)
    out.extend(eng.step())            # steady state: window launched
    assert eng._inflight is not None, "window should be in flight"
    # mid-window arrivals — admitted at the next boundary, windows held
    assert eng.submit(reqs[1]) is None
    assert eng.submit(reqs[2]) is None
    out.extend(eng.drain())
    got = {r.id: r.tokens for r in out}
    assert got == want
    wb = eng.metrics_summary()["window_breaks"]
    assert wb["admit"] == 0, wb


def test_backlog_does_not_break_windows(params):
    """Admission batching: while the pool is FULL, a queued backlog
    must not force the engine back to blocked k=1 steps — arrivals
    wait at window boundaries."""
    reqs = _requests(4, seed=11, max_new=16)
    want = _offline(params, reqs)
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8,
                                           decode_window=4))
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want
    dp = eng.metrics_summary()["dispatch"]
    # 4 requests x 16 tokens: a blocked engine would pay ~64 dispatches
    assert dp["dispatches"] < 40, dp


# ---------------------------------------------------------------------------
# EOS inside a window
# ---------------------------------------------------------------------------

def test_eos_inside_window_parity_and_release(params):
    """A request whose eos lands mid-window finishes with reason
    ``eos``, its stream is the offline stream truncated at (and
    including) the eos token, and its slot + pages free at the window
    boundary — identical at every window size."""
    base = _greedy("e0", [3, 1, 4, 1, 5], max_new=14)
    offline = _offline(params, [base])["e0"]
    eos_tok = offline[5]              # mid-stream token becomes the stop
    want = offline[:offline.index(eos_tok) + 1]
    for window in (1, 4, 8):
        eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4,
                                               decode_window=window))
        req = _greedy("e0", [3, 1, 4, 1, 5], max_new=14, eos=eos_tok)
        assert eng.submit(req) is None
        res = {r.id: r for r in eng.drain()}["e0"]
        assert res.finish_reason == FINISH_EOS
        assert res.tokens == want, (window, res.tokens, want)
        assert res.ok
        assert eng.pool.n_free == 2   # slot + pages released
        assert eng.pool.alloc.pages_in_use == eng.metrics_summary()[
            "pages"]["radix_pages"]


def test_eos_out_of_vocab_rejected(params):
    eng = Engine(params, CFG, EngineConfig(pool_size=1))
    res = eng.submit(_greedy("bad", [1, 2], eos=CFG.vocab_size + 3))
    assert res is not None and res.finish_reason == REJECT_BAD_REQUEST


# ---------------------------------------------------------------------------
# cancel during a window
# ---------------------------------------------------------------------------

def test_cancel_during_window_masks_at_next_dispatch(params):
    """cancel() with a dispatch in flight is a LIFECYCLE MASK, not a
    window break: the call defers (no drain, the in-flight window
    keeps flying), the kill flag rides the NEXT dispatch — after which
    the slot emits nothing — and the terminal result surfaces from the
    next step with the already-committed tokens, slot + pages freed at
    that boundary."""
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4,
                                           decode_window=4))
    req = _greedy("c0", [9, 2, 6], max_new=20)
    offline = _offline(params, [req])["c0"]
    assert eng.submit(req) is None
    eng.step()                        # admission boundary (mixed window)
    eng.step()                        # window 2 launched, window 1 drained
    assert eng._inflight is not None
    assert eng.cancel("c0")
    assert eng._inflight is not None, \
        "a masked cancel must NOT drain the in-flight window"
    out = eng.step()                  # kill flag rides this dispatch
    res = {r.id: r for r in out}["c0"]
    assert res.finish_reason == FINISH_CANCELLED
    assert eng.pool.n_free == 2, "slot + pages freed at the boundary"
    # tokens committed before the mask landed, byte-identical to the
    # offline prefix
    assert 1 <= len(res.tokens) <= 20
    assert res.tokens == offline[:len(res.tokens)]
    n_before = len(res.tokens)
    rest = eng.drain()                # the masked window drains empty
    assert eng.idle
    assert not rest and len(res.tokens) == n_before, \
        "a cancelled slot must emit no tokens after the mask lands"
    wb = eng.metrics_summary()["window_breaks"]
    assert wb["cancel"] == 0, wb


def test_deadline_expiry_masks_without_breaking_windows(params):
    """An ACTIVE request passing its deadline is killed through the
    same per-dispatch mask as a cancel — reason ``deadline``, tokens
    produced so far on the terminal result, zero window breaks — with
    the deadline precomputed at admission into the engine's vectorized
    expiry mirror."""
    class Clk:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clk()
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4,
                                           decode_window=4), clock=clk)
    req = _greedy("d0", [9, 2, 6], max_new=24)
    req.deadline = 100.0
    offline = _offline(params, [req])["d0"]
    assert eng.submit(req) is None
    eng.step()
    eng.step()
    assert eng._inflight is not None
    clk.t = 100.0                     # the deadline passes mid-window
    out = eng.step()                  # expiry -> kill flag, no drain-break
    res = {r.id: r for r in out}["d0"]
    assert res.finish_reason == FINISH_DEADLINE
    assert res.tokens == offline[:len(res.tokens)]
    assert eng.pool.n_free == 2
    n_before = len(res.tokens)
    eng.drain()
    assert eng.idle and len(res.tokens) == n_before
    wb = eng.metrics_summary()["window_breaks"]
    assert wb["deadline"] == 0 and wb["cancel"] == 0, wb


def test_cancel_after_window_finished_it(params):
    """A cancel racing a window that already finished the request (its
    eos landed mid-window): the drain surfaces the natural finish;
    cancel reports found. (Budget finishes can't race — the engine
    stops double-buffering once every live budget fits one window.)"""
    # a prompt whose greedy stream has a token whose FIRST occurrence is
    # at index 1 or 2 — inside the first (mixed prefill+decode) window,
    # so the eos fires while that window is in flight. Searched, not
    # hard-coded: one prompt's stream moves with the jax release.
    for seed in range(200):
        prompt = np.random.default_rng(seed).integers(
            0, CFG.vocab_size, 4).tolist()
        base = _offline(params, [_greedy("c1", prompt, max_new=20)])["c1"]
        firsts = [base[i] for i in (1, 2) if base.index(base[i]) == i]
        if firsts:
            break
    eos_tok = firsts[0]
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=4,
                                           decode_window=4))
    assert eng.submit(_greedy("c1", prompt, max_new=20,
                              eos=eos_tok)) is None
    eng.step()          # admission rides the window; eos inside it
    assert eng._inflight is not None
    assert eng.cancel("c1")
    res = {r.id: r for r in eng.drain()}["c1"]
    assert res.finish_reason == FINISH_EOS
    assert res.tokens == base[:base.index(eos_tok) + 1]


# ---------------------------------------------------------------------------
# speculative verify interleaved with windows
# ---------------------------------------------------------------------------

def test_spec_verify_interleaves_with_windows(params):
    """An engine with a drafter attached composes with decode windows:
    verify steps while speculation is active, multi-token windows while
    it is degraded, byte-identical greedy output through a
    disable -> window -> re-enable cycle."""
    from replicatinggpt_tpu.serve.speculative import NGramDrafter
    prompt = np.tile(np.array([7, 3, 7, 3], np.int32), 4)
    req = _greedy("sp0", prompt, max_new=20)
    want = _offline(params, [req])["sp0"]

    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4,
                                           decode_window=4),
                 drafter=NGramDrafter(k=3))
    assert eng.submit(_greedy("sp0", prompt, max_new=20)) is None
    out = []
    out.extend(eng.step())            # admission
    out.extend(eng.step())            # verify step (spec active)
    assert eng.metrics.counters.get("spec_draft_tokens", 0) > 0
    disp_before = eng.metrics.counters.get("decode_dispatches", 0)
    eng.set_spec_active(False)        # degrade -> window path
    out.extend(eng.step())
    out.extend(eng.step())
    assert eng.metrics.counters["decode_dispatches"] > disp_before, \
        "degraded steps should run decode windows"
    out.extend(eng._drain_pending())  # settle before flipping back
    eng.set_spec_active(True)         # resync drafter from host history
    out.extend(eng.drain())
    got = {r.id: r.tokens for r in out}
    assert got == {"sp0": want}


def test_spec_eos_truncates_verify_window(params):
    """An eos accepted inside a speculative verify window ends the
    stream at the eos token — reason ``eos``, committed suffix past it
    dropped."""
    from replicatinggpt_tpu.serve.speculative import NGramDrafter
    prompt = np.tile(np.array([7, 3, 7, 3], np.int32), 4)
    base = _offline(params, [_greedy("x", prompt, max_new=16)])["x"]
    eos_tok = base[7]
    want = base[:base.index(eos_tok) + 1]
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=4),
                 drafter=NGramDrafter(k=3))
    assert eng.submit(_greedy("x", prompt, max_new=16,
                              eos=eos_tok)) is None
    res = {r.id: r for r in eng.drain()}["x"]
    assert res.finish_reason == FINISH_EOS
    assert res.tokens == want


# ---------------------------------------------------------------------------
# zero recompiles across the whole async surface
# ---------------------------------------------------------------------------

def test_zero_recompiles_across_async_replay(params):
    """compile_counts stays flat through a scenario containing every
    async seam: mid-window admissions, EOS inside a window, a
    cancel-during-window, and a speculative disable/re-enable — after
    one warmup engine of identical shapes compiled the programs."""
    from replicatinggpt_tpu.serve.speculative import NGramDrafter
    ecfg = EngineConfig(pool_size=2, max_queue=16, decode_window=4)

    def build():
        return Engine(params, CFG, ecfg, drafter=NGramDrafter(k=3))

    def scenario(eng):
        out = []
        prompt = np.tile(np.array([7, 3, 7, 3], np.int32), 2)
        assert eng.submit(_greedy("a", prompt, max_new=24)) is None
        out.extend(eng.step())
        out.extend(eng.step())                 # verify steps
        eng.set_spec_active(False)             # -> windows
        out.extend(eng.step())
        out.extend(eng.step())
        assert eng.submit(_greedy("b", [1, 2, 3], max_new=12,
                                  eos=44)) is None   # mid-window arrival
        out.extend(eng.step())
        assert eng.submit(_greedy("c", [4, 4], max_new=16)) is None
        out.extend(eng.step())
        out.extend(eng.step())
        eng.cancel("a")                        # cancel during window
        out.extend(eng._drain_pending())
        eng.set_spec_active(True)              # re-probe path
        out.extend(eng.drain())
        return {r.id: r.finish_reason for r in out}

    warm = build()
    scenario(warm)
    counts = compile_counts()
    eng = build()
    reasons = scenario(eng)
    assert compile_counts() == counts, "async replay recompiled"
    assert set(reasons) == {"a", "b", "c"}
    assert reasons["a"] == FINISH_CANCELLED


# ---------------------------------------------------------------------------
# the BENCH_r03 CPU proxy: dispatch-split acceptance
# ---------------------------------------------------------------------------

def test_dispatch_split_on_shared_prefix_trace(params):
    """The dispatch-amortization acceptance pin, continuous-window
    edition. The DETERMINISTIC half is the load-bearing one: dispatches
    per decoded token collapse >= 4x at --decode-window 8 vs the
    blocked k=1 loop (admissions now ride mixed windows, so the old
    k=1-admission dilution is gone), with zero recompiles after warmup
    in both arms. The wall-clock half is a regression floor, not a
    multiplier: this PR's launch-input caching removed the
    per-dispatch device_put tax that WAS the 3-5x timing headroom of
    the PR 10 pin (both arms now skip it), and what remains of a CPU
    launch is XLA:CPU executing thunks inline on the dispatching
    thread — device time a TPU launch does not pay, scaling with k by
    construction. So on CPU we pin that the windowed arm's wall-clock
    launch cost per token stays in the same band as blocked (<= 1.6x,
    3 trials, best kept) while the TPU row queued in RESULTS.md
    carries the real timing multiplier."""
    rcfg = ReplayConfig(n_requests=12, rate=50_000.0, seed=3,
                        prompt_len_min=6, prompt_len_max=9,
                        shared_prefix_len=5, max_new_tokens=24,
                        greedy=True, prompt_mode="shared_prefix")
    ecfg = EngineConfig(pool_size=4, max_queue=32, page_size=8)
    ratio = float("inf")
    for _ in range(3):
        win = run_replay(params, CFG, rcfg,
                         dataclasses.replace(ecfg, decode_window=8))
        blk = run_replay(params, CFG, rcfg, ecfg)
        assert win["recompiles_after_warmup"] == 0
        assert blk["recompiles_after_warmup"] == 0
        assert win["n_completed"] == blk["n_completed"] == 12
        dw, db = win["dispatch"], blk["dispatch"]
        assert dw["window_k"] == 8 and db["window_k"] == 1
        # deterministic half: dispatches per token
        tok_w = win["counters"]["decode_tokens"]
        tok_b = blk["counters"]["decode_tokens"]
        assert tok_w == tok_b
        assert ((db["dispatches"] / tok_b)
                / (dw["dispatches"] / tok_w)) >= 4.0
        # continuous windows: the saturating backlog admits at window
        # boundaries without a single break
        assert win["window_breaks"]["admit"] == 0
        # wall-clock floor (see docstring)
        assert db["host_dispatch_ms_per_token"] > 0
        ratio = min(ratio, dw["host_dispatch_ms_per_token"]
                    / db["host_dispatch_ms_per_token"])
        if ratio <= 1.6:
            break
    assert ratio <= 1.6, (
        f"windowed launch cost fell {ratio:.2f}x behind blocked across "
        f"3 trials (blocked {db}, windowed {dw})")


def test_windowed_greedy_byte_identical_on_shared_prefix_trace(params):
    """The other half of the acceptance line: the SAME shared-prefix
    request set decoded at window 8 and window 1 produces byte-
    identical greedy streams (run_replay measures; this pins tokens)."""
    rng = np.random.default_rng(3)
    shared = rng.integers(0, CFG.vocab_size, (8,)).astype(np.int32)

    def reqs():
        out = []
        for i in range(8):
            tail = rng.integers(0, CFG.vocab_size,
                                (int(rng.integers(2, 8)),))
            out.append(_greedy(f"p{i}",
                               np.concatenate([shared, tail]),
                               max_new=12))
        return out

    streams = []
    for window in (1, 8):
        rng = np.random.default_rng(3)
        shared = rng.integers(0, CFG.vocab_size, (8,)).astype(np.int32)
        eng = Engine(params, CFG, EngineConfig(pool_size=4, max_queue=32,
                                               page_size=8,
                                               decode_window=window))
        for r in reqs():
            assert eng.submit(r) is None
        streams.append({r.id: r.tokens for r in eng.drain()})
    assert streams[0] == streams[1]


# ---------------------------------------------------------------------------
# continuous windows: admission storm, retention, k-autotune (ISSUE 13)
# ---------------------------------------------------------------------------

class _VClock:
    """Virtual clock: the storm driver advances it one dt per engine
    step, so admission order, deadline expiry and cancel timing are
    identical run to run (the loadgen StepClock pattern)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _drive_storm(params, storm, window, dt=0.005, pool=4):
    """Replay an admission_storm() tuple through a fresh engine on a
    virtual clock; returns (engine, {id: RequestResult})."""
    trace, cancels, deadlines = storm
    clk = _VClock()
    eng = Engine(params, CFG,
                 EngineConfig(pool_size=pool, max_queue=128,
                              decode_window=window), clock=clk)
    results = {}
    i = ci = 0
    guard = 0
    while len(results) < len(trace):
        guard += 1
        assert guard < 100_000, "storm replay did not converge"
        now = clk()
        while i < len(trace) and trace[i][0] <= now:
            _, req = trace[i]
            if req.id in deadlines:
                req.deadline = now + deadlines[req.id]
            rej = eng.submit(req)
            if rej is not None:
                results[rej.id] = rej
            i += 1
        while ci < len(cancels) and cancels[ci][0] <= now:
            eng.cancel(cancels[ci][1])
            ci += 1
        if eng.idle:
            if i < len(trace):
                clk.t = max(clk.t + dt, trace[i][0])
                continue
            break
        for r in eng.step():
            results[r.id] = r
        clk.t += dt
    return eng, results


def _storm(n=48, seed=0, **kw):
    from replicatinggpt_tpu.serve.loadgen import (AdmissionStormConfig,
                                                  admission_storm)
    return admission_storm(CFG, AdmissionStormConfig(
        n_requests=n, seed=seed, deadline_s=0.08, cancel_after_s=0.02,
        **kw))


def test_admission_storm_token_identity_and_no_breaks(params):
    """THE satellite pin: across an admission+cancel+deadline storm at
    decode_window > 1, every greedy stream is a byte-prefix of the
    offline stream (cut exactly where its cancel/deadline mask landed),
    fully-completed streams are byte-identical, compile_counts stays
    flat against a warm engine of the same shapes, and NOT ONE window
    break is charged to admit/deadline/cancel — the storm rides the
    continuous-window path end to end."""
    storm = _storm()
    offline = _offline(params, [r for _, r in storm[0]])
    _drive_storm(params, storm, 8)            # warm (construction + drive)
    counts = compile_counts()
    eng, res = _drive_storm(params, storm, 8)
    assert compile_counts() == counts, "storm replay recompiled"
    assert len(res) == len(storm[0])
    finished = {r.finish_reason for r in res.values()}
    assert FINISH_CANCELLED in finished       # the storm really stormed
    assert FINISH_DEADLINE in finished
    for _, req in storm[0]:
        toks = res[req.id].tokens
        assert toks == offline[req.id][:len(toks)], req.id
        if res[req.id].finish_reason == FINISH_MAX_TOKENS:
            assert toks == offline[req.id], req.id
    wb = eng.metrics_summary()["window_breaks"]
    assert wb["admit"] == wb["deadline"] == wb["cancel"] == 0, wb


def test_storm_retains_idle_amortization(params):
    """THE ISSUE 13 acceptance: on the admission-heavy saturating
    trace the dispatch-split retains >= 90% of the idle-trace window
    amortization. Amortization is the deterministic dispatch-count
    split (blocked dispatches-per-token over windowed
    dispatches-per-token, same virtual-clock trace both arms); the
    pre-continuous-windows engine collapses to ~1.0 here by
    construction, because every admission-laden step fell back to
    blocked k=1."""
    storm = _storm()
    idle = (storm[0], [], {})     # same arrivals, no lifecycle churn

    def amortization(tr):
        eng_w, _ = _drive_storm(params, tr, 8)
        eng_b, _ = _drive_storm(params, tr, 1)
        cw, cb = eng_w.metrics.counters, eng_b.metrics.counters
        dpt_w = cw["decode_dispatches"] / cw["decode_tokens"]
        dpt_b = cb["decode_dispatches"] / cb["decode_tokens"]
        return dpt_b / dpt_w

    a_idle = amortization(idle)
    a_storm = amortization(storm)
    assert a_idle >= 4.0, a_idle  # windows genuinely amortize when idle
    assert a_storm >= 0.9 * a_idle, (
        f"storm kept only {a_storm / a_idle:.1%} of the idle-trace "
        f"amortization ({a_storm:.2f}x vs {a_idle:.2f}x)")


def test_autotune_climbs_buckets_zero_recompiles(params):
    """decode_window_auto: the additive-increase policy walks the
    bucketed window sizes (2 -> 4 -> 8 under CPU host-dispatch
    fractions) without a single recompile — every bucket's programs
    compiled at construction — and greedy streams are byte-identical
    to offline through the bucket moves."""
    ecfg = EngineConfig(pool_size=2, max_queue=64, decode_window=8,
                        decode_window_auto=True)
    assert ecfg.window_buckets() == (2, 4, 8)

    def reqs():
        return [_greedy(f"a{i}", [3 + i % 5, 1, 4], max_new=28)
                for i in range(12)]

    want = _offline(params, reqs())
    warm = Engine(params, CFG, ecfg)
    for r in reqs():
        warm.submit(r)
    warm.drain()
    counts = compile_counts()
    eng = Engine(params, CFG, ecfg)
    for r in reqs():
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want, "bucket moves must not change the streams"
    assert compile_counts() == counts, "a bucket move recompiled"
    dp = eng.metrics_summary()["dispatch"]
    assert dp["autotune"] and dp["window_k_max"] == 8
    assert dp["window_k"] in (2, 4, 8)
    assert dp["autotune_increases"] >= 1, dp


def test_spec_transition_mid_prefill_flushes_chunks(params):
    """A speculative re-enable while a windowed admission's in-window
    prefill is still INCOMPLETE (multi-window prefill: small
    prefill_chunk, window smaller than the chunk count) must complete
    the outstanding chunks host-side before the verify path runs —
    verify attends the slot's whole prompt range, so abandoned chunks
    would leave never-written (zero) K/V pages in that range and
    silently corrupt the stream (review-caught). Greedy argmax at
    random init is too flat to catch zero-row dilution, so the
    detector is the invariant itself: after the flip, no chunks
    outstanding and every prompt position's K row physically written —
    plus end-to-end parity."""
    from replicatinggpt_tpu.serve.speculative import NGramDrafter
    prompt = np.tile(np.array([7, 3, 7, 3], np.int32), 5)   # 20 tokens
    req = _greedy("mp0", prompt, max_new=10)
    want = _offline(params, [req])["mp0"]
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4,
                                           decode_window=2,
                                           prefill_chunk=4),
                 drafter=NGramDrafter(k=3))
    eng.set_spec_active(False)        # windows engage (pinned degraded)
    assert eng.submit(_greedy("mp0", prompt, max_new=10)) is None
    out = []
    out.extend(eng.step())            # admission boundary: mixed window
                                      # covers 2 of the 5 prompt chunks
    assert eng._pf_left.max() > 0, "prefill must still be outstanding"
    slot = eng.pool.slot_of("mp0")
    eng.set_spec_active(True)         # mid-prefill spec flip
    assert eng._pf_left.max() == 0, \
        "outstanding chunks must flush at the spec flip"
    # every prompt position's K row is physically written (the offset
    # axis is -2 in both cache layouts); position P-1 gets rewritten by
    # the first decode either way, so [0, P) is the invariant range
    k = np.asarray(eng.pool.cache["k"])
    psz = eng.pool.page_size
    tbl = eng.pool.tables[slot]
    for p_abs in range(int(prompt.size)):
        row = np.moveaxis(k[:, tbl[p_abs // psz]], -2, 0)[p_abs % psz]
        assert np.abs(row).sum() > 0, f"prompt position {p_abs} unwritten"
    out.extend(eng.drain())           # verify path decodes the rest
    got = {r.id: r.tokens for r in out}
    assert got == {"mp0": want}


def test_spec_transitions_still_count_window_breaks(params):
    """The one seam that legitimately still breaks windows: a
    speculative mode flip drains the in-flight window and the
    window_breaks{spec} counter records it (the PR's before/after
    observability — lifecycle reasons stay zero, spec does not)."""
    from replicatinggpt_tpu.serve.speculative import NGramDrafter
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4,
                                           decode_window=4),
                 drafter=NGramDrafter(k=3))
    eng.set_spec_active(False)
    prompt = np.tile(np.array([7, 3, 7, 3], np.int32), 4)
    assert eng.submit(_greedy("s0", prompt, max_new=20)) is None
    eng.step()
    eng.step()
    assert eng._inflight is not None
    eng.set_spec_active(True)         # drains the window: a spec break
    eng.drain()
    wb = eng.metrics_summary()["window_breaks"]
    assert wb["spec"] >= 1, wb
    assert wb["admit"] == wb["deadline"] == wb["cancel"] == 0, wb


# ---------------------------------------------------------------------------
# the paged kernel composes with windows
# ---------------------------------------------------------------------------

def test_paged_kernel_with_decode_window(params, monkeypatch):
    """The per-layer paged kernel inside the k = 2 window scan: parity
    with offline generate() (interpret mode on CPU)."""
    from replicatinggpt_tpu.ops import paged_pallas
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)
    cfg = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                      n_embd=64, dropout=0.0, attn_dropout=0.0,
                      dtype="float32", decode_cache_layout="packed")
    p64 = init_params(jax.random.PRNGKey(1), cfg)
    reqs = [_greedy("f0", [3, 1, 4, 1, 5], max_new=6),
            _greedy("f1", [9, 2, 6], max_new=5)]
    want = _offline(p64, reqs, cfg=cfg)
    eng = Engine(p64, cfg, EngineConfig(pool_size=2, max_queue=4,
                                        page_size=8, paged_kernel=True,
                                        decode_window=2))
    assert eng._use_pallas and eng.kernel_route.decode == "pallas"
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want
