"""Paged KV cache + radix prefix reuse (serve/pages.py, the paged
device programs in models/gpt.py, and ops/paged_pallas.py): allocator
fuzz vs a reference model, prefix-hit/COW/eviction engine behavior with
greedy parity and pinned-flat compile counts, paged-vs-contiguous
program equivalence, the Pallas fast path in interpret mode, and the
metrics_summary key schema bench dashboards depend on."""

import dataclasses
import heapq

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import init_params
from replicatinggpt_tpu.sample import GenerateConfig, generate
from replicatinggpt_tpu.serve import (Engine, EngineConfig, PageAllocator,
                                      ReplayConfig, Request, SamplingParams,
                                      Scheduler, compile_counts, run_replay)
from replicatinggpt_tpu.serve.requests import FINISH_MAX_TOKENS

# three layers: the paged programs address the stacked pool by (layer,
# page), and every parity below has a middle layer to get wrong
CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=3, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _offline_greedy(params, reqs, cfg=CFG):
    return {r.id: np.asarray(generate(
        params, r.prompt[None, :], cfg,
        GenerateConfig(max_new_tokens=r.max_new_tokens, greedy=True))
    )[0].tolist() for r in reqs}


def _greedy(rid, prompt, max_new=6):
    return Request(id=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new,
                   sampling=SamplingParams(greedy=True))


# ---------------------------------------------------------------------------
# allocator fuzz vs a host-side reference model (satellite)
# ---------------------------------------------------------------------------

def _check_allocator(alloc: PageAllocator, live):
    """Reference-model invariants: refcounts equal slot references
    exactly, free/in-use/radix sets are consistent, nothing leaks, and
    the extents' free counts, bitmasks, heap and buckets say what the
    free pages do."""
    counts = np.zeros_like(alloc.ref)
    for claim, _pos in live.values():
        for p in claim.pages:
            counts[p] += 1
    assert (counts == alloc.ref).all(), "refcount drift vs live claims"
    free = set(np.flatnonzero(alloc._is_free).tolist())
    assert alloc.pages_free == len(free)
    used = {p for claim, _ in live.values() for p in claim.pages}
    assert not (free & used), "page simultaneously free and mapped"
    assert not (free & set(alloc.page_node)), "cached page on free list"
    leaked = [p for p in range(alloc.n_pages)
              if p not in free and alloc.ref[p] == 0
              and p not in alloc.page_node]
    assert not leaked, f"leaked pages {leaked}"
    for claim, _ in live.values():
        assert len(set(claim.pages)) == len(claim.pages), \
            "one slot double-mapped a physical page"
    # a page shared by >= 2 slots can only have come from the radix
    for p in np.nonzero(counts >= 2)[0]:
        assert int(p) in alloc.page_node or any(
            p in (s for pair in [c.cow] for s, _ in pair)
            for c, _ in live.values()), f"untracked shared page {p}"
    P = alloc.block
    whole = set()
    for e, m in enumerate(alloc._ext_mask):
        bits = alloc._is_free[e * P:(e + 1) * P]
        assert m == sum(1 << int(i) for i in np.flatnonzero(bits))
        n = m.bit_count()
        if n == P:
            whole.add(e)
        else:
            assert e in alloc._part[n]
    assert sorted(alloc._whole) == sorted(whole) == sorted(set(alloc._whole))
    assert alloc._n_whole == len(whole)
    assert sum(map(len, alloc._part)) + len(whole) == len(alloc._ext_mask)


def _fuzz(seed: int, blocks, steps: int = 400):
    """Seeded random acquire/advance/release ops, the same on one
    allocator a block size in ``blocks``, against the reference model:
    refcounts, no double-map, no leaks, claimed prefixes byte-identical
    to the prompts that registered them; and an acquire succeeds exactly
    when the rule says (the pages the claim needs beyond its cached
    prefix, one more for a copy-on-write split, against the free pages
    and the refcount-0 radix pages it does not claim), with the same
    outcome, free count and evictions whatever the block: where the
    pages go never moves admission."""
    rng = np.random.default_rng(seed)
    psz = 4
    allocs = [PageAllocator(n_pages=20, page_size=psz, prefix_cache=True,
                            block_pages=b) for b in blocks]
    seen = []           # past prompts, replayed verbatim for full hits
    lives = [{} for _ in allocs]    # id -> (claim, next-write pos)
    contents = [{} for _ in allocs]  # phys page -> token bytes
    next_id = 0
    for step in range(steps):
        op = rng.choice(["acquire", "advance", "release"],
                        p=[0.45, 0.3, 0.25])
        if op == "acquire":
            if seen and rng.random() < 0.35:
                # verbatim repeat of an earlier prompt: the full-prefix-
                # hit arm, which is the only path to copy-on-write
                prompt = seen[int(rng.integers(len(seen)))].copy()
            else:
                P = int(rng.integers(1, 17))
                # tiny alphabet so partial prefixes collide often too
                prompt = rng.integers(0, 3, (P,)).astype(np.int32)
                seen.append(prompt)
            P = int(prompt.size)
            cap = int(rng.integers(1, 9))
            outcomes = []
            for alloc, live, content in zip(allocs, lives, contents):
                chain = alloc.radix.lookup(prompt, psz, touch=False)
                need = (alloc.n_pages_for(P, cap) - len(chain)
                        + (bool(chain) and len(chain) * psz == P))
                claimed = {n.page for n in chain}
                held = ({p for c, _ in live.values() for p in c.pages}
                        | set(alloc.page_node))
                reclaim = sum(1 for p in alloc.page_node
                              if alloc.ref[p] == 0 and p not in claimed)
                rule = need <= alloc.n_pages - len(held) + reclaim
                can = alloc.can_acquire(prompt, cap)
                claim = alloc.acquire(prompt, cap)
                assert (claim is not None) == can == rule, \
                    "acquire disagreed with the admission rule"
                outcomes.append(claim and (claim.claimed_tokens,
                                           len(claim.pages), len(claim.cow)))
                if claim is None:
                    continue
                assert claim.claimed_tokens % psz == 0
                assert claim.claimed_tokens <= P
                # claimed pages must hold exactly the prompt's prefix bytes
                for g in range(claim.claimed_tokens // psz):
                    want = prompt[g * psz:(g + 1) * psz].tobytes()
                    got_page = claim.pages[g]
                    if claim.cow and g == claim.claimed_tokens // psz - 1:
                        got_page = claim.cow[0][0]  # COW source held them
                    assert content[got_page] == want, "stale prefix claim"
                assert len(claim.pages) == alloc.n_pages_for(P, cap)
                alloc.register(claim, P - 1)
                live[next_id] = (claim, P - 1)
            assert len(set(outcomes)) == 1, outcomes
            next_id += outcomes[0] is not None
        elif op == "advance" and lives[0]:
            cid = int(rng.choice(list(lives[0])))
            step_by = int(rng.integers(1, 5))
            for alloc, live in zip(allocs, lives):
                claim, pos = live[cid]
                alloc.register(claim, pos + step_by)
                live[cid] = (claim, pos + step_by)
        elif op == "release" and lives[0]:
            cid = int(rng.choice(list(lives[0])))
            for alloc, live in zip(allocs, lives):
                alloc.release(live.pop(cid)[0])
        for alloc, live, content in zip(allocs, lives, contents):
            # sync the content shadow with registrations/evictions
            for claim, _pos in live.values():
                for g in range(claim.next_reg):
                    p = claim.pages[g]
                    if p in alloc.page_node:
                        content[p] = claim.prompt[g * psz:(g + 1) * psz]\
                            .tobytes()
            for p in list(content):
                if p not in alloc.page_node:
                    del content[p]
            _check_allocator(alloc, live)
        assert len({(a.pages_free, a.evictions, a.prefix_hits,
                     a.cow_copies) for a in allocs}) == 1
    for alloc in allocs:
        assert alloc.prefix_hits > 0, "fuzz never exercised a prefix hit"
        assert alloc.evictions > 0, "fuzz never exercised eviction"
        assert alloc.cow_copies > 0, "fuzz never exercised copy-on-write"


def test_page_allocator_fuzz():
    """A few hundred seeded random ops against the reference model
    (``_fuzz``), pages handed out one at a time."""
    _fuzz(42, (1,))


@pytest.mark.parametrize("seed", [42, 5, 1234])
def test_page_allocator_fuzz_in_blocks(seed):
    """The same ops on allocators whose blocks are 1, 2, 3, 4 and 8
    pages (an extent of 3 leaves the pool's last one short), in
    lockstep: each holds to the reference model, and every acquire
    succeeds or fails as on the others and as the rule says."""
    _fuzz(seed, (1, 2, 3, 4, 8))


def test_allocator_hands_a_request_its_blocks_as_runs():
    """A block the request fills whole is a whole aligned extent, the
    request's last part of a block one run beside another request's
    part, and a prefix claim's copy-on-write split starts the run of
    the pages after it; ``ops.paged_pallas.block_runs`` reads each
    block's run back."""
    from replicatinggpt_tpu.ops.paged_pallas import block_runs
    alloc = PageAllocator(n_pages=32, page_size=4, block_pages=4)
    a = alloc.acquire(np.arange(20, dtype=np.int32), cap=9)   # 7 pages
    assert a.pages == [0, 1, 2, 3, 4, 5, 6]
    b = alloc.acquire(np.arange(1, 9, dtype=np.int32), cap=1)  # 2 pages
    assert b.pages == [8, 9]
    c = alloc.acquire(np.arange(2, 6, dtype=np.int32), cap=1)  # 1 page
    assert c.pages == [7]            # the fullest extent with room
    assert block_runs(a.pages, 4).tolist() == [4, 3]
    alloc.release(a)
    assert alloc.pages_free == 32 - 3
    alloc.register(b, 8)             # b's two prompt pages: the radix
    d = alloc.acquire(np.arange(1, 9, dtype=np.int32), cap=7)  # 4 pages
    assert d.claimed_tokens == 8 and d.cow == [(9, d.pages[1])]
    assert d.pages[0] == 8 and block_runs(d.pages[1:], 4).tolist() == [3]


def _lognormal(rng, n, median, sigma, lo, hi):
    return np.clip(np.round(rng.lognormal(np.log(median), sigma, n)),
                   lo, hi).astype(int)


def test_allocator_runs_survive_ten_turnovers_of_the_pool():
    """The serve-decode-joyai cell's pool on the host: 256 slots over
    40,960 pages of 16, blocks of 32 (its latent kernel's), the closed
    loop's lengths (prompts lognormal median 512, sigma 1.0, 16-3,072;
    answers median 1,024, sigma 0.7, 64-4,096), each request's whole
    budget reserved at admission, admitted first come first served as
    pages allow (99% of them reserved), each finishing after its answer.
    Over the tenth turnover of the pool, at least 85% of the live
    blocks (a block holding a position under its slot's) own one run of
    the pool, by the kernel's rule (``block_runs``); a LIFO free list
    left about 5%."""
    from replicatinggpt_tpu.ops.paged_pallas import block_runs
    rng = np.random.default_rng(0)
    n_pages, psz, P, slots, N = 40_960, 16, 32, 256, 5000
    alloc = PageAllocator(n_pages, psz, prefix_cache=False, block_pages=P)
    prompts = _lognormal(rng, N, 512, 1.0, 16, 3072)
    answers = _lognormal(rng, N, 1024, 0.7, 64, 4096)
    live, ends, t, nxt, freed, shares, reserved = {}, [], 0, 0, 0, [], []
    while freed < 10 * n_pages:
        while len(live) < slots:
            prompt = np.zeros(prompts[nxt], np.int32)
            claim = alloc.acquire(prompt, int(answers[nxt]))
            if claim is None:
                break
            live[nxt] = (t, prompts[nxt], block_runs(claim.pages, P), claim)
            heapq.heappush(ends, (t + answers[nxt], nxt))
            nxt += 1
        reserved.append(alloc.pages_in_use / n_pages)
        t, done = heapq.heappop(ends)
        claim = live.pop(done)[3]
        alloc.release(claim)
        freed += len(claim.pages)
        if freed > 9 * n_pages:
            runs = blocks = 0
            for t0, n_prompt, run, _ in live.values():
                pages = -(-(n_prompt - 1 + t - t0) // psz)
                full, rest = divmod(pages, P)
                runs += (run[:full] == P).sum() + (rest > 0
                                                  and run[full] >= rest)
                blocks += full + (rest > 0)
            shares.append(runs / blocks)
    assert np.mean(reserved) > 0.99
    assert np.mean(shares) >= 0.85, np.mean(shares)


def test_allocator_rejects_when_exhausted_and_recovers():
    alloc = PageAllocator(n_pages=4, page_size=4, prefix_cache=True)
    a = alloc.acquire(np.arange(8, dtype=np.int32), cap=8)   # 4 pages
    assert a is not None and alloc.pages_free == 0
    assert not alloc.can_acquire(np.arange(4, dtype=np.int32), cap=1)
    assert alloc.acquire(np.arange(4, dtype=np.int32), cap=1) is None
    alloc.register(a, 20)
    alloc.release(a)
    # the two full prompt pages stay as radix cache (refcount 0) and are
    # evictable; a new request can reclaim through them
    assert alloc.can_acquire(np.ones((12,), np.int32), cap=4)
    b = alloc.acquire(np.ones((12,), np.int32), cap=4)
    assert b is not None
    assert alloc.evictions > 0


# ---------------------------------------------------------------------------
# engine: prefix hits, copy-on-write, eviction — parity + flat compiles
# ---------------------------------------------------------------------------

def test_prefix_hit_skips_prefill_with_parity(params):
    """Identical page-aligned prompt twice: the second admission claims
    the whole prefix (zero prefill dispatches beyond the COW split) and
    still produces the exact offline greedy stream."""
    prompt = (np.arange(16, dtype=np.int32) % 13) + 1     # P == 2 pages
    ecfg = EngineConfig(pool_size=2, max_queue=8, page_size=8)
    eng = Engine(params, CFG, ecfg)
    a, b = _greedy("a", prompt), _greedy("b", prompt.copy())
    want = _offline_greedy(params, [a, b])
    eng.submit(a)
    res = {r.id: r.tokens for r in eng.drain()}
    prefill_calls = eng._prefill_guard.calls
    counts = compile_counts()
    eng.submit(b)
    res.update({r.id: r.tokens for r in eng.drain()})
    assert res == want
    assert eng._prefill_guard.calls == prefill_calls   # fully cached
    assert compile_counts() == counts                  # COW + hit: no compile
    pg = eng.metrics_summary()["pages"]
    assert pg["prefix_hit_tokens"] == 16
    assert pg["cow_copies"] == 1                       # frontier page split
    assert eng.metrics.counters["prefill_tokens"] == 16  # first request only


def test_concurrent_shared_prompts_parity(params):
    """Several requests with one shared prompt admitted in the SAME
    step: later admissions claim the earlier one's just-registered
    pages; every stream matches offline."""
    prompt = (np.arange(16, dtype=np.int32) % 11).astype(np.int32)
    eng = Engine(params, CFG, EngineConfig(pool_size=4, max_queue=8,
                                           page_size=8))
    reqs = [_greedy(f"c{i}", prompt.copy(), max_new=5) for i in range(4)]
    want = _offline_greedy(params, reqs)
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want
    assert eng.metrics_summary()["pages"]["prefix_hits"] == 3


def test_eviction_under_page_pressure_parity_and_flat_compiles(params):
    """Acceptance: a physical pool much smaller than slots*max_pages —
    admissions, prefix hits, LRU evictions and a COW split all happen
    mid-replay and compile_counts stays pinned flat, with every greedy
    stream identical to offline generate()."""
    # seed chosen for a trace OFF the f32 knife edge: generate() runs one
    # fused jitted scan while the engine dispatches separate programs, so
    # CPU f32 rounding can differ by ~1e-2 in logits — on near-tie prompts
    # that flips an argmax for the CONTIGUOUS engine exactly as for the
    # paged one (verified bit-identical), i.e. it is not a paging effect
    rng = np.random.default_rng(1)
    shared = ((np.arange(16) % 9) + 2).astype(np.int32)
    ecfg = EngineConfig(pool_size=2, max_queue=64, page_size=8, n_pages=6)
    eng = Engine(params, CFG, ecfg)
    eng.submit(_greedy("warm", shared, max_new=2))
    eng.drain()
    base = compile_counts()
    reqs = []
    for i in range(10):
        if i % 3 == 0:
            prompt = shared.copy()                 # prefix-hit + COW arm
        else:
            P = int(rng.integers(3, 20))
            prompt = rng.integers(0, CFG.vocab_size, (P,))\
                .astype(np.int32)
        reqs.append(_greedy(f"e{i}", prompt, max_new=4))
    want = _offline_greedy(params, reqs)
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert compile_counts() == base     # zero recompiles through it all
    assert got == want
    pg = eng.metrics_summary()["pages"]
    assert pg["evictions"] > 0
    assert pg["cow_copies"] > 0
    assert pg["prefix_hit_tokens"] > 0
    assert eng.pool.n_free == 2         # no leaked slots
    counts = np.zeros((eng.pool.n_pages,), np.int64)
    assert eng.pool.alloc.ref.max() == 0  # no leaked page refs
    del counts


def test_admission_gates_on_free_pages_not_just_slots(params):
    """With pages scarcer than slots, a request that cannot reserve its
    whole lifetime stays QUEUED (strict FIFO) until a finish frees
    pages — and then completes with parity."""
    ecfg = EngineConfig(pool_size=4, max_queue=8, page_size=8, n_pages=4,
                        prefix_cache=False)
    eng = Engine(params, CFG, ecfg)
    big = _greedy("big", np.arange(1, 17, dtype=np.int32), max_new=16)
    big2 = _greedy("big2", np.arange(2, 18, dtype=np.int32), max_new=16)
    want = _offline_greedy(params, [big, big2])
    assert eng.submit(big) is None
    assert eng.submit(big2) is None
    eng.step()
    # big took the whole 4-page pool; big2 must wait despite 3 free slots
    assert eng.pool.slot_of("big") is not None
    assert eng.pool.slot_of("big2") is None
    assert eng.pool.n_free == 3
    res = {r.id: r.tokens for r in eng.drain()}
    assert res == want


def test_duplicate_request_id_rejected_in_flight(params):
    """Ids key results, cancellation, the journal and the pools'
    reverse indexes — a duplicate of an IN-FLIGHT id must be rejected
    at submit (and the id becomes reusable after the first finishes)."""
    eng = Engine(params, CFG, EngineConfig(pool_size=1, max_queue=4))
    assert eng.submit(_greedy("dup", [1, 2], max_new=3)) is None
    assert eng.submit(_greedy("other", [3], max_new=3)) is None  # queued
    for req_again in ([4, 5], [6]):        # active dup AND queued dup
        rej = eng.submit(_greedy("dup" if req_again == [4, 5] else "other",
                                 req_again, max_new=2))
        assert rej is not None
        assert rej.finish_reason == "rejected_bad_request"
    res = {r.id: r for r in eng.drain()}
    assert set(res) == {"dup", "other"}
    assert eng.submit(_greedy("dup", [7], max_new=2)) is None  # reusable
    assert len(eng.drain()) == 1


def test_cancel_during_decode_releases_pages_promptly(params):
    """Cancel of an ACTIVELY STREAMING request (tokens already
    committed, mid-decode — the SSE-stream cancellation path): the slot
    AND every reserved KV page release immediately at cancel(), not at
    the next step or at what would have been completion. Only
    refcount-0 radix-cached prefix pages may stay resident, and the
    freed capacity admits a page-hungry successor at once — with
    parity, without a recompile."""
    ecfg = EngineConfig(pool_size=2, max_queue=4, page_size=4, n_pages=8)
    eng = Engine(params, CFG, ecfg)
    # 6-token prompt + 20-token budget = ceil(25/4) = 7 of 8 pages
    doomed = _greedy("doomed", np.arange(1, 7, dtype=np.int32),
                     max_new=20)
    assert eng.submit(doomed) is None
    for _ in range(5):
        eng.step()
    n_streamed = len(eng.partial_tokens("doomed"))
    assert n_streamed >= 4                      # genuinely mid-stream
    assert eng.pool.alloc.pages_in_use == 7
    counts = compile_counts()
    assert eng.cancel("doomed")
    # released NOW: slot free, every slot-referenced page refcount 0
    assert eng.pool.n_free == eng.pool.n_slots
    assert (eng.pool.alloc.ref > 0).sum() == 0
    # resident pages are exactly the radix-cached prefix (refcount 0)
    assert (eng.pool.alloc.pages_in_use
            == len(eng.pool.alloc.page_node))
    # a successor needing most of the pool admits immediately
    succ = _greedy("succ", np.arange(2, 8, dtype=np.int32), max_new=18)
    want = _offline_greedy(params, [succ])
    assert eng.submit(succ) is None
    res = {r.id: r for r in eng.step()}   # surfaces doomed's terminal
    assert eng.pool.slot_of("succ") is not None     # admitted at once
    res.update({r.id: r for r in eng.drain()})
    assert res["doomed"].finish_reason == "cancelled"
    assert len(res["doomed"].tokens) == n_streamed  # partials preserved
    assert res["succ"].tokens == want["succ"]
    assert compile_counts() == counts               # cancel is host-only


def test_scheduler_fits_blocks_head_fifo():
    sch = Scheduler(max_queue=4, block_size=8, clock=lambda: 0.0)
    a = Request(id="a", prompt=np.array([1, 1, 1], np.int32))
    b = Request(id="b", prompt=np.array([2], np.int32))
    assert sch.submit(a) is None and sch.submit(b) is None
    # head does not fit: nothing admitted, ORDER preserved (no skip)
    admitted, dropped = sch.admit(2, fits=lambda r: r.prompt.size <= 2)
    assert admitted == [] and dropped == [] and sch.depth == 2
    admitted, _ = sch.admit(2, fits=lambda r: True)
    assert [r.id for r, _ in admitted] == ["a", "b"]


# ---------------------------------------------------------------------------
# paged device programs == contiguous programs (unit equivalence)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["heads", "packed"])
def test_decode_step_paged_matches_multi(params, layout):
    from replicatinggpt_tpu.models.gpt import (decode_step_multi,
                                               decode_step_paged,
                                               init_kv_cache,
                                               init_paged_kv_pool)
    cfg = dataclasses.replace(CFG, decode_cache_layout=layout)
    B, psz = 3, 8
    mp = cfg.block_size // psz
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, (B, 6)).astype(np.int32)
    pos0 = np.array([0, 3, 5], np.int32)
    cache_m = init_kv_cache(cfg, B)
    pool = init_paged_kv_pool(cfg, B * mp, psz)
    # identity mapping: slot b's logical page g -> physical b*mp + g
    tables = (np.arange(B)[:, None] * mp
              + np.arange(mp)[None, :]).astype(np.int32)
    active = np.ones((B,), bool)
    for step in range(6):
        pos = (pos0 + step).astype(np.int32)
        lg_m, cache_m = decode_step_multi(
            params, jnp.asarray(toks[:, step]), jnp.asarray(pos),
            cache_m, cfg)
        lg_p, pool = decode_step_paged(
            params, jnp.asarray(toks[:, step]), jnp.asarray(pos),
            jnp.asarray(active), jnp.asarray(tables), pool, cfg)
        np.testing.assert_allclose(np.asarray(lg_m), np.asarray(lg_p),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layout", ["heads", "packed"])
def test_verify_step_paged_matches_multi(params, layout):
    from replicatinggpt_tpu.models.gpt import (init_kv_cache,
                                               init_paged_kv_pool,
                                               prefill, verify_step_multi,
                                               verify_step_paged)
    cfg = dataclasses.replace(CFG, decode_cache_layout=layout)
    B, W, psz = 2, 4, 8
    mp = cfg.block_size // psz
    rng = np.random.default_rng(2)
    warm = rng.integers(0, cfg.vocab_size, (B, 10)).astype(np.int32)
    cache_m = prefill(params, jnp.asarray(warm), init_kv_cache(cfg, B), cfg)
    pool = init_paged_kv_pool(cfg, B * mp, psz)
    tables = (np.arange(B)[:, None] * mp
              + np.arange(mp)[None, :]).astype(np.int32)
    # mirror the contiguous prefill into the paged pool page by page
    km, vm = np.asarray(cache_m["k"]), np.asarray(cache_m["v"])
    kp, vp = (np.array(pool["k"]), np.array(pool["v"]))  # writable copies
    for b in range(B):
        for g in range(mp):
            sl = slice(g * psz, (g + 1) * psz)
            if layout == "packed":
                kp[:, b * mp + g] = km[:, b, sl]
                vp[:, b * mp + g] = vm[:, b, sl]
            else:
                kp[:, b * mp + g] = km[:, b, :, sl]
                vp[:, b * mp + g] = vm[:, b, :, sl]
    pool = {"k": jnp.asarray(kp), "v": jnp.asarray(vp)}
    window = rng.integers(0, cfg.vocab_size, (B, W)).astype(np.int32)
    pos = np.array([9, 6], np.int32)
    m = np.array([3, 2], np.int32)
    active = np.ones((B,), bool)
    lg_m, _ = verify_step_multi(params, jnp.asarray(window),
                                jnp.asarray(pos), jnp.asarray(m),
                                cache_m, cfg)
    lg_p, _ = verify_step_paged(params, jnp.asarray(window),
                                jnp.asarray(pos), jnp.asarray(m),
                                jnp.asarray(active), jnp.asarray(tables),
                                pool, cfg)
    # compare only REAL window positions (padding logits are garbage on
    # both paths, but differently-garbage: the multi path scatters pads
    # to S, the paged path drops them)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(lg_m)[b, :m[b] + 1],
                                   np.asarray(lg_p)[b, :m[b] + 1],
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layout", ["heads", "packed"])
def test_prefill_chunk_paged_addresses_its_own_layer(params, layout):
    """``prefill_chunk_paged`` over a stacked pool of three layers whose
    every row starts as noise: a prompt in two chunks through a scrambled
    table. THE PAGES IT WRITES: each layer's pages of the table hold the
    plain forward's K/V of that layer (``prefill``), position by
    position, and every other row of the pool is the noise it was. THE
    ROWS IT ATTENDS: the second chunk reads the first's rows through the
    table, layer by layer, so a deeper layer's K/V already say whether
    the layer before it gathered its own pages; the last layer's gather
    shows in the logits of the next decode step against the plain
    forward's. A gather that read layer 0's pages for every layer, or a
    slice that lost the layer, fails both."""
    from replicatinggpt_tpu.models.gpt import (decode_step_paged, forward,
                                               init_kv_cache, prefill,
                                               prefill_chunk_paged)
    cfg = dataclasses.replace(CFG, decode_cache_layout=layout)
    assert cfg.n_layer >= 3
    psz, Pc, n_prompt = 8, 8, 13
    mp = cfg.block_size // psz
    N = 2 * mp
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, (1, n_prompt + 1)).astype(
        np.int32)
    shape = ((cfg.n_layer, N, psz, cfg.n_embd) if layout == "packed"
             else (cfg.n_layer, N, cfg.n_head, psz, cfg.head_dim))
    noise = {n: rng.normal(size=shape).astype(np.float32) for n in "kv"}
    pool = {n: jnp.asarray(a) for n, a in noise.items()}
    table = rng.permutation(N)[:mp].astype(np.int32)
    for off in range(0, n_prompt, Pc):
        chunk = np.zeros((1, Pc), np.int32)
        n = min(Pc, n_prompt - off)
        chunk[0, :n] = prompt[0, off:off + n]
        pool = prefill_chunk_paged(
            params, jnp.asarray(chunk), jnp.int32(off), jnp.int32(n_prompt),
            jnp.asarray(table), pool, cfg)
    want = prefill(params, jnp.asarray(prompt[:, :n_prompt]),
                   init_kv_cache(cfg, 1), cfg)
    for name in "kv":
        got, ref = np.asarray(pool[name]), np.asarray(want[name])
        written = np.zeros(got.shape[:2] + (psz,), bool)   # (L, N, psz)
        for pos in range(n_prompt):
            page, o = int(table[pos // psz]), pos % psz
            written[:, page, o] = True
            for l in range(cfg.n_layer):
                g = got[l, page, o] if layout == "packed" else got[
                    l, page, :, o]
                r = ref[l, 0, pos] if layout == "packed" else ref[
                    l, 0, :, pos]
                np.testing.assert_allclose(g, r, atol=1e-5, rtol=1e-5,
                                           err_msg=f"{name} L{l} p{pos}")
        same = got == noise[name]
        rows = (same.all(-1) if layout == "packed"
                else same.all(-1).all(-2))                 # (L, N, psz)
        assert (rows == ~written).all()
    # the layers' K differ, so layer 0's rows would not pass for layer 1's
    assert np.abs(np.asarray(want["k"])[1] - np.asarray(want["k"])[0]
                  ).max() > 1e-2
    lg, _ = decode_step_paged(
        params, jnp.asarray(prompt[:, n_prompt]),
        jnp.asarray([n_prompt], jnp.int32), jnp.asarray([True]),
        jnp.asarray(table[None]), pool, cfg)
    ref_lg, _ = forward(params, jnp.asarray(prompt), cfg)
    np.testing.assert_allclose(np.asarray(lg)[0],
                               np.asarray(ref_lg)[0, n_prompt],
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Pallas fast path (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("psz,mp,pos", [
    (8, 4, [17, 9, 0]),            # one block a slot
    (16, 9, [130, 9, 0]),          # a block of 8 pages and a short one
    (8, 24, [128, 127, 191]),      # blocks of 16: frontier on their edges
], ids=["one-block", "table-of-9", "block-edges"])
def test_paged_pallas_kernel_matches_gather_reference(psz, mp, pos):
    from replicatinggpt_tpu.ops import paged_pallas
    from replicatinggpt_tpu.ops.attention import cached_attention
    rng = np.random.default_rng(0)
    B, H, D = 3, 2, 32
    N = B * mp + 1
    C = H * D
    # a stacked pool of three layers; the kernel reads the middle one
    kp = jnp.asarray(rng.normal(size=(3, N, psz, C)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(3, N, psz, C)), jnp.float32)
    pos = np.array(pos, np.int32)          # incl. the pos=0 fresh-only row
    # mapped up to the page the fresh row lands in; the rest stale 0s
    tables = np.zeros((B, mp), np.int32)
    perm = rng.permutation(N)
    for b in range(B):
        n = pos[b] // psz + 1
        tables[b, :n] = perm[b * mp:b * mp + n]
    q = jnp.asarray(rng.normal(size=(B, C)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, C)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, C)), jnp.float32)
    out = paged_pallas.paged_decode_attention(
        q, kn, vn, kp, vp, jnp.asarray(tables), jnp.asarray(pos), n_head=H,
        layer=1)
    ka = np.asarray(kp)[1][tables].reshape(B, mp * psz, C).copy()
    va = np.asarray(vp)[1][tables].reshape(B, mp * psz, C).copy()
    for b in range(B):
        ka[b, pos[b]] = np.asarray(kn)[b]
        va[b, pos[b]] = np.asarray(vn)[b]

    def split(x):
        return jnp.asarray(x.reshape(B, -1, H, D).transpose(0, 2, 1, 3))

    ref = cached_attention(split(np.asarray(q)[:, None, :]), split(ka),
                           split(va), jnp.asarray(pos))
    ref = np.asarray(ref).transpose(0, 2, 1, 3).reshape(B, C)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


# tables whose blocks are runs of the pool, or not (``page_runs``), and
# the same pages scattered: (W, page, table length, positions, layout).
# Under ``lane_tile_blocks`` pages of 16 are blocks of 8, pages of 8 of 16;
# a table of 20 pages of 16 ends in a short block of 4
RUN_CASES = {
    "all-runs": (1, 16, 24, [384, 384, 0, 17], "runs"),
    "none": (1, 16, 24, [384, 200, 0, 17], "none"),
    "mixed": (1, 16, 24, [384, 383, 129, 40], "mixed"),
    "short-last-block": (1, 16, 20, [320, 300, 129, 3], "runs"),
    "w8-mixed": (8, 8, 20, [150, 128, 3, 0], "mixed"),
}


@pytest.mark.parametrize("kind", ["plain", "int8", "shard-mask"])
@pytest.mark.parametrize("case", list(RUN_CASES))
def test_run_blocks_match_their_pages_scattered(lane_tile_blocks, page_runs,
                                                case, kind):
    """A block whose owned pages are one run of the pool is started as a
    copy a set bit of their count (``_walk_blocks``), any other a copy a
    page: the output is BIT-equal to that of the same pages scattered
    over the pool, whose blocks are not runs, and matches the gather
    reference. Every block a run, none, every other, a table's short
    last block and a window of 8 rows; a plain pool, an int8 one with its
    page scales, and a shard's owned subset (the 'data' halves of the
    pool, each a call of its own, ``fold=False``), where a run of the
    table splits between the halves (the tables rotated by 4 pages) and
    a half's part of a block starts past its first page. The walk marks
    as runs the blocks the rule says."""
    from test_pallas_routes import LAYER, N_LAYERS, _window_ref

    from replicatinggpt_tpu.ops import paged_pallas as pp
    from replicatinggpt_tpu.quant.kv import quantize_rows
    W, psz, mp, pos, layout = RUN_CASES[case]
    pos = np.array(pos, np.int32)
    H, D = 2, 32
    B, C, N = len(pos), H * D, len(pos) * mp
    rng = np.random.default_rng(9)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    q, kn, vn = f(B, W, C), f(B, W, C), f(B, W, C)
    kp, vp = f(N_LAYERS, N, psz, C), f(N_LAYERS, N, psz, C)
    P, _ = pp._walk_shape(mp, psz, C * 4, 2)
    tables = page_runs.tables(B, mp, P, layout)
    pools, extra = [kp, vp], {}
    if kind == "int8":
        (kq, ks), (vq, vs) = (quantize_rows(jnp.array(a), "int8", H, "page")
                              for a in (kp, vp))
        pools = [kq, vq, ks, vs]
    owned = [None]
    if kind == "shard-mask":
        tables = (tables + 4) % N
        prefix = np.asarray(pp.gqa_owned_pages(
            jnp.array(pos), jnp.zeros_like(pos), mp, psz, 0))
        owned = [prefix & (tables < N // 2), prefix & (tables >= N // 2)]
    moved, spread = page_runs.scatter(tables, pools, seed=5)

    def call(tab, arrays, own):
        if kind == "int8":
            extra.update(k_scales=jnp.array(arrays[2]),
                         v_scales=jnp.array(arrays[3]))
        out = pp.paged_window_attention(
            *map(jnp.array, (q, kn, vn, arrays[0], arrays[1], tab, pos)),
            n_head=H, layer=LAYER, fold=own is None,
            owned=None if own is None else jnp.array(own), **extra)
        return [np.asarray(o) for o in (out if own is not None else [out])]

    late = False    # a run that starts past its block's first page
    for own in owned:
        walk = pp.window_walk(jnp.array(tables), jnp.array(pos), psz,
                              C * pools[0].dtype.itemsize, 2,
                              None if own is None else jnp.array(own))
        words = np.asarray(walk[6])
        assert (words >> pp.RUN_SHIFT).tolist() \
            == page_runs.words(np.asarray(walk[0]), P)
        count = words & pp.COUNT_MASK
        first = words >> pp.RUN_SHIFT
        if own is None and layout == "runs":
            assert (first[count > 0] == 1).all()
        elif own is None and layout == "none":
            assert not first[count > 1].any()
        elif own is None:
            assert first[count > 1].any() and not first[count > 1].all()
        late = late or (first > 1).any()
        for a, b in zip(call(tables, pools, own), call(moved, spread, own)):
            np.testing.assert_array_equal(a, b)
    if kind == "shard-mask" and case == "all-runs":
        assert late          # a half's part of a run past the block's first
    if kind == "plain":
        np.testing.assert_allclose(
            call(tables, pools, None)[0],
            _window_ref(q, kn, vn, kp[LAYER], vp[LAYER], tables, pos, H),
            atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("case", ["mixed", "short-last-block"])
def test_sharded_run_blocks_match_their_pages_scattered(lane_tile_blocks,
                                                        page_runs, case):
    """The ``shard_map`` wrapper on the 2x2 (data, model) mesh over run
    tables rotated so that runs cross the 'data' halves of the pool (a
    shard's part of a run is a run: the shard-mask case above reads the
    walk): the merged output is bit-equal to that of the same pages
    scattered, each within its shard's half."""
    from test_pallas_routes import LAYER, N_LAYERS, _window_ref

    from replicatinggpt_tpu.ops import paged_pallas as pp
    from replicatinggpt_tpu.parallel.mesh import make_serve_mesh
    W, psz, mp, pos, layout = RUN_CASES[case]
    H, C, B = 2, 64, len(pos)
    N = B * mp
    rng = np.random.default_rng(4)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    q, kn, vn = f(B, W, C), f(B, W, C), f(B, W, C)
    kp, vp = f(N_LAYERS, N, psz, C), f(N_LAYERS, N, psz, C)
    P, _ = pp._walk_shape(mp, psz, C * 4, 2)
    tables = (page_runs.tables(B, mp, P, layout) + 4) % N
    moved, (kp2, vp2) = page_runs.scatter(tables, [kp, vp], seed=6,
                                          parts=2)
    mesh = make_serve_mesh(2, 2)
    outs = [np.asarray(pp.sharded_paged_window_attention(
        *map(jnp.array, (q, kn, vn, k, v, t, pos)), n_head=H, mesh=mesh,
        layer=LAYER)) for t, k, v in ((tables, kp, vp), (moved, kp2, vp2))]
    np.testing.assert_array_equal(*outs)
    np.testing.assert_allclose(
        outs[0], _window_ref(q, kn, vn, kp[LAYER], vp[LAYER], tables,
                             np.array(pos), H), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("case", ["mixed", "short-last-block", "w8-mixed"])
def test_run_blocks_wait_for_their_bytes(dma_interpret, lane_tile_blocks,
                                         page_runs, case):
    """A run block's few copies under the interpreter that models each
    copy and its semaphore: its waits, a set bit of its owned count as
    before, release exactly when the copies' rows have landed, and no
    read races a copy."""
    test_run_blocks_match_their_pages_scattered(lane_tile_blocks,
                                                page_runs, case, "plain")


def test_paged_kernel_engine_greedy_parity(params, monkeypatch):
    """The engine's opt-in Pallas paged decode route (the per-layer
    kernel, ops/paged_pallas) must keep exact greedy parity with
    offline generate(). Only the backend gate is opened: the envelope
    decides for itself at the test's widths."""
    from replicatinggpt_tpu.ops import paged_pallas
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)
    cfg = ModelConfig(vocab_size=65, block_size=32, n_layer=3, n_head=2,
                      n_embd=64, dropout=0.0, attn_dropout=0.0,
                      dtype="float32", decode_cache_layout="packed")
    p64 = init_params(jax.random.PRNGKey(1), cfg)
    reqs = [_greedy("k0", np.array([3, 1, 4, 1, 5], np.int32), max_new=6),
            _greedy("k1", np.array([9, 2, 6], np.int32), max_new=5)]
    want = _offline_greedy(p64, reqs, cfg=cfg)

    ecfg = EngineConfig(pool_size=2, max_queue=4, page_size=8,
                        paged_kernel=True)
    eng = Engine(p64, cfg, ecfg)
    assert eng._use_pallas, "the kernel route should be on under the patch"
    assert eng.kernel_route.decode == "pallas"
    assert eng.kernel_route.reasons == ()
    for r in reqs:
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want


@pytest.mark.parametrize("kv_quant,granularity",
                         [("none", "page"), ("int8", "page"),
                          ("fp8", "head")],
                         ids=["float32", "int8-page", "fp8-head"])
def test_decode_step_paged_kernel_matches_xla_reference(kv_quant,
                                                        granularity):
    """Interpret-mode parity of ``decode_step_paged``'s kernel route
    against its XLA gather route on one step: logits of the live rows
    and the post-write page pools, on mixed active/inactive slots at
    ragged positions. The kernel attends the STALE pool plus a fresh
    column and scatters afterwards, the gather route writes first; an
    inactive slot's write is dropped on both."""
    from replicatinggpt_tpu.models.gpt import decode_step_paged
    from replicatinggpt_tpu.quant.kv import quantize_rows
    cfg = ModelConfig(vocab_size=97, block_size=64, n_layer=3, n_head=2,
                      n_embd=64, dropout=0.0, attn_dropout=0.0,
                      dtype="float32", decode_cache_layout="packed")
    p = init_params(jax.random.PRNGKey(0), cfg)
    B, psz, N, mp = 4, 8, 32, 8
    rng = np.random.default_rng(0)
    cache = {}
    for name in ("k", "v"):
        pages = jnp.asarray(rng.normal(size=(cfg.n_layer, N, psz,
                                             cfg.n_embd)), jnp.float32)
        if kv_quant == "none":
            cache[name] = pages
        else:
            cache[name], cache[name + "s"] = quantize_rows(
                pages, kv_quant, cfg.n_head, granularity)
    tables = jnp.asarray(rng.permutation(N)[:B * mp]
                         .reshape(B, mp).astype(np.int32))
    pos = jnp.asarray(np.array([5, 0, 17, 23], np.int32))
    active = jnp.asarray(np.array([True, False, True, True]))
    tok = jnp.asarray(np.array([3, 0, 9, 50], np.int32))
    ref_lg, ref_c = decode_step_paged(p, tok, pos, active, tables,
                                      cache, cfg)
    ker_lg, ker_c = decode_step_paged(p, tok, pos, active, tables,
                                      cache, cfg, use_pallas=True)
    am = np.asarray(active)
    np.testing.assert_allclose(np.asarray(ker_lg)[am],
                               np.asarray(ref_lg)[am],
                               atol=1e-5, rtol=1e-5)
    assert set(ker_c) == set(ref_c) == set(cache)
    for name in cache:
        np.testing.assert_allclose(np.asarray(ker_c[name], np.float32),
                                   np.asarray(ref_c[name], np.float32),
                                   atol=1e-5, rtol=1e-5)
    # the step wrote one row a layer for each LIVE slot and nothing else
    changed = np.asarray(ker_c["k"], np.float32) != np.asarray(
        cache["k"], np.float32)
    rows = changed.any(axis=-1)                       # (L, N, psz)
    assert rows.sum() == cfg.n_layer * int(am.sum())
    for b in np.flatnonzero(am):
        page = int(tables[b, int(pos[b]) // psz])
        assert rows[:, page, int(pos[b]) % psz].all()


# ---------------------------------------------------------------------------
# replay + metrics schema
# ---------------------------------------------------------------------------

def test_shared_prefix_replay_hits_and_fewer_prefills(params):
    """The shared-prefix trace through run_replay: cache ON claims
    prefix tokens and dispatches less prefill than the SAME trace with
    the cache off, with identical greedy token streams."""
    rcfg = ReplayConfig(n_requests=12, rate=5000.0, seed=3,
                        prompt_len_min=10, prompt_len_max=16,
                        shared_prefix_len=8, max_new_tokens=4,
                        greedy=True, prompt_mode="shared_prefix")
    on = run_replay(params, CFG,
                    rcfg, EngineConfig(pool_size=4, max_queue=32,
                                       page_size=8))
    off = run_replay(params, CFG,
                     rcfg, EngineConfig(pool_size=4, max_queue=32,
                                        page_size=8, prefix_cache=False))
    assert on["n_completed"] == off["n_completed"] == 12
    assert on["recompiles_after_warmup"] == 0
    assert off["recompiles_after_warmup"] == 0
    assert on["pages"]["prefix_hit_tokens"] > 0
    assert off["pages"]["prefix_hit_tokens"] == 0
    assert (on["counters"]["prefill_tokens"]
            < off["counters"]["prefill_tokens"])


def test_metrics_summary_key_schema(params):
    """Pin the summary schema bench dashboards consume — a silently
    dropped field is a dashboard hole nobody notices until an incident
    (satellite)."""
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=4))
    eng.submit(_greedy("m", np.array([1, 2, 3], np.int32), max_new=3))
    res = eng.drain()
    assert res[0].finish_reason == FINISH_MAX_TOKENS
    s = eng.metrics_summary()
    for key in ("counters", "gauges", "histograms", "step_latency",
                "n_steps", "compile_counts", "compile_guards", "recovery",
                "pages", "kernel_route"):
        assert key in s, key
    # kernel-route decision (ISSUE 20): static per engine; the bench
    # serve artifact carries this block verbatim
    assert set(s["kernel_route"]) == {
        "route", "decode", "window", "sharded", "mesh", "kv_quant",
        "weight_quant", "granularity", "act_quant", "reasons"}
    assert s["kernel_route"]["route"] in ("pallas", "xla")
    assert "kernel_route_pallas" in s["gauges"]
    assert set(s["compile_counts"]) == {
        "decode", "mixed", "prefill", "verify", "page_copy",
        "page_export", "page_install", "draft_decode", "draft_prefill"}
    assert set(s["compile_guards"]) == {"decode", "mixed", "prefill",
                                        "verify", "page_copy",
                                        "page_export", "page_install"}
    # continuous-window observability (ISSUE 13): the break counters
    # keyed by reason, and the k-autotune fields in the dispatch block
    assert set(s["window_breaks"]) == {"admit", "deadline", "cancel",
                                       "spec", "reprobe"}
    for key in ("window_k", "window_k_max", "autotune",
                "autotune_increases"):
        assert key in s["dispatch"], key
    assert set(s["recovery"]) == {
        "watchdog_stalls", "spec_disables", "spec_reprobes",
        "shed_requests", "spec_active", "events"}
    assert set(s["pages"]) == {
        "page_size", "max_pages_per_slot", "n_pages", "pages_in_use",
        "pages_free", "page_utilization", "radix_pages", "prefix_cache",
        "prefix_lookups", "prefix_hits", "prefix_hit_tokens",
        "prefix_hit_rate", "evictions", "cow_copies",
        # sharded-serving block (ISSUE 12): on 1x1 the per-chip numbers
        # degenerate to the aggregate ones but the SCHEMA is mesh-
        # independent — dashboards and the router gauges never branch
        "mesh_shape", "aggregate_pages", "pages_per_chip",
        "pages_in_use_by_chip", "page_utilization_by_chip",
        # quantization gauges (ISSUE 15): same schema quantized or not
        # (values differ — pinned for a quantized engine in
        # tests/test_quant.py); bytes_per_page is the fixed-HBM
        # capacity denominator, kv_quant_bits the numeric mode gauge
        "kv_quant", "quant_granularity", "bytes_per_page",
        "kv_quant_bits",
        # disaggregation gauges (ISSUE 16): page export/install traffic
        # and transfer-pinned pages; zero on a colocated engine but the
        # schema never branches on tier
        "pages_exported", "pages_installed", "transfer_pins"}
    assert s["pages"]["kv_quant"] == "none"
    assert s["pages"]["kv_quant_bits"] == 32      # f32 test pool
    assert s["pages"]["mesh_shape"] == [1, 1]
    assert s["pages"]["aggregate_pages"] == s["pages"]["n_pages"]
    assert s["pages"]["pages_per_chip"] == s["pages"]["n_pages"]
    assert s["pages"]["pages_in_use_by_chip"] == \
        [s["pages"]["pages_in_use"]]
    for guard in s["compile_guards"].values():
        assert set(guard) == {"calls", "compiles", "budget"}
    # every histogram summary carries the pinned hist_summary schema
    # (incl. min) — the telemetry exporters index these keys directly
    from replicatinggpt_tpu.utils.logging import Metrics
    assert s["histograms"], "expected at least one histogram"
    for name, h in s["histograms"].items():
        assert set(h) == set(Metrics.HIST_KEYS), name
