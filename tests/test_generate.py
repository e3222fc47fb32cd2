"""Generation tests: greedy KV-cache decode must match a naive
re-encode-everything rollout (the reference's algorithm, GPT1.py:196-212);
sampling modes; long generation via window refresh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import forward, init_params
from replicatinggpt_tpu.sample import GenerateConfig, generate

CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _naive_greedy(params, prompt, n_new):
    """Reference-style rollout: full forward over the (cropped) window per
    token, argmax of the last position (GPT1.py:200-208 with argmax)."""
    idx = np.asarray(prompt)
    out = []
    for _ in range(n_new):
        window = idx[:, -CFG.block_size:]
        logits, _ = forward(params, jnp.asarray(window), CFG)
        nxt = np.argmax(np.asarray(logits[:, -1, :]), axis=-1)[:, None]
        idx = np.concatenate([idx, nxt], axis=1)
        out.append(nxt)
    return np.concatenate(out, axis=1).astype(np.int32)


@pytest.mark.slow
def test_greedy_matches_naive_rollout(params):
    prompt = np.array([[1, 5, 9], [3, 3, 3]], dtype=np.int32)
    n_new = 12  # stays within block_size
    got = np.asarray(generate(params, prompt, CFG,
                              GenerateConfig(max_new_tokens=n_new,
                                             greedy=True)))
    want = _naive_greedy(params, prompt, n_new)
    np.testing.assert_array_equal(got, want)


def test_zero_context_start(params):
    """The reference's 500-from-zero workload shape (GPT1.py:235-236)."""
    prompt = np.zeros((1, 1), dtype=np.int32)
    toks = generate(params, prompt, CFG,
                    GenerateConfig(max_new_tokens=10))
    assert toks.shape == (1, 10)
    assert int(toks.min()) >= 0 and int(toks.max()) < CFG.vocab_size


def test_sampling_deterministic_given_rng(params):
    prompt = np.array([[1, 2]], dtype=np.int32)
    g = GenerateConfig(max_new_tokens=8, temperature=0.8, top_k=10)
    a = generate(params, prompt, CFG, g, rng=jax.random.PRNGKey(7))
    b = generate(params, prompt, CFG, g, rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = generate(params, prompt, CFG, g, rng=jax.random.PRNGKey(8))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_top_k_restricts_support(params):
    """With top_k=1, sampling degenerates to greedy."""
    prompt = np.array([[4, 7, 2]], dtype=np.int32)
    greedy = generate(params, prompt, CFG,
                      GenerateConfig(max_new_tokens=6, greedy=True))
    k1 = generate(params, prompt, CFG,
                  GenerateConfig(max_new_tokens=6, top_k=1),
                  rng=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))


def test_long_generation_window_refresh(params):
    """Generate 3x block_size tokens — exercises the half-window refresh
    path that replaces the reference's per-token crop (GPT1.py:200)."""
    prompt = np.zeros((2, 1), dtype=np.int32)
    n = CFG.block_size * 3
    toks = generate(params, prompt, CFG, GenerateConfig(max_new_tokens=n))
    assert toks.shape == (2, n)
    assert int(toks.max()) < CFG.vocab_size
    # trained-free model should still produce varied tokens, not a constant
    assert len(np.unique(np.asarray(toks))) > 3


def test_temperature_extremes(params):
    prompt = np.array([[1]], dtype=np.int32)
    cold = generate(params, prompt, CFG,
                    GenerateConfig(max_new_tokens=6, temperature=1e-4),
                    rng=jax.random.PRNGKey(0))
    greedy = generate(params, prompt, CFG,
                      GenerateConfig(max_new_tokens=6, greedy=True))
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(greedy))


@pytest.mark.slow
def test_sharded_decode_matches_single_device(params):
    """TP-sharded decoding (shard_for_decode + the unchanged generate)
    must produce the same greedy tokens as the single-device path: the
    Megatron TP specs shard qkv heads and the vocab dims, GSPMD inserts
    the psum/gather collectives, and the result is numerically the same
    computation."""
    import dataclasses

    from replicatinggpt_tpu.config import MeshConfig
    from replicatinggpt_tpu.parallel.mesh import make_mesh
    from replicatinggpt_tpu.sample import shard_for_decode

    # vocab 64 divides the model axis, so wte/lm_head really shard over
    # 'model' and the gather-at-sampling step is exercised (vocab 65
    # would silently drop the vocab-parallel specs via the divisibility
    # fallback in parallel.mesh._leaf_spec)
    cfg = dataclasses.replace(CFG, vocab_size=64)
    vparams = init_params(jax.random.PRNGKey(1), cfg)
    prompt = jnp.asarray([[1, 5, 9], [3, 3, 3]], jnp.int32)
    gcfg = GenerateConfig(max_new_tokens=12, greedy=True)
    want = generate(vparams, prompt, cfg, gcfg)

    mesh_cfg = MeshConfig(data=2, model=2)
    mesh = make_mesh(mesh_cfg)
    sp, sprompt = shard_for_decode(vparams, prompt, cfg, mesh, mesh_cfg)
    from jax.sharding import PartitionSpec as P
    assert sp["wte"].sharding.spec == P("model", None), sp["wte"].sharding
    got = generate(sp, sprompt, cfg, gcfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # window refresh (long generation) under sharding
    gcfg_long = GenerateConfig(max_new_tokens=2 * cfg.block_size,
                               greedy=True)
    long_want = generate(vparams, prompt, cfg, gcfg_long)
    long_got = generate(sp, sprompt, cfg, gcfg_long)
    np.testing.assert_array_equal(np.asarray(long_got),
                                  np.asarray(long_want))


def test_generate_compile_stability(params):
    """A long sample must cost a fixed small set of compiled segment
    shapes (bucketed prompt pad + fixed refresh shape), and repeat runs
    with different lengths/prompts within the same buckets must add NO new
    compiles — the recompile-per-segment failure mode stays dead."""
    cfg = CFG
    from replicatinggpt_tpu.sample import generate
    from replicatinggpt_tpu.sample.generate import _decode_segment

    _decode_segment.clear_cache()
    gcfg = GenerateConfig(max_new_tokens=3 * cfg.block_size, top_k=10)
    out = generate(params, jnp.zeros((1, 1), jnp.int32), cfg, gcfg,
                   rng=jax.random.PRNGKey(0))
    assert out.shape == (1, 3 * cfg.block_size)
    n_first = _decode_segment._cache_size()
    assert n_first <= 2, n_first
    # same buckets, different length/rng: zero fresh compiles
    gcfg2 = GenerateConfig(max_new_tokens=3 * cfg.block_size - 17, top_k=10)
    generate(params, jnp.zeros((1, 1), jnp.int32), cfg, gcfg2,
             rng=jax.random.PRNGKey(1))
    assert _decode_segment._cache_size() == n_first


def test_top_p_filter_keeps_nucleus_only():
    """The nucleus filter keeps exactly the smallest descending-probability
    prefix reaching mass p (always >= 1 token), masks the rest to -inf."""
    import jax.numpy as jnp
    from replicatinggpt_tpu.sample.generate import _top_p_filter

    # probs ~ [0.6, 0.3, 0.08, 0.02] after softmax
    logits = jnp.log(jnp.asarray([[0.6, 0.3, 0.08, 0.02]], jnp.float32))
    out = _top_p_filter(logits, 0.5)      # 0.6 alone reaches 0.5
    assert jnp.isfinite(out[0, 0]) and not jnp.any(jnp.isfinite(out[0, 1:]))
    out = _top_p_filter(logits, 0.85)     # needs 0.6 + 0.3
    assert bool(jnp.all(jnp.isfinite(out[0, :2])))
    assert not jnp.any(jnp.isfinite(out[0, 2:]))
    out = _top_p_filter(logits, 1.0)      # keeps everything
    assert bool(jnp.all(jnp.isfinite(out)))
    # extreme p always keeps the argmax
    out = _top_p_filter(logits, 1e-9)
    assert jnp.isfinite(out[0, 0]) and not jnp.any(jnp.isfinite(out[0, 1:]))
    # boundary ties cannot widen the nucleus (rank-based, not
    # value-thresholded): fully tied row at p=0.25 keeps exactly one
    tied = jnp.zeros((1, 4), jnp.float32)
    out = _top_p_filter(tied, 0.25)
    assert int(jnp.sum(jnp.isfinite(out))) == 1


def test_sample_token_top_p_never_draws_masked_tail():
    """_sample_token with top_p draws only nucleus members: over many
    draws from a known distribution, the masked tail never appears (this
    pins the guard wiring, not just the filter math)."""
    import jax
    import jax.numpy as jnp
    from replicatinggpt_tpu.sample.generate import (GenerateConfig,
                                                    _sample_token)

    logits = jnp.log(jnp.asarray([[0.6, 0.3, 0.08, 0.02]], jnp.float32))
    batched = jnp.broadcast_to(logits, (500, 4))
    draws = _sample_token(jax.random.PRNGKey(0), batched,
                          GenerateConfig(top_p=0.5))
    assert bool(jnp.all(draws == 0))              # nucleus = {0}
    draws = _sample_token(jax.random.PRNGKey(1), batched,
                          GenerateConfig(top_p=0.85))
    assert bool(jnp.all(draws <= 1))              # nucleus = {0, 1}
    assert bool(jnp.any(draws == 1))              # and it still samples


def test_generate_top_p_end_to_end():
    """End-to-end: top-p generation produces valid tokens and greedy
    decoding ignores top_p (nucleus membership itself is pinned by
    test_sample_token_top_p_never_draws_masked_tail)."""
    import jax
    import jax.numpy as jnp
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.sample import GenerateConfig, generate
    from replicatinggpt_tpu.train.state import create_train_state

    cfg = get_config("test-tiny")
    m = cfg.model
    state = create_train_state(jax.random.PRNGKey(0), m, cfg.train)
    toks = generate(state.params, jnp.zeros((1, 1), jnp.int32), m,
                    GenerateConfig(max_new_tokens=24, top_p=0.9),
                    rng=jax.random.PRNGKey(1))
    assert toks.shape == (1, 24)
    assert bool(jnp.all((toks >= 0) & (toks < m.vocab_size)))
    # greedy unaffected by top_p
    g1 = generate(state.params, jnp.zeros((1, 1), jnp.int32), m,
                  GenerateConfig(max_new_tokens=8, greedy=True, top_p=0.5),
                  rng=jax.random.PRNGKey(2))
    g2 = generate(state.params, jnp.zeros((1, 1), jnp.int32), m,
                  GenerateConfig(max_new_tokens=8, greedy=True),
                  rng=jax.random.PRNGKey(3))
    assert bool(jnp.all(g1 == g2))


def test_top_k_filter_radix_matches_sort():
    """The radix-select top-k filter must be bit-identical to the
    lax.top_k formulation (same kept set, same tie semantics) — across
    random rows, heavy ties, -inf entries, and the k=1 / k=V edges."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from replicatinggpt_tpu.sample.generate import _top_k_filter

    def ref_filter(logits, k):
        kth = jax.lax.top_k(logits, k)[0][:, -1:]
        return jnp.where(logits < kth, -jnp.inf, logits)

    rng = np.random.default_rng(0)
    V = 1031  # not a multiple of anything convenient
    cases = []
    cases.append(rng.normal(size=(3, V)).astype(np.float32))
    tied = rng.normal(size=(2, V)).astype(np.float32)
    tied[:, : V // 2] = tied[:, :1]            # half the row ties at one value
    cases.append(tied)
    winf = rng.normal(size=(2, V)).astype(np.float32)
    winf[:, ::3] = -np.inf                     # -inf entries survive bitspace
    cases.append(winf)
    cases.append(np.full((1, V), 2.5, np.float32))   # fully tied row
    neg = -np.abs(rng.normal(size=(2, V))).astype(np.float32)  # all negative
    cases.append(neg)
    for x in cases:
        xj = jnp.asarray(x)
        for k in (1, 7, 50, V):
            got = np.asarray(_top_k_filter(xj, k))
            want = np.asarray(ref_filter(xj, k))
            np.testing.assert_array_equal(got, want)


def test_kth_largest_exact_values():
    import jax.numpy as jnp
    import numpy as np
    from replicatinggpt_tpu.sample.generate import _kth_largest

    x = jnp.asarray([[5.0, -1.0, 3.0, 3.0, 0.0, -jnp.inf],
                     [0.5, 0.25, 0.125, -0.5, -0.25, -0.125]], jnp.float32)
    np.testing.assert_array_equal(np.asarray(_kth_largest(x, 1)),
                                  np.asarray([5.0, 0.5], np.float32))
    np.testing.assert_array_equal(np.asarray(_kth_largest(x, 3)),
                                  np.asarray([3.0, 0.125], np.float32))
    np.testing.assert_array_equal(np.asarray(_kth_largest(x, 6)),
                                  np.asarray([-np.inf, -0.5], np.float32))


def test_prefill_matches_sequential_decode():
    """The parallel prefill must build the same KV cache (and leave the
    decode continuation identical) as teacher-forcing the prompt through
    sequential decode_steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.models.gpt import (decode_step, init_kv_cache,
                                               prefill)
    from replicatinggpt_tpu.train.state import create_train_state

    cfg = get_config("test-tiny").model
    state = create_train_state(jax.random.PRNGKey(0), cfg,
                               get_config("test-tiny").train)
    B, P = 2, 12
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                cfg.vocab_size)
    cache_p = prefill(state.params, prompt, init_kv_cache(cfg, B), cfg)
    cache_s = init_kv_cache(cfg, B)
    for pos in range(P):
        logits_s, cache_s = decode_step(state.params, prompt[:, pos],
                                        jnp.int32(pos), cache_s, cfg)
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_p[key][:, :, :, :P], np.float32),
            np.asarray(cache_s[key][:, :, :, :P], np.float32),
            atol=2e-5, rtol=2e-5)
    # continuations agree: next decode step from either cache matches
    nxt = jnp.argmax(logits_s, -1).astype(jnp.int32)
    lp, _ = decode_step(state.params, nxt, jnp.int32(P), cache_p, cfg)
    ls, _ = decode_step(state.params, nxt, jnp.int32(P), cache_s, cfg)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ls), atol=2e-5,
                               rtol=2e-5)


def test_refresh_group_matches_sequential_segments():
    """One _refresh_group(n_seg=2) dispatch must produce exactly the
    tokens of two sequential _decode_segment calls (same ordinal-keyed
    rngs, same window sliding) — non-greedy, so rng threading is
    covered too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.sample.generate import (GenerateConfig,
                                                    _decode_segment,
                                                    _refresh_group)
    from replicatinggpt_tpu.train.state import create_train_state

    cfg = get_config("test-tiny")
    m = cfg.model
    state = create_train_state(jax.random.PRNGKey(0), m, cfg.train)
    gcfg = GenerateConfig(max_new_tokens=0, top_k=5)
    S = m.block_size
    Pw, n_mid = S // 2, S // 2 + 1
    B = 2
    window = jax.random.randint(jax.random.PRNGKey(3), (B, Pw), 0,
                                m.vocab_size)
    base = jax.random.PRNGKey(11)

    grouped, gw = _refresh_group(state.params, window, 2, jnp.int32(0),
                                 base, m, gcfg)

    seq_chunks = []
    w = window
    for ordinal in range(2):
        sub = jax.random.fold_in(base, ordinal)
        toks = _decode_segment(state.params, w, Pw, n_mid, sub, m, gcfg)
        seq_chunks.append(toks)
        w = jnp.concatenate([w, toks], axis=1)[:, -Pw:]
    sequential = jnp.concatenate(seq_chunks, axis=1)

    np.testing.assert_array_equal(np.asarray(grouped),
                                  np.asarray(sequential))
    np.testing.assert_array_equal(np.asarray(gw), np.asarray(w))


def test_decode_chunks_cover_exactly():
    """_decode_chunks partitions [0, n_new) with attend_len always a
    valid bound for every position its chunk writes (pos <= P_pad-1+i
    < attend_len) and never exceeding S."""
    from replicatinggpt_tpu.sample.generate import _decode_chunks
    GRANULE = 128
    for P_pad, n_new, S in [(1, 1024, 1024), (512, 513, 1024),
                            (1, 1, 32), (32, 1, 32), (7, 250, 256),
                            (128, 897, 1024)]:
        chunks = _decode_chunks(P_pad, n_new, S, GRANULE)
        i = 0
        for n_c, a in chunks:
            assert n_c >= 1 and a <= S
            assert a % GRANULE == 0 or a == S
            last_pos = P_pad - 1 + i + n_c - 1
            assert last_pos < a, (P_pad, n_new, S, chunks)
            i += n_c
        assert i == n_new
        assert P_pad - 1 + n_new - 1 <= S - 1


@pytest.mark.slow
def test_chunked_segment_matches_monolithic():
    """The chunked-attend decode scan must produce the bit-identical
    sampled trajectory of a single full-S scan (the rng-split sequence
    per step is unchanged; the cache prefix slice only drops slots the
    mask already zeroed). attend_granule is a GenerateConfig field —
    part of the static jit key — so the two arms compile separately
    with no cache clearing."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = np.array([[1, 5, 9], [3, 3, 3]], dtype=np.int32)
    rng = jax.random.PRNGKey(42)
    # granule S = one chunk at full attend width (the old monolithic scan)
    mono_cfg = GenerateConfig(max_new_tokens=60, temperature=0.9, top_k=8,
                              attend_granule=CFG.block_size)
    mono = np.asarray(generate(params, prompt, CFG, mono_cfg, rng=rng))
    # granule 8 engages real chunking at block_size=32
    chunk_cfg = GenerateConfig(max_new_tokens=60, temperature=0.9, top_k=8,
                               attend_granule=8)
    chunked = np.asarray(generate(params, prompt, CFG, chunk_cfg, rng=rng))
    np.testing.assert_array_equal(mono, chunked)


@pytest.mark.slow
def test_decode_step_short_cache_parity():
    """decode_step on a shorter cache buffer (init_kv_cache max_len)
    returns the same logits and cache writes as the full bucket while
    pos stays inside it — the invariant the chunked grow-as-you-go
    decode relies on."""
    from replicatinggpt_tpu.models.gpt import decode_step, init_kv_cache
    params = init_params(jax.random.PRNGKey(0), CFG)
    B = 2
    rng = jax.random.PRNGKey(5)
    cache_a = init_kv_cache(CFG, B)                  # full block_size=32
    cache_b = init_kv_cache(CFG, B, max_len=16)      # short buffer
    toks = jax.random.randint(rng, (B, 10), 0, CFG.vocab_size)
    for pos in range(10):
        la, cache_a = decode_step(params, toks[:, pos], jnp.int32(pos),
                                  cache_a, CFG)
        lb, cache_b = decode_step(params, toks[:, pos], jnp.int32(pos),
                                  cache_b, CFG)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for key in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(cache_a[key][:, :, :, :16]),
            np.asarray(cache_b[key]))


PACKED_CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                         n_embd=64, dropout=0.0, attn_dropout=0.0,
                         dtype="float32")  # D=32: packed-kernel envelope


def test_packed_cache_layout_trajectory_matches_heads():
    """The (L,B,S,C) packed cache layout must sample the bit-identical
    trajectory of the (L,B,H,S,D) heads layout through the XLA fallback
    path (same math, different carry layout)."""
    import dataclasses
    params = init_params(jax.random.PRNGKey(0), PACKED_CFG)
    prompt = np.array([[1, 5, 9], [3, 3, 3]], np.int32)
    gcfg = GenerateConfig(max_new_tokens=50, temperature=0.9, top_k=8)
    rng = jax.random.PRNGKey(42)
    heads = np.asarray(generate(params, prompt, PACKED_CFG, gcfg, rng=rng))
    pc = dataclasses.replace(PACKED_CFG, decode_cache_layout="packed")
    packed = np.asarray(generate(params, prompt, pc, gcfg, rng=rng))
    np.testing.assert_array_equal(heads, packed)


def test_packed_decode_kernel_engages_and_matches(monkeypatch):
    """With the backend gate open, the packed decode-attention Pallas
    kernel (interpret mode on CPU) must be routed AND reproduce the
    heads-layout trajectory."""
    import dataclasses

    import replicatinggpt_tpu.ops.decode_pallas as dp
    params = init_params(jax.random.PRNGKey(0), PACKED_CFG)
    prompt = np.array([[1, 5, 9], [3, 3, 3]], np.int32)
    gcfg = GenerateConfig(max_new_tokens=50, temperature=0.9, top_k=8)
    rng = jax.random.PRNGKey(42)
    heads = np.asarray(generate(params, prompt, PACKED_CFG, gcfg, rng=rng))

    calls = []
    orig = dp.packed_decode_attention

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(dp, "_packed_attn_backend_ok", lambda: True)
    monkeypatch.setattr(dp, "packed_decode_attention", spy)
    # the backend gate is read at trace time and is NOT part of the jit
    # key (it cannot change in production processes) — drop programs an
    # earlier gate-closed test may have compiled for this same config,
    # and drop the gate-open programs afterwards. (importlib: the package
    # re-exports the `generate` function under the submodule's name)
    import importlib
    G = importlib.import_module("replicatinggpt_tpu.sample.generate")
    G._decode_segment.clear_cache()
    G._refresh_group.clear_cache()
    try:
        pc = dataclasses.replace(PACKED_CFG, decode_cache_layout="packed")
        got = np.asarray(generate(params, prompt, pc, gcfg, rng=rng))
    finally:
        G._decode_segment.clear_cache()
        G._refresh_group.clear_cache()
    assert calls, "packed decode kernel was not routed"
    np.testing.assert_array_equal(heads, got)


def test_packed_layout_chunked_growth_matches_monolithic():
    """Chunked cache growth (attend_granule < S) under the packed layout
    — the grow axis differs from the heads layout (cache_seq_axis) and
    must still produce the monolithic trajectory."""
    import dataclasses
    pc = dataclasses.replace(PACKED_CFG, decode_cache_layout="packed")
    params = init_params(jax.random.PRNGKey(0), pc)
    prompt = np.array([[2, 4], [7, 1]], np.int32)
    rng = jax.random.PRNGKey(9)
    mono = np.asarray(generate(
        params, prompt, pc,
        GenerateConfig(max_new_tokens=60, top_k=5,
                       attend_granule=pc.block_size), rng=rng))
    chunked = np.asarray(generate(
        params, prompt, pc,
        GenerateConfig(max_new_tokens=60, top_k=5, attend_granule=8),
        rng=rng))
    np.testing.assert_array_equal(mono, chunked)


def test_packed_decode_attention_kernel_unit():
    """Direct kernel-vs-reference parity on random inputs: the packed
    kernel's per-head lane-slice math against a plain split-heads
    softmax attention with write-then-attend semantics."""
    from replicatinggpt_tpu.ops.attention import cached_attention
    from replicatinggpt_tpu.ops.decode_pallas import packed_decode_attention
    B, S, H, D = 3, 16, 4, 32
    C = H * D
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, C)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((B, C)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, C)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, C)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, C)), jnp.float32)
    for pos in (0, 5, S - 1):
        got = packed_decode_attention(q, k_new, v_new, kc, vc,
                                      jnp.int32(pos), n_head=H)
        # reference: write fresh k/v at pos, then attend <= pos
        kc2 = kc.at[:, pos, :].set(k_new)
        vc2 = vc.at[:, pos, :].set(v_new)

        def heads(x):
            return x.reshape(B, -1, H, D).transpose(0, 2, 1, 3)

        ref = cached_attention(heads(q[:, None, :]), heads(kc2),
                               heads(vc2), jnp.int32(pos))
        ref = ref.transpose(0, 2, 1, 3).reshape(B, C)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
