"""Quantization subsystem (replicatinggpt_tpu/quant/, ISSUE 15): int8/
fp8 paged KV with quantize-on-write + in-kernel dequant, int8/fp8
weight inference with dequant fused into the matmuls, and the capacity
economics the subsystem exists for.

Acceptance pinned here:
- greedy token parity vs the unquantized engine on short traces, and a
  max-logit-divergence budget (quant.DIVERGENCE_BUDGET) on long
  teacher-forced traces — for int8 KV AND int8 weights;
- pages-per-request HALVED at fixed HBM in the pool-geometry test
  (page count is the admission currency);
- zero recompiles across a quantized replay containing admissions,
  prefix hits, evictions and copy-on-write;
- scales tracked through COW splits / eviction / radix prefix hits
  (the scale arrays ride the pool's page axis), and the pages metrics
  block + Prometheus exposition carrying the quant gauges;
- the engine-shape hash covering the quant knobs (mismatched fleets
  reject at registration) and the CLI forwarding them to workers.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import (decode_step_paged, init_params,
                                           init_paged_kv_pool)
from replicatinggpt_tpu.quant import DIVERGENCE_BUDGET, QuantConfig
from replicatinggpt_tpu.quant.weights import (calibrate, load_calibration,
                                              params_are_quantized,
                                              quantize_params,
                                              save_calibration)
from replicatinggpt_tpu.serve import (Engine, EngineConfig, ReplayConfig,
                                      Request, SamplingParams, run_replay)
from replicatinggpt_tpu.serve.pages import n_pages_for_hbm, page_bytes

CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _greedy(rid, prompt, max_new=6):
    return Request(id=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new,
                   sampling=SamplingParams(greedy=True))


def _short_reqs():
    return [_greedy("q0", [3, 1, 4, 1, 5]), _greedy("q1", [9, 2, 6]),
            _greedy("q2", [7, 7, 7, 7])]


def _streams(params, ecfg, reqs=None):
    eng = Engine(params, CFG, ecfg)
    for r in (reqs or _short_reqs()):
        assert eng.submit(r) is None
    return {r.id: r.tokens for r in eng.drain()}, eng


# ---------------------------------------------------------------------------
# divergence budgets: greedy parity short, logit budget long
# ---------------------------------------------------------------------------

BASE = EngineConfig(pool_size=2, max_queue=8, page_size=8)


@pytest.mark.parametrize("kv,wt", [("int8", "none"), ("fp8", "none"),
                                   ("none", "int8"), ("int8", "int8")])
def test_greedy_parity_short_traces(params, kv, wt):
    """The acceptance's short-trace half: every quantized mode emits
    the exact token streams the unquantized engine does on short
    greedy traces (both layouts' paged programs quantize-on-write and
    dequant-on-gather — parity means the round-trip error stayed
    under every argmax margin on this trace)."""
    want, _ = _streams(params, BASE)
    got, eng = _streams(params, dataclasses.replace(
        BASE, kv_quant=kv, weight_quant=wt))
    assert got == want
    bits = eng.metrics_summary()["pages"]["kv_quant_bits"]
    assert bits == (8 if kv != "none" else 32)


def test_head_granularity_and_heads_layout_parity(params):
    cfg2 = dataclasses.replace(CFG, decode_cache_layout="heads")
    p2 = init_params(jax.random.PRNGKey(0), cfg2)

    def run(ecfg):
        eng = Engine(p2, cfg2, ecfg)
        for r in _short_reqs():
            assert eng.submit(r) is None
        return {r.id: r.tokens for r in eng.drain()}

    want = run(BASE)
    assert run(dataclasses.replace(BASE, kv_quant="int8",
                                   quant_granularity="head")) == want
    assert run(dataclasses.replace(BASE, kv_quant="int8")) == want


def _teacher_forced_divergence(params, qparams, pool_ref, pool_q,
                               cfg, n_steps):
    """Drive both pools through ``n_steps`` paged decode steps on the
    SAME (reference-greedy) token trajectory and return the max
    |Δlogit| — teacher forcing keeps the trajectories aligned so the
    number measures quantization error, not compounding divergence."""
    tables = jnp.asarray(np.arange(8, dtype=np.int32)[None].repeat(1, 0)
                         .reshape(1, 8))
    pos = jnp.asarray(np.array([0], np.int32))
    act = jnp.asarray(np.array([True]))
    tok = jnp.asarray(np.array([3], np.int32))
    worst = 0.0
    for _ in range(n_steps):
        lr, pool_ref = decode_step_paged(params, tok, pos, act, tables,
                                         pool_ref, cfg)
        lq, pool_q = decode_step_paged(qparams, tok, pos, act, tables,
                                       pool_q, cfg)
        worst = max(worst, float(jnp.abs(lr - lq).max()))
        tok = jnp.argmax(lr, axis=-1).astype(jnp.int32)   # teacher force
        pos = pos + 1
    return worst


def test_kv_int8_logit_divergence_budget_long(params):
    """The acceptance's long-trace half for int8 KV: max |Δlogit| over
    a full-buffer teacher-forced decode stays under the pinned
    budget."""
    q = QuantConfig(kv_dtype="int8")
    worst = _teacher_forced_divergence(
        params, params,
        init_paged_kv_pool(CFG, 8, 8),
        init_paged_kv_pool(CFG, 8, 8, quant=q),
        CFG, n_steps=CFG.block_size - 1)
    assert 0.0 < worst < DIVERGENCE_BUDGET["int8"], worst


def test_weight_int8_logit_divergence_budget_long(params):
    """Ditto for int8 weights (unquantized KV on both sides isolates
    the weight error)."""
    qp = quantize_params(params, "int8")
    worst = _teacher_forced_divergence(
        params, qp,
        init_paged_kv_pool(CFG, 8, 8),
        init_paged_kv_pool(CFG, 8, 8),
        CFG, n_steps=CFG.block_size - 1)
    assert 0.0 < worst < DIVERGENCE_BUDGET["int8"], worst


def test_fp8_weight_divergence_budget(params):
    qp = quantize_params(params, "fp8")
    worst = _teacher_forced_divergence(
        params, qp,
        init_paged_kv_pool(CFG, 8, 8),
        init_paged_kv_pool(CFG, 8, 8),
        CFG, n_steps=16)
    assert 0.0 < worst < DIVERGENCE_BUDGET["fp8"], worst


# ---------------------------------------------------------------------------
# pool geometry: pages-per-request halved at fixed HBM
# ---------------------------------------------------------------------------

def test_pages_per_request_halved_at_fixed_hbm():
    """The acceptance's capacity half, as pool geometry: size two
    pools from ONE HBM byte budget — bf16 K/V vs int8+scales — and a
    request's whole-lifetime page reservation is HALF the pool
    fraction on the quantized side (page count is the admission
    currency, so that IS doubled concurrency)."""
    cfg = dataclasses.replace(CFG, n_embd=512, n_head=8,
                              dtype="bfloat16", block_size=256)
    psz = 16
    pb_bf16 = page_bytes(cfg, psz)                  # 2 bytes/elem
    pb_int8 = page_bytes(cfg, psz, "int8")          # 1 byte + scales
    # bytes/page ratio: ~2x minus the per-row scale metadata (8 bytes
    # per token per layer at page granularity vs 1024 row bytes)
    assert 1.9 < pb_bf16 / pb_int8 <= 2.0
    # fixed budget = exactly 2N int8 pages: bf16 fits only N, so a
    # request needing k pages reserves k/N of the bf16 pool but
    # k/(2N) — HALF — of the int8 pool
    N = 64
    hbm = 2 * N * pb_int8
    assert n_pages_for_hbm(hbm, cfg, psz, "int8") == 2 * N
    assert n_pages_for_hbm(hbm, cfg, psz) == N
    # head-granularity scales cost H x the metadata but still land
    # close to the 2x (H=8: 64 bytes vs 1024 row bytes per token)
    assert n_pages_for_hbm(hbm, cfg, psz, "int8", "head") >= int(1.8 * N)


def test_quantized_pool_stats_and_bytes(params):
    eng = Engine(params, CFG, dataclasses.replace(BASE, kv_quant="int8"))
    pg = eng.metrics_summary()["pages"]
    assert pg["kv_quant"] == "int8"
    assert pg["quant_granularity"] == "page"
    assert pg["kv_quant_bits"] == 8
    assert pg["bytes_per_page"] == page_bytes(CFG, pg["page_size"],
                                              "int8")
    assert eng.pool.cache["k"].dtype == jnp.int8
    assert eng.pool.cache["ks"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# zero recompiles + scales through COW / eviction / prefix hits
# ---------------------------------------------------------------------------

def test_quantized_replay_zero_recompiles_with_all_page_events(params):
    """The steady-state acceptance: a quantized replay whose pool
    pressure forces admissions, prefix hits, LRU evictions AND
    copy-on-write splits compiles nothing after warmup — quantize-on-
    write and scale scatters are traced math, never new programs. The
    trace interleaves byte-identical full-page prompts (the
    full-prompt-hit arm, the only path to COW), same-prefix tails
    (partial hits) and disjoint prompts (eviction pressure on a
    6-page pool)."""
    rng = np.random.default_rng(3)
    shared = ((np.arange(16) % 13) + 1).astype(np.int32)  # 2 full pages
    reqs = []
    for i in range(16):
        if i % 4 == 1:
            prompt = shared.copy()                 # full-prompt hit/COW
        elif i % 4 == 2:
            prompt = np.concatenate(
                [shared[:8], rng.integers(1, 60, (4,)).astype(np.int32)])
        else:
            prompt = rng.integers(1, 60, (12,)).astype(np.int32)
        reqs.append(Request(id=f"z{i}", prompt=prompt, max_new_tokens=4,
                            sampling=SamplingParams(greedy=True)))
    trace = [(i * 1e-4, r) for i, r in enumerate(reqs)]
    rcfg = ReplayConfig(n_requests=16, greedy=True)
    ecfg = EngineConfig(pool_size=2, max_queue=32, page_size=8,
                        n_pages=6, kv_quant="int8")
    s = run_replay(params, CFG, rcfg, ecfg, trace=trace)
    assert s["n_completed"] == 16
    assert s["recompiles_after_warmup"] == 0
    pg = s["pages"]
    assert pg["prefix_hits"] > 0
    assert pg["evictions"] > 0
    assert pg["cow_copies"] > 0
    assert pg["kv_quant"] == "int8"


def test_cow_split_carries_scales(params):
    """Scales track COW: a full-prompt prefix hit splits the frontier
    page with a device page copy, and the copy carries the page's
    scale rows (ks/vs share the page axis) — the split page dequants
    to the same K/V the shared original holds."""
    ecfg = dataclasses.replace(BASE, kv_quant="int8")
    eng = Engine(params, CFG, ecfg)
    prompt = ((np.arange(16) % 13) + 1).astype(np.int32)  # 2 full pages
    assert eng.submit(_greedy("w", prompt, max_new=2)) is None
    eng.drain()                                 # registers both pages
    assert eng.submit(_greedy("c", prompt, max_new=2)) is None
    eng.step()                                  # admission + 1st decode
    slot = eng.pool.slot_of("c")
    assert slot is not None
    claim = eng.pool._claims[slot]
    assert claim.cow, "full-prompt hit must have COW-split"
    src, dst = claim.cow[0]
    ks = np.asarray(eng.pool.cache["ks"], np.float32)
    k = np.asarray(eng.pool.cache["k"], np.float32)
    # the first decode write rewrote only position P-1 (row 7 of the
    # dst page); rows 0..6 are the verbatim copy, scales included
    np.testing.assert_array_equal(ks[:, dst, :7], ks[:, src, :7])
    np.testing.assert_array_equal(k[:, dst, :7, :], k[:, src, :7, :])
    assert eng.metrics_summary()["pages"]["cow_copies"] == 1
    eng.drain()


def test_quantized_pool_allocator_fuzz():
    """The 400-op seeded fuzz (tests/test_pages.py's reference-model
    invariants) re-run through a QUANTIZED PagedCachePool's host API:
    the allocator/radix/COW planning must be storage-agnostic, and the
    pool's scale arrays must keep their page axis aligned with the K/V
    arrays through every acquire / prefix hit / COW plan / eviction /
    release — a page id indexes rows AND scales or the device programs
    scatter scales onto the wrong page."""
    from test_pages import _check_allocator

    from replicatinggpt_tpu.serve.pages import PagedCachePool
    rng = np.random.default_rng(42)
    psz = 4
    pool = PagedCachePool(CFG, 8, page_size=psz, n_pages=20,
                          quant=QuantConfig(kv_dtype="int8"))
    # the scale arrays share the physical page axis (axis 1) with the
    # pool arrays for the engine's COW copy + the mesh scale spec
    for name in ("ks", "vs"):
        assert pool.cache[name].shape[:2] == pool.cache["k"].shape[:2]
    seen, live, next_id = [], {}, 0
    for _ in range(400):
        op = rng.choice(["acquire", "advance", "release"],
                        p=[0.45, 0.3, 0.25])
        if op == "acquire":
            if seen and rng.random() < 0.35:
                prompt = seen[int(rng.integers(len(seen)))].copy()
            else:
                P = int(rng.integers(1, 17))
                prompt = rng.integers(0, 3, (P,)).astype(np.int32)
                seen.append(prompt)
            cap = int(rng.integers(1, 9))
            rid = f"f{next_id}"
            adm = pool.acquire(rid, prompt, cap)
            if adm is None:
                continue
            claim = pool._claims[adm.slot]
            # COW plans stay inside the physical pool: scale scatters
            # use the same ids, so an out-of-range dst would corrupt
            for src, dst in adm.cow:
                assert 0 <= src < pool.n_pages
                assert 0 <= dst < pool.n_pages
            pool.commit_admission(adm.slot)
            live[rid] = (claim, int(prompt.size) - 1)
            next_id += 1
        elif op == "advance" and live:
            rid = str(rng.choice(list(live)))
            claim, pos = live[rid]
            pos += int(rng.integers(1, 5))
            slot = pool.slot_of(rid)
            pool.positions[slot] = pos
            pool.flush_pending()
            live[rid] = (claim, pos)
        elif op == "release" and live:
            rid = str(rng.choice(list(live)))
            claim, _ = live.pop(rid)
            pool.release(pool.slot_of(rid))
        _check_allocator(pool.alloc,
                         {i: v for i, v in enumerate(live.values())})
    a = pool.alloc
    assert a.prefix_hits > 0, "fuzz never exercised a prefix hit"
    assert a.evictions > 0, "fuzz never exercised eviction"
    assert a.cow_copies > 0, "fuzz never exercised copy-on-write"


def test_prefix_hit_reuses_quantized_pages_with_parity(params):
    """Radix prefix hits on a quantized pool: the claimer attends the
    registrant's int8 pages + scales and still matches the unquantized
    engine's streams (3 same-prefix requests, prefill only pays the
    tails)."""
    shared = ((np.arange(8) % 11) + 1).astype(np.int32)
    reqs = [
        _greedy(f"p{i}", np.concatenate([shared, np.array([i + 1, i + 2],
                                                          np.int32)]))
        for i in range(3)]
    want, _ = _streams(params, BASE, reqs=[dataclasses.replace(r)
                                           for r in reqs])
    got, eng = _streams(params, dataclasses.replace(BASE,
                                                    kv_quant="int8"),
                        reqs=reqs)
    assert got == want
    assert eng.metrics_summary()["pages"]["prefix_hits"] >= 2


def test_speculative_verify_quantized_parity(params):
    """The speculative verify program scatters its drafted window
    through the quantized pool too (same _scatter_kv discipline):
    greedy streams with an n-gram drafter match the quantized
    plain-decode engine token-for-token on a repetitive trace."""
    from replicatinggpt_tpu.serve.speculative import make_drafter
    reqs = lambda: [_greedy("s0", [5, 6, 5, 6, 5, 6], max_new=8),  # noqa: E731
                    _greedy("s1", [2, 3, 2, 3], max_new=6)]
    ecfg = dataclasses.replace(BASE, kv_quant="int8")
    want, _ = _streams(params, ecfg, reqs=reqs())
    eng = Engine(params, CFG, ecfg,
                 drafter=make_drafter("ngram", 3, 3, ecfg.pool_size))
    for r in reqs():
        assert eng.submit(r) is None
    got = {r.id: r.tokens for r in eng.drain()}
    assert got == want


# ---------------------------------------------------------------------------
# kernel routes (interpret mode): in-kernel dequant parity
# ---------------------------------------------------------------------------

def test_quantized_kernel_routes_greedy_parity(params, monkeypatch):
    """The Pallas route (per-layer paged attention) dequants int8
    pages IN-KERNEL and attends a fake-quantized fresh column — greedy
    streams stay identical to the quantized XLA gather route, which is
    itself parity-pinned against bf16 above."""
    from replicatinggpt_tpu.ops import paged_pallas
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)
    cfg = dataclasses.replace(CFG, n_embd=64, vocab_size=65,
                              decode_cache_layout="packed")
    p64 = init_params(jax.random.PRNGKey(1), cfg)
    reqs = lambda: [_greedy("k0", [3, 1, 4, 1, 5], max_new=6),  # noqa: E731
                    _greedy("k1", [9, 2, 6], max_new=5)]

    def run(ecfg):
        eng = Engine(p64, cfg, ecfg)
        for r in reqs():
            assert eng.submit(r) is None
        return {r.id: r.tokens for r in eng.drain()}, eng

    ecfg = EngineConfig(pool_size=2, max_queue=4, page_size=8,
                        kv_quant="int8")
    want, _ = run(ecfg)
    got, eng = run(dataclasses.replace(ecfg, paged_kernel=True))
    assert eng._use_pallas and eng.kernel_route.decode == "pallas"
    assert got == want


def test_kernel_envelopes_accept_every_quant_mode(params):
    """ISSUE 20 flips the old seams: fp8 pools and head-granularity
    scales dequant INSIDE the unified kernel family now, so the
    envelopes accept every shipped (kv_quant, granularity) cell —
    decided once per engine and exported via kernel_route."""
    from replicatinggpt_tpu.ops.paged_pallas import paged_decode_supported
    for kvq in ("none", "int8", "fp8"):
        for gran in ("page", "head"):
            assert paged_decode_supported(2, 32, 8, 1, kv_quant=kvq,
                                          granularity=gran), (kvq, gran)
    # unknown modes still gate (the reasons vocabulary stays honest)
    assert not paged_decode_supported(2, 32, 8, 1, kv_quant="int4")
    assert not paged_decode_supported(2, 32, 8, 1, granularity="token")


# ---------------------------------------------------------------------------
# weight calibration workflow
# ---------------------------------------------------------------------------

def test_calibration_roundtrip_and_budget(params, tmp_path):
    """The checkpoint-adjacent workflow: calibrate measures a logit
    divergence under the pinned budget, serializes scales + report,
    and a reload quantizes BIT-IDENTICALLY from the stored scales."""
    qp, report = calibrate(params, CFG, "int8")
    assert params_are_quantized(qp)
    assert not params_are_quantized(params)
    assert 0.0 < report["max_logit_div"] < DIVERGENCE_BUDGET["int8"]
    save_calibration(str(tmp_path), qp, report)
    scales, rep2 = load_calibration(str(tmp_path))
    assert rep2["max_logit_div"] == report["max_logit_div"]
    qp2 = quantize_params(params, "int8",
                          scales={k: jnp.asarray(v)
                                  for k, v in scales.items()})
    for name, arr in qp["blocks"].items():
        np.testing.assert_array_equal(np.asarray(arr, np.float32),
                                      np.asarray(qp2["blocks"][name],
                                                 np.float32))
    assert load_calibration(str(tmp_path / "missing")) == (None, None)


def test_load_calibration_tolerates_corrupt_artifact(params, tmp_path):
    """A torn/corrupt quant_scales.npz (crashed writer predating the
    atomic rename) must read as 'no calibration' — the caller then
    recalibrates instead of a fleet worker dying at startup."""
    qp, report = calibrate(params, CFG, "int8")
    npz, _ = save_calibration(str(tmp_path), qp, report)
    with open(npz, "wb") as f:
        f.write(b"\x00not a zip")
    assert load_calibration(str(tmp_path)) == (None, None)


def test_quantize_params_idempotent_and_dtypes(params):
    qp = quantize_params(params, "int8")
    assert qp["blocks"]["qkv_kernel"].dtype == jnp.int8
    assert qp["blocks"]["qkv_kernel_scale"].dtype == jnp.float32
    assert quantize_params(qp, "int8") is qp      # already quantized
    # non-kernel params untouched
    assert qp["blocks"]["ln1_scale"].dtype == \
        params["blocks"]["ln1_scale"].dtype
    assert qp["wte"].dtype == params["wte"].dtype


# ---------------------------------------------------------------------------
# config plumbing: validation, shape hash, CLI forwarding, prometheus
# ---------------------------------------------------------------------------

def test_quant_config_validation():
    QuantConfig().validate()
    QuantConfig(kv_dtype="int8", weight_dtype="fp8",
                granularity="head").validate()
    with pytest.raises(ValueError):
        QuantConfig(kv_dtype="int4").validate()
    with pytest.raises(ValueError):
        QuantConfig(granularity="tensor").validate()
    with pytest.raises(ValueError):
        Engine(None, CFG, EngineConfig(kv_quant="int4"))


def test_shape_hash_covers_quant_knobs():
    """Mismatched quant modes are DIFFERENT engines numerically: the
    registration hash must move with every quant knob so a mixed
    fleet rejects at the handshake, never mid-stream."""
    from replicatinggpt_tpu.serve.rpc import engine_shape_hash
    base = engine_shape_hash(CFG, EngineConfig())
    assert engine_shape_hash(CFG, EngineConfig(kv_quant="int8")) != base
    assert engine_shape_hash(CFG, EngineConfig(weight_quant="int8")) \
        != base
    assert engine_shape_hash(
        CFG, EngineConfig(kv_quant="int8", quant_granularity="head")) \
        != engine_shape_hash(CFG, EngineConfig(kv_quant="int8"))
    assert engine_shape_hash(
        CFG, EngineConfig(weight_quant="int8", act_quant="int8")) \
        != engine_shape_hash(CFG, EngineConfig(weight_quant="int8"))
    assert engine_shape_hash(CFG, EngineConfig()) == base


def test_cli_forwards_quant_flags():
    """serve --multiproc must respawn workers with the quant knobs —
    the ENGINE_FORWARD_FLAGS round trip covers them."""
    import argparse

    from replicatinggpt_tpu.cli import (add_engine_flags,
                                        engine_config_from_args,
                                        engine_forward_args)
    p = argparse.ArgumentParser()
    add_engine_flags(p)
    args = p.parse_args(["--kv-quant", "int8", "--weight-quant", "int8",
                         "--quant-granularity", "head",
                         "--act-quant", "int8"])
    fwd = engine_forward_args(args)
    assert "--kv-quant" in fwd and "int8" in fwd
    args2 = p.parse_args(fwd)
    e1, e2 = (engine_config_from_args(a) for a in (args, args2))
    assert e1 == e2
    assert e1.kv_quant == "int8" and e1.weight_quant == "int8"
    assert e1.quant_granularity == "head"
    assert e1.act_quant == "int8"


def test_prometheus_carries_quant_gauges(params, tmp_path):
    out = tmp_path / "metrics.prom"
    rcfg = ReplayConfig(n_requests=3, rate=5000.0, seed=1,
                        prompt_len_min=4, prompt_len_max=8,
                        max_new_tokens=3, greedy=True)
    run_replay(params, CFG,
               rcfg, dataclasses.replace(BASE, kv_quant="int8"),
               metrics_out=str(out))
    text = out.read_text()
    assert "bytes_per_page" in text
    assert "kv_quant_bits 8" in text
