"""Sharded serving (ISSUE 12): the async engine on a (data, model)
mesh. Acceptance: greedy streams token-identical between a
single-device engine and a forced-multi-device-CPU 2x2 mesh engine
through a trace containing prefix hits, COW splits, LRU eviction and a
mid-window admission; compile_counts flat after warmup with
recompiles_after_warmup == 0 on the mesh path; the sampled token block
leaves the device fully replicated (the host fetch is a local read);
the pages block reports per-chip and aggregate utilization; the
multiproc engine-flag forwarding round-trips the mesh slice; and the
graftlint mesh rules (GL010-14) run clean over the sharded serve path.

Mesh tests skip below 4 devices so tier-1 stays green on one device
(tests/conftest.py forces 8 CPU devices, so they RUN in tier-1)."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import init_params
from replicatinggpt_tpu.sample import GenerateConfig, generate
from replicatinggpt_tpu.serve import (Engine, EngineConfig, Request,
                                      SamplingParams, compile_counts)

CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0,
                  dtype="float32")

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (JAX_PLATFORMS=cpu with XLA_FLAGS="
           "--xla_force_host_platform_device_count=4; tests/conftest.py "
           "forces 8, so tier-1 runs these)")

#: the acceptance mesh: pages sharded 2-way over 'data', TP 2-way over
#: 'model' (n_head=2, n_embd=32 both divide)
MESH = dict(mesh_data=2, mesh_model=2)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _greedy(rid, prompt, max_new=4, eos=None):
    return Request(id=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new,
                   sampling=SamplingParams(greedy=True),
                   eos_token_id=eos)


def _offline_greedy(params, reqs, cfg=CFG):
    return {r.id: np.asarray(generate(
        params, r.prompt[None, :], cfg,
        GenerateConfig(max_new_tokens=min(
            r.max_new_tokens, cfg.block_size - int(r.prompt.size) + 1),
            greedy=True)))[0].tolist() for r in reqs}


def _pressure_trace(n=10, max_new=4):
    """The test_pages eviction trace shape: a shared page-aligned
    prompt every third request (prefix hit + full-prompt COW) among
    random prompts that overrun a 6-page pool (LRU evictions)."""
    rng = np.random.default_rng(1)
    shared = ((np.arange(16) % 9) + 2).astype(np.int32)
    reqs = []
    for i in range(n):
        if i % 3 == 0:
            prompt = shared.copy()
        else:
            prompt = rng.integers(0, CFG.vocab_size, (int(
                rng.integers(3, 20)),)).astype(np.int32)
        reqs.append(_greedy(f"e{i}", prompt, max_new=max_new))
    return shared, reqs


def _run(params, ecfg, reqs):
    eng = Engine(params, CFG, ecfg)
    for r in reqs:
        assert eng.submit(r) is None, r.id
    return eng, {r.id: r.tokens for r in eng.drain()}


# ---------------------------------------------------------------------------
# acceptance: greedy parity 1x1 vs 2x2 through prefix/COW/eviction,
# zero recompiles in mesh steady state
# ---------------------------------------------------------------------------

@needs4
def test_mesh_greedy_parity_prefix_cow_eviction(params):
    """The ISSUE 12 acceptance bar: the SAME trace (prefix hits, COW
    splits, evictions under a 6-page pool) through a single-device and
    a 2x2-mesh engine produces byte-identical greedy streams — and the
    mesh engine matches offline generate() too (sharding changed the
    layout, not the math)."""
    shared, reqs = _pressure_trace()
    base = EngineConfig(pool_size=2, max_queue=64, page_size=8,
                        n_pages=6)
    want = _offline_greedy(params, reqs)
    e1, got1 = _run(params, base, [dataclasses.replace(r) for r in reqs])
    e2, got2 = _run(params, dataclasses.replace(base, **MESH),
                    [dataclasses.replace(r) for r in reqs])
    assert got1 == got2
    assert got2 == want
    pg = e2.metrics_summary()["pages"]
    assert pg["evictions"] > 0 and pg["cow_copies"] > 0
    assert pg["prefix_hit_tokens"] > 0
    # the mesh engine's host bookkeeping is untouched by sharding
    assert e2.pool.alloc.ref.max() == 0
    assert e2.mesh is not None and e2.mesh.size == 4


@needs4
def test_mesh_zero_recompiles_at_steady_state(params):
    """compile_counts stays pinned flat across a SECOND mesh replay
    containing admissions + hits + evictions + COW — the zero-recompile
    steady state survives sharding (every program keys on the engine's
    static ServeShardings, so the sharded variants compiled once at
    warmup are the only ones that ever exist)."""
    _, reqs = _pressure_trace()
    ecfg = EngineConfig(pool_size=2, max_queue=64, page_size=8,
                        n_pages=6, decode_window=4, **MESH)
    eng, _ = _run(params, ecfg, reqs)          # warmup: compiles happen
    base = compile_counts()
    _, reqs2 = _pressure_trace()
    for r in reqs2:
        assert eng.submit(_greedy("x" + r.id, r.prompt,
                                  r.max_new_tokens)) is None
    eng.drain()
    assert compile_counts() == base
    for name, g in eng.metrics_summary()["compile_guards"].items():
        assert g["compiles"] <= g["budget"], (name, g)


@needs4
def test_mesh_mid_window_admission_parity(params):
    """A request arriving while a 4-step window is in flight on the
    mesh: the window drains at the boundary, the admission runs the k=1
    fallback, and both streams stay identical to the 1x1 engine's."""
    rng = np.random.default_rng(7)
    reqs = [_greedy(f"r{i}", rng.integers(0, CFG.vocab_size, (int(
        rng.integers(2, 15)),)).astype(np.int32), max_new=20)
        for i in range(3)]

    def run(ecfg):
        eng = Engine(params, CFG, ecfg)
        assert eng.submit(dataclasses.replace(reqs[0])) is None
        out = []
        out.extend(eng.step())                 # admission (blocked k=1)
        out.extend(eng.step())                 # window launched
        assert eng._inflight is not None, "window should be in flight"
        assert eng.submit(dataclasses.replace(reqs[1])) is None
        assert eng.submit(dataclasses.replace(reqs[2])) is None
        out.extend(eng.drain())
        return {r.id: r.tokens for r in out}

    base = EngineConfig(pool_size=2, max_queue=8, decode_window=4)
    assert run(base) == run(dataclasses.replace(base, **MESH))


@needs4
def test_mesh_spec_verify_parity(params):
    """Speculative decoding on the mesh: the paged verify program runs
    TP-sharded (drafter stays single-device host-side) and greedy
    streams match both the 1x1 spec engine and the plain mesh engine."""
    from replicatinggpt_tpu.serve.speculative import make_drafter
    pat = (np.arange(3) % CFG.vocab_size).astype(np.int32) + 3
    reqs = [_greedy(f"s{i}", np.tile(pat, 4 + i)[:12 + i], max_new=6)
            for i in range(3)]
    base = EngineConfig(pool_size=2, max_queue=8, page_size=8)

    def run(ecfg, spec):
        dr = make_drafter("ngram" if spec else "off", 3, 3,
                          ecfg.pool_size, None, None, 0)
        eng = Engine(params, CFG, ecfg, drafter=dr)
        for r in reqs:
            assert eng.submit(dataclasses.replace(r)) is None
        out = {r.id: r.tokens for r in eng.drain()}
        return eng, out

    _, spec1 = run(base, True)
    eng, spec2 = run(dataclasses.replace(base, **MESH), True)
    _, plain = run(dataclasses.replace(base, **MESH), False)
    assert spec1 == spec2 == plain
    g = eng.metrics_summary()["compile_guards"]["verify"]
    assert g["compiles"] <= g["budget"]


# ---------------------------------------------------------------------------
# sharding mechanics: replicated token block, pinned pool layout
# ---------------------------------------------------------------------------

@needs4
def test_mesh_token_block_replicated_and_pool_pinned(params):
    """The async fetch contract under sharding: the in-flight window's
    (k, n_slots) token block is FULLY REPLICATED (np.asarray reads a
    local shard — no cross-device gather on the host path), and the
    page pool's committed sharding survives every dispatch exactly
    (donation aliased, no GSPMD drift between windows)."""
    ecfg = EngineConfig(pool_size=2, max_queue=8, page_size=8,
                        decode_window=4, **MESH)
    eng = Engine(params, CFG, ecfg)
    pool_sharding = eng.pool.cache["k"].sharding
    assert pool_sharding == eng._plan.cache
    spec = eng._plan.cache.spec
    assert spec[1] == "data", spec             # page axis over 'data'
    assert "model" in spec, spec               # model dim over 'model'
    assert eng.submit(_greedy("a", np.arange(1, 10), max_new=16)) is None
    eng.step()                                 # admission
    eng.step()                                 # steady state: window up
    assert eng._inflight is not None
    assert eng._inflight.toks.sharding.is_fully_replicated
    assert eng._inflight.emitted.sharding.is_fully_replicated
    eng.drain()
    assert eng.pool.cache["k"].sharding == pool_sharding
    assert eng.pool.cache["v"].sharding == pool_sharding


@needs4
def test_mesh_pages_per_chip_and_aggregate_stats(params):
    """metrics_summary()['pages'] on a mesh: aggregate_pages stays the
    admission currency, pages_per_chip is the per-device HBM share of
    it, and the by-chip occupancy splits the in-use count exactly."""
    ecfg = EngineConfig(pool_size=2, max_queue=8, page_size=8,
                        n_pages=8, **MESH)
    eng = Engine(params, CFG, ecfg)
    assert eng.submit(_greedy("a", np.arange(1, 17), max_new=4)) is None
    eng.step()
    pg = eng.metrics_summary()["pages"]
    assert pg["mesh_shape"] == [2, 2]
    assert pg["aggregate_pages"] == 8 and pg["pages_per_chip"] == 4
    assert len(pg["pages_in_use_by_chip"]) == 2
    assert sum(pg["pages_in_use_by_chip"]) == pg["pages_in_use"]
    assert len(pg["page_utilization_by_chip"]) == 2
    eng.drain()


def test_page_pool_pspec_layouts_and_divisibility():
    """The design-first layout (parallel.mesh): packed pools shard C
    over 'model', heads pools shard H; the page axis shards over
    'data'; non-divisible dims drop their axis (never pad-shard); and
    trailing Nones are trimmed to the jit-normalized representation
    (the representation IS the jit cache key)."""
    from replicatinggpt_tpu.parallel.mesh import page_pool_pspec
    heads = CFG
    packed = dataclasses.replace(CFG, decode_cache_layout="packed")
    assert page_pool_pspec(heads, 8, 2, 2) == P(None, "data", "model")
    assert page_pool_pspec(packed, 8, 2, 2) == \
        P(None, "data", None, "model")
    # 7 pages on data=2: page axis drops to replication
    assert page_pool_pspec(heads, 7, 2, 2) == P(None, None, "model")
    # n_head=2 on model=4: TP axis drops (heads layout)
    assert page_pool_pspec(heads, 8, 2, 4) == P(None, "data")
    # fully non-divisible -> fully replicated, trimmed to P()
    assert page_pool_pspec(heads, 7, 2, 4) == P()


# ---------------------------------------------------------------------------
# satellites: multiproc forwarding round-trip, graftlint mesh rules
# ---------------------------------------------------------------------------

def test_engine_forward_args_round_trips_mesh_shape():
    """`serve --multiproc` must spawn workers owning the SAME engine
    shape — mesh slice included: every add_engine_flags knob set on the
    parent survives engine_forward_args -> a fresh serve-worker-style
    parser -> engine_config_from_args (the PR 9 model-override
    round-trip, applied to the engine flags)."""
    import argparse

    from replicatinggpt_tpu.cli import (add_engine_flags,
                                        engine_config_from_args,
                                        engine_forward_args)

    def parse(argv):
        p = argparse.ArgumentParser()
        add_engine_flags(p)
        return p.parse_args(argv)

    argv = ["--pool-size", "4", "--max-queue", "32", "--prefill-chunk",
            "16", "--page-size", "8", "--n-pages", "24",
            "--decode-window", "4", "--mesh-shape", "2x2",
            "--no-prefix-cache"]
    parent = parse(argv)
    forwarded = parse(engine_forward_args(parent))
    assert engine_config_from_args(forwarded) == \
        engine_config_from_args(parent)
    if jax.device_count() >= 4:
        assert engine_config_from_args(parent).mesh_shape == (2, 2)


def _engine_args(argv):
    import argparse

    from replicatinggpt_tpu.cli import add_engine_flags
    p = argparse.ArgumentParser()
    add_engine_flags(p)
    return p.parse_args(argv)


def test_serve_mesh_past_device_count_is_an_error():
    """A serve mesh the process cannot satisfy is an error — never a
    quiet 1x1 run under the mesh's name."""
    from replicatinggpt_tpu.cli import engine_config_from_args
    with pytest.raises(ValueError, match="wants 4096 devices"):
        engine_config_from_args(_engine_args(["--mesh-shape", "64x64"]))


def test_forwarding_parent_keeps_mesh_shape_unchecked():
    """The `serve --multiproc` parent only forwards the shape (its
    workers own the devices and validate it): check_devices=False
    neither downgrades nor raises, so the fleet's shape hash is the
    operator's shape."""
    from replicatinggpt_tpu.cli import engine_config_from_args
    ecfg = engine_config_from_args(_engine_args(["--mesh-shape", "64x64"]),
                                   check_devices=False)
    assert ecfg.mesh_shape == (64, 64)


def test_train_mesh_past_device_count_is_an_error():
    """`--preset gpt2-small` carries MeshConfig(data=8); a process with
    fewer devices must say so, not train unsharded."""
    from replicatinggpt_tpu.cli import _build_mesh_if_needed
    from replicatinggpt_tpu.config import MeshConfig, get_config
    cfg = get_config("test-tiny").replace(mesh=MeshConfig(data=64))
    with pytest.raises(SystemExit, match="mesh wants 64 devices"):
        _build_mesh_if_needed(cfg)
    assert _build_mesh_if_needed(
        cfg.replace(mesh=MeshConfig(data=1))) is None


def test_parse_mesh_shape_formats():
    from replicatinggpt_tpu.parallel.mesh import parse_mesh_shape
    assert parse_mesh_shape("2x2") == (2, 2)
    assert parse_mesh_shape("4,1") == (4, 1)
    assert parse_mesh_shape("1X2") == (1, 2)
    for bad in ("", "2", "2x2x2", "0x2", "ax2"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)


def test_graftlint_mesh_rules_clean_over_sharded_serve_path():
    """GL010-14 (the mesh/sharding family) over the files this PR
    shards — zero findings, zero pragmas (the PR 6 parallel/+serve/
    pin, extended to the sharded serve path)."""
    from pathlib import Path

    from replicatinggpt_tpu.analysis import lint_paths
    repo = Path(__file__).resolve().parent.parent / "replicatinggpt_tpu"
    res = lint_paths(
        [repo / "serve", repo / "parallel" / "mesh.py",
         repo / "models" / "gpt.py"],
        ["GL010", "GL011", "GL012", "GL013", "GL014"],
        severity={})
    assert not res.findings, [f.format() for f in res.findings]
    assert not res.warnings, [f.format() for f in res.warnings]
