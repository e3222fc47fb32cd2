"""What the served families beside GPT-2 (``exaone_moe``, ``lfm2_moe``) have
in common, each test once a family at test widths on the CPU: the ONE expert
layer both call and the share it is told to hold, routing, the decode window,
the allocator over pages and per-slot state, the precision the tolerance
tells apart, the engine through ``submit`` / ``step`` with more requests than
slots, kills, the launch's stats, and every refusal. What one family alone
has is in ``tests/test_exaone_moe.py`` / ``tests/test_lfm2_moe.py``.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu import reference_exaone_moe, reference_lfm2_moe
from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.models import exaone_moe, layers, lfm2_moe
from replicatinggpt_tpu.models.families import family, serve_refusals
from replicatinggpt_tpu.ops import paged_pallas
from replicatinggpt_tpu.serve import Engine, EngineConfig
from replicatinggpt_tpu.serve.pages import PagedCachePool, page_bytes
from replicatinggpt_tpu.serve.requests import Request, SamplingParams

#: float32 program against the float32 reference at test widths: rounding
#: of different summation orders reads 1e-6; a wrong mask, position, page,
#: state column or expert moves a logit by 1e-2 and more. The precision
#: tests hold that bfloat16 routing and 8-bit weights both land above it.
LOGIT_TOL = 2e-4
PSZ = 8

CASES = {
    "exaone_moe": SimpleNamespace(
        cfg=get_config("exaone-moe-tiny").model, m=exaone_moe,
        ref=reference_exaone_moe, sparse_layer=1, state="window"),
    "lfm2_moe": SimpleNamespace(
        cfg=get_config("lfm2-moe-tiny").model, m=lfm2_moe,
        ref=reference_lfm2_moe, sparse_layer=2, state="conv"),
}


@pytest.fixture(scope="module", params=list(CASES))
def fam(request):
    case = CASES[request.param]
    if not hasattr(case, "params"):
        case.params = case.m.init_params(jax.random.PRNGKey(7), case.cfg)
    return case


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    for m in (exaone_moe, lfm2_moe):
        monkeypatch.setattr(m, "FORWARD_BLOCK", 16)
        monkeypatch.setattr(m, "PREFILL_KV_BLOCK", 16)


@pytest.fixture()
def kernel_on_cpu(monkeypatch):
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)


def _ids(cfg, seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         cfg.vocab_size), np.int32)


def _ref_logits(fam, params, seq, cfg=None):
    out, _ = fam.ref.logits(params, jnp.asarray(seq),
                            fam.ref.spec_of(cfg or fam.cfg),
                            row_block=16 if len(seq) % 16 == 0 else 1024)
    return np.asarray(out)


def slot_state_bytes(cfg, n_slots: int, itemsize: int = 4) -> dict:
    """Bytes of each kind of per-slot state, reckoned by hand."""
    if cfg.family == "exaone_moe":
        ring = exaone_moe.ring_pages(cfg, PSZ) * PSZ
        return {"window": (len(cfg.window_layers) * 2 * n_slots * ring
                           * cfg.kv_channels * itemsize)}
    return {"conv": (len(cfg.conv_layers) * n_slots * cfg.conv_reach
                     * cfg.n_embd * itemsize)}


# ------------------------------------------------------------ decode window

def test_decode_window_is_the_step_repeated(fam):
    """``decode_window_paged`` (W steps in one program) emits what W single
    steps emit, and its token block carries the held pairs in its last
    column."""
    cfg, m = fam.cfg, fam.m
    B, mp = 2, cfg.block_size // PSZ
    cache = m.init_paged_kv_pool(cfg, B * mp, PSZ, n_slots=B)
    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    tok, pos = jnp.asarray([5, 9], jnp.int32), jnp.zeros((B,), jnp.int32)
    active = jnp.ones((B,), bool)
    rngs = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    greedy = lambda r, logits, live: (jnp.argmax(logits, -1)
                                      .astype(jnp.int32), r)
    toks, emitted, *_ = m.decode_window_paged(
        fam.params, tok, pos, active, jnp.full((B,), 9, jnp.int32),
        jnp.full((B,), -1, jnp.int32), tables, cache, rngs, cfg,
        sample_fn=greedy, length=4)
    assert toks.shape == (4, B + 1) and bool(emitted.all())
    c, t, p = cache, tok, pos
    for s in range(4):
        logits, c, pairs = m.decode_step_paged(fam.params, t, p, active,
                                               tables, c, cfg)
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        p = p + 1
        assert np.array_equal(np.asarray(toks[s, :B]), np.asarray(t))
        assert int(toks[s, B]) == int(pairs)


# ------------------------------------------ the ONE expert layer, its share

def test_both_families_call_the_one_expert_layer():
    """Neither family's module has an expert layer of its own: both reach
    ``layers.moe`` through ``layers._mlp``."""
    assert lfm2_moe._mlp is layers._mlp and exaone_moe._mlp is layers._mlp
    for m in (exaone_moe, lfm2_moe):
        assert not hasattr(m, "moe") and not hasattr(m, "route")


def test_the_shares_and_what_every_chip_computes_once_add_up(fam):
    """The share tied to the model, for the shared expert layer: eight
    chips holding one expert each, with the shared expert (where the family
    has one: every chip computes it alike) counted once, add up to the
    layer a chip holding all eight computes, and that is the uncut
    reference layer."""
    cfg = fam.cfg
    whole = dataclasses.replace(cfg, experts_held=tuple(range(8)))
    lp = fam.m.init_params(jax.random.PRNGKey(3),
                           whole)["layers"][fam.sparse_layer]
    m = jax.random.normal(jax.random.PRNGKey(4), (12, cfg.n_embd))
    y_whole, top, pairs = layers.moe(m, lp, whole)
    assert int(pairs) == 12 * cfg.experts_per_token     # every pair is held
    shared = (layers._swiglu(m, lp["s_gate"], lp["s_up"], lp["s_down"])
              if "s_gate" in lp else jnp.zeros_like(m))
    assert ("s_gate" in lp) == bool(cfg.shared_intermediate_size)
    total = shared
    for e in range(8):
        share = dataclasses.replace(cfg, experts_held=(e,))
        lp_e = {**lp, **{n: lp[n][e:e + 1]
                         for n in ("e_gate", "e_up", "e_down")}}
        y_e, top_e, _ = layers.moe(m, lp_e, share)
        assert np.array_equal(np.asarray(top_e), np.asarray(top))
        total = total + (y_e - shared)      # its routed part alone
    assert np.abs(np.asarray(total - y_whole)).max() < 1e-5
    # the uncut reference layer: its router, then every expert by its own
    # scores, in float32 at full precision. The reference norms its rows
    # itself; the program's layer is handed them normed
    spec, eps = fam.ref.spec_of(whole), cfg.layernorm_eps
    ones = jnp.ones((cfg.n_embd,))
    m_n = fam.ref._rms(m, ones, eps)
    w, _, _ = fam.ref._router(
        m, ones, lp["router"], lp["router_bias"], None, 0.0,
        jnp.ones((12,), bool), eps=eps, k=spec["experts_per_token"],
        scaling=spec["routed_scaling"],
        **({"norm_eps": spec["router_norm_eps"]}
           if "router_norm_eps" in spec else {}))
    with jax.default_matmul_precision("highest"):
        want = sum(w[:, e:e + 1] * fam.ref._swiglu(
            m_n, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e])
            for e in range(8))
        if "s_gate" in lp:
            want = want + fam.ref._swiglu(m_n, lp["s_gate"], lp["s_up"],
                                          lp["s_down"])
    y_n, _, _ = layers.moe(m_n, lp, whole)
    assert np.abs(np.asarray(y_n - want)).max() < 1e-5


def test_selection_by_s_plus_b_weights_from_s_alone(fam):
    cfg = fam.cfg
    lp = dict(fam.params["layers"][fam.sparse_layer])
    # expert 5 is pushed into every token's choice by its bias alone
    lp["router_bias"] = jnp.zeros((8,)).at[5].set(10.0)
    m = jax.random.normal(jax.random.PRNGKey(2), (9, cfg.n_embd))
    w, top = layers.route(m, lp, cfg)
    s = np.asarray(jax.nn.sigmoid(
        jnp.dot(m, lp["router"], precision=jax.lax.Precision.HIGHEST)))
    w, top = np.asarray(w), np.asarray(top)
    scale = cfg.routed_scaling
    for r in range(9):
        chosen = set(top[r].tolist())
        assert 5 in chosen and len(chosen) == cfg.experts_per_token
        other = max((e for e in range(8) if e != 5), key=lambda e: s[r, e])
        assert chosen == {5, other}
        denom = sum(s[r, e] for e in chosen) + cfg.router_norm_eps
        for e in range(8):
            want = scale * s[r, e] / denom if e in chosen else 0.0
            assert abs(w[r, e] - want) < 1e-6       # the bias is not in w
        assert abs(w[r].sum() - scale) < 1e-5   # normalised, times scaling


# --------------------------------------------------------------- allocator

def _pool(cfg, n_slots=3, n_pages=0):
    return PagedCachePool(cfg, n_slots, page_size=PSZ, n_pages=n_pages,
                          prefix_cache=False)


def test_slot_state_does_not_grow_with_context(fam):
    cfg = fam.cfg
    pool = _pool(cfg)
    kinds = pool.bytes_by_kind()
    assert set(kinds) == {"pages", fam.state}
    assert kinds[fam.state] == slot_state_bytes(cfg, 3)[fam.state]
    assert kinds["pages"] == pool.n_pages * page_bytes(cfg, PSZ)
    before = {n: a.shape for n, a in pool.cache.items()}
    short = pool.acquire("a", _ids(cfg, 1, 4), 4)
    long_ = pool.acquire("b", _ids(cfg, 2, 40), 20)
    assert {n: a.shape for n, a in pool.cache.items()} == before
    assert pool.bytes_by_kind() == kinds
    held = lambda adm: int((pool.tables[adm.slot] != 0).sum())
    assert held(long_) > held(short)        # pages follow the context
    # what is a page and what is a slot's: told apart by name alone
    assert all(a.shape[1] == pool.n_pages for a in pool.pages.values())
    assert len(pool.pages) == 2 * len(cfg.paged_layers)
    assert sum(map(len, pool.slot_state().values())) \
        == len(pool.cache) - len(pool.pages)


def test_a_finished_request_gives_back_pages_and_state(fam):
    pool = _pool(fam.cfg)
    free0, slots0 = pool.alloc.pages_free, pool.n_free
    adm = pool.acquire("a", _ids(fam.cfg, 1, 30), 10)
    assert pool.alloc.pages_free == free0 - 5 and pool.n_free == slots0 - 1
    pool.release(adm.slot)
    assert pool.alloc.pages_free == free0 and pool.n_free == slots0
    again = pool.acquire("b", _ids(fam.cfg, 2, 3), 2)  # its state with it
    assert again.slot == adm.slot


def test_admission_counts_the_full_layers_pages_only(fam):
    cfg = fam.cfg
    pool = _pool(cfg, n_slots=4, n_pages=8)      # one slot's worst case
    assert pool.alloc.n_pages_for(30, 10) == 5
    assert page_bytes(cfg, PSZ) == (len(cfg.paged_layers) * PSZ * 2
                                    * cfg.kv_channels * 4)
    assert len(cfg.paged_layers) == cfg.layer_types.count("full_attention")
    assert pool.can_admit(_ids(cfg, 1, 30), 10)          # 5 of 8 pages
    pool.acquire("a", _ids(cfg, 1, 30), 10)
    assert not pool.can_admit(_ids(cfg, 2, 30), 10)  # pages, not slots
    assert pool.can_admit(_ids(cfg, 3, 10), 6)           # 2 pages still fit


# --------------------------------------------------------------- precision

def _worst(fam, params, idx):
    got = np.asarray(fam.m.forward(params, jnp.asarray(idx[None]),
                                   fam.cfg))[0]
    return np.abs(got - _ref_logits(fam, fam.params, idx)).max()


def test_tolerance_fails_under_bfloat16_routing(fam, monkeypatch):
    idx = _ids(fam.cfg, 21, 48)
    assert _worst(fam, fam.params, idx) < LOGIT_TOL
    exact = layers.route

    def bf16_route(m, lp, cfg):
        lp = {**lp, "router": lp["router"].astype(jnp.bfloat16)
              .astype(jnp.float32)}
        return exact(m.astype(jnp.bfloat16).astype(jnp.float32), lp, cfg)

    monkeypatch.setattr(layers, "route", bf16_route)
    assert _worst(fam, fam.params, idx) > LOGIT_TOL


def test_tolerance_fails_under_8_bit_weights(fam):
    def to8(a):
        if a.ndim < 2:
            return a
        scale = jnp.abs(a).max() / 127.0
        return jnp.round(a / scale) * scale

    idx = _ids(fam.cfg, 22, 48)
    rounded = jax.tree_util.tree_map(to8, fam.params)
    assert _worst(fam, rounded, idx) > LOGIT_TOL


# ---------------------------------------------------------------- the engine

ECFG = EngineConfig(pool_size=3, page_size=PSZ, prefill_chunk=16,
                    paged_kernel=True, prefix_cache=False, max_queue=16)


def test_engine_serves_the_family_through_submit_and_step(fam,
                                                          kernel_on_cpu):
    cfg = fam.cfg
    eng = Engine(fam.params, cfg, ECFG)
    rng = np.random.default_rng(0)
    reqs = [Request(id=f"r{i}", prompt=rng.integers(
        0, cfg.vocab_size, (n,), dtype=np.int32), max_new_tokens=m,
        sampling=SamplingParams(greedy=True))
        for i, (n, m) in enumerate([(3, 12), (17, 9), (30, 20), (9, 5),
                                    (24, 11), (1, 6)])]
    for r in reqs:
        assert eng.submit(r) is None
    done = {r.id: r for r in eng.drain()}
    assert len(done) == 6 and all(r.ok for r in done.values())
    gaps, mean_gap, _ = fam.ref.stream_gaps(
        fam.params, fam.ref.spec_of(cfg), cfg.block_size,
        [r.prompt for r in reqs],
        [np.asarray(done[r.id].tokens, np.int32) for r in reqs],
        row_block=16)
    assert max(gaps) < LOGIT_TOL and mean_gap <= max(gaps), gaps
    s = eng.metrics_summary()
    assert s["kernel_route"]["route"] == "pallas"
    assert s["kernel_route"]["reasons"] == []
    assert s["kernel_route"]["decode"] == "pallas"
    assert s["kernel_route"]["window"] == "none"    # no mixed, no verify
    assert s["counters"]["moe_pairs_held"] > 0
    kinds = eng.pool.bytes_by_kind()
    state = slot_state_bytes(cfg, 3)
    assert s["kv_global_bytes"] == kinds["pages"]
    assert s["kv_window_bytes"] == state.get("window", 0)
    assert s["conv_state_bytes"] == state.get("conv", 0)
    assert kinds[fam.state] == state[fam.state]
    assert eng.pool.alloc.pages_free == eng.pool.n_pages


def test_launch_stats_tell_the_kinds_of_state_apart(fam, kernel_on_cpu):
    from replicatinggpt_tpu.utils.telemetry import Telemetry
    cfg, tel = fam.cfg, Telemetry()
    eng = Engine(fam.params, cfg, ECFG, telemetry=tel)
    fx = eng._launch_extra
    leaves = lambda start: sum(
        int(np.prod(a.shape)) * 4 for lp in fam.params["layers"]
        for n, a in lp.items() if n.startswith(start))
    assert fx["expert_bytes"] == leaves("e_") > 0
    # a context token's bytes: the full layers alone
    assert eng._kv_token_bytes == (len(cfg.paged_layers) * 2
                                   * cfg.kv_channels * 4)
    if fam.state == "window":
        assert fx["window"] == cfg.sliding_window
        assert fx["swa_token_bytes"] == (len(cfg.window_layers) * 2
                                         * cfg.kv_channels * 4)
        assert "conv_slot_bytes" not in fx
    else:
        assert fx["conv_slot_bytes"] == (len(cfg.conv_layers)
                                         * cfg.conv_reach * cfg.n_embd * 4)
        assert fx["conv_weight_bytes"] == leaves("conv_") > 0
        assert "swa_token_bytes" not in fx
    # what a launch reports, read off the phases the recorder keeps
    for i, n in enumerate((5, 11)):
        eng.submit(Request(id=f"s{i}", prompt=_ids(cfg, 30 + i, n),
                           max_new_tokens=4,
                           sampling=SamplingParams(greedy=True)))
    eng.drain()
    launches = [ev["args"] for ev in tel.events
                if ev["name"] == "serve/launch" and ev["args"]["n_active"] == 2]
    assert launches
    for kw in launches:
        assert kw["live_kv_bytes"] == kw["live_tokens"] * eng._kv_token_bytes
        assert kw["expert_weight_bytes"] == fx["expert_bytes"]
        assert kw["moe_rows"] == 2
        if fam.state == "conv":
            assert kw["conv_state_bytes"] == 2 * fx["conv_slot_bytes"]
            assert kw["short_conv_bytes"] == (fx["conv_weight_bytes"]
                                              + kw["conv_state_bytes"])
            assert "swa_kv_bytes" not in kw
        else:
            assert kw["swa_kv_bytes"] > 0 and "conv_state_bytes" not in kw


def test_a_tree_with_nothing_to_cast_is_served_as_it_is(fam, kernel_on_cpu):
    """The families' parameters come in the compute dtype, so the engine
    serves the caller's own tree, containers and all: the benchmark's 8-bit
    control rounds 10 GB in place leaf by leaf after the run, and a second
    tree of dicts holding the old arrays would keep every one of them."""
    from replicatinggpt_tpu.serve.engine import served_tree
    eng = Engine(fam.params, fam.cfg, ECFG)
    assert eng.served_params is fam.params is eng.params
    assert eng.metrics_summary()["weight_cast_bytes"] == 0
    half = dataclasses.replace(fam.cfg, dtype="bfloat16")
    cast = served_tree(fam.params, family(half).serve_cast_leaves, "bfloat16")
    assert cast is not fam.params and cast["wte"].dtype == jnp.bfloat16
    assert cast["norm_f"] is fam.params["norm_f"]     # not a leaf it names


# ----------------------------------------------------------------- refusals

class _Drafter:
    name, k, pool_size = "stub", 2, 3


@pytest.mark.parametrize("change,drafter,word", [
    (dict(decode_window=4), None, "mixed"),
    (dict(), _Drafter(), "speculative"),
    (dict(prefix_cache=True), None, "prefix_cache"),
    (dict(mesh_model=2), None, "mesh"),
    (dict(kv_quant="int8"), None, "quantised"),
    (dict(weight_quant="int8"), None, "quantised"),
], ids=["mixed-window", "verify", "prefix-cache", "mesh", "kv-quant",
        "weight-quant"])
def test_engine_refuses_what_the_family_lacks(fam, change, drafter, word):
    ecfg = dataclasses.replace(ECFG, **change)
    why = serve_refusals(fam.cfg, ecfg, drafter)
    assert len(why) == 1 and word in why[0]
    with pytest.raises(ValueError, match=word):
        Engine(fam.params, fam.cfg, ecfg, drafter=drafter)
    assert serve_refusals(fam.cfg, ECFG) == []


@pytest.mark.parametrize("name", ["mixed_window_paged", "verify_step_paged"])
def test_programs_the_family_lacks_refuse_by_name(fam, name):
    with pytest.raises(NotImplementedError, match=fam.cfg.family):
        getattr(family(fam.cfg), name)()


def test_the_pool_refuses_the_radix_cache_over_slot_state(fam):
    with pytest.raises(ValueError, match=f"prefix_cache.*{fam.state}"):
        PagedCachePool(fam.cfg, 2, page_size=PSZ, prefix_cache=True)


def test_refusals_are_the_familys_own_and_the_engine_names_no_family():
    import inspect
    from replicatinggpt_tpu.models import families
    from replicatinggpt_tpu.serve import engine, pages
    gpt = get_config("test-tiny").model
    every = dataclasses.replace(ECFG, decode_window=4, prefix_cache=True,
                                mesh_model=2, kv_quant="int8")
    assert serve_refusals(gpt, every, _Drafter()) == []
    assert family(gpt).refusals == {} and family(gpt).slot_entries == ()
    for name, case in CASES.items():
        assert set(family(case.cfg).refusals) == set(families.REFUSABLE)
        assert len(serve_refusals(case.cfg, every, _Drafter())) == 5
    assert "cfg.family ==" not in inspect.getsource(families.serve_refusals)
    for mod in (engine, pages):
        src = inspect.getsource(mod)
        assert "exaone" not in src and "lfm2" not in src, mod.__name__


# ------------------------------------- more requests than slots, and kills

def _drive(eng, reqs, hook=None):
    for r in reqs:
        assert eng.submit(r) is None
    done, steps = {}, 0
    while not eng.idle:
        for r in eng.step():
            done[r.id] = r
        steps += 1
        if hook is not None:
            hook(eng, steps, done)
        assert steps < 2000
    return done


def _requests(cfg, seed, sizes):
    rng = np.random.default_rng(seed)
    return [Request(id=f"q{i}", prompt=rng.integers(
        0, cfg.vocab_size, (n,), dtype=np.int32), max_new_tokens=m,
        sampling=SamplingParams(greedy=True))
        for i, (n, m) in enumerate(sizes)]


def test_a_reused_slot_emits_what_the_request_emits_alone(fam,
                                                          kernel_on_cpu):
    """More requests than slots, so later ones take a slot, its pages and
    its per-slot state after another request: token for token what each
    emits alone in a fresh engine (the state of the request before it is
    not read), and every page and slot comes back."""
    sizes = [(5, 9), (17, 14), (30, 6), (9, 11), (24, 8), (3, 13), (12, 7),
             (1, 5)]
    eng = Engine(fam.params, fam.cfg, ECFG)
    got = _drive(eng, _requests(fam.cfg, 3, sizes))
    assert all(got[f"q{i}"].ok and len(got[f"q{i}"].tokens) == m
               for i, (_, m) in enumerate(sizes))
    alone = Engine(fam.params, fam.cfg, ECFG)
    for r in _requests(fam.cfg, 3, sizes)[3:]:     # those that waited
        assert _drive(alone, [r])[r.id].tokens == got[r.id].tokens
    assert eng.pool.alloc.pages_free == eng.pool.n_pages
    assert eng.pool.n_free == ECFG.pool_size
    assert eng.metrics.counters["decode_tokens"] == sum(
        len(r.tokens) for r in got.values())


def test_cancel_and_deadline_give_back_pages_and_state(fam, kernel_on_cpu):
    clock = [0.0]
    eng = Engine(fam.params, fam.cfg, ECFG, clock=lambda: clock[0])
    reqs = _requests(fam.cfg, 4, [(6, 40), (11, 40), (20, 40)])
    reqs[2] = dataclasses.replace(reqs[2], deadline=5.0)

    def hook(e, n, done):
        if n == 6:
            assert e.cancel("q0")
        if n == 10:
            clock[0] = 10.0                    # q2's deadline passes

    done = _drive(eng, reqs, hook)
    assert done["q0"].finish_reason == "cancelled" and done["q0"].tokens
    assert done["q2"].finish_reason == "deadline"
    assert done["q1"].ok and len(done["q1"].tokens) == 40
    assert eng.pool.alloc.pages_free == eng.pool.n_pages
    assert eng.pool.n_free == ECFG.pool_size
    # the slots the killed requests held serve the next ones correctly
    after = _drive(eng, _requests(fam.cfg, 5, [(7, 6), (13, 6), (2, 6)]))
    alone = Engine(fam.params, fam.cfg, ECFG)
    for r in _requests(fam.cfg, 5, [(7, 6), (13, 6), (2, 6)]):
        assert _drive(alone, [r])[r.id].tokens == after[r.id].tokens
