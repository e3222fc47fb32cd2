"""The olmo_hybrid family (Olmo-Hybrid-7B) against its plain reference, at
test widths on the CPU, logits not tokens: the whole-sequence forward;
prefill in chunks whose edges fall on and beside the chunked scan's 64 rows,
then decode through the pool, on the XLA and the kernel routes (interpret
mode), in float32 and bfloat16; whose state a row reads, and that a reused
slot leaks nothing; the chunked scan against the recurrence at the ends of
alpha's and beta's ranges; the reference's recurrence against
``transformers``' own; the decode kernel at the published head shape; the
stage's parameters and a slot's state at the published widths; the
engine's stats and the family's refusals.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_olmo_hybrid as ref
from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.models import olmo_hybrid as oh
from replicatinggpt_tpu.models.families import family, serve_refusals
from replicatinggpt_tpu.ops import gated_delta, paged_pallas
from replicatinggpt_tpu.serve import Engine, EngineConfig
from replicatinggpt_tpu.serve.pages import PagedCachePool
from replicatinggpt_tpu.serve.requests import Request, SamplingParams

#: float32 program against the float32 reference at test widths: rounding
#: of different summation orders (the chunked scan's solve, the kernel's
#: sums) reads 1e-5; a wrong tap, state, chunk edge, page or position moves
#: a logit by 1e-3 and more (``test_a_state_one_row_late_is_seen``)
LOGIT_TOL = 2e-4

#: the bfloat16 program (activations, pages and conv columns in bfloat16,
#: the delta-rule state float32) against the float32 reference: rounding
#: reads 0.046 of logits up to 0.43 (each post-norm block scales its
#: mixer's rounded output back to unit size); an unrelated row sits about
#: 0.4 off. The float32 test above is the one that tells a wrong state
BF16_TOL = 0.15

CFG = get_config("olmo-hybrid-tiny").model     # L L L F, block 384
PSZ = 8
CHUNK = 128          # two of the scan's 64-row chunks a prefill chunk


@pytest.fixture(scope="module")
def params():
    return oh.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture()
def kernel_on_cpu(monkeypatch):
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)


def _ids(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         CFG.vocab_size), np.int32)


def _ref_logits(params, seq, cfg=CFG):
    return np.asarray(ref.logits(params, jnp.asarray(seq), ref.spec_of(cfg)))


# ------------------------------------------------------- 1. whole sequence

def test_forward_matches_reference(params):
    idx = _ids(1, 2, 100)
    got = np.asarray(oh.forward(params, jnp.asarray(idx), CFG))
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(params, idx[b])).max() < LOGIT_TOL


# ------------------------------------- 2. chunked prefill, then paged decode

def _serve_by_hand(params, cfg, prompts, n_new, use_pallas, cache=None):
    """Prefill each prompt in chunks into its own slot, then decode all
    slots together teacher-forced with seeded tokens: logits (B, n_new, V)
    at positions P-1 .. P+n_new-2 of each row, the sequences, the cache."""
    B = len(prompts)
    mp = cfg.block_size // PSZ
    if cache is None:
        cache = oh.init_paged_kv_pool(cfg, B * mp, PSZ, n_slots=B)
    rng = np.random.default_rng(5)
    tables = rng.permutation(B * mp).astype(np.int32).reshape(B, mp)
    prefill = jax.jit(lambda *a: oh.prefill_chunk_paged(*a, cfg))
    for b, p in enumerate(prompts):
        n = -(-len(p) // CHUNK)
        padded = np.zeros((n * CHUNK,), np.int32)
        padded[:len(p)] = p
        for c in range(n):
            cache = prefill(params, jnp.asarray(padded[None, c * CHUNK:
                                                       (c + 1) * CHUNK]),
                            jnp.int32(c * CHUNK), jnp.int32(len(p)),
                            jnp.asarray(tables[b]), jnp.int32(b), cache)
    step = jax.jit(lambda *a: oh.decode_step_paged(
        *a, cfg, use_pallas=use_pallas))
    seqs = [list(p) for p in prompts]
    pos = np.array([len(p) - 1 for p in prompts], np.int32)
    tok = np.array([p[-1] for p in prompts], np.int32)
    out = []
    for t in range(n_new):
        logits, cache, pairs = step(params, jnp.asarray(tok),
                                    jnp.asarray(pos), jnp.ones((B,), bool),
                                    jnp.asarray(tables), cache)
        assert pairs is None
        out.append(np.asarray(logits))
        tok = rng.integers(0, cfg.vocab_size, (B,)).astype(np.int32)
        for b in range(B):
            seqs[b].append(int(tok[b]))
        pos = pos + 1
    return np.stack(out, 1), seqs, cache


def _check(params, prompts, got, seqs, n_new, tol=LOGIT_TOL):
    for b, p in enumerate(prompts):
        want = _ref_logits(params, np.asarray(seqs[b][:-1], np.int32))
        rows = want[len(p) - 1:len(p) - 1 + n_new]
        assert np.abs(got[b] - rows).max() < tol, (b, len(p))


#: a one-token prompt; one ending a row short of the scan's chunk, one on
#: it, one a row past it (its last chunk holds only the position the first
#: decode step re-runs); one ending a prefill chunk's second edge (256),
#: one a row past that
PROMPTS = (1, 63, 64, 65, 256, 257)


@pytest.mark.parametrize("dtype,tol", [("float32", LOGIT_TOL),
                                       ("bfloat16", BF16_TOL)])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
def test_prefill_in_chunks_then_decode_matches_reference(params, use_pallas,
                                                         dtype, tol):
    cfg = dataclasses.replace(CFG, dtype=dtype)
    prompts = [_ids(10 + n, n) for n in PROMPTS]
    n_new = 6
    got, seqs, _ = _serve_by_hand(params, cfg, prompts, n_new, use_pallas)
    _check(params, prompts, got, seqs, n_new, tol)


def test_a_sequence_starts_from_zeros_whatever_the_slot_held(params):
    """The state a request before left in the slot is not read: a pool
    whose conv columns and delta-rule state are NaN everywhere serves what
    a fresh one serves, from a prefill chunk at offset 0 and from a decode
    step at position 0, on both routes."""
    B, mp = 2, CFG.block_size // PSZ
    for use_pallas in (False, True):
        dirty = oh.init_paged_kv_pool(CFG, B * mp, PSZ, n_slots=B)
        for name in dirty:
            if name[0] in (oh.CONV_ENTRY_PREFIX, oh.DELTA_ENTRY_PREFIX):
                dirty[name] = jnp.full_like(dirty[name], jnp.nan)
        prompts = [_ids(31, 1), _ids(32, 70)]
        got, seqs, cache = _serve_by_hand(params, CFG, prompts, 4,
                                          use_pallas, cache=dirty)
        assert np.isfinite(got).all()
        _check(params, prompts, got, seqs, 4)
        assert all(bool(jnp.isfinite(a).all()) for a in cache.values())


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
def test_an_idle_row_keeps_its_state_and_writes_nothing(params, use_pallas):
    B, mp = 2, CFG.block_size // PSZ
    cache = oh.init_paged_kv_pool(CFG, B * mp, PSZ, n_slots=B)
    cache = {n: a + 0.25 for n, a in cache.items()}
    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    _, after, _ = oh.decode_step_paged(
        params, jnp.asarray([3, 4]), jnp.asarray([5, 9]),
        jnp.asarray([True, False]), tables, cache, CFG,
        use_pallas=use_pallas)
    for name, a in after.items():
        if name[0] in (oh.CONV_ENTRY_PREFIX, oh.DELTA_ENTRY_PREFIX):
            assert np.array_equal(np.asarray(a[1]), np.asarray(cache[name][1]))
            assert not np.array_equal(np.asarray(a[0]),
                                      np.asarray(cache[name][0]))
        else:
            changed = np.asarray((a != cache[name]).any((0, 2, 3)))
            assert changed.sum() == 1 and changed[0]     # slot 0's page 0


def test_a_state_one_row_late_is_seen(params, monkeypatch):
    """The tolerance is tight enough for the state: a prefill that leaves
    both kinds of state one row late (after ``limit - 1``, which the first
    decode step re-runs) is caught."""
    prompts = [_ids(41, 70)]
    exact = oh._rows_taken
    monkeypatch.setattr(oh, "_rows_taken", lambda offset, limit, Pc:
                        jnp.minimum(exact(offset, limit, Pc) + 1, Pc))
    got, seqs, _ = _serve_by_hand(params, CFG, prompts, 3, False)
    with pytest.raises(AssertionError):
        _check(params, prompts, got, seqs, 3)


def _engine(params, cfg=CFG, **kw):
    return Engine(params, cfg, EngineConfig(
        pool_size=kw.pop("pool_size", 2), page_size=PSZ,
        prefill_chunk=CHUNK, decode_window=1, paged_kernel=True,
        prefix_cache=False), **kw)


def _request(rid, n, m, seed):
    return Request(id=rid, prompt=_ids(seed, n), max_new_tokens=m,
                   sampling=SamplingParams(greedy=True))


def _drive(eng, reqs):
    for r in reqs:
        assert eng.submit(r) is None
    done = {}
    while not eng.idle:
        for r in eng.step():
            done[r.id] = r.tokens
    return done


def test_a_reused_slot_emits_what_the_request_emits_alone(params,
                                                          kernel_on_cpu):
    """One slot: a long request first, then a short one. Nothing of the
    first occupant's conv columns or state reaches the second."""
    long_, short = _request("a", 200, 5, 50), _request("b", 30, 6, 51)
    reused = _drive(_engine(params, pool_size=1), [long_, short])
    alone = _drive(_engine(params, pool_size=1), [_request("b", 30, 6, 51)])
    assert reused["b"] == alone["b"]
    seq = np.concatenate([short.prompt, np.asarray(alone["b"][:-1])])
    want = _ref_logits(params, seq)[len(short.prompt) - 1:]
    assert (want.max(-1) - want[np.arange(6), alone["b"]]).max() < LOGIT_TOL


# ------------------------------------------------------- 3. the recurrence

def _rule_inputs(seed, T, H, dk, dv, beta_range, alpha_range):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = l2(jax.random.normal(ks[0], (T, H, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    u = jax.random.uniform(ks[3], (2, T, H))
    beta = beta_range[0] + u[0] * (beta_range[1] - beta_range[0])
    alpha = alpha_range[0] + u[1] * (alpha_range[1] - alpha_range[0])
    S0 = 0.1 * jax.random.normal(ks[4], (H, dk, dv))
    return q, k, v, alpha, beta, S0


@pytest.mark.parametrize("alpha_range", [(1e-4, 1e-3), (0.999, 1.0)],
                         ids=["alpha-near-0", "alpha-near-1"])
@pytest.mark.parametrize("beta_range", [(0.0, 1e-3), (1.999, 2.0)],
                         ids=["beta-near-0", "beta-near-2"])
def test_chunk_scan_matches_the_recurrence(beta_range, alpha_range):
    """Two chunks of 64 and a state carried into the first: the chunked
    form against the rule one token a turn, where the state forgets almost
    everything a token (alpha near 0) or nothing, and writes almost nothing
    (beta near 0) or reflects (beta near 2: an eigenvalue near -1)."""
    H, dk, dv, T = 3, 16, 32, 128
    q, k, v, alpha, beta, S0 = _rule_inputs(0, T, H, dk, dv, beta_range,
                                            alpha_range)
    S, want = S0, []
    for t in range(T):
        o, S = gated_delta.recurrent_step(S, q[t], k[t], v[t], alpha[t],
                                          beta[t])
        want.append(o)
    got, S_got = gated_delta.chunk_scan(q, k, v, jnp.log(alpha), beta, S0)
    assert np.abs(np.asarray(got) - np.stack(want)).max() < 1e-4
    assert np.abs(np.asarray(S_got) - np.asarray(S)).max() < 1e-4


def test_the_reference_recurrence_is_transformers_own():
    """An independent check of the equations: the reference's rule against
    ``transformers``' ``torch_recurrent_gated_delta_rule`` (and its chunked
    twin) on CPU torch, l2 norms inside."""
    torch = pytest.importorskip("torch")
    from transformers.models.qwen3_next import modeling_qwen3_next as q3n
    T, H, dk, dv = 70, 3, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (T, H, dk))
    k = jax.random.normal(ks[1], (T, H, dk))
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (T, H)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    want = np.asarray(ref.recurrence(q, k, v, g, beta))
    t = lambda a: torch.from_numpy(np.array(a, np.float32))[None]
    for rule in (q3n.torch_recurrent_gated_delta_rule,
                 q3n.torch_chunk_gated_delta_rule):
        kw = ({"initial_state": None}
              if rule is q3n.torch_recurrent_gated_delta_rule else {})
        got, _ = rule(t(q), t(k), t(v), g=t(g), beta=t(beta),
                      output_final_state=False, use_qk_l2norm_in_kernel=True,
                      **kw)
        assert np.abs(got[0].numpy() - want).max() < 1e-4, rule.__name__


def test_the_reference_can_hold_its_state_in_bfloat16(params):
    """The hand control of the state's precision: the reference's
    recurrence rounds its state to bfloat16 after every token (a rounding
    the compiler keeps, unlike a pair of converts), which moves the logits
    by more than the float32 program's rounding and less than a wrong
    state."""
    seq = _ids(70, 90)
    spec = ref.spec_of(CFG)
    f32 = _ref_logits(params, seq)
    bf16 = np.asarray(ref.logits(params, jnp.asarray(seq),
                                 {**spec, "state_dtype": "bfloat16"}))
    assert LOGIT_TOL / 100 < np.abs(bf16 - f32).max() < 0.05


def test_decode_kernel_at_the_published_head_shape():
    """30 heads of 96 x 192 (two heads abreast: rows of 384 lanes), a live
    row, a row at its first position and an idle row, in interpret mode
    against the kernel's contract in XLA."""
    H, dk, dv, B = 30, 96, 192, 3
    assert gated_delta.state_shape(H, dk, dv) == (15, 96, 384)
    q, k, v, alpha, beta, _ = _rule_inputs(5, B, H, dk, dv, (0.0, 2.0),
                                           (0.5, 1.0))
    state = gated_delta.from_heads(
        jax.random.normal(jax.random.PRNGKey(6), (B, H, dk, dv)))
    first = jnp.asarray([False, True, False])
    active = jnp.asarray([True, True, False])
    o_want, s_want = gated_delta.decode_xla(state, q, k, v, alpha, beta,
                                            first, active)
    o_got, s_got = gated_delta.gated_delta_decode(state, q, k, v, alpha,
                                                  beta, first, active)
    assert np.abs(np.asarray(o_got) - np.asarray(o_want)).max() < 1e-5
    assert np.abs(np.asarray(s_got) - np.asarray(s_want)).max() < 1e-5
    assert np.array_equal(np.asarray(s_got[2]), np.asarray(state[2]))
    # the live row is one token of the rule on its state
    o1, S1 = gated_delta.recurrent_step(
        gated_delta.to_heads(state[:1], dv), q[:1], k[:1], v[:1],
        alpha[:1], beta[:1])
    assert np.abs(np.asarray(o_got[:1]) - np.asarray(o1)).max() < 1e-5
    assert np.abs(np.asarray(gated_delta.to_heads(s_got[:1], dv))
                  - np.asarray(S1)).max() < 1e-5


# ---------------------------------------------- 4. the published first stage

def test_the_stage_holds_2_435_748_072_parameters():
    """Counted leaf by leaf at the published widths (no array is made):
    6 linear layers of 215,570,172, 2 full layers of 185,809,920, the
    embedding and the untied head of 385,351,680 each and the final norm."""
    cfg = get_config("olmo-hybrid-7b").model
    shapes = jax.eval_shape(lambda: oh.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    count = lambda t: sum(int(np.prod(a.shape))
                          for a in jax.tree_util.tree_leaves(t))
    layers = shapes["layers"]
    assert [count(layers[i]) for i in range(8)] == (
        [215_570_172] * 3 + [185_809_920]) * 2
    assert count(shapes) == 2_435_748_072
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(shapes))


def test_a_slot_holds_13_271_040_bytes_of_delta_rule_state():
    """At the cell's widths (a context of 64 here: the state does not grow
    with it): the pool's ``delta`` kind, 6 layers x 30 x 96 x 192 float32,
    and the conv columns, 6 x 3 x 11,520 bfloat16."""
    cfg = dataclasses.replace(get_config("olmo-hybrid-7b").model,
                              block_size=64)
    pool = PagedCachePool(cfg, 2, page_size=16, prefix_cache=False)
    kinds = pool.bytes_by_kind()
    assert kinds["delta"] == 2 * 13_271_040
    assert kinds["conv"] == 2 * 6 * 3 * 11_520 * 2
    assert kinds["pages"] == pool.n_pages * 16 * 2 * 15_360
    assert cfg.kv_token_width * 2 == 15_360


def test_the_preset_is_the_published_first_stage():
    cfg = get_config("olmo-hybrid-7b").model
    assert cfg.layer_types == (("linear_attention",) * 3
                               + ("full_attention",)) * 2
    assert (cfg.n_embd, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size) == (
                3840, 30, 30, 128, 11_008, 100_352)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_allow_neg_eigval) == (
                30, 30, 96, 192, 4, True)
    assert not cfg.tied_head
    assert cfg.layernorm_eps == 1e-6
    assert cfg.paged_layers == (3, 7) and len(cfg.linear_layers) == 6


@pytest.mark.parametrize("bad,word", [
    (dict(linear_key_head_dim=0), "linear_key_head_dim"),
    (dict(linear_num_key_heads=15), "key head"),
    (dict(linear_conv_kernel_dim=1), "one column back"),
    (dict(tied_head=True), "untied"),
    (dict(layer_types=("linear_attention", "conv", "linear_attention",
                       "full_attention")), "layer_types="),
])
def test_validate_names_what_is_wrong(bad, word):
    with pytest.raises(AssertionError, match=word):
        dataclasses.replace(CFG, **bad).validate()


def test_the_other_families_refuse_this_ones_fields():
    lfm2 = get_config("lfm2-moe-tiny").model
    with pytest.raises(AssertionError, match="olmo_hybrid"):
        dataclasses.replace(lfm2, linear_key_head_dim=16).validate()


# ------------------------------------------- 5. the engine: stats, refusals

def test_launch_and_prefill_stats_count_each_kind_of_state(params,
                                                           kernel_on_cpu):
    from replicatinggpt_tpu.utils.telemetry import Telemetry
    tel = Telemetry()
    eng = _engine(params, telemetry=tel)
    fx = eng._launch_extra
    H, dk, dv, R = oh.linear_widths(CFG)
    n_lin = len(CFG.linear_layers)
    assert fx["delta_slot_bytes"] == n_lin * H * dk * dv * 4
    assert fx["conv_slot_bytes"] == n_lin * (R - 1) * oh.conv_channels(CFG) \
        * 4
    assert fx["gdn_scan"] == (n_lin, H, dk, dv)
    # a context token's bytes: the full layers alone
    assert eng._kv_token_bytes == len(CFG.paged_layers) * 2 \
        * CFG.kv_channels * 4
    _drive(eng, [_request("s0", 5, 4, 60), _request("s1", 150, 4, 61)])
    launches = [ev["args"] for ev in tel.events
                if ev["name"] == "serve/launch"
                and ev["args"]["n_active"] == 2]
    assert launches
    for kw in launches:
        assert kw["delta_state_bytes"] == 2 * 2 * fx["delta_slot_bytes"]
        assert kw["conv_state_bytes"] == 2 * fx["conv_slot_bytes"]
        assert kw["live_kv_bytes"] == kw["live_tokens"] * eng._kv_token_bytes
        for absent in ("short_conv_bytes", "swa_kv_bytes",
                       "expert_weight_bytes", "mla_kv_bytes"):
            assert absent not in kw
    prefills = {ev["args"]["tokens"]: ev["args"] for ev in tel.events
                if ev["name"] == "serve/prefill"}
    cost = lambda t: gated_delta.chunk_scan_cost(t, H, dk, dv)
    b128, f128 = cost(128)
    b22, f22 = cost(22)
    assert prefills[150]["chunks"] == 2
    assert prefills[150]["gdn_scan_bytes"] == n_lin * (b128 + b22)
    assert prefills[150]["gdn_scan_flops"] == n_lin * (f128 + f22)
    s = eng.metrics_summary()
    assert s["delta_state_bytes"] == eng.pool.bytes_by_kind()["delta"] \
        == 2 * fx["delta_slot_bytes"]
    assert s["conv_state_bytes"] == 2 * fx["conv_slot_bytes"]
    assert "moe_pairs_held" not in s["counters"]


class _Drafter:
    name, k, pool_size = "stub", 2, 3


@pytest.mark.parametrize("change,drafter,word", [
    (dict(decode_window=4), None, "mixed"),
    (dict(), _Drafter(), "delta-rule state"),
    (dict(prefix_cache=True), None, "delta-rule state"),
    (dict(mesh_model=2), None, "mesh"),
    (dict(kv_quant="int8"), None, "quantised"),
], ids=["mixed-window", "verify", "prefix-cache", "mesh", "kv-quant"])
def test_engine_refuses_what_the_family_lacks(params, change, drafter, word):
    ecfg = dataclasses.replace(EngineConfig(
        pool_size=2, page_size=PSZ, prefill_chunk=CHUNK, decode_window=1,
        paged_kernel=True, prefix_cache=False), **change)
    why = serve_refusals(CFG, ecfg, drafter)
    assert len(why) == 1 and word in why[0]
    with pytest.raises(ValueError):
        Engine(params, CFG, ecfg, drafter=drafter)


@pytest.mark.parametrize("name", ["mixed_window_paged", "verify_step_paged"])
def test_programs_the_family_lacks_refuse_by_name(name):
    with pytest.raises(NotImplementedError, match="olmo_hybrid"):
        getattr(family(CFG), name)()


def test_the_pool_refuses_the_radix_cache_over_slot_state():
    with pytest.raises(ValueError, match="prefix_cache.*conv/delta"):
        PagedCachePool(CFG, 2, page_size=PSZ, prefix_cache=True)


def test_the_engine_names_no_family():
    import inspect
    from replicatinggpt_tpu.serve import engine, pages
    for mod in (engine, pages):
        assert "olmo" not in inspect.getsource(mod), mod.__name__
