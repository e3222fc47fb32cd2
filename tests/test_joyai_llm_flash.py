"""The joyai_llm_flash family (JoyAI-LLM-Flash: latent attention over a
paged latent cache, sigmoid-routed experts beside a shared one) against its
plain reference, at test widths on the CPU, logits not tokens: the
whole-sequence forward; prefill in chunks then decode through the pool, on
the XLA and the kernel routes (interpret mode); the absorbed decode against
the expanded attention; interleaved RoPE by hand; the eight shares of the
expert layer against the uncut reference layer; ``paged_mla_attention``
against its XLA contract at the published widths; the published share's
leaves and bytes; the engine through ``submit`` / ``step`` with and without
the radix prefix cache; every refusal by name.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu import reference_joyai_llm_flash as ref
from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.models import joyai_llm_flash as jm
from replicatinggpt_tpu.models import layers
from replicatinggpt_tpu.models.families import family, serve_refusals
from replicatinggpt_tpu.ops import paged_pallas
from replicatinggpt_tpu.serve import Engine, EngineConfig
from replicatinggpt_tpu.serve.pages import PagedCachePool, page_bytes
from replicatinggpt_tpu.serve.requests import Request, SamplingParams

#: float32 program against the float32 reference at test widths: rounding
#: of different summation orders (and of the absorbed form's reassociated
#: products) reads 3e-7; a wrong mask, position, page, rotation or expert
#: moves a logit by 1e-3 and more (``test_a_rotate_half_rope_is_seen``,
#: the precision tests below)
LOGIT_TOL = 2e-4

CFG = get_config("joyai-llm-flash-tiny").model   # dense, then 3 sparse
PSZ = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def params():
    return jm.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(jm, "FORWARD_BLOCK", 16)
    monkeypatch.setattr(jm, "PREFILL_KV_BLOCK", 16)


@pytest.fixture()
def kernel_on_cpu(monkeypatch):
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)


def _ids(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         CFG.vocab_size), np.int32)


def _ref_logits(params, seq, cfg=CFG):
    out, _ = ref.logits(params, jnp.asarray(seq), ref.spec_of(cfg),
                        row_block=16 if len(seq) % 16 == 0 else 1024)
    return np.asarray(out)


def _worst(params, idx, cfg=CFG, ref_params=None):
    """The program's forward at ``cfg`` against the float32 reference (of
    ``ref_params``, the program's own weights where None)."""
    got = np.asarray(jm.forward(params, jnp.asarray(idx[None]), cfg))[0]
    want = _ref_logits(params if ref_params is None else ref_params, idx)
    return np.abs(got - want).max()


# ------------------------------------------------------- 1. whole sequence

def test_forward_matches_reference(params):
    idx = _ids(1, 2, 48)
    got = np.asarray(jm.forward(params, jnp.asarray(idx), CFG))
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(params, idx[b])).max() < LOGIT_TOL


def test_interleaved_rope_turns_each_pair_by_hand():
    """The pair (2i, 2i+1) of a rotary row turns by ``t * theta **
    (-2i / D)`` in place: numpy, pair by pair, at several positions."""
    D, theta = 8, 3.2e7
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (5, 2, D)))
    t = np.array([0, 1, 7, 300, 8191])
    got = np.asarray(jm.rope_pairs(jnp.asarray(x), jnp.asarray(t), theta))
    want = np.zeros_like(x)
    for r in range(5):
        for i in range(D // 2):
            a = t[r] * theta ** (-2 * i / D)
            c, s = np.cos(a), np.sin(a)
            x0, x1 = x[r, :, 2 * i], x[r, :, 2 * i + 1]
            want[r, :, 2 * i] = x0 * c - x1 * s
            want[r, :, 2 * i + 1] = x1 * c + x0 * s
    assert np.abs(got - want).max() < 1e-5
    assert np.array_equal(got[0], x[0])             # position 0 turns nothing
    # the reference's own formulation agrees at positions 0..T-1
    seq = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (9, 3, D)))
    assert np.abs(np.asarray(ref._rope_pairs(jnp.asarray(seq), theta))
                  - np.asarray(jm.rope_pairs(jnp.asarray(seq),
                                             jnp.arange(9), theta))
                  ).max() < 1e-6


def test_a_rotate_half_rope_is_seen(params, monkeypatch):
    """The tolerance is tight enough for the rotation's pairing: a program
    that turns the halves (``layers._rope``, the other families') is
    caught, though the same angles are used."""
    idx = _ids(5, 48)
    assert _worst(params, idx) < LOGIT_TOL
    monkeypatch.setattr(jm, "rope_pairs", layers._rope)
    assert _worst(params, idx) > 5 * LOGIT_TOL


# ------------------------------------- 2. chunked prefill, then paged decode

def _serve_by_hand(params, prompts, n_new, use_pallas, chunk):
    """Prefill each prompt in chunks into its own slot, then decode all
    slots together teacher-forced with seeded tokens: logits (B, n_new, V)
    at positions P-1 .. P+n_new-2 of each row, and the sequences."""
    B = len(prompts)
    mp = CFG.block_size // PSZ
    cache = jm.init_paged_kv_pool(CFG, B * mp, PSZ)
    rng = np.random.default_rng(5)
    tables = rng.permutation(B * mp).astype(np.int32).reshape(B, mp)
    prefill = jax.jit(lambda *a: jm.prefill_chunk_paged(*a, CFG))
    for b, p in enumerate(prompts):
        n = -(-len(p) // chunk)
        padded = np.zeros((n * chunk,), np.int32)
        padded[:len(p)] = p
        for c in range(n):
            cache = prefill(params, jnp.asarray(padded[None, c * chunk:
                                                       (c + 1) * chunk]),
                            jnp.int32(c * chunk), jnp.int32(len(p)),
                            jnp.asarray(tables[b]), jnp.int32(b), cache)
    step = jax.jit(lambda *a: jm.decode_step_paged(
        *a, CFG, use_pallas=use_pallas))
    seqs = [list(p) for p in prompts]
    pos = np.array([len(p) - 1 for p in prompts], np.int32)
    tok = np.array([p[-1] for p in prompts], np.int32)
    out = []
    for _ in range(n_new):
        logits, cache, _ = step(params, jnp.asarray(tok), jnp.asarray(pos),
                                jnp.ones((B,), bool), jnp.asarray(tables),
                                cache)
        out.append(np.asarray(logits))
        tok = rng.integers(0, CFG.vocab_size, (B,)).astype(np.int32)
        for b in range(B):
            seqs[b].append(int(tok[b]))
        pos = pos + 1
    return np.stack(out, 1), seqs


#: a one-token prompt, one ending a page, one a token past a page, one
#: past a prefill turn of 16 positions, one several chunks and turns long
PROMPTS = (1, 8, 9, 17, 29)


@pytest.mark.parametrize("chunk", [4, 16], ids=["chunk-4", "chunk-16"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
def test_prefill_in_chunks_then_decode_matches_reference(params, use_pallas,
                                                         chunk):
    """Unequal slots in one batch, against the reference's full forward
    LOGITS: the expanded prefill writes the latent rows and the absorbed
    decode reads them (on the kernel route through ``paged_mla_attention``
    in interpret mode)."""
    prompts = [_ids(10 + n, n) for n in PROMPTS]
    n_new = 12
    got, seqs = _serve_by_hand(params, prompts, n_new, use_pallas, chunk)
    for b, p in enumerate(prompts):
        want = _ref_logits(params, np.asarray(seqs[b][:-1], np.int32))
        rows = want[len(p) - 1:len(p) - 1 + n_new]
        assert np.abs(got[b] - rows).max() < LOGIT_TOL, (b, len(p))


def test_absorbed_decode_equals_expanded_attention(params):
    """One layer's attention output for a fresh row at position n over n
    cached rows: the absorbed form (every head's query times its key
    expansion against the latent rows, the latent output times its value
    expansion) equals expanding the rows into keys and values."""
    lp, n, B = params["layers"][1], 13, 2
    a = jax.random.normal(jax.random.PRNGKey(9), (B, n + 1, CFG.n_embd))
    positions = jnp.arange(n + 1)
    rows = jm._latent_row(a, lp, CFG, positions)           # (B, n+1, row)
    qn, qr = jm._queries(a[:, -1:], lp, CFG, positions[-1:])
    w_uk, w_uv = jm.absorbed(lp, CFG, jnp.float32)
    q_abs = jnp.concatenate(
        [jnp.einsum("bhd,rhd->bhr", qn[:, 0], w_uk), qr[:, 0],
         jnp.zeros((B, CFG.n_head, CFG.kv_token_width - CFG.kv_lora_rank
                    - CFG.qk_rope_head_dim))], -1)
    mp = 2                                         # 16 positions of pages
    pages = jnp.zeros((B * mp, PSZ, CFG.kv_token_width))
    pages = pages.reshape(B, mp * PSZ, -1).at[:, :n].set(
        rows[:, :n]).reshape(B * mp, PSZ, -1)
    tables = jnp.arange(B * mp).reshape(B, mp)
    o_lat = jm._xla_mla_attention(q_abs, rows[:, -1], pages, tables,
                                  jnp.full((B,), n), CFG)
    absorbed = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
    for b in range(B):
        kn, v, kr = jm._expand(rows[b], lp, CFG)
        s = jm._expanded_scores(qn[b], qr[b], kn, kr, CFG)[:, 0]  # (H, n+1)
        want = jnp.einsum("hk,khd->hd", jax.nn.softmax(s, -1), v)
        assert np.abs(np.asarray(absorbed[b] - want)).max() < 1e-5


# ------------------------------------------ 3. the expert layer's share

def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    """The share tied to the model: eight chips holding 2 of the 16
    experts each (the published cut holds 32 of 256), with the shared
    expert that every chip computes alike counted once, add up to the
    layer a chip holding all 16 computes, and that is the uncut reference
    layer (its router over every expert by its own scores, every expert
    and the shared one, float32 at full precision)."""
    whole = dataclasses.replace(CFG, experts_held=tuple(range(16)))
    lp = jm.init_params(jax.random.PRNGKey(3), whole)["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(4), (12, CFG.n_embd))
    y_whole, top, pairs = layers.moe(m, lp, whole)
    assert int(pairs) == 12 * CFG.experts_per_token     # every pair is held
    shared = layers._swiglu(m, lp["s_gate"], lp["s_up"], lp["s_down"])
    total = shared
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        share = dataclasses.replace(CFG, experts_held=held)
        lp_c = {**lp, **{n: lp[n][2 * chip:2 * chip + 2]
                         for n in ("e_gate", "e_up", "e_down")}}
        y_c, top_c, _ = layers.moe(m, lp_c, share)
        assert np.array_equal(np.asarray(top_c), np.asarray(top))
        total = total + (y_c - shared)      # its routed part alone
    assert np.abs(np.asarray(total - y_whole)).max() < 1e-5
    spec, eps = ref.spec_of(whole), CFG.layernorm_eps
    ones = jnp.ones((CFG.n_embd,))
    m_n = ref._rms(m, ones, eps)
    w, _, _ = ref._router(
        m, ones, lp["router"], lp["router_bias"], None, 0.0,
        jnp.ones((12,), bool), eps=eps, k=spec["experts_per_token"],
        scaling=spec["routed_scaling"], norm_eps=spec["router_norm_eps"])
    with jax.default_matmul_precision("highest"):
        want = ref._swiglu(m_n, lp["s_gate"], lp["s_up"], lp["s_down"])
        want = want + sum(w[:, e:e + 1] * ref._swiglu(
            m_n, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e])
            for e in range(16))
    y_n, _, _ = layers.moe(m_n, lp, whole)
    assert np.abs(np.asarray(y_n - want)).max() < 1e-5


def test_routing_is_the_shared_route_at_256_outputs():
    """``layers.route`` unchanged at the published router: 256 sigmoid
    scores, 8 chosen by score + bias, weights from the scores alone over
    the chosen (+ 1e-20), times 2.5."""
    cfg = get_config("joyai-llm-flash").model
    assert (cfg.n_experts, cfg.experts_per_token, cfg.routed_scaling,
            cfg.router_norm_eps) == (256, 8, 2.5, 1e-20)
    r = np.random.default_rng(1)
    lp = {"router": jnp.asarray(r.standard_normal((64, 256)) * 0.02,
                                jnp.float32),
          "router_bias": jnp.asarray(r.standard_normal(256) * 0.02,
                                     jnp.float32)}
    small = dataclasses.replace(cfg, n_embd=64)
    m = jnp.asarray(r.standard_normal((5, 64)), jnp.float32)
    w, top = layers.route(m, lp, small)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        m, lp["router"], precision=jax.lax.Precision.HIGHEST)))
    c = s + np.asarray(lp["router_bias"])
    for row in range(5):
        chosen = set(np.argsort(-c[row])[:8].tolist())
        assert set(np.asarray(top[row]).tolist()) == chosen
        want = np.zeros(256)
        idx = sorted(chosen)
        want[idx] = 2.5 * s[row, idx] / (s[row, idx].sum() + 1e-20)
        assert np.abs(np.asarray(w[row]) - want).max() < 1e-6


# ------------------------------------------------------------------ 4. kernel

def _einsum_latent(q, new, pages, tables, pos, latent, scale):
    """The kernel's contract by hand: every head of slot b scores the rows
    at positions < pos[b] of its table's pages and its fresh row, and
    weighs their first ``latent`` lanes."""
    B, H, _ = q.shape
    out = np.zeros((B, H, latent), np.float32)
    for b in range(B):
        rows = np.concatenate([pages[tables[b]].reshape(-1, q.shape[2])
                               [:pos[b]], new[b][None]])
        s = q[b] @ rows.T * scale                         # (H, n + 1)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ rows[:, :latent]
    return out


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1.6e-2)])
def test_mla_kernel_against_its_contract_at_published_widths(dtype, tol):
    """``paged_mla_attention`` at the published widths (32 heads, rows of
    512 latent + 64 rotary lanes padded to 640, pages of 16) against the
    contract by hand AND ``_xla_mla_attention``: a slot at position 0 (no
    live block: the fresh row alone), one inside its first page, one
    several blocks of 8 pages deep ending inside a page; the pool stacked
    two layers deep and read at layer 1."""
    H, W, R, psz, mp = 32, 640, 512, 16, 20
    pos = np.array([0, 5, 150, 300], np.int32)
    B = len(pos)
    r = np.random.default_rng(3)
    f = lambda *s: r.standard_normal(s).astype(np.float32)
    cast = lambda a: np.asarray(jnp.asarray(a, dtype), np.float32)
    q, new = cast(f(B, H, W)), cast(f(B, W))
    pages = cast(f(2, B * mp, psz, W))
    tables = r.permutation(B * mp).astype(np.int32).reshape(B, mp)
    scale = 192 ** -0.5
    got = paged_pallas.paged_mla_attention(
        jnp.asarray(q, dtype), jnp.asarray(new, dtype),
        jnp.asarray(pages, dtype), jnp.asarray(tables), jnp.asarray(pos),
        layer=1, latent=R, scale=scale)
    want = _einsum_latent(q, new, pages[1], tables, pos, R, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)
    cfg = get_config("joyai-llm-flash").model
    xla = jm._xla_mla_attention(jnp.asarray(q), jnp.asarray(new),
                                jnp.asarray(pages[1]), jnp.asarray(tables),
                                jnp.asarray(pos), cfg)
    np.testing.assert_allclose(np.asarray(xla), want, atol=1e-4, rtol=1e-4)
    ok, why = paged_pallas.mla_attention_envelope(H, W, psz)
    assert ok and not why


@pytest.mark.parametrize("change,word", [
    (dict(row_width=576), "mla_row_lanes"), (dict(page_size=12), "page_align"),
    (dict(kv_quant="int8"), "mla_kv_quant")],
    ids=["row-not-whole-tiles", "page-align", "quantised"])
def test_mla_envelope_names_what_it_refuses(change, word):
    args = dict(n_head=32, row_width=640, page_size=16)
    args.update(change)
    ok, why = paged_pallas.mla_attention_envelope(**args)
    assert not ok and word in why


# ------------------------------------------------------- 5. the published cut

def _config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


def test_the_preset_is_the_published_share_leaf_by_leaf():
    """1,779,892,480 parameters (3.56 GB of bf16) and 11,520 B of latent
    rows a token, counted from the CONFIGURATION FILE's numbers and from
    the preset's leaves; the pool's rows are padded to 640 lanes."""
    c = _config_file()
    cfg = get_config("joyai-llm-flash").model.validate()
    h, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attn = (h * c["q_lora_rank"] + c["q_lora_rank"] * H * qk
            + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + H * c["v_head_dim"] * h
            + 2 * h + c["q_lora_rank"] + c["kv_lora_rank"])
    assert attn == 26_351_616
    expert = 3 * h * c["moe_intermediate_size"]
    moe = (attn + h * c["router_outputs"] + c["router_outputs"]
           + c["n_shared_experts"] * expert + len(c["experts_held"]) * expert)
    dense = attn + 3 * h * c["intermediate_size"]
    n_sparse = c["num_hidden_layers"] - c["first_k_dense_replace"]
    total = dense + n_sparse * moe + 2 * c["vocab_size"] * h + h
    assert (expert, dense, moe, total) == (4_718_592, 70_391_808,
                                           182_589_696, 1_779_892_480)
    shapes = jax.eval_shape(lambda: jm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == total
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(shapes))
    # one latent row a token a layer: 576 values, 1,152 B; 11,520 B a token
    row = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    assert c["num_hidden_layers"] * row * 2 == 11_520
    assert cfg.kv_token_width == 640                 # whole 128-lane tiles
    assert page_bytes(cfg, 16) == 16 * 10 * 640 * 2
    pool = jax.eval_shape(lambda: jm.init_paged_kv_pool(cfg, 64, 16))
    assert sorted(pool) == [f"ckv{j}" for j in range(10)]
    assert all(a.shape == (1, 64, 16, 640) for a in pool.values())


@pytest.mark.parametrize("bad,word", [
    (dict(qk_rope_head_dim=7), "even"),
    (dict(kv_lora_rank=0), "kv_lora_rank"),
    (dict(tied_head=True), "untied"),
    (dict(n_kv_head=2), "n_kv_head"),
    (dict(layer_types=("sliding_attention",) * 4), "layer_types"),
    (dict(conv_reach=3), "conv_reach"),
    (dict(mlp_layer_types=("sparse", "dense", "sparse", "sparse")), "lead"),
    (dict(experts_held=(16,)), "experts_held"),
], ids=["odd-rotary-width", "no-latent", "tied-head", "kv-heads", "window-layers",
        "conv", "dense-after-sparse", "experts-held"])
def test_validate_names_what_is_wrong(bad, word):
    with pytest.raises(AssertionError, match=word):
        dataclasses.replace(CFG, **bad).validate()


def test_the_other_families_refuse_latent_fields():
    gpt = get_config("test-tiny").model
    with pytest.raises(AssertionError, match="kv_lora_rank"):
        dataclasses.replace(gpt, kv_lora_rank=8).validate()
    for preset in ("exaone-moe-tiny", "lfm2-moe-tiny"):
        with pytest.raises(AssertionError, match="joyai_llm_flash"):
            dataclasses.replace(get_config(preset).model,
                                q_lora_rank=8).validate()


# ---------------------------------------------------------------- precision

def test_tolerance_fails_under_bfloat16_activations(params):
    """The test preset states float32: the same weights run with bfloat16
    activations (the latent rows, the absorbed queries and every product's
    inputs rounded) land above the tolerance. (A router rounded to bfloat16
    alone does not: at 2 of 16 experts held and init std 0.02 the routed
    part moves a logit by 1e-5.)"""
    idx = _ids(21, 48)
    assert _worst(params, idx) < LOGIT_TOL
    half = dataclasses.replace(CFG, dtype="bfloat16")
    assert _worst(params, idx, cfg=half) > LOGIT_TOL


def test_tolerance_fails_under_8_bit_weights(params):
    def to8(a):
        if a.ndim < 2:
            return a
        scale = jnp.abs(a).max() / 127.0
        return jnp.round(a / scale) * scale

    idx = _ids(22, 48)
    rounded = jax.tree_util.tree_map(to8, params)
    assert _worst(rounded, idx, ref_params=params) > LOGIT_TOL


# ---------------------------------------------------------------- the engine

ECFG = EngineConfig(pool_size=3, page_size=PSZ, prefill_chunk=16,
                    paged_kernel=True, prefix_cache=False, max_queue=16)


def _requests(seed, sizes, prefix=None):
    rng = np.random.default_rng(seed)
    out = []
    for i, (n, m) in enumerate(sizes):
        p = rng.integers(0, CFG.vocab_size, (n,), dtype=np.int32)
        if prefix is not None:
            p = np.concatenate([prefix, p])
        out.append(Request(id=f"q{i}", prompt=p, max_new_tokens=m,
                           sampling=SamplingParams(greedy=True)))
    return out


def _drive(eng, reqs):
    for r in reqs:
        assert eng.submit(r) is None
    return {r.id: r for r in eng.drain()}


def test_engine_serves_the_family_on_the_latent_kernel(params,
                                                       kernel_on_cpu):
    from replicatinggpt_tpu.utils.telemetry import Telemetry
    tel = Telemetry()
    eng = Engine(params, CFG, ECFG, telemetry=tel)
    reqs = _requests(0, [(3, 12), (17, 9), (30, 20), (9, 5), (24, 11),
                         (1, 6)])
    done = _drive(eng, reqs)
    assert len(done) == 6 and all(r.ok for r in done.values())
    gaps, mean_gap, _ = ref.stream_gaps(
        params, ref.spec_of(CFG), CFG.block_size, [r.prompt for r in reqs],
        [np.asarray(done[r.id].tokens, np.int32) for r in reqs],
        row_block=16)
    assert max(gaps) < LOGIT_TOL and mean_gap <= max(gaps), gaps
    s = eng.metrics_summary()
    assert s["kernel_route"]["route"] == "pallas"
    assert s["kernel_route"]["reasons"] == []
    assert s["kernel_route"]["window"] == "none"     # no mixed, no verify
    assert s["counters"]["moe_pairs_held"] > 0
    # every page is a latent row, none K/V of heads; nothing a slot
    assert s["kv_latent_bytes"] == eng.pool.bytes_by_kind()["pages"] > 0
    assert s["kv_global_bytes"] == 0 == s["conv_state_bytes"]
    assert eng.pool.bytes_by_kind().keys() == {"pages"}
    # what a launch reports: the latent rows' model bytes, and the pool's
    row_model = CFG.n_layer * (CFG.kv_lora_rank + CFG.qk_rope_head_dim) * 4
    assert eng._launch_extra["mla_token_bytes"] == row_model
    assert eng._kv_token_bytes == CFG.n_layer * CFG.kv_token_width * 4
    launches = [ev["args"] for ev in tel.events
                if ev["name"] == "serve/launch"]
    assert launches
    for kw in launches:
        assert kw["mla_kv_bytes"] == kw["live_tokens"] * row_model
        assert kw["live_kv_bytes"] == kw["live_tokens"] * eng._kv_token_bytes
        assert kw["kv_block_passes"] == kw["kv_blocks_live"]   # one pass
    assert eng.pool.alloc.pages_free == eng.pool.n_pages


def test_the_radix_prefix_cache_serves_latent_pages_unchanged(
        params, kernel_on_cpu):
    """Latent pages hold no state of a slot, so ``serve/pages.py``'s radix
    cache shares them as it is: requests behind a shared 20-token prefix
    emit token for token what an engine without the cache emits, and the
    later ones start their prefill past the cached pages."""
    fam = family(CFG)
    assert "prefix_cache" not in fam.refusals and fam.slot_entries == ()
    prefix = _ids(50, 20)
    sizes = [(3, 8), (7, 6), (2, 9), (11, 5)]
    cached = Engine(params, CFG, dataclasses.replace(ECFG,
                                                     prefix_cache=True))
    first = _drive(cached, _requests(6, sizes[:1], prefix))
    later = _drive(cached, _requests(6, sizes, prefix)[1:])
    plain = Engine(params, CFG, ECFG)
    want = _drive(plain, _requests(6, sizes, prefix))
    got = {**first, **later}
    for rid, r in want.items():
        assert got[rid].tokens == r.tokens, rid
    assert cached.metrics.counters["prefix_hit_tokens"] >= 3 * 16


def test_a_reused_slot_emits_what_the_request_emits_alone(params,
                                                          kernel_on_cpu):
    sizes = [(5, 9), (17, 14), (30, 6), (9, 11), (24, 8), (3, 13)]
    eng = Engine(params, CFG, ECFG)
    got = _drive(eng, _requests(3, sizes))
    alone = Engine(params, CFG, ECFG)
    for r in _requests(3, sizes)[3:]:              # those that waited
        assert _drive(alone, [r])[r.id].tokens == got[r.id].tokens
    assert eng.pool.alloc.pages_free == eng.pool.n_pages
    assert eng.pool.n_free == ECFG.pool_size


def test_decode_window_is_the_step_repeated(params):
    B, mp = 2, CFG.block_size // PSZ
    cache = jm.init_paged_kv_pool(CFG, B * mp, PSZ)
    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    tok, pos = jnp.asarray([5, 9], jnp.int32), jnp.zeros((B,), jnp.int32)
    active = jnp.ones((B,), bool)
    rngs = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    greedy = lambda r, logits, live: (jnp.argmax(logits, -1)
                                      .astype(jnp.int32), r)
    toks, emitted, *_ = jm.decode_window_paged(
        params, tok, pos, active, jnp.full((B,), 9, jnp.int32),
        jnp.full((B,), -1, jnp.int32), tables, cache, rngs, CFG,
        sample_fn=greedy, length=4)
    assert toks.shape == (4, B + 1) and bool(emitted.all())
    c, t, p = cache, tok, pos
    for s in range(4):
        logits, c, pairs = jm.decode_step_paged(params, t, p, active,
                                                tables, c, CFG)
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        p = p + 1
        assert np.array_equal(np.asarray(toks[s, :B]), np.asarray(t))
        assert int(toks[s, B]) == int(pairs)


def test_an_idle_row_writes_nothing(params):
    B, mp = 2, CFG.block_size // PSZ
    cache = jm.init_paged_kv_pool(CFG, B * mp, PSZ)
    cache = {n: a + 0.25 for n, a in cache.items()}
    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    _, after, _ = jm.decode_step_paged(
        params, jnp.asarray([3, 4]), jnp.asarray([5, 9]),
        jnp.asarray([True, False]), tables, cache, CFG)
    for name, a in after.items():
        changed = np.asarray((a != cache[name]).any((0, 2, 3)))
        assert changed.sum() == 1 and changed[0]     # slot 0's page 0


# ----------------------------------------------------------------- refusals

class _Drafter:
    name, k, pool_size = "stub", 2, 3


@pytest.mark.parametrize("change,drafter,word", [
    (dict(decode_window=4), None, "mixed"),
    (dict(), _Drafter(), "speculative"),
    (dict(mesh_model=2), None, "paged_mla_attention) has no shard_map"),
    (dict(kv_quant="int8"), None, "paged_mla_attention"),
    (dict(weight_quant="int8"), None, "not joyai_llm_flash's"),
], ids=["mixed-window", "verify", "mesh", "kv-quant", "weight-quant"])
def test_engine_refuses_what_the_family_lacks(params, change, drafter, word):
    ecfg = dataclasses.replace(ECFG, **change)
    why = serve_refusals(CFG, ecfg, drafter)
    assert len(why) == 1 and word in why[0]
    with pytest.raises(ValueError, match=re.escape(word)):
        Engine(params, CFG, ecfg, drafter=drafter)
    assert serve_refusals(CFG, ECFG) == []


@pytest.mark.parametrize("name", ["mixed_window_paged", "verify_step_paged"])
def test_programs_the_family_lacks_refuse_by_name(name):
    with pytest.raises(NotImplementedError, match="joyai_llm_flash"):
        getattr(family(CFG), name)()


def test_each_refusal_names_the_refusing_familys_own_kernel():
    """The mesh and quantisation refusals are true of the family that
    refuses: each names the paged kernel ITS decode step runs and its own
    leaves, and no other family's kernel."""
    kernels = {"exaone-moe-tiny": "paged_gqa_attention",
               "lfm2-moe-tiny": "paged_gqa_attention",
               "joyai-llm-flash-tiny": "paged_mla_attention"}
    for preset, kernel in kernels.items():
        cfg = get_config(preset).model
        refusals = family(cfg).refusals
        other = ({"paged_gqa_attention", "paged_mla_attention"}
                 - {kernel}).pop()
        for option in ("mesh", "quant"):
            assert kernel in refusals[option], (preset, option)
            assert other not in refusals[option], (preset, option)
        assert f"not {cfg.family}'s" in refusals["quant"]
    # where the engine serves it the pool agrees: no radix refusal
    PagedCachePool(CFG, 2, page_size=PSZ, prefix_cache=True)
