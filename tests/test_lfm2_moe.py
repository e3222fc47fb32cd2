"""The lfm2_moe family (LFM2-24B-A2B) against its plain reference, at test
widths on the CPU, logits not tokens: the whole-sequence forward; prefill in
chunks whose edges fall inside the short convolution's reach, then decode
through the pool, on the XLA and the kernel routes (interpret mode); whose
state a row reads; the grouped-query kernel at this family's group and head
size; the preset's published numbers; what ``validate()`` names. What the
family shares with ``exaone_moe`` (the expert layer, the allocator, the
engine, the refusals) is ``tests/test_served_families.py``'s.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_lfm2_moe as ref
from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.models import lfm2_moe as lm
from replicatinggpt_tpu.ops import paged_pallas
from replicatinggpt_tpu.serve.pages import page_bytes

#: float32 program against the float32 reference at test widths: rounding
#: of different summation orders reads 3e-7; a wrong tap, state column,
#: chunk edge, page, position or expert moves a logit by 1e-3 and more
#: (``test_a_wrong_state_column_is_seen``);
#: ``tests/test_served_families.py`` holds that bfloat16 routing and 8-bit
#: weights land above it.
LOGIT_TOL = 2e-4

CFG = get_config("lfm2-moe-tiny").model      # c c F c c c F, block 64
PSZ = 8


@pytest.fixture(scope="module")
def params():
    return lm.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(lm, "FORWARD_BLOCK", 16)
    monkeypatch.setattr(lm, "PREFILL_KV_BLOCK", 16)


def _ids(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         CFG.vocab_size), np.int32)


def _ref_logits(params, seq, cfg=CFG):
    out, _ = ref.logits(params, jnp.asarray(seq), ref.spec_of(cfg),
                        row_block=16 if len(seq) % 16 == 0 else 1024)
    return np.asarray(out)


# ------------------------------------------------------- 1. whole sequence

def test_forward_matches_reference(params):
    idx = _ids(1, 2, 48)
    got = np.asarray(lm.forward(params, jnp.asarray(idx), CFG))
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(params, idx[b])).max() < LOGIT_TOL


def test_the_short_conv_is_three_causal_taps_of_b_times_v(params):
    """The operator by hand for one conv layer: numpy, token by token."""
    lp = params["layers"][0]
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (9, CFG.n_embd)))
    C = CFG.n_embd
    u = h / np.sqrt((h * h).mean(-1, keepdims=True) + CFG.layernorm_eps)
    bcv = u @ np.asarray(lp["conv_in"])
    s = bcv[:, :C] * bcv[:, 2 * C:]
    w = np.asarray(lp["conv_w"])
    want = np.zeros_like(h)
    for t in range(9):
        c = sum(w[:, j] * s[t - 2 + j] for j in range(3) if t - 2 + j >= 0)
        want[t] = (bcv[t, C:2 * C] * c) @ np.asarray(lp["conv_out"])
    got, ext = lm._short_conv(jnp.asarray(h), lp,
                              jnp.zeros((3, C)), CFG)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert np.abs(np.asarray(ext[3:]) - s).max() < 1e-5


# ------------------------------------- 2. chunked prefill, then paged decode

def _serve_by_hand(params, cfg, prompts, n_new, use_pallas, chunk,
                   cache=None):
    """Prefill each prompt in chunks into its own slot, then decode all
    slots together teacher-forced with seeded tokens: logits (B, n_new, V)
    at positions P-1 .. P+n_new-2 of each row, the sequences, the cache."""
    B = len(prompts)
    mp = cfg.block_size // PSZ
    if cache is None:
        cache = lm.init_paged_kv_pool(cfg, B * mp, PSZ, n_slots=B)
    rng = np.random.default_rng(5)
    tables = rng.permutation(B * mp).astype(np.int32).reshape(B, mp)
    prefill = jax.jit(lambda *a: lm.prefill_chunk_paged(*a, cfg))
    for b, p in enumerate(prompts):
        n = -(-len(p) // chunk)
        padded = np.zeros((n * chunk,), np.int32)
        padded[:len(p)] = p
        for c in range(n):
            cache = prefill(params, jnp.asarray(padded[None, c * chunk:
                                                       (c + 1) * chunk]),
                            jnp.int32(c * chunk), jnp.int32(len(p)),
                            jnp.asarray(tables[b]), jnp.int32(b), cache)
    step = jax.jit(lambda *a: lm.decode_step_paged(
        *a, cfg, use_pallas=use_pallas))
    seqs = [list(p) for p in prompts]
    pos = np.array([len(p) - 1 for p in prompts], np.int32)
    tok = np.array([p[-1] for p in prompts], np.int32)
    out = []
    for t in range(n_new):
        logits, cache, pairs = step(params, jnp.asarray(tok),
                                    jnp.asarray(pos), jnp.ones((B,), bool),
                                    jnp.asarray(tables), cache)
        out.append(np.asarray(logits))
        tok = rng.integers(0, cfg.vocab_size, (B,)).astype(np.int32)
        for b in range(B):
            seqs[b].append(int(tok[b]))
        pos = pos + 1
    return np.stack(out, 1), seqs, cache


def _check(params, prompts, got, seqs, n_new, cfg=CFG):
    for b, p in enumerate(prompts):
        want = _ref_logits(params, np.asarray(seqs[b][:-1], np.int32), cfg)
        rows = want[len(p) - 1:len(p) - 1 + n_new]
        assert np.abs(got[b] - rows).max() < LOGIT_TOL, (b, len(p))


#: prompts of 1, 2 and 3 tokens (shorter than the reach, as long as it,
#: one more), one ending a chunk, one ending a token past a chunk's edge
#: (its last chunk holds only the position the first decode step re-runs),
#: one ending two past it, one several chunks long
PROMPTS = (1, 2, 3, 16, 17, 18, 29)


@pytest.mark.parametrize("chunk", [2, 8], ids=["chunk-2", "chunk-8"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
def test_prefill_in_chunks_then_decode_matches_reference(params, use_pallas,
                                                         chunk):
    """Unequal slots in one batch. With chunks of 2 every chunk's edge lies
    inside the conv's reach of 3: a row reads one column of its own chunk,
    one of the chunk before and one of the chunk before that."""
    prompts = [_ids(10 + n, n) for n in PROMPTS]
    n_new = 12
    got, seqs, _ = _serve_by_hand(params, CFG, prompts, n_new, use_pallas,
                                  chunk)
    _check(params, prompts, got, seqs, n_new)


def test_a_sequence_starts_from_zeros_whatever_the_slot_held(params):
    """The state a request before left in the slot is not read: a pool
    whose conv state is NaN everywhere serves what a fresh one serves, from
    a prefill chunk at offset 0 and from a decode step at position 0."""
    B, mp = 3, CFG.block_size // PSZ
    dirty = lm.init_paged_kv_pool(CFG, B * mp, PSZ, n_slots=B)
    for name in dirty:
        if name.startswith(lm.CONV_ENTRY_PREFIX):
            dirty[name] = jnp.full_like(dirty[name], jnp.nan)
    prompts = [_ids(31, 1), _ids(32, 5), _ids(33, 20)]
    got, seqs, cache = _serve_by_hand(params, CFG, prompts, 6, False, 8,
                                      cache=dirty)
    assert np.isfinite(got).all()
    _check(params, prompts, got, seqs, 6)
    assert all(bool(jnp.isfinite(a).all()) for a in cache.values())


def test_an_idle_row_keeps_its_state_and_writes_nothing(params):
    B, mp = 2, CFG.block_size // PSZ
    cache = lm.init_paged_kv_pool(CFG, B * mp, PSZ, n_slots=B)
    cache = {n: a + 0.25 for n, a in cache.items()}
    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    _, after, _ = lm.decode_step_paged(
        params, jnp.asarray([3, 4]), jnp.asarray([5, 9]),
        jnp.asarray([True, False]), tables, cache, CFG)
    for name, a in after.items():
        if name.startswith(lm.CONV_ENTRY_PREFIX):
            assert np.array_equal(np.asarray(a[1]), np.asarray(cache[name][1]))
            # the live row rolled: its two newest old columns moved up
            assert np.array_equal(np.asarray(a[0, :2]),
                                  np.asarray(cache[name][0, 1:]))
            assert not np.array_equal(np.asarray(a[0, 2]),
                                      np.asarray(cache[name][0, 2]))
        else:
            changed = np.asarray((a != cache[name]).any((0, 2, 3)))
            assert changed.sum() == 1 and changed[0]     # slot 0's page 0


def test_a_wrong_state_column_is_seen(params, monkeypatch):
    """The tolerance is tight enough for the state: a prefill that leaves
    the state one column late (after ``limit - 1``, which the first decode
    step re-runs) is caught."""
    prompts = [_ids(41, 11)]
    exact = lm._rows_taken
    monkeypatch.setattr(lm, "_rows_taken", lambda offset, limit, Pc:
                        jnp.minimum(exact(offset, limit, Pc) + 1, Pc))
    got, seqs, _ = _serve_by_hand(params, CFG, prompts, 4, False, 8)
    want = _ref_logits(params, np.asarray(seqs[0][:-1], np.int32))
    assert np.abs(got[0] - want[10:14]).max() > 5 * LOGIT_TOL


# ------------------------------------------------------------------ 3. kernel

def _einsum_attention(q, kn, vn, kp, vp, tables, pos, Hq, Hkv):
    """The kernel's contract by hand: row b attends positions < pos[b] of
    its table's pages and its fresh row; query head n reads KV head
    n // (Hq // Hkv)."""
    B, _, Cq = q.shape
    D, G = Cq // Hq, Hq // Hkv
    psz = kp.shape[1]
    out = np.zeros((B, 1, Cq), np.float32)
    for b in range(B):
        k = np.concatenate([kp[tables[b]].reshape(-1, Hkv, D)[:pos[b]],
                            kn[b].reshape(1, Hkv, D)])
        v = np.concatenate([vp[tables[b]].reshape(-1, Hkv, D)[:pos[b]],
                            vn[b].reshape(1, Hkv, D)])
        for n in range(Hq):
            s = k[:, n // G] @ q[b, 0, n * D:(n + 1) * D] * D ** -0.5
            p = np.exp(s - s.max())
            out[b, 0, n * D:(n + 1) * D] = (p / p.sum()) @ v[:, n // G]
    assert psz * tables.shape[1] >= pos.max()
    return out


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1.6e-2)])
def test_gqa_kernel_at_four_query_heads_a_kv_head_of_64(dtype, tol):
    """The grouped-query kernel at this family's group and head size (4
    query heads a KV head of 64: a block of 4 x 64 rows, a pool row of 8 x
    64 lanes), at least two blocks of pages deep (the bytes rule's: 128
    tokens of a float32 row, 256 of the cell's bf16 one), an idle slot
    among them; a bf16 pool goes to the MXU as stored."""
    Hq, Hkv, D, psz, mp, B = 32, 8, 64, 16, 24, 3
    pos = np.array([0, 37, 300], np.int32)
    row = Hkv * D * jnp.dtype(dtype).itemsize
    assert paged_pallas.block_pages(psz, mp, row, 2) * psz \
        == (128 if dtype == "float32" else 256)
    r = np.random.default_rng(3)
    f = lambda *s: r.standard_normal(s).astype(np.float32)
    q, kn, vn = f(B, 1, Hq * D), f(B, 1, Hkv * D), f(B, 1, Hkv * D)
    kp, vp = f(2, B * mp, psz, Hkv * D), f(2, B * mp, psz, Hkv * D)
    tables = r.permutation(B * mp).astype(np.int32).reshape(B, mp)
    cast = lambda a: np.asarray(jnp.asarray(a, dtype), np.float32)
    q, kn, vn, kp, vp = map(cast, (q, kn, vn, kp, vp))
    got = paged_pallas.paged_gqa_attention(
        *(jnp.asarray(a, dtype) for a in (q, kn, vn, kp, vp)),
        jnp.asarray(tables), jnp.asarray(pos), n_head=Hq, n_kv_head=Hkv,
        layer=1)
    want = _einsum_attention(q, kn, vn, kp[1], vp[1], tables, pos, Hq, Hkv)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)
    ok, why = paged_pallas.paged_attention_envelope(
        Hq, D, psz, n_kv_head=Hkv, n_pages=40_960)
    assert ok and not why


# ------------------------------------------------------- 4. the published cut

def test_the_preset_is_the_published_first_stage():
    cfg = get_config("lfm2-24b-a2b").model.validate()
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv",
                               "conv", "conv", "full_attention", "conv",
                               "conv", "conv")
    assert cfg.mlp_layer_types == ("dense",) * 2 + ("sparse",) * 8
    assert (cfg.n_embd, cfg.n_head, cfg.kv_heads, cfg.head_dim) \
        == (2048, 32, 8, 64)
    assert (cfg.n_experts, len(cfg.experts_held), cfg.experts_per_token,
            cfg.moe_intermediate_size, cfg.intermediate_size,
            cfg.vocab_size, cfg.conv_reach) \
        == (64, 64, 4, 1536, 11_776, 65_536, 3)
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 64 * 3 * 2048 * 1536 + 2048 * 64 + 64
    want = (8 * conv + 2 * attn + 10 * 2 * 2048 + 2 * 3 * 2048 * 11_776
            + 8 * experts + 65_536 * 2048 + 2048)
    assert n == want and 5.26e9 < n < 5.28e9             # 10.53 GB in bf16
    assert "lm_head" not in shapes                       # the head is tied
    # 4,096 B a context token over the 2 full layers, 98,304 B a slot over
    # the 8 conv layers
    assert page_bytes(cfg, 16) == 16 * 4096
    pool = jax.eval_shape(lambda: lm.init_paged_kv_pool(cfg, 64, 16,
                                                        n_slots=2))
    state = sum(int(np.prod(a.shape)) * 2 for n, a in pool.items()
                if n.startswith(lm.CONV_ENTRY_PREFIX))
    assert state == 2 * 98_304


@pytest.mark.parametrize("bad,word", [
    (dict(tied_head=False), "tied"),
    (dict(conv_reach=0), "conv_reach"),
    (dict(layer_types=("sliding_attention",) + CFG.layer_types[1:]),
     "layer_types"),
    (dict(layer_types=CFG.layer_types[:3]), "layer_types"),
    (dict(mlp_layer_types=("sparse", "dense") + ("sparse",) * 5), "lead"),
    (dict(sliding_window=8), "sliding_window"),
    (dict(shared_intermediate_size=48), "shared"),
    (dict(n_kv_head=3), "group"),
    (dict(experts_held=(9,)), "experts_held"),
], ids=["untied-head", "no-reach", "window-layer", "short-layer-types",
        "dense-after-sparse", "window", "shared-expert", "kv-heads",
        "experts-held"])
def test_validate_names_what_is_wrong(bad, word):
    with pytest.raises(AssertionError, match=word):
        dataclasses.replace(CFG, **bad).validate()


def test_the_other_families_refuse_this_ones_fields():
    gpt = get_config("test-tiny").model
    with pytest.raises(AssertionError, match="conv_reach"):
        dataclasses.replace(gpt, conv_reach=3).validate()
    xcfg = get_config("exaone-moe-tiny").model
    with pytest.raises(AssertionError, match="lfm2_moe"):
        dataclasses.replace(xcfg, router_norm_eps=1e-6).validate()
    with pytest.raises(AssertionError, match="layer_types"):
        dataclasses.replace(xcfg, layer_types=("conv",) * 4).validate()
