"""The exaone_moe family (K-EXAONE-236B-A23B) against its plain reference,
at test widths on the CPU: the whole-sequence forward, chunked prefill and
decode through both kinds of KV state, the share of an expert-parallel
deployment, routing and its near ties, the grouped-query kernel and its
lower bound (interpret mode), the allocator, the precision the tolerance
tells apart, and every refusal. GPT-2's pool, program and route are held
to what they were.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_exaone_moe as ref
from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.models import exaone_moe as xm, layers
from replicatinggpt_tpu.models.families import family, serve_refusals
from replicatinggpt_tpu.ops import paged_pallas
from replicatinggpt_tpu.serve import Engine, EngineConfig
from replicatinggpt_tpu.serve.pages import PagedCachePool, page_bytes
from replicatinggpt_tpu.serve.requests import Request, SamplingParams

#: float32 program against the float32 reference at test widths: rounding
#: of different summation orders reads 1e-6; a wrong mask, position, page or
#: expert moves a logit by 1e-2 and more. Test 7 holds that bfloat16 routing
#: and 8-bit weights both land above it.
LOGIT_TOL = 2e-4

CFG = get_config("exaone-moe-tiny").model       # window 8, block 64
PSZ = 8


@pytest.fixture(scope="module")
def params():
    return xm.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(xm, "FORWARD_BLOCK", 16)
    monkeypatch.setattr(xm, "PREFILL_KV_BLOCK", 16)


def _ids(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         CFG.vocab_size), np.int32)


def _ref_logits(params, seq, cfg=CFG, **kw):
    # in blocks of rows where the length allows, whole otherwise
    out, counts = ref.logits(params, jnp.asarray(seq), ref.spec_of(cfg),
                             row_block=16 if len(seq) % 16 == 0 else 1024,
                             **kw)
    return np.asarray(out), counts


# ------------------------------------------------------- 1. whole sequence

def test_forward_matches_reference_past_the_window(params):
    idx = _ids(1, 2, 48)                       # 6 windows long
    got = np.asarray(xm.forward(params, jnp.asarray(idx), CFG))
    for b in range(2):
        want, _ = _ref_logits(params, idx[b])
        assert np.abs(got[b] - want).max() < LOGIT_TOL


# ------------------------------------- 2. chunked prefill, then paged decode

def _serve_by_hand(params, cfg, prompts, n_new, use_pallas, chunk=8):
    """Prefill each prompt in chunks into its own slot, then decode all
    slots together teacher-forced with seeded tokens: logits (B, n_new, V)
    at positions P-1 .. P+n_new-2 of each row, and the sequences."""
    B = len(prompts)
    mp = cfg.block_size // PSZ
    cache = xm.init_paged_kv_pool(cfg, B * mp, PSZ, n_slots=B)
    tables = np.arange(B * mp, dtype=np.int32).reshape(B, mp)
    rng = np.random.default_rng(5)
    perm = rng.permutation(B * mp).astype(np.int32)     # scattered pages
    tables = perm[tables]
    prefill = jax.jit(lambda *a: xm.prefill_chunk_paged(*a, cfg))
    for b, p in enumerate(prompts):
        n = -(-len(p) // chunk)
        padded = np.zeros((n * chunk,), np.int32)
        padded[:len(p)] = p
        for c in range(n):
            cache = prefill(params, jnp.asarray(padded[None, c * chunk:
                                                       (c + 1) * chunk]),
                            jnp.int32(c * chunk), jnp.int32(len(p)),
                            jnp.asarray(tables[b]), jnp.int32(b), cache)
    step = jax.jit(lambda *a: xm.decode_step_paged(
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
    return np.stack(out, 1), seqs, int(pairs)


@pytest.mark.parametrize("window", [8, 12], ids=["window-1-page",
                                                 "window-1.5-pages"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_matches_reference(params, use_pallas, window):
    """Unequal slots in one batch, over page (8), chunk (8) and window
    boundaries: one prompt inside its first window, one ending on a page
    boundary, one several windows long."""
    cfg = dataclasses.replace(CFG, sliding_window=window)
    prompts = [_ids(11, 3), _ids(12, 16), _ids(13, 29)]
    n_new = 20
    got, seqs, pairs = _serve_by_hand(params, cfg, prompts, n_new,
                                      use_pallas)
    assert pairs >= 0
    for b, p in enumerate(prompts):
        want, _ = _ref_logits(params, np.asarray(seqs[b][:-1], np.int32),
                              cfg)
        rows = want[len(p) - 1:len(p) - 1 + n_new]
        assert np.abs(got[b] - rows).max() < LOGIT_TOL, (b, use_pallas)


# --------------------------------------------------------------- 3. the share

def test_the_share_is_the_uncut_reference_layer_less_the_absent_experts():
    """The reference, given the same share, leaves out the same part."""
    whole = dataclasses.replace(CFG, experts_held=tuple(range(8)))
    p = xm.init_params(jax.random.PRNGKey(3), whole)
    idx = _ids(6, 24)
    full, _ = _ref_logits(p, idx, whole)
    cut = {**p, "layers": [
        {n: (a[:2] if n.startswith("e_") else a) for n, a in lp.items()}
        for lp in p["layers"]]}
    want, _ = _ref_logits(cut, idx, CFG)
    got = np.asarray(xm.forward(cut, jnp.asarray(idx[None]), CFG))[0]
    assert np.abs(got - want).max() < LOGIT_TOL
    assert np.abs(full - want).max() > 10 * LOGIT_TOL    # they DO differ


def test_sliced_head_gives_the_uncut_heads_columns(params):
    idx = _ids(8, 1, 16) % 48
    cut_cfg = dataclasses.replace(CFG, vocab_size=48)
    cut = {**params, "wte": params["wte"][:48],
           "lm_head": params["lm_head"][:, :48]}
    full = np.asarray(xm.forward(params, jnp.asarray(idx), CFG))
    got = np.asarray(xm.forward(cut, jnp.asarray(idx), cut_cfg))
    assert np.array_equal(got, full[..., :48])


# ----------------------------------------------------------------- 4. routing

def test_near_ties_are_counted_inside_the_margin_and_never_taken(params):
    """Choices from a program whose router was nudged: where the chosen
    sets differ the reference counts a near tie inside the margin and a
    mismatch beyond it, and routes by its own scores either way."""
    idx = _ids(9, 40)
    nudged = {**params, "layers": [
        ({**lp, "router": lp["router"] + 2e-3 * jax.random.normal(
            jax.random.PRNGKey(i), lp["router"].shape)}
         if "router" in lp else lp)
        for i, lp in enumerate(params["layers"])]}
    _, theirs = xm.forward(nudged, jnp.asarray(idx[None]), CFG,
                           return_routing=True)
    _, own = xm.forward(params, jnp.asarray(idx[None]), CFG,
                        return_routing=True)
    differ = int((np.sort(np.asarray(theirs), -1)
                  != np.sort(np.asarray(own), -1)).any(-1).sum())
    assert differ > 0, "the nudge flipped nothing: no tie to test"
    plain, _ = _ref_logits(params, idx)
    strict, c0 = _ref_logits(params, idx, choices=theirs[:, 0], margin=0.0)
    assert int(c0["near_ties"]) == 0 and int(c0["mismatches"]) >= differ
    wide, c1 = _ref_logits(params, idx, choices=theirs[:, 0], margin=1.0)
    assert int(c1["mismatches"]) == 0
    assert int(c1["near_ties"]) == int(c0["mismatches"])
    # its own routing whatever it was handed
    assert np.array_equal(strict, plain) and np.array_equal(wide, plain)
    # the program's own choices: the chosen sets are equal everywhere
    _, c2 = _ref_logits(params, idx, choices=own[:, 0], margin=0.0)
    assert int(c2["near_ties"]) == 0 and int(c2["mismatches"]) == 0


# ------------------------------------------------------------------ 5. kernel

# the kernel addresses (layer, page) of a stacked pool: the cases stack
# three layers of different values and read the middle one, which alone
# the einsum reference is handed
KERNEL_LAYER = 1


def _kernel_case(seed, B, W, Hq, Hkv, D, psz, mp, pos, window, page0=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    N = B * mp
    q = jax.random.normal(ks[0], (B, W, Hq * D))
    kn = jax.random.normal(ks[1], (B, W, Hkv * D))
    vn = jax.random.normal(ks[2], (B, W, Hkv * D))
    kp = jax.random.normal(ks[3], (3, N, psz, Hkv * D))
    vp = jax.random.normal(ks[4], (3, N, psz, Hkv * D))
    tables = jnp.asarray(np.random.default_rng(seed).permutation(N)
                         .astype(np.int32).reshape(B, mp))
    return q, kn, vn, kp, vp, tables, jnp.asarray(pos, jnp.int32)


def _einsum_attention(q, kn, vn, kp, vp, tables, pos, Hq, Hkv, window,
                      page0):
    """Scatter-then-attend in plain einsum: row j of slot b at pos + j
    attends stale positions < pos of its table and fresh rows 0..j, all
    above pos + j - window."""
    B, W, _ = q.shape
    kp, vp = kp[KERNEL_LAYER], vp[KERNEL_LAYER]
    psz = kp.shape[1]
    mp = tables.shape[1]
    D = kp.shape[-1] // Hkv
    G = Hq // Hkv
    out = np.zeros((B, W, Hq * D), np.float32)
    for b in range(B):
        p0 = 0 if page0 is None else int(page0[b])
        kpos = p0 * psz + np.arange(mp * psz)
        ks = np.concatenate([np.asarray(kp)[np.asarray(tables[b])]
                             .reshape(mp * psz, Hkv, D),
                             np.asarray(kn[b]).reshape(W, Hkv, D)])
        vs = np.concatenate([np.asarray(vp)[np.asarray(tables[b])]
                             .reshape(mp * psz, Hkv, D),
                             np.asarray(vn[b]).reshape(W, Hkv, D)])
        kpos = np.concatenate([kpos, int(pos[b]) + np.arange(W)])
        stale = np.concatenate([np.ones(mp * psz, bool),
                                np.zeros(W, bool)])
        for j in range(W):
            qp = int(pos[b]) + j
            ok = np.where(stale, kpos < int(pos[b]), kpos <= qp)
            if window:
                ok &= kpos > qp - window
            for n in range(Hq):
                qv = np.asarray(q[b, j]).reshape(Hq, D)[n]
                s = ks[:, n // G] @ qv * D ** -0.5
                s = np.where(ok, s, -np.inf)
                w = np.exp(s - s.max())
                w /= w.sum()
                out[b, j, n * D:(n + 1) * D] = w @ vs[:, n // G]
    return out


# query heads, KV heads and head size: KV heads of 32 lanes, and LFM2's
# geometry in small (KV heads of 64 at 64-lane offsets, 4 query heads a KV
# head): a block is ONE pass over the whole row in both
GQA_HEAD_IDS = {"kv32": (4, 2, 32), "kv64": (16, 4, 64)}
GQA_HEADS = list(GQA_HEAD_IDS.values())


@pytest.mark.parametrize("W,window,pos,mp", [
    (1, 0, [0, 5, 37], 6),       # full layer: idle slot, mid page, deep
    (1, 8, [3, 8, 41], 6),       # window = 1 page; inside the first window
    (1, 12, [3, 20, 47], 6),     # window not a multiple of the page
    (2, 12, [1, 16, 30], 6),     # two fresh rows under the band
    # a table of 20 pages of 8 is walked in blocks of 16 (128 tokens) and
    # ends in a short one: an idle slot, the frontier on the second
    # block's first position and on its last
    (1, 0, [0, 129, 159], 20),
    (1, 12, [3, 133, 150], 20),  # the band's lower bound inside a block,
    (2, 12, [1, 128, 140], 20),  # and across the two blocks' edge
    # a slot's loop is as long as its own live blocks: none (idle), one
    # live slot between two idle ones (the fetch-ahead crosses them),
    # every block of every slot, and a window whose only live block is the
    # table's SECOND (the loop starts behind the lower bound)
    (1, 0, [0, 159, 0], 20),
    (8, 0, [160, 160, 160], 20),
    (1, 12, [0, 150, 0], 20),
], ids=["full", "window-1-page", "window-1.5-pages", "two-rows",
        "blocks-full", "blocks-window", "blocks-two-rows",
        "blocks-live-between-idle", "blocks-all-live-w8",
        "blocks-window-second-block-only"])
@pytest.mark.parametrize("heads", GQA_HEADS, ids=list(GQA_HEAD_IDS))
def test_gqa_kernel_and_its_lower_bound_against_einsum(lane_tile_blocks, W,
                                                       window, pos, mp,
                                                       heads):
    Hq, Hkv, D = heads
    psz = 8
    assert (paged_pallas.block_pages(psz, mp, Hkv * D * 4, 2) < mp) \
        == (mp > 16)
    q, kn, vn, kp, vp, tables, pos = _kernel_case(3, 3, W, Hq, Hkv, D, psz,
                                                  mp, pos, window)
    got = paged_pallas.paged_gqa_attention(
        q, kn, vn, kp, vp, tables, pos, n_head=Hq, n_kv_head=Hkv,
        layer=KERNEL_LAYER, attn_window=window,
        name="swa_test" if window else "full_test")
    want = _einsum_attention(q, kn, vn, kp, vp, tables, pos, Hq, Hkv,
                             window, None)
    assert np.abs(np.asarray(got) - want).max() < 1e-5


@pytest.mark.parametrize("psz,mp,window,pos,dtype,tol", [
    (8, 3, 12, [45, 18], "float32", 1e-5),
    # K-EXAONE's ring: 9 pages of 16 under a window of 128 are a block of
    # 8 and a short one; the lower bound falls inside the first block
    (16, 9, 128, [300, 140, 1000], "float32", 1e-5),
    # the cell's storage: bf16 K goes to the MXU as it is; what is left
    # is the output's own rounding
    (16, 9, 128, [300, 140, 1000], "bfloat16", 1.6e-2),
    # idle slots around a live one: their loops take no turn
    (16, 9, 128, [0, 1000, 0, 0, 140], "float32", 1e-5),
], ids=["ring-of-3", "ring-of-9-in-two-blocks", "ring-of-9-bf16",
        "ring-of-9-live-between-idle"])
@pytest.mark.parametrize("heads", GQA_HEADS, ids=list(GQA_HEAD_IDS))
def test_gqa_kernel_walks_a_ring_from_page0(lane_tile_blocks, psz, mp,
                                            window, pos, dtype, tol, heads):
    """A window layer's ring: the table's first entry is absolute page
    ``page0`` of the slot, not page 0."""
    Hq, Hkv, D = heads
    page0 = [max(p - window + 1, 0) // psz for p in pos]
    *rows, tables, pos = _kernel_case(4, len(pos), 1, Hq, Hkv, D, psz, mp,
                                      pos, window)
    rows = [a.astype(dtype) for a in rows]
    got = paged_pallas.paged_gqa_attention(
        *rows, tables, pos, n_head=Hq, n_kv_head=Hkv,
        layer=KERNEL_LAYER, attn_window=window,
        page0=jnp.asarray(page0, jnp.int32),
        name="swa_test")
    want = _einsum_attention(*(np.asarray(a, np.float32) for a in rows),
                             tables, pos, Hq, Hkv, window, page0)
    assert got.dtype == jnp.dtype(dtype)
    assert np.abs(np.asarray(got, np.float32) - want).max() < tol


@pytest.mark.parametrize("mp,window,pos", [
    (9, 128, [300, 140, 1000]),       # K-EXAONE's ring: blocks of 8 + 1
    (16, 200, [400, 1000, 77]),       # 8 + 5, and 5 in a first block
    (16, 240, [255, 1000]),           # 8 + 7 and 8 + 8
], ids=["ring-of-9", "window-200", "window-240"])
@pytest.mark.parametrize("heads", GQA_HEADS, ids=list(GQA_HEAD_IDS))
def test_a_windows_blocks_wait_for_their_owned_pages_bytes(
        dma_interpret, lane_tile_blocks, mp, window, pos, heads):
    """A window layer's ring walk under the interpreter that models each
    copy and its semaphore: a block is waited by its owned pages' bytes,
    and the rings' blocks own 1, 5, 7 and 8 of their 8 pages (every bit
    of the waits); a wait short of them would read unlanded rows."""
    test_gqa_kernel_walks_a_ring_from_page0(
        lane_tile_blocks, 16, mp, window, pos, "float32", 1e-5, heads)


@pytest.mark.parametrize("layout", ["runs", "mixed"])
@pytest.mark.parametrize("psz,mp,W,window,pos,ring", [
    # a band of 40 behind two fresh rows: owned pages 10-14 of a block of
    # 16 (a run that starts past the block's first page), 13-18 across
    # two blocks
    (8, 20, 2, 40, [1, 120, 150], False),
    # K-EXAONE's ring: 9 pages of 16 from page0, a block of 8 and one of 1
    (16, 9, 1, 128, [300, 140, 1000], True),
], ids=["window-w2", "ring-of-9"])
def test_gqa_run_blocks_match_their_pages_scattered(
        lane_tile_blocks, page_runs, psz, mp, W, window, pos, ring, layout):
    """The grouped-query kernel over tables whose blocks are runs of the
    pool (``page_runs``): a window behind its lower bound and a window
    layer's ring from ``page0``, whose owned pages are a run that may
    start past its block's first page. The output is BIT-equal to that of
    the same pages scattered, and the walk marks the runs by the rule."""
    Hq, Hkv, D = GQA_HEADS[0]
    B = len(pos)
    q, kn, vn, kp, vp, _, pos = _kernel_case(6, B, W, Hq, Hkv, D, psz, mp,
                                             pos, window)
    page0 = jnp.asarray([max(int(p) - window + 1, 0) // psz for p in pos]
                        if ring else [0] * B, jnp.int32)
    P, _ = paged_pallas._walk_shape(mp, psz, Hkv * D * 4, 2)
    tables = page_runs.tables(B, mp, P, layout)
    moved, (kp2, vp2) = page_runs.scatter(tables, [kp, vp], seed=2)
    outs = [np.asarray(paged_pallas.paged_gqa_attention(
        q, kn, vn, jnp.asarray(k), jnp.asarray(v), jnp.asarray(t), pos,
        n_head=Hq, n_kv_head=Hkv, layer=KERNEL_LAYER, attn_window=window,
        page0=page0 if ring else None, name="swa_test"))
        for t, k, v in ((tables, kp, vp), (moved, kp2, vp2))]
    np.testing.assert_array_equal(*outs)
    want = _einsum_attention(q, kn, vn, kp, vp, tables, pos, Hq, Hkv,
                             window, np.asarray(page0) if ring else None)
    assert np.abs(outs[0] - want).max() < 1e-5
    walk = paged_pallas._blocked_walk(
        jnp.asarray(tables), paged_pallas.gqa_owned_pages(
            pos, page0, mp, psz, window), psz, Hkv * D * 4, 2)
    word = np.asarray(walk[6])
    first = word >> paged_pallas.RUN_SHIFT
    assert first.tolist() == page_runs.words(np.asarray(walk[0]), P)
    if layout == "runs":
        live = (word & paged_pallas.COUNT_MASK) > 0
        assert (first[live] > 0).all()
        assert (first > 1).any() != ring    # the band's run past a first


def test_pages_behind_the_window_are_not_owned():
    owned = np.asarray(paged_pallas.gqa_owned_pages(
        jnp.asarray([41, 3], jnp.int32), jnp.zeros((2,), jnp.int32), 8, 8,
        12))
    # pos 41, window 12: rows read 30..40 -> pages 3, 4, 5 (40 is in 5)
    assert owned[0].tolist() == [False] * 3 + [True] * 3 + [False] * 2
    assert owned[1].tolist() == [True] + [False] * 7


# --------------------------------------------------------------- 6. allocator

def _pool(n_slots=3, n_pages=0):
    return PagedCachePool(CFG, n_slots, page_size=PSZ, n_pages=n_pages,
                          prefix_cache=False)


def test_window_state_does_not_grow_with_context():
    pool = _pool()
    ring = xm.ring_pages(CFG, PSZ)
    assert ring * PSZ <= CFG.sliding_window + PSZ       # 128 + W to pages
    for j in range(len(CFG.window_layers)):
        assert pool.cache[f"wk{j}"].shape == (1, 3 * ring, PSZ,
                                              CFG.kv_channels)
    kinds = pool.bytes_by_kind()
    before = {n: a.shape for n, a in pool.cache.items()}
    short = pool.acquire("a", _ids(1, 4), 4)
    long_ = pool.acquire("b", _ids(2, 40), 20)
    assert {n: a.shape for n, a in pool.cache.items()} == before
    assert pool.bytes_by_kind() == kinds and set(kinds) == {"pages",
                                                            "window"}
    held = lambda adm: int((pool.tables[adm.slot] != 0).sum())
    assert held(long_) > held(short)        # pages follow the context


# --------------------------------------------------------------- 7. precision

# ---------------------------------------------------------------- the engine

@pytest.fixture()
def kernel_on_cpu(monkeypatch):
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)


ECFG = EngineConfig(pool_size=3, page_size=PSZ, prefill_chunk=16,
                    paged_kernel=True, prefix_cache=False, max_queue=16)


# ---------------------------------------------------------------- 8. refusals

def test_the_decode_kernel_is_routed_and_a_quantised_pool_is_not(
        params, kernel_on_cpu):
    assert Engine(params, CFG, ECFG).kernel_route.decode == "pallas"
    ok, why = paged_pallas.paged_attention_envelope(
        4, 32, PSZ, n_kv_head=2, kv_quant="int8")
    assert not ok and "gqa_kv_quant" in why


@pytest.mark.parametrize("bad,word", [
    (dict(n_kv_head=3), "group"),
    (dict(layer_types=("full_attention",) * 3), "layer_types"),
    (dict(experts_held=(9,)), "experts_held"),
    (dict(tied_head=True), "untied"),
    (dict(sliding_window=0), "window"),
], ids=["kv-heads", "layer-types", "experts-held", "tied-head", "window"])
def test_validate_names_what_is_wrong(bad, word):
    with pytest.raises(AssertionError, match=word):
        dataclasses.replace(CFG, **bad).validate()


def test_gpt2_fields_are_refused_on_the_gpt_family():
    gpt = get_config("test-tiny").model
    with pytest.raises(AssertionError, match="n_kv_head"):
        dataclasses.replace(gpt, n_kv_head=1).validate()


# --------------------------------------------- GPT-2 is what it was (test 8)

def test_gpt2_pool_program_names_and_route_unchanged(kernel_on_cpu):
    from replicatinggpt_tpu.models import gpt
    from replicatinggpt_tpu.serve import engine as E
    cfg = dataclasses.replace(get_config("test-tiny").model, n_embd=64,
                              decode_cache_layout="packed")
    p = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(p, cfg, EngineConfig(pool_size=2, page_size=8,
                                      paged_kernel=True))
    assert set(eng.pool.cache) == {"k", "v"}
    assert eng.pool.cache["k"].shape == (cfg.n_layer, eng.pool.n_pages, 8,
                                         cfg.n_embd)
    assert eng.pool.pages is eng.pool.cache and eng._launch_extra is None
    assert set(eng.pool.bytes_by_kind()) == {"pages"}
    r = eng.kernel_route.summary()
    assert (r["route"], r["window"], r["reasons"]) == ("pallas", "pallas",
                                                       [])
    li = eng._launch_inputs()
    state = [jnp.asarray(a) for a in (eng._tok, eng._pos, eng._active,
                                      eng._budget)]
    text = str(E._engine_decode_window.trace(
        p, *state, li[0], eng._z_life, li[1], eng.pool.cache, eng._rngs,
        *li[2:], cfg, k=1, use_pallas=True).jaxpr)
    assert "paged_window_attention" in text and "swa_" not in text
    assert E._engine_decode_window.__name__ == "_engine_decode_window"
    assert family(cfg).step_counters == ()


# ------------------------------------- more requests than slots, and kills

def test_the_route_asks_the_family_and_names_no_family():
    """``decide_kernel_route`` reads the windowed steps' fitness off
    ``family(cfg)``: GPT-2 answers it, this family has no windowed step
    (None)."""
    import inspect
    from replicatinggpt_tpu.serve import engine as E
    assert "cfg.family" not in inspect.getsource(E.decide_kernel_route)
    qcfg = EngineConfig().quant()
    fam = family(CFG)
    assert fam.window_kernel_ok(CFG, PSZ, 16, 4, None, qcfg) is None
    gcfg = dataclasses.replace(get_config("test-tiny").model, n_embd=64,
                               decode_cache_layout="packed")
    assert family(gcfg).window_kernel_ok(gcfg, 8, 16, 4, None,
                                         qcfg) in (True, False)
