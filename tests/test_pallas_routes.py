"""Unified Pallas kernel family (ISSUE 20): every engine route —
decode, mixed prefill+decode windows, speculative verify — through ONE
parameterized kernel (`ops/paged_pallas.paged_window_attention`), with
in-kernel dequant for int8/fp8 at page/head granularity, a shard_map
wrapper for >1 (data, model) meshes, and the route decision made once
per engine and exported (`metrics_summary()["kernel_route"]`).

Acceptance pinned here:
- route matrix: `kernel_route == "pallas"` (empty reasons) for every
  shipped configuration — quantized, weight-quantized, W8A8, sharded;
- interpret-mode parity of the windowed kernel vs the XLA gather
  reference for fp8 KV and head-granularity scales (the old
  documented fallback seams), unsharded and under shard_map;
- engine greedy-stream parity with the XLA route for mixed windows,
  speculative verify, and a sharded 2x2 engine;
- zero recompiles across a paged-kernel replay with admissions.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.models.gpt import init_params
from replicatinggpt_tpu.serve import (Engine, EngineConfig, ReplayConfig,
                                      Request, SamplingParams, run_replay)

# three layers: the kernel and the gather address the stacked pool by
# (layer, page), so a program that read layer 0's pages (or scales) for
# every layer would stream other tokens than the XLA route
CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=3, n_head=2,
                  n_embd=64, dropout=0.0, attn_dropout=0.0,
                  dtype="float32", decode_cache_layout="packed")


@pytest.fixture(scope="module")
def p64():
    return init_params(jax.random.PRNGKey(1), CFG)


@pytest.fixture
def kernel_backend(monkeypatch):
    """CPU runs the kernels in interpret mode; route predicates gate on
    the backend check, so parity tests force it open."""
    from replicatinggpt_tpu.ops import paged_pallas
    monkeypatch.setattr(paged_pallas, "_paged_attn_backend_ok",
                        lambda: True)


def _greedy(rid, prompt, max_new=6):
    return Request(id=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new,
                   sampling=SamplingParams(greedy=True))


def _run(params, ecfg, reqs, cfg=CFG, drafter=None):
    eng = Engine(params, cfg, ecfg, drafter=drafter)
    for r in reqs:
        assert eng.submit(r) is None
    return {r.id: r.tokens for r in eng.drain()}, eng


# ---------------------------------------------------------------------------
# the route matrix: Pallas everywhere (tier-1)
# ---------------------------------------------------------------------------

def test_kernel_route_matrix_every_shipped_config(kernel_backend):
    """THE ISSUE 20 acceptance: `decide_kernel_route` returns
    route == "pallas" with empty reasons for every shipped
    configuration — no silent XLA fallback is left in the matrix."""
    from replicatinggpt_tpu.parallel.mesh import make_serve_mesh
    from replicatinggpt_tpu.serve.engine import decide_kernel_route
    mesh22 = make_serve_mesh(2, 2)
    matrix = [
        (EngineConfig(paged_kernel=True), None),
        (EngineConfig(paged_kernel=True, kv_quant="int8"), None),
        (EngineConfig(paged_kernel=True, kv_quant="int8",
                      quant_granularity="head"), None),
        (EngineConfig(paged_kernel=True, kv_quant="fp8"), None),
        (EngineConfig(paged_kernel=True, kv_quant="fp8",
                      quant_granularity="head"), None),
        (EngineConfig(paged_kernel=True, weight_quant="int8"), None),
        (EngineConfig(paged_kernel=True, weight_quant="fp8"), None),
        (EngineConfig(paged_kernel=True, weight_quant="int8",
                      act_quant="int8"), None),
        (EngineConfig(paged_kernel=True, decode_window=8), None),
        (EngineConfig(paged_kernel=True, mesh_data=2, mesh_model=2,
                      kv_quant="int8"), mesh22),
        (EngineConfig(paged_kernel=True, mesh_data=2, mesh_model=2,
                      kv_quant="fp8", quant_granularity="head"), mesh22),
    ]
    for ecfg, mesh in matrix:
        route = decide_kernel_route(CFG, ecfg, ecfg.quant(),
                                    page_size=8, n_pages=16, itemsize=4,
                                    mesh=mesh)
        assert route.route == "pallas", (ecfg, route)
        assert route.reasons == (), (ecfg, route)
        assert route.window == "pallas", (ecfg, route)
        assert route.decode == "pallas", (ecfg, route)
        assert route.sharded == (mesh is not None), (ecfg, route)
    # the knob still exists, and an off-route is attributable
    off = decide_kernel_route(CFG, EngineConfig(), EngineConfig().quant(),
                              page_size=8, n_pages=16, itemsize=4,
                              mesh=None)
    assert off.route == "xla" and off.decode == "xla"
    assert "paged_kernel_off" in off.reasons
    # indivisible mesh geometry names itself
    odd = decide_kernel_route(
        CFG, EngineConfig(paged_kernel=True, mesh_data=2, mesh_model=2),
        EngineConfig().quant(), page_size=8, n_pages=15, itemsize=4,
        mesh=mesh22)
    assert odd.route == "xla" and "mesh_indivisible" in odd.reasons


# ---------------------------------------------------------------------------
# interpret-mode kernel parity: the old fallback seams, in-kernel now
# ---------------------------------------------------------------------------

def _window_ref(q, kn, vn, kp, vp, tables, pos, n_head):
    """XLA-free reference: gather the logical view, append the fresh
    window rows causally, softmax per head in f64-free numpy."""
    B, W, C = q.shape
    D = C // n_head
    mp = tables.shape[1]
    psz = kp.shape[1]
    out = np.zeros((B, W, C), np.float32)
    for b in range(B):
        hk = kp[tables[b]].reshape(mp * psz, C)[: pos[b]]
        hv = vp[tables[b]].reshape(mp * psz, C)[: pos[b]]
        for j in range(W):
            kk = np.concatenate([hk, kn[b, : j + 1]], 0)
            vv = np.concatenate([hv, vn[b, : j + 1]], 0)
            for h in range(n_head):
                sl = slice(h * D, (h + 1) * D)
                s = kk[:, sl] @ q[b, j, sl] * D ** -0.5
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, j, sl] = p @ vv[:, sl]
    return out


# geometries of the walk: (W, page, table length, positions). "one-block"
# is a table the kernel covers in ONE block a slot; the others make a
# slot's loop walk blocks of P pages (``paged_pallas.block_pages`` under
# ``lane_tile_blocks``: 8 pages of 16, 16 of 8; under the bytes rule these
# narrow rows' blocks cover a table or most of it), with the frontier in
# the middle of a block, on a
# block's first and last position, an idle slot (no turn of the loop), a
# table P does not divide (9, 20), slots whose every block is live,
# live slots between idle ones (the call's first live block is not slot
# 0's, and the fetch-ahead crosses one idle slot and two), and slots whose
# last block owns 1, P - 1 and P of its 8 pages (7 in a slot's only block)
WALKS = {
    "one-block": (4, 8, 4, [17, 9, 0]),
    "mid-block": (1, 16, 24, [200, 70, 0]),
    "block-edges": (1, 16, 24, [128, 129, 255, 256]),
    "table-of-9": (1, 16, 9, [143, 130, 127, 5]),
    "w8-short-last-block": (8, 8, 20, [150, 128, 3, 0]),
    "every-block-live": (1, 16, 24, [384, 383]),
    "live-between-idle": (1, 16, 24, [0, 300, 0, 0, 40, 0]),
    "w8-live-between-idle": (8, 8, 20, [0, 0, 152, 0, 9]),
    "six-heads-w1": (1, 16, 24, [200, 70, 0]),
    "six-heads-w8": (8, 8, 20, [150, 128, 3, 0]),
    "last-block-owns-1-7-8": (1, 16, 24, [100, 240, 256, 129, 0]),
}

# heads and head size of a walk's rows: two of 32 (one 64-lane row)
# unless named here; six of 64 span three 128-lane slabs, which a block
# takes in ONE pass all the same
WALK_HEADS = {"six-heads-w1": (6, 64), "six-heads-w8": (6, 64)}


def _heads(walk):
    return WALK_HEADS.get(walk, (2, 32))


# the pools are STACKED, three layers of different values, and every
# parity below reads the middle one: the reference is handed that layer
# alone, so a kernel that read layer 0 (or its scales) would miss it
N_LAYERS, LAYER = 3, 1


def _window_inputs(seed=0, walk="one-block"):
    rng = np.random.default_rng(seed)
    W, psz, mp, pos = WALKS[walk]
    pos = np.array(pos, np.int32)          # incl. the fresh-only row
    H, D = _heads(walk)
    B, C = len(pos), H * D
    N = B * mp
    tables = rng.permutation(N).reshape(B, mp).astype(np.int32)
    mk = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return (mk(B, W, C), mk(B, W, C), mk(B, W, C),
            mk(N_LAYERS, N, psz, C), mk(N_LAYERS, N, psz, C), tables, pos)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1.6e-2)])
@pytest.mark.parametrize("walk_name", list(WALKS))
def test_blocked_walk_matches_gather_reference(lane_tile_blocks, walk_name,
                                               dtype, tol,
                                               blocks="lane-tile"):
    """The walk in blocks of pages against the XLA-free gather
    reference: every geometry of ``WALKS``, W = 1 and W = 8, a float32
    pool and a bf16 one (the cells': K goes to the MXU as stored; the
    output's own rounding is the tolerance), in blocks of one lane tile
    (``blocks="bytes"``: the program's own); and the scalars the kernel
    fetches by: a whole number of blocks a slot, the owned mask padded
    with unowned entries, each slot's own live blocks in rising order
    with their count, the rank of its first among the call's, the next
    slot that has one, and a block's owned pages as the bits of its
    words."""
    from replicatinggpt_tpu.ops import paged_pallas as pp
    q, kn, vn, kp, vp, tables, pos = _window_inputs(5, walk_name)
    H = _heads(walk_name)[0]
    q, kn, vn, kp, vp = (np.asarray(jnp.asarray(a, dtype), np.float32)
                         for a in (q, kn, vn, kp, vp))
    out = pp.paged_window_attention(
        *(jnp.asarray(a, dtype) for a in (q, kn, vn, kp, vp)),
        jnp.array(tables), jnp.array(pos), n_head=H, layer=LAYER)
    assert out.dtype == jnp.dtype(dtype)
    ref = lambda l: _window_ref(q, kn, vn, kp[l], vp[l], tables,  # noqa: E731
                                pos, H)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref(LAYER),
                               atol=tol, rtol=tol)
    # the layers differ by far more than the tolerance: layer 0 would fail
    assert np.abs(ref(LAYER) - ref(0)).max() > 0.1
    psz, mp = kp.shape[2], tables.shape[1]
    owned = pp.gqa_owned_pages(jnp.array(pos), jnp.zeros_like(pos), mp,
                               psz, 0)
    row = kp.shape[3] * jnp.dtype(dtype).itemsize
    walk = pp.window_walk(jnp.array(tables), jnp.array(pos), psz, row, 2)
    P, nb = pp._walk_shape(mp, psz, row, 2)
    tokens = -(-pp.BLOCK_BYTES // (2 * row * 128)) * 128
    assert P == min(-(-tokens // psz), mp) and nb == -(-mp // P)
    if blocks == "lane-tile":
        assert (nb > 1) == (walk_name != "one-block")
    # one walk for every layer of a step: handed in at another layer, it
    # gives that layer's output
    handed = pp.paged_window_attention(
        *(jnp.asarray(a, dtype) for a in (q, kn, vn, kp, vp)),
        jnp.array(tables), jnp.array(pos), n_head=H, layer=2, walk=walk)
    np.testing.assert_allclose(np.asarray(handed, np.float32), ref(2),
                               atol=tol, rtol=tol)
    assert len(walk) + 1 == pp.N_WALK          # and the pool's layer
    table, order, count, rank, nxt, bits, n_owned = (np.asarray(a)
                                                      for a in walk)
    own = table >= 0             # an unowned entry reads -1
    assert table.shape == (len(pos), nb * P)
    assert (own[:, :mp] == np.asarray(owned)).all() and not own[:, mp:].any()
    assert (table[:, :mp][own[:, :mp]] == tables[own[:, :mp]]).all()
    # a block's owned pages, counted: what its waits add up to; above the
    # count, where they are one run of the pool, the first's index + 1
    assert (n_owned & pp.COUNT_MASK).tolist() \
        == own.reshape(-1, P).sum(-1).tolist()
    runs = [0] * len(n_owned)
    for i, blk in enumerate(table.reshape(-1, P)):
        idx = np.flatnonzero(blk >= 0)
        if idx.size and (np.diff(idx) == 1).all() \
                and (np.diff(blk[idx]) == 1).all():
            runs[i] = int(idx[0]) + 1
    assert (n_owned >> pp.RUN_SHIFT).tolist() == runs
    # a block is live if it owns a page: a slot's loop takes its own live
    # blocks in rising order, and the live blocks hand the double buffer
    # on to one another, slot after slot
    live = own.reshape(len(pos), nb, P).any(-1)
    assert count.tolist() == live.sum(1).tolist()
    assert count.tolist() == pp.live_blocks(pos, psz, P).tolist()
    for b, got in enumerate(order.reshape(len(pos), nb)):
        assert got[:count[b]].tolist() == np.flatnonzero(live[b]).tolist()
    # page j of a block is bit j % 32 of its word j // 32
    nw = -(-P // 32)
    words = bits.view(np.uint32).reshape(len(pos), nb, nw)
    for b, p, j in np.ndindex(len(pos), nb, P):
        assert (words[b, p, j // 32] >> (j % 32)) & 1 == own[b, p * P + j]
    assert rank.tolist() == (np.cumsum(count) - count).tolist()
    has = np.flatnonzero(count)
    assert nxt[has].tolist() == has[1:].tolist() + [-1]
    assert all(nxt[b] == (has[has > b].tolist() + [-1])[0]
               for b in range(len(pos)))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1.6e-2)])
@pytest.mark.parametrize("walk_name", list(WALKS))
def test_blocked_walk_in_the_programs_own_blocks(walk_name, dtype, tol):
    """The same walks in the blocks ``BLOCK_BYTES`` gives (at these
    narrow rows a block covers a slot's table, or most of it)."""
    test_blocked_walk_matches_gather_reference(None, walk_name, dtype, tol,
                                               blocks="bytes")


QUANT_WALKS = [
    ("int8", "head", "one-block"), ("fp8", "page", "one-block"),
    ("fp8", "head", "one-block"), ("int8", "head", "w8-short-last-block"),
    ("int8", "head", "mid-block"), ("fp8", "page", "table-of-9"),
    ("int8", "page", "mid-block"), ("int8", "page", "live-between-idle"),
    ("int8", "head", "live-between-idle"),
    ("int8", "page", "w8-live-between-idle"),
    ("int8", "head", "every-block-live"), ("int8", "head", "six-heads-w1"),
    ("fp8", "head", "six-heads-w8"), ("int8", "page", "six-heads-w8")]


@pytest.mark.parametrize("kv_dtype,gran,walk", QUANT_WALKS)
def test_windowed_kernel_quantized_parity(lane_tile_blocks, kv_dtype, gran,
                                          walk):
    """fp8 KV and head-granularity scales were the documented XLA
    seams — the per-head scale-lane selection and the saturating e4m3
    fake-quant now run inside the accumulation loop, parity-pinned
    against the dequantized gather reference, over one block and over
    a walk of several: a slot's scales ride as ONE row operand, cut by
    block inside its loop (a block of P * page tokens, whichever rule
    sized it)."""
    from replicatinggpt_tpu.ops import paged_pallas as pp
    from replicatinggpt_tpu.quant.kv import (fake_quantize_rows,
                                             quantize_rows)
    q, kn, vn, kp, vp, tables, pos = _window_inputs(walk=walk)
    H, D = _heads(walk)
    kq, ks = quantize_rows(jnp.array(kp), kv_dtype, H, gran)
    vq, vs = quantize_rows(jnp.array(vp), kv_dtype, H, gran)
    expand = (lambda s: np.asarray(s)[..., None] if gran == "page"
              else np.asarray(jnp.repeat(s, D, -1)))
    kpf = np.asarray(kq, np.float32).astype(np.float32) * expand(ks)
    vpf = np.asarray(vq, np.float32).astype(np.float32) * expand(vs)
    knf = np.asarray(fake_quantize_rows(jnp.array(kn), kv_dtype, H, gran))
    vnf = np.asarray(fake_quantize_rows(jnp.array(vn), kv_dtype, H, gran))
    ref = _window_ref(q, knf, vnf, kpf[LAYER], vpf[LAYER], tables, pos, H)
    out = pp.paged_window_attention(
        jnp.array(q), jnp.array(knf), jnp.array(vnf), kq, vq,
        jnp.array(tables), jnp.array(pos), n_head=H, layer=LAYER,
        k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4,
                               rtol=1e-4)
    # the right pages under another layer's scales are told apart
    wrong = _window_ref(q, knf, vnf,
                        np.asarray(kq[LAYER], np.float32) * expand(ks)[0],
                        vpf[LAYER], tables, pos, H)
    if (pos > 0).any():
        assert np.abs(wrong - ref).max() > 1e-3


@pytest.mark.parametrize("kv_dtype,gran,walk", QUANT_WALKS)
def test_windowed_kernel_quantized_parity_in_the_programs_own_blocks(
        kv_dtype, gran, walk):
    """The same, in the blocks ``BLOCK_BYTES`` gives: an int8 or fp8
    row's block is longer still, and its scales operand follows it."""
    test_windowed_kernel_quantized_parity(None, kv_dtype, gran, walk)


@pytest.mark.parametrize("walk", ["one-block", "mid-block",
                                  "w8-short-last-block",
                                  "live-between-idle",
                                  "w8-live-between-idle",
                                  "every-block-live", "six-heads-w1",
                                  "six-heads-w8"])
def test_owned_subsets_partials_merge_to_the_reference(lane_tile_blocks,
                                                       walk):
    """``owned`` hands a call an ARBITRARY subset of a slot's pages (a
    shard's, under the ``shard_map`` wrapper): its loop takes the blocks
    that hold one, wherever they lie, and ``fold=False`` returns the raw
    partials. Two calls that split every slot's prefix between them at
    random (a slot's pages may all fall to one of them: the other's loop
    takes no turn there) merge, the wrapper's way, to the reference."""
    from replicatinggpt_tpu.ops import paged_pallas as pp
    q, kn, vn, kp, vp, tables, pos = _window_inputs(seed=7, walk=walk)
    H, (B, W, C) = _heads(walk)[0], q.shape
    psz, mp = kp.shape[2], tables.shape[1]
    prefix = np.asarray(pp.gqa_owned_pages(
        jnp.array(pos), jnp.zeros_like(pos), mp, psz, 0))
    mine = np.random.default_rng(11).random((B, mp)) < 0.4
    mine[np.flatnonzero(pos)[0]] = True        # one slot whole to one call
    halves = [pp.paged_window_attention(
        *map(jnp.array, (q, kn, vn, kp, vp, tables, pos)), n_head=H,
        layer=LAYER, owned=jnp.array(prefix & own), fold=False)
        for own in (mine, ~mine)]
    (a0, m0, l0), (a1, m1, l1) = halves
    m = jnp.maximum(m0, m1)
    c0, c1 = jnp.exp(m0 - m), jnp.exp(m1 - m)
    rep = lambda c: jnp.repeat(c[..., :H], C // H, axis=-1)  # noqa: E731
    out = pp._fold_fresh_window(a0 * rep(c0) + a1 * rep(c1), m,
                                l0 * c0 + l1 * c1, jnp.array(q),
                                jnp.array(kn), jnp.array(vn), H)
    ref = _window_ref(q, kn, vn, kp[LAYER], vp[LAYER], tables, pos, H)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


SHARDED_WALKS = ["one-block", "mid-block", "w8-short-last-block",
                 "live-between-idle"]


@pytest.mark.parametrize("walk", SHARDED_WALKS)
def test_sharded_window_kernel_matches_reference(lane_tile_blocks, walk):
    """The shard_map wrapper on a 2x2 (data, model) mesh: per-shard
    table localization + cross-shard online-softmax merge of the
    kernel's ``fold=False`` partials must match the unsharded reference
    bit-for-float — plain AND fp8/head pools (forced 8-device CPU mesh
    from conftest), over one block and over a walk of several, where a
    block's pages lie on both 'data' shards."""
    from replicatinggpt_tpu.ops import paged_pallas as pp
    from replicatinggpt_tpu.parallel.mesh import make_serve_mesh
    from replicatinggpt_tpu.quant.kv import (fake_quantize_rows,
                                             quantize_rows)
    mesh = make_serve_mesh(2, 2)
    q, kn, vn, kp, vp, tables, pos = _window_inputs(seed=3, walk=walk)
    H, D = 2, 32
    ref = _window_ref(q, kn, vn, kp[LAYER], vp[LAYER], tables, pos, H)
    out = pp.sharded_paged_window_attention(
        jnp.array(q), jnp.array(kn), jnp.array(vn), jnp.array(kp),
        jnp.array(vp), jnp.array(tables), jnp.array(pos), n_head=H,
        mesh=mesh, layer=LAYER)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5,
                               rtol=1e-5)
    kq, ks = quantize_rows(jnp.array(kp), "fp8", H, "head")
    vq, vs = quantize_rows(jnp.array(vp), "fp8", H, "head")
    rep = lambda s: np.asarray(jnp.repeat(s, D, -1))  # noqa: E731
    kpf = np.asarray(kq, np.float32) * rep(ks)
    vpf = np.asarray(vq, np.float32) * rep(vs)
    knf = np.asarray(fake_quantize_rows(jnp.array(kn), "fp8", H, "head"))
    vnf = np.asarray(fake_quantize_rows(jnp.array(vn), "fp8", H, "head"))
    ref_q = _window_ref(q, knf, vnf, kpf[LAYER], vpf[LAYER], tables, pos,
                        H)
    out_q = pp.sharded_paged_window_attention(
        jnp.array(q), jnp.array(knf), jnp.array(vnf), kq, vq,
        jnp.array(tables), jnp.array(pos), n_head=H, mesh=mesh,
        layer=LAYER, k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out_q), ref_q, atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("walk", SHARDED_WALKS)
def test_sharded_window_kernel_in_the_programs_own_blocks(walk):
    """The same, in the blocks ``BLOCK_BYTES`` gives: a shard's row is
    narrower than the pool's, so its blocks are longer."""
    test_sharded_window_kernel_matches_reference(None, walk)


# walks under the interpreter that models DMAs: slots whose last block
# owns 1, 7 and 8 of its 8 pages (every bit of a block's waits), a table P
# does not divide, a W = 8 walk of 16-page blocks (a last block of 3 and
# one of 1) and live slots between idle ones (the fetch-ahead across
# slots: a block's copies started a slot earlier)
DMA_WALKS = ["last-block-owns-1-7-8", "table-of-9", "w8-short-last-block",
             "live-between-idle"]


@pytest.mark.parametrize("walk", DMA_WALKS)
def test_a_block_waits_for_its_owned_pages_bytes(dma_interpret,
                                                 lane_tile_blocks, walk):
    """A block's copies are waited by their bytes (``_walk_blocks``): a
    wait short of them leaves rows unlanded, which only the interpreter
    that models each copy and its semaphore can see (the default one
    lands a copy when it starts). Under it, every walk above matches the
    gather reference and no read races a copy."""
    test_blocked_walk_matches_gather_reference(lane_tile_blocks, walk,
                                               "float32", 1e-5)


@pytest.mark.parametrize("walk", SHARDED_WALKS)
def test_a_shards_subset_waits_for_its_owned_pages_bytes(dma_interpret,
                                                         lane_tile_blocks,
                                                         walk):
    """The same for a shard's arbitrary subset of a slot's pages (the
    owned mask the ``shard_map`` wrapper hands each shard): a block owns
    any count of its pages, anywhere in it, and is waited for that
    count's bytes."""
    test_owned_subsets_partials_merge_to_the_reference(lane_tile_blocks,
                                                       walk)


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(x, "jaxpr", x)
            if hasattr(j, "eqns"):
                yield j


def _kernel_body(fn, *args):
    """The jaxpr of the one paged kernel that ``fn`` calls."""
    def kernels(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["jaxpr"]
            for j in _subjaxprs(e):
                yield from kernels(j)

    (body,) = kernels(jax.make_jaxpr(fn)(*args).jaxpr)
    return body


def _dots_in_block_loop(fn, *args) -> int:
    """``dot_general``s inside the paged kernel's loop over a slot's
    blocks (the top-level ``while`` of the kernel's body: the page
    copies' loops inside it hold none), from the jaxpr of ``fn``."""
    def count(jaxpr):
        return sum((e.primitive.name == "dot_general")
                   + sum(count(j) for j in _subjaxprs(e))
                   for e in jaxpr.eqns)

    return sum(count(j) for e in _kernel_body(fn, *args).eqns
               if e.primitive.name == "while" for j in _subjaxprs(e))


def _abstract(*shapes_dtypes):
    return [jax.ShapeDtypeStruct(s, d) for s, d in shapes_dtypes]


# (n_head, n_kv_head, head_dim, W, pool dtype, attn_window, passes): the
# cells' decode steps (gpt2-large, K-EXAONE's full and ring layers,
# LFM2's full layers), a head-granularity int8 pool, three slabs of
# heads at W = 8, and gpt2-large's W = 8, whose stacked rows split into
# two passes of ten heads
PASS_GEOMETRIES = {
    "gpt2-large-w1": (20, 20, 64, 1, jnp.bfloat16, 0, 1),
    "gpt2-large-w1-int8-head": (20, 20, 64, 1, jnp.int8, 0, 1),
    "six-heads-w8": (6, 6, 64, 8, jnp.bfloat16, 0, 1),
    "gpt2-large-w8": (20, 20, 64, 8, jnp.bfloat16, 0, 2),
    "kexaone-full": (64, 8, 128, 1, jnp.bfloat16, 0, 1),
    "kexaone-ring": (64, 8, 128, 1, jnp.bfloat16, 128, 1),
    "lfm2-full": (32, 8, 64, 1, jnp.bfloat16, 0, 1),
}


@pytest.mark.parametrize("geometry", list(PASS_GEOMETRIES))
def test_a_block_is_one_pass_for_all_of_a_slots_heads(geometry):
    """A block's step is ONE score product and ONE value product over
    the whole row, whatever the head count (a return to a pass per
    128-lane slab of heads, 10 at gpt2-large and 8 at the grouped-query
    cells, fails here): the kernel's block loop, read from the jaxpr,
    holds two ``dot_general``s a pass, and ``block_passes`` (the
    engine's ``kv_block_passes`` over ``kv_blocks_live``) is 1 at the
    cells' decode geometries."""
    from replicatinggpt_tpu.ops import paged_pallas as pp
    H, Hkv, D, W, pool_dtype, window, passes = PASS_GEOMETRIES[geometry]
    B, psz, mp, N = 4, 16, 16, 64
    assert pp.block_passes(H, D, W, n_kv_head=Hkv) == passes
    rows = lambda heads: ((B, W, heads * D), jnp.bfloat16)  # noqa: E731
    pool = ((3, N, psz, Hkv * D), pool_dtype)
    tail = _abstract(((B, mp), jnp.int32), ((B,), jnp.int32))
    if Hkv != H:
        args = _abstract(rows(H), rows(Hkv), rows(Hkv), pool, pool) + tail
        dots = _dots_in_block_loop(
            lambda *a: pp.paged_gqa_attention(
                *a, n_head=H, n_kv_head=Hkv, layer=1, attn_window=window),
            *args)
    else:
        scales = _abstract(*[((3, N, psz, H), jnp.float32)] * 2) \
            if pool_dtype == jnp.int8 else []
        args = _abstract(rows(H), rows(H), rows(H), pool, pool) + tail
        dots = _dots_in_block_loop(
            lambda *a: pp.paged_window_attention(
                *a[:7], n_head=H, layer=1,
                **dict(zip(("k_scales", "v_scales"), a[7:]))),
            *args, *scales)
    assert dots == 2 * passes, (geometry, dots)


# the cells' kernels at their widths, bf16 pools of 16-token pages:
# (n_head, n_kv_head, head_dim or a latent row's lanes, table entries,
# attn_window, pools, pages a block)
WAIT_GEOMETRIES = {
    "gpt2-large": (20, 20, 64, 64, 0, 2, 8),
    "kexaone-full": (64, 8, 128, 512, 0, 2, 8),
    "kexaone-ring": (64, 8, 128, 9, 128, 2, 8),
    "lfm2-full": (32, 8, 64, 512, 0, 2, 16),
    "joyai-latent": (32, None, 640, 512, 0, 1, 32),
}


def _dma_ops(jaxpr, loops=0):
    """``(op, loops)`` for each ``dma_start`` / ``dma_wait`` of a kernel
    body: ``loops`` the loops around it (a slot's block loop is one, a
    page loop inside it two)."""
    for e in jaxpr.eqns:
        if e.primitive.name in ("dma_start", "dma_wait"):
            yield e.primitive.name, loops
        inner = loops + (e.primitive.name in ("while", "scan"))
        for j in _subjaxprs(e):
            yield from _dma_ops(j, inner)


@pytest.mark.parametrize("geometry", list(WAIT_GEOMETRIES))
def test_a_block_is_waited_by_its_bytes_not_a_page_at_a_time(geometry):
    """The kernels' bodies, read from the jaxpr at the cells' widths: no
    DMA wait inside a page loop (a return to one wait a page, 32 a
    latent block, fails here); a block's waits sit in the block loop, one
    a pool a bit of its owned count (``P.bit_length()``, of which a whole
    block of a power of two takes one); a block whose pages are a run
    starts its copies there too, one a pool a bit of the count, and any
    other block one a page in the page loops (each start is emitted
    twice: a call's first block and the block ahead)."""
    from collections import Counter

    from replicatinggpt_tpu.ops import paged_pallas as pp
    H, Hkv, D, mp, window, pools, P = WAIT_GEOMETRIES[geometry]
    B, psz, N, bf = 4, 16, 64, jnp.bfloat16
    width = D if Hkv is None else Hkv * D
    assert pp.block_pages(psz, mp, width * 2, pools) == P
    tail = _abstract(((B, mp), jnp.int32), ((B,), jnp.int32))
    pool = ((3, N, psz, width), bf)
    if Hkv is None:
        fn = lambda *a: pp.paged_mla_attention(  # noqa: E731
            *a, layer=1, latent=512, scale=0.1)
        args = _abstract(((B, H, width), bf), ((B, width), bf), pool) + tail
    elif Hkv == H:
        fn = lambda *a: pp.paged_window_attention(  # noqa: E731
            *a, n_head=H, layer=1)
        row = ((B, 1, width), bf)
        args = _abstract(row, row, row, pool, pool) + tail
    else:
        fn = lambda *a: pp.paged_gqa_attention(  # noqa: E731
            *a, n_head=H, n_kv_head=Hkv, layer=1, attn_window=window)
        row = ((B, 1, width), bf)
        args = _abstract(((B, 1, H * D), bf), row, row, pool, pool) + tail
    ops = Counter(_dma_ops(_kernel_body(fn, *args)))
    assert ops == {("dma_wait", 1): pools * P.bit_length(),
                   ("dma_start", 1): 2 * pools * P.bit_length(),
                   ("dma_start", 2): 2 * pools}, (geometry, ops)


@pytest.mark.parametrize("check", ["gather", "int8-head", "partials"])
def test_a_wide_window_splits_into_whole_head_passes(monkeypatch,
                                                     lane_tile_blocks,
                                                     check):
    """Where the stacked rows and the accumulator would pass the VMEM
    share (``PASS_STATE_BYTES``, cut here to fit two of six heads at W =
    8), a block takes whole-head passes, each over its own lanes, and
    the parities above hold: plain, a head-granularity pool (each pass
    reads its own heads' scales) and the ``fold=False`` partials (each
    head's max and denominator in its own column)."""
    from replicatinggpt_tpu.ops import paged_pallas as pp
    monkeypatch.setattr(pp, "PASS_STATE_BYTES", 2 * 12 * 6 * 8 * 64)
    pp._window_call.clear_cache()        # traced under the usual share
    try:
        assert pp.heads_per_pass(6, 8, 64) == 2
        assert pp.block_passes(6, 64, 8) == 3
        if check == "gather":
            test_blocked_walk_matches_gather_reference(
                lane_tile_blocks, "six-heads-w8", "float32", 1e-5)
        elif check == "int8-head":
            test_windowed_kernel_quantized_parity(
                lane_tile_blocks, "int8", "head", "six-heads-w8")
        else:
            test_owned_subsets_partials_merge_to_the_reference(
                lane_tile_blocks, "six-heads-w8")
    finally:
        pp._window_call.clear_cache()


def test_launch_stats_count_the_blocks_the_device_mask_owns(
        kernel_backend, lane_tile_blocks):
    """``serve/launch`` says how the kernel's walk engages, from the host
    mirrors: ``kv_blocks_grid`` = the turns the
    kernel's grid takes (one a slot), ``kv_block_tokens`` = the tokens a
    block covers, and ``kv_blocks_live`` = the turns
    of the slots' loops: the blocks that hold an owned page in the mask
    the kernel is handed on the device (rebuilt here from what the launch
    uploads: tables, positions, the live slots), ``kv_block_waits`` =
    the DMA waits those turns make: one a pool (K and V) a set bit of a
    block's owned-page count in the same walk, and ``kv_block_runs`` =
    the live blocks that walk marks as runs of the pool (the allocator
    hands each request's blocks out as runs: all of them here)."""
    from replicatinggpt_tpu.ops import paged_pallas as pp
    from replicatinggpt_tpu.utils.telemetry import Telemetry
    cfg = dataclasses.replace(CFG, block_size=256)
    params = init_params(jax.random.PRNGKey(2), cfg)
    tel = Telemetry()
    eng = Engine(params, cfg, EngineConfig(
        pool_size=3, max_queue=8, page_size=8, prefill_chunk=64,
        paged_kernel=True, prefix_cache=False), telemetry=tel)
    assert eng.kernel_route.decode == "pallas"
    psz, mp = eng.pool.page_size, eng.pool.max_pages
    P = pp.block_pages(psz, mp, cfg.n_embd * 4, 2)
    assert (psz, mp, P) == (8, 32, 16)
    assert eng._kv_block_pages == P           # the walk's block, not a stat
    want, waits, runs, dispatch = [], [], [], eng._dispatch

    def spy(k, kill, *a):
        pos = jnp.asarray(np.where(eng._active & ~kill, eng._pos, 0),
                          jnp.int32)
        owned = pp.gqa_owned_pages(pos, jnp.zeros_like(pos), mp, psz, 0)
        walk = pp._blocked_walk(
            jnp.asarray(eng.pool.tables), owned, psz, cfg.n_embd * 4, 2)
        want.append(int(np.asarray(walk[2]).sum()))  # the loops' iterations
        word = np.asarray(walk[6])
        waits.append(2 * int(np.bitwise_count(word & pp.COUNT_MASK).sum()))
        runs.append(int(((word >> pp.RUN_SHIFT) > 0).sum()))
        return dispatch(k, kill, *a)

    eng._dispatch = spy
    rng = np.random.default_rng(0)
    for i, n in enumerate([5, 126, 140, 3]):    # one block, its edge, two
        assert eng.submit(_greedy(f"b{i}", rng.integers(0, 65, (n,)),
                                  max_new=4)) is None
    eng.drain()
    stats = [e["args"] for e in tel.events
             if e.get("ph") == "X" and e.get("name") == "serve/launch"]
    assert want and [a["kv_blocks_live"] for a in stats] == want
    # a block is ONE pass for all of a slot's heads
    assert eng._kv_block_passes == 1
    assert [a["kv_block_passes"] for a in stats] == want
    assert {1, 2, 3, 4} & set(want) and max(want) >= 4    # 1 + 1 + 2
    # 16 pages of 8 a block: 1 to 5 waits a pool a block, one when whole
    assert [a["kv_block_waits"] for a in stats] == waits
    assert all(2 * n <= w <= 10 * n for n, w in zip(want, waits))
    assert [a["kv_block_runs"] for a in stats] == runs == want
    assert all(a["kv_blocks_grid"] == 3 and "kv_block_pages" not in a
               and a["kv_block_tokens"] == P * psz == 128 for a in stats)


# ---------------------------------------------------------------------------
# engine greedy parity: mixed windows, verify, sharded — Pallas vs XLA
# ---------------------------------------------------------------------------

def test_mixed_window_kernel_engine_parity(p64, kernel_backend):
    """Mixed prefill+decode windows through the windowed kernel:
    admissions ride mixed dispatches (pool smaller than the request
    set, window > 1), and greedy streams must match the XLA route
    token-for-token."""
    reqs = lambda: [_greedy(f"m{i}", [3 + i, 1, 4, 1, 5 + i][: 3 + i % 3],  # noqa: E731
                            max_new=5) for i in range(5)]
    ecfg = EngineConfig(pool_size=2, max_queue=8, page_size=8,
                        decode_window=4)
    want, _ = _run(p64, ecfg, reqs())
    got, eng = _run(p64, dataclasses.replace(ecfg, paged_kernel=True),
                    reqs())
    assert eng._use_window_kernel
    assert eng.kernel_route.route == "pallas"
    assert eng.kernel_route.window == "pallas"
    assert got == want


def test_verify_kernel_engine_parity(p64, kernel_backend):
    """Speculative verify through the windowed kernel: the drafted
    (k+1)-window scores in-kernel (scatter AFTER attention — the
    write-then-attend equivalence), streams identical to the XLA
    verify on a repetitive greedy trace."""
    from replicatinggpt_tpu.serve.speculative import make_drafter
    reqs = lambda: [_greedy("v0", [5, 6, 5, 6, 5, 6], max_new=8),  # noqa: E731
                    _greedy("v1", [2, 3, 2, 3], max_new=6)]
    ecfg = EngineConfig(pool_size=2, max_queue=4, page_size=8)
    mk = lambda: make_drafter("ngram", 3, 3, ecfg.pool_size)  # noqa: E731
    want, _ = _run(p64, ecfg, reqs(), drafter=mk())
    got, eng = _run(p64, dataclasses.replace(ecfg, paged_kernel=True),
                    reqs(), drafter=mk())
    assert eng._use_window_kernel
    assert got == want


def test_sharded_engine_kernel_greedy_parity(p64, kernel_backend):
    """A 2x2-mesh engine on the Pallas route (shard_map wrapper for
    decode AND windows) streams identically to the unsharded XLA
    engine — the route reads sharded=True, pallas everywhere."""
    reqs = lambda: [_greedy("s0", [3, 1, 4, 1, 5], max_new=6),  # noqa: E731
                    _greedy("s1", [9, 2, 6], max_new=5)]
    want, _ = _run(p64, EngineConfig(pool_size=2, max_queue=4,
                                     page_size=8), reqs())
    got, eng = _run(p64, EngineConfig(pool_size=2, max_queue=4,
                                      page_size=8, paged_kernel=True,
                                      mesh_data=2, mesh_model=2),
                    reqs())
    assert eng.kernel_route.route == "pallas"
    assert eng.kernel_route.sharded
    assert eng.kernel_route.decode == "pallas"
    assert got == want


def test_paged_kernel_replay_zero_recompiles(p64, kernel_backend):
    """The unified route holds compile discipline: a replay with
    admissions on the Pallas route recompiles nothing after warmup,
    and the summary/artifact carry the route block + gauge."""
    s = run_replay(p64, CFG,
                   ReplayConfig(n_requests=8, rate=2000.0, seed=0,
                                prompt_len_max=10, max_new_tokens=4,
                                greedy=True),
                   EngineConfig(pool_size=2, max_queue=16, page_size=8,
                                paged_kernel=True, decode_window=2))
    assert s["n_completed"] == 8
    assert s["recompiles_after_warmup"] == 0
    assert s["kernel_route"]["route"] == "pallas"
    assert s["kernel_route"]["reasons"] == []
    assert s["gauges"]["kernel_route_pallas"] == 1.0


# ---------------------------------------------------------------------------
# W8A8 rides along
# ---------------------------------------------------------------------------

def test_w8a8_divergence_and_threading(p64, kernel_backend):
    """--act-quant int8 (W8A8): activation rows quantize per-row into
    the int8 weight matmuls. The engine threads it into ModelConfig
    (a different jit key), the route block reports it, streams
    complete, and the numerics actually move (it is not a no-op)
    while staying inside the int8 divergence budget on the first
    decode logits."""
    from replicatinggpt_tpu.quant import DIVERGENCE_BUDGET
    reqs = lambda: [_greedy("w0", [3, 1, 4, 1, 5], max_new=5),  # noqa: E731
                    _greedy("w1", [9, 2, 6], max_new=4)]
    ecfg = EngineConfig(pool_size=2, max_queue=4, page_size=8,
                        paged_kernel=True, weight_quant="int8",
                        act_quant="int8")
    got, eng = _run(p64, ecfg, reqs())
    assert eng.cfg.act_quant == "int8"     # threaded via replace()
    assert eng.kernel_route.act_quant == "int8"
    assert eng.kernel_route.route == "pallas"
    assert all(len(t) > 0 for t in got.values())
    # teacher-forced divergence of the W8A8 matmuls vs weight-only
    # int8: nonzero (the activation quant is real) and far under the
    # int8 budget at this scale
    from replicatinggpt_tpu.models.gpt import (decode_step_paged,
                                               init_paged_kv_pool)
    from replicatinggpt_tpu.quant.weights import quantize_params
    qp = quantize_params(p64, "int8")
    pool = init_paged_kv_pool(CFG, 8, 8)
    tables = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    tok = jnp.array([3, 9], jnp.int32)
    pos = jnp.array([0, 0], jnp.int32)
    active = jnp.array([True, True])
    cfg_w8 = dataclasses.replace(CFG, act_quant="int8")
    lg_a, _ = decode_step_paged(qp, tok, pos, active, tables,
                                dict(pool), cfg_w8)
    lg_w, _ = decode_step_paged(qp, tok, pos, active, tables,
                                dict(pool), CFG)
    div = float(jnp.max(jnp.abs(lg_a - lg_w)))
    assert 0.0 < div < DIVERGENCE_BUDGET["int8"]


def test_act_quant_requires_int8_weights():
    from replicatinggpt_tpu.quant import QuantConfig
    with pytest.raises(ValueError):
        QuantConfig(act_dtype="int8").validate()
    with pytest.raises(ValueError):
        QuantConfig(act_dtype="int8", weight_dtype="fp8").validate()
    QuantConfig(act_dtype="int8", weight_dtype="int8").validate()
