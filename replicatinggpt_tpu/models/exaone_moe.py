"""The ``exaone_moe`` family (K-EXAONE-236B-A23B's ``config.json``), serve-only.

Pre-norm residual blocks, RMSNorm everywhere, no bias in any projection:

- attention: 64 query heads read 8 KV heads (query head ``n`` reads KV head
  ``n // 8``), RMSNorm over the head dim of q and k, then either a WINDOW
  layer (rotate-half RoPE, token ``i`` attends ``i - window < j <= i``) or a
  FULL layer (causal over everything, no position at all);
- MLP: layer 0 a dense SwiGLU, every later layer sigmoid-routed experts
  (selection by ``s + b``, weights from ``s`` alone, normalised over the
  chosen ``k``, times ``routed_scaling``) beside one shared expert;
- untied head over the vocabulary slice the config names.

**The share.** ``cfg.experts_held`` names the routed experts this program
holds: what expert parallelism asks of a chip. The router scores all
``cfg.n_experts``, the top-k and its weights are those of the whole model,
and the layer sums only the chosen experts it holds, plus the shared expert
once. Nothing stands in for the absent chips: their part of ``y`` is left
out, here and in the plain reference alike.

Parameters are a LIST of per-layer dicts (the layers differ in kind, and one
float32 draw of the stacked experts would not fit a chip), made leaf by
leaf on the device in ``cfg.param_dtype``.

Two kinds of KV state side by side (``init_paged_kv_pool``): a full layer
``j`` keeps paged history in ``k{j}`` / ``v{j}`` (1, n_pages, page, Ckv),
walked through the slot's page table exactly like GPT-2's pool; a window
layer ``j`` keeps a RING of ``ring_pages`` pages a slot in ``wk{j}`` /
``wv{j}`` (1, n_slots * ring_pages, page, Ckv): position ``p`` lives at
ring token ``p % (ring_pages * page)`` of its slot, whatever the context.
Every entry has its page axis at 1, so the engine's programs that walk a
pool dict need no second shape; entries that start with ``w`` are not pool
pages (``WINDOW_ENTRY_PREFIX``) and are skipped by page copies.

The layer pieces this family shares with ``models/lfm2_moe.py`` (RMSNorm,
the QK-normed grouped-query projections, the gated MLP, the EXPERT LAYER
``route`` / ``moe``, the XLA form of the paged kernel's contract, a prefill
chunk's attention over its pages, the decode window's loop) live in
``models/layers.py``; this module keeps what is K-EXAONE's alone.

The serving entry points are those of ``models/gpt.py``
(``models/families.py`` hands the engine one family's set):
``prefill_chunk_paged``, ``decode_step_paged``, ``decode_window_paged``; the
mixed prefill+decode window and speculative verify are REFUSED by name.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import NEG_INF
from ..utils.sanitize import check_in_bounds
from . import layers
from .layers import (STEP_COUNTERS, Params, _dtype, _grouped_scores,  # noqa: F401
                     _grouped_values, _mlp, _mm, _prefill_full_attention,
                     _qkv, _rms, paged_page_size)

#: pool-dict entries that are per-slot window rings, not pool pages
WINDOW_ENTRY_PREFIX = "w"

#: the leaves the serving entry points read ONLY in the compute dtype
#: (``_mm`` and the expert products cast a weight to its rows' dtype, the
#: embedding is cast before the lookup): what an engine may hand over cast
#: once (``models/gpt.py`` ``SERVE_CAST_LEAVES``). The norm gains, the
#: router and its bias are read as float32 and are not here.
SERVE_CAST_LEAVES = ("wte", "lm_head", "wq", "wk", "wv", "wo",
                     "e_gate", "e_up", "e_down",
                     "s_gate", "s_up", "s_down",
                     "w_gate", "w_up", "w_down")

#: rows of a whole-sequence forward that go through attention / the MLP at
#: a time (``forward`` is the program's plain path: tests, the benchmark's
#: routing choices; the serving programs never exceed a prefill chunk)
FORWARD_BLOCK = 512

#: KV positions a prefill chunk's full-attention layers read per loop turn
PREFILL_KV_BLOCK = 512

_F32 = jnp.float32
#: a scatter index past any array: the row is dropped (``mode="drop"``)
_DROP = 2 ** 31 - 1


# ------------------------------------------------------------------ params

def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded normal(0, init_std) matrices, unit norm gains, and a small
    non-zero router bias (so selection and weighting can be told apart);
    one jitted draw a leaf, cast to ``param_dtype`` where it is drawn."""
    cfg.validate()
    C, D = cfg.n_embd, cfg.head_dim
    Cq, Ckv = cfg.n_head * D, cfg.kv_channels
    norm, ones = layers.leaf_makers(rng, cfg)
    stack: List[Dict[str, jnp.ndarray]] = []
    for i in range(cfg.n_layer):
        lp = {"norm1": ones(C), "wq": norm((C, Cq)), "wk": norm((C, Ckv)),
              "wv": norm((C, Ckv)), "q_norm": ones(D), "k_norm": ones(D),
              "wo": norm((Cq, C)), "norm2": ones(C)}
        lp.update(layers.mlp_params(norm, cfg, i))
        stack.append(lp)
    return {"wte": norm((cfg.vocab_size, C)), "layers": stack,
            "norm_f": ones(C), "lm_head": norm((C, cfg.vocab_size))}


def param_count(params: Params) -> int:
    return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))


# ------------------------------------------------------------- the layers

@jax.named_scope("head")
def _head(x, params: Params, cfg: ModelConfig):
    x = _rms(x, params["norm_f"], cfg.layernorm_eps)
    return _mm(x, params["lm_head"])


# --------------------------------------------------- whole-sequence forward

def forward(params: Params, idx: jnp.ndarray, cfg: ModelConfig, *,
            return_routing: bool = False):
    """(B, T) ids -> (B, T, V) f32 logits, no cache: the program's plain
    path (same layers, einsum attention over ``FORWARD_BLOCK`` query rows
    at a time). With ``return_routing`` also the chosen expert ids of every
    sparse layer, (n_sparse, B, T, k) int32."""
    cd = _dtype(cfg.dtype)
    B, T_in = idx.shape
    blk = min(FORWARD_BLOCK, T_in)
    # whole blocks: the padding lies after every real token, which a
    # causal model never reads, and is cut off again below
    idx = jnp.pad(idx, ((0, 0), (0, -T_in % blk)))
    T = idx.shape[1]
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx]
    positions = jnp.arange(T, dtype=jnp.int32)
    tops = []

    for i, lp in enumerate(params["layers"]):
        win = cfg.is_window_layer(i)
        scope = "attn_swa" if win else "attn_global"
        with jax.named_scope(scope):
            q, k, v = _qkv(x, lp, cfg, positions[None], win)
            att = jax.vmap(lambda q, k, v: layers._sequence_attention(
                q, k, v, cfg, blk, cfg.sliding_window if win else 0))(
                    q, k, v)
            x = x + _mm(att.astype(cd), lp["wo"]).astype(cd)

        def mlp_rows(r, lp=lp, i=i):
            h, top, _ = _mlp(r, lp, cfg, i)
            return h, (jnp.zeros((blk, 0), jnp.int32) if top is None
                       else top)

        out, top = jax.lax.map(mlp_rows, x.reshape(B * T // blk, blk, -1))
        x = out.reshape(B, T, -1)
        if cfg.is_sparse_layer(i):
            tops.append(top.reshape(B, T, -1))
    logits = _head(x[:, :T_in], params, cfg)
    if return_routing:
        return logits, (jnp.stack(tops)[:, :, :T_in] if tops else None)
    return logits


# ------------------------------------------------------------ the KV state

def ring_pages(cfg: ModelConfig, page_size: int) -> int:
    """Pages of a window layer's ring a slot: what ``sliding_window``
    consecutive positions can touch (the window's stale rows and the row
    being written), 128 + 16 tokens at the published sizes."""
    return -(-(cfg.sliding_window - 1) // page_size) + 1


def init_paged_kv_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                       dtype=None, quant=None, n_slots: int = 0
                       ) -> Dict[str, jnp.ndarray]:
    """Both kinds of KV state (module docstring). ``n_slots`` sizes the
    window rings: they belong to slots, not to requests' page budgets, so
    admission counts the full layers' pages only."""
    assert quant is None or not quant.kv_enabled, (
        "exaone_moe: quantised pools are refused (the grouped-query kernel "
        "reads plain pages)")
    assert n_slots >= 1, "exaone_moe's pool needs the slot count"
    dt = dtype or _dtype(cfg.dtype)
    Ckv = cfg.kv_channels
    pool = {}
    for j, _ in enumerate(cfg.paged_layers):
        pool[f"k{j}"] = jnp.zeros((1, n_pages, page_size, Ckv), dt)
        pool[f"v{j}"] = jnp.zeros((1, n_pages, page_size, Ckv), dt)
    R = ring_pages(cfg, page_size)
    for j, _ in enumerate(cfg.window_layers):
        pool[f"wk{j}"] = jnp.zeros((1, n_slots * R, page_size, Ckv), dt)
        pool[f"wv{j}"] = jnp.zeros((1, n_slots * R, page_size, Ckv), dt)
    return pool


# ------------------------------------------------------------------ decode

def decode_step_paged(params: Params, idx_t, pos, active, tables,
                      cache: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
                      use_pallas: bool = False, shardings=None):
    """One token a slot through both kinds of state: ``(logits (B, V)
    f32, cache, routed pairs on held experts)``. Full layers walk the
    slot's page table, window layers their slot's ring from the first page
    the window still reaches; both attend the STALE state plus the fresh
    row and scatter afterwards (``ops.paged_pallas.paged_gqa_attention``).
    Inactive rows run at position 0 and their writes are dropped."""
    assert shardings is None, "exaone_moe serves on one chip"
    cd = _dtype(cfg.dtype)
    B = idx_t.shape[0]
    psz = paged_page_size(cache)
    mp = tables.shape[1]
    R = ring_pages(cfg, psz)
    W = cfg.sliding_window
    pos_eff = jnp.where(active, pos, 0)
    check_in_bounds(pos_eff, 1, mp * psz, what="paged decode write")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx_t][:, None, :]          # (B, 1, C)
    bidx = jnp.arange(B)
    phys = tables[bidx, jnp.minimum(pos_eff // psz, mp - 1)]
    woff = jnp.where(active, pos_eff % psz, psz)       # inactive: dropped
    # the ring walk: absolute pages first..first+R-1 of each slot
    first = jnp.maximum(pos_eff - W + 1, 0) // psz                # (B,)
    walk = first[:, None] + jnp.arange(R, dtype=jnp.int32)[None]  # (B, R)
    ring_tables = bidx[:, None] * R + walk % R
    ring_phys = bidx * R + (pos_eff // psz) % R
    cc = dict(cache)
    pairs = jnp.int32(0)
    kinds = layers.layer_kinds(cfg, cfg.is_window_layer)
    for i, (lp, (win, j)) in enumerate(zip(params["layers"], kinds)):
        with jax.named_scope("attn_swa" if win else "attn_global"):
            q, k, v = _qkv(x, lp, cfg, pos_eff[:, None], win)
            kn, vn = (f"wk{j}", f"wv{j}") if win else (f"k{j}", f"v{j}")
            att = layers._decode_attention(
                q, k, v, cc[kn], cc[vn], ring_tables if win else tables,
                pos_eff, cfg, use_pallas=use_pallas, window=W if win else 0,
                page0=first if win else None,
                name=("swa_window_attention" if win
                      else "paged_window_attention"))
            layers._scatter_rows(cc, kn, vn, ring_phys if win else phys,
                                 woff, k[:, 0], v[:, 0])
            x = x + _mm(att, lp["wo"]).astype(cd)
        h, _, n = _mlp(x[:, 0], lp, cfg, i)
        x = h[:, None]
        pairs = pairs + n
    return _head(x[:, 0], params, cfg), cc, pairs


#: ``length`` decode + sample steps in one program
decode_window_paged = layers.decode_window_of(decode_step_paged)


# ----------------------------------------------------------------- prefill

def prefill_chunk_paged(params: Params, idx, offset, limit, table_row,
                        slot, cache: Dict[str, jnp.ndarray],
                        cfg: ModelConfig, *, shardings=None):
    """One chunk (1, Pc) of ONE slot's prompt into both kinds of state.
    Full layers: write the chunk's rows through the page table (positions
    >= ``limit`` dropped), then read the slot's pages ``PREFILL_KV_BLOCK``
    positions at a time up to the chunk's end with an online softmax: what
    is read follows the context, not ``block_size``. Window layers: attend
    the ring's last ``window`` positions before the chunk beside the fresh
    chunk under the band mask, then write the chunk's last rows that a
    ring holds. A chunk longer than the window is fine."""
    assert shardings is None, "exaone_moe serves on one chip"
    cd = _dtype(cfg.dtype)
    Pc = idx.shape[1]
    psz = paged_page_size(cache)
    mp = table_row.shape[0]
    R = ring_pages(cfg, psz) * psz                   # ring tokens a slot
    W = cfg.sliding_window
    positions = offset + jnp.arange(Pc, dtype=jnp.int32)
    check_in_bounds(offset, 1, cfg.block_size, what="paged prefill chunk")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx[0]]                      # (Pc, C)
    valid = (positions < limit) & (positions < mp * psz)
    phys = table_row[jnp.minimum(positions // psz, mp - 1)]
    woff = jnp.where(valid, positions % psz, psz)
    # ring: history rows offset - W .. offset - 1, and the chunk's rows that
    # no later valid row of the chunk overwrites
    hist_pos = offset - W + jnp.arange(W, dtype=jnp.int32)
    hist_at = slot * R + hist_pos % R
    end = jnp.minimum(limit, offset + Pc)
    ring_at = jnp.where(valid & (positions >= end - R),
                        slot * R + positions % R, _DROP)
    kp_win = jnp.concatenate([hist_pos, positions])
    ok_win = ((kp_win[None] <= positions[:, None]) & (kp_win[None] >= 0)
              & (kp_win[None] > positions[:, None] - W))
    n_blk = (offset + Pc + PREFILL_KV_BLOCK - 1) // PREFILL_KV_BLOCK
    ppb = PREFILL_KV_BLOCK // psz
    cc = dict(cache)
    kinds = layers.layer_kinds(cfg, cfg.is_window_layer)
    for i, (lp, (win, j)) in enumerate(zip(params["layers"], kinds)):
        with jax.named_scope("attn_swa" if win else "attn_global"):
            q, k, v = _qkv(x, lp, cfg, positions, win)
            if win:
                kn, vn = f"wk{j}", f"wv{j}"
                flat_k = cc[kn].reshape(-1, k.shape[-1])
                flat_v = cc[vn].reshape(-1, v.shape[-1])
                with jax.named_scope("kv_gather"):
                    kb = jnp.concatenate([flat_k[hist_at].astype(cd), k])
                    vb = jnp.concatenate([flat_v[hist_at].astype(cd), v])
                s = _grouped_scores(q, kb, cfg)
                p = jax.nn.softmax(jnp.where(ok_win, s, NEG_INF), -1)
                att = _grouped_values(p, vb, cfg)
                with jax.named_scope("kv_scatter"):
                    cc[kn] = flat_k.at[ring_at].set(
                        k.astype(flat_k.dtype), mode="drop"
                    ).reshape(cc[kn].shape)
                    cc[vn] = flat_v.at[ring_at].set(
                        v.astype(flat_v.dtype), mode="drop"
                    ).reshape(cc[vn].shape)
            else:
                kn, vn = f"k{j}", f"v{j}"
                layers._scatter_rows(cc, kn, vn, phys, woff, k, v)
                att = _prefill_full_attention(
                    q, cc[kn][0], cc[vn][0], table_row, positions, n_blk,
                    ppb, cfg)
            x = x + _mm(att.astype(cd), lp["wo"]).astype(cd)
        x, _, _ = _mlp(x, lp, cfg, i)
    return cc


# --------------------------------------------------------------- refusals

def mixed_window_paged(*args, **kw):
    raise NotImplementedError(
        "exaone_moe has no mixed prefill+decode window: a prefill chunk "
        "writes a window layer's ring a slot at a time "
        "(prefill_chunk_paged); serve it with decode_window=1")


def verify_step_paged(*args, **kw):
    raise NotImplementedError(
        "exaone_moe has no speculative verify step: a rejected draft's "
        "rows would have to be taken back out of the window rings")
