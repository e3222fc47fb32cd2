"""The ``joyai_llm_flash`` family (JoyAI-LLM-Flash's ``config.json``: the
DeepSeek-V3 lineage's latent attention and experts), serve-only.

Pre-norm residual blocks, RMSNorm everywhere, no bias in any projection.
A layer maps h (T, C) by ``reference_joyai_llm_flash.py``'s equations:
``a = rms(h)``; the query through a rank-``q_lora_rank`` bottleneck, ``q =
rms(a q_a) q_b`` as H heads of ``[q_nope | q_rope]``; ONE latent a token,
``[c | k_rope] = a kv_a``, ``c = rms(c)``; ``q_rope`` and ``k_rope`` (every
head shares the one) turned by INTERLEAVED RoPE (the pair (2i, 2i+1) by
``pos * theta ** (-2i / qk_rope_head_dim)``); ``[k_nope | v]_h = c kv_b``;
``s_h = (q_nope,h . k_nope,h + q_rope,h . k_rope) / sqrt(qk_nope +
qk_rope)``, causal; ``h += concat_h(softmax(s_h) v_h) wo``; then ``h +=
ff(rms(h))``: layer 0 a dense SwiGLU, every later layer the sigmoid-routed
experts of ``models/layers.py`` ``moe`` (its ``route``, told which experts
this chip holds) beside one shared expert; the final norm and an untied head
over the vocabulary slice.

**Two forms of one attention.** The whole-sequence ``forward`` and a
prefill chunk EXPAND the latent into every head's keys and values. A decode
step ABSORBS the key expansion into the query instead (``kv_b`` = per head
``[W_UK | W_UV]``): ``q~_h = q_nope,h W_UK,h^T`` is ``kv_lora_rank`` wide,
``s = (q~_h . c_j + q_rope,h . k_rope_j) / sqrt(qk_nope + qk_rope)`` and
``o_h = (sum_j p_j c_j) W_UV,h``: the product of every head's query row with
ONE cached row, whose latent lanes are also the value
(``ops.paged_pallas.paged_mla_attention``).

**The cache.** A layer ``j`` keeps ONE row a token in ``ckv{j}`` (1,
n_pages, page, row): ``[c | k_rope | zeros]``, ``row`` = ``kv_lora_rank +
qk_rope_head_dim`` rounded up to whole 128-lane tiles
(``ModelConfig.kv_token_width``): 576 values padded to 640 at the published
widths. A bf16 array 576 wide is stored in (16, 128) tiles in HBM, so it
takes 640 lanes whatever is asked; padding it by hand costs no byte and
lets the kernel and the page copies work on whole tiles. There is no head
axis and no per-slot state: every entry is pool pages, so the radix prefix
cache shares them as it shares GPT-2's.

Parameters are a LIST of per-layer dicts (``models/exaone_moe.py``), made
leaf by leaf on the device in ``cfg.param_dtype``.

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
from .layers import (STEP_COUNTERS, Params, _dtype, _mlp, _mm,  # noqa: F401
                     _rms)

#: pool-dict entries: one latent row a token a layer
LATENT_ENTRY_PREFIX = "ckv"

#: the leaves the serving entry points read ONLY in the compute dtype
#: (``models/exaone_moe.py`` ``SERVE_CAST_LEAVES``). The norm gains, the
#: router and its bias are read as float32 and are not here.
SERVE_CAST_LEAVES = ("wte", "lm_head", "q_a", "q_b", "kv_a", "kv_b", "wo",
                     "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
                     "w_gate", "w_up", "w_down")

#: rows of a whole-sequence forward that go through attention / the MLP at
#: a time (``models/exaone_moe.py`` ``FORWARD_BLOCK``)
FORWARD_BLOCK = 512

#: KV positions a prefill chunk's layers read (and expand) per loop turn
PREFILL_KV_BLOCK = 512

_F32 = jnp.float32


# ------------------------------------------------------------------ params

def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded normal(0, init_std) matrices, unit norm gains, and a small
    non-zero router bias (so selection and weighting can be told apart);
    one jitted draw a leaf, cast to ``param_dtype`` where it is drawn."""
    cfg.validate()
    C, H = cfg.n_embd, cfg.n_head
    Rq, Rkv, Dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    Dn, Dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    norm, ones = layers.leaf_makers(rng, cfg)
    stack: List[Dict[str, jnp.ndarray]] = []
    for i in range(cfg.n_layer):
        lp = {"norm1": ones(C), "q_a": norm((C, Rq)), "q_a_norm": ones(Rq),
              "q_b": norm((Rq, H * (Dn + Dr))), "kv_a": norm((C, Rkv + Dr)),
              "kv_a_norm": ones(Rkv), "kv_b": norm((Rkv, H * (Dn + Dv))),
              "wo": norm((H * Dv, C)), "norm2": ones(C)}
        lp.update(layers.mlp_params(norm, cfg, i))
        stack.append(lp)
    return {"wte": norm((cfg.vocab_size, C)), "layers": stack,
            "norm_f": ones(C), "lm_head": norm((C, cfg.vocab_size))}


# ------------------------------------------------------------- the layers

def rope_pairs(x, positions, theta: float):
    """Interleaved RoPE: x (..., T, H, D), positions (..., T); the pair
    (2i, 2i+1) of a head turns by ``pos * theta ** (-2i / D)``."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=_F32) / D)        # (D/2,)
    ang = positions.astype(_F32)[..., None, None] * inv       # (.., T, 1, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(_F32).reshape(x.shape[:-1] + (D // 2, 2))
    a, b = x32[..., 0], x32[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape).astype(x.dtype)


def _queries(a, lp, cfg: ModelConfig, positions):
    """Normed rows a (..., T, C) -> ``(q_nope (..., T, H, Dn), q_rope
    rotated (..., T, H, Dr))``."""
    cd, Dn = a.dtype, cfg.qk_nope_head_dim
    cq = _rms(_mm(a, lp["q_a"]).astype(cd), lp["q_a_norm"], cfg.layernorm_eps)
    q = _mm(cq, lp["q_b"]).astype(cd).reshape(
        a.shape[:-1] + (cfg.n_head, Dn + cfg.qk_rope_head_dim))
    return q[..., :Dn], rope_pairs(q[..., Dn:], positions, cfg.rope_theta)


def _latent_row(a, lp, cfg: ModelConfig, positions):
    """Normed rows a (..., T, C) -> the row each caches, ``[c normed |
    k_rope rotated | zeros]`` (..., T, ``kv_token_width``)."""
    cd, R = a.dtype, cfg.kv_lora_rank
    ckv = _mm(a, lp["kv_a"]).astype(cd)
    c = _rms(ckv[..., :R], lp["kv_a_norm"], cfg.layernorm_eps)
    kr = rope_pairs(ckv[..., None, R:], positions, cfg.rope_theta)[..., 0, :]
    pad = cfg.kv_token_width - R - cfg.qk_rope_head_dim
    return jnp.concatenate([c, kr, jnp.zeros(c.shape[:-1] + (pad,), cd)], -1)


def _expand(rows, lp, cfg: ModelConfig):
    """Cached rows (n, row) -> ``(k_nope (n, H, Dn), v (n, H, Dv), k_rope
    (n, Dr))``: the latent times ``kv_b``."""
    R, Dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = _mm(rows[:, :R], lp["kv_b"]).astype(rows.dtype).reshape(
        rows.shape[0], cfg.n_head, Dn + cfg.v_head_dim)
    return kv[..., :Dn], kv[..., Dn:], rows[:, R:R + cfg.qk_rope_head_dim]


def _expanded_scores(qn, qr, kn, kr, cfg: ModelConfig):
    """q_nope (Tq, H, Dn), q_rope (Tq, H, Dr) on k_nope (Tk, H, Dn), the
    shared k_rope (Tk, Dr) -> scaled f32 scores (H, Tq, Tk)."""
    s = (jnp.einsum("qhd,khd->hqk", qn, kn, preferred_element_type=_F32)
         + jnp.einsum("qhd,kd->hqk", qr, kr, preferred_element_type=_F32))
    return s * scale_of(cfg)


def scale_of(cfg: ModelConfig) -> float:
    """The softmax scale: a head's score is ``qk_nope + qk_rope`` wide
    (``rope_scaling`` is null: no YaRN factor)."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def absorbed(lp, cfg: ModelConfig, cd):
    """``kv_b`` as each head's key and value expansions: ``(W_UK (R, H,
    Dn), W_UV (R, H, Dv))`` in the compute dtype."""
    kv_b = lp["kv_b"].astype(cd).reshape(cfg.kv_lora_rank, cfg.n_head, -1)
    return (kv_b[..., :cfg.qk_nope_head_dim],
            kv_b[..., cfg.qk_nope_head_dim:])


@jax.named_scope("head")
def _head(x, params: Params, cfg: ModelConfig):
    x = _rms(x, params["norm_f"], cfg.layernorm_eps)
    return _mm(x, params["lm_head"])


# --------------------------------------------------- whole-sequence forward

def _sequence_attention(a, lp, cfg: ModelConfig, blk: int):
    """Causal expanded attention of ONE whole sequence of normed rows a (T,
    C), T a multiple of ``blk``, ``blk`` query rows at a time: (T, H * Dv)."""
    T = a.shape[0]
    positions = jnp.arange(T, dtype=jnp.int32)
    qn, qr = _queries(a, lp, cfg, positions)
    kn, v, kr = _expand(_latent_row(a, lp, cfg, positions), lp, cfg)

    def rows(i):
        q0 = i * blk
        take = lambda t: jax.lax.dynamic_slice_in_dim(t, q0, blk)
        s = _expanded_scores(take(qn), take(qr), kn, kr, cfg)
        ok = jnp.arange(T)[None] <= (q0 + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), -1)
        return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                          preferred_element_type=_F32)

    return jax.lax.map(rows, jnp.arange(T // blk)).reshape(T, -1)


def forward(params: Params, idx: jnp.ndarray, cfg: ModelConfig, *,
            return_routing: bool = False):
    """(B, T) ids -> (B, T, V) f32 logits, no cache: the program's plain
    path (same layers, the expanded form, ``FORWARD_BLOCK`` query rows at a
    time). With ``return_routing`` also the chosen expert ids of every
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
    tops = []
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("attn_mla"):
            a = _rms(x, lp["norm1"], cfg.layernorm_eps)
            att = jax.vmap(lambda r, lp=lp: _sequence_attention(
                r, lp, cfg, blk))(a)
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

def init_paged_kv_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                       dtype=None, quant=None, n_slots: int = 0
                       ) -> Dict[str, jnp.ndarray]:
    """One latent row a token a layer (module docstring); nothing a slot."""
    assert quant is None or not quant.kv_enabled, (
        "joyai_llm_flash: quantised pools are refused (paged_mla_attention "
        "reads plain latent rows)")
    dt = dtype or _dtype(cfg.dtype)
    return {f"{LATENT_ENTRY_PREFIX}{j}":
            jnp.zeros((1, n_pages, page_size, cfg.kv_token_width), dt)
            for j in range(cfg.n_layer)}


def _page_size(cache) -> int:
    return int(cache[f"{LATENT_ENTRY_PREFIX}0"].shape[2])


@jax.named_scope("kv_scatter")
def _scatter_row(cc: dict, name: str, page, offset, rows) -> None:
    """Write latent rows (R, row) at ``(page, offset)`` of ``cc[name]`` in
    place of the dict; an offset past the page drops the row."""
    cc[name] = cc[name].at[0, page, offset, :].set(rows.astype(cc[name].dtype),
                                                   mode="drop")


# ------------------------------------------------------------------ decode

def _xla_mla_attention(q, kv_new, pages, tables, pos, cfg: ModelConfig):
    """``ops.paged_pallas.paged_mla_attention``'s contract in plain XLA
    (the route a backend without Pallas takes, and what the kernel's tests
    compare with): gather every table entry of ONE layer's (N, page, row)
    pages, mask to the stale positions, fold the fresh row. q (B, H, row)
    -> (B, H, kv_lora_rank)."""
    B, mp = tables.shape
    psz = pages.shape[1]
    ok = jnp.arange(mp * psz)[None] < pos[:, None]

    def one(q1, new, table, okb):
        rows = jnp.concatenate([pages[table].reshape(mp * psz, -1),
                                new[None]])
        s = jnp.einsum("hw,nw->hn", q1, rows,
                       preferred_element_type=_F32) * scale_of(cfg)
        okb = jnp.concatenate([okb, jnp.ones((1,), bool)])
        p = jax.nn.softmax(jnp.where(okb, s, NEG_INF), -1)
        return jnp.einsum("hn,nr->hr", p.astype(rows.dtype),
                          rows[:, :cfg.kv_lora_rank],
                          preferred_element_type=_F32)

    return jax.vmap(one)(q, kv_new, tables, ok).astype(q.dtype)


def decode_step_paged(params: Params, idx_t, pos, active, tables,
                      cache: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
                      use_pallas: bool = False, shardings=None):
    """One token a slot, the absorbed form: ``(logits (B, V) f32, cache,
    routed pairs on held experts)``. Row ``b`` is slot ``b``. Every head's
    absorbed query row reads the STALE latent rows through the slot's page
    table plus the fresh row, and the fresh row is scattered afterwards
    (``ops.paged_pallas.paged_mla_attention``). Inactive rows run at
    position 0 and their writes are dropped."""
    assert shardings is None, "joyai_llm_flash serves on one chip"
    cd = _dtype(cfg.dtype)
    B = idx_t.shape[0]
    psz = _page_size(cache)
    mp = tables.shape[1]
    R, H = cfg.kv_lora_rank, cfg.n_head
    pad = cfg.kv_token_width - R - cfg.qk_rope_head_dim
    pos_eff = jnp.where(active, pos, 0)
    check_in_bounds(pos_eff, 1, mp * psz, what="paged decode write")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx_t]                       # (B, C)
    phys = tables[jnp.arange(B), jnp.minimum(pos_eff // psz, mp - 1)]
    woff = jnp.where(active, pos_eff % psz, psz)       # inactive: dropped
    cc = dict(cache)
    pairs = jnp.int32(0)
    for i, lp in enumerate(params["layers"]):
        name = f"{LATENT_ENTRY_PREFIX}{i}"
        with jax.named_scope("attn_mla"):
            a = _rms(x, lp["norm1"], cfg.layernorm_eps)[:, None]  # (B, 1, C)
            qn, qr = _queries(a, lp, cfg, pos_eff[:, None])
            row = _latent_row(a, lp, cfg, pos_eff[:, None])[:, 0]  # (B, row)
            w_uk, w_uv = absorbed(lp, cfg, cd)
            q_abs = jnp.einsum("bhd,rhd->bhr", qn[:, 0], w_uk,
                               preferred_element_type=_F32).astype(cd)
            q_abs = jnp.concatenate(
                [q_abs, qr[:, 0], jnp.zeros((B, H, pad), cd)], -1)
            if use_pallas:
                from ..ops.paged_pallas import paged_mla_attention
                o_lat = paged_mla_attention(
                    q_abs, row, cc[name], tables, pos_eff, layer=0,
                    latent=R, scale=scale_of(cfg))
            else:
                o_lat = _xla_mla_attention(q_abs, row, cc[name][0], tables,
                                           pos_eff, cfg)
            _scatter_row(cc, name, phys, woff, row)
            o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv,
                           preferred_element_type=_F32).astype(cd)
            x = x + _mm(o.reshape(B, -1), lp["wo"]).astype(cd)
        x, _, n = _mlp(x, lp, cfg, i)
        pairs = pairs + n
    return _head(x, params, cfg), cc, pairs


#: ``length`` decode + sample steps in one program
decode_window_paged = layers.decode_window_of(decode_step_paged)


# ----------------------------------------------------------------- prefill

def _prefill_attention(qn, qr, pages, table_row, positions, n_blk,
                       ppb: int, lp, cfg: ModelConfig):
    """Write-then-attend over the slot's latent pages, ``ppb`` pages a
    turn for ``n_blk`` (traced) turns, each turn's rows expanded into every
    head's keys and values: online softmax in f32. q_nope (Pc, H, Dn),
    q_rope (Pc, H, Dr) -> (Pc, H * Dv)."""
    Pc, H, _ = qn.shape
    psz = pages.shape[1]
    mp = table_row.shape[0]
    Dv = cfg.v_head_dim

    def turn(b, carry):
        acc, m, l = carry
        take = jnp.minimum(b * ppb + jnp.arange(ppb), mp - 1)
        with jax.named_scope("kv_gather"):
            rows = pages[table_row[take]].reshape(ppb * psz, -1)
        kn, v, kr = _expand(rows.astype(qn.dtype), lp, cfg)
        kpos = b * ppb * psz + jnp.arange(ppb * psz)
        s = _expanded_scores(qn, qr, kn, kr, cfg)             # (H, Pc, n)
        s = jnp.where(kpos[None] <= positions[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[..., None]), 0.0)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(v.dtype), v,
            preferred_element_type=_F32)
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, n_blk, turn,
        (jnp.zeros((H, Pc, Dv), _F32), jnp.full((H, Pc), NEG_INF, _F32),
         jnp.zeros((H, Pc), _F32)))
    out = acc / l[..., None]                 # every row attends itself
    return out.transpose(1, 0, 2).reshape(Pc, -1)


def prefill_chunk_paged(params: Params, idx, offset, limit, table_row,
                        slot, cache: Dict[str, jnp.ndarray],
                        cfg: ModelConfig, *, shardings=None):
    """One chunk (1, Pc) of ONE slot's prompt, the expanded form: write the
    chunk's latent rows through the page table (positions >= ``limit``
    dropped), then read the slot's rows ``PREFILL_KV_BLOCK`` positions at a
    time up to the chunk's end, expanding each turn's rows. ``slot`` is not
    read: the family keeps nothing a slot."""
    assert shardings is None, "joyai_llm_flash serves on one chip"
    cd = _dtype(cfg.dtype)
    Pc = idx.shape[1]
    psz = _page_size(cache)
    mp = table_row.shape[0]
    positions = offset + jnp.arange(Pc, dtype=jnp.int32)
    check_in_bounds(offset, 1, cfg.block_size, what="paged prefill chunk")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx[0]]                      # (Pc, C)
    valid = (positions < limit) & (positions < mp * psz)
    phys = table_row[jnp.minimum(positions // psz, mp - 1)]
    woff = jnp.where(valid, positions % psz, psz)
    n_blk = (offset + Pc + PREFILL_KV_BLOCK - 1) // PREFILL_KV_BLOCK
    ppb = PREFILL_KV_BLOCK // psz
    cc = dict(cache)
    for i, lp in enumerate(params["layers"]):
        name = f"{LATENT_ENTRY_PREFIX}{i}"
        with jax.named_scope("attn_mla"):
            a = _rms(x, lp["norm1"], cfg.layernorm_eps)
            qn, qr = _queries(a, lp, cfg, positions)
            _scatter_row(cc, name, phys, woff,
                         _latent_row(a, lp, cfg, positions))
            att = _prefill_attention(qn, qr, cc[name][0], table_row,
                                     positions, n_blk, ppb, lp, cfg)
            x = x + _mm(att.astype(cd), lp["wo"]).astype(cd)
        x, _, _ = _mlp(x, lp, cfg, i)
    return cc


# --------------------------------------------------------------- refusals

def mixed_window_paged(*args, **kw):
    raise NotImplementedError(
        "joyai_llm_flash has no mixed prefill+decode window: its prefill is "
        "the expanded form a slot at a time (prefill_chunk_paged) and its "
        "decode the absorbed one; serve it with decode_window=1")


def verify_step_paged(*args, **kw):
    raise NotImplementedError(
        "joyai_llm_flash has no speculative verify step: a verify window "
        "over the latent pages is not built, and the drafter the model "
        "ships (its next-token prediction block) is not built either")
