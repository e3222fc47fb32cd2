"""The layer pieces the served families beside GPT-2 share
(``models/exaone_moe.py``, ``models/lfm2_moe.py``,
``models/joyai_llm_flash.py``, ``models/olmo_hybrid.py``): RMSNorm, rows
times a parameter matrix, rotate-half RoPE, the QK-normed grouped-query
projections, grouped scores and values, the paged kernel's contract in
plain XLA, a prefill chunk's attention over its slot's pages, the gated
MLP, THE EXPERT LAYER (``route`` / ``moe``: one function for every family
that has routed experts, told which experts it holds) and the decode
window's loop.

Nothing here knows a family: what differs between them (which layers
rotate, what state a slot keeps, how the head is tied) is data of
``ModelConfig`` or an argument.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import NEG_INF

Params = Dict[str, object]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, _F32) * std).astype(dtype)


def leaf_makers(rng: jax.Array, cfg: ModelConfig) -> Tuple[Callable, Callable]:
    """``(norm(shape), ones(n))`` for a family's ``init_params``: a seeded
    normal(0, init_std) leaf from ONE jitted draw, cast to ``param_dtype``
    where it is drawn (one float32 draw of stacked experts would not fit a
    chip), each call folding the next leaf's number into ``rng``; and a
    unit norm gain."""
    pd = _dtype(cfg.param_dtype)
    n_leaf = 0

    def norm(shape):
        nonlocal n_leaf
        n_leaf += 1
        return _draw(jax.random.fold_in(rng, n_leaf), tuple(shape),
                     cfg.init_std, pd)

    return norm, lambda n: jnp.ones((n,), pd)


def _rms(x, gain, eps):
    x32 = x.astype(_F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * gain.astype(_F32)).astype(x.dtype)


def _mm(x, w):
    """Rows times a parameter matrix in the compute dtype, f32 sums."""
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=_F32)


def _rope(x, positions, theta: float):
    """Rotate-half over the whole head: x (..., T, H, D), positions (..., T)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = positions.astype(_F32)[..., None, None] * inv          # (.., T, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(_F32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _qkv(h, lp, cfg: ModelConfig, positions, rotate: bool):
    """h (..., T, C) -> merged q (..., T, Hq*D), k, v (..., T, Hkv*D):
    pre-norm, projections, QK-norm over the head dim, and RoPE where the
    layer carries a position (``rotate``)."""
    cd = h.dtype
    D = cfg.head_dim
    a = _rms(h, lp["norm1"], cfg.layernorm_eps)
    lead = a.shape[:-1]
    q = _mm(a, lp["wq"]).astype(cd).reshape(lead + (cfg.n_head, D))
    k = _mm(a, lp["wk"]).astype(cd).reshape(lead + (cfg.kv_heads, D))
    v = _mm(a, lp["wv"]).astype(cd)
    q = _rms(q, lp["q_norm"], cfg.layernorm_eps)
    k = _rms(k, lp["k_norm"], cfg.layernorm_eps)
    if rotate:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q.reshape(lead + (-1,)), k.reshape(lead + (-1,)), v


def _swiglu(m, w_gate, w_up, w_down):
    g, u = _mm(m, w_gate), _mm(m, w_up)
    return _mm((jax.nn.silu(g) * u).astype(m.dtype), w_down)


# --------------------------------------------------------- the expert layer

def route(m, lp, cfg: ModelConfig):
    """The router over ALL ``n_experts``, float32 at full precision:
    ``(weights (R, E), chosen ids (R, k))``. Selection by ``s + b``, weights
    from ``s`` alone, normalised over the k chosen (``router_norm_eps``
    added to their sum where the family's published code adds one), times
    the scaling; weights of unchosen experts are exactly 0."""
    s = jax.nn.sigmoid(jnp.dot(m.astype(_F32), lp["router"].astype(_F32),
                               precision=_HI))
    _, top = jax.lax.top_k(s + lp["router_bias"].astype(_F32),
                           cfg.experts_per_token)
    chosen = (top[..., None] == jnp.arange(cfg.n_experts)).any(-2)
    w = jnp.where(chosen, s, 0.0)
    return (w / (jnp.sum(w, -1, keepdims=True) + cfg.router_norm_eps)
            * cfg.routed_scaling), top


def moe(m, lp, cfg: ModelConfig):
    """The expert layer of THIS share over rows m (R, C): ``(y (R, C) f32,
    chosen ids (R, k), routed pairs that landed on held experts)``.
    ``cfg.experts_held`` names the experts whose weights ``lp`` stacks (all
    of them where a chip holds a whole layer). Every held expert runs on
    every row and the router's weight (0 where the expert was not chosen)
    scales its hidden row before ONE down projection contracted over
    (expert, width): no token is dropped and nothing is gathered; at
    decode the step is bound by streaming the held experts' weights either
    way. A shared expert, where ``lp`` has one, is added once."""
    with jax.named_scope("moe_router"):
        w, top = route(m, lp, cfg)
        w_held = w[:, jnp.asarray(cfg.experts_held)]             # (R, Eh)
        pairs = jnp.sum(w_held > 0)
    with jax.named_scope("moe_experts"):
        cd = m.dtype
        g = jnp.einsum("rc,ecf->erf", m, lp["e_gate"].astype(cd),
                       preferred_element_type=_F32)
        u = jnp.einsum("rc,ecf->erf", m, lp["e_up"].astype(cd),
                       preferred_element_type=_F32)
        hid = (jax.nn.silu(g) * u * w_held.T[..., None]).astype(cd)
        y = jnp.einsum("erf,efc->rc", hid, lp["e_down"].astype(cd),
                       preferred_element_type=_F32)
    if "s_gate" in lp:
        with jax.named_scope("moe_shared"):
            y = y + _swiglu(m, lp["s_gate"], lp["s_up"], lp["s_down"])
    return y, top, pairs


def _mlp(h, lp, cfg: ModelConfig, i: int):
    """Residual MLP half of layer ``i`` over rows h (R, C): ``(h + y,
    chosen ids or None, held pairs)``."""
    m = _rms(h, lp["norm2"], cfg.layernorm_eps)
    if cfg.is_sparse_layer(i):
        y, top, pairs = moe(m, lp, cfg)
    else:
        with jax.named_scope("mlp"):
            y = _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
        top, pairs = None, jnp.int32(0)
    return h + y.astype(h.dtype), top, pairs


def mlp_params(norm: Callable, cfg: ModelConfig, i: int) -> dict:
    """Layer ``i``'s MLP leaves, each drawn by ``norm(shape)``: a dense
    SwiGLU, or the router, its bias and the HELD experts (and a shared
    expert where the configuration has one)."""
    C = cfg.n_embd
    if not cfg.is_sparse_layer(i):
        F = cfg.intermediate_size
        return dict(w_gate=norm((C, F)), w_up=norm((C, F)),
                    w_down=norm((F, C)))
    E, Eh = cfg.n_experts, len(cfg.experts_held)
    F, Fs = cfg.moe_intermediate_size, cfg.shared_intermediate_size
    lp = dict(router=norm((C, E)), router_bias=norm((E,)),
              e_gate=norm((Eh, C, F)), e_up=norm((Eh, C, F)),
              e_down=norm((Eh, F, C)))
    if Fs:
        lp.update(s_gate=norm((C, Fs)), s_up=norm((C, Fs)),
                  s_down=norm((Fs, C)))
    return lp


# ---------------------------------------------------- grouped-query attention

def _grouped_scores(q, k, cfg: ModelConfig):
    """q (Tq, Hq*D), k (Tk, Hkv*D) -> scaled f32 scores (Hkv, G, Tq, Tk)."""
    D, Hkv = cfg.head_dim, cfg.kv_heads
    G = cfg.n_head // Hkv
    qg = q.reshape(q.shape[0], Hkv, G, D)
    kg = k.reshape(k.shape[0], Hkv, D)
    return jnp.einsum("qhgd,khd->hgqk", qg, kg,
                      preferred_element_type=_F32) * D ** -0.5


def _grouped_values(p, v, cfg: ModelConfig):
    """p (Hkv, G, Tq, Tk) f32, v (Tk, Hkv*D) -> (Tq, Hq*D) f32."""
    vg = v.reshape(v.shape[0], cfg.kv_heads, cfg.head_dim)
    out = jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), vg,
                     preferred_element_type=_F32)
    return out.reshape(out.shape[0], -1)


def _sequence_attention(q, k, v, cfg: ModelConfig, blk: int, window: int):
    """Causal attention of ONE whole sequence without a cache (a family's
    plain ``forward``): q (T, Cq), k, v (T, Ckv), T a multiple of ``blk``,
    ``blk`` query rows at a time; under ``window`` token i attends
    i - window < j <= i."""
    T = q.shape[0]

    def rows(i):
        q0 = i * blk
        qp = q0 + jnp.arange(blk)
        if window:          # the keys a block's band can reach
            kp = q0 - window + jnp.arange(blk + window)
            take = jnp.clip(kp, 0, T - 1)
            kb, vb = k[take], v[take]
            ok = ((kp[None] <= qp[:, None]) & (kp[None] >= 0)
                  & (kp[None] > qp[:, None] - window))
        else:
            kb, vb = k, v
            ok = jnp.arange(T)[None] <= qp[:, None]
        s = _grouped_scores(jax.lax.dynamic_slice_in_dim(q, q0, blk), kb,
                            cfg)
        p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), -1)
        return _grouped_values(p, vb, cfg)

    return jax.lax.map(rows, jnp.arange(T // blk)).reshape(T, -1)


def _decode_attention(q, k, v, k_pages, v_pages, tables, pos,
                      cfg: ModelConfig, *, use_pallas: bool, window: int = 0,
                      page0=None, name: str = "paged_window_attention"):
    """One fresh row a slot on the STALE pages of one layer's array pair
    (1, N, page, Ckv) plus that row: the grouped-query kernel, or its
    contract in XLA. ``window`` / ``page0`` / ``name``:
    ``ops.paged_pallas.paged_gqa_attention``. The caller scatters after."""
    if use_pallas:
        # the kernel addresses (layer, page): these arrays have one layer
        from ..ops.paged_pallas import paged_gqa_attention
        return paged_gqa_attention(
            q, k, v, k_pages, v_pages, tables, pos, n_head=cfg.n_head,
            n_kv_head=cfg.kv_heads, layer=0, attn_window=window,
            page0=page0, name=name)
    return _xla_paged_attention(
        q, k, v, k_pages[0], v_pages[0], tables, pos,
        jnp.zeros_like(pos) if page0 is None else page0, window, cfg)


@jax.named_scope("kv_scatter")
def _scatter_rows(cc: dict, kn: str, vn: str, page, offset, k, v) -> None:
    """Write rows k, v (R, Ckv) at ``(page, offset)`` of ``cc``'s arrays
    ``kn`` / ``vn`` in place of the dict; an offset past the page drops
    the row."""
    cc[kn] = cc[kn].at[0, page, offset, :].set(k.astype(cc[kn].dtype),
                                               mode="drop")
    cc[vn] = cc[vn].at[0, page, offset, :].set(v.astype(cc[vn].dtype),
                                               mode="drop")


def _xla_paged_attention(q, k_new, v_new, k_pages, v_pages, tables, pos,
                         page0, window: int, cfg: ModelConfig):
    """The kernel's contract in plain XLA (the route a backend without
    Pallas takes, and what the kernel's tests compare with): gather every
    table entry, mask to the stale positions the row reads, fold the fresh
    row. q (B, 1, Cq) -> (B, 1, Cq)."""
    B, mp = tables.shape
    psz = k_pages.shape[1]
    kpos = ((page0[:, None] + jnp.arange(mp))[:, :, None] * psz
            + jnp.arange(psz)).reshape(B, mp * psz)
    ok = kpos < pos[:, None]
    if window:
        ok &= kpos > pos[:, None] - window

    def one(q1, kn, vn, rows, okb):
        kb = jnp.concatenate([k_pages[rows].reshape(mp * psz, -1), kn])
        vb = jnp.concatenate([v_pages[rows].reshape(mp * psz, -1), vn])
        s = _grouped_scores(q1, kb, cfg)
        okb = jnp.concatenate([okb, jnp.ones((1,), bool)])
        p = jax.nn.softmax(jnp.where(okb, s, NEG_INF), -1)
        return _grouped_values(p, vb, cfg)

    return jax.vmap(one)(q, k_new, v_new, tables, ok).astype(q.dtype)


def _prefill_full_attention(q, k_pages, v_pages, table_row, positions,
                            n_blk, ppb: int, cfg: ModelConfig):
    """Write-then-attend over the slot's pages, ``ppb`` pages a turn, for
    ``n_blk`` (traced) turns: online softmax in f32. q (Pc, Cq)."""
    Pc = q.shape[0]
    psz = k_pages.shape[1]
    mp = table_row.shape[0]
    Hkv, D = cfg.kv_heads, cfg.head_dim
    G = cfg.n_head // Hkv

    def turn(b, carry):
        acc, m, l = carry
        pages = jnp.minimum(b * ppb + jnp.arange(ppb), mp - 1)
        with jax.named_scope("kv_gather"):
            kb = k_pages[table_row[pages]].reshape(ppb * psz, -1)
            vb = v_pages[table_row[pages]].reshape(ppb * psz, -1)
        kpos = b * ppb * psz + jnp.arange(ppb * psz)
        s = _grouped_scores(q, kb.astype(q.dtype), cfg)   # (Hkv, G, Pc, n)
        s = jnp.where(kpos[None] <= positions[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[..., None]), 0.0)
        l = l * alpha + p.sum(-1)
        vg = vb.astype(q.dtype).reshape(-1, Hkv, D)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hgqk,khd->hgqd", p.astype(q.dtype), vg,
            preferred_element_type=_F32)
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, n_blk, turn,
        (jnp.zeros((Hkv, G, Pc, D), _F32),
         jnp.full((Hkv, G, Pc), NEG_INF, _F32),
         jnp.zeros((Hkv, G, Pc), _F32)))
    out = acc / l[..., None]                 # every row attends itself
    return out.transpose(2, 0, 1, 3).reshape(Pc, -1)


def layer_kinds(cfg: ModelConfig, is_other) -> list:
    """Per layer ``(is_other(i), index among the layers of its kind)``: a
    family's layers are full-attention layers (pages ``k{j}`` / ``v{j}``)
    and ONE other kind that keeps per-slot state (``is_other``)."""
    out, n = [], [0, 0]
    for i in range(cfg.n_layer):
        other = bool(is_other(i))
        out.append((other, n[other]))
        n[other] += 1
    return out


def paged_page_size(cache: Dict[str, jnp.ndarray]) -> int:
    """Tokens a page of a pool dict holds: its first full layer's K array
    is (1, n_pages, page, Ckv). By name: under ``jit`` a dict comes sorted,
    and a slot's state may sort before the pages."""
    return int(cache["k0"].shape[2])


# ------------------------------------------------------ the decode window

#: what the token block's trailing columns count, in order
STEP_COUNTERS = ("moe_pairs_held",)


def decode_window_of(decode_step: Callable) -> Callable:
    """``models.gpt.decode_window_paged`` for a family, from its
    ``decode_step_paged(params, tok, pos, active, tables, cache, cfg, *,
    use_pallas, shardings) -> (logits, cache, pairs)``: ``length`` decode +
    sample steps in one program, the same carry and the same returned
    tuple. The token block gains ONE trailing column, the routed pairs
    that landed on held experts at each step (``STEP_COUNTERS``): the
    engine fetches it with the tokens and strips it. A step that returns
    ``pairs`` None (a family without experts) adds no column."""
    def decode_window_paged(params, tok, pos, active, budget, eos, tables,
                            cache, rngs, cfg: ModelConfig, *, sample_fn,
                            length: int, use_pallas: bool = False,
                            shardings=None):
        def body(carry, _):
            tok, pos, active, budget, cache, rngs = carry
            logits, cache, pairs = decode_step(
                params, tok, pos, active, tables, cache, cfg,
                use_pallas=use_pallas, shardings=shardings)
            nxt, rngs = sample_fn(rngs, logits, active)
            nxt = jnp.where(active, nxt, 0)
            emitted = active
            budget = jnp.where(active, budget - 1, budget)
            hit_eos = active & (eos >= 0) & (nxt == eos)
            pos = jnp.where(emitted, pos + 1, pos)
            tok = jnp.where(emitted, nxt, tok)
            active = active & (budget > 0) & ~hit_eos
            row = nxt if pairs is None else jnp.concatenate(
                [nxt, pairs[None].astype(nxt.dtype)])
            return (tok, pos, active, budget, cache, rngs), (row, emitted)

        carry = (tok, pos, active, budget, cache, rngs)
        (tok, pos, active, budget, cache, rngs), (toks, emitted) = \
            jax.lax.scan(body, carry, None, length=length)
        return toks, emitted, tok, pos, active, budget, cache, rngs

    return decode_window_paged
