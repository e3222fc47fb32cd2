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

The serving entry points are those of ``models/gpt.py``
(``models/families.py`` hands the engine one family's set):
``prefill_chunk_paged``, ``decode_step_paged``, ``decode_window_paged``; the
mixed prefill+decode window and speculative verify are REFUSED by name.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import NEG_INF
from ..utils.sanitize import check_in_bounds

Params = Dict[str, object]

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
_HI = jax.lax.Precision.HIGHEST


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


# ------------------------------------------------------------------ params

@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, _F32) * std).astype(dtype)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded normal(0, init_std) matrices, unit norm gains, and a small
    non-zero router bias (so selection and weighting can be told apart);
    one jitted draw a leaf, cast to ``param_dtype`` where it is drawn."""
    cfg.validate()
    pd = _dtype(cfg.param_dtype)
    C, D = cfg.n_embd, cfg.head_dim
    Cq, Ckv = cfg.n_head * D, cfg.kv_channels
    n_leaf = 0

    def norm(shape):
        nonlocal n_leaf
        n_leaf += 1
        return _draw(jax.random.fold_in(rng, n_leaf), tuple(shape),
                     cfg.init_std, pd)

    ones = lambda n: jnp.ones((n,), pd)
    layers: List[Dict[str, jnp.ndarray]] = []
    for i in range(cfg.n_layer):
        lp = {"norm1": ones(C), "wq": norm((C, Cq)), "wk": norm((C, Ckv)),
              "wv": norm((C, Ckv)), "q_norm": ones(D), "k_norm": ones(D),
              "wo": norm((Cq, C)), "norm2": ones(C)}
        if cfg.is_sparse_layer(i):
            E, Eh = cfg.n_experts, len(cfg.experts_held)
            F, Fs = cfg.moe_intermediate_size, cfg.shared_intermediate_size
            lp.update(router=norm((C, E)), router_bias=norm((E,)),
                      e_gate=norm((Eh, C, F)), e_up=norm((Eh, C, F)),
                      e_down=norm((Eh, F, C)))
            if Fs:
                lp.update(s_gate=norm((C, Fs)), s_up=norm((C, Fs)),
                          s_down=norm((Fs, C)))
        else:
            F = cfg.intermediate_size
            lp.update(w_gate=norm((C, F)), w_up=norm((C, F)),
                      w_down=norm((F, C)))
        layers.append(lp)
    return {"wte": norm((cfg.vocab_size, C)), "layers": layers,
            "norm_f": ones(C), "lm_head": norm((C, cfg.vocab_size))}


def param_count(params: Params) -> int:
    return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))


# ------------------------------------------------------------- the layers

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


def _qkv(h, lp, cfg: ModelConfig, positions, window_layer: bool):
    """h (..., T, C) -> merged q (..., T, Hq*D), k, v (..., T, Hkv*D):
    pre-norm, projections, QK-norm over the head dim, and RoPE where the
    layer carries a position."""
    cd = h.dtype
    D = cfg.head_dim
    a = _rms(h, lp["norm1"], cfg.layernorm_eps)
    lead = a.shape[:-1]
    q = _mm(a, lp["wq"]).astype(cd).reshape(lead + (cfg.n_head, D))
    k = _mm(a, lp["wk"]).astype(cd).reshape(lead + (cfg.kv_heads, D))
    v = _mm(a, lp["wv"]).astype(cd)
    q = _rms(q, lp["q_norm"], cfg.layernorm_eps)
    k = _rms(k, lp["k_norm"], cfg.layernorm_eps)
    if window_layer:                 # full layers carry no position
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q.reshape(lead + (-1,)), k.reshape(lead + (-1,)), v


def _swiglu(m, w_gate, w_up, w_down):
    g, u = _mm(m, w_gate), _mm(m, w_up)
    return _mm((jax.nn.silu(g) * u).astype(m.dtype), w_down)


def route(m, lp, cfg: ModelConfig):
    """The router over ALL ``n_experts``, float32 at full precision:
    ``(weights (R, E), chosen ids (R, k))``. Selection by ``s + b``, weights
    from ``s`` alone, normalised over the k chosen, times the scaling;
    weights of unchosen experts are exactly 0."""
    s = jax.nn.sigmoid(jnp.dot(m.astype(_F32), lp["router"].astype(_F32),
                               precision=_HI))
    _, top = jax.lax.top_k(s + lp["router_bias"].astype(_F32),
                           cfg.experts_per_token)
    chosen = (top[..., None] == jnp.arange(cfg.n_experts)).any(-2)
    w = jnp.where(chosen, s, 0.0)
    return w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scaling, top


def moe(m, lp, cfg: ModelConfig):
    """The expert layer of THIS share over rows m (R, C): ``(y (R, C) f32,
    chosen ids (R, k), routed pairs that landed on held experts)``. Every
    held expert runs on every row and the router's weight (0 where the
    expert was not chosen) scales its hidden row before ONE down
    projection contracted over (expert, width): no token is dropped and
    nothing is gathered; at decode the step is bound by streaming the
    held experts' weights either way."""
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


@jax.named_scope("head")
def _head(x, params: Params, cfg: ModelConfig):
    x = _rms(x, params["norm_f"], cfg.layernorm_eps)
    return _mm(x, params["lm_head"])


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

    def attend(q, k, v, window: int):
        """One row of the batch: q (T, Cq), k, v (T, Ckv)."""
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
                kb, vb, kp = k, v, positions
                ok = kp[None] <= qp[:, None]
            s = _grouped_scores(jax.lax.dynamic_slice_in_dim(q, q0, blk),
                                kb, cfg)
            p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), -1)
            return _grouped_values(p, vb, cfg)
        out = jax.lax.map(rows, jnp.arange(T // blk))
        return out.reshape(T, -1)

    for i, lp in enumerate(params["layers"]):
        win = cfg.is_window_layer(i)
        scope = "attn_swa" if win else "attn_global"
        with jax.named_scope(scope):
            q, k, v = _qkv(x, lp, cfg, positions[None], win)
            att = jax.vmap(partial(
                attend, window=cfg.sliding_window if win else 0))(q, k, v)
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


def paged_page_size(cache: Dict[str, jnp.ndarray]) -> int:
    return int(next(iter(cache.values())).shape[2])


def _kinds(cfg: ModelConfig) -> List[Tuple[bool, int]]:
    """Per layer ``(window layer?, index among its kind)``."""
    out, n_w, n_g = [], 0, 0
    for i in range(cfg.n_layer):
        if cfg.is_window_layer(i):
            out.append((True, n_w))
            n_w += 1
        else:
            out.append((False, n_g))
            n_g += 1
    return out


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
    for i, (lp, (win, j)) in enumerate(zip(params["layers"], _kinds(cfg))):
        with jax.named_scope("attn_swa" if win else "attn_global"):
            q, k, v = _qkv(x, lp, cfg, pos_eff[:, None], win)
            kn, vn = (f"wk{j}", f"wv{j}") if win else (f"k{j}", f"v{j}")
            if use_pallas:
                # the kernel addresses (layer, page): these arrays have
                # one layer
                from ..ops.paged_pallas import paged_gqa_attention
                att = paged_gqa_attention(
                    q, k, v, cc[kn], cc[vn],
                    ring_tables if win else tables, pos_eff,
                    n_head=cfg.n_head, n_kv_head=cfg.kv_heads, layer=0,
                    attn_window=W if win else 0,
                    page0=first if win else None,
                    name=("swa_window_attention" if win
                          else "paged_window_attention"))
            else:
                att = _xla_paged_attention(
                    q, k, v, cc[kn][0], cc[vn][0],
                    ring_tables if win else tables, pos_eff,
                    first if win else jnp.zeros_like(first),
                    W if win else 0, cfg)
            with jax.named_scope("kv_scatter"):
                p_at = ring_phys if win else phys
                cc[kn] = cc[kn].at[0, p_at, woff, :].set(
                    k[:, 0].astype(cc[kn].dtype), mode="drop")
                cc[vn] = cc[vn].at[0, p_at, woff, :].set(
                    v[:, 0].astype(cc[vn].dtype), mode="drop")
            x = x + _mm(att, lp["wo"]).astype(cd)
        h, _, n = _mlp(x[:, 0], lp, cfg, i)
        x = h[:, None]
        pairs = pairs + n
    return _head(x[:, 0], params, cfg), cc, pairs


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


def decode_window_paged(params: Params, tok, pos, active, budget, eos,
                        tables, cache, rngs, cfg: ModelConfig, *, sample_fn,
                        length: int, use_pallas: bool = False,
                        shardings=None):
    """``models.gpt.decode_window_paged`` for this family: ``length``
    decode + sample steps in one program, the same carry and the same
    returned tuple. The token block gains ONE trailing column, the routed
    pairs that landed on held experts at each step (``STEP_COUNTERS``):
    the engine fetches it with the tokens and strips it."""
    def body(carry, _):
        tok, pos, active, budget, cache, rngs = carry
        logits, cache, pairs = decode_step_paged(
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
        row = jnp.concatenate([nxt, pairs[None].astype(nxt.dtype)])
        return (tok, pos, active, budget, cache, rngs), (row, emitted)

    carry = (tok, pos, active, budget, cache, rngs)
    (tok, pos, active, budget, cache, rngs), (toks, emitted) = jax.lax.scan(
        body, carry, None, length=length)
    return toks, emitted, tok, pos, active, budget, cache, rngs


#: what the token block's trailing columns count, in order
STEP_COUNTERS = ("moe_pairs_held",)


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
    for i, (lp, (win, j)) in enumerate(zip(params["layers"], _kinds(cfg))):
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
                with jax.named_scope("kv_scatter"):
                    cc[kn] = cc[kn].at[0, phys, woff, :].set(
                        k.astype(cc[kn].dtype), mode="drop")
                    cc[vn] = cc[vn].at[0, phys, woff, :].set(
                        v.astype(cc[vn].dtype), mode="drop")
                att = _prefill_full_attention(
                    q, cc[kn][0], cc[vn][0], table_row, positions, n_blk,
                    ppb, cfg)
            x = x + _mm(att.astype(cd), lp["wo"]).astype(cd)
        x, _, _ = _mlp(x, lp, cfg, i)
    return cc


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
