"""The ``lfm2_moe`` family (LFM2-24B-A2B's ``config.json``, the layer
equations of ``transformers``' ``lfm2_moe``), serve-only.

Pre-norm residual blocks, RMSNorm everywhere, no bias in any projection:
``h = x + op(rms(x; norm1))``, ``y = h + ff(rms(h; norm2))``.

- ``op`` of a CONV layer, the gated short convolution: ``B, C, v =
  split3(u conv_in)``, ``s = B * v``, ``c_t = sum_{j < reach} conv_w[:, j] *
  s_{t - (reach - 1) + j}`` (depthwise, causal, zero before the sequence),
  ``(C * c) conv_out``. Its state is the last ``conv_reach`` columns of
  ``s``, hidden-size wide, a layer and a sequence;
- ``op`` of a FULL layer: 32 query heads read 8 KV heads (query head ``n``
  reads KV head ``n // 4``), RMSNorm over the head dim of q and k, then
  rotate-half RoPE, causal over everything;
- ``ff``: the first ``num_dense_layers`` layers a dense SwiGLU, every later
  layer sigmoid-routed experts (``models/layers.py`` ``moe``: the expert
  layer ``models/exaone_moe.py`` calls too; no shared expert here);
- the head is the embedding transposed, after the final norm.

Parameters are a LIST of per-layer dicts (``models/exaone_moe.py``), made
leaf by leaf on the device in ``cfg.param_dtype``; a conv layer has
``conv_in`` (C, 3C), ``conv_w`` (C, reach), ``conv_out`` (C, C) where a
full layer has ``wq`` ... ``wo``.

Two kinds of state side by side (``init_paged_kv_pool``): a full layer ``j``
keeps paged history in ``k{j}`` / ``v{j}`` (1, n_pages, page, Ckv), walked
through the slot's page table like GPT-2's pool; a conv layer ``j`` keeps
``c{j}`` (n_slots, reach, C): the slot's last ``reach`` columns of ``s``,
oldest first, whatever the context. Entries that start with ``c`` are not
pool pages (``CONV_ENTRY_PREFIX``): admission reserves nothing for them
and page copies skip them.

**Whose state a row reads.** A sequence's first position reads zeros
whatever the slot held: a prefill chunk at offset 0 and a decode step at
position 0 start from a zero state, so a reused slot needs no reset. The
engine's first decode step of a request re-runs the prompt's last position
(for pages an idempotent rewrite), and a decode step rolls the state: so a
prefill chunk leaves the state as it stands BEFORE position ``limit - 1``.

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
from ..utils.sanitize import check_in_bounds
from . import layers
from .layers import (STEP_COUNTERS, Params, _dtype, _mlp, _mm,  # noqa: F401
                     _qkv, _rms, paged_page_size)

#: pool-dict entries that are per-slot conv state, not pool pages
CONV_ENTRY_PREFIX = "c"

#: the leaves the serving entry points read ONLY in the compute dtype
#: (``models/exaone_moe.py`` ``SERVE_CAST_LEAVES``). The norm gains, the
#: conv taps, the router and its bias are read as float32 and are not here.
SERVE_CAST_LEAVES = ("wte", "wq", "wk", "wv", "wo", "conv_in", "conv_out",
                     "e_gate", "e_up", "e_down", "w_gate", "w_up", "w_down")

#: rows of a whole-sequence forward that go through attention / the MLP at
#: a time (``models/exaone_moe.py`` ``FORWARD_BLOCK``)
FORWARD_BLOCK = 512

#: KV positions a prefill chunk's full-attention layers read per loop turn
PREFILL_KV_BLOCK = 512

_F32 = jnp.float32


# ------------------------------------------------------------------ params

def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded normal(0, init_std) matrices, unit norm gains, a small
    non-zero router bias (so selection and weighting can be told apart),
    and conv taps of std ``reach ** -0.5`` (a depthwise filter's usual
    scale: at init_std its three taps would all but silence the operator);
    one jitted draw a leaf, cast to ``param_dtype`` where it is drawn."""
    cfg.validate()
    C, D, R = cfg.n_embd, cfg.head_dim, cfg.conv_reach
    Cq, Ckv = cfg.n_head * D, cfg.kv_channels
    norm, ones = layers.leaf_makers(rng, cfg)
    stack: List[Dict[str, jnp.ndarray]] = []
    for i in range(cfg.n_layer):
        lp = {"norm1": ones(C)}
        if cfg.is_conv_layer(i):
            lp.update(conv_in=norm((C, 3 * C)),
                      conv_w=norm((C, R)) * (R ** -0.5 / cfg.init_std),
                      conv_out=norm((C, C)))
        else:
            lp.update(wq=norm((C, Cq)), wk=norm((C, Ckv)),
                      wv=norm((C, Ckv)), q_norm=ones(D), k_norm=ones(D),
                      wo=norm((Cq, C)))
        lp["norm2"] = ones(C)
        lp.update(layers.mlp_params(norm, cfg, i))
        stack.append(lp)
    return {"wte": norm((cfg.vocab_size, C)), "layers": stack,
            "norm_f": ones(C)}


# ------------------------------------------------------------- the layers

@jax.named_scope("head")
def _head(x, params: Params, cfg: ModelConfig):
    """Final norm, then the embedding transposed (the head is tied)."""
    x = _rms(x, params["norm_f"], cfg.layernorm_eps)
    return jnp.einsum("...c,vc->...v", x, params["wte"].astype(x.dtype),
                      preferred_element_type=_F32)


def _conv_gates(h, lp, cfg: ModelConfig):
    """h (..., C) -> ``(s, C gate)``: pre-norm, the in projection split in
    three, ``s = B * v``."""
    a = _rms(h, lp["norm1"], cfg.layernorm_eps)
    b, c, v = jnp.split(_mm(a, lp["conv_in"]).astype(h.dtype), 3, -1)
    return b * v, c


def _conv_taps(cols, lp):
    """``sum_j conv_w[:, j] * cols[j]`` in float32: cols (reach, ..., C),
    oldest first, the last one the row's own ``s``."""
    w = lp["conv_w"].astype(_F32)                                # (C, reach)
    return sum(w[:, j] * cols[j].astype(_F32)
               for j in range(w.shape[1]))


def _short_conv(h, lp, before, cfg: ModelConfig):
    """The whole operator over a run of rows h (T, C) that follows the
    ``reach`` columns ``before`` (reach, C) of ``s``: ``(its output (T, C)
    in h's dtype, every column of s with ``before`` in front (reach + T,
    C))``."""
    R, T = cfg.conv_reach, h.shape[0]
    s, gate = _conv_gates(h, lp, cfg)
    ext = jnp.concatenate([before.astype(s.dtype), s])
    # row t reads s_{t - (R - 1) + j}, which stands at ext[t + 1 + j]
    c = _conv_taps([ext[j + 1:j + 1 + T] for j in range(R)], lp)
    return _mm(gate * c.astype(h.dtype), lp["conv_out"]).astype(h.dtype), ext


# --------------------------------------------------- whole-sequence forward

def forward(params: Params, idx: jnp.ndarray, cfg: ModelConfig, *,
            return_routing: bool = False):
    """(B, T) ids -> (B, T, V) f32 logits, no cache and no state: the
    program's plain path (same layers; the short conv over the whole
    sequence from zeros, einsum attention over ``FORWARD_BLOCK`` query rows
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
    zeros = jnp.zeros((cfg.conv_reach, cfg.n_embd), cd)
    tops = []
    for i, lp in enumerate(params["layers"]):
        if cfg.is_conv_layer(i):
            with jax.named_scope("short_conv"):
                x = x + jax.vmap(
                    lambda r, lp=lp: _short_conv(r, lp, zeros, cfg)[0])(x)
        else:
            with jax.named_scope("attn_global"):
                q, k, v = _qkv(x, lp, cfg, positions[None], True)
                att = jax.vmap(lambda q, k, v: layers._sequence_attention(
                    q, k, v, cfg, blk, 0))(q, k, v)
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


# --------------------------------------------------------------- the state

def init_paged_kv_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                       dtype=None, quant=None, n_slots: int = 0
                       ) -> Dict[str, jnp.ndarray]:
    """Both kinds of state (module docstring). ``n_slots`` sizes the conv
    state: it belongs to slots, not to requests' page budgets, so
    admission counts the full layers' pages only."""
    assert quant is None or not quant.kv_enabled, (
        "lfm2_moe: quantised pools are refused (the grouped-query kernel "
        "reads plain pages)")
    assert n_slots >= 1, "lfm2_moe's pool needs the slot count"
    dt = dtype or _dtype(cfg.dtype)
    pool = {}
    for j, _ in enumerate(cfg.paged_layers):
        pool[f"k{j}"] = jnp.zeros((1, n_pages, page_size, cfg.kv_channels),
                                  dt)
        pool[f"v{j}"] = jnp.zeros((1, n_pages, page_size, cfg.kv_channels),
                                  dt)
    for j, _ in enumerate(cfg.conv_layers):
        pool[f"c{j}"] = jnp.zeros((n_slots, cfg.conv_reach, cfg.n_embd), dt)
    return pool


# ------------------------------------------------------------------ decode

def decode_step_paged(params: Params, idx_t, pos, active, tables,
                      cache: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
                      use_pallas: bool = False, shardings=None):
    """One token a slot through both kinds of state: ``(logits (B, V)
    f32, cache, routed pairs on held experts)``. Row ``b`` is slot ``b``. A
    conv layer rolls the slot's state by the row's ``s`` (from zeros at
    position 0) and reads all of it; a full layer walks the slot's page
    table, attends the STALE pages plus the fresh row and scatters
    afterwards (``ops.paged_pallas.paged_gqa_attention``). Inactive rows
    run at position 0; their writes are dropped and their state kept."""
    assert shardings is None, "lfm2_moe serves on one chip"
    cd = _dtype(cfg.dtype)
    B = idx_t.shape[0]
    psz = paged_page_size(cache)
    mp = tables.shape[1]
    pos_eff = jnp.where(active, pos, 0)
    check_in_bounds(pos_eff, 1, mp * psz, what="paged decode write")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx_t]                       # (B, C)
    phys = tables[jnp.arange(B), jnp.minimum(pos_eff // psz, mp - 1)]
    woff = jnp.where(active, pos_eff % psz, psz)       # inactive: dropped
    first = (pos_eff == 0)[:, None, None]    # nothing before the sequence
    keep = active[:, None, None]
    cc = dict(cache)
    pairs = jnp.int32(0)
    kinds = layers.layer_kinds(cfg, cfg.is_conv_layer)
    for i, (lp, (conv, j)) in enumerate(zip(params["layers"], kinds)):
        if conv:
            with jax.named_scope("short_conv"):
                name = f"c{j}"
                s, gate = _conv_gates(x, lp, cfg)
                state = cc[name]
                rolled = jnp.concatenate(
                    [jnp.where(first, 0, state[:, 1:]),
                     s[:, None].astype(state.dtype)], 1)       # (B, R, C)
                c = _conv_taps(jnp.moveaxis(rolled, 1, 0), lp)
                x = x + _mm(gate * c.astype(cd), lp["conv_out"]).astype(cd)
                cc[name] = jnp.where(keep, rolled, state)
        else:
            with jax.named_scope("attn_global"):
                kn, vn = f"k{j}", f"v{j}"
                q, k, v = _qkv(x[:, None], lp, cfg, pos_eff[:, None], True)
                att = layers._decode_attention(
                    q, k, v, cc[kn], cc[vn], tables, pos_eff, cfg,
                    use_pallas=use_pallas)
                layers._scatter_rows(cc, kn, vn, phys, woff, k[:, 0],
                                     v[:, 0])
                x = x + _mm(att[:, 0], lp["wo"]).astype(cd)
        x, _, n = _mlp(x, lp, cfg, i)
        pairs = pairs + n
    return _head(x, params, cfg), cc, pairs


#: ``length`` decode + sample steps in one program
decode_window_paged = layers.decode_window_of(decode_step_paged)


# ----------------------------------------------------------------- prefill

def _rows_taken(offset, limit, Pc: int):
    """How many of a chunk's rows a conv layer's state takes in: those
    before position ``limit - 1``, which the first decode step re-runs."""
    return jnp.clip(jnp.minimum(limit - 1, offset + Pc) - offset, 0, Pc)


def prefill_chunk_paged(params: Params, idx, offset, limit, table_row,
                        slot, cache: Dict[str, jnp.ndarray],
                        cfg: ModelConfig, *, shardings=None):
    """One chunk (1, Pc) of ONE slot's prompt into both kinds of state.
    Conv layers: the chunk's rows follow the slot's state (zeros at offset
    0, whatever the slot held), and the slot is left the ``reach`` columns
    before position ``min(limit - 1, offset + Pc)``: the next chunk's, or
    the first decode step's, which re-runs ``limit - 1`` (module
    docstring). Full layers: write the chunk's rows through the page table
    (positions >= ``limit`` dropped), then read the slot's pages
    ``PREFILL_KV_BLOCK`` positions at a time up to the chunk's end with an
    online softmax."""
    assert shardings is None, "lfm2_moe serves on one chip"
    cd = _dtype(cfg.dtype)
    Pc = idx.shape[1]
    psz = paged_page_size(cache)
    mp = table_row.shape[0]
    R = cfg.conv_reach
    positions = offset + jnp.arange(Pc, dtype=jnp.int32)
    check_in_bounds(offset, 1, cfg.block_size, what="paged prefill chunk")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx[0]]                      # (Pc, C)
    valid = (positions < limit) & (positions < mp * psz)
    phys = table_row[jnp.minimum(positions // psz, mp - 1)]
    woff = jnp.where(valid, positions % psz, psz)
    seen = _rows_taken(offset, limit, Pc)
    n_blk = (offset + Pc + PREFILL_KV_BLOCK - 1) // PREFILL_KV_BLOCK
    ppb = PREFILL_KV_BLOCK // psz
    cc = dict(cache)
    kinds = layers.layer_kinds(cfg, cfg.is_conv_layer)
    for i, (lp, (conv, j)) in enumerate(zip(params["layers"], kinds)):
        if conv:
            with jax.named_scope("short_conv"):
                name = f"c{j}"
                before = jnp.where(offset > 0, cc[name][slot], 0)
                y, ext = _short_conv(x, lp, before, cfg)
                x = x + y
                # the columns before chunk row ``seen`` stand at ext[seen:]
                cc[name] = cc[name].at[slot].set(
                    jax.lax.dynamic_slice_in_dim(ext, seen, R)
                    .astype(cc[name].dtype))
        else:
            with jax.named_scope("attn_global"):
                kn, vn = f"k{j}", f"v{j}"
                q, k, v = _qkv(x, lp, cfg, positions, True)
                layers._scatter_rows(cc, kn, vn, phys, woff, k, v)
                att = layers._prefill_full_attention(
                    q, cc[kn][0], cc[vn][0], table_row, positions, n_blk,
                    ppb, cfg)
                x = x + _mm(att.astype(cd), lp["wo"]).astype(cd)
        x, _, _ = _mlp(x, lp, cfg, i)
    return cc


# --------------------------------------------------------------- refusals

def mixed_window_paged(*args, **kw):
    raise NotImplementedError(
        "lfm2_moe has no mixed prefill+decode window: a prefill chunk "
        "carries a conv layer's state a slot at a time "
        "(prefill_chunk_paged); serve it with decode_window=1")


def verify_step_paged(*args, **kw):
    raise NotImplementedError(
        "lfm2_moe has no speculative verify step: a rejected draft's "
        "columns would have to be rolled back out of the conv layers' "
        "state, and no snapshot of it is kept")
