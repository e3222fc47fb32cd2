"""The ``olmo_hybrid`` family (Olmo-Hybrid-7B's ``config.json``: OLMo 3's
blocks with three Gated DeltaNet layers to one full-attention layer),
serve-only.

Post-norm residual blocks, RMSNorm everywhere, no bias in any projection
(OLMo 3): ``h = x + RMSNorm(op(x); norm1)``, ``y = h + RMSNorm(ff(h);
norm2)``, ``ff`` a dense SwiGLU in every layer.

- ``op`` of a LINEAR layer (Gated DeltaNet, Yang et al. 2024,
  arXiv:2412.06464; ``transformers``' ``qwen3_next`` writes the same
  rule): ``q, k, v = SiLU(causal depthwise conv of conv_kernel_dim taps
  over W_q x, W_k x, W_v x)``; per head ``q <- q / |q| * d_k ** -0.5``,
  ``k <- k / |k|`` (l2 with eps 1e-6); ``beta = 2 sigmoid(W_b x)`` (``allow_
  neg_eigval``: beta in (0, 2)); ``alpha = exp(-exp(A_log) softplus(W_a x
  + dt_bias))``; the delta rule over a ``d_k x d_v`` float32 state a head
  (``ops/gated_delta.py``); ``W_o [RMSNorm_dv(o_h; o_norm) * SiLU(W_g
  x)_h]`` (the gated output norm, one gain for all heads);
- ``op`` of a FULL layer: ``q = RMSNorm(W_q x; q_norm)`` and ``k =
  RMSNorm(W_k x; k_norm)`` over the WHOLE projection (OLMo 3's QK-norm),
  multi-head causal softmax attention, NO rotary position (``rope_theta``
  null), ``W_o``;
- the head is untied (``lm_head``), after the final norm.

Parameters are a LIST of per-layer dicts (``models/exaone_moe.py``), made
leaf by leaf on the device in ``cfg.param_dtype``.

Three kinds of state side by side (``init_paged_kv_pool``): a full layer
``j`` keeps paged history in ``k{j}`` / ``v{j}`` (1, n_pages, page, C),
walked through the slot's page table; a linear layer ``j`` keeps ``c{j}``
(n_slots, taps - 1, 2 Hk + Hv): the slot's last columns of the conv's
input, oldest first, and ``d{j}`` (n_slots, ``gated_delta.state_shape``):
the slot's float32 delta-rule state, heads side by side on the lanes.
Entries that start with ``c`` or ``d`` are not pool pages: admission
reserves nothing for them and page copies skip them.

**Whose state a row reads.** A sequence's first position reads zeros
whatever the slot held: a prefill chunk at offset 0 and a decode step at
position 0 start both kinds from zeros, so a reused slot needs no reset.
The engine's first decode step of a request re-runs the prompt's last
position (for pages an idempotent rewrite), and a decode step advances
the state: so a prefill chunk leaves both kinds as they stand BEFORE
position ``limit - 1`` (its rows from there on leave the state alone).

The serving entry points are those of ``models/gpt.py``
(``models/families.py`` hands the engine one family's set):
``prefill_chunk_paged``, ``decode_step_paged``, ``decode_window_paged``;
the mixed prefill+decode window and speculative verify are REFUSED by
name.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops import gated_delta
from ..utils.sanitize import check_in_bounds
from . import layers
from .layers import Params, _dtype, _mm, _rms, paged_page_size

#: pool-dict entries that are a slot's conv columns / delta-rule state
CONV_ENTRY_PREFIX = "c"
DELTA_ENTRY_PREFIX = "d"

#: the leaves the serving entry points read ONLY in the compute dtype
#: (``models/exaone_moe.py`` ``SERVE_CAST_LEAVES``). The norm gains, the
#: conv taps, ``A_log`` and ``dt_bias`` are read as float32 and are not
#: here.
SERVE_CAST_LEAVES = ("wte", "lm_head", "wq", "wk", "wv", "wo", "wg", "wa",
                     "wb", "w_gate", "w_up", "w_down")

#: no experts: the decode window's token block carries no counter
STEP_COUNTERS = ()

#: rows of a whole-sequence forward that go through a layer at a time (a
#: multiple of the chunked scan's chunk)
FORWARD_BLOCK = 512

#: KV positions a prefill chunk's full-attention layers read per loop turn
PREFILL_KV_BLOCK = 512

#: l2 norm's eps on q and k (FLA's, and ``transformers``' ``l2norm``)
L2_EPS = 1e-6

_F32 = jnp.float32


def linear_widths(cfg: ModelConfig):
    """``(heads, key head, value head, conv taps)`` of a linear layer."""
    return (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim)


def conv_channels(cfg: ModelConfig) -> int:
    """Channels the conv runs over: q, k and v side by side."""
    H, dk, dv, _ = linear_widths(cfg)
    return H * (2 * dk + dv)


# ------------------------------------------------------------------ params

def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded normal(0, init_std) matrices and unit norm gains; conv taps
    of std ``taps ** -0.5`` (a depthwise filter's usual scale: at init_std
    v would all but vanish); ``A_log`` = log of U(1, 16) and ``dt_bias`` the
    inverse softplus of a log-uniform step in (0.001, 0.1), as FLA's Gated
    DeltaNet draws them; one jitted draw a leaf, cast to ``param_dtype``
    where it is drawn."""
    cfg.validate()
    C, V = cfg.n_embd, cfg.vocab_size
    H, dk, dv, R = linear_widths(cfg)
    Cq = cfg.n_head * cfg.head_dim
    norm, ones = layers.leaf_makers(rng, cfg)
    pd = _dtype(cfg.param_dtype)
    stack: List[Dict[str, jnp.ndarray]] = []
    for i in range(cfg.n_layer):
        if cfg.is_linear_layer(i):
            u = jax.random.uniform(jax.random.fold_in(rng, 1_000_000 + i),
                                   (2, H))
            dt = jnp.exp(jnp.log(1e-3) + u[1] * (jnp.log(0.1)
                                                 - jnp.log(1e-3)))
            lp = dict(wq=norm((C, H * dk)), wk=norm((C, H * dk)),
                      wv=norm((C, H * dv)), wg=norm((C, H * dv)),
                      wa=norm((C, H)), wb=norm((C, H)),
                      A_log=jnp.log(1.0 + 15.0 * u[0]).astype(pd),
                      dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
                      qkv_conv=norm((conv_channels(cfg), R))
                      * (R ** -0.5 / cfg.init_std),
                      o_norm=ones(dv), wo=norm((H * dv, C)))
        else:
            lp = dict(wq=norm((C, Cq)), wk=norm((C, cfg.kv_channels)),
                      wv=norm((C, cfg.kv_channels)), wo=norm((Cq, C)),
                      q_norm=ones(Cq), k_norm=ones(cfg.kv_channels))
        F = cfg.intermediate_size
        lp.update(norm1=ones(C), norm2=ones(C), w_gate=norm((C, F)),
                  w_up=norm((C, F)), w_down=norm((F, C)))
        stack.append(lp)
    return {"wte": norm((V, C)), "layers": stack, "norm_f": ones(C),
            "lm_head": norm((C, V))}


# ------------------------------------------------------------- the layers

@jax.named_scope("head")
def _head(x, params: Params, cfg: ModelConfig):
    """Final norm, then the untied head."""
    return _mm(_rms(x, params["norm_f"], cfg.layernorm_eps),
               params["lm_head"])


def _post(x, y, gain, cfg: ModelConfig):
    """OLMo 3's residual: ``x + RMSNorm(y)``."""
    return x + _rms(y.astype(x.dtype), gain, cfg.layernorm_eps)


def _ffn(x, lp, cfg: ModelConfig):
    with jax.named_scope("mlp"):
        y = layers._swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return _post(x, y, lp["norm2"], cfg)


def _qkv(x, lp, cfg: ModelConfig):
    """A full layer's q, k, v rows (..., C) -> (..., heads x head) each,
    q and k RMS-normed over the whole projection, in x's dtype."""
    eps = cfg.layernorm_eps
    q = _rms(_mm(x, lp["wq"]), lp["q_norm"], eps)
    k = _rms(_mm(x, lp["wk"]), lp["k_norm"], eps)
    return (q.astype(x.dtype), k.astype(x.dtype),
            _mm(x, lp["wv"]).astype(x.dtype))


def _l2(x):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _projections(x, lp):
    """Rows (..., C) -> the conv's input columns (..., 2 Hk + Hv) in x's
    dtype: W_q x, W_k x, W_v x side by side."""
    return jnp.concatenate([_mm(x, lp["wq"]), _mm(x, lp["wk"]),
                            _mm(x, lp["wv"])], -1).astype(x.dtype)


def _conv_taps(cols, lp):
    """SiLU of ``sum_j taps[:, j] * cols[j]`` in float32: cols (taps, ...,
    channels), oldest first, the last the row's own column."""
    w = lp["qkv_conv"].astype(_F32)                      # (channels, taps)
    return jax.nn.silu(sum(w[:, j] * cols[j].astype(_F32)
                           for j in range(w.shape[1])))


def _rule_inputs(conv, x, lp, cfg: ModelConfig):
    """The conv's output (..., channels) float32 and the rows x -> the
    delta rule's ``q, k (..., H, dk), v (..., H, dv), log alpha, beta
    (..., H)``, float32."""
    H, dk, dv, _ = linear_widths(cfg)
    lead = conv.shape[:-1]
    q, k, v = jnp.split(conv, [H * dk, 2 * H * dk], -1)
    q = _l2(q.reshape(lead + (H, dk))) * dk ** -0.5
    k = _l2(k.reshape(lead + (H, dk)))
    beta = jax.nn.sigmoid(_mm(x, lp["wb"]))
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    log_alpha = -jnp.exp(lp["A_log"].astype(_F32)) * jax.nn.softplus(
        _mm(x, lp["wa"]) + lp["dt_bias"].astype(_F32))
    return q, k, v.reshape(lead + (H, dv)), log_alpha, beta


def _gated_out(o, x, lp, cfg: ModelConfig):
    """``W_o [RMSNorm(o_h; o_norm) * SiLU(W_g x)_h]``: o (..., H, dv)
    float32 -> (..., C) in x's dtype."""
    gate = jax.nn.silu(_mm(x, lp["wg"])).reshape(o.shape)
    y = _rms(o, lp["o_norm"], cfg.layernorm_eps) * gate
    return _mm(y.reshape(o.shape[:-2] + (-1,)).astype(x.dtype),
               lp["wo"]).astype(x.dtype)


def _gdn_rows(x, lp, cfg: ModelConfig, before, S, taken):
    """A linear layer's mixer over a run of rows x (T, C), T a multiple of
    the scan's chunk, that follows the conv columns ``before`` (taps - 1,
    channels) and the state ``S`` (H, dk, dv): ``(its output (T, C) in x's
    dtype, every conv column with ``before`` in front, the state after the
    rows ``taken`` (T,) bool marks)``. A row not taken leaves the state
    as it found it."""
    T, R = x.shape[0], cfg.linear_conv_kernel_dim
    ext = jnp.concatenate([before.astype(x.dtype), _projections(x, lp)])
    # row t reads the columns t - (R - 1) .. t, which stand at ext[t + j]
    conv = _conv_taps([ext[j:j + T] for j in range(R)], lp)
    q, k, v, log_alpha, beta = _rule_inputs(conv, x, lp, cfg)
    with jax.named_scope("gdn_chunk_scan"):
        o, S = gated_delta.chunk_scan(
            q, k, v, jnp.where(taken[:, None], log_alpha, 0.0),
            jnp.where(taken[:, None], beta, 0.0), S)
    return _gated_out(o, x, lp, cfg), ext, S


# --------------------------------------------------- whole-sequence forward

def forward(params: Params, idx: jnp.ndarray, cfg: ModelConfig):
    """(B, T) ids -> (B, T, V) f32 logits, no cache and no state: the
    program's plain path (the same layers; the conv and the chunked scan
    over the whole sequence from zeros, einsum attention over
    ``FORWARD_BLOCK`` query rows at a time)."""
    cd = _dtype(cfg.dtype)
    B, T_in = idx.shape
    H, dk, dv, R = linear_widths(cfg)
    blk = min(FORWARD_BLOCK, -(-T_in // gated_delta.CHUNK)
              * gated_delta.CHUNK)
    # whole blocks: the padding lies after every real token, which a
    # causal model never reads, and is cut off again below
    idx = jnp.pad(idx, ((0, 0), (0, -T_in % blk)))
    T = idx.shape[1]
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx]
    before = jnp.zeros((R - 1, conv_channels(cfg)), cd)
    S0 = jnp.zeros((H, dk, dv), _F32)
    every = jnp.ones((T,), bool)
    for i, lp in enumerate(params["layers"]):
        if cfg.is_linear_layer(i):
            with jax.named_scope("gdn"):
                y = jax.vmap(lambda r, lp=lp: _gdn_rows(
                    r, lp, cfg, before, S0, every)[0])(x)
        else:
            with jax.named_scope("attn_global"):
                q, k, v = _qkv(x, lp, cfg)
                att = jax.vmap(lambda q, k, v: layers._sequence_attention(
                    q, k, v, cfg, blk, 0))(q, k, v)
                y = _mm(att.astype(cd), lp["wo"])
        x = _post(x, y, lp["norm1"], cfg)
        out = jax.lax.map(lambda r, lp=lp: _ffn(r, lp, cfg),
                          x.reshape(B * T // blk, blk, -1))
        x = out.reshape(B, T, -1)
    return _head(x[:, :T_in], params, cfg)


# --------------------------------------------------------------- the state

def init_paged_kv_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                       dtype=None, quant=None, n_slots: int = 0
                       ) -> Dict[str, jnp.ndarray]:
    """The three kinds of state (module docstring). ``n_slots`` sizes the
    conv columns and the delta-rule state: they belong to slots, not to
    requests' page budgets, so admission counts the full layers' pages
    only. The delta-rule state is float32 whatever the pool's dtype."""
    assert quant is None or not quant.kv_enabled, (
        "olmo_hybrid: quantised pools are refused (the grouped-query "
        "kernel reads plain pages)")
    assert n_slots >= 1, "olmo_hybrid's pool needs the slot count"
    dt = dtype or _dtype(cfg.dtype)
    H, dk, dv, R = linear_widths(cfg)
    pool = {}
    for j, _ in enumerate(cfg.paged_layers):
        for kind in "kv":
            pool[f"{kind}{j}"] = jnp.zeros(
                (1, n_pages, page_size, cfg.kv_channels), dt)
    for j, _ in enumerate(cfg.linear_layers):
        pool[f"{CONV_ENTRY_PREFIX}{j}"] = jnp.zeros(
            (n_slots, R - 1, conv_channels(cfg)), dt)
        pool[f"{DELTA_ENTRY_PREFIX}{j}"] = jnp.zeros(
            (n_slots,) + gated_delta.state_shape(H, dk, dv), _F32)
    return pool


# ------------------------------------------------------------------ decode

def decode_step_paged(params: Params, idx_t, pos, active, tables,
                      cache: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
                      use_pallas: bool = False, shardings=None):
    """One token a slot through the three kinds of state: ``(logits (B, V)
    f32, cache, None)``. Row ``b`` is slot ``b``. A linear layer rolls the
    slot's conv columns by the row's own (from zeros at position 0) and
    advances its delta-rule state one token (``gated_delta_decode``, in
    place; from zeros at position 0); a full layer walks the slot's page
    table, attends the STALE pages plus the fresh row and scatters
    afterwards (``ops.paged_pallas.paged_gqa_attention``). Inactive rows
    run at position 0; their writes are dropped and their state kept."""
    assert shardings is None, "olmo_hybrid serves on one chip"
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
    first = pos_eff == 0                     # nothing before the sequence
    cc = dict(cache)
    kinds = layers.layer_kinds(cfg, cfg.is_linear_layer)
    for i, (lp, (linear, j)) in enumerate(zip(params["layers"], kinds)):
        if linear:
            with jax.named_scope("gdn"):
                cn, dn = f"{CONV_ENTRY_PREFIX}{j}", f"{DELTA_ENTRY_PREFIX}{j}"
                cols = cc[cn]
                window = jnp.concatenate(
                    [jnp.where(first[:, None, None], 0, cols),
                     _projections(x, lp)[:, None].astype(cols.dtype)], 1)
                cc[cn] = jnp.where(active[:, None, None], window[:, 1:],
                                   cols)
                q, k, v, log_alpha, beta = _rule_inputs(
                    _conv_taps(jnp.moveaxis(window, 1, 0), lp), x, lp, cfg)
                with jax.named_scope("gdn_recurrence"):
                    step = (gated_delta.gated_delta_decode if use_pallas
                            else gated_delta.decode_xla)
                    o, cc[dn] = step(cc[dn], q, k, v, jnp.exp(log_alpha),
                                     beta, first, active)
                y = _gated_out(o, x, lp, cfg)
        else:
            with jax.named_scope("attn_global"):
                kn, vn = f"k{j}", f"v{j}"
                q, k, v = _qkv(x[:, None], lp, cfg)
                att = layers._decode_attention(
                    q, k, v, cc[kn], cc[vn], tables, pos_eff, cfg,
                    use_pallas=use_pallas)
                layers._scatter_rows(cc, kn, vn, phys, woff, k[:, 0],
                                     v[:, 0])
                y = _mm(att[:, 0], lp["wo"])
        x = _ffn(_post(x, y, lp["norm1"], cfg), lp, cfg)
    return _head(x, params, cfg), cc, None


#: ``length`` decode + sample steps in one program
decode_window_paged = layers.decode_window_of(decode_step_paged)


# ----------------------------------------------------------------- prefill

def _rows_taken(offset, limit, Pc: int):
    """How many of a chunk's rows the state takes in: those before
    position ``limit - 1``, which the first decode step re-runs."""
    return jnp.clip(jnp.minimum(limit - 1, offset + Pc) - offset, 0, Pc)


def prefill_chunk_paged(params: Params, idx, offset, limit, table_row,
                        slot, cache: Dict[str, jnp.ndarray],
                        cfg: ModelConfig, *, shardings=None):
    """One chunk (1, Pc) of ONE slot's prompt into the three kinds of
    state. Linear layers: the chunk's rows follow the slot's conv columns
    and state (zeros at offset 0, whatever the slot held), run the chunked
    scan, and leave the slot both as they stand before position
    ``min(limit - 1, offset + Pc)``: the next chunk's, or the first decode
    step's, which re-runs ``limit - 1`` (module docstring). Full layers:
    write the chunk's rows through the page table (positions >= ``limit``
    dropped), then read the slot's pages ``PREFILL_KV_BLOCK`` positions at
    a time up to the chunk's end with an online softmax."""
    assert shardings is None, "olmo_hybrid serves on one chip"
    cd = _dtype(cfg.dtype)
    Pc = idx.shape[1]
    assert Pc % gated_delta.CHUNK == 0, (
        f"a prefill chunk of {Pc} is not whole chunks of the scan's "
        f"{gated_delta.CHUNK}")
    psz = paged_page_size(cache)
    mp = table_row.shape[0]
    _, _, dv, R = linear_widths(cfg)
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
    kinds = layers.layer_kinds(cfg, cfg.is_linear_layer)
    for i, (lp, (linear, j)) in enumerate(zip(params["layers"], kinds)):
        if linear:
            with jax.named_scope("gdn"):
                cn, dn = f"{CONV_ENTRY_PREFIX}{j}", f"{DELTA_ENTRY_PREFIX}{j}"
                later = offset > 0
                y, ext, S = _gdn_rows(
                    x, lp, cfg, jnp.where(later, cc[cn][slot], 0),
                    jnp.where(later, gated_delta.to_heads(cc[dn][slot], dv),
                              0.0),
                    jnp.arange(Pc) < seen)
                # the columns before chunk row ``seen`` stand at ext[seen:]
                cc[cn] = cc[cn].at[slot].set(
                    jax.lax.dynamic_slice_in_dim(ext, seen, R - 1)
                    .astype(cc[cn].dtype))
                cc[dn] = cc[dn].at[slot].set(
                    gated_delta.from_heads(S).astype(cc[dn].dtype))
        else:
            with jax.named_scope("attn_global"):
                kn, vn = f"k{j}", f"v{j}"
                q, k, v = _qkv(x, lp, cfg)
                layers._scatter_rows(cc, kn, vn, phys, woff, k, v)
                att = layers._prefill_full_attention(
                    q, cc[kn][0], cc[vn][0], table_row, positions, n_blk,
                    ppb, cfg)
                y = _mm(att.astype(cd), lp["wo"])
        x = _ffn(_post(x, y, lp["norm1"], cfg), lp, cfg)
    return cc


# --------------------------------------------------------------- refusals

def mixed_window_paged(*args, **kw):
    raise NotImplementedError(
        "olmo_hybrid has no mixed prefill+decode window: a prefill chunk "
        "carries a linear layer's conv columns and delta-rule state a slot "
        "at a time (prefill_chunk_paged); serve it with decode_window=1")


def verify_step_paged(*args, **kw):
    raise NotImplementedError(
        "olmo_hybrid has no speculative verify step: a rejected draft "
        "cannot be rolled back out of the linear layers' delta-rule state, "
        "and no snapshot of it is kept")
