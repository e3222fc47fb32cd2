"""Fused single-stream decode-step kernel: every transformer layer of one
autoregressive token in ONE Pallas call.

Why: the XLA decode step at char-GPT scale is op-issue-latency-bound, not
bandwidth-bound — ~125 device ops per token (per-layer ln/matvec/attention
/mlp fusions) at ~0.4 us issue latency each ≈ 102 us/token against a
~28 us parameter-byte floor (benchmarks/RESULTS.md decode roofline,
round 4). This kernel replaces the whole layer loop with one launch:
grid over layers ("arbitrary" = sequential), the residual-stream row
carried in VMEM scratch across grid steps, per-layer weights and the
layer's KV cache fetched as double-buffered blocks — so the per-token
cost approaches the parameter stream time instead of the op count. The
reference's decode ancestry is the O(T^2) full re-forward per token
(GPT1.py:196-212); the XLA cache path replaced the re-forward, this
kernel replaces the op soup.

Scope: B == 1 (the single-stream latency workload, BASELINE config 5);
batched decode stays on the XLA path where per-op work is large enough
to hide issue latency. The kernel computes attention against the STALE
cache block masked to positions < pos plus an explicit fresh-KV column
(bit-equivalent to write-then-attend: cache[pos] would equal the fresh
k/v), and emits the fresh per-layer K/V rows; the caller scatters them
into the cache at ``pos`` with one dynamic_update_slice over all layers.

Numerics mirror the XLA decode body (models/gpt.py decode_step /
ops/attention.cached_attention): LN statistics in f32, matmuls on
compute-dtype operands with f32 accumulation, attention scores and
softmax in f32, probabilities cast to the cache dtype for the PV
product. Parity with decode_step is asserted in tests/test_generate.py.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..quant.kv import fake_quantize_row_body as _fake_quant_row
from .flash_pallas import (LANES, NEG_INF, _compiler_params,
                           _interpret_mode, _smem_spec, _vmem_spec, pltpu)

# Per-layer VMEM budget for the fused kernel: weights (qkv C*3C + proj
# C*C + mlp 2*C*4C), the (H, S, D) k/v cache blocks, and the (S, lanes)
# score temporaries, double-buffered across layer grid steps. 6 MiB
# covers char-GPT (3.7 MiB at C=384, S=256 bf16) with margin and
# excludes GPT-2 124M (14+ MiB), whose decode is byte-floor-bound on
# the XLA path anyway (RESULTS.md roofline: 1.29x of floor).
FUSED_LAYER_BYTES = 6 * 1024 * 1024


def fused_decode_supported(cfg, batch: int, itemsize: int = 2,
                           seq_len: int = 0) -> bool:
    """Envelope: single stream, lane-aligned head dim, per-layer weights
    + cache within FUSED_LAYER_BYTES. ``seq_len`` is the ACTUAL cache
    length (init_kv_cache callers may override max_len past
    cfg.block_size); 0 means cfg.block_size."""
    C, H = cfg.n_embd, cfg.n_head
    S = seq_len or cfg.block_size
    if batch != 1 or C % H != 0:
        return False
    D = C // H
    if D not in (32, 64, 128, 256) or S % 8 != 0:
        return False
    weights = (C * 3 * C + C * C + 2 * C * 4 * C) * itemsize
    cache = 2 * H * S * D * itemsize
    return weights + cache <= FUSED_LAYER_BYTES


# VMEM budget for the per-layer packed decode-attention kernel: one
# (S, C) K and V block per grid step plus (1, C)/(S, 1) temporaries.
# 8 MiB covers GPT-2 124M at S=1024 bf16 (2 * 1.5 MiB) with margin and
# S up to ~2048 at C=768.
PACKED_DECODE_BYTES = 8 * 1024 * 1024


def _packed_attn_backend_ok() -> bool:
    """Pallas lowering gate for the packed decode-attention kernel
    (tests monkeypatch this to exercise the interpret-mode kernel on
    CPU). Sharding safety (a bare pallas_call cannot be partitioned by
    GSPMD) is the caller's allow_pallas gate — models.gpt.decode_step."""
    return jax.default_backend() == "tpu"


def packed_decode_supported(cfg, itemsize: int = 2,
                            seq_len: int = 0) -> bool:
    """Envelope for the packed-layout decode attention kernel: head dim
    lane-sliceable and both (S, C) cache blocks within
    PACKED_DECODE_BYTES."""
    C, H = cfg.n_embd, cfg.n_head
    S = seq_len or cfg.block_size
    if C % H != 0:
        return False
    D = C // H
    if D not in (32, 64, 128, 256) or S % 8 != 0:
        return False
    return 2 * S * C * itemsize <= PACKED_DECODE_BYTES


def _packed_attn_kernel(pos_ref, q_ref, knew_ref, vnew_ref, kc_ref, vc_ref,
                        out_ref, *, n_head, head_dim, seq_len, scale):
    """One batch row's decode attention over the lane-packed (S, C)
    cache: heads are static D-wide lane slices of the packed row
    (exactly the packed-flash trick, flash_pallas.py packed section),
    so the cache block streams fully packed — no D-minor tile padding.
    Numerics per head mirror the fused decode kernel above (stale cache
    masked to < pos + explicit fresh column; f32 scores/softmax, probs
    cast to the cache dtype for PV)."""
    pos = pos_ref[0]
    S, D = seq_len, head_dim
    kpos = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    for i in range(n_head):
        sl = slice(i * D, (i + 1) * D)
        q = q_ref[:, sl].astype(jnp.float32)                    # (1, D)
        k_new = knew_ref[:, sl]
        v_new = vnew_ref[:, sl]
        kc = kc_ref[:, sl]                                      # (S, D)
        vc = vc_ref[:, sl]
        s = jnp.sum(kc.astype(jnp.float32) * q, axis=-1,
                    keepdims=True) * scale                      # (S, 1)
        s = jnp.where(kpos < pos, s, NEG_INF)
        s_new = jnp.sum(k_new.astype(jnp.float32) * q) * scale  # scalar
        m = jnp.maximum(jnp.max(s), s_new)
        p = jnp.exp(s - m)
        p_new = jnp.exp(s_new - m)
        denom = jnp.sum(p) + p_new
        w = (p / denom).astype(vc.dtype)
        pv = jnp.sum(w * vc, axis=0, keepdims=True)             # (1, D)
        out = pv + (p_new / denom).astype(v_new.dtype) * v_new
        out_ref[:, sl] = out.astype(out_ref.dtype)


def packed_decode_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                            v_new: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, pos: jnp.ndarray, *,
                            n_head: int) -> jnp.ndarray:
    """Decode attention for the packed (B, S, C) cache layout.

    q, k_new, v_new: (B, C) fresh merged rows; caches: (B, S, C) STALE
    (position ``pos`` not yet written). Returns the merged (B, C)
    attention output — bit-equivalent to writing k_new/v_new at ``pos``
    and attending positions <= pos (models.gpt._decode_step_packed does
    the write afterwards). Grid over B (parallel); each step streams one
    row's fully-packed cache blocks."""
    B, S, C = k_cache.shape
    D = C // n_head
    kernel = functools.partial(
        _packed_attn_kernel, n_head=n_head, head_dim=D, seq_len=S,
        scale=D ** -0.5)
    row = _vmem_spec((None, 1, C), lambda b: (b, 0, 0))
    kw = {"compiler_params": _compiler_params(1, 1)}
    out = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            _smem_spec(),
            row, row, row,
            _vmem_spec((None, S, C), lambda b: (b, 0, 0)),
            _vmem_spec((None, S, C), lambda b: (b, 0, 0)),
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, 1, C), q.dtype),
        name="decode_attention",
        interpret=_interpret_mode(),
        **kw,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q[:, None, :],
      k_new[:, None, :], v_new[:, None, :], k_cache, v_cache)
    return out[:, 0, :]


def _ln_row(x, scale, bias, eps):
    """(1, C) layernorm, f32 statistics, result in x.dtype — mirrors
    models.gpt._layer_norm."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _row_matmul(h, w, b):
    """(1, Cin) @ (Cin, Cout) + (1, Cout) on compute-dtype operands with
    f32 accumulation, result in h.dtype — mirrors `h @ W + b`."""
    y = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return (y + b.astype(jnp.float32)).astype(h.dtype)


def _decode_kernel(pos_ref, x0_ref, ln1s_ref, ln1b_ref, wqkv_ref, bqkv_ref,
                   wproj_ref, bproj_ref, ln2s_ref, ln2b_ref, wup_ref,
                   bup_ref, wdown_ref, bdown_ref, kc_ref, vc_ref,
                   xout_ref, newk_ref, newv_ref, x_ref, *, n_layer, n_head,
                   head_dim, seq_len, eps, scale, activation, packed_cache):
    l = pl.program_id(0)
    H, D, S = n_head, head_dim, seq_len
    C = H * D
    pos = pos_ref[0]

    @pl.when(l == 0)
    def _init():
        x_ref[...] = x0_ref[...]

    x = x_ref[...]                                   # (1, C) compute dtype
    h = _ln_row(x, ln1s_ref[...], ln1b_ref[...], eps)
    qkv = _row_matmul(h, wqkv_ref[...], bqkv_ref[...])   # (1, 3C)

    kpos = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    outs = []
    for i in range(H):
        q = qkv[:, i * D:(i + 1) * D].astype(jnp.float32)       # (1, D)
        k_new = qkv[:, C + i * D:C + (i + 1) * D]               # (1, D)
        v_new = qkv[:, 2 * C + i * D:2 * C + (i + 1) * D]
        newk_ref[:, i * D:(i + 1) * D] = k_new
        newv_ref[:, i * D:(i + 1) * D] = v_new
        if packed_cache:
            # lane slice of the (S, C) packed row — same trick as
            # packed_decode_attention below; fully-packed cache stream
            kc = kc_ref[:, i * D:(i + 1) * D]                   # (S, D)
            vc = vc_ref[:, i * D:(i + 1) * D]
        else:
            kc = kc_ref[i]                                      # (S, D)
            vc = vc_ref[i]
        # scores vs the stale cache, masked to positions < pos; the
        # fresh position's score rides a separate column (write-then-
        # attend equivalence: cache[pos] would hold exactly k_new)
        s = jnp.sum(kc.astype(jnp.float32) * q, axis=-1,
                    keepdims=True) * scale                      # (S, 1)
        s = jnp.where(kpos < pos, s, NEG_INF)
        s_new = jnp.sum(k_new.astype(jnp.float32) * q) * scale  # scalar
        m = jnp.maximum(jnp.max(s), s_new)
        p = jnp.exp(s - m)                                      # (S, 1)
        p_new = jnp.exp(s_new - m)
        denom = jnp.sum(p) + p_new
        w = (p / denom).astype(vc.dtype)
        pv = jnp.sum(w * vc, axis=0, keepdims=True)             # (1, D)
        out = pv + ((p_new / denom).astype(v_new.dtype) * v_new)
        outs.append(out.astype(x.dtype))
    attn = jnp.concatenate(outs, axis=1)                        # (1, C)
    attn = _row_matmul(attn, wproj_ref[...], bproj_ref[...])
    x_mid = x + attn
    h = _ln_row(x_mid, ln2s_ref[...], ln2b_ref[...], eps)
    h = _row_matmul(h, wup_ref[...], bup_ref[...])
    h = (jax.nn.gelu(h) if activation == "gelu" else jax.nn.relu(h))
    h = _row_matmul(h.astype(x.dtype), wdown_ref[...], bdown_ref[...])
    x_ref[...] = x_mid + h

    @pl.when(l == n_layer - 1)
    def _finalize():
        xout_ref[...] = x_ref[...]


def fused_decode_layers(x0: jnp.ndarray, blocks: Dict[str, jnp.ndarray],
                        pos: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                        cfg) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Run all n_layer blocks for one (B=1) decode token in one Pallas
    call. x0: (1, C) embedded input row (compute dtype); blocks: the
    layer-stacked param dict (weights will be cast to x0.dtype —
    hoisted out of the token scan by XLA exactly like the unfused
    path's per-use casts); cache: {"k","v"} — (L, 1, H, S, D) heads
    layout or (L, 1, S, C) packed layout, per
    ``cfg.decode_cache_layout``. Returns (x_out (1, C), updated
    cache)."""
    packed = cfg.decode_cache_layout == "packed"
    if packed:
        L, _, S, C = cache["k"].shape
        H = cfg.n_head
        D = C // H
    else:
        L, _, H, S, D = cache["k"].shape
        C = H * D
    cd = x0.dtype
    w = {k: v.astype(cd) for k, v in blocks.items()}
    # (L, width) row vectors -> (L, 1, width) so in-kernel refs are 2-d
    vec = lambda name: w[name].reshape(L, 1, -1)
    kernel = functools.partial(
        _decode_kernel, n_layer=L, n_head=H, head_dim=D, seq_len=S,
        eps=cfg.layernorm_eps, scale=D ** -0.5, activation=cfg.activation,
        packed_cache=packed)
    row = lambda width: _vmem_spec((None, 1, width), lambda l: (l, 0, 0))
    mat = lambda a, b: _vmem_spec((None, a, b), lambda l: (l, 0, 0))
    cache_spec = (_vmem_spec((None, None, S, C), lambda l: (l, 0, 0, 0))
                  if packed else
                  _vmem_spec((None, None, H, S, D),
                             lambda l: (l, 0, 0, 0, 0)))
    kw = {"compiler_params": _compiler_params(0, 1)}
    xout, newk, newv = pl.pallas_call(
        kernel,
        grid=(L,),
        in_specs=[
            _smem_spec(),
            _vmem_spec((1, C), lambda l: (0, 0)),
            row(C), row(C), mat(C, 3 * C), row(3 * C),
            mat(C, C), row(C), row(C), row(C),
            mat(C, 4 * C), row(4 * C), mat(4 * C, C), row(C),
            cache_spec, cache_spec,
        ],
        out_specs=[
            _vmem_spec((1, C), lambda l: (0, 0)),
            row(C), row(C),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, C), cd),
            jax.ShapeDtypeStruct((L, 1, C), cd),
            jax.ShapeDtypeStruct((L, 1, C), cd),
        ],
        scratch_shapes=[pltpu.VMEM((1, C), cd)],
        name="fused_decode_layers",
        interpret=_interpret_mode(),
        **kw,
    )(jnp.asarray(pos, jnp.int32).reshape(1), x0,
      vec("ln1_scale"), vec("ln1_bias"), w["qkv_kernel"], vec("qkv_bias"),
      w["attn_out_kernel"], vec("attn_out_bias"), vec("ln2_scale"),
      vec("ln2_bias"), w["mlp_up_kernel"], vec("mlp_up_bias"),
      w["mlp_down_kernel"], vec("mlp_down_bias"), cache["k"], cache["v"])
    # scatter every layer's fresh K/V row into the cache at pos — ONE
    # dynamic_update_slice per array for all layers. An out-of-range pos
    # would CLAMP onto the last valid row (lint GL006); eager calls
    # assert, jitted callers bound pos host-side (decode_step's guard
    # already ran on this pos before dispatching here).
    from ..utils.sanitize import check_in_bounds
    seq_axis = 2 if packed else 3
    check_in_bounds(pos, 1, cache["k"].shape[seq_axis],
                    what="fused decode cache write")
    zero = jnp.int32(0)
    p = jnp.asarray(pos, jnp.int32)
    if packed:
        newk_u = newk.reshape(L, 1, 1, C)
        newv_u = newv.reshape(L, 1, 1, C)
        start = (zero, zero, p, zero)
    else:
        newk_u = newk.reshape(L, 1, H, 1, D)
        newv_u = newv.reshape(L, 1, H, 1, D)
        start = (zero, zero, zero, p, zero)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], newk_u.astype(cache["k"].dtype), start)
    cv = jax.lax.dynamic_update_slice(
        cache["v"], newv_u.astype(cache["v"].dtype), start)
    return xout, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Fused PAGED decode: the all-layers kernel above, page-table-aware
# ---------------------------------------------------------------------------

def fused_paged_decode_supported(cfg, n_slots: int, page_size: int,
                                 itemsize: int = 2, mesh=None,
                                 kv_quant: str = "none",
                                 granularity: str = "page") -> bool:
    """Envelope for ``fused_paged_decode_layers``: packed cache layout,
    lane-sliceable heads, sublane-aligned pages, per-head accumulator
    lanes available, and one layer's weights + a double-buffered page
    pair + the (n_slots, C) residual scratch within FUSED_LAYER_BYTES.
    The serve engine prefers this route over the per-layer paged kernel
    (ops/paged_pallas.py) whenever it fits — one launch per decode step
    instead of one per layer. The shape/quant checks are the SHARED
    envelope (``ops.paged_pallas.paged_attention_envelope`` — int8 AND
    fp8, page AND head granularity all dequant in the accumulation
    loop now); this predicate layers the fused-only gates on top:
    packed cache layout, a 1x1 mesh (the fused kernel streams whole
    weight matrices per layer step, which tensor parallelism shards —
    sharded engines route the per-layer kernel's shard_map wrapper
    instead), and one layer's weights + a double-buffered page pair +
    the (n_slots, C) residual scratch within FUSED_LAYER_BYTES."""
    from .paged_pallas import paged_attention_envelope
    if mesh is not None and mesh.size > 1:
        return False
    if cfg.decode_cache_layout != "packed":
        return False
    C, H = cfg.n_embd, cfg.n_head
    if C % H != 0:
        return False
    D = C // H
    ok, _ = paged_attention_envelope(
        H, D, page_size, itemsize=itemsize, kv_quant=kv_quant,
        granularity=granularity)
    if not ok:
        return False
    weights = (C * 3 * C + C * C + 2 * C * 4 * C) * itemsize
    pages = 2 * page_size * C * itemsize
    # the (n_slots, 1, C) residual scratch pads every row to a full
    # sublane tile: 32 bytes per lane whatever the dtype
    scratch = n_slots * 32 * C + 3 * C * itemsize + C * 4 + 2 * LANES * 4
    return weights + pages + scratch <= FUSED_LAYER_BYTES


def _paged_fused_kernel(tables_ref, pos_ref, x0_ref, ln1s_ref, ln1b_ref,
                        wqkv_ref, bqkv_ref, wproj_ref, bproj_ref, ln2s_ref,
                        ln2b_ref, wup_ref, bup_ref, wdown_ref, bdown_ref,
                        kp_ref, vp_ref, *rest, n_layer, n_head, head_dim,
                        page_size, n_pages_per_slot, eps, scale,
                        activation, quantized, kv_dtype, head_gran):
    """Grid (layer, slot, logical page), all sequential: the residual
    row of every slot is carried across layer steps in VMEM scratch
    (exactly ``_decode_kernel``'s trick, widened to B rows), each
    slot's QKV projection runs once at its first page step, attention
    accumulates online-softmax across its LIVE pages (the block index
    map repeats the previous physical page past the frontier, skipping
    the DMA — ops/paged_pallas.clamped_live_page), and the block tail
    (proj/ln2/MLP/residual) lands at the last page step. Layer weights
    keep a constant block index across the whole (slot, page) subgrid,
    so they stream exactly once per layer.

    ``quantized`` (int8 OR fp8 pool): two extra f32 scale blocks —
    (psz, 1) page granularity, (psz, H) head granularity with the
    per-head lane column selected in the loop — ride the page index
    map and dequant the K/V pages inside the accumulation loop, and
    the fresh K/V rows are FAKE-QUANTIZED (``_fake_quant_row`` —
    bit-identical math to quant.kv, including fp8's saturating e4m3
    round-trip) before attending, so the fresh column scores exactly
    what the caller's quantize-on-write scatter will store; the raw
    rows still leave through newk/newv for that scatter."""
    if quantized:
        (ksp_ref, vsp_ref, xout_ref, newk_ref, newv_ref, x_scr, q_scr,
         knew_scr, vnew_scr, acc_ref, m_ref, l_ref) = rest
    else:
        (xout_ref, newk_ref, newv_ref, x_scr, q_scr, knew_scr,
         vnew_scr, acc_ref, m_ref, l_ref) = rest
    l = pl.program_id(0)
    b = pl.program_id(1)
    p = pl.program_id(2)
    H, D, psz = n_head, head_dim, page_size
    C = H * D
    pos = pos_ref[b]
    live = (pos + psz - 1) // psz        # pages holding positions < pos

    @pl.when((l == 0) & (p == 0))
    def _seed():
        x_scr[b] = x0_ref[...]

    @pl.when(p == 0)
    def _project():
        x = x_scr[b]
        h = _ln_row(x, ln1s_ref[...], ln1b_ref[...], eps)
        qkv = _row_matmul(h, wqkv_ref[...], bqkv_ref[...])   # (1, 3C)
        q_scr[...] = qkv[:, :C]
        k_row = qkv[:, C:2 * C]
        v_row = qkv[:, 2 * C:]
        if quantized:
            # attend the value the pool will actually hold (docstring)
            kdq = _fake_quant_row(k_row, kv_dtype, n_head,
                                  "head" if head_gran else "page")
            vdq = _fake_quant_row(v_row, kv_dtype, n_head,
                                  "head" if head_gran else "page")
            knew_scr[...] = kdq.astype(knew_scr.dtype)
            vnew_scr[...] = vdq.astype(vnew_scr.dtype)
        else:
            knew_scr[...] = k_row
            vnew_scr[...] = v_row
        newk_ref[...] = k_row
        newv_ref[...] = v_row
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(p < live)
    def _accumulate():
        kpos = jax.lax.broadcasted_iota(jnp.int32, (psz, 1), 0) + p * psz
        if quantized:
            ksc = ksp_ref[...]           # (psz, 1) page / (psz, H) head
            vsc = vsp_ref[...]
        for i in range(H):
            sl = slice(i * D, (i + 1) * D)
            q = q_scr[:, sl].astype(jnp.float32)                 # (1, D)
            kc = kp_ref[:, sl]                                   # (psz, D)
            vc = vp_ref[:, sl]
            kcf = kc.astype(jnp.float32)
            vcf = vc.astype(jnp.float32)
            if quantized:
                kcf = kcf * (ksc[:, i:i + 1] if head_gran else ksc)
                vcf = vcf * (vsc[:, i:i + 1] if head_gran else vsc)
            s = jnp.sum(kcf * q, axis=-1,
                        keepdims=True) * scale                   # (psz, 1)
            s = jnp.where(kpos < pos, s, NEG_INF)
            # per-head running max/sum are (1, 1) VECTOR windows of the
            # (1, LANES) scratch rows (paged_window_attention's layout):
            # Mosaic has no scalar store to VMEM
            m_prev = m_ref[:, i:i + 1]                           # (1, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # masked rows contribute EXACTLY zero (not exp(0)): with a
            # fully-masked page m_new stays NEG_INF and s - m_new == 0
            pexp = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
            l_ref[:, i:i + 1] = (l_ref[:, i:i + 1] * alpha
                                 + jnp.sum(pexp, axis=0, keepdims=True))
            acc_ref[:, sl] = (acc_ref[:, sl] * alpha
                              + jnp.sum(pexp * vcf,
                                        axis=0, keepdims=True))
            m_ref[:, i:i + 1] = m_new

    @pl.when(p == n_pages_per_slot - 1)
    def _finalize():
        outs = []
        for i in range(H):
            sl = slice(i * D, (i + 1) * D)
            q = q_scr[:, sl].astype(jnp.float32)
            s_new = jnp.sum(knew_scr[:, sl].astype(jnp.float32) * q,
                            axis=-1, keepdims=True) * scale      # (1, 1)
            m_prev = m_ref[:, i:i + 1]
            m2 = jnp.maximum(m_prev, s_new)
            alpha = jnp.exp(m_prev - m2)
            p_new = jnp.exp(s_new - m2)
            denom = (l_ref[:, i:i + 1] * alpha
                     + p_new)                 # >= p_new > 0 always
            outs.append((acc_ref[:, sl] * alpha
                         + p_new * vnew_scr[:, sl].astype(jnp.float32))
                        / denom)
        x = x_scr[b]
        attn = jnp.concatenate(outs, axis=1).astype(x.dtype)
        attn = _row_matmul(attn, wproj_ref[...], bproj_ref[...])
        x_mid = x + attn
        h = _ln_row(x_mid, ln2s_ref[...], ln2b_ref[...], eps)
        h = _row_matmul(h, wup_ref[...], bup_ref[...])
        h = (jax.nn.gelu(h) if activation == "gelu" else jax.nn.relu(h))
        h = _row_matmul(h.astype(x.dtype), wdown_ref[...], bdown_ref[...])
        x_new = x_mid + h
        x_scr[b] = x_new
        xout_ref[...] = x_new


def fused_paged_decode_layers(x0: jnp.ndarray,
                              blocks: Dict[str, jnp.ndarray],
                              pos: jnp.ndarray, tables: jnp.ndarray,
                              cache: Dict[str, jnp.ndarray], cfg
                              ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                         jnp.ndarray]:
    """Every transformer layer of one multi-slot PAGED decode step in
    ONE Pallas call. x0: (B, C) embedded rows (compute dtype); pos:
    (B,) int32 effective logical positions (inactive slots at 0);
    tables: (B, max_pages) int32; cache: packed ``init_paged_kv_pool``
    arrays (L, n_pages, page, C), STALE at ``pos``. Returns
    ``(x (B, C), newk (L, B, C), newv (L, B, C))`` — the caller
    scatters the fresh K/V rows through the page tables (drop-routed
    for inactive slots), mirroring ``fused_decode_layers``'s
    attend-stale-then-write contract."""
    from ..quant.kv import pool_quant_mode
    from .paged_pallas import clamped_live_page
    L, N, psz, C = cache["k"].shape
    H = cfg.n_head
    D = C // H
    B, mp = tables.shape
    cd = x0.dtype
    kv_dtype, gran = pool_quant_mode(cache)
    quantized = kv_dtype is not None
    head_gran = gran == "head"
    w = {k: v.astype(cd) for k, v in blocks.items()}
    vec = lambda name: w[name].reshape(L, 1, -1)
    kernel = functools.partial(
        _paged_fused_kernel, n_layer=L, n_head=H, head_dim=D,
        page_size=psz, n_pages_per_slot=mp, eps=cfg.layernorm_eps,
        scale=D ** -0.5, activation=cfg.activation,
        quantized=quantized, kv_dtype=kv_dtype, head_gran=head_gran)
    lrow = lambda width: _vmem_spec((None, 1, width),
                                    lambda l, b, p, t, q: (l, 0, 0))
    lmat = lambda a, c: _vmem_spec((None, a, c),
                                   lambda l, b, p, t, q: (l, 0, 0))
    brow = _vmem_spec((None, 1, C), lambda l, b, p, t, q: (b, 0, 0))

    def page_map(l, b, p, tables, pos):
        return (l, tables[b, clamped_live_page(p, pos[b], psz)], 0, 0)

    page_spec = _vmem_spec((None, None, psz, C), page_map)
    # residual rows as (B, 1, C): the slot index is dynamic, and Mosaic
    # only takes a dynamic index on an untiled (leading) dimension
    scratch = [pltpu.VMEM((B, 1, C), cd), pltpu.VMEM((1, C), cd),
               pltpu.VMEM((1, C), cd), pltpu.VMEM((1, C), cd),
               pltpu.VMEM((1, C), jnp.float32),
               pltpu.VMEM((1, LANES), jnp.float32),
               pltpu.VMEM((1, LANES), jnp.float32)]
    kw = {"compiler_params": _compiler_params(0, 3)}
    in_specs = [brow,
                lrow(C), lrow(C), lmat(C, 3 * C), lrow(3 * C),
                lmat(C, C), lrow(C), lrow(C), lrow(C),
                lmat(C, 4 * C), lrow(4 * C), lmat(4 * C, C), lrow(C),
                page_spec, page_spec]
    inputs = [x0[:, None, :],
              vec("ln1_scale"), vec("ln1_bias"), w["qkv_kernel"],
              vec("qkv_bias"), w["attn_out_kernel"],
              vec("attn_out_bias"), vec("ln2_scale"), vec("ln2_bias"),
              w["mlp_up_kernel"], vec("mlp_up_bias"),
              w["mlp_down_kernel"], vec("mlp_down_bias"),
              cache["k"], cache["v"]]
    if quantized:
        # (L, N, psz) page-granularity scales -> (psz, 1) blocks, or
        # packed head-granularity (L, N, psz, H) -> (psz, H) blocks,
        # per (layer, physical page) on the same fetch-skip index map
        swidth = H if head_gran else 1
        scale_spec = _vmem_spec((None, None, psz, swidth), page_map)
        in_specs += [scale_spec, scale_spec]
        inputs += [cache["ks"].reshape(L, N, psz, swidth),
                   cache["vs"].reshape(L, N, psz, swidth)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, B, mp),
        in_specs=in_specs,
        out_specs=[brow,
                   _vmem_spec((None, None, 1, C),
                              lambda l, b, p, t, q: (l, b, 0, 0)),
                   _vmem_spec((None, None, 1, C),
                              lambda l, b, p, t, q: (l, b, 0, 0))],
        scratch_shapes=scratch,
    )
    xout, newk, newv = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, C), cd),
                   jax.ShapeDtypeStruct((L, B, 1, C), cd),
                   jax.ShapeDtypeStruct((L, B, 1, C), cd)],
        name="fused_paged_decode_layers",
        interpret=_interpret_mode(), **kw,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
      *inputs)
    return xout[:, 0, :], newk[:, :, 0, :], newv[:, :, 0, :]
