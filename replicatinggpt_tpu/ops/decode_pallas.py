"""Decode attention for the lane-packed KV cache: one Pallas call per
layer reads a row's (S, C) K and V blocks fully packed, heads as static
D-wide lane slices, so a head dim below the lane width pads no tile.

It serves the offline decode stack (models.gpt._decode_step_packed behind
sample/generate.py); the serve engine's paged pool has its own kernel
(ops/paged_pallas.py). The kernel attends the STALE cache masked to
positions < pos plus an explicit fresh-KV column (bit-equivalent to
write-then-attend: cache[pos] would equal the fresh k/v); the caller
writes the fresh rows at ``pos`` afterwards.

Numerics mirror the XLA decode body (models/gpt.py decode_step /
ops/attention.cached_attention): attention scores and softmax in f32,
probabilities cast to the cache dtype for the PV product. Parity with
the XLA route is asserted in tests/test_generate.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_pallas import (NEG_INF, _compiler_params, _interpret_mode,
                           _smem_spec, _vmem_spec)

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
    Per head: stale cache masked to < pos + explicit fresh column, f32
    scores/softmax, probs cast to the cache dtype for PV."""
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
