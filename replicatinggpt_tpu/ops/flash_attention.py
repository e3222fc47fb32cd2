"""Fused / flash attention entry point.

``flash_attention(q, k, v)`` is the memory-efficient attention core used when
``cfg.attention_impl == 'flash'`` (and by 'auto' on TPU): it avoids
materializing the (T, T) weight matrix in HBM that the einsum path (and the
reference, GPT1.py:114-116) allocates.

Current implementation: a Pallas TPU kernel (blockwise online-softmax) with
an XLA-SDPA fallback on non-TPU backends / unsupported shapes. The kernel
lives in :mod:`.flash_pallas`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Measured 'auto' flash/einsum crossover on v5e with auto-sized tiles
# (benchmarks/RESULTS.md): flash wins from T=256 up. Single source of
# truth for the local policy in models.gpt._block AND the mesh wrapper
# in parallel/sharded_flash.py — re-tune it here only.
FLASH_MIN_T = 256


def _xla_sdpa(q, k, v, scale, causal):
    # (B,H,T,D) -> jax.nn.dot_product_attention wants (B,T,H,D)
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    out = jax.nn.dot_product_attention(qt, kt, vt, scale=scale,
                                       is_causal=causal)
    return out.transpose(0, 2, 1, 3)


def _pallas_supported(q) -> bool:
    if jax.default_backend() != "tpu":
        return False
    *_, T, D = q.shape
    # kernel tiles: lane dim 128, sequence blocks of 128
    return D in (32, 64, 128, 256) and T % 128 == 0 and T >= 128


def _packed_backend_ok() -> bool:
    """Pallas lowering gate for the packed family (tests monkeypatch this
    to exercise the interpret-mode kernel on CPU). One site — the local
    routing and the mesh packed hook both go through it."""
    return jax.default_backend() == "tpu"


def packed_envelope_ok(qkv: jnp.ndarray, n_head: int) -> bool:
    """THE packed-family gate: backend + shape/residency envelope. Both
    packed entry points — the local routing below and the mesh hook's
    precheck (parallel/sharded_flash.py) — must use this one predicate,
    so a gate added here can never diverge the two paths."""
    if not _packed_backend_ok():
        return False
    from . import flash_pallas as fp
    _, T, C3 = qkv.shape
    itemsize = jnp.dtype(qkv.dtype).itemsize
    # group_stream joins the envelope only behind its hardware-validation
    # gate (fp.GROUP_STREAM_AUTOROUTE) — read dynamically so flipping the
    # gate (a chip run validating the family, or a test) takes effect
    # here too
    return (fp.packed_supported(T, C3 // 3, n_head, itemsize)
            or fp.packed_group_supported(T, C3 // 3, n_head, itemsize)
            or (fp.GROUP_STREAM_AUTOROUTE
                and fp.packed_group_stream_supported(T, C3 // 3, n_head,
                                                     itemsize)))


def packed_qkv_attention(qkv: jnp.ndarray, n_head: int, *,
                         scale: Optional[float] = None,
                         dropout_rate: float = 0.0,
                         rng: Optional[jax.Array] = None,
                         train: bool = False) -> Optional[jnp.ndarray]:
    """Attention straight off the fused (B, T, 3C) QKV projection via the
    packed-heads kernel (flash_pallas packed family): returns the merged
    (B, T, C) output, or None when the kernel does not apply (non-TPU
    backend or off the residency/shape envelope) — callers then take the
    split-heads path. Skipping the (B,T,H,D)<->(B,H,T,D) layout round
    trip is worth ~18% of attention fwd+bwd at char-GPT shapes on v5e
    (benchmarks/RESULTS.md)."""
    if not packed_envelope_ok(qkv, n_head):
        return None
    from .flash_pallas import pallas_flash_attention_packed
    training_dropout = train and dropout_rate > 0.0 and rng is not None
    return pallas_flash_attention_packed(
        qkv, n_head, scale=scale, causal=True,
        dropout_rate=dropout_rate if training_dropout else 0.0,
        dropout_rng=rng if training_dropout else None)


def supports_dropout(q) -> bool:
    """Attention-weight dropout is implemented in the Pallas kernel only
    (counter-based in-kernel mask); the XLA-SDPA fallback has no hook for
    it — callers route dropout-training to the einsum path elsewhere."""
    return _pallas_supported(q)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: Optional[float] = None,
                    causal: bool = True,
                    dropout_rate: float = 0.0,
                    dropout_rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """q, k, v: (B, H, T, D). Returns (B, H, T, D)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if _pallas_supported(q):
        from .flash_pallas import pallas_flash_attention
        return pallas_flash_attention(q, k, v, scale=scale, causal=causal,
                                      dropout_rate=dropout_rate,
                                      dropout_rng=dropout_rng)
    if dropout_rate > 0.0:
        raise ValueError(
            "attention-weight dropout needs the Pallas kernel (TPU, "
            "lane-aligned shapes); use the einsum path here")
    return _xla_sdpa(q, k, v, scale, causal)
