"""KV page quantization: quantize-on-write, dequant-on-gather.

The paged pool's write sites (decode/prefill/verify scatters in
models/gpt.py) hand each fresh K/V row here and scatter the returned
(quantized row, scale) pair at the SAME (layer, physical page, offset)
coordinates — scales are just two more pool arrays (``ks``/``vs``)
riding the cache dict, so COW page copies, LRU eviction and radix
prefix sharing carry them for free. Gathers dequant right after the
page gather (``dequant_gathered``), and the paged Pallas kernels do
the same multiply inside their accumulation loops.

Numerics contract (what the parity tests pin): quantization math runs
in float32 regardless of the compute dtype — ``scale = max(amax/qmax,
eps)``, ``q = clip(round(x/scale))`` for int8 or a saturating e4m3
cast for fp8 — and dequant is ``q * scale`` cast back to the compute
dtype. Both routes (XLA gather, per-layer kernel) use exactly this
formula, so kernel-vs-XLA greedy streams stay token-identical (the
kernel route attends a fresh column that ``fake_quantize_rows`` has
already taken through the store's round trip).
"""

from __future__ import annotations

import jax.numpy as jnp

#: floor on a row's scale: an all-zero row (pool init, padding) must
#: dequant to exactly zero, never divide by zero
SCALE_EPS = 1e-8


def kv_store_dtype(kv_dtype: str):
    """Storage dtype of a quantized pool's K/V arrays."""
    return {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}[kv_dtype]


def kv_qmax(kv_dtype: str) -> float:
    """Largest magnitude the storage dtype represents (int8 symmetric
    127; fp8 e4m3 448)."""
    return {"int8": 127.0, "fp8": 448.0}[kv_dtype]


def kv_itemsize(kv_dtype: str, cfg=None) -> int:
    """Bytes per stored K/V element ("none" = the compute dtype's)."""
    if kv_dtype == "none":
        return jnp.dtype({"float32": jnp.float32,
                          "bfloat16": jnp.bfloat16,
                          "float16": jnp.float16}[cfg.dtype]).itemsize \
            if cfg is not None else 2
    return 1


def scale_bytes_per_token(kv_dtype: str, granularity: str,
                          n_head: int) -> int:
    """Scale metadata bytes per token position per layer (K + V):
    2 x f32 at page granularity, 2 x H x f32 at head granularity."""
    if kv_dtype == "none":
        return 0
    return 2 * 4 * (n_head if granularity == "head" else 1)


def pool_quant_mode(cache) -> tuple:
    """(kv_dtype, granularity) of a paged pool, derived from the
    arrays themselves — dtypes and ranks are static under jit, so the
    paged programs never need the config threaded through their traced
    signatures. ``(None, None)`` for an unquantized pool."""
    if "ks" not in cache:
        return None, None
    kv_dtype = "int8" if cache["k"].dtype == jnp.int8 else "fp8"
    # packed pool (L,N,psz,C) / heads pool (L,N,H,psz,D); page-gran
    # scales are (L,N,psz) either way, head-gran adds the H axis
    gran = "head" if cache["ks"].ndim == 4 else "page"
    return kv_dtype, gran


def init_scales(cfg, n_pages: int, page_size: int, granularity: str):
    """Zero-initialized scale arrays for a fresh pool (an unwritten
    row dequants to exactly zero — the same harmless-stale-state
    contract the unquantized pool relies on)."""
    if granularity == "head":
        if cfg.decode_cache_layout == "packed":
            shape = (cfg.n_layer, n_pages, page_size, cfg.n_head)
        else:
            shape = (cfg.n_layer, n_pages, cfg.n_head, page_size)
    else:
        shape = (cfg.n_layer, n_pages, page_size)
    # two DISTINCT arrays: the engine donates the whole pool dict, and
    # XLA rejects the same buffer donated twice
    return {"ks": jnp.zeros(shape, jnp.float32),
            "vs": jnp.zeros(shape, jnp.float32)}


def quantize_rows(rows: jnp.ndarray, kv_dtype: str, n_head: int,
                  granularity: str):
    """Quantize merged K or V rows (..., C) for a pool write.

    Returns ``(q, scale)``: ``q`` (..., C) in the storage dtype and
    ``scale`` (...,) float32 at page granularity or (..., H) at head
    granularity. Math in f32 (see module docstring); an all-zero row
    gets ``SCALE_EPS`` and round-trips to exact zero."""
    qmax = kv_qmax(kv_dtype)
    f = rows.astype(jnp.float32)
    if granularity == "head":
        fh = f.reshape(f.shape[:-1] + (n_head, f.shape[-1] // n_head))
        scale = jnp.maximum(jnp.max(jnp.abs(fh), axis=-1) / qmax,
                            SCALE_EPS)                     # (..., H)
        q = (fh / scale[..., None]).reshape(f.shape)
    else:
        scale = jnp.maximum(jnp.max(jnp.abs(f), axis=-1) / qmax,
                            SCALE_EPS)                     # (...,)
        q = f / scale[..., None]
    if kv_dtype == "int8":
        q = jnp.clip(jnp.round(q), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(q, -qmax, qmax).astype(jnp.float8_e4m3fn)
    return q, scale


def fake_quantize_rows(rows: jnp.ndarray, kv_dtype: str, n_head: int,
                       granularity: str) -> jnp.ndarray:
    """quantize -> dequantize in one step (f32 out): what a fresh row
    is WORTH once it lands in the pool. The kernel routes attend this
    for the fresh column so write-then-attend equivalence survives
    quantization (the stored row dequants to exactly this value)."""
    q, scale = quantize_rows(rows, kv_dtype, n_head, granularity)
    if granularity == "head":
        qh = q.astype(jnp.float32).reshape(
            q.shape[:-1] + (n_head, q.shape[-1] // n_head))
        return (qh * scale[..., None]).reshape(q.shape)
    return q.astype(jnp.float32) * scale[..., None]


def dequant_gathered(g: jnp.ndarray, s: jnp.ndarray, packed: bool,
                     n_head: int, cd) -> jnp.ndarray:
    """Dequantize a page-gathered view back to the compute dtype.

    ``g``: (B, mp, psz, C) packed or (B, mp, H, psz, D) heads layout,
    fresh off ``pool[tables]``; ``s``: the same-gathered scales —
    (B, mp, psz) page granularity, or head granularity's
    (B, mp, psz, H) packed / (B, mp, H, psz) heads."""
    gf = g.astype(jnp.float32)
    if packed:
        if s.ndim == 4:     # head granularity: per (row, head) scale
            B, mp, psz, C = g.shape
            gh = gf.reshape(B, mp, psz, n_head, C // n_head)
            gf = (gh * s[..., None]).reshape(B, mp, psz, C)
        else:
            gf = gf * s[..., None]
    else:
        if s.ndim == 4:     # (B, mp, H, psz)
            gf = gf * s[..., None]
        else:               # (B, mp, psz): broadcast over H and D
            gf = gf * s[:, :, None, :, None]
    return gf.astype(cd)
