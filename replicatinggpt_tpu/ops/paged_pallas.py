"""Paged decode attention: one Pallas kernel whose scalar-prefetched
page table streams ONLY a slot's mapped pages.

The XLA paged decode path (models/gpt.py decode_step_paged) gathers
every slot's full (max_pages, page, C) view each layer each step —
simple and parity-exact, but it fetches max_pages pages per slot
regardless of how short the slot's sequence actually is. This kernel
puts the page table in scalar-prefetch SMEM and lets the BLOCK INDEX
MAP translate (slot, logical page) -> physical page right before the
DMA: grid (B, max_pages), page minor, and logical pages past the slot's
live frontier map to the SAME physical page as the previous grid step —
Pallas skips the re-fetch for a repeated block index (the exact trick
the streamed flash kernels' triangular tile map uses for fully-masked
tiles), so a slot at position p streams ceil(p/page) pages, not
max_pages. Accumulation is online softmax across page steps (f32
running max / denominator per head in VMEM scratch); the fresh K/V
column rides separately and folds in at the final page step, so the
kernel attends the STALE pool bit-equivalently to write-then-attend
(cache[pos] would hold exactly the fresh k/v) — the caller scatters the
fresh row afterwards, mirroring ops/decode_pallas.py's packed kernel.

Packed (page, C) layout only: heads are static D-wide lane slices of
the fully-packed row (no D-minor tile padding in the stream). Gated to
TPU (`_paged_attn_backend_ok`, monkeypatched by tests to exercise the
interpreter on CPU) and to shapes inside `paged_decode_supported`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl

from .flash_pallas import (LANES, NEG_INF, _compiler_params,
                           _interpret_mode, _vmem_spec, pltpu)

# VMEM budget: one (page, C) K and V block per grid step, double-
# buffered, plus the (1, C) rows and f32 accumulators. 4 MiB covers
# C=768 pages of 1024 tokens bf16 with margin.
PAGED_DECODE_BYTES = 4 * 1024 * 1024


def _paged_attn_backend_ok() -> bool:
    """Pallas lowering gate (tests monkeypatch this to run the
    interpret-mode kernel on CPU). Sharding safety is a SEPARATE gate:
    ``paged_kernel_mesh_ok`` — the serve engine may now run on a
    (data, model) mesh, where a bare pallas_call cannot partition."""
    return jax.default_backend() == "tpu"


def paged_kernel_mesh_ok(mesh, n_pages=None, n_embd=None,
                         n_head=None) -> bool:
    """Sharding-aware kernel routing. A bare ``pallas_call`` cannot be
    GSPMD-partitioned, but the per-layer windowed kernel now ships a
    ``shard_map`` wrapper (``sharded_paged_window_attention``): each
    chip runs the kernel on its own contiguous page block with the
    scalar-prefetched table localized per shard, partial online-softmax
    state merged across 'data' and heads fully local over 'model'. The
    wrapper needs clean per-shard blocks, so a >1 mesh routes the
    kernel iff the page axis divides over 'data' and channels AND heads
    divide over 'model' (the same divisibility-drop rule
    parallel.mesh.page_pool_pspec applies to the pool specs). Callers
    that cannot supply the geometry get the conservative answer for a
    >1 mesh. The FUSED all-layers kernel stays 1x1-only — it streams
    whole weight matrices per layer step, which TP shards."""
    if mesh is None or mesh.size == 1:
        return True
    if n_pages is None or n_embd is None or n_head is None:
        return False
    shape = dict(getattr(mesh, "shape", {}))
    data = int(shape.get("data", 1))
    model = int(shape.get("model", 1))
    if data * model != mesh.size:
        return False
    return (n_pages % data == 0 and n_embd % model == 0
            and n_head % model == 0)


def mixed_step_kernel_ok(n_head: int, head_dim: int, page_size: int,
                         itemsize: int = 2, mesh=None,
                         kv_quant: str = "none",
                         granularity: str = "page",
                         n_pages=None) -> bool:
    """Kernel routing for the MIXED prefill+decode window step and the
    speculative verify forward (models.gpt.verify_step_paged): the seam
    PR 12 documented is now FLIPPED — ``paged_window_attention`` walks
    a (W, C) query block per slot, so prefilling slots scatter chunk
    rows through their page tables and decoding slots do the
    verify<->decode row math in ONE kernel launch per layer (same
    ``mode='drop'`` routing as the XLA path; the scatter itself stays
    outside the kernel, exactly like the decode kernels'
    attend-stale-then-write contract). Same envelope as the decode
    kernel — the window width W is a block-shape parameter, not an
    envelope axis (Pallas pads the sublane dim)."""
    ok, _ = paged_attention_envelope(
        n_head, head_dim, page_size, itemsize=itemsize, mesh=mesh,
        kv_quant=kv_quant, granularity=granularity, n_pages=n_pages)
    return ok


def clamped_live_page(p, pos, page_size: int):
    """The fetch-skip trick, shared by every paged block index map
    (this file's per-layer kernel and the fused all-layers kernel in
    ops/decode_pallas.py): logical pages past a slot's live frontier
    map to the SAME logical page as the previous grid step, and Pallas
    skips the DMA for a repeated block index — so a slot at position
    ``pos`` streams ceil(pos/page) pages regardless of max_pages. An
    idle slot (pos == 0) clamps to page 0; its zero live pages are
    never read (the accumulation loop is gated on ``p < live``)."""
    live = (pos + page_size - 1) // page_size
    return jnp.where(p < live, p, jnp.maximum(live - 1, 0))


def paged_attention_envelope(n_head: int, head_dim: int, page_size: int,
                             *, itemsize: int = 2, mesh=None,
                             kv_quant: str = "none",
                             granularity: str = "page",
                             n_pages=None, n_kv_head=None) -> tuple:
    """THE shared kernel envelope — one set of gate checks consumed by
    every route predicate (``paged_decode_supported``,
    ``mixed_step_kernel_ok`` here; ``fused_paged_decode_supported`` in
    ops/decode_pallas.py layers its VMEM/weight checks on top), so the
    mesh/quant/shape logic cannot drift between the fused and per-layer
    kernels. Returns ``(ok, reasons)`` — ``reasons`` names every failed
    check (the engine's kernel-route export surfaces them, so a silent
    XLA fallback is observable, not asserted).

    What the unified kernel family now accepts: int8 AND fp8 pools at
    page AND head granularity (per-head scale-lane selection + the
    saturating e4m3 cast run inside the accumulation loop), and >1
    (data, model) meshes through the shard_map wrapper when the pool
    geometry divides (``paged_kernel_mesh_ok``)."""
    reasons = []
    n_kv_head = n_kv_head or n_head
    if n_kv_head != n_head:
        # grouped queries run ``paged_gqa_attention``: one chip, a plain
        # pool, whole groups of query heads to a KV head
        if mesh is not None and mesh.size > 1:
            reasons.append("gqa_mesh")
        if kv_quant != "none":
            reasons.append("gqa_kv_quant")
        if n_head % n_kv_head:
            reasons.append("gqa_group")
    if not paged_kernel_mesh_ok(mesh, n_pages=n_pages,
                                n_embd=n_head * head_dim,
                                n_head=n_head):
        reasons.append("mesh_indivisible")
    if kv_quant not in ("none", "int8", "fp8"):
        reasons.append("kv_quant_unknown")
    if granularity not in ("page", "head"):
        reasons.append("granularity_unknown")
    if head_dim not in (32, 64, 128, 256):
        reasons.append("head_dim")
    if n_head > LANES:
        reasons.append("n_head_gt_lanes")
    if page_size % 8 != 0:
        reasons.append("page_align")
    C = n_kv_head * head_dim          # a page's row is KV heads wide
    if 2 * page_size * C * itemsize > PAGED_DECODE_BYTES:
        reasons.append("vmem_budget")
    return (not reasons), tuple(reasons)


def paged_decode_supported(n_head: int, head_dim: int, page_size: int,
                           itemsize: int = 2, mesh=None,
                           kv_quant: str = "none",
                           granularity: str = "page",
                           n_pages=None) -> bool:
    """Per-layer decode-kernel envelope — a thin view over
    ``paged_attention_envelope`` (one shared gate, no drift)."""
    ok, _ = paged_attention_envelope(
        n_head, head_dim, page_size, itemsize=itemsize, mesh=mesh,
        kv_quant=kv_quant, granularity=granularity, n_pages=n_pages)
    return ok


def _fill_last_owned(phys: jnp.ndarray, owned: jnp.ndarray) -> jnp.ndarray:
    """Localize a page table for the kernel's fetch-skip contract:
    positions the kernel must not read (``~owned``) repeat the LAST
    owned physical index to their left (a repeated block index skips
    the DMA — the generalization of ``clamped_live_page`` to the
    sharded case, where a shard's owned pages can be any subset of the
    logical walk, not just a prefix). Slots with no owned page at all
    clamp to physical 0 (never accumulated — the kernel gates on the
    owned mask)."""
    marked = jnp.where(owned, phys, -1)
    filled = jax.lax.associative_scan(
        lambda a, b: jnp.where(b >= 0, b, a), marked, axis=1)
    return jnp.maximum(filled, 0).astype(jnp.int32)


def effective_tables(tables: jnp.ndarray, pos: jnp.ndarray,
                     page_size: int) -> tuple:
    """(effective table, owned mask) for the UNSHARDED kernel call:
    owned = the prefix of pages holding positions < pos, effective
    table = ``clamped_live_page`` materialized host^Wtrace-side so the
    kernel's index map is a plain (B, max_pages) lookup shared with the
    sharded wrapper's localized tables."""
    mp = tables.shape[1]
    live = (pos + page_size - 1) // page_size
    p_idx = jnp.arange(mp, dtype=jnp.int32)[None, :]
    owned = p_idx < live[:, None]
    return (_fill_last_owned(jnp.asarray(tables, jnp.int32), owned),
            owned)


def _paged_window_kernel(tables_ref, pos_ref, owned_ref, q_ref, knew_ref,
                         vnew_ref, kp_ref, vp_ref, *rest, n_head,
                         head_dim, page_size, n_pages_per_slot, window,
                         scale, quantized, head_gran, fold):
    """ONE kernel body for the whole paged-attention family.

    W = ``window`` query rows per slot (W=1 is plain decode; W>1 is the
    mixed prefill+decode / speculative-verify step, where row j sits at
    logical position pos+j). Stale pool pages accumulate online-softmax
    gated on the scalar-prefetched OWNED mask (per-slot page prefix
    unsharded; an arbitrary owned subset under the shard_map wrapper),
    masked to positions < pos — identical for every query row, since
    rows 0..W-1 attend the fresh window via the causal fold. Quantized
    pools stream (psz, 1) page-granularity or (psz, H) head-granularity
    scale blocks through the same fetch-skip index map; the per-head
    lane column dequants in the accumulation loop (int8 AND fp8 — the
    e4m3 block ``astype``s to f32 like any other storage dtype).

    ``fold=True`` folds the fresh causal (W, W) block per head at the
    last page step and writes normalized output; ``fold=False`` emits
    the raw (acc, m, l) partials instead — the shard_map wrapper merges
    them across the 'data' axis (pmax/psum softmax merge) and folds the
    fresh window outside, where the collective lives."""
    if quantized:
        ksp_ref, vsp_ref, *rest = rest
    if fold:
        out_ref, acc_ref, m_ref, l_ref = rest
    else:
        accout_ref, mout_ref, lout_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    D, psz, W = head_dim, page_size, window
    pos = pos_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(owned_ref[b, p] > 0)
    def _accumulate():
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, psz), 1) + p * psz
        if quantized:
            ksc = ksp_ref[...]           # (psz, 1) page / (psz, H) head
            vsc = vsp_ref[...]
        for i in range(n_head):
            sl = slice(i * D, (i + 1) * D)
            q = q_ref[:, sl].astype(jnp.float32)                 # (W, D)
            kcf = kp_ref[:, sl].astype(jnp.float32)              # (psz, D)
            vcf = vp_ref[:, sl].astype(jnp.float32)
            if quantized:
                kcf = kcf * (ksc[:, i:i + 1] if head_gran else ksc)
                vcf = vcf * (vsc[:, i:i + 1] if head_gran else vsc)
            s = jax.lax.dot_general(
                q, kcf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (W, psz)
            s = jnp.where(kpos < pos, s, NEG_INF)
            m_prev = m_ref[:, i:i + 1]                           # (W, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # masked rows contribute EXACTLY zero (not exp(0)): with a
            # fully-masked page m_new stays NEG_INF and s - m_new == 0
            pexp = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
            l_ref[:, i:i + 1] = (l_ref[:, i:i + 1] * alpha
                                 + jnp.sum(pexp, axis=1, keepdims=True))
            acc_ref[:, sl] = (acc_ref[:, sl] * alpha
                              + jax.lax.dot_general(
                                  pexp, vcf, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32))
            m_ref[:, i:i + 1] = m_new

    @pl.when(p == n_pages_per_slot - 1)
    def _finalize():
        if not fold:
            accout_ref[...] = acc_ref[...]
            mout_ref[...] = m_ref[...]
            lout_ref[...] = l_ref[...]
            return
        row = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
        causal = col <= row            # fresh row j attends rows 0..j
        for i in range(n_head):
            sl = slice(i * D, (i + 1) * D)
            q = q_ref[:, sl].astype(jnp.float32)
            kn = knew_ref[:, sl].astype(jnp.float32)
            vn = vnew_ref[:, sl].astype(jnp.float32)
            s_new = jax.lax.dot_general(
                q, kn, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (W, W)
            s_new = jnp.where(causal, s_new, NEG_INF)
            m_prev = m_ref[:, i:i + 1]
            m2 = jnp.maximum(m_prev,
                             jnp.max(s_new, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m2)
            p_new = jnp.where(causal, jnp.exp(s_new - m2), 0.0)
            # denom >= diagonal term > 0 always (row j attends itself)
            denom = (l_ref[:, i:i + 1] * alpha
                     + jnp.sum(p_new, axis=1, keepdims=True))
            out = (acc_ref[:, sl] * alpha
                   + jax.lax.dot_general(
                       p_new, vn, (((1,), (0,)), ((), ())),
                       preferred_element_type=jnp.float32)) / denom
            out_ref[:, sl] = out.astype(out_ref.dtype)


def paged_window_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray, *, n_head: int,
                           k_scales=None, v_scales=None, owned=None,
                           fold: bool = True):
    """Windowed paged attention for one layer of a packed pool — the
    SINGLE entry point behind every per-layer engine route.

    q, k_new, v_new: (B, W, C) fresh merged window rows (row j of slot
    b sits at logical position ``pos[b] + j``; callers pad dead rows —
    garbage-in-garbage-out, the diagonal fold keeps them NaN-free);
    k_pages/v_pages: (n_pages, page, C) STALE pool (positions >= pos
    not yet written); tables: (B, max_pages) int32; pos: (B,) int32.
    Returns (B, W, C) — bit-equivalent to scattering the window rows at
    pos..pos+W-1 and attending causally, because stale-pool history is
    masked to positions < pos and the in-window positions are covered
    by the causal fresh fold (write-then-attend == attend-stale-then-
    fold, the same contract the W=1 decode kernel always had).

    ``k_scales``/``v_scales`` mark a QUANTIZED pool — (n_pages, page)
    f32 at page granularity or (n_pages, page, H) at head granularity
    (int8 or fp8 storage; the kernel only ever sees f32 scale blocks
    and ``astype``s the e4m3 pages like any storage dtype). The caller
    passes window rows already fake-quantized so the fresh fold attends
    exactly what the post-kernel scatter stores.

    ``owned``/pre-localized ``tables`` are the shard_map wrapper's
    seam (with ``fold=False`` it returns raw (acc, m, l) partials for
    the cross-'data' softmax merge); plain callers leave both unset and
    get the ``effective_tables`` prefix mask."""
    N, psz, C = k_pages.shape
    B, W, _ = q.shape
    mp = tables.shape[1]
    D = C // n_head
    quantized = k_scales is not None
    head_gran = quantized and k_scales.ndim == 3
    if owned is None:
        tables, owned = effective_tables(tables, pos, psz)
    kernel = functools.partial(
        _paged_window_kernel, n_head=n_head, head_dim=D, page_size=psz,
        n_pages_per_slot=mp, window=W, scale=D ** -0.5,
        quantized=quantized, head_gran=head_gran, fold=fold)

    def row_map(b, p, tables, pos, owned):
        return (b, 0, 0)

    def page_map(b, p, tables, pos, owned):
        # unowned steps repeat an already-fetched physical page (the
        # table is pre-filled by _fill_last_owned) — a repeated block
        # index skips the DMA (the fetch-skip trick)
        return (tables[b, p], 0, 0)

    row = _vmem_spec((None, W, C), row_map)
    kw = {"compiler_params": _compiler_params(0, 2)}
    scratch = [pltpu.VMEM((W, C), jnp.float32),
               pltpu.VMEM((W, LANES), jnp.float32),
               pltpu.VMEM((W, LANES), jnp.float32)]
    in_specs = [row, row, row,
                _vmem_spec((None, psz, C), page_map),
                _vmem_spec((None, psz, C), page_map)]
    inputs = [q, k_new, v_new, k_pages, v_pages]
    if quantized:
        swidth = n_head if head_gran else 1
        in_specs += [_vmem_spec((None, psz, swidth), page_map),
                     _vmem_spec((None, psz, swidth), page_map)]
        inputs += [k_scales.reshape(N, psz, swidth),
                   v_scales.reshape(N, psz, swidth)]
    if fold:
        out_specs = row
        out_shape = jax.ShapeDtypeStruct((B, W, C), q.dtype)
    else:
        rowL = _vmem_spec((None, W, LANES), row_map)
        out_specs = [_vmem_spec((None, W, C), row_map), rowL, rowL]
        out_shape = [jax.ShapeDtypeStruct((B, W, C), jnp.float32),
                     jax.ShapeDtypeStruct((B, W, LANES), jnp.float32),
                     jax.ShapeDtypeStruct((B, W, LANES), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, mp),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        name="paged_window_attention",
        interpret=_interpret_mode(), **kw,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
      jnp.asarray(owned, jnp.int32), *inputs)


def paged_decode_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray, *, n_head: int,
                           k_scales=None, v_scales=None) -> jnp.ndarray:
    """Decode attention for one layer of a paged packed pool — the
    W=1 view of :func:`paged_window_attention` (kept as the named
    decode entry point; a single-row window's causal fold degenerates
    to the scalar fresh-column fold of the original decode kernel).

    q, k_new, v_new: (B, C) fresh merged rows. Returns (B, C) —
    bit-equivalent to scattering k_new/v_new at ``pos`` and attending
    positions <= pos; the caller scatters afterwards."""
    return paged_window_attention(
        q[:, None, :], k_new[:, None, :], v_new[:, None, :],
        k_pages, v_pages, tables, pos, n_head=n_head,
        k_scales=k_scales, v_scales=v_scales)[:, 0, :]


def _fold_fresh_window(acc: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray,
                       q: jnp.ndarray, k_new: jnp.ndarray,
                       v_new: jnp.ndarray, n_head: int) -> jnp.ndarray:
    """Fold the fresh causal (W, W) window into raw kernel partials —
    the jnp twin of the kernel's ``fold=True`` finalize, run by the
    shard_map wrapper AFTER the cross-'data' merge (the fresh rows are
    replicated over 'data'; folding them per shard before the psum
    would double-count). acc: (B, W, C) f32; m/l: (B, W, LANES) f32
    with per-head state in columns :n_head."""
    B, W, C = q.shape
    D = C // n_head
    qh = q.astype(jnp.float32).reshape(B, W, n_head, D)
    knh = k_new.astype(jnp.float32).reshape(B, W, n_head, D)
    vnh = v_new.astype(jnp.float32).reshape(B, W, n_head, D)
    s_new = jnp.einsum("bwhd,bjhd->bhwj", qh, knh) * D ** -0.5
    causal = (jnp.arange(W)[None, :]
              <= jnp.arange(W)[:, None])[None, None]   # col <= row
    s_new = jnp.where(causal, s_new, NEG_INF)
    m_h = jnp.swapaxes(m[..., :n_head], 1, 2)          # (B, H, W)
    l_h = jnp.swapaxes(l[..., :n_head], 1, 2)
    m2 = jnp.maximum(m_h, jnp.max(s_new, axis=-1))
    alpha = jnp.exp(m_h - m2)
    p_new = jnp.where(causal, jnp.exp(s_new - m2[..., None]), 0.0)
    # denom >= diagonal term > 0 always (row j attends itself)
    denom = l_h * alpha + jnp.sum(p_new, axis=-1)
    acch = jnp.swapaxes(acc.reshape(B, W, n_head, D), 1, 2)
    out = (acch * alpha[..., None]
           + jnp.einsum("bhwj,bjhd->bhwd", p_new, vnh)) / denom[..., None]
    return jnp.swapaxes(out, 1, 2).reshape(B, W, C)


def sharded_paged_window_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                                   v_new: jnp.ndarray,
                                   k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray,
                                   tables: jnp.ndarray, pos: jnp.ndarray,
                                   *, n_head: int, mesh,
                                   k_scales=None, v_scales=None):
    """:func:`paged_window_attention` over a (data, model) serve mesh.

    ``shard_map`` runs the kernel per chip: the pool's page axis splits
    over 'data' (each shard holds a contiguous physical block of
    ``n_pages // data`` pages), channels/heads split over 'model'
    (heads are whole per shard — ``paged_kernel_mesh_ok`` gates on
    that), and the replicated page table is LOCALIZED per shard — a
    shard owns a logical page iff its physical index lands in the
    shard's block, the owned mask gates accumulation, and
    ``_fill_last_owned`` rewrites unowned steps to repeat an owned
    block index so the fetch-skip contract survives arbitrary owned
    subsets (a slot's pages interleave across shards under allocation
    churn). Each shard emits raw (acc, m, l) partials (``fold=False``);
    the online-softmax merge across 'data' is exact — pmax the maxima,
    rescale, psum — and the fresh causal window folds once afterwards
    on the merged state ('model' needs no collective: heads are fully
    local). Output matches the unsharded kernel to f32 merge order."""
    P = jax.sharding.PartitionSpec
    shape = dict(mesh.shape)
    data = int(shape.get("data", 1))
    model = int(shape.get("model", 1))
    N, psz, C = k_pages.shape
    mp = tables.shape[1]
    N_loc = N // data
    H_loc = n_head // model
    quantized = k_scales is not None
    head_gran = quantized and k_scales.ndim == 3
    d_ax = "data" if data > 1 else None
    m_ax = "model" if model > 1 else None
    qspec = P(None, None, m_ax)
    pspec = P(d_ax, None, m_ax)

    def local_fn(q_l, kn_l, vn_l, kp_l, vp_l, tab, pos_l, *scales):
        ks_l, vs_l = scales if scales else (None, None)
        lo = jax.lax.axis_index("data") * N_loc
        live = (pos_l + psz - 1) // psz
        p_idx = jnp.arange(mp, dtype=jnp.int32)[None, :]
        tab = jnp.asarray(tab, jnp.int32)
        owned = ((p_idx < live[:, None]) & (tab >= lo)
                 & (tab < lo + N_loc))
        eff = _fill_last_owned(tab - lo, owned)
        acc, m_, l_ = paged_window_attention(
            q_l, kn_l, vn_l, kp_l, vp_l, eff, pos_l, n_head=H_loc,
            k_scales=ks_l, v_scales=vs_l, owned=owned, fold=False)
        # exact cross-shard online-softmax merge: max, rescale, sum
        m_g = jax.lax.pmax(m_, "data")
        corr = jnp.exp(m_ - m_g)      # 1 where both stayed NEG_INF
        l_g = jax.lax.psum(l_ * corr, "data")
        D = (C // model) // H_loc
        corr_c = jnp.repeat(corr[..., :H_loc], D, axis=-1)
        acc_g = jax.lax.psum(acc * corr_c, "data")
        return _fold_fresh_window(acc_g, m_g, l_g, q_l, kn_l, vn_l,
                                  H_loc).astype(q_l.dtype)

    in_specs = [qspec, qspec, qspec, pspec, pspec, P(), P()]
    args = [q, k_new, v_new, k_pages, v_pages,
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32)]
    if quantized:
        sspec = P(d_ax, None, m_ax) if head_gran else P(d_ax, None)
        in_specs += [sspec, sspec]
        args += [k_scales, v_scales]
    return shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=qspec, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# grouped queries, and a lower bound on the positions read
#
# The family above has one head count on both sides (C = n_head * D for q
# and for a page's row). A grouped-query model's page row is n_kv_head * D
# wide and G = n_head // n_kv_head query heads read each KV head, so the
# kernel below stacks a group's G * W query rows into ONE (G*W, D) block a
# KV head: a page costs n_kv_head small matmuls, not n_head. ``attn_window``
# bounds the positions a row reads from below (row j at position pos + j
# attends k with pos + j - window < k <= pos + j): pages wholly behind the
# bound are unowned and skipped exactly like pages past the frontier, the
# fetch-skip trick at the other end. ``page0`` gives the absolute logical
# page of a slot's first table entry, so the same walk serves a per-slot
# RING of pages (a window layer's bounded state) as well as the pool.
# Both are static: gpt2's programs never reach this function.


def _paged_gqa_kernel(tables_ref, pos_ref, owned_ref, page0_ref, q_ref,
                      knew_ref, vnew_ref, kp_ref, vp_ref, out_ref,
                      acc_ref, m_ref, l_ref, *, n_kv_head, head_dim,
                      page_size, n_pages_per_slot, window, attn_window,
                      scale):
    b = pl.program_id(0)
    p = pl.program_id(1)
    D, psz, W = head_dim, page_size, window
    GW = q_ref.shape[1]                       # G * W rows a KV head
    pos = pos_ref[b]
    # row r of a group's block is query row j = r % W of the window
    row_j = jax.lax.broadcasted_iota(jnp.int32, (GW, 1), 0) % W

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(owned_ref[b, p] > 0)
    def _accumulate():
        kpos = (jax.lax.broadcasted_iota(jnp.int32, (1, psz), 1)
                + (page0_ref[b] + p) * psz)
        live = kpos < pos                                       # (1, psz)
        if attn_window:
            live = live & (kpos > pos + row_j - attn_window)    # (GW, psz)
        for g in range(n_kv_head):
            sl = slice(g * D, (g + 1) * D)
            q = q_ref[g].astype(jnp.float32)                    # (GW, D)
            kcf = kp_ref[:, sl].astype(jnp.float32)             # (psz, D)
            vcf = vp_ref[:, sl].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kcf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # (GW, psz)
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[g]                                   # (GW, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(pexp, axis=1,
                                                  keepdims=True)
            acc_ref[g] = (acc_ref[g] * alpha
                          + jax.lax.dot_general(
                              pexp, vcf, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))
            m_ref[g] = m_new

    @pl.when(p == n_pages_per_slot - 1)
    def _finalize():
        col = jax.lax.broadcasted_iota(jnp.int32, (GW, W), 1)
        fresh = col <= row_j           # row j attends fresh rows 0..j
        if attn_window:
            fresh = fresh & (col > row_j - attn_window)
        for g in range(n_kv_head):
            sl = slice(g * D, (g + 1) * D)
            q = q_ref[g].astype(jnp.float32)
            kn = knew_ref[:, sl].astype(jnp.float32)            # (W, D)
            vn = vnew_ref[:, sl].astype(jnp.float32)
            s_new = jax.lax.dot_general(
                q, kn, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # (GW, W)
            s_new = jnp.where(fresh, s_new, NEG_INF)
            m_prev = m_ref[g]
            m2 = jnp.maximum(m_prev,
                             jnp.max(s_new, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m2)
            p_new = jnp.where(fresh, jnp.exp(s_new - m2), 0.0)
            # denom >= diagonal term > 0 always (row j attends itself)
            denom = l_ref[g] * alpha + jnp.sum(p_new, axis=1,
                                               keepdims=True)
            out = (acc_ref[g] * alpha
                   + jax.lax.dot_general(
                       p_new, vn, (((1,), (0,)), ((), ())),
                       preferred_element_type=jnp.float32)) / denom
            out_ref[g] = out.astype(out_ref.dtype)


def gqa_owned_pages(pos: jnp.ndarray, page0: jnp.ndarray, n_table: int,
                    page_size: int, attn_window: int) -> jnp.ndarray:
    """(B, n_table) bool: the table entries whose page holds a position
    the window's rows read from the STALE state: some k < pos and, under
    a window, some k > pos - attn_window (row 0's bound, the lowest)."""
    a = page0[:, None] + jnp.arange(n_table, dtype=jnp.int32)[None, :]
    owned = a * page_size < pos[:, None]
    if attn_window:
        owned &= (a + 1) * page_size - 1 > pos[:, None] - attn_window
    return owned & (a >= 0)


def paged_gqa_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                        v_new: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, tables: jnp.ndarray,
                        pos: jnp.ndarray, *, n_head: int, n_kv_head: int,
                        attn_window: int = 0, page0=None,
                        name: str = "paged_window_attention"):
    """``paged_window_attention`` for grouped queries: q (B, W, n_head*D),
    k_new/v_new (B, W, n_kv_head*D), pages (N, page, n_kv_head*D); query
    head n reads KV head ``n // (n_head // n_kv_head)``. Same contract:
    attends the STALE pages masked to positions < pos and folds the fresh
    causal window, so the caller scatters afterwards.

    ``attn_window`` > 0 keeps row j to positions > pos + j - attn_window;
    pages wholly behind it are never fetched. ``page0`` (B,) is the
    absolute logical page of ``tables[:, 0]`` (default 0: the table
    starts at the sequence's first page); a window layer's ring passes
    the first page of its walk. ``name`` is the kernel's name in the
    HLO and the trace: full layers keep ``paged_window_attention``, a
    window layer's call says ``swa_...``."""
    N, psz, Ckv = k_pages.shape
    B, W, Cq = q.shape
    mp = tables.shape[1]
    D = Ckv // n_kv_head
    G = n_head // n_kv_head
    assert Cq == n_head * D and n_head == G * n_kv_head, (q.shape,
                                                          k_pages.shape)
    pos = jnp.asarray(pos, jnp.int32)
    page0 = (jnp.zeros((B,), jnp.int32) if page0 is None
             else jnp.asarray(page0, jnp.int32))
    owned = gqa_owned_pages(pos, page0, mp, psz, attn_window)
    eff = _fill_last_owned(jnp.asarray(tables, jnp.int32), owned)
    # a KV head's G query heads as G*W rows of one block
    qg = (q.reshape(B, W, n_kv_head, G, D).transpose(0, 2, 3, 1, 4)
          .reshape(B, n_kv_head, G * W, D))
    kernel = functools.partial(
        _paged_gqa_kernel, n_kv_head=n_kv_head, head_dim=D, page_size=psz,
        n_pages_per_slot=mp, window=W, attn_window=int(attn_window),
        scale=D ** -0.5)

    def q_map(b, p, tables, pos, owned, page0):
        return (b, 0, 0, 0)

    def row_map(b, p, tables, pos, owned, page0):
        return (b, 0, 0)

    def page_map(b, p, tables, pos, owned, page0):
        return (tables[b, p], 0, 0)

    qspec = _vmem_spec((None, n_kv_head, G * W, D), q_map)
    row = _vmem_spec((None, W, Ckv), row_map)
    page = _vmem_spec((None, psz, Ckv), page_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, mp),
        in_specs=[qspec, row, row, page, page],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((n_kv_head, G * W, D), jnp.float32),
                        pltpu.VMEM((n_kv_head, G * W, 1), jnp.float32),
                        pltpu.VMEM((n_kv_head, G * W, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv_head, G * W, D), q.dtype),
        name=name, interpret=_interpret_mode(),
        compiler_params=_compiler_params(0, 2),
    )(eff, pos, owned.astype(jnp.int32), page0, qg, k_new, v_new,
      k_pages, v_pages)
    return (out.reshape(B, n_kv_head, G, W, D).transpose(0, 3, 1, 2, 4)
            .reshape(B, W, Cq))
