"""Ring attention: causal self-attention with the sequence axis sharded
across devices ('seq' mesh axis), KV blocks rotating around the ring via
``lax.ppermute`` over ICI.

The reference caps context at block_size because attention materializes the
full (T, T) weight matrix on one device (GPT1.py:106,114-116; the assert at
GPT-2.py:109). This module removes the single-device sequence cap: each of
the ``n`` devices on the 'seq' axis holds a (B, H, T/n, D) shard of q/k/v,
and at ring step ``s`` device ``i`` computes the attention block between its
local queries and the KV chunk originating on device ``(i - s) mod n``,
accumulated with the online-softmax recurrence (running max ``m``, running
normalizer ``l``, rescaled accumulator) so nothing bigger than a
(T/n, T/n) score tile ever exists. KV chunks move one hop per step
(device j -> j+1), so the collective is a neighbor ``ppermute`` that rides
ICI links, overlapping with the local block matmul.

Causality falls out of masking on *global* positions (chunk_index * T_local
+ local offset) — the diagonal block gets a triangular mask, blocks from
earlier chunks are unmasked, blocks from later chunks mask to -inf and
contribute nothing. The loop is a ``lax.scan`` with static trip count
``n``, so the whole ring is reverse-mode differentiable (the VJP of
``ppermute`` is the inverse rotation, and XLA overlaps those transfers the
same way).

Composition: ``make_ring_attention_fn(mesh)`` returns an ``attention_fn``
for ``models.gpt.forward`` — a ``jax.shard_map`` region over the mesh whose
'data' and 'model' axes are plain partitioning (batch, heads) and whose
'seq' axis carries the ring. It drops into the otherwise-GSPMD training
step; XLA stitches the sharding transitions.

Attention-weight dropout (GPT1.py:117) applies inside the ring with the
framework's shared uint8/1-in-256-quantized scheme: the mask multiplies
the unnormalized p *after* the running normalizer l accumulates it (the
same normalized-weights semantics as the dense path and the flash
kernel's in-kernel mask), keyed per (device, hop, q-chunk) so every
(q, k) block — computed on exactly one device — draws an independent
stream. Per-hop score memory is bounded by ``q_chunk``: queries process
in chunks of at most that many rows (a lax.map, sequential), so nothing
bigger than a (B, H, q_chunk, T_local) tile exists no matter how large
the per-device sequence shard is.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF, uint8_inverted_dropout

# per-hop q-chunk row bound: peak score-tile memory is
# B * H * Q_CHUNK * T_local * 4 bytes instead of B * H * T_local^2 * 4
Q_CHUNK = 2048


def _flash_hop_supported(q) -> bool:
    """Envelope for running ring hops through the Pallas chunk kernels:
    the shared kernel-eligibility check (ops.flash_attention.
    _pallas_supported — TPU backend, lane-aligned shapes). No residency
    bound anymore: past flash_pallas.STREAM_KV_BYTES the chunk op
    auto-routes to its streamed kernels (kv/q axis on the pallas grid,
    O(block^2) VMEM), so arbitrarily long per-device shards keep a
    Pallas kernel instead of falling back to the q-chunked einsum
    body — exactly the long-per-shard runs ring attention exists for
    (round-3 verdict item 4)."""
    from ..ops.flash_attention import _pallas_supported

    return _pallas_supported(q)


def _ring_local_flash(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      axis_name: str, scale: Optional[float],
                      dropout_rate: float = 0.0,
                      rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """Ring body with Pallas chunk-attention hops.

    Each hop is one fused (o, lse) kernel call
    (ops.flash_pallas.pallas_flash_chunk) with global-position causal
    masking and in-kernel dropout; hops merge by the logsumexp
    recurrence in plain JAX, so the whole ring is differentiable through
    the kernels' custom VJPs. Per-hop HBM is O(B*H*Tl*D) — no (Tl, Tl)
    score materialization at all (vs the einsum body's q-chunked tiles).
    Below STREAM_KV_BYTES the kernel holds one (batch, head)'s K/V chunk
    resident in VMEM; past it the chunk op auto-routes to its streamed
    kernels (kv/q grid axis + VMEM scratch state), so shard length is
    bounded by HBM only.

    Dropout: the kernel's counter-hash mask keys on absolute (seed,
    program bh, q position, k position); positions are global here and
    every (q, k) pair is computed on exactly one device/hop, while
    ``rng`` arrives pre-folded per (data, model) shard, so streams never
    collide.
    """
    from ..ops.flash_pallas import pallas_flash_chunk

    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    q_off = idx * Tl

    def hop_attn(k_cur, v_cur, src):
        return pallas_flash_chunk(q, k_cur, v_cur, scale=scale, causal=True,
                                  q_offset=q_off, k_offset=src * Tl,
                                  dropout_rate=dropout_rate,
                                  dropout_rng=rng)

    def merge(o_acc, lse_acc, o_s, lse_s):
        # both lse's are finite on every executed hop: the diagonal hop's
        # rows attend at least themselves, earlier-chunk hops are fully
        # unmasked, and future chunks never execute (cond below)
        m = jnp.maximum(lse_acc, lse_s)
        w1 = jnp.exp(lse_acc - m)
        w2 = jnp.exp(lse_s - m)
        denom = w1 + w2
        o = (o_acc * w1[..., None] + o_s.astype(jnp.float32) * w2[..., None]
             ) / denom[..., None]
        return o, m + jnp.log(denom)

    o_acc, lse_acc = hop_attn(k, v, idx)  # resident diagonal block
    o_acc = o_acc.astype(jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, s):
        o_acc, lse_acc, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (idx - s) % n

        def do_hop(o_a, lse_a):
            o_s, lse_s = hop_attn(k_cur, v_cur, src)
            return merge(o_a, lse_a, o_s, lse_s)

        o_acc, lse_acc = jax.lax.cond(src <= idx, do_hop,
                                      lambda a, b: (a, b), o_acc, lse_acc)
        return (o_acc, lse_acc, k_cur, v_cur), None

    if n > 1:
        (o_acc, _, _, _), _ = jax.lax.scan(
            step, (o_acc, lse_acc, k, v), jnp.arange(1, n))
    return o_acc.astype(q.dtype)


def _ring_local(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                axis_name: str, scale: Optional[float],
                dropout_rate: float = 0.0,
                rng: Optional[jax.Array] = None, train: bool = False,
                q_chunk: int = Q_CHUNK,
                hop_impl: str = "auto") -> jnp.ndarray:
    """Per-device ring attention body. q/k/v: local (B, H, T_local, D).

    ``rng`` must already be decorrelated across every sharded axis except
    ``axis_name`` (the ring folds in its own seq-axis index, hop and
    q-chunk); callers whose batch/heads are sharded fold those axis
    indices in first (ring_attention does this for the GSPMD wrapper).

    ``hop_impl``: 'einsum' (q-chunked XLA tiles, runs everywhere),
    'flash' (Pallas chunk kernel per hop — _ring_local_flash), or 'auto'
    (flash on TPU when the shape fits the kernel envelope).
    """
    if hop_impl not in ("auto", "flash", "einsum"):
        raise ValueError(f"hop_impl must be 'auto', 'flash' or 'einsum', "
                         f"got {hop_impl!r}")
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    if scale is None:
        scale = D ** -0.5
    dropping = train and dropout_rate > 0.0 and rng is not None
    if hop_impl == "flash" or (
            hop_impl == "auto" and _flash_hop_supported(q)):
        return _ring_local_flash(q, k, v, axis_name=axis_name, scale=scale,
                                 dropout_rate=dropout_rate if dropping
                                 else 0.0,
                                 rng=rng if dropping else None)
    key = (jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
           if dropping else None)
    # largest divisor of Tl that fits the chunk bound, so the per-hop
    # score-tile guarantee holds for every shard size (not only exact
    # multiples); trace-time loop, worst case q_chunk iterations
    qc = next(d for d in range(min(q_chunk, Tl), 0, -1) if Tl % d == 0)
    nc = Tl // qc

    qf = q.astype(jnp.float32) * scale

    def chunk_update(q_c, acc, m, l, k_cur, v_cur, src, c_idx, hop_key):
        """Online-softmax update of one (qc, Tl) score tile: this
        device's q rows [c_idx*qc, ...) against the KV chunk originating
        on device ``src``."""
        logits = jnp.einsum("bhqd,bhkd->bhqk", q_c,
                            k_cur.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
        qpos = (idx * Tl + c_idx * qc
                + jax.lax.broadcasted_iota(jnp.int32, (qc, Tl), 0))
        kpos = src * Tl + jax.lax.broadcasted_iota(jnp.int32, (qc, Tl), 1)
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        # l is dropout-free (dropout applies to the normalized weights);
        # only the V accumulation sees the inverted-dropout multiplier —
        # flash-kernel semantics (flash_pallas._fwd_tile)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if hop_key is not None:
            p = uint8_inverted_dropout(
                p, dropout_rate, jax.random.fold_in(hop_key, c_idx))
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    # remat the per-chunk update: its backward recomputes the (qc, Tl)
    # score/probability tiles from q/k/v instead of storing them as scan
    # residuals — without this the einsum ring saves O(T^2/n) f32 tiles
    # per hop (measured 76.8 GB/device at Tl=32k in the longctx
    # rehearsal; 0.82 GB with remat), which is the flash hops' recompute
    # semantics anyway (their custom VJP re-derives tiles from lse)
    chunk_update_r = jax.checkpoint(chunk_update)

    def block_update(acc, m, l, k_cur, v_cur, src, hop):
        hop_key = jax.random.fold_in(key, hop) if dropping else None
        if nc == 1:
            return chunk_update_r(qf, acc, m, l, k_cur, v_cur, src,
                                  jnp.int32(0), hop_key)

        def per_chunk(xs):
            q_c, acc_c, m_c, l_c, c_idx = xs
            return chunk_update_r(q_c, acc_c, m_c, l_c, k_cur, v_cur, src,
                                  c_idx, hop_key)

        def split(t):  # (B, H, Tl, X) -> (nc, B, H, qc, X)
            return jnp.moveaxis(
                t.reshape(B, H, nc, qc, t.shape[-1]), 2, 0)

        def join(t):
            return jnp.moveaxis(t, 0, 2).reshape(B, H, Tl, t.shape[-1])

        acc_n, m_n, l_n = jax.lax.map(
            per_chunk, (split(qf), split(acc), split(m), split(l),
                        jnp.arange(nc)))
        return join(acc_n), join(m_n), join(l_n)

    # step 0 is the resident diagonal block — no rotation needed for it, and
    # peeling it keeps the scan at n-1 rotations (no dead final ppermute)
    acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    m0 = jnp.full((B, H, Tl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl, 1), jnp.float32)
    acc, m, l = block_update(acc0, m0, l0, k, v, idx, jnp.int32(0))

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, s):
        acc, m, l, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (idx - s) % n  # chunk id the rotating KV now holds
        # chunks from the future (src > idx) are fully causal-masked —
        # their block_update is all wasted FLOPs. The predicate is
        # per-device (axis_index), which XLA:TPU lowers to a real
        # conditional, so each device does only its causal share and the
        # ring's total compute matches flash-style block skipping.
        acc, m, l = jax.lax.cond(
            src <= idx,
            lambda a, mm, ll: block_update(a, mm, ll, k_cur, v_cur, src, s),
            lambda a, mm, ll: (a, mm, ll),
            acc, m, l)
        return (acc, m, l, k_cur, v_cur), None

    if n > 1:
        # n == 1 (e.g. a degenerate seq axis inside the pipeline region)
        # must skip the rotation scan entirely: a zero-trip scan carries a
        # size-0 xs array whose cotangent trips XLA sharding-override
        # assertions under shard_map transpose — and it is dead code anyway
        (acc, _, l, _, _), _ = jax.lax.scan(
            step, (acc, m, l, k, v), jnp.arange(1, n))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   mesh: Mesh, scale: Optional[float] = None,
                   seq_axis: str = "seq", dropout_rate: float = 0.0,
                   rng: Optional[jax.Array] = None,
                   train: bool = False,
                   hop_impl: str = "auto") -> jnp.ndarray:
    """Causal ring attention over a sharded sequence.

    q, k, v: global (B, H, T, D) with T sharded over ``seq_axis`` (and
    optionally B over 'data', H over 'model'). Returns (B, H, T, D) with the
    same sharding. T must divide evenly by the seq axis size.

    With ``dropout_rate`` > 0 (and ``rng``, while ``train``), inverted
    attention-weight dropout applies inside the ring. The replicated key
    is decorrelated per (data, model) shard here — batch elements and
    heads live on different devices and must not share mask streams —
    and per (seq device, hop, q-chunk) inside ``_ring_local``.
    """
    spec = P("data", "model", seq_axis, None)
    if not (train and dropout_rate > 0.0 and rng is not None):
        fn = shard_map(
            functools.partial(_ring_local, axis_name=seq_axis, scale=scale,
                              hop_impl=hop_impl),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return fn(q, k, v)

    def body(q, k, v, key):
        shard = (jax.lax.axis_index("data") * axis_size("model")
                 + jax.lax.axis_index("model"))
        return _ring_local(q, k, v, axis_name=seq_axis, scale=scale,
                           dropout_rate=dropout_rate,
                           rng=jax.random.fold_in(key, shard), train=True,
                           hop_impl=hop_impl)

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec, P()),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v, rng)


def make_ring_attention_fn(mesh: Mesh, scale: Optional[float] = None,
                           dropout_rate: float = 0.0,
                           hop_impl: str = "auto"):
    """attention_fn for ``models.gpt.forward`` / ``train.steps`` — plugs the
    sharded ring core into the per-block attention slot. ``hop_impl``
    pins the per-hop body ('einsum' | 'flash' | 'auto')."""
    def attention_fn(q, k, v, rng=None, train=False):
        return ring_attention(q, k, v, mesh=mesh, scale=scale,
                              dropout_rate=dropout_rate, rng=rng,
                              train=train, hop_impl=hop_impl)
    return attention_fn
