"""Pallas TPU flash attention: blockwise online-softmax, fwd + custom-VJP bwd.

Replaces the O(T^2)-HBM attention the reference materializes per head
(GPT1.py:114-116) with a fused kernel that keeps only (block_q, block_k)
score tiles in VMEM. Forward follows the standard flash algorithm (running
max m, running normalizer l, rescaled accumulator); backward recomputes
score tiles blockwise from the saved logsumexp, producing dq in a q-major
kernel and dk/dv in a kv-major kernel (no stored attention matrix anywhere).

Layout notes (TPU): q/do tiles are (block, D) with D in {32, 64, 128,
256} and block auto-sized to the largest of {512, 256, 128} dividing T
(``_auto_block`` — 512x512 score tiles measured 2.3x faster fwd+bwd than
128x128 on v5e; callers may override). LSE/delta are per-row scalars,
which Mosaic cannot tile as a bare (T,) lane — they are carried
broadcast across a LANES-wide trailing dim ((BH, T, LANES) arrays,
(block_q, LANES) tiles), the same layout the reference TPU flash kernel
in jax.experimental.pallas.ops.tpu uses for its m/l stats.
Causal masking skips fully-masked kv blocks entirely (the fori_loop upper
bound is derived from the q-block index), so the kernel does ~half the
FLOPs of the dense path on causal workloads.

Two kernel families, auto-selected by K/V footprint (STREAM_KV_BYTES):
the resident kernels above hold one (batch, head)'s full (T, D) K/V in
VMEM and carry the online-softmax state in registers across a fori_loop
(fastest while it fits; Mosaic stops allocating it around T=32k for
D=64 bf16); the streamed kernels put the kv axis on the pallas grid and
carry the state in VMEM scratch, so VMEM use is O(block^2) and T is
bounded by HBM only — with a scalar-prefetched triangular tile map for
causal runs that skips masked tiles' fetches and grid steps entirely.
The families share their tile math (_fwd_tile/_dq_tile/_dkv_tile — one
source of truth, identical ops in identical order) and the counter-based
dropout mask keys off absolute positions, so their outputs are
bit-identical: measured exactly equal on v5e hardware, and
test_stream_dropout_matches_resident asserts exact equality in
interpret mode.

Why each family exists (the GPT presets in config.py, run through the
routing predicates at their widths and block_size):
  packed resident (_flash_packed)       char-gpt, gpt2-shakespeare,
                                        o200k-shakespeare (T=256, C=384)
  head-group (_flash_packed_group)      gpt2-small, gpt2-medium, gpt2-large
  split-heads resident (_flash)         gpt2-xl (25 heads pair into no
                                        128-lane group)
  split-heads streamed (_flash_stream)  K/V past STREAM_KV_BYTES a head
                                        (--block-size past 16,384 at D=64)
  ring chunk (pallas_flash_chunk)       --attention ring on a sequence axis
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger(__name__)

BLOCK = 128
LANES = 128  # trailing width for per-row stats (Mosaic lane alignment)
NEG_INF = -1e30


def _vmem_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# in-kernel dropout bits
#
# Counter-based hash instead of pltpu.prng_*: the mask for tile
# (bh, q-block, k-block) must be regenerated bit-identically by three
# different kernels (fwd, bwd-dq, bwd-dkv) whose loop structures differ,
# and must also run under the CPU interpreter (prng_seed has no CPU
# lowering). Two murmur3 fmix32 rounds chained over (seed^bh, qpos, kpos)
# give full avalanche per element at a handful of VPU integer ops — noise
# quality is plenty for dropout, and tests pin the keep-rate statistics.
# ---------------------------------------------------------------------------

def _fmix32(x: jnp.ndarray) -> jnp.ndarray:
    # murmur3 finalizer; uint32 arithmetic wraps mod 2^32
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _dropout_mult(seed, bh, q_first, k_first, block_q, block_k, rate):
    """(block_q, block_k) float32 tile of {0, 1/(1-q)} — inverted
    dropout on attention weights, deterministic in (seed, bh, q, k).

    The rate quantizes to the same 1/256 granularity as every other
    dropout site (ops.attention.quantize_dropout_rate), so the flash
    path applies the identical effective rate as the einsum path the
    'auto' router may pick instead."""
    from .attention import quantize_dropout_rate
    qpos = (jnp.asarray(q_first).astype(jnp.uint32)
            + jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 0))
    kpos = (jnp.asarray(k_first).astype(jnp.uint32)
            + jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 1))
    h = _fmix32(jnp.asarray(seed).astype(jnp.uint32)
                ^ (jnp.asarray(bh).astype(jnp.uint32)
                   * jnp.uint32(0x9E3779B9)))
    y = _fmix32(_fmix32(h ^ qpos) ^ kpos)
    q = quantize_dropout_rate(rate)
    threshold = jnp.uint32(int(q * 256) * 2**24)  # q * 2^32, exact
    return jnp.where(y > threshold, jnp.float32(1.0 / (1.0 - q)),
                     jnp.float32(0.0))


# ---------------------------------------------------------------------------
# shared tile math
#
# One source of truth for the score/mask/online-softmax/gradient tile
# updates. Every kernel family (resident fori_loop, rectangular stream,
# triangular stream) wraps these on plain (block_q, ...) arrays — only
# how the operands arrive (refs, loop carries, VMEM scratch) differs.
# Keeping the math in one place is also what makes the families
# bit-identical: identical ops in identical order.
# ---------------------------------------------------------------------------


def _causal_mask(s, q_first, k_first, block_q, block_k):
    qpos = q_first + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = k_first + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(kpos <= qpos, s, NEG_INF)


def _fwd_tile(q, k, v, acc, m, l, *, scale, causal, q_first, k_first,
              block_q, block_k, seed, bh, dropout_rate):
    """One online-softmax update: returns (acc', m', l'). The softmax
    normalizer l is dropout-free (dense-path semantics: dropout applies
    to the normalized weights); only the V accumulation sees the
    inverted-dropout multiplier.

    Matmuls run on the operands' native dtype (bf16 inputs hit the MXU's
    bf16 path — ~4x the f32 rate) with f32 accumulation
    (preferred_element_type); scaling, max/exp and the normalizer stay
    f32. The probability tile is cast back to the value dtype for the
    p@v matmul — the standard flash-kernel trade (weights are in [0, 1],
    so the cast costs ~3 relative digits on an already-bf16 pipeline)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, q_first, k_first, block_q, block_k)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if dropout_rate > 0.0:
        p = p * _dropout_mult(seed, bh, q_first, k_first, block_q, block_k,
                              dropout_rate)
    acc_new = acc * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def _dq_tile(q, k, v, do, lse, delta, *, scale, causal, q_first, k_first,
             block_q, block_k, seed, bh, dropout_rate):
    """dq contribution of one (q-block, kv-block) tile. d(softmax):
    ds_ij = p_ij (z_ij dp_ij - delta_i); delta (the do.o rowsum) already
    absorbs the dropout mask z from forward. Matmuls on native dtype
    with f32 accumulation (see _fwd_tile)."""
    z = (_dropout_mult(seed, bh, q_first, k_first, block_q, block_k,
                       dropout_rate) if dropout_rate > 0.0 else None)
    p = _bwd_p_tile(q, k, lse, scale=scale, causal=causal, q_first=q_first,
                    k_first=k_first, block_q=block_q, block_k=block_k)
    ds = _bwd_ds_tile(p, do, v, delta, scale=scale, z=z)
    return jax.lax.dot_general(ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bwd_p_tile(q, k, lse, *, scale, causal, q_first, k_first, block_q,
                block_k):
    """Recompute one probability tile from the forward's lse — the shared
    first half of every backward tile (split dq / dkv kernels and the
    fused single-tile kernel all call this; keep it the one source of
    truth for the score/mask/exp math)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, q_first, k_first, block_q, block_k)
    return jnp.exp(s - lse)


def _bwd_ds_tile(p, do, v, delta, *, scale, z):
    """d(softmax) tile ds = p (z dp - delta) scale — the shared second
    half (see _bwd_p_tile). ``z`` is the inverted-dropout multiplier or
    None; callers cast ds to the operand dtype at their final matmuls."""
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if z is not None:
        dp = dp * z
    return p * (dp - delta) * scale


def _dkv_tile(q, k, v, do, lse, delta, *, scale, causal, q_first, k_first,
              block_q, block_k, seed, bh, dropout_rate):
    """(dk, dv) contributions of one tile, plus the ds tile (cast to the
    operand dtype) so fully-fused callers can derive dq from the same
    recompute. The dropout stream keys off absolute (seed, bh, q-pos,
    k-pos), so kv-major loops regenerate the exact forward mask. Matmuls
    on native dtype with f32 accumulation (see _fwd_tile)."""
    z = (_dropout_mult(seed, bh, q_first, k_first, block_q, block_k,
                       dropout_rate) if dropout_rate > 0.0 else None)
    p = _bwd_p_tile(q, k, lse, scale=scale, causal=causal, q_first=q_first,
                    k_first=k_first, block_q=block_q, block_k=block_k)
    dv_c = jax.lax.dot_general(
        (p * z if z is not None else p).astype(do.dtype), do,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dsc = _bwd_ds_tile(p, do, v, delta, scale=scale, z=z).astype(q.dtype)
    dk_c = jax.lax.dot_general(dsc, q, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    return dk_c, dv_c, dsc


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                causal, seq_len, block_q, block_k, dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[...]                                      # (bq, D) native dtype
    D = q.shape[-1]
    q_first = j * block_q

    if causal:
        n_kv = (q_first + block_q + block_k - 1) // block_k
    else:
        n_kv = seq_len // block_k

    def body(kb, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        return _fwd_tile(q, k, v, acc, m, l, scale=scale, causal=causal,
                         q_first=q_first, k_first=kb * block_k,
                         block_q=block_q, block_k=block_k, seed=seed_ref[0],
                         bh=i, dropout_rate=dropout_rate)

    acc = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_kv, body, (acc, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), (block_q, LANES))


def _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k,
               dropout_rate):
    B, H, T, D = q.shape
    BH = B * H
    qf = q.reshape(BH, T, D)
    kf = k.reshape(BH, T, D)
    vf = v.reshape(BH, T, D)
    grid = (BH, T // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               seq_len=T, block_q=block_q, block_k=block_k,
                               dropout_rate=dropout_rate)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, T, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, T, D), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        ],
        name="flash_fwd",
        interpret=_interpret_mode(),
    )(seed, qf, kf, vf)
    return o.reshape(B, H, T, D), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, scale, causal, seq_len, block_q,
                   block_k, dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[...]                                       # (bq, D) native dtype
    do = do_ref[...]
    lse = lse_ref[...][:, :1]                            # (bq, 1) of (bq, LANES)
    delta = delta_ref[...][:, :1]
    q_first = j * block_q
    if causal:
        n_kv = (q_first + block_q + block_k - 1) // block_k
    else:
        n_kv = seq_len // block_k

    def body(kb, dq):
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        return dq + _dq_tile(q, k, v, do, lse, delta, scale=scale,
                             causal=causal, q_first=q_first,
                             k_first=kb * block_k, block_q=block_q,
                             block_k=block_k, seed=seed_ref[0], bh=i,
                             dropout_rate=dropout_rate)

    dq = jax.lax.fori_loop(0, n_kv,
                           body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, scale, causal, seq_len,
                    block_q, block_k, dropout_rate):
    i = pl.program_id(0)
    kb = pl.program_id(1)
    k = k_ref[...]                                       # (bk, D) native dtype
    v = v_ref[...]
    k_first = kb * block_k
    n_q = seq_len // block_q
    first_q = (k_first // block_q) if causal else 0

    def body(jb, carry):
        dk, dv = carry
        q = q_ref[pl.ds(jb * block_q, block_q), :]
        do = do_ref[pl.ds(jb * block_q, block_q), :]
        lse = lse_ref[pl.ds(jb * block_q, block_q), :][:, :1]
        delta = delta_ref[pl.ds(jb * block_q, block_q), :][:, :1]
        dk_c, dv_c, _ = _dkv_tile(q, k, v, do, lse, delta, scale=scale,
                               causal=causal, q_first=jb * block_q,
                               k_first=k_first, block_q=block_q,
                               block_k=block_k, seed=seed_ref[0], bh=i,
                               dropout_rate=dropout_rate)
        return dk + dk_c, dv + dv_c

    dk0 = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    dk, dv = jax.lax.fori_loop(first_q, n_q, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, *, scale, causal,
                      block_q, block_k, dropout_rate):
    """Single-tile fused backward (T == block_q == block_k): the score /
    probability tile is computed once and dq, dk AND dv all come from it —
    one kernel launch and one s/p recompute instead of two of each. At
    short T the per-step cost is launch- and recompute-bound (traced on
    v5e: 12 bwd launches were 23% of the char-GPT step), which is exactly
    what this halves. Same dropout stream as the split kernels
    (seed, bh, q_first=0, k_first=0), so fused and split backwards see the
    forward's mask."""
    i = pl.program_id(0)
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[...][:, :1]
    delta = delta_ref[...][:, :1]
    dk, dv, dsc = _dkv_tile(q, k, v, do, lse, delta, scale=scale,
                            causal=causal, q_first=0, k_first=0,
                            block_q=block_q, block_k=block_k,
                            seed=seed_ref[0], bh=i,
                            dropout_rate=dropout_rate)
    dq = jax.lax.dot_general(dsc, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd_fused(scale, causal, block_q, block_k, dropout_rate,
                     seed, qf, kf, vf, gf, lse, delta, BH, T, D, dtype):
    kernel = functools.partial(
        _bwd_fused_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    spec_td = _vmem_spec((None, T, D), lambda i: (i, 0, 0))
    spec_tl = _vmem_spec((None, T, LANES), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=[_smem_spec(), spec_td, spec_td, spec_td, spec_td,
                  spec_tl, spec_tl],
        out_specs=[spec_td, spec_td, spec_td],
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), dtype)] * 3,
        name="flash_bwd",
        interpret=_interpret_mode(),
    )(seed, qf, kf, vf, gf, lse, delta)


# dq scratch bound for the kv-major fused backward. The kernel's VMEM
# footprint per program is the full-T q/do/lse/delta blocks (~1.3 kB/row
# at D=64) PLUS this (T, D) f32 scratch and the full-T dq output block;
# 1 MiB of scratch (T<=4096 at D=64) keeps the total comfortably inside
# what the resident family is measured to compile, and leaves the split
# kernels reachable for longer resident sequences (T in (4k, 16k])
FUSED_DQ_SCRATCH_BYTES = 1024 * 1024


def _fused_kv_major_bwd(scale, causal, block_q, block_k, dropout_rate,
                        seed, offs, qf, kf, vf, gf, lse, delta,
                        BH, Tq, Tk, D, dtype):
    """Shared kv-major fully-fused backward launch: one kernel computes
    dq, dk AND dv with a (Tq, D) f32 dq scratch (see
    _chunk_bwd_fused_kernel). The resident family is exactly the
    offs == (0, 0, 0), Tq == Tk special case — one kernel serves both
    the per-layer and ring-hop gradient paths."""
    kernel = functools.partial(
        _chunk_bwd_fused_kernel, scale=scale, causal=causal,
        seq_len_q=Tq, seq_len_k=Tk, block_q=block_q, block_k=block_k,
        dropout_rate=dropout_rate)
    spec_q = _vmem_spec((None, Tq, D), lambda i, kb: (i, 0, 0))
    spec_kv = _vmem_spec((None, block_k, D), lambda i, kb: (i, kb, 0))
    spec_tl = _vmem_spec((None, Tq, LANES), lambda i, kb: (i, 0, 0))
    kw = {"compiler_params": _compiler_params(1, 2)}
    return pl.pallas_call(
        kernel,
        grid=(BH, Tk // block_k),
        in_specs=[_smem_spec(), _smem_spec(), spec_q, spec_kv, spec_kv,
                  spec_q, spec_tl, spec_tl],
        out_specs=[spec_q, spec_kv, spec_kv],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), dtype),
        ],
        scratch_shapes=[_scratch((Tq, D))],
        name="flash_kvmajor_bwd",
        interpret=_interpret_mode(),
        **kw,
    )(seed, offs, qf, kf, vf, gf, lse, delta)


def _flash_bwd(scale, causal, block_q, block_k, dropout_rate, residuals, g):
    q, k, v, seed, o, lse = residuals  # lse: (BH, T) — see _flash_fwd_rule
    B, H, T, D = q.shape
    BH = B * H
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1).reshape(BH, T)
    # stats ride a LANES-wide trailing dim (see module docstring) — but
    # only transiently, materialized here just before the kernels; the
    # per-layer residual that lives across the whole backward pass is the
    # compact (BH, T) form (128x less HBM)
    delta = jnp.broadcast_to(delta[:, :, None], (BH, T, LANES))
    lse = jnp.broadcast_to(lse[:, :, None], (BH, T, LANES))
    qf, kf, vf = (t.reshape(BH, T, D) for t in (q, k, v))
    gf = g.reshape(BH, T, D)

    if T == block_q and T == block_k:
        # single-tile case: one fused launch computes dq, dk, dv together
        dq, dk, dv = _flash_bwd_fused(
            scale, causal, block_q, block_k, dropout_rate,
            seed, qf, kf, vf, gf, lse, delta, BH, T, D, q.dtype)
        shape = (B, H, T, D)
        return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape),
                None)

    if T * D * 4 <= FUSED_DQ_SCRATCH_BYTES:
        # multi-tile but the (T, D) f32 dq scratch fits VMEM: kv-major
        # fully-fused backward — one launch and one p/ds recompute per
        # tile instead of two of each (split kernels below remain for
        # longer resident sequences)
        dq, dk, dv = _fused_kv_major_bwd(
            scale, causal, block_q, block_k, dropout_rate,
            seed, jnp.zeros((3,), jnp.int32), qf, kf, vf, gf, lse, delta,
            BH, T, T, D, q.dtype)
        shape = (B, H, T, D)
        return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape),
                None)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, seq_len=T,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, T // block_q),
        in_specs=[
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, T, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, T, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j: (i, j, 0)),
        ],
        out_specs=_vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        name="flash_bwd_dq",
        interpret=_interpret_mode(),
    )(seed, qf, kf, vf, gf, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, seq_len=T,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, T // block_k),
        in_specs=[
            _smem_spec(),
            _vmem_spec((None, T, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, T, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, T, LANES), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, T, LANES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        ],
        name="flash_bwd_dkv",
        interpret=_interpret_mode(),
    )(seed, qf, kf, vf, gf, lse, delta)

    shape = (B, H, T, D)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape), None


# ---------------------------------------------------------------------------
# streamed variant: K/V blocks fetched from HBM per grid step
#
# The resident kernels above hold the full (T, D) K and V for one
# (batch, head) in VMEM, which caps single-chip T at roughly 32k for
# D=64 bf16. These variants add the kv axis to the pallas grid — TPU
# grids iterate sequentially with the last dimension minor, so the
# online-softmax state (acc, m, l) carries across kv steps in VMEM
# scratch while Mosaic double-buffers the (block, D) K/V fetches.
# VMEM use is then O(block^2) regardless of T: the sequence length is
# bounded by HBM only, and ring/Ulysses take over past one chip.
# Fully-masked causal tiles skip their matmuls via pl.when (the block
# fetch still happens; at block>=128 the kernel stays compute-bound).
# ---------------------------------------------------------------------------


def _compiler_params(n_parallel: int, n_total: int):
    """Mark leading grid dims parallel, trailing (carry) dims arbitrary."""
    sem = (("parallel",) * n_parallel
           + ("arbitrary",) * (n_total - n_parallel))
    return pltpu.CompilerParams(dimension_semantics=sem)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _fwd_kernel_stream(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_ref, m_ref, l_ref, *, scale, causal, seq_len,
                       block_q, block_k, dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kv = seq_len // block_k
    q_first = j * block_q
    k_first = kb * block_k
    last_kb = (((j + 1) * block_q - 1) // block_k) if causal else n_kv - 1

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    needed = (k_first <= q_first + block_q - 1) if causal else kb >= 0

    @pl.when(needed)
    def _update():
        acc, m_new, l_new = _fwd_tile(
            q_ref[...], k_ref[...], v_ref[...],
            acc_ref[...], m_ref[...][:, :1], l_ref[...][:, :1],
            scale=scale, causal=causal, q_first=q_first, k_first=k_first,
            block_q=block_q, block_k=block_k, seed=seed_ref[0], bh=i,
            dropout_rate=dropout_rate)
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == last_kb)
    def _finalize():
        m = m_ref[...][:, :1]
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape)


def _flash_fwd_stream(q, k, v, seed, scale, causal, block_q, block_k,
                      dropout_rate):
    B, H, T, D = q.shape
    BH = B * H
    qf, kf, vf = (t.reshape(BH, T, D) for t in (q, k, v))
    grid = (BH, T // block_q, T // block_k)
    kernel = functools.partial(
        _fwd_kernel_stream, scale=scale, causal=causal, seq_len=T,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    kw = {"compiler_params": _compiler_params(2, 3)}
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j, kb: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        ],
        scratch_shapes=[_scratch((block_q, D)), _scratch((block_q, LANES)),
                        _scratch((block_q, LANES))],
        name="flash_stream_fwd",
        interpret=_interpret_mode(),
        **kw,
    )(seed, qf, kf, vf)
    return o.reshape(B, H, T, D), lse


def _bwd_dq_kernel_stream(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dq_ref, dq_acc_ref, *, scale, causal,
                          seq_len, block_q, block_k, dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kv = seq_len // block_k
    q_first = j * block_q
    k_first = kb * block_k
    last_kb = (((j + 1) * block_q - 1) // block_k) if causal else n_kv - 1

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    needed = (k_first <= q_first + block_q - 1) if causal else kb >= 0

    @pl.when(needed)
    def _update():
        dq_acc_ref[...] = dq_acc_ref[...] + _dq_tile(
            q_ref[...], k_ref[...], v_ref[...], do_ref[...],
            lse_ref[...][:, :1], delta_ref[...][:, :1], scale=scale,
            causal=causal, q_first=q_first, k_first=k_first,
            block_q=block_q, block_k=block_k, seed=seed_ref[0], bh=i,
            dropout_rate=dropout_rate)

    @pl.when(kb == last_kb)
    def _finalize():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_stream(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                           *, scale, causal, seq_len, block_q, block_k,
                           dropout_rate):
    i = pl.program_id(0)
    kb = pl.program_id(1)
    jb = pl.program_id(2)
    n_q = seq_len // block_q
    k_first = kb * block_k
    q_first = jb * block_q

    @pl.when(jb == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    needed = (q_first + block_q - 1 >= k_first) if causal else jb >= 0

    @pl.when(needed)
    def _update():
        dk_c, dv_c, _ = _dkv_tile(
            q_ref[...], k_ref[...], v_ref[...], do_ref[...],
            lse_ref[...][:, :1], delta_ref[...][:, :1], scale=scale,
            causal=causal, q_first=q_first, k_first=k_first,
            block_q=block_q, block_k=block_k, seed=seed_ref[0], bh=i,
            dropout_rate=dropout_rate)
        dk_acc_ref[...] = dk_acc_ref[...] + dk_c
        dv_acc_ref[...] = dv_acc_ref[...] + dv_c

    @pl.when(jb == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_stream(scale, causal, block_q, block_k, dropout_rate,
                      residuals, g):
    q, k, v, seed, o, lse = residuals  # lse: (BH, T)
    B, H, T, D = q.shape
    BH = B * H
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1).reshape(BH, T)
    delta = jnp.broadcast_to(delta[:, :, None], (BH, T, LANES))
    lse = jnp.broadcast_to(lse[:, :, None], (BH, T, LANES))
    qf, kf, vf = (t.reshape(BH, T, D) for t in (q, k, v))
    gf = g.reshape(BH, T, D)
    kw = {"compiler_params": _compiler_params(2, 3)}

    dq_kernel = functools.partial(
        _bwd_dq_kernel_stream, scale=scale, causal=causal, seq_len=T,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, T // block_q, T // block_k),
        in_specs=[
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j, kb: (i, j, 0)),
        ],
        out_specs=_vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[_scratch((block_q, D))],
        name="flash_stream_bwd_dq",
        interpret=_interpret_mode(),
        **kw,
    )(seed, qf, kf, vf, gf, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel_stream, scale=scale, causal=causal, seq_len=T,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, T // block_k, T // block_q),
        in_specs=[
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, kb, jb: (i, jb, 0)),
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
            _vmem_spec((None, block_q, D), lambda i, kb, jb: (i, jb, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, kb, jb: (i, jb, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, kb, jb: (i, jb, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        ],
        scratch_shapes=[_scratch((block_k, D)), _scratch((block_k, D))],
        name="flash_stream_bwd_dkv",
        interpret=_interpret_mode(),
        **kw,
    )(seed, qf, kf, vf, gf, lse, delta)

    shape = (B, H, T, D)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape), None


# --- triangular causal grid (scalar-prefetched tile map) -------------------
#
# The rectangular (BH, n_q, n_kv) streamed grid runs — and fetches K/V
# for — every tile, including the ~half that causal masking discards
# (pl.when skips their matmuls, not their copies). For causal with
# block_q == block_k the grid is flattened to just the lower-triangle
# tiles: a host-precomputed (2, M) int32 tile map (M = n(n+1)/2) rides
# scalar prefetch into SMEM, and the BlockSpec index maps read the
# (q-block, kv-block) coordinates from it per grid step. Tiles of one
# q-row stay adjacent, so the output block and the online-softmax
# scratch carry across kv steps exactly as in the rectangular grid.


def _tri_tile_map(n: int, kv_major: bool) -> np.ndarray:
    """(2, M) int32: row 0 = outer block index, row 1 = inner (carried)
    block index. q-major (fwd/dq): for each q-block j, kv 0..j.
    kv-major (dkv): for each kv-block kb, q kb..n-1."""
    if kv_major:
        pairs = [(kb, jb) for kb in range(n) for jb in range(kb, n)]
    else:
        pairs = [(j, kb) for j in range(n) for kb in range(j + 1)]
    return np.asarray(pairs, np.int32).T.copy()


def _fwd_kernel_tri(tmap_ref, seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    acc_ref, m_ref, l_ref, *, scale, block, dropout_rate):
    i = pl.program_id(0)
    t = pl.program_id(1)
    j = tmap_ref[0, t]
    kb = tmap_ref[1, t]
    q_first = j * block
    k_first = kb * block

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    acc, m_new, l_new = _fwd_tile(
        q_ref[...], k_ref[...], v_ref[...],
        acc_ref[...], m_ref[...][:, :1], l_ref[...][:, :1], scale=scale,
        causal=True, q_first=q_first, k_first=k_first, block_q=block,
        block_k=block, seed=seed_ref[0], bh=i, dropout_rate=dropout_rate)
    acc_ref[...] = acc
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == j)
    def _finalize():
        mf = m_ref[...][:, :1]
        lf = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / lf).astype(o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(mf + jnp.log(lf), lse_ref.shape)


def _flash_fwd_tri(q, k, v, seed, scale, block, dropout_rate):
    B, H, T, D = q.shape
    BH = B * H
    n = T // block
    tmap = jnp.asarray(_tri_tile_map(n, kv_major=False))
    qf, kf, vf = (t.reshape(BH, T, D) for t in (q, k, v))
    kernel = functools.partial(_fwd_kernel_tri, scale=scale, block=block,
                               dropout_rate=dropout_rate)
    kw = {"compiler_params": _compiler_params(1, 2)}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, tmap.shape[1]),
        in_specs=[
            _vmem_spec((None, block, D), lambda i, t, tm, sd: (i, tm[0, t], 0)),
            _vmem_spec((None, block, D), lambda i, t, tm, sd: (i, tm[1, t], 0)),
            _vmem_spec((None, block, D), lambda i, t, tm, sd: (i, tm[1, t], 0)),
        ],
        out_specs=[
            _vmem_spec((None, block, D), lambda i, t, tm, sd: (i, tm[0, t], 0)),
            _vmem_spec((None, block, LANES),
                       lambda i, t, tm, sd: (i, tm[0, t], 0)),
        ],
        scratch_shapes=[_scratch((block, D)), _scratch((block, LANES)),
                        _scratch((block, LANES))],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        ],
        name="flash_tri_fwd",
        interpret=_interpret_mode(),
        **kw,
    )(tmap, seed, qf, kf, vf)
    return o.reshape(B, H, T, D), lse


def _bwd_dq_kernel_tri(tmap_ref, seed_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dq_ref, dq_acc_ref, *, scale,
                       block, dropout_rate):
    i = pl.program_id(0)
    t = pl.program_id(1)
    j = tmap_ref[0, t]
    kb = tmap_ref[1, t]
    q_first = j * block
    k_first = kb * block

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    dq_acc_ref[...] = dq_acc_ref[...] + _dq_tile(
        q_ref[...], k_ref[...], v_ref[...], do_ref[...],
        lse_ref[...][:, :1], delta_ref[...][:, :1], scale=scale,
        causal=True, q_first=q_first, k_first=k_first, block_q=block,
        block_k=block, seed=seed_ref[0], bh=i, dropout_rate=dropout_rate)

    @pl.when(kb == j)
    def _finalize():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_tri(tmap_ref, seed_ref, q_ref, k_ref, v_ref, do_ref,
                        lse_ref, delta_ref, dk_ref, dv_ref, dk_acc_ref,
                        dv_acc_ref, *, scale, block, n_q, dropout_rate):
    i = pl.program_id(0)
    t = pl.program_id(1)
    kb = tmap_ref[0, t]
    jb = tmap_ref[1, t]
    k_first = kb * block
    q_first = jb * block

    @pl.when(jb == kb)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    dk_c, dv_c, _ = _dkv_tile(
        q_ref[...], k_ref[...], v_ref[...], do_ref[...],
        lse_ref[...][:, :1], delta_ref[...][:, :1], scale=scale,
        causal=True, q_first=q_first, k_first=k_first, block_q=block,
        block_k=block, seed=seed_ref[0], bh=i, dropout_rate=dropout_rate)
    dk_acc_ref[...] = dk_acc_ref[...] + dk_c
    dv_acc_ref[...] = dv_acc_ref[...] + dv_c

    @pl.when(jb == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_tri(scale, block, dropout_rate, residuals, g):
    q, k, v, seed, o, lse = residuals  # lse: (BH, T)
    B, H, T, D = q.shape
    BH = B * H
    n = T // block
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1).reshape(BH, T)
    delta = jnp.broadcast_to(delta[:, :, None], (BH, T, LANES))
    lse = jnp.broadcast_to(lse[:, :, None], (BH, T, LANES))
    qf, kf, vf = (t.reshape(BH, T, D) for t in (q, k, v))
    gf = g.reshape(BH, T, D)
    kw = {"compiler_params": _compiler_params(1, 2)}

    tmap_q = jnp.asarray(_tri_tile_map(n, kv_major=False))
    dq_kernel = functools.partial(_bwd_dq_kernel_tri, scale=scale,
                                  block=block, dropout_rate=dropout_rate)
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, tmap_q.shape[1]),
            in_specs=[
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[1, t], 0)),
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[1, t], 0)),
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
                _vmem_spec((None, block, LANES),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
                _vmem_spec((None, block, LANES),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
            ],
            out_specs=_vmem_spec((None, block, D),
                                 lambda i, t, tm, sd: (i, tm[0, t], 0)),
            scratch_shapes=[_scratch((block, D))],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        name="flash_tri_bwd_dq",
        interpret=_interpret_mode(),
        **kw,
    )(tmap_q, seed, qf, kf, vf, gf, lse, delta)

    tmap_kv = jnp.asarray(_tri_tile_map(n, kv_major=True))
    dkv_kernel = functools.partial(_bwd_dkv_kernel_tri, scale=scale,
                                   block=block, n_q=n,
                                   dropout_rate=dropout_rate)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, tmap_kv.shape[1]),
            in_specs=[
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[1, t], 0)),
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[1, t], 0)),
                _vmem_spec((None, block, LANES),
                           lambda i, t, tm, sd: (i, tm[1, t], 0)),
                _vmem_spec((None, block, LANES),
                           lambda i, t, tm, sd: (i, tm[1, t], 0)),
            ],
            out_specs=[
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
                _vmem_spec((None, block, D),
                           lambda i, t, tm, sd: (i, tm[0, t], 0)),
            ],
            scratch_shapes=[_scratch((block, D)), _scratch((block, D))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        ],
        name="flash_tri_bwd_dkv",
        interpret=_interpret_mode(),
        **kw,
    )(tmap_kv, seed, qf, kf, vf, gf, lse, delta)

    shape = (B, H, T, D)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape), None


def _tri_eligible(causal, block_q, block_k):
    return causal and block_q == block_k


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_stream(q, k, v, seed, scale, causal, block_q, block_k,
                  dropout_rate):
    if _tri_eligible(causal, block_q, block_k):
        o, _ = _flash_fwd_tri(q, k, v, seed, scale, block_q, dropout_rate)
    else:
        o, _ = _flash_fwd_stream(q, k, v, seed, scale, causal, block_q,
                                 block_k, dropout_rate)
    return o


def _flash_stream_fwd_rule(q, k, v, seed, scale, causal, block_q, block_k,
                           dropout_rate):
    if _tri_eligible(causal, block_q, block_k):
        o, lse = _flash_fwd_tri(q, k, v, seed, scale, block_q, dropout_rate)
    else:
        o, lse = _flash_fwd_stream(q, k, v, seed, scale, causal, block_q,
                                   block_k, dropout_rate)
    return o, (q, k, v, seed, o, lse[..., 0])  # compact (BH, T) residual


def _flash_stream_bwd_rule(scale, causal, block_q, block_k, dropout_rate,
                           residuals, g):
    if _tri_eligible(causal, block_q, block_k):
        return _flash_bwd_tri(scale, block_q, dropout_rate, residuals, g)
    return _flash_bwd_stream(scale, causal, block_q, block_k, dropout_rate,
                             residuals, g)


_flash_stream.defvjp(_flash_stream_fwd_rule, _flash_stream_bwd_rule)


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------

_INTERPRET = False
_ROUTE_LOGGED = None


def _interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted. Only ever because
    someone asked (``set_interpret(True)``: tests/conftest.py, bench.py
    --platform cpu) — never because of the backend. A kernel reached
    on a backend that cannot lower it fails at compile, loudly. The
    route is logged once per process (and again if it flips)."""
    global _ROUTE_LOGGED
    if _ROUTE_LOGGED != _INTERPRET:
        _ROUTE_LOGGED = _INTERPRET
        log.info("pallas kernels: %s", "INTERPRET mode (set_interpret)"
                 if _INTERPRET else "compiled (Mosaic)")
    return _INTERPRET


def set_interpret(flag: bool) -> None:
    """Run every Pallas kernel in interpreter mode (CPU testing)."""
    global _INTERPRET
    _INTERPRET = flag


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, seed, scale, causal, block_q, block_k, dropout_rate):
    o, _ = _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k,
                      dropout_rate)
    return o


def _flash_fwd_rule(q, k, v, seed, scale, causal, block_q, block_k,
                    dropout_rate):
    o, lse = _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k,
                        dropout_rate)
    # keep the residual compact: the kernel emits lse LANES-broadcast
    # ((BH,T,LANES), a Mosaic tiling requirement), but storing that per
    # layer until the backward pass wastes 128x the HBM — save (BH, T)
    # and rebroadcast in _flash_bwd
    return o, (q, k, v, seed, o, lse[..., 0])


def _flash_bwd_rule(scale, causal, block_q, block_k, dropout_rate,
                    residuals, g):
    return _flash_bwd(scale, causal, block_q, block_k, dropout_rate,
                      residuals, g)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _auto_block(T: int) -> int:
    """Largest tile size in {512, 256, 128} dividing T. 512x512 tiles
    measured 18.2 TF/s fwd+bwd vs 7.9 at 128x128 on v5e (T=1024, D=64) —
    bigger tiles amortize the kv fori_loop and feed the MXU longer
    contractions; past 512 returns flatten (1024 measured 17.5)."""
    for b in (512, 256, 128):
        if T % b == 0:
            return b
    return BLOCK


# ---------------------------------------------------------------------------
# chunk attention: (o, lse) with global-position offsets — the ring
# attention hop core (parallel/ring_attention.py)
#
# One (q-chunk, kv-chunk) block attention where q holds global positions
# [q_offset, q_offset+Tq) and k/v [k_offset, k_offset+Tk). Returns the
# chunk-local softmax output AND its logsumexp, both differentiable, so
# callers can merge chunks with the online-softmax recurrence in plain
# JAX (the VJP of the merge needs d(lse), hence the custom rule below).
#
# Backward trick: for o = softmax(s) @ v and lse = logsumexp(s),
# upstream (do, dlse) gives ds = p * (dot(do, v) - delta + dlse) with
# delta = rowsum(do * o) — i.e. exactly the standard flash backward with
# delta replaced by (delta - dlse). The existing dq/dkv kernels are
# reused unmodified with that substitution.
# ---------------------------------------------------------------------------


def _flash_prologue(D, scale, dropout_rate, dropout_rng):
    """Shared entry prologue: head-dim scale default, dropout validation,
    and the in-kernel seed derivation — one source of truth for every
    flash entry point (single-chip attention and the ring chunk op)."""
    if scale is None:
        scale = D ** -0.5
    rate = float(dropout_rate)
    if rate > 0.0 and dropout_rng is None:
        raise ValueError("dropout_rate > 0 requires dropout_rng")
    if dropout_rng is not None and rate > 0.0:
        seed = jax.random.randint(dropout_rng, (1,), 0, 2**31 - 1,
                                  dtype=jnp.int32)
    else:
        rate = 0.0
        seed = jnp.zeros((1,), jnp.int32)
    return float(scale), rate, seed


def _block_for(T, override):
    b = min(override if override is not None else _auto_block(T), T)
    assert T % b == 0, (T, b)
    return b


def pallas_flash_chunk(q, k, v, *, scale=None, causal=True,
                       q_offset=0, k_offset=0,
                       block_q=None, block_k=None,
                       dropout_rate: float = 0.0,
                       dropout_rng=None, bh_offset=0):
    """Chunk attention with stats: returns (o, lse).

    q: (B, H, Tq, D); k, v: (B, H, Tk, D). Causal masking compares
    global positions (q_offset + row) >= (k_offset + col); the offsets
    may be Python ints or traced int32 scalars (e.g. derived from
    ``jax.lax.axis_index`` in a ring), so one compiled kernel serves
    every hop. lse is (B, H, Tq) float32 (logsumexp over this chunk's
    keys only; -inf rows are possible when causal masks an entire row —
    callers merging chunks handle that in the recurrence).
    Differentiable in q, k, v including through lse. ``bh_offset``
    decorrelates the in-kernel dropout stream when the (batch, head)
    dims are themselves shards of a larger array.
    """
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale, rate, seed = _flash_prologue(D, scale, dropout_rate, dropout_rng)
    block_q = _block_for(Tq, block_q)
    block_k = _block_for(Tk, block_k)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32),
                      jnp.asarray(bh_offset, jnp.int32)])
    o, lse = _flash_chunk(q, k, v, seed, offs, scale, bool(causal),
                          block_q, block_k, rate)
    return o, lse


def _chunk_fwd_kernel(seed_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
                      lse_ref, *, scale, causal, seq_len_k, block_q,
                      block_k, dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[...]
    D = q.shape[-1]
    q_first = off_ref[0] + j * block_q
    n_kv = seq_len_k // block_k
    if causal:
        # skip fully-masked kv tiles: tile kb contributes iff its first
        # key position <= this q block's last position (dynamic bound —
        # the offsets live in SMEM). Negative/zero bounds make the loop
        # a no-op (fully masked hop; lse stays -inf).
        n_kv = jnp.clip(
            (q_first + block_q - 1 - off_ref[1]) // block_k + 1, 0, n_kv)

    def body(kb, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        return _fwd_tile(q, k, v, acc, m, l, scale=scale, causal=causal,
                         q_first=q_first,
                         k_first=off_ref[1] + kb * block_k,
                         block_q=block_q, block_k=block_k,
                         seed=seed_ref[0], bh=off_ref[2] + i,
                         dropout_rate=dropout_rate)

    acc = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_kv, body, (acc, m0, l0))
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)),
                    NEG_INF)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(lse, (block_q, LANES))


def _chunk_bwd_dq_kernel(seed_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
                         lse_ref, deltap_ref, dq_ref, *, scale, causal,
                         seq_len_k, block_q, block_k, dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[...][:, :1]
    deltap = deltap_ref[...][:, :1]
    q_first = off_ref[0] + j * block_q
    n_kv = seq_len_k // block_k
    if causal:
        n_kv = jnp.clip(
            (q_first + block_q - 1 - off_ref[1]) // block_k + 1, 0, n_kv)

    def body(kb, dq):
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        return dq + _dq_tile(q, k, v, do, lse, deltap, scale=scale,
                             causal=causal, q_first=q_first,
                             k_first=off_ref[1] + kb * block_k,
                             block_q=block_q, block_k=block_k,
                             seed=seed_ref[0], bh=off_ref[2] + i,
                             dropout_rate=dropout_rate)

    dq_ref[...] = jax.lax.fori_loop(
        0, n_kv, body, jnp.zeros(q.shape, jnp.float32)).astype(dq_ref.dtype)


def _chunk_bwd_dkv_kernel(seed_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, deltap_ref, dk_ref, dv_ref, *, scale,
                          causal, seq_len_q, block_q, block_k,
                          dropout_rate):
    i = pl.program_id(0)
    kb = pl.program_id(1)
    k = k_ref[...]
    v = v_ref[...]
    k_first = off_ref[1] + kb * block_k
    n_q = seq_len_q // block_q
    if causal:
        # first q tile whose last row can see this kv tile's first key
        jb0 = jnp.clip((k_first - off_ref[0]) // block_q, 0, n_q)
    else:
        jb0 = 0

    def body(jb, carry):
        dk, dv = carry
        q = q_ref[pl.ds(jb * block_q, block_q), :]
        do = do_ref[pl.ds(jb * block_q, block_q), :]
        lse = lse_ref[pl.ds(jb * block_q, block_q), :][:, :1]
        deltap = deltap_ref[pl.ds(jb * block_q, block_q), :][:, :1]
        dk_c, dv_c, _ = _dkv_tile(q, k, v, do, lse, deltap, scale=scale,
                               causal=causal,
                               q_first=off_ref[0] + jb * block_q,
                               k_first=k_first,
                               block_q=block_q, block_k=block_k,
                               seed=seed_ref[0], bh=off_ref[2] + i,
                               dropout_rate=dropout_rate)
        return dk + dk_c, dv + dv_c

    dk0 = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(jb0, n_q, body, (dk0, jnp.zeros_like(dk0)))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


# --- streamed chunk kernels: ring hops past the resident K/V bound ---------
#
# The resident chunk kernels above hold one (batch, head)'s full (Tk, D)
# K/V (fwd, dq) or (Tq, D) q-side arrays (dkv) in VMEM, so ring hops
# were bounded by STREAM_KV_BYTES per device shard — exactly the
# long-per-shard runs ring attention exists for fell back to the
# q-chunked einsum body (round-3 verdict). These variants put the
# streamed axis on the pallas grid with online state in VMEM scratch —
# the same transformation the single-chip streamed family applies to
# the resident family — while keeping the chunk op's contract: global
# positions from the SMEM offsets vector (so one compiled kernel serves
# every hop), (o, lse) outputs, -inf lse on fully-masked rows, and the
# shared tile math (bit-identical numerics incl. the dropout stream).
# Causality with dynamic offsets: tiles skip via pl.when on global
# positions; the finalize index is the clipped last contributing kv
# tile (clip to 0 makes fully-masked q rows finalize on an untouched
# accumulator -> o = 0, lse = -inf, as in the resident kernel).


def _chunk_fwd_kernel_stream(seed_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
                             lse_ref, acc_ref, m_ref, l_ref, *, scale,
                             causal, seq_len_k, block_q, block_k,
                             dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kv = seq_len_k // block_k
    q_first = off_ref[0] + j * block_q
    k_first = off_ref[1] + kb * block_k
    if causal:
        last_kb = jnp.clip((q_first + block_q - 1 - off_ref[1]) // block_k,
                           0, n_kv - 1)
        needed = k_first <= q_first + block_q - 1
    else:
        last_kb = n_kv - 1
        needed = kb >= 0

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(needed)
    def _update():
        acc, m_new, l_new = _fwd_tile(
            q_ref[...], k_ref[...], v_ref[...],
            acc_ref[...], m_ref[...][:, :1], l_ref[...][:, :1],
            scale=scale, causal=causal, q_first=q_first, k_first=k_first,
            block_q=block_q, block_k=block_k, seed=seed_ref[0],
            bh=off_ref[2] + i, dropout_rate=dropout_rate)
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == last_kb)
    def _finalize():
        m = m_ref[...][:, :1]
        l = l_ref[...][:, :1]
        lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)),
                        NEG_INF)
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _chunk_bwd_dq_kernel_stream(seed_ref, off_ref, q_ref, k_ref, v_ref,
                                do_ref, lse_ref, deltap_ref, dq_ref,
                                dq_acc_ref, *, scale, causal, seq_len_k,
                                block_q, block_k, dropout_rate):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kv = seq_len_k // block_k
    q_first = off_ref[0] + j * block_q
    k_first = off_ref[1] + kb * block_k
    if causal:
        last_kb = jnp.clip((q_first + block_q - 1 - off_ref[1]) // block_k,
                           0, n_kv - 1)
        needed = k_first <= q_first + block_q - 1
    else:
        last_kb = n_kv - 1
        needed = kb >= 0

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    @pl.when(needed)
    def _update():
        dq_acc_ref[...] = dq_acc_ref[...] + _dq_tile(
            q_ref[...], k_ref[...], v_ref[...], do_ref[...],
            lse_ref[...][:, :1], deltap_ref[...][:, :1], scale=scale,
            causal=causal, q_first=q_first, k_first=k_first,
            block_q=block_q, block_k=block_k, seed=seed_ref[0],
            bh=off_ref[2] + i, dropout_rate=dropout_rate)

    @pl.when(kb == last_kb)
    def _finalize():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _chunk_bwd_dkv_kernel_stream(seed_ref, off_ref, q_ref, k_ref, v_ref,
                                 do_ref, lse_ref, deltap_ref, dk_ref,
                                 dv_ref, dk_acc_ref, dv_acc_ref, *, scale,
                                 causal, seq_len_q, block_q, block_k,
                                 dropout_rate):
    i = pl.program_id(0)
    kb = pl.program_id(1)
    jb = pl.program_id(2)
    n_q = seq_len_q // block_q
    k_first = off_ref[1] + kb * block_k
    q_first = off_ref[0] + jb * block_q
    needed = (q_first + block_q - 1 >= k_first) if causal else jb >= 0

    @pl.when(jb == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when(needed)
    def _update():
        dk_c, dv_c, _ = _dkv_tile(
            q_ref[...], k_ref[...], v_ref[...], do_ref[...],
            lse_ref[...][:, :1], deltap_ref[...][:, :1], scale=scale,
            causal=causal, q_first=q_first, k_first=k_first,
            block_q=block_q, block_k=block_k, seed=seed_ref[0],
            bh=off_ref[2] + i, dropout_rate=dropout_rate)
        dk_acc_ref[...] = dk_acc_ref[...] + dk_c
        dv_acc_ref[...] = dv_acc_ref[...] + dv_c

    @pl.when(jb == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _chunk_streaming(Tq, Tk, D, itemsize) -> bool:
    """Route a chunk call to the streamed kernels when either side's
    resident arrays (K/V for fwd/dq, q-side for dkv) exceed the measured
    resident-compile bound."""
    return _should_stream(max(Tq, Tk), D, itemsize)


def _chunk_fwd_stream(q, k, v, seed, offs, scale, causal, block_q, block_k,
                      dropout_rate):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    BH = B * H
    qf = q.reshape(BH, Tq, D)
    kf = k.reshape(BH, Tk, D)
    vf = v.reshape(BH, Tk, D)
    kernel = functools.partial(
        _chunk_fwd_kernel_stream, scale=scale, causal=causal, seq_len_k=Tk,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    kw = {"compiler_params": _compiler_params(2, 3)}
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, Tq // block_q, Tk // block_k),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j, kb: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tq, LANES), jnp.float32),
        ],
        scratch_shapes=[_scratch((block_q, D)), _scratch((block_q, LANES)),
                        _scratch((block_q, LANES))],
        name="flash_chunk_stream_fwd",
        interpret=_interpret_mode(),
        **kw,
    )(seed, offs, qf, kf, vf)
    return o.reshape(B, H, Tq, D), lse[..., 0].reshape(B, H, Tq)


def _chunk_bwd_stream(scale, causal, block_q, block_k, dropout_rate,
                      seed, offs, qf, kf, vf, gf, lse_b, deltap,
                      BH, Tq, Tk, D, dtype):
    kw = {"compiler_params": _compiler_params(2, 3)}
    dq = pl.pallas_call(
        functools.partial(
            _chunk_bwd_dq_kernel_stream, scale=scale, causal=causal,
            seq_len_k=Tk, block_q=block_q, block_k=block_k,
            dropout_rate=dropout_rate),
        grid=(BH, Tq // block_q, Tk // block_k),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, j, kb: (i, kb, 0)),
            _vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j, kb: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j, kb: (i, j, 0)),
        ],
        out_specs=_vmem_spec((None, block_q, D), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), dtype),
        scratch_shapes=[_scratch((block_q, D))],
        name="flash_chunk_stream_bwd_dq",
        interpret=_interpret_mode(),
        **kw,
    )(seed, offs, qf, kf, vf, gf, lse_b, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(
            _chunk_bwd_dkv_kernel_stream, scale=scale, causal=causal,
            seq_len_q=Tq, block_q=block_q, block_k=block_k,
            dropout_rate=dropout_rate),
        grid=(BH, Tk // block_k, Tq // block_q),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, kb, jb: (i, jb, 0)),
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
            _vmem_spec((None, block_q, D), lambda i, kb, jb: (i, jb, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, kb, jb: (i, jb, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, kb, jb: (i, jb, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
            _vmem_spec((None, block_k, D), lambda i, kb, jb: (i, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), dtype),
        ],
        scratch_shapes=[_scratch((block_k, D)), _scratch((block_k, D))],
        name="flash_chunk_stream_bwd_dkv",
        interpret=_interpret_mode(),
        **kw,
    )(seed, offs, qf, kf, vf, gf, lse_b, deltap)
    return dq, dk, dv


def _chunk_fwd(q, k, v, seed, offs, scale, causal, block_q, block_k,
               dropout_rate):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if _chunk_streaming(Tq, Tk, D, jnp.dtype(q.dtype).itemsize):
        return _chunk_fwd_stream(q, k, v, seed, offs, scale, causal,
                                 block_q, block_k, dropout_rate)
    BH = B * H
    qf = q.reshape(BH, Tq, D)
    kf = k.reshape(BH, Tk, D)
    vf = v.reshape(BH, Tk, D)
    kernel = functools.partial(
        _chunk_fwd_kernel, scale=scale, causal=causal, seq_len_k=Tk,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, Tq // block_q),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, Tk, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, Tk, D), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tq, LANES), jnp.float32),
        ],
        name="flash_chunk_fwd",
        interpret=_interpret_mode(),
    )(seed, offs, qf, kf, vf)
    return o.reshape(B, H, Tq, D), lse[..., 0].reshape(B, H, Tq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_chunk(q, k, v, seed, offs, scale, causal, block_q, block_k,
                 dropout_rate):
    return _chunk_fwd(q, k, v, seed, offs, scale, causal, block_q, block_k,
                      dropout_rate)


def _flash_chunk_fwd_rule(q, k, v, seed, offs, scale, causal, block_q,
                          block_k, dropout_rate):
    o, lse = _chunk_fwd(q, k, v, seed, offs, scale, causal, block_q,
                        block_k, dropout_rate)
    return (o, lse), (q, k, v, seed, offs, o, lse)


def _chunk_bwd_fused_kernel(seed_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
                            lse_ref, deltap_ref, dq_ref, dk_ref, dv_ref,
                            dq_acc_ref, *, scale, causal, seq_len_q,
                            seq_len_k, block_q, block_k, dropout_rate):
    """kv-major fully-fused chunk backward (the ring-hop gradient path;
    also serves the resident multi-tile path via _fused_kv_major_bwd with
    zero offsets): dq accumulates in a (Tq, D) f32 VMEM scratch across
    the sequential grid, dk/dv write per kv block, and every tile's p/ds
    recompute (through _dkv_tile, the shared math) serves all three
    gradients. Global-position causal skip identical to
    _chunk_bwd_dkv_kernel."""
    i = pl.program_id(0)
    kb = pl.program_id(1)
    n_kv = seq_len_k // block_k
    k = k_ref[...]
    v = v_ref[...]
    k_first = off_ref[1] + kb * block_k
    n_q = seq_len_q // block_q
    if causal:
        jb0 = jnp.clip((k_first - off_ref[0]) // block_q, 0, n_q)
    else:
        jb0 = 0

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def body(jb, carry):
        dk, dv = carry
        q_first = jb * block_q
        q = q_ref[pl.ds(q_first, block_q), :]
        do = do_ref[pl.ds(q_first, block_q), :]
        lse = lse_ref[pl.ds(q_first, block_q), :][:, :1]
        deltap = deltap_ref[pl.ds(q_first, block_q), :][:, :1]
        dk_c, dv_c, dsc = _dkv_tile(q, k, v, do, lse, deltap, scale=scale,
                                    causal=causal,
                                    q_first=off_ref[0] + q_first,
                                    k_first=k_first, block_q=block_q,
                                    block_k=block_k, seed=seed_ref[0],
                                    bh=off_ref[2] + i,
                                    dropout_rate=dropout_rate)
        dq_c = jax.lax.dot_general(dsc, k, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        dq_acc_ref[pl.ds(q_first, block_q), :] = (
            dq_acc_ref[pl.ds(q_first, block_q), :] + dq_c)
        return dk + dk_c, dv + dv_c

    dk0 = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(jb0, n_q, body, (dk0, jnp.zeros_like(dk0)))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(kb == n_kv - 1)
    def _finalize():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _flash_chunk_bwd_rule(scale, causal, block_q, block_k, dropout_rate,
                          residuals, g):
    q, k, v, seed, offs, o, lse = residuals
    do, dlse = g
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    BH = B * H
    # delta' = rowsum(do * o) - dlse: folds the lse cotangent into the
    # standard flash backward (ds = p * (dp - delta'))
    deltap = (jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                      axis=-1) - dlse.astype(jnp.float32)).reshape(BH, Tq)
    # rows fully masked in this chunk have lse = -inf and p = exp(s - lse)
    # would be inf * 0; clamp lse for the recompute (p rows are all-masked
    # anyway, so any finite value yields p = exp(NEG_INF - c) = 0)
    lse_c = jnp.maximum(lse, NEG_INF / 2).reshape(BH, Tq)
    deltap = jnp.broadcast_to(deltap[:, :, None], (BH, Tq, LANES))
    lse_b = jnp.broadcast_to(lse_c[:, :, None], (BH, Tq, LANES))
    qf = q.reshape(BH, Tq, D)
    kf = k.reshape(BH, Tk, D)
    vf = v.reshape(BH, Tk, D)
    gf = do.reshape(BH, Tq, D)

    if _chunk_streaming(Tq, Tk, D, jnp.dtype(q.dtype).itemsize):
        dq, dk, dv = _chunk_bwd_stream(
            scale, causal, block_q, block_k, dropout_rate,
            seed, offs, qf, kf, vf, gf, lse_b, deltap,
            BH, Tq, Tk, D, q.dtype)
        return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
                dv.reshape(B, H, Tk, D), None, None)

    if Tq * D * 4 <= FUSED_DQ_SCRATCH_BYTES:
        # one fused kv-major launch (see _chunk_bwd_fused_kernel); the
        # split kernels below remain for long chunks
        dq, dk, dv = _fused_kv_major_bwd(
            scale, causal, block_q, block_k, dropout_rate,
            seed, offs, qf, kf, vf, gf, lse_b, deltap,
            BH, Tq, Tk, D, q.dtype)
        shape_q = (B, H, Tq, D)
        shape_k = (B, H, Tk, D)
        return (dq.reshape(shape_q), dk.reshape(shape_k),
                dv.reshape(shape_k), None, None)

    dq = pl.pallas_call(
        functools.partial(
            _chunk_bwd_dq_kernel, scale=scale, causal=causal, seq_len_k=Tk,
            block_q=block_q, block_k=block_k, dropout_rate=dropout_rate),
        grid=(BH, Tq // block_q),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, Tk, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, Tk, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_q, LANES), lambda i, j: (i, j, 0)),
        ],
        out_specs=_vmem_spec((None, block_q, D), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
        name="flash_chunk_bwd_dq",
        interpret=_interpret_mode(),
    )(seed, offs, qf, kf, vf, gf, lse_b, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(
            _chunk_bwd_dkv_kernel, scale=scale, causal=causal, seq_len_q=Tq,
            block_q=block_q, block_k=block_k, dropout_rate=dropout_rate),
        grid=(BH, Tk // block_k),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _vmem_spec((None, Tq, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, Tq, D), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, Tq, LANES), lambda i, j: (i, 0, 0)),
            _vmem_spec((None, Tq, LANES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
            _vmem_spec((None, block_k, D), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), q.dtype),
        ],
        name="flash_chunk_bwd_dkv",
        interpret=_interpret_mode(),
    )(seed, offs, qf, kf, vf, gf, lse_b, deltap)

    shape_q = (B, H, Tq, D)
    shape_k = (B, H, Tk, D)
    return (dq.reshape(shape_q), dk.reshape(shape_k), dv.reshape(shape_k),
            None, None)


_flash_chunk.defvjp(_flash_chunk_fwd_rule, _flash_chunk_bwd_rule)


# above this many K+V bytes per (batch, head), stream K/V blockwise
# instead of holding them resident in VMEM. Measured on v5e (D=64 bf16,
# fwd+bwd): resident wins while it compiles (59 ms vs tri-stream 75 at
# T=8192; 102 vs 122 at T=16384 = 4 MiB K+V) and fails Mosaic
# allocation from T=32768 (8 MiB); past the threshold the triangular
# stream carries on at 12.1 TF/s (T=32k) to 18.2 TF/s (T=64k) with
# VMEM use independent of T.
STREAM_KV_BYTES = 4 * 1024 * 1024


def _should_stream(T: int, D: int, itemsize: int) -> bool:
    return 2 * T * D * itemsize > STREAM_KV_BYTES


def pallas_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           scale: Optional[float] = None,
                           causal: bool = True,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           dropout_rate: float = 0.0,
                           dropout_rng: Optional[jax.Array] = None,
                           stream: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention. q,k,v: (B, H, T, D); T must be a multiple of the
    block sizes (callers pad or fall back to the einsum path otherwise).

    ``dropout_rate`` > 0 (with ``dropout_rng``) applies inverted dropout to
    the normalized attention weights inside the kernel — the capability the
    dense path gets from _softmax_dropout (GPT1.py:117 semantics) without
    materializing the (T, T) weight matrix. The mask derives from a
    counter-based hash of (seed, head, absolute q-pos, absolute k-pos), so
    the backward kernels — and both kernel variants — regenerate it exactly.

    ``stream`` selects the K/V-streaming grid (VMEM use independent of T;
    sequence length bounded by HBM only). None = auto by K/V footprint.
    """
    B, H, T, D = q.shape
    scale, rate, seed = _flash_prologue(D, scale, dropout_rate, dropout_rng)
    block_q = _block_for(T, block_q)
    block_k = _block_for(T, block_k)
    if stream is None:
        stream = _should_stream(T, D, jnp.dtype(q.dtype).itemsize)
    fn = _flash_stream if stream else _flash
    return fn(q, k, v, seed, scale, bool(causal), block_q,
              block_k, rate)


# ---------------------------------------------------------------------------
# packed-heads resident family: attention straight off the fused QKV
# projection, no head transposes anywhere
#
# The (B, H, T, D) layout the families above consume costs real HBM: the
# char-GPT HLO carries ~1.1 GB/step of (B,T,H,D)<->(B,H,T,D) transpose
# copies feeding/draining the kernels (benchmarks/RESULTS.md), and a
# per-head 4-d BlockSpec that would read (B,T,H,D) directly is
# Mosaic-unrepresentable (a (1, bq, 1, D) block's trailing dims neither
# divide (8, 128) nor equal the array dims). This family sidesteps the
# layout question entirely: the kernel consumes the QKV projection's own
# (B, T, 3C) output — q as columns [0, C), k [C, 2C), v [2C, 3C), heads
# as D-wide column strips — with grid (B,) and the whole (T, 3C) block
# resident in VMEM. Heads are a static in-kernel loop over lane slices;
# per-head tile math is byte-identical to the unpacked kernels
# (_fwd_tile/_dkv_tile with bh = b * H + h), so dropout masks and
# numerics match the unpacked family bit-for-bit.
#
# The backward emits d(qkv) as one packed (B, T, 3C) array — dq columns
# from a (T, C) f32 VMEM scratch accumulated kv-major (one p/ds
# recompute per tile serves dq, dk and dv, as in the fused kv-major
# kernel above), dk/dv written per kv-row-block — so the gradient flows
# straight into the projection matmul's VJP with no split/concat/
# transpose on either side of either pass.
#
# Residency bound: the whole (T, 3C) block (plus do/dqkv/scratch in the
# backward) must fit VMEM (~16 MB/core), so this family owns the
# short-T/many-head regime (char-GPT: T=256, C=384 -> 0.6 MB) and the
# general (B, H, T, D) families keep everything past PACKED_QKV_BYTES.
# ---------------------------------------------------------------------------

# (T, 3C) itemsize bound for the packed family. The backward's VMEM
# footprint per program is qkv + do + dqkv + (T, C) f32 scratch
# ~= 2.8x the qkv block (bf16), double-buffered across batch programs;
# 2 MiB keeps the worst case ~11 MiB under the ~16 MiB/core budget.
PACKED_QKV_BYTES = 2 * 1024 * 1024


def packed_supported(T: int, C: int, n_head: int, itemsize: int) -> bool:
    """Envelope for the packed-heads family: head strips must be
    lane-sliceable D in {32, 64, 128, 256}, T tileable, and the whole
    (T, 3C) block resident (see PACKED_QKV_BYTES)."""
    if C % n_head != 0:
        return False
    D = C // n_head
    return (D in (32, 64, 128, 256) and T >= 128 and T % 128 == 0
            and T * 3 * C * itemsize <= PACKED_QKV_BYTES)


def _fwd_kernel_packed(seed_ref, qkv_ref, o_ref, lse_ref, *, scale, causal,
                       n_head, head_dim, seq_len, block_q, block_k,
                       dropout_rate):
    b = pl.program_id(0)
    H, D, C = n_head, head_dim, n_head * head_dim
    n_q = seq_len // block_q
    n_kv_total = seq_len // block_k
    for jb in range(n_q):
        q_first = jb * block_q
        rows = slice(jb * block_q, (jb + 1) * block_q)
        if causal:
            n_kv = min((q_first + block_q + block_k - 1) // block_k,
                       n_kv_total)
        else:
            n_kv = n_kv_total
        outs = []
        lses = []
        for h in range(H):
            q = qkv_ref[rows, h * D:(h + 1) * D]
            acc = jnp.zeros((block_q, D), jnp.float32)
            m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
            l = jnp.zeros((block_q, 1), jnp.float32)
            for kb in range(n_kv):
                krows = slice(kb * block_k, (kb + 1) * block_k)
                k = qkv_ref[krows, C + h * D:C + (h + 1) * D]
                v = qkv_ref[krows, 2 * C + h * D:2 * C + (h + 1) * D]
                acc, m, l = _fwd_tile(
                    q, k, v, acc, m, l, scale=scale, causal=causal,
                    q_first=q_first, k_first=kb * block_k,
                    block_q=block_q, block_k=block_k, seed=seed_ref[0],
                    bh=b * H + h, dropout_rate=dropout_rate)
            l = jnp.maximum(l, 1e-30)
            outs.append((acc / l).astype(o_ref.dtype))
            lses.append(m + jnp.log(l))
        o_ref[rows, :] = jnp.concatenate(outs, axis=1)
        lse_ref[rows, :] = jnp.concatenate(lses, axis=1)


def _packed_fwd(qkv, seed, scale, causal, n_head, block_q, block_k,
                dropout_rate):
    B, T, C3 = qkv.shape
    C = C3 // 3
    D = C // n_head
    kernel = functools.partial(
        _fwd_kernel_packed, scale=scale, causal=causal, n_head=n_head,
        head_dim=D, seq_len=T, block_q=block_q, block_k=block_k,
        dropout_rate=dropout_rate)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            _smem_spec(),
            _vmem_spec((None, T, C3), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((None, T, C), lambda b: (b, 0, 0)),
            _vmem_spec((None, T, n_head), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, C), qkv.dtype),
            jax.ShapeDtypeStruct((B, T, n_head), jnp.float32),
        ],
        name="flash_packed_fwd",
        interpret=_interpret_mode(),
    )(seed, qkv)
    return o, lse


def _bwd_kernel_packed(seed_ref, qkv_ref, do_ref, lse_ref, delta_ref,
                       dqkv_ref, dq_scratch, *, scale, causal, n_head,
                       head_dim, seq_len, block_q, block_k, dropout_rate):
    """kv-major fully-fused packed backward: one p/ds recompute per
    (head, q-block, kv-block) tile serves dq (into the (T, C) f32
    scratch), dk and dv (register accumulators over q-blocks, written
    per kv-row-block). Loops are static Python — the residency bound
    keeps n_q * n_kv * H small — so accumulators live in registers."""
    b = pl.program_id(0)
    H, D, C = n_head, head_dim, n_head * head_dim
    n_q = seq_len // block_q
    n_kv = seq_len // block_k
    dq_scratch[...] = jnp.zeros((seq_len, C), jnp.float32)
    for kb in range(n_kv):
        k_first = kb * block_k
        krows = slice(kb * block_k, (kb + 1) * block_k)
        dks = []
        dvs = []
        for h in range(H):
            k = qkv_ref[krows, C + h * D:C + (h + 1) * D]
            v = qkv_ref[krows, 2 * C + h * D:2 * C + (h + 1) * D]
            dk_acc = jnp.zeros((block_k, D), jnp.float32)
            dv_acc = jnp.zeros((block_k, D), jnp.float32)
            jb0 = (k_first // block_q) if causal else 0
            for jb in range(jb0, n_q):
                rows = slice(jb * block_q, (jb + 1) * block_q)
                q = qkv_ref[rows, h * D:(h + 1) * D]
                do = do_ref[rows, h * D:(h + 1) * D]
                lse = lse_ref[rows, h:h + 1]
                delta = delta_ref[rows, h:h + 1]
                dk_c, dv_c, dsc = _dkv_tile(
                    q, k, v, do, lse, delta, scale=scale, causal=causal,
                    q_first=jb * block_q, k_first=k_first,
                    block_q=block_q, block_k=block_k, seed=seed_ref[0],
                    bh=b * H + h, dropout_rate=dropout_rate)
                dk_acc += dk_c
                dv_acc += dv_c
                dq_c = jax.lax.dot_general(
                    dsc, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dq_scratch[rows, h * D:(h + 1) * D] += dq_c
            dks.append(dk_acc.astype(dqkv_ref.dtype))
            dvs.append(dv_acc.astype(dqkv_ref.dtype))
        dqkv_ref[krows, C:2 * C] = jnp.concatenate(dks, axis=1)
        dqkv_ref[krows, 2 * C:3 * C] = jnp.concatenate(dvs, axis=1)
    dqkv_ref[:, 0:C] = dq_scratch[...].astype(dqkv_ref.dtype)


def _packed_bwd(qkv, do, lse, delta, seed, scale, causal, n_head, block_q,
                block_k, dropout_rate):
    B, T, C3 = qkv.shape
    C = C3 // 3
    D = C // n_head
    kernel = functools.partial(
        _bwd_kernel_packed, scale=scale, causal=causal, n_head=n_head,
        head_dim=D, seq_len=T, block_q=block_q, block_k=block_k,
        dropout_rate=dropout_rate)
    spec_full = lambda w: _vmem_spec((None, T, w), lambda b: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[_smem_spec(), spec_full(C3), spec_full(C),
                  spec_full(n_head), spec_full(n_head)],
        out_specs=spec_full(C3),
        out_shape=jax.ShapeDtypeStruct((B, T, C3), qkv.dtype),
        scratch_shapes=[_scratch((T, C))],
        name="flash_packed_bwd",
        interpret=_interpret_mode(),
    )(seed, qkv, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _flash_packed(qkv, seed, scale, causal, n_head, block_q, block_k,
                  dropout_rate):
    o, _ = _packed_fwd(qkv, seed, scale, causal, n_head, block_q, block_k,
                       dropout_rate)
    return o


def _flash_packed_fwd_rule(qkv, seed, scale, causal, n_head, block_q,
                           block_k, dropout_rate):
    o, lse = _packed_fwd(qkv, seed, scale, causal, n_head, block_q,
                         block_k, dropout_rate)
    return o, (qkv, seed, o, lse)


def _flash_packed_bwd_rule(scale, causal, n_head, block_q, block_k,
                           dropout_rate, residuals, g):
    qkv, seed, o, lse = residuals
    B, T, C = o.shape
    D = C // n_head
    # delta = rowsum(do * o) per head — a minor-dim split + reduce on the
    # packed layout, no transposes (dropout's mask is already inside o,
    # matching the unpacked families' delta semantics)
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
        B, T, n_head, D).sum(-1)
    dqkv = _packed_bwd(qkv, g.astype(qkv.dtype), lse, delta, seed, scale,
                       causal, n_head, block_q, block_k, dropout_rate)
    return dqkv, None


_flash_packed.defvjp(_flash_packed_fwd_rule, _flash_packed_bwd_rule)


# ---------------------------------------------------------------------------
# packed head-group family: the packed layout past the full-residency bound
#
# The resident packed family above needs the whole (T, 3C) block (plus
# do/dqkv/scratch in the backward, ~2.8x) in VMEM, which caps it at
# PACKED_QKV_BYTES — char-GPT fits (0.6 MB), GPT-2 124M (T=1024, C=768:
# 4.7 MB, backward ~14 MB) does not; Mosaic refuses the allocation
# (benchmarks/RESULTS.md round-3 "measured and rejected" row). This
# family keeps the no-transpose property but shrinks residency from
# O(T*3C) to O(T*W) by splitting heads into lane-aligned GROUPS: a group
# is hpg = max(1, 128 // D) adjacent heads, W = hpg*D in {128, 256}
# columns wide, so the group's q/k/v strips are addressable as last-dim
# BlockSpec blocks of the untouched (B, T, 3C) array (block width W is
# lane-aligned where a bare D=64 head strip is Mosaic-unrepresentable).
# Grid carries (batch, group): each program sees only its (T, W) strips
# — 124M: 256 KB vs the 4.7 MB full block — and loops its hpg sub-heads
# as static in-kernel lane slices, exactly like the resident family
# loops all H. The head->HBM gather that the (B,H,T,D) families pay as
# separate transpose ops happens inside the kernel's double-buffered
# block fetches instead.
#
# Forward grid is (B, G, n_q) with K/V strip index maps independent of
# the q axis (fetched once per (b, g), pipelined across q blocks);
# online-softmax state lives in registers within one grid step — no
# cross-step carry, no scratch state. Backward is the fused kv-major
# form of the resident packed backward on one (b, g) per program: one
# p/ds recompute per (sub-head, q-block, kv-block) serves dq (a (T, W)
# f32 VMEM scratch), dk and dv (register accumulators, written per
# kv-row-block). dq/dk/dv emerge as three (B, T, C) arrays whose
# concatenation is the packed d(qkv) — one contiguous copy, no
# transposes.
#
# Per-head tile math and the dropout counter stream key off
# bh = b*H + (g*hpg + s), identical to every other family, so outputs
# are bit-identical to the unpacked and resident-packed kernels.
#
# LSE layout: narrow (B, G, T, hpg) f32 — one column per sub-head, the
# same equal-to-array-dim trailing block the resident family's (T, H)
# lse output uses. The first cut of this family carried stats
# strip-broadcast (B, G, T, W); at B=64 the extra ~600 MB/layer of lse +
# delta temps pushed the 124M k-step scan 2.7 GB past HBM (measured OOM,
# 18.46/15.75 GB) — narrow stats fit it back (and are 128x less traffic
# than the unpacked families' (B*H, T, LANES) broadcasts).
# ---------------------------------------------------------------------------

# (T, W) strip-residency bound. Backward VMEM per program: q/k/v/do
# strips (4S bf16, S = T*W*itemsize), dq/dk/dv outs (3S), (T, W) f32
# dq scratch (2S), narrow (T, hpg) f32 lse/delta (negligible, ~S/16) —
# ~9S, roughly doubled by block double-buffering; 512 KiB keeps the
# worst case ~9 MiB under the ~16 MiB/core budget with headroom for
# Mosaic's own temporaries. W=128 bf16 -> T <= 2048.
GROUP_STRIP_BYTES = 512 * 1024


def _group_geometry(C: int, n_head: int):
    """(D, heads_per_group, W, n_groups) for the head-group family, or
    None when heads cannot form lane-aligned groups."""
    if C % n_head != 0:
        return None
    D = C // n_head
    if D not in (32, 64, 128, 256):
        return None
    hpg = max(1, 128 // D)
    if n_head % hpg != 0:
        return None
    return D, hpg, hpg * D, n_head // hpg


def packed_group_supported(T: int, C: int, n_head: int,
                           itemsize: int) -> bool:
    """Envelope for the head-group packed family (see GROUP_STRIP_BYTES)."""
    geo = _group_geometry(C, n_head)
    return (geo is not None and T >= 128 and T % 128 == 0
            and T * geo[2] * itemsize <= GROUP_STRIP_BYTES)


def _fwd_kernel_group(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      scale, causal, n_head, head_dim, heads_per_group,
                      seq_len, block_q, block_k, dropout_rate):
    b = pl.program_id(0)
    g = pl.program_id(1)
    jb = pl.program_id(2)
    D = head_dim
    q_first = jb * block_q
    # jb is a grid index (traced), so the causal kv bound is a traced
    # fori_loop bound with pl.ds row slices, as in _fwd_kernel
    if causal:
        n_kv = (q_first + block_q + block_k - 1) // block_k
    else:
        n_kv = seq_len // block_k
    lses = []
    for s in range(heads_per_group):
        cols = slice(s * D, (s + 1) * D)
        q = q_ref[:, cols]
        bh = b * n_head + g * heads_per_group + s

        def body(kb, carry, q=q, bh=bh, cols=cols):
            acc, m, l = carry
            k = k_ref[pl.ds(kb * block_k, block_k), cols]
            v = v_ref[pl.ds(kb * block_k, block_k), cols]
            return _fwd_tile(q, k, v, acc, m, l, scale=scale,
                             causal=causal, q_first=q_first,
                             k_first=kb * block_k, block_q=block_q,
                             block_k=block_k, seed=seed_ref[0], bh=bh,
                             dropout_rate=dropout_rate)

        acc = jnp.zeros((block_q, D), jnp.float32)
        m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, n_kv, body, (acc, m0, l0))
        l = jnp.maximum(l, 1e-30)
        o_ref[:, cols] = (acc / l).astype(o_ref.dtype)
        lses.append(m + jnp.log(l))
    lse_ref[...] = jnp.concatenate(lses, axis=1)


def _group_fwd(qkv, seed, scale, causal, n_head, block_q, block_k,
               dropout_rate):
    B, T, C3 = qkv.shape
    C = C3 // 3
    D, hpg, W, G = _group_geometry(C, n_head)
    kernel = functools.partial(
        _fwd_kernel_group, scale=scale, causal=causal, n_head=n_head,
        head_dim=D, heads_per_group=hpg, seq_len=T, block_q=block_q,
        block_k=block_k, dropout_rate=dropout_rate)
    kw = {"compiler_params": _compiler_params(3, 3)}
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, G, T // block_q),
        in_specs=[
            _smem_spec(),
            # three W-wide last-dim-blocked views of the one (B, T, 3C)
            # array: q strip g, k strip G + g, v strip 2G + g. K/V maps
            # ignore the q axis, so their fetches amortize across it.
            _vmem_spec((None, block_q, W), lambda b, g, j: (b, j, g)),
            _vmem_spec((None, T, W), lambda b, g, j: (b, 0, G + g)),
            _vmem_spec((None, T, W), lambda b, g, j: (b, 0, 2 * G + g)),
        ],
        out_specs=[
            _vmem_spec((None, block_q, W), lambda b, g, j: (b, j, g)),
            _vmem_spec((None, None, block_q, hpg),
                       lambda b, g, j: (b, g, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, C), qkv.dtype),
            jax.ShapeDtypeStruct((B, G, T, hpg), jnp.float32),
        ],
        name="flash_group_fwd",
        interpret=_interpret_mode(),
        **kw,
    )(seed, qkv, qkv, qkv)
    # (B, G, T, hpg) -> (B, H, T) for the residual
    lse_c = lse.transpose(0, 1, 3, 2).reshape(B, n_head, T)
    return o, lse_c


def _group_stats(x, hpg):
    """(B, H, T) per-head rows -> the (B, G, T, hpg) column-per-sub-head
    layout the group kernels read."""
    B, H, T = x.shape
    return x.reshape(B, H // hpg, hpg, T).transpose(0, 1, 3, 2)


def _bwd_kernel_group(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, dq_scratch, *,
                      scale, causal, n_head, head_dim, heads_per_group,
                      seq_len, block_q, block_k, dropout_rate):
    b = pl.program_id(0)
    g = pl.program_id(1)
    D, hpg = head_dim, heads_per_group
    W = hpg * D
    n_q = seq_len // block_q
    n_kv = seq_len // block_k
    dq_scratch[...] = jnp.zeros((seq_len, W), jnp.float32)
    for kb in range(n_kv):
        k_first = kb * block_k
        krows = slice(kb * block_k, (kb + 1) * block_k)
        for s in range(hpg):
            cols = slice(s * D, (s + 1) * D)
            k = k_ref[krows, cols]
            v = v_ref[krows, cols]
            dk_acc = jnp.zeros((block_k, D), jnp.float32)
            dv_acc = jnp.zeros((block_k, D), jnp.float32)
            bh = b * n_head + g * hpg + s
            jb0 = (k_first // block_q) if causal else 0
            for jb in range(jb0, n_q):
                rows = slice(jb * block_q, (jb + 1) * block_q)
                dk_c, dv_c, dsc = _dkv_tile(
                    q_ref[rows, cols], k, v, do_ref[rows, cols],
                    lse_ref[rows, s:s + 1],
                    delta_ref[rows, s:s + 1], scale=scale,
                    causal=causal, q_first=jb * block_q, k_first=k_first,
                    block_q=block_q, block_k=block_k, seed=seed_ref[0],
                    bh=bh, dropout_rate=dropout_rate)
                dk_acc += dk_c
                dv_acc += dv_c
                dq_c = jax.lax.dot_general(
                    dsc, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dq_scratch[rows, cols] += dq_c
            dk_ref[krows, cols] = dk_acc.astype(dk_ref.dtype)
            dv_ref[krows, cols] = dv_acc.astype(dv_ref.dtype)
    dq_ref[...] = dq_scratch[...].astype(dq_ref.dtype)


def _group_bwd(qkv, do, lse_c, delta_c, seed, scale, causal, n_head,
               block_q, block_k, dropout_rate):
    B, T, C3 = qkv.shape
    C = C3 // 3
    D, hpg, W, G = _group_geometry(C, n_head)
    lse4 = _group_stats(lse_c, hpg)
    delta4 = _group_stats(delta_c, hpg)
    kernel = functools.partial(
        _bwd_kernel_group, scale=scale, causal=causal, n_head=n_head,
        head_dim=D, heads_per_group=hpg, seq_len=T, block_q=block_q,
        block_k=block_k, dropout_rate=dropout_rate)
    kw = {"compiler_params": _compiler_params(2, 2)}
    strip = lambda blk: _vmem_spec((None, T, W), lambda b, g: (b, 0, blk(g)))
    stat = _vmem_spec((None, None, T, hpg), lambda b, g: (b, g, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B, G),
        in_specs=[_smem_spec(),
                  strip(lambda g: g), strip(lambda g: G + g),
                  strip(lambda g: 2 * G + g), strip(lambda g: g),
                  stat, stat],
        out_specs=[strip(lambda g: g)] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, T, C), qkv.dtype)] * 3,
        scratch_shapes=[_scratch((T, W))],
        name="flash_group_bwd",
        interpret=_interpret_mode(),
        **kw,
    )(seed, qkv, qkv, qkv, do, lse4, delta4)
    return jnp.concatenate([dq, dk, dv], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _flash_packed_group(qkv, seed, scale, causal, n_head, block_q, block_k,
                        dropout_rate):
    o, _ = _group_fwd(qkv, seed, scale, causal, n_head, block_q, block_k,
                      dropout_rate)
    return o


def _flash_packed_group_fwd_rule(qkv, seed, scale, causal, n_head, block_q,
                                 block_k, dropout_rate):
    o, lse_c = _group_fwd(qkv, seed, scale, causal, n_head, block_q,
                          block_k, dropout_rate)
    return o, (qkv, seed, o, lse_c)


def _flash_packed_group_bwd_rule(scale, causal, n_head, block_q, block_k,
                                 dropout_rate, residuals, g):
    qkv, seed, o, lse_c = residuals
    B, T, C = o.shape
    D = C // n_head
    # delta = rowsum(do * o) per head, straight off the packed layout
    delta_c = (g.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
        B, T, n_head, D).sum(-1).transpose(0, 2, 1)
    dqkv = _group_bwd(qkv, g.astype(qkv.dtype), lse_c, delta_c, seed,
                      scale, causal, n_head, block_q, block_k, dropout_rate)
    return dqkv, None


_flash_packed_group.defvjp(_flash_packed_group_fwd_rule,
                           _flash_packed_group_bwd_rule)


def pallas_flash_attention_packed(qkv: jnp.ndarray, n_head: int, *,
                                  scale: Optional[float] = None,
                                  causal: bool = True,
                                  block_q: Optional[int] = None,
                                  block_k: Optional[int] = None,
                                  dropout_rate: float = 0.0,
                                  dropout_rng: Optional[jax.Array] = None
                                  ) -> jnp.ndarray:
    """Packed-heads flash attention. qkv: (B, T, 3C) — the fused QKV
    projection output, untouched. Returns the merged (B, T, C) attention
    output, ready for the output projection. Numerics (including the
    in-kernel dropout stream) are bit-identical to
    ``pallas_flash_attention`` on the same logical q/k/v.

    Routes by residency: the fully-resident family while (T, 3C) fits
    PACKED_QKV_BYTES (short-T/many-head, e.g. char-GPT), then the
    head-group family while (T, W) strips fit GROUP_STRIP_BYTES
    (GPT-2-scale T=1024). Past both, callers take the split-heads
    families (``pallas_flash_attention``)."""
    B, T, C3 = qkv.shape
    C = C3 // 3
    D = C // n_head
    scale, rate, seed = _flash_prologue(D, scale, dropout_rate, dropout_rng)
    block_q = _block_for(T, block_q)
    block_k = _block_for(T, block_k)
    itemsize = jnp.dtype(qkv.dtype).itemsize
    if packed_supported(T, C, n_head, itemsize):
        fn = _flash_packed
    elif packed_group_supported(T, C, n_head, itemsize):
        fn = _flash_packed_group
    else:
        raise ValueError(
            f"packed families do not support T={T}, C={C}, "
            f"n_head={n_head}; gate callers on "
            "ops.flash_attention.packed_envelope_ok")
    return fn(qkv, seed, scale, bool(causal), n_head, block_q, block_k, rate)
