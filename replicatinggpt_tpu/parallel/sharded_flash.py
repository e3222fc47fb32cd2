"""Batch/head-parallel flash attention for meshes without a 'seq' axis.

The Pallas flash kernel (ops/flash_pallas.py) has no GSPMD partitioning
rule, so a jit-sharded program cannot call ``pallas_call`` directly — the
compiler would have to either replicate the kernel (wrong numbers) or fail
to lower. Sequence-parallel runs already solve this with explicit shard_map
regions (parallel/ring_attention.py, parallel/ulysses.py); this module is
the same move for the remaining — and most common — mesh shapes: pure DP,
FSDP, and TP, where attention is embarrassingly parallel per device
(batch sharded over 'data', heads over 'model', full sequence local).

The body runs the ordinary local attention core: the Pallas flash kernel
on TPU (the whole point — BASELINE configs 3/4 train at T=1024 where flash
is worth tens of percent, benchmarks/RESULTS.md), XLA SDPA / einsum
elsewhere. Attention-weight dropout decorrelates per (data, model) shard
by folding the device indices into the rng, mirroring the Ulysses wrapper.

The reference never loses its flash path on its device
(/root/reference/GPT-2.py:46); with this wrapper, neither do mesh runs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import full_causal_attention
from ..ops.flash_attention import FLASH_MIN_T


def _local_attention(q, k, v, key=None, *, scale: Optional[float],
                     dropout_rate: float, impl: str, batch_axis, head_axis):
    """Per-device body: plain causal attention over the local
    (B/data, H/model, T, D) shard — no collectives; causality is exact
    because the full sequence is local. The rng folds in only the mesh
    axes that actually partition the block (devices along an unused axis
    compute identical replicated outputs and must stay bit-identical)."""
    if impl == "auto":
        impl = "flash" if q.shape[2] >= FLASH_MIN_T else "einsum"
    if key is not None:
        shard = jax.lax.axis_index(batch_axis) if batch_axis else 0
        if head_axis:
            shard = (shard * axis_size(head_axis)
                     + jax.lax.axis_index(head_axis))
        key = jax.random.fold_in(key, shard)
    return full_causal_attention(q, k, v, scale=scale, impl=impl,
                                 dropout_rate=dropout_rate, rng=key,
                                 train=key is not None)


def sharded_flash_attention(q, k, v, *, mesh: Mesh,
                            scale: Optional[float] = None,
                            impl: str = "auto",
                            dropout_rate: float = 0.0,
                            rng: Optional[jax.Array] = None,
                            train: bool = False):
    """Causal attention on a mesh whose 'seq' axis is 1.

    q, k, v: global (B, H, T, D) with B sharded over 'data' and H over
    'model' (the layout GSPMD produces from the batch sharding and the
    Megatron column-parallel qkv projection, parallel/mesh.py). Same
    attention_fn contract as the ring/Ulysses wrappers, including
    in-core attention-weight dropout.

    Self-guarding on shard_map's even-division requirement: an axis whose
    size does not divide the corresponding dim drops out of the specs
    (the body then sees that dim whole, at the cost of a gather), and if
    neither axis divides, the call falls back to the plain GSPMD einsum
    core — the envelope the wrapper replaced.
    """
    data_n = mesh.shape.get("data", 1)
    model_n = mesh.shape.get("model", 1)
    batch_axis = "data" if (data_n > 1 and q.shape[0] % data_n == 0) else None
    head_axis = "model" if (model_n > 1 and q.shape[1] % model_n == 0) \
        else None
    dropped = ((data_n > 1 and batch_axis is None)
               or (model_n > 1 and head_axis is None))
    if dropped and impl != "flash":
        # 'auto' must not degrade to replicated compute: dropping an
        # indivisible axis from the specs makes every device along it
        # gather and redundantly compute that whole dimension's
        # attention — strictly worse than the GSPMD einsum this wrapper
        # replaced. Only an explicit 'flash' (the user opting into the
        # memory-efficient kernel at any cost) pays the gather below.
        return full_causal_attention(q, k, v, scale=scale, impl="einsum",
                                     dropout_rate=dropout_rate, rng=rng,
                                     train=train)
    # Reaching here with both axes dropped means explicit 'flash' on a
    # mesh where nothing divides: the specs below are fully replicated,
    # every device computes the whole batch's attention redundantly —
    # wasteful, but memory-efficient and what the user asked for (dense
    # einsum at the long T that motivates 'flash' would materialize the
    # O(T^2) weights instead). Runtime-signal the N-fold redundancy once.
    if dropped:
        import warnings
        parts = []
        if data_n > 1 and batch_axis is None:
            parts.append(f"batch (B={q.shape[0]} vs data={data_n})")
        if model_n > 1 and head_axis is None:
            parts.append(f"heads (H={q.shape[1]} vs model={model_n})")
        warnings.warn(
            f"sharded flash attention: {' and '.join(parts)} do(es) not "
            "divide the mesh axis, so that dimension is replicated — "
            "every device along the dropped axis redundantly computes it "
            "(explicit impl='flash' opts into this for the "
            "memory-efficient kernel). Pad the dimension to a multiple "
            "of the mesh axis to shard the compute.",
            stacklevel=2)
    spec = P(batch_axis, head_axis, None, None)
    local = functools.partial(_local_attention, scale=scale,
                              dropout_rate=dropout_rate, impl=impl,
                              batch_axis=batch_axis, head_axis=head_axis)
    if not (train and dropout_rate > 0.0 and rng is not None):
        fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        return fn(q, k, v)
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec, P()),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v, rng)


def _local_packed(qkv, key=None, *, n_head, scale: Optional[float],
                  dropout_rate: float):
    """Per-device body of the packed-qkv fast path: the packed-heads
    kernel on this device's batch shard, dropout stream folded per
    'data' shard (the in-kernel counter already decorrelates heads).
    Routes through ops.flash_attention.packed_qkv_attention — the one
    envelope-gating site — which cannot return None here because the
    hook prechecked the identical envelope before opening shard_map."""
    from ..ops.flash_attention import packed_qkv_attention
    if key is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
    out = packed_qkv_attention(qkv, n_head, scale=scale,
                               dropout_rate=dropout_rate, rng=key,
                               train=key is not None)
    assert out is not None, "packed envelope changed between gate and body"
    return out


def make_sharded_flash_attention_fn(mesh: Mesh,
                                    scale: Optional[float] = None,
                                    impl: str = "auto",
                                    dropout_rate: float = 0.0):
    """attention_fn for ``models.gpt.forward`` / ``train.steps``.

    On meshes that shard neither heads nor sequence (pure DP / FSDP),
    the returned fn also carries a ``packed_qkv`` hook: models.gpt._block
    offers it the fused (B, T, 3C) projection output so the packed-heads
    kernel family — the round-3 +45-50% char-GPT win — engages per
    device instead of paying the split/transpose round trip the
    (B, H, T, D) contract implies. The hook returns None off the packed
    envelope (non-TPU, indivisible batch, VMEM bound); _block then takes
    the ordinary split-heads path through this same wrapper.
    """
    def attention_fn(q, k, v, rng=None, train=False):
        return sharded_flash_attention(q, k, v, mesh=mesh, scale=scale,
                                       impl=impl, dropout_rate=dropout_rate,
                                       rng=rng, train=train)

    model_n = mesh.shape.get("model", 1)
    seq_n = mesh.shape.get("seq", 1)
    if model_n == 1 and seq_n == 1:
        def packed_qkv(qkv, n_head, rng=None, train=False):
            from ..ops.flash_attention import (FLASH_MIN_T,
                                               packed_envelope_ok)
            B, T, _ = qkv.shape
            data_n = mesh.shape.get("data", 1)
            if B % data_n != 0:
                return None
            if impl != "flash" and T < FLASH_MIN_T:
                return None  # 'auto' keeps the measured crossover
            if not packed_envelope_ok(qkv, n_head):
                return None
            spec = P("data", None, None)
            local = functools.partial(_local_packed, n_head=n_head,
                                      scale=scale,
                                      dropout_rate=dropout_rate)
            if not (train and dropout_rate > 0.0 and rng is not None):
                fn = shard_map(local, mesh=mesh, in_specs=(spec,),
                                   out_specs=spec, check_vma=False)
                return fn(qkv)
            fn = shard_map(local, mesh=mesh, in_specs=(spec, P()),
                               out_specs=spec, check_vma=False)
            return fn(qkv, rng)

        attention_fn.packed_qkv = packed_qkv
    return attention_fn
