"""Pipeline parallelism: the block stack sharded over a 'pipe' mesh axis,
microbatches flowing stage-to-stage via ``lax.ppermute`` (GPipe-style
skewed schedule).

The reference runs its blocks in an in-process Python loop on one device
(GPT-2.py:117-118); SURVEY.md §2.1 lists PP as the remaining parallelism
row. TPU-native formulation: each of the P stages holds n_layer/P of the
layer-stacked block params (the (L, ...) leading dim is sharded over
'pipe' — see mesh.py partition rules), the global batch splits into M
microbatches, and the schedule runs M + P - 1 ticks. At tick t, stage s
works on microbatch m = t - s (stage 0 reads fresh microbatches, the last
stage banks finished ones), then every stage hands its activation to stage
s+1 over a neighbor ppermute riding ICI. Finished outputs are broadcast
from the last stage with a masked psum. The whole schedule is a
``lax.scan``, so reverse-mode AD gives GPipe's backward for free.

Composition: inside the shard_map region the 'seq' axis name is in scope,
so the per-block attention core is the ring-attention local body — seq
parallelism composes with PP natively (a 1-sized seq axis degrades to the
plain causal core). The 'data' axis partitions microbatch rows as usual.
The 'model' axis runs real Megatron TP inside the region (:func:`_block_tp`):
column-parallel QKV/MLP-up, row-parallel attn-out/MLP-down with explicit
``psum`` over 'model', biases added post-reduction. One layout wrinkle: a
contiguous shard of the fused (C, 3C) [q|k|v] kernel's last dim crosses
projection boundaries, so the kernel is reshaped host-side to (L, C, 3, C)
and sharded on the per-projection dim — each device then holds the same
head-slice of q, k, and v (heads stay whole: requires n_head % tp == 0,
else TP falls back to replicated kernels for that run).

Bubble math: utilization = M / (M + P - 1); pick microbatches >= 4*P to
keep the bubble under ~25%.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..config import MeshConfig, ModelConfig


def _block_tp(x: jnp.ndarray, lp: Dict[str, jnp.ndarray], cfg: ModelConfig,
              *, rng: Optional[jax.Array], train: bool, attention_fn,
              tp_axis: str = "model") -> jnp.ndarray:
    """Megatron tensor-parallel transformer block for shard_map regions.

    Mirrors models.gpt._block, but kernels arrive as raw local shards:
    qkv (C, 3, C/tp) column-parallel (per-projection dim pre-reshaped by
    pipeline_blocks), mlp_up (C, 4C/tp) column-parallel, attn_out (C/tp, C)
    and mlp_down (4C/tp, C) row-parallel with an explicit psum over
    ``tp_axis``. Row-parallel biases are added after the reduction (adding
    per-shard then summing would count them tp times). Activations stay
    replicated over 'model', so dropout masks (same rng on every model
    shard) remain consistent.
    """
    from ..models.gpt import (_activation, _dropout, _layer_norm,
                              _merge_heads, _split_heads)

    cd = x.dtype
    tp = axis_size(tp_axis)
    r_attn, r_drop1, r_drop2 = (jax.random.split(rng, 3)
                                if rng is not None else (None, None, None))
    if r_attn is not None:
        # heads are sharded over 'model' here (unlike the activations,
        # whose dropout keys must agree across model shards) — each head
        # shard needs its own attention-mask stream
        r_attn = jax.random.fold_in(r_attn, jax.lax.axis_index(tp_axis))
    h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], cfg.layernorm_eps)
    C = h.shape[-1]
    qkv_k = lp["qkv_kernel"].astype(cd)      # (C, 3, C/tp) local
    qkv_b = lp["qkv_bias"].astype(cd)        # (3, C/tp) local
    qkv = h @ qkv_k.reshape(C, -1) + qkv_b.reshape(-1)
    q, k, v = jnp.split(qkv, 3, axis=-1)     # each (B, T, C/tp)
    q, k, v = (_split_heads(t, cfg.n_head // tp) for t in (q, k, v))
    attn = attention_fn(q, k, v, rng=r_attn, train=train)
    attn = _merge_heads(attn)                # (B, T, C/tp): this shard's heads
    attn = attn @ lp["attn_out_kernel"].astype(cd)        # partial (B, T, C)
    attn = (jax.lax.psum(attn, tp_axis)
            + lp["attn_out_bias"].astype(cd))
    x = x + _dropout(attn, cfg.dropout, r_drop1, train)
    h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], cfg.layernorm_eps)
    h = _activation(h @ lp["mlp_up_kernel"].astype(cd)
                    + lp["mlp_up_bias"].astype(cd), cfg.activation)
    h = h @ lp["mlp_down_kernel"].astype(cd)              # partial (B, T, C)
    h = jax.lax.psum(h, tp_axis) + lp["mlp_down_bias"].astype(cd)
    return x + _dropout(h, cfg.dropout, r_drop2, train)


def _pp_local(x: jnp.ndarray, blocks: Dict[str, jnp.ndarray],
              rng: Optional[jax.Array], *, cfg: ModelConfig, train: bool,
              n_stages: int, tp_sharded: bool,
              axis_name: str = "pipe") -> jnp.ndarray:
    """Per-device pipeline schedule.

    x: (M, Bm, T_local, C) — all microbatches (replicated over 'pipe';
    only stage 0 reads them). blocks: local leaves with leading
    n_layer/n_stages ('model'-sharded kernels when tp_sharded). Returns
    (M, Bm, T_local, C) finished activations (identical on every stage
    after the final broadcast).
    """
    from ..models.gpt import _block
    from .ring_attention import _ring_local

    stage = jax.lax.axis_index(axis_name)
    M = x.shape[0]
    Lp = cfg.n_layer // n_stages
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    # the in-scope ring core applies attention-weight dropout from the
    # per-layer rng (pre-folded by (data, seq) shard below — the ring
    # folds its own seq/hop/chunk indices, and _block_tp folds 'model')
    attn_local = functools.partial(_ring_local, axis_name="seq", scale=None,
                                   dropout_rate=cfg.attn_dropout)

    if rng is not None:
        # the rng enters replicated; decorrelate dropout masks across the
        # data/seq shards (each device draws masks over its *local* shape,
        # so an unfolded key would repeat the same mask on every shard).
        # NOT folded over 'model': activations are replicated across model
        # shards, so their dropout masks must agree.
        shard_id = (jax.lax.axis_index("data") * axis_size("seq")
                    + jax.lax.axis_index("seq"))
        rng = jax.random.fold_in(rng, shard_id)

    def run_stage(h: jnp.ndarray, m_idx: jnp.ndarray) -> jnp.ndarray:
        """One microbatch through this stage's local layers."""
        def body(carry, inputs):
            lp, l_local = inputs
            r = None
            if rng is not None:
                g_layer = stage * Lp + l_local
                r = jax.random.fold_in(jax.random.fold_in(rng, g_layer),
                                       m_idx)
            if tp_sharded:
                out = _block_tp(carry, lp, cfg, rng=r, train=train,
                                attention_fn=attn_local)
            else:
                out = _block(carry, lp, cfg, rng=r, train=train,
                             attention_fn=attn_local)
            return out, None

        h, _ = jax.lax.scan(body, h, (blocks, jnp.arange(Lp)))
        return h

    def tick(carry, t):
        buf, out = carry
        m = t - stage                       # microbatch this stage handles
        active = jnp.logical_and(m >= 0, m < M)
        m_c = jnp.clip(m, 0, M - 1)
        # stage 0 ingests a fresh microbatch; later stages consume what
        # arrived over the ring last tick (zeros during fill — harmless)
        inp = jnp.where(stage == 0, x[jnp.clip(t, 0, M - 1)], buf)
        h = run_stage(inp, m_c)
        banked = jax.lax.dynamic_update_index_in_dim(out, h, m_c, 0)
        out = jnp.where(jnp.logical_and(stage == n_stages - 1, active),
                        banked, out)
        buf = jax.lax.ppermute(h, axis_name, perm)
        return (buf, out), None

    buf0 = jnp.zeros_like(x[0])
    out0 = jnp.zeros_like(x)
    (_, out), _ = jax.lax.scan(tick, (buf0, out0),
                               jnp.arange(M + n_stages - 1))
    # everyone needs the result (loss/head are replicated over 'pipe'):
    # masked psum broadcasts the last stage's bank
    out = jax.lax.psum(
        jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
        axis_name)
    return out


def pipeline_blocks(x: jnp.ndarray, blocks, cfg: ModelConfig, *,
                    mesh: Mesh, n_microbatches: int,
                    rng: Optional[jax.Array] = None,
                    train: bool = False) -> jnp.ndarray:
    """Run the block stack pipelined. x: global (B, T, C); blocks: the
    layer-stacked params dict ((L, ...) leaves, 'pipe'-sharded on dim 0).

    Drop-in replacement for models.gpt._run_blocks on a pipe>1 mesh.
    """
    B, T, C = x.shape
    M = n_microbatches
    assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
    n_stages = mesh.shape["pipe"]
    assert cfg.n_layer % n_stages == 0, (
        f"n_layer {cfg.n_layer} not divisible by {n_stages} pipeline stages")

    xm = x.reshape(M, B // M, T, C)
    x_spec = P(None, "data", "seq", None)
    tp = mesh.shape.get("model", 1)
    tp_sharded = tp > 1 and cfg.n_head % tp == 0 and cfg.n_embd % tp == 0
    if tp > 1 and not tp_sharded:
        import warnings
        warnings.warn(
            f"pipeline TP disabled: n_head={cfg.n_head}/n_embd={cfg.n_embd} "
            f"not divisible by model axis {tp}; kernels replicate through "
            f"the pipeline region (2x+ HBM per stage, idle model-axis "
            f"devices)")
    if tp_sharded:
        # keep Megatron TP live inside the region: kernels enter sharded
        # over 'model' instead of being all-gathered. The fused [q|k|v]
        # last dim can't be contiguously column-sharded (a 3C/tp slice
        # crosses projection boundaries), so it is reshaped to a
        # per-projection dim first — each shard then holds the same head
        # slice of q, k and v. Known trade-off: the at-rest spec
        # (mesh.py, contiguous 3C shard) differs from this region layout,
        # so XLA reshards the QKV weights across 'model' each step —
        # O(12 d^2/tp) per layer, small next to activations but not free;
        # a per-projection at-rest layout would remove it at the cost of
        # changing the checkpoint/HF-import pytree shape.
        L = blocks["qkv_kernel"].shape[0]
        blocks = dict(blocks)
        blocks["qkv_kernel"] = blocks["qkv_kernel"].reshape(L, C, 3, C)
        blocks["qkv_bias"] = blocks["qkv_bias"].reshape(L, 3, C)
        tp_specs = {
            "qkv_kernel": P("pipe", None, None, "model"),
            "qkv_bias": P("pipe", None, "model"),
            "mlp_up_kernel": P("pipe", None, "model"),
            "mlp_up_bias": P("pipe", "model"),
            "attn_out_kernel": P("pipe", "model", None),
            "mlp_down_kernel": P("pipe", "model", None),
        }
        blocks_spec = {
            name: tp_specs.get(
                name, P(*(("pipe",) + (None,) * (leaf.ndim - 1))))
            for name, leaf in blocks.items()}
    else:
        blocks_spec = jax.tree_util.tree_map(
            lambda leaf: P(*(("pipe",) + (None,) * (leaf.ndim - 1))), blocks)
    rng_spec = None if rng is None else P()

    fn = shard_map(
        functools.partial(_pp_local, cfg=cfg, train=train,
                          n_stages=n_stages, tp_sharded=tp_sharded),
        mesh=mesh,
        in_specs=(x_spec, blocks_spec, rng_spec),
        out_specs=x_spec,
        check_vma=False)
    out = fn(xm, blocks, rng)
    return out.reshape(B, T, C)


def make_pipeline_blocks_fn(mesh: Mesh, mesh_cfg: MeshConfig):
    """blocks_fn for ``models.gpt.forward`` — binds mesh + microbatch count
    (mesh_cfg.microbatches, defaulting to 2 per stage)."""
    M = mesh_cfg.microbatches or 2 * mesh_cfg.pipe

    def blocks_fn(x, blocks, cfg, *, rng, train):
        return pipeline_blocks(x, blocks, cfg, mesh=mesh, n_microbatches=M,
                               rng=rng, train=train)

    return blocks_fn
