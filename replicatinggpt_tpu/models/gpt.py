"""GPT as pure functions over a parameter pytree.

Re-expresses the reference's module stacks (GPT1.py:100-212 and
GPT-2.py:22-128) as ``init_params(rng, cfg) -> params`` and
``forward(params, idx, cfg, ...) -> (logits, loss)``:

- fused QKV projection (the GPT-2.py:28 formulation; GPT1's per-head Python
  loop, GPT1.py:130-136, is strictly worse on any hardware),
- pre-LN residual blocks (GPT1.py:162-165 / GPT-2.py:76-79),
- learned positional embeddings (GPT1.py:170-171 / GPT-2.py:97),
- optional weight tying (GPT-2.py:104) / untied head (GPT1.py:174) via
  ``cfg.tied_head``,
- GELU or ReLU MLP via ``cfg.activation``,
- GPT-2-paper init (std 0.02, residual projections scaled by
  1/sqrt(2*n_layer)) — the reference *tags* this intent
  (NANOGPT_SCALE_INIT, GPT-2.py:31,59) but never applies it (SURVEY.md
  §8-Q4); here it is real.

Layer parameters are stacked along a leading (n_layer,) axis and the block
stack runs under ``lax.scan`` — one compiled block body regardless of depth,
which keeps compile time flat and maps cleanly onto pipeline/FSDP sharding.
A KV-cache decode path shares the same block body (one position per step)
for the lax.scan generation loop.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import (cached_attention, full_causal_attention,
                             uint8_inverted_dropout,
                             windowed_cached_attention)
from ..utils.sanitize import check_in_bounds

Params = Dict[str, Any]


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize the parameter pytree. Shapes (C = n_embd, L = n_layer):

    wte (V, C) · wpe (block, C) · per-layer stacked tensors with leading L ·
    final layernorm · optional untied lm_head (C, V).
    """
    cfg.validate()
    C, L, V = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    pd = _dtype(cfg.param_dtype)
    std = cfg.init_std
    resid_std = std * (2 * L) ** -0.5
    keys = jax.random.split(rng, 8)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pd)

    blocks = {
        "ln1_scale": jnp.ones((L, C), pd),
        "ln1_bias": jnp.zeros((L, C), pd),
        "qkv_kernel": norm(keys[2], (L, C, 3 * C), std),
        "qkv_bias": jnp.zeros((L, 3 * C), pd),
        "attn_out_kernel": norm(keys[3], (L, C, C), resid_std),
        "attn_out_bias": jnp.zeros((L, C), pd),
        "ln2_scale": jnp.ones((L, C), pd),
        "ln2_bias": jnp.zeros((L, C), pd),
        "mlp_up_kernel": norm(keys[4], (L, C, 4 * C), std),
        "mlp_up_bias": jnp.zeros((L, 4 * C), pd),
        "mlp_down_kernel": norm(keys[5], (L, 4 * C, C), resid_std),
        "mlp_down_bias": jnp.zeros((L, C), pd),
    }
    params: Params = {
        "wte": norm(keys[0], (V, C), std),
        "wpe": norm(keys[1], (cfg.block_size, C), std),
        "blocks": blocks,
        "ln_f_scale": jnp.ones((C,), pd),
        "ln_f_bias": jnp.zeros((C,), pd),
    }
    if not cfg.tied_head:
        params["lm_head"] = norm(keys[6], (C, V), std)
    return params


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
                eps: float) -> jnp.ndarray:
    # LN statistics in float32 for bf16 stability; result back in x.dtype.
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _dropout(x: jnp.ndarray, rate: float, rng: Optional[jax.Array],
             train: bool) -> jnp.ndarray:
    # Residual/MLP dropout (GPT1.py:147). uint8-bits inverted dropout,
    # 1/256-quantized rate shared with every other dropout site — see
    # ops.attention.quantize_dropout_rate.
    if not train or rate <= 0.0 or rng is None:
        return x
    return uint8_inverted_dropout(x, rate, rng)


def _activation(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    return jax.nn.gelu(x) if kind == "gelu" else jax.nn.relu(x)


#: the leaves every serving entry point below reads ONLY as
#: ``leaf.astype(cd)``: the embeddings, the untied head, and in ``blocks``
#: the four kernels (``_wmm``) and their biases. A caller may hand them over
#: already in the compute dtype (``serve/engine.py`` casts them once, at its
#: build): the cast here is then the identity and ``cast_params`` is empty.
#: NOT here: the LayerNorm leaves (``_layer_norm`` reads them as float32)
#: and a quantised kernel's ``<name>_scale`` (W8A8 reads it as float32).
SERVE_CAST_LEAVES = ("wte", "wpe", "lm_head",
                     "qkv_kernel", "attn_out_kernel",
                     "mlp_up_kernel", "mlp_down_kernel",
                     "qkv_bias", "attn_out_bias",
                     "mlp_up_bias", "mlp_down_bias")


def _wmm(h: jnp.ndarray, lp: Dict[str, jnp.ndarray], name: str,
         cd, aq: bool = False) -> jnp.ndarray:
    """``h @ lp[name]`` with weight-quantization dequant fused into the
    matmul: quantized params (quant/weights.py) store the kernel in
    int8/fp8 plus a per-OUTPUT-channel f32 ``<name>_scale`` vector, and
    per-output-channel scales commute through the contraction — so the
    dequant is one multiply on the output row, never a rematerialized
    full-precision weight. Unquantized params take the identical
    ``h @ W.astype(cd)`` path (the scale key is simply absent, a static
    pytree property — no recompile churn, one program per params
    structure).

    ``aq`` (W8A8): when the kernel is already int8, quantize the
    ACTIVATION rows too — per-row symmetric int8 (same ``max(amax/127,
    eps)`` scale law as quant/kv.py) into an int8 x int8 -> int32
    ``dot_general``, dequanted by the separable rank-1 scale product
    ``s_act (rows) x s_w (output channels)``. Rows-within-int8-range is
    exact in int32, so W8A8 divergence comes only from the activation
    rounding (bounded like the KV int8 budget). Falls through to the
    weight-only path when the kernel is not int8 (fp8 kernels keep
    f32-accumulated matmuls)."""
    s = lp.get(name + "_scale")
    if aq and s is not None and lp[name].dtype == jnp.int8:
        f = h.astype(jnp.float32)
        s_act = jnp.maximum(
            jnp.max(jnp.abs(f), axis=-1, keepdims=True) / 127.0, 1e-8)
        hq = jnp.clip(jnp.round(f / s_act), -127.0,
                      127.0).astype(jnp.int8)
        y = jax.lax.dot_general(
            hq, lp[name], (((hq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return (y.astype(jnp.float32) * s_act
                * s.astype(jnp.float32)).astype(cd)
    with jax.named_scope("cast_params"):
        w = lp[name].astype(cd)
    y = h @ w
    if s is not None:
        y = y * s.astype(cd)
    return y


def _split_heads(x: jnp.ndarray, n_head: int) -> jnp.ndarray:
    B, T, C = x.shape
    return x.reshape(B, T, n_head, C // n_head).transpose(0, 2, 1, 3)


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _block(x: jnp.ndarray, lp: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
           rng: Optional[jax.Array], train: bool,
           attention_fn=None) -> jnp.ndarray:
    """One pre-LN transformer block over a full (B, T, C) sequence.

    ``attention_fn`` overrides the attention core (used by the ring-attention
    sequence-parallel path); default picks einsum/flash per cfg.
    """
    cd = x.dtype
    r_attn, r_drop1, r_drop2 = (jax.random.split(rng, 3)
                                if rng is not None else (None, None, None))
    attn = _block_attention(x, lp, cfg, r_attn, train, attention_fn)
    # Projection dropout: declared-but-unapplied in the reference
    # (GPT1.py:132,136, SURVEY.md §8-Q2); correct-by-default here.
    x = x + _dropout(attn, cfg.dropout, r_drop1, train)
    with jax.named_scope("mlp"):
        h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"],
                        cfg.layernorm_eps)
        h = _activation(_wmm(h, lp, "mlp_up_kernel", cd)
                        + lp["mlp_up_bias"].astype(cd), cfg.activation)
        h = (_wmm(h, lp, "mlp_down_kernel", cd)
             + lp["mlp_down_bias"].astype(cd))
    return x + _dropout(h, cfg.dropout, r_drop2, train)


@jax.named_scope("attn")
def _block_attention(x, lp, cfg: ModelConfig, r_attn, train: bool,
                     attention_fn):
    """ln1, the fused QKV projection, the attention core and the output
    projection of ``_block``: what it runs under the ``attn`` scope."""
    cd = x.dtype
    h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], cfg.layernorm_eps)
    qkv = _wmm(h, lp, "qkv_kernel", cd) + lp["qkv_bias"].astype(cd)
    attn = None
    impl = cfg.attention_impl
    if attention_fn is not None:
        # mesh wrappers without head/seq sharding expose a packed-qkv
        # hook (parallel/sharded_flash.py) so sharded runs also skip the
        # head-layout round trip; None -> ordinary split-heads path
        packed_hook = getattr(attention_fn, "packed_qkv", None)
        if packed_hook is not None:
            attn = packed_hook(qkv, cfg.n_head, rng=r_attn, train=train)
    if attention_fn is None and impl in ("auto", "ring", "ulysses",
                                         "flash"):
        if impl != "flash":
            # seq-parallel impls ('ring'/'ulysses') only exist as sharded
            # wrappers (parallel/ring_attention.py, parallel/ulysses.py)
            # passed in via attention_fn; locally they degrade to the
            # dense/flash choice. FLASH_MIN_T is the measured v5e
            # crossover (19.2 vs 19.7 ms/step on the char-GPT workload at
            # T=256; 2.3x kernel speedup at 512x512 auto tiles made the
            # old T>=1024 threshold stale). Kernel-envelope and dropout
            # fallbacks belong to full_causal_attention/_pallas_supported
            # (one source of truth — attention-weight dropout runs
            # in-kernel on the Pallas path, and degrades to dense einsum
            # elsewhere).
            from ..ops.flash_attention import FLASH_MIN_T
            impl = "flash" if qkv.shape[1] >= FLASH_MIN_T else "einsum"
        if impl == "flash":
            # packed-heads kernel consumes the fused projection output
            # directly — no (B,T,H,D)<->(B,H,T,D) round trip on either
            # pass; None off the envelope -> split-heads path below
            from ..ops.flash_attention import packed_qkv_attention
            attn = packed_qkv_attention(qkv, cfg.n_head,
                                        dropout_rate=cfg.attn_dropout,
                                        rng=r_attn, train=train)
    if attn is None:
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (_split_heads(t, cfg.n_head) for t in (q, k, v))
        if attention_fn is not None:
            # seq-parallel cores (ring/Ulysses) apply attention-weight
            # dropout themselves from the per-block rng (per-device
            # streams derived inside their shard_map regions)
            attn = attention_fn(q, k, v, rng=r_attn, train=train)
        else:
            attn = full_causal_attention(
                q, k, v, dropout_rate=cfg.attn_dropout, rng=r_attn,
                train=train, impl=impl)
        attn = _merge_heads(attn)
    return (_wmm(attn, lp, "attn_out_kernel", cd)
            + lp["attn_out_bias"].astype(cd))


def _remat_policy(name: str):
    """Resolve cfg.remat_policy to a jax.checkpoint policy (None = save
    nothing, recompute the whole block — the 'full' default)."""
    if name == "full":
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_saveable
    if name == "dots_no_batch":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f"remat_policy must be 'full', 'dots' or "
                     f"'dots_no_batch', got {name!r}")


def _run_blocks(x: jnp.ndarray, blocks: Dict[str, jnp.ndarray],
                cfg: ModelConfig, *, rng: Optional[jax.Array], train: bool,
                attention_fn=None) -> jnp.ndarray:
    L = cfg.n_layer

    def body(carry, inputs):
        lp, layer_idx = inputs
        r = (jax.random.fold_in(rng, layer_idx)
             if rng is not None else None)
        if cfg.remat:
            fn = jax.checkpoint(
                lambda c, p: _block(c, p, cfg, rng=r, train=train,
                                    attention_fn=attention_fn),
                policy=_remat_policy(cfg.remat_policy))
            return fn(carry, lp), None
        return _block(carry, lp, cfg, rng=r, train=train,
                      attention_fn=attention_fn), None

    layer_ids = jnp.arange(L)
    if cfg.use_layer_scan:
        x, _ = jax.lax.scan(body, x, (blocks, layer_ids))
        return x
    for i in range(L):
        lp = jax.tree_util.tree_map(lambda a: a[i], blocks)
        x, _ = body(x, (lp, layer_ids[i]))
    return x


# ---------------------------------------------------------------------------
# forward (training / full-sequence)
# ---------------------------------------------------------------------------

def forward(params: Params, idx: jnp.ndarray, cfg: ModelConfig, *,
            targets: Optional[jnp.ndarray] = None,
            rng: Optional[jax.Array] = None, train: bool = False,
            attention_fn=None, blocks_fn=None
            ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Full-sequence forward. idx: (B, T) int32.

    Always returns ``(logits, loss)``; loss is None without targets — the
    reference's asymmetric return (GPT-2.py:124-128) is normalized away.
    Cross-entropy is computed in float32 over flattened (B*T) positions
    (GPT1.py:186-192 semantics). Exception: with ``cfg.loss_chunk`` set
    and targets given, the chunked CE head returns ``(None, loss)`` —
    the full logits array is exactly what that mode avoids building. ``blocks_fn`` replaces the whole block
    stack (the pipeline-parallel schedule plugs in here); ``attention_fn``
    replaces just the attention core inside the default stack.
    """
    B, T = idx.shape
    cd = _dtype(cfg.dtype)
    # Out-of-range ids would silently clamp on TPU gathers; the reference
    # instead crashed (SURVEY.md §8-B1/B5). Config and tokenizer are
    # validated host-side in the pipeline instead.
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx] + params["wpe"].astype(cd)[:T]
    if blocks_fn is not None:
        x = blocks_fn(x, params["blocks"], cfg, rng=rng, train=train)
    else:
        x = _run_blocks(x, params["blocks"], cfg, rng=rng, train=train,
                        attention_fn=attention_fn)
    with jax.named_scope("head"):
        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"],
                        cfg.layernorm_eps)
        head = (params["wte"].astype(cd).T if cfg.tied_head
                else params["lm_head"].astype(cd))
    if targets is not None and cfg.loss_chunk:
        if (B * T) % cfg.loss_chunk != 0:
            # a silent fallback here would let an A/B arm measure the
            # one-shot head while claiming the chunked one (and forfeit
            # the HBM saving a config was chosen for) — fail loudly
            raise ValueError(
                f"loss_chunk={cfg.loss_chunk} must divide B*T="
                f"{B * T}; pick a divisor or set loss_chunk=0")
        return None, _chunked_ce_loss(x, head, targets, cfg.loss_chunk)
    with jax.named_scope("head"):
        logits = (x @ head).astype(jnp.float32)
    if targets is None:
        return logits, None
    import optax
    with jax.named_scope("loss"):
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.reshape(B * T, -1), targets.reshape(B * T)).mean()
    return logits, loss


@jax.named_scope("loss")
def _chunked_ce_loss(x, head, targets, chunk: int) -> jnp.ndarray:
    """Cross-entropy without materializing the full (B*T, V) f32 logits:
    a lax.scan over ``chunk``-row slices computes each chunk's logits +
    per-row CE and accumulates the sum; the chunk body is jax.checkpoint
    so the backward recomputes chunk logits instead of storing them as
    scan residuals (full-logits storage is exactly what this avoids).
    Per-row math is identical to the unchunked head — rows are
    independent under softmax-CE — so only the final mean's reduction
    order differs (f32 sum). At GPT-2 vocab (V=50304, B=32, T=1024) the
    unchunked head round-trips a ~6.6 GB f32 logits array through HBM
    for loss + backward; chunked, the working set is chunk*V bytes.
    Trades one extra head matmul in the backward (~+10% model FLOPs at
    124M) for that traffic — measure before defaulting
    (cfg.loss_chunk=0 keeps the unchunked head)."""
    import optax
    N = x.shape[0] * x.shape[1]
    C = x.shape[-1]
    xf = x.reshape(N // chunk, chunk, C)
    tf = targets.reshape(N // chunk, chunk)

    @jax.checkpoint
    def body(acc, xs):
        xc, tc = xs
        lg = (xc @ head).astype(jnp.float32)
        return acc + optax.softmax_cross_entropy_with_integer_labels(
            lg, tc).sum(), None

    acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xf, tf))
    return acc / N


# ---------------------------------------------------------------------------
# KV-cache decode path (shared weights, single-position block body)
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def _cached_qkv_merged(h_in, lp, cfg: ModelConfig, cd):
    """ln1 + fused QKV projection, heads still merged — the cache-path
    front half of a block as (B, T, C) q/k/v rows (one source of truth
    for the math that must produce identical K/V on decode and
    prefill). The packed cache layout writes these rows untouched."""
    aq = getattr(cfg, "act_quant", "none") == "int8"
    h = _layer_norm(h_in, lp["ln1_scale"], lp["ln1_bias"],
                    cfg.layernorm_eps)
    qkv = _wmm(h, lp, "qkv_kernel", cd, aq=aq) + lp["qkv_bias"].astype(cd)
    return jnp.split(qkv, 3, axis=-1)


def _cached_qkv(h_in, lp, cfg: ModelConfig, cd):
    """`_cached_qkv_merged` + head split — the (B, H, T, D) form the
    einsum attention cores consume."""
    q, k, v = _cached_qkv_merged(h_in, lp, cfg, cd)
    return tuple(_split_heads(t, cfg.n_head) for t in (q, k, v))


def _cached_block_tail(h_in, attn_merged, lp, cfg: ModelConfig, cd):
    """Output projection + residual + ln2 + MLP + residual — the
    cache-path back half of a block, shared by decode_step and prefill
    (no dropout: decode paths never train)."""
    aq = getattr(cfg, "act_quant", "none") == "int8"
    with jax.named_scope("attn"):
        attn = (_wmm(attn_merged, lp, "attn_out_kernel", cd, aq=aq)
                + lp["attn_out_bias"].astype(cd))
    h_mid = h_in + attn
    with jax.named_scope("mlp"):
        h = _layer_norm(h_mid, lp["ln2_scale"], lp["ln2_bias"],
                        cfg.layernorm_eps)
        h = _activation(_wmm(h, lp, "mlp_up_kernel", cd, aq=aq)
                        + lp["mlp_up_bias"].astype(cd), cfg.activation)
        h = (_wmm(h, lp, "mlp_down_kernel", cd, aq=aq)
             + lp["mlp_down_bias"].astype(cd))
    return h_mid + h


def cache_seq_axis(cfg: ModelConfig) -> int:
    """Axis of the sequence dimension in the stacked KV cache — layout-
    dependent (callers that grow/measure the cache buffer must not
    hard-code it)."""
    return 2 if cfg.decode_cache_layout == "packed" else 3


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: Optional[int] = None,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """Cache layout, stacked over layers for lax.scan:
    (L, B, H, S, D) for ``decode_cache_layout='heads'``, or the fully
    lane-packed (L, B, S, C) for ``'packed'`` (see the config field)."""
    S = max_len or cfg.block_size
    dt = dtype or _dtype(cfg.dtype)
    if cfg.decode_cache_layout == "packed":
        shape = (cfg.n_layer, batch, S, cfg.n_embd)
    else:
        shape = (cfg.n_layer, batch, cfg.n_head, S, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _all_single_device(tree) -> bool:
    """True when every array leaf lives on one device (no sharding over
    a multi-device mesh) — the GSPMD-safety answer the decode kernels'
    gate needs: a bare pallas_call cannot be partitioned, so the kernels
    are only safe when the program cannot be mesh-sharded. Concrete
    arrays answer with their committed sharding; inside a trace the
    tracer's type carries the mesh of the array it stands for (empty
    for a single-device input)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.core.Tracer):
            mesh = jax.typeof(leaf).sharding.mesh
            if not mesh.empty and mesh.size > 1:
                return False
            continue
        s = getattr(leaf, "sharding", None)
        if s is not None and len(s.device_set) > 1:
            return False
    return True


_PALLAS_GATE_LOGGED = False


def _default_allow_pallas(*inputs) -> bool:
    """Default kernel gate for direct decode_step callers: the
    shardings of the inputs themselves (``_all_single_device`` — what
    generate() computes eagerly), traced or concrete, so single-device
    inputs on a multi-chip host keep the decode kernel. Logs once per
    process when the gate turns the kernels off on a backend that would
    otherwise run them (a silent perf cliff is worse than one stderr
    line)."""
    from ..ops.decode_pallas import _packed_attn_backend_ok
    ok = _all_single_device(inputs)
    if not ok and _packed_attn_backend_ok():
        global _PALLAS_GATE_LOGGED
        if not _PALLAS_GATE_LOGGED:
            _PALLAS_GATE_LOGGED = True
            import sys
            print("note: decode kernels gated off (inputs sharded over "
                  "a multi-device mesh; a bare pallas_call cannot be "
                  "partitioned)", file=sys.stderr)
    return ok


def decode_step(params: Params, idx_t: jnp.ndarray, pos: jnp.ndarray,
                cache: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
                allow_pallas: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One autoregressive step. idx_t: (B,) int32 current tokens; pos: scalar
    int32 position. Returns (logits (B, V) float32, updated cache).

    Replaces the reference's full re-forward per generated token
    (GPT1.py:200-202) with O(T) work per token.

    The cache may be shorter than cfg.block_size (``init_kv_cache``'s
    max_len): every step streams the whole buffer, so callers that know
    ``pos`` stays small keep the buffer small — sample.generate grows it
    chunk-by-chunk instead of paying the full static bucket from token 1
    (a static prefix *slice* here instead was measured 10x WORSE at
    124M B=8: slicing the scan-carried buffer defeats XLA's in-place
    aliasing of the dynamic_update_slice writes and copies the cache
    every step).
    """
    cd = _dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx_t] + params["wpe"].astype(cd)[pos]
    x = x[:, None, :]  # (B, 1, C)

    # a past-the-end pos would CLAMP in the cache write below and
    # overwrite the last valid K/V (lint GL006); concrete (eager) calls
    # assert here, traced callers bound pos host-side (generate's
    # window refresh, the serve engine's admission room check)
    check_in_bounds(pos, 1, cache["k"].shape[cache_seq_axis(cfg)],
                    what="decode_step cache write")
    if cfg.decode_cache_layout == "packed":
        if allow_pallas is None:
            allow_pallas = _default_allow_pallas(params, idx_t, cache)
        return _decode_step_packed(params, x, pos, cache, cfg, cd,
                                   allow_pallas)

    def body(carry, inputs):
        # Caches ride the carry as the full stacked (L, B, H, S, D)
        # arrays, updated by dynamic_update_slice at (layer, pos) — XLA
        # keeps ONE buffer in place across layers and across the outer
        # decode scan. The previous formulation emitted per-layer caches
        # as scan ys, which allocates and copies the entire cache every
        # generated token (measured: decode step time scaled with cache
        # bytes, 0.44 ms at B=8 -> 1.54 ms at B=32 for a model whose
        # per-token math is microseconds).
        h_in, ck, cv = carry
        lp, layer_idx = inputs
        q, k, v = _cached_qkv(h_in, lp, cfg, cd)  # (B, H, 1, D)
        zero = jnp.int32(0)
        start = (layer_idx, zero, zero, pos, zero)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype)[None],
                                          start)
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype)[None],
                                          start)
        k_cache = jax.lax.dynamic_index_in_dim(ck, layer_idx, 0,
                                               keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(cv, layer_idx, 0,
                                               keepdims=False)
        attn = cached_attention(q, k_cache, v_cache, pos)
        return (_cached_block_tail(h_in, _merge_heads(attn), lp, cfg, cd),
                ck, cv), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (x, new_k, new_v), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], layer_ids))
    else:
        # shallow stacks: unrolled layers fuse/overlap better (same
        # measured rationale as _run_blocks); the static Python index
        # keeps the layer offset a compile-time constant
        carry = (x, cache["k"], cache["v"])
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        x, new_k, new_v = carry
    return _decode_head(x, params, cfg, cd), {"k": new_k, "v": new_v}


def _decode_step_packed(params: Params, x, pos, cache, cfg: ModelConfig,
                        cd, allow_pallas: bool
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """decode_step body for the (L, B, S, C) packed cache layout.

    The fresh K/V rows are written as (B, 1, C) rows — no head split, no
    D-minor tile padding in the carried buffer. Attention reads the
    layer's (B, S, C) slice through the packed decode kernel
    (ops/decode_pallas.py: per-head static lane slices of fully-packed
    rows) on TPU, or the reshape->einsum fallback elsewhere; both attend
    the stale cache masked to positions < pos plus the fresh column,
    which is bit-equivalent to write-then-attend (cache[pos] would hold
    exactly the fresh k/v)."""
    from ..ops.decode_pallas import (_packed_attn_backend_ok,
                                     packed_decode_attention,
                                     packed_decode_supported)
    H = cfg.n_head
    S = cache["k"].shape[2]
    check_in_bounds(pos, 1, S, what="packed decode cache write")
    # the kernel attends the fresh column at compute precision, so
    # write-then-attend bit-equivalence needs the stored value to
    # round-trip losslessly
    use_kernel = (allow_pallas
                  and _packed_attn_backend_ok()
                  and cache["k"].dtype == cd
                  and packed_decode_supported(
                      cfg, jnp.dtype(cache["k"].dtype).itemsize, seq_len=S))

    def body(carry, inputs):
        h_in, ck, cv = carry
        lp, layer_idx = inputs
        q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)  # (B, 1, C)
        if use_kernel:
            # kernel attends the STALE cache + fresh column, so the
            # write can land after (bit-equivalent final cache)
            k_cache = jax.lax.dynamic_index_in_dim(ck, layer_idx, 0,
                                                   keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(cv, layer_idx, 0,
                                                   keepdims=False)
            attn_merged = packed_decode_attention(
                q_m[:, 0, :], k_m[:, 0, :], v_m[:, 0, :],
                k_cache, v_cache, pos, n_head=H)[:, None, :]
            write_first = False
        else:
            write_first = True
        zero = jnp.int32(0)
        start = (layer_idx, zero, pos, zero)
        ck = jax.lax.dynamic_update_slice(ck, k_m.astype(ck.dtype)[None],
                                          start)
        cv = jax.lax.dynamic_update_slice(cv, v_m.astype(cv.dtype)[None],
                                          start)
        if write_first:
            k_cache = jax.lax.dynamic_index_in_dim(ck, layer_idx, 0,
                                                   keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(cv, layer_idx, 0,
                                                   keepdims=False)
            attn = cached_attention(_split_heads(q_m, H),
                                    _split_heads(k_cache, H),
                                    _split_heads(v_cache, H), pos)
            attn_merged = _merge_heads(attn)
        return (_cached_block_tail(h_in, attn_merged, lp, cfg, cd),
                ck, cv), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (x, new_k, new_v), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], layer_ids))
    else:
        carry = (x, cache["k"], cache["v"])
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        x, new_k, new_v = carry
    return _decode_head(x, params, cfg, cd), {"k": new_k, "v": new_v}


@jax.named_scope("head")
def _decode_head(x, params: Params, cfg: ModelConfig, cd) -> jnp.ndarray:
    """Final layernorm + (tied/untied) head over a (B, 1, C) decode
    state — one source of truth for every decode step's tail."""
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"],
                    cfg.layernorm_eps)
    head = (params["wte"].astype(cd).T if cfg.tied_head
            else params["lm_head"].astype(cd))
    return (x[:, 0, :] @ head).astype(jnp.float32)


def prefill(params: Params, idx: jnp.ndarray,
            cache: Dict[str, jnp.ndarray], cfg: ModelConfig
            ) -> Dict[str, jnp.ndarray]:
    """Parallel KV-cache fill: one full-sequence causal forward over the
    (B, P) prompt writing every position's K/V into cache[..., :P, :].
    Replaces P-1 *sequential* ``decode_step`` calls per segment — the
    teacher-forced prompt replay was ~43% of all decode steps on the
    1k-token char workload (window refresh re-prefills block_size//2
    tokens per segment). K/V at position p depends only on tokens
    <= p (causal attention, per-position projections), so positions at
    or beyond the true prompt length may hold padding-derived values —
    harmless: the decode scan overwrites position p before attending it
    and masks everything beyond. Attention core is the einsum path on
    purpose (see the inline comment: the segment is GSPMD-partitioned
    under sharded decode, where a bare pallas_call cannot partition).
    """
    cd = _dtype(cfg.dtype)
    B, P = idx.shape
    # shapes are static, so this guard holds even under jit: a prompt
    # longer than the cache buffer would clamp-corrupt the tail
    check_in_bounds(0, P, cache["k"].shape[cache_seq_axis(cfg)],
                    what="prefill prompt write")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx] + params["wpe"].astype(cd)[:P]

    packed = cfg.decode_cache_layout == "packed"

    def body(carry, inputs):
        h_in, ck, cv = carry
        lp, layer_idx = inputs
        q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)
        q, k, v = (_split_heads(t, cfg.n_head) for t in (q_m, k_m, v_m))
        zero = jnp.int32(0)
        if packed:
            # merged (B, P, C) rows straight into the lane-packed cache
            start = (layer_idx, zero, zero, zero)
            ck = jax.lax.dynamic_update_slice(
                ck, k_m.astype(ck.dtype)[None], start)
            cv = jax.lax.dynamic_update_slice(
                cv, v_m.astype(cv.dtype)[None], start)
        else:
            start = (layer_idx, zero, zero, zero, zero)
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype)[None],
                                              start)
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype)[None],
                                              start)
        # einsum core on purpose: this runs inside the jitted decode
        # segment, which sharded decodes partition with GSPMD
        # (shard_for_decode) — a bare pallas_call cannot partition
        # (parallel/__init__ policy), and the einsum core is already the
        # decode path's attention everywhere else (cached_attention)
        attn = full_causal_attention(q, k, v, impl="einsum")
        return (_cached_block_tail(h_in, _merge_heads(attn), lp, cfg, cd),
                ck, cv), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (_, ck, cv), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], layer_ids))
    else:
        carry = (x, cache["k"], cache["v"])
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        _, ck, cv = carry
    return {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Multi-slot decode (continuous batching: per-slot positions)
# ---------------------------------------------------------------------------

def decode_step_multi(params: Params, idx_t: jnp.ndarray, pos: jnp.ndarray,
                      cache: Dict[str, jnp.ndarray], cfg: ModelConfig
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One autoregressive step over B independent cache slots at
    PER-SLOT positions. idx_t: (B,) int32 current tokens; pos: (B,)
    int32 per-slot positions. Returns (logits (B, V) float32, updated
    cache).

    This is ``decode_step`` generalized for the continuous-batching
    serving engine (serve/engine.py): each batch row is a pool slot
    decoding its own request at its own offset, so the K/V write is a
    batched scatter at (layer, b, pos[b]) instead of one
    dynamic_update_slice, and the attention mask is per-row
    (ops.attention.cached_attention accepts a (B,) cache_index). The
    per-row math is identical to the scalar-pos XLA path — rows are
    independent through every op — which is what makes the engine's
    greedy output token-identical to offline ``generate`` (pinned in
    tests/test_serve.py). No Pallas route: the packed decode
    kernel assumes one shared position; the serving engine is a
    steady-state multi-slot batch where the XLA path is the right tool.
    """
    cd = _dtype(cfg.dtype)
    B = idx_t.shape[0]
    bidx = jnp.arange(B)
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx_t] + params["wpe"].astype(cd)[pos]
    x = x[:, None, :]  # (B, 1, C)
    packed = cfg.decode_cache_layout == "packed"
    H = cfg.n_head

    def body(carry, inputs):
        h_in, ck, cv = carry
        lp, layer_idx = inputs
        if packed:
            q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)
            ck = ck.at[layer_idx, bidx, pos, :].set(
                k_m[:, 0, :].astype(ck.dtype))
            cv = cv.at[layer_idx, bidx, pos, :].set(
                v_m[:, 0, :].astype(cv.dtype))
            k_cache = jax.lax.dynamic_index_in_dim(ck, layer_idx, 0,
                                                   keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(cv, layer_idx, 0,
                                                   keepdims=False)
            attn = cached_attention(_split_heads(q_m, H),
                                    _split_heads(k_cache, H),
                                    _split_heads(v_cache, H), pos)
        else:
            q, k, v = _cached_qkv(h_in, lp, cfg, cd)  # (B, H, 1, D)
            ck = ck.at[layer_idx, bidx, :, pos, :].set(
                k[:, :, 0, :].astype(ck.dtype))
            cv = cv.at[layer_idx, bidx, :, pos, :].set(
                v[:, :, 0, :].astype(cv.dtype))
            k_cache = jax.lax.dynamic_index_in_dim(ck, layer_idx, 0,
                                                   keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(cv, layer_idx, 0,
                                                   keepdims=False)
            attn = cached_attention(q, k_cache, v_cache, pos)
        return (_cached_block_tail(h_in, _merge_heads(attn), lp, cfg, cd),
                ck, cv), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (x, new_k, new_v), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], layer_ids))
    else:
        carry = (x, cache["k"], cache["v"])
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        x, new_k, new_v = carry
    return _decode_head(x, params, cfg, cd), {"k": new_k, "v": new_v}


def verify_step_multi(params: Params, window: jnp.ndarray, pos: jnp.ndarray,
                      n_valid: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                      cfg: ModelConfig
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The target-side forward of speculative decoding: score a static
    (k+1)-wide token window per slot in ONE pass over the pooled cache.

    window: (B, W) int32 — per slot ``[last_committed, draft_1..draft_k]``;
    pos: (B,) int32 per-slot base positions (window token j sits at
    ``pos[b] + j``); n_valid: (B,) int32 — how many DRAFT positions are
    real for each slot (0..W-1; the base token at j=0 is always real).
    Returns (logits (B, W, V) float32, updated cache): logits[:, j] is
    the next-token distribution after window token j, so j=0 reproduces
    ``decode_step_multi``'s output and j>=1 scores the drafted suffix.

    Cache discipline mirrors ``decode_step_multi``: K/V for window token
    j is scattered at (layer, b, pos[b]+j) and queries attend positions
    <= their own (ops.attention.windowed_cached_attention), i.e.
    write-then-attend. Padding window positions (j > n_valid[b]) route
    their scatter index to S — explicitly out of bounds, where scatter
    drops the update (mode='drop'), so a slot near the end of its buffer
    never clamp-corrupts earlier K/V; their logits are garbage and the
    caller discards them (acceptance is masked by n_valid). Rejected
    drafts leave stale K/V past the committed frontier — harmless under
    the pool invariant (every position is overwritten before any query
    sits at or beyond it). Per-row, per-position math is the decode
    path's exactly, which is what greedy speculative parity rests on
    (tests/test_speculative.py).
    """
    cd = _dtype(cfg.dtype)
    B, W = window.shape
    S = cache["k"].shape[cache_seq_axis(cfg)]
    bidx = jnp.arange(B)[:, None]                       # (B, 1)
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]      # (1, W)
    abs_pos = pos[:, None] + offs                       # (B, W)
    # wpe gather clamps out-of-bounds rows (padding only — real window
    # positions are bounded host-side: pos + n_valid <= S - 1)
    with jax.named_scope("embed"):
        x = (params["wte"].astype(cd)[window]                    # (B, W, C)
             + params["wpe"].astype(cd)[jnp.minimum(abs_pos, S - 1)])
    # padding writes go to S where the scatter drops them
    wpos = jnp.where(offs <= n_valid[:, None], abs_pos, S)
    packed = cfg.decode_cache_layout == "packed"
    H = cfg.n_head

    def body(carry, inputs):
        h_in, ck, cv = carry
        lp, layer_idx = inputs
        if packed:
            q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)  # (B, W, C)
            ck = ck.at[layer_idx, bidx, wpos, :].set(
                k_m.astype(ck.dtype), mode="drop")
            cv = cv.at[layer_idx, bidx, wpos, :].set(
                v_m.astype(cv.dtype), mode="drop")
            k_cache = jax.lax.dynamic_index_in_dim(ck, layer_idx, 0,
                                                   keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(cv, layer_idx, 0,
                                                   keepdims=False)
            attn = windowed_cached_attention(
                _split_heads(q_m, H), _split_heads(k_cache, H),
                _split_heads(v_cache, H), pos)
        else:
            q, k, v = _cached_qkv(h_in, lp, cfg, cd)    # (B, H, W, D)
            # scatter value laid out (B, W, H, D): advanced indices
            # (bidx, wpos) broadcast to (B, W) and land first
            ck = ck.at[layer_idx, bidx, :, wpos, :].set(
                k.transpose(0, 2, 1, 3).astype(ck.dtype), mode="drop")
            cv = cv.at[layer_idx, bidx, :, wpos, :].set(
                v.transpose(0, 2, 1, 3).astype(cv.dtype), mode="drop")
            k_cache = jax.lax.dynamic_index_in_dim(ck, layer_idx, 0,
                                                   keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(cv, layer_idx, 0,
                                                   keepdims=False)
            attn = windowed_cached_attention(q, k_cache, v_cache, pos)
        return (_cached_block_tail(h_in, _merge_heads(attn), lp, cfg, cd),
                ck, cv), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (x, new_k, new_v), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], layer_ids))
    else:
        carry = (x, cache["k"], cache["v"])
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        x, new_k, new_v = carry
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"],
                    cfg.layernorm_eps)
    head = (params["wte"].astype(cd).T if cfg.tied_head
            else params["lm_head"].astype(cd))
    return (x @ head).astype(jnp.float32), {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# Paged KV pool (serving engine: page tables instead of contiguous slots)
# ---------------------------------------------------------------------------

def _constrain(x, s):
    """``jax.lax.with_sharding_constraint`` when a sharding is given;
    identity when ``s`` is None. The sharded serving engine pins the
    page pool and the per-slot step state to their PartitionSpecs
    (parallel.mesh.ServeShardings) INSIDE every traced program: GSPMD
    left alone may re-layout a scan carry mid-program, and donation
    only aliases input to output when their shardings match — so the
    pool spec must survive every window/verify/prefill body unchanged,
    and the sampled token block must leave fully replicated (the
    engine's one-``np.asarray``-per-window fetch stays a local read)."""
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def pool_entry_sharding(shardings, name: str):
    """Per-entry sharding of a paged pool dict: the K/V page arrays
    take the (data, model) pool spec, the quantization scale arrays
    (``ks``/``vs`` — different rank, no model dim) their own page-axis
    spec (``ServeShardings.scale``). One mapping shared by the traced
    constraints here and the engine's COW page copy."""
    if shardings is None:
        return None
    if name in ("k", "v"):
        return shardings.cache
    return shardings.scale


def _constrain_cache(cache: Dict[str, jnp.ndarray], shardings
                     ) -> Dict[str, jnp.ndarray]:
    if shardings is None:
        return cache
    return {n: _constrain(a, pool_entry_sharding(shardings, n))
            for n, a in cache.items()}


def init_paged_kv_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                       dtype=None, quant=None) -> Dict[str, jnp.ndarray]:
    """Paged KV storage for the serving engine (serve/pages.py): the
    batch/slot axis of ``init_kv_cache`` becomes a PHYSICAL PAGE axis —
    (L, n_pages, page, C) for the packed layout, (L, n_pages, H, page, D)
    for heads. A slot's logical sequence is the concatenation of the
    pages its (host-side) page table maps, so HBM is sized by pages in
    use, not slots*block_size, and pages holding a shared prompt prefix
    appear in many tables while existing once.

    ``quant`` (a quant.QuantConfig with ``kv_dtype`` set) stores the
    pages in int8/fp8 and adds ``ks``/``vs`` scale arrays indexed by
    the same (layer, page, offset) coordinates — halving bytes/page
    (the admission-capacity doubler) at the cost of tiny per-row scale
    metadata. The paged programs derive the quant mode from the dict
    itself (quant.kv.pool_quant_mode), so their traced signatures
    never change."""
    if quant is not None and quant.kv_enabled:
        from ..quant.kv import init_scales, kv_store_dtype
        dt = kv_store_dtype(quant.kv_dtype)
        if cfg.decode_cache_layout == "packed":
            shape = (cfg.n_layer, n_pages, page_size, cfg.n_embd)
        else:
            shape = (cfg.n_layer, n_pages, cfg.n_head, page_size,
                     cfg.head_dim)
        pool = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        pool.update(init_scales(cfg, n_pages, page_size,
                                quant.granularity))
        return pool
    dt = dtype or _dtype(cfg.dtype)
    if cfg.decode_cache_layout == "packed":
        shape = (cfg.n_layer, n_pages, page_size, cfg.n_embd)
    else:
        shape = (cfg.n_layer, n_pages, cfg.n_head, page_size, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_page_size(cfg: ModelConfig, cache: Dict[str, jnp.ndarray]) -> int:
    """Page length of a paged pool — layout-dependent axis, one accessor
    (the paged decode/prefill/verify programs derive it from the arrays
    they are handed, never from config)."""
    return int(cache["k"].shape[
        2 if cfg.decode_cache_layout == "packed" else 3])


def _gather_pages(pool: jnp.ndarray, layer_idx, tables: jnp.ndarray,
                  packed: bool, n_head: int, scales=None,
                  cd=None) -> jnp.ndarray:
    """Assemble per-slot logical K or V from layer ``layer_idx`` of the
    stacked page pool, addressed IN PLACE: one gather whose index
    carries the layer, so only the table's pages are read and no layer
    of the pool is sliced out first.

    pool: (L, N, page, C) packed or (L, N, H, page, D) heads; tables:
    (B, max_pages) int32 physical-page ids (unmapped entries clamp to 0
    — the positions they cover are beyond every query's mask, so the
    garbage rows get exactly zero softmax weight). Returns the
    (B, H, max_pages*page, D) logical view the attention cores consume.
    This materialized gather streams the same bytes per step as the old
    contiguous (B, S, ...) slot read; the Pallas fast path
    (ops/paged_pallas.py) is the route that skips unmapped pages.

    ``scales`` (a quantized pool's stacked ``ks``/``vs`` array, indexed
    the same way) dequantizes the gathered view to ``cd`` right here —
    the XLA half of the in-kernel dequant contract: every route reads
    quantized pages natively and multiplies scales at the gather, never
    materializing a full-precision pool."""
    g = pool[layer_idx, tables]
    if scales is not None:
        from ..quant.kv import dequant_gathered
        g = dequant_gathered(g, scales[layer_idx, tables], packed, n_head,
                             cd)
    if packed:
        B, mp, psz, C = g.shape
        return _split_heads(g.reshape(B, mp * psz, C), n_head)
    B, mp, H, psz, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, H, mp * psz, D)


@jax.named_scope("kv_scatter")
def _scatter_kv(cc: Dict[str, jnp.ndarray], layer_idx, phys, woff,
                k_m: jnp.ndarray, v_m: jnp.ndarray, packed: bool,
                n_head: int) -> Dict[str, jnp.ndarray]:
    """Scatter merged fresh K/V rows into one layer of the paged pool
    at (phys, woff) — ONE write discipline for the decode / verify /
    prefill programs, both layouts, quantized or not.

    ``k_m``/``v_m`` carry shape ``phys.shape + (C,)``; out-of-range
    ``woff`` entries (inactive slots, padding, past-``limit``
    positions) route to mode='drop' exactly as before. On a quantized
    pool (``ks`` present) the rows quantize-on-write
    (quant.kv.quantize_rows) and their scales land at the SAME
    coordinates in the ``ks``/``vs`` arrays with the same drop
    routing — a dropped row drops its scale with it."""
    from ..quant.kv import pool_quant_mode, quantize_rows
    kv_dtype, gran = pool_quant_mode(cc)
    ck, cv = cc["k"], cc["v"]
    H = n_head
    if kv_dtype is None:
        if packed:
            ck = ck.at[layer_idx, phys, woff, :].set(
                k_m.astype(ck.dtype), mode="drop")
            cv = cv.at[layer_idx, phys, woff, :].set(
                v_m.astype(cv.dtype), mode="drop")
        else:
            shp = phys.shape + (H, k_m.shape[-1] // H)
            ck = ck.at[layer_idx, phys, :, woff, :].set(
                k_m.reshape(shp).astype(ck.dtype), mode="drop")
            cv = cv.at[layer_idx, phys, :, woff, :].set(
                v_m.reshape(shp).astype(cv.dtype), mode="drop")
        return {**cc, "k": ck, "v": cv}
    kq, ksc = quantize_rows(k_m, kv_dtype, H, gran)
    vq, vsc = quantize_rows(v_m, kv_dtype, H, gran)
    cks, cvs = cc["ks"], cc["vs"]
    if packed:
        ck = ck.at[layer_idx, phys, woff, :].set(
            kq.astype(ck.dtype), mode="drop")
        cv = cv.at[layer_idx, phys, woff, :].set(
            vq.astype(cv.dtype), mode="drop")
        if gran == "head":
            cks = cks.at[layer_idx, phys, woff, :].set(
                ksc.astype(cks.dtype), mode="drop")
            cvs = cvs.at[layer_idx, phys, woff, :].set(
                vsc.astype(cvs.dtype), mode="drop")
        else:
            cks = cks.at[layer_idx, phys, woff].set(
                ksc.astype(cks.dtype), mode="drop")
            cvs = cvs.at[layer_idx, phys, woff].set(
                vsc.astype(cvs.dtype), mode="drop")
    else:
        shp = phys.shape + (H, k_m.shape[-1] // H)
        ck = ck.at[layer_idx, phys, :, woff, :].set(
            kq.reshape(shp).astype(ck.dtype), mode="drop")
        cv = cv.at[layer_idx, phys, :, woff, :].set(
            vq.reshape(shp).astype(cv.dtype), mode="drop")
        if gran == "head":
            cks = cks.at[layer_idx, phys, :, woff].set(
                ksc.astype(cks.dtype), mode="drop")
            cvs = cvs.at[layer_idx, phys, :, woff].set(
                vsc.astype(cvs.dtype), mode="drop")
        else:
            cks = cks.at[layer_idx, phys, woff].set(
                ksc.astype(cks.dtype), mode="drop")
            cvs = cvs.at[layer_idx, phys, woff].set(
                vsc.astype(cvs.dtype), mode="drop")
    return {**cc, "k": ck, "v": cv, "ks": cks, "vs": cvs}


@jax.named_scope("kv_gather")
def _gather_kv(cc: Dict[str, jnp.ndarray], layer_idx, tables,
               packed: bool, n_head: int, cd):
    """Per-layer logical K/V views through ``_gather_pages``, with the
    stacked scales threaded for quantized pools (dequant at the gather —
    the XLA fallback's half of the in-kernel dequant contract)."""
    return tuple(_gather_pages(cc[n], layer_idx, tables, packed, n_head,
                               scales=cc.get(n + "s"), cd=cd)
                 for n in ("k", "v"))


def _serve_kernel_mesh(shardings):
    """The >1-device serve mesh behind a ServeShardings plan, or None
    when the engine is effectively single-device — the static fact the
    paged kernel branches switch on to pick the bare ``pallas_call``
    vs its ``shard_map`` wrapper (shardings ride the jit STATIC args,
    so this resolves at trace time, one program per plan)."""
    if shardings is None:
        return None
    mesh = shardings.cache.mesh
    return mesh if mesh.size > 1 else None


@jax.named_scope("attn")
def _kernel_walk(cache: Dict[str, jnp.ndarray], tables, pos_eff, mesh):
    """The paged kernel's walk of this step's tables and positions
    (``ops.paged_pallas.window_walk``), built ONCE outside the layer
    scan: every layer walks the same pages. None on a >1 mesh, where
    each shard builds its own inside the ``shard_map``."""
    if mesh is not None:
        return None
    from ..ops.paged_pallas import window_walk
    k = cache["k"]
    return window_walk(tables, pos_eff, k.shape[2],
                       k.shape[3] * k.dtype.itemsize, 2)


@jax.named_scope("attn")
def _paged_window_attn(q_w, k_w, v_w, cc, layer_idx, tables, pos_eff,
                       n_head, mesh, walk):
    """One layer of windowed paged attention through the unified Pallas
    kernel family (ops/paged_pallas.py): the bare kernel on a single
    device, the ``shard_map`` wrapper on a >1 (data, model) mesh. Both
    take the stacked pool ``cc`` whole and read layer ``layer_idx`` of
    it in place, on the step's ``_kernel_walk``. All (B, W, C) in,
    (B, W, C) out, attending STALE pool + causal fresh window — callers
    scatter the window rows afterwards."""
    from ..ops import paged_pallas
    kw = dict(n_head=n_head, layer=layer_idx, k_scales=cc.get("ks"),
              v_scales=cc.get("vs"))
    if mesh is not None:
        return paged_pallas.sharded_paged_window_attention(
            q_w, k_w, v_w, cc["k"], cc["v"], tables, pos_eff, mesh=mesh,
            **kw)
    return paged_pallas.paged_window_attention(
        q_w, k_w, v_w, cc["k"], cc["v"], tables, pos_eff, walk=walk, **kw)


def decode_step_paged(params: Params, idx_t: jnp.ndarray, pos: jnp.ndarray,
                      active: jnp.ndarray, tables: jnp.ndarray,
                      cache: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
                      use_pallas: bool = False, shardings=None
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``decode_step_multi`` over a PAGED pool: per-slot positions are
    logical, and each slot's K/V is gathered through its page table.

    idx_t/pos: (B,) tokens and logical positions; active: (B,) bool;
    tables: (B, max_pages) int32; cache: ``init_paged_kv_pool`` arrays.
    The fresh K/V row for slot b lands at physical page
    ``tables[b, pos//page]``, offset ``pos % page``. INACTIVE rows run
    at position 0 and their writes are routed off the page axis
    (mode='drop'): a released slot's stale table may reference pages
    now owned by another request, so the contiguous pool's
    "next occupant overwrites before attending" invariant does NOT
    carry over — dropping is correctness, not tidiness. Per-row math is
    ``decode_step_multi``'s exactly (the gathered view holds the same
    values at the same logical offsets), which is what keeps the paged
    engine's greedy stream token-identical to offline ``generate``.
    """
    cd = _dtype(cfg.dtype)
    B = idx_t.shape[0]
    packed = cfg.decode_cache_layout == "packed"
    psz = paged_page_size(cfg, cache)
    mp = tables.shape[1]
    H = cfg.n_head
    bidx = jnp.arange(B)
    pos_eff = jnp.where(active, pos, 0)
    # eager calls assert; the engine bounds pos host-side at admission
    check_in_bounds(pos_eff, 1, mp * psz, what="paged decode write")
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[idx_t] + params["wpe"].astype(cd)[pos_eff]
    x = x[:, None, :]  # (B, 1, C)
    phys = tables[bidx, jnp.minimum(pos_eff // psz, mp - 1)]
    woff = jnp.where(active, pos_eff % psz, psz)   # inactive -> dropped

    quantized = "ks" in cache
    mesh = _serve_kernel_mesh(shardings)
    use_pallas = packed and use_pallas
    walk = (_kernel_walk(cache, tables, pos_eff, mesh) if use_pallas
            else None)

    def body(carry, inputs):
        h_in, cc = carry
        lp, layer_idx = inputs
        q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)
        if use_pallas:
            # kernel attends the STALE pages + fresh column (bit-
            # equivalent to write-then-attend); write lands after.
            # Quantized pools hand the kernel their stacked scales
            # (dequant inside the accumulation loop) and a fresh
            # column pre-quantize-dequantized to the exact value
            # the scatter below stores. On a >1 serve mesh the
            # shard_map wrapper runs the same kernel per chip.
            k_new, v_new = k_m, v_m                      # (B, 1, C)
            if quantized:
                from ..quant.kv import (fake_quantize_rows,
                                        pool_quant_mode)
                kv_dtype, gran = pool_quant_mode(cc)
                k_new = fake_quantize_rows(k_new, kv_dtype, H,
                                           gran).astype(cd)
                v_new = fake_quantize_rows(v_new, kv_dtype, H,
                                           gran).astype(cd)
            attn_merged = _paged_window_attn(
                q_m, k_new, v_new, cc, layer_idx, tables, pos_eff, H,
                mesh, walk)
            cc = _scatter_kv(cc, layer_idx, phys, woff,
                             k_m[:, 0, :], v_m[:, 0, :], packed, H)
        else:
            cc = _scatter_kv(cc, layer_idx, phys, woff,
                             k_m[:, 0, :], v_m[:, 0, :], packed, H)
            k_all, v_all = _gather_kv(cc, layer_idx, tables, packed,
                                      H, cd)
            attn_merged = _merge_heads(cached_attention(
                _split_heads(q_m, H), k_all, v_all, pos_eff))
        return (_cached_block_tail(h_in, attn_merged, lp, cfg, cd),
                cc), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (x, cc), _ = jax.lax.scan(
            body, (x, dict(cache)), (params["blocks"], layer_ids))
    else:
        carry = (x, dict(cache))
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        x, cc = carry
    return _decode_head(x, params, cfg, cd), cc


def decode_window_paged(params: Params, tok: jnp.ndarray, pos: jnp.ndarray,
                        active: jnp.ndarray, budget: jnp.ndarray,
                        eos: jnp.ndarray, tables: jnp.ndarray,
                        cache: Dict[str, jnp.ndarray], rngs: jnp.ndarray,
                        cfg: ModelConfig, *, sample_fn, length: int,
                        use_pallas: bool = False, shardings=None):
    """``length`` decode steps over the paged pool in ONE traced program
    — the device-resident loop the async serving engine dispatches once
    per WINDOW instead of once per token (the lax.scan analogue of the
    training loop's steps-per-dispatch amortization).

    tok/pos/active: the per-slot step state ``decode_step_paged`` takes;
    budget: (B,) int32 tokens each slot may still emit; eos: (B,) int32
    per-slot stop token (< 0 = disabled); rngs: (B, key) sampling
    streams; ``sample_fn(rngs, logits, live) -> (tokens, new_rngs)`` is
    the caller's sampler (injected so this module does not depend on
    sample.generate; ``live`` is the step's ``active`` mask, so that a
    dead slot's stale parameters cost nothing). Per step every ACTIVE
    slot decodes exactly as a standalone ``decode_step_paged`` + sample
    would — per-row math, masking and RNG stream advance are identical,
    which is what keeps a windowed greedy stream byte-identical to the
    step-at-a-time one —
    then the slot's budget decrements and its on-device active flag
    drops when the budget hits zero or the sampled token == eos. A slot
    that finishes mid-window therefore IDLES inside the window (writes
    dropped, emissions masked off) instead of forcing an early exit: the
    window width is static, so partial windows never compile a second
    program. The window's last real write position is bounded host-side
    by the caller (pos + budget <= logical capacity — the admission
    cap's invariant).

    Returns ``(toks, emitted, tok, pos, active, budget, cache, rngs)``:
    toks/emitted are (length, B) — the sampled token and whether the
    slot was live at each step (``emitted[:, b]`` is a prefix mask: a
    slot deactivates once and never re-arms inside a window); the rest
    is the advanced step state the caller feeds to the NEXT window
    (donated end to end by the engine's jit wrapper).

    ``shardings`` (parallel.mesh.ServeShardings, None = unsharded)
    pins the scan carry on a serving mesh: the page pool to its
    (data, model) PartitionSpec and the step state + per-step token
    outputs to replication, so window-to-window donation aliases and
    the engine's token-block fetch stays a local read (see
    ``_constrain``).
    """
    rep = None if shardings is None else shardings.rep

    def body(carry, _):
        tok, pos, active, budget, cache, rngs = carry
        logits, cache = decode_step_paged(
            params, tok, pos, active, tables, cache, cfg,
            use_pallas=use_pallas, shardings=shardings)
        nxt, rngs = sample_fn(rngs, logits, active)
        nxt = jnp.where(active, nxt, 0)
        emitted = active
        budget = jnp.where(active, budget - 1, budget)
        hit_eos = active & (eos >= 0) & (nxt == eos)
        pos = jnp.where(emitted, pos + 1, pos)
        tok = jnp.where(emitted, nxt, tok)
        active = active & (budget > 0) & ~hit_eos
        cache = _constrain_cache(cache, shardings)
        tok, pos, active, budget, rngs, nxt, emitted = (
            _constrain(a, rep) for a in (tok, pos, active, budget, rngs,
                                         nxt, emitted))
        return (tok, pos, active, budget, cache, rngs), (nxt, emitted)

    carry = (tok, pos, active, budget, cache, rngs)
    (tok, pos, active, budget, cache, rngs), (toks, emitted) = jax.lax.scan(
        body, carry, None, length=length)
    return toks, emitted, tok, pos, active, budget, cache, rngs


def mixed_window_paged(params: Params, tok: jnp.ndarray, pos: jnp.ndarray,
                       active: jnp.ndarray, budget: jnp.ndarray,
                       eos: jnp.ndarray, pf_left: jnp.ndarray,
                       pf_off: jnp.ndarray, pf_limit: jnp.ndarray,
                       pf_toks: jnp.ndarray, tables: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], rngs: jnp.ndarray,
                       cfg: ModelConfig, *, sample_fn, length: int,
                       shardings=None, use_kernel: bool = False):
    """``decode_window_paged`` with chunked prefill folded INTO the
    window — the Sarathi-style mixed step the continuous-window engine
    dispatches when an admission landed at the window boundary: newly
    admitted slots prefill their prompt's uncached tail chunk-by-chunk
    while live slots decode, all inside ONE ``length``-step lax.scan,
    so an admission no longer costs a window break (the blocked-k=1
    fallback that used to erase the dispatch amortization exactly when
    traffic peaks).

    Per-slot phase mask: at scan step ``t`` a slot is PREFILLING while
    ``t < pf_left[b]`` (``pf_left``: chunks this window must write for
    the slot; 0 = plain decode) and DECODING afterwards. Each step runs
    one ``verify_step_paged`` forward over a (B, W) token window
    (W = the prefill chunk width, ``pf_toks.shape[-1]``):

    - a prefilling slot's row is its next chunk ``pf_toks[t, b]``,
      written through its page table at absolute positions
      ``pf_off + t*W + j`` (positions >= ``pf_limit`` — the true prompt
      length — are scatter-DROPPED, exactly ``prefill_chunk_paged``'s
      padding discipline); its sampled token is discarded and its rng
      stream does NOT advance, so the first decoded token still uses
      split 0 of the slot's admission-fresh key (stream parity with the
      blocked path, where decode starts the admission step);
    - a decoding slot's row is its current token at window position 0
      (rows past 0 are dropped padding) at its frontier position — the
      same write-then-attend row math as ``decode_step_paged`` via the
      pinned verify<->decode per-row equivalence — and its sample /
      budget / eos bookkeeping is ``decode_window_paged``'s exactly.

    A slot whose prefill exhausts mid-window (``t == pf_left - 1``
    consumed its last chunk) flips to decode at the NEXT scan step with
    no transition math: ``pos``/``tok`` were primed at admission to the
    decode frontier (P-1, last prompt token) and stay untouched while
    prefilling. The caller sizes ``pf_left <= length`` per window and
    carries longer prefills across windows host-side (consumption is
    deterministic, so no device fetch is needed to know the cursor).

    Returns the same ``(toks, emitted, tok, pos, active, budget, cache,
    rngs)`` tuple as ``decode_window_paged`` — ``emitted[:, b]`` is now
    False during b's prefill steps and True from its first decode step
    until deactivation (a suffix-start run, not a prefix: the engine
    commits tokens by mask, not by count).
    """
    rep = None if shardings is None else shardings.rep
    steps = jnp.arange(length, dtype=jnp.int32)
    W = pf_toks.shape[-1]

    def body(carry, xs):
        tok, pos, active, budget, cache, rngs = carry
        chunk_toks, t = xs                       # (B, W), scalar step
        prefilling = active & (t < pf_left)
        cur = pf_off + t * W
        n_tok = jnp.where(prefilling, jnp.clip(pf_limit - cur, 1, W), 1)
        base = jnp.where(prefilling, cur, pos)
        col0 = jnp.zeros_like(chunk_toks).at[:, 0].set(tok)
        window = jnp.where(prefilling[:, None], chunk_toks, col0)
        logits, cache = verify_step_paged(
            params, window, base, n_tok - 1, active, tables, cache, cfg,
            shardings=shardings, logits_rows=1, use_kernel=use_kernel)
        decoding = active & ~prefilling
        nxt, new_rngs = sample_fn(rngs, logits[:, 0, :], decoding)
        rngs = jnp.where(decoding[:, None], new_rngs, rngs)
        nxt = jnp.where(decoding, nxt, 0)
        emitted = decoding
        budget = jnp.where(decoding, budget - 1, budget)
        hit_eos = decoding & (eos >= 0) & (nxt == eos)
        pos = jnp.where(decoding, pos + 1, pos)
        tok = jnp.where(decoding, nxt, tok)
        active = active & ~(decoding & ((budget <= 0) | hit_eos))
        cache = _constrain_cache(cache, shardings)
        tok, pos, active, budget, rngs, nxt, emitted = (
            _constrain(a, rep) for a in (tok, pos, active, budget, rngs,
                                         nxt, emitted))
        return (tok, pos, active, budget, cache, rngs), (nxt, emitted)

    carry = (tok, pos, active, budget, cache, rngs)
    (tok, pos, active, budget, cache, rngs), (toks, emitted) = jax.lax.scan(
        body, carry, (pf_toks, steps), length=length)
    return toks, emitted, tok, pos, active, budget, cache, rngs


def verify_step_paged(params: Params, window: jnp.ndarray, pos: jnp.ndarray,
                      n_valid: jnp.ndarray, active: jnp.ndarray,
                      tables: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                      cfg: ModelConfig, *, shardings=None,
                      logits_rows: Optional[int] = None,
                      use_kernel: bool = False
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``verify_step_multi`` over a paged pool: the speculative window's
    K/V scatters through each slot's page table and the whole drafted
    window attends the gathered logical view. ``shardings`` pins the
    pool layout per layer on a serving mesh (see ``_constrain``).

    Window token j of slot b sits at logical position pos[b]+j, physical
    page ``tables[b, (pos+j)//page]`` offset ``(pos+j) % page``. Padding
    positions (j > n_valid) AND every position of inactive rows route
    their page offset to ``page`` — out of bounds, where the scatter
    drops the update (a stale table must never be written through; see
    ``decode_step_paged``). Per-row logits are ``verify_step_multi``'s
    exactly, so speculative greedy parity survives paging unchanged.
    ``logits_rows`` limits the final layernorm + vocab head to the
    first that-many window rows (the mixed-window caller samples only
    row 0 — projecting all W rows to the vocab every scan step would
    multiply the head cost by the chunk width for nothing); None keeps
    the full (B, W, V) output the speculative verifier needs.

    ``use_kernel`` routes the attention core through the unified paged
    Pallas kernel (``paged_window_attention`` / its shard_map wrapper):
    the kernel attends the STALE pool (positions < pos) plus the causal
    fresh window in-launch, then the scatter lands AFTER — equivalent to
    this function's scatter-then-gather because valid query rows only
    ever attend valid fresh rows (``valid`` is a prefix mask) and the
    quantized fresh rows are fake-quantized to exactly what the scatter
    stores. Padding rows (j > n_valid) and inactive rows produce
    garbage either way and are discarded by callers (the diagonal
    self-attention keeps them NaN-free). Callers gate on
    ``ops.paged_pallas.mixed_step_kernel_ok`` + packed layout.
    """
    cd = _dtype(cfg.dtype)
    B, W = window.shape
    packed = cfg.decode_cache_layout == "packed"
    psz = paged_page_size(cfg, cache)
    mp = tables.shape[1]
    H = cfg.n_head
    Smax = mp * psz
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]      # (1, W)
    pos_eff = jnp.where(active, pos, 0)
    m_eff = jnp.where(active, n_valid, 0)
    abs_pos = pos_eff[:, None] + offs                   # (B, W)
    # wpe gather clamps padding rows (real window positions are bounded
    # host-side: pos + n_valid <= block_size - 1)
    with jax.named_scope("embed"):
        x = (params["wte"].astype(cd)[window]
             + params["wpe"].astype(cd)[jnp.minimum(abs_pos,
                                                    cfg.block_size - 1)])
    valid = (offs <= m_eff[:, None]) & active[:, None]
    lpage = jnp.minimum(abs_pos // psz, mp - 1)
    phys = jnp.take_along_axis(tables, lpage, axis=1)   # (B, W)
    woff = jnp.where(valid & (abs_pos < Smax), abs_pos % psz, psz)
    quantized = "ks" in cache
    mesh = _serve_kernel_mesh(shardings)
    walk = (_kernel_walk(cache, tables, pos_eff, mesh) if use_kernel
            else None)

    def body(carry, inputs):
        h_in, cc = carry
        lp, layer_idx = inputs
        q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)  # (B, W, C)
        if use_kernel:
            # attend stale pool + causal fresh window in-kernel, then
            # scatter (write-then-attend equivalence, see docstring)
            k_w, v_w = k_m, v_m
            if quantized:
                from ..quant.kv import (fake_quantize_rows,
                                        pool_quant_mode)
                kv_dtype, gran = pool_quant_mode(cc)
                k_w = fake_quantize_rows(k_m, kv_dtype, H,
                                         gran).astype(cd)
                v_w = fake_quantize_rows(v_m, kv_dtype, H,
                                         gran).astype(cd)
            attn_merged = _paged_window_attn(
                q_m, k_w, v_w, cc, layer_idx, tables, pos_eff, H, mesh,
                walk)
            cc = _scatter_kv(cc, layer_idx, phys, woff, k_m, v_m,
                             packed, H)
        else:
            # scatter values laid out phys.shape-major: advanced
            # indices (phys, woff) broadcast to (B, W) and land first
            cc = _scatter_kv(cc, layer_idx, phys, woff, k_m, v_m,
                             packed, H)
            q_h = _split_heads(q_m, H)
            k_all, v_all = _gather_kv(cc, layer_idx, tables, packed, H,
                                      cd)
            attn_merged = _merge_heads(windowed_cached_attention(
                q_h, k_all, v_all, pos_eff))
        cc = _constrain_cache(cc, shardings)
        return (_cached_block_tail(h_in, attn_merged, lp, cfg, cd),
                cc), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (x, cc), _ = jax.lax.scan(
            body, (x, dict(cache)), (params["blocks"], layer_ids))
    else:
        carry = (x, dict(cache))
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        x, cc = carry
    if logits_rows is not None:
        x = x[:, :logits_rows, :]
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"],
                    cfg.layernorm_eps)
    head = (params["wte"].astype(cd).T if cfg.tied_head
            else params["lm_head"].astype(cd))
    return (x @ head).astype(jnp.float32), cc


def prefill_chunk_paged(params: Params, idx: jnp.ndarray,
                        offset: jnp.ndarray, limit: jnp.ndarray,
                        table_row: jnp.ndarray,
                        cache: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
                        shardings=None) -> Dict[str, jnp.ndarray]:
    """Chunked prefill of ONE slot's prompt through its page table.
    ``shardings`` pins the pool layout per layer on a serving mesh
    (see ``_constrain``).

    idx: (1, Pc) chunk of the prompt; offset: scalar int32 first
    absolute position (with a prefix-cache hit the first chunk starts at
    the first UNCACHED token, any position — no chunk-alignment
    requirement); limit: scalar int32 true prompt length — writes at
    positions >= limit are DROPPED. Dropping padding is load-bearing
    here where the contiguous pool merely tolerated it: a padded final
    chunk's tail positions can fall past the slot's reserved pages,
    where the clamped table entry (0) references a page owned by a
    DIFFERENT request. Queries attend the gathered logical view masked
    to k <= offset+i (``windowed_cached_attention`` — write-then-attend
    across chunks, exactly ``prefill_chunk_into_slot``'s discipline);
    padded queries' outputs are garbage and discarded.
    """
    cd = _dtype(cfg.dtype)
    _, Pc = idx.shape
    packed = cfg.decode_cache_layout == "packed"
    psz = paged_page_size(cfg, cache)
    mp = table_row.shape[0]
    H = cfg.n_head
    Smax = mp * psz
    positions = offset + jnp.arange(Pc, dtype=jnp.int32)   # (Pc,)
    # eager calls assert; the engine bounds [offset, limit) at admission
    check_in_bounds(offset, 1, cfg.block_size, what="paged prefill chunk")
    with jax.named_scope("embed"):
        x = (params["wte"].astype(cd)[idx]
             + params["wpe"].astype(cd)[jnp.minimum(positions,
                                                    cfg.block_size - 1)][None])
    lpage = jnp.minimum(positions // psz, mp - 1)
    phys = table_row[lpage]                                # (Pc,)
    woff = jnp.where((positions < limit) & (positions < Smax),
                     positions % psz, psz)
    base = jnp.reshape(offset, (1,))

    def body(carry, inputs):
        h_in, cc = carry
        lp, layer_idx = inputs
        q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)  # (1, Pc, C)
        cc = _scatter_kv(cc, layer_idx, phys, woff, k_m[0], v_m[0],
                         packed, H)
        k_all, v_all = _gather_kv(cc, layer_idx, table_row[None],
                                  packed, H, cd)
        attn = windowed_cached_attention(_split_heads(q_m, H), k_all,
                                         v_all, base)
        cc = _constrain_cache(cc, shardings)
        return (_cached_block_tail(h_in, _merge_heads(attn), lp, cfg, cd),
                cc), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (_, cc), _ = jax.lax.scan(
            body, (x, dict(cache)), (params["blocks"], layer_ids))
    else:
        carry = (x, dict(cache))
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        _, cc = carry
    return cc


def prefill_chunk_into_slot(params: Params, idx: jnp.ndarray,
                            offset: jnp.ndarray, slot: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cfg: ModelConfig
                            ) -> Dict[str, jnp.ndarray]:
    """Chunked prefill into ONE slot of a pooled multi-slot KV cache.

    idx: (1, Pc) int32 — a chunk of the prompt; offset: scalar int32 —
    the chunk's first absolute position; slot: scalar int32 — the pool
    slot. Writes the chunk's K/V rows at cache[:, slot, ..,
    offset:offset+Pc, ..] and runs the block stack with each query at
    position offset+i attending the slot's whole cache buffer masked to
    j <= offset+i (write-then-attend: chunk 2's queries see chunk 1's
    K/V through the buffer, so a long prompt prefills in fixed-size
    chunks under ONE compiled program regardless of prompt length —
    the serving engine's admission path). Positions beyond the true
    prompt inside a right-padded final chunk hold padding-derived K/V;
    same invariant as ``prefill``: decode overwrites position p before
    attending it, and the per-query mask hides everything later.
    Masked-out buffer entries get exactly zero softmax weight (f32
    underflow of NEG_INF), so the math per valid row is the
    ``full_causal_attention`` einsum's.
    """
    cd = _dtype(cfg.dtype)
    _, Pc = idx.shape
    H, S = cfg.n_head, cache["k"].shape[cache_seq_axis(cfg)]
    # THE site of PR 1's clamp bug: a padded final chunk whose offset
    # pushes past the buffer would silently overwrite chunk 1's K/V.
    # Eager calls assert here; the jitted serving path (offset traced)
    # is bounded host-side at admission (Engine._admit) and by
    # EngineConfig.chunk's divisibility invariant.
    check_in_bounds(offset, Pc, S, what="prefill chunk write")
    check_in_bounds(slot, 1, cache["k"].shape[1], what="prefill slot index")
    scale = cfg.head_dim ** -0.5
    with jax.named_scope("embed"):
        x = (params["wte"].astype(cd)[idx]
             + jax.lax.dynamic_slice_in_dim(params["wpe"].astype(cd), offset,
                                            Pc, axis=0))
    packed = cfg.decode_cache_layout == "packed"
    from ..ops.attention import NEG_INF

    def body(carry, inputs):
        h_in, ck, cv = carry
        lp, layer_idx = inputs
        q_m, k_m, v_m = _cached_qkv_merged(h_in, lp, cfg, cd)  # (1, Pc, C)
        zero = jnp.int32(0)
        if packed:
            start = (layer_idx, slot, offset, zero)
            ck = jax.lax.dynamic_update_slice(
                ck, k_m[None].astype(ck.dtype), start)
            cv = jax.lax.dynamic_update_slice(
                cv, v_m[None].astype(cv.dtype), start)
            k_slot = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(ck, layer_idx, 0, False),
                slot, 0, False)          # (S, C)
            v_slot = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(cv, layer_idx, 0, False),
                slot, 0, False)
            k_h = _split_heads(k_slot[None].astype(cd), H)  # (1, H, S, D)
            v_h = _split_heads(v_slot[None].astype(cd), H)
        else:
            k = _split_heads(k_m, H)                        # (1, H, Pc, D)
            v = _split_heads(v_m, H)
            start = (layer_idx, slot, zero, offset, zero)
            ck = jax.lax.dynamic_update_slice(
                ck, k[None].astype(ck.dtype), start)
            cv = jax.lax.dynamic_update_slice(
                cv, v[None].astype(cv.dtype), start)
            k_h = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(ck, layer_idx, 0, False),
                slot, 0, False)[None].astype(cd)            # (1, H, S, D)
            v_h = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(cv, layer_idx, 0, False),
                slot, 0, False)[None].astype(cd)
        q = _split_heads(q_m, H)                            # (1, H, Pc, D)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_h,
                            preferred_element_type=jnp.float32) * scale
        qpos = jax.lax.broadcasted_iota(jnp.int32, (Pc, S), 0) + offset
        kpos = jax.lax.broadcasted_iota(jnp.int32, (Pc, S), 1)
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
        weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        attn = jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v_h.dtype), v_h)
        return (_cached_block_tail(h_in, _merge_heads(attn), lp, cfg, cd),
                ck, cv), None

    if cfg.use_layer_scan:
        layer_ids = jnp.arange(cfg.n_layer)
        (_, ck, cv), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], layer_ids))
    else:
        carry = (x, cache["k"], cache["v"])
        for i in range(cfg.n_layer):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
        _, ck, cv = carry
    return {"k": ck, "v": cv}
