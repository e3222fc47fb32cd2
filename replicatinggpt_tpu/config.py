"""Configuration system for the TPU-native GPT framework.

Unifies the reference's three scattered config surfaces (module-level globals
in GPT1.py:12-23 and GPT-2.py:6-16, plus the GPTConfig dataclass at
GPT-2.py:81-87) into frozen dataclasses with named presets covering every
configuration the reference can express, and the five BASELINE.json workloads.

Everything is hashable/frozen so configs can be closed over by ``jax.jit`` as
static arguments without retracing surprises.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    """Architecture of a decoder-only pre-LN transformer LM.

    One definition serves both reference flavors (GPT1.py:100-212 and
    GPT-2.py:22-128); they differ only in field values:

    - GPT-1 flavor: untied lm_head (GPT1.py:174), ReLU MLP (GPT1.py:144),
      dropout 0.2.
    - GPT-2 flavor: tied wte/lm_head (GPT-2.py:104), GELU MLP (GPT-2.py:62),
      fused QKV (always fused here; the per-head Python loop of GPT1.py:130
      is a strictly worse formulation on any hardware).
    """

    vocab_size: int = 65
    block_size: int = 256
    n_layer: int = 6
    n_head: int = 6
    n_embd: int = 384
    dropout: float = 0.2          # residual + MLP dropout (GPT1.py:147)
    attn_dropout: float = 0.2     # dropout on attention weights (GPT1.py:117)
    tied_head: bool = True        # GPT-2.py:104 weight tying; False = GPT1.py:174
    activation: str = "gelu"      # 'gelu' (GPT-2.py:62) or 'relu' (GPT1.py:144)
    layernorm_eps: float = 1e-5
    init_std: float = 0.02        # GPT-2 paper init; reference's NANOGPT_SCALE_INIT
                                  # tag (GPT-2.py:31,59) is honored here for real:
                                  # residual projections get std/sqrt(2*n_layer)
    # --- numerics -----------------------------------------------------------
    dtype: str = "bfloat16"       # activation/compute dtype on TPU (MXU-native)
    param_dtype: str = "float32"  # master params stay f32
    # --- execution ----------------------------------------------------------
    attention_impl: str = "auto"  # 'auto' | 'einsum' | 'flash' | 'ring' |
                                  # 'ulysses' (seq-parallel all-to-all)
    remat: bool = False           # jax.checkpoint each block (HBM <-> FLOPs)
    remat_policy: str = "full"    # 'full' (save nothing) | 'dots' (save
                                  # matmul outputs, recompute elementwise:
                                  # jax.checkpoint_policies.dots_saveable) |
                                  # 'dots_no_batch' (…with_no_batch_dims…).
                                  # Measured at 350M B=8 on v5e-16G: 'full'
                                  # wins — see benchmarks/RESULTS.md
                                  # selective-remat table
    loss_chunk: int = 0
    # Rows of the flattened (B*T, V) logits computed per lax.scan step in
    # the training loss head; 0 = the plain one-shot head. Non-zero never
    # materializes the full f32 logits array (models.gpt._chunked_ce_loss)
    # — at GPT-2 vocab that array is the step's largest HBM tenant. With
    # loss_chunk on, forward(targets=...) returns (None, loss): callers
    # that need logits keep the default. Opt-in until a chip A/B sizes
    # the win (ROADMAP A5).
    decode_cache_layout: str = "heads"
    # KV-cache memory layout for decode: 'heads' = (L, B, H, S, D) (the
    # original layout), 'packed' = (L, B, S, C) with heads as static lane
    # slices of the C row. At D=64 the TPU tiles a (S, D)-minor array to
    # 128 lanes, so the heads layout physically streams ~2x the logical
    # cache bytes per decode step — the packed layout stores fully-packed
    # (S, C) rows and reads them through ops/decode_pallas.py's
    # packed_decode_attention kernel (the packed-flash lane-slice trick
    # applied to decode). 'heads' stays the default until a chip A/B of
    # the two layouts says otherwise (ROADMAP A5).
    act_quant: str = "none"
    # W8A8 serving: 'int8' quantizes the ACTIVATION rows feeding the
    # already-int8-quantized weight matmuls of the cached decode paths
    # (per-row symmetric, models.gpt._wmm) so the contraction runs
    # int8 x int8 -> int32. No effect unless the params carry int8
    # kernels (quant/weights.py) — the serve engine sets this from
    # EngineConfig.act_quant; training paths never quantize. 'none'
    # default keeps every existing config byte-identical.
    scan_layers: Optional[bool] = None
    # lax.scan over stacked layer params. None = auto: on TPU, unroll
    # shallow stacks (n_layer <= 16) — measured on v5e, unrolling the
    # 6-layer char-GPT cuts step time 25.9 -> 19.7 ms (+31% throughput)
    # because scan blocks XLA's cross-layer fusion/overlap; scan deep
    # stacks, where compile time and code size dominate. On CPU scan
    # always (unrolling measured strictly worse there: +60% compile AND
    # +28% step time). Params stay stacked (L, ...) either way, so
    # shardings/checkpoints are unaffected.

    # --- family (models/families.py picks the body by this) ---------------
    # Everything below is DATA of an architecture other than GPT-2's; the
    # defaults are GPT-2's, so every older preset is unchanged. The
    # families beside it, 'exaone_moe' (models/exaone_moe.py), 'lfm2_moe'
    # (models/lfm2_moe.py) and 'joyai_llm_flash'
    # (models/joyai_llm_flash.py), are serve-only. What a family alone
    # decides (RMSNorm, QK-norm, which layers rotate, sigmoid scores
    # normalised over the chosen) is its module's, not a field here: no
    # caller can vary it.
    family: str = "gpt"
    # 'gpt' | 'exaone_moe' | 'lfm2_moe' | 'joyai_llm_flash'
    n_kv_head: int = 0            # KV heads (GQA); 0 = n_head
    attn_head_dim: int = 0        # head size; 0 = n_embd // n_head
    rope_theta: float = 10000.0   # rotary base, where the family rotates
    layer_types: Tuple[str, ...] = ()
    # per layer 'sliding_attention' | 'full_attention' | 'conv' (a gated
    # short convolution, lfm2_moe's); () = all full
    sliding_window: int = 0       # token i attends j, i - window < j <= i
    conv_reach: int = 0
    # taps of a conv layer's depthwise causal convolution, and the columns
    # of its state a slot (config.json conv_L_cache)
    mlp_layer_types: Tuple[str, ...] = ()
    # per layer 'dense' | 'sparse'; () = all dense
    intermediate_size: int = 0    # gated (SwiGLU) dense MLP width
    n_experts: int = 0            # routed experts the ROUTER scores
    experts_held: Tuple[int, ...] = ()
    # ids of the routed experts THIS program holds (expert parallelism's
    # share); the layer routes over all n_experts and sums its own
    experts_per_token: int = 0
    moe_intermediate_size: int = 0
    routed_scaling: float = 1.0
    router_norm_eps: float = 0.0
    # added to the chosen scores' sum before they are normalised
    shared_intermediate_size: int = 0   # one shared expert; 0 = none
    # latent attention (MLA, joyai_llm_flash's): the query goes through a
    # rank-q_lora_rank bottleneck, keys and values are expanded from ONE
    # normed kv_lora_rank-wide latent a token, beside a qk_rope_head_dim
    # rotary key that every head shares; a head scores qk_nope_head_dim +
    # qk_rope_head_dim wide and weighs v_head_dim wide. 0 = no latent
    # attention
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def kv_channels(self) -> int:
        """Width of one cached K (or V) row: KV heads x head size."""
        return self.kv_heads * self.head_dim

    @property
    def kv_token_width(self) -> int:
        """Values ONE layer caches for a token: a K and a V row of
        ``kv_channels`` each, or under latent attention one row of the
        latent and the shared rotary key (no head axis), padded with zeros
        to whole 128-lane tiles: what the TPU's HBM tiling holds for it
        anyway, and what the latent kernel's page copies must move."""
        if self.kv_lora_rank:
            return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) \
                * 128
        return 2 * self.kv_channels

    @property
    def paged_layers(self) -> Tuple[int, ...]:
        """Layers whose K/V history lives in pool pages (all of GPT-2's;
        the full-attention layers of a family that has other kinds)."""
        return tuple(i for i in range(self.n_layer)
                     if not (self.is_window_layer(i)
                             or self.is_conv_layer(i)))

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """Layers that keep a bounded ring of K/V a slot instead."""
        return tuple(i for i in range(self.n_layer)
                     if self.is_window_layer(i))

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        """Layers that keep ``conv_reach`` columns of state a slot."""
        return tuple(i for i in range(self.n_layer)
                     if self.is_conv_layer(i))

    def is_window_layer(self, i: int) -> bool:
        return bool(self.layer_types) and \
            self.layer_types[i] == "sliding_attention"

    def is_conv_layer(self, i: int) -> bool:
        return bool(self.layer_types) and self.layer_types[i] == "conv"

    def is_sparse_layer(self, i: int) -> bool:
        return bool(self.mlp_layer_types) and \
            self.mlp_layer_types[i] == "sparse"

    @property
    def use_layer_scan(self) -> bool:
        if self.scan_layers is not None:
            return self.scan_layers
        if self.n_layer > 16:
            return True
        import jax
        return jax.default_backend() != "tpu"

    @property
    def head_dim(self) -> int:
        if self.attn_head_dim:
            return self.attn_head_dim
        assert self.n_embd % self.n_head == 0, (
            f"n_embd={self.n_embd} not divisible by n_head={self.n_head}"
        )
        return self.n_embd // self.n_head

    def validate(self) -> "ModelConfig":
        _ = self.head_dim
        assert self.family in ("gpt", "exaone_moe", "lfm2_moe",
                               "joyai_llm_flash"), self.family
        if self.family == "gpt":
            assert self.activation in ("gelu", "relu"), self.activation
            for name in ("n_kv_head", "attn_head_dim", "layer_types",
                         "sliding_window", "conv_reach", "mlp_layer_types",
                         "intermediate_size", "n_experts", "experts_held",
                         "experts_per_token", "moe_intermediate_size",
                         "router_norm_eps", "shared_intermediate_size",
                         *_LATENT_FIELDS):
                assert not getattr(self, name), (
                    f"{name} is not GPT-2's: models/gpt.py has one head "
                    f"count, learned positions and a dense MLP")
        else:
            self._validate_served_family()
        assert self.attention_impl in ("auto", "einsum", "flash", "ring",
                                       "ulysses")
        assert self.remat_policy in ("full", "dots", "dots_no_batch"), (
            self.remat_policy)
        assert self.act_quant in ("none", "int8"), self.act_quant
        return self

    def _validate_served_family(self) -> None:
        """What models/exaone_moe.py, models/lfm2_moe.py and
        models/joyai_llm_flash.py compute, and nothing near it."""
        L = self.n_layer
        assert self.activation == "swiglu", self.activation
        if self.family == "joyai_llm_flash":
            self._validate_latent_attention()
        else:
            assert not any(getattr(self, n) for n in _LATENT_FIELDS), (
                f"{', '.join(_LATENT_FIELDS)}: latent attention is "
                f"joyai_llm_flash's")
            assert self.n_head % self.kv_heads == 0, (
                f"{self.n_head} query heads do not group over "
                f"{self.kv_heads} KV heads")
            assert self.head_dim % 2 == 0, "rotate-half needs an even head"
        assert len(self.mlp_layer_types) == L and all(
            t in ("dense", "sparse") for t in self.mlp_layer_types), (
            f"mlp_layer_types={self.mlp_layer_types}")
        kinds = {"exaone_moe": ("full_attention", "sliding_attention"),
                 "lfm2_moe": ("full_attention", "conv")}.get(self.family)
        if kinds:
            assert len(self.layer_types) == L and all(
                t in kinds for t in self.layer_types), (
                f"layer_types={self.layer_types}")
        if self.family == "exaone_moe":
            assert not self.tied_head, "the family's head is untied"
            assert not self.conv_reach and not self.router_norm_eps, (
                "conv_reach is lfm2_moe's, router_norm_eps lfm2_moe's and "
                "joyai_llm_flash's")
            if self.window_layers:
                assert self.sliding_window > 0, "window layers need a window"
        elif self.family == "joyai_llm_flash":
            assert not self.tied_head, "the family's head is untied"
            assert not self.conv_reach and not self.sliding_window, (
                "conv_reach, sliding_window: every layer of the family is "
                "latent attention over the whole context")
            self._validate_dense_lead()
        else:
            assert self.tied_head, (
                "the family's head is tied to the embedding")
            assert self.conv_reach >= 2, (
                f"conv_reach={self.conv_reach}: a conv layer reaches at "
                f"least one column back")
            assert not self.sliding_window, (
                "sliding_window: the family has no window layer")
            assert not self.shared_intermediate_size, (
                "shared_intermediate_size: the family has no shared expert")
            self._validate_dense_lead()
        if "dense" in self.mlp_layer_types:
            assert self.intermediate_size > 0
        if "sparse" in self.mlp_layer_types:
            assert 0 < self.experts_per_token <= self.n_experts
            assert self.moe_intermediate_size > 0
            held = self.experts_held
            assert held and len(set(held)) == len(held) and all(
                0 <= e < self.n_experts for e in held), (
                f"experts_held={held} of {self.n_experts}")
        assert self.dropout == 0.0 and self.attn_dropout == 0.0, (
            "serve-only family: no dropout")
        assert self.decode_cache_layout == "packed", (
            "the family's pool is packed (KV heads as lane slices)")
        assert self.act_quant == "none", "no W8A8 path for this family"

    def _validate_dense_lead(self) -> None:
        n_dense = self.mlp_layer_types.count("dense")
        assert self.mlp_layer_types[:n_dense] == ("dense",) * n_dense, (
            f"mlp_layer_types={self.mlp_layer_types}: the dense layers "
            f"lead (num_dense_layers, first_k_dense_replace)")

    def _validate_latent_attention(self) -> None:
        for name in _LATENT_FIELDS:
            assert getattr(self, name) > 0, (
                f"{name}={getattr(self, name)}: latent attention needs it")
        assert self.qk_rope_head_dim % 2 == 0, (
            "qk_rope_head_dim: RoPE turns the pairs (2i, 2i+1) of an even "
            "rotary width")
        assert not self.n_kv_head and not self.attn_head_dim, (
            "n_kv_head, attn_head_dim: every head reads the one latent row, "
            "and the head sizes are qk_nope/qk_rope/v_head_dim")
        assert all(t == "full_attention" for t in self.layer_types), (
            f"layer_types={self.layer_types}: every layer is latent "
            f"attention")


#: the ModelConfig fields of latent attention
_LATENT_FIELDS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim")


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axis names are fixed framework-wide.

    - ``data``: data parallelism (batch dim) + FSDP parameter sharding
    - ``seq``:  sequence/context parallelism (ring attention over ICI)
    - ``model``: tensor parallelism (column/row-parallel matmuls)
    - ``pipe``: pipeline parallelism (layer-stacked block params sharded by
      stage; microbatches flow via ppermute — parallel/pipeline.py)

    The reference has no distributed machinery (SURVEY.md §2.1-§2.2); this is
    the TPU-native replacement: XLA GSPMD collectives derived from
    NamedSharding annotations over this mesh.
    """

    data: int = 1
    seq: int = 1
    model: int = 1
    pipe: int = 1
    fsdp: bool = False  # additionally shard params/opt-state over 'data'
    microbatches: int = 0  # pipeline microbatches (0 = 2 per stage)

    @property
    def n_devices(self) -> int:
        return self.data * self.seq * self.model * self.pipe

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("data", "seq", "model", "pipe")


@dataclass(frozen=True)
class TrainConfig:
    """Optimization + loop schedule.

    Reference semantics preserved: AdamW (GPT1.py:218), periodic mean-of-K
    train/val eval (GPT1.py:85-98, eval_interval GPT1.py:223), per-step loss
    logging (GPT-2.py:229). The committed lr=5e-1 bug (GPT1.py:218) is fixed
    to the declared 2e-4 (GPT1.py:17) per SURVEY.md §8-B4.
    """

    batch_size: int = 64
    lr: float = 2e-4
    betas: Tuple[float, float] = (0.9, 0.999)
    weight_decay: float = 0.01
    grad_clip: float = 0.0           # 0 = off (reference has none)
    max_iters: int = 3000
    warmup_iters: int = 0
    lr_schedule: str = "constant"    # 'constant' | 'cosine'
    min_lr: float = 0.0
    eval_interval: int = 200
    eval_iters: int = 200
    log_interval: int = 10
    steps_per_dispatch: int = 1      # >1: lax.scan K optimizer steps per
                                     # dispatch (amortizes host->device
                                     # round trips; loss curve unchanged)
    grad_accum_steps: int = 1        # >1: each optimizer step averages
                                     # grads over this many batch_size
                                     # microbatches (effective batch =
                                     # grad_accum_steps * batch_size) via an
                                     # on-device lax.scan — big global
                                     # batches without the activation memory
    seed: int = 1337                 # GPT1.py:10
    sampling: str = "random"         # 'random' (GPT1.py:75-83) |
                                     # 'sequential' (GPT-2.py:200-213)
    val_fraction: float = 0.1        # 90/10 split, GPT1.py:68-70
    checkpoint_every: int = 0        # 0 = only at end
    checkpoint_dir: str = "checkpoints"


@dataclass(frozen=True)
class Config:
    model: ModelConfig = ModelConfig()
    train: TrainConfig = TrainConfig()
    mesh: MeshConfig = MeshConfig()
    tokenizer: str = "char"          # 'char' | 'bpe' | 'bpe:<path>' |
                                     # 'tiktoken:gpt2' | 'tiktoken:o200k_base'
    dataset: str = "datasets/shakespeare.txt"
    name: str = "default"

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)


# ---------------------------------------------------------------------------
# Presets: every configuration the reference can express + BASELINE workloads
# ---------------------------------------------------------------------------

def _gpt2_ladder(n_layer: int, n_head: int, n_embd: int,
                 remat: bool = False) -> ModelConfig:
    # Size table from GPT-2.py:140-147 (vocab 50257, context 1024).
    # remat=True for 350M+: without it the layer-stacked residuals of a
    # 24-48 layer scan (~18 GB at 350M/B=8) exceed a single chip's HBM —
    # measured OOM on v5e-16G; with remat the same config trains (the
    # FLOPs-for-HBM trade jax.checkpoint exists for).
    return ModelConfig(
        vocab_size=50257, block_size=1024, n_layer=n_layer, n_head=n_head,
        n_embd=n_embd, dropout=0.0, attn_dropout=0.0, tied_head=True,
        activation="gelu", remat=remat,
    )


def _exaone_moe(n_layer: int, **kw) -> ModelConfig:
    """The exaone_moe family's constants (K-EXAONE config.json): RMSNorm
    eps 1e-5, QK-norm, RoPE theta 1e6 on the window layers only, three
    window layers to one full layer, a dense SwiGLU MLP in layer 0 and
    sigmoid-routed experts (scaling 2.5, normalised top-k) beside one
    shared expert after it, untied head."""
    pattern = ("sliding_attention",) * 3 + ("full_attention",)
    return ModelConfig(
        family="exaone_moe", n_layer=n_layer, dropout=0.0, attn_dropout=0.0,
        tied_head=False, activation="swiglu", rope_theta=1e6,
        layer_types=tuple(pattern[i % 4] for i in range(n_layer)),
        mlp_layer_types=("dense",) + ("sparse",) * (n_layer - 1),
        routed_scaling=2.5,
        decode_cache_layout="packed", scan_layers=False, **kw)


def _lfm2_moe(n_layer: int, n_dense: int, **kw) -> ModelConfig:
    """The lfm2_moe family's constants (LFM2-24B-A2B config.json and
    ``transformers``' ``lfm2_moe``): RMSNorm eps 1e-5, gated short
    convolutions of reach 3 with a full-attention layer (QK-norm, RoPE
    theta 1e6) at every fourth place from the third, ``n_dense`` leading
    dense SwiGLU layers and sigmoid-routed experts after them (scaling 1,
    top-k normalised over a sum that has 1e-6 added, no shared expert),
    head tied to the embedding."""
    return ModelConfig(
        family="lfm2_moe", n_layer=n_layer, dropout=0.0, attn_dropout=0.0,
        tied_head=True, activation="swiglu", rope_theta=1e6,
        layer_types=tuple("full_attention" if i % 4 == 2 else "conv"
                          for i in range(n_layer)),
        conv_reach=3,
        mlp_layer_types=("dense",) * n_dense
        + ("sparse",) * (n_layer - n_dense),
        routed_scaling=1.0, router_norm_eps=1e-6,
        decode_cache_layout="packed", scan_layers=False, **kw)


def _joyai_llm_flash(n_layer: int, **kw) -> ModelConfig:
    """The joyai_llm_flash family's constants (JoyAI-LLM-Flash config.json,
    the DeepSeek-V3 lineage): RMSNorm eps 1e-6, latent attention on every
    layer with interleaved RoPE of theta 3.2e7 on the rotary part, layer 0
    a dense SwiGLU (first_k_dense_replace 1) and every later layer
    sigmoid-routed experts (noaux_tc with one group: selection by score +
    bias, weights normalised over the chosen with 1e-20 added, times 2.5)
    beside one shared expert, untied head."""
    return ModelConfig(
        family="joyai_llm_flash", n_layer=n_layer, dropout=0.0,
        attn_dropout=0.0, tied_head=False, activation="swiglu",
        rope_theta=3.2e7, layernorm_eps=1e-6,
        mlp_layer_types=("dense",) + ("sparse",) * (n_layer - 1),
        routed_scaling=2.5, router_norm_eps=1e-20,
        decode_cache_layout="packed", scan_layers=False, **kw)


PRESETS = {
    # BASELINE.json config 1/2: canonical char-GPT (n_embd=384 per
    # BASELINE.md; GPT1.py semantics: untied head, ReLU, dropout 0.2).
    "char-gpt": Config(
        name="char-gpt",
        model=ModelConfig(
            vocab_size=65, block_size=256, n_layer=6, n_head=6, n_embd=384,
            dropout=0.2, attn_dropout=0.2, tied_head=False, activation="relu",
        ),
        train=TrainConfig(batch_size=64, lr=2e-4, max_iters=3000,
                          eval_interval=200, eval_iters=200, seed=1337,
                          sampling="random"),
        tokenizer="char",
    ),
    # The GPT1.py file exactly as committed (n_embd=126), for parity audits.
    "char-gpt1-ref": Config(
        name="char-gpt1-ref",
        model=ModelConfig(
            vocab_size=65, block_size=256, n_layer=6, n_head=6, n_embd=126,
            dropout=0.2, attn_dropout=0.2, tied_head=False, activation="relu",
        ),
        train=TrainConfig(batch_size=64, lr=2e-4, max_iters=3000,
                          eval_interval=200, eval_iters=200, seed=1337,
                          sampling="random"),
        tokenizer="char",
    ),
    # The GPT-2.py training run as intended (B=4/T=32/50 iters,
    # lr 3e-4, sequential loader; vocab fixed to the tokenizer's per §8-B5).
    "gpt2-shakespeare": Config(
        name="gpt2-shakespeare",
        model=ModelConfig(
            vocab_size=50304, block_size=256, n_layer=6, n_head=6, n_embd=384,
            dropout=0.0, attn_dropout=0.0, tied_head=True, activation="gelu",
        ),
        train=TrainConfig(batch_size=4, lr=3e-4, max_iters=50,
                          eval_interval=0, eval_iters=20, seed=1337,
                          sampling="sequential", log_interval=1),
        tokenizer="bpe",
    ),
    # BASELINE.json config 3: GPT-2 124M, 8-chip DP.
    "gpt2-small": Config(
        name="gpt2-small",
        model=_gpt2_ladder(12, 12, 768),
        train=TrainConfig(batch_size=32, lr=3e-4, max_iters=1000,
                          sampling="sequential", lr_schedule="cosine",
                          warmup_iters=100, grad_clip=1.0),
        mesh=MeshConfig(data=8),
        tokenizer="bpe",
    ),
    # BASELINE.json config 4: GPT-2 350M, v4-32, bf16, FSDP.
    "gpt2-medium": Config(
        name="gpt2-medium",
        model=_gpt2_ladder(24, 16, 1024, remat=True),
        train=TrainConfig(batch_size=64, lr=3e-4, max_iters=1000,
                          sampling="sequential", lr_schedule="cosine",
                          warmup_iters=100, grad_clip=1.0),
        mesh=MeshConfig(data=16, fsdp=True),
        tokenizer="bpe",
    ),
    "gpt2-large": Config(
        name="gpt2-large", model=_gpt2_ladder(36, 20, 1280, remat=True),
        mesh=MeshConfig(data=16, fsdp=True), tokenizer="bpe",
    ),
    "gpt2-xl": Config(
        name="gpt2-xl", model=_gpt2_ladder(48, 25, 1600, remat=True),
        mesh=MeshConfig(data=16, fsdp=True), tokenizer="bpe",
    ),
    # The reference GPT1.py's DEFAULT tokenizer branch as intended:
    # tiktoken o200k_base with the §8-B1 vocab bug fixed (the reference
    # hard-coded vocab 50257 under a ~200k-token encoding, so most ids
    # indexed past the embedding; here the tokenizer's true n_vocab
    # (200,019) is rounded up to an MXU-friendly 200,064 = 128*1563).
    # Giant-vocab caveat measured on v5e (benchmarks/RESULTS.md o200k
    # row): the (B*T, C) @ (C, 200k) f32 logits matmul + softmax
    # dominates the step at char-GPT scale. Needs tiktoken's cached BPE
    # ranks (network once); this zero-egress image measures the
    # giant-vocab cost via `--preset char-gpt --vocab-size 200064`.
    "o200k-shakespeare": Config(
        name="o200k-shakespeare",
        model=ModelConfig(
            vocab_size=200_064, block_size=256, n_layer=6, n_head=6,
            n_embd=384, dropout=0.2, attn_dropout=0.2, tied_head=False,
            activation="relu",
            # at V=200k the one-shot f32 logits array is B*T*V*4 =
            # 13.1 GB — past a 16 GB chip once the backward doubles it;
            # the chunked CE head makes this preset feasible at all
            # (2048 divides B*T = 16384)
            loss_chunk=2048,
        ),
        train=TrainConfig(batch_size=64, lr=2e-4, max_iters=3000,
                          eval_interval=200, eval_iters=200, seed=1337,
                          sampling="random"),
        tokenizer="tiktoken:o200k_base",
    ),
    # K-EXAONE-236B-A23B (LGAI-EXAONE, config.json on the Hugging Face hub),
    # ONE CHIP'S SHARE of an 8-way expert-parallel deployment, serve-only:
    # every width as published; published layers 0-7 (two periods of three
    # window-128 layers and one full layer; layer 0's MLP dense, 1-7
    # sparse); experts 0-15 of a router that keeps its 128 outputs and 8 a
    # token; vocabulary rows 0-19,199 of 153,600. bf16 parameters as
    # published. chipbench/configs/k-exaone-236b-a23b.json states the cut.
    "k-exaone-236b-a23b": Config(
        name="k-exaone-236b-a23b",
        model=_exaone_moe(
            vocab_size=19_200, block_size=8192, n_layer=8, n_head=64,
            n_kv_head=8, attn_head_dim=128, n_embd=6144,
            sliding_window=128, intermediate_size=18_432, n_experts=128,
            experts_held=tuple(range(16)), experts_per_token=8,
            moe_intermediate_size=2048, shared_intermediate_size=2048,
            dtype="bfloat16", param_dtype="bfloat16"),
        tokenizer="char",
    ),
    # the same family at test widths (CPU tests, chipbench/rehearse.py):
    # window 8 of a 64-token context, 2 of 8 experts held, 2 a token
    "exaone-moe-tiny": Config(
        name="exaone-moe-tiny",
        model=_exaone_moe(
            vocab_size=96, block_size=64, n_layer=4, n_head=4, n_kv_head=2,
            attn_head_dim=32, n_embd=64, sliding_window=8,
            intermediate_size=96, n_experts=8, experts_held=(0, 1),
            experts_per_token=2, moe_intermediate_size=48,
            shared_intermediate_size=48, dtype="float32",
            param_dtype="float32"),
        tokenizer="char",
    ),
    # LFM2-24B-A2B (LiquidAI, config.json on the Hugging Face hub), THE FIRST
    # STAGE of a four-stage pipeline on one four-chip host, serve-only:
    # every width, all 64 experts and 4 a token, the whole vocabulary as
    # published; published layers 0-9 (conv conv full, conv conv conv full,
    # conv conv conv: the 2 leading dense layers, then 8 expert layers = two
    # whole periods). bf16 parameters as published.
    # chipbench/configs/lfm2-24b-a2b.json states the cut.
    "lfm2-24b-a2b": Config(
        name="lfm2-24b-a2b",
        model=_lfm2_moe(
            10, 2, vocab_size=65_536, block_size=8192, n_head=32,
            n_kv_head=8, attn_head_dim=64, n_embd=2048,
            intermediate_size=11_776, n_experts=64,
            experts_held=tuple(range(64)), experts_per_token=4,
            moe_intermediate_size=1536, dtype="bfloat16",
            param_dtype="bfloat16"),
        tokenizer="char",
    ),
    # the same family at test widths (CPU tests, chipbench/rehearse.py):
    # 7 layers (conv conv full conv conv conv full), 2 dense then 5 sparse,
    # all 8 experts held, 2 a token
    "lfm2-moe-tiny": Config(
        name="lfm2-moe-tiny",
        model=_lfm2_moe(
            7, 2, vocab_size=96, block_size=64, n_head=4, n_kv_head=2,
            attn_head_dim=32, n_embd=64, intermediate_size=96, n_experts=8,
            experts_held=tuple(range(8)), experts_per_token=2,
            moe_intermediate_size=48, dtype="float32",
            param_dtype="float32"),
        tokenizer="char",
    ),
    # JoyAI-LLM-Flash (jdopensource, config.json on the Hugging Face hub),
    # ONE CHIP OF THE FIRST STAGE of a deployment whose stages each divide
    # their layers over 8 chips by expert parallelism, serve-only: every
    # width as published; published layers 0-9 (layer 0's MLP dense, 1-9
    # sparse); experts 0-31 of a router that keeps its 256 outputs and 8 a
    # token; vocabulary rows 0-16,159 of 129,280. bf16 parameters.
    # chipbench/configs/joyai-llm-flash.json states the cut.
    "joyai-llm-flash": Config(
        name="joyai-llm-flash",
        model=_joyai_llm_flash(
            10, vocab_size=16_160, block_size=8192, n_head=32, n_embd=2048,
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, intermediate_size=7168,
            n_experts=256, experts_held=tuple(range(32)),
            experts_per_token=8, moe_intermediate_size=768,
            shared_intermediate_size=768, dtype="bfloat16",
            param_dtype="bfloat16"),
        tokenizer="char",
    ),
    # the same family at test widths (CPU tests, chipbench/rehearse.py):
    # 4 layers (dense, then 3 sparse), 2 of 16 experts held, 4 a token
    "joyai-llm-flash-tiny": Config(
        name="joyai-llm-flash-tiny",
        model=_joyai_llm_flash(
            4, vocab_size=96, block_size=64, n_head=4, n_embd=64,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            n_experts=16, experts_held=(0, 1), experts_per_token=4,
            moe_intermediate_size=24, shared_intermediate_size=24,
            dtype="float32", param_dtype="float32"),
        tokenizer="char",
    ),
    # Tiny config for tests / smoke runs.
    "test-tiny": Config(
        name="test-tiny",
        model=ModelConfig(
            vocab_size=65, block_size=32, n_layer=2, n_head=2, n_embd=32,
            dropout=0.0, attn_dropout=0.0, tied_head=True, activation="gelu",
            dtype="float32",
        ),
        train=TrainConfig(batch_size=8, lr=1e-3, max_iters=50,
                          eval_interval=25, eval_iters=4, log_interval=10),
        tokenizer="char",
    ),
}


def get_config(name: str, **overrides) -> Config:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    cfg = PRESETS[name]
    return cfg.replace(**overrides) if overrides else cfg


# ---------------------------------------------------------------------------
# CLI overlay (the reference has no CLI at all — SURVEY.md §5 config row)
# ---------------------------------------------------------------------------

def add_config_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--preset", default="char-gpt", choices=sorted(PRESETS))
    p.add_argument("--backend", default="jax", choices=["jax"],
                   help="execution backend (BASELINE.json names --backend=jax)")
    # model overrides — each registered under BOTH spellings
    # (--vocab_size and --vocab-size): the o200k preset's documented
    # repro command uses the dashed form, and every other flag here is
    # dashed, so the underscore-only registration was a paper cut
    # (ADVICE round 5)
    for f in ("vocab_size", "block_size", "n_layer", "n_head", "n_embd"):
        p.add_argument(f"--{f}", f"--{f.replace('_', '-')}", type=int,
                       default=None)
    p.add_argument("--dropout", type=float, default=None)
    p.add_argument("--dtype", type=str, default=None)
    p.add_argument("--attention", dest="attention_impl", default=None,
                   choices=["auto", "einsum", "flash", "ring", "ulysses"])
    p.add_argument("--remat", action="store_true", default=None,
                   help="jax.checkpoint each block (trade FLOPs for HBM)")
    p.add_argument("--no-remat", dest="remat", action="store_false",
                   help="disable the preset's remat (e.g. 350M+ presets "
                        "default remat on for single-chip HBM; a pod-slice "
                        "FSDP run may not need it)")
    p.add_argument("--loss-chunk", dest="loss_chunk", type=int, default=None,
                   help="chunked training CE head: rows per scan step "
                        "(0 = one-shot logits; see ModelConfig.loss_chunk)")
    p.add_argument("--decode-cache-layout", dest="decode_cache_layout",
                   default=None, choices=["heads", "packed"],
                   help="KV-cache memory layout for decode (see "
                        "ModelConfig.decode_cache_layout)")
    p.add_argument("--remat-policy", dest="remat_policy", default=None,
                   choices=["full", "dots", "dots_no_batch"],
                   help="what jax.checkpoint saves per block: 'full' "
                        "recomputes everything (v5e-measured default), "
                        "'dots'/'dots_no_batch' save matmul outputs")
    # train overrides
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--max-iters", type=int, default=None)
    p.add_argument("--eval-interval", type=int, default=None)
    p.add_argument("--eval-iters", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--steps-per-dispatch", type=int, default=None,
                   help="lax.scan K optimizer steps per device dispatch")
    p.add_argument("--grad-accum-steps", type=int, default=None,
                   help="microbatches averaged per optimizer step "
                        "(effective batch = this * batch-size)")
    # mesh overrides
    p.add_argument("--dp", type=int, default=None, help="mesh data axis size")
    p.add_argument("--sp", type=int, default=None, help="mesh seq axis size")
    p.add_argument("--tp", type=int, default=None, help="mesh model axis size")
    p.add_argument("--pp", type=int, default=None, help="mesh pipe axis size")
    p.add_argument("--microbatches", type=int, default=None,
                   help="pipeline microbatches (default 2 per stage)")
    p.add_argument("--fsdp", action="store_true", default=None)
    p.add_argument("--lr-schedule", default=None,
                   choices=["constant", "cosine"])
    p.add_argument("--warmup-iters", type=int, default=None)
    p.add_argument("--min-lr", type=float, default=None)
    p.add_argument("--grad-clip", type=float, default=None)
    p.add_argument("--log-interval", type=int, default=None)
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--dataset", default=None)


#: (dest, flag) pairs for every MODEL-shape override registered by
#: add_config_flags — kept adjacent so a new model flag is added to
#: both in one edit. config_override_args() reconstructs these for a
#: respawned process (`serve --multiproc` workers): a flag missing
#: here means a worker silently builds a DIFFERENT model than the
#: operator asked for.
MODEL_OVERRIDE_FLAGS = (
    ("vocab_size", "--vocab-size"), ("block_size", "--block-size"),
    ("n_layer", "--n-layer"), ("n_head", "--n-head"),
    ("n_embd", "--n-embd"), ("dropout", "--dropout"),
    ("dtype", "--dtype"), ("attention_impl", "--attention"),
    ("loss_chunk", "--loss-chunk"),
    ("decode_cache_layout", "--decode-cache-layout"),
    ("remat_policy", "--remat-policy"),
)


def config_override_args(args: argparse.Namespace) -> list:
    """Reconstruct the model-override CLI arguments present on
    ``args`` (None = unset = omitted) so one process can spawn another
    with the same model config through its own add_config_flags
    parser."""
    out: list = []
    for dest, flag in MODEL_OVERRIDE_FLAGS:
        v = getattr(args, dest, None)
        if v is not None:
            out += [flag, str(v)]
    remat = getattr(args, "remat", None)
    if remat is not None:                # tri-state store_true/false
        out.append("--remat" if remat else "--no-remat")
    return out


def config_from_args(args: argparse.Namespace) -> Config:
    cfg = get_config(args.preset)
    m, t, mesh = cfg.model, cfg.train, cfg.mesh
    mk = {k: v for k, v in (
        ("vocab_size", args.vocab_size), ("block_size", args.block_size),
        ("n_layer", args.n_layer), ("n_head", args.n_head),
        ("n_embd", args.n_embd), ("dropout", args.dropout),
        ("dtype", args.dtype), ("attention_impl", args.attention_impl),
        ("remat", args.remat), ("remat_policy", args.remat_policy),
        ("decode_cache_layout", getattr(args, "decode_cache_layout", None)),
        ("loss_chunk", getattr(args, "loss_chunk", None)),
    ) if v is not None}
    if args.dropout is not None:
        mk["attn_dropout"] = args.dropout
    tk = {k: v for k, v in (
        ("batch_size", args.batch_size), ("lr", args.lr),
        ("max_iters", args.max_iters), ("eval_interval", args.eval_interval),
        ("eval_iters", args.eval_iters), ("seed", args.seed),
        ("steps_per_dispatch", args.steps_per_dispatch),
        ("grad_accum_steps", args.grad_accum_steps),
        ("lr_schedule", args.lr_schedule),
        ("warmup_iters", args.warmup_iters), ("min_lr", args.min_lr),
        ("grad_clip", args.grad_clip), ("log_interval", args.log_interval),
    ) if v is not None}
    meshk = {k: v for k, v in (
        ("data", args.dp), ("seq", args.sp), ("model", args.tp),
        ("pipe", args.pp), ("microbatches", args.microbatches),
        ("fsdp", args.fsdp),
    ) if v is not None}
    ck = {}
    if args.tokenizer is not None:
        ck["tokenizer"] = args.tokenizer
    if args.dataset is not None:
        ck["dataset"] = args.dataset
    return cfg.replace(
        model=dataclasses.replace(m, **mk).validate(),
        train=dataclasses.replace(t, **tk),
        mesh=dataclasses.replace(mesh, **meshk),
        **ck,
    )
