"""The yardstick of ``serve_step_mfu`` for the ``joyai_llm_flash`` family:
the forward FLOPs its configuration file's share of the model NEEDS for a
window (``flops.py`` holds GPT-2's and ``exaone_moe``'s and is not edited).
"""

from __future__ import annotations


def attention_weights(config: dict) -> int:
    """Weights of one latent-attention layer's five projections: q_a
    (hidden x q rank), q_b (q rank x heads x (nope + rope)), kv_a (hidden x
    (kv rank + rope)), kv_b (kv rank x heads x (nope + v)), o (heads x v x
    hidden). 26,345,472 at the published widths."""
    h, H = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    return (h * rq + rq * H * (nope + rope) + h * (rkv + rope)
            + rkv * H * (nope + v) + H * v * h)


def context_flops(config: dict) -> float:
    """FLOPs of ONE (row, context position) pair of ONE layer in the
    absorbed form: every head scores the cached row (kv rank + rope wide)
    and weighs its latent (kv rank wide), 2 FLOPs a product. 69,632 at the
    published widths, for 1,152 B of the row: 60 FLOPs a byte."""
    rkv, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return 2.0 * config["num_attention_heads"] * ((rkv + rope) + rkv)


def joyai_llm_flash_serve_flops(config: dict, *, tokens: float,
                                emitted: float, pairs_held: float,
                                full_context_pairs: float) -> float:
    """Every row goes through every layer's attention projections (kv_b
    once a row: the expansion of its own latent in prefill, the absorption
    of its query and latent output in decode, the same weights), the
    leading dense layers' MLP (3 x hidden x intermediate), a sparse
    layer's router (hidden x router outputs) and shared experts (3 x
    hidden x width each). A routed expert's three hidden x width matrices
    count once for each (row, expert) pair that landed on a HELD expert
    (``pairs_held``: what the routing needs, not the rows the program
    pushes through all 32 held experts). The untied head is hidden x
    vocabulary for the ``emitted`` rows (a prompt's rows need no logits).
    Attention is ``context_flops`` for each (row, context position) pair
    of every layer (``full_context_pairs`` counts the pairs of one layer).
    2 FLOPs a weight a row throughout."""
    h, width = config["hidden_size"], config["moe_intermediate_size"]
    n_layer = config["num_hidden_layers"]
    n_dense = config["first_k_dense_replace"]
    per_row = (n_layer * 2.0 * attention_weights(config)
               + n_dense * 2.0 * 3 * h * config["intermediate_size"]
               + (n_layer - n_dense) * 2.0 * (
                   h * config["router_outputs"]
                   + config["n_shared_experts"] * 3 * h * width))
    return (tokens * per_row + pairs_held * 2.0 * 3 * h * width
            + emitted * 2.0 * h * config["vocab_size"]
            + n_layer * context_flops(config) * full_context_pairs)
