"""The yardstick of ``serve_step_mfu`` for the ``olmo_hybrid`` family: the
forward FLOPs its configuration file's stage NEEDS for a window
(``flops.py`` holds GPT-2's and ``exaone_moe``'s and is not edited).
"""

from __future__ import annotations


def linear_layer_flops(config: dict) -> float:
    """FLOPs of ONE row through ONE Gated DeltaNet layer: its projections
    (q, k of heads x key head, v and the gate of heads x value head, a and
    b of heads, from the hidden size; the out projection back), the conv's
    taps over q, k and v, and the delta rule on a key head x value head
    state a head (the decay, S^T k, the rank-one update and S^T q: 7
    FLOPs a state element). 2 FLOPs a weight."""
    h, H = config["hidden_size"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    channels = H * (2 * dk + dv)
    return (2.0 * h * (2 * H * dk + 2 * H * dv + 2 * H) + 2.0 * H * dv * h
            + 2.0 * channels * config["linear_conv_kernel_dim"]
            + 7.0 * H * dk * dv)


def olmo_hybrid_serve_flops(config: dict, *, tokens: float, emitted: float,
                            full_context_pairs: float) -> float:
    """Every row goes through its layers: a linear layer
    (``linear_layer_flops``), a full layer's q, k, v and o projections
    (hidden x hidden each: 30 heads of 128), and every layer's dense MLP (3
    x hidden x intermediate). The untied head is hidden x vocabulary for
    the ``emitted`` rows (a prompt's rows need no logits). Attention is 4
    FLOPs a query-width element for each (row, context position) pair of
    the full layers."""
    h = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    n_full = config["layer_types"].count("full_attention")
    n_linear = config["layer_types"].count("linear_attention")
    per_row = (n_linear * linear_layer_flops(config)
               + n_full * 2.0 * (h * q * 3 + q * h)
               + len(config["layer_types"]) * 2.0 * 3 * h
               * config["intermediate_size"])
    return (tokens * per_row + emitted * 2.0 * h * config["vocab_size"]
            + 4.0 * q * n_full * full_context_pairs)
