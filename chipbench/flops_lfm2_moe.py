"""The yardstick of ``serve_step_mfu`` for the ``lfm2_moe`` family: the
forward FLOPs its configuration file's share of the model NEEDS for a
window (``flops.py`` holds GPT-2's and ``exaone_moe``'s and is not edited).
"""

from __future__ import annotations


def lfm2_moe_serve_flops(config: dict, *, tokens: float, emitted: float,
                         pairs_held: float,
                         full_context_pairs: float) -> float:
    """Every row goes through its layers' operators: a conv layer's in
    projection (hidden x 3 hidden), three taps and out projection (hidden x
    hidden), a full layer's q, k, v and o projections; the leading dense
    layers' MLP (3 x hidden x intermediate); a sparse layer's router. A
    routed expert's three hidden x width matrices count once for each (row,
    expert) pair that landed on a HELD expert (``pairs_held``: what the
    routing needs, 4 a row a sparse layer here, not the rows the program
    pushes through all 64 experts). The tied head is hidden x vocabulary for
    the ``emitted`` rows (a prompt's rows need no logits). Attention is 4
    FLOPs a query-width element for each (row, context position) pair of
    the full layers. 2 FLOPs a weight a row throughout."""
    h = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    width = config["moe_intermediate_size"]
    n_layer = len(config["layer_types"])
    n_full = config["layer_types"].count("full_attention")
    n_conv = n_layer - n_full
    n_dense = config["num_dense_layers"]
    per_row = (n_full * 2.0 * (h * (q + 2 * kv) + q * h)
               + n_conv * 2.0 * (h * 3 * h + h * h
                                 + h * config["conv_L_cache"])
               + n_dense * 2.0 * 3 * h * config["intermediate_size"]
               + (n_layer - n_dense) * 2.0 * h * config["num_experts"])
    return (tokens * per_row + pairs_held * 2.0 * 3 * h * width
            + emitted * 2.0 * h * config["vocab_size"]
            + 4.0 * q * n_full * full_context_pairs)
