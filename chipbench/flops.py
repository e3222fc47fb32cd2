"""The benchmark's own yardstick arithmetic: analytic FLOPs and the table of
peaks. Copied from ``bench.py`` (``train_flops_per_token``, ``_PEAK_FLOPS``)
so that no later change to the program can move it.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def train_flops_per_token(n_layer: int, n_embd: int, block_size: int,
                          vocab_size: int) -> float:
    """Training FLOPs per token, matmul terms only (the usual MFU count;
    layernorm, softmax and the embedding gather are left out, recomputed
    work under remat is NOT counted).

    Per layer the matmul weights are qkv 3d^2 + attn-proj d^2 + mlp 8d^2 =
    12d^2; the head is d*V (tied or not: tying shares storage, not work).
    Forward is 2 FLOPs per weight use, backward twice the forward;
    attention scores and values add 4dT per token per layer forward, halved
    by the causal mask.
    """
    L, d, T, V = n_layer, n_embd, block_size, vocab_size
    fwd_matmul = 2.0 * (12.0 * L * d * d + d * V)
    fwd_attn = 2.0 * L * d * T
    return 3.0 * (fwd_matmul + fwd_attn)


def gpt_serve_flops(n_layer: int, n_embd: int, vocab_size: int, *,
                    tokens: float, emitted: float,
                    context_pairs: float) -> float:
    """Forward FLOPs a GPT-2 body NEEDS to serve a window: ``tokens`` rows
    (decoded or prefilled) through every layer's 12d^2 matmul weights at 2
    FLOPs a weight, the head's d*V for the ``emitted`` ones (a prompt's rows
    need no logits), and the attention scores and values, 4d FLOPs a layer
    for each (row, context position) pair in ``context_pairs``: for a decode
    step the live context of its slots. A prefilled row's own pairs (half its
    prompt, under 1% of its matmul FLOPs at these lengths) are left out, so
    the count errs low."""
    L, d, V = n_layer, n_embd, vocab_size
    return (tokens * 2.0 * 12.0 * L * d * d + emitted * 2.0 * d * V
            + context_pairs * 4.0 * L * d)


def exaone_moe_serve_flops(config: dict, *, tokens: float, emitted: float,
                           pairs_held: float, full_context_pairs: float,
                           window_context_pairs: float) -> float:
    """The same for ONE CHIP'S SHARE of an ``exaone_moe`` stage as its
    configuration file states it: every row goes through each layer's
    attention projections (whole on each chip), the dense layers' MLP, the
    sparse layers' shared expert and router; a routed expert's three
    hidden x width matrices count once for each (row, expert) pair that
    landed on a HELD expert (``pairs_held``: what the routing needs, not the
    rows the program pushes through every held expert); the head is this
    chip's slice of the vocabulary; attention is 4 FLOPs a query-width
    element for each (row, context position) pair, over the full layers'
    whole context and the window layers' window."""
    h = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    width = config["moe_intermediate_size"]
    n_full = config["layer_types"].count("full_attention")
    n_window = len(config["layer_types"]) - n_full
    n_dense = config["mlp_layer_types"].count("dense")
    n_sparse = len(config["mlp_layer_types"]) - n_dense
    per_row = (len(config["layer_types"]) * 2.0 * (h * (q + 2 * kv) + q * h)
               + n_dense * 2.0 * 3 * h * config["intermediate_size"]
               + n_sparse * 2.0 * (3 * h * width
                                   * config["num_shared_experts"]
                                   + h * config["router_outputs"]))
    return (tokens * per_row + pairs_held * 2.0 * 3 * h * width
            + emitted * 2.0 * h * config["vocab_size"]
            + 4.0 * q * (n_full * full_context_pairs
                         + n_window * window_context_pairs))


def peaks(device_kind: str) -> dict:
    """The peak row of one chip of ``device_kind``; KeyError if unknown."""
    with open(_PEAKS) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)}); add "
                       f"the row with its source")
    return table[device_kind]
