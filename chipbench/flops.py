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


def peaks(device_kind: str) -> dict:
    """The peak row of one chip of ``device_kind``; KeyError if unknown."""
    with open(_PEAKS) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)}); add "
                       f"the row with its source")
    return table[device_kind]
