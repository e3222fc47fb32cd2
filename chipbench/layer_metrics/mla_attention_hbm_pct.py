"""Reads ``mla_attention_hbm_pct`` as ``mla_attention_hbm_pct.json`` beside
this file says (``chipbench/trace_stats.py`` ``read_spec``). The bytes are
the program's ``mla_kv_bytes`` stat on ``serve/launch``; ``mla_kv_bytes``
below is the function they follow, whatever implements the kernel.
"""

from chipbench import trace_stats


def mla_kv_bytes(live_tokens: int, layers: int, kv_lora_rank: int,
                 qk_rope_head_dim: int, itemsize: int) -> int:
    """Bytes of the latent rows a decode step's attention must read: one
    row of the latent and the shared rotary key a context token a layer
    (``live_tokens``: the sum of ``pos + 1`` over the live slots). 10 x 576
    x 2 B = 11,520 B a token at the published widths."""
    return live_tokens * layers * (kv_lora_rank + qk_rope_head_dim) * itemsize


read = trace_stats.reader(__file__)
