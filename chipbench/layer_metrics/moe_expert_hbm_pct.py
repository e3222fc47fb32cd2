"""Reads ``moe_expert_hbm_pct``: the held routed experts' share of their
BYTES-bound roofline, as ``moe_expert_hbm_pct.json`` beside this file says.

``trace_stats.read_spec``'s ``over`` takes op names, and the expert products
are XLA's own fusions, known by their ``jax.named_scope`` alone: so this
reader divides by the scope's device self time instead. The bytes are the
program's ``expert_weight_bytes`` stat on ``serve/launch``; ``expert_bytes``
below is the function they follow, whatever implements the products.
"""

from __future__ import annotations

import json
from typing import Optional

from chipbench import flops, trace_stats

with open(__file__[:-3] + ".json") as _f:
    SPEC = json.load(_f)


def expert_bytes(held: int, sparse_layers: int, hidden: int, width: int,
                 itemsize: int, steps: int = 1) -> int:
    """Bytes a launch of ``steps`` decode steps streams for its held routed
    experts: each is a gate, an up and a down matrix of hidden x width. At
    the published sizes 16 x 7 x 3 x 6144 x 2048 x 2 B = 8.46 GB a step."""
    return held * sparse_layers * 3 * hidden * width * itemsize * steps


def read(counters: dict, trace: Optional[dict], stats: Optional[dict] = None,
         device_kind: Optional[str] = None) -> Optional[float]:
    """None (and the line leaves the metric out) in an untraced run, and
    where the program has no such stat or scope, as the parent has not."""
    if not trace:
        return None
    stats = trace_stats.current() if stats is None else stats
    if stats is None:
        return None
    amount = trace_stats.mean_stat(stats, SPEC["span"], SPEC["stat"])
    took_ms = trace_stats.scope_self_ms_per_launch(stats, SPEC["scope"],
                                                   SPEC["per"])
    if amount is None or not took_ms:
        return None
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    floor_ms = amount / flops.peaks(device_kind)[SPEC["peak"]] * 1e3
    return 100.0 * floor_ms / took_ms
