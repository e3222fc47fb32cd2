"""Reads ``gdn_prefill_roofline_pct`` as ``gdn_prefill_roofline_pct.json``
beside this file says. The bytes and FLOPs are the program's
``gdn_scan_bytes`` / ``gdn_scan_flops`` stats on ``serve/prefill``, which
cover all of a span's chunks; ``chunk_scan_bytes`` and ``chunk_scan_flops``
below are the functions they follow, whatever implements the scan.
"""

from __future__ import annotations

import json
from typing import Optional

from chipbench import flops, trace_stats

with open(__file__[:-3] + ".json") as _f:
    SPEC = json.load(_f)

#: rows of the chunked form's chunk (the WY / UT form of Yang et al. 2024)
CHUNK = 64


def chunk_scan_bytes(tokens: int, heads: int, key_head: int,
                     value_head: int) -> int:
    """Bytes ONE layer's scan of ``tokens`` rows of one prefill launch must
    move: the float32 state read and written once, and a row's q and k
    (key head), v and output (value head), alpha and beta, float32, once a
    head. 4.42 MB of state + 69,360 B a row at the published widths."""
    return 4 * heads * (2 * key_head * value_head
                        + tokens * (2 * key_head + 2 * value_head + 2))


def chunk_scan_flops(tokens: int, heads: int, key_head: int,
                     value_head: int) -> int:
    """FLOPs of ONE layer's chunked scan of ``tokens`` rows, a chunk of
    ``CHUNK`` at a time (a short last chunk computes a whole one): a
    chunk's triangular solve (CHUNK^3), the products inside it (beta k k^T,
    q k^T and the solve applied to the keys: 6 CHUNK^2 key head; to the
    values and the intra-chunk output: 4 CHUNK^2 value head) and those
    against the state (w S, q S, k^T v': 6 CHUNK key head value head), a
    head."""
    n = -(-tokens // CHUNK)
    return heads * n * (CHUNK ** 3 + 6 * CHUNK * CHUNK * key_head
                        + 4 * CHUNK * CHUNK * value_head
                        + 6 * CHUNK * key_head * value_head)


def read(counters: dict, trace: Optional[dict], stats: Optional[dict] = None,
         device_kind: Optional[str] = None) -> Optional[float]:
    """The larger floor of the two, a launch, as a share (%) of the scope's
    device self time a launch. None in an untraced run and where the
    program has no such stats or scope, as the parent has not."""
    if not trace:
        return None
    stats = trace_stats.current() if stats is None else stats
    if stats is None:
        return None
    spans = [h[3] for h in trace_stats.spans(stats, SPEC["span"])
             if all(isinstance(h[3].get(k), (int, float))
                    for k in SPEC["stats"] + [SPEC["per_span"]])]
    launches = sum(s[SPEC["per_span"]] for s in spans)
    took_ms = trace_stats.scope_self_ms_per_launch(stats, SPEC["scope"],
                                                   SPEC["per"])
    if not launches or not took_ms:
        return None
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    peaks = flops.peaks(device_kind)
    floor_ms = max(sum(s[k] for s in spans) / launches / peaks[p]
                   for k, p in zip(SPEC["stats"], SPEC["peaks"])) * 1e3
    return 100.0 * floor_ms / took_ms
