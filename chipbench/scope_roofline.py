"""The reader of a BYTES-bound roofline share whose time is a
``jax.named_scope``'s: ``{"span", "stat", "peak", "scope", "per"}``.

``trace_stats.read_spec``'s ``over`` takes op names; what XLA makes of a
scope is its own fusions, known by the scope alone, so this divides by the
scope's device self time a launch (``moe_expert_hbm_pct.py`` reads its one
metric the same way; this is that reader with the spec handed in, for the
metrics PR 37 adds).
"""

from __future__ import annotations

from typing import Optional

from chipbench import flops, trace_stats


def read(spec: dict, counters: dict, trace: Optional[dict],
         stats: Optional[dict] = None,
         device_kind: Optional[str] = None) -> Optional[float]:
    """The least time the device's peak ``spec["peak"]`` allows for the mean
    of stat ``spec["stat"]`` of the ``spec["span"]`` spans, as a share (%) of
    the device self time under ``spec["scope"]`` a launch matching
    ``spec["per"]``. None (and the line leaves the metric out) in an
    untraced run, and where the program has no such stat or scope, as the
    parent has not."""
    if not trace:
        return None
    stats = trace_stats.current() if stats is None else stats
    if stats is None:
        return None
    amount = trace_stats.mean_stat(stats, spec["span"], spec["stat"])
    took_ms = trace_stats.scope_self_ms_per_launch(stats, spec["scope"],
                                                   spec["per"])
    if amount is None or not took_ms:
        return None
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    floor_ms = amount / flops.peaks(device_kind)[spec["peak"]] * 1e3
    return 100.0 * floor_ms / took_ms
