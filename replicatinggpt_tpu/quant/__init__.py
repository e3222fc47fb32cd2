"""Quantization subsystem: int8/fp8 paged KV storage and weight
inference for the serving engine.

Page count is the engine's admission currency — every admitted request
reserves whole pages for its lifetime (serve/pages.py), so bytes per
page directly caps concurrent users per chip. Storing K/V pages in
int8 (or fp8 e4m3) with small per-row scale metadata roughly HALVES
bytes/page vs bf16, which at fixed HBM roughly doubles ``n_pages`` and
therefore doubles admission capacity without touching the scheduler.
Weight-side, int8/fp8 kernels with per-output-channel scales halve the
parameter stream the decode step is bound by and feed the MXU its
native low-precision matmuls.

Two halves, one config:

- :mod:`~.kv` — quantize-on-write / dequant-on-gather for the paged KV
  pool. Scale metadata rides the pool dict as ``ks``/``vs`` arrays
  indexed by the SAME (layer, physical page, page offset) coordinates
  as the K/V writes, so scales flow through copy-on-write splits, LRU
  eviction and radix prefix hits with zero extra bookkeeping — a page
  IS its rows plus their scales. Dequant happens inside the paged
  Pallas kernels (ops/paged_pallas.py) and in
  the XLA gather fallback (models.gpt._gather_pages), so every decode
  route reads quantized pages natively.
- :mod:`~.weights` — absmax-per-output-channel weight quantization
  with dequant FUSED into the matmuls (per-output-channel scales
  commute through ``x @ W``: ``(x @ Wq) * s == x @ (Wq * s)`` up to
  rounding, so the scale lands on the tiny output row, never on a
  rematerialized weight). A calibration pass over a short trace
  measures the resulting logit divergence and serializes scales +
  budget next to the checkpoint.

Threading: :class:`QuantConfig` hangs off ``EngineConfig``
(``kv_quant`` / ``weight_quant`` / ``quant_granularity``, the
``--kv-quant``/``--weight-quant`` CLI knobs), sizes the pool in
``serve/pages.py``, keys the fleet's engine-shape hash
(serve/rpc.py — mismatched quant modes reject at registration), and
carries its own PartitionSpec for the scale arrays on a serving mesh
(parallel.mesh.ServeShardings.scale, page axis over 'data' like the
pool itself).
"""

from __future__ import annotations

from dataclasses import dataclass

#: quantized storage dtypes the subsystem accepts for KV pages and
#: weights ("none" = the unquantized identity)
QUANT_DTYPES = ("none", "int8", "fp8")
#: KV scale granularities: "page" = one f32 scale per written row
#: (page position) shared across the whole model dim — the cheapest
#: metadata that still tracks per-token dynamic range; "head" = one
#: scale per (row, head), tighter for outlier heads at H× the metadata
GRANULARITIES = ("page", "head")

#: pinned logit-divergence budgets vs the unquantized engine (max
#: |Δlogit| over a long greedy trace — measured in tests/test_quant.py
#: at the test-tiny scale with >10x headroom: int8 KV measures ~2e-4,
#: int8 weights ~1.5e-3, fp8 weights ~6e-3 there; the calibration
#: report (quant/weights.py) records the model-specific number next
#: to the checkpoint). Budgets are per quantized HALF: enabling both
#: int8 KV and int8 weights budgets their sum.
DIVERGENCE_BUDGET = {"int8": 0.05, "fp8": 0.2}


@dataclass(frozen=True)
class QuantConfig:
    """What is quantized and how finely the KV scales resolve.

    Hashable + frozen on purpose: the engine threads it (inside
    EngineConfig) next to the static jit arguments, and the fleet's
    shape hash covers it — two workers disagreeing on any field are
    different engines.
    """

    kv_dtype: str = "none"        # paged KV page storage
    weight_dtype: str = "none"    # block matmul kernels
    granularity: str = "page"     # KV scale granularity (page | head)
    act_dtype: str = "none"       # W8A8: activation rows into int8
                                  # weight matmuls (int8 only; requires
                                  # weight_dtype == "int8")

    def validate(self) -> None:
        if self.kv_dtype not in QUANT_DTYPES:
            raise ValueError(f"kv_dtype must be one of {QUANT_DTYPES}, "
                             f"got {self.kv_dtype!r}")
        if self.weight_dtype not in QUANT_DTYPES:
            raise ValueError(f"weight_dtype must be one of "
                             f"{QUANT_DTYPES}, got {self.weight_dtype!r}")
        if self.granularity not in GRANULARITIES:
            raise ValueError(f"granularity must be one of "
                             f"{GRANULARITIES}, got {self.granularity!r}")
        if self.act_dtype not in ("none", "int8"):
            raise ValueError(f"act_dtype must be 'none' or 'int8', "
                             f"got {self.act_dtype!r}")
        if self.act_dtype == "int8" and self.weight_dtype != "int8":
            raise ValueError(
                "act_dtype='int8' (W8A8) requires weight_dtype='int8' — "
                "activation quantization feeds the int8 weight matmuls")

    @property
    def kv_enabled(self) -> bool:
        return self.kv_dtype != "none"

    @property
    def weight_enabled(self) -> bool:
        return self.weight_dtype != "none"

    @property
    def act_enabled(self) -> bool:
        return self.act_dtype != "none"

    @property
    def enabled(self) -> bool:
        return self.kv_enabled or self.weight_enabled or self.act_enabled


from .kv import (dequant_gathered, kv_itemsize, kv_qmax,  # noqa: E402
                 kv_store_dtype, pool_quant_mode, quantize_rows,
                 scale_bytes_per_token)
from .weights import (calibrate, load_calibration,  # noqa: E402
                      params_are_quantized, quantize_params,
                      save_calibration)

__all__ = [
    "QUANT_DTYPES", "GRANULARITIES", "DIVERGENCE_BUDGET", "QuantConfig",
    "kv_store_dtype", "kv_qmax", "kv_itemsize", "quantize_rows",
    "dequant_gathered", "pool_quant_mode", "scale_bytes_per_token",
    "quantize_params", "params_are_quantized", "calibrate",
    "save_calibration", "load_calibration",
]
