"""Weight quantization: absmax-per-output-channel int8/fp8 inference
with dequant fused into the matmuls, behind a calibration pass.

The four block kernels (qkv / attn_out / mlp_up / mlp_down) carry
~all of a decode step's parameter bytes. Each is quantized symmetrically
per OUTPUT channel: ``scale[c] = absmax(W[:, c]) / qmax``, stored as a
``<name>_scale`` float32 vector next to the int8/fp8 kernel in the
params pytree. Per-output-channel scales commute through the matmul,
so dequant is ``(x @ Wq) * scale`` — one multiply on the tiny output
row, fused by XLA into the matmul's epilogue; the full-precision
weight is never rematerialized (models.gpt._wmm is the one consumer).

Embeddings, positional table, layernorms, biases and the LM head stay
at their original precision: they are a rounding error of the byte
stream and the head's logit precision is the product's accuracy.

Calibration (``calibrate``): scales themselves are data-free (weight
absmax), but the PASS runs a short token trace through the quantized
and unquantized models and measures the logit divergence the chosen
dtype actually costs — the artifact serialized next to the checkpoint
(``save_calibration``: scales as .npz + a JSON report with the
measured max/mean |Δlogit| against the pinned budget in
quant.DIVERGENCE_BUDGET). A reloaded engine applies the SERIALIZED
scales (``load_calibration`` + ``quantize_params(scales=...)``), so
the served model is bit-identical to the calibrated artifact.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the block kernels quantized for inference (everything else keeps
#: its original dtype — see module docstring)
QUANT_KERNELS = ("qkv_kernel", "attn_out_kernel", "mlp_up_kernel",
                 "mlp_down_kernel")
SCALES_FILE = "quant_scales.npz"
REPORT_FILE = "quant_calib.json"


def _qmax(weight_dtype: str) -> float:
    return {"int8": 127.0, "fp8": 448.0}[weight_dtype]


def params_are_quantized(params) -> bool:
    # probe the QUANT_KERNELS scale keys specifically: the layernorm
    # gains (ln1_scale/ln2_scale) are ordinary params that merely end
    # in "_scale"
    blocks = params.get("blocks", {})
    return any(name + "_scale" in blocks for name in QUANT_KERNELS)


def weight_scales(params, weight_dtype: str) -> Dict[str, jnp.ndarray]:
    """Absmax-per-output-channel scales for every QUANT_KERNELS entry:
    kernel (L, Cin, Cout) -> scale (L, Cout) float32."""
    qmax = _qmax(weight_dtype)
    out = {}
    for name in QUANT_KERNELS:
        w = params["blocks"][name].astype(jnp.float32)
        out[name] = jnp.maximum(jnp.max(jnp.abs(w), axis=1) / qmax,
                                1e-8)
    return out


def quantize_params(params, weight_dtype: str,
                    scales: Optional[Dict[str, jnp.ndarray]] = None):
    """Return a params pytree with QUANT_KERNELS stored in
    ``weight_dtype`` plus ``<name>_scale`` f32 vectors. ``scales``
    applies a serialized calibration verbatim (bit-identical reload);
    None computes fresh absmax scales."""
    if weight_dtype == "none" or params_are_quantized(params):
        return params
    if scales is None:
        scales = weight_scales(params, weight_dtype)
    qmax = _qmax(weight_dtype)
    blocks = dict(params["blocks"])
    for name in QUANT_KERNELS:
        w = blocks[name].astype(jnp.float32)
        s = jnp.asarray(scales[name], jnp.float32)
        q = w / s[:, None, :]
        if weight_dtype == "int8":
            q = jnp.clip(jnp.round(q), -qmax, qmax).astype(jnp.int8)
        else:
            q = jnp.clip(q, -qmax, qmax).astype(jnp.float8_e4m3fn)
        blocks[name] = q
        blocks[name + "_scale"] = s
    out = dict(params)
    out["blocks"] = blocks
    return out


def calibrate(params, cfg, weight_dtype: str,
              calib_tokens: Optional[np.ndarray] = None,
              seed: int = 0) -> Tuple[dict, dict]:
    """The calibration pass: quantize, then measure what it costs.

    ``calib_tokens`` is a (B, T) int32 token trace (None = a seeded
    synthetic trace over the model's vocab — the zero-egress default).
    Returns ``(quantized_params, report)``; the report carries the
    scales' summary stats and the measured logit divergence on the
    trace, ready for :func:`save_calibration`."""
    from ..models.gpt import forward
    if calib_tokens is None:
        rng = np.random.default_rng(seed)
        T = min(cfg.block_size, 64)
        calib_tokens = rng.integers(0, cfg.vocab_size, (4, T),
                                    dtype=np.int64).astype(np.int32)
    scales = weight_scales(params, weight_dtype)
    qparams = quantize_params(params, weight_dtype, scales=scales)
    toks = jnp.asarray(calib_tokens)
    ref, _ = forward(params, toks, cfg)
    got, _ = forward(qparams, toks, cfg)
    # ONE host fetch of the divergence stats (calibration is offline)
    diff = np.asarray(jnp.abs(got - ref))
    report = {
        "weight_dtype": weight_dtype,
        "kernels": list(QUANT_KERNELS),
        "calib_shape": list(calib_tokens.shape),
        "max_logit_div": float(diff.max()),
        "mean_logit_div": float(diff.mean()),
        "scale_stats": {
            name: {"min": float(np.asarray(s).min()),
                   "max": float(np.asarray(s).max())}
            for name, s in scales.items()},
    }
    return qparams, report


def save_calibration(dir_path: str, params_or_scales, report: dict
                     ) -> Tuple[str, str]:
    """Serialize the calibration next to a checkpoint: the per-channel
    scales as ``quant_scales.npz`` and the report (divergence measured
    on the calibration trace, dtype, kernel list) as
    ``quant_calib.json``. Accepts quantized params (scales extracted)
    or a bare scales dict."""
    os.makedirs(dir_path, exist_ok=True)
    blocks = params_or_scales.get("blocks", params_or_scales)
    scales = {name: np.asarray(blocks[name + "_scale"]
                               if name + "_scale" in blocks
                               else blocks[name])
              for name in QUANT_KERNELS}
    npz = os.path.join(dir_path, SCALES_FILE)
    # atomic tmp+rename on BOTH files (the checkpoint manifest
    # discipline): fleet workers sharing a checkpoint dir may race
    # through prepare_params at startup, and a reader must only ever
    # see a complete artifact or none. pid-suffixed tmp so concurrent
    # writers never clobber each other's half-written file.
    tmp = f"{npz}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **scales)
    os.replace(tmp, npz)
    rep = os.path.join(dir_path, REPORT_FILE)
    tmp = f"{rep}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    os.replace(tmp, rep)
    return npz, rep


def prepare_params(params, cfg, weight_dtype: str,
                   checkpoint_dir: Optional[str] = None, log=None):
    """The CLI-side calibration workflow (serve-replay / serve /
    serve-worker): apply a calibration serialized next to the
    checkpoint when one matches ``weight_dtype`` (bit-identical
    reload), otherwise run :func:`calibrate` now and serialize the
    scales + divergence report for the next start. Engines also
    self-quantize (data-free) when handed unquantized params, so this
    helper is about the durable artifact, not correctness."""
    if weight_dtype == "none" or params_are_quantized(params):
        return params
    if checkpoint_dir:
        scales, report = load_calibration(checkpoint_dir)
        if scales is not None \
                and report.get("weight_dtype") == weight_dtype:
            if log is not None:
                log(f"weight quant: applying serialized {weight_dtype} "
                    f"calibration from {checkpoint_dir} (max logit "
                    f"div {report.get('max_logit_div', 0.0):.4g})")
            return quantize_params(
                params, weight_dtype,
                scales={k: jnp.asarray(v) for k, v in scales.items()})
    qparams, report = calibrate(params, cfg, weight_dtype)
    if log is not None:
        log(f"weight quant: calibrated {weight_dtype} "
            f"(max logit div {report['max_logit_div']:.4g} on the "
            f"calibration trace)")
    if checkpoint_dir:
        try:
            save_calibration(checkpoint_dir, qparams, report)
        except OSError as e:
            if log is not None:
                log(f"weight quant: could not serialize calibration "
                    f"({e}); serving the in-memory quantization")
    return qparams


def load_calibration(dir_path: str):
    """``(scales, report)`` of a serialized calibration, or
    ``(None, None)`` when the directory holds none — including a
    corrupt/truncated artifact (a crashed writer predating the atomic
    rename, a torn disk): the caller recalibrates instead of a worker
    dying at startup on BadZipFile."""
    import zipfile
    npz = os.path.join(dir_path, SCALES_FILE)
    rep = os.path.join(dir_path, REPORT_FILE)
    if not os.path.exists(npz):
        return None, None
    try:
        with np.load(npz) as z:
            scales = {name: z[name] for name in z.files}
        report = {}
        if os.path.exists(rep):
            with open(rep) as f:
                report = json.load(f)
    except (OSError, ValueError, KeyError, zipfile.BadZipFile,
            json.JSONDecodeError):
        return None, None
    return scales, report
