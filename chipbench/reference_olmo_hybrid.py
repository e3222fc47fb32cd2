"""The plain reference of the ``olmo_hybrid`` family: Olmo-Hybrid-7B's layer
equations (OLMo 3's post-norm blocks, ``transformers``' ``olmo3``; the
Gated DeltaNet rule as ``transformers``' ``qwen3_next``
``torch_recurrent_gated_delta_rule`` writes it) in straightforward
``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``.
No cache, no kernel, no chunking, no batching (one sequence at a time), the
recurrence a ``lax.scan`` over positions, one token a turn, nothing imported
from the program: it knows the NAMES of the program's parameter tree
because it is handed the very weights the program runs, and takes the
architecture's numbers as a plain dict (``spec_of`` reads them off any
object that has them).

Per layer ``l``, hidden ``x`` (T, C), RMSNorm eps from the spec, no biases:
``h = x + RMSNorm(op(x); norm1)``, ``x' = h + RMSNorm(ff(h); norm2)``,
``ff(h) = w_down (silu(w_gate h) * (w_up h))``.

- ``linear_attention`` (Gated DeltaNet): ``[q | k | v] = [x wq | x wk | x
  wv]`` padded with ``taps - 1`` zero rows in front, ``c_t = sum_j
  qkv_conv[:, j] * padded_{t + j}`` (depthwise, causal), SiLU; per head
  ``q <- q rsqrt(|q|^2 + 1e-6) * d_k ** -0.5``, ``k <- k rsqrt(|k|^2 +
  1e-6)``; ``beta = sigmoid(x wb)``, times 2 where negative eigenvalues
  are allowed; ``g = -exp(A_log) softplus(x wa + dt_bias)``; from ``S = 0``
  (H, d_k, d_v) for every position t: ``S <- exp(g_t) S``, ``kv = sum_i
  S[i] k_t[i]``, ``S <- S + k_t (beta_t (v_t - kv))^T``, ``o_t = sum_i S[i]
  q_t[i]``; ``op = (RMSNorm_dv(o_h; o_norm) * silu(x wg)_h) wo``;
- ``full_attention``: ``q = RMSNorm(x wq; q_norm)`` and ``k = RMSNorm(x wk;
  k_norm)`` over the whole projection, ``v = x wv``, ``n_head`` heads of
  ``head_dim``, scores times ``head_dim ** -0.5``, a causal softmax over the
  whole sequence, no position; ``op = concat(heads) wo``;
- after the last layer ``logits = RMSNorm(x; norm_f) lm_head``.

Departures from the published description, none of which changes
arithmetic: the forward is an eager Python loop over layers that calls
small jitted pieces, attention goes a head at a time, the head's logits are
reduced to the gap a block of rows at a time (4,096 x 100,352 float32
logits would be 1.6 GB), and weights are cast to float32 inside the piece
that uses them. ``transformers`` keeps a cache position and an attention
mask for padded batches; one unpadded sequence needs neither.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG = -1e30


def spec_of(cfg) -> dict:
    """The architecture's numbers, read off an object that has them."""
    return dict(
        n_head=cfg.n_head, head_dim=cfg.head_dim, eps=cfg.layernorm_eps,
        layer_types=tuple(cfg.layer_types),
        heads=cfg.linear_num_value_heads, dk=cfg.linear_key_head_dim,
        dv=cfg.linear_value_head_dim, taps=cfg.linear_conv_kernel_dim,
        neg_eigval=bool(cfg.linear_allow_neg_eigval))


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(gain)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, state_dtype: str = "float32"):
    """The delta rule over one sequence from a zero state, one token a
    turn: q, k (T, H, dk) before their l2 norms, v (T, H, dv), g (T, H) the
    log decay, beta (T, H) -> (T, H, dv). What ``transformers``'
    ``torch_recurrent_gated_delta_rule`` computes with
    ``use_qk_l2norm_in_kernel``. ``state_dtype`` "bfloat16" rounds the
    state to bfloat16 after every token (a control of the state's
    precision, not the model)."""
    q = _l2(_f32(q)) * q.shape[-1] ** -0.5
    k = _l2(_f32(k))
    H, dk, dv = k.shape[1], k.shape[2], v.shape[2]
    bits = {"float32": None, "bfloat16": (8, 7)}[state_dtype]

    def turn(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        kv = jnp.sum(S * k_t[:, :, None], 1)
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - kv))[:, None, :]
        if bits:      # a rounding XLA may not drop, as it may a convert pair
            S = jax.lax.reduce_precision(S, *bits)
        return S, jnp.sum(S * q_t[:, :, None], 1)

    _, o = jax.lax.scan(turn, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, _f32(v), _f32(g), _f32(beta)))
    return o


@partial(jax.jit, static_argnames=("H", "dk", "dv", "taps", "eps", "neg",
                                   "state_dtype"))
def _linear_half(x, lp, *, H, dk, dv, taps, eps, neg, state_dtype):
    """``x + RMSNorm(GatedDeltaNet(x))`` of one linear layer."""
    T = x.shape[0]
    with jax.default_matmul_precision("highest"):
        qkv = jnp.concatenate([x @ _f32(lp["wq"]), x @ _f32(lp["wk"]),
                               x @ _f32(lp["wv"])], -1)
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, qkv.shape[1]), jnp.float32), qkv])
        w = _f32(lp["qkv_conv"])                        # (channels, taps)
        c = jax.nn.silu(sum(w[:, j] * padded[j:j + T] for j in range(taps)))
        q = c[:, :H * dk].reshape(T, H, dk)
        k = c[:, H * dk:2 * H * dk].reshape(T, H, dk)
        v = c[:, 2 * H * dk:].reshape(T, H, dv)
        beta = jax.nn.sigmoid(x @ _f32(lp["wb"])) * (2.0 if neg else 1.0)
        g = -jnp.exp(_f32(lp["A_log"])) * jax.nn.softplus(
            x @ _f32(lp["wa"]) + _f32(lp["dt_bias"]))
        o = recurrence(q, k, v, g, beta, state_dtype)
        gate = jax.nn.silu(x @ _f32(lp["wg"])).reshape(T, H, dv)
        y = (_rms(o, lp["o_norm"], eps) * gate).reshape(T, H * dv) \
            @ _f32(lp["wo"])
        return x + _rms(y, lp["norm1"], eps)


@partial(jax.jit, static_argnames=("H", "D", "eps"))
def _full_half(x, lp, *, H, D, eps):
    """``x + RMSNorm(Attention(x))`` of one full layer, a head at a time."""
    T = x.shape[0]
    pos = jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        q = _rms(x @ _f32(lp["wq"]), lp["q_norm"], eps).reshape(T, H, D)
        k = _rms(x @ _f32(lp["wk"]), lp["k_norm"], eps).reshape(T, H, D)
        v = (x @ _f32(lp["wv"])).reshape(T, H, D)

        def head(qkv):
            qh, kh, vh = qkv
            s = (qh @ kh.T) * D ** -0.5
            p = jax.nn.softmax(jnp.where(pos[None] <= pos[:, None], s, NEG),
                               -1)
            return p @ vh

        att = jax.lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                                 v.swapaxes(0, 1)))         # (H, T, D)
        y = att.swapaxes(0, 1).reshape(T, H * D) @ _f32(lp["wo"])
        return x + _rms(y, lp["norm1"], eps)


@partial(jax.jit, static_argnames=("eps",))
def _mlp_half(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(x @ _f32(lp["w_gate"])) * (x @ _f32(lp["w_up"]))) \
            @ _f32(lp["w_down"])
        return x + _rms(y, lp["norm2"], eps)


def hidden(params, idx, spec):
    """(T,) ids -> (T, C) final hidden states before the last norm."""
    eps = spec["eps"]
    x = _f32(params["wte"][jnp.asarray(idx)])
    for i, lp in enumerate(params["layers"]):
        if spec["layer_types"][i] == "linear_attention":
            x = _linear_half(
                x, {n: lp[n] for n in ("wq", "wk", "wv", "wg", "wa", "wb",
                                       "A_log", "dt_bias", "qkv_conv",
                                       "o_norm", "wo", "norm1")},
                H=spec["heads"], dk=spec["dk"], dv=spec["dv"],
                taps=spec["taps"], eps=eps, neg=spec["neg_eigval"],
                state_dtype=spec.get("state_dtype", "float32"))
        else:
            x = _full_half(
                x, {n: lp[n] for n in ("wq", "wk", "wv", "wo", "q_norm",
                                       "k_norm", "norm1")},
                H=spec["n_head"], D=spec["head_dim"], eps=eps)
        x = _mlp_half(x, {n: lp[n] for n in ("w_gate", "w_up", "w_down",
                                             "norm2")}, eps=eps)
    return x


@partial(jax.jit, static_argnames=("eps",))
def _head(h, norm_f, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(h, norm_f, eps) @ _f32(lm_head)


@partial(jax.jit, static_argnames=("eps", "row_block"))
def _gap(h, pos, toks, norm_f, lm_head, *, eps, row_block):
    """How far below the row's best logit the logit of ``toks`` sits, at
    the rows ``pos`` of h, ``row_block`` rows of logits at a time."""
    def block(args):
        hb, tb = args
        rows = _head(hb, norm_f, lm_head, eps=eps)
        return rows.max(-1) - jnp.take_along_axis(rows, tb[:, None], 1)[:, 0]

    hp = h[pos]
    T = hp.shape[0]
    if T <= row_block:
        return block((hp, toks))
    return jax.lax.map(block, (hp.reshape(T // row_block, row_block, -1),
                               toks.reshape(T // row_block, row_block))
                       ).reshape(T)


def logits(params, idx, spec):
    """(T,) ids -> (T, V) logits."""
    return _head(hidden(params, idx, spec), params["norm_f"],
                 params["lm_head"], eps=spec["eps"])


def token_gaps(params, spec, pad_to: int, prompt, stream, *,
               row_block=1024):
    """Teacher-force ONE emitted stream through the reference, padded to
    ``pad_to`` tokens (one set of compiled pieces whatever the lengths):
    how far below the reference's best logit each token of the stream
    sits, given its own prefix (0: it is the reference's argmax)."""
    import numpy as np
    p, s = np.asarray(prompt), np.asarray(stream)
    seq = np.concatenate([p, s[:-1]]).astype(np.int32)
    idx = np.zeros((pad_to,), np.int32)
    idx[:len(seq)] = seq
    h = hidden(params, idx, spec)
    pos = np.zeros((pad_to,), np.int32)
    toks = np.zeros((pad_to,), np.int32)
    pos[:len(s)] = len(p) - 1 + np.arange(len(s))
    toks[:len(s)] = s
    g = _gap(h, pos, toks, params["norm_f"], params["lm_head"],
             eps=spec["eps"], row_block=min(row_block, pad_to))
    return np.asarray(g)[:len(s)]


def stream_gaps(params, spec, pad_to: int, prompts, streams, *,
                row_block=1024):
    """``token_gaps`` of every stream: ``(worst gap of each stream, mean
    gap over all their tokens)``."""
    import numpy as np
    rows = [token_gaps(params, spec, pad_to, p, s, row_block=row_block)
            for p, s in zip(prompts, streams)]
    return ([float(g.max()) for g in rows],
            float(np.concatenate(rows).mean()) if rows else None)
