"""The plain reference of the ``lfm2_moe`` family: LFM2-24B-A2B's layer
equations (``transformers``' ``lfm2_moe``: ``Lfm2MoeShortConv``,
``Lfm2MoeAttention``, ``Lfm2MoeSparseMoeBlock``) in straightforward
``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``. No
cache, no state, no kernel, no batching (one sequence at a time), a Python
loop over the experts, nothing imported from the program: it knows the NAMES
of the program's parameter tree because it is handed the very weights the
program runs, and takes the architecture's numbers as a plain dict
(``spec_of`` reads them off any object that has them).

Per layer ``l``, hidden ``x`` (T, C), RMSNorm eps from the spec, no biases:
``h = x + op(RMSNorm(x; norm1))``, ``x' = h + ff(RMSNorm(h; norm2))``.

- ``conv`` (the gated short convolution): ``B, C, v = split3(u conv_in)``,
  ``s = B * v``; the sequence ``s`` is padded with ``reach - 1`` zero
  columns in front and ``c_t = sum_{j < reach} conv_w[:, j] *
  padded_{t + j}`` (= ``s_{t - (reach - 1) + j}``: depthwise, causal, an
  explicit sum over the taps); ``op = (C * c) conv_out``;
- ``full_attention``: ``q = u wq`` as ``n_head`` heads of ``head_dim``,
  ``k = u wk``, ``v = u wv`` as ``n_kv_head`` heads; q and k RMS-normed over
  the head dim (``q_norm``, ``k_norm``), then rotated (rotate-half, whole
  head, theta); query head ``n`` reads KV head ``n // (n_head //
  n_kv_head)``; scores times ``head_dim ** -0.5``; a masked softmax over the
  whole sequence; ``op = concat(heads) wo``;
- dense ``ff`` (the first ``num_dense_layers`` layers): ``w_down
  (silu(w_gate m) * (w_up m))``;
- sparse ``ff``: ``s = sigmoid(m router)``, selection scores ``c = s +
  router_bias`` (``use_expert_bias``), ``T`` = the ``experts_per_token``
  largest ``c``, ``w_e = scaling * s_e / (sum_{j in T} s_j + 1e-6)``
  (``norm_topk_prob``; the 1e-6 is ``transformers``'), ``y = sum_{e in T, e
  held} w_e Expert_e(m)``: only the experts ``experts_held`` names are
  summed (all 64 in the benchmark's cut), and there is no shared expert;
- after the last layer ``logits = RMSNorm(x; norm_f) wte^T`` (tied head).

**Near ties.** Top-k over 64 sigmoid scores has ties that rounding decides.
``choices`` (the program's chosen ids, (n_sparse, T, k)) may be handed in to
be COUNTED, never used: the reference always routes by its own scores. At a
token where the two sets differ it counts a ``near_ties`` if every expert they
disagree on has a selection score within ``margin`` of the reference's own
k-th largest, and a ``mismatches`` otherwise.

Departures from the published description, none of which changes
arithmetic: the forward is an eager Python loop over layers and experts that
calls small jitted pieces (one operator, one MLP, one expert at a time),
rows go through each piece ``row_block`` at a time under ``lax.map`` (the
attention's softmax is still over the whole sequence, a block of query rows
at a time), the head's logits are reduced to the gap a block of rows at a
time (8,192 x 65,536 float32 logits would be 2 GB), and weights are cast to
float32 inside the piece that uses them: so 8,192 tokens at the published
widths fit beside 10.5 GB of bf16 parameters, one expert's float32 copy at a
time. ``transformers`` keeps a cache position and an attention mask for
padded batches; one unpadded sequence needs neither.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG = -1e30


def spec_of(cfg) -> dict:
    """The architecture's numbers, read off an object that has them."""
    return dict(
        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head or cfg.n_head,
        head_dim=cfg.head_dim, eps=cfg.layernorm_eps,
        rope_theta=cfg.rope_theta, conv_reach=cfg.conv_reach,
        layer_types=tuple(cfg.layer_types),
        mlp_layer_types=tuple(cfg.mlp_layer_types),
        n_experts=cfg.n_experts, experts_held=tuple(cfg.experts_held),
        experts_per_token=cfg.experts_per_token,
        routed_scaling=cfg.routed_scaling,
        router_norm_eps=cfg.router_norm_eps)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(gain)


def _rope(x, theta):
    """x (T, H, D) at positions 0..T-1, rotate-half over the whole head."""
    T, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rows(fn, x, row_block):
    """``fn`` over blocks of the rows of x (an array (T, ...) or a tuple of
    them); T a multiple of the block."""
    leaves = jax.tree_util.tree_leaves(x)
    T = leaves[0].shape[0]
    if T <= row_block:
        return fn(x)
    assert T % row_block == 0, (T, row_block)
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((T // row_block, row_block) + a.shape[1:]), x)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((T,) + a.shape[2:]), jax.lax.map(fn, blocks))


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) @ _f32(w_down)


def _attention(q, k, v, row_block):
    """q (T, Hq, D), k, v (T, Hkv, D) -> (T, Hq * D). One KV head and the
    G query heads that read it at a time (query head n reads n // G)."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    kpos = jnp.arange(T)
    groups = []
    for g in range(Hkv):
        kn, vn = k[:, g], v[:, g]

        def block(args, kn=kn, vn=vn):
            qb, qpos = args                                   # (blk, G, D)
            s = jnp.einsum("qgd,kd->gqk", qb, kn) * D ** -0.5
            p = jax.nn.softmax(
                jnp.where(kpos[None] <= qpos[:, None], s, NEG), -1)
            return jnp.einsum("gqk,kd->qgd", p, vn)

        groups.append(_rows(block, (q[:, g * G:(g + 1) * G], kpos),
                            row_block))
    return jnp.concatenate(groups, 1).reshape(T, Hq * D)


def _chosen(c, k, n_experts):
    """(T, E) bool of the k largest of c, and the k-th largest value."""
    vals, ids = jax.lax.top_k(c, k)
    return (ids[..., None] == jnp.arange(n_experts)).any(-2), vals[:, -1:]


@partial(jax.jit, static_argnames=("Hq", "Hkv", "D", "eps", "theta",
                                   "row_block"))
def _attention_half(x, lp, *, Hq, Hkv, D, eps, theta, row_block):
    """``x + Attention(RMSNorm(x))`` of one full layer; lp its leaves."""
    T = x.shape[0]
    with jax.default_matmul_precision("highest"):
        def qkv(h):
            a = _rms(h, lp["norm1"], eps)
            return (a @ _f32(lp["wq"]), a @ _f32(lp["wk"]),
                    a @ _f32(lp["wv"]))

        q, k, v = _rows(qkv, x, row_block)
        q = _rope(_rms(q.reshape(T, Hq, D), lp["q_norm"], eps), theta)
        k = _rope(_rms(k.reshape(T, Hkv, D), lp["k_norm"], eps), theta)
        att = _attention(q, k, v.reshape(T, Hkv, D), row_block)
        return x + _rows(lambda r: r @ _f32(lp["wo"]), att, row_block)


@partial(jax.jit, static_argnames=("eps", "reach", "row_block"))
def _conv_half(x, lp, *, eps, reach, row_block):
    """``x + ShortConv(RMSNorm(x))`` of one conv layer; lp its leaves."""
    T, C = x.shape
    with jax.default_matmul_precision("highest"):
        bcv = _rows(lambda r: _rms(r, lp["norm1"], eps)
                    @ _f32(lp["conv_in"]), x, row_block)
        b, c, v = bcv[:, :C], bcv[:, C:2 * C], bcv[:, 2 * C:]
        padded = jnp.concatenate([jnp.zeros((reach - 1, C), jnp.float32),
                                  b * v])
        w = _f32(lp["conv_w"])                                # (C, reach)
        conv = sum(w[:, j] * padded[j:j + T] for j in range(reach))
        return x + _rows(lambda r: r @ _f32(lp["conv_out"]), c * conv,
                         row_block)


@partial(jax.jit, static_argnames=("eps", "row_block"))
def _gated_mlp(x, norm2, w_gate, w_up, w_down, *, eps, row_block):
    """``w_down (silu(w_gate m) * (w_up m))`` of ``m = RMSNorm(x)``: the
    dense MLP and ONE routed expert alike."""
    with jax.default_matmul_precision("highest"):
        return _rows(lambda r: _swiglu(_rms(r, norm2, eps), w_gate, w_up,
                                       w_down), x, row_block)


@partial(jax.jit, static_argnames=("eps", "k", "scaling", "norm_eps"))
def _router(x, norm2, router, bias, theirs, margin, counted, *, eps, k,
            scaling, norm_eps):
    """Weights (T, E) of the chosen experts (0 elsewhere), normalised over
    the k chosen, times the scaling; and the counts of near ties and of
    mismatches against ``theirs``, the program's chosen ids (T, k) or
    None (module docstring)."""
    E = router.shape[1]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(_rms(x, norm2, eps) @ _f32(router))
    c = s + _f32(bias)
    mask, cutoff = _chosen(c, k, E)
    near = miss = jnp.int32(0)
    if theirs is not None:
        differ = mask != (theirs[..., None] == jnp.arange(E)).any(-2)
        close = (~differ | (jnp.abs(c - cutoff) < margin)).all(-1)
        near = (differ.any(-1) & close & counted).sum()
        miss = (differ.any(-1) & ~close & counted).sum()
    w = jnp.where(mask, s, 0.0)
    return w / (w.sum(-1, keepdims=True) + norm_eps) * scaling, near, miss


def hidden(params, idx, spec, *, choices=None, margin=0.0,
           row_block=1024, n_rows=None):
    """(T,) ids -> ((T, C) final hidden states before the last norm,
    ``{"near_ties", "mismatches"}`` counts over (token, sparse layer) of
    the first ``n_rows`` tokens: the rest is padding)."""
    eps = spec["eps"]
    idx = jnp.asarray(idx)
    T = idx.shape[0]
    counted = jnp.arange(T) < (T if n_rows is None else n_rows)
    near = miss = 0
    n_sparse = 0
    x = _f32(params["wte"][idx])
    for i, lp in enumerate(params["layers"]):
        if spec["layer_types"][i] == "conv":
            x = _conv_half(
                x, {n: lp[n] for n in ("norm1", "conv_in", "conv_w",
                                       "conv_out")},
                eps=eps, reach=spec["conv_reach"], row_block=row_block)
        else:
            x = _attention_half(
                x, {n: lp[n] for n in ("norm1", "wq", "wk", "wv", "q_norm",
                                       "k_norm", "wo")},
                Hq=spec["n_head"], Hkv=spec["n_kv_head"],
                D=spec["head_dim"], eps=eps, theta=spec["rope_theta"],
                row_block=row_block)
        mlp = partial(_gated_mlp, x, lp["norm2"], eps=eps,
                      row_block=row_block)
        if spec["mlp_layer_types"][i] == "dense":
            y = mlp(lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            w, n, m = _router(
                x, lp["norm2"], lp["router"], lp["router_bias"],
                None if choices is None else choices[n_sparse], margin,
                counted, eps=eps, k=spec["experts_per_token"],
                scaling=spec["routed_scaling"],
                norm_eps=spec["router_norm_eps"])
            near, miss, n_sparse = near + n, miss + m, n_sparse + 1
            y = jnp.zeros_like(x)
            for at, e in enumerate(spec["experts_held"]):   # one at a time
                y = y + w[:, e:e + 1] * mlp(lp["e_gate"][at],
                                            lp["e_up"][at],
                                            lp["e_down"][at])
        x = x + y
    return x, {"near_ties": near, "mismatches": miss}


@partial(jax.jit, static_argnames=("eps",))
def _head(h, norm_f, wte, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(h, norm_f, eps) @ _f32(wte).T


@partial(jax.jit, static_argnames=("eps", "row_block"))
def _gap(h, pos, toks, norm_f, wte, *, eps, row_block):
    """How far below the row's best logit the logit of ``toks`` sits, at
    the rows ``pos`` of h, ``row_block`` rows of logits at a time."""
    def block(args):
        hb, tb = args
        rows = _head(hb, norm_f, wte, eps=eps)
        return rows.max(-1) - jnp.take_along_axis(rows, tb[:, None], 1)[:, 0]
    return _rows(block, (h[pos], toks), row_block)


def logits(params, idx, spec, **kw):
    """(T,) ids -> ((T, V) logits, counts)."""
    h, counts = hidden(params, idx, spec, **kw)
    return _head(h, params["norm_f"], params["wte"],
                 eps=spec["eps"]), counts


def token_gaps(params, spec, pad_to: int, prompt, stream, *, choices=None,
               margin=0.0, row_block=1024):
    """Teacher-force ONE emitted stream through the reference, padded to
    ``pad_to`` tokens (one set of compiled pieces whatever the lengths):
    how far below the reference's best logit each token of the stream
    sits, given its own prefix (0: it is the reference's argmax), and the
    near-tie counts. ``choices``: the program's chosen ids for this
    sequence, (n_sparse, pad_to, k), or None."""
    import numpy as np
    p, s = np.asarray(prompt), np.asarray(stream)
    seq = np.concatenate([p, s[:-1]]).astype(np.int32)
    idx = np.zeros((pad_to,), np.int32)
    idx[:len(seq)] = seq
    h, counts = hidden(params, idx, spec, margin=margin, choices=choices,
                       row_block=row_block, n_rows=len(seq))
    pos = np.zeros((pad_to,), np.int32)
    toks = np.zeros((pad_to,), np.int32)
    pos[:len(s)] = len(p) - 1 + np.arange(len(s))
    toks[:len(s)] = s
    g = _gap(h, pos, toks, params["norm_f"], params["wte"],
             eps=spec["eps"], row_block=row_block)
    return np.asarray(g)[:len(s)], {n: int(v) for n, v in counts.items()}


def stream_gaps(params, spec, pad_to: int, prompts, streams, *,
                choices=None, margin=0.0, row_block=1024):
    """``token_gaps`` of every stream: ``(worst gap of each stream, mean
    gap over all their tokens, summed near-tie counts)``."""
    import numpy as np
    rows, total = [], {"near_ties": 0, "mismatches": 0}
    for i, (p, s) in enumerate(zip(prompts, streams)):
        g, counts = token_gaps(
            params, spec, pad_to, p, s, margin=margin, row_block=row_block,
            choices=None if choices is None else choices[i])
        rows.append(g)
        for name in total:
            total[name] += counts[name]
    return ([float(g.max()) for g in rows],
            float(np.concatenate(rows).mean()) if rows else None, total)
