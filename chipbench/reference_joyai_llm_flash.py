"""The plain reference of the ``joyai_llm_flash`` family: JoyAI-LLM-Flash's
layer equations (its ``config.json`` names the DeepSeek-V3 lineage: latent
attention, ``noaux_tc`` sigmoid routing, a shared expert) in straightforward
``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``. No
cache, no kernel, no batching (one sequence at a time), the EXPANDED form of
the attention only, a Python loop over the experts, nothing imported from
the program: it knows the NAMES of the program's parameter tree because it
is handed the very weights the program runs, and takes the architecture's
numbers as a plain dict (``spec_of`` reads them off any object that has
them).

Per layer, hidden h (T, C), RMSNorm eps from the spec (1e-6), no biases; H
heads, ranks ``q_lora_rank`` / ``kv_lora_rank`` (R), head sizes ``qk_nope``
(Dn), ``qk_rope`` (Dr), ``v_head`` (Dv):

- ``a = RMSNorm(h; norm1)``;
- ``c_q = RMSNorm(a q_a; q_a_norm)``, ``q = c_q q_b`` as H heads of
  ``[q_nope (Dn) | q_rope (Dr)]``;
- ``[c | k_rope] = a kv_a`` (R + Dr), ``c = RMSNorm(c; kv_a_norm)``;
- ``q_rope`` (every head) and ``k_rope`` (ONE, shared by every head) turned
  by INTERLEAVED RoPE: the pair (2i, 2i+1) by the angle ``t * theta ** (-2i
  / Dr)`` at position t (``rope_interleave``; ``rope_scaling`` null: no
  YaRN factor and no mscale);
- ``[k_nope | v]_h = c kv_b`` (H x (Dn + Dv));
- ``s_h(i, j) = (q_nope,h(i) . k_nope,h(j) + q_rope,h(i) . k_rope(j)) /
  sqrt(Dn + Dr)``, causal; ``o = concat_h softmax(s_h) v_h``;
  ``h = h + o wo``;
- ``m = RMSNorm(h; norm2)``; layer 0 (``first_k_dense_replace`` 1): ``y =
  w_down (silu(w_gate m) * (w_up m))`` (7,168 wide at the published
  sizes); every later layer: ``s = sigmoid(m router)`` (float32), the
  experts chosen by the ``experts_per_token`` largest ``s + router_bias``
  (``noaux_tc``: with ``n_group`` = ``topk_group`` = 1 the group step
  keeps every expert), ``w_e = routed_scaling * s_e / (sum_{chosen} s +
  1e-20)`` (``norm_topk_prob``), ``y = sum_{e chosen and held} w_e
  Expert_e(m) + Shared(m)``: only the experts ``experts_held`` names are
  summed (the chip's share: 32 of 256 in the benchmark's cut), the shared
  expert once; ``h = h + y``;
- after the last layer ``logits = RMSNorm(h; norm_f) lm_head`` (untied).

The program DECODES in the absorbed form (``q~_h = q_nope,h W_UK,h^T``
against the cached ``[c | k_rope]``, ``(sum_j p_j c_j) W_UV,h``), which is
the same arithmetic reassociated; the reference never absorbs, so that the
comparison covers the absorption too.

**Near ties.** Top-k over 256 sigmoid scores has ties that rounding
decides. ``choices`` (the program's chosen ids, (n_sparse, T, k)) may be
handed in to be COUNTED, never used: the reference always routes by its own
scores. At a token where the two sets differ it counts a ``near_ties`` if
every expert they disagree on has a selection score within ``margin`` of the
reference's own k-th largest, and a ``mismatches`` otherwise.

Departures from the published description, none of which changes
arithmetic: the forward is an eager Python loop over layers and experts that
calls small jitted pieces, rows go through each piece ``row_block`` at a
time under ``lax.map`` and attention one head at a time (the softmax is
still over the whole sequence, a block of query rows at a time), the head's
logits are reduced to the gap a block of rows at a time, and weights are
cast to float32 inside the piece that uses them: so 8,192 tokens at the
published widths fit beside the share's bf16 parameters.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG = -1e30


def spec_of(cfg) -> dict:
    """The architecture's numbers, read off an object that has them."""
    return dict(
        n_head=cfg.n_head, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope=cfg.qk_nope_head_dim,
        qk_rope=cfg.qk_rope_head_dim, v_head=cfg.v_head_dim,
        eps=cfg.layernorm_eps, rope_theta=cfg.rope_theta,
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


def _rope_pairs(x, theta):
    """x (T, H, D) at positions 0..T-1: the pair (2i, 2i+1) turned by
    ``t * theta ** (-2i / D)``."""
    T, H, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(T, H, D)


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


def _attention(qn, qr, kn, kr, v, scale, row_block):
    """q_nope (T, H, Dn), q_rope (T, H, Dr), k_nope (T, H, Dn), the shared
    k_rope (T, Dr), v (T, H, Dv) -> (T, H * Dv), one head at a time."""
    T = qn.shape[0]
    kpos = jnp.arange(T)

    def head(args):
        qnh, qrh, knh, vh = args

        def block(bargs):
            qb, rb, qpos = bargs
            s = (qb @ knh.T + rb @ kr.T) * scale
            p = jax.nn.softmax(
                jnp.where(kpos[None] <= qpos[:, None], s, NEG), -1)
            return p @ vh

        return _rows(block, (qnh, qrh, kpos), row_block)

    heads = jax.lax.map(head, tuple(jnp.swapaxes(t, 0, 1)
                                    for t in (qn, qr, kn, v)))
    return jnp.swapaxes(heads, 0, 1).reshape(T, -1)


@partial(jax.jit, static_argnames=("H", "R", "Dn", "Dr", "Dv", "eps",
                                   "theta", "row_block"))
def _attention_half(x, lp, *, H, R, Dn, Dr, Dv, eps, theta, row_block):
    """``x + Attention(RMSNorm(x))`` of one latent-attention layer."""
    T = x.shape[0]
    with jax.default_matmul_precision("highest"):
        def project(h):
            a = _rms(h, lp["norm1"], eps)
            q = _rms(a @ _f32(lp["q_a"]), lp["q_a_norm"], eps) \
                @ _f32(lp["q_b"])
            return q, a @ _f32(lp["kv_a"])

        q, ckv = _rows(project, x, row_block)
        q = q.reshape(T, H, Dn + Dr)
        c = _rms(ckv[:, :R], lp["kv_a_norm"], eps)
        kr = _rope_pairs(ckv[:, None, R:], theta)[:, 0]
        kv = _rows(lambda r: r @ _f32(lp["kv_b"]), c, row_block)
        kv = kv.reshape(T, H, Dn + Dv)
        att = _attention(q[..., :Dn], _rope_pairs(q[..., Dn:], theta),
                         kv[..., :Dn], kr, kv[..., Dn:],
                         (Dn + Dr) ** -0.5, row_block)
        return x + _rows(lambda r: r @ _f32(lp["wo"]), att, row_block)


@partial(jax.jit, static_argnames=("eps", "row_block"))
def _gated_mlp(x, norm2, w_gate, w_up, w_down, *, eps, row_block):
    """``w_down (silu(w_gate m) * (w_up m))`` of ``m = RMSNorm(x)``: the
    dense MLP, ONE routed expert and the shared expert alike."""
    with jax.default_matmul_precision("highest"):
        return _rows(lambda r: _swiglu(_rms(r, norm2, eps), w_gate, w_up,
                                       w_down), x, row_block)


def _chosen(c, k, n_experts):
    """(T, E) bool of the k largest of c, and the k-th largest value."""
    vals, ids = jax.lax.top_k(c, k)
    return (ids[..., None] == jnp.arange(n_experts)).any(-2), vals[:, -1:]


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
        x = _attention_half(
            x, {n: lp[n] for n in ("norm1", "q_a", "q_a_norm", "q_b",
                                   "kv_a", "kv_a_norm", "kv_b", "wo")},
            H=spec["n_head"], R=spec["kv_lora_rank"], Dn=spec["qk_nope"],
            Dr=spec["qk_rope"], Dv=spec["v_head"], eps=eps,
            theta=spec["rope_theta"], row_block=row_block)
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
            y = mlp(lp["s_gate"], lp["s_up"], lp["s_down"])   # shared, once
            for at, e in enumerate(spec["experts_held"]):   # one at a time
                y = y + w[:, e:e + 1] * mlp(lp["e_gate"][at],
                                            lp["e_up"][at],
                                            lp["e_down"][at])
        x = x + y
    return x, {"near_ties": near, "mismatches": miss}


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
    return _rows(block, (h[pos], toks), row_block)


def logits(params, idx, spec, **kw):
    """(T,) ids -> ((T, V) logits, counts)."""
    h, counts = hidden(params, idx, spec, **kw)
    return _head(h, params["norm_f"], params["lm_head"],
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
    g = _gap(h, pos, toks, params["norm_f"], params["lm_head"],
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
