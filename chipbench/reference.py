"""The plain reference: GPT-2 as published, in straightforward ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``.

Pre-LN blocks (LayerNorm, causal softmax attention over ``n_head`` heads,
residual; LayerNorm, GELU MLP of width 4C, residual), learned positions, a
final LayerNorm and a head tied to the token embedding (Radford et al. 2019;
the tanh GELU of the released code). No kernel, no cache, no batching trick,
nothing imported from the program: it only knows the names of the program's
parameter tree (``wte``, ``wpe``, ``blocks/*`` stacked over layers, ``ln_f_*``)
because it is handed the very weights the program runs.

Departures from a textbook loop: the layers run under ``lax.scan`` over the
stacked weights, which keeps the compile of a 48-layer float32 model short,
and each block is a ``jax.checkpoint``, so that a gradient through the
reference keeps one layer's float32 attention matrices at a time and not all
of them. Neither changes any arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


def _ln(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(params, idx, n_head: int):
    """(B, T) token ids -> (B, T, C) final hidden states, float32."""
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    B, T = idx.shape
    wte, wpe = f32(params["wte"]), f32(params["wpe"])
    x = wte[idx] + wpe[:T]
    C = x.shape[-1]
    D = C // n_head
    mask = jnp.tril(jnp.ones((T, T), bool))

    def block(x, w):
        h = _ln(x, w["ln1_scale"], w["ln1_bias"])
        qkv = h @ w["qkv_kernel"] + w["qkv_bias"]
        q, k, v = (t.reshape(B, T, n_head, D).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        att = (q @ k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(D))
        att = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), axis=-1)
        y = (att @ v).transpose(0, 2, 1, 3).reshape(B, T, C)
        x = x + y @ w["attn_out_kernel"] + w["attn_out_bias"]
        h = _ln(x, w["ln2_scale"], w["ln2_bias"])
        h = _gelu(h @ w["mlp_up_kernel"] + w["mlp_up_bias"])
        return x + h @ w["mlp_down_kernel"] + w["mlp_down_bias"], None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(jax.checkpoint(block), x, f32(params["blocks"]))
        return _ln(x, f32(params["ln_f_scale"]), f32(params["ln_f_bias"]))


def logits_at(params, idx, pos, n_head: int):
    """Logits (B, N, V) at the positions ``pos`` (B, N) of each row."""
    h = hidden(params, idx, n_head)
    rows = jnp.take_along_axis(h, pos[:, :, None], axis=1)
    with jax.default_matmul_precision("highest"):
        return rows @ params["wte"].astype(jnp.float32).T


def loss(params, idx, targets, n_head: int):
    """Mean next-token cross-entropy over all (B*T) positions."""
    h = hidden(params, idx, n_head)
    with jax.default_matmul_precision("highest"):
        logits = h @ params["wte"].astype(jnp.float32).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def stream_gaps(params, n_head: int, block_size: int, prompts, streams,
                batch: int = 2):
    """Teacher-force each emitted stream through the reference. Returns, per
    stream, how far below the reference's best logit its worst token sits
    (0: every token is the reference's argmax given its own prefix)."""
    import numpy as np
    if not streams:
        return []
    n = block_size      # one shape whatever was sampled: one cached program

    @jax.jit
    def gap(params, idx, pos, toks):
        rows = logits_at(params, idx, pos, n_head)
        got = jnp.take_along_axis(rows, toks[:, :, None], axis=2)[..., 0]
        return rows.max(-1) - got

    gaps = []
    for lo in range(0, len(prompts), batch):
        idx = np.zeros((batch, block_size), np.int32)
        pos = np.zeros((batch, n), np.int32)
        toks = np.zeros((batch, n), np.int32)
        live = np.zeros((batch, n), bool)
        group = list(zip(prompts[lo:lo + batch], streams[lo:lo + batch]))
        for b, (p, s) in enumerate(group):
            seq = np.concatenate([p, s[:-1]]).astype(np.int32)
            idx[b, :len(seq)] = seq
            pos[b, :len(s)] = len(p) - 1 + np.arange(len(s))
            toks[b, :len(s)] = s
            live[b, :len(s)] = True
        g = np.where(live, np.asarray(gap(params, idx, pos, toks)), 0.0)
        gaps += [float(x) for x in g.max(1)[:len(group)]]
    return gaps
