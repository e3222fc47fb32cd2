"""Ulysses-style sequence parallelism: all-to-all head<->sequence resharding
around the attention core (the DeepSpeed-Ulysses recipe, re-expressed as XLA
``lax.all_to_all`` over the 'seq' mesh axis).

Alternative to ring attention (parallel/ring_attention.py) for the same
capability gap — the reference's hard single-device sequence cap
(GPT1.py:106, GPT-2.py:109). Where the ring keeps queries resident and
rotates KV chunks hop-by-hop, Ulysses does one all-to-all that trades the
sequence sharding for a head sharding: each device goes from holding
(B, H, T/n, D) — all heads, a sequence slice — to (B, H/n, T, D) — a head
slice, the full sequence — runs an ordinary *local* causal attention
(einsum or the Pallas flash kernel, since it now sees the whole sequence),
and a second all-to-all restores the sequence sharding. Two collectives per
attention call, both pure ICI all-to-alls, vs the ring's n ppermute hops;
requires local head count divisible by the seq axis size (the ring has no
such constraint).

Composable with tensor parallelism: heads arrive already sharded over
'model', and Ulysses further splits the *local* head dim over 'seq'.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import full_causal_attention


def _ulysses_local(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   key: Optional[jax.Array] = None, *,
                   axis_name: str, scale: Optional[float], impl: str,
                   dropout_rate: float = 0.0) -> jnp.ndarray:
    n = axis_size(axis_name)
    H = q.shape[1]
    assert H % n == 0, (
        f"Ulysses needs local head count {H} divisible by seq axis {n} "
        f"(use ring attention otherwise)")
    # seq-sharded (B, H, T/n, D) -> head-sharded (B, H/n, T, D)
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=1, concat_axis=2, tiled=True)
    qh, kh, vh = a2a(q), a2a(k), a2a(v)
    if key is not None:
        # every device holds a distinct (batch, head-group) after the
        # all-to-all and emits only its own output shard, so masks
        # decorrelate over all three sharded axes
        shard = ((jax.lax.axis_index("data") * axis_size("model")
                  + jax.lax.axis_index("model")) * n
                 + jax.lax.axis_index(axis_name))
        key = jax.random.fold_in(key, shard)
    # full sequence locally -> plain causal mask is globally correct;
    # dropout runs in the local core (in-kernel on the flash path)
    out = full_causal_attention(qh, kh, vh, scale=scale, impl=impl,
                                dropout_rate=dropout_rate, rng=key,
                                train=key is not None)
    return jax.lax.all_to_all(out, axis_name=axis_name, split_axis=2,
                              concat_axis=1, tiled=True)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      mesh: Mesh, scale: Optional[float] = None,
                      seq_axis: str = "seq", impl: str = "einsum",
                      dropout_rate: float = 0.0,
                      rng: Optional[jax.Array] = None,
                      train: bool = False) -> jnp.ndarray:
    """Causal attention over a 'seq'-sharded sequence via head all-to-all.

    q, k, v: global (B, H, T, D), T sharded over ``seq_axis`` (B over
    'data', H over 'model'). Same contract as
    ``ring_attention.ring_attention``, including in-core attention-weight
    dropout when ``dropout_rate`` > 0 with ``rng`` while training.
    """
    spec = P("data", "model", seq_axis, None)
    local = functools.partial(_ulysses_local, axis_name=seq_axis,
                              scale=scale, impl=impl,
                              dropout_rate=dropout_rate)
    if not (train and dropout_rate > 0.0 and rng is not None):
        fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        return fn(q, k, v)
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec, P()),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v, rng)


def make_ulysses_attention_fn(mesh: Mesh, scale: Optional[float] = None,
                              impl: str = "einsum",
                              dropout_rate: float = 0.0):
    """attention_fn for ``models.gpt.forward`` / ``train.steps``."""
    def attention_fn(q, k, v, rng=None, train=False):
        return ulysses_attention(q, k, v, mesh=mesh, scale=scale, impl=impl,
                                 dropout_rate=dropout_rate, rng=rng,
                                 train=train)
    return attention_fn
