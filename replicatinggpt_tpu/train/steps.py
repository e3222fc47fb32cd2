"""Jitted train / eval steps.

One compiled ``train_step(state, batch) -> (state, metrics)`` replaces the
reference's eager zero_grad/forward/backward/step sequence (GPT1.py:227-233,
GPT-2.py:223-228); a jitted K-batch eval replaces ``estimate_loss``
(GPT1.py:85-98) — same semantics (dropout off, mean over eval_iters fresh
batches per split) but compiled, so the 400-forwards-per-eval cost
(SURVEY.md §3.3) stops dominating wall-clock.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig, TrainConfig
from ..models.gpt import forward
from .state import TrainState, make_optimizer


def loss_fn(params, batch, cfg: ModelConfig, rng=None, train=False,
            attention_fn=None, blocks_fn=None):
    x, y = batch
    # tokens may arrive as uint8/uint16 (narrow host->device transfers —
    # the loaders pick the smallest dtype covering the vocab); widen on
    # device where the cast is free
    if x.dtype != jnp.int32:
        x = x.astype(jnp.int32)
    if y.dtype != jnp.int32:
        y = y.astype(jnp.int32)
    _, loss = forward(params, x, cfg, targets=y, rng=rng, train=train,
                      attention_fn=attention_fn, blocks_fn=blocks_fn)
    return loss


def _accum_grads(params, batch, *, mcfg: ModelConfig, rng, train,
                 attention_fn, blocks_fn, accum: int):
    """Mean loss/grads over ``accum`` stacked microbatches (each array of
    ``batch`` is (accum, b, T)) via an on-device ``lax.scan`` — one
    microbatch's activations live at a time, so the effective batch
    ``accum * b`` costs single-microbatch activation memory. Equal-sized
    microbatches make the mean-of-means identical to the full-batch mean."""
    vg = jax.value_and_grad(loss_fn)

    def body(carry, xs):
        loss_sum, gsum = carry
        mb, j = xs
        loss, g = vg(params, mb, mcfg,
                     rng=None if rng is None else jax.random.fold_in(rng, j),
                     train=train, attention_fn=attention_fn,
                     blocks_fn=blocks_fn)
        with jax.named_scope("grad_accum"):
            return (loss_sum + loss,
                    jax.tree_util.tree_map(jnp.add, gsum, g)), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss_sum, gsum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros),
        (batch, jnp.arange(accum)), length=accum)
    inv = 1.0 / accum
    with jax.named_scope("grad_accum"):
        return (loss_sum * inv,
                jax.tree_util.tree_map(lambda g: g * inv, gsum))


def _one_step(state: TrainState, batch, *, mcfg: ModelConfig, optimizer,
              with_grad_norm: bool, attention_fn, blocks_fn, accum: int = 1
              ) -> Tuple[TrainState, Dict[str, Any]]:
    """The single optimizer step shared by make_train_step (jitted 1:1) and
    make_train_scan (scanned K:1) — one body, so the two dispatch shapes
    cannot drift apart semantically."""
    rng = jax.random.fold_in(state.rng, state.step)
    train = mcfg.dropout > 0 or mcfg.attn_dropout > 0
    if accum > 1:
        loss, grads = _accum_grads(
            state.params, batch, mcfg=mcfg, rng=rng if train else None,
            train=train, attention_fn=attention_fn, blocks_fn=blocks_fn,
            accum=accum)
    else:
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, batch, mcfg, rng=rng, train=train,
            attention_fn=attention_fn, blocks_fn=blocks_fn)
    with jax.named_scope("optimizer"):
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u.astype(p.dtype)), state.params, updates)
    new_state = TrainState(step=state.step + 1, params=params,
                           opt_state=opt_state, rng=state.rng)
    metrics = {"loss": loss}
    if with_grad_norm:
        metrics["grad_norm"] = jax.tree_util.tree_reduce(
            lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))),
            grads, jnp.float32(0.0)) ** 0.5
    return new_state, metrics


def make_train_step(mcfg: ModelConfig, tcfg: TrainConfig,
                    donate: bool = True,
                    with_grad_norm: bool = False,
                    attention_fn=None, blocks_fn=None) -> Callable:
    """Build the jitted train step. Sharded execution comes from the
    shardings already attached to ``state``/``batch`` arrays (GSPMD); this
    function is mesh-agnostic. ``with_grad_norm`` adds a tree-wide grad-norm
    reduction to the metrics (off by default — it costs a full-tree
    reduction per step). ``attention_fn`` overrides the attention core —
    the sequence-parallel paths (ring / Ulysses) plug in here.

    With ``tcfg.grad_accum_steps > 1`` the batch arrays are stacked
    ``(accum, batch_size, T)`` microbatches (host-assembled like the K-step
    superbatch, sharded P(None,'data','seq') on mesh runs)."""
    step = partial(_one_step, mcfg=mcfg, optimizer=make_optimizer(tcfg),
                   with_grad_norm=with_grad_norm, attention_fn=attention_fn,
                   blocks_fn=blocks_fn, accum=tcfg.grad_accum_steps)
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_train_scan(mcfg: ModelConfig, tcfg: TrainConfig, k: int,
                    donate: bool = True,
                    with_grad_norm: bool = False,
                    attention_fn=None, blocks_fn=None) -> Callable:
    """K train steps per dispatch: ``(state, (K,B,T) batches) -> (state,
    {'loss': (K,), ...})`` with an on-device ``lax.scan`` over the steps;
    metrics come back stacked, one entry per step.

    Why this exists: a single-step dispatch pays one host->device round trip
    per optimizer step, which for any small model whose step time is
    comparable to dispatch latency can dominate wall-clock. Scanning K steps on device amortizes that overhead to 1/K
    and lets the host assemble the next superbatch while the chip runs.
    Shares ``_one_step`` with ``make_train_step`` (same per-step RNG fold on
    ``state.step``), so loss curves are unchanged — asserted in
    tests/test_train.py::test_train_scan_matches_single_steps."""
    one = partial(_one_step, mcfg=mcfg, optimizer=make_optimizer(tcfg),
                  with_grad_norm=with_grad_norm, attention_fn=attention_fn,
                  blocks_fn=blocks_fn, accum=tcfg.grad_accum_steps)

    def run(state: TrainState, batches) -> Tuple[TrainState, Dict[str, Any]]:
        xs, ys = batches  # (K, B, T) each; (K, accum, B, T) under accumulation
        return jax.lax.scan(lambda s, b: one(s, b), state, (xs, ys),
                            length=k)

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def make_eval_step(mcfg: ModelConfig, attention_fn=None,
                   blocks_fn=None) -> Callable:
    """Jitted single-batch eval loss (dropout off — GPT1.py:88 model.eval)."""

    @jax.jit
    def eval_step(params, batch) -> jnp.ndarray:
        return loss_fn(params, batch, mcfg, rng=None, train=False,
                       attention_fn=attention_fn, blocks_fn=blocks_fn)

    return eval_step


def make_eval_scan(mcfg: ModelConfig, attention_fn=None,
                   blocks_fn=None) -> Callable:
    """Jitted K-batch eval: ``(params, (K,B,T) xs/ys) -> (K,) losses`` via
    an on-device ``lax.scan`` — the whole estimate_loss pass in one
    dispatch per split instead of eval_iters of them (the reference's
    eval is 400 separate forwards, SURVEY.md §3.3)."""

    @jax.jit
    def eval_scan(params, batches) -> jnp.ndarray:
        def body(carry, b):
            return carry, loss_fn(params, b, mcfg, rng=None, train=False,
                                  attention_fn=attention_fn,
                                  blocks_fn=blocks_fn)
        _, losses = jax.lax.scan(body, None, batches)
        return losses

    return eval_scan


def estimate_loss(params, batchers: Dict[str, Any], eval_step: Callable,
                  eval_iters: int, device_put: Callable = None,
                  eval_scan: Callable = None,
                  superbatch_put: Callable = None) -> Dict[str, float]:
    """Mean loss over ``eval_iters`` fresh batches for each split —
    ``estimate_loss`` semantics (GPT1.py:85-98), including the quirk that
    'train' loss is itself a random K-batch sample (SURVEY.md §8-Q8).

    With ``eval_scan`` (from :func:`make_eval_scan`), each split is one
    stacked dispatch; identical batches and per-batch losses either way
    (tests/test_train.py::test_estimate_loss_scan_matches_loop). Sharded
    runs pass ``superbatch_put`` to place the stacked (K, B, T) arrays with
    the P(None,'data','seq') superbatch sharding (multi-host: per-process
    rows assembled via make_array_from_process_local_data)."""
    import numpy as np
    out = {}
    if eval_scan is not None and superbatch_put is None:
        assert device_put is None or device_put is jax.device_put, (
            "eval_scan on a sharded run needs superbatch_put to keep the "
            "batch sharding on the stacked (K,B,T) arrays")
    for split, batcher in batchers.items():
        if eval_scan is not None:
            xs, ys = zip(*(batcher.next_batch()
                           for _ in range(eval_iters)))
            stacked = (np.stack(xs), np.stack(ys))
            if superbatch_put is not None:
                stacked = tuple(superbatch_put(a) for a in stacked)
            losses = eval_scan(params, stacked)
            # one fetch per split is the contract:
            out[split] = float(jnp.mean(losses))  # graftlint: disable=GL004
        else:
            total = None
            for _ in range(eval_iters):
                xb, yb = batcher.next_batch()
                if device_put is not None:
                    xb, yb = device_put(xb), device_put(yb)
                # accumulate ON DEVICE — float() here would force a
                # device round-trip per eval batch (the host stall
                # graftlint GL004 exists for)
                loss = eval_step(params, (xb, yb))
                total = loss if total is None else total + loss
            # one fetch per split is the contract:
            out[split] = float(total) / eval_iters  # graftlint: disable=GL004
    return out
