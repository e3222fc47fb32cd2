"""Speculative decoding for the serving engine: drafters + acceptance.

The engine's steady-state cost is one full-model forward per token per
slot (serve/engine.py). Speculative decoding turns that into one
full-model forward per *window*: a cheap drafter proposes up to k
tokens per slot, the target model scores the whole ``[last_committed,
draft_1..draft_k]`` window in ONE jitted pass (``_engine_verify`` in
engine.py over ``models.gpt.verify_step_multi``), and per-position
acceptance commits between 1 and k+1 tokens per slot per step. Draft-k
is static, so the verify program compiles exactly once and the
zero-recompile steady-state contract holds unchanged.

Acceptance rule (this module's ``spec_accept_and_sample``): drafters
propose DETERMINISTIC token sequences — a point-mass proposal q. With
q a point mass at d, standard speculative rejection sampling reduces
to: accept d with probability p(d) under the target's fully-filtered
per-slot distribution (temperature -> top-k -> top-p, the exact
``sample.generate`` pipeline via ``filter_logits_batched``); on the
first rejection, resample from p with d masked out, renormalized.
This preserves the target distribution EXACTLY for any drafter, and
for greedy slots degenerates to argmax equality — which is why greedy
speculative output is token-for-token the non-speculative stream
(pinned in tests/test_speculative.py).

Two drafters behind one host-side interface:

- :class:`NGramDrafter` — prompt-lookup drafting: match the slot's
  trailing n-gram against its own prompt+generated history and propose
  the continuation of the most recent earlier occurrence. Zero
  parameters, zero device work; pays off on repetitive text (and on
  greedy loops, where it converges to accept-rate ~1).
- :class:`ModelDrafter` — a second, smaller ``ModelConfig`` + params
  with its own pooled KV cache, drafting k tokens greedily via one
  jitted k-step scan per engine step. Same slot ids as the engine's
  pool; its cache stays consistent for free because accepted tokens
  are exactly the tokens it drafted (stale K/V past the committed
  frontier is overwritten before ever being attended — the standing
  pool invariant).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..faults.inject import fire as fault_fire
from ..models.gpt import (decode_step_multi, init_kv_cache, param_count,
                          prefill_chunk_into_slot)
from ..ops.attention import NEG_INF
from ..sample.generate import filter_logits_batched
from ..utils.sanitize import CompileGuard, check_in_bounds
from ..utils.telemetry import ENGINE_TRACK, NULL
from .cache_pool import commit_default, prefill_chunk_size


# ---------------------------------------------------------------------------
# device-side acceptance (traced inside the engine's verify jit)
# ---------------------------------------------------------------------------

def spec_accept_and_sample(rngs: jnp.ndarray, logits: jnp.ndarray,
                           window: jnp.ndarray, n_valid: jnp.ndarray,
                           temperature: jnp.ndarray, top_k: jnp.ndarray,
                           top_p: jnp.ndarray, greedy: jnp.ndarray,
                           live: Optional[jnp.ndarray] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-slot speculative acceptance + the committed-token layout.

    logits: (B, W, V) f32 from ``verify_step_multi`` (position j scores
    the token after window token j); window: (B, W) int32; n_valid:
    (B,) int32 — drafts beyond it are padding; per-slot sampling params
    as in ``sample_tokens_batched``; rngs: (B, key) per-slot streams.

    Returns ``(n_acc, out, new_rngs)``: ``n_acc[b]`` accepted drafts
    (0..n_valid[b]); ``out[b, :n_acc[b]+1]`` the committed tokens —
    accepted drafts followed by the correction token (resampled from
    the draft-masked renormalized target at the first rejection) or the
    bonus token (sampled from the full target after total acceptance).
    Greedy rows use raw-logits argmax for acceptance AND for the
    correction/bonus token, exactly ``sample_tokens_batched``'s greedy
    mode — so a greedy slot's stream is the non-speculative stream.
    ``live`` ((B,) bool, None = every row): only a live stochastic row
    reads the filtered distribution, so the filters run only where one
    asks for them (``filter_logits_batched``'s ``rows``).
    """
    B, W, V = logits.shape
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]          # (1, W)
    # candidate at logits position j is window token j+1 (pad last col)
    cand = jnp.concatenate(
        [window[:, 1:], jnp.zeros((B, 1), window.dtype)], axis=1)
    next_raw = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, W)

    flat = logits.reshape(B * W, V)
    rep = lambda a: jnp.repeat(jnp.asarray(a), W)           # noqa: E731
    greedy = jnp.asarray(greedy, bool)
    rows = ~greedy if live is None else jnp.asarray(live, bool) & ~greedy
    f = filter_logits_batched(flat, rep(temperature), rep(top_k),
                              rep(top_p), rep(rows)).reshape(B, W, V)
    logp = jax.nn.log_softmax(f, axis=-1)
    p_acc = jnp.exp(jnp.take_along_axis(
        logp, cand[..., None].astype(jnp.int32), axis=-1))[..., 0]

    def per_slot(key):
        ku, kc, kb, knext = jax.random.split(key, 4)
        return jax.random.uniform(ku, (W,)), kc, kb, knext

    u, ckeys, bkeys, new_rngs = jax.vmap(per_slot)(rngs)
    accept = jnp.where(greedy[:, None], next_raw == cand, u < p_acc)
    valid = offs < n_valid[:, None]
    chain = jnp.cumprod((accept & valid).astype(jnp.int32), axis=1)
    n_acc = jnp.sum(chain, axis=1).astype(jnp.int32)

    # only position r = n_acc per row emits a sampled token, so gather
    # its distribution first and draw ONE correction + ONE bonus
    # categorical per row (not per window position)
    take = lambda a: jnp.take_along_axis(a, n_acc[:, None], axis=1)[:, 0]  # noqa: E731
    f_r = jnp.take_along_axis(
        f, n_acc[:, None, None], axis=1)[:, 0, :]            # (B, V)
    cand_r, raw_r = take(cand), take(next_raw)
    # correction: target with the rejected draft masked out, renormalized
    # (NEG_INF, not -inf: a fully-masked row must stay NaN-free; it is
    # only reachable when acceptance was certain, so it is never used)
    masked_r = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (B, V), 1)
        == cand_r[:, None], NEG_INF, f_r)
    cat = jax.vmap(jax.random.categorical)
    corr = cat(ckeys, masked_r).astype(jnp.int32)
    bonus = cat(bkeys, f_r).astype(jnp.int32)
    final = jnp.where(greedy, raw_r,
                      jnp.where(n_acc < n_valid, corr, bonus))
    out = jnp.where(offs < n_acc[:, None], cand,
                    jnp.where(offs == n_acc[:, None], final[:, None], 0)
                    ).astype(jnp.int32)
    return n_acc, out, new_rngs


# ---------------------------------------------------------------------------
# host-side drafter interface
# ---------------------------------------------------------------------------

@dataclass
class DraftContext:
    """Per-step host snapshot handed to ``Drafter.draft`` — built ONCE
    per engine step from the engine's host-side state (no per-slot
    device syncs: token histories are host bookkeeping and positions
    live in ``CachePool.positions``)."""

    tok: np.ndarray                    # (P,) int32 last committed token
    pos: np.ndarray                    # (P,) int32 per-slot positions
    active: np.ndarray                 # (P,) bool
    histories: Optional[List[Optional[np.ndarray]]] = None
    # per-slot prompt+generated token history; only materialized when
    # the drafter sets ``needs_history`` (the n-gram drafter)


class Drafter:
    """Host-side proposal source for speculative decoding.

    ``draft`` returns ``(tokens (P, k) int32, lens (P,) int32)`` —
    deterministic proposals per slot; the engine further clamps lens by
    cache room and token budget. Lifecycle hooks mirror slot admission
    so stateful drafters (the model drafter's pooled KV cache) stay in
    sync with the engine's pool.
    """

    name = "base"
    needs_history = False

    def __init__(self, k: int):
        assert k >= 1, k
        self.k = k

    def on_admit(self, slot: int, prompt: np.ndarray) -> None:
        pass

    def on_release(self, slot: int) -> None:
        pass

    def resync(self, slot: int, history: np.ndarray) -> None:
        """Rebuild the drafter's per-slot state from the slot's full
        committed history (prompt + generated). The engine calls this
        when re-enabling a drafter after a degraded window: tokens were
        committed by the plain decode path while the drafter sat idle,
        so a stateful drafter's cache is behind the frontier. The
        default treats the history as a fresh admission — which is
        exactly a chunked re-prefill for the model drafter and a no-op
        for the stateless n-gram drafter."""
        self.on_admit(slot, history)

    def draft(self, ctx: DraftContext) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the slot's trailing n-gram, falling
    back to shorter n-grams down to 1; no match (or a <2-token history)
    proposes nothing. Pure host numpy over histories <= block_size —
    microseconds next to a model forward."""

    name = "ngram"
    needs_history = True

    def __init__(self, k: int, ngram: int = 3):
        super().__init__(k)
        assert ngram >= 1, ngram
        self.ngram = ngram

    def _lookup(self, history: np.ndarray) -> np.ndarray:
        L = int(history.size)
        for n in range(min(self.ngram, L - 1), 0, -1):
            pat = history[L - n:]
            win = np.lib.stride_tricks.sliding_window_view(history, n)
            hits = np.nonzero((win == pat).all(axis=1))[0]
            hits = hits[hits < L - n]          # exclude the suffix itself
            if hits.size:
                i = int(hits[-1])
                cont = history[i + n:i + n + self.k]
                if cont.size:
                    return cont.astype(np.int32)
        return np.empty((0,), np.int32)

    def draft(self, ctx: DraftContext) -> Tuple[np.ndarray, np.ndarray]:
        P = ctx.tok.shape[0]
        toks = np.zeros((P, self.k), np.int32)
        lens = np.zeros((P,), np.int32)
        for slot in range(P):
            if not ctx.active[slot] or ctx.histories[slot] is None:
                continue
            cont = self._lookup(ctx.histories[slot])
            toks[slot, :cont.size] = cont
            lens[slot] = cont.size
        return toks, lens


# module-level jits (like the engine's): programs accumulate across
# drafter instances, steady-state enforcement is per-drafter CompileGuard
@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _draft_prefill(params, chunk, offset, slot, cache, cfg: ModelConfig):
    return prefill_chunk_into_slot(params, chunk, offset, slot, cache, cfg)


@partial(jax.jit, static_argnames=("cfg", "k"), donate_argnames=("cache",))
def _draft_decode_k(params, tok, pos, active, cache, cfg: ModelConfig,
                    k: int):
    """k greedy draft proposals per slot in ONE dispatch (lax.scan over
    ``decode_step_multi``). Greedy on purpose: proposals are point-mass,
    which keeps the acceptance rule exact for every target sampling
    mode (module docstring).

    The scan runs k+1 iterations, not k: iteration j writes K/V for
    window token j at pos+j, so stopping at k would leave the k-th
    proposal's K/V unwritten — and after a FULL acceptance the engine's
    frontier jumps past that position, which the draft cache would then
    hold stale prefill-padding for, silently degrading every later
    proposal for the request (exactly in the drafter's best case). The
    extra iteration commits d_k's K/V, making the draft cache's writes
    mirror the verify window's; its own proposal is discarded. Slots
    whose positions run off the cache buffer mid-scan write nothing
    (scatter drops out-of-bounds updates) and their surplus proposals
    are clamped away host-side."""
    pos0 = jnp.where(active, pos, 0)

    def body(carry, _):
        tok, pos, cache = carry
        logits, cache = decode_step_multi(params, tok, pos, cache, cfg)
        nxt = jnp.where(active, jnp.argmax(logits, axis=-1)
                        .astype(jnp.int32), 0)
        return (nxt, pos + 1, cache), nxt

    (_, _, cache), toks = jax.lax.scan(
        body, (tok, pos0, cache), None, length=k + 1)
    return toks[:k].T, cache                   # (B, k)


class ModelDrafter(Drafter):
    """Small-model drafter: a second ``ModelConfig`` + params with its
    own pooled KV cache, same slot ids as the engine pool. Per engine
    step it drafts k tokens per slot greedily in one jitted scan; per
    admission it chunk-prefills the prompt into its own slot region.
    The draft cache needs no post-verification repair: accepted tokens
    ARE the drafted tokens and the draft scan writes K/V for the whole
    window [tok, d_1..d_k] (see ``_draft_decode_k``'s k+1-iteration
    note), so K/V up to and including each slot's committed frontier is
    always for the committed stream; everything past it is overwritten
    before being attended (pool invariant). With draft params == target
    params this makes greedy acceptance exact — pinned as a regression
    test for the cache-alignment property."""

    name = "model"

    def __init__(self, params, cfg: ModelConfig, k: int, pool_size: int,
                 prefill_chunk: int = 0):
        super().__init__(k)
        cfg.validate()
        self.params = params
        self.cfg = cfg
        self.pool_size = pool_size
        self._chunk = prefill_chunk_size(prefill_chunk, cfg.block_size)
        self.cache = commit_default(init_kv_cache(cfg, pool_size))
        self._decode_guard = CompileGuard(_draft_decode_k, "spec/draft")
        self._prefill_guard = CompileGuard(_draft_prefill,
                                           "spec/draft-prefill")

    @property
    def n_params(self) -> int:
        return param_count(self.params)

    def on_admit(self, slot: int, prompt: np.ndarray) -> None:
        P = int(prompt.size)
        S = self.cfg.block_size
        chunk = self._chunk
        n_chunks = -(-P // chunk)
        # same clamp-corruption bound as Engine._admit (lint GL006)
        check_in_bounds((n_chunks - 1) * chunk, chunk, S,
                        what=f"draft prefill of {P}-token prompt")
        padded = np.zeros((n_chunks * chunk,), np.int32)
        padded[:P] = prompt
        cache = self.cache
        for c in range(n_chunks):
            cache = self._prefill_guard(
                self.params,
                jnp.asarray(padded[None, c * chunk:(c + 1) * chunk]),
                jnp.int32(c * chunk), jnp.int32(slot), cache, self.cfg)
        self.cache = cache

    def draft(self, ctx: DraftContext) -> Tuple[np.ndarray, np.ndarray]:
        toks, cache = self._decode_guard(
            self.params, jnp.asarray(ctx.tok), jnp.asarray(ctx.pos),
            jnp.asarray(ctx.active), self.cache, self.cfg, self.k)
        self.cache = cache
        out = np.asarray(toks)                 # one snapshot per step
        lens = np.where(ctx.active, self.k, 0).astype(np.int32)
        return out, lens

    def compile_stats(self) -> dict:
        return {"decode": self._decode_guard.stats(),
                "prefill": self._prefill_guard.stats()}


# ---------------------------------------------------------------------------
# construction helpers (CLI / bench / replay)
# ---------------------------------------------------------------------------

def draft_config_from_preset(target: ModelConfig,
                             preset: str) -> ModelConfig:
    """A drafter ``ModelConfig`` from a named preset, forced compatible
    with the target: same vocab (proposals must be valid target ids),
    same block_size (slot regions line up), same compute dtype and
    cache layout (one set of engine invariants)."""
    import dataclasses

    from ..config import get_config
    base = get_config(preset).model
    return dataclasses.replace(
        base, vocab_size=target.vocab_size, block_size=target.block_size,
        dtype=target.dtype, decode_cache_layout=target.decode_cache_layout)


def make_drafter(mode: str, k: int, ngram: int, pool_size: int,
                 draft_params=None, draft_cfg: Optional[ModelConfig] = None,
                 prefill_chunk: int = 0) -> Optional[Drafter]:
    """Drafter factory: ``mode`` is 'off' | 'ngram' | 'model'. The model
    mode needs ``draft_params``/``draft_cfg`` (see
    ``draft_config_from_preset``). Called once per Engine — drafters
    are stateful (per-slot caches, compile guards)."""
    if mode in ("off", "", None):
        return None
    if mode == "ngram":
        return NGramDrafter(k, ngram=ngram)
    if mode == "model":
        if draft_params is None or draft_cfg is None:
            raise ValueError("mode='model' needs draft_params and draft_cfg")
        return ModelDrafter(draft_params, draft_cfg, k, pool_size,
                            prefill_chunk=prefill_chunk)
    raise ValueError(f"unknown drafter mode {mode!r}")


def timed_draft(drafter: Drafter, ctx: DraftContext,
                vocab_size: int = 0, tel=NULL,
                track: int = ENGINE_TRACK
                ) -> Tuple[np.ndarray, np.ndarray, float]:
    """``drafter.draft`` + wall-clock overhead (seconds) — the engine
    records it per step so the drafter's cost is visible next to the
    verify step it amortizes. ``tel`` (utils.telemetry) additionally
    records the draft phase as a span on the engine track, so the
    drafter's host cost sits on the same timeline as the verify step
    it feeds.

    Chaos seam ``spec/draft`` (kind ``collapse``): shifts every proposed
    token by one (mod the vocab), turning the drafter's proposals into
    deterministic garbage — the accept rate collapses toward zero while
    every token stays a valid vocab id, which is exactly the failure the
    engine's speculative auto-disable must catch. No-op without an
    installed FaultPlan."""
    t0_us = tel.now_us() if tel.enabled else 0.0
    t0 = time.perf_counter()
    toks, lens = drafter.draft(ctx)
    f = fault_fire("spec/draft")
    if f is not None and f.kind == "collapse" and vocab_size > 1:
        toks = (toks + 1) % vocab_size
    dt = time.perf_counter() - t0
    if tel.enabled:
        tel.complete("draft", track, t0_us, dt * 1e6,
                     drafter=drafter.name, k=drafter.k)
    return toks, lens, dt
