"""Autoregressive generation: KV-cached, jit-compiled, O(T) work per token.

Capability parity with the reference's two samplers, re-designed for XLA:

- multinomial sampling from the last position's softmax
  (``BigramLanguageModel.generate``, GPT1.py:196-212) — but without the
  O(T^2)-per-token full re-forward: a single ``lax.scan`` teacher-forces
  through the prompt (filling the KV cache) and then emits one sampled token
  per step against the cache;
- temperature / top-k sampling (the reference's dead GPT-2 sampler used
  top-k=50, GPT-2.py:245-247);
- greedy decoding (argmax) as the deterministic mode.

Long generations (beyond ``block_size``, e.g. the reference's 500-token
char-GPT sample with block 256, GPT1.py:236, or the BASELINE.json 1k-token
latency workload) use **window refresh**: when the cache fills, the last
``block_size//2`` tokens are re-prefilled and decoding continues. The
reference instead crops the window per token (GPT1.py:200), which shifts
every absolute position each step and therefore cannot be KV-cached at all
with learned positional embeddings; window refresh keeps the same effective
context length with amortized O(1) full forwards per half-window. This is a
documented deviation (same capability, cache-compatible semantics).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..models.gpt import (_all_single_device, cache_seq_axis, decode_step,
                          init_kv_cache, prefill)


@dataclass(frozen=True)
class GenerateConfig:
    max_new_tokens: int = 500          # GPT1.py:236 default workload
    temperature: float = 1.0
    top_k: int = 0                     # 0 = full multinomial (GPT1.py:208);
                                       # 50 = the GPT-2 sampler (GPT-2.py:245)
    top_p: float = 0.0                 # 0 = off; (0, 1] = nucleus sampling
                                       # (beyond the reference's samplers;
                                       # composes with top_k: k-filter first)
    greedy: bool = False
    attend_granule: int = 128          # KV-cache growth granule for the
                                       # chunked decode scan (_decode_chunks);
                                       # block_size = the monolithic
                                       # full-bucket scan. Lives here (a
                                       # static jit arg) so changing it keys
                                       # a fresh compile — a module global
                                       # read at trace time silently reused
                                       # stale chunking across mutations.


def _sortable_f32(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 with the same total order (monotone bijection):
    flip all bits of negatives, set the sign bit of non-negatives. -inf
    maps near 0, +inf near 2^32-1."""
    u = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(u < 0, ~u, u | jnp.int32(-2 ** 31)).astype(jnp.uint32)


def _unsortable_f32(u: jnp.ndarray) -> jnp.ndarray:
    i = u.astype(jnp.int32)
    back = jnp.where(i < 0, i & jnp.int32(2 ** 31 - 1), ~i)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def _kth_largest(logits: jnp.ndarray, k) -> jnp.ndarray:
    """Exact per-row k-th largest of (B, V) float32 via radix select in
    sortable bit space: 8 passes of 4 bits, each counting elements >= 16
    candidate thresholds with a fused compare+reduce. Replaces
    ``lax.top_k`` for the top-k *filter*, where only the k-th value is
    needed: XLA lowers top_k to a full (B, V) sort, measured 377 us per
    decode step at B=1/V=50304 on v5e vs ~20 us for this select (the
    sort was 44% of the 124M decode step). ``k`` is a python int or a
    (B,) int32 array of per-row ranks (the serving engine's per-slot
    top-k) — k only ever feeds the counts comparison, so the select is
    rank-vectorized for free. Returns (B,) float32."""
    u = _sortable_f32(logits)
    B = logits.shape[0]
    k_col = jnp.broadcast_to(jnp.asarray(k, jnp.int32), (B,))[:, None]
    lo = jnp.zeros((B,), jnp.uint32)
    for shift in range(28, -1, -4):
        cand = (lo[:, None]
                + (jnp.arange(16, dtype=jnp.uint32)[None, :] << shift))
        counts = jnp.sum((u[:, :, None] >= cand[:, None, :])
                         .astype(jnp.int32), axis=1)
        # candidates are ascending, so counts are non-increasing: the
        # chosen bucket is the largest whose count still reaches k.
        # count(u >= lo) >= k holds at every pass (lo starts at 0 and
        # only advances to satisfying prefixes), so sel >= 0 always.
        sel = jnp.sum((counts >= k_col).astype(jnp.int32), axis=1) - 1
        lo = lo + (sel.astype(jnp.uint32) << shift)
    return _unsortable_f32(lo)


def _top_k_filter(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask logits strictly below the k-th largest to -inf — the
    reference's filter semantics (``logits < v[:, [-1]]``,
    /root/reference/GPT-2.py:245-247; ties at the k-th value are kept).
    Bit-identical to the ``lax.top_k`` formulation (asserted in
    tests/test_generate.py), without the full-vocab sort. Small vocabs
    keep the sort: the radix select's 8 fixed passes only pay off once
    the sort is the bigger cost (char-GPT's V=65 sort is trivial; the
    win is GPT-2's V=50257)."""
    if logits.dtype != jnp.float32 or logits.shape[-1] < 1024:
        kth = jax.lax.top_k(logits, k)[0][:, -1:]
        return jnp.where(logits < kth, -jnp.inf, logits)
    t = _kth_largest(logits, k)
    return jnp.where(logits < t[:, None], -jnp.inf, logits)


def _top_p_filter(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus filter: keep the smallest prefix of the descending-softmax
    distribution whose cumulative probability reaches ``p`` (always
    including the top token), mask the rest to -inf. Sort-based, O(V log V)
    on device — static shapes, jit/scan-friendly.

    Rank-based (keep flags scattered back through the argsort), not
    value-thresholded: boundary ties cannot widen the nucleus past the
    prefix (a value threshold would keep every token tied with the
    boundary logit — a no-op on fully tied rows)."""
    idx = jnp.argsort(logits, axis=-1)[:, ::-1]          # descending order
    sorted_logits = jnp.take_along_axis(logits, idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # sorted position i is kept iff the cumulative mass BEFORE it is < p
    # (so the top token is always kept and the prefix first reaches >= p)
    keep = (cum - probs) < p
    rows = jnp.arange(logits.shape[0])[:, None]
    mask = jnp.zeros(logits.shape, bool).at[rows, idx].set(keep)
    return jnp.where(mask, logits, -jnp.inf)


def _sample_token(rng: jax.Array, logits: jnp.ndarray,
                  gcfg: GenerateConfig) -> jnp.ndarray:
    """logits: (B, V) float32 -> (B,) int32."""
    if gcfg.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(gcfg.temperature, 1e-6)
    if gcfg.top_k and gcfg.top_k > 0:
        k = min(gcfg.top_k, logits.shape[-1])
        logits = _top_k_filter(logits, k)
    if gcfg.top_p and gcfg.top_p > 0.0:
        logits = _top_p_filter(logits, gcfg.top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Batched per-row sampling (the continuous-batching engine's sampler:
# every row is a pool slot with its OWN temperature/top-k/top-p/greedy
# and its own rng stream — same filter math as the scalar path above,
# vectorized over rows with per-row off-switches)
# ---------------------------------------------------------------------------

def batched_top_k_filter(logits: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Per-row top-k filter: k is (B,) int32; rows with k <= 0 or
    k >= V pass through UNCHANGED (bit-exact off-switch — not a k=V
    filter, which would still mask zero-probability ties differently).
    Same kept-set semantics as ``_top_k_filter`` (ties at the k-th value
    kept), via the radix select (``_kth_largest`` takes per-row k: it
    only ever compares counts >= k)."""
    V = logits.shape[-1]
    k = jnp.asarray(k, jnp.int32)
    off = (k <= 0) | (k >= V)
    k_eff = jnp.where(off, 1, k)  # any valid k; rows masked back below
    t = _kth_largest(logits.astype(jnp.float32), k_eff)
    filtered = jnp.where(logits < t[:, None], -jnp.inf, logits)
    return jnp.where(off[:, None], logits, filtered)


def batched_top_p_filter(logits: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """Per-row nucleus filter: p is (B,) float32; rows with p <= 0 or
    p >= 1 pass through unchanged. Same rank-based prefix semantics as
    ``_top_p_filter``."""
    p = jnp.asarray(p, jnp.float32)
    off = (p <= 0.0) | (p >= 1.0)
    idx = jnp.argsort(logits, axis=-1)[:, ::-1]
    sorted_logits = jnp.take_along_axis(logits, idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < jnp.where(off, 1.0, p)[:, None]
    rows = jnp.arange(logits.shape[0])[:, None]
    mask = jnp.zeros(logits.shape, bool).at[rows, idx].set(keep)
    filtered = jnp.where(mask, logits, -jnp.inf)
    return jnp.where(off[:, None], logits, filtered)


def filter_logits_batched(logits: jnp.ndarray, temperature: jnp.ndarray,
                          top_k: jnp.ndarray, top_p: jnp.ndarray,
                          rows: Optional[jnp.ndarray] = None
                          ) -> jnp.ndarray:
    """The per-row stochastic filter pipeline — temperature -> top-k ->
    top-p, each (B,)-parameterized — factored out of
    ``sample_tokens_batched`` so the speculative verifier
    (serve/speculative.py) scores drafted tokens against EXACTLY the
    distribution the engine would have sampled from (rejection sampling
    is only target-preserving if both sides use the same filters).

    ``rows`` ((B,) bool, None = all) marks the rows whose result the
    caller READS: live and not greedy. Each filter runs only if one of
    them has it switched on — ONE scalar predicate over the batch behind
    a ``lax.cond``, decided on the device from the parameters the
    program already receives, so an all-greedy launch pays for no radix
    select and no sort/cumsum/scatter (94 ms of a 96-slot, V=50257
    decode step on v5e). A marked row reads what the unconditional
    pipeline gives it, bit for bit: a filter is skipped only when every
    marked row has it off, and an off row passes through it unchanged.
    The other rows' values are unspecified. The predicates are never
    ``vmap``ped: a batched ``cond`` lowers to a ``select`` that runs
    both sides."""
    V = logits.shape[-1]
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    rows = True if rows is None else jnp.asarray(rows, bool)
    need_top_k = jnp.any(rows & (top_k > 0) & (top_k < V))
    need_top_p = jnp.any(rows & (top_p > 0.0) & (top_p < 1.0))
    scaled = logits / jnp.maximum(
        jnp.asarray(temperature, jnp.float32), 1e-6)[:, None]
    f = jax.lax.cond(need_top_k,
                     lambda x: batched_top_k_filter(x, top_k),
                     lambda x: x, scaled)
    return jax.lax.cond(need_top_p,
                        lambda x: batched_top_p_filter(x, top_p),
                        lambda x: x, f)


@jax.named_scope("sample")
def sample_tokens_batched(rngs: jnp.ndarray, logits: jnp.ndarray,
                          temperature: jnp.ndarray, top_k: jnp.ndarray,
                          top_p: jnp.ndarray, greedy: jnp.ndarray,
                          live: Optional[jnp.ndarray] = None
                          ) -> jnp.ndarray:
    """Per-row sampling: (B,) params, (B, key) rngs, (B, V) f32 logits
    -> (B,) int32. Greedy rows take argmax of the RAW logits (exactly
    ``_sample_token``'s greedy mode, so a greedy slot in a mixed batch
    is token-identical to a scalar greedy decode); stochastic rows get
    temperature -> top-k -> top-p, each per-row, then a per-row
    categorical draw from the row's own key.

    The draw and everything before it run only when a LIVE row is
    stochastic (``live``: (B,) bool, None = every row): the serving
    engine's idle slots carry whatever parameters their last request
    left (a never-used slot reads "not greedy"), so without the mask a
    half-empty all-greedy pool would pay for the machinery on every
    step. A dead row's token is unspecified (the callers mask it)."""
    greedy = jnp.asarray(greedy, bool)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    rows = ~greedy if live is None else jnp.asarray(live, bool) & ~greedy

    def draw():
        f = filter_logits_batched(logits, temperature, top_k, top_p, rows)
        return jax.vmap(jax.random.categorical)(rngs, f).astype(jnp.int32)

    sampled = jax.lax.cond(jnp.any(rows), draw, lambda: greedy_tok)
    return jnp.where(greedy, greedy_tok, sampled)


def _decode_chunks(P_pad: int, n_new: int, S: int, g: int):
    """Static (n_steps, cache_len) chunks covering an ``n_new``-step
    decode scan whose step i writes position <= P_pad - 1 + i. The KV
    cache buffer starts at the first chunk's cache_len (a multiple of
    the granule ``g``, capped at S) and is zero-padded up between chunks,
    so early steps stop paying for the whole static bucket — at B >= 8
    the cache read dominates decode step bytes and a 1k-token sample
    from a short prompt otherwise streams all S slots from token 1
    (measured 2.8-3.0x above the full-cache byte floor at 124M; the
    chunked scan reads ~0.56x the bytes on that workload). Growing the
    *buffer* keeps the in-chunk loop byte-identical to the plain
    fixed-bucket scan — a static prefix slice of the carried buffer
    instead was measured 10x worse (see models.gpt.decode_step). All
    chunks compile into the ONE jitted segment — more scan bodies, zero
    extra dispatches."""
    if n_new <= 0:
        # one zero-step chunk: callers still get a valid cache bound
        return [(0, min(-(-P_pad // g) * g, S))]
    chunks = []
    i = 0
    while i < n_new:
        a = min(-(-(P_pad + i) // g) * g, S)
        n_c = n_new - i if a >= S else min(n_new - i, a - (P_pad - 1) - i)
        chunks.append((n_c, a))
        i += n_c
    return chunks


def _segment_core(params, prompt: jnp.ndarray, prompt_len, n_new: int,
                  rng: jax.Array, cfg: ModelConfig, gcfg: GenerateConfig,
                  allow_pallas: bool = False) -> jnp.ndarray:
    """One prefill + decode scan: fill the KV cache for the whole padded
    prompt in ONE parallel forward (``models.gpt.prefill`` — the previous
    formulation teacher-forced the prompt through ``P_pad - 1``
    sequential decode steps, ~43% of all steps on the 1k-token char
    workload), then run exactly ``n_new`` sampling steps starting at
    position ``prompt_len - 1``. ``prompt_len`` is a TRACED scalar — the
    prompt array may be right-padded to a bucketed width, so true length
    does not force a recompile; padding-derived cache entries at
    positions >= prompt_len are overwritten before being attended.
    Requires P_pad + n_new <= block_size + 1.

    The scan is split into ``_decode_chunks`` with a cache buffer grown
    chunk-by-chunk (see there); the rng-split sequence per step is
    unchanged and the padded slots are masked exactly like unfilled
    bucket slots, so the sampled trajectory matches a single full-bucket
    scan (asserted in tests/test_generate.py)."""
    B, P_pad = prompt.shape
    chunks = _decode_chunks(P_pad, n_new, cfg.block_size,
                            gcfg.attend_granule)
    cache = init_kv_cache(cfg, B, max_len=chunks[0][1])
    prompt_len = jnp.asarray(prompt_len, jnp.int32)
    cache = prefill(params, prompt, cache, cfg)
    start = prompt_len - 1
    first = jax.lax.dynamic_slice_in_dim(prompt, start, 1, axis=1)[:, 0]

    def body(carry, i):
        tok, cache, rng = carry
        logits, cache = decode_step(params, tok, start + i, cache, cfg,
                                    allow_pallas=allow_pallas)
        rng, sub = jax.random.split(rng)
        next_tok = _sample_token(sub, logits, gcfg)
        return (next_tok, cache, rng), next_tok

    carry = (first, cache, rng)
    parts = []
    i = 0
    seq_ax = cache_seq_axis(cfg)  # layout-dependent (packed vs heads)
    for n_c, a_len in chunks:
        tok, cache, crng = carry
        if cache["k"].shape[seq_ax] < a_len:
            grow = a_len - cache["k"].shape[seq_ax]
            pad = [(0, 0)] * cache["k"].ndim
            pad[seq_ax] = (0, grow)
            cache = {key: jnp.pad(val, pad) for key, val in cache.items()}
        carry, toks_c = jax.lax.scan(body, (tok, cache, crng),
                                     jnp.arange(i, i + n_c))
        parts.append(toks_c)
        i += n_c
    toks = (parts[0] if len(parts) == 1
            else jnp.concatenate(parts, axis=0))
    return toks.T


@partial(jax.jit, static_argnames=("n_new", "cfg", "gcfg", "allow_pallas"))
def _decode_segment(params, prompt: jnp.ndarray, prompt_len, n_new: int,
                    rng: jax.Array, cfg: ModelConfig, gcfg: GenerateConfig,
                    allow_pallas: bool = False) -> jnp.ndarray:
    """Jitted ``_segment_core`` — compiled shapes are keyed on
    (P_pad, n_new) buckets only (plus the static allow_pallas kernel
    gate); see ``generate`` for the bucketing."""
    return _segment_core(params, prompt, prompt_len, n_new, rng, cfg, gcfg,
                         allow_pallas)


@partial(jax.jit, static_argnames=("n_seg", "cfg", "gcfg", "allow_pallas"))
def _refresh_group(params, window: jnp.ndarray, n_seg: int, first_ord,
                   base_rng: jax.Array, cfg: ModelConfig,
                   gcfg: GenerateConfig, allow_pallas: bool = False):
    """``n_seg`` window-refresh segments in ONE dispatch: an on-device
    ``lax.scan`` whose body is a full segment (prefill the (B, S//2)
    window, sample S//2 + 1 tokens, slide the window). The host loop
    used one dispatch per segment, so a 1k-token char-GPT sample paid
    ~7 sequential host round trips; ``generate`` now dispatches
    power-of-two group sizes from the binary decomposition of the
    segment count — popcount(k) dispatches, a bounded compile set
    (one program per power of two), zero wasted decode steps. Segment
    rngs derive from ``fold_in(base_rng, segment ordinal)`` so the
    sampled stream is invariant to how segments are grouped (a
    sequential split chain would make tokens depend on max_new_tokens
    through the decomposition). Returns ((B, n_seg * (S//2+1)) tokens,
    the final (B, S//2) window)."""
    S = cfg.block_size
    Pw, n_mid = S // 2, S // 2 + 1

    def seg(window, i):
        sub = jax.random.fold_in(base_rng, first_ord + i)
        toks = _segment_core(params, window, Pw, n_mid, sub, cfg, gcfg,
                             allow_pallas)
        window = jnp.concatenate([window, toks], axis=1)[:, -Pw:]
        return window, toks

    window, toks = jax.lax.scan(seg, window, jnp.arange(n_seg))
    B = window.shape[0]
    return jnp.moveaxis(toks, 0, 1).reshape(B, n_seg * n_mid), window


def _pow2_at_least(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def shard_for_decode(params, prompt: jnp.ndarray, cfg: ModelConfig,
                     mesh, mesh_cfg):
    """Lay out params and prompt for sharded decoding on ``mesh``.

    Decode-time layout differs from training: params use the Megatron TP
    specs over 'model' but replicate over 'data' (FSDP's gather-per-use
    trades latency for memory in exactly the wrong direction for
    single-token steps) and the pipe axis is ignored (no microbatching at
    decode). The prompt batch shards over 'data' when divisible, else
    replicates. The KV cache needs no explicit spec: it is created inside
    the jitted segment from TP-sharded k/v projections, so GSPMD
    propagates the head sharding to it.

    The result feeds straight into ``generate`` — the same jitted
    ``_decode_segment`` runs sharded, with XLA inserting the TP
    collectives (psum after row-parallel projections, gather for the
    sharded-vocab logits at the sampling step).
    """
    import dataclasses as _dc

    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import param_pspecs

    decode_cfg = _dc.replace(mesh_cfg, fsdp=False, pipe=1)
    specs = param_pspecs(cfg, decode_cfg)
    params = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs))
    B = prompt.shape[0]
    bspec = P("data") if B % mesh_cfg.data == 0 else P(None)
    prompt = jax.device_put(jnp.asarray(prompt, jnp.int32),
                            NamedSharding(mesh, P(*bspec, None)))
    return params, prompt


def generate(params, prompt: jnp.ndarray, cfg: ModelConfig,
             gcfg: GenerateConfig = GenerateConfig(),
             rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """Generate ``gcfg.max_new_tokens`` continuations of ``prompt``.

    prompt: (B, P) int32, 1 <= P <= block_size (the reference's "zero
    context" start, GPT1.py:235, is a single 0 token). Returns
    (B, max_new_tokens) int32.

    Sharded decoding: pass params/prompt through ``shard_for_decode``
    first; everything below is sharding-agnostic (jit + GSPMD propagate
    the layouts through the scan).

    Compile stability: segment shapes are bucketed so a long sample costs
    a fixed small set of XLA programs instead of one per segment —
    (a) the prompt is right-padded to a power-of-two width with the true
    length passed traced, (b) the first segment's decode count rounds up
    to a power of two (capped by cache room), and (c) every window-refresh
    segment uses the single shape (block_size//2, block_size//2 + 1), with
    the final segment's surplus tokens truncated (surplus decode steps are
    bounded by block_size//2 per sample — cheap next to a recompile).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    prompt = jnp.asarray(prompt, jnp.int32)
    assert prompt.ndim == 2 and prompt.shape[1] >= 1
    assert prompt.shape[1] <= cfg.block_size, "prompt longer than block_size"
    assert gcfg.attend_granule >= 1, "attend_granule must be >= 1"
    S = cfg.block_size
    B, P0 = prompt.shape
    chunks = []
    remaining = gcfg.max_new_tokens
    if remaining <= 0:
        return jnp.zeros((B, 0), jnp.int32)
    # gcfg is a static jit arg of _decode_segment; normalize the length
    # field out of it so requesting a different max_new_tokens cannot
    # recompile the segments (only sampling params belong in the key)
    import dataclasses as _dc
    gcfg = _dc.replace(gcfg, max_new_tokens=0)

    # the packed decode-attention kernel only where GSPMD cannot
    # shard the segment — decided on the REAL params, outside jit
    allow_pallas = _all_single_device(params) and _all_single_device(prompt)

    # first segment: bucketed prompt pad + bucketed decode count
    P_pad = min(_pow2_at_least(P0), S)
    padded = (prompt if P_pad == P0 else jnp.pad(
        prompt, ((0, 0), (0, P_pad - P0))))
    room = S - P_pad + 1
    n1 = min(_pow2_at_least(remaining), room)
    rng, sub = jax.random.split(rng)
    toks = _decode_segment(params, padded, P0, n1, sub, cfg, gcfg,
                           allow_pallas)
    take = min(n1, remaining)
    chunks.append(toks[:, :take])
    remaining -= take
    window = jnp.concatenate([prompt, toks[:, :take]], axis=1)

    # refresh segments: one fixed shape (S//2 prompt, S//2+1 new),
    # dispatched in power-of-two groups (binary decomposition of the
    # segment count — popcount(k) dispatches instead of k, final
    # surplus tokens truncated as before)
    Pw, n_mid = S // 2, S // 2 + 1
    if remaining > 0:
        window = window[:, -Pw:]
        # only entered after a full first segment, which always leaves
        # P0 + (S - P_pad + 1) > Pw true tokens — padding here would
        # teacher-force fabricated context, so fail loudly instead
        assert window.shape[1] == Pw, window.shape
        # every refresh segment's rng is fold_in(base, ordinal) — the
        # stream does not depend on batch gate or group decomposition
        rng, base = jax.random.split(rng)
        ordinal = 0
        if B < 16:
            # grouped dispatch pays when per-step device time is small
            # relative to the per-dispatch overhead (measured on v5e
            # char-GPT 1k tokens: B=1 166-204 -> 129-153 ms, B=8
            # 201-247 -> 168-176; at B=32 device time dominates and the
            # scan costs ~7% — the per-segment loop keeps it)
            k = -(-remaining // n_mid)
            g = 1 << (k.bit_length() - 1)
            while k > 0:
                if g <= k:
                    # key+counter idiom: _refresh_group fold_ins the
                    # per-segment ordinal internally, so passing `base`
                    # each iteration is NOT stream reuse (see the
                    # fold_in(base, ordinal) comment above)
                    toks, window = _refresh_group(  # graftlint: disable=GL003
                        params, window, g, jnp.int32(ordinal), base,
                        cfg, gcfg, allow_pallas)
                    take = min(g * n_mid, remaining)
                    chunks.append(toks[:, :take])
                    remaining -= take
                    ordinal += g
                    k -= g
                g //= 2
        else:
            while remaining > 0:
                sub = jax.random.fold_in(base, ordinal)
                toks = _decode_segment(params, window, Pw, n_mid, sub, cfg,
                                       gcfg, allow_pallas)
                take = min(n_mid, remaining)
                chunks.append(toks[:, :take])
                remaining -= take
                ordinal += 1
                window = jnp.concatenate([window, toks[:, :take]],
                                         axis=1)[:, -Pw:]
    return jnp.concatenate(chunks, axis=1)
