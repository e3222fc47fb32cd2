"""Continuous-batching inference engine.

One pre-compiled multi-slot decode step, driven by a host-side
scheduler — the serving shape both the compiler-first O(1)-caching and
the pjit/TPU-scaling playbooks converge on (PAPERS.md): the device
program never changes at steady state, and all request-level dynamism
(arrivals, lengths, completions, cancellations) lives in cheap host
bookkeeping plus small per-step input arrays.

Per step the engine:

1. expires deadlines (queued and active),
2. admits queued prompts into free pool slots, gated on free PAGES as
   well as free slots (serve/pages.py: the KV cache is a paged pool +
   per-slot page tables with radix prefix reuse) — admission claims the
   longest cached prefix and chunked prefill
   (``models.gpt.prefill_chunk_paged``) writes only the UNCACHED tail's
   K/V through the slot's page table, under ONE compiled program
   regardless of prompt length or prefix-hit length,
3. runs ONE jitted decode dispatch over ALL slots — per-slot page
   tables, positions, active mask, RNG streams and sampling params
   (``sample.generate.sample_tokens_batched``). With
   ``EngineConfig.decode_window > 1`` the dispatch is a WINDOW of k
   decode steps rolled into one program
   (``models.gpt.decode_window_paged``: a lax.scan over the step body
   with per-slot budget/EOS masks computed ON DEVICE, so a slot
   finishing mid-window idles inside it instead of forcing an early
   exit), the step state ``(tok, pos, active, budget, rngs)`` lives on
   the device and is DONATED from window to window alongside the
   cache, and the host runs AHEAD of the device: window N+1 is
   dispatched before window N's token block is fetched (one async
   ``copy_to_host_async`` + ``np.asarray`` per window, not one
   blocking snapshot per token).
   The window cadence is CONTINUOUS (ROADMAP item 4): an admission
   lands at a window boundary as host bookkeeping while window N-1 is
   still in flight, and the prompt's uncached tail prefills INSIDE
   window N as a Sarathi-style mixed prefill+decode program
   (``models.gpt.mixed_window_paged`` — new slots write prompt chunks
   while resident slots decode, one per-slot phase mask, no separate
   prefill dispatches); deadline expiry and cancels land as per-
   dispatch lifecycle masks (``_merge_lifecycle`` — the slot goes
   inactive on device, its pages free at the boundary, and a
   cancelled slot emits no tokens after the mask lands); the window
   size can AUTO-TUNE from the live host-vs-device dispatch split
   (bounded additive increase over construction-warmed buckets,
   ``decode_window_auto``). Only a speculative mode flip still drains
   the window (counted in ``window_breaks_*``). With a drafter
   attached (serve/speculative.py) the decode phase is instead ONE
   jitted ``_engine_verify``: score a static (k+1)-token drafted
   window per slot against the pooled cache and commit 1..k+1
   accepted tokens — up to k+1 tokens per slot per full-model
   forward, interleaved with chunked prefill admissions exactly like
   plain decode (and with continuous windows while speculation is
   degraded).

Zero recompiles at steady state: the decode/verify programs are keyed
only on the (static) model config, pool/page shapes, draft width and
the engine's sharding plan, the prefill program only on the chunk
shape, the COW page copy on the pool shape alone; page tables,
positions and every other request-level input are traced fixed-shape
arrays, so admissions, prefix hits, LRU evictions and copy-on-write
splits all happen without a recompile. All are module-level jits whose
cache sizes the tests assert stay flat across a long replay
(tests/test_serve.py, tests/test_speculative.py, tests/test_pages.py).

Sharded serving (``EngineConfig.mesh_data``/``mesh_model``, the
``--mesh-shape`` knob): the SAME engine runs GSPMD-partitioned over a
(data, model) mesh — params take the decode TP layout, the paged pool
shards its physical page axis over 'data' and its model dim over
'model' (parallel.mesh.page_pool_pspec, designed first per ROADMAP),
and every program above carries the engine's static
``ServeShardings`` bundle so the pool layout survives each traced body
(donation needs matching shardings to alias) while the step state and
the per-window token block stay replicated — the host fetch contract
(one ``np.asarray`` per window, reading a local shard) is unchanged.
Request-level architecture, host bookkeeping and the paged Pallas
fallback routing (ops/paged_pallas.paged_kernel_mesh_ok) are all
mesh-agnostic; greedy streams are token-identical across mesh shapes
(tests/test_serve_mesh.py).

Observability: per-request TTFT / decode tok/s / queue wait, engine
counters (admissions, rejections, completions, tokens), slot-occupancy
and queue-depth gauges, batch-fill-ratio and step-latency histograms —
through ``utils.logging.Metrics`` and ``utils.profiling.StepTimer``.
Every step marks its host phases through ``self.tel.phase`` (a
``jax.profiler.TraceAnnotation`` whether or not a recorder is attached:
``serve/step`` around ``serve/expire_shed``, ``serve/admit``,
``serve/prefill``, and ``serve/decode`` | ``serve/verify`` around
``serve/launch``, ``serve/fetch``, ``serve/commit``), with the live
context (``live_tokens``, ``live_kv_bytes``, ``stochastic_rows``, the
paged kernel's ``kv_blocks_live`` / ``kv_block_passes`` /
``kv_blocks_grid``, from the host
mirrors) as stats of ``serve/launch``;
docs/observability.md
has the vocabulary. Construction is the ``setup/engine`` span of the
process's set-up record (``utils.telemetry.setup_phase``), and every
guarded program's first call a build in it; ``metrics_summary()["setup"]``
and the ``setup_*`` gauges carry its totals.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..faults.inject import fire as fault_fire
from ..faults.watchdog import (LoadShedder, ResilienceConfig, SpecHealth,
                               StepWatchdog)
from ..models.families import family, serve_refusals
from ..sample.generate import sample_tokens_batched
from ..utils.logging import Metrics
from ..utils.profiling import StepTimer
from ..utils.sanitize import CompileGuard, check_in_bounds, sanitize_enabled
from ..utils.telemetry import (ENGINE_TRACK, NULL, SLOT_TRACK_BASE,
                               setup_phase, setup_record)
from .pages import PagedCachePool
from .requests import (FINISH_CANCELLED, FINISH_DEADLINE, FINISH_EOS,
                       FINISH_LENGTH_CAP, FINISH_MAX_TOKENS,
                       FINISH_PREFILLED, FINISH_SHED, REJECT_BAD_REQUEST,
                       Request, RequestResult)
from .scheduler import Scheduler
from .speculative import (DraftContext, Drafter, spec_accept_and_sample,
                          timed_draft)

#: k-autotune policy (EngineConfig.decode_window_auto): consult the
#: host-vs-device dispatch split every this-many windows, and climb one
#: bucket while the host tax still exceeds this fraction of window wall
#: time. Small interval on purpose — the policy is bounded (one bucket
#: per decision, capped at decode_window) so eagerness cannot overshoot.
WINDOW_AUTOTUNE_INTERVAL = 8
WINDOW_AUTOTUNE_HOST_FRAC = 0.05

log = logging.getLogger(__name__)


@dataclass(frozen=True)
class EngineConfig:
    """Engine sizing. ``prefill_chunk=0`` auto-sizes to
    min(64, block_size): small enough that short prompts don't pay a
    huge padded chunk, large enough that long prompts take few chunk
    dispatches — and ONE compiled prefill program either way."""

    pool_size: int = 8
    max_queue: int = 64
    prefill_chunk: int = 0
    # --- paged KV cache (serve/pages.py) --------------------------------
    page_size: int = 0        # tokens per KV page; 0 = min(16, block_size)
    max_pages: int = 0        # logical pages per slot; 0 = ceil(block/page)
    n_pages: int = 0          # physical pool pages; 0 = pool_size*max_pages
                              # (the contiguous pool's HBM exactly); fewer
                              # pages shrinks HBM and admission gates on it
    prefix_cache: bool = True  # radix prefix reuse (False: pages only)
    paged_kernel: bool = False  # opt-in Pallas paged decode fast path
                                # (TPU, packed cache layout only):
                                # the per-layer kernel (ops/paged_pallas)
    decode_window: int = 1      # decode steps rolled into one dispatch
                                # at steady state (the --decode-window
                                # knob): 1 = the blocked step-per-
                                # dispatch loop; >1 enables the async
                                # double-buffered window path. The
                                # continuous-window engine keeps
                                # windows engaged through admissions
                                # (mixed prefill+decode dispatch),
                                # deadlines and cancels (on-device
                                # lifecycle masks); only speculative
                                # verify/re-probe still breaks windows
    decode_window_auto: bool = False
                                # auto-tune the window size from the
                                # live dispatch split (host-us vs
                                # device-us per window): bounded
                                # additive increase over the bucketed
                                # sizes window_buckets(), with
                                # decode_window as the cap. Every
                                # bucket's programs are compiled at
                                # engine construction, so tuning moves
                                # between ALREADY-WARM programs and can
                                # never recompile mid-traffic
    # --- serving mesh (parallel/mesh.py, the --mesh-shape knob) ---------
    mesh_data: int = 1          # 'data' axis: the paged pool's physical
                                # page axis shards across it — each chip
                                # stores n_pages/data pages, so the same
                                # per-chip HBM holds data× more
                                # aggregate pages (capacity multiplier)
    mesh_model: int = 1         # 'model' axis: Megatron TP over the
                                # decode/prefill/verify programs
                                # (attention+MLP FLOPs multiplier);
                                # params shard by the training TP specs,
                                # replicated over 'data'
    # --- quantization (replicatinggpt_tpu/quant/, the --kv-quant /
    # --weight-quant knobs) ----------------------------------------------
    kv_quant: str = "none"      # paged KV page storage: none|int8|fp8.
                                # int8/fp8 pages + per-row scale
                                # metadata halve bytes/page — at fixed
                                # HBM that doubles n_pages, the
                                # admission currency (size the pool
                                # with pages.n_pages_for_hbm)
    weight_quant: str = "none"  # block matmul kernels: none|int8|fp8,
                                # absmax-per-output-channel scales with
                                # dequant fused into the matmuls
                                # (quant/weights.py; params quantize at
                                # engine construction unless already
                                # carrying scales from a serialized
                                # calibration)
    quant_granularity: str = "page"
                                # KV scale granularity: 'page' = one
                                # f32 scale per written row, 'head' =
                                # one per (row, head) — tighter for
                                # outlier heads at H x the metadata
                                # (both granularities dequant inside
                                # the paged kernels)
    act_quant: str = "none"     # W8A8: 'int8' quantizes activation
                                # rows into the int8 weight matmuls
                                # (requires weight_quant='int8';
                                # models.gpt._wmm runs the contraction
                                # int8 x int8 -> int32, dequanted by
                                # the separable row x channel scales)

    @property
    def mesh_shape(self) -> tuple:
        return (self.mesh_data, self.mesh_model)

    def quant(self):
        """The QuantConfig this engine runs under (validated)."""
        from ..quant import QuantConfig
        q = QuantConfig(kv_dtype=self.kv_quant,
                        weight_dtype=self.weight_quant,
                        granularity=self.quant_granularity,
                        act_dtype=self.act_quant)
        q.validate()
        return q

    def chunk(self, block_size: int) -> int:
        """Effective prefill chunk — see ``cache_pool.prefill_chunk_size``
        for the divisor-rounding rule and why it is load-bearing."""
        from .cache_pool import prefill_chunk_size
        return prefill_chunk_size(self.prefill_chunk, block_size)

    def window_buckets(self) -> tuple:
        """The static window sizes this engine may dispatch, smallest
        first. Fixed small set by design: every bucket is a separate
        compiled program (the window width is static), all of them
        warmed at engine construction, so the k-autotuner's additive
        increase walks between warm programs and ``decode_window_auto``
        can never cost a mid-traffic compile. Non-auto engines own
        exactly one window program (their configured k)."""
        W = max(int(self.decode_window), 1)
        if W <= 1:
            return (1,)
        if not self.decode_window_auto:
            return (W,)
        out, b = [], 2
        while b < W:
            out.append(b)
            b *= 2
        out.append(W)
        return tuple(out)

    def warmup_tokens(self) -> int:
        """Tokens a warmup request must generate so that the
        request-driven warmup EXERCISES the steady-state window path on
        top of the admission boundary's mixed dispatch (the window
        programs themselves are compiled at engine construction —
        ``Engine._warm_windows`` — so this is a drive-through, not the
        compile). ONE definition, shared by the replay warmup and the
        worker's readiness warmup."""
        return 1 if self.decode_window <= 1 else 2 * self.decode_window + 2


@dataclass(frozen=True)
class KernelRoute:
    """The per-engine kernel-route decision, computed ONCE at
    construction (``decide_kernel_route``) and exported verbatim —
    ``metrics_summary()['kernel_route']``, the
    ``kernel_route_pallas`` Prometheus gauge and the serve bench
    artifact all read this object, so "no XLA fallback" is observable,
    not asserted.

    ``route`` is the headline: "pallas" iff EVERY hot step of this
    engine (decode windows, mixed prefill+decode windows, speculative
    verify) runs the unified Pallas kernel family; "xla" otherwise,
    with ``reasons`` naming each failed envelope check (the shared
    ``ops.paged_pallas.paged_attention_envelope`` vocabulary plus the
    engine-level gates below). ``decode`` is the decode step's own
    route: "pallas" (the per-layer windowed kernel) or "xla" (the
    gather)."""

    route: str                    # "pallas" | "xla"
    decode: str                   # "pallas" | "xla"
    window: str                   # mixed/verify windowed steps ("none":
                                  # the family refuses both)
    sharded: bool                 # kernels run under shard_map
    mesh: tuple                   # (data, model)
    kv_quant: str
    weight_quant: str
    granularity: str
    act_quant: str
    reasons: tuple                # every failed gate ("" when pallas)

    def summary(self) -> dict:
        """The pinned ``metrics_summary()['kernel_route']`` schema."""
        return {
            "route": self.route,
            "decode": self.decode,
            "window": self.window,
            "sharded": self.sharded,
            "mesh": list(self.mesh),
            "kv_quant": self.kv_quant,
            "weight_quant": self.weight_quant,
            "granularity": self.granularity,
            "act_quant": self.act_quant,
            "reasons": list(self.reasons),
        }


def decide_kernel_route(cfg: ModelConfig, ecfg: EngineConfig, qcfg,
                        page_size: int, n_pages: int, itemsize: int,
                        mesh) -> KernelRoute:
    """Route every engine step family onto the unified Pallas kernel
    family, once, statically. The ONLY gates left are real envelope
    limits (shape/VMEM/backend) and the explicit ``paged_kernel`` knob
    — mixed windows, fp8/head-granularity pools, weight-quantized
    params and >1 (data, model) meshes all route Pallas now (ISSUE 20;
    the shard_map wrapper covers sharded engines when the pool
    geometry divides, ``paged_kernel_mesh_ok``)."""
    from ..ops import paged_pallas
    reasons = []
    if not ecfg.paged_kernel:
        reasons.append("paged_kernel_off")
    if cfg.decode_cache_layout != "packed":
        reasons.append("cache_layout")
    if not paged_pallas._paged_attn_backend_ok():
        reasons.append("backend")
    if cfg.kv_lora_rank:        # latent rows: the latent kernel's own
        _, env_reasons = paged_pallas.mla_attention_envelope(
            cfg.n_head, cfg.kv_token_width, page_size, itemsize=itemsize,
            mesh=mesh, kv_quant=qcfg.kv_dtype)
    else:
        _, env_reasons = paged_pallas.paged_attention_envelope(
            cfg.n_head, cfg.head_dim, page_size, itemsize=itemsize,
            mesh=mesh, kv_quant=qcfg.kv_dtype, granularity=qcfg.granularity,
            n_pages=n_pages, n_kv_head=cfg.kv_heads)
    reasons.extend(env_reasons)
    base_ok = not reasons
    # None: the family has no mixed or verify step to route, and the
    # headline follows its decode kernel alone
    windowed_ok = family(cfg).window_kernel_ok(
        cfg, page_size, n_pages, itemsize, mesh, qcfg)
    decode = "pallas" if base_ok else "xla"
    window = ("none" if windowed_ok is None
              else "pallas" if (base_ok and windowed_ok) else "xla")
    route = "pallas" if (decode != "xla" and window != "xla") else "xla"
    return KernelRoute(
        route=route, decode=decode, window=window,
        sharded=bool(mesh is not None and mesh.size > 1
                     and decode != "xla"),
        mesh=(ecfg.mesh_data, ecfg.mesh_model),
        kv_quant=qcfg.kv_dtype, weight_quant=qcfg.weight_dtype,
        granularity=qcfg.granularity, act_quant=qcfg.act_dtype,
        reasons=tuple(reasons))


@dataclass
class _Active:
    """Host-side record of a request occupying a slot."""

    req: Request
    t_submit: float
    t_admit: float
    cap: int                      # max new tokens this slot can produce
    capped: bool                  # cap < req.max_new_tokens (context limit)
    tokens: List[int] = field(default_factory=list)
    t_first_token: float = 0.0
    t_last_token: float = 0.0


@dataclass
class _InFlight:
    """One dispatched-but-not-yet-fetched decode window. ``toks`` and
    ``emitted`` are the dispatch's (k, n_slots) device outputs; their
    host copy starts the moment the dispatch launches
    (``copy_to_host_async``) so the drain's ``np.asarray`` overlaps
    device compute instead of stalling on it."""

    toks: jax.Array               # (k, n_slots) sampled tokens
    emitted: jax.Array            # (k, n_slots) bool live-at-step mask
    k: int                        # static window width of the dispatch
    t0_us: float                  # launch timestamp (telemetry clock)
    t_wall: float                 # launch timestamp (perf_counter)
    n_active: int                 # live slots at launch
    host_s: float = 0.0           # host dispatch tax of the launch (the
                                  # numerator of the autotuner's
                                  # host-vs-device split)
    #: (slot, request_id) pairs whose in-window prefill COMPLETES in
    #: this dispatch — their radix registration (pool.commit_admission)
    #: happens at this window's drain, once the writes are known landed;
    #: the id guards against the slot having been recycled since
    pf_done: List = field(default_factory=list)


def _upload(mirror: np.ndarray) -> jax.Array:
    """Device copy of a LIVE host mirror for an async dispatch. The
    copy is the point: ``jnp.asarray`` of a numpy array may alias its
    memory (the CPU backend does, zero-copy), and the windowed engine
    keeps mutating its mirrors at later boundaries while the window
    that was handed them is still running — an admission's
    ``_active[slot] = True`` then reached back into the in-flight
    window, whose slot decoded one garbage token before its prefill
    (found rehearsing chip_smoke.py: a 7-token stream under
    max_new_tokens=6, timing-dependent)."""
    return jnp.array(mirror)          # copy=True, unlike jnp.asarray


def _merge_lifecycle(tok, pos, active, budget, life, shardings):
    """Fold the boundary's host-side lifecycle deltas into the donated
    device step state AT THE TOP of a window dispatch — the mechanism
    that keeps admissions, deadlines and cancels from ever invalidating
    the device-resident state (which would force a blocking drain and a
    re-upload, the old k=1 fallback).

    ``life`` is ONE packed (5, n_slots) int32 array — a deliberate
    single device_put per boundary (per-array transfer setup, not
    bytes, dominates small-array upload cost on the hot path):

    - row 0, kill flags: slots whose request was cancelled or passed
      its deadline since the last dispatch go inactive ON DEVICE —
      their writes drop and their emissions mask off from scan step 0,
      so a cancelled slot emits no tokens after the mask lands;
    - row 1, admission flags + rows 2-4 (token, position, budget):
      slots admitted at this boundary take their host-mirror state
      (last prompt token, decode frontier P-1, full budget) and go
      active.

    A traced input, so lifecycle traffic never retraces; a quiet
    boundary passes a cached all-zero array (no device_put at all, and
    the merge folds into the window program — no extra dispatch,
    ever)."""
    kill = life[0].astype(bool)
    adm = life[1].astype(bool)
    tok = jnp.where(adm, life[2], tok)
    pos = jnp.where(adm, life[3], pos)
    budget = jnp.where(adm, life[4], budget)
    active = (active | adm) & ~kill
    if shardings is not None:
        tok, pos, active, budget = (
            jax.lax.with_sharding_constraint(a, shardings.rep)
            for a in (tok, pos, active, budget))
    return tok, pos, active, budget


def _sampler(temp, top_k, top_p, greedy):
    """The window programs' ``sample_fn(rngs, logits, live)``: advance
    every slot's stream, then sample what the LIVE rows asked for
    (``sample_tokens_batched``: argmax alone when they are all greedy).
    The split stays outside the sampler's ``cond``s: a sampled row's
    stream must not depend on whether its neighbours were greedy, and a
    greedy row's key advances all the same."""

    @jax.named_scope("sample")
    def sample_fn(rngs, logits, live):
        splits = jax.vmap(lambda r: jax.random.split(r, 2))(rngs)
        nxt = sample_tokens_batched(splits[:, 0], logits, temp, top_k,
                                    top_p, greedy, live)
        return nxt, splits[:, 1]

    return sample_fn


@partial(jax.jit, static_argnames=("cfg", "k", "use_pallas", "shardings"),
         donate_argnames=("tok", "pos", "active", "budget", "cache",
                          "rngs"))
def _engine_decode_window(params, tok, pos, active, budget, eos, life,
                          tables, cache, rngs, temp, top_k, top_p,
                          greedy, cfg: ModelConfig, k: int,
                          use_pallas: bool = False, shardings=None):
    """The steady-state program: ``k`` multi-slot PAGED decode + batched
    sample steps in ONE dispatch (``models.gpt.decode_window_paged``),
    with the whole per-slot step state ``(tok, pos, active, budget,
    rngs)`` donated alongside the cache — at k > 1 the engine feeds each
    window the previous window's returned state without ever touching
    the host, so the old buffers alias the new in place.

    All request-level inputs are small traced arrays — the (n_slots,)
    step vectors plus the (n_slots, max_pages) page tables — so
    admissions/completions/prefix-hits/evictions/COW remaps never
    retrace, and the window width is static: a slot that exhausts its
    budget or samples its eos token mid-window goes inactive ON DEVICE
    and idles for the window's remainder (partial windows are a masked
    tail, never a second program). Inactive slots run at position 0
    with their cache writes DROPPED inside ``decode_step_paged`` (a
    released slot's stale table may reference pages another request now
    owns) and their sampled token is masked to 0.

    ``shardings`` (parallel.mesh.ServeShardings; STATIC — hashable, one
    value per engine, so sharded and unsharded engines are distinct
    programs under the same budget discipline) runs the whole window on
    the serving mesh: the page pool stays pinned to its (data, model)
    PartitionSpec through every scan step (donation needs matching in/
    out shardings to alias), the step state and the (k, n_slots) token
    block leave fully replicated — the caller's ``np.asarray`` fetch is
    a local read, never a cross-device gather.
    """
    tok, pos, active, budget = _merge_lifecycle(
        tok, pos, active, budget, life, shardings)

    return family(cfg).decode_window_paged(
                               params, tok, pos, active, budget, eos,
                               tables, cache, rngs, cfg,
                               sample_fn=_sampler(temp, top_k, top_p, greedy),
                               length=k,
                               use_pallas=use_pallas, shardings=shardings)


@partial(jax.jit, static_argnames=("cfg", "k", "use_kernel", "shardings"),
         donate_argnames=("tok", "pos", "active", "budget", "cache",
                          "rngs"))
def _engine_mixed_window(params, tok, pos, active, budget, eos, life,
                         pfc, pf_toks, tables, cache, rngs,
                         temp, top_k, top_p, greedy, cfg: ModelConfig,
                         k: int, use_kernel: bool = False,
                         shardings=None):
    """The mixed steady-state program: ``models.gpt.mixed_window_paged``
    behind the same lifecycle merge, donation set and sampling closure
    as ``_engine_decode_window`` — dispatched instead of the pure decode
    window whenever an admission left prompt chunks to write, so newly
    admitted slots prefill while resident slots decode and the window
    cadence never breaks. One compiled program per window bucket (the
    prefill chunk width and pool shapes are static); the per-slot phase
    mask, chunk cursors and chunk payloads are all traced inputs, so
    WHICH slots prefill and how much never retraces. ``use_kernel``
    (STATIC; the engine gates it on
    ``ops.paged_pallas.mixed_step_kernel_ok``) routes every step's
    windowed forward through the unified paged Pallas kernel —
    prefilling slots scatter chunk rows through their page tables and
    decoding slots do the verify<->decode row math in the SAME launch
    (the seam PR 12 documented, now flipped). ``pfc`` packs the three
    (n_slots,) prefill cursors — chunks-this-window / next write
    position / true prompt length — into one (3, n_slots) upload,
    like ``life``."""
    tok, pos, active, budget = _merge_lifecycle(
        tok, pos, active, budget, life, shardings)

    return family(cfg).mixed_window_paged(
                              params, tok, pos, active, budget, eos,
                              pfc[0], pfc[1], pfc[2], pf_toks,
                              tables, cache, rngs, cfg,
                              sample_fn=_sampler(temp, top_k, top_p, greedy),
                              length=k,
                              shardings=shardings, use_kernel=use_kernel)


@partial(jax.jit, static_argnames=("cfg", "shardings"),
         donate_argnames=("cache",))
def _engine_prefill(params, chunk, offset, limit, table_row, slot, cache,
                    cfg: ModelConfig, shardings=None):
    """One prefill chunk of one slot through ``cfg``'s family. ``slot``
    names the slot for a family that keeps per-slot state beside the
    pages; GPT-2's program never reads it, and jit drops an argument
    nothing reads BEFORE it is put on the device: callers hand it over as
    a numpy scalar, never as a device array made for the call (one
    upload a chunk for nothing)."""
    return family(cfg).prefill_chunk_paged(
        params, chunk, offset, limit, table_row, slot, cache, cfg,
        shardings=shardings)


@partial(jax.jit, static_argnames=("cfg", "use_kernel", "shardings"),
         donate_argnames=("cache", "rngs"))
def _engine_verify(params, window, pos, m, active, tables, cache, rngs,
                   temp, top_k, top_p, greedy, cfg: ModelConfig,
                   use_kernel: bool = False, shardings=None):
    """The speculative steady-state program: ONE target forward over a
    static (n_slots, k+1) window against the PAGED pool + per-position
    acceptance. Draft count k is carried by the window's static width,
    so a fixed --spec-k means exactly one extra compiled program next
    to decode/prefill. All request-level inputs — positions, valid-
    draft counts, page tables, sampling params, the drafted tokens —
    are traced fixed-shape arrays, so acceptance outcomes never
    retrace. Inactive slots run at position 0 with zero valid drafts
    and dropped writes; their outputs are masked. ``shardings`` runs
    the verify forward on the serving mesh (pool pinned per layer) with
    the acceptance outputs replicated for the host commit.
    """
    logits, cache = family(cfg).verify_step_paged(
                                      params, window, pos, m, active,
                                      tables, cache, cfg,
                                      shardings=shardings,
                                      use_kernel=use_kernel)
    m_eff = jnp.where(active, m, 0)
    n_acc, out, rngs = spec_accept_and_sample(rngs, logits, window, m_eff,
                                              temp, top_k, top_p, greedy,
                                              active)
    n_acc = jnp.where(active, n_acc, 0)
    out = jnp.where(active[:, None], out, 0)
    if shardings is not None:
        n_acc = jax.lax.with_sharding_constraint(n_acc, shardings.rep)
        out = jax.lax.with_sharding_constraint(out, shardings.rep)
        rngs = jax.lax.with_sharding_constraint(rngs, shardings.rep)
    return n_acc, out, cache, rngs


@partial(jax.jit, static_argnames=("shardings",),
         donate_argnames=("cache",))
def _engine_page_copy(cache, src, dst, shardings=None):
    """Copy-on-write page split: duplicate physical page ``src`` into
    ``dst`` across all layers of EVERY pool array — the quantized
    pool's ``ks``/``vs`` scale arrays share the page axis (axis 1), so
    a COW split carries a page's scales with its rows for free. One
    program for any (src, dst) — both traced scalars — warmed at
    engine construction so the first real COW mid-replay cannot cost a
    compile. The caller bounds dst host-side (check_in_bounds below
    no-ops on tracers). On a serving mesh the copy crosses data shards
    when src and dst land on different chips — GSPMD inserts the
    collective; each output stays pinned to its entry's spec
    (models.gpt.pool_entry_sharding) so the donated buffers alias."""
    from ..models.gpt import pool_entry_sharding
    out = {}
    for name, arr in cache.items():
        check_in_bounds(dst, 1, arr.shape[1], what="COW page copy")
        page = jax.lax.dynamic_index_in_dim(arr, src, 1, keepdims=True)
        new = jax.lax.dynamic_update_slice_in_dim(arr, page, dst, axis=1)
        if shardings is not None:
            new = jax.lax.with_sharding_constraint(
                new, pool_entry_sharding(shardings, name))
        out[name] = new
    return out


@jax.jit
def _engine_page_export(pool_entries, src):
    """Disaggregated transfer, source side (serve/disagg.py): slice
    physical page ``src`` out of every pool entry — K/V rows at the
    storage dtype AND the quantized pool's per-row scale arrays, which
    share the page axis (axis 1), so an int8/fp8 page's scales leave
    with its rows for free. One program for any page (``src`` traced),
    warmed at engine construction next to the COW copy; the caller
    batches every requested page's dispatch before its single
    ``device_get`` sync. A READ of the pool, never an update — the
    pool must survive, so nothing donates (hence ``pool_entries``,
    not the update programs' donated ``cache``)."""
    return {name: jax.lax.dynamic_index_in_dim(arr, src, 1, keepdims=True)
            for name, arr in pool_entries.items()}


@partial(jax.jit, static_argnames=("shardings",),
         donate_argnames=("cache",))
def _engine_page_install(cache, dst, blocks, shardings=None):
    """Disaggregated transfer, destination side: scatter one
    transferred page's blocks (the exact per-entry slices
    ``_engine_page_export`` produced, round-tripped through the RPC
    byte codec) into physical page ``dst`` of the local pool. Same
    shape/dtype discipline as the COW copy — ``dst`` is a traced
    scalar and the blocks are fixed-shape, so installing any page into
    any slot of the pool is ONE compiled program, warmed at engine
    construction (a transfer mid-traffic can never cost a compile).
    The table rebase the tentpole names happens host-side: installed
    pages enter the local radix (``PagedCachePool.commit_install``)
    and the next admission's claim maps logical prompt pages to these
    LOCAL physical indices through the ordinary chain walk."""
    from ..models.gpt import pool_entry_sharding
    out = {}
    for name, arr in cache.items():
        check_in_bounds(dst, 1, arr.shape[1], what="page install")
        new = jax.lax.dynamic_update_slice_in_dim(arr, blocks[name], dst,
                                                  axis=1)
        if shardings is not None:
            new = jax.lax.with_sharding_constraint(
                new, pool_entry_sharding(shardings, name))
        out[name] = new
    return out


@partial(jax.jit, static_argnames=("dtype",))
def _cast_leaves(leaves, dtype):
    return [a.astype(dtype) for a in leaves]


def _wide_leaves(flat, names, dtype) -> List[int]:
    """Indices of the leaves of ``flat`` (``tree_flatten_with_path``)
    that ``served_tree`` casts."""
    return [i for i, (path, a) in enumerate(flat)
            if getattr(path[-1], "key", None) in names
            and jnp.issubdtype(a.dtype, jnp.floating)
            and a.dtype.itemsize > dtype.itemsize]


def served_cast_bytes(params, names, dtype) -> int:
    """Bytes of the copies ``served_tree(params, names, dtype)`` makes,
    from the leaves' shapes (nothing is cast)."""
    dtype = jnp.dtype(dtype)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return sum(int(flat[i][1].size) * dtype.itemsize
               for i in _wide_leaves(flat, names, dtype))


def served_tree(params, names, dtype):
    """The tree the engine's programs take: ``params`` with every leaf
    NAMED in ``names`` (the family's ``serve_cast_leaves``: what its
    entry points read only as ``leaf.astype(dtype)``) that is a floating
    array wider than ``dtype`` replaced by its ``dtype`` copy, made by
    one jitted cast (the rounding the launch did); every other leaf is
    the SAME array. The programs' own ``astype`` is then the identity,
    so no launch converts a weight again. A tree with nothing to cast
    (already in the compute dtype, or quantised kernels) comes back AS IT
    IS, the same containers: whoever replaces a leaf of ``params`` in
    place afterwards (the benchmark's 8-bit control rounds 10 GB leaf by
    leaf) then frees the old array instead of leaving it alive here."""
    dtype = jnp.dtype(dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [a for _, a in flat]
    wide = _wide_leaves(flat, names, dtype)
    if not wide:
        return params
    for i, c in zip(wide, _cast_leaves([leaves[i] for i in wide], dtype)):
        leaves[i] = c
    return treedef.unflatten(leaves)


def engine_summary_block(engine: "Engine") -> dict:
    """The per-replica block of the fleet summary — ONE definition
    consumed by both sides of the process boundary (the in-process
    ``router.Replica.summary_block`` and the worker's ``summary`` RPC),
    so the multiproc bench artifact can never silently diverge in
    shape from the in-process one."""
    s = engine.metrics_summary()
    return {
        "occupancy_mean": round(
            s["histograms"].get("batch_fill_ratio", {})
            .get("mean", 0.0), 4),
        "n_steps": engine.n_steps,
        "pages": s["pages"],
        "finished": {k: int(v) for k, v in
                     engine.metrics.counters.items()
                     if k.startswith("finished_")},
    }


def compile_counts() -> Dict[str, int]:
    """Process-wide compiled-program counts for the engine entry points
    (module-level jits, so they accumulate across engines), including
    the speculative verify step, the COW page copy, and the model
    drafter's two programs. The replay driver's before/after
    bookkeeping reads these; the *live* steady-state enforcement is
    per-engine via :class:`CompileGuard` (utils.sanitize), which raises
    from the offending step instead of reporting after the fact."""
    from .speculative import _draft_decode_k, _draft_prefill
    return {"decode": _engine_decode_window._cache_size(),
            "mixed": _engine_mixed_window._cache_size(),
            "prefill": _engine_prefill._cache_size(),
            "verify": _engine_verify._cache_size(),
            "page_copy": _engine_page_copy._cache_size(),
            "page_export": _engine_page_export._cache_size(),
            "page_install": _engine_page_install._cache_size(),
            "draft_decode": _draft_decode_k._cache_size(),
            "draft_prefill": _draft_prefill._cache_size()}


class Engine:
    """Continuous-batching engine over a pooled KV cache.

    Host API (single-threaded by design — drive it from one loop):

    - ``submit(req)`` -> None (accepted) or a rejected ``RequestResult``
      (backpressure / validation, with the reason as finish_reason);
    - ``cancel(request_id)`` -> bool;
    - ``step()`` -> list of requests finishing this step;
    - ``drain()`` -> run steps until idle, return all finishes;
    - ``metrics_summary()`` -> counters/gauges/histograms + step-latency
      percentiles.
    """

    @setup_phase("setup/engine")
    def __init__(self, params, cfg: ModelConfig,
                 ecfg: EngineConfig = EngineConfig(),
                 clock: Callable[[], float] = time.monotonic,
                 drafter: Optional[Drafter] = None,
                 rcfg: Optional[ResilienceConfig] = None,
                 journal=None, telemetry=None, track_base: int = 0,
                 track_label: str = ""):
        """``rcfg`` (faults.watchdog.ResilienceConfig) opts into the
        self-healing policies — stall watchdog, speculative auto-disable
        with re-probe, load shedding; None/all-zero changes nothing.
        ``journal`` (serve.journal.RequestJournal) records accepted and
        finished requests for restart recovery. ``telemetry`` (a
        utils.telemetry.Telemetry, ideally sharing this engine's
        ``clock`` so request envelopes and step spans land on one
        timeline) opts into request-lifecycle tracing: one span tree
        per request on per-slot tracks plus step/draft spans and
        prefix-hit/COW/eviction/recovery instants; None means the
        zero-cost NULL recorder and changes nothing. ``track_base``
        offsets every track id this engine emits on — the fleet router
        gives replica ``i`` base ``i * REPLICA_TRACK_STRIDE`` so N
        replicas share one recorder without colliding tracks
        (``track_label`` prefixes the human-readable track names).

        All of it is the ``setup/engine`` span of the set-up record
        (``utils.telemetry.setup_phase``), with ``setup/weights_ready``,
        ``setup/served_tree``, ``setup/pool`` and ``setup/warm_programs``
        inside."""
        # the caller's weights are dispatched asynchronously: wait for
        # them HERE, or their device time lands in whatever waits first
        with setup_phase("setup/weights_ready"):
            jax.block_until_ready(params)
        cfg.validate()
        refused = serve_refusals(cfg, ecfg, drafter)
        if refused:
            raise ValueError(f"the {cfg.family} family cannot be served "
                             f"under this engine configuration: "
                             + "; ".join(refused))
        self._fam = family(cfg)
        self.params = params
        # quantization (replicatinggpt_tpu/quant/): weight-side params
        # quantize HERE, before any mesh placement, unless the caller
        # handed in an already-quantized tree (a serialized calibration
        # applied at the CLI layer — quant/weights.py load_calibration)
        self.qcfg = ecfg.quant()
        if self.qcfg.act_enabled and cfg.act_quant != self.qcfg.act_dtype:
            # W8A8 threads through ModelConfig (models.gpt._wmm reads
            # it) — replace() keeps the caller's cfg untouched; the
            # field is part of the fleet shape hash via asdict(cfg)
            import dataclasses as _dc
            cfg = _dc.replace(cfg, act_quant=self.qcfg.act_dtype)
        self.cfg = cfg
        self.ecfg = ecfg
        if self.qcfg.weight_enabled:
            from ..quant.weights import quantize_params
            self.params = quantize_params(self.params,
                                          self.qcfg.weight_dtype)
        # what the programs take: the leaves they would cast in every
        # launch, cast ONCE here (float32 masters: XLA hoisted those
        # casts out of the layer scan, a bf16 copy of all the weights
        # made and dropped a launch). ``self.params`` stays the caller's
        # tree (masters, or the quantised tree): references read it
        self._cast_bytes = served_cast_bytes(
            self.params, self._fam.serve_cast_leaves, cfg.dtype)
        with setup_phase("setup/served_tree",
                         weight_cast_bytes=self._cast_bytes):
            self.served_params = served_tree(
                self.params, self._fam.serve_cast_leaves, cfg.dtype)
        self.clock = clock
        self.drafter = drafter
        self.tel = telemetry or NULL
        self._tb = track_base
        if self.tel.enabled:
            self.tel.name_track(self._tb + ENGINE_TRACK,
                                f"{track_label}engine")
            for s in range(ecfg.pool_size):
                self.tel.name_track(self._tb + SLOT_TRACK_BASE + s,
                                    f"{track_label}slot {s}")
        if drafter is not None:
            dcfg = getattr(drafter, "cfg", None)
            if dcfg is not None:       # model drafter: pools must line up
                assert dcfg.vocab_size == cfg.vocab_size, \
                    "draft model must share the target vocab"
                assert dcfg.block_size == cfg.block_size, \
                    "draft model must share the target block_size"
                assert drafter.pool_size == ecfg.pool_size, \
                    "draft pool must match the engine pool"
        # serving mesh (parallel/mesh.py): params take the decode TP
        # layout (Megatron over 'model', replicated over 'data'), the
        # page pool its (data, model) PartitionSpec — both placed ONCE
        # here; every jitted program then carries the same static
        # ServeShardings bundle, so GSPMD runs the whole engine sharded
        # without any program gaining a second compiled variant.
        # Drafter params/caches stay single-device (they are separate
        # jits over separate state — prefix reuse logic is unchanged).
        self.mesh = None
        self._plan = None
        if ecfg.mesh_data > 1 or ecfg.mesh_model > 1:
            from ..parallel.mesh import (make_serve_mesh,
                                         serve_param_shardings,
                                         serve_shardings)
            from .pages import pool_geometry
            self.mesh = make_serve_mesh(ecfg.mesh_data, ecfg.mesh_model)
            _, _, n_pages_eff = pool_geometry(
                cfg, ecfg.pool_size, ecfg.page_size, ecfg.max_pages,
                ecfg.n_pages)
            self._plan = serve_shardings(self.mesh, cfg, n_pages_eff,
                                         ecfg.mesh_data, ecfg.mesh_model)
            self.served_params = jax.device_put(
                self.served_params,
                serve_param_shardings(cfg, self.mesh, ecfg.mesh_model,
                                      params=self.served_params))
        self._rep = self._plan.rep if self._plan is not None else None
        with setup_phase("setup/pool"):
            self.pool = PagedCachePool(
                cfg, ecfg.pool_size, page_size=ecfg.page_size,
                max_pages=ecfg.max_pages, n_pages=ecfg.n_pages,
                prefix_cache=ecfg.prefix_cache, telemetry=self.tel,
                sharding=(self._plan.cache if self._plan is not None
                          else None),
                scale_sharding=(self._plan.scale if self._plan is not None
                                else None),
                mesh_shape=(ecfg.mesh_data, ecfg.mesh_model),
                quant=(self.qcfg if self.qcfg.kv_enabled else None))
        self.scheduler = Scheduler(ecfg.max_queue, cfg.block_size,
                                   clock=clock)
        self.metrics = Metrics()
        self.step_timer = StepTimer()
        # bytes ONE context token holds in the pool across layers, from
        # the pool's own arrays (K, V and a quantized pool's scales):
        # what ``serve/launch`` multiplies the live tokens by
        self._kv_token_bytes = (
            sum(a.nbytes for a in self.pool.pages.values())
            // (self.pool.n_pages * self.pool.page_size))
        # what a family with window layers and experts adds to the stats
        # of ``serve/launch`` (None: GPT-2, whose stats are unchanged)
        self._launch_extra = self._family_launch_stats()
        P = ecfg.pool_size
        self._chunk = ecfg.chunk(cfg.block_size)
        self._window = max(int(ecfg.decode_window), 1)
        # bucketed window sizes + the autotune cursor: _window_cur is
        # the size the next steady-state dispatch uses; the additive-
        # increase policy (_maybe_autotune) only ever moves it UP the
        # bucket list, and every bucket's programs compile at
        # construction (_warm_windows), so a bucket move is free
        self._buckets = ecfg.window_buckets()
        self._wk = 0
        self._window_cur = self._buckets[0]
        self._at_host = 0.0           # autotune accumulators: host
        self._at_wall = 0.0           # dispatch tax vs window wall time
        self._at_n = 0                # over windows since last decision
        # Kernel route: decided ONCE, statically, for every step family
        # (decode windows, mixed prefill+decode windows, speculative
        # verify) — decide_kernel_route() above; the decision is logged,
        # exported through metrics_summary()["kernel_route"], and
        # mirrored as the kernel_route_pallas Prometheus gauge. One
        # per-layer windowed kernel (its shard_map wrapper on a >1
        # mesh) carries every step.
        itemsize = jnp.dtype(self.pool.kv_array.dtype).itemsize
        self.kernel_route = decide_kernel_route(
            cfg, ecfg, self.qcfg, self.pool.page_size,
            self.pool.kv_array.shape[1], itemsize, self.mesh)
        self._use_pallas = self.kernel_route.decode == "pallas"
        self._use_window_kernel = self.kernel_route.window == "pallas"
        self.metrics.gauge("kernel_route_pallas",
                           1.0 if self.kernel_route.route == "pallas"
                           else 0.0)
        # the per-layer kernel's walk over the pool's tables, for the
        # stats of ``serve/launch``: pages a block of its loop covers (0:
        # the decode step runs no such kernel) and the passes a block
        # takes in the decode step (one query row a head)
        self._kv_block_pages = self._kv_block_passes = 0
        if self._use_pallas:
            from ..ops.paged_pallas import block_pages, block_passes
            self._kv_block_pages = block_pages(
                self.pool.page_size, self.pool.max_pages,
                self.pool.kv_array.shape[-1] * itemsize)
            self._kv_block_passes = block_passes(
                cfg.n_head, cfg.head_dim, n_kv_head=cfg.kv_heads)
        log.info("kernel route: %s (decode=%s window=%s sharded=%s%s)",
                 self.kernel_route.route, self.kernel_route.decode,
                 self.kernel_route.window, self.kernel_route.sharded,
                 (" reasons=" + ",".join(self.kernel_route.reasons)
                  if self.kernel_route.reasons else ""))
        self._tok = np.zeros((P,), np.int32)
        # ALIAS of pool.positions (one host buffer): the pool exposes the
        # committed frontier to drafters, the engine advances it in place
        self._pos = self.pool.positions
        self._active = np.zeros((P,), bool)
        self._budget = np.zeros((P,), np.int32)   # tokens still allowed
        self._eos = np.full((P,), -1, np.int32)   # per-slot stop token
        # lifecycle masks (continuous windows): per-slot deadline
        # precomputed at admission (vectorized expiry check, no dict
        # walk), the pending-kill map feeding the per-dispatch kill
        # flags, and the admission-merge mask — all consumed by
        # _merge_lifecycle at the top of the next window dispatch
        self._deadline = np.full((P,), np.inf)
        self._kill: Dict[str, str] = {}           # request_id -> reason
        self._adm_mask = np.zeros((P,), bool)
        # in-window prefill cursors (mixed steps): chunks left to write,
        # next absolute write position, true prompt length, and the
        # pending padded prompt tails — consumption is deterministic
        # (min(k, pf_left) chunks per window), so the host tracks the
        # cursor without ever fetching device state
        self._pf_left = np.zeros((P,), np.int32)
        self._pf_off = np.zeros((P,), np.int32)
        self._pf_limit = np.zeros((P,), np.int32)
        self._pf_tail: Dict[int, np.ndarray] = {}
        self._temp = np.ones((P,), np.float32)
        self._top_k = np.zeros((P,), np.int32)
        self._top_p = np.zeros((P,), np.float32)
        self._greedy = np.zeros((P,), bool)
        # launch-invariant device inputs (eos / page tables / sampling
        # params), converted ONCE per change instead of once per
        # dispatch — a window dispatch's host tax is mostly device_put
        # calls, so re-uploading arrays that only change at admission/
        # finish boundaries would tax exactly the steady state the
        # window amortizes (None = rebuild at the next launch); plus
        # shared all-zero lifecycle masks for quiet boundaries
        self._li = None
        self._z_life = jnp.zeros((5, P), jnp.int32)
        # async window machinery: the device-resident donated step state
        # (tok, pos, active, budget) between window dispatches — None
        # means "host mirrors are authoritative, re-upload at the next
        # launch" — and the in-flight dispatch whose token block has
        # not been fetched yet (double buffering: window N+1 launches
        # before window N's block is read)
        self._dev_state = None
        self._inflight: Optional[_InFlight] = None
        # committed up front for the same jit-key stability reason as
        # CachePool.cache (the array becomes a committed jit output
        # after the first step)
        from .cache_pool import commit_default
        # (replication is spelled P() for every rank — ServeShardings)
        self._rngs = commit_default(
            jnp.stack([jax.random.PRNGKey(i) for i in range(P)]),
            sharding=self._rep)
        self._slots: Dict[int, _Active] = {}
        self._pending: List[RequestResult] = []  # cancellations between steps
        self.n_steps = 0
        # the steady-state contract, enforced live: each entry point may
        # compile ONE program for this engine's shapes (counted relative
        # to engine construction — the module jit caches accumulate
        # across engines); a second compile raises RecompileError from
        # the step that caused it. Replaces the ad-hoc two-program
        # bookkeeping the first serving PR shipped (compile_counts()
        # remains for offline summaries).
        # a windowed engine owns one decode-window program and one mixed
        # prefill+decode program PER BUCKET (the admission path is a
        # mixed window, never a k=1 fallback — the blocked program only
        # exists on decode_window=1 engines)
        self._decode_guard = CompileGuard(
            _engine_decode_window, "serve/decode",
            max_programs=len(self._buckets))
        self._mixed_guard = CompileGuard(
            _engine_mixed_window, "serve/mixed",
            max_programs=len(self._buckets))
        self._prefill_guard = CompileGuard(_engine_prefill, "serve/prefill")
        self._verify_guard = CompileGuard(_engine_verify, "serve/verify")
        self._copy_guard = CompileGuard(_engine_page_copy, "serve/page-copy")
        self._export_guard = CompileGuard(_engine_page_export,
                                          "serve/page-export")
        self._install_guard = CompileGuard(_engine_page_install,
                                           "serve/page-install")
        with setup_phase("setup/warm_programs"):
            self._warm_programs()
        self._sanitize = sanitize_enabled()
        # self-healing (faults.watchdog): all policies opt-in via rcfg.
        # Degraded transitions move between the two already-budgeted
        # steady-state programs (verify <-> decode), so CompileGuard
        # keeps enforcing zero recompiles through every mode switch.
        self.rcfg = rcfg or ResilienceConfig()
        self.journal = journal
        self._spec_active = drafter is not None
        self._watchdog = (StepWatchdog(self.rcfg, telemetry=self.tel)
                          if self.rcfg.watchdog_on else None)
        self._spec_health = (SpecHealth(self.rcfg, telemetry=self.tel)
                             if (self.rcfg.spec_guard_on
                                 and drafter is not None) else None)
        self._shedder = (LoadShedder(self.rcfg, telemetry=self.tel)
                         if self.rcfg.shed_on else None)
        self._probe_pending = False
        self._spec_pinned = False     # operator pin (set_spec_active)
        #: host-side log of resilience events (bounded — see _event),
        #: for tests/ops
        self.events: List[str] = []

    # ---------------------------------------------------------------- API

    def submit(self, req: Request) -> Optional[RequestResult]:
        self.metrics.inc("requests_submitted")
        if (self.pool.slot_of(req.id) is not None
                or self.scheduler.contains(req.id)):
            # an id must be unique among in-flight requests: results,
            # cancellation, the journal and the pool's reverse index all
            # key on it
            self.metrics.inc(REJECT_BAD_REQUEST)
            return RequestResult(id=req.id, tokens=[],
                                 finish_reason=REJECT_BAD_REQUEST)
        eos = req.eos_token_id
        if eos is not None and not (0 <= int(eos) < self.cfg.vocab_size):
            # the device-side stop mask compares sampled ids against
            # this value; an out-of-vocab eos can never match and is a
            # caller bug — reject it loudly
            self.metrics.inc(REJECT_BAD_REQUEST)
            return RequestResult(id=req.id, tokens=[],
                                 finish_reason=REJECT_BAD_REQUEST)
        reason = self.scheduler.submit(req)
        if reason is not None:
            # an expired-at-submit deadline is a terminal finish, not a
            # backpressure rejection — count it with the finishes
            self.metrics.inc("finished_" + reason
                             if reason == FINISH_DEADLINE else reason)
            return RequestResult(id=req.id, tokens=[], finish_reason=reason)
        if self.journal is not None:
            self.journal.record_submit(req)
        return None

    def cancel(self, request_id: str, migrated: bool = False) -> bool:
        """Cancel a queued or running request. The terminal
        ``RequestResult`` (with any tokens already produced) surfaces
        from the next ``step()``; True iff the request was found.

        On a windowed engine a plain cancel is a LIFECYCLE MASK, not a
        window break: the request id joins the pending-kill map, the
        kill flag rides the next window dispatch (deactivating the slot
        on device from its first scan step — the slot emits nothing
        after the mask lands), and the slot + pages release at that
        boundary, right after the in-flight window's already-committed
        tokens are fetched to ride the terminal result. A cancel racing
        a window that already finished the request surfaces the natural
        finish. On blocked (k=1) engines, and for ``migrated=True`` —
        the fleet router's re-route path, where the id must be
        releasable BEFORE the router resubmits it elsewhere — the old
        drain-now semantics hold: fetch the in-flight window, finish
        and free immediately (counted as a ``cancel`` window break).
        ``migrated=True`` closes the telemetry envelope tagged
        ``migrated`` (a non-terminal segment, see tools/trace_check.py)
        and still journals a finish so THIS replica's journal replay
        never resurrects the id."""
        now = self.clock()
        if self.scheduler.cancel(request_id):
            self.metrics.inc("finished_" + FINISH_CANCELLED)
            self._journal_finish(request_id, FINISH_CANCELLED)
            self._pending.append(RequestResult(
                id=request_id, tokens=[], finish_reason=FINISH_CANCELLED))
            return True
        slot = self.pool.slot_of(request_id)
        if slot is None:
            return False
        if self._window > 1 and not migrated:
            self._kill[request_id] = FINISH_CANCELLED
            return True
        self._pending.extend(self._drain_pending("cancel"))
        slot = self.pool.slot_of(request_id)
        if slot is None:
            # the drained window finished it naturally; its terminal
            # result is already pending
            return True
        self._pending.append(self._finish_slot(slot, FINISH_CANCELLED, now,
                                               migrated=migrated))
        return True

    def partial_tokens(self, request_id: str) -> Optional[List[int]]:
        """Tokens committed so far for an ACTIVE request (host list
        copy; None when the request holds no slot — still queued, or
        already finished). The streaming front door (serve/http.py) and
        the fleet router's delivery dedupe poll this between steps."""
        slot = self.pool.slot_of(request_id)
        if slot is None or slot not in self._slots:
            return None
        return list(self._slots[slot].tokens)

    def in_flight_ids(self) -> List[str]:
        """Every accepted-but-unfinished request id: queued first (in
        arrival order), then active slots. The router's re-route path
        reads this for a wedged replica (for a DEAD one it replays the
        journal instead — host memory died with the replica)."""
        queued = self.scheduler.ids()
        active = [self._slots[s].req.id for s in sorted(self._slots)]
        return queued + active

    def slot_track(self, slot: int) -> int:
        """Telemetry track id of a slot (``track_base``-offset) — the
        router closes a killed replica's open request envelopes on the
        right tracks."""
        return self._tb + SLOT_TRACK_BASE + slot

    # ------------------------------------------- disaggregated transfer

    def export_pages(self, pages: List[int]) -> List[Dict[str, np.ndarray]]:
        """Fetch physical pages to host memory for a cross-tier
        transfer (serve/disagg.py): one warmed jitted slice per page,
        each a dict of per-entry blocks — K/V rows plus any quantized
        scale rows, exactly what ``install_pages`` scatters on the far
        side. Every page's slice is dispatched before the single
        ``device_get`` sync fetches the whole batch. The caller pins
        the pages first (``pool.pin_prefix``) so LRU eviction cannot
        recycle one mid-copy."""
        out = []
        for p in pages:
            check_in_bounds(int(p), 1, self.pool.n_pages,
                            what="page export")
            out.append(self._export_guard(self.pool.pages, jnp.int32(p)))
        self.pool.pages_exported += len(pages)
        return jax.device_get(out)

    def install_pages(self, pages: List[int],
                      blocks: List[Dict[str, np.ndarray]]) -> None:
        """Scatter transferred page blocks into local physical pages
        (allocated + pinned by ``pool.install_prefix``) through the
        construction-warmed install program — zero recompiles, any
        traffic. Shapes/dtypes must match this pool's entries exactly;
        the engine-shape hash both tiers agreed on at registration
        guarantees that, and the assert keeps a codec bug loud."""
        cache = self.pool.pages
        for p, blk in zip(pages, blocks):
            check_in_bounds(int(p), 1, self.pool.n_pages,
                            what="page install")
            dev = {}
            for name, arr in cache.items():
                want = (arr.shape[0], 1) + tuple(arr.shape[2:])
                b = blk[name]
                assert b.shape == want and b.dtype == arr.dtype, (
                    f"page block {name!r}: got {b.shape}/{b.dtype}, "
                    f"pool wants {want}/{arr.dtype}")
                dev[name] = jnp.asarray(b)
            cache = self._install_guard(cache, jnp.int32(p), dev,
                                        shardings=self._plan)
        self.pool.pages = cache

    @property
    def idle(self) -> bool:
        return (not self._active.any() and len(self.scheduler) == 0
                and not self._pending and self._inflight is None)

    def step(self) -> List[RequestResult]:
        """One scheduling iteration: expire -> shed -> admit -> decode,
        with the self-healing policies (watchdog / speculative health /
        shedding) folded around the decode phase when configured.

        With ``decode_window > 1`` the steady-state decode phase is the
        CONTINUOUS window path: dispatch the NEXT k-step window, then
        fetch the previous one's token block — the host stays one
        window ahead of the device, and host-side request dynamism
        rides the dispatch instead of breaking it. Admissions land at
        window boundaries: page tables, COW copies and slot mirrors are
        written host-side while window N-1 is still in flight, and the
        prompt's uncached tail prefills INSIDE window N as a mixed
        prefill+decode program (``_engine_mixed_window``). Deadline
        expiry and cancels land as per-dispatch lifecycle masks
        (``_merge_lifecycle``): the slot goes inactive on device, its
        in-flight tokens ride the terminal result, and its pages free
        at the boundary. Only a speculative verify / re-probe still
        drains the window and leaves the path (counted in the
        ``window_breaks_*`` counters); queued-deadline expiry and
        overload shedding are host-only and never touch it."""
        with self.tel.phase("serve/step", self._tb + ENGINE_TRACK,
                            step=self.n_steps,
                            queue_depth=self.scheduler.depth,
                            n_active=int(self._active.sum())):
            return self._step()

    def _step(self) -> List[RequestResult]:
        finished: List[RequestResult] = self._pending
        self._pending = []
        now = self.clock()
        t_wall = time.perf_counter()

        with self.tel.phase("serve/expire_shed", self._tb + ENGINE_TRACK):
            for req, t_submit, reason in self.scheduler.drain_expired(now):
                finished.append(self._finish_unstarted(req, t_submit,
                                                       reason, now))
            if self._shedder is not None:
                n_shed = self._shedder.observe(self.scheduler.depth,
                                               self.ecfg.max_queue)
                if n_shed:
                    for req, t_submit in self.scheduler.shed(n_shed):
                        finished.append(self._finish_unstarted(
                            req, t_submit, FINISH_SHED, now))
                    self.metrics.inc("shed_requests", n_shed)
                    self._event(f"step {self.n_steps}: shed {n_shed} "
                                f"queued request(s) under sustained "
                                f"overload")

            # active-deadline expiry against the per-slot deadline
            # mirror precomputed at admission (one vectorized compare,
            # no dict walk). On the windowed path these become
            # lifecycle-mask kills.
            expired = [int(s) for s in
                       np.flatnonzero(self._active
                                      & (self._deadline <= now))
                       if int(s) in self._slots]

        # speculative re-probe countdown while degraded (auto-disabled
        # only: an operator pin via set_spec_active(False) must stick)
        reprobe = False
        if (self.drafter is not None and not self._spec_active
                and not self._spec_pinned
                and self._spec_health is not None
                and self._active.any()):
            reprobe = self._spec_health.tick_disabled()

        use_spec = (self.drafter is not None
                    and (self._spec_active or reprobe))
        # the continuous-window steady state: everything except a
        # speculative mode flip stays on the window path — admissions
        # become mixed dispatches, deadlines/cancels become masks
        windowed = (self._window > 1 and not use_spec
                    and (bool(self._active.any()) or bool(self._kill)
                         or bool(expired) or self._head_admissible()))

        if windowed:
            for slot in expired:
                self._kill.setdefault(self._slots[slot].req.id,
                                      FINISH_DEADLINE)
        else:
            # a speculative transition (or a blocked k=1 engine): fetch
            # the in-flight window first — its tokens commit now,
            # finished slots' pages and slots free at this boundary
            finished.extend(self._drain_pending(
                "reprobe" if reprobe else "spec" if use_spec else
                "deadline" if expired else "cancel" if self._kill else
                "admit"))
            # any slot still mid-prefill (its chunks were riding the
            # mixed windows this branch just abandoned) completes
            # host-side NOW: the verify/decode paths assume every
            # admitted slot's prompt pages are fully written
            self._flush_prefill()
            # kills deferred while windows were engaged resolve here the
            # old way (host-initiated finish; the device state rebuilds
            # from mirrors at the next upload)
            for rid, reason in list(self._kill.items()):
                slot = self.pool.slot_of(rid)
                if slot is not None and slot in self._slots:
                    finished.append(self._finish_slot(slot, reason, now))
            self._kill.clear()
            for slot in expired:
                if slot in self._slots:   # may have finished in the drain
                    finished.append(self._finish_slot(
                        slot, FINISH_DEADLINE, now))
            if reprobe:
                self.set_spec_active(True)
                self._probe_pending = True
                self.metrics.inc("spec_reprobes")
                self._event(f"step {self.n_steps}: re-probing "
                                   f"speculative decoding")
            self._admit_queue(now, finished, self._admit)

        self.metrics.gauge("queue_depth", self.scheduler.depth)
        self.metrics.gauge("slots_active", int(self._active.sum()))
        self.metrics.gauge("slot_occupancy", self.pool.occupancy)
        self.metrics.gauge("pages_in_use", self.pool.alloc.pages_in_use)

        # chaos seam: an artificially slow/stuck step (no-op without an
        # installed FaultPlan) — what the watchdog must catch
        flt = fault_fire("serve/step", index=self.n_steps)
        if flt is not None and flt.kind == "delay":
            time.sleep(flt.arg)

        ran_decode = False
        if windowed:
            with self.tel.phase("serve/decode", self._tb + ENGINE_TRACK):
                self._window_step(now, finished)
            ran_decode = True
        elif self._active.any():
            spec_now = self.drafter is not None and self._spec_active
            finished.extend(self._verify_once() if spec_now
                            else self._decode_once())
            ran_decode = True
        elif self._inflight is not None:
            # endgame: every slot finished while a window was in flight
            # — fetch it (it emits nothing) so drain() reaches idle
            finished.extend(self._drain_pending())
        if ran_decode and self._watchdog is not None:
            dur = time.perf_counter() - t_wall
            if self._watchdog.observe(dur):
                self.metrics.inc("watchdog_stalls")
                self.metrics.gauge("last_stall_s", dur)
                self._event(f"step {self.n_steps}: stall — "
                                   f"{dur * 1e3:.1f} ms step against "
                                   f"a p99-derived budget")
        return finished

    def _window_step(self, now: float, finished: List[RequestResult]
                     ) -> None:
        """One continuous-window boundary: resolve pending kills into
        this dispatch's flag array, admit the queue head(s) host-side
        (their prefill chunks ride the dispatch), launch window N,
        fetch window N-1, then finish masked-out slots — whose pages
        are safe to release while window N flies, because the kill flag
        already deactivated them on device (writes dropped, reads
        masked) before the launch."""
        k = self._window_cur
        P = self.ecfg.pool_size
        kill_arr = np.zeros((P,), bool)
        kills: List = []
        for rid, reason in self._kill.items():
            slot = self.pool.slot_of(rid)
            if slot is not None and slot in self._slots:
                kill_arr[slot] = True
                kills.append((slot, reason))
        # admissions at the boundary: host bookkeeping only (window N-1
        # is still in flight); slots freed by this boundary's kills
        # become available at the NEXT one
        self._admit_queue(now, finished, self._admit_windowed)
        adm_any = bool(self._adm_mask.any())
        live = self._active & ~kill_arr
        live_any = bool(live.any())
        if kills or adm_any:
            if live_any:
                # the masks/merge must land on device: dispatch window N
                # (kill flags + admission merge ride it), then fetch
                # N-1 so a killed slot's already-committed tokens ride
                # its terminal result
                nxt = self._launch(k, kill=kill_arr)
                finished.extend(self._drain_pending())
                for slot, reason in kills:
                    if slot in self._slots:   # may have finished in N-1
                        finished.append(self._finish_slot(
                            slot, reason, now, masked=True))
                self._inflight = nxt
            else:
                # the kills empty the engine: nothing left to dispatch,
                # so no mask ever lands — finish host-side (invalidates
                # the device state; the next upload rebuilds it)
                finished.extend(self._drain_pending())
                for slot, reason in kills:
                    if slot in self._slots:
                        finished.append(self._finish_slot(slot, reason,
                                                          now))
            self._kill.clear()
        elif live_any:
            # remaining work per slot in window steps: pending prefill
            # chunks + the decode budget. When it all fits one more
            # window, that window is the LAST (barring eos, which only
            # ends sooner): no point dispatching blind past it.
            rem = np.where(live, self._pf_left + self._budget, 0)
            last = int(rem.max()) <= k
            if self._inflight is not None and last:
                # the in-flight window already finishes everything
                finished.extend(self._drain_pending())
            elif last:
                finished.extend(self._drain_window(self._launch(k)))
            else:
                # double buffering: launch window N BEFORE fetching
                # window N-1's token block
                nxt = self._launch(k)
                finished.extend(self._drain_pending())
                self._inflight = nxt
        else:
            finished.extend(self._drain_pending())
            self._kill.clear()   # stale ids whose requests already ended

    def set_spec_active(self, active: bool) -> None:
        """Flip speculative decoding between its verify program and the
        plain decode program (both CompileGuard-budgeted — no new
        compilations at steady state). Re-enabling resyncs stateful
        drafters from host-side histories: tokens committed while
        degraded never went through the drafter's cache. A manual
        disable through this method PINS the degraded mode — the
        auto-re-probe policy leaves it alone until set_spec_active(True)
        lifts the pin (the auto-disable path flips ``_spec_active``
        directly and stays re-probeable)."""
        active = active and self.drafter is not None
        if active and not self._spec_active:
            # an in-flight decode window holds tokens the drafters'
            # resync must see — fetch it before reading histories; a
            # slot still mid-prefill completes host-side (the verify
            # path attends its whole prompt range)
            self._pending.extend(self._drain_pending("spec"))
            self._flush_prefill()
            hists = self._histories()
            for slot in self._slots:
                if self._active[slot] and hists[slot] is not None:
                    self.drafter.resync(slot, hists[slot])
        self._spec_pinned = not active and self.drafter is not None
        self._spec_active = active

    @property
    def spec_active(self) -> bool:
        return self._spec_active

    def _journal_finish(self, request_id: str, reason: str) -> None:
        if self.journal is not None:
            self.journal.record_finish(request_id, reason)

    def _event(self, msg: str) -> None:
        # a soak run with recurring degradations must not grow host
        # memory without bound (the Metrics reservoir rationale)
        self.events.append(msg)
        if len(self.events) > 256:
            del self.events[:len(self.events) - 256]

    def drain(self, max_steps: int = 1_000_000) -> List[RequestResult]:
        out: List[RequestResult] = []
        for _ in range(max_steps):
            if self.idle:
                return out
            out.extend(self.step())
        raise RuntimeError(f"engine did not drain in {max_steps} steps")

    def metrics_summary(self) -> dict:
        s = self.metrics.summary()
        s["step_latency"] = self.step_timer.summary(skip=1)
        s["n_steps"] = self.n_steps
        s["compile_counts"] = compile_counts()
        # kernel-route decision: static per engine, schema pinned in
        # tests/test_pages.py (bench serve artifacts carry it verbatim)
        s["kernel_route"] = self.kernel_route.summary()
        s["compile_guards"] = {"decode": self._decode_guard.stats(),
                               "mixed": self._mixed_guard.stats(),
                               "prefill": self._prefill_guard.stats(),
                               "verify": self._verify_guard.stats(),
                               "page_copy": self._copy_guard.stats(),
                               "page_export": self._export_guard.stats(),
                               "page_install": self._install_guard.stats()}
        # paged-pool health: bench dashboards key on this block (schema
        # pinned in tests/test_pages.py)
        s["pages"] = self.pool.stats()
        # the pool's bytes by kind of state: pages that admission
        # reserves, and what a slot keeps beside them (0 for GPT-2)
        kinds = self.pool.bytes_by_kind()
        # latent attention's pages hold latent rows, not K/V of heads
        latent = kinds["pages"] if self.cfg.kv_lora_rank else 0
        s["kv_global_bytes"] = kinds["pages"] - latent
        s["kv_latent_bytes"] = latent
        s["kv_window_bytes"] = kinds.get("window", 0)
        s["conv_state_bytes"] = kinds.get("conv", 0)
        # bytes of the compute-dtype copies made at build (0: the tree
        # came in the compute dtype and is served as it is)
        s["weight_cast_bytes"] = self._cast_bytes
        # the process's set-up record (utils.telemetry): its time to
        # ready, also as the tpu_gpt_setup_* gauges
        s["setup"] = setup_record().summary()
        for k in ("engine_s", "build_s", "trace_s", "lower_s", "compile_s"):
            self.metrics.gauge("setup_" + k, s["setup"][k])
        # dispatch amortization: the host tax per dispatch vs per token
        # (the serve-side analogue of the train bench's dispatch split)
        c = self.metrics.counters
        disp = self.metrics.hist_summary("decode_dispatch_s")
        n_disp = int(c.get("decode_dispatches", 0))
        dec_tokens = int(c.get("dispatch_tokens", 0))
        mean_ms = disp.get("mean", 0.0) * 1e3
        s["dispatch"] = {
            "window_k": self._window_cur,
            "window_k_max": self._window,
            "autotune": bool(self.ecfg.decode_window_auto),
            "autotune_increases": int(
                c.get("autotune_window_increases", 0)),
            "dispatches": n_disp,
            "mean_dispatch_ms": round(mean_ms, 4),
            "host_dispatch_ms_per_token": (
                round(mean_ms * n_disp / dec_tokens, 4)
                if dec_tokens else 0.0),
        }
        # window-break observability (continuous windows): which host
        # mutations still force the engine off the window path. Post
        # continuous-windows only the speculative reasons should move
        # on a healthy engine — admit/deadline/cancel ride the window.
        s["window_breaks"] = {
            r: int(c.get("window_breaks_" + r, 0))
            for r in ("admit", "deadline", "cancel", "spec", "reprobe")}
        c = self.metrics.counters
        s["recovery"] = {
            "watchdog_stalls": int(c.get("watchdog_stalls", 0)),
            "spec_disables": int(c.get("spec_disables", 0)),
            "spec_reprobes": int(c.get("spec_reprobes", 0)),
            "shed_requests": int(c.get("shed_requests", 0)),
            "spec_active": self._spec_active,
            "events": list(self.events[-32:]),
        }
        if self.drafter is not None:
            c = self.metrics.counters
            drafted = c.get("spec_draft_tokens", 0)
            slot_steps = c.get("slot_steps", 0)
            s["speculative"] = {
                "drafter": self.drafter.name,
                "k": self.drafter.k,
                "accept_rate": (round(c.get("spec_accepted_tokens", 0)
                                      / drafted, 4) if drafted else 0.0),
                "mean_tokens_per_step": (round(c.get("decode_tokens", 0)
                                               / slot_steps, 3)
                                         if slot_steps else 0.0),
                "draft_overhead_s":
                    self.metrics.hist_summary("draft_overhead_s"),
            }
        return s

    # ----------------------------------------------------------- internals

    def _cap(self, req: Request) -> int:
        """Decode budget for a request: decode step i runs at position
        P-1+i (the first rewrites the last prompt position), so a slot
        supports S - P + 1 new tokens before the write position would
        leave the logical buffer. A ``prefill_only`` request budgets
        exactly ONE decode token — enough to rewrite position P-1 and
        finalize the last full prompt page for registration — so the
        prefill tier reserves prompt pages only, never a decode
        budget's worth."""
        if req.prefill_only:
            return 1
        return min(req.max_new_tokens,
                   self.pool.seq_len - int(req.prompt.size) + 1)

    def _fits(self, req: Request) -> bool:
        """Admission gate beyond free slots: enough free (or LRU-
        reclaimable) pages for the request's WHOLE lifetime — prompt
        minus cached prefix plus the full decode budget, reserved
        eagerly so an admitted request can never strand mid-decode."""
        return self.pool.can_admit(req.prompt, self._cap(req))

    def _admit(self, req: Request, t_submit: float, now: float) -> None:
        P = int(req.prompt.size)
        cap = self._cap(req)
        t_admit_us = self.tel.now_us() if self.tel.enabled else 0.0
        # acquire claims the longest radix-cached prefix, reserves the
        # remaining pages, and sets pool.positions[slot] = P - 1 (which
        # self._pos aliases — the first decode rewrites the last prompt
        # index)
        adm = self.pool.acquire(req.id, req.prompt, cap)
        assert adm is not None, "scheduler admitted past pool capacity"
        slot = adm.slot
        tid = self._tb + SLOT_TRACK_BASE + slot
        if self.tel.enabled:
            # the request's span tree opens BACKDATED to its submit
            # time (viewers sort by ts, so out-of-order emission is
            # fine); the queue phase closes it out to this admission
            ts_sub = self.tel.ts_us(t_submit)
            self.tel.begin("request", tid, ts_us=ts_sub, request=req.id,
                           prompt_tokens=P, max_new_tokens=cap)
            self.tel.complete("queue", tid, ts_sub,
                              self.tel.ts_us(now) - ts_sub,
                              request=req.id)
        for src, dst in adm.cow:
            # copy-on-write split of a fully-cached prompt's frontier
            # page; program warmed at construction (budget 1)
            check_in_bounds(dst, 1, self.pool.n_pages, what="COW page")
            self.tel.instant("cow_split", tid, src=src, dst=dst,
                             request=req.id)
            self.pool.pages = self._copy_guard(self.pool.pages,
                                               jnp.int32(src),
                                               jnp.int32(dst),
                                               shardings=self._plan)
        claimed = adm.claimed
        S = self.pool.seq_len
        if claimed < P:
            chunk = self._chunk
            n_chunks = -(-(P - claimed) // chunk)
            # host-side bound for the jitted prefill (offset traced):
            # every REAL token position must sit inside the logical
            # buffer — padded tail positions are routed to scatter-drop
            # inside prefill_chunk_paged, so only [claimed, P) matters
            check_in_bounds(claimed, P - claimed, S,
                            what=f"prefill of {P}-token prompt from "
                                 f"{claimed} in {chunk}-chunks")
            padded = np.zeros((n_chunks * chunk,), np.int32)
            padded[:P - claimed] = req.prompt[claimed:]
            table_row = jnp.asarray(self.pool.tables[slot])
            cache = self.pool.cache
            with self.tel.phase("serve/prefill", self._tb + ENGINE_TRACK,
                                tokens=P - claimed, chunks=n_chunks,
                                cached_tokens=claimed):
                for c in range(n_chunks):
                    tc_us = (self.tel.now_us() if self.tel.enabled
                             else 0.0)
                    cache = self._prefill_guard(
                        self.served_params,
                        jnp.asarray(padded[None,
                                           c * chunk:(c + 1) * chunk]),
                        jnp.int32(claimed + c * chunk), jnp.int32(P),
                        table_row, np.int32(slot), cache, self.cfg,
                        shardings=self._plan)
                    if self.tel.enabled:
                        # host dispatch time (the device runs async);
                        # a jax.profiler capture of the same run shows
                        # the device-side cost under this serve/prefill
                        self.tel.complete(
                            "prefill_chunk", tid, tc_us,
                            self.tel.now_us() - tc_us, chunk=c,
                            n_chunks=n_chunks, request=req.id)
            self.pool.cache = cache
        # registration AFTER the prefill wrote the pages: a same-step
        # neighbor may claim them the moment they hit the radix
        self.pool.commit_admission(slot)
        # host mirrors changed: the next window launch re-uploads them
        # (blocked-path admission only runs with no dispatch in flight)
        self._dev_state = None
        self._admit_finalize(req, t_submit, now, slot, cap, claimed,
                             t_admit_us)

    def _admit_windowed(self, req: Request, t_submit: float, now: float
                        ) -> None:
        """Admission at a CONTINUOUS window boundary: identical host
        bookkeeping to ``_admit`` — page acquisition, COW copies, slot
        mirrors — but the prompt's uncached tail is NOT dispatched as
        separate prefill programs: its chunks are queued on the
        in-window prefill cursors and ride the next MIXED window
        dispatch, and the slot's state enters the donated device loop
        through the admission-merge mask instead of invalidating it
        (``_merge_lifecycle``). Window N-1 stays in flight throughout:
        the COW copy and the coming prefill writes consume its output
        cache, so device dispatch order sequences them after it. Radix
        registration is DEFERRED until the window that finishes the
        prefill drains (``_InFlight.pf_done``) — registering pages a
        still-flying window is writing would let a same-boundary
        neighbor attend garbage."""
        P = int(req.prompt.size)
        cap = self._cap(req)
        t_admit_us = self.tel.now_us() if self.tel.enabled else 0.0
        adm = self.pool.acquire(req.id, req.prompt, cap,
                                defer_commit=True)
        assert adm is not None, "scheduler admitted past pool capacity"
        slot = adm.slot
        tid = self._tb + SLOT_TRACK_BASE + slot
        if self.tel.enabled:
            ts_sub = self.tel.ts_us(t_submit)
            self.tel.begin("request", tid, ts_us=ts_sub, request=req.id,
                           prompt_tokens=P, max_new_tokens=cap)
            self.tel.complete("queue", tid, ts_sub,
                              self.tel.ts_us(now) - ts_sub,
                              request=req.id)
        for src, dst in adm.cow:
            check_in_bounds(dst, 1, self.pool.n_pages, what="COW page")
            self.tel.instant("cow_split", tid, src=src, dst=dst,
                             request=req.id)
            self.pool.pages = self._copy_guard(self.pool.pages,
                                               jnp.int32(src),
                                               jnp.int32(dst),
                                               shardings=self._plan)
        claimed = adm.claimed
        S = self.pool.seq_len
        if claimed < P:
            chunk = self._chunk
            n_chunks = -(-(P - claimed) // chunk)
            # host-side bound for the traced in-window prefill writes:
            # every REAL position sits inside the logical buffer;
            # padded tail positions scatter-drop past pf_limit
            check_in_bounds(claimed, P - claimed, S,
                            what=f"windowed prefill of {P}-token prompt "
                                 f"from {claimed} in {chunk}-chunks")
            padded = np.zeros((n_chunks * chunk,), np.int32)
            padded[:P - claimed] = req.prompt[claimed:]
            self._pf_tail[slot] = padded
            self._pf_left[slot] = n_chunks
            self._pf_off[slot] = claimed
            self._pf_limit[slot] = P
        else:
            # fully-cached prompt (COW split aside): nothing to write —
            # the slot decodes from its first window step, and the
            # claim registers immediately (its pages were written and
            # registered by previous owners)
            self.pool.commit_admission(slot)
        self._adm_mask[slot] = True
        self._admit_finalize(req, t_submit, now, slot, cap, claimed,
                             t_admit_us)

    def _admit_finalize(self, req: Request, t_submit: float, now: float,
                        slot: int, cap: int, claimed: int,
                        t_admit_us: float) -> None:
        """Mirror/record/telemetry bookkeeping shared by the blocked
        and windowed admission paths — ONE definition so the two can
        never drift on a per-slot field (the deadline mirror and the
        rng reset are both parity-load-bearing)."""
        P = int(req.prompt.size)
        tid = self._tb + SLOT_TRACK_BASE + slot
        if self.drafter is not None:
            # drafters keep their own (unpaged) cache and see the full
            # prompt — prefix reuse is a target-pool concern
            self.drafter.on_admit(slot, req.prompt)
        self._tok[slot] = req.prompt[-1]
        self._active[slot] = True
        self._budget[slot] = cap
        self._eos[slot] = (-1 if req.eos_token_id is None
                           else int(req.eos_token_id))
        # deadline precomputed at admission into the vectorized expiry
        # mirror (inf = none): the step loop's check is one compare
        # (req.deadline is a host float already — no conversion)
        self._deadline[slot] = (np.inf if req.deadline is None
                                else req.deadline)
        sp = req.sampling
        self._temp[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._top_p[slot] = sp.top_p
        self._greedy[slot] = sp.greedy
        self._rngs = self._rngs.at[slot].set(jax.random.PRNGKey(req.rng_seed))
        self._li = None           # eos/tables/sampling mirrors changed
        self._slots[slot] = _Active(req=req, t_submit=t_submit, t_admit=now,
                                    cap=cap,
                                    capped=cap < req.max_new_tokens)
        if self.tel.enabled:
            self.tel.complete("admit", tid, t_admit_us,
                              self.tel.now_us() - t_admit_us,
                              request=req.id, cached_tokens=claimed,
                              prefill_tokens=P - claimed)
        self.metrics.inc("requests_admitted")
        self.metrics.inc("prefill_tokens", P - claimed)
        self.metrics.inc("prefix_hit_tokens", claimed)
        self.metrics.observe("queue_wait_s", now - t_submit)

    def _admit_queue(self, now: float, finished: List[RequestResult],
                     admit_fn) -> None:
        """One-at-a-time admission off the queue head — ONE definition
        of the FIFO protocol for the blocked (``_admit``) and windowed
        (``_admit_windowed``) paths: each admission changes page
        availability, so the fits check must see fresh allocator state
        per request, and a head that does not fit BLOCKS the queue
        rather than being skipped (big requests cannot starve)."""
        while self.pool.n_free > 0:
            admitted, dropped = self.scheduler.admit(1, now,
                                                     fits=self._fits)
            for req, t_submit, reason in dropped:
                finished.append(self._finish_unstarted(req, t_submit,
                                                       reason, now))
            if not admitted:
                break
            req, t_submit = admitted[0]
            with self.tel.phase("serve/admit", self._tb + ENGINE_TRACK):
                admit_fn(req, t_submit, now)

    def _flush_prefill(self) -> None:
        """Complete any still-pending in-window prefill through the
        blocked prefill program — called whenever the engine LEAVES the
        windowed path with chunks outstanding (a speculative
        verify/re-probe transition, which only exists on drafter
        engines, whose warmup compiles ``_engine_prefill``): the
        verify/decode paths attend each admitted slot's full prompt
        range, so abandoning unwritten chunks would read never-written
        pages. The deferred radix registration commits here too — the
        writes are enqueued ahead of any later dispatch."""
        for slot in np.flatnonzero(self._pf_left > 0):
            slot = int(slot)
            chunk = self._chunk
            tail = self._pf_tail.pop(slot)
            n = int(self._pf_left[slot])
            off = int(self._pf_off[slot])
            limit = int(self._pf_limit[slot])
            table_row = jnp.asarray(self.pool.tables[slot])
            cache = self.pool.cache
            with self.tel.phase("serve/prefill", self._tb + ENGINE_TRACK,
                                tokens=limit - off, chunks=n,
                                cached_tokens=off):
                for c in range(n):
                    cache = self._prefill_guard(
                        self.served_params,
                        jnp.asarray(tail[None,
                                         c * chunk:(c + 1) * chunk]),
                        jnp.int32(off + c * chunk), jnp.int32(limit),
                        table_row, np.int32(slot), cache, self.cfg,
                        shardings=self._plan)
            self.pool.cache = cache
            self._pf_left[slot] = 0
            self._pf_off[slot] = 0
            self._pf_limit[slot] = 0
            if slot in self._slots:
                self.pool.commit_admission(slot)

    def _head_admissible(self) -> bool:
        """Whether this step could admit: a free slot AND a queued,
        unexpired head that fits the page gate. While False, a backlog
        does not break decode windows — arrivals batch at window
        boundaries (the scheduler's strict FIFO is unchanged: only the
        HEAD is consulted, exactly like the admission loop)."""
        if self.pool.n_free <= 0:
            return False
        head = self.scheduler.peek()
        return head is not None and self._fits(head[0])

    def _warm_programs(self) -> None:
        """The programs a request may need mid-traffic, run once now."""
        # warm the COW program NOW (page 0 onto itself — a value no-op):
        # the first real copy-on-write happens mid-replay, where a
        # compile would break the pinned-flat compile_counts invariant
        self.pool.pages = self._copy_guard(self.pool.pages, jnp.int32(0),
                                           jnp.int32(0),
                                           shardings=self._plan)
        # warm the disaggregated-transfer pair the same way: export page
        # 0, round-trip its blocks through host memory (matching the
        # live path's placement — uncommitted uploads — so the warm
        # program IS the steady-state program), install them back onto
        # page 0. A value no-op; the first real transfer lands
        # mid-traffic on either tier.
        blocks = {name: np.asarray(arr) for name, arr in
                  self._export_guard(self.pool.pages,
                                     jnp.int32(0)).items()}
        self.pool.pages = self._install_guard(
            self.pool.pages, jnp.int32(0),
            {name: jnp.asarray(arr) for name, arr in blocks.items()},
            shardings=self._plan)
        if self._window > 1:
            # compile every bucketed window program up front (masked
            # no-op dispatches) — admissions, lifecycle masks and
            # autotune bucket moves then always hit a warm program
            self._warm_windows()

    def _warm_windows(self) -> None:
        """Compile every bucketed window program — the pure decode
        window AND the mixed prefill+decode window at each
        ``window_buckets()`` size — with masked no-op dispatches at
        construction: all slots inactive, all masks False, so writes
        drop, emissions mask off and the step-state values pass through
        unchanged (the donated cache/rng buffers are threaded through
        and reassigned). After this, admissions, lifecycle masks and
        k-autotune bucket moves always land on a warm program; the
        request-driven replay/worker warmups merely EXERCISE the paths.
        Per-slot rng streams are reset at admission, so the decode
        windows' unconditional in-scan splits here cannot perturb any
        request's sampled stream."""
        P = self.ecfg.pool_size
        from .cache_pool import commit_default
        zi = np.zeros((P,), np.int32)
        zb = np.zeros((P,), bool)
        state = tuple(commit_default(jnp.asarray(a), sharding=self._rep)
                      for a in (zi, zi, zb, zi))
        cache, rngs = self.pool.cache, self._rngs
        eos_d, tables_d, *sample = self._launch_inputs()
        for k in self._buckets:
            out = self._decode_guard(
                self.served_params, *state, eos_d, self._z_life,
                tables_d, cache, rngs, *sample,
                self.cfg, k=k, use_pallas=self._use_pallas,
                shardings=self._plan)
            _, _, t_, p_, a_, b_, cache, rngs = out
            state = (t_, p_, a_, b_)
            out = self._mixed_guard(
                self.served_params, *state, eos_d, self._z_life,
                jnp.zeros((3, P), jnp.int32),
                jnp.zeros((k, P, self._chunk), jnp.int32),
                tables_d, cache, rngs, *sample,
                self.cfg, k=k, use_kernel=self._use_window_kernel,
                shardings=self._plan)
            _, _, t_, p_, a_, b_, cache, rngs = out
            state = (t_, p_, a_, b_)
        self.pool.cache = cache
        self._rngs = rngs
        # mirrors stay authoritative: the warm state is discarded, the
        # first real launch re-uploads (values were untouched anyway)

    def _launch_inputs(self) -> tuple:
        """Device copies of the launch-invariant per-slot inputs (eos,
        page tables, sampling params), rebuilt only when an admission
        or finish dirtied them (``self._li = None``) — at steady state
        a window dispatch re-uses them with zero device_put calls,
        which is most of the host tax the window amortizes."""
        if self._li is None:
            # COPIES of the mirrors (see _upload): the host rewrites a
            # slot's row at the next admission while a window that
            # reads these may still be in flight
            self._li = tuple(_upload(a) for a in (
                self._eos, self.pool.tables, self._temp, self._top_k,
                self._top_p, self._greedy))
        return self._li

    def _count_stochastic_rows(self, sampling: np.ndarray) -> int:
        """How many of the slots that sample in the launch being built
        are not greedy, from the host mirrors: what the program's own
        predicate (``sample_tokens_batched``: any live stochastic row)
        will find on the device. A launch where it is not 0 runs the
        draw and whichever filters those rows switched on, for every
        slot, and counts in ``sample_filter_launches``."""
        n = int((sampling & ~self._greedy).sum())
        self.metrics.inc("sample_filter_launches", int(n > 0))
        return n

    def _launch(self, k: int, kill: Optional[np.ndarray] = None
                ) -> _InFlight:
        """Dispatch one ``k``-step window WITHOUT fetching its results
        — the pure decode-window program, or the MIXED prefill+decode
        program whenever any slot still has prompt chunks to write.
        The donated device step state from the previous dispatch feeds
        straight back in (``_dev_state``); boundary lifecycle traffic —
        ``kill`` flags and the admission-merge mask — rides the
        dispatch as small traced inputs (``_merge_lifecycle``) instead
        of invalidating it. Only a host-initiated finish outside the
        mask path forces a mirror re-upload. The token block's
        device->host copy starts immediately (``copy_to_host_async``),
        so by the time ``_drain_window`` reads it the transfer has been
        overlapping device compute.

        The whole of it is the ``serve/launch`` phase, whose stats carry
        the context this dispatch attends: ``live_tokens``, the sum of
        ``pos + 1`` over the live slots from the host mirrors (no device
        read), and ``live_kv_bytes``, what those tokens hold in the pool
        across layers, and ``stochastic_rows``, the live slots that
        sample in this window (a slot that prefills all of it does
        not); on the per-layer Pallas route also how the kernel's walk
        engages (``_kv_walk_stats``)."""
        t0_us = self.tel.now_us() if self.tel.enabled else 0.0
        t_wall = time.perf_counter()
        if kill is None:
            kill = np.zeros((self.ecfg.pool_size,), bool)
        live = self._active & ~kill
        n_active = int(live.sum())
        live_tokens = int(self._pos[live].sum()) + n_active
        stochastic = self._count_stochastic_rows(live & (self._pf_left < k))
        extra = {}
        fx = self._launch_extra or {}
        if "swa_token_bytes" in fx:
            extra["swa_kv_bytes"] = int(np.minimum(
                self._pos[live] + 1, fx["window"]).sum()) \
                * fx["swa_token_bytes"]
        if "conv_slot_bytes" in fx:
            state = n_active * fx["conv_slot_bytes"] * k
            extra.update(conv_state_bytes=state,
                         short_conv_bytes=fx["conv_weight_bytes"] * k + state)
        if "expert_bytes" in fx:
            extra.update(expert_weight_bytes=fx["expert_bytes"] * k,
                         moe_rows=n_active * k)
        if "mla_token_bytes" in fx:
            extra["mla_kv_bytes"] = live_tokens * fx["mla_token_bytes"]
        if self._kv_block_pages:
            extra.update(self._kv_walk_stats(live))
        with self.tel.phase("serve/launch", self._tb + ENGINE_TRACK, k=k,
                            n_active=n_active, live_tokens=live_tokens,
                            live_kv_bytes=live_tokens
                            * self._kv_token_bytes,
                            stochastic_rows=stochastic, **extra):
            return self._dispatch(k, kill, n_active, t0_us, t_wall)

    def _kv_walk_stats(self, live: np.ndarray) -> dict:
        """How the paged kernel's walk engages in the launch being
        built, from the host mirrors: ``kv_blocks_live`` (loop iterations
        with work: the sum over the live slots of the blocks of
        ``ops.paged_pallas.block_pages`` pages that hold a position under
        the slot's, as the kernel's owned mask will have it on the
        device; one step of one pool layer), ``kv_block_passes`` (the
        passes those iterations make, a score product, an online update
        and a value product each: ``ops.paged_pallas.block_passes`` a
        block, so over ``kv_blocks_live`` it reads 1 where a block is ONE
        pass for all of a slot's heads) and ``kv_blocks_grid`` (the
        turns the kernel's grid takes there: one a slot, so live over
        grid reads blocks a turn)."""
        from ..ops.paged_pallas import live_blocks
        n_live = int(live_blocks(self._pos[live], self.pool.page_size,
                                 self._kv_block_pages).sum())
        return dict(kv_blocks_live=n_live,
                    kv_block_passes=n_live * self._kv_block_passes,
                    kv_blocks_grid=self.ecfg.pool_size)

    def _family_launch_stats(self) -> Optional[dict]:
        """What a family's state beside the pages and its experts add to
        the stats of ``serve/launch``, as bytes a unit (None: GPT-2, whose
        stats are unchanged): ``window`` and ``swa_token_bytes`` (a ring
        token over the window layers) for ``swa_kv_bytes``;
        ``conv_slot_bytes`` (a slot's state over the conv layers, read and
        written once a step) and ``conv_weight_bytes`` (the conv layers'
        projections and taps) for ``conv_state_bytes`` and
        ``short_conv_bytes``; ``expert_bytes`` (the held experts over the
        sparse layers) for ``expert_weight_bytes``: a decode step streams
        every held expert once (at 64 live rows 98% of them get a token),
        so that is per step whatever was routed; ``mla_token_bytes`` (a
        context token's latent rows over the layers: the model's values,
        not the lanes the rows are padded to) for ``mla_kv_bytes``."""
        cfg = self.cfg
        out = {}
        state = self.pool.slot_state()
        if state.get("window"):
            rings = state["window"]
            out.update(window=cfg.sliding_window, swa_token_bytes=(
                sum(a.nbytes for a in rings)
                // (rings[0].shape[1] * rings[0].shape[2])))
        if state.get("conv"):
            out.update(
                conv_slot_bytes=(sum(a.nbytes for a in state["conv"])
                                 // self.pool.n_slots),
                conv_weight_bytes=sum(
                    a.nbytes for lp in self.served_params["layers"]
                    for n, a in lp.items() if n.startswith("conv_")))
        if cfg.n_experts:
            out["expert_bytes"] = sum(
                a.nbytes for lp in self.served_params["layers"]
                for n, a in lp.items() if n.startswith("e_"))
        if cfg.kv_lora_rank:
            out["mla_token_bytes"] = (
                len(cfg.paged_layers)
                * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                * self.pool.kv_array.dtype.itemsize)
        return out or None

    def _dispatch(self, k: int, kill: np.ndarray, n_active: int,
                  t0_us: float, t_wall: float) -> _InFlight:
        """``_launch``'s body: upload what the boundary dirtied, enqueue
        the window program, start the token block's copy home."""
        P = self.ecfg.pool_size
        if self._dev_state is None:
            # host-side bound for the traced window writes: every REAL
            # write position (bounded by the per-slot budget — the
            # admission cap's pos + budget <= seq_len invariant) stays
            # inside the logical buffer
            check_in_bounds(
                np.where(self._active,
                         self._pos + np.minimum(
                             np.maximum(self._budget, 1), k) - 1, 0),
                1, self.pool.seq_len, what="decode window write")
            # committed, like every engine-owned jit input: the state
            # must enter this call exactly as it leaves the donated
            # steady-state loop (a committed output), or the jit cache
            # keys the two placements as two programs — on a mesh that
            # means replicated over every device (the constrained
            # window output's placement), not one chip
            from .cache_pool import commit_default
            state = tuple(commit_default(_upload(a),
                                         sharding=self._rep) for a in
                          (self._tok, self._pos, self._active,
                           self._budget))
        else:
            state = self._dev_state
        tok, pos, active, budget = state
        eos_d, tables_d, temp_d, top_k_d, top_p_d, greedy_d = \
            self._launch_inputs()
        # lifecycle inputs: quiet boundaries (the steady state) reuse
        # the cached all-zero pack — no device_put; a boundary with
        # kills or admissions uploads ONE (5, P) array (the admission
        # merge reads the host mirrors directly, which were written at
        # this boundary's admissions)
        adm = self._adm_mask
        if kill.any() or adm.any():
            life_np = np.zeros((5, P), np.int32)
            life_np[0] = kill
            life_np[1] = adm
            life_np[2] = self._tok
            life_np[3] = self._pos
            life_np[4] = self._budget
            life = jnp.asarray(life_np)
        else:
            life = self._z_life
        pf = np.flatnonzero((self._pf_left > 0) & ~kill)
        if pf.size:
            # mixed window: lay each still-prefilling slot's next
            # min(k, pf_left) chunks into the scan's per-step payload;
            # consumption is deterministic, so the cursors advance
            # host-side with no fetch
            chunk = self._chunk
            pf_toks = np.zeros((k, P, chunk), np.int32)
            pfc = np.zeros((3, P), np.int32)
            pfc[1] = self._pf_off
            pfc[2] = self._pf_limit
            pf_done: List = []
            for slot in pf:
                slot = int(slot)
                n = min(k, int(self._pf_left[slot]))
                pfc[0, slot] = n
                pf_toks[:n, slot, :] = \
                    self._pf_tail[slot][:n * chunk].reshape(n, chunk)
            out = self._mixed_guard(
                self.served_params, tok, pos, active, budget, eos_d, life,
                jnp.asarray(pfc), jnp.asarray(pf_toks),
                tables_d, self.pool.cache, self._rngs,
                temp_d, top_k_d, top_p_d, greedy_d, self.cfg, k=k,
                use_kernel=self._use_window_kernel,
                shardings=self._plan)
            for slot in pf:
                slot = int(slot)
                n = int(pfc[0, slot])
                self._pf_left[slot] -= n
                self._pf_off[slot] += n * chunk
                if self._pf_left[slot] <= 0:
                    self._pf_tail.pop(slot, None)
                    pf_done.append((slot, self._slots[slot].req.id))
                else:
                    self._pf_tail[slot] = self._pf_tail[slot][n * chunk:]
        else:
            pf_done = []
            out = self._decode_guard(
                self.served_params, tok, pos, active, budget, eos_d, life,
                tables_d, self.pool.cache, self._rngs,
                temp_d, top_k_d, top_p_d, greedy_d, self.cfg, k=k,
                use_pallas=self._use_pallas, shardings=self._plan)
        toks, emitted, tok, pos, active, budget, cache, rngs = out
        self.pool.cache = cache
        self._rngs = rngs
        self._dev_state = (tok, pos, active, budget)
        self._adm_mask[:] = False       # the merge landed with this launch
        for out_arr in (toks, emitted):
            copy_async = getattr(out_arr, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        # the host-side dispatch tax this PR amortizes: arg conversion +
        # trace-cache lookup + enqueue, all BEFORE any device wait (the
        # bench dispatch-split line and the k-autotuner read this)
        host_s = time.perf_counter() - t_wall
        self.metrics.inc("decode_dispatches")
        self.metrics.observe("decode_dispatch_s", host_s)
        return _InFlight(toks=toks, emitted=emitted, k=k, t0_us=t0_us,
                         t_wall=t_wall, n_active=n_active, host_s=host_s,
                         pf_done=pf_done)

    def _drain_pending(self, break_reason: str = "") -> List[RequestResult]:
        """Fetch the in-flight window, if any. A non-empty
        ``break_reason`` marks this drain as a WINDOW BREAK — the
        continuous-window path had to be abandoned for a host mutation
        — and feeds the ``window_breaks_{reason}`` counters
        (admit|deadline|cancel|spec|reprobe), the PR's before/after
        observability: post-continuous-windows only the speculative
        reasons should ever move on a healthy engine."""
        if self._inflight is None:
            return []
        if break_reason and self._window > 1:
            self.metrics.inc("window_breaks_" + break_reason)
        w, self._inflight = self._inflight, None
        return self._drain_window(w)

    def _commit_tokens(self, slot: int, st: _Active, committed: List[int],
                       now: float, t0_us: float, dur_us: float) -> None:
        """Append a dispatch's committed tokens to a slot's host record
        — ONE definition for the decode-window and speculative-verify
        drains: TTFT on the first token, one ``token`` telemetry
        instant per committed token interpolated across the dispatch
        span (indices are the request's running count — the strictly-
        increasing contract tools/trace_check.py enforces), and the
        ``_tok``/``_pos``/``_budget`` mirrors advanced."""
        tid = self._tb + SLOT_TRACK_BASE + slot
        first = not st.tokens
        base = len(st.tokens)
        st.tokens.extend(committed)
        if self.tel.enabled:
            n = len(committed)
            for j in range(n):
                self.tel.instant("token", tid,
                                 ts_us=t0_us + dur_us * (j + 1) / n,
                                 request=st.req.id, index=base + j + 1)
        if first:
            st.t_first_token = now
            self.metrics.observe("ttft_s", now - st.t_submit)
        st.t_last_token = now
        self._tok[slot] = st.tokens[-1]
        self._pos[slot] += len(committed)
        self._budget[slot] = st.cap - len(st.tokens)

    def _drain_window(self, w: _InFlight) -> List[RequestResult]:
        """Fetch one dispatched window's token block (ONE host snapshot
        per window — ``np.asarray`` on the async-copied outputs) and run
        the host bookkeeping: append tokens, advance the mirrors,
        finish slots whose budget ran out or whose eos landed. Slots
        that finished mid-window already idled on device; their pages
        and slot free HERE, at the window boundary. Two phases: the
        wait for the device is ``serve/fetch``, the bookkeeping
        ``serve/commit``."""
        with self.tel.phase("serve/fetch", self._tb + ENGINE_TRACK):
            toks = np.asarray(w.toks)
            emitted = np.asarray(w.emitted)
        if self._fam.step_counters:
            # the family's per-step counters ride the token block as
            # trailing columns: one fetch, then the block is the tokens'
            P = self.ecfg.pool_size
            for j, name in enumerate(self._fam.step_counters):
                self.metrics.inc(name, int(toks[:, P + j].sum()))
            toks = toks[:, :P]
        now = self.clock()
        self.n_steps += 1
        self.step_timer.laps.append(time.perf_counter() - w.t_wall)
        n_tok = int(emitted.sum())
        with self.tel.phase("serve/commit", self._tb + ENGINE_TRACK,
                            step=self.n_steps, k=w.k, tokens=n_tok):
            return self._commit_window(w, toks, emitted, now, n_tok)

    def _commit_window(self, w: _InFlight, toks: np.ndarray,
                       emitted: np.ndarray, now: float, n_tok: int
                       ) -> List[RequestResult]:
        """``_drain_window``'s host half, after the fetch."""
        if self._sanitize:
            # GRAFT_SANITIZE: sampled ids must be valid vocab entries
            # (an out-of-range id would clamp in the next embedding
            # gather and silently decode garbage)
            live = toks[emitted]
            bad = (live < 0) | (live >= self.cfg.vocab_size)
            if bad.any():
                raise FloatingPointError(
                    f"sanitize: decode produced out-of-range token(s) "
                    f"{live[bad][:4].tolist()} (vocab "
                    f"{self.cfg.vocab_size})")
        self.metrics.observe("batch_fill_ratio",
                             w.n_active / self.ecfg.pool_size)
        self.metrics.inc("decode_steps")
        self.metrics.inc("decode_tokens", n_tok)
        # plain-decode tokens only (decode_tokens also counts verify
        # commits): the denominator of host_dispatch_ms_per_token —
        # dispatch time is only accumulated on this path, so a
        # spec-enabled run must not dilute the ratio
        self.metrics.inc("dispatch_tokens", n_tok)
        tel_on = self.tel.enabled
        # span end at ts_us(now) — the same clock reading the finish
        # path stamps on a request's E event, so a slot's last decode
        # span never spills past its request envelope
        dur_us = (self.tel.ts_us(now) - w.t0_us) if tel_on else 0.0
        # windowed-admission radix registration: a slot whose in-window
        # prefill COMPLETED in this dispatch has verifiably written its
        # prompt pages — they become claimable from this boundary on
        # (never earlier: a same-window neighbor sharing a page still
        # being written would attend garbage). The id guards against
        # the slot having been killed and recycled since the launch.
        for slot, rid in w.pf_done:
            st = self._slots.get(slot)
            if st is not None and st.req.id == rid:
                self.pool.commit_admission(slot)
        finished: List[RequestResult] = []
        for slot in list(self._slots):
            # emitted[:, slot] is a RUN mask: False while the slot
            # prefills its admission chunks (mixed windows), True from
            # its first decode step, False again once it deactivates —
            # commit by mask, not by count
            mask = emitted[:, slot]
            n_emit = int(mask.sum())
            if n_emit == 0:
                continue
            st = self._slots[slot]
            if tel_on:
                self.tel.complete("decode",
                                  self._tb + SLOT_TRACK_BASE + slot,
                                  w.t0_us, dur_us,
                                  step=self.n_steps, request=st.req.id,
                                  k=w.k, tokens=n_emit)
            self._commit_tokens(slot, st,
                                [int(t) for t in toks[mask, slot]],
                                now, w.t0_us, dur_us)
            eos = int(self._eos[slot])
            if eos >= 0 and st.tokens[-1] == eos:
                # the device deactivated the slot the step its eos
                # landed (emission stops right there — the eos token is
                # the stream's last)
                finished.append(self._finish_slot(
                    slot, FINISH_EOS, now, device_stopped=True))
            elif self._budget[slot] <= 0:
                reason = (FINISH_LENGTH_CAP if st.capped
                          else FINISH_MAX_TOKENS)
                finished.append(self._finish_slot(
                    slot, reason, now, device_stopped=True))
        # deferred radix registration: the full prompt page holding
        # position P-1 becomes shareable once the frontier passed it
        self.pool.flush_pending()
        # k-autotune: accumulate this window's host-vs-device split and
        # let the bounded additive-increase policy climb the buckets
        if w.k > 1:
            self._at_host += w.host_s
            self._at_wall += self.step_timer.laps[-1]
            self._at_n += 1
            self._maybe_autotune()
        return finished

    def _maybe_autotune(self) -> None:
        """Bounded additive-increase window sizing from the live
        dispatch split: every ``WINDOW_AUTOTUNE_INTERVAL`` windows,
        when the host dispatch tax is still more than
        ``WINDOW_AUTOTUNE_HOST_FRAC`` of window wall time, move ONE
        bucket up (never down, never past ``decode_window``). Every
        bucket's programs compiled at construction, so a move is a
        warm-cache dispatch-size change — zero recompiles by design."""
        if (not self.ecfg.decode_window_auto
                or self._wk >= len(self._buckets) - 1
                or self._at_n < WINDOW_AUTOTUNE_INTERVAL):
            return
        host_frac = self._at_host / max(self._at_wall, 1e-9)
        if host_frac > WINDOW_AUTOTUNE_HOST_FRAC:
            self._wk += 1
            self._window_cur = self._buckets[self._wk]
            self.metrics.inc("autotune_window_increases")
            self.metrics.gauge("decode_window_k", self._window_cur)
            self._event(
                f"step {self.n_steps}: autotune k -> {self._window_cur} "
                f"(host dispatch {host_frac:.1%} of window wall over "
                f"{self._at_n} windows)")
        self._at_host = self._at_wall = 0.0
        self._at_n = 0

    def _decode_once(self) -> List[RequestResult]:
        """Blocked k=1 decode: dispatch one step and immediately fetch
        it — the fallback around host-side state mutations (admission,
        deadline, cancel, speculative transitions)."""
        with self.tel.phase("serve/decode", self._tb + ENGINE_TRACK):
            return self._drain_window(self._launch(1))

    def _histories(self) -> List[Optional[np.ndarray]]:
        """Per-slot prompt+generated token history — pure host data (the
        engine appends every committed token), so drafters never pay a
        device sync for it."""
        out: List[Optional[np.ndarray]] = [None] * self.ecfg.pool_size
        for slot, st in self._slots.items():
            # fromiter, not asarray: tokens is a host list of ints — no
            # device round-trip here, and the conversion can't be
            # mistaken (by reader or linter) for one
            out[slot] = np.concatenate(
                [st.req.prompt,
                 np.fromiter(st.tokens, np.int32, len(st.tokens))])
        return out

    def _verify_once(self) -> List[RequestResult]:
        """One speculative step: host-side draft -> ONE jitted verify
        over all slots -> commit 1..k+1 tokens per slot. The drafter's
        proposals are clamped per slot by cache room (the window's last
        REAL write position must stay inside the slot buffer) and by
        the remaining token budget, both host-side — the device program
        only ever sees traced (n_slots,)-sized inputs."""
        k = self.drafter.k
        S = self.pool.seq_len
        P = self.ecfg.pool_size
        # verify works off the host mirrors and advances them below:
        # any device-resident window state is stale after this step
        self._dev_state = None
        ctx = DraftContext(
            tok=self._tok, pos=self._pos, active=self._active,
            histories=(self._histories() if self.drafter.needs_history
                       else None))
        draft_toks, draft_len, dt = timed_draft(
            self.drafter, ctx, self.cfg.vocab_size, tel=self.tel,
            track=self._tb + ENGINE_TRACK)
        self.metrics.observe("draft_overhead_s", dt)
        t0_us = self.tel.now_us() if self.tel.enabled else 0.0
        m = np.zeros((P,), np.int32)
        for slot, st in self._slots.items():
            if not self._active[slot]:
                continue
            room = S - 1 - int(self._pos[slot])
            budget = st.cap - len(st.tokens) - 1
            m[slot] = max(0, min(int(draft_len[slot]), k, room, budget))
        window = np.zeros((P, k + 1), np.int32)
        window[:, 0] = self._tok
        window[:, 1:] = draft_toks
        # the host-side bound the traced verify writes rely on: every
        # ACTIVE slot's real window positions (j <= m) stay inside the
        # slot buffer; padding positions route to an explicit
        # scatter-drop (GL006). Scoped to active slots: a released
        # slot's stale frontier can legitimately sit at S (a request
        # that finished by filling its buffer), and the verify program
        # runs those slots at position 0 anyway.
        check_in_bounds(np.where(self._active, self._pos + m, 0), 1, S,
                        what="speculative verify window")
        with self.tel.phase("serve/verify", self._tb + ENGINE_TRACK,
                            k=k, n_active=int(self._active.sum()),
                            drafted=int(m.sum()),
                            stochastic_rows=self._count_stochastic_rows(
                                self._active)):
            self.step_timer.start()
            n_acc, out, cache, rngs = self._verify_guard(
                self.served_params, jnp.asarray(window),
                jnp.asarray(self._pos),
                jnp.asarray(m), jnp.asarray(self._active),
                jnp.asarray(self.pool.tables), self.pool.cache,
                self._rngs, jnp.asarray(self._temp),
                jnp.asarray(self._top_k), jnp.asarray(self._top_p),
                jnp.asarray(self._greedy), self.cfg,
                use_kernel=self._use_window_kernel,
                shardings=self._plan)
            self.step_timer.lap(n_acc)
        self.pool.cache = cache
        self._rngs = rngs
        # ONE host snapshot per verify step for every slot's outcome
        # (np.asarray, not jax.device_get: the engine's step loop is
        # GL004-clean — syncs happen once per dispatch, never per token)
        n_acc_h = np.asarray(n_acc)
        out_h = np.asarray(out)
        if self._sanitize:
            bad = (out_h < 0) | (out_h >= self.cfg.vocab_size)
            if bad.any():
                raise FloatingPointError(
                    f"sanitize: verify produced out-of-range token(s) "
                    f"{out_h[bad][:4].tolist()} (vocab "
                    f"{self.cfg.vocab_size})")
        now = self.clock()
        self.n_steps += 1
        n_active = int(self._active.sum())
        drafted = int(m.sum())
        accepted = int(n_acc_h.sum())
        emitted = accepted + n_active          # +1 correction/bonus each
        self.metrics.observe("batch_fill_ratio", n_active / P)
        self.metrics.inc("decode_steps")
        self.metrics.inc("decode_tokens", emitted)
        self.metrics.inc("slot_steps", n_active)
        self.metrics.inc("spec_draft_tokens", drafted)
        self.metrics.inc("spec_accepted_tokens", accepted)
        if drafted:
            self.metrics.observe("accept_rate", accepted / drafted)
        self.metrics.observe("tokens_per_slot_step", emitted / n_active)
        tel_on = self.tel.enabled
        dur_us = (self.tel.ts_us(now) - t0_us) if tel_on else 0.0
        if self._spec_health is not None:
            if self._spec_health.observe(drafted, accepted):
                # the drafter is a pure tax at this accept rate: fall
                # back to plain decode (same shapes, already-budgeted
                # program) and re-probe later with backoff
                self._spec_active = False
                self._probe_pending = False
                self._spec_health.on_disable()
                self.metrics.inc("spec_disables")
                self._event(
                    f"step {self.n_steps}: speculative decoding disabled "
                    f"(windowed accept rate below "
                    f"{self.rcfg.spec_disable_threshold})")
            elif (self._probe_pending
                  and len(self._spec_health.window)
                  >= self.rcfg.spec_window):
                self._probe_pending = False
                self._spec_health.on_reenable()
                self._event(f"step {self.n_steps}: speculative "
                                   f"re-probe healthy; backoff reset")
        finished: List[RequestResult] = []
        with self.tel.phase("serve/commit", self._tb + ENGINE_TRACK,
                            step=self.n_steps, tokens=emitted,
                            accepted=accepted):
            for slot in list(self._slots):
                if not self._active[slot]:
                    continue
                st = self._slots[slot]
                n_emit = int(n_acc_h[slot]) + 1
                committed = [int(t) for t in out_h[slot, :n_emit]]
                eos = int(self._eos[slot])
                if eos >= 0 and eos in committed:
                    # a drafted/accepted eos ends the stream there — drop
                    # whatever the verify window committed past it
                    n_emit = committed.index(eos) + 1
                    committed = committed[:n_emit]
                if tel_on:
                    self.tel.complete("verify",
                                      self._tb + SLOT_TRACK_BASE + slot,
                                      t0_us, dur_us, step=self.n_steps,
                                      request=st.req.id, drafted=int(m[slot]),
                                      committed=n_emit)
                self._commit_tokens(slot, st, committed, now, t0_us, dur_us)
                if eos >= 0 and st.tokens[-1] == eos:
                    finished.append(self._finish_slot(slot, FINISH_EOS, now))
                elif len(st.tokens) >= st.cap:
                    reason = (FINISH_LENGTH_CAP if st.capped
                              else FINISH_MAX_TOKENS)
                    finished.append(self._finish_slot(slot, reason, now))
            self.pool.flush_pending()
        return finished

    def _finish_slot(self, slot: int, reason: str, now: float,
                     migrated: bool = False,
                     device_stopped: bool = False,
                     masked: bool = False) -> RequestResult:
        st = self._slots.pop(slot)
        if st.req.prefill_only and reason in (
                FINISH_MAX_TOKENS, FINISH_LENGTH_CAP, FINISH_EOS):
            # disaggregated prefill completed: the prompt's full pages
            # are final (the 1-token budget rewrote position P-1) and
            # registered for export; the envelope closes migrated — the
            # decode tier's segment is the terminal one. Deadline /
            # cancel / shed outcomes keep their reason: those ARE
            # terminal for the request.
            reason = FINISH_PREFILLED
            migrated = True
        self._active[slot] = False
        self._deadline[slot] = np.inf
        self._adm_mask[slot] = False
        self._li = None           # release zeroes the slot's table row
        self._pf_left[slot] = 0
        self._pf_off[slot] = 0
        self._pf_limit[slot] = 0
        self._pf_tail.pop(slot, None)
        if not (device_stopped or masked):
            # a host-initiated finish outside the mask path (a migrated
            # cancel, or any finish on a blocked engine): the device-
            # resident step state still believes the slot is live —
            # rebuild from the mirrors at the next launch. Budget/eos
            # finishes flipped the slot off ON DEVICE, and masked
            # kills landed through the kill flags of a dispatch that
            # has already launched, so both leave the state donatable.
            self._dev_state = None
        if self.tel.enabled:
            extra = {"migrated": True} if migrated else {}
            self.tel.end("request", self._tb + SLOT_TRACK_BASE + slot,
                         ts_us=self.tel.ts_us(now), request=st.req.id,
                         reason=reason, n_tokens=len(st.tokens), **extra)
        self.pool.release(slot)
        if self.drafter is not None:
            self.drafter.on_release(slot)
        n = len(st.tokens)
        decode_tps = 0.0
        if n > 1 and st.t_last_token > st.t_first_token:
            decode_tps = (n - 1) / (st.t_last_token - st.t_first_token)
        res = RequestResult(
            id=st.req.id, tokens=st.tokens, finish_reason=reason,
            queue_wait_s=st.t_admit - st.t_submit,
            ttft_s=(st.t_first_token - st.t_submit) if n else 0.0,
            decode_tokens_per_s=decode_tps, total_s=now - st.t_submit)
        self.metrics.inc(f"finished_{reason}")
        self._journal_finish(st.req.id, reason)
        if decode_tps:
            self.metrics.observe("decode_tokens_per_s", decode_tps)
        return res

    def _finish_unstarted(self, req: Request, t_submit: float, reason: str,
                          now: float) -> RequestResult:
        # never admitted -> no slot track and no open envelope; one
        # instant marks the terminal outcome on the engine timeline
        self.tel.instant("request_unstarted", self._tb + ENGINE_TRACK,
                         ts_us=(self.tel.ts_us(now) if self.tel.enabled
                                else None),
                         request=req.id, reason=reason)
        self.metrics.inc(f"finished_{reason}")
        self._journal_finish(req.id, reason)
        return RequestResult(id=req.id, tokens=[], finish_reason=reason,
                             queue_wait_s=now - t_submit,
                             total_s=now - t_submit)
