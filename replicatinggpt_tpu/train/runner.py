"""End-to-end training runner: the framework's L5/L6 (SURVEY.md §1).

Drives the full reference pipeline — corpus → tokenize → split → train loop
with periodic train/val eval → sample → checkpoint (GPT1.py:215-241) — on
top of the jitted steps, with optional mesh sharding, async device prefetch,
structured logging, and resumable checkpoints.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..config import Config
from ..data.dataset import TokenDataset, load_corpus
from ..data.loader import make_batcher, prefetch
from ..faults.inject import (apply_loss_fault, apply_train_state_fault,
                             fire as fault_fire)
from ..faults.supervise import (LossTracker, NonFiniteLossError,
                                SupervisionConfig)
from ..models.gpt import param_count
from ..tokenizers import get_tokenizer
from ..utils.logging import StepLogger
from ..utils.sanitize import (CompileGuard, check_finite, sanitize_enabled,
                              sanitized)
from ..utils.telemetry import NULL, setup_phase
from .state import TrainState, create_train_state
from .steps import estimate_loss, make_eval_step, make_train_step


@dataclass
class TrainResult:
    state: TrainState
    history: list          # [(step, train_loss, val_loss)]
    final_eval: Dict[str, float]
    tokenizer: Any
    tokens_per_sec_per_chip: float


def _make_lr_reader(tcfg):
    """step -> learning rate for the log line, or None when the schedule
    is a bare constant with no warmup (the reference's fixed-lr loop,
    GPT1.py:218 — an lr column there would be noise). Any real schedule
    (cosine, or constant with warmup) logs its current value. Built once
    per run: the schedule closure is reconstructed here, not per log
    boundary."""
    from .state import lr_schedule_fn
    sched = lr_schedule_fn(tcfg)
    if not callable(sched):
        return lambda step: None
    return lambda step: float(sched(step))


def _resolve_vocab(cfg: Config, tokenizer) -> Config:
    """Make model vocab consistent with the tokenizer (fixes SURVEY.md
    §8-B1/B5, where reference vocab/tokenizer mismatches crashed training).
    Keeps a configured vocab that is >= tokenizer vocab (padded vocabs like
    50304 are MXU-friendlier than 50257)."""
    v = tokenizer.vocab_size
    if cfg.model.vocab_size < v:
        import dataclasses as dc
        cfg = cfg.replace(model=dc.replace(cfg.model, vocab_size=v))
    return cfg


def train(cfg: Config, *, mesh=None, logger: Optional[StepLogger] = None,
          checkpoint_manager=None, resume: bool = False,
          profile_dir: Optional[str] = None,
          profile_start: int = 10, profile_steps: int = 5,
          stop_event=None,
          supervision: Optional[SupervisionConfig] = None,
          skip_data_steps: int = 0, telemetry=None) -> TrainResult:
    """``stop_event`` (a ``threading.Event``-like object) requests a
    graceful stop: the loop finishes the in-flight dispatch, saves a
    checkpoint (when a manager is present), and returns normally — the
    preemption story for TPU VMs, where SIGTERM precedes eviction (the
    CLI wires this to SIGTERM/SIGINT; the reference loses the entire run,
    SURVEY.md §5 failure-detection row).

    ``supervision`` (a :class:`~replicatinggpt_tpu.faults.supervise.
    SupervisionConfig`) turns on per-dispatch loss checks: a non-finite
    or spiking loss raises a typed error that
    ``faults.supervise.supervised_train`` converts into a rollback to
    the last verified checkpoint — each check is one host sync, the
    price of detection latency. ``skip_data_steps`` (supervisor-driven)
    advances the data cursor that many optimizer steps after restore,
    stepping past a data window that keeps blowing the loss up.

    The loop marks its host phases through ``tel.phase``:
    ``train/data`` (the next batch off the prefetch queue),
    ``train/dispatch`` (enqueueing the step; the device runs async),
    ``train/fetch_loss`` (the one sync per log boundary),
    ``train/eval`` and ``train/checkpoint``. Each is a
    ``jax.profiler.TraceAnnotation``, so a ``profile_dir`` capture
    shows them on the device's clock with no recorder attached;
    ``telemetry`` (utils.telemetry.Telemetry) also keeps them as spans,
    exportable to Perfetto. None means the NULL recorder. Set-up is in
    the process's set-up record (``utils.telemetry.setup_phase``):
    ``setup/train_state`` around the state's init and placement, and the
    step's first call a build of its ``CompileGuard``."""
    logger = logger or StepLogger()
    tel = telemetry or NULL
    text = load_corpus(cfg.dataset)
    tokenizer = get_tokenizer(cfg.tokenizer, corpus_text=text,
                              cache_dir=os.path.dirname(cfg.dataset) or ".")
    cfg = _resolve_vocab(cfg, tokenizer)
    mcfg, tcfg = cfg.model, cfg.train

    ds = TokenDataset.from_text(text, tokenizer, tcfg.val_fraction)
    logger.log(f"dataset: {len(ds.train):,} train / {len(ds.val):,} val "
               f"tokens, vocab {tokenizer.vocab_size}")

    # Multi-host: each process assembles only its slice of the global batch
    # (rows land in the global array via make_array_from_process_local_data
    # in the prefetch producer). Single-process: local == global, seeds
    # untouched so the reference-seeded run is bit-stable.
    n_proc = jax.process_count()
    seed = tcfg.seed
    proc = 0
    if n_proc > 1:
        from ..parallel.distributed import (is_coordinator,
                                            local_batch_slice,
                                            per_process_seed)
        sl = local_batch_slice(tcfg.batch_size)
        local_bs = sl.stop - sl.start
        seed = per_process_seed(tcfg.seed)
        proc = jax.process_index()
        # the batch's 'data' dim must split along process boundaries for
        # make_array_from_process_local_data to assemble per-host rows
        assert cfg.mesh.data % n_proc == 0, (
            f"multi-host runs need the 'data' mesh axis ({cfg.mesh.data}) "
            f"to span the {n_proc} processes")
        logger.quiet = not is_coordinator()
    else:
        local_bs = tcfg.batch_size

    train_batcher = make_batcher(tcfg.sampling, ds.train, local_bs,
                                 mcfg.block_size, seed=seed,
                                 shard=(proc, n_proc))
    eval_batchers = {
        "train": make_batcher("random", ds.train, local_bs,
                              mcfg.block_size, seed=seed + 1),
        "val": make_batcher("random", ds.val, local_bs,
                            mcfg.block_size, seed=seed + 2),
    }

    rng = jax.random.PRNGKey(tcfg.seed)
    batch_sharding = None
    n_chips = 1
    # the per-seed init program traces and compiles here
    with setup_phase("setup/train_state"):
        if mesh is not None:
            from ..parallel.mesh import (make_batch_sharding,
                                         shard_train_state)
            batch_sharding = make_batch_sharding(mesh)
            n_chips = mesh.size
            state = shard_train_state(
                lambda: create_train_state(rng, mcfg, tcfg), mesh,
                cfg.mesh)
        else:
            # commit the fresh state to an explicit device: jit keys on
            # placement, and an uncommitted initial state whose successor
            # comes back committed can split the cache into a throwaway
            # first program (the serve engine's commit_default rationale
            # — and the train CompileGuard below would flag it as a
            # recompile)
            state = jax.device_put(
                create_train_state(rng, mcfg, tcfg),
                jax.config.jax_default_device or jax.local_devices()[0])
    logger.log(f"model: {param_count(state.params):,} params "
               f"({mcfg.n_layer}L/{mcfg.n_head}H/{mcfg.n_embd}C, "
               f"dtype={mcfg.dtype})")

    attention_fn = blocks_fn = None
    if mesh is not None:
        from ..parallel import select_attention_fn, select_blocks_fn
        blocks_fn = select_blocks_fn(mcfg, cfg.mesh, mesh)
        if blocks_fn is not None:
            logger.log(f"pipeline parallelism: {cfg.mesh.pipe} stages, "
                       f"{cfg.mesh.microbatches or 2 * cfg.mesh.pipe} "
                       f"microbatches")
        else:
            attention_fn = select_attention_fn(mcfg, cfg.mesh, mesh)
            if attention_fn is not None:
                # impl_name may differ from the configured impl ('auto'
                # or explicit 'flash' route to ring/ulysses on a seq
                # mesh; DP/FSDP/TP meshes get the shard_map wrapper)
                resolved = getattr(attention_fn, "impl_name",
                                   mcfg.attention_impl)
                if cfg.mesh.seq > 1:
                    logger.log(f"sequence parallelism: seq axis "
                               f"{cfg.mesh.seq}, impl {resolved!r} "
                               f"(configured {mcfg.attention_impl!r})")
                else:
                    axes = [a for a, n in (("data", cfg.mesh.data),
                                           ("model", cfg.mesh.model))
                            if n > 1] or ["data"]
                    on_tpu = jax.default_backend() == "tpu"
                    logger.log(f"mesh attention: {resolved!r} shard_map "
                               f"wrapper over {tuple(axes)}; local core "
                               + ("Pallas flash (SDPA/einsum off the "
                                  "kernel envelope)" if on_tpu
                                  else "SDPA/einsum (non-TPU backend)"))
    if (mesh is not None
            and mcfg.attention_impl in ("auto", "ring", "ulysses")
            and attention_fn is None and blocks_fn is None):
        # No shard_map wrapper claimed the attention ('auto' off-TPU, at
        # sub-crossover T, or with heads indivisible by the 'model'
        # axis) — pin the local core to einsum so 'auto' can never
        # resolve to a bare pallas_call inside the sharded jit program
        # (the kernel has no GSPMD partitioning rule). Explicit 'flash'
        # never reaches here: on an active mesh select_attention_fn
        # always returns a wrapper for it (shard_map or seq-parallel).
        import dataclasses as dc
        prev_impl = mcfg.attention_impl
        mcfg = dc.replace(mcfg, attention_impl="einsum")
        logger.log(f"attention_impl {prev_impl!r} -> 'einsum': mesh run "
                   "where the shard_map flash wrapper does not apply")
    # steady-state contract, same as the serve engine's: ONE compiled
    # program per dispatch shape; a silent mid-run recompile (shape /
    # weak-type / placement drift) raises RecompileError naming the
    # step instead of quietly halving throughput
    train_step = CompileGuard(
        make_train_step(mcfg, tcfg, attention_fn=attention_fn,
                        blocks_fn=blocks_fn), "train/step")
    super_sharding = None
    superbatch_put = None
    if mesh is not None:
        from ..parallel.distributed import global_batch
        from ..parallel.mesh import make_superbatch_sharding
        super_sharding = make_superbatch_sharding(mesh)
        superbatch_put = (lambda a: global_batch(a, super_sharding,
                                                 batch_axis=1))
    # the whole eval pass rides one stacked dispatch per split; sharded runs
    # keep the batch sharding via the P(None,'data','seq') superbatch layout
    from .steps import make_eval_scan
    eval_scan = make_eval_scan(mcfg, attention_fn=attention_fn,
                               blocks_fn=blocks_fn)
    train_scan = None
    scan_k = 1
    if tcfg.steps_per_dispatch > 1:
        # Chunks never cross an eval/checkpoint boundary, so a dispatch
        # larger than those cadences could never run — clamp it. (Log
        # cadence does NOT clamp: log lines inside a chunk are emitted
        # from the stacked per-step losses after it completes.)
        scan_k = tcfg.steps_per_dispatch
        for interval in (tcfg.eval_interval, tcfg.checkpoint_every):
            if interval:
                scan_k = min(scan_k, interval)
        if scan_k != tcfg.steps_per_dispatch:
            logger.log(f"steps_per_dispatch clamped "
                       f"{tcfg.steps_per_dispatch} -> {scan_k} to fit the "
                       f"eval/checkpoint cadence")
        if scan_k > 1:
            from .steps import make_train_scan
            train_scan = CompileGuard(
                make_train_scan(mcfg, tcfg, scan_k,
                                attention_fn=attention_fn,
                                blocks_fn=blocks_fn), "train/scan")
        else:
            scan_k = 1
    eval_step = make_eval_step(mcfg, attention_fn=attention_fn,
                               blocks_fn=blocks_fn)
    if batch_sharding is not None:
        from ..parallel.distributed import global_batch
        dput = (lambda a: global_batch(a, batch_sharding))
    else:
        dput = jax.device_put

    start_step = 0
    if checkpoint_manager is not None and resume:
        # Random-sampling batcher state is a host-local RNG; restoring the
        # (single, primary-host) saved copy onto every host would collapse
        # the per-process decorrelation. The sequential cursor is global
        # state and restores safely on any host count.
        restore_batcher = (train_batcher
                           if (n_proc == 1 or tcfg.sampling == "sequential")
                           else None)
        if restore_batcher is None:
            logger.log("multi-host resume: random-batcher RNG state not "
                       "restored; streams re-seeded per process")
        # restore straight into the mesh layout: every leaf of the live
        # state already carries its NamedSharding (shard_train_state), so
        # orbax lays each array out shard-by-shard — an FSDP-sized model
        # never materializes replicated (which would blow HBM)
        restore_shardings = None
        if mesh is not None:
            restore_shardings = jax.tree_util.tree_map(
                lambda x: x.sharding, state)
        restored = checkpoint_manager.restore_latest(
            state, restore_batcher, shardings=restore_shardings)
        if restored is not None:
            state = restored
            start_step = int(jax.device_get(state.step))
            logger.log(f"resumed from step {start_step}")

    history = []
    accum = max(tcfg.grad_accum_steps, 1)
    if accum > 1:
        logger.log(f"gradient accumulation: {accum} x {tcfg.batch_size} "
                   f"rows/optimizer step "
                   f"(effective batch {accum * tcfg.batch_size})")
    tokens_per_batch = tcfg.batch_size * mcfg.block_size * accum
    # ship tokens in the smallest dtype covering the vocab (2-4x less H2D
    # traffic); the jitted steps widen to int32 on device (steps.loss_fn)
    wire = (np.uint8 if mcfg.vocab_size <= 0xff
            else np.uint16 if mcfg.vocab_size <= 0xffff else np.int32)
    narrow = ((x.astype(wire), y.astype(wire))
              for x, y in iter(train_batcher))
    if skip_data_steps:
        # supervisor-directed recovery: the same data window blew the
        # loss up twice — draw and discard whole optimizer steps so the
        # resumed run trains past it (the cursor snapshot feed() saves
        # reflects the advanced position)
        for _ in range(skip_data_steps * accum):
            next(narrow)
        logger.log(f"supervisor: data cursor advanced {skip_data_steps} "
                   f"optimizer step(s) past the offending window")

    def chunk_at(i: int) -> int:
        """Steps the dispatch issued at iteration ``i`` advances: scan_k,
        or 1 when an eval/checkpoint/max_iters boundary is closer. Pure in
        ``i``, so the feed producer below and the consuming loop walk the
        same schedule independently."""
        if train_scan is None:
            return 1
        room = tcfg.max_iters - i
        for interval in (tcfg.eval_interval, tcfg.checkpoint_every):
            if interval:
                room = min(room, interval - i % interval)
        return scan_k if room >= scan_k else 1

    def feed():
        # host-side assembly of exactly what each dispatch consumes: a
        # (B, T) batch, or a host-stacked (K, B, T) superbatch for scan
        # dispatches (prefetch shards 3-d items with P(None,'data','seq'),
        # so mesh runs keep their batch sharding through the scan).
        # Each item carries the batcher-state snapshot taken right after
        # its batches were drawn: the prefetch producer runs ahead of the
        # consumed step, so a mid-run checkpoint must save the cursor
        # as-of-consumption, not the live (raced-ahead) batcher state.
        def draw_step():
            # one optimizer step's batch: (B, T), or stacked (accum, B, T)
            # microbatches under gradient accumulation
            if accum == 1:
                return next(narrow)
            xs, ys = zip(*(next(narrow) for _ in range(accum)))
            return np.stack(xs), np.stack(ys)

        i = start_step
        while i < tcfg.max_iters:
            c = chunk_at(i)
            if c > 1:
                xs, ys = zip(*(draw_step() for _ in range(c)))
                item = (np.stack(xs), np.stack(ys))
            else:
                item = draw_step()
            yield (*item, train_batcher.state())
            i += c

    class _ConsumedCursor:
        """Batcher-shaped view holding the snapshot matching the consumed
        step — what checkpoints must persist (see feed())."""

        def __init__(self, snap):
            self.snap = snap

        def state(self):
            return self.snap

    cursor = _ConsumedCursor(train_batcher.state())
    batches_raw = prefetch(feed(), sharding=batch_sharding)

    def batches_iter():
        for *batch, snap in batches_raw:
            cursor.snap = snap
            yield tuple(batch)

    batches = batches_iter()
    import time

    from ..utils.profiling import trace_window
    if profile_dir and start_step + profile_start >= tcfg.max_iters:
        # clamp so a short/resumed run still produces the promised trace
        profile_start = max(tcfg.max_iters - start_step - profile_steps, 0)
    profiler = trace_window(profile_dir, start=start_step + profile_start,
                            n_steps=profile_steps)
    if profile_dir:
        logger.log(f"profiling steps {start_step + profile_start}.."
                   f"{start_step + profile_start + profile_steps} "
                   f"-> {profile_dir}")
    t0 = time.perf_counter()
    tokens_seen = 0
    logger.reset_timer()
    def _stop_requested(it: int) -> bool:
        if stop_event is None:
            return False
        if n_proc == 1:
            return stop_event.is_set()
        # Multi-host: signal delivery is skewed across hosts, and acting on
        # a process-local flag would have hosts leave the loop at different
        # iterations — the collective checkpoint save then deadlocks. Agree
        # on the coordinator's flag, but only at checkpoint boundaries (a
        # blocking host collective per step would throttle the loop); with
        # no checkpoint cadence there is nothing durable to gain by
        # stopping early, so the signal is ignored (logged at setup).
        if (tcfg.checkpoint_every and it > start_step
                and it % tcfg.checkpoint_every == 0):
            from jax.experimental import multihost_utils
            return bool(multihost_utils.broadcast_one_to_all(
                np.int32(stop_event.is_set())))
        return False

    if stop_event is not None and n_proc > 1 and not tcfg.checkpoint_every:
        logger.log("note: graceful stop disabled (multi-host run without "
                   "checkpoint_every; no agreed boundary to stop at)")

    tokens_since_log = 0
    lr_at = _make_lr_reader(tcfg)
    stopped_early = False
    tracker = None
    n_dispatches = 0
    if supervision is not None:
        tracker = LossTracker(supervision)
        logger.log(f"supervision: loss checked every "
                   f"{supervision.check_every} dispatch(es)"
                   + (f", spike budget {supervision.spike_factor:.1f}x EMA"
                      if supervision.spike_factor else ""))
    import contextlib
    sanitizer = contextlib.ExitStack()
    if sanitize_enabled():
        # GRAFT_SANITIZE=1: jax tracer-leak + NaN checks for the whole
        # loop, host finiteness check on every logged loss (below) —
        # debug equipment, off by default (costs compile time/fusions)
        logger.log("GRAFT_SANITIZE=1: tracer-leak + NaN checks enabled")
        sanitizer.enter_context(sanitized(True))
    try:
        it = start_step
        while it < tcfg.max_iters:
            # chaos seam (no-op without an installed FaultPlan): raises
            # SIGTERM through the real handler, or corrupts the live
            # state — the faults the supervision layer must survive
            flt = fault_fire("train/step", index=it)
            if flt is not None:
                state = apply_train_state_fault(flt, state)
            if _stop_requested(it):
                stopped_early = True
                logger.log(f"stop requested at step {it}; "
                           "checkpointing and exiting")
                if checkpoint_manager is not None:
                    checkpoint_manager.save(state, cursor)
                break
            if (tcfg.eval_interval and it % tcfg.eval_interval == 0):
                with tel.phase("train/eval", step=it):
                    losses = estimate_loss(state.params, eval_batchers,
                                           eval_step, tcfg.eval_iters,
                                           device_put=dput,
                                           eval_scan=eval_scan,
                                           superbatch_put=superbatch_put)
                logger.log_eval(it, losses["train"], losses["val"])
                history.append((it, losses["train"], losses["val"]))
                logger.reset_timer()
            # after the eval block so the trace captures train steps only
            profiler.step(it)
            # a chunk never crosses an eval/checkpoint boundary, so those
            # cadences behave exactly as in the single-step loop; the feed
            # producer assembled this dispatch's batch to the same schedule
            chunk = chunk_at(it)
            with tel.phase("train/data"):
                batch = next(batches)
            # host dispatch time only: the device runs this chunk
            # asynchronously (a profile_dir capture shows the device's
            # side on the same clock)
            with tel.phase("train/dispatch", step=it, chunk=chunk):
                if chunk > 1:
                    state, metrics = train_scan(state, batch)
                else:
                    state, metrics = train_step(state, batch)
            prev_it, it = it, it + chunk
            tokens_seen += tokens_per_batch * chunk
            tokens_since_log += tokens_per_batch * chunk
            n_dispatches += 1
            if (tracker is not None
                    and n_dispatches % supervision.check_every == 0):
                losses_arr = metrics["loss"]
                # one reviewed sync per supervised dispatch — detection
                # latency is what supervision buys with it
                with tel.phase("train/fetch_loss"):
                    sup_loss = float(  # graftlint: disable=GL004
                        losses_arr if chunk == 1 else losses_arr[-1])
                flt = fault_fire("train/loss", index=it - 1)
                if flt is not None:
                    sup_loss = apply_loss_fault(flt, sup_loss)
                tracker.check(it - 1, sup_loss)
            if tcfg.log_interval:
                # most recent log boundary crossed by this chunk (one line
                # per chunk even if it spans several boundaries)
                b = (it // tcfg.log_interval) * tcfg.log_interval
                if b > prev_it:
                    losses_arr = metrics["loss"]
                    loss_b = (losses_arr if chunk == 1
                              else losses_arr[b - prev_it - 1])
                    # one reviewed sync per LOG boundary, not per step;
                    # the fetch is also the NaN tripwire under sanitize
                    with tel.phase("train/fetch_loss"):
                        loss_val = float(loss_b)  # graftlint: disable=GL004
                    if sanitize_enabled():
                        check_finite(loss_val, f"train loss at step {b - 1}")
                    if not np.isfinite(loss_val):
                        # a NaN loss is a dead run whether or not anyone
                        # is supervising — raise the typed error (the
                        # supervisor rolls back; an unsupervised caller
                        # at least dies naming the step, not 10k steps
                        # later at the final eval)
                        raise NonFiniteLossError(b - 1, loss_val)
                    logger.log_step(b - 1, loss_val, tokens_since_log,
                                    n_chips, lr=lr_at(b - 1))
                    tokens_since_log = 0
            if (checkpoint_manager is not None and tcfg.checkpoint_every
                    and it % tcfg.checkpoint_every == 0):
                with tel.phase("train/checkpoint", step=it):
                    checkpoint_manager.save(state, cursor)
    finally:
        profiler.close()
        sanitizer.close()
    jax.block_until_ready(state.params)
    wall = time.perf_counter() - t0
    end_step = int(jax.device_get(state.step))
    # under a preemption stop, keep the epilogue cheap: a short eval, and
    # the checkpoint was already written before leaving the loop
    # under a stop, also skip eval_scan: its (8,B,T) shape was never
    # compiled and a fresh XLA compile is exactly what the grace window
    # cannot afford — 8 already-compiled eval_step dispatches are cheap
    final_eval = estimate_loss(state.params, eval_batchers, eval_step,
                               min(tcfg.eval_iters, 8) if stopped_early
                               else tcfg.eval_iters, device_put=dput,
                               eval_scan=None if stopped_early
                               else eval_scan,
                               superbatch_put=superbatch_put)
    logger.log_eval(end_step, final_eval["train"], final_eval["val"])
    history.append((end_step, final_eval["train"], final_eval["val"]))
    if checkpoint_manager is not None and not stopped_early:
        checkpoint_manager.save(state, cursor)
    tps = tokens_seen / wall / n_chips if wall > 0 else 0.0
    logger.log(f"trained {tokens_seen:,} tokens in {wall:.1f}s "
               f"({tps:,.0f} tok/s/chip)")
    return TrainResult(state=state, history=history, final_eval=final_eval,
                       tokenizer=tokenizer, tokens_per_sec_per_chip=tps)
