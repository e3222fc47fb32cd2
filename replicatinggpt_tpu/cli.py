"""Command-line interface.

The reference has no CLI — both scripts train at import time with
module-global hyperparameters (SURVEY.md §1 L6, §8-Q9). This CLI exposes
every pipeline as a subcommand over the preset/override config system:

    python -m replicatinggpt_tpu train    --preset char-gpt
    python -m replicatinggpt_tpu generate --preset char-gpt --checkpoint ...
    python -m replicatinggpt_tpu import-hf --model-type gpt2
    python -m replicatinggpt_tpu eval     --preset char-gpt --checkpoint ...
    python -m replicatinggpt_tpu export-torch --preset char-gpt \
        --checkpoint-dir ... --out model.pth
    python -m replicatinggpt_tpu serve-replay --preset char-gpt \
        --n-requests 64 --pool-size 8
"""

from __future__ import annotations

import argparse
import sys

from .config import add_config_flags, config_from_args

#: (dest, flag) pairs for every ENGINE-shape flag registered by
#: add_engine_flags — the serving analogue of
#: config.MODEL_OVERRIDE_FLAGS, kept adjacent to the registration for
#: the same reason: `serve --multiproc` respawns workers with
#: engine_forward_args(), so a flag missing here means a fleet of
#: workers silently serving a DIFFERENT engine shape (pool, pages,
#: decode window, mesh slice) than the operator asked for. Round-trip
#: pinned in tests/test_serve_mesh.py.
ENGINE_FORWARD_FLAGS = (
    ("pool_size", "--pool-size"),
    ("max_queue", "--max-queue"),
    ("prefill_chunk", "--prefill-chunk"),
    ("page_size", "--page-size"),
    ("max_pages", "--max-pages"),
    ("n_pages", "--n-pages"),
    ("decode_window", "--decode-window"),
    ("mesh_shape", "--mesh-shape"),
    ("kv_quant", "--kv-quant"),
    ("weight_quant", "--weight-quant"),
    ("quant_granularity", "--quant-granularity"),
    ("act_quant", "--act-quant"),
)
#: store_true engine switches, forwarded only when set
ENGINE_FORWARD_SWITCHES = (("no_prefix_cache", "--no-prefix-cache"),
                           ("decode_window_auto", "--decode-window-auto"),
                           ("paged_kernel", "--paged-kernel"))


def add_engine_flags(p: argparse.ArgumentParser) -> None:
    """Engine-shape knobs shared by serve-replay / serve / serve-worker
    (one registration — the three parsers must agree or the multiproc
    forwarding in ``engine_forward_args`` breaks)."""
    p.add_argument("--pool-size", type=int, default=8,
                   help="KV-cache slots pre-allocated at engine start")
    p.add_argument("--max-queue", type=int, default=64,
                   help="admission queue bound (backpressure past it)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="prompt tokens per prefill dispatch "
                        "(0 = min(64, block_size))")
    p.add_argument("--page-size", type=int, default=0,
                   help="tokens per KV-cache page (0 = min(16, "
                        "block_size)); see docs/serving.md")
    p.add_argument("--max-pages", type=int, default=0,
                   help="logical KV pages per slot (0 = "
                        "ceil(block_size / page_size)); capping below "
                        "that bounds per-request KV length")
    p.add_argument("--n-pages", type=int, default=0,
                   help="physical KV pages in the pool (0 = "
                        "pool_size * pages-per-slot — the contiguous "
                        "pool's HBM exactly; fewer pages shrinks HBM "
                        "and admission gates on free pages)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable radix prefix reuse (pages only) — "
                        "the A/B arm for prefix-hit TTFT claims")
    p.add_argument("--decode-window", type=int, default=1,
                   help="decode steps rolled into ONE jitted dispatch "
                        "at steady state (async engine; 1 = blocked "
                        "step-per-dispatch loop). Continuous windows: "
                        "admissions ride mixed prefill+decode "
                        "dispatches and deadlines/cancels land as "
                        "on-device lifecycle masks, so only "
                        "speculative verify/re-probe still breaks a "
                        "window — see docs/serving.md#async-engine")
    p.add_argument("--decode-window-auto", action="store_true",
                   help="auto-tune the window size from the live "
                        "host-vs-device dispatch split: bounded "
                        "additive increase over power-of-two buckets "
                        "up to --decode-window (all bucket programs "
                        "compiled at engine start, so tuning never "
                        "recompiles)")
    p.add_argument("--mesh-shape", default="1x1",
                   help="serving mesh DATAxMODEL (e.g. 2x2): run the "
                        "engine GSPMD-sharded over a (data, model) "
                        "device mesh — the paged KV pool's page axis "
                        "shards over data (aggregate page capacity "
                        "multiplier at fixed per-chip HBM), Megatron "
                        "TP over model (attention/MLP FLOPs per "
                        "step). 1x1 = single device. See "
                        "docs/serving.md#sharded-serving")
    p.add_argument("--kv-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="paged KV page storage precision: int8/fp8 "
                        "pages + per-row scale metadata roughly halve "
                        "bytes/page, so at fixed HBM --n-pages can "
                        "roughly double (pages are the admission "
                        "currency; size with "
                        "serve.pages.n_pages_for_hbm). Dequant runs "
                        "inside the paged decode kernels / the XLA "
                        "gather. See docs/serving.md#quantization")
    p.add_argument("--weight-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="block matmul kernel precision: absmax-per-"
                        "output-channel scales with dequant fused "
                        "into the matmuls (quant/weights.py); a "
                        "serialized calibration next to "
                        "--checkpoint-dir is applied when present, "
                        "else computed (and saved) at startup")
    p.add_argument("--paged-kernel", action="store_true",
                   help="opt into the unified Pallas paged-attention "
                        "kernel family for EVERY engine step (decode, "
                        "mixed prefill+decode windows, speculative "
                        "verify; shard_map-wrapped on a >1 mesh). The "
                        "route decision is static per engine and "
                        "exported — metrics_summary()['kernel_route'] "
                        "names any envelope gate that forced XLA")
    p.add_argument("--quant-granularity", default="page",
                   choices=["page", "head"],
                   help="KV scale granularity: 'page' = one f32 scale "
                        "per written row, 'head' = one per (row, head) "
                        "— tighter for outlier heads at H x the "
                        "metadata; both dequant in-kernel on the "
                        "Pallas route")
    p.add_argument("--act-quant", default="none",
                   choices=["none", "int8"],
                   help="W8A8: quantize activation rows to int8 "
                        "(absmax per row) into the int8 weight "
                        "matmuls — requires --weight-quant int8; "
                        "halves the activation operand and feeds the "
                        "MXU a native int8 x int8 contraction")


def engine_forward_args(args: argparse.Namespace) -> list:
    """Reconstruct the add_engine_flags CLI arguments present on
    ``args`` so `serve --multiproc` can respawn workers with the exact
    engine shape (the config_override_args pattern)."""
    out: list = []
    for dest, flag in ENGINE_FORWARD_FLAGS:
        out += [flag, str(getattr(args, dest))]
    for dest, flag in ENGINE_FORWARD_SWITCHES:
        if getattr(args, dest, False):
            out.append(flag)
    return out


def engine_config_from_args(args: argparse.Namespace,
                            check_devices: bool = True):
    """EngineConfig from an add_engine_flags parse. A mesh shape this
    process's devices cannot satisfy is an error. ``check_devices=False``
    is for a process that only FORWARDS the shape — the `serve
    --multiproc` parent computes the fleet's shape hash from it and
    must never initialize a backend (one process per chip: the workers
    own the chips, and each validates the mesh against its own)."""
    from .parallel.mesh import parse_mesh_shape, resolve_mesh_shape
    from .serve import EngineConfig
    d, m = parse_mesh_shape(args.mesh_shape)
    if check_devices and d * m > 1:
        import jax
        d, m = resolve_mesh_shape(args.mesh_shape, len(jax.devices()))
    return EngineConfig(pool_size=args.pool_size,
                        max_queue=args.max_queue,
                        prefill_chunk=args.prefill_chunk,
                        page_size=args.page_size,
                        max_pages=args.max_pages, n_pages=args.n_pages,
                        prefix_cache=not args.no_prefix_cache,
                        paged_kernel=args.paged_kernel,
                        decode_window=args.decode_window,
                        decode_window_auto=args.decode_window_auto,
                        mesh_data=d, mesh_model=m,
                        kv_quant=args.kv_quant,
                        weight_quant=args.weight_quant,
                        quant_granularity=args.quant_granularity,
                        act_quant=args.act_quant)


def _build_mesh_if_needed(cfg):
    import jax
    if cfg.mesh.n_devices <= 1 and not cfg.mesh.fsdp:
        return None
    from .parallel.mesh import make_mesh
    n = cfg.mesh.n_devices
    if len(jax.devices()) < n:
        # never "run unsharded": that would report one chip's run under
        # the mesh's name (gpt2-small's preset mesh is data=8 — on a
        # smaller machine say so with --dp/--tp/--sp/--pp)
        raise SystemExit(
            f"error: mesh wants {n} devices (data={cfg.mesh.data} "
            f"seq={cfg.mesh.seq} model={cfg.mesh.model} "
            f"pipe={cfg.mesh.pipe}), this process has "
            f"{len(jax.devices())}; size the mesh explicitly "
            f"(e.g. --dp 1)")
    return make_mesh(cfg.mesh)


def _apply_rng_impl(args) -> None:
    if getattr(args, "rng_impl", None):
        import jax
        jax.config.update("jax_default_prng_impl", args.rng_impl)


def cmd_train(args) -> int:
    _apply_rng_impl(args)
    if args.coordinator or args.num_processes:
        from .parallel.distributed import initialize
        pi, pn = initialize(args.coordinator, args.num_processes,
                            args.process_id)
        print(f"distributed: process {pi}/{pn}", file=sys.stderr)
    cfg = config_from_args(args)
    from .train.checkpoint import CheckpointManager
    from .train.runner import train
    from .utils.logging import StepLogger
    logger = StepLogger(jsonl_path=args.log_jsonl)
    ck = (CheckpointManager(args.checkpoint_dir)
          if args.checkpoint_dir else None)
    mesh = _build_mesh_if_needed(cfg)
    if args.profile_port:
        from .utils.profiling import start_server
        start_server(args.profile_port)
        print(f"profiler server on :{args.profile_port}", file=sys.stderr)
    # graceful preemption: SIGTERM/SIGINT finish the in-flight dispatch,
    # checkpoint, and exit 0 — resume later with --resume
    import signal
    import threading
    stop = threading.Event()

    def _on_signal(signum, frame):
        if stop.is_set() and signum == signal.SIGINT:
            # second Ctrl+C: the user wants out NOW (e.g. a hung
            # device where no further step will ever complete)
            signal.signal(signal.SIGINT, signal.default_int_handler)
            raise KeyboardInterrupt
        stop.set()

    telemetry = None
    if args.trace_out:
        from .utils.telemetry import Telemetry
        telemetry = Telemetry()
    prev_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        prev_handlers[sig] = signal.signal(sig, _on_signal)
    try:
        res = train(cfg, mesh=mesh, logger=logger, checkpoint_manager=ck,
                    resume=args.resume, profile_dir=args.profile_dir,
                    profile_start=args.profile_start,
                    profile_steps=args.profile_steps, stop_event=stop,
                    telemetry=telemetry)
    finally:
        for sig, h in prev_handlers.items():
            signal.signal(sig, h)
        if telemetry is not None:
            n = telemetry.export_chrome_trace(args.trace_out)
            telemetry.close()
            print(f"telemetry: {n} trace events -> {args.trace_out} "
                  f"(open in Perfetto)", file=sys.stderr)
    if args.sample_after:
        _sample(res.state.params, cfg, res.tokenizer, args.sample_tokens,
                mesh=mesh)
    if ck:
        ck.wait()
    return 0


def _sample(params, cfg, tokenizer, n_tokens: int, prompt_text: str = None,
            top_k: int = 0, top_p: float = 0.0, temperature: float = 1.0,
            mesh=None) -> None:
    import jax.numpy as jnp
    import numpy as np
    from .sample import GenerateConfig, generate, shard_for_decode
    if prompt_text:
        prompt = np.asarray([tokenizer.encode(prompt_text)], np.int32)
    else:
        # the reference's zero-context start (GPT1.py:235)
        prompt = np.zeros((1, 1), np.int32)
    prompt = jnp.asarray(prompt)
    if mesh is not None:
        # TP-sharded decode: Megatron specs over 'model', replicated over
        # 'data' (see sample.generate.shard_for_decode)
        params, prompt = shard_for_decode(params, prompt, cfg.model, mesh,
                                          cfg.mesh)
    toks = generate(params, prompt, cfg.model,
                    GenerateConfig(max_new_tokens=n_tokens, top_k=top_k,
                                   top_p=top_p, temperature=temperature))
    print(tokenizer.decode(np.asarray(toks)[0].tolist()))


def cmd_generate(args) -> int:
    _apply_rng_impl(args)
    import jax
    cfg = config_from_args(args)
    from .data.dataset import load_corpus
    from .tokenizers import get_tokenizer
    from .train.checkpoint import CheckpointManager
    from .train.runner import _resolve_vocab
    from .train.state import create_train_state
    text = load_corpus(cfg.dataset)
    tokenizer = get_tokenizer(cfg.tokenizer, corpus_text=text)
    cfg = _resolve_vocab(cfg, tokenizer)
    state = create_train_state(jax.random.PRNGKey(cfg.train.seed),
                               cfg.model, cfg.train)
    if args.checkpoint_dir:
        ck = CheckpointManager(args.checkpoint_dir)
        restored = ck.restore_latest(state)
        if restored is None:
            print("no checkpoint found; sampling from random init",
                  file=sys.stderr)
        else:
            state = restored
    _sample(state.params, cfg, tokenizer, args.sample_tokens,
            prompt_text=args.prompt, top_k=args.top_k, top_p=args.top_p,
            temperature=args.temperature, mesh=_build_mesh_if_needed(cfg))
    return 0


def cmd_import_hf(args) -> int:
    from .interop.hf import from_pretrained
    params, mcfg = from_pretrained(args.model_type)
    from .models.gpt import param_count
    print(f"imported {args.model_type}: {param_count(params):,} params, "
          f"{mcfg.n_layer}L/{mcfg.n_head}H/{mcfg.n_embd}C")
    if args.save_dir:
        import jax.numpy as jnp
        import jax
        from .train.checkpoint import CheckpointManager
        from .train.state import TrainState
        state = TrainState(step=jnp.zeros((), jnp.int32),
                           params=params, opt_state=(),
                           rng=jax.random.PRNGKey(0))
        ck = CheckpointManager(args.save_dir)
        ck.save(state, wait=True)
        print(f"saved to {args.save_dir}")
    return 0


def cmd_export_torch(args) -> int:
    """Write the reference's durable artifact — a torch ``state_dict``
    file (``torch.save(m.state_dict(), 'model.pth')``,
    /root/reference/GPT1.py:239-241) — from a framework checkpoint.
    The tensors land in :class:`~.reference_torch.RefGPT`'s layout
    ((in, out) kernels, applied as ``x @ W``), so
    ``RefGPT(cfg).load_state_dict(torch.load(out))`` reproduces the
    checkpointed model bit-for-bit in torch (round-trip pinned in
    tests/test_cli.py). Closes the import/export asymmetry: import-hf
    brings torch weights in, this takes them out."""
    _apply_rng_impl(args)
    import jax
    import torch
    cfg = config_from_args(args)
    from .data.dataset import load_corpus
    from .reference_torch import RefGPT, params_to_torch
    from .tokenizers import get_tokenizer
    from .train.checkpoint import CheckpointManager
    from .train.runner import _resolve_vocab
    from .train.state import create_train_state
    text = load_corpus(cfg.dataset)
    tokenizer = get_tokenizer(cfg.tokenizer, corpus_text=text)
    cfg = _resolve_vocab(cfg, tokenizer)
    state = create_train_state(jax.random.PRNGKey(cfg.train.seed),
                               cfg.model, cfg.train)
    if args.checkpoint_dir:
        ck = CheckpointManager(args.checkpoint_dir)
        restored = ck.restore_latest(state)
        if restored is None:
            print("no checkpoint found; exporting random init",
                  file=sys.stderr)
        else:
            state = restored
    else:
        print("no --checkpoint-dir; exporting random init", file=sys.stderr)
    model = params_to_torch(jax.device_get(state.params), RefGPT(cfg.model))
    with open(args.out, "wb") as f:
        torch.save(model.state_dict(), f)
    n_params = sum(p.numel() for p in model.parameters())
    print(f"exported {n_params:,} params (step "
          f"{int(state.step)}) to {args.out}")
    return 0


def cmd_serve_replay(args) -> int:
    """Replay a synthetic Poisson request trace through the
    continuous-batching engine (serve/) and print the serving metrics
    summary — the offline stand-in for real traffic (zero-egress image).
    Random-init params by default; --checkpoint-dir serves a trained
    model (token ids are synthetic either way, so no tokenizer/corpus
    is needed)."""
    _apply_rng_impl(args)
    import json

    import jax

    from .config import config_from_args
    from .serve import EngineConfig, ReplayConfig, format_summary, run_replay
    from .train.state import create_train_state
    cfg = config_from_args(args)
    if cfg.model.family != "gpt":
        # a serve-only family: seeded weights from its own initialiser (no
        # optimizer state beside 12 GB of parameters, no checkpoint format)
        from collections import namedtuple

        from .models.families import family
        if args.checkpoint_dir:
            raise SystemExit(f"--checkpoint-dir: the {cfg.model.family} "
                             f"family is served from seeded weights")
        state = namedtuple("Served", "params")(family(cfg.model).init_params(
            jax.random.PRNGKey(cfg.train.seed), cfg.model))
    else:
        state = create_train_state(jax.random.PRNGKey(cfg.train.seed),
                                   cfg.model, cfg.train)
    if args.checkpoint_dir:
        from .train.checkpoint import CheckpointManager
        restored = CheckpointManager(args.checkpoint_dir).restore_latest(state)
        if restored is None:
            print("no checkpoint found; serving random init",
                  file=sys.stderr)
        else:
            state = restored
    rcfg = ReplayConfig(
        n_requests=args.n_requests, rate=args.rate, seed=args.seed or 0,
        prompt_len_min=args.prompt_len_min,
        prompt_len_max=args.prompt_len_max or cfg.model.block_size // 2,
        max_new_tokens=args.request_max_new_tokens, greedy=args.greedy,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        deadline_s=args.deadline_s, prompt_mode=args.prompt_mode,
        shared_prefix_len=args.shared_prefix_len,
        spec=args.spec, spec_k=args.spec_k, spec_ngram=args.spec_ngram)
    ecfg = engine_config_from_args(args)
    if ecfg.weight_quant != "none":
        # the serialized-calibration workflow: reuse the scales next to
        # the checkpoint, or calibrate + save them now (quant/weights)
        from .quant.weights import prepare_params
        state = state._replace(params=prepare_params(
            state.params, cfg.model, ecfg.weight_quant,
            checkpoint_dir=args.checkpoint_dir,
            log=lambda m: print(m, file=sys.stderr)))
    draft_params = draft_cfg = None
    if rcfg.spec == "model":
        from .models.gpt import init_params, param_count
        from .serve import draft_config_from_preset
        draft_cfg = draft_config_from_preset(cfg.model, args.draft_model)
        draft_params = init_params(jax.random.PRNGKey(cfg.train.seed + 1),
                                   draft_cfg)
        print(f"draft model: {args.draft_model} -> "
              f"{draft_cfg.n_layer}L/{draft_cfg.n_head}H/"
              f"{draft_cfg.n_embd}C ({param_count(draft_params):,} params, "
              f"random init)", file=sys.stderr)
    dev = jax.devices()[0]
    mesh_note = (f", mesh {ecfg.mesh_data}x{ecfg.mesh_model}"
                 if ecfg.mesh_data * ecfg.mesh_model > 1 else "")
    print(f"serve-replay: {rcfg.n_requests} requests @ {rcfg.rate}/s, "
          f"pool {ecfg.pool_size}, queue {ecfg.max_queue}, "
          f"spec {rcfg.spec} (k={rcfg.spec_k}){mesh_note}, "
          f"model {cfg.model.n_layer}L/{cfg.model.n_head}H/"
          f"{cfg.model.n_embd}C on {dev.platform} ({dev.device_kind})",
          file=sys.stderr)
    summary = run_replay(state.params, cfg.model, rcfg, ecfg,
                         draft_params=draft_params, draft_cfg=draft_cfg,
                         trace_out=args.trace_out,
                         metrics_timeline=args.metrics_timeline,
                         metrics_timeline_interval_s=(
                             args.metrics_timeline_interval),
                         metrics_out=args.metrics_out,
                         profile_dir=args.profile_dir,
                         profile_start=args.profile_start,
                         profile_steps=args.profile_steps)
    print(format_summary(summary))
    for k, v in summary.get("artifacts", {}).items():
        print(f"artifact {k}: {v}", file=sys.stderr)
    if args.json:
        print(json.dumps(summary))
    return 0


def _multiproc_plan(args):
    """What the `serve --multiproc` PARENT decides before it spawns
    anything: the worker specs, the fleet's expected engine-shape hash,
    and the autoscaler. One process per chip: the workers own the
    chips, so nothing here may initialize a JAX backend (pinned in
    tests/test_bring_up.py) — the shape hash is computed from the flags
    alone, and each worker validates the mesh against its own devices."""
    from .faults.procsup import (AutoscaleConfig, make_worker_specs,
                                 worker_spec_factory)
    # the workers must build the SAME model the operator asked
    # for: forward every set model-override flag (the serve-worker
    # parser takes the full add_config_flags set too) — silently
    # serving the preset's defaults would be a different model.
    # The flag list lives NEXT TO add_config_flags
    # (config.MODEL_OVERRIDE_FLAGS) so new flags can't fall out.
    from .config import config_override_args
    config_args = (["--preset", args.preset]
                   + config_override_args(args))
    if args.rng_impl is not None:
        config_args += ["--rng-impl", args.rng_impl]
    # the full engine shape — pool/pages/window/MESH SLICE — rides
    # the same pinned plumbing as the model overrides above
    # (ENGINE_FORWARD_FLAGS next to add_engine_flags), so each
    # worker process builds exactly the engine the operator asked
    # for, mesh included
    engine_args = engine_forward_args(args)
    if args.no_fsync:
        engine_args.append("--no-fsync")
    if args.checkpoint_dir:
        engine_args += ["--checkpoint-dir", args.checkpoint_dir]
    specs = make_worker_specs(args.replicas, args.journal_dir,
                              config_args, engine_args)
    # pin the fleet's expected engine shape from THIS process's
    # parse of the same flags the workers receive: a worker whose
    # build resolves a different model/engine is rejected at
    # registration with RpcProtocolError, never served traffic
    from .serve.rpc import engine_shape_hash
    expect = engine_shape_hash(
        config_from_args(args).model,
        engine_config_from_args(args, check_devices=False))
    autoscale = spec_factory = None
    if args.autoscale_max > 0:
        autoscale = AutoscaleConfig(min_workers=args.autoscale_min,
                                    max_workers=args.autoscale_max)
        spec_factory = worker_spec_factory(args.journal_dir,
                                           config_args, engine_args)
    return specs, expect, autoscale, spec_factory


def cmd_serve(args) -> int:
    """The fleet front door: N engine replicas behind the prefix-
    affinity router (serve/router.py), exposed over HTTP/SSE
    (serve/http.py) — submit/stream/cancel/healthz/readyz/metrics.
    Binds loopback by default (the zero-egress image takes no outside
    traffic; this is the ingress path's real implementation, exercised
    by tests and local clients). Ctrl-C shuts down cleanly, closing
    the per-replica crash journals.

    ``--multiproc`` runs the replicas as real worker PROCESSES
    (serve-worker subcommand) under the process supervisor
    (faults/procsup.py): each worker owns its own engine and an
    exclusively-locked journal in --journal-dir; the router speaks
    serve/rpc.py to them, the supervisor restarts the dead with
    backoff and quarantines past the restart budget
    (docs/serving.md#deployment)."""
    _apply_rng_impl(args)
    import asyncio

    from .serve.http import ServeApp
    from .serve.router import RouterConfig

    import os

    ledger = args.ledger
    if ledger is None and args.multiproc and args.journal_dir:
        ledger = os.path.join(args.journal_dir, "router_ledger.jsonl")
    rcfg = RouterConfig(n_replicas=args.replicas,
                        journal_dir=args.journal_dir,
                        ledger_path=ledger,
                        ledger_fsync=args.ledger_fsync,
                        affinity=not args.no_affinity,
                        wedge_budget_s=args.wedge_budget_s,
                        wedge_patience=args.wedge_patience,
                        step_timeout_s=args.step_timeout_s)
    telemetry = None
    if args.trace_out or args.trace_jsonl:
        from .utils.telemetry import Telemetry
        telemetry = Telemetry(jsonl_path=args.trace_jsonl)
    supervisor = None
    if args.multiproc:
        if not args.journal_dir:
            print("--multiproc requires --journal-dir (the base "
                  "directory for per-worker PRIVATE journal dirs and "
                  "the router's own ledger — nothing in it is shared "
                  "between processes)", file=sys.stderr)
            return 2
        from .faults.procsup import SupervisorConfig, spawn_fleet
        specs, expect, autoscale, spec_factory = _multiproc_plan(args)
        print(f"spawning {args.replicas} worker process(es); waiting "
              f"for warmup + RPC registration (expect shape {expect})",
              file=sys.stderr)
        router, supervisor = spawn_fleet(
            specs, rcfg,
            SupervisorConfig(restart_budget=args.restart_budget,
                             expect_shape_hash=expect),
            telemetry=telemetry, autoscale=autoscale,
            spec_factory=spec_factory, listen_host=args.listen_host)
        if args.listen_host not in ("127.0.0.1", "localhost"):
            print(f"fleet up: workers on other hosts join via "
                  f"`serve-worker --router-addr "
                  f"<this-host>:{supervisor.listener.port}`",
                  file=sys.stderr)
        else:
            print(f"fleet up: registration on "
                  f"{supervisor.router_addr} (loopback — pass "
                  f"`--listen-host 0.0.0.0` to accept workers from "
                  f"other hosts)", file=sys.stderr)
    else:
        import jax

        from .serve import Router
        from .train.state import create_train_state
        cfg = config_from_args(args)
        state = create_train_state(jax.random.PRNGKey(cfg.train.seed),
                                   cfg.model, cfg.train)
        if args.checkpoint_dir:
            from .train.checkpoint import CheckpointManager
            restored = (CheckpointManager(args.checkpoint_dir)
                        .restore_latest(state))
            if restored is None:
                print("no checkpoint found; serving random init",
                      file=sys.stderr)
            else:
                state = restored
        in_ecfg = engine_config_from_args(args)
        if in_ecfg.weight_quant != "none":
            from .quant.weights import prepare_params
            state = state._replace(params=prepare_params(
                state.params, cfg.model, in_ecfg.weight_quant,
                checkpoint_dir=args.checkpoint_dir,
                log=lambda m: print(m, file=sys.stderr)))
        router = Router(state.params, cfg.model, rcfg, in_ecfg,
                        telemetry=telemetry)
    rate_limit = None
    if args.rate_limit_rps > 0:
        from .serve.http import RateLimitConfig
        rate_limit = RateLimitConfig(rps=args.rate_limit_rps,
                                     burst=args.rate_limit_burst)
    app = ServeApp(router, idle_timeout_s=args.idle_timeout_s,
                   supervisor=supervisor, rate_limit=rate_limit)
    rc = 0
    try:
        asyncio.run(app.serve_forever(args.host, args.port))
    except KeyboardInterrupt:
        print("shutting down", file=sys.stderr)
    except asyncio.CancelledError:
        # the driver task died (ServeApp._on_driver_done printed the
        # traceback and closed the server out from under serve_forever)
        rc = 1
    finally:
        if supervisor is not None:
            supervisor.stop_all()
        router.close()
        if telemetry is not None:
            if args.trace_out:
                n = telemetry.export_chrome_trace(args.trace_out)
                print(f"telemetry: {n} trace events -> {args.trace_out}",
                      file=sys.stderr)
            telemetry.close()
            if args.trace_jsonl:
                print(f"telemetry: event sink -> {args.trace_jsonl}",
                      file=sys.stderr)
    return rc


def cmd_serve_worker(args) -> int:
    """One fleet worker process (serve/worker.py): builds + warms one
    engine, opens its exclusively-locked crash journal, replays the
    previous incarnation's unfinished requests, then serves the
    serve/rpc.py protocol on loopback until the router shuts it down
    (or something kills it — which is the point: the journal + the
    router's delivery ledger make that survivable)."""
    _apply_rng_impl(args)
    from .serve.worker import run_worker
    return run_worker(args)


def cmd_eval(args) -> int:
    _apply_rng_impl(args)
    import jax
    cfg = config_from_args(args)
    from .data.dataset import TokenDataset, load_corpus
    from .data.loader import make_batcher
    from .tokenizers import get_tokenizer
    from .train.checkpoint import CheckpointManager
    from .train.runner import _resolve_vocab
    from .train.state import create_train_state
    from .train.steps import estimate_loss, make_eval_scan, make_eval_step
    text = load_corpus(cfg.dataset)
    tokenizer = get_tokenizer(cfg.tokenizer, corpus_text=text)
    cfg = _resolve_vocab(cfg, tokenizer)
    state = create_train_state(jax.random.PRNGKey(cfg.train.seed),
                               cfg.model, cfg.train)
    if args.checkpoint_dir:
        state = (CheckpointManager(args.checkpoint_dir)
                 .restore_latest(state) or state)
    ds = TokenDataset.from_text(text, tokenizer, cfg.train.val_fraction)
    batchers = {
        "train": make_batcher("random", ds.train, cfg.train.batch_size,
                              cfg.model.block_size, seed=1),
        "val": make_batcher("random", ds.val, cfg.train.batch_size,
                            cfg.model.block_size, seed=2),
    }
    out = estimate_loss(state.params, batchers, make_eval_step(cfg.model),
                        cfg.train.eval_iters,
                        eval_scan=make_eval_scan(cfg.model))
    print(f"train loss {out['train']:.4f}, val loss = {out['val']:.4f}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="replicatinggpt_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    pt = sub.add_parser("train", help="train a model")
    add_config_flags(pt)
    pt.add_argument("--checkpoint-dir", default=None)
    pt.add_argument("--resume", action="store_true")
    pt.add_argument("--log-jsonl", default=None)
    pt.add_argument("--sample-after", action="store_true",
                    help="print a sample after training (GPT1.py:235-236)")
    pt.add_argument("--sample-tokens", type=int, default=500)
    pt.add_argument("--coordinator", default=None,
                    help="multi-host coordinator address host:port "
                         "(jax.distributed.initialize); TPU pods usually "
                         "auto-detect and need none of these")
    pt.add_argument("--num-processes", type=int, default=None)
    pt.add_argument("--process-id", type=int, default=None)
    pt.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace of a few hot-loop "
                         "steps here (view in TensorBoard/Perfetto)")
    pt.add_argument("--profile-start", type=int, default=10)
    pt.add_argument("--profile-steps", type=int, default=5)
    pt.add_argument("--profile-port", type=int, default=0,
                    help="start a live profiler server on this port")
    pt.add_argument("--trace-out", default=None,
                    help="write a Perfetto-loadable Chrome trace of the "
                         "host timeline (dispatch/eval spans, checkpoint "
                         "markers) here — the host half of --profile-dir")
    pt.add_argument("--rng-impl", default=None,
                    choices=["threefry2x32", "rbg"],
                    help="dropout PRNG; 'rbg' uses the TPU hardware "
                         "generator (~15%% faster steps at dropout 0.2)")
    pt.set_defaults(fn=cmd_train)

    pg = sub.add_parser("generate", help="sample from a model")
    add_config_flags(pg)
    pg.add_argument("--rng-impl", default=None,
                    choices=["threefry2x32", "rbg"],
                    help="must match the checkpoint's training run")
    pg.add_argument("--checkpoint-dir", default=None)
    pg.add_argument("--prompt", default=None)
    pg.add_argument("--sample-tokens", type=int, default=500)
    pg.add_argument("--top-k", type=int, default=0)
    pg.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass; 0 = off")
    pg.add_argument("--temperature", type=float, default=1.0)
    pg.set_defaults(fn=cmd_generate)

    pi = sub.add_parser("import-hf", help="import HF GPT-2 weights")
    pi.add_argument("--model-type", default="gpt2",
                    choices=["gpt2", "gpt2-medium", "gpt2-large", "gpt2-xl"])
    pi.add_argument("--save-dir", default=None)
    pi.set_defaults(fn=cmd_import_hf)

    px = sub.add_parser("export-torch",
                        help="export a checkpoint as a torch state_dict "
                             "(the reference's model.pth artifact)")
    add_config_flags(px)
    px.add_argument("--checkpoint-dir", default=None)
    px.add_argument("--out", default="model.pth")
    px.set_defaults(fn=cmd_export_torch)

    ps = sub.add_parser("serve-replay",
                        help="replay a synthetic Poisson request trace "
                             "through the continuous-batching serving "
                             "engine and report TTFT/throughput/occupancy")
    add_config_flags(ps)
    ps.add_argument("--rng-impl", default=None,
                    choices=["threefry2x32", "rbg"])
    ps.add_argument("--checkpoint-dir", default=None)
    ps.add_argument("--n-requests", type=int, default=64)
    ps.add_argument("--rate", type=float, default=200.0,
                    help="mean Poisson arrival rate, requests/sec")
    add_engine_flags(ps)
    ps.add_argument("--shared-prefix-len", type=int, default=0,
                    help="--prompt-mode shared_prefix: common prefix "
                         "length (0 = prompt-len-max // 2)")
    ps.add_argument("--prompt-len-min", type=int, default=1)
    ps.add_argument("--prompt-len-max", type=int, default=0,
                    help="0 = block_size // 2")
    ps.add_argument("--request-max-new-tokens", type=int, default=16)
    ps.add_argument("--greedy", action="store_true")
    ps.add_argument("--temperature", type=float, default=1.0)
    ps.add_argument("--top-k", type=int, default=20)
    ps.add_argument("--top-p", type=float, default=0.0)
    ps.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request deadline after arrival (0 = none)")
    ps.add_argument("--spec", default="off",
                    choices=["off", "ngram", "model"],
                    help="speculative decoding drafter: host-side n-gram "
                         "prompt lookup (no extra params) or a small "
                         "random-init draft model (--draft-model preset)")
    ps.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per slot per step (static: one "
                         "verify program per k)")
    ps.add_argument("--spec-ngram", type=int, default=3,
                    help="n-gram drafter match width")
    ps.add_argument("--draft-model", default="test-tiny",
                    help="--spec model: preset whose architecture sizes "
                         "the draft model (vocab/block/dtype forced to "
                         "the target's)")
    ps.add_argument("--prompt-mode", default="random",
                    choices=["random", "repeat", "shared_prefix"],
                    help="'repeat' tiles small patterns (the "
                         "speculative-friendly repetitive trace); "
                         "'shared_prefix' gives every prompt one common "
                         "prefix (the radix-prefix-cache traffic shape)")
    ps.add_argument("--json", action="store_true",
                    help="also print the summary as one JSON line")
    ps.add_argument("--trace-out", default=None,
                    help="write a Perfetto-loadable Chrome trace of the "
                         "replay here: one span tree per request "
                         "(submit -> queue -> admit -> prefill -> "
                         "decode/verify -> finish) on per-slot tracks, "
                         "with prefix-hit/COW/eviction/recovery markers "
                         "(docs/observability.md)")
    ps.add_argument("--metrics-timeline", default=None,
                    help="write a JSONL time series of every engine "
                         "counter/gauge/histogram here (one snapshot per "
                         "--metrics-timeline-interval, plus first/last)")
    ps.add_argument("--metrics-timeline-interval", type=float, default=0.5,
                    help="seconds between metrics-timeline snapshots")
    ps.add_argument("--metrics-out", default=None,
                    help="write the end-of-run metrics as Prometheus "
                         "text exposition here (the /metrics scrape "
                         "format)")
    ps.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler device trace of a few "
                         "engine steps here (same contract as the train "
                         "subcommand; view in TensorBoard/Perfetto next "
                         "to --trace-out)")
    ps.add_argument("--profile-start", type=int, default=10,
                    help="engine step the device capture opens at")
    ps.add_argument("--profile-steps", type=int, default=5,
                    help="engine steps the device capture covers")
    ps.set_defaults(fn=cmd_serve_replay)

    pv = sub.add_parser("serve",
                        help="run the HTTP/SSE serving fleet: N engine "
                             "replicas behind the prefix-affinity "
                             "router, with submit/stream/cancel/"
                             "healthz/metrics endpoints")
    add_config_flags(pv)
    pv.add_argument("--rng-impl", default=None,
                    choices=["threefry2x32", "rbg"])
    pv.add_argument("--checkpoint-dir", default=None)
    pv.add_argument("--host", default="127.0.0.1")
    pv.add_argument("--port", type=int, default=8000)
    pv.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the router")
    pv.add_argument("--journal-dir", default=None,
                    help="in-process mode: per-replica crash journals "
                         "live here (cross-replica requeue after a "
                         "replica death); --multiproc: the LAUNCHER's "
                         "base dir for per-worker PRIVATE dirs "
                         "(worker{i}/journal.jsonl + log) — nothing "
                         "is shared between processes "
                         "(docs/robustness.md)")
    pv.add_argument("--ledger", default=None,
                    help="the ROUTER's own crash journal: submits at "
                         "fleet acceptance, finishes at terminal "
                         "results; a restarted router requeues its "
                         "accepted-but-unfinished set from here — "
                         "recovery that reads NO worker filesystem "
                         "(survives total worker-host loss). Default "
                         "under --multiproc: "
                         "<journal-dir>/router_ledger.jsonl")
    pv.add_argument("--ledger-fsync", action="store_true",
                    help="fsync the router ledger's finish records "
                         "(narrows the torn-tail window to the submit "
                         "side, which only ever re-decodes)")
    pv.add_argument("--no-affinity", action="store_true",
                    help="disable radix-prefix-affinity routing "
                         "(pure least-loaded)")
    pv.add_argument("--wedge-budget-s", type=float, default=0.0,
                    help="per-replica step budget for the router's "
                         "wedge probe (0 = detection off); a replica "
                         "over budget --wedge-patience times in a row "
                         "is quarantined and its in-flight work "
                         "re-routed")
    pv.add_argument("--wedge-patience", type=int, default=2)
    add_engine_flags(pv)
    pv.add_argument("--multiproc", action="store_true",
                    help="run replicas as real worker PROCESSES "
                         "(serve-worker) under the process supervisor: "
                         "supervised restarts with backoff, rolling "
                         "restarts, SIGKILL-survivable exactly-once "
                         "streams; requires --journal-dir")
    pv.add_argument("--restart-budget", type=int, default=3,
                    help="--multiproc: crash restarts per worker before "
                         "quarantine (in-flight work requeued onto "
                         "survivors from the router's ledger)")
    pv.add_argument("--autoscale-max", type=int, default=0,
                    help="--multiproc: enable the autoscaler with this "
                         "many workers as the ceiling (0 = fixed "
                         "fleet). --replicas is the STARTING size; "
                         "sustained backlog spawns workers up to the "
                         "ceiling, sustained lull drains them down to "
                         "--autoscale-min through the rolling-restart "
                         "drain path (zero dropped requests)")
    pv.add_argument("--autoscale-min", type=int, default=1,
                    help="--multiproc autoscaler floor")
    pv.add_argument("--listen-host", default="127.0.0.1",
                    help="--multiproc: interface the worker "
                         "registration listener binds (default "
                         "loopback — the zero-egress posture; "
                         "0.0.0.0 accepts `serve-worker "
                         "--router-addr` registrations from other "
                         "hosts)")
    pv.add_argument("--step-timeout-s", type=float, default=10.0,
                    help="--multiproc: RPC budget for one worker step; "
                         "a hung (SIGSTOPped) worker costs the router "
                         "at most this per step")
    pv.add_argument("--no-fsync", action="store_true",
                    help="--multiproc: disable the workers' "
                         "fsync-per-finish journal durability")
    pv.add_argument("--idle-timeout-s", type=float, default=30.0,
                    help="drop a connection that stalls mid-headers/"
                         "body or stops consuming its SSE stream for "
                         "this long (slow-loris guard; 0 = off)")
    pv.add_argument("--rate-limit-rps", type=float, default=0.0,
                    help="per-client submit rate (token bucket keyed "
                         "on the x-client-id header; over-rate submits "
                         "get 429 + Retry-After; 0 = off)")
    pv.add_argument("--rate-limit-burst", type=float, default=10.0,
                    help="token-bucket capacity: submits a quiet "
                         "client may burst before the sustained rate "
                         "applies")
    pv.add_argument("--trace-out", default=None,
                    help="write a Perfetto trace (router + per-replica "
                         "tracks) at shutdown")
    pv.add_argument("--trace-jsonl", default=None,
                    help="stream trace events to this JSONL sink as "
                         "they happen (crash-tolerant)")
    pv.set_defaults(fn=cmd_serve)

    pw = sub.add_parser("serve-worker",
                        help="one fleet worker process: an engine "
                             "behind the serve/rpc.py socket protocol "
                             "with a locked crash journal and startup "
                             "journal replay (spawned by `serve "
                             "--multiproc` / the process supervisor; "
                             "runnable by hand for debugging)")
    add_config_flags(pw)
    pw.add_argument("--rng-impl", default=None,
                    choices=["threefry2x32", "rbg"])
    pw.add_argument("--checkpoint-dir", default=None)
    pw.add_argument("--host", default="127.0.0.1")
    pw.add_argument("--port", type=int, default=0,
                    help="RPC port (0 = ephemeral; the bound port is "
                         "announced in the --router-addr register "
                         "frame and the stderr banner)")
    pw.add_argument("--journal", default=None,
                    help="crash journal path (exclusively flock-ed; "
                         "replayed at startup; WORKER-LOCAL — the "
                         "router reconciles over the journal_drain "
                         "RPC, never this file)")
    pw.add_argument("--router-addr", default=None,
                    help="host:port of the fleet's registration "
                         "listener: once warmed + replayed + bound, "
                         "the worker announces itself there with one "
                         "register frame (port/pid/gen/replayed + "
                         "protocol version + engine shape hash) and "
                         "becomes routable — the no-shared-filesystem "
                         "handshake; run a worker on ANY host that "
                         "can reach this address. A protocol/shape "
                         "mismatch exits 3 (RpcProtocolError)")
    pw.add_argument("--worker-idx", type=int, default=-1,
                    help="supervisor-managed replica index (-1 = "
                         "unmanaged: register as a brand-new replica "
                         "and grow the fleet)")
    pw.add_argument("--gen", type=int, default=0,
                    help="spawn generation (carried in the register "
                         "frame so the supervisor never attaches a "
                         "stale incarnation)")
    pw.add_argument("--no-fsync", action="store_true",
                    help="disable fsync-per-finish journal durability")
    pw.add_argument("--tier", default="mixed",
                    choices=["mixed", "prefill", "decode"],
                    help="disaggregation role (serve/disagg.py), "
                         "advertised at registration: 'prefill' "
                         "workers take only prefill_only prompt work "
                         "and export finished KV pages, 'decode' "
                         "workers receive pages and own the streams, "
                         "'mixed' (default) does both — the colocated "
                         "fleet")
    pw.add_argument("--reregister-idle-s", type=float, default=5.0,
                    help="router-silence threshold before this worker "
                         "re-sends its register frame (bounded "
                         "exponential backoff): a RESTARTED router's "
                         "fresh listener re-attaches the worker "
                         "without operator action — registration is "
                         "no longer once-at-startup")
    add_engine_flags(pw)
    pw.set_defaults(fn=cmd_serve_worker)

    pe = sub.add_parser("eval", help="estimate train/val loss")
    add_config_flags(pe)
    pe.add_argument("--rng-impl", default=None,
                    choices=["threefry2x32", "rbg"],
                    help="must match the checkpoint's training run")
    pe.add_argument("--checkpoint-dir", default=None)
    pe.set_defaults(fn=cmd_eval)

    pl = sub.add_parser("lint",
                        help="graftlint: JAX-hazard static analysis "
                             "(recompiles, host syncs, RNG reuse, "
                             "dynamic_update_slice clamps, ...) — "
                             "CPU-only, no jax import, tier-1 fast")
    from .analysis.cli import add_lint_flags, run_lint
    add_lint_flags(pl)
    pl.set_defaults(fn=run_lint)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd != "lint":                # lint never imports jax
        from .utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
