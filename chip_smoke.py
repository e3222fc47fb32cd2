#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # the mesh paths only, on one 4-chip host

One process, the normal entry points (``cli._build_mesh_if_needed`` +
``train.runner.train``, ``serve.replay.run_replay``, ``cli.main
generate``), GPT-2 124M at full width with random weights from a seed.
Every phase prints one JSON line with what it saw and raises when
something is wrong; the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only if every phase passed. Without a TPU the ``device``
phase fails first: there is no CPU fallback, no interpret mode, no
downgraded mesh. This process is the only one that touches the chip —
it starts no child that needs it.

The phase functions take the preset arguments, so a rehearsal can drive
them at test-tiny size on the CPU (.claude/skills/verify/SKILL.md has
the recipe); nothing here chooses a smaller size by itself.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DATASET = os.path.join(ROOT, "datasets", "shakespeare.txt")
SEED = 1337

#: the one-chip model of every phase: GPT-2 124M as published
#: (12L/12H/768C, T=1024, vocab 50257, bf16), its mesh sized explicitly
LM = ["--preset", "gpt2-small", "--dataset", DATASET]

_COMPILE = {"s": 0.0, "hits": 0, "misses": 0}


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _watch_compiles() -> None:
    """Sum XLA backend-compile seconds and persistent-cache hits."""
    from jax import monitoring

    def on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            _COMPILE["s"] += secs

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            _COMPILE["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            _COMPILE["misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


@contextlib.contextmanager
def compiles(out: dict):
    """Fill ``out`` with the compile seconds / cache traffic of a span."""
    before = dict(_COMPILE)
    t0 = time.perf_counter()
    yield
    out["wall_s"] = round(time.perf_counter() - t0, 1)
    out["compile_s"] = round(_COMPILE["s"] - before["s"], 1)
    out["cache_hits"] = _COMPILE["hits"] - before["hits"]
    out["cache_misses"] = _COMPILE["misses"] - before["misses"]


def _cfg(argv):
    """A Config from the CLI's own flags (what a user would type)."""
    from replicatinggpt_tpu.config import add_config_flags, config_from_args
    p = argparse.ArgumentParser()
    add_config_flags(p)
    return config_from_args(p.parse_args(argv))


def _device_sets(tree) -> list:
    """Sorted ids of the devices that hold a shard of any leaf."""
    import jax
    ids = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "sharding"):
            ids |= {d.id for d in leaf.sharding.device_set}
    return sorted(ids)


# ---------------------------------------------------------------- phases

def phase_device(chips: int, cache_dir: str) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", **info, jax=jax.__version__, compile_cache=cache_dir,
        cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax found {info}); this "
                         f"script proves the chip path and has no other")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but jax found "
                         f"{info['count']} device(s)")
    return info


def phase_build() -> None:
    """What git does not carry is built here, from committed files: the
    native fastpath .so and the corpus-trained BPE tokenizer."""
    from replicatinggpt_tpu.data.dataset import load_corpus
    from replicatinggpt_tpu.native import binding
    from replicatinggpt_tpu.native.build import build
    from replicatinggpt_tpu.tokenizers import get_tokenizer
    cxx = (os.environ.get("CXX") or shutil.which("g++")
           or shutil.which("c++"))
    so = build(verbose=True)
    if cxx and so is None:
        raise RuntimeError(f"native build failed with {cxx} present")
    tok_path = os.path.join(os.path.dirname(DATASET), "bpe_1024.json")
    cached = os.path.exists(tok_path)
    t0 = time.perf_counter()
    tok = get_tokenizer("bpe", corpus_text=load_corpus(DATASET),
                        cache_dir=os.path.dirname(DATASET))
    say("build", native_available=binding.available(),
        compiler=bool(cxx), tokenizer="bpe", vocab=tok.vocab_size,
        tokenizer_was_cached=cached,
        tokenizer_s=round(time.perf_counter() - t0, 1))


def _train_once(argv, *, want_kernel: bool) -> dict:
    """One trainer run through the CLI's mesh builder and the runner.
    Returns the loss trajectory, timings and where the state lives."""
    from replicatinggpt_tpu.cli import _build_mesh_if_needed
    from replicatinggpt_tpu.train.runner import train
    from replicatinggpt_tpu.utils.logging import StepLogger

    class Recorder(StepLogger):
        def __init__(self):
            super().__init__(stream=sys.stderr)
            self.events = []

        def _jsonl(self, obj):
            self.events.append({**obj, "t": time.perf_counter()})

    cfg = _cfg(argv)
    mesh = _build_mesh_if_needed(cfg)       # raises on too few devices
    rec = Recorder()
    out = {}
    with compiles(out):
        res = train(cfg, mesh=mesh, logger=rec)
    steps = [e for e in rec.events if e["event"] == "step"]
    k = max(cfg.train.steps_per_dispatch, 1)
    if len(steps) < 2:
        raise AssertionError(f"need >= 2 dispatches, logged {len(steps)}")
    losses = [e["loss"] for e in steps]
    val = res.final_eval["val"]
    if not all(l == l and abs(l) < 1e9 for l in losses + [val]):
        raise AssertionError(f"non-finite loss: {losses} val {val}")
    steady = ((steps[-1]["t"] - steps[0]["t"])
              / ((steps[-1]["step"] - steps[0]["step"]) or 1))
    out.update(
        model=f"{cfg.model.n_layer}L/{cfg.model.n_head}H/"
              f"{cfg.model.n_embd}C T={cfg.model.block_size} "
              f"V={cfg.model.vocab_size} {cfg.model.dtype}",
        mesh={"data": cfg.mesh.data, "model": cfg.mesh.model,
              "fsdp": cfg.mesh.fsdp},
        batch=cfg.train.batch_size, steps=cfg.train.max_iters,
        steps_per_dispatch=k,
        losses=[round(l, 4) for l in losses], val_loss=round(val, 4),
        steady_ms_per_step=round(steady * 1e3, 2),
        params_on=_device_sets(res.state.params),
        opt_state_on=_device_sets(res.state.opt_state))
    if want_kernel:
        out["attention"] = _train_attention_route(cfg, mesh, res.state)
    return out


def _train_attention_route(cfg, mesh, state) -> str:
    """Lower (not compile) the step the runner just ran and look for the
    Pallas call: einsum by accident is a finding, not a pass."""
    import jax
    import numpy as np
    from replicatinggpt_tpu.train.steps import make_train_step
    attention_fn = None
    if mesh is not None:
        from replicatinggpt_tpu.parallel import select_attention_fn
        attention_fn = select_attention_fn(cfg.model, cfg.mesh, mesh)
    step = make_train_step(cfg.model, cfg.train, attention_fn=attention_fn)
    B, T = cfg.train.batch_size, cfg.model.block_size
    tok = jax.ShapeDtypeStruct((B, T), np.uint16)
    if mesh is not None:
        from replicatinggpt_tpu.parallel.mesh import make_batch_sharding
        tok = jax.ShapeDtypeStruct((B, T), np.uint16,
                                   sharding=make_batch_sharding(mesh))
    text = step.lower(state, (tok, tok)).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("the train step lowered without a Pallas "
                             "attention kernel (no tpu_custom_call)")
    return "pallas (tpu_custom_call in the lowered step)"


def _train_argv(base, mesh_flags, steps: int, k: int):
    return base + mesh_flags + [
        "--batch-size", "8", "--max-iters", str(steps),
        "--steps-per-dispatch", str(k), "--eval-interval", "0",
        "--eval-iters", "2", "--log-interval", "1",
        "--lr-schedule", "constant", "--warmup-iters", "0",
        "--seed", str(SEED)]


def phase_train(lm=LM, steps: int = 20, k: int = 5,
                want_kernel: bool = True) -> dict:
    out = _train_once(_train_argv(lm, ["--dp", "1"], steps, k),
                      want_kernel=want_kernel)
    say("train", **out)
    if not out["losses"][-1] < out["losses"][0]:
        raise AssertionError(f"loss did not fall: {out['losses']}")
    return out


def _serve_setup(argv):
    import jax
    from replicatinggpt_tpu.train.state import create_train_state
    cfg = _cfg(argv + ["--decode-cache-layout", "packed",
                       "--seed", str(SEED)])
    state = create_train_state(jax.random.PRNGKey(cfg.train.seed),
                               cfg.model, cfg.train)
    return cfg.model, state.params


def _trace(mcfg, ecfg, n_requests: int, n_new: int):
    """Greedy requests whose prompt lengths sit on and around the page
    and prefill-chunk boundaries, arriving faster than the pool drains
    (so admissions land mid-flight)."""
    import numpy as np
    from replicatinggpt_tpu.serve.requests import Request, SamplingParams
    page = ecfg.page_size or min(16, mcfg.block_size)
    chunk = ecfg.chunk(mcfg.block_size)
    cands = [page - 1, page, page + 1, 2 * page - 1, 2 * page + 1,
             chunk - 1, chunk, chunk + 1, chunk + page + 4,
             2 * chunk - 1, 2 * chunk, 2 * chunk + 1, 3 * chunk + 8,
             4 * chunk - 6]
    lens = sorted({l for l in cands if 1 <= l <= mcfg.block_size - n_new})
    rng = np.random.default_rng(SEED)
    trace, t = [], 0.0
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / 100.0))
        prompt = rng.integers(0, mcfg.vocab_size, (lens[i % len(lens)],))
        trace.append((t, Request(
            id=f"r{i:03d}", prompt=prompt.astype(np.int32),
            max_new_tokens=n_new, sampling=SamplingParams(greedy=True))))
    return trace


def _reference_gaps(params, mcfg, prompts, streams):
    """The plain reference: a full-sequence einsum forward, teacher-
    forced on each stream. Returns, per stream, how far below the
    reference's best logit its worst token sits (0 = every token is the
    reference argmax given its own prefix)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from replicatinggpt_tpu.models.gpt import forward
    if not streams:
        return []
    ref_cfg = dataclasses.replace(mcfg, attention_impl="einsum")
    T = mcfg.block_size
    n = max(len(s) for s in streams)

    @jax.jit
    def gap(params, idx, pos, toks):
        logits, _ = forward(params, idx, ref_cfg)
        rows = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        got = jnp.take_along_axis(rows, toks[:, :, None], axis=2)[..., 0]
        return rows.max(-1) - got

    gaps = []
    for lo in range(0, len(prompts), 4):
        idx = np.zeros((4, T), np.int32)
        pos = np.zeros((4, n), np.int32)
        toks = np.zeros((4, n), np.int32)
        live = np.zeros((4, n), bool)
        for b, (p, s) in enumerate(zip(prompts[lo:lo + 4],
                                       streams[lo:lo + 4])):
            seq = np.concatenate([p, s[:-1]]).astype(np.int32)
            idx[b, :len(seq)] = seq
            pos[b, :len(s)] = len(p) - 1 + np.arange(len(s))
            toks[b, :len(s)] = s
            live[b, :len(s)] = True
        g = np.where(live, np.asarray(gap(params, idx, pos, toks)), 0.0)
        gaps += [float(x) for x in g.max(1)[:len(prompts[lo:lo + 4])]]
    return gaps


#: generate() compiles one program per power-of-two prompt bucket, half
#: a minute each at 124M: the comparison with it covers the buckets on
#: the page (16), prefill-chunk (64) and multi-chunk boundaries. Every
#: stream, in every bucket, is held to the reference forward.
PARITY_BUCKETS = (16, 64, 256)


def _replay(mcfg, params, *, window: int, mesh=(1, 1), n_requests: int,
            n_new: int, want_route, generate_parity: bool) -> dict:
    """One serve-replay run, held to: every request finishes, nothing
    recompiles after warmup, the kernel route is the one asked for, and
    every stream is the reference's greedy stream (ties within the
    compute dtype's noise aside)."""
    import jax.numpy as jnp
    import numpy as np
    from replicatinggpt_tpu.serve import (EngineConfig, ReplayConfig,
                                          run_replay)
    ecfg = EngineConfig(pool_size=8, max_queue=4 * n_requests,
                        paged_kernel=True, decode_window=window,
                        mesh_data=mesh[0], mesh_model=mesh[1])
    trace = _trace(mcfg, ecfg, n_requests, n_new)
    seen = {}

    def inspect(engine, results):
        seen["results"] = {r.id: r for r in results}
        seen["pool_on"] = _device_sets(engine.pool.cache)
        seen["params_on"] = _device_sets(engine.served_params)

    out = {}
    with compiles(out):
        summary = run_replay(
            params, mcfg, ReplayConfig(n_requests=n_requests, greedy=True,
                                       max_new_tokens=n_new, seed=SEED),
            ecfg, trace=trace, inspect=inspect)
    route = summary["kernel_route"]
    prompts = [req.prompt for _, req in trace]
    streams = [np.asarray(seen["results"][req.id].tokens, np.int32)
               for _, req in trace]
    tol = 1e-3 if mcfg.dtype == "float32" else 0.1
    gaps = _reference_gaps(params, mcfg, prompts, streams)
    out.update(
        window=window, mesh=list(mesh), requests=n_requests,
        completed=summary["n_completed"],
        prompt_lens=sorted({len(p) for p in prompts}),
        page=summary["pages"]["page_size"],
        prefill_chunk=ecfg.chunk(mcfg.block_size),
        recompiles_after_warmup=summary["recompiles_after_warmup"],
        kernel_route=route, tokens=summary["generated_tokens"],
        tokens_per_s=summary["aggregate_tokens_per_s"],
        ttft_p50_ms=round(summary["histograms"]["ttft_s"]["p50"] * 1e3, 1),
        worst_gap_to_reference_argmax=round(max(gaps), 4), tol=tol,
        pool_on=seen["pool_on"], params_on=seen["params_on"])
    if generate_parity:
        from replicatinggpt_tpu.sample import GenerateConfig, generate
        gcfg = GenerateConfig(max_new_tokens=n_new, greedy=True)
        picked = [i for i, p in enumerate(prompts)
                  if 1 << (len(p) - 1).bit_length() in PARITY_BUCKETS]
        theirs = {i: np.asarray(generate(
            params, jnp.asarray(prompts[i])[None], mcfg, gcfg))[0]
            for i in picked}
        differ = [i for i in picked
                  if not np.array_equal(streams[i], theirs[i])]
        # a stream that leaves generate()'s must do so at a tie: both
        # have to be greedy streams of the reference
        tie_gaps = _reference_gaps(params, mcfg,
                                   [prompts[i] for i in differ],
                                   [theirs[i] for i in differ])
        out.update(compared_with_generate=len(picked),
                   equal_to_generate=len(picked) - len(differ),
                   diverged_at_a_tie=len(differ),
                   worst_tie_gap=round(max(tie_gaps, default=0.0), 4))
        gaps = gaps + tie_gaps
    out["streams"] = {req.id: s.tolist() for (_, req), s
                      in zip(trace, streams)}
    problems = []
    if summary["n_completed"] != n_requests:
        problems.append("requests left unfinished")
    if summary["recompiles_after_warmup"] != 0:
        problems.append("recompiled after warmup")
    if want_route is not None and (
            route["route"] != "pallas" or route["reasons"]
            or route["decode"] != want_route):
        problems.append(f"kernel route is not pallas/{want_route}")
    if max(gaps) > tol:
        problems.append("a stream is not the reference's greedy stream")
    out["problems"] = problems
    return out


def _say_replay(phase: str, out: dict) -> None:
    say(phase, **{k: v for k, v in out.items() if k != "streams"})
    if out["problems"]:
        raise AssertionError(f"{phase}: {out['problems']}")


def phase_serve(lm=LM, n_requests: int = 24, n_new: int = 16,
                window: int = 8, want_route="pallas") -> None:
    mcfg, params = _serve_setup(lm)
    for w in (1, window):
        _say_replay("serve", _replay(
            mcfg, params, window=w, n_requests=n_requests, n_new=n_new,
            want_route=want_route, generate_parity=True))


def phase_generate(lm=LM, n_tokens: int = 64,
                   want_kernel: bool = True) -> None:
    """`cli generate` (the offline decode stack) on the packed
    per-layer decode kernel at 124M."""
    import jax
    import jax.numpy as jnp
    from replicatinggpt_tpu import cli
    from replicatinggpt_tpu.models import gpt
    from replicatinggpt_tpu.ops import decode_pallas as dp
    argv = lm + ["--decode-cache-layout", "packed"]
    mcfg = _cfg(argv).model
    route = ("packed_decode_attention" if dp.packed_decode_supported(mcfg)
             else "xla")
    out = {"route": route, "tokens": n_tokens}
    if want_kernel:
        params = jax.eval_shape(
            lambda: gpt.init_params(jax.random.PRNGKey(0), mcfg))
        cache = jax.eval_shape(lambda: gpt.init_kv_cache(mcfg, 1))
        text = jax.jit(functools.partial(
            gpt.decode_step, cfg=mcfg, allow_pallas=True)).lower(
            params, jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32), cache).as_text()
        if route != "xla" and "tpu_custom_call" not in text:
            raise AssertionError("decode_step lowered without its kernel")
        out["kernel_in_lowered_step"] = "tpu_custom_call" in text
    # the sample's text is not a result line: keep it off stdout
    with compiles(out), contextlib.redirect_stdout(sys.stderr):
        rc = cli.main(["generate", *argv, "--dp", "1",
                       "--sample-tokens", str(n_tokens),
                       "--top-k", "50", "--seed", str(SEED)])
    if rc != 0:
        raise AssertionError(f"cli generate returned {rc}")
    say("generate", **out)


# ------------------------------------------------------------ four chips

def phase_mesh_train(lm=LM, steps: int = 6, k: int = 3,
                     want_kernel: bool = True, tol: float = 0.05) -> None:
    """The 124M train steps on data=2 x model=2 (shard_map flash) and on
    data=4 FSDP, against one chip at the same global batch and seed."""
    runs = {}
    for name, flags in (("one_chip", ["--dp", "1"]),
                        ("dp2_tp2", ["--dp", "2", "--tp", "2"]),
                        ("dp4_fsdp", ["--dp", "4", "--fsdp"])):
        runs[name] = _train_once(_train_argv(lm, flags, steps, k),
                                 want_kernel=want_kernel)
        say("mesh_train", run=name, **runs[name])
    base = runs["one_chip"]
    problems = []
    for name in ("dp2_tp2", "dp4_fsdp"):
        r = runs[name]
        worst = max(abs(a - b) for a, b in zip(
            r["losses"] + [r["val_loss"]],
            base["losses"] + [base["val_loss"]]))
        say("mesh_train_compare", run=name, against="one_chip",
            worst_abs_loss_diff=round(worst, 4), tol=tol)
        if worst > tol:
            problems.append(f"{name} losses left one chip's by {worst}")
        if len(r["params_on"]) < 4 or len(r["opt_state_on"]) < 4:
            problems.append(f"{name} state is not on four devices")
    if problems:
        raise AssertionError(problems)


def phase_mesh_serve(lm=LM, n_requests: int = 16, n_new: int = 16,
                     window: int = 8, want_route="pallas") -> None:
    """The server at --mesh-shape 2x2 (shard_map-wrapped paged kernel)
    against 1x1 on the same requests."""
    mcfg, params = _serve_setup(lm)
    runs = {}
    for mesh in ((1, 1), (2, 2)):
        runs[mesh] = _replay(mcfg, params, window=window, mesh=mesh,
                             n_requests=n_requests, n_new=n_new,
                             want_route=want_route, generate_parity=False)
        _say_replay("mesh_serve", runs[mesh])
    a, b = runs[(1, 1)]["streams"], runs[(2, 2)]["streams"]
    same = sum(a[i] == b[i] for i in a)
    # both passed the reference check above; a stream that differs
    # between the two left at a tie
    say("mesh_serve_compare", identical_streams=same, of=len(a),
        diverged_at_a_tie=len(a) - same,
        pool_on=runs[(2, 2)]["pool_on"],
        params_on=runs[(2, 2)]["params_on"])
    if len(runs[(2, 2)]["pool_on"]) < 4:
        raise AssertionError("the 2x2 page pool is not on four devices")
    if not runs[(2, 2)]["kernel_route"]["sharded"]:
        raise AssertionError("the 2x2 engine did not run the sharded "
                             "kernel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs the mesh paths only (and what they are "
                         "compared with) on one four-chip host")
    args = ap.parse_args(argv)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    from replicatinggpt_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    _watch_compiles()
    t0 = time.perf_counter()
    info = phase_device(args.chips, cache_dir)
    phase_build()
    if args.chips == 4:
        phase_mesh_train()
        phase_mesh_serve()
    else:
        phase_train()
        phase_serve()
        phase_generate()
    say("total", wall_s=round(time.perf_counter() - t0, 1),
        compile_s=round(_COMPILE["s"], 1), cache_hits=_COMPILE["hits"],
        cache_misses=_COMPILE["misses"])
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
