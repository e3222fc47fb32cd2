#!/usr/bin/env python
"""Benchmark harness: char-GPT train tokens/sec/chip (+ MFU, + generate p50).

Runs the BASELINE.json parity workload (char-GPT: 6L/6H/384C, block 256,
batch 64 — BASELINE.md config 1/2) as jitted bf16 train steps on the
available accelerator and reports steady-state throughput.

vs_baseline is the ratio against the PyTorch-CPU reference path
(replicatinggpt_tpu/reference_torch.py) on this machine — the BASELINE.md
target is >50x ("reach reference loss in <1/50 wall-clock", and step time
dominates wall-clock at fixed iteration count). The CPU measurement is
cached in BENCH_BASELINE_CACHE.json so repeated bench runs don't re-pay it.

Robustness contract (the driver keeps exactly one artifact per round):
- prints exactly ONE JSON line to stdout, ALWAYS — on any failure the line
  carries an "error" field (and the exit code is non-zero) instead of
  silently dying with no output;
- a measurement needs a TPU: a run that finds none emits the error
  artifact and exits non-zero. ``--platform cpu`` asks for a CPU run
  explicitly (correctness and counts only); there is no fallback;
- every artifact names the device it ran on (platform, device_kind,
  device_count);
- one process per chip: this process is the only one that touches the
  backend, except under ``--mode fleet --multiproc``, where the workers
  own the chips and this parent never initializes one;
- a watchdog thread emits the JSON line and exits if the whole run exceeds
  its budget (mid-run device hangs can't swallow the artifact either).

Self-auditing: the JSON line includes an analytic FLOPs model (see
train_flops_per_token) and the resulting MFU against the device's bf16
peak, plus the dispatch/compute split, so the throughput number can be
sanity-checked at a glance.

All narration goes to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


CACHE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "BENCH_BASELINE_CACHE.json")

_EMIT_LOCK = threading.Lock()
_EMITTED = False
# tags merged into every artifact by emit(): the device the run
# measured (device_tags), so no number is ever without its platform
_EMIT_TAGS: dict = {}


def emit(payload: dict) -> None:
    """Print the single JSON artifact line (first caller wins)."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
    if os.environ.get("GRAFT_SANITIZE", "") not in ("", "0"):
        # sanitized runs pay for leak/NaN checks — never comparable to
        # (or mistakable for) a real measurement
        payload = {**payload, "sanitize": True}
    if _EMIT_TAGS:
        payload = {**payload, **_EMIT_TAGS}
    print(json.dumps(payload), flush=True)


def error_payload(metric: str, unit: str, err: str) -> dict:
    return {"metric": metric, "value": 0.0, "unit": unit,
            "vs_baseline": 0.0, "error": err[:500]}


def start_watchdog(seconds: float, metric: str, unit: str) -> None:
    """Emit an error artifact and hard-exit if the run outlives its budget.

    os._exit (not sys.exit) because the typical cause is a thread wedged
    inside a PJRT call that will never return or honor interpreters exits.
    """
    def fire():
        time.sleep(seconds)
        log(f"WATCHDOG: bench exceeded {seconds:.0f}s budget; emitting "
            "error artifact and exiting")
        emit(error_payload(metric, unit,
                           f"watchdog: exceeded {seconds:.0f}s budget "
                           "(device hang?)"))
        sys.stdout.flush()
        os._exit(3)

    t = threading.Thread(target=fire, daemon=True)
    t.start()


def device_tags(platform_flag, dev=None) -> dict:
    """The artifact's device identity, and the no-fallback gate: a
    device that is not a TPU is an error unless ``--platform cpu``
    asked for it. First touch of the backend in this process when
    ``dev`` is None; ``--mode fleet --multiproc`` passes the
    {platform, kind, count} a worker registered with instead (the
    parent holds no chip)."""
    if dev is None:
        import jax
        d = jax.devices()
        dev = {"platform": d[0].platform, "kind": d[0].device_kind,
               "count": len(d)}
    if dev["platform"] != "tpu" and platform_flag != "cpu":
        raise RuntimeError(
            f"no TPU: the backend is {dev['platform']!r} "
            f"({dev['kind']}). A measurement needs the chip; pass "
            f"--platform cpu to ask for a CPU run explicitly")
    return {"platform": dev["platform"], "device_kind": dev["kind"],
            "device_count": dev["count"]}


def train_flops_per_token(mcfg) -> float:
    """Analytic training FLOPs per token (matmul terms only; the standard
    MFU accounting — layernorm/softmax/embedding-gather excluded).

    Per layer the matmul weights are qkv 3d^2 + attn-proj d^2 + mlp 8d^2
    = 12d^2; the lm_head matmul is d*V (counted tied or not — tying shares
    storage, not FLOPs). Forward = 2 FLOPs/param-use; backward = 2x
    forward; attention scores+values add 4dT FLOPs/token/layer forward,
    halved by causal masking.
    """
    L, d, T, V = (mcfg.n_layer, mcfg.n_embd, mcfg.block_size,
                  mcfg.vocab_size)
    fwd_matmul = 2.0 * (12.0 * L * d * d + d * V)
    fwd_attn = 2.0 * L * d * T  # 4dT full, /2 causal
    return 3.0 * (fwd_matmul + fwd_attn)


# Per-chip peaks keyed by the EXACT ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture
# page (197 TFLOP/s bf16, 819 GB/s HBM per chip). A device that is not
# in the table is an error, not a default: add the row, with its
# source, when the repo first runs on another chip.
_PEAK_FLOPS = {"TPU v5 lite": 197e12}     # bf16 dense FLOP/s
_HBM_BW = {"TPU v5 lite": 819e9}          # HBM bytes/s


def _peak(table: dict, what: str, device_kind: str) -> float:
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(
            f"no {what} entry for device kind {device_kind!r} (known: "
            f"{sorted(table)}); add it to bench.py with its "
            f"source") from None


def peak_flops_per_sec(device_kind: str) -> float:
    return _peak(_PEAK_FLOPS, "peak FLOP/s", device_kind)


def hbm_bw_bytes_per_sec(device_kind: str) -> float:
    return _peak(_HBM_BW, "HBM bandwidth", device_kind)


def _baseline_key(mcfg, batch_size: int) -> str:
    return (f"char_gpt_L{mcfg.n_layer}_H{mcfg.n_head}_C{mcfg.n_embd}"
            f"_T{mcfg.block_size}_B{batch_size}")


def torch_cpu_baseline(mcfg, batch_size: int, remeasure: bool) -> float:
    key = _baseline_key(mcfg, batch_size)
    cache = {}
    if os.path.exists(CACHE_PATH):
        try:
            with open(CACHE_PATH) as f:
                cache = json.load(f)
        except (OSError, ValueError):   # unreadable/corrupt cache: remeasure
            cache = {}
    if not remeasure and key in cache:
        log(f"torch-CPU baseline (cached): {cache[key]:,.0f} tok/s")
        return cache[key]
    log("measuring torch-CPU reference baseline (few steps)...")
    import torch

    from replicatinggpt_tpu.reference_torch import measure_train_throughput
    torch.set_num_threads(os.cpu_count() or 8)
    tps = measure_train_throughput(mcfg, batch_size=batch_size, steps=3,
                                   warmup=1)
    cache[key] = tps
    try:
        with open(CACHE_PATH, "w") as f:
            json.dump(cache, f, indent=1)
    except OSError:
        pass
    log(f"torch-CPU baseline: {tps:,.0f} tok/s")
    return tps


def measure_generate_p50(mcfg, tcfg, steps: int = 4,
                         batch_size: int = 1, state=None) -> dict:
    """BASELINE.json config 5: autoregressive generate latency — 1k-token
    sample, p50 tokens/sec — with real device->host fetch per lap.
    ``batch_size`` > 1 measures batched decode (aggregate throughput =
    B * 1000 / p50); pass ``state`` to reuse one model across a sweep."""
    import jax
    import jax.numpy as jnp

    from replicatinggpt_tpu.sample import GenerateConfig, generate
    from replicatinggpt_tpu.train.state import create_train_state
    from replicatinggpt_tpu.utils.profiling import StepTimer

    if state is None:
        state = create_train_state(jax.random.PRNGKey(0), mcfg, tcfg)
    gcfg = GenerateConfig(max_new_tokens=1000, top_k=50)
    prompt = jnp.zeros((batch_size, 1), jnp.int32)
    log(f"generate bench: B={batch_size}, 1000 tokens, top-k 50, "
        f"{mcfg.n_layer}L/{mcfg.n_head}H/{mcfg.n_embd}C")
    jax.device_get(generate(state.params, prompt, mcfg, gcfg))  # warm/compile
    timer = StepTimer()
    timer.start()
    for i in range(steps):
        toks = generate(state.params, prompt, mcfg, gcfg,
                        rng=jax.random.PRNGKey(i))
        timer.lap(toks)
    s = timer.summary(tokens_per_step=gcfg.max_new_tokens * batch_size)
    log(f"generate: p50 {s['p50_s'] * 1e3:.1f} ms/1k-tok, "
        f"{s['tokens_per_sec_per_chip']:,.0f} aggregate tok/s p50")
    # Distinct keys: B=1 is per-stream latency-derived throughput; B>1 is
    # aggregate (B x per-stream) — the same key would make artifacts from
    # the two modes silently incomparable.
    tps_key = ("generate_tokens_per_sec_p50" if batch_size == 1
               else "generate_aggregate_tokens_per_sec_p50")
    return {"generate_1k_p50_s": round(s["p50_s"], 4),
            tps_key: round(s["tokens_per_sec_per_chip"], 1),
            "batch_size": batch_size}


def _decode_byte_floor_us(mcfg, batch: int, device_kind: str,
                          n_params: int):
    """Ideal µs/token for the 1k-token decode workload: every model
    parameter (bf16, the per-segment cast copies XLA hoists out of the
    token scan) plus the LOGICAL valid-prefix KV bytes per step, over
    the device's HBM bandwidth. Logical bytes on purpose: the ratio
    then exposes layout padding (the heads layout's D-minor tile pad)
    as excess, matching the RESULTS.md roofline convention."""
    bw = hbm_bw_bytes_per_sec(device_kind)
    weight_bytes = n_params * 2
    # avg valid-prefix cache read per step over 1k tokens (window refresh
    # caps pos at block_size; itemsize 2 = bf16 cache)
    S = mcfg.block_size
    avg_pos = sum(min(t, S) for t in range(1, 1001)) / 1000
    kv_bytes = 2 * mcfg.n_layer * batch * avg_pos * mcfg.n_embd * 2
    return (weight_bytes + kv_bytes) / bw * 1e6


def bench_decode_sweep(args) -> None:
    """Batched decode: aggregate tok/s vs batch size, one model/state
    reused across the sweep (the RESULTS.md batched-decode table).
    ``--decode-cache-layout`` overrides the KV-cache layout for the
    heads/packed A/B."""
    import dataclasses

    import jax

    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.train.state import create_train_state

    cfg = get_config(args.preset)
    if args.decode_cache_layout:
        cfg = dataclasses.replace(cfg, model=dataclasses.replace(
            cfg.model, decode_cache_layout=args.decode_cache_layout))
        log(f"decode cache layout: {args.decode_cache_layout}")
    state = create_train_state(jax.random.PRNGKey(0), cfg.model, cfg.train)
    rows = {}
    laps = min(args.steps, 8)  # per-lap cost grows with B; 5-8 laps
    dev = jax.devices()[0]
    from replicatinggpt_tpu.models.gpt import param_count
    n_params = param_count(state.params)
    for B in (int(b) for b in args.decode_batch_sizes.split(",")):
        r = measure_generate_p50(cfg.model, cfg.train, steps=laps,
                                 batch_size=B, state=state)
        if dev.platform == "tpu":
            # roofline columns are device metrics: a --platform cpu run
            # carries none (and an unknown TPU kind raises)
            floor = _decode_byte_floor_us(cfg.model, B, dev.device_kind,
                                          n_params)
            r["byte_floor_us_per_tok"] = round(floor, 1)
            r["x_floor"] = round(
                r["generate_1k_p50_s"] * 1e6 / 1000 / floor, 2)
        rows[f"B{B}"] = r
    last = rows[sorted(rows, key=lambda k: int(k[1:]))[-1]]
    emit({
        "metric": "generate_batched_aggregate_tokens_per_sec_p50",
        "value": last.get("generate_aggregate_tokens_per_sec_p50",
                          last.get("generate_tokens_per_sec_p50")),
        "unit": "tokens/sec",
        "vs_baseline": 0.0,  # reference publishes no generation numbers
        "sweep": rows,
    })


def bench_serve(args) -> None:
    """Continuous-batching serving replay (serve/): a seeded Poisson
    trace through the pooled-KV engine; artifact is the aggregate
    decode throughput plus the TTFT/step-latency/occupancy summary and
    the recompiles-after-warmup count (must be 0 at steady state).

    ``--spec`` switches on speculative decoding over a repetitive
    greedy trace (the drafter's favorable regime — the point of the
    artifact is the serving-side multiplier: accept rate and mean
    committed tokens per slot-step, which is 1.0 exactly without
    speculation). ``--draft-model <preset>`` swaps the host-side
    n-gram drafter for a small random-init draft model.

    ``--serve-prefix-trace`` replays the system-prompt traffic shape
    instead (every prompt shares one common prefix) TWICE — radix
    prefix cache on, then off on the same trace — so the artifact's
    TTFT delta is the prefix cache's, not the workload's. Every serve
    artifact carries the paged-pool block (pages_in_use /
    page_utilization / prefix_hit_rate / evictions / cow_copies)."""
    import jax

    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.serve import EngineConfig, ReplayConfig, run_replay
    from replicatinggpt_tpu.train.state import create_train_state

    cfg = get_config(args.preset)
    dev = jax.devices()[0]
    spec_mode = ("model" if args.spec and args.draft_model
                 else "ngram" if args.spec else "off")
    prompt_mode = ("shared_prefix" if args.serve_prefix_trace
                   else "repeat" if args.spec else "random")
    log(f"serve replay: {args.serve_requests} requests @ "
        f"{args.serve_rate}/s, pool {args.serve_pool}, spec {spec_mode}, "
        f"trace {prompt_mode}, model {cfg.model.n_layer}L/"
        f"{cfg.model.n_head}H/{cfg.model.n_embd}C on {dev.device_kind}")
    state = create_train_state(jax.random.PRNGKey(0), cfg.model, cfg.train)
    rcfg = ReplayConfig(n_requests=args.serve_requests,
                        rate=args.serve_rate, seed=0,
                        prompt_len_max=cfg.model.block_size // 2,
                        max_new_tokens=args.serve_max_new_tokens,
                        top_k=50,
                        # the speculative artifact measures the
                        # multiplier where drafting can win: repetitive
                        # prompts, greedy (deterministic accept rule)
                        greedy=bool(args.spec),
                        prompt_mode=prompt_mode,
                        spec=spec_mode, spec_k=args.spec_k)
    draft_params = draft_cfg = None
    if spec_mode == "model":
        from replicatinggpt_tpu.models.gpt import init_params
        from replicatinggpt_tpu.serve import draft_config_from_preset
        draft_cfg = draft_config_from_preset(cfg.model, args.draft_model)
        draft_params = init_params(jax.random.PRNGKey(1), draft_cfg)
        log(f"draft model: {args.draft_model} -> {draft_cfg.n_layer}L/"
            f"{draft_cfg.n_head}H/{draft_cfg.n_embd}C (random init)")
    # detection-only resilience defaults: stall watchdog + speculative
    # auto-disable on (healthy runs pay only the bookkeeping — the
    # robustness overhead this artifact's trajectory tracks), shedding
    # off (it would change the measured workload)
    from replicatinggpt_tpu.faults import DEFAULT_SERVE_RESILIENCE
    from replicatinggpt_tpu.parallel.mesh import resolve_mesh_shape
    mesh_d, mesh_m = resolve_mesh_shape(args.mesh_shape,
                                        len(jax.devices()))
    if mesh_d * mesh_m > 1:
        log(f"serving mesh: {mesh_d}x{mesh_m} (data x model)")
    if args.kv_quant != "none" or args.weight_quant != "none":
        log(f"quantization: kv {args.kv_quant}, weights "
            f"{args.weight_quant}")
    ecfg = EngineConfig(pool_size=args.serve_pool,
                        max_queue=2 * args.serve_requests,
                        page_size=args.serve_page_size,
                        n_pages=args.serve_n_pages,
                        decode_window=args.decode_window,
                        decode_window_auto=args.decode_window_auto,
                        mesh_data=mesh_d, mesh_model=mesh_m,
                        kv_quant=args.kv_quant,
                        weight_quant=args.weight_quant,
                        act_quant=args.act_quant,
                        paged_kernel=args.paged_kernel)
    summary = run_replay(state.params, cfg.model, rcfg, ecfg,
                         draft_params=draft_params, draft_cfg=draft_cfg,
                         resilience=DEFAULT_SERVE_RESILIENCE,
                         trace_out=args.trace_out,
                         metrics_timeline=args.metrics_timeline,
                         metrics_out=args.metrics_out)
    if "artifacts" in summary:
        log(f"observability artifacts: {summary['artifacts']}")
    h = summary["histograms"]
    sp = summary.get("speculative") or {}
    pg = summary["pages"]
    dp = summary.get("dispatch", {})
    dispatch_split: dict = {}
    # spec mode keeps the verify program as the steady-state dispatch
    # (windows only engage while speculation is degraded), so the
    # blocked-vs-amortized A/B is only meaningful without a drafter
    if args.decode_window > 1 and spec_mode == "off":
        # the serve-side dispatch split the train bench has had since
        # BENCH_r03 (77.4 ms blocked vs 12.1 ms/step amortized at k=25):
        # replay the SAME request set at BOTH window sizes and compare
        # host-overhead per decoded token. Both arms run at a
        # saturating arrival rate — the split measures steady-state
        # dispatch amortization. CPU caveat (continuous windows): with
        # the launch-input caching both arms now skip the per-dispatch
        # device_puts that used to dominate this number, and what
        # remains of a CPU "launch" is XLA:CPU executing thunks inline
        # on the dispatching thread — device time proportional to k —
        # so on CPU this ratio can sit near/below 1.0 while the
        # deterministic dispatch-count split (admission_storm block)
        # shows the real amortization; the TPU row carries the
        # wall-clock multiplier
        import dataclasses
        dense = dataclasses.replace(rcfg,
                                    rate=max(rcfg.rate, 10_000.0))
        windowed = run_replay(state.params, cfg.model, dense, ecfg,
                              resilience=DEFAULT_SERVE_RESILIENCE)
        blocked = run_replay(state.params, cfg.model, dense,
                             dataclasses.replace(ecfg, decode_window=1),
                             resilience=DEFAULT_SERVE_RESILIENCE)
        wdp = windowed.get("dispatch", {})
        bdp = blocked.get("dispatch", {})
        amortized = wdp.get("host_dispatch_ms_per_token", 0.0)
        per_tok_blocked = bdp.get("host_dispatch_ms_per_token", 0.0)
        # the headline replay's numbers stay the top-level
        # decode_window_k / decode_dispatch_ms /
        # host_dispatch_ms_per_token keys; this block is the dense A/B
        dispatch_split = {
            "host_ms_per_token": amortized,
            "host_ms_per_token_blocked": per_tok_blocked,
            "host_overhead_speedup": (
                round(per_tok_blocked / amortized, 3)
                if amortized > 0 else 0.0),
            "recompiles_after_warmup_blocked":
                blocked["recompiles_after_warmup"],
        }
        log(f"dispatch split (saturating-rate A/B): host "
            f"{per_tok_blocked:.3f} ms/token blocked (k=1) vs "
            f"{amortized:.3f} ms/token amortized "
            f"(k={args.decode_window}) -> "
            f"{dispatch_split['host_overhead_speedup']}x")
    storm_block: dict = {}
    if args.serve_storm_trace and args.decode_window > 1 \
            and spec_mode == "off":
        # the continuous-window acceptance workload (ISSUE 13): an
        # admission-heavy saturating trace with mixed deadlines and
        # mid-flight cancels, replayed at window k and blocked k=1.
        # Amortization is the DETERMINISTIC dispatch-count split
        # (dispatches per decoded token, blocked over windowed);
        # retention compares it against the same trace with the
        # lifecycle churn stripped — the pre-continuous-windows
        # engine collapses to ~1.0 under the storm by construction.
        from replicatinggpt_tpu.serve.loadgen import (
            AdmissionStormConfig, admission_storm)
        scfg = AdmissionStormConfig(n_requests=args.serve_requests)
        strace, scancels, sdeadlines = admission_storm(cfg.model, scfg)

        def amortization(cancels, deadlines):
            import dataclasses as _dc
            out = {}
            for label, e in (("windowed", ecfg),
                             ("blocked",
                              _dc.replace(ecfg, decode_window=1))):
                s = run_replay(state.params, cfg.model, rcfg, e,
                               resilience=DEFAULT_SERVE_RESILIENCE,
                               trace=[(t, _dc.replace(r))
                                      for t, r in strace],
                               cancels=cancels, deadlines=deadlines)
                c = s["counters"]
                out[label] = (s, c["decode_dispatches"]
                              / max(c["decode_tokens"], 1))
            return out["windowed"], out["blocked"]

        (storm_w, dpt_sw), (_, dpt_sb) = amortization(scancels,
                                                      sdeadlines)
        (idle_w, dpt_iw), (_, dpt_ib) = amortization([], {})
        a_storm = dpt_sb / dpt_sw
        a_idle = dpt_ib / dpt_iw
        storm_block = {
            "n_requests": scfg.n_requests,
            "deadline_frac": scfg.deadline_frac,
            "cancel_frac": scfg.cancel_frac,
            "amortization_storm": round(a_storm, 3),
            "amortization_idle": round(a_idle, 3),
            "retention": (round(a_storm / a_idle, 4) if a_idle else 0.0),
            "window_breaks": storm_w["window_breaks"],
            "recompiles_after_warmup":
                storm_w["recompiles_after_warmup"],
        }
        log(f"admission storm: {a_storm:.2f}x dispatch amortization "
            f"under the storm vs {a_idle:.2f}x idle -> "
            f"{storm_block['retention']:.1%} retained "
            f"(breaks {storm_w['window_breaks']})")
    quant_ab: dict = {}
    if args.quant_ab:
        # bf16-vs-int8 KV at FIXED HBM on the shared-prefix trace
        # (ISSUE 15 acceptance): one byte budget, each arm sized in ITS
        # pages (pages.n_pages_for_hbm) — page count is the admission
        # currency, so the int8 arm admits ~2x the concurrent requests
        # the budget allows the baseline. The budget is deliberately
        # HALF the default pool so pages (not slots) are the binding
        # constraint and the capacity win shows up as queue wait, not
        # just a bigger idle pool. Divergence rides the same block:
        # both arms replay an identical greedy trace through fresh
        # engines and the streams are compared token-for-token.
        import dataclasses
        from replicatinggpt_tpu.serve import Engine
        from replicatinggpt_tpu.serve.pages import (n_pages_for_hbm,
                                                    page_bytes,
                                                    pool_geometry)
        from replicatinggpt_tpu.serve.replay import make_trace
        psz, mp, n_default = pool_geometry(
            cfg.model, args.serve_pool, args.serve_page_size, 0,
            args.serve_n_pages)
        pb_base = page_bytes(cfg.model, psz)
        pb_int8 = page_bytes(cfg.model, psz, "int8")
        hbm = pb_base * max(n_default // 2, mp)
        ab_rcfg = dataclasses.replace(
            rcfg, prompt_mode="shared_prefix", greedy=True, spec="off",
            rate=max(rcfg.rate, 10_000.0))
        arms = {}
        streams = {}
        for label, kvq in (("base", "none"), ("int8", "int8")):
            n_p = max(n_pages_for_hbm(hbm, cfg.model, psz, kvq), mp)
            e = dataclasses.replace(ecfg, kv_quant=kvq, n_pages=n_p,
                                    weight_quant="none")
            arms[label] = (run_replay(state.params, cfg.model, ab_rcfg,
                                      e,
                                      resilience=DEFAULT_SERVE_RESILIENCE),
                           n_p)
            # divergence arm: the SAME greedy request set through a
            # fresh engine, streams compared token-for-token
            eng = Engine(state.params, cfg.model,
                         dataclasses.replace(e, max_queue=4096))
            div_trace = make_trace(cfg.model, dataclasses.replace(
                ab_rcfg, n_requests=min(16, args.serve_requests)))
            for _, r in div_trace:
                eng.submit(dataclasses.replace(r, deadline=None))
            streams[label] = {r.id: list(r.tokens)
                              for r in eng.drain()}
        matches = [streams["base"][rid] == streams["int8"][rid]
                   for rid in streams["base"]]
        sb, n_b = arms["base"]
        si, n_i = arms["int8"]

        def _pick(s):
            h2 = s["histograms"]
            return {
                "queue_wait_p50_ms": round(
                    h2.get("queue_wait_s", {}).get("p50", 0) * 1e3, 2),
                "ttft_p50_ms": round(
                    h2.get("ttft_s", {}).get("p50", 0) * 1e3, 2),
                "prefix_hit_rate": s["pages"]["prefix_hit_rate"],
                "recompiles_after_warmup": s["recompiles_after_warmup"],
            }

        quant_ab = {
            "kv_dtype": "int8",
            "hbm_budget_bytes": hbm,
            "bytes_per_page": {"base": pb_base, "int8": pb_int8},
            "n_pages": {"base": n_b, "int8": n_i},
            "capacity_ratio": round(n_i / n_b, 3),
            "greedy_stream_match_rate": round(
                sum(matches) / len(matches), 3),
            "base": _pick(sb),
            "int8": _pick(si),
        }
        log(f"quant A/B (fixed {hbm / 1e6:.2f} MB KV budget): "
            f"{n_b} pages base vs {n_i} pages int8 "
            f"({quant_ab['capacity_ratio']}x capacity), greedy stream "
            f"match {quant_ab['greedy_stream_match_rate']:.0%}, queue "
            f"wait p50 {quant_ab['base']['queue_wait_p50_ms']} -> "
            f"{quant_ab['int8']['queue_wait_p50_ms']} ms")
    prefix_ab: dict = {}
    if args.serve_prefix_trace:
        # same trace, radix prefix cache OFF: the TTFT delta isolates
        # the prefix cache (prompt lengths, arrivals, sampling all fixed)
        import dataclasses
        off = run_replay(state.params, cfg.model, rcfg,
                         dataclasses.replace(ecfg, prefix_cache=False),
                         draft_params=draft_params, draft_cfg=draft_cfg,
                         resilience=DEFAULT_SERVE_RESILIENCE)
        ttft_on = h.get("ttft_s", {}).get("mean", 0) * 1e3
        ttft_off = (off["histograms"].get("ttft_s", {}).get("mean", 0)
                    * 1e3)
        prefix_ab = {
            "ttft_mean_ms": round(ttft_on, 3),
            "ttft_mean_ms_no_prefix_cache": round(ttft_off, 3),
            "ttft_mean_speedup": (round(ttft_off / ttft_on, 3)
                                  if ttft_on > 0 else 0.0),
            "prefill_tokens": summary["counters"].get("prefill_tokens", 0),
            "prefill_tokens_no_prefix_cache":
                off["counters"].get("prefill_tokens", 0),
        }
        log(f"prefix A/B: TTFT mean {ttft_on:.2f} ms cached vs "
            f"{ttft_off:.2f} ms uncached "
            f"({pg['prefix_hit_tokens']} prefix-hit tokens)")
    log(f"serve: {summary['aggregate_tokens_per_s']} tok/s aggregate, "
        f"TTFT p50 {h.get('ttft_s', {}).get('p50', 0) * 1e3:.1f} ms, "
        f"{summary['recompiles_after_warmup']} recompiles after warmup, "
        f"pages {pg['pages_in_use']}/{pg['n_pages']}, prefix hit rate "
        f"{pg['prefix_hit_rate']}"
        + (f", accept rate {sp['accept_rate']}, "
           f"{sp['mean_tokens_per_step']} tok/slot-step" if sp else ""))
    emit({
        "metric": "serve_replay_aggregate_tokens_per_sec",
        "value": summary["aggregate_tokens_per_s"],
        "unit": "tokens/sec",
        "vs_baseline": 0.0,  # reference has no serving path at all
        "n_requests": summary["n_requests"],
        "n_completed": summary["n_completed"],
        "ttft_p50_ms": round(h.get("ttft_s", {}).get("p50", 0) * 1e3, 2),
        "ttft_p99_ms": round(h.get("ttft_s", {}).get("p99", 0) * 1e3, 2),
        "step_p50_ms": round(summary["step_latency"]["p50_s"] * 1e3, 3),
        "batch_fill_mean": round(
            h.get("batch_fill_ratio", {}).get("mean", 0), 3),
        "recompiles_after_warmup": summary["recompiles_after_warmup"],
        # async-engine dispatch amortization (the BENCH_r03 gap's serve
        # proxy): mean host ms per decode dispatch + the chosen window
        "decode_window_k": dp.get("window_k", 1),
        "decode_dispatch_ms": dp.get("mean_dispatch_ms", 0.0),
        "host_dispatch_ms_per_token": dp.get("host_dispatch_ms_per_token",
                                             0.0),
        # paged KV pool health (serve/pages.py) — the dashboard keys the
        # acceptance criteria name explicitly
        "pages_in_use": pg["pages_in_use"],
        "page_utilization": pg["page_utilization"],
        "page_size": pg["page_size"],
        # serving mesh (ISSUE 12): the EFFECTIVE shape (1x1 when the
        # backend had too few devices), per-chip page capacity, and the
        # aggregate admission currency — n_pages is aggregate, each
        # data-axis chip physically stores pages_per_chip of it
        "mesh_shape": pg["mesh_shape"],
        "pages_per_chip": pg["pages_per_chip"],
        "aggregate_pages": pg["aggregate_pages"],
        "prefix_hit_rate": pg["prefix_hit_rate"],
        "prefix_hit_tokens": pg["prefix_hit_tokens"],
        "evictions": pg["evictions"],
        "cow_copies": pg["cow_copies"],
        # self-healing counters (faults/): nonzero means the measured
        # run was degraded — the number is then not a healthy-path claim
        "recovery": {k: summary["recovery"][k]
                     for k in ("watchdog_stalls", "spec_disables",
                               "spec_reprobes", "shed_requests")},
        # continuous-window health: which host mutations still broke
        # windows in the headline replay (admit/deadline/cancel should
        # be zero — only spec reasons may move), and the autotuned k
        "window_breaks": summary.get("window_breaks", {}),
        # quantization (ISSUE 15): the pool's storage mode + the
        # capacity denominator ride every serve artifact
        "kv_quant": pg["kv_quant"],
        "bytes_per_page": pg["bytes_per_page"],
        # kernel-route decision (ISSUE 20): which step families ran the
        # unified Pallas kernel family vs XLA, with the envelope
        # reasons for any fallback — schema pinned in tests/test_pages
        "kernel_route": summary.get("kernel_route", {}),
        **({"speculative": sp} if sp else {}),
        **({"dispatch_split": dispatch_split} if dispatch_split else {}),
        **({"admission_storm": storm_block} if storm_block else {}),
        **({"prefix_ab": prefix_ab} if prefix_ab else {}),
        **({"quant_ab": quant_ab} if quant_ab else {}),
        # observability artifacts (utils.telemetry): paths + counts of
        # the Perfetto trace / metrics timeline / Prometheus text this
        # run emitted, so the dashboard can link the evidence
        **({"artifacts": summary["artifacts"]}
           if "artifacts" in summary else {}),
    })


def _ttft_ms(results, lcfg, want_long, session_is_long, q=0.99):
    """Percentile TTFT (ms) over the long or short slice of a fleet
    replay's per-request results (request ids are ``s{sid:03d}t{k}``)."""
    import numpy as np
    vals = [r.ttft_s for r in results.values()
            if r.ok and session_is_long(int(r.id[1:4]), lcfg) == want_long]
    if not vals:
        return 0.0
    return round(float(np.quantile(np.asarray(vals), q)) * 1e3, 2)


def bench_fleet_disagg_ab(args, cfg, lcfg, ecfg) -> None:
    """The disaggregation A/B (``--mode fleet --disagg``): the SAME
    mixed long+short session trace through two fleets of equal worker
    count — colocated (every replica prefills and decodes) vs
    disaggregated (one prefill worker feeds N-1 decode workers over
    ``page_transfer``). The claim under test: long prompts monopolize
    colocated batch budget and spike short-prompt TTFT; pulling them
    onto a prefill tier keeps the decode tier's windows dense, so
    short-prompt TTFT p99 drops at identical capacity. The artifact's
    ``disagg_ab`` block carries both arms' short/long TTFT, the
    transfer-path counters + latency, and the token-identity bit
    (greedy streams must match across arms — placement must never
    change results).

    On CPU both arms replay on the fleet's deterministic VIRTUAL step
    clock (loadgen.StepClock, ``virtual_dt``): this box serializes all
    replicas through one device (and CI containers are single-core),
    so wall-clock TTFT here measures compute serialization identically
    in both arms — not placement. Virtual TTFT counts router
    scheduling steps — FIFO slot wait, chunked-prefill progress,
    per-chunk transfer round-trips — which is precisely the structure
    disaggregation changes, and is reproducible bit-for-bit run to
    run. A wall-clock row needs a chip run (not measured)."""
    import dataclasses

    import jax

    from replicatinggpt_tpu.serve import RouterConfig, run_fleet_replay
    from replicatinggpt_tpu.serve.loadgen import session_is_long
    from replicatinggpt_tpu.train.state import create_train_state

    block = cfg.model.block_size
    n = args.fleet_replicas
    if n < 2:
        raise SystemExit("--disagg needs --fleet-replicas >= 2 "
                         "(one prefill tier + at least one decode)")
    # TTFT is a PROMPT-phase metric, so the A/B trace is prefill-heavy
    # by construction: short decode budgets (slots turn over on prompt
    # work, not decode), every 2nd session opening a unique
    # near-block-size prompt — the largest prefill the trace can carry
    max_new = min(lcfg.max_new_tokens, 4)
    user_len = min(lcfg.user_len_max, 4)
    long_len = max(block - lcfg.turns * (user_len + max_new),
                   lcfg.prefix_len + 1)
    lcfg = dataclasses.replace(lcfg, max_new_tokens=max_new,
                               user_len_max=user_len,
                               long_every=2, long_prefix_len=long_len)
    # the two policy knobs that make the A/B measure what it claims:
    # (1) only LONG prompts divert to the prefill tier — the tail
    # threshold sits at half the long prompt, far above any short
    # session's uncached pages; (2) both arms run a deliberately small
    # pool, because the phenomenon under test IS saturation (an
    # unsaturated colocated fleet admits every short instantly and
    # there is nothing for disaggregation to win back)
    min_tail = max(2, (long_len // ecfg.page_size) // 2)
    # a small prefill chunk restores the accelerator's compute ratio on
    # CPU: a real TPU's long-prompt prefill costs ~50x a decode step,
    # but this CPU model's 64-token chunk costs about ONE decode step —
    # chunking at 16 makes a near-block-size prompt many dispatches
    # while shorts stay at 2-3, which is the asymmetry the prefill
    # tier exists to absorb (both arms run the identical config)
    # pool headroom on the PAGE axis only: an in-flight transfer pins
    # the request's full prompt on the decode worker before it owns a
    # slot, so the decode pool needs pages beyond pool_size * max_pages
    # or transfers lose the pool race to admission (sink_refused)
    # the windowed engine (decode_window > 1) paces prefill one chunk
    # per window iteration — prompt length costs router STEPS in
    # proportion, which the k=1 path hides (it prefills a whole prompt
    # inside one step); pool_size=1 makes FIFO slot wait visible
    # the page pool is sized EVICTION-FREE (worst-case every session
    # resident on one replica, plus transfer-pin headroom): the two
    # arms evict in different orders, and under KV quantization an
    # evicted prefix does not recompute bit-identically (the original
    # decode-path rows attended dequantized cache; the recomputed
    # prefill rows attend fresh in-chunk values) — token identity
    # across placements is only a meaningful invariant when neither
    # arm evicts, and slot scarcity (pool_size=1), not page scarcity,
    # is the saturation under test
    max_pages = -(-block // ecfg.page_size)
    pool = 1
    ecfg = dataclasses.replace(ecfg, pool_size=pool, prefill_chunk=16,
                               n_pages=(lcfg.n_sessions + pool + 2)
                               * max_pages,
                               decode_window=2,
                               kv_quant=args.kv_quant)
    # saturating arrivals: every session is queued almost immediately
    # (in virtual time), so TTFT measures queueing structure, not
    # arrival spacing
    lcfg = dataclasses.replace(lcfg, rate=2000.0)
    dt = 0.01                       # one router step = 10 virtual ms

    state = create_train_state(jax.random.PRNGKey(0), cfg.model, cfg.train)

    def arm(tiers, tag):
        rcfg = RouterConfig(n_replicas=n, tiers=tiers,
                            disagg_min_tail=min_tail)
        t0 = time.time()
        s = run_fleet_replay(state.params, cfg.model, lcfg, rcfg, ecfg,
                             virtual_dt=dt, collect_streams=True)
        log(f"{tag}: {s['n_completed']}/{s['n_requests']} turns in "
            f"{time.time() - t0:.1f}s wall, short TTFT p99 "
            f"{_ttft_ms(s['results'], lcfg, False, session_is_long)} "
            f"virtual ms")
        return s

    log(f"disagg A/B: {lcfg.n_sessions} sessions (every 2nd opens "
        f"{long_len}-tok unique prompt), {n} workers each arm")
    colo = arm(None, "colocated")
    dis = arm(("prefill",) + ("decode",) * (n - 1), "disagg")
    identical = colo["streams"] == dis["streams"]

    def side(s):
        return {
            "short_ttft_p50_ms": _ttft_ms(s["results"], lcfg, False,
                                          session_is_long, 0.50),
            "short_ttft_p99_ms": _ttft_ms(s["results"], lcfg, False,
                                          session_is_long),
            "long_ttft_p99_ms": _ttft_ms(s["results"], lcfg, True,
                                         session_is_long),
            "n_completed": s["n_completed"],
            "wall_s": s["wall_s"],
            "recompiles_after_warmup": s["recompiles_after_warmup"],
        }

    rc = dis["router"]
    colo_p99 = _ttft_ms(colo["results"], lcfg, False, session_is_long)
    dis_p99 = _ttft_ms(dis["results"], lcfg, False, session_is_long)
    log(f"disagg A/B: short TTFT p99 {colo_p99} ms colocated -> "
        f"{dis_p99} ms disagg, tokens_identical={identical}, "
        f"{rc.get('fleet_transfers', 0)} transfers "
        f"({rc.get('fleet_transfer_bytes', 0)} B)")
    emit({
        "metric": "fleet_disagg_short_ttft_p99_ms",
        "value": dis_p99,
        "unit": "virtual_ms",
        "vs_baseline": colo_p99,
        "disagg_ab": {
            "clock": f"virtual-step (dt={dt * 1e3:g} ms/router-step)",
            "workers_per_arm": n,
            "kv_quant": ecfg.kv_quant,
            "tiers": {"prefill": 1, "decode": n - 1},
            "trace": {"n_sessions": lcfg.n_sessions,
                      "turns": lcfg.turns,
                      "long_every": lcfg.long_every,
                      "long_prefix_len": long_len},
            "colocated": side(colo),
            "disagg": {
                **side(dis),
                "disagg_prefills": rc.get("fleet_disagg_prefills", 0),
                "shortcircuits":
                    rc.get("fleet_disagg_shortcircuits", 0),
                "fallbacks": rc.get("fleet_disagg_fallbacks", 0),
                "transfers": rc.get("fleet_transfers", 0),
                "transfer_pages": rc.get("fleet_transfer_pages", 0),
                "transfer_bytes": rc.get("fleet_transfer_bytes", 0),
                "transfer_failures":
                    rc.get("fleet_transfer_failures", 0),
                "transfer_p99_ms": round(
                    dis["transfer_s"].get("p99", 0) * 1e3, 3),
            },
            "tokens_identical": identical,
            "short_ttft_p99_improves": dis_p99 < colo_p99,
        },
    })


def bench_fleet(args) -> None:
    """Fleet serving replay (serve/router.py + serve/loadgen.py):
    multi-turn session traffic through N engine replicas behind the
    prefix-affinity router, in wall-clock time. The artifact is the
    fleet's aggregate decode throughput plus the blocks the fleet
    acceptance criteria key on: per-replica occupancy and pages,
    requeue/re-route counters, the fleet TTFT distribution, and the
    aggregate prefix-hit rate (affinity keeps it near a single
    replica's on the same workload).

    ``--fleet-kill-at N`` injects a deterministic ``replica_kill`` of
    replica 0 at router step N mid-run (faults/fleet.py): the artifact
    then also demonstrates the requeue path — every in-flight request
    finishes via the crash journal, and the run is tagged
    ``chaos: replica_kill``.

    ``--multiproc`` runs the replicas as real worker PROCESSES
    (serve-worker + faults/procsup.py supervisor) registering over
    RPC, each with a PRIVATE journal dir: the artifact gains
    per-worker pid/restart counts and the requeue-latency
    distribution, and ``--fleet-kill-at`` becomes a REAL ``SIGKILL``
    of worker 0's process (``proc_kill``) — recovery is supervised
    restart + journal replay, and the completed turn count still has
    to come out whole. ``--fleet-host-loss`` upgrades the kill to
    ``host_loss`` (SIGKILL + the worker's journal/workdir deleted):
    recovery is then the ROUTER's own request ledger, nothing on the
    worker's filesystem survives by construction.

    ``--fleet-load-step`` is the autoscaler preset: ONE worker starts,
    session arrivals double mid-run then halve
    (``SessionLoadConfig.load_step``), and the supervisor's autoscaler
    spawns/drains workers from the router's offered-load gauges up to
    ``--fleet-replicas``. The artifact emits scale-up/scale-down
    counts, peak/final worker counts, and the zero-drop verification
    (completed == submitted)."""
    import jax

    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.faults import Fault, FaultPlan, installed
    from replicatinggpt_tpu.faults.fleet import (FLEET_STEP,
                                                 KIND_HOST_LOSS,
                                                 KIND_PROC_KILL,
                                                 KIND_REPLICA_KILL)
    from replicatinggpt_tpu.serve import (EngineConfig, RouterConfig,
                                          SessionLoadConfig,
                                          run_fleet_replay)
    from replicatinggpt_tpu.train.state import create_train_state

    cfg = get_config(args.preset)
    multiproc = args.multiproc or args.fleet_load_step
    # one process per chip: under --multiproc the workers own the
    # chips and this parent never initializes a backend — the device
    # line comes from the workers' registration (below)
    dev_kind = ("worker-owned" if multiproc
                else jax.devices()[0].device_kind)
    block = cfg.model.block_size
    # size turns to the model's context: prefix + turns*(user+gen) must
    # fit block_size with headroom
    prefix_len = min(args.fleet_prefix_len, block // 4)
    max_new = min(args.serve_max_new_tokens,
                  max((block - prefix_len) // (2 * args.fleet_turns), 1))
    user_len = max(min(max_new // 2, 8), 1)
    if args.fleet_host_loss and not multiproc:
        raise SystemExit("--fleet-host-loss requires --multiproc "
                         "(host loss is a real SIGKILL + workdir "
                         "deletion of a worker PROCESS; the "
                         "in-process fleet has no host to lose)")
    if getattr(args, "net_chaos", False) and not multiproc:
        raise SystemExit("--net-chaos requires --multiproc (netchaos "
                         "faults land on the fleet RPC wire; the "
                         "in-process fleet has no wire to hurt)")
    lcfg = SessionLoadConfig(
        n_sessions=args.fleet_sessions, turns=args.fleet_turns,
        n_prefix_groups=args.fleet_prefix_groups, prefix_len=prefix_len,
        user_len_min=1, user_len_max=user_len, max_new_tokens=max_new,
        rate=args.serve_rate, greedy=True, seed=0,
        load_step=args.fleet_load_step)
    rcfg = RouterConfig(n_replicas=args.fleet_replicas,
                        journal_dir=args.fleet_journal_dir or None)
    # default the page size so the shared prefix spans >= 2 full pages
    # (radix sharing works on whole pages; a prefix shorter than one
    # page would make the artifact's hit-rate block structurally zero)
    page_size = args.serve_page_size or max(2, min(16, prefix_len // 2))
    ecfg = EngineConfig(pool_size=args.serve_pool,
                        max_queue=4 * args.fleet_sessions,
                        page_size=page_size,
                        n_pages=args.serve_n_pages)
    if getattr(args, "disagg", False):
        bench_fleet_disagg_ab(args, cfg, lcfg, ecfg)
        return
    n_initial = 1 if args.fleet_load_step else rcfg.n_replicas
    log(f"fleet replay: {lcfg.n_sessions} sessions x {lcfg.turns} turns "
        f"@ {lcfg.rate}/s{' (load-step x2 then /2)' if lcfg.load_step else ''} "
        f"over {n_initial} "
        f"{'worker process' if multiproc else 'replica'}(s)"
        f"{f' (autoscale <= {rcfg.n_replicas})' if args.fleet_load_step else ''} "
        f"(pool {ecfg.pool_size} each), prefix {prefix_len} tok x "
        f"{lcfg.n_prefix_groups} groups, model {cfg.model.n_layer}L/"
        f"{cfg.model.n_head}H/{cfg.model.n_embd}C on {dev_kind}")
    import contextlib
    import tempfile
    plan_ctx = contextlib.nullcontext()
    chaos_kind = None
    chaos_faults = []
    if args.fleet_kill_at >= 0:
        # in-process: simulated replica_kill; multiproc: a REAL SIGKILL
        # of worker 0's OS process through the supervisor —
        # --fleet-host-loss additionally deletes its journal/workdir
        if not multiproc:
            chaos_kind = KIND_REPLICA_KILL
        elif args.fleet_host_loss:
            chaos_kind = KIND_HOST_LOSS
        else:
            chaos_kind = KIND_PROC_KILL
        chaos_faults.append(Fault(
            site=FLEET_STEP, kind=chaos_kind, at=args.fleet_kill_at,
            arg=0))
    if getattr(args, "net_chaos", False):
        # the wire-fault ladder, fleet-wide spellings: duplicated and
        # reordered submit frames (answered from the workers' reply
        # caches — rpc_dup_suppressed must account for every one),
        # delayed and dropped step frames (the ack/redelivery protocol
        # absorbs the losses), and a 3-call one-way partition (the
        # maybe-executed case: requests execute, responses vanish)
        from replicatinggpt_tpu.faults.netchaos import (KIND_NET_DELAY,
                                                        KIND_NET_DROP,
                                                        KIND_NET_DUP,
                                                        KIND_NET_PARTITION,
                                                        KIND_NET_REORDER,
                                                        net_site)
        chaos_faults += [
            Fault(site=net_site("*", "*", "submit"), kind=KIND_NET_DUP,
                  at=1, times=2),
            Fault(site=net_site("*", "*", "submit"),
                  kind=KIND_NET_REORDER, at=4),
            Fault(site=net_site("*", "*", "step"), kind=KIND_NET_DELAY,
                  at=10, times=2, arg=0.01),
            Fault(site=net_site("*", "*", "step"), kind=KIND_NET_DROP,
                  at=25),
            Fault(site=net_site("*", "*", "step"),
                  kind=KIND_NET_PARTITION, at=40, times=3, arg2=1),
        ]
        chaos_kind = ("net_chaos" if chaos_kind is None
                      else f"{chaos_kind}+net_chaos")
    if chaos_faults:
        plan_ctx = installed(FaultPlan(*chaos_faults))
    workers = None
    scale = None
    with tempfile.TemporaryDirectory() as td:
        import dataclasses
        if rcfg.journal_dir is None:
            # requeue-after-kill needs journals; default them to a temp
            # dir so the chaos arm always has the recovery path
            rcfg = dataclasses.replace(rcfg, journal_dir=td)
        if multiproc:
            from replicatinggpt_tpu.faults.procsup import (
                AutoscaleConfig, SupervisorConfig, make_worker_specs,
                spawn_fleet, worker_spec_factory)
            # the router's own ledger: host_loss recovery reads no
            # worker filesystem
            rcfg = dataclasses.replace(
                rcfg, ledger_path=os.path.join(rcfg.journal_dir,
                                               "router_ledger.jsonl"))
            config_args = ["--preset", args.preset]
            engine_args = ["--pool-size", str(ecfg.pool_size),
                           "--max-queue", str(ecfg.max_queue),
                           "--page-size", str(ecfg.page_size),
                           "--n-pages", str(ecfg.n_pages)]
            # --platform cpu is an explicit ask; it reaches the
            # workers (which own the backend) through their env
            wenv = ({"JAX_PLATFORMS": "cpu"}
                    if getattr(args, "platform", None) == "cpu" else None)
            specs = make_worker_specs(n_initial, rcfg.journal_dir,
                                      config_args, engine_args, env=wenv)
            autoscale = spec_factory = None
            if args.fleet_load_step:
                autoscale = AutoscaleConfig(
                    min_workers=1,
                    max_workers=max(rcfg.n_replicas, 2),
                    up_backlog_per_worker=1.0, up_patience=2,
                    down_active_per_worker=2.0, down_patience=12,
                    cooldown_ticks=8)
                spec_factory = worker_spec_factory(
                    rcfg.journal_dir, config_args, engine_args, env=wenv)
            log(f"spawning {n_initial} worker process(es) "
                f"(private dirs under {rcfg.journal_dir}; RPC "
                f"registration)")
            tel = None
            if args.trace_out:
                # the pre-built-router replay exports the ROUTER's own
                # recorder — it must exist before spawn_fleet wires it
                from replicatinggpt_tpu.utils.telemetry import Telemetry
                tel = Telemetry()
            router, sup = spawn_fleet(specs, rcfg,
                                      SupervisorConfig(backoff_s=0.2),
                                      telemetry=tel,
                                      autoscale=autoscale,
                                      spec_factory=spec_factory)
            try:
                # the workers' registered device is this artifact's
                # device line — and the no-fallback gate (a fleet that
                # came up on CPU unasked fails here)
                wdev = next((h.device for h in sup.handles if h.device),
                            None)
                if wdev is not None:
                    _EMIT_TAGS.update(device_tags(
                        getattr(args, "platform", None), wdev))
                with plan_ctx:
                    summary = run_fleet_replay(
                        None, cfg.model, lcfg,
                        router=router, supervisor=sup,
                        trace_out=args.trace_out,
                        metrics_timeline=args.metrics_timeline,
                        metrics_out=args.metrics_out)
                workers = [{
                    "worker": h.spec.idx, "pid": h.pid, "gen": h.gen,
                    "restarts": h.restarts,
                    "crash_restarts": h.crash_restarts,
                    "state": h.state,
                } for h in sup.handles]
                if args.fleet_load_step:
                    from replicatinggpt_tpu.faults.procsup import RUNNING
                    # let the post-trace lull land: the scale-DOWN
                    # decision needs its patience window of idle ticks
                    # after the last session finished
                    lull_deadline = time.time() + 30.0
                    while (sup.scale_downs == 0 and sup.scale_ups > 0
                           and time.time() < lull_deadline):
                        router.step()
                        sup.tick()
                        time.sleep(0.01)
                    scale = {
                        "scale_ups": sup.scale_ups,
                        "scale_downs": sup.scale_downs,
                        "workers_peak": sup.peak_workers,
                        "workers_final": sum(
                            h.state == RUNNING for h in sup.handles),
                        "zero_drop": (summary["n_completed"]
                                      == summary["n_requests"]),
                    }
            finally:
                sup.stop_all()
                router.close()
                if tel is not None:
                    tel.close()
        else:
            state = create_train_state(jax.random.PRNGKey(0),
                                       cfg.model, cfg.train)
            with plan_ctx:
                summary = run_fleet_replay(
                    state.params, cfg.model, lcfg, rcfg, ecfg,
                    trace_out=args.trace_out,
                    metrics_timeline=args.metrics_timeline,
                    metrics_out=args.metrics_out)
    ttft = summary["fleet_ttft_s"]
    requeue_lat = summary["requeue_latency_s"]
    agg = (summary["generated_tokens"] / summary["wall_s"]
           if summary["wall_s"] > 0 else 0.0)
    log(f"fleet: {summary['n_completed']}/{summary['n_requests']} "
        f"turns completed, {round(agg, 1)} tok/s aggregate, fleet TTFT "
        f"p50 {ttft.get('p50', 0) * 1e3:.1f} ms, prefix hit rate "
        f"{summary['aggregate_prefix_hit_rate']}, requeued "
        f"{summary['router'].get('fleet_requeued_requests', 0)}, "
        f"{summary['recompiles_after_warmup']} recompiles after warmup")
    if scale is not None:
        log(f"autoscale: {scale['scale_ups']} up / "
            f"{scale['scale_downs']} down, peak "
            f"{scale['workers_peak']} workers, final "
            f"{scale['workers_final']}, zero_drop={scale['zero_drop']}")
    emit({
        "metric": "fleet_replay_aggregate_tokens_per_sec",
        "value": round(agg, 1),
        "unit": "tokens/sec",
        "vs_baseline": 0.0,      # reference has no serving path at all
        "n_replicas": summary["n_replicas"],
        "n_alive": summary["n_alive"],
        "n_sessions": summary["n_sessions"],
        "turns_per_session": summary["turns_per_session"],
        "n_requests": summary["n_requests"],
        "n_completed": summary["n_completed"],
        "fleet_ttft_p50_ms": round(ttft.get("p50", 0) * 1e3, 2),
        "fleet_ttft_p99_ms": round(ttft.get("p99", 0) * 1e3, 2),
        "requeue_latency_p50_ms": round(
            requeue_lat.get("p50", 0) * 1e3, 2),
        "requeue_latency_p99_ms": round(
            requeue_lat.get("p99", 0) * 1e3, 2),
        "aggregate_prefix_hit_rate":
            summary["aggregate_prefix_hit_rate"],
        "recompiles_after_warmup": summary["recompiles_after_warmup"],
        # the fleet acceptance blocks: per-replica occupancy + pages,
        # and the router's requeue/health counters
        "router": summary["router"],
        "replicas": [{
            "replica": r["health"]["replica"],
            "alive": r["health"]["alive"],
            "occupancy_mean": r["occupancy_mean"],
            "n_steps": r["n_steps"],
            "pages_in_use": r.get("pages", {}).get("pages_in_use", 0),
            "page_utilization": r.get("pages", {})
            .get("page_utilization", 0.0),
            "prefix_hit_rate": r.get("pages", {})
            .get("prefix_hit_rate", 0.0),
            "finished": r["finished"],
        } for r in summary["replicas"]],
        **({"multiproc": True, "workers": workers}
           if multiproc else {}),
        **({"chaos": chaos_kind, "kill_at": args.fleet_kill_at}
           if chaos_kind is not None else {}),
        **({"load_step": True, **scale} if scale is not None else {}),
        **({"artifacts": summary["artifacts"]}
           if "artifacts" in summary else {}),
    })


def bench_generate(args) -> None:
    import jax

    from replicatinggpt_tpu.config import get_config

    cfg = get_config(args.preset)
    jax.devices()
    gen = measure_generate_p50(cfg.model, cfg.train, steps=args.steps)
    emit({
        "metric": "generate_1k_tokens_per_sec_p50",
        "value": gen["generate_tokens_per_sec_p50"],
        "unit": "tokens/sec",
        "vs_baseline": 0.0,  # reference publishes no generation numbers
    })


def bench_longctx(args) -> None:
    """Long-context single-chip training: one end-to-end train step
    (embeddings, K/V-streaming flash attention with in-kernel dropout,
    remat, loss, AdamW) at --longctx-t tokens, batch 1. Proves the
    sequence-length story past the reference's block_size cap
    (GPT1.py:106, GPT-2.py:109) on real hardware, not just the kernel
    in isolation."""
    import jax
    import numpy as np

    from replicatinggpt_tpu.config import ModelConfig, TrainConfig
    from replicatinggpt_tpu.train.state import create_train_state
    from replicatinggpt_tpu.train.steps import make_train_step

    T = args.longctx_t
    mcfg = ModelConfig(vocab_size=256, block_size=T, n_layer=4, n_head=4,
                       n_embd=256, dropout=0.1, attn_dropout=0.1,
                       dtype="bfloat16", remat=True, attention_impl="auto")
    tcfg = TrainConfig(batch_size=1, lr=1e-3)
    dev = jax.devices()[0]
    log(f"longctx: T={T}, 4L/4H/256C bf16 remat, dropout 0.1, "
        f"{dev.device_kind}")
    state = create_train_state(jax.random.PRNGKey(0), mcfg, tcfg)
    step = make_train_step(mcfg, tcfg)
    toks = np.random.default_rng(0).integers(0, 256, (1, T + 1),
                                             dtype=np.int32)
    batch = (toks[:, :-1], toks[:, 1:])  # next-token targets, as training
    t0 = time.perf_counter()
    state, m = step(state, batch)
    loss = float(jax.device_get(m["loss"]))
    log(f"compile+first step {time.perf_counter() - t0:.0f}s, loss {loss:.3f}")
    assert np.isfinite(loss)
    t0 = time.perf_counter()
    n = 3
    for _ in range(n):
        state, m = step(state, batch)
    loss = float(jax.device_get(m["loss"]))  # blocks the timer; end-of-run
    dt = (time.perf_counter() - t0) / n
    emit({
        "metric": f"longctx_t{T}_train_tokens_per_sec_per_chip",
        "value": round(T / dt, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": 0.0,  # reference hard-caps T at 256/1024
        "step_ms": round(dt * 1e3, 1),
        "final_loss": round(loss, 4),
    })


def _repeat_median(fn, *, repeats: int, inner: int) -> dict:
    """Run ``fn`` (one timed lap = ``inner`` dispatched iterations ending
    in a real device fetch) ``repeats`` times and report median + spread.

    Host-clock timings of short kernels are noisy; medians over >= 5
    repeats with the spread attached are the defensibility floor for
    any perf claim."""
    import time
    laps = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        laps.append((time.perf_counter() - t0) / inner * 1e3)
    laps.sort()
    return {
        "median_ms": round(laps[len(laps) // 2], 4),
        "min_ms": round(laps[0], 4),
        "max_ms": round(laps[-1], 4),
        "spread_pct": round((laps[-1] - laps[0]) / laps[len(laps) // 2]
                            * 100, 1),
        "repeats": repeats,
    }


def bench_kernel(args) -> None:
    """Kernel-level attention microbench with a repeat-median protocol:
    fwd+bwd through the packed family (char-GPT shapes) and the unpacked
    resident family (124M-ish shapes), each as median over --repeats
    laps with min/max spread. Every kernel perf row added to
    benchmarks/RESULTS.md should come from this mode."""
    import jax
    import jax.numpy as jnp

    from replicatinggpt_tpu.ops.flash_pallas import (
        packed_supported, pallas_flash_attention,
        pallas_flash_attention_packed)

    repeats, inner = max(args.repeats, 1), max(args.kernel_inner, 1)
    results = {}

    def fwd_bwd_lap(grad_fn, x):
        def lap():
            for _ in range(inner):
                l, _ = grad_fn(x)
            jax.device_get(l)
        return lap

    # packed family at char-GPT shapes
    B, T, H, D = 64, 256, 6, 64
    C = H * D
    if packed_supported(T, C, H, 2):
        qkv = jax.random.normal(jax.random.PRNGKey(0), (B, T, 3 * C),
                                jnp.bfloat16)
        g = jax.jit(jax.value_and_grad(lambda q: jnp.sum(
            pallas_flash_attention_packed(q, H).astype(jnp.float32) ** 2)))
        jax.device_get(g(qkv)[0])  # compile + warm
        results["packed_char_B64_T256_H6_D64"] = _repeat_median(
            fwd_bwd_lap(g, qkv), repeats=repeats, inner=inner)
        log(f"packed char shapes: {results['packed_char_B64_T256_H6_D64']}")

    # unpacked resident family at the round-2 noise workload
    BH, T2, D2 = 192, 1024, 64
    qkv2 = [jax.random.normal(jax.random.PRNGKey(i), (BH // 6, 6, T2, D2),
                              jnp.bfloat16) for i in range(3)]
    g2 = jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(
        pallas_flash_attention(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))
    jax.device_get(g2(*qkv2)[0])
    results["unpacked_BH192_T1024_D64"] = _repeat_median(
        fwd_bwd_lap(lambda x: g2(*x), qkv2), repeats=repeats, inner=inner)
    log(f"unpacked 124M-ish shapes: {results['unpacked_BH192_T1024_D64']}")

    key = ("packed_char_B64_T256_H6_D64"
           if "packed_char_B64_T256_H6_D64" in results
           else "unpacked_BH192_T1024_D64")
    emit({
        "metric": "flash_kernel_fwdbwd_median_ms",
        "value": results[key]["median_ms"],
        "unit": "ms",
        "vs_baseline": 0.0,  # reference has no kernel-level numbers
        "configs": results,
    })


def bench_train(args) -> None:
    import jax
    import numpy as np

    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.data.dataset import TokenDataset, load_corpus
    from replicatinggpt_tpu.data.loader import RandomBatcher, prefetch
    from replicatinggpt_tpu.tokenizers import get_tokenizer
    from replicatinggpt_tpu.train.state import create_train_state
    from replicatinggpt_tpu.train.steps import (make_train_scan,
                                                make_train_step)

    cfg = get_config(args.preset)
    mcfg, tcfg = cfg.model, cfg.train
    if args.loss_chunk is not None:
        import dataclasses
        mcfg = dataclasses.replace(mcfg, loss_chunk=args.loss_chunk)
        log(f"loss_chunk: {args.loss_chunk}")
    B, T = args.batch_size, mcfg.block_size
    dev = jax.devices()[0]
    log(f"benchmark device: {dev.platform} ({dev.device_kind}), "
        f"model {mcfg.n_layer}L/{mcfg.n_head}H/{mcfg.n_embd}C "
        f"T={T} B={B} dtype={mcfg.dtype}")

    # real input pipeline: tokenized Tiny Shakespeare, random windows
    text = load_corpus(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    cfg.dataset))
    tok = get_tokenizer(cfg.tokenizer, corpus_text=text)
    ds = TokenDataset.from_text(text, tok, tcfg.val_fraction)
    batcher = RandomBatcher(ds.train, B, T, seed=tcfg.seed)

    state = create_train_state(jax.random.PRNGKey(tcfg.seed), mcfg, tcfg)
    k = max(args.steps_per_dispatch, 1)
    # narrow transfer dtype: token ids fit uint8/uint16 for every preset
    # vocab; 2-4x less H2D traffic, widened to int32 on device inside
    # the jitted step (steps.loss_fn)
    wire = (np.uint8 if mcfg.vocab_size <= 0xff
            else np.uint16 if mcfg.vocab_size <= 0xffff else np.int32)
    if k > 1:
        run = make_train_scan(mcfg, tcfg, k)
        def stacked():
            xs, ys = zip(*(batcher.next_batch() for _ in range(k)))
            return np.stack(xs).astype(wire), np.stack(ys).astype(wire)
        batches = prefetch(iter(stacked, None), depth=2)
    else:
        run = make_train_step(mcfg, tcfg)
        batches = prefetch(iter(batcher), depth=2)
    # round the requested counts UP to whole dispatches and report what
    # actually runs (tps is computed over the actual count either way)
    n_dispatch = -(-args.steps // k)
    n_warmup = -(-args.warmup // k) if args.warmup > 0 else 0
    if (n_dispatch * k, n_warmup * k) != (args.steps, args.warmup):
        log(f"note: measuring {n_dispatch * k} steps / warming up "
            f"{n_warmup * k} (rounded up to whole {k}-step dispatches)")

    log(f"compiling... ({k} steps/dispatch)")
    t0 = time.perf_counter()
    warm_metrics = None
    for _ in range(n_warmup):
        state, warm_metrics = run(state, next(batches))
    if warm_metrics is not None:
        # blocking on the LAST dispatch blocks on the whole warmup
        # queue (device execution is in-order)
        jax.block_until_ready(warm_metrics["loss"])
    log(f"warmup done in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    for _ in range(n_dispatch):
        state, metrics = run(state, next(batches))
    loss = float(np.asarray(jax.device_get(metrics["loss"])).ravel()[-1])
    dt = time.perf_counter() - t0
    tps = B * T * n_dispatch * k / dt
    step_ms = dt / (n_dispatch * k) * 1e3
    log(f"{n_dispatch * k} steps in {dt:.2f}s -> {tps:,.0f} tok/s/chip, "
        f"loss {loss:.4f}")
    assert np.isfinite(loss), f"non-finite loss {loss}"

    # dispatch/compute split: a few single-step dispatches, each blocked by
    # a real loss fetch, give per-step latency with full host round-trip;
    # the scan number above amortizes it over k steps
    # (a phase that fails fails the run: a partial artifact would
    # read as a healthy one)
    extra: dict = {}
    single = make_train_step(mcfg, tcfg)
    xb, yb = batcher.next_batch()
    b1 = (xb.astype(wire), yb.astype(wire))
    state2, m2 = single(state, b1)
    jax.block_until_ready(m2["loss"])  # compile + warm
    t1 = time.perf_counter()
    n1 = 3
    for _ in range(n1):
        state2, m2 = single(state2, b1)
        jax.block_until_ready(m2["loss"])
    blocked_ms = (time.perf_counter() - t1) / n1 * 1e3
    extra["blocked_step_ms"] = round(blocked_ms, 2)
    extra["dispatch_overhead_ms"] = round(max(blocked_ms - step_ms, 0.0), 2)
    log(f"dispatch split: {step_ms:.2f} ms/step amortized (k={k}) vs "
        f"{blocked_ms:.2f} ms blocked single-step")

    if not args.no_generate:
        extra.update(measure_generate_p50(mcfg, tcfg))

    if args.skip_baseline:
        base = 0.0
        if os.path.exists(CACHE_PATH):
            try:
                with open(CACHE_PATH) as f:
                    base = json.load(f).get(_baseline_key(mcfg, B), 0.0)
            except (OSError, ValueError):   # no cache: no baseline column
                base = 0.0
    else:
        base = torch_cpu_baseline(mcfg, B, args.remeasure_baseline)

    flops_tok = train_flops_per_token(mcfg)
    # MFU is a device metric: a --platform cpu run reports none (and an
    # unknown TPU kind raises rather than assuming a peak)
    mfu = None
    if dev.platform == "tpu":
        peak = peak_flops_per_sec(dev.device_kind)
        mfu = tps * flops_tok / peak
        log(f"MFU: {mfu * 100:.1f}% of {peak / 1e12:.0f} TF/s bf16 peak "
            f"({flops_tok / 1e6:.2f} MFLOPs/token)")

    emit({
        "metric": "char_gpt_train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tps / base, 2) if base > 0 else 0.0,
        "step_ms": round(step_ms, 3),
        "steps_per_dispatch": k,
        "final_loss": round(loss, 4),
        "train_flops_per_token": round(flops_tok),
        "mfu": round(mfu, 4) if mfu is not None else None,
        # recovery counters (faults/supervise + checkpoint integrity):
        # the bench loop runs unsupervised with no checkpointing, so a
        # healthy round reports zeros — the keys exist so the BENCH
        # trajectory can see a round that was NOT healthy (a non-finite
        # loss now raises instead of silently finishing)
        "recovery": {"rollbacks": 0, "data_skips": 0, "ckpt_fallbacks": 0},
        **extra,
    })


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="char-gpt")
    p.add_argument("--mode", default="train",
                   choices=["train", "generate", "longctx", "kernel",
                            "decode", "serve", "fleet"])
    p.add_argument("--fleet-replicas", type=int, default=2,
                   help="--mode fleet: engine replicas behind the "
                        "prefix-affinity router")
    p.add_argument("--fleet-sessions", type=int, default=24,
                   help="--mode fleet: multi-turn sessions in the "
                        "load-generator trace")
    p.add_argument("--fleet-turns", type=int, default=3,
                   help="--mode fleet: turns per session (each turn "
                        "re-enters with the whole history — the "
                        "prefix-cache / affinity traffic shape)")
    p.add_argument("--fleet-prefix-groups", type=int, default=3,
                   help="--mode fleet: distinct shared system prefixes")
    p.add_argument("--fleet-prefix-len", type=int, default=32,
                   help="--mode fleet: shared-prefix length in tokens "
                        "(clamped to block_size // 4)")
    p.add_argument("--fleet-kill-at", type=int, default=-1,
                   help="--mode fleet: inject replica_kill of replica 0 "
                        "at this router step (-1 = no chaos); the "
                        "journal-requeue path then runs inside the "
                        "measured replay. With --multiproc this is a "
                        "REAL SIGKILL of worker 0's process")
    p.add_argument("--multiproc", action="store_true",
                   help="--mode fleet: run the replicas as real worker "
                        "PROCESSES (serve-worker subprocesses over "
                        "serve/rpc.py under the faults/procsup.py "
                        "supervisor, RPC registration, private journal "
                        "dirs); the artifact gains per-worker "
                        "pid/restart counts and requeue latency")
    p.add_argument("--net-chaos", action="store_true",
                   help="--mode fleet --multiproc: install the network "
                        "fault ladder (faults/netchaos.py) on the "
                        "fleet RPC wire mid-run — duplicated and "
                        "reordered submit frames, delayed/dropped "
                        "step frames, a one-way partition — and tag "
                        "the artifact net_chaos; the router's "
                        "idempotency keys, reply caches and "
                        "ack/redelivery must absorb all of it "
                        "(rpc_dup_suppressed et al. land in the "
                        "artifact's router block)")
    p.add_argument("--fleet-host-loss", action="store_true",
                   help="--mode fleet --multiproc: upgrade "
                        "--fleet-kill-at to host_loss chaos (SIGKILL "
                        "+ the worker's journal/workdir DELETED) — "
                        "recovery must come from the router's own "
                        "request ledger, nothing on the worker's "
                        "filesystem survives")
    p.add_argument("--fleet-load-step", action="store_true",
                   help="--mode fleet: the autoscaler preset (implies "
                        "--multiproc): start ONE worker, run the "
                        "load-step session trace (arrival rate "
                        "doubles mid-run, then halves), autoscale up "
                        "to --fleet-replicas workers on sustained "
                        "backlog and drain back down on the lull; the "
                        "artifact emits scale-up/scale-down counts, "
                        "peak/final worker counts and the zero-drop "
                        "verification")
    p.add_argument("--disagg", action="store_true",
                   help="--mode fleet: run the disaggregation A/B "
                        "instead of the plain replay — the same mixed "
                        "long+short trace through a colocated fleet "
                        "and a 1-prefill/(N-1)-decode fleet at equal "
                        "worker count; the artifact's disagg_ab block "
                        "carries both arms' short-prompt TTFT, the "
                        "page-transfer counters, and the greedy "
                        "token-identity bit")
    p.add_argument("--fleet-journal-dir", default="",
                   help="--mode fleet: per-replica crash journals "
                        "(default: a temp dir)")
    p.add_argument("--serve-requests", type=int, default=64,
                   help="--mode serve: trace length")
    p.add_argument("--serve-rate", type=float, default=200.0,
                   help="--mode serve: Poisson arrival rate, req/s")
    p.add_argument("--serve-pool", type=int, default=8,
                   help="--mode serve: KV-cache pool slots")
    p.add_argument("--serve-max-new-tokens", type=int, default=32,
                   help="--mode serve: per-request decode budget")
    p.add_argument("--serve-prefix-trace", action="store_true",
                   help="--mode serve: shared-prefix trace (every prompt "
                        "shares one system-prompt-style prefix), replayed "
                        "with the radix prefix cache ON and OFF — the "
                        "artifact carries the TTFT A/B and prefix metrics")
    p.add_argument("--serve-page-size", type=int, default=0,
                   help="--mode serve: tokens per KV page "
                        "(0 = min(16, block_size))")
    p.add_argument("--serve-n-pages", type=int, default=0,
                   help="--mode serve: physical KV pages (0 = "
                        "pool * pages-per-slot, the contiguous pool's HBM)")
    p.add_argument("--decode-window", type=int, default=8,
                   help="--mode serve: decode steps rolled into one "
                        "jitted dispatch at steady state (the async "
                        "engine window; 1 = the blocked per-token "
                        "loop). When > 1 the artifact carries the "
                        "dispatch split: blocked (k=1) vs amortized "
                        "host-overhead per token on the same trace")
    p.add_argument("--decode-window-auto", action="store_true",
                   help="--mode serve: auto-tune the window size from "
                        "the live dispatch split (bounded additive "
                        "increase over warm power-of-two buckets up "
                        "to --decode-window; never recompiles)")
    p.add_argument("--kv-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="--mode serve: paged KV page storage precision "
                        "(quant/ — int8/fp8 pages + per-row scales "
                        "halve bytes/page; see --quant-ab for the "
                        "fixed-HBM capacity A/B)")
    p.add_argument("--weight-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="--mode serve: block matmul kernel precision "
                        "(absmax-per-channel, dequant fused into the "
                        "matmuls)")
    p.add_argument("--paged-kernel", action="store_true",
                   help="--mode serve: run the unified Pallas "
                        "paged-attention kernel family for every "
                        "engine step (decode, mixed windows, verify; "
                        "shard_map on a >1 mesh) — the artifact's "
                        "kernel_route block records the decision and "
                        "any envelope fallback reasons")
    p.add_argument("--act-quant", default="none",
                   choices=["none", "int8"],
                   help="--mode serve: W8A8 activation quantization "
                        "into the int8 weight matmuls (requires "
                        "--weight-quant int8)")
    p.add_argument("--quant-ab", action="store_true",
                   help="--mode serve: bf16-vs-int8 KV capacity + "
                        "divergence A/B at a FIXED HBM budget on the "
                        "shared-prefix trace — each arm's pool sized "
                        "in its own pages (the admission currency), "
                        "greedy streams compared token-for-token; "
                        "emits the quant_ab artifact block")
    p.add_argument("--serve-storm-trace", action="store_true",
                   help="--mode serve: also replay the admission-heavy "
                        "saturating storm (short prompts, mixed "
                        "deadlines + mid-flight cancels) at the "
                        "configured window AND blocked k=1 — the "
                        "continuous-window acceptance workload. The "
                        "artifact's admission_storm block carries the "
                        "dispatch-count amortization under the storm, "
                        "the idle reference, and the retention ratio "
                        "(>= 0.90 is the ISSUE 13 acceptance bar)")
    p.add_argument("--mesh-shape", default="1x1",
                   help="--mode serve: serving mesh DATAxMODEL (e.g. "
                        "2x2) — the engine runs GSPMD-sharded over a "
                        "(data, model) mesh: paged KV pages over data "
                        "(aggregate capacity at fixed per-chip HBM), "
                        "Megatron TP over model; the artifact carries "
                        "mesh_shape / pages_per_chip / aggregate_pages. "
                        "Downgrades to 1x1 with a log line when the "
                        "backend has fewer devices")
    p.add_argument("--trace-out", default=None,
                   help="--mode serve: write a Perfetto-loadable Chrome "
                        "trace of the replay (one span tree per request "
                        "on per-slot tracks; docs/observability.md) — "
                        "path lands in the artifact JSON")
    p.add_argument("--metrics-timeline", default=None,
                   help="--mode serve: write a JSONL time series of "
                        "every engine counter/gauge/histogram")
    p.add_argument("--metrics-out", default=None,
                   help="--mode serve: write end-of-run metrics as "
                        "Prometheus text exposition")
    p.add_argument("--spec", action="store_true",
                   help="--mode serve: speculative decoding over a "
                        "repetitive greedy trace (n-gram drafter unless "
                        "--draft-model is given)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="--mode serve --spec: drafted tokens per slot "
                        "per step (static; one verify program per k)")
    p.add_argument("--draft-model", default="",
                   help="--mode serve --spec: preset sizing a small "
                        "random-init draft model (vocab/block forced to "
                        "the target's); empty = n-gram drafter")
    p.add_argument("--loss-chunk", type=int, default=None,
                   help="train modes: chunked CE head override "
                        "(ModelConfig.loss_chunk; 0 = one-shot logits)")
    p.add_argument("--decode-cache-layout", default="",
                   choices=["", "heads", "packed"],
                   help="--mode decode: KV-cache layout override "
                        "(ModelConfig.decode_cache_layout)")
    p.add_argument("--decode-batch-sizes", default="1,8,32",
                   help="--mode decode: comma-separated batch sizes for "
                        "the aggregate-throughput sweep")
    p.add_argument("--longctx-t", type=int, default=32768,
                   help="sequence length for --mode longctx")
    p.add_argument("--repeats", type=int, default=7,
                   help="--mode kernel: timed laps per config (median + "
                        "spread reported; >= 5 for defensible claims)")
    p.add_argument("--kernel-inner", type=int, default=20,
                   help="--mode kernel: dispatched iterations per lap")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--steps-per-dispatch", type=int, default=25,
                   help="lax.scan K optimizer steps per device dispatch "
                        "(amortizes host->device round-trip latency)")
    p.add_argument("--rng-impl", default="rbg",
                   choices=["threefry2x32", "rbg"],
                   help="dropout PRNG; rbg uses the TPU hardware generator "
                        "(~15%% faster steps at dropout 0.2; same mask "
                        "distribution, different bits than threefry)")
    p.add_argument("--remeasure-baseline", action="store_true")
    p.add_argument("--skip-baseline", action="store_true",
                   help="report vs_baseline from cache or 0 if absent")
    p.add_argument("--no-generate", action="store_true",
                   help="skip the embedded generate-p50 measurement")
    p.add_argument("--platform", default=None,
                   help="'cpu' asks for a CPU run explicitly "
                        "(correctness and counts; Pallas kernels "
                        "interpreted). Without it a run that finds no "
                        "TPU fails — there is no fallback")
    p.add_argument("--sanitize", action="store_true",
                   help="run the benched mode under GRAFT_SANITIZE "
                        "(jax tracer-leak + NaN checks; numbers are NOT "
                        "comparable to unsanitized runs — the JSON "
                        "artifact is tagged sanitize=true)")
    p.add_argument("--watchdog", type=float, default=1500.0,
                   help="hard wall-clock budget (s); past it the error "
                        "artifact is emitted and the process exits")
    args = p.parse_args()

    metric = {"generate": "generate_1k_tokens_per_sec_p50",
              "longctx": f"longctx_t{args.longctx_t}_train_tokens_per_sec"
                         "_per_chip",
              "kernel": "flash_kernel_fwdbwd_median_ms",
              "decode": "generate_batched_aggregate_tokens_per_sec_p50",
              "serve": "serve_replay_aggregate_tokens_per_sec",
              "fleet": "fleet_replay_aggregate_tokens_per_sec",
              "train": "char_gpt_train_tokens_per_sec_per_chip"}[args.mode]
    unit = ("tokens/sec" if args.mode in ("generate", "decode", "serve",
                                          "fleet")
            else "ms" if args.mode == "kernel" else "tokens/sec/chip")
    try:
        start_watchdog(args.watchdog, metric, unit)
        import jax

        from replicatinggpt_tpu.utils.compile_cache import (
            enable_compile_cache)
        if args.platform:
            jax.config.update("jax_platforms", args.platform)
        if args.platform == "cpu":
            # the one way, tests aside, to ask for interpreted kernels
            from replicatinggpt_tpu.ops.flash_pallas import set_interpret
            set_interpret(True)
        log(f"compile cache: {enable_compile_cache()}")
        if not (args.mode == "fleet"
                and (args.multiproc or args.fleet_load_step)):
            # first touch of the backend; no TPU and no --platform cpu
            # is an error. (The multiproc fleet's parent never touches
            # it: its workers own the chips — bench_fleet reads the
            # device off their registration.)
            _EMIT_TAGS.update(device_tags(args.platform))
        jax.config.update("jax_default_prng_impl", args.rng_impl)
        import contextlib
        san = contextlib.nullcontext()
        if args.sanitize:
            # env first so Engine/runner construction sees it; the
            # context flips jax's leak/NaN checks for the whole mode
            os.environ["GRAFT_SANITIZE"] = "1"
            from replicatinggpt_tpu.utils.sanitize import sanitized
            san = sanitized(True)
            log("GRAFT_SANITIZE: tracer-leak + NaN checks on (numbers "
                "not comparable to unsanitized runs)")
        with san:
            if args.mode == "generate":
                bench_generate(args)
            elif args.mode == "longctx":
                bench_longctx(args)
            elif args.mode == "kernel":
                bench_kernel(args)
            elif args.mode == "decode":
                bench_decode_sweep(args)
            elif args.mode == "serve":
                bench_serve(args)
            elif args.mode == "fleet":
                bench_fleet(args)
            else:
                bench_train(args)
    except BaseException as e:  # noqa: BLE001 — artifact must still emit
        log(f"bench failed: {e!r}")
        emit(error_payload(metric, unit, repr(e)))
        raise SystemExit(1)


if __name__ == "__main__":
    main()
