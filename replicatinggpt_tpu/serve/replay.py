"""Offline serving-trace replay: a synthetic Poisson workload through
the engine.

The zero-egress image cannot take real traffic, so the serving story is
proven the way load tests do it: a seeded Poisson arrival process over
random prompts/lengths/budgets is replayed in wall-clock time through
the engine, and the metrics summary (TTFT, decode tok/s, occupancy,
batch fill, step latency, recompiles-after-warmup) is the artifact.
Drives both ``python -m replicatinggpt_tpu serve-replay`` and
``bench.py --mode serve``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from ..config import ModelConfig
from ..utils.sanitize import sanitized
from ..utils.telemetry import MetricsTimeline, Telemetry, prometheus_text
from .engine import Engine, EngineConfig, compile_counts
from .requests import Request, RequestResult, SamplingParams
from .speculative import make_drafter


@dataclass(frozen=True)
class ReplayConfig:
    n_requests: int = 64
    rate: float = 200.0            # mean arrivals/sec (Poisson)
    seed: int = 0
    prompt_len_min: int = 1
    prompt_len_max: int = 32
    max_new_tokens: int = 16
    greedy: bool = False
    temperature: float = 1.0
    top_k: int = 20
    top_p: float = 0.0
    deadline_s: float = 0.0        # per-request deadline after arrival; 0=off
    prompt_mode: str = "random"    # 'random' | 'repeat' (tiled small
                                   # pattern — the speculative bench trace)
                                   # | 'shared_prefix' (every prompt =
                                   # one common random prefix + a random
                                   # suffix — the system-prompt traffic
                                   # shape the radix prefix cache serves)
    shared_prefix_len: int = 0     # 'shared_prefix' common-prefix length;
                                   # 0 = prompt_len_max // 2
    spec: str = "off"              # drafter: 'off' | 'ngram' | 'model'
    spec_k: int = 4                # drafted tokens per slot per step
    spec_ngram: int = 3            # n-gram drafter match width


def make_trace(mcfg: ModelConfig, rcfg: ReplayConfig
               ) -> List[Tuple[float, Request]]:
    """Seeded (arrival_time, request) list: exponential inter-arrivals,
    uniform prompt lengths (clamped to block_size), uniform token ids —
    or, with ``prompt_mode='repeat'``, each prompt a tiled random <=4
    token pattern (repetitive text is the n-gram drafter's favorable
    regime; the serve-spec bench row uses this trace)."""
    rng = np.random.default_rng(rcfg.seed)
    hi = min(rcfg.prompt_len_max, mcfg.block_size)
    lo = min(rcfg.prompt_len_min, hi)
    shared = None
    if rcfg.prompt_mode == "shared_prefix":
        n_shared = min(rcfg.shared_prefix_len or max(hi // 2, 1), hi - 1)
        shared = rng.integers(0, mcfg.vocab_size, (n_shared,),
                              dtype=np.int64)
    t = 0.0
    trace = []
    sp = SamplingParams(temperature=rcfg.temperature, top_k=rcfg.top_k,
                        top_p=rcfg.top_p, greedy=rcfg.greedy)
    for i in range(rcfg.n_requests):
        # host numpy RNG: float() here is not a device round-trip
        t += float(rng.exponential(1.0 / max(rcfg.rate, 1e-9)))  # graftlint: disable=GL004
        if shared is not None:
            # the system-prompt shape: identical prefix + unique tail
            # (>= 1 token so requests are distinct streams); total
            # length still honors the [lo, hi] knobs
            P = int(rng.integers(max(lo, shared.size + 1), hi + 1))
            prompt = np.concatenate([
                shared, rng.integers(0, mcfg.vocab_size,
                                     (P - shared.size,), dtype=np.int64)])
        elif rcfg.prompt_mode == "repeat":
            P = int(rng.integers(lo, hi + 1))
            pat = rng.integers(0, mcfg.vocab_size,
                               (min(int(rng.integers(1, 5)), P),),
                               dtype=np.int64)
            prompt = np.tile(pat, -(-P // pat.size))[:P]
        else:
            P = int(rng.integers(lo, hi + 1))
            prompt = rng.integers(0, mcfg.vocab_size, (P,), dtype=np.int64)
        trace.append((t, Request(
            id=f"r{i:04d}", prompt=prompt.astype(np.int32),
            max_new_tokens=rcfg.max_new_tokens, sampling=sp,
            rng_seed=rcfg.seed * 100_003 + i)))
    return trace


def run_replay(params, mcfg: ModelConfig, rcfg: ReplayConfig,
               ecfg: EngineConfig, warmup: bool = True,
               draft_params=None,
               draft_cfg: Optional[ModelConfig] = None,
               resilience=None, journal=None,
               trace_out: Optional[str] = None,
               metrics_timeline: Optional[str] = None,
               metrics_timeline_interval_s: float = 0.5,
               metrics_out: Optional[str] = None,
               profile_dir: Optional[str] = None,
               profile_start: int = 10,
               profile_steps: int = 5,
               trace: Optional[List[Tuple[float, Request]]] = None,
               cancels: Optional[List[Tuple[float, str]]] = None,
               deadlines: Optional[dict] = None,
               inspect=None) -> dict:
    """Replay the trace in wall-clock time; returns the summary dict.

    ``warmup`` first pushes one tiny request through a throwaway engine
    of the same shapes so the device programs (including the
    speculative verify step and the model drafter's two programs, when
    configured) compile outside the timed replay — the summary's
    ``recompiles_after_warmup`` then asserts the steady-state claim
    (0 on a healthy run). With a drafter configured the warmup also
    runs the plain-decode path once: the speculative auto-disable
    policy (``resilience``, a faults.watchdog.ResilienceConfig) may
    legitimately switch to it mid-replay, and a degraded transition
    must not cost a compile. ``rcfg.spec`` selects the drafter; the
    'model' mode additionally needs ``draft_params``/``draft_cfg``
    (see ``speculative.draft_config_from_preset``). Drafters are
    stateful, so each engine gets its own. ``journal`` (a
    serve.journal.RequestJournal) is handed to the replay engine for
    restart-recovery coverage.

    Observability outputs (utils.telemetry; all off by default):
    ``trace_out`` writes a Perfetto-loadable Chrome trace of the whole
    replay (one span tree per request on per-slot tracks, recovery /
    prefix-hit / COW / eviction instants); ``metrics_timeline`` writes
    a JSONL time series of the engine's Metrics every
    ``metrics_timeline_interval_s`` (plus one snapshot at attach and a
    forced final one — >= 2 points always); ``metrics_out`` writes the
    end-of-run Prometheus text exposition. ``profile_dir`` captures a
    ``jax.profiler`` device trace of engine steps [profile_start,
    profile_start + profile_steps) — the device-side half of the
    timeline, with host spans linked by ``annotate`` region names.
    Paths of everything written land in the summary's ``artifacts``
    block (bench.py attaches it to the artifact JSON).

    ``trace`` replays a PREBUILT (arrival_time, request) list instead of
    ``make_trace(mcfg, rcfg)`` — the admission-storm preset
    (serve/loadgen.admission_storm) enters here. ``cancels`` is a
    time-sorted [(t, request_id), ...] schedule issued through
    ``engine.cancel`` as the replay clock passes each t (a cancel for a
    request that already finished is a no-op), and ``deadlines`` maps
    request ids to RELATIVE deadlines applied at submit (per-request,
    where ``rcfg.deadline_s`` is uniform).

    ``inspect(engine, results)`` is called once after the replay, with
    the live engine and every RequestResult — for callers that check
    what the summary does not carry (token streams against a
    reference, where the pool's shards live: chip_smoke.py).
    """
    def drafter():
        return make_drafter(rcfg.spec, rcfg.spec_k, rcfg.spec_ngram,
                            ecfg.pool_size, draft_params, draft_cfg,
                            ecfg.prefill_chunk)

    def tiny(rid):
        # long enough to EXERCISE the steady-state window path past the
        # admission boundary's mixed dispatch (the window programs
        # themselves compile at engine construction —
        # Engine._warm_windows; EngineConfig.warmup_tokens is one
        # definition shared with the worker's readiness warmup)
        return Request(id=rid, prompt=np.zeros((1,), np.int32),
                       max_new_tokens=ecfg.warmup_tokens(),
                       sampling=SamplingParams(greedy=True))

    if warmup:
        w = Engine(params, mcfg, ecfg, drafter=drafter())
        w.submit(tiny("warmup"))
        w.drain()
        if w.drafter is not None:
            # compile the degraded (plain decode) program too — see above
            w.set_spec_active(False)
            w.submit(tiny("warmup-degraded"))
            w.drain()
    warm = compile_counts()

    tel = Telemetry() if trace_out else None
    engine = Engine(params, mcfg, ecfg, drafter=drafter(),
                    rcfg=resilience, journal=journal, telemetry=tel)
    timeline = None
    if metrics_timeline:
        timeline = MetricsTimeline(engine.metrics, metrics_timeline,
                                   interval_s=metrics_timeline_interval_s)
        timeline.snapshot(step=0)          # the t=0 anchor point
    from ..utils.profiling import trace_window
    profiler = trace_window(profile_dir, start=profile_start,
                            n_steps=profile_steps)
    if trace is None:
        trace = make_trace(mcfg, rcfg)
    cancels = sorted(cancels) if cancels else []
    results: List[RequestResult] = []
    i = 0
    ci = 0
    n_trace_events = 0
    t0 = time.monotonic()
    # GRAFT_SANITIZE=1 runs the whole replay under jax's tracer-leak +
    # NaN checks (no-op context otherwise). Cleanup rides a finally: a
    # replay that dies mid-run (injected fault, sanitize trip, Ctrl-C)
    # must still stop the jax profiler (a started trace poisons the
    # next start_trace in this process) and flush the trace/timeline
    # artifacts — the crash window is exactly when they matter.
    try:
        with sanitized():
            while len(results) < len(trace):
                now = time.monotonic() - t0
                while i < len(trace) and trace[i][0] <= now:
                    arr_t, req = trace[i]
                    if deadlines and req.id in deadlines:
                        req.deadline = (time.monotonic()
                                        + deadlines[req.id])
                    elif rcfg.deadline_s > 0:
                        req.deadline = time.monotonic() + rcfg.deadline_s
                    rej = engine.submit(req)
                    if rej is not None:
                        results.append(rej)
                    i += 1
                while ci < len(cancels) and cancels[ci][0] <= now:
                    # mid-flight cancel traffic (the storm trace); a
                    # cancel for an already-finished id is a no-op
                    engine.cancel(cancels[ci][1])
                    ci += 1
                if engine.idle:
                    if i >= len(trace):
                        break
                    # nothing in flight: sleep to the next arrival
                    time.sleep(min(max(trace[i][0] - now, 0.0), 0.05))
                    continue
                profiler.step(engine.n_steps)
                results.extend(engine.step())
                if timeline is not None:
                    timeline.maybe_snapshot(step=engine.n_steps)
    finally:
        profiler.close()
        if tel is not None:
            n_trace_events = tel.export_chrome_trace(trace_out)
            tel.close()
        if timeline is not None:
            timeline.close(step=engine.n_steps)  # forced end-of-run point
    wall_s = time.monotonic() - t0
    if inspect is not None:
        inspect(engine, results)

    done = compile_counts()
    ok = [r for r in results if r.ok]
    gen_tokens = sum(len(r.tokens) for r in results)
    summary = engine.metrics_summary()
    summary.update({
        "n_requests": len(trace),
        "n_completed": len(ok),
        "n_rejected": sum(r.finish_reason.startswith("rejected")
                          for r in results),
        "generated_tokens": gen_tokens,
        "wall_s": round(wall_s, 3),
        "aggregate_tokens_per_s": round(gen_tokens / wall_s, 1)
        if wall_s > 0 else 0.0,
        "recompiles_after_warmup": sum(done.values()) - sum(warm.values()),
    })
    artifacts = {}
    if tel is not None:
        artifacts["trace_out"] = trace_out
        artifacts["trace_events"] = n_trace_events
    if timeline is not None:
        artifacts["metrics_timeline"] = metrics_timeline
        artifacts["metrics_timeline_snapshots"] = timeline.n_snapshots
    if metrics_out:
        pages = summary.get("pages", {})
        with open(metrics_out, "w") as f:
            f.write(prometheus_text(
                engine.metrics,
                extra_gauges={k: pages[k] for k in
                              ("pages_in_use", "page_utilization",
                               "prefix_hit_rate", "radix_pages",
                               "pages_per_chip", "aggregate_pages",
                               # quantization gauges (ISSUE 15): the
                               # capacity denominator + numeric mode
                               "bytes_per_page", "kv_quant_bits")
                              if k in pages}))
        artifacts["metrics_out"] = metrics_out
    if profile_dir:
        artifacts["profile_dir"] = profile_dir
    if artifacts:
        summary["artifacts"] = artifacts
    return summary


def format_summary(s: dict) -> str:
    """Human-readable metrics block (the serve-replay stdout report)."""
    h = s["histograms"]

    def pct(name, scale=1.0, unit=""):
        d = h.get(name, {})
        return (f"p50 {d.get('p50', 0) * scale:.2f}{unit} / "
                f"p90 {d.get('p90', 0) * scale:.2f}{unit} / "
                f"p99 {d.get('p99', 0) * scale:.2f}{unit}")

    sl = s["step_latency"]
    lines = [
        f"requests: {s['n_requests']} submitted, {s['n_completed']} "
        f"completed, {s['n_rejected']} rejected",
        f"tokens: {s['generated_tokens']} generated in {s['wall_s']}s "
        f"-> {s['aggregate_tokens_per_s']} tok/s aggregate",
        f"TTFT: {pct('ttft_s', 1e3, ' ms')}",
        f"decode rate/request: {pct('decode_tokens_per_s', 1.0, ' tok/s')}",
        f"step latency: p50 {sl['p50_s'] * 1e3:.2f} ms / "
        f"p90 {sl['p90_s'] * 1e3:.2f} ms over {s['n_steps']} steps",
        f"batch fill: mean {h.get('batch_fill_ratio', {}).get('mean', 0):.2f}"
        f" (pool), queue wait {pct('queue_wait_s', 1e3, ' ms')}",
        f"recompiles after warmup: {s['recompiles_after_warmup']}",
    ]
    dp = s.get("dispatch")
    if dp and dp.get("dispatches"):
        auto = (f" (autotuned from {dp['window_k_max']} cap, "
                f"{dp['autotune_increases']} increase(s))"
                if dp.get("autotune") else "")
        lines.insert(4, (
            f"dispatch split: window k={dp['window_k']}{auto}, "
            f"{dp['dispatches']} dispatches, host "
            f"{dp['mean_dispatch_ms']:.3f} ms/dispatch -> "
            f"{dp['host_dispatch_ms_per_token']:.3f} ms/token"))
        wb = s.get("window_breaks") or {}
        if dp.get("window_k_max", dp["window_k"]) > 1:
            lines.insert(5, (
                "window breaks: "
                + " ".join(f"{r}={wb.get(r, 0)}" for r in
                           ("admit", "deadline", "cancel", "spec",
                            "reprobe"))))
    pg = s.get("pages")
    if pg:
        if pg.get("kv_quant", "none") != "none":
            lines.insert(2, (
                f"quant: KV {pg['kv_quant']} "
                f"({pg['quant_granularity']}-granularity scales), "
                f"{pg['bytes_per_page']} bytes/page"))
        lines.insert(2, (
            f"pages: {pg['pages_in_use']}/{pg['n_pages']} in use "
            f"({pg['page_size']} tok/page, util "
            f"{pg['page_utilization']:.2f}), prefix hits "
            f"{pg['prefix_hits']}/{pg['prefix_lookups']} "
            f"({pg['prefix_hit_tokens']} tok, rate "
            f"{pg['prefix_hit_rate']:.2f}), {pg['evictions']} evictions, "
            f"{pg['cow_copies']} COW copies"))
        if pg.get("mesh_shape", [1, 1]) != [1, 1]:
            d, m = pg["mesh_shape"]
            lines.insert(2, (
                f"mesh: {d}x{m} (data x model), "
                f"{pg['pages_per_chip']} pages/chip of "
                f"{pg['aggregate_pages']} aggregate, per-chip in use "
                f"{pg['pages_in_use_by_chip']}"))
    sp = s.get("speculative")
    if sp:
        lines.insert(2, (
            f"speculative ({sp['drafter']}, k={sp['k']}): accept rate "
            f"{sp['accept_rate']:.3f}, {sp['mean_tokens_per_step']:.2f} "
            f"tokens/slot-step, draft overhead p50 "
            f"{sp['draft_overhead_s'].get('p50', 0) * 1e3:.2f} ms"))
    return "\n".join(lines)
