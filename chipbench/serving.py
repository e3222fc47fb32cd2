"""What the serve traffic kinds share: ONE engine with weights made on the
device from the seed, its warm-up, the seeded sizes, the drive loop that
times every request from the instant it was DUE, the window's counters, and
the check against the plain reference.

The engine is the program's (``serve.engine.Engine``); the loop is the
benchmark's, because ``serve.replay.run_replay`` times from submit and keeps
a second engine (and page pool) alive beside the measured one.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import statistics
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import common, reference

_NORMAL = statistics.NormalDist()


# ------------------------------------------------------------------ sizes

def strata(n: int) -> np.ndarray:
    """The ``n`` mid-points of equal strata of (0, 1): the same set of
    quantiles for every seed, so every seed draws the same set of sizes."""
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(n: int, median: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    z = np.array([_NORMAL.inv_cdf(float(u)) for u in strata(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def uniform_sizes(n: int, lo: int, hi: int) -> np.ndarray:
    return np.clip(np.floor(lo + strata(n) * (hi - lo + 1)), lo,
                   hi).astype(int)


def exponential_gaps(n: int, total_s: float) -> np.ndarray:
    """``n`` gaps with the exponential's quantiles, scaled to sum to
    ``total_s``: Poisson arrivals whose count and span do not vary."""
    gaps = -np.log1p(-strata(n))
    return gaps * (total_s / gaps.sum())


def aged(prompt_len: int, out_len: int, age: int) -> tuple:
    """A request that is ``age`` tokens into its answer at the instant the
    run starts: what it has said so far is context like its prompt, what is
    left is its answer. So a run can open on the pool a long-running server
    would hold, without the minutes of traffic that would fill it."""
    age = int(min(max(age, 0), out_len - 1))
    return prompt_len + age, out_len - age


def sizes_of(spec: dict, n: int) -> np.ndarray:
    """A length distribution of a mix file: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``."""
    if spec["dist"] == "lognormal":
        return lognormal_sizes(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])
    if spec["dist"] == "uniform":
        return uniform_sizes(n, spec["min"], spec["max"])
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


# ----------------------------------------------------------------- engine

def build_engine(ctx: common.Ctx, sizes=None):
    """The program's configuration from its flags, float32 master weights
    made on the device in one jitted call from the seed (the engine serves
    them as they are), and one engine with the cell's ``engine`` settings."""
    import jax
    from replicatinggpt_tpu.models import gpt
    from replicatinggpt_tpu.serve import Engine, EngineConfig
    argv = (list(sizes) if sizes is not None
            else common.config_argv(ctx.cell["config"]))
    argv += list(ctx.cell["program"].get("flags", []))
    mcfg = common.program_config(argv + ["--seed", str(ctx.seed31)]).model
    params = jax.jit(lambda key: gpt.init_params(key, mcfg))(
        jax.random.PRNGKey(ctx.seed31))
    ecfg = EngineConfig(**ctx.cell["program"]["engine"])
    return Engine(params, mcfg, ecfg), mcfg, ecfg


def step_mfu_pct(model_flops: float, seconds: float, sizes=None):
    """The FLOPs the model needs for what the window served, a second, as a
    share (%) of the chip's bf16 peak: the whole step's share of the chip,
    beside the kernels' shares of their rooflines. None in a rehearsal
    (``sizes``): a CPU has no row in ``peaks.json``."""
    if sizes is not None or not seconds:
        return None
    import jax
    from chipbench import flops
    peak = flops.peaks(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * model_flops / seconds / peak


def gpt_step_mfu_pct(mcfg, win: dict, sizes=None):
    from chipbench import flops
    return step_mfu_pct(flops.gpt_serve_flops(
        mcfg.n_layer, mcfg.n_embd, mcfg.vocab_size,
        tokens=win["tokens"] + win["prefill_tokens"], emitted=win["tokens"],
        context_pairs=win["live_token_steps"]), win["seconds"], sizes)


def make_request(rid: str, rng: np.random.Generator, vocab: int,
                 prompt_len: int, out_len: int):
    from replicatinggpt_tpu.serve.requests import Request, SamplingParams
    return Request(id=rid, prompt=rng.integers(0, vocab, (prompt_len,),
                                               dtype=np.int32),
                   max_new_tokens=int(out_len),
                   sampling=SamplingParams(greedy=True))


def warm_up(engine, mcfg, ecfg, rng: np.random.Generator) -> None:
    """A few requests whose prompts sit on and around the page and the
    prefill-chunk boundaries: every program of this engine runs once."""
    page = ecfg.page_size or min(16, mcfg.block_size)
    chunk = ecfg.chunk(mcfg.block_size)
    lens = sorted({l for l in (page - 1, page + 1, chunk, chunk + 1,
                               2 * chunk + page + 3)
                   if 1 <= l <= mcfg.block_size - 8})
    for i, n in enumerate(lens):
        rej = engine.submit(make_request(f"warm{i}", rng, mcfg.vocab_size,
                                         n, 4 + i))
        if rej is not None:
            common.fail(f"warm-up request rejected: {rej.finish_reason}")
    engine.drain()


# ------------------------------------------------------------------- loop

@dataclasses.dataclass
class Sent:
    """One request as the generator saw it."""
    req: object
    due: float                   # seconds on the loop's clock
    submitted: float = math.nan
    counted: bool = True         # False: ramp traffic, set-up
    result: Optional[object] = None
    cut_at: float = math.nan     # hung up on: when its last token came


class Snapshot:
    """The engine's counters and the lengths of its histograms at one
    instant, so a window's part can be cut out by position."""

    def __init__(self, engine):
        m = engine.metrics
        self.t = time.monotonic()
        self.counters = dict(m.counters)
        self.hist_len = {k: len(v) for k, v in m.hists.items()}
        self.laps = len(engine.step_timer.laps)
        self.compiles = dict(common.COMPILES)
        from replicatinggpt_tpu.serve.engine import compile_counts
        self.programs = sum(compile_counts().values())


class Window:
    """The measured window of a serve run: ``open`` and ``close`` are the
    loop's ``at_open`` / ``at_close``; set-up ends where the window opens.
    While it is open, ``sample`` (the loop's ``each_step``) keeps what no
    counter of the engine holds: the context tokens live in the pool, the
    pages its requests hold, and how long every turn of the loop took, in
    the engine's step and outside it, with the garbage collector's pauses.
    A run whose loop stood still explains itself by these."""

    def __init__(self, engine, ctx: common.Ctx):
        self.engine, self.ctx = engine, ctx
        self.a: Optional[Snapshot] = None
        self.b: Optional[Snapshot] = None
        self.live, self.claimed, self.held = [], [], []
        self.active_steps = 0        # rows of the window's decode steps
        self.turns = []              # (seconds, of them in engine.step, at)
        self.gc_pauses = []
        self._gc_t0 = 0.0
        self.ramp_turn_s_max = 0.0   # the standing requests' admission

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.gc_pauses.append(time.monotonic() - self._gc_t0)

    def open(self) -> None:
        self.setup_s = common.now() - self.ctx.t_start
        self.a = Snapshot(self.engine)
        self._waits = common.host_waits()
        gc.callbacks.append(self._on_gc)

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)
        self.host_waits = common.waits_between(self._waits,
                                               common.host_waits())
        self.b = Snapshot(self.engine)
        self.queue_at_close = self.engine.scheduler.depth

    @property
    def is_open(self) -> bool:
        return self.a is not None and self.b is None

    def sample(self, ids, turn_s: float, step_s: float, at: float) -> None:
        if self.a is None:
            self.ramp_turn_s_max = max(self.ramp_turn_s_max, turn_s)
        if not self.is_open:
            return
        pool = self.engine.pool
        slots = [pool.slot_of(i) for i in ids]
        self.live.append(sum(int(pool.positions[s]) + 1
                             for s in slots if s is not None))
        self.active_steps += sum(s is not None for s in slots)
        self.claimed.append(int((pool.alloc.ref > 0).sum()))
        self.held.append(int(pool.alloc.pages_in_use))
        self.turns.append((turn_s, step_s, at))

    def counters(self) -> dict:
        out = window_counters(self.engine, self.a, self.b)
        pool = self.engine.pool
        mean = lambda v: sum(v) / len(v) if v else None
        share = lambda v, whole: (100.0 * mean(v) / whole if v else None)
        slow = sorted(self.turns, reverse=True)[:3]
        out.update(
            pool_pages=pool.n_pages, pool_tokens=pool.n_pages * pool.page_size,
            kv_live_tokens_mean=mean(self.live),
            # (row, context position) pairs of the window's decode steps, and
            # its rows: what ``flops.*_serve_flops`` counts attention from
            live_token_steps=sum(self.live), active_steps=self.active_steps,
            kv_live_pct=share(self.live, pool.n_pages * pool.page_size),
            kv_live_pct_halves=[                 # a steady state: no trend
                share(h, pool.n_pages * pool.page_size) for h in
                (self.live[:len(self.live) // 2],
                 self.live[len(self.live) // 2:])],
            kv_pages_claimed_pct=share(self.claimed, pool.n_pages),
            kv_pages_held_pct=share(self.held, pool.n_pages),
            ramp_turn_s_max=self.ramp_turn_s_max,
            loop_turns=len(self.turns),
            # the turns' seconds inside ``engine.step`` and outside it: the
            # second is the benchmark's own share of the window (submits,
            # the callers' next requests, this sample)
            loop_in_engine_step_s=sum(t[1] for t in self.turns),
            loop_outside_engine_step_s=sum(t[0] - t[1] for t in self.turns),
            loop_turn_s_max=slow[0][0] if slow else None,
            loop_slowest_turns=[{"s": t, "in_engine_step_s": e, "at_s": at}
                                for t, e, at in slow],
            gc_pauses=len(self.gc_pauses), gc_s=sum(self.gc_pauses),
            gc_pause_s_max=max(self.gc_pauses, default=0.0),
            host_waits=self.host_waits)
        return out


def window_counters(engine, a: Snapshot, b: Snapshot) -> dict:
    """Deltas of the engine's counters and the window's part of its
    histograms between two snapshots. The histograms keep the last
    ``reservoir`` observations: if one filled up, positions moved, and the
    run fails rather than read the wrong part."""
    m = engine.metrics
    for k, v in m.hists.items():
        if len(v) >= m.reservoir:
            common.fail(f"the engine's histogram {k!r} reached its "
                        f"reservoir of {m.reservoir}: shorten the run")
    delta = {k: b.counters.get(k, 0) - a.counters.get(k, 0)
             for k in b.counters}
    part = lambda k: m.hists.get(k, [])[a.hist_len.get(k, 0):
                                        b.hist_len.get(k, 0)]
    laps = engine.step_timer.laps[a.laps:b.laps]
    disp = part("decode_dispatch_s")
    fill = part("batch_fill_ratio")
    seconds = b.t - a.t
    tokens = delta.get("decode_tokens", 0)
    return {
        "seconds": seconds,
        "tokens": tokens,
        "tokens_per_s": tokens / seconds if seconds > 0 else None,
        "steps": len(laps),
        "step_ms_median": common.median(laps) * 1e3 if laps else None,
        "step_ms_p95": common.pct(laps, 0.95) * 1e3 if laps else None,
        "host_dispatch_ms_per_token": (sum(disp) * 1e3 / tokens
                                       if tokens else None),
        "batch_fill_pct": (100.0 * sum(fill) / len(fill)
                           if fill else None),
        "prefill_tokens": delta.get("prefill_tokens", 0),
        "admitted": delta.get("requests_admitted", 0),
        "admitted_per_s": (delta.get("requests_admitted", 0) / seconds
                           if seconds > 0 else None),
        "counter_deltas": delta,
        "backend_compiles": b.compiles["n"] - a.compiles["n"],
        "engine_programs_added": b.programs - a.programs,
    }


class Tracer:
    """Profiles ``span_s`` seconds of the loop right AFTER the window has
    closed, while the same traffic goes on, under the benchmark's own
    ``chipbench/window`` span. Stopping a trace stalls the host for seconds
    (3.5 s for these 2 s on the v5e's host), so ``tick`` only ends the span
    and ``close`` stops the profiler: the loop calls it when it has ended
    and the callers have hung up, and the stall is in no request's times."""

    def __init__(self, logdir: Optional[str], span_s: float):
        self.logdir = logdir
        self.span_s = span_s if logdir else 0.0
        self.state = "idle" if logdir else "done"
        self._span = None

    def tick(self, since_close: float) -> None:
        import jax
        if self.state == "idle" and since_close >= 0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("chipbench/window")
            self._span.__enter__()
            self.state = "on"
        elif self.state == "on" and since_close >= self.span_s:
            self._span.__exit__(None, None, None)
            self.state = "spanned"

    def close(self) -> None:
        import jax
        if self.state == "on":
            self._span.__exit__(None, None, None)
        if self.state in ("on", "spanned"):
            jax.profiler.stop_trace()
        self.state = "done"


def drive(engine, *, due: List[Sent], on_finish: Callable, t_open: float,
          t_close: float, t_give_up: float, tracer: Tracer,
          at_open: Callable, at_close: Callable,
          each_step: Callable = lambda ids, turn_s, step_s, at: None,
          hang_up: bool = False) -> List[Sent]:
    """The benchmark's serve loop, one thread, the engine's own clock.

    ``due`` is time-sorted; a request is submitted in the first iteration
    at or after its due instant (its lateness is recorded, and every time
    is later taken from ``due``, not from the submit). ``on_finish(sent)``
    may return requests to send at once (a closed loop's next request).
    ``at_open`` / ``at_close`` run at the first iteration boundary at or
    after ``t_open`` / ``t_close``; a traced run profiles the seconds that
    follow the close and stops its profiler only when the loop has ended
    and the callers have hung up (``Tracer``). ``each_step(ids, turn_s,
    step_s, at)`` runs after every engine step with the ids of the requests
    in flight, the seconds since the step before ended and those of them
    spent inside ``engine.step``.
    The loop ends when nothing is left or ``t_give_up`` has passed; with
    ``hang_up`` the callers of whatever is still running then hang up
    (``engine.cancel``): the engine's terminal record of each, with the
    tokens and times it had, is kept as its result, and ``cut_at`` is the
    instant the last step ended, when its last token came."""
    import jax
    sent: Dict[str, Sent] = {}
    out: List[Sent] = []
    queue = list(due)
    i = 0
    t0 = time.monotonic()
    opened = closed = False
    last_end = 0.0               # when the last engine step ended

    def submit(s: Sent, now: float) -> None:
        s.submitted = now
        out.append(s)
        rej = engine.submit(s.req)
        if rej is not None:
            s.result = rej
        else:
            sent[s.req.id] = s

    try:
        while True:
            now = time.monotonic() - t0
            if not opened and now >= t_open:
                opened = True
                at_open()
            if not closed and now >= t_close:
                closed = True
                at_close()
            if closed:
                tracer.tick(now - t_close)
            if now >= t_give_up:
                break
            with jax.profiler.TraceAnnotation("chipbench/submit"):
                while i < len(queue) and queue[i].due <= now:
                    submit(queue[i], now)
                    i += 1
            if engine.idle:
                if i >= len(queue):
                    break
                with jax.profiler.TraceAnnotation("chipbench/wait_arrival"):
                    time.sleep(min(max(queue[i].due - now, 0.0), 0.002))
                continue
            t_step = time.monotonic() - t0
            with jax.profiler.TraceAnnotation("chipbench/engine_step"):
                finished = engine.step()
            now = time.monotonic() - t0
            for r in finished:
                s = sent.pop(r.id, None)
                if s is None:
                    continue
                s.result = r
                for nxt in on_finish(s) or ():
                    nxt.due = now
                    submit(nxt, now)
            each_step(sent.keys(), now - last_end, now - t_step, now)
            last_end = now
        if hang_up:
            for s in list(sent.values()):
                s.cut_at = last_end
                engine.cancel(s.req.id)
            for _ in range(8):       # a cancel surfaces from the next step
                if not sent:
                    break
                for r in engine.step():
                    s = sent.pop(r.id, None)
                    if s is not None:
                        s.result = r
    finally:
        tracer.close()               # stalls the host: after the hang-up
    if not closed:
        common.fail("the serve loop ended before the window closed")
    return out


# --------------------------------------------------------------- checking

#: the tail the serve cells report. It was chosen (PR 26) as the highest
#: percentile with ten samples beyond it when a window held some 50
#: requests. A window now holds hundreds (the open cell some 400 due, the
#: closed cells 560 and 100 finished), which would carry a 95th; the 80th
#: stays, so that ``tpot_p80_ms`` and ``ttft_p80_ms`` keep their meaning and
#: their history in the ledger
TAIL = 0.8

#: a request the benchmark hung up on gives a time per token only if it had
#: this many tokens: with fewer, one slow gap is the whole reading
CUT_MIN_TOKENS = 8

#: a request hung up on has STALLED, and counts as failed, if it had fewer
#: than this share of the tokens that the median pace of the window's
#: requests would have given it between its first token and the hang-up
STALL_SHARE = 0.5


def request_times(s: Sent) -> Optional[dict]:
    """The times in ms, from the instant it was due, of a request that
    finished or that had its first token when its caller hung up; None for
    one that was rejected, failed or never got that far. A request hung up
    on got its ``n`` tokens between its first token and the end of the
    loop's last step (``cut_at``): ``n - 1`` whole gaps, like a finished
    one's."""
    from replicatinggpt_tpu.serve.requests import FINISH_CANCELLED
    r = s.result
    cut = r is not None and r.finish_reason == FINISH_CANCELLED
    if r is None or not r.tokens or not (r.ok or cut):
        return None
    late = s.submitted - s.due
    n = len(r.tokens)
    if cut:
        decode_s = (s.cut_at - s.submitted) - r.ttft_s
        tpot = decode_s / (n - 1) if n >= CUT_MIN_TOKENS else None
    else:
        decode_s = r.total_s - r.ttft_s
        tpot = decode_s / (n - 1) if n > 1 else None
    return {"ttft_ms": (late + r.ttft_s) * 1e3,
            "queue_wait_ms": (late + r.queue_wait_s) * 1e3,
            "late_ms": late * 1e3, "cut": cut, "tokens": n,
            "decode_ms": decode_s * 1e3,
            "tpot_ms": None if tpot is None else tpot * 1e3}


def stalled(times: List[Optional[dict]]) -> List[bool]:
    """Which of the requests hung up on had stalled (``STALL_SHARE``): a
    program that stops feeding long answers fails them, it does not merely
    hand the benchmark fewer gaps to read."""
    pace = [x["tpot_ms"] for x in times if x and x["tpot_ms"] is not None]
    if not pace:
        return [False] * len(times)
    typical = common.median(pace)
    return [bool(x and x["cut"] and
                 x["tokens"] - 1 < STALL_SHARE * x["decode_ms"] / typical - 1)
            for x in times]


def check(engine, mcfg, finished: List[Sent], window: dict, seed: int,
          tol: float, why: str, n_streams: int = 8) -> List[str]:
    """Outside the window: the kernel route, no compile after warm-up, and
    a seeded sample of streams (finished, or hung up on: the tokens a stream
    had are held to the reference all the same) teacher-forced through the
    plain float32 reference. The engine's page pool is dropped first: it and
    the reference's activations do not fit the chip together."""
    problems = []
    t0 = common.now()
    route = engine.metrics_summary()["kernel_route"]
    common.note("kernel_route", **route)
    if route["route"] != "pallas" or route["reasons"]:
        problems.append(f"kernel route is {route['route']} "
                        f"{route['reasons']}, not pallas")
    if not common.compare("compiled_in_window", window["backend_compiles"]
                          + window["engine_programs_added"], 0):
        problems.append(
            f"compiled inside the window: {window['backend_compiles']} "
            f"backend compiles, {window['engine_programs_added']} programs")
    params = engine.params
    rng = np.random.default_rng(seed)
    pick = [finished[i] for i in
            rng.permutation(len(finished))[:n_streams]] if finished else []
    prompts = [np.asarray(s.req.prompt) for s in pick]
    streams = [np.asarray(s.result.tokens, np.int32) for s in pick]
    engine.pool.cache = None
    gc.collect()
    gaps = reference.stream_gaps(params, mcfg.n_head, mcfg.block_size,
                                 prompts, streams)
    common.note("reference_streams", streams=len(gaps),
                stream_tokens=[len(s) for s in streams],
                prompt_tokens=[len(p) for p in prompts],
                worst_logit_gap=max(gaps, default=None), gaps=gaps,
                tol=tol, why=why, check_s=common.now() - t0)
    if not gaps:
        problems.append("no finished stream to hold to the reference")
    if not common.compare("worst_logit_gap", max(gaps, default=None), tol):
        problems.append(f"a stream's token sits {max(gaps, default=None)} "
                        f"below the reference's best logit, tolerance {tol}")
    return problems
