"""Traffic kind ``serve_open``: an open loop. Requests arrive on a schedule
fixed before the run, at ``rate_rps``, whatever the engine does; each is
timed from the instant it was due.

Parameters of a mix: ``source`` (the public trace the lengths stand for),
``rate_rps`` (a number: four fifths of the knee found by a sweep, see
PERF.md), ``prompt`` and ``output`` (length distributions, see
``serving.sizes_of``), ``standing_tpot_ms`` (where answers outlast the
window: the pace at which the requests of the time BEFORE the run are aged,
see ``standing``; absent or 0: the run starts on an empty pool), ``ramp_s``
(the standing requests are sent at its start and arrivals run through it;
set-up), ``tail_s`` (arrivals go on that long after the window, not counted,
so that the window's last requests get their first tokens under the same
load and not in an emptying engine; then the callers of whatever is still
decoding hang up; a traced run profiles ``trace_s`` of them and its callers
hang up there, before the profiler stops, which stalls the host for
seconds), ``logit_tol``.

A request due in the window counts with the times it had when it finished or
when its caller hung up (``serving.request_times``). It has FAILED, and
counts in the TTFT with the whole time from its due instant to the hang-up,
if it was rejected, errored or had no first token by then, and it has failed
too if it was hung up on while stalled (``serving.stalled``). How many were
hung up on while decoding is ``cut`` on the result line.

Every seed replays the same schedule: gaps and lengths are the distributions'
quantiles (``serving.strata``) in an order that ``schedule_seed`` of the mix
fixes, and ``--seed`` gives the token ids (and the weights). An order drawn
from ``--seed`` moved the window's tails by 15% from seed to seed while two
runs of one seed agreed within a few (PERF.md, PR 26): the order is part of
the work, so the bounds hold for this one sample path. Another order is
another mix file with another ``schedule_seed``.
"""

from __future__ import annotations

import numpy as np

from chipbench import common, serving


def standing(traffic: dict, rng, vocab: int) -> list:
    """The requests a server that had been under this traffic for a long
    time would hold at the instant the run starts, each as far into its
    answer as ``standing_tpot_ms`` a token has brought it (``serving.aged``).
    Arrivals of the last ``output.max`` tokens' time are replayed on paper;
    what would be finished is dropped. Long answers are as over-represented
    here as they are in a real pool. Sent at time 0, not counted."""
    tpot_s = float(traffic.get("standing_tpot_ms", 0)) / 1e3
    if tpot_s <= 0:
        return []
    order = np.random.default_rng([int(traffic["schedule_seed"]), 1])
    past = traffic["output"]["max"] * tpot_s
    n = int(round(traffic["rate_rps"] * past))
    gaps = order.permutation(serving.exponential_gaps(n, past))
    prompts = order.permutation(serving.sizes_of(traffic["prompt"], n))
    outputs = order.permutation(serving.sizes_of(traffic["output"], n))
    since = past - np.cumsum(gaps) * (1 - 1e-9)      # seconds ago
    out = []
    for i in range(n):
        age = int(since[i] / tpot_s)
        if age < outputs[i]:
            p, o = serving.aged(int(prompts[i]), int(outputs[i]), age)
            out.append(serving.Sent(
                req=serving.make_request(f"held{i:05d}", rng, vocab, p, o),
                due=0.0, counted=False))
    return out


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """Time-sorted ``Sent``: the standing requests, then the arrivals of the
    ramp, the window and the tail; only the window's are counted."""
    rng = np.random.default_rng(seed)                      # token ids
    order = np.random.default_rng(int(traffic["schedule_seed"]))
    out = standing(traffic, rng, vocab)
    ramp, tail = float(traffic["ramp_s"]), float(traffic["tail_s"])
    for part, t0, span in (("ramp", 0.0, ramp), ("win", ramp, seconds),
                           ("tail", ramp + seconds, tail)):
        n = int(round(traffic["rate_rps"] * span))
        if n == 0:
            continue
        gaps = order.permutation(serving.exponential_gaps(n, span))
        prompts = order.permutation(serving.sizes_of(traffic["prompt"], n))
        outputs = order.permutation(serving.sizes_of(traffic["output"], n))
        due = t0 + np.cumsum(gaps) * (1 - 1e-9)
        for i in range(n):
            out.append(serving.Sent(
                req=serving.make_request(f"{part}{i:05d}", rng, vocab,
                                         int(prompts[i]), int(outputs[i])),
                due=float(due[i]), counted=part == "win"))
    return out


def run(ctx: common.Ctx, sizes=None) -> dict:
    t = ctx.cell["traffic"]
    engine, mcfg, ecfg = serving.build_engine(ctx, sizes)
    serving.warm_up(engine, mcfg, ecfg, np.random.default_rng(ctx.seed31))
    sched = schedule(t, ctx.seed31, ctx.seconds, mcfg.vocab_size)
    ramp = float(t["ramp_s"])
    window = serving.Window(engine, ctx)
    tracer = serving.Tracer(ctx.trace_dir if ctx.trace else None,
                            float(t["trace_s"]))
    # a traced run's callers hang up where its trace ends: the profiler is
    # stopped after them, and its stall is in no request's times
    give_up = ramp + ctx.seconds + (tracer.span_s if ctx.trace
                                    else float(t["tail_s"]))
    sent = serving.drive(
        engine, due=sched, on_finish=lambda s: None, t_open=ramp,
        t_close=ramp + ctx.seconds, t_give_up=give_up, tracer=tracer,
        at_open=window.open, at_close=window.close,
        each_step=window.sample, hang_up=True)
    mem = common.memory_peak_bytes()
    win = window.counters()
    win["step_mfu_pct"] = serving.gpt_step_mfu_pct(mcfg, win, sizes)
    counted = [s for s in sched if s.counted]
    times = [serving.request_times(s) for s in counted]
    stalled = serving.stalled(times)
    good = [x for x, bad in zip(times, stalled) if x is not None and not bad]
    failed = len(counted) - len(good)
    # a request that was rejected or had no first token waited, at the
    # least, from its due instant until the callers hung up
    worst = [(give_up - s.due) * 1e3
             for s, x in zip(counted, times) if x is None]
    have = [x for x in times if x is not None]
    ttft = [x["ttft_ms"] for x in have] + worst
    wait = [x["queue_wait_ms"] for x in have] + worst
    tpot = [x["tpot_ms"] for x in have if x["tpot_ms"] is not None]
    late = [(s.submitted - s.due) * 1e3 for s in sent if s.counted]
    if not have or not tpot:
        common.fail("no request of the window got as far as its tokens")
    cut = sum(x["cut"] for x in have)
    tail = lambda v: common.pct(v, serving.TAIL)
    common.note("serve_open_window", rate_rps=t["rate_rps"],
                standing=sum(s.req.id.startswith("held") for s in sched),
                due_in_window=len(counted), finished=len(have) - cut,
                cut_while_decoding=cut, stalled=sum(stalled),
                failed=failed, admitted_per_s=win["admitted_per_s"],
                queue_at_close=window.queue_at_close,
                ttft_ms={"n": len(ttft), "mean": sum(ttft) / len(ttft),
                         "p50": common.median(ttft),
                         "p80": tail(ttft), "max": max(ttft)},
                tpot_ms={"n": len(tpot), "p50": common.median(tpot),
                         "p80": tail(tpot)},
                queue_wait_ms={"p50": common.median(wait), "p80": tail(wait)},
                generator_late_ms={"n": len(late), "p50": common.median(late),
                                   "p80": tail(late), "max": max(late)},
                prompt_tokens=int(sum(len(s.req.prompt) for s in counted)),
                output_tokens=int(sum(s.req.max_new_tokens
                                      for s in counted)),
                engine_window=win)
    # streams to hold to the reference: whatever ran, counted or not, that
    # got far enough (a standing request's "prompt" is prompt and age)
    streams = [s for s in sent if serving.request_times(s)
               and len(s.result.tokens) >= serving.CUT_MIN_TOKENS]
    problems = serving.check(engine, mcfg, streams, win, ctx.seed31,
                             float(t["logit_tol"]), t["logit_tol_why"])
    if problems:
        common.note("problems", problems=problems)
    return {
        "correct": not problems,
        "attempted": len(counted),
        "failed": failed,
        "cut": cut,
        "memory_peak_bytes": mem,
        "end_to_end": {"ttft_mean_ms": sum(ttft) / len(ttft),
                       "tpot_p80_ms": tail(tpot),
                       "serve_tokens_per_s": win["tokens_per_s"],
                       "setup_s": window.setup_s},
        "counters": {
            "setup": {"compile_s": window.a.compiles["s"]},
            "serve": {**win, "queue_wait_p80_ms": tail(wait),
                      "ttft_p80_ms": tail(ttft),
                      "queue_at_close": window.queue_at_close},
        },
    }
