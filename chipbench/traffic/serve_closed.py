"""Traffic kind ``serve_closed``: a closed loop. ``clients`` callers each
send their next request the moment the last one finished, so the pool stays
full and a slow engine receives less load.

Parameters of a mix: ``source`` (the public trace the lengths stand for),
``clients``, ``prompt`` and ``output`` (length distributions),
``requests_per_client`` (the size of the seeded set of lengths each client
walks through, again and again), ``ramp_s`` (set-up: the time the file
states before the window opens), ``trace_s`` (a traced run goes on that long
after the window and profiles it), ``logit_tol``.

The run opens on the pool a long-running server would hold: every client
starts at a seeded point of its own cycle of requests, counted in answer
tokens, so its first request is as far into its answer as that point lies
(``serving.aged``), and long answers are as over-represented at the start as
they are in a real pool.

Every seed replays the same lengths in the same order (the distributions'
quantiles, ordered by ``schedule_seed`` of the mix); ``--seed`` gives the
token ids (and the weights). See ``serve_open`` for why.
"""

from __future__ import annotations

import numpy as np

from chipbench import common, serving


class Clients:
    """The callers: client ``c``'s ``j``-th request, from the seeded set."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.rng = np.random.default_rng(seed)             # token ids
        order = np.random.default_rng(int(traffic["schedule_seed"]))
        self.vocab = vocab
        self.n = int(traffic["clients"])
        self.per = int(traffic["requests_per_client"])
        m = self.n * self.per
        self.prompts = order.permutation(
            serving.sizes_of(traffic["prompt"], m))
        self.outputs = order.permutation(
            serving.sizes_of(traffic["output"], m))
        # where in its cycle each client stands when the run starts
        self.start = order.permutation(serving.strata(self.n))
        self.count = [0] * self.n
        self.age = [0] * self.n
        for c in range(self.n):
            outs = [int(self.outputs[self._index(c, j)])
                    for j in range(self.per)]
            at = int(self.start[c] * sum(outs))
            while at >= outs[self.count[c]]:
                at -= outs[self.count[c]]
                self.count[c] += 1
            self.age[c] = at

    def _index(self, c: int, j: int) -> int:
        return ((j % self.per) * self.n + c) % len(self.prompts)

    def next(self, c: int) -> serving.Sent:
        j = self.count[c]
        self.count[c] += 1
        i = self._index(c, j)
        p, o = serving.aged(int(self.prompts[i]), int(self.outputs[i]),
                            self.age[c])
        self.age[c] = 0
        req = serving.make_request(f"c{c:03d}.{j:04d}", self.rng, self.vocab,
                                   p, o)
        return serving.Sent(req=req, due=0.0)


def run(ctx: common.Ctx, sizes=None) -> dict:
    t = ctx.cell["traffic"]
    engine, mcfg, ecfg = serving.build_engine(ctx, sizes)
    serving.warm_up(engine, mcfg, ecfg, np.random.default_rng(ctx.seed31))
    clients = Clients(t, ctx.seed31, mcfg.vocab_size)
    ramp = float(t["ramp_s"])
    window = serving.Window(engine, ctx)
    finished_at = []             # (sent, whether inside the window)

    def on_finish(s: serving.Sent):
        finished_at.append((s, window.is_open))
        return [clients.next(int(s.req.id[1:4]))]

    tracer = serving.Tracer(ctx.trace_dir if ctx.trace else None,
                            float(t["trace_s"]))
    first = [clients.next(c) for c in range(clients.n)]
    sent = serving.drive(
        engine, due=first, on_finish=on_finish, t_open=ramp,
        t_close=ramp + ctx.seconds,
        t_give_up=ramp + ctx.seconds + tracer.span_s, tracer=tracer,
        at_open=window.open, at_close=window.close,
        each_step=window.sample)
    mem = common.memory_peak_bytes()
    win = window.counters()
    win["step_mfu_pct"] = serving.gpt_step_mfu_pct(mcfg, win, sizes)
    in_window = [s for s, inside in finished_at if inside]
    rejected = [s for s in sent if s.result is not None
                and not s.result.ok]
    times = [serving.request_times(s) for s in in_window]
    tpot = [x["tpot_ms"] for x in times if x and x["tpot_ms"] is not None]
    if not tpot:
        common.fail("no request finished inside the window")
    tail = lambda v: common.pct(v, serving.TAIL)
    common.note("serve_closed_window", clients=clients.n,
                finished_in_window=len(in_window), rejected=len(rejected),
                queue_at_close=window.queue_at_close,
                tpot_ms={"n": len(tpot), "p50": common.median(tpot),
                         "p80": tail(tpot)},
                engine_window=win)
    ok = [s for s in in_window if serving.request_times(s)]
    problems = serving.check(engine, mcfg, ok, win, ctx.seed31,
                             float(t["logit_tol"]), t["logit_tol_why"])
    if problems:
        common.note("problems", problems=problems)
    return {
        "correct": not problems,
        "attempted": len(in_window) + len(rejected),
        "failed": len(rejected) + len(in_window) - len(ok),
        "memory_peak_bytes": mem,
        "end_to_end": {"serve_tokens_per_s": win["tokens_per_s"],
                       "tpot_p80_ms": tail(tpot), "setup_s": window.setup_s},
        "counters": {"setup": {"compile_s": window.a.compiles["s"]},
                     "serve": win},
    }
