"""Quick CPU tests of the benchmark's own code.

    python -m pytest chipbench/test_chipbench.py -q

Nothing here touches the TPU library at import; the one test that needs JAX
runs it on the CPU.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import flops, manifest, serving, trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAN = manifest.Manifest(ROOT)


def test_manifest_names_units_and_moves():
    doc = MAN.doc
    cells = [w["name"] for w in doc["workloads"]]
    names = ([m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
             + cells + [c["name"] for c in doc["configs"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in doc["end_to_end"])
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in doc["end_to_end"]}
    for m in doc["per_layer"]:
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
    for cell in cells:       # setup_s, one more end-to-end, one per-layer
        assert len(MAN.metrics("end_to_end", cell)) >= 2
        assert MAN.metrics("per_layer", cell)
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= 1
    # a why, a layer, a source: 1 to 200 characters on one line, no tab
    lines = ([w["why"] for w in doc["workloads"]]
             + [c[k] for c in doc["configs"] for k in ("why", "source")]
             + [m["layer"] for m in doc["per_layer"]])
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in lines), [t for t in lines if len(t) > 200]
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m


def test_everything_is_found_by_name():
    for w in MAN.doc["workloads"]:
        cell = MAN.cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert callable(MAN.kind(cell["traffic"]["kind"]).run)
    for c in MAN.doc["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == MAN.cell(next(
            w["name"] for w in MAN.doc["workloads"]
            if w["config"] == c["name"]))["config"]["reduced"]
    for m in MAN.doc["per_layer"]:
        assert callable(MAN.reader(m["name"]))


def test_new_cell_config_kind_and_metric_need_only_new_files(tmp_path):
    """A later PR adds files and entries and edits nothing that is here."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads(json.dumps(MAN.doc))
    doc["configs"].append({"name": "toy", "source": "x", "reduced": [],
                           "file": "chipbench/configs/toy.json", "why": "x"})
    doc["workloads"].append({"name": "toy-cell", "config": "toy",
                             "traffic": "toy-mix", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "toy_metric", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "entry", "moves": "setup_s",
                             "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    b = tmp_path / "chipbench"
    (b / "configs" / "toy.json").write_text('{"name": "toy"}')
    (b / "traffic" / "toy-mix.json").write_text(
        '{"kind": "toy_kind", "rate_rps": 1}')
    (b / "traffic" / "toy_kind.py").write_text(
        "def run(ctx, sizes=None):\n    return {'counters': {'x': {'y': 2}}}")
    (b / "workloads" / "toy-cell.json").write_text(
        '{"program": {"flags": []}, "traffic_params": {"rate_rps": 7}}')
    (b / "layer_metrics" / "toy_metric.json").write_text(
        '{"counter": "x.y", "scale": 3}')
    man = manifest.Manifest(str(tmp_path))
    cell = man.cell("toy-cell")
    assert cell["traffic"] == {"kind": "toy_kind", "rate_rps": 7}
    counters = man.kind("toy_kind").run(None)["counters"]
    assert man.reader("toy_metric")(counters, None) == 6.0
    assert [m["name"] for m in man.metrics("per_layer", "toy-cell")
            if "workloads" in m] == ["toy_metric"]
    (b / "layer_metrics" / "toy_metric.py").write_text(
        "def read(counters, trace):\n    return None")
    assert man.reader("toy_metric")(counters, None) is None


def test_yardstick():
    # gpt2-medium: 3 * (2 * (12*24*1024^2 + 1024*50257) + 2*24*1024*1024)
    assert flops.train_flops_per_token(24, 1024, 1024, 50257) == \
        2271713280.0
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    # serving: one row through one layer of width 2 is 2 * 12 * 4 FLOPs, its
    # logits over 10 classes 2 * 2 * 10, a context position 4 * 2 a layer
    assert flops.gpt_serve_flops(1, 2, 10, tokens=1, emitted=0,
                                 context_pairs=0) == 96.0
    assert flops.gpt_serve_flops(3, 2, 10, tokens=5, emitted=2,
                                 context_pairs=7) == \
        5 * 3 * 96.0 + 2 * 40.0 + 7 * 3 * 8.0
    # the second family, from its configuration file: a row's attention
    # projections, dense MLP, shared experts and routers; a held pair's
    # three matrices; this chip's slice of the head; both kinds of layer
    cfg = MAN.cell("serve-decode-kexaone")["config"]
    h, q, kv, w = 6144, 64 * 128, 8 * 128, 2048
    row = (8 * 2 * (h * (q + 2 * kv) + q * h) + 2 * 3 * h * 18432
           + 7 * 2 * (3 * h * w + h * 128))
    none = dict(tokens=0, emitted=0, pairs_held=0, full_context_pairs=0,
                window_context_pairs=0)
    f = lambda **kw: flops.exaone_moe_serve_flops(cfg, **{**none, **kw})
    assert f(tokens=1) == row and f(pairs_held=1) == 2 * 3 * h * w
    assert f(emitted=1) == 2 * h * 19200
    assert f(full_context_pairs=1) == 4 * q * 2
    assert f(window_context_pairs=1) == 4 * q * 6
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_open_schedule_is_seeded_clipped_and_the_same_for_every_seed():
    kind = MAN.kind("serve_open")
    t = MAN.cell("serve-chat-large")["traffic"]
    a, b, c = (kind.schedule(t, s, 20.0, 50257) for s in (7, 7, 2 ** 31 + 5))
    assert [(s.due, s.req.prompt.tolist()) for s in a] == \
        [(s.due, s.req.prompt.tolist()) for s in b]
    # another seed: the same schedule (instants, lengths, order), other ids
    assert [(s.due, len(s.req.prompt), s.req.max_new_tokens) for s in a] == \
        [(s.due, len(s.req.prompt), s.req.max_new_tokens) for s in c]
    assert [s.req.prompt.tolist() for s in a] != \
        [s.req.prompt.tolist() for s in c]
    win = [s for s in a if s.counted]
    assert len(win) == round(t["rate_rps"] * 20.0)
    assert all(t["ramp_s"] <= s.due <= t["ramp_s"] + 20.0 for s in win)
    assert [s.due for s in a] == sorted(s.due for s in a)
    held = [s for s in a if s.req.id.startswith("held")]
    for s in a:
        if s not in held:
            assert (t["prompt"]["min"] <= len(s.req.prompt)
                    <= t["prompt"]["max"])
        assert 1 <= s.req.max_new_tokens <= t["output"]["max"]
        assert len(s.req.prompt) + s.req.max_new_tokens <= 1024
    # the standing requests: sent at 0, not counted, about rate x mean
    # residence of them (Little), some of them far into their answers
    assert held and all(s.due == 0.0 and not s.counted for s in held)
    outs = serving.sizes_of(t["output"], 4000)
    little = t["rate_rps"] * outs.mean() * t["standing_tpot_ms"] / 1e3
    assert 0.7 * little <= len(held) <= 1.3 * little
    assert max(len(s.req.prompt) for s in held) > t["prompt"]["max"]
    assert not kind.standing({**t, "standing_tpot_ms": 0}, None, 50257)


def test_closed_clients_are_seeded_clipped_and_start_mid_cycle():
    # aging keeps the whole of a request and leaves it a token to say
    assert serving.aged(100, 50, 0) == (100, 50)
    assert serving.aged(100, 50, 20) == (120, 30)
    assert serving.aged(100, 50, 80) == (149, 1)
    kind = MAN.kind("serve_closed")
    t = MAN.cell("serve-decode-large")["traffic"]
    runs = []
    for seed in (3, 3, 4):
        cl = kind.Clients(t, seed, 50257)
        reqs = [cl.next(c).req for _ in range(1 + t["requests_per_client"])
                for c in range(cl.n)]
        runs.append([(r.id, r.prompt.tolist(), r.max_new_tokens)
                     for r in reqs])
    assert runs[0] == runs[1] and runs[0] != runs[2]
    first, later = runs[0][:t["clients"]], runs[0][t["clients"]:]
    # another seed: the same lengths in the same order, other token ids
    assert [(r[0], len(r[1]), r[2]) for r in runs[0]] == \
        [(r[0], len(r[1]), r[2]) for r in runs[2]]
    assert all(1 <= r[2] <= t["output"]["max"] and
               len(r[1]) + r[2] <= 1024 for r in first)
    assert all(t["output"]["min"] <= r[2] <= t["output"]["max"]
               for r in later)
    assert all(t["prompt"]["min"] <= len(r[1]) <= t["prompt"]["max"]
               for r in later)
    assert len({r[0] for r in runs[0]}) == len(runs[0])
    # a client stands, on average, in the middle of a LONG answer: the
    # first requests hold more context than fresh ones, and have less to say
    mean = lambda v: sum(v) / len(v)
    assert mean([len(r[1]) for r in first]) > \
        1.5 * mean([len(r[1]) for r in later])
    assert mean([len(r[1]) + r[2] for r in first]) > \
        1.2 * mean([len(r[1]) + r[2] for r in later])


def test_stalled_requests_fail_and_cut_ones_read_whole_gaps():
    from replicatinggpt_tpu.serve.requests import (FINISH_CANCELLED,
                                                   FINISH_MAX_TOKENS,
                                                   RequestResult)

    def sent(n, reason, ttft_s, end_s):
        s = serving.Sent(req=None, due=1.0, submitted=1.5)
        s.result = RequestResult(id="x", tokens=[0] * n,
                                 finish_reason=reason, ttft_s=ttft_s,
                                 total_s=end_s - 1.5, queue_wait_s=0.1)
        s.cut_at = end_s
        return s
    done = [sent(11, FINISH_MAX_TOKENS, 0.5, 4.0) for _ in range(5)]
    cut = sent(9, FINISH_CANCELLED, 0.5, 3.6)       # 8 gaps in 1.6 s
    slow = sent(9, FINISH_CANCELLED, 0.5, 22.0)     # 8 gaps in 20 s
    times = [serving.request_times(s) for s in done + [cut, slow]]
    assert times[0]["ttft_ms"] == pytest.approx(1000.0)   # from DUE
    assert times[0]["tpot_ms"] == pytest.approx(200.0)
    assert times[5]["cut"] and times[5]["tpot_ms"] == pytest.approx(200.0)
    assert serving.stalled(times) == [False] * 6 + [True]
    assert serving.request_times(sent(0, FINISH_CANCELLED, 0, 2.0)) is None


class _HandMadeEngine:
    """What ``serving.drive`` needs of an engine: every request in it gets
    one token a step of ``step_s``; a cancel surfaces from the next step."""

    def __init__(self, step_s):
        from replicatinggpt_tpu.serve import requests
        self.requests, self.step_s = requests, step_s
        self.live, self.cancelled = {}, []

    @property
    def idle(self):
        return not self.live and not self.cancelled

    def submit(self, req):
        self.live[req.id] = [req, [], time.monotonic(), None]

    def cancel(self, rid):
        self.cancelled.append(self.live.pop(rid))

    def _result(self, rec, reason):
        req, tokens, t_submit, t_first = rec
        return self.requests.RequestResult(
            id=req.id, tokens=tokens, finish_reason=reason,
            ttft_s=(t_first or t_submit) - t_submit,
            total_s=time.monotonic() - t_submit)

    def step(self):
        time.sleep(self.step_s)
        out = [self._result(rec, self.requests.FINISH_CANCELLED)
               for rec in self.cancelled]
        self.cancelled = []
        for rid, rec in list(self.live.items()):
            rec[1].append(0)
            rec[3] = rec[3] or time.monotonic()
            if len(rec[1]) >= rec[0].max_new_tokens:
                out.append(self._result(self.live.pop(rid),
                                        self.requests.FINISH_MAX_TOKENS))
        return out


class _SlowTracer:
    """What ``serving.drive`` needs of a tracer, with a profiler that takes
    ``stop_s`` to stop, as the real one takes 3.5 s on the chip's host; it
    notes when it was asked to."""

    def __init__(self, span_s, stop_s):
        self.span_s, self.stop_s = span_s, stop_s
        self.state, self.stopped_at = "idle", None

    def tick(self, since_close):
        if self.state == "idle" and since_close >= 0:
            self.state = "on"
        elif self.state == "on" and since_close >= self.span_s:
            self.state = "spanned"

    def close(self):
        if self.state in ("on", "spanned"):
            self.stopped_at = time.monotonic()
            time.sleep(self.stop_s)
        self.state = "done"


@pytest.mark.parametrize("traced", [False, True])
def test_the_profilers_stop_stalls_no_request_of_a_hand_made_schedule(traced):
    """An open loop on a hand-made schedule: five short requests that finish
    and four long ones still decoding when the callers hang up, at 2 ms a
    token. The trace ends 0.15 s before the callers hang up and its profiler
    takes 0.4 s to stop, 200 tokens' time of every request still decoding:
    it is stopped AFTER the hang-up, so ``cut_at`` is the instant the last
    step ended and nobody counts as stalled (with the stop inside the loop a
    traced chat run counted 2 of 41, PERF.md PR 28, and this test 4 of 9)."""
    from replicatinggpt_tpu.serve.requests import Request
    req = lambda i, n: Request(id=f"r{i}", prompt=np.zeros(4, np.int32),
                               max_new_tokens=n)
    due = ([serving.Sent(req=req(i, 20), due=0.02 * i) for i in range(5)]
           + [serving.Sent(req=req(5 + i, 10_000), due=0.1 + 0.01 * i)
              for i in range(4)])
    tracer = (_SlowTracer(0.05, 0.4) if traced
              else serving.Tracer(None, 0.0))
    marks = {}
    t0 = time.monotonic()
    sent = serving.drive(
        _HandMadeEngine(0.002), due=due, on_finish=lambda s: None,
        t_open=0.0, t_close=0.2, t_give_up=0.4, tracer=tracer,
        at_open=lambda: marks.setdefault("open", time.monotonic()),
        at_close=lambda: marks.setdefault("close", time.monotonic()),
        hang_up=True)
    assert len(sent) == 9 and set(marks) == {"open", "close"}
    times = [serving.request_times(s) for s in sent]
    assert all(times) and sum(x["cut"] for x in times) == 4
    assert serving.stalled(times) == [False] * 9
    for s, x in zip(sent[5:], times[5:]):
        # every long request decoded from its first token to the hang-up:
        # its gaps are the engine's 2 ms and some, not the profiler's stop
        assert x["tpot_ms"] < 20.0, x
    if traced:
        assert tracer.state == "done" and tracer.stopped_at >= t0 + 0.4
        assert all(t0 + s.cut_at <= tracer.stopped_at for s in sent[5:])


def test_exponential_gaps_fill_the_span():
    gaps = serving.exponential_gaps(400, 20.0)
    assert gaps.sum() == pytest.approx(20.0) and (gaps > 0).all()
    # the exponential's coefficient of variation is 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_reference_against_the_program_at_test_tiny():
    import jax
    from chipbench import common, reference
    from replicatinggpt_tpu.models import gpt
    mcfg = common.program_config(
        ["--preset", "test-tiny", "--attention", "einsum"]).model
    assert mcfg.dtype == "float32"
    params = gpt.init_params(jax.random.PRNGKey(5), mcfg)
    rng = np.random.default_rng(5)
    seq = rng.integers(0, mcfg.vocab_size, (2, mcfg.block_size + 1),
                       dtype=np.int32)
    x, y = seq[:, :-1], seq[:, 1:]
    logits, loss = gpt.forward(params, x, mcfg, targets=y)
    pos = np.tile(np.arange(mcfg.block_size, dtype=np.int32), (2, 1))
    ours = reference.logits_at(params, x, pos, mcfg.n_head)
    # float32 both sides, different order of operations
    np.testing.assert_allclose(np.asarray(ours), np.asarray(logits),
                               atol=2e-5)
    assert float(reference.loss(params, x, y, mcfg.n_head)) == \
        pytest.approx(float(loss), abs=1e-5)
    # a greedy stream of the program's own forward sits on the reference's
    # argmax; a wrong token does not
    prompt = x[0, :8]
    stream = [int(np.asarray(logits)[0, 7].argmax())]
    assert reference.stream_gaps(params, mcfg.n_head, mcfg.block_size,
                                 [prompt], [np.array(stream)]) == [0.0]
    wrong = [int(np.asarray(logits)[0, 7].argmin())]
    assert reference.stream_gaps(params, mcfg.n_head, mcfg.block_size,
                                 [prompt], [np.array(wrong)])[0] > 0.01


def _ev(name, start_us, dur_us):
    return [name, start_us * 1e3, dur_us * 1e3]


def test_trace_arithmetic_on_a_hand_made_case():
    ops = [_ev("while", 0, 100),            # container: 0..100
           _ev("fusion.1", 0, 40),          # leaf
           _ev("all-gather.2", 30, 30),     # overlaps fusion.1 by 10 us
           _ev("fusion.3", 50, 50),         # leaf, covers 50..60 of it
           _ev("fusion.1", 150, 50)]        # after a 50 us gap
    trace = {"devices": {"/device:TPU:0": {
        "XLA Ops": ops,
        "XLA Modules": [_ev("jit_step(1)", 0, 100),
                        _ev("jit_step(1)", 150, 50)]}},
        "host": [_ev("chipbench/engine_step", 90, 30),
                 _ev("serve/decode", 100, 10),
                 _ev("chipbench/submit", 120, 40)]}
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 12)]) == \
        [(0, 2), (3, 5)]
    b = trace_reduce.busy(trace)
    assert b["window_s"] == pytest.approx(200e-6)
    assert b["busy_s"] == pytest.approx(150e-6)
    assert b["idle_share_mean"] == pytest.approx(0.25)
    selfs = trace_reduce.self_times(
        [_ev("while", 0, 100), _ev("fusion.1", 0, 40),
         _ev("fusion.3", 50, 50), _ev("fusion.1", 150, 50)])
    assert selfs["while"] == pytest.approx(10e-6)
    assert selfs["fusion.1"] == pytest.approx(90e-6)
    assert trace_reduce.device_ops(trace)[0][0] == "fusion.1"
    assert [e[0] for e in trace_reduce.leaves(ops)] == \
        ["fusion.1", "all-gather.2", "fusion.3", "fusion.1"]
    # the gap 100..150: its middle (125) lies in chipbench/submit
    assert trace_reduce.idle_gaps(trace) == [["chipbench/submit",
                                              pytest.approx(50e-6)]]
    assert trace_reduce.launches(trace, r"^jit_step") == 2
    assert trace_reduce.pattern_per_launch(
        trace, r"^fusion\.1", r"^jit_step", scale=1e6) == pytest.approx(45.0)
    assert trace_reduce.pattern_per_launch(trace, r"^nothing",
                                           r"^jit_step") is None


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench", "testdata"))
    if f.endswith(".json")))
def test_trace_reduction_on_a_trace_recorded_on_the_chip(name):
    """A few hundred events cut from a --trace 1 run on the v5e, with what
    the reduction made of them when they were recorded."""
    with open(os.path.join(ROOT, "chipbench", "testdata",
                           name + ".json")) as f:
        doc = json.load(f)
    trace, want = doc["trace"], doc["expect"]
    red = trace_reduce.reduce(trace)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert [n for n, _ in red["device_ops"]] == want["device_ops"]
    ops = next(iter(trace["devices"].values()))["XLA Ops"]
    total = sum(trace_reduce.self_times(ops).values())
    assert total == pytest.approx(
        trace_reduce.measure(trace_reduce._spans(ops)) / 1e9, rel=1e-6)
    for pattern, seconds in want["pattern_s"].items():
        rx = re.compile(pattern)
        got = sum(e[2] for e in trace_reduce.leaves(ops)
                  if rx.search(e[0])) / 1e9
        assert got == pytest.approx(seconds, rel=1e-9)


def test_run_without_a_tpu_fails_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "train-medium", "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"metrics"' not in p.stdout
