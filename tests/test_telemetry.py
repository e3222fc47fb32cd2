"""Request-lifecycle tracing + telemetry export (utils/telemetry.py,
tools/trace_check.py): zero-cost disabled mode (no buffer growth, the
shared null span, GL004-clean with zero pragmas), the three exporters
(Perfetto Chrome trace validated by trace_check, metrics-timeline
JSONL, Prometheus text), per-request span trees with
prefix-hit/COW/recovery markers from a real shared-prefix replay, and
the torn-tail-tolerant JSONL sink."""

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from replicatinggpt_tpu.config import ModelConfig
from replicatinggpt_tpu.faults.watchdog import (LoadShedder,
                                                ResilienceConfig,
                                                SpecHealth, StepWatchdog)
from replicatinggpt_tpu.models.gpt import init_params
from replicatinggpt_tpu.serve import (Engine, EngineConfig, ReplayConfig,
                                      Request, RequestJournal,
                                      SamplingParams, run_replay)
from replicatinggpt_tpu.utils.logging import Metrics
from replicatinggpt_tpu.utils.telemetry import (ENGINE_TRACK, NULL,
                                                MetricsTimeline,
                                                Telemetry,
                                                chrome_trace_from_jsonl,
                                                load_jsonl,
                                                prometheus_text)

REPO = Path(__file__).resolve().parent.parent

CFG = ModelConfig(vocab_size=65, block_size=32, n_layer=2, n_head=2,
                  n_embd=32, dropout=0.0, attn_dropout=0.0,
                  dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _trace_check():
    spec = importlib.util.spec_from_file_location(
        "trace_check", REPO / "tools" / "trace_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _greedy(rid, prompt, max_new=4):
    return Request(id=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new,
                   sampling=SamplingParams(greedy=True))


def _names(tel):
    return {ev["name"] for ev in tel.events}


# ---------------------------------------------------------------------------
# disabled mode: zero cost, zero state, zero lint findings (satellite)
# ---------------------------------------------------------------------------

def test_null_telemetry_is_stateless_and_allocation_free():
    """The disabled recorder accumulates nothing — the structural pin
    behind the 'disabled telemetry changes nothing' claim (events is a
    tuple: it CANNOT grow) — and has no ``span``: ``phase`` is the one
    way to mark a phase."""
    assert not NULL.enabled
    assert not hasattr(NULL, "span") and not hasattr(Telemetry, "span")
    with NULL.phase("a", 3, x=1), NULL.phase("b"):
        pass
    NULL.begin("a"), NULL.end("a"), NULL.instant("m", step=1)
    NULL.complete("x", 0, 0.0, 1.0)
    NULL.name_track(0, "engine")
    assert NULL.now_us() == 0.0 and NULL.ts_us(123.0) == 0.0
    assert NULL.events == ()
    NULL.close()


def test_null_phase_is_the_bare_annotation_and_records_nothing():
    """``NULL.phase`` is ``profiling.annotate`` and nothing else: a
    ``TraceAnnotation`` a profiler capture would see, no event, no
    buffer that could grow."""
    ph = NULL.phase("serve/launch", 7, k=1, live_tokens=12)
    assert isinstance(ph, jax.profiler.TraceAnnotation)
    for _ in range(100):
        with NULL.phase("serve/step", step=3):
            with NULL.phase("serve/fetch"):
                pass
    assert NULL.events == () and not NULL.enabled
    assert not hasattr(NULL, "__dict__") or not vars(NULL)


def test_phase_records_one_x_event_with_its_args():
    """``Telemetry.phase`` enters the same annotation and keeps ONE X
    event with the counters as args."""
    t = [0.0]
    tel = Telemetry(clock=lambda: t[0])
    with tel.phase("serve/launch", 5, k=2, live_tokens=40):
        t[0] = 0.004
    assert list(tel.events) == [
        {"ph": "X", "name": "serve/launch", "tid": 5, "ts": 0.0,
         "dur": pytest.approx(4000.0),
         "args": {"k": 2, "live_tokens": 40}}]
    with tel.phase("train/data"):
        pass
    assert tel.events[-1]["name"] == "train/data"
    assert tel.events[-1]["tid"] == ENGINE_TRACK
    assert "args" not in tel.events[-1]


def _profiler_phases(logdir):
    """``(name, start, end, stats)`` of every ``serve/*`` / ``train/*``
    event of the capture under ``logdir``, by start."""
    import glob
    path = sorted(glob.glob(str(Path(logdir) / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve/", "train/")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


def test_profiler_capture_holds_engine_phases_and_live_context(params,
                                                               tmp_path):
    """With NO recorder attached, a ``jax.profiler`` capture of a tiny
    engine run holds the phases on its own clock: every ``serve/launch``
    carries ``live_tokens`` equal to the host mirrors' sum at that launch
    (and ``live_kv_bytes`` = that x the pool's bytes a token), and
    ``serve/launch``, ``serve/fetch``, ``serve/commit`` nest inside a
    ``serve/step``."""
    eng = Engine(params, CFG, EngineConfig(pool_size=3, max_queue=8,
                                           page_size=4))
    assert eng.tel is NULL
    for i in range(4):
        eng.submit(_greedy(f"w{i}", [1 + i, 2, 3], max_new=3))
    eng.drain()                                   # every program compiled
    want = []
    dispatch = eng._dispatch

    def spy(k, kill, n_active, *a):
        want.append(int((eng._pos + 1)[eng._active & ~kill].sum()))
        return dispatch(k, kill, n_active, *a)

    eng._dispatch = spy
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(5):
            eng.submit(_greedy(f"r{i}", 10 * i + np.arange(1, 6 + i),
                               max_new=4))      # no shared prefix
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    ev = _profiler_phases(tmp_path)
    launches = [e for e in ev if e[0] == "serve/launch"]
    assert want and [e[3]["live_tokens"] for e in launches] == want
    per_token = (sum(a.nbytes for a in eng.pool.cache.values())
                 // (eng.pool.n_pages * eng.pool.page_size))
    assert per_token == 2 * CFG.n_layer * CFG.n_embd * 4      # K and V, f32
    assert all(e[3]["live_kv_bytes"] == e[3]["live_tokens"] * per_token
               and e[3]["k"] == 1 for e in launches)
    steps = [e for e in ev if e[0] == "serve/step"]
    for name in ("serve/launch", "serve/fetch", "serve/commit",
                 "serve/expire_shed", "serve/admit", "serve/prefill",
                 "serve/decode"):
        inner = [e for e in ev if e[0] == name]
        assert inner, name
        assert all(any(s[1] <= e[1] and e[2] <= s[2] for s in steps)
                   for e in inner), name
    assert len([e for e in ev if e[0] == "serve/fetch"]) == len(launches)
    pre = [e for e in ev if e[0] == "serve/prefill"]
    assert sorted(e[3]["tokens"] for e in pre) == [5, 6, 7, 8, 9]
    assert all(e[3]["cached_tokens"] == 0 and e[3]["chunks"] >= 1
               for e in pre)


def test_profiler_capture_holds_train_phases(tmp_path):
    """A tiny train run with ``profile_dir`` and no recorder: the
    capture holds ``train/data``, ``train/dispatch`` (with its step) and
    ``train/fetch_loss`` of the traced steps."""
    import dataclasses as dc
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.train.runner import train
    from replicatinggpt_tpu.utils.logging import StepLogger
    cfg = get_config("test-tiny")
    cfg = cfg.replace(tokenizer="char", train=dc.replace(
        cfg.train, max_iters=4, eval_interval=0, eval_iters=1,
        log_interval=1, batch_size=2))
    train(cfg, logger=StepLogger(quiet=True), profile_dir=str(tmp_path),
          profile_start=1, profile_steps=2)
    ev = _profiler_phases(tmp_path)
    names = [e[0] for e in ev]
    assert names.count("train/dispatch") == 2
    assert [e[3]["step"] for e in ev if e[0] == "train/dispatch"] == [1, 2]
    assert names.count("train/data") == 2
    assert names.count("train/fetch_loss") == 2


def test_engine_without_telemetry_holds_null_and_records_nothing(params):
    """Default engine construction wires the NULL recorder end to end
    (engine, paged pool, allocator) and a full replay leaves no
    telemetry state anywhere — the disabled serve step path is the
    seed's."""
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8))
    assert eng.tel is NULL
    assert eng.pool.alloc.tel is NULL
    for i in range(3):
        assert eng.submit(_greedy(f"r{i}", [1 + i, 2, 3])) is None
    res = eng.drain()
    assert len(res) == 3
    assert NULL.events == ()


def test_telemetry_module_is_gl004_clean_with_zero_pragmas():
    """The recorder is called from inside engine/runner step loops, so
    it must contain NO host-sync sites (float()/.item()/np.asarray/
    device_get) and claim NO pragma exemptions — graftlint's dataflow
    would otherwise propagate a sync into every instrumented loop.
    (The whole-project baseline gate in test_lint.py enforces the
    instrumented call sites themselves.)"""
    from replicatinggpt_tpu.analysis import lint_paths
    for rel in ("replicatinggpt_tpu/utils/telemetry.py",
                "tools/trace_check.py"):
        path = REPO / rel
        assert "graftlint: disable" not in path.read_text(), rel
        res = lint_paths([path], severity={})
        assert not res.findings, (rel, res.findings)


# ---------------------------------------------------------------------------
# Metrics.hist_summary schema (satellite)
# ---------------------------------------------------------------------------

def test_metrics_hist_summary_schema_pinned():
    """Exporters (Prometheus summaries, the timeline) index hist_summary
    keys directly — pin the schema, including the new ``min``."""
    m = Metrics()
    assert set(m.hist_summary("empty")) == set(Metrics.HIST_KEYS)
    for v in (5.0, 1.0, 3.0):
        m.observe("lat", v)
    h = m.hist_summary("lat")
    assert set(h) == set(Metrics.HIST_KEYS) == {
        "n", "mean", "min", "p50", "p90", "p99", "max"}
    assert h["n"] == 3 and h["min"] == 1.0 and h["max"] == 5.0
    assert h["mean"] == pytest.approx(3.0)
    assert set(m.summary()) == {"counters", "gauges", "histograms"}


# ---------------------------------------------------------------------------
# recorder + exporters (unit)
# ---------------------------------------------------------------------------

def test_ring_buffer_bounded():
    tel = Telemetry(capacity=8)
    for i in range(100):
        tel.instant("m", step=i)
    assert len(tel.events) == 8
    assert tel.events[0]["args"]["step"] == 92    # oldest dropped


def test_phase_nests_and_exports_chrome_trace(tmp_path):
    t = [0.0]
    tel = Telemetry(clock=lambda: t[0])
    tel.name_track(0, "engine")
    tel.begin("request", 1, ts_us=0.0, request="r1")
    t[0] = 0.001
    with tel.phase("work", 1, request="r1"):
        t[0] = 0.002
    t[0] = 0.003
    tel.end("request", 1, ts_us=tel.now_us(), request="r1")
    out = tmp_path / "trace.json"
    n = tel.export_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    assert len(doc["traceEvents"]) == n
    tc = _trace_check()
    assert tc.check_trace(str(out), min_requests=1) == []


def test_jsonl_sink_tolerates_torn_tail(tmp_path):
    """The sink's reason to exist is the crash window: a torn final
    line must not poison the offline trace assembly."""
    sink = tmp_path / "events.jsonl"
    tel = Telemetry(jsonl_path=str(sink))
    tel.begin("request", 1, ts_us=0.0, request="r1")
    tel.instant("marker", 1)
    tel.end("request", 1, ts_us=5.0, request="r1")
    tel.close()
    with open(sink, "a") as f:
        f.write('{"ph": "i", "name": "torn')     # crash mid-write
    evs = load_jsonl(str(sink))
    assert [e["ph"] for e in evs] == ["B", "i", "E"]
    out = tmp_path / "trace.json"
    assert chrome_trace_from_jsonl(str(sink), str(out)) == 3
    tc = _trace_check()
    assert tc.check_trace(str(out), min_requests=1) == []


def test_shared_jsonl_reader_contract_and_dedup(tmp_path):
    """utils.jsonl is THE torn-tail reader: telemetry and the request
    journal import it rather than carrying private copies, and its
    contract (skip blank, skip unparseable, missing file == empty
    history) is pinned here once for all three consumers."""
    from replicatinggpt_tpu.serve import journal as journal_mod
    from replicatinggpt_tpu.utils import jsonl as jsonl_mod
    from replicatinggpt_tpu.utils import telemetry as telemetry_mod

    # dedup: both consumers resolve to the one shared implementation
    assert telemetry_mod.load_jsonl is jsonl_mod.load_jsonl
    assert (journal_mod.load_jsonl_if_exists
            is jsonl_mod.load_jsonl_if_exists)

    p = tmp_path / "records.jsonl"
    p.write_text('{"a": 1}\n'
                 '\n'                        # blank line
                 'not json at all\n'         # interior corruption
                 '{"b": 2}\n'
                 '{"c": 3, "torn')           # crash mid-write
    assert jsonl_mod.load_jsonl(str(p)) == [{"a": 1}, {"b": 2}]
    assert list(jsonl_mod.iter_jsonl(str(p))) == [{"a": 1}, {"b": 2}]
    # a journal that was never created is an empty history, not an error
    assert jsonl_mod.load_jsonl_if_exists(str(tmp_path / "never")) == []
    with pytest.raises(FileNotFoundError):
        jsonl_mod.load_jsonl(str(tmp_path / "never"))


def test_metrics_timeline_interval_and_forced_final(tmp_path):
    t = [0.0]
    m = Metrics()
    m.inc("steps")
    path = tmp_path / "tl.jsonl"
    tl = MetricsTimeline(m, str(path), interval_s=1.0, clock=lambda: t[0])
    tl.snapshot(step=0)
    t[0] = 0.5
    assert not tl.maybe_snapshot(step=1)          # inside the interval
    t[0] = 1.5
    m.inc("steps")
    assert tl.maybe_snapshot(step=2)
    tl.close(step=3)                              # forced final point
    rows = MetricsTimeline.load(str(path))
    assert len(rows) == 3 == tl.n_snapshots
    assert rows[0]["counters"]["steps"] == 1
    assert rows[1]["counters"]["steps"] == 2
    assert rows[-1]["step"] == 3
    assert rows[1]["t_s"] == pytest.approx(1.5)


def test_prometheus_text_exposition():
    m = Metrics()
    m.inc("requests_admitted", 3)
    m.gauge("queue depth!", 7)                    # needs sanitizing
    for v in (0.1, 0.2, 0.3):
        m.observe("ttft_s", v)
    txt = prometheus_text(m, prefix="tpu_gpt",
                          extra_gauges={"pages_in_use": 5})
    assert "# TYPE tpu_gpt_requests_admitted counter" in txt
    assert "tpu_gpt_requests_admitted 3" in txt
    assert "# TYPE tpu_gpt_queue_depth_ gauge" in txt
    assert "tpu_gpt_pages_in_use 5" in txt
    assert "# TYPE tpu_gpt_ttft_s summary" in txt
    assert 'tpu_gpt_ttft_s{quantile="0.5"} 0.2' in txt
    assert "tpu_gpt_ttft_s_count 3" in txt
    assert "tpu_gpt_ttft_s_min 0.1" in txt
    assert "tpu_gpt_ttft_s_sum" in txt
    # full precision: a big counter must not collapse to %g notation
    # (1.23457e+06 would corrupt every rate computed from the scrape)
    m.inc("decode_tokens", 1_234_567)
    assert "tpu_gpt_decode_tokens 1234567" in prometheus_text(
        m, prefix="tpu_gpt")


def test_artifact_paths_overwrite_not_append(tmp_path):
    """A reused --trace-out/--metrics-timeline path holds ONE run: the
    JSONL sink and timeline open 'w' (appending a rerun would duplicate
    request envelopes, which trace_check rightly rejects)."""
    sink = tmp_path / "events.jsonl"
    for _ in range(2):
        tel = Telemetry(jsonl_path=str(sink))
        tel.begin("request", 1, ts_us=0.0, request="r1")
        tel.end("request", 1, ts_us=5.0, request="r1")
        tel.close()
    assert len(load_jsonl(str(sink))) == 2        # second run only
    out = tmp_path / "trace.json"
    chrome_trace_from_jsonl(str(sink), str(out))
    assert _trace_check().check_trace(str(out), min_requests=1) == []
    tl = tmp_path / "tl.jsonl"
    m = Metrics()
    for _ in range(2):
        t = MetricsTimeline(m, str(tl))
        t.snapshot(step=0)
        t.close(step=1)
    assert len(MetricsTimeline.load(str(tl))) == 2


# ---------------------------------------------------------------------------
# recovery markers (faults/watchdog.py, faults/supervise.py seam)
# ---------------------------------------------------------------------------

def test_watchdog_policies_emit_instant_markers():
    tel = Telemetry()
    rcfg = ResilienceConfig(stall_factor=2.0, stall_floor_s=0.0,
                            stall_min_steps=4, stall_skip_steps=0,
                            spec_disable_threshold=0.5, spec_window=2,
                            shed_watermark=0.25, shed_patience=1)
    wd = StepWatchdog(rcfg, telemetry=tel)
    for _ in range(8):
        wd.observe(0.01)
    assert wd.observe(10.0)                       # stall
    sh = SpecHealth(rcfg, telemetry=tel)
    sh.observe(4, 0)
    assert sh.observe(4, 0)                       # accept-rate collapse
    sh.on_disable()
    for _ in range(rcfg.spec_reprobe_after):
        if sh.tick_disabled():
            break
    sh.on_reenable()
    shd = LoadShedder(rcfg, telemetry=tel)
    assert shd.observe(depth=8, max_queue=8) > 0
    names = _names(tel)
    assert {"watchdog_stall", "spec_disable", "spec_reprobe",
            "spec_probe_healthy", "load_shed"} <= names


def test_journal_replay_marker(tmp_path):
    tel = Telemetry()
    path = str(tmp_path / "journal.jsonl")
    j = RequestJournal(path)
    j.record_submit(_greedy("a", [1, 2]))
    j.record_submit(_greedy("b", [3, 4]))
    j.record_finish("a", "max_tokens")
    j.close()
    reqs = RequestJournal.unfinished(path, telemetry=tel)
    assert [r.id for r in reqs] == ["b"]
    ev = [e for e in tel.events if e["name"] == "journal_replay"]
    assert len(ev) == 1 and ev[0]["args"]["requeued"] == 1


# ---------------------------------------------------------------------------
# engine span trees: prefix hits, COW, full replay acceptance
# ---------------------------------------------------------------------------

def test_engine_trace_has_request_tree_prefix_hit_and_cow(params, tmp_path):
    """Two identical page-aligned prompts back to back: the second is a
    full-prompt radix hit, which takes the copy-on-write path — the
    trace must carry the complete span tree for both requests plus the
    prefix_hit and cow_split markers, and validate."""
    tel = Telemetry()
    eng = Engine(params, CFG, EngineConfig(pool_size=2, max_queue=8,
                                           page_size=4),
                 telemetry=tel)
    prompt = np.arange(1, 9, dtype=np.int32)      # 8 tokens = 2 full pages
    eng.submit(_greedy("a", prompt, max_new=5))
    eng.drain()
    eng.submit(_greedy("b", prompt, max_new=5))
    eng.drain()
    assert eng.pool.alloc.cow_copies == 1         # scenario sanity
    names = _names(tel)
    assert {"request", "queue", "admit", "prefill_chunk", "decode",
            "serve/step", "serve/admit", "serve/prefill", "serve/decode",
            "serve/launch", "serve/fetch", "serve/commit", "prefix_hit",
            "cow_split"} <= names
    # one name a region: the engine track's old envelopes are gone
    assert not {"engine_step", "decode_step"} & names
    out = tmp_path / "trace.json"
    tel.export_chrome_trace(str(out))
    tc = _trace_check()
    assert tc.check_trace(str(out), min_requests=2) == []
    # the request trees live on per-slot tracks, markers carry args
    cow = [e for e in tel.events if e["name"] == "cow_split"]
    assert cow and cow[0]["args"]["request"] == "b"
    doc = json.loads(out.read_text())
    thread_names = {e["args"]["name"] for e in doc["traceEvents"]
                    if e.get("name") == "thread_name"}
    assert "engine" in thread_names and "slot 0" in thread_names


def test_shared_prefix_replay_emits_all_three_artifacts(params, tmp_path):
    """The acceptance run: a CPU shared-prefix replay emits (a) a
    Perfetto-loadable trace with one complete nested span tree per
    request, (b) a metrics-timeline JSONL with >= 2 snapshots, (c)
    Prometheus text — all validated."""
    tr = str(tmp_path / "trace.json")
    tl = str(tmp_path / "timeline.jsonl")
    mo = str(tmp_path / "metrics.prom")
    s = run_replay(params, CFG,
                   ReplayConfig(n_requests=12, rate=5000.0, seed=3,
                                prompt_len_min=10, prompt_len_max=16,
                                shared_prefix_len=8, max_new_tokens=4,
                                greedy=True, prompt_mode="shared_prefix"),
                   EngineConfig(pool_size=4, max_queue=32, page_size=8),
                   trace_out=tr, metrics_timeline=tl, metrics_out=mo)
    assert s["n_completed"] == 12
    assert s["recompiles_after_warmup"] == 0      # tracing adds no compiles
    art = s["artifacts"]
    assert art["trace_out"] == tr and art["trace_events"] > 0
    # (a) Perfetto trace: every request's spans nest and close, with
    # prefix-hit markers from the radix cache on the same timeline
    tc = _trace_check()
    assert tc.check_trace(tr, min_requests=12) == []
    doc = json.loads(Path(tr).read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"request", "queue", "admit", "decode", "prefix_hit"} <= names
    # (b) metrics timeline: >= 2 snapshots, full Metrics schema each
    rows = MetricsTimeline.load(tl)
    assert len(rows) >= 2 and art["metrics_timeline_snapshots"] >= 2
    for row in rows:
        assert {"t_s", "step", "counters", "gauges",
                "histograms"} <= set(row)
    assert (rows[-1]["counters"]["requests_admitted"] == 12)
    # (c) Prometheus text: counters + summary quantiles + pages gauges
    txt = Path(mo).read_text()
    assert "# TYPE tpu_gpt_requests_admitted counter" in txt
    assert "tpu_gpt_requests_admitted 12" in txt
    assert 'tpu_gpt_ttft_s{quantile="0.99"}' in txt
    assert "tpu_gpt_pages_in_use" in txt


def test_run_replay_flushes_artifacts_on_midrun_crash(params, tmp_path,
                                                      monkeypatch):
    """A replay that dies mid-run must still export the trace and
    force-close the timeline (and stop the profiler) — the crash
    window is exactly when the artifacts matter."""
    from replicatinggpt_tpu.serve import replay as replay_mod
    real_step = replay_mod.Engine.step
    calls = {"n": 0}

    def boom(self):
        calls["n"] += 1
        if calls["n"] > 3:
            raise RuntimeError("injected mid-replay crash")
        return real_step(self)

    monkeypatch.setattr(replay_mod.Engine, "step", boom)
    tr = str(tmp_path / "t.json")
    tl = str(tmp_path / "tl.jsonl")
    with pytest.raises(RuntimeError, match="injected"):
        run_replay(params, CFG,
                   ReplayConfig(n_requests=8, rate=5000.0, seed=0,
                                prompt_len_max=8, max_new_tokens=6,
                                greedy=True),
                   EngineConfig(pool_size=2, max_queue=16),
                   warmup=False, trace_out=tr, metrics_timeline=tl)
    doc = json.loads(Path(tr).read_text())
    assert any(e.get("name") == "request" for e in doc["traceEvents"])
    assert len(MetricsTimeline.load(tl)) >= 2     # attach + forced final


def test_decode_window_spans_and_token_instants(params, tmp_path):
    """Async-engine telemetry: one decode X span per DISPATCH carrying
    ``k`` and tokens-emitted args, multiple per-request ``token``
    instants inside a window span with strictly increasing indices —
    and the whole trace still validates through trace_check."""
    from replicatinggpt_tpu.serve import EngineConfig, ReplayConfig
    out = tmp_path / "window_trace.json"
    rcfg = ReplayConfig(n_requests=6, rate=50_000.0, seed=3,
                        prompt_len_min=4, prompt_len_max=8,
                        max_new_tokens=12, greedy=True)
    s = run_replay(params, CFG, rcfg,
                   EngineConfig(pool_size=3, max_queue=16,
                                decode_window=4),
                   trace_out=str(out))
    assert s["n_completed"] == 6
    assert s["recompiles_after_warmup"] == 0
    tc = _trace_check()
    assert tc.check_trace(str(out), min_requests=6) == []
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    # engine-track phases: a launch carries k and the live context, the
    # commit of its window k and the tokens it emitted; some are real
    # windows
    launches = [e for e in evs if e.get("ph") == "X"
                and e.get("name") == "serve/launch"]
    assert launches and all(
        {"k", "n_active", "live_tokens", "live_kv_bytes"} <= set(e["args"])
        for e in launches)
    steps = [e for e in evs if e.get("ph") == "X"
             and e.get("name") == "serve/commit"]
    assert steps and all("k" in e["args"] and "tokens" in e["args"]
                         for e in steps)
    assert any(e["args"]["k"] == 4 and e["args"]["tokens"] > 1
               for e in steps), "no multi-token window span in trace"
    # slot-track decode spans: one per dispatch per live request, with
    # the window's token count
    slot_spans = [e for e in evs if e.get("ph") == "X"
                  and e.get("name") == "decode"]
    assert any(e["args"].get("tokens", 0) > 1 for e in slot_spans)
    # token instants: > 1 per window span, strictly increasing per id
    toks = [e for e in evs if e.get("ph") == "i"
            and e.get("name") == "token"]
    assert toks
    by_req = {}
    for e in toks:
        by_req.setdefault(e["args"]["request"], []).append(
            e["args"]["index"])
    for rid, idxs in by_req.items():
        assert idxs == sorted(idxs) and len(set(idxs)) == len(idxs), \
            (rid, idxs)
    assert any(len(v) > 4 for v in by_req.values())


def test_trace_check_rejects_bad_token_indices(tmp_path):
    """The window-delivery check has teeth: duplicate / backwards /
    non-int token indices fail, a well-formed multi-token window
    passes."""
    tc = _trace_check()

    def write(tokens):
        env = [{"ph": "B", "name": "request", "tid": 1, "ts": 0.0,
                "args": {"request": "r"}}]
        env += [{"ph": "i", "name": "token", "tid": 1, "ts": 1.0 + i,
                 "args": {"request": "r", "index": ix}}
                for i, ix in enumerate(tokens)]
        env += [{"ph": "E", "name": "request", "tid": 1, "ts": 50.0,
                 "args": {"request": "r"}}]
        p = tmp_path / "tok.json"
        p.write_text(json.dumps({"traceEvents": env}))
        return str(p)

    assert tc.check_trace(write([1, 2, 3, 4])) == []
    assert tc.check_trace(write([3, 4, 5])) == []   # ring-buffer suffix
    assert tc.check_trace(write([1, 2, 2]))         # duplicate
    assert tc.check_trace(write([2, 1]))            # backwards
    assert tc.check_trace(write([0, 1]))            # index < 1
    assert tc.check_trace(write(["x"]))             # non-int


def test_trace_check_rejects_malformed_traces(tmp_path):
    """The validator actually validates: unclosed envelopes, crossed
    B/E, negative durations, out-of-envelope spans all fail."""
    tc = _trace_check()

    def write(events):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"traceEvents": events}))
        return str(p)

    assert tc.check_trace(str(tmp_path / "missing.json"))
    p = tmp_path / "notjson.json"
    p.write_text("{")
    assert tc.check_trace(str(p))
    # unclosed request envelope
    assert tc.check_trace(write([
        {"ph": "B", "name": "request", "tid": 1, "ts": 0.0,
         "args": {"request": "r"}}]))
    # crossed spans
    assert tc.check_trace(write([
        {"ph": "B", "name": "a", "tid": 1, "ts": 0.0},
        {"ph": "B", "name": "b", "tid": 1, "ts": 1.0},
        {"ph": "E", "name": "a", "tid": 1, "ts": 2.0},
        {"ph": "E", "name": "b", "tid": 1, "ts": 3.0}]))
    # negative duration
    assert tc.check_trace(write([
        {"ph": "X", "name": "x", "tid": 1, "ts": 0.0, "dur": -1.0}]))
    # tagged span outside its request envelope
    assert tc.check_trace(write([
        {"ph": "B", "name": "request", "tid": 1, "ts": 10.0,
         "args": {"request": "r"}},
        {"ph": "X", "name": "decode", "tid": 1, "ts": 0.0, "dur": 2.0,
         "args": {"request": "r"}},
        {"ph": "E", "name": "request", "tid": 1, "ts": 20.0,
         "args": {"request": "r"}}]))
    # min_requests enforced
    assert tc.check_trace(write([]), min_requests=1)
    # and a valid trace still passes through the same writer
    assert tc.check_trace(write([
        {"ph": "B", "name": "request", "tid": 1, "ts": 0.0,
         "args": {"request": "r"}},
        {"ph": "X", "name": "decode", "tid": 1, "ts": 1.0, "dur": 2.0,
         "args": {"request": "r"}},
        {"ph": "E", "name": "request", "tid": 1, "ts": 5.0,
         "args": {"request": "r"}}]), min_requests=1) == []


# ---------------------------------------------------------------------------
# CLI surface (serve-replay flags incl. the mirrored profiler flags)
# ---------------------------------------------------------------------------

def test_serve_replay_cli_observability_flags(tmp_path, capsys):
    from replicatinggpt_tpu.cli import main
    tr = str(tmp_path / "trace.json")
    tl = str(tmp_path / "tl.jsonl")
    mo = str(tmp_path / "m.prom")
    prof = str(tmp_path / "prof")
    rc = main(["serve-replay", "--preset", "test-tiny", "--n-requests",
               "8", "--pool-size", "4", "--rate", "2000",
               "--request-max-new-tokens", "4", "--greedy",
               "--trace-out", tr, "--metrics-timeline", tl,
               "--metrics-out", mo,
               "--profile-dir", prof, "--profile-start", "1",
               "--profile-steps", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "8 completed" in out
    tc = _trace_check()
    assert tc.check_trace(tr, min_requests=8) == []
    assert len(MetricsTimeline.load(tl)) >= 2
    assert "requests_admitted" in Path(mo).read_text()
    # mirrored profiler flags: a real device trace landed next to the
    # span trace, from the same run
    import glob
    assert glob.glob(f"{prof}/**/*.xplane.pb", recursive=True)


def test_trace_check_cli_smoke(tmp_path):
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": [
        {"ph": "B", "name": "request", "tid": 1, "ts": 0.0,
         "args": {"request": "r"}},
        {"ph": "E", "name": "request", "tid": 1, "ts": 5.0,
         "args": {"request": "r"}}]}))
    r = subprocess.run([sys.executable, str(REPO / "tools" /
                                            "trace_check.py"),
                        str(p), "--min-requests", "1"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
    r = subprocess.run([sys.executable, str(REPO / "tools" /
                                            "trace_check.py"),
                        str(p), "--min-requests", "2"],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "expected >= 2" in r.stderr


# ---------------------------------------------------------------------------
# the set-up record: spans, builds, stage intervals as a union
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_record(monkeypatch):
    """A record of this test's own: the process's ONE listener forwards
    to whichever record is current."""
    from replicatinggpt_tpu.utils import telemetry as T
    T.setup_record()
    rec = T.SetupRecord()
    monkeypatch.setattr(T, "_RECORD", rec)
    return rec


def test_interval_union_counts_nested_and_overlapping_once():
    from replicatinggpt_tpu.utils.telemetry import IntervalUnion
    u = IntervalUnion()
    for a, b in [(1.0, 1.4), (0.5, 3.5),        # inner first, then outer
                 (5.0, 6.0), (5.5, 7.0), (4.0, 4.5), (3.0, 4.2)]:
        u.add(a, b)
    assert u.iv == [(0.5, 4.5), (5.0, 7.0)]
    assert u.seconds == pytest.approx(6.0)


def test_a_jit_traced_inside_another_is_counted_once(fresh_record):
    """An inner ``jax.jit`` traced inside its caller fires its own trace
    event INSIDE the caller's: each function keeps its own seconds, the
    set-up total is their union (the outer's interval), not their sum."""
    import jax.numpy as jnp
    from replicatinggpt_tpu.utils.telemetry import setup_phase

    @jax.jit
    def inner_of_setup_test(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer_of_setup_test(x):
        return inner_of_setup_test(x) + jnp.cos(inner_of_setup_test(x + 1))

    x = jnp.ones(8)
    with setup_phase("setup/test"):
        outer_of_setup_test(x).block_until_ready()
    by_fun = fresh_record.trace_by_fun
    outer, inner = (by_fun["outer_of_setup_test"],
                    by_fun["inner_of_setup_test"])
    assert inner > 0 and outer > inner
    total = fresh_record.summary()["trace_s"]
    assert outer <= total + 1e-6
    assert total < outer + inner      # the inner one is not counted twice
    assert fresh_record.summary()["process"]["trace_s"] >= total


def test_a_stage_interval_belongs_to_the_innermost_open_span(fresh_record):
    """Stage events are assigned to the innermost open set-up span; one
    outside every span is not set-up unless a build holds it."""
    from replicatinggpt_tpu.utils import telemetry as T
    ev = "/jax/core/compile/jaxpr_trace_duration"
    T._on_duration(ev, 0.001, fun_name="outside")
    with T.setup_phase("setup/outer"):
        T._on_duration(ev, 0.001, fun_name="in_outer")
        with T.setup_phase("setup/inner", n=3):
            T._on_duration(ev, 0.001, fun_name="in_inner")
    rec = fresh_record
    assert set(rec.trace_by_fun) == {"in_outer", "in_inner"}
    by_span = rec.summary()["stages_by_span"]
    assert set(by_span) == {"setup/outer", "setup/inner"}
    assert by_span["setup/inner"]["trace_s"] > 0
    assert [(n, p, st) for n, p, _, _, st in rec.spans] == [
        ("setup/inner", "setup/outer", {"n": 3}), ("setup/outer", None, {})]
    assert rec.open == []
    # a build claims the stages that ran inside it, in no span
    t0 = rec.clock()
    T._on_duration(ev, 0.0, fun_name="in_build")
    rec.record_build("serve/test", t0, rec.clock())
    assert "in_build" in rec.trace_by_fun
    assert rec.builds[0][0] == "serve/test" and rec.n_builds == 1


def test_the_setup_record_stays_bounded():
    """Totals always; the first ``keep`` spans and builds, the rest
    counted as dropped; the union and the intervals a build may claim
    held to their caps."""
    from replicatinggpt_tpu.utils.telemetry import SetupRecord
    t = [0.0]
    rec = SetupRecord(keep=8, clock=lambda: t[0])
    ev = "/jax/core/compile/backend_compile_duration"
    for i in range(2000):
        t[0] = 10.0 * i + 1.0
        rec.open.append("setup/x")
        rec.on_stage(ev, 0.5, fun_name=f"f{i}")
        rec.open.pop()
        rec.record_span("setup/x", None, 10.0 * i, t[0], {})
        rec.record_build("serve/x", 10.0 * i, t[0])
    assert len(rec.spans) == len(rec.builds) == 8
    assert rec.dropped == {"spans": 1992, "builds": 1992}
    assert len(rec.setup["compile"].iv) <= rec.setup["compile"].cap
    assert len(rec._recent) <= rec._recent.maxlen
    s = rec.summary()
    assert s["compile_s"] == pytest.approx(1000.0)
    assert s["build_s"] == pytest.approx(2000.0) and s["builds"] == 2000


def test_null_phase_leaves_the_setup_record_alone(fresh_record):
    """``NULL.phase`` is unchanged: the bare annotation, no set-up span."""
    with NULL.phase("serve/step"):
        pass
    assert fresh_record.spans == [] and fresh_record.open == []
    assert isinstance(NULL.phase("x"), jax.profiler.TraceAnnotation)


def test_engine_setup_summary_and_builds(params, fresh_record):
    """After a drain, ``metrics_summary()["setup"]`` holds the engine's
    construction and a build of ``serve/decode`` and ``serve/prefill``
    (shapes no other test uses, so the programs are this engine's to
    build); a second identical drain adds no build. The totals are also
    the ``tpu_gpt_setup_*`` gauges."""
    eng = Engine(params, CFG, EngineConfig(pool_size=5, max_queue=8,
                                           prefill_chunk=8))
    for rnd in range(2):
        for i in range(3):
            assert eng.submit(_greedy(f"r{rnd}{i}", [1 + i, 2, 3])) is None
        eng.drain()
        s = eng.metrics_summary()["setup"]
        if rnd == 0:
            first = dict(s["build_s_by_guard"]), s["builds"]
    assert s["engine_s"] > 0
    assert {"serve/decode", "serve/prefill"} <= set(first[0])
    assert (dict(s["build_s_by_guard"]), s["builds"]) == first
    names = {n for n, *_ in fresh_record.spans}
    assert {"setup/engine", "setup/weights_ready", "setup/served_tree",
            "setup/pool", "setup/warm_programs"} <= names
    txt = prometheus_text(eng.metrics)
    for k in ("engine_s", "build_s", "trace_s", "lower_s", "compile_s"):
        assert f"tpu_gpt_setup_{k} " in txt


def test_trainer_records_its_state_and_the_steps_build(fresh_record):
    """``setup/train_state`` around the state's init and placement, and
    the step's first call a build."""
    import dataclasses as dc
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.train.runner import train
    from replicatinggpt_tpu.utils.logging import StepLogger
    cfg = get_config("test-tiny")
    cfg = cfg.replace(tokenizer="char", train=dc.replace(
        cfg.train, max_iters=3, eval_interval=0, eval_iters=1,
        log_interval=1, batch_size=3))
    train(cfg, logger=StepLogger(quiet=True))
    assert "setup/train_state" in fresh_record.span_s
    assert {"train/step", "train/scan"} & set(fresh_record.build_s)
    s = fresh_record.summary()
    assert s["build_s"] > 0 and s["compile_s"] > 0
