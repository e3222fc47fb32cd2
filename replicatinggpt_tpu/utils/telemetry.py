"""Request-lifecycle tracing + unified telemetry export.

The serving engine's only evidence of where time goes used to be one
end-of-run ``metrics_summary()`` dict and a raw ``jax.profiler`` trace
with no request context. This module is the measurement substrate the
scaling roadmap items lean on (per-phase timelines are how the pjit
TPUv4 and Gemma-on-TPU serving playbooks attribute cost): a
zero-cost-when-disabled event/span recorder plus three exporters.

- :class:`Telemetry` — monotonic-clock span/instant recorder over a
  bounded ring buffer (a soak run must not grow host memory without
  bound — the ``Metrics`` reservoir rationale), with an optional
  append-only JSONL sink whose reader tolerates a torn tail (the crash
  window lands mid-write, exactly like ``serve.journal``).
- ``phase(name, **counters)`` — the ONE way the program marks a host
  phase (``serve/launch``, ``train/dispatch``, ...). Both recorders
  enter ``profiling.annotate(name, **counters)``, a
  ``jax.profiler.TraceAnnotation``: the phase is on the profiler's
  timeline, the clock the device ops are on, whether or not a recorder
  is attached, and its counters ride on the event as stats. The enabled
  recorder also keeps the phase as one X event with the counters as
  args. The xplane's clock is neither ``time.monotonic`` nor
  ``time.time``, so the two views are never converted into each other:
  to share the device's clock a phase IS a ``TraceAnnotation``.
- Chrome trace-event JSON (:meth:`Telemetry.export_chrome_trace` /
  :func:`chrome_trace_from_jsonl`) — load the file straight into
  Perfetto (ui.perfetto.dev) or ``chrome://tracing``. The serving
  engine lays requests out as one span tree per request on per-slot
  tracks: request B/E envelope, queue/admit/prefill/decode/verify
  complete-events nested inside, prefix-hit/COW/eviction/recovery
  instants on the same timeline.
- Metrics snapshot timeline (:class:`MetricsTimeline`) — a periodic
  JSONL time series of every counter/gauge/histogram in a
  ``utils.logging.Metrics``, for soak runs where one end-of-run
  summary hides the interesting transient.
- Prometheus text exposition (:func:`prometheus_text`) — the scrape
  format an HTTP front door serves from ``/metrics``.
- The set-up record (:func:`setup_record`, :func:`setup_phase`) — one
  process-wide account of the time before the first request:
  ``setup/*`` spans where the program gets ready, a build for every
  guarded program's first call (``utils.sanitize.CompileGuard``), and
  JAX's trace / lower / compile events assigned to the innermost open
  span or to the build they ran in, summed as a union of intervals.
  On ``time.perf_counter``, the clock of the benchmark's ``setup_s``.

Zero-cost-when-disabled is load-bearing: the :data:`NULL` recorder is
what every instrumented subsystem holds by default, its methods are
no-ops, its ``phase()`` is the bare profiler annotation and nothing
else, and nothing in this module performs a
device->host sync — graftlint GL004-clean with zero pragmas (pinned in
tests/test_telemetry.py, along with the no-buffer-growth property).
"""

from __future__ import annotations

import bisect
import contextlib
import json
import re
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, TextIO

from .jsonl import load_jsonl

__all__ = [
    "ENGINE_TRACK", "SLOT_TRACK_BASE", "REPLICA_TRACK_STRIDE",
    "ROUTER_TRACK", "ROUTER_TRACK_NAME", "NULL", "NullTelemetry",
    "Telemetry", "MetricsTimeline", "chrome_trace_from_jsonl",
    "load_jsonl", "prometheus_text", "PROM_PINNED_COUNTERS",
    "SETUP_STAGES", "IntervalUnion", "SetupRecord", "setup_record",
    "setup_phase",
]

#: The fleet-dashboard counter schema: every name a Grafana panel or
#: alert rule keys on. ``prometheus_text`` emits each of these at 0
#: even before its first increment, so a freshly started router scrapes
#: a complete series set (a rate() over a counter that APPEARS mid-run
#: is indistinguishable from a restart). graftlint GL021 holds this
#: tuple against the actual ``metrics.inc(...)`` literals — a counter
#: renamed in code without updating this pin (or vice versa) is a
#: silently-flatlined dashboard panel.
PROM_PINNED_COUNTERS = (
    # serve/router.py — fleet lifecycle, routing, disagg, transfers
    "fleet_ledger_recovered", "fleet_requests_submitted",
    "fleet_dedup_rejects", "fleet_replica_downs", "fleet_replicas_added",
    "fleet_requeued_requests", "fleet_ghost_cancels",
    "fleet_replica_attaches", "fleet_drains", "fleet_requests_routed",
    "fleet_route_fallbacks", "fleet_disagg_shortcircuits",
    "fleet_disagg_fallbacks", "fleet_disagg_prefills", "fleet_transfers",
    "fleet_transfer_pages", "fleet_transfer_bytes",
    "fleet_transfer_failures", "fleet_stale_finishes",
    "fleet_ghost_finishes", "fleet_requests_finished",
    "fleet_replica_rejoins", "fleet_replica_wedges", "fleet_replica_kills",
    "fleet_requeue_submits", "fleet_requeue_exhausted",
    "fleet_requeue_retries",
    # faults/procsup.py — autoscaler actions
    "fleet_scale_ups", "fleet_scale_downs",
    # serve/http.py — front-door admission
    "http_rate_limited",
    # serve/router.py — RPC protocol hardening under network faults
    # (serve/rpc.py checksums + idempotency, faults/netchaos.py)
    "rpc_dup_suppressed", "rpc_corrupt_frames", "rpc_partitions_active",
    "rpc_stale_generation_rejects",
)

#: engine-level track (steps, drafts, recovery markers); per-slot
#: request trees live on SLOT_TRACK_BASE + slot
ENGINE_TRACK = 0
SLOT_TRACK_BASE = 1

#: fleet layout: replica ``i``'s engine passes ``track_base = i *
#: REPLICA_TRACK_STRIDE`` so its engine/slot tracks never collide with a
#: neighbor's on the shared fleet recorder (pool sizes are far below the
#: stride). The router's own spans/instants (route decisions, requeues,
#: health transitions) live on ROUTER_TRACK, named ROUTER_TRACK_NAME —
#: tools/trace_check.py recognizes the *name*, so it needs no import.
REPLICA_TRACK_STRIDE = 100
ROUTER_TRACK = 9000
ROUTER_TRACK_NAME = "router"


class NullTelemetry:
    """The disabled recorder: every method is a no-op, ``phase`` is the
    bare profiler annotation, and no state ever accumulates.
    Instrumented hot loops additionally guard whole blocks with
    ``if tel.enabled:`` so the disabled step path pays one attribute
    read, not N method calls."""

    enabled = False
    events: tuple = ()

    def phase(self, name: str, track: int = ENGINE_TRACK, **args):
        from .profiling import annotate    # lazy: see Telemetry.phase
        return annotate(name, **args)

    def begin(self, name, track=ENGINE_TRACK, ts_us=None, **args) -> None:
        pass

    def end(self, name, track=ENGINE_TRACK, ts_us=None, **args) -> None:
        pass

    def complete(self, name, track, ts_us, dur_us, **args) -> None:
        pass

    def instant(self, name, track=ENGINE_TRACK, ts_us=None, **args) -> None:
        pass

    def name_track(self, track: int, name: str) -> None:
        pass

    def now_us(self) -> float:
        return 0.0

    def ts_us(self, t: float) -> float:
        return 0.0

    def close(self) -> None:
        pass


#: the module-wide disabled recorder — hold this, not None, so call
#: sites never branch on presence
NULL = NullTelemetry()


class Telemetry:
    """Enabled span/instant recorder.

    Events are Chrome trace-event dicts (``ph`` B/E/X/i) over a
    monotonic clock, appended to a bounded ring buffer and (optionally)
    streamed to a JSONL sink as they happen — a crash preserves the
    prefix, and the tolerant readers below skip the torn final line.
    ``clock`` is injectable for deterministic tests and so the serving
    engine's fake-clock tests keep request timestamps coherent with
    span timestamps.
    """

    enabled = True

    def __init__(self, capacity: int = 1 << 16,
                 jsonl_path: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic,
                 process_name: str = "replicatinggpt_tpu"):
        self._clock = clock
        self._t0 = clock()
        self.events: deque = deque(maxlen=capacity)
        # 'w', not 'a': each recorder is one run's artifact — appending
        # a rerun onto a reused path would duplicate request envelopes
        # (which trace_check rightly rejects). The journal keeps append
        # semantics; this sink does not want them.
        self._sink: Optional[TextIO] = (open(jsonl_path, "w")
                                        if jsonl_path else None)
        self._track_names: Dict[int, str] = {}
        self.process_name = process_name

    # ------------------------------------------------------------- clock

    def ts_us(self, t: float) -> float:
        """A ``clock()`` reading -> trace microseconds (relative to
        recorder construction, so timestamps stay small and the trace
        starts near 0)."""
        return (t - self._t0) * 1e6

    def now_us(self) -> float:
        return self.ts_us(self._clock())

    # ------------------------------------------------------------ record

    def _emit(self, ev: dict) -> None:
        self.events.append(ev)
        if self._sink is not None:
            # flushed per event: the sink's whole point is surviving a
            # crash mid-run (torn-tail-tolerant readers handle the rest)
            self._sink.write(json.dumps(ev) + "\n")
            self._sink.flush()

    def name_track(self, track: int, name: str) -> None:
        """Register a human-readable track (thread) name once."""
        if self._track_names.get(track) == name:
            return
        self._track_names[track] = name
        if self._sink is not None:
            # the crash-tolerant sink must carry the metadata too: a
            # trace assembled offline (chrome_trace_from_jsonl) needs
            # the thread_name M event for trace_check's router-track
            # envelope exemption
            self._sink.write(json.dumps(
                {"ph": "M", "name": "thread_name", "pid": 0,
                 "tid": track, "args": {"name": name}}) + "\n")
            self._sink.flush()

    def begin(self, name: str, track: int = ENGINE_TRACK,
              ts_us: Optional[float] = None, **args) -> None:
        """Open a span (phase B). ``ts_us`` lets the caller backdate —
        the engine opens a request's envelope at its *submit* time once
        the request is admitted (viewers sort by ts, so out-of-order
        emission is fine)."""
        ev = {"ph": "B", "name": name, "tid": track,
              "ts": self.now_us() if ts_us is None else ts_us}
        if args:
            ev["args"] = args
        self._emit(ev)

    def end(self, name: str, track: int = ENGINE_TRACK,
            ts_us: Optional[float] = None, **args) -> None:
        ev = {"ph": "E", "name": name, "tid": track,
              "ts": self.now_us() if ts_us is None else ts_us}
        if args:
            ev["args"] = args
        self._emit(ev)

    def complete(self, name: str, track: int, ts_us: float,
                 dur_us: float, **args) -> None:
        """One closed span (phase X) with explicit start + duration."""
        ev = {"ph": "X", "name": name, "tid": track, "ts": ts_us,
              "dur": max(dur_us, 0.0)}
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name: str, track: int = ENGINE_TRACK,
                ts_us: Optional[float] = None, **args) -> None:
        """A point marker (phase i) — recovery events, COW splits,
        evictions, prefix hits land on the timeline as these."""
        ev = {"ph": "i", "name": name, "tid": track, "s": "t",
              "ts": self.now_us() if ts_us is None else ts_us}
        if args:
            ev["args"] = args
        self._emit(ev)

    @contextlib.contextmanager
    def phase(self, name: str, track: int = ENGINE_TRACK,
              **args) -> Iterator[None]:
        """A host phase: ``profiling.annotate(name, **args)`` entered
        around the block, so it is on the profiler's timeline with
        ``args`` as its stats, and one X event with ``args`` recorded
        on exit."""
        from .profiling import annotate    # lazy: keep module import
        t0 = self.now_us()                 # jax-free for the exporters
        try:
            with annotate(name, **args):
                yield
        finally:
            self.complete(name, track, t0, self.now_us() - t0, **args)

    # ------------------------------------------------------------ export

    def chrome_events(self) -> List[dict]:
        """Trace-event list: metadata (process/thread names) + the ring
        buffer's events, normalized with pid and track sort order."""
        meta: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": self.process_name}}]
        for tid, name in sorted(self._track_names.items()):
            meta.append({"ph": "M", "name": "thread_name", "pid": 0,
                         "tid": tid, "args": {"name": name}})
            meta.append({"ph": "M", "name": "thread_sort_index", "pid": 0,
                         "tid": tid, "args": {"sort_index": tid}})
        return meta + [{**ev, "pid": 0} for ev in self.events]

    def export_chrome_trace(self, path: str) -> int:
        """Write the Perfetto-loadable JSON; returns the event count
        (metadata included). The ring buffer bounds memory, so a very
        long soak exports its most recent window — the JSONL sink is
        the full-history option."""
        events = self.chrome_events()
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return len(events)

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()
            self._sink = None


# ---------------------------------------------------------------------------
# set-up record: where the time before the first request goes
# ---------------------------------------------------------------------------

#: JAX's compile-stage duration events, and the stage each one times
#: (``backend_compile`` includes a persistent-cache retrieval)
SETUP_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


class IntervalUnion:
    """Seconds covered by intervals, each instant counted once: a jit
    traced inside its caller's trace fires its own event INSIDE the
    caller's, so a plain sum counts the inner one twice. Keeps at most
    ``cap`` disjoint intervals; past that the earliest is folded into a
    running total (an interval that later arrives over a folded one
    would be counted again: stage events arrive in the order they END,
    so only a trace longer than ``cap`` disjoint others could)."""

    __slots__ = ("cap", "folded", "iv")

    def __init__(self, cap: int = 64):
        self.cap = cap
        self.folded = 0.0
        self.iv: List[tuple] = []        # sorted, disjoint (start, end)

    def add(self, a: float, b: float) -> None:
        iv = self.iv
        i = bisect.bisect_left(iv, (a,))
        if i and iv[i - 1][1] >= a:      # the one before reaches into it
            i -= 1
            a = iv[i][0]
        j = i
        while j < len(iv) and iv[j][0] <= b:
            b = max(b, iv[j][1])
            j += 1
        iv[i:j] = [(a, b)]
        if len(iv) > self.cap:
            a0, b0 = iv.pop(0)
            self.folded += b0 - a0

    @property
    def seconds(self) -> float:
        return self.folded + sum(b - a for a, b in self.iv)


class SetupRecord:
    """What a process spends getting ready, on ``time.perf_counter``: the
    clock the benchmark's ``setup_s`` is read on, so spans and
    ``setup_s`` add up on one clock.

    - **Spans** (``setup_phase``): ``(name, parent, t0, t1, stats)``,
      the innermost open span the parent.
    - **Builds** (``CompileGuard``): a guarded call whose jit cache grew,
      i.e. a program's first call: trace, lower, compile or cache load,
      and dispatch. ``(guard, t0, t1, {stage: seconds inside it})``.
    - **Stages**: JAX's trace / lower / compile duration events, each the
      interval ``[now - secs, now]``, tagged with its ``fun_name`` and the
      innermost open span (``stages_by_span``). One that ends inside a
      set-up span, or inside a build, is set-up: its totals are the union
      of those intervals (``IntervalUnion``); ``process`` holds the union
      of every interval of the process, set-up or not (a reference check
      after the traffic, a harness's own jits).

    Bounded: the totals always, the first ``keep`` spans and builds, and
    a count of those dropped."""

    def __init__(self, keep: int = 256,
                 clock: Callable[[], float] = time.perf_counter):
        self.keep = keep
        self.clock = clock
        self.spans: List[tuple] = []
        self.builds: List[tuple] = []
        self.dropped = {"spans": 0, "builds": 0}
        self.open: List[str] = []        # names of the open spans
        self.span_s: Dict[str, float] = {}
        self.build_s: Dict[str, float] = {}
        self.n_builds = 0
        self.ready_at: Optional[float] = None   # end of the last of them
        self.setup = {st: IntervalUnion() for st in SETUP_STAGES.values()}
        self.process = {st: IntervalUnion()
                        for st in SETUP_STAGES.values()}
        self.trace_by_fun: Dict[str, float] = {}
        #: per innermost open span, each stage's union (64 names at most)
        self.by_span: Dict[str, Dict[str, IntervalUnion]] = {}
        # stage intervals a build that is still running may own:
        # [stage, fun, a, b, counted as set-up yet]
        self._recent: deque = deque(maxlen=512)

    # ------------------------------------------------------------ record

    def on_stage(self, event: str, secs: float, fun_name: str = "",
                 **_) -> None:
        """The ``jax.monitoring`` duration listener's body."""
        stage = SETUP_STAGES.get(event)
        if stage is None:
            return
        b = self.clock()
        a = b - secs
        self.process[stage].add(a, b)
        item = [stage, fun_name, a, b, False]
        if self.open:
            self._count(item)
            span = self.open[-1]
            if span in self.by_span or len(self.by_span) < 64:
                self.by_span.setdefault(span, {}).setdefault(
                    stage, IntervalUnion()).add(a, b)
        self._recent.append(item)

    def _count(self, item: list) -> None:
        stage, fun, a, b, _ = item
        item[4] = True
        self.setup[stage].add(a, b)
        if stage == "trace":
            if fun in self.trace_by_fun or len(self.trace_by_fun) < 1024:
                self.trace_by_fun[fun] = (self.trace_by_fun.get(fun, 0.0)
                                          + b - a)

    def _ready(self, t1: float) -> None:
        self.ready_at = t1 if self.ready_at is None else max(
            self.ready_at, t1)

    def record_span(self, name: str, parent: Optional[str], t0: float,
                    t1: float, stats: dict) -> None:
        self.span_s[name] = self.span_s.get(name, 0.0) + t1 - t0
        self._ready(t1)
        if len(self.spans) < self.keep:
            self.spans.append((name, parent, t0, t1, stats))
        else:
            self.dropped["spans"] += 1

    def record_build(self, name: str, t0: float, t1: float) -> None:
        """A guarded call ``[t0, t1]`` that added a program: the stage
        intervals inside it are set-up, and its own split by stage."""
        inside = {st: IntervalUnion() for st in SETUP_STAGES.values()}
        for item in self._recent:
            if t0 <= item[2] and item[3] <= t1:
                inside[item[0]].add(item[2], item[3])
                if not item[4]:
                    self._count(item)
        self.n_builds += 1
        self.build_s[name] = self.build_s.get(name, 0.0) + t1 - t0
        self._ready(t1)
        if len(self.builds) < self.keep:
            self.builds.append((name, t0, t1, {
                st + "_s": u.seconds for st, u in inside.items()}))
        else:
            self.dropped["builds"] += 1

    # ------------------------------------------------------------- views

    def top_trace(self, n: int = 5) -> List[list]:
        """The ``n`` functions with the most set-up trace seconds (an
        inner jit's seconds count for it AND for the function it was
        traced in)."""
        return [[f, s] for f, s in sorted(
            self.trace_by_fun.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self, t_start: float,
                  setup_s: Optional[float] = None) -> dict:
        """Every span and build as seconds from ``t_start`` (a harness's
        process start, on this clock), the stage totals, the five
        functions with the most trace seconds, and how much of set-up
        the spans and builds cover: ``covered_s`` (the union of the
        top-level spans and the builds), ``before_first_s`` (``t_start``
        to the first of them: imports, the backend, the weights'
        dispatch), ``between_s`` (first to last, outside them: a
        harness's own work, a warm-up's turns that built nothing) and,
        given the harness's ``setup_s``, ``after_last_s`` (the last of
        them to the end of set-up: a ramp, the last warm-up turns). The
        four add up to ``setup_s``."""
        at = lambda t: t - t_start
        covered = IntervalUnion(cap=len(self.spans) + len(self.builds) + 1)
        for _, parent, t0, t1, _ in self.spans:
            if parent is None:
                covered.add(t0, t1)
        for _, t0, t1, _ in self.builds:
            covered.add(t0, t1)
        first = covered.iv[0][0] if covered.iv else None
        s = self.summary()
        out = {
            "spans": [[name, parent, at(t0), at(t1), stats]
                      for name, parent, t0, t1, stats in self.spans],
            "builds": [[name, at(t0), at(t1), split]
                       for name, t0, t1, split in self.builds],
            "dropped": dict(self.dropped),
            **{k: s[k] for k in ("trace_s", "lower_s", "compile_s",
                                 "process", "top_trace")},
            "covered_s": covered.seconds,
            "before_first_s": None if first is None else at(first),
            "between_s": (None if first is None else
                          self.ready_at - first - covered.seconds),
            "ready_s": None if self.ready_at is None else at(self.ready_at),
        }
        if setup_s is not None and self.ready_at is not None:
            out["after_last_s"] = setup_s - at(self.ready_at)
        return out

    def summary(self) -> dict:
        """The totals: ``engine_s`` (every ``setup/engine`` span),
        ``build_s`` and its split ``build_s_by_guard``, the set-up
        stages' unions ``trace_s`` / ``lower_s`` / ``compile_s``, the
        process's (``process``), and the top functions by trace
        seconds."""
        return {
            "engine_s": self.span_s.get("setup/engine", 0.0),
            "build_s": sum(self.build_s.values()),
            "builds": self.n_builds,
            "build_s_by_guard": dict(self.build_s),
            **{st + "_s": u.seconds for st, u in self.setup.items()},
            "process": {st + "_s": u.seconds
                        for st, u in self.process.items()},
            "top_trace": self.top_trace(),
            "stages_by_span": {
                span: {st + "_s": u.seconds for st, u in d.items()}
                for span, d in self.by_span.items()},
            "dropped": dict(self.dropped),
        }


_RECORD: Optional[SetupRecord] = None


def _on_duration(event: str, secs: float, **kw) -> None:
    if _RECORD is not None:
        _RECORD.on_stage(event, secs, **kw)


def setup_record() -> SetupRecord:
    """The process's set-up record. Its first use registers the ONE
    ``jax.monitoring`` listener (not at import: this module stays
    jax-free for the exporters); stages before it are not seen."""
    global _RECORD
    if _RECORD is None:
        from jax import monitoring
        _RECORD = SetupRecord()
        monitoring.register_event_duration_secs_listener(_on_duration)
    return _RECORD


@contextlib.contextmanager
def setup_phase(name: str, **stats) -> Iterator[None]:
    """A span of set-up (``setup/engine``, ``setup/train_state``, ...):
    ``profiling.annotate(name, **stats)`` around the block, so a capture
    started at process start shows it beside the warm-up's ops, and one
    entry of the set-up record on exit."""
    from .profiling import annotate
    rec = setup_record()
    parent = rec.open[-1] if rec.open else None
    rec.open.append(name)
    t0 = rec.clock()
    try:
        with annotate(name, **stats):
            yield
    finally:
        rec.open.pop()
        rec.record_span(name, parent, t0, rec.clock(), stats)


# ---------------------------------------------------------------------------
# offline Chrome-trace assembly (the torn-tail-tolerant JSONL reader
# itself is utils.jsonl.load_jsonl — one implementation shared with the
# request journal and the fleet router's journal replay; re-exported
# here for existing callers)
# ---------------------------------------------------------------------------

def chrome_trace_from_jsonl(jsonl_path: str, out_path: str,
                            process_name: str = "replicatinggpt_tpu"
                            ) -> int:
    """Assemble a Perfetto-loadable trace from a (possibly torn) event
    sink — the offline path for a crashed run whose in-memory recorder
    died with it."""
    events = [{**ev, "pid": 0} for ev in load_jsonl(jsonl_path)
              if "ph" in ev]
    meta = [{"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": process_name}}]
    with open(out_path, "w") as f:
        json.dump({"traceEvents": meta + events,
                   "displayTimeUnit": "ms"}, f)
    return len(events)


# ---------------------------------------------------------------------------
# Metrics snapshot timeline (JSONL time series)
# ---------------------------------------------------------------------------

class MetricsTimeline:
    """Periodic JSONL snapshots of a ``utils.logging.Metrics``.

    One line per snapshot: wall offset, a caller-supplied step counter,
    every counter and gauge, and the histogram summaries. The replay
    driver snapshots on attach, every ``interval_s`` while running, and
    force-snapshots at the end — so even a sub-interval run yields the
    >= 2 points a timeline needs to show direction.
    """

    def __init__(self, metrics, path: str, interval_s: float = 0.5,
                 clock: Callable[[], float] = time.monotonic):
        self.metrics = metrics
        self.path = path
        self.interval_s = interval_s
        self._clock = clock
        self._t0 = clock()
        self._last: Optional[float] = None
        # 'w': one run per timeline file — a reused path must not mix
        # two runs' series (t_s/counters would reset mid-stream)
        self._f: Optional[TextIO] = open(path, "w")
        self.n_snapshots = 0

    def maybe_snapshot(self, step: Optional[int] = None) -> bool:
        now = self._clock()
        if self._last is not None and now - self._last < self.interval_s:
            return False
        self.snapshot(step=step, _now=now)
        return True

    def snapshot(self, step: Optional[int] = None,
                 _now: Optional[float] = None, **extra) -> None:
        assert self._f is not None, "timeline is closed"
        now = self._clock() if _now is None else _now
        self._last = now
        s = self.metrics.summary()
        rec = {"t_s": round(now - self._t0, 6), "step": step,
               "counters": s["counters"], "gauges": s["gauges"],
               "histograms": s["histograms"], **extra}
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()
        self.n_snapshots += 1

    def close(self, step: Optional[int] = None) -> None:
        """Force a final snapshot (the end-of-run point) and close."""
        if self._f is None:
            return
        self.snapshot(step=step)
        self._f.close()
        self._f = None

    @staticmethod
    def load(path: str) -> List[dict]:
        return load_jsonl(path)


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str, prefix: str) -> str:
    n = _PROM_BAD.sub("_", name)
    if n and n[0].isdigit():
        n = "_" + n
    return f"{prefix}_{n}" if prefix else n


def _prom_value(v) -> str:
    """Full-precision sample value: json.dumps is the shortest string
    that round-trips the number exactly — '%g' would silently collapse
    a 1,234,567-token counter to 1.23457e+06, corrupting every
    rate/delta computed from the scrape."""
    if isinstance(v, bool):
        v = 1 if v else 0
    return json.dumps(v)


def prometheus_text(metrics, prefix: str = "tpu_gpt",
                    extra_gauges: Optional[Dict[str, Any]] = None) -> str:
    """Render a ``Metrics`` in the Prometheus text exposition format
    (v0.0.4 — what a ``/metrics`` scrape endpoint serves): counters as
    ``counter``, gauges as ``gauge``, histograms as ``summary`` with
    p50/p90/p99 quantiles plus ``_sum``/``_count``/``_min``/``_max``
    companions derived from the reservoir summary. ``extra_gauges``
    lets the caller fold in derived values (pages_in_use, spec accept
    rate, ...) without teaching Metrics about them."""
    lines: List[str] = []
    counters = dict(metrics.counters)
    for name in PROM_PINNED_COUNTERS:
        counters.setdefault(name, 0)
    for name in sorted(counters):
        pn = _prom_name(name, prefix)
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {_prom_value(counters[name])}")
    gauges = dict(metrics.gauges)
    if extra_gauges:
        gauges.update(extra_gauges)
    for name in sorted(gauges):
        pn = _prom_name(name, prefix)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {_prom_value(gauges[name])}")
    for name in sorted(metrics.hists):
        pn = _prom_name(name, prefix)
        h = metrics.hist_summary(name)
        lines.append(f"# TYPE {pn} summary")
        for q in ("0.5", "0.9", "0.99"):
            key = {"0.5": "p50", "0.9": "p90", "0.99": "p99"}[q]
            lines.append(f'{pn}{{quantile="{q}"}} {_prom_value(h[key])}')
        lines.append(f"{pn}_sum {_prom_value(h['mean'] * h['n'])}")
        lines.append(f"{pn}_count {_prom_value(h['n'])}")
        lines.append(f"{pn}_min {_prom_value(h['min'])}")
        lines.append(f"{pn}_max {_prom_value(h['max'])}")
    return "\n".join(lines) + "\n"
