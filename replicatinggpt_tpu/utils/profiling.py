"""Tracing / profiling subsystem.

The reference has no profiler, timers, or even per-step timing (SURVEY.md §5
row 1 — ABSENT). The TPU-native equivalent supplied here:

- ``trace_window``: step-triggered ``jax.profiler`` capture for the hot
  loop — steps [start, start+n) of a run, viewable in TensorBoard /
  Perfetto and reduced to numbers by ``chipbench/trace_reduce.py``; no
  trace overhead elsewhere.
- ``start_server``: on-demand profiling of a live job from TensorBoard.
- ``annotate``: a named host-side region on the profiler's timeline, with
  optional counters as its stats. The program marks its phases through
  ``utils.telemetry``'s ``phase()``, which enters one of these; the
  profiler's clock is the one the device ops are on.
- ``StepTimer``: blocking per-step latency statistics (p50/p90/mean,
  tokens/sec) — the serving engine's ``step_latency`` and the generate
  path's per-token latency; every lap fetches what it is given, so async
  dispatch can't hide device time. Throughput measurements deliberately
  time an unsynchronized span that ends in one sync instead, since a
  per-step device sync would dominate small step times.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax


def start_server(port: int = 9012):
    """Start the profiler RPC server so TensorBoard can capture on demand."""
    return jax.profiler.start_server(port)


def annotate(name: str, **stats):
    """Named region on the profiler timeline; ``stats`` (numbers or
    strings) ride on the event, where a trace reader finds them by
    name. Costs an enter and an exit when no capture is running."""
    return jax.profiler.TraceAnnotation(name, **stats)


class trace_window:
    """Step-triggered tracing: trace steps [start, start + n_steps).

    Usage in a loop::

        win = trace_window(logdir, start=10, n_steps=5)
        for it in range(max_iters):
            win.step(it)        # starts/stops the trace at the boundaries
            ...
        win.close()             # in case the loop ended mid-window

    ``step`` may be called with strides > 1 (the runner's multi-step scan
    dispatches advance K steps at a time): the window opens at the first
    call at-or-past ``start`` and closes at the first call at-or-past
    ``stop_at`` after opening, then never reopens — a jumped-over window
    still produces a trace of at least one dispatch.
    """

    def __init__(self, logdir: Optional[str], start: int = 10,
                 n_steps: int = 5):
        self.logdir = logdir
        self.start = start
        self.stop_at = start + n_steps
        self._active = False
        self._done = False

    def step(self, it: int) -> None:
        if not self.logdir or self._done:
            return
        if self._active and it >= self.stop_at:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
        elif not self._active and it >= self.start:
            jax.profiler.start_trace(self.logdir)
            self._active = True

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True


class StepTimer:
    """Blocking wall-clock statistics for jitted steps."""

    def __init__(self) -> None:
        self.laps: List[float] = []
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def lap(self, *block_on: Any) -> float:
        """End the current lap, blocking on ``block_on`` first. Returns
        the lap time and immediately starts the next lap.

        Blocking is a real device->host fetch (``jax.device_get``):
        the latency a caller of generate() feels includes bringing the
        tokens home, so the lap does too.
        """
        if block_on:
            jax.device_get(block_on)
        now = time.perf_counter()
        assert self._t0 is not None, "call start() before lap()"
        dt = now - self._t0
        self.laps.append(dt)
        self._t0 = now
        return dt

    @staticmethod
    def _pct(sorted_laps: List[float], q: float) -> float:
        if not sorted_laps:
            return 0.0
        i = min(int(q * (len(sorted_laps) - 1) + 0.5), len(sorted_laps) - 1)
        return sorted_laps[i]

    def summary(self, tokens_per_step: int = 0, n_chips: int = 1,
                skip: int = 0) -> Dict[str, float]:
        """Stats over laps[skip:] (skip warmup/compile laps)."""
        laps = self.laps[skip:]
        if not laps:
            return {"n": 0, "mean_s": 0.0, "p50_s": 0.0, "p90_s": 0.0,
                    "tokens_per_sec_per_chip": 0.0}
        s = sorted(laps)
        mean = sum(laps) / len(laps)
        p50 = self._pct(s, 0.50)
        out = {"n": float(len(laps)), "mean_s": mean, "p50_s": p50,
               "p90_s": self._pct(s, 0.90),
               "tokens_per_sec_per_chip": 0.0}
        if tokens_per_step and p50 > 0:
            out["tokens_per_sec_per_chip"] = (
                tokens_per_step / p50 / max(n_chips, 1))
        return out
