"""From a profiler trace to numbers: device busy and idle time, op time by
name, idle gaps by host span. Nothing but JAX reads
the ``.xplane.pb`` (``jax.profiler.ProfileData``); everything after
``load()`` works on plain lists, so a cut trace can be kept as JSON and the
arithmetic tested without a chip.

A loaded trace is::

    {"devices": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...],
                                   "XLA Modules": [...]}, ...},
     "host": [[name, start_ns, dur_ns], ...]}     # named spans only

Device planes are those whose name starts with ``/device:``. ``OPS`` is the
line whose events are the operations a core ran, ``MODULES`` the line with
one event per launched program.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS = "XLA Ops"
MODULES = "XLA Modules"
#: host events kept by ``load``: TraceAnnotations of the program
#: (``serve/decode``, ``serve/prefill``) and the benchmark's own spans
HOST_SPAN = re.compile(r"^(serve|train|chipbench)/")
WINDOW_SPAN = "chipbench/window"

Interval = Tuple[float, float]


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """An op's name as the trace prints it is its whole HLO instruction,
    shapes, operands and all. Kept: the instruction's name and opcode, and
    ``tpu_custom_call`` where it is a Pallas kernel:
    ``closed_call.16 custom-call tpu_custom_call``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    op = _OPCODE.search(" " + rest)
    out = head.lstrip("%") + (" " + op.group(1) if op else "")
    if 'custom_call_target="tpu_custom_call"' in rest:
        out += " tpu_custom_call"
    return out


def find_xplane(logdir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` into the plain form above."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [[short_name(e.name), float(e.start_ns),
                                     float(e.duration_ns)]
                                    for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if HOST_SPAN.match(e.name):
                        out["host"].append([e.name, float(e.start_ns),
                                            float(e.duration_ns)])
    out["host"].sort(key=lambda e: e[1])
    return out


def describe(trace: dict, top: int = 25) -> dict:
    """What a person looks at first: planes, lines, event counts and the
    names that take most time on each line."""
    out = {"host_spans": _totals(trace["host"])[:top], "devices": {}}
    for plane, lines in trace["devices"].items():
        out["devices"][plane] = {
            name: {"events": len(evs), "top": _totals(evs)[:top]}
            for name, evs in lines.items()}
    return out


def _totals(events: Iterable[list]) -> List[list]:
    acc: Dict[str, List[float]] = {}
    for name, _, dur in events:
        a = acc.setdefault(name, [0.0, 0])
        a[0] += dur
        a[1] += 1
    return sorted(([n, a[0] / 1e9, a[1]] for n, a in acc.items()),
                  key=lambda r: -r[1])


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def measure(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _spans(events: Iterable[list]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events if d > 0]


def self_times(events: List[list]) -> Dict[str, float]:
    """Seconds by op name, each event counted for the time none of the
    events nested inside it covers (a ``while`` that holds a scanned layer
    stack is then not counted for its body)."""
    acc: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, self_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        # an event is nested only if it ends inside the one before it; two
        # that merely overlap are siblings
        while stack and (stack[-1][1] <= start
                         or start + dur > stack[-1][1]):
            done = stack.pop()
            acc[done[0]] = acc.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for done in stack:
        acc[done[0]] = acc.get(done[0], 0.0) + done[2]
    return {n: ns / 1e9 for n, ns in acc.items()}


def leaves(events: List[list]) -> List[list]:
    """Events with no event nested inside them."""
    out = []
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    for i, ev in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] + nxt[2] > ev[1] + ev[2]:
            out.append(ev)
    return out


def window_of(trace: dict) -> Optional[Interval]:
    """The traced window: the benchmark's ``chipbench/window`` span where
    it recorded one, else first to last device event."""
    for name, start, dur in trace["host"]:
        if name == WINDOW_SPAN:
            return (start, start + dur)
    spans = [sp for lines in trace["devices"].values()
             for sp in _spans(lines.get(OPS, []))]
    if not spans:
        return None
    return (min(s for s, _ in spans), max(e for _, e in spans))


def _clip(spans: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in spans
            if e > lo and s < hi]


def busy(trace: dict) -> dict:
    """``busy_s`` (mean over the device planes of the seconds in which an op
    ran), ``window_s``, and the idle share as the mean over chips and on the
    worst chip."""
    window = window_of(trace)
    if window is None:
        return {}
    width = (window[1] - window[0]) / 1e9
    per_chip = {plane: measure(_clip(_spans(lines.get(OPS, [])), window))
                / 1e9 for plane, lines in trace["devices"].items()
                if lines.get(OPS)}
    if not per_chip or width <= 0:
        return {}
    mean = sum(per_chip.values()) / len(per_chip)
    return {"busy_s": mean, "window_s": width,
            "idle_share_mean": 1.0 - mean / width,
            "idle_share_worst": 1.0 - min(per_chip.values()) / width,
            "chips_traced": len(per_chip)}


def device_ops(trace: dict, top: int = 10) -> List[list]:
    """The ops that took most device time (self time, summed over chips and
    divided by their number), under the names the trace prints."""
    acc: Dict[str, float] = {}
    planes = [l for l in trace["devices"].values() if l.get(OPS)]
    for lines in planes:
        for name, s in self_times(lines[OPS]).items():
            acc[name] = acc.get(name, 0.0) + s / len(planes)
    return [[n, s] for n, s in
            sorted(acc.items(), key=lambda r: -r[1])[:top]]


def idle_gaps(trace: dict, top: int = 10) -> List[list]:
    """Idle seconds of the first device by the innermost host span that was
    open in the middle of each gap; ``(no span)`` where none was."""
    window = window_of(trace)
    planes = sorted(p for p, l in trace["devices"].items() if l.get(OPS))
    if window is None or not planes:
        return []
    ops = union(_clip(_spans(trace["devices"][planes[0]][OPS]), window))
    acc: Dict[str, float] = {}
    host = [h for h in trace["host"] if h[0] != WINDOW_SPAN]
    for lo, hi in subtract([window], ops):
        mid = (lo + hi) / 2
        open_ = [h for h in host if h[1] <= mid < h[1] + h[2]]
        label = max(open_, key=lambda h: h[1])[0] if open_ else "(no span)"
        acc[label] = acc.get(label, 0.0) + (hi - lo) / 1e9
    return [[n, s] for n, s in
            sorted(acc.items(), key=lambda r: -r[1])[:top]]


def launches(trace: dict, pattern: str) -> float:
    """Program launches matching ``pattern``, as the mean over chips."""
    rx = re.compile(pattern)
    counts = [sum(1 for e in lines[MODULES] if rx.search(e[0]))
              for lines in trace["devices"].values() if lines.get(MODULES)]
    return sum(counts) / len(counts) if counts else 0.0


def pattern_per_launch(trace: dict, op_pattern: str, launch_pattern: str,
                       launches_scale: float = 1.0,
                       scale: float = 1e3) -> Optional[float]:
    """Device time (ms by default, mean over chips) of the leaf ops matching
    ``op_pattern`` that ran inside a launch of a program matching
    ``launch_pattern``, per such launch (times ``launches_scale``: steps a
    launch holds). None where either matches nothing."""
    rx, launch_rx = re.compile(op_pattern), re.compile(launch_pattern)
    per_chip = []
    for lines in trace["devices"].values():
        if not lines.get(OPS):
            continue
        inside = union(_spans(e for e in lines.get(MODULES, [])
                              if launch_rx.search(e[0])))
        starts = [lo for lo, _ in inside]
        total = 0.0
        for name, start, dur in leaves(lines[OPS]):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < inside[i][1] and rx.search(name):
                total += dur
        per_chip.append(total / 1e9)
    n = launches(trace, launch_pattern) * launches_scale
    if not per_chip or not n or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / n * scale


def reduce(trace: dict) -> dict:
    """Everything ``run.py`` prints from a trace."""
    out = busy(trace)
    out["device_ops"] = device_ops(trace)
    out["idle_gaps"] = idle_gaps(trace)
    return out


def cut(trace: dict, max_events: int = 400) -> dict:
    """The first ``max_events`` events of each device line and the host
    spans over the same time: small enough to commit as test data."""
    out = {"devices": {}, "host": []}
    hi = 0.0
    for plane, lines in trace["devices"].items():
        out["devices"][plane] = {}
        for name in (OPS, MODULES):
            evs = sorted(lines.get(name, []), key=lambda e: e[1])
            if name == OPS:
                evs = evs[:max_events]
                hi = max([hi] + [e[1] + e[2] for e in evs])
            else:
                evs = [e for e in evs if e[1] < hi]
            out["devices"][plane][name] = evs
    out["host"] = [h for h in trace["host"]
                   if h[1] < hi and h[0] != WINDOW_SPAN]
    return out
