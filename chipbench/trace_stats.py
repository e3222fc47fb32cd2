"""What ``trace_reduce.load`` drops and the program's names need: host spans
WITH their stats (the counters a ``phase()`` carries: ``live_tokens``,
``live_kv_bytes``, ``tokens``) and device ops WITH the ``jax.named_scope``
path they ran under (``.../while/body/sample/sort``). ``run.py`` hands a
reader ``(counters, trace)`` with names only, so a reader that needs more
asks ``current()`` for the run's own ``.xplane.pb``, parsed once however many
readers ask.

A stats trace is the plain form of ``trace_reduce`` with one more field an
event::

    {"devices": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns, scope],
                                               ...],
                                   "XLA Modules": [[name, start_ns, dur_ns]]}},
     "host": [[name, start_ns, dur_ns, {stat: value}], ...]}

``scope`` is the op's ``op_name`` as XLA recorded it ("" where the trace has
none). A program without these spans, stats or scopes (the parent of the PR
that added them) gives readers nothing to read: they return None and raise
nothing.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
from typing import Dict, List, Optional

from chipbench import trace_reduce
from chipbench.manifest import ROOT
from chipbench.trace_reduce import MODULES, OPS

# ------------------------------------------------- the programs' own HLO
#
# A device op event carries its HLO instruction as its name and no
# ``op_name`` (TPU v5e, jax 0.9.0: its stats are ``device_offset_ps``,
# ``device_duration_ps`` and ``Time Scale Multiplier``). The capture keeps
# every program that ran in it as an ``Hlo Proto`` stat in the
# ``/host:metadata`` plane, which ``ProfileData`` does not show; the scopes
# are read from there, off the protobuf wire format, as far down as an
# instruction's ``metadata.op_name`` and no further.

_METADATA_PLANE = "/host:metadata"


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field (string, bytes,
    message), None for a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield number, value


def _sub(buf, number: int):
    return (v for n, v in _fields(buf) if n == number)


def _text(buf, number: int) -> str:
    return next((bytes(v).decode("utf-8", "replace")
                 for v in _sub(buf, number)), "")


def _instruction_scopes(hlo_proto) -> Dict[str, str]:
    """``{instruction name: op_name}`` over every computation of an
    ``HloProto`` (hlo_module=1 / computations=3 / instructions=2 / name=1,
    metadata=7 / op_name=2)."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for computation in _sub(module, 3):
            for instruction in _sub(computation, 2):
                out[_text(instruction, 1)] = next(
                    (_text(md, 2) for md in _sub(instruction, 7)), "")
    return out


def hlo_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction name: op_name}}`` for every program the
    capture at ``path`` kept the HLO of, ``program`` as the ``XLA Modules``
    line names its launches (``jit_run(1593...)``). Empty where the capture
    kept none. (XSpace planes=1; XPlane name=2, event_metadata=4, a map
    whose entries hold the XEventMetadata as 2; its name=2, stats=5; an
    XStat's bytes_value=6.)"""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _sub(space, 1):
        if _text(plane, 2) != _METADATA_PLANE:
            continue
        for entry in _sub(plane, 4):
            for metadata in _sub(entry, 2):
                for stat in _sub(metadata, 5):
                    for hlo_proto in _sub(stat, 6):
                        out[_text(metadata, 2)] = _instruction_scopes(
                            hlo_proto)
    return out


def run_logdirs(argv: Optional[List[str]] = None) -> List[str]:
    """Where this process's ``--workload`` leaves its trace: ``run.py``'s
    directory first, then ``rehearse.py``'s."""
    argv = sys.argv if argv is None else argv
    if "--workload" not in argv[:-1]:
        return []
    cell = argv[argv.index("--workload") + 1]
    return [os.path.join(ROOT, "chipbench_out", kind, cell)
            for kind in ("trace", "rehearse")]


_NUMBER = (int, float)


def _plain(value):
    return value if isinstance(value, (str, bool) + _NUMBER) else str(value)


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` into the stats form above. An op's scope is
    the ``op_name`` of its instruction in the HLO of the program whose
    launch it ran in (instruction names repeat from program to program)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    programs = hlo_scopes(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            by_line = {line.name: line for line in plane.lines}
            modules = sorted(
                ([e.name, float(e.start_ns), float(e.duration_ns)]
                 for e in getattr(by_line.get(MODULES), "events", ())),
                key=lambda e: e[1])
            starts = [e[1] for e in modules]
            ops = []
            for e in getattr(by_line.get(OPS), "events", ()):
                name, start = trace_reduce.short_name(e.name), float(e.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                scopes = programs.get(modules[i][0], {}) if i >= 0 else {}
                ops.append([name, start, float(e.duration_ns),
                            scopes.get(name.split(" ")[0], "")])
            out["devices"][plane.name] = {OPS: ops, MODULES: modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if trace_reduce.HOST_SPAN.match(e.name):
                        out["host"].append(
                            [e.name, float(e.start_ns), float(e.duration_ns),
                             {k: _plain(v) for k, v in e.stats}])
    out["host"].sort(key=lambda e: e[1])
    return out


@functools.lru_cache(maxsize=2)
def _loaded(path: str, mtime: float) -> dict:
    return load(path)


def current(argv: Optional[List[str]] = None) -> Optional[dict]:
    """The stats trace of this run, or None where it left no trace."""
    for logdir in run_logdirs(argv):
        path = trace_reduce.find_xplane(logdir)
        if path is not None:
            return _loaded(path, os.path.getmtime(path))
    return None


# ------------------------------------------------------------- host spans

def spans(trace: dict, name: str) -> List[list]:
    return [h for h in trace["host"] if h[0] == name]


def mean_stat(trace: dict, span: str, stat: str) -> Optional[float]:
    """Mean over the ``span`` events of their numeric stat ``stat``."""
    vals = [h[3][stat] for h in spans(trace, span)
            if isinstance(h[3].get(stat), _NUMBER)]
    return sum(vals) / len(vals) if vals else None


def uncovered_ms(trace: dict, outer: str, holds: str,
                 minus: List[str]) -> List[float]:
    """For every ``outer`` span that holds a ``holds`` span: its duration
    less what the spans named in ``minus`` cover inside it, in ms."""
    inner = sorted((h for h in trace["host"] if h[0] in minus or
                    h[0] == holds), key=lambda h: h[1])
    starts = [h[1] for h in inner]
    out = []
    for _, lo, dur, _ in spans(trace, outer):
        hi = lo + dur
        inside = [h for h in inner[bisect.bisect_left(starts, lo):
                                   bisect.bisect_left(starts, hi)]
                  if h[1] + h[2] <= hi]
        if not any(h[0] == holds for h in inside):
            continue
        covered = trace_reduce.measure(
            (h[1], h[1] + h[2]) for h in inside if h[0] in minus)
        out.append((dur - covered) / 1e6)
    return out


# -------------------------------------------------------------- device ops

def scope_rx(scope: str) -> "re.Pattern":
    """Matches an ``op_name`` that has ``scope`` as one component of its
    path, also inside ``transpose(jvp(...))`` of a backward pass."""
    return re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")


def has_scopes(trace: dict) -> bool:
    return any(e[3] for lines in trace["devices"].values()
               for e in lines.get(OPS, []))


def _labelled_self_times(ops: List[list], label_of) -> List[tuple]:
    """``(start_ns, label, self seconds)`` of every op of one device line,
    ``label_of(op_name path)`` its label. Self time: an op is not counted
    for what the ops nested in it cover, so a ``while`` around the layers
    counts for nothing. XLA leaves what it makes itself without an
    ``op_name``, or with a bare one that is no path (the sorts and the
    ``reduce_window_sum`` it expands a scatter and a cumsum into, a cast it
    hoists out of the layer loop): such an op takes the label of the ops
    around it where the nearest op with a path before it and the nearest
    after it carry the same label, and None otherwise."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    labels = [label_of(e[3]) if "/" in e[3] else None for e in ops]
    after, last = [None] * len(ops), None
    for i in range(len(ops) - 1, -1, -1):
        after[i] = last
        if labels[i] is not None:
            last = labels[i]
    before = None
    for i, label in enumerate(labels):
        if label is not None:
            before = label
        elif before == after[i]:
            labels[i] = before
    # self_times keys by name: key by position instead
    own = trace_reduce.self_times([[i, e[1], e[2]]
                                   for i, e in enumerate(ops)])
    return [(ops[i][1], labels[i], s) for i, s in own.items()]


def scope_self_ms_per_launch(trace: dict, scope: str,
                             launch_pattern: str) -> Optional[float]:
    """Device self time (ms, mean over chips) of the ops under ``scope``
    (``_labelled_self_times``) that ran inside a launch of a program
    matching ``launch_pattern``, per such launch. None where the trace has
    no scopes, no such launch or no such op."""
    rx, launch_rx = scope_rx(scope), re.compile(launch_pattern)
    per_chip = []
    for lines in trace["devices"].values():
        if not lines.get(OPS):
            continue
        inside = trace_reduce.union(trace_reduce._spans(
            e for e in lines.get(MODULES, []) if launch_rx.search(e[0])))
        starts = [lo for lo, _ in inside]
        total = 0.0
        for start, under, s in _labelled_self_times(
                lines[OPS], lambda path: bool(rx.search(path))):
            j = bisect.bisect_right(starts, start) - 1
            if under and j >= 0 and start < inside[j][1]:
                total += s
        per_chip.append(total)
    n = trace_reduce.launches(trace, launch_pattern)
    if not per_chip or not n or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / n * 1e3


def by_scope(trace: dict, vocabulary: List[str]) -> List[list]:
    """Device self seconds (mean over chips) by the INNERMOST scope of
    ``vocabulary`` on each op's path, ``(other)`` for a path that holds
    none, ``(no op_name)`` for what XLA left without one and no neighbours
    claim: the table a person reads first."""
    rxs = [(v, scope_rx(v)) for v in vocabulary]

    def innermost(path: str) -> str:
        hits = [(m.start(), v) for v, rx in rxs for m in rx.finditer(path)]
        return max(hits)[1] if hits else "(other)"

    acc: Dict[str, float] = {}
    planes = [l for l in trace["devices"].values() if l.get(OPS)]
    for lines in planes:
        for _, label, s in _labelled_self_times(lines[OPS], innermost):
            label = "(no op_name)" if label is None else label
            acc[label] = acc.get(label, 0.0) + s / len(planes)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda r: -r[1])]


def cut(trace: dict, max_events: int = 400, first: int = 0) -> dict:
    """``trace_reduce.cut`` for the stats form, from anywhere in the trace:
    ``max_events`` device ops from the ``first`` on, the launches they ran
    in, the host spans open during them and whatever is nested in those
    (a ``serve/step`` then keeps its launch, fetch and commit)."""
    out = {"devices": {}, "host": []}
    lo, hi = float("inf"), 0.0
    for plane, lines in trace["devices"].items():
        ops = sorted(lines.get(OPS, []),
                     key=lambda e: e[1])[first:first + max_events]
        lo = min([lo] + [e[1] for e in ops])
        hi = max([hi] + [e[1] + e[2] for e in ops])
        out["devices"][plane] = {
            OPS: ops,
            MODULES: [e for e in sorted(lines.get(MODULES, []),
                                        key=lambda e: e[1])
                      if e[1] < hi and e[1] + e[2] > lo]}
    host = [h for h in trace["host"] if h[0] != trace_reduce.WINDOW_SPAN]
    open_ = [h for h in host if h[1] < hi and h[1] + h[2] > lo]
    out["host"] = [h for h in host
                   if any(o[1] <= h[1] and h[1] + h[2] <= o[1] + o[2]
                          for o in open_)]
    return out


def names_only(trace: dict) -> dict:
    """The stats form with the fourth field dropped: what
    ``trace_reduce``'s functions take."""
    return {"devices": {p: {n: [e[:3] for e in evs]
                            for n, evs in lines.items()}
                        for p, lines in trace["devices"].items()},
            "host": [h[:3] for h in trace["host"]]}


# ----------------------------------------------------------------- readers

def read_spec(spec: dict, counters: dict, trace: Optional[dict],
              stats: Optional[dict] = None,
              device_kind: Optional[str] = None) -> Optional[float]:
    """The readers of ``layer_metrics/<name>.json`` that need the stats
    trace. ``trace`` is what ``run.py`` loaded (names only; None in an
    untraced run); ``stats`` and ``device_kind`` are for tests, a run reads
    its own file and its own device.

    ``{"scope": s, "per": launches}``: device self ms of the ops under
    ``jax.named_scope`` ``s`` per matching launch.
    ``{"span": n, "stat": k, "peak": p, "over": {"trace_sum", "per"}}``:
    the least time the device's peak ``p`` (of ``peaks.json``) allows for
    the mean of stat ``k`` of the ``n`` spans, as a share (%) of the device
    time of the ops ``over`` names, per launch.
    ``{"outer": a, "holds": b, "minus": [..]}``: median over the ``a`` spans
    that hold a ``b`` of their ms not covered by the ``minus`` spans."""
    if not trace:
        return None
    stats = current() if stats is None else stats
    if stats is None:
        return None
    if "scope" in spec:
        return scope_self_ms_per_launch(stats, spec["scope"], spec["per"])
    if "outer" in spec:
        left = uncovered_ms(stats, spec["outer"], spec["holds"],
                            spec["minus"])
        return sorted(left)[len(left) // 2] if left else None
    if "span" in spec:
        amount = mean_stat(stats, spec["span"], spec["stat"])
        took_ms = trace_reduce.pattern_per_launch(
            trace, spec["over"]["trace_sum"], spec["over"]["per"], scale=1e3)
        if amount is None or not took_ms:
            return None
        if device_kind is None:
            import jax
            device_kind = jax.devices()[0].device_kind
        from chipbench import flops
        floor_ms = amount / flops.peaks(device_kind)[spec["peak"]] * 1e3
        return 100.0 * floor_ms / took_ms
    raise ValueError(f"layer metric spec has no stats reader: {spec}")


def reader(py_path: str):
    """``read(counters, trace)`` for the ``<name>.py`` at ``py_path``, from
    the spec in the ``<name>.json`` beside it."""
    with open(py_path[:-3] + ".json") as f:
        spec = json.load(f)
    return functools.partial(read_spec, spec)


#: the ``jax.named_scope`` vocabulary of the program (docs/observability.md);
#: the second row is the ``exaone_moe`` family's (PR 29): its expert layer and
#: its two kinds of attention layer (``by_scope`` takes the innermost scope
#: of a path, so the order here does not matter)
SCOPES = ("embed", "attn", "kv_gather", "kv_scatter", "mlp", "head", "sample",
          "cast_params", "loss", "grad_accum", "optimizer",
          "moe_router", "moe_experts", "moe_shared", "attn_swa", "attn_global")


def main(argv: Optional[List[str]] = None) -> int:
    """``python3 -m chipbench.trace_stats <file.xplane.pb>``: device seconds
    by scope and the host phases with the mean of each numeric stat, as one
    JSON object: what PERF.md section 5 is written from."""
    argv = sys.argv[1:] if argv is None else argv
    trace = load(argv[0])
    phases: Dict[str, dict] = {}
    for name, _, dur, stats in trace["host"]:
        p = phases.setdefault(name, {"n": 0, "s": 0.0})
        p["n"] += 1
        p["s"] += dur / 1e9
        for k, v in stats.items():
            if isinstance(v, _NUMBER):
                p[k] = p.get(k, 0.0) + v
    for p in phases.values():
        for k in set(p) - {"n", "s"}:
            p[k] /= p["n"]
    print(json.dumps({"by_scope": by_scope(trace, list(SCOPES)),
                      "phases": phases}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
