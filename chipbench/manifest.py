"""Finds everything the benchmark runs BY NAME, from ``BENCHMARK.json``.

A cell is an entry of ``workloads`` plus ``chipbench/workloads/<name>.json``;
its configuration is ``chipbench/configs/<config>.json``; its traffic mix is
``chipbench/traffic/<traffic>.json``, read by the one generator its ``kind``
names, ``chipbench/traffic/<kind>.py``; a per-layer metric is
``chipbench/layer_metrics/<name>.json`` with an optional ``<name>.py`` reader
beside it. A later PR adds files and entries and edits nothing that is here.

No JAX in this module: the tests and ``run.py``'s first steps import it.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` of the checkout at ``root`` and the files under
    its one benchmark directory."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _read(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, self.doc["paths"][0])

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def cell(self, name: str) -> dict:
        """The cell ``name`` with its configuration and traffic mix merged
        in: ``{name, config: {...}, traffic: {...}, chips, program, why}``.
        ``traffic_params`` of the cell file overrides the mix (a rate is four
        fifths of THIS configuration's knee)."""
        entry = next((w for w in self.doc["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: "
                           f"{[w['name'] for w in self.doc['workloads']]})")
        cell = _read(self.path("workloads", name + ".json"))
        config = _read(self.path("configs", entry["config"] + ".json"))
        traffic = _read(self.path("traffic", entry["traffic"] + ".json"))
        traffic.update(cell.get("traffic_params", {}))
        return {"name": name, "chips": entry["chips"], "why": entry["why"],
                "config": config, "traffic": traffic,
                "traffic_name": entry["traffic"],
                "program": cell.get("program", {})}

    def metrics(self, section: str, cell: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` that ``cell`` reports:
        those without a ``workloads`` key, or that list it."""
        return [m for m in self.doc[section]
                if "workloads" not in m or cell in m["workloads"]]

    def _module(self, path: str, name: str):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def kind(self, kind: str):
        """The generator module of a traffic kind: ``run(ctx) -> dict``."""
        path = self.path("traffic", kind + ".py")
        if not os.path.exists(path):
            raise KeyError(f"no traffic kind {kind!r}: {path} is missing")
        return self._module(path, "chipbench_traffic_" + kind)

    def reader(self, metric: str):
        """``read(counters, trace) -> float | None`` for a per-layer metric:
        its own ``<name>.py`` if there is one, else built from the spec in
        ``<name>.json`` (a path into the counters, or a trace pattern)."""
        own = self.path("layer_metrics", metric + ".py")
        if os.path.exists(own):
            return self._module(
                own, "chipbench_metric_" + metric.replace(".", "_")).read
        spec = _read(self.path("layer_metrics", metric + ".json"))
        return lambda counters, trace: read_spec(spec, counters, trace)


def dig(tree: dict, path: str):
    """``tree["a"]["b"]`` for ``"a.b"``; None where a key is missing."""
    for key in path.split("."):
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree


def read_spec(spec: dict, counters: dict, trace: Optional[dict]):
    """The two declarative readers. ``{"counter": "serve.step_ms_median"}``
    digs a number out of what the run collected. ``{"trace_sum": regex,
    "per": regex}`` sums the device time of the ops matching the first
    pattern and divides by the number of program launches matching the
    second (times ``counters[per_scale]`` where given: optimizer steps per
    dispatch). A reader that finds nothing returns None and the metric is
    left out of the line."""
    if "counter" in spec:
        v = dig(counters, spec["counter"])
        return None if v is None else float(v) * spec.get("scale", 1.0)
    if "trace_sum" in spec:
        if not trace:
            return None
        from chipbench import trace_reduce
        steps = dig(counters, spec["per_scale"]) if "per_scale" in spec else 1
        return trace_reduce.pattern_per_launch(
            trace, spec["trace_sum"], spec["per"],
            launches_scale=float(steps or 1), scale=spec.get("scale", 1.0))
    raise ValueError(f"layer metric spec has no reader: {spec}")
