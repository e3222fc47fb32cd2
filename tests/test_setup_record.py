"""The program's set-up record on a rehearsed benchmark cell.

Each cell runs as ``chipbench/rehearse.py`` runs it (its traffic kind,
``TINY`` sizes, the CPU) in a process of its own, as ``chipbench/run.py``
does, so the record is that run's alone. The ramp is cut to a second: the
set-up is what is read, not the traffic.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: one cell's kind at TINY sizes in a fresh process, then the record's
#: totals and its breakdown against the run's own ``setup_s``
_CELL = r"""
import time
T_START = time.perf_counter()
import json, os, sys
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from chipbench import common, manifest, rehearse
from replicatinggpt_tpu.utils.telemetry import setup_record
man = manifest.Manifest({root!r})
cell = man.cell({cell!r})
if "ramp_s" in cell["traffic"]:
    cell["traffic"]["ramp_s"] = 1.0
common.watch_compiles()
setup_record()              # its listener from the start, beside the watch
ctx = common.Ctx(cell=cell, seed=2 ** 31 + 11, seconds=2.0, trace=False,
                 t_start=T_START, trace_dir="")
res = man.kind(cell["traffic"]["kind"]).run(ctx, sizes=rehearse.TINY)
rec = setup_record()
setup_s = res["end_to_end"]["setup_s"]
print("RESULT " + json.dumps({{
    "setup_s": setup_s,
    "compile_s_at_open": res["counters"]["setup"]["compile_s"],
    "compiles_s_now": common.COMPILES["s"],
    "summary": rec.summary(),
    "breakdown": rec.breakdown(T_START, setup_s)}}, default=str))
"""


@pytest.fixture(scope="module",
                params=["serve-decode-large", "train-medium"])
def rehearsed(request):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", _CELL.format(root=ROOT, cell=request.param)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")]
    return request.param, json.loads(line[-1][len("RESULT "):])


def test_the_setup_totals_are_read_in_every_kind(rehearsed):
    """Trace, lowering and builds in every cell; the engine's span in the
    serve cells, the train state's in the trainer."""
    cell, r = rehearsed
    s, b = r["summary"], r["breakdown"]
    for k in ("trace_s", "lower_s", "build_s", "compile_s"):
        assert s[k] > 0, (k, s)
    spans = {x[0] for x in b["spans"]}
    builds = {x[0] for x in b["builds"]}
    if cell == "train-medium":
        assert s["engine_s"] == 0.0 and "setup/train_state" in spans
        assert builds & {"train/step", "train/scan"}
    else:
        assert s["engine_s"] > 0 and "setup/warm_programs" in spans
        assert {"serve/decode", "serve/prefill"} <= builds, builds
        decode = next(x for x in b["builds"] if x[0] == "serve/decode")
        assert decode[3]["trace_s"] > 0 and decode[3]["lower_s"] > 0


def test_the_breakdown_adds_up_to_setup_s(rehearsed):
    """Before the first span, the spans and builds, between them and after
    the last: ``setup_s`` on one clock, to a millisecond. Every span and
    build lies inside set-up."""
    _, r = rehearsed
    b = r["breakdown"]
    parts = (b["before_first_s"], b["covered_s"], b["between_s"],
             b["after_last_s"])
    assert all(p >= -1e-6 for p in parts), parts
    assert sum(parts) == pytest.approx(r["setup_s"], abs=1e-3)
    assert 0 < b["ready_s"] <= r["setup_s"]
    assert all(0 <= x[2] <= x[3] <= r["setup_s"] for x in b["spans"])
    assert all(0 <= x[1] <= x[2] <= r["setup_s"] for x in b["builds"])


def test_the_record_sees_every_compile_the_harness_counts(rehearsed):
    """The record's union of backend-compile intervals over the process
    (its listener installed beside the harness's watch: in a run the
    program installs it at its first span or guard, and sees no stage
    before) is what ``common.watch_compiles`` sums at the same moment; its
    set-up part is no more than what the harness had counted when the
    window opened (``compile_s``)."""
    _, r = rehearsed
    assert r["summary"]["process"]["compile_s"] == pytest.approx(
        r["compiles_s_now"], abs=1e-3)
    assert r["summary"]["compile_s"] <= r["compile_s_at_open"] + 1e-3
