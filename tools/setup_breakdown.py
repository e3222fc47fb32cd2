#!/usr/bin/env python3
"""One run of one benchmark cell, then where its set-up went.

    python3 tools/setup_breakdown.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs ``chipbench/run.py`` in this process with the same arguments (its
output is unchanged) and then prints one line, ``{"setup_breakdown": ...}``:
the program's set-up record (``utils.telemetry.SetupRecord.breakdown``)
against the run's own ``setup_s``: every ``setup/*`` span and build as
seconds from process start, the trace / lower / compile totals, the
functions with the most trace seconds, and the part of ``setup_s`` no span
or build covers. The benchmark reads none of it (PERF.md, Open questions).
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402  (its T_START: process start)


class _Tee(io.TextIOBase):
    """stdout as it was, and a copy of every line."""

    def __init__(self, out):
        self.out, self.lines = out, []

    def write(self, s):
        self.lines.append(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def main(argv=None) -> int:
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = run.main(argv)
    setup_s = None
    for line in "".join(tee.lines).splitlines():
        if line.startswith('{"note": "seconds_since_process_start"'):
            setup_s = json.loads(line)["setup"]
    from replicatinggpt_tpu.utils.telemetry import setup_record
    print(json.dumps({"setup_breakdown": setup_record().breakdown(
        run.T_START, setup_s), "setup_s": setup_s}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
