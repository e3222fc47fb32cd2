#!/usr/bin/env python3
"""Rehearse a cell without the chip: the same traffic kind, the same loop and
checks, at a tiny model on the CPU. NOT a measurement: it prints what the run
collected so that wrong paths, arguments and control flow show before a chip
call, and never the benchmark's result line.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload train-medium --seconds 3

The kinds' ``run(ctx, sizes)`` take the sizes; ``run.py`` never passes any.
The Pallas kernels are not steered here (on the CPU the program takes its XLA
path, and the route check says so): the verify skill has the recipe for
interpret mode.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import common, manifest  # noqa: E402

#: test-tiny, wide enough for two heads of 32 and as long as the family
TINY = ["--preset", "test-tiny", "--n-embd", "64", "--n-head", "2",
        "--block-size", "1024"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    man = manifest.Manifest(ROOT)
    cell = man.cell(args.workload)
    kind = man.kind(cell["traffic"]["kind"])
    common.watch_compiles()
    trace_dir = os.path.join(ROOT, "chipbench_out", "rehearse",
                             args.workload)
    ctx = common.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START,
                     trace_dir=trace_dir)
    res = kind.run(ctx, sizes=TINY)
    print(json.dumps({"rehearsal_not_a_measurement": res}, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
