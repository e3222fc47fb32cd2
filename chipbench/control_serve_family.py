#!/usr/bin/env python3
"""The control of ``serve_closed_family``'s comparison: one run of a cell of
that kind whose streams are held to the reference with every matrix rounded
to 8 bits, the nearest precision below the configuration's bfloat16
(``serve_closed_family.run(ctx, control=True)``). The limits of the mix are set between the
program's own readings and this run's, so it has to come out NOT ``correct``,
and by a limit of the comparison, not by the route or a compile.

    python3 chipbench/control_serve_family.py --workload serve-decode-kexaone --seed <n> --seconds <s>
    JAX_PLATFORMS=cpu python3 chipbench/control_serve_family.py --workload serve-decode-kexaone --tiny 1

Not a measurement and not a cell: the driver never runs it. Exit 0 where the
control was told apart, 1 where it passed as correct or failed for another
reason. The last line is one JSON object with the problems ``check`` found;
the ``reference_streams`` note before it carries the readings.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import inspect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import common, manifest  # noqa: E402

#: how a problem of the comparison itself starts (``serve_closed_family.check``)
OF_THE_COMPARISON = ("a stream's token sits", "the streams' tokens sit",
                     "the program's router chose")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 29)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--tiny", type=int, choices=[0, 1], default=0,
                    help="the family's tiny preset (a CPU rehearsal)")
    args = ap.parse_args(argv)
    man = manifest.Manifest(ROOT)
    cell = man.cell(args.workload)
    kind = man.kind(cell["traffic"]["kind"])
    if "control" not in inspect.signature(kind.run).parameters:
        common.fail(f"{cell['traffic']['kind']} has no control")
    from replicatinggpt_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    common.watch_compiles()
    ctx = common.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                     trace=False, t_start=T_START, trace_dir="")
    res = kind.run(ctx, sizes=[] if args.tiny else None, control=True)
    problems = res["problems"]
    told_apart = bool(problems) and all(
        p.startswith(OF_THE_COMPARISON) for p in problems)
    print(json.dumps({"control": "8-bit reference",
                      "correct": res["correct"], "told_apart": told_apart,
                      "problems": problems}), flush=True)
    return 0 if told_apart and not res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
