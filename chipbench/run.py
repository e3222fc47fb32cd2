#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the machine it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It reads the cell before it touches JAX, fails at
once (no result line) where there is no TPU, too few chips or a device kind
that ``peaks.json`` does not know, keeps JAX's persistent compilation cache at
a fixed place inside the checkout, warms the cell's own shapes, measures for
``--seconds``, checks the outputs against the plain reference outside the
window, and prints as the LAST line of stdout one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, traced ``breakdown``, and
last ``compared``: every number the check compared beside its limit (also the
last lines of stderr).
Earlier lines (``{"note": ...}``) carry sample counts, the generator's
lateness, losses, the kernel route and the cache traffic. With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import common, flops, manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    man = manifest.Manifest(ROOT)
    cell = man.cell(args.workload)
    kind = man.kind(cell["traffic"]["kind"])
    e2e = man.metrics("end_to_end", args.workload)
    layer = man.metrics("per_layer", args.workload)

    # ---- only now JAX: the cell is known, and so are its chips ----------
    import jax
    from replicatinggpt_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()   # the environment's, else a fixed one
    # the program caches only what took a second to compile: the sub-second
    # programs would compile again in every run, inside set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    common.watch_compiles()
    try:
        device = common.device_info()
    except RuntimeError as e:
        common.fail(f"no TPU: jax found no accelerator ({e})")
    if device["platform"] != "tpu":
        common.fail(f"no TPU: jax found {device}; the benchmark has no "
                    f"other path")
    if device["count"] < cell["chips"]:
        common.fail(f"{args.workload} needs {cell['chips']} chip(s), jax "
                    f"found {device['count']}")
    try:
        flops.peaks(device["kind"])
    except KeyError as e:
        common.fail(str(e))
    common.note("start", workload=args.workload, seed=args.seed,
                seconds=args.seconds, trace=args.trace, device=device,
                jax=jax.__version__, compile_cache=cache_dir,
                config=cell["config"]["name"],
                traffic=cell["traffic_name"])

    trace_dir = os.path.join(ROOT, "chipbench_out", "trace",
                             args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = common.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START,
                     trace_dir=trace_dir)
    res = kind.run(ctx)
    common.note("compile_cache", **common.COMPILES)
    common.note("seconds_since_process_start", run=common.now() - T_START,
                setup=res["end_to_end"]["setup_s"])
    if res["memory_peak_bytes"] is None:
        common.fail("the backend reports no peak memory")
    device["memory_peak_bytes"] = res["memory_peak_bytes"]

    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if "cut" in res:         # requests hung up on while decoding: not failed
        out["cut"] = res["cut"]
    if not args.trace:
        for m in e2e:
            value = res["end_to_end"].get(m["name"])
            if value is None:
                common.fail(f"{args.workload} did not measure {m['name']}")
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from chipbench import trace_reduce
        path = trace_reduce.find_xplane(trace_dir)
        if path is None:
            common.fail(f"--trace 1 left no trace under {trace_dir}")
        trace = trace_reduce.load(path)
        red = trace_reduce.reduce(trace)
        if not red.get("busy_s"):
            common.fail("the trace shows no operation on the device")
        common.note("trace", file=os.path.relpath(path, ROOT),
                    **{k: red[k] for k in ("busy_s", "window_s",
                                           "idle_share_mean",
                                           "idle_share_worst",
                                           "chips_traced")})
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        for m in layer:
            value = man.reader(m["name"])(res["counters"], trace)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        # for the person who writes the next pattern: what the trace holds
        with open(os.path.join(trace_dir, "reduced.json"), "w") as f:
            json.dump({"describe": trace_reduce.describe(trace),
                       "cut": trace_reduce.cut(trace)}, f)
    # what the check compared, each number beside its limit: the last lines
    # of stderr and the last key of the line
    out["compared"] = dict(common.COMPARED)
    for name, c in common.COMPARED.items():
        print(f"chipbench: compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
