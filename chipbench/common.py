"""What every traffic kind shares: the run's context, the compile watcher,
the program's configuration from its own flags, the device line, plain
statistics. Copies of ``chip_smoke.py``'s ``_watch_compiles`` and ``_cfg``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import List, Optional

from chipbench.manifest import ROOT

DATASET = os.path.join(ROOT, "datasets", "shakespeare.txt")


@dataclasses.dataclass
class Ctx:
    """One run: the cell (configuration and traffic merged in), the seed as
    given and folded into 31 bits for the program's 32-bit generators, the
    window's length, whether to trace, and the clock reading at process
    start that ``setup_s`` is taken from."""
    cell: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    trace_dir: str

    @property
    def seed31(self) -> int:
        return self.seed % (2 ** 31 - 1)


def note(what: str, **fields) -> None:
    """One JSON line of detail on stdout, before the result line."""
    print(json.dumps({"note": what, **fields}), flush=True)


#: every number a run's check compared, beside its limit: ``run.py`` prints
#: them as the run's last lines on stderr and last in the result line
COMPARED = {}


def compare(name: str, value, limit) -> bool:
    """Record ``value`` beside ``limit`` and say whether it is within it
    (a value that is missing is not)."""
    COMPARED[name] = {"value": value, "limit": limit}
    return value is not None and value <= limit


COMPILES = {"s": 0.0, "n": 0, "hits": 0, "misses": 0}


def watch_compiles() -> None:
    """Sum XLA backend-compile seconds and persistent-cache traffic."""
    from jax import monitoring

    def on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILES["s"] += secs
            COMPILES["n"] += 1

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            COMPILES["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            COMPILES["misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def program_config(argv: List[str]):
    """A ``Config`` from the CLI's own flags (what a user would type)."""
    from replicatinggpt_tpu.config import add_config_flags, config_from_args
    p = argparse.ArgumentParser()
    add_config_flags(p)
    return config_from_args(p.parse_args(argv))


def config_argv(config: dict) -> List[str]:
    """The program's flags for a configuration file: its preset, and every
    published size spelled out, so the file is what is run."""
    return ["--preset", config["preset"],
            "--n-layer", str(config["n_layer"]),
            "--n-head", str(config["n_head"]),
            "--n-embd", str(config["n_embd"]),
            "--block-size", str(config["n_positions"]),
            "--vocab-size", str(config["vocab_size"])]


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes on the fullest chip, where the backend says. The TPU
    runtime counts live arrays under ``peak_bytes_in_use`` and the space a
    running program's temporaries take under ``peak_bytes_reserved`` (carved
    from what the arrays leave free), so the chip's peak is their sum: for
    the trainer the first alone is the state and misses 7 GB of a step."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    note("memory_stats", device0=stats[0], devices=len(stats))
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if s.get("peak_bytes_in_use")]
    return max(peaks) if peaks else None


def host_waits() -> dict:
    """Seconds, since boot, that work on this host waited: for a CPU, for
    I/O, for memory (``/proc/pressure``, where the kernel has it), those a
    hypervisor took from it (``steal`` of ``/proc/stat``), and those this
    process's own threads stood runnable without a CPU (``schedstat``). A
    window's delta goes on a note, so that a run whose loop or trainer stood
    still for seconds says whether the host took them: if none of these
    moved, the host was waiting for the device."""
    out = {}
    for what in ("cpu", "io", "memory"):
        try:
            with open(f"/proc/pressure/{what}") as f:
                some = f.readline().split()
            out[what + "_wait_s"] = int(some[-1].split("=")[1]) / 1e6
        except (OSError, IndexError, ValueError):
            pass
    try:
        with open("/proc/stat") as f:
            out["steal_s"] = int(f.readline().split()[8]) / os.sysconf(
                "SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    # this process's threads (the runtime's among them): runnable, no CPU
    delay = 0
    try:
        for task in os.listdir("/proc/self/task"):
            with open(f"/proc/self/task/{task}/schedstat") as f:
                delay += int(f.read().split()[1])
        out["threads_kept_waiting_s"] = delay / 1e9
    except (OSError, IndexError, ValueError):
        pass
    return out


def waits_between(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b if k in a}


def pct(values: List[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..1) of a non-empty list."""
    s = sorted(values)
    return s[min(int(q * (len(s) - 1) + 0.5), len(s) - 1)]


def median(values: List[float]) -> float:
    return pct(values, 0.5)


def now() -> float:
    return time.perf_counter()


def fail(msg: str, code: int = 1) -> "NoReturn":
    """Stop with a message on stderr and no result line."""
    print("chipbench: " + msg, file=sys.stderr, flush=True)
    raise SystemExit(code)
