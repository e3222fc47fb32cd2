"""Traffic kind ``train_job``: the trainer, through ``cli._build_mesh_if_needed``
and ``train.runner.train`` as a user's ``train`` command goes, stopped through
the runner's ``stop_event`` when the window has passed. A copy of
``chip_smoke.py``'s ``_train_once`` with a recording ``StepLogger``.

Parameters of a mix (``chipbench/traffic/<name>.json``): ``batch_size``,
``steps_per_dispatch``, ``log_interval``, ``warm_dispatches`` (dispatches
before the window opens: the first compiles or loads), ``trace_dispatches``
(a traced run profiles that many dispatches BEFORE its window opens, because
stopping a trace stalls the host for a second or more), ``loss_tol`` and
``grad_tol`` with their reasons (``reference_gap``).
"""

from __future__ import annotations

import math
import sys
import threading
import time

from chipbench import common, flops, reference


def _argv(ctx: common.Ctx, sizes=None) -> list:
    t = ctx.cell["traffic"]
    return ((list(sizes) if sizes is not None
             else common.config_argv(ctx.cell["config"]))
            + list(ctx.cell["program"].get("flags", []))
            + ["--dataset", common.DATASET, "--tokenizer", "char",
               "--batch-size", str(t["batch_size"]),
               "--steps-per-dispatch", str(t["steps_per_dispatch"]),
               "--log-interval", str(t["log_interval"]),
               "--eval-interval", "0", "--eval-iters", "2",
               "--lr-schedule", "constant", "--warmup-iters", "0",
               "--max-iters", str(t.get("max_iters", 1_000_000)),
               "--seed", str(ctx.seed31)])


def run(ctx: common.Ctx, sizes=None) -> dict:
    """``sizes`` replaces the configuration's flags in a chip-less rehearsal
    (``["--preset", "test-tiny", ...]``); the command never passes it."""
    import jax
    import numpy as np
    from replicatinggpt_tpu.cli import _build_mesh_if_needed
    from replicatinggpt_tpu.train.runner import train
    from replicatinggpt_tpu.utils.logging import StepLogger

    t = ctx.cell["traffic"]
    cfg = common.program_config(_argv(ctx, sizes))
    k = max(cfg.train.steps_per_dispatch, 1)
    warm = int(t["warm_dispatches"])
    traced = int(t["trace_dispatches"]) if ctx.trace else 0
    opens_at = warm + (traced + 2 if traced else 0)
    stop = threading.Event()
    win = {}

    class Recorder(StepLogger):
        """Keeps every log line with the clock reading at which its loss had
        been fetched; opens the window after the warm dispatches and asks
        the runner to stop once it has lasted ``--seconds``."""

        def __init__(self):
            super().__init__(stream=sys.stderr)
            self.events = []

        def _jsonl(self, obj):
            ev = {**obj, "t": time.perf_counter()}
            self.events.append(ev)
            if ev["event"] != "step" or "end" in win:
                return
            n = sum(e["event"] == "step" for e in self.events)
            if n == opens_at:
                win["start"] = ev
                win["compiles"] = dict(common.COMPILES)
                win["waits"] = common.host_waits()
            elif n > opens_at and ev["t"] - win["start"]["t"] >= ctx.seconds:
                win["end"] = ev
                win["compiles_end"] = dict(common.COMPILES)
                win["waits"] = common.waits_between(win["waits"],
                                                    common.host_waits())
                stop.set()

    mesh = _build_mesh_if_needed(cfg)       # raises on too few devices
    chips = mesh.size if mesh is not None else 1
    rec = Recorder()
    profile = {}
    if ctx.trace:
        profile = dict(profile_dir=ctx.trace_dir,
                       profile_start=k * (warm + 1),
                       profile_steps=k * traced)
    res = train(cfg, mesh=mesh, logger=rec, stop_event=stop, **profile)
    mem = common.memory_peak_bytes()
    if "end" not in win:
        common.fail("the trainer returned before the window closed "
                    f"({len(rec.events)} log lines)")
    mcfg = cfg.model
    steps = [e for e in rec.events if e["event"] == "step"]
    inside = [e for e in steps
              if win["start"]["t"] <= e["t"] <= win["end"]["t"]]
    n_steps = win["end"]["step"] - win["start"]["step"]
    seconds = win["end"]["t"] - win["start"]["t"]
    tokens = n_steps * cfg.train.batch_size * mcfg.block_size
    rate = tokens / seconds / chips
    laps = [(b["t"] - a["t"]) / (b["step"] - a["step"]) * 1e3
            for a, b in zip(inside, inside[1:])]
    kind = jax.devices()[0].device_kind
    fpt = flops.train_flops_per_token(mcfg.n_layer, mcfg.n_embd,
                                      mcfg.block_size, mcfg.vocab_size)
    peak = flops.peaks(kind)["bf16_flops_per_s"] if sizes is None else None
    losses = [e["loss"] for e in steps]
    in_window = win["compiles_end"]["n"] - win["compiles"]["n"]
    common.note("train_window", dispatches=len(inside) - 1, steps=n_steps,
                seconds=seconds, tokens=tokens, chips=chips,
                step_ms_median=common.median(laps), step_ms_n=len(laps),
                step_ms_p90=common.pct(laps, 0.9), step_ms_max=max(laps),
                host_waits=win["waits"],
                losses_first_last=[losses[0], losses[-1]],
                compiles_before_window=win["compiles"],
                compiles_in_window=in_window,
                model=f"{mcfg.n_layer}L/{mcfg.n_head}H/{mcfg.n_embd}C "
                      f"T={mcfg.block_size} V={mcfg.vocab_size} "
                      f"{mcfg.dtype}/{mcfg.param_dtype} remat={mcfg.remat}",
                mesh={"data": cfg.mesh.data, "model": cfg.mesh.model,
                      "fsdp": cfg.mesh.fsdp})

    # ---- correct, outside the window -----------------------------------
    problems = []
    bad = [l for l in losses if not math.isfinite(l)]
    if bad:
        problems.append(f"{len(bad)} non-finite losses")
    if not common.compare("compiled_in_window", in_window, 0):
        problems.append(f"{in_window} programs compiled inside the window")
    if not losses[-1] < losses[0]:
        problems.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    del res
    gap = reference_gap(mcfg, ctx.seed31)
    tol, gtol = float(t["loss_tol"]), float(t["grad_tol"])
    common.note("reference_step", **gap, loss_tol=tol,
                loss_tol_why=t["loss_tol_why"], grad_tol=gtol,
                grad_tol_why=t["grad_tol_why"])
    if not common.compare("loss_abs_diff", gap["abs_diff"], tol):
        problems.append(f"loss at the initial weights is {gap['abs_diff']} "
                        f"from the reference's, tolerance {tol}")
    if not common.compare("grad_rel_err_worst", gap["grad_rel_err_worst"],
                          gtol):
        problems.append(f"gradient of {gap['grad_worst_leaf']} is "
                        f"{gap['grad_rel_err_worst']} (relative) from the "
                        f"reference's, tolerance {gtol}")
    if problems:
        common.note("problems", problems=problems)
    return {
        "correct": not problems,
        "attempted": len(steps) * k,
        "failed": len(bad) * k,
        "memory_peak_bytes": mem,
        "end_to_end": {
            "train_tokens_per_s_chip": rate,
            "setup_s": win["start"]["t"] - ctx.t_start,
        },
        "counters": {
            "setup": {"compile_s": win["compiles"]["s"]},
            "train": {"step_ms_median": common.median(laps),
                      "steps_per_dispatch": k,
                      "mfu_pct": (100.0 * rate * fpt / peak
                                  if peak else None)},
        },
    }


def reference_gap(mcfg, seed: int) -> dict:
    """The program's loss AND gradient (its own ``train.steps.loss_fn`` under
    ``jax.value_and_grad``: bf16 compute, the flash kernel forward and
    backward, remat as the configuration has it) against the plain float32
    reference's, on one seeded batch of two sequences at the initial weights
    of this seed. Per leaf of the parameter tree, the distance between the
    two gradients over the reference's length; the worst leaf decides."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from replicatinggpt_tpu.models import gpt
    from replicatinggpt_tpu.train.steps import loss_fn
    # create_train_state's own split: the weights the trainer started from
    params = jax.jit(lambda key: gpt.init_params(
        jax.random.split(key)[0], mcfg))(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, mcfg.vocab_size, (2, mcfg.block_size + 1),
                       dtype=np.int32)
    x, y = seq[:, :-1], seq[:, 1:]
    theirs, g_theirs = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss_fn(p, (x, y), mcfg)))(params, x, y)
    ours, g_ours = jax.jit(jax.value_and_grad(
        lambda p, x, y: reference.loss(p, x, y, mcfg.n_head)))(params, x, y)

    @jax.jit
    def rel_err(a, b):
        f32 = lambda t: t.astype(jnp.float32)
        return jax.tree_util.tree_map(
            lambda u, v: jnp.linalg.norm(f32(u) - f32(v))
            / jnp.linalg.norm(f32(v)), a, b)

    errs = {jax.tree_util.keystr(k): float(v) for k, v in
            jax.tree_util.tree_leaves_with_path(rel_err(g_theirs, g_ours))}
    worst = max(errs, key=errs.get)
    theirs, ours = float(theirs), float(ours)
    return {"program_loss": theirs, "reference_loss": ours,
            "abs_diff": abs(theirs - ours), "grad_rel_err": errs,
            "grad_rel_err_worst": errs[worst], "grad_worst_leaf": worst}
