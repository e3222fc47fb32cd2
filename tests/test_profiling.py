"""Profiling subsystem tests (SURVEY.md §5 row 1 — absent in reference;
supplied as jax.profiler traces + blocking step-latency statistics)."""

import glob
import os

import pytest

import jax
import jax.numpy as jnp

from replicatinggpt_tpu.utils.profiling import (StepTimer, annotate,
                                                trace_window)


@pytest.mark.parametrize("start,n_steps", [(2, 2), (0, 6)],
                         ids=["inner-steps", "whole-loop"])
def test_trace_window_covers_requested_steps(tmp_path, start, n_steps):
    """The capture opens and closes at the requested steps, writes its
    artifact, and holds the ``annotate`` regions entered while it was
    open, with their stats, and no other."""
    logdir = str(tmp_path / "win")
    win = trace_window(logdir, start=start, n_steps=n_steps)
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8)
    for it in range(6):
        win.step(it)
        assert win._active == (start <= it < start + n_steps)
        with annotate("hot-region", it=it):
            x = jax.block_until_ready(f(x))
    win.close()
    assert not win._active
    hits = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
    assert hits, f"no trace artifacts under {logdir}"
    seen = sorted(dict(e.stats)["it"]
                  for plane in jax.profiler.ProfileData.from_file(
                      hits[-1]).planes
                  for line in plane.lines for e in line.events
                  if e.name == "hot-region")
    assert seen == list(range(start, min(start + n_steps, 6)))


def test_trace_window_disabled_without_logdir():
    win = trace_window(None, start=0, n_steps=100)
    for it in range(5):
        win.step(it)
        assert not win._active
    win.close()


def test_step_timer_stats():
    t = StepTimer()
    t.start()
    t.laps = [0.1, 0.2, 0.3, 0.4, 1.0]  # inject deterministic laps
    s = t.summary(tokens_per_step=1000, n_chips=2, skip=1)
    assert s["n"] == 4
    assert abs(s["mean_s"] - (0.2 + 0.3 + 0.4 + 1.0) / 4) < 1e-9
    assert s["p50_s"] in (0.3, 0.4)
    assert s["tokens_per_sec_per_chip"] == 1000 / s["p50_s"] / 2


def test_step_timer_laps_block():
    t = StepTimer()
    t.start()
    y = jax.jit(lambda x: x @ x)(jnp.ones((128, 128)))
    dt = t.lap(y)
    assert dt > 0 and len(t.laps) == 1
    assert t.summary()["n"] == 1


@pytest.mark.slow
def test_runner_profile_dir(tmp_path):
    from replicatinggpt_tpu.config import get_config
    from replicatinggpt_tpu.train.runner import train
    from replicatinggpt_tpu.utils.logging import StepLogger
    import dataclasses as dc

    cfg = get_config("test-tiny")
    cfg = cfg.replace(train=dc.replace(cfg.train, max_iters=4,
                                       eval_interval=0, log_interval=0))
    logdir = str(tmp_path / "prof")
    train(cfg, logger=StepLogger(stream=open(os.devnull, "w")),
          profile_dir=logdir, profile_start=1, profile_steps=2)
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def test_trace_window_close_mid_window(tmp_path):
    """A loop that ends while the window is still open must still get a
    trace from close(): the profiler stops, marks itself done, and a
    late step() can never reopen it (double-start would raise inside
    jax.profiler)."""
    logdir = str(tmp_path / "midwin")
    win = trace_window(logdir, start=0, n_steps=100)
    f = jax.jit(lambda x: x + 1)
    win.step(0)
    assert win._active
    jax.block_until_ready(f(jnp.zeros(8)))
    win.close()                     # loop ended at step 1 of 100
    assert not win._active and win._done
    win.step(1)                     # a straggler call must not reopen
    assert not win._active
    win.close()                     # idempotent
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def test_trace_window_with_strided_steps(tmp_path):
    # multi-step dispatch loops advance it by K; a window jumped over must
    # still open (and close on the next call), producing a trace
    logdir = str(tmp_path / "stride")
    win = trace_window(logdir, start=10, n_steps=5)
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8)
    for it in (0, 25, 50, 75):
        win.step(it)
        assert win._active == (it == 25)
        x = f(x)
    win.close()
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
