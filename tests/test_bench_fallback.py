"""bench.py has no fallback: a measurement needs the chip.

A run that finds no TPU emits the error artifact and exits non-zero,
unless ``--platform cpu`` asked for a CPU run explicitly; every artifact
names the device it ran on; an unknown device kind is an error in both
peak tables (never a substring guess, never ``None``)."""

import json
import sys

import jax
import pytest


def _run_main(monkeypatch, capsys, argv):
    import bench

    monkeypatch.setattr(bench, "_EMITTED", False)
    monkeypatch.setattr(bench, "_EMIT_TAGS", {})
    monkeypatch.setattr(bench, "start_watchdog", lambda *a, **k: None)
    monkeypatch.setattr(sys, "argv", ["bench.py", *argv])
    prev_prng = jax.config.jax_default_prng_impl
    prev_dir = jax.config.jax_compilation_cache_dir
    rc = 0
    try:
        bench.main()
    except SystemExit as e:
        rc = e.code
    finally:
        # bench.main flips global jax config; tests share the process
        jax.config.update("jax_default_prng_impl", prev_prng)
        jax.config.update("jax_compilation_cache_dir", prev_dir)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(line)


def test_no_accelerator_and_no_platform_flag_is_an_error(
        monkeypatch, capsys):
    """No TPU and no `--platform cpu`: error artifact, non-zero exit,
    and the mode never runs — no measurement under a device metric's
    name."""
    import bench
    ran = []
    monkeypatch.setattr(bench, "bench_serve", lambda args: ran.append(1))
    rc, payload = _run_main(monkeypatch, capsys, ["--mode", "serve"])
    assert rc == 1
    assert not ran
    assert payload["value"] == 0.0
    assert "no TPU" in payload["error"]
    assert "backend" not in payload          # no cpu-fallback tag either


def test_explicit_platform_cpu_measures_and_names_the_device(
        monkeypatch, capsys):
    """`--platform cpu` is an explicit ask: the (tiny) serve bench runs
    end to end and the artifact says platform cpu, kind and count."""
    rc, payload = _run_main(monkeypatch, capsys, [
        "--mode", "serve", "--platform", "cpu",
        "--preset", "test-tiny", "--serve-requests", "8",
        "--serve-rate", "2000", "--serve-pool", "4",
        "--serve-max-new-tokens", "4", "--skip-baseline"])
    assert rc == 0
    assert "error" not in payload
    assert payload["platform"] == "cpu"
    assert payload["device_kind"] == jax.devices()[0].device_kind
    assert payload["device_count"] == len(jax.devices())
    assert payload["metric"] == "serve_replay_aggregate_tokens_per_sec"
    assert payload["value"] > 0
    assert payload["n_completed"] == 8
    assert payload["recompiles_after_warmup"] == 0
    # the paged-pool block rides every serve artifact
    for key in ("pages_in_use", "page_utilization", "prefix_hit_rate",
                "evictions", "cow_copies"):
        assert key in payload, key


@pytest.mark.parametrize("table", ["peak_flops_per_sec",
                                   "hbm_bw_bytes_per_sec"])
def test_unknown_device_kind_raises(table):
    """Exact-kind tables: the v5e row is there, anything else — a kind
    that merely contains 'v5', the CPU — raises."""
    import bench
    fn = getattr(bench, table)
    assert fn("TPU v5 lite") > 0
    for kind in ("TPU v5", "TPU v5p", "tpu v5 lite", "cpu", ""):
        with pytest.raises(KeyError, match="no .* entry for device kind"):
            fn(kind)
