"""Pins from the chip bring-up (ISSUE 24): where the compile cache
lives, one process per chip, no quiet way off the device, and the two
faults the rehearsals of chip_smoke.py found in the program."""

import os
import subprocess
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code: str, **env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": REPO, **env})


def test_compile_cache_dir_comes_from_the_environment(monkeypatch):
    from replicatinggpt_tpu.utils import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    # set from outside: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == prev


def test_compile_cache_default_is_the_fixed_checkout_path(monkeypatch):
    from replicatinggpt_tpu.utils import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got   # no pid/time
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_multiproc_parent_initializes_no_backend(tmp_path):
    """`serve --multiproc`'s parent builds the worker specs and the
    fleet's shape hash — mesh included — without touching a device:
    its workers own the chips."""
    r = _child(f"""
from jax._src import xla_bridge
from replicatinggpt_tpu import cli
from replicatinggpt_tpu.utils.compile_cache import enable_compile_cache
args = cli.build_parser().parse_args([
    "serve", "--preset", "test-tiny", "--multiproc", "--replicas", "2",
    "--journal-dir", {str(tmp_path)!r}, "--mesh-shape", "2x2",
    "--paged-kernel", "--autoscale-max", "3"])
enable_compile_cache()
cli._apply_rng_impl(args)
specs, expect, autoscale, factory = cli._multiproc_plan(args)
assert len(specs) == 2 and "--mesh-shape" in specs[0].cmd
assert len(expect) == 16 and autoscale.max_workers == 3
factory(2)
assert not xla_bridge.backends_are_initialized(), "parent touched jax"
print("PARENT-OFF-JAX")
""")
    assert "PARENT-OFF-JAX" in r.stdout, r.stderr[-2000:]


def test_chip_smoke_without_a_tpu_fails_at_the_device_phase():
    """No accelerator: the first phase fails, the exit code is not 0,
    and no ok line is printed."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    assert [l for l in lines if '"phase": "device"' in l]
    assert not [l for l in lines if '"phase": "build"' in l]
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_interpret_mode_is_asked_for_never_inferred():
    """On a backend that is not a TPU the kernels do NOT quietly run
    interpreted: only set_interpret(True) (this suite's conftest, or
    bench.py --platform cpu) does that."""
    from replicatinggpt_tpu.ops import flash_pallas
    assert jax.default_backend() == "cpu"
    assert flash_pallas._interpret_mode() is True       # conftest asked
    flash_pallas.set_interpret(False)
    try:
        assert flash_pallas._interpret_mode() is False
    finally:
        flash_pallas.set_interpret(True)


def test_decode_kernel_gate_reads_the_inputs_shardings():
    """Single-device inputs keep the decode kernels on a multi-device
    host (this one has 8), traced or concrete; mesh-sharded inputs
    turn them off."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from replicatinggpt_tpu.models.gpt import _default_allow_pallas
    assert jax.device_count() > 1
    one = jnp.zeros((4, 4))
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    split = jax.device_put(one, NamedSharding(mesh, P("model")))
    assert _default_allow_pallas(one) is True
    assert _default_allow_pallas(one, split) is False
    seen = []
    jax.jit(lambda a: seen.append(_default_allow_pallas(a)) or a)(one)
    jax.jit(lambda a: seen.append(_default_allow_pallas(a)) or a)(split)
    assert seen == [True, False]


def test_window_uploads_do_not_alias_the_host_mirrors():
    """An in-flight window must not see the host's later writes to the
    mirrors it was launched from (jnp.asarray of a numpy array aliases
    its memory on the CPU backend): an admission's `_active[slot] =
    True` reached back into the running window and the slot decoded a
    garbage token before its prefill."""
    from replicatinggpt_tpu.serve.engine import _upload
    mirror = np.zeros((4096,), np.int32)
    dev = _upload(mirror)
    mirror[:] = 7
    assert int(np.asarray(dev).sum()) == 0


def test_state_specs_are_in_jits_own_spelling():
    """Trailing Nones trimmed and size-1 axes dropped, so the train
    state enters the K-step mesh dispatch exactly as it leaves it and
    the step compiles once (it compiled twice on dp x tp and on FSDP,
    and the runner's CompileGuard refused the second)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from replicatinggpt_tpu.config import MeshConfig
    from replicatinggpt_tpu.parallel.mesh import state_pspecs
    tree = {"blocks": {"attn_out_kernel": jnp.zeros((2, 32, 32)),
                       "qkv_kernel": jnp.zeros((2, 32, 96)),
                       "ln1_scale": jnp.zeros((2, 32))}}
    tp = state_pspecs(tree, MeshConfig(data=2, model=2))["blocks"]
    assert tp["attn_out_kernel"] == P(None, "model")
    assert tp["qkv_kernel"] == P(None, None, "model")
    assert tp["ln1_scale"] == P()
    fsdp = state_pspecs(tree, MeshConfig(data=4, fsdp=True))["blocks"]
    assert fsdp["qkv_kernel"] == P(None, "data")      # no "model" of 1
