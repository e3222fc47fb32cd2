"""Compile the main path's Pallas kernels for a DESCRIBED v5e chip.

Interpret mode (every other kernel test in this suite) cannot see what
the TPU compiler refuses: scalar stores to VMEM, unaligned slices, VMEM
budgets. The compiler is installed here without a chip, and it compiles
for a topology that is described, not attached — so these tests prove
the kernels chip_smoke.py reaches LOWER at their real widths. Nothing
runs: a pass here is not a chip run.

The topology is described inside a module-scoped fixture (never at
import, in a skipif or in parametrize arguments): only one process may
hold the TPU library, and under xdist every worker imports this file.
This is the only file of its kind for the same reason — a second file
could land on another worker, whose fixture would then skip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.ops import flash_pallas

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


@pytest.fixture()
def mosaic():
    """Real Mosaic lowering (conftest runs the suite interpreted) with
    the persistent compile cache off: an executable compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    prev = flash_pallas._INTERPRET
    flash_pallas.set_interpret(False)
    try:
        yield
    finally:
        flash_pallas.set_interpret(prev)
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_fwd_bwd_124m(mosaic, one_chip):
    q = _s((8, 12, 1024, 64), BF16, one_chip)
    _compile(jax.grad(lambda q, k, v: jnp.sum(
        flash_pallas.pallas_flash_attention(q, k, v)
        .astype(jnp.float32) ** 2), argnums=(0, 1, 2)), q, q, q)


def test_flash_packed_head_group_124m(mosaic, one_chip):
    qkv = _s((8, 1024, 3 * 768), BF16, one_chip)
    _compile(jax.grad(lambda x: jnp.sum(
        flash_pallas.pallas_flash_attention_packed(x, 12)
        .astype(jnp.float32) ** 2)), qkv)


def test_flash_packed_char_dropout(mosaic, one_chip):
    qkv = _s((64, 256, 3 * 384), BF16, one_chip)
    key = _s((2,), jnp.uint32, one_chip)
    _compile(jax.grad(lambda x, k: jnp.sum(
        flash_pallas.pallas_flash_attention_packed(
            x, 6, dropout_rate=0.2, dropout_rng=k)
        .astype(jnp.float32) ** 2)), qkv, key)


def _paged_args(sh, B, W, C=768, psz=16, mp=64, pool_dtype=BF16):
    N = B * mp
    row = _s((B, W, C), BF16, sh["row"])
    pages = _s((N, psz, C), pool_dtype, sh["pool"])
    return (row, row, row, pages, pages,
            _s((B, mp), jnp.int32, sh["rep"]),
            _s((B,), jnp.int32, sh["rep"]))


@pytest.mark.parametrize("window,quant", [(1, False), (8, False),
                                          (1, True)],
                         ids=["w1", "w8", "w1-int8"])
def test_paged_window_attention_124m(mosaic, one_chip, window, quant):
    from replicatinggpt_tpu.ops.paged_pallas import paged_window_attention
    sh = {"row": one_chip, "pool": one_chip, "rep": one_chip}
    args = _paged_args(sh, 8, window,
                       pool_dtype=jnp.int8 if quant else BF16)
    if quant:
        sc = _s((8 * 64, 16), jnp.float32, one_chip)
        fn = lambda q, kn, vn, kp, vp, t, p, ks, vs: (
            paged_window_attention(q, kn, vn, kp, vp, t, p, n_head=12,
                                   k_scales=ks, v_scales=vs))
        _compile(fn, *args, sc, sc)
    else:
        _compile(lambda *a: paged_window_attention(*a, n_head=12), *args)


def test_sharded_paged_window_attention_2x2(mosaic, mesh2x2):
    from replicatinggpt_tpu.ops.paged_pallas import (
        sharded_paged_window_attention)
    sh = {"row": NamedSharding(mesh2x2, P(None, None, "model")),
          "pool": NamedSharding(mesh2x2, P("data", None, "model")),
          "rep": NamedSharding(mesh2x2, P())}
    text = _compile(lambda *a: sharded_paged_window_attention(
        *a, n_head=12, mesh=mesh2x2), *_paged_args(sh, 8, 8))
    assert "all-reduce" in text          # the cross-'data' softmax merge


def test_packed_decode_attention_124m(mosaic, one_chip):
    from replicatinggpt_tpu.ops.decode_pallas import packed_decode_attention
    row = _s((8, 768), BF16, one_chip)
    cache = _s((8, 1024, 768), BF16, one_chip)
    _compile(lambda q, kn, vn, kc, vc, p: packed_decode_attention(
        q, kn, vn, kc, vc, p, n_head=12),
        row, row, row, cache, cache, _s((), jnp.int32, one_chip))


def _char_blocks(cfg, sharding):
    from replicatinggpt_tpu.models.gpt import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                cfg))["blocks"]
    return jax.tree_util.tree_map(
        lambda a: _s(a.shape, a.dtype, sharding), shapes)


@pytest.mark.parametrize("layout", ["heads", "packed"])
def test_fused_decode_layers_char(mosaic, one_chip, layout):
    import dataclasses
    from replicatinggpt_tpu.ops.decode_pallas import (
        fused_decode_layers, fused_decode_supported)
    cfg = dataclasses.replace(get_config("char-gpt").model,
                              decode_cache_layout=layout)
    assert fused_decode_supported(cfg, 1)
    L, H, S, C = cfg.n_layer, cfg.n_head, cfg.block_size, cfg.n_embd
    shape = ((L, 1, S, C) if layout == "packed"
             else (L, 1, H, S, C // H))
    kv = _s(shape, BF16, one_chip)
    _compile(lambda x, b, p, k, v: fused_decode_layers(
        x, b, p, {"k": k, "v": v}, cfg),
        _s((1, C), BF16, one_chip), _char_blocks(cfg, one_chip),
        _s((), jnp.int32, one_chip), kv, kv)


def test_fused_paged_decode_layers_char(mosaic, one_chip):
    """The kernel the engine's decode == "fused" route runs — refused
    by the TPU compiler ("Cannot store scalars to VMEM") until its
    per-head running max/sum became (1, 1) vector-row updates."""
    import dataclasses
    from replicatinggpt_tpu.ops.decode_pallas import (
        fused_paged_decode_layers, fused_paged_decode_supported)
    cfg = dataclasses.replace(get_config("char-gpt").model,
                              decode_cache_layout="packed")
    B, psz, mp = 8, 16, 16
    assert fused_paged_decode_supported(cfg, B, psz)
    L, C = cfg.n_layer, cfg.n_embd
    pool = _s((L, B * mp, psz, C), BF16, one_chip)
    _compile(lambda x, b, p, t, k, v: fused_paged_decode_layers(
        x, b, p, t, {"k": k, "v": v}, cfg),
        _s((B, C), BF16, one_chip), _char_blocks(cfg, one_chip),
        _s((B,), jnp.int32, one_chip), _s((B, mp), jnp.int32, one_chip),
        pool, pool)
