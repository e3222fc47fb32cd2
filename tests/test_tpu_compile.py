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
import re

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


N_LAYERS = 3    # the kernels take the STACKED pool and a traced layer


def _paged_args(sh, B, W, C=768, psz=16, mp=64, pool_dtype=BF16):
    """``(q, k_new, v_new, k_pool, v_pool, tables, pos, layer)``."""
    N = B * mp
    row = _s((B, W, C), BF16, sh["row"])
    pages = _s((N_LAYERS, N, psz, C), pool_dtype, sh["pool"])
    return (row, row, row, pages, pages,
            _s((B, mp), jnp.int32, sh["rep"]),
            _s((B,), jnp.int32, sh["rep"]), _s((), jnp.int32, sh["rep"]))


@pytest.mark.parametrize("window,quant,B,C,H", [
    (1, False, 8, 768, 12), (8, False, 8, 768, 12), (1, True, 8, 768, 12),
    # the serve cells' engine: gpt2-large, 96 slots, 64 pages of 16 a slot
    (1, False, 96, 1280, 20), (8, False, 96, 1280, 20),
    (1, True, 96, 1280, 20), (1, "head", 96, 1280, 20)],
    ids=["w1", "w8", "w1-int8", "large-w1", "large-w8", "large-w1-int8",
         "large-w1-int8-head"])
def test_paged_window_attention_124m(mosaic, one_chip, window, quant, B, C,
                                     H):
    """The walk in blocks of 8 pages (``block_pages``) at gpt2-small's
    and gpt2-large's widths, a grid turn a slot and the slot's blocks a
    loop of traced length in the body: a block is ONE pass for all of a
    slot's heads at W = 1 (two passes of ten at gpt2-large's W = 8), and a
    quantized pool's page or head scales one (blocks, 1 or H, 128)
    operand a slot, indexed by block inside the loop."""
    from replicatinggpt_tpu.ops.paged_pallas import (block_pages,
                                                     paged_window_attention)
    sh = {"row": one_chip, "pool": one_chip, "rep": one_chip}
    args = _paged_args(sh, B, window, C=C,
                       pool_dtype=jnp.int8 if quant else BF16)
    assert block_pages(16, 64, C * (1 if quant else 2)) == 8
    if quant:
        sc = _s((N_LAYERS, B * 64, 16) + ((H,) if quant == "head" else ()),
                jnp.float32, one_chip)
        fn = lambda q, kn, vn, kp, vp, t, p, l, ks, vs: (
            paged_window_attention(q, kn, vn, kp, vp, t, p, n_head=H,
                                   layer=l, k_scales=ks, v_scales=vs))
        text = _compile(fn, *args, sc, sc)
    else:
        text = _compile(lambda *a: paged_window_attention(
            *a[:-1], n_head=H, layer=a[-1]), *args)
    assert all(n.startswith("paged_window_attention")
               for n in _kernel_names(text)) and _kernel_names(text)


@pytest.mark.parametrize("window,name", [
    (0, "paged_window_attention"), (128, "swa_window_attention")],
    ids=["full-layer", "window-layer-ring"])
def test_paged_gqa_attention_kexaone_widths(mosaic, one_chip, window, name):
    """The grouped-query kernel at K-EXAONE's published widths (64 query
    heads on 8 KV heads of 128, pages of 16) for the described v5e: a full
    layer over a slot's 512-entry table, a window layer over its 9-page
    ring from ``page0``; the instruction wears the ``name=`` it was given."""
    from replicatinggpt_tpu.ops.paged_pallas import (block_pages,
                                                     paged_gqa_attention)
    B, psz, mp = 64, 16, (9 if window else 512)
    # blocks of 8 pages: up to 64 a slot's loop, and the ring a block and
    # a short one
    assert block_pages(psz, mp, 8 * 128 * 2) == 8
    q = _s((B, 1, 64 * 128), BF16, one_chip)
    kv = _s((B, 1, 8 * 128), BF16, one_chip)
    # the family's pools: one layer an array, read at layer 0
    pages = _s((1, B * mp if window else 10240, psz, 8 * 128), BF16,
               one_chip)
    vec = _s((B,), jnp.int32, one_chip)
    text = _compile(
        lambda q, k, v, kp, vp, t, p, p0: paged_gqa_attention(
            q, k, v, kp, vp, t, p, n_head=64, n_kv_head=8, layer=0,
            attn_window=window, page0=p0 if window else None, name=name),
        q, kv, kv, pages, pages, _s((B, mp), jnp.int32, one_chip), vec, vec)
    assert re.search(rf"%{name}[\w.]* = [^\n]*tpu_custom_call", text)


def test_paged_gqa_attention_lfm2_widths(mosaic, one_chip):
    """The grouped-query kernel at LFM2-24B-A2B's published widths (32
    query heads on 8 KV heads of 64: a block of 4 x 64 rows a KV head, a
    pool row of 512 lanes) at the cell's 256 slots of 512 table entries
    and 40,960 pages: 256 grid turns of up to 64 blocks, and a walk whose
    scalars fit the chip's scalar memory only because the table says
    which entries are owned (a table AND a mask were 1.19 MB of its 1 MB;
    the table and the slots' block lists are 0.59 MB)."""
    from replicatinggpt_tpu.ops.paged_pallas import (block_pages,
                                                     paged_gqa_attention)
    B, psz, mp = 256, 16, 512
    assert block_pages(psz, mp, 8 * 64 * 2) == 8
    q = _s((B, 1, 32 * 64), BF16, one_chip)
    kv = _s((B, 1, 8 * 64), BF16, one_chip)
    pages = _s((1, 40_960, psz, 8 * 64), BF16, one_chip)
    vec = _s((B,), jnp.int32, one_chip)
    text = _compile(
        lambda q, k, v, kp, vp, t, p: paged_gqa_attention(
            q, k, v, kp, vp, t, p, n_head=32, n_kv_head=8, layer=0),
        q, kv, kv, pages, pages, _s((B, mp), jnp.int32, one_chip), vec)
    assert re.search(r"%paged_window_attention[\w.]* = [^\n]*tpu_custom_call",
                     text)


def _lower_decode_window(one_chip, cfg, family, B, n_pages, mp,
                         served=None):
    """The engine's decode window (k = 1, the Pallas route, pages of 16)
    of ``family`` at ``cfg``, ``B`` slots and ``mp`` table entries,
    lowered for the described v5e (``served``: GPT-2's cast leaves, the
    avals of the tree its engine serves)."""
    from replicatinggpt_tpu.serve import engine
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: _s(a.shape, a.dtype, one_chip), tree)
    params = jax.eval_shape(
        lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    if served:
        params = jax.eval_shape(
            lambda t: engine.served_tree(t, served, cfg.dtype), params)
    cache = jax.eval_shape(lambda: family.init_paged_kv_pool(
        cfg, n_pages, 16, **({} if served else {"n_slots": B})))
    vec = lambda dt: _s((B,), dt, one_chip)
    return engine._engine_decode_window.lower(
        shaped(params), vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32), vec(jnp.int32), _s((5, B), jnp.int32, one_chip),
        _s((B, mp), jnp.int32, one_chip), shaped(cache),
        _s((B, 2), jnp.uint32, one_chip), vec(jnp.float32), vec(jnp.int32),
        vec(jnp.float32), vec(jnp.bool_), cfg, k=1, use_pallas=True)


def test_lfm2_decode_step_carries_its_scopes(mosaic, one_chip):
    """The lfm2_moe decode window (published widths, the first three
    layers: conv conv full, dense dense sparse, 8 experts) compiled for
    the described v5e on the Pallas route: the scopes the cell's readers
    read (``short_conv``, ``moe_experts``) are on the ops, the full
    layer's kernel keeps the accepted readers' name, and the conv state
    is donated and written in place like the pages."""
    import dataclasses
    from replicatinggpt_tpu.models import lfm2_moe
    base = get_config("lfm2-24b-a2b").model
    cfg = dataclasses.replace(
        base, n_layer=3, layer_types=base.layer_types[:3],
        mlp_layer_types=("dense", "dense", "sparse"), n_experts=8,
        experts_held=tuple(range(8)), vocab_size=4096)
    text = _lower_decode_window(one_chip, cfg, lfm2_moe, 16, 1024,
                                512).compile().as_text()
    assert _kernel_names(text) and all(
        n.startswith("paged_window_attention") for n in _kernel_names(text))
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("short_conv", "attn_global", "kv_scatter", "mlp",
                  "moe_router", "moe_experts", "head", "embed", "sample"):
        assert any(re.search(rf"(^|/){scope}(/|$)", n) for n in op_names), \
            scope
    assert not any(re.search(r"(^|/)(attn_swa|moe_shared)(/|$)", n)
                   for n in op_names)


@pytest.mark.parametrize("preset,kinds", [
    ("k-exaone-236b-a23b", {"swa_window_attention": 6,
                            "paged_window_attention": 2}),
    ("lfm2-24b-a2b", {"paged_window_attention": 2}),
    ("gpt2-large", {"paged_window_attention": 3}),
], ids=["kexaone", "lfm2", "gpt2-layers-unrolled"])
def test_decode_program_lowers_the_kernel_once_a_layer_kind(mosaic, one_chip,
                                                            preset, kinds):
    """Every process start traces and lowers its decode program, and
    ``setup_s`` carries that whether or not the compile cache is warm
    (PR 38 was refused for it: a costlier kernel body lowered once a
    LAYER). The three served families' decode windows at their cells'
    real widths and slots, lowered for the described v5e (the text
    ``jit(...).lower()`` leaves, BEFORE the compiler inlines): where the
    layers are a Python loop the LOWERED module holds each kernel ONCE A
    KIND (K-EXAONE's 6 window layers and 2 full layers: 2 kernels, not 8;
    LFM2's 2 full layers: 1; gpt2-large cut to 3 layers and unrolled: 1),
    and every layer of a kind calls the one private function, which the
    compiler inlines afterwards."""
    import dataclasses
    from replicatinggpt_tpu.models import exaone_moe, gpt, lfm2_moe
    cfg = get_config(preset).model
    if preset == "gpt2-large":
        cfg = dataclasses.replace(cfg, n_layer=3, scan_layers=False,
                                  decode_cache_layout="packed")
        low = _lower_decode_window(one_chip, cfg, gpt, 96, 3072, 64,
                                   served=gpt.SERVE_CAST_LEAVES)
    elif preset == "lfm2-24b-a2b":
        low = _lower_decode_window(one_chip, cfg, lfm2_moe, 256, 40_960, 512)
    else:
        low = _lower_decode_window(one_chip, cfg, exaone_moe, 64, 10_240,
                                   512)
    text = low.as_text()
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call",
                          text)) == len(kinds)
    for name, layers in kinds.items():
        held = re.findall(rf'kernel_name = "{name}"', text)
        assert len(held) == 1, (name, len(held))
    # and the layers of a kind are calls of the kind's one function
    calls = re.findall(r"call @(_(?:gqa|window)_call\w*)\(", text)
    assert sorted(map(calls.count, set(calls))) == sorted(kinds.values())


def test_sharded_paged_window_attention_2x2(mosaic, mesh2x2):
    from replicatinggpt_tpu.ops.paged_pallas import (
        sharded_paged_window_attention)
    sh = {"row": NamedSharding(mesh2x2, P(None, None, "model")),
          "pool": NamedSharding(mesh2x2, P(None, "data", None, "model")),
          "rep": NamedSharding(mesh2x2, P())}
    text = _compile(lambda *a: sharded_paged_window_attention(
        *a[:-1], n_head=12, mesh=mesh2x2, layer=a[-1]),
        *_paged_args(sh, 8, 8))
    assert "all-reduce" in text          # the cross-'data' softmax merge


def test_packed_decode_attention_124m(mosaic, one_chip):
    from replicatinggpt_tpu.ops.decode_pallas import packed_decode_attention
    row = _s((8, 768), BF16, one_chip)
    cache = _s((8, 1024, 768), BF16, one_chip)
    _compile(lambda q, kn, vn, kc, vc, p: packed_decode_attention(
        q, kn, vn, kc, vc, p, n_head=12),
        row, row, row, cache, cache, _s((), jnp.int32, one_chip))


# ---------------------------------------------------------------------------
# names: a kernel's name= is its HLO instruction's name, a named_scope is
# in the op's metadata (what the profiler's trace and chipbench read)
# ---------------------------------------------------------------------------

_CUSTOM_CALL = re.compile(
    r'%([\w.\-]+) = [^\n]*custom-call\([^\n]*'
    r'custom_call_target="tpu_custom_call"')


def _kernel_names(text):
    return _CUSTOM_CALL.findall(text)


def _flash_split(one_chip):
    q = _s((2, 12, 1024, 64), BF16, one_chip)
    return (jax.grad(lambda q, k, v: jnp.sum(
        flash_pallas.pallas_flash_attention(q, k, v)
        .astype(jnp.float32) ** 2), argnums=(0, 1, 2)), (q, q, q))


def _flash_group(one_chip):
    qkv = _s((2, 1024, 3 * 768), BF16, one_chip)
    return (jax.grad(lambda x: jnp.sum(
        flash_pallas.pallas_flash_attention_packed(x, 12)
        .astype(jnp.float32) ** 2)), (qkv,))


def _flash_group_remat(one_chip):
    """Forward under jax.checkpoint, as the trainer runs it: the remat
    replay of the forward kernel keeps the forward's name."""
    qkv = _s((2, 1024, 3 * 768), BF16, one_chip)
    fwd = jax.checkpoint(lambda x: flash_pallas
                         .pallas_flash_attention_packed(x, 12))
    return (jax.grad(lambda x: jnp.sum(
        fwd(x * 2).astype(jnp.float32) ** 2)), (qkv,))


def _paged(one_chip):
    from replicatinggpt_tpu.ops.paged_pallas import paged_window_attention
    sh = {"row": one_chip, "pool": one_chip, "rep": one_chip}
    return (lambda *a: paged_window_attention(*a[:-1], n_head=12,
                                              layer=a[-1]),
            _paged_args(sh, 8, 1))


def _packed_decode(one_chip):
    from replicatinggpt_tpu.ops.decode_pallas import packed_decode_attention
    row = _s((8, 768), BF16, one_chip)
    cache = _s((8, 1024, 768), BF16, one_chip)
    return (lambda q, kn, vn, kc, vc, p: packed_decode_attention(
        q, kn, vn, kc, vc, p, n_head=12),
        (row, row, row, cache, cache, _s((), jnp.int32, one_chip)))


@pytest.mark.parametrize("build,fwd,bwd", [
    (_flash_split, r"flash_\w*fwd", r"flash_\w*bwd"),
    (_flash_group, r"flash_group\w*_fwd", r"flash_group\w*_bwd"),
    (_flash_group_remat, r"flash_group\w*_fwd", r"flash_group\w*_bwd"),
    (_paged, r"^paged_window_attention\.", None),
    (_packed_decode, r"^decode_attention\.", None),
], ids=["flash", "flash-group", "flash-group-remat", "paged-window",
        "packed-decode"])
def test_kernel_name_is_the_hlo_instruction_name(mosaic, one_chip, build,
                                                 fwd, bwd):
    """Compiled for the described v5e, every ``tpu_custom_call`` of a
    main-path kernel is an instruction named after the kernel's
    ``name=`` (``%paged_window_attention.3``), which is the name the
    profiler's trace prints and ``chipbench``'s ``trace_sum`` patterns
    match: none is left under the enclosing function's name
    (``closed_call``, ``checkpoint``, ``rematted_computation``). The
    name arrives through the name stack, so a kernel traced under
    autodiff wears the transform: ``jvp_flash_group_fwd_`` for the
    forward of a differentiated call, ``transpose_jvp_flash_group_bwd__``
    for its backward, the bare ``flash_group_fwd`` / ``flash_group_bwd``
    under ``jax.checkpoint``. Patterns over the flash family therefore
    search for the name and do not anchor it."""
    fn, shapes = build(one_chip)
    names = _kernel_names(_compile(fn, *shapes))
    assert names
    wanted = [rx for rx in (fwd, bwd) if rx]
    assert all(any(re.search(rx, n) for rx in wanted) for n in names), names
    for rx in wanted:
        assert any(re.search(rx, n) for n in names), (rx, names)
    if build is _flash_group_remat:      # forward + its replay
        assert sum(bool(re.search(fwd, n)) for n in names) == 2, names


POOL_LAYER = (64, 16, 768)      # one layer of ``_window_hlo``'s pool


def _window_hlo(program, sharding, n_layer=2, scan_layers=False,
                served=False, **route):
    """Optimized HLO of the engine's decode-window (``"decode"``),
    mixed-window (``"mixed"``) program at k=1 or prefill-chunk
    (``"prefill"``) program, gpt2-small's widths and ``n_layer`` layers
    (``scan_layers``: one traced layer index, as gpt2-large's 36 layers
    run), compiled for ``sharding``'s device (None: this process's
    CPU). The pool is ``(n_layer,) + POOL_LAYER``. ``served``: the
    parameters' avals are those of the tree the engine serves
    (``engine.served_tree``), not of the float32 masters."""
    import dataclasses
    from replicatinggpt_tpu.models.gpt import (
        SERVE_CAST_LEAVES as gpt_serve_cast_leaves, init_paged_kv_pool,
        init_params)
    from replicatinggpt_tpu.serve import engine
    cfg = dataclasses.replace(
        get_config("gpt2-small").model, n_layer=n_layer,
        scan_layers=scan_layers, decode_cache_layout="packed")
    B, psz, mp, chunk = 8, 16, 8, 16
    assert (B * mp, psz, cfg.n_embd) == POOL_LAYER
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: _s(a.shape, a.dtype, sharding), tree)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    if served:
        params = jax.eval_shape(
            lambda t: engine.served_tree(t, gpt_serve_cast_leaves, cfg.dtype),
            params)
    params = shaped(params)
    cache = shaped(jax.eval_shape(
        lambda: init_paged_kv_pool(cfg, B * mp, psz)))
    vec = lambda dt: _s((B,), dt, sharding)
    state = (params, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
             vec(jnp.int32), vec(jnp.int32),
             _s((5, B), jnp.int32, sharding))
    prefill = (_s((3, B), jnp.int32, sharding),
               _s((1, B, chunk), jnp.int32, sharding))
    rest = (_s((B, mp), jnp.int32, sharding), cache,
            _s((B, 2), jnp.uint32, sharding), vec(jnp.float32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.bool_), cfg)
    if program == "decode":
        low = engine._engine_decode_window.lower(*state, *rest, k=1, **route)
    elif program == "prefill":
        scalar = _s((), jnp.int32, sharding)
        low = engine._engine_prefill.lower(
            params, _s((1, chunk), jnp.int32, sharding), scalar, scalar,
            _s((mp,), jnp.int32, sharding), np.int32(0), cache, cfg)
    else:
        low = engine._engine_mixed_window.lower(*state, *prefill, *rest,
                                                k=1, **route)
    return low.compile().as_text()


def test_decode_step_hlo_carries_the_phase_scopes(mosaic, one_chip):
    """The engine's jitted decode step, compiled for the described v5e
    on the Pallas route: ``jax.named_scope`` reaches no instruction NAME
    (they stay ``%sort.N``, ``%fusion.N``; only the PARAMETER of a
    ``cond`` branch is called after its scope) but is in the ops'
    ``op_name`` metadata, where the trace's scope stat comes from."""
    text = _window_hlo("decode", one_chip, use_pallas=True)
    assert _kernel_names(text) and all(
        n.startswith("paged_window_attention") for n in _kernel_names(text))
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("sample", "kv_gather", "kv_scatter", "attn", "mlp",
                  "head", "embed"):
        assert any(re.search(rf"(^|/){scope}(/|$)", n) for n in op_names), \
            scope
    assert not re.search(r"%(sample|kv_gather)[\w.]* = (?!.* parameter\()",
                         text)
    # what is left under ``kv_gather`` on this route is the kernel's walk
    # (``_blocked_walk``: integer work on the tables and positions), inside
    # ``attn``; the kernel itself is not under it, or a reader of the scope
    # (``prefill_kv_gather_ms``; ``decode_kv_gather_ms`` until PR 36) would
    # read the kernel's time
    under = [n for n in op_names if re.search(r"(^|/)kv_gather(/|$)", n)]
    assert under and all(re.search(r"(^|/)attn/kv_gather(/|$)", n)
                         for n in under), under
    assert not any(re.search(r"kv_gather/.*paged_window_attention", n)
                   for n in op_names)


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_programs_address_the_stacked_pool_in_place(mosaic, one_chip,
                                                          program):
    """The decode window on the Pallas route and the prefill chunk,
    compiled for the described v5e with the layers in ONE scan (a traced
    layer index, as gpt2-large runs): no instruction's result has a
    layer's shape ``[n_pages, page, C]`` (the slice the kernel and the
    gather used to be handed: the whole pool read and written once a
    step), and the only instructions with the pool's whole shape are the
    program's parameters, the loop's carries and the in-place
    ``scatter`` of the fresh rows, bare or as the root of its fusion. No
    ``copy``, no ``dynamic-slice``: the kernel and the gather read the
    carried pool in place and XLA orders them before the write."""
    L = 3
    text = _window_hlo(program, one_chip, n_layer=L, scan_layers=True,
                       **({"use_pallas": True} if program == "decode"
                          else {}))
    layer = ",".join(map(str, POOL_LAYER))
    roots, found = {}, []
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line)
        if head:
            comp = head.group(1)
        m = _HLO_RESULT.match(line)
        if m:
            if "ROOT " in line:
                roots[comp] = m.group(3)
            found.append((m.group(1), m.group(2), m.group(3), line))
    assert any(dims == f"{L},{layer}" for _, dims, _, _ in found)
    for name, dims, opcode, line in found:
        assert dims != layer, f"a layer of the pool is materialised: {line}"
        if dims == f"{L},{layer}":
            if opcode == "fusion":
                opcode = roots[re.search(r"calls=%?([\w.\-]+)",
                                         line).group(1)]
            assert opcode in ("parameter", "get-tuple-element",
                              "scatter"), line


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_served_tree_leaves_no_weight_cast_in_a_launch(mosaic, one_chip,
                                                       program):
    """The decode window and the prefill chunk compiled for the described
    v5e with the layers in one scan: given the avals of the tree the
    engine SERVES (kernels, biases, ``wte``, ``wpe`` in bfloat16) the
    optimised HLO converts no float32 operand of a kernel's, ``wte``'s or
    ``wpe``'s shape to bfloat16; given the float32 masters' avals it
    does, for each of them, OUTSIDE the layer loop and for all layers at
    once: the copy of the weights every launch made."""
    L, C = 3, 768
    weights = {f"{L},{C},{3 * C}", f"{L},{C},{C}", f"{L},{C},{4 * C}",
               f"{L},{4 * C},{C}", f"50257,{C}", f"1024,{C}"}

    def weight_casts(text):
        """Shapes, among ``weights``, that a ``convert`` to bfloat16
        produces (XLA leaves these bare: one whole-array instruction
        each, hoisted out of the layer loop)."""
        return {dims for dims in re.findall(
            r"= bf16\[([\d,]*)\]\S* convert\(", text) if dims in weights}

    route = {"use_pallas": True} if program == "decode" else {}
    masters = _window_hlo(program, one_chip, n_layer=L, scan_layers=True,
                          **route)
    served = _window_hlo(program, one_chip, n_layer=L, scan_layers=True,
                         served=True, **route)
    assert weight_casts(masters) == weights, weight_casts(masters)
    assert weight_casts(served) == set()
    # and nothing float32 of a weight's shape is left in the program at all
    assert not [d for d in re.findall(r"f32\[([\d,]*)\]", served)
                if d in weights]


_HLO_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation"
    r")=%?([\w.\-]+)|(?:branch_computations|called_computations)=\{([^}]*)\}")


def _sorts_by_branch(text):
    """``(n_conditionals, sorts, sorts_always)`` of an optimized HLO
    module: how many ``conditional`` instructions it holds, how many
    ``sort``s, and how many of those the entry computation reaches
    WITHOUT going through a conditional's taken-when-true branch (the
    last of ``lax.cond``'s two ``branch_computations``)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif cur is not None and line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    names = lambda blob: [n.strip().lstrip("%") for n in blob.split(",")]
    n_cond, n_sorts, always = 0, {}, {}
    for comp, lines in comps.items():
        n_sorts[comp] = sum(bool(re.search(r"\bsort\(", ln)) for ln in lines)
        always[comp] = set()     # what it calls whatever a predicate says
        for ln in lines:
            called = [n for one, many in _HLO_CALLS.findall(ln)
                      for n in ([one] if one else names(many))]
            if " conditional(" in ln:
                n_cond += 1
                true = re.search(r"true_computation=%?([\w.\-]+)", ln)
                called.remove(true.group(1) if true else names(re.search(
                    r"branch_computations=\{([^}]*)\}", ln).group(1))[-1])
            always[comp].update(called)
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp not in seen:
            seen.add(comp)
            todo.extend(always[comp])
    return (n_cond, sum(n_sorts.values()),
            sum(n for comp, n in n_sorts.items() if comp in seen))


@pytest.mark.parametrize("program,target", [
    ("decode", "v5e"), ("mixed", "v5e"), ("decode", "cpu"), ("mixed", "cpu")])
def test_window_program_sorts_only_inside_a_taken_branch(request, program,
                                                         target):
    """The sampler's ``lax.cond``s survive optimization as real
    ``conditional`` instructions, in the decode window and in the mixed
    window, for the described v5e and for the CPU: the top-p filter's
    ``sort``s sit in a taken-when-true branch and nowhere else, so an
    all-greedy launch never runs them. A ``cond`` that a ``vmap`` or
    the compiler turned into a ``select`` puts them back on every
    step's path, and fails here."""
    if target == "v5e":
        request.getfixturevalue("mosaic")
        sharding = request.getfixturevalue("one_chip")
        route = ({"use_pallas": True} if program == "decode"
                 else {"use_kernel": True})
    else:
        sharding, route = None, {}
    n_cond, sorts, sorts_always = _sorts_by_branch(
        _window_hlo(program, sharding, **route))
    assert n_cond >= 3             # draw, top-k, top-p: one scalar each
    assert sorts >= 1 and sorts_always == 0
