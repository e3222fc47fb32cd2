"""Device mesh + partition rules: the framework's entire distributed layer.

The reference has no distributed machinery (SURVEY.md §2.1-§2.2). The
TPU-native replacement is declarative: build a ``jax.sharding.Mesh`` over
axes ``('data', 'seq', 'model')``, attach ``NamedSharding``s to the train
state and batches, and let XLA GSPMD insert the collectives (psum for DP
grad reduction, all-gather for FSDP parameter gathering, reduce-scatter /
all-reduce around the Megatron-style column/row-parallel matmuls) over
ICI/DCN. No hand-written transport code exists anywhere in the framework —
that is the point.

Partition rules (Megatron-style TP over 'model', SURVEY.md §2.1 table):

=====================  ==================  ==========================
param                  shape               spec (layer-stacked dim 0)
=====================  ==================  ==========================
wte                    (V, C)              ('model', None) — vocab-parallel
                                           embedding + tied head
lm_head (untied)       (C, V)              (None, 'model')
qkv_kernel             (L, C, 3C)          (None, None, 'model')  column
attn_out_kernel        (L, C, C)           (None, 'model', None)  row
mlp_up_kernel          (L, C, 4C)          (None, None, 'model')  column
mlp_down_kernel        (L, 4C, C)          (None, 'model', None)  row
biases of column ops   (L, K)              (None, 'model')
everything else        —                   replicated
=====================  ==================  ==========================

FSDP (``MeshConfig.fsdp``) additionally shards each param (and its Adam
moments, which inherit specs by tree-path) over 'data' on the largest
still-unsharded divisible dim — ZeRO-3 semantics for free under GSPMD.
Batches are (B, T) sharded ('data', 'seq').
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import set_mesh
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import MeshConfig, ModelConfig

# param-name → (tp_dim or None); dims are indices into the *unstacked* shape
# (block params carry a leading layer dim handled by offset)
_COLUMN_PARALLEL = {"qkv_kernel", "mlp_up_kernel"}
_COLUMN_BIAS = {"qkv_bias", "mlp_up_bias"}
_ROW_PARALLEL = {"attn_out_kernel", "mlp_down_kernel"}


def make_mesh(mesh_cfg: MeshConfig,
              devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    n = mesh_cfg.n_devices
    assert len(devices) >= n, (
        f"mesh needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(
        mesh_cfg.data, mesh_cfg.seq, mesh_cfg.model, mesh_cfg.pipe)
    return Mesh(arr, mesh_cfg.axis_names)


def batch_pspec() -> P:
    return P("data", "seq")


def make_batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec())


def superbatch_pspec() -> P:
    """(K, B, T) stacked multi-step superbatch: the scan dim replicates,
    batch rows and sequence keep the (data, seq) layout of a single batch —
    so a K-step lax.scan dispatch sees each step's batch sharded exactly
    like the single-step path."""
    return P(None, "data", "seq")


def make_superbatch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, superbatch_pspec())


def _tp_spec(name: str, ndim: int) -> list:
    """Tensor-parallel placement for a leaf called ``name``."""
    spec = [None] * ndim
    if name == "wte":
        spec[0] = "model"
    elif name == "lm_head":
        spec[1] = "model"
    elif name in _COLUMN_PARALLEL:
        spec[ndim - 1] = "model"
    elif name in _COLUMN_BIAS:
        spec[ndim - 1] = "model"
    elif name in _ROW_PARALLEL:
        spec[ndim - 2] = "model"
    return spec


def _leaf_spec(path, shape: Tuple[int, ...], mesh_cfg: MeshConfig) -> P:
    """Spec for one leaf of the train state, identified by its tree path.

    Works uniformly for params and optimizer moments because optax's
    mu/nu subtrees mirror the params dict, so the param name appears as the
    final DictKey on the path either way.
    """
    name = None
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            name = str(k.key)
            break
    in_blocks = any(isinstance(k, jax.tree_util.DictKey)
                    and str(k.key) == "blocks" for k in path)
    ndim = len(shape)
    spec = [None] * ndim
    if name is not None and ndim > 0:
        spec = _tp_spec(name, ndim)
        # drop TP sharding where the dim isn't divisible by the axis size
        for d, ax in enumerate(spec):
            if ax == "model" and shape[d] % mesh_cfg.model != 0:
                spec[d] = None
    # pipeline: each stage stores its slice of the layer-stacked (L, ...) dim
    if (mesh_cfg.pipe > 1 and in_blocks and ndim > 0
            and shape[0] % mesh_cfg.pipe == 0):
        spec[0] = "pipe"
    if mesh_cfg.fsdp and ndim > 0:
        # shard the largest unsharded divisible dim over 'data' (ZeRO-3)
        dims = sorted(range(ndim), key=lambda d: -shape[d])
        for d in dims:
            if spec[d] is None and shape[d] % mesh_cfg.data == 0 \
                    and shape[d] >= mesh_cfg.data:
                spec[d] = "data"
                break
    # size-1 axes dropped and trailing Nones trimmed: jit NORMALIZES
    # output specs this way and keys its cache on the spec's
    # REPRESENTATION, so a state that goes in as P(None, 'model', None)
    # and comes back as P(None, 'model') — or names a 'model' axis of
    # size 1 — compiles the step a second time (CompileGuard caught it
    # on the runner's K-step mesh path); page_pool_pspec does the same
    spec = [None if ax is not None and getattr(mesh_cfg, ax) == 1
            else ax for ax in spec]
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def state_pspecs(tree: Any, mesh_cfg: MeshConfig) -> Any:
    """PartitionSpec pytree for any state-shaped tree (TrainState, params,
    opt_state, ...). Scalars / unnamed leaves replicate."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path, tuple(leaf.shape), mesh_cfg),
        tree)


def state_shardings(tree: Any, mesh: Mesh, mesh_cfg: MeshConfig) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), state_pspecs(tree, mesh_cfg))


def param_pspecs(mcfg: ModelConfig, mesh_cfg: MeshConfig) -> Any:
    """Specs for just the model params (used by checkpoint restore and the
    HF importer)."""
    from ..models.gpt import init_params
    abstract = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), mcfg))
    return state_pspecs(abstract, mesh_cfg)


def shard_train_state(create_fn: Callable[[], Any], mesh: Mesh,
                      mesh_cfg: MeshConfig) -> Any:
    """Initialize train state directly in its sharded layout: jit the
    initializer with out_shardings so every device materializes only its own
    parameter/optimizer shards (no host-side full copy)."""
    abstract = jax.eval_shape(create_fn)
    shardings = state_shardings(abstract, mesh, mesh_cfg)
    with set_mesh(mesh):
        return jax.jit(create_fn, out_shardings=shardings)()


# ---------------------------------------------------------------------------
# Serving mesh: the (data, model) layout of the sharded engine
# ---------------------------------------------------------------------------
#
# The serving engine (serve/engine.py) runs on a 2-axis slice of the
# framework mesh: 'data' multiplies KV capacity (the paged pool's
# physical page axis shards across it, so each chip stores
# n_pages/data pages and the same per-chip HBM holds data× more
# aggregate pages), 'model' multiplies attention/MLP FLOPs per step
# (Megatron TP — the same column/row specs training uses, but
# replicated over 'data': FSDP's gather-per-use trades latency for
# memory in exactly the wrong direction for single-token decode).
#
# Page-pool PartitionSpec, designed first (ROADMAP item 1):
#
# =========================  ===========================  ==============
# array                      shape                        spec
# =========================  ===========================  ==============
# page pool (packed)         (L, n_pages, page, C)        (None, 'data',
#                                                          None, 'model')
# page pool (heads)          (L, n_pages, H, page, D)     (None, 'data',
#                                                          'model', None,
#                                                          None)
# step vectors / tables /    (n_slots,) (n_slots, mp)     replicated
# token block                (k, n_slots)
# params                     Megatron TP over 'model'     (see table up
#                            (decode layout: no FSDP)      top)
# =========================  ===========================  ==============
#
# Rationale: the model dim (C, or H for the heads layout) shards over
# 'model' so each chip's page shard stores only its TP heads' K/V —
# the gathered logical view then lines up with the TP-sharded QKV
# activations without resharding. The PAGE axis (not the slot axis)
# shards over 'data': pages are the physical storage (slots are host
# bookkeeping + fixed-shape tables), so page-axis sharding is what
# actually divides HBM bytes per chip. The tiny per-step vectors and
# the (k, n_slots) sampled-token block replicate — the engine fetches
# ONE replicated block per window (`np.asarray` reads a local shard,
# never a cross-device gather), preserving the async engine's
# one-host-snapshot-per-window contract. Non-divisible dims drop their
# axis to None exactly like `_leaf_spec` (documented, not silent: the
# pool's stats() reports the effective mesh shape).


def parse_mesh_shape(text: str) -> Tuple[int, int]:
    """'2x2' / '2,2' / '4x1' -> (data, model). The serving CLI/bench
    flag format; '1x1' is the unsharded identity."""
    s = text.lower().replace(",", "x").split("x")
    if len(s) != 2:
        raise ValueError(f"--mesh-shape must be DxM (e.g. 2x2), got "
                         f"{text!r}")
    d, m = int(s[0]), int(s[1])
    if d < 1 or m < 1:
        raise ValueError(f"--mesh-shape axes must be >= 1, got {text!r}")
    return d, m


def resolve_mesh_shape(text: str, n_devices: int) -> Tuple[int, int]:
    """``parse_mesh_shape`` + the device-count check — ONE definition
    (message included) for the CLI (`engine_config_from_args`) and
    bench: a mesh the process cannot satisfy is an error. Running
    unsharded under a flag that asked for a mesh would report one
    chip's behaviour under four chips' name."""
    d, m = parse_mesh_shape(text)
    if d * m > max(n_devices, 1):
        raise ValueError(
            f"serve mesh {text} wants {d * m} devices, this process "
            f"has {n_devices}")
    return d, m


def make_serve_mesh(data: int, model: int,
                    devices: Optional[Sequence] = None) -> Mesh:
    """The serving engine's (data, model) mesh — a MeshConfig slice of
    the framework mesh (seq=pipe=1), so every PartitionSpec axis name
    used anywhere in the framework stays valid on it."""
    return make_mesh(MeshConfig(data=data, model=model), devices=devices)


def page_pool_pspec(cfg: ModelConfig, n_pages: int, data: int,
                    model: int) -> P:
    """The paged KV pool's PartitionSpec (table above), with
    non-divisible axes dropped to replication the same way `_leaf_spec`
    drops TP dims — a 7-page pool on data=2 replicates pages rather
    than pad-sharding them."""
    d_ax = "data" if data > 1 and n_pages % data == 0 else None
    if cfg.decode_cache_layout == "packed":
        axes = (None, d_ax, None,
                "model" if model > 1 and cfg.n_embd % model == 0 else None)
    else:
        axes = (None, d_ax,
                "model" if model > 1 and cfg.n_head % model == 0 else None,
                None, None)
    # trailing Nones trimmed: jit NORMALIZES output specs this way, and
    # the engine's jit caches key on input shardings — an untrimmed
    # spec here would make "cache fresh from device_put" and "cache
    # from the previous program's output" two different programs (a
    # recompile per step, caught by CompileGuard)
    while axes and axes[-1] is None:
        axes = axes[:-1]
    return P(*axes)


def page_scale_pspec(n_pages: int, data: int) -> P:
    """PartitionSpec of a quantized pool's ``ks``/``vs`` scale arrays
    ((L, n_pages, page[, H]) — quant/kv.py): the page axis shards over
    'data' EXACTLY like the pool itself (``page_pool_pspec``'s d_ax
    rule, divisibility drop included), so each chip stores the scale
    rows of precisely the pages it stores; the remaining axes
    replicate (scale metadata is ~1/C of the pool's bytes — sharding
    its model dim buys nothing). Trailing Nones trimmed for the same
    jit-cache-representation reason as the pool spec."""
    d_ax = "data" if data > 1 and n_pages % data == 0 else None
    axes = (None, d_ax)
    while axes and axes[-1] is None:
        axes = axes[:-1]
    return P(*axes)


@dataclass(frozen=True)
class ServeShardings:
    """The sharding bundle threaded through every device program the
    engine owns (a STATIC jit argument: hashable, one value per
    engine). ``cache`` pins the page pool's layout inside every traced
    program — donation aliases input to output only when their
    shardings match, so the pool spec must survive each scan body
    unchanged; ``rep`` pins the per-slot step state and the sampled
    token block to full replication (the host fetch stays local).

    Replication is spelled ``P()`` everywhere, whatever the rank: the
    jit cache key is representational (``P() != P(None, None)`` even
    though both mean replicated), and jit hands a replicated OUTPUT the
    spelling of the first replicated INPUT it finds. The params come
    first and their specs are normalized (``_leaf_spec`` trims trailing
    Nones), so every replicated output — the (B, 2) rng streams
    included — comes back as ``P()``; engine state that is committed
    any other way compiles its program twice (caught by CompileGuard,
    pinned in tests/test_serve_mesh.py)."""

    cache: NamedSharding
    rep: NamedSharding
    #: quantized-pool scale arrays (``ks``/``vs`` — page axis over
    #: 'data' via page_scale_pspec); present on every plan so the
    #: static bundle's hash does not depend on whether quantization is
    #: on (the pool dict's KEYS already key the programs)
    scale: NamedSharding = None


def serve_shardings(mesh: Mesh, cfg: ModelConfig, n_pages: int,
                    data: int, model: int) -> ServeShardings:
    return ServeShardings(
        cache=NamedSharding(mesh, page_pool_pspec(cfg, n_pages, data,
                                                  model)),
        rep=NamedSharding(mesh, P()),
        scale=NamedSharding(mesh, page_scale_pspec(n_pages, data)))


def serve_param_shardings(cfg: ModelConfig, mesh: Mesh, model: int,
                          params: Any = None) -> Any:
    """Decode-time parameter layout: Megatron TP over 'model',
    replicated over 'data' (the `shard_for_decode` rationale — no FSDP,
    no pipe at decode). ``params`` computes the specs from an ACTUAL
    tree instead of the init_params abstract structure — the
    weight-quantized tree (quant/weights.py) carries extra
    ``<name>_scale`` leaves (replicated: no TP name match) and int8
    kernels that keep their column/row TP dims by name."""
    if params is not None:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            state_pspecs(params, MeshConfig(model=model)))
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_pspecs(cfg, MeshConfig(model=model)))
