"""Pipeline parallelism (parallel/pipeline.py) on the virtual 8-device CPU
mesh: the GPipe-style ppermute schedule must reproduce the plain scan-over
-layers forward bit-for-bit (same params, float32)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import MeshConfig, ModelConfig, TrainConfig
from replicatinggpt_tpu.models.gpt import forward, init_params
from replicatinggpt_tpu.parallel import (make_pipeline_blocks_fn,
                                         select_blocks_fn)
from replicatinggpt_tpu.parallel.mesh import (make_batch_sharding, make_mesh,
                                              shard_train_state)


def _mcfg(**kw):
    base = dict(vocab_size=64, block_size=32, n_layer=4, n_head=4,
                n_embd=64, dropout=0.0, attn_dropout=0.0, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("axes,micro", [
    ((1, 1, 1, 4), 4),   # pure PP
    ((2, 1, 1, 4), 2),   # PP x DP
    ((1, 2, 1, 4), 4),   # PP x SP (ring attention inside the region)
    ((1, 1, 2, 4), 4),   # PP x TP (Megatron block inside the region)
    ((2, 1, 2, 2), 2),   # PP x TP x DP
])
@pytest.mark.slow
def test_pipeline_forward_matches_dense(axes, micro):
    data, seq, model, pipe = axes
    mesh_cfg = MeshConfig(data=data, seq=seq, model=model, pipe=pipe,
                          microbatches=micro)
    mesh = make_mesh(mesh_cfg)
    mcfg = _mcfg()
    params = init_params(jax.random.PRNGKey(0), mcfg)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 64, (8, 32), dtype=np.int32))

    want, _ = forward(params, idx, mcfg)
    blocks_fn = make_pipeline_blocks_fn(mesh, mesh_cfg)
    got, _ = forward(params, idx, mcfg, blocks_fn=blocks_fn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_pipeline_train_step_matches_dense():
    from replicatinggpt_tpu.train.state import create_train_state
    from replicatinggpt_tpu.train.steps import make_train_step

    mcfg = _mcfg()
    tcfg = TrainConfig(batch_size=8, lr=1e-3)
    mesh_cfg = MeshConfig(data=2, seq=1, model=1, pipe=4, microbatches=2)
    mesh = make_mesh(mesh_cfg)

    rng = np.random.default_rng(1)
    x = rng.integers(0, 64, (8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)

    state0 = create_train_state(jax.random.PRNGKey(0), mcfg, tcfg)
    step0 = make_train_step(mcfg, tcfg, donate=False)
    _, m0 = step0(state0, (jnp.asarray(x), jnp.asarray(y)))

    blocks_fn = select_blocks_fn(mcfg, mesh_cfg, mesh)
    assert blocks_fn is not None
    state = shard_train_state(
        lambda: create_train_state(jax.random.PRNGKey(0), mcfg, tcfg),
        mesh, mesh_cfg)
    bs = make_batch_sharding(mesh)
    batch = (jax.device_put(x, bs), jax.device_put(y, bs))
    step = make_train_step(mcfg, tcfg, donate=False, blocks_fn=blocks_fn)
    new_state, metrics = step(state, batch)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss)
    np.testing.assert_allclose(loss, float(m0["loss"]), atol=1e-4, rtol=1e-4)


def test_pipeline_params_sharded_by_stage():
    """Block params carry 'pipe' on their stacked layer dim."""
    from replicatinggpt_tpu.parallel.mesh import state_pspecs
    mcfg = _mcfg()
    params = init_params(jax.random.PRNGKey(0), mcfg)
    mesh_cfg = MeshConfig(pipe=4)
    specs = state_pspecs({"params": params}, mesh_cfg)
    qkv_spec = specs["params"]["blocks"]["qkv_kernel"]
    assert qkv_spec[0] == "pipe", qkv_spec
    assert "pipe" not in tuple(specs["params"]["wte"])


@pytest.mark.slow
def test_pipeline_tp_grads_match_dense():
    """TP-inside-PP backward: psum/identity transposes through the Megatron
    block must give the same parameter gradients as the dense stack."""
    mcfg = _mcfg()
    mesh_cfg = MeshConfig(data=1, seq=1, model=2, pipe=4, microbatches=4)
    mesh = make_mesh(mesh_cfg)
    params = init_params(jax.random.PRNGKey(0), mcfg)
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, 64, (8, 32), dtype=np.int32))
    tgt = jnp.asarray(np.roll(np.asarray(idx), -1, axis=1))

    def loss_dense(p):
        return forward(p, idx, mcfg, targets=tgt)[1]

    blocks_fn = make_pipeline_blocks_fn(mesh, mesh_cfg)

    def loss_pp(p):
        return forward(p, idx, mcfg, targets=tgt, blocks_fn=blocks_fn)[1]

    gd = jax.grad(loss_dense)(params)
    gp = jax.grad(loss_pp)(params)
    for path_leaf, (pl_, leaf) in zip(
            jax.tree_util.tree_flatten_with_path(gd)[0],
            jax.tree_util.tree_flatten_with_path(gp)[0]):
        np.testing.assert_allclose(
            np.asarray(path_leaf[1]), np.asarray(leaf), atol=2e-4, rtol=2e-4,
            err_msg=jax.tree_util.keystr(pl_))


@pytest.mark.slow
def test_pipeline_tp_falls_back_when_heads_indivisible():
    """n_head % tp != 0: kernels replicate through the region (old
    behavior) instead of mis-sharding heads."""
    mcfg = _mcfg(n_head=3, n_embd=48)
    mesh_cfg = MeshConfig(data=1, seq=1, model=2, pipe=4, microbatches=4)
    mesh = make_mesh(mesh_cfg)
    params = init_params(jax.random.PRNGKey(0), mcfg)
    rng = np.random.default_rng(3)
    idx = jnp.asarray(rng.integers(0, 64, (8, 32), dtype=np.int32))
    want, _ = forward(params, idx, mcfg)
    got, _ = forward(params, idx, mcfg,
                     blocks_fn=make_pipeline_blocks_fn(mesh, mesh_cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
