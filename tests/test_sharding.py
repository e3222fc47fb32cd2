"""Distributed tests on a virtual 8-device CPU mesh (conftest forces
--xla_force_host_platform_device_count=8).

Covers the SURVEY.md §2.1 strategy table: DP, FSDP (ZeRO-3 param+opt
sharding), TP (Megatron column/row), and their composition — all via GSPMD
shardings, no hand-written collectives.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from replicatinggpt_tpu.config import MeshConfig, ModelConfig, get_config
from replicatinggpt_tpu.models.gpt import forward, init_params
from replicatinggpt_tpu.parallel.mesh import (make_batch_sharding, make_mesh,
                                              state_pspecs,
                                              shard_train_state)
from replicatinggpt_tpu.train.state import create_train_state
from replicatinggpt_tpu.train.steps import make_train_step

TINY = ModelConfig(vocab_size=64, block_size=32, n_layer=2, n_head=2,
                   n_embd=32, dropout=0.0, attn_dropout=0.0, dtype="float32")


def _state_fn(mcfg, tcfg):
    return lambda: create_train_state(jax.random.PRNGKey(0), mcfg, tcfg)


def _find_adam(state):
    """Locate ScaleByAdamState anywhere in optax's nested chain tuples."""
    if type(state).__name__ == "ScaleByAdamState":
        return state
    if isinstance(state, (tuple, list)):
        for s in state:
            r = _find_adam(s)
            if r is not None:
                return r
    return None


@pytest.fixture(scope="module")
def tcfg():
    return get_config("test-tiny").train


def _batch(mcfg, B=8, seed=0):
    x = jax.random.randint(jax.random.PRNGKey(seed), (B, mcfg.block_size), 0,
                           mcfg.vocab_size)
    return x, x


def test_requires_eight_devices():
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"


def test_mesh_construction():
    mesh = make_mesh(MeshConfig(data=2, seq=2, model=2))
    assert mesh.shape == {"data": 2, "seq": 2, "model": 2, "pipe": 1}
    assert make_batch_sharding(mesh).spec == P("data", "seq")


def test_tp_specs_follow_megatron_pattern(tcfg):
    specs = state_pspecs(jax.eval_shape(_state_fn(TINY, tcfg)),
                         MeshConfig(data=1, seq=1, model=2))
    p = specs.params
    # specs are in jit's normalized representation (trailing Nones
    # trimmed): the state must enter the step exactly as it leaves it
    assert p["blocks"]["qkv_kernel"] == P(None, None, "model")
    assert p["blocks"]["attn_out_kernel"] == P(None, "model")
    assert p["blocks"]["mlp_up_kernel"] == P(None, None, "model")
    assert p["blocks"]["mlp_down_kernel"] == P(None, "model")
    assert p["blocks"]["ln1_scale"] == P()
    assert p["wte"] == P("model")  # 64 % 2 == 0 → vocab-parallel
    # Adam moments mirror param specs through the tree path
    adam = _find_adam(specs.opt_state)
    assert adam.mu["blocks"]["qkv_kernel"] == P(None, None, "model")


def test_tp_indivisible_dims_stay_replicated(tcfg):
    odd = dataclasses.replace(TINY, vocab_size=65)  # 65 % 2 != 0
    specs = state_pspecs(jax.eval_shape(_state_fn(odd, tcfg)),
                         MeshConfig(model=2))
    assert specs.params["wte"] == P()


def test_fsdp_shards_params_and_moments(tcfg):
    specs = state_pspecs(jax.eval_shape(_state_fn(TINY, tcfg)),
                         MeshConfig(data=8, fsdp=True))
    p = specs.params
    # largest dim of (L=2, C=32, 3C=96) divisible by 8 → last dim
    assert "data" in tuple(p["blocks"]["qkv_kernel"])
    assert "data" in tuple(p["wte"])
    adam = _find_adam(specs.opt_state)
    assert "data" in tuple(adam.mu["blocks"]["qkv_kernel"])


def test_dp_training_matches_single_device(tcfg):
    """8-way DP must be numerically equivalent to single-device training
    (same global batch, same init)."""
    tcfg = dataclasses.replace(tcfg, lr=1e-3)
    batch = _batch(TINY, B=8)
    # single device
    state1 = _state_fn(TINY, tcfg)()
    step1 = make_train_step(TINY, tcfg, donate=False)
    losses1 = []
    for _ in range(3):
        state1, m = step1(state1, batch)
        losses1.append(float(m["loss"]))
    # 8-way DP
    mesh = make_mesh(MeshConfig(data=8))
    state8 = shard_train_state(_state_fn(TINY, tcfg), mesh,
                               MeshConfig(data=8))
    bs = make_batch_sharding(mesh)
    batch8 = tuple(jax.device_put(np.asarray(b), bs) for b in batch)
    step8 = make_train_step(TINY, tcfg, donate=False)
    losses8 = []
    for _ in range(3):
        state8, m = step8(state8, batch8)
        losses8.append(float(m["loss"]))
    np.testing.assert_allclose(losses1, losses8, rtol=2e-4)


def test_tp_forward_matches_unsharded(tcfg):
    mesh = make_mesh(MeshConfig(data=2, seq=1, model=2))
    mesh_cfg = MeshConfig(data=2, seq=1, model=2)
    params = init_params(jax.random.PRNGKey(0), TINY)
    specs = state_pspecs(params, mesh_cfg)
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)
    x, _ = _batch(TINY, B=4)
    ref, _ = forward(params, x, TINY)
    xb = jax.device_put(np.asarray(x), NamedSharding(mesh, P("data", None)))
    got, _ = jax.jit(lambda p, i: forward(p, i, TINY))(sharded, xb)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-4)


@pytest.mark.slow
def test_fsdp_training_matches_single_device(tcfg):
    tcfg = dataclasses.replace(tcfg, lr=1e-3)
    batch = _batch(TINY, B=8)
    state1 = _state_fn(TINY, tcfg)()
    step = make_train_step(TINY, tcfg, donate=False)
    state1, m1 = step(state1, batch)
    mesh = make_mesh(MeshConfig(data=8, fsdp=True))
    mesh_cfg = MeshConfig(data=8, fsdp=True)
    state8 = shard_train_state(_state_fn(TINY, tcfg), mesh, mesh_cfg)
    bs = make_batch_sharding(mesh)
    batch8 = tuple(jax.device_put(np.asarray(b), bs) for b in batch)
    state8, m8 = step(state8, batch8)
    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                               rtol=2e-4)
    # params stayed sharded after the step (no silent gather-to-replicated)
    qkv = state8.params["blocks"]["qkv_kernel"]
    assert "data" in tuple(qkv.sharding.spec)


@pytest.mark.slow
def test_runner_with_mesh(tcfg):
    """End-to-end runner on a 4-way DP mesh."""
    cfg = get_config("test-tiny")
    cfg = cfg.replace(
        train=dataclasses.replace(cfg.train, max_iters=5, eval_interval=0,
                                  eval_iters=2, log_interval=0,
                                  batch_size=8),
        mesh=MeshConfig(data=4),
        dataset="datasets/shakespeare.txt")
    from replicatinggpt_tpu.train.runner import train
    mesh = make_mesh(cfg.mesh)
    res = train(cfg, mesh=mesh)
    assert np.isfinite(res.final_eval["val"])


@pytest.mark.slow
def test_mesh_scan_dispatch_matches_single_steps(tcfg):
    """K-step scan over a P(None,'data','seq')-sharded superbatch must
    produce the same per-step losses as K single-step dispatches on the
    same mesh (the steps_per_dispatch>1 path for sharded runs)."""
    from replicatinggpt_tpu.parallel.mesh import make_superbatch_sharding
    from replicatinggpt_tpu.train.steps import make_train_scan
    tcfg = dataclasses.replace(tcfg, lr=1e-3)
    mesh_cfg = MeshConfig(data=4, seq=2)
    mesh = make_mesh(mesh_cfg)
    K = 4
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, TINY.vocab_size, (8, TINY.block_size),
                            dtype=np.int32) for _ in range(K)]
    bs = make_batch_sharding(mesh)
    ss = make_superbatch_sharding(mesh)
    s1 = shard_train_state(_state_fn(TINY, tcfg), mesh, mesh_cfg)
    step = make_train_step(TINY, tcfg, donate=False)
    losses1 = []
    for b in batches:
        xb = jax.device_put(b, bs)
        s1, m = step(s1, (xb, xb))
        losses1.append(float(m["loss"]))
    s2 = shard_train_state(_state_fn(TINY, tcfg), mesh, mesh_cfg)
    scan = make_train_scan(TINY, tcfg, K, donate=False)
    stacked = jax.device_put(np.stack(batches), ss)
    assert stacked.sharding.spec == P(None, "data", "seq")
    s2, m = scan(s2, (stacked, stacked))
    np.testing.assert_allclose(losses1, np.asarray(m["loss"]), rtol=2e-4)
    # params stayed in their sharded layout through the scan dispatch
    assert (s2.params["blocks"]["qkv_kernel"].sharding.spec
            == s1.params["blocks"]["qkv_kernel"].sharding.spec)


@pytest.mark.slow
def test_runner_mesh_multi_step_dispatch_matches_single(tcfg):
    """End-to-end: the runner with steps_per_dispatch>1 on a DP mesh walks
    the same eval-loss trajectory as single-step dispatch (identical token
    stream, chunk schedule respecting the eval cadence)."""
    from replicatinggpt_tpu.train.runner import train
    cfg = get_config("test-tiny")
    base = cfg.replace(
        train=dataclasses.replace(cfg.train, max_iters=8, eval_interval=4,
                                  eval_iters=2, log_interval=0, batch_size=8,
                                  steps_per_dispatch=1),
        mesh=MeshConfig(data=4),
        dataset="datasets/shakespeare.txt")
    mesh = make_mesh(base.mesh)
    r1 = train(base, mesh=mesh)
    multi = base.replace(
        train=dataclasses.replace(base.train, steps_per_dispatch=3))
    r2 = train(multi, mesh=mesh)
    h1 = np.asarray([[tr, va] for _, tr, va in r1.history])
    h2 = np.asarray([[tr, va] for _, tr, va in r2.history])
    assert h1.shape == h2.shape
    np.testing.assert_allclose(h1, h2, rtol=2e-4)


@pytest.mark.slow
def test_runner_gates_flash_auto_on_mesh(tcfg):
    """'auto' must not resolve to the Pallas flash kernel inside a sharded
    jit program (no GSPMD partitioning rule) — the runner rewrites it to
    'einsum' on mesh runs without a seq-parallel attention wrapper."""
    import io

    from replicatinggpt_tpu.train.runner import train
    from replicatinggpt_tpu.utils.logging import StepLogger

    cfg = get_config("test-tiny")
    cfg = cfg.replace(
        train=dataclasses.replace(cfg.train, max_iters=2, eval_interval=0,
                                  eval_iters=1, log_interval=0,
                                  batch_size=8),
        mesh=MeshConfig(data=4),
        dataset="datasets/shakespeare.txt")
    assert cfg.model.attention_impl == "auto"
    stream = io.StringIO()
    mesh = make_mesh(cfg.mesh)
    train(cfg, mesh=mesh, logger=StepLogger(stream=stream))
    assert "'auto' -> 'einsum'" in stream.getvalue()


@pytest.mark.slow
def test_grad_accum_on_mesh_matches_unsharded(tcfg):
    """Gradient accumulation on a (data, seq) mesh — (A, b, T) microbatch
    stack sharded P(None,'data','seq') — must match the unsharded step
    bit-for-bit in loss and stay in the sharded layout."""
    from replicatinggpt_tpu.parallel.mesh import make_superbatch_sharding
    t = dataclasses.replace(tcfg, lr=1e-3, batch_size=8, grad_accum_steps=2)
    A = 2
    rng = np.random.default_rng(3)
    x = rng.integers(0, TINY.vocab_size, (A, 8, TINY.block_size),
                     dtype=np.int32)
    step = make_train_step(TINY, t, donate=False)

    s_un = create_train_state(jax.random.PRNGKey(0), TINY, t)
    s_un, m_un = step(s_un, (x, x))

    mesh_cfg = MeshConfig(data=4, seq=2, fsdp=True)
    mesh = make_mesh(mesh_cfg)
    ss = make_superbatch_sharding(mesh)
    xb = jax.device_put(x, ss)
    assert xb.sharding.spec == P(None, "data", "seq")
    s_sh = shard_train_state(_state_fn(TINY, t), mesh, mesh_cfg)
    s_sh, m_sh = step(s_sh, (xb, xb))

    np.testing.assert_allclose(float(m_un["loss"]), float(m_sh["loss"]),
                               rtol=2e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(jax.device_get(b)), rtol=1e-4,
            atol=1e-5),
        s_un.params, s_sh.params)


# ---------------------------------------------------------------------------
# batch/head shard_map flash wrapper (parallel/sharded_flash.py) — the
# DP/FSDP/TP mesh path that keeps the Pallas kernel instead of degrading
# to dense einsum (VERDICT r2 item 1)
# ---------------------------------------------------------------------------

def _wrapper_qkv(B=8, H=4, T=256, D=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) for k in ks)


@pytest.mark.slow
def test_sharded_flash_wrapper_matches_einsum_interpret(monkeypatch):
    """The shard_map wrapper running the *actual Pallas kernel* (interpret
    mode on CPU) over a (data=4, model=2) mesh must match the unsharded
    einsum core in outputs AND grads."""
    from replicatinggpt_tpu.ops import flash_attention as fa
    from replicatinggpt_tpu.ops.attention import full_causal_attention
    from replicatinggpt_tpu.parallel.sharded_flash import \
        sharded_flash_attention

    monkeypatch.setattr(fa, "_pallas_supported", lambda q: True)
    mesh = make_mesh(MeshConfig(data=4, seq=1, model=2))
    q, k, v = _wrapper_qkv()

    def loss_wrapped(q, k, v):
        out = sharded_flash_attention(q, k, v, mesh=mesh, impl="flash")
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_causal_attention(q, k, v, impl="einsum") ** 2)

    ref_out = full_causal_attention(q, k, v, impl="einsum")
    got_out = sharded_flash_attention(q, k, v, mesh=mesh, impl="flash")
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(ref_out),
                               atol=2e-5, rtol=2e-5)
    gw = jax.grad(loss_wrapped, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gw, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5)


def test_sharded_flash_wrapper_dropout_streams_decorrelate(monkeypatch):
    """With attention dropout on, each (data, model) shard must draw an
    independent mask stream (fold_in of the device indices): a replicated
    batch row on different 'data' shards gets different masks."""
    from replicatinggpt_tpu.ops import flash_attention as fa
    from replicatinggpt_tpu.parallel.sharded_flash import \
        sharded_flash_attention

    monkeypatch.setattr(fa, "_pallas_supported", lambda q: False)
    mesh = make_mesh(MeshConfig(data=4, seq=1, model=2))
    q, k, v = _wrapper_qkv(B=4, H=2, T=64, D=16)
    # identical rows across the batch: without per-shard folding, the
    # dropout pattern would repeat across 'data' shards
    q = jnp.broadcast_to(q[:1], q.shape)
    k = jnp.broadcast_to(k[:1], k.shape)
    v = jnp.broadcast_to(v[:1], v.shape)
    out = sharded_flash_attention(q, k, v, mesh=mesh, impl="einsum",
                                  dropout_rate=0.5,
                                  rng=jax.random.PRNGKey(7), train=True)
    out = np.asarray(out)
    assert not np.allclose(out[0], out[1]), \
        "data shards 0 and 1 drew identical dropout masks"


@pytest.mark.slow
def test_dp_training_with_flash_wrapper_matches_single_device(tcfg):
    """DP training through the shard_map wrapper (explicit 'flash'; the
    local core resolves to SDPA on CPU) must match single-device training
    on the same global batch."""
    mcfg = dataclasses.replace(TINY, attention_impl="flash")
    t = dataclasses.replace(tcfg, lr=1e-3)
    batch = _batch(mcfg, B=8)
    state1 = _state_fn(mcfg, t)()
    step1 = make_train_step(mcfg, t, donate=False)
    losses1 = []
    for _ in range(3):
        state1, m = step1(state1, batch)
        losses1.append(float(m["loss"]))

    from replicatinggpt_tpu.parallel import select_attention_fn
    mesh_cfg = MeshConfig(data=8)
    mesh = make_mesh(mesh_cfg)
    attn_fn = select_attention_fn(mcfg, mesh_cfg, mesh)
    assert attn_fn is not None, "explicit 'flash' must select the wrapper"
    state8 = shard_train_state(_state_fn(mcfg, t), mesh, mesh_cfg)
    bs = make_batch_sharding(mesh)
    batch8 = tuple(jax.device_put(np.asarray(b), bs) for b in batch)
    step8 = make_train_step(mcfg, t, donate=False, attention_fn=attn_fn)
    losses8 = []
    for _ in range(3):
        state8, m = step8(state8, batch8)
        losses8.append(float(m["loss"]))
    np.testing.assert_allclose(losses1, losses8, rtol=2e-4)


def test_select_attention_fn_policy_no_seq_axis():
    """Wrapper selection policy on meshes without a seq axis: explicit
    'flash' always wraps (the wrapper self-guards indivisible dims);
    'auto' wraps only on TPU (einsum under GSPMD is the CPU answer);
    explicit 'einsum' never wraps."""
    from replicatinggpt_tpu.parallel import select_attention_fn
    mesh_cfg = MeshConfig(data=4, seq=1, model=2)
    mesh = make_mesh(mesh_cfg)
    flash = dataclasses.replace(TINY, attention_impl="flash")
    assert select_attention_fn(flash, mesh_cfg, mesh) is not None
    # 'auto' on this CPU backend: no wrapper (einsum under GSPMD)
    auto = dataclasses.replace(TINY, attention_impl="auto")
    assert select_attention_fn(auto, mesh_cfg, mesh) is None
    einsum = dataclasses.replace(TINY, attention_impl="einsum")
    assert select_attention_fn(einsum, mesh_cfg, mesh) is None
    # explicit 'flash' with n_head=3 indivisible by model=2 still wraps
    # (the wrapper drops the head axis from its specs, never dense einsum)
    bad = dataclasses.replace(TINY, n_head=3, n_embd=33,
                              attention_impl="flash")
    assert select_attention_fn(bad, mesh_cfg, mesh) is not None
    # explicit 'flash' on a seq-sharded mesh routes to a flash-capable
    # seq-parallel core (never dense einsum — the O(T^2) memory the user
    # opted out of)
    seq_cfg = MeshConfig(data=2, seq=2, model=2)
    assert select_attention_fn(flash, seq_cfg, make_mesh(seq_cfg)) \
        is not None


def test_sharded_flash_wrapper_self_guards_indivisible_dims():
    """shard_map requires even division; the wrapper must drop an
    indivisible axis from its specs (gather instead of crash) and fall
    back to plain einsum when nothing divides — matching the GSPMD
    envelope it replaced."""
    from replicatinggpt_tpu.ops.attention import full_causal_attention
    from replicatinggpt_tpu.parallel.sharded_flash import \
        sharded_flash_attention

    mesh = make_mesh(MeshConfig(data=4, seq=1, model=2))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    # B=6 does not divide data=4 -> heads-only sharding
    q, k, v = (jax.random.normal(kk, (6, 4, 64, 16), jnp.float32)
               for kk in ks)
    ref = full_causal_attention(q, k, v, impl="einsum")
    got = sharded_flash_attention(q, k, v, mesh=mesh, impl="einsum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # B=6, H=3: neither axis divides -> plain einsum fallback
    q3, k3, v3 = (t[:, :3] for t in (q, k, v))
    ref3 = full_causal_attention(q3, k3, v3, impl="einsum")
    got3 = sharded_flash_attention(q3, k3, v3, mesh=mesh, impl="einsum")
    np.testing.assert_allclose(np.asarray(got3), np.asarray(ref3),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_mesh_packed_qkv_hook_matches_single_device(monkeypatch):
    """On a DP/FSDP mesh the wrapper's packed_qkv hook must route the
    fused (B,T,3C) projection through the packed-heads kernel (interpret
    mode here) and match single-device training numerics."""
    import replicatinggpt_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_packed_backend_ok", lambda: True)
    mcfg = dataclasses.replace(TINY, block_size=256, n_head=4, n_embd=128,
                               attention_impl="flash")
    tcfg = dataclasses.replace(get_config("test-tiny").train, lr=1e-3)
    batch = _batch(mcfg, B=8)
    # single device: the packed kernel also engages locally off-mesh only
    # on TPU, so the reference here is the plain split-heads path
    state1 = _state_fn(mcfg, tcfg)()
    step1 = make_train_step(mcfg, tcfg, donate=False)
    state1, m1 = step1(state1, batch)

    from replicatinggpt_tpu.parallel import select_attention_fn
    mesh_cfg = MeshConfig(data=8, fsdp=True)
    mesh = make_mesh(mesh_cfg)
    attn_fn = select_attention_fn(mcfg, mesh_cfg, mesh)
    assert attn_fn is not None and hasattr(attn_fn, "packed_qkv")
    # the hook must actually fire (not fall back to the split path)
    import jax.numpy as jnp2
    probe = attn_fn.packed_qkv(
        jnp.zeros((8, 256, 3 * 128), jnp2.float32), 4)
    assert probe is not None, "packed hook declined in-envelope shapes"

    state8 = shard_train_state(_state_fn(mcfg, tcfg), mesh, mesh_cfg)
    bs = make_batch_sharding(mesh)
    batch8 = tuple(jax.device_put(np.asarray(b), bs) for b in batch)
    step8 = make_train_step(mcfg, tcfg, donate=False, attention_fn=attn_fn)
    state8, m8 = step8(state8, batch8)
    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                               rtol=2e-4)


def test_mesh_packed_qkv_hook_absent_with_tp():
    """Meshes that shard heads ('model' > 1) must not carry the packed
    hook — head strips would not be local."""
    from replicatinggpt_tpu.parallel.sharded_flash import \
        make_sharded_flash_attention_fn
    mesh = make_mesh(MeshConfig(data=4, seq=1, model=2))
    fn = make_sharded_flash_attention_fn(mesh)
    assert not hasattr(fn, "packed_qkv")


def test_dp_training_with_chunked_ce_matches_single_device(tcfg):
    """loss_chunk under 8-way DP: the chunked-CE reshape folds the
    dp-sharded batch axis into the scan axis, and GSPMD must still
    produce the single-device numbers (it may pay collectives — the
    hardware A/B prices that; this pins correctness)."""
    tcfg = dataclasses.replace(tcfg, lr=1e-3)
    mcfg = dataclasses.replace(TINY, loss_chunk=32)  # B*T=256 -> 8 chunks
    batch = _batch(mcfg, B=8)
    state1 = _state_fn(mcfg, tcfg)()
    step1 = make_train_step(mcfg, tcfg, donate=False)
    losses1 = []
    for _ in range(3):
        state1, m = step1(state1, batch)
        losses1.append(float(m["loss"]))
    # unchunked single-device oracle: same numbers (order-of-sum only)
    state0 = _state_fn(TINY, tcfg)()
    step0 = make_train_step(TINY, tcfg, donate=False)
    _, m0 = step0(state0, batch)
    np.testing.assert_allclose(losses1[0], float(m0["loss"]), rtol=1e-5)
    mesh = make_mesh(MeshConfig(data=8))
    state8 = shard_train_state(_state_fn(mcfg, tcfg), mesh,
                               MeshConfig(data=8))
    bs = make_batch_sharding(mesh)
    batch8 = tuple(jax.device_put(np.asarray(b), bs) for b in batch)
    step8 = make_train_step(mcfg, tcfg, donate=False)
    losses8 = []
    for _ in range(3):
        state8, m = step8(state8, batch8)
        losses8.append(float(m["loss"]))
    np.testing.assert_allclose(losses1, losses8, rtol=2e-4)
