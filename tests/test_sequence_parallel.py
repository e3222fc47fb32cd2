"""Sequence parallelism: ring attention (ppermute KV rotation) and Ulysses
(head<->sequence all-to-all) against the dense causal core, on the 8-device
virtual CPU mesh (conftest). Covers the capability the reference hard-caps
at a single device's block_size (GPT1.py:106, GPT-2.py:109)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.config import MeshConfig, ModelConfig, TrainConfig
from replicatinggpt_tpu.ops.attention import full_causal_attention
from jax import shard_map
from replicatinggpt_tpu.parallel import (make_ring_attention_fn,
                                         make_ulysses_attention_fn,
                                         select_attention_fn)
from replicatinggpt_tpu.parallel.mesh import (make_batch_sharding, make_mesh,
                                              shard_train_state)
from replicatinggpt_tpu.parallel.ring_attention import ring_attention
from replicatinggpt_tpu.parallel.ulysses import ulysses_attention


def _qkv(B=2, H=4, T=64, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) for k in ks)


def _mesh(data=1, seq=8, model=1):
    cfg = MeshConfig(data=data, seq=seq, model=model)
    return make_mesh(cfg), cfg


@pytest.mark.parametrize("axes", [(1, 8, 1), (2, 2, 2)])
@pytest.mark.slow
def test_ring_matches_dense(axes):
    data, seq, model = axes
    mesh, _ = _mesh(data, seq, model)
    q, k, v = _qkv()
    want = full_causal_attention(q, k, v)
    got = ring_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("axes", [(1, 4, 1), (2, 2, 1)])
def test_ulysses_matches_dense(axes):
    data, seq, model = axes
    mesh, _ = _mesh(data, seq, model)
    q, k, v = _qkv()  # H=4 divisible by seq
    want = full_causal_attention(q, k, v)
    got = ulysses_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_ring_gradients_match_dense():
    mesh, _ = _mesh(1, 8, 1)
    q, k, v = _qkv(T=32)

    def dense_loss(q, k, v):
        return jnp.sum(full_causal_attention(q, k, v) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=mesh) ** 2)

    gw = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_ulysses_gradients_match_dense():
    mesh, _ = _mesh(1, 4, 1)
    q, k, v = _qkv(T=32)

    def dense_loss(q, k, v):
        return jnp.sum(full_causal_attention(q, k, v) ** 2)

    def uly_loss(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, mesh=mesh) ** 2)

    gw = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(uly_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_ring_under_jit_with_sharded_inputs():
    mesh, _ = _mesh(2, 2, 2)
    q, k, v = _qkv()
    from jax.sharding import NamedSharding, PartitionSpec as P
    s = NamedSharding(mesh, P("data", "model", "seq", None))
    qs, ks, vs = (jax.device_put(t, s) for t in (q, k, v))
    fn = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh=mesh))
    got = fn(qs, ks, vs)
    want = full_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# in-core attention-weight dropout (the GPT1.py:117 capability, previously a
# documented deviation on the seq-parallel paths)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("core,axes", [("ring", (1, 4, 1)),
                                       ("ring", (2, 2, 2)),
                                       ("ulysses", (1, 4, 1))])
@pytest.mark.slow
def test_seq_parallel_dropout_statistics(core, axes):
    """q=k=0 makes weights uniform over the causal prefix; with v=1 each
    output entry is (#kept / #allowed) / (1 - rate_q), so the global mean
    estimates 1 (unbiasedness) and recovers the empirical keep rate."""
    fn = ring_attention if core == "ring" else ulysses_attention
    mesh, _ = _mesh(*axes)
    B, H, T, D = 2, 4, 128, 8
    rate, rate_q = 0.5, 128 / 256
    q = jnp.zeros((B, H, T, D), jnp.float32)
    v = jnp.ones((B, H, T, D), jnp.float32)
    out = fn(q, q, v, mesh=mesh, dropout_rate=rate,
             rng=jax.random.PRNGKey(42), train=True)
    rows = np.asarray(out)[..., 0]                     # (B, H, T)
    n_allowed = np.arange(1, T + 1, dtype=np.float64)
    keeps = rows * n_allowed * (1.0 - rate_q)
    keep_frac = keeps.sum() / (B * H * n_allowed.sum())
    assert abs(keep_frac - (1.0 - rate_q)) < 0.02, keep_frac
    assert abs(rows.mean() - 1.0) < 0.03, rows.mean()
    # deterministic in rng; decorrelated across batch/head shards
    out2 = fn(q, q, v, mesh=mesh, dropout_rate=rate,
              rng=jax.random.PRNGKey(42), train=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    r = np.asarray(out)[..., 0]
    assert not np.array_equal(r[0], r[1]), "mask repeats across batch"
    assert not np.array_equal(r[:, 0], r[:, 1]), "mask repeats across heads"


@pytest.mark.parametrize("core", ["ring", "ulysses"])
@pytest.mark.slow
def test_seq_parallel_dropout_off_paths_unchanged(core):
    """rate=0 / train=False / rng=None must all reduce to the exact
    dropout-free computation."""
    fn = ring_attention if core == "ring" else ulysses_attention
    mesh, _ = _mesh(1, 4, 1)
    q, k, v = _qkv()
    want = np.asarray(fn(q, k, v, mesh=mesh))
    for kw in [dict(dropout_rate=0.0, rng=jax.random.PRNGKey(0), train=True),
               dict(dropout_rate=0.3, rng=jax.random.PRNGKey(0), train=False),
               dict(dropout_rate=0.3, rng=None, train=True)]:
        np.testing.assert_array_equal(
            np.asarray(fn(q, k, v, mesh=mesh, **kw)), want)


@pytest.mark.parametrize("core", ["ring", "ulysses"])
@pytest.mark.slow
def test_seq_parallel_dropout_grads_match_finite_difference(core):
    """Both cores' dropout masks regenerate deterministically from
    (rng, shard indices, and for the ring: hop, chunk) in the VJP
    recomputation, so autodiff of the fixed-seed dropout attention must
    match finite differences."""
    fn = ring_attention if core == "ring" else ulysses_attention
    mesh, _ = _mesh(1, 4, 1)
    # H=4: divisible by the seq axis, as Ulysses requires
    q, k, v = _qkv(B=1, H=4, T=32, D=8, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    rng = jax.random.PRNGKey(11)

    def loss(q, k, v):
        out = fn(q, k, v, mesh=mesh, dropout_rate=0.25, rng=rng,
                 train=True)
        return jnp.sum(out * w)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    eps = 1e-2
    for arg, (g, rd) in enumerate(zip(
            grads, jax.random.split(jax.random.PRNGKey(13), 3))):
        d = jax.random.normal(rd, g.shape)
        d = d / jnp.linalg.norm(d)
        args = [q, k, v]
        ap = list(args); ap[arg] = args[arg] + eps * d
        am = list(args); am[arg] = args[arg] - eps * d
        fd = (loss(*ap) - loss(*am)) / (2 * eps)
        np.testing.assert_allclose(float(jnp.sum(g * d)), float(fd),
                                   rtol=2e-2, atol=2e-3)


@pytest.mark.slow
def test_ring_q_chunking_matches_unchunked():
    """Chunking only re-blocks the q rows; every row's reductions run in
    the same order, so chunked and unchunked results are identical."""
    import functools

    from jax.sharding import PartitionSpec as P

    from replicatinggpt_tpu.parallel.ring_attention import _ring_local

    mesh, _ = _mesh(1, 4, 1)
    q, k, v = _qkv(T=64)
    want = np.asarray(ring_attention(q, k, v, mesh=mesh))
    # q_chunk=4 divides T_local=16; q_chunk=5 does not and must fall
    # back to the largest divisor (4), keeping the memory bound rather
    # than silently processing the whole shard in one tile
    for q_chunk in (4, 5):
        fn = shard_map(
            functools.partial(_ring_local, axis_name="seq", scale=None,
                              q_chunk=q_chunk),
            mesh=mesh, in_specs=(P("data", "model", "seq", None),) * 3,
            out_specs=P("data", "model", "seq", None), check_vma=False)
        got = np.asarray(fn(q, k, v))
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
    # and with dropout: chunked mask streams are keyed per chunk, so only
    # statistics (not bits) are comparable — check determinism instead
    a = shard_map(
        functools.partial(_ring_local, axis_name="seq", scale=None,
                          q_chunk=4, dropout_rate=0.3,
                          rng=jax.random.PRNGKey(5), train=True),
        mesh=mesh, in_specs=(P("data", "model", "seq", None),) * 3,
        out_specs=P("data", "model", "seq", None), check_vma=False)(q, k, v)
    b = shard_map(
        functools.partial(_ring_local, axis_name="seq", scale=None,
                          q_chunk=4, dropout_rate=0.3,
                          rng=jax.random.PRNGKey(5), train=True),
        mesh=mesh, in_specs=(P("data", "model", "seq", None),) * 3,
        out_specs=P("data", "model", "seq", None), check_vma=False)(q, k, v)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Pallas chunk-kernel ring hops (hop_impl='flash'; interpret mode on CPU)
# ---------------------------------------------------------------------------


def _ring_fn(mesh, **kw):
    import functools

    from jax.sharding import PartitionSpec as P

    from replicatinggpt_tpu.parallel.ring_attention import _ring_local

    spec = P("data", "model", "seq", None)
    return shard_map(
        functools.partial(_ring_local, axis_name="seq", scale=None, **kw),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)


@pytest.mark.slow
def test_ring_flash_hops_match_einsum_hops():
    """hop_impl='flash' routes hops through the Pallas chunk kernel with
    lse-merged accumulation; output and grads must match the einsum ring
    (and therefore the dense core)."""
    mesh, _ = _mesh(1, 4, 1)
    q, k, v = _qkv(T=512, D=32)  # T_local=128, kernel-eligible
    want = np.asarray(_ring_fn(mesh)(q, k, v))
    got = np.asarray(_ring_fn(mesh, hop_impl="flash")(q, k, v))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    ge = jax.grad(lambda q, k, v: loss(_ring_fn(mesh), q, k, v),
                  argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda q, k, v: loss(_ring_fn(mesh, hop_impl="flash"), q, k, v),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_ring_flash_hop_dropout_statistics():
    """In-kernel dropout on the flash hops: uniform-weights construction
    recovers the quantized keep rate; deterministic in rng."""
    mesh, _ = _mesh(1, 2, 1)
    B, H, T, D = 1, 2, 256, 32
    rate, rate_q = 0.5, 128 / 256
    q = jnp.zeros((B, H, T, D), jnp.float32)
    v = jnp.ones((B, H, T, D), jnp.float32)
    fn = _ring_fn(mesh, hop_impl="flash", dropout_rate=rate,
                  rng=jax.random.PRNGKey(42), train=True)
    out = fn(q, q, v)
    rows = np.asarray(out)[..., 0]
    n_allowed = np.arange(1, T + 1, dtype=np.float64)
    keeps = rows * n_allowed * (1.0 - rate_q)
    keep_frac = keeps.sum() / (B * H * n_allowed.sum())
    assert abs(keep_frac - (1.0 - rate_q)) < 0.03, keep_frac
    assert abs(rows.mean() - 1.0) < 0.04, rows.mean()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(fn(q, q, v)))


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.slow
def test_train_step_with_sequence_parallelism(impl):
    """Full sharded train step, seq axis 2: loss finite and close to the
    unsharded single-device step on identical init + batch."""
    from replicatinggpt_tpu.train.state import create_train_state
    from replicatinggpt_tpu.train.steps import make_train_step

    mcfg = ModelConfig(vocab_size=64, block_size=32, n_layer=2, n_head=4,
                       n_embd=64, dropout=0.0, attn_dropout=0.0,
                       dtype="float32", attention_impl=impl)
    tcfg = TrainConfig(batch_size=4, lr=1e-3)
    mesh_cfg = MeshConfig(data=2, seq=2, model=2)
    mesh = make_mesh(mesh_cfg)

    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, (4, 32), dtype=np.int32)
    batch_np = (x, np.roll(x, -1, axis=1).astype(np.int32))

    # reference: unsharded train step
    state0 = create_train_state(jax.random.PRNGKey(0), mcfg, tcfg)
    step0 = make_train_step(mcfg, tcfg, donate=False)
    _, m0 = step0(state0, (jnp.asarray(batch_np[0]), jnp.asarray(batch_np[1])))

    # sharded with seq-parallel attention
    attention_fn = select_attention_fn(mcfg, mesh_cfg, mesh)
    assert attention_fn is not None
    state = shard_train_state(
        lambda: create_train_state(jax.random.PRNGKey(0), mcfg, tcfg),
        mesh, mesh_cfg)
    bs = make_batch_sharding(mesh)
    batch = (jax.device_put(batch_np[0], bs), jax.device_put(batch_np[1], bs))
    step = make_train_step(mcfg, tcfg, donate=False, attention_fn=attention_fn)
    new_state, metrics = step(state, batch)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss)
    np.testing.assert_allclose(loss, float(m0["loss"]), atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_chunk_fused_bwd_matches_split_kernels():
    """The kv-major fused chunk backward (default within the dq-scratch
    bound) must match the split dq + dkv chunk kernels — multi-kv-tile
    shapes, runtime offsets (incl. a partially-masked hop), dropout, and
    a loss that feeds both o and lse cotangents."""
    from replicatinggpt_tpu.ops import flash_pallas as fp

    B, H, Tq, Tk, D = 1, 2, 256, 256, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, Tq, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, Tk, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, Tk, D), jnp.float32)

    def grads(q_off, rate, scratch_bytes):
        old = fp.FUSED_DQ_SCRATCH_BYTES
        fp.FUSED_DQ_SCRATCH_BYTES = scratch_bytes
        try:
            def loss(q, k, v):
                kw = dict(q_offset=jnp.int32(q_off),
                          k_offset=jnp.int32(0),
                          block_q=128, block_k=128)
                if rate > 0:
                    kw.update(dropout_rate=rate,
                              dropout_rng=jax.random.PRNGKey(9))
                o, lse = fp.pallas_flash_chunk(q, k, v, **kw)
                safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
                return jnp.sum(o ** 2) + 0.1 * jnp.sum(safe ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        finally:
            fp.FUSED_DQ_SCRATCH_BYTES = old

    # fully visible hop; partially masked hop; diagonal self-hop (q_off=0
    # drives the causal q-tile skip jb0 >= 1 for the later kv blocks)
    for q_off in (Tk, 128, 0):
        for rate in (0.0, 0.2):
            fused = grads(q_off, rate, fp.FUSED_DQ_SCRATCH_BYTES)
            split = grads(q_off, rate, 0)
            for a, b in zip(fused, split):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_chunk_streamed_kernels_match_resident():
    """The streamed chunk kernels (kv/q grid axis + scratch state; engaged
    past STREAM_KV_BYTES) must match the resident chunk kernels — (o, lse)
    outputs and all three grads, across runtime offsets (fully visible,
    partially masked, diagonal, fully masked hops), dropout, and a loss
    feeding both cotangents."""
    from replicatinggpt_tpu.ops import flash_pallas as fp

    B, H, Tq, Tk, D = 1, 2, 256, 256, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, Tq, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, Tk, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, Tk, D), jnp.float32)

    def run(q_off, rate, stream_bytes):
        old = fp.STREAM_KV_BYTES
        fp.STREAM_KV_BYTES = stream_bytes
        try:
            kw = dict(q_offset=jnp.int32(q_off), k_offset=jnp.int32(0),
                      block_q=128, block_k=128)
            if rate > 0:
                kw.update(dropout_rate=rate,
                          dropout_rng=jax.random.PRNGKey(9))
            o, lse = fp.pallas_flash_chunk(q, k, v, **kw)

            def loss(q, k, v):
                o, lse = fp.pallas_flash_chunk(q, k, v, **kw)
                safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
                return jnp.sum(o ** 2) + 0.1 * jnp.sum(safe ** 2)

            g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return (o, lse) + tuple(g)
        finally:
            fp.STREAM_KV_BYTES = old

    big = 4 * 1024 * 1024
    # q_off = -Tk: every (q, k) pair masked (k > q globally) -> lse -inf,
    # o = 0; the clipped finalize-at-kb==0 path must produce the same
    # (zero) grads as the resident kernels, so grads run for it too
    for q_off in (Tk, 128, 0, -Tk):
        for rate in (0.0, 0.2):
            res = run(q_off, rate, big)
            stm = run(q_off, rate, 0)
            for a, b in zip(stm, res):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-4)
            if q_off == -Tk:  # fully masked: grads must actually be zero
                for gz in stm[2:]:
                    np.testing.assert_array_equal(np.asarray(gz),
                                                  np.zeros_like(gz))


@pytest.mark.slow
def test_ring_streamed_hops_match_einsum_hops(monkeypatch):
    """With STREAM_KV_BYTES forced to 0 every flash hop routes through the
    streamed chunk kernels; the ring must still match the einsum-hop ring
    (and the envelope keeps flash hops past the old resident bound)."""
    from replicatinggpt_tpu.ops import flash_pallas as fp

    monkeypatch.setattr(fp, "STREAM_KV_BYTES", 0)
    mesh, _ = _mesh(1, 4, 1)
    q, k, v = _qkv(T=512, D=32)  # T_local=128
    want = np.asarray(_ring_fn(mesh)(q, k, v))
    got = np.asarray(_ring_fn(mesh, hop_impl="flash")(q, k, v))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    ge = jax.grad(lambda q, k, v: loss(_ring_fn(mesh), q, k, v),
                  argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda q, k, v: loss(_ring_fn(mesh, hop_impl="flash"), q, k, v),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_hop_envelope_has_no_residency_bound(monkeypatch):
    """Round-3 verdict item 4: _flash_hop_supported must not reject long
    per-device shards anymore (the streamed chunk kernels cover them)."""
    import replicatinggpt_tpu.ops.flash_attention as fa
    from replicatinggpt_tpu.parallel.ring_attention import \
        _flash_hop_supported

    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    # 64k rows x D=64 bf16 = 16 MiB K+V: far past STREAM_KV_BYTES
    q = jnp.zeros((1, 1, 65536, 64), jnp.bfloat16)
    assert _flash_hop_supported(q)
