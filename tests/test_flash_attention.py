"""Flash-attention kernel parity vs the einsum reference path (interpret
mode on CPU; the same kernel compiles via Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replicatinggpt_tpu.ops.attention import full_causal_attention
from replicatinggpt_tpu.ops.flash_pallas import pallas_flash_attention


def _qkv(B=2, H=2, T=256, D=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, H, T, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def test_fwd_matches_einsum_causal():
    q, k, v = _qkv()
    ref = full_causal_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_fwd_noncausal():
    q, k, v = _qkv(T=128)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v)
    got = pallas_flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_grads_match_einsum():
    q, k, v = _qkv(B=1, H=2, T=128, D=32)

    def loss_flash(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_causal_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5)


def test_custom_scale():
    q, k, v = _qkv(T=128)
    ref = full_causal_attention(q, k, v, scale=0.5)
    got = pallas_flash_attention(q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_bf16_inputs():
    q, k, v = _qkv(T=128, dtype=jnp.bfloat16)
    ref = full_causal_attention(q, k, v)
    got = pallas_flash_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_uneven_T_rejected():
    # T <= block clamps the block to T, so T=96 is fine...
    q, k, v = _qkv(T=96)
    pallas_flash_attention(q, k, v)
    # ...but T=160 > block=128 and 160 % 128 != 0 must be rejected
    q2, k2, v2 = _qkv(T=160)
    with pytest.raises(AssertionError):
        pallas_flash_attention(q2, k2, v2)


def test_auto_impl_picks_flash_at_long_T(monkeypatch):
    """'auto' routes to the flash core once the dense (T,T) weight
    materialization stops being the right trade (measured crossover), and
    stays dense at short T / when attention-weight dropout must apply."""
    from replicatinggpt_tpu.config import ModelConfig
    from replicatinggpt_tpu.models.gpt import forward, init_params
    from replicatinggpt_tpu.ops import attention as attn_mod

    calls = []
    real = attn_mod.full_causal_attention

    def spy(q, k, v, **kw):
        calls.append(kw.get("impl"))
        return real(q, k, v, **kw)

    import replicatinggpt_tpu.models.gpt as gpt_mod
    monkeypatch.setattr(gpt_mod, "full_causal_attention", spy)

    def route_for(T, attn_dropout=0.0, train=False):
        cfg = ModelConfig(vocab_size=65, block_size=T, n_layer=1, n_head=2,
                          n_embd=64, dropout=0.0, attn_dropout=attn_dropout,
                          attention_impl="auto", dtype="float32")
        params = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        rng = jax.random.PRNGKey(0) if train else None
        calls.clear()
        jax.make_jaxpr(
            lambda p, x: forward(p, x, cfg, rng=rng, train=train)[0]
        )(params, jnp.zeros((1, T), jnp.int32))
        assert calls, "attention core was not invoked"
        return calls[0]

    assert route_for(128) == "einsum"
    assert route_for(256) == "flash"  # measured crossover, v5e auto-tiles
    assert route_for(1024) == "flash"
    # dropout training still routes to flash: the kernel applies
    # attention-weight dropout in-kernel on TPU, and full_causal_attention
    # degrades to einsum elsewhere (one source of truth, no warning)
    assert route_for(1024, attn_dropout=0.2, train=True) == "flash"


# ---------------------------------------------------------------------------
# in-kernel attention-weight dropout (counter-based mask; interpret mode)
# ---------------------------------------------------------------------------

def test_dropout_keep_rate_statistics():
    """q=0 makes attention weights uniform over the causal prefix; with
    v=1 each output entry is (#kept / #allowed) / (1-rate), so the global
    mean estimates 1 and recovers the empirical keep rate."""
    B, H, T, D = 2, 2, 256, 32
    rate = 0.5
    q = jnp.zeros((B, H, T, D), jnp.float32)
    k = jnp.zeros((B, H, T, D), jnp.float32)  # s=0 -> uniform weights
    v = jnp.ones((B, H, T, D), jnp.float32)
    out = pallas_flash_attention(q, k, v, causal=True,
                                 dropout_rate=rate,
                                 dropout_rng=jax.random.PRNGKey(42))
    rows = np.asarray(out)[..., 0]                     # (B, H, T)
    n_allowed = np.arange(1, T + 1, dtype=np.float64)  # causal prefix sizes
    keeps = rows * n_allowed * (1.0 - rate)            # #kept per row
    keep_frac = keeps.sum() / (B * H * n_allowed.sum())
    assert abs(keep_frac - (1.0 - rate)) < 0.01, keep_frac
    # inverted dropout is unbiased: mean output ~ dropout-off output (=1)
    assert abs(rows.mean() - 1.0) < 0.02, rows.mean()


def test_dropout_deterministic_in_rng():
    q, k, v = _qkv(B=1, H=2, T=128, D=32)
    kw = dict(causal=True, dropout_rate=0.3)
    a = pallas_flash_attention(q, k, v, dropout_rng=jax.random.PRNGKey(7),
                               **kw)
    b = pallas_flash_attention(q, k, v, dropout_rng=jax.random.PRNGKey(7),
                               **kw)
    c = pallas_flash_attention(q, k, v, dropout_rng=jax.random.PRNGKey(8),
                               **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(a) - np.asarray(c)).max() > 1e-3


def test_dropout_bwd_matches_finite_difference():
    """The backward kernels regenerate the forward mask exactly: the
    custom VJP of the (deterministic, fixed-seed) dropout kernel must
    match finite differences."""
    B, H, T, D = 1, 1, 128, 32
    q, k, v = _qkv(B=B, H=H, T=T, D=D, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, H, T, D))
    rng = jax.random.PRNGKey(11)

    def loss(q, k, v):
        out = pallas_flash_attention(q, k, v, causal=True, dropout_rate=0.25,
                                     dropout_rng=rng)
        return jnp.sum(out * w)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    rng_dir = jax.random.split(jax.random.PRNGKey(13), 3)
    eps = 1e-2
    for arg, (g, rd) in enumerate(zip(grads, rng_dir)):
        d = jax.random.normal(rd, g.shape)
        d = d / jnp.linalg.norm(d)
        args = [q, k, v]
        ap = list(args); ap[arg] = args[arg] + eps * d
        am = list(args); am[arg] = args[arg] - eps * d
        fd = (loss(*ap) - loss(*am)) / (2 * eps)
        ad = jnp.sum(g * d)
        np.testing.assert_allclose(float(ad), float(fd), rtol=2e-2,
                                   atol=2e-3)


def test_dropout_training_routes_to_einsum_off_tpu():
    """full_causal_attention(impl='flash') while training with dropout on a
    backend without the Pallas kernel must silently use the einsum path
    with identical semantics (same rng -> same mask)."""
    if jax.default_backend() == "tpu":
        pytest.skip("on TPU the flash path applies in-kernel dropout "
                    "(different mask stream than the einsum path)")
    q, k, v = _qkv(B=1, H=2, T=128, D=32)
    rng = jax.random.PRNGKey(5)
    a = full_causal_attention(q, k, v, dropout_rate=0.2, rng=rng,
                              train=True, impl="flash")
    b = full_causal_attention(q, k, v, dropout_rate=0.2, rng=rng,
                              train=True, impl="einsum")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# K/V-streaming kernels (VMEM-unbounded T): parity vs the resident kernels
# ---------------------------------------------------------------------------

def test_stream_causal_matches_einsum():
    """Causal stream uses the triangular scalar-prefetch grid; block 128 at
    T=512 exercises multi-tile rows and the init/finalize carry."""
    q, k, v = _qkv(B=1, H=2, T=512, D=32)
    ref = full_causal_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, causal=True, stream=True,
                                 block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_stream_noncausal_matches_einsum():
    q, k, v = _qkv(B=1, H=2, T=256, D=32)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v)
    got = pallas_flash_attention(q, k, v, causal=False, stream=True,
                                 block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_stream_rectangular_causal_unequal_blocks():
    """causal + block_q != block_k routes to the rectangular streamed grid
    (triangular needs square tiles); its pl.when skip/finalize logic must
    hold."""
    q, k, v = _qkv(B=1, H=1, T=512, D=32)
    ref = full_causal_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, causal=True, stream=True,
                                 block_q=256, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_stream_grads_match_einsum():
    q, k, v = _qkv(B=1, H=2, T=256, D=32)

    def loss_stream(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v, stream=True,
                                              block_q=128, block_k=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_causal_attention(q, k, v) ** 2)

    gf = jax.grad(loss_stream, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5)


def test_stream_dropout_matches_resident():
    """The kernel families share their tile math and the counter-based
    dropout mask keys off absolute positions, so streamed output must be
    BIT-identical to the resident kernels' — fwd and grads (the module
    docstring's bit-identity claim is asserted here)."""
    q, k, v = _qkv(B=1, H=2, T=256, D=32)
    rng = jax.random.PRNGKey(7)
    kw = dict(dropout_rate=0.3, dropout_rng=rng, block_q=128, block_k=128)
    a = pallas_flash_attention(q, k, v, stream=True, **kw)
    b = pallas_flash_attention(q, k, v, stream=False, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ga = jax.grad(lambda q: jnp.sum(
        pallas_flash_attention(q, k, v, stream=True, **kw) ** 2))(q)
    gb = jax.grad(lambda q: jnp.sum(
        pallas_flash_attention(q, k, v, stream=False, **kw) ** 2))(q)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))


def test_stream_auto_threshold():
    from replicatinggpt_tpu.ops.flash_pallas import (STREAM_KV_BYTES,
                                                     _should_stream)
    # D=64 bf16: K+V bytes = 2*T*64*2 = 256*T -> threshold at T=16384
    assert not _should_stream(16384, 64, 2)
    assert _should_stream(16384 + 128, 64, 2)
    assert _should_stream(STREAM_KV_BYTES, 1, 1)


def test_tri_tile_map():
    from replicatinggpt_tpu.ops.flash_pallas import _tri_tile_map
    qm = _tri_tile_map(3, kv_major=False)
    assert qm.tolist() == [[0, 1, 1, 2, 2, 2], [0, 0, 1, 0, 1, 2]]
    km = _tri_tile_map(3, kv_major=True)
    assert km.tolist() == [[0, 0, 0, 1, 1, 2], [0, 1, 2, 1, 2, 2]]


@pytest.mark.slow
def test_auto_tile_512_parity_and_grads():
    """T=1024 auto-selects 512-wide tiles (_auto_block); the causal
    n_kv bound, the dkv first_q skip, and the dropout tiling must hold
    at that size, not just the 128/256 tiles the other tests use."""
    from replicatinggpt_tpu.ops.flash_pallas import _auto_block
    assert _auto_block(1024) == 512
    q, k, v = _qkv(B=1, H=1, T=1024, D=64, seed=5)
    ref = full_causal_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    gf = jax.grad(lambda q: jnp.sum(pallas_flash_attention(q, k, v) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(full_causal_attention(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=5e-5,
                               rtol=5e-5)
    # dropout mask is position-keyed, so tile size must not change it
    rng = jax.random.PRNGKey(3)
    a = pallas_flash_attention(q, k, v, dropout_rate=0.3, dropout_rng=rng)
    b = pallas_flash_attention(q, k, v, dropout_rate=0.3, dropout_rng=rng,
                               block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.slow
def test_fused_single_tile_bwd_matches_split_kernels():
    """T == block triggers the fused dq/dk/dv backward; forcing smaller
    blocks runs the split dq + dkv kernels. Gradients must agree (same
    tile math, different launch structure), with and without dropout."""
    B, H, T, D = 2, 3, 256, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, T, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, T, D), jnp.float32)

    def grads(block, rate):
        def loss(q, k, v):
            kw = dict(causal=True, block_q=block, block_k=block)
            if rate > 0:
                kw.update(dropout_rate=rate,
                          dropout_rng=jax.random.PRNGKey(7))
            return jnp.sum(pallas_flash_attention(q, k, v, **kw) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for rate in (0.0, 0.2):
        fused = grads(T, rate)        # single tile -> fused kernel
        split = grads(T // 2, rate)   # 2x2 tiles -> split dq + dkv kernels
        for a, b in zip(fused, split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_fused_multi_tile_bwd_matches_split_kernels():
    """The kv-major fully-fused backward (1 < n_tiles, dq in VMEM
    scratch) must match the split dq + dkv kernels; forcing tiny blocks
    at T big enough to exceed the scratch bound runs the split path."""
    from replicatinggpt_tpu.ops import flash_pallas as fp

    B, H, T, D = 2, 2, 512, 64
    q = jax.random.normal(jax.random.PRNGKey(3), (B, H, T, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(4), (B, H, T, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(5), (B, H, T, D), jnp.float32)

    def grads(rate, scratch_bytes):
        old = fp.FUSED_DQ_SCRATCH_BYTES
        fp.FUSED_DQ_SCRATCH_BYTES = scratch_bytes
        try:
            def loss(q, k, v):
                kw = dict(causal=True, block_q=128, block_k=128)
                if rate > 0:
                    kw.update(dropout_rate=rate,
                              dropout_rng=jax.random.PRNGKey(11))
                return jnp.sum(pallas_flash_attention(q, k, v, **kw) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        finally:
            fp.FUSED_DQ_SCRATCH_BYTES = old
    for rate in (0.0, 0.2):
        fused = grads(rate, fp.FUSED_DQ_SCRATCH_BYTES)  # multi-tile fused
        split = grads(rate, 0)                           # forced split
        for a, b in zip(fused, split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def test_bf16_grads_match_einsum():
    """bf16 inputs run the native-bf16 matmul tiles (p/ds cast to operand
    dtype, f32 accumulation); gradients must track the einsum reference
    within bf16 tolerance on BOTH backward families — fused (block == T)
    and split (forced smaller blocks). Pins the bf16-specific precision
    envelope the f32 parity tests can't see (ADVICE r2)."""
    B, H, T, D = 2, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
               for kk in ks)

    def flash_grads(block):
        def loss(q, k, v):
            out = pallas_flash_attention(q, k, v, causal=True,
                                         block_q=block, block_k=block)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def ref_grads():
        def loss(q, k, v):
            out = full_causal_attention(q, k, v, impl="einsum")
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    gr = ref_grads()
    for block in (T, T // 2):  # fused single-tile, then split kernels
        gf = flash_grads(block)
        for a, b in zip(gf, gr):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            # bf16 has ~8 mantissa bits; grads here are O(1-30), so the
            # elementwise band is dominated by the final bf16 rounding
            np.testing.assert_allclose(a, b, rtol=6e-2, atol=0.25)


# ---------------------------------------------------------------------------
# packed-heads family: attention straight off the fused (B, T, 3C) QKV
# projection (no head transposes) — must match the unpacked family
# bit-for-bit on the same logical q/k/v
# ---------------------------------------------------------------------------

def _packed_inputs(B=2, T=256, H=6, D=64, seed=0, dtype=jnp.float32):
    C = H * D
    qkv = jax.random.normal(jax.random.PRNGKey(seed), (B, T, 3 * C), dtype)
    return qkv, C


def _heads(x, H):
    B, T, C = x.shape
    return x.reshape(B, T, H, C // H).transpose(0, 2, 1, 3)


def test_packed_fwd_bit_identical_to_unpacked():
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H = 6
    qkv, C = _packed_inputs(H=H)
    B, T = qkv.shape[:2]
    q, k, v = jnp.split(qkv, 3, -1)
    ref = pallas_flash_attention(_heads(q, H), _heads(k, H), _heads(v, H))
    ref = ref.transpose(0, 2, 1, 3).reshape(B, T, C)
    got = pallas_flash_attention_packed(qkv, H)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_packed_dropout_bit_identical_to_unpacked():
    """The packed kernel derives its dropout stream from bh = b*H + h —
    the same counter the unpacked kernels use — so masks must be exactly
    equal, not just statistically alike."""
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H = 4
    qkv, C = _packed_inputs(B=2, T=128, H=H, D=32, seed=3)
    B, T = qkv.shape[:2]
    rng = jax.random.PRNGKey(7)
    got = pallas_flash_attention_packed(qkv, H, dropout_rate=0.2,
                                        dropout_rng=rng)
    q, k, v = (_heads(t, H) for t in jnp.split(qkv, 3, -1))
    ref = pallas_flash_attention(q, k, v, dropout_rate=0.2, dropout_rng=rng)
    ref = ref.transpose(0, 2, 1, 3).reshape(B, T, C)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_packed_grads_match_unpacked():
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H = 4
    qkv, C = _packed_inputs(B=1, T=256, H=H, D=32, seed=11)
    B, T = qkv.shape[:2]

    def loss_packed(qkv):
        return jnp.sum(pallas_flash_attention_packed(qkv, H) ** 2)

    def loss_unpacked(qkv):
        q, k, v = (_heads(t, H) for t in jnp.split(qkv, 3, -1))
        o = pallas_flash_attention(q, k, v)
        return jnp.sum(o.transpose(0, 2, 1, 3).reshape(B, T, C) ** 2)

    gp = jax.grad(loss_packed)(qkv)
    gu = jax.grad(loss_unpacked)(qkv)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gu), atol=2e-4,
                               rtol=2e-4)


def test_packed_grads_with_dropout_match_unpacked():
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H = 2
    qkv, C = _packed_inputs(B=1, T=128, H=H, D=32, seed=13)
    B, T = qkv.shape[:2]
    rng = jax.random.PRNGKey(5)

    def loss_packed(qkv):
        o = pallas_flash_attention_packed(qkv, H, dropout_rate=0.25,
                                          dropout_rng=rng)
        return jnp.sum(o ** 2)

    def loss_unpacked(qkv):
        q, k, v = (_heads(t, H) for t in jnp.split(qkv, 3, -1))
        o = pallas_flash_attention(q, k, v, dropout_rate=0.25,
                                   dropout_rng=rng)
        return jnp.sum(o.transpose(0, 2, 1, 3).reshape(B, T, C) ** 2)

    gp = jax.grad(loss_packed)(qkv)
    gu = jax.grad(loss_unpacked)(qkv)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gu), atol=2e-4,
                               rtol=2e-4)


def test_packed_supported_envelope():
    from replicatinggpt_tpu.ops.flash_pallas import (PACKED_QKV_BYTES,
                                                     packed_supported)
    assert packed_supported(256, 384, 6, 2)        # char-GPT bf16
    assert not packed_supported(1024, 768, 12, 2)  # 124M: 4.7MB > bound
    assert not packed_supported(256, 384, 5, 2)    # C % H != 0
    assert not packed_supported(192, 384, 6, 2)    # T % 128 != 0
    assert not packed_supported(256, 96, 6, 2)     # D=16 not sliceable
    t_max = PACKED_QKV_BYTES // (3 * 384 * 2) // 128 * 128
    assert packed_supported(t_max, 384, 6, 2)
    assert not packed_supported(t_max + 128, 384, 6, 2)


def test_model_block_routes_packed(monkeypatch):
    """forward() with attention_impl resolving to flash must produce the
    same logits through the packed path (backend check monkeypatched so
    the interpret-mode kernel engages on CPU) as through the split-heads
    path."""
    import replicatinggpt_tpu.ops.flash_attention as fa
    from replicatinggpt_tpu.config import ModelConfig
    from replicatinggpt_tpu.models.gpt import forward, init_params

    mcfg = ModelConfig(vocab_size=64, block_size=256, n_layer=2, n_head=4,
                       n_embd=128, dropout=0.0, attn_dropout=0.0,
                       dtype="float32", attention_impl="flash")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 64)

    ref, _ = forward(params, x, mcfg)  # CPU backend -> split path (SDPA)

    calls = []

    def force_packed(qkv, n_head, **kw):
        from replicatinggpt_tpu.ops.flash_pallas import \
            pallas_flash_attention_packed
        calls.append(qkv.shape)
        rng, train = kw.get("rng"), kw.get("train", False)
        rate = kw.get("dropout_rate", 0.0)
        on = train and rate > 0.0 and rng is not None
        return pallas_flash_attention_packed(
            qkv, n_head, scale=kw.get("scale"),
            dropout_rate=rate if on else 0.0,
            dropout_rng=rng if on else None)

    monkeypatch.setattr(fa, "packed_qkv_attention", force_packed)
    got, _ = forward(params, x, mcfg)
    assert calls, "packed path was not routed"
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


# --- packed head-group family (GPT-2-scale shapes past the resident bound) --


@pytest.fixture
def group_family(monkeypatch):
    """Close the resident family's envelope, so the packed entry's own
    router takes the head-group family at the tests' small shapes."""
    import replicatinggpt_tpu.ops.flash_pallas as fp
    monkeypatch.setattr(fp, "PACKED_QKV_BYTES", 0)


# (B, T, block_q, block_k, causal): one tile at the auto block, then the
# several tiles, unequal tiles and non-causal grid gpt2-medium's T=1024
# in 512-row tiles runs
GROUP_TILINGS = {
    "one-tile": (2, 128, None, None, True),
    "multi-tile": (1, 256, 128, 128, True),
    "unequal": (1, 256, 128, 256, True),
    "noncausal-multi-tile": (1, 256, 128, 128, False),
}


def _group_tiling(*ids):
    return pytest.mark.parametrize(
        "B,T,block_q,block_k,causal", [GROUP_TILINGS[i] for i in ids],
        ids=ids)


@_group_tiling("one-tile", "multi-tile", "unequal", "noncausal-multi-tile")
def test_group_fwd_bit_identical_to_unpacked(group_family, B, T, block_q,
                                             block_k, causal):
    """hpg=4 (D=32): four sub-heads lane-sliced per 128-wide strip."""
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H, D = 4, 32
    qkv, C = _packed_inputs(B=B, T=T, H=H, D=D, seed=21)
    blocks = dict(block_q=block_q, block_k=block_k, causal=causal)
    q, k, v = jnp.split(qkv, 3, -1)
    ref = pallas_flash_attention(_heads(q, H), _heads(k, H), _heads(v, H),
                                 **blocks)
    ref = ref.transpose(0, 2, 1, 3).reshape(B, T, C)
    got = pallas_flash_attention_packed(qkv, H, **blocks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_group_fwd_single_head_groups(group_family):
    """hpg=1 (D=128): strip == head, no in-kernel sub-head loop."""
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H, D = 2, 128
    qkv, C = _packed_inputs(B=1, T=128, H=H, D=D, seed=22)
    B, T = qkv.shape[:2]
    q, k, v = jnp.split(qkv, 3, -1)
    ref = pallas_flash_attention(_heads(q, H), _heads(k, H), _heads(v, H))
    ref = ref.transpose(0, 2, 1, 3).reshape(B, T, C)
    got = pallas_flash_attention_packed(qkv, H)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_group_matches_resident_packed(monkeypatch):
    """Both packed families on the same in-envelope shape must agree
    exactly (same tile math, same bh counter stream)."""
    import replicatinggpt_tpu.ops.flash_pallas as fp
    H = 6
    qkv, _ = _packed_inputs(B=2, T=256, H=H, D=64, seed=23)
    res = fp.pallas_flash_attention_packed(qkv, H)
    monkeypatch.setattr(fp, "PACKED_QKV_BYTES", 0)
    grp = fp.pallas_flash_attention_packed(qkv, H)
    np.testing.assert_array_equal(np.asarray(grp), np.asarray(res))


@_group_tiling("one-tile", "multi-tile", "unequal")
def test_group_dropout_bit_identical_to_unpacked(group_family, B, T,
                                                 block_q, block_k, causal):
    """Sub-head s of group g keys dropout off bh = b*H + g*hpg + s — the
    global head counter — so masks must equal the unpacked family's."""
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H, D = 4, 32
    qkv, C = _packed_inputs(B=B, T=T, H=H, D=D, seed=24)
    rng = jax.random.PRNGKey(9)
    kw = dict(block_q=block_q, block_k=block_k, causal=causal,
              dropout_rate=0.2, dropout_rng=rng)
    got = pallas_flash_attention_packed(qkv, H, **kw)
    q, k, v = (_heads(t, H) for t in jnp.split(qkv, 3, -1))
    ref = pallas_flash_attention(q, k, v, **kw)
    ref = ref.transpose(0, 2, 1, 3).reshape(B, T, C)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("block_q,block_k", [(None, None), (128, 128),
                                             (128, 256)],
                         ids=["one-tile", "multi-tile", "unequal"])
def test_group_grads_match_unpacked(group_family, block_q, block_k):
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H, D = 4, 32
    qkv, C = _packed_inputs(B=1, T=256, H=H, D=D, seed=25)
    B, T = qkv.shape[:2]
    blocks = dict(block_q=block_q, block_k=block_k)

    def loss_group(qkv):
        o = pallas_flash_attention_packed(qkv, H, **blocks)
        return jnp.sum(o ** 2)

    def loss_unpacked(qkv):
        q, k, v = (_heads(t, H) for t in jnp.split(qkv, 3, -1))
        o = pallas_flash_attention(q, k, v, **blocks)
        return jnp.sum(o.transpose(0, 2, 1, 3).reshape(B, T, C) ** 2)

    gp = jax.grad(loss_group)(qkv)
    gu = jax.grad(loss_unpacked)(qkv)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gu), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("T,block", [(128, None), (256, 128)],
                         ids=["one-tile", "multi-tile"])
def test_group_grads_with_dropout_match_unpacked(group_family, T, block):
    from replicatinggpt_tpu.ops.flash_pallas import \
        pallas_flash_attention_packed
    H, D = 2, 64
    qkv, C = _packed_inputs(B=1, T=T, H=H, D=D, seed=26)
    B = qkv.shape[0]
    kw = dict(block_q=block, block_k=block, dropout_rate=0.25,
              dropout_rng=jax.random.PRNGKey(15))

    def loss_group(qkv):
        o = pallas_flash_attention_packed(qkv, H, **kw)
        return jnp.sum(o ** 2)

    def loss_unpacked(qkv):
        q, k, v = (_heads(t, H) for t in jnp.split(qkv, 3, -1))
        o = pallas_flash_attention(q, k, v, **kw)
        return jnp.sum(o.transpose(0, 2, 1, 3).reshape(B, T, C) ** 2)

    gp = jax.grad(loss_group)(qkv)
    gu = jax.grad(loss_unpacked)(qkv)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gu), atol=2e-4,
                               rtol=2e-4)


def test_group_supported_envelope():
    from replicatinggpt_tpu.ops.flash_pallas import (GROUP_STRIP_BYTES,
                                                     packed_group_supported)
    assert packed_group_supported(1024, 768, 12, 2)    # GPT-2 124M bf16
    assert packed_group_supported(1024, 1024, 16, 2)   # GPT-2 350M bf16
    assert packed_group_supported(2048, 768, 12, 2)    # T at the W=128 cap
    assert not packed_group_supported(4096, 768, 12, 2)   # past the cap
    assert not packed_group_supported(1024, 1600, 25, 2)  # H=25 % hpg=2
    assert not packed_group_supported(1024, 768, 7, 2)    # C % H != 0
    assert not packed_group_supported(192, 768, 12, 2)    # T % 128 != 0
    t_max = GROUP_STRIP_BYTES // (128 * 2) // 128 * 128
    assert packed_group_supported(t_max, 768, 12, 2)
    assert not packed_group_supported(t_max + 128, 768, 12, 2)


def test_model_block_routes_split_heads_past_strip_bound(monkeypatch):
    """forward() with both packed residency bounds closed must call
    neither packed family and produce the split-path logits (the path
    long sequences take). Bounds are shrunk instead of using a real
    >2048-token model so the test stays in the fast tier."""
    import replicatinggpt_tpu.ops.flash_attention as fa
    import replicatinggpt_tpu.ops.flash_pallas as fp
    from replicatinggpt_tpu.config import ModelConfig
    from replicatinggpt_tpu.models.gpt import forward, init_params

    mcfg = ModelConfig(vocab_size=64, block_size=512, n_layer=1, n_head=4,
                       n_embd=128, dropout=0.0, attn_dropout=0.0,
                       dtype="float32", attention_impl="flash")
    params = init_params(jax.random.PRNGKey(0), mcfg)
    x = jax.random.randint(jax.random.PRNGKey(1), (1, 512), 0, 64)
    ref, _ = forward(params, x, mcfg)  # CPU backend -> split path

    calls = []

    def spy(name):
        def fn(*a, **kw):
            calls.append(name)
            raise AssertionError(f"{name} routed past its bound")
        return fn

    monkeypatch.setattr(fp, "PACKED_QKV_BYTES", 1)
    monkeypatch.setattr(fp, "GROUP_STRIP_BYTES", 1)
    monkeypatch.setattr(fp, "_flash_packed", spy("resident"))
    monkeypatch.setattr(fp, "_flash_packed_group", spy("group"))
    monkeypatch.setattr(fa, "_packed_backend_ok", lambda: True)
    got, _ = forward(params, x, mcfg)
    assert not calls, calls
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("T,C,H,inside", [
    (2048, 768, 12, True),     # the last T a 128-lane bf16 strip holds
    (2176, 768, 12, False),    # one tile past GROUP_STRIP_BYTES
    (4096, 768, 12, False),
    (1024, 1600, 25, False),   # gpt2-xl: 25 heads form no 128-lane group
], ids=["strip-edge", "past-strip-edge", "long-T", "no-groups"])
def test_packed_envelope_edges(monkeypatch, T, C, H, inside):
    """The envelope gate, the group predicate and the entry's router
    agree at the strip bound: inside, the entry takes the head-group
    family; outside, the gate is closed and the entry refuses."""
    import replicatinggpt_tpu.ops.flash_attention as fa
    import replicatinggpt_tpu.ops.flash_pallas as fp
    monkeypatch.setattr(fa, "_packed_backend_ok", lambda: True)
    calls = []

    def group_stub(qkv, *args):
        calls.append(qkv.shape)
        return qkv[..., :C]

    monkeypatch.setattr(fp, "_flash_packed_group", group_stub)
    qkv = jnp.zeros((1, T, 3 * C), jnp.bfloat16)
    assert fp.packed_group_supported(T, C, H, 2) is inside
    assert fa.packed_envelope_ok(qkv, H) is inside
    if inside:
        fp.pallas_flash_attention_packed(qkv, H)
        assert calls == [qkv.shape]
    else:
        with pytest.raises(ValueError, match="packed families"):
            fp.pallas_flash_attention_packed(qkv, H)


def test_packed_entry_routes_group_past_resident_bound():
    """At 124M shapes (T=1024, C=768) the resident family is off-envelope
    and the entry must route to the group family; the envelope gate in
    ops.flash_attention must agree."""
    from replicatinggpt_tpu.ops.flash_attention import packed_envelope_ok
    from replicatinggpt_tpu.ops.flash_pallas import (packed_group_supported,
                                                     packed_supported)
    assert not packed_supported(1024, 768, 12, 2)
    assert packed_group_supported(1024, 768, 12, 2)
    import replicatinggpt_tpu.ops.flash_attention as fa
    orig = fa._packed_backend_ok
    fa._packed_backend_ok = lambda: True
    try:
        qkv = jnp.zeros((1, 1024, 3 * 768), jnp.bfloat16)
        assert packed_envelope_ok(qkv, 12)
    finally:
        fa._packed_backend_ok = orig
