"""Gated DeltaNet's recurrence (Yang, Kautz and Hatamizadeh 2024, "Gated
Delta Networks", arXiv:2412.06464): one state ``S`` of ``d_k x d_v``
float32 a head, and for every token ``t`` of a sequence

    S <- alpha_t S
    S <- S + k_t (beta_t (v_t - S^T k_t))^T
    o_t = S^T q_t

(``q`` and ``k`` l2-normed, ``q`` times ``d_k ** -0.5``; ``alpha`` in (0, 1],
``beta`` in (0, 2) where negative eigenvalues are allowed). Three forms of
the same rule:

- ``recurrent_step``: one token a row in XLA, what the kernel computes
  (the contract the kernel's tests and a backend without Pallas take);
- ``gated_delta_decode``: the Pallas decode kernel. Grid ``(rows,)``, ONE
  TURN A SLOT: the slot's whole state comes into VMEM, is updated and goes
  back to the same buffer (aliased: the pool is updated in place), a row
  that is not live copies it through, a row at its sequence's first
  position starts from zeros whatever the slot held;
- ``chunk_scan``: the chunked (WY / UT) form for prefill, chunks of
  ``CHUNK`` rows: within a chunk the delta rule's rank-one updates are
  gathered by one triangular solve into matrix products, and the state
  crosses chunk edges only. float32 at the highest matmul precision.

**The state's layout in the pool** (``state_shape``): a slot's heads go
``g`` abreast on the lanes, ``(H / g, d_k, g * d_v)``, with ``g`` the
fewest heads whose ``d_v`` columns fill whole 128-lane tiles (2 at d_v
192): no lane of HBM or VMEM is padding. ``to_heads`` / ``from_heads``
turn it into ``(H, d_k, d_v)`` and back. ``chunk_scan_cost`` is what a
chunk of prefill must move and compute, for the program's stats.
"""

from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_pallas import LANES, _interpret_mode, pltpu

_F32 = jnp.float32

#: rows of a prefill chunk the chunked scan gathers into one triangular
#: solve (the WY / UT form's chunk; FLA and transformers use 64)
CHUNK = 64

#: VMEM the decode kernel may take: a slot's state in and out, each double
#: buffered (4 x 2.2 MB at the published 30 heads of 96 x 192), beside its
#: q, k, v, alpha, beta and output rows. A v5e core has 128 MiB of VMEM;
#: the compiler's default scope is 16 MiB.
DECODE_VMEM_BYTES = 32 * 1024 * 1024


# ------------------------------------------------------------- the layout

def heads_abreast(dv: int) -> int:
    """Heads whose ``dv`` columns side by side fill whole lane tiles."""
    return LANES // math.gcd(dv, LANES)


def state_shape(heads: int, dk: int, dv: int) -> Tuple[int, int, int]:
    """A slot's state as the pool keeps it: ``(heads / g, dk, g * dv)``."""
    g = heads_abreast(dv)
    assert heads % g == 0, (
        f"{heads} heads do not go {g} abreast (value head {dv})")
    return heads // g, dk, g * dv


def to_heads(state, dv: int):
    """(..., G, dk, g*dv) -> (..., G*g, dk, dv)."""
    *lead, G, dk, gl = state.shape
    g = gl // dv
    s = state.reshape(*lead, G, dk, g, dv)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, G * g, dk, dv)


def from_heads(S):
    """(..., H, dk, dv) -> the pool's (..., H/g, dk, g*dv)."""
    *lead, H, dk, dv = S.shape
    g = heads_abreast(dv)
    s = S.reshape(*lead, H // g, g, dk, dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, H // g, dk, g * dv)


# ------------------------------------------------------- token by token

def recurrent_step(S, q, k, v, alpha, beta):
    """One token a row: S (..., H, dk, dv), q, k (..., H, dk), v (..., H,
    dv), alpha, beta (..., H), all float32 -> ``(o (..., H, dv), S)``.
    Elementwise products and sums, as ``transformers``'
    ``torch_recurrent_gated_delta_rule`` writes them (no matmul whose
    precision a backend could lower)."""
    S = S * alpha[..., None, None]
    kv = jnp.sum(S * k[..., :, None], -2)
    d = beta[..., None] * (v - kv)
    S = S + k[..., :, None] * d[..., None, :]
    return jnp.sum(S * q[..., :, None], -2), S


def decode_xla(state, q, k, v, alpha, beta, first, active):
    """``gated_delta_decode``'s contract in plain XLA, on the pool's
    layout: row b of ``state`` is slot b."""
    dv = v.shape[-1]
    old = to_heads(state, dv)
    S = jnp.where(first[:, None, None, None], 0.0, old)
    o, S = recurrent_step(S, q, k, v, alpha, beta)
    live = active[:, None, None, None]
    return (jnp.where(active[:, None, None], o, 0.0),
            from_heads(jnp.where(live, S, old)))


# ------------------------------------------------------ the decode kernel

def decode_envelope(heads: int, dk: int, dv: int) -> Tuple[bool, List[str]]:
    """Whether the decode kernel takes these widths: a state row of whole
    lane tiles, key rows of whole sublane tiles, and a slot's state in and
    out (double-buffered) inside ``DECODE_VMEM_BYTES``."""
    reasons = []
    if heads % heads_abreast(dv):
        reasons.append("gdn_heads_abreast")
    if dk % 8:
        reasons.append("gdn_key_head_sublanes")
    if 4 * heads * dk * dv * 4 + (1 << 20) > DECODE_VMEM_BYTES:
        reasons.append("gdn_state_vmem")
    return not reasons, reasons


def _decode_kernel(flags_ref, qk_ref, v_ref, a_ref, b_ref, s_ref, o_ref,
                   s_out, *, heads: int, g: int, dv: int):
    """One slot: its state ``s_ref`` (1, G, dk, g*dv), keys then queries
    as COLUMNS ``qk_ref`` (1, dk, 2H), its v, alpha and beta on the lanes
    of the state's rows (1, G, g*dv). A head's key column is picked by a
    masked lane sum and spread over the head's ``dv`` lanes, so the rule
    is vector work on whole tiles: a sublane sum for ``S^T k`` and for
    ``S^T q``, a broadcast for the rank-one update."""
    b = pl.program_id(0)
    _, G, dk, gl = s_ref.shape
    fresh = flags_ref[0, b] != 0
    live = flags_ref[1, b] != 0

    @pl.when(live)
    def _step():
        qk = qk_ref[0]                                        # (dk, 2H)
        lane = jax.lax.broadcasted_iota(jnp.int32, qk.shape, 1)
        head_of = jax.lax.broadcasted_iota(jnp.int32, (1, gl), 1) // dv
        row = jax.lax.broadcasted_iota(jnp.int32, (G, gl), 0)
        vs, al, be = v_ref[0], a_ref[0], b_ref[0]             # (G, gl)

        def col(j):
            return jnp.sum(jnp.where(lane == j, qk, 0.0), 1, keepdims=True)

        def spread(first_col):                                # (dk, gl)
            out = jnp.broadcast_to(col(first_col), (dk, gl))
            for r in range(1, g):
                out = jnp.where(head_of == r, col(first_col + r), out)
            return out

        def pick(x, p):                                       # (1, gl)
            return jnp.sum(jnp.where(row == p, x, 0.0), 0, keepdims=True)

        def group(p, o):
            S = jnp.where(fresh, 0.0, s_ref[0, p]) * pick(al, p)
            K = spread(p * g)
            kv = jnp.sum(S * K, 0, keepdims=True)
            S = S + K * (pick(be, p) * (pick(vs, p) - kv))
            s_out[0, p] = S
            Q = spread(heads + p * g)
            return jnp.where(row == p, jnp.sum(S * Q, 0, keepdims=True), o)

        o_ref[0] = jax.lax.fori_loop(0, G, group, jnp.zeros((G, gl), _F32))

    @pl.when(jnp.logical_not(live))
    def _keep():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, _F32)


# the engine's decode program, into which this inlines, donates the pool
@functools.partial(jax.jit, static_argnames=("name",))  # graftlint: disable=GL005
def gated_delta_decode(state, q, k, v, alpha, beta, first, active, *,
                       name: str = "gdn_recurrence"):
    """One decode step of every slot's recurrence, in place.

    state (B, G, dk, g*dv) float32, the pool's layout (row b is slot b);
    q, k (B, H, dk) and v (B, H, dv) float32; alpha, beta (B, H) float32;
    first, active (B,) bool. Returns ``(o (B, H, dv) float32, state)``:
    the state buffer is the kernel's output (``input_output_aliases``), a
    row that is not ``active`` keeps its state and reads zeros, a ``first``
    row starts from zeros. Under one inner ``jit``: a program whose layers
    are a Python loop lowers the kernel once."""
    B, G, dk, gl = state.shape
    H, dv = v.shape[1], v.shape[2]
    g = gl // dv
    qk = jnp.concatenate([k, q], 1).swapaxes(1, 2).astype(_F32)
    lanes = lambda a: a.astype(_F32).reshape(B, G, gl)
    flags = jnp.stack([first, active]).astype(jnp.int32)
    rows = pl.BlockSpec((1, G, gl), lambda b, f: (b, 0, 0))
    slot = pl.BlockSpec((1, G, dk, gl), lambda b, f: (b, 0, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=H, g=g, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec((1, dk, 2 * H), lambda b, f: (b, 0, 0)),
                      rows, rows, rows, slot],
            out_specs=[rows, slot]),
        out_shape=[jax.ShapeDtypeStruct((B, G, gl), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=DECODE_VMEM_BYTES),
        name=name, interpret=_interpret_mode(),
    )(flags, qk, lanes(v), lanes(jnp.repeat(alpha, dv, 1)),
      lanes(jnp.repeat(beta, dv, 1)), state)
    return o.reshape(B, H, dv), state


# ------------------------------------------------------- the chunked scan

def chunk_scan(q, k, v, log_alpha, beta, state, chunk: int = CHUNK):
    """The rule over T rows of ONE sequence from ``state``, a chunk of
    ``chunk`` rows a turn: q, k (T, H, dk), v (T, H, dv), log_alpha, beta
    (T, H), state (H, dk, dv), T a multiple of ``chunk`` -> ``(o (T, H,
    dv), the state after row T - 1)``, float32. A row with ``beta`` 0 and
    ``log_alpha`` 0 leaves the state as it found it.

    Within a chunk, with ``c`` the cumulative sum of ``log_alpha`` and
    ``D_ij = exp(c_i - c_j)`` (i >= j): ``T = (I + L)^-1`` for ``L`` the
    strictly lower part of ``(beta k) k^T * D``; ``u = T (beta v)`` and
    ``w = T (beta k exp(c))`` are the chunk's corrected values and keys;
    then ``v' = u - w S``, ``o = (q exp(c)) S + ((q k^T * D), lower) v'``
    and ``S <- exp(c_last) S + (k exp(c_last - c))^T v'``
    (``transformers``' ``torch_chunk_gated_delta_rule``)."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    n, C = T // chunk, chunk
    with jax.default_matmul_precision("highest"):
        split = lambda a: jnp.swapaxes(
            a.astype(_F32).reshape((n, C) + a.shape[1:]), 1, 2)
        q, k, v, la, be = map(split, (q, k, v, log_alpha, beta))
        c = jnp.cumsum(la, -1)                                # (n, H, C)
        i = jnp.arange(C)
        lower = i[:, None] >= i[None, :]
        strict = i[:, None] > i[None, :]
        D = jnp.where(lower, jnp.exp(jnp.where(
            lower, c[..., :, None] - c[..., None, :], 0.0)), 0.0)
        kb = k * be[..., None]
        L = jnp.where(strict, jnp.einsum("nhik,nhjk->nhij", kb, k) * D, 0.0)
        eye = jnp.broadcast_to(jnp.eye(C, dtype=_F32), L.shape)
        Tm = jax.scipy.linalg.solve_triangular(
            eye + L, eye, lower=True, unit_diagonal=True)
        u = jnp.einsum("nhij,nhjv->nhiv", Tm, v * be[..., None])
        w = jnp.einsum("nhij,nhjk->nhik", Tm, kb * jnp.exp(c)[..., None])
        qk = jnp.where(lower, jnp.einsum("nhik,nhjk->nhij", q, k) * D, 0.0)

        def turn(S, xs):
            q_i, k_i, u_i, w_i, qk_i, c_i = xs
            vn = u_i - jnp.einsum("hik,hkv->hiv", w_i, S)
            o = (jnp.einsum("hik,hkv->hiv", q_i * jnp.exp(c_i)[..., None], S)
                 + jnp.einsum("hij,hjv->hiv", qk_i, vn))
            last = c_i[:, -1]
            S = (S * jnp.exp(last)[:, None, None]
                 + jnp.einsum("hik,hiv->hkv",
                              k_i * jnp.exp(last[:, None] - c_i)[..., None],
                              vn))
            return S, o

        S, o = jax.lax.scan(turn, state.astype(_F32), (q, k, u, w, qk, c))
    return jnp.swapaxes(o, 1, 2).reshape(T, H, dv), S


def chunk_scan_cost(tokens: int, heads: int, dk: int, dv: int,
                    chunk: int = CHUNK) -> Tuple[int, int]:
    """``(bytes, FLOPs)`` the chunked scan of ``tokens`` rows of ONE layer
    must move and compute: the float32 state read and written once, q, k,
    v, alpha and beta read and the output written once a row; a chunk's
    ``chunk^3`` of triangular solve, ``6 chunk^2 dk + 4 chunk^2 dv`` of
    products inside it and ``6 chunk dk dv`` against the state, a head."""
    n = -(-tokens // chunk)
    moved = 4 * heads * (2 * dk * dv + tokens * (2 * dk + 2 * dv + 2))
    work = heads * n * (chunk ** 3 + 6 * chunk * chunk * dk
                        + 4 * chunk * chunk * dv + 6 * chunk * dk * dv)
    return moved, work
