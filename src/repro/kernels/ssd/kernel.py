"""SSD (state-space duality) Pallas kernel — Mamba2's chunked scan with the
intra-chunk quadratic form kept in VMEM.

The jnp fallback materialises the (B, nc, Q, Q, H) decay/attention tensors
in HBM; this kernel computes the (Q, Q) intra-chunk form per (batch, head,
chunk) block in VMEM and carries the (N, P) recurrent state in scratch
across the (sequential) chunk grid dimension — HBM traffic is one read of
xdt/da/B/C and one write of y, independent of Q.

Grid (B, H, nc), nc innermost.  All math fp32 (SSD recurrences are
decay-sensitive; matches the production Mamba2 kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


def _kernel(xdt_ref, da_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, state_scr,
            *, nc: int):
    c_idx = pl.program_id(2)
    q = xdt_ref.shape[1]
    xdt = xdt_ref[...].reshape(q, xdt_ref.shape[3])  # (Q, P)
    da = da_ref[...].reshape(q)  # (Q,)
    b = b_ref[...].reshape(q, b_ref.shape[3])  # (Q, N)
    c = c_ref[...].reshape(q, c_ref.shape[3])  # (Q, N)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = h0_ref[...].reshape(state_scr.shape)

    cum = jnp.cumsum(da)  # (Q,) inclusive
    cum_last = cum[q - 1]

    # Intra-chunk: seg[i, j] = exp(cum_i - cum_j) for i >= j.
    seg = cum[:, None] - cum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    seg = jnp.where(row >= col, jnp.exp(seg), 0.0)
    att = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * seg  # (Q, Q)
    y = jax.lax.dot_general(
        att, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # Inter-chunk: y += exp(cum) * (C @ state_before).
    state = state_scr[...]  # (N, P)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[...] = y.reshape(y_ref.shape)

    # State update: S' = S * exp(cum_last) + Σ_j exp(cum_last - cum_j) B_j xdt_j.
    w_decay = jnp.exp(cum_last - cum)  # (Q,)
    s_chunk = jax.lax.dot_general(
        b * w_decay[:, None], xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (N, P)
    state_scr[...] = state * jnp.exp(cum_last) + s_chunk

    @pl.when(c_idx == nc - 1)
    def _finish():
        hout_ref[...] = state_scr[...].reshape(hout_ref.shape)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(xdt, da, b_h, c_h, h0, chunk: int = 256, interpret: bool | None = None):
    """xdt (B, L, H, P); da (B, L, H); b_h/c_h (B, L, H, N); h0 (B, H, N, P).

    Returns (y (B, L, H, P) f32, h_final (B, H, N, P) f32)."""
    bsz, l, h, p = xdt.shape
    n = b_h.shape[-1]
    q = min(chunk, l)
    assert l % q == 0, (l, q)
    nc = l // q
    grid = (bsz, h, nc)
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_kernel, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, 1, p), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, q, 1), lambda b_, h_, c_: (b_, c_, h_)),
            pl.BlockSpec((1, q, 1, n), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, q, 1, n), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, 1, p), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, l, h, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
    )(xdt, da, b_h, c_h, h0)
