"""Exponent-delta transform Pallas kernel (paper §III.B eq. 6–7, Fig. 6 ③).

The controller's "small integer subtractor" per channel: for a channel-major
token group (C, G), subtract the per-channel minimum exponent from every
token's exponent field, emitting the per-channel base as the block header.

Block tiling: (bc, G) channels × the whole group (G = 16 tokens, the paper's
page).  The min-reduction runs along the in-VMEM group axis; one kernel
invocation handles bc channels — the analogue of the per-channel metadata
buffer in the ASIC datapath.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


def _encode_kernel(u_ref, enc_ref, base_ref, *, man_bits: int, exp_mask: int):
    u = u_ref[...].astype(jnp.uint32)  # (bc, G)
    exp = (u >> man_bits) & exp_mask
    base = exp.min(axis=1)  # (bc,)
    delta = exp - base[:, None]
    field = jnp.uint32(exp_mask << man_bits)
    enc_ref[...] = (u & ~field) | (delta << man_bits)
    base_ref[...] = base


def _decode_kernel(enc_ref, base_ref, u_ref, *, man_bits: int, exp_mask: int):
    enc = enc_ref[...].astype(jnp.uint32)
    base = base_ref[...].astype(jnp.uint32)  # (bc,)
    delta = (enc >> man_bits) & exp_mask
    exp = (delta + base[:, None]) & exp_mask
    field = jnp.uint32(exp_mask << man_bits)
    u_ref[...] = (enc & ~field) | (exp << man_bits)


@functools.partial(
    jax.jit, static_argnames=("man_bits", "exp_mask", "block_c", "interpret")
)
def encode(u: jnp.ndarray, man_bits: int, exp_mask: int, block_c: int = 256,
           interpret: bool | None = None):
    """u: (C, G) uint32 (C % block_c == 0) -> (encoded (C, G), base (C,))."""
    c, g = u.shape
    assert c % block_c == 0, (c, block_c)
    grid = (c // block_c,)
    return pl.pallas_call(
        functools.partial(_encode_kernel, man_bits=man_bits, exp_mask=exp_mask),
        grid=grid,
        in_specs=[pl.BlockSpec((block_c, g), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_c, g), lambda i: (i, 0)),
            pl.BlockSpec((block_c,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, g), jnp.uint32),
            jax.ShapeDtypeStruct((c,), jnp.uint32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(u)


@functools.partial(
    jax.jit, static_argnames=("man_bits", "exp_mask", "block_c", "interpret")
)
def decode(encoded: jnp.ndarray, base: jnp.ndarray, man_bits: int, exp_mask: int,
           block_c: int = 256, interpret: bool | None = None):
    c, g = encoded.shape
    assert c % block_c == 0
    grid = (c // block_c,)
    return pl.pallas_call(
        functools.partial(_decode_kernel, man_bits=man_bits, exp_mask=exp_mask),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, g), lambda i: (i, 0)),
            pl.BlockSpec((block_c,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_c, g), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, g), jnp.uint32),
        interpret=default_interpret() if interpret is None else interpret,
    )(encoded, base)
