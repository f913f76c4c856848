"""Jit'd wrappers: value-space KV groups in, delta-encoded uints + bases out.

Handles channel padding to the kernel's block granularity and the integer
formats (exp_bits == 0 -> pass-through, mirroring core.kv_clustering).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.bitplane import FloatSpec
from repro.kernels.exp_delta import kernel as K


def _pad_channels(u: jnp.ndarray, block_c: int):
    c = u.shape[0]
    rem = (-c) % block_c
    if rem:
        u = jnp.concatenate([u, jnp.zeros((rem, u.shape[1]), u.dtype)])
    return u, c


def encode(u: jnp.ndarray, spec: FloatSpec, block_c: int = 256,
           interpret: bool | None = None):
    """u: (C, G) raw uint view (any uint dtype). Returns (encoded, base)
    in the input dtype / uint8 base."""
    if spec.exp_bits == 0:
        return u, jnp.zeros(u.shape[:-1], jnp.uint8)
    orig_dtype = u.dtype
    u32, c = _pad_channels(u.astype(jnp.uint32), block_c)
    enc, base = K.encode(u32, spec.man_bits, spec.exp_mask, block_c, interpret)
    return enc[:c].astype(orig_dtype), base[:c].astype(jnp.uint8)


def decode(encoded: jnp.ndarray, base: jnp.ndarray, spec: FloatSpec,
           block_c: int = 256, interpret: bool | None = None):
    if spec.exp_bits == 0:
        return encoded
    orig_dtype = encoded.dtype
    e32, c = _pad_channels(encoded.astype(jnp.uint32), block_c)
    b32, _ = _pad_channels(base.astype(jnp.uint32)[:, None], block_c)
    out = K.decode(e32, b32[:, 0], spec.man_bits, spec.exp_mask, block_c, interpret)
    return out[:c].astype(orig_dtype)
