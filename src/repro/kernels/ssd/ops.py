"""Jit'd wrapper for the SSD kernel (zero-state default, chunk padding)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd import kernel as K


def ssd(xdt, da, b_h, c_h, h0=None, chunk: int = 256, interpret: bool | None = None):
    """Drop-in for models.ssm.ssd_scan (same contract)."""
    bsz, l, h, p = xdt.shape
    n = b_h.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    q = min(chunk, l)
    pad = (-l) % q
    if pad:
        # Pad with zero inputs and da=0 (decay exp(0)=1 keeps state frozen).
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        da = jnp.pad(da, ((0, 0), (0, pad), (0, 0)))
        b_h = jnp.pad(b_h, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_h = jnp.pad(c_h, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, h_final = K.ssd(
        xdt.astype(jnp.float32), da.astype(jnp.float32),
        b_h.astype(jnp.float32), c_h.astype(jnp.float32),
        h0.astype(jnp.float32), chunk=q, interpret=interpret,
    )
    return y[:, :l], h_final
