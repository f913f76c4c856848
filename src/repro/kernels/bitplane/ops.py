"""Public jit'd wrappers for the bit-plane kernels: dtype plumbing, padding
to the kernel's block granularity, and value-space convenience entry points
(bf16/fp16/fp8 tensors in, plane arrays out).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.bitplane import FloatSpec, from_uint, to_uint
from repro.kernels.bitplane import kernel as K


def _pad_values(u: jnp.ndarray, block_values: int) -> tuple:
    n = u.shape[0]
    rem = (-n) % block_values
    if rem:
        u = jnp.concatenate([u, jnp.zeros((rem,), u.dtype)])
    return u, n


def pack(x: jnp.ndarray, spec: FloatSpec, block_bytes: int = K.DEFAULT_BLOCK_BYTES,
         interpret: bool | None = None) -> tuple:
    """Tensor -> (planes (bits, padded//8) uint8, n_values).

    One plane row of ``block_bytes`` bytes corresponds to 8·block_bytes
    values — the paper's 4 KB compression block."""
    u = to_uint(x, spec).astype(jnp.uint32)
    u, n = _pad_values(u, 8 * block_bytes)
    planes = K.pack(u, spec.bits, block_bytes, interpret=interpret)
    return planes, n


def unpack(planes: jnp.ndarray, spec: FloatSpec, shape, keep: int | None = None,
           block_bytes: int = K.DEFAULT_BLOCK_BYTES, interpret: bool | None = None) -> jnp.ndarray:
    """Planes -> tensor of ``shape`` (top-``keep``-plane truncation applied
    when keep < bits — the memory-side meaning of FP-k)."""
    import numpy as np

    u = K.unpack(planes, spec.bits, keep, block_bytes, interpret=interpret)
    n = int(np.prod(shape))
    return from_uint(u[:n].astype(jnp.dtype(f"uint{max(8, spec.bits)}")), spec, shape)
