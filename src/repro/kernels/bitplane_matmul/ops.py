"""Jit'd wrappers for bitplane_matmul: weight packing (store path) and the
value-space matmul entry point with shape padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bitplane_matmul import kernel as K
from repro.kernels.bitplane_matmul.ref import pack_weights_ref


def pack_weights(w: jnp.ndarray, bits: int = 16) -> jnp.ndarray:
    """(K, N) bf16 -> (bits, K, N//8) uint8 planes (store-path transform;
    on hardware this happens once at weight upload)."""
    u = jax.lax.bitcast_convert_type(w.astype(jnp.bfloat16), jnp.uint16)
    return pack_weights_ref(u, bits)


def bitplane_matmul(x: jnp.ndarray, planes: jnp.ndarray, keep: int = 16,
                    bits: int = 16, interpret: bool | None = None, **blocks) -> jnp.ndarray:
    """x (M, K) bf16 × plane-packed weights -> (M, N) f32.

    M is padded to the 128-row MXU tile if needed."""
    m = x.shape[0]
    bm = min(blocks.get("bm", 128), max(8, m))
    pad = (-m) % bm
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
    out = K.bitplane_matmul(
        x, planes, keep, bits,
        bm=bm, bk=blocks.get("bk", 512), bn=blocks.get("bn", 256),
        interpret=interpret,
    )
    return out[:m]


def weight_fetch_bytes(planes: jnp.ndarray, keep: int) -> int:
    """HBM bytes a (keep)-plane fetch moves — the roofline's memory term."""
    bits, k, n8 = planes.shape
    return keep * k * n8
