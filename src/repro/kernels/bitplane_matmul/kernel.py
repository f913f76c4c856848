"""Bit-plane matmul Pallas kernel — the paper's partial-plane weight fetch
(Fig. 5) fused into the consuming matmul.

This is the TPU-native realization of "memory bandwidth scales with dynamic
quantization" (DESIGN.md §2): weights live in HBM as bit-planes
(bits, K, N//8); the kernel's BlockSpec maps ONLY the top ``keep`` planes of
each (K, N) tile, so HBM→VMEM weight traffic is keep/16 of the bf16 bytes.
Inside VMEM the planes are re-aggregated to bf16 with VPU shifts (the ASIC's
de-shuffle network) and fed straight to the MXU — the reconstructed tile
never round-trips to HBM.

Grid (M/bm, N/bn, K/bk), K innermost; fp32 accumulation in the output block
across the K dimension (standard Pallas matmul revisiting pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


def _kernel(x_ref, p_ref, o_ref, *, keep: int, bits: int):
    """x (bm, bk) bf16; p (keep, bk, bn//8) uint8; o (bm, bn) f32."""
    p = p_ref[...].astype(jnp.uint32)  # (keep, bk, bn8)
    byte_w = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 1, 8), 3)
    bm8 = (p[..., None] >> (7 - byte_w)) & 1  # (keep, bk, bn8, 8)
    plane_w = jax.lax.broadcasted_iota(jnp.uint32, (keep, 1, 1, 1), 0)
    u = (bm8 << ((bits - 1) - plane_w)).sum(axis=0)  # (bk, bn8, 8)
    bk = u.shape[0]
    u16 = u.reshape(bk, -1).astype(jnp.uint16)
    w = jax.lax.bitcast_convert_type(u16, jnp.bfloat16)  # (bk, bn)

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...], w, preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit,
    static_argnames=("keep", "bits", "bm", "bk", "bn", "interpret"),
)
def bitplane_matmul(
    x: jnp.ndarray,
    planes: jnp.ndarray,
    keep: int,
    bits: int = 16,
    bm: int = 128,
    bk: int = 512,
    bn: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x (M, K) bf16 × planes (bits, K, N//8) -> (M, N) f32.

    keep = plane count fetched (16 = exact bf16, 8 ≈ bf8, ...); HBM weight
    bytes per step = keep · K · N / 8."""
    m, k = x.shape
    bits_, k2, n8 = planes.shape
    n = n8 * 8
    assert bits_ == bits and k2 == k
    bm = min(bm, m)
    bk = min(bk, k)
    bn = min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, keep=keep, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            # Only the top `keep` plane rows of the (bk, bn) weight tile are
            # mapped — the partial-plane fetch.
            pl.BlockSpec((keep, bk, bn // 8), lambda i, j, l: (0, l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=default_interpret() if interpret is None else interpret,
    )(x, planes)
