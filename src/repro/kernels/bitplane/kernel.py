"""Bit-plane pack/unpack Pallas kernels — the controller's "bit-plane
aggregator" (paper §III.A, Fig. 5) as a VPU bit-matrix transpose.

Hardware adaptation (DESIGN.md §2): the ASIC shuffle network routing bits
into 1–4 KB plane buffers becomes a tiled VPU kernel; the plane buffer is a
VMEM block.  The unpack kernel's BlockSpec maps ONLY the top ``keep`` plane
rows, so the HBM→VMEM traffic is ``keep/bits`` of the stored planes — the
bandwidth-proportional partial-plane fetch, expressed structurally in the
index map rather than by a runtime branch.

Layouts (pinned to core.bitplane / numpy packbits):
  values (m,) viewed as (m//8, 8) uint32  <->  planes (bits, m//8) uint8,
  plane 0 = MSB, bit 7 of each byte = first value of its group of 8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


DEFAULT_BLOCK_BYTES = 4096  # one VMEM plane-block == the paper's 4 KB block


def _pack_kernel(u_ref, planes_ref, *, bits: int):
    """u_ref: (bm, 8) uint32 block -> planes_ref: (bits, bm) uint8 block."""
    x = u_ref[...].astype(jnp.uint32)  # (bm, 8)
    bm = x.shape[0]
    # (bits, bm, 8) bit matrix: plane i = bit (bits-1-i).
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (bits, 1, 1), 0)
    bits_mat = (x[None, :, :] >> ((bits - 1) - shifts)) & 1
    # Pack along the value-octet axis, MSB-first (value 0 -> bit 7).
    byte_w = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 8), 2)
    packed = (bits_mat << (7 - byte_w)).sum(axis=2)  # (bits, bm)
    planes_ref[...] = packed.astype(jnp.uint8)


def _unpack_kernel(planes_ref, u_ref, *, bits: int, keep: int):
    """planes_ref: (keep, bm) uint8 block -> u_ref: (bm, 8) uint32 block."""
    p = planes_ref[...].astype(jnp.uint32)  # (keep, bm)
    bm = p.shape[1]
    byte_w = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 8), 2)
    bits_mat = (p[:, :, None] >> (7 - byte_w)) & 1  # (keep, bm, 8)
    plane_w = jax.lax.broadcasted_iota(jnp.uint32, (keep, 1, 1), 0)
    vals = (bits_mat << ((bits - 1) - plane_w)).sum(axis=0)  # (bm, 8)
    u_ref[...] = vals.astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bits", "block_bytes", "interpret"))
def pack(u: jnp.ndarray, bits: int, block_bytes: int = DEFAULT_BLOCK_BYTES,
         interpret: bool | None = None) -> jnp.ndarray:
    """(m,) uint32 (m % (8*block_bytes) == 0) -> (bits, m//8) uint8."""
    m = u.shape[0]
    mbytes = m // 8
    assert m % 8 == 0 and mbytes % block_bytes == 0, (m, block_bytes)
    grid = (mbytes // block_bytes,)
    return pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((block_bytes, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bits, block_bytes), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((bits, mbytes), jnp.uint8),
        interpret=default_interpret() if interpret is None else interpret,
    )(u.reshape(mbytes, 8))


@functools.partial(
    jax.jit, static_argnames=("bits", "keep", "block_bytes", "interpret")
)
def unpack(planes: jnp.ndarray, bits: int, keep: int | None = None,
           block_bytes: int = DEFAULT_BLOCK_BYTES, interpret: bool | None = None) -> jnp.ndarray:
    """(bits, m//8) uint8 -> (m,) uint32, fetching only the top ``keep``
    planes from memory (BlockSpec block height = keep)."""
    keep = bits if keep is None else keep
    n_planes, mbytes = planes.shape
    assert n_planes == bits and mbytes % block_bytes == 0
    grid = (mbytes // block_bytes,)
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, bits=bits, keep=keep),
        grid=grid,
        # Block height `keep`: planes keep..bits-1 are never mapped, never
        # fetched — bandwidth scales with the chosen precision.
        in_specs=[pl.BlockSpec((keep, block_bytes), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_bytes, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mbytes, 8), jnp.uint32),
        interpret=default_interpret() if interpret is None else interpret,
    )(planes)
    return out.reshape(mbytes * 8)
