"""Pure-jnp oracle and packing for the bit-plane paged KV layout.

Device layout (one array per K and per V):

    planes (bits, *lead, S/PAGE, 1, W) int32,   W = 128 * ceil(Hkv*hd / 256)

A (plane, slot, page) unit is one row of ``W`` int32 words — the unit the
decode kernel DMAs when the page's plane count says that plane is live.
Elements ``n = h*hd + d`` of a token's KV vector are split into two halves:
element ``n < W`` sits in the low 16 bits of word ``n``, element ``n >= W``
in the high 16 bits of word ``n - W``; elements ``>= Hkv*hd`` are zero
padding.  Within a half, bit ``t`` holds token ``t`` of the page, and plane
``i`` (0 = MSB) holds bit ``15 - i`` of the bf16 value.  So the bits of a
page's plane are packed along tokens: the kernel broadcasts a unit row over
the page's 16 token rows and shifts by the token index, and every element
stays in its own lane.  The unit is a multiple of 128 lanes because the TPU
moves and tiles HBM in (1, 128)-word rows; the shape therefore does not
record ``(Hkv, hd)``, and unpacking takes them as ``kv_shape``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
#: tokens per device page: one 16-bit half-word holds a page's bits
PAGE = 16


def plane_words(n_elems: int) -> int:
    """Words per (plane, page) unit for ``n_elems`` = Hkv*hd KV elements."""
    return 128 * -(-n_elems // 256)


def pack_kv_ref(kv: jnp.ndarray, bits: int = 16) -> jnp.ndarray:
    """(*lead, S, Hkv, hd) bf16 -> (bits, *lead, S/PAGE, 1, W) int32."""
    *lead, s, hkv, hd = kv.shape
    if s % PAGE:
        raise ValueError(f"bit-plane packing needs S % {PAGE} == 0, got {s}")
    n = hkv * hd
    w = plane_words(n)
    u = jax.lax.bitcast_convert_type(kv.astype(jnp.bfloat16), jnp.uint16)
    u = u.reshape(*lead, s // PAGE, PAGE, n).astype(jnp.uint32)
    u = jnp.pad(u, [(0, 0)] * (len(lead) + 2) + [(0, 2 * w - n)])
    tok = jnp.arange(PAGE, dtype=jnp.uint32)[:, None]
    out = []
    for i in range(bits):  # one plane at a time bounds the temporaries
        bit = (u >> (bits - 1 - i)) & 1
        half = (bit << tok).sum(axis=-2, dtype=jnp.uint32)  # (*lead, P, 2W)
        out.append(half[..., :w] | (half[..., w:] << PAGE))
    words = jnp.stack(out)
    return jax.lax.bitcast_convert_type(words, jnp.int32)[..., None, :]


def unpack_kv_ref(planes: jnp.ndarray, keep: int, kv_shape,
                  bits: int = 16) -> jnp.ndarray:
    """planes -> (*lead, S, Hkv, hd) bf16 with planes keep..bits-1 zeroed
    (truncation); ``kv_shape`` = (Hkv, hd)."""
    hkv, hd = kv_shape
    words = jax.lax.bitcast_convert_type(planes[:keep, ..., 0, :], jnp.uint32)
    half = jnp.concatenate([words & 0xFFFF, words >> PAGE], axis=-1)
    tok = jnp.arange(PAGE, dtype=jnp.uint32)[:, None]
    u = jnp.zeros(half.shape[1:-1] + (PAGE, half.shape[-1]), jnp.uint32)
    for i in range(keep):
        bit = (half[i, ..., None, :] >> tok) & 1  # (*lead, P, PAGE, 2W)
        u = u | (bit << (bits - 1 - i))
    *lead, p, _, _ = u.shape
    u = u[..., :hkv * hd].reshape(*lead, p * PAGE, hkv, hd)
    return jax.lax.bitcast_convert_type(u.astype(jnp.uint16), jnp.bfloat16)


def ladder_attention_ref(q, k_planes, v_planes, ladder, valid_len, kv_heads,
                         bits=16):
    """q: (B, 1, Hp, hd); ladder: ((start_s, end_s, keep), ...) covering
    [0, S) at page boundaries.  Page ranges decode at their rung's
    precision; softmax runs over the union.  Returns (B, 1, Hp, hd)."""
    b, _, hp, hd = q.shape
    rep = hp // kv_heads
    k_parts, v_parts = [], []
    for (s0, s1, keep) in ladder:
        pages = slice(s0 // PAGE, s1 // PAGE)
        k_parts.append(unpack_kv_ref(k_planes[:, :, pages], keep,
                                     (kv_heads, hd), bits))
        v_parts.append(unpack_kv_ref(v_planes[:, :, pages], keep,
                                     (kv_heads, hd), bits))
    k = jnp.concatenate(k_parts, axis=1)
    v = jnp.concatenate(v_parts, axis=1)
    s_total = k.shape[1]
    head_map = np.arange(hp) // rep
    kh = k[:, :, head_map].astype(jnp.float32)
    vh = v[:, :, head_map].astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kh) / np.sqrt(hd)
    ok = jnp.arange(s_total) < valid_len
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vh)
    return o.astype(q.dtype)
