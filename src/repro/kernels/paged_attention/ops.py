"""Ladder composition and layout helpers for bit-plane paged attention.

``ladder_paged_attention`` runs one kernel call per precision rung (a
contiguous, page-aligned KV range at ``keep`` planes) and merges the
unnormalised online-softmax partials — mathematically identical to a single
softmax over the mixed-precision KV (the ref oracle computes it that way).

``batched_ladder_paged_attention`` is the serving entry point: one call
covers every slot of a continuous-batching decode step.  Each slot carries
its own valid length and its own per-page plane assignment (the ladder
re-ranks pages per slot, so the rung geometry differs row by row).  Two
kernel strategies (``kernel=``):

* ``"fused"`` (default) — ONE launch of ``paged_attention_fused``: the
  kernel walks the per-page plane map inline (scalar-prefetched keeps +
  predicated per-plane async copies), so the compile count is one per
  model config and there is no partials merge at all;
* ``"rung"`` — one launch per *distinct* plane count in ``keeps`` with a
  (slot, position) participation mask, partials merged here; the compile
  count is bounded by the ladder's rung set.  Kept for differential
  testing against the fused path.

Either way planes keep..15 are structurally unreadable — the rung
BlockSpec never maps them, the fused DMA loop never issues their copies —
which is the bandwidth-proportionality property the device path inherits
from the store (Fig. 5).

The page unit packs a page's 16 tokens together (``ref.py``), so writes go
through ``insert_kv_token`` (one token per row, bitwise, inside jit) or
``write_kv_rows`` (any host-indexed rows: unpack, update, repack the
touched pages).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention.ref import (
    PAGE,
    pack_kv_ref,
    unpack_kv_ref,
)


def pack_kv_planes(kv: jnp.ndarray, bits: int = 16) -> jnp.ndarray:
    """(*lead, S, Hkv, hd) bf16 -> (bits, *lead, S/16, 1, W) int32."""
    return pack_kv_ref(kv, bits)


def _page_index(rows):
    """Host rows -> (pages they touch, each row's index into those pages'
    unpacked rows)."""
    if isinstance(rows, slice):
        rows = np.arange(rows.start, rows.stop)
    rows = np.asarray(rows)
    pages = np.unique(rows // PAGE)
    return pages, np.searchsorted(pages, rows // PAGE) * PAGE + rows % PAGE


def read_kv_rows(planes: jnp.ndarray, rows, kv_shape) -> jnp.ndarray:
    """Full-precision KV at device ``rows`` (host ints or a slice) of
    planes (bits, *lead, S/16, 1, W) -> (*lead, len(rows), Hkv, hd) bf16;
    only the pages the rows touch are unpacked."""
    pages, idx = _page_index(rows)
    bits = planes.shape[0]
    dense = unpack_kv_ref(planes[..., pages, :, :], bits, kv_shape, bits)
    return dense[..., idx, :, :]


def write_kv_rows(planes: jnp.ndarray, rows, kv: jnp.ndarray) -> jnp.ndarray:
    """``planes`` with kv (*lead, T, Hkv, hd) written at device ``rows``
    (host ints or a slice): the touched pages are unpacked, updated and
    repacked, so their other tokens keep their bits."""
    pages, idx = _page_index(rows)
    bits = planes.shape[0]
    dense = unpack_kv_ref(planes[..., pages, :, :], bits, kv.shape[-2:], bits)
    dense = dense.at[..., idx, :, :].set(kv.astype(dense.dtype))
    return planes.at[..., pages, :, :].set(pack_kv_ref(dense, bits))


def insert_kv_token(planes: jnp.ndarray, kv: jnp.ndarray,
                    slot: jnp.ndarray) -> jnp.ndarray:
    """Decode append: planes (bits, B, S/16, 1, W); kv (B, Hkv, hd); slot
    (B,) int32 device row per batch row.  Sets the token's bit in every
    word of its page unit — lossless, and the page's other tokens are
    untouched."""
    bits, b = planes.shape[:2]
    w = planes.shape[-1]
    n = kv.shape[1] * kv.shape[2]
    u = jax.lax.bitcast_convert_type(
        kv.astype(jnp.bfloat16).reshape(b, n), jnp.uint16
    ).astype(jnp.uint32)
    u = jnp.pad(u, ((0, 0), (0, 2 * w - n)))
    shifts = jnp.arange(bits - 1, -1, -1, dtype=jnp.uint32)[:, None, None]
    bit = (u[None] >> shifts) & 1  # (bits, B, 2W)
    t = (slot % PAGE).astype(jnp.uint32)[None, :, None]
    new = (bit[..., :w] << t) | (bit[..., w:] << (t + PAGE))
    clear = ~((jnp.uint32(1) << t) | (jnp.uint32(1) << (t + PAGE)))
    rows, page = jnp.arange(b), slot // PAGE
    old = jax.lax.bitcast_convert_type(planes[:, rows, page, 0, :],
                                       jnp.uint32)
    words = jax.lax.bitcast_convert_type((old & clear) | new, jnp.int32)
    return planes.at[:, rows, page, 0, :].set(words)


def _spread_q(q: jnp.ndarray, kv_heads: int, w: int) -> jnp.ndarray:
    """(B, 1, Hp, hd) -> (B, 2, Hp, W): q-head p's vector on the element
    lanes of its KV head p // rep (zeros elsewhere), split into the plane
    layout's two element halves — one matmul per half then scores every
    head against its own KV head."""
    b, _, hp, hd = q.shape
    own = np.arange(hp)[:, None] // (hp // kv_heads) == np.arange(kv_heads)
    full = jnp.where(own[None, :, :, None], q[:, 0, :, None, :], 0)
    full = full.reshape(b, hp, kv_heads * hd)
    full = jnp.pad(full, ((0, 0), (0, 0), (0, 2 * w - kv_heads * hd)))
    return full.reshape(b, hp, 2, w).transpose(0, 2, 1, 3)


def _gather_heads(o: jnp.ndarray, kv_heads: int, hd: int) -> jnp.ndarray:
    """(B, 2, Hp, W) element-lane outputs -> (B, Hp, hd): each q-head's
    own KV head's lanes (the other lanes mixed in foreign values)."""
    b, _, hp, w = o.shape
    full = o.transpose(0, 2, 1, 3).reshape(b, hp, 2 * w)
    full = full[..., :kv_heads * hd].reshape(b, hp, kv_heads, hd)
    return full[:, np.arange(hp), np.arange(hp) // (hp // kv_heads)]


def _tile_mask(ok: jnp.ndarray, bs: int) -> jnp.ndarray:
    """(B, S) bool -> (B, S/bs, 1, bs) int32, the kernels' mask blocks."""
    b, s = ok.shape
    return ok.astype(jnp.int32).reshape(b, s // bs, 1, bs)


def _pick_bs(s_total: int, bs: int) -> int:
    """Largest page-multiple tile <= ``bs`` that divides the sequence
    length (a page-aligned cache always admits one page)."""
    cap = min(bs, s_total)
    for cand in sorted({cap, 128, 64, 32, PAGE}, reverse=True):
        if cand <= cap and s_total % cand == 0 and cand % PAGE == 0:
            return cand
    raise ValueError(f"no page-multiple tile divides S={s_total}")


def _merge(parts):
    """Merge unnormalised (o, m, l) partials; normalise; zero rows that
    every part fully masked (m stayed -inf, the partials are garbage)."""
    o_all, m_all, l_all = parts[0]
    for o_r, m_r, l_r in parts[1:]:
        m_new = jnp.maximum(m_all, m_r)
        c_old = jnp.exp(m_all - m_new)
        c_new = jnp.exp(m_r - m_new)
        o_all = o_all * c_old[:, None] + o_r * c_new[:, None]
        l_all = l_all * c_old + l_r * c_new
        m_all = m_new
    out = o_all / jnp.maximum(l_all, 1e-30)[:, None]
    return jnp.where(m_all[:, None] > K.NEG_INF / 2, out, 0.0)


def ladder_paged_attention(
    q: jnp.ndarray,
    k_planes: jnp.ndarray,
    v_planes: jnp.ndarray,
    ladder,
    valid_len: int,
    kv_heads: int,
    bits: int = 16,
    bs: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q (B, 1, Hp, hd); ladder ((s0, s1, keep), ...) covering [0, S) at
    page boundaries.

    Returns (B, 1, Hp, hd) attention output in q.dtype.  HBM KV bytes =
    Σ_rungs keep/16 · the range's plane units (``kv_fetch_bytes``)."""
    b, one, hp, hd = q.shape
    assert one == 1
    w = k_planes.shape[-1]
    qs = _spread_q(q, kv_heads, w)
    s_total = k_planes.shape[2] * PAGE
    ok = jnp.broadcast_to(jnp.arange(s_total) < valid_len, (b, s_total))
    parts = []
    for (s0, s1, keep) in ladder:
        tile = _pick_bs(s1 - s0, bs)
        pages = slice(s0 // PAGE, s1 // PAGE)
        parts.append(K.paged_attention_rung(
            qs, k_planes[:, :, pages], v_planes[:, :, pages],
            _tile_mask(ok[:, s0:s1], tile), keep=keep,
            scale=float(1.0 / np.sqrt(hd)), bits=bits, bs=tile,
            interpret=interpret,
        ))
    out = _gather_heads(_merge(parts), kv_heads, hd)
    return out.reshape(b, 1, hp, hd).astype(q.dtype)


def batched_ladder_paged_attention(
    q: jnp.ndarray,
    k_planes: jnp.ndarray,
    v_planes: jnp.ndarray,
    page_planes: jnp.ndarray,
    valid_len: jnp.ndarray,
    keeps: tuple,
    *,
    kv_heads: int,
    bits: int = 16,
    bs: int = 128,
    interpret: bool | None = None,
    q_pos: jnp.ndarray | None = None,
    kv_pos: jnp.ndarray | None = None,
    window: int = 0,
    kernel: str = "fused",
) -> jnp.ndarray:
    """Multi-slot decode step over a shared bit-plane cache.

    q (B, 1, Hp, hd); k/v_planes (bits, B, S/16, 1, W) int32 holding
    ``kv_heads`` KV heads; page_planes (B, S/16) int32 — the plane count
    the ladder assigned to each slot's device page (entries must come from
    ``keeps``); valid_len (B,) int32 — per-slot valid cache entries;
    keeps — the static set of distinct plane counts the ladder can assign.

    kernel — ``"fused"`` (one launch, the kernel gathers each page's
    planes itself; ``keeps`` only bounds the values ``page_planes`` may
    hold) or ``"rung"`` (one launch per member of ``keeps``, partials
    merged here).  Both walk whole pages: a cache with no page-multiple
    tile is a ValueError, never a fallback to the other kernel.

    q_pos (B, 1) optional absolute query positions (causality belt for
    rows whose valid_len overshoots); kv_pos (B, S) optional absolute slot
    positions for ring caches (-1 = unfilled) with ``window`` masking.

    A fully-masked rung contributes m = -inf, l = 0 partials and drops out
    of the merge; a row with no valid entries at all returns zeros (idle
    serving slots — the scheduler discards those rows).
    """
    if kernel not in ("fused", "rung"):
        raise ValueError(f"kernel must be 'fused' or 'rung', got {kernel!r}")
    b, one, hp, hd = q.shape
    assert one == 1
    w = k_planes.shape[-1]
    s_total = k_planes.shape[2] * PAGE
    bs = _pick_bs(s_total, bs)
    qs = _spread_q(q, kv_heads, w)
    scale = float(1.0 / np.sqrt(hd))
    valid_len = jnp.asarray(valid_len)
    if valid_len.ndim == 0:
        valid_len = jnp.broadcast_to(valid_len, (b,))

    kpos = (kv_pos if kv_pos is not None
            else jnp.broadcast_to(jnp.arange(s_total, dtype=jnp.int32),
                                  (b, s_total)))
    ok = (kpos >= 0) & (kpos < valid_len[:, None])
    if q_pos is not None:
        ok &= kpos <= q_pos[:, :1]
        if window > 0:
            ok &= kpos > q_pos[:, :1] - window
    page_of = jnp.arange(s_total) // PAGE  # (S,) device page index
    page_planes = page_planes.astype(jnp.int32)

    if kernel == "fused":
        # the fused kernel reads planes [0, keep) of every page directly;
        # a page outside the rung set entirely (keep <= 0) must stay
        # unread, exactly as no rung mask would have covered it
        mask = _tile_mask(ok & (page_planes[:, page_of] > 0), bs)
        out = K.paged_attention_fused(
            qs, k_planes, v_planes, page_planes, mask, scale=scale,
            bits=bits, bs=bs, interpret=interpret,
        )
    else:
        out = _merge([
            K.paged_attention_rung(
                qs, k_planes, v_planes,
                _tile_mask(ok & (page_planes[:, page_of] == keep), bs),
                keep=keep, scale=scale, bits=bits, bs=bs,
                interpret=interpret,
            )
            for keep in keeps
        ])
    out = _gather_heads(out, kv_heads, hd)
    return out.reshape(b, 1, hp, hd).astype(q.dtype)


def kv_fetch_bytes(k_planes: jnp.ndarray, ladder) -> int:
    """HBM bytes both KV streams move for a ladder fetch: ``keep`` plane
    units per page, each unit its padded W int32 words."""
    _, b, _, _, w = k_planes.shape
    unit = 4 * w
    total = sum(keep * (s1 - s0) // PAGE * unit for (s0, s1, keep) in ladder)
    return 2 * b * total  # k and v
