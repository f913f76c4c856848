"""Paged decode attention over the bit-plane KV layout (paper Fig. 5/6
device path; layout in ``ref.py``).

Two kernels serve the ladder:

* ``paged_attention_rung`` — one invocation per precision rung (a page set
  at a fixed ``keep``); the ops wrapper composes rungs of the Quest ladder
  (§II.C) and merges their online-softmax partials.  One compile per
  rung-set member.
* ``paged_attention_fused`` — ONE invocation walks the per-page plane map
  inline: the map arrives by scalar prefetch, every page's planes
  [0, keep) arrive via predicated async copies from the planes left in
  ``ANY`` memory space, and planes keep..15 are never touched.  No per-rung
  launch loop, no partials merge — one compile per model config, whatever
  the ladder's rung set.

HBM traffic per page = keep/16 of the padded plane units — the "memory
bandwidth scales proportionally with dynamic quantization" claim, enforced
structurally (rung: the BlockSpec maps only ``keep`` planes; fused: the
plane DMA loop is predicated on the page's keep).

Grid (B, S/bs), S innermost; scratch carries m/l/acc.  Each program serves
every KV head of its slot: q arrives spread over the element lanes of its
own KV head (zeros elsewhere, see ``ops._spread_q``), so one matmul per
element half scores all heads at once.  The rung kernel emits UNNORMALISED
partials (o·l, m, l) so rungs merge exactly; the fused kernel normalises in
its finish block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret
from repro.kernels.paged_attention.ref import PAGE

NEG_INF = -1e30

#: kernel-body trace counters (bumped when Pallas traces the body, i.e.
#: once per distinct compiled variant) — the compile-count regression test
#: reads these: a serving decode step must trace the fused kernel exactly
#: once vs ``len(rung_set)`` rung traces.
TRACE_COUNTS = {"rung": 0, "fused": 0}


def _unpack_tile(read_plane, n_planes: int, ppt: int, w: int, bits: int,
                 live=None):
    """Assemble a tile's KV from its plane units: ``read_plane(i)`` gives
    plane i as (ppt, w) int32 words; returns the two element halves, each
    (ppt*PAGE, w) bf16 with rows in token order.  ``live`` (ppt, w) int32
    zeroes plane i of every page whose keep is <= i (its buffer rows may
    hold an earlier tile's bytes — the DMA loop never refreshed them)."""
    t = jax.lax.broadcasted_iota(jnp.int32, (ppt, PAGE, w), 1)
    t = t.reshape(ppt * PAGE, w)
    lo = jnp.zeros((ppt * PAGE, w), jnp.int32)
    hi = jnp.zeros((ppt * PAGE, w), jnp.int32)
    for i in range(n_planes):
        x = read_plane(i)
        if live is not None:
            x = jnp.where(i < live, x, 0)
        x = jnp.broadcast_to(x[:, None, :], (ppt, PAGE, w))
        x = x.reshape(ppt * PAGE, w)
        lo = lo | (((x >> t) & 1) << (bits - 1 - i))
        hi = hi | (((x >> (t + PAGE)) & 1) << (bits - 1 - i))
    # a bf16 is the top half of an f32 with the same bits
    return [jax.lax.bitcast_convert_type(u << 16, jnp.float32)
            .astype(jnp.bfloat16) for u in (lo, hi)]


def _attend(q_ref, mask_ref, k, v, m_scr, l_scr, acc_scr, *, first,
            scale: float):
    """One tile of the online softmax over all heads' (hq, bs) scores."""

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    dims = (((1,), (1,)), ((), ()))
    s = sum(jax.lax.dot_general(q_ref[0, c], k[c], dims,
                                preferred_element_type=jnp.float32)
            for c in range(2)) * scale  # (hq, bs)
    s = jnp.where(mask_ref[0, 0] > 0, s, NEG_INF)

    m_prev = m_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_scr[:, 0:1] * corr + p.sum(axis=1, keepdims=True)
    for c in range(2):
        acc_scr[c] = acc_scr[c] * corr + jax.lax.dot_general(
            p.astype(v[c].dtype), v[c], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _softmax_scratch(hq: int, w: int) -> list:
    """m, l (lane-broadcast) and the two element-half accumulators."""
    return [
        pltpu.VMEM((hq, 128), jnp.float32),
        pltpu.VMEM((hq, 128), jnp.float32),
        pltpu.VMEM((2, hq, w), jnp.float32),
    ]


def _rung_kernel(q_ref, kp_ref, vp_ref, mask_ref, o_ref, m_ref, l_ref,
                 m_scr, l_scr, acc_scr, *, keep: int, bits: int,
                 scale: float, n_s: int):
    TRACE_COUNTS["rung"] += 1
    j = pl.program_id(1)
    ppt, w = kp_ref.shape[2], kp_ref.shape[4]
    k = _unpack_tile(lambda i: kp_ref[i, 0, :, 0, :], keep, ppt, w, bits)
    v = _unpack_tile(lambda i: vp_ref[i, 0, :, 0, :], keep, ppt, w, bits)
    _attend(q_ref, mask_ref, k, v, m_scr, l_scr, acc_scr, first=j == 0,
            scale=scale)

    @pl.when(j == n_s - 1)
    def _finish():
        for c in range(2):
            o_ref[0, c] = acc_scr[c]  # unnormalised (o·l)
        m_ref[0] = m_scr[:, 0:1]
        l_ref[0] = l_scr[:, 0:1]


@functools.partial(
    jax.jit, static_argnames=("keep", "scale", "bits", "bs", "interpret")
)
def paged_attention_rung(
    q: jnp.ndarray,
    k_planes: jnp.ndarray,
    v_planes: jnp.ndarray,
    mask: jnp.ndarray,
    keep: int,
    scale: float,
    bits: int = 16,
    bs: int = 128,
    interpret: bool | None = None,
):
    """One precision rung over a page range.

    q (B, 2, Hq, W) bf16 spread over element lanes; k/v_planes
    (bits, B, S/16, 1, W) int32; mask (B, S/bs, 1, bs) int32 (1 = valid
    token); scale = 1/sqrt(head_dim).  Returns unnormalised partials
    (o (B, 2, Hq, W) f32, m (B, Hq, 1) f32, l (B, Hq, 1) f32)."""
    if interpret is None:
        interpret = default_interpret()
    b, _, hq, w = q.shape
    n_pages = k_planes.shape[2]
    ppt = bs // PAGE
    assert bs % PAGE == 0 and n_pages % ppt == 0, (n_pages, bs)
    n_s = n_pages // ppt
    planes_spec = pl.BlockSpec((keep, 1, ppt, 1, w),
                               lambda b_, j: (0, b_, j, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _rung_kernel, keep=keep, bits=bits, scale=scale, n_s=n_s,
        ),
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, 2, hq, w), lambda b_, j: (b_, 0, 0, 0)),
            planes_spec,  # top `keep` planes only: the partial-plane fetch
            planes_spec,
            pl.BlockSpec((1, 1, 1, bs), lambda b_, j: (b_, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 2, hq, w), lambda b_, j: (b_, 0, 0, 0)),
            pl.BlockSpec((1, hq, 1), lambda b_, j: (b_, 0, 0)),
            pl.BlockSpec((1, hq, 1), lambda b_, j: (b_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 2, hq, w), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
        ],
        scratch_shapes=_softmax_scratch(hq, w),
        interpret=interpret,
    )(q, k_planes, v_planes, mask)


def _fused_kernel(keeps_ref, q_ref, mask_ref, kp_hbm, vp_hbm, o_ref,
                  m_scr, l_scr, acc_scr, k_buf, v_buf, k_sem, v_sem, *,
                  bits: int, scale: float, n_s: int, n_pages: int):
    """Single-launch ladder decode: walks the tile's per-page plane map
    (scalar-prefetched) and gathers each page's planes [0, keep) from the
    HBM planes with predicated async copies — planes keep..15 are never
    moved.  One online softmax across the whole tile sequence; the finish
    block normalises in-kernel (guarding fully-masked rows), so there are
    no partials to merge and no per-rung launches."""
    TRACE_COUNTS["fused"] += 1
    b_, j = pl.program_id(0), pl.program_id(1)
    ppt, w = k_buf.shape[1], k_buf.shape[3]
    keeps = [keeps_ref[b_ * n_pages + j * ppt + pp] for pp in range(ppt)]
    for pp in range(ppt):

        def plane_body(i, carry, pp=pp):
            @pl.when(i < keeps[pp])
            def _copy():
                src = j * ppt + pp
                ck = pltpu.make_async_copy(kp_hbm.at[i, b_, src],
                                           k_buf.at[i, pp], k_sem)
                cv = pltpu.make_async_copy(vp_hbm.at[i, b_, src],
                                           v_buf.at[i, pp], v_sem)
                ck.start()
                cv.start()
                ck.wait()
                cv.wait()
            return carry

        jax.lax.fori_loop(0, bits, plane_body, 0)

    # each page row's live plane count, broadcast over its words
    row = jax.lax.broadcasted_iota(jnp.int32, (ppt, w), 0)
    live = jnp.zeros((ppt, w), jnp.int32)
    for pp in range(ppt):
        live = jnp.where(row == pp, keeps[pp], live)
    k = _unpack_tile(lambda i: k_buf[i, :, 0, :], bits, ppt, w, bits, live)
    v = _unpack_tile(lambda i: v_buf[i, :, 0, :], bits, ppt, w, bits, live)
    _attend(q_ref, mask_ref, k, v, m_scr, l_scr, acc_scr, first=j == 0,
            scale=scale)

    @pl.when(j == n_s - 1)
    def _finish():
        m = m_scr[:, 0:1]
        inv = 1.0 / jnp.maximum(l_scr[:, 0:1], 1e-30)
        # a row whose every position is masked: m stayed -inf, l == 0 —
        # any residual (exp(-inf - -inf) = nan) must not escape: gate on m
        ok = m > NEG_INF / 2
        for c in range(2):
            o_ref[0, c] = jnp.where(ok, acc_scr[c] * inv, 0.0)


@functools.partial(
    jax.jit, static_argnames=("scale", "bits", "bs", "interpret")
)
def paged_attention_fused(
    q: jnp.ndarray,
    k_planes: jnp.ndarray,
    v_planes: jnp.ndarray,
    page_keeps: jnp.ndarray,
    mask: jnp.ndarray,
    scale: float,
    bits: int = 16,
    bs: int = 128,
    interpret: bool | None = None,
):
    """One launch over the whole mixed-precision cache.

    q (B, 2, Hq, W) bf16 spread over element lanes; k/v_planes
    (bits, B, S/16, 1, W) int32; page_keeps (B, S/16) int32 — planes
    [0, keep) of each page are gathered, the rest never read; mask
    (B, S/bs, 1, bs) int32 (1 = valid token); scale = 1/sqrt(head_dim).
    Returns the NORMALISED output (B, 2, Hq, W) f32 — fully-masked rows
    are zero."""
    if interpret is None:
        interpret = default_interpret()
    b, _, hq, w = q.shape
    n_pages = k_planes.shape[2]
    ppt = bs // PAGE
    assert bs % PAGE == 0 and n_pages % ppt == 0, (n_pages, bs)
    n_s = n_pages // ppt
    return pl.pallas_call(
        functools.partial(
            _fused_kernel, bits=bits, scale=scale, n_s=n_s, n_pages=n_pages,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_s),
            in_specs=[
                pl.BlockSpec((1, 2, hq, w), lambda b_, j, kp: (b_, 0, 0, 0)),
                pl.BlockSpec((1, 1, 1, bs), lambda b_, j, kp: (b_, j, 0, 0)),
                # planes stay in HBM; the kernel gathers [0, keep) of each
                # page itself — the predicated partial-plane fetch
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 2, hq, w),
                                   lambda b_, j, kp: (b_, 0, 0, 0)),
            scratch_shapes=_softmax_scratch(hq, w) + [
                pltpu.VMEM((bits, ppt, 1, w), jnp.int32),
                pltpu.VMEM((bits, ppt, 1, w), jnp.int32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 2, hq, w), jnp.float32),
        interpret=interpret,
    )(page_keeps.reshape(-1), q, mask, k_planes, v_planes)
