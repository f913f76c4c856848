"""Flash attention Pallas kernel (train / prefill path).

Scores, the online-softmax state and the output accumulator live entirely in
VMEM: HBM traffic is exactly one read of q/k/v and one write of o — the
property the roofline analysis credits when the jnp fallback (whose chunked
scores round-trip HBM) is replaced by this kernel.

Tiling: grid (B, Hp, Sq/bq, Skv/bkv), Skv innermost (sequential on TPU, so
VMEM scratch carries m/l/acc across kv blocks).  GQA is an index-map: q-head
h fetches kv-head h // rep — no head-expanded KV is ever materialised.
Causal and sliding-window masks are evaluated per block; fully-masked blocks
still iterate (a block-skip grid is a §Perf follow-up, noted in
EXPERIMENTS.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, kv_len: int,
            bq: int, bkv: int, n_kv: int):
    j = pl.program_id(3)
    q = q_ref[...].reshape(q_ref.shape[1], q_ref.shape[3])  # (bq, hd)
    k = k_ref[...].reshape(k_ref.shape[1], k_ref.shape[3])  # (bkv, hd)
    v = v_ref[...].reshape(v_ref.shape[1], v_ref.shape[3])

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bkv)

    i = pl.program_id(2)
    q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_idx = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = kv_idx < kv_len
    if causal:
        ok &= kv_idx <= q_idx
        if window > 0:
            ok &= kv_idx > q_idx - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_scr[:, 0] * corr + p.sum(axis=1)
    acc = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
    acc_scr[...] = acc

    @pl.when(j == n_kv - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "kv_len", "bq", "bkv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    kv_len: int | None = None,
    bq: int = 512,
    bkv: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q (B, Sq, Hp, hd) bf16; k/v (B, Skv, Hkv, hd); Hp % Hkv == 0.

    ``kv_len`` masks trailing (padded) kv positions; scale uses the REAL
    head_dim even if hd was padded upstream (ops.py handles padding)."""
    b, sq, hp, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = hp // hkv
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    kv_len = skv if kv_len is None else kv_len
    n_kv = skv // bkv
    grid = (b, hp, sq // bq, n_kv)
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(
            _kernel, scale=1.0 / np.sqrt(hd), causal=causal, window=window,
            kv_len=kv_len, bq=bq, bkv=bkv, n_kv=n_kv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b_, h, i, j: (b_, i, h, 0)),
            pl.BlockSpec((1, bkv, 1, hd), lambda b_, h, i, j, rep=rep: (b_, j, h // rep, 0)),
            pl.BlockSpec((1, bkv, 1, hd), lambda b_, h, i, j, rep=rep: (b_, j, h // rep, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd), lambda b_, h, i, j: (b_, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(q, k, v)
