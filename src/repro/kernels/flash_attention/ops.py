"""Jit'd wrapper: pads head_dim to the 128-lane MXU width and sequence
lengths to block multiples, then strips the padding."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import kernel as K


def flash_attention(q, k, v, *, causal=True, window=0, bq=512, bkv=512,
                    interpret=None):
    b, sq, hp, hd = q.shape
    skv = k.shape[1]
    scale_hd = hd  # real head_dim defines the softmax scale
    hd_pad = (-hd) % 128 if hd > 16 else (-hd) % 8
    bq = min(bq, max(8, 1 << (sq - 1).bit_length()))
    bkv = min(bkv, max(8, 1 << (skv - 1).bit_length()))
    sq_pad = (-sq) % bq
    skv_pad = (-skv) % bkv

    def pad(x, s_pad, h_pad):
        return jnp.pad(x, ((0, 0), (0, s_pad), (0, 0), (0, h_pad)))

    qp = pad(q, sq_pad, hd_pad)
    kp = pad(k, skv_pad, hd_pad)
    vp = pad(v, skv_pad, hd_pad)
    # hd padding adds zero components: dot products unchanged; scale must
    # stay 1/sqrt(real hd) — the kernel derives it from the padded shape, so
    # rescale q to compensate.
    if hd_pad:
        qp = qp * np.sqrt((hd + hd_pad) / scale_hd).astype(np.float32)
    out = K.flash_attention(
        qp, kp, vp, causal=causal, window=window, kv_len=skv,
        bq=bq, bkv=bkv, interpret=interpret,
    )
    return out[:, :sq, :, :hd]
