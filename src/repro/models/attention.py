"""GQA attention with a flash-style chunked softmax (pure jnp).

Design notes (DESIGN.md §3):

* **GQA with awkward head counts.** q-heads may be padded to a multiple of
  the TP degree (``cfg.pad_heads_to``); padded heads have zero ``wq`` rows
  and are masked out before ``wo``, so the function is exactly the published
  architecture while every sharded einsum stays balanced.  KV heads are
  *replicated* across the model axis (standard practice when
  n_kv_heads < TP), and each q head gathers its kv head via a static
  ``head_map`` (clipped ``h // rep``), which is comm-free on replicated KV.

* **Flash-style chunking.** Attention scans over KV chunks with an online
  softmax in fp32, so the (Sq, Skv) score matrix never materialises — the
  32 k-token prefill fits in VMEM-scale working sets.  This jnp version is
  also the oracle for the Pallas kernel (kernels/flash_attention).

* **One code path** for train (Sq == Skv, causal), prefill (same), decode
  (Sq == 1 against a long cache with ``kv_valid`` masking), sliding-window
  (Mixtral) and bidirectional (Whisper encoder / cross-attention).

* **Bit-plane device caches.**  A serving cache may store KV as packed
  bit-planes (``{'k_planes','v_planes'}``, layout (bits, B, S/16, 1, W)
  int32, see ``kernels/paged_attention/ref.py``) instead of dense bf16.
  Decode appends set the new token's bits in its page
  (:func:`~repro.kernels.paged_attention.ops.insert_kv_token` — lossless
  for bf16) and attention runs the Pallas paged-attention kernel, reading
  only the planes the per-page ``kv_planes`` map prescribes — the device
  path of the paper's bandwidth-proportionality claim.  Prefill chunks
  attend densely at full precision (unpack -> flash -> repack), since the
  ladder only governs decode fetches.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.ops import (
    batched_ladder_paged_attention,
    insert_kv_token,
    pack_kv_planes,
)
from repro.kernels.paged_attention.ref import PAGE, unpack_kv_ref
from repro.models.layers import (
    apply_rope,
    he_init,
    rmsnorm,
    rmsnorm_params,
    rope_angles,
)

NEG_INF = -1e30


def head_map_static(n_q_heads_padded, n_heads, n_kv_heads):
    """Static q-head -> kv-head mapping, *grouped* layout.

    Padded q-heads are interleaved per kv group: q-head ``h`` serves kv head
    ``h // rep_p`` where ``rep_p = Hp / Hkv``; within each group the first
    ``n_heads/n_kv_heads`` slots are real heads and the rest are padding.
    The grouped layout keeps each kv head's q-heads contiguous, so GQA decode
    attention is a reshape (no head gather) and TP sharding of the q-head
    axis never splits a kv group unevenly."""
    hkv = max(1, n_kv_heads)
    assert n_q_heads_padded % hkv == 0, (n_q_heads_padded, n_kv_heads)
    rep_p = n_q_heads_padded // hkv
    return jnp.asarray(np.arange(n_q_heads_padded) // rep_p, jnp.int32)


def valid_q_heads(n_q_heads_padded, n_heads, n_kv_heads) -> np.ndarray:
    """(Hp,) bool — which padded q-head slots are real heads."""
    hkv = max(1, n_kv_heads)
    rep_p = n_q_heads_padded // hkv
    rep = max(1, n_heads) // hkv
    return (np.arange(n_q_heads_padded) % rep_p) < rep


def attn_params(key, cfg, dtype, d_model=None):
    d = d_model or cfg.d_model
    hp, hkv, hd = cfg.n_q_heads_padded, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    valid = jnp.asarray(valid_q_heads(hp, cfg.n_heads, hkv), dtype)
    wq = he_init(ks[0], (d, hp, hd), dtype, fan_in=d) * valid[None, :, None]
    wo = he_init(ks[3], (hp, hd, d), dtype, fan_in=hp * hd) * valid[:, None, None]
    return {
        "wq": wq,
        "wk": he_init(ks[1], (d, hkv, hd), dtype, fan_in=d),
        "wv": he_init(ks[2], (d, hkv, hd), dtype, fan_in=d),
        "wo": wo,
    }


def flash_attention(
    q,
    k,
    v,
    head_map,
    *,
    q_pos,
    kv_valid,
    window=0,
    bidirectional=False,
    chunk=512,
    kv_pos=None,
):
    """Online-softmax attention.

    q: (B, Sq, Hp, hd); k/v: (B, Skv, Hkv, hd); head_map: (Hp,) int32.
    q_pos: (B, Sq) absolute positions of the queries.
    kv_valid: scalar or (B,) — number of valid cache entries.
    kv_pos: optional (B, Skv) absolute positions of the cache slots
    (ring-buffer SWA caches); default is ``arange(Skv)``.  Negative
    positions mark unfilled slots.

    The body is wrapped in named_scope "flash_vmem": on TPU this region is
    served by kernels/flash_attention (scores/softmax state stay in VMEM),
    so the roofline analysis discounts its interior HBM traffic and charges
    the kernel's boundary bytes instead (DESIGN.md §2, hlo_analysis).  A
    custom VJP implements the standard flash backward — scores are
    RECOMPUTED chunk-by-chunk from (q, k, v, o, lse); no per-chunk score
    residual is ever saved (exactly the production flash-kernel contract).
    """
    return _flash(
        q, k, v, head_map, q_pos, jnp.asarray(kv_valid), kv_pos,
        window, bool(bidirectional), int(chunk),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _flash(q, k, v, head_map, q_pos, kv_valid, kv_pos, window, bidirectional, chunk):
    with jax.named_scope("flash_vmem"):
        out, _ = _flash_attention_body(
            q, k, v, head_map, q_pos=q_pos, kv_valid=kv_valid,
            window=window, bidirectional=bidirectional, chunk=chunk,
            kv_pos=kv_pos,
        )
    return out


def _flash_fwd(q, k, v, head_map, q_pos, kv_valid, kv_pos, window, bidirectional, chunk):
    with jax.named_scope("flash_vmem"):
        out, lse = _flash_attention_body(
            q, k, v, head_map, q_pos=q_pos, kv_valid=kv_valid,
            window=window, bidirectional=bidirectional, chunk=chunk,
            kv_pos=kv_pos,
        )
    return out, (q, k, v, head_map, q_pos, kv_valid, out, lse)


def _flash_bwd(window, bidirectional, chunk, res, dout):
    """Flash backward: per-chunk score recomputation from the saved
    log-sum-exp.  Residuals are O(B·S·H·hd) — never the score matrix."""
    q, k, v, head_map, q_pos, kv_valid, out, lse = res
    with jax.named_scope("flash_vmem"):
        B, Sq, Hp, hd = q.shape
        Skv = k.shape[1]
        hkv = k.shape[2]
        rep = Hp // hkv
        ck = int(min(chunk, Skv))
        pad = (-Skv) % ck
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
        n_chunks = (Skv + pad) // ck
        kc = jnp.moveaxis(kp.reshape(B, n_chunks, ck, hkv, hd), 1, 0)
        vc = jnp.moveaxis(vp.reshape(B, n_chunks, ck, hkv, hd), 1, 0)
        scale = 1.0 / np.sqrt(hd)
        # scan partial-eval can hand constant residuals back as Python ints
        kv_valid = jnp.asarray(kv_valid)
        if kv_valid.ndim == 0:
            kv_valid = jnp.broadcast_to(kv_valid, (B,))
        # delta_i = sum_d do_i o_i  (B, Hp, Sq)
        delta = jnp.einsum(
            "bqhd,bqhd->bhq", dout.astype(jnp.float32), out.astype(jnp.float32)
        )

        def body(dq_acc, xs):
            k_i, v_i, c_i = xs
            kpos = (c_i * ck + jnp.arange(ck))[None, None, None, :]
            kh = k_i[:, :, head_map, :]
            vh = v_i[:, :, head_map, :]
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q, kh.astype(q.dtype),
                preferred_element_type=jnp.float32,
            ) * scale
            ok = (kpos >= 0) & (kpos < kv_valid[:, None, None, None])
            if not bidirectional:
                ok &= kpos <= q_pos[:, None, :, None]
            if window > 0:
                ok &= kpos > q_pos[:, None, :, None] - window
            p = jnp.where(ok, jnp.exp(s - lse[..., None]), 0.0)  # (B,Hp,Sq,ck)
            pb = p.astype(q.dtype)
            # dv (per kv head): group-sum over the rep axis.
            dvh = jnp.einsum(
                "bhqk,bqhd->bkhd", pb, dout, preferred_element_type=jnp.float32
            )  # (B, ck, Hp, hd)
            dv_i = dvh.reshape(B, ck, hkv, rep, hd).sum(3)
            dp = jnp.einsum(
                "bqhd,bkhd->bhqk", dout, vh.astype(dout.dtype),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta[..., None]) * scale  # (B,Hp,Sq,ck) f32
            dsb = ds.astype(q.dtype)
            dq_acc = dq_acc + jnp.einsum(
                "bhqk,bkhd->bqhd", dsb, kh.astype(q.dtype),
                preferred_element_type=jnp.float32,
            )
            dkh = jnp.einsum(
                "bhqk,bqhd->bkhd", dsb, q, preferred_element_type=jnp.float32
            )
            dk_i = dkh.reshape(B, ck, hkv, rep, hd).sum(3)
            return dq_acc, (dk_i, dv_i)

        dq0 = jnp.zeros((B, Sq, Hp, hd), jnp.float32)
        dq, (dk_c, dv_c) = jax.lax.scan(
            body, dq0, (kc, vc, jnp.arange(n_chunks))
        )
        dk = jnp.moveaxis(dk_c, 0, 1).reshape(B, Skv + pad, hkv, hd)[:, :Skv]
        dv = jnp.moveaxis(dv_c, 0, 1).reshape(B, Skv + pad, hkv, hd)[:, :Skv]
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        None, None, None, None,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_attention_body(
    q, k, v, head_map, *, q_pos, kv_valid, window, bidirectional, chunk, kv_pos
):
    """Returns (out (B,Sq,Hp,hd_v), lse (B,Hp,Sq) fp32)."""
    B, Sq, Hp, hd = q.shape
    Skv = k.shape[1]
    chunk = int(min(chunk, Skv))
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_pos is not None:
            kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = (Skv + pad) // chunk
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, *k.shape[2:]), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, *v.shape[2:]), 1, 0)
    if kv_pos is not None:
        kpc = jnp.moveaxis(kv_pos.reshape(B, n_chunks, chunk), 1, 0)
    scale = 1.0 / np.sqrt(hd)
    kv_valid = jnp.asarray(kv_valid)
    if kv_valid.ndim == 0:
        kv_valid = jnp.broadcast_to(kv_valid, (B,))

    def body(carry, xs):
        m, l, acc = carry
        if kv_pos is not None:
            k_i, v_i, c_i, kp_i = xs
            kpos = kp_i[:, None, None, :]  # (B,1,1,chunk)
        else:
            k_i, v_i, c_i = xs
            kpos = (c_i * chunk + jnp.arange(chunk))[None, None, None, :]
        kh = k_i[:, :, head_map, :]  # (B, chunk, Hp, hd)
        vh = v_i[:, :, head_map, :]
        # bf16 operands, fp32 MXU accumulation — no f32 copies of q/k/v.
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kh.astype(q.dtype),
            preferred_element_type=jnp.float32,
        ) * scale
        ok = (kpos >= 0) & (kpos < kv_valid[:, None, None, None])
        if not bidirectional:
            ok &= kpos <= q_pos[:, None, :, None]
        if window > 0:
            ok &= kpos > q_pos[:, None, :, None] - window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vh.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hp, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hp, Sq), jnp.float32)
    # values may be narrower than queries and keys (MLA: 128 against 192)
    a0 = jnp.zeros((B, Hp, Sq, v.shape[-1]), jnp.float32)
    xs = (kc, vc, jnp.arange(n_chunks))
    if kv_pos is not None:
        xs = xs + (kpc,)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return jnp.moveaxis(out, 1, 2).astype(q.dtype), lse  # (B, Sq, Hp, hd)


def merge_attention_partials(parts):
    """Combine online-softmax partials [(o_unnorm, m, l), ...] -> output.

    o_unnorm: (B, 1, Hp, hd) f32 = acc (pre-normalisation); m/l (B,Hp)."""
    o_all, m_all, l_all = parts[0]
    for o, m, l in parts[1:]:
        m_new = jnp.maximum(m_all, m)
        c_old = jnp.exp(m_all - m_new)
        c_new = jnp.exp(m - m_new)
        o_all = o_all * c_old[:, None, :, None] + o * c_new[:, None, :, None]
        l_all = l_all * c_old + l * c_new
        m_all = m_new
    return o_all / jnp.maximum(l_all, 1e-30)[:, None, :, None]


def decode_attention(
    q,
    k,
    v,
    *,
    q_pos,
    kv_valid,
    window=0,
    bidirectional=False,
    kv_pos=None,
    return_partials=False,
):
    """Single-token (Sq == 1) attention, GSPMD-native.

    Unlike :func:`flash_attention`, there is no chunk scan and the GQA
    expansion is a *reshape of q* (grouped head layout), never a gather that
    materialises per-q-head KV.  Scores (B, Hkv, rep, Skv) are fp32 and
    reductions run over the (possibly sharded) Skv axis, so a KV cache
    sharded over sequence works under plain jit: XLA inserts one
    all-reduce(max), one all-reduce(sum) and one all-reduce for the output —
    the context-parallel decode pattern (DESIGN.md §6).

    q: (B, 1, Hp, hd); k/v: (B, Skv, Hkv, hd).

    named_scope "decode_attn_vmem": on TPU this region is served by
    kernels/paged_attention (bit-plane KV fetch, VMEM-resident scores); the
    roofline discounts interior traffic and charges q + KV + o boundary
    bytes instead.
    """
    with jax.named_scope("decode_attn_vmem"):
        return _decode_attention_body(
            q, k, v, q_pos=q_pos, kv_valid=kv_valid, window=window,
            bidirectional=bidirectional, kv_pos=kv_pos,
            return_partials=return_partials,
        )


def _decode_attention_body(
    q, k, v, *, q_pos, kv_valid, window, bidirectional, kv_pos,
    return_partials=False,
):
    b, sq, hp, hd = q.shape
    assert sq == 1
    hkv = k.shape[2]
    rep = hp // hkv
    scale = 1.0 / np.sqrt(hd)
    qf = q.reshape(b, hkv, rep, hd)  # bf16 stays bf16; MXU accumulates fp32
    s = jnp.einsum(
        "bkrd,bskd->bkrs", qf, k.astype(qf.dtype),
        preferred_element_type=jnp.float32,
    ) * scale  # (B, Hkv, rep, Skv)
    skv = k.shape[1]
    kpos = kv_pos if kv_pos is not None else jnp.arange(skv, dtype=jnp.int32)[None]
    kv_valid = jnp.asarray(kv_valid)
    if kv_valid.ndim == 0:
        kv_valid = jnp.broadcast_to(kv_valid, (b,))
    ok = (kpos >= 0) & (kpos < kv_valid[:, None])
    if not bidirectional:
        ok &= kpos <= q_pos[:, :1]
    if window > 0:
        ok &= kpos > q_pos[:, :1] - window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1)
    acc = jnp.einsum(
        "bkrs,bskd->bkrd", p.astype(qf.dtype), v.astype(qf.dtype),
        preferred_element_type=jnp.float32,
    )
    if return_partials:
        return (
            acc.reshape(b, 1, hp, hd),
            m.reshape(b, hp),
            l.reshape(b, hp),
        )
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, 1, hp, hd).astype(q.dtype)


def _ring_chunk_append(q, k, v, hm, ck, cv, cpos, *, pos, cache_len,
                       append_valid, window, bidirectional):
    """Ring chunk append (bucketed prefill into a sliding-window slot;
    chunk size <= w, enforced by the serving bucket cap).  The chunk
    attends over [old ring entries] ++ [the chunk itself]: ring slots the
    chunk is about to overwrite are still visible (at their OLD absolute
    kv_pos) to the chunk's early queries, and a slot's old position p and
    its new occupant p + w can never both pass the window mask for one
    query.  Write-back keeps REAL tokens only: a right-padded ragged tail
    must not clobber older in-window ring entries."""
    w = ck.shape[1]
    c = k.shape[1]
    slots = (jnp.asarray(cache_len, jnp.int32) + jnp.arange(c)) % w
    valid_end = (jnp.asarray(append_valid, jnp.int32)
                 if append_valid is not None
                 else jnp.asarray(cache_len + c, jnp.int32))
    k_cat = jnp.concatenate([ck, k.astype(ck.dtype)], axis=1)
    v_cat = jnp.concatenate([cv, v.astype(cv.dtype)], axis=1)
    pos_cat = jnp.concatenate([cpos, pos.astype(cpos.dtype)], axis=1)
    out = flash_attention(
        q, k_cat, v_cat, hm, q_pos=pos, kv_valid=valid_end,
        window=window, bidirectional=bidirectional, kv_pos=pos_cat,
    )
    keep = (cache_len + jnp.arange(c)) < valid_end  # (C,)
    new_k = jnp.where(keep[None, :, None, None],
                      k.astype(ck.dtype), ck[:, slots])
    new_v = jnp.where(keep[None, :, None, None],
                      v.astype(cv.dtype), cv[:, slots])
    new_p = jnp.where(keep[None, :], pos.astype(cpos.dtype),
                      cpos[:, slots])
    ck = ck.at[:, slots].set(new_k)
    cv = cv.at[:, slots].set(new_v)
    cpos = cpos.at[:, slots].set(new_p)
    return out, ck, cv, cpos


def _bitplane_cache_step(q, k, v, hm, cache, *, pos, cache_len, window,
                         bidirectional, append_valid, kv_planes, keeps,
                         decode_kernel="fused"):
    """One step against a bit-plane packed device cache.

    cache: (k_planes, v_planes[, kv_pos]) — per-layer slices, planes
    (bits, B, S/16, 1, W) int32.  kv_planes: (B, S/16) int32 per-device-
    page plane counts (the serving backend pushes the ladder assignment
    here); keeps: static tuple of the distinct plane counts kv_planes may
    hold.  Decode (S == 1) inserts the token and runs the Pallas kernel;
    a prefill chunk (S > 1) attends densely at full precision — unpack,
    run the matching dense/ring append, repack (a page's plane unit packs
    its 16 tokens together, so a chunk that starts mid-page rewrites the
    page's other tokens with their own bits)."""
    ring = len(cache) == 3
    kp, vp = cache[0], cache[1]
    cpos = cache[2] if ring else None
    bits = kp.shape[0]
    c = k.shape[1]
    if c > 1:  # prefill chunk: full-precision dense attend, pack on adoption
        kd = unpack_kv_ref(kp, bits, k.shape[2:], bits)
        vd = unpack_kv_ref(vp, bits, k.shape[2:], bits)
        if ring:
            out, ckd, cvd, cpos = _ring_chunk_append(
                q, k, v, hm, kd, vd, cpos, pos=pos, cache_len=cache_len,
                append_valid=append_valid, window=window,
                bidirectional=bidirectional,
            )
            # scattered ring slots were rewritten: repack the whole window
            return out, (pack_kv_planes(ckd, bits), pack_kv_planes(cvd, bits),
                         cpos)
        ckd = jax.lax.dynamic_update_slice(kd, k.astype(kd.dtype),
                                           (0, cache_len, 0, 0))
        cvd = jax.lax.dynamic_update_slice(vd, v.astype(vd.dtype),
                                           (0, cache_len, 0, 0))
        out = flash_attention(
            q, ckd, cvd, hm, q_pos=pos, kv_valid=cache_len + c,
            window=window, bidirectional=bidirectional,
        )
        return out, (pack_kv_planes(ckd, bits), pack_kv_planes(cvd, bits))
    # decode: insert the token at each row's own position, then the
    # partial-plane kernel (per-slot valid lengths and ladders)
    ln = jnp.asarray(cache_len, jnp.int32)
    if ln.ndim == 0:
        ln = jnp.broadcast_to(ln, (kp.shape[1],))
    rows = jnp.arange(kp.shape[1])
    s_cache = kp.shape[2] * PAGE
    slot = (ln % s_cache) if ring else jnp.clip(ln, 0, s_cache - 1)
    kp = insert_kv_token(kp, k[:, 0], slot)
    vp = insert_kv_token(vp, v[:, 0], slot)
    if ring:
        cpos = cpos.at[rows, slot].set(ln.astype(cpos.dtype))
    out = batched_ladder_paged_attention(
        q, kp, vp, kv_planes, ln + 1,
        keeps=tuple(keeps) if keeps is not None else (bits,),
        kv_heads=k.shape[2], bits=bits, q_pos=pos, kv_pos=cpos,
        window=0 if bidirectional else window,
        kernel=decode_kernel,
    )
    return out.astype(q.dtype), ((kp, vp, cpos) if ring else (kp, vp))


def attn_apply(
    params,
    x,
    cfg,
    *,
    pos,
    cache=None,
    cache_len=None,
    kv_input=None,
    use_rope=True,
    bidirectional=False,
    window=None,
    append_valid=None,
    kv_planes=None,
    keeps=None,
    decode_kernel="fused",
    stage_base=None,
):
    """One attention sub-layer.

    x: (B, S, d).  pos: (B, S) absolute positions.
    cache: optional (k, v) or (k, v, kv_pos), each k/v (B, S_cache, Hkv, hd) —
    decode/prefill-append.  The 3-tuple form is a *ring buffer* (sliding-window
    archs: S_cache == window): new tokens land at slot ``pos % S_cache`` and
    ``kv_pos`` (B, S_cache) records absolute positions (-1 = unfilled).
    cache_len: scalar int32, valid entries already in the cache; a (B,)
    vector selects the continuous-batching per-row append paths (dense AND
    ring caches — each batch row appends at its own position).
    kv_input: cross-attention source (B, S_kv, d) — projects k/v from it and
    ignores the cache-append path when paired with precomputed caches.
    append_valid: optional absolute end of REAL appended tokens for the ring
    chunk-append path (S > 1 into a ring cache): a ragged prefill chunk
    arrives right-padded to its bucket, and in a ring the pad rows would
    *overwrite* older in-window entries, so the write-back keeps only
    positions < ``append_valid`` (dense caches don't need this — pad rows
    land past the true length and the next chunk/decode overwrites them).
    kv_planes/keeps: per-device-page ladder plane map + its static value
    set, for bit-plane packed caches (int32 plane tuples — see
    :func:`_bitplane_cache_step`); ignored for dense caches.
    decode_kernel: "fused" | "rung" — Pallas strategy for bit-plane decode
    (one plane-gathering launch vs one launch per ladder rung).
    stage_base: optional (B,) int32 — per-row staging base for a 4-tuple
    staged cache under continuous batching: row i's main cache holds
    [0, stage_base[i]) and its staging ring holds [stage_base[i],
    cache_len[i]].  Required whenever cache_len is per-row and the cache
    is staged (the scalar staged path derives it as ``cache_len % ws``).
    Returns (y, new_cache) — with cache=None, new_cache is the freshly
    projected (k, v) pair (post-rope), which prefill uses to build the cache.
    """
    window = cfg.attn_window if window is None else window
    hp = params["wq"].shape[1]
    hm = head_map_static(hp, cfg.n_heads, cfg.n_kv_heads)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    src = x if kv_input is None else kv_input
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
    if use_rope:
        cos_q, sin_q = rope_angles(pos, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)
        if kv_input is None:
            k = apply_rope(k, cos_q, sin_q)

    if cache is None:
        kv_valid = pos[:, -1] + 1 if not bidirectional else k.shape[1]
        out = flash_attention(
            q, k, v, hm, q_pos=pos, kv_valid=kv_valid,
            window=window, bidirectional=bidirectional,
        )
        new_cache = (k, v)
    elif cache[0].dtype == jnp.int32:
        # bit-plane packed device cache (serving device_kv='bitplane')
        out, new_cache = _bitplane_cache_step(
            q, k, v, hm, cache, pos=pos, cache_len=cache_len,
            window=window, bidirectional=bidirectional,
            append_valid=append_valid, kv_planes=kv_planes, keeps=keeps,
            decode_kernel=decode_kernel,
        )
    elif len(cache) == 4 and stage_base is not None and \
            getattr(cache_len, "ndim", 0) == 1:
        # Staged decode under continuous batching (ISSUE 6 satellite): the
        # big cache is read-only this step; row i's token lands in its
        # staging-ring slot ``cache_len[i] - stage_base[i]`` and rows whose
        # ring just filled fold it back into the main cache in one scatter.
        # Mid-prefill rows arrive with stage_base == cache_len (the
        # scheduler anchors staging at the prefill end), so their dummy
        # token lands at staging slot 0 and — like the dense per-row path —
        # is masked for every real query and overwritten later.
        ck, cv, sk, sv = cache
        ws = sk.shape[1]
        rows = jnp.arange(ck.shape[0])
        staged_n = cache_len - stage_base  # (B,) in [0, ws)
        slot = jnp.clip(staged_n, 0, ws - 1)
        sk = sk.at[rows, slot].set(k[:, 0].astype(sk.dtype))
        sv = sv.at[rows, slot].set(v[:, 0].astype(sv.dtype))
        stage_pos = stage_base[:, None] + jnp.arange(ws, dtype=jnp.int32)[None]
        parts = [
            decode_attention(
                q, ck, cv, q_pos=pos, kv_valid=stage_base,
                window=window, bidirectional=bidirectional,
                return_partials=True,
            ),
            # stale ring slots from the previous window sit at stage_pos >=
            # cache_len + 1 and mask out; so do idle rows (stage_base == 0).
            decode_attention(
                q, sk, sv, q_pos=pos, kv_valid=cache_len + 1,
                window=window, bidirectional=bidirectional,
                kv_pos=stage_pos, return_partials=True,
            ),
        ]
        out = merge_attention_partials(parts).astype(q.dtype)
        flush = staged_n + 1 == ws  # ring full after this append
        idx = jnp.clip(stage_pos, 0, ck.shape[1] - 1)  # (B, ws)
        ck = ck.at[rows[:, None], idx].set(
            jnp.where(flush[:, None, None, None], sk.astype(ck.dtype),
                      ck[rows[:, None], idx]))
        cv = cv.at[rows[:, None], idx].set(
            jnp.where(flush[:, None, None, None], sv.astype(cv.dtype),
                      cv[rows[:, None], idx]))
        new_cache = (ck, cv, sk, sv)
    elif len(cache) == 4:
        # Staged decode cache (§Perf Cell-3): the big cache (ck, cv) is
        # READ-ONLY this step — the new token lands in a small staging ring
        # (sk, sv), and a separate amortised flush folds staging into the
        # main cache every `ws` steps.  Eliminates the per-step masked
        # rewrite of the full sequence-sharded cache shard.
        ck, cv, sk, sv = cache
        ws = sk.shape[1]
        staged_n = cache_len % ws
        sk = jax.lax.dynamic_update_slice(sk, k.astype(sk.dtype), (0, staged_n, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, v.astype(sv.dtype), (0, staged_n, 0, 0))
        big_valid = cache_len - staged_n
        stage_pos = big_valid + jnp.arange(ws, dtype=jnp.int32)[None]
        parts = [
            decode_attention(
                q, ck, cv, q_pos=pos, kv_valid=big_valid,
                window=window, bidirectional=bidirectional,
                return_partials=True,
            ),
            decode_attention(
                q, sk, sv, q_pos=pos, kv_valid=cache_len + x.shape[1],
                window=window, bidirectional=bidirectional,
                kv_pos=stage_pos, return_partials=True,
            ),
        ]
        out = merge_attention_partials(parts).astype(q.dtype)
        new_cache = (ck, cv, sk, sv)
    elif len(cache) == 3:
        ck, cv, cpos = cache
        w = ck.shape[1]
        if x.shape[1] > 1:
            out, ck, cv, cpos = _ring_chunk_append(
                q, k, v, hm, ck, cv, cpos, pos=pos, cache_len=cache_len,
                append_valid=append_valid, window=window,
                bidirectional=bidirectional,
            )
        elif getattr(cache_len, "ndim", 0) == 1:
            # Continuous batching on a ring cache: per-row lengths (B,) —
            # each row appends at its own slot ``len % w``; same dummy-row
            # contract as the dense per-slot path below (garbage lands at
            # the row's own next position and is overwritten by its next
            # chunk/decode, masked for every real query meanwhile).
            rows = jnp.arange(ck.shape[0])
            slot = cache_len % w
            ck = ck.at[rows, slot].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[rows, slot].set(v[:, 0].astype(cv.dtype))
            cpos = cpos.at[rows, slot].set(cache_len.astype(cpos.dtype))
        else:
            # Ring-buffer append (S == 1 decode steps, aligned batch).
            slot = cache_len % w
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
            cpos = jax.lax.dynamic_update_slice(
                cpos, jnp.broadcast_to(cache_len, (cpos.shape[0], 1)).astype(cpos.dtype),
                (0, slot),
            )
        if x.shape[1] == 1:
            out = decode_attention(
                q, ck, cv, q_pos=pos, kv_valid=cache_len + 1,
                window=window, bidirectional=bidirectional, kv_pos=cpos,
            )
        new_cache = (ck, cv, cpos)
    elif getattr(cache_len, "ndim", 0) == 1:
        # Continuous batching: per-sequence cache lengths (B,).  Each batch
        # row appends its token at its own slot; kv_valid is per-row, so
        # retired/empty slots simply mask to nothing.  Decode (S == 1) only.
        #
        # Contract with chunked prefill (lm_prefill_chunk): a row that is
        # still mid-prefill participates in this batched append with a dummy
        # token — its garbage k/v lands exactly at row cache_len[i] ==
        # prefill_pos, which the NEXT prefill chunk overwrites (chunks cover
        # [pos, pos+C)), and no other row can read it because attention is
        # row-independent and kv_valid masks it for every real query.  The
        # chunk path itself reuses the scalar prefill-append branch below on
        # a one-row slice of this cache.
        assert x.shape[1] == 1, "per-slot cache lengths are a decode-only path"
        ck, cv = cache
        rows = jnp.arange(ck.shape[0])
        slot = jnp.clip(cache_len, 0, ck.shape[1] - 1)
        ck = ck.at[rows, slot].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[rows, slot].set(v[:, 0].astype(cv.dtype))
        out = decode_attention(
            q, ck, cv, q_pos=pos, kv_valid=cache_len + 1,
            window=window, bidirectional=bidirectional,
        )
        new_cache = (ck, cv)
    else:
        ck, cv = cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_len, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_len, 0, 0))
        kv_valid = cache_len + x.shape[1]
        if x.shape[1] == 1:
            out = decode_attention(
                q, ck, cv, q_pos=pos, kv_valid=kv_valid,
                window=window, bidirectional=bidirectional,
            )
        else:
            out = flash_attention(
                q, ck, cv, hm, q_pos=pos, kv_valid=kv_valid,
                window=window, bidirectional=bidirectional,
            )
        new_cache = (ck, cv)

    if hp != cfg.n_heads:  # mask padded heads (exactness + zero grads)
        valid = jnp.asarray(valid_q_heads(hp, cfg.n_heads, cfg.n_kv_heads), out.dtype)
        out = out * valid[None, None, :, None]
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (HF ``yarn_get_mscale``)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """(qk_rope_head_dim // 2,) rotary frequencies of an MLA model.  With
    YaRN (``yarn_factor`` > 1, HF ``DeepseekV2YarnRotaryEmbedding``) the
    dims that turn fewer than ``yarn_beta_slow`` times over the original
    context are slowed by the factor, those that turn more than
    ``yarn_beta_fast`` times keep their frequency, and a linear ramp joins
    the two."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = cfg.yarn_factor
    if factor <= 1:
        return extra.astype(np.float32)

    def dim_of(rotations):
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def mla_softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times YaRN's ``mscale_all_dim`` factor
    squared (HF ``DeepseekV2Attention.softmax_scale``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def mla_rope(x, pos, cfg):
    """Rotate x (B, S, H, qk_rope_head_dim) at positions pos (B, S) as HF
    DeepSeek-V2 does: the interleaved pairs are first regrouped, evens then
    odds, and the two halves rotate (``rotate_half``), with cos and sin
    scaled by ``mscale / mscale_all_dim``."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(yarn_inv_freq(cfg))
    m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, jnp.cos(ang) * m, jnp.sin(ang) * m)


def mla_params(key, cfg, dtype):
    """q: d -> H x (nope + rope) (no q LoRA); kv_a: d -> kv_lora_rank +
    rope, with an RMSNorm on the rank part; kv_b: rank -> H x (nope + v),
    held as its key half ``wk_b`` and value half ``wv_b``; o: H x v -> d."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, pe, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": he_init(ks[0], (d, h, nope + pe), dtype, fan_in=d),
        "wkv_a": he_init(ks[1], (d, r + pe), dtype, fan_in=d),
        "kv_norm": rmsnorm_params(r, dtype),
        "wk_b": he_init(ks[2], (r, h, nope), dtype, fan_in=r),
        "wv_b": he_init(ks[3], (r, h, vd), dtype, fan_in=r),
        "wo": he_init(ks[4], (h, vd, d), dtype, fan_in=h * vd),
    }


def mla_apply(params, x, cfg, *, pos, cache=None, cache_len=None):
    """One MLA sub-layer.  x: (B, S, d); pos: (B, S).

    The cache is latent: one ``c_kv`` row (kv_lora_rank, after the kv_a
    RMSNorm) and one rotated ``k_pe`` row (qk_rope_head_dim) per token,
    shared by every head.  ``cache`` is (c_kv (B, S_cache, r), k_pe (B,
    S_cache, rope)), read only: rows [0, cache_len) are valid.

    Returns (y (B, S, d), (c_kv, k_pe) of the S new tokens); the caller
    writes the new rows into its cache.  A decode step (S == 1 with a
    cache) attends in absorbed form over the latent rows; a prefill chunk
    (scalar ``cache_len``) or a cacheless sequence decompresses them.
    """
    with jax.named_scope("mla_attention"):
        nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        q_nope = q[..., :nope]
        q_pe = mla_rope(q[..., nope:], pos, cfg)
        kv = jnp.einsum("bsd,dk->bsk", x, params["wkv_a"])
        c = rmsnorm(kv[..., :r], params["kv_norm"], cfg.norm_eps)
        k_pe = mla_rope(kv[..., None, r:], pos, cfg)[..., 0, :]
        scale = mla_softmax_scale(cfg)
        if cache is not None and x.shape[1] == 1:
            o = _mla_absorbed(params, q_nope[:, 0], q_pe[:, 0], c[:, 0],
                              k_pe[:, 0], cache, cache_len, scale)[:, None]
        else:
            o = _mla_decompressed(params, q_nope, q_pe, c, k_pe, cache,
                                  cache_len, pos, scale)
        y = jnp.einsum("bshv,hvd->bsd", o, params["wo"])
    return y, (c, k_pe)


def _mla_absorbed(params, q_nope, q_pe, c, k_pe, cache, cache_len, scale):
    """One token per row against its latent rows, with W_UK folded into the
    query and W_UV into the output, so the step reads only the latent rows:
    score = (q_nope W_UK) . c_kv + q_pe . k_pe, out = (p . c_kv) W_UV.

    q_nope (B, H, nope), q_pe (B, H, rope); c (B, r) and k_pe (B, rope) are
    the token's own rows, which take part beside the cache's rows [0,
    cache_len) (B,) without being written into them.  Returns (B, H, v)."""
    ck, cp = cache
    dt = ck.dtype
    f32 = jnp.float32
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, params["wk_b"],
                       preferred_element_type=f32).astype(dt)
    q_pe = q_pe.astype(dt)
    c, k_pe = c.astype(dt), k_pe.astype(dt)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ck, preferred_element_type=f32)
         + jnp.einsum("bhp,bsp->bhs", q_pe, cp, preferred_element_type=f32))
    s_self = (jnp.einsum("bhr,br->bh", q_lat, c, preferred_element_type=f32)
              + jnp.einsum("bhp,bp->bh", q_pe, k_pe,
                           preferred_element_type=f32))
    ok = jnp.arange(ck.shape[1])[None, :] < cache_len[:, None]  # (B, S)
    s = jnp.where(ok[:, None, :], s * scale, NEG_INF)
    s_self = s_self * scale
    m = jnp.maximum(s.max(axis=-1), s_self)  # (B, H)
    p = jnp.exp(s - m[..., None])
    p_self = jnp.exp(s_self - m)
    l = p.sum(axis=-1) + p_self
    ctx = (jnp.einsum("bhs,bsr->bhr", p.astype(dt), ck,
                      preferred_element_type=f32)
           + p_self[..., None] * c.astype(f32)[:, None, :])
    ctx = (ctx / l[..., None]).astype(dt)
    return jnp.einsum("bhr,rhv->bhv", ctx, params["wv_b"])


def _mla_decompressed(params, q_nope, q_pe, c, k_pe, cache, cache_len, pos,
                      scale):
    """Naive MLA: the new rows join the cache's (at ``cache_len``) or stand
    alone; every row decompresses into per-head keys [c W_UK, k_pe] and
    values c W_UV, and the queries attend causally (flash_attention).
    Returns (B, S, H, v)."""
    if cache is None:
        c_all, pe_all, kv_valid = c, k_pe, pos[:, -1] + 1
    else:
        ck, cp = cache
        c_all = jax.lax.dynamic_update_slice(ck, c.astype(ck.dtype),
                                             (0, cache_len, 0))
        pe_all = jax.lax.dynamic_update_slice(cp, k_pe.astype(cp.dtype),
                                              (0, cache_len, 0))
        kv_valid = cache_len + c.shape[1]
    k_nope = jnp.einsum("bsr,rhn->bshn", c_all, params["wk_b"])
    v = jnp.einsum("bsr,rhv->bshv", c_all, params["wv_b"])
    pe_h = jnp.broadcast_to(pe_all[:, :, None, :].astype(k_nope.dtype),
                            k_nope.shape[:3] + pe_all.shape[-1:])
    k = jnp.concatenate([k_nope, pe_h], axis=-1)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
    # flash_attention scales scores by 1/sqrt(head dim): fold the rest of
    # the MLA scale into the queries
    q = (q.astype(jnp.float32) * (scale * math.sqrt(q.shape[-1]))).astype(
        q_nope.dtype)
    hm = jnp.arange(q.shape[2], dtype=jnp.int32)
    return flash_attention(q, k, v, hm, q_pos=pos, kv_valid=kv_valid)
