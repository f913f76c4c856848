"""Decoder-only LM assembly (dense / MoE / VLM families).

Layer params are stacked (leading depth axis) and the stack is a single
``jax.lax.scan`` (+ optional remat), so HLO size is depth-independent.

Loss uses *chunked* cross-entropy: logits are produced and reduced in
sequence chunks inside a scan so the (B, S, vocab) tensor never
materialises — with a 256 k vocab (Nemotron) that tensor would be tens of
GB per device at train_4k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import attn_apply, attn_params, mla_apply, mla_params
from repro.models.layers import (
    embed_apply,
    embed_params,
    he_init,
    lm_head_params,
    mlp_apply,
    mlp_params,
    pdtype,
    rmsnorm,
    rmsnorm_params,
)
from repro.models.moe import moe_apply, moe_params, moe_share_apply
from repro.models.frontends import VISION_DIM


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stack_layer_params(cfg, key, init_one):
    """vmap a single-layer initializer over stacked per-layer keys."""
    keys = jax.random.split(key, cfg.n_layers)
    return jax.vmap(init_one)(keys)


def init_lm_params(cfg, key):
    if cfg.kv_lora_rank:
        return _init_mla_lm_params(cfg, key)
    dtype = pdtype(cfg)
    k_embed, k_layers, k_head, k_patch = jax.random.split(key, 4)

    def one_layer(k):
        ka, km = jax.random.split(k)
        p = {
            "ln1": rmsnorm_params(cfg.d_model, dtype),
            "attn": attn_params(ka, cfg, dtype),
            "ln2": rmsnorm_params(cfg.d_model, dtype),
        }
        if cfg.family == "moe":
            p["moe"] = moe_params(km, cfg, dtype)
        else:
            p["mlp"] = mlp_params(km, cfg.d_model, cfg.d_ff, cfg.act, dtype)
        return p

    params = {
        "embed": embed_params(k_embed, cfg.vocab_padded, cfg.d_model, dtype),
        "layers": _stack_layer_params(cfg, k_layers, one_layer),
        "final_norm": rmsnorm_params(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = lm_head_params(k_head, cfg.vocab_padded, cfg.d_model, dtype)
    if cfg.family == "vlm":
        params["patch_proj"] = he_init(k_patch, (VISION_DIM, cfg.d_model), dtype)
    return params


def _init_mla_lm_params(cfg, key):
    """Latent-attention stack (DeepSeek-V2): MLA and its norms in every
    layer (``layers``), then a silu-gated MLP in each of the first
    ``n_dense_layers`` (``dense``) and the expert layer at this chip's
    share in the rest (``moe``)."""
    dtype = pdtype(cfg)
    d, k = cfg.d_model, cfg.n_dense_layers
    k_embed, k_layers, k_dense, k_moe, k_head = jax.random.split(key, 5)

    def stack(key, n, init_one):
        return jax.vmap(init_one)(jax.random.split(key, n))

    params = {
        "embed": embed_params(k_embed, cfg.vocab_padded, d, dtype),
        "layers": stack(k_layers, cfg.n_layers, lambda kk: {
            "ln1": rmsnorm_params(d, dtype),
            "attn": mla_params(kk, cfg, dtype),
            "ln2": rmsnorm_params(d, dtype),
        }),
        "final_norm": rmsnorm_params(d, dtype),
    }
    if k:
        params["dense"] = {"mlp": stack(k_dense, k, lambda kk: mlp_params(
            kk, d, cfg.dense_d_ff, "swiglu", dtype))}
    if cfg.n_layers > k:
        params["moe"] = stack(k_moe, cfg.n_layers - k,
                              lambda kk: moe_params(kk, cfg, dtype))
    if not cfg.tie_embeddings:
        params["lm_head"] = lm_head_params(k_head, cfg.vocab_padded, d, dtype)
    return params


def head_weight(params):
    return params.get("lm_head", {"w": params["embed"]["table"]})["w"]


def split_layer_params(params):
    """Per-layer weight handles: views into the stacked ``params["layers"]``
    pytree, one pytree per transformer layer.  The weight-streaming
    subsystem ingests these (per-layer per-tensor blocks) instead of the
    monolithic resident pytree; ``run_stack`` keeps scanning the stacked
    form, so handles are zero-copy slices, not a second residency."""
    layers = params["layers"]
    n = jax.tree_util.tree_leaves(layers)[0].shape[0]
    return [
        jax.tree_util.tree_map(lambda a, i=i: a[i], layers) for i in range(n)
    ]


def join_layer_params(handles):
    """Inverse of :func:`split_layer_params` — restack per-layer handles
    into the scan-ready ``params["layers"]`` pytree (round-trip tests)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *handles)


def named_layer_tensors(handle):
    """Flatten one layer handle to ``(path_string, leaf)`` pairs — stable
    tensor names ("attn/wq", "mlp/w1", ...) for the weight store."""
    flat, _ = jax.tree_util.tree_flatten_with_path(handle)
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
        out.append(("/".join(parts), leaf))
    return out


# ---------------------------------------------------------------------------
# Layer body + stack
# ---------------------------------------------------------------------------


def _layer_seq(lp, x, cfg, pos, cache_kv, cache_len, want_cache,
               append_valid=None, kv_planes=None, keeps=None,
               decode_kernel="fused", stage_base=None):
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    attn_out, new_kv = attn_apply(
        lp["attn"], h, cfg, pos=pos, cache=cache_kv, cache_len=cache_len,
        append_valid=append_valid, kv_planes=kv_planes, keeps=keeps,
        decode_kernel=decode_kernel, stage_base=stage_base,
    )
    x = x + attn_out
    h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        inference = want_cache or cache_kv is not None  # prefill/decode
        ffn_out, aux = moe_apply(lp["moe"], h2, cfg, inference=inference)
    else:
        ffn_out, aux = mlp_apply(lp["mlp"], h2, cfg.act), jnp.float32(0)
    x = x + ffn_out
    if not want_cache:
        new_kv = None
    return x, new_kv, aux


def _mla_stack(params, cfg, x, pos, cache=None, cache_len=None, slot=None):
    """One scan over every layer of a latent-attention model: MLA, then the
    layer's dense MLP (the first ``n_dense_layers``) or its expert layer,
    picked by ``lax.cond`` on the layer index.  Every layer's cache rows
    are scanned in place, so the stacked cache is never sliced or joined
    around the dense layers.

    cache: (c_kv, k_pe) stacked (L, B, S_cache, .), read only; with
    ``slot`` each layer attends over that one batch row's rows.  Returns
    (x, (c_kv, k_pe) of x's tokens stacked (L, B_x, S_x, .), (B_x,) int32
    (token, pick) pairs routed to held experts, summed over the layers)."""
    k = cfg.n_dense_layers
    n_moe = cfg.n_layers - k
    no_picks = jnp.zeros(x.shape[0], jnp.int32)

    def at(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            tree)

    def dense(i, h):
        mlp = at(params["dense"]["mlp"], jnp.minimum(i, k - 1))
        return mlp_apply(mlp, h, "swiglu"), no_picks

    def moe(i, h):
        return moe_share_apply(at(params["moe"], jnp.maximum(i - k, 0)), h,
                               cfg)

    def ffn(i, h):
        if not k:
            return moe(i, h)
        if not n_moe:
            return dense(i, h)
        return jax.lax.cond(i < k, dense, moe, i, h)

    def body(carry, xs):
        x, picks = carry
        lp, i, *kv = xs
        if kv and slot is not None:
            kv = [jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)
                  for a in kv]
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, rows = mla_apply(lp["attn"], h, cfg, pos=pos,
                            cache=tuple(kv) or None, cache_len=cache_len)
        x = x + a
        y, p = ffn(i, rmsnorm(x, lp["ln2"], cfg.norm_eps))
        return (x + y, picks + p), rows

    xs = (params["layers"], jnp.arange(cfg.n_layers))
    if cache is not None:
        xs = xs + tuple(cache)
    (x, picks), rows = jax.lax.scan(body, (x, no_picks), xs)
    return x, rows, picks


def run_stack(params, cfg, x, pos, cache=None, want_cache=False, remat=None,
              keeps=None, decode_kernel="fused"):
    """x: (B, S, d). cache: {'k','v'} stacked (L, B, Smax, Hkv, hd) + 'len'
    [+ 'pos' (L, B, Smax) for sliding-window ring caches; + 'valid' (scalar,
    not per-layer) = absolute end of real appended tokens for a ring chunk
    append — see ``attn_apply(append_valid=...)``; + 'sbase' (B,) int32
    per-row staging bases for staged caches under continuous batching —
    shared across layers like 'valid', see ``attn_apply(stage_base=...)``].
    ``decode_kernel`` picks the bit-plane decode strategy ("fused"|"rung").

    Bit-plane serving caches carry {'k_planes','v_planes'} stacked
    (L, bits, B, Smax/16, 1, W) int32 in place of {'k','v'}, plus a
    'planes' map (B, Smax/16) int32 that is shared across layers (the
    serving ladder ranks on the last layer and applies everywhere, so it is
    closed over, not scanned); ``keeps`` is that map's static value set.

    Latent-attention models (``cfg.kv_lora_rank``) run a cacheless
    sequence here (:func:`_mla_stack`); their decode and prefill chunks go
    through :func:`lm_decode` and :func:`lm_prefill_chunk`.

    Returns (x_final, new_cache_stack_or_None, aux_sum).
    """
    if cfg.kv_lora_rank:
        assert cache is None, "latent caches decode through lm_decode"
        x, (c, pe), _ = _mla_stack(params, cfg, x, pos)
        return x, ({"k": c, "v": pe} if want_cache else None), jnp.float32(0)
    remat = cfg.remat if remat is None else remat
    append_valid = None
    if cache is not None and "valid" in cache:
        append_valid = cache["valid"]
        cache = {k: v for k, v in cache.items() if k != "valid"}
    stage_base = None
    if cache is not None and "sbase" in cache:
        stage_base = cache["sbase"]
        cache = {k: v for k, v in cache.items() if k != "sbase"}
    cache_len = cache["len"] if cache is not None else jnp.int32(0)
    bitplane = cache is not None and "k_planes" in cache
    kv_planes = cache.get("planes") if bitplane else None
    ring = cache is not None and "pos" in cache
    staged = cache is not None and "sk" in cache

    def body(carry, xs):
        x, aux_acc = carry
        if cache is not None:
            lp, *kv = xs
            kv = tuple(kv)
        else:
            lp = xs
            kv = None
        x, new_kv, aux = _layer_seq(lp, x, cfg, pos, kv, cache_len,
                                    want_cache or cache is not None,
                                    append_valid=append_valid,
                                    kv_planes=kv_planes, keeps=keeps,
                                    decode_kernel=decode_kernel,
                                    stage_base=stage_base)
        ys = new_kv if (want_cache or cache is not None) else None
        return (x, aux_acc + aux), ys

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    if cache is None:
        xs = params["layers"]
    elif bitplane:
        xs = (params["layers"], cache["k_planes"], cache["v_planes"])
        if ring:
            xs = xs + (cache["pos"],)
    elif staged:
        xs = (params["layers"], cache["k"], cache["v"], cache["sk"], cache["sv"])
    elif ring:
        xs = (params["layers"], cache["k"], cache["v"], cache["pos"])
    else:
        xs = (params["layers"], cache["k"], cache["v"])
    (x, aux), kv_stack = jax.lax.scan(body, (x, jnp.float32(0)), xs)
    new_cache = None
    if kv_stack is not None:
        if bitplane:
            names = ("k_planes", "v_planes", "pos")
            new_cache = dict(zip(names, kv_stack))
            if kv_planes is not None:
                new_cache["planes"] = kv_planes
        elif len(kv_stack) == 4:
            ks, vs, sks, svs = kv_stack
            new_cache = {"k": ks, "v": vs, "sk": sks, "sv": svs}
        elif len(kv_stack) == 3:
            ks, vs, ps = kv_stack
            new_cache = {"k": ks, "v": vs, "pos": ps}
        else:
            ks, vs = kv_stack
            new_cache = {"k": ks, "v": vs}
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Embedding front (+ VLM patch prepend)
# ---------------------------------------------------------------------------


def embed_inputs(params, cfg, batch):
    """Returns (x (B, S, d), pos (B, S))."""
    tokens = batch["tokens"]
    x = embed_apply(params["embed"], tokens)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([patches, x], axis=1)
    b, s = x.shape[0], x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, pos


# ---------------------------------------------------------------------------
# Chunked cross-entropy
# ---------------------------------------------------------------------------


def chunked_ce(x_final, head_w, labels, vocab_real, chunk=1024):
    """Mean CE without materialising (B, S, V). labels -1 = ignore."""
    b, s, d = x_final.shape
    chunk = int(min(chunk, s))
    pad = (-s) % chunk
    if pad:
        x_final = jnp.pad(x_final, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (s + pad) // chunk
    xc = jnp.moveaxis(x_final.reshape(b, nc, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0)
    vpad = head_w.shape[0]
    vmask = (jnp.arange(vpad) < vocab_real)[None, None, :]

    def body(carry, xs):
        nll_sum, count = carry
        xi, li = xs
        logits = jnp.einsum("bcd,vd->bcv", xi, head_w).astype(jnp.float32)
        logits = jnp.where(vmask, logits, -1e30)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
        valid = (li >= 0).astype(jnp.float32)
        nll_sum = nll_sum + ((logz - gold) * valid).sum()
        count = count + valid.sum()
        return (nll_sum, count), None

    (nll, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (xc, lc))
    return nll / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def lm_loss(params, cfg, batch):
    """batch: tokens (B,S_text), labels (B,S_text) [, patches (B,P,VISION_DIM)].

    VLM: patch positions are prepended and excluded from the loss.
    """
    x, pos = embed_inputs(params, cfg, batch)
    x, _, aux = run_stack(params, cfg, x, pos)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    labels = batch["labels"]
    if cfg.family == "vlm":
        p = x.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], p), -1, labels.dtype), labels], axis=1
        )
    loss = chunked_ce(x, head_weight(params), labels, cfg.vocab)
    return loss + 0.01 * aux


def lm_prefill(params, cfg, batch):
    """Returns (last-token logits (B, Vpad), cache)."""
    x, pos = embed_inputs(params, cfg, batch)
    x, cache, _ = run_stack(params, cfg, x, pos, want_cache=True, remat=False)
    x_last = rmsnorm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x_last, head_weight(params))[:, 0]
    cache["len"] = jnp.int32(x.shape[1])
    return logits.astype(jnp.float32), cache


def lm_prefill_chunk(params, cfg, tokens, cache, slot, start, last_idx):
    """Bucketed chunked prefill: append one prompt chunk into one slot's
    rows of the serving batch cache (continuous batching, ISSUE 3).

    tokens: (1, C) int32 — C is a power-of-two bucket size, so the serving
    scheduler compiles at most ``log2(max_ctx)`` prefill variants instead of
    one per distinct prompt length.  A ragged final chunk arrives
    right-padded to its bucket; the pad tokens sit at positions *after*
    every real token, so causal masking keeps them out of all real rows'
    attention, and the scheduler's true ``cache["len"]`` keeps decode from
    ever attending to them.

    cache: the batch cache {"k","v": (L, B, Smax, Hkv, hd), "len": (B,)}.
    slot / start / last_idx: traced scalars — the slot row, the absolute
    position of ``tokens[0]``, and the chunk-local index of the last *real*
    token (C-1 except on a padded final chunk).  Tracing them means one
    compile covers every slot/offset/length at a given bucket size.

    Returns ``(logits (1, Vpad) at last_idx, cache)`` with rows
    [start, start+C) of ``slot`` replaced and everything else untouched —
    the chunk attends to the slot's rows [0, start) (flash prefill-append
    path in models/attention), so interleaving chunks with batched decode
    steps of *other* slots is safe.

    Ring caches (sliding-window archs: ``cache`` carries 'pos') take the
    ring chunk-append path instead: the chunk's tokens land at slots
    ``pos % window`` of the slot's ring, the chunk attends over the old
    ring entries plus itself under the window mask, and only REAL tokens
    are written back (``cache['valid']`` = start + last_idx + 1), so a
    ragged tail's pad can never clobber older in-window entries.  The
    serving scheduler caps bucket sizes at the window for this path.
    """
    if cfg.kv_lora_rank:
        return _mla_prefill_chunk(params, cfg, tokens, cache, slot, start,
                                  last_idx)
    ring = "pos" in cache
    bitplane = "k_planes" in cache
    # bit-plane caches stack as (L, bits, B, S, ...): the slot axis moves
    kn, vn, slot_ax = (("k_planes", "v_planes", 2) if bitplane
                       else ("k", "v", 1))
    ksl = jax.lax.dynamic_slice_in_dim(cache[kn], slot, 1, axis=slot_ax)
    vsl = jax.lax.dynamic_slice_in_dim(cache[vn], slot, 1, axis=slot_ax)
    x = embed_apply(params["embed"], tokens)
    c = x.shape[1]
    start = jnp.asarray(start, jnp.int32)
    pos = start + jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (1, c))
    sub = {kn: ksl, vn: vsl, "len": start}
    if ring:
        sub["pos"] = jax.lax.dynamic_slice_in_dim(cache["pos"], slot, 1, axis=1)
        sub["valid"] = start + jnp.asarray(last_idx, jnp.int32) + 1
    x, new_kv, _ = run_stack(params, cfg, x, pos, cache=sub, remat=False)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    x_last = rmsnorm(x_last, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x_last, head_weight(params))[:, 0]
    out = {
        **cache,
        kn: jax.lax.dynamic_update_slice_in_dim(
            cache[kn], new_kv[kn], slot, axis=slot_ax),
        vn: jax.lax.dynamic_update_slice_in_dim(
            cache[vn], new_kv[vn], slot, axis=slot_ax),
    }
    if ring:
        out["pos"] = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], new_kv["pos"], slot, axis=1
        )
    return logits.astype(jnp.float32), out


def _mla_prefill_chunk(params, cfg, tokens, cache, slot, start, last_idx):
    """:func:`lm_prefill_chunk` on a latent cache: the chunk attends over
    the slot's rows and its new rows land at [start, start + C)."""
    x = embed_apply(params["embed"], tokens)
    c = x.shape[1]
    start = jnp.asarray(start, jnp.int32)
    pos = start + jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (1, c))
    x, rows, _ = _mla_stack(params, cfg, x, pos, (cache["k"], cache["v"]),
                            start, slot=slot)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    x_last = rmsnorm(x_last, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x_last, head_weight(params))[:, 0]
    out = dict(cache)
    for name, new in zip(("k", "v"), rows):  # new: (L, 1, C, .)
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], new.astype(cache[name].dtype), (0, slot, start, 0))
    return logits.astype(jnp.float32), out


def _mla_decode(params, cfg, token, cache):
    """:func:`lm_decode` on a latent cache.  The layers read the cache
    without writing it; after the last layer each row's new latent rows of
    every layer are written at its own position, one dynamic-update-slice
    per row and stream (a scatter of all rows at once makes the TPU
    compiler relayout the whole cache twice).  ``moe_held_picks`` in the
    returned cache holds each row's (token, pick) pairs routed to held
    experts this step."""
    x = embed_apply(params["embed"], token[:, None])
    b = x.shape[0]
    ln = jnp.asarray(cache["len"], jnp.int32)
    if ln.ndim == 0:
        ln = jnp.broadcast_to(ln, (b,))
    x, rows, picks = _mla_stack(params, cfg, x, ln[:, None],
                                (cache["k"], cache["v"]), ln)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, head_weight(params))[:, 0]
    at = jnp.clip(ln, 0, cache["k"].shape[2] - 1)
    new_cache = dict(cache, len=cache["len"] + 1)
    for name, new in zip(("k", "v"), rows):  # new: (L, B, 1, .)
        c = cache[name]
        new = new.astype(c.dtype)
        for i in range(b):
            c = jax.lax.dynamic_update_slice(c, new[:, i:i + 1],
                                             (0, i, at[i], 0))
        new_cache[name] = c
    if "moe_held_picks" in cache:
        new_cache["moe_held_picks"] = picks
    return logits.astype(jnp.float32), new_cache


def lm_decode(params, cfg, token, cache, keeps=None, decode_kernel="fused"):
    """token: (B,) int32; cache from prefill or init_decode_cache.

    ``cache["len"]`` may be a scalar (aligned batch) or a (B,) vector of
    per-sequence lengths (continuous batching — each slot decodes at its own
    position against its own valid prefix; dense and ring caches both take
    per-row append paths in models/attention).

    Bit-plane caches ({'k_planes','v_planes','planes'}) additionally take
    ``keeps`` — the static set of plane counts the serving ladder can
    assign — and run decode attention through a Pallas partial-plane kernel
    instead of the dense einsum path; ``decode_kernel`` picks the strategy
    ("fused" = one plane-gathering launch, "rung" = one launch per plane
    count).

    A staged cache with a per-row 'sbase' (continuous batching) advances
    each row's staging base here when its ring filled and was folded back.

    A latent cache (``cfg.kv_lora_rank``) takes :func:`_mla_decode`.

    Returns (logits (B, Vpad), new cache).
    """
    if cfg.kv_lora_rank:
        return _mla_decode(params, cfg, token, cache)
    x = embed_apply(params["embed"], token[:, None])
    ln = jnp.asarray(cache["len"], jnp.int32)
    if ln.ndim == 1:
        pos = ln[:, None]
    else:
        pos = jnp.broadcast_to(ln, (x.shape[0], 1)).astype(jnp.int32)
    x, new_cache, _ = run_stack(params, cfg, x, pos, cache=cache, remat=False,
                                keeps=keeps, decode_kernel=decode_kernel)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, head_weight(params))[:, 0]
    new_cache["len"] = cache["len"] + 1
    if "sbase" in cache:
        ws = cache["sk"].shape[2]
        staged_n = ln - cache["sbase"]
        new_cache["sbase"] = cache["sbase"] + jnp.where(
            (staged_n >= 0) & (staged_n + 1 == ws), ws, 0)
    return logits.astype(jnp.float32), new_cache


def kv_row_shapes(cfg) -> dict:
    """One token's row in each cache stream of one layer: GQA's K and V
    are (n_kv_heads, head_dim); a latent (MLA) cache's "k" stream carries
    ``c_kv`` (kv_lora_rank,) and its "v" stream the rotated ``k_pe``
    (qk_rope_head_dim,)."""
    if cfg.kv_lora_rank:
        return {"k": (cfg.kv_lora_rank,), "v": (cfg.qk_rope_head_dim,)}
    row = (cfg.n_kv_heads, cfg.head_dim)
    return {"k": row, "v": row}


def init_decode_cache(cfg, batch, max_len, dtype=None):
    """Sliding-window archs get a ring buffer of the window size (the cache
    for a ``max_len`` context is bounded by the window — Mixtral's SWA is
    exactly why its ``long_500k`` cell is feasible).  A latent-attention
    model's cache holds its latent rows (:func:`kv_row_shapes`), plus
    ``moe_held_picks`` (B,) int32 that each decode step returns where the
    model has expert layers."""
    dtype = dtype or pdtype(cfg)
    if cfg.kv_lora_rank:
        cache = {name: jnp.zeros((cfg.n_layers, batch, max_len) + row, dtype)
                 for name, row in kv_row_shapes(cfg).items()}
        cache["len"] = jnp.int32(0)
        if cfg.n_layers > cfg.n_dense_layers:
            cache["moe_held_picks"] = jnp.zeros(batch, jnp.int32)
        return cache
    ring = 0 < cfg.attn_window < max_len
    s_cache = cfg.attn_window if ring else max_len
    shape = (cfg.n_layers, batch, s_cache, cfg.n_kv_heads, cfg.head_dim)
    cache = {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.int32(0),
    }
    if ring:
        cache["pos"] = jnp.full((cfg.n_layers, batch, s_cache), -1, jnp.int32)
    elif cfg.decode_staging > 0:
        ws = cfg.decode_staging
        sshape = (cfg.n_layers, batch, ws, cfg.n_kv_heads, cfg.head_dim)
        cache["sk"] = jnp.zeros(sshape, dtype)
        cache["sv"] = jnp.zeros(sshape, dtype)
    return cache


def flush_staging(cache, cfg):
    """Fold the staging ring into the main cache (run every
    ``cfg.decode_staging`` steps by the serving engine; one DUS of ws
    tokens per layer — amortised cost ~1/ws of a full-cache rewrite)."""
    ws = cache["sk"].shape[2]
    # at a flush boundary (len % ws == 0) the ring holds ws entries
    staged_n = ((cache["len"] - 1) % ws) + 1
    start = cache["len"] - staged_n
    k = jax.lax.dynamic_update_slice(
        cache["k"], cache["sk"].astype(cache["k"].dtype), (0, 0, start, 0, 0)
    )
    v = jax.lax.dynamic_update_slice(
        cache["v"], cache["sv"].astype(cache["v"].dtype), (0, 0, start, 0, 0)
    )
    return {**cache, "k": k, "v": v,
            "sk": jnp.zeros_like(cache["sk"]), "sv": jnp.zeros_like(cache["sv"])}


def bitplane_cache_from_dense(cache, bits: int = 16):
    """Convert a dense serving cache {'k','v'[,'pos'],...} into the
    bit-plane device layout: {'k_planes','v_planes'} stacked
    (L, bits, B, S/16, 1, W) int32 (``kernels/paged_attention/ref.py``)
    plus a per-device-page 'planes' map (B, S/16) int32, initialised to
    full precision (the serving ladder re-ranks it per slot).  Packing is
    a bf16 bitcast — an all-zero dense cache packs to all-zero planes, and
    a populated one round-trips bit-exactly at keep == bits.  S must be a
    whole number of 16-token pages (ValueError otherwise)."""
    from repro.kernels.paged_attention.ops import pack_kv_planes
    from repro.kernels.paged_attention.ref import PAGE

    b, s = cache["k"].shape[1:3]
    if s % PAGE:
        raise ValueError(
            f"bit-plane caches need a whole number of {PAGE}-token pages, "
            f"got S={s}"
        )
    out = {k: v for k, v in cache.items() if k not in ("k", "v")}
    # (L, B, S, Hkv, hd) -> (bits, L, B, S/16, 1, W) -> (L, bits, ...)
    out["k_planes"] = jnp.moveaxis(pack_kv_planes(cache["k"], bits), 0, 1)
    out["v_planes"] = jnp.moveaxis(pack_kv_planes(cache["v"], bits), 0, 1)
    out["planes"] = jnp.full((b, s // PAGE), bits, jnp.int32)
    return out


def ring_cache_from_prefill(cache, cfg, max_len):
    """Convert a full-length prefill cache {'k','v','len'} for decoding up to
    ``max_len`` total context.  Sliding-window archs get a ring buffer of the
    window size holding the last ``window`` prefill tokens at slots
    ``pos % window``; full-attention archs get the sequence axis padded."""
    s = cache["k"].shape[2]
    w = cfg.attn_window
    if not (0 < w < max_len):
        out = dict(cache)
        if s < max_len:
            pad = ((0, 0), (0, 0), (0, max_len - s)) + ((0, 0),) * (
                cache["k"].ndim - 3)
            out["k"] = jnp.pad(cache["k"], pad)
            out["v"] = jnp.pad(cache["v"], pad)
        if cfg.decode_staging > 0 and "sk" not in out:
            l, b = out["k"].shape[0], out["k"].shape[1]
            sshape = (l, b, cfg.decode_staging, cfg.n_kv_heads, cfg.head_dim)
            out["sk"] = jnp.zeros(sshape, out["k"].dtype)
            out["sv"] = jnp.zeros(sshape, out["v"].dtype)
        return out
    keep = min(s, w)
    k_tail = cache["k"][:, :, s - keep :]
    v_tail = cache["v"][:, :, s - keep :]
    ln = cache["len"]
    abs_pos = ln - keep + jnp.arange(keep, dtype=jnp.int32)
    slots = abs_pos % w
    l, b = k_tail.shape[0], k_tail.shape[1]
    shape = (l, b, w) + k_tail.shape[3:]
    k = jnp.zeros(shape, k_tail.dtype).at[:, :, slots].set(k_tail)
    v = jnp.zeros(shape, v_tail.dtype).at[:, :, slots].set(v_tail)
    pos = jnp.full((l, b, w), -1, jnp.int32).at[:, :, slots].set(
        jnp.broadcast_to(abs_pos, (l, b, keep))
    )
    return {"k": k, "v": v, "pos": pos, "len": cache["len"]}
