"""Plain float32 forward of a DeepSeek-V2 decoder (HF ``modeling_deepseek.py``,
``DeepseekV2ForCausalLM``) at one chip's share of its routed experts.

Per layer: RMSNorm; multi-head latent attention, computed the naive way
(every token's latent row decompresses into per-head keys and values, and
the queries attend over them); RMSNorm; then a silu-gated MLP in the first
``first_k_dense_replace`` layers and DeepSeekMoE in the rest; then a final
RMSNorm and the output head.

- Attention: ``q = x W_q`` per head is split into a no-rope part (128) and
  a rope part (64); ``x W_kv_a`` gives the latent ``c_kv`` (512), which goes
  through its own RMSNorm, and one rope key ``k_pe`` (64) shared by every
  head.  Keys are ``[c_kv W_UK, k_pe]`` and values ``c_kv W_UV`` per head.
  Rope rotates the de-interleaved pairs (evens first, then odds), as HF
  does before ``rotate_half``, at YaRN's frequencies, computed here from
  the configuration's ``rope_scaling``.  Scores scale by ``(q_nope +
  q_rope)^-0.5 * mscale(factor, mscale_all_dim)^2``.
- Expert layer: softmax over all ``n_routed_experts`` router logits,
  greedy top-k, gates renormalised only under ``norm_topk_prob``, times
  ``routed_scaling_factor``.  Each token's output is the gate-weighted sum
  of the silu-gated experts it picked, plus the shared experts.

Departures from HF, all stated by the configuration:

- the routed experts are this chip's share (ids 0 .. held - 1): a pick of
  an absent expert adds nothing, as on one chip of the deployment without
  its exchange of expert outputs;
- ``kv_b_proj`` is given as its two halves, ``wk_b`` (rank, heads, nope)
  and ``wv_b`` (rank, heads, v), and every weight as ``bench.arch`` draws
  it from the seed;
- everything is float32, where HF rounds the normalised ``c_kv`` to the
  input dtype before its scale;
- the auxiliary balance loss (``seq_aux``) plays no part in a forward.

It imports nothing of the serving program.  Every matmul runs at
``default_matmul_precision("highest")``.  The sequence runs in one jitted
call at a padded length, layer by layer and in blocks of query rows.
``quant="fp8"`` is the control: every matmul's operands, the router's
included, go through float8_e4m3 with one scale per tensor.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: query rows per attention and head block
Q_BLOCK = 256
#: float8_e4m3's largest finite value
FP8_MAX = 448.0


def _fake_fp8(x):
    import jax.numpy as jnp

    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, quant):
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    return jnp.einsum(eq, x, w)


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp

    x = x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))
    return x * scale.astype(jnp.float32)


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(arch):
    """(inv_freq (rope/2,), cos/sin factor, softmax scale), by HF's
    ``DeepseekV2YarnRotaryEmbedding`` and ``DeepseekV2Attention``."""
    dim, base = arch.qk_rope_head_dim, arch.rope_theta
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    scale = (arch.qk_nope_head_dim + dim) ** -0.5
    if not arch.yarn:
        return freq_extra, 1.0, scale
    factor, orig, beta_fast, beta_slow, mscale, mscale_all = arch.yarn
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def correction_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    m = (_yarn_get_mscale(factor, mscale)
         / _yarn_get_mscale(factor, mscale_all))
    if mscale_all:
        scale *= _yarn_get_mscale(factor, mscale_all) ** 2
    return inv_freq, m, scale


def _rope(x, pos, inv_freq, m):
    """x (T, H, d): de-interleave, then rotate the two halves."""
    import jax.numpy as jnp

    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq,
                                                         jnp.float32)
    cos = (jnp.cos(ang) * m)[:, None]
    sin = (jnp.sin(ang) * m)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(x, a, pos, n_valid, arch, quant):
    """x (T, d) normalised; a: one layer's attention weights."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    nope, r = arch.qk_nope_head_dim, arch.kv_lora_rank
    inv_freq, m, scale = rope_tables(arch)
    q = _mm("td,dhk->thk", x, a["wq"], quant)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos, inv_freq, m)], -1)
    kv = _mm("td,dk->tk", x, a["wkv_a"], quant)
    c = _rmsnorm(kv[:, :r], a["kv_norm"]["scale"], arch.norm_eps)
    k_pe = _rope(kv[:, None, r:], pos, inv_freq, m)  # (T, 1, rope)
    k_nope = _mm("tr,rhn->thn", c, a["wk_b"], quant)
    v = _mm("tr,rhv->thv", c, a["wv_b"], quant)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:2] + k_pe.shape[-1:])], -1)
    col = jnp.arange(t)

    def block(b):
        r0 = b * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(q, r0, Q_BLOCK)
        row = r0 + jnp.arange(Q_BLOCK)
        ok = (col[None, :] <= row[:, None]) & (col[None, :] < n_valid)
        s = jnp.einsum("qhk,thk->qht", qb, k) * scale
        s = jnp.where(ok[:, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)  # padding rows see no key
        return jnp.einsum("qht,thv->qhv", p, v)

    o = jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(
        t, arch.n_heads, arch.v_head_dim)
    return _mm("thv,hvd->td", o, a["wo"], quant)


def _mlp(x, w, quant):
    import jax

    g = jax.nn.silu(_mm("td,df->tf", x, w["w_gate"], quant))
    return _mm("tf,fd->td", g * _mm("td,df->tf", x, w["w_in"], quant),
               w["w_out"], quant)


def _moe(x, w, arch, quant):
    """One expert layer at this chip's share: experts 0 .. held - 1."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(_mm("td,de->te", x, w["router"], quant), axis=-1)
    gate, idx = jax.lax.top_k(probs, arch.top_k)
    if arch.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * arch.routed_scaling_factor
    held = jnp.arange(arch.n_held)
    weight = jnp.sum(jnp.where(idx[:, :, None] == held, gate[:, :, None],
                               0.0), axis=1)  # (T, held)
    g = jax.nn.silu(_mm("td,edf->tef", x, w["w_gate"], quant))
    h = g * _mm("td,edf->tef", x, w["w_in"], quant) * weight[..., None]
    y = _mm("tef,efd->td", h, w["w_out"], quant)
    if "shared" in w:
        y = y + _mlp(x, w["shared"], quant)
    return y


def _forward(w, tokens, n_valid, *, arch, quant):
    """Final hidden states (T, d) float32 of the padded sequence."""
    import jax
    import jax.numpy as jnp

    pos = jnp.arange(tokens.shape[0])
    x = w["embed"]["table"][tokens].astype(jnp.float32)
    eps, k = arch.norm_eps, arch.n_dense_layers

    def attend(x, lw):
        h = _rmsnorm(x, lw["ln1"]["scale"], eps)
        x = x + _mla(h, lw["attn"], pos, n_valid, arch, quant)
        return x, _rmsnorm(x, lw["ln2"]["scale"], eps)

    for i in range(k):
        lw = jax.tree.map(lambda a, i=i: a[i], w["layers"])
        x, h = attend(x, lw)
        x = x + _mlp(h, jax.tree.map(lambda a, i=i: a[i], w["dense"]["mlp"]),
                     quant)

    def moe_layer(x, lws):
        lw, mw = lws
        x, h = attend(x, lw)
        return x + _moe(h, mw, arch, quant), None

    if arch.n_layers > k:
        rest = jax.tree.map(lambda a: a[k:], w["layers"])
        x, _ = jax.lax.scan(moe_layer, x, (rest, w["moe"]))
    return _rmsnorm(x, w["final_norm"]["scale"], eps)


def _logits_blocks(w, x, quant, fn):
    """Apply ``fn(logits_block, row0)`` to (Q_BLOCK, V) float32 logits of
    each block of rows; stacks the per-row results."""
    import jax
    import jax.numpy as jnp

    head = w["lm_head"]["w"] if "lm_head" in w else w["embed"]["table"]

    def block(b):
        xb = jax.lax.dynamic_slice_in_dim(x, b * Q_BLOCK, Q_BLOCK)
        return fn(_mm("td,vd->tv", xb, head, quant), b * Q_BLOCK)

    out = jax.lax.map(block, jnp.arange(x.shape[0] // Q_BLOCK))
    return out.reshape((x.shape[0],) + out.shape[2:])


@functools.lru_cache(maxsize=None)
def _gap_fn(arch, control: bool):
    import jax
    import jax.numpy as jnp

    def gaps(w, tokens, n_valid, targets):
        """Per row: how far below the float32 reference's best logit lies
        the served token (``targets``), or, for the control, the token the
        fp8 forward puts first."""
        x = _forward(w, tokens, n_valid, arch=arch, quant=None)
        if control:
            xc = _forward(w, tokens, n_valid, arch=arch, quant="fp8")
            targets = _logits_blocks(w, xc, "fp8",
                                     lambda lg, r0: jnp.argmax(lg, axis=1))

        def below(lg, r0):
            tgt = jax.lax.dynamic_slice_in_dim(targets, r0, Q_BLOCK)
            at = jnp.take_along_axis(lg, tgt[:, None], 1)[:, 0]
            return lg.max(axis=1) - at
        return _logits_blocks(w, x, None, below)

    return jax.jit(gaps)


def _padded(tokens: np.ndarray, pad_to: int):
    n = len(tokens)
    t = -(-n // pad_to) * pad_to
    tok = np.zeros(t, np.int32)
    tok[:n] = tokens
    return tok


def logit_gaps(w, arch, tokens: np.ndarray, n_prompt: int,
               keep_rows=None, levels=(16,), control: bool = False,
               pad_to: int = 1024) -> np.ndarray:
    """Gaps of the served tokens ``tokens[n_prompt:]`` (or, with
    ``control``, of the fp8 forward's own first choices) below the float32
    reference's best logit, one per served token.  The latent cache is
    dense, so ``keep_rows`` must be None and ``levels`` full precision."""
    import jax
    import jax.numpy as jnp

    if keep_rows is not None or tuple(levels) != (16,):
        raise ValueError("a latent (MLA) cache has no bit-plane layout")
    n = len(tokens)
    tok = _padded(tokens, pad_to)
    targets = np.zeros(len(tok), np.int32)
    targets[:n - 1] = tokens[1:]
    with jax.default_matmul_precision("highest"):
        g = _gap_fn(arch, control)(w, jnp.asarray(tok), jnp.int32(n),
                                   jnp.asarray(targets))
    # row i predicts token i + 1: the served tokens are rows
    # n_prompt - 1 .. n - 2
    return np.asarray(g)[n_prompt - 1:n - 1]


def logits(w, arch, tokens: np.ndarray, pad_to: int = Q_BLOCK) -> np.ndarray:
    """(len(tokens), vocab) float32 logits of the full forward, for tests
    at small sizes."""
    import jax
    import jax.numpy as jnp

    n = len(tokens)
    tok = jnp.asarray(_padded(tokens, pad_to))
    with jax.default_matmul_precision("highest"):
        x = _forward(w, tok, jnp.int32(n), arch=arch, quant=None)
        lg = _logits_blocks(w, x, None, lambda lg, r0: lg)
    return np.asarray(lg)[:n]
