"""Mixture-of-Experts layer: top-k routing with sort-based static dispatch.

Dispatch strategy (TPU-friendly, all static shapes):
  1. router softmax -> top-k (expert_idx, gate) per token;
  2. flatten (token, k) assignments, stable-sort by expert id;
  3. rank-within-expert via exclusive-cumsum of expert counts;
  4. tokens with rank >= capacity are dropped (GShard-style capacity factor);
  5. scatter surviving tokens into an (E, C, d) buffer, batched expert
     matmuls (einsum over the expert axis), gather-add back weighted by the
     gate.

Sharding: experts shard over the ``model`` axis when E is divisible by it
(``expert_shard='ep'``, DeepSeekMoE's 64 experts), otherwise the expert FFN
dim shards (``'tp'``, Mixtral's 8 experts).  The scatter/gather become
all-to-all-class collectives under pjit.

Shared experts (DeepSeekMoE) are plain always-on MLPs added to the routed
output.

:func:`moe_share_apply` is the expert layer of one chip of an
expert-parallel deployment: the router keeps its published width
(``n_routed_experts``) and top-k, and the chip computes the part of the
result its ``held_experts`` give.  Picks of absent experts add nothing
here; on the deployment the exchange of expert outputs brings them in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import he_init, mlp_apply, mlp_params


def moe_params(key, cfg, dtype):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": he_init(ks[0], (d, cfg.n_routed_experts or e), dtype),
        "w_gate": he_init(ks[1], (e, d, ff), dtype, fan_in=d),
        "w_in": he_init(ks[2], (e, d, ff), dtype, fan_in=d),
        "w_out": he_init(ks[3], (e, ff, d), dtype, fan_in=ff),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_params(
            ks[4], d, ff * cfg.n_shared_experts, "swiglu", dtype
        )
    return p


def capacity(n_tokens: int, cfg, inference: bool = False) -> int:
    """Train: GShard capacity factor (dropping acts as a regularizer).
    Inference at small token counts: DROPLESS (capacity = n_tokens — an
    expert can receive at most one slot per token), so serving results are
    independent of batch composition.  Very large inference dispatches
    (32k-token prefills) fall back to a generous 2× capacity — the paper's
    serving regime, documented in DESIGN.md §8."""
    if inference:
        if n_tokens * cfg.n_experts <= (1 << 22):
            return n_tokens
        return max(8, int(n_tokens * cfg.moe_top_k / cfg.n_experts * 2.0 + 0.999))
    ideal = n_tokens * cfg.moe_top_k / cfg.n_experts
    return max(8, int(ideal * cfg.capacity_factor + 0.999))


def moe_apply(params, x, cfg, inference: bool = False):
    """x: (B, S, d) -> (B, S, d). Aux losses returned for load balancing.

    Dispatch is PER BATCH ROW (vmapped): each row's sort/scatter stays
    local to its data shard, so GSPMD never all-reduces dispatch buffers
    across the data axis — the fix for the §Perf Cell-1 finding where flat
    B·S dispatch cost 4 GB-per-layer buffer all-reduces (EXPERIMENTS §Perf,
    hypothesis 2).  Capacity is per-row (how real systems provision)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    c = capacity(s, cfg, inference)

    def row(xt):  # (S, d) -> ((S, d), aux)
        t = xt.shape[0]
        logits = (xt @ params["router"]).astype(jnp.float32)  # router fp32
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_idx = jax.lax.top_k(probs, k)  # (t, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        flat_e = expert_idx.reshape(-1)  # (t*k,)
        flat_t = jnp.arange(t * k, dtype=jnp.int32) // k
        flat_g = gate.reshape(-1)

        order = jnp.argsort(flat_e, stable=True)
        e_sorted = flat_e[order]
        t_sorted = flat_t[order]
        g_sorted = flat_g[order]

        counts = jnp.bincount(flat_e, length=e)  # (e,)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(t * k, dtype=jnp.int32) - starts[e_sorted]
        keep = rank < c

        slot = jnp.where(keep, e_sorted * c + rank, e * c)  # overflow row
        buf = jnp.zeros((e * c + 1, d), xt.dtype)
        buf = buf.at[slot].set(xt[t_sorted] * keep[:, None].astype(xt.dtype))
        h_in = buf[: e * c].reshape(e, c, d)

        gh = jnp.einsum("ecd,edf->ecf", h_in, params["w_gate"])
        hh = jnp.einsum("ecd,edf->ecf", h_in, params["w_in"])
        act = jax.nn.silu(gh) * hh
        y_exp = jnp.einsum("ecf,efd->ecd", act, params["w_out"])

        y_flat = jnp.concatenate(
            [y_exp.reshape(e * c, d), jnp.zeros((1, d), xt.dtype)]
        )
        y_tok = y_flat[slot] * (g_sorted * keep)[:, None].astype(xt.dtype)
        out = jnp.zeros((t, d), xt.dtype).at[t_sorted].add(y_tok)

        # Load-balance aux loss (Switch-style): E * sum_e f_e * p_e.
        frac_tokens = counts.astype(jnp.float32) / (t * k)
        aux = e * jnp.sum(frac_tokens * probs.mean(axis=0))
        return out, aux

    out, aux = jax.vmap(row)(x)
    if cfg.n_shared_experts:
        out = out + mlp_apply(params["shared"], x, "swiglu")
    return out, aux.mean()


def moe_share_apply(params, x, cfg):
    """The expert layer at this chip's share.  x: (B, S, d) ->
    ((B, S, d), (B,) int32 count of each row's (token, pick) pairs routed
    to a held expert).

    The router scores all ``n_routed_experts`` (softmax in float32, then
    greedy top-k); gates are renormalised over the k picks only where
    ``norm_topk_prob`` holds, then scaled by ``routed_scaling_factor``.
    Every held expert runs on every token and its output is weighted by
    the token's gate for it, which is zero where the token did not pick
    it: dropless, and at decode bound by the held experts' weight bytes
    either way.  The shared experts run on every token."""
    k = cfg.moe_top_k
    with jax.named_scope("moe_router"):
        logits = jnp.einsum("bsd,de->bse", x, params["router"],
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)  # (B, S, k)
        if cfg.norm_topk_prob:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        gate = gate * cfg.routed_scaling_factor
        held = jnp.asarray(cfg.held_experts or range(cfg.n_experts),
                           jnp.int32)
        hit = idx[..., None] == held  # (B, S, k, e)
        w = jnp.sum(jnp.where(hit, gate[..., None], 0.0), axis=2)  # (B,S,e)
        picks = hit.sum(axis=(1, 2, 3), dtype=jnp.int32)
    with jax.named_scope("moe_experts"):
        g = jnp.einsum("bsd,edf->bsef", x, params["w_gate"])
        u = jnp.einsum("bsd,edf->bsef", x, params["w_in"])
        h = jax.nn.silu(g) * u * w[..., None].astype(x.dtype)
        out = jnp.einsum("bsef,efd->bsd", h, params["w_out"])
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            out = out + mlp_apply(params["shared"], x, "swiglu")
    return out, picks
