"""DeepSeek-V2's latent attention (MLA) and its expert layer at one chip's
share, at tiny sizes on the CPU, against the plain float32 reference
``bench/reference/deepseek_v2.py`` and against HF's formulas.

The configuration is ``TINY_DSV2`` of ``bench/test_arch_deepseek_v2.py``:
3 layers (one dense, two with experts), MLA with rank 32 and rope 8, 4 of
16 routed experts held, top-3, 2 shared experts, YaRN at factor 40.  The program runs in
float32 here, so it and the reference differ only by the order of float32
sums: the 1e-4 tolerance on logits of unit scale is about 15 times the
widest difference seen (6e-6), and a wrong rotation, scale, gate or
cache row moves logits by tenths.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.arch import make_weights
from bench.reference import deepseek_v2 as reference
from bench.test_arch_deepseek_v2 import DSV2, dsv2_config
from repro.models import attention, moe
from repro.models.model import build_model
from repro.serving import ContinuousScheduler, EngineConfig, Request
from repro.serving import scheduler as scheduler_mod
from repro.telemetry import steps


def _tiny(dtype="float32", **changes):
    cfg = dsv2_config("tiny")
    cfg["serving"]["dtype"] = dtype
    cfg.update(changes)
    arch = DSV2.parse(cfg)
    return arch, build_model(DSV2.model_config(cfg, arch))


def _engine(**kw):
    kw.setdefault("device_kv", "dense")
    return EngineConfig(max_batch=3, max_ctx=128, backend="paged",
                        prefix_sharing=False, weight_stream="resident", **kw)


def _requests(lens):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=m) for i, (n, m) in enumerate(lens)]


def test_served_path_matches_the_reference_logits(monkeypatch):
    """Chunked prefill (a 45-token prompt takes a 32- and a padded
    16-bucket chunk) and then batched decode through the latent cache, in
    three slots with a fourth request joining a freed one: every decode
    step's logits equal the reference's full forward at that position."""
    arch, model = _tiny()
    w = make_weights(DSV2, arch, 0)
    sched = ContinuousScheduler(model, w, _engine())
    seen = []
    real = scheduler_mod.sample_slots

    def spy(keys, draws, logits, cfg):
        seen.append({i: (s.req.rid, int(sched._lens[i]),
                         np.asarray(logits[i]))
                     for i, s in enumerate(sched._slots)
                     if s is not None and not s.prefilling})
        return real(keys, draws, logits, cfg)

    monkeypatch.setattr(scheduler_mod, "sample_slots", spy)
    reqs = _requests([(20, 6), (45, 9), (33, 5), (60, 4)])
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    compared = 0
    for r in reqs:
        assert len(r.output) == r.max_new_tokens
        tokens = np.concatenate([r.prompt, np.asarray(r.output, np.int32)])
        want = reference.logits(w, arch, tokens)
        for step in seen:
            for rid, pos, got in step.values():
                if rid == r.rid:
                    np.testing.assert_allclose(got[:256], want[pos],
                                               atol=1e-4)
                    compared += 1
    assert compared == sum(r.max_new_tokens for r in reqs)


def test_absorbed_decode_matches_naive_mla():
    """One token per row over latent rows of different lengths: W_UK
    folded into the query and W_UV into the output give what decompressing
    every row into per-head keys and values gives."""
    _, model = _tiny()
    cfg = model.cfg
    lp = jax.tree.map(lambda a: a[1], model.init(jax.random.PRNGKey(1))[
        "layers"])["attn"]
    rng = np.random.default_rng(2)
    b, s = 3, 24
    lens = np.array([5, 17, 0], np.int32)
    cache = (jnp.asarray(rng.normal(size=(b, s, cfg.kv_lora_rank)),
                         jnp.float32),
             jnp.asarray(rng.normal(size=(b, s, cfg.qk_rope_head_dim)),
                         jnp.float32))
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    pos = jnp.asarray(lens)[:, None]
    got, rows = attention.mla_apply(lp, x, cfg, pos=pos, cache=cache,
                                    cache_len=jnp.asarray(lens))
    for i in range(b):
        # the naive path: the row's token written at its position, every
        # row decompressed, causal attention
        one = tuple(c[i:i + 1] for c in cache)
        want, _ = attention.mla_apply(
            lp, jnp.concatenate([x[i:i + 1]] * 2, axis=1), cfg,
            pos=jnp.asarray([[lens[i], lens[i] + 1]]), cache=one,
            cache_len=int(lens[i]))
        np.testing.assert_allclose(got[i, 0], want[0, 0], atol=1e-5)
    assert rows[0].shape == (b, 1, cfg.kv_lora_rank)


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips of 4 experts each: their partial outputs, with the shared
    experts (which every chip computes alike) counted once, add up to the
    layer that holds all 16; every pick lands on exactly one share; and
    the uncut layer is the reference's expert layer."""
    _, model = _tiny()
    cfg = model.cfg
    uncut = dataclasses.replace(cfg, n_experts=16, held_experts=())
    params = moe.moe_params(jax.random.PRNGKey(3), uncut, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 7, 64)),
                    jnp.float32)
    whole, whole_picks = moe.moe_share_apply(params, x, uncut)
    shared = moe.mlp_apply(params["shared"], x, "swiglu")
    total, picks = shared, 0
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        part = dict(params, **{n: params[n][4 * chip:4 * chip + 4]
                               for n in ("w_gate", "w_in", "w_out")})
        out, p = moe.moe_share_apply(
            part, x, dataclasses.replace(cfg, held_experts=held))
        total = total + (out - shared)
        picks = picks + p
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert (np.asarray(picks) == np.asarray(whole_picks)).all()
    assert (np.asarray(whole_picks) == 7 * 3).all()  # every pick is held
    arch = DSV2.parse(dict(dsv2_config("tiny"), n_routed_experts=16))
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda t: reference._moe(t, params, arch, None))(x)
    np.testing.assert_allclose(whole, want, atol=1e-5)


def _hf_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
             mscale_all_dim, nope):
    """HF ``modeling_deepseek.py``: ``DeepseekV2YarnRotaryEmbedding`` and
    ``DeepseekV2Attention.softmax_scale``, transcribed in float64."""
    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    cos_factor = get_mscale(factor, mscale) / get_mscale(factor,
                                                         mscale_all_dim)
    m = get_mscale(factor, mscale_all_dim)
    return inv_freq, cos_factor, (nope + dim) ** -0.5 * m * m


def test_yarn_frequencies_and_softmax_scale_are_hfs():
    cfg = dsv2_config("published")
    arch = DSV2.parse(cfg)
    mc = DSV2.model_config(cfg, arch)
    inv_freq, cos_factor, scale = _hf_yarn(64, 10000.0, 40.0, 4096, 32.0,
                                           1.0, 0.707, 0.707, 128)
    np.testing.assert_allclose(attention.yarn_inv_freq(mc), inv_freq,
                               rtol=1e-6)
    theta = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv_freq[:11], theta[:11])
    np.testing.assert_allclose(inv_freq[23:], theta[23:] / 40)
    assert cos_factor == 1.0
    assert attention.mla_softmax_scale(mc) == pytest.approx(scale,
                                                            rel=1e-12)
    assert scale == pytest.approx(0.11472, abs=5e-6)
    ref_freq, ref_factor, ref_scale = reference.rope_tables(arch)
    np.testing.assert_allclose(ref_freq, inv_freq, rtol=1e-12)
    assert (ref_factor, ref_scale) == (cos_factor, pytest.approx(scale))


def test_latent_cache_refuses_bitplane_and_sharded():
    arch, model = _tiny("bfloat16")
    w = make_weights(DSV2, arch, 0)
    with pytest.raises(ValueError, match="latent .MLA. cache"):
        ContinuousScheduler(model, w, _engine(device_kv="bitplane"))
    with pytest.raises(NotImplementedError, match="latent .MLA. cache"):
        ContinuousScheduler(model, w, dataclasses.replace(
            _engine(), backend="sharded"))


def test_held_picks_count_only_decoding_rows():
    """With every routed expert held, each decoded token's rows send
    top-k picks per expert layer to held experts, so the step log's
    ``moe_held_picks`` is decoded x layers x k, whatever the idle and
    mid-prefill rows of the batch routed."""
    arch, model = _tiny("bfloat16", n_routed_experts=16)
    sched = ContinuousScheduler(model, make_weights(DSV2, arch, 5),
                                _engine())
    first = len(steps.LOG.records)
    for r in _requests([(20, 6), (45, 9), (33, 5), (60, 4)]):
        sched.submit(r)
    sched.run_until_drained()
    recs = list(steps.LOG.records)[first:]
    decoded = sum(r["decoded"] for r in recs)
    assert decoded == 6 + 9 + 5 + 4
    assert sum(r["moe_held_picks"] for r in recs) == decoded * 2 * 3
