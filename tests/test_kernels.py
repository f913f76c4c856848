"""Per-kernel shape/dtype sweeps against the pure-jnp ref oracles
(interpret=True executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.core.bitplane import BF16, FP8_E4M3, disaggregate_np, reaggregate_np


def _bf16(rng, *shape, scale=1.0):
    return jnp.asarray(rng.normal(0, scale, shape).astype(ml_dtypes.bfloat16))


# ---------------------------------------------------------------- bitplane
class TestBitplaneKernel:
    @pytest.mark.parametrize("bits,nblocks", [(16, 1), (16, 3), (8, 2), (32, 1)])
    def test_pack_matches_numpy(self, bits, nblocks, rng):
        from repro.kernels.bitplane import kernel as K

        m = 8 * 4096 * nblocks
        u = rng.integers(0, 2**min(bits, 31), m).astype(np.uint32)
        got = np.asarray(K.pack(jnp.asarray(u), bits))
        dt = np.uint8 if bits == 8 else (np.uint16 if bits == 16 else np.uint32)
        want = disaggregate_np(u.astype(dt), bits)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("keep", [16, 12, 8, 3, 1])
    def test_unpack_partial(self, keep, rng):
        from repro.kernels.bitplane import kernel as K

        u = rng.integers(0, 2**16, 8 * 4096).astype(np.uint32)
        planes = K.pack(jnp.asarray(u), 16)
        got = np.asarray(K.unpack(planes, 16, keep))
        want = reaggregate_np(np.asarray(planes), 16, keep)
        np.testing.assert_array_equal(got.astype(np.uint16), want)

    def test_ops_value_roundtrip(self, rng):
        from repro.kernels.bitplane import ops

        for spec, dt in ((BF16, ml_dtypes.bfloat16), (FP8_E4M3, ml_dtypes.float8_e4m3fn)):
            x = jnp.asarray(rng.normal(0, 0.1, (777,)).astype(dt))
            planes, n = ops.pack(x, spec)
            back = ops.unpack(planes, spec, x.shape)
            np.testing.assert_array_equal(
                np.asarray(back).view(np.uint8), np.asarray(x).view(np.uint8)
            )


# ---------------------------------------------------------------- exp_delta
class TestExpDeltaKernel:
    @pytest.mark.parametrize("spec", [BF16, FP8_E4M3])
    @pytest.mark.parametrize("c,g", [(256, 16), (300, 8), (64, 4)])
    def test_matches_ref_and_roundtrips(self, spec, c, g, rng):
        from repro.kernels.exp_delta import ops
        from repro.kernels.exp_delta.ref import encode_ref

        u = jnp.asarray(
            rng.integers(0, 2**spec.bits, (c, g)).astype(np.uint32)
        )
        enc, base = ops.encode(u, spec)
        enc_r, base_r = encode_ref(u, spec)
        np.testing.assert_array_equal(np.asarray(enc), np.asarray(enc_r))
        np.testing.assert_array_equal(np.asarray(base), np.asarray(base_r).astype(np.uint8))
        dec = ops.decode(enc, base, spec)
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(u))


# ----------------------------------------------------------- bitplane_matmul
class TestBitplaneMatmul:
    @pytest.mark.parametrize("keep", [16, 8, 4])
    @pytest.mark.parametrize("m,k,n", [(32, 512, 256), (100, 1024, 512)])
    def test_matches_ref(self, keep, m, k, n, rng):
        from repro.kernels.bitplane_matmul import ops
        from repro.kernels.bitplane_matmul.ref import bitplane_matmul_ref

        x = _bf16(rng, m, k)
        w = _bf16(rng, k, n, scale=0.02)
        planes = ops.pack_weights(w)
        got = ops.bitplane_matmul(x, planes, keep=keep)
        want = bitplane_matmul_ref(x, planes, keep)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_fetch_bytes_proportional(self, rng):
        from repro.kernels.bitplane_matmul import ops

        planes = ops.pack_weights(_bf16(rng, 512, 256))
        full = ops.weight_fetch_bytes(planes, 16)
        assert ops.weight_fetch_bytes(planes, 8) == full // 2
        assert ops.weight_fetch_bytes(planes, 4) == full // 4


# ------------------------------------------------------------ flash_attention
class TestFlashAttentionKernel:
    @pytest.mark.parametrize(
        "b,sq,skv,hp,hkv,hd,causal,window",
        [
            (2, 128, 128, 8, 2, 64, True, 0),
            (1, 256, 256, 4, 4, 128, True, 64),
            (2, 64, 192, 6, 3, 32, False, 0),
            (1, 96, 96, 9, 3, 112, True, 0),
        ],
    )
    def test_matches_naive_ref(self, b, sq, skv, hp, hkv, hd, causal, window, rng):
        from repro.kernels.flash_attention.ops import flash_attention
        from repro.kernels.flash_attention.ref import attention_ref

        q, k, v = _bf16(rng, b, sq, hp, hd), _bf16(rng, b, skv, hkv, hd), _bf16(rng, b, skv, hkv, hd)
        got = flash_attention(q, k, v, causal=causal, window=window, bq=64, bkv=64)
        want = attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.06
        )

    def test_model_flash_vjp_matches_ref_grads(self, rng):
        """The model's custom-VJP flash backward == autodiff of naive attn."""
        from repro.kernels.flash_attention.ref import attention_ref
        from repro.models.attention import flash_attention, head_map_static

        B, S, Hp, Hkv, hd = 2, 64, 4, 2, 32
        q = jnp.asarray(rng.normal(0, 0.5, (B, S, Hp, hd)).astype(np.float32))
        k = jnp.asarray(rng.normal(0, 0.5, (B, S, Hkv, hd)).astype(np.float32))
        v = jnp.asarray(rng.normal(0, 0.5, (B, S, Hkv, hd)).astype(np.float32))
        hm = head_map_static(Hp, Hp, Hkv)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))

        def f1(q, k, v):
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, hm, q_pos=pos, kv_valid=S, chunk=16
            ).astype(jnp.float32)))

        def f2(q, k, v):
            return jnp.sum(jnp.sin(attention_ref(q, k, v, causal=True).astype(jnp.float32)))

        g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


# ------------------------------------------------------------ paged_attention
class TestPagedAttention:
    @pytest.mark.parametrize(
        "ladder,valid",
        [
            (((0, 512, 16),), 512),
            (((0, 128, 16), (128, 384, 8), (384, 512, 4)), 512),
            (((0, 256, 16), (256, 512, 8)), 400),
        ],
    )
    def test_ladder_matches_ref(self, ladder, valid, rng):
        from repro.kernels.paged_attention.ops import (
            kv_fetch_bytes,
            ladder_paged_attention,
            pack_kv_planes,
        )
        from repro.kernels.paged_attention.ref import ladder_attention_ref

        B, S, Hkv, rep, hd = 2, 512, 4, 2, 64
        q = _bf16(rng, B, 1, Hkv * rep, hd)
        k = _bf16(rng, B, S, Hkv, hd)
        v = _bf16(rng, B, S, Hkv, hd)
        kp, vp = pack_kv_planes(k), pack_kv_planes(v)
        got = ladder_paged_attention(q, kp, vp, ladder, valid, Hkv)
        want = ladder_attention_ref(q, kp, vp, ladder, valid, Hkv)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.06
        )
        full = 2 * B * S * Hkv * hd * 2
        assert kv_fetch_bytes(kp, ladder) <= full

    def test_batched_multi_slot_matches_per_slot_ref(self, rng):
        """ISSUE 5 tentpole kernel surface: one batched call with per-slot
        valid lengths AND per-slot page-plane maps equals composing the ref
        oracle slot by slot over each slot's own contiguous rungs."""
        from repro.kernels.paged_attention.ops import (
            batched_ladder_paged_attention,
            pack_kv_planes,
        )
        from repro.kernels.paged_attention.ref import ladder_attention_ref

        B, S, Hkv, rep, hd = 3, 96, 2, 2, 16
        q = _bf16(rng, B, 1, Hkv * rep, hd)
        k = _bf16(rng, B, S, Hkv, hd)
        v = _bf16(rng, B, S, Hkv, hd)
        kp, vp = pack_kv_planes(k), pack_kv_planes(v)
        pp = np.full((B, S // 16), 16, np.int32)
        pp[1] = [16, 8, 8, 4, 4, 4]
        pp[2] = [4, 16, 4, 8, 16, 8]  # scattered — no contiguous-rung luxury
        valid = np.array([96, 77, 50], np.int32)
        got = batched_ladder_paged_attention(
            q, kp, vp, jnp.asarray(pp), jnp.asarray(valid), keeps=(4, 8, 16),
            kv_heads=Hkv,
        )
        for b in range(B):
            runs = []
            for p in range(S // 16):
                if runs and runs[-1][2] == pp[b, p]:
                    runs[-1] = (runs[-1][0], (p + 1) * 16, runs[-1][2])
                else:
                    runs.append((p * 16, (p + 1) * 16, int(pp[b, p])))
            want = ladder_attention_ref(
                q[b:b + 1], kp[:, b:b + 1], vp[:, b:b + 1], runs,
                int(valid[b]), Hkv,
            )
            np.testing.assert_allclose(
                np.asarray(got[b:b + 1], np.float32),
                np.asarray(want, np.float32), atol=0.08,
            )
        # a slot with nothing valid returns zeros, not softmax garbage
        idle = batched_ladder_paged_attention(
            q, kp, vp, jnp.asarray(pp), jnp.zeros(B, jnp.int32), keeps=(16,),
            kv_heads=Hkv,
        )
        assert np.all(np.asarray(idle, np.float32) == 0)

    def test_fused_matches_rung_kernel(self, rng):
        """ISSUE 6 tentpole: the single-launch fused kernel (per-page plane
        gather in-kernel) equals the per-rung launch loop + host merge on
        scattered per-slot plane maps and ragged valid lengths."""
        from repro.kernels.paged_attention.ops import (
            batched_ladder_paged_attention,
            pack_kv_planes,
        )

        B, S, Hkv, rep, hd = 3, 96, 2, 2, 16
        q = _bf16(rng, B, 1, Hkv * rep, hd)
        k = _bf16(rng, B, S, Hkv, hd)
        v = _bf16(rng, B, S, Hkv, hd)
        kp, vp = pack_kv_planes(k), pack_kv_planes(v)
        pp = np.asarray(rng.choice([4, 8, 16], (B, S // 16)), np.int32)
        valid = jnp.asarray([96, 50, 17], jnp.int32)
        args = (q, kp, vp, jnp.asarray(pp), valid)
        fused = batched_ladder_paged_attention(*args, keeps=(4, 8, 16),
                                               kv_heads=Hkv,
                                               kernel="fused")
        rung = batched_ladder_paged_attention(*args, keeps=(4, 8, 16),
                                              kv_heads=Hkv,
                                              kernel="rung")
        np.testing.assert_allclose(
            np.asarray(fused, np.float32), np.asarray(rung, np.float32),
            atol=0.01,
        )
        with pytest.raises(ValueError, match="kernel"):
            batched_ladder_paged_attention(*args, keeps=(16,), kv_heads=Hkv,
                                           kernel="warp")

    @pytest.mark.parametrize("kernel", ["fused", "rung"])
    def test_fully_masked_row_returns_zeros(self, kernel, rng):
        """ISSUE 6 satellite bugfix: a slot whose EVERY page is masked
        leaves m = -inf, l = 0 — the final normalisation must not divide
        unguarded.  Pinned on both kernel paths with a row of all-masked
        pages (plane count 0 on every page) and a row with valid_len 0."""
        from repro.kernels.paged_attention.ops import (
            batched_ladder_paged_attention,
            pack_kv_planes,
        )

        B, S, Hkv, rep, hd = 3, 64, 2, 2, 16
        q = _bf16(rng, B, 1, Hkv * rep, hd)
        k = _bf16(rng, B, S, Hkv, hd)
        v = _bf16(rng, B, S, Hkv, hd)
        kp, vp = pack_kv_planes(k), pack_kv_planes(v)
        pp = np.full((B, S // 16), 16, np.int32)
        pp[1] = 0  # row 1: every page masked out of the ladder entirely
        valid = jnp.asarray([64, 64, 0], jnp.int32)  # row 2: nothing valid
        out = np.asarray(batched_ladder_paged_attention(
            q, kp, vp, jnp.asarray(pp), valid, keeps=(4, 8, 16),
            kv_heads=Hkv, kernel=kernel,
        ), np.float32)
        assert np.all(np.isfinite(out))
        assert np.all(out[1] == 0) and np.all(out[2] == 0)
        assert np.any(out[0] != 0)  # live row unaffected by the guard

    def test_interpret_default_follows_backend(self, monkeypatch):
        """ISSUE 5 satellite: interpret=None resolves from the JAX backend
        (interpreter on CPU, compiled elsewhere) with an env override — the
        old hardcoded True silently interpreted on TPU."""
        from repro.kernels import default_interpret

        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        assert default_interpret() == (jax.default_backend() == "cpu")
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        assert default_interpret() is False
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert default_interpret() is True

    def test_interpret_override_refused_on_accelerator(self, monkeypatch):
        """The interpreter override is for CPU debugging: on an accelerator
        it raises instead of silently interpreting every kernel, while the
        default and the compile override resolve to compiled Mosaic."""
        from repro.kernels import default_interpret

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        assert default_interpret() is False
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        assert default_interpret() is False
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        with pytest.raises(ValueError, match="CPU debugging only"):
            default_interpret()


# ------------------------------------------------------------------- ssd
class TestSSDKernel:
    @pytest.mark.parametrize("chunk", [64, 128])
    @pytest.mark.parametrize("l", [256, 192])
    def test_matches_ssd_scan(self, chunk, l, rng):
        from repro.kernels.ssd.ops import ssd
        from repro.kernels.ssd.ref import ssd_ref

        B, H, P, N = 2, 4, 32, 16
        xdt = jnp.asarray(rng.normal(0, 1, (B, l, H, P)).astype(np.float32))
        da = jnp.asarray(-np.abs(rng.normal(0.05, 0.05, (B, l, H))).astype(np.float32))
        b_h = jnp.asarray(rng.normal(0, 1, (B, l, H, N)).astype(np.float32))
        c_h = jnp.asarray(rng.normal(0, 1, (B, l, H, N)).astype(np.float32))
        h0 = jnp.asarray(rng.normal(0, 1, (B, H, N, P)).astype(np.float32))
        y_k, h_k = ssd(xdt, da, b_h, c_h, h0=h0, chunk=chunk)
        # ref math is chunking-invariant; 64 divides every tested length
        y_r, h_r = ssd_ref(xdt, da, b_h, c_h, h0=h0, chunk=64)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-3)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-3)
