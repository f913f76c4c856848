"""Continuous-batching scheduler + compressed-KV eviction (ISSUE 1).

Covers the tentpole acceptance criteria: heterogeneous requests finish at
their own step, slots are reused, retired pages leave the store, the
``max_stored_bytes`` LRU budget holds its invariants, and ``report()``
emits sane steady-state accounting.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.quantization import PrecisionLadder
from repro.core.surrogates import logmag_kv_cache
from repro.models.model import build_model
from repro.serving import (
    CompressedKVStore,
    ContinuousScheduler,
    EngineConfig,
    PageEvictedError,
    Request,
    ServingEngine,
)
from repro.serving.kv_cache import PAGE_TOKENS, PageKey
from repro.telemetry import PHASES


# ---------------------------------------------------------------------------
# CompressedKVStore: LRU eviction + byte budget
# ---------------------------------------------------------------------------


def _page(seed):
    return logmag_kv_cache(PAGE_TOKENS, 64, seed=seed)


def test_store_budget_and_lru_order():
    probe = CompressedKVStore()
    probe.put_page(PageKey(0, 0, 0), _page(0))
    page_bytes = probe.footprint()["stored_bytes"]

    store = CompressedKVStore(max_stored_bytes=int(2.5 * page_bytes))
    for p in range(3):
        store.put_page(PageKey(0, 0, p), _page(p))
    fp = store.footprint()
    assert fp["stored_bytes"] <= store.max_stored_bytes
    assert fp["evictions"] == 1 and fp["evicted_bytes"] > 0
    # LRU: the oldest page went, the newer two stayed
    assert not store.contains(PageKey(0, 0, 0))
    assert store.contains(PageKey(0, 0, 1)) and store.contains(PageKey(0, 0, 2))


def test_store_lru_touch_protects_page():
    probe = CompressedKVStore()
    probe.put_page(PageKey(0, 0, 0), _page(0))
    page_bytes = probe.footprint()["stored_bytes"]

    store = CompressedKVStore(max_stored_bytes=int(2.5 * page_bytes))
    store.put_page(PageKey(0, 0, 0), _page(0))
    store.put_page(PageKey(0, 0, 1), _page(1))
    store.account_fetch(PageKey(0, 0, 0))  # touch page 0 -> page 1 is coldest
    store.put_page(PageKey(0, 0, 2), _page(2))
    assert store.contains(PageKey(0, 0, 0))
    assert not store.contains(PageKey(0, 0, 1))


def test_store_evicted_page_raises_then_reactivates():
    probe = CompressedKVStore()
    probe.put_page(PageKey(0, 0, 0), _page(0))
    page_bytes = probe.footprint()["stored_bytes"]

    store = CompressedKVStore(max_stored_bytes=int(1.5 * page_bytes))
    kv0 = _page(0)
    store.put_page(PageKey(0, 0, 0), kv0)
    store.put_page(PageKey(0, 0, 1), _page(1))  # evicts page 0
    with pytest.raises(PageEvictedError):
        store.get_page(PageKey(0, 0, 0))
    assert store.footprint()["misses"] == 1
    store.put_page(PageKey(0, 0, 0), kv0)  # re-activation = re-compress write
    back = store.get_page(PageKey(0, 0, 0))
    np.testing.assert_array_equal(back.view(np.uint16), kv0.view(np.uint16))


def test_store_planes_hint_drives_default_fetch():
    store = CompressedKVStore()
    kv = _page(3)
    store.put_page(PageKey(0, 0, 0), kv, planes=8)
    low = store.get_page(PageKey(0, 0, 0))  # defaults to the ladder hint
    full = store.get_page(PageKey(0, 0, 0), keep_planes=16)
    np.testing.assert_array_equal(full.view(np.uint16), kv.view(np.uint16))
    assert np.any(low.view(np.uint16) != kv.view(np.uint16))
    reads = [e for e in store.controller.stats.events if e.kind == "kv_read"]
    assert reads[0].physical_bytes < reads[1].physical_bytes


def test_store_drop_sequence_frees_budget_without_eviction_counts():
    store = CompressedKVStore(max_stored_bytes=1 << 20)
    store.put_sequence(7, 0, "k", logmag_kv_cache(40, 64, seed=9))  # 3 pages
    store.put_sequence(8, 0, "k", logmag_kv_cache(16, 64, seed=10))
    assert store.footprint()["pages"] == 4
    store.drop_sequence(7)
    fp = store.footprint()
    assert fp["pages"] == 1 and fp["evictions"] == 0
    assert store.sequence_pages(8) and not store.sequence_pages(7)


def test_store_tail_page_padding_roundtrip():
    store = CompressedKVStore()
    kv = logmag_kv_cache(100, 64, rho=0.995, seed=5)  # non page-multiple
    n = store.put_sequence(0, 0, "k", kv)
    assert n == 7
    back = store.get_sequence(0, 0, "k", 100)
    np.testing.assert_array_equal(back.view(np.uint16), kv.view(np.uint16))


# ---------------------------------------------------------------------------
# Scheduler: admission, join/retire, slot reuse, accounting
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompt(n, offset=0):
    return ((np.arange(n) + offset) % 500).astype(np.int32)


def test_heterogeneous_requests_finish_at_their_own_step(smoke_model):
    model, params = smoke_model
    ladder = PrecisionLadder([(2, 16), (2, 8), (-1, 4)])
    sched = ContinuousScheduler(
        model, params, EngineConfig(max_batch=4, max_ctx=192, ladder=ladder)
    )
    short = Request(rid=0, prompt=_prompt(20), max_new_tokens=4)
    long = Request(rid=1, prompt=_prompt(90, 3), max_new_tokens=32)
    sched.submit(short)
    sched.submit(long)
    sched.run_until_drained()
    assert short.done and len(short.output) == 4
    assert long.done and len(long.output) == 32
    assert short.finish_step < long.finish_step
    # the short request's pages left the store the step it retired
    assert not sched.store.sequence_pages(0)
    assert sched.report()["requests_completed"] == 2


def test_slots_are_reused_under_oversubscription(smoke_model):
    model, params = smoke_model
    sched = ContinuousScheduler(
        model, params, EngineConfig(max_batch=2, max_ctx=160)
    )
    reqs = [Request(rid=i, prompt=_prompt(18 + 2 * i, i), max_new_tokens=3 + i)
            for i in range(4)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    assert all(r.done and len(r.output) == 3 + i for i, r in enumerate(reqs))
    # only 2 slots: the last two admissions had to wait for a retirement
    first_wave = {reqs[0].admit_step, reqs[1].admit_step}
    second_wave = {reqs[2].admit_step, reqs[3].admit_step}
    assert max(first_wave) < min(second_wave)
    rep = sched.report()
    assert rep["requests_completed"] == 4
    assert 0 < rep["mean_batch_occupancy"] <= 1


def test_mixed_batch_evicts_under_budget_and_reports_savings(smoke_model):
    """ISSUE 1 acceptance: short + long requests under a byte budget smaller
    than the logical KV footprint -> short retires early, pages evicted,
    kv_capacity_saving > 0."""
    model, params = smoke_model
    ladder = PrecisionLadder([(2, 16), (2, 8), (-1, 4)])

    def build(budget):
        return ContinuousScheduler(
            model, params,
            EngineConfig(max_batch=4, max_ctx=192, ladder=ladder,
                         max_stored_bytes=budget),
        )

    # calibrate: measure the unconstrained peak, then halve it
    probe = build(None)
    reqs = [Request(rid=0, prompt=_prompt(24), max_new_tokens=4),
            Request(rid=1, prompt=_prompt(100, 5), max_new_tokens=32)]
    for r in reqs:
        probe.submit(r)
    probe.run_until_drained()
    peak_logical = probe.report()["kv_peak_logical_bytes"]
    peak_stored = probe.report()["kv_peak_stored_bytes"]
    assert peak_logical > peak_stored > 0

    sched = build(peak_stored // 2)  # budget < logical footprint (and stored)
    short = Request(rid=0, prompt=_prompt(24), max_new_tokens=4)
    long = Request(rid=1, prompt=_prompt(100, 5), max_new_tokens=32)
    sched.submit(short)
    sched.submit(long)
    sched.run_until_drained()
    rep = sched.report()
    assert short.done and short.finish_step < long.finish_step
    assert not sched.store.sequence_pages(0)  # retired pages gone
    assert rep["kv_evictions"] > 0  # budget pressure really evicted
    assert rep["kv_peak_stored_bytes"] <= peak_stored // 2 + 1
    assert rep["kv_capacity_saving"] > 0
    assert 0 < rep["kv_bandwidth_saving"] < 1
    assert rep["requests_completed"] == 2


def test_report_emits_per_1k_request_stats(smoke_model):
    model, params = smoke_model
    eng = ServingEngine(model, params, EngineConfig(max_batch=4, max_ctx=160))
    # page-multiple prompts: capacity saving must be positive on full pages
    # (ragged tails are stored exact-length and can erode the ratio — that
    # is the honest pad-free accounting, covered by the pad-free tests)
    reqs = [Request(rid=i, prompt=_prompt(32 + PAGE_TOKENS * i, i),
                    max_new_tokens=4)
            for i in range(3)]
    eng.run(reqs)
    rep = eng.report()
    for key in ("host_s_by_phase", "kv_capacity_saving", "per_1k_requests",
                "decode_steps", "mean_batch_occupancy"):
        assert key in rep, key
    per = rep["per_1k_requests"]
    assert per["kv_stored_bytes"] > 0
    assert per["decode_tokens"] == pytest.approx(12 * 1000 / 3)  # 3 reqs x 4 tok
    assert set(rep["host_s_by_phase"]) == set(PHASES)
    assert rep["host_s_by_phase"]["decode_wait"] > 0
    assert 0 < rep["kv_capacity_saving"] < 1


def test_scheduler_rejects_oversized_and_unsupported(smoke_model):
    model, params = smoke_model
    sched = ContinuousScheduler(model, params, EngineConfig(max_ctx=64))
    # a prompt that leaves no decode room is rejected; one that merely asks
    # for more new tokens than fit is admitted and truncated at the window
    with pytest.raises(ValueError, match="exceeds max_ctx"):
        sched.submit(Request(rid=0, prompt=_prompt(64), max_new_tokens=1))
    # bucketed chunks are page-aligned: a ragged max_ctx would let the
    # final bucket clamp and overwrite earlier rows — rejected up front
    with pytest.raises(ValueError, match="multiple of PAGE_TOKENS"):
        ContinuousScheduler(model, params, EngineConfig(max_ctx=100))


def test_engine_config_exposes_codec_and_geometry(smoke_model):
    """ISSUE 2 satellite: serving deployments pick the codec and engine
    geometry on EngineConfig instead of inheriting default_codec()."""
    from repro.memctl import MemCtlConfig

    model, params = smoke_model
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=96, codec="lz4",
        engine=MemCtlConfig(lanes=8, clock_ghz=1.0, block_bits=16384,
                            step_cycles=1024),
    ))
    assert sched.store.config.codec == "lz4"
    assert sched.controller.config.codec == "lz4"
    assert sched.engine.cfg.engine == "lz4"  # lane silicon follows the codec
    assert sched.engine.cfg.lanes == 8
    assert sched.engine.cfg.block_bytes == 2048
    # 512 Gb/s lane at 1 GHz = 64 B/cycle; window = 8 lanes x 64 x 1024
    assert sched.engine.cfg.lane_bytes_per_cycle == 64.0
    assert sched.engine.cfg.step_budget_bytes == 8 * 64 * 1024
    assert sched.engine.report()["silicon"]["lanes"] == 8

    sched.submit(Request(rid=0, prompt=_prompt(20), max_new_tokens=3))
    sched.run_until_drained()
    rep = sched.report()
    for key in ("engine_utilization", "engine_modeled_latency_ns",
                "engine_deferred_jobs", "engine_queue_depth_p99", "engine"):
        assert key in rep, key


def test_engine_run_matches_scheduler_outputs(smoke_model):
    """run() wrapper and direct scheduler use produce identical greedy text."""
    model, params = smoke_model
    prompt = _prompt(40)
    eng = ServingEngine(model, params, EngineConfig(max_batch=2, max_ctx=160))
    r1 = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])[0]

    sched = ContinuousScheduler(
        model, params, EngineConfig(max_batch=2, max_ctx=160)
    )
    r2 = Request(rid=9, prompt=prompt, max_new_tokens=5)
    sched.submit(r2)
    sched.run_until_drained()
    assert r1.output == r2.output


# ---------------------------------------------------------------------------
# Bucketed chunked-prefill admission (ISSUE 3)
# ---------------------------------------------------------------------------


def test_chunk_schedule_is_page_aligned_and_exact():
    from repro.serving.scheduler import chunk_schedule, prefill_buckets

    buckets = prefill_buckets(256)
    assert buckets == [16, 32, 64, 128, 256]
    for n in (1, 5, 16, 17, 37, 90, 200, 255):
        chunks = chunk_schedule(n, buckets)
        assert sum(real for _, real in chunks) == n
        start = 0
        for i, (bucket, real) in enumerate(chunks):
            assert bucket in buckets
            assert start % PAGE_TOKENS == 0  # every chunk starts page-aligned
            if i < len(chunks) - 1:
                assert real == bucket  # only the final chunk may be ragged
            start += real


def test_bucketed_prefill_bounds_compiles_on_mixed_trace(smoke_model):
    """64 mixed-length requests compile at most log2(max_ctx) prefill
    variants; the left-pad baseline needs strictly more on the same trace."""
    import math

    model, params = smoke_model
    rng = np.random.default_rng(0)
    lens = rng.integers(8, 200, 64)

    def run(mode):
        # sharing pinned off: the padded baseline rejects prefix_sharing
        # (no chunk schedule to skip from), and the comparison only counts
        # prefill compiles/tokens, which sharing never changes here
        sched = ContinuousScheduler(model, params, EngineConfig(
            max_batch=8, max_ctx=256, store_kv_compressed=False,
            prefill_mode=mode, prefix_sharing=False,
        ))
        for i, n in enumerate(lens):
            sched.submit(Request(rid=i, prompt=_prompt(int(n), i),
                                 max_new_tokens=2))
        sched.run_until_drained()
        return sched.report()

    bucketed = run("bucketed")
    padded = run("padded")
    assert bucketed["requests_completed"] == 64
    assert bucketed["prefill_compiles"] <= math.log2(256)
    assert padded["prefill_compiles"] > bucketed["prefill_compiles"]
    # pad-free admission: bucketed prefill feeds exactly the prompt tokens
    assert bucketed["prefill_tokens"] == int(lens.sum())
    assert padded["prefill_tokens"] > bucketed["prefill_tokens"]


def test_chunked_prefill_is_pad_free(smoke_model):
    """cache["len"] holds the TRUE prompt length and every stored page
    round-trips to the device KV — no left-pad garbage, no phantom logical
    bytes for the ragged tail."""
    model, params = smoke_model
    # paged pinned: the test round-trips full-channel pages against the
    # device cache, which is a single-tier layout property; sharing pinned
    # off because it round-trips via rid-keyed get_sequence, and prefix
    # sharing stores full prompt pages under backend-held content keys
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=160, store_layers=2, backend="paged",
        prefix_sharing=False,
    ))
    n = 37  # 2 full pages + a 5-token ragged tail
    req = Request(rid=0, prompt=_prompt(n), max_new_tokens=8)
    sched.submit(req)
    sched.step()  # full admission (idle scheduler) + first decode token

    # true length: prompt tokens + the one decoded token, never padded
    assert int(sched._lens[0]) == n + 1
    assert sched.report()["prefill_tokens"] == n
    # exact-length tail page: logical accounting counts 37 tokens, not 48
    ch = model.cfg.n_kv_heads * model.cfg.head_dim  # layout-agnostic
    per_tok = 2 * ch * 2  # k+v streams, bf16
    assert sched.store.footprint()["logical_bytes"] == 2 * n * per_tok
    # stored pages hold the real KV (tail pad rows are repeats of the last
    # real token, excluded from accounting and never attended)
    k_dev, v_dev = sched.backend.slot_kv_host(0, 0, n)
    for li in range(2):
        back = sched.store.get_sequence(0, li, "k", n)
        np.testing.assert_array_equal(
            back.view(np.uint16), k_dev[li].view(np.uint16)
        )
        back = sched.store.get_sequence(0, li, "v", n)
        np.testing.assert_array_equal(
            back.view(np.uint16), v_dev[li].view(np.uint16)
        )
    sched.run_until_drained()
    assert req.done and len(req.output) == 8


def test_chunked_admission_overlaps_decode(smoke_model):
    """A long prompt joins chunk-by-chunk while the batch keeps decoding —
    admission no longer stalls in-flight requests."""
    model, params = smoke_model
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=256, store_kv_compressed=False,
    ))
    a = Request(rid=0, prompt=_prompt(16), max_new_tokens=24)
    sched.submit(a)
    for _ in range(3):
        sched.step()
    assert len(a.output) == 3

    b = Request(rid=1, prompt=_prompt(96, 5), max_new_tokens=4)  # 2 chunks
    sched.submit(b)
    sched.step()  # b advances ONE chunk; a still decodes
    slot_b = next(s for s in sched._slots if s is not None and s.req.rid == 1)
    assert slot_b.prefilling, "long admission must spread across steps"
    assert len(a.output) == 4, "decode must not stall during admission"
    sched.step()  # final chunk lands; b joins decode this step
    assert not slot_b.prefilling
    assert len(a.output) == 5 and len(b.output) == 1
    sched.run_until_drained()
    assert a.done and b.done and len(b.output) == 4


def test_async_admission_keeps_chunk_dispatch_rate(smoke_model):
    """ISSUE 5 satellite: prefill chunks now dispatch without a per-chunk
    host sync and the backend's storage flush runs after the decode
    dispatch — the admission PACING must be unchanged: a joining prompt
    advances exactly ``prefill_chunks_per_step`` chunks per step while the
    batch decodes, and decode never stalls."""
    model, params = smoke_model
    for cps in (1, 2):
        sched = ContinuousScheduler(model, params, EngineConfig(
            max_batch=2, max_ctx=256, store_kv_compressed=False,
            prefill_chunks_per_step=cps,
        ))
        a = Request(rid=0, prompt=_prompt(16), max_new_tokens=30)
        sched.submit(a)
        for _ in range(2):
            sched.step()
        # 213 tokens -> chunks 128, 64, 16, 16(ragged): 4 dispatches
        b = Request(rid=1, prompt=_prompt(213, 7), max_new_tokens=2)
        sched.submit(b)
        deltas = []
        while True:
            before = sched.stats["prefill_chunks"]
            out_a = len(a.output)
            sched.step()
            deltas.append(sched.stats["prefill_chunks"] - before)
            assert len(a.output) == out_a + 1, "decode stalled on admission"
            slot_b = next((s for s in sched._slots
                           if s is not None and s.req.rid == 1), None)
            if slot_b is not None and not slot_b.prefilling:
                break
        assert deltas == [cps] * (4 // cps)
        sched.run_until_drained()
        assert a.done and b.done


# ---------------------------------------------------------------------------
# Serving-path correctness sweep (ISSUE 3 satellites)
# ---------------------------------------------------------------------------


def test_mid_flight_seed_does_not_disturb_active_streams(smoke_model):
    """Submitting a request with rng_seed must not change the sampling
    stream of requests already in flight (the shared-key reset bug)."""
    from repro.serving.sampler import SamplerConfig

    model, params = smoke_model
    samp = SamplerConfig(temperature=0.8, top_k=8)

    def tokens_of_a(with_seeded_b):
        sched = ContinuousScheduler(model, params, EngineConfig(
            max_batch=2, max_ctx=192, sampler=samp,
            store_kv_compressed=False,
        ))
        a = Request(rid=0, prompt=_prompt(20), max_new_tokens=10)
        sched.submit(a)
        for _ in range(3):
            sched.step()
        if with_seeded_b:
            sched.submit(Request(rid=1, prompt=_prompt(24, 7),
                                 max_new_tokens=4), rng_seed=123)
        sched.run_until_drained()
        return list(a.output)

    assert tokens_of_a(False) == tokens_of_a(True)


def test_requests_truncated_at_context_window_say_so(smoke_model):
    model, params = smoke_model
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=64, store_kv_compressed=False,
    ))
    r = Request(rid=0, prompt=_prompt(40), max_new_tokens=32)
    done = Request(rid=1, prompt=_prompt(20, 3), max_new_tokens=4)
    sched.submit(r)
    sched.submit(done)
    sched.run_until_drained()
    assert r.done and r.truncated and len(r.output) == 64 - 40
    assert done.done and not done.truncated and len(done.output) == 4
    assert sched.report()["requests_truncated"] == 1


def test_run_until_drained_services_engine_backlog(smoke_model):
    """The drain loop must keep ticking until queued engine jobs (eviction
    write-backs with fn=None among them) are serviced — otherwise report()
    underquotes utilization and modeled latency."""
    from repro.memctl import MemCtlConfig

    model, params = smoke_model
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=96,
        engine=MemCtlConfig(lanes=1, step_cycles=64),  # 2 KB per step
    ))
    r = Request(rid=0, prompt=_prompt(20), max_new_tokens=3)
    sched.submit(r)
    sched.run_until_drained()
    assert len(sched.engine.queue) == 0 and not sched.has_work()

    # raw backlog (no slots, no waiting) must still count as work
    sched.engine.submit_eviction(("k", 0, 0), 64 * 1024)
    assert sched.has_work()
    sched.run_until_drained()
    assert len(sched.engine.queue) == 0 and not sched.has_work()
    assert sched.engine.stats.serviced_bytes["BACKGROUND"] >= 64 * 1024


def test_shed_latency_rejects_at_submit_with_reason(smoke_model):
    """ISSUE 10 satellite: with the modeled engine backlog past
    ``shed_latency_ns_max``, submit() rejects the request outright —
    done, never enqueued, never decoded, with a reason naming both the
    pressure and the bound — and counts it; once the backlog drains,
    submissions admit normally again."""
    from repro.memctl import MemCtlConfig

    model, params = smoke_model
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=96, store_layers=2,
        engine=MemCtlConfig(lanes=1, step_cycles=64),
        shed_latency_ns_max=200.0,
    ))
    a = Request(rid=0, prompt=_prompt(80), max_new_tokens=8)
    sched.submit(a)
    for _ in range(3):
        sched.step()  # build a real backlog on the tiny lane window
    assert sched.backend.admit_pressure_ns() > 200.0
    b = Request(rid=1, prompt=_prompt(40, 5), max_new_tokens=4)
    sched.submit(b)
    assert b.done and b.shed and b.output == []
    assert "shed_latency_ns_max" in b.shed_reason
    assert "exceeds" in b.shed_reason
    rep_mid = sched.stats["requests_shed"]
    assert rep_mid == 1
    sched.run_until_drained()
    assert a.done and not a.shed
    # drained: the same request body admits now
    c = Request(rid=2, prompt=_prompt(40, 5), max_new_tokens=4)
    sched.submit(c)
    sched.run_until_drained()
    assert c.done and not c.shed and len(c.output) == 4
    rep = sched.report()
    assert rep["requests_shed"] == 1
    assert rep["per_1k_requests"]["requests_shed"] > 0


@pytest.mark.parametrize("device_kv", ["dense", "bitplane"])
def test_programs_are_named_decode_and_prefill_chunk(device_kv):
    """A fresh scheduler's decode and prefill-chunk jits lower to modules
    named ``jit_decode`` and ``jit_prefill_chunk``: a profiler trace shows
    those names with no renaming by the caller."""
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=64, device_kv=device_kv))
    cache = sched.backend.ensure_cache()
    lowered = {
        "jit_decode": sched._decode.lower(
            params, jnp.zeros(2, jnp.int32), cache),
        "jit_prefill_chunk": sched._prefill_chunk.lower(
            params, jnp.zeros((1, 16), jnp.int32), cache, jnp.int32(0),
            jnp.int32(0), jnp.int32(15)),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @{name} "), name
