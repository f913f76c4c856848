"""KVBackend conformance suite (ISSUE 4).

One serving API, three memory tiers: every backend must decode exactly what
the plain model loop decodes, ``ShardedBackend(shards=1)`` must be
bit-exact with ``PagedBackend`` (tokens AND byte accounting), eviction
re-activations must charge exactly one kv_write per tier, and the pad-free
savings invariant must hold whichever tier is behind the scheduler.  Plus
the satellites: shard-scoped job cancellation, admission backpressure, ring
live-window page retirement.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.quantization import PrecisionLadder
from repro.memctl import Job, JobClass, MemCtlConfig, PriorityJobQueue
from repro.models.model import build_model, prepare_decode_cache
from repro.serving import ContinuousScheduler, EngineConfig, Request
from repro.serving.backends import BACKENDS, make_backend
from repro.serving.kv_cache import PAGE_TOKENS


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def ring_model():
    """Sliding-window variant of the smoke config (Mixtral-shaped cache)."""
    cfg = dataclasses.replace(get_config("smollm-135m", smoke=True),
                              attn_window=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompt(n, offset=0):
    return ((np.arange(n) + offset) % 500).astype(np.int32)


def _reference_greedy(model, params, prompt, n_new, max_ctx):
    """The pre-scheduler decode loop: one-shot prefill + step-wise greedy
    decode straight against the model — the ground truth every backend's
    served tokens must reproduce."""
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(prompt[None])}
    )
    cache = prepare_decode_cache(model.cfg, cache, max_ctx)
    dec = jax.jit(model.decode)
    out = []
    tok = int(np.asarray(jnp.argmax(logits, -1))[0])
    for _ in range(n_new):
        out.append(tok)
        logits, cache = dec(params, jnp.asarray([tok], jnp.int32), cache)
        tok = int(np.asarray(jnp.argmax(logits, -1))[0])
    return out


def _serve(model, params, cfg, prompts, max_new):
    sched = ContinuousScheduler(model, params, cfg)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    assert all(r.done for r in reqs)
    return sched, reqs


BACKEND_CASES = [("paged", 1), ("sharded", 1), ("sharded", 2)]
LADDER = PrecisionLadder([(2, 16), (2, 8), (-1, 4)])


def _cfg(backend, shards, **kw):
    return EngineConfig(max_batch=4, max_ctx=192, backend=backend,
                        shards=shards, store_layers=2, **kw)


# ---------------------------------------------------------------------------
# Decoded-token conformance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,shards", BACKEND_CASES)
def test_backend_decodes_match_model_loop(smoke_model, backend, shards):
    """Whatever tier sits behind the scheduler, served greedy tokens equal
    the plain model loop's (the pre-refactor paged path's contract).

    device_kv pinned dense: with a MIXED ladder the bit-plane device path
    truncates decode reads for real (that is its point), so only the dense
    layout promises model-loop-exact tokens under this ladder; the
    bit-plane layout's token conformance — full-precision bit-identity
    against the dense path — has its own test below."""
    model, params = smoke_model
    prompts = [_prompt(37), _prompt(80, 11)]
    sched, reqs = _serve(model, params,
                         _cfg(backend, shards, ladder=LADDER,
                              device_kv="dense"),
                         prompts, max_new=6)
    for r, p in zip(reqs, prompts):
        assert r.output == _reference_greedy(model, params, p, 6, 192), (
            backend, shards, r.rid
        )


def test_sharded_one_is_bit_exact_with_paged(smoke_model):
    """ISSUE 4 acceptance: ShardedBackend(shards=1) == PagedBackend, tokens
    AND byte accounting."""
    model, params = smoke_model
    prompts = [_prompt(24), _prompt(90, 3), _prompt(50, 7)]

    def run(backend, shards):
        sched, reqs = _serve(
            model, params,
            _cfg(backend, shards, ladder=LADDER, max_stored_bytes=48 * 1024),
            prompts, max_new=8,
        )
        return sched.report(), [r.output for r in reqs]

    rep_p, out_p = run("paged", 1)
    rep_s, out_s = run("sharded", 1)
    assert out_p == out_s
    for key in ("kv_logical_bytes", "kv_stored_bytes", "kv_fetch_logical",
                "kv_fetch_physical", "kv_evictions", "kv_evicted_bytes",
                "kv_reactivations", "kv_fetch_misses", "kv_fetch_deferrals",
                "engine_jobs_cancelled", "kv_peak_stored_bytes"):
        assert rep_p[key] == rep_s[key], key


# ---------------------------------------------------------------------------
# Accounting invariants, per backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,shards", BACKEND_CASES)
def test_pad_free_savings_invariant(smoke_model, backend, shards):
    """Logical bytes are quoted over REAL tokens only — an exact-length
    ragged tail never inflates them, whichever tier stores the pages (a
    sharded tier's channel slices must sum back to the full page)."""
    model, params = smoke_model
    n = 37  # 2 full pages + a 5-token ragged tail
    sched = ContinuousScheduler(model, params, _cfg(backend, shards))
    sched.submit(Request(rid=0, prompt=_prompt(n), max_new_tokens=8))
    sched.step()  # idle scheduler: full admission + first decode token
    ch = model.cfg.n_kv_heads * model.cfg.head_dim  # layout-agnostic
    per_tok = 2 * ch * 2  # k+v streams, bf16
    logical = sum(t.store.footprint()["logical_bytes"]
                  for t in sched.backend.tiers)
    assert logical == 2 * n * per_tok  # store_layers=2, pad-free


@pytest.mark.parametrize("backend,shards", BACKEND_CASES)
def test_eviction_reactivation_charged_exactly_once(smoke_model, backend,
                                                    shards):
    """Every kv_write event on every tier is exactly one serviced KV_WRITE
    job or one serviced re-activation — eviction write-backs (occupancy
    only) never inflate the count, and a deferred re-activation is charged
    once no matter how many steps it waits."""
    model, params = smoke_model
    # weight_stream pinned resident: this lane window (2 lanes x 512
    # cycles) is sized so KV writes thrash the byte budget; a streamed
    # weight pass outranks KV_WRITE and would monopolize it entirely (the
    # streaming x thrash interaction is pinned in test_weight_stream.py)
    cfg = _cfg(backend, shards, ladder=LADDER, max_stored_bytes=10 * 1024,
               engine=MemCtlConfig(lanes=2, step_cycles=512),
               weight_stream="resident")
    sched, reqs = _serve(model, params, cfg, [_prompt(80), _prompt(80, 3)],
                         max_new=16)
    rep = sched.report()
    assert rep["kv_evictions"] > 0
    assert rep["kv_reactivations"] > 0
    n_writes = sum(t.controller.stats.kind_count("kv_write")
                   for t in sched.backend.tiers)
    serviced_writes = sum(t.engine.stats.serviced_jobs["KV_WRITE"]
                          for t in sched.backend.tiers)
    assert n_writes == serviced_writes + rep["kv_reactivations"]


def test_scheduler_has_no_direct_store_or_cache_access():
    """ISSUE 4 acceptance (now ISSUE 8): the scheduler module neither
    touches CompressedKVStore nor indexes into the device cache dict — all
    memory traffic goes through the KVBackend protocol.  The substring pin
    moved into the ``layering-scheduler`` repro-lint rule so the
    conformance suite and the CI linter share one source of truth."""
    import inspect

    from repro.analysis import check_file
    from repro.serving import scheduler as sched_mod

    findings = check_file(inspect.getsourcefile(sched_mod),
                          rule_names=["layering-scheduler"])
    assert findings == [], "\n".join(
        f"{f.location()}: {f.message}" for f in findings)


def test_make_backend_rejects_unknown_name(smoke_model):
    model, params = smoke_model
    with pytest.raises(ValueError, match="unknown KV backend"):
        ContinuousScheduler(model, params,
                            EngineConfig(max_ctx=64, backend="nvme"))
    assert set(BACKENDS) == {"paged", "sharded", "ring"}


# ---------------------------------------------------------------------------
# Sharded routing + shard-scoped cancellation (satellite)
# ---------------------------------------------------------------------------


def test_sharded_routes_follow_mesh_rules(smoke_model):
    """Hkv=2 divides shards=2 -> KV-head ownership (channel slices); a
    shard count the heads can't divide falls back to the sequence axis
    (block-cyclic pages) exactly like _kv_spec's context-parallel rule."""
    model, params = smoke_model
    head = make_backend(model, _cfg("sharded", 2))
    assert head._route == "head" and len(head.tiers) == 2
    seq = make_backend(model, _cfg("sharded", 3))  # 2 % 3 != 0; 192 % 3 == 0
    assert seq._route == "seq"
    with pytest.raises(ValueError, match="divides neither"):
        make_backend(model, EngineConfig(max_batch=4, max_ctx=100,
                                         prefill_mode="padded",
                                         backend="sharded", shards=7))


def test_queue_cancellation_is_shard_scoped():
    """Retire-time cancellation keys on the full (shard, rid) scope:
    cancelling rid 7's work on shard 0 must not touch the same-rid job
    queued for shard 1 (the cross-shard write-back bug)."""
    q = PriorityJobQueue()
    q.push(Job(JobClass.KV_WRITE, 64, key=("p", 0), seq_id=(0, 7)))
    q.push(Job(JobClass.KV_WRITE, 64, key=("p", 1), seq_id=(1, 7)))
    q.push(Job(JobClass.BACKGROUND, 64, key=("e", 0), seq_id=None))
    assert q.cancel_seq((0, 7)) == 1
    assert q.pending(("p", 1), JobClass.KV_WRITE)  # shard 1's job survives
    assert q.pending(("e", 0), JobClass.BACKGROUND)  # committed work survives
    assert q.cancel_seq(7) == 0  # bare-rid cancel can't reach scoped jobs


def test_sharded_retire_cancels_on_every_shard_without_crosstalk(smoke_model):
    """End to end: a retiring request's queued jobs are cancelled on all of
    ITS scopes while another in-flight request's jobs survive on every
    shard."""
    model, params = smoke_model
    cfg = _cfg("sharded", 2, engine=MemCtlConfig(lanes=1, step_cycles=16))
    sched = ContinuousScheduler(model, params, cfg)
    a = Request(rid=0, prompt=_prompt(40), max_new_tokens=2)
    b = Request(rid=1, prompt=_prompt(40, 5), max_new_tokens=30)
    sched.submit(a)
    sched.submit(b)
    while not a.done:
        sched.step()
    # a retired with a tiny engine window: its jobs were cancelled from both
    # shard queues, b's queued writes survive on both shards
    for tier in sched.backend.tiers:
        for q in tier.engine.queue._queues.values():
            assert all(job.seq_id in (None, (tier.index, 1)) for job in q)
    assert sched.stats["engine_jobs_cancelled"] > 0
    sched.run_until_drained()
    assert b.done


# ---------------------------------------------------------------------------
# Admission backpressure (satellite)
# ---------------------------------------------------------------------------


def test_admission_backpressure_defers_and_recovers(smoke_model):
    """With the lane engine saturated past admit_latency_ns_max, a new
    submit waits in the queue (counted), then admits once the backlog
    drains; without a threshold it admits immediately."""
    model, params = smoke_model

    def run(limit):
        # paged pinned: the deferral logic is backend-independent scheduler
        # code, but the trip point depends on total lane count — sharded
        # instantiates the 1-lane geometry PER SHARD and halves the
        # pressure, so the threshold is calibrated for one tier
        sched = ContinuousScheduler(model, params, EngineConfig(
            max_batch=2, max_ctx=192, store_layers=2, backend="paged",
            engine=MemCtlConfig(lanes=1, step_cycles=64),
            admit_latency_ns_max=limit,
        ))
        a = Request(rid=0, prompt=_prompt(80), max_new_tokens=12)
        b = Request(rid=1, prompt=_prompt(40, 5), max_new_tokens=4)
        sched.submit(a)
        for _ in range(3):
            sched.step()
        sched.submit(b)
        sched.run_until_drained()
        assert a.done and b.done
        return b, sched.report()

    b, rep = run(limit=200.0)
    assert rep["admits_deferred"] > 0
    assert rep["backpressure_steps"] > 0
    assert b.admit_step - b.arrival_step >= rep["backpressure_steps"]
    assert rep["admit_pressure_ns"] == 0.0  # drained by the end

    b0, rep0 = run(limit=None)
    assert rep0["admits_deferred"] == 0
    assert b0.admit_step == b0.arrival_step


# ---------------------------------------------------------------------------
# Ring backend: sliding-window configs join continuous batching
# ---------------------------------------------------------------------------


def test_ring_backend_matches_model_loop(ring_model):
    """Per-slot ring serving decodes exactly what the scalar ring decode
    loop decodes — including a prompt longer than the window (the dead
    prefix is masked and skipped)."""
    model, params = ring_model
    cfg = EngineConfig(max_batch=2, max_ctx=96, backend="ring",
                       store_layers=2)
    prompts = [_prompt(40), _prompt(70, 9)]  # 70 > window=32
    sched, reqs = _serve(model, params, cfg, prompts, max_new=8)
    for r, p in zip(reqs, prompts):
        assert r.output == _reference_greedy(model, params, p, 8, 96), r.rid


def test_ring_backend_mixed_lengths_batch(ring_model):
    """Heterogeneous ring slots decode at their own positions in one batch
    and retire at their own step."""
    model, params = ring_model
    cfg = EngineConfig(max_batch=2, max_ctx=96, backend="ring",
                       store_layers=1)
    sched = ContinuousScheduler(model, params, cfg)
    short = Request(rid=0, prompt=_prompt(20), max_new_tokens=4)
    long = Request(rid=1, prompt=_prompt(50, 3), max_new_tokens=24)
    sched.submit(short)
    sched.submit(long)
    sched.run_until_drained()
    assert short.done and len(short.output) == 4
    assert long.done and len(long.output) == 24
    assert short.finish_step < long.finish_step
    assert short.output == _reference_greedy(model, params, _prompt(20), 4, 96)
    assert long.output == _reference_greedy(model, params, _prompt(50, 3), 24, 96)


def test_ring_pages_retire_with_the_window(ring_model):
    """The compressed tier tracks the LIVE window, not the whole context:
    resident pages stay bounded by the window (+1 boundary page per
    stream/layer) and dead pages leave without eviction accounting."""
    model, params = ring_model
    w = model.cfg.attn_window
    cfg = EngineConfig(max_batch=1, max_ctx=96, backend="ring",
                       store_layers=1)
    sched = ContinuousScheduler(model, params, cfg)
    r = Request(rid=0, prompt=_prompt(24), max_new_tokens=60)
    sched.submit(r)
    max_resident = 0
    while sched.has_work():
        sched.step()
        max_resident = max(max_resident,
                           sched.backend.store.footprint()["pages"])
    assert r.done
    # 1 layer x 2 streams x (window pages + 1 boundary + 1 growing tail)
    assert max_resident <= 2 * (w // PAGE_TOKENS + 2)
    assert sched.report()["kv_evictions"] == 0  # dead, never "evicted"


def test_ring_backend_serves_mixtral_family():
    """The ROADMAP item verbatim: a Mixtral-family (MoE + sliding-window)
    config joins continuous batching through the ring backend, and the
    paged backend still refuses it."""
    cfg_m = get_config("mixtral-8x7b", smoke=True)
    assert 0 < cfg_m.attn_window
    model = build_model(cfg_m)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="ring"):
        ContinuousScheduler(model, params,
                            EngineConfig(max_batch=2, max_ctx=128,
                                         backend="paged"))
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=2, max_ctx=128, backend="ring", store_layers=1))
    reqs = [Request(rid=i, prompt=_prompt(30 + 20 * i, i), max_new_tokens=5)
            for i in range(3)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    assert all(r.done and len(r.output) == 5 for r in reqs)


def test_ring_slot_reuse_clears_stale_positions(ring_model):
    """A retired request's ring entries must not leak into the next request
    admitted into the same slot: stale positions BELOW the newcomer's
    valid range would pass the position mask and poison its attention
    (the dense cache is immune — index==position — the ring is not)."""
    model, params = ring_model
    cfg = EngineConfig(max_batch=1, max_ctx=96, backend="ring",
                       store_layers=1)
    sched = ContinuousScheduler(model, params, cfg)
    a = Request(rid=0, prompt=_prompt(20), max_new_tokens=4)
    sched.submit(a)
    sched.run_until_drained()
    assert a.done
    # slot 0 is reused by a LONGER request: its early positions overlap the
    # retiree's stale entries, which is exactly the poisoned regime
    b = Request(rid=1, prompt=_prompt(40, 5), max_new_tokens=6)
    sched.submit(b)
    sched.run_until_drained()
    assert b.output == _reference_greedy(model, params, _prompt(40, 5), 6, 96)


def test_ring_backend_rejects_full_attention(smoke_model):
    model, params = smoke_model  # attn_window == 0
    with pytest.raises(ValueError, match="full attention"):
        ContinuousScheduler(model, params,
                            EngineConfig(max_ctx=64, backend="ring"))


# ---------------------------------------------------------------------------
# Bit-plane device KV (ISSUE 5): the ladder's bytes become wall-clock bytes
# ---------------------------------------------------------------------------

FULL_LADDER = PrecisionLadder([(-1, 16)])  # keep=16 everywhere: lossless


@pytest.mark.parametrize("backend,shards", BACKEND_CASES)
def test_bitplane_full_precision_is_bit_identical(smoke_model, backend,
                                                  shards):
    """device_kv='bitplane' at keep=16 serves bit-identical greedy tokens
    to the dense device path on every backend: bf16 <-> bit-plane packing
    is a bitcast, so the Pallas rung kernel reads exactly the dense
    cache's values."""
    model, params = smoke_model
    prompts = [_prompt(37), _prompt(80, 11)]

    def run(device_kv, ladder):
        _, reqs = _serve(
            model, params,
            _cfg(backend, shards, device_kv=device_kv, ladder=ladder),
            prompts, max_new=6,
        )
        return [r.output for r in reqs]

    dense = run("dense", None)
    assert run("bitplane", None) == dense
    assert run("bitplane", FULL_LADDER) == dense  # assigned, all 16 planes


def test_bitplane_ring_full_precision_is_bit_identical(ring_model):
    """Same conformance through the ring backend — per-slot sliding-window
    planes, including a prompt longer than the window."""
    model, params = ring_model

    def run(device_kv):
        cfg = EngineConfig(max_batch=2, max_ctx=96, backend="ring",
                           store_layers=2, device_kv=device_kv)
        _, reqs = _serve(model, params, cfg,
                         [_prompt(40), _prompt(70, 9)], max_new=8)
        return [r.output for r in reqs]

    assert run("bitplane") == run("dense")


@pytest.mark.parametrize("backend,shards", BACKEND_CASES + [("ring", 1)])
def test_bitplane_device_bytes_equal_controller_kv_read(
        smoke_model, ring_model, backend, shards):
    """ISSUE 5 acceptance: under a mixed ladder — with eviction thrash and
    engine windows small enough to defer fetches across steps — the device
    path's bytes (``device_bytes_read``, accumulated per serviced fetch at
    the planes the kernel maps) equal the controller's plane-scaled kv_read
    bytes exactly, and sit strictly below the dense path's full-precision
    reads."""
    model, params = (ring_model if backend == "ring" else smoke_model)
    # ring: the 32-token window holds only 2 live pages, which LADDER's
    # top rung would keep at full precision wholesale — rank just one
    ladder = (PrecisionLadder([(1, 16), (-1, 4)]) if backend == "ring"
              else LADDER)
    kw = dict(
        device_kv="bitplane", ladder=ladder, max_stored_bytes=10 * 1024,
        engine=MemCtlConfig(lanes=2, step_cycles=512),
        # resident weights: this window is sized for KV-only thrash; a
        # streamed weight pass outranks KV_WRITE and would starve the
        # eviction path this test exists to pin
        weight_stream="resident",
    )
    cfg = (_cfg(backend, shards, **kw) if backend != "ring" else
           EngineConfig(max_batch=2, max_ctx=96, backend="ring",
                        store_layers=2, **kw))
    sched, _ = _serve(model, params, cfg, [_prompt(80), _prompt(80, 3)],
                      max_new=16)
    rep = sched.report()
    assert rep["kv_evictions"] > 0  # the budget really thrashed
    dev_controller = sum(t.controller.stats.kind_device_bytes("kv_read")
                         for t in sched.backend.tiers)
    assert rep["device_bytes_read"] == dev_controller > 0
    assert rep["device_bytes_read"] == rep["kv_read_device_bytes"]
    assert rep["device_bytes_read"] < rep["kv_fetch_logical"]


def test_dense_device_path_exposes_accounting_gap(smoke_model):
    """The dense device cache reads full precision no matter what the
    ladder charges: device_bytes_read == the pad-free logical fetch bytes,
    strictly above the plane-scaled accounting — the gap the bit-plane
    layout exists to close."""
    model, params = smoke_model
    sched, _ = _serve(model, params,
                      _cfg("paged", 1, device_kv="dense", ladder=LADDER),
                      [_prompt(80)], max_new=8)
    rep = sched.report()
    assert rep["device_bytes_read"] == rep["kv_fetch_logical"] > 0
    assert rep["device_bytes_read"] > rep["kv_read_device_bytes"]


def test_bitplane_ladder_reranks_reach_the_device_plane_map(smoke_model):
    """_assign_ladder_planes must push each re-rank into the device cache's
    per-page plane map: what the NEXT decode step's kernel reads is what
    the store will charge.  Values are snapped to the ladder's rung set
    (== the static keeps the kernel compiled for)."""
    model, params = smoke_model
    sched = ContinuousScheduler(model, params,
                                _cfg("paged", 1, device_kv="bitplane",
                                     ladder=LADDER))
    sched.submit(Request(rid=0, prompt=_prompt(80), max_new_tokens=40))
    for _ in range(3):
        sched.step()
    backend = sched.backend
    keeps = set(backend.device_keeps())
    st = backend._slots[0]
    assert st.page_planes, "the 80-token prompt must have ranked pages"
    row = np.asarray(backend.cache["planes"])[0]
    assert set(row.tolist()) <= keeps
    for p, keep in st.page_planes.items():
        assert keep in keeps
        assert row[p] == keep, (p, keep, row)
    # decode until another page fills -> a re-rank happened; map follows
    before = dict(st.page_planes)
    while dict(backend._slots[0].page_planes) == before:
        sched.step()
    row2 = np.asarray(backend.cache["planes"])[0]
    for p, keep in backend._slots[0].page_planes.items():
        assert row2[p] == keep
    sched.run_until_drained()


def test_ring_bitplane_head_reclaims_rows_at_full_precision(ring_model):
    """Boundary policy, pinned at the exact page-aligned step: the moment
    the NEXT append would land in a ranked page's first device row, that
    page's plane-map entry falls back to full precision — the newest token
    must never be attended at a dying page's truncated rung."""
    model, params = ring_model  # window = 32 -> 2 device pages
    cfg = EngineConfig(max_batch=1, max_ctx=96, backend="ring",
                       store_layers=1, device_kv="bitplane",
                       ladder=PrecisionLadder([(-1, 4)]))
    sched = ContinuousScheduler(model, params, cfg)
    sched.submit(Request(rid=0, prompt=_prompt(40), max_new_tokens=40))
    while int(sched._lens[0]) < 47:
        sched.step()
    # ln == 47: page 1 (ring rows 16..31) is still fully its own -> rung 4
    assert np.asarray(sched.backend.cache["planes"])[0, 1] == 4
    sched.step()
    # ln == 48: the next append lands at ring slot 16 — page 1's first row
    assert np.asarray(sched.backend.cache["planes"])[0, 1] == 16
    sched.run_until_drained()


# ---------------------------------------------------------------------------
# Fused single-kernel ladder decode (ISSUE 6)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,shards",
                         [("paged", 1), ("sharded", 2), ("ring", 1)])
def test_fused_decode_matches_rung_across_backends(smoke_model, ring_model,
                                                   backend, shards):
    """ISSUE 6 acceptance: decode_kernel='fused' serves bit-identical
    greedy tokens to the per-rung path on every backend under a MIXED
    ladder, with decode running long enough to fill pages mid-stream (the
    ladder re-ranks and the per-page plane map changes under the kernel)."""
    model, params = (ring_model if backend == "ring" else smoke_model)
    ladder = (PrecisionLadder([(1, 16), (-1, 4)]) if backend == "ring"
              else LADDER)

    def run(kernel):
        kw = dict(device_kv="bitplane", ladder=ladder, decode_kernel=kernel)
        cfg = (_cfg(backend, shards, **kw) if backend != "ring" else
               EngineConfig(max_batch=2, max_ctx=96, backend="ring",
                            store_layers=2, **kw))
        _, reqs = _serve(model, params, cfg, [_prompt(80), _prompt(37, 5)],
                         max_new=20)
        return [r.output for r in reqs]

    assert run("fused") == run("rung")


def test_fused_compile_count_one_per_model_config():
    """ISSUE 6 satellite: under a 64-request trace whose ladder re-ranks
    across every rung, the fused path traces exactly ONE Pallas decode
    kernel for the whole run; the rung path traces one per member of the
    static rung set.  (Kernel bodies bump ``TRACE_COUNTS`` at trace time,
    so a re-trace anywhere in the trace would show up here.)"""
    from repro.kernels.paged_attention import kernel as K

    mcfg = get_config("smollm-135m", smoke=True)
    params = build_model(mcfg).init(jax.random.PRNGKey(0))
    for kernel in ("fused", "rung"):
        model = build_model(mcfg)  # fresh object -> fresh scheduler jits
        K.paged_attention_fused.clear_cache()
        K.paged_attention_rung.clear_cache()
        K.TRACE_COUNTS["fused"] = K.TRACE_COUNTS["rung"] = 0
        sched = ContinuousScheduler(
            model, params,
            EngineConfig(max_batch=8, max_ctx=192, store_layers=1,
                         device_kv="bitplane", ladder=LADDER,
                         decode_kernel=kernel))
        for i in range(64):
            sched.submit(Request(rid=i, prompt=_prompt(17 + (i % 5) * 13, i),
                                 max_new_tokens=4))
        sched.run_until_drained()
        keeps = sched.backend.device_keeps()
        assert len(keeps) > 1, "mixed ladder must produce a multi-rung set"
        want_rung = len(keeps) if kernel == "rung" else 0
        assert K.TRACE_COUNTS["fused"] == (1 if kernel == "fused" else 0), (
            kernel, dict(K.TRACE_COUNTS))
        assert K.TRACE_COUNTS["rung"] == want_rung, (
            kernel, dict(K.TRACE_COUNTS))


# ---------------------------------------------------------------------------
# Staged decode under continuous batching (ISSUE 6 satellite)
# ---------------------------------------------------------------------------


def test_staged_decode_matches_unstaged(smoke_model):
    """decode_staging > 0 on the paged backend (device_kv='dense') serves
    greedy tokens identical to the unstaged cache — across prefill joins at
    four different anchors, multiple flushed staging windows per row, and
    page-fill store writes that span the main-cache/staging-ring boundary.

    (The staged path merges two attention partials where the plain path
    sums once; the orders agree to the last ulp, so — as in
    ``test_staged_decode_cache_matches_plain`` — an exact bf16 logit tie
    could flip argmax without a real defect.  This trace has no such tie.)
    """
    model, params = smoke_model
    cfg_st = dataclasses.replace(get_config("smollm-135m", smoke=True),
                                 decode_staging=4)
    model_st = build_model(cfg_st)
    prompts = [_prompt(20), _prompt(27, 1), _prompt(34, 2), _prompt(41, 3)]
    kw = dict(device_kv="dense", ladder=LADDER)
    _, staged = _serve(model_st, params, _cfg("paged", 1, **kw), prompts,
                       max_new=12)
    _, ref = _serve(model, params, _cfg("paged", 1, **kw), prompts,
                    max_new=12)
    assert [r.output for r in staged] == [r.output for r in ref]


def test_staged_decode_unsupported_combinations_raise():
    """The PR-4 blanket raise is gone: staged decode works on paged/dense,
    and every other combination names itself in a precise ValueError."""
    base = get_config("smollm-135m", smoke=True)
    model_st = build_model(dataclasses.replace(base, decode_staging=4))
    with pytest.raises(ValueError, match="device_kv='dense'"):
        make_backend(model_st, _cfg("paged", 1, device_kv="bitplane"))
    with pytest.raises(ValueError, match="sharded"):
        make_backend(model_st, _cfg("sharded", 2, device_kv="dense"))
    model_ring = build_model(dataclasses.replace(base, attn_window=32,
                                                 decode_staging=4))
    with pytest.raises(ValueError, match="ring"):
        make_backend(model_ring, EngineConfig(max_batch=2, max_ctx=96,
                                              backend="ring"))


def test_bitplane_rejects_unpackable_head_dim(smoke_model):
    """The int32 plane layout packs KV elements, not bytes, so every
    head_dim packs (head_dim 12 included).  What it cannot pack is a
    partial page: a max_ctx that is not a whole number of 16-token pages
    is refused up front, as is an unknown device_kv."""
    cfg_odd = dataclasses.replace(get_config("smollm-135m", smoke=True),
                                  head_dim=12, n_heads=4, n_kv_heads=2)
    backend = make_backend(build_model(cfg_odd),
                           _cfg("paged", 1, device_kv="bitplane"))
    assert backend.ensure_cache()["k_planes"].dtype == jnp.int32
    with pytest.raises(ValueError, match="max_ctx"):
        make_backend(smoke_model[0], EngineConfig(
            max_batch=2, max_ctx=100, prefill_mode="padded",
            device_kv="bitplane"))
    with pytest.raises(ValueError, match="device_kv"):
        make_backend(smoke_model[0], _cfg("paged", 1, device_kv="fp4"))


# ---------------------------------------------------------------------------
# Weight streaming conformance (ISSUE 9)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,shards", BACKEND_CASES)
def test_weight_stream_tokens_bit_identical(smoke_model, backend, shards):
    """Streamed block-compressed weights are lossless end to end: greedy
    tokens under weight_stream='compressed' equal 'resident' exactly, on
    every tier topology, while report()['weights'] carries real traffic."""
    model, params = smoke_model
    prompts = [_prompt(37), _prompt(64, 9)]

    def run(mode):
        sched, reqs = _serve(model, params,
                             _cfg(backend, shards, weight_stream=mode),
                             prompts, max_new=8)
        return sched.report(), [r.output for r in reqs]

    rep_r, out_r = run("resident")
    rep_c, out_c = run("compressed")
    assert out_r == out_c, (backend, shards)
    assert rep_r["weights"] == {"mode": "resident"}
    w = rep_c["weights"]
    assert w["mode"] == "compressed"
    assert w["read_logical_bytes"] > 0
    assert 0.0 < w["bandwidth_saving"] < 1.0
    # KV accounting is untouched by the weight traffic riding the lanes
    for key in ("kv_logical_bytes", "kv_stored_bytes", "kv_fetch_logical",
                "kv_fetch_physical"):
        assert rep_r[key] == rep_c[key], key
    # the lane-budget split now has a WEIGHT_FETCH share
    assert rep_c["engine"]["serviced_bytes"]["WEIGHT_FETCH"] > 0


def test_ring_weight_stream_tokens_bit_identical(ring_model):
    """Same lossless contract on the sliding-window tier."""
    model, params = ring_model
    prompts = [_prompt(20), _prompt(41, 5)]

    def run(mode):
        sched, reqs = _serve(
            model, params,
            EngineConfig(max_batch=2, max_ctx=96, backend="ring",
                         store_layers=2, weight_stream=mode),
            prompts, max_new=10)
        return sched.report(), [r.output for r in reqs]

    rep_r, out_r = run("resident")
    rep_c, out_c = run("compressed")
    assert out_r == out_c
    assert rep_c["weights"]["bandwidth_saving"] > 0.0
    assert (rep_c["weights"]["passes_fetched"]
            >= rep_c["weights"]["passes_consumed"])


def test_weight_stream_rejects_unknown_mode(smoke_model):
    with pytest.raises(ValueError, match="weight_stream"):
        make_backend(smoke_model[0], _cfg("paged", 1, weight_stream="mmap"))


# ---------------------------------------------------------------------------
# Batched decode page-fill copy (one compiled gather, one transfer a step)
# ---------------------------------------------------------------------------


def _fill_cfg(backend, device_kv, **kw):
    if backend == "ring":
        return EngineConfig(max_batch=3, max_ctx=96, backend="ring",
                            store_layers=2, device_kv=device_kv, **kw)
    return _cfg(backend, 2 if backend == "sharded" else 1,
                device_kv=device_kv, **kw)


@pytest.mark.parametrize("filling", ["one", "all"])
@pytest.mark.parametrize("backend", ["paged", "ring", "sharded"])
@pytest.mark.parametrize("device_kv", ["dense", "bitplane"])
def test_batched_fill_copy_is_bitwise_slot_kv_host(
        smoke_model, ring_model, device_kv, backend, filling):
    """The batched page-fill copy returns, for each slot it is given,
    bitwise the K and V ``slot_kv_host(slot, ln-16, ln)`` copies — on both
    device layouts, through paged, ring (pages folded into the window)
    and two-shard backends, for one slot of the batch or all of them in
    an order of their own."""
    model, params = ring_model if backend == "ring" else smoke_model
    sched = ContinuousScheduler(model, params,
                                _fill_cfg(backend, device_kv))
    for i, n in enumerate([37, 50, 29]):
        sched.submit(Request(rid=i, prompt=_prompt(n, i),
                             max_new_tokens=60))
    be = sched.backend
    lens = {}
    while len(lens) < 3 or min(lens.values()) < 40:
        sched.step()
        lens = {i: int(sched._lens[i]) for i, slot in enumerate(sched._slots)
                if slot is not None and not slot.prefilling}
    fills = [(s, ln // PAGE_TOKENS * PAGE_TOKENS)
             for s, ln in sorted(lens.items(), reverse=True)]
    if filling == "one":
        fills = fills[1:2]
    got = be._copy_fill_pages(fills)
    assert sorted(got) == sorted(s for s, _ in fills)
    for s, ln in fills:
        want = be.slot_kv_host(s, ln - PAGE_TOKENS, ln)
        for g, w in zip(got[s], want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.any(w != 0)
            np.testing.assert_array_equal(g.view(np.uint16),
                                          w.view(np.uint16))


@pytest.mark.parametrize("backend,device_kv", [
    ("paged", "dense"), ("paged", "bitplane"), ("sharded", "bitplane"),
    ("ring", "bitplane")])
def test_batched_fill_job_stream_matches_per_slot_copy(
        smoke_model, ring_model, monkeypatch, backend, device_kv):
    """Pages filling in the same step and in different steps: the memctl
    job stream (tier, class, key, bytes, scope, in order), the backend's
    ``report()`` and the served tokens equal those of a run whose slots
    commit one by one, each copying its filled page through
    ``slot_kv_host``.  The step log counts each fill in ``fill_pages``, a
    step's fills add one transfer to ``d2h_copies``, and no step from the
    first fill on compiles the copy."""
    from repro.memctl.runtime import CompressionEngineRuntime
    from repro.serving.backends.base import KVBackend, fill_pages
    from repro.telemetry import StepLog, steps

    model, params = ring_model if backend == "ring" else smoke_model
    # the ladder re-ranks at every fill, and a store budget that evicts
    # re-activates pages through slot_kv_host; both cost the bit-plane
    # layout's eager unpack a minute in the interpreter, so the dense case
    # carries both and the bit-plane paged cases neither
    ladder = {("ring", "bitplane"): PrecisionLadder([(1, 16), (-1, 4)]),
              ("paged", "dense"): LADDER}.get((backend, device_kv))
    budget = 16 * 1024 if device_kv == "dense" else None
    jobs = []
    real_submit = CompressionEngineRuntime.submit

    def submit(engine, job):
        jobs.append((engine.tier, job.klass, job.key, job.nbytes,
                     job.seq_id))
        return real_submit(engine, job)

    monkeypatch.setattr(CompressionEngineRuntime, "submit", submit)

    def run(spy):
        jobs.clear()
        sched = ContinuousScheduler(model, params, _fill_cfg(
            backend, device_kv, ladder=ladder, max_stored_bytes=budget))
        be = sched.backend
        fills, copies, sizes = {}, {}, []
        if spy:
            real_tokens, real_host = be.on_decode_tokens, be.slot_kv_host

            def on_decode_tokens(tokens):
                n = sum(ln % PAGE_TOKENS == 0 for _, ln in tokens)
                fills[sched.step_count] = n
                if n:
                    sizes.append(fill_pages._cache_size())
                real_tokens(tokens)

            def slot_kv_host(*a, **kw):
                copies[sched.step_count] = copies.get(sched.step_count, 0) + 1
                return real_host(*a, **kw)

            be.on_decode_tokens = on_decode_tokens
            be.slot_kv_host = slot_kv_host
        reqs = [Request(rid=i, prompt=_prompt(n, i), max_new_tokens=40)
                for i, n in enumerate([32, 48, 37])]
        for r in reqs:
            sched.submit(r)
        sched.run_until_drained()
        assert all(r.done for r in reqs)
        return (list(jobs), be.report(), [r.output for r in reqs],
                fills, copies, sizes)

    log = StepLog()
    monkeypatch.setattr(steps, "LOG", log)
    jobs_b, rep_b, out_b, fills, copies, sizes = run(spy=True)
    recs = list(log.records)

    def per_slot(be, tokens):
        # each slot's commit copies its own filled page (slot_kv_host)
        be._note_compute()
        for s, ln in tokens:
            be._commit_decode_token(s, ln, None)

    monkeypatch.setattr(KVBackend, "on_decode_tokens", per_slot)
    jobs_p, rep_p, out_p, *_ = run(spy=False)

    assert out_b == out_p
    assert jobs_b == jobs_p
    assert any(j[1] == JobClass.KV_WRITE for j in jobs_b)
    assert rep_b["kv_evictions"] > 0 or budget is None
    assert rep_b == rep_p
    # the trace fills pages both together and alone
    assert max(fills.values()) >= 2 and 1 in fills.values()
    assert sum(r["fill_pages"] for r in recs) == sum(fills.values())
    for r in recs:
        n = fills.get(r["step"], 0)
        assert r["fill_pages"] == n
        assert r["d2h_copies"] == 2 * copies.get(r["step"], 0) + (n > 0)
    assert sizes and sizes[0] >= 1 and set(sizes) == {
        fill_pages._cache_size()}


def test_staged_decode_fills_copy_slot_by_slot(smoke_model, monkeypatch):
    """Staged decode keeps the per-slot copy: a filled page's newest rows
    may still sit in the staging ring.  Every decode fill is copied by
    ``slot_kv_host`` at [ln-16, ln), and the batched copy serves none."""
    from repro.telemetry import StepLog, steps

    _, params = smoke_model
    model_st = build_model(dataclasses.replace(
        get_config("smollm-135m", smoke=True), decode_staging=4))
    log = StepLog()
    monkeypatch.setattr(steps, "LOG", log)
    sched = ContinuousScheduler(model_st, params,
                                _cfg("paged", 1, device_kv="dense"))
    be = sched.backend
    spans, fills = [], []
    real_tokens, real_host = be.on_decode_tokens, be.slot_kv_host

    def on_decode_tokens(tokens):
        fills.extend((s, ln - PAGE_TOKENS, ln) for s, ln in tokens
                     if ln % PAGE_TOKENS == 0)
        real_tokens(tokens)

    def slot_kv_host(s, t0, t1, **kw):
        spans.append((s, t0, t1))
        return real_host(s, t0, t1, **kw)

    be.on_decode_tokens = on_decode_tokens
    be.slot_kv_host = slot_kv_host
    for i, n in enumerate([20, 27]):
        sched.submit(Request(rid=i, prompt=_prompt(n, i), max_new_tokens=30))
    sched.run_until_drained()
    assert not be._batched_fills()
    assert fills and set(fills) <= set(spans)
    assert sum(r["fill_pages"] for r in log.records) == 0
