"""Decode fetch accounting's page-key index against a full probe.

``KVBackend._account_step_fetch`` keeps, per slot, which live page keys wait
on a queued write (counted in bulk) and probes only the others.  Each
backend here is served twice over the same mixed trace: once as shipped and
once through a test-only subclass whose ``_account_step_fetch`` is the full
probe of every live key on every step (the body before the index, kept
verbatim below).  Writes starve under a small lane window, as in the
benchmark's cells.  Every engine must receive the same jobs in the same
order at every step, and the report, the stats and the tokens must match.
"""

import dataclasses

import numpy as np
import pytest

import jax

from repro.configs.base import get_config
from repro.memctl import JobClass, MemCtlConfig
from repro.memctl.runtime import CompressionEngineRuntime
from repro.models.model import build_model
from repro.serving import ContinuousScheduler, EngineConfig, Request
from repro.serving.backends import (
    BACKENDS, PagedBackend, RingBackend, ShardedBackend,
)
from repro.serving.backends.base import STREAMS, make_fetch_job
from repro.telemetry import StepLog, steps


def _full_probe_account_step_fetch(self, slot_id: int, ln: int) -> None:
    """The reference: every live key of the slot, probed one by one."""
    st = self._slots[slot_id]
    rid = st.rid
    p0, n_pages = self._live_page_range(st)
    for li in range(self.stored_layers()):
        for stream in ("k", "v"):
            for p in range(p0, n_pages):
                key = self._slot_key(st, li, p, stream)
                # shared pages fetch at THIS holder's ladder assignment
                # (the store hint is whichever holder re-ranked last)
                keep_fn = (None if key.seq_id == rid
                           else lambda st=st, p=p: st.page_planes.get(p))
                kt = key.astuple()
                reactivate = []
                for tier, cols in self._page_targets(key):
                    if tier.store.contains(key):
                        tier.engine.submit(make_fetch_job(
                            tier.store, self.stats, key,
                            self._seq_key(tier, rid),
                            device_kv=self.device_kv,
                            telemetry=self.telemetry,
                            rid=rid, keep_fn=keep_fn,
                        ))
                    elif (tier.engine.pending(kt, JobClass.KV_WRITE)
                          or tier.engine.pending(kt, JobClass.BACKGROUND)):
                        # write or re-activation already queued — only
                        # those classes restore the page; a stale queued
                        # fetch must not suppress the re-activation
                        self.stats["kv_fetch_deferrals"] += 1
                    elif self._can_reactivate(st, p, ln):
                        reactivate.append((tier, cols))
                    else:
                        # ring: the window slid over part of the page's
                        # device rows — nothing left to re-compress, and
                        # the page dies shortly anyway
                        self.stats["kv_fetch_misses"] += 1
                if reactivate:
                    self._reactivate(slot_id, key, reactivate)


class FullProbePaged(PagedBackend):
    _account_step_fetch = _full_probe_account_step_fetch


class FullProbeSharded(ShardedBackend):
    _account_step_fetch = _full_probe_account_step_fetch


class FullProbeRing(RingBackend):
    def _account_step_fetch(self, slot_id: int, ln: int) -> None:
        if self.device_kv == "bitplane":
            self._sync_ring_planes(slot_id, self._slots[slot_id], ln)
        _full_probe_account_step_fetch(self, slot_id, ln)


FULL_PROBE = {"paged": FullProbePaged, "sharded": FullProbeSharded,
              "ring": FullProbeRing}


@pytest.fixture(scope="module")
def models():
    base = get_config("smollm-135m", smoke=True)
    out = {}
    for name, cfg in (("full", base),
                      ("ring", dataclasses.replace(base, attn_window=32))):
        model = build_model(cfg)
        out[name] = (model, model.init(jax.random.PRNGKey(0)))
    return out


def _prompt(n, offset=0):
    return ((np.arange(n) + offset) % 500).astype(np.int32)


#: (arrival step, prompt, new tokens): ragged prompt tails, slots that
#: retire and are reused, pages that fill together and alone
MIXED = [(0, _prompt(37), 44), (0, _prompt(21, 5), 30),
         (2, _prompt(50, 9), 36), (9, _prompt(29, 3), 40),
         (16, _prompt(18, 7), 28)]
#: a 48-token shared prefix: two cold writers at once, then followers
SHARED = [(0, np.concatenate([_prompt(48), _prompt(7, 100)]), 30),
          (0, np.concatenate([_prompt(48), _prompt(12, 200)]), 26),
          (6, np.concatenate([_prompt(48), _prompt(9, 300)]), 30),
          (14, np.concatenate([_prompt(48), _prompt(5, 400)]), 24)]
#: three cold writers of a 32-token shared prefix that retire at different
#: steps, one unshared prompt, one late follower
SHARED_COLD = [(0, np.concatenate([_prompt(32), _prompt(7, 100)]), 8),
               (0, np.concatenate([_prompt(32), _prompt(12, 200)]), 40),
               (0, np.concatenate([_prompt(32), _prompt(9, 300)]), 20),
               (5, _prompt(40, 7), 30),
               (8, np.concatenate([_prompt(32), _prompt(5, 400)]), 24)]

#: name -> (backend, shards, device_kv, model, trace, extra config)
CASES = {
    "paged-dense": ("paged", 1, "dense", "full", MIXED, {}),
    "paged-bitplane": ("paged", 1, "bitplane", "full", MIXED, {}),
    "ring-dense": ("ring", 1, "dense", "ring", MIXED, {}),
    "ring-bitplane": ("ring", 1, "bitplane", "ring", MIXED, {}),
    "sharded2-dense": ("sharded", 2, "dense", "full", MIXED, {}),
    "sharded2-bitplane": ("sharded", 2, "bitplane", "full", MIXED, {}),
    "prefix-sharing": ("paged", 1, "dense", "full", SHARED,
                       {"prefix_sharing": True}),
    "paged-evicting": ("paged", 1, "dense", "full", MIXED,
                       {"max_stored_bytes": 8 * 1024}),
    "ring-evicting": ("ring", 1, "dense", "ring", MIXED,
                      {"max_stored_bytes": 8 * 1024}),
    "sharded2-evicting": ("sharded", 2, "dense", "full", MIXED,
                          {"max_stored_bytes": 8 * 1024}),
    # evicted shared pages re-activated by one cold writer while another
    # defers them, and then the first retires (its queued jobs cancelled)
    "prefix-evicting": ("paged", 1, "dense", "full", SHARED_COLD,
                        {"prefix_sharing": True,
                         "max_stored_bytes": 4 * 1024}),
}


def _engine_cfg(backend, shards, device_kv, extra):
    return EngineConfig(max_batch=3, max_ctx=128, backend=backend,
                        shards=shards, store_layers=2, device_kv=device_kv,
                        engine=MemCtlConfig(step_cycles=12), **extra)


class JobLog:
    """What every engine receives, as (step, tier, class, key, scope,
    bytes), stamped with the step of the scheduler being served."""

    def __init__(self):
        self.sched = None
        self.jobs = []


def _serve(model, params, cfg, trace, log, on_step=None):
    """Serve ``trace`` step by step; ``on_step(sched, stats_before)``
    runs after each step."""
    sched = ContinuousScheduler(model, params, cfg)
    log.sched, log.jobs = sched, []
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (_, p, n) in enumerate(trace)]
    due = sorted(zip((a for a, _, _ in trace), reqs), key=lambda x: x[0])
    while due or sched.has_work():
        while due and due[0][0] <= sched.step_count:
            sched.submit(due.pop(0)[1])
        before = dict(sched.stats)
        sched.step()
        if on_step is not None:
            on_step(sched, before)
    assert all(r.done for r in reqs)
    return sched, [r.output for r in reqs]


@pytest.fixture
def job_log(monkeypatch):
    log = JobLog()
    real_submit = CompressionEngineRuntime.submit

    def submit(engine, job):
        log.jobs.append((log.sched.step_count, engine.tier, job.klass,
                         job.key, job.seq_id, job.nbytes))
        return real_submit(engine, job)

    monkeypatch.setattr(CompressionEngineRuntime, "submit", submit)
    return log


def _deferred_but_not_queued(be):
    """Keys a slot's index counts as deferred, inside its live range, that
    are resident or have no queued write or re-activation: each would be
    a deferral the full probe does not count."""
    bad = []
    for st in be._slots.values():
        lo, hi = be._live_page_range(st)
        lo, hi = max(lo, st.fetch_range[0]), min(hi, st.fetch_range[1])
        for li, si, p, ti in st.fetch_deferred:
            if not lo <= p < hi:
                continue
            key = be._slot_key(st, li, p, STREAMS[si])
            tier, kt = be.tiers[ti], key.astuple()
            if tier.store.contains(key) or not (
                    tier.engine.pending(kt, JobClass.KV_WRITE)
                    or tier.engine.pending(kt, JobClass.BACKGROUND)):
                bad.append((st.rid, kt, ti))
    return bad


def _live_pairs(be, st):
    """(key, tier) pairs of the slot's live pages, as the full probe
    visits them."""
    p0, n_pages = be._live_page_range(st)
    for li in range(be.stored_layers()):
        for stream in ("k", "v"):
            for p in range(p0, n_pages):
                key = be._slot_key(st, li, p, stream)
                for tier, _cols in be._page_targets(key):
                    yield key, tier


@pytest.mark.parametrize("case", list(CASES))
def test_fetch_index_queues_what_the_full_probe_queues(models, monkeypatch,
                                                       job_log, case):
    backend, shards, device_kv, model_name, trace, extra = CASES[case]
    model, params = models[model_name]
    cfg = _engine_cfg(backend, shards, device_kv, extra)

    seen = {"resident_and_queued": 0}
    real = BACKENDS[backend]._account_step_fetch

    def watched(be, slot_id, ln):
        # a live key that is resident while its re-put is queued (a ragged
        # prompt tail whose page filled): both states hold at once
        for key, tier in _live_pairs(be, be._slots[slot_id]):
            if tier.store.contains(key) and tier.engine.pending(
                    key.astuple(), JobClass.KV_WRITE):
                seen["resident_and_queued"] += 1
        real(be, slot_id, ln)

    monkeypatch.setattr(BACKENDS[backend], "_account_step_fetch", watched)

    def check_index(sched, _before):
        assert not _deferred_but_not_queued(sched.backend), sched.step_count

    sched, out = _serve(model, params, cfg, trace, job_log,
                        on_step=check_index)
    got_jobs, got_report = job_log.jobs, sched.backend.report()
    got_stats = dict(sched.stats)

    monkeypatch.setitem(BACKENDS, backend, FULL_PROBE[backend])
    ref, ref_out = _serve(model, params, cfg, trace, job_log)
    ref_jobs = job_log.jobs
    assert type(ref.backend) is FULL_PROBE[backend]

    by_step = {}
    for j in ref_jobs:
        by_step.setdefault(j[0], []).append(j)
    for s in sorted(by_step):
        assert [j for j in got_jobs if j[0] == s] == by_step[s], s
    assert got_jobs == ref_jobs
    assert out == ref_out
    assert got_report == ref.backend.report()
    assert got_stats == dict(ref.stats)
    # the trace exercised what the index must follow
    assert got_stats["kv_fetch_deferrals"] > 0
    assert seen["resident_and_queued"] > 0
    if "max_stored_bytes" in extra:
        assert got_report["kv_evictions"] > 0
        assert got_stats["kv_reactivations"] > 0
    elif extra.get("prefix_sharing"):
        assert got_stats["prefix_requests_matched"] > 0


def test_fetch_counters_split_live_keys_into_probed_and_deferred(
        models, monkeypatch, job_log):
    """Under a starved engine, per step: ``fetch_probed`` is at most the
    decoding slots' resident keys (no page is evicted, so no other key is
    probed), ``fetch_deferred`` is the step's ``kv_fetch_deferrals``
    delta, and the two add up to every live key the full probe visits."""
    model, params = models["full"]
    cfg = _engine_cfg("paged", 1, "dense", {})
    log = StepLog()
    monkeypatch.setattr(steps, "LOG", log)
    live, resident = {}, {}
    real = PagedBackend._account_step_fetch

    def counted(be, slot_id, ln):
        s = job_log.sched.step_count
        for key, tier in _live_pairs(be, be._slots[slot_id]):
            live[s] = live.get(s, 0) + 1
            resident[s] = resident.get(s, 0) + tier.store.contains(key)
        real(be, slot_id, ln)

    monkeypatch.setattr(PagedBackend, "_account_step_fetch", counted)
    deltas = {}

    def on_step(sched, before):
        deltas[sched.step_count - 1] = (sched.stats["kv_fetch_deferrals"]
                                        - before["kv_fetch_deferrals"])

    _serve(model, params, cfg, MIXED, job_log, on_step=on_step)
    recs = {r["step"]: r for r in log.records}
    assert set(live) <= set(recs)
    for s, r in recs.items():
        assert r["fetch_deferred"] == deltas[s], s
        assert r["fetch_probed"] + r["fetch_deferred"] == live.get(s, 0), s
        assert r["fetch_probed"] <= resident.get(s, 0), s
    probed = sum(r["fetch_probed"] for r in recs.values())
    deferred = sum(r["fetch_deferred"] for r in recs.values())
    # writes starve: most live keys are counted in bulk
    assert deferred > probed > 0
    assert sum(live.values()) == probed + deferred
