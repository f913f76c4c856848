"""Serving telemetry: span lifecycle, byte attribution, exporters (ISSUE 7).

Pins the tentpole's contracts:

* every submitted request closes exactly ONE span, and each span's stamps
  are monotone in BOTH clock domains (host wall clock and modeled engine
  clock);
* per-request ``device_bytes_read`` attribution sums exactly to the run
  totals ``report()`` quotes — on all three backends;
* telemetry disabled (the default) records nothing and the served tokens
  and byte counters are bit-identical to an instrumented-but-off run;
* the Perfetto exporter emits schema-valid Chrome Trace Event JSON (the
  same gate CI runs on the benchmark artifact) and the Prometheus snapshot
  renders counters/quantiles in exposition format;
* ``aggregate_engine_reports`` pools per-step queue depths across shards
  (fleet backlog percentiles) instead of max-ing per-shard percentiles;
* the always-on step log (``repro.telemetry.steps``): its phases nest in
  ``serve.step`` in a profiler trace and fit inside their step's time, and
  its counters equal what the engine serviced, what the backend copied to
  the host, and what compiled.
"""

import dataclasses

import numpy as np
import pytest

import jax

from repro.configs.base import get_config
from repro.core.quantization import PrecisionLadder
from repro.memctl.runtime import CompressionEngineRuntime, aggregate_engine_reports
from repro.memctl import Job, JobClass, MemCtlConfig
from repro.models.model import build_model
from repro.serving import ContinuousScheduler, EngineConfig, Request
from repro.telemetry import (
    NULL_COLLECTOR,
    PHASES,
    StepLog,
    TelemetryCollector,
    TelemetryConfig,
    build_trace_events,
    prometheus_snapshot,
    quantiles,
    validate_trace,
    steps,
    write_perfetto_trace,
)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def ring_model():
    cfg = dataclasses.replace(get_config("smollm-135m", smoke=True),
                              attn_window=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


LADDER = PrecisionLadder([(2, 16), (2, 8), (-1, 4)])


def _prompt(n, offset=0):
    return ((np.arange(n) + offset) % 500).astype(np.int32)


def _cfg(backend="paged", shards=2, **kw):
    kw.setdefault("telemetry", TelemetryConfig())
    kw.setdefault("max_ctx", 192)
    return EngineConfig(max_batch=4, backend=backend,
                        shards=shards, store_layers=2, **kw)


def _serve(model, params, cfg, prompts, max_new=5):
    sched = ContinuousScheduler(model, params, cfg)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    assert all(r.done for r in reqs)
    return sched, reqs


# ---------------------------------------------------------------------------
# Span lifecycle invariants
# ---------------------------------------------------------------------------


def test_span_lifecycle_and_monotone_clocks(smoke_model):
    """Every submitted request closes exactly one span; each span's stamp
    list is monotone in the wall clock AND the engine clock, and records
    one token stamp per emitted token."""
    model, params = smoke_model
    prompts = [_prompt(37), _prompt(80, 11), _prompt(24, 5)]
    sched, reqs = _serve(model, params, _cfg(backend="paged", ladder=LADDER),
                         prompts)
    tel = sched.telemetry
    assert tel.enabled
    assert not tel.open_spans  # drained run: nothing left open
    assert sorted(sp.rid for sp in tel.closed_spans) == [r.rid for r in reqs]
    for sp, r in zip(sorted(tel.closed_spans, key=lambda s: s.rid), reqs):
        assert sp.prompt_tokens == len(r.prompt)
        assert sp.admit is not None and sp.first_token is not None
        assert sp.retire is not None and 0 <= sp.slot < sched.cfg.max_batch
        assert sp.new_tokens == len(r.output)
        assert len(sp.token_stamps) == sp.new_tokens
        assert sp.prefill_chunks and sp.prefill_chunks[-1][3]  # final chunk
        stamps = sp.stamps_in_order()
        for a, b in zip(stamps, stamps[1:]):
            assert b.wall_ns >= a.wall_ns, sp.rid
            assert b.engine_ns >= a.engine_ns, sp.rid
            assert b.step >= a.step, sp.rid
        assert sp.ttft_wall_ns() > 0
        assert sp.ttft_engine_ns() >= 0.0


def test_latency_report_quantile_shape(smoke_model):
    model, params = smoke_model
    sched, _ = _serve(model, params, _cfg(backend="paged"),
                      [_prompt(20), _prompt(33, 7)])
    rep = sched.report()
    lat = rep["latency"]
    assert lat["requests"] == 2
    for key in ("ttft_wall_ns", "ttft_engine_ns", "tpot_wall_ns",
                "tpot_engine_ns", "queue_wall_ns"):
        q = lat[key]
        assert set(q) == {"p50", "p95", "p99", "mean", "max", "count"}
        assert q["p50"] <= q["p95"] <= q["p99"] <= q["max"]
    assert lat["ttft_wall_ns"]["count"] == 2
    # summary block rides along
    assert rep["telemetry"]["spans_closed"] == 2
    # satellite: steady-state normalisation now includes the shed/truncated
    # request rates
    assert "requests_truncated" in rep["per_1k_requests"]
    assert "admits_deferred" in rep["per_1k_requests"]


# ---------------------------------------------------------------------------
# Per-request byte attribution (all three backends)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,shards,device_kv", [
    ("paged", 1, "bitplane"),
    ("sharded", 2, "dense"),
])
def test_attribution_sums_to_totals(smoke_model, backend, shards, device_kv):
    """Span-attributed fetch bytes sum EXACTLY to the run totals: device
    bytes to ``report()['device_bytes_read']``, controller-side bytes to
    the plane-scaled kv_read summed across tiers."""
    model, params = smoke_model
    sched, _ = _serve(model, params,
                      _cfg(backend=backend, shards=shards, ladder=LADDER,
                           device_kv=device_kv),
                      [_prompt(37), _prompt(80, 11), _prompt(24, 5)])
    rep = sched.report()
    att = sched.telemetry.attribution_report()
    assert rep["device_bytes_read"] > 0
    assert att["device_bytes_read"] == rep["device_bytes_read"]
    controller_total = sum(
        t.controller.stats.kind_device_bytes("kv_read")
        for t in sched.backend.tiers
    )
    assert att["controller_device_bytes"] == controller_total
    assert sched.telemetry.counts["fetches"] == sum(
        a["fetches"] for a in att["per_request"].values()
    )


def test_attribution_sums_on_ring_backend(ring_model):
    model, params = ring_model
    sched, _ = _serve(model, params,
                      _cfg(backend="ring", shards=1, ladder=LADDER,
                           max_ctx=128),
                      [_prompt(48), _prompt(70, 9)], max_new=6)
    rep = sched.report()
    att = sched.telemetry.attribution_report()
    assert rep["device_bytes_read"] > 0
    assert att["device_bytes_read"] == rep["device_bytes_read"]
    assert att["controller_device_bytes"] == sum(
        t.controller.stats.kind_device_bytes("kv_read")
        for t in sched.backend.tiers
    )


# ---------------------------------------------------------------------------
# Disabled telemetry: no events, bit-identical serving
# ---------------------------------------------------------------------------


def test_null_collector_records_nothing_and_serving_is_bit_identical(
        smoke_model):
    """The default (telemetry=None) wires the null collector: no spans, no
    events — and the served tokens AND byte counters are bit-identical to
    the telemetry-on run (observability must not perturb the system)."""
    model, params = smoke_model
    prompts = [_prompt(37), _prompt(60, 3)]

    def run(telemetry):
        sched, reqs = _serve(model, params,
                             _cfg(backend="paged", ladder=LADDER,
                                  device_kv="bitplane", telemetry=telemetry),
                             prompts)
        return sched, [r.output for r in reqs]

    sched_off, toks_off = run(telemetry=None)
    sched_on, toks_on = run(telemetry=TelemetryConfig())
    assert sched_off.telemetry is NULL_COLLECTOR
    assert not sched_off.telemetry.enabled
    # NullCollector is stateless: hooks resolve to no-ops, nothing is stored
    assert sched_off.telemetry.on_submit(0, 1) is None
    assert vars(NULL_COLLECTOR) == {}

    assert toks_off == toks_on
    rep_off, rep_on = sched_off.report(), sched_on.report()
    for key in ("device_bytes_read", "kv_read_device_bytes",
                "kv_logical_bytes", "kv_stored_bytes", "kv_fetch_logical",
                "kv_fetch_physical", "decode_tokens", "kv_evictions"):
        assert rep_off[key] == rep_on[key], key
    # the latency/telemetry blocks exist ONLY when enabled
    assert "latency" not in rep_off and "telemetry" not in rep_off
    assert "latency" in rep_on and "telemetry" in rep_on


def test_disabled_runtime_emits_no_engine_events():
    eng = CompressionEngineRuntime(MemCtlConfig(lanes=2, step_cycles=64))
    assert eng.telemetry is NULL_COLLECTOR
    eng.submit(Job(JobClass.KV_WRITE, 4096, fn=None, key=("p", 0)))
    eng.tick()
    # nothing recorded anywhere: the null collector has no storage at all
    assert vars(NULL_COLLECTOR) == {}


def test_enabled_runtime_records_engine_steps_and_lane_blocks():
    tel = TelemetryCollector(TelemetryConfig())
    eng = CompressionEngineRuntime(MemCtlConfig(lanes=2, step_cycles=64),
                                   telemetry=tel, tier=3)
    eng.submit(Job(JobClass.KV_WRITE, 4096, fn=None, key=("p", 0)))
    eng.tick()
    eng.tick()
    assert [r["tier"] for r in tel.engine_steps] == [3, 3]
    assert tel.engine_steps[0]["serviced_bytes"] > 0
    assert tel.engine_steps[0]["window_start_cycle"] == 0
    assert tel.engine_steps[1]["window_start_cycle"] == 64
    assert tel.lane_blocks and all(t == 3 for t, *_ in tel.lane_blocks)
    for _t, _lane, c0, c1, nb in tel.lane_blocks:
        assert c1 > c0 and nb > 0
    # raw queue-depth samples ride the report for pooled aggregation
    assert eng.report()["step_queue_depth"] == [0, 0]


def test_lane_block_cap_is_counted_not_silent():
    tel = TelemetryCollector(TelemetryConfig(max_lane_blocks=1))
    eng = CompressionEngineRuntime(MemCtlConfig(lanes=2, step_cycles=1024),
                                   telemetry=tel)
    eng.submit(Job(JobClass.KV_WRITE, 3 * 4096, fn=None, key=("p", 0)))
    eng.tick()
    assert len(tel.lane_blocks) == 1
    assert tel.counts["lane_blocks_dropped"] > 0


# ---------------------------------------------------------------------------
# Sharded aggregation: pooled queue-depth percentiles
# ---------------------------------------------------------------------------


def _engine_report(depths):
    eng = CompressionEngineRuntime(MemCtlConfig(lanes=2, step_cycles=64))
    r = eng.report()
    r["step_queue_depth"] = list(depths)
    depths_sorted = sorted(depths)
    n = len(depths_sorted)
    r["queue_depth"] = {
        "p50": float(depths_sorted[min(n - 1, round(0.50 * (n - 1)))]),
        "p90": float(depths_sorted[min(n - 1, round(0.90 * (n - 1)))]),
        "p99": float(depths_sorted[min(n - 1, round(0.99 * (n - 1)))]),
        "max": float(depths_sorted[-1]),
    } if n else {"p50": 0.0, "p90": 0.0, "p99": 0.0, "max": 0.0}
    return r


def test_aggregate_pools_queue_depth_across_shards():
    """The fleet's queue-depth percentiles come from the per-step SUM of
    shard depths — simultaneous backlog — not from max-ing per-shard
    percentiles (which can both over- and understate the fleet)."""
    a = _engine_report([0, 10, 0, 10])
    b = _engine_report([10, 0, 10, 0])
    agg = aggregate_engine_reports([a, b])
    # pooled series is [10, 10, 10, 10]: constant fleet backlog
    assert agg["step_queue_depth"] == [10, 10, 10, 10]
    assert agg["queue_depth"] == {"p50": 10.0, "p90": 10.0, "p99": 10.0,
                                  "max": 10.0}
    # max-of-percentiles would have said p50 = 5 ... the old aggregation
    # hid exactly this anti-correlated-load case


def test_aggregate_pools_unequal_lengths_and_falls_back():
    a = _engine_report([1, 2, 3])
    b = _engine_report([4])
    agg = aggregate_engine_reports([a, b])
    assert agg["step_queue_depth"] == [5, 2, 3]
    # reports without raw samples (older producers): max-of-percentiles
    a2, b2 = _engine_report([0, 10]), _engine_report([2, 2])
    del a2["step_queue_depth"]
    agg2 = aggregate_engine_reports([a2, b2])
    assert agg2["step_queue_depth"] is None
    assert agg2["queue_depth"]["max"] == 10.0


def test_sharded_serving_report_carries_pooled_queue_depth(smoke_model):
    model, params = smoke_model
    sched, _ = _serve(model, params, _cfg(backend="sharded", shards=2),
                      [_prompt(40), _prompt(25, 3)])
    er = sched.report()["engine"]
    assert er["shards"] == 2
    assert isinstance(er["step_queue_depth"], list)
    assert len(er["step_queue_depth"]) == max(
        len(t.engine.stats.step_queue_depth) for t in sched.backend.tiers
    )


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def test_perfetto_trace_schema_and_tracks(smoke_model, tmp_path):
    model, params = smoke_model
    sched, reqs = _serve(model, params,
                         _cfg(backend="paged", ladder=LADDER,
                              device_kv="bitplane"),
                         [_prompt(37), _prompt(60, 3)])
    path = tmp_path / "trace.json"
    trace = write_perfetto_trace(sched.telemetry, str(path))
    summary = validate_trace(str(path))  # same gate CI runs on the artifact
    assert summary["events"] == len(trace["traceEvents"])
    assert summary["has_lane_track"] and summary["has_counters"]
    ev = trace["traceEvents"]
    # one request slice per closed span, on a per-slot track in pid 1
    req_slices = [e for e in ev if e.get("cat") == "request"
                  and e["ph"] == "X"]
    assert len(req_slices) == len(reqs)
    assert all(e["pid"] == 1 for e in req_slices)
    # memctl lane slices live in a DIFFERENT process (engine clock domain)
    lane_slices = [e for e in ev if e.get("cat") == "lane"]
    assert lane_slices and all(e["pid"] >= 100 for e in lane_slices)
    # counter tracks for the scheduler
    assert any(e["ph"] == "C" and e["name"] == "decoding" for e in ev)


def test_perfetto_export_refuses_disabled_collector():
    with pytest.raises(ValueError, match="disabled collector"):
        build_trace_events(NULL_COLLECTOR)


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="invalid phase"):
        validate_trace({"traceEvents": [
            {"ph": "Z", "pid": 1, "tid": 0, "ts": 0}]})
    with pytest.raises(ValueError, match="pid/tid"):
        validate_trace({"traceEvents": [
            {"ph": "X", "pid": "one", "tid": 0, "ts": 0, "dur": 1}]})
    with pytest.raises(ValueError, match="dur"):
        validate_trace({"traceEvents": [
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "slot 0"}},
            {"ph": "X", "pid": 1, "tid": 0, "ts": 0, "dur": -1}]})
    with pytest.raises(ValueError, match="no per-slot"):
        validate_trace({"traceEvents": [
            {"ph": "i", "pid": 1, "tid": 0, "ts": 0, "s": "t"}]})


def test_prometheus_snapshot_format(smoke_model):
    model, params = smoke_model
    sched, _ = _serve(model, params, _cfg(backend="paged"),
                      [_prompt(20), _prompt(33, 7)])
    snap = prometheus_snapshot(sched.report())
    lines = snap.splitlines()
    assert "# TYPE repro_serving_decode_tokens_total counter" in lines
    assert any(ln.startswith("repro_serving_decode_tokens_total ")
               for ln in lines)
    assert any('repro_serving_ttft_wall_ns{quantile="p99"}' in ln
               for ln in lines)
    assert any(ln.startswith("repro_serving_telemetry_spans_closed ")
               for ln in lines)
    # exposition format: every series line is "name[{labels}] value"
    for ln in lines:
        if ln.startswith("#"):
            continue
        name, value = ln.rsplit(" ", 1)
        float(value)
        assert name[0].isalpha()


def test_quantiles_nearest_rank():
    q = quantiles(list(range(1, 101)))
    assert q == {"p50": 51.0, "p95": 95.0, "p99": 99.0,
                 "mean": 50.5, "max": 100.0, "count": 100}
    assert quantiles([])["count"] == 0


# ---------------------------------------------------------------------------
# Step spans and counters (repro.telemetry.steps)
# ---------------------------------------------------------------------------


@pytest.fixture
def step_log(monkeypatch):
    """A fresh step log for the test, in place of the process-wide one."""
    log = StepLog()
    monkeypatch.setattr(steps, "LOG", log)
    return log


def test_step_phases_nest_in_step_on_the_profiler_clock(smoke_model,
                                                        tmp_path):
    """Two steps under the profiler: every ``serve.<phase>`` lies inside a
    ``serve.step`` on the thread of the annotation around them, so the
    spans share the profiler's clock with everything else it records."""
    from jax.profiler import ProfileData

    model, params = smoke_model
    sched = ContinuousScheduler(model, params, _cfg(telemetry=None))
    sched.submit(Request(rid=0, prompt=_prompt(40), max_new_tokens=4))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            sched.step()
            sched.step()
    path, = tmp_path.rglob("*.xplane.pb")
    events = None
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(n == "test.outer" for n, _, _ in evs):
                events = evs
    assert events is not None
    step_spans = [e for e in events if e[0] == "serve.step"]
    phases = [e for e in events
              if e[0].startswith("serve.") and e[0] != "serve.step"]
    assert len(step_spans) == 2
    assert {n for n, _, _ in phases} == {"serve." + p for p in PHASES}
    for n, s, e in phases:
        assert any(s0 <= s and e <= e0 for _, s0, e0 in step_spans), n


def test_phase_times_fit_inside_their_step(smoke_model, step_log):
    model, params = smoke_model
    _serve(model, params, _cfg(telemetry=None),
           [_prompt(37), _prompt(80, 11), _prompt(24, 5)])
    recs = list(step_log.records)
    assert recs and step_log.dropped == 0
    assert [r["step"] for r in recs] == list(range(len(recs)))
    for r in recs:
        assert set(r["phase_ns"]) <= set(PHASES)
        assert sum(r["phase_ns"].values()) <= r["end_ns"] - r["start_ns"]
    # a step that decoded ran every phase
    assert any(set(r["phase_ns"]) == set(PHASES) for r in recs
               if r["decoded"])
    assert sum(r["decoded"] for r in recs) == 3 * 5


@pytest.mark.parametrize("backend,shards", [("paged", 1), ("sharded", 2)])
def test_memctl_jobs_equal_serviced_jobs(smoke_model, step_log, backend,
                                         shards):
    model, params = smoke_model
    sched = ContinuousScheduler(model, params, _cfg(
        backend=backend, shards=shards, telemetry=None, ladder=LADDER))

    def serviced():
        return sum(sum(t.engine.stats.serviced_jobs.values())
                   for t in sched.backend.tiers)

    before = serviced()
    for i, p in enumerate([_prompt(37), _prompt(80, 11)]):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    sched.run_until_drained()
    jobs = sum(r["memctl_jobs"] for r in step_log.records)
    assert jobs == serviced() - before > 0
    assert step_log.records[-1]["memctl_queue"] == sched.backend.backlog()


@pytest.mark.parametrize("device_kv", ["dense", "bitplane"])
def test_d2h_bytes_equal_bytes_copied(smoke_model, step_log, device_kv):
    model, params = smoke_model
    sched = ContinuousScheduler(model, params, _cfg(
        telemetry=None, device_kv=device_kv))
    real = sched.backend.slot_kv_host
    copied = []

    def counting(*a, **kw):
        k, v = real(*a, **kw)
        copied.append(k.nbytes + v.nbytes)
        return k, v

    sched.backend.slot_kv_host = counting
    # decode page fills go to the host in one batched transfer a step;
    # the bind-time warm-up (no fills) runs the program but copies nothing
    real_fill = sched.backend._run_fill_pages
    batched = []

    def fill_counting(fills):
        out = real_fill(fills)
        if fills:
            batched.append(out.nbytes)
        return out

    sched.backend._run_fill_pages = fill_counting
    for i, p in enumerate([_prompt(37), _prompt(20, 3)]):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=12))
    sched.run_until_drained()
    assert copied and batched
    assert (sum(r["d2h_bytes"] for r in step_log.records)
            == sum(copied) + sum(batched))


def test_new_prefill_shape_compiles_in_its_own_step(step_log):
    """A prompt that needs a prefill bucket no earlier request used
    compiles in the step that dispatches it, and in no later step."""
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)  # a model of its own: no jit shared
    params = model.init(jax.random.PRNGKey(0))
    sched = ContinuousScheduler(model, params, EngineConfig(
        max_batch=1, max_ctx=192, store_layers=2))
    # 20 tokens: chunks of the 16 bucket; 24 new tokens fill a page
    sched.submit(Request(rid=0, prompt=_prompt(20), max_new_tokens=24))
    sched.run_until_drained()
    n0 = len(step_log.records)
    # 40 tokens: a 32 chunk, new; 4 new tokens fill no page
    sched.submit(Request(rid=1, prompt=_prompt(40, 3), max_new_tokens=4))
    sched.run_until_drained()
    later = list(step_log.records)[n0:]
    assert len(later) >= 2
    assert later[0]["compiles"] >= 1 and later[0]["compile_s"] > 0
    assert [r["compiles"] for r in later[1:]] == [0] * (len(later) - 1)


def test_step_log_is_bounded_and_counts_overflow():
    assert steps.LOG.records.maxlen == 8192
    log = StepLog(maxlen=3)
    for i in range(5):
        log.append({"step": i})
    assert [r["step"] for r in log.records] == [2, 3, 4]
    assert log.dropped == 2


def test_counters_outside_a_step_are_dropped(step_log):
    steps.count("decoded", 3)  # no step open: nothing to add to
    with steps.span("admit"):
        pass
    with steps.step(7) as rec:
        steps.count("decoded", 3)
        with steps.span("admit"):
            pass
    assert list(step_log.records) == [rec]
    assert rec["step"] == 7 and rec["decoded"] == 3
    assert set(rec["phase_ns"]) == {"admit"}


def test_prometheus_exports_host_seconds_by_phase(smoke_model):
    model, params = smoke_model
    sched, _ = _serve(model, params, _cfg(telemetry=None), [_prompt(20)])
    rep = sched.report()
    assert set(rep["host_s_by_phase"]) == set(PHASES)
    lines = prometheus_snapshot(rep).splitlines()
    assert "# TYPE repro_serving_host_seconds_total counter" in lines
    for phase in PHASES:
        line, = [ln for ln in lines if ln.startswith(
            f'repro_serving_host_seconds_total{{phase="{phase}"}} ')]
        assert float(line.rsplit(" ", 1)[1]) == pytest.approx(
            rep["host_s_by_phase"][phase])
