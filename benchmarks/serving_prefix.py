"""Shared-prefix KV pages: shared vs cold serving economics (ISSUE 10).

Drives the paged backend with waves of requests that share a page-aligned
system prompt, at several *share factors* (requests per distinct prefix),
with prefix sharing ON — and replays the share-factor-8 trace with
sharing OFF as the cold baseline.  Reported per row:

* tok/s and TTFT p50/p99 (wall) — shared admissions skip matched prefill
  chunks entirely, so first tokens arrive earlier;
* hit ratio / bytes deduplicated — ``report()["prefix"]``: the store
  holds ONE copy of a shared prefix regardless of how many requests bind
  it (refcounted content-addressed pages);
* prefill engine jobs — serviced ``KV_WRITE`` count: matched pages are
  bound, not re-compressed, so the lane engine is never charged for them.

Two hard claims are asserted, not just printed:

* sharing is a MEMORY policy, not a numerics change — sampled tokens with
  sharing ON are bit-identical to OFF on the same trace;
* at share factor 8, TTFT p50 is strictly lower AND serviced prefill
  compression jobs are strictly fewer than the cold baseline.

With ``json_path`` the rows are MERGED into ``BENCH_serving.json`` under
a ``"prefix"`` key (after ``serving_weight_stream``, read-modify-write).

    PYTHONPATH=src python -m benchmarks.run --only serving_prefix
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import decode_tok_per_s, fmt_table


def _wave_requests(n, share_factor, seed, prefix_pages=6):
    """``n`` requests in ``n // share_factor`` prefix groups: each group
    shares one page-aligned system prompt + a unique per-request tail."""
    from repro.serving import Request
    from repro.serving.kv_cache import PAGE_TOKENS

    rng = np.random.default_rng(seed)
    groups = max(1, n // share_factor)
    prefixes = [rng.integers(0, 500, prefix_pages * PAGE_TOKENS)
                .astype(np.int32) for _ in range(groups)]
    reqs = []
    for i in range(n):
        tail = rng.integers(0, 500, int(rng.integers(4, 20))).astype(np.int32)
        reqs.append(Request(
            rid=i,
            prompt=np.concatenate([prefixes[i % groups], tail]),
            max_new_tokens=int(rng.choice([8, 12, 16])),
        ))
    return reqs


def _run(model, params, cfg, reqs, gap=6, max_steps=None):
    """Staggered submission (one request every ``gap`` steps): later
    arrivals find the donor's prefix registered, which a synchronized
    wave would not (registration flushes after the prefill tick)."""
    from repro.serving import ContinuousScheduler, Request

    warm = ContinuousScheduler(model, params, cfg)
    warm.submit(Request(rid=10 ** 6, prompt=np.arange(16, dtype=np.int32),
                        max_new_tokens=4))
    warm.run_until_drained(60)

    sched = ContinuousScheduler(model, params, cfg)
    nxt = 0
    while nxt < len(reqs) or sched.has_work():
        if max_steps is not None and sched.step_count >= max_steps:
            break
        while nxt < len(reqs) and nxt * gap <= sched.step_count:
            sched.submit(reqs[nxt])
            nxt += 1
        sched.step()
    rep = sched.report()
    return rep, [list(r.output) for r in reqs]


def run(n_requests: int = 16, seed: int = 0, share_factors=(1, 4, 8),
        max_steps: int | None = None, json_path: str | None = None):
    import dataclasses

    import jax

    from repro.configs.base import get_config
    from repro.models.model import build_model
    from repro.serving import EngineConfig, TelemetryConfig

    cfg_m = get_config("smollm-135m", smoke=True)
    model = build_model(cfg_m)
    params = model.init(jax.random.PRNGKey(0))
    base = EngineConfig(max_batch=4, max_ctx=256, store_layers=2,
                        prefix_sharing=True,
                        telemetry=TelemetryConfig(lane_timeline=False))

    out, rows = {}, []

    def measure(cfg, reqs, label):
        rep, toks = _run(model, params, cfg, reqs, max_steps=max_steps)
        lat = rep["latency"]["ttft_wall_ns"]
        px = rep["prefix"]
        kv_writes = rep["engine"]["serviced_jobs"].get("KV_WRITE", 0)
        row = {
            "decode_tok_per_s": decode_tok_per_s(rep),
            "ttft_p50_ns": lat["p50"], "ttft_p99_ns": lat["p99"],
            "hit_ratio": px.get("hit_ratio", 0.0),
            "requests_matched": px.get("requests_matched", 0),
            "bytes_deduplicated": px.get("bytes_deduplicated", 0),
            "prefill_chunks_skipped": px.get("prefill_chunks_skipped", 0),
            "kv_write_jobs": kv_writes,
        }
        out[label] = row
        rows.append([label, f"{row['decode_tok_per_s']:.1f}",
                     f"{lat['p50']:.2e}", f"{lat['p99']:.2e}",
                     f"{row['hit_ratio']:.2f}",
                     str(row['bytes_deduplicated']),
                     str(kv_writes)])
        return row, toks

    for sf in share_factors:
        reqs = _wave_requests(n_requests, sf, seed)
        measure(base, reqs, f"shared_x{sf}")

    # cold baseline: the share-factor-max trace replayed with sharing OFF —
    # identical prompts, identical arrivals, no prefix index
    sf = max(share_factors)
    cold_cfg = dataclasses.replace(base, prefix_sharing=False)
    cold_reqs = _wave_requests(n_requests, sf, seed)
    cold, cold_toks = measure(cold_cfg, cold_reqs, "cold")
    shared_reqs = _wave_requests(n_requests, sf, seed)
    shared, shared_toks = measure(base, shared_reqs, f"shared_x{sf}_rerun")
    out["shared"] = out.pop(f"shared_x{sf}_rerun")
    rows[-1][0] = "shared(rerun)"

    # claim 1: sharing never changes a single sampled token
    assert shared_toks == cold_toks, \
        "prefix sharing changed sampled tokens vs the cold baseline"
    # claim 2: the economics — strictly earlier first tokens, strictly
    # fewer lane-engine compression jobs (matched pages are bound, never
    # re-compressed)
    assert shared["ttft_p50_ns"] < cold["ttft_p50_ns"], (shared, cold)
    assert shared["kv_write_jobs"] < cold["kv_write_jobs"], (shared, cold)
    assert shared["requests_matched"] > 0, shared

    print(fmt_table(rows, ["trace", "tok/s", "ttft p50", "ttft p99",
                           "hit ratio", "dedup B", "kv_write jobs"]))
    print("[serving_prefix] shared tokens bit-identical to cold; TTFT p50 "
          f"{cold['ttft_p50_ns'] / max(shared['ttft_p50_ns'], 1):.2f}x "
          f"faster, prefill compression jobs "
          f"{cold['kv_write_jobs']} -> {shared['kv_write_jobs']}")

    if json_path is not None:
        merged = {}
        if os.path.exists(json_path):
            with open(json_path) as fh:
                merged = json.load(fh)
        merged["prefix"] = out
        with open(json_path, "w") as fh:
            json.dump(merged, fh, indent=1)
        print(f"[serving_prefix] merged into {json_path}")
    return out


if __name__ == "__main__":
    run()
