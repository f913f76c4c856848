"""Dense vs bit-plane device KV under the same serving load, and the first
wall-clock bandwidth trajectory (ISSUE 5 + ISSUE 6).

Drives identical mixed-length Poisson traffic through the paged backend
three ways at each ladder mix:

* ``device_kv="dense"`` — decode attends a bf16 cache; the ladder's
  bandwidth saving is accounting-only;
* ``device_kv="bitplane"`` + ``decode_kernel="rung"`` — packed int32
  planes, one partial-plane Pallas launch per rung in the ladder's static
  rung set, partials merged outside the kernel;
* ``device_kv="bitplane"`` + ``decode_kernel="fused"`` — ONE Pallas launch
  walks the per-page plane map inline (ISSUE 6 tentpole).

Reported per (mix, variant):

* tokens/s — the device paths genuinely differ (einsum vs rung loop vs
  fused kernel), so throughput is measured, not assumed (CPU runs the
  kernels in interpret mode; TPU runs compile them);
* device bytes/decode-token — dense always moves the full-precision page,
  whatever the ladder charged; bit-plane moves the ladder's bytes, and
  ``device_bytes_read`` == the controller's plane-scaled kv_read exactly
  (asserted at every mix);
* roofline fraction — achieved device KV bytes/s over the modeled memctl
  peak (``MemCtlConfig``: lanes x per-lane decompressed-side throughput),
  the first point of the wall-clock bandwidth trajectory;
* fused-vs-rung speedup at each mix.

Bitplane device bytes are asserted ``<=`` dense at every mix, and strictly
``<`` on mixed-ladder rows (a full-precision ladder legitimately moves
exactly the dense byte count).

With ``json_path`` (the driver passes it under ``--json``) the campaign
rows are written to ``BENCH_serving.json`` for the CI artifact.

    PYTHONPATH=src python -m benchmarks.run --only serving_bitplane
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks.common import decode_tok_per_s, fmt_table, pct


def _mixed_requests(n, seed, vocab):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [
        Request(rid=i,
                prompt=rng.integers(0, vocab, int(rng.integers(8, 120)))
                .astype(np.int32),
                max_new_tokens=int(rng.choice([4, 8, 16, 24])))
        for i in range(n)
    ]


def _run(model, params, cfg, reqs, arrivals, max_steps=None):
    from repro.serving import ContinuousScheduler, Request

    # warm pass: jit caches key on (model, keeps, kernel) and survive the
    # scheduler, so a throwaway trace moves every compile out of the
    # measured window — tok/s below is steady-state, not compile time
    warm = ContinuousScheduler(model, params, cfg)
    warm.submit(Request(rid=10 ** 6, prompt=np.arange(24, dtype=np.int32),
                        max_new_tokens=4))
    warm.run_until_drained(60)

    sched = ContinuousScheduler(model, params, cfg)
    nxt = 0
    while nxt < len(reqs) or sched.has_work():
        if max_steps is not None and sched.step_count >= max_steps:
            break
        while nxt < len(reqs) and arrivals[nxt] <= sched.step_count:
            sched.submit(reqs[nxt])
            nxt += 1
        sched.step()
    return sched.report(), sched


def _span_latency(rep: dict) -> dict:
    """p50/p99 TTFT + per-token latency from the telemetry spans, in both
    clock domains — the per-request numbers the aggregate report can't
    give (ISSUE 7 satellite)."""
    lat = rep.get("latency", {})
    out = {}
    for key in ("ttft_wall_ns", "ttft_engine_ns",
                "tpot_wall_ns", "tpot_engine_ns"):
        q = lat.get(key, {})
        out[key + "_p50"] = q.get("p50", 0.0)
        out[key + "_p99"] = q.get("p99", 0.0)
    return out


def _peak_device_bytes_per_s(engine) -> float:
    """Modeled memctl peak: lanes x decompressed-side bytes/s per lane."""
    return (engine.lanes * engine.lane_bytes_per_cycle
            * engine.clock_ghz * 1e9)


def run(n_requests: int = 16, rate: float = 0.6, seed: int = 0,
        max_steps: int | None = None, json_path: str | None = None):
    import dataclasses

    import jax

    from repro.configs.base import get_config
    from repro.core.quantization import PrecisionLadder
    from repro.models.model import build_model
    from repro.serving import EngineConfig, TelemetryConfig

    cfg_m = get_config("smollm-135m", smoke=True)
    model = build_model(cfg_m)
    params = model.init(jax.random.PRNGKey(0))
    # telemetry on for every measured run: the campaign's TTFT/TPOT
    # quantiles come from request spans, and the last bitplane/fused run's
    # Perfetto trace ships as a CI artifact next to the JSON
    base = EngineConfig(max_batch=4, max_ctx=256, store_layers=2,
                        telemetry=TelemetryConfig())
    peak = _peak_device_bytes_per_s(base.engine)
    mixes = [
        ("full (16)", None),
        ("top4@16/4@12/rest@8", PrecisionLadder([(4, 16), (4, 12), (-1, 8)])),
        ("top2@16/2@8/rest@4", PrecisionLadder([(2, 16), (2, 8), (-1, 4)])),
    ]
    variants = [("dense", "fused"), ("bitplane", "rung"),
                ("bitplane", "fused")]
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(rng.exponential(1.0 / rate, n_requests)))

    out = {}
    rows = []
    last_sched = None
    for mix_name, ladder in mixes:
        for device_kv, kernel in variants:
            cfg = dataclasses.replace(base, ladder=ladder,
                                      device_kv=device_kv,
                                      decode_kernel=kernel)
            rep, sched = _run(model, params, cfg,
                              _mixed_requests(n_requests, seed, cfg_m.vocab),
                              arrivals, max_steps=max_steps)
            if device_kv == "bitplane" and kernel == "fused":
                last_sched = sched
            if device_kv == "bitplane":
                # the acceptance identity, demonstrated at every mix and
                # on BOTH kernel strategies
                assert rep["device_bytes_read"] == rep["kv_read_device_bytes"]
            dec = max(1, rep["decode_tokens"])
            tok_s = decode_tok_per_s(rep)
            bpt = rep["device_bytes_read"] / dec
            variant = (device_kv if device_kv == "dense"
                       else f"{device_kv}/{kernel}")
            rows.append([
                mix_name, variant, f"{tok_s:.1f}", f"{bpt:.0f}",
                f"{rep['kv_read_device_bytes'] / dec:.0f}",
                pct(rep.get("kv_device_bandwidth_saving", 0)),
                f"{tok_s * bpt / peak:.2e}",
            ])
            out[f"{mix_name}/{variant}"] = {
                "decode_tok_per_s": tok_s,
                "device_bytes_per_token": bpt,
                "accounted_bytes_per_token": rep["kv_read_device_bytes"] / dec,
                "device_bandwidth_saving":
                    rep.get("kv_device_bandwidth_saving", 0),
                "roofline_fraction": tok_s * bpt / peak,
                **_span_latency(rep),
            }
    print(fmt_table(rows, ["ladder mix", "device path", "tok/s",
                           "device B/tok", "accounted B/tok",
                           "device bw saving", "roofline frac"]))
    for mix_name, ladder in mixes:
        d = out[f"{mix_name}/dense"]["device_bytes_per_token"]
        for kernel in ("rung", "fused"):
            b = out[f"{mix_name}/bitplane/{kernel}"]["device_bytes_per_token"]
            # dense can never be beaten by a full-precision ladder; a MIXED
            # ladder must strictly shrink the device read
            assert b <= d, (mix_name, kernel, b, d)
            if ladder is not None:
                assert b < d, (mix_name, kernel, b, d)
        r = out[f"{mix_name}/bitplane/rung"]
        f = out[f"{mix_name}/bitplane/fused"]
        f["fused_vs_rung_speedup"] = (
            f["decode_tok_per_s"] / r["decode_tok_per_s"]
            if r["decode_tok_per_s"] else 0.0)
    out["peak_device_bytes_per_s"] = peak
    if json_path is not None:
        with open(json_path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"[serving_bitplane] wrote {json_path}")
        if last_sched is not None and last_sched.telemetry.enabled:
            from repro.telemetry import write_perfetto_trace

            trace_path = str(json_path).replace(".json", "") + "_trace.json"
            write_perfetto_trace(last_sched.telemetry, trace_path,
                                 clock_ghz=base.engine.clock_ghz)
            print(f"[serving_bitplane] wrote {trace_path} (Perfetto)")
    print("[serving_bitplane] dense device bytes ignore the ladder "
          "(accounting fiction); bitplane device bytes == the controller's "
          "plane-scaled kv_read — and the fused single-kernel walk turns "
          "the ladder's saving into one launch per decode step")
    return out
