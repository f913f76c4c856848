"""Serving throughput under load: admission modes and batching modes.

Drives the continuous-batching scheduler with a Poisson arrival trace of
mixed-length requests and reports decode tokens/s, batch occupancy, prefill
compile count / host seconds (``host_s_by_phase["prefill_dispatch"]``), and
the KV capacity/bandwidth savings the compressed store +
dynamic-quantization ladder deliver at steady state (normalised per 1k
requests).  Three modes, each on a FRESH model object so
prefill numbers include its own compiles (that is the point of bucketing):

* ``bucketed``   — chunked prefill over power-of-two length buckets
  (<= log2(max_ctx) compiles, pad-free accounting; ISSUE 3 tentpole).
* ``left-pad``   — the legacy pad-to-``prefill_align`` admission: one
  compile per distinct padded prompt length, pad KV stored and charged.
* ``one-shot waves`` — left-pad admission AND fixed admission waves (the
  seed engine's behaviour): every wave decodes to its longest request.

Savings are quoted over pad-free logical bytes only — the left-pad rows
inflate ``prefill_tokens`` and the store traffic, which is visible in the
table instead of flattering it.

    PYTHONPATH=src python -m benchmarks.run --only serving
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import decode_tok_per_s, fmt_table, pct


def _mixed_requests(n, seed, vocab, max_new_choices=(4, 8, 16, 24)):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(8, 120))
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=int(rng.choice(max_new_choices)),
        ))
    return reqs


def _run_continuous(model, params, cfg, reqs, arrivals, max_steps=None):
    from repro.serving import ContinuousScheduler

    sched = ContinuousScheduler(model, params, cfg)
    next_req = 0
    while next_req < len(reqs) or sched.has_work():
        if max_steps is not None and sched.step_count >= max_steps:
            break
        while next_req < len(reqs) and arrivals[next_req] <= sched.step_count:
            sched.submit(reqs[next_req])
            next_req += 1
        sched.step()
    return sched.report()


def _run_waves(model, params, cfg, reqs, max_steps=None):
    """Seed-style one-shot batching: admit in fixed waves of max_batch."""
    from repro.serving import ServingEngine

    eng = ServingEngine(model, params, cfg)
    budget = max_steps
    for off in range(0, len(reqs), cfg.max_batch):
        if budget is not None and budget <= 0:
            break
        wave = reqs[off : off + cfg.max_batch]
        # one-shot semantics: nothing joins until the whole wave drains
        for r in wave:
            eng.scheduler.submit(r)
        before = eng.scheduler.step_count
        eng.scheduler.run_until_drained(
            max_steps=budget if budget is not None else 100_000
        )
        if budget is not None:
            budget -= eng.scheduler.step_count - before
    return eng.report()


def run(n_requests: int = 24, rate: float = 0.6, seed: int = 0,
        max_steps: int | None = None):
    import dataclasses

    import jax

    from repro.configs.base import get_config
    from repro.core.quantization import PrecisionLadder
    from repro.models.model import build_model
    from repro.serving import EngineConfig

    cfg_m = get_config("smollm-135m", smoke=True)
    params = build_model(cfg_m).init(jax.random.PRNGKey(0))
    ladder = PrecisionLadder([(4, 16), (4, 12), (-1, 8)])
    base_cfg = EngineConfig(max_batch=4, max_ctx=256, ladder=ladder,
                            max_stored_bytes=128 * 1024)

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)

    def fresh(mode):
        # a fresh Model object = a cold jit cache, so each mode pays (and
        # reports) exactly its own prefill compiles
        return build_model(cfg_m), dataclasses.replace(base_cfg,
                                                       prefill_mode=mode)

    model, cfg = fresh("bucketed")
    bucketed = _run_continuous(model, params, cfg,
                               _mixed_requests(n_requests, seed, cfg_m.vocab),
                               arrivals, max_steps=max_steps)
    model, cfg = fresh("padded")
    leftpad = _run_continuous(model, params, cfg,
                              _mixed_requests(n_requests, seed, cfg_m.vocab),
                              arrivals, max_steps=max_steps)
    model, cfg = fresh("padded")
    wave = _run_waves(model, params, cfg,
                      _mixed_requests(n_requests, seed, cfg_m.vocab),
                      max_steps=max_steps)

    rows = []
    out = {}
    for name, rep in (("bucketed", bucketed), ("left-pad", leftpad),
                      ("one-shot waves", wave)):
        rows.append([
            name,
            f"{rep['prefill_compiles']:.0f}",
            f"{rep['host_s_by_phase']['prefill_dispatch']:.2f}s",
            f"{decode_tok_per_s(rep):.1f}",
            f"{rep['decode_steps']:.0f}",
            pct(rep.get("mean_batch_occupancy", 0)),
            pct(rep.get("kv_capacity_saving", 0)),
            pct(rep.get("kv_bandwidth_saving", 0)),
            f"{rep['kv_evictions']:.0f}",
        ])
        out[name] = {
            "prefill_compiles": rep["prefill_compiles"],
            "prefill_s": rep["host_s_by_phase"]["prefill_dispatch"],
            "prefill_tokens": rep["prefill_tokens"],
            "decode_tok_per_s": decode_tok_per_s(rep),
            "decode_steps": rep["decode_steps"],
            "occupancy": rep.get("mean_batch_occupancy", 0),
            "kv_capacity_saving": rep.get("kv_capacity_saving", 0),
            "kv_bandwidth_saving": rep.get("kv_bandwidth_saving", 0),
            "per_1k_requests": rep.get("per_1k_requests", {}),
        }
    print(fmt_table(rows, ["mode", "compiles", "prefill", "tok/s", "steps",
                           "occupancy", "KV capacity", "KV bandwidth",
                           "evictions"]))
    steps_c, steps_w = bucketed["decode_steps"], wave["decode_steps"]
    print(f"\n[serving] bucketed admission: "
          f"{bucketed['prefill_compiles']:.0f} prefill compiles vs "
          f"{leftpad['prefill_compiles']:.0f} left-pad "
          f"({out['bucketed']['prefill_s']:.2f}s vs "
          f"{out['left-pad']['prefill_s']:.2f}s "
          f"prefill); pad-free prefill tokens "
          f"{bucketed['prefill_tokens']:.0f} vs "
          f"{leftpad['prefill_tokens']:.0f}")
    print(f"[serving] continuous batching: {steps_c:.0f} decode steps vs "
          f"{steps_w:.0f} one-shot ({pct(1 - steps_c / max(1, steps_w))} fewer); "
          f"retire-at-own-step reclaims the padded-decode waste")
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=0.6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=None,
                    help="cap scheduler steps per mode (CI-sized runs)")
    a = ap.parse_args()
    run(n_requests=a.requests, rate=a.rate, seed=a.seed, max_steps=a.steps)
