"""Paged vs sharded KV backends under the same Poisson serving load.

Drives the continuous-batching scheduler with identical mixed-length
traffic through ``backend="paged"`` (one memory tier) and
``backend="sharded"`` (per-shard compressed tier + lane engine, pages
routed by KV-head ownership via the runtime/sharding mesh rules) and puts
the trade side by side:

* throughput and occupancy are identical by construction (the device
  compute path is shared — the backends differ in the MEMORY tier), which
  the table makes visible instead of assuming;
* capacity/bandwidth savings drop slightly with head-sharding (each shard
  entropy-codes a narrower channel slice, so cross-channel correlation is
  lost at the shard boundary) — the honest cost of shard isolation;
* engine pressure halves per shard: per-shard utilization and the worst
  shard's modeled latency show the scale-out headroom Table IV's silicon
  buys when it is instantiated per shard.

    PYTHONPATH=src python -m benchmarks.run --only serving_sharded
"""

from __future__ import annotations

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
    from repro.serving import ContinuousScheduler

    sched = ContinuousScheduler(model, params, cfg)
    nxt = 0
    while nxt < len(reqs) or sched.has_work():
        if max_steps is not None and sched.step_count >= max_steps:
            break
        while nxt < len(reqs) and arrivals[nxt] <= sched.step_count:
            sched.submit(reqs[nxt])
            nxt += 1
        sched.step()
    return sched.report()


def run(n_requests: int = 24, rate: float = 0.6, shards: int = 2,
        seed: int = 0, max_steps: int | None = None):
    import dataclasses

    import jax

    from repro.configs.base import get_config
    from repro.core.quantization import PrecisionLadder
    from repro.memctl import MemCtlConfig
    from repro.models.model import build_model
    from repro.serving import EngineConfig

    cfg_m = get_config("smollm-135m", smoke=True)
    model = build_model(cfg_m)
    params = model.init(jax.random.PRNGKey(0))
    base = EngineConfig(
        max_batch=4, max_ctx=256,
        ladder=PrecisionLadder([(4, 16), (4, 12), (-1, 8)]),
        max_stored_bytes=128 * 1024,
        engine=MemCtlConfig(lanes=4, step_cycles=1024),
    )
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(rng.exponential(1.0 / rate, n_requests)))

    # warm the shared jit cache so neither mode's tok/s carries the compile
    # bill — this benchmark compares MEMORY tiers, not compile schedules
    # (benchmarks/serving_throughput owns the cold-compile comparison)
    _run(model, params, dataclasses.replace(base, backend="paged"),
         _mixed_requests(2, seed + 1, cfg_m.vocab), np.zeros(2))

    out = {}
    rows = []
    for name, cfg in (
        ("paged", dataclasses.replace(base, backend="paged")),
        (f"sharded x{shards}",
         dataclasses.replace(base, backend="sharded", shards=shards)),
    ):
        rep = _run(model, params, cfg,
                   _mixed_requests(n_requests, seed, cfg_m.vocab),
                   arrivals, max_steps=max_steps)
        rows.append([
            name,
            f"{decode_tok_per_s(rep):.1f}",
            pct(rep.get("mean_batch_occupancy", 0)),
            pct(rep.get("kv_capacity_saving", 0)),
            pct(rep.get("kv_bandwidth_saving", 0)),
            f"{rep['kv_evictions']:.0f}",
            pct(rep["engine_utilization"]),
            f"{rep['engine_modeled_latency_ns'] / 1e3:.1f}us",
        ])
        out[name] = {
            "decode_tok_per_s": decode_tok_per_s(rep),
            "kv_capacity_saving": rep.get("kv_capacity_saving", 0),
            "kv_bandwidth_saving": rep.get("kv_bandwidth_saving", 0),
            "engine_utilization": rep["engine_utilization"],
            "engine_modeled_latency_ns": rep["engine_modeled_latency_ns"],
            "shards": rep.get("shards"),
        }
    print(fmt_table(rows, ["backend", "tok/s", "occupancy", "KV capacity",
                           "KV bandwidth", "evictions", "engine util",
                           "modeled lat"]))
    sh = out[f"sharded x{shards}"]["shards"] or []
    if sh:
        per = ", ".join(
            f"shard{d['shard']}: {pct(d['engine_utilization'])} util / "
            f"{d['kv_stored_bytes'] / 1024:.0f} KiB stored" for d in sh
        )
        print(f"\n[serving_sharded] per-shard balance — {per}")
    print("[serving_sharded] same device compute, different memory tier: "
          "savings trade a few points for per-shard stores + lane engines "
          "(worst-shard latency is the quoted modeled latency)")
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=0.6)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=None)
    a = ap.parse_args()
    run(n_requests=a.requests, rate=a.rate, shards=a.shards, seed=a.seed,
        max_steps=a.steps)
