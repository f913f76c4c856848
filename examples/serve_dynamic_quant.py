"""Serving with the compression-aware memory path: a Poisson arrival trace
through the continuous-batching scheduler, with (a) compressed paged KV
storage under a byte budget (LRU eviction) and (b) a Quest-style
dynamic-quantization ladder controlling KV fetch precision.

Requests arrive mid-flight (new prompts join the running batch the step a
slot frees), short requests retire at their own step, and the report quotes
steady-state capacity/bandwidth savings normalised per 1k requests.

    PYTHONPATH=src python examples/serve_dynamic_quant.py
"""

import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.quantization import PrecisionLadder
from repro.data.tokenizer import ByteTokenizer
from repro.models.model import build_model
from repro.serving import ContinuousScheduler, EngineConfig, Request
from repro.serving.sampler import SamplerConfig

PROMPTS = [
    b"The compression-aware memory controller reorganizes",
    b"Key-value caches grow with sequence length until",
    b"Bit-plane disaggregation stores the sign bits together and",
    b"Dynamic quantization assigns high precision to critical pages and",
    b"Continuous batching admits requests the step a slot frees so",
    b"Cold pages are evicted through the compressed store when",
]


def poisson_trace(n_requests: int, rate: float, seed: int = 0):
    """Arrival step for each request: Poisson process with ``rate`` requests
    per decode step (inter-arrival gaps ~ Exp(rate), accumulated)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-6), n_requests)
    return np.floor(np.cumsum(gaps)).astype(np.int64)


def main():
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = ByteTokenizer(cfg.vocab)

    ladder = PrecisionLadder([(4, 16), (4, 12), (-1, 8)])
    sched = ContinuousScheduler(
        model, params,
        EngineConfig(max_batch=4, max_ctx=256, ladder=ladder,
                     sampler=SamplerConfig(temperature=0.8, top_k=40),
                     max_stored_bytes=40 * 1024),  # force budget pressure
    )

    n_requests = 12
    arrivals = poisson_trace(n_requests, rate=0.5, seed=7)
    reqs = [
        Request(rid=i, prompt=tok.encode(PROMPTS[i % len(PROMPTS)]),
                max_new_tokens=8 + 6 * (i % 4))
        for i in range(n_requests)
    ]

    t0 = time.time()
    next_req = 0
    while next_req < n_requests or sched.has_work():
        while next_req < n_requests and arrivals[next_req] <= sched.step_count:
            sched.submit(reqs[next_req], rng_seed=7)  # re-keys each stream
            next_req += 1
        retired = sched.step()
        for r in retired:
            body = tok.decode_bytes(np.array(r.output))
            print(f"[req {r.rid:2d}] arrived@{r.arrival_step:3d} "
                  f"admitted@{r.admit_step:3d} done@{r.finish_step:3d} "
                  f"+{len(r.output)} tokens: {body[:40]!r}")
    dt = time.time() - t0

    rep = sched.report()
    print(f"\n[serve] {rep['requests_completed']:.0f} requests, "
          f"{rep['decode_tokens']:.0f} decode tokens in {dt:.1f}s "
          f"({rep['decode_tokens'] / dt:.1f} tok/s on CPU), "
          f"mean occupancy {rep.get('mean_batch_occupancy', 0):.0%}")
    print(f"[serve] KV capacity saving (clustered+delta+codec store): "
          f"{rep.get('kv_capacity_saving', 0):.1%}")
    print(f"[serve] KV bandwidth saving (ladder partial-plane fetch): "
          f"{rep.get('kv_bandwidth_saving', 0):.1%}")
    print(f"[serve] budget pressure: {rep['kv_evictions']:.0f} evictions, "
          f"{rep['kv_reactivations']:.0f} re-activations, peak stored "
          f"{rep['kv_peak_stored_bytes'] / 1024:.0f} KiB")
    per = rep.get("per_1k_requests", {})
    if per:
        print(f"[serve] per 1k requests: "
              f"{per['kv_stored_bytes'] / 2**20:.1f} MiB stored vs "
              f"{per['kv_logical_bytes'] / 2**20:.1f} MiB logical, "
              f"{per['kv_fetch_physical'] / 2**20:.1f} MiB fetched vs "
              f"{per['kv_fetch_logical'] / 2**20:.1f} MiB logical")


if __name__ == "__main__":
    main()
