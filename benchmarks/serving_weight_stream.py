"""Resident vs streamed block-compressed weights under serving load (ISSUE 9).

Drives identical Poisson traffic through the paged backend twice:

* ``weight_stream="resident"`` — layer weights live uncompressed on-device;
  the scheduler never submits a WEIGHT_FETCH job (the baseline every prior
  campaign row was measured against);
* ``weight_stream="compressed"`` — weights live block-compressed behind the
  memory controller and a ``WeightStreamer`` double-buffers layer
  decompresses through the same lane budget KV fetches contend for.

Reported per mode:

* tokens/s — streamed compute is bit-identical (asserted on every request's
  output tokens), so any delta is pure modeling overhead, not numerics;
* weight bytes/decode-token — physical (compressed) weight-read traffic per
  generated token, the number the paper's weight-side 25.2% is quoted over;
* weight bandwidth saving — ``report()["weights"]["bandwidth_saving"]``,
  the ONE savings definition shared with table3 (exact block bytes, never
  padded bytes);
* stall fraction — steps that closed their lane window before the pass's
  layers finished fetching, charged to modeled latency.

With ``json_path`` (the driver passes it under ``--json``) the rows are
MERGED into ``BENCH_serving.json`` under a ``"weight_stream"`` key — the
module runs after ``serving_bitplane`` and must not clobber its campaign
rows.

    PYTHONPATH=src python -m benchmarks.run --only serving_weight_stream
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import decode_tok_per_s, fmt_table, pct


def _mixed_requests(n, seed, vocab):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [
        Request(rid=i,
                prompt=rng.integers(0, vocab, int(rng.integers(8, 96)))
                .astype(np.int32),
                max_new_tokens=int(rng.choice([4, 8, 16])))
        for i in range(n)
    ]


def _run(model, params, cfg, reqs, arrivals, max_steps=None):
    from repro.serving import ContinuousScheduler, Request

    # warm pass: move every jit compile out of the measured window so tok/s
    # compares steady-state decode, not trace time
    warm = ContinuousScheduler(model, params, cfg)
    warm.submit(Request(rid=10 ** 6, prompt=np.arange(16, dtype=np.int32),
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
    return sched.report(), [list(r.output) for r in reqs]


def run(n_requests: int = 12, rate: float = 0.6, seed: int = 0,
        max_steps: int | None = None, json_path: str | None = None):
    import dataclasses

    import jax

    from repro.configs.base import get_config
    from repro.models.model import build_model
    from repro.serving import EngineConfig

    cfg_m = get_config("smollm-135m", smoke=True)
    model = build_model(cfg_m)
    params = model.init(jax.random.PRNGKey(0))
    base = EngineConfig(max_batch=4, max_ctx=256, store_layers=2,
                        weight_stream="resident")
    reqs_args = (n_requests, seed, cfg_m.vocab)
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(rng.exponential(1.0 / rate, n_requests)))

    out, rows, tokens = {}, [], {}
    for mode in ("resident", "compressed"):
        cfg = dataclasses.replace(base, weight_stream=mode)
        reqs = _mixed_requests(*reqs_args)
        rep, outs = _run(model, params, cfg, reqs, arrivals,
                         max_steps=max_steps)
        tokens[mode] = outs
        dec = max(1, rep["decode_tokens"])
        tok_s = decode_tok_per_s(rep)
        w = rep["weights"]
        if mode == "resident":
            bpt, saving, stall = 0.0, 0.0, 0.0
        else:
            bpt = w["read_physical_bytes"] / dec
            saving = w["bandwidth_saving"]
            stall = w["stall_fraction"]
        rows.append([mode, f"{tok_s:.1f}", f"{bpt:.0f}", pct(saving),
                     f"{stall:.3f}"])
        out[mode] = {
            "decode_tok_per_s": tok_s,
            "weight_bytes_per_token": bpt,
            "weight_bandwidth_saving": saving,
            "stall_fraction": stall,
            "decode_tokens": rep["decode_tokens"],
            "weights": w,
        }

    # the subsystem's whole claim: streaming is a memory-system model, not
    # a numerics change — every request's tokens must match exactly
    assert tokens["compressed"] == tokens["resident"], \
        "streamed decode diverged from resident weights"
    ws = out["compressed"]["weights"]
    assert 0.0 < ws["bandwidth_saving"] < 1.0, ws

    print(fmt_table(rows, ["weight mode", "tok/s", "weight B/tok",
                           "weight bw saving", "stall frac"]))
    print("[serving_weight_stream] streamed tokens bit-identical to "
          "resident; weight bandwidth saving is table3's exact-block "
          "definition (paper ballpark: ~25.2% on bf16 surrogates)")

    if json_path is not None:
        # merge, don't clobber: serving_bitplane owns this file and writes
        # its campaign rows first in the same --json run
        merged = {}
        if os.path.exists(json_path):
            with open(json_path) as fh:
                merged = json.load(fh)
        merged["weight_stream"] = out
        with open(json_path, "w") as fh:
            json.dump(merged, fh, indent=1)
        print(f"[serving_weight_stream] merged into {json_path}")
    return out


if __name__ == "__main__":
    run()
