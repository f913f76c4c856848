"""Smoke run of the serving path on one TPU chip.

    python chip_smoke.py

Serves smollm-135m at its published widths (30 layers, d_model 576, 9 query
and 3 KV heads of 64) with weights drawn from a seed, through the normal
path: ``ContinuousScheduler.submit/step`` -> ``KVBackend`` -> device cache
-> decode kernel.  One process, three phases over the same seeded request
set (8 prompts of 64-600 tokens, 32 greedy tokens each):

  (a) dense      device_kv="dense"
  (b) bitplane   device_kv="bitplane", decode_kernel="fused", no ladder
  (c) ladder     (b) under PrecisionLadder([(4, 16), (4, 12), (-1, 8)])

It fails (non-zero exit, no result line) unless JAX runs on a TPU with the
Pallas kernels compiled, every request retires with its tokens, (b)'s
first decode logits match (a)'s within LOGIT_TOL of their scale, the
bit-plane decode step holds a Mosaic kernel (``tpu_custom_call``) and never
the rung kernel, and (c) reads fewer device bytes than (b).  The seconds it
prints are smoke timings of this run, not metrics.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_REQUESTS = 8
NEW_TOKENS = 32
PROMPT_LENS = (64, 600)
MAX_BATCH = 8
MAX_CTX = 1024
LADDER = [(4, 16), (4, 12), (-1, 8)]
#: (b) vs (a) first-decode-step logits: max |diff| <= LOGIT_TOL * max |a|.
#: The two paths differ only in decode attention: the dense einsum versus
#: the Pallas kernel, which rounds the softmax weights to bf16 (relative
#: error 2^-9) before its PV matmul and sums in another order.  That is a
#: few bf16 ulps per layer; over 30 residual layers and a bf16 vocab
#: projection it stays well inside 1/32 of the logit scale (8 bf16 ulps
#: of the largest logit), while a wrong plane, head or page moves logits
#: by their own scale.
LOGIT_TOL = 1 / 32


def make_requests(seed: int, vocab: int):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(lens)]


def run_phase(name: str, model, params, seed: int, **engine) -> dict:
    """Serve the request set once; returns the first decode step's logits,
    the compiled decode step's text, the report and the outputs."""
    from repro.serving import ContinuousScheduler, EngineConfig

    cfg = EngineConfig(max_batch=MAX_BATCH, max_ctx=MAX_CTX, backend="paged",
                       **engine)
    sched = ContinuousScheduler(model, params, cfg)
    decode = sched._decode
    first = {}

    def recording_decode(p, tok, cache):
        if not first:
            first["args"] = (p, tok, cache)
            logits, new_cache = decode(p, tok, cache)
            first["logits"] = np.asarray(logits)
            return logits, new_cache
        return decode(p, tok, cache)

    sched._decode = recording_decode
    reqs = make_requests(seed, model.cfg.vocab)
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    wall = time.perf_counter() - t0
    for r in reqs:
        if not r.done or r.truncated or len(r.output) != NEW_TOKENS:
            raise RuntimeError(f"phase {name}: request {r.rid} retired with "
                               f"{len(r.output)}/{NEW_TOKENS} tokens")
    if "logits" not in first:
        raise RuntimeError(f"phase {name}: no decode step ran")
    step_text = decode.lower(*first["args"]).compile().as_text()
    rep = sched.report()
    print(f"[{name}] {N_REQUESTS} requests retired, "
          f"device_bytes_read={rep['device_bytes_read']}, "
          f"smoke wall {wall:.2f} s", flush=True)
    del sched, first["args"]
    return {"logits": first["logits"], "step_text": step_text, "report": rep,
            "outputs": [list(r.output) for r in reqs]}


def run(model, params, seed: int = 0) -> dict:
    """The three phases and their checks; raises on any failure."""
    from repro.core.quantization import PrecisionLadder
    from repro.kernels.paged_attention.kernel import TRACE_COUNTS

    dense = run_phase("a dense", model, params, seed, device_kv="dense")
    full = run_phase("b bitplane", model, params, seed, device_kv="bitplane",
                     decode_kernel="fused")
    ladder = run_phase("c ladder", model, params, seed, device_kv="bitplane",
                       decode_kernel="fused", ladder=PrecisionLadder(LADDER))

    la, lb = dense["logits"], full["logits"]
    diff = float(np.max(np.abs(lb - la)))
    scale = float(np.max(np.abs(la)))
    agree = float(np.mean(np.argmax(la, -1) == np.argmax(lb, -1)))
    print(f"[b vs a] first decode step: max |logit diff| {diff:.6g} "
          f"(logit scale {scale:.6g}, ratio {diff / scale:.3g}, "
          f"limit {LOGIT_TOL:.3g}); greedy agreement {agree:.3f}", flush=True)
    if not diff <= LOGIT_TOL * scale:
        raise RuntimeError("bit-plane logits disagree with dense beyond "
                           "the stated tolerance")
    bytes_b = full["report"]["device_bytes_read"]
    bytes_c = ladder["report"]["device_bytes_read"]
    print(f"[c vs b] device_bytes_read {bytes_c} < {bytes_b}: "
          f"{bytes_c < bytes_b}", flush=True)
    if not bytes_c < bytes_b:
        raise RuntimeError("the ladder did not cut device bytes read")
    if TRACE_COUNTS["rung"] > 0:
        raise RuntimeError(f"rung kernel traced {TRACE_COUNTS['rung']} times")
    for name, phase in (("b", full), ("c", ladder)):
        if "tpu_custom_call" not in phase["step_text"]:
            raise RuntimeError(f"phase {name}: decode step holds no Mosaic "
                               f"kernel (tpu_custom_call)")
    return {"logit_diff": diff, "greedy_agreement": agree}


def main() -> None:
    t_start = time.perf_counter()
    from repro.runtime.device import require_tpu, use_compile_cache

    cache_dir = use_compile_cache(ROOT / ".jax_cache")
    import jax
    import jax.monitoring

    from repro.configs.base import get_config
    from repro.kernels import default_interpret
    from repro.models.model import build_model

    device = require_tpu()
    if default_interpret():
        raise SystemExit("Pallas kernels would run in interpret mode")
    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    cache_path = Path(cache_dir)
    cached = len(list(cache_path.iterdir())) if cache_path.is_dir() else 0
    print(f"device {device['kind']} x{device['count']}, compile cache "
          f"{cache_dir} ({cached} entries at start)", flush=True)
    model = build_model(get_config("smollm-135m"))
    params = model.init(jax.random.PRNGKey(0))
    run(model, params)
    print(f"smoke timings: compile {compile_s[0]:.2f} s, total "
          f"{time.perf_counter() - t_start:.2f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
