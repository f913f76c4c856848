"""One run of one cell: set up, measure a window, check, print one line.

Set-up (``setup_s``, from process start to the window's opening): find the
chips, turn on the compile cache, draw the weights from the seed on the
device, build the serving program from the configuration, serve one
block of the traffic's own request sizes through the normal path (every
shape the window can meet), then bring the traffic to its steady state (a closed backlog
admits ``max_batch`` requests until every slot decodes; an open loop runs
its arrival process for ``lead_in_s``).

The window drives ``ContinuousScheduler.submit``/``step`` for ``--seconds``
(the last step started runs to its end), with the profiler on when
``--trace 1``.  After it: the peak memory is read, the program's state is
freed, and the served tokens are checked against the plain reference.
Standard output ends with one JSON line; everything else goes to standard
error, whose last lines are the numbers compared and their limits.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import itertools
import json
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Optional

import numpy as np

from bench import check, device, probe, spec
from bench.arch import make_weights
from bench.client import Client
from bench.traffic import Generator


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: spec.Cell
    #: the configuration's architecture file, and its ``parse`` result
    arch_module: ModuleType
    arch: object
    bitplane: bool
    setup_s: float
    client: Client
    trace: Optional[object]
    peaks: dict


def engine_config(config: dict):
    """The serving program's engine configuration."""
    from repro.core.quantization import PrecisionLadder
    from repro.serving import EngineConfig

    s = config["serving"]
    ladder = s.get("ladder")
    return EngineConfig(
        max_batch=s["max_batch"], max_ctx=s["max_ctx"], backend="paged",
        device_kv=s["device_kv"], decode_kernel=s.get("decode_kernel",
                                                      "fused"),
        ladder=PrecisionLadder([tuple(r) for r in ladder]) if ladder else None,
        prefix_sharing=False, weight_stream="resident",
    )


#: request ids of the warm-up requests, clear of the traffic's (from 0)
WARM_RID = 1 << 30


def warm_up(sched, traffic: dict, vocab: int, max_batch: int) -> None:
    """One block of the mix's own requests, two tokens each, through the
    normal path.  Every seed serves the same set of sizes, so this compiles
    (or loads) every prefill chunk and every host-side copy of a prompt's
    pages that the window can meet, and the decode step."""
    from repro.serving import Request

    gen = Generator(traffic, 0, vocab, max_batch)
    for item in itertools.islice(gen, gen.block):
        sched.submit(Request(rid=WARM_RID + item.rid, prompt=item.prompt,
                             max_new_tokens=2))
    sched.run_until_drained()


def attempted_failed(client: Client) -> tuple:
    """Requests in the window (open loop: due before it closed; closed
    backlog: admitted before it closed) and not finished before it opened,
    and how many of them failed (truncated or refused)."""
    backlog = client.gen.kind == "backlog"
    recs = [r for r in client.records.values()
            if r.due < client.t_close
            and (r.req.admit_step >= 0 or not backlog)
            and not (r.req.done and r.stamps
                     and r.stamps[-1] <= client.t_open)]
    return len(recs), sum(r.failed for r in recs)


def summarize_trace(trace_dir: str):
    from bench import trace as tr

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return tr.summarize(tr.load(str(files[-1])))


def run(args, t_start: float, root: Path = spec.ROOT, chips: bool = True,
        control: bool = False, cell: Optional[spec.Cell] = None,
        on_window=None) -> dict:
    """One run of ``args.workload``; returns the result line.  ``chips``
    False skips the look for a chip (the tests drive a run on the CPU);
    ``control`` also reads the fp8 control's gap on the same requests
    (``bench/calibrate.py``; the benchmark's own runs never do); ``cell``
    replaces the one the files describe and ``on_window(client)`` sees the
    client once the window has closed."""
    cell = cell or spec.load_cell(args.workload, root)
    dev = (device.require_chips(cell.chips) if chips else
           {"platform": "cpu", "kind": "cpu", "count": 1})
    cache_dir = device.use_compile_cache(root)
    compiles = device.CompileCounter()
    import jax

    from repro.models.model import build_model
    from repro.serving import ContinuousScheduler, Request

    cfg, traffic = cell.config, cell.traffic
    arch_module = spec.arch_module(cfg["model_type"], root)
    arch = arch_module.parse(cfg)
    bitplane = cfg["serving"]["device_kv"] == "bitplane"
    log(f"[setup] {dev['kind']} x{dev['count']}, compile cache {cache_dir}")
    params = jax.block_until_ready(make_weights(arch_module, arch,
                                                args.seed))
    t_w = time.perf_counter()
    sched = ContinuousScheduler(
        build_model(arch_module.model_config(cfg, arch)), params,
        engine_config(cfg))
    probe.name_programs(sched)
    max_batch = cfg["serving"]["max_batch"]
    warm_up(sched, traffic, arch.vocab, max_batch)
    t_warm = time.perf_counter()
    client = Client(sched, Generator(traffic, args.seed, arch.vocab,
                                     max_batch), max_batch, Request)
    if client.gen.kind == "backlog":
        client.prime_backlog()
    else:
        client.lead_in(float(traffic["arrivals"].get("lead_in_s", 0.0)))
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.3f} s: weights {t_w - t_start:.3f}, warm-up "
        f"{t_warm - t_w:.3f}, traffic to steady state "
        f"{t_start + setup_s - t_warm:.3f}; compile requests "
        f"{compiles.requests}, compiled {compiles.compiled}")

    before = compiles.snapshot()
    names_before = dict(compiles.names)
    summary = None
    with contextlib.ExitStack() as keep:
        trace_dir = None
        if args.trace:
            trace_dir = args.trace_dir or keep.enter_context(
                tempfile.TemporaryDirectory())
        with contextlib.ExitStack() as profiling:
            if trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                profiling.enter_context(jax.profiler.trace(
                    trace_dir, profiler_options=opts))
            with jax.profiler.TraceAnnotation("bench.window"):
                client.window(args.seconds)
        if trace_dir:
            summary = summarize_trace(trace_dir)
    if on_window:
        on_window(client)
    req, hits, secs = (a - b for a, b in zip(compiles.snapshot(), before))
    steps = client.window_steps()
    late = client.lateness()
    log(f"[window] {client.t_close - client.t_open:.3f} s, {len(steps)} "
        f"steps, {sum(len(s.decoded) for s in steps)} tokens; compiles in "
        f"window: {req} requests, {req - hits} compiled, {secs:.3f} s")
    if req:
        new = {n: k - names_before.get(n, 0) for n, k in compiles.names.items()
               if k > names_before.get(n, 0)}
        log(f"[window] programs compiled or loaded in the window: {new}")
    if late:
        log(f"[window] client lateness over {len(late)} requests: p50 "
            f"{np.percentile(late, 50) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms")
    memory = device.memory_peak_bytes() if chips else 0
    attempted, failed = attempted_failed(client)

    # free the program's state before the reference takes the chip
    sampled = check.sample(client.records.values(), args.seed,
                           cfg["check"]["sample_requests"])
    client.sched = sched = params = None
    del sched, params
    gc.collect()
    t_ref = time.perf_counter()
    gap, n_tok = check.widest_gap(cfg, arch_module, arch, args.seed,
                                  sampled)
    limit = check.limit(cfg)
    log(f"[check] reference over {len(sampled)} requests, {n_tok} served "
        f"tokens in {time.perf_counter() - t_ref:.3f} s")
    checks = {}
    if control:
        cgap, _ = check.widest_gap(cfg, arch_module, arch, args.seed,
                                   sampled, control=True)
        checks["control_logit_gap"] = {"value": cgap, "limit": limit}
    checks.update({
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "served_tokens_compared": {"value": n_tok, "limit": 1},
    })
    correct = (limit is not None and gap <= limit and failed == 0
               and n_tok >= 1)

    pk = device.peaks(dev["kind"]) if chips else device.peaks("TPU v5e")
    r = Run(cell, arch_module, arch, bitplane, setup_s, client, summary, pk)
    metrics = spec.read_metrics(cell.per_layer if args.trace
                                else cell.end_to_end, r, root)
    dev_out = dict(dev, memory_peak_bytes=memory)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev_out}
    if summary is not None:
        dev_out.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [list(x) for x in summary.device_ops],
            "idle_gaps": [list(x) for x in summary.idle_gaps],
        }
    result["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    return result


def parse(argv) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--trace-dir", default=None,
                   help="keep the raw trace here instead of a temporary "
                        "directory (for reading it by hand)")
    return p.parse_args(argv)


def main(argv, t_start: float) -> None:
    result = run(parse(argv), t_start)
    print(json.dumps(result), flush=True)
