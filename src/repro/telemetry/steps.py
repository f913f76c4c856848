"""Per-step spans and counters of the serving loop, on the profiler's clock.

``ContinuousScheduler.step()`` opens one step record with :func:`step` and
wraps each of its phases in :func:`span`; the code that does the work adds
to the step's counters with :func:`count`.  Each of the three has two views:

* **The profiler's.**  A step is a ``StepTraceAnnotation("serve.step")``
  and a span a ``TraceAnnotation("serve.<phase>")``, so when a profiler
  trace is recorded they land on the calling host thread of its
  ``.xplane.pb``, on the same clock as the device's operations.  Idle
  device time then carries the phase of the step that held it.
* **The step log's.**  The record also adds each span's elapsed
  ``perf_counter_ns`` to its phase and, when the step closes, goes into
  :data:`LOG`, a process-wide bounded log (the flight recorder): start and
  end in ``perf_counter_ns``, nanoseconds per phase, and counters.

The log is always on and does not depend on ``TelemetryConfig``: a step
costs a few clock reads and one small dict against steps of tens of
milliseconds, and a caller that never enables the collector (a benchmark
measuring the served path as deployed) still reads where the host time
went.  It lives outside the scheduler so it outlasts the scheduler's
state.  Overflow drops the oldest records and counts them
(:attr:`StepLog.dropped`), never silently.

Counters (zero unless the step did the work):

* ``decoded``: tokens committed;
* ``memctl_jobs``: memctl jobs serviced in the step's engine tick, summed
  over tiers; ``memctl_queue``: jobs still queued after it;
* ``d2h_bytes``: bytes the backend copied device->host (``slot_kv_host``
  and the batched decode page-fill copy); ``d2h_copies``: the transfers
  that moved them;
* ``fill_pages``: decode pages the batched page-fill copy served, one per
  slot whose page filled;
* ``fetch_probed`` / ``fetch_deferred``: (page key, tier) pairs of the
  decoding slots' live pages that decode fetch accounting classified one
  by one (resident, evicted or dead), and those it counted in bulk as
  waiting on a queued write; their sum is every live pair;
* ``compiles`` / ``compile_s``: XLA compile requests during the step and
  their seconds, from JAX's ``backend_compile_duration`` event (a load
  from the persistent compile cache is a request too);
* ``moe_held_picks``: (token, pick) pairs of the step's decoding rows that
  the router sent to an expert this chip holds, summed over the expert
  layers (models with experts at a chip's share; the decode step returns
  it beside its tokens).

``span``, ``count`` and ``step`` are module functions, not collector
methods: they are not gated on ``telemetry.enabled``.
"""

from __future__ import annotations

from collections import deque
from time import perf_counter_ns
from typing import Optional

import jax

#: the phases of ``ContinuousScheduler.step()``, in the order they run;
#: together they tile it (its idle branch runs the last three)
PHASES = ("admit", "prefill_dispatch", "decode_dispatch", "kv_flush",
          "decode_wait", "decode_commit", "engine_tick", "retire")
COUNTERS = ("decoded", "memctl_jobs", "memctl_queue", "d2h_bytes",
            "d2h_copies", "fill_pages", "fetch_probed", "fetch_deferred",
            "compiles", "compile_s", "moe_held_picks")
PREFIX = "serve."
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class StepLog:
    """Bounded log of closed step records, oldest first.  A record that
    pushes out the oldest is counted in ``dropped``."""

    def __init__(self, maxlen: int = 8192):
        self.records: deque = deque(maxlen=maxlen)
        self.dropped = 0

    def append(self, record: dict) -> None:
        if len(self.records) == self.records.maxlen:
            self.dropped += 1
        self.records.append(record)


#: the process-wide flight recorder every step closes into
LOG = StepLog()
#: the record of the step now running (None between steps)
_open: Optional[dict] = None


class _Span:
    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str):
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(PREFIX + name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = perf_counter_ns()

    def __exit__(self, *exc):
        dt = perf_counter_ns() - self.t0
        self.ann.__exit__(*exc)
        rec = _open
        if rec is not None:
            ph = rec["phase_ns"]
            ph[self.name] = ph.get(self.name, 0) + dt


class _Step:
    __slots__ = ("ann", "record", "prev")

    def __init__(self, step_num: int):
        self.ann = jax.profiler.StepTraceAnnotation(PREFIX + "step",
                                                    step_num=step_num)
        self.record = {"step": step_num, "start_ns": 0, "end_ns": 0,
                       "phase_ns": {}, **dict.fromkeys(COUNTERS, 0)}

    def __enter__(self) -> dict:
        global _open
        self.ann.__enter__()
        self.prev, _open = _open, self.record
        self.record["start_ns"] = perf_counter_ns()
        return self.record

    def __exit__(self, *exc):
        global _open
        self.record["end_ns"] = perf_counter_ns()
        _open = self.prev
        LOG.append(self.record)
        self.ann.__exit__(*exc)


def step(step_num: int) -> _Step:
    """``with step(n) as record:`` — one scheduler step; the record closes
    into :data:`LOG` on exit."""
    return _Step(step_num)


def span(name: str) -> _Span:
    """``with span(phase):`` — one phase of the open step (``serve.<phase>``
    in a profiler trace); outside a step it only annotates."""
    return _Span(name)


def count(name: str, n) -> None:
    """Add ``n`` to counter ``name`` of the open step (no-op outside one)."""
    rec = _open
    if rec is not None:
        rec[name] += n


def _on_duration(event: str, secs: float, **_) -> None:
    rec = _open
    if event == _COMPILE_EVENT and rec is not None:
        rec["compiles"] += 1
        rec["compile_s"] += secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)
