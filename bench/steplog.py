"""Per-layer metrics read from the serving program's step log.

``repro.telemetry.steps.LOG`` holds one record per ``ContinuousScheduler
.step()``: its start and end in ``perf_counter_ns``, nanoseconds per phase
(``serve.<phase>`` spans) and counters.  The client's window is on the same
clock (``perf_counter``), so a window step is one that starts and ends in
``[client.t_open, client.t_close]``, the rule ``Client.window_steps`` uses.

A program without the step log, a window with no step in it, or a log that
dropped records the window may have held gives None: the metric is then
left out of the line, where the benchmark's check sees it missing.
"""

from __future__ import annotations

from typing import List, Optional


def window_records(run) -> Optional[List[dict]]:
    try:
        from repro.telemetry.steps import LOG
    except ImportError:  # a program from before the step log
        return None
    c = run.client
    if c.t_open is None or c.t_close is None or not LOG.records:
        return None
    lo, hi = c.t_open * 1e9, c.t_close * 1e9
    if LOG.dropped and LOG.records[0]["start_ns"] >= lo:
        return None  # records pushed out of the log may lie in the window
    recs = [r for r in LOG.records if r["start_ns"] >= lo
            and r["end_ns"] <= hi]
    return recs or None


def mean_phase_ms(run, phases, decoded_only: bool = False
                  ) -> Optional[float]:
    """Mean over window steps of the phases' summed host time, in ms."""
    recs = window_records(run)
    if recs is not None and decoded_only:
        recs = [r for r in recs if r["decoded"]] or None
    if recs is None:
        return None
    total = sum(r["phase_ns"].get(p, 0) for r in recs for p in phases)
    return total * 1e-6 / len(recs)


def engine_tick_ms(run) -> Optional[float]:
    """``serve.engine_tick``: memctl servicing and every queued job's work."""
    return mean_phase_ms(run, ("engine_tick",))


def kv_pages_ms(run) -> Optional[float]:
    """``serve.kv_flush`` + ``serve.decode_commit``: prompt and decode page
    writes, device->host copies and fetch-job building."""
    return mean_phase_ms(run, ("kv_flush", "decode_commit"))


def decode_wait_ms(run) -> Optional[float]:
    """``serve.decode_wait`` over the steps that decoded: the part of the
    device step that host work does not hide."""
    return mean_phase_ms(run, ("decode_wait",), decoded_only=True)


def memctl_jobs_per_token(run) -> Optional[float]:
    recs = window_records(run)
    if recs is None:
        return None
    tokens = sum(r["decoded"] for r in recs)
    return sum(r["memctl_jobs"] for r in recs) / tokens if tokens else None
