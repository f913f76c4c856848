"""The arithmetic behind each metric; ``bench/metrics/<name>.py`` binds a
metric's name to one of these.  Each takes the finished run and returns a
number, or None when the run holds nothing for it to read."""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def p95(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 95)) if values else None


def ttft_samples(client) -> List[float]:
    """Seconds from due time to first token of every request due in the
    window; one with no first token when the window closes enters at its
    wait so far."""
    out = []
    for r in client.records.values():
        if not client.t_open <= r.due < client.t_close:
            continue
        first = r.stamps[0] if r.stamps else None
        out.append((first if first is not None and first <= client.t_close
                    else client.t_close) - r.due)
    return out


def itl_samples(client) -> List[float]:
    """Every gap between consecutive tokens of a request whose later token
    came in the window."""
    out = []
    for r in client.records.values():
        for a, b in zip(r.stamps, r.stamps[1:]):
            if client.in_window(b):
                out.append(b - a)
    return out


# ------------------------------------------------------------ end to end
def setup_s(run) -> float:
    return run.setup_s


def decode_tokens_per_s(run) -> Optional[float]:
    c = run.client
    n = sum(1 for r in c.records.values() for t in r.stamps
            if c.in_window(t))
    return n / (c.t_close - c.t_open) if n else None


def ttft_p95_ms(run) -> Optional[float]:
    v = p95(ttft_samples(run.client))
    return None if v is None else v * 1e3


def itl_p95_ms(run) -> Optional[float]:
    v = p95(itl_samples(run.client))
    return None if v is None else v * 1e3


# ------------------------------------------------------- from the trace
def device_idle_share(run) -> Optional[float]:
    s = run.trace
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)


def host_ms_per_step(run) -> Optional[float]:
    """Host time of a ``step()`` less the device-busy time inside it, mean
    over the window's steps."""
    s = run.trace
    if s is None or not s.steps:
        return None
    return 1e3 * float(np.mean([host - busy for host, busy in s.steps]))


def _program_ms(run, role: str) -> Optional[float]:
    s = run.trace
    if s is None or not s.programs.get(role):
        return None
    return 1e3 * float(np.mean(s.programs[role]))


def decode_step_ms(run) -> Optional[float]:
    return _program_ms(run, "decode")


def _window_decodes(run):
    return [st.decoded for st in run.client.window_steps() if st.decoded]


def fused_kernel_roofline(run) -> Optional[float]:
    """Least time for the KV bytes the traced decode steps needed, at the
    chip's HBM bandwidth, over the fused kernel's device time (bound by
    bytes: the kernel's operations are far below the compute peak)."""
    s = run.trace
    secs = s.kernels.get("fused_kernel") if s is not None else None
    if not secs or not run.bitplane:
        return None
    kv = sum(run.arch_module.decode_step_need(run.arch, d, True)[2]
             for d in _window_decodes(run))
    return 100.0 * kv / run.peaks["hbm_bytes_per_s"] / secs


def decode_mfu(run) -> Optional[float]:
    """Least time for each traced decode step's necessary work (the larger
    of its operations at the bf16 peak and its bytes at the HBM peak),
    summed, over the traced window's wall time."""
    s = run.trace
    steps = _window_decodes(run)
    if s is None or not steps or s.window_s <= 0:
        return None
    least = 0.0
    for d in steps:
        flops, nbytes, _ = run.arch_module.decode_step_need(run.arch, d,
                                                            run.bitplane)
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s.window_s
