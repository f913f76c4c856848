"""The per-layer metrics read from the program's step log: a tiny cell on
the CPU, traced, reports each of them, and the window rule drops nothing
silently."""

import json
import shutil
import time
import types

import pytest

from bench import harness, steplog
from bench.test_harness import BENCH, _config, own_cache_config  # noqa: F401

METRICS = ("engine_tick_ms.backlog", "kv_pages_ms.backlog",
           "decode_wait_ms.backlog", "memctl_jobs_per_token.backlog")


@pytest.fixture
def root(tmp_path):
    """A checkout holding one tiny backlog cell, the architecture files
    and the metric readers."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(BENCH / "archs", tmp_path / "bench" / "archs")
    cfg = _config("smollm-135m-bp", "tiny-bp", max_batch=2, max_ctx=256)
    (tmp_path / "bench" / "configs" / "tiny-bp.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "tiny-backlog.json").write_text(
        json.dumps({"arrivals": {"kind": "backlog"},
                    "prompt_tokens": {"dist": "uniform", "lo": 40, "hi": 80},
                    "output_tokens": {"dist": "uniform", "lo": 8,
                                      "hi": 16}}))
    cell = "tiny-bp.backlog"
    bench = {
        "configs": [{"name": "tiny-bp", "file": "bench/configs/tiny-bp.json"}],
        "workloads": [{"name": cell, "config": "tiny-bp",
                       "traffic": "tiny-backlog", "chips": 1}],
        "end_to_end": [{"name": "decode_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": m, "unit": "ms", "moves":
                       "decode_tokens_per_s", "workloads": [cell]}
                      for m in METRICS],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_traced_cell_reports_step_log_metrics(root, own_cache_config):
    args = harness.parse(["--workload", "tiny-bp.backlog", "--seed",
                          "2147483659", "--seconds", "2", "--trace", "1"])
    res = harness.run(args, time.perf_counter(), root=root, chips=False)
    assert res["correct"] is True
    for m in METRICS:
        assert res["metrics"][m]["value"] > 0, m


def _run_over(records, dropped, t_open, t_close, monkeypatch):
    from repro.telemetry import StepLog, steps

    log = StepLog()
    for r in records:
        log.append(r)
    log.dropped = dropped
    monkeypatch.setattr(steps, "LOG", log)
    return types.SimpleNamespace(client=types.SimpleNamespace(
        t_open=t_open, t_close=t_close))


def _rec(start_s, end_s, decoded=1, jobs=4, **phase_ms):
    return {"start_ns": int(start_s * 1e9), "end_ns": int(end_s * 1e9),
            "decoded": decoded, "memctl_jobs": jobs,
            "phase_ns": {p: int(ms * 1e6) for p, ms in phase_ms.items()}}


def test_window_rule_and_dropped_records(monkeypatch):
    recs = [_rec(0.5, 1.5, engine_tick=9.0),    # straddles the opening
            _rec(2.0, 3.0, engine_tick=2.0, decode_wait=1.0),
            _rec(3.0, 4.0, decoded=0, jobs=2, engine_tick=4.0),
            _rec(4.5, 6.0, engine_tick=9.0)]    # ends after the close
    run = _run_over(recs, 0, 1.0, 5.0, monkeypatch)
    assert steplog.engine_tick_ms(run) == pytest.approx(3.0)
    assert steplog.decode_wait_ms(run) == pytest.approx(1.0)
    assert steplog.memctl_jobs_per_token(run) == pytest.approx(6.0)
    # records pushed out of the log before the window: nothing lost in it
    run = _run_over(recs, 5, 1.0, 5.0, monkeypatch)
    assert steplog.engine_tick_ms(run) == pytest.approx(3.0)
    # the oldest record left starts in the window: some may be lost
    run = _run_over(recs[1:], 1, 1.0, 5.0, monkeypatch)
    assert steplog.engine_tick_ms(run) is None
    # no step inside the window
    run = _run_over(recs, 0, 6.5, 7.0, monkeypatch)
    assert steplog.kv_pages_ms(run) is None
