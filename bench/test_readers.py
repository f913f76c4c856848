"""The tail arithmetic of the end-to-end metrics, censored waits included,
and the bytes and operations a decode step needs."""

import types

import numpy as np
import pytest

from bench import cost, readers, spec
from bench.client import Client, Record, Step


def client_with(records, t_open=10.0, t_close=20.0, steps=()):
    c = Client(None, [], 8, None)
    c.t_open, c.t_close = t_open, t_close
    c.records = {r.rid: r for r in records}
    c.steps = list(steps)
    return c


def rec(rid, due, stamps, prompt_len=4):
    req = types.SimpleNamespace(output=[0] * len(stamps), admit_step=0,
                                done=False)
    return Record(rid, prompt_len, req, due, due, list(stamps))


def test_ttft_counts_from_due_time_and_censors_at_close():
    c = client_with([
        rec(0, 11.0, [11.5, 12.0]),   # served: 0.5 s
        rec(1, 15.0, [21.0]),         # first token after close: 5 s so far
        rec(2, 19.0, []),             # none yet: 1 s so far
        rec(3, 9.0, [10.5]),          # due before the window: not counted
        rec(4, 20.0, []),             # due at the close: not counted
    ])
    assert sorted(readers.ttft_samples(c)) == pytest.approx([0.5, 1.0, 5.0])


def test_itl_takes_gaps_whose_later_token_is_in_the_window():
    c = client_with([rec(0, 1.0, [9.0, 10.5, 12.0, 20.0, 21.0])])
    assert readers.itl_samples(c) == pytest.approx([1.5, 1.5, 8.0])


def test_p95_is_linear_between_order_statistics():
    vals = list(range(1, 101))
    assert readers.p95(vals) == pytest.approx(95.05)
    assert readers.p95([]) is None


def test_tail_metrics_in_ms():
    c = client_with([rec(i, 11.0, [11.0 + 0.01 * (i + 1), 11.5 + i])
                     for i in range(20)])
    run = types.SimpleNamespace(client=c)
    ttft = [0.01 * (i + 1) for i in range(20)]
    assert readers.ttft_p95_ms(run) == pytest.approx(
        1e3 * np.percentile(ttft, 95))
    assert readers.itl_p95_ms(run) is not None


def test_decode_rate_counts_tokens_in_the_window():
    c = client_with([rec(0, 1.0, [9.0, 11.0, 12.0]),
                     rec(1, 1.0, [15.0, 25.0])])
    assert readers.decode_tokens_per_s(types.SimpleNamespace(client=c)) \
        == pytest.approx(3 / 10)


LLAMA = spec.arch_module("llama")
ARCH = LLAMA.Arch(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=256, rope_theta=1e4,
                  norm_eps=1e-5, tie_embeddings=True, dtype="bfloat16")


def test_kv_bytes_follow_the_plane_map_of_live_pages():
    per_token = ARCH.kv_bytes_per_token()
    assert per_token == 2 * 2 * 2 * 16 * 2
    assert cost.kv_bytes(per_token, 40, None) == 40 * per_token
    row = np.array([16, 8, 12, 4, 4])  # pages past the length do not count
    want = per_token * (16 * 16 + 16 * 8 + 8 * 12) / 16
    assert cost.kv_bytes(per_token, 40, row) == pytest.approx(want)


def test_decode_step_need_adds_weights_once():
    dec = [(0, 40, None), (1, 20, None)]
    flops, nbytes, kv = LLAMA.decode_step_need(ARCH, dec, bitplane=False)
    assert kv == 60 * ARCH.kv_bytes_per_token()
    assert nbytes == kv + LLAMA.weight_bytes(ARCH)
    one, _, _ = LLAMA.decode_step_need(ARCH, dec[:1], bitplane=False)
    assert flops > one


def test_decode_mfu_is_least_time_over_the_window():
    steps = [Step(11.0, 12.0, [(0, 40, None)]),
             Step(12.0, 13.0, [(0, 41, None)])]
    c = client_with([], steps=steps)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    trace = types.SimpleNamespace(window_s=10.0)
    run = types.SimpleNamespace(client=c, arch_module=LLAMA, arch=ARCH,
                                bitplane=False, trace=trace, peaks=peaks)
    least = sum(max(f / 1e12, b / 1e9) for f, b, _ in (
        LLAMA.decode_step_need(ARCH, s.decoded, False) for s in steps))
    assert readers.decode_mfu(run) == pytest.approx(100 * least / 10.0)


def test_a_metric_that_finds_nothing_is_left_out_and_named(tmp_path,
                                                            capsys):
    from bench import spec

    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "nothing.py").write_text(
        "def read(run):\n    return None\n")
    (tmp_path / "bench" / "metrics" / "one.py").write_text(
        "def read(run):\n    return 1.0\n")
    entries = [{"name": "one", "unit": "s"}, {"name": "nothing", "unit": "%"}]
    assert spec.read_metrics(entries, None, tmp_path) == {
        "one": {"value": 1.0, "unit": "s"}}
    assert "nothing: nothing to read" in capsys.readouterr().err


def _sched(backend_slots, rid=5):
    slot = types.SimpleNamespace(prefilling=False,
                                 req=types.SimpleNamespace(rid=rid))
    return types.SimpleNamespace(_slots=[None, slot],
                                 backend=types.SimpleNamespace(
                                     _slots=backend_slots))


def test_probe_reads_the_plane_row_and_raises_when_it_is_gone():
    from bench import probe

    row = np.array([16, 8])
    st = types.SimpleNamespace(rid=5, device_row=row)
    assert probe.plane_rows(_sched({1: st}))[5] is row
    with pytest.raises(probe.ProbeError):
        probe.plane_rows(_sched({}))             # no state for the slot
    with pytest.raises(probe.ProbeError):
        probe.plane_rows(_sched({1: types.SimpleNamespace(rid=5)}))
    with pytest.raises(probe.ProbeError):
        probe.plane_rows(types.SimpleNamespace(
            _slots=[], backend=types.SimpleNamespace()))


def test_probe_names_the_programs_or_raises():
    from bench import probe

    def decode():
        pass

    def chunk():
        pass

    sched = types.SimpleNamespace(
        _decode=types.SimpleNamespace(__wrapped__=decode),
        _prefill_chunk=types.SimpleNamespace(__wrapped__=chunk))
    probe.name_programs(sched)
    assert (decode.__name__, chunk.__name__) == ("decode", "prefill_chunk")
    with pytest.raises(probe.ProbeError):
        probe.name_programs(types.SimpleNamespace(_decode=sched._decode))
    with pytest.raises(probe.ProbeError):
        probe.name_programs(types.SimpleNamespace(_decode=decode,
                                                  _prefill_chunk=chunk))
