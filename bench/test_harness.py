"""Whole runs of the harness on the CPU at a tiny size, with the look for a
chip skipped: a cell, configuration, traffic mix and metric added as files
alone run end to end and pass; the fp8 control fails the limit; and a token
altered where the scheduler samples it makes ``correct`` false.

The tiny limit (0.1) sits between readings on a CPU: over 6 s windows of
these cells (250-600 served tokens compared), program gaps of seeds 0-7
were 0.009-0.033 (bit-plane, pages read at 8, 12 and 16 planes) and
0.013-0.029 on seeds 0-2 (dense), and the control's 0.32-0.79; the
control's widest gap over 224 positions was 0.36-0.63 on seeds 0-4.
"""

import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, probe, spec
from bench.arch import make_weights
from bench.reference import llama

BENCH = Path(__file__).resolve().parent
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "vocab_size": 256}


def _config(src: str, name: str, **serving) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{src}.json").read_text())
    cfg.update(TINY, name=name)
    cfg["serving"].update(serving)
    cfg["check"].update(sample_requests=50, max_logit_gap=0.1)
    return cfg


@pytest.fixture
def root(tmp_path):
    """A checkout holding only throwaway entries: two tiny cells, their
    configurations and mixes, the architecture files, the metric readers,
    and two metrics more."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(BENCH / "archs", tmp_path / "bench" / "archs")
    (tmp_path / "bench" / "metrics" / "requests_served.py").write_text(
        "def read(run):\n"
        "    return sum(1 for r in run.client.records.values() if r.stamps)\n")
    (tmp_path / "bench" / "metrics" / "ttft_p95_ms.py").write_text(
        "from bench.readers import ttft_p95_ms as read  # noqa: F401\n")
    configs = {
        # under a precision ladder, so the check reads the plane map
        "tiny-bp": _config("smollm-135m-bp", "tiny-bp", max_batch=2,
                           max_ctx=256, ladder=[[4, 16], [4, 12], [-1, 8]]),
        "tiny-dense": _config("yi-9b-s8", "tiny-dense", max_batch=3,
                              max_ctx=128),
    }
    for name, cfg in configs.items():
        (tmp_path / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    mixes = {
        # 9-13 pages: past the ladder's 4 + 4 upper rungs, so the check
        # reads pages at 12 and 8 planes too
        "tiny-backlog": {"arrivals": {"kind": "backlog"},
                         "prompt_tokens": {"dist": "uniform", "lo": 140,
                                           "hi": 200},
                         "output_tokens": {"dist": "uniform", "lo": 8,
                                           "hi": 16}},
        "tiny-poisson": {"arrivals": {"kind": "poisson", "rate_per_s": 4.0,
                                      "lead_in_s": 1.0},
                         "prompt_tokens": {"dist": "lognormal", "median": 40,
                                           "sigma": 0.6, "lo": 10, "hi": 100},
                         "output_tokens": {"dist": "uniform", "lo": 4,
                                           "hi": 8},
                         "block": 8},
    }
    for name, mix in mixes.items():
        (tmp_path / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench = {
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in configs],
        "workloads": [
            {"name": "tiny-bp.backlog", "config": "tiny-bp",
             "traffic": "tiny-backlog", "chips": 1},
            {"name": "tiny-dense.poisson", "config": "tiny-dense",
             "traffic": "tiny-poisson", "chips": 1},
        ],
        "end_to_end": [
            {"name": "decode_tokens_per_s", "unit": "tokens/s",
             "workloads": ["tiny-bp.backlog"]},
            {"name": "ttft_p95_ms", "unit": "ms",
             "workloads": ["tiny-dense.poisson"]},
            {"name": "setup_s", "unit": "s"},
            {"name": "requests_served", "unit": "requests"},
        ],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.fixture
def own_cache_config():
    """The harness turns the persistent compile cache on for the process;
    put JAX's settings back for the tests that follow."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _run(root, workload, seed, control=False, on_window=None):
    args = harness.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", "2"])
    return harness.run(args, time.perf_counter(), root=root, chips=False,
                       control=control, on_window=on_window)


def test_cell_from_files_alone_runs_and_passes(root, own_cache_config):
    seen = {}
    res = _run(root, "tiny-bp.backlog", 3, control=True,
               on_window=lambda c: seen.update(client=c))
    assert res["correct"] is True
    # the ladder cut some page below full precision before a compared token
    assert any(row is not None and (row < 16).any()
               for r in seen["client"].records.values() if r.req.output
               for _, row in r.maps)
    assert set(res["metrics"]) == {"decode_tokens_per_s", "setup_s",
                                   "requests_served"}
    assert res["metrics"]["requests_served"]["value"] >= 2
    checks = res["checks"]
    assert list(res)[-1] == "checks"
    assert checks["max_logit_gap"]["value"] <= 0.1
    assert checks["served_tokens_compared"]["value"] >= 10
    assert checks["control_logit_gap"]["value"] >= 0


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("bitplane", [False, True])
def test_fp8_control_fails_the_limit(seed, bitplane):
    """The reference computed in fp8 in the program's place, read at each
    of 224 positions for the token it puts first."""
    cfg = _config("smollm-135m-bp", "tiny-bp")
    llama_arch = spec.arch_module(cfg["model_type"])
    arch = llama_arch.parse(cfg)
    tokens = np.random.default_rng(seed).integers(0, 256, 256,
                                                  dtype=np.int32)
    keep = None
    if bitplane:  # pages read at 8 and 12 planes from position 64 on
        keep = np.full((256, 16), 16, np.int32)
        keep[64:, :3], keep[64:, 3:6] = 8, 12
    gaps = llama.logit_gaps(make_weights(llama_arch, arch, seed), arch,
                            tokens, 32, keep_rows=keep, levels=(8, 12, 16),
                            control=True, pad_to=256)
    assert len(gaps) == 224
    assert gaps.max() > cfg["check"]["max_logit_gap"]


@pytest.mark.parametrize("workload,seed", [("tiny-bp.backlog", 3),
                                           ("tiny-dense.poisson", 1)])
def test_token_altered_where_sampled_is_not_correct(
        root, own_cache_config, monkeypatch, workload, seed):
    from repro.serving import scheduler

    real = scheduler.sample_slots
    calls = [0]

    def altered(keys, draws, logits, cfg):
        """Slot 0's decode token, one id off."""
        tok = real(keys, draws, logits, cfg)
        calls[0] += 1
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(scheduler, "sample_slots", altered)
    res = _run(root, workload, seed)
    assert calls[0] > 0
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > 0.1
    assert "ttft_p95_ms" in res["metrics"] or workload.endswith("backlog")
    assert np.isfinite(res["metrics"]["setup_s"]["value"])


@pytest.mark.parametrize("workload,seed", [("tiny-bp.backlog", 3),
                                           ("tiny-dense.poisson", 1)])
def test_decode_step_that_keeps_its_cache_is_not_correct(
        root, own_cache_config, monkeypatch, workload, seed):
    """The decode step returns its cache unchanged: no decoded token's key
    and value reach the cache, so later tokens attend without them."""
    from repro.serving import scheduler

    real = scheduler._jitted
    calls = [0]

    def stale(model, keeps=None, decode_kernel="fused"):
        prefill, decode, chunk = real(model, keeps, decode_kernel)

        def decode_keeping_cache(params, tokens, cache):
            calls[0] += 1
            logits, _ = decode(params, tokens, cache)
            return logits, cache
        return prefill, decode_keeping_cache, chunk

    monkeypatch.setattr(scheduler, "_jitted", stale)
    # the stand-in decode is a plain function, with no program to name
    monkeypatch.setattr(probe, "name_programs", lambda sched: None)
    res = _run(root, workload, seed)
    assert calls[0] > 0
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > 0.1


TOY_ARCH = '''\
import dataclasses
from pathlib import Path

from bench import spec

_llama = spec.arch_module("llama", Path(__file__).resolve().parents[2])
weight_shapes = _llama.weight_shapes
model_config = _llama.model_config


@dataclasses.dataclass(frozen=True)
class ToyArch(_llama.Arch):
    marker: str = "toyllama"


def parse(config):
    return ToyArch(**dataclasses.asdict(_llama.parse(config)))


def decode_step_need(arch, decoded, bitplane):
    flops, nbytes, kv = _llama.decode_step_need(arch, decoded, bitplane)
    return 2 * flops, nbytes, kv
'''

#: the FLOPs of the window's first decode step, as the run's architecture
#: counts them, where that architecture is the toy
TOY_FLOPS = '''\
def read(run):
    steps = [s.decoded for s in run.client.window_steps() if s.decoded]
    if getattr(run.arch, "marker", None) != "toyllama" or not steps:
        return None
    return run.arch_module.decode_step_need(run.arch, steps[0], False)[0]
'''


def _add_cell(root, model_type: str) -> str:
    """A tiny dense configuration of ``model_type`` and a backlog cell of
    it, added to the checkout as files and entries; returns the cell."""
    cfg = _config("yi-9b-s8", f"tiny-{model_type}", max_batch=2,
                  max_ctx=256)
    cfg["model_type"] = model_type
    (root / "bench" / "configs" / f"tiny-{model_type}.json").write_text(
        json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = f"tiny-{model_type}.backlog"
    bench["configs"].append({"name": f"tiny-{model_type}",
                             "file": f"bench/configs/tiny-{model_type}.json"})
    bench["workloads"].append({"name": cell, "config": f"tiny-{model_type}",
                               "traffic": "tiny-backlog", "chips": 1})
    bench["end_to_end"].append({"name": "toy_step_flops", "unit": "flop",
                                "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def test_architecture_added_as_a_file_alone_runs_and_passes(
        root, own_cache_config):
    (root / "bench" / "archs" / "toyllama.py").write_text(TOY_ARCH)
    (root / "bench" / "metrics" / "toy_step_flops.py").write_text(TOY_FLOPS)
    cell = _add_cell(root, "toyllama")
    seen = {}
    res = _run(root, cell, 5, on_window=lambda c: seen.update(client=c))
    assert res["correct"] is True
    llama_arch = spec.arch_module("llama")
    cfg = json.loads(
        (root / "bench" / "configs" / "tiny-toyllama.json").read_text())
    first = next(s.decoded for s in seen["client"].window_steps()
                 if s.decoded)
    flops, _, _ = llama_arch.decode_step_need(llama_arch.parse(cfg), first,
                                              False)
    assert res["metrics"]["toy_step_flops"]["value"] == 2 * flops


def test_configuration_without_an_architecture_file_fails_at_setup(
        root, own_cache_config):
    cell = _add_cell(root, "nosucharch")
    with pytest.raises(FileNotFoundError) as err:
        _run(root, cell, 0)
    assert str(root / "bench" / "archs" / "nosucharch.py") in str(err.value)
