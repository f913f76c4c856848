"""Shared benchmark utilities: table formatting, surrogate suites, KV
harvesting from the repo's own models, timing."""

from __future__ import annotations

import time

import numpy as np


def fmt_table(rows: list, headers: list) -> str:
    widths = [
        max(len(str(h)), max((len(str(r[i])) for r in rows), default=0))
        for i, h in enumerate(headers)
    ]
    line = " | ".join(str(h).ljust(w) for h, w in zip(headers, widths))
    sep = "-+-".join("-" * w for w in widths)
    body = "\n".join(
        " | ".join(str(c).ljust(w) for c, w in zip(r, widths)) for r in rows
    )
    return f"{line}\n{sep}\n{body}"


def pct(x: float) -> str:
    return f"{100 * x:.1f}%"


def decode_tok_per_s(rep: dict) -> float:
    """Decode tokens over the host seconds from each decode dispatch to its
    tokens on the host: the ``decode_dispatch``, ``kv_flush`` and
    ``decode_wait`` phases of ``report()["host_s_by_phase"]``.  Off a chip
    this is a host number, not a device metric."""
    ph = rep["host_s_by_phase"]
    secs = ph["decode_dispatch"] + ph["kv_flush"] + ph["decode_wait"]
    return rep["decode_tokens"] / secs if secs else 0.0


def time_call(fn, *args, reps: int = 3, **kw):
    fn(*args, **kw)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / reps
    return out, dt * 1e6  # us


def harvest_model_kv(arch: str = "smollm-135m", tokens: int = 512,
                     train_steps: int = 0, seed: int = 0):
    """Run the repo's own (smoke) model over synthetic text and return the
    per-layer KV tensors [(tokens, channels) bf16] — real KV, not surrogate.

    ``train_steps`` > 0 briefly trains first so the KV statistics move from
    random-init toward a trained model's (channel structure emerges fast).
    """
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from repro.configs.base import get_config
    from repro.data import DataConfig, ShardedLoader
    from repro.models.model import build_model
    from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)

    dc = DataConfig(vocab=cfg.vocab, seq_len=min(tokens, 256), global_batch=8, seed=seed)
    loader = ShardedLoader(dc)
    if train_steps:
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=train_steps)
        opt = adamw_init(params)

        @jax.jit
        def step(params, opt, batch):
            loss, g = jax.value_and_grad(model.loss)(params, batch)
            params, opt, _ = adamw_update(g, opt, params, opt_cfg)
            return params, opt, loss

        for s in range(train_steps):
            b = loader.batch_at(s)
            params, opt, loss = step(params, opt, {k: jnp.asarray(v) for k, v in b.items()})

    dc_long = DataConfig(vocab=cfg.vocab, seq_len=tokens, global_batch=1, seed=seed + 1)
    prompt = ShardedLoader(dc_long).batch_at(0)["tokens"]
    _, cache = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(prompt)})
    k_np = np.asarray(cache["k"], np.float32)  # (L, 1, S, H, hd)
    out = []
    for li in range(k_np.shape[0]):
        out.append(k_np[li, 0].reshape(tokens, -1).astype(ml_dtypes.bfloat16))
    return out
