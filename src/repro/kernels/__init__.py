"""Pallas kernels.  ``default_interpret`` is the one place a kernel's
``interpret=None`` resolves."""

from __future__ import annotations

import os

import jax


def default_interpret() -> bool:
    """Pallas interpret mode: the interpreter on the CPU, compiled Mosaic on
    a TPU.  ``REPRO_PALLAS_INTERPRET=0`` forces compilation (the described-
    topology compile tests lower for a TPU from a CPU process);
    ``REPRO_PALLAS_INTERPRET=1`` is for debugging on the CPU only, and is
    refused on an accelerator rather than silently interpreting there."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    on_cpu = jax.default_backend() == "cpu"
    if env is None:
        return on_cpu
    if env in ("0", "false", "False"):
        return False
    if not on_cpu:
        raise ValueError(
            f"REPRO_PALLAS_INTERPRET={env} on backend "
            f"{jax.default_backend()!r}: the interpreter override is for "
            f"CPU debugging only; pass interpret=True explicitly instead"
        )
    return True
