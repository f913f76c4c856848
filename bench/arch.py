"""The weights every run draws from its seed, for any architecture.

Configuration files keep the public ``config.json`` keys of the model
(``hidden_size``, ``num_hidden_layers`` ...), so a key changed from the
source is visible by name.  The architecture file that the configuration's
``model_type`` names (``bench/archs/<model_type>.py``) parses them into the
handful of sizes the weights, the reference and the cost functions need,
and lays out the parameter tree.  The weights are drawn on the device in
one jitted call, in the dtype they are served in, and laid out as the
serving program takes them; the reference draws the same weights again from
the same seed instead of taking the program's arrays.
"""

from __future__ import annotations

import functools

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (two 32-bit words of its
    SeedSequence, so seeds past 2**32 do not wrap onto small ones)."""
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


@functools.lru_cache(maxsize=None)
def _generator(arch_module, a):
    import jax
    import jax.numpy as jnp

    shapes = arch_module.weight_shapes(a)
    dtype = jnp.dtype(a.dtype)

    def make(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, (shape, fan_in)) in zip(keys, sorted(shapes.items())):
            z = jax.random.normal(k, shape, jnp.float32)
            # norm scales near 1 (not exactly 1, so a dropped scale shows);
            # matrices at 1/sqrt(fan_in), so logits have unit scale
            w = 1.0 + 0.1 * z if fan_in == 0 else z / np.sqrt(fan_in)
            flat[path] = w.astype(dtype)
        return _nest(flat)

    return jax.jit(make)


def make_weights(arch_module, a, seed: int) -> dict:
    """All weights of the configuration from ``seed``, on the default
    device, in one jitted call: ``arch_module`` is its architecture file
    and ``a`` what that file's ``parse`` made of the configuration."""
    return _generator(arch_module, a)(seed_key(seed))
