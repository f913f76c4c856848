"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests that were served tokens, drawn from the seed and always
holding the one with the most, is run once through the configuration's
plain float32 reference over its prompt and served tokens.  The number
compared is the widest gap by which a served token's logit lies below the
reference's best logit at its position (greedy decoding serves the argmax,
so a sound run only loses near-ties to rounding).  For a bit-plane
configuration the reference reads each page at the planes the ladder's map
gave the decode step that produced the token.
"""

from __future__ import annotations

import importlib
from typing import List, Optional

import numpy as np

from bench.arch import make_weights

PAGE = 16


def sample(records, seed: int, n: int) -> list:
    """Up to ``n`` records that were served tokens: the longest, and the
    rest drawn from the seed."""
    served = sorted((r for r in records if r.req.output), key=lambda r: r.rid)
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.req.output), -r.rid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def keep_rows(rec, n_tokens: int) -> np.ndarray:
    """(n_tokens, pages) planes each query position read of each page: full
    precision through the prompt and the first decode step; decode step k
    (query position prompt + k - 1) read the map taken after step k - 1."""
    pages = -(-n_tokens // PAGE)
    out = np.full((n_tokens, pages), 16, np.int32)
    p = rec.prompt_len
    for i, (m, row) in enumerate(rec.maps):
        if row is None:
            continue
        end = rec.maps[i + 1][0] if i + 1 < len(rec.maps) else n_tokens
        lo, hi = p + m, min(p + end, n_tokens)
        if lo < hi:
            out[lo:hi] = np.asarray(row[:pages])
    return out


def plane_levels(config: dict) -> tuple:
    ladder = config["serving"].get("ladder") or []
    return tuple(sorted({16} | {int(planes) for _, planes in ladder}))


def reference_module(config: dict):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def sequences(records) -> List[tuple]:
    """(tokens, prompt length, record) of each sampled request."""
    return [(np.concatenate([r.req.prompt,
                             np.asarray(r.req.output, np.int32)]),
             r.prompt_len, r) for r in records]


def widest_gap(config: dict, arch_module, arch, seed: int, records,
               control: bool = False) -> tuple:
    """(widest gap, served tokens compared) over the sampled requests —
    of the served tokens, or with ``control`` of the fp8 forward's own
    picks at the same positions.  ``arch_module`` is the configuration's
    architecture file and ``arch`` its ``parse`` of the configuration."""
    ref = reference_module(config)
    bitplane = config["serving"]["device_kv"] == "bitplane"
    levels = plane_levels(config)
    w = make_weights(arch_module, arch, seed)
    widest, count = 0.0, 0
    for tokens, p, rec in sequences(records):
        rows = keep_rows(rec, len(tokens)) if bitplane else None
        g = ref.logit_gaps(w, arch, tokens, p, keep_rows=rows,
                           levels=levels, control=control)
        widest = max(widest, float(np.max(g)))
        count += len(g)
    return widest, count


def limit(config: dict) -> Optional[float]:
    return config["check"]["max_logit_gap"]
