"""Operations and bytes a decode step needs, from shapes alone.

A step's necessary bytes are its weights read once plus the live tokens' KV;
for a bit-plane cache the KV counts the logical bytes of the planes the
step's plane map keeps.  Padding, plane-unit rounding and pages past a
slot's length are not counted, so a share computed from these rises when a
change removes waste.  Necessary operations are two per weight per token
plus the attention's products over the live tokens.

Each architecture file (``bench/archs/<model_type>.py``) counts its own
weights, operations and bytes of KV a token; what is shared by every
architecture is here.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

PAGE = 16

#: bytes of one element of each served dtype
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def kv_bytes(per_token: int, live: int, row: Optional[np.ndarray],
             bits: int = 16) -> float:
    """KV bytes of ``live`` tokens at ``per_token`` bytes each (all layers)
    at the planes the map ``row`` keeps per 16-token page (None: every
    plane)."""
    if row is None:
        return float(live * per_token)
    pages = -(-live // PAGE)
    tokens = np.full(pages, PAGE)
    tokens[-1] = live - PAGE * (pages - 1)
    keep = np.asarray(row[:pages], np.float64)
    return float(per_token * np.sum(tokens * keep) / bits)
