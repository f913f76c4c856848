from repro.kernels.paged_attention.ops import (  # noqa: F401
    batched_ladder_paged_attention,
    ladder_paged_attention,
    pack_kv_planes,
)
