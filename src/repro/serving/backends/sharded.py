"""Sharded-KV serving tier: per-shard slot map + compressed store + memctl
lane budget, with pages routed by KV-head ownership.

The route comes from the SAME mesh rules the runtime uses to shard real
decode caches (``runtime/sharding``): an abstract ``('data', 'model')``
mesh of ``shards`` model-parallel workers is consulted through
:func:`cache_pspecs` / ``_kv_spec`` on the decode-cache shapes —

* the KV-head axis divides the shard count -> **head routing**: every page
  splits into per-shard channel slices (comm-free decode ownership; each
  shard compresses, stores, fetches and re-activates its own heads' slice
  of every page);
* otherwise, if the sequence axis divides -> **sequence routing**
  (context-parallel decode): whole pages are owned block-cyclically by
  ``page_idx % shards``;
* neither -> the config is rejected, exactly like the real mesh rules
  falling back to replication (which would make "sharded" a lie).

Each shard models its own memory controller and its own lane engine
(Table IV silicon per shard — the aggregate report sums silicon and takes
the worst shard's latency), and every queued job is cancellation-scoped
``(shard, rid)`` so retiring a request's work on shard 0 can never cancel
a same-rid job queued on shard 1.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax

from repro.core.controller import MemoryController
from repro.runtime.sharding import abstract_mesh, cache_pspecs
from repro.serving.backends.base import KVBackend, MemTier
from repro.serving.kv_cache import PageKey


class ShardedBackend(KVBackend):
    name = "sharded"

    # ------------------------------------------------------------ validation
    @classmethod
    def check_model(cls, mcfg, cfg) -> None:
        if mcfg.kv_lora_rank:
            raise NotImplementedError(
                "backend='sharded' routes pages by KV head or by sequence "
                "block of a (L, B, S, Hkv, hd) cache; a latent (MLA) cache "
                "has no head axis to split — use backend='paged'"
            )
        if mcfg.decode_staging > 0:
            raise ValueError(
                f"decode_staging={mcfg.decode_staging} with "
                f"backend='sharded' is not supported: the staging ring is "
                f"not split along the page route, so per-shard byte "
                f"accounting would be wrong — use backend='paged' with "
                f"device_kv='dense' for staged decode"
            )
        super().check_model(mcfg, cfg)

    def __init__(self, model, cfg, controller: MemoryController | None = None,
                 stats=None, telemetry=None):
        self.shards = max(1, int(cfg.shards))
        super().__init__(model, cfg, controller=controller, stats=stats,
                         telemetry=telemetry)
        self._route, self._cols = self._plan_route(model, cfg)

    # ----------------------------------------------------------------- tiers
    def _build_tiers(self, controller) -> List[MemTier]:
        if controller is not None and self.shards > 1:
            raise ValueError(
                "ShardedBackend models one MemoryController per shard; an "
                "externally supplied controller only makes sense with "
                "shards=1 (use backend='paged' to capture a single trace)"
            )
        budget = self.cfg.max_stored_bytes
        per = None if budget is None else max(1, budget // self.shards)
        return [
            MemTier(self.cfg, controller if s == 0 else None, per, index=s,
                    telemetry=self.telemetry)
            for s in range(self.shards)
        ]

    def _plan_route(self, model, cfg):
        """Consult the runtime's cache-sharding rules on an abstract mesh of
        ``shards`` model-parallel workers and translate the resulting
        PartitionSpec into a page route."""
        mesh = abstract_mesh((1, self.shards), ("data", "model"))
        shapes = jax.eval_shape(
            lambda: model.init_cache(cfg.max_batch, cfg.max_ctx)
        )
        kspec = tuple(cache_pspecs(model.cfg, shapes, mesh)["k"])
        kshape = shapes["k"].shape  # (L, B, S, Hkv, hd)
        head_dim, seq_dim = len(kshape) - 2, len(kshape) - 3
        if len(kspec) > head_dim and kspec[head_dim] == "model":
            hkv, hd = kshape[head_dim], kshape[-1]
            per_shard = (hkv // self.shards) * hd
            cols = [slice(s * per_shard, (s + 1) * per_shard)
                    for s in range(self.shards)]
            return "head", cols
        if len(kspec) > seq_dim and kspec[seq_dim] == "model":
            return "seq", None
        raise ValueError(
            f"shards={self.shards} divides neither n_kv_heads "
            f"({kshape[head_dim]}) nor max_ctx ({kshape[seq_dim]}) — the "
            f"mesh rules would replicate the cache, so there is nothing to "
            f"shard"
        )

    # --------------------------------------------------------------- routing
    def _page_targets(self, key: PageKey) -> List[Tuple[MemTier, Optional[slice]]]:
        if self._route == "head":
            return [(tier, self._cols[tier.index]) for tier in self.tiers]
        return [(self.tiers[key.page_idx % self.shards], None)]

    def _seq_key(self, tier: MemTier, rid: int):
        # shard-scoped cancellation: retire-time cancel_seq((s, rid)) on one
        # shard's queue can never match another shard's (s', rid) jobs
        return (tier.index, rid)

    # ------------------------------------------------------------- reporting
    def report(self) -> dict:
        s = super().report()
        s["shard_route"] = self._route
        shards = []
        for tier in self.tiers:
            er = tier.engine.report()
            fp = tier.store.footprint()
            shards.append({
                "shard": tier.index,
                "kv_logical_bytes": tier.controller.stats.kind_bytes("kv_write")[0],
                "kv_stored_bytes": tier.controller.stats.kind_bytes("kv_write")[1],
                "kv_fetch_physical": tier.controller.stats.kind_bytes("kv_read")[1],
                "kv_evictions": fp["evictions"],
                "shared_stored_bytes": fp["shared_stored_bytes"],
                "engine_utilization": er["utilization"],
                "engine_modeled_latency_ns": er["modeled_latency_ns"],
            })
        s["shards"] = shards
        return s
