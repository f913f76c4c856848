"""``KVBackend``: the protocol between the continuous-batching scheduler
and the memory tier (ISSUE 4 tentpole).

The scheduler used to reach directly into ONE ``CompressedKVStore`` and one
dense device cache dict — page writes, decode-fetch planning, eviction
re-activation, ladder-plane assignment, retirement cleanup and savings
reporting were all inline scheduler code, which hard-wired a single-device
single-tier deployment.  ``KVBackend`` extracts that whole surface behind a
protocol so the backing tier is a *policy*:

* ``PagedBackend``  — today's single-device compressed paged tier
  (bit-exact with the pre-refactor scheduler).
* ``ShardedBackend`` — per-shard slot map + compressed tier + memctl lane
  budget; pages are routed by KV-head ownership (or block-cyclic over the
  sequence axis) using the ``runtime/sharding`` mesh rules.
* ``RingBackend``   — per-slot sliding-window ring caches (Mixtral-family
  configs), with pages retired as they slide out of the window.

Protocol surface (what the scheduler calls — everything else is private):

========================  ===================================================
``ensure_cache()``        build/return the device decode cache (opaque to
                          the scheduler beyond passing it to jitted fns)
``cache`` (property)      get/set the device cache between jitted calls
``sync_lens(lens)``       publish the per-slot true lengths to the cache
``adopt_prefill(...)``    legacy padded admission: copy a 1-seq prefill
                          cache into a slot's rows
``max_prefill_bucket()``  largest chunk the backend's cache layout accepts
``bind_slot/retire``      slot lifecycle (retire cancels queued engine jobs
                          — shard-scoped — and drops the request's pages)
``on_prefill_progress``   store newly completed prompt KV (pages + ragged
                          exact-length tail), assign ladder planes when done
``on_decode_tokens``      one decode step's tokens, every slot: copy the
                          pages they filled to the host in one transfer,
                          store them, re-rank the ladder, queue the step's
                          decode-critical fetches (``on_decode_token`` is
                          the one-slot form)
``tick/backlog``          service each tier's engine window / queued work
``admit_pressure_ns()``   engine-limited latency signal for admission
                          backpressure
``note_peaks/report``     footprint peaks + aggregated savings/engine stats
========================  ===================================================

A backend owns one or more :class:`MemTier` (controller + compressed store
+ finite-throughput engine); all byte accounting flows through tiers, never
through the scheduler.
"""

from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression import default_codec
from repro.core.compressed_store import StoreConfig
from repro.core.controller import MemoryController
from repro.core.quantization import (
    assign_page_precision,
    page_minmax,
    quest_scores,
)
from repro.memctl import CompressionEngineRuntime, Job, JobClass
from repro.memctl.runtime import aggregate_engine_reports
from repro.models.transformer import kv_row_shapes
from repro.serving.kv_cache import (
    PAGE_TOKENS,
    CompressedKVStore,
    PageEvictedError,
    PageKey,
    PrefixEntry,
    PrefixIndex,
    is_prefix_seq,
    iter_page_chunks,
    page_chain_hashes,
    prefix_seq_id,
)
from repro.telemetry import steps
from repro.telemetry.collector import NULL_COLLECTOR

#: stat keys the backend mutates on the (shared) scheduler stats dict
BACKEND_STATS = (
    "kv_fetch_misses", "kv_fetch_deferrals", "kv_reactivations",
    "engine_jobs_cancelled", "kv_peak_stored_bytes", "kv_peak_logical_bytes",
    "device_bytes_read",
    "prefix_requests_matched", "prefix_tokens_matched",
    "prefix_pages_matched", "prefix_bytes_deduped",
)

#: per-row counters a decode step returns in its cache (see
#: ``models.transformer.init_decode_cache``)
DECODE_COUNTERS = ("moe_held_picks",)

#: a page's two cache streams, in the order fetch accounting visits them
STREAMS = ("k", "v")


@dataclasses.dataclass
class SlotState:
    """Backend-side per-slot bookkeeping (the scheduler no longer tracks
    any memory state)."""

    rid: int
    #: device tokens [0, stored_tokens) have been submitted to the store
    #: (exact-length tail pages included); fetch accounting and
    #: re-activation range over exactly these pages
    stored_tokens: int = 0
    #: ladder plane count per page index (consulted by queued write jobs at
    #: service time, so evicted pages keep their precision)
    page_planes: Dict[int, int] = dataclasses.field(default_factory=dict)
    #: first page not yet fully slid out of the attention window (ring
    #: tiers; always 0 for full-attention backends)
    live_from_page: int = 0
    #: last plane-map row pushed to the device cache (bit-plane layouts) —
    #: lets per-token re-syncs skip the device write when nothing changed
    device_row: Optional[np.ndarray] = None
    #: staged decode (``decode_staging > 0``): first token living in the
    #: slot's staging ring — main cache holds [0, stage_base), the ring
    #: holds [stage_base, len); mirrors the device 'sbase' row
    stage_base: int = 0
    # --- shared-prefix state (EngineConfig.prefix_sharing; empty = cold) ---
    #: chain hash per FULL prompt page — page p < prompt_pages is keyed
    #: ``px:<hash[p]>`` instead of the rid (CONTENT addressing), so equal
    #: prefixes share store pages; tail/decode pages stay rid-keyed
    prefix_hashes: List[str] = dataclasses.field(default_factory=list)
    #: raw prompt ids the hashes digest (registration stores them so a
    #: match can verify token equality, not just hash equality)
    prefix_tokens: Optional[np.ndarray] = None
    #: number of FULL prompt pages (== len(prefix_hashes))
    prompt_pages: int = 0
    #: pages [bound_from_page, shared_pages) were adopted via a prefix
    #: match and hold a store refcount each; released at retire, or as a
    #: ring window slides past them (advancing bound_from_page)
    shared_pages: int = 0
    bound_from_page: int = 0
    # --- fetch accounting's index of the live pages' keys, one entry per
    # (layer, stream index, page, tier index) over ``fetch_range`` ---
    #: keys probed one by one each step: resident, evicted or dead
    fetch_open: set = dataclasses.field(default_factory=set)
    #: keys known to wait on a queued write or re-activation and not
    #: resident: counted in bulk as deferrals until a put or a
    #: cancellation reopens them
    fetch_deferred: set = dataclasses.field(default_factory=set)
    #: [first, last) page the two sets cover
    fetch_range: Tuple[int, int] = (0, 0)


class MemTier:
    """One shard's memory stack: MemoryController + CompressedKVStore +
    finite-throughput CompressionEngineRuntime, wired exactly the way the
    pre-refactor scheduler wired its single tier (codec resolution
    included), so a one-tier backend is bit-exact with it."""

    def __init__(self, cfg, controller: MemoryController | None = None,
                 max_stored_bytes: int | None = None, index: int = 0,
                 telemetry=None):
        self.index = index
        codec = cfg.codec or default_codec()
        store_cfg = StoreConfig(codec=codec)
        # accounting-only by default: one event per resident page per decode
        # step would grow without bound on long runs; pass a controller with
        # retain_events=True to capture a replayable DRAM trace
        if controller is None:
            controller = MemoryController(store_cfg, retain_events=False)
        elif cfg.codec is None:
            # no explicit codec: follow the caller's controller so the pages
            # it compresses match the store config and modeled lane silicon
            codec = controller.config.codec
            store_cfg = controller.config
        else:
            # explicit codec wins end to end — a passed controller must not
            # silently compress with a different codec than the one the
            # report's store/silicon numbers are quoted for
            controller.config = store_cfg
        mc = cfg.engine
        if mc.engine is None:  # lane silicon follows the serving codec
            # Table IV only characterises lz4/zstd lanes; any other
            # registered codec falls back to the cheaper lz4 silicon
            mc = dataclasses.replace(
                mc, engine=codec if codec in ("lz4", "zstd") else "lz4"
            )
        self.engine = CompressionEngineRuntime(mc, telemetry=telemetry,
                                               tier=index)
        controller.attach_engine_clock(self.engine.clock)
        self.controller = controller
        self.store = CompressedKVStore(
            config=store_cfg, max_stored_bytes=max_stored_bytes,
            controller=controller, engine=self.engine,
        )


def make_fetch_job(store: CompressedKVStore, stats: Dict[str, float],
                   key: PageKey, seq_key, device_kv: str = "dense",
                   telemetry=None, rid=None, keep_fn=None) -> Job:
    """Decode-critical fetch with SERVICE-TIME sizing.

    The plane count is resolved exactly once — by ``size_fn`` when the
    engine starts servicing the job — and the completion ``fn`` charges the
    controller's kv_read at that same resolved count, so the lane-pool
    bytes and the accounting can never disagree across a ladder
    re-assignment (or an eviction) that lands between submit and service.

    The job also accumulates ``device_bytes_read`` — the bytes the DEVICE
    cache moves for this page's decode read.  A bit-plane device cache
    reads exactly the planes the ladder prescribes (the engine-job bytes);
    a dense cache reads the full-precision page no matter what the ladder
    charged — the accounting-vs-device gap the bit-plane layout closes.

    With a live ``telemetry`` collector, every serviced fetch is attributed
    to its request in BOTH byte currencies: the device bytes above (sums to
    the backend's ``device_bytes_read``) and the controller's plane-scaled
    kv_read delta (sums to the controller totals) — the per-request
    breakdown of the two bandwidth claims.  ``rid`` names that request
    explicitly; it defaults to ``key.seq_id``, which shared-prefix
    (content-addressed) keys no longer carry.

    ``keep_fn`` resolves the plane count from the FETCHING slot's ladder
    assignment at service time (shared pages: every holder ranks the page
    against its own query, so the store's last-writer hint is the wrong
    holder's); None keeps the store's ladder hint as before.
    """
    plan: dict = {}
    telemetry = telemetry if telemetry is not None else NULL_COLLECTOR
    rid = key.seq_id if rid is None else rid

    def size() -> int:
        if not store.contains(key):
            store.note_miss()  # keep the store's counters honest too
            return 0  # evicted since submit; fn counts the scheduler miss
        keep = "ladder" if keep_fn is None else keep_fn()
        nbytes, keep = store.fetch_plan(key, keep)
        plan["keep"] = keep
        plan["device"] = (nbytes if device_kv == "bitplane"
                          else store.page_logical_bytes(key))
        return nbytes

    def fn() -> None:
        if "keep" not in plan:
            stats["kv_fetch_misses"] += 1
            return
        live = telemetry.enabled
        before = (store.controller.stats.kind_device_bytes("kv_read")
                  if live else 0)
        try:
            store.account_fetch(key, keep_planes=plan["keep"])
        except PageEvictedError:
            stats["kv_fetch_misses"] += 1
            return
        # direct callers (tests) may pass a bare stats dict; backends
        # pre-seed every BACKEND_STATS key
        stats["device_bytes_read"] = (
            stats.get("device_bytes_read", 0) + plan["device"]
        )
        if live:
            delta = (store.controller.stats.kind_device_bytes("kv_read")
                     - before)
            telemetry.on_fetch(rid, plan["device"], delta)

    return Job(JobClass.DECODE_FETCH, 0, fn=fn, key=key.astuple(),
               seq_id=seq_key, size_fn=size)


@functools.partial(jax.jit, static_argnames=("layers", "kv_shapes"))
def fill_pages(k, v, slots, rows, *, layers: int, kv_shapes):
    """The decode pages a step filled, every slot's at once: K and V of
    the first ``layers`` layers at device ``rows`` (n, 16) of ``slots``
    (n,), as one (2, n, layers, 16, channels) array for one device->host
    transfer (a latent cache's two streams are gathered as one width: the
    narrower is zero-padded, and :meth:`KVBackend._copy_fill_pages` cuts
    it back).  The layout of ``k``/``v`` picks the body: dense caches
    (L, B, S, *row) are gathered; bit-plane caches (L, bits, B, S/16,
    1, W) gather each row's page (``rows[:, 0] // 16``: a filled page is
    page-aligned) and unpack it at full precision to the stream's row
    shape in ``kv_shapes``, a bf16 bitcast, so the result is bitwise what
    ``slot_kv_host`` copies.  One program per (layout, layers, n, cache
    shape)."""
    n = slots.shape[0]

    def gather(c, kv_shape):
        if c.ndim == 6:
            from repro.kernels.paged_attention.ref import unpack_kv_ref

            bits = c.shape[1]
            pl = c[:layers, :, slots, rows[:, 0] // PAGE_TOKENS]
            # (ls, bits, n, 1, W) -> (bits, ls, n, 1 page, 1, W)
            pl = jnp.moveaxis(pl, 1, 0)[..., None, :, :]
            dense = unpack_kv_ref(pl, bits, kv_shape, bits)
        else:
            dense = c[:layers][:, slots[:, None], rows]
        return jnp.moveaxis(dense.reshape(layers, n, PAGE_TOKENS, -1), 1, 0)

    out = [gather(c, shape) for c, shape in zip((k, v), kv_shapes)]
    width = max(o.shape[-1] for o in out)
    if any(o.shape[-1] != width for o in out):
        out = [jnp.pad(o, ((0, 0),) * 3 + ((0, width - o.shape[-1]),))
               for o in out]
    return jnp.stack(out)


class KVBackend(abc.ABC):
    """Base implementation of the protocol: single-tier, full-attention,
    paged.  Subclasses override the routing/layout hooks (``_page_targets``,
    ``_device_rows``, ``_build_tiers``, ``check_model`` ...), never the
    scheduler-facing surface."""

    name = "?"

    def __init__(self, model, cfg, controller: MemoryController | None = None,
                 stats: Dict[str, float] | None = None, telemetry=None):
        self.model = model
        self.mcfg = model.cfg
        self.cfg = cfg
        #: one token's row in each cache stream, as the model lays it out
        self._rows = kv_row_shapes(model.cfg)
        self.device_kv = cfg.device_kv
        self.check_model(model.cfg, cfg)
        self.stats = stats if stats is not None else {}
        for key in BACKEND_STATS:
            self.stats.setdefault(key, 0)
        self.telemetry = telemetry if telemetry is not None else NULL_COLLECTOR
        self.tiers: List[MemTier] = self._build_tiers(controller)
        self._cache = None
        self._slots: Dict[int, SlotState] = {}
        #: ``fill_pages`` has run once for this cache (compiled or loaded)
        self._fill_pages_ready = False
        # weight streaming (ISSUE 9): one streamer per tier, built by
        # attach_weights; empty under weight_stream='resident'
        if cfg.weight_stream not in ("resident", "compressed"):
            raise ValueError(
                f"weight_stream must be 'resident' or 'compressed', got "
                f"{cfg.weight_stream!r}"
            )
        self.streamers: list = []
        self._weight_pass_pending = False
        # shared-prefix index (ISSUE 10): None = sharing off, every page
        # rid-keyed, bit- and accounting-identical to the pre-prefix code
        self.prefix: Optional[PrefixIndex] = (
            PrefixIndex(getattr(cfg, "prefix_index_entries", 128))
            if getattr(cfg, "prefix_sharing", False) else None
        )

    # ------------------------------------------------------------ validation
    @classmethod
    def check_model(cls, mcfg, cfg) -> None:
        """Raise when this backend cannot serve the model/config."""
        if mcfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"continuous batching supports dense-cache families, got "
                f"{mcfg.family!r} (use family-specific engines for "
                f"ssm/hybrid/encdec)"
            )
        if 0 < mcfg.attn_window < cfg.max_ctx:
            raise NotImplementedError(
                "sliding-window ring caches need backend='ring'"
            )
        if mcfg.kv_lora_rank and (mcfg.attn_window or mcfg.decode_staging):
            raise NotImplementedError(
                "a latent (MLA) cache has no sliding-window or staged form"
            )
        if mcfg.decode_staging > 0 and cfg.device_kv != "dense":
            raise ValueError(
                f"decode_staging={mcfg.decode_staging} with "
                f"device_kv={cfg.device_kv!r} is not supported: the staging "
                f"ring appends dense bf16 rows, so staged decode needs "
                f"device_kv='dense'"
            )
        cls.check_device_kv(mcfg, cfg)

    @classmethod
    def check_device_kv(cls, mcfg, cfg) -> None:
        if cfg.device_kv not in ("dense", "bitplane"):
            raise ValueError(
                f"device_kv must be 'dense' or 'bitplane', got "
                f"{cfg.device_kv!r}"
            )
        if cfg.device_kv == "bitplane" and mcfg.kv_lora_rank:
            raise ValueError(
                "device_kv='bitplane' cannot hold a latent (MLA) cache: the "
                "bit-plane row and the fused kernel are laid out per KV "
                "head, and an MLA token has one shared c_kv and k_pe row; "
                "serve it with device_kv='dense'"
            )
        if cfg.device_kv == "bitplane" and cfg.max_ctx % PAGE_TOKENS:
            raise ValueError(
                f"bit-plane device caches need max_ctx to be a whole "
                f"number of {PAGE_TOKENS}-token pages, got {cfg.max_ctx}"
            )

    # ----------------------------------------------------------------- tiers
    def _build_tiers(self, controller) -> List[MemTier]:
        return [MemTier(self.cfg, controller, self.cfg.max_stored_bytes,
                        telemetry=self.telemetry)]

    def _seq_key(self, tier: MemTier, rid: int):
        """Cancellation scope for jobs of request ``rid`` on ``tier``
        (sharded backends scope per shard — see memctl.queue.cancel_seq)."""
        return rid

    def _page_targets(self, key: PageKey) -> List[Tuple[MemTier, Optional[slice]]]:
        """Which tiers own (a channel slice of) this page: [(tier, cols)].
        ``cols=None`` means the full page."""
        return [(self.tiers[0], None)]

    # -------------------------------------------------------- weight streaming
    def attach_weights(self, params) -> None:
        """Ingest the model's per-layer weight handles into each tier's
        block-compressed weight store and build the streamers
        (``weight_stream='compressed'``; no-op under 'resident').  Sharded
        backends ingest a contiguous 1/n tensor-parallel slice of every
        tensor per tier, so total weight bytes across tiers are conserved
        and every shard streams its own share through its own lanes."""
        if self.cfg.weight_stream != "compressed":
            return
        from repro.models.transformer import split_layer_params
        from repro.weights import CompressedWeightStore, WeightStreamer

        handles = split_layer_params(params)
        n = len(self.tiers)
        self.streamers = []
        for tier in self.tiers:
            store = CompressedWeightStore.from_handles(
                handles, tier.controller, part=(tier.index, n)
            )
            self.streamers.append(WeightStreamer(
                store, tier.engine, telemetry=self.telemetry,
                prefetch_depth=self.cfg.weight_prefetch_depth,
                tier=tier.index,
            ))

    def _note_compute(self) -> None:
        """A prefill chunk or decode token ran this step: the step's engine
        window must carry one weight pass (all compute in a step shares the
        streamed layer buffers — weight bytes are charged exactly once per
        layer per step)."""
        self._weight_pass_pending = True

    # ---------------------------------------------------------- device cache
    @property
    def cache(self):
        """The device decode cache — opaque to the scheduler (passed whole
        into the jitted prefill/decode functions and assigned back)."""
        return self._cache

    @cache.setter
    def cache(self, value):
        self._cache = value

    def ensure_cache(self):
        if self._cache is None:
            self._cache = self._build_cache()
        return self._cache

    def _build_cache(self):
        cache = self.model.init_cache(self.cfg.max_batch, self.cfg.max_ctx)
        assert "k" in cache and "v" in cache and "pos" not in cache
        cache = self._apply_device_layout(cache)
        cache["len"] = jnp.zeros(self.cfg.max_batch, jnp.int32)
        if "sk" in cache:  # staged decode: per-row staging bases (ISSUE 6)
            cache["sbase"] = jnp.zeros(self.cfg.max_batch, jnp.int32)
        return cache

    def _apply_device_layout(self, cache):
        """Convert the model's dense cache to the configured device layout
        (``device_kv='bitplane'``: packed int32 planes + a per-page plane
        map the ladder assignment is pushed into)."""
        if self.device_kv != "bitplane":
            return cache
        from repro.models.transformer import bitplane_cache_from_dense

        return bitplane_cache_from_dense(cache)

    def device_keeps(self) -> Optional[tuple]:
        """Static plane-count set the device decode kernel may be asked to
        read (one Pallas rung per member) — the ladder's rung planes plus
        full precision (unassigned pages: growing tails, pre-ladder pages).
        ``None`` on the dense layout (no kernel, no static set)."""
        if self.device_kv != "bitplane":
            return None
        bits = self.tiers[0].store.spec.bits
        keeps = {bits}
        if self.cfg.ladder is not None:
            keeps |= {planes for _, planes in self.cfg.ladder.rungs}
        return tuple(sorted(keeps))

    def decode_counters(self) -> dict:
        """Counters the last decode step returned with its cache, as device
        arrays with one value per batch row: ``moe_held_picks`` where the
        model has expert layers at a chip's share, else nothing.  The
        scheduler reads them in the same wait as the step's tokens."""
        return {name: self._cache[name] for name in DECODE_COUNTERS
                if name in self._cache}

    def sync_lens(self, lens, stage_anchor=None) -> None:
        lens = jnp.asarray(lens)
        self._cache["len"] = lens
        if "sbase" in self._cache:
            # authoritative per-row staging base for this decode step:
            # windows of ws tokens anchored at each row's prefill end
            # (``stage_anchor``; -1 = unanchored — idle / mid-prefill rows
            # stage nothing, so their base tracks the length itself)
            ws = self._cache["sk"].shape[2]
            if stage_anchor is None:
                anchor = lens
            else:
                a = jnp.asarray(stage_anchor)
                anchor = jnp.where(a >= 0, a, lens)
            self._cache["sbase"] = (
                anchor + ws * ((lens - anchor) // ws)
            ).astype(jnp.int32)

    def adopt_prefill(self, slot_id: int, pcache, s: int) -> None:
        """Legacy padded admission: copy a single-sequence prefill cache
        into this slot's rows [0, s) (bit-plane layouts pack on adoption)."""
        cache = self.ensure_cache()
        if self.device_kv == "bitplane":
            for name in ("k", "v"):
                self._write_device_rows(name + "_planes", slot_id,
                                        slice(0, s), pcache[name][:, 0, :s])
            return
        cache["k"] = cache["k"].at[:, slot_id, :s].set(pcache["k"][:, 0])
        cache["v"] = cache["v"].at[:, slot_id, :s].set(pcache["v"][:, 0])

    def max_prefill_bucket(self) -> int:
        """Largest prefill chunk the cache layout accepts (ring caches cap
        at the window so a chunk's slots never collide)."""
        return self.cfg.max_ctx

    def _device_rows(self, t0: int, t1: int):
        """Cache sequence-axis index holding absolute tokens [t0, t1)."""
        return slice(t0, t1)

    def _kv_shape(self, stream: str) -> Tuple[int, ...]:
        """One token's row of ``stream`` ("k" or "v") in one layer."""
        return self._rows[stream]

    def _write_device_rows(self, name: str, slot_id: int, rows,
                           dense) -> None:
        """Write dense KV (L, T, Hkv, hd) into this slot's device ``rows``
        of the bit-plane cache ``name`` (L, bits, B, S/16, 1, W)."""
        from repro.kernels.paged_attention.ops import write_kv_rows

        col = jnp.moveaxis(self._cache[name][:, :, slot_id], 1, 0)
        col = write_kv_rows(col, rows, dense)
        self._cache[name] = self._cache[name].at[:, :, slot_id].set(
            jnp.moveaxis(col, 0, 1)
        )

    def stored_layers(self) -> int:
        n_layers = self.mcfg.n_layers
        cap = self.cfg.store_layers
        return n_layers if cap is None else min(cap, n_layers)

    def slot_kv_host(self, slot_id: int, t0: int, t1: int,
                     layers: Optional[int] = None):
        """Device->host copy of this slot's KV rows [t0, t1) for the stored
        layers, flattened to (L_stored, tokens, channels) bf16.  The
        bit-plane layout unpacks at full precision first — packing is a
        bf16 bitcast, so the copy is bit-identical to the dense layout's.
        ``layers`` overrides the layer count (prefix-index snapshots copy
        ALL layers: adoption rebuilds the whole device column, not just the
        compressed-store's capped subset).  The copied bytes go to the
        step's ``d2h_bytes`` counter and each transfer to ``d2h_copies``."""
        k, v = self._copy_kv_host(slot_id, t0, t1, layers)
        steps.count("d2h_bytes", k.nbytes + v.nbytes)
        return k, v

    def _copy_kv_host(self, slot_id: int, t0: int, t1: int,
                      layers: Optional[int]):
        import ml_dtypes

        ls = self.stored_layers() if layers is None else layers
        rows = self._device_rows(t0, t1)
        t = t1 - t0
        if self.device_kv == "bitplane":
            from repro.kernels.paged_attention.ops import read_kv_rows

            out = []
            for stream in ("k", "v"):
                # (ls, bits, S/16, 1, W) -> unpack layers as the batch axis
                pl = jnp.moveaxis(
                    self._cache[stream + "_planes"][:ls, :, slot_id], 1, 0)
                dense = read_kv_rows(pl, rows, self._kv_shape(stream))
                out.append(np.asarray(dense.reshape(ls, t, -1)))
            steps.count("d2h_copies", 2)
            return tuple(out)
        k = np.asarray(self._cache["k"][:ls, slot_id, rows], np.float32)
        v = np.asarray(self._cache["v"][:ls, slot_id, rows], np.float32)
        steps.count("d2h_copies", 2)
        st = self._slots.get(slot_id)
        if "sk" in self._cache and st is not None:
            # staged decode: tokens >= stage_base still live in the staging
            # ring, not the main cache — read them from their ring slots
            sb = st.stage_base
            ws = self._cache["sk"].shape[2]
            for tok in range(max(t0, sb), min(t1, sb + ws)):
                k[:, tok - t0] = np.asarray(
                    self._cache["sk"][:ls, slot_id, tok - sb], np.float32)
                v[:, tok - t0] = np.asarray(
                    self._cache["sv"][:ls, slot_id, tok - sb], np.float32)
                steps.count("d2h_copies", 2)
        return (k.reshape(ls, t, -1).astype(ml_dtypes.bfloat16),
                v.reshape(ls, t, -1).astype(ml_dtypes.bfloat16))

    # --------------------------------------------------------- slot lifecycle
    def bind_slot(self, slot_id: int, rid: int) -> None:
        self._slots[slot_id] = SlotState(rid=rid)
        self._reset_device_planes(slot_id)
        if not self._fill_pages_ready and self._batched_fills():
            # compile (or load) the page-fill copy before any decode page
            # fills, so no serving step after admission compiles it
            jax.block_until_ready(self._run_fill_pages([]))
            self._fill_pages_ready = True

    def _reset_device_planes(self, slot_id: int) -> None:
        """Bit-plane layout: a reused slot must not inherit the previous
        occupant's ladder — reset its device plane map to full precision."""
        if self.device_kv == "bitplane" and self._cache is not None:
            bits = self.tiers[0].store.spec.bits
            self._cache["planes"] = self._cache["planes"].at[slot_id].set(bits)

    def retire(self, slot_id: int, rid: int) -> int:
        """Cancel the request's queued engine jobs (shard-scoped — a cancel
        on one tier can never reach a same-rid job on another) and drop its
        pages.  Eviction write-backs carry ``seq_id=None`` and survive: the
        stream-out is committed work the drain loop services.  Returns the
        number of cancelled jobs (also accumulated on the stats dict)."""
        st = self._slots.get(slot_id)
        if st is not None:
            self._release_prefix(st)
        cancelled = 0
        for tier in self.tiers:
            cancelled += tier.engine.cancel_seq(self._seq_key(tier, rid))
            # shared (px:) pages are untouched: drop_sequence matches the
            # integer rid only — the prefix cache outlives its writers
            tier.store.drop_sequence(rid)
        self.stats["engine_jobs_cancelled"] += cancelled
        self._slots.pop(slot_id, None)
        if st is not None and st.prefix_hashes:
            # the cancelled jobs may have been the last queued write of a
            # shared page another slot counts as deferred: probe those again
            for other in self._slots.values():
                self._reopen(other, [pair for pair in other.fetch_deferred
                                     if pair[2] < other.prompt_pages])
        self._reset_device_planes(slot_id)
        return cancelled

    # --------------------------------------------------------- prefix sharing
    def _slot_key(self, st: SlotState, layer: int, page_idx: int,
                  stream: str) -> PageKey:
        """Store key for one of this slot's pages: content-addressed while
        the page is a hashed FULL prompt page (sharing on), rid-keyed
        otherwise (sharing off, ragged prompt tails, decode appends)."""
        if st.prefix_hashes and page_idx < st.prompt_pages:
            return PageKey(prefix_seq_id(st.prefix_hashes[page_idx]),
                           layer, page_idx, stream)
        return PageKey(st.rid, layer, page_idx, stream)

    def _prefix_adopt_lo(self, m: int) -> int:
        """First device row a slot adopting an ``m``-token prefix must
        rebuild (ring windows only reach back ``window`` tokens)."""
        return 0

    def _prefix_register_ok(self, st: SlotState, end: int) -> bool:
        """Whether a finished prefill can be indexed for sharing (ring:
        only while the WHOLE prompt is still inside the window — a prefix
        partially slid out has no device rows left to snapshot)."""
        return True

    def match_prefix(self, slot_id: int, prompt: np.ndarray) -> int:
        """Longest indexed page-aligned shared prefix this slot can adopt;
        binds the matched pages by refcount, copies the donor's device rows
        into the slot, and returns the matched token count (0 = cold).
        Called once per slot at its first prefill tick; also the point
        where the slot's page hashes are computed, so even a cold slot
        writes its full prompt pages content-addressed (becoming a donor).

        The match is capped one page short of the prompt (at least one
        token always prefills: the final chunk's logits drive sampling
        draw 0, so a matched request keeps the exact fold_in(base, rid)
        stream a cold prefill would have used)."""
        if self.prefix is None or not self.cfg.store_kv_compressed:
            return 0
        st = self._slots[slot_id]
        arr = np.ascontiguousarray(np.asarray(prompt, np.int32))
        hashes = page_chain_hashes(arr)
        st.prefix_hashes = hashes
        st.prefix_tokens = arr
        st.prompt_pages = len(hashes)
        if not hashes:
            return 0
        cap = (len(arr) - 1) // PAGE_TOKENS
        m_pages, entry = self.prefix.match(arr, hashes, max_pages=cap)
        while m_pages > 0:
            m = m_pages * PAGE_TOKENS
            lo = self._prefix_adopt_lo(m)
            if lo < entry.r0_token:
                return 0  # donor snapshot no longer covers the window start
            bind_from = -(-lo // PAGE_TOKENS)
            missing = self._first_missing_prefix_page(hashes, bind_from,
                                                      m_pages)
            if missing is None:
                break
            if missing <= bind_from:
                return 0  # nothing resident to bind
            m_pages = missing  # truncate to the resident prefix and retry
        else:
            return 0
        self._adopt_prefix_rows(slot_id, entry, lo, m)
        st.stored_tokens = m
        st.live_from_page = bind_from
        st.bound_from_page = bind_from
        st.shared_pages = m_pages
        deduped = 0
        for p in range(bind_from, m_pages):
            for li in range(self.stored_layers()):
                for stream in ("k", "v"):
                    key = self._slot_key(st, li, p, stream)
                    for tier, _cols in self._page_targets(key):
                        tier.store.retain_page(key)
                        deduped += tier.store.page_stored_bytes(key)
        self.stats["prefix_requests_matched"] += 1
        self.stats["prefix_tokens_matched"] += m
        self.stats["prefix_pages_matched"] += m_pages - bind_from
        self.stats["prefix_bytes_deduped"] += deduped
        if self.telemetry.enabled:
            self.telemetry.on_prefill_chunk(st.rid, 0, m, False)
        return m

    def _first_missing_prefix_page(self, hashes: List[str], p0: int,
                                   p1: int) -> Optional[int]:
        """First page in [p0, p1) not resident on EVERY owning tier (a
        queued-but-unserviced donor write counts as missing — there is no
        compressed copy to bind yet), or None when all are resident."""
        for p in range(p0, p1):
            for li in range(self.stored_layers()):
                for stream in ("k", "v"):
                    key = PageKey(prefix_seq_id(hashes[p]), li, p, stream)
                    for tier, _cols in self._page_targets(key):
                        if not tier.store.contains(key):
                            return p
        return None

    def _adopt_prefix_rows(self, slot_id: int, entry: PrefixEntry,
                           lo: int, m: int) -> None:
        """Copy the donor snapshot's device rows [lo, m) into this slot —
        a device-internal copy (like legacy ``adopt_prefill``), charged to
        neither the lane engine nor the controller: the whole point is
        that no compress/prefill work runs for adopted rows.  Snapshots
        are bf16 and bit-plane packing is a bf16 bitcast, so the adopted
        rows are bit-identical to a cold prefill's."""
        cache = self.ensure_cache()
        t = m - lo
        o = lo - entry.r0_token
        n_layers = entry.k.shape[0]
        rows = self._device_rows(lo, m)
        if self.device_kv == "bitplane":
            for name, arr in (("k", entry.k), ("v", entry.v)):
                dense = jnp.asarray(arr[:, o:o + t]).reshape(
                    n_layers, t, *self._kv_shape(name)
                )
                self._write_device_rows(name + "_planes", slot_id, rows,
                                        dense)
            return
        for name, arr in (("k", entry.k), ("v", entry.v)):
            dense = jnp.asarray(arr[:, o:o + t]).reshape(
                n_layers, t, *self._kv_shape(name)
            ).astype(cache[name].dtype)
            cache[name] = cache[name].at[:, slot_id, rows].set(dense)

    def _register_prefix(self, slot_id: int, end: int) -> None:
        """Index a finished prefill's full prompt pages for future sharing
        (skipped when every page hash is already covered — re-snapshotting
        an indexed prefix would only churn host memory)."""
        if self.prefix is None:
            return
        st = self._slots[slot_id]
        n_pages = st.prompt_pages
        if (n_pages == 0 or st.prefix_tokens is None
                or not self._prefix_register_ok(st, end)):
            return
        if all(self.prefix.has_page(h) for h in st.prefix_hashes):
            return
        t1 = n_pages * PAGE_TOKENS
        k, v = self.slot_kv_host(slot_id, 0, t1, layers=self.mcfg.n_layers)
        self.prefix.register(PrefixEntry(
            tokens=st.prefix_tokens[:t1].copy(), hashes=list(st.prefix_hashes),
            r0_token=0, k=np.asarray(k), v=np.asarray(v),
        ))

    def _release_prefix(self, st: SlotState) -> None:
        """Drop this slot's remaining shared-page bindings (retire, or a
        ring window sliding past them)."""
        for p in range(st.bound_from_page, st.shared_pages):
            for li in range(self.stored_layers()):
                for stream in ("k", "v"):
                    key = self._slot_key(st, li, p, stream)
                    for tier, _cols in self._page_targets(key):
                        tier.store.release_page(key)
        st.bound_from_page = st.shared_pages

    # ---------------------------------------------------------- page traffic
    def on_prefill_progress(self, slot_id: int, end: int, final: bool) -> None:
        """Prompt KV for tokens [0, end) is now on device: stream the newly
        completed pages to the tier (full pages as chunks land; on the
        final call also the ragged tail as an exact-length page), then
        assign ladder planes once the prompt is complete."""
        self._note_compute()
        if final and self.mcfg.decode_staging > 0:
            # prompt KV landed in the main cache; staging anchors here
            self._slots[slot_id].stage_base = end
        if not self.cfg.store_kv_compressed:
            return
        st = self._slots[slot_id]
        self._expire_dead_pages(st, end)
        lo = max(st.stored_tokens, self._first_storable_token(end))
        if lo > st.stored_tokens:
            # a ring skipped a dead prompt prefix entirely: those pages were
            # never stored, so fetch accounting must not range over them
            st.live_from_page = max(st.live_from_page, lo // PAGE_TOKENS)
        hi = end if final else (end // PAGE_TOKENS) * PAGE_TOKENS
        if hi > lo:
            self._write_span(slot_id, lo, hi)
        if hi > st.stored_tokens:
            st.stored_tokens = hi
        if final:
            self._assign_ladder_planes(slot_id, end)
            self._register_prefix(slot_id, end)

    def on_decode_token(self, slot_id: int, ln: int) -> None:
        """One slot's form of :meth:`on_decode_tokens`."""
        self.on_decode_tokens([(slot_id, ln)])

    def on_decode_tokens(self, tokens: Sequence[Tuple[int, int]]) -> None:
        """One decode step's tokens, ``(slot, ln)`` each: the token landed
        at position ln-1.  First the pages that just filled (ln a multiple
        of 16) go to the host together, one compiled gather and one
        transfer (:meth:`_copy_fill_pages`); then, slot by slot in the
        given order, each filled page is stored (and the ladder
        re-ranked) and the step's decode-critical fetch traffic queued.
        The device K/V does not change between the two stages, so the jobs
        and bytes are those of a per-slot copy in the same order."""
        self._note_compute()
        fills = ([(s, ln) for s, ln in tokens if ln % PAGE_TOKENS == 0]
                 if self._batched_fills() else [])
        pages = self._copy_fill_pages(fills) if fills else {}
        for slot_id, ln in tokens:
            self._commit_decode_token(slot_id, ln, pages.get(slot_id))

    def _batched_fills(self) -> bool:
        """Whether decode page fills take :func:`fill_pages`.  Staged decode
        (``'sk'`` in the cache) keeps the per-slot ``slot_kv_host`` copy:
        a filled page's newest rows may sit in the staging ring, read at
        the staging base the slot's own commit advances first."""
        return (self.cfg.store_kv_compressed and self._cache is not None
                and "sk" not in self._cache)

    def _copy_fill_pages(self, fills: Sequence[Tuple[int, int]]
                         ) -> Dict[int, tuple]:
        """Host copies (K, V), each (L_stored, 16, channels) bf16, of the
        page [ln-16, ln) of every ``(slot, ln)`` in ``fills``: bitwise what
        ``slot_kv_host(slot, ln-16, ln)`` returns, in one transfer."""
        import ml_dtypes

        dev = self._run_fill_pages(fills)
        out = np.asarray(dev)
        if out.dtype != ml_dtypes.bfloat16:
            # the rounding slot_kv_host's dense copy applies on the host
            out = out.astype(np.float32).astype(ml_dtypes.bfloat16)
        steps.count("d2h_bytes", dev.nbytes)
        steps.count("d2h_copies", 1)
        steps.count("fill_pages", len(fills))
        kw, vw = (int(np.prod(self._kv_shape(s))) for s in ("k", "v"))
        return {s: (out[0, j, ..., :kw], out[1, j, ..., :vw])
                for j, (s, _) in enumerate(fills)}

    def _run_fill_pages(self, fills: Sequence[Tuple[int, int]]):
        """Dispatch :func:`fill_pages` for ``fills`` padded to ``max_batch``
        entries (the padding reads slot 0's first page, and is dropped), so
        every step runs the one program of this cache's shapes."""
        slots = np.zeros(self.cfg.max_batch, np.int32)
        rows = np.tile(np.arange(PAGE_TOKENS, dtype=np.int32),
                       (self.cfg.max_batch, 1))
        for j, (s, ln) in enumerate(fills):
            r = self._device_rows(ln - PAGE_TOKENS, ln)
            slots[j] = s
            rows[j] = (np.arange(r.start, r.stop) if isinstance(r, slice)
                       else r)
        names = (("k_planes", "v_planes") if self.device_kv == "bitplane"
                 else ("k", "v"))
        return fill_pages(*(self._cache[n] for n in names), slots, rows,
                          layers=self.stored_layers(),
                          kv_shapes=(self._kv_shape("k"),
                                     self._kv_shape("v")))

    def _commit_decode_token(self, slot_id: int, ln: int,
                             page: Optional[tuple]) -> None:
        """Store the slot's page if it just filled (``page``: its host copy,
        None to copy it here) and re-rank the ladder, then queue the
        step's decode-critical fetch traffic for the slot."""
        st = self._slots[slot_id]
        ws = self.mcfg.decode_staging
        if ws > 0 and ln - st.stage_base >= ws:
            # the device step just folded a full staging window back into
            # the main cache — advance the host mirror in lockstep
            st.stage_base += ws
        if not self.cfg.store_kv_compressed:
            return
        self._expire_dead_pages(st, ln)
        if ln % PAGE_TOKENS == 0:  # a decode page just filled
            self._write_span(slot_id, ln - PAGE_TOKENS, ln, page)
            st.stored_tokens = ln
            self._assign_ladder_planes(slot_id, ln)
        self._account_step_fetch(slot_id, ln)

    def _first_storable_token(self, end: int) -> int:
        """First token whose page may still be written (ring backends skip
        pages already outside the window; full attention stores from 0)."""
        return 0

    def _expire_dead_pages(self, st: SlotState, ln: int) -> None:
        """Drop pages that can never be read again (ring only; no-op
        here)."""

    def _can_reactivate(self, st: SlotState, page_idx: int, ln: int) -> bool:
        """Whether the device working set still holds every row of this
        page (ring backends lose rows as the window slides)."""
        return True

    def _live_page_range(self, st: SlotState) -> Tuple[int, int]:
        """[first, last) stored page indices fetch accounting ranges over;
        derived from the stored-tokens watermark so a decode-growing tail
        page that was never stored is not phantom-fetched."""
        return st.live_from_page, -(-st.stored_tokens // PAGE_TOKENS)

    def _write_span(self, slot_id: int, t0: int, t1: int,
                    copied: Optional[tuple] = None) -> None:
        """Page-split device KV rows [t0, t1) (t0 page-aligned; a ragged t1
        becomes an exact-length tail page) and queue one write job per page
        per stream per stored layer on the owning tier(s).  ``copied`` is
        the rows' host (K, V) when the caller already made it."""
        st = self._slots[slot_id]
        k_np, v_np = (self.slot_kv_host(slot_id, t0, t1) if copied is None
                      else copied)
        first_page = t0 // PAGE_TOKENS
        for li in range(k_np.shape[0]):
            for stream, kv in (("k", k_np[li]), ("v", v_np[li])):
                for p, chunk, valid in iter_page_chunks(kv, first_page):
                    self._submit_page_write(
                        slot_id, self._slot_key(st, li, p, stream),
                        chunk, valid
                    )

    def _submit_page_write(self, slot_id: int, key: PageKey,
                           chunk: np.ndarray, valid: int) -> None:
        """Queue one page's compress-and-store on the owning tier(s).  The
        chunk is captured at submit time (the token range is append-only, so
        it cannot change); the store put — and its charged kv_write —
        happens when the engine services the job, at the ladder planes
        assigned by then.  ``valid`` < PAGE_TOKENS marks an exact-length
        tail page; the job is sized by its pad-free bytes."""
        st = self._slots[slot_id]
        for tier, cols in self._page_targets(key):
            part = chunk if cols is None else chunk[:, cols]

            def fn(tier=tier, key=key, part=part, st=st, valid=valid):
                tier.store.put_page(key, part,
                                    planes=st.page_planes.get(key.page_idx),
                                    valid_tokens=valid)
                self._note_put(tier, key, st)

            tier.engine.submit(Job(JobClass.KV_WRITE, part[:valid].nbytes,
                                   fn=fn, key=key.astuple(),
                                   seq_id=self._seq_key(tier, st.rid)))

    def _account_step_fetch(self, slot_id: int, ln: int) -> None:
        """Queue this decode step's KV traffic for one slot as
        decode-critical fetch jobs: every stored-resident page at its ladder
        planes, sized at SERVICE time (see :func:`make_fetch_job`).  Evicted
        pages queue a background re-activation instead (a re-compress write,
        charged once when the engine services it — possibly steps later
        under load); pages whose write or re-activation is still queued are
        skipped, since their ground truth is still the device working set
        and no compressed-tier copy exists to fetch.

        The skipped keys are not probed: the slot's index
        (:meth:`_sync_fetch_range`) holds them in ``fetch_deferred`` until
        a put or a cancellation reopens them, and they count as deferrals
        in one addition.  Only the keys in ``fetch_open`` are classified
        one by one, in the order layer, stream, page, tier; so a step costs
        the resident keys plus the state changes, not every live key, and
        queues the same jobs in the same order.  The step log counts the
        two kinds in ``fetch_probed`` and ``fetch_deferred``."""
        st = self._slots[slot_id]
        rid = st.rid
        self._sync_fetch_range(st)
        bulk = len(st.fetch_deferred)
        self.stats["kv_fetch_deferrals"] += bulk
        probed = 0
        for li, si, p in sorted({pair[:3] for pair in st.fetch_open}):
            key = self._slot_key(st, li, p, STREAMS[si])
            # shared pages fetch at THIS holder's ladder assignment
            # (the store hint is whichever holder re-ranked last)
            keep_fn = (None if key.seq_id == rid
                       else lambda st=st, p=p: st.page_planes.get(p))
            kt = key.astuple()
            reactivate = []
            for tier, cols in self._page_targets(key):
                pair = (li, si, p, tier.index)
                if pair not in st.fetch_open:
                    continue  # waits on a queued write: counted in bulk
                probed += 1
                if tier.store.contains(key):
                    tier.engine.submit(make_fetch_job(
                        tier.store, self.stats, key,
                        self._seq_key(tier, rid),
                        device_kv=self.device_kv,
                        telemetry=self.telemetry,
                        rid=rid, keep_fn=keep_fn,
                    ))
                elif (tier.engine.pending(kt, JobClass.KV_WRITE)
                      or tier.engine.pending(kt, JobClass.BACKGROUND)):
                    # write or re-activation already queued — only
                    # those classes restore the page; a stale queued
                    # fetch must not suppress the re-activation
                    self.stats["kv_fetch_deferrals"] += 1
                    self._defer(st, pair)
                elif self._can_reactivate(st, p, ln):
                    reactivate.append((tier, cols))
                    self._defer(st, pair)
                else:
                    # ring: the window slid over part of the page's
                    # device rows — nothing left to re-compress, and
                    # the page dies shortly anyway
                    self.stats["kv_fetch_misses"] += 1
            if reactivate:
                self._reactivate(slot_id, key, reactivate)
        steps.count("fetch_probed", probed)
        steps.count("fetch_deferred", bulk)

    def _sync_fetch_range(self, st: SlotState) -> None:
        """Move the slot's fetch index to its live page range: keys of pages
        that left it are forgotten, and each key of a page that entered it
        starts deferred when it is not resident and a write or
        re-activation of it is queued, else open."""
        lo, hi = self._live_page_range(st)
        old_lo, old_hi = st.fetch_range
        if (lo, hi) == (old_lo, old_hi):
            return
        ls = self.stored_layers()
        for p in range(old_lo, min(old_hi, lo)):
            for li in range(ls):
                for si in range(len(STREAMS)):
                    for tier in self.tiers:
                        pair = (li, si, p, tier.index)
                        st.fetch_open.discard(pair)
                        st.fetch_deferred.discard(pair)
        for p in range(max(old_hi, lo), hi):
            for li in range(ls):
                for si, stream in enumerate(STREAMS):
                    key = self._slot_key(st, li, p, stream)
                    kt = key.astuple()
                    for tier, _cols in self._page_targets(key):
                        queued = not tier.store.contains(key) and (
                            tier.engine.pending(kt, JobClass.KV_WRITE)
                            or tier.engine.pending(kt, JobClass.BACKGROUND))
                        (st.fetch_deferred if queued else st.fetch_open).add(
                            (li, si, p, tier.index))
        st.fetch_range = (lo, hi)

    @staticmethod
    def _defer(st: SlotState, pair: tuple) -> None:
        st.fetch_open.discard(pair)
        st.fetch_deferred.add(pair)

    @staticmethod
    def _reopen(st: SlotState, pairs) -> None:
        for pair in pairs:
            st.fetch_deferred.discard(pair)
            st.fetch_open.add(pair)

    def _note_put(self, tier: MemTier, key: PageKey, writer: SlotState) -> None:
        """A serviced write or re-activation put ``key`` on ``tier``: every
        slot whose index counts that key as deferred probes it again.  A
        request-keyed page belongs to its writer alone; a shared page to
        every slot whose key at that place is the same."""
        pair = (key.layer, STREAMS.index(key.stream), key.page_idx,
                tier.index)
        holders = (self._slots.values() if is_prefix_seq(key.seq_id)
                   else (writer,))
        for st in holders:
            if pair in st.fetch_deferred and self._slot_key(
                    st, key.layer, key.page_idx, key.stream) == key:
                self._reopen(st, (pair,))

    def _reactivate(self, slot_id: int, key: PageKey, targets) -> None:
        """An evicted page is needed again: queue a background re-compress
        from the device working set, keeping the plane count the ladder last
        assigned.  The page data is captured at submit time (append-only
        token range) and the kv_write is charged exactly once per tier, when
        the engine services the job.  A ragged stored tail re-activates at
        its exact valid length."""
        st = self._slots[slot_id]
        t0 = key.page_idx * PAGE_TOKENS
        valid = min(PAGE_TOKENS, st.stored_tokens - t0)
        k_np, v_np = self.slot_kv_host(slot_id, t0, t0 + valid)
        kv = k_np[key.layer] if key.stream == "k" else v_np[key.layer]
        _, page, valid = next(iter_page_chunks(kv))
        for tier, cols in targets:
            part = page if cols is None else page[:, cols]

            def fn(tier=tier, key=key, part=part, valid=valid, st=st):
                tier.store.put_page(key, part,
                                    planes=st.page_planes.get(key.page_idx),
                                    valid_tokens=valid)
                self._note_put(tier, key, st)
                self.stats["kv_reactivations"] += 1

            tier.engine.submit(Job(JobClass.BACKGROUND, part[:valid].nbytes,
                                   fn=fn, key=key.astuple(),
                                   seq_id=self._seq_key(tier, st.rid)))

    def _device_k_rows(self, slot_id: int, t0: int, t1: int):
        """Last-layer device keys for absolute tokens [t0, t1) — the quest
        ranking input, identical between layouts (bit-plane unpack at full
        precision is a bf16 bitcast)."""
        rows = self._device_rows(t0, t1)
        if self.device_kv != "bitplane":
            k = self._cache["k"][-1, slot_id, rows]
            st = self._slots.get(slot_id)
            if "sk" in self._cache and st is not None:
                # staged tokens (incl. the q-proxy row ln-1) live in the ring
                sb = st.stage_base
                ws = self._cache["sk"].shape[2]
                for tok in range(max(t0, sb), min(t1, sb + ws)):
                    k = k.at[tok - t0].set(
                        self._cache["sk"][-1, slot_id, tok - sb])
            return k
        from repro.kernels.paged_attention.ops import read_kv_rows

        pl = self._cache["k_planes"][-1][:, slot_id]  # (bits, S/16, 1, W)
        return read_kv_rows(pl, rows, self._kv_shape("k"))

    def _device_page(self, page_idx: int) -> int:
        """Device plane-map column holding this absolute page (ring layouts
        fold modulo the window's page count)."""
        return page_idx

    def _push_device_planes(self, slot_id: int, st: SlotState) -> None:
        """Publish the slot's ladder assignment into the device plane map,
        so the NEXT decode step's kernel reads exactly the planes the
        controller will charge.  Pages without an assignment (growing tail,
        dead ring prefix already pruned from ``page_planes``) stay at full
        precision."""
        if self.device_kv != "bitplane":
            return
        bits = self.tiers[0].store.spec.bits
        row = np.full(self._cache["planes"].shape[1], bits, np.int32)
        for p, keep in st.page_planes.items():
            if p >= st.live_from_page:
                row[self._device_page(p)] = keep
        self._set_device_row(slot_id, st, row)

    def _set_device_row(self, slot_id: int, st: SlotState,
                        row: np.ndarray) -> None:
        """Write a slot's plane-map row to the device cache, skipping the
        transfer when it matches the last pushed row (steady-state decode
        re-syncs change nothing between page fills)."""
        if st.device_row is not None and np.array_equal(st.device_row, row):
            return
        st.device_row = row
        self._cache["planes"] = self._cache["planes"].at[slot_id].set(
            jnp.asarray(row)
        )
        if self.telemetry.enabled:  # only actual device writes, not re-syncs
            self.telemetry.on_plane_push(st.rid, slot_id)

    def _assign_ladder_planes(self, slot_id: int, ln: int) -> None:
        """Re-rank this slot's live full pages against the newest query
        proxy and record the ladder's plane count on every stored page (all
        layers share the last layer's ranking, as the seed engine did).  A
        ragged stored tail page keeps full precision until it fills.

        The per-page count is SNAPPED to the ladder's rung planes (nearest;
        ties keep the higher precision): a page is always at one of the
        ladder's named precisions, which is both the paper's Table II
        semantics and what bounds the device kernel's compile count to the
        rung set (``device_keeps``)."""
        ladder = self.cfg.ladder
        if ladder is None:
            return
        st = self._slots[slot_id]
        n_pages = ln // PAGE_TOKENS
        p0 = st.live_from_page
        if n_pages <= p0:
            return
        k_last = self._device_k_rows(slot_id, p0 * PAGE_TOKENS,
                                     n_pages * PAGE_TOKENS)
        kmin, kmax = page_minmax(k_last, PAGE_TOKENS)
        q_proxy = self._device_k_rows(slot_id, ln - 1, ln)[0]
        planes = assign_page_precision(quest_scores(q_proxy, kmin, kmax), ladder)
        mean_planes = np.asarray(jnp.mean(planes.astype(jnp.float32), axis=1))
        spec_bits = self.tiers[0].store.spec.bits
        rung_planes = sorted({min(spec_bits, max(1, p))
                              for _, p in ladder.rungs})
        for i, p in enumerate(range(p0, n_pages)):
            m = float(mean_planes[i])
            keep = min(rung_planes, key=lambda r: (abs(r - m), -r))
            st.page_planes[p] = keep
            for li in range(self.stored_layers()):
                for stream in ("k", "v"):
                    key = self._slot_key(st, li, p, stream)
                    for tier, _cols in self._page_targets(key):
                        tier.store.set_planes(key, keep)
        if self.telemetry.enabled:
            self.telemetry.on_ladder_rerank(st.rid, n_pages - p0)
        self._push_device_planes(slot_id, st)

    # ---------------------------------------------------------------- engine
    def tick(self) -> None:
        compute = self._weight_pass_pending
        self._weight_pass_pending = False
        if compute:
            # weight jobs enter the SAME lane window the step's KV traffic
            # is about to contend for: current pass first, then the next
            # pass's prefetch-depth layers (the double buffer)
            for ws in self.streamers:
                ws.begin_pass()
        jobs = queued = 0
        for tier in self.tiers:
            done = tier.engine.tick()
            jobs += done["serviced_jobs"]
            queued += done["queue_depth"]
        steps.count("memctl_jobs", jobs)
        steps.count("memctl_queue", queued)
        if compute:
            # any current-pass layer the window could not service is a
            # stall, charged to modeled latency (engine_time_ns)
            for ws in self.streamers:
                ws.window_close()

    def backlog(self) -> int:
        """Queued engine jobs across all tiers (eviction write-backs,
        deferred writes) — the drain loop services these before report()."""
        return sum(len(tier.engine.queue) for tier in self.tiers)

    def admit_pressure_ns(self) -> float:
        """Worst tier's engine-limited latency right now — the admission
        backpressure signal (`EngineConfig.admit_latency_ns_max`)."""
        return max(tier.engine.pressure_ns() for tier in self.tiers)

    def engine_time_ns(self) -> float:
        """Current modeled engine-clock time: the worst tier's serviced-work
        watermark (monotone — a request's fetches are only as done as the
        slowest shard's), plus the worst tier's cumulative weight-stream
        stall time (compute waited for a layer the lane window could not
        deliver; both terms are monotone, so the telemetry clock domain
        stays monotone).  The telemetry collector's second clock domain."""
        base = max(tier.engine.clock.elapsed_ns for tier in self.tiers)
        stall = max(
            (ws.counters["stall_ns"] for ws in self.streamers), default=0.0
        )
        return base + stall

    # ------------------------------------------------------------- reporting
    def note_peaks(self) -> None:
        stored = logical = 0
        for tier in self.tiers:
            fp = tier.store.footprint()
            stored += fp["stored_bytes"]
            logical += fp["logical_bytes"]
        self.stats["kv_peak_stored_bytes"] = max(
            self.stats["kv_peak_stored_bytes"], stored
        )
        self.stats["kv_peak_logical_bytes"] = max(
            self.stats["kv_peak_logical_bytes"], logical
        )

    def report(self) -> dict:
        """Memory-tier half of the scheduler's report: pad-free logical vs
        stored/fetched bytes (capacity + bandwidth savings), eviction
        counters, and the engine-limited numbers — aggregated across tiers
        (a single tier passes its engine report through unchanged)."""
        s: dict = {}
        w_log = w_phys = r_log = r_phys = r_dev = 0
        evictions = evicted_bytes = resident = 0
        for tier in self.tiers:
            wl, wp = tier.controller.stats.kind_bytes("kv_write")
            rl, rp = tier.controller.stats.kind_bytes("kv_read")
            w_log += wl
            w_phys += wp
            r_log += rl
            r_phys += rp
            r_dev += tier.controller.stats.kind_device_bytes("kv_read")
            fp = tier.store.footprint()
            evictions += fp["evictions"]
            evicted_bytes += fp["evicted_bytes"]
            resident += fp["stored_bytes"]
        s["kv_logical_bytes"] = w_log
        s["kv_stored_bytes"] = w_phys
        s["kv_fetch_logical"] = r_log
        s["kv_fetch_physical"] = r_phys
        if w_log:
            s["kv_capacity_saving"] = 1 - w_phys / w_log
        if r_log:
            s["kv_bandwidth_saving"] = 1 - r_phys / r_log
        # device half of the bandwidth claim: what the DEVICE cache read for
        # the same serviced decode fetches.  Bit-plane layout: equals the
        # controller's plane-scaled kv_read (kv_read_device_bytes) — the
        # ladder's bytes are wall-clock bytes.  Dense layout: equals the
        # full-precision logical bytes, exposing the accounting-vs-device
        # gap the bit-plane layout closes.
        s["device_kv"] = self.device_kv
        s["device_bytes_read"] = self.stats["device_bytes_read"]
        s["kv_read_device_bytes"] = r_dev
        if r_log:
            s["kv_device_bandwidth_saving"] = \
                1 - self.stats["device_bytes_read"] / r_log
        s["kv_evictions"] = evictions
        s["kv_evicted_bytes"] = evicted_bytes
        s["kv_resident_stored_bytes"] = resident
        # engine-limited numbers: what the modeled silicon actually sustained
        reports = [tier.engine.report() for tier in self.tiers]
        er = reports[0] if len(reports) == 1 else aggregate_engine_reports(reports)
        s["engine"] = er
        s["engine_utilization"] = er["utilization"]
        s["engine_modeled_latency_ns"] = er["modeled_latency_ns"]
        s["engine_deferred_jobs"] = er["deferred_job_steps"]
        s["engine_queue_depth_p99"] = er["queue_depth"]["p99"]
        s["admit_pressure_ns"] = self.admit_pressure_ns()
        # lane-budget split: which job class the modeled silicon spent its
        # utilization on (WEIGHT_FETCH appears once weights stream)
        total_sb = sum(er["serviced_bytes"].values())
        if total_sb:
            s["engine_utilization_by_class"] = {
                k: er["utilization"] * v / total_sb
                for k, v in er["serviced_bytes"].items()
            }
        # weight-side traffic (ISSUE 9): savings quoted over exact
        # (pad-free) block bytes — the same definition Table III quotes —
        # next to KV's, plus streamer stall exposure
        s["weights"] = self._weights_report()
        # shared-prefix traffic (ISSUE 10): hit ratio, dedup ledger,
        # resident shared footprint
        s["prefix"] = self._prefix_report()
        return s

    def _prefix_report(self) -> dict:
        pr: dict = {"enabled": self.prefix is not None}
        if self.prefix is None:
            return pr
        shared_pages = shared_bytes = bound = shared_evs = 0
        for tier in self.tiers:
            fp = tier.store.footprint()
            shared_pages += fp["shared_pages"]
            shared_bytes += fp["shared_stored_bytes"]
            bound += fp["bound_pages"]
            shared_evs += fp["shared_evictions"]
        matched = self.stats.get("prefix_tokens_matched", 0)
        prefilled = self.stats.get("prefill_tokens", 0)
        pr.update({
            "requests_matched": self.stats.get("prefix_requests_matched", 0),
            "tokens_matched": matched,
            "pages_matched": self.stats.get("prefix_pages_matched", 0),
            "bytes_deduplicated": self.stats.get("prefix_bytes_deduped", 0),
            "prefill_chunks_skipped":
                self.stats.get("prefill_chunks_skipped", 0),
            # matched tokens never prefill, so matched/(matched+prefilled)
            # is the fraction of prompt work the index absorbed
            "hit_ratio": (matched / (matched + prefilled)
                          if matched + prefilled else 0.0),
            "index_entries": len(self.prefix),
            "resident_shared_pages": shared_pages,
            "resident_shared_bytes": shared_bytes,
            "bound_pages": bound,
            "shared_evictions": shared_evs,
        })
        return pr

    def _weights_report(self) -> dict:
        w: dict = {"mode": self.cfg.weight_stream}
        if not self.streamers:
            return w
        rl = rp = stored = logical = 0
        for tier in self.tiers:
            l, p = tier.controller.stats.kind_bytes("weight_read")
            rl += l
            rp += p
            fp = tier.controller.footprint()
            stored += fp["weights_stored"]
            logical += fp["weights_logical"]
        reps = [ws.report() for ws in self.streamers]
        w.update({
            "n_layers": reps[0]["n_layers"],
            "prefetch_depth": reps[0]["prefetch_depth"],
            "stored_bytes": stored,
            "logical_bytes": logical,
            # capacity: resident compressed footprint vs pad-free logical
            "capacity_saving": 1 - stored / logical if logical else 0.0,
            "read_logical_bytes": rl,
            "read_physical_bytes": rp,
            # bandwidth: what the bus moved for streamed reads vs what the
            # compute fabric consumed (the paper's 25.2% headline, now a
            # serving number)
            "bandwidth_saving": 1 - rp / rl if rl else 0.0,
            "fetch_jobs": sum(r["fetch_jobs"] for r in reps),
            # passes: every tier consumes the same step stream, so these
            # are per-tier values, not sums
            "passes_consumed": max(r["passes_consumed"] for r in reps),
            "passes_fetched": max(r["passes_fetched"] for r in reps),
            "stall_steps": max(r["stall_steps"] for r in reps),
            "stall_layers": sum(r["stall_layers"] for r in reps),
            "stall_ns": max(r["stall_ns"] for r in reps),
            "stall_fraction": max(r["stall_fraction"] for r in reps),
        })
        return w

    # ------------------------------------------------- single-tier compat
    @property
    def store(self) -> CompressedKVStore:
        """Tier-0 store (compat; sharded deployments have one per shard)."""
        return self.tiers[0].store

    @property
    def controller(self) -> MemoryController:
        return self.tiers[0].controller

    @property
    def engine(self) -> CompressionEngineRuntime:
        return self.tiers[0].engine
