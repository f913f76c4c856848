"""Continuous-batching scheduler over a pluggable KV memory tier.

The seed engine ran one synchronous batch: every request was padded to the
longest prompt and decoded to the longest ``max_new_tokens``, and the
compressed store was dropped wholesale at the end.  This module replaces that
with the serving loop the paper's accounting actually pays off in:

* **Admission queue + slot map.**  ``submit()`` enqueues requests;
  every ``step()`` first admits waiting requests into free slots, then runs
  ONE batched decode step over all active slots, then retires requests that
  hit their own ``max_new_tokens`` — a short request frees its slot (and its
  KV pages) the step it finishes instead of riding along with the longest
  request.

* **Bucketed chunked prefill (ISSUE 3).**  Prompts are processed in
  page-aligned chunks whose sizes come from a power-of-two bucket set, so at
  most ``log2(max_ctx)`` prefill variants ever compile; each chunk appends
  directly into the slot's rows (``models.transformer.lm_prefill_chunk``)
  and ``cache["len"]`` holds the TRUE prompt length — no pad token is ever
  attended to, stored, ladder-ranked, or charged through the engine.
  Chunking also overlaps admission with decode: while other slots decode, a
  joining prompt advances ``prefill_chunks_per_step`` chunks per step
  (double-buffered slot join), so a long admission never stalls the batch.
  The legacy left-pad path survives as ``prefill_mode="padded"``.

* **Per-request sampling streams.**  The scheduler holds ONE base PRNG key
  (``EngineConfig.rng_seed``); request ``rid`` samples from
  ``fold_in(base, rid)`` with a per-request draw counter, so a request's
  tokens never depend on batch composition or on seeds passed for other
  requests mid-flight.

* **Pluggable memory tier (ISSUE 4).**  The scheduler owns NO memory state:
  every page write, decode fetch, eviction re-activation, ladder-plane
  assignment, retirement cleanup, engine tick and savings report goes
  through the :class:`~repro.serving.backends.KVBackend` protocol
  (``EngineConfig.backend``):

  - ``"paged"``   — single-device compressed paged tier (bit-exact with the
    pre-backend scheduler; the conformance suite pins it);
  - ``"sharded"`` — per-shard slot map + compressed tier + lane budget,
    pages routed by KV-head ownership via the runtime/sharding mesh rules;
  - ``"ring"``    — per-slot sliding-window ring caches, so Mixtral-family
    configs join continuous batching.

  The backend schedules *all* (de)compression on the finite-throughput
  memctl engine (ISSUE 2): jobs are serviced once per step in strict
  priority order (decode fetch > KV write > background re-compress) within
  each tier's lane budget, decode fetches are sized at service time, and
  ``run_until_drained`` keeps ticking until the backlog empties.

* **Admission backpressure (ISSUE 4 satellite).**  When the engine's
  modeled service latency runs more than ``admit_latency_ns_max`` ns behind
  the wall clock (``backend.admit_pressure_ns()``), new admissions are
  deferred — waiting requests stay queued until the lanes catch up — and
  ``report()`` counts the shed/deferred admits (``admits_deferred``,
  ``backpressure_steps``).

Scope: dense-cache families ({"k","v","len"}; dense/moe).  Sliding-window
(ring) caches are served by ``backend="ring"``; staged decode caches
(``decode_staging > 0``) are served by the paged backend under
``device_kv="dense"`` (ISSUE 6) — other combinations raise a precise
``ValueError``.  ``engine.ServingEngine`` keeps the old one-shot ``run()``
as a thin submit+drain wrapper.
"""

from __future__ import annotations

import dataclasses
import os
import weakref
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import MemoryController
from repro.core.quantization import PrecisionLadder
from repro.memctl import MemCtlConfig
from repro.models.model import Model
from repro.serving.backends import make_backend
from repro.serving.kv_cache import PAGE_TOKENS
from repro.serving.sampler import SamplerConfig, sample, sample_slots
from repro.telemetry import steps
from repro.telemetry.collector import TelemetryConfig, make_collector


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 32
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    #: retired because the context window filled before max_new_tokens —
    #: ``done`` with fewer tokens than asked, and this says why
    truncated: bool = False
    #: per-request sampling seed (None = the scheduler's base stream);
    #: affects ONLY this request's stream, never in-flight neighbours
    rng_seed: Optional[int] = None
    #: rejected at submit by the load-shedding policy
    #: (``EngineConfig.shed_latency_ns_max``): ``done`` with no output, and
    #: ``shed_reason`` says why — callers retry elsewhere/later instead of
    #: growing an unserviceable queue
    shed: bool = False
    shed_reason: str = ""
    # --- scheduler bookkeeping (filled in as the request moves through) ---
    arrival_step: int = -1  # step submit() saw it
    admit_step: int = -1  # step it won a slot
    finish_step: int = -1  # step it retired


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Shared by the scheduler and the compatibility engine wrapper."""

    max_batch: int = 8
    max_ctx: int = 512
    sampler: SamplerConfig = SamplerConfig()
    ladder: Optional[PrecisionLadder] = None  # None = full precision
    store_kv_compressed: bool = True
    #: compressed-tier byte budget (None = unbounded, the seed behaviour);
    #: sharded backends split it evenly across shards
    max_stored_bytes: Optional[int] = None
    #: cap on layers written through the compressed store (cost cap; None=all)
    store_layers: Optional[int] = 4
    #: legacy left-pad admission alignment — only used by
    #: ``prefill_mode="padded"``; PAGE_TOKENS keeps seed semantics
    prefill_align: int = PAGE_TOKENS
    #: KV-tier compression codec ('lz4' | 'zstd'); None = default_codec(),
    #: which picks zstd when the optional package is present, else lz4
    codec: Optional[str] = None
    #: (de)compression-engine geometry + per-step service window (memctl
    #: runtime).  ``MemCtlConfig(step_cycles=None)`` models the pre-memctl
    #: unbounded engine; sharded backends instantiate this geometry PER
    #: SHARD (scale-out silicon, summed in the report)
    engine: MemCtlConfig = MemCtlConfig()
    #: 'bucketed' — chunked prefill over power-of-two length buckets
    #: (<= log2(max_ctx) compiles, pad-free cache/store/accounting);
    #: 'padded' — the legacy left-pad-to-``prefill_align`` admission
    #: (one compile per distinct padded length; kept as the benchmark
    #: baseline)
    prefill_mode: str = "bucketed"
    #: chunks each mid-prefill slot advances per step while other slots
    #: decode (the admission/decode overlap knob); idle schedulers always
    #: run a joining prompt to completion in one step
    prefill_chunks_per_step: int = 1
    #: base sampling seed; request streams are fold_in(PRNGKey(seed), rid)
    rng_seed: int = 0
    #: memory-tier policy behind the KVBackend protocol:
    #: 'paged' | 'sharded' | 'ring'.  The default honours the
    #: REPRO_SERVING_BACKEND env var so CI can run the whole scheduler
    #: suite against another tier without editing tests.
    backend: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_SERVING_BACKEND",
                                               "paged")
    )
    #: shard count for backend='sharded' (shards=1 is bit-exact with
    #: 'paged'; the conformance suite asserts it)
    shards: int = 2
    #: device KV-cache layout (ISSUE 5): 'dense' — bf16 rows, decode reads
    #: full precision regardless of the ladder (bandwidth savings are
    #: accounting-only); 'bitplane' — packed int32 bit-planes, decode runs
    #: the Pallas partial-plane rung kernel and reads exactly the planes
    #: the ladder prescribes (``report()["device_bytes_read"]`` equals the
    #: controller's plane-scaled kv_read).  The default honours the
    #: REPRO_SERVING_DEVICE_KV env var (CI leg), mirroring
    #: REPRO_SERVING_BACKEND.
    device_kv: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_SERVING_DEVICE_KV",
                                               "dense")
    )
    #: Pallas decode strategy for device_kv='bitplane' (ISSUE 6):
    #: 'fused' — ONE kernel launch per decode step that walks the per-page
    #: plane map inline (one compile per model config); 'rung' — one launch
    #: per distinct ladder plane count with a host-side partials merge
    #: (compiles bounded by the rung set; kept for differential testing).
    #: The default honours the REPRO_DECODE_KERNEL env var (CI leg).
    decode_kernel: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_DECODE_KERNEL",
                                               "fused")
    )
    #: admission backpressure threshold: defer new admits while the
    #: engine's modeled service latency lags the wall clock by more than
    #: this many ns (None = admit regardless, the pre-backpressure
    #: behaviour)
    admit_latency_ns_max: Optional[float] = None
    #: load-shedding threshold (ISSUE 10 satellite): REJECT a request at
    #: ``submit()`` — ``req.shed = True`` with a reason, never enqueued —
    #: when ``backend.admit_pressure_ns()`` already exceeds this.  Unlike
    #: ``admit_latency_ns_max`` (which parks requests in the queue until
    #: the lanes catch up), shedding bounds queueing delay: a caller with
    #: an SLO learns NOW that this engine cannot meet it.  None = never
    #: shed (the pre-policy behaviour).
    shed_latency_ns_max: Optional[float] = None
    #: shared-prefix KV pages (ISSUE 10): key full prompt pages by a
    #: rolling content hash so requests sharing a page-aligned prefix
    #: store its KV once; a new prompt's longest indexed prefix is adopted
    #: at its first prefill tick (pages bound by refcount, prefill chunks
    #: skipped, decode diverges copy-on-write at page granularity).
    #: Default OFF — page keys, eviction order and accounting are then
    #: bit-identical to the pre-prefix scheduler.  Honours the
    #: REPRO_PREFIX_SHARING env var (CI leg), mirroring
    #: REPRO_SERVING_BACKEND.
    prefix_sharing: bool = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_PREFIX_SHARING",
                                               "0") == "1"
    )
    #: LRU capacity of the prefix index (registered distinct prefixes,
    #: each holding a host snapshot of its device KV rows for adoption)
    prefix_index_entries: int = 128
    #: serving telemetry (ISSUE 7): request-lifecycle spans, per-step
    #: structured events, memctl lane timelines, and the
    #: Perfetto/Prometheus exporters they feed.  None (the default) wires
    #: the no-op null collector — every instrumentation site pays one
    #: branch and the serving output stays bit-identical.
    telemetry: Optional[TelemetryConfig] = None
    #: weight-side streaming (ISSUE 9): 'resident' — layer weights sit
    #: dense in HBM and no weight traffic touches the lanes (the
    #: pre-weight-stream behaviour); 'compressed' — layer weights are
    #: stored block-compressed behind each tier's controller and every
    #: compute step streams one decompress pass through the SAME lane
    #: budget KV fetches contend for (``JobClass.WEIGHT_FETCH``),
    #: double-buffered one pass ahead.  Compression is lossless, so
    #: streamed decoding is bit-identical to resident (the conformance
    #: suite asserts it).  The default honours the REPRO_WEIGHT_STREAM
    #: env var (CI leg), mirroring REPRO_SERVING_BACKEND.
    weight_stream: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_WEIGHT_STREAM",
                                               "resident")
    )
    #: layers of the NEXT weight pass prefetched during the current step's
    #: lane window (weight_stream='compressed').  None = the whole next
    #: pass (full double buffer, fewest stalls); 0 = no overlap — every
    #: pass is fetched cold inside its own window (upper-bounds stall
    #: exposure under tight ``engine`` budgets)
    weight_prefetch_depth: Optional[int] = None


@dataclasses.dataclass
class _Slot:
    req: Request
    pending: int  # next token to feed the decoder (already sampled)
    prompt: np.ndarray  # (S,) int32 — exact length, never padded
    #: per-request sampling stream (fold_in(base, rid)); draw i uses
    #: fold_in(key, i) so the stream is independent of batch composition
    key: jax.Array = None
    draws: int = 0  # tokens sampled so far from this stream
    prefill_pos: int = 0  # prompt tokens already appended to the slot rows
    prefilling: bool = True  # still consuming prompt chunks (no decode yet)
    #: prefix-index lookup already ran for this slot (it runs exactly once,
    #: at the slot's first prefill tick — after same-step earlier slots
    #: have had a chance to register their own prefixes)
    prefix_checked: bool = False


def prefill_buckets(max_ctx: int) -> List[int]:
    """Power-of-two chunk sizes [PAGE_TOKENS, 2*PAGE_TOKENS, ... <= max_ctx]
    — the complete set of prefill shapes the scheduler can ever request, so
    compiles are bounded by log2(max_ctx) regardless of traffic."""
    out = []
    b = PAGE_TOKENS
    while b <= max_ctx:
        out.append(b)
        b *= 2
    return out or [max_ctx]


def next_chunk(rem: int, buckets: List[int]) -> tuple:
    """(bucket, real) for the next prefill chunk of a prompt with ``rem``
    tokens left: the largest bucket that fits, or the smallest bucket
    right-padded for the ragged tail.  The single definition both the
    scheduler's admission loop and :func:`chunk_schedule` use."""
    fit = [b for b in buckets if b <= rem]
    bucket = fit[-1] if fit else buckets[0]
    return bucket, min(bucket, rem)


def chunk_schedule(prompt_len: int, buckets: List[int]) -> List[tuple]:
    """Greedy largest-first decomposition of a prompt into (bucket, real)
    chunks.  All buckets are page multiples, so every chunk starts page-
    aligned; only the final chunk may be ragged (real < bucket), and its pad
    sits AFTER every real token where causality masks it."""
    out = []
    rem = int(prompt_len)
    while rem > 0:
        bucket, real = next_chunk(rem, buckets)
        out.append((bucket, real))
        rem -= real
    return out


#: jitted prefill/decode/chunk shared across schedulers of the same model
#: instance, so compile time is paid once (benchmarks compare modes on
#: equal footing when they reuse one model object — and build fresh model
#: objects when they want cold-compile numbers).  Keyed per (model, keeps):
#: the bit-plane device path bakes the ladder's static plane-count set into
#: the decode closure (one Pallas rung per member).
_JIT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _jitted(model: Model, keeps: tuple | None = None,
            decode_kernel: str = "fused"):
    """(prefill, decode, prefill_chunk) jits of named functions, so a
    profiler trace shows ``jit_decode(...)`` and ``jit_prefill_chunk(...)``
    (the name is part of the program, and of its compile-cache key)."""
    per = _JIT_CACHE.setdefault(model, {})
    key = (keeps, decode_kernel)
    try:
        return per[key]
    except KeyError:
        def prefill(p, b):
            return model.prefill(p, b)

        def decode(p, t, c):
            if keeps is None:
                return model.decode(p, t, c)
            return model.decode(p, t, c, keeps=keeps,
                                decode_kernel=decode_kernel)

        def prefill_chunk(p, t, c, slot, start, last):
            return model.prefill_chunk(p, t, c, slot, start, last)

        chunk = (jax.jit(prefill_chunk)
                 if model.prefill_chunk is not None else None)
        fns = (jax.jit(prefill), jax.jit(decode), chunk)
        per[key] = fns
        return fns


class ContinuousScheduler:
    """Admission queue + slot map + in-flight join/retire serving loop.

    All memory-tier traffic flows through ``self.backend`` (a
    :class:`~repro.serving.backends.KVBackend`); the scheduler itself holds
    no store, no controller, no engine and never indexes into the device
    cache dict — it only passes the opaque cache between jitted calls."""

    def __init__(self, model: Model, params, cfg: EngineConfig,
                 controller: MemoryController | None = None):
        if cfg.prefill_mode not in ("bucketed", "padded"):
            raise ValueError(
                f"prefill_mode must be 'bucketed' or 'padded', "
                f"got {cfg.prefill_mode!r}"
            )
        if cfg.decode_kernel not in ("fused", "rung"):
            raise ValueError(
                f"decode_kernel must be 'fused' or 'rung', "
                f"got {cfg.decode_kernel!r}"
            )
        if cfg.prefix_sharing and cfg.prefill_mode == "padded":
            raise ValueError(
                "prefix_sharing requires prefill_mode='bucketed': padded "
                "admission runs one monolithic prefill inside _admit, so "
                "there is no chunk schedule to skip matched pages from"
            )
        if cfg.prefill_mode == "bucketed" and cfg.max_ctx % PAGE_TOKENS != 0:
            # a ragged final bucket landing near the cache end would be
            # CLAMPED by dynamic_update_slice and silently overwrite earlier
            # KV rows; page-multiple max_ctx makes that unreachable (every
            # chunk start is a page multiple and every bucket fits)
            raise ValueError(
                f"bucketed prefill needs max_ctx to be a multiple of "
                f"PAGE_TOKENS ({PAGE_TOKENS}), got {cfg.max_ctx}"
            )
        self.model = model
        self.params = params
        self.cfg = cfg
        self.step_count = 0
        self.stats: Dict[str, float] = {
            "prefill_tokens": 0, "decode_tokens": 0,
            "prefill_chunks": 0, "prefill_compiles": 0,
            "requests_submitted": 0, "requests_completed": 0,
            "requests_truncated": 0,
            "decode_steps": 0, "decode_batch_occupancy": 0.0,
            "kv_reactivations": 0,
            "kv_fetch_misses": 0, "kv_fetch_deferrals": 0,
            "engine_jobs_cancelled": 0,
            "kv_peak_stored_bytes": 0, "kv_peak_logical_bytes": 0,
            "admits_deferred": 0, "backpressure_steps": 0,
            "requests_shed": 0, "prefill_chunks_skipped": 0,
        }
        #: host nanoseconds of this scheduler's steps, per phase
        #: (``steps.PHASES``); ``report()["host_s_by_phase"]``
        self._phase_ns: Dict[str, int] = dict.fromkeys(steps.PHASES, 0)
        # the memory tier: store(s) + controller(s) + lane engine(s) live
        # behind the protocol; the backend mutates the shared stats dict
        self.telemetry = make_collector(cfg.telemetry)
        self.backend = make_backend(model, cfg, controller=controller,
                                    stats=self.stats,
                                    telemetry=self.telemetry)
        # weight streaming (ISSUE 9): ingest the per-layer handles into the
        # backend's tiers; no-op under weight_stream='resident'.  Compute
        # still runs from the resident params (compression is lossless and
        # the streamer models bandwidth/latency), so decoding stays
        # bit-identical either way.
        self.backend.attach_weights(params)
        if self.telemetry.enabled:
            # both readers are monotone, so span stamps are monotone in
            # both clock domains (the lifecycle invariant tests pin)
            self.telemetry.bind_clocks(lambda: self.step_count,
                                       self.backend.engine_time_ns)
        self._prefill, self._decode, self._prefill_chunk = _jitted(
            model, self.backend.device_keeps(), cfg.decode_kernel
        )
        # chunked admission needs the chunk kernel; families without one
        # (none today among dense/moe) fall back to the padded path
        self._mode = (cfg.prefill_mode if self._prefill_chunk is not None
                      else "padded")
        self._buckets = prefill_buckets(
            min(cfg.max_ctx, self.backend.max_prefill_bucket())
        )
        self._prefill_shapes: set = set()  # distinct compiled variants asked
        self._waiting: Deque[Request] = deque()
        self._slots: List[Optional[_Slot]] = [None] * cfg.max_batch
        self._lens = np.zeros(cfg.max_batch, np.int32)
        self._base_key = jax.random.PRNGKey(cfg.rng_seed)
        self._zero_key = jax.random.PRNGKey(0)  # filler for idle slot rows

    # --------------------------------------------------- compat passthroughs
    @property
    def store(self):
        """Tier-0 compressed store (compat shim; use ``backend.store`` /
        ``backend.tiers``)."""
        return self.backend.store

    @property
    def controller(self):
        """Tier-0 memory controller (compat shim)."""
        return self.backend.controller

    @property
    def engine(self):
        """Tier-0 compression-engine runtime (compat shim)."""
        return self.backend.engine

    # ------------------------------------------------------------------ queue
    def submit(self, req: Request, rng_seed: int | None = None) -> None:
        if rng_seed is not None:
            # per-REQUEST stream seed: scoped to this request only, so it
            # cannot disturb the sampling streams of in-flight neighbours
            req.rng_seed = rng_seed
        admitted = (len(req.prompt) if self._mode == "bucketed"
                    else self._padded_len(len(req.prompt)))
        if len(req.prompt) < 1 or admitted + 1 > self.cfg.max_ctx:
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"(admitted as {admitted}) leaves no decode room — exceeds "
                f"max_ctx {self.cfg.max_ctx}"
            )
        req.arrival_step = self.step_count
        lim = self.cfg.shed_latency_ns_max
        if lim is not None:
            pressure = self.backend.admit_pressure_ns()
            if pressure > lim:
                # reject-with-reason instead of unbounded queueing: the
                # request is done (no output), never enqueued, no span
                req.done = True
                req.shed = True
                req.shed_reason = (
                    f"admission rejected: modeled engine backlog "
                    f"{pressure:.0f}ns exceeds shed_latency_ns_max "
                    f"{lim:.0f}ns"
                )
                req.finish_step = self.step_count
                self.stats["requests_shed"] += 1
                return
        self._waiting.append(req)
        self.stats["requests_submitted"] += 1
        if self.telemetry.enabled:
            self.telemetry.on_submit(req.rid, len(req.prompt))

    @property
    def active(self) -> int:
        """Occupied slots (prefilling or decoding)."""
        return sum(s is not None for s in self._slots)

    @property
    def decoding(self) -> int:
        """Slots past prefill, generating tokens."""
        return sum(s is not None and not s.prefilling for s in self._slots)

    def has_work(self) -> bool:
        """Anything left to do — including engine backlog: queued jobs
        (eviction write-backs, deferred writes) must be serviced before the
        run's utilization/latency report means anything."""
        return (bool(self._waiting) or self.active > 0
                or self.backend.backlog() > 0)

    # ------------------------------------------------------------------- step
    def step(self) -> List[Request]:
        """Admit -> dispatch prefill chunks -> dispatch one batched decode
        step -> flush prefill storage -> commit decode -> engine tick ->
        retire.  Returns the requests retired this step.

        Each phase is a ``steps.span`` (``serve.<phase>`` in a profiler
        trace, nanoseconds in the step's record in ``steps.LOG``); the
        phases tile the step, its idle branch included.

        True async admission (ISSUE 5 satellite): prefill chunks are
        DISPATCHED without a host sync — the old per-chunk
        ``block_until_ready`` serialized every chunk ahead of the decode
        dispatch — and the backend's host-side page streaming
        (``on_prefill_progress``: device->host copy + engine job
        submission) runs AFTER the decode step is dispatched, overlapping
        with its device execution.  The overlap is safe because a chunk's
        rows [0, end) are append-only: the concurrent decode writes only at
        each row's own ``len`` position (== the mid-prefill row's next
        chunk start).  Chunk pacing is unchanged — a joining prompt still
        advances exactly ``prefill_chunks_per_step`` chunks per step while
        others decode.

        The engine tick is where every (de)compression submitted this step
        — prefill/decode page writes, decode fetches, re-activations — is
        serviced against each tier's per-step lane budget; leftovers stay
        queued for later windows."""
        with steps.step(self.step_count) as record:
            done = self._step(record)
        for phase, ns in record["phase_ns"].items():
            self._phase_ns[phase] += ns
        return done

    def _step(self, record: dict) -> List[Request]:
        with steps.span("admit"):
            self._admit_tick()
        with steps.span("prefill_dispatch"):
            progressed = self._prefill_tick()
        if self.decoding == 0:
            with steps.span("kv_flush"):
                self._flush_prefill_progress(progressed)
            with steps.span("engine_tick"):
                self.backend.tick()  # engine windows track wall steps
            with steps.span("retire"):
                self._note_step(record)
                self.step_count += 1  # idle tick: arrival traces keyed on
            return []                 # step_count must still advance time
        with steps.span("decode_dispatch"):
            pending_decode = self._decode_dispatch()
        with steps.span("kv_flush"):
            self._flush_prefill_progress(progressed)
        self._decode_commit(pending_decode)
        with steps.span("engine_tick"):
            self.backend.tick()
        with steps.span("retire"):
            if self.cfg.store_kv_compressed:
                self.backend.note_peaks()
            self._note_step(record)
            self.step_count += 1
            return self._retire_finished()

    def _note_step(self, record: dict) -> None:
        """Hand the step's record (``steps.step``) to the collector, with
        occupancy, waiting queue and engine backlog (the Perfetto counter
        tracks); the record's phases and end fill in when the step
        closes."""
        if self.telemetry.enabled:
            record.update(active=self.active, decoding=self.decoding,
                          waiting=len(self._waiting),
                          backlog=self.backend.backlog())
            self.telemetry.on_step(record)

    def _flush_prefill_progress(self, progressed) -> None:
        """Hand this step's completed prompt spans to the backend (page
        writes + ladder assignment), in dispatch order."""
        for slot_id, end, final in progressed:
            self.backend.on_prefill_progress(slot_id, end, final)

    def run_until_drained(self, max_steps: int = 100_000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_steps):
            if not self.has_work():
                break
            done.extend(self.step())
        return done

    def _padded_len(self, prompt_len: int) -> int:
        align = max(1, self.cfg.prefill_align)
        return -(-prompt_len // align) * align

    # -------------------------------------------------------------- admission
    def _admit_tick(self) -> None:
        """Fill free slots from the waiting queue — unless the engine's
        modeled latency lags the wall clock past
        ``admit_latency_ns_max`` (admission backpressure): then waiting
        requests stay queued and the deferral is counted, so saturated
        lanes shed load visibly instead of growing an unserviceable
        backlog."""
        if not self._waiting:
            return
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return
        lim = self.cfg.admit_latency_ns_max
        if lim is not None and self.backend.admit_pressure_ns() > lim:
            self.stats["admits_deferred"] += min(len(free), len(self._waiting))
            self.stats["backpressure_steps"] += 1
            return
        for slot_id in free:
            if not self._waiting:
                break
            self._admit(self._waiting.popleft(), slot_id)

    def _admit(self, req: Request, slot_id: int) -> None:
        self.backend.ensure_cache()
        prompt = np.asarray(req.prompt, np.int32)
        base = (jax.random.PRNGKey(req.rng_seed)
                if req.rng_seed is not None else self._base_key)
        self._slots[slot_id] = _Slot(
            req=req, pending=-1, prompt=prompt,
            key=jax.random.fold_in(base, req.rid),
        )
        self._lens[slot_id] = 0
        self.backend.bind_slot(slot_id, req.rid)
        req.admit_step = self.step_count
        if self.telemetry.enabled:
            self.telemetry.on_admit(req.rid, slot_id)

    def _prefill_tick(self) -> List[tuple]:
        """Advance every mid-prefill slot (the padded path runs a slot's
        whole prompt at once, in its admission step).  Overlap policy — the
        double-buffered slot join: while other slots decode, a joining
        prompt advances only ``prefill_chunks_per_step`` chunks per step so
        admission never stalls the batch; with nothing decoding, the prompt
        runs to completion now (nobody is waiting on the step).

        Returns the (slot_id, end, final) progress events of the chunks it
        dispatched; the caller flushes them to the backend AFTER the decode
        dispatch, so the backend's host-side copies don't sit on the decode
        critical path."""
        progressed: List[tuple] = []
        decode_live = self.decoding > 0
        for slot_id, slot in enumerate(self._slots):
            if slot is None or not slot.prefilling:
                continue
            if self._mode == "padded":
                self._prefill_padded(slot_id)
                continue
            if not slot.prefix_checked:
                # shared-prefix adoption (EngineConfig.prefix_sharing; the
                # backend returns 0 when sharing is off or nothing
                # matched): matched pages are already bound + on device,
                # so prefill starts at the divergence page — the matched
                # chunks are SKIPPED, never computed, stored or charged
                slot.prefix_checked = True
                m = self.backend.match_prefix(slot_id, slot.prompt)
                if m:
                    slot.prefill_pos = m
                    self._lens[slot_id] = m
                    self.stats["prefill_chunks_skipped"] += len(
                        chunk_schedule(m, self._buckets)
                    )
            budget = (max(1, self.cfg.prefill_chunks_per_step)
                      if decode_live else len(slot.prompt))
            while slot.prefilling and budget > 0:
                self._prefill_chunk_once(slot_id, progressed)
                budget -= 1
        return progressed

    def _prefill_chunk_once(self, slot_id: int, progressed: List[tuple]) -> None:
        """Dispatch ONE bucketed chunk of this slot's prompt through the
        chunked prefill kernel, appending it into the slot's cache rows.
        No host sync: the chunk's completion is recorded on ``progressed``
        for a post-decode-dispatch flush.  Only the final chunk
        materializes its logits — the first output token must exist before
        the slot joins this step's batched decode."""
        slot = self._slots[slot_id]
        start = slot.prefill_pos
        bucket, real = next_chunk(len(slot.prompt) - start, self._buckets)
        tokens = np.empty(bucket, np.int32)
        tokens[:real] = slot.prompt[start:start + real]
        if real < bucket:  # ragged tail: pad value is irrelevant (masked)
            tokens[real:] = slot.prompt[-1]

        logits, cache = self._prefill_chunk(
            self.params, jnp.asarray(tokens[None]), self.backend.cache,
            jnp.int32(slot_id), jnp.int32(start), jnp.int32(real - 1),
        )
        self.backend.cache = cache
        self.stats["prefill_tokens"] += real
        self.stats["prefill_chunks"] += 1
        self._prefill_shapes.add(("bucket", bucket))
        self.stats["prefill_compiles"] = len(self._prefill_shapes)

        slot.prefill_pos = start + real
        self._lens[slot_id] = slot.prefill_pos
        final = slot.prefill_pos >= len(slot.prompt)
        progressed.append((slot_id, slot.prefill_pos, final))
        if self.telemetry.enabled:
            self.telemetry.on_prefill_chunk(slot.req.rid, start,
                                            slot.prefill_pos, final)
        if final:
            slot.prefilling = False
            slot.pending = self._first_token(slot, logits)
            if self.telemetry.enabled:
                self.telemetry.on_first_token(slot.req.rid)

    def _prefill_padded(self, slot_id: int) -> None:
        """Legacy admission: left-pad to ``prefill_align`` and run one
        monolithic prefill (one compile per distinct padded length).  Pad
        KV lands inside ``cache["len"]`` and the store — the inflated
        baseline ``prefill_mode="bucketed"`` exists to beat."""
        slot = self._slots[slot_id]
        prompt = slot.prompt
        s = self._padded_len(len(prompt))
        padded = np.zeros(s, np.int32)
        padded[s - len(prompt):] = prompt  # left-pad (seed semantics)

        logits, pcache = self._prefill(
            self.params, {"tokens": jnp.asarray(padded[None])}
        )
        logits = jax.block_until_ready(logits)
        self.stats["prefill_tokens"] += s
        self._prefill_shapes.add(("padded", s))
        self.stats["prefill_compiles"] = len(self._prefill_shapes)

        # join in flight: copy the prefill KV into this slot's rows
        self.backend.adopt_prefill(slot_id, pcache, s)
        self._lens[slot_id] = s
        slot.prefill_pos = s
        slot.prefilling = False
        slot.pending = self._first_token(slot, logits)
        if self.telemetry.enabled:
            self.telemetry.on_prefill_chunk(slot.req.rid, 0, s, True)
            self.telemetry.on_first_token(slot.req.rid)
        self.backend.on_prefill_progress(slot_id, s, final=True)

    def _first_token(self, slot: _Slot, logits) -> int:
        """Draw 0 of the slot's own stream (greedy = argmax, as before)."""
        tok = sample(jax.random.fold_in(slot.key, 0), logits,
                     self.cfg.sampler)
        slot.draws = 1
        return int(np.asarray(tok)[0])

    # ----------------------------------------------------------------- decode
    def _decode_dispatch(self):
        """Dispatch one batched decode step + sampling; returns the pending
        device result — the sampled tokens and the step's per-row counters
        (``backend.decode_counters``) — WITHOUT materializing it, so
        host-side work (the prefill storage flush) overlaps the device
        execution."""
        b = self.cfg.max_batch
        tok = np.zeros(b, np.int32)
        draws = np.zeros(b, np.int64)
        keys = []
        for i, slot in enumerate(self._slots):
            if slot is not None and not slot.prefilling:
                tok[i] = slot.pending
                draws[i] = slot.draws
                keys.append(slot.key)
            else:
                # idle or mid-prefill row: dummy token/key; its appended k/v
                # is masked by kv_valid and overwritten by the next prefill
                # chunk or admission (see models/attention per-slot path)
                keys.append(self._zero_key)
        # staging anchor for staged decode caches: a post-prefill row's
        # staging window is anchored at its prefill end (its main cache
        # holds the whole prompt, flushed windows follow in ws strides);
        # -1 = no anchor (idle / mid-prefill rows stage nothing)
        anchor = np.full(b, -1, np.int32)
        for i, slot in enumerate(self._slots):
            if slot is not None and not slot.prefilling:
                anchor[i] = slot.prefill_pos
        self.backend.sync_lens(self._lens, stage_anchor=anchor)

        logits, cache = self._decode(
            self.params, jnp.asarray(tok), self.backend.cache
        )
        self.backend.cache = cache
        return (sample_slots(jnp.stack(keys), draws, logits, self.cfg.sampler),
                self.backend.decode_counters())

    def _decode_commit(self, pending) -> None:
        """Materialize the dispatched decode step (``serve.decode_wait``:
        tokens and counters in one wait) and run its bookkeeping
        (``serve.decode_commit``: outputs, lengths, per-slot page
        traffic)."""
        with steps.span("decode_wait"):
            nxt, counters = jax.device_get(pending)
        with steps.span("decode_commit"):
            self._commit_tokens(nxt, counters)

    def _commit_tokens(self, nxt: np.ndarray, counters=None) -> None:
        """Commit each decoding slot's token; each per-row counter of the
        step (``counters``) adds its decoding rows to the step log."""
        b = self.cfg.max_batch
        n_dec = self.decoding
        self.stats["decode_steps"] += 1
        self.stats["decode_batch_occupancy"] += n_dec / b
        live = self.telemetry.enabled
        committed: List[tuple] = []
        tokens: List[tuple] = []
        for i, slot in enumerate(self._slots):
            if slot is None or slot.prefilling:
                continue
            slot.req.output.append(slot.pending)
            slot.pending = int(nxt[i])
            slot.draws += 1
            self._lens[i] += 1
            self.stats["decode_tokens"] += 1
            if live:
                committed.append((slot.req.rid, i))
            tokens.append((i, int(self._lens[i])))
        self.backend.on_decode_tokens(tokens)
        steps.count("decoded", n_dec)
        for name, per_row in (counters or {}).items():
            steps.count(name, int(sum(per_row[i] for i, _ in tokens)))
        if live and committed:
            # one shared stamp for the whole batch — the tokens
            # materialized together in one device step
            self.telemetry.on_decode_commit(committed)

    # ----------------------------------------------------------------- retire
    def _retire_finished(self) -> List[Request]:
        done = []
        for i, slot in enumerate(self._slots):
            if slot is None or slot.prefilling:
                continue
            r = slot.req
            hit_ctx = int(self._lens[i]) >= self.cfg.max_ctx
            if len(r.output) >= r.max_new_tokens or hit_ctx:
                r.done = True
                if len(r.output) < r.max_new_tokens:
                    # context window filled first: fewer tokens than asked,
                    # and the request says why instead of silently stopping
                    r.truncated = True
                    self.stats["requests_truncated"] += 1
                r.finish_step = self.step_count
                # queued work for a retired request is dead: the backend
                # cancels it (shard-scoped) before dropping pages, so no
                # engine ever services stale jobs (eviction write-backs
                # carry seq_id=None and survive — committed work the drain
                # loop services)
                self.backend.retire(i, r.rid)
                self._slots[i] = None
                self._lens[i] = 0
                self.stats["requests_completed"] += 1
                if self.telemetry.enabled:
                    self.telemetry.on_retire(r.rid, len(r.output),
                                             r.truncated)
                done.append(r)
        return done

    # ----------------------------------------------------------------- report
    def report(self) -> dict:
        s = dict(self.stats)
        # memory-tier half (savings, evictions, engine-limited numbers) —
        # aggregated across the backend's tiers
        s.update(self.backend.report())
        s["host_s_by_phase"] = {p: ns * 1e-9
                                for p, ns in self._phase_ns.items()}
        if s["decode_steps"]:
            s["mean_batch_occupancy"] = (
                s["decode_batch_occupancy"] / s["decode_steps"]
            )
        # steady-state accounting: normalise per 1k requests, not per batch
        n = s["requests_completed"]
        if n:
            per = 1000.0 / n
            s["per_1k_requests"] = {
                "kv_stored_bytes": s["kv_stored_bytes"] * per,
                "kv_logical_bytes": s["kv_logical_bytes"] * per,
                "kv_fetch_physical": s["kv_fetch_physical"] * per,
                "kv_fetch_logical": s["kv_fetch_logical"] * per,
                "kv_evicted_bytes": s["kv_evicted_bytes"] * per,
                "decode_tokens": s["decode_tokens"] * per,
                "requests_truncated": s["requests_truncated"] * per,
                "admits_deferred": s["admits_deferred"] * per,
                "requests_shed": s["requests_shed"] * per,
            }
        if self.telemetry.enabled:
            # span-derived latency quantiles (both clock domains) + the
            # collector's own bookkeeping — the Prometheus snapshot and
            # the serving benchmark read these blocks
            s["latency"] = self.telemetry.latency_report()
            s["telemetry"] = self.telemetry.summary()
        return s
