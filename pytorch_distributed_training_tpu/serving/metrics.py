"""Serving-side metrics: latency percentiles, throughput, batch shape.

Latency is recorded per REQUEST (enqueue -> result set), so batching
delay is included — the number a client actually observes.  Throughput
counts work items (images for classification, generated tokens for LM)
over the window from the first to the last recorded request.

Storage is BOUNDED (telemetry/registry.py): per-request latencies,
batch sizes, and generated-token lengths land in Algorithm-R reservoir
histograms instead of the lists that previously grew one float per
request forever under sustained traffic.  Counts, sums, and means in the
snapshot stay exact (tracked outside the reservoir); the reported
percentiles are estimates of the TRUE stream percentiles once the stream
exceeds the reservoir (and exact below it, which keeps the snapshot
byte-stable for short runs and the existing tests).

Instruments live in a PRIVATE :class:`MetricsRegistry` (not the process
one): each engine owns its counts, and two engines in one process must
not share a ledger.

Fleet mode (PR 12): N replicas in one process each mirror their counters
into the PROCESS-global registry too (``ContinuousScheduler._bump``),
which used to collide on the shared ``serving_*`` names.  A
:class:`ServingMetrics` constructed with ``replica_id`` namespaces that
mirror (``serving_r<id>_*`` via :meth:`global_name`), and
:func:`aggregate_snapshots` folds the per-replica sub-snapshots into one
fleet view for ``ServingFleet.snapshot()``.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional

from ..telemetry.registry import MetricsRegistry

__all__ = ["TICK_PHASES", "ServingMetrics", "aggregate_snapshots"]

# The phases a productive scheduler tick's wall time is split over, in the
# order they run: each is a span of that kind (a child of ``tick``,
# serving/scheduler.py) and a histogram ``tick_<kind>_ms`` here.
TICK_PHASES = (
    "admit", "prefill", "decode_prep", "decode_step", "readback", "deliver",
)

# reservoir per distribution: big enough that p99 of a uniform sample is a
# tight estimate, small enough to cap memory at a few KB per engine
_RESERVOIR = 2048


class ServingMetrics:
    """Thread-safe accumulator; ``record_batch`` runs on the flush thread."""

    def __init__(self, replica_id: Optional[int] = None):
        self.replica_id = replica_id
        self._lock = threading.Lock()
        self._registry = MetricsRegistry()
        self._latency_ms = self._registry.histogram("latency_ms", _RESERVOIR)
        self._batch_size = self._registry.histogram("batch_size", _RESERVOIR)
        self._gen_len = self._registry.histogram("gen_len", _RESERVOIR)
        # continuous-scheduler shape: per-iteration slot occupancy and
        # block-pool utilization, both recorded as fractions in [0, 1]
        self._slot_occ = self._registry.histogram("slot_occupancy", _RESERVOIR)
        self._block_util = self._registry.histogram("block_util", _RESERVOIR)
        # of the ``slots x table_blocks`` entries of a decode step's block
        # tables, the share that holds a live position: what the step's
        # attention reads (ops/paged_decode.py walks those and no others)
        self._live_block_share = self._registry.histogram(
            "paged_live_block_share", _RESERVOIR
        )
        # of the slots of a ``[slots, ...]`` state leaf, the share a decode
        # step's rows live in: what the state's walk reads and writes
        # (ops/state_rows.py); only a model that carries a state records it
        self._state_live_row_share = self._registry.histogram(
            "state_live_row_share", _RESERVOIR
        )
        # disaggregated serving (PR 19): per-import host-staging wall
        # time; the byte/block counters ride the counter namespace
        self._kv_transfer_ms = self._registry.histogram(
            "kv_transfer_ms", _RESERVOIR
        )
        # async decode pipeline (PR 20): per-tick host overhead (tick
        # wall minus device-readback waits) and the host gap between
        # consecutive decode dispatch enqueues — the pair that makes the
        # pipeline win observable instead of inferred: async mode should
        # shrink the dispatch gap toward pure bookkeeping cost while
        # tick_host_ms stays flat
        self._tick_host_ms = self._registry.histogram(
            "tick_host_ms", _RESERVOIR
        )
        self._dispatch_gap_ms = self._registry.histogram(
            "decode_dispatch_gap_ms", _RESERVOIR
        )
        # where a productive tick's wall time goes (PR 24): one
        # observation a tick in each phase's histogram (0 for a phase the
        # tick did not run, so the means add up to the wall's), the whole
        # tick, and admit + decode_prep as "prep", the host work that
        # stands before the dispatch
        self._tick_wall_ms = self._registry.histogram(
            "tick_wall_ms", _RESERVOIR
        )
        self._tick_prep_ms = self._registry.histogram(
            "tick_prep_ms", _RESERVOIR
        )
        self._tick_phase_ms = {
            kind: self._registry.histogram(f"tick_{kind}_ms", _RESERVOIR)
            for kind in TICK_PHASES
        }
        # a request's life as the engine sees it, on the scheduler's own
        # stamps: submit to first admission, submit to first token
        # pushed, every gap between a request's consecutive pushes
        # (pooled), and how long a prefill held up rows already decoding
        self._life_ms = {
            name: self._registry.histogram(name, _RESERVOIR)
            for name in (
                "queue_wait_ms", "ttft_ms", "itl_ms", "prefill_stall_ms",
            )
        }
        # a model with expert layers (serving/decode.py's fourth output), one
        # observation a productive tick, each summed over the expert
        # layers: the experts that got a token, the largest count at one
        # expert, and that count over the mean count of a held expert
        self._moe = {
            name: self._registry.histogram(name, _RESERVOIR)
            for name in (
                "moe_experts_hit", "moe_expert_load_max",
                "moe_load_max_over_mean",
            )
        }
        self._items = 0  # guarded by: self._lock
        self._first_t: Optional[float] = None  # guarded by: self._lock
        self._last_t: Optional[float] = None  # guarded by: self._lock
        self._max_depth = 0  # guarded by: self._lock
        # LM phase split (round 6): accumulated prefill/decode device
        # seconds and the tokens each phase is RESPONSIBLE for.  Generated
        # token 0 is sampled by the prefill program, so it counts as a
        # prefill token (the attribution fix of PR 7 — it was previously
        # lumped into decode throughput and documented-not-corrected).
        self._prefill_tokens = 0  # guarded by: self._lock
        self._decode_tokens = 0  # guarded by: self._lock
        self._prefill_s = 0.0  # guarded by: self._lock
        self._decode_s = 0.0  # guarded by: self._lock
        # the decode steps dispatched, and those of them dispatched while
        # an earlier step was still in the scheduler's ring (its tokens
        # not yet read): the share says whether the ring engages
        self._decode_dispatches = 0  # guarded by: self._lock
        self._decode_overlapped = 0  # guarded by: self._lock
        # multi-tenant (serving.lora): per-adapter latency/len histograms,
        # lazily created in THIS private registry under adapter_<name>_*
        # — the same namespacing move replica_id makes in the process
        # registry, one level down.  Base-model requests stay in the flat
        # instruments only.
        self._adapter_hists: Dict[str, tuple] = {}  # guarded by: self._lock
        # speculative-decode acceptance floor (serving.speculative.
        # min_acceptance, plumbed in by the engine): a measured rate
        # below it makes snapshot() warn ONCE that speculation is
        # costing latency rather than saving it — the bench round that
        # motivated the gate measured 0.371x end-to-end throughput at a
        # 3.4% acceptance rate.  0.0 disables the gate.
        self.spec_min_acceptance = 0.0
        self._spec_floor_warned = False  # guarded by: self._lock
        # autoscaler scale-up readiness: wall ms from replica construction
        # to warm (every program compiled) — set once by the fleet's
        # add_replica after InferenceEngine.warmup()
        self._scale_up_ready_ms: Optional[float] = None  # guarded by: self._lock

    def adapter_name(self, adapter: str, name: str) -> str:
        """Registry name for adapter-scoped instrument ``name``."""
        return f"adapter_{adapter}_{name}"

    def _adapter_instruments(self, adapter: str):
        with self._lock:
            pair = self._adapter_hists.get(adapter)
            if pair is None:
                pair = (
                    self._registry.histogram(
                        self.adapter_name(adapter, "latency_ms"), _RESERVOIR
                    ),
                    self._registry.histogram(
                        self.adapter_name(adapter, "gen_len"), _RESERVOIR
                    ),
                )
                self._adapter_hists[adapter] = pair
            return pair

    def incr(self, name: str, n: int = 1) -> None:
        """Bump a named degradation counter (e.g. ``timeouts``, ``sheds``)."""
        self._registry.counter(name).inc(n)

    def global_name(self, name: str) -> str:
        """The PROCESS-registry mirror name for a serving instrument.

        Replica-less engines keep the historical flat ``serving_<name>``
        namespace (every existing test and bench reads it); a fleet
        replica gets ``serving_r<id>_<name>`` so N replicas in one
        process stop colliding in the shared ledger.
        """
        if self.replica_id is None:
            return f"serving_{name}"
        return f"serving_r{self.replica_id}_{name}"

    def record_batch(
        self,
        enqueued_ats: List[float],
        n_items: int,
        queue_depth: int = 0,
        gen_lens: Optional[List[int]] = None,
        prompt_tokens: int = 0,
        prefill_s: float = 0.0,
        decode_s: float = 0.0,
    ) -> None:
        """One flushed batch: per-request enqueue stamps + work-item count.

        LM batches additionally pass ``gen_lens`` (generated tokens per
        request), ``prompt_tokens`` (REAL prompt tokens consumed, not the
        padded bucket area), and the measured ``prefill_s`` / ``decode_s``
        phase wall times.
        """
        now = time.monotonic()
        for t0 in enqueued_ats:
            self._latency_ms.observe((now - t0) * 1000.0)
        self._batch_size.observe(len(enqueued_ats))
        if gen_lens is not None:
            for g in gen_lens:
                self._gen_len.observe(int(g))
        with self._lock:
            self._items += n_items
            if self._first_t is None:
                self._first_t = now
            self._last_t = now
            self._max_depth = max(self._max_depth, queue_depth)
            self._prefill_s += float(prefill_s)
            self._decode_s += float(decode_s)
            # prefill answers for the real prompt tokens it consumed PLUS
            # the first generated token of each request (it sampled them);
            # decode answers for the rest
            n_req = len(gen_lens) if gen_lens else 0
            self._prefill_tokens += int(prompt_tokens) + n_req
            if gen_lens:
                self._decode_tokens += int(sum(gen_lens)) - n_req

    # ------------------------------------------------------------------ #
    # continuous-scheduler instruments (serving/scheduler.py): the
    # scheduler has no "batch" — requests retire one by one and device
    # time accrues per prefill call / per decode step

    def record_request(
        self, enqueued_at: float, gen_len: int,
        adapter: Optional[str] = None,
    ) -> None:
        """One RETIRED request: end-to-end latency + generated length.

        ``adapter`` (the request's LoRA adapter name) additionally lands
        the observation in that tenant's own instruments, so one snapshot
        answers per-tenant latency questions without a second ledger."""
        now = time.monotonic()
        self._latency_ms.observe((now - enqueued_at) * 1000.0)
        self._gen_len.observe(int(gen_len))
        if adapter is not None:
            lat_h, gen_h = self._adapter_instruments(adapter)
            lat_h.observe((now - enqueued_at) * 1000.0)
            gen_h.observe(int(gen_len))
            self._registry.counter(
                self.adapter_name(adapter, "requests")
            ).inc()
        with self._lock:
            self._items += int(gen_len)
            if self._first_t is None:
                self._first_t = now
            self._last_t = now

    def record_prefill(
        self, prompt_tokens: int, n_requests: int, prefill_s: float
    ) -> None:
        """One prefill call: suffix tokens consumed + token 0 per row."""
        with self._lock:
            self._prefill_tokens += int(prompt_tokens) + int(n_requests)
            self._prefill_s += float(prefill_s)

    def record_decode(self, n_tokens: int, decode_s: float) -> None:
        """One decode step: tokens sampled across the occupied slots."""
        with self._lock:
            self._decode_tokens += int(n_tokens)
            self._decode_s += float(decode_s)

    def record_decode_dispatch(self, inflight: int) -> None:
        """One decode step about to be dispatched with ``inflight`` earlier
        steps still in the scheduler's ring: its length, so 0 at depth 0
        and in a speculative round."""
        with self._lock:
            self._decode_dispatches += 1
            self._decode_overlapped += inflight > 0

    def record_iteration(
        self,
        active_slots: int,
        total_slots: int,
        blocks_in_use: int,
        total_blocks: int,
        live_block_share: Optional[float] = None,
        state_live_row_share: Optional[float] = None,
    ) -> None:
        """Scheduler-state sample at one decode iteration
        (``live_block_share``: of a single-position step's block tables;
        ``state_live_row_share``: of a state-carrying model's slots)."""
        self._slot_occ.observe(active_slots / max(total_slots, 1))
        self._block_util.observe(blocks_in_use / max(total_blocks, 1))
        if live_block_share is not None:
            self._live_block_share.observe(float(live_block_share))
        if state_live_row_share is not None:
            self._state_live_row_share.observe(float(state_live_row_share))

    def record_tick(self, host_ms: float) -> None:
        """One scheduler tick's HOST overhead: wall time minus the spans
        spent blocked on device readbacks — what the accelerator would
        idle through between dispatches were each step read before the
        next is sent (depth 0)."""
        self._tick_host_ms.observe(float(host_ms))

    def record_tick_phases(
        self, wall_ms: float, phase_ms: Dict[str, float]
    ) -> None:
        """One productive tick: its wall time and the milliseconds of
        each of :data:`TICK_PHASES` (absent = the phase did not run)."""
        self._tick_wall_ms.observe(float(wall_ms))
        self._tick_prep_ms.observe(
            phase_ms.get("admit", 0.0) + phase_ms.get("decode_prep", 0.0)
        )
        for kind, hist in self._tick_phase_ms.items():
            hist.observe(phase_ms.get(kind, 0.0))

    def record_queue_wait(self, ms: float) -> None:
        """Submit to first admission into a slot, one a request."""
        self._life_ms["queue_wait_ms"].observe(float(ms))

    def record_first_token(self, ms: float) -> None:
        """Submit to the first token pushed, one a request."""
        self._life_ms["ttft_ms"].observe(float(ms))

    def record_token_gap(self, ms: float) -> None:
        """The gap between two consecutive pushes of one request."""
        self._life_ms["itl_ms"].observe(float(ms))

    def record_prefill_stall(self, ms: float) -> None:
        """A tick's prefill phase that rows already decoding sat through:
        what their next token waited beyond a plain decode step."""
        self._life_ms["prefill_stall_ms"].observe(float(ms))

    def record_moe(self, experts_hit: int, load_max: int, tokens: int,
                   shape) -> None:
        """One decode step of a model with expert layers: ``experts_hit``
        and ``load_max`` summed over its ``shape[0]`` expert layers,
        ``tokens`` live rows each choosing ``shape[1]`` of the ``shape[2]``
        experts held."""
        layers, top_k, held = shape
        self._moe["moe_experts_hit"].observe(float(experts_hit))
        self._moe["moe_expert_load_max"].observe(float(load_max))
        if tokens:
            self._moe["moe_load_max_over_mean"].observe(
                (load_max / layers) / (tokens * top_k / held)
            )

    def record_dispatch_gap(self, gap_ms: float) -> None:
        """Host wall time between two consecutive decode dispatch
        enqueues during back-to-back decode ticks.  At depth 0 the gap
        includes the full readback + bookkeeping window; a ring that
        holds steps leaves bookkeeping only."""
        self._dispatch_gap_ms.observe(float(gap_ms))

    def record_scale_up_ready(self, ms: float) -> None:
        """Wall ms from replica construction to warm (all programs
        compiled) at autoscaler scale-up — the cold-compile TTFT a
        warmed ``add_replica`` no longer pays on first traffic."""
        with self._lock:
            self._scale_up_ready_ms = float(ms)
        self._registry.gauge("scale_up_ready_ms").set(float(ms))

    def record_pool_programs(
        self, *, pool_aliased_bytes: int, decode_program_temp_bytes: int
    ) -> None:
        """The warm-up's programs as compiled.  ``pool_aliased_bytes``: the
        bytes of the paged pool whose buffers the outputs reuse (the fewest
        over the programs): the pool's size where the donation took.  It
        does not say that nothing is copied: a leaf the program turns into
        another layout and back is written into its own buffer by a copy.
        ``decode_program_temp_bytes``, the decode step's temporaries, does:
        a whole leaf copied is a leaf's bytes there."""
        self._registry.gauge("pool_aliased_bytes").set(float(pool_aliased_bytes))
        self._registry.gauge("decode_program_temp_bytes").set(
            float(decode_program_temp_bytes))

    def record_cache_bytes(
        self, *, kv_pool_bytes: int, state_cache_bytes: int, pool_rows: int,
        window_ring_bytes: int = 0,
    ) -> None:
        """The cache tree as the warm-up found it: bytes of the paged pool's
        token rows and bytes of the per-slot leaves beside them (0 for a
        model that carries none).  ``pool_aliased_bytes`` is their sum
        where every program updates the whole tree in place.
        ``pool_bytes_per_token`` is what one more position of a row costs in
        the pool (the layers that keep a row's whole history), and
        ``window_ring_bytes``, a part of ``state_cache_bytes``, what the
        window layers' rings hold whatever the rows' lengths (recorded only
        for a model that has such layers)."""
        self._registry.gauge("kv_pool_bytes").set(float(kv_pool_bytes))
        self._registry.gauge("state_cache_bytes").set(float(state_cache_bytes))
        self._registry.gauge("pool_bytes_per_token").set(
            float(kv_pool_bytes) / max(int(pool_rows), 1))
        if window_ring_bytes:
            self._registry.gauge("window_ring_bytes").set(float(window_ring_bytes))

    def record_prefill_cost(self, fixed_ms: float, ms_per_ktoken: float) -> None:
        """What the warm-up's timed calls put a prefill call at: ``fixed_ms
        + ms_per_ktoken x batch bucket x sequence bucket / 1000``, the
        estimate by which the scheduler groups a tick's fresh admissions
        into calls."""
        self._registry.gauge("prefill_call_fixed_ms").set(float(fixed_ms))
        self._registry.gauge("prefill_call_ms_per_ktoken").set(float(ms_per_ktoken))

    def record_prefill_flash_layers(self, layers: int) -> None:
        """The most attention layers any prefill program of the compiled
        grid scores through the flash forward over the call's own K/V
        (``ContinuousScheduler._flash_layers``; beside it the counter
        ``prefill_flash_calls`` of ``prefill_calls``)."""
        self._registry.gauge("prefill_flash_layers").set(float(layers))

    def record_kv_transfer(
        self, *, nbytes: int, seconds: float, blocks: int
    ) -> None:
        """One serviced KV-block import (disaggregated serving): bytes
        and blocks that actually landed plus the host-staging wall time.
        Rejected payloads are counted by the scheduler's
        ``kv_transfer_rejects`` counter, not here."""
        if nbytes:
            self._registry.counter("kv_transfer_bytes").inc(int(nbytes))
        if blocks:
            self._registry.counter("kv_transfer_blocks").inc(int(blocks))
        self._kv_transfer_ms.observe(float(seconds) * 1000.0)

    def observe_depth(self, depth: int) -> None:
        with self._lock:
            self._max_depth = max(self._max_depth, depth)

    def record_health(self, health: Dict[str, object]) -> None:
        """Mirror a scheduler health snapshot into ``health_*`` gauges.

        The prefix keeps gauges out of the counter namespace
        (``engine_restarts`` is already a counter in this registry and
        :class:`MetricsRegistry` rejects cross-type name reuse).  Only
        numeric/bool fields are mirrored; a None ``last_tick_age_s``
        (no tick yet) is skipped rather than encoded as a sentinel.
        """
        for key, val in health.items():
            if isinstance(val, bool):
                self._registry.gauge(f"health_{key}").set(1.0 if val else 0.0)
            elif isinstance(val, (int, float)):
                self._registry.gauge(f"health_{key}").set(float(val))

    def snapshot(self) -> Dict[str, float]:
        """Aggregate view: p50/p99 latency, items/sec, batch occupancy."""
        lat = self._latency_ms.snapshot()
        sizes = self._batch_size.snapshot()
        gen = self._gen_len.snapshot()
        occ = self._slot_occ.snapshot()
        util = self._block_util.snapshot()
        with self._lock:
            span = (
                (self._last_t - self._first_t)
                if self._first_t is not None and self._last_t > self._first_t
                else 0.0
            )
            items = self._items
            depth = self._max_depth
            prefill_tokens = self._prefill_tokens
            decode_tokens = self._decode_tokens
            prefill_s = self._prefill_s
            decode_s = self._decode_s
            dispatches = self._decode_dispatches
            overlapped = self._decode_overlapped
        out = {
            "requests": int(lat["count"]),
            "batches": int(sizes["count"]),
            "items": int(items),
            "max_queue_depth": int(depth),
        }
        out.update({k: v for k, v in self._registry.counters().items() if v})
        if lat["count"]:
            out["latency_ms_p50"] = float(lat["p50"])
            out["latency_ms_p99"] = float(lat["p99"])
            out["latency_ms_mean"] = float(lat["mean"])
        if sizes["count"]:
            out["batch_size_mean"] = float(sizes["mean"])
        # open-loop throughput needs a time span; a single flush has none,
        # so fall back to unreported rather than divide-by-zero noise
        if span > 0:
            out["items_per_sec"] = float(items / span)
        if gen["count"]:
            out["gen_tokens"] = int(gen["sum"])
            out["gen_len_mean"] = float(gen["mean"])
            out["gen_len_p50"] = float(gen["p50"])
        # phase rates: each phase is divided by the tokens it actually
        # produced/consumed — generated token 0 is a PREFILL token (the
        # prefill program samples it), the remaining gen tokens are
        # decode's.  Fixes the round-6 attribution skew that inflated
        # decode throughput by one token per request.
        if prefill_s > 0 and prefill_tokens:
            out["prefill_tokens_per_sec"] = float(prefill_tokens / prefill_s)
        if decode_s > 0 and decode_tokens:
            out["decode_tokens_per_sec"] = float(decode_tokens / decode_s)
        # continuous-scheduler shape (absent on the batcher path)
        if occ["count"]:
            out["slot_occupancy_mean"] = float(occ["mean"])
        if util["count"]:
            out["block_util_mean"] = float(util["mean"])
            out["block_util_max"] = float(util["max"])
        share = self._live_block_share.snapshot()
        if share["count"]:
            out["paged_live_block_share_mean"] = float(share["mean"])
            out["paged_live_block_share_p50"] = float(share["p50"])
        share = self._state_live_row_share.snapshot()
        if share["count"]:
            out["state_live_row_share_mean"] = float(share["mean"])
            out["state_live_row_share_p50"] = float(share["p50"])
        xfer = self._kv_transfer_ms.snapshot()
        if xfer["count"]:
            out["kv_transfer_ms_p50"] = float(xfer["p50"])
            out["kv_transfer_ms_p99"] = float(xfer["p99"])
        # async-pipeline observability (absent until a tick/dispatch-gap
        # sample lands, keeping batcher-path snapshots byte-stable)
        if dispatches:
            # near 1 in a steady window of the ring, 0 at depth 0
            out["decode_steps_dispatched"] = dispatches
            out["decode_steps_overlapped"] = overlapped
            out["decode_overlap_share"] = overlapped / dispatches
        tick = self._tick_host_ms.snapshot()
        if tick["count"]:
            out["tick_host_ms_p50"] = float(tick["p50"])
            out["tick_host_ms_p99"] = float(tick["p99"])
            out["tick_host_ms_mean"] = float(tick["mean"])
        gap = self._dispatch_gap_ms.snapshot()
        if gap["count"]:
            out["decode_dispatch_gap_ms_p50"] = float(gap["p50"])
            out["decode_dispatch_gap_ms_p99"] = float(gap["p99"])
            out["decode_dispatch_gap_ms_mean"] = float(gap["mean"])
        wall = self._tick_wall_ms.snapshot()
        if wall["count"]:
            out["tick_wall_ms_p50"] = float(wall["p50"])
            out["tick_wall_ms_mean"] = float(wall["mean"])
            out["tick_prep_ms_p50"] = float(
                self._tick_prep_ms.snapshot()["p50"]
            )
            for kind, hist in self._tick_phase_ms.items():
                phase = hist.snapshot()
                out[f"tick_{kind}_ms_p50"] = float(phase["p50"])
                out[f"tick_{kind}_ms_mean"] = float(phase["mean"])
        for name, hist in self._life_ms.items():
            life = hist.snapshot()
            if life["count"]:
                out[f"{name}_count"] = int(life["count"])
                out[f"{name}_p50"] = float(life["p50"])
                out[f"{name}_p95"] = float(life["p95"])
        for name, hist in self._moe.items():
            moe = hist.snapshot()
            if moe["count"]:
                out[f"{name}_count"] = int(moe["count"])
                out[f"{name}_mean"] = float(moe["mean"])
                out[f"{name}_p50"] = float(moe["p50"])
        with self._lock:
            ready_ms = self._scale_up_ready_ms
        if ready_ms is not None:
            out["scale_up_ready_ms"] = float(ready_ms)
        counters = self._registry.counters()
        hits = counters.get("prefix_hit_blocks", 0)
        misses = counters.get("prefix_miss_blocks", 0)
        if hits + misses:
            out["prefix_hit_rate"] = float(hits / (hits + misses))
        # speculative decode: fraction of draft proposals the target kept
        # (the bonus token is free and not counted on either side)
        proposed = counters.get("spec_proposed", 0)
        if proposed:
            rate = float(counters.get("spec_accepted", 0) / proposed)
            out["spec_acceptance_rate"] = rate
            floor = float(self.spec_min_acceptance or 0.0)
            if floor > 0.0 and rate < floor:
                out["spec_acceptance_below_floor"] = 1.0
                with self._lock:
                    warn = not self._spec_floor_warned
                    self._spec_floor_warned = True
                if warn:
                    logging.getLogger(__name__).warning(
                        "speculative acceptance rate %.1f%% is below the "
                        "configured serving.speculative.min_acceptance "
                        "floor %.1f%% — draft verification is costing "
                        "decode latency, not saving it; disable "
                        "serving.speculative or use a stronger draft",
                        100.0 * rate, 100.0 * floor,
                    )
        # per-adapter (multi-LoRA) views: same shape as the flat latency
        # fields, one set per tenant that retired at least one request
        with self._lock:
            adapter_hists = dict(self._adapter_hists)
        for name, (lat_h, gen_h) in sorted(adapter_hists.items()):
            a_lat = lat_h.snapshot()
            a_gen = gen_h.snapshot()
            if a_lat["count"]:
                pre = self.adapter_name(name, "latency_ms")
                out[f"{pre}_p50"] = float(a_lat["p50"])
                out[f"{pre}_p99"] = float(a_lat["p99"])
                out[f"{pre}_mean"] = float(a_lat["mean"])
            if a_gen["count"]:
                out[self.adapter_name(name, "gen_tokens")] = int(a_gen["sum"])
        # health gauges ride along once record_health has run (absent
        # otherwise, keeping pre-resilience snapshots byte-stable)
        gauges = self._registry.snapshot()["gauges"]
        for name, g in gauges.items():
            out[name] = float(g["value"])
        return out

    def log_summary(self, logger, prefix: str = "serving") -> Dict[str, float]:
        snap = self.snapshot()
        parts = ", ".join(
            f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in sorted(snap.items())
        )
        logger.info("%s metrics: %s", prefix, parts)
        return snap


# --------------------------------------------------------------------- #
# fleet aggregation

# additive fields: exact under summation (counts and token totals; every
# counter key not otherwise classified is summed too)
_AGG_SUM = ("requests", "batches", "items", "gen_tokens")
# distribution fields where the fleet view takes the worst replica: a
# percentile of merged samples cannot be recovered from per-replica
# percentiles, but the MAX is a valid (and operationally honest) bound
_AGG_MAX = (
    "latency_ms_p50", "latency_ms_p99", "max_queue_depth",
    "block_util_max", "kv_transfer_ms_p50", "kv_transfer_ms_p99",
    "tick_host_ms_p50", "tick_host_ms_p99",
    "decode_dispatch_gap_ms_p50", "decode_dispatch_gap_ms_p99",
    "scale_up_ready_ms", "prefill_call_fixed_ms", "prefill_call_ms_per_ktoken",
    "queue_wait_ms_p95", "ttft_ms_p95", "itl_ms_p95", "prefill_stall_ms_p95",
)


def aggregate_snapshots(
    snapshots: Dict[str, Dict[str, float]]
) -> Dict[str, float]:
    """Fold per-replica :meth:`ServingMetrics.snapshot` dicts into one
    fleet view.

    Counts/token totals sum exactly; rates (``items_per_sec``,
    ``*_tokens_per_sec``) sum because the replicas serve concurrently;
    latency percentiles take the max across replicas (a bound, labeled as
    such by keeping the per-replica snapshots alongside); the prefix-cache
    hit rate is recomputed from the summed hit/miss block counters rather
    than averaged, and so is ``decode_overlap_share`` from the dispatch
    counts.  ``health_*``/gauge-like fields are per-replica state
    and are left to the sub-snapshots.
    """
    out: Dict[str, float] = {"replicas": len(snapshots)}
    sums: Dict[str, float] = {}
    maxes: Dict[str, float] = {}
    for snap in snapshots.values():
        for key, val in snap.items():
            if not isinstance(val, (int, float)) or isinstance(val, bool):
                continue
            if key in _AGG_MAX:
                maxes[key] = max(maxes.get(key, val), val)
            elif key.endswith("_per_sec") or key in _AGG_SUM or (
                not key.startswith("health_")
                and not key.endswith(("_mean", "_p50", "_p95", "_p99", "_rate"))
            ):
                sums[key] = sums.get(key, 0) + val
    out.update(sums)
    out.update(maxes)
    hits = sums.get("prefix_hit_blocks", 0)
    misses = sums.get("prefix_miss_blocks", 0)
    if hits + misses:
        out["prefix_hit_rate"] = float(hits / (hits + misses))
    dispatched = sums.get("decode_steps_dispatched", 0)
    if dispatched:
        # a share, like the hit rate: from the summed counts, not summed
        out["decode_overlap_share"] = float(
            sums["decode_steps_overlapped"] / dispatched
        )
    return out
