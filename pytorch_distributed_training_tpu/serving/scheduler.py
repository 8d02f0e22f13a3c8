"""Iteration-level (continuous) batching over the paged KV pool.

The :class:`..serving.batcher.DynamicBatcher` schedules at request-batch
granularity: a batch holds its jit program until every member finishes
decoding, so one long generation stalls the accelerator for the whole
group — the pathology PERF.md's serve bench measures directly.  This
module replaces that with Orca-style iteration-level scheduling (Yu et
al. OSDI'22) over a vLLM-style paged cache (Kwon et al. SOSP'23,
serving/kv_pool.py): the decode loop is a HOST-driven step loop over a
fixed-width slot array, and between single-token steps finished rows are
retired and their slots refilled from the queue with freshly prefilled
requests.  A slot never waits on its neighbors.

Compile count stays bounded by construction, exactly like the batcher
path: every device call has a fixed shape — prefill pads (rows, suffix
tokens) up to the (batch, seq) bucket grid, and the decode step is ONE
[slots, 1] program reused forever (inactive slots ride along with
position -1; their pool scatter drops and their sampled token is ignored
host-side).  Admitting more traffic changes the CONTENT of those arrays,
never their shape.

Degradation composes with PR 3's levers: per-request ``deadline_ms``
expires requests still QUEUED past their deadline (admitted requests run
to completion — retiring mid-flight would waste the blocks already
computed), and ``max_backlog`` sheds with the batcher's
:class:`OverloadedError` after sweeping expired entries out of the depth
accounting.  Counters flow through :class:`ServingMetrics` and are
mirrored into the process telemetry registry (``serving_*``) so the
one-ledger rule holds.

The pool is updated in place: every program that returns the pool takes it
donated (serving/decode.py), so each call site rebinds ``self._pool`` /
``self._draft_pool`` to what the call returned on the line that makes the
call, and nothing here keeps a pool LEAF across a call: exports take
slices, the fault injector and the importer build new leaves
(``tree_map``).  A call that raises after its dispatch can leave the pool
deleted; :meth:`ContinuousScheduler._pool_lost` says so and the supervisor
then rebuilds the pool and replays instead of probing.

Fault tolerance (PR 9, serving/resilience.py): a tick exception no
longer fails the world — :class:`ServingSupervisor` classifies it and
either evicts the one poisoned request (poison-bisect over
``_decode_probe``, or the on-device ``isfinite`` output guard for NaN
emitters) or hot-restarts the engine, rebuilding the compiled programs
and pool and replaying every in-flight request token-identically
(``_replay``; a request's sampling key is a ``uint32 [2]`` row of key data
held on the HOST from ``submit`` on, a call's ``row_keys`` are those rows
in ONE ``numpy`` array beside ``pos`` and ``tables``, and the program folds
each row's token index into its key on the device, so the resample is
bitwise reproducible).  ``drain()`` gives SIGTERM a bounded
graceful shutdown and ``health()`` the readiness/liveness snapshot; an
optional tick watchdog (engine/watchdog.py) turns a hung step into a
diagnosed restart.  The ``serve_*`` kinds in engine/fault.py drive all
of it deterministically.

One decode body (:meth:`ContinuousScheduler._ring_step`): the sampled
token stays ON DEVICE — ``decode_step`` takes its own output back as the
next ``prev_tok``, and the rows whose last token the host holds are spliced
in through ``fresh_mask`` — and a ring of up to ``async_depth`` dispatched
steps is read back behind the dispatch: at depth 1, which is what an engine
serves, tick k dispatches step k and only then reads step k-1, so the device
does not idle through the launch, its own step's return and the host's
bookkeeping every token.  At depth 0 (this constructor's default: the
lock-step tests and ``engine/chaos.py`` tick by hand) the same body reads
each step in the tick that dispatched it: every row is then fresh every
tick.  Host bookkeeping stays exact through per-request ``dispatched``
counters, and the stream is bitwise token-identical at every depth (greedy
and sampled).  Beside a speculative draft a tick runs a round instead
(:meth:`ContinuousScheduler._spec_round`), which has nothing to
pipeline.  Every caller of the ONE ``decode_step`` program — the ring, the
probe, the replay, a draft's steps — takes its host arrays from
``serving/step_inputs.py``.

Where a tick's time goes (PR 24): every tick is a ``tick`` span whose
children are its phases — ``admit``, ``prefill`` (one a CALL: a tick's fresh
admissions run as the calls on the compiled grid whose summed estimated time
is least, :meth:`ContinuousScheduler._prefill_calls`), ``decode_prep``,
``decode_step`` (the dispatch; telemetry/slo.py pairs recoveries with this
kind), ``readback`` (blocked on the sampled tokens), ``deliver`` (push,
``on_token``, retire) — the same kinds from the ring at every depth and from
a speculative round; each phase's milliseconds also land in a histogram of
:class:`ServingMetrics` for every productive tick.  A request is stamped at
submit, first admission, first and every later token, and leaves one
``request`` record on the span ring when it retires; ``queue_wait_ms``,
``ttft_ms``, ``itl_ms`` and ``prefill_stall_ms`` are histograms of the same
stamps.  During a profiler trace the spans are annotations on the host
plane (telemetry/spans.py), so an idle gap of the device reads as the phase
the scheduler was in.

Single-process by design (for now): inputs are handed to jit uncommitted
rather than sharded over the mesh — multi-host serving stays on the
batcher path until the scheduler learns sharded block tables.

Determinism for tests: construct with ``start=False`` and drive
:meth:`tick` by hand — one tick = admit + prefill + one decode step, so a
scripted arrival trace replays bit-identically (at a depth above 0 the
step's tokens are delivered by a LATER tick, and by the tick that finds
nothing left to dispatch).
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..engine import fault
from ..engine.watchdog import StepWatchdog
from ..telemetry.registry import get_registry
from ..telemetry.spans import record as record_span, span
from ..ops.attention import pool_leaf_role, whole_prompt_flash
from ..ops.quant import quantize_tree
from . import kv_transfer
from .batcher import OverloadedError
from .decode import build_paged_fns
from .kv_pool import PagedKVPool
from .metrics import ServingMetrics
from .prefill_plan import LinearCost, bucket_for, plan_calls
from .resilience import HungTickError, PoisonedRequestError, ServingSupervisor
from .speculative import greedy_accept
from .step_inputs import step_inputs

__all__ = ["ContinuousScheduler"]


def _cpu_device():
    """The host's own JAX device, or None (= the default device) in a
    process whose ``JAX_PLATFORMS`` names no CPU backend."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


class _PagedRequest:
    """One request's slot-side state: prompt, reservation, token stream.

    ``row_key`` is the request's sampling key as the scheduler keeps it: the
    key's raw data, a ``numpy`` ``uint32 [2]`` row on the host, made once at
    ``submit`` and copied into a row of every call's ``row_keys``."""

    __slots__ = (
        "prompt", "max_new", "future", "enqueued_at", "deadline",
        "on_token", "row_key", "admission", "slot", "tokens", "poison",
        "adapter", "adapter_name", "draft_admission", "dispatched",
        "rid", "admitted_at", "first_token_at", "last_token_at",
    )

    def __init__(self, prompt, max_new, deadline, on_token, row_key):
        self.prompt = prompt  # 1-D np.int32
        self.max_new = max_new
        self.future: Future = Future()
        self.enqueued_at = time.monotonic()
        self.deadline = deadline  # absolute monotonic, None = forever
        self.on_token = on_token
        self.row_key = row_key
        self.admission = None  # set when a slot admits us
        self.slot = -1
        self.tokens: List[int] = []
        self.poison = None  # fault-injection marker ("raise")
        self.adapter = -1  # LoRA adapter id; -1 = base model
        self.adapter_name: Optional[str] = None
        self.draft_admission = None  # speculative mode: draft-pool blocks
        # the ring: generated tokens DETERMINED so far — drained
        # into ``tokens`` plus steps still in the in-flight ring.  The
        # host derives every dispatch input (position, sampling index)
        # from this counter, so only the token VALUE needs to stay on
        # device.  Invariant: dispatched >= len(tokens); equal whenever
        # the ring holds no step of this row (always, between ticks at
        # depth 0).
        self.dispatched = 0
        # the request's life, stamped by the scheduler on the clock of
        # ``enqueued_at``: first admission, first and latest token pushed
        self.rid = -1
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.last_token_at: Optional[float] = None

    @property
    def gen_idx(self) -> int:
        """Generated-token count so far == index of the NEXT token."""
        return len(self.tokens)


class _PrefillCall(NamedTuple):
    """One call of the target's prefill program in a tick: its rows and the
    point of the compiled grid it runs at."""

    reqs: List["_PagedRequest"]
    suffix: List[int]  # tokens past each row's cached prefix: what is fed
    batch_bucket: int
    seq_bucket: int
    replay: bool = False

    @property
    def padded_tokens(self) -> int:
        """Tokens the call runs, padding of rows and positions included."""
        return self.batch_bucket * self.seq_bucket


class _Phase:
    """One phase of the current tick: a span of ``kind`` whose duration is
    also added to the scheduler's account of this tick (a phase may run
    more than once a tick: the ring's endgame drains several entries)."""

    __slots__ = ("_sched", "_kind", "_span", "_t0")

    def __init__(self, sched, kind, extra):
        self._sched = sched
        self._kind = kind
        self._span = span(kind, step=sched._tick_no, **extra)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        ms = (time.perf_counter() - self._t0) * 1e3
        self._span.__exit__(*exc)
        acct = self._sched._phase_ms
        acct[self._kind] = acct.get(self._kind, 0.0) + ms
        return False


class ContinuousScheduler:
    """Slot array + block pool + host step loop.

    ``submit(prompt)`` returns a future resolved with the batcher-path
    result shape ``{"tokens": int32 [gen_len], "gen_len": int}``; the
    optional ``on_token`` callback streams each token the moment the host
    sees it (called on the scheduler thread — keep it cheap).
    """

    def __init__(
        self,
        model,
        params,
        *,
        slots: int = 8,
        block_size: int = 16,
        num_blocks: int = 64,
        prefix_cache: bool = True,
        batch_buckets: Sequence[int],
        seq_buckets: Sequence[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        max_backlog: Optional[int] = None,
        metrics: Optional[ServingMetrics] = None,
        seed: int = 0,
        pool_sharding=None,
        resilience: Optional[Dict[str, Any]] = None,
        quant: bool = False,
        lora=None,
        speculative=None,
        async_depth: int = 0,
        logger: Optional[logging.Logger] = None,
        start: bool = True,
        replica_id: Optional[int] = None,
        heartbeat_path: Optional[str] = None,
        heartbeat_interval_s: float = 0.5,
        liveness_timeout_s: Optional[float] = None,
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if max_backlog is not None and max_backlog < 1:
            raise ValueError(f"max_backlog must be >= 1, got {max_backlog}")
        self.slots_n = int(slots)
        self.batch_buckets = sorted(set(int(b) for b in batch_buckets))
        self.seq_buckets = sorted(set(int(s) for s in seq_buckets))
        if not self.batch_buckets or not self.seq_buckets:
            raise ValueError("scheduler needs batch_buckets and seq_buckets")
        self.max_new_tokens = int(max_new_tokens)
        worst = self.seq_buckets[-1] + self.max_new_tokens
        if worst > model.max_len:
            raise ValueError(
                f"largest seq bucket {self.seq_buckets[-1]} + max_new_tokens "
                f"{self.max_new_tokens} = {worst} exceeds model max_len "
                f"{model.max_len}"
            )
        self.eos_id = eos_id
        self.deadline_ms = deadline_ms
        self.max_backlog = max_backlog
        self.logger = logger or logging.getLogger(__name__)
        self.metrics = metrics or ServingMetrics(replica_id)
        # fleet identity + external liveness (PR 12, serving/router.py):
        # the heartbeat file's mtime is this replica's liveness clock for
        # observers OUTSIDE the process/thread — the scheduler thread
        # itself touches it (tick + idle wakeups), deliberately not a
        # side thread, so a wedged scheduler goes stale instead of being
        # masked by a healthy beater.
        self.replica_id = replica_id
        self.heartbeat_path = heartbeat_path
        if heartbeat_interval_s <= 0:
            raise ValueError(
                f"heartbeat_interval_s must be > 0, got {heartbeat_interval_s}"
            )
        self._hb_interval = float(heartbeat_interval_s)
        self._liveness_timeout_s = (
            float(liveness_timeout_s) if liveness_timeout_s is not None
            else None
        )
        if self._liveness_timeout_s is not None and self._liveness_timeout_s <= 0:
            raise ValueError(
                f"liveness_timeout_s must be > 0, got {liveness_timeout_s}"
            )
        self._last_beat = 0.0  # scheduler-thread confined (+ constructor)

        # kept for hot-restart: _rebuild_and_requeue reconstructs the
        # compiled programs and the pool from the same ingredients
        self._model = model
        self._temperature = float(temperature)
        self._block_size = int(block_size)
        self._num_blocks = int(num_blocks)
        self._prefix_cache = bool(prefix_cache)
        self._pool_sharding = pool_sharding

        # multi-tenant decode modes (PR 17), each default-off:
        #   quant — decode programs take the int8 tree (ops/quant.py);
        #   lora — LoraRegistry: per-row adapter selection over a model
        #     already cloned/grafted with stacked factors (engine's job);
        #   speculative — SpeculativeSpec: draft-proposed, target-verified
        #     rounds over a SECOND paged pool for the draft.
        self._quant = bool(quant)
        self._lora = lora
        self._spec = speculative
        self._has_lora = getattr(model, "lora_adapters", 0) > 0
        # (expert layers, experts a token, experts) of a model that has
        # expert layers, as the model states it; None for any other
        self._moe_shape = getattr(model, "moe_shape", None)
        # a model that carries a fixed-size state a sequence says so: its
        # cache tree holds [slots, ...] leaves beside the pool's rows and
        # every program takes ``state_rows`` (serving/decode.py).  What
        # assumes that a cache is blocks of token rows refuses it here, with
        # the reason, rather than serve something else in silence.
        self._state_shape = getattr(model, "state_shape", None)
        # (window layers, window, full layers) of a model some of whose
        # layers read a row's newest ``window`` positions only, from a ring
        # a slot (one kind of slot-addressed leaf); None for any other
        self._window_shape = getattr(model, "window_shape", None)
        if self._state_shape is not None:
            if prefix_cache:
                raise ValueError(self._state_refusal(
                    "serving.scheduler.prefix_cache", "a block's hash names "
                    "its token rows and cannot restore the state the layers "
                    "reached at the block's end (set prefix_cache: false)"))
            if speculative is not None:
                raise ValueError(self._state_refusal(
                    "serving.speculative", "a rejected draft token would "
                    "have to be taken back out of the state, and the fork "
                    "copies pool rows, not states"))
        if self._lora is not None and not self._has_lora:
            raise ValueError(
                "a LoRA registry was given but the model has no stacked "
                "factors — pass the registry's grafted (model, params) pair"
            )
        if self._spec is not None and self._temperature != 0.0:
            raise ValueError(
                "speculative decoding requires temperature 0.0: the greedy "
                "accept rule is exact only against the argmax stream (the "
                "sampled accept rule is serving/speculative.py's "
                "sampled_accept, not yet wired to the scheduler)"
            )
        # how many dispatched steps the ring may hold unread (_ring_step).
        # 0 = a step is read in the tick that dispatched it; N >= 1 = it is
        # read up to N ticks later, the sampled token fed back ON DEVICE
        # meanwhile (decode_step's prev_tok), so the accelerator never waits
        # out the host's per-token bookkeeping.  An engine serves depth 1
        # (serving/engine.py); ticking by hand in lock step wants 0.
        self._async_depth = int(async_depth)
        if self._async_depth < 0:
            raise ValueError(
                f"async_depth must be >= 0, got {async_depth}"
            )
        if self._async_depth and self._spec is not None:
            raise ValueError(
                "async_depth and speculative decoding are mutually "
                "exclusive: a speculative round's host accept/reject "
                "must observe every verify result before the next round "
                "can be proposed, so there is nothing to pipeline"
            )
        # speculative branch forking reserves ONE private spare block per
        # request on top of its footprint (the CoW target for the
        # boundary block each round)
        self._extra_blocks = 1 if self._spec is not None else 0

        self._kv = PagedKVPool(num_blocks, block_size, prefix_cache)
        # every block table is padded to the worst-case footprint so the
        # decode program's shape never depends on a request's length
        self.table_blocks = self._kv.blocks_needed(
            self.seq_buckets[-1], self.max_new_tokens
        )
        if self.table_blocks + self._extra_blocks > self._kv.num_blocks:
            raise ValueError(
                f"worst-case request needs "
                f"{self.table_blocks + self._extra_blocks} blocks but "
                f"num_blocks is {self._kv.num_blocks}; grow the pool or "
                "shrink seq_buckets/max_new_tokens"
            )
        self._fns = self._build_fns()
        self.params = params
        # decode programs stream the int8 tree in quant mode; prefill and
        # verify always take the plain tree (compute-bound / accuracy
        # anchor respectively — see ops/quant.py)
        self._qparams = quantize_tree(params) if self._quant else None
        self._pool = self._fns.init_pool(params)
        if pool_sharding is not None:
            # land the initial pool under the same sharding jit will give
            # the UPDATED pool, or the second call of each prefill shape
            # recompiles for the sharding change (engine passes the mesh's
            # replicated sharding; plain single-device use needs nothing)
            self._pool = jax.device_put(self._pool, pool_sharding)
        self._draft_fns = None
        self._draft_pool = None
        self._dkv: Optional[PagedKVPool] = None
        if self._spec is not None:
            # self-draft (no dedicated draft model) = draft IS the target:
            # acceptance pins at 1.0, the end-to-end exactness test
            self._draft_model = (
                self._spec.draft_model
                if self._spec.draft_model is not None else model
            )
            self._draft_params = (
                self._spec.draft_params
                if self._spec.draft_params is not None else params
            )
            self._draft_lora = (
                getattr(self._draft_model, "lora_adapters", 0) > 0
            )
            self._build_draft()
        # sampling keys are host rows of key data (see _PagedRequest); they
        # are made on the CPU backend, where the process has one, so that a
        # submit neither waits behind the device's step nor dispatches to it
        # (jitted: the bare function spends 0.4 ms in python wrappers a call)
        self._key_device = _cpu_device()
        self._fold_in = jax.jit(jax.random.fold_in)
        with jax.default_device(self._key_device):
            self._pad_key = np.asarray(jax.random.PRNGKey(0))
            self._base_rng = np.asarray(jax.random.PRNGKey(int(seed)))
        self._seq_no = 0  # guarded by: self._cond
        self._req_no = 0  # guarded by: self._cond

        # _slots is the scheduler thread's working set: only _admit /
        # _fail_inflight / drain touch it cross-thread, and they take the
        # condition; per-iteration reads/writes in the loop body stay
        # lock-free by thread confinement (see module docstring).
        self._slots: List[Optional[_PagedRequest]] = [None] * self.slots_n  # confined: _loop
        self._queue: "deque[_PagedRequest]" = deque()  # guarded by: self._cond
        # cross-replica KV transfer verbs (serving/kv_transfer.py):
        # foreign threads enqueue export/import requests here and the
        # scheduler thread services them at its next tick boundary, so
        # pool reads and scatters keep their single-thread confinement
        self._xfer_q: deque = deque()  # guarded by: self._cond
        self._cond = threading.Condition()
        self._closed = False  # guarded by: self._cond
        self._draining = False  # guarded by: self._cond
        self._drain_deadline: Optional[float] = None  # guarded by: self._cond
        self._last_tick: Optional[float] = None  # guarded by: self._cond
        self._hang_info = None  # guarded by: self._cond
        # fleet kill/hang switches (hard_kill / inject_hang set them from
        # the router's monitor thread; the scheduler thread processes
        # them at its next tick boundary so slot/pool mutation stays
        # thread-confined)
        self._die_exc: Optional[BaseException] = None  # guarded by: self._cond
        self._dead = False  # guarded by: self._cond
        self._hang_sec: Optional[float] = None  # guarded by: self._cond
        self._tick_started_at: Optional[float] = None  # guarded by: self._cond
        # prefix-cache block tallies for the registry gauges (tick-thread
        # reads; _admit writes under the condition it already holds)
        self._hit_blocks = 0
        self._miss_blocks = 0

        # tick-thread-confined recovery state (supervisor runs inside
        # tick's except clause, on the same thread); health/_on_tick_hang
        # read them cross-thread as best-effort diagnostics
        self._tick_no = 0  # confined: _loop
        self._tick_phase = ""  # confined: _loop
        # this tick's milliseconds by phase kind (see _Phase)
        self._phase_ms: Dict[str, float] = {}  # confined: _loop

        # the ring's state (all confined: _loop).  _inflight holds
        # (tok_dev, finite_dev, rows, moe, dispatching tick) per
        # dispatched-but-undrained step; _carry_tok is the LAST dispatch's
        # on-device token vector — the next step's prev_tok input.
        # _last_dispatch feeds decode_dispatch_gap_ms; _tick_block_s is a
        # fresh prefill's own read, which tick() takes out of tick_host_ms
        # beside the readback phase.
        self._inflight: deque = deque()  # confined: _loop
        self._carry_tok = None  # confined: _loop
        # the prev_tok of a call that carries nothing — the ring's first
        # dispatch, the probe, the replay, a draft's steps: zeros on the
        # device (_zero_carry, made once)
        self._zero_tok = None  # confined: _loop
        # (tick_no, perf_counter) of the latest decode dispatch
        self._last_dispatch: Optional[tuple] = None  # confined: _loop
        self._tick_block_s = 0.0  # confined: _loop
        # what a prefill call is estimated to cost (set_prefill_cost, from
        # the warm-up's thread); None = never measured: a tick's fresh
        # admissions are one call
        self._prefill_cost: Optional[LinearCost] = None  # guarded by: self._cond

        res = dict(resilience or {})
        wd = dict(res.pop("watchdog", None) or {})
        self.drain_deadline_ms = res.pop("drain_deadline_ms", None)
        if self.drain_deadline_ms is not None:
            self.drain_deadline_ms = float(self.drain_deadline_ms)
            if self.drain_deadline_ms <= 0:
                raise ValueError(
                    f"drain_deadline_ms must be > 0, got {self.drain_deadline_ms}"
                )
        # a bisect probe re-drives a decode step: idempotent for pool rows,
        # but it would apply a step's update to a STATE twice.  With a state
        # a decode failure among several requests goes to the restart, whose
        # replay rebuilds every state from position 0.
        self._supervisor = ServingSupervisor(
            self,
            max_restarts=int(res.pop("max_restarts", 2)),
            poison_bisect=(
                bool(res.pop("poison_bisect", True))
                and self._state_shape is None
            ),
            logger=self.logger,
        )
        if res:
            raise ValueError(f"unknown serving.resilience keys: {sorted(res)}")
        wd_enabled = bool(wd.pop("enabled", False))
        wd_factor = float(wd.pop("factor", 10.0))
        wd_min_seconds = float(wd.pop("min_seconds", 60.0))
        wd_warmup = int(wd.pop("warmup", 3))
        wd_poll = wd.pop("poll_seconds", None)
        if wd:
            raise ValueError(
                f"unknown serving.resilience.watchdog keys: {sorted(wd)}"
            )
        self._watchdog: Optional[StepWatchdog] = None
        if wd_enabled:
            self._watchdog = StepWatchdog(
                factor=wd_factor,
                min_seconds=wd_min_seconds,
                warmup=wd_warmup,
                poll_seconds=wd_poll,
                on_hang=self._on_tick_hang,
                logger=self.logger,
            )

        self._beat(force=True)  # exists-from-birth: no startup-grace races
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(
                target=self._loop, name="serving-scheduler", daemon=True
            )
            self._thread.start()

    def _build_fns(self):
        return build_paged_fns(
            self._model, self._block_size, self._num_blocks,
            temperature=self._temperature, quant=self._quant,
            state_slots=self.slots_n if self._state_shape is not None else 0,
        )

    def _state_refusal(self, what: str, why: str) -> str:
        return (
            f"{what} cannot serve {type(self._model).__name__}: the model "
            f"carries a fixed-size state a sequence (state_shape "
            f"{self._state_shape}) beside its pool rows, and {why}"
        )

    def _state_rows(self, slots) -> tuple:
        """The paged programs' last argument, ``(state_rows,)``, for a call
        whose batch row ``i`` belongs to slot ``slots[i]`` (-1 = padding);
        ``()`` for a model that carries no state."""
        if self._state_shape is None:
            return ()
        return (np.asarray(slots, np.int32),)

    def _slot_rows(self, pos) -> tuple:
        """:meth:`_state_rows` of a fixed-width decode call: row ``i`` is
        slot ``i`` where it is live (``pos[i] >= 0``)."""
        return self._state_rows(
            np.where(pos >= 0, np.arange(self.slots_n), -1))

    def _live_block_share(self, pos) -> float:
        """Of a fixed-width decode call's ``slots x table_blocks`` table
        entries, the share whose block holds a position the step reads
        (``pos[i]`` and all before it; a padding row reads none)."""
        bs = self._kv.block_size
        live = np.where(pos >= 0, pos // bs + 1, 0).sum()
        return float(live) / (self.slots_n * self.table_blocks)

    def _state_live_row_share(self, pos):
        """Of a fixed-width decode call's slots, the share whose row lives:
        what the step reads and writes of a ``[slots, ...]`` state leaf;
        ``None`` for a model that carries no state."""
        if self._state_shape is None:
            return None
        return float((pos >= 0).sum()) / self.slots_n

    def _window_reads(self, pos) -> dict:
        """What a fixed-width decode call's live rows read of their history,
        as fields of its ``decode_step`` span: ``window_keys``, the positions
        a WINDOW layer reads (``min(length, window)`` a row), and
        ``full_keys``, those a full layer reads (every one).  ``{}`` for a
        model with no window layer."""
        if self._window_shape is None:
            return {}
        lengths = pos[pos >= 0].astype(np.int64) + 1
        return dict(
            window_keys=int(np.minimum(lengths, self._window_shape[1]).sum()),
            full_keys=int(lengths.sum()),
        )

    def _refuse_a_piece(self, positions: np.ndarray) -> None:
        """A model that carries a state a sequence is prefilled a whole
        prompt a call: its layers take a multi-token call's column for the
        position (a state's scan starts from the slot's zero state at column
        0, a window layer's band and ring write count from it, and a full
        layer may score the call's own keys and read no table:
        ``ops/attention.py::paged_attention``, ``whole_prompts``).  The
        scheduler owns what a call holds, so it refuses here a call that
        starts anywhere else (a prefix hit's suffix, a prefill in pieces)
        rather than let the layers read other keys in silence."""
        if self._state_shape is not None and (positions[:, 0] > 0).any():
            raise ValueError(self._state_refusal(
                f"a prefill call whose rows start at {positions[:, 0].tolist()}",
                "its layers take a call's column for the position: "
                "a prompt is prefilled whole, from position 0"))

    def _flash_layers(self, seq_bucket: int) -> int:
        """Of a prefill call's attention layers, those that score through
        the causal flash forward over the call's own K/V and read no table
        (``ops/attention.py::whole_prompt_flash``, the rule the layers
        themselves go by): the cache tree's K pools (one a full-attention
        layer) where the model carries a state, so that its calls hold whole
        prompts, and the call's shape is one the kernel takes on this
        backend.  Static a program."""
        n_rows = self._kv.num_blocks * self._kv.block_size
        return sum(
            whole_prompt_flash(
                self._state_shape is not None, seq_bucket, leaf.shape[-1])
            for path, leaf in jax.tree_util.tree_flatten_with_path(self._pool)[0]
            if leaf.ndim == 3 and pool_leaf_role(path, leaf, n_rows) == "scored")

    def _pad_keys(self, n: int) -> np.ndarray:
        """The ``row_keys`` argument of a paged call of ``n`` batch rows,
        every row the pad key: ONE host ``uint32 [n, 2]`` array, which the
        caller overwrites at its live rows with ``req.row_key``."""
        return np.tile(self._pad_key, (n, 1))

    def _key_row(self, rng) -> np.ndarray:
        """A caller's sampling key (a typed key, or key data on the device
        or the host) as the host row :class:`_PagedRequest` keeps."""
        dtype = getattr(rng, "dtype", None)
        if dtype is not None and jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key):
            rng = jax.random.key_data(rng)
        row = np.asarray(rng, np.uint32)
        if row.shape != self._pad_key.shape:
            raise ValueError(
                f"rng must be ONE sampling key, uint32 {self._pad_key.shape} "
                f"of key data; got shape {row.shape}"
            )
        return row

    # ------------------------------------------------------------------ #
    # client side

    def submit(
        self,
        prompt,
        deadline_ms: Optional[float] = None,
        max_new_tokens: Optional[int] = None,
        on_token: Optional[Callable[[int], None]] = None,
        rng=None,
        replay_tokens: Optional[Sequence[int]] = None,
        adapter: Optional[str] = None,
    ) -> Future:
        """Enqueue one prompt; the future resolves at retirement.

        ``adapter`` names a registered LoRA adapter (serving.lora): this
        request decodes through that adapter's low-rank delta, batched in
        the SAME iteration as every other tenant's rows; None = the base
        model.  Requires the engine's LoRA registry.

        ``max_new_tokens`` caps THIS request below the scheduler-wide
        budget (its slot retires early instead of padding the batch with
        dead decode steps — the whole point of iteration-level
        scheduling); ``rng`` overrides the request's sampling key (a
        PRNGKey, typed or as ``uint32 [2]`` key data, on the device or the
        host) so tests can replay the whole-batch path row for row.

        ``replay_tokens`` pre-populates the request's generated stream:
        admission takes the hot-restart replay path (``_replay``) instead
        of a fresh prefill, re-deriving the KV state for those tokens
        through the same decode program and verifying each one against
        the stream bit-for-bit — WITHOUT refiring ``on_token`` for them.
        This is how the fleet router fails a half-generated request over
        from a dead replica to a survivor token-identically; pass the
        exact ``rng`` the original submission used or the continuation
        diverges.
        """
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(
                f"prompt must be a non-empty 1-D token sequence, got shape "
                f"{prompt.shape}"
            )
        if prompt.size > self.seq_buckets[-1]:
            raise ValueError(
                f"prompt length {prompt.size} exceeds largest seq bucket "
                f"{self.seq_buckets[-1]}"
            )
        mnt = self.max_new_tokens if max_new_tokens is None else int(max_new_tokens)
        if not 1 <= mnt <= self.max_new_tokens:
            raise ValueError(
                f"max_new_tokens must be in [1, {self.max_new_tokens}], got {mnt}"
            )
        dl = deadline_ms if deadline_ms is not None else self.deadline_ms
        if dl is not None and dl <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {dl}")
        aid = -1
        if adapter is not None:
            if self._lora is None:
                raise ValueError(
                    "adapter= requires serving.lora.enabled (no adapter "
                    "registry on this engine)"
                )
            aid = self._lora.id_of(adapter)
        replay = [int(t) for t in replay_tokens] if replay_tokens else []
        if replay:
            if rng is None:
                raise ValueError(
                    "replay_tokens needs the ORIGINAL submission's rng — "
                    "a fresh key would resample a different stream and "
                    "every replayed token would flag replay_parity_mismatch"
                )
            if len(replay) >= mnt:
                raise ValueError(
                    f"replay_tokens ({len(replay)}) must be shorter than "
                    f"max_new_tokens ({mnt}); a fully-generated request "
                    "has nothing left to decode"
                )
        # a caller's key is read to the host before the lock is taken
        row_key = None if rng is None else self._key_row(rng)
        with self._cond:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if self._draining:
                raise RuntimeError(
                    "scheduler is draining; not accepting new requests"
                )
            # sweep expired entries BEFORE the backlog check so live
            # requests are never shed to protect doomed ones (the
            # DynamicBatcher bug this PR also fixes)
            self._sweep_expired_locked()
            if (
                self.max_backlog is not None
                and len(self._queue) >= self.max_backlog
            ):
                self._bump("sheds")
                raise OverloadedError(
                    f"serving backlog full ({self.max_backlog} waiting); "
                    "request shed"
                )
            if row_key is None:
                with jax.default_device(self._key_device):
                    row_key = np.asarray(
                        self._fold_in(self._base_rng, self._seq_no)
                    )
                self._seq_no += 1
            req = _PagedRequest(
                prompt, mnt,
                deadline=(time.monotonic() + dl / 1000.0) if dl else None,
                on_token=on_token, row_key=row_key,
            )
            req.rid = self._req_no
            self._req_no += 1
            req.adapter = aid
            req.adapter_name = adapter
            if replay:
                req.tokens = replay
                req.dispatched = len(replay)
            self._queue.append(req)
            self.metrics.observe_depth(len(self._queue))
            self._cond.notify_all()
        return req.future

    def depth(self) -> int:
        """Requests queued but not yet admitted to a slot."""
        with self._cond:
            return len(self._queue)

    def active(self) -> int:
        """Slots currently decoding.

        Takes the condition: callers poll this from foreign threads, and
        an unlocked read races _fail_inflight's wholesale rebind of the
        slot list (it could observe retired requests as still active).
        """
        with self._cond:
            return sum(1 for s in self._slots if s is not None)

    def require_idle(self) -> None:
        """Raise while the scheduler holds work.  The engine's warm-up asks
        before it consumes the pool from its caller's thread: a tick
        beside it would find the pool it reads already donated."""
        with self._cond:
            busy = (
                bool(self._queue) or bool(self._xfer_q)
                or any(s is not None for s in self._slots)
            )
        if busy:
            raise RuntimeError(
                "the warm-up consumes the scheduler's pool and cannot run "
                "beside queued or in-flight requests: call it before traffic"
            )

    def _pool_lost(self) -> bool:
        """True once a leaf of the pool (or of the draft's) is deleted: a
        donating program raised AFTER it took the pool, so no program can
        run until :meth:`_rebuild_and_requeue` has made a new one."""
        pools = (self._pool, self._draft_pool)
        return any(
            leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(pools)
        )

    def compile_count(self) -> int:
        """Distinct XLA programs compiled so far: bounded by the prefill
        bucket grid + ONE program each for decode/verify/copy (per model —
        the speculative draft has its own set), whatever traffic does."""
        n = self._fns._cache_size()
        if self._draft_fns is not None:
            n += self._draft_fns._cache_size()
        return n

    def drain(self, deadline_ms: Optional[float] = None) -> float:
        """Graceful shutdown: stop admitting NEW submissions, finish the
        queued + in-flight work, then close.  Returns wall ms spent.

        Past ``deadline_ms`` (default ``resilience.drain_deadline_ms``;
        None = unbounded) the next tick fails the remaining requests with
        ``TimeoutError`` and the drain completes — bounded, like every
        other recovery path.  Safe from any thread; idempotent.
        """
        t0 = time.monotonic()
        dl = deadline_ms if deadline_ms is not None else self.drain_deadline_ms
        with self._cond:
            if self._closed:
                return 0.0
            self._draining = True
            if dl is not None:
                self._drain_deadline = t0 + dl / 1000.0
            self._cond.notify_all()
        if self._thread is None:
            while self.tick():
                pass
        else:
            with self._cond:
                while not self._closed and (
                    self._queue or any(s is not None for s in self._slots)
                ):
                    # the loop thread does the work (and enforces the
                    # deadline inside tick); this is just a progress watch
                    self._cond.wait(timeout=0.01)
        self.close()
        return (time.monotonic() - t0) * 1000.0

    def health(self) -> Dict[str, Any]:
        """Readiness/liveness snapshot for orchestration probes.

        ``ready`` = accepting submissions; ``live`` = worth keeping the
        process (False once the restart budget is exhausted, the replica
        is hard-killed, or — with ``liveness_timeout_s`` set — the
        scheduler thread has made no Python progress for that long while
        it HAD work, i.e. it is wedged inside a tick or a device call;
        idle-with-nothing-to-do never counts as stalled).  Mirrored into
        :class:`ServingMetrics` gauges (``health_*``) so one metrics
        snapshot carries health alongside latency/throughput.
        """
        now = time.monotonic()
        with self._cond:
            depth = len(self._queue)
            active = sum(1 for s in self._slots if s is not None)
            closed = self._closed
            draining = self._draining
            last = self._last_tick
            started = self._tick_started_at
            dead = self._dead
        restarts = self._supervisor.restarts()
        exhausted = self._supervisor.exhausted()
        stalled = False
        if self._liveness_timeout_s is not None:
            # a tick in progress counts as busy from its START stamp (a
            # hung device call never updates _last_tick); otherwise only
            # pending work makes an old tick suspicious — an idle healthy
            # replica legitimately stops ticking
            busy = started is not None or depth > 0 or active > 0
            ref = started if started is not None else last
            if busy and ref is not None:
                stalled = (now - ref) > self._liveness_timeout_s
        snap = {
            "ready": not (closed or draining or exhausted or dead or stalled),
            "live": not (exhausted or dead or stalled),
            "stalled": stalled,
            "queue_depth": depth,
            "active_slots": active,
            "slots": self.slots_n,
            "engine_restarts": restarts,
            "restart_budget": self._supervisor.max_restarts,
            "last_tick_age_s": (now - last) if last is not None else None,
            "draining": draining,
            "closed": closed,
        }
        self.metrics.record_health(snap)
        return snap

    def hard_kill(self, exc: BaseException) -> None:
        """Fleet-level kill switch: fail this whole replica with ``exc``.

        Safe from ANY thread (the fleet router's monitor calls it): only
        a flag is set here; the scheduler thread processes the death at
        its next tick boundary, so slot/pool mutation keeps its
        single-thread contract.  Every queued and in-flight request fails
        with ``exc`` (the router fails them over to a survivor) and the
        scheduler closes.  Idempotent; a no-op after a clean close.
        """
        with self._cond:
            if self._closed or self._die_exc is not None:
                return
            self._die_exc = exc
            self._cond.notify_all()

    def inject_hang(self, seconds: float) -> None:
        """Wedge the scheduler thread for ``seconds`` at its next tick
        boundary (the ``replica_hang`` fault hook): no Python progress,
        no heartbeat — only an OUTSIDE observer reading the heartbeat
        file's age (or ``health()``'s liveness clock) can see it, which
        is exactly what the router's staleness detection must prove."""
        with self._cond:
            if self._closed:
                return
            self._hang_sec = float(seconds)
            self._cond.notify_all()

    def export_kv_prefix(
        self,
        prompt: Sequence[int],
        namespace=None,
        stall_s: Optional[float] = None,
    ) -> Future:
        """Stage ``prompt``'s cached prefix blocks for transfer (any thread).

        Resolves to a list of CRC-sealed :class:`kv_transfer.BlockPayload`
        — possibly empty when nothing is cached.  The host-side gather
        runs on the scheduler thread at its next tick boundary, so the
        pool is quiescent for the copy.  ``stall_s`` is the
        ``kv_transfer_stall`` fault hook: the SOURCE side sleeps before
        resolving, so the importing coordinator's bounded deadline is
        exercised against a genuinely late payload.
        """
        self._refuse_kv_transfer()
        fut: Future = Future()
        arr = np.asarray(prompt, dtype=np.int32).reshape(-1)
        with self._cond:
            if self._closed or self._dead:
                raise RuntimeError("cannot export KV from a closed scheduler")
            self._xfer_q.append(("export", (arr, namespace, stall_s), fut))
            self._cond.notify_all()
        return fut

    def export_kv_refs(
        self,
        prompt: Sequence[int],
        namespace=None,
        stall_s: Optional[float] = None,
    ) -> Future:
        """Stage ``prompt``'s cached prefix blocks as LAZY refs (any thread).

        Resolves to a list of :class:`kv_transfer.BlockRef` — the cheap
        half of an export.  Only the device slice dispatch runs on the
        scheduler thread; the caller materializes the refs into
        CRC-sealed payloads (``kv_transfer.materialize_payloads``) on its
        own executor, keeping the device→host copies and checksum work
        off the scheduler loop entirely.  This is the disaggregated
        transfer path's verb; :meth:`export_kv_prefix` keeps the one-shot
        payload contract.
        """
        self._refuse_kv_transfer()
        fut: Future = Future()
        arr = np.asarray(prompt, dtype=np.int32).reshape(-1)
        with self._cond:
            if self._closed or self._dead:
                raise RuntimeError("cannot export KV from a closed scheduler")
            self._xfer_q.append(("export_refs", (arr, namespace, stall_s), fut))
            self._cond.notify_all()
        return fut

    def _refuse_kv_transfer(self) -> None:
        """The transfer verbs move blocks of token rows between prefix
        caches; a model that carries a state has no prefix cache to put
        them in (``serving/disagg.py`` asks at construction)."""
        if self._state_shape is not None:
            raise ValueError(self._state_refusal(
                "kv_transfer / serving.disagg", "a transferred block "
                "carries token rows, not the state the layers reached at "
                "its end"))

    def import_kv_blocks(self, payloads) -> Future:
        """Adopt transferred blocks into the local prefix cache (any thread).

        Resolves to ``{"accepted", "rejected", "bytes"}``.  Per payload,
        in chain order: a checksum mismatch rejects the block AND stops
        the chain (descendants of a corrupt link would be unreachable),
        an already-cached key is skipped (first-writer-wins — a local
        prefill beat the transfer), a full pool stops the chain.  Bad
        payloads never raise: rejection is an accounted, recoverable
        event (``kv_transfer_rejects``) and the decode side simply
        recomputes whatever did not land.
        """
        self._refuse_kv_transfer()
        fut: Future = Future()
        with self._cond:
            if self._closed or self._dead:
                raise RuntimeError("cannot import KV into a closed scheduler")
            self._xfer_q.append(("import", list(payloads), fut))
            self._cond.notify_all()
        return fut

    def close(self) -> None:
        """Drain queue and in-flight slots, then stop the loop."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
        else:
            # test mode (start=False): drain synchronously
            while self.tick():
                pass
        if self._watchdog is not None:
            self._watchdog.close()
        self._report_unfired_faults()

    def _report_unfired_faults(self) -> None:
        """Account injected serve-side faults still armed at close.

        A one-shot fault scheduled for a tick this engine never reached
        (drain deadline expired first, queue emptied early) would otherwise
        vanish silently — the chaos oracle then mis-reads the scenario as
        "fault recovered" when it never fired.  Count and log each leftover
        so every injected fault ends the scenario as exactly one of
        fired-and-recovered or reported-unfired.
        """
        pending = fault.get_injector().pending()
        for kind, steps in pending.items():
            if not (kind.startswith("serve_") or kind.startswith("replica_")):
                continue
            fault.bump(f"fault_unfired_{kind}", len(steps))
            logging.getLogger(__name__).warning(
                "scheduler closed with injected %s fault(s) still armed for "
                "tick(s) %s — the engine never reached them", kind, steps,
            )

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------ #
    # scheduler side — everything below runs on ONE thread (the loop, or
    # the test driving tick() by hand), which is what lets kv_pool.py go
    # lock-free

    def tick(self) -> bool:
        """One scheduler iteration: admit+prefill, then one decode step.

        Returns True if any work happened (the synchronous drain in
        ``close`` loops on it).  A failing tick is handed to the
        supervisor, which evicts the poisoned request or hot-restarts —
        the caller never sees the exception unless recovery itself dies.
        """
        with self._cond:
            self._tick_started_at = time.monotonic()
            die = self._die_exc
            hang, self._hang_sec = self._hang_sec, None
        if hang is not None:
            # simulated wedge: sleep BEFORE the heartbeat touch so the
            # file goes stale exactly like a real stuck device call
            self.logger.warning(
                "fault injection: replica scheduler wedged for %.2fs", hang
            )
            time.sleep(hang)
        if die is not None:
            try:
                self._die(die)
            finally:
                with self._cond:
                    self._tick_started_at = None
            return True
        self._beat()
        self._tick_no += 1
        self._tick_phase = "setup"
        if self._watchdog is not None:
            self._watchdog.step_started(self._tick_no)
        try:
            try:
                # tick_host_ms = tick wall minus time BLOCKED on device
                # readbacks: the decode body's ``readback`` phase, and the
                # fresh prefill's own read, which no phase times alone and
                # _prefill_fresh adds to _tick_block_s — the host-overhead
                # number the ring exists to hide
                self._tick_block_s = 0.0
                self._phase_ms = {}
                t_tick0 = time.perf_counter()
                with span("tick", step=self._tick_no):
                    did = self._tick_inner()
                if did:
                    wall_ms = (time.perf_counter() - t_tick0) * 1000.0
                    blocked_ms = (
                        self._phase_ms.get("readback", 0.0)
                        + self._tick_block_s * 1000.0
                    )
                    self.metrics.record_tick(max(wall_ms - blocked_ms, 0.0))
                    self.metrics.record_tick_phases(wall_ms, self._phase_ms)
            finally:
                if self._watchdog is not None:
                    self._watchdog.step_finished()
                with self._cond:
                    self._last_tick = time.monotonic()
                    self._tick_started_at = None
            with self._cond:
                hang, self._hang_info = self._hang_info, None
            if hang is not None and hang[0] == self._tick_no:
                raise HungTickError(
                    f"scheduler tick {hang[0]} ran {hang[1]:.2f}s "
                    f"(watchdog limit {hang[2]:.2f}s)"
                )
            return did
        except Exception as exc:
            self.logger.exception(
                "scheduler tick %d failed in phase %r; invoking supervisor",
                self._tick_no, self._tick_phase,
            )
            # settle the in-flight dispatch ring BEFORE recovery.  The
            # supervisor's bisect probes and replays assume every row's
            # last token on the host, and a step that was merely in
            # flight when an unrelated row poisoned the tick must not
            # confound attribution.  Nothing to settle at depth 0.
            self.flush_async()
            return self._supervisor.handle_tick_failure(exc)

    def _tick_inner(self) -> bool:
        with self._cond:
            expired = (
                self._draining
                and self._drain_deadline is not None
                and time.monotonic() >= self._drain_deadline
                and (
                    bool(self._queue)
                    or any(s is not None for s in self._slots)
                )
            )
        if expired:
            self._bump("drain_expired")
            self._fail_inflight(
                TimeoutError(
                    "graceful drain exceeded its deadline; failing the "
                    "remaining requests"
                )
            )
            return True
        self._tick_phase = "kv_transfer"
        did_xfer = self._service_kv_transfers()
        self._tick_phase = "admit"
        with self._phase("admit"):
            newly = self._admit()
        self._tick_phase = "prefill"
        if newly:
            # the rows already decoding sit through this tick's prefills:
            # each of their next gaps has them inside
            decoding = self.active() - len(newly)
            self._prefill(newly, decoding)
            if decoding > 0:
                # what the rows already decoding waited for their next
                # token while this tick's admissions were prefilled
                self.metrics.record_prefill_stall(self._phase_ms["prefill"])
        self._tick_phase = "inject"
        self._consult_injector()
        n_active = self.active()
        if n_active:
            self._tick_phase = "decode"
            if self._spec is not None:
                self._spec_round()
            else:
                self._ring_step()
        self._publish_pool_gauges()
        return bool(newly) or n_active > 0 or did_xfer

    def _phase(self, kind: str, **extra) -> _Phase:
        return _Phase(self, kind, extra)

    def _bump(self, name: str, n: int = 1) -> None:
        """Engine-local AND process-global: the snapshot shows the
        engine's own counts, the telemetry registry the fleet view.  The
        global mirror is namespaced per replica (``serving_r<id>_*``)
        when this scheduler has a fleet identity, so N replicas in one
        process stop colliding on the shared names."""
        self.metrics.incr(name, n)
        get_registry().counter(self.metrics.global_name(name)).inc(n)

    def _beat(self, force: bool = False) -> None:
        """Touch the heartbeat file (throttled to ``heartbeat_interval_s``).

        Atomic tmp + ``os.replace`` against readers, mtime as the clock —
        the ElasticCoordinator pattern.  Write failures are logged and
        swallowed: a full disk must not take down serving, it just makes
        this replica look stale (fail-safe direction)."""
        if self.heartbeat_path is None:
            return
        now = time.monotonic()
        if not force and now - self._last_beat < self._hb_interval:
            return
        self._last_beat = now
        tmp = self.heartbeat_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(
                    {
                        "replica_id": self.replica_id,
                        "pid": os.getpid(),
                        "tick": self._tick_no,
                    },
                    f,
                )
            os.replace(tmp, self.heartbeat_path)
        except OSError:
            self.logger.exception("heartbeat write failed; continuing")

    def _publish_pool_gauges(self) -> None:
        """Per-replica pool-state gauges in the PROCESS registry: the
        router's placement reads these cross-thread (block utilization
        for load scoring, prefix-hit rate for affinity telemetry), and
        the serve bench surfaces them in its JSON line."""
        reg = get_registry()
        util = self._kv.blocks_in_use / max(self._kv.num_blocks, 1)
        reg.gauge(self.metrics.global_name("block_util")).set(util)
        total = self._hit_blocks + self._miss_blocks
        if total:
            reg.gauge(self.metrics.global_name("prefix_hit_rate")).set(
                self._hit_blocks / total
            )

    # ------------------------------------------------------------------ #
    # KV transfer service (disaggregated serving — serving/disagg.py
    # coordinates; serving/kv_transfer.py is the wire format)

    def _service_kv_transfers(self) -> bool:
        """Run queued export/import verbs on the scheduler thread."""
        did = False
        while True:
            with self._cond:
                if not self._xfer_q:
                    return did
                verb, arg, fut = self._xfer_q.popleft()
            did = True
            try:
                if verb == "export":
                    res = self._export_kv(*arg)
                elif verb == "export_refs":
                    res = self._export_kv_refs(*arg)
                else:
                    res = self._import_kv(arg)
            except Exception as exc:
                # the verb failed, not the engine: the pool was either
                # only read (export) or mutated through invariant-safe
                # adopt/scatter (import) — fail the one future and move on
                if not fut.done():
                    fut.set_exception(exc)
            else:
                if not fut.done():
                    fut.set_result(res)

    def _export_kv(self, prompt, namespace, stall_s):
        payloads = kv_transfer.extract_payloads(
            self._kv, self._pool, prompt, namespace=namespace
        )
        if payloads:
            self._bump("kv_transfer_exported_blocks", len(payloads))
        if stall_s is not None:
            self.logger.warning(
                "fault injection: kv transfer export stalled %.2fs", stall_s
            )
            time.sleep(float(stall_s))
        return payloads

    def _export_kv_refs(self, prompt, namespace, stall_s):
        refs = kv_transfer.extract_block_refs(
            self._kv, self._pool, prompt, namespace=namespace
        )
        if refs:
            self._bump("kv_transfer_exported_blocks", len(refs))
        if stall_s is not None:
            self.logger.warning(
                "fault injection: kv transfer export stalled %.2fs", stall_s
            )
            time.sleep(float(stall_s))
        return refs

    def _import_kv(self, payloads):
        t0 = time.perf_counter()
        accepted = []
        rejected = 0
        nbytes = 0
        for p in payloads:
            if not kv_transfer.verify_payload(p):
                rejected += 1
                self._bump("kv_transfer_rejects")
                self.logger.warning(
                    "kv transfer: checksum reject of block %d — dropping "
                    "the rest of the chain; decode recomputes locally",
                    p.index,
                )
                break
            if self._kv.is_cached(p.key):
                continue
            blk = self._kv.adopt_block(p.key)
            if blk is None:
                break  # pool full even after LRU eviction: partial adopt is fine
            accepted.append((blk, p))
            nbytes += p.nbytes
        if accepted:
            self._pool = kv_transfer.scatter_payloads(
                self._pool, self._kv.num_blocks * self._kv.block_size, accepted
            )
        if accepted or rejected:
            self.metrics.record_kv_transfer(
                nbytes=nbytes,
                seconds=time.perf_counter() - t0,
                blocks=len(accepted),
            )
            reg = get_registry()
            if nbytes:
                reg.counter(
                    self.metrics.global_name("kv_transfer_bytes")
                ).inc(nbytes)
            if accepted:
                reg.counter(
                    self.metrics.global_name("kv_transfer_blocks")
                ).inc(len(accepted))
        return {"accepted": len(accepted), "rejected": rejected, "bytes": nbytes}

    def _expire(self, req: _PagedRequest, now: float) -> bool:
        if req.deadline is None or now < req.deadline:
            return False
        self._bump("timeouts")
        if not req.future.done():
            req.future.set_exception(
                TimeoutError(
                    "serving request exceeded its deadline after "
                    f"{now - req.enqueued_at:.3f}s in queue"
                )
            )
        return True

    def _sweep_expired_locked(self) -> None:
        now = time.monotonic()
        if any(r.deadline is not None and now >= r.deadline for r in self._queue):
            self._queue = deque(
                r for r in self._queue if not self._expire(r, now)
            )

    def _admit(self) -> List[_PagedRequest]:
        """Fill free slots from the queue head (FCFS: a head request the
        pool cannot cover blocks those behind it — no starvation, at the
        cost of head-of-line blocking; counted as ``admission_waits``)."""
        newly: List[_PagedRequest] = []
        with self._cond:
            self._sweep_expired_locked()
            free = [i for i, s in enumerate(self._slots) if s is None]
            # cap a tick's admissions at the largest batch bucket: whether
            # they run as one call or as several (_prefill_calls), every
            # call stays on the compiled grid
            max_admit = min(len(free), self.batch_buckets[-1])
            while self._queue and len(newly) < max_admit:
                req = self._queue[0]
                # the adapter id namespaces the prefix cache: identical
                # prompts under different adapters have DIFFERENT K/V
                # (cross-tenant reuse would be silent corruption)
                adm = self._kv.admit(
                    req.prompt.tolist(), req.max_new,
                    namespace=req.adapter,
                    extra_blocks=self._extra_blocks,
                )
                if adm is None:
                    self._bump("admission_waits")
                    break
                if self._spec is not None:
                    # all-or-nothing across BOTH pools: holding the target
                    # reservation while waiting on the draft pool could
                    # deadlock two half-admitted requests
                    dadm = self._dkv.admit(req.prompt.tolist(), req.max_new)
                    if dadm is None:
                        self._kv.release(adm)
                        self._bump("admission_waits")
                        break
                    req.draft_admission = dadm
                self._queue.popleft()
                if req.admitted_at is None:  # not a hot-restart requeue
                    req.admitted_at = time.monotonic()
                    self.metrics.record_queue_wait(
                        (req.admitted_at - req.enqueued_at) * 1000.0
                    )
                req.admission = adm
                req.slot = free[len(newly)]
                self._slots[req.slot] = req
                newly.append(req)
                self._bump("admitted")
                cacheable = (req.prompt.size - 1) // self._kv.block_size
                self._hit_blocks += adm.n_shared
                self._miss_blocks += cacheable - adm.n_shared
                if adm.n_shared:
                    self._bump("prefix_hit_blocks", adm.n_shared)
                if cacheable - adm.n_shared:
                    self._bump("prefix_miss_blocks", cacheable - adm.n_shared)
        return newly

    def _table_ids(self, req: _PagedRequest) -> List[int]:
        """The request's LOGICAL block table: the admission's footprint
        blocks in order.  In speculative mode the admission carries one
        extra trailing block — the private spare — which is never in the
        table; the verify step reaches it through the branch table and
        commit swaps it in (swapping entries inside ``block_ids`` keeps
        release/refcount accounting exact)."""
        ids = req.admission.block_ids
        if self._extra_blocks:
            return ids[: len(ids) - self._extra_blocks]
        return ids

    def _prefill_calls(self, newly: List[_PagedRequest]) -> List[_PrefillCall]:
        """The target's prefill calls of this tick, each at ITS batch bucket
        x ITS sequence bucket: computed once, and the one source of what the
        calls run, of their spans' fields and of their padded tokens.

        The fresh admissions run as the partition that ``plan_calls`` finds
        cheapest under the engine's own estimate of a call's time
        (:meth:`set_prefill_cost`), in the order of their earliest arrival;
        with no estimate, as ONE call.  The rows a hot-restart re-admitted
        replay in one call of their own, last.
        """
        def call(reqs, replay=False):
            suffix = [r.prompt.size - r.admission.cached_len for r in reqs]
            return _PrefillCall(
                reqs, suffix,
                bucket_for(len(reqs), self.batch_buckets, "admitted rows"),
                bucket_for(max(suffix), self.seq_buckets, "prefill suffix"),
                replay,
            )

        fresh = [r for r in newly if not r.tokens]
        replay = [r for r in newly if r.tokens]
        with self._cond:
            cost = self._prefill_cost
        if len(fresh) > 1 and cost is not None:
            suffix = [r.prompt.size - r.admission.cached_len for r in fresh]
            calls = [
                call([fresh[i] for i in rows])
                for rows in plan_calls(
                    suffix, self.batch_buckets, self.seq_buckets, cost
                )
            ]
        else:
            calls = [call(fresh)] if fresh else []
        return calls + ([call(replay, replay=True)] if replay else [])

    def set_prefill_cost(self, fixed_ms: float, ms_per_ktoken: float) -> None:
        """What this engine observed a prefill call to cost, ``fixed_ms +
        ms_per_ktoken x batch bucket x sequence bucket / 1000`` (the
        warm-up's timed calls: ``serving/engine.py``).  From here on a
        tick's fresh admissions run as the calls whose summed estimate is
        least (:meth:`_prefill_calls`); a scheduler that was never told
        keeps one call a tick."""
        cost = LinearCost(float(fixed_ms), float(ms_per_ktoken))
        with self._cond:
            self._prefill_cost = cost
        self.metrics.record_prefill_cost(*cost)

    def _prefill(self, newly: List[_PagedRequest], decoding: int) -> None:
        """Prefill every request admitted this tick, one ``prefill`` phase
        span a call.

        Fresh requests (no tokens yet) go through the bucketed calls of
        :meth:`_prefill_calls`; requests re-admitted by a hot-restart carry
        their delivered token stream and take the replay path instead.  A
        span's ``rows``, ``tokens``, ``bucket``, ``reqs`` and
        ``padded_tokens`` are its call's own; ``stalled`` is the
        ``decoding`` rows on the tick's first call and 0 on the rest (a
        live row waits once a tick, through all of them).
        """
        calls = self._prefill_calls(newly)
        if sum(not r.tokens for r in newly) > 1:
            self._bump("prefill_multi_row_ticks")
            if sum(not c.replay for c in calls) > 1:
                self._bump("prefill_split_ticks")
        for call in calls:
            flash_layers = self._flash_layers(call.seq_bucket)
            with self._phase(
                "prefill", rows=len(call.reqs), tokens=sum(call.suffix),
                bucket=call.seq_bucket, reqs=[r.rid for r in call.reqs],
                stalled=decoding, padded_tokens=call.padded_tokens,
                flash_layers=flash_layers,
            ):
                if call.replay:
                    self._replay(call)
                else:
                    self._prefill_fresh(call)
            decoding = 0
            self._bump("prefill_calls")
            if flash_layers:
                self._bump("prefill_flash_calls")
        if self._spec is not None:
            # the draft pool needs the prompt K/V too (its own programs,
            # its own blocks); requests evicted by the target prefill's
            # output guard have already released both reservations
            live = [r for r in newly if r.admission is not None]
            if live:
                # a span of the tick's prefill time that is no target call:
                # no ``bucket`` and no ``padded_tokens``
                with self._phase(
                    "prefill", rows=len(live), draft=True, stalled=0,
                ):
                    self._draft_prefill(live)

    def _prefill_fresh(self, call: _PrefillCall) -> None:
        """One bucketed prefill call over fresh admissions of this tick: its
        dispatch, the read of its tokens, and their push.

        Prefix-cache hits shorten the device work directly: only the
        SUFFIX past ``cached_len`` is fed (positions ``cached_len ..
        prompt_len-1``), padded up to the call's seq bucket.
        """
        t0 = time.perf_counter()
        newly, suffix, bb, sb, _ = call
        tokens = np.zeros((bb, sb), np.int32)
        positions = np.full((bb, sb), -1, np.int32)
        tables = np.zeros((bb, self.table_blocks), np.int32)
        last_col = np.zeros((bb,), np.int32)
        aids = np.full((bb,), -1, np.int32)
        keys = self._pad_keys(bb)
        for i, req in enumerate(newly):
            cl = req.admission.cached_len
            tokens[i, : suffix[i]] = req.prompt[cl:]
            positions[i, : suffix[i]] = np.arange(cl, req.prompt.size)
            ids = self._table_ids(req)
            tables[i, : len(ids)] = ids
            last_col[i] = suffix[i] - 1
            aids[i] = req.adapter
            keys[i] = req.row_key
        slots = [r.slot for r in newly] + [-1] * (bb - len(newly))
        self._refuse_a_piece(positions)
        tok, finite, self._pool = self._fns.prefill(
            self.params, self._pool, tokens, positions, tables,
            last_col, keys, np.zeros((bb,), np.int32), aids,
            *self._state_rows(slots),
        )
        rb0 = time.perf_counter()
        tok = np.asarray(tok)
        finite = np.asarray(finite)
        t1 = time.perf_counter()
        self._tick_block_s += t1 - rb0
        for i, req in enumerate(newly):
            if not finite[i]:
                # output guard: this prompt produced non-finite logits —
                # evict it (and keep its blocks out of the prefix cache)
                self._evict_poisoned(
                    req, cause=None, trigger="non-finite prefill logits"
                )
                continue
            # blocks are filled now — publish them for future prefix hits
            # BEFORE this request can retire and release them
            self._kv.register_prefix(
                req.prompt.tolist(), req.admission, namespace=req.adapter
            )
            self._push_token(req, int(tok[i]))
        self.metrics.record_prefill(
            prompt_tokens=int(sum(suffix)), n_requests=len(newly),
            prefill_s=t1 - t0,
        )

    def _draft_prefill(self, reqs: List[_PagedRequest]) -> None:
        """Scatter each admitted request's FULL prompt K/V into the draft
        pool (speculative mode).  Always the whole prompt — the draft pool
        runs without a prefix cache, so the target's cache hits cannot
        shorten this call.  The sampled token is discarded (draft rounds
        start from the COMMITTED stream) and the keys are the pad key:
        the draft is always greedy.

        Replayed (hot-restart) requests get the same treatment: their
        generated tokens' draft K/V is NOT rebuilt — those rows read as
        zeros, which can only depress the acceptance rate, never change
        the committed stream (every emitted token is the target's).
        """
        bb = bucket_for(len(reqs), self.batch_buckets, "draft rows")
        sb = bucket_for(
            max(r.prompt.size for r in reqs), self.seq_buckets, "draft prompt"
        )
        tokens = np.zeros((bb, sb), np.int32)
        positions = np.full((bb, sb), -1, np.int32)
        tables = np.zeros((bb, self.table_blocks), np.int32)
        last_col = np.zeros((bb,), np.int32)
        aids = np.full((bb,), -1, np.int32)
        for i, req in enumerate(reqs):
            n = req.prompt.size
            tokens[i, :n] = req.prompt
            positions[i, :n] = np.arange(n)
            dids = req.draft_admission.block_ids
            tables[i, : len(dids)] = dids
            last_col[i] = n - 1
            aids[i] = req.adapter if self._draft_lora else -1
        keys = self._pad_keys(bb)
        _tok, _finite, self._draft_pool = self._draft_fns.prefill(
            self._draft_params, self._draft_pool, tokens, positions, tables,
            last_col, keys, np.zeros((bb,), np.int32), aids,
        )

    def _replay(self, call: _PrefillCall) -> None:
        """Rebuild restart-surviving requests' KV state bit-exactly.

        Prompt K/V comes back through the bucketed prefill (prefix-cache
        hits shorten it exactly like a fresh admission); the already-
        delivered generated tokens are then re-fed through the SAME
        decode program that produced them.  Per-row per-token-index
        sampling keys make every resampled token bitwise identical to
        the stored stream — verified per token, never re-delivered
        (clients already hold these tokens; ``on_token`` does not refire).
        """
        reqs, suffix, bb, sb, _ = call
        tokens = np.zeros((bb, sb), np.int32)
        positions = np.full((bb, sb), -1, np.int32)
        tables = np.zeros((bb, self.table_blocks), np.int32)
        last_col = np.zeros((bb,), np.int32)
        aids = np.full((bb,), -1, np.int32)
        keys = self._pad_keys(bb)
        for i, req in enumerate(reqs):
            cl = req.admission.cached_len
            tokens[i, : suffix[i]] = req.prompt[cl:]
            positions[i, : suffix[i]] = np.arange(cl, req.prompt.size)
            ids = self._table_ids(req)
            tables[i, : len(ids)] = ids
            last_col[i] = suffix[i] - 1
            aids[i] = req.adapter
            keys[i] = req.row_key
        slots = [r.slot for r in reqs] + [-1] * (bb - len(reqs))
        self._refuse_a_piece(positions)
        tok, finite, self._pool = self._fns.prefill(
            self.params, self._pool, tokens, positions, tables,
            last_col, keys, np.zeros((bb,), np.int32), aids,
            *self._state_rows(slots),
        )
        tok = np.asarray(tok)
        finite = np.asarray(finite)
        live: List[_PagedRequest] = []
        for i, req in enumerate(reqs):
            if not finite[i]:
                self._evict_poisoned(
                    req, cause=None, trigger="non-finite replay prefill logits"
                )
                continue
            self._kv.register_prefix(
                req.prompt.tolist(), req.admission, namespace=req.adapter
            )
            self._verify_replay(req, 0, int(tok[i]))
            live.append(req)
        # feed generated tokens 0..K-2 back through the decode program,
        # re-verifying tokens 1..K-1 — identical per-row inputs through
        # the identical program reproduce the original run's writes
        max_gen = max((r.gen_idx for r in live), default=0)
        for k in range(1, max_gen):
            step_reqs = [r for r in live if r.gen_idx > k]
            if not step_reqs:
                break
            tok, finite, *_ = self._step(self._step_inputs(
                self._step_row(r, k, r.tokens[k - 1]) for r in step_reqs
            ))
            tok = np.asarray(tok)
            finite = np.asarray(finite)
            for req in step_reqs:
                if not finite[req.slot]:
                    self._evict_poisoned(
                        req, cause=None,
                        trigger="non-finite replay decode logits",
                    )
                    live.remove(req)
                    continue
                self._verify_replay(req, k, int(tok[req.slot]))
        for req in live:
            self._bump("replayed_tokens", req.gen_idx)

    def _verify_replay(self, req: _PagedRequest, idx: int, tok: int) -> None:
        """Replay parity check: the resample must equal what the client
        already received.  A mismatch is counted and logged but the
        DELIVERED stream stays authoritative."""
        if tok != req.tokens[idx]:
            self._bump("replay_parity_mismatch")
            self.logger.error(
                "replay divergence: slot %d generated token %d resampled as "
                "%d but %d was delivered (keeping the delivered stream)",
                req.slot, idx, tok, req.tokens[idx],
            )

    # ------------------------------------------------------------------ #
    # fault injection (engine/fault.py serve_* kinds) — consulted once per
    # tick, after admissions so the slot targets exist

    def _consult_injector(self) -> None:
        inj = fault.get_injector()
        if not inj.active:
            return
        t = self._tick_no
        sec = inj.take("serve_hang", t)
        if sec is not None:
            fault.bump("injected_serve_hangs")
            self.logger.warning(
                "fault injection: hanging tick %d for %.2fs", t, sec
            )
            time.sleep(sec)
        slot = inj.take("serve_raise", t)
        if slot is not None:
            req = self._slot_target(int(slot), "serve_raise")
            if req is not None:
                fault.bump("injected_serve_raises")
                req.poison = "raise"
        slot = inj.take("serve_nan", t)
        if slot is not None:
            req = self._slot_target(int(slot), "serve_nan")
            if req is not None:
                fault.bump("injected_serve_nans")
                self._corrupt_pool_rows(req)
        if inj.take("serve_device_lost", t) is not None:
            fault.bump("injected_serve_device_lost")
            raise fault.DeviceLostError(
                f"injected device loss at serving tick {t}"
            )

    def _slot_target(self, slot: int, kind: str) -> Optional[_PagedRequest]:
        req = self._slots[slot] if 0 <= slot < self.slots_n else None
        if req is None:
            self.logger.warning(
                "fault injection: %s@%d targets empty slot %d; dropped",
                kind, self._tick_no, slot,
            )
        return req

    def _corrupt_pool_rows(self, req: _PagedRequest) -> None:
        """NaN the SCORED pool row of ``req``'s last WRITTEN position: the
        key row of a K/V pair, the one row of a latent cache.

        That position's block sits past the prefix-cache registration cap
        ((prompt_len-1)//block_size), so it is exclusively owned — the
        poison is per-request by construction.  Only leaves a query is
        scored against are corrupted (:func:`..ops.attention.
        pool_leaf_role`): a NaN there makes the OWNER's attention logits
        NaN (position is live for it) while every other reader — including
        a later request recycling the freed block — masks it to -inf before
        the softmax and zeroes the dead row before any product.  A NaN in a
        pair's VALUE row would leak through recycling: masked positions get
        exactly-zero softmax weight, and 0 * NaN is NaN in the value
        contraction.
        """
        bs = self._kv.block_size
        p = req.prompt.size + max(req.gen_idx, 1) - 2
        row = req.admission.block_ids[p // bs] * bs + p % bs
        n_rows = self._kv.num_blocks * bs

        def corrupt(path, leaf):
            if (
                pool_leaf_role(path, leaf, n_rows) == "scored"
                and jnp.issubdtype(leaf.dtype, jnp.floating)
            ):
                return leaf.at[row].set(jnp.nan)
            return leaf

        self._pool = jax.tree_util.tree_map_with_path(corrupt, self._pool)

    # ------------------------------------------------------------------ #
    # decode

    def _step_row(self, req: _PagedRequest, index: int, token: Optional[int]):
        """``req``'s row (``step_inputs.StepRow``) of a target step that
        samples its token ``index``, fed ``token`` (None = the carried one)."""
        return (
            req.slot, req.prompt.size, index, token, self._table_ids(req),
            req.adapter, req.row_key,
        )

    def _step_inputs(self, rows):
        return step_inputs(
            self.slots_n, self.table_blocks, self._pad_key, rows
        )

    def _step(self, inputs, carry=None):
        """Dispatch the target's ``decode_step`` over ``inputs`` and rebind
        the pool; returns ``(tok, finite, *moe)``, still on the device.  A
        call that carries nothing passes the committed zeros the ring
        starts from, so the program keeps ONE entry in its cache whoever
        calls (:meth:`_zero_carry`)."""
        tok, finite, self._pool, *moe = self._fns.decode_step(
            self._qparams if self._quant else self.params, self._pool,
            self._zero_carry() if carry is None else carry,
            *inputs, *self._slot_rows(inputs.pos),
        )
        return (tok, finite, *moe)

    def _record_iteration(self, live: int, pos=None) -> None:
        """File one decode iteration's sample of the scheduler's state:
        ``live`` rows, the pool's blocks and, of a single-position step
        (``pos`` = its positions), the shares of the block tables and of
        the state rows it reads.  A speculative round reads several
        positions a row and files neither."""
        shares = {} if pos is None else dict(
            live_block_share=self._live_block_share(pos),
            state_live_row_share=self._state_live_row_share(pos),
        )
        self.metrics.record_iteration(
            active_slots=live, total_slots=self.slots_n,
            blocks_in_use=self._kv.blocks_in_use,
            total_blocks=self._kv.num_blocks, **shares,
        )

    def _poison_shim(self, reqs: List[_PagedRequest]) -> None:
        """Injected per-request dispatch failure (``serve_raise``).  The
        message deliberately names no slot: attribution is the
        supervisor's bisect's job."""
        for req in reqs:
            if req.poison == "raise":
                raise fault.FaultInjectionError(
                    f"injected decode-dispatch failure (tick {self._tick_no})"
                )

    def _readback_wait(self, for_step: int):
        """The span around a decode body's first read of a step's output,
        a child of ``readback`` and NOT a phase (no place in ``_phase_ms``
        or ``TICK_PHASES``).  ``for_step`` is the tick that dispatched the
        step this read drains: this tick at depth 0 and in a speculative
        round, an earlier one on a ring that holds steps."""
        return span("readback_wait", step=self._tick_no, for_step=for_step)

    def _record_moe(self, moe, n_rows: int) -> None:
        """File a decode step's expert counts (``decode.py``: the fourth
        output of a model with expert layers, ``[]`` for any other), read
        back beside the step's tokens."""
        if moe:
            hit, load_max = (int(v) for v in np.asarray(moe[0]))
            self.metrics.record_moe(hit, load_max, n_rows, self._moe_shape)
            # the step's counts in a trace too: the histograms hold the
            # whole run, a reader of traced seconds needs theirs
            with span("moe_counts", step=self._tick_no, hit=hit, rows=n_rows):
                pass

    def _decode_probe(self, reqs: List[_PagedRequest]) -> None:
        """Re-drive the decode dispatch for a SUBSET of the active slots —
        the supervisor's bisect primitive.  Inputs are identical to the
        failed step's, so the pool scatter is idempotent and sampling is
        pure: probing commits nothing the real step would not."""
        self._poison_shim(reqs)
        tok, *_ = self._step(self._step_inputs(
            self._step_row(r, r.gen_idx, r.tokens[-1]) for r in reqs
        ))
        # surface async dispatch errors here, inside the probe's try
        jax.block_until_ready(tok)

    # ------------------------------------------------------------------ #
    # the decode body: a ring of dispatched steps, read behind the dispatch

    def _note_dispatch(self, inflight: int) -> None:
        """One decode step is about to be dispatched with ``inflight``
        steps still in the ring (0 at depth 0): count it
        (``decode_overlap_share``) and record the host-side gap between
        consecutive decode dispatch enqueues — the number the ring exists
        to shrink.  Only gaps between BACK-TO-BACK decode
        ticks count: an idle queue between two dispatches is not host
        overhead."""
        self.metrics.record_decode_dispatch(inflight)
        now = time.perf_counter()
        if (
            self._last_dispatch is not None
            and self._tick_no - self._last_dispatch[0] <= 1
        ):
            self.metrics.record_dispatch_gap(
                (now - self._last_dispatch[1]) * 1000.0
            )
        self._last_dispatch = (self._tick_no, now)

    def _ring_step(self) -> None:
        """One single-token step for every occupied slot: dispatch step *k*,
        then read back what the ring holds beyond ``async_depth`` steps —
        step *k* itself at depth 0, step *k-1* at depth 1, which is what an
        engine serves.

        The sampled-token carry stays ON DEVICE — ``decode_step`` takes its
        own token output back as the next ``prev_tok``, with rows the host
        just (re)filled spliced in via ``fresh_mask`` — so at depth 1 tick k
        reads step k-1 only after it has dispatched step k, which the
        device finished while the host was dispatching (or, where the
        device sets the pace, is finishing).  Host state stays exact
        without the tokens: the per-request ``dispatched`` counter derives
        every position and sampling index, so the drained stream is bitwise
        identical at every depth (same per-row fold_in keys, same per-row
        pool writes in the same order).  At depth 0 nothing is ever left in
        flight: every row is fresh every tick and the carry is never read.

        Lag consequences, all bounded by ``async_depth``: retire/refill
        and the NaN output guard observe tokens late, so a row can
        execute past EOS — never past ``max_new`` (the dispatch cap is
        host-exact) — and those overrun writes land at positions
        ``<= prompt_len + max_new - 2``, inside the row's reserved
        footprint; the sampled overrun tokens are discarded at drain
        because the request has already retired (``admission is None``),
        and once its blocks recycle, any stale overrun rows are masked
        exactly like every other recycled-block row.  A request's first
        decode token is drained ``async_depth`` ticks after its prefill.

        ``record_decode`` files the tokens PUSHED and the time of the drain
        (the read and the delivery), not the preparation or the dispatch: a
        row the output guard evicts is no token.
        """
        with self._phase("decode_prep"):
            active = [req for req in self._slots if req is not None]
            self._poison_shim(active)
            # host-exact dispatch cap: a row never dispatches past its
            # token budget, so only EOS (host-unknown until drain) can
            # overrun
            disp = [r for r in active if r.dispatched < r.max_new]
            if disp:
                inputs, rows = self._fed_arrays(disp)
        if disp:
            # steps still in the ring as this one is dispatched: 1 in a
            # steady window at depth 1 (the step the drain below reads)
            inflight = len(self._inflight)
            self._note_dispatch(inflight)
            # the span marks this tick as PRODUCTIVE serving work — the
            # serve-side MTTR endpoint (telemetry/slo.py pairs it with the
            # preceding poison_bisect/serving_restart recovery span)
            with self._phase(
                "decode_step", active=len(disp), inflight=inflight,
                **self._window_reads(inputs.pos),
            ):
                # the first dispatch of a run carries nothing: every
                # dispatched row is fresh by construction, and the zeros
                # are never sampled
                tok, finite, *moe = self._step(inputs, self._carry_tok)
            for req in disp:
                req.dispatched += 1
            self._carry_tok = tok
            self._inflight.append((tok, finite, rows, moe, self._tick_no))
            self._record_iteration(len(disp), inputs.pos)
        # drain behind the dispatch (ring bounded at async_depth); when
        # nothing is left to dispatch, drain EVERYTHING so the endgame
        # cannot strand determined tokens in flight
        target = self._async_depth if disp else 0
        pushed = 0
        t0 = time.perf_counter()
        while len(self._inflight) > target:
            pushed += self._drain_entry(self._inflight.popleft())
        if pushed:
            self.metrics.record_decode(
                n_tokens=pushed, decode_s=time.perf_counter() - t0
            )

    def _fed_arrays(self, disp: List[_PagedRequest]):
        """The ring's step inputs with ``disp`` live, derived from each
        row's ``dispatched`` counter, and the rows' ``(request, slot, token
        index)`` for the drain.  A row with nothing in flight (``dispatched
        == gen_idx``: fresh prefill, refill, post-recovery rollback, and
        every row at depth 0) hands over its last token, which overrides
        the stale carry in-graph; any other is fed the carried one."""
        rows = [(req, req.slot, req.dispatched) for req in disp]
        return self._step_inputs(
            self._step_row(req, d, req.tokens[-1] if d == req.gen_idx else None)
            for req, _, d in rows
        ), rows

    def _zero_carry(self):
        """A mesh-replicated, COMMITTED int32[slots] zeros vector whose
        sharding matches ``decode_step``'s token output, made once.

        The jit cache keys on input shardings: feeding an uncommitted
        ``jnp.zeros`` as the first carry and the committed program output
        as every later one would compile the SAME program twice (one
        re-layout entry).  Matching the output's replicated NamedSharding
        up front keeps every caller — the ring's first and carried
        dispatches, the probe and the replay — at exactly one compiled
        decode program — the compile-count pin the tests hold."""
        if self._zero_tok is None:
            z = jnp.zeros((self.slots_n,), jnp.int32)
            leaf_sh = getattr(
                jax.tree_util.tree_leaves(self.params)[0], "sharding", None
            )
            if isinstance(leaf_sh, jax.sharding.NamedSharding):
                z = jax.device_put(
                    z,
                    jax.sharding.NamedSharding(
                        leaf_sh.mesh, jax.sharding.PartitionSpec()
                    ),
                )
            self._zero_tok = z
        return self._zero_tok

    def _drain_entry(self, entry) -> int:
        """Materialize one ring entry's host readback and apply it.

        Rows whose request already left its slot (EOS overrun after a
        lagged retire, poison eviction, hot-restart requeue) or whose
        host stream was rolled back since dispatch are discarded — their
        token was never part of the committed stream.  Returns the
        number of tokens pushed."""
        tok_dev, finite_dev, rows, moe, dispatched_at = entry
        with self._phase("readback", for_step=dispatched_at):
            with self._readback_wait(dispatched_at):
                tok = np.asarray(tok_dev)
            finite = np.asarray(finite_dev)
            self._record_moe(moe, len(rows))
        pushed = 0
        with self._phase("deliver"):
            for req, slot, idx in rows:
                if req.admission is None or idx != req.gen_idx:
                    continue
                if not finite[slot]:
                    # the on-device output guard, observed async_depth
                    # ticks late: the emitter's own table re-reads its NaN
                    # rows every overrun step, so the flag stays false and
                    # the eviction lands on exactly this request
                    self._evict_poisoned(
                        req, cause=None, trigger="non-finite decode logits"
                    )
                    continue
                self._push_token(req, int(tok[slot]))
                pushed += 1
        return pushed

    def flush_async(self) -> None:
        """Drain what the in-flight ring can still deliver, discard the
        rest, and roll every live row's dispatch counter back to its
        host-known stream.

        ``tick`` calls this on any failure BEFORE invoking the
        supervisor: probes and replays assume every row's last token on
        the host (``_decode_probe`` re-dispatches from ``tokens[-1]``), and
        attribution must not blame a request for a step that was merely
        in flight when an unrelated row poisoned the tick.  Runs on the
        tick thread only.  Discarded steps cost nothing —
        re-dispatching them reproduces the same tokens and the same
        idempotent pool writes.  At depth 0 the ring is empty already.
        """
        while self._inflight:
            entry = self._inflight.popleft()
            try:
                self._drain_entry(entry)
            except Exception:
                # the device state behind the remaining entries is part
                # of the same failure — discard, the rollback below makes
                # re-dispatch exact
                self.logger.warning(
                    "ring drain failed mid-recovery; discarding %d "
                    "remaining in-flight step(s)", len(self._inflight),
                )
                self._inflight.clear()
                break
        self._carry_tok = None
        self._last_dispatch = None
        for req in self._slots:
            if req is not None:
                req.dispatched = req.gen_idx

    # ------------------------------------------------------------------ #
    # speculative decoding (serving/speculative.py)

    def _spec_round(self) -> None:
        """One speculative round for every occupied slot, replacing the
        single-token decode step: k+1 greedy draft steps on the draft
        pool (the last a pure K/V backfill of the final proposal), one
        batched ``verify`` on FORKED block tables, exact host-side
        accept/reject, then commit-by-swap.  Emits 1..k+1 tokens per live
        request; the committed stream is token-identical to plain greedy
        decode (the parity oracle) because every emitted token is the
        TARGET's argmax — the draft only decides how many of them one
        target forward amortizes.

        Fork mechanics: the round's verify writes positions ``P..P+ke``
        (``P`` = the last committed token's position).  Positions beyond
        block ``bi = P // block_size`` land in footprint blocks that hold
        no committed data yet, so they need no protection; block ``bi``
        DOES hold committed rows ``[bi*bs, P)``, so those are CoW-copied
        into the request's private spare block and the verify runs on a
        branch table with ``table[bi] := spare``.  On commit the spare
        becomes the real block (swap inside ``block_ids`` — refcount
        accounting unchanged); the old block becomes the next round's
        spare, pristine until then (rollback-safety).  Rows a REJECTED
        proposal wrote past the commit point are harmless: every verify
        scatters all its columns before it gathers, so any position a
        later round can read is rewritten by that round first, and
        positions past its coverage are causally masked.
        """
        t0 = time.perf_counter()
        with self._phase("decode_prep"):
            active = [req for req in self._slots if req is not None]
            self._poison_shim(active)
            W = self.slots_n
            k = self._spec.k
            bs = self._kv.block_size
            # clamp each row's proposal count to its remaining budget so
            # no verify write can land past the reserved footprint
            k_eff = {r.slot: min(k, r.max_new - r.gen_idx) for r in active}

        # a round reads its own verify before the next is proposed:
        # nothing is ever in the ring (decode_overlap_share stays 0)
        self.metrics.record_decode_dispatch(0)
        with self._phase("decode_step", active=len(active), inflight=0):
            # -- draft: k+1 greedy single-token steps (step j feeds the
            # committed tail for j=0, else proposal j-1, at position
            # P+j, producing proposal j).  Step k_eff is a pure K/V
            # BACKFILL: it feeds the final proposal so its position is
            # written to the draft pool (the sample is discarded) —
            # without it that position would stay stale forever once the
            # proposal commits, and even a self-draft would drift off the
            # target (acceptance < 1 for no reason) ---------------------
            draft_tok = np.zeros((W, k), np.int32)
            for j in range(k + 1):
                # the draft's own tables, the pad key (it is greedy), and
                # the row's adapter only where the draft has factors
                rows = [
                    (r.slot, r.prompt.size, r.gen_idx + j,
                     r.tokens[-1] if j == 0 else draft_tok[r.slot, j - 1],
                     r.draft_admission.block_ids,
                     r.adapter if self._draft_lora else -1, None)
                    for r in active if j <= k_eff[r.slot]
                ]
                if not rows:
                    break
                tok, _, self._draft_pool, *_ = self._draft_fns.decode_step(
                    self._draft_params, self._draft_pool,
                    self._zero_carry(), *self._step_inputs(rows),
                )
                if j < k:
                    draft_tok[:, j] = np.asarray(tok)

            # -- fork + verify: one batched target forward over
            # [committed tail, proposals...] on branch tables ----------
            pool_rows = self._kv.num_blocks * bs
            src = np.full((W, bs), pool_rows, np.int32)  # OOB rows drop
            dst = np.full((W, bs), pool_rows, np.int32)
            ver_tok = np.zeros((W, k + 1), np.int32)
            ver_pos = np.full((W, k + 1), -1, np.int32)
            vtables = np.zeros((W, self.table_blocks), np.int32)
            aids = np.full((W,), -1, np.int32)
            offs = np.arange(bs)
            for req in active:
                i = req.slot
                ke = k_eff[i]
                P = req.prompt.size + req.gen_idx - 1
                bi = P // bs
                ids = self._table_ids(req)
                spare = req.admission.block_ids[-1]
                off = P % bs
                if off:
                    src[i, :off] = ids[bi] * bs + offs[:off]
                    dst[i, :off] = spare * bs + offs[:off]
                ver_tok[i, 0] = req.tokens[-1]
                ver_tok[i, 1 : 1 + ke] = draft_tok[i, :ke]
                ver_pos[i, : ke + 1] = np.arange(P, P + ke + 1)
                vtables[i, : len(ids)] = ids
                vtables[i, bi] = spare
                aids[i] = req.adapter
            self._pool = self._fns.copy_rows(
                self._pool, src.reshape(-1), dst.reshape(-1)
            )
            # verify ALWAYS takes the plain tree, quant mode included:
            # the target's scoring is the accuracy anchor
            logits, self._pool = self._fns.verify(
                self.params, self._pool, ver_tok, ver_pos, vtables, aids,
            )
        with self._phase("readback", for_step=self._tick_no):
            with self._readback_wait(self._tick_no):
                logits = np.asarray(logits)

        # -- host accept/reject + commit -------------------------------
        t1 = time.perf_counter()
        emitted_total = proposed = accepted = 0
        with self._phase("deliver"):
            for req in active:
                i = req.slot
                ke = k_eff[i]
                if not np.isfinite(logits[i, : ke + 1]).all():
                    self._evict_poisoned(
                        req, cause=None, trigger="non-finite verify logits"
                    )
                    continue
                target = logits[i, : ke + 1].argmax(-1).astype(np.int32)
                n_acc, emit = greedy_accept(draft_tok[i, :ke], target)
                if n_acc == ke and req.gen_idx + len(emit) > req.max_new:
                    emit = emit[:-1]  # no room for the bonus under the cap
                proposed += ke
                accepted += n_acc
                # commit-by-swap: the branch boundary block becomes real, the
                # displaced block becomes the next round's pristine spare
                P = req.prompt.size + req.gen_idx - 1
                bi = P // bs
                ids = req.admission.block_ids
                ids[bi], ids[-1] = ids[-1], ids[bi]
                for t in emit:
                    self._push_token(req, int(t))
                    emitted_total += 1
                    if req.admission is None:
                        break  # retired (eos / cap) mid-round
        self._bump("spec_rounds")
        if proposed:
            self._bump("spec_proposed", proposed)
        if accepted:
            self._bump("spec_accepted", accepted)
        self.metrics.record_decode(n_tokens=emitted_total, decode_s=t1 - t0)
        self._record_iteration(len(active))

    # ------------------------------------------------------------------ #
    # retirement and recovery

    def _push_token(self, req: _PagedRequest, tok: int) -> None:
        req.tokens.append(tok)
        if req.dispatched < len(req.tokens):
            req.dispatched = len(req.tokens)
        now = time.monotonic()
        if req.last_token_at is not None:
            self.metrics.record_token_gap((now - req.last_token_at) * 1000.0)
        elif len(req.tokens) == 1:  # not the continuation of a replay
            req.first_token_at = now
            self.metrics.record_first_token((now - req.enqueued_at) * 1000.0)
        req.last_token_at = now
        if req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception:  # a client callback must not kill the loop
                self.logger.exception("on_token callback raised; ignoring")
        if (self.eos_id is not None and tok == self.eos_id) or (
            req.gen_idx >= req.max_new
        ):
            self._retire(req)

    def _release_draft(self, req: _PagedRequest) -> None:
        if req.draft_admission is not None:
            self._dkv.release(req.draft_admission)
            req.draft_admission = None

    def _retire(self, req: _PagedRequest) -> None:
        self._slots[req.slot] = None
        now = time.monotonic()
        # the request's life as one record on the span ring: a hang dump
        # (and the span file of an operator who sets one) ties the phases
        # that name this ``req`` to what the request waited for
        record_span(
            "request", req.enqueued_at, now - req.enqueued_at, req=req.rid,
            admitted_at=req.admitted_at, first_token_at=req.first_token_at,
            finished_at=round(now, 6), tokens=len(req.tokens),
            prefix_blocks=req.admission.n_shared,
        )
        self._kv.release(req.admission)
        req.admission = None
        self._release_draft(req)
        if not req.future.done():
            req.future.set_result(
                {
                    "tokens": np.asarray(req.tokens, np.int32),
                    "gen_len": len(req.tokens),
                }
            )
        self._bump("retired")
        self.metrics.record_request(
            req.enqueued_at, gen_len=len(req.tokens),
            adapter=req.adapter_name,
        )
        if self._kv.prefix_evictions:
            # drain the pool's eviction tally into the ledger (the pool
            # itself is metrics-free bookkeeping)
            self._bump("prefix_evictions", self._kv.prefix_evictions)
            self._kv.prefix_evictions = 0

    def _evict_poisoned(
        self, req: _PagedRequest, *, cause: Optional[BaseException],
        trigger: str,
    ) -> None:
        """Fail ONE request with a diagnosed :class:`PoisonedRequestError`
        and free its reservation; every other slot keeps decoding."""
        err = PoisonedRequestError(
            f"request in slot {req.slot} poisoned the engine at tick "
            f"{self._tick_no} ({trigger}) after {req.gen_idx} generated "
            "tokens"
        )
        err.__cause__ = cause
        self._slots[req.slot] = None
        self._kv.release(req.admission)
        req.admission = None
        self._release_draft(req)
        if not req.future.done():
            req.future.set_exception(err)
        self._bump("requests_poisoned")
        self.logger.error("%s", err)

    def _die(self, exc: BaseException) -> None:
        """Process a :meth:`hard_kill` on the scheduler thread: fail every
        queued and in-flight request with the replica-level error and
        close.  The router's done-callbacks see the error, classify it as
        replica loss, and fail the requests over to a survivor."""
        self.logger.error("replica hard-killed: %s", exc)
        self._bump("replica_down")
        # flags first: once _dead is visible, export/import verbs refuse
        # new work, so the _fail_inflight drain below cannot race a KV
        # transfer into a queue nobody will ever service again
        with self._cond:
            self._die_exc = None
            self._dead = True
            self._closed = True
            self._cond.notify_all()
        self._fail_inflight(exc)

    def _fail_inflight(self, exc: BaseException) -> None:
        """A device error poisons every in-flight request (their pool
        state is unknown); queued requests are failed too rather than
        retried into the same error."""
        # in-flight async steps die with the requests they belong to
        self._inflight.clear()
        self._carry_tok = None
        with self._cond:
            doomed = [s for s in self._slots if s is not None]
            doomed.extend(self._queue)
            self._queue.clear()
            self._slots = [None] * self.slots_n
            doomed_xfer = list(self._xfer_q)
            self._xfer_q.clear()
        # pending KV transfers die with the engine state they index; the
        # disagg coordinator catches the failure and degrades to local
        # recompute — a transfer error never fails a serving request
        for _verb, _arg, xfut in doomed_xfer:
            if not xfut.done():
                xfut.set_exception(exc)
        for req in doomed:
            if req.admission is not None:
                self._kv.release(req.admission)
                req.admission = None
            self._release_draft(req)
            if not req.future.done():
                req.future.set_exception(exc)
        if doomed:
            self._bump("failed_inflight", len(doomed))

    def _rebuild_and_requeue(self) -> None:
        """Hot-restart: rebuild the compiled programs and the pool, then
        push every in-flight request back onto the queue head (FCFS order
        preserved) for replay admission.  Queued requests ride along
        untouched.  Runs on the scheduler thread (inside tick's except)."""
        # the ring indexes the dead pool/programs: discard it outright
        # (the requeued requests replay their host-known streams, and the
        # discarded steps' tokens were never delivered)
        self._inflight.clear()
        self._carry_tok = None
        self._zero_tok = None
        self._last_dispatch = None
        with self._cond:
            inflight = [s for s in self._slots if s is not None]
            self._slots = [None] * self.slots_n
            for req in reversed(inflight):
                # the reservation indexes the DEAD pool: drop it without
                # release — allocator and prefix cache are rebuilt below
                req.admission = None
                req.draft_admission = None
                req.slot = -1
                req.dispatched = req.gen_idx
                self._queue.appendleft(req)
        self._fns = self._build_fns()
        self._kv = PagedKVPool(
            self._num_blocks, self._block_size, self._prefix_cache
        )
        self._pool = self._fns.init_pool(self.params)
        if self._pool_sharding is not None:
            self._pool = jax.device_put(self._pool, self._pool_sharding)
        if self._spec is not None:
            # the draft side restarts with the target: fresh programs,
            # fresh pool, fresh allocator (requests re-prefill both)
            self._build_draft()
        if self._watchdog is not None:
            # the rebuilt programs recompile on first use — re-enter
            # warmup or the compile stall reads as another hang
            self._watchdog.reset()

    def _build_draft(self) -> None:
        """(Re)build the speculative draft side: its own compiled program
        set over its OWN paged pool (prefix cache off — draft K/V and
        target K/V must never share rows, and draft blocks are private to
        their request).  The draft is always greedy regardless of the
        engine temperature; speculative mode itself requires greedy."""
        self._draft_fns = build_paged_fns(
            self._draft_model, self._block_size, self._num_blocks,
            temperature=0.0,
        )
        self._dkv = PagedKVPool(
            self._num_blocks, self._block_size, prefix_cache=False
        )
        self._draft_pool = self._draft_fns.init_pool(self._draft_params)
        if self._pool_sharding is not None:
            self._draft_pool = jax.device_put(
                self._draft_pool, self._pool_sharding
            )

    def _on_tick_hang(self, step: int, elapsed: float, limit: float) -> None:
        # runs on the watchdog monitor thread: record the diagnosis; the
        # scheduler thread raises HungTickError when the tick returns
        with self._cond:
            self._hang_info = (int(step), float(elapsed), float(limit))
        self._bump("serve_watchdog_fires")

    # ------------------------------------------------------------------ #

    def _next_wakeup_locked(self) -> float:
        """Sleep bound while head-of-line blocked: wake for the nearest
        queued (or drain) deadline, else poll the pool at 50 ms."""
        now = time.monotonic()
        deadlines = [r.deadline for r in self._queue if r.deadline is not None]
        if self._draining and self._drain_deadline is not None:
            deadlines.append(self._drain_deadline)
        if not deadlines:
            return 0.05
        return min(0.05, max(min(deadlines) - now, 0.001))

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not (
                    self._closed
                    or self._die_exc is not None
                    or self._hang_sec is not None
                    or self._queue
                    or self._xfer_q
                    or any(s is not None for s in self._slots)
                ):
                    # loop_idle: only where the loop really sleeps, so a
                    # reader tells "nothing to do" from the overhead
                    # between two back-to-back ticks
                    if self.heartbeat_path is None:
                        with span("loop_idle"):
                            self._cond.wait()
                    else:
                        # bounded wait so an IDLE healthy replica keeps
                        # beating — external staleness must mean "wedged",
                        # never "merely quiet"
                        with span("loop_idle"):
                            self._cond.wait(
                                timeout=max(self._hb_interval / 2.0, 0.01)
                            )
                        self._beat()
                if (
                    self._closed
                    and not self._queue
                    and not self._xfer_q
                    and all(s is None for s in self._slots)
                ):
                    return
            try:
                did = self.tick()
            except BaseException as exc:  # supervisor itself failed
                self.logger.exception("scheduler tick failed beyond recovery")
                self._fail_inflight(exc)
                did = True
            with self._cond:
                self._cond.notify_all()  # drain()/close() watchers
                if not did and not self._closed and self._queue:
                    # head-of-line blocked on pool admission with nothing
                    # decoding: sleep until a deadline can expire or the
                    # state changes instead of spinning on admit attempts
                    # (this is also what guarantees an admission-waiting
                    # request is swept AT its deadline, not at the next
                    # submit)
                    with span("loop_idle"):
                        self._cond.wait(timeout=self._next_wakeup_locked())
