"""InferenceEngine: orbax checkpoint -> compiled, batched inference.

The serving mirror of ``engine/runner.py``: build the model from the same
``model:`` config section a training run used, restore the forward-pass
leaves of its checkpoint (:func:`..engine.checkpoint.load_serving_state`),
and compile one jit program per shape bucket.  Requests of any size/length
are padded UP to a bucket, so the number of XLA compiles is bounded by
``len(batch_buckets) * len(seq_buckets)`` (classification: just
``len(batch_buckets)``) no matter what traffic looks like — the serving
analog of the fixed-shape training step.

Batch buckets are rounded up to multiples of the mesh data-axis size so
every program shards its batch the way the training step did
(``parallel/mesh.py``); compute runs in the serving dtype (default bf16,
the paper's mixed-precision stance) with f32 logits.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.checkpoint import load_serving_state
from ..engine.steps import _input_normalizer
from ..models import get_model
from ..ops.quant import quantize_tree
from ..parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    make_mesh,
    replicated_sharding,
)
from .batcher import DynamicBatcher, Request
from ..ops.attention import WINDOW_LEAVES, is_state_leaf
from .decode import build_generate_fn
from .lora import LoraRegistry
from .metrics import ServingMetrics
from .prefill_plan import LinearCost, bucket_for
from .scheduler import ContinuousScheduler
from .speculative import SpeculativeSpec

__all__ = ["InferenceEngine"]

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


class InferenceEngine:
    """Restore a checkpoint and serve it through a dynamic batcher.

    Use :meth:`from_config`; ``submit`` returns a future per request:

      - LM (a model whose class states ``is_language_model``): payload is a 1-D int token prompt; result
        ``{"tokens": int32 [gen_len], "gen_len": int}``.
      - classification (ResNet/ViT): payload is one HWC image
        (uint8, normalized in-graph; or pre-normalized float32); result
        ``{"label": int, "logits": float32 [n_classes]}``.
    """

    def __init__(
        self,
        model,
        params,
        batch_stats,
        mesh,
        *,
        is_lm: bool,
        batch_buckets: Sequence[int],
        seq_buckets: Sequence[int],
        max_batch_size: int,
        max_delay_ms: float,
        deadline_ms: Optional[float] = None,
        max_backlog: Optional[int] = None,
        max_new_tokens: int = 0,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        image_size: int = 0,
        input_norm=None,
        seed: int = 0,
        scheduler: Optional[Dict[str, Any]] = None,
        resilience: Optional[Dict[str, Any]] = None,
        quant: Optional[Dict[str, Any]] = None,
        lora: Optional[Dict[str, Any]] = None,
        speculative: Optional[Dict[str, Any]] = None,
        logger: Optional[logging.Logger] = None,
        replica_id: Optional[int] = None,
        heartbeat_path: Optional[str] = None,
        heartbeat_interval_s: float = 0.5,
        liveness_timeout_s: Optional[float] = None,
    ):
        self.model = model
        self.mesh = mesh
        self.is_lm = is_lm
        self.max_new_tokens = max_new_tokens
        self.image_size = image_size
        self.logger = logger or logging.getLogger(__name__)
        # fleet identity (serving/fleet.py): namespaces this engine's
        # process-registry mirror and names its heartbeat file; None =
        # the historical single-replica engine, byte-identical behavior
        self.replica_id = replica_id
        self.heartbeat_path = heartbeat_path
        self.metrics = ServingMetrics(replica_id)
        n_data = mesh.shape[DATA_AXIS]
        self.batch_buckets = sorted({_round_up(b, n_data) for b in batch_buckets})
        self.seq_buckets = sorted(set(int(s) for s in seq_buckets))
        # stacked decode-path modes (serving.quant / serving.lora /
        # serving.speculative), each parsed with the scheduler block's
        # copy-pop-raise idiom so a typo'd key fails at build time
        quant_cfg = dict(quant or {})
        use_quant = bool(quant_cfg.pop("enabled", False))
        if quant_cfg:
            raise ValueError(f"unknown serving.quant keys: {sorted(quant_cfg)}")
        lora_cfg = dict(lora or {})
        use_lora = bool(lora_cfg.pop("enabled", False))
        lora_rank = int(lora_cfg.pop("rank", 8))
        lora_adapters = lora_cfg.pop("adapters", None)
        if lora_cfg:
            raise ValueError(f"unknown serving.lora keys: {sorted(lora_cfg)}")
        spec_cfg = dict(speculative or {})
        use_spec = bool(spec_cfg.pop("enabled", False))
        spec_k = int(spec_cfg.pop("k", 4))
        spec_draft = spec_cfg.pop("draft", None)
        spec_draft_seed = int(spec_cfg.pop("draft_seed", 0))
        spec_min_acceptance = float(spec_cfg.pop("min_acceptance", 0.0))
        if spec_cfg:
            raise ValueError(
                f"unknown serving.speculative keys: {sorted(spec_cfg)}"
            )
        if not 0.0 <= spec_min_acceptance <= 1.0:
            raise ValueError(
                "serving.speculative.min_acceptance must be in [0, 1], "
                f"got {spec_min_acceptance}"
            )
        # the metrics object owns the one-shot floor warning: acceptance
        # is only measurable where spec_proposed/spec_accepted live
        self.metrics.spec_min_acceptance = spec_min_acceptance
        use_sched = is_lm and bool((scheduler or {}).get("enabled", False))
        if (use_quant or use_lora or use_spec) and not is_lm:
            raise ValueError("serving.quant/lora/speculative are LM-only")
        if (use_lora or use_spec) and not use_sched:
            raise ValueError(
                "serving.lora and serving.speculative require "
                "serving.scheduler.enabled — adapter multiplexing and "
                "draft verification live in the continuous scheduler's "
                "paged decode programs"
            )
        base_model = model
        # surfaced for logs/bench: which decode-path modes are on
        self.serving_modes = {
            "quant": use_quant, "lora": use_lora, "speculative": use_spec,
        }
        self.lora_registry: Optional[LoraRegistry] = None
        if use_lora:
            self.lora_registry = LoraRegistry(lora_rank, lora_adapters)
            model, params = self.lora_registry.graft(model, params)
            self.model = model
            self.logger.info(
                "multi-LoRA serving: rank %d, adapters %s",
                self.lora_registry.rank, self.lora_registry.names,
            )
        if is_lm:
            if not self.seq_buckets:
                raise ValueError("LM serving needs at least one seq bucket")
            worst = self.seq_buckets[-1] + max_new_tokens
            if worst > model.max_len:
                raise ValueError(
                    f"largest seq bucket {self.seq_buckets[-1]} + "
                    f"max_new_tokens {max_new_tokens} = {worst} exceeds "
                    f"model max_len {model.max_len}"
                )
            # the contiguous generate pair is the batcher path's; a model
            # that carries a state a sequence has none (build_generate_fn
            # refuses it with the reason) and is served by the scheduler
            self._generate = None
            if not (use_sched and getattr(model, "state_shape", None) is not None):
                self._generate = build_generate_fn(
                    model, max_new_tokens, temperature=temperature,
                    eos_id=eos_id, quant=use_quant,
                )
        else:
            normalize = _input_normalizer(input_norm)

            @jax.jit
            def classify(params, batch_stats, img):
                img = normalize(img)
                variables = {"params": params}
                if batch_stats:
                    variables["batch_stats"] = batch_stats
                return model.apply(variables, img, train=False)

            self._classify = classify
        # params live on-device replicated for the engine's lifetime — the
        # per-batch device_put only moves the (small) padded inputs
        rep = replicated_sharding(mesh)
        self.params = jax.device_put(params, rep)
        self.batch_stats = (
            jax.device_put(batch_stats, rep) if batch_stats else {}
        )
        # int8 decode (serving.quant) on the BATCHER path: quantize once
        # at build and hand the int8 tree to the decode phase only; the
        # scheduler path quantizes its own copy (serving/scheduler.py)
        self._decode_params = None
        if use_quant and is_lm and not use_sched:
            self._decode_params = jax.device_put(
                quantize_tree(self.params), rep
            )
        self._rng = jax.random.PRNGKey(seed)
        self._batch_counter = 0
        # continuous batching (serving.scheduler.enabled): the LM decode
        # loop moves to the iteration-level scheduler over the paged KV
        # pool; the DynamicBatcher path stays the default (and the only
        # path for classification and multi-host serving)
        sched_cfg = dict(scheduler or {})
        use_sched = is_lm and bool(sched_cfg.pop("enabled", False))
        self.scheduler: Optional[ContinuousScheduler] = None
        self.batcher: Optional[DynamicBatcher] = None
        if use_sched:
            spec = None
            if use_spec:
                if spec_draft is not None:
                    # the draft clones the BASE model (never the LoRA
                    # graft: a draft miss only costs acceptance) with the
                    # config's field overrides, random-init like the
                    # checkpoint-less smoke mode — restoring a trained
                    # draft checkpoint is ROADMAP work
                    draft_model = base_model.clone(**dict(spec_draft))
                    draft_params = jax.device_put(
                        draft_model.init(
                            jax.random.PRNGKey(spec_draft_seed),
                            jnp.zeros((1, 1), jnp.int32),
                        )["params"],
                        rep,
                    )
                    spec = SpeculativeSpec(spec_k, draft_model, draft_params)
                else:
                    spec = SpeculativeSpec(spec_k)
            sched_keys = dict(
                slots=int(sched_cfg.pop("slots", 8)),
                block_size=int(sched_cfg.pop("block_size", 16)),
                num_blocks=int(sched_cfg.pop("num_blocks", 64)),
                prefix_cache=bool(sched_cfg.pop("prefix_cache", True)),
            )
            if sched_cfg:  # before a scheduler and its thread exist
                raise ValueError(
                    f"unknown serving.scheduler keys: {sorted(sched_cfg)}"
                )
            self.scheduler = ContinuousScheduler(
                model, self.params, **sched_keys,
                batch_buckets=self.batch_buckets,
                seq_buckets=self.seq_buckets,
                max_new_tokens=max_new_tokens,
                temperature=temperature,
                eos_id=eos_id,
                deadline_ms=deadline_ms,
                max_backlog=max_backlog,
                metrics=self.metrics,
                seed=seed,
                pool_sharding=rep,
                resilience=resilience,
                quant=use_quant,
                lora=self.lora_registry,
                speculative=spec,
                # the ring of depth 1: tick k dispatches step k before it
                # reads step k-1.  A speculative round reads its own verify
                # before the next is proposed: nothing to hold in a ring
                async_depth=0 if spec is not None else 1,
                logger=self.logger,
                replica_id=replica_id,
                heartbeat_path=heartbeat_path,
                heartbeat_interval_s=heartbeat_interval_s,
                liveness_timeout_s=liveness_timeout_s,
            )
        else:
            if resilience is not None:
                raise ValueError(
                    "serving.resilience requires serving.scheduler.enabled "
                    "— the batcher path has no supervisor (poison-bisect, "
                    "hot-restart and replay all live in the continuous "
                    "scheduler)"
                )
            self.batcher = DynamicBatcher(
                self._run_batch, max_batch_size, max_delay_ms,
                deadline_ms=deadline_ms, max_backlog=max_backlog,
                # degradation events land in the same metrics ledger as
                # latency/throughput, so one snapshot tells the whole story
                on_timeout=lambda: self.metrics.incr("timeouts"),
                on_shed=lambda: self.metrics.incr("sheds"),
            )

    # ------------------------------------------------------------------ #

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], logger=None) -> "InferenceEngine":
        """Build from a ``serve-*.yml`` config (see config_parsing)."""
        model, params, batch_stats, mesh, kwargs = cls.resolve_config(
            cfg, logger
        )
        return cls(model, params, batch_stats, mesh, **kwargs)

    @classmethod
    def resolve_config(cls, cfg: Dict[str, Any], logger=None):
        """Resolve a ``serve-*.yml`` config into constructor ingredients.

        Returns ``(model, params, batch_stats, mesh, kwargs)`` so callers
        that build SEVERAL engines from one checkpoint (the serving fleet
        — N replicas share one restored parameter tree and one mesh) pay
        the restore/init exactly once and stamp each replica's identity
        into a copy of ``kwargs``.
        """
        logger = logger or logging.getLogger(__name__)
        serve = cfg["serving"]
        dtype_name = serve.get("dtype", "bfloat16")
        if dtype_name not in _DTYPES:
            raise ValueError(
                f"serving.dtype must be one of {sorted(_DTYPES)}, got {dtype_name!r}"
            )
        dtype = _DTYPES[dtype_name]
        model_cfg = dict(cfg["model"])
        model_name = model_cfg.pop("name")
        n_classes = cfg["dataset"]["n_classes"]
        model = get_model(model_name, num_classes=n_classes, dtype=dtype, **model_cfg)
        # the model's class says what it is; no name is compared
        is_lm = bool(getattr(model, "is_language_model", False))

        mesh = make_mesh()
        ckpt_dir = serve.get("checkpoint")
        image_size = int(cfg["dataset"].get("image_size", 224))
        if ckpt_dir:
            params, batch_stats, step = load_serving_state(ckpt_dir, logger)
            logger.info("Serving %s from checkpoint iter %d", model_name, step)
        else:
            # smoke / bench mode: random init, loudly
            logger.warning(
                "serving.checkpoint not set — serving RANDOM-INIT %s "
                "weights (smoke/bench mode only)", model_name
            )
            rng = jax.random.PRNGKey(int(serve.get("seed", 0)))
            if is_lm:
                seq = min(int(s) for s in serve.get("seq_buckets", [16]))
                init_in = jnp.zeros((1, seq), jnp.int32)
            else:
                init_in = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
            variables = model.init(rng, init_in)
            params = variables["params"]
            batch_stats = variables.get("batch_stats", {})

        max_batch = int(serve.get("max_batch_size", 8))
        input_norm = None
        if not is_lm and serve.get("normalize", True):
            from ..data.datasets import IMAGENET_MEAN, IMAGENET_STD

            input_norm = (IMAGENET_MEAN, IMAGENET_STD)
        kwargs = dict(
            is_lm=is_lm,
            batch_buckets=serve.get("batch_buckets", [max_batch]),
            seq_buckets=serve.get("seq_buckets", [16]),
            max_batch_size=max_batch,
            max_delay_ms=float(serve.get("max_delay_ms", 5.0)),
            deadline_ms=(
                float(serve["deadline_ms"])
                if serve.get("deadline_ms") is not None else None
            ),
            max_backlog=(
                int(serve["max_backlog"])
                if serve.get("max_backlog") is not None else None
            ),
            max_new_tokens=int(serve.get("max_new_tokens", 16)),
            temperature=float(serve.get("temperature", 0.0)),
            eos_id=serve.get("eos_id"),
            image_size=image_size,
            input_norm=input_norm,
            seed=int(serve.get("seed", 0)),
            scheduler=serve.get("scheduler"),
            resilience=serve.get("resilience"),
            quant=serve.get("quant"),
            lora=serve.get("lora"),
            speculative=serve.get("speculative"),
            logger=logger,
        )
        return model, params, batch_stats, mesh, kwargs

    # ------------------------------------------------------------------ #

    def submit(
        self,
        payload,
        deadline_ms: Optional[float] = None,
        max_new_tokens: Optional[int] = None,
        on_token=None,
        rng=None,
        replay_tokens=None,
        adapter: Optional[str] = None,
    ):
        """Validate + enqueue one request; returns its result future.

        ``deadline_ms`` overrides the engine's default per-request
        deadline (``serving.deadline_ms``); past it an unflushed request
        resolves with ``TimeoutError``.  LM-only extras: ``max_new_tokens``
        caps this request below ``serving.max_new_tokens`` (on the
        batcher path the result is truncated host-side — the batch still
        pays the full decode; the scheduler path retires the slot the
        moment the cap is hit), ``on_token``/``rng`` stream tokens /
        override the sampling key and need the continuous scheduler, and
        ``adapter`` routes the request through a registered LoRA adapter
        (``serving.lora``, scheduler path only).
        """
        if self.is_lm:
            prompt = np.asarray(payload, np.int32)
            if prompt.ndim != 1 or prompt.size < 1:
                raise ValueError(
                    f"LM payload must be a non-empty 1-D token sequence, "
                    f"got shape {prompt.shape}"
                )
            if prompt.size > self.seq_buckets[-1]:
                raise ValueError(
                    f"prompt length {prompt.size} exceeds largest seq "
                    f"bucket {self.seq_buckets[-1]}"
                )
            if max_new_tokens is not None and not (
                1 <= int(max_new_tokens) <= self.max_new_tokens
            ):
                raise ValueError(
                    f"max_new_tokens must be in [1, {self.max_new_tokens}], "
                    f"got {max_new_tokens}"
                )
            if self.scheduler is not None:
                return self.scheduler.submit(
                    prompt, deadline_ms=deadline_ms,
                    max_new_tokens=max_new_tokens, on_token=on_token, rng=rng,
                    replay_tokens=replay_tokens, adapter=adapter,
                )
            if (
                on_token is not None or rng is not None or replay_tokens
                or adapter is not None
            ):
                raise ValueError(
                    "on_token / per-request rng / replay_tokens / adapter "
                    "require serving.scheduler.enabled (the batcher path "
                    "samples whole batches and resolves futures only at "
                    "the end)"
                )
            return self.batcher.submit(
                prompt, deadline_ms=deadline_ms,
                max_new=(int(max_new_tokens) if max_new_tokens else None),
            )
        if (
            max_new_tokens is not None or on_token is not None
            or rng is not None or replay_tokens or adapter is not None
        ):
            raise ValueError(
                "max_new_tokens/on_token/rng/replay_tokens/adapter are "
                "LM-only"
            )
        img = np.asarray(payload)
        want = (self.image_size, self.image_size, 3)
        if img.shape != want:
            raise ValueError(f"image payload must have shape {want}, got {img.shape}")
        return self.batcher.submit(img, deadline_ms=deadline_ms)

    def depth(self) -> int:
        if self.scheduler is not None:
            return self.scheduler.depth()
        return self.batcher.depth()

    def compile_count(self) -> int:
        """Number of distinct XLA programs compiled so far (<= bucket grid)."""
        if self.scheduler is not None:
            return self.scheduler.compile_count()
        fn = self._generate if self.is_lm else self._classify
        return fn._cache_size()

    def drain(self, deadline_ms: Optional[float] = None) -> float:
        """Graceful shutdown: stop admitting, finish in-flight, close.

        Returns wall ms spent.  On the scheduler path the drain is
        deadline-bounded (``serving.resilience.drain_deadline_ms`` or the
        override); the batcher path has no admission gate beyond
        ``close()``'s synchronous flush, so drain == close there.
        """
        if self.scheduler is not None:
            return self.scheduler.drain(deadline_ms)
        import time

        t0 = time.monotonic()
        self.batcher.close()
        return (time.monotonic() - t0) * 1000.0

    def health(self) -> Dict[str, Any]:
        """Readiness/liveness snapshot for orchestration probes."""
        if self.scheduler is not None:
            return self.scheduler.health()
        return {
            "ready": True,
            "live": True,
            "queue_depth": self.batcher.depth(),
        }

    def warmup(self) -> Dict[str, float]:
        """Compile every program the engine can ever run, NOW.

        A freshly restored engine pays its XLA compiles on first traffic
        — which is exactly when an autoscaler scale-up needs the new
        replica to absorb load, so cold-compile latency lands in client
        TTFT at the worst possible moment.  Warmup drives one throwaway
        call through each (batch-bucket × seq-bucket) prefill program and
        each decode-phase program instead: scheduler-path calls use
        all-``-1`` positions (every pool scatter drops — the OOB idiom),
        so the pool's contents do not change.  The programs consume the
        pool they are given, so the warm-up threads the scheduler's own
        pool through its calls and hands the last one back: it belongs
        BEFORE traffic, and is refused while the scheduler holds work.

        Returns ``{"warmup_ms", "programs"}`` (programs = compile-count
        delta, 0 when everything was already warm — warmup is
        idempotent).  On the scheduler path it also sets the gauges
        ``pool_aliased_bytes`` of ``metrics.snapshot()``, how much of the
        pool the programs update in place, and ``decode_program_temp_bytes``,
        the temporaries of the compiled decode step (a whole pool leaf that
        a step copies shows here, a leaf's bytes a copy, where the alias
        cannot: the output reuses the input's buffer and is still written by
        a copy), and logs them beside the program count; and the gauges
        ``kv_pool_bytes`` and ``state_cache_bytes``, the cache tree's token
        rows and its per-slot state (0 for a model that carries none), whose
        sum a full donation aliases.  ``ServingFleet.add_replica`` calls
        this before routing traffic to a new replica and publishes the wall
        time as the ``scale_up_ready_ms`` gauge.
        """
        import time

        t0 = time.perf_counter()
        before = self.compile_count()
        compiled = {}
        if not self.is_lm:
            self._warmup_classify()
        elif self.scheduler is not None:
            compiled = self._warmup_scheduler()
        else:
            self._warmup_batcher()
        warmed = self.compile_count() - before
        ms = (time.perf_counter() - t0) * 1000.0
        self.logger.info(
            "engine warmup: %d program(s) compiled in %.0f ms%s", warmed, ms,
            "".join(f", {name}={n}" for name, n in compiled.items()),
        )
        return {"warmup_ms": ms, "programs": float(warmed)}

    def _compile_side_by_side(self, calls) -> None:
        """Cold start: a warm-up's programs are independent, so their
        compiles run side by side (XLA releases the interpreter lock while
        it compiles) and land in JAX's persistent cache, from which the
        calls that follow read them back.  Seven programs of a seven-layer
        expert model compiled one after another took 138 s of a cold start.
        Without that cache a compile made here could not be reused, so
        nothing is done.  Lowering reads shapes only: the pool among the
        arguments is not consumed."""
        if len(calls) < 2 or not jax.config.jax_compilation_cache_dir:
            return
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=min(len(calls), 8)) as pool:
            list(pool.map(lambda c: c[0].lower(*c[1]).compile(), calls))

    def _warm_pool_programs(self, calls, sched, attr):
        """Run every ``(program, arguments before the pool, arguments after
        it, index of the pool among the outputs)`` once on the scheduler's
        pool ``attr``.  The programs consume the pool they are given
        (``decode.py``), so each call's returned pool is rebound there and
        is the next call's argument.  Returns each program's memory
        analysis, in the calls' order (of its compiled form; None where the
        backend does not say)."""
        pool = getattr(sched, attr)
        self._compile_side_by_side(
            [(fn, (*head, pool, *tail)) for fn, head, tail, _ in calls]
        )
        accounts = []
        for fn, head, tail, at in calls:
            out = fn(*head, pool, *tail)
            pool = out if at is None else out[at]
            setattr(sched, attr, pool)
            jax.block_until_ready(pool)
            # compiled by the call above: this only reads it back
            accounts.append(fn.lower(*head, pool, *tail).compile().memory_analysis())
        return accounts

    def _fit_prefill_cost(self, sched, grid) -> None:
        """Tell the scheduler what a prefill call costs HERE, from a second,
        timed run of two programs the warm-up has just compiled: the
        smallest of the grid and its neighbour along the sequence buckets
        (the longest; along the batch buckets where there is one sequence
        bucket).  Each is timed as a tick runs it, from the dispatch to the
        read of its token, and the line through the two points is the
        estimate by which the scheduler groups a tick's admissions into
        calls (``ContinuousScheduler.set_prefill_cost``).  The timed inputs
        are a full call's, not the warm-up's padding: token ids drawn over
        the vocabulary at LIVE positions, because an expert layer leaves a
        padding position's pairs out of its grouped products and a call of
        all padding then runs a fraction of a real one's (PERF.md, PR 44:
        9.0 ms for 21.2).  The pool's contents still do not change: every
        block-table entry is one past the pool (each scatter drops) and
        every row's state slot is -1.  A grid of one program has nothing
        to choose between and is not timed."""
        import time

        bb, sb = sched.batch_buckets, sched.seq_buckets
        if len(sb) > 1:
            pair = [(bb[0], sb[0]), (bb[0], sb[-1])]
        elif len(bb) > 1:
            pair = [(bb[0], sb[0]), (bb[1], sb[0])]
        else:
            return
        points = []
        rng = np.random.default_rng(0)
        for rows, length in pair:
            # (tokens, positions, tables, last_col, *the padding call's rest)
            fn, head, (_, _, tables, _, *rest), _ = grid[rows, length]
            full = (
                rng.integers(
                    0, self.model.vocab_size, (rows, length), dtype=np.int32
                ),
                np.tile(np.arange(length, dtype=np.int32), (rows, 1)),
                np.full_like(tables, sched._kv.num_blocks),
                np.full((rows,), length - 1, np.int32),
            )
            t0 = time.perf_counter()
            tok, finite, sched._pool = fn(*head, sched._pool, *full, *rest)
            np.asarray(tok), np.asarray(finite)
            points.append((rows * length, (time.perf_counter() - t0) * 1e3))
        sched.set_prefill_cost(*LinearCost.through(*points))

    def _warmup_scheduler(self) -> Dict[str, int]:
        """Returns the gauges it set from the compiled programs' memory
        analyses, by name (none where the backend does not say):
        ``pool_aliased_bytes``, the fewest bytes any program updates in
        place (``alias_size_in_bytes``: the whole pool where the donation
        took), and ``decode_program_temp_bytes``, the decode step's
        ``temp_size_in_bytes`` (its alone: a large prefill bucket's
        ``[B, H, S, L]`` scores would drown a copied leaf)."""
        sched = self.scheduler
        sched.require_idle()
        T = sched.table_blocks
        W = sched.slots_n
        # the tick's own kind of argument (a step with no live row; its
        # keys a host uint32 [n, 2] array), so that each program's jit
        # cache holds the ONE entry the ticks hit
        step = sched._step_inputs(())

        def no_slot(n):
            # a model that carries a state: every row's slot is -1 (padding)
            return sched._state_rows(np.full((n,), -1, np.int32))

        def prefills(fns, params):
            # {(batch bucket, sequence bucket): the program's call}
            return {
                (bb, sb): (fns.prefill, (params,), (
                    np.zeros((bb, sb), np.int32),
                    np.full((bb, sb), -1, np.int32),
                    np.zeros((bb, T), np.int32),
                    np.zeros((bb,), np.int32), sched._pad_keys(bb),
                    np.zeros((bb,), np.int32),
                    np.full((bb,), -1, np.int32), *no_slot(bb),
                ), 2)
                for bb in sched.batch_buckets for sb in sched.seq_buckets
            }

        def decode(fns, params):
            # ONE decode program a model, whoever calls it: _zero_carry
            # matches the program's own token-output sharding, so this
            # call covers the ring's first and carried dispatches and the
            # probe's and the replay's (one cache entry)
            return (fns.decode_step, (params,), (
                sched._zero_carry(), *step, *no_slot(W),
            ), 2)

        fns = sched._fns
        dparams = sched._qparams if sched._quant else sched.params
        grid = prefills(fns, sched.params)
        calls = [*grid.values(), decode(fns, dparams)]
        if sched._spec is not None:
            # the speculative round's extra programs on the target side:
            # the verify scorer and the fork's row copy
            k = sched._spec.k
            n_rows = sched._kv.num_blocks * sched._kv.block_size
            oob = np.full((W * sched._kv.block_size,), n_rows, np.int32)
            calls.append((fns.verify, (sched.params,), (
                np.zeros((W, k + 1), np.int32),
                np.full((W, k + 1), -1, np.int32), step.tables, step.aids,
            ), 1))
            calls.append((fns.copy_rows, (), (oob, oob), None))
        accounts = self._warm_pool_programs(calls, sched, "_pool")
        self._fit_prefill_cost(sched, grid)
        flash_layers = max(map(sched._flash_layers, sched.seq_buckets))
        if flash_layers:  # a gauge that is 0 is left out, as a counter is
            self.metrics.record_prefill_flash_layers(flash_layers)
        if sched._spec is not None:
            # ... and the draft model's own prefill/decode set over its pool
            dfns, dparams = sched._draft_fns, sched._draft_params
            self._warm_pool_programs(
                [*prefills(dfns, dparams).values(), decode(dfns, dparams)],
                sched, "_draft_pool",
            )
        compiled = {}
        if None not in accounts:
            compiled = {
                "pool_aliased_bytes": min(
                    int(mem.alias_size_in_bytes) for mem in accounts),
                # the grid's prefills come first, then the decode step
                "decode_program_temp_bytes": int(
                    accounts[len(grid)].temp_size_in_bytes),
            }
            self.metrics.record_pool_programs(**compiled)
        # the cache tree's two kinds of leaf, in bytes: the pool's token rows
        # and (a model that carries a state) the per-slot state beside them
        flat = jax.tree_util.tree_flatten_with_path(sched._pool)[0]
        state = sum(int(leaf.nbytes) for path, leaf in flat if is_state_leaf(path))
        self.metrics.record_cache_bytes(
            kv_pool_bytes=sum(int(leaf.nbytes) for _, leaf in flat) - state,
            state_cache_bytes=state,
            pool_rows=sched._kv.num_blocks * sched._kv.block_size,
            # the rings' part of ``state``: a leaf a window layer declared
            window_ring_bytes=sum(
                int(leaf.nbytes) for path, leaf in flat
                if getattr(path[-1], "key", None) in WINDOW_LEAVES),
        )
        return compiled

    def _warmup_batcher(self) -> None:
        """Batcher-path warmup: one (prefill, decode) execution per
        (batch, seq) bucket pair through the exact ``_run_lm`` shapes.
        Decode here actually runs its while_loop (bounded by
        ``max_new_tokens``) — warmup cost is dominated by the compiles
        it exists to front-load."""
        tok_sh = batch_sharding(self.mesh, 2)
        row_sh = batch_sharding(self.mesh, 1)
        rng = jax.random.PRNGKey(0)
        dp = (
            self.params if self._decode_params is None
            else self._decode_params
        )
        for bb in self.batch_buckets:
            plen = jax.device_put(np.ones((bb,), np.int32), row_sh)
            for sb in self.seq_buckets:
                carry = self._generate.prefill(
                    self.params,
                    jax.device_put(np.zeros((bb, sb), np.int32), tok_sh),
                    plen, rng,
                )
                out, _gen = self._generate.decode(dp, plen, carry)
                jax.block_until_ready(out)

    def _warmup_classify(self) -> None:
        for bb in self.batch_buckets:
            img = np.zeros(
                (bb, self.image_size, self.image_size, 3), np.float32
            )
            jax.block_until_ready(
                self._classify(
                    self.params, self.batch_stats,
                    jax.device_put(img, batch_sharding(self.mesh, 4)),
                )
            )

    def install_drain_handler(self, signum=None) -> None:
        """Route SIGTERM (or ``signum``) to a graceful :meth:`drain`.

        The handler only spawns a daemon thread — drain joins the
        scheduler thread, which a signal handler must not do inline
        (handlers run ON the main thread, possibly inside scheduler-
        adjacent code).  Call from the main thread (signal.signal's own
        requirement).
        """
        import signal
        import threading

        signum = signal.SIGTERM if signum is None else signum

        def _handler(sig, frame):
            self.logger.warning(
                "signal %s received — draining serving engine", sig
            )
            threading.Thread(
                target=self.drain, name="serving-drain", daemon=True
            ).start()

        signal.signal(signum, _handler)

    def close(self) -> None:
        if self.scheduler is not None:
            self.scheduler.close()
        else:
            self.batcher.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------ #

    def _next_rng(self):
        self._batch_counter += 1
        return jax.random.fold_in(self._rng, self._batch_counter)

    def _run_batch(self, requests: List[Request]) -> List[Any]:
        depth = self.batcher.depth()
        if self.is_lm:
            results, phase = self._run_lm(requests)
            n_items = int(sum(r["gen_len"] for r in results))
            self.metrics.record_batch(
                [r.enqueued_at for r in requests], n_items, depth,
                gen_lens=[r["gen_len"] for r in results], **phase,
            )
        else:
            results = self._run_images(requests)
            self.metrics.record_batch(
                [r.enqueued_at for r in requests], len(results), depth
            )
        return results

    def _run_lm(self, requests: List[Request]):
        import time

        lens = [req.payload.size for req in requests]
        bb = bucket_for(len(requests), self.batch_buckets, "batch size")
        sb = bucket_for(max(lens), self.seq_buckets, "prompt length")
        tokens = np.zeros((bb, sb), np.int32)
        prompt_len = np.ones((bb,), np.int32)  # pad rows: 1-token dummy
        for i, req in enumerate(requests):
            tokens[i, : lens[i]] = req.payload
            prompt_len[i] = lens[i]
        tok_sh = batch_sharding(self.mesh, 2)
        row_sh = batch_sharding(self.mesh, 1)
        plen_dev = jax.device_put(prompt_len, row_sh)
        # phase-timed (round 6): prefill and decode are separate programs
        # (serving/decode.py), so each gets its own wall clock — the sync
        # between them is one block_until_ready on the carry, which the
        # decode dispatch would have waited on anyway
        t0 = time.perf_counter()
        carry = self._generate.prefill(
            self.params, jax.device_put(tokens, tok_sh), plen_dev,
            self._next_rng(),
        )
        jax.block_until_ready(carry)
        t1 = time.perf_counter()
        out, gen_len = self._generate.decode(
            self.params if self._decode_params is None
            else self._decode_params,
            plen_dev, carry,
        )
        out = np.asarray(out)  # host materialization = decode sync
        gen_len = np.asarray(gen_len)
        t2 = time.perf_counter()
        results = []
        for i, req in enumerate(requests):
            g = int(gen_len[i])
            # per-request cap on the batch path: TRUNCATE host-side — the
            # whole batch already paid the full decode loop, which is
            # precisely the pathology the continuous scheduler removes
            cap = req.meta.get("max_new")
            if cap:
                g = min(g, int(cap))
            results.append({"tokens": out[i, :g], "gen_len": g})
        phase = dict(
            prompt_tokens=int(sum(lens)), prefill_s=t1 - t0, decode_s=t2 - t1
        )
        return results, phase

    def _run_images(self, requests: List[Request]) -> List[Any]:
        bb = bucket_for(len(requests), self.batch_buckets, "batch size")
        first = requests[0].payload
        img = np.zeros((bb,) + first.shape, first.dtype)
        for i, req in enumerate(requests):
            img[i] = req.payload
        logits = self._classify(
            self.params,
            self.batch_stats,
            jax.device_put(img, batch_sharding(self.mesh, 4)),
        )
        logits = np.asarray(logits, np.float32)
        return [
            {"label": int(logits[i].argmax()), "logits": logits[i]}
            for i in range(len(requests))
        ]
