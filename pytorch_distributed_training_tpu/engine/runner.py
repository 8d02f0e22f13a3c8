"""The Runner: process orchestration + training loop.

Mirrors the reference's ``Runner`` (train_distributed.py:89-331) with the
same constructor surface and loop semantics, re-architected for TPU
(SURVEY.md §7 design stance): ONE controller process per host — no
``mp.spawn`` of one process per accelerator (boundary #2 of §3.1 collapses);
``--multiprocessing`` is accepted as a compat no-op.  Multi-host bootstrap
goes through ``jax.distributed.initialize`` (see ``parallel.distributed``),
after which the 2-D ``(data, model)`` mesh spans every chip of every host and
the compiled train step handles all cross-device communication in-graph.

Loop parity (reference line refs inline):
  - iteration-based outer loop with ``is_val()`` gating (:251-265),
  - ``train_iter``: one compiled step; loss is pmean-reduced in-graph and
    only synced to host at ``print_interval`` (:267-299); scheduler steps
    every iteration (:299),
  - ``validate``: per-batch compiled eval with in-graph pmean of
    loss/acc1/acc5, AverageMeter accumulation, rank-0 logging + TB (:301-331),
  - batch division: per-device batch = ``batch_size / local_device_count``
    (the reference divides by *local* GPU count, :194 — global batch scales
    with node count; replicated deliberately, SURVEY.md §7 stage 4).  The
    config-gated alternative ``training.batch_division: world`` divides by
    the world device count instead (cfg batch_size == global batch),
  - the val loader reuses the *training* batch size / workers (:235-241);
    the YAML ``validation:`` section stays dead (parity).

Additions beyond the reference (config-gated or additive-only, SURVEY.md §7
deviations): images/sec throughput metering (required by the north-star
metric), optional bf16 compute (``training.dtype: bfloat16``).
"""
from __future__ import annotations

import contextlib
import logging
import os
import time
from collections import deque
from logging.handlers import QueueHandler
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import tqdm

from ..config_parsing import validate_cfg
from ..data import (
    DataLoader,
    DistributedShardSampler,
    RandomSampler,
    SequentialSampler,
    device_prefetch,
    get_dataset,
)
from ..metrics import AverageMeter
from ..optimizers import get_optimizer
from ..parallel import initialize_distributed
from ..schedulers import get_scheduler
from ..utils import enable_compile_cache, make_deterministic, make_iter_dataloader
from ..telemetry import Telemetry
from . import fault
from .checkpoint import Checkpointer
from .elastic import ElasticCoordinator, PeerLostError
from .integrity import DivergedReplicaError, IntegritySentinel
from .paths import select_path
from .profiling import TraceProfiler
from .steps import TrainState
from .topology import (
    parse_batch,
    parse_elastic,
    parse_fault_tolerance,
    parse_integrity,
    parse_telemetry,
    parse_topology,
)
from .watchdog import StepWatchdog

__all__ = ["Runner"]


class Runner:
    """Drop-in counterpart of the reference Runner (train_distributed.py:89)."""

    def __init__(
        self,
        num_nodes: int,
        rank: int,
        seed: Optional[int],
        dist_url: str,
        dist_backend: str,
        multiprocessing: bool,
        logger_queue,
        global_cfg: dict,
        tb_writer_constructor: Callable,
    ):
        self.num_nodes = num_nodes
        self.rank = rank
        self.seed = seed
        self.dist_url = dist_url
        self.dist_backend = dist_backend
        self.multiprocessing = multiprocessing
        self.logger_queue = logger_queue
        self.global_cfg = validate_cfg(global_cfg)
        self.tb_writer_constructor = tb_writer_constructor
        self.iter: int = 0
        self.tb_writer = None
        self._telemetry: Optional[Telemetry] = None
        self._queue_handlers: list = []

    def __call__(self):
        logger = logging.getLogger("Runner")
        self._attach_queue(logger)
        logger.setLevel(logging.INFO)
        if self.multiprocessing:
            # Reference spawns one process per GPU here (:130-132); the TPU
            # runtime is single-controller-per-host, so the flag is a no-op.
            logger.info(
                "--multiprocessing requested: single-controller JAX runtime "
                "drives all local devices from one process (flag is a no-op)"
            )
        logger.info("Start from direct call")
        try:
            self.worker(0)
        finally:
            # the loggers are process-global by name: a handler left behind
            # would feed the NEXT run's records into this run's closed queue
            for named, handler in self._queue_handlers:
                named.removeHandler(handler)
            self._queue_handlers.clear()

    def _attach_queue(self, logger: logging.Logger) -> None:
        if self.logger_queue is not None:
            handler = QueueHandler(self.logger_queue)
            logger.addHandler(handler)
            self._queue_handlers.append((logger, handler))

    # ------------------------------------------------------------------ setup
    def worker(self, local_id: int):
        if self.seed is not None:
            make_deterministic(self.seed)  # same seed on all hosts (:141-142)

        if self.num_nodes is not None and self.num_nodes > 1:
            initialize_distributed(
                self.dist_url, self.num_nodes, self.rank, self.dist_backend
            )
        # confined: api — setup writes happen before any watchdog/callback
        # thread exists; _on_hang's cross-thread reads are best-effort
        # diagnostics on purpose
        self.current_rank = jax.process_index()  # confined: api
        self.world_size = jax.device_count()  # chips, not processes
        self.distributed = self.world_size > 1

        self.logger = logging.getLogger(f"worker_rank_{self.current_rank}")  # confined: api
        self.logger.propagate = False
        self._attach_queue(self.logger)
        self.logger.setLevel(logging.INFO)

        if self.current_rank == 0:
            self.tb_writer = self.tb_writer_constructor()

        device = jax.devices()[0]
        self.logger.info(
            "Use %d %s device(s) (%s) across %d process(es), current rank: %d",
            self.world_size,
            device.platform,
            device.device_kind,
            jax.process_count(),
            self.current_rank,
        )
        if (self.dist_backend or "").lower() == "tpu" and device.platform != "tpu":
            # the flag names a runtime, it does not pick one (CPU test runs
            # pass ``tpu`` too) — say so instead of letting the log imply a chip
            self.logger.warning(
                "--dist-backend tpu, but JAX found no TPU: running on %s",
                device.platform,
            )

        cfg = self.global_cfg
        train_cfg = cfg["training"]

        # Additive key ``training.compile_cache``: persistent XLA compilation
        # cache directory — the autotune analog of the reference's
        # ``cudnn.benchmark`` (train_distributed.py:54, SURVEY §2.3).  Set
        # BEFORE any step is built so the first jit of this process can
        # already hit a previous launch's entry.  Where the cache lands
        # (JAX_COMPILATION_CACHE_DIR wins; a relative path anchors at the
        # checkout) is utils.enable_compile_cache's rule.
        compile_cache = train_cfg.get("compile_cache")
        if compile_cache:
            path = enable_compile_cache(str(compile_cache))
            self.logger.info("Persistent XLA compilation cache at %s", path)

        ds_kwargs = dict(
            n_classes=cfg["dataset"]["n_classes"],
            image_size=cfg["dataset"].get("image_size", 224),
            n_samples=cfg["dataset"].get("n_samples"),
            seq_len=cfg["dataset"].get("seq_len"),
        )
        train_dataset = get_dataset(
            cfg["dataset"]["name"], cfg["dataset"]["root"], split="train", **ds_kwargs
        )
        val_dataset = get_dataset(
            cfg["dataset"]["name"], cfg["dataset"]["root"], split="val", **ds_kwargs
        )

        # Flags, parallelism degrees, cross-constraints + model construction
        # (engine/topology.py — extracted, semantics unchanged; every
        # documented config error lives there).
        parse_topology(self, cfg, train_cfg, train_dataset)
        host_batch = parse_batch(self, train_cfg)
        # ``training.comm`` (removed in PR 29) is refused, not ignored: its
        # explicit reduction counted the gradient world_size times.
        if "comm" in train_cfg:
            raise ValueError(
                "training.comm is no longer accepted: the step's own "
                "differentiation reduces the gradient, so overlap, bucket_mb "
                "and reduce_dtype have no replacement; for optimizer state "
                "sharded over the data axis (ZeRO-1) set training.zero: 1"
            )
        # Fault-tolerance keys (additive, all off by default) + the fault
        # injector: the PDT_FAULT_SPEC env var wins over the config key so a
        # chaos wrapper can override any run (engine/fault.py).
        parse_fault_tolerance(self, train_cfg)
        # Elastic multi-host recovery keys (additive, off by default):
        # heartbeat coordinator + peer-loss guard (engine/elastic.py).
        parse_elastic(self, train_cfg)
        # Unified telemetry keys (additive, in-memory layer on by default;
        # files only when dir is set — telemetry/ package, README
        # "Observability").
        parse_telemetry(self, train_cfg)
        # Integrity-sentinel keys (additive, off by default): periodic
        # state-fingerprint votes + quarantine (engine/integrity.py,
        # README "Integrity").
        parse_integrity(self, train_cfg)
        if self.fault_spec and not os.environ.get(fault.ENV_VAR):
            fault.install(self.fault_spec)
        self._injector = fault.get_injector()
        if self._injector.active:
            self.logger.warning(
                "fault injection ACTIVE: %s", self._injector.spec
            )
        if self.anomaly_enabled:
            # host-side trailing-median state for the on-device guard: the
            # history holds APPLIED steps' grad norms only, so one spike
            # cannot poison its own reference
            self._gnorm_hist: deque = deque(maxlen=self.anomaly_window)
            self._consec_anomalies = 0
        n_workers = train_cfg["num_workers"]
        # One controller per host: cfg num_workers = decode threads per host
        # (the reference divides workers among its per-GPU processes, :195 —
        # same total per host).
        self.logger.info("host batch_size: %d, workers: %d", host_batch, n_workers)

        optimizer_params = dict(train_cfg["optimizer"])
        optimizer_cls = get_optimizer(optimizer_params)
        optimizer_params.pop("name")
        self.optimizer = optimizer_cls(**optimizer_params)
        self.logger.info("Loaded optimizer: %s(%s)", optimizer_cls.__name__, optimizer_params)

        self.scheduler = get_scheduler(self.optimizer, train_cfg["lr_schedule"])

        n_hosts = jax.process_count()
        seed = self.seed if self.seed is not None else 0
        if self.distributed:
            train_sampler = DistributedShardSampler(
                len(train_dataset),
                num_replicas=n_hosts,
                rank=self.current_rank,
                shuffle=True,
                drop_last=True,
                seed=seed,
            )
            val_sampler = DistributedShardSampler(
                len(val_dataset),
                num_replicas=n_hosts,
                rank=self.current_rank,
                shuffle=False,
                seed=seed,
            )
        else:
            train_sampler = RandomSampler(len(train_dataset), seed=seed)
            val_sampler = SequentialSampler(len(val_dataset))

        # Additive key (unknown to the reference schema): loader backend —
        # "auto" picks the native C++ batch decoder for JPEG folder datasets,
        # threads otherwise; "process"/"thread" force a backend (loader.py).
        worker_mode = train_cfg.get("worker_mode", "auto")
        # Additive key ``training.device_normalize``: ship raw uint8 pixels
        # and run the (x/255 - mean)/std affine in-graph on the accelerator —
        # 4x less host->device traffic and one fewer host pass per image.
        # Default False = host-side normalization (reference parity).
        self.device_normalize = bool(train_cfg.get("device_normalize", False))
        norm_mean = getattr(train_dataset, "norm_mean", None)
        if self.device_normalize and (self.is_lm or norm_mean is None):
            raise ValueError(
                "training.device_normalize requires an image dataset with "
                "norm_mean/norm_std (e.g. imagenet)"
            )
        output_dtype = "uint8" if self.device_normalize else "float32"
        self._input_norm = (
            (train_dataset.norm_mean, train_dataset.norm_std)
            if self.device_normalize
            else None
        )
        # Additive key ``training.dct_denom``: libjpeg DCT-domain pre-scale
        # for the native decoder (1 = exact full decode, 2/4/8 = fixed,
        # 0 = auto-pick the largest that keeps the crop >= output size —
        # large speedup on big photos at a small resampling-fidelity cost).
        # TRAINING loader only: validation always decodes at full fidelity
        # so eval metrics stay comparable across dct settings.
        dct_denom = int(train_cfg.get("dct_denom", 1))
        if dct_denom not in (0, 1, 2, 4, 8):
            raise ValueError(
                f"training.dct_denom must be 0 (auto), 1, 2, 4, or 8; got {dct_denom}"
            )
        self.train_loader = train_loader = DataLoader(  # confined: api
            train_dataset,
            batch_size=host_batch,
            sampler=train_sampler,
            num_workers=n_workers,
            drop_last=True,
            worker_mode=worker_mode,
            output_dtype=output_dtype,
            dct_denom=dct_denom,
        )
        # Parity: val loader reuses TRAINING batch/workers (:235-241).
        self.val_loader = DataLoader(
            val_dataset,
            batch_size=host_batch,
            sampler=val_sampler,
            num_workers=n_workers,
            drop_last=False,
            worker_mode=worker_mode,
            output_dtype=output_dtype,
        )
        self.logger.info(
            "Load dataset done\nTraining: %d imgs, %d batchs\nEval: %d imgs, %d batchs",
            len(train_dataset),
            len(train_loader),
            len(val_dataset),
            len(self.val_loader),
        )

        # Exact-count eval (``validation.exact: true``; beyond reference —
        # masks the DistributedSampler wrap-padded tail + ragged-batch
        # padding out of the in-graph psum, steps.build_eval_step_exact).
        # Default off = reference parity (tail double-count, SURVEY §2.3).
        self._exact_eval = bool(cfg.get("validation", {}).get("exact", False))
        self._eval_step_exact = None
        self._host_batch = host_batch
        self._val_len = len(val_dataset)
        self._val_n_hosts = n_hosts if self.distributed else 1
        if self._exact_eval and self.is_lm:
            self.logger.warning(
                "validation.exact is implemented for the image eval path; "
                "LM validation keeps the parity (per-batch meter) semantics"
            )

        # --- mesh + compiled steps + sharded state (engine/paths.py) --------
        # Strategy table: the first matching PathSpec builds mesh, state,
        # train/eval steps, and the input shardings for this topology.
        path = select_path(self)
        self.logger.info("Execution path: %s", path.name)
        path.build(self, seed, train_dataset)
        self.global_batch = host_batch * n_hosts
        self._tput_t0 = time.monotonic()
        self._tput_iters = 0

        # --- optional checkpoint/resume (absent in reference; config-gated) --
        self.checkpointer = Checkpointer.from_config(train_cfg)
        if self.checkpointer:
            if train_cfg["checkpoint"].get("resume", True):
                self.state, start_iter = self.checkpointer.restore_latest(
                    self.state, self.logger
                )
                self.iter = start_iter
                self.scheduler.last_epoch = start_iter
            elif self.checkpointer.latest() is not None:
                # orbax never overwrites an existing step; starting a fresh
                # run into a populated dir would crash at the first save
                raise ValueError(
                    f"checkpoint dir {self.checkpointer.directory} already has "
                    f"step {self.checkpointer.latest()} but resume is False — "
                    "clear the directory or point checkpoint.dir elsewhere"
                )

        # --- input-pipeline position (mid-epoch resume; elastic layer) ------
        # (epoch, batches consumed this epoch) — persisted as a sidecar next
        # to every checkpoint so a resume (even at a DIFFERENT topology under
        # batch_division: world, where batches/epoch is world-invariant)
        # restarts the stream on exactly the next unseen batch.
        self._init_pipeline_position()

        # --- integrity sentinel (engine/integrity.py; config-gated) ---------
        # Fingerprint votes between steps + a retained known-good snapshot;
        # seeded with the state we are about to train from (post-restore),
        # so even the first check has a recovery point to replay from.
        self._integrity = None
        if self.integrity_enabled:
            self._integrity = IntegritySentinel(
                check_interval=self.integrity_check_interval,
                replicas=self.integrity_replicas,
                rank=self.current_rank,
                process_count=jax.process_count(),
                max_consecutive=self.integrity_max_consecutive,
                logger=self.logger,
            )
            self._integrity.retain(
                self.state, self.iter - 1, self._pipeline_extras()
            )
            self.logger.info(
                "integrity sentinel ON: fingerprint vote every %d step(s) "
                "across %d replica(s)%s, quarantine after %d consecutive "
                "diverged check(s)",
                self._integrity.check_interval, self._integrity.replicas,
                " (simulated)" if self._integrity.simulated else "",
                self._integrity.max_consecutive,
            )

        # --- elastic heartbeat coordinator (engine/elastic.py; config-gated) -
        self._elastic = None
        if self.elastic_enabled:
            hb_dir = self.elastic_dir or os.path.join(
                self.checkpointer.directory, "heartbeats"
            )
            self._elastic = ElasticCoordinator(
                hb_dir,
                process_index=jax.process_index(),
                num_processes=jax.process_count(),
                heartbeat_interval=self.elastic_heartbeat_interval,
                timeout=self.elastic_timeout,
                startup_grace=self.elastic_startup_grace,
                logger=self.logger,
            )
            self._elastic.start()
            self.logger.info(
                "elastic recovery ON: heartbeats in %s every %.2fs, peer "
                "timeout %.2fs", hb_dir, self.elastic_heartbeat_interval,
                self.elastic_timeout,
            )

        # --- unified telemetry (telemetry/; README "Observability") ---------
        # Built after the step path so its span recorder is live for the
        # whole loop; the compiled step families already registered with the
        # process-global jit-cache probe during path.build.
        self._telemetry = Telemetry(  # confined: api
            enabled=self.telemetry_enabled,
            dir=self.telemetry_dir,
            host=self.current_rank,
            is_rank0=self.current_rank == 0,
            snapshot_interval=self.telemetry_interval,
            span_ring=self.telemetry_span_ring,
            retrace_warn=self.telemetry_retrace_warn,
            tb_writer=self.tb_writer,
            use_tensorboard=self.telemetry_tensorboard,
            capture_signal=self.telemetry_capture_signal,
            capture_iters=self.telemetry_capture_iters,
            capture_at_iter=self.telemetry_capture_at_iter,
            capture_dir=self.telemetry_capture_dir,
            capture_python_tracer=self.telemetry_capture_python_tracer,
            logger=self.logger,
        )

        # --- optional jax.profiler trace window (absent in reference; §5.1) --
        self.profiler = (
            TraceProfiler.from_config(train_cfg, self.logger)
            if self.current_rank == 0
            else None
        )

        iter_generator = self._make_stream()

        # --- preemption safety (engine/preemption.py; beyond reference) -----
        # Eviction notice (default SIGTERM; the latched set is configurable
        # via ``training.checkpoint.preemption_signals`` for platforms that
        # notify on other signals) -> checkpoint at the current iteration and
        # exit cleanly; the relaunch resumes from it.  Active whenever
        # checkpointing is configured, opt-out via
        # ``training.checkpoint.preemption: False``.
        from .preemption import PreemptionGuard

        use_guard = self.checkpointer is not None and train_cfg["checkpoint"].get(
            "preemption", True
        )
        self._preempt = None  # confined: api
        if use_guard:
            sigs = PreemptionGuard.parse_signals(
                train_cfg["checkpoint"].get("preemption_signals", ("SIGTERM",))
            )
            self._preempt = PreemptionGuard(signals=sigs, logger=self.logger)
        if self.watchdog_exit and not use_guard:
            raise ValueError(
                "fault_tolerance.watchdog.checkpoint_and_exit needs the "
                "preemption path: configure training.checkpoint.dir and "
                "leave checkpoint.preemption enabled"
            )
        # Multi-process: checkpointer.save is a COLLECTIVE, and the signal
        # may land on one host only (or at different loop positions), so
        # hosts must AGREE on preemption at the same iteration or the save
        # deadlocks with mismatched participants (r2 code-review finding).
        # Every ``preemption_sync_interval`` iters (default 10) all hosts
        # allgather their local flags and act only on the global OR —
        # well within any eviction grace window.  Single process acts on
        # the local flag immediately, no collective.
        self._preempt_sync = 10
        if use_guard:
            self._preempt_sync = int(
                train_cfg["checkpoint"].get("preemption_sync_interval", 10)
            )
            if self._preempt_sync < 1:
                raise ValueError(
                    f"checkpoint.preemption_sync_interval must be >= 1, got "
                    f"{self._preempt_sync}"
                )
        # --- hung-step watchdog (engine/watchdog.py; config-gated) ----------
        self._watchdog = None  # confined: api
        if self.watchdog_enabled:
            self._watchdog = StepWatchdog(
                factor=self.watchdog_factor,
                min_seconds=self.watchdog_min_seconds,
                window=self.watchdog_window,
                warmup=self.watchdog_warmup,
                poll_seconds=self.watchdog_poll,
                on_hang=self._on_hang,
                logger=self.logger,
            )

        try:
            with self._preempt if self._preempt else contextlib.nullcontext():
                self._train_loop(iter_generator, train_cfg)
        except DivergedReplicaError as e:
            # persistent silent corruption: quarantine — a healthy rank
            # emergency-checkpoints, the corrupt one just exits with the
            # diagnosis; the relaunch reshapes without it (the subclass
            # relationship with PeerLostError is the contract: peers see
            # this process's exit as an ordinary peer loss)
            self._on_diverged(e)
            raise
        except PeerLostError as e:
            # diagnosed dead peer: emergency-checkpoint what this process can
            # still save, then propagate — the caller relaunches at the new
            # world size and the restore path picks the emergency step up
            self._on_peer_lost(e)
            raise
        finally:
            if self._watchdog:
                self._watchdog.close()
            if self._elastic:
                self._elastic.close()
            # crash-path flush: buffered spans reach disk even when an
            # exception is propagating (full close happens below on the
            # clean path only)
            self._telemetry.flush()
        if self.profiler:
            self.profiler.finalize()
        if self.checkpointer:
            self.checkpointer.wait()
            self.checkpointer.close()
        self.train_loader.close()
        self.val_loader.close()
        # final snapshot + human summary AFTER the checkpointer drained, so
        # the last async write's stall/commit numbers are in the ledger
        self._telemetry.close(step=self.iter)

    # ------------------------------------------------- pretrained ingestion
    def _load_torch_state_dict(self) -> dict:
        """Read ``model.pretrained`` as a torch ``state_dict`` mapping."""
        import os

        path = self.pretrained
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"model.pretrained: checkpoint '{path}' does not exist"
            )
        import torch

        state_dict = torch.load(path, map_location="cpu", weights_only=True)
        if isinstance(state_dict, dict) and "state_dict" in state_dict:
            state_dict = state_dict["state_dict"]  # harness checkpoints nest it
        if not isinstance(state_dict, dict):
            raise ValueError(
                f"model.pretrained: '{path}' does not contain a state_dict "
                f"mapping (got {type(state_dict).__name__})"
            )
        return state_dict

    def _apply_pretrained_image(self, state: TrainState) -> TrainState:
        """Replace params (+ BN stats) with a ported torchvision checkpoint.

        ResNets use the torchvision ResNet layout (params + running stats);
        ViTs the torchvision ``VisionTransformer`` layout (params only — no
        batch statistics).  Anything else is rejected with the family list.
        """
        from ..models.resnet import ResNet
        from ..models.vit import ViT

        if not isinstance(self.model, (ResNet, ViT)):
            # family check BEFORE the (possibly multi-GB) torch.load
            raise ValueError(
                f"model.pretrained: only the ResNet and ViT families have a "
                f"torchvision state_dict layout (got model.name: "
                f"{self.model_name})"
            )
        state_dict = self._load_torch_state_dict()
        if isinstance(self.model, ResNet):
            from ..models.torch_port import import_torch_resnet_state_dict

            variables = {
                "params": state.params, "batch_stats": state.batch_stats,
            }
            loaded = import_torch_resnet_state_dict(variables, state_dict)
            new = state.replace(
                params=loaded["params"], batch_stats=loaded["batch_stats"]
            )
        else:
            from ..models.torch_port import import_torch_vit_state_dict

            params = import_torch_vit_state_dict(
                {"params": state.params}, state_dict,
                num_heads=self.model.num_heads,
            )
            new = state.replace(params=params)
        self.logger.info(
            "Initialized %s from pretrained torch checkpoint %s",
            self.model_name, self.pretrained,
        )
        return new

    def _apply_pretrained_lm(self, params):
        """Replace LM params with a ported torch decoder checkpoint."""
        from ..models.torch_port import import_torch_lm_state_dict

        loaded = import_torch_lm_state_dict(params, self._load_torch_state_dict())
        self.logger.info(
            "Initialized %s from pretrained torch checkpoint %s",
            self.model_name, self.pretrained,
        )
        return loaded

    # ------------------------------------------------------- fault tolerance
    def _init_pipeline_position(self):
        """Set (``_epoch``, ``_batch_in_epoch``) for the NEXT batch to draw.

        Preference order: the persisted sidecar of the checkpoint we resumed
        from (topology-independent under ``batch_division: world`` — a mesh
        reshape changes neither the global batch nor batches/epoch), else
        derive from the step counter and the CURRENT epoch length (exact
        whenever the topology didn't change)."""
        self._batches_per_epoch = len(self.train_loader)
        self._epoch, self._batch_in_epoch = divmod(
            self.iter, self._batches_per_epoch
        )
        if self.checkpointer is None or self.iter == 0:
            return
        extras = self.checkpointer.read_extras(self.iter - 1)
        if extras is None:
            return
        saved_bpe = int(extras.get("batches_per_epoch", self._batches_per_epoch))
        if saved_bpe != self._batches_per_epoch:
            self.logger.warning(
                "pipeline sidecar was written with %d batches/epoch but this "
                "topology yields %d — resuming at its recorded position, but "
                "bit-exact batch identity is not guaranteed (is "
                "training.batch_division 'world' on both runs?)",
                saved_bpe, self._batches_per_epoch,
            )
        self._epoch = int(extras["epoch"])
        self._batch_in_epoch = int(extras["batch_in_epoch"])
        self.logger.info(
            "pipeline position restored from sidecar: epoch %d, %d/%d "
            "batches consumed", self._epoch, self._batch_in_epoch,
            self._batches_per_epoch,
        )

    def _pipeline_extras(self) -> dict:
        """The sidecar payload persisted with each checkpoint (JSON-safe)."""
        return {
            "epoch": int(self._epoch),
            "batch_in_epoch": int(self._batch_in_epoch),
            "seed": int(self.seed) if self.seed is not None else 0,
            "world_processes": int(jax.process_count()),
            "batches_per_epoch": int(self._batches_per_epoch),
        }

    def _advance_pipeline(self):
        """Account one consumed training batch (called once per step)."""
        self._batch_in_epoch += 1
        if self._batch_in_epoch >= self._batches_per_epoch:
            self._epoch += 1
            self._batch_in_epoch = 0

    def _make_stream(self):
        """Build the training input stream: epoch iterator (fast-forwarded
        to ``self.iter``) -> optional NaN-batch injection -> device-side
        double buffering (the next batch's H2D transfer is dispatched while
        the current step computes — the reference's pinned memory +
        non_blocking copies, :272-273).  A rollback rebuilds the whole
        stream from the restored iteration."""
        host_iter = make_iter_dataloader(
            self.train_loader,
            start_iter=self.iter,
            start_epoch=self._epoch,
            skip_batches=self._batch_in_epoch,
        )
        if self._injector.active:
            host_iter = fault.poison_batches(
                host_iter, self._injector, start_iter=self.iter,
                logger=self.logger,
            )
        return device_prefetch(host_iter, self._put_batch)

    def _apply_step_faults(self):
        """Fire any host-side injected faults keyed to this step (the
        NaN-batch fault lives in the stream instead — see _make_stream)."""
        inj = self._injector
        if not inj.active:
            return
        k = inj.take("kill_peer", self.iter)
        if k is not None:
            target = int(k)
            if target < 0 or target == jax.process_index():
                import signal as _signal

                self.logger.error(
                    "fault injection: kill_peer@%d — SIGKILL self "
                    "(process %d, pid %d); surviving ranks must detect the "
                    "silence via the elastic heartbeat layer",
                    self.iter, jax.process_index(), os.getpid(),
                )
                os.kill(os.getpid(), _signal.SIGKILL)
        w = inj.take("kill_worker", self.iter)
        if w is not None:
            import signal as _signal

            pool = getattr(self.train_loader, "_pool", None)
            if pool is None:
                self.logger.warning(
                    "fault injection: kill_worker@%d ignored — the loader "
                    "has no process pool (worker_mode)", self.iter,
                )
            else:
                wid = int(w) % pool.num_workers
                pid = pool._procs[wid].pid
                self.logger.warning(
                    "fault injection: SIGKILL loader worker %d (pid %d) at "
                    "step %d", wid, pid, self.iter,
                )
                os.kill(pid, _signal.SIGKILL)
        s = inj.take("stall_step", self.iter)
        if s is not None:
            self.logger.warning(
                "fault injection: stalling step %d for %.2fs", self.iter, s
            )
            time.sleep(float(s))
        f = inj.take("sdc_flip", self.iter)
        if f is not None:
            if self._integrity is None:
                self.logger.warning(
                    "fault injection: sdc_flip@%d ignored — the integrity "
                    "sentinel is not configured (training.integrity)",
                    self.iter,
                )
            else:
                self.logger.warning(
                    "fault injection: arming silent bit flip on replica %d "
                    "at step %d — the sentinel's next fingerprint vote must "
                    "attribute it", int(f), self.iter,
                )
                self._integrity.arm_flip(int(f))

    def _on_hang(self, step: int, elapsed: float, limit: float) -> None:
        """Watchdog diagnostic dump (monitor thread): step identity,
        per-host progress, loader queue depth, and all-thread stacks."""
        fault.bump("watchdog_fires")
        pool = getattr(self.train_loader, "_pool", None)
        median = self._watchdog.trailing_median()
        self.logger.error(
            "watchdog: host %d stuck in step %d for %.1fs (limit %.1fs, "
            "trailing median %.3fs); loader pool tasks outstanding: %s",
            self.current_rank, step, elapsed, limit,
            -1.0 if median is None else median,
            getattr(pool, "_outstanding", "n/a"),
        )
        try:
            # GIL-safe all-thread dump: sys._current_frames + format_stack
            # run as ordinary Python, so frame objects stay refcounted while
            # walked.  (faulthandler.dump_traceback walks OTHER threads'
            # frames without synchronization — against a main thread busy
            # inside a compiled step it reads freed frames and segfaults.)
            import sys
            import threading
            import traceback

            names = {t.ident: t.name for t in threading.enumerate()}
            dump = []
            for tid, frame in sys._current_frames().items():
                dump.append(
                    f"Thread {names.get(tid, '?')} (id {tid}):\n"
                    + "".join(traceback.format_stack(frame))
                )
            self.logger.error("watchdog stack dump:\n%s", "\n".join(dump))
        except Exception:  # the dump is best-effort diagnostics
            pass
        tel = self._telemetry
        if tel is not None and tel.enabled:
            try:
                # what the process was DOING when it stalled: the last phase
                # spans + the full counter ledger (telemetry/runtime.py)
                self.logger.error(
                    "watchdog telemetry diagnostics:\n%s", tel.diagnostics()
                )
            except Exception:  # pragma: no cover - best-effort diagnostics
                pass
        if self.watchdog_exit and self._preempt is not None:
            # reuse the eviction path: the loop checkpoints at the current
            # iteration and exits cleanly (multi-host agreement included)
            self.logger.error(
                "watchdog: requesting checkpoint-and-exit via the "
                "preemption flag"
            )
            self._preempt.triggered = True

    def _synced_train_iter(self, g_img, g_label):
        """One training iteration, blocked to completion — elastic mode runs
        this under :meth:`ElasticCoordinator.guard` so the step's collectives
        cannot outlive the peer-liveness watch (the per-step sync is the
        documented cost of enabling elastic recovery)."""
        self.train_iter(g_img, g_label)
        jax.block_until_ready(self.state)

    def _on_peer_lost(self, e: PeerLostError):
        """A peer stopped heartbeating: checkpoint what this process can
        still save, log the diagnosis, and let the error propagate (the
        relaunch — possibly at a different world size — resumes from the
        emergency step via the mesh-reshape-tolerant restore path)."""
        fault.bump("peer_lost")
        self.logger.error("elastic recovery: %s", e)
        tel = self._telemetry
        if tel is not None and tel.enabled:
            try:
                # same dump the watchdog makes: where the loop was when the
                # peer's silence surfaced, plus every recovery counter
                self.logger.error(
                    "peer-loss telemetry diagnostics:\n%s", tel.diagnostics()
                )
            except Exception:  # pragma: no cover - best-effort diagnostics
                pass
        if e.mid_step:
            # the in-flight step donated the previous state's buffers into
            # an unfinished computation — nothing consistent left to save
            self.logger.error(
                "peer died mid-step %d: the in-flight step is unrecoverable; "
                "the relaunch resumes from the last durable checkpoint",
                self.iter,
            )
            return
        if self.checkpointer is None or self.iter == 0:
            self.logger.error(
                "no emergency checkpoint possible (%s) — the relaunch "
                "starts from the last durable checkpoint, if any",
                "no checkpointer configured" if self.checkpointer is None
                else "no step has completed yet",
            )
            return
        step = self.iter - 1
        try:
            path = self.checkpointer.save_emergency(
                step, self.state, extras=self._pipeline_extras()
            )
            self.logger.error(
                "EMERGENCY checkpoint for step %d written to %s — exiting; "
                "the relaunch resumes from it at any world size",
                step, path,
            )
        except ValueError as ve:
            # non-replicated state: a single survivor only holds one shard
            self.logger.error(
                "emergency checkpoint skipped: %s — the relaunch resumes "
                "from the last durable checkpoint", ve,
            )

    def _rollback(self, iter_generator, train_cfg):
        """N consecutive anomalous steps: restore the last checkpoint and
        rebuild the input stream from the restored iteration."""
        fault.bump("rollbacks")
        if self.checkpointer is None:
            raise RuntimeError(
                f"{self._consec_anomalies} consecutive anomalous steps at "
                f"iter {self.iter} and no training.checkpoint configured "
                "to roll back to"
            )
        self.logger.error(
            "anomaly guard: %d consecutive anomalous steps at iter %d — "
            "rolling back to the last checkpoint",
            self._consec_anomalies, self.iter,
        )
        try:
            iter_generator.close()
        except Exception:  # pragma: no cover - abandoned stream cleanup
            pass
        # flush the in-flight async save before restoring: the writer must
        # not race the restore on the checkpoint dir, and a save that
        # FAILED in the background must not abort the rollback — the
        # restore is the recovery (errors are logged and dropped)
        self.checkpointer.drain(raise_errors=False)
        self.state, start_iter = self.checkpointer.restore_latest(
            self.state, self.logger
        )
        # A restore that hands back non-finite params would immediately
        # re-trip the anomaly guard and loop rollback -> restore forever;
        # fail loudly instead (seen in the wild when a stale persistent
        # compile cache corrupted the restore path).
        restored_finite = all(
            bool(jnp.isfinite(leaf).all())
            for leaf in jax.tree.leaves(self.state.params)
        )
        if not restored_finite:
            raise RuntimeError(
                f"rollback restore of step {start_iter} returned non-finite "
                "parameters — checkpoint or restore path is corrupt"
            )
        self.iter = start_iter
        self.scheduler.last_epoch = start_iter
        self._init_pipeline_position()
        self._consec_anomalies = 0
        self._gnorm_hist.clear()
        # The restored steps replay against a cold pipeline (recompiles,
        # page cache misses) — a trailing median learned before the fault
        # would read the first replayed step as a hang/anomaly.  Re-enter
        # the watchdog's warmup instead of trusting the stale window.
        if self._watchdog:
            self._watchdog.reset()
        # Re-base the integrity sentinel on the restored state: its retained
        # snapshot still belongs to the ABANDONED pre-rollback timeline, so
        # an SDC detected during the replay would "recover" to state the
        # rollback just discarded (or all the way to the startup snapshot),
        # silently resurrecting the dropped anomalous steps.
        if self._integrity is not None:
            self._integrity.rebase(
                self.state, start_iter - 1, self._pipeline_extras()
            )
        return self._make_stream()

    def _integrity_recover(self, iter_generator, verdict):
        """This replica's fingerprint fell outside the healthy majority:
        restore the retained known-good snapshot in place and replay from
        it.  A transient flip heals here — the replayed steps recompute
        bit-identically (deterministic input stream, one-shot faults
        consumed) and the next check passes, resetting the consecutive
        count.  A flip that survives the restore (the snapshot's
        fingerprint does not reproduce) is persistent by definition —
        escalate to quarantine instead of looping restore→diverge."""
        sen = self._integrity
        self.logger.error(
            "integrity: replica %d diverged at step %d (reports %s) — "
            "restoring the retained snapshot of step %s and replaying",
            self.current_rank, self.iter,
            [f"{r:08x}" for r in verdict["reports"]], sen.snapshot_step,
        )
        try:
            iter_generator.close()
        except Exception:  # pragma: no cover - abandoned stream cleanup
            pass
        restored, snap_step, position, ok = sen.restore_snapshot(self.state)
        if not ok:
            raise DivergedReplicaError(
                f"replica {self.current_rank}'s state diverged at step "
                f"{self.iter} and restoring the retained snapshot of step "
                f"{snap_step} did not reproduce its fingerprint — the "
                "corruption is persistent (bad host/device memory), "
                "quarantining",
                ranks=(self.current_rank,), step=self.iter,
            )
        self.state = restored
        fault.bump("integrity_transient_flips")
        self.iter = snap_step + 1
        self.scheduler.last_epoch = self.iter
        if position is not None:
            self._epoch = int(position["epoch"])
            self._batch_in_epoch = int(position["batch_in_epoch"])
        else:
            self._epoch, self._batch_in_epoch = divmod(
                self.iter, self._batches_per_epoch
            )
        # Same staleness hazard as _rollback: the replay runs cold, so the
        # hang watchdog and the anomaly guard's grad-norm median must both
        # re-warm instead of judging replayed steps by pre-fault timings.
        self._consec_anomalies = 0
        self._gnorm_hist.clear()
        if self._watchdog:
            self._watchdog.reset()
        return self._make_stream()

    def _on_diverged(self, e: DivergedReplicaError):
        """Persistent corruption diagnosed: log, count, and emergency-
        checkpoint — but ONLY when this replica is healthy (a quarantined
        rank must never persist its corrupted state; peers save theirs,
        and the heartbeat layer turns this process's exit into an ordinary
        peer loss the relaunch reshapes around)."""
        fault.bump("integrity_quarantines")
        self.logger.error("integrity quarantine: %s", e)
        tel = self._telemetry
        if tel is not None and tel.enabled:
            try:
                self.logger.error(
                    "quarantine telemetry diagnostics:\n%s", tel.diagnostics()
                )
            except Exception:  # pragma: no cover - best-effort diagnostics
                pass
        if self.current_rank in e.ranks:
            self.logger.error(
                "local replica %d is the quarantined one — skipping the "
                "emergency checkpoint (corrupted state must not be saved); "
                "a healthy rank's emergency step or the last verified "
                "periodic checkpoint carries the resume", self.current_rank,
            )
            return
        if self.checkpointer is None:
            self.logger.error(
                "no checkpointer configured — the relaunch starts from "
                "the last durable checkpoint, if any"
            )
            return
        try:
            path = self.checkpointer.save_emergency(
                self.iter, self.state, extras=self._pipeline_extras()
            )
            self.logger.error(
                "EMERGENCY checkpoint for step %d written to %s by healthy "
                "rank %d — the relaunch resumes from it without the "
                "quarantined replica(s) %s",
                self.iter, path, self.current_rank, list(e.ranks),
            )
        except ValueError as ve:
            # non-replicated state: a single survivor only holds one shard
            self.logger.error(
                "emergency checkpoint skipped: %s — the relaunch resumes "
                "from the last durable checkpoint", ve,
            )

    def _train_loop(self, iter_generator, train_cfg):
        tel = self._telemetry
        # goodput accounting: a step at an iteration index we already passed
        # is a post-rollback REPLAY (paid-again work, not fresh progress)
        self._max_iter_seen = self.iter - 1
        self._last_step_applied = True
        # --- the reference outer loop (:251-265), line for line -------------
        while self.iter < train_cfg["train_iters"]:
            # one step of the profiler's trace: a viewer groups the spans
            # below, and the device work they launch, under this number
            with jax.profiler.StepTraceAnnotation("train", step_num=self.iter):
                step_t0 = time.monotonic()
                if self._watchdog:
                    self._watchdog.step_started(self.iter)
                self._apply_step_faults()
                if self._elastic is not None:
                    # pre-step liveness gate: a peer that died BETWEEN steps is
                    # caught here, before this process enters any collective —
                    # the committed state is still saveable (emergency path)
                    self._elastic.check_peers()
                with tel.span("data_wait", step=self.iter):
                    g_img, g_label = next(iter_generator)
                if self._elastic is not None:
                    # elastic mode's documented per-step cost: the step runs
                    # under the peer-loss guard and is synced to completion, so
                    # a peer dying MID-collective turns an indefinite hang into
                    # a diagnosed PeerLostError within the heartbeat timeout
                    with tel.span("step_dispatch", step=self.iter, cpu=True):
                        self._elastic.guard(
                            self._synced_train_iter, g_img, g_label,
                            what=f"train step {self.iter}",
                        )
                else:
                    # cpu=True: the span also records the loop thread's own
                    # CPU time, so wall less the nested device_block less
                    # cpu_ms is what the thread spent OFF the CPU: waiting
                    # for a core, the interpreter lock or a runtime thread
                    # (two thread_time() reads a step)
                    with tel.span("step_dispatch", step=self.iter, cpu=True):
                        self.train_iter(g_img, g_label)
                self._advance_pipeline()
                if self._watchdog:
                    self._watchdog.step_finished()
                replayed = self.iter <= self._max_iter_seen
                self._max_iter_seen = max(self._max_iter_seen, self.iter)
                tel.note_step(
                    time.monotonic() - step_t0,
                    applied=self._last_step_applied,
                    replayed=replayed,
                )
                if (
                    self.anomaly_enabled
                    and self._consec_anomalies >= self.anomaly_max_consec
                ):
                    rb_t0 = time.monotonic()
                    with tel.span("rollback", step=self.iter):
                        iter_generator = self._rollback(iter_generator, train_cfg)
                    tel.note_lost("rollback", time.monotonic() - rb_t0)
                    continue
                if self._integrity is not None and self._integrity.due(self.iter):
                    # between steps the state is quiescent and owned (no
                    # donation conflict with the compiled step) — fingerprint,
                    # vote, and either retain a new known-good snapshot or
                    # enter the classify-then-quarantine ladder
                    with tel.span("integrity_check", step=self.iter):
                        self.state, verdict = self._integrity.check(
                            self.state, self.iter
                        )
                    if verdict["persistent"]:
                        raise DivergedReplicaError(
                            f"replica(s) {verdict['persistent']} stayed outside "
                            f"the healthy fingerprint majority for "
                            f"{self._integrity.max_consecutive} consecutive "
                            f"checks at step {self.iter} — persistent "
                            "corruption, quarantining",
                            ranks=verdict["persistent"], step=self.iter,
                        )
                    if verdict["local_diverged"]:
                        rc_t0 = time.monotonic()
                        with tel.span("integrity_restore", step=self.iter):
                            iter_generator = self._integrity_recover(
                                iter_generator, verdict
                            )
                        tel.note_lost(
                            "integrity_restore", time.monotonic() - rc_t0
                        )
                        continue
                    # healthy consensus (a diverged SIMULATED peer restores
                    # its own copy; our state is good) — retain it as the
                    # recovery point for the next check
                    self._integrity.retain(
                        self.state, self.iter, self._pipeline_extras()
                    )
                if self._preempt and self._globally_preempted():
                    self.logger.warning(
                        "Preemption signal received: saving checkpoint at iter "
                        "%d and exiting",
                        self.iter,
                    )
                    self.checkpointer.save(
                        self.iter, self.state, extras=self._pipeline_extras()
                    )
                    self.checkpointer.wait()
                    return
                if self.profiler:
                    self.profiler.after_step(self.iter, sync=self.state)

                def is_val():
                    p1 = self.iter != 0
                    p2 = (self.iter + 1) % train_cfg["val_interval"] == 0
                    p3 = self.iter == train_cfg["train_iters"] - 1
                    return (p1 and p2) or p3

                if is_val():
                    # keep validation (and checkpoint I/O below) out of the trace:
                    # the window is a bounded steady-state sample of train steps
                    if self.profiler:
                        self.profiler.stop(sync=self.state)
                    with tel.span("eval", step=self.iter):
                        self.validate()
                if self.checkpointer and self.checkpointer.should_save(
                    self.iter, train_cfg["train_iters"]
                ):
                    if self.profiler:
                        self.profiler.stop(sync=self.state)
                    with tel.span("ckpt_save", step=self.iter):
                        self.checkpointer.save(
                            self.iter, self.state, extras=self._pipeline_extras()
                        )
                    if self.profiler:
                        # with checkpoint.async the write is in flight — block
                        # until it commits so the profiler window can't reopen
                        # over background checkpoint I/O
                        self.checkpointer.wait()
                # retrace-probe poll + on-demand capture window + periodic export
                tel.after_step(self.iter, sync=self.state)
                self.iter += 1

    def _globally_preempted(self) -> bool:
        """Whether to act on preemption at THIS iteration, agreed across
        processes (see the wiring comment in ``worker``).  Single process:
        the local flag, immediately.  Multi-process: all hosts execute the
        same allgather at the same iterations (the condition depends only
        on the shared iteration counter), so the collective cannot
        mismatch, and every host sees the same OR-ed verdict."""
        if jax.process_count() == 1:
            return self._preempt.triggered
        if (self.iter + 1) % self._preempt_sync != 0:
            return False
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray(bool(self._preempt.triggered))
        )
        return bool(np.any(flags))

    # ------------------------------------------------------------- hot loop
    def _put_batch(self, img: np.ndarray, label: np.ndarray):
        """Host shard -> globally-sharded device arrays (the reference's
        pinned-memory ``non_blocking`` H2D copies, :272-273).  For the LM
        task both halves are int32 token grids (inputs, next-token targets)."""
        if self.is_lm:
            img_dtype = np.int32
        elif self.device_normalize:
            img_dtype = np.uint8  # normalized in-graph (4x smaller transfer)
        else:
            img_dtype = np.float32
        nbytes = np.size(img) * np.dtype(img_dtype).itemsize + np.size(label) * 4
        with self._tspan("h2d_put", bytes=nbytes):
            img = np.asarray(img, dtype=img_dtype)
            label = np.asarray(label, dtype=np.int32)
            g_img = jax.make_array_from_process_local_data(self._img_sharding, img)
            g_label = jax.make_array_from_process_local_data(
                self._label_sharding, label
            )
        return g_img, g_label

    def _tspan(self, kind: str, **extra):
        """Telemetry span bound to the current iteration (no-op before the
        telemetry facade is built — direct ``train_iter`` calls in tests)."""
        tel = self._telemetry
        if tel is None:
            return contextlib.nullcontext()
        return tel.span(kind, step=self.iter, **extra)

    def train_iter(self, g_img, g_label):
        """One training iteration on already-device-resident arrays."""
        train_cfg = self.global_cfg["training"]
        if self.anomaly_enabled:
            # the trailing median rides into the compiled step as a python
            # float (weak-typed scalar: a new value never retraces); the
            # returned ``applied`` flag is the guard's one extra per-step
            # host sync — the documented cost of arming it
            ref = float(np.median(self._gnorm_hist)) if self._gnorm_hist else 0.0
            self.state, loss, gnorm, applied = self.train_step(
                self.state, g_img, g_label, ref
            )
            with self._tspan("device_block"):
                applied_host = float(applied)
            self._last_step_applied = applied_host >= 0.5
            if self._last_step_applied:
                self._gnorm_hist.append(float(gnorm))
                self._consec_anomalies = 0
            else:
                self._consec_anomalies += 1
                fault.bump("skipped_steps")
                self.logger.warning(
                    "anomaly guard: step %d SKIPPED (loss=%g grad_norm=%g, "
                    "trailing median %g) — %d consecutive",
                    self.iter, float(loss), float(gnorm), ref,
                    self._consec_anomalies,
                )
        else:
            self.state, loss = self.train_step(self.state, g_img, g_label)
            self._last_step_applied = True
        self._tput_iters += 1

        if self.iter % train_cfg["print_interval"] == 0:
            # loss is already replica-averaged in-graph; this is the only
            # host<->device sync of the steady-state loop (reference :280-284).
            with self._tspan("device_block"):
                loss_val = float(loss)
            last_lr_group = self.scheduler.get_last_lr()
            now = time.monotonic()
            if self.iter == 0:
                # the first window is dominated by XLA compilation — don't
                # pollute the throughput metric with it
                imgs_per_sec = None
            else:
                imgs_per_sec = (
                    self.global_batch * self._tput_iters / max(now - self._tput_t0, 1e-9)
                )
            self._tput_t0, self._tput_iters = now, 0
            if self.current_rank == 0:
                tput_str = (
                    f" ({imgs_per_sec:.1f} img/s, {imgs_per_sec / self.world_size:.1f} img/s/chip)"
                    if imgs_per_sec is not None
                    else ""
                )
                self.logger.info(
                    "Iter [%d/%d] Lr: %s Loss: %.4f%s",
                    self.iter,
                    train_cfg["train_iters"],
                    last_lr_group,
                    loss_val,
                    tput_str,
                )
                if self.tb_writer is not None:
                    self.tb_writer.add_scalar("loss/train", loss_val, self.iter)
                    for gid, lr in enumerate(last_lr_group):
                        self.tb_writer.add_scalar(f"lr_group/{gid}", lr, self.iter)
                    if imgs_per_sec is not None:
                        self.tb_writer.add_scalar(
                            "throughput/images_per_sec", imgs_per_sec, self.iter
                        )
        self.scheduler.step()  # every iteration (:299)

    # ------------------------------------------------------------ validation
    def _eval_state(self):
        # with EMA enabled, validation runs on the averaged weights
        return (
            self.state.replace(params=self.state.ema)
            if getattr(self, "ema_decay", None) is not None
            else self.state
        )

    def _report_validation(self, loss, acc1, acc5):
        if self.current_rank == 0:
            self.logger.info(
                "Acc@1: %.4f, Acc@5: %.4f, Loss: %.5f", acc1, acc5, loss
            )
            if self.tb_writer is not None:
                self.tb_writer.add_scalar("eval/Acc@1", acc1, self.iter)
                self.tb_writer.add_scalar("eval/Acc@5", acc5, self.iter)
                self.tb_writer.add_scalar("eval/loss", loss, self.iter)

    def validate(self):
        if self._exact_eval and not self.is_lm:
            return self._validate_exact()
        if self.current_rank == 0:
            self.logger.info("Start valuation")
        loss_meter = AverageMeter()
        top_1 = AverageMeter()
        top_5 = AverageMeter()
        eval_state = self._eval_state()
        for img, label in tqdm.tqdm(self.val_loader, disable=self.current_rank != 0):
            g_img, g_label = self._put_batch(img, label)
            loss, acc1, acc5 = self.eval_step(eval_state, g_img, g_label)
            # already replica-averaged in-graph (reference :315-321)
            loss_meter.update(float(loss))
            top_1.update(float(acc1))
            top_5.update(float(acc5))
        self._report_validation(loss_meter.value(), top_1.value(), top_5.value())

    def _validate_exact(self):
        """Exact-count eval (``validation.exact``): per-sample sums with a
        validity mask instead of per-batch meter averages — wrap-padded
        tail samples and ragged-batch padding contribute nothing, so the
        metrics equal the unsharded full-set computation exactly
        (tests/test_engine.py::test_exact_eval_matches_unsharded)."""
        from .steps import build_eval_step_exact

        if self.current_rank == 0:
            self.logger.info("Start valuation")
        if self._eval_step_exact is None:
            self._eval_step_exact = build_eval_step_exact(
                self.model, self.mesh, input_norm=self._input_norm
            )
        eval_state = self._eval_state()
        # local position p maps to global sampler slot rank + n_hosts*p;
        # wrap-padded duplicates occupy the slots past the dataset length
        n_real = max(
            0, -(-(self._val_len - self.current_rank) // self._val_n_hosts)
        )
        totals = np.zeros(4, np.float64)
        seen = 0
        for img, label in tqdm.tqdm(self.val_loader, disable=self.current_rank != 0):
            label = np.asarray(label)
            b = len(label)
            # the loader wrap-pads its final chunk to full batch_size
            # (data/loader.py, drop_last=False) — those duplicates occupy
            # positions >= the sampler's local count, so the same position
            # mask that covers sampler wrap-pads masks them too
            assert b == self._host_batch, (b, self._host_batch)
            mask = (np.arange(seen, seen + b) < n_real).astype(np.int32)
            seen += b
            g_img, g_label = self._put_batch(img, label)
            g_mask = jax.make_array_from_process_local_data(
                self._label_sharding, mask
            )
            sums = self._eval_step_exact(eval_state, g_img, g_label, g_mask)
            totals += np.asarray([float(x) for x in sums])
        n = max(totals[3], 1.0)
        self._report_validation(
            totals[0] / n, 100.0 * totals[1] / n, 100.0 * totals[2] / n
        )
