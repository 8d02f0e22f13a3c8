"""Topology/config parsing for the Runner: flags, validation, model build.

Extracted from ``Runner.worker`` (round-3 VERDICT weak #5: the 630-line
method's four-way path selection deserved extraction before a fifth path
lands).  Everything here is pure config -> attributes/raises: the semantics
(and every documented error message the composition-matrix tests pin,
tests/test_composition_matrix.py) are unchanged.

Two stages, called in order by ``Runner.worker``:

  - :func:`parse_topology` — compute dtype, model-section keys
    (``pretrained``, MoE), the parallelism degrees (SP/TP/PP/microbatches/
    schedule/ZeRO) with their cross-constraints, and the constructed model.
  - :func:`parse_batch` — batch division (``local``/``world``,
    SURVEY §7 stage 4), grad accumulation, label smoothing, EMA; returns the
    per-host batch.

The actual mesh/step construction lives in :mod:`.paths` (the strategy
table keyed on the flags this module sets).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import get_model
from ..parallel import DATA_AXIS
from ..parallel.sequence import SEQUENCE_AXIS

__all__ = [
    "parse_topology",
    "parse_batch",
    "parse_fault_tolerance",
    "parse_elastic",
    "parse_integrity",
    "parse_telemetry",
]


def parse_topology(r, cfg: dict, train_cfg: dict, train_dataset) -> None:
    """Parse model + parallelism config onto Runner ``r`` and build
    ``r.model``.  Raises the documented ``ValueError`` for every unsupported
    combination (the composition matrix's source of truth)."""
    r.compute_dtype = {
        "float32": jnp.float32,
        "bfloat16": jnp.bfloat16,
    }[train_cfg.get("dtype", "float32")]
    # Model section: ``name`` is the reference's only key (:183-186);
    # extra keys are architecture hyperparameters forwarded to the zoo
    # (additive — e.g. embed_dim/depth/num_heads for TransformerLM).
    model_cfg = dict(cfg["model"])
    model_name = model_cfg.pop("name")
    r.model_name = model_name
    # Additive key ``model.pretrained``: initialize the run from a torch
    # ``state_dict`` checkpoint (torchvision layout for the ResNet family,
    # the twin layout of tests/test_torch_port_lm.py for TransformerLM) —
    # the user-facing form of the reference's TORCH_HOME model-zoo
    # weights (/root/reference/train.sh:2).  Ported via models/torch_port
    # at state construction (engine/paths.py); strict shape/name checking
    # raises descriptive errors instead of silently part-loading.
    r.pretrained = model_cfg.pop("pretrained", None)
    # The long-context LM task (beyond the reference, SURVEY.md §5.7):
    # first-class from the config surface — ``model.name: TransformerLM`` +
    # an LM dataset + optional ``training.sequence_parallelism``
    # (ring/Ulysses over a sequence mesh axis, parallel.sequence).
    # Asked of the model's class (models.model_class), not of its name.
    from ..models import model_class

    family = model_class(model_name)
    r.is_lm = bool(getattr(family, "is_language_model", False))
    refusal = getattr(family, "training_unsupported", None)
    if refusal:
        # a served-only family (models._LM_FAMILIES lists the five, each
        # with its reason) must not fall into the TransformerLM paths
        raise ValueError(f"model.name: {model_name} cannot be trained: {refusal}")
    # MoE (model.moe_experts > 0, ops/moe.py): trains on the GSPMD path
    # whatever the parallelism degrees — the routing einsums and the
    # sown aux loss need the partitioner's global-token view, and under
    # tensor_parallelism the stacked expert weights shard over the
    # model axis (expert parallelism).
    r.is_moe = r.is_lm and int(model_cfg.get("moe_experts", 0) or 0) > 0
    if r.pretrained and r.is_moe:
        # the torch-twin LM layout has no expert tensors — a part-load
        # would silently leave experts at random init
        raise ValueError(
            "model.pretrained does not support MoE models "
            "(no torch-twin layout for expert weights)"
        )
    r.sync_bn = bool(train_cfg["sync_bn"]) and r.distributed and not r.is_lm
    # ResNet-only model keys, validated BEFORE the LM/image split so an LM
    # config with either key gets the curated error, not a raw constructor
    # TypeError (tests/test_space_to_depth.py pins the messages).
    s2d = bool(model_cfg.pop("space_to_depth", False))
    bn_stat = model_cfg.pop("bn_stat_dtype", None)
    if bn_stat is not None and bn_stat not in ("float32", "bfloat16"):
        raise ValueError(
            f"model.bn_stat_dtype must be 'float32' or 'bfloat16', "
            f"got {bn_stat!r}"
        )
    if s2d or bn_stat:
        from ..models.resnet import RESNET_CONFIGS

        if model_name.lower() not in {k.lower() for k in RESNET_CONFIGS}:
            raise ValueError(
                f"model.space_to_depth / bn_stat_dtype are only wired "
                f"for the ResNet family (got model.name: {model_name})"
            )
    r.seq_par = int(train_cfg.get("sequence_parallelism", 1))
    r.tensor_par = int(train_cfg.get("tensor_parallelism", 1))
    # Additive key ``training.pipeline_parallelism``: GPipe microbatch
    # pipeline over a (data, stage) mesh (parallel/pipeline.py,
    # engine/pp_steps.py).  ``training.microbatches`` tunes the schedule
    # (default = stage count; the bubble fraction is (S-1)/(M+S-1)).
    r.pipe_par = int(train_cfg.get("pipeline_parallelism", 1))
    r.microbatches = int(train_cfg.get("microbatches", r.pipe_par))
    if "microbatches" in train_cfg and r.pipe_par <= 1:
        # silently ignoring the key would read as "microbatch streaming
        # enabled" — grad_accumulation is the non-pipelined equivalent
        raise ValueError(
            "training.microbatches requires pipeline_parallelism > 1 "
            "(use training.grad_accumulation for non-pipelined "
            "micro-batching)"
        )
    if (r.seq_par > 1 or r.tensor_par > 1 or r.pipe_par > 1) and not r.is_lm:
        raise ValueError(
            "training.sequence_parallelism / tensor_parallelism / "
            "pipeline_parallelism require model.name: TransformerLM"
        )
    if r.pipe_par > 1 and r.seq_par > 1 and r.tensor_par > 1:
        # the pipeline mesh supports ONE inner axis besides stage:
        # model (PP x TP) or sequence (PP x SP) — a 4-axis composition
        # is not wired (parallel/pipeline.make_pp_mesh)
        raise ValueError(
            "pipeline_parallelism x sequence_parallelism x "
            "tensor_parallelism (three-way) is not wired; pick "
            "PP x SP or PP x TP"
        )
    # Additive key ``training.pp_schedule``: microbatch schedule for the
    # pipeline step — "gpipe" (autodiff backward, O(M) activation
    # residuals) or "1f1b" (manual interleaved backward with per-stage
    # recompute, O(S) buffered microbatch inputs; engine/pp_steps.py).
    r.pp_schedule = str(train_cfg.get("pp_schedule", "gpipe"))
    if r.pp_schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"training.pp_schedule must be 'gpipe' or '1f1b', "
            f"got {r.pp_schedule!r}"
        )
    if "pp_schedule" in train_cfg and r.pipe_par <= 1:
        raise ValueError("training.pp_schedule requires pipeline_parallelism > 1")
    if r.pipe_par > 1 and r.is_moe:
        # MoE blocks break the homogeneous stacked-layer layout the
        # pipeline step scans over, and its sown aux loss is discarded
        # by the manual per-stage block apply
        raise ValueError(
            "model.moe_experts does not compose with pipeline_parallelism"
        )
    if r.is_moe and int(model_cfg.get("moe_experts")) % r.tensor_par != 0:
        raise ValueError(
            f"model.moe_experts ({model_cfg.get('moe_experts')}) must be "
            f"divisible by training.tensor_parallelism ({r.tensor_par}) "
            "for an even expert split"
        )
    if r.microbatches < max(r.pipe_par, 1):
        raise ValueError(
            f"training.microbatches ({r.microbatches}) must be >= "
            f"pipeline_parallelism ({r.pipe_par})"
        )
    # Additive key ``training.zero``: ZeRO stage 0|1|2|3 (True = 1) —
    # stage 1 shards optimizer moments over the data axis, stage 2 adds
    # sharded gradient buffers, stage 3 shards the PARAMETERS themselves
    # (FSDP semantics; GSPMD LM path, parallel/tensor.py).  Parsed here
    # because it changes BOTH the path selection and the model's
    # attention mode.
    zero_cfg = train_cfg.get("zero", False)
    if isinstance(zero_cfg, bool):
        r.zero = 1 if zero_cfg else 0  # True = ZeRO-1 (back-compat)
    elif isinstance(zero_cfg, int) and zero_cfg in (0, 1, 2, 3):
        r.zero = zero_cfg
    else:
        raise ValueError(
            f"training.zero must be a bool or a stage in (0, 1, 2, 3), "
            f"got {zero_cfg!r}"
        )
    if r.zero and not r.is_lm:
        raise ValueError(
            "training.zero is only wired for the LM task (GSPMD path)"
        )
    # Additive key ``training.remat``: rematerialization policy for the
    # transformer blocks — ``none`` (default), ``block`` (full recompute,
    # nn.remat with nothing saveable), ``dots`` / ``dots_saveable``
    # (jax.checkpoint_policies: save matmul outputs, recompute
    # elementwise; ``dots_saveable`` additionally saves batch-dim dots
    # like attention scores).  A TRAINING-section alias of the model-level
    # ``model.remat``/``model.remat_policy`` pair so memory/recompute
    # sweeps live next to batch size in the recipe; setting both is a
    # loud conflict rather than a silent precedence rule.
    remat_cfg = train_cfg.get("remat", None)
    if remat_cfg is not None:
        if not r.is_lm:
            raise ValueError(
                "training.remat is only wired for the LM task "
                "(model.name: TransformerLM)"
            )
        if "remat" in model_cfg or "remat_policy" in model_cfg:
            raise ValueError(
                "set either training.remat or model.remat/"
                "model.remat_policy, not both"
            )
        remat_map = {
            "none": (False, "nothing"),
            "block": (True, "nothing"),
            "dots": (True, "dots"),
            "dots_saveable": (True, "dots_saveable"),
        }
        if remat_cfg not in remat_map:
            raise ValueError(
                f"training.remat must be one of {sorted(remat_map)}, "
                f"got {remat_cfg!r}"
            )
        model_cfg["remat"], model_cfg["remat_policy"] = remat_map[remat_cfg]
    if r.zero >= 3 and r.pipe_par > 1:
        # FSDP-scattered params would need a stage-stacked scattered
        # layout inside the manual shard_map — not wired (ZeRO-1/2 do
        # compose with the pipeline)
        raise ValueError(
            f"training.zero: {r.zero} does not compose with "
            "pipeline_parallelism — use zero: 1 or 2 under the pipeline"
        )
    if r.is_lm:
        for key, par in (
            ("sequence_parallelism", r.seq_par),
            ("tensor_parallelism", r.tensor_par),
            ("pipeline_parallelism", r.pipe_par),
        ):
            if par < 1 or jax.local_device_count() % par != 0:
                # the host-batch layout (and
                # make_array_from_process_local_data) assumes each host
                # holds whole shard groups
                raise ValueError(
                    f"training.{key} ({par}) must divide the local "
                    f"device count ({jax.local_device_count()})"
                )
        non_data_par = r.seq_par * r.tensor_par * r.pipe_par
        if jax.local_device_count() % non_data_par != 0:
            # combined: one data shard spans a seq x tensor x pipe
            # device group — the whole group must fit within a host or
            # units_local becomes 0 and the host batch degenerates
            raise ValueError(
                f"sequence_parallelism x tensor_parallelism x "
                f"pipeline_parallelism ({r.seq_par} x {r.tensor_par}"
                f" x {r.pipe_par}) must divide the local device count "
                f"({jax.local_device_count()})"
            )
        sample_inp, _ = train_dataset[0]
        r.seq_len = int(sample_inp.shape[0])
        if r.seq_len % r.seq_par != 0:
            raise ValueError(
                f"dataset.seq_len ({r.seq_len}) must be divisible by "
                f"training.sequence_parallelism ({r.seq_par})"
            )
        model_cfg.setdefault("max_len", r.seq_len)
        if (
            r.seq_par > 1
            and r.tensor_par == 1
            and r.pipe_par == 1
            and not r.zero
            and not r.is_moe
        ):
            # ring-attention path only; the GSPMD path (tensor_par or
            # zero or MoE) keeps seq_axis=None and lets the partitioner
            # distribute, and the PP x SP path builds its own
            # seq_axis'd stage blocks (pp_steps._stage_applies) — a
            # seq_axis model requires shard_map
            model_cfg.setdefault("seq_axis", SEQUENCE_AXIS)
        r.model = get_model(
            model_name,
            num_classes=cfg["dataset"]["n_classes"],
            dtype=r.compute_dtype,
            **model_cfg,
        )
        if r.is_moe and not (1 <= r.model.moe_every <= r.model.depth):
            # read from the CONSTRUCTED model, not re-hardcoded class
            # defaults (r2 review): moe_every 0 would div-by-zero at
            # init; > depth silently trains a fully dense model while
            # every MoE restriction still applies
            raise ValueError(
                f"model.moe_every ({r.model.moe_every}) must be in "
                f"[1, depth={r.model.depth}] (moe_every > depth "
                "would make no block MoE)"
            )
    else:
        # reference behavior: only ``model.name`` is read for the image
        # zoo — extra keys stay ignored (forwarding them would crash
        # ResNet/ViT constructors on e.g. annotation-only keys).  Two
        # sanctioned additive keys (validated above, before the LM split):
        # ``model.space_to_depth`` and ``model.bn_stat_dtype``.
        extra = {}
        if s2d:
            extra["space_to_depth"] = True
        if bn_stat:
            extra["bn_stat_dtype"] = {
                "float32": jnp.float32, "bfloat16": jnp.bfloat16,
            }[bn_stat]
        r.model = get_model(
            model_name,
            num_classes=cfg["dataset"]["n_classes"],
            axis_name=DATA_AXIS if r.sync_bn else None,
            dtype=r.compute_dtype,
            **extra,
        )


def parse_batch(r, train_cfg: dict) -> int:
    """Batch division + per-step micro-batching keys; returns the per-host
    batch size.  Reference parity notes inline (train_distributed.py:194)."""
    batch_size = train_cfg["batch_size"]
    local_devices = jax.local_device_count()
    # SURVEY §7 stage 4 decision, config-gated (additive key, unknown to
    # the reference schema):
    #   batch_division: local  — reference parity (:194): per-device batch
    #       divides by the LOCAL device count, so the global batch scales
    #       with node count (default).
    #   batch_division: world  — divide by the WORLD device count, so cfg
    #       batch_size IS the global batch at any topology.
    division = train_cfg.get("batch_division", "local")
    if division not in ("local", "world"):
        raise ValueError(
            f"training.batch_division must be 'local' or 'world', got {division!r}"
        )
    # Batch rows shard over the DATA axis only; each data shard spans a
    # seq_par x tensor_par device group (either may be 1), so the
    # division unit is a data shard, not a device.
    non_data = r.seq_par * r.tensor_par * r.pipe_par if r.is_lm else 1
    units_local = local_devices // non_data
    units_world = r.world_size // non_data
    # Additive key ``training.grad_accumulation``: per-step micro-batch
    # count (lax.scan inside the compiled step — activation memory / N,
    # identical update math; engine/steps.py).
    r.grad_accum = int(train_cfg.get("grad_accumulation", 1))
    if r.grad_accum < 1:
        raise ValueError(f"grad_accumulation must be >= 1, got {r.grad_accum}")
    if r.grad_accum > 1 and r.pipe_par > 1:
        raise ValueError(
            "grad_accumulation is redundant under pipeline_parallelism — "
            "raise training.microbatches instead (same memory effect, "
            "and it also shrinks the pipeline bubble)"
        )
    # Additive keys: torch-convention label smoothing + params EMA
    # (evaluation runs with the EMA weights when enabled).
    r.label_smoothing = float(train_cfg.get("label_smoothing", 0.0))
    if not (0.0 <= r.label_smoothing < 1.0):
        raise ValueError(
            f"label_smoothing must be in [0, 1), got {r.label_smoothing}"
        )
    ema_cfg = train_cfg.get("ema")
    r.ema_decay = float(ema_cfg["decay"]) if ema_cfg else None
    if r.ema_decay is not None and not (0.0 < r.ema_decay < 1.0):
        raise ValueError(f"ema.decay must be in (0, 1), got {r.ema_decay}")
    if r.ema_decay is not None and r.is_lm:
        raise ValueError("training.ema is only wired for the image task")
    if r.distributed:
        divisor = units_world if division == "world" else units_local
        per_device_batch = batch_size // max(divisor, 1)
        if per_device_batch == 0 or divisor == 0:
            raise ValueError(
                f"batch_size {batch_size} < {division} batch-shard count {divisor}"
            )
        if division == "world" and batch_size % divisor != 0:
            # the mode's whole contract is "cfg batch_size IS the global
            # batch" — a silent floor would break it, so fail loudly
            raise ValueError(
                f"batch_division: world requires batch_size ({batch_size}) "
                f"divisible by the world batch-shard count ({divisor})"
            )
        host_batch = per_device_batch * units_local
    else:
        host_batch = batch_size
        per_device_batch = batch_size
    if per_device_batch % r.grad_accum != 0:
        # fail fast like every other config error, not at jit trace time
        raise ValueError(
            f"per-shard batch ({per_device_batch}) not divisible by "
            f"training.grad_accumulation ({r.grad_accum})"
        )
    if r.pipe_par > 1 and per_device_batch % r.microbatches != 0:
        raise ValueError(
            f"per-shard batch ({per_device_batch}) not divisible by "
            f"training.microbatches ({r.microbatches})"
        )
    return host_batch


def parse_fault_tolerance(r, train_cfg: dict) -> None:
    """Parse the additive ``training.fault_tolerance`` section (all off by
    default — reference parity) onto the runner:

    .. code-block:: yaml

        training:
            fault_tolerance:
                anomaly:               # anomaly-step guard (engine/steps.py)
                    enabled: true      # implied by a non-empty section
                    grad_norm_factor: 10.0   # 0 = non-finite-only check
                    window: 64         # trailing-median history length
                    max_consecutive: 5 # then roll back to last checkpoint
                watchdog:              # hung-step watchdog (engine/watchdog.py)
                    enabled: true
                    factor: 10.0       # x trailing-median step time
                    min_seconds: 60.0  # floor (compiles, first steps)
                    poll_seconds: null # default min_seconds / 4
                    checkpoint_and_exit: false  # fire the PreemptionGuard
                fault_spec: null       # injection script (engine/fault.py;
                                       # the PDT_FAULT_SPEC env var wins)
    """
    ft = train_cfg.get("fault_tolerance") or {}
    unknown = set(ft) - {"anomaly", "watchdog", "fault_spec"}
    if unknown:
        raise ValueError(
            f"training.fault_tolerance: unknown key(s) {sorted(unknown)} "
            "(want anomaly/watchdog/fault_spec)"
        )

    an = ft.get("anomaly") or {}
    unknown = set(an) - {"enabled", "grad_norm_factor", "window", "max_consecutive"}
    if unknown:
        raise ValueError(
            f"training.fault_tolerance.anomaly: unknown key(s) "
            f"{sorted(unknown)} (want enabled/grad_norm_factor/window/"
            "max_consecutive)"
        )
    r.anomaly_enabled = bool(an) and bool(an.get("enabled", True))
    r.anomaly_factor = float(an.get("grad_norm_factor", 10.0))
    r.anomaly_window = int(an.get("window", 64))
    r.anomaly_max_consec = int(an.get("max_consecutive", 5))
    if r.anomaly_factor < 0:
        raise ValueError(
            "fault_tolerance.anomaly.grad_norm_factor must be >= 0 "
            f"(0 = non-finite-only), got {r.anomaly_factor}"
        )
    if r.anomaly_window < 1:
        raise ValueError(
            f"fault_tolerance.anomaly.window must be >= 1, got {r.anomaly_window}"
        )
    if r.anomaly_max_consec < 1:
        raise ValueError(
            "fault_tolerance.anomaly.max_consecutive must be >= 1, got "
            f"{r.anomaly_max_consec}"
        )

    wd = ft.get("watchdog") or {}
    unknown = set(wd) - {
        "enabled", "factor", "min_seconds", "poll_seconds", "window",
        "warmup", "checkpoint_and_exit",
    }
    if unknown:
        raise ValueError(
            f"training.fault_tolerance.watchdog: unknown key(s) "
            f"{sorted(unknown)} (want enabled/factor/min_seconds/"
            "poll_seconds/window/warmup/checkpoint_and_exit)"
        )
    r.watchdog_enabled = bool(wd) and bool(wd.get("enabled", True))
    r.watchdog_factor = float(wd.get("factor", 10.0))
    r.watchdog_min_seconds = float(wd.get("min_seconds", 60.0))
    r.watchdog_poll = (
        float(wd["poll_seconds"]) if wd.get("poll_seconds") is not None else None
    )
    r.watchdog_window = int(wd.get("window", 32))
    r.watchdog_warmup = int(wd.get("warmup", 3))
    r.watchdog_exit = bool(wd.get("checkpoint_and_exit", False))
    if r.watchdog_enabled:
        if r.watchdog_factor <= 1.0:
            raise ValueError(
                "fault_tolerance.watchdog.factor must be > 1, got "
                f"{r.watchdog_factor}"
            )
        if r.watchdog_min_seconds <= 0:
            raise ValueError(
                "fault_tolerance.watchdog.min_seconds must be > 0, got "
                f"{r.watchdog_min_seconds}"
            )
        if r.watchdog_poll is not None and r.watchdog_poll <= 0:
            raise ValueError(
                "fault_tolerance.watchdog.poll_seconds must be > 0, got "
                f"{r.watchdog_poll}"
            )
        if r.watchdog_warmup < 1:
            raise ValueError(
                "fault_tolerance.watchdog.warmup must be >= 1, got "
                f"{r.watchdog_warmup}"
            )

    spec = ft.get("fault_spec")
    r.fault_spec = str(spec) if spec else None
    if r.fault_spec:
        # validate the spec HERE, at config-parse time: an unknown kind or
        # malformed entry raises the descriptive ValueError immediately
        # instead of silently never firing (engine/fault.py grammar)
        from .fault import FaultInjector

        FaultInjector(r.fault_spec)


def parse_elastic(r, train_cfg: dict) -> None:
    """Parse the additive ``training.elastic`` section (off by default) onto
    the runner — the multi-host elastic-recovery layer (engine/elastic.py):

    .. code-block:: yaml

        training:
            elastic:
                enabled: true          # implied by a non-empty section
                dir: null              # heartbeat dir (default:
                                       #   <checkpoint.dir>/heartbeats)
                heartbeat_interval: 0.5  # seconds between beats
                timeout: 5.0           # peer presumed dead past this
                startup_grace: null    # allowance for peers that have not
                                       # written a first beat (default
                                       # max(30, 4 x timeout))
    """
    el = train_cfg.get("elastic") or {}
    unknown = set(el) - {
        "enabled", "dir", "heartbeat_interval", "timeout", "startup_grace",
    }
    if unknown:
        raise ValueError(
            f"training.elastic: unknown key(s) {sorted(unknown)} "
            "(want enabled/dir/heartbeat_interval/timeout/startup_grace)"
        )
    r.elastic_enabled = bool(el) and bool(el.get("enabled", True))
    r.elastic_dir = el.get("dir")
    r.elastic_heartbeat_interval = float(el.get("heartbeat_interval", 0.5))
    r.elastic_timeout = float(el.get("timeout", 5.0))
    r.elastic_startup_grace = (
        float(el["startup_grace"]) if el.get("startup_grace") is not None
        else None
    )
    if r.elastic_enabled:
        if r.elastic_heartbeat_interval <= 0:
            raise ValueError(
                "training.elastic.heartbeat_interval must be > 0, got "
                f"{r.elastic_heartbeat_interval}"
            )
        if r.elastic_timeout <= r.elastic_heartbeat_interval:
            raise ValueError(
                f"training.elastic.timeout ({r.elastic_timeout}) must exceed "
                f"heartbeat_interval ({r.elastic_heartbeat_interval})"
            )
        ck = train_cfg.get("checkpoint") or {}
        if not (r.elastic_dir or ck.get("dir")):
            # without either dir there is nowhere to put heartbeats, and
            # without a checkpoint the detected peer loss has nothing to
            # save — the layer would detect and then lose the run anyway
            raise ValueError(
                "training.elastic requires training.checkpoint.dir (the "
                "heartbeat dir defaults to <checkpoint.dir>/heartbeats and "
                "peer loss triggers a checkpoint-and-exit), or an explicit "
                "training.elastic.dir"
            )


def parse_integrity(r, train_cfg: dict) -> None:
    """Parse the additive ``training.integrity`` section (off by default)
    onto the runner — the silent-data-corruption sentinel
    (engine/integrity.py):

    .. code-block:: yaml

        training:
            integrity:
                enabled: true         # implied by a non-empty section
                check_interval: 100   # steps between fingerprint votes
                replicas: null        # voters; null = real process count,
                                      # > process count simulates peers
                                      # (the 1-device injection/test path)
                max_consecutive: 2    # diverged checks before a replica is
                                      # PERSISTENTLY corrupt (quarantine)
    """
    ig = train_cfg.get("integrity") or {}
    unknown = set(ig) - {
        "enabled", "check_interval", "replicas", "max_consecutive",
    }
    if unknown:
        raise ValueError(
            f"training.integrity: unknown key(s) {sorted(unknown)} "
            "(want enabled/check_interval/replicas/max_consecutive)"
        )
    r.integrity_enabled = bool(ig) and bool(ig.get("enabled", True))
    r.integrity_check_interval = int(ig.get("check_interval", 100))
    r.integrity_replicas = (
        int(ig["replicas"]) if ig.get("replicas") is not None else None
    )
    r.integrity_max_consecutive = int(ig.get("max_consecutive", 2))
    if r.integrity_enabled:
        if r.integrity_check_interval < 1:
            raise ValueError(
                "training.integrity.check_interval must be >= 1, got "
                f"{r.integrity_check_interval}"
            )
        if r.integrity_replicas is not None and r.integrity_replicas < 1:
            raise ValueError(
                "training.integrity.replicas must be >= 1, got "
                f"{r.integrity_replicas}"
            )
        if r.integrity_max_consecutive < 1:
            raise ValueError(
                "training.integrity.max_consecutive must be >= 1, got "
                f"{r.integrity_max_consecutive}"
            )


def parse_telemetry(r, train_cfg: dict) -> None:
    """Parse the additive ``training.telemetry`` section (ON by default —
    the in-memory registry/goodput/retrace layer is near-free and files are
    only written when ``dir`` is set) onto the runner (telemetry/):

    .. code-block:: yaml

        training:
            telemetry:
                enabled: true          # in-memory instruments + summary
                dir: null              # spans_rank<k>.jsonl, snapshots.jsonl,
                                       # profile/ captures land here
                snapshot_interval: 100 # steps between JSONL/TB snapshots
                span_ring: 256         # in-memory spans kept for diagnostics
                tensorboard: true      # mirror snapshots into the TB writer
                retrace_warn: 3        # compiles per fn before the storm warn
                capture:               # on-demand jax.profiler window
                    signal: SIGUSR2    # arm via kill -USR2 <pid> (null = off)
                    n_iters: 5         # window length in steps
                    at_iter: null      # config-triggered arm at this step
                    dir: null          # default <telemetry.dir>/profile
    """
    tl = train_cfg.get("telemetry") or {}
    unknown = set(tl) - {
        "enabled", "dir", "snapshot_interval", "span_ring", "tensorboard",
        "retrace_warn", "capture",
    }
    if unknown:
        raise ValueError(
            f"training.telemetry: unknown key(s) {sorted(unknown)} "
            "(want enabled/dir/snapshot_interval/span_ring/tensorboard/"
            "retrace_warn/capture)"
        )
    r.telemetry_enabled = bool(tl.get("enabled", True))
    r.telemetry_dir = tl.get("dir")
    r.telemetry_interval = int(tl.get("snapshot_interval", 100))
    r.telemetry_span_ring = int(tl.get("span_ring", 256))
    r.telemetry_tensorboard = bool(tl.get("tensorboard", True))
    r.telemetry_retrace_warn = int(tl.get("retrace_warn", 3))
    if r.telemetry_interval < 1:
        raise ValueError(
            "training.telemetry.snapshot_interval must be >= 1, got "
            f"{r.telemetry_interval}"
        )
    if r.telemetry_span_ring < 1:
        raise ValueError(
            "training.telemetry.span_ring must be >= 1, got "
            f"{r.telemetry_span_ring}"
        )
    if r.telemetry_retrace_warn < 1:
        raise ValueError(
            "training.telemetry.retrace_warn must be >= 1, got "
            f"{r.telemetry_retrace_warn}"
        )

    cap = tl.get("capture") or {}
    unknown = set(cap) - {"signal", "n_iters", "at_iter", "dir", "python_tracer"}
    if unknown:
        raise ValueError(
            f"training.telemetry.capture: unknown key(s) {sorted(unknown)} "
            "(want signal/n_iters/at_iter/dir/python_tracer)"
        )
    from ..telemetry.capture import parse_signal

    # an explicit capture section arms the signal path by default; without
    # one nothing is installed (signal handlers are process-global state)
    r.telemetry_capture_signal = (
        parse_signal(cap.get("signal", "SIGUSR2")) if cap else None
    )
    r.telemetry_capture_iters = int(cap.get("n_iters", 5))
    r.telemetry_capture_at_iter = (
        int(cap["at_iter"]) if cap.get("at_iter") is not None else None
    )
    r.telemetry_capture_dir = cap.get("dir")
    r.telemetry_capture_python_tracer = bool(cap.get("python_tracer", False))
    if r.telemetry_capture_iters < 1:
        raise ValueError(
            "training.telemetry.capture.n_iters must be >= 1, got "
            f"{r.telemetry_capture_iters}"
        )
    if cap and not (
        r.telemetry_capture_dir or r.telemetry_dir
    ):
        raise ValueError(
            "training.telemetry.capture needs somewhere to write traces: "
            "set training.telemetry.dir or training.telemetry.capture.dir"
        )
