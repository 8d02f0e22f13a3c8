"""TPU-native distributed training framework.

A brand-new JAX/XLA re-design with the capabilities of
zhfeing/pytorch-distributed-training (reference mounted at /root/reference):
multi-host data-parallel ImageNet classification with synchronized batch
normalization, iteration-based training, distributed validation, multiprocess
logging and TensorBoard.

Layer map (mirrors SURVEY.md L1-L8, re-architected for TPU):
  - ``config_parsing``  -- YAML config + loggers + TB writer factories
                           (reference: dl_lib.config_parsing, train_distributed.py:29)
  - ``logger``          -- multiprocess log aggregation
                           (reference: dl_lib.logger.MultiProcessLoggerListener, :28)
  - ``utils``           -- determinism + infinite iterator helpers (:27)
  - ``models``          -- ResNet-18/34/50/101/152 zoo in Flax (:25)
  - ``data``            -- datasets + distributed samplers + prefetching loader (:26, :213-241)
  - ``optimizers``      -- PyTorch-semantics SGD (+LARS) factories (:30)
  - ``schedulers``      -- per-iteration multi_step (+warmup) schedules (:31)
  - ``metrics``         -- top-k accuracy + AverageMeter (:32)
  - ``parallel``        -- device mesh, multi-host init, collective helpers
                           (reference: torch.distributed/NCCL, :149-154, :283)
  - ``ops``             -- TPU-native nn ops: distributed BatchNorm, losses,
                           Pallas kernels (reference: SyncBatchNorm/cuDNN natives)
  - ``engine``          -- Runner + pjit/shard_map train & eval steps
                           (reference: Runner, train_distributed.py:89-331)
"""

__version__ = "0.1.0"
