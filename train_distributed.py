"""TPU-native distributed ImageNet training — CLI entry point.

The user-facing surface of the reference (train_distributed.py:38-86) kept
intact: the same 9 flags, the same YAML configs, the same log/TensorBoard
layout — with ``--dist-backend tpu`` selecting the JAX/XLA runtime (the
``nccl`` default is accepted as a compat alias).  ``--multiprocessing`` is a
no-op under the single-controller-per-host design (SURVEY.md §7 deviations).

Crash handling reproduces the reference's *intent*, not its bug: on failure
only the TensorBoard event subdir (``<log-dir>/tf-board-logs``) is removed —
the reference's ``shutil.rmtree(log_dir, "tf-board-logs")`` (:82) passes the
subdir name as ``ignore_errors`` and would delete the whole log dir.  The
reference then falls off ``main()`` with status 0; here a crashed run exits
with status 1, so a launcher or a smoke run cannot take it for a finished one.
"""
import argparse
import os
import shutil
import time
import traceback
from functools import partial

from pytorch_distributed_training_tpu.config_parsing import (
    TB_SUBDIR,
    get_cfg,
    get_tb_writer,
    get_train_logger,
)
from pytorch_distributed_training_tpu.engine import Runner
from pytorch_distributed_training_tpu.logger import MultiProcessLoggerListener
from pytorch_distributed_training_tpu.utils import make_deterministic

START_METHOD = "spawn"


def main(argv=None) -> Runner:
    """Parse the flags, train, and return the finished :class:`Runner`.

    A failure inside the runner is logged CRITICAL with its traceback, the
    TensorBoard event dir is removed, the log listener is stopped, and the
    exception propagates: the CLI exits with status 1, and an in-process
    caller (``chip_smoke.py``) sees the failure itself.
    """
    parser = argparse.ArgumentParser(description="TPU ImageNet Training")
    parser.add_argument("--num-nodes", default=-1, type=int,
                        help="number of hosts for distributed training")
    parser.add_argument("--rank", default=-1, type=int,
                        help="host rank for distributed training")
    parser.add_argument("--dist-url", default="tcp://127.0.0.1:9876", type=str,
                        help="coordinator address (maps to jax.distributed.initialize)")
    parser.add_argument("--dist-backend", default="tpu", type=str,
                        help="distributed backend (tpu/xla; nccl accepted as alias)")
    parser.add_argument("--seed", default=None, type=int,
                        help="seed for initializing training.")
    parser.add_argument("--multiprocessing", action="store_true",
                        help="compat no-op: one controller process drives all local devices")
    parser.add_argument("--file-name-cfg", type=str)
    parser.add_argument("--log-dir", type=str)
    parser.add_argument("--cfg-filepath", type=str)
    args = parser.parse_args(argv)

    if args.seed is not None:
        print("Set seed:", args.seed)
        make_deterministic(args.seed)

    logger_constructor = partial(
        get_train_logger, logdir=args.log_dir, filename=args.file_name_cfg
    )
    logger_listener = MultiProcessLoggerListener(logger_constructor, START_METHOD)
    logger = logger_listener.get_logger()

    global_cfg = get_cfg(args.cfg_filepath)
    runner = Runner(
        num_nodes=args.num_nodes,
        rank=args.rank,
        seed=args.seed,
        dist_url=args.dist_url,
        dist_backend=args.dist_backend,
        multiprocessing=args.multiprocessing,
        logger_queue=logger_listener.queue,
        global_cfg=global_cfg,
        tb_writer_constructor=partial(get_tb_writer, args.log_dir, args.file_name_cfg),
    )
    logger.info("Starting distributed runner")
    try:
        runner()
    except Exception as e:
        tb = traceback.format_exc()
        logger.critical("While running, exception:\n%s\nTraceback:\n%s", str(e), str(tb))
        shutil.rmtree(os.path.join(args.log_dir, TB_SUBDIR), ignore_errors=True)
        time.sleep(1.5)
        raise
    finally:
        # make sure listener is stopped
        logger_listener.stop()
    return runner


if __name__ == "__main__":
    main()
