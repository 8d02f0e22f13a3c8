"""Feature-composition matrix (round-2 VERDICT next #8).

One parametrized test per cell of the ``training.*`` composition matrix:
every SUPPORTED combination must construct a Runner (all config validation
happens in ``Runner.__init__``, runner.py — the source of truth these
cases mirror), and every UNSUPPORTED combination must raise its documented
``ValueError`` — no silent acceptance, no undocumented walls.  The
README's "feature composition" table is generated from the same pairs.

Each supported cell runs 2 full training iterations end to end (compile +
execute on the 8-virtual-device mesh); the execution SEMANTICS of each
path carry their own parity oracles elsewhere (test_engine /
test_sequence_parallel / test_tensor_parallel / test_pipeline_parallel /
test_moe / test_grad_accum / test_ema_smoothing) — this matrix pins which
combinations are reachable and that each one actually trains.
"""
import types

import jax
import numpy as np
import pytest

from pytorch_distributed_training_tpu.engine import Runner
from pytorch_distributed_training_tpu.engine.paths import PATHS, select_path

LM_DATASET = {
    "name": "synthetic_text",
    "root": "/unused",
    "n_classes": 64,
    "seq_len": 32,
    "n_samples": 64,
}
IMG_DATASET = {
    "name": "synthetic",
    "root": "/unused",
    "n_classes": 8,
    "image_size": 32,
    "n_samples": 64,
}


def _cfg(task="lm", model_extra=None, **train_extra):
    is_lm = task == "lm"
    model = (
        {"name": "TransformerLM", "embed_dim": 32, "depth": 2, "num_heads": 4}
        if is_lm
        else {"name": "ResNet18"}
    )
    model.update(model_extra or {})
    return {
        "dataset": LM_DATASET if is_lm else IMG_DATASET,
        "training": {
            "optimizer": {
                "name": "SGD", "lr": 0.01, "weight_decay": 1e-4, "momentum": 0.9,
            },
            "lr_schedule": {"name": "multi_step", "milestones": [100], "gamma": 0.1},
            "train_iters": 2,
            "print_interval": 1,
            "val_interval": 100,
            "batch_size": 16,
            "num_workers": 1,
            "sync_bn": not is_lm,
            **train_extra,
        },
        "validation": {"batch_size": 16, "num_workers": 1},
        "model": model,
    }


class _NullTB:
    def add_scalar(self, *a, **k):
        pass


def _construct(cfg):
    runner = Runner(
        num_nodes=1, rank=0, seed=7, dist_url="tcp://127.0.0.1:9942",
        dist_backend="tpu", multiprocessing=False, logger_queue=None,
        global_cfg=cfg, tb_writer_constructor=_NullTB,
    )
    runner()  # config validation AND the 2-iteration run live in worker()
    return runner


# (id, cfg) — combinations that MUST construct.  Mirrors runner.py's
# path-selection logic; see the README "feature composition" table.
SUPPORTED = [
    ("sp4", _cfg(sequence_parallelism=4)),
    ("tp4", _cfg(tensor_parallelism=4)),
    ("sp2xtp2", _cfg(sequence_parallelism=2, tensor_parallelism=2)),
    ("pp2", _cfg(pipeline_parallelism=2, microbatches=4)),
    ("pp2-1f1b", _cfg(pipeline_parallelism=2, microbatches=4,
                      pp_schedule="1f1b")),
    ("pp2xtp2", _cfg(pipeline_parallelism=2, tensor_parallelism=2,
                     microbatches=4)),
    ("pp2xtp2-1f1b", _cfg(pipeline_parallelism=2, tensor_parallelism=2,
                          microbatches=4, pp_schedule="1f1b")),
    ("pp2xsp2", _cfg(pipeline_parallelism=2, sequence_parallelism=2,
                     microbatches=4)),
    ("pp2xsp2-1f1b", _cfg(pipeline_parallelism=2, sequence_parallelism=2,
                          microbatches=4, pp_schedule="1f1b")),
    ("zero", _cfg(zero=True)),
    ("zeroxpp2", _cfg(zero=True, pipeline_parallelism=2, microbatches=4)),
    ("zeroxpp2xtp2", _cfg(zero=True, pipeline_parallelism=2,
                          tensor_parallelism=2, microbatches=4)),
    ("zeroxpp2xsp2", _cfg(zero=True, pipeline_parallelism=2,
                          sequence_parallelism=2, microbatches=4)),
    ("zeroxtp2", _cfg(zero=True, tensor_parallelism=2)),
    ("zeroxsp2", _cfg(zero=True, sequence_parallelism=2)),
    ("zero2", _cfg(zero=2)),
    ("zero2xtp2", _cfg(zero=2, tensor_parallelism=2)),
    ("zero2xsp2", _cfg(zero=2, sequence_parallelism=2)),
    ("zero2-grad-accum", _cfg(zero=2, grad_accumulation=2)),
    ("zero2xpp2", _cfg(zero=2, pipeline_parallelism=2, microbatches=4)),
    ("zero2xpp2xtp2", _cfg(zero=2, pipeline_parallelism=2,
                           tensor_parallelism=2, microbatches=4)),
    ("zero3", _cfg(zero=3)),
    ("zero3xtp2", _cfg(zero=3, tensor_parallelism=2)),
    ("zero3xsp2", _cfg(zero=3, sequence_parallelism=2)),
    ("moe-ep4", _cfg(model_extra={"moe_experts": 4}, tensor_parallelism=4)),
    ("lm-grad-accum", _cfg(grad_accumulation=2)),
    ("lm-smoothing", _cfg(label_smoothing=0.1)),
    ("img-ema", _cfg(task="img", ema={"decay": 0.99})),
    ("img-grad-accum", _cfg(task="img", grad_accumulation=2)),
]

# (id, cfg, error-message fragment) — combinations that MUST raise.
UNSUPPORTED = [
    ("ppxspxtp", _cfg(pipeline_parallelism=2, sequence_parallelism=2,
                      tensor_parallelism=2),
     "three-way"),
    ("ppxmoe", _cfg(model_extra={"moe_experts": 4}, pipeline_parallelism=2),
     "moe_experts does not compose with pipeline_parallelism"),
    ("ppxgrad-accum", _cfg(pipeline_parallelism=2, grad_accumulation=2),
     "grad_accumulation is redundant under pipeline_parallelism"),
    ("micro-no-pp", _cfg(microbatches=4),
     "microbatches requires pipeline_parallelism"),
    ("sched-no-pp", _cfg(pp_schedule="1f1b"),
     "pp_schedule requires pipeline_parallelism"),
    ("bad-sched", _cfg(pipeline_parallelism=2, pp_schedule="interleaved"),
     "pp_schedule must be"),
    ("micro-lt-pp", _cfg(pipeline_parallelism=4, microbatches=2),
     "must be >= "),
    ("emaxlm", _cfg(ema={"decay": 0.99}),
     "ema is only wired for the image task"),
    ("zeroximg", _cfg(task="img", zero=True),
     "zero is only wired for the LM task"),
    ("zero3xpp2", _cfg(zero=3, pipeline_parallelism=2, microbatches=4),
     "zero: 3 does not compose with"),
    ("zero4", _cfg(zero=4), "training.zero must be"),
    ("spximg", _cfg(task="img", sequence_parallelism=2),
     "require model.name: TransformerLM"),
    ("moe-odd-ep", _cfg(model_extra={"moe_experts": 3}, tensor_parallelism=2),
     "must be divisible by training.tensor_parallelism"),
    ("ppxlars", _cfg(pipeline_parallelism=2, microbatches=4,
                     optimizer={"name": "LARS", "lr": 0.01}),
     "LARS is not supported with"),
    # training.comm (removed in PR 29) is refused whichever former key it
    # carries, on either task, and the message names what replaced it
    ("comm-overlap", _cfg(comm={"overlap": True}), "training.zero: 1"),
    ("comm-bucket_mb", _cfg(comm={"bucket_mb": 4}), "no longer accepted"),
    ("comm-reduce_dtype", _cfg(comm={"reduce_dtype": "bfloat16"}),
     "no longer accepted"),
    ("comm-zero1", _cfg(zero=1, comm={"overlap": True}), "training.zero: 1"),
    ("comm-img", _cfg(task="img", comm={"overlap": False}),
     "no longer accepted"),
]


@pytest.mark.slow
@pytest.mark.parametrize(
    "cfg", [c for _, c in SUPPORTED], ids=[i for i, _ in SUPPORTED]
)
def test_supported_composition_constructs(cfg):
    runner = _construct(cfg)
    assert runner.state is not None
    assert runner.iter == cfg["training"]["train_iters"]


@pytest.mark.parametrize(
    "cfg,msg",
    [(c, m) for _, c, m in UNSUPPORTED],
    ids=[i for i, _, _ in UNSUPPORTED],
)
def test_unsupported_composition_raises_documented_error(cfg, msg):
    with pytest.raises(ValueError, match=msg):
        _construct(cfg)


# ---------------------------------------------------------------- path table


def _knobs(**kw):
    base = dict(is_lm=True, pipe_par=1, tensor_par=1, zero=0, is_moe=False)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_path_table_is_exactly_four_rows():
    assert [p.name for p in PATHS] == ["pipeline", "gspmd", "ring-sp", "image-dp"]
    assert select_path(_knobs()).name == "ring-sp"
    assert select_path(_knobs(is_lm=False)).name == "image-dp"
    assert select_path(_knobs(pipe_par=2, zero=1)).name == "pipeline"


@pytest.mark.parametrize("zero", [1, 2, 3])
def test_zero_at_tp1_selects_gspmd(zero):
    """Sharded optimizer state has one home: every ``training.zero`` stage
    at ``tensor_parallelism: 1`` is the GSPMD family's."""
    assert select_path(_knobs(zero=zero)).name == "gspmd"


@pytest.mark.parametrize(
    "optimizer",
    [
        {"name": "SGD", "lr": 0.01, "weight_decay": 1e-4, "momentum": 0.9},
        {"name": "AdamW", "lr": 1e-3, "weight_decay": 0.01},
    ],
    ids=["sgd-momentum", "adamw"],
)
def test_zero1_through_config_matches_unsharded_moments(optimizer):
    """``training.zero: 1`` on 8 devices, from the config down: the Runner
    takes the gspmd row, the moment leaves hold 1/8 of themselves per device,
    and one step lands where the step with replicated (unsharded) moments
    lands — the ring-sp row on the same 8 x 1 mesh, same seed, same batch."""
    plain = _construct(_cfg(optimizer=optimizer, train_iters=1))
    zero1 = _construct(_cfg(optimizer=optimizer, train_iters=1, zero=1))
    assert select_path(plain).name == "ring-sp"
    assert select_path(zero1).name == "gspmd"
    moments = [
        leaf for leaf in jax.tree.leaves(zero1.state.opt_state) if leaf.ndim
    ]
    assert moments
    whole = [
        leaf.shape for leaf in moments
        if leaf.addressable_shards[0].data.size * 8 != leaf.size
    ]
    # the one exception is the program's own: a qkv/fc1 bias has a single
    # dimension and the (size-1) model axis already names it
    assert all(len(shape) == 1 for shape in whole), whole
    assert len(whole) < len(moments) / 4, whole
    for leaf in jax.tree.leaves(plain.state.opt_state):
        assert leaf.sharding.is_fully_replicated
    for a, b in zip(
        jax.tree.leaves(plain.state.params), jax.tree.leaves(zero1.state.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
