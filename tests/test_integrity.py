"""Integrity sentinel: fingerprints, divergence votes, checksummed
checkpoints, quarantine (engine/integrity.py + the checkpoint/runner/data
wiring).  Every scenario is driven through deterministic injection
(``sdc_flip``/``ckpt_corrupt``) — silent corruption is exactly the failure
class production never reproduces on demand."""
import json
import os

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.engine import Runner
from pytorch_distributed_training_tpu.engine import fault
from pytorch_distributed_training_tpu.engine.checkpoint import (
    Checkpointer,
    CheckpointIntegrityError,
)
from pytorch_distributed_training_tpu.engine.integrity import (
    DivergedReplicaError,
    IntegritySentinel,
    fingerprint_state,
    leaf_checksums,
    _flip_one_bit,
)


@pytest.fixture(autouse=True)
def _fault_hygiene():
    """Process-global injector/counters must not leak between tests."""
    fault.install(None)
    fault.reset_counters()
    yield
    fault.install(None)
    fault.reset_counters()


@pytest.fixture
def one_device_mesh(monkeypatch):
    """ONE-device mesh — same rationale as the fault-tolerance suite's
    fixture: the sentinel logic under test is device-count independent."""
    from pytorch_distributed_training_tpu.engine import paths
    from pytorch_distributed_training_tpu.parallel import make_mesh

    mesh = make_mesh(jax.devices()[:1])
    monkeypatch.setattr(paths, "make_mesh", lambda *a, **kw: mesh)
    return mesh


def _tree(fill=1.0):
    return {
        "params": {
            "w": jnp.arange(24, dtype=jnp.float32).reshape(6, 4) * fill,
            "b": jnp.zeros((4,), jnp.float32),
        },
        "step": jnp.int32(3),
    }


# ======================================================================
# fingerprint primitives
# ======================================================================
def test_fingerprint_deterministic_and_bit_sensitive():
    a, b = _tree(), _tree()
    assert fingerprint_state(a) == fingerprint_state(b)
    flipped = _flip_one_bit(a)
    assert fingerprint_state(flipped) != fingerprint_state(a)
    # the flip is a LOW bit: numerically negligible (the anomaly guard
    # could never see it), only the bitwise fingerprint can
    da = np.abs(
        np.asarray(flipped["params"]["w"]) - np.asarray(a["params"]["w"])
    ).max()
    db = np.abs(
        np.asarray(flipped["params"]["b"]) - np.asarray(a["params"]["b"])
    ).max()
    assert max(da, db) < 1e-5


def test_fingerprint_position_sensitive():
    # same multiset of words, different positions -> different hash (a
    # plain XOR/sum of words would collide here)
    a = {"w": jnp.asarray([1.0, 2.0], jnp.float32)}
    b = {"w": jnp.asarray([2.0, 1.0], jnp.float32)}
    assert fingerprint_state(a) != fingerprint_state(b)


def test_leaf_checksums_detect_flip_and_cover_all_leaves():
    t = _tree()
    cs = leaf_checksums(t)
    assert len(cs) == len(jax.tree_util.tree_leaves(t))
    cs2 = leaf_checksums(_flip_one_bit(t))
    assert set(cs) == set(cs2) and cs != cs2


# ======================================================================
# the vote: attribution + classification (simulated replicas, 1 device)
# ======================================================================
@pytest.mark.parametrize("bad_rank", [0, 1, 2, 3])
def test_vote_attributes_exact_rank(bad_rank):
    sen = IntegritySentinel(
        check_interval=1, replicas=4, rank=0, process_count=1,
        max_consecutive=2,
    )
    state = _tree()
    sen.retain(state, -1)
    state, verdict = sen.check(state, 0)
    assert verdict["diverged"] == []
    sen.arm_flip(bad_rank)
    state, verdict = sen.check(state, 1)
    assert verdict["diverged"] == [bad_rank]
    assert verdict["local_diverged"] == (bad_rank == 0)
    assert verdict["persistent"] == []
    assert verdict["majority"] is not None


def test_transient_vs_persistent_classification():
    sen = IntegritySentinel(
        check_interval=1, replicas=3, rank=0, process_count=1,
        max_consecutive=2,
    )
    state = _tree()
    # one diverged check: transient (counted, not persistent)
    sen.arm_flip(1)
    state, v = sen.check(state, 0)
    assert v["diverged"] == [1] and v["persistent"] == []
    # a clean check in between resets the consecutive count
    state, v = sen.check(state, 1)
    assert v["diverged"] == []
    sen.arm_flip(1)
    state, v = sen.check(state, 2)
    assert v["persistent"] == []
    # the SECOND consecutive diverged check crosses max_consecutive
    sen.arm_flip(1)
    state, v = sen.check(state, 3)
    assert v["diverged"] == [1] and v["persistent"] == [1]
    c = fault.counters()
    assert c.get("integrity_checks") == 4
    assert c.get("integrity_divergences") == 3


def test_local_flip_really_corrupts_and_snapshot_restores():
    sen = IntegritySentinel(
        check_interval=1, replicas=3, rank=0, process_count=1,
    )
    state = _tree()
    healthy_fp = fingerprint_state(state)
    sen.retain(state, 7, {"epoch": 1, "batch_in_epoch": 2})
    sen.arm_flip(0)
    state, verdict = sen.check(state, 8)
    # the returned state IS the corrupted one (detection is not fiction)
    assert fingerprint_state(state) != healthy_fp
    assert verdict["local_diverged"]
    restored, snap_step, position, ok = sen.restore_snapshot(state)
    assert ok and snap_step == 7
    assert position == {"epoch": 1, "batch_in_epoch": 2}
    assert fingerprint_state(restored) == healthy_fp


def test_diverged_replica_error_is_a_peer_loss():
    from pytorch_distributed_training_tpu.engine.elastic import PeerLostError

    e = DivergedReplicaError("bad", ranks=(2,), step=11)
    assert isinstance(e, PeerLostError)
    assert e.ranks == (2,) and e.dead_ranks == (2,)
    assert e.step == 11 and not e.mid_step


# ======================================================================
# fault-grammar surface
# ======================================================================
def test_spec_parses_sdc_flip_and_ckpt_corrupt():
    inj = fault.FaultInjector("sdc_flip@4:2;sdc_flip@9;ckpt_corrupt@11")
    assert inj.take("sdc_flip", 4) == 2.0
    assert inj.take("sdc_flip", 4) is None  # one-shot
    assert inj.take("sdc_flip", 9) == 0.0  # default rank 0
    assert inj.take("ckpt_corrupt", 11) == 1.0
    with pytest.raises(ValueError, match="takes no arg"):
        fault.FaultInjector("ckpt_corrupt@1:3")
    with pytest.raises(ValueError) as ei:
        fault.FaultInjector("sdc_wobble@1")
    assert "sdc_flip" in str(ei.value) and "ckpt_corrupt" in str(ei.value)


# ======================================================================
# checkpoint content integrity (manifest write/verify/fallback)
# ======================================================================
def _tiny_state(fill):
    from pytorch_distributed_training_tpu.engine import TrainState
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel import replicated_sharding
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh

    opt = SGD(lr=0.1, momentum=0.9)
    params = {"w": jnp.full((8, 4), float(fill)), "b": jnp.full((4,), float(fill))}
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    return jax.device_put(state, replicated_sharding(make_mesh()))


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_every_save_writes_a_verifying_manifest(tmp_path):
    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=4)
    ck.save(0, _tiny_state(0.5), extras={"epoch": 0})
    ck.save(1, _tiny_state(1.5), extras={"epoch": 0})
    for it in (0, 1):
        mpath = os.path.join(ck.directory, f"manifest_{it}.json")
        assert os.path.exists(mpath)
        with open(mpath) as fp:
            manifest = json.load(fp)
        assert manifest["step"] == it and manifest["algo"] == "crc32-leaf"
        assert manifest["leaves"] == leaf_checksums(_tiny_state(it + 0.5))
    restored, next_iter = ck.restore_latest(_tiny_state(0.0))
    assert next_iter == 2
    _assert_trees_equal(restored, _tiny_state(1.5))
    assert "integrity_manifest_rejects" not in fault.counters()


def test_ckpt_corrupt_rejected_at_restore_falls_back(tmp_path):
    """The tentpole checkpoint scenario: a corrupt-but-well-formed newest
    checkpoint restores cleanly through orbax, fails CRC verification, and
    loses to the newest VERIFIED earlier step."""
    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=4)
    ck.save(0, _tiny_state(0.0))
    fault.install("ckpt_corrupt@1")
    try:
        ck.save(1, _tiny_state(1.0))  # bit-flipped AFTER its manifest
    finally:
        fault.install(None)
    restored, next_iter = ck.restore_latest(_tiny_state(9.0))
    assert next_iter == 1  # step 1 rejected, step 0 restored
    _assert_trees_equal(restored, _tiny_state(0.0))
    c = fault.counters()
    assert c.get("injected_ckpt_corruptions") == 1
    assert c.get("integrity_manifest_rejects") == 1
    assert c.get("ckpt_fallbacks") == 1


def test_ckpt_corrupt_async_path_also_rejected(tmp_path):
    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=4,
                      async_save=True, max_inflight=1)
    fault.install("ckpt_corrupt@1")
    try:
        ck.save(0, _tiny_state(0.0))
        ck.save(1, _tiny_state(1.0))
        ck.wait()
    finally:
        fault.install(None)
    restored, next_iter = ck.restore_latest(_tiny_state(9.0))
    assert next_iter == 1
    _assert_trees_equal(restored, _tiny_state(0.0))
    assert fault.counters().get("integrity_manifest_rejects") == 1


def test_manifestless_checkpoint_restores_with_single_warning(tmp_path, caplog):
    """Backward compatibility: a pre-manifest checkpoint (manifest deleted)
    restores fine — one warning, never a rejection."""
    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=4)
    ck.save(0, _tiny_state(0.0))
    ck.save(1, _tiny_state(1.0))
    for it in (0, 1):
        os.remove(os.path.join(ck.directory, f"manifest_{it}.json"))
    with caplog.at_level("WARNING"):
        restored, next_iter = ck.restore_latest(_tiny_state(9.0))
    assert next_iter == 2
    _assert_trees_equal(restored, _tiny_state(1.0))
    c = fault.counters()
    assert "integrity_manifest_rejects" not in c
    assert "ckpt_fallbacks" not in c
    warnings = [
        r for r in caplog.records if "no integrity manifest" in r.getMessage()
    ]
    assert len(warnings) == 1  # warn ONCE, not per step


def test_mispaired_sidecar_step_rejected(tmp_path):
    """The sidecar cross-check: a ``pipeline_<step>.json`` claiming a
    different step marks the checkpoint a corrupt candidate (fall back)
    instead of silently restoring the wrong pipeline position."""
    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=4)
    ck.save(0, _tiny_state(0.0), extras={"epoch": 0})
    ck.save(1, _tiny_state(1.0), extras={"epoch": 0})
    sidecar = os.path.join(ck.directory, "pipeline_1.json")
    with open(sidecar) as fp:
        payload = json.load(fp)
    assert payload["step"] == 1  # the new self-describing format
    payload["step"] = 999
    with open(sidecar, "w") as fp:
        json.dump(payload, fp)
    restored, next_iter = ck.restore_latest(_tiny_state(9.0))
    assert next_iter == 1  # step 1 rejected on the sidecar cross-check
    _assert_trees_equal(restored, _tiny_state(0.0))
    c = fault.counters()
    assert c.get("integrity_sidecar_rejects") == 1
    assert c.get("ckpt_fallbacks") == 1


def test_flat_legacy_sidecar_still_reads_and_passes(tmp_path):
    """A pre-wrapper sidecar (flat extras dict, no step field) must
    neither fail the cross-check nor break read_extras."""
    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=4)
    ck.save(0, _tiny_state(0.0), extras={"epoch": 4})
    sidecar = os.path.join(ck.directory, "pipeline_0.json")
    with open(sidecar, "w") as fp:
        json.dump({"epoch": 4}, fp)  # legacy format
    assert ck.read_extras(0) == {"epoch": 4}
    restored, next_iter = ck.restore_latest(_tiny_state(9.0))
    assert next_iter == 1
    assert "integrity_sidecar_rejects" not in fault.counters()


def test_manifests_garbage_collected_with_their_steps(tmp_path):
    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=2)
    for it in range(4):
        ck.save(it, _tiny_state(it), extras={"epoch": it})
    assert ck.all_steps() == [2, 3]
    manifests = sorted(
        f for f in os.listdir(ck.directory)
        if f.startswith("manifest_") and f.endswith(".json")
    )
    assert manifests == ["manifest_2.json", "manifest_3.json"]


# ======================================================================
# runner end-to-end: detect -> attribute -> classify -> recover
# ======================================================================
def _it_cfg(tmp_path, train_iters, fault_spec=None, ckpt=False,
            check_interval=2, replicas=3, max_consecutive=2):
    cfg = {
        "dataset": {
            "name": "synthetic", "root": str(tmp_path), "n_classes": 4,
            "image_size": 16, "n_samples": 64,
        },
        "training": {
            "optimizer": {
                "name": "SGD", "lr": 0.01, "weight_decay": 1.0e-4,
                "momentum": 0.9,
            },
            "lr_schedule": {
                "name": "multi_step", "milestones": [100], "gamma": 0.1,
            },
            "train_iters": train_iters,
            "print_interval": 10,
            "val_interval": 100,
            "batch_size": 16,
            "num_workers": 0,
            "sync_bn": False,
            "integrity": {
                "check_interval": check_interval,
                "replicas": replicas,
                "max_consecutive": max_consecutive,
            },
        },
        "validation": {"batch_size": 16, "num_workers": 0},
        "model": {"name": "ResNet18"},
    }
    if fault_spec is not None:
        cfg["training"]["fault_tolerance"] = {"fault_spec": fault_spec}
    if ckpt:
        cfg["training"]["checkpoint"] = {
            "dir": str(tmp_path / "ckpt"), "interval": 2, "resume": True,
        }
    return cfg


def _run(cfg):
    runner = Runner(
        num_nodes=1, rank=0, seed=3, dist_url="tcp://127.0.0.1:9901",
        dist_backend="tpu", multiprocessing=False, logger_queue=None,
        global_cfg=cfg, tb_writer_constructor=lambda: None,
    )
    runner()
    return runner


@pytest.mark.slow  # two full runner compiles (~30s) — over the tier-1 budget
def test_runner_flip_recovery_end_to_end(tmp_path, one_device_mesh):
    """The tentpole end-to-end: a flip on the LOCAL replica is detected at
    the next check, attributed, classified transient, the retained
    snapshot is restored, the replay re-converges; a later flip on a
    SIMULATED peer replica diverges one vote but never touches local state
    (no restore) — and the final state is bit-identical to a run that
    never saw either flip."""
    clean = _run(_it_cfg(tmp_path / "clean", train_iters=6))
    clean_fp = fingerprint_state(clean.state)
    assert fault.counters().get("integrity_checks") == 3
    assert "integrity_divergences" not in fault.counters()

    fault.reset_counters()
    injected = _run(
        _it_cfg(
            tmp_path / "flip", train_iters=6,
            fault_spec="sdc_flip@2:0;sdc_flip@4:2",
        )
    )
    assert injected.iter == 6
    c = fault.counters()
    assert c.get("injected_sdc_flips") == 2
    assert c.get("integrity_divergences") == 2
    # only the rank-0 flip restored the snapshot; the remote (rank 2)
    # divergence was attributed without touching local state
    assert c.get("integrity_transient_flips") == 1
    assert "integrity_quarantines" not in c
    assert fingerprint_state(injected.state) == clean_fp
    _assert_trees_equal(injected.state.params, clean.state.params)


@pytest.mark.slow  # full runner compile — over the tier-1 budget
def test_runner_persistent_divergence_quarantines(tmp_path, one_device_mesh):
    """A replica that stays diverged for max_consecutive checks is
    quarantined: diagnosed DivergedReplicaError + emergency checkpoint
    from the healthy local rank."""
    cfg = _it_cfg(
        tmp_path, train_iters=8, ckpt=True,
        fault_spec="sdc_flip@2:1;sdc_flip@4:1",
    )
    with pytest.raises(DivergedReplicaError) as ei:
        _run(cfg)
    assert ei.value.ranks == (1,)
    c = fault.counters()
    assert c.get("integrity_quarantines") == 1
    assert c.get("integrity_divergences") == 2
    # the HEALTHY local rank wrote the emergency checkpoint
    emergency = os.path.join(str(tmp_path / "ckpt"), "emergency")
    assert os.path.isdir(emergency) and os.listdir(emergency)


# ======================================================================
# data-loader quarantine (satellite): corrupt sample != dead worker
# ======================================================================
def _image_folder(tmp_path, n_good=3):
    from PIL import Image

    root = tmp_path / "imgs"
    cdir = root / "train" / "class_a"
    cdir.mkdir(parents=True)
    rng = np.random.default_rng(0)
    for i in range(n_good):
        Image.fromarray(
            rng.integers(0, 255, (32, 32, 3), dtype=np.uint8)
        ).save(cdir / f"good_{i}.jpg")
    # a TRUNCATED jpeg: valid header (PIL opens it, dims readable), body
    # cut off mid-scan (decode raises)
    full = (cdir / "good_0.jpg").read_bytes()
    (cdir / "bad_trunc.jpg").write_bytes(full[: len(full) // 2])
    return str(root)


def test_truncated_jpeg_quarantined_not_fatal(tmp_path, caplog):
    from pytorch_distributed_training_tpu.data.datasets import ImageFolderDataset

    ds = ImageFolderDataset(_image_folder(tmp_path), "train", image_size=16)
    bad_idx = next(
        i for i, (p, _) in enumerate(ds.samples) if "bad_trunc" in p
    )
    with caplog.at_level("WARNING"):
        px1, label1 = ds.get_sample(bad_idx, np.random.default_rng(1))
        px2, label2 = ds.get_sample(bad_idx, np.random.default_rng(2))
    assert px1.shape == (16, 16, 3) and px1.dtype == np.uint8
    assert not px1.any()  # quarantined rows are zeros under the true label
    assert label1 == label2 == ds.samples[bad_idx][1]
    assert fault.counters().get("data_corrupt_samples") == 2
    logged = [
        r for r in caplog.records
        if "quarantined corrupt sample" in r.getMessage()
    ]
    assert len(logged) == 1  # once per path, not per occurrence
    # a healthy sample still decodes real pixels
    good_idx = next(
        i for i, (p, _) in enumerate(ds.samples) if "good_" in p
    )
    good_px, _ = ds.get_sample(good_idx, np.random.default_rng(1))
    assert good_px.any()


def test_loader_epoch_survives_corrupt_sample(tmp_path):
    from pytorch_distributed_training_tpu.data import DataLoader, SequentialSampler
    from pytorch_distributed_training_tpu.data.datasets import ImageFolderDataset

    ds = ImageFolderDataset(_image_folder(tmp_path), "train", image_size=16)
    loader = DataLoader(
        ds, batch_size=2, sampler=SequentialSampler(len(ds)),
        num_workers=0, drop_last=False,
    )
    batches = list(loader)
    assert sum(b[0].shape[0] for b in batches) == len(ds)
    assert fault.counters().get("data_corrupt_samples", 0) >= 1
    loader.close()
