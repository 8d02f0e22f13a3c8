"""Sampler sharding + loader semantics (reference: train_distributed.py:213-241)."""
import numpy as np
import pytest

from pytorch_distributed_training_tpu.data import (
    DataLoader,
    DistributedShardSampler,
    RandomSampler,
    SequentialSampler,
    SyntheticDataset,
    get_dataset,
)
from pytorch_distributed_training_tpu.utils import make_iter_dataloader


@pytest.mark.quick
def test_shard_disjoint_cover_no_drop():
    n, world = 103, 4
    all_idx = []
    for r in range(world):
        s = DistributedShardSampler(n, world, r, shuffle=False, drop_last=False)
        idx = list(s)
        assert len(idx) == len(s) == 26  # ceil(103/4)
        all_idx.extend(idx)
    # padded total covers every sample; only the wrap-pad duplicates
    assert len(all_idx) == 104
    counts = np.bincount(all_idx, minlength=n)
    assert (counts >= 1).all()
    assert counts.sum() == 104


def test_shard_drop_last_matches_torch():
    import torch.utils.data as tud

    class _DS(tud.Dataset):
        def __len__(self):
            return 103

        def __getitem__(self, i):
            return i

    n, world = 103, 4
    for r in range(world):
        ours = DistributedShardSampler(n, world, r, shuffle=False, drop_last=True)
        theirs = tud.DistributedSampler(
            _DS(), num_replicas=world, rank=r, shuffle=False, drop_last=True
        )
        assert len(ours) == len(theirs) == 25
        assert list(ours) == list(theirs)  # same interleaved assignment


def test_epoch_reshuffle():
    s = DistributedShardSampler(64, 2, 0, shuffle=True, drop_last=True, seed=7)
    s.set_epoch(0)
    e0 = list(s)
    s.set_epoch(1)
    e1 = list(s)
    assert e0 != e1
    s.set_epoch(0)
    assert list(s) == e0  # deterministic per epoch


def test_shards_disjoint_when_shuffled():
    n, world = 64, 4
    shards = []
    for r in range(world):
        s = DistributedShardSampler(n, world, r, shuffle=True, drop_last=True, seed=3)
        s.set_epoch(5)
        shards.append(set(s))
    union = set().union(*shards)
    assert len(union) == n
    for a in range(world):
        for b in range(a + 1, world):
            assert not (shards[a] & shards[b])


def test_loader_shapes_and_drop_last():
    ds = SyntheticDataset(n_samples=50, n_classes=10, image_size=8)
    s = SequentialSampler(len(ds))
    train_like = DataLoader(ds, batch_size=16, sampler=s, drop_last=True)
    batches = list(train_like)
    assert len(batches) == len(train_like) == 3  # 50 // 16
    for img, label in batches:
        assert img.shape == (16, 8, 8, 3)
        assert label.shape == (16,)
        assert label.dtype == np.int64

    val_like = DataLoader(ds, batch_size=16, sampler=s, drop_last=False)
    batches = list(val_like)
    assert len(batches) == len(val_like) == 4  # ceil(50/16), tail wrap-padded
    assert batches[-1][0].shape == (16, 8, 8, 3)
    # wrap-pad: last batch tail repeats the shard head
    np.testing.assert_array_equal(batches[-1][1][2:], batches[0][1][: 16 - 2])


def test_loader_pads_shard_smaller_than_batch():
    """Tail padding must tile when the host shard < batch (static shapes)."""
    ds = SyntheticDataset(n_samples=25, n_classes=5, image_size=4)
    s = DistributedShardSampler(25, 4, 0, shuffle=False, drop_last=False)
    loader = DataLoader(ds, batch_size=64, sampler=s, drop_last=False)
    batches = list(loader)
    assert len(batches) == 1
    img, label = batches[0]
    assert img.shape == (64, 4, 4, 3)  # 7-sample shard tiled to a full batch
    assert label.shape == (64,)


def test_loader_threaded_matches_serial():
    ds = SyntheticDataset(n_samples=40, n_classes=5, image_size=4)
    s = SequentialSampler(len(ds))
    serial = list(DataLoader(ds, batch_size=8, sampler=s, num_workers=0))
    threaded = list(DataLoader(ds, batch_size=8, sampler=s, num_workers=4))
    for (i1, l1), (i2, l2) in zip(serial, threaded):
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(l1, l2)


def test_synthetic_deterministic_and_class_signal():
    ds = SyntheticDataset(n_samples=20, n_classes=4, image_size=8, split="train")
    img1, label1 = ds[3]
    img2, label2 = ds[3]
    np.testing.assert_array_equal(img1, img2)
    assert label1 == label2 == 3
    # train and val streams differ
    ds_val = SyntheticDataset(n_samples=20, n_classes=4, image_size=8, split="val")
    assert not np.allclose(ds[0][0], ds_val[0][0])


def test_make_iter_dataloader_advances_epochs():
    ds = SyntheticDataset(n_samples=8, n_classes=2, image_size=4)
    s = RandomSampler(len(ds), seed=0)
    loader = DataLoader(ds, batch_size=4, sampler=s, drop_last=True)
    gen = make_iter_dataloader(loader)
    first_epoch = [next(gen)[1] for _ in range(2)]
    second_epoch = [next(gen)[1] for _ in range(2)]
    # reshuffle happened between epochs (labels order differs)
    assert not all(
        np.array_equal(a, b) for a, b in zip(first_epoch, second_epoch)
    )


def test_skip_next_rejects_negative_and_clamps_past_epoch_end():
    ds = SyntheticDataset(n_samples=32, n_classes=4, image_size=4)
    s = SequentialSampler(len(ds))
    loader = DataLoader(ds, batch_size=8, sampler=s, drop_last=True)
    assert len(loader) == 4

    with pytest.raises(ValueError, match="got -1"):
        loader.skip_next(-1)

    # skip within the epoch: exactly the tail batches remain
    full = [label.copy() for _, label in loader]
    loader.skip_next(3)
    tail = [label.copy() for _, label in loader]
    assert len(tail) == 1
    np.testing.assert_array_equal(tail[0], full[3])

    # skip past the end is CLAMPED: the next iteration yields nothing (the
    # epoch-boundary resume case), and the one after is back to full length
    loader.skip_next(99)
    assert list(loader) == []
    assert len(list(loader)) == 4  # skip is one-shot, not sticky


def test_make_iter_dataloader_explicit_position_overrides_derivation():
    """The elastic-resume entry point: (start_epoch, skip_batches) places
    the stream independently of start_iter — required after a mesh reshape
    where the step counter divided by the CURRENT epoch length would land
    on the wrong sample."""
    ds = SyntheticDataset(n_samples=16, n_classes=2, image_size=4)

    def fresh():
        s = RandomSampler(len(ds), seed=5)
        return DataLoader(ds, batch_size=4, sampler=s, drop_last=True)

    straight = make_iter_dataloader(fresh())
    want = [next(straight)[1] for _ in range(7)]  # epoch 0 (4) + epoch 1 (3)

    resumed = make_iter_dataloader(fresh(), start_epoch=1, skip_batches=2)
    got = [next(resumed)[1] for _ in range(1)]
    np.testing.assert_array_equal(got[0], want[6])  # epoch 1, batch 2

    with pytest.raises(ValueError, match="together"):
        make_iter_dataloader(fresh(), start_epoch=1)
    with pytest.raises(ValueError, match=">= 0"):
        make_iter_dataloader(fresh(), start_epoch=-1, skip_batches=0)


def test_get_dataset_factory():
    ds = get_dataset("synthetic", "/nonexistent", "train", n_classes=7, image_size=16, n_samples=32)
    assert len(ds) == 32
    img, label = ds[0]
    assert img.shape == (16, 16, 3)
    assert 0 <= label < 7
    with pytest.raises(KeyError):
        get_dataset("cifar10", "/x", "train")
    with pytest.raises(FileNotFoundError):
        get_dataset("imagenet", "/nonexistent", "train")


# ------------------------------------------------------------------- spans
@pytest.mark.parametrize("mode", ["thread", "native", "process"])
def test_loader_emits_one_batch_assemble_a_batch(mode, tmp_path):
    """Every assembly backend brackets each batch in one ``batch_assemble``
    span carrying its sample count, and the consumer's wait for a batch in
    ``loader_wait`` (telemetry/spans.py; the benchmark's input-pipeline
    metrics read both)."""
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    if mode == "native":
        from PIL import Image

        from pytorch_distributed_training_tpu.native import native_available

        if not native_available():
            pytest.skip("native library unavailable")
        rng = np.random.default_rng(0)
        for cls in ("a", "b"):
            d = tmp_path / "train" / cls
            d.mkdir(parents=True)
            for i in range(6):
                pixels = rng.integers(0, 256, size=(40, 48, 3), dtype=np.uint8)
                Image.fromarray(pixels).save(d / f"{i}.jpg", "JPEG")
        ds = get_dataset("imagenet", str(tmp_path), "train")
    else:
        ds = SyntheticDataset(n_samples=12, n_classes=3, image_size=8)
    rec = set_recorder(SpanRecorder(ring=64))
    loader = DataLoader(
        ds, batch_size=4, sampler=SequentialSampler(len(ds)), num_workers=2,
        drop_last=True, worker_mode=mode,
    )
    try:
        assert loader.worker_mode == mode
        batches = list(loader)
    finally:
        loader.close()
        set_recorder(None)
    assert len(batches) == 3
    spans = rec.recent()
    made = [s for s in spans if s["kind"] == "batch_assemble"]
    assert [s["n"] for s in made] == [4, 4, 4]
    waits = [s for s in spans if s["kind"] == "loader_wait"]
    assert len(waits) >= 3 and all(s["parent"] is None for s in waits)
    if mode == "process":
        # the workers decode in other processes; this process copies out
        assert {s["thread"] for s in made} == {waits[0]["thread"]}
    else:
        # a producer thread assembles ahead of the consumer
        assert {s["thread"] for s in made}.isdisjoint({waits[0]["thread"]})
