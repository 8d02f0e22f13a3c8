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
from pytorch_distributed_training_tpu.data.datasets import (
    fetch_sample,
    fetch_sample_into,
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


# ------------------------------------------------- rows written in place
def _small_dataset(name, root):
    """One small dataset of each kind ``_assemble`` serves: float32 images
    the dataset writes itself, array-valued second halves, PIL uint8 images."""
    if name == "synthetic":
        return SyntheticDataset(n_samples=22, n_classes=5, image_size=8)
    if name == "synthetic_text":
        return get_dataset("synthetic_text", "/none", "train", n_classes=64,
                           n_samples=22, seq_len=16)
    if name == "tokens":
        rng = np.random.default_rng(5)
        rng.integers(0, 500, size=22 * 16 + 1).astype(np.uint16).tofile(
            root / "train.bin")
        return get_dataset("tokens", str(root), "train", seq_len=16)
    from PIL import Image

    rng = np.random.default_rng(0)
    for cls in ("a", "b"):
        d = root / "train" / cls
        d.mkdir(parents=True)
        for i in range(11):
            pixels = rng.integers(0, 256, size=(20, 24, 3), dtype=np.uint8)
            Image.fromarray(pixels).save(d / f"{i}.png")
    return get_dataset("imagenet", str(root), "train", image_size=16)


@pytest.mark.parametrize("num_workers", [0, 4])
@pytest.mark.parametrize(
    "name,output_dtype",
    [("synthetic", "float32"), ("synthetic_text", "float32"),
     ("tokens", "float32"), ("imagefolder", "float32"),
     ("imagefolder", "uint8")],
)
def test_loader_batches_equal_the_plain_stack(name, output_dtype, num_workers, tmp_path):
    """The thread and synchronous loaders' batches are, bit for bit, what
    stacking ``fetch_sample`` of the same indices gives (the assembly the
    loader had before its workers wrote rows in place, kept here as the
    plain reference), tail wrap included."""
    from pytorch_distributed_training_tpu.native import normalize_batch

    ds = _small_dataset(name, tmp_path)
    loader = DataLoader(
        ds, batch_size=8, sampler=RandomSampler(len(ds), seed=3),
        num_workers=num_workers, drop_last=False, worker_mode="thread",
        output_dtype=output_dtype,
    )
    loader.set_epoch(1)
    indices = loader._batch_indices()
    assert len(indices) == 3 and set(indices[-1][6:]) <= set(indices[0])  # wraps
    batches = list(loader)
    assert len(batches) == 3
    for idx, (imgs, labels) in zip(indices, batches):
        samples = [fetch_sample(ds, int(i), loader.seed, 1) for i in idx]
        want = np.stack([s[0] for s in samples])
        if want.dtype == np.uint8 and output_dtype == "float32":
            want = normalize_batch(want, ds.norm_mean, ds.norm_std)
        want_labels = np.asarray([s[1] for s in samples], dtype=np.int64)
        assert imgs.dtype == want.dtype and labels.dtype == np.int64
        assert imgs.flags["C_CONTIGUOUS"]
        np.testing.assert_array_equal(imgs, want)
        np.testing.assert_array_equal(labels, want_labels)


@pytest.mark.parametrize("split", ["train", "val"])
@pytest.mark.parametrize("idx", [0, 7, 19])
def test_synthetic_fill_sample_equals_getitem(split, idx):
    """``fill_sample`` into a batch row gives the pixels ``__getitem__``
    gives, and both give what the generator draws when it allocates."""
    ds = SyntheticDataset(n_samples=20, n_classes=6, image_size=8, split=split)
    batch = np.full((3, 8, 8, 3), np.nan, np.float32)
    label = ds.fill_sample(idx, batch[1])
    img, want_label = ds[idx]
    np.testing.assert_array_equal(batch[1], img)
    assert label == want_label == idx % 6 and isinstance(label, np.int64)
    assert np.isnan(batch[0]).all() and np.isnan(batch[2]).all()
    rng = np.random.default_rng(ds._salt * 1_000_003 + idx)
    drawn = rng.standard_normal((8, 8, 3), dtype=np.float32)
    drawn += 0.1 * (((idx % 6) % 16) - 8) / 8.0
    np.testing.assert_array_equal(img, drawn)
    with pytest.raises(ValueError, match=f"sample {idx}"):
        ds.fill_sample(idx, np.empty((8, 8), np.float32))


class _OneOddSample:
    """Index-seeded dataset whose sample 5 differs in one half."""

    def __init__(self, odd_img, odd_label):
        self.odd = (odd_img, odd_label)

    def __len__(self):
        return 8

    def __getitem__(self, idx):
        if idx == 5:
            return self.odd
        return np.full((4, 4, 3), idx, np.float32), np.int64(idx)


@pytest.mark.parametrize("num_workers", [0, 4])
@pytest.mark.parametrize(
    "odd",
    [(np.zeros((4, 1, 3), np.float32), np.int64(5)),  # would broadcast
     (np.zeros((2, 4, 3), np.float32), np.int64(5)),
     (np.zeros((4, 4, 3), np.float64), np.int64(5)),  # would be cast
     (np.zeros((4, 4, 3), np.float32), np.zeros(2, np.int64))],
    ids=["broadcastable", "shape", "dtype", "second-half"],
)
def test_loader_raises_on_a_sample_unlike_the_probe(odd, num_workers):
    """One sample that is not of the probed shape and dtype raises
    ``ValueError`` naming its index out of the iterator, where ``np.stack``
    raised; nothing is broadcast or cast into the row."""
    ds = _OneOddSample(*odd)
    loader = DataLoader(ds, batch_size=4, sampler=SequentialSampler(len(ds)),
                        num_workers=num_workers, worker_mode="thread")
    it = iter(loader)
    imgs, labels = next(it)
    np.testing.assert_array_equal(labels, [0, 1, 2, 3])
    with pytest.raises(ValueError, match="sample 5"):
        next(it)


def test_fetch_sample_into_copies_where_the_dataset_has_no_fill_sample(tmp_path):
    ds = _small_dataset("tokens", tmp_path)
    row = np.zeros((2, 16), np.int32)
    targets = fetch_sample_into(ds, 3, seed=0, epoch=0, out=row[1])
    want, want_targets = ds[3]
    np.testing.assert_array_equal(row[1], want)
    np.testing.assert_array_equal(targets, want_targets)
    assert not row[0].any()


# ------------------------------------------------------------------- spans
@pytest.mark.parametrize("mode", ["thread", "thread-copied", "native", "process"])
def test_loader_emits_one_batch_assemble_a_batch(mode, tmp_path):
    """Every assembly backend brackets each batch in one ``batch_assemble``
    span carrying its sample count, and the consumer's wait for a batch in
    ``loader_wait`` (telemetry/spans.py; the benchmark's input-pipeline
    metrics read both).  In thread mode the span also says how many rows the
    dataset wrote itself (``in_place``): all of them for ``synthetic``, none
    for a dataset whose samples the workers copy in."""
    in_place = {"thread": 4, "thread-copied": 0}.get(mode)
    copied = mode == "thread-copied"
    if copied:
        mode = "thread"
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
    elif copied:
        ds = get_dataset("synthetic_text", "/none", "train", n_classes=32,
                         n_samples=12, seq_len=8)
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
    assert [s.get("in_place") for s in made] == [in_place] * 3
    waits = [s for s in spans if s["kind"] == "loader_wait"]
    assert len(waits) >= 3 and all(s["parent"] is None for s in waits)
    if mode == "process":
        # the workers decode in other processes; this process copies out
        assert {s["thread"] for s in made} == {waits[0]["thread"]}
    else:
        # a producer thread assembles ahead of the consumer
        assert {s["thread"] for s in made}.isdisjoint({waits[0]["thread"]})
