"""Datasets.

Re-provides ``dl_lib.classification.data.get_dataset`` (reference import at
train_distributed.py:26, calls at :171-181): ``get_dataset(name, root, split)``
with ``split in {"train", "val"}``, returning a map-style dataset of
``(image, label)`` samples.

Names:
  - ``imagenet``  — ImageFolder layout (``<root>/train/<wnid>/*.JPEG``,
    ``<root>/val/<wnid>/*.JPEG``), torchvision-recipe transforms
    (RandomResizedCrop(224)+flip for train, Resize(256)+CenterCrop(224) for
    val, ImageNet mean/std normalization).  The exact dl_lib transforms are
    unobservable (library not mounted); this is the standard recipe the
    reference's accuracy table assumes (SURVEY.md §7 hard part #3).
  - ``synthetic`` — deterministic random 224x224 images; the smoke-test /
    benchmarking dataset (BASELINE.json config #1 names "synthetic 224x224
    batch"), shaped like ImageNet but with zero host I/O cost.
  - ``synthetic_text`` — deterministic Markov-chain token sequences for the
    long-context LM path (beyond the reference, SURVEY.md §5.7); yields
    host-shifted ``(inputs [S], targets [S])`` pairs.
  - ``tokens`` — memory-mapped binary token file (``<root>/<split>.bin`` of
    little-endian token ids + optional ``<root>/meta.json``), cut into
    non-overlapping ``seq_len``-token windows; the real-data LM input with
    zero decode cost (np.memmap reads pages on demand).

TPU-native notes: samples are NHWC float32 (or uint8 pre-normalize), the
layout XLA:TPU convolutions want; decode/augment runs on host CPU inside the
loader's worker threads (see loader.py).
"""
from __future__ import annotations

import os
import threading
import zlib
from typing import List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "get_dataset",
    "fetch_sample",
    "fetch_sample_into",
    "fills_in_place",
    "sample_rng",
    "sample_crop_params",
    "SyntheticDataset",
    "SyntheticTextDataset",
    "TokenFileDataset",
    "ImageFolderDataset",
    "IMAGENET_MEAN",
    "IMAGENET_STD",
]

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], dtype=np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], dtype=np.float32)

_IMG_EXTS = (".jpg", ".jpeg", ".png", ".bmp", ".webp")


class SyntheticDataset:
    """Deterministic fake ImageNet: class-dependent Gaussian images.

    Each sample is reproducible from its index alone, so the dataset behaves
    identically across hosts/ranks without any shared storage — the property
    the smoke config needs (SURVEY.md §4: "synthetic dataset" integration
    target).  Images carry class-dependent signal (mean shift per class) so
    short training runs have learnable structure and loss visibly decreases.
    """

    def __init__(
        self,
        n_samples: int = 1280,
        n_classes: int = 1000,
        image_size: int = 224,
        split: str = "train",
        seed: int = 0,
    ):
        self.n_samples = int(n_samples)
        self.n_classes = int(n_classes)
        self.image_size = int(image_size)
        # different split -> disjoint sample streams; crc32 (not hash()) so
        # the salt is identical across processes/hosts regardless of
        # PYTHONHASHSEED — required for the "same dataset on every host"
        # premise of distributed sharding.
        self._salt = (zlib.crc32(split.encode()) & 0xFFFF) ^ seed

    def __len__(self) -> int:
        return self.n_samples

    def fill_sample(self, idx: int, out: np.ndarray) -> np.int64:
        """Write sample ``idx``'s pixels into ``out`` (a C-contiguous
        float32 ``[size, size, 3]`` array, e.g. a row of the loader's batch)
        and return its label: the in-place method ``fetch_sample_into``
        looks for, so a batch costs no per-sample array and no copy."""
        shape = (self.image_size, self.image_size, 3)
        if out.shape != shape:
            raise ValueError(
                f"sample {idx}: shape {shape} does not fit a row of shape {out.shape}"
            )
        rng = np.random.default_rng(self._salt * 1_000_003 + idx)
        label = idx % self.n_classes
        rng.standard_normal(out=out, dtype=np.float32)
        # class-dependent mean shift: learnable but not trivially separable
        out += 0.1 * ((label % 16) - 8) / 8.0
        return np.int64(label)

    def __getitem__(self, idx: int) -> Tuple[np.ndarray, np.int64]:
        img = np.empty((self.image_size, self.image_size, 3), np.float32)
        return img, self.fill_sample(idx, img)


class SyntheticTextDataset:
    """Deterministic fake corpus: per-index Markov-chain token sequences.

    Sequences follow a fixed random bigram transition table (seeded per
    split), so next-token structure is learnable and short LM runs show a
    decreasing loss — the text analog of :class:`SyntheticDataset`'s
    class-dependent mean shift.  Each sample is reproducible from its index
    alone (same property the distributed sharding premise needs).

    Yields ``(inputs [seq_len], targets [seq_len])`` int32 pairs — targets
    are the next tokens, shifted on the host because the shift crosses
    sequence-shard boundaries (engine/sp_steps.py batch-layout contract).
    """

    def __init__(
        self,
        n_samples: int = 1024,
        vocab_size: int = 512,
        seq_len: int = 128,
        split: str = "train",
        seed: int = 0,
    ):
        self.n_samples = int(n_samples)
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self._salt = (zlib.crc32(split.encode()) & 0xFFFF) ^ seed
        # one shared transition table per split: row t -> 8 likely successors
        table_rng = np.random.default_rng(self._salt)
        self._successors = table_rng.integers(
            0, self.vocab_size, (self.vocab_size, 8), dtype=np.int32
        )
        # nested-python-list view of the table, built lazily on first use:
        # python-int indexing is much faster than per-element numpy scalar
        # indexing for the inherently sequential chain walk, but the list
        # blow-up must not be paid by shape probes or pickled into process
        # workers (it rebuilds per process on demand)
        self._succ_rows = None

    def __len__(self) -> int:
        return self.n_samples

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_succ_rows"] = None  # rebuilt lazily in the worker
        return state

    def __getitem__(self, idx: int) -> Tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng(self._salt * 1_000_003 + idx)
        # 90% of steps follow the bigram table (learnable), 10% jump randomly
        cur = int(rng.integers(0, self.vocab_size))
        choices = rng.integers(0, 8, self.seq_len).tolist()
        jumps = (rng.random(self.seq_len) < 0.1).tolist()
        randoms = rng.integers(0, self.vocab_size, self.seq_len).tolist()
        if self._succ_rows is None:
            self._succ_rows = self._successors.tolist()
        succ = self._succ_rows
        out = [cur]
        for t in range(self.seq_len):
            cur = randoms[t] if jumps[t] else succ[cur][choices[t]]
            out.append(cur)
        toks = np.asarray(out, dtype=np.int32)
        return toks[:-1], toks[1:]


class TokenFileDataset:
    """``<root>/<split>.bin`` of little-endian token ids, windowed.

    The LM analog of the ImageFolder path: a flat binary corpus (the format
    nanoGPT-style preprocessors emit) memory-mapped and cut into
    non-overlapping ``seq_len + 1``-token windows; window ``i`` yields
    host-shifted ``(inputs, targets)``.  Optional ``<root>/meta.json`` keys:
    ``dtype`` (default ``uint16``) and ``vocab_size`` (validated against the
    config's ``n_classes`` by the caller if present).
    """

    def __init__(self, root: str, split: str, seq_len: int = 128):
        import json

        self.root = os.path.expanduser(root)
        self.seq_len = int(seq_len)
        path = os.path.join(self.root, f"{split}.bin")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"token file not found: {path}")
        dtype = "uint16"
        meta_path = os.path.join(self.root, "meta.json")
        self.vocab_size: Optional[int] = None
        if os.path.isfile(meta_path):
            with open(meta_path) as fp:
                meta = json.load(fp)
            dtype = meta.get("dtype", dtype)
            self.vocab_size = meta.get("vocab_size")
        self._tokens = np.memmap(path, dtype=np.dtype(dtype), mode="r")
        self.n_windows = (len(self._tokens) - 1) // self.seq_len
        if self.n_windows <= 0:
            raise ValueError(
                f"{path}: {len(self._tokens)} tokens < one {self.seq_len + 1}-token window"
            )

    def __len__(self) -> int:
        return self.n_windows

    def __getitem__(self, idx: int) -> Tuple[np.ndarray, np.ndarray]:
        start = int(idx) * self.seq_len
        window = np.asarray(
            self._tokens[start : start + self.seq_len + 1], dtype=np.int32
        )
        return window[:-1], window[1:]


def sample_rng(seed: int, epoch: int, idx: int) -> np.random.Generator:
    """Per-sample augmentation RNG: ``default_rng([seed, epoch, idx])``.

    numpy's ``SeedSequence`` mixes the triple, so every (seed, epoch, sample)
    gets an independent, *reproducible* stream — augmentation no longer
    depends on thread/process scheduling or on a shared global RNG, and
    different samples get different crop/flip draws even though every host
    seeds identically (reference train_distributed.py:141-142).
    """
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(epoch), int(idx)])


def fetch_sample(dataset, idx: int, seed: int, epoch: int):
    """Fetch ``dataset[idx]`` with an explicit per-sample augmentation RNG.

    Datasets exposing ``get_sample(idx, rng)`` (stochastic augmentation) get
    the deterministic per-sample stream; plain ``__getitem__`` datasets
    (index-seeded, e.g. :class:`SyntheticDataset`) are called directly.
    """
    get = getattr(dataset, "get_sample", None)
    if get is not None:
        return get(idx, sample_rng(seed, epoch, idx))
    return dataset[int(idx)]


def fills_in_place(dataset) -> bool:
    """Whether ``fetch_sample_into`` has the dataset write its own rows."""
    return hasattr(dataset, "fill_sample")


def fetch_sample_into(dataset, idx: int, seed: int, epoch: int, out: np.ndarray):
    """Write sample ``idx`` into ``out`` (a row of a batch) and return its
    second half (label or target array).

    A dataset with ``fill_sample(idx, out)`` generates straight into the row;
    any other is fetched exactly as :func:`fetch_sample` does and copied in.
    Either way the work runs in the calling thread, so a pool of callers
    spreads the copy too.  A sample that is not of the row's shape and dtype
    raises ``ValueError``: numpy would broadcast or cast it in silence.
    """
    if fills_in_place(dataset):
        return dataset.fill_sample(int(idx), out)
    img, label = fetch_sample(dataset, idx, seed, epoch)
    if img.shape != out.shape or img.dtype != out.dtype:
        raise ValueError(
            f"sample {idx}: {img.dtype}{list(img.shape)} does not match the "
            f"batch's rows, {out.dtype}{list(out.shape)} (probed from the first sample)"
        )
    out[...] = img
    return label


def sample_crop_params(
    w: int,
    h: int,
    rng: Optional[np.random.Generator],
    train: bool,
    scale=(0.08, 1.0),
    ratio=(3 / 4, 4 / 3),
    resize_to: int = 256,
    size: int = 224,
) -> Tuple[float, float, float, float, bool]:
    """Sample the source crop box ``(x, y, cw, ch)`` + horizontal-flip flag.

    Train: torchvision ``RandomResizedCrop`` semantics — 10 attempts at an
    area/aspect-jittered box, center-crop fallback — plus a p=0.5 flip.
    Val (``train=False``): the deterministic Resize(``resize_to``) +
    CenterCrop(``size``) pipeline expressed as one equivalent source box
    (``size/scale`` pixels centered after shorter-side scaling), so both the
    PIL path and the native decode kernel resample the original image exactly
    once.  Separating parameter *sampling* (host RNG, here) from pixel work
    (PIL or the native C++ kernel) keeps augmentation bit-reproducible no
    matter which backend executes the pixels.
    """
    if train:
        assert rng is not None, "train crop sampling requires an RNG"
        area = w * h
        log_ratio = (np.log(ratio[0]), np.log(ratio[1]))
        for _ in range(10):
            target_area = area * rng.uniform(*scale)
            aspect = np.exp(rng.uniform(*log_ratio))
            cw = int(round(np.sqrt(target_area * aspect)))
            ch = int(round(np.sqrt(target_area / aspect)))
            if 0 < cw <= w and 0 < ch <= h:
                x = int(rng.integers(0, w - cw + 1))
                y = int(rng.integers(0, h - ch + 1))
                return float(x), float(y), float(cw), float(ch), bool(rng.random() < 0.5)
        # fallback: central crop at clamped aspect (torchvision semantics)
        in_ratio = w / h
        if in_ratio < ratio[0]:
            cw, ch = w, int(round(w / ratio[0]))
        elif in_ratio > ratio[1]:
            cw, ch = int(round(h * ratio[1])), h
        else:
            cw, ch = w, h
        x, y = (w - cw) // 2, (h - ch) // 2
        return float(x), float(y), float(cw), float(ch), bool(rng.random() < 0.5)
    # val: shorter side -> resize_to, center size x size
    s = resize_to / min(w, h)
    cw = size / s
    ch = size / s
    x = (w - cw) / 2
    y = (h - ch) / 2
    return x, y, cw, ch, False


class ImageFolderDataset:
    """``<root>/<split>/<class_dir>/<image>`` layout, torchvision semantics.

    Class indices are assigned by sorted class-dir name (torchvision
    ``ImageFolder`` parity — required for val accuracy comparability).
    Crop/flip parameters are sampled on the host (``sample_crop_params``);
    pixel work (decode, crop, resize, flip) runs in PIL here, or — the hot
    path — in the native C++ batch kernel (``native.decode_jpeg_batch``),
    which the loader uses for whole batches when every sample is a JPEG.
    """

    def __init__(self, root: str, split: str, image_size: int = 224, train_transform: Optional[bool] = None):
        self.root = os.path.expanduser(root)
        self.split = split
        self.image_size = image_size
        self.train = train_transform if train_transform is not None else (split == "train")
        split_dir = os.path.join(self.root, split)
        if not os.path.isdir(split_dir):
            raise FileNotFoundError(f"dataset split dir not found: {split_dir}")
        classes = sorted(
            d for d in os.listdir(split_dir) if os.path.isdir(os.path.join(split_dir, d))
        )
        if not classes:
            raise FileNotFoundError(f"no class directories under {split_dir}")
        self.class_to_idx = {c: i for i, c in enumerate(classes)}
        self.samples: List[Tuple[str, int]] = []
        for c in classes:
            cdir = os.path.join(split_dir, c)
            for fname in sorted(os.listdir(cdir)):
                if fname.lower().endswith(_IMG_EXTS):
                    self.samples.append((os.path.join(cdir, fname), self.class_to_idx[c]))
        # dims memo allocated lazily on the first image_dims call (w==0
        # sentinel = unseen); a dict of tuples would cost ~200MB of Python
        # objects at ImageNet's 1.28M samples vs ~10MB for the array, and
        # instances whose pixels flow through the pure-PIL path never pay it.
        # The lock guards only the allocation: two threads hitting the
        # first-use check together could each assign a fresh array, losing
        # the other's dims writes (and the reader's view of them)
        self._dims_cache: Optional[np.ndarray] = None
        self._dims_lock = threading.Lock()
        # corrupt-sample quarantine: paths already logged (log once per
        # path; the counter still bumps per occurrence)
        self._corrupt_logged: set = set()  # guarded by: self._corrupt_lock
        self._corrupt_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.samples)

    def __getstate__(self):
        # locks don't pickle; workers start with an empty memo anyway
        state = self.__dict__.copy()
        state["_dims_lock"] = None
        state["_dims_cache"] = None
        state["_corrupt_lock"] = None
        state["_corrupt_logged"] = set()
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._dims_lock = threading.Lock()
        self._corrupt_lock = threading.Lock()

    # Per-channel normalization applied at batch-assembly time by the
    # loader's fused native kernel (see data/loader.py + native/).
    norm_mean = IMAGENET_MEAN
    norm_std = IMAGENET_STD

    def image_dims(self, idx: int) -> Tuple[int, int]:
        """(width, height) from the image header only — no pixel decode
        (PIL ``open`` is lazy).  Memoized: the header open costs ~44us and
        sits on the SERIAL path of the native batch pipeline (crop-box
        sampling happens in Python before the parallel C++ decode), so
        caching it cuts the Amdahl serial fraction of multi-core hosts
        roughly in half from the second visit on (PERF.md round 4).

        The speedup assumes crop-box sampling stays on a long-lived
        main-process serial path (data/loader.py's native backend): forked
        DataLoader workers each hold their own copy-on-write cache and
        repopulate independently, and concurrent writers race benignly
        (both write the same dims) — but only once a single array exists,
        hence the locked allocation."""
        if self._dims_cache is None:
            with self._dims_lock:
                if self._dims_cache is None:
                    self._dims_cache = np.zeros(
                        (len(self.samples), 2), np.int32
                    )
        w, h = self._dims_cache[idx]
        if w:
            return int(w), int(h)
        from PIL import Image

        try:
            with Image.open(self.samples[idx][0]) as im:
                dims = im.size
        except (OSError, ValueError, SyntaxError):
            # unreadable header: dummy dims keep the batch's serial
            # crop-sampling pass alive — the decode stage then fails this
            # row too and _quarantine feeds zeros for it
            dims = (self.image_size, self.image_size)
        self._dims_cache[idx] = dims
        return dims

    def crop_task(self, idx: int, rng: Optional[np.random.Generator]):
        """(path, label, crop box+flip) for the native batch decode path."""
        path, label = self.samples[idx]
        w, h = self.image_dims(idx)
        params = sample_crop_params(w, h, rng, self.train, size=self.image_size)
        return path, label, params

    def _pil_pixels(self, im, params) -> np.ndarray:
        """Crop/resize/flip an open PIL image with already-sampled params."""
        from PIL import Image

        x, y, cw, ch, flip = params
        im = im.convert("RGB")
        im = im.resize(
            (self.image_size, self.image_size),
            Image.BILINEAR,
            box=(x, y, x + cw, y + ch),
        )
        if flip:
            im = im.transpose(Image.FLIP_LEFT_RIGHT)
        # uint8 here; the /255-mean/std normalization is fused into the
        # native batch-assembly pass (one pass, no per-image temporaries)
        return np.asarray(im, dtype=np.uint8)

    def _quarantine(self, idx: int, exc: Exception) -> np.ndarray:
        """A sample whose image fails to decode is quarantined — zero
        pixels under its true label — instead of raising out of the loader
        backend: a raise in a pool worker kills the worker and burns a
        respawn from the fault-tolerance budget on a PERMANENT input
        problem no respawn can fix.  Every occurrence bumps the
        ``data_corrupt_samples`` counter; the path is logged once."""
        import logging

        from ..telemetry.registry import get_registry

        get_registry().counter("data_corrupt_samples").inc()
        path = self.samples[idx][0]
        with self._corrupt_lock:
            first = path not in self._corrupt_logged
            self._corrupt_logged.add(path)
        if first:
            logging.getLogger(__name__).warning(
                "quarantined corrupt sample %s (%s: %s) — feeding zero "
                "pixels with its label; fix or remove the file",
                path, type(exc).__name__, exc,
            )
        return np.zeros((self.image_size, self.image_size, 3), np.uint8)

    def decode_with_params(self, idx: int, params) -> np.ndarray:
        """PIL pixel path for an already-sampled crop box + flip flag.

        Used directly by the loader when the native kernel reports a row it
        cannot decode (non-JPEG, CMYK) — the *same* params the native path
        would have used, so fallback rows stay bit-reproducible.  A row
        that PIL cannot decode either (truncated/corrupt file) is
        quarantined, not raised.
        """
        from PIL import Image

        try:
            with Image.open(self.samples[idx][0]) as im:
                return self._pil_pixels(im, params)
        except (OSError, ValueError, SyntaxError) as e:
            return self._quarantine(idx, e)

    def get_sample(self, idx: int, rng: Optional[np.random.Generator]) -> Tuple[np.ndarray, np.int64]:
        """PIL reference path: one open — header dims, param sampling, then
        decode + one-shot box resize (+flip).  Corrupt images quarantine
        (zeros + true label) instead of raising — see :meth:`_quarantine`."""
        from PIL import Image

        path, label = self.samples[idx]
        try:
            with Image.open(path) as im:
                w, h = im.size
                params = sample_crop_params(w, h, rng, self.train, size=self.image_size)
                return self._pil_pixels(im, params), np.int64(label)
        except (OSError, ValueError, SyntaxError) as e:
            return self._quarantine(idx, e), np.int64(label)

    def __getitem__(self, idx: int) -> Tuple[np.ndarray, np.int64]:
        # Index-seeded fallback (epoch-0 stream); loaders use fetch_sample /
        # crop_task with the (seed, epoch, idx) stream instead.
        return self.get_sample(idx, sample_rng(0, 0, idx))


def get_dataset(
    name: str,
    root: str,
    split: str,
    n_classes: Optional[int] = None,
    image_size: int = 224,
    n_samples: Optional[int] = None,
    seq_len: Optional[int] = None,
):
    """Dataset factory (reference: train_distributed.py:171-181).

    ``n_classes`` / ``image_size`` / ``n_samples`` / ``seq_len`` parameterize
    the synthetic + token datasets (the engine forwards the optional
    ``dataset.image_size`` / ``dataset.n_samples`` / ``dataset.seq_len``
    config keys — additive, unknown to the reference schema).  For LM
    datasets ``n_classes`` is the vocabulary size.
    """
    name = name.lower()
    if name in ("synthetic", "fake", "fake_imagenet"):
        n = n_samples if n_samples else (12_800 if split == "train" else 1_280)
        return SyntheticDataset(
            n_samples=n,
            n_classes=n_classes or 1000,
            image_size=image_size,
            split=split,
        )
    if name == "imagenet":
        return ImageFolderDataset(root, split, image_size=image_size)
    if name in ("synthetic_text", "fake_text"):
        n = n_samples if n_samples else (4_096 if split == "train" else 512)
        return SyntheticTextDataset(
            n_samples=n,
            vocab_size=n_classes or 512,
            seq_len=seq_len or 128,
            split=split,
        )
    if name in ("tokens", "tokenbin"):
        ds = TokenFileDataset(root, split, seq_len=seq_len or 128)
        if ds.vocab_size is not None and n_classes and ds.vocab_size > n_classes:
            raise ValueError(
                f"{root}/meta.json vocab_size {ds.vocab_size} exceeds "
                f"dataset.n_classes {n_classes}"
            )
        return ds
    raise KeyError(
        f"unknown dataset '{name}' (have: imagenet, synthetic, synthetic_text, tokens)"
    )
