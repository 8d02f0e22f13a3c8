"""Host-side batched loader: native batch decode, thread, or process workers.

The TPU-native replacement for ``torch.utils.data.DataLoader`` with worker
processes and pinned memory (reference: train_distributed.py:227-241,
SURVEY.md §2.3).  JAX keeps one controller process per host, so the loader
offers three assembly backends, selected by ``worker_mode``:

  - ``"native"`` (auto-picked for JPEG folder datasets): crop/flip params are
    sampled per-sample on the host (counter-based RNG streams — reproducible
    regardless of scheduling), then ONE call into the native C++ kernel
    (native/decode.cpp) decodes, crops, antialias-resizes, flips and
    normalizes the whole batch on an internal thread pool with the GIL
    released — the torch-worker-pool capability without processes.
  - ``"process"``: N spawned worker processes assemble batches into a
    shared-memory slot ring (worker_pool.py) — the generic GIL-free path for
    pure-Python datasets.
  - ``"thread"``: in-process thread pool; right for datasets whose
    ``__getitem__`` releases the GIL (numpy-heavy synthetic data) and for
    tiny smoke runs.  The batch's array is allocated first and every worker
    writes its sample into its own row of it (``fetch_sample_into``): a
    dataset with ``fill_sample(idx, out)`` generates straight into the row,
    any other sample is fetched as before and copied in by the worker that
    fetched it, so no single thread copies a whole batch.  Each batch gets a
    fresh array, first touched (page-faulted) by the workers; it is never
    recycled, because the runtime's re-layout thread still reads a batch
    after the engine's put returns and ``device_prefetch`` keeps two in
    flight.  ``num_workers: 0`` fills the same rows in the producer thread.

Every backend prefetches assembled batches through a bounded queue so host
work overlaps device compute — the role pinned memory + ``non_blocking`` H2D
copies play in the reference (:272-273); device placement happens in the
engine (``jax.device_put`` with the batch sharding), double-buffered by
``data.prefetch.device_prefetch``.

Batch-shape policy (XLA static shapes — SURVEY.md §7 design stance):
  - ``drop_last=True`` (train): only full batches are yielded; with the
    sampler's ``drop_last`` this mirrors the reference's equal-per-rank
    training stream, minus at most one partial batch per epoch that torch
    would have yielded (deviation documented; it avoids one extra XLA
    compilation and a ragged global batch across hosts).
  - ``drop_last=False`` (val): the final partial batch is padded by wrapping
    to a full batch, and every rank yields the same batch count — the same
    "tail may double-count" semantics the reference's val path already has
    via DistributedSampler padding (train_distributed.py:219-222).
"""
from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Tuple

import numpy as np

from ..telemetry.spans import span
from .datasets import fetch_sample, fetch_sample_into, fills_in_place, sample_rng
from .sampler import DistributedShardSampler

__all__ = ["DataLoader"]

_MODES = ("auto", "native", "thread", "process")


class DataLoader:
    def __init__(
        self,
        dataset,
        batch_size: int,
        sampler: DistributedShardSampler,
        num_workers: int = 0,
        drop_last: bool = False,
        prefetch_batches: int = 2,
        worker_mode: str = "auto",
        dct_denom: int = 1,
        output_dtype: str = "float32",
    ):
        """``output_dtype``: ``"float32"`` (default) yields host-normalized
        batches — reference parity, the normalization runs on the host;
        ``"uint8"`` yields raw uint8 pixels so the ``(x/255 - mean)/std``
        affine runs on the accelerator instead (``engine.steps`` input_norm)
        and host->device transfer shrinks 4x."""
        if worker_mode not in _MODES:
            raise ValueError(f"worker_mode must be one of {_MODES}, got {worker_mode!r}")
        if output_dtype not in ("float32", "uint8"):
            raise ValueError(
                f"output_dtype must be 'float32' or 'uint8', got {output_dtype!r}"
            )
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.sampler = sampler
        self.num_workers = int(num_workers)
        self.drop_last = bool(drop_last)
        self.prefetch_batches = max(1, int(prefetch_batches))
        self.dct_denom = int(dct_denom)
        self.output_dtype = output_dtype
        self.seed = int(getattr(sampler, "seed", 0))
        self._pool = None  # lazily-created ProcessLoaderPool
        self._spec = None  # lazily-probed sample shapes (_sample_spec)
        self.worker_mode = self._resolve_mode(worker_mode)
        if output_dtype == "uint8" and getattr(dataset, "norm_mean", None) is None:
            raise ValueError(
                "output_dtype='uint8' requires a dataset with uint8 samples "
                "and norm_mean/norm_std (device-side normalization constants)"
            )

    def _resolve_mode(self, mode: str) -> str:
        if mode != "auto":
            return mode
        if hasattr(self.dataset, "crop_task"):
            from ..native import native_available

            if native_available():
                return "native"
        return "thread"

    def set_epoch(self, epoch: int) -> None:
        self.sampler.set_epoch(epoch)

    def skip_next(self, n_batches: int) -> None:
        """Skip the first ``n_batches`` of the NEXT iteration only — an
        index-level fast-forward (no decode cost) used by checkpoint resume
        to re-align the data stream with the restored iteration counter.

        Negative ``n_batches`` raises immediately (a corrupted resume
        offset must fail at the call site, not as a silent negative-slice
        far from the cause).  ``n_batches`` past the end of the epoch is
        CLAMPED: the next iteration yields zero batches (that epoch is
        fully consumed) and the epoch loop moves on — the resume semantics
        when the saved position was exactly an epoch boundary.
        """
        n = int(n_batches)
        if n < 0:
            raise ValueError(f"skip_next: n_batches must be >= 0, got {n}")
        self._skip_next = n

    def close(self) -> None:
        """Shut down persistent worker processes (no-op for other modes)."""
        if self._pool is not None:
            self._pool.close()
            self._pool = None

    def _batch_indices(self) -> list:
        idx = self.sampler.local_indices()
        n = len(idx)
        batches = []
        for start in range(0, n, self.batch_size):
            chunk = idx[start : start + self.batch_size]
            if len(chunk) < self.batch_size:
                if self.drop_last:
                    break
                # wrap-pad the tail, tiling if the shard is smaller than a batch
                chunk = np.resize(np.concatenate([chunk, idx]), self.batch_size)
            batches.append(chunk)
        return batches

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    # ----------------------------------------------------- batch assembly
    def _normalize_u8(self, imgs: np.ndarray) -> np.ndarray:
        """Fused uint8 -> normalized float32 (native kernel, numpy fallback)."""
        from ..native import normalize_batch

        mean = getattr(self.dataset, "norm_mean", None)
        std = getattr(self.dataset, "norm_std", None)
        if mean is not None and std is not None:
            return normalize_batch(imgs, mean, std)
        return imgs.astype(np.float32) / 255.0

    def _sample_spec(self, idx: int, epoch: int):
        """(image shape, image dtype, shape of the second half), probed once
        per loader from one sample: what a batch's arrays are made from."""
        if self._spec is None:
            img, label = fetch_sample(self.dataset, int(idx), self.seed, epoch)
            self._spec = (img.shape, img.dtype, np.shape(label))
        return self._spec

    def _assemble(
        self, indices: np.ndarray, epoch: int, pool: Optional[ThreadPoolExecutor]
    ):
        """Thread/sync path: a fresh batch array, each row written in place.

        Row ``j`` is one task, ``fetch_sample_into(dataset, indices[j], ...,
        imgs[j])``, mapped over ``pool`` (or looped when there is none): the
        dataset's own ``fill_sample`` where it has one, else the usual fetch
        and a copy into the row by the thread that fetched.  The second half
        (a label, or the ``tokens`` dataset's target array) lands in row ``j``
        of an int64 array.  uint8 rows are normalized afterwards in one
        batched call.  A sample that is not of the probed shape raises
        ``ValueError`` from the iterator.  The arrays are new every batch:
        a yielded batch is still read by the runtime after the put, so a
        recycled buffer would be a data race.
        """
        n = len(indices)
        img_shape, img_dtype, label_shape = self._sample_spec(indices[0], epoch)
        imgs = np.empty((n, *img_shape), img_dtype)
        labels = np.empty((n, *label_shape), np.int64)

        def fill_row(j: int) -> None:
            idx = int(indices[j])
            label = fetch_sample_into(self.dataset, idx, self.seed, epoch, imgs[j])
            if np.shape(label) != label_shape:
                raise ValueError(
                    f"sample {idx}: second half of shape {list(np.shape(label))} "
                    f"does not match the batch's, {list(label_shape)}"
                )
            labels[j] = label

        if pool is not None:
            for _ in pool.map(fill_row, range(n)):  # reads every task's error
                pass
        else:
            for j in range(n):
                fill_row(j)
        if imgs.dtype == np.uint8 and self.output_dtype == "float32":
            imgs = self._normalize_u8(imgs)
        return imgs, labels

    def _assemble_native(self, indices: np.ndarray, epoch: int):
        """Native path: sample params on host, decode the batch in C++."""
        from ..native import decode_jpeg_batch

        ds = self.dataset
        tasks = [
            ds.crop_task(int(i), sample_rng(self.seed, epoch, int(i)))
            for i in indices
        ]
        paths = [t[0] for t in tasks]
        labels = np.asarray([t[1] for t in tasks], dtype=np.int64)
        boxes = np.asarray([t[2][:4] for t in tasks], dtype=np.float64)
        flips = np.asarray([t[2][4] for t in tasks], dtype=np.uint8)
        raw_u8 = self.output_dtype == "uint8"
        out, status = decode_jpeg_batch(
            paths,
            boxes,
            flips,
            ds.image_size,
            None if raw_u8 else ds.norm_mean,
            None if raw_u8 else ds.norm_std,
            dct_denom=self.dct_denom,
            n_threads=self.num_workers if self.num_workers > 0 else 1,
        )
        if status.any():
            # rows libjpeg can't handle (PNG, CMYK, corrupt) -> PIL, with the
            # SAME already-sampled params, so bytes don't depend on the path
            from ..native import normalize_batch

            for r in np.nonzero(status)[0]:
                arr = ds.decode_with_params(int(indices[r]), tasks[r][2])
                if raw_u8:
                    out[r] = arr
                else:
                    out[r] = normalize_batch(arr[None], ds.norm_mean, ds.norm_std)[0]
        return out, labels

    # ------------------------------------------------------------ iteration
    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        batches = self._batch_indices()
        skip = getattr(self, "_skip_next", 0)
        if skip:
            # clamped: skip >= len(batches) consumes the whole epoch
            batches = batches[min(skip, len(batches)):]
            self._skip_next = 0
        if not batches:
            return iter(())
        epoch = int(getattr(self.sampler, "epoch", 0))
        if self.worker_mode == "process":
            return self._iter_process(batches, epoch)
        return self._iter_queued(batches, epoch)

    def _iter_process(self, batches, epoch: int):
        if self._pool is None:
            from .worker_pool import ProcessLoaderPool

            sample_shape, sample_dtype, _ = self._sample_spec(batches[0][0], epoch)
            self._pool = ProcessLoaderPool(
                self.dataset,
                batch_size=self.batch_size,
                sample_shape=sample_shape,
                sample_dtype=sample_dtype,
                num_workers=max(1, self.num_workers),
                seed=self.seed,
            )

        def postprocess(slot_view: np.ndarray, label_view: np.ndarray):
            # the workers' own decode runs in other processes; what this
            # process pays per batch is this copy out of the slot
            with span("batch_assemble", n=len(label_view)):
                if slot_view.dtype == np.uint8 and self.output_dtype == "float32":
                    imgs = self._normalize_u8(slot_view)  # writes a fresh array
                else:
                    imgs = np.array(slot_view)  # copy out: slot is recycled next
                return imgs, np.array(label_view)

        return self._pool.run_epoch(batches, epoch, postprocess)

    def _iter_queued(self, batches, epoch: int):
        """Producer thread assembling batches ahead through a bounded queue."""
        use_threads = self.worker_mode == "thread" and self.num_workers > 0
        pool = ThreadPoolExecutor(self.num_workers) if use_threads else None
        out_q: queue.Queue = queue.Queue(maxsize=self.prefetch_batches)
        stop = threading.Event()

        own_rows = fills_in_place(self.dataset)

        def assemble(b):
            if self.worker_mode == "native":
                with span("batch_assemble", n=len(b)):
                    return self._assemble_native(b, epoch)
            # in_place: rows the dataset wrote itself (the rest were copied
            # in by the thread that fetched them)
            with span("batch_assemble", n=len(b), in_place=len(b) if own_rows else 0):
                return self._assemble(b, epoch, pool)

        def producer():
            try:
                for b in batches:
                    if stop.is_set():
                        return
                    out_q.put(assemble(b))
                out_q.put(None)
            except BaseException as e:  # surface worker errors to the consumer
                out_q.put(e)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                with span("loader_wait"):
                    item = out_q.get()
                if item is None:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            # drain so the producer can exit
            while t.is_alive():
                try:
                    out_q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=1.0)
            if pool is not None:
                pool.shutdown(wait=False, cancel_futures=True)
