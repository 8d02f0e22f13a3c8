"""Process-based decode workers with shared-memory batch handoff.

The reference scales host-side decode with DataLoader worker *processes* +
pinned-memory staging (train_distributed.py:227-241, SURVEY.md §2.3).  The
TPU rebuild's primary hot path is the native C++ batch decoder (GIL-free by
construction, native/decode.cpp); this pool is the generic equivalent for
*Python-side* datasets: N spawned worker processes assemble whole batches
into a shared-memory slot ring, so pure-Python ``__getitem__`` pipelines
(PIL fallback, custom datasets) scale across cores exactly the way torch's
worker processes do.

Design:
  - ``spawn`` start method (safe alongside an initialized JAX runtime; the
    workers import only numpy/PIL — never JAX).
  - One shared-memory slab of ``n_slots`` batch slots (+ a label slab);
    workers write samples straight into their assigned slot — the handoff
    queue carries only ``(seq, slot)`` tuples, never pixels.
  - Batch order is preserved via a reorder buffer keyed by submission
    sequence number; augmentation determinism is per-sample
    (``fetch_sample``'s counter-based streams), so *which* worker decodes a
    batch cannot change its bytes.
  - A generation counter lets an abandoned epoch iterator drain its
    in-flight results without poisoning the next epoch.

Fault tolerance (worker respawn): each worker owns BOTH of its queues — a
process SIGKILLed while blocked in ``Queue.get`` dies holding the queue's
shared reader lock, and one killed while its feeder thread holds the
*result* queue's write lock wedges every other writer, so any queue a dead
worker ever touched is unrecoverable and must be abandoned wholesale
(single-owner queues make that safe; a shared result queue would poison
the survivors).  The pool keeps its own ledger of what each worker owes
(``_inflight``: submitted minus collected), so when ``_collect_one``'s
poll times out and an exitcode check finds a dead worker, the pool
replaces both its queues, resubmits every batch the worker still owed,
and respawns it with the same shard (queue) assignment — the epoch
continues without dropping or duplicating a batch.  Results the dying
worker managed to flush are either collected before the poll can time out
(popped from the ledger, never resubmitted) or discarded along with its
result queue and re-executed from the ledger — identical bytes either
way, since batch content is deterministic per (seed, epoch, index).
"""
from __future__ import annotations

import atexit
import os
import queue
import traceback
from collections import deque
from typing import Iterator, List, Optional, Sequence, Tuple

import multiprocessing as mp
from multiprocessing import shared_memory

import numpy as np

from ..telemetry.spans import span
from .datasets import fetch_sample

__all__ = ["ProcessLoaderPool"]


def _pool_worker_main(
    dataset,
    seed: int,
    shm_name: str,
    lshm_name: str,
    n_slots: int,
    batch_size: int,
    sample_shape: tuple,
    sample_dtype: str,
    task_q,
    result_q,
):
    """Worker loop: fetch per-sample data into the assigned shm slot."""
    shm = shared_memory.SharedMemory(name=shm_name)
    lshm = shared_memory.SharedMemory(name=lshm_name)
    try:
        slots = np.ndarray(
            (n_slots, batch_size) + sample_shape,
            dtype=np.dtype(sample_dtype),
            buffer=shm.buf,
        )
        labels = np.ndarray((n_slots, batch_size), dtype=np.int64, buffer=lshm.buf)
        while True:
            task = task_q.get()
            if task is None:
                return
            gen, seq, slot, epoch, indices = task
            try:
                for row, idx in enumerate(indices):
                    img, lab = fetch_sample(dataset, int(idx), seed, epoch)
                    slots[slot, row] = img
                    labels[slot, row] = lab
                result_q.put((gen, seq, slot, None))
            except Exception:
                result_q.put((gen, seq, slot, traceback.format_exc()))
    finally:
        shm.close()
        lshm.close()


class ProcessLoaderPool:
    """Persistent pool of decode worker processes + shm slot ring."""

    def __init__(
        self,
        dataset,
        batch_size: int,
        sample_shape: Sequence[int],
        sample_dtype: np.dtype,
        num_workers: int,
        seed: int,
        n_slots: Optional[int] = None,
        max_respawns: int = 8,
        stall_timeout: float = 60.0,
    ):
        if num_workers < 1:
            raise ValueError("ProcessLoaderPool requires num_workers >= 1")
        if stall_timeout <= 0:
            raise ValueError(f"stall_timeout must be > 0, got {stall_timeout}")
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.sample_shape = tuple(int(s) for s in sample_shape)
        self.sample_dtype = np.dtype(sample_dtype)
        self.num_workers = int(num_workers)
        # enough slots that every worker can be busy while a couple of
        # finished batches wait in the reorder buffer
        self.n_slots = int(n_slots) if n_slots else self.num_workers + 2
        self.seed = int(seed)
        self._gen = 0
        # tasks submitted but not yet collected off the result queue — pool-
        # level (not per-epoch) so an abandoned, never-closed epoch iterator
        # can't undercount: accounting happens at submit/collect time, never
        # in a generator finally that may not have run yet
        self._outstanding = 0
        from ..telemetry.registry import get_registry

        self._gauge = get_registry().gauge("data_pool_outstanding")
        self._closed = False
        # (gen, seq) -> (wid, task): every task submitted and not yet
        # collected, in submission order — the respawn ledger
        self._inflight = {}
        self.max_respawns = int(max_respawns)
        self.respawns = 0
        self._poll_seconds = 1.0
        self._stall_timeout = float(stall_timeout)

        slot_bytes = (
            self.batch_size * int(np.prod(self.sample_shape)) * self.sample_dtype.itemsize
        )
        self._shm = shared_memory.SharedMemory(
            create=True, size=max(1, self.n_slots * slot_bytes)
        )
        self._lshm = shared_memory.SharedMemory(
            create=True, size=self.n_slots * self.batch_size * 8
        )
        self._slots = np.ndarray(
            (self.n_slots, self.batch_size) + self.sample_shape,
            dtype=self.sample_dtype,
            buffer=self._shm.buf,
        )
        self._labels = np.ndarray(
            (self.n_slots, self.batch_size), dtype=np.int64, buffer=self._lshm.buf
        )

        self._ctx = mp.get_context("spawn")
        self._task_qs = [self._ctx.Queue() for _ in range(self.num_workers)]
        self._result_qs = [self._ctx.Queue() for _ in range(self.num_workers)]
        self._procs = [self._spawn_worker(i) for i in range(self.num_workers)]
        atexit.register(self.close)

    def _spawn_worker(self, wid: int):
        p = self._ctx.Process(
            target=_pool_worker_main,
            args=(
                self.dataset,
                self.seed,
                self._shm.name,
                self._lshm.name,
                self.n_slots,
                self.batch_size,
                self.sample_shape,
                self.sample_dtype.str,
                self._task_qs[wid],
                self._result_qs[wid],
            ),
            daemon=True,
        )
        p.start()
        return p

    # ------------------------------------------------------------------ epoch
    def run_epoch(
        self, batches: List[np.ndarray], epoch: int, postprocess
    ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Stream ``batches`` (index arrays) through the pool in order.

        ``postprocess(slot_view, label_view) -> (imgs, labels)`` converts a
        filled slot into caller-owned arrays (normalize or copy); the slot is
        recycled immediately after it returns.
        """
        # Only one epoch is live at a time, so every task still uncollected
        # here belongs to an abandoned epoch: its worker may be mid-write
        # into a slot this epoch would otherwise hand out.  Drain them all
        # before rebuilding the slot ring.  (The counter is maintained at
        # submit/collect time on the pool — correct even when the abandoned
        # iterator was never closed and its finally never ran.)
        while self._outstanding > 0:
            self._collect_one()
        self._gen += 1
        gen = self._gen
        pending = deque(enumerate(batches))
        free = list(range(self.n_slots))
        done = {}  # seq -> slot
        next_yield = 0
        while next_yield < len(batches):
            while free and pending:
                seq, idxs = pending.popleft()
                slot = free.pop()
                # fixed shard assignment: batch seq always goes to worker
                # seq % num_workers, and a respawned worker inherits its
                # predecessor's queue position — so which process decodes a
                # batch is deterministic across kills (batch bytes already
                # are, via per-sample augmentation streams)
                wid = seq % self.num_workers
                task = (gen, seq, slot, int(epoch), np.asarray(idxs))
                self._inflight[(gen, seq)] = (wid, task)
                self._task_qs[wid].put(task)
                self._outstanding += 1
                self._gauge.set(self._outstanding)
            if next_yield in done:
                slot = done.pop(next_yield)
                out = postprocess(self._slots[slot], self._labels[slot])
                free.append(slot)
                next_yield += 1
                yield out
                continue
            with span("loader_wait"):
                r = self._collect_one()
            if r[0] != gen:  # stale result from an abandoned epoch
                continue
            _, seq, slot, err = r
            if err is not None:
                raise RuntimeError(f"decode worker failed:\n{err}")
            done[seq] = slot

    def _collect_one(self):
        waited = 0.0
        per_q = self._poll_seconds / self.num_workers
        while True:
            r = None
            for result_q in self._result_qs:
                try:
                    r = result_q.get(timeout=per_q)
                    break
                except queue.Empty:
                    continue
            if r is None:
                waited += self._poll_seconds
                if self._reap_and_respawn():
                    waited = 0.0
                elif waited >= self._stall_timeout:
                    raise RuntimeError(
                        f"loader pool stalled: no result for {waited:.0f}s "
                        f"with {self._outstanding} task(s) outstanding and "
                        f"all {self.num_workers} worker(s) alive"
                    ) from None
                continue
            self._outstanding -= 1
            self._gauge.set(self._outstanding)
            self._inflight.pop((r[0], r[1]), None)
            return r

    def _reap_and_respawn(self) -> bool:
        """Respawn dead workers, resubmitting every task they still owed.

        Called only after a full result poll cycle came up Empty, so any
        result a dying worker managed to flush has normally been collected
        already (ledger entry popped); whatever remains under the dead
        worker's id is re-executed.  Both of the worker's queues are
        abandoned — the corpse may hold the task queue's reader lock or
        the result queue's writer lock, either of which would wedge a
        reusing successor — and a flushed-but-uncollected result discarded
        with the old result queue is simply re-executed from the ledger
        (same bytes: batch content is deterministic per (seed, epoch,
        index)).  Returns True when a worker was respawned.
        """
        respawned = False
        for wid, p in enumerate(self._procs):
            if p.is_alive():
                continue
            if self.respawns >= self.max_respawns:
                raise RuntimeError(
                    f"decode worker {wid} (pid {p.pid}) died with exitcode "
                    f"{p.exitcode} and the respawn budget "
                    f"({self.max_respawns}) is exhausted"
                )
            for old_q in (self._task_qs[wid], self._result_qs[wid]):
                old_q.cancel_join_thread()
                old_q.close()
            self._task_qs[wid] = self._ctx.Queue()
            self._result_qs[wid] = self._ctx.Queue()
            for owner, task in self._inflight.values():
                if owner == wid:
                    self._task_qs[wid].put(task)
            self.respawns += 1
            self._procs[wid] = self._spawn_worker(wid)
            respawned = True
            from ..engine import fault

            fault.bump("worker_respawns")
        return respawned

    # ------------------------------------------------------------------ close
    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            for q in self._task_qs:
                try:
                    q.put(None)
                except Exception:  # pragma: no cover - queue already broken
                    pass
            for p in self._procs:
                p.join(timeout=2.0)
            # escalate: a wedged worker (stuck decode, poisoned lock) must
            # not hang interpreter shutdown — terminate, then SIGKILL
            for p in self._procs:
                if p.is_alive():
                    p.terminate()
            for p in self._procs:
                if p.is_alive():
                    p.join(timeout=1.0)
            for p in self._procs:
                if p.is_alive():
                    p.kill()
                    p.join(timeout=1.0)
            for q in self._task_qs + self._result_qs:
                q.cancel_join_thread()
                q.close()
        finally:
            for shm in (self._shm, self._lshm):
                try:
                    shm.close()
                    shm.unlink()
                except FileNotFoundError:  # pragma: no cover
                    pass
        try:
            atexit.unregister(self.close)
        except Exception:  # pragma: no cover
            pass
