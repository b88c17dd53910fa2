"""DataLoader.

Reference: python/paddle/io/reader.py:218 (DataLoader) and the multiprocess
worker loop (dataloader/dataloader_iter.py:451, worker.py _worker_loop).
TPU-native design: collation produces numpy batches; a background
prefetch thread overlaps host work with XLA's async execution, and
``num_workers>0`` runs REAL worker processes (fork) that fetch + collate
samples to numpy off the main process — device arrays are only created in
the parent (jax state does not survive into forked children safely).
"""
from __future__ import annotations

import multiprocessing as mp
import queue
import threading
import time
import traceback
from typing import Any, Callable, Optional

import jax
import numpy as np

from ..tensor import Tensor, to_tensor
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler


def numpy_collate_fn(batch):
    """Collate to NUMPY (worker-process safe — no device arrays)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._value) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [numpy_collate_fn(list(s)) for s in transposed]
    if isinstance(sample, dict):
        return {k: numpy_collate_fn([d[k] for d in batch]) for k in sample}
    return batch


def _to_device_tree(obj):
    """numpy leaves -> Tensor (parent-process side of the worker pipeline)."""
    if isinstance(obj, np.ndarray):
        return to_tensor(obj)
    if isinstance(obj, list):
        return [_to_device_tree(o) for o in obj]
    if isinstance(obj, tuple):
        return tuple(_to_device_tree(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _to_device_tree(v) for k, v in obj.items()}
    return obj


def default_collate_fn(batch):
    return _to_device_tree(numpy_collate_fn(batch))


class _WorkerError:
    def __init__(self, exc):
        self.msg = "".join(traceback.format_exception(exc))


def _host_only(obj):
    """Raise if a worker's sample holds a device array.  The workers are
    forked from a process whose JAX runtime is live; neither the runtime
    nor, on a TPU host, its hold on the chip survives a fork, so a worker
    stays on numpy and the parent alone makes Tensors."""
    if isinstance(obj, (Tensor, jax.Array)):
        raise TypeError(
            "a DataLoader worker produced a device array "
            f"({type(obj).__name__}); with num_workers > 0 the dataset and "
            "collate_fn must return numpy — Tensor conversion happens in "
            "the parent process")
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _host_only(o)
    elif isinstance(obj, dict):
        for o in obj.values():
            _host_only(o)
    return obj


def _worker_loop(dataset, index_queue, data_queue, collate_fn, init_fn, wid):
    """Worker process body (reference: io/dataloader/worker.py _worker_loop).
    Receives (batch_idx, indices); sends (batch_idx, numpy_batch)."""
    try:
        if init_fn is not None:
            init_fn(wid)
    except BaseException as e:  # noqa: BLE001
        data_queue.put((-1, _WorkerError(e)))
        return
    while True:
        item = index_queue.get()
        if item is None:
            break
        bidx, indices = item
        try:
            batch = collate_fn([_host_only(dataset[i]) for i in indices])
            data_queue.put((bidx, _host_only(batch)))
        except BaseException as e:  # noqa: BLE001
            data_queue.put((bidx, _WorkerError(e)))


class DataLoader:
    def __init__(
        self,
        dataset: Dataset,
        feed_list=None,
        places=None,
        return_list=True,
        batch_sampler: Optional[BatchSampler] = None,
        batch_size: int = 1,
        shuffle: bool = False,
        drop_last: bool = False,
        collate_fn: Optional[Callable] = None,
        num_workers: int = 0,
        use_buffer_reader: bool = True,
        prefetch_factor: int = 2,
        use_shared_memory: bool = True,
        timeout: int = 0,
        worker_init_fn=None,
        persistent_workers=False,
    ):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(prefetch_factor, 1)
        self.use_buffer_reader = use_buffer_reader
        self._worker_init_fn = worker_init_fn
        self._timeout = timeout
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last
            )

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    @property
    def prefetch_window(self) -> int:
        """Depth of the in-flight batch pipeline.  ``num_workers *
        prefetch_factor`` is the multiprocess window, but computed
        unclamped it collapses to a 0-deep pipeline for the common
        single-process ``num_workers == 0`` path — treat the consumer
        process as one worker there, so ``prefetch_factor`` keeps its
        meaning (a depth-``prefetch_factor`` background pipeline) and
        the window is always >= 1."""
        return max(self.num_workers, 1) * self.prefetch_factor

    def device_prefetch(self, depth: int = 2, sharding=None):
        """Wrap iteration in a :class:`~paddle_tpu.io.DevicePrefetcher`:
        up to ``depth`` batches are ``device_put`` (with ``sharding`` when
        given) ahead of the consumer, overlapping host->device transfer
        with the running step; consumer wait lands in the
        ``train_input_stall_seconds`` histogram."""
        from .device_prefetch import DevicePrefetcher

        return DevicePrefetcher(iter(self), depth=depth, sharding=sharding)

    def _batches(self):
        if self._iterable_mode:
            batch = []
            for sample in self.dataset:
                batch.append(sample)
                if len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                yield self.collate_fn(batch)
            return
        for indices in self.batch_sampler:
            yield self.collate_fn([self.dataset[i] for i in indices])

    def _mp_batches(self):
        """Multiprocess pipeline: fork ``num_workers`` processes, round-robin
        index batches, reorder results (reference dataloader_iter.py:451
        _DataLoaderIterMultiProcess)."""
        ctx = mp.get_context("fork")
        # workers apply the user's collate when given one, else numpy
        # collate; Tensor conversion always happens in the parent
        user_collate = (self.collate_fn
                        if self.collate_fn is not default_collate_fn
                        else numpy_collate_fn)
        index_queues = [ctx.Queue() for _ in range(self.num_workers)]
        data_queue = ctx.Queue()
        workers = []
        for wid in range(self.num_workers):
            w = ctx.Process(
                target=_worker_loop,
                args=(self.dataset, index_queues[wid], data_queue,
                      user_collate, self._worker_init_fn, wid),
                daemon=True,
            )
            w.start()
            workers.append(w)
        try:
            all_batches = list(self.batch_sampler)
            n = len(all_batches)
            window = self.prefetch_window
            sent = 0
            for sent in range(min(window, n)):
                index_queues[sent % self.num_workers].put(
                    (sent, all_batches[sent]))
            sent = min(window, n)
            received = {}
            next_out = 0
            timeout = self._timeout or None
            while next_out < n:
                deadline = (time.monotonic() + timeout) if timeout else None
                while next_out not in received:
                    # poll in short slices so a worker that died WITHOUT
                    # enqueueing an error (OOM-kill, segfault) raises
                    # instead of hanging the training process forever
                    try:
                        bidx, payload = data_queue.get(timeout=5.0)
                    except queue.Empty:
                        dead = [w.pid for w in workers if not w.is_alive()]
                        if dead:
                            raise RuntimeError(
                                f"DataLoader worker(s) {dead} died "
                                "unexpectedly (killed or crashed without "
                                "reporting an error)")
                        if deadline and time.monotonic() > deadline:
                            raise RuntimeError(
                                f"DataLoader timed out after {timeout}s "
                                "waiting for a worker batch")
                        continue
                    if isinstance(payload, _WorkerError):
                        raise RuntimeError(
                            f"DataLoader worker failed:\n{payload.msg}")
                    received[bidx] = payload
                batch = received.pop(next_out)
                if sent < n:
                    index_queues[sent % self.num_workers].put(
                        (sent, all_batches[sent]))
                    sent += 1
                next_out += 1
                yield _to_device_tree(batch)
        finally:
            for iq in index_queues:
                try:
                    iq.put(None)
                except Exception:
                    pass
            for w in workers:
                w.join(timeout=1.0)
                if w.is_alive():
                    w.terminate()

    def __iter__(self):
        if self.num_workers > 0 and not self._iterable_mode:
            yield from self._mp_batches()
            return
        if not self.use_buffer_reader:
            yield from self._batches()
            return
        # background prefetch thread (async host pipeline); window clamped
        # >= 1 even at num_workers == 0 (the single-process bench path)
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_window)
        sentinel = object()
        err = []
        # consumer-side shutdown signal: a consumer that breaks out of
        # iteration early (or is gc'd) closes the generator, which must
        # release a producer blocked on a full queue — a plain q.put would
        # leak the thread (parked forever) plus its prefetched batches
        stop = threading.Event()

        def producer():
            try:
                for b in self._batches():
                    while not stop.is_set():
                        try:
                            q.put(b, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
            except BaseException as e:  # propagate into consumer
                err.append(e)
            finally:
                # normal completion: wait for space (never displace a real
                # batch); on shutdown: force-place so nothing ever blocks
                placed = False
                while not stop.is_set():
                    try:
                        q.put(sentinel, timeout=0.1)
                        placed = True
                        break
                    except queue.Full:
                        continue
                while not placed:
                    try:
                        q.put_nowait(sentinel)
                        placed = True
                    except queue.Full:
                        try:
                            q.get_nowait()
                        except queue.Empty:
                            pass

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is sentinel:
                    break
                yield item
            if err:
                raise err[0]
        finally:
            # runs on normal exhaustion AND on generator close() (early
            # break / gc): unblock + retire the producer
            stop.set()
            while True:  # drain so a blocked put releases immediately
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            # best-effort reap: the daemon thread exits at its next put
            # poll (<=0.1s) unless it is mid-computation inside
            # _batches(); don't stall the caller's break/GC path for that
            t.join(timeout=0.5)
