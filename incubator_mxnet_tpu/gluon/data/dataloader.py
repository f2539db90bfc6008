"""DataLoader (reference: python/mxnet/gluon/data/dataloader.py).

TPU-native re-design of the worker model.  The reference forks
multiprocessing workers that build batches in POSIX shared memory
(cpu_shared context, reference: src/storage/cpu_shared_storage_manager.h
+ _MultiWorkerIter) and passes fds over sockets.  Here:

* ``num_workers>0`` forks worker PROCESSES (default, reference parity) —
  each worker runs ``dataset[idx]`` + batchify to NUMPY (workers never
  touch jax: a chip belongs to ONE process, so the device and all XLA
  state stay owned by the parent), batches come back over pipes, and the
  parent does the one ``device_put``.  Fork inheritance replaces
  fd-passing — the dataset is inherited, not pickled per task.
* ``thread_pool=True`` keeps the round-2 prefetching thread pool
  (decode/augment in numpy/PIL releases the GIL) for workloads where fork
  is undesirable.

Start method is FORK deliberately, for the inheritance above.  Under
libtpu the parent usually holds the chip (and jax's threads) by the time
workers start; a forked child that called into jax would deadlock or
fight the parent for the device, which is what jax's fork warning is
about — and exactly what the numpy-only contract rules out.  A worker
that never calls jax never opens the chip, forked or spawned;
``thread_pool=True`` is the escape hatch if a platform makes fork
unsafe.
"""
from __future__ import annotations

import multiprocessing
import time as _time
from concurrent.futures import ThreadPoolExecutor

import numpy as _np

from ...base import MXNetError
from ... import fault as _fault
from ... import telemetry as _telemetry
from ...ndarray import ndarray as _ndmod
from ...ndarray.ndarray import NDArray
from .dataset import Dataset
from . import sampler as _sampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (reference: default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        from ...ndarray import ops as _ops
        return _ops.stack(*data, axis=0)
    if isinstance(data[0], (tuple, list)):
        return tuple(default_batchify_fn(list(x)) for x in zip(*data))
    arr = _np.asarray(data)
    if arr.dtype == _np.float64:
        arr = arr.astype(_np.float32)
    if arr.dtype == _np.int64:
        arr = arr.astype(_np.int32)
    return _ndmod.array(arr, dtype=arr.dtype)


def default_mp_batchify_fn(data):
    """Worker-side batchify: stacks to NUMPY only (reference:
    default_mp_batchify_fn builds cpu_shared NDArrays; here the no-jax-in-
    workers rule means numpy over the pipe, one device_put in the parent)."""
    if isinstance(data[0], NDArray):
        # the dataset produced device arrays INSIDE a forked worker —
        # that breaks the no-jax-in-workers contract fork depends on
        # (deadlock risk); fail loudly with the two safe spellings
        raise MXNetError(
            "Dataset returned NDArray under num_workers>0: worker "
            "processes must stay jax-free. Return numpy from "
            "__getitem__/transform, or use thread_pool=True")
    if isinstance(data[0], (tuple, list)):
        return tuple(default_mp_batchify_fn(list(x)) for x in zip(*data))
    arr = _np.asarray(data)
    if arr.dtype == _np.float64:
        arr = arr.astype(_np.float32)
    if arr.dtype == _np.int64:
        arr = arr.astype(_np.int32)
    return arr


def _to_device(batch):
    """Parent-side: numpy → NDArray (the single host→device hop)."""
    if isinstance(batch, (tuple, list)):
        return tuple(_to_device(b) for b in batch)
    if isinstance(batch, _np.ndarray):
        return _ndmod.array(batch, dtype=batch.dtype)
    return batch


# worker globals, inherited through fork (reference: _worker_initializer)
_worker_dataset = None
_worker_batchify = None


def _worker_initializer():
    pass  # dataset/batchify arrive via fork-inherited module globals


def _worker_fn(indices):
    return _worker_batchify([_worker_dataset[i] for i in indices])


class DataLoader:
    """Mini-batch iterator over a Dataset (reference: DataLoader)."""

    def __init__(self, dataset, batch_size=None, shuffle=False,
                 sampler=None, last_batch=None, batch_sampler=None,
                 batchify_fn=None, num_workers=0, pin_memory=False,
                 prefetch=None, thread_pool=False, timeout=120):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise MXNetError(
                    "batch_size is required unless batch_sampler is given")
            if sampler is None:
                sampler = (_sampler.RandomSampler(len(dataset)) if shuffle
                           else _sampler.SequentialSampler(len(dataset)))
            elif shuffle:
                raise MXNetError("shuffle is mutually exclusive w/ sampler")
            batch_sampler = _sampler.BatchSampler(
                sampler, batch_size, last_batch or "keep")
        elif (batch_size is not None or shuffle or sampler is not None
              or last_batch is not None):
            raise MXNetError(
                "batch_size/shuffle/sampler/last_batch are mutually "
                "exclusive with batch_sampler")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._thread_pool = thread_pool
        self._timeout = timeout
        if thread_pool:
            self._batchify_fn = batchify_fn or default_batchify_fn
        else:
            self._batchify_fn = batchify_fn or (
                default_mp_batchify_fn if self._num_workers > 0
                else default_batchify_fn)

    def _make_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def prefetch_to_device(self, buffers=None, placement=None):
        """Wrap this loader in an :class:`io.prefetch.DevicePrefetcher`:
        a background thread stages fetch AND h2d transfer ``buffers``
        batches ahead (``MXNET_PREFETCH_BUFFERS``, default 2), so batch
        i+1 lands on device while batch i computes.  ``placement`` maps
        each array to its device form (e.g. a trainer's mesh sharding);
        default plain ``jax.device_put``.  See docs/performance.md."""
        from ...io.prefetch import DevicePrefetcher
        return DevicePrefetcher(self, buffers=buffers,
                                placement=placement)

    def __iter__(self):
        it = self._iter_impl()
        observe = bool(_telemetry.DATALOADER.subscribers)
        if not observe and not _telemetry.tracer.active:
            yield from it
            return
        # fetch-wait plane: time the consumer spends blocked obtaining the
        # next batch (worker stalls surface here, compute does not); the
        # same window is a "dataloader.fetch" span in the trace
        while True:
            t0 = _time.perf_counter()
            with _telemetry.trace_span("dataloader.fetch", cat="data"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            if observe:
                _telemetry.DATALOADER.publish(
                    seconds=_time.perf_counter() - t0)
            yield batch

    def _iter_impl(self):
        if self._num_workers == 0:
            for indices in self._batch_sampler:
                _fault.inject("dataloader.fetch")
                yield self._make_batch(indices)
            return
        if self._thread_pool:
            yield from self._iter_threaded()
        else:
            yield from self._iter_multiprocess()

    def _fallback_batch(self, indices, exc):
        """A worker crashed or its result is unusable: rebuild the batch
        in-process so the epoch survives (graceful degradation — one slow
        batch instead of a dead run).  Publishes a FAULT fallback event so
        ``mxtpu_dataloader_fallbacks`` records the rescue."""
        import logging
        logging.getLogger(__name__).warning(
            "dataloader worker failed (%s: %s); rebuilding batch of %d "
            "samples in-process", type(exc).__name__, exc, len(indices))
        _telemetry.FAULT.publish(site="dataloader.fetch", event="fallback")
        return self._make_batch(indices)

    def _iter_threaded(self):
        # prefetching pool: keep `prefetch` batch futures in flight
        with ThreadPoolExecutor(self._num_workers) as pool:
            batches = iter(self._batch_sampler)
            inflight = []
            try:
                for _ in range(max(1, self._prefetch)):
                    indices = next(batches)
                    inflight.append(
                        (pool.submit(self._make_batch, indices), indices))
            except StopIteration:
                pass
            while inflight:
                fut, indices = inflight.pop(0)
                try:
                    nxt = next(batches)
                    inflight.append(
                        (pool.submit(self._make_batch, nxt), nxt))
                except StopIteration:
                    pass
                try:
                    _fault.inject("dataloader.fetch")
                    batch = fut.result()
                except Exception as exc:     # noqa: BLE001 — rescue any
                    batch = self._fallback_batch(indices, exc)
                yield batch

    def _iter_multiprocess(self):
        """Reference _MultiWorkerIter flow: dispatch index batches to forked
        workers, keep `prefetch` in flight, reorder-free FIFO collection.
        A crashed/hung worker result falls back to an in-process rebuild of
        the same index batch (order and content preserved)."""
        global _worker_dataset, _worker_batchify
        ctx = multiprocessing.get_context("fork")
        _worker_dataset = self._dataset
        _worker_batchify = self._batchify_fn
        pool = ctx.Pool(self._num_workers, initializer=_worker_initializer)
        try:
            batches = iter(self._batch_sampler)
            inflight = []
            try:
                for _ in range(max(1, self._prefetch)):
                    indices = next(batches)
                    inflight.append(
                        (pool.apply_async(_worker_fn, (indices,)), indices))
            except StopIteration:
                pass
            while inflight:
                res, indices = inflight.pop(0)
                try:
                    nxt = next(batches)
                    inflight.append(
                        (pool.apply_async(_worker_fn, (nxt,)), nxt))
                except StopIteration:
                    pass
                try:
                    _fault.inject("dataloader.fetch")
                    batch = res.get(self._timeout)
                except Exception as exc:     # noqa: BLE001 — rescue any
                    batch = self._fallback_batch(indices, exc)
                yield _to_device(batch)
        finally:
            pool.terminate()
            pool.join()

    def __len__(self):
        return len(self._batch_sampler)
