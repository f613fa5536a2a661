"""DataLoader (reference: ``python/mxnet/gluon/data/dataloader.py:307``).

Multi-worker decode uses a ``multiprocessing.Pool``; batches cross process
boundaries as NumPy arrays (host memory is host memory on TPU — the
reference's POSIX-shm NDArray rebuild, ``cpu_shared_storage_manager.h``,
has no device-pinned analog; ``pin_memory`` is accepted and ignored,
documented delta).  Device upload happens on first use of the returned
``mx.np`` arrays.
"""
from __future__ import annotations

import logging
import multiprocessing
import os
import pickle
import signal
import threading
import time

import numpy as _onp

from ... import fault as _fault
from ... import numpy as mnp
from ... import profiler as _profiler
from ...ndarray.ndarray import NDArray
from .sampler import BatchSampler, RandomSampler, SequentialSampler


class _WorkerLost(Exception):
    """A pool worker died while a batch was in flight."""


def default_batchify_fn(data):
    """Stack samples into a batch (dataloader.py default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        return mnp.stack(data)
    if isinstance(data[0], (tuple, list)):
        return [default_batchify_fn(list(x)) for x in zip(*data)]
    out = _onp.asarray(data)
    return mnp.array(out)


def default_mp_batchify_fn(data):
    if isinstance(data[0], (tuple, list)):
        return [default_mp_batchify_fn(list(x)) for x in zip(*data)]
    if isinstance(data[0], NDArray):
        return _onp.stack([d.asnumpy() for d in data])
    return _onp.asarray(data)


_worker_dataset = None
_END = object()  # a batch iterator ran out


def _worker_initializer(dataset):
    global _worker_dataset
    _worker_dataset = dataset
    # pool workers must not inherit parent signal handlers (e.g. the
    # mx.fault preemption autosaver): terminate() must kill them cleanly
    import signal
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    signal.signal(signal.SIGINT, signal.SIG_IGN)


def _worker_fn(samples, batchify_fn):
    batch = batchify_fn([_worker_dataset[i] for i in samples])
    return pickle.dumps(batch, protocol=pickle.HIGHEST_PROTOCOL)


def _as_nd(batch):
    if isinstance(batch, _onp.ndarray):
        return mnp.array(batch)
    if isinstance(batch, (list, tuple)):
        return [_as_nd(b) for b in batch]
    return batch


def _batch_len(batch):
    """Leading-axis length of the first array leaf of a batch."""
    while isinstance(batch, (list, tuple)) and batch:
        batch = batch[0]
    shape = getattr(batch, "shape", None)
    return int(shape[0]) if shape else 1


class _ElasticPlanSampler:
    """Batch-sampler view of a :class:`~mxnet_tpu.parallel.EpochPlan`
    (duck-typed — anything with ``done``/``next_for``/``remaining``):
    each iteration step yields THIS rank's global indices and advances
    the replicated cursor, so an elastic fleet reads every index of the
    epoch exactly once across mid-epoch resizes.  ``rank`` may be a
    callable (e.g. ``lambda: runner.info.rank``) because a resize
    renumbers ranks; it is re-read every step.  Like the plan itself,
    NOT thread-safe — one loader per plan, the repo-wide norm."""

    def __init__(self, plan, rank):
        self._plan = plan
        self._rank = rank

    def _rank_now(self):
        r = self._rank
        return int(r() if callable(r) else r)

    def __iter__(self):
        while not self._plan.done():
            yield [int(i) for i in self._plan.next_for(self._rank_now())]

    def __len__(self):
        # steps left at the CURRENT world/batch (a later resize changes
        # the window, so this is an estimate — the iteration contract,
        # exactly-once over [cursor, total), is what holds)
        window = self._plan.world * self._plan.batch_per_rank
        return -(-self._plan.remaining() // max(1, window))


class DataLoader:
    """Loads data from a Dataset and returns mini-batches.

    ``elastic_plan=`` (opt-in) drives iteration from a shared
    :class:`~mxnet_tpu.parallel.EpochPlan` instead of a sampler: the
    loader consumes one plan window per batch via ``next_for(rank)``,
    giving resize-aware exactly-once epoch reads without hand-driving
    the plan.  Mutually exclusive with ``batch_size``/``shuffle``/
    ``sampler``/``batch_sampler``/``last_batch``; ``elastic_rank`` is
    this process's rank, or a callable re-read every step (ranks are
    renumbered by a resize)."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=False, timeout=120,
                 try_nopython=None, elastic_plan=None, elastic_rank=0):
        self._dataset = dataset
        self._pin_memory = pin_memory  # accepted; no-op on TPU hosts
        self._timeout = timeout
        if elastic_plan is not None:
            if batch_sampler is not None or batch_size is not None or \
                    shuffle or sampler is not None or last_batch is not None:
                raise ValueError(
                    "elastic_plan drives batching itself: batch_size, "
                    "shuffle, sampler, last_batch and batch_sampler must "
                    "not be specified with it")
            batch_sampler = _ElasticPlanSampler(elastic_plan, elastic_rank)
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        if batchify_fn is None:
            self._batchify_fn = default_mp_batchify_fn \
                if self._num_workers > 0 else default_batchify_fn
        else:
            self._batchify_fn = batchify_fn
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._pool = None
        self._worker_pids = ()
        self._rebuilt = False  # worker supervision rebuilds the pool once
        if self._num_workers > 0:
            self._make_pool()

    def _make_pool(self):
        self._pool = multiprocessing.get_context("fork").Pool(
            self._num_workers, initializer=_worker_initializer,
            initargs=(self._dataset,))
        self._worker_pids = tuple(sorted(
            w.pid for w in self._pool._pool))

    def __iter__(self):
        # profiler seam: time each batch *fetch* (excluding the consumer's
        # work between iterations) and count batches/samples through the
        # loader; one flag read per batch when profiling is off
        t_fetch = _profiler._now_us() if _profiler._DATA else None
        host_batches = self._iter_batches()
        while True:
            # mx.data.next: the wait for a batch (dataset reads and
            # batchify, or a worker's result); mx.data.h2d: the upload
            # of what came as NumPy (the default single-process batchify
            # uploads inside mx.data.next)
            with _profiler.span("mx.data.next"):
                batch = next(host_batches, _END)
            if batch is _END:
                return
            with _profiler.span("mx.data.h2d"):
                batch = _as_nd(batch)
            if _profiler._DATA:
                if t_fetch is not None:
                    _profiler.record_duration(
                        "DataLoader::next", "data", t_fetch,
                        _profiler._now_us() - t_fetch)
                _profiler.counter_add("dataloader::batches", 1, cat="data")
                _profiler.counter_add("dataloader::samples",
                                      _batch_len(batch), cat="data")
            yield batch
            t_fetch = _profiler._now_us() if _profiler._DATA else None

    def _iter_batches(self):
        if self._pool is None:
            for batch in self._batch_sampler:
                yield self._batchify_fn(
                    [self._dataset[i] for i in batch])
            return

        batchify = self._batchify_fn
        it = iter(self._batch_sampler)
        # one rebuild allowed per iteration: two deaths within one epoch
        # mean persistent crashing, but isolated deaths epochs apart are
        # each independently recoverable
        self._rebuilt = False
        pending = []  # [samples, AsyncResult] — samples kept for resubmit

        def submit(samples):
            if _fault._ACTIVE:
                _fault.dataloader_hook(self._pool)
            return [samples, self._pool.apply_async(_worker_fn,
                                                    (samples, batchify))]

        for _ in range(self._prefetch or 1):
            batch = next(it, None)
            if batch is None:
                break
            pending.append(submit(batch))
        while pending:
            samples, res = pending.pop(0)
            nxt = next(it, None)
            if nxt is not None:
                pending.append(submit(nxt))
            try:
                payload = self._supervised_get(res)
            except _WorkerLost:
                payload = self._recover(samples, pending)
            yield pickle.loads(payload)

    def _supervised_get(self, res):
        """Wait for a batch, watching the pool's workers: a worker that
        dies mid-flight (OOM-killed, segfault, injected SIGKILL) takes
        its task with it and would otherwise hang the iterator until
        the full timeout.  Detection is by pid-set change (the Pool's
        maintainer thread replaces dead workers) or a nonzero exitcode."""
        deadline = None if self._timeout is None \
            else time.monotonic() + self._timeout
        while True:
            res.wait(0.1)
            if res.ready():
                return res.get()  # re-raises a worker-side exception
            procs = list(self._pool._pool)
            if any(w.exitcode is not None for w in procs) or \
                    tuple(sorted(w.pid for w in procs)) != self._worker_pids:
                raise _WorkerLost()
            if deadline is not None and time.monotonic() >= deadline:
                raise RuntimeError(
                    "DataLoader worker timed out after %ds" % self._timeout)

    def _recover(self, samples, pending):
        """A worker died: rebuild the pool (once per loader) and
        resubmit every batch that had not completed.  Batches are pure
        functions of their sample indices, so recomputation is safe."""
        if self._rebuilt:
            raise self._persistent_crash_error()
        self._rebuilt = True
        logging.getLogger("mxnet_tpu.data").warning(
            "DataLoader worker died; rebuilding the %d-worker pool and "
            "resubmitting %d in-flight batch(es)", self._num_workers,
            1 + sum(1 for _, r in pending if not r.ready()))
        self._hard_terminate(self._pool)
        self._make_pool()
        _profiler.counter_bump("fault::worker_restarts", 1, cat="fault")
        # resubmits are retries of already-counted fetches — bypass the
        # injection hook so they don't consume fresh fault occurrences
        for entry in pending:
            if not entry[1].ready():  # completed results stay valid
                entry[1] = self._pool.apply_async(
                    _worker_fn, (entry[0], self._batchify_fn))
        try:
            return self._supervised_get(self._pool.apply_async(
                _worker_fn, (samples, self._batchify_fn)))
        except _WorkerLost:
            raise self._persistent_crash_error() from None

    @staticmethod
    def _persistent_crash_error():
        return RuntimeError(
            "DataLoader worker died again after the pool was already "
            "rebuilt once; dataset workers are crashing persistently "
            "(check for OOM or a native crash in Dataset.__getitem__)")

    @staticmethod
    def _hard_terminate(pool):
        """Tear down a pool whose worker died violently.  A SIGKILLed
        worker can die holding the task-queue read lock, and
        ``Pool.terminate`` then blocks forever in ``_help_stuff_finish``
        on a semaphore no live process will ever release — so run the
        graceful terminate in a daemon thread with a deadline and, if it
        wedges, SIGKILL the remaining workers and abandon the pool (its
        exit finalizer has already been consumed by the terminate call,
        so interpreter shutdown cannot hang on it either)."""
        done = threading.Event()

        def _terminate():
            try:
                pool.terminate()
                pool.join()
            except Exception:
                pass
            finally:
                done.set()

        threading.Thread(target=_terminate, daemon=True,
                         name="dataloader-pool-reaper").start()
        if done.wait(5.0):
            return
        for w in list(getattr(pool, "_pool", []) or []):
            try:
                os.kill(w.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass
        done.wait(2.0)

    def __len__(self):
        return len(self._batch_sampler)

    def close(self):
        """Terminate and join the worker pool.  Idempotent; also called
        by ``__del__`` and on context-manager exit, so the pool is never
        leaked on GC."""
        pool, self._pool = self._pool, None
        if pool is not None:
            self._hard_terminate(pool)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # interpreter teardown: modules half-gone
            pass
