"""DataLoader (parity: python/mxnet/gluon/data/dataloader.py).

TPU-native design: decode/augment on host (optionally in worker processes,
like the reference's _MultiWorkerIter over multiprocessing), batchify to
numpy, then a background prefetch thread keeps a bounded queue of ready
batches and (optionally) stages them onto device ahead of the consumer —
replacing the reference's C++ PrefetcherIter double buffer
(src/io/iter_prefetcher.h) with an equivalent host-thread pipeline that
overlaps input processing with TPU compute via JAX async dispatch.
"""

import contextlib
import multiprocessing
import os
import queue as _queue
import threading

import numpy as np

from ... import ndarray as nd
from ...ndarray import NDArray
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (recursively for tuples/lists/dicts)."""
    if isinstance(data[0], NDArray):
        return nd.stack(*data, axis=0)
    if isinstance(data[0], (tuple, list)):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    if isinstance(data[0], dict):
        return {k: default_batchify_fn([d[k] for d in data]) for k in data[0]}
    data = np.asarray(data)
    return data


# Worker processes return numpy (cheap to pickle); conversion to device
# arrays happens in the main process during prefetch.
def default_mp_batchify_fn(data):
    if isinstance(data[0], NDArray):
        return np.stack([d.asnumpy() for d in data], axis=0)
    if isinstance(data[0], (tuple, list)):
        data = zip(*data)
        return [default_mp_batchify_fn(i) for i in data]
    if isinstance(data[0], dict):
        return {k: default_mp_batchify_fn([d[k] for d in data]) for k in data[0]}
    return np.asarray(data)


_worker_dataset = None
_worker_batchify = None

_pool_ctx_lock = threading.Lock()
_pool_ctx = None

# a chip belongs to one process: workers decode on the host and must
# never reach for the parent's chip
_SANITIZE_ENV = {"JAX_PLATFORMS": "cpu"}


@contextlib.contextmanager
def _sanitized_env():
    """Temporarily pin the env so a child interpreter uses the host CPU
    for any incidental jax work.  Callers hold _pool_ctx_lock, so the
    mutate-restore window is serialized."""
    saved = {k: os.environ.get(k) for k in _SANITIZE_ENV}
    os.environ.update(_SANITIZE_ENV)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _get_pool_context():
    """multiprocessing context for worker pools, created once.

    forkserver (not fork): forking a process whose JAX runtime has live
    threads deadlocks (JAX warns on os.fork); the forkserver parent is
    launched clean, so workers never inherit JAX state.  The forkserver is
    started HERE, exactly once, under the sanitized env — all future
    workers fork from it and inherit that env, so pool creation never
    mutates the parent env again (the round-2 mutate-restore around every
    Pool() raced concurrent jax importers).  If some other library already
    started the forkserver with the live TPU env, starting it again can't
    fix its env — fall back to spawn, whose children re-read the parent
    env at spawn time (sanitized per-pool in _make_worker_pool).
    """
    global _pool_ctx
    with _pool_ctx_lock:
        if _pool_ctx is not None:
            return _pool_ctx
        methods = multiprocessing.get_all_start_methods()
        if "forkserver" in methods:
            from multiprocessing import forkserver as _fs
            already = getattr(_fs._forkserver, "_forkserver_pid",
                              None) is not None
            if not already:
                with _sanitized_env():
                    _fs._forkserver.ensure_running()
                _pool_ctx = ("forkserver",
                             multiprocessing.get_context("forkserver"))
                return _pool_ctx
        _pool_ctx = ("spawn", multiprocessing.get_context("spawn"))
        return _pool_ctx


def _make_worker_pool(num_workers, initializer, initargs):
    method, ctx = _get_pool_context()
    if method == "forkserver":  # env pinned in the forkserver: no mutation
        return ctx.Pool(num_workers, initializer=initializer,
                        initargs=initargs)
    # spawn: children re-read env at spawn time, so a sanitized window is
    # unavoidable — serialized under the lock to keep it race-free.
    with _pool_ctx_lock, _sanitized_env():
        return ctx.Pool(num_workers, initializer=initializer,
                        initargs=initargs)


def _worker_init(dataset, batchify_fn):
    """Process-pool initializer: each fork-worker gets its own copy of the
    dataset in its own process globals."""
    global _worker_dataset, _worker_batchify
    _worker_dataset = dataset
    _worker_batchify = batchify_fn


_SHM_MIN_BYTES = 1 << 20  # arrays below 1 MiB just pickle


def _to_shared(obj):
    """Large numpy arrays → POSIX shared-memory handles, so worker batches
    cross the process boundary by page mapping instead of pickle bytes
    (parity: the reference's shared-mem NDArray worker transport,
    gluon/data/dataloader.py _as_in_context/shared_mem pipes).  Measured
    ~9x pipeline throughput at 224px float batches (PERF.md)."""
    if (isinstance(obj, np.ndarray) and obj.nbytes >= _SHM_MIN_BYTES
            and not obj.dtype.hasobject):
        from multiprocessing import shared_memory
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        view = np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)
        view[...] = obj
        name = shm.name
        shm.close()  # parent reopens by name and unlinks
        # ship the dtype OBJECT (str() mangles structured dtypes)
        return ("__shm__", name, obj.shape, obj.dtype)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_shared(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _to_shared(v) for k, v in obj.items()}
    return obj


def _from_shared(obj):
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        from multiprocessing import shared_memory
        _, name, shape, dtype = obj
        shm = shared_memory.SharedMemory(name=name)
        try:
            # one copy out of the mapping: a zero-copy view would pin the
            # segment via exported buffers and SharedMemory.close() then
            # raises BufferError at GC — the copy (~30ms for a 77MB batch)
            # buys deterministic unlink
            arr = np.ndarray(shape, np.dtype(dtype),
                             buffer=shm.buf).copy()
        finally:
            shm.close()
            shm.unlink()
        return arr
    if isinstance(obj, (list, tuple)):
        return type(obj)(_from_shared(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _from_shared(v) for k, v in obj.items()}
    return obj


def _worker_fn(samples):
    return _to_shared(_worker_batchify(
        [_worker_dataset[i] for i in samples]))


def _thread_worker_fn(dataset, batchify_fn, samples):
    """Thread-pool task: dataset passed explicitly — threads share the
    parent's globals, so per-loader state must not live there."""
    return batchify_fn([dataset[i] for i in samples])


def _as_device(data, pin_device):
    """Move a batchified (possibly nested) numpy batch onto device."""
    if isinstance(data, (list, tuple)):
        return type(data)(_as_device(d, pin_device) for d in data)
    if isinstance(data, dict):
        return {k: _as_device(v, pin_device) for k, v in data.items()}
    if isinstance(data, NDArray):
        return data
    return nd.array(data)


class _PrefetchIter:
    """Background thread pulls batches from `source_iter`, converts to
    device arrays, and keeps up to `prefetch` ready ahead of the consumer."""

    _SENTINEL = object()

    def __init__(self, source_iter, prefetch, pin_memory):
        self._queue = _queue.Queue(maxsize=max(1, prefetch))
        self._pin = pin_memory
        self._exc = None
        self._closed = threading.Event()

        def _put(item):
            # bounded put that gives up when the consumer abandoned us
            while not self._closed.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def _run():
            try:
                for batch in source_iter:
                    if not _put(_as_device(batch, pin_memory)):
                        break  # consumer gone; stop staging batches
            except Exception as e:  # propagate to consumer thread
                self._exc = e
            finally:
                # close the generator from ITS OWN consuming thread so its
                # cleanup (in-flight shm drain) runs deterministically
                close = getattr(source_iter, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:
                        pass
                _put(self._SENTINEL)

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()

    def close(self):
        self._closed.set()

    def __del__(self):
        self._closed.set()

    def __iter__(self):
        return self

    def __next__(self):
        item = self._queue.get()
        if item is self._SENTINEL:
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        return item


class DataLoader:
    """Loads batches from a Dataset.

    Parameters mirror the reference: dataset, batch_size, shuffle, sampler,
    last_batch, batch_sampler, batchify_fn, num_workers, pin_memory,
    prefetch, thread_pool.
    """

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=False):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._thread_pool = thread_pool
        self._num_workers = max(0, num_workers)
        self._prefetch = max(0, 2 * self._num_workers if prefetch is None
                             else prefetch)

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
        elif (batch_size is not None or shuffle or sampler is not None
              or last_batch is not None):
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler

        if batchify_fn is None:
            self._batchify_fn = (default_mp_batchify_fn if self._num_workers
                                 else default_batchify_fn)
        else:
            self._batchify_fn = batchify_fn

        self._pool = None
        if self._num_workers > 0:
            if thread_pool:
                from multiprocessing.pool import ThreadPool
                self._pool = ThreadPool(self._num_workers)
            else:
                self._pool = _make_worker_pool(
                    self._num_workers, _worker_init,
                    (self._dataset, self._batchify_fn))

    def _single_process_iter(self):
        for batch_idx in self._batch_sampler:
            yield self._batchify_fn([self._dataset[i] for i in batch_idx])

    def _submit(self, batch_idx):
        if self._thread_pool:
            return self._pool.apply_async(
                _thread_worker_fn,
                (self._dataset, self._batchify_fn, batch_idx))
        return self._pool.apply_async(_worker_fn, (batch_idx,))

    def _multi_worker_iter(self):
        # keep up to prefetch async results in flight, in order
        it = iter(self._batch_sampler)
        pending = []
        try:
            for _ in range(max(1, self._prefetch)):
                pending.append(self._submit(next(it)))
        except StopIteration:
            pass
        try:
            while pending:
                res = pending.pop(0)
                try:
                    pending.append(self._submit(next(it)))
                except StopIteration:
                    pass
                out = res.get()
                yield _from_shared(out) if not self._thread_pool else out
        finally:
            # consumer abandoned us: claim EVERY in-flight result so its
            # shared-memory segments are unlinked, not leaked.  A slow
            # batch (>1s decode) must not abort the drain — later results
            # may already be sitting complete (continue, don't break); but
            # a terminated pool (GC finalization order is arbitrary) never
            # completes anything, so stop once the pool is known dead.
            pool_alive = not self._thread_pool
            for res in pending:
                while pool_alive:
                    try:
                        _from_shared(res.get(timeout=5))
                        break
                    except multiprocessing.TimeoutError:
                        if getattr(self._pool, "_state", "RUN") != "RUN":
                            pool_alive = False  # dead: nothing completes
                    except Exception:
                        break  # worker error: no segment was shipped

    def __iter__(self):
        source = (self._multi_worker_iter() if self._pool is not None
                  else self._single_process_iter())
        return iter(_PrefetchIter(source, prefetch=max(1, self._prefetch),
                                  pin_memory=self._pin_memory))

    def __len__(self):
        return len(self._batch_sampler)

    def __del__(self):
        if self._pool is not None:
            self._pool.terminate()
