"""DataLoader. Parity: python/paddle/fluid/reader.py:DataLoader +
fluid/dataloader/dataloader_iter.py.

TPU-first: worker threads/processes produce numpy batches; a double-buffered
prefetcher overlaps host batch assembly and host->HBM transfer with device
compute (the reference overlaps via pinned-memory + CUDA streams; here the
async dispatch of jax.device_put plays that role). A native C++ prefetch ring
(csrc/prefetch.cpp) backs the queue when built.

Self-healing (docs/RESILIENCE.md, "Distributed fault tolerance"): a worker
that raises propagates the exception to the consumer instead of dying
silently; a worker that hangs or is killed trips a deadlock watchdog
(bounded queue waits + liveness checks, budget = ``timeout`` seconds or
``PADDLE_TPU_DATA_TIMEOUT``); poisoned samples are quarantined up to a
bounded skip budget (``skip_bad_samples`` / ``PADDLE_TPU_DATA_SKIP_BUDGET``)
with a per-index report; crashed process workers are respawned up to
``worker_max_restarts`` times.
"""
import itertools
import os
import queue
import threading

import numpy as np

from ..core.tensor import Tensor
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler, SequenceSampler, RandomSampler
from .. import observability as _obs
from ..resilience import watchdog as _watchdog

__all__ = ['DataLoader', 'DevicePrefetcher', 'default_collate_fn',
           'default_convert_fn', 'DataLoaderWorkerError']

# consumer-side stall budget when DataLoader(timeout=0): generous enough
# for any real batch assembly, small enough that a wedged pipeline fails
# the job the same hour it wedges
_DEFAULT_WATCHDOG_S = 300.0


class DataLoaderWorkerError(RuntimeError):
    """A DataLoader worker failed (raised, hung past the watchdog budget,
    or died) and the loader could not self-heal within its budgets.
    ``quarantined`` carries the (index, error) pairs skipped so far."""

    def __init__(self, message, quarantined=()):
        self.quarantined = list(quarantined)
        if self.quarantined:
            message += (f"; {len(self.quarantined)} sample(s) were "
                        f"quarantined first: {self.quarantined}")
        super().__init__(message)


class _WorkerFailure:
    """A worker-side exception in transit to the consumer thread."""

    def __init__(self, exc, where):
        import traceback
        self.where = where
        self.exc = exc
        self.tb = traceback.format_exc()


_SKIPPED_BATCH = object()   # every sample of the batch was quarantined


def default_collate_fn(batch):
    """Stack samples into batch arrays (mirrors reference default_collate_fn)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch, axis=0)
    if isinstance(sample, Tensor):
        return np.stack([s.numpy() for s in batch], axis=0)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn(list(items))
                            for items in zip(*batch))
    raise TypeError(f"cannot collate {type(sample)}")


def default_convert_fn(batch):
    return batch


def _to_device(batch, to_tensor=True):
    import jax.numpy as jnp
    if not to_tensor:
        return batch
    if isinstance(batch, np.ndarray):
        return Tensor(jnp.asarray(batch))
    if isinstance(batch, dict):
        return {k: _to_device(v) for k, v in batch.items()}
    if isinstance(batch, (list, tuple)):
        return type(batch)(_to_device(v) for v in batch)
    return batch


class DevicePrefetcher:
    """Double-buffered device-feed prefetch (docs/PERF.md).

    A background thread pulls host batches from ``source``, uploads them
    (``jax.device_put`` dispatches async) and keeps up to ``depth``
    device-resident batches ready, so the consumer's ``next()`` — i.e. the
    accelerator's feed — never waits on host batch assembly + transfer.
    The inline double-buffer in ``DataLoader.__iter__`` only overlaps the
    upload dispatch; this moves the whole host side (sample fetch,
    collate, conversion) off the consumer thread.

    Failure contract matches the self-healing DataLoader: a raising source
    ships its exception to the consumer (``DataLoaderWorkerError``), the
    done sentinel posts from a ``finally``, and every consumer wait is
    watchdog-bounded. Abandoning the iterator (break / GC) stops the
    thread promptly via the bounded hand-off.
    """

    def __init__(self, source, depth=2, timeout=None, convert=None):
        self.source = source
        self.depth = max(int(depth), 1)
        if timeout is None:
            timeout = float(os.environ.get('PADDLE_TPU_DATA_TIMEOUT', '')
                            or _DEFAULT_WATCHDOG_S)
        self.timeout = timeout
        self._convert = convert if convert is not None else _to_device

    def __iter__(self):
        out_q = queue.Queue(maxsize=self.depth)
        done = object()
        stop = threading.Event()

        def worker():
            try:
                source = iter(self.source)
                for n in itertools.count():
                    with _obs.span('prefetch.source', batch=n):
                        batch = next(source, done)
                    if batch is done:
                        return
                    attrs = {'bytes': _host_bytes(batch)} \
                        if _obs.enabled() else {}
                    sw = _obs.Stopwatch()
                    with _obs.span('prefetch.convert', batch=n, **attrs):
                        item = self._convert(batch)
                    _note_slow('prefetch.convert', sw, n, out_q)
                    with _obs.span('prefetch.put_wait', batch=n):
                        _post(item)
                    if stop.is_set():
                        return
            except BaseException as e:
                _post(_WorkerFailure(e, 'device prefetch'))
            finally:
                _post(done)

        def _post(item):
            while not stop.is_set():
                try:
                    out_q.put(item, timeout=0.1)
                    return
                except queue.Full:
                    continue

        t = threading.Thread(target=worker, daemon=True,
                             name='paddle-tpu-device-prefetch')
        t.start()
        try:
            for n in itertools.count():
                # the depth the consumer FOUND: 0 means this get starved
                # (the step it feeds had to wait for its input)
                depth = out_q.qsize()
                attrs = {}
                if _obs.enabled():
                    _obs.gauge('dataloader.prefetch_depth').set(depth)
                    attrs = {
                        'depth': depth,
                        'gets': _obs.counter('prefetch.gets').inc(),
                        'starved': _obs.counter('prefetch.starved').inc(
                            1 if depth == 0 else 0)}
                sw = _obs.Stopwatch()
                with _obs.span('prefetch.get_wait', batch=n, **attrs):
                    batch = _watchdog.bounded_get(
                        out_q, timeout=self.timeout, alive=t.is_alive,
                        what='device prefetch batch')
                _note_slow('prefetch.get_wait', sw, n, out_q, depth)
                if batch is done:
                    return
                if isinstance(batch, _WorkerFailure):
                    raise DataLoaderWorkerError(
                        f"DataLoader device prefetch failed: "
                        f"{batch.exc!r}\n{batch.tb}")
                yield batch
        finally:
            stop.set()
            # bounded join: the worker exits within one 0.1s put tick of
            # stop; a worker wedged inside _convert just times the join
            # out (False) rather than hanging generator teardown
            _watchdog.join_thread(t, timeout=2.0)


# a prefetcher boundary slower than this is an anomaly worth a record in
# the always-on flight ring (a step's input is normally ready in ms)
_SLOW_S = 1.0


def _note_slow(what, sw, n, out_q, depth=None):
    """Flight-record a ``prefetch.convert`` / ``prefetch.get_wait`` that
    took over ``_SLOW_S``: which batch (the nth of this iterator, feeding
    the loop's nth step) and how deep the queue was."""
    seconds = sw.elapsed()
    if seconds > _SLOW_S:
        _obs.flight.record(
            'prefetch.slow', what=what, seconds=round(seconds, 3), step=n,
            depth=out_q.qsize() if depth is None else depth)


def _host_bytes(batch):
    """Bytes of a host batch's array leaves (the ``bytes`` a
    ``prefetch.convert`` span carries)."""
    if isinstance(batch, dict):
        batch = list(batch.values())
    if isinstance(batch, (list, tuple)):
        return sum(_host_bytes(v) for v in batch)
    if isinstance(batch, Tensor):
        batch = batch._value
    return int(getattr(batch, 'nbytes', 0))


def _env_prefetch_depth():
    """PADDLE_TPU_PREFETCH: '' / '0' off, '1' -> depth 2, N -> depth N."""
    raw = os.environ.get('PADDLE_TPU_PREFETCH', '')
    try:
        n = int(raw or 0)
    except ValueError:
        return 0
    return 2 if n == 1 else max(n, 0)


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False,
                 drop_last=False, collate_fn=None, num_workers=0,
                 use_buffer_reader=True, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, prefetch_factor=2,
                 persistent_workers=False, skip_bad_samples=None,
                 worker_max_restarts=None, prefetch_to_device=None):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = max(int(num_workers), 0)
        self.worker_init_fn = worker_init_fn
        self.prefetch_factor = max(int(prefetch_factor), 1)
        self.use_buffer_reader = use_buffer_reader
        self.use_shared_memory = use_shared_memory
        # fault-tolerance budgets (module docstring): watchdog wait, poison
        # quarantine, crashed-process-worker respawn. timeout=0 means
        # "unspecified" (env, then the 300s default); PADDLE_TPU_DATA_TIMEOUT=0
        # or a negative timeout= disables the deadline — consumer waits stay
        # liveness-probed but unbounded.
        if timeout:
            self.timeout = max(float(timeout), 0.0)
        else:
            self.timeout = float(
                os.environ.get('PADDLE_TPU_DATA_TIMEOUT', '')
                or _DEFAULT_WATCHDOG_S)
        if skip_bad_samples is None:
            skip_bad_samples = int(
                os.environ.get('PADDLE_TPU_DATA_SKIP_BUDGET', 0) or 0)
        self.skip_bad_samples = max(int(skip_bad_samples), 0)
        if worker_max_restarts is None:
            worker_max_restarts = int(
                os.environ.get('PADDLE_TPU_WORKER_RESTARTS', 2) or 0)
        self.worker_max_restarts = max(int(worker_max_restarts), 0)
        # device-feed prefetch (docs/PERF.md): None defers to
        # PADDLE_TPU_PREFETCH; an int is the prefetch depth (0 = off)
        if prefetch_to_device is None:
            self.prefetch_to_device = _env_prefetch_depth()
        elif prefetch_to_device is True:
            self.prefetch_to_device = 2
        else:
            self.prefetch_to_device = max(int(prefetch_to_device or 0), 0)
        self._quarantined = []       # (index, repr(exc)) of skipped samples
        self._q_lock = threading.Lock()
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            if batch_size is None:
                self.batch_sampler = None
                self.batch_size = None
            else:
                self.batch_sampler = BatchSampler(
                    dataset, shuffle=shuffle, batch_size=batch_size,
                    drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    # -- poison-sample quarantine ------------------------------------------

    def quarantine_report(self):
        """(index, error) pairs for every sample skipped under the
        ``skip_bad_samples`` budget, in the order they were quarantined."""
        with self._q_lock:
            return list(self._quarantined)

    def _quarantine(self, index, exc):
        """Record one poisoned sample. True when the budget covered it;
        False when the budget is exhausted (caller must fail)."""
        with self._q_lock:
            if len(self._quarantined) >= self.skip_bad_samples:
                return False
            self._quarantined.append((index, repr(exc)))
        if _obs.enabled():
            _obs.counter('dataloader.quarantined').inc()
            _obs.event('quarantine', index=index, error=repr(exc))
        return True

    def _fetch_samples(self, indices):
        """dataset[i] for each index, quarantining poisoned samples within
        budget. Returns (samples, None) or (None, _WorkerFailure)."""
        samples = []
        for i in indices:
            try:
                samples.append(self.dataset[i])
            except Exception as e:
                if not self._quarantine(i, e):
                    return None, _WorkerFailure(
                        e, f"dataset[{i}] (skip budget "
                           f"{self.skip_bad_samples} exhausted)")
        return samples, None

    def _raw_batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            batches = self.batch_sampler if self.batch_sampler is not None \
                else ([i] for i in range(len(self.dataset)))
            for indices in batches:
                samples, failure = self._fetch_samples(indices)
                if failure is not None:
                    raise DataLoaderWorkerError(
                        f"DataLoader failed in {failure.where}: "
                        f"{failure.exc!r}", self.quarantine_report()) \
                        from failure.exc
                if samples:     # skip a batch that was quarantined whole
                    yield self.collate_fn(samples)

    def _threaded_batches(self):
        """num_workers>0: worker threads build batches, main thread uploads.

        Failure contract: a worker that raises ships the exception to the
        consumer (re-raised as ``DataLoaderWorkerError``) and ALWAYS posts
        its done sentinel from a finally block — the silent-hang mode where
        a raising ``dataset[i]``/``collate_fn`` killed the thread and left
        the consumer blocked forever is structurally impossible. The
        consumer's queue wait is bounded (watchdog): dead workers are
        detected within a poll tick, hung workers within ``self.timeout``
        seconds."""
        if self._iterable_mode:
            yield from self._raw_batches()
            return
        indices_iter = iter(self.batch_sampler) if self.batch_sampler else \
            iter([[i] for i in range(len(self.dataset))])
        out_q = queue.Queue(maxsize=self.num_workers * self.prefetch_factor)
        lock = threading.Lock()
        seq = [0]
        pending = {}
        done = object()

        def worker(wid):
            try:
                if self.worker_init_fn:
                    self.worker_init_fn(wid)
                while True:
                    with lock:
                        try:
                            my_seq = seq[0]
                            indices = next(indices_iter)
                            seq[0] += 1
                        except StopIteration:
                            return
                    samples, failure = self._fetch_samples(indices)
                    if failure is not None:
                        out_q.put((my_seq, failure))
                        return
                    if not samples:     # whole batch quarantined
                        out_q.put((my_seq, _SKIPPED_BATCH))
                        continue
                    try:
                        batch = self.collate_fn(samples)
                    except Exception as e:
                        out_q.put((my_seq, _WorkerFailure(e, 'collate_fn')))
                        return
                    out_q.put((my_seq, batch))
            except BaseException as e:   # worker_init_fn, sampler, ...
                out_q.put((None, _WorkerFailure(e, 'worker')))
            finally:
                # the sentinel is unconditional: the consumer must never
                # wait on a thread that already died
                out_q.put((None, done))

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(self.num_workers)]
        for t in threads:
            t.start()

        def workers_alive():
            return any(t.is_alive() for t in threads)

        finished = 0
        next_seq = 0
        while finished < self.num_workers:
            if _obs.enabled():
                _obs.gauge('dataloader.queue_depth').set(out_q.qsize())
            try:
                s, batch = _watchdog.bounded_get(
                    out_q, timeout=self.timeout, alive=workers_alive,
                    what='DataLoader batch')
            except _watchdog.WatchdogTimeout as e:
                if _obs.enabled():
                    _obs.counter('dataloader.watchdog_timeouts').inc()
                    _obs.event('dataloader_watchdog', error=str(e))
                raise DataLoaderWorkerError(
                    f"DataLoader wedged: {e}", self.quarantine_report()) \
                    from e
            if batch is done:
                finished += 1
                continue
            if isinstance(batch, _WorkerFailure):
                raise DataLoaderWorkerError(
                    f"DataLoader worker failed in {batch.where}: "
                    f"{batch.exc!r}\n{batch.tb}", self.quarantine_report())
            pending[s] = batch
            while next_seq in pending:
                b = pending.pop(next_seq)
                next_seq += 1
                if b is not _SKIPPED_BATCH:
                    yield b
        while next_seq in pending:
            b = pending.pop(next_seq)
            next_seq += 1
            if b is not _SKIPPED_BATCH:
                yield b

    def _process_batches(self):
        """num_workers>0 + shared memory: fork()ed worker processes collate
        batches into the native shm prefetch ring (csrc/prefetch.cpp) — no
        pickling of array payloads. Falls back to the threaded path when the
        native lib is unavailable or batches are not plain ndarray tuples.

        The pool self-heals: crashed workers are respawned (up to
        ``worker_max_restarts``) with their in-flight batch requeued,
        poisoned samples are quarantined through the shared budget, and a
        stall past the watchdog budget raises instead of hanging."""
        from .._native.process_pool import ProcessWorkerPool
        indices = list(self.batch_sampler) if self.batch_sampler is not None \
            else [[i] for i in range(len(self.dataset))]
        pool = ProcessWorkerPool(self.dataset, indices, self.collate_fn,
                                 self.num_workers,
                                 capacity=self.num_workers *
                                 self.prefetch_factor,
                                 worker_init_fn=self.worker_init_fn,
                                 max_restarts=self.worker_max_restarts,
                                 watchdog_timeout=self.timeout,
                                 quarantine=self._quarantine)
        yield from pool

    def _shm_compatible(self):
        """Process+shm transport handles flat tuples of numeric ndarrays
        (the hot path); dicts/strings/objects use the threaded path."""
        try:
            if self.batch_sampler is not None:
                it = iter(self.batch_sampler)
                first = next(it, None)
                if it is self.batch_sampler and first is not None:
                    # one-shot sampler (generator): the probe consumed its
                    # first batch — stitch it back so iteration sees it
                    import itertools
                    self.batch_sampler = itertools.chain([first], it)
            else:
                first = [0] if len(self.dataset) else None
            if first is None:
                return False
            batch = self.collate_fn([self.dataset[i] for i in first[:1]])
            items = batch if isinstance(batch, (list, tuple)) else [batch]
            import numpy as _np
            for a in items:
                a = _np.asarray(a)
                if a.dtype == object or a.dtype.kind in 'USV':
                    return False
            return True
        except Exception:
            return False

    def _parallel_batches(self):
        if self._iterable_mode or not self.use_shared_memory:
            return self._threaded_batches()
        try:
            from .._native import available as _native_ok
            import multiprocessing as mp
            if (_native_ok() and 'fork' in mp.get_all_start_methods()
                    and self._shm_compatible()):
                return self._process_batches()
        except Exception:
            pass
        return self._threaded_batches()

    # a single batch wait above this lands a streamed `input_stall` event
    # (the anomaly doctor's input-bound corroboration; the histogram alone
    # only shows up at snapshot time)
    _STALL_EVENT_MS = 1000.0

    def _timed(self, source):
        """Telemetry wrapper: how long the consumer waits for each host
        batch (assembly + collate stall the device would see)."""
        it = iter(source)
        while True:
            sw = _obs.Stopwatch()
            try:
                b = next(it)
            except StopIteration:
                return
            if _obs.enabled():
                wait_ms = sw.elapsed_ms()
                _obs.histogram('dataloader.next_wait_ms').observe(wait_ms)
                _obs.counter('dataloader.batches').inc()
                if wait_ms >= self._STALL_EVENT_MS:
                    _obs.counter('dataloader.stalls').inc()
                    _obs.event('input_stall', wait_ms=round(wait_ms, 1))
            yield b

    def __iter__(self):
        source = self._parallel_batches() if self.num_workers > 0 else \
            self._raw_batches()
        if self.prefetch_to_device:
            # background device-feed prefetch: the whole host side (sample
            # fetch + collate + upload) runs ahead of the consumer; _timed
            # wraps the OUTSIDE so dataloader.next_wait_ms measures the
            # wait the accelerator would actually see
            prefetched = DevicePrefetcher(source,
                                          depth=self.prefetch_to_device,
                                          timeout=self.timeout)
            if _obs.enabled():
                prefetched = self._timed(prefetched)
            yield from prefetched
            return
        if _obs.enabled():
            source = self._timed(source)
        if not self.use_buffer_reader:
            for b in source:
                yield _to_device(b)
            return
        # double-buffer: upload batch N+1 while N is being consumed
        it = iter(source)
        try:
            nxt = _to_device(next(it))
        except StopIteration:
            return
        for b in it:
            cur, nxt = nxt, _to_device(b)  # device_put dispatches async
            yield cur
        yield nxt

    @staticmethod
    def from_generator(feed_list=None, capacity=4, use_double_buffer=True,
                       iterable=True, return_list=True, use_multiprocess=False,
                       drop_last=True):
        """fluid-era generator loader."""
        return _GeneratorLoader(capacity, drop_last)

    @staticmethod
    def from_dataset(dataset, places=None, drop_last=True):
        return DataLoader(dataset, drop_last=drop_last)


class _GeneratorLoader:
    def __init__(self, capacity, drop_last):
        self._gen = None
        self.capacity = capacity

    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        from ..batch import batch as batch_reader
        self._gen = lambda: (default_collate_fn(b)
                             for b in batch_reader(reader, batch_size,
                                                   drop_last)())
        return self

    def set_sample_list_generator(self, reader, places=None):
        self._gen = lambda: (default_collate_fn(b) for b in reader())
        return self

    def set_batch_generator(self, reader, places=None):
        self._gen = lambda: iter(reader())
        return self

    def __iter__(self):
        for b in self._gen():
            yield _to_device(b)

    def __call__(self):
        return iter(self)
