"""Span tracer: Chrome trace-event JSON + jax.profiler bridge.

``span(name)`` times a host-side region and records a Chrome trace "complete"
event (``ph: "X"``, microsecond ``ts``/``dur``) into a bounded in-process
ring; ``dump_chrome_trace(path)`` writes the ring as a JSON array that
loads directly in Perfetto / chrome://tracing.

One clock: a record's ``t0_ns``/``t1_ns`` are ``time.perf_counter_ns()``
as it reads (no private epoch; ``ts`` is the same instant in microseconds),
the clock the benchmark's own spans use, so a reader cuts the program's
records to any window it timed itself. A record also carries ``span_id``,
``parent`` (the ``span_id`` of the span open around it on its thread, None
at top level) and ``step`` (the dispatch number, where the site knows one).
The ring keeps the NEWEST ``MAX_TRACE_EVENTS`` records: a long run loses
its oldest spans, counted by ``dropped()``, never its latest. ``record()``
writes an interval that was timed elsewhere (JAX's compile-path phases, the
package's import) as the same kind of record.

Besides synchronous spans, the buffer carries **async (flow) events** —
``async_begin``/``async_instant``/``async_end`` record nestable Chrome
async events (``ph: b/n/e``) sharing a ``cat`` + ``id`` pair, which
Perfetto renders as ONE connected lane spanning threads and time. The
serving engine threads each request's id through them so a request's
lifecycle (admitted → prefill chunks → decode iterations → speculative
verify → completion) reads as a single flow in the merged cluster trace
(docs/OBSERVABILITY.md, "Per-request traces").

Two disciplines keep the tracer honest on an async accelerator runtime:

- **device-trace bridging**: every span enters a
  ``jax.profiler.TraceAnnotation`` under its name (a span with ``step=``
  enters the per-step ``StepTraceAnnotation(..., step_num=step)``),
  telemetry on or off. The profiler's own switch makes that a no-op while
  no session is open, and puts the region on the host plane of the xplane
  — on the device trace's clock — while one is, whoever opened it
  (``jax.profiler.start_trace``, ``utils.profiler.start_profiler``).
- **sampled sync**: a span wrapping dispatched device work measures only
  host dispatch time unless it blocks. ``span(name, sync=value)`` calls
  ``jax.block_until_ready(value)`` on a *sampled* subset of occurrences (the
  1st and every ``PADDLE_TPU_TELEMETRY_SYNC_EVERY``-th per span name, default
  16) so timing never adds an unsampled host sync to the steady-state step.
  Synced occurrences carry ``args.synced: true`` so readers can tell real
  latencies from dispatch times.
"""
import collections
import itertools
import json
import os
import threading
import time

from . import state

__all__ = ['span', 'Span', 'record', 'dump_chrome_trace', 'trace_events',
           'async_begin', 'async_instant', 'async_end',
           'clear', 'dropped', 'MAX_TRACE_EVENTS']

MAX_TRACE_EVENTS = 65536

_lock = threading.Lock()
_events = collections.deque(maxlen=MAX_TRACE_EVENTS)
_dropped = [0]
_sync_counts = {}
_ids = itertools.count(1)
_open = threading.local()       # .stack: ids of this thread's open spans


def _should_sync(name):
    every = state.sync_every()
    if every <= 0:
        return False
    with _lock:
        n = _sync_counts.get(name, 0)
        _sync_counts[name] = n + 1
    return n % every == 0


def _append(ev):
    with _lock:
        if len(_events) == _events.maxlen:
            _dropped[0] += 1        # the ring drops its oldest record
        _events.append(ev)


def _complete(name, t0_ns, t1_ns, span_id, parent, step, args):
    """Append one ``ph: 'X'`` record."""
    ev = {'name': name, 'ph': 'X', 'ts': t0_ns / 1e3,
          'dur': (t1_ns - t0_ns) / 1e3, 'pid': os.getpid(),
          'tid': threading.get_ident(), 't0_ns': t0_ns, 't1_ns': t1_ns,
          'span_id': span_id, 'parent': parent, 'step': step}
    if args:
        ev['args'] = args
    _append(ev)


_OPEN = object()        # record(parent=): the span open on this thread


def record(name, t0_ns, t1_ns, parent=_OPEN, **args):
    """Write an interval timed elsewhere (``perf_counter_ns`` stamps) as a
    span record -> its ``span_id``, or None while telemetry is off.
    ``parent`` defaults to the span open on the calling thread: what caused
    the work."""
    if not state.enabled():
        return None
    if parent is _OPEN:
        stack = getattr(_open, 'stack', None)
        parent = stack[-1] if stack else None
    span_id = next(_ids)
    _complete(name, t0_ns, t1_ns, span_id, parent, None, args)
    return span_id


def _annotation(name, step):
    """The profiler's annotation for a span: a no-op object while no
    profiler session is open (None where jax cannot be imported)."""
    try:
        from jax import profiler
    except ImportError:
        return None
    if step is None:
        return profiler.TraceAnnotation(name)
    return profiler.StepTraceAnnotation(name, step_num=step)


class Span:
    """Reentrant-per-instance context manager; use via ``span(name, ...)``.

    ``step``: the dispatch number this span belongs to (kept on the record;
    the profiler bridge is then a ``StepTraceAnnotation``). ``annotation``:
    the name the region has in the profiler's trace where that differs from
    the record's. The bridge is independent of the telemetry switch; the
    record is only kept while telemetry is enabled.
    """

    __slots__ = ('name', 'sync', 'args', 'step', 'annotation', '_t0',
                 '_bridge', '_recording', '_id', '_parent')

    def __init__(self, name, sync=None, step=None, annotation=None, **attrs):
        self.name = name
        self.sync = sync
        self.step = step
        self.annotation = annotation
        self.args = dict(attrs) if attrs else None
        self._t0 = 0
        self._bridge = None
        self._recording = False
        self._id = self._parent = None

    def __enter__(self):
        self._recording = state.enabled()
        self._bridge = _annotation(self.annotation or self.name, self.step)
        if self._bridge is not None:
            self._bridge.__enter__()
        if self._recording:
            try:
                stack = _open.stack
            except AttributeError:
                stack = _open.stack = []
            self._parent = stack[-1] if stack else None
            self._id = next(_ids)
            stack.append(self._id)
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._recording:
            if self.sync is not None and exc_type is None and \
                    _should_sync(self.name):
                try:
                    import jax
                    # a callable defers capture to exit time, for values
                    # that only exist once the wrapped block ran
                    val = self.sync() if callable(self.sync) else self.sync
                    if val is not None:
                        jax.block_until_ready(val)
                        self.args = dict(self.args or {})
                        self.args['synced'] = True
                except Exception:
                    pass
            t1 = time.perf_counter_ns()
            stack = _open.stack
            if stack and stack[-1] == self._id:
                stack.pop()
            _complete(self.name, self._t0, t1, self._id, self._parent,
                      self.step, self.args)
        if self._bridge is not None:
            self._bridge.__exit__(exc_type, exc, tb)
            self._bridge = None
        return False


def span(name, sync=None, step=None, annotation=None, **attrs):
    """Context manager timing a named host region (see module docstring)."""
    return Span(name, sync=sync, step=step, annotation=annotation, **attrs)


def _record_async(ph, name, aid, cat, args):
    if not state.enabled():
        return
    ev = {'name': name, 'ph': ph, 'cat': cat, 'id': str(aid),
          'ts': time.perf_counter_ns() / 1e3, 'pid': os.getpid(),
          'tid': threading.get_ident()}
    if args:
        ev['args'] = args
    _append(ev)


def async_begin(name, aid, cat='async', **args):
    """Open one async lane: events sharing ``(cat, id)`` until the matching
    ``async_end`` render as a single connected flow in Perfetto."""
    _record_async('b', name, aid, cat, args or None)


def async_instant(name, aid, cat='async', **args):
    """A point milestone on an open async lane (``ph: 'n'``)."""
    _record_async('n', name, aid, cat, args or None)


def async_end(name, aid, cat='async', **args):
    _record_async('e', name, aid, cat, args or None)


def trace_events():
    with _lock:
        return list(_events)


def dropped():
    """Records the ring has dropped (its oldest) since the last clear()."""
    return _dropped[0]


def clear():
    with _lock:
        _events.clear()
        _sync_counts.clear()
        _dropped[0] = 0


def dump_chrome_trace(path):
    """Write buffered spans as a Chrome trace-event JSON array (loads in
    Perfetto / chrome://tracing). Returns the number of events written."""
    evs = trace_events()
    with open(path, 'w', encoding='utf-8') as f:
        json.dump(evs, f)
    return len(evs)
