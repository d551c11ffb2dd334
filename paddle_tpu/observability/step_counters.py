"""Counters that are values of a compiled train step.

Some of a layer's counts exist only on the device: how many of a step's
routing assignments landed on the experts this chip holds, the rows of its
busiest expert. The step returns them beside its loss (the net names them:
``step_counter_names``) and ``engine.TrainStep`` hands each dispatch's
device array to :func:`push`, which does NOT wait for it: the array joins a
queue, and a later push (or :func:`drain`) records those that have become
ready meanwhile. A record is a zero-length ``engine.step_counters`` span
stamped with its step's dispatch time, the values in ``args``; the registry
keeps the newest value of each name as a gauge and, for the names that add
up (``sums``), a running counter. Nothing here syncs with the device unless
a reader asks ``drain(wait=True)``.
"""
import collections
import os
import threading
import time

from . import registry, spans, state

__all__ = ['push', 'drain', 'clear', 'SPAN']

SPAN = 'engine.step_counters'
_lock = threading.Lock()
_queue = collections.deque()


def push(step, names, values, sums=()):
    """Queue one dispatch's counters (a device array, one value per name)
    and record the earlier ones that are ready."""
    if not state.enabled():
        return
    with _lock:
        _queue.append((step, tuple(names), values, frozenset(sums),
                       time.perf_counter_ns()))
    drain()


def drain(wait=False):
    """Record the queued counters whose values are ready, oldest first,
    stopping at the first that is not; ``wait=True`` blocks for every one
    (a reader's call, after the measured window). Returns how many were
    recorded."""
    import numpy as np
    done = 0
    while True:
        with _lock:
            if not _queue:
                return done
            ready = getattr(_queue[0][2], 'is_ready', None)
            if not wait and ready is not None and not ready():
                return done
            step, names, values, sums, t_ns = _queue.popleft()
        numbers = [float(v) for v in np.asarray(values).reshape(-1)]
        args = dict(zip(names, numbers))
        for name, value in args.items():
            registry.gauge(name).set(value)
            if name in sums:
                registry.counter(name + '.total').inc(value)
        spans._append({'name': SPAN, 'ph': 'X', 'ts': t_ns / 1e3, 'dur': 0.0,
                       'pid': os.getpid(), 'tid': threading.get_ident(),
                       't0_ns': t_ns, 't1_ns': t_ns, 'span_id': None,
                       'parent': None, 'step': step, 'args': args})
        done += 1


def clear():
    with _lock:
        _queue.clear()
