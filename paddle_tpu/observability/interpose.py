"""Interposed runtime counters: jit retraces/compiles and host transfers.

Two families of counters no library author has to remember to bump:

- **retrace/compile**: ``install_jax_hooks()`` registers two
  ``jax.monitoring`` listeners; every jaxpr trace and every backend
  compile anywhere in the process (Executor programs, hapi jit steps, bench
  loops, user code) increments ``jax.traces`` / ``jax.compiles`` and
  accumulates ``jax.compile_ms``. A growing ``jax.traces`` count on a
  steady-state loop is the retrace-storm signal GL004–GL006 lint for
  statically. Each phase of JAX's compile path is also one **span record**
  (``jax.trace`` / ``jax.lower`` / ``jax.backend`` / ``jax.cache_load``)
  under the program span that caused it: see ``_on_duration``.
- **host transfers**: the narrow host-boundary waists (``Tensor.numpy()``,
  ``Executor.run``'s fetch) call ``record_host_transfer(nbytes)``; the
  ``host_transfer.bytes`` counter is the "how much crosses PCIe/ICI per
  step" number the ROADMAP's serving goal needs.

Collectives report through ``record_collective(op, nbytes)`` from the eager
wrappers (inside a traced region the record happens once at trace time, so
counts there reflect compilations, not executions).
"""
import threading
import time

from . import registry, spans, state

__all__ = ['install_jax_hooks', 'remove_jax_hooks', 'record_host_transfer',
           'record_collective', 'summary']

_installed = [False]

# JAX's duration events of the compile path -> the span record each becomes
_PHASES = {
    '/jax/core/compile/jaxpr_trace_duration': 'jax.trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'jax.lower',
    '/jax/core/compile/backend_compile_duration': 'jax.backend',
    '/jax/compilation_cache/cache_retrieval_time_sec': 'jax.cache_load',
}
# the persistent cache's events -> (what a backend record says, the counter)
_CACHE_EVENTS = {
    '/jax/compilation_cache/cache_hits': ('hit', 'jax.cache_hits'),
    '/jax/compilation_cache/cache_misses': ('miss', 'jax.cache_misses'),
}
# what the persistent cache said inside the backend phase this thread is in:
# JAX fires the cache's events before the phase's own, on the same thread
_inside = threading.local()     # .cache: 'hit' | 'miss'; .load: (t0, t1)


def install_jax_hooks():
    """Register the jax.monitoring listeners once. Safe to call repeatedly;
    returns True when the hooks are (already) in place."""
    if _installed[0]:
        return True
    try:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    except Exception:
        return False
    _installed[0] = True
    return True


def remove_jax_hooks():
    """Unregister the listeners: with telemetry off JAX calls nothing of
    ours."""
    if not _installed[0]:
        return
    import jax
    jax.monitoring.unregister_event_duration_listener(_on_duration)
    jax.monitoring.unregister_event_listener(_on_event)
    _installed[0] = False


def _on_event(name, **kwargs):
    if name not in _CACHE_EVENTS or not state.enabled():
        return
    _inside.cache, counter = _CACHE_EVENTS[name]
    registry.counter(counter).inc()


def _on_duration(name, secs, **kwargs):
    """One ``ph: 'X'`` record a phase. JAX fires the event at the END of the
    phase, on the thread that did it: ``t1_ns`` is now, ``t0_ns`` that less
    the duration, ``parent`` the program span open on this thread
    (``engine.dispatch``, ``engine.init_state``, ``costs.capture``; None for
    an eager op or a caller's own ``jit``). A trace made inside another
    trace fires its own event and both records cover it: a reader takes the
    UNION of a name's intervals, never the sum."""
    phase = _PHASES.get(name)
    if phase is None or not state.enabled():
        return
    t1 = time.perf_counter_ns()
    t0 = t1 - int(secs * 1e9)
    if phase == 'jax.cache_load':
        _inside.load = (t0, t1)     # written under the backend record
        return
    args = {'fun_name': kwargs.get('fun_name')}
    load = None
    if phase == 'jax.trace':
        registry.counter('jax.traces').inc()
    elif phase == 'jax.backend':
        registry.counter('jax.compiles').inc()
        registry.counter('jax.compile_ms').inc(secs * 1e3)
        heard = vars(_inside)
        load = heard.pop('load', None)
        if 'cache' in heard:
            args['cache'] = heard.pop('cache')
    span_id = spans.record(phase, t0, t1, **args)
    if load is not None:
        spans.record('jax.cache_load', *load, parent=span_id,
                     fun_name=args['fun_name'])


def record_host_transfer(nbytes, kind='device_get'):
    """Count one device→host materialization of ``nbytes`` bytes."""
    if not state.enabled():
        return
    registry.counter('host_transfer.calls').inc()
    registry.counter('host_transfer.bytes').inc(int(nbytes))
    registry.counter(f'host_transfer.{kind}.bytes').inc(int(nbytes))


def record_collective(op, nbytes):
    """Count one collective launch of ``nbytes`` payload bytes."""
    if not state.enabled():
        return
    registry.counter(f'collective.{op}.calls').inc()
    registry.counter(f'collective.{op}.bytes').inc(int(nbytes))


def summary():
    """The headline interposed counters, for bench extras / train_end
    events: retraces (jaxpr traces), compiles, total compile ms,
    host-transfer traffic, and the fault-tolerance tallies (worker
    restarts, quarantined samples, watchdog/collective timeouts, rank
    failures/restarts) — a run that self-healed is not the same run as one
    that never faulted, and the record should say so."""
    snap = registry.snapshot()['counters']
    return {
        'jax_traces': snap.get('jax.traces', 0),
        'jax_compiles': snap.get('jax.compiles', 0),
        'jax_compile_ms': round(float(snap.get('jax.compile_ms', 0)), 3),
        'host_transfer_bytes': snap.get('host_transfer.bytes', 0),
        'host_transfer_calls': snap.get('host_transfer.calls', 0),
        'engine_steps': snap.get('engine.steps', 0),
        'engine_loss_fetch_bytes': snap.get(
            'host_transfer.engine.loss_fetch.bytes', 0),
        'worker_restarts': snap.get('dataloader.worker_restarts', 0),
        'quarantined_samples': snap.get('dataloader.quarantined', 0),
        'watchdog_timeouts': snap.get('dataloader.watchdog_timeouts', 0),
        'dist_timeouts': snap.get('distributed.timeouts', 0),
        'rank_failures': snap.get('distributed.rank_failures', 0),
        'rank_restarts': snap.get('distributed.rank_restarts', 0),
        'serving_requests': snap.get('serving.requests', 0),
        'serving_shed': snap.get('serving.shed', 0),
        'serving_shed_queue_full': snap.get('serving.shed.queue_full', 0),
        'serving_shed_page_exhaustion': snap.get(
            'serving.shed.page_exhaustion', 0),
        'serving_deadline_expired': snap.get('serving.deadline_expired', 0),
        'serving_kv_decode_stalls': snap.get('serving.kv.decode_stalls', 0),
        'serving_kv_prefill_stalls': snap.get(
            'serving.kv.prefill_stalls', 0),
        'serving_preemptions': snap.get('serving.preemptions', 0),
        'serving_prefix_hit_pages': snap.get(
            'serving.kv.prefix_hit_pages', 0),
        'serving_spec_proposed': snap.get('serving.spec.proposed', 0),
        'serving_spec_accepted': snap.get('serving.spec.accepted', 0),
        'cost_programs': snap.get('cost.programs', 0),
        'cost_captures': snap.get('cost.captures', 0),
        'slo_requests': snap.get('slo.requests_total', 0),
        'slo_violations': snap.get('slo.violations_total', 0),
    }
