"""Profiler. Parity: python/paddle/fluid/profiler.py.

TPU-first: wraps jax.profiler — traces go to TensorBoard-compatible xplane
dumps; scoped annotations map to TraceAnnotation.
"""
import contextlib
import cProfile
import io
import os
import pstats

import jax

__all__ = ['profiler', 'start_profiler', 'stop_profiler', 'profile_scope',
           'annotate', 'get_hlo']

_active = {'dir': None, 'py': None}


def start_profiler(state='All', tracer_option='Default',
                   log_dir='/tmp/paddle_tpu_profile'):
    from .. import observability as _obs
    try:
        jax.profiler.start_trace(log_dir)
        _active['dir'] = log_dir
        _obs.event('profiler.start_trace', log_dir=log_dir)
    except Exception as e:
        # device trace unavailable (or already running): cProfile fallback
        # still gives a host-side picture. stop_profiler clears BOTH states,
        # so a failed double-start cannot leak an enabled profile.
        _active['py'] = cProfile.Profile()
        _active['py'].enable()
        _obs.event('profiler.fallback_cprofile', error=repr(e))


def stop_profiler(sorted_key=None, profile_path='/tmp/profile'):
    """Stop profiling and print a sorted per-op time table (the reference
    profiler.py contract: sorted_key in calls/total/max/min/ave)."""
    if sorted_key not in _SORT_FIELD:
        raise ValueError(
            f"sorted_key must be one of "
            f"{sorted(k for k in _SORT_FIELD if isinstance(k, str))} or "
            f"None, got {sorted_key!r}")
    table = None
    if _active['dir'] is not None:
        jax.profiler.stop_trace()
        log_dir = _active['dir']
        _active['dir'] = None
        from .. import observability as _obs
        _obs.event('profiler.stop_trace', log_dir=log_dir)
        print(f"profile trace written to {log_dir}")
        table = _op_summary(log_dir, sorted_key)
        if table:
            print(table)
    # always clear a cProfile fallback too (a failed double-start can leave
    # one enabled alongside an active trace)
    if _active['py'] is not None:
        _active['py'].disable()
        s = io.StringIO()
        pstats.Stats(_active['py'], stream=s).sort_stats('cumulative') \
            .print_stats(30)
        print(s.getvalue())
        _active['py'] = None
    return table


_SORT_FIELD = {'total': 'total_ms', 'calls': 'calls', 'max': 'max_ms',
               'min': 'min_ms', 'ave': 'ave_ms', None: 'total_ms',
               'default': 'total_ms'}


def _op_summary(log_dir, sorted_key=None, limit=40):
    """Aggregate the xplane dump under log_dir into the reference-style
    per-op table string ('Event / Calls / Total / Max / Min / Ave')."""
    import glob
    from . import xplane
    paths = glob.glob(os.path.join(log_dir, '**', '*.xplane.pb'),
                      recursive=True)
    if not paths:
        return None
    # newest dump wins (each start/stop cycle writes a new timestamp dir)
    path = max(paths, key=os.path.getmtime)
    ops = xplane.op_table(path)
    if not ops:
        return None
    field = _SORT_FIELD.get(sorted_key, 'total_ms')
    rows = sorted(ops.items(), key=lambda kv: -kv[1][field])[:limit]
    width = max([len('Event')] + [len(k) for k, _ in rows])
    lines = [f"{'Event':<{width}}  {'Calls':>6} {'Total(ms)':>10} "
             f"{'Max(ms)':>9} {'Min(ms)':>9} {'Ave(ms)':>9}"]
    for op, a in rows:
        lines.append(
            f"{op:<{width}}  {a['calls']:>6} {a['total_ms']:>10.4f} "
            f"{a['max_ms']:>9.4f} {a['min_ms']:>9.4f} {a['ave_ms']:>9.4f}")
    return "\n".join(lines)


@contextlib.contextmanager
def profiler(state='All', sorted_key=None, profile_path='/tmp/profile',
             tracer_option='Default'):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


profile_scope = profiler


def annotate(name):
    """Named trace region. Shows up in the xplane/TensorBoard dump of any
    open profiler session (an observability span always enters
    ``jax.profiler.TraceAnnotation``) AND in the telemetry Chrome trace
    whenever ``PADDLE_TPU_TELEMETRY=1`` — one annotation, both viewers."""
    from .. import observability as _obs
    if not _obs.enabled():
        # nothing to record: the raw annotation the span would enter
        return jax.profiler.TraceAnnotation(name)
    return _obs.span(name)


def get_hlo(fn, *args, optimized=False):
    """Dump HLO for a jitted callable — debugging/tracing parity."""
    lowered = jax.jit(fn).lower(*args)
    if optimized:
        return lowered.compile().as_text()
    return lowered.as_text()


# -- utils-level Profiler wrapper (parity: python/paddle/utils/profiler.py:
# ProfilerOptions:26, Profiler:63, get_profiler:131) ----------------------
class ProfilerOptions:
    def __init__(self, options=None):
        self.options = {
            'state': 'All',
            'sorted_key': 'default',
            'tracer_level': 'Default',
            'batch_range': [0, 2 ** 31 - 1],
            'output_thread_detail': False,
            'profile_path': 'none',
            'timeline_path': 'none',
            'op_summary_path': 'none',
        }
        if options is not None:
            for key in self.options:
                if options.get(key, None) is not None:
                    self.options[key] = options[key]

    def with_state(self, state):
        self.options['state'] = state
        return self

    def __getitem__(self, name):
        if name not in self.options:
            raise ValueError(
                "ProfilerOptions does not have an option named %s." % name)
        value = self.options[name]
        return None if isinstance(value, str) and value == 'none' else value


_current_profiler = None


class Profiler:
    """Batch-range-aware profiler driver over start/stop_profiler (the
    reference's utils.Profiler contract: context manager + record_step)."""

    def __init__(self, enabled=True, options=None):
        self.profiler_options = (options if options is not None
                                 else ProfilerOptions())
        self.batch_id = 0
        self.enabled = enabled
        self._running = False

    def __enter__(self):
        global _current_profiler
        self.previous_profiler = _current_profiler
        _current_profiler = self
        if self.enabled and self.profiler_options['batch_range'][0] == 0:
            self.start()
        return self

    def __exit__(self, exception_type, exception_value, traceback):
        global _current_profiler
        _current_profiler = self.previous_profiler
        if self.enabled:
            self.stop()

    def start(self):
        if self.enabled and not self._running:
            start_profiler(state=self.profiler_options['state'],
                           tracer_option=self.profiler_options[
                               'tracer_level'])
            self._running = True

    def stop(self):
        if self.enabled and self._running:
            stop_profiler(
                # __getitem__ converts the 'none' sentinel to None for
                # sorted_key the same as every other option
                sorted_key=self.profiler_options['sorted_key'],
                profile_path=self.profiler_options['profile_path']
                or '/tmp/profile')
            self._running = False

    def reset(self):
        """The xplane trace has no in-flight reset: restart the window."""
        if self.enabled and self._running:
            self.stop()
            self.start()

    def record_step(self, change_profiler_status=True):
        if not self.enabled:
            return
        self.batch_id += 1
        if change_profiler_status:
            if self.batch_id == self.profiler_options['batch_range'][0]:
                self.reset() if self._running else self.start()
            if self.batch_id == self.profiler_options['batch_range'][1]:
                self.stop()


def get_profiler():
    global _current_profiler
    if _current_profiler is None:
        _current_profiler = Profiler()
    return _current_profiler


__all__ += ['Profiler', 'ProfilerOptions', 'get_profiler']
