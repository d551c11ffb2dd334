"""paddle_tpu.observability: one telemetry spine for the whole runtime.

Three cooperating pieces (docs/OBSERVABILITY.md has the operator guide):

- a process-wide **metrics registry** (``counter``/``gauge``/``histogram``)
  with JSONL **step-event** export and Prometheus-style text exposition;
- a **span tracer** emitting Chrome trace-event JSON (Perfetto-loadable)
  that bridges into ``jax.profiler.TraceAnnotation`` while a device trace is
  active, with a sampled ``block_until_ready`` discipline;
- **interposed counters** for jit retraces/compiles (via ``jax.monitoring``)
  and host-transfer bytes (``Tensor.numpy()``, Executor fetches).

Built-in instrumentation rides the narrow waists: ``Executor.run`` (program
cache, verify/compile time), ``hapi.Model.fit`` (``TelemetryCallback``),
``io.DataLoader`` / ``reader.buffered`` (queue depth, wait time),
``optimizer.step``, the resilience layer (NaN skips, retries, checkpoint
durations), and ``distributed.collective``.

MISSION CONTROL layers cluster-wide operation on the same spine
(docs/OBSERVABILITY.md, "Mission control"): per-rank telemetry flushed
live into the supervisor's run dir (``flush``), merged into one cluster
snapshot + a one-lane-per-rank Perfetto trace (``aggregate``), served over
a localhost HTTP endpoint — ``/metrics`` Prometheus exposition,
``/healthz``, ``/events``, ``/diagnosis`` (``endpoint``) — and diagnosed
by streaming anomaly detectors that name stragglers, retrace storms,
input-bound runs, and serving overload with fix-it hints (``doctor``).

Everything is off (near-zero overhead: one flag check per site) until
``PADDLE_TPU_TELEMETRY=1`` or an explicit ``observability.enable()``.

This package is imported by ``core.tensor`` at interpreter start: modules
here must stay stdlib-only at import time (jax strictly lazy) and must not
import other ``paddle_tpu`` modules at the top level.
"""
from . import events as _events
from . import interpose, registry, spans, state, timing  # noqa: F401
from . import aggregate, doctor, endpoint, flush  # noqa: F401  mission ctl
from . import costs, flight, slo  # noqa: F401  cost explorer + black box
from . import timeseries  # noqa: F401  in-run time series
from . import step_counters  # noqa: F401  values of the compiled step
from .state import enable, disable, enabled, log_dir, sync_every
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       get_registry, counter, gauge, histogram, snapshot,
                       to_prometheus)
from .registry import reset as reset_metrics
from .spans import (span, Span, dump_chrome_trace, trace_events,
                    async_begin, async_instant, async_end)
from .timing import Stopwatch, timer
from .interpose import (install_jax_hooks, record_host_transfer,
                        record_collective)
from .interpose import summary as counters_summary
from .flush import start_rank_flusher, stop_rank_flusher
from .endpoint import MetricsServer
from .doctor import diagnose, run_doctor

# event-log surface (module name 'events' is kept for the submodule; the
# buffered-event accessor is exported as event_log to avoid shadowing it)
event = _events.emit
event_log = _events.events
dump_jsonl = _events.dump_jsonl
set_sink = _events.set_sink
close_sink = _events.close_sink
wall_ts = _events.wall_ts

__all__ = [
    'enable', 'disable', 'enabled', 'log_dir', 'sync_every',
    'Counter', 'Gauge', 'Histogram', 'MetricsRegistry', 'get_registry',
    'counter', 'gauge', 'histogram', 'snapshot', 'to_prometheus',
    'reset_metrics', 'reset',
    'span', 'Span', 'dump_chrome_trace', 'trace_events',
    'async_begin', 'async_instant', 'async_end',
    'event', 'event_log', 'dump_jsonl', 'set_sink', 'close_sink', 'wall_ts',
    'Stopwatch', 'timer',
    'install_jax_hooks', 'record_host_transfer', 'record_collective',
    'counters_summary', 'TelemetryCallback',
    # mission control (docs/OBSERVABILITY.md, "Mission control")
    'aggregate', 'doctor', 'endpoint', 'flush',
    'start_rank_flusher', 'stop_rank_flusher', 'MetricsServer',
    'diagnose', 'run_doctor',
    # cost explorer + SLO tracker + flight recorder
    'costs', 'slo', 'flight',
    # in-run time series
    'timeseries',
    # counters that are values of the compiled train step
    'step_counters',
]


def reset():
    """Clear every buffer (metrics, events, spans, cost ledger, SLO
    tallies, flight ring, time-series ring) — test isolation hook."""
    reset_metrics()
    _events.clear()
    spans.clear()
    costs.reset()
    slo.reset()
    flight.clear()
    timeseries.clear()
    step_counters.clear()


def __getattr__(name):
    # TelemetryCallback subclasses hapi.Callback; resolving it lazily keeps
    # this package importable from core.tensor before hapi exists.
    if name == 'TelemetryCallback':
        from .callback import TelemetryCallback
        return TelemetryCallback
    raise AttributeError(name)


# honor PADDLE_TPU_TELEMETRY=1 from the environment: state already read the
# flag; bring the jax hooks up with it
if enabled():
    install_jax_hooks()
