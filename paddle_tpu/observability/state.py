"""Telemetry runtime state: the process-wide on/off switch and knobs.

Stdlib-only and import-cycle-free on purpose: every instrumented narrow
waist (``core.tensor``, ``static.executor``, ``io.dataloader``, ...) imports
the observability package at module load, so nothing here may import jax or
any other ``paddle_tpu`` module at import time.

Env vars (read once at import; ``enable()``/``disable()`` override):

- ``PADDLE_TPU_TELEMETRY=1``       turn telemetry on for the process
- ``PADDLE_TPU_TELEMETRY_DIR``     where exporters write events.jsonl /
                                   trace.json (default /tmp/paddle_tpu_telemetry)
- ``PADDLE_TPU_TELEMETRY_SYNC_EVERY``
                                   sampled block_until_ready cadence for
                                   spans carrying device values: sample the
                                   1st and every Nth occurrence of a span
                                   name (default 16; 0 disables syncing)

Mission-control knobs (docs/OBSERVABILITY.md, "Mission control"):

- ``PADDLE_TPU_TELEMETRY_HTTP``    port for the live ``/metrics`` +
                                   ``/healthz`` endpoint (0 = pick a free
                                   port; unset/empty = no endpoint)
- ``PADDLE_TPU_TELEMETRY_HTTP_HOST``
                                   bind address (default 127.0.0.1 — the
                                   endpoint is diagnostics, not a public
                                   service; bind wider explicitly)
- ``PADDLE_TPU_TELEMETRY_FLUSH_EVERY``
                                   per-rank flush cadence in seconds for
                                   the cross-rank files (default 1.0)
- ``PADDLE_TPU_TELEMETRY_RUN_DIR`` cluster run dir for per-rank telemetry
                                   files (default: the supervisor's run
                                   dir, passed via heartbeat env)
- ``PADDLE_TPU_HEARTBEAT_DIR``     that default: ``distributed/launch.py``
                                   sets it for every supervised rank

Time-series knobs (owned by ``timeseries.py``, docs/OBSERVABILITY.md,
"Time series"):

- ``PADDLE_TPU_TELEMETRY_SAMPLE_EVERY``
                                   ring-sampler cadence in seconds for the
                                   in-run counter/gauge/histogram time
                                   series (default 1.0; 0 disables the
                                   sampler; off with telemetry off)
- ``PADDLE_TPU_TELEMETRY_TIMESERIES_CAP``
                                   ring capacity in samples (default 512 —
                                   ~8.5 min at the default cadence; memory
                                   stays O(cap) over arbitrarily long runs)

Cost explorer / SLO / flight-recorder knobs (owned by ``costs.py`` /
``slo.py`` / ``flight.py``, catalogued here so one file documents the env
surface):

- ``PADDLE_TPU_HBM_BUDGET``        device memory budget in bytes for the
                                   doctor's memory_pressure detector
- ``PADDLE_TPU_SLO_MS`` / ``PADDLE_TPU_SLO_OBJECTIVE``
                                   default per-model latency SLO
- ``PADDLE_TPU_FLIGHT=0``          disable the always-on flight recorder
- ``PADDLE_TPU_FLIGHT_EVENTS``     flight ring capacity (default 512)
- ``PADDLE_TPU_FLIGHT_DIR``        where crash dumps land
"""
import os
import threading
import time

_DEFAULT_DIR = '/tmp/paddle_tpu_telemetry'


def _env_int(name, default):
    try:
        return int(os.environ.get(name, '') or default)
    except ValueError:
        return default


def _env_float(name, default):
    try:
        return float(os.environ.get(name, '') or default)
    except ValueError:
        return default


class _State:
    def __init__(self):
        self.enabled = os.environ.get('PADDLE_TPU_TELEMETRY', '') == '1'
        self.log_dir = os.environ.get('PADDLE_TPU_TELEMETRY_DIR',
                                      _DEFAULT_DIR)
        self.sync_every = _env_int('PADDLE_TPU_TELEMETRY_SYNC_EVERY', 16)
        self.lock = threading.Lock()
        # (t0_ns, t1_ns) of `import paddle_tpu`, until it is a span record
        self.import_ns = None


_STATE = _State()


def enabled():
    """Cheap hot-path guard; every instrumentation site checks this first."""
    return _STATE.enabled


def enable(log_dir=None, sync_every=None):
    """Turn telemetry on (also installs the jax compile/retrace hooks)."""
    if log_dir is not None:
        _STATE.log_dir = log_dir
    if sync_every is not None:
        _STATE.sync_every = int(sync_every)
    _STATE.enabled = True
    from . import interpose
    interpose.install_jax_hooks()
    _record_import()


def note_import(t0_ns):
    """``paddle_tpu/__init__.py`` calls this as its last statement with the
    ``perf_counter_ns()`` reading of its first: the package's import
    precedes any ``enable()``, so its span is kept here and written as the
    record ``paddle_tpu.import`` when telemetry first comes on."""
    _STATE.import_ns = (t0_ns, time.perf_counter_ns())
    if _STATE.enabled:      # PADDLE_TPU_TELEMETRY=1: on since before it
        _record_import()


def _record_import():
    stamps, _STATE.import_ns = _STATE.import_ns, None
    if stamps is not None:
        from . import spans
        spans.record('paddle_tpu.import', *stamps, parent=None)


def disable():
    """Turn telemetry off and take the jax hooks out again."""
    _STATE.enabled = False
    from . import interpose
    interpose.remove_jax_hooks()


def log_dir():
    return _STATE.log_dir


def sync_every():
    return _STATE.sync_every


# -- mission-control knobs (read live: the supervisor sets the run-dir env
# for its children after this module was first imported) -------------------

def http_port():
    """Requested endpoint port, or None when no endpoint was asked for.
    0 means "pick a free port" (the server reports the bound one)."""
    raw = os.environ.get('PADDLE_TPU_TELEMETRY_HTTP', '')
    if raw == '':
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def http_host():
    return os.environ.get('PADDLE_TPU_TELEMETRY_HTTP_HOST', '127.0.0.1')


def flush_every():
    return _env_float('PADDLE_TPU_TELEMETRY_FLUSH_EVERY', 1.0)


def sample_every():
    """Time-series sampler cadence in seconds (0 disables the sampler)."""
    return _env_float('PADDLE_TPU_TELEMETRY_SAMPLE_EVERY', 1.0)


def timeseries_cap():
    """Ring capacity (samples) for the in-run time series."""
    return max(2, _env_int('PADDLE_TPU_TELEMETRY_TIMESERIES_CAP', 512))


def run_dir():
    """Cluster run dir for per-rank telemetry files: the explicit override,
    else the supervisor's heartbeat dir (set for every supervised rank),
    else None (not part of a cluster run)."""
    return (os.environ.get('PADDLE_TPU_TELEMETRY_RUN_DIR')
            or os.environ.get('PADDLE_TPU_HEARTBEAT_DIR') or None)


def rank_id():
    """This process's rank in the cluster (0 in a single-process run) —
    the ONE definition of the per-rank file-naming identity, shared by the
    flusher (telemetry_rank<R>.json) and the flight recorder
    (flight_rank<R>.json)."""
    try:
        return int(os.environ.get('PADDLE_TRAINER_ID', '0') or 0)
    except ValueError:
        return 0
