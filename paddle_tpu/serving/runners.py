"""Model runners: how one scheduler step turns queued requests into math.

Two execution shapes cover the inference surface:

- ``BatchRunner`` — one-shot predict models (classify/embed/score). Each
  engine step re-packs the queue into the smallest bucket that fits
  (dynamic batching): requests that arrived while the previous batch ran
  join the very next one. The batch callable is either ``jax.jit``-wrapped
  here (Layer / function models) or an ``Executor.run`` closure, in which
  case the Executor **program cache** is the warm-program store and its
  hit/miss counters are the cache telemetry.
- ``paged_runner.PagedGenerativeRunner`` — iteration-level continuous
  batching for decode over the paged KV cache (its own module; it shares
  ``_Stats``, ``_count`` and ``finish_request`` with this one).

Runners never block: ``step()`` does at most one batch / one decode
iteration and returns whether it did work; the engine's worker loop (or a
test's manual pump) drives it.
"""
import numpy as np
import jax
import jax.numpy as jnp

from .. import compilecache as _cc
from .. import observability as _obs
from .bucketing import BucketSpec, stack_examples
from .scheduler import STATUS_OK, STATUS_DEADLINE, STATUS_ERROR

__all__ = ['BatchRunner', 'finish_request']


def _count(name, n=1):
    if _obs.enabled():
        _obs.counter(name).inc(n)


def _observe(name, v):
    if _obs.enabled():
        _obs.histogram(name).observe(v)


def finish_request(req, status, outputs=None, error=None):
    """Complete a request and mirror the outcome onto the telemetry spine
    (latency/queue-wait histograms, a per-request event carrying the
    queue/prefill/decode breakdown, the SLO tracker's judgment, and the
    closing edge of the request's async trace lane)."""
    req.complete(status, outputs, error=error)
    resp = req.response
    _count('serving.completed')
    _count(f'serving.status.{status}')
    from ..observability import slo as _slo
    _slo.record(req.model, status, resp.latency_ms)
    from .admission import record_completion
    record_completion(req, status, resp.latency_ms)
    if _obs.enabled():
        _obs.histogram('serving.latency_ms').observe(resp.latency_ms)
        _obs.histogram('serving.queue_wait_ms').observe(resp.queue_ms)
        _obs.event('serving.request', model=req.model, status=status,
                   tenant=getattr(req, 'tenant', None) or 'default',
                   latency_ms=round(resp.latency_ms, 3),
                   queue_ms=round(resp.queue_ms, 3),
                   **{f'{k}_ms': round(v, 3)
                      for k, v in resp.breakdown.items()})
        _obs.async_end('request', req.id, cat='serving.request',
                       status=status,
                       latency_ms=round(resp.latency_ms, 3))


def _slice_outputs(outs, i):
    """Per-request view of batched outputs: slice leading axis ``i`` through
    dict/tuple/list structure."""
    if isinstance(outs, dict):
        return {k: _slice_outputs(v, i) for k, v in outs.items()}
    if isinstance(outs, (list, tuple)):
        return type(outs)(_slice_outputs(v, i) for v in outs)
    return np.asarray(outs)[i]


class _Stats:
    """Plain always-on tallies (telemetry mirrors them when enabled)."""

    def __init__(self):
        self.completed = 0
        self.expired = 0
        self.errors = 0
        self.batches = 0
        self.joins = 0
        self.leaves = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self._occ_sum = 0.0
        self._occ_n = 0

    def occupancy(self, frac):
        self._occ_sum += frac
        self._occ_n += 1
        _observe('serving.batch_occupancy', frac)

    def as_dict(self):
        return {
            'completed': self.completed, 'expired': self.expired,
            'errors': self.errors, 'batches': self.batches,
            'joins': self.joins, 'leaves': self.leaves,
            'decode_tokens': self.decode_tokens,
            'prefill_tokens': self.prefill_tokens,
            'mean_batch_occupancy': (
                round(self._occ_sum / self._occ_n, 4) if self._occ_n else 0.0),
        }


class BatchRunner:
    """Dynamic batching over a one-shot batched callable.

    ``batch_fn(feeds)`` takes ``{name: array [B, ...]}`` and returns an
    array / tuple / dict with leading batch axis. ``example`` (one request's
    inputs, no batch axis) pins the shape/dtype spec: submits that disagree
    are rejected at admission, warmup knows what zeros to fabricate, and
    the compiled shape set stays closed. ``jit_compile=False`` is for
    callables that already manage compilation (Executor programs,
    Predictor exports).
    """

    kind = 'batch'

    def __init__(self, name, queue, batch_fn, example, bucket_spec=None,
                 jit_compile=True):
        self.name = name
        self.queue = queue
        self.spec = bucket_spec or BucketSpec()
        self.example = {k: np.asarray(v) for k, v in example.items()}
        self._jitted = bool(jit_compile)
        # CachedJit = jax.jit + the persistent executable tier: warmup
        # against a bound artifact dir deserializes instead of compiling
        self._fn = _cc.CachedJit(batch_fn) if jit_compile else batch_fn
        self.stats = _Stats()

    def validate(self, req):
        missing = sorted(set(self.example) - set(req.inputs))
        if missing:
            raise ValueError(
                f"serving[{self.name}]: request missing inputs {missing}")
        for k, ex in self.example.items():
            a = np.asarray(req.inputs[k])
            if a.shape != ex.shape or a.dtype != ex.dtype:
                raise ValueError(
                    f"serving[{self.name}]: input {k!r} has shape/dtype "
                    f"{a.shape}/{a.dtype}, registered example is "
                    f"{ex.shape}/{ex.dtype} — serving shapes are a closed "
                    "set (see serving.bucketing); pad client-side or "
                    "register a matching model")

    def has_work(self):
        return len(self.queue) > 0

    def evict_in_flight(self):
        """-> [(request, partial_outputs)] for requests resident in the
        runner but no longer in the queue (engine shutdown). One-shot
        batches are synchronous inside step(), so nothing is resident."""
        return []

    def warmup(self):
        """Ready every bucket once with zero feeds: against a bound
        compilecache artifact dir this deserializes the bucket's AOT
        executable (zero compiles); otherwise it compiles once — the only
        compiles a well-bucketed model ever pays. With telemetry on, each
        bucket's program is cost-ledgered either way (Executor-backed
        models are ledgered by the Executor itself at its cache miss)."""
        for b in self.spec.batch_buckets:
            feeds = {k: jnp.asarray(np.zeros((b,) + ex.shape, ex.dtype))
                     for k, ex in self.example.items()}
            if self._jitted:
                out = self._fn.warm(f'serving.{self.name}.b{b}', feeds,
                                    kind='serving.batch',
                                    meta={'model': self.name, 'bucket': b})
            else:
                out = self._fn(feeds)
            jax.tree_util.tree_map(lambda x: np.asarray(x), out)
        return len(self.spec.batch_buckets)

    def step(self):
        ready, expired = self.queue.pop_ready(self.spec.max_batch)
        for r in expired:
            self.stats.expired += 1
            _count('serving.deadline_expired')
            finish_request(r, STATUS_DEADLINE)
        if not ready:
            return bool(expired)
        bucket = self.spec.batch_bucket(len(ready))
        feeds = {k: jnp.asarray(
                     stack_examples([r.inputs[k] for r in ready], bucket))
                 for k in self.example}
        self.stats.batches += 1
        _count('serving.batches')
        self.stats.occupancy(len(ready) / bucket)
        telemetry = _obs.enabled()
        if telemetry:
            for r in ready:
                _obs.async_instant('batch', r.id, cat='serving.request',
                                   bucket=bucket, n=len(ready))
        try:
            with _obs.timer('serving.batch', model=self.name,
                            batch=len(ready), bucket=bucket) as t:
                outs = self._fn(feeds)
            outs = jax.tree_util.tree_map(np.asarray, outs)
            for r in ready:
                r.add_phase_ms('run', t.elapsed_ms)
            # slice before completing anything: a malformed output (e.g. no
            # leading batch axis) must fail the whole batch, not the engine
            per_req = [_slice_outputs(outs, i) for i in range(len(ready))]
        except Exception as e:                       # model bug: fail the
            for r in ready:                          # batch, not the engine
                self.stats.errors += 1
                finish_request(r, STATUS_ERROR, error=e)
            return True
        for r, out in zip(ready, per_req):
            self.stats.completed += 1
            status = STATUS_DEADLINE if r.expired() else STATUS_OK
            if status == STATUS_DEADLINE:
                self.stats.expired += 1
                _count('serving.deadline_expired')
            finish_request(r, status, out)
        return True
