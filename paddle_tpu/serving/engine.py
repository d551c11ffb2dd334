"""ServingEngine: multi-tenant inference on the warm program cache.

One engine serves many models. Each registered model gets a bounded
admission queue and a runner (``runners.py``); a single worker thread
round-robins the runners, so every pump is one bounded unit of work per
model — a flood on one tenant cannot starve another of scheduler
iterations (it can only fill its own queue and shed).

Registration adapters (all funnel into the two runner shapes):

- ``predict_fn=`` — a batched jnp callable, jit-wrapped here;
- ``layer=`` — an ``nn.Layer`` (e.g. ``jit.load``'s TranslatedLayer after
  re-save, or any eager model): wrapped in no-grad eval calls and
  jit-compiled; ``quantize='int8'`` first routes it through the ``slim``
  per-channel post-training quantization pass (``calib_data`` required);
- ``program=`` — a ``(program, feed_names, fetch_vars)`` triple from
  ``static.io.load_inference_model`` plus an Executor: batches run through
  ``Executor.run``, so the **Executor program cache** is the warm-program
  store (hits/misses already counted on the telemetry spine);
- ``predictor=`` — an ``inference.Predictor`` (portable export);
- ``generative=`` — a ``kv_cache.GenerativeSpec`` for continuous-batching
  decode over the **paged KV cache** (block tables + free-list allocator,
  prefix sharing, chunked prefill, speculative decoding via ``draft=``).

Drive it either with ``start()`` (background worker thread; clients block
on ``Endpoint.predict``) or synchronously with ``pump()`` /
``run_until_idle()`` for deterministic tests and benches.
"""
import threading

import numpy as np

from .. import observability as _obs
from ..resilience.watchdog import join_thread
from .admission import WeightedFairQueue, record_shed
from .paged_runner import PagedGenerativeRunner
from .runners import BatchRunner, _count
from .scheduler import (AdmissionQueue, PendingRequest, QueueFullError,
                        Request)

__all__ = ['ServingEngine', 'Endpoint', 'EngineDeadError']


class EngineDeadError(RuntimeError):
    """Submit/cancel on an engine that was ``kill()``-ed (or never
    started). Distinguishable from model errors so a router can classify
    it as replica death (fail over) rather than request failure."""

# Idle backstop only: submit() and stop() notify the condition, so the
# worker wakes immediately on new work — a long tick avoids 100 Hz busy
# polling in an idle daemon while still bounding any missed wakeup.
_IDLE_TICK = 0.5


class Endpoint:
    """Client-facing handle for one served model."""

    def __init__(self, engine, model):
        self._engine = engine
        self.model = model

    def submit(self, inputs, deadline_ms=None, max_new_tokens=None,
               tenant=None):
        """Enqueue one request -> ``PendingRequest``. Raises
        ``QueueFullError`` when the admission queue sheds it (429-style,
        including the tenant-quota flavor ``QuotaExceededError``),
        ``ValueError`` when inputs don't match the registered spec."""
        return self._engine.submit(self.model, inputs,
                                   deadline_ms=deadline_ms,
                                   max_new_tokens=max_new_tokens,
                                   tenant=tenant)

    def predict(self, inputs, deadline_ms=None, max_new_tokens=None,
                timeout=None, tenant=None):
        """Blocking one-call convenience: submit + result."""
        return self.submit(inputs, deadline_ms=deadline_ms,
                           max_new_tokens=max_new_tokens,
                           tenant=tenant).result(timeout=timeout)


class ServingEngine:
    def __init__(self, queue_capacity=256, default_deadline_ms=None,
                 tenants=None):
        """``tenants=`` attaches a ``serving.admission.TenantArbiter``:
        every model's queue becomes a ``WeightedFairQueue`` (deficit-
        round-robin pop order by tenant weight) and ``submit`` charges the
        tenant's token-bucket quota before the queue push — over-quota
        submits shed as ``QuotaExceededError`` (reason ``'quota'``)
        without ever touching the queue (docs/SERVING.md, "Tenancy +
        autoscaling")."""
        self.queue_capacity = int(queue_capacity)
        self.default_deadline_ms = default_deadline_ms
        self.tenants = tenants         # TenantArbiter or None
        self._models = {}              # name -> runner
        self._queues = {}              # name -> AdmissionQueue
        self._rr = []                  # round-robin order
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._thread = None
        self._stop = threading.Event()
        self._shed = 0
        self._shed_queue_full = 0      # real overload: offered > drained
        self._shed_page_exhaustion = 0  # memory pressure wearing a queue-
        self._shed_quota = 0           # full mask (doctor tells them apart)
        self._submitted = 0
        self._endpoint = None          # MetricsServer this engine owns
        self._own_sampler = False      # ring sampler this engine started
        self._killed = False           # chaos: abrupt death, see kill()

    # -- registration ---------------------------------------------------
    def register(self, name, predict_fn=None, layer=None, program=None,
                 executor=None, predictor=None, generative=None,
                 example=None, bucket_spec=None, quantize=None,
                 calib_data=None, default_max_new_tokens=32,
                 queue_capacity=None, jit_compile=True,
                 page_size=16, num_pages=None, max_concurrency=None,
                 draft=None, draft_k=4, prefix_cache=True, slo_ms=None,
                 slo_objective=0.99, artifact_dir=None):
        """Register one model under ``name``. Exactly one of
        ``predict_fn``/``layer``/``program``/``predictor``/``generative``
        must be given; one-shot kinds also need ``example`` (one request's
        inputs, no batch axis) to pin the closed shape set.

        Generative models decode over a **paged KV cache**
        (docs/SERVING.md "Paged KV cache"):
        ``page_size`` tokens per page, ``num_pages`` total (default:
        worst case — size it below that to realize the memory win),
        ``max_concurrency`` block-table rows (default
        ``spec.max_batch``), ``prefix_cache=`` hash-consed shared-prompt
        pages, and ``draft=``/``draft_k=`` speculative decoding (a small
        ``GenerativeSpec`` proposing ``draft_k`` tokens per verify
        step).

        ``slo_ms=`` declares this model's latency objective for the SLO
        tracker: ``slo_objective`` (default 0.99) of requests must
        complete OK within ``slo_ms`` end-to-end. Violations burn the
        error budget; the doctor's ``slo_burn`` detector fires when the
        burn rate crosses 1x (docs/OBSERVABILITY.md, "SLO tracking").

        ``artifact_dir=`` binds this model to a persistent compile-cache
        directory (``paddle_tpu.compilecache``): ``warmup()`` deserializes
        the model's AOT-serialized executables from it instead of
        compiling — a replica booted against a populated dir serves its
        first request with ``jax.compiles == 0`` — and a first boot
        populates it for the next one. Applies to every kind (predict_fn/
        layer models through the runner's jits, program= through the
        Executor's persistent tier, predictor= through the export's
        cached call path). Overrides the process-wide
        ``PADDLE_TPU_COMPILE_CACHE`` binding for this model's warmup
        (docs/SERVING.md, "AOT registration")."""
        given = [k for k, v in (('predict_fn', predict_fn), ('layer', layer),
                                ('program', program),
                                ('predictor', predictor),
                                ('generative', generative)) if v is not None]
        if len(given) != 1:
            raise ValueError(
                f"register({name!r}): give exactly one model kind, got "
                f"{given or 'none'}")
        if name in self._models:
            raise ValueError(f"register: model {name!r} already registered")
        if quantize is not None and layer is None:
            raise ValueError(
                f"register({name!r}): quantize= applies only to layer= "
                "models (slim PTQ rewrites the Layer); quantize the model "
                "before export for the other kinds")
        if generative is not None:
            bad = [k for k, v in (('example', example),
                                  ('bucket_spec', bucket_spec),
                                  ('calib_data', calib_data)) if v is not None]
            if bad:
                raise ValueError(
                    f"register({name!r}): {bad} do not apply to "
                    "generative= models — prompt buckets and batch size "
                    "come from the GenerativeSpec itself")
        else:
            paged_given = [k for k, v in (
                ('num_pages', num_pages), ('draft', draft),
                ('max_concurrency', max_concurrency)) if v is not None]
            if paged_given:
                raise ValueError(
                    f"register({name!r}): {paged_given} apply only to "
                    "generative= models (the paged KV cache)")
        if queue_capacity is not None and int(queue_capacity) < 1:
            raise ValueError(
                f"register({name!r}): queue_capacity must be >= 1, got "
                f"{queue_capacity!r}")
        if slo_ms is not None:
            from ..observability import slo as _slo
            _slo.set_objective(name, slo_ms, slo_objective)
        capacity = (self.queue_capacity if queue_capacity is None
                    else queue_capacity)
        if self.tenants is not None:
            queue = WeightedFairQueue(name, capacity, arbiter=self.tenants)
        else:
            queue = AdmissionQueue(name, capacity)
        if generative is not None:
            runner = PagedGenerativeRunner(
                name, queue, generative, page_size=page_size,
                num_pages=num_pages, max_concurrency=max_concurrency,
                draft=draft, draft_k=draft_k, prefix_cache=prefix_cache,
                default_max_new_tokens=default_max_new_tokens)
        else:
            if example is None:
                raise ValueError(
                    f"register({name!r}): one-shot models need example= "
                    "(one request's inputs, no batch axis) to fix the "
                    "compiled shape set")
            if predict_fn is not None:
                # jit_compile=False is for callables that are already
                # compiled (or host-side wrappers, e.g. faultinject
                # slow_model around a jitted fn)
                fn = predict_fn
            elif layer is not None:
                fn = self._layer_fn(name, layer, quantize, calib_data,
                                    example)
            elif predictor is not None:
                fn = self._predictor_fn(predictor)
                jit_compile = False    # export manages its own compilation
            else:
                fn = self._program_fn(name, program, executor)
                jit_compile = False    # Executor program cache owns it
            runner = BatchRunner(name, queue, fn, example,
                                 bucket_spec=bucket_spec,
                                 jit_compile=jit_compile)
        runner.artifact_dir = artifact_dir
        with self._cond:
            self._models[name] = runner
            self._queues[name] = queue
            self._rr.append(name)
        if _obs.enabled():
            _obs.gauge('serving.models').set(len(self._models))
        return Endpoint(self, name)

    def _layer_fn(self, name, layer, quantize, calib_data, example):
        import inspect
        from ..core.tensor import Tensor
        from ..core import autograd
        if quantize is not None:
            if quantize != 'int8':
                raise ValueError(
                    f"register({name!r}): quantize must be 'int8', "
                    f"got {quantize!r}")
            if calib_data is None:
                raise ValueError(
                    f"register({name!r}): quantize='int8' needs "
                    "calib_data= (iterable of input batches for the slim "
                    "PTQ calibration pass)")
            from ..slim import PostTrainingQuantization
            layer = PostTrainingQuantization(layer, calib_data).quantize()
        layer.eval()
        # Bind feeds to forward's parameters BY NAME: a dict has no
        # positional order, so multi-input layers whose feed names don't
        # match forward's parameter names must be registered through
        # predict_fn= (where the caller owns the binding) rather than be
        # silently miswired by an arbitrary key sort.
        if len(example) == 1:
            order = list(example)
        else:
            try:
                params = [
                    p.name for p in
                    inspect.signature(layer.forward).parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
            except (TypeError, ValueError):
                params = []
            if not set(example) <= set(params):
                raise ValueError(
                    f"register({name!r}): multi-input layer — feed names "
                    f"{sorted(example)} must match {type(layer).__name__}"
                    f".forward parameter names {params} so arguments bind "
                    "unambiguously; rename the feeds or register via "
                    "predict_fn= with explicit binding")
            order = [p for p in params if p in example]

        by_name = len(example) > 1

        def fn(feeds):
            with autograd.no_grad():
                # multi-input feeds bind BY NAME (checked above): a
                # positional call would hand e.g. attention_mask to the
                # parameter that follows input_ids in forward's signature
                if by_name:
                    out = layer(**{k: Tensor(feeds[k]) for k in order})
                else:
                    out = layer(Tensor(feeds[order[0]]))
            if isinstance(out, (tuple, list)):
                return type(out)(o._value if isinstance(o, Tensor) else o
                                 for o in out)
            return out._value if isinstance(out, Tensor) else out
        return fn

    def _predictor_fn(self, predictor):
        def fn(feeds):
            outs = predictor.run({k: np.asarray(v)
                                  for k, v in feeds.items()})
            return tuple(outs)
        return fn

    def _program_fn(self, name, program, executor):
        if executor is None:
            raise ValueError(
                f"register({name!r}): program= also needs executor=")
        try:
            prog, feed_names, fetch_vars = program
        except (TypeError, ValueError):
            raise ValueError(
                f"register({name!r}): program= expects the (program, "
                "feed_names, fetch_vars) triple load_inference_model "
                "returns") from None

        def fn(feeds):
            outs = executor.run(prog,
                                feed={k: np.asarray(v)
                                      for k, v in feeds.items()},
                                fetch_list=list(fetch_vars))
            return tuple(outs)
        return fn

    # -- client surface -------------------------------------------------
    def endpoint(self, name):
        if name not in self._models:
            raise KeyError(f"serving: no model {name!r} registered "
                           f"(have {sorted(self._models)})")
        return Endpoint(self, name)

    def has_model(self, name):
        return name in self._models

    def model_kind(self, name):
        """'generative' or 'batch' for a registered model (KeyError else)."""
        return self._models[name].kind

    def page_starved(self, model):
        """Is ``model``'s paged runner currently unable to allocate KV
        pages? Always False for non-paged models — a router health gate,
        mirrored in ``/healthz``."""
        runner = self._models.get(model)
        if runner is None:
            return False
        return bool(getattr(runner, 'page_starved', lambda: False)())

    def submit(self, model, inputs, deadline_ms=None, max_new_tokens=None,
               tenant=None):
        if self._killed:
            raise EngineDeadError(
                f"serving: engine is dead (killed) — request for "
                f"{model!r} refused")
        runner = self._models.get(model)
        if runner is None:
            raise KeyError(f"serving: no model {model!r} registered")
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        if max_new_tokens is not None and int(max_new_tokens) < 1:
            raise ValueError(
                f"serving: max_new_tokens must be >= 1, got "
                f"{max_new_tokens!r}")
        req = Request(model, inputs, deadline_ms=deadline_ms,
                      max_new_tokens=max_new_tokens, tenant=tenant)
        runner.validate(req)
        if self.tenants is not None:
            # quota gate at the front door, BEFORE the queue push: a shed
            # here never touches the queue, so the queue-full path below
            # can keep stamping its own reasons without masking 'quota'
            try:
                self.tenants.check(req.tenant, model)
            except QueueFullError as e:
                self._record_shed(req, e.reason)
                raise
        _count('serving.requests')
        if _obs.enabled():
            # open the request's async trace lane BEFORE the queue push:
            # the worker may pop, run, and emit the closing async_end
            # before this thread resumes — a begin after that would leave
            # Perfetto an unmatched lane. Everything the runners stamp
            # with this id (prefill chunks, decode iterations,
            # speculative verify) renders as ONE connected flow, closed
            # by finish_request's async_end (or the shed edge below).
            _obs.async_begin('request', req.id, cat='serving.request',
                             model=model, deadline_ms=deadline_ms,
                             tenant=req.tenant)
        try:
            self._queues[model].push(req)
        except QueueFullError as e:
            # attribute the shed: a queue that backed up behind a page-
            # starved runner is memory pressure, not traffic overload —
            # the doctor must not prescribe replicas for an OOM
            starved = getattr(runner, 'page_starved', lambda: False)()
            e.reason = 'page_exhaustion' if starved else 'queue_full'
            self._record_shed(req, e.reason, lane_open=True)
            raise
        with self._cond:
            self._submitted += 1
            if _obs.enabled():
                _obs.gauge('serving.queue_depth').set(
                    sum(len(q) for q in self._queues.values()))
            self._cond.notify_all()
        return PendingRequest(req, self.alive)

    def _record_shed(self, req, reason, lane_open=False):
        """Tally one shed (reason: queue_full / page_exhaustion / quota)
        under the lock, mirror to telemetry, attribute to the tenant."""
        with self._lock:
            # submit() runs on arbitrary client threads while the
            # endpoint's health probe reads these; += is a racy
            # read-modify-write without the lock
            self._shed += 1
            if reason == 'page_exhaustion':
                self._shed_page_exhaustion += 1
            elif reason == 'quota':
                self._shed_quota += 1
            else:
                self._shed_queue_full += 1
        _count('serving.shed')
        _count(f'serving.shed.{reason}')
        record_shed(req.tenant, reason)
        if _obs.enabled():
            _obs.event('serving.shed', model=req.model, request=req.id,
                       reason=reason, tenant=req.tenant)
            if lane_open:
                _obs.async_end('request', req.id, cat='serving.request',
                               status='shed', reason=reason)

    def cancel(self, pending):
        """Withdraw a still-queued request: it is removed from the
        admission queue and completed with status ``'cancelled'`` without
        ever running. Returns True on success, False when the worker
        already owns the request (it will run to completion; discard the
        answer). The router's hedge path uses this to reap the losing
        duplicate for free when it never reached a batch slot."""
        req = pending._req if isinstance(pending, PendingRequest) else pending
        queue = self._queues.get(req.model)
        if queue is None or not queue.remove(req):
            return False
        from .scheduler import STATUS_CANCELLED
        req.complete(STATUS_CANCELLED)
        _count('serving.cancelled')
        if _obs.enabled():
            _obs.event('serving.cancelled', model=req.model, request=req.id)
            _obs.async_end('request', req.id, cat='serving.request',
                           status='cancelled')
        return True

    def queued_count(self, model=None):
        """Requests admitted but not yet popped by a runner."""
        with self._lock:
            if model is not None:
                q = self._queues.get(model)
                return 0 if q is None else len(q)
            return sum(len(q) for q in self._queues.values())

    def resident_count(self, model=None):
        """Generative requests currently resident in KV batch slots
        (mid-decode). One-shot batches run synchronously inside a single
        pump, so they are never observed resident between pumps."""
        with self._lock:
            runners = ([self._models[model]] if model in self._models
                       else [] if model is not None
                       else list(self._models.values()))
        return sum(sum(1 for s in r.slots if s is not None)
                   for r in runners if r.kind == 'generative')

    # -- scheduler loop -------------------------------------------------
    def pump(self):
        """One scheduler iteration over every model (round-robin order).
        Returns True when any runner did work."""
        if self._killed:
            return False               # a dead replica does no work
        # snapshot under the lock: register() may grow these dicts from
        # another thread and iterating a resizing dict raises
        with self._lock:
            order = list(self._rr)
            if order:
                self._rr.append(self._rr.pop(0))
            runners = [self._models[n] for n in order]
            queues = list(self._queues.values())
        did = False
        for runner in runners:
            if runner.has_work():
                did = runner.step() or did
        if _obs.enabled():
            _obs.gauge('serving.queue_depth').set(
                sum(len(q) for q in queues))
            _obs.gauge('serving.active_slots').set(sum(
                sum(1 for s in r.slots if s is not None)
                for r in runners if r.kind == 'generative'))
        return did

    def run_until_idle(self, max_steps=100000):
        """Pump until no runner has work (manual-drive mode for tests and
        benches). Returns the number of iterations that did work."""
        steps = 0
        for _ in range(int(max_steps)):
            if not self.pump():
                if not any(r.has_work() for r in self._models.values()):
                    return steps
            else:
                steps += 1
        return steps

    def warmup(self):
        """Ready every registered model's closed shape set now, so the
        first real request never pays an XLA compile. Models registered
        with ``artifact_dir=`` (or a process-wide
        ``PADDLE_TPU_COMPILE_CACHE`` binding) deserialize their
        AOT-serialized executables instead of compiling them — and a
        first boot commits what it compiled for the next one. Returns
        {model: programs_readied}."""
        from .. import compilecache as _cc
        out = {}
        with _obs.timer('serving.warmup'):
            for name, runner in self._models.items():
                with _cc.use(getattr(runner, 'artifact_dir', None)):
                    out[name] = runner.warmup() \
                        if hasattr(runner, 'warmup') else 0
        return out

    def start(self):
        """Start the background worker thread (idempotent). A worker that
        died from an escaped exception (counted as serving.worker_crash)
        is replaced, not silently left dead. With telemetry enabled and
        ``PADDLE_TPU_TELEMETRY_HTTP`` set, the live ``/metrics`` +
        ``/healthz`` endpoint comes up alongside (mission control)."""
        # flight recorder: a serving worker that dies takes its black box
        # with it unless the crash hooks are in (always-on, telemetry or
        # not — threading.excepthook catches an escaped worker exception)
        _obs.flight.install_crash_hooks()
        if _obs.enabled():
            from ..observability import endpoint as _endpoint
            _endpoint.maybe_start_from_env(extra_health=self._health)
            # ring sampler: the doctor's trend detectors (page_leak,
            # latency_creep, qps_collapse) need timelines of this
            # engine's gauges/histograms, not just the last frame
            had = _obs.timeseries.active_sampler() is not None
            self._own_sampler = (_obs.timeseries.start_sampler() is not None
                                 and not had)
        with self._cond:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._worker, name='paddle-tpu-serving', daemon=True)
            self._thread.start()
        return self

    def start_endpoint(self, port=0, host=None):
        """Explicitly export this engine's live ``/metrics`` + ``/healthz``
        (``port=0`` picks a free port; binds 127.0.0.1 unless ``host`` or
        ``PADDLE_TPU_TELEMETRY_HTTP_HOST`` widens it). Returns the
        ``observability.MetricsServer``; ``stop()`` tears it down."""
        from ..observability.endpoint import MetricsServer
        if self._endpoint is None:
            self._endpoint = MetricsServer(
                host=host, port=port, extra_health=self._health).start()
        return self._endpoint

    def _health(self):
        """The serving slice of ``/healthz``."""
        with self._lock:
            queues = {n: len(q) for n, q in self._queues.items()}
        starved = {n: bool(getattr(r, 'page_starved', lambda: False)())
                   for n, r in self._models.items()}
        out = {'serving': {
            'worker_alive': self.alive(),
            'models': sorted(queues),
            'queue_depth': queues,
            'resident': self.resident_count(),
            'page_starved': starved,
            'submitted': self._submitted,
            'shed': self._shed,
        }}
        from ..observability import slo as _slo
        burns = _slo.burn_rates()
        if burns:
            out['serving']['slo_burn'] = burns
        return out

    def alive(self):
        if self._killed:
            return False
        return self._thread is not None and self._thread.is_alive()

    @property
    def killed(self):
        return self._killed

    def dispatchable(self):
        """Can this engine accept work and eventually run it? False once
        ``kill()``-ed, or once a started worker thread has died (crash).
        A never-started engine IS dispatchable — manual ``pump()`` mode —
        which is also why this is not ``alive()``: alive() answers "is the
        background worker running", dispatchable() answers "is this
        replica a valid dispatch target"."""
        if self._killed:
            return False
        with self._lock:
            t = self._thread
        return t is None or t.is_alive()

    def kill(self):
        """Chaos surface: die abruptly, the in-process analogue of a
        replica SIGKILL. Unlike ``stop()``, queued and resident requests
        are NOT completed — they are stranded exactly as a real crash
        strands them, so their clients' watchdog-bounded waits fire and a
        router above can observe the loss and re-dispatch. The worker
        thread (if any) exits on its next iteration; ``alive()`` is False
        immediately. Idempotent."""
        if self._killed:
            return
        self._killed = True
        with self._cond:
            self._stop.set()
            self._cond.notify_all()
        _count('serving.killed')
        if _obs.enabled():
            _obs.event('serving.killed',
                       queued=sum(len(q) for q in self._queues.values()))
        _obs.flight.record('serving.killed', models=sorted(self._models))

    def stop(self, timeout=10.0):
        """Stop the worker; queued AND in-flight (KV-slot-resident)
        requests are completed as errors rather than stranded (their
        clients' bounded waits would fire anyway, but a shaped answer —
        with any partial generative output — beats a timeout)."""
        with self._cond:
            self._stop.set()
            self._cond.notify_all()
            t = self._thread
        # Join BEFORE clearing _thread: alive() must stay True while the
        # worker finishes its current batch, or clients blocked in
        # result() race into a spurious "engine stopped" WatchdogTimeout
        # for a request that completes milliseconds later. A join timeout
        # must abort the shutdown — evicting KV slots under a live worker
        # would have two threads mutating runner state.
        if t is not None and not join_thread(t, timeout=timeout):
            from ..resilience.watchdog import WatchdogTimeout
            raise WatchdogTimeout(
                f"serving: worker thread still running {timeout:.1f}s "
                "after stop() — a batch is stuck; not evicting in-flight "
                "requests under a live worker", what='serving worker join',
                waited=timeout)
        with self._cond:
            self._thread = None
        from .runners import finish_request
        from .scheduler import STATUS_ERROR
        for name, runner in self._models.items():
            for req, outputs in runner.evict_in_flight():
                finish_request(
                    req, STATUS_ERROR, outputs,
                    error=RuntimeError(
                        f"serving: engine stopped with request {req.id} "
                        "mid-decode"))
        for name, q in self._queues.items():
            for req in q.drain():
                finish_request(
                    req, STATUS_ERROR,
                    error=RuntimeError(
                        f"serving: engine stopped before request "
                        f"{req.id} ran"))
        if self._endpoint is not None:
            self._endpoint.stop()
            self._endpoint = None
        from ..observability import endpoint as _endpoint
        _endpoint.detach_health(self._health)
        if self._own_sampler:
            sm = _obs.timeseries.active_sampler()
            if sm is not None:
                sm.sample_now()   # the engine's tail lands in the ring
            _obs.timeseries.stop_sampler()
            self._own_sampler = False

    def _worker(self):
        try:
            while not self._stop.is_set():
                did = self.pump()
                if not did:
                    with self._cond:
                        if self._stop.is_set():
                            break
                        has = any(r.has_work()
                                  for r in self._models.values())
                        if not has:
                            self._cond.wait(_IDLE_TICK)
        except BaseException as e:
            # Runners contain model errors, so nothing should escape pump();
            # if something does, leave a trace — a dead worker otherwise
            # looks like an idle engine while every client times out.
            _count('serving.worker_crash')
            if _obs.enabled():
                _obs.event('serving.worker_crash', error=repr(e))
            raise

    # -- introspection --------------------------------------------------
    def stats(self):
        from ..observability import slo as _slo
        out = {
            'submitted': self._submitted,
            'shed': self._shed,
            'shed_queue_full': self._shed_queue_full,
            'shed_page_exhaustion': self._shed_page_exhaustion,
            'shed_quota': self._shed_quota,
            'queue_depth': {n: len(q) for n, q in self._queues.items()},
            'models': {n: r.stats.as_dict()
                       for n, r in self._models.items()},
            'slo_burn': _slo.burn_rates(),
        }
        if self.tenants is not None:
            from .admission import tenant_stats
            out['tenants'] = {'policies': self.tenants.stats(),
                              'ledger': tenant_stats()}
        return out
