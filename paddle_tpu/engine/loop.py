"""The eager convenience loop over the unified step builder.

Users with an eager ``nn.Layer`` + loss + Optimizer and a batch iterable
get the whole zero-stall fast path in one call::

    report = engine.fit(net, loss_fn, opt, loader, epochs=2, microbatch=4)

Under the hood this is exactly the same compiled step hapi
``Model.fit(jit=True)`` and the static ``Executor`` train path run —
``build_train_step`` with buffer donation, the in-graph NaN guard, AMP
folded in, and ``lax.scan`` microbatching — fed through the DataLoader
device prefetcher so batch assembly overlaps compute. Losses stay
on-device and are fetched at ``log_every`` cadence only.
"""
import functools

import numpy as np
import jax.numpy as jnp

from .. import observability as _obs
from .builder import build_train_step

__all__ = ['fit', 'write_back_state', 'adopt_optimizer_state']


def adopt_optimizer_state(network, optimizer, param_values):
    """Functional opt-state seeded from the optimizer's eager accumulators
    (``set_state_dict`` on resume) instead of fresh zeros — a compiled
    resume must continue Adam/Momentum moments exactly like eager does."""
    opt_state = optimizer.init_state_values(param_values)
    acc = optimizer._accumulators
    name_of = {k: (p.name or str(id(p)))
               for k, p in network.named_parameters()}
    for key in opt_state:
        nm = name_of.get(key)
        if nm in acc and acc[nm]:
            opt_state[key] = dict(acc[nm])
    return opt_state


def write_back_state(network, optimizer, state):
    """Mirror the functional state back into the eager world: params and
    buffers into the network, optimizer slots into the eager accumulators
    (so ``state_dict()``/checkpointing sees the live moments)."""
    from ..nn.layer_base import load_state_values
    load_state_values(network, state['params'])
    load_state_values(network, state['buffers'])
    if optimizer is not None and state.get('opt'):
        name_of = {k: (p.name or str(id(p)))
                   for k, p in network.named_parameters()}
        for key, slots in state['opt'].items():
            nm = name_of.get(key)
            if nm is not None and slots:
                optimizer._accumulators[nm] = dict(slots)


def _value_tuple(part):
    """A batch part (array / Tensor / list of either) as raw value tuple."""
    from ..core.tensor import Tensor
    items = part if isinstance(part, (list, tuple)) else [part]
    out = []
    for it in items:
        if isinstance(it, Tensor):
            out.append(it._value)
        else:
            out.append(np.asarray(it))
    return tuple(out)


def _split(batch):
    if isinstance(batch, (list, tuple)) and len(batch) >= 2:
        return _value_tuple(batch[0]), _value_tuple(batch[1])
    if isinstance(batch, (list, tuple)) and len(batch) == 1:
        return _value_tuple(batch[0]), ()
    return _value_tuple(batch), ()


def _grouped(data, k):
    """Yield host batches as ((bx, by), n_micro) groups: k==1 passes
    through; k>1 stacks k consecutive batches along a new leading axis
    (the lax.scan axis). An incomplete trailing group is dropped, like
    ``drop_last`` — a second compiled shape per epoch tail would defeat
    the one-program discipline."""
    if k == 1:
        for batch in data:
            yield _split(batch)
        return
    def shape_sig(parts):
        # np.shape reads .shape without materializing device arrays
        return tuple(np.shape(p) for p in parts)

    group = []
    dropped = 0
    canon = None
    for batch in data:
        bx, by = _split(batch)
        sig = (shape_sig(bx), shape_sig(by))
        if canon is None:
            canon = sig        # the ONE compiled shape (first batch wins)
        if sig != canon:
            # ragged member (e.g. a drop_last=False tail batch): stacking
            # would raise and a second compiled shape would retrace — drop
            # the odd batch, keep the group accumulating
            dropped += 1
            continue
        group.append((bx, by))
        if len(group) == k:
            # jnp.stack keeps device-resident members on device (a
            # DataLoader source yields uploaded batches — np.stack would
            # silently round-trip every one through the host)
            yield (tuple(jnp.stack([g[0][i] for g in group])
                         for i in range(len(group[0][0]))),
                   tuple(jnp.stack([g[1][i] for g in group])
                         for i in range(len(group[0][1]))))
            group = []
    dropped += len(group)
    if dropped:
        if _obs.enabled():
            _obs.counter('engine.dropped_batches').inc(dropped)
        import warnings
        warnings.warn(
            "engine.fit(microbatch=%d): dropped %d batch(es) whose shape "
            "differed from the first batch (one compiled shape per run) — "
            "pad/bucket your batches or use microbatch=1 if this is most "
            "of your data" % (k, dropped), RuntimeWarning, stacklevel=2)


def fit(network, loss, optimizer, data, *, epochs=1, microbatch=1,
        log_every=10, nan_guard=None, scaler=None, prefetch=2,
        remat=None, donate='auto', matmul_precision='auto', sharding=None,
        checkpoint=None, checkpoint_every=0, async_save=True,
        resume_from=None, preempt_save=True, checkpoint_max_keep=3,
        world=None, rank=None, serve_artifacts=None, serve_generative=None,
        on_step=None):
    """Train ``network`` over ``data`` through the unified compiled step.

    ``data``: a DataLoader or any iterable of ``(inputs, labels)`` batches
    (numpy arrays / Tensors, single or lists). ``prefetch``: depth of the
    background device-feed prefetcher (0/None disables). ``nan_guard``: a
    ``resilience.NanGuard`` (or True for a default one). Losses are
    fetched to host every ``log_every`` dispatches; guard/scaler host
    state reconciles on the same cadence (bounded by the guard's
    consecutive-skip limit). ``sharding``: a ``distributed.ShardingConfig``
    (or fleet ``DistributedStrategy``) — params/optimizer state shard over
    the mesh through the compiled step, feeds shard over the data axis
    (docs/PERF.md, "Sharded training").

    Checkpointing (docs/RESILIENCE.md, "Elastic training"):

    - ``checkpoint=``: a directory or ``resilience.CheckpointManager`` —
      the loop saves the whole functional state (params/buffers/opt/guard/
      scaler + RNG streams) every ``checkpoint_every`` dispatches (0 =
      epoch boundaries only) in the sharded format, following the step's
      sharding config when one is set; ``async_save=True`` commits on a
      background thread so the training thread's save stall is ~0
      (``checkpoint.save_stall_ms`` proves it).
    - ``resume_from=`` (defaults to ``checkpoint=``): restore the newest
      non-corrupt checkpoint — saved on ANY mesh shape — onto this run's
      mesh (resharding restore), replay the loop position, and continue
      bitwise-identically to an uninterrupted run (deterministic ``data``
      iteration assumed).
    - ``preempt_save=True``: a SIGTERM (fleet preemption) is caught at the
      next dispatch boundary; any in-flight async save is fenced (finished
      or cleanly abandoned) FIRST, then a final synchronous checkpoint
      commits and the loop stops with ``report['preempted'] = True``.
    - ``world=``/``rank=``: multi-process elastic jobs — each rank writes
      only its checkpoint shard; rank 0 commits the manifest after the
      shard barrier.

    Train→serve warm handoff (docs/SERVING.md, "AOT registration"):

    - ``serve_artifacts=``: a directory — after the final epoch, the loop
      AOT-compiles + serializes the trained network's eval/infer program
      at the training batch shapes into it (``paddle_tpu.compilecache``
      format), so a serving replica registering against that dir boots
      with zero compiles.
    - ``serve_generative=``: a ``serving.GenerativeSpec`` (wrapping the
      trained weights), or ``(name, spec)`` — additionally exports the
      paged serving tier's whole closed program set (chunked-prefill
      buckets, decode, and the speculative draft/verify set when the spec
      carries one) into the same dir. Cache keys embed the model name:
      the serving replica must ``register(name, ...)`` under the same one
      (a bare spec exports as ``'model'``). A preempted run skips the
      export (the artifact dir only ever holds programs a completed run
      stands behind).

    ``on_step=``: a callable ``on_step(i, result)`` handed every dispatch's
    ``StepResult`` once, in order, on the training thread, ONE BEHIND the
    dispatch: after dispatching step ``i`` the loop hands over step
    ``i - 1`` (its successor is already queued, so waiting on
    ``result.loss.raw`` there times step ``i - 1``'s completion without
    idling the device), and the last one after the loop. ``i`` counts this
    call's dispatches from 0. It cannot change the training.

    Returns a report dict: floated losses at log cadence, step counts,
    steps/sec, and the final functional state (already written back into
    ``network``/``optimizer``); with ``serve_artifacts=`` also a
    ``serve_artifacts`` entry naming the dir and exported program count.
    """
    from ..core import rng as _rng
    from ..nn.layer_base import buffer_values, param_values
    if nan_guard is True:
        from ..resilience import NanGuard
        nan_guard = NanGuard()
    if nan_guard is not None and scaler is not None:
        nan_guard.attach_scaler(scaler)
    step = build_train_step(net=network, loss=loss, optimizer=optimizer,
                            scaler=scaler, nan_guard=nan_guard is not None,
                            microbatch=microbatch, donate=donate,
                            remat=remat, matmul_precision=matmul_precision,
                            sharding=sharding)
    network.train()
    pv = param_values(network)
    state = step.init_state(
        pv, buffer_values(network),
        opt_state=adopt_optimizer_state(network, optimizer, pv),
        nan_guard=nan_guard, scaler=scaler)
    k = step.k

    mgr = _to_manager(checkpoint, checkpoint_max_keep)
    resume_mgr = _to_manager(resume_from, checkpoint_max_keep) or mgr
    start_epoch = skip_dispatches = 0
    report = {'loss': [], 'steps': 0, 'dispatches': 0,
              'microbatch': k, 'donated': step.donates,
              'checkpoints': 0, 'resumed_from': None, 'preempted': False}
    if resume_mgr is not None:
        got = resume_mgr.restore(return_extra=True)
        if got is not None:
            loaded, meta, extra = got
            state = step.adopt_state(loaded)
            start_epoch = int(meta.get('epoch', 0))
            skip_dispatches = int(meta.get('dispatch_in_epoch', 0))
            report['dispatches'] = int(meta.get('dispatches', 0))
            report['steps'] = report['dispatches'] * k
            report['resumed_from'] = int(meta.get('dispatches', 0))
            if extra and extra.get('rng') is not None:
                from ..resilience.checkpoint import restore_rng
                restore_rng(extra['rng'])

    guard = None
    if mgr is not None and preempt_save:
        from ..resilience import PreemptionGuard
        guard = PreemptionGuard().install()   # inert off the main thread

    def save_now(epoch, dispatch_in_epoch, async_ok=True):
        from ..resilience.checkpoint import capture_rng
        meta = {'epoch': int(epoch),
                'dispatch_in_epoch': int(dispatch_in_epoch),
                'dispatches': report['dispatches'],
                'microbatch': k,
                'world': int(world or 1)}
        mgr.save(state, step=report['dispatches'], meta=meta,
                 async_=bool(async_save and async_ok),
                 sharding=step.sharding,
                 world=world if step.sharding is None else None,
                 rank=rank if step.sharding is None else None,
                 extra={'rng': capture_rng()})
        report['checkpoints'] += 1

    # cadence is in DISPATCHES and each dispatch advances the streak by up
    # to k steps: reconcile every ceil(limit/k) dispatches so a diverging
    # run cannot overshoot the guard's consecutive-skip limit by ~k×
    guard_cap = (-(-nan_guard.max_consecutive_skips // k)
                 if nan_guard is not None else log_every)
    sync_every = max(1, min(log_every, guard_cap))
    needs_sync = nan_guard is not None or step.scaler is not None
    sw = _obs.Stopwatch()
    first_feed = None
    behind = None       # (i, StepResult) dispatched, not yet handed over
    dispatched = 0

    def hand_over(latest):
        nonlocal behind
        if on_step is not None:
            if behind is not None:
                on_step(*behind)
            behind = latest

    try:
        for epoch in range(int(start_epoch), int(epochs)):
            source = _grouped(data, k)
            if skip_dispatches:
                # resumed mid-epoch: these groups were already trained
                # (keys for them were drawn BEFORE the restored RNG
                # snapshot, so skipping draws nothing). Sliced BEFORE the
                # prefetcher so skipped groups are never uploaded.
                import itertools
                source = itertools.islice(source, skip_dispatches, None)
            if prefetch:
                from ..io.dataloader import DevicePrefetcher
                convert = _batch_to_device
                if step.sharding is not None:
                    # prefetch straight to the mesh placement: uploading to
                    # the default device first would reshard on every step
                    convert = functools.partial(_batch_to_mesh,
                                                step._batch_sharding)
                source = DevicePrefetcher(source, depth=int(prefetch),
                                          convert=convert)
            dispatch_in_epoch = skip_dispatches
            for bx, by in source:
                if first_feed is None:
                    # the serving export compiles at the training feed
                    # shapes; microbatch groups carry a leading scan axis
                    # the per-request program does not have
                    first_feed = tuple(
                        (tuple(np.shape(v))[1:] if k > 1
                         else tuple(np.shape(v)),
                         np.dtype(getattr(v, 'dtype', np.float32)))
                        for v in bx)
                if k == 1:
                    key = _rng.next_key()
                else:
                    key = jnp.stack([_rng.next_key() for _ in range(k)])
                state, out = step(state, (bx, by), key)
                hand_over((dispatched, out))
                dispatched += 1
                report['dispatches'] += 1
                report['steps'] += k
                dispatch_in_epoch += 1
                if needs_sync and report['dispatches'] % sync_every == 0:
                    step.sync(state, nan_guard=nan_guard, scaler=scaler)
                if report['dispatches'] % max(int(log_every), 1) == 0 or \
                        report['dispatches'] == 1:
                    report['loss'].append(float(out.loss))
                if guard is not None and guard.preempted:
                    # the preemption contract: fence the in-flight async
                    # save (finish or cleanly abandon) BEFORE the final
                    # synchronous checkpoint commits, then stop cleanly.
                    # A PRIOR background save's stored failure (or a
                    # wedged fence) must not abort this last chance to
                    # persist progress inside the grace window.
                    try:
                        mgr.fence(timeout=_PREEMPT_FENCE_S, abandon=True)
                    except Exception as e:
                        if _obs.enabled():
                            _obs.event('checkpoint.preempt_fence_error',
                                       error=repr(e))
                    save_now(epoch, dispatch_in_epoch, async_ok=False)
                    report['preempted'] = True
                    hand_over(None)
                    return _finish(report, sw, step, state, network,
                                   optimizer, nan_guard, scaler, needs_sync,
                                   mgr, guard)
                if mgr is not None and checkpoint_every and \
                        report['dispatches'] % int(checkpoint_every) == 0:
                    save_now(epoch, dispatch_in_epoch)
            skip_dispatches = 0
            if mgr is not None and not checkpoint_every:
                save_now(epoch + 1, 0)
        if mgr is not None and checkpoint_every:
            save_now(int(epochs), 0)
        hand_over(None)
        out = _finish(report, sw, step, state, network, optimizer,
                      nan_guard, scaler, needs_sync, mgr, guard)
        if serve_artifacts is not None:
            out['serve_artifacts'] = _export_serve_artifacts(
                serve_artifacts, network, state, first_feed,
                serve_generative)
        return out
    except BaseException:
        _cleanup(step, state, network, optimizer, nan_guard, scaler,
                 needs_sync, mgr, guard)
        raise


_PREEMPT_FENCE_S = 5.0


def _export_serve_artifacts(art_dir, network, state, first_feed,
                            generative):
    """Train→serve warm handoff: AOT-compile + serialize the programs the
    serving tier will run, into ``art_dir`` (compilecache format).

    Two program families: the trained network's eval/infer forward at the
    training feed shapes (the programs ``ServingEngine.register(layer=)``
    / batch serving dispatches), and — when ``generative`` carries a
    ``GenerativeSpec`` over the trained weights — the paged runner's whole
    closed set (chunked-prefill buckets, decode, draft/verify). Executable
    bytes are weight-independent (params are runtime inputs), so the
    artifacts stay valid as the checkpoint advances.
    """
    from .. import compilecache as _cc
    from ..core.rng import key_scope, next_key
    from ..core.tensor import Tensor
    from ..nn.layer_base import functional_call
    info = {'dir': str(art_dir), 'programs': 0}
    with _cc.use(art_dir):
        if first_feed:
            was_training = getattr(network, 'training', False)
            network.eval()
            try:
                def infer_fn(params_and_buffers, *feed):
                    with key_scope(key0):
                        out, _ = functional_call(
                            network, params_and_buffers,
                            *[Tensor(v) for v in feed])
                    outs = out if isinstance(out, (list, tuple)) else [out]
                    return tuple(o._value for o in outs)

                key0 = next_key()
                st = {**state['params'], **state['buffers']}
                feed_zeros = tuple(jnp.asarray(np.zeros(s, d))
                                   for s, d in first_feed)
                cj = _cc.CachedJit(infer_fn)
                cj.warm('engine.infer.%s' % type(network).__name__,
                        st, *feed_zeros, kind='engine.infer',
                        meta={'net': type(network).__name__})
                info['programs'] += 1
            finally:
                if was_training:
                    network.train()
        if generative is not None:
            # a throwaway paged runner's warmup IS the export: it walks
            # the exact closed program set a serving replica will
            # register. Cache keys embed the model name, so the replica
            # must register under the same one — pass (name, spec) to
            # pick it, bare spec exports as 'model'
            from ..serving.paged_runner import PagedGenerativeRunner
            from ..serving.scheduler import AdmissionQueue
            if isinstance(generative, tuple):
                serve_name, spec = generative
            else:
                serve_name, spec = 'model', generative
            runner = PagedGenerativeRunner(serve_name,
                                           AdmissionQueue(serve_name, 4),
                                           spec)
            info['programs'] += runner.warmup()
            info['generative'] = serve_name
    stats = _cc.stats()
    info['stores'] = stats['stores']
    if _obs.enabled():
        _obs.event('engine.serve_export', **info)
    return info


def _to_manager(source, max_keep):
    if source is None:
        return None
    from ..resilience import CheckpointManager
    if isinstance(source, CheckpointManager):
        return source
    return CheckpointManager(source, max_keep=max_keep)


def _cleanup(step, state, network, optimizer, nan_guard, scaler,
             needs_sync, mgr, guard, raise_fence=False):
    write_back_state(network, optimizer, state)
    if needs_sync:
        # final reconcile; never raise from the cleanup path — the
        # in-flight NanStepError (if any) already propagated above
        try:
            step.sync(state, nan_guard=nan_guard, scaler=scaler,
                      raise_on_limit=False)
        except Exception:
            pass
    if guard is not None:
        guard.uninstall()
    if mgr is not None:
        # the final async save must land before we return; on the normal
        # path its failure IS the caller's business
        if raise_fence:
            mgr.fence()
        else:
            try:
                mgr.fence()
            except Exception:
                pass


def _finish(report, sw, step, state, network, optimizer, nan_guard, scaler,
            needs_sync, mgr, guard):
    _cleanup(step, state, network, optimizer, nan_guard, scaler,
             needs_sync, mgr, guard, raise_fence=True)
    elapsed = sw.elapsed()
    if elapsed > 0:
        report['steps_per_sec'] = round(report['steps'] / elapsed, 3)
    report['state'] = state
    report['compiled_signatures'] = step.cache_size()
    return report


def _batch_to_device(batch):
    """Upload one (bx, by) host group as raw jax arrays (the prefetcher's
    default converter wraps Tensors — the compiled step wants bare
    arrays)."""
    bx, by = batch
    return (tuple(jnp.asarray(v) for v in bx),
            tuple(jnp.asarray(v) for v in by))


def _batch_to_mesh(batch_sharding, batch):
    """Sharded-step converter: upload each leaf directly to its mesh
    placement (batch dim over the data axis)."""
    import jax
    bx, by = batch
    return (tuple(jax.device_put(v, batch_sharding) for v in bx),
            tuple(jax.device_put(v, batch_sharding) for v in by))
