"""The unified train-step compiler (docs/PERF.md).

``build_train_step`` turns a pure loss function (or an nn.Layer + loss
callable) plus a paddle_tpu Optimizer into ONE jitted step::

    step = build_train_step(net=net, loss=loss_fn, optimizer=opt,
                            nan_guard=True, scaler=scaler)
    state = step.init_state(param_values(net), buffer_values(net))
    state, out = step(state, (batch_x, batch_y), key)
    # out.loss is a DeviceLoss: float(out.loss) syncs (and is counted);
    # until then the step chain never touches the host.

The functional state is one dict pytree::

    {'params': {...}, 'buffers': {...}, 'opt': {...},
     'guard': {...}?, 'scaler': {...}?}

and the whole dict is donated to the step on backends that honor donation,
so params/opt-state update in place on TPU instead of being copied every
step. The NaN guard and the AMP loss scaler both live INSIDE the graph:
a non-finite loss (or non-finite unscaled grads under AMP) selects the
pre-step state via ``lax.cond`` — no host round-trip, no host-side
rollback snapshot (which donation would invalidate). Host bookkeeping
(`NanGuard` counters/NanStepError, `GradScaler` state) is reconciled at
the caller's log cadence through :meth:`TrainStep.sync`.
"""
import contextlib
import functools
import itertools
import os

import numpy as np
import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..kernels._common import kernel_mesh

# distinguishes the default cost-ledger labels of multiple TrainSteps
# built in one process (frontends that care set .cost_label explicitly)
_STEP_SEQ = itertools.count()

__all__ = ['build_train_step', 'TrainStep', 'StepResult', 'DeviceLoss',
           'donation_supported', 'matmul_preference']

# backends whose PJRT runtime honors donate_argnums; everything else
# (notably CPU) ignores donation with a per-compile warning, so the gate
# keeps the warning (and the false sense of zero-copy) out of CPU runs
_DONATING_BACKENDS = ('tpu', 'gpu', 'cuda', 'rocm')


def donation_supported(backend=None):
    """Whether buffer donation is effective here.

    ``PADDLE_TPU_DONATE=1`` forces it on (bench/debug), ``=0`` forces it
    off (e.g. when aliasing params outside the step); otherwise it follows
    the backend capability.
    """
    env = os.environ.get('PADDLE_TPU_DONATE', '')
    if env == '0':
        return False
    if env == '1':
        return True
    if backend is None:
        backend = jax.default_backend()
    return backend in _DONATING_BACKENDS


def matmul_preference(backend=None):
    """The step's default matmul precision: bf16 where it is the hardware
    fast path (TPU), backend default elsewhere (CPU parity tests stay
    bitwise against eager). ``PADDLE_TPU_MATMUL_PRECISION`` overrides
    ('bfloat16' / 'float32' / 'tensorfloat32' / '' for backend default)."""
    env = os.environ.get('PADDLE_TPU_MATMUL_PRECISION', None)
    if env is not None:
        return env or None
    if backend is None:
        backend = jax.default_backend()
    return 'bfloat16' if backend == 'tpu' else None


class DeviceLoss:
    """A loss that stays on-device until someone actually needs the number.

    ``float(loss)`` (or ``.value()``) materializes it on host exactly once
    — recorded against the ``host_transfer.engine.loss_fetch.bytes``
    counter so the telemetry can prove steady-state steps transfer 0
    bytes. ``is_ready()`` tells log-cadence consumers (TelemetryCallback)
    whether reading it is free.
    """

    __slots__ = ('_value', '_host')

    def __init__(self, value):
        self._value = value
        self._host = None

    def is_ready(self):
        return self._host is not None

    @property
    def raw(self):
        """The on-device jax scalar (no sync)."""
        return self._value

    def value(self):
        if self._host is None:
            arr = np.asarray(self._value)
            _obs.record_host_transfer(arr.nbytes, kind='engine.loss_fetch')
            self._host = float(arr)
        return self._host

    def __float__(self):
        return self.value()

    # numeric duck-typing: a user callback that treats the fit loop's
    # logs['loss'] as a number (compare, add, format) keeps working — each
    # such use materializes on demand, i.e. opts that callback back into
    # the per-step sync it is asking for (and the transfer stays counted)
    def __lt__(self, other):
        return self.value() < other

    def __le__(self, other):
        return self.value() <= other

    def __gt__(self, other):
        return self.value() > other

    def __ge__(self, other):
        return self.value() >= other

    def __eq__(self, other):
        if isinstance(other, DeviceLoss):
            return self.value() == other.value()
        return self.value() == other

    def __hash__(self):
        return hash(self.value())

    def __add__(self, other):
        return self.value() + other

    __radd__ = __add__

    def __sub__(self, other):
        return self.value() - other

    def __rsub__(self, other):
        return other - self.value()

    def __mul__(self, other):
        return self.value() * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self.value() / other

    def __rtruediv__(self, other):
        return other / self.value()

    def __neg__(self):
        return -self.value()

    def __pos__(self):
        return self.value()

    def __abs__(self):
        return abs(self.value())

    def __round__(self, ndigits=None):
        return round(self.value(), ndigits)

    def __format__(self, spec):
        return format(self.value(), spec)

    def __repr__(self):
        if self._host is not None:
            return f'DeviceLoss({self._host})'
        return 'DeviceLoss(<on device>)'


class StepResult:
    """What one compiled step hands back (besides the new state)."""

    __slots__ = ('loss', 'losses', 'outputs')

    def __init__(self, loss, losses, outputs):
        self.loss = loss          # DeviceLoss of the (last) microbatch loss
        self.losses = losses      # device scalar (k=1) or [k] device array
        self.outputs = outputs    # model outputs tuple (k=1) or None


def _net_loss_fn(net, loss):
    """The canonical pure loss over an nn.Layer: functional_call under a
    key_scope, summing list losses exactly like the eager path does."""
    from ..core.rng import key_scope
    from ..core.tensor import Tensor
    from ..nn.layer_base import functional_call

    def loss_fn(params, buffers, batch, key):
        batch_x, batch_y = batch
        with key_scope(key):
            out, new_buf = functional_call(net, {**params, **buffers},
                                           *[Tensor(v) for v in batch_x])
            outs = out if isinstance(out, (list, tuple)) else [out]
            losses = loss(*outs, *[Tensor(v) for v in batch_y])
        losses = losses if isinstance(losses, (list, tuple)) else [losses]
        total = losses[0]
        for extra in losses[1:]:
            total = total + extra
        return total._value, tuple(o._value for o in outs), new_buf
    return loss_fn


_REMAT_POLICIES = {
    # recompute everything in the backward pass (max memory win)
    'full': None,
    # save matmul/dot results, recompute the cheap elementwise stages
    'dots': 'dots_saveable',
}


def _resolve_remat(remat):
    """None | 'full' | 'dots' | jax checkpoint policy callable."""
    if remat is None or remat == 'none':
        return False, None
    if callable(remat):
        return True, remat
    if remat not in _REMAT_POLICIES:
        raise ValueError(
            f"build_train_step: unknown remat policy {remat!r} "
            f"(use None, 'full', 'dots', or a jax.checkpoint_policies "
            f"callable)")
    name = _REMAT_POLICIES[remat]
    return True, (getattr(jax.checkpoint_policies, name) if name else None)


def build_train_step(loss_fn=None, optimizer=None, *, net=None, loss=None,
                     params_meta=None, trainable=None, scaler=None,
                     nan_guard=False, microbatch=1, donate='auto',
                     remat=None, matmul_precision='auto', with_key=None,
                     in_shardings=None, sharding=None):
    """Compile ONE train step every frontend can share.

    Either pass a pure ``loss_fn(params, buffers, batch, key) ->
    (loss, outputs, new_buffers)`` or an eager ``net=`` + ``loss=``
    callable pair (the builder derives the functional loss via
    ``functional_call``). ``optimizer`` is any paddle_tpu Optimizer — its
    ``functional_update`` rule (decay + clip included) becomes the in-graph
    update, so eager and compiled paths cannot diverge.

    - ``scaler``: an ``amp.GradScaler`` folded into the step (scale,
      unscale, found-inf skip, dynamic-scale update — all on device).
    - ``nan_guard=True``: in-graph finiteness check + ``lax.cond``
      state-select; reconcile host counters with :meth:`TrainStep.sync`.
    - ``microbatch=k``: the compiled step scans k microbatches per
      dispatch (batch leaves need a leading ``k`` axis; pass k stacked
      keys). Model outputs are only returned for ``k == 1``.
    - ``donate='auto'|True|False``: donate the state pytree
      (feature-gated off where the backend ignores donation).
    - ``remat``: ``'full'`` / ``'dots'`` / a checkpoint policy — wraps the
      loss computation in ``jax.checkpoint``.
    - ``matmul_precision='auto'``: bf16 on TPU, backend default elsewhere;
      or pass an explicit jax precision string.
    - ``trainable``: optional set of param names to update (others flow
      through untouched — the Executor's ``stop_gradient`` filter).
    - ``in_shardings``: passed straight to ``jax.jit`` for sharded feeds
      (the Executor's data-parallel compile); the pytree must match the
      step signature ``(state, batch[, keys])``.
    - ``sharding``: a ``distributed.ShardingConfig`` (or a fleet
      ``DistributedStrategy`` / kwargs dict — resolved via
      ``distributed.strategy.resolve_sharding``). The whole state pytree
      gets ``NamedSharding``s derived from the config's FSDP/tensor-
      parallel rules: params + optimizer moments live sharded at rest
      (and stay sharded through donation and the scan carry), feeds
      shard over the data axis, and FSDP params are gathered at use time
      inside the step so the math is bitwise-identical to the replicated
      step (docs/PERF.md, "Sharded training"). The jit program is built
      lazily by :meth:`TrainStep.init_state`, which also places the
      state and records ``sharding.param_bytes_per_device``.
    """
    if net is not None:
        if loss_fn is not None:
            raise ValueError("build_train_step: pass loss_fn OR net+loss, "
                             "not both")
        if loss is None:
            raise ValueError("build_train_step: net= needs loss=")
        loss_fn = _net_loss_fn(net, loss)
        if params_meta is None:
            params_meta = {k: p for k, p in net.named_parameters()
                           if p.trainable}
        if with_key is None:
            with_key = True
        # counters that are values of the step: the net's last output, one
        # value per name (observability.step_counters)
        counters = (tuple(getattr(net, 'step_counter_names', ())),
                    frozenset(getattr(net, 'step_counter_sums', ())))
    else:
        counters = ((), frozenset())
    if loss_fn is None:
        raise ValueError("build_train_step: need loss_fn= or net=+loss=")
    if optimizer is None:
        raise ValueError("build_train_step: optimizer is required")
    if with_key is None:
        with_key = False
    k = int(microbatch)
    if k < 1:
        raise ValueError(f"build_train_step: microbatch must be >= 1, "
                         f"got {microbatch}")
    if scaler is not None and not scaler.is_enable():
        scaler = None
    if sharding is not None:
        from ..distributed.strategy import resolve_sharding
        sharding = resolve_sharding(sharding)
    if sharding is not None and net is not None:
        # tensor-parallel layers placed their weights on the model axis
        # eagerly (shard_tensor at construction) — the compiled step keeps
        # those layouts instead of FSDP-sharding/gathering them
        sharding = sharding.with_rules_from(net)
    if sharding is not None and in_shardings is not None:
        raise ValueError("build_train_step: sharding= derives the step's "
                         "in_shardings itself — pass one or the other")
    with _obs.span('engine.build'):
        return TrainStep(
            loss_fn, optimizer, params_meta=params_meta,
            # an EMPTY set is a real filter (every param frozen: update
            # nothing) — only None means "no filter"
            trainable=(frozenset(trainable)
                       if trainable is not None else None),
            scaler=scaler, nan_guard=bool(nan_guard), microbatch=k,
            donate=donate, remat=remat, matmul_precision=matmul_precision,
            with_key=with_key, in_shardings=in_shardings, sharding=sharding,
            counters=counters)


def _tree_bytes(tree):
    """Total bytes of a pytree's leaves, by shape and dtype."""
    return sum(int(np.prod(np.shape(leaf) or (1,))) *
               np.dtype(getattr(leaf, 'dtype', np.float32)).itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def _abstract(leaf):
    """What ``jit.lower`` needs of an array the step has donated: its
    shape, dtype and, where it is committed, its sharding, so that the
    lowering asked for is the one the dispatch made (found in jit's own
    cache, not lowered or loaded a second time)."""
    if not isinstance(leaf, jax.Array):
        return leaf
    return jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, weak_type=leaf.weak_type,
        sharding=leaf.sharding if leaf.committed else None)


def kernel_mesh_of(sharding, in_shardings):
    """The ``kernel_mesh`` scope of a partitioned step — the mesh, the
    axes its batch is split over, the axes its attention heads are — or
    a null scope for a one-device step. ``sharding=`` names them; with raw
    ``in_shardings=`` (the Executor's data-parallel steps) they are read
    off the batch's NamedSharding."""
    if sharding is not None:
        if sharding.num_devices <= 1:
            return contextlib.nullcontext()
        heads = (sharding.model_axis,) \
            if sharding.tensor_parallel_degree > 1 else ()
        return kernel_mesh(sharding.mesh, (sharding.data_axis,), heads)
    for sh in jax.tree_util.tree_leaves(tuple(in_shardings or ())[1:]):
        for entry in getattr(sh, 'spec', ()):
            if entry is not None and sh.mesh.size > 1:
                axes = (entry,) if isinstance(entry, str) else tuple(entry)
                return kernel_mesh(sh.mesh, axes)
    return contextlib.nullcontext()


class TrainStep:
    """A compiled train step: call it with (state, batch[, key])."""

    def __init__(self, loss_fn, optimizer, params_meta, trainable, scaler,
                 nan_guard, microbatch, donate, remat, matmul_precision,
                 with_key, in_shardings, sharding=None,
                 counters=((), frozenset())):
        self.optimizer = optimizer
        self._counter_names, self._counter_sums = counters
        self.k = microbatch
        self.guard_enabled = nan_guard
        self.scaler = scaler
        self.sharding = sharding
        # cost explorer: this step's ledger label (Executor overrides it
        # with the program fingerprint) + the captured-once latch
        self.cost_label = f'engine.train_step{next(_STEP_SEQ)}'
        self._cost_captured = False
        self._dispatches = 0         # the step number of the next dispatch
        self._params_meta = params_meta
        self._trainable = trainable
        self._with_key = with_key
        use_remat, remat_policy = _resolve_remat(remat)
        if use_remat:
            loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
        self._loss_fn = loss_fn
        if matmul_precision == 'auto':
            matmul_precision = matmul_preference()
        self._matmul_precision = matmul_precision
        self.donates = donation_supported() if donate == 'auto' \
            else bool(donate)
        # sharded-state wiring (filled by init_state once the real state
        # pytree exists — shardings must match its exact structure)
        self._gather = frozenset()
        self._state_constraints = None
        self._state_shardings = None
        self._batch_sharding = None
        self._collective_bytes_est = 0
        self._kernel_mesh = functools.partial(kernel_mesh_of, sharding,
                                              in_shardings)
        if sharding is not None:
            # the jit program needs the state pytree's shardings: built
            # lazily by init_state (which every frontend goes through)
            self._jit = None
            self._batch_sharding = sharding.batch_sharding(self.k)
            return
        jit_kwargs = {}
        if self.donates:
            jit_kwargs['donate_argnums'] = (0,)
        if in_shardings is not None:
            jit_kwargs['in_shardings'] = in_shardings
        self._jit = jax.jit(self._make_step(), **jit_kwargs)

    # -- state --------------------------------------------------------------
    def init_state(self, params, buffers=None, opt_state=None,
                   nan_guard=None, scaler=None):
        """Assemble the functional state pytree.

        ``opt_state=None`` initializes fresh optimizer slots; pass restored
        accumulators to resume. ``nan_guard``/``scaler`` host objects seed
        the in-graph counters so a resumed run continues its skip/scale
        history exactly.
        """
        with _obs.span('engine.init_state',
                       sharded=self.sharding is not None) as span:
            state = self._assemble_state(params, buffers, opt_state,
                                         nan_guard, scaler)
            if self.sharding is not None:
                state = self._shard_state(state)
            if _obs.enabled():
                span.args['bytes'] = _tree_bytes(state)
        return state

    def _assemble_state(self, params, buffers, opt_state, nan_guard, scaler):
        state = {'params': dict(params), 'buffers': dict(buffers or {}),
                 'opt': opt_state if opt_state is not None
                 else self.optimizer.init_state_values(dict(params))}
        if self.guard_enabled:
            g = nan_guard
            state['guard'] = {
                'steps': jnp.int32(g.total_steps if g else 0),
                'skipped': jnp.int32(g.skipped_steps if g else 0),
                'consecutive': jnp.int32(g.consecutive_skips if g else 0),
                # running MAX of the streak SINCE THE LAST SYNC: a
                # limit-length streak that ends between two host reconciles
                # must still abort at the next one (the eager guard would
                # have aborted mid-streak). Seeded 0 and rebased to 0 by
                # sync(): a continued streak re-enters through
                # 'consecutive', so nothing is lost, and a run that
                # recovered after a caught abort is not re-aborted forever.
                'peak': jnp.int32(0),
            }
        if self.scaler is not None:
            s = scaler or self.scaler
            state['scaler'] = {
                'scale': jnp.float32(s.get_loss_scaling()),
                'good': jnp.int32(s._good_steps),
                'bad': jnp.int32(s._bad_steps),
            }
        return state

    def _shard_state(self, state):
        """Place the state on the mesh per the config and (first time)
        compile the sharded step against its exact pytree structure.
        Derivation + telemetry run once; repeat calls (the Executor runs
        init_state per step to adopt fresh eager params) only pay the
        device_put — which is a no-op for already-placed leaves."""
        cfg = self.sharding
        first = self._jit is None
        if first:
            specs = cfg.param_specs(state['params'])
            shardings = cfg.state_shardings(state, specs)
            self._gather = cfg.gather_names(state['params'], specs)
            self._state_shardings = shardings
            self._state_constraints = {
                'params': shardings['params'], 'opt': shardings['opt']}
            self._collective_bytes_est = cfg.collective_bytes_estimate(
                state['params'], specs)
            repl = cfg.replicated()
            jit_kwargs = {
                'in_shardings': (
                    (shardings, self._batch_sharding) +
                    ((repl,) if self._with_key else ())),
                # pin outputs to the SAME NamedShardings as the inputs:
                # without this the output state carries GSPMD-inferred
                # sharding objects that compare unequal to the input
                # NamedShardings, and every call re-traces (the XLA cache
                # hides it from jax.compiles, but the jit cache grows)
                'out_shardings': (shardings, repl, repl),
            }
            if self.donates:
                jit_kwargs['donate_argnums'] = (0,)
            self._jit = jax.jit(self._make_step(), **jit_kwargs)
        with _obs.span('engine.place_state'):
            state = cfg.device_put_state(state, self._state_shardings)
        if first and _obs.enabled():
            _obs.gauge('sharding.param_bytes_per_device').set(
                cfg.bytes_per_device(state['params']))
            _obs.gauge('sharding.opt_bytes_per_device').set(
                cfg.bytes_per_device(state['opt']))
            _obs.gauge('sharding.state_bytes_per_device').set(
                cfg.bytes_per_device(state))
            _obs.gauge('sharding.mesh_devices').set(cfg.num_devices)
            _obs.gauge('sharding.collective_bytes_per_step_est').set(
                self._collective_bytes_est)
        return state

    def restore_state(self, source, step=None):
        """Resume this step from a checkpoint — possibly saved on a
        DIFFERENT mesh shape (resharding restore, docs/RESILIENCE.md,
        "Elastic training").

        ``source`` is a checkpoint directory or a
        ``resilience.CheckpointManager``. The newest non-corrupt
        checkpoint (or ``step``) is reassembled on host and placed per
        THIS step's sharding config (compiling the sharded program
        against the restored structure when needed) — bitwise-equal to a
        same-mesh restore. Guard/scaler slots the checkpoint carries but
        this step does not use are dropped (warning); missing ones are
        seeded fresh. Returns ``(state, meta)`` or ``None`` when nothing
        loadable exists.
        """
        from ..resilience import CheckpointManager
        mgr = source if isinstance(source, CheckpointManager) \
            else CheckpointManager(source)
        got = mgr.restore(step=step)
        if got is None:
            return None
        state, meta = got
        state = self.adopt_state(state)
        return state, meta

    def adopt_state(self, state):
        """Align a restored host state with this step's contract: seed or
        drop guard/scaler slots, then shard/place it for dispatch."""
        import warnings
        state = dict(state)
        if self.guard_enabled and 'guard' not in state:
            state['guard'] = {'steps': jnp.int32(0), 'skipped': jnp.int32(0),
                              'consecutive': jnp.int32(0),
                              'peak': jnp.int32(0)}
        if self.scaler is not None and 'scaler' not in state:
            s = self.scaler
            state['scaler'] = {'scale': jnp.float32(s.get_loss_scaling()),
                               'good': jnp.int32(s._good_steps),
                               'bad': jnp.int32(s._bad_steps)}
        for slot, enabled in (('guard', self.guard_enabled),
                              ('scaler', self.scaler is not None)):
            if not enabled and slot in state:
                warnings.warn(
                    f"TrainStep.restore_state: checkpoint carries a "
                    f"{slot!r} slot this step was built without — "
                    f"dropping it", RuntimeWarning, stacklevel=2)
                state.pop(slot)
        if self.sharding is not None:
            state = self._shard_state(state)
        else:
            state = jax.tree_util.tree_map(jnp.asarray, state)
        return state

    def sharding_info(self, state):
        """Per-device residency + traffic accounting for a (sharded)
        state — what bench/tier-1 assert the memory win with."""
        cfg = self.sharding
        if cfg is None:
            return {'param_bytes_per_device': _tree_bytes(state['params']),
                    'state_bytes_per_device': _tree_bytes(state),
                    'mesh_devices': 1, 'collective_bytes_per_step_est': 0,
                    'sharded_params': 0}
        specs = cfg.param_specs(state['params'])
        from jax.sharding import PartitionSpec as _P
        return {
            'param_bytes_per_device': cfg.bytes_per_device(state['params']),
            'opt_bytes_per_device': cfg.bytes_per_device(state['opt']),
            'state_bytes_per_device': cfg.bytes_per_device(state),
            'mesh_devices': cfg.num_devices,
            'collective_bytes_per_step_est': self._collective_bytes_est,
            'sharded_params': sum(1 for s in specs.values() if s != _P()),
        }

    # -- the compiled step ---------------------------------------------------
    def _make_step(self):
        one = self._one_step
        k = self.k
        precision = self._matmul_precision
        with_key = self._with_key
        batch_sharding = self._batch_sharding

        def constrain_batch(batch):
            # pin activations to the data axis at the step boundary so
            # GSPMD keeps the batch dim sharded through the network
            # instead of inferring a replicated layout from the params
            return jax.tree_util.tree_map(
                lambda v: jax.lax.with_sharding_constraint(v, batch_sharding),
                batch)

        def run(state, batch, keys):
            if batch_sharding is not None:
                batch = constrain_batch(batch)
            if k == 1:
                key = keys
                return one(state, batch, key)

            def body(st, xs):
                if with_key:
                    b, kk = xs
                else:
                    b, kk = xs, None
                st, loss, _ = one(st, b, kk)
                return st, loss

            xs = (batch, keys) if with_key else batch
            new_state, losses = jax.lax.scan(body, state, xs)
            return new_state, losses, None

        scope = self._kernel_mesh

        def traced(state, batch, keys):
            with contextlib.ExitStack() as scopes:
                if precision:
                    scopes.enter_context(
                        jax.default_matmul_precision(precision))
                # a partitioned program: the Pallas kernel sites split
                # themselves over the mesh (shard_map), since the TPU
                # compiler will not do it for them
                scopes.enter_context(scope())
                return run(state, batch, keys)

        if with_key:
            def step(state, batch, keys):
                return traced(state, batch, keys)
        else:
            def step(state, batch):
                return traced(state, batch, None)
        return step

    def _one_step(self, state, batch, key):
        loss_fn = self._loss_fn
        opt = self.optimizer
        use_scaler = self.scaler is not None
        use_guard = self.guard_enabled
        params, buffers = state['params'], state['buffers']
        opt_state = state['opt']
        scale = state['scaler']['scale'] if use_scaler else None
        if self._gather:
            # the ZeRO use-time gather: FSDP-sharded params become
            # replicated for the forward/backward, so every reduction
            # runs in the same order as the replicated step (bitwise
            # parity); tensor-parallel params are NOT in the gather set —
            # their sharding IS the parallelism. The constraint's
            # transpose keeps the cotangent replicated; the update math
            # is elementwise, and the carry constraint below reshards
            # the new state on the way out.
            repl = self.sharding.replicated()
            with jax.named_scope('fsdp.gather'):
                params = {n: (jax.lax.with_sharding_constraint(v, repl)
                              if n in self._gather else v)
                          for n, v in params.items()}

        # the scopes below name the step's phases in every HLO
        # instruction's op_name (metadata only: no instruction changes):
        # what value_and_grad differentiates is `forward`, so its transpose
        # reads `transpose(jvp(forward))`: the backward
        # (observability.costs.instruction_phases reads them back)
        def scaled_loss(p):
            with jax.named_scope('forward'):
                loss, outs, new_buf = loss_fn(p, buffers, batch, key)
                out_loss = loss * scale if use_scaler else loss
            return out_loss, (loss, outs, new_buf)

        (_, (loss, outs, new_buf)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params)
        if self._trainable is not None:
            grads = {n: g for n, g in grads.items() if n in self._trainable}
        if use_scaler:
            with jax.named_scope('guard'):
                grads = {n: g / scale for n, g in grads.items()}
        with jax.named_scope('update'):
            new_params, new_opt = opt.functional_update(
                params, grads, opt_state, params_meta=self._params_meta)
        applied = {'params': new_params, 'buffers': new_buf, 'opt': new_opt}
        kept = {'params': params, 'buffers': buffers, 'opt': opt_state}

        with jax.named_scope('guard'):
            loss_ok = jnp.isfinite(loss) if (use_guard or use_scaler) \
                else None
            grads_ok = None
            if use_scaler:
                grads_ok = functools.reduce(
                    jnp.logical_and,
                    [jnp.all(jnp.isfinite(g)) for g in
                     jax.tree_util.tree_leaves(grads)],
                    jnp.bool_(True))
            if use_guard and use_scaler:
                ok = jnp.logical_and(loss_ok, grads_ok)
            elif use_guard:
                ok = loss_ok
            elif use_scaler:
                ok = jnp.logical_and(loss_ok, grads_ok)
            else:
                ok = None

            if ok is None:
                new_state = applied
            else:
                # the donation-safe replacement for the old host-side
                # rollback snapshot: select the pre-step state in-graph,
                # no copy held
                new_state = jax.lax.cond(ok, lambda: applied, lambda: kept)
            if use_guard:
                g = state['guard']
                skipped = jnp.logical_not(loss_ok)
                streak = jnp.where(skipped, g['consecutive'] + 1, 0)
                new_state['guard'] = {
                    'steps': g['steps'] + 1,
                    'skipped': g['skipped'] + skipped.astype(jnp.int32),
                    'consecutive': streak,
                    'peak': jnp.maximum(g['peak'], streak),
                }
            if use_scaler:
                new_state['scaler'] = self._advance_scaler(
                    state['scaler'], ok)
        if self._state_constraints is not None:
            # reshard the updated params/opt on the way out: the scan
            # carry (and the donated output buffers) stay sharded across
            # microbatches instead of riding replicated through the loop
            wsc = jax.lax.with_sharding_constraint
            with jax.named_scope('fsdp.reshard'):
                new_state['params'] = {
                    n: wsc(v, self._state_constraints['params'][n])
                    for n, v in new_state['params'].items()}
                new_state['opt'] = jax.tree_util.tree_map(
                    wsc, new_state['opt'], self._state_constraints['opt'])
        return new_state, loss, outs

    def _advance_scaler(self, sc, ok):
        """GradScaler.update as pure state math (bitwise-same policy)."""
        s = self.scaler
        if not s._dynamic:
            return sc
        bad1 = sc['bad'] + 1
        dec = bad1 >= s._decr_every
        scale_bad = jnp.where(
            dec, jnp.maximum(sc['scale'] * s._decr_ratio, 1.0), sc['scale'])
        good1 = sc['good'] + 1
        inc = good1 >= s._incr_every
        scale_good = jnp.where(inc, sc['scale'] * s._incr_ratio, sc['scale'])
        return {
            'scale': jnp.where(ok, scale_good, scale_bad),
            'good': jnp.where(ok, jnp.where(inc, 0, good1), 0),
            'bad': jnp.where(ok, 0, jnp.where(dec, 0, bad1)),
        }

    # -- dispatch ------------------------------------------------------------
    def __call__(self, state, batch, key=None):
        """Run one compiled dispatch (k microbatches). Returns
        ``(new_state, StepResult)``; nothing here touches the host."""
        if self._with_key and key is None:
            raise ValueError("this TrainStep was built with_key=True — pass "
                             "key= (k stacked keys for microbatch>1)")
        if self.sharding is not None:
            if self._jit is None:
                raise RuntimeError(
                    "sharded TrainStep: call init_state() first — it "
                    "derives the state shardings and compiles the step")
            # feeds go straight to their mesh placement (device_put on an
            # already-matching array is a no-op), so a committed host/
            # single-device batch never fights the jit's in_shardings
            bsh = self._batch_sharding
            batch = jax.tree_util.tree_map(
                lambda v: jax.device_put(v, bsh), batch)
            if key is not None:
                key = jax.device_put(key, self.sharding.replicated())
        args = (state, batch, key) if self._with_key else (state, batch)
        telemetry = _obs.enabled()
        capture = telemetry and not self._cost_captured
        if capture:
            # the dispatch donates the state: what the capture below needs
            # of it is taken first
            args_then = (jax.tree_util.tree_map(_abstract, state),) + args[1:]
        n = self._dispatches
        self._dispatches = n + 1
        # the ENQUEUE of step n, not the step: the jit call returns before
        # the device is done. In a profiler trace it is the per-step
        # annotation `train_step` with step_num=n. Dispatch 0 (`first`)
        # also traces, lowers and compiles or loads the program: those are
        # its child records (`jax.*`), with telemetry on or off alike.
        with _obs.timer('engine.dispatch', step=n, annotation='train_step',
                        k=self.k, **({} if n else {'first': True})):
            new_state, losses, outs = self._jit(*args)
        if capture:
            # cost explorer: AOT-ledger this program's FLOPs/bytes/peak
            # memory and the phase of each of its instructions once, from
            # the executable the dispatch above has just made
            self._cost_captured = True
            _obs.costs.capture(
                self.cost_label, self._jit, *args_then, kind='train_step',
                phases=True,
                meta={'microbatch': self.k, 'donates': self.donates,
                      'sharded': self.sharding is not None})
        if telemetry:
            _obs.counter('engine.steps').inc(self.k)
            _obs.counter('engine.dispatches').inc()
            if self._collective_bytes_est:
                _obs.counter('sharding.collective_bytes_est').inc(
                    self._collective_bytes_est * self.k)
            if self._counter_names and outs:
                # queued, not waited for: recorded once the device has them
                _obs.step_counters.push(n, self._counter_names, outs[-1],
                                        self._counter_sums)
        loss = losses if self.k == 1 else losses[-1]
        return new_state, StepResult(DeviceLoss(loss), losses, outs)

    # -- host reconciliation -------------------------------------------------
    def sync(self, state, nan_guard=None, scaler=None, raise_on_limit=True):
        """Reconcile in-graph guard/scaler bookkeeping with the host objects.

        Call at the log/telemetry cadence (and before checkpointing). Syncs
        the handful of counter scalars (counted as a host transfer), writes
        the live loss scale back into the ``GradScaler``, updates
        ``NanGuard`` counters (emitting skip events for steps skipped since
        the last sync), and raises ``NanStepError`` when the consecutive
        limit was hit — the same abort contract the eager path has.
        """
        fetched = {}
        nbytes = 0
        for slot in ('guard', 'scaler'):
            if slot in state:
                vals = {kk: np.asarray(vv) for kk, vv in state[slot].items()}
                nbytes += sum(v.nbytes for v in vals.values())
                fetched[slot] = vals
        if not fetched:
            return {}
        _obs.record_host_transfer(nbytes, kind='engine.state_sync')
        if 'guard' in fetched:
            # rebase the since-last-sync streak maximum BEFORE judging, so
            # a caught NanStepError doesn't re-raise on every later sync
            # (the live streak re-enters via 'consecutive'; eager recovers
            # the same way — one good step resets the count)
            state['guard']['peak'] = jnp.int32(0)
        scaler = scaler or self.scaler
        if 'scaler' in fetched and scaler is not None:
            sv = fetched['scaler']
            scaler._scale = float(sv['scale'])
            scaler._good_steps = int(sv['good'])
            scaler._bad_steps = int(sv['bad'])
        if 'guard' in fetched and nan_guard is not None:
            gv = fetched['guard']
            nan_guard.absorb_device_counts(
                int(gv['steps']), int(gv['skipped']), int(gv['consecutive']),
                # the scaler's decrement already happened in-graph; marking
                # it again on the host would double-decay the scale
                mark_scaler=self.scaler is None,
                raise_on_limit=raise_on_limit,
                peak_consecutive=int(gv.get('peak', gv['consecutive'])))
        return {slot: {kk: vv.item() for kk, vv in vals.items()}
                for slot, vals in fetched.items()}

    def cache_size(self):
        """Compiled-signature count of the underlying jit cache (a growing
        number in steady state is the retrace-storm signal)."""
        try:
            return self._jit._cache_size()
        except Exception:
            return -1

    # TelemetryCallback reads the jit cache size through this legacy name
    _cache_size = cache_size
