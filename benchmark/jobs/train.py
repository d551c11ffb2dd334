"""Job `train`: the benchmark's copy of `engine.fit`'s inner loop, with a
completion time for every step.

`engine.fit` offers no per-step completion to time, so the loop here is its
own: `build_train_step(net=, loss=, optimizer=[, sharding=])`, `init_state`,
`DevicePrefetcher(depth=2)` with fit's own `convert`, `step(state, batch,
key)` under `amp.auto_cast`. fit's log cadence and guard sync are not on the
measured path (PERF.md section 7 lists the hook that would let this file
call fit).

A run, in order:
  set-up   the net (built abstractly: no eager op runs), the state from the
           seed in one jitted call, the pool of host batches, the
           prefetcher; the first three steps of the CHECKED object through
           the window's own call and feed, with the readings the comparison
           needs; the timed step's warm-up.
  window   dispatch step i, then wait for the loss of step i-1: the host
           clock at that moment is step i-1's completion. It is WHOLE PASSES
           over the pool: its first counted step is the first batch of a
           pass, and it closes at the first completion at or after
           `--seconds` that completes one. Every pool batch is so timed once
           a pass, in every run alike: where a step's time follows its batch
           (the packed cells) two runs time the same work, and an interval
           has a second reading of the same batch to be held against.
  (trace)  with --trace 1, a few more steps under the profiler.
  check    the state is freed; the plain reference follows the same three
           batches from the same seeded weights; every number is printed
           beside its limit.

The checked object is the timed object itself where the family's step is
deterministic (`resnet`). Where it is not (`bert`: dropout drawn by the
kernels' hardware PRNG, which no reference can follow) it is a twin built by
the same calls with dropout off, and the timed object is held to what
dropout leaves steady: finite losses, no compile, a loss that falls.
"""
import collections
import functools
import gc
import json
import os
import shutil
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import check, params as params_mod, peaks, trace as trace_mod  # noqa: E402
from harness.compiles import Compiles  # noqa: E402
from harness.spans import Spans  # noqa: E402

CHECK_STEPS = 3
WARMUP_STEPS = 3
TRACE_STEPS = 6
# the calls before the first counted step: the checked steps, the warm-up
# and the one call of `window` whose completion is the window's start
LEAD_STEPS = CHECK_STEPS + WARMUP_STEPS + 1


_T0 = [time.perf_counter()]     # run() sets it to the process's start


def say(**facts):
    """One line of facts, with the seconds since the process started."""
    print(json.dumps(dict(facts, t=round(time.perf_counter() - _T0[0], 2)),
                     sort_keys=True), flush=True)


class Feed:
    """Host batches from the seeded pool, for ever, every batch through the
    family's host augmentation. The first `lead` batches (set-up's and the
    window's lead-in call) are the first of a seeded order and are not run
    out to the end of a pass; behind them every pass over the pool is a
    whole new seeded order, so the first counted step is the first batch of
    a pass. Remembers the first batches it gave, for the reference, and the
    pool index of every batch it gave (`order[k]` is the batch of the
    consumer's call k, however far the prefetcher runs ahead). Runs on the
    prefetcher's thread."""

    def __init__(self, family, traffic, pool, seed, remember,
                 lead=LEAD_STEPS):
        self.family, self.traffic, self.pool = family, traffic, pool
        self.rs = np.random.default_rng([int(seed), 0xFEED])
        self.lead, self.remember = lead, remember
        self.first, self.order = [], []

    def _indices(self):
        left = self.lead
        while left:
            walk = self.rs.permutation(len(self.pool))[:left]
            left -= len(walk)
            yield from walk
        while True:
            yield from self.rs.permutation(len(self.pool))

    def __iter__(self):
        for i in self._indices():
            batch = self.family.augment(self.traffic, self.pool[i], self.rs)
            if len(self.first) < self.remember:
                self.first.append(batch)
            self.order.append(int(i))
            yield batch


class Caller:
    """One call into the program per step, the same for the checked steps,
    the warm-up and the window: the next batch off the prefetcher, a key,
    the compiled step."""

    def __init__(self, feed_iter, spans, compute_dtype):
        from paddle_tpu import amp
        from paddle_tpu.core import rng
        self.feed, self.spans = feed_iter, spans
        self.amp, self.rng, self.dtype = amp, rng, compute_dtype
        self.calls = 0      # call k takes the feed's batch k

    def __call__(self, step, state):
        self.calls += 1
        with self.spans.span('input.wait'):
            batch = next(self.feed)
        with self.spans.span('step.key'):
            key = self.rng.next_key()
        with self.spans.span('step.dispatch'):
            with self.amp.auto_cast(dtype=self.dtype):
                state, res = step(state, batch, key)
        return state, res.loss.raw


def build_step(family, config, traffic, devices, deterministic):
    """(step, make_state): the program's compiled step for this cell and a
    function that makes its state from the seed."""
    import jax
    from paddle_tpu import engine
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    holder = {}

    def abstract():
        # under eval_shape no initializer runs on the device: the net is
        # needed for its structure, its weights come from `params.make`
        holder['built'] = family.build(config, deterministic=deterministic)
        net = holder['built'][0]
        return param_values(net), buffer_values(net)

    shapes, buffer_shapes = jax.eval_shape(abstract)
    net, loss, opt = holder['built']
    spec, bspec = family.param_spec(config), family.buffer_spec(config)
    for own, theirs, what in ((spec, shapes, 'parameters'),
                              (bspec, buffer_shapes, 'buffers')):
        got = {k: tuple(v.shape) for k, v in theirs.items()}
        want = {k: tuple(s) for k, (s, _) in own.items()}
        if got != want:
            odd = sorted(set(got.items()) ^ set(want.items()))[:6]
            raise AssertionError(
                "the family's %s differ from the program's net: %s"
                % (what, odd))
    sharding = None
    if traffic.get('sharding') == 'fsdp':
        from jax.sharding import Mesh
        from paddle_tpu.distributed.strategy import ShardingConfig
        sharding = ShardingConfig(mesh=Mesh(np.asarray(devices), ('data',)))
    elif traffic.get('sharding'):
        raise ValueError('unknown sharding %r' % traffic['sharding'])
    step = engine.build_train_step(net=net, loss=loss, optimizer=opt,
                                   sharding=sharding)

    def make_state(seed):
        return step.init_state(params_mod.make(spec, seed),
                               params_mod.make(bspec, seed, salt=1))
    return step, make_state, spec


def prefetcher(step, feed):
    """`engine.fit`'s own feed: a DevicePrefetcher two deep, converting
    straight to the device or, for a sharded step, to the mesh."""
    from paddle_tpu.engine import loop as fit_loop
    from paddle_tpu.io.dataloader import DevicePrefetcher
    convert = fit_loop._batch_to_device
    if step.sharding is not None:
        convert = functools.partial(fit_loop._batch_to_mesh,
                                    step._batch_sharding)
    return iter(DevicePrefetcher(feed, depth=2, convert=convert))


def checked_steps(call, step, state, family, config, make_start):
    """The first CHECK_STEPS steps through `call`, with what the comparison
    reads: each loss, every leaf of the first gradient as the optimizer got
    it (from its slots after one step; kept on the host, the device holds
    nothing of it through the window), the norm of every leaf's change over
    the steps."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def first_gradient(opt_state, start):
        return {k: family.first_gradient(config, slots, start[k])
                .astype(jnp.float32) for k, slots in opt_state.items()}

    @jax.jit
    def change_norms(now, start):
        return {k: jnp.sqrt(jnp.sum(jnp.square(now[k] - start[k])))
                for k in start}

    losses, first = [], None
    for i in range(CHECK_STEPS):
        state, loss = call(step, state)
        losses.append(loss)
        if i == 0:
            # the step's temporaries are freed before the gradient (as
            # large as the parameters, like `start`) is made beside them
            loss.block_until_ready()
            first = jax.device_get(first_gradient(state['opt'],
                                                  make_start()))
    changed = change_norms(state['params'], make_start())
    readings = {
        'losses': [float(v) for v in jax.device_get(losses)],
        'first_gradient': first,
        'change_norms': jax.device_get(changed)}
    return state, readings


def window(call, step, state, seconds, pool_batches):
    """-> (state, t0, completions, losses, dispatched, first): completion
    instants of the steps that completed from `t0` (the completion of the
    step before the first counted one) to the first completion at or after
    t0 + seconds that completes a pass over the pool; `dispatched[i]` is
    when the loop went on to dispatch behind step i; `first` is which of
    `call`'s calls the first counted step was."""
    state, current = call(step, state)
    first = call.calls
    state, pending = call(step, state)
    current.block_until_ready()
    t0 = time.perf_counter()
    completions, losses, dispatched = [], [], []
    while True:
        state, upcoming = call(step, state)
        dispatched.append(time.perf_counter())
        pending.block_until_ready()
        now = time.perf_counter()
        completions.append(now)
        losses.append(pending)
        pending = upcoming
        if now - t0 >= seconds and len(completions) % pool_batches == 0:
            break
    pending.block_until_ready()
    return state, t0, completions, losses, dispatched, first


def other_passes(intervals, batches):
    """For every interval the median of the SAME pool batch's intervals in
    the window's other passes (nan where it has no other)."""
    where = collections.defaultdict(list)
    for i, b in enumerate(batches):
        where[b].append(i)
    other = np.full(len(intervals), np.nan)
    for same in where.values():
        for i in same:
            rest = [intervals[j] for j in same if j != i]
            if rest:
                other[i] = statistics.median(rest)
    return other


def late_notices(intervals, batches):
    """-> (repaired intervals, [i, ...]): the pairs (i, i + 1) in which the
    host noticed completion i late and the device was on pace. The clock is
    read when the host wakes up, so a late wake-up makes interval i long by
    some d and interval i + 1 short by the same d. With every batch timed
    once a pass that can be told exactly: interval i is longer than its
    batch's reading in the other passes by d, more than 1% of it, and
    interval i + 1 is shorter than ITS batch's other reading by d to within
    a tenth. Both then take the other passes' reading. A stall that is not
    paid back (a compile, a starved input, a slow device) is no such pair
    and stays as it is; so does everything in a window of one pass."""
    other = other_passes(intervals, batches)
    repaired, found, i = np.array(intervals, float), [], 0
    while i + 1 < len(intervals):
        late = intervals[i] - other[i]
        back = other[i + 1] - intervals[i + 1]
        # (a comparison with nan, where a batch has no other pass, is False)
        if late > 0.01 * other[i] and abs(back - late) <= 0.1 * late:
            repaired[i:i + 2] = other[i:i + 2]
            found.append(i)
            i += 2
        else:
            i += 1
    return repaired, found


def traced_steps(call, step, state, spans, directory):
    """TRACE_STEPS steady steps under the profiler -> (state, xplane path,
    which of `call`'s calls were made under it: the step the trace ends in
    is the last of them, since the loop waits for it before it stops)."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    state, pending = call(step, state)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)
    spans.annotate = True
    under = range(call.calls, call.calls + TRACE_STEPS + 1)
    try:
        for _ in under:
            state, upcoming = call(step, state)
            pending.block_until_ready()
            pending = upcoming
        pending.block_until_ready()
    finally:
        spans.annotate = False
        jax.profiler.stop_trace()
    found = [os.path.join(d, f) for d, _, files in os.walk(directory)
             for f in files if f.endswith('.xplane.pb')]
    if len(found) != 1:
        raise RuntimeError('expected one .xplane.pb under %s, found %s'
                           % (directory, found))
    return state, found[0], under


def compiler_memory(call, step, state):
    """What the compiler's `memory_analysis` says of the step, beside the
    allocator's `peak_bytes_in_use` (PERF.md section 7 asks which of the two
    bounds a batch). Lowers the step again, so only a traced run pays it."""
    import jax
    batch, key = next(call.feed), call.rng.next_key()
    if step.sharding is not None:      # as TrainStep.__call__ places it
        key = jax.device_put(key, step.sharding.replicated())
    with call.amp.auto_cast(dtype=call.dtype):
        m = step._jit.lower(state, batch, key).compile().memory_analysis()
    return {'argument_bytes': int(m.argument_size_in_bytes),
            'output_bytes': int(m.output_size_in_bytes),
            'temp_bytes': int(m.temp_size_in_bytes),
            'alias_bytes': int(m.alias_size_in_bytes),
            'total_bytes': int(m.argument_size_in_bytes
                               + m.output_size_in_bytes
                               + m.temp_size_in_bytes
                               - m.alias_size_in_bytes)}


def memory_facts(devices):
    """Each chip's memory as the allocator reports it. `bytes_in_use` and its
    peak count the live buffers (state, batches, results); what a running
    program needs besides (its temporaries) is booked as `bytes_reserved`
    and in no `in_use` figure (PERF.md section 6: in_use + reserved is the
    compiler's `memory_analysis` total within 4%). The footprint of a chip is
    therefore the buffers that live through the window plus the largest
    reservation, or the peak of the live buffers where that is more."""
    stats = [d.memory_stats() or {} for d in devices]

    def column(key):
        return [int(s.get(key, 0)) for s in stats]
    facts = {key: column(key) for key in (
        'peak_bytes_in_use', 'bytes_in_use', 'peak_bytes_reserved',
        'bytes_limit')}
    facts['footprint_bytes'] = [
        max(peak, live + reserved) for peak, live, reserved in zip(
            facts['peak_bytes_in_use'], facts['bytes_in_use'],
            facts['peak_bytes_reserved'])]
    return facts


def run(*, cell, config, traffic, limits, family, reference, seed, seconds,
        trace, wanted, readers, t_start, devices, scratch, facts,
        wrap_step=None):
    """One run of a `train` cell -> the result object. `wrap_step` is for
    the tests: it is handed each built step, to break it underneath."""
    import jax
    _T0[0] = t_start
    compiles = Compiles()
    spans = Spans()
    chips = len(devices)
    rows = traffic['batch_per_chip'] * chips
    device0 = devices[0]
    say(phase='start', cell=cell['name'], seed=seed, chips=chips, rows=rows,
        device_kind=device0.device_kind, jax=jax.__version__, **facts)

    # ------------------------------------------------------------ set-up
    twin = family.stochastic(config)
    step, make_state, spec = build_step(family, config, traffic, devices,
                                        deterministic=False)
    if wrap_step is not None:
        step = wrap_step(step)
    pool = family.make_pool(config, traffic, seed, traffic['pool_batches'],
                            rows)
    pool_batches = len(pool)
    feed = Feed(family, traffic, pool, seed, remember=CHECK_STEPS)
    feed_iter = prefetcher(step, feed)
    call = Caller(feed_iter, spans, config['compute_dtype'])
    try:
        def make_start():       # made anew where needed, not kept
            return params_mod.make(spec, seed)

        layout = getattr(family, 'layout_digest', None)
        say(phase='built', pool_batches=pool_batches,
            **({'layout': layout(pool)} if layout else {}))
        if twin:
            checked, make_checked, _ = build_step(
                family, config, traffic, devices, deterministic=True)
            if wrap_step is not None:
                checked = wrap_step(checked)
            state, readings = checked_steps(call, checked,
                                            make_checked(seed), family,
                                            config, make_start)
            del state, checked, make_checked
            state = make_state(seed)
        else:
            state, readings = checked_steps(call, step, make_state(seed),
                                            family, config, make_start)
        say(phase='checked_steps', twin=twin, losses=readings['losses'],
            **compiles.facts())
        for _ in range(WARMUP_STEPS):
            state, loss = call(step, state)
            loss.block_until_ready()
        setup_compiles = compiles.facts()
        mark = spans.mark()
        in_window = compiles.count
        setup_s = time.perf_counter() - t_start

        # ------------------------------------------------------------ window
        # set-up leaves a large heap (two traced 24-layer nets); a full
        # collection of it stops the loop for 110-150 ms (my chip runs, PR
        # 24). What set-up made is set aside, so the window collects only
        # what the window makes.
        gc.collect()
        gc.freeze()
        state, t0, completions, losses, dispatched, first = window(
            call, step, state, seconds, pool_batches)
        if first != feed.lead:
            raise RuntimeError('the first counted step is call %d and the '
                               'feed began its passes at batch %d'
                               % (first, feed.lead))
        compiles_in_window = compiles.count - in_window
        window_mark = spans.mark()
        memory = memory_facts(devices)
        losses = [float(v) for v in jax.device_get(losses)]
        xplane, under_trace = None, ()
        if trace:
            state, xplane, under_trace = traced_steps(
                call, step, state, spans, os.path.join(scratch, 'trace'))
            say(phase='memory_analysis', per_device=compiler_memory(
                call, step, state), **memory)
    finally:
        feed_iter.close()
    batches = [jax.tree_util.tree_map(np.asarray, b) for b in feed.first]
    order = list(feed.order)
    del state, pool, feed, call
    gc.collect()

    # ------------------------------------------------------------- numbers
    intervals = np.diff([t0] + completions) * 1e3
    batch_of = order[first:first + len(intervals)]  # each interval's batch
    repaired, late = late_notices(intervals, batch_of)
    length = completions[-1] - t0
    samples_per_s = len(completions) * rows / length
    peak = peaks.peaks_of(device0.device_kind)
    flops = family.flops_per_sample(config, traffic)
    values = {
        'samples_per_s': samples_per_s,
        'step_ms_p95': float(np.quantile(repaired, 0.95)),
        'mfu_pct': 100.0 * samples_per_s * flops
        / (chips * peak['bf16_flops_per_s']),
        'setup_s': setup_s,
    }
    slowest = int(np.argmax(intervals))
    before = completions[slowest - 1] if slowest else t0
    say(phase='slowest_step', index=slowest, ms=float(intervals[slowest]),
        pool_batch=batch_of[slowest], late_notice=slowest in late,
        host_ms_before_wait=(dispatched[slowest] - before) * 1e3,
        wait_ms=(completions[slowest] - dispatched[slowest]) * 1e3)
    say(phase='window', steps=len(completions), window_s=length,
        passes=len(completions) // pool_batches, late_notices=len(late),
        late_ms=[float(intervals[i] - repaired[i]) for i in late],
        step_ms_median=statistics.median(intervals),
        step_ms_min=float(min(intervals)), step_ms_max=float(max(intervals)),
        step_ms_p95_as_read=float(np.quantile(intervals, 0.95)),
        compiles_in_window=compiles_in_window,
        flops_per_sample=flops, setup=setup_compiles, **memory)

    # --------------------------------------------------------------- check
    t_check = time.perf_counter()
    rows_out, ok = check.compare(
        readings,
        reference.follow_steps(config, config['optimizer'],
                               params_mod.make(spec, seed), batches),
        limits)
    head = max(1, len(losses) // 10)
    fall = statistics.median(losses[:head]) - statistics.median(losses[-head:])
    timed = [('finite_losses', float(sum(not np.isfinite(v) for v in losses)),
              0.0, all(np.isfinite(losses)), 'of %d' % len(losses)),
             ('compiles_in_window', float(compiles_in_window), 0.0,
              compiles_in_window == 0, ''),
             ('loss_fall', fall, limits['loss_fall'],
              bool(fall >= limits['loss_fall']),
              'median of the first %d losses minus median of the last %d, '
              'at least the limit' % (head, head))]
    for name, value, limit, good, note in rows_out + timed:
        say(phase='compare', number=name, value=value, limit=limit, ok=good,
            note=note)
    correct = bool(ok and all(r[3] for r in timed))
    say(phase='check', correct=correct,
        seconds=round(time.perf_counter() - t_check, 2))

    # -------------------------------------------------------------- result
    device = {'platform': device0.platform, 'kind': device0.device_kind,
              'count': len(jax.devices()),
              'memory_peak_bytes': max(memory['footprint_bytes'])}
    result = {'correct': correct, 'attempted': len(completions),
              'failed': int(sum(not np.isfinite(v) for v in losses)),
              'device': device}
    if not trace:
        result['metrics'] = {m['name']: {'value': float(values[m['name']]),
                                         'unit': m['unit']} for m in wanted}
        return result
    reduced = trace_mod.reduce(
        trace_mod.read_xplane(xplane, span_names=('input.wait', 'step.key',
                                                  'step.dispatch')),
        scopes=sorted({s for r in readers.values()
                       for s in getattr(r, 'SCOPES', ())}))
    # what the window read for the batches of the calls made under the
    # trace: a pool batch's intervals (a late notice repaired), their mean
    batch_ms = {b: float(np.mean([ms for ms, bb in zip(repaired, batch_of)
                                  if bb == b])) for b in set(batch_of)}
    # the calls made under the trace, each from the start of its
    # `input.wait` to the end of its `step.dispatch`: what the program
    # recorded inside them (a get, the step's counters) is theirs
    behind = spans.records[window_mark:]
    calls_ns = [(w[1], d[2]) for w, d in zip(
        (r for r in behind if r[0] == 'input.wait'),
        (r for r in behind if r[0] == 'step.dispatch'))]
    context = {'spans': spans, 'window': (mark, window_mark),
               'traced_step_ms': [batch_ms[order[k]] for k in under_trace],
               'traced_calls_ns': calls_ns[-len(under_trace):],
               'compiles_in_window': compiles_in_window, 'trace': reduced,
               'config': config, 'traffic': traffic, 'rows': rows,
               'chips': chips, 'peaks': peak, 'family': family,
               'flops_per_sample': flops}
    metrics = {}
    for m in wanted:
        value = readers[m['name']].read(context)
        if value is not None:
            metrics[m['name']] = {'value': float(value), 'unit': m['unit']}
    result['metrics'] = metrics
    per_chip = list(reduced.values())
    if per_chip:
        device['busy_s'] = sum(c['busy_s'] for c in per_chip) / len(per_chip)
        device['window_s'] = sum(c['window_s'] for c in per_chip) \
            / len(per_chip)
        worst = max(per_chip, key=lambda c: c['window_s'] - c['busy_s'])
        result['breakdown'] = {'device_ops': worst['device_ops'],
                               'idle_gaps': worst['idle_gaps']}
        say(phase='trace', traced_step_ms=context['traced_step_ms'],
            per_chip={str(k): {
                kk: vv for kk, vv in v.items()
                if kk not in ('device_ops', 'idle_gaps')}
                for k, v in reduced.items()})
    return result
