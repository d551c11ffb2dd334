"""The train path's own measurement (ISSUE 25): spans and counters at the
layer boundaries — the prefetcher's two threads, the step's dispatch, the
phases inside the compiled step — on the profiler's clock.

- ``observability/spans.py``: absolute ``perf_counter_ns`` stamps, ``step``
  and ``parent`` on a record, a ring that drops its OLDEST, a bridge into
  ``jax.profiler`` that needs no telemetry and no ``utils.profiler``;
- ``io.DevicePrefetcher``: ``prefetch.source`` / ``convert`` / ``put_wait``
  on the worker thread, ``prefetch.get_wait`` + ``prefetch.gets`` /
  ``prefetch.starved`` + the depth gauge on the consumer, a flight record
  for a boundary slower than a second;
- ``engine.TrainStep``: ``engine.dispatch`` around the jit call alone
  (``train_step`` with its ``step_num`` in a profiler trace), the scopes
  ``forward`` / ``update`` / ``guard`` in every instruction's ``op_name``
  with the instructions themselves unchanged, ``costs``' phase map;
- ``engine.fit(on_step=)``: every step once, in order, one behind.
"""
import collections
import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import engine, nn
from paddle_tpu import observability as obs
from paddle_tpu.io.dataloader import DevicePrefetcher
from paddle_tpu.nn.layer_base import buffer_values, param_values
from paddle_tpu.observability import costs, flight, spans

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _data(n=6, batch=8, feat=16, seed=0):
    rng = np.random.RandomState(seed)
    return [([rng.rand(batch, feat).astype('float32')],
             [rng.rand(batch, 1).astype('float32')]) for _ in range(n)]


def _net(seed=3):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 1))
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=net.parameters())
    return net, opt


def _step_and_args(**kw):
    net, opt = _net()
    step = engine.build_train_step(net=net, loss=nn.MSELoss(), optimizer=opt,
                                   **kw)
    state = step.init_state(param_values(net), buffer_values(net))
    (bx, by), = _data(1)
    batch = (tuple(jnp.asarray(v) for v in bx),
             tuple(jnp.asarray(v) for v in by))
    return step, state, batch, jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# observability/spans.py
# ---------------------------------------------------------------------------

def test_ring_drops_the_oldest_and_counts_it(monkeypatch):
    # a process's first enable() writes `paddle_tpu.import`: into the real
    # ring, so that the count below does not depend on which test came first
    obs.enable()
    monkeypatch.setattr(spans, '_events', collections.deque(maxlen=4))
    for i in range(7):
        with obs.span('s%d' % i):
            pass
    assert [e['name'] for e in obs.trace_events()] == ['s3', 's4', 's5', 's6']
    assert spans.dropped() == 3
    spans.clear()
    assert spans.dropped() == 0 and obs.trace_events() == []


def test_record_is_stamped_on_perf_counter_ns():
    obs.enable()
    before = time.perf_counter_ns()
    with obs.span('stamped'):
        pass
    after = time.perf_counter_ns()
    ev, = obs.trace_events()
    assert before <= ev['t0_ns'] <= ev['t1_ns'] <= after
    # the Chrome fields are the same instant, not an offset from an epoch
    assert ev['ts'] == pytest.approx(ev['t0_ns'] / 1e3)
    assert ev['dur'] == pytest.approx((ev['t1_ns'] - ev['t0_ns']) / 1e3)


def test_record_carries_step_and_parent():
    obs.enable()
    with obs.span('outer', step=41):
        with obs.span('inner'):
            pass
        with obs.span('second'):
            pass
    by = {e['name']: e for e in obs.trace_events()}
    assert by['outer']['step'] == 41 and by['outer']['parent'] is None
    assert by['inner']['parent'] == by['outer']['span_id']
    assert by['second']['parent'] == by['outer']['span_id']
    assert by['inner']['step'] is None


def test_parent_is_the_enclosing_span_of_its_own_thread():
    obs.enable()

    def other():
        with obs.span('elsewhere'):
            pass
    with obs.span('here'):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    by = {e['name']: e for e in obs.trace_events()}
    assert by['elsewhere']['parent'] is None
    assert by['elsewhere']['tid'] != by['here']['tid']


def test_bridge_no_longer_asks_utils_profiler():
    from paddle_tpu.utils import profiler as prof
    assert not hasattr(spans, '_device_trace_active')
    # telemetry off, no session: a span is still a context manager that
    # records nothing, and annotate() hands out the raw annotation
    with obs.span('quiet', step=1):
        pass
    assert obs.trace_events() == []
    assert isinstance(prof.annotate('r'), jax.profiler.TraceAnnotation)


# ---------------------------------------------------------------------------
# the spans in a profiler trace, under a plain jax.profiler.start_trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def host_plane(tmp_path_factory):
    """fit() under `jax.profiler.start_trace` with telemetry OFF -> the
    events of the xplane's /host:CPU plane as (name, stats, start, end)."""
    from jax.profiler import ProfileData
    obs.disable()
    directory = str(tmp_path_factory.mktemp('trace'))
    net, opt = _net()
    jax.profiler.start_trace(directory)
    try:
        engine.fit(net, nn.MSELoss(), opt, _data(5), epochs=1, prefetch=2)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(directory + '/**/*.xplane.pb', recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == '/host:CPU':
            for line in plane.lines:
                for ev in line.events:
                    events.append((ev.name, dict(ev.stats), ev.start_ns,
                                   ev.start_ns + ev.duration_ns))
    assert not obs.trace_events()           # telemetry stayed off
    return events


@pytest.mark.parametrize('name', ['prefetch.source', 'prefetch.convert',
                                  'prefetch.put_wait', 'prefetch.get_wait'])
def test_xplane_holds_the_prefetcher_span(host_plane, name):
    found = [e for e in host_plane if e[0] == name]
    assert len(found) >= 5, sorted({e[0] for e in host_plane})[:40]
    assert all(end >= start for _, _, start, end in found)


def test_xplane_holds_train_step_with_its_step_num(host_plane):
    steps = sorted(e[1].get('step_num') for e in host_plane
                   if e[0] == 'train_step')
    assert steps == [0, 1, 2, 3, 4]


def test_xplane_spans_share_one_clock(host_plane):
    """A step's batch was converted before the step was dispatched: the
    worker thread's and the training thread's events order on one clock."""
    convert = sorted(e[3] for e in host_plane if e[0] == 'prefetch.convert')
    dispatch = sorted(e[2] for e in host_plane if e[0] == 'train_step')
    assert all(c <= d for c, d in zip(convert, dispatch))


# ---------------------------------------------------------------------------
# io.DevicePrefetcher
# ---------------------------------------------------------------------------

def _slow_source(n, seconds):
    for i in range(n):
        time.sleep(seconds)
        yield np.full((4,), i, 'float32')


def _counters():
    return obs.snapshot()['counters']


def test_slow_source_starves_every_get():
    obs.enable()
    for _ in DevicePrefetcher(_slow_source(5, 0.03), depth=2,
                              convert=lambda b: b):
        pass
    c = _counters()
    assert c['prefetch.gets'] == 6              # 5 batches + the sentinel
    assert c['prefetch.starved'] >= 4           # (a loaded host may slip one)


def test_fast_source_starves_hardly_any_get():
    obs.enable()
    for _ in DevicePrefetcher(_slow_source(6, 0.0), depth=2,
                              convert=lambda b: b):
        time.sleep(0.03)                        # the consumer is the slow one
    c = _counters()
    assert c['prefetch.gets'] == 7
    assert c['prefetch.starved'] <= 2           # the first, and a slip


def test_get_wait_records_carry_the_counters_as_they_stood():
    obs.enable()
    for _ in DevicePrefetcher(_slow_source(4, 0.02), depth=2,
                              convert=lambda b: b):
        pass
    gets = [e['args'] for e in obs.trace_events()
            if e['name'] == 'prefetch.get_wait']
    assert [a['gets'] for a in gets] == [1, 2, 3, 4, 5]
    assert [a['starved'] for a in gets] == sorted(a['starved'] for a in gets)
    assert gets[-1]['starved'] == _counters()['prefetch.starved']
    assert all(a['starved'] - b['starved'] == (1 if a['depth'] == 0 else 0)
               for a, b in zip(gets[1:], gets))


def test_depth_gauge_reads_the_depth_before_the_get():
    """Two batches ready and a source that has stopped: the get finds depth
    2. The old reading (after the get) would have said 1."""
    obs.enable()
    ready = threading.Event()

    def source():
        for _ in range(3):
            yield np.zeros(2, 'float32')
        ready.set()             # the worker came back for a fourth: the
        time.sleep(0.5)         # second and third are in the queue
        yield np.zeros(2, 'float32')
    it = iter(DevicePrefetcher(source(), depth=2, convert=lambda b: b))
    try:
        next(it)                # starts the worker
        assert ready.wait(timeout=10)
        next(it)
        assert obs.snapshot()['gauges']['dataloader.prefetch_depth'] == 2
        depths = [e['args']['depth'] for e in obs.trace_events()
                  if e['name'] == 'prefetch.get_wait']
        assert len(depths) == 2 and depths[1] == 2
    finally:
        it.close()


def test_worker_spans_carry_batch_and_bytes():
    obs.enable()
    batches = [(np.zeros((8, 4), 'float32'), np.zeros((8,), 'int32'))] * 3
    for _ in DevicePrefetcher(batches, depth=2, convert=lambda b: b):
        pass
    by = collections.defaultdict(list)
    for e in obs.trace_events():
        by[e['name']].append(e)
    assert [e['args']['batch'] for e in by['prefetch.convert']] == [0, 1, 2]
    assert {e['args']['bytes'] for e in by['prefetch.convert']} == {
        8 * 4 * 4 + 8 * 4}
    assert len(by['prefetch.source']) == 4      # the last one hit the end
    assert len(by['prefetch.put_wait']) == 3
    worker = {e['tid'] for e in by['prefetch.convert']}
    consumer = {e['tid'] for e in by['prefetch.get_wait']}
    assert len(worker) == 1 and worker.isdisjoint(consumer)


def test_slow_boundary_lands_in_the_flight_ring_with_telemetry_off(
        monkeypatch):
    from paddle_tpu.io import dataloader
    monkeypatch.setattr(dataloader, '_SLOW_S', 0.02)
    flight.clear()

    def convert(batch):
        time.sleep(0.05)
        return batch
    for _ in DevicePrefetcher(_slow_source(2, 0.0), depth=2,
                              convert=convert):
        pass
    slow = [r for r in flight.records() if r['ev'] == 'prefetch.slow']
    assert {r['what'] for r in slow} == {'prefetch.convert',
                                         'prefetch.get_wait'}
    first = [r for r in slow if r['what'] == 'prefetch.convert'][0]
    assert first['step'] == 0 and first['seconds'] >= 0.05
    assert 'depth' in first
    assert obs.trace_events() == []             # telemetry was off


# ---------------------------------------------------------------------------
# engine.TrainStep: the dispatch span
# ---------------------------------------------------------------------------

def test_dispatch_span_carries_the_step_number():
    obs.enable()
    step, state, batch, key = _step_and_args()
    for _ in range(3):
        state, _ = step(state, batch, key)
    found = [e for e in obs.trace_events() if e['name'] == 'engine.dispatch']
    assert [e['step'] for e in found] == [0, 1, 2]
    snap = obs.snapshot()
    assert snap['histograms']['engine.dispatch_ms']['count'] == 3
    assert snap['counters']['engine.dispatches'] == 3
    assert snap['counters']['engine.steps'] == 3


def test_engine_step_ms_is_gone():
    """The enqueue is not a step: no histogram under a step's name, and the
    checkpoint-stall detector does not divide by one."""
    obs.enable()
    step, state, batch, key = _step_and_args()
    state, _ = step(state, batch, key)
    snap = obs.snapshot()
    assert 'engine.step_ms' not in snap['histograms']
    assert 'engine.step.calls' not in snap['counters']
    snapshot = {'histograms': {
        'checkpoint.save_stall_ms': {'count': 4, 'mean': 50.0, 'sum': 200.0},
        'engine.step_ms': {'count': 100, 'mean': 7.0, 'sum': 700.0}},
        'counters': {}, 'gauges': {}}
    assert not [d for d in obs.diagnose(snapshot=snapshot)
                if d['cause'] == 'checkpoint_stall']


def test_boundaries_cost_under_50_us_a_step_when_off():
    """Off (no telemetry, no profiler session) a step crosses five
    boundaries: three on the worker thread, two on the consumer's."""
    def one_step(n):
        for name in ('prefetch.source', 'prefetch.convert',
                     'prefetch.put_wait', 'prefetch.get_wait'):
            with obs.span(name, batch=n):
                pass
        with obs.timer('engine.dispatch', step=n, annotation='train_step',
                       k=1):
            pass
    one_step(0)
    best = float('inf')
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for n in range(200):
            one_step(n)
        best = min(best, (time.perf_counter_ns() - t0) / 200)
    assert best < 50_000, 'ns a step: %d' % best
    assert obs.trace_events() == []


# ---------------------------------------------------------------------------
# the scopes inside the compiled step, and costs' phase map
# ---------------------------------------------------------------------------

_OP = re.compile(r'^\s+(?:ROOT\s+)?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*'
                 r'op_name="([^"]*)"', re.M)


def _compiled_text(**kw):
    step, state, batch, key = _step_and_args(**kw)
    return step._jit.lower(state, batch, key).compile().as_text()


def _strip(text):
    """The module's text without what only describes where it came from:
    every instruction's `metadata={...}` and the header's tables of source
    files, functions, locations and stack frames."""
    blocks = [b for b in text.split('\n\n')
              if b.split('\n', 1)[0] not in (
                  'FileNames', 'FunctionNames', 'FileLocations',
                  'StackFrames')]
    return re.sub(r',? ?metadata=\{[^}]*\}', '', '\n\n'.join(blocks))


def test_every_matmul_is_forward_or_backward_and_adamw_is_update():
    ops = _OP.findall(_compiled_text())
    dots = [name for kind, name in ops if kind in ('dot', 'convolution')]
    assert len(dots) >= 4           # 2 forward, 2 backward at this size
    assert all('jvp(forward)' in n for n in dots), dots
    assert any('transpose(jvp(forward))' in n for n in dots)
    assert any('transpose(' not in n for n in dots)
    # AdamW's arithmetic: its square root is nowhere else in this step
    roots = [name for kind, name in ops if kind in ('sqrt', 'rsqrt')]
    assert roots and all('/update/' in n for n in roots), roots


def test_guard_bookkeeping_is_under_guard():
    ops = _OP.findall(_compiled_text(nan_guard=True))
    finite = [name for kind, name in ops if kind == 'is-finite']
    assert finite and all('/guard/' in n for n in finite), finite


def test_scopes_change_metadata_only(monkeypatch):
    import contextlib
    scoped = _compiled_text(nan_guard=True)
    monkeypatch.setattr(jax, 'named_scope',
                        lambda name: contextlib.nullcontext())
    unscoped = _compiled_text(nan_guard=True)
    assert 'jvp(forward)' in scoped and '/update/' in scoped
    assert 'jvp(forward)' not in unscoped and '/update/' not in unscoped
    assert _strip(scoped) == _strip(unscoped)


def test_telemetry_leaves_the_compiled_step_alone():
    """ISSUE 39's spans and records are host-side: with telemetry on the
    step compiles to the text it compiles to with telemetry off."""
    off = _compiled_text(nan_guard=True)
    obs.enable()
    assert _strip(_compiled_text(nan_guard=True)) == _strip(off)


_FUSED = '''HloModule jit_step

%fused_computation (p0: f32[8,4], p1: f32[8,2], p2: f32[4,2]) -> f32[4,2] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[8,2]{1,0} parameter(1)
  %dot.1 = f32[4,2]{1,0} dot(%p0, %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(forward))/linear/dot_general" source_file="x.py" source_line=3}
  %p2 = f32[4,2]{1,0} parameter(2)
  %c = f32[] constant(0.01)
  %b = f32[4,2]{1,0} broadcast(%c), dimensions={}
  %mul.2 = f32[4,2]{1,0} multiply(%dot.1, %b), metadata={op_name="jit(step)/update/mul"}
  ROOT %sub.3 = f32[4,2]{1,0} subtract(%p2, %mul.2), metadata={op_name="jit(step)/update/sub"}
}

%region_0.7 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/jvp(forward)/reduce_sum"}
}

ENTRY %main.20 (x: f32[8,4], g: f32[8,2], w: f32[4,2]) -> (f32[4,2], f32[]) {
  %x = f32[8,4]{1,0} parameter(0), metadata={op_name="x"}
  %g = f32[8,2]{1,0} parameter(1)
  %w = f32[4,2]{1,0} parameter(2)
  %dot.4 = f32[8,2]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(forward)/linear/dot_general"}
  %zero = f32[] constant(0)
  %reduce.5 = f32[] reduce(%dot.4, %zero), dimensions={0,1}, to_apply=%region_0.7, metadata={op_name="jit(step)/jvp(forward)/reduce_sum"}
  %multiply_subtract_fusion = f32[4,2]{1,0} fusion(%x, %g, %w), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(step)/update/sub"}
  %is-finite.6 = pred[] is-finite(%reduce.5), metadata={op_name="jit(step)/guard/is_finite"}
  %copy.8 = f32[4,2]{1,0} copy(%multiply_subtract_fusion)
  ROOT %tuple.9 = (f32[4,2]{1,0}, f32[]) tuple(%copy.8, %reduce.5)
}
'''


def test_phase_map_calls_a_gradient_matmul_fused_with_its_update_mixed():
    phases = costs.instruction_phases(_FUSED)
    # the fusion's own op_name says `update`; what it holds says more
    assert phases['multiply_subtract_fusion'] == 'backward+update'
    assert phases['dot.4'] == 'forward' and phases['reduce.5'] == 'forward'
    assert phases['dot.1'] == 'backward' and phases['sub.3'] == 'update'
    assert phases['is-finite.6'] == 'guard'
    assert phases['copy.8'] == 'other' and phases['x'] == 'other'


@pytest.mark.parametrize('op_name,phase', [
    ('jit(step)/jit(main)/jvp(forward)/bert/dot_general', 'forward'),
    ('jit(step)/transpose(jvp(forward))/bert/dot_general', 'backward'),
    ('jit(step)/while/body/vmap(jvp(forward))/mul', 'forward'),
    ('jit(step)/update/sqrt', 'update'),
    ('jit(step)/guard/cond/branch_1_fun/select_n', 'guard'),
    ('jit(step)/fsdp.gather/sharding_constraint', 'other'),
    ('jit(step)/forward_hook/add', 'other'),
])
def test_phase_of_op_name(op_name, phase):
    assert costs.phase_of_op_name(op_name) == phase


def test_each_train_step_gets_its_own_phase_map():
    """Two steps built in one process (the benchmark's dropout-off twin and
    its timed step): the second capture is no hit of the first."""
    obs.enable()
    maps = []
    for guard in (False, True):
        step, state, batch, key = _step_and_args(nan_guard=guard)
        step(state, batch, key)
        maps.append(costs.phases(step.cost_label))
        meta = costs.entry(step.cost_label)['meta']
        assert sum(meta['phase_instructions'].values()) == len(maps[-1])
    plain, guarded = maps
    assert 'guard' not in plain.values() and 'guard' in guarded.values()
    assert {'forward', 'backward'} <= set(plain.values())
    assert any('update' in p for p in plain.values())
    # the map is of the compile the jit runs: same instruction names
    text = step._jit.lower(state, batch, key).compile().as_text()
    assert set(guarded) == set(costs.instruction_phases(text))
    obs.disable()
    assert costs.capture('off', None) is None


# ---------------------------------------------------------------------------
# engine.fit(on_step=)
# ---------------------------------------------------------------------------

def _fit(on_step, n=6):
    net, opt = _net()
    report = engine.fit(net, nn.MSELoss(), opt, _data(n), epochs=1,
                        log_every=1, prefetch=2, on_step=on_step)
    return report, [np.asarray(p.numpy()) for p in net.parameters()]


def test_on_step_sees_every_step_once_in_order_one_behind():
    obs.enable()
    seen = []

    def on_step(i, result):
        seen.append((i, obs.snapshot()['counters']['engine.dispatches'],
                     float(result.loss)))
    report, _ = _fit(on_step)
    assert [i for i, _, _ in seen] == [0, 1, 2, 3, 4, 5]
    # step i was handed over after step i + 1 was dispatched; the last one
    # after the loop
    assert [d for _, d, _ in seen] == [2, 3, 4, 5, 6, 6]
    assert [loss for _, _, loss in seen] == report['loss']


def test_blocking_on_step_leaves_the_training_bit_identical():
    def blocking(i, result):
        result.loss.raw.block_until_ready()
        time.sleep(0.01)
    plain_report, plain = _fit(None)
    report, params = _fit(blocking)
    assert report['loss'] == plain_report['loss']
    assert all(np.array_equal(a, b) for a, b in zip(params, plain))
