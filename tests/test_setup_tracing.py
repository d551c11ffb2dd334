"""Set-up seen from inside the program (ISSUE 39): JAX's compile-path events
as span records under the program span that caused them, and spans where
set-up's own work happens.

- ``observability/interpose.py``: ``jax.trace`` / ``jax.lower`` /
  ``jax.backend`` / ``jax.cache_load`` records with ``fun_name``, ``parent``
  and, from the persistent cache's events, ``cache: 'hit' | 'miss'``; the
  listeners are registered only while telemetry is on; the two ``jax.*``
  histograms are gone, the three counters stay;
- ``engine/builder.py``: ``engine.build``, ``engine.init_state`` (``bytes``,
  ``sharded``) with ``engine.place_state`` inside it, ``first`` on a step's
  dispatch 0, the cost capture AFTER that dispatch and under its own span;
- ``paddle_tpu/__init__.py``: the import's stamps, one record at the first
  ``enable()``;
- none of it changes a lowered step (the compiled text: the digest tests of
  ``tests/test_train_tracing.py``).
"""
import hashlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import engine, nn
from paddle_tpu import observability as obs
from paddle_tpu.nn.layer_base import buffer_values, param_values
from paddle_tpu.observability import costs, interpose, spans, state

pytestmark = pytest.mark.obs

PHASES = ('jax.trace', 'jax.lower', 'jax.backend')


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _fresh(scale=2.0):
    """A jit nothing has compiled yet (its own function object)."""
    def fresh(x):
        return jnp.tanh(x) * scale
    return jax.jit(fresh)


def _records(name=None, fun=None):
    return [e for e in obs.trace_events()
            if (name is None or e['name'] == name)
            and (fun is None or (e.get('args') or {}).get('fun_name') == fun)]


def _step_and_args(**kw):
    paddle.seed(3)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 1))
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=net.parameters())
    step = engine.build_train_step(net=net, loss=nn.MSELoss(), optimizer=opt,
                                   **kw)
    state_ = step.init_state(param_values(net), buffer_values(net))
    rng = np.random.RandomState(0)
    batch = ((jnp.asarray(rng.rand(8, 16).astype('float32')),),
             (jnp.asarray(rng.rand(8, 1).astype('float32')),))
    return step, state_, batch, jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# observability/interpose.py: the compile path's phases as span records
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('phase,fun', [('jax.trace', 'fresh'),
                                       ('jax.lower', 'jit(fresh)'),
                                       ('jax.backend', 'jit(fresh)')])
def test_compile_under_a_span_leaves_a_record_with_that_parent(phase, fun):
    obs.enable()
    with obs.span('outer'):
        _fresh()(jnp.ones(4)).block_until_ready()
    outer, = _records('outer')
    ev, = _records(phase, fun)
    assert ev['parent'] == outer['span_id'] and ev['ph'] == 'X'
    assert outer['t0_ns'] <= ev['t0_ns'] <= ev['t1_ns'] <= outer['t1_ns']
    assert ev['tid'] == outer['tid']


def test_phases_of_one_program_follow_one_another():
    obs.enable()
    _fresh()(jnp.ones(4)).block_until_ready()
    trace, = _records('jax.trace', 'fresh')
    lower, = _records('jax.lower', 'jit(fresh)')
    backend, = _records('jax.backend', 'jit(fresh)')
    assert trace['t1_ns'] <= lower['t1_ns'] <= backend['t1_ns']
    assert lower['t0_ns'] >= trace['t0_ns']
    assert backend['t0_ns'] >= lower['t0_ns']


def test_compile_under_no_span_has_no_parent():
    obs.enable()
    _fresh()(jnp.ones(4)).block_until_ready()
    found = [e for p in PHASES for e in _records(p)
             if 'fresh' in e['args']['fun_name']]
    assert len(found) == 3 and all(e['parent'] is None for e in found)


def test_a_trace_inside_a_trace_has_its_own_record_inside_the_outer():
    """Why a reader takes the union of a name's intervals."""
    inner = _fresh()

    @jax.jit
    def outer_fn(x):
        return inner(x) + 1
    obs.enable()
    outer_fn(jnp.ones(4)).block_until_ready()
    outer, = _records('jax.trace', 'outer_fn')
    nested, = _records('jax.trace', 'fresh')
    assert outer['t0_ns'] <= nested['t0_ns'] <= nested['t1_ns'] \
        <= outer['t1_ns']
    # one program was lowered and compiled, not two
    assert not _records('jax.backend', 'jit(fresh)')
    assert len(_records('jax.backend', 'jit(outer_fn)')) == 1


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compile cache in a fresh directory, every program
    admitted; handed back without a live cache (the cache is process-global
    and decided once: tests/test_paged_serving.py says what that breaks)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update('jax_compilation_cache_dir', str(tmp_path))
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    cc.reset_cache()
    yield
    jax.config.update('jax_compilation_cache_dir', None)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 1.0)
    cc.reset_cache()


def test_persistent_cache_says_miss_then_hit_with_a_load(persistent_cache):
    obs.enable()
    x = jnp.ones(4)
    _fresh(3.25)(x).block_until_ready()
    first, = _records('jax.backend', 'jit(fresh)')
    assert first['args']['cache'] == 'miss'
    assert not _records('jax.cache_load')
    # a new process in all that matters: the same program, not in memory
    jax.clear_caches()
    _fresh(3.25)(x).block_until_ready()
    _, second = _records('jax.backend', 'jit(fresh)')
    assert second['args']['cache'] == 'hit'
    load, = _records('jax.cache_load')
    assert load['parent'] == second['span_id']
    assert load['args']['fun_name'] == 'jit(fresh)'
    assert second['t0_ns'] - 5e6 <= load['t0_ns'] <= load['t1_ns'] \
        <= second['t1_ns']
    counters = obs.snapshot()['counters']
    assert counters['jax.cache_misses'] >= 1
    assert counters['jax.cache_hits'] == 1


def test_without_a_persistent_cache_a_backend_record_says_nothing_of_it():
    obs.enable()
    _fresh()(jnp.ones(4)).block_until_ready()
    ev, = _records('jax.backend', 'jit(fresh)')
    assert 'cache' not in ev['args']


def test_the_two_histograms_are_gone_and_the_counters_stay():
    obs.enable()
    _fresh()(jnp.ones(4)).block_until_ready()
    snap = obs.snapshot()
    assert not [k for k in snap['histograms'] if k.startswith('jax.')]
    assert snap['counters']['jax.traces'] >= 1
    assert snap['counters']['jax.compiles'] >= 1
    assert snap['counters']['jax.compile_ms'] > 0


def _listeners():
    from jax._src import monitoring
    return (monitoring._event_duration_secs_listeners,
            monitoring._event_listeners)


def test_telemetry_off_no_record_and_no_listener():
    durations, events = _listeners()
    assert interpose._on_duration not in durations
    assert interpose._on_event not in events
    _fresh()(jnp.ones(4)).block_until_ready()
    step, state_, batch, key = _step_and_args()
    step(state_, batch, key)
    assert obs.trace_events() == []
    assert spans.record('by.hand', 1, 2) is None
    assert not [k for k in obs.snapshot()['counters'] if k.startswith('jax.')]


def test_listeners_come_and_go_with_the_switch():
    obs.enable()
    obs.enable()
    durations, events = _listeners()
    assert durations.count(interpose._on_duration) == 1
    assert events.count(interpose._on_event) == 1
    obs.disable()
    durations, events = _listeners()
    assert interpose._on_duration not in durations
    assert interpose._on_event not in events
    obs.disable()                   # a second time: nothing to take out


# ---------------------------------------------------------------------------
# engine/builder.py: spans where set-up's work happens
# ---------------------------------------------------------------------------

def test_build_and_init_state_have_spans_and_the_state_its_bytes():
    obs.enable()
    step, state_, _, _ = _step_and_args()
    build, = _records('engine.build')
    init, = _records('engine.init_state')
    assert build['t1_ns'] <= init['t0_ns']
    want = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(state_))
    assert init['args'] == {'sharded': False, 'bytes': want}
    assert want == step.sharding_info(state_)['state_bytes_per_device']
    # creation's one-op programs are its children, not a caller's
    inside = [e for p in PHASES for e in _records(p)
              if e['parent'] == init['span_id']]
    assert all(init['t0_ns'] <= e['t0_ns'] and e['t1_ns'] <= init['t1_ns']
               for e in inside)


def test_dispatch_number_0_carries_first_and_the_steps_compile():
    obs.enable()
    step, state_, batch, key = _step_and_args()
    for _ in range(3):
        state_, _ = step(state_, batch, key)
    zero, one, two = _records('engine.dispatch')
    assert zero['args'] == {'k': 1, 'first': True} and zero['step'] == 0
    assert one['args'] == two['args'] == {'k': 1}
    for phase, fun in (('jax.lower', 'jit(step)'),
                       ('jax.backend', 'jit(step)')):
        ev, = _records(phase, fun)
        assert ev['parent'] == zero['span_id'], phase
    # the step is traced once; the capture's question is answered from
    # jit's cache, which JAX reports as a trace of next to no time
    trace, again = _records('jax.trace', 'step')
    capture, = _records('costs.capture')
    assert trace['parent'] == zero['span_id']
    assert again['parent'] == capture['span_id']
    assert again['t1_ns'] - again['t0_ns'] < (trace['t1_ns']
                                              - trace['t0_ns']) / 10
    # self time: the enqueue and the executable's way to the device
    covered = sum(e['t1_ns'] - e['t0_ns'] for p in ('jax.lower',
                  'jax.backend') for e in _records(p, 'jit(step)'))
    assert 0 < covered < zero['t1_ns'] - zero['t0_ns']


def test_the_capture_comes_after_the_dispatch_and_compiles_nothing_again():
    obs.enable()
    step, state_, batch, key = _step_and_args()
    state_, _ = step(state_, batch, key)
    state_, _ = step(state_, batch, key)
    zero, one = _records('engine.dispatch')
    capture, = _records('costs.capture')
    assert capture['args'] == {'program': step.cost_label}
    assert capture['parent'] is None
    assert zero['t1_ns'] <= capture['t0_ns'] <= capture['t1_ns'] \
        <= one['t0_ns']
    caused = [e for p in PHASES for e in _records(p)
              if e['parent'] == capture['span_id']]
    # jit hands back what the dispatch made: no second lower, no second load
    assert {e['name'] for e in caused} <= {'jax.trace'}
    assert len(_records('jax.backend', 'jit(step)')) == 1
    assert costs.entry(step.cost_label)['flops'] > 0
    assert costs.phases(step.cost_label)


def test_a_captures_own_compiles_are_under_costs_capture():
    obs.enable()
    fn = _fresh()
    with obs.span('caller'):
        assert costs.capture('fresh.program', fn, jnp.ones(4)) is not None
    caller, = _records('caller')
    capture, = _records('costs.capture')
    assert capture['parent'] == caller['span_id']
    for phase in PHASES:
        ev, = [e for e in _records(phase)
               if 'fresh' in e['args']['fun_name']]
        assert ev['parent'] == capture['span_id'], phase
    # a ledger hit asks for nothing and has no span
    costs.capture('fresh.program', fn, jnp.ones(4))
    assert len(_records('costs.capture')) == 1


@pytest.mark.sharding
def test_a_sharded_state_is_placed_under_init_state():
    from jax.sharding import Mesh
    from paddle_tpu.distributed.strategy import ShardingConfig
    obs.enable()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ('data',))
    step, state_, batch, key = _step_and_args(
        sharding=ShardingConfig(mesh=mesh))
    init, = _records('engine.init_state')
    place, = _records('engine.place_state')
    assert place['parent'] == init['span_id']
    assert init['args']['sharded'] is True
    assert init['args']['bytes'] == sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(state_))
    state_, _ = step(state_, batch, key)
    zero, = _records('engine.dispatch')
    backend, = _records('jax.backend', 'jit(step)')
    assert backend['parent'] == zero['span_id']
    # the capture finds the sharded program in jit's cache too
    assert len(_records('jax.lower', 'jit(step)')) == 1


# ---------------------------------------------------------------------------
# paddle_tpu/__init__.py: the import's record
# ---------------------------------------------------------------------------

def test_import_stamps_become_one_record_at_the_first_enable(monkeypatch):
    monkeypatch.setattr(state._STATE, 'import_ns', None)
    t0 = time.perf_counter_ns()
    state.note_import(t0)                   # telemetry off: kept, not written
    assert obs.trace_events() == []
    obs.enable()
    ev, = _records('paddle_tpu.import')
    assert ev['t0_ns'] == t0 < ev['t1_ns'] and ev['parent'] is None
    obs.disable()
    obs.enable()
    assert len(_records('paddle_tpu.import')) == 1


def test_a_fresh_process_records_its_import():
    code = ('import time; t0 = time.perf_counter_ns()\n'
            'import paddle_tpu\n'
            't1 = time.perf_counter_ns()\n'
            'from paddle_tpu import observability as obs\n'
            'assert obs.trace_events() == []\n'
            'obs.enable()\n'
            'ev, = obs.trace_events()\n'
            'assert ev["name"] == "paddle_tpu.import", ev\n'
            'assert t0 <= ev["t0_ns"] < ev["t1_ns"] <= t1\n'
            'assert ev["t1_ns"] - ev["t0_ns"] > 0.9 * (t1 - t0)\n')
    done = subprocess.run([sys.executable, '-c', code], timeout=300,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-2000:]


# ---------------------------------------------------------------------------
# no compiled step changes
# ---------------------------------------------------------------------------

# sha256 of `step._jit.lower(...).as_text()` at commit b452196 (PR 38), for
# `_step_and_args()` and `_step_and_args(nan_guard=True)`
_PARENT_LOWERED = {
    (): 'c8032d2714897dc2c43a5d69f15e844271e4e7c6b315fc48f37f58768bdb7514',
    ('nan_guard',):
        '46e30679aacfdcbfa2e83951483a1f18587aaabe3542cc4878ac2ea45477b974',
}


@pytest.mark.parametrize('telemetry', [False, True])
@pytest.mark.parametrize('options', list(_PARENT_LOWERED))
def test_the_lowered_step_is_the_parents_byte_for_byte(options, telemetry):
    if telemetry:
        obs.enable()
    step, state_, batch, key = _step_and_args(**{k: True for k in options})
    text = step._jit.lower(state_, batch, key).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _PARENT_LOWERED[options]
