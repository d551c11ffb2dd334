"""The readers of set-up's records (`harness/setup.py` and the six
`layer_metrics/setup.*.py`): on synthetic records, through a traced run of
the train job at the test size, and against a program that keeps none."""
import json
import os
import time
import types

import pytest

import _tiny
from harness import phases, program, setup
from harness.spans import Spans

NEW = ('setup.import_ms', 'setup.trace_ms', 'setup.lower_ms',
       'setup.backend_ms', 'setup.state_ms', 'setup.cache_misses')


def reader(name):
    return _tiny.harness_run.load_module('layer_metrics', name)


@pytest.fixture(autouse=True)
def _telemetry_off_again():
    yield
    from paddle_tpu import observability
    observability.disable()
    observability.reset()


def ms(n):
    return int(n * 1e6)


_ids = iter(range(1, 10 ** 6))


def span(name, t0, t1, parent=None, tid=1, **args):
    return {'name': name, 'ph': 'X', 't0_ns': ms(t0), 't1_ns': ms(t1),
            'span_id': next(_ids), 'parent': parent, 'tid': tid,
            'step': None, 'args': args}


def setup_records():
    """A set-up of one second before a window that opens at 1000 ms: the
    import, a step built and its state made (with one one-op compile inside),
    its first dispatch (a nested trace, a lowering, a cache load), a capture
    with a lowering of its own, a caller's jit with a miss, a second step's
    state, and records that end after the window opened."""
    imp = span('paddle_tpu.import', 0, 100)
    build = span('engine.build', 150, 151)
    init = span('engine.init_state', 200, 300, bytes=4096, sharded=False)
    first = span('engine.dispatch', 400, 700, first=True, k=1)
    first['step'] = 0
    backend = span('jax.backend', 600, 690, first['span_id'],
                   fun_name='jit(step)', cache='hit')
    capture = span('costs.capture', 700, 760, program='engine.train_step0')
    second = span('engine.init_state', 900, 920, bytes=4096, sharded=False)
    late = span('engine.dispatch', 990, 1010, k=1)
    return [
        imp, build, init,
        span('jax.trace', 210, 215, init['span_id'], fun_name='zeros'),
        span('jax.lower', 215, 220, init['span_id'], fun_name='jit(zeros)'),
        span('jax.backend', 220, 240, init['span_id'],
             fun_name='jit(zeros)'),
        # the step's trace and, inside it, an inner jit's: 100 ms in all
        span('jax.trace', 450, 480, first['span_id'], fun_name='inner'),
        span('jax.trace', 400, 500, first['span_id'], fun_name='step'),
        span('jax.lower', 500, 600, first['span_id'], fun_name='jit(step)'),
        backend,
        span('jax.cache_load', 610, 680, backend['span_id'],
             fun_name='jit(step)'),
        first, capture,
        span('jax.trace', 700, 701, capture['span_id'], fun_name='step'),
        span('jax.lower', 701, 741, capture['span_id'],
             fun_name='jit(step)'),
        span('jax.backend', 741, 751, capture['span_id'],
             fun_name='jit(step)', cache='miss'),
        # the benchmark's own jit, under no span of the program
        span('jax.trace', 800, 810, fun_name='_make'),
        span('jax.lower', 810, 820, fun_name='jit(_make)'),
        span('jax.backend', 820, 880, fun_name='jit(_make)', cache='miss'),
        second,
        # the prefetcher's thread waits through all of set-up
        span('prefetch.put_wait', 300, 995, tid=2),
        late,
        span('jax.trace', 1100, 1200, fun_name='late'),
        {'name': 'engine.dispatch', 'ph': 'X', 'ts': 1.0, 'dur': 2.0},
    ]


def context(monkeypatch, events, dropped=0):
    fake = types.SimpleNamespace(
        trace_events=lambda: events,
        spans=types.SimpleNamespace(dropped=lambda: dropped))
    monkeypatch.setattr(program, 'enable', lambda: fake)
    spans = Spans()
    spans.records = [('input.wait', ms(380), ms(390)),
                     ('step.dispatch', ms(395), ms(765)),
                     ('input.wait', ms(1000), ms(1001)),
                     ('step.dispatch', ms(1400), ms(1500)),
                     ('input.wait', ms(1600), ms(1601))]
    return {'spans': spans, 'window': (2, 4)}


@pytest.mark.parametrize('name,value', [
    ('setup.import_ms', 100.0),
    # 5 in the state's making + 100 of the step (the inner jit's 30 lie
    # inside the step's) + the caller's 10; the capture's 1 is left out and
    # the record after the window's opening is cut
    ('setup.trace_ms', 5 + 100 + 10),
    ('setup.lower_ms', 5 + 100 + 10),
    ('setup.backend_ms', 20 + 90 + 60),
    # 100 less the 30 ms of compiles inside it, and the second state's 20
    ('setup.state_ms', 70 + 20),
    # the capture's miss counts: a cold traced run compiles there or not
    # at all
    ('setup.cache_misses', 2),
])
def test_reader_cuts_at_the_windows_opening(monkeypatch, capsys, name, value):
    ctx = context(monkeypatch, setup_records())
    assert reader(name).read(ctx) == pytest.approx(value)
    # the records are read once and the line printed once, whoever asks
    assert reader(NEW[0]).read(ctx) == pytest.approx(100.0)
    lines = [json.loads(text) for text in capsys.readouterr().out.split('\n')
             if text.startswith('{')]
    assert [x['phase'] for x in lines] == ['setup_spans']


def test_the_line_lists_spans_their_children_and_what_lies_outside(
        monkeypatch):
    ctx = context(monkeypatch, setup_records())
    line = setup._read_once(ctx)['line']
    assert line['interval_s'] == 1.0
    rows = {(r['name'], r.get('step')): r for r in line['spans']}
    first = rows['engine.dispatch', 0]
    assert first['first'] is True and first['start_s'] == -0.6
    assert first['children'] == {'jax.backend': 0.09, 'jax.lower': 0.1,
                                 'jax.trace': 0.1}
    # 300 ms less the 290 its children cover (the cache load is the
    # backend record's child, not the dispatch's)
    assert first['self_s'] == pytest.approx(0.01)
    init = [r for r in line['spans'] if r['name'] == 'engine.init_state']
    assert [r['bytes'] for r in init] == [4096, 4096]
    assert [r['self_s'] for r in init] == [0.07, 0.02]
    assert line['short_spans'] == {}
    assert line['phases']['jax.trace'] == {'n': 4, 'union_s': 0.115}
    assert line['costs_capture'] == {'n': 1, 'total_s': 0.06,
                                     'jax_s': 0.051}
    by_fun = {p['fun']: p for p in line['programs']}
    assert by_fun['step'] == {
        'fun': 'step', 'n': 2, 'hit': 1, 'miss': 1, 'lower_s': 0.14,
        'backend_s': 0.1, 'under': ['costs.capture', 'engine.dispatch']}
    assert by_fun['_make']['under'] == ['none']
    assert by_fun['zeros']['uncached'] == 1
    assert line['other_programs']['funs'] == 0
    # this thread's records cover 100 + 1 + 100 + 300 + 60 + 80 + 20 ms of
    # the second; the prefetcher's thread is not this one's time
    assert line['named_s'] == pytest.approx(0.661)
    assert line['named_share'] == pytest.approx(0.661)
    gaps = {(g['after'], g['before']): g for g in line['outside']}
    # (the 49 ms between the build and the state are under what is listed)
    assert set(gaps) == {
        ('paddle_tpu.import', 'engine.build'),
        ('engine.init_state', 'engine.dispatch'),
        ('costs.capture', 'engine.init_state'),
        ('engine.init_state', 'window')}
    made = gaps['costs.capture', 'engine.init_state']
    assert made['dur_s'] == 0.14 and made['jax_s'] == 0.08
    assert gaps['engine.init_state', 'engine.dispatch'][
        'benchmark_spans'] == {'input.wait': 0.01, 'step.dispatch': 0.005}
    assert line['outside_s'] == pytest.approx(0.05 + 0.1 + 0.14 + 0.08)
    assert line['outside_unnamed_s'] == pytest.approx(line['outside_s']
                                                      - 0.08)
    assert 'window_t' not in line           # no run.py in this process


def test_a_ring_that_dropped_records_fails_loudly(monkeypatch):
    ctx = context(monkeypatch, setup_records(), dropped=3)
    with pytest.raises(RuntimeError, match='dropped 3 records'):
        reader('setup.trace_ms').read(ctx)


@pytest.mark.parametrize('name', NEW)
def test_reader_of_a_program_without_records_reads_nothing(monkeypatch,
                                                           capsys, name):
    """The parent commit keeps `engine.dispatch` and `prefetch.*` and none of
    set-up's records: every new reader returns None there, prints no line
    and does not raise."""
    old = [span('engine.dispatch', 400, 700, k=1),
           span('prefetch.get_wait', 380, 390, depth=0, gets=1, starved=1)]
    ctx = context(monkeypatch, old)
    assert reader(name).read(ctx) is None
    assert 'setup_spans' not in capsys.readouterr().out
    monkeypatch.setattr(program, 'enable', lambda: None)    # no program
    ctx.pop('setup_spans')
    assert reader(name).read(ctx) is None


def test_new_manifest_entries_have_a_reader_and_every_cell():
    """The six `setup.*` entries, looked up by name: every cell the
    manifest has lists them (set-up is every run's), in the manifest's
    order, however many cells and entries later PRs have added."""
    with open(os.path.join(_tiny.BENCH, '..', 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    cells = [c['name'] for c in manifest['workloads']]
    by_name = {m['name']: m for m in manifest['per_layer']}
    assert len(by_name) == len(manifest['per_layer'])
    for name in NEW:
        metric = by_name[name]
        assert metric['workloads'] == cells
        assert metric['moves'] == 'setup_s' and metric['better'] == 'lower'
        assert metric['layer'] == 'set-up path'
        assert metric['source'] == ('program_counter' if metric['unit']
                                    == 'count' else 'program_span')
        assert callable(reader(name).read)
    # nothing else moves setup_s, and the six stand together, in order
    assert [m['name'] for m in manifest['per_layer']
            if m['moves'] == 'setup_s'] == list(NEW)


def test_traced_run_reads_set_up(monkeypatch, capsys):
    """The train job at the test size with the six readers, on the CPU: a
    twin and a timed step, each with its state, its first dispatch and its
    capture; no persistent cache, so no program says hit or miss."""
    import jax
    from harness import peaks
    monkeypatch.setattr(peaks, 'peaks_of', lambda kind: {
        'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11})
    run = _tiny.harness_run
    config, traffic = (_tiny.load(n) for n in _tiny.SIZES['bert'])
    family = run.load_module('families', config['family'])
    wanted = [{'name': n, 'unit': 'x'} for n in ('step.dispatch_ms',) + NEW]
    readers = {m['name']: reader(m['name']) for m in wanted}
    scratch = os.path.join(_tiny.HERE, '.scratch')
    monkeypatch.setattr(phases, 'ROOT', os.path.dirname(scratch))
    t_start = time.perf_counter()
    # this process imported the program long ago and an earlier test may
    # have cleared the record: stand in for it
    from paddle_tpu.observability import state
    monkeypatch.setattr(state._STATE, 'import_ns',
                        (int(t_start * 1e9), int(t_start * 1e9) + ms(1)))
    result = run.load_module('jobs', traffic['job']).run(
        cell={'name': 'bert.tiny', 'chips': 1}, config=config,
        traffic=traffic, family=family, seed=11, seconds=0.5, trace=True,
        limits={'loss_gap': 1, 'change_gap': 1, 'loss_fall': -10,
                'first_gradient_gap': 10},
        reference=run.load_module('families', family.REFERENCE),
        wanted=wanted, readers=readers, t_start=t_start,
        devices=jax.devices()[:1], scratch=scratch, facts={})
    got = {k: v['value'] for k, v in result['metrics'].items()}
    assert set(got) == {'step.dispatch_ms'} | set(NEW)
    assert got['setup.trace_ms'] > 0 and got['setup.lower_ms'] > 0
    assert got['setup.backend_ms'] > 0 and got['setup.state_ms'] > 0
    assert got['setup.cache_misses'] == 0
    line, = [json.loads(text) for text in capsys.readouterr().out.split('\n')
             if text.startswith('{"') and '"setup_spans"' in text]
    assert got['setup.trace_ms'] == pytest.approx(
        1e3 * line['phases']['jax.trace']['union_s'], abs=0.1)
    firsts = [r for r in line['spans'] if r.get('first')]
    assert [r['name'] for r in firsts] == ['engine.dispatch'] * 2
    assert all(r['step'] == 0 and r['children']['jax.backend'] > 0
               for r in firsts)
    assert [r['name'] for r in line['spans']].count('engine.init_state') == 2
    assert line['costs_capture']['n'] == 2
    step = [p for p in line['programs'] if p['fun'] == 'step']
    assert step and step[0]['n'] == 2
    assert step[0]['under'] == ['engine.dispatch']
    assert 0 < line['named_share'] <= 1
    assert line['named_s'] + line['outside_unnamed_s'] \
        <= line['interval_s'] + 1e-3
