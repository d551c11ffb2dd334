"""The readers of the program's own records (`harness/program.py`,
`harness/phases.py` and the nine `layer_metrics/` files that use them): on
synthetic records, on the recorded v5e trace, through a traced run of the
train job at the test size, and against a program that keeps no record."""
import gzip
import json
import os
import time
import types

import pytest

import _tiny
from harness import phases, program
from harness.spans import Spans

DATA = os.path.join(_tiny.HERE, 'data')
NEW = ('input.get_wait_ms', 'input.source_ms', 'input.convert_ms',
       'input.starved_pct', 'step.jit_call_ms', 'step.forward_ms',
       'step.backward_ms', 'step.update_ms', 'step.mixed_ms')


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


def span(name, t0, t1, **args):
    return {'name': name, 'ph': 'X', 't0_ns': ms(t0), 't1_ns': ms(t1),
            'args': args}


@pytest.fixture
def records(monkeypatch):
    """A window from 100 ms to 400 ms of the benchmark's own spans, and the
    program's records around and inside it."""
    events = [
        span('prefetch.get_wait', 50, 90, depth=0, gets=1, starved=1),
        span('engine.dispatch', 91, 99),
        span('prefetch.get_wait', 100, 101, depth=0, gets=2, starved=2),
        span('prefetch.source', 102, 152),
        span('prefetch.convert', 152, 160, bytes=4096),
        span('engine.dispatch', 110, 112),
        span('prefetch.get_wait', 200, 203, depth=2, gets=3, starved=2),
        span('engine.dispatch', 210, 214),
        span('prefetch.source', 230, 260),
        span('prefetch.convert', 260, 264, bytes=4096),
        span('prefetch.get_wait', 300, 302, depth=1, gets=4, starved=2),
        span('prefetch.get_wait', 390, 410, depth=0, gets=5, starved=3),
        {'name': 'prefetch.get_wait', 'ph': 'X', 'ts': 1.0, 'dur': 2.0},
    ]
    fake = types.SimpleNamespace(trace_events=lambda: events)
    monkeypatch.setattr(program, 'enable', lambda: fake)
    spans = Spans()
    spans.records = [('input.wait', ms(10), ms(20)),
                     ('input.wait', ms(100), ms(101)),
                     ('step.dispatch', ms(390), ms(400)),
                     ('input.wait', ms(500), ms(501))]
    return {'spans': spans, 'window': (1, 3)}


@pytest.mark.parametrize('name,value', [
    ('input.get_wait_ms', (1 + 3 + 2) / 3),
    ('input.source_ms', (50 + 30) / 2),
    ('input.convert_ms', (8 + 4) / 2),
    ('step.jit_call_ms', (2 + 4) / 2),
    # gets 2, 3, 4 lie in the window; the first of them found depth 0
    ('input.starved_pct', 100.0 * 1 / 3),
])
def test_span_reader_cuts_the_programs_records_to_the_window(records, name,
                                                             value):
    assert reader(name).read(records) == pytest.approx(value)


@pytest.mark.parametrize('name', NEW)
def test_reader_of_a_program_without_records_reads_nothing(monkeypatch,
                                                           tmp_path, name):
    """The parent commit keeps none of these spans and no phase map: every
    new reader returns None there and does not raise."""
    old = types.SimpleNamespace(
        trace_events=lambda: [{'name': 'engine.step', 'ph': 'X', 'ts': 1.0,
                               'dur': 2.0, 'pid': 1, 'tid': 1}],
        costs=types.SimpleNamespace(ledger=lambda: []))
    monkeypatch.setattr(program, 'enable', lambda: old)
    monkeypatch.setattr(phases, 'ROOT', str(tmp_path))
    spans = Spans()
    spans.records = [('input.wait', 0, 10), ('step.dispatch', 10, 20)]
    assert reader(name).read({'spans': spans, 'window': (0, 2)}) is None
    monkeypatch.setattr(program, 'enable', lambda: None)    # no program
    assert reader(name).read({'spans': spans, 'window': (0, 2)}) is None


def recorded_trace():
    with gzip.open(os.path.join(DATA, 'trace_v5e_seq512.json.gz')) as f:
        recorded = json.load(f)
    recorded['devices'] = {int(k): v for k, v in recorded['devices'].items()}
    dev = recorded['devices'][0]
    dev['modules'] = dev['modules'][1:]         # the whole step
    return recorded


def map_from_names(trace_):
    """A phase map for the recorded step (PR 24's program, which had no
    scopes: the phases here are made up from its instruction names)."""
    out = {}
    for name, _, _ in trace_['devices'][0]['ops']:
        head = name.split(' = ', 1)[0].lstrip('%')
        if head.startswith('transpose_jvp'):
            out[head] = 'backward'
        elif head.startswith('jvp_'):
            out[head] = 'forward'
        elif head.startswith('multiply_subtract'):
            out[head] = 'backward+update'
        elif head.startswith('copy'):
            out[head] = 'other'
        elif head.startswith('fusion'):
            out[head] = 'forward+backward' if head.endswith('7') \
                else 'backward'
    return out


def test_phases_of_the_recorded_step_add_up_to_its_busy_time():
    recorded = recorded_trace()
    phase_map = map_from_names(recorded)
    out = phases.reduce(recorded, {'engine.train_step0': phase_map,
                                   'engine.train_step1': {'fusion.1': 'x'}})
    assert out['program'] == 'engine.train_step0' and out['steps'] == 1
    assert 0.85 < out["coverage"] < 0.9      # convolution_..., maximum_... unnamed
    per = out['per_step_ms']
    assert out['busy_ms'] == pytest.approx(173.4, rel=0.01)
    assert sum(per.values()) == pytest.approx(out['busy_ms'], rel=0.02)
    assert phases.phase_ms(out, 'mixed') == pytest.approx(
        per['backward+update'] + per['forward+backward'])
    assert phases.phase_ms(out, 'forward') == per['forward'] > 5.0
    assert phases.phase_ms(out, 'update') == 0.0
    assert per['other'] > 0         # the ops the map does not name, too


def test_phases_need_a_map_that_knows_the_scopes():
    """A compile cache filled before the scopes existed serves executables
    without them (the cache's key leaves metadata out): every instruction
    of such a map reads `other`, and the readers then read nothing."""
    recorded = recorded_trace()
    stale = {k: 'other' for k in map_from_names(recorded)}
    assert phases.reduce(recorded, {'engine.train_step0': stale}) is None
    assert phases.reduce(recorded, {}) is None
    assert phases.reduce({'devices': {}, 'host': []}, {'a': {}}) is None


def test_an_instruction_the_map_lacks_takes_its_own_op_name():
    dev = {'modules': [('jit_step(1)', 0, 100)] * 3,
           'ops': [('%fusion.1 = f32[8] fusion(%p), kind=kOutput', 0, 40),
                   ('%add.2 = f32[8] add(%p, %q), metadata={op_name='
                    '"jit(step)/update/add"}', 40, 60),
                   ('%copy.3 = f32[8] copy(%p)', 60, 70)]}
    dev['modules'] = [('jit_step(1)', -100, 0), ('jit_step(1)', 0, 100),
                      ('jit_step(1)', 100, 200)]
    out = phases.reduce(
        {'devices': {0: dev}, 'host': []}, {'s': {'fusion.1': 'forward'}},
        phase_of_op_name=lambda n: 'update' if '/update/' in n else 'other')
    assert out['per_step_ms'] == {
        'forward': pytest.approx(40e-6), 'update': pytest.approx(20e-6),
        'other': pytest.approx(10e-6)}
    assert out['coverage'] == pytest.approx(40 / 70)


def test_new_manifest_entries_have_a_reader_and_their_cells():
    with open(os.path.join(_tiny.BENCH, '..', 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    by_name = {m['name']: m for m in manifest['per_layer']}
    cells = [c['name'] for c in manifest['workloads']]
    for name in NEW:
        metric = by_name[name]
        # PR 25's three first, as they were; then the cells in which PR 36
        # read the reader on a traced run first (a later cell inherits none)
        assert metric['workloads'][:3] == cells[:3]
        assert set(metric['workloads']) <= set(cells)
        assert os.path.exists(os.path.join(
            _tiny.BENCH, 'layer_metrics', name + '.py'))
        assert callable(reader(name).read)
        assert metric['source'] in ('program_span', 'program_counter',
                                    'device_trace')
    # additions only: what was there stands first, as it was
    assert [m['name'] for m in manifest['per_layer']][:6] == [
        'input.wait_ms', 'step.dispatch_ms', 'step.compiles_in_window',
        'flash_attention_roofline', 'fused_norms_ms', 'device.idle_pct']


def test_traced_run_reads_the_programs_spans(monkeypatch):
    """The train job at the test size with `--trace 1`'s readers, on the
    CPU: the program's spans give the five span metrics beside their
    outside twins; no TPU plane, so the four phase readers read nothing."""
    import jax
    from harness import peaks
    monkeypatch.setattr(peaks, 'peaks_of', lambda kind: {
        'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11})
    run = _tiny.harness_run
    config, traffic = (_tiny.load(n) for n in _tiny.SIZES['bert'])
    family = run.load_module('families', config['family'])
    wanted = [{'name': n, 'unit': 'x'}
              for n in ('input.wait_ms', 'step.dispatch_ms') + NEW]
    readers = {m['name']: reader(m['name']) for m in wanted}
    scratch = os.path.join(_tiny.HERE, '.scratch')
    monkeypatch.setattr(phases, 'ROOT', os.path.dirname(scratch))
    result = run.load_module('jobs', traffic['job']).run(
        cell={'name': 'bert.tiny', 'chips': 1}, config=config,
        traffic=traffic, family=family, seed=11, seconds=0.5, trace=True,
        limits={'loss_gap': 1, 'change_gap': 1, 'loss_fall': -10,
                'first_gradient_gap': 10},
        reference=run.load_module('families', family.REFERENCE),
        wanted=wanted, readers=readers, t_start=time.perf_counter(),
        devices=jax.devices()[:1], scratch=scratch, facts={})
    got = {k: v['value'] for k, v in result['metrics'].items()}
    assert set(got) == {'input.wait_ms', 'step.dispatch_ms'} | set(NEW[:5])
    assert 0 <= got['input.get_wait_ms'] <= got['input.wait_ms']
    assert 0 < got['step.jit_call_ms'] <= got['step.dispatch_ms']
    assert got['input.source_ms'] > 0 and got['input.convert_ms'] > 0
    assert 0 <= got['input.starved_pct'] <= 100
