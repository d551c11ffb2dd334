"""The harness's own arithmetic: intervals, the trace reduction on the small
recorded trace beside this file, the operation counts, the peaks table, the
manifest and the files it names."""
import gzip
import json
import os

import pytest

import _tiny
from harness import check, peaks, trace

DATA = os.path.join(_tiny.HERE, 'data')


def test_intervals():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [[0, 3], [5, 8]] and trace.length(u) == 6
    assert trace.subtract([[0, 10]], u) == [[3, 5], [8, 10]]
    assert trace.subtract(u, [[2, 6]]) == [[0, 2], [6, 8]]
    ops = [('while', 0, 10), ('a', 0, 4), ('b', 5, 10)]
    assert [o[0] for o in trace.leaves(ops)] == ['a', 'b']
    assert trace.clip(ops, 2, 6) == [('while', 2, 6), ('a', 2, 4),
                                     ('b', 5, 6)]


def test_reduce_synthetic():
    """Two steps of 100 ns on one chip: a kernel under a scope, a collective
    that is half hidden behind compute, a gap while the host dispatches."""
    dev = {'modules': [('jit_step(1)', 0, 100), ('jit_step(1)', 100, 200),
                       ('jit_other(2)', 300, 301)],
           'ops': [('%fusion.1 = f32[8] fusion(f32[8] %p), kind=kOutput', 0, 40),
                   ('%jvp_k.pallas_.2 = f32[8] custom-call(f32[8] %p)', 40, 60),
                   ('%all-gather.3 = f32[8] all-gather(f32[2] %p)', 60, 80),
                   ('%fusion.4 = f32[8] fusion(f32[8] %p), kind=kLoop', 70, 90),
                   ('%fusion.1 = f32[8] fusion(f32[8] %p), kind=kOutput',
                    100, 190)]}
    out = trace.reduce({'devices': {0: dev},
                        'host': [('step.dispatch', 85, 105)]},
                       scopes=('k.pallas',))[0]
    assert out['steps'] == 2 and out['window_s'] == pytest.approx(200e-9)
    assert out['busy_s'] == pytest.approx(180e-9)
    assert out['scopes']['k.pallas'] == {'seconds': pytest.approx(20e-9),
                                         'events': 1}
    assert out['collective_s'] == pytest.approx(20e-9)
    assert out['exposed_collective_s'] == pytest.approx(10e-9)
    assert dict(map(tuple, out['idle_gaps'])) == {
        'step.dispatch': pytest.approx(10e-9),
        'no benchmark span': pytest.approx(10e-9)}
    assert out['device_ops'][0] == ['fusion kOutput', pytest.approx(130e-9)]


def test_reduce_recorded_trace():
    """One whole step of `bert-large.pretrain-seq512` on a TPU v5e (my chip
    run, PR 24; op names cut to 120 characters), behind the step the trace
    began in."""
    with gzip.open(os.path.join(DATA, 'trace_v5e_seq512.json.gz')) as f:
        recorded = json.load(f)
    recorded['devices'] = {int(k): v for k, v in recorded['devices'].items()}
    dev = recorded['devices'][0]
    dev['modules'] = [m for m in dev['modules']][1:]    # the whole step
    out = trace.reduce(recorded, scopes=(
        'flash_attention.pallas', 'fused_dropout_norm.pallas',
        'fused_layer_norm.pallas'))[0]
    assert out['steps'] == 1
    assert out['window_s'] == pytest.approx(0.17345, rel=1e-3)
    assert 0.99 < out['busy_s'] / out['window_s'] <= 1.0
    flash = out['scopes']['flash_attention.pallas']
    assert flash['events'] == 24 * 3      # forward, dq, dkv in every layer
    assert 0.15 < flash['seconds'] / out['window_s'] < 0.20
    assert out['scopes']['fused_dropout_norm.pallas']['events'] == 24 * 2 * 2
    assert out['collective_s'] == 0
    # (the recording's names lost their `kind=` with their tails)
    assert out['device_ops'][0][0] == 'fusion'
    assert {g[0] for g in out['idle_gaps']} <= {
        'no benchmark span', 'input.wait', 'step.key', 'step.dispatch'}


def test_worst_leaf_gap():
    ref = {'a': 1.0, 'b': 2.0, 'c': 1e-9}
    gap, leaf = check.worst_leaf_gap({'a': 1.1, 'b': 2.0, 'c': 2e-9}, ref)
    assert leaf == 'a' and gap == pytest.approx(0.1)
    gap, leaf = check.worst_leaf_gap({'a': 1.0, 'b': float('nan'), 'c': 0},
                                     ref)
    assert leaf == 'b' and gap != gap


def test_operation_counts():
    """The counts ISSUE 24 gives: 241 GFLOP a sample at seq 128, 1.02 TFLOP
    at seq 512, about 24.6 GFLOP an image."""
    run = _tiny.harness_run
    bert = run.load_module('families', 'bert')
    resnet = run.load_module('families', 'resnet')
    large = run.load_json('configs', 'bert-large.json')
    assert bert.flops_per_sample(
        large, run.load_json('traffic', 'pretrain-seq128.json')) \
        == pytest.approx(241e9, rel=0.02)
    assert bert.flops_per_sample(
        large, run.load_json('traffic', 'pretrain-seq512.json')) \
        == pytest.approx(1.02e12, rel=0.02)
    assert resnet.flops_per_sample(
        run.load_json('configs', 'resnet50.json'), {}) \
        == pytest.approx(24.6e9, rel=0.02)
    assert sum(int(__import__('numpy').prod(s)) for s, _ in
               bert.param_spec(large).values()) == 336226108


def test_peaks_table():
    assert peaks.peaks_of('TPU v5 lite')['bf16_flops_per_s'] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_of('cpu')


def test_manifest_names_files_that_exist():
    with open(os.path.join(_tiny.BENCH, '..', 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    run = _tiny.harness_run
    e2e = {m['name'] for m in manifest['end_to_end']}
    cells = {c['name'] for c in manifest['workloads']}
    for cell in manifest['workloads']:
        config = run.load_json('configs', cell['config'] + '.json')
        traffic = run.load_json('traffic', cell['traffic'] + '.json')
        limits = run.load_json('limits', cell['name'] + '.json')
        assert {'loss_gap', 'change_gap', 'loss_fall'} <= set(limits)
        assert any(k.startswith('first_gradient_') for k in limits)
        for kind, name in (('families', config['family']),
                           ('jobs', traffic['job'])):
            assert os.path.exists(os.path.join(_tiny.BENCH, kind,
                                               name + '.py'))
        assert cell['name'] == cell['config'] + '.' + cell['traffic']
    for metric in manifest['per_layer']:
        assert metric['moves'] in e2e
        assert set(metric.get('workloads', cells)) <= cells
        reader = run.load_module('layer_metrics', metric['name'])
        assert callable(reader.read)


def test_idle_share_takes_its_period_from_the_untraced_window():
    """ResNet-50's traced steps on the chip (my chip run, PR 24): 6 whole
    steps, 0.7317 s busy in a traced window of 2.863 s that the profiler's
    slow host stretched; the untraced window's steps came every 122.0055 ms."""
    reader = _tiny.harness_run.load_module('layer_metrics', 'device.idle_pct')
    ctx = {'step_ms_median': 122.0055,
           'trace': {0: {'steps': 6, 'busy_s': 0.731716132,
                         'window_s': 2.863382462}}}
    assert reader.read(ctx) == pytest.approx(0.043, abs=0.001)
    assert reader.read({'step_ms_median': 1.0, 'trace': {}}) is None
