"""The harness's own arithmetic: intervals, the trace reduction on the small
recorded trace beside this file, the operation counts, the peaks table, the
manifest and the files it names."""
import gzip
import json
import os

import numpy as np
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
    # a reduce-scatter the compiler runs as a fusion on the compute stream
    # (the four-chip cell's gradient reduction) is a collective by what it
    # calls, and nothing overlaps it
    dev['ops'].append(('%fusion.14 = f32[2] fusion(f32[8] %p), kind=kCustom, '
                       'calls=%all-reduce-scatter.2', 190, 196))
    fused = trace.reduce({'devices': {0: dev}, 'host': []})[0]
    assert fused['collective_s'] == pytest.approx(26e-9)
    assert fused['exposed_collective_s'] == pytest.approx(16e-9)
    assert not trace.is_collective(
        '%fusion.9 = f32[8] fusion(f32[8] %all-gather.3), kind=kLoop, '
        'calls=%fused_computation.9')
    assert trace.is_collective(
        '%async-collective-start = (f32[256,1024], f32[1024,1024]) fusion('
        '%custom-call.175), kind=kCustom, calls=%fused_computation.1080')


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


def test_idle_share_of_a_stretched_trace_takes_the_untraced_windows_period(
        capsys):
    """ResNet-50's traced steps on the chip (my chip run, PR 24): 6 whole
    steps, 0.7317 s busy in a traced window of 2.863 s that the profiler's
    slow host stretched; the untraced window's steps came every 122.0055 ms,
    whatever their batch."""
    reader = _tiny.harness_run.load_module('layer_metrics', 'device.idle_pct')
    ctx = {'traced_step_ms': [122.0055] * 7,
           'trace': {0: {'steps': 6, 'busy_s': 0.731716132,
                         'window_s': 2.863382462}}}
    assert reader.read(ctx) == pytest.approx(0.043, abs=0.001)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line['per_chip']['0']['form'] == 'window'
    assert reader.read({'traced_step_ms': [1.0] * 7, 'trace': {}}) is None


def test_idle_share_of_a_trace_that_kept_up_is_the_traces_own(capsys):
    """A routed cell (Kimi, ledger PR 46): the traced steps took 692.7 ms
    each under the routing of the window's END, the device busy 99.92% of
    them; the window read the same pool batches at 682 ms, tens of steps
    earlier under a lighter routing, and over that the share read -1.5.
    A trace that caught five whole steps takes its own span all the same;
    a chip the host left waiting falls back to the window's reading."""
    reader = _tiny.harness_run.load_module('layer_metrics', 'device.idle_pct')
    ms = [682.0] * 7
    chip = {'steps': 6, 'window_s': 6 * 0.6927, 'busy_s': 0.9992 * 6 * 0.6927}
    assert 100.0 * (1.0 - chip['busy_s'] / (6 * 0.682)) < -1.4
    got = reader.read({'traced_step_ms': ms, 'trace': {0: chip}})
    assert got == pytest.approx(0.08, abs=1e-6)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line['per_chip']['0'] == {
        'form': 'trace', 'steps': 6, 'idle_pct': pytest.approx(0.08),
        'period_ms': pytest.approx(692.7)}
    slow = {'steps': 5, 'window_s': 5 * 0.800, 'busy_s': 0.9 * 5 * 0.700}
    got = reader.read({'traced_step_ms': [700.0] * 7,
                       'trace': {0: chip, 1: slow}})
    assert got == pytest.approx(10.0)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert [line['per_chip'][k]['form'] for k in '01'] == ['trace', 'window']


def test_a_starved_traced_get_sends_the_period_back_to_the_window(
        monkeypatch):
    """The host's own record decides before the busy share does: a get of
    the whole traced steps that found the queue empty."""
    from harness import period, program
    gets = [{'name': 'prefetch.get_wait', 'ph': 'X', 't0_ns': t, 't1_ns': t + 1,
             'args': {'gets': i, 'starved': 3 + (i >= 4), 'depth': 1}}
            for i, t in enumerate(range(5, 75, 10))]
    monkeypatch.setattr(program, 'enable', lambda: type('o', (), {
        'trace_events': staticmethod(lambda: gets)}))
    calls = [(t, t + 8) for t in range(0, 70, 10)]
    chip = {'steps': 3, 'window_s': 3.0, 'busy_s': 2.99}
    ctx = {'traced_step_ms': [900.0] * 7, 'traced_calls_ns': calls}
    assert program.traced_calls(ctx, 3) == calls[3:6]
    assert period.starved(ctx, 3)              # get 4 lies in call 4
    assert period.read(ctx, chip) == (pytest.approx(2.7), 'window')
    assert not period.starved(ctx, 2)          # calls 4 and 5: 4 and 4
    assert period.read(ctx, dict(chip, steps=2, window_s=2.0, busy_s=1.99)) \
        == (2.0, 'trace')


# ------------------------------------------------- the packed rows' layout

def packed_pool(seed):
    run = _tiny.harness_run
    traffic = run.load_json('traffic', 'train-pack8k.json')
    family = run.load_module('families', 'kimi_linear')
    # (the ids' range does not touch the layout; a small one is quick)
    pool = family.make_pool({'vocab_size': 64}, traffic, seed,
                            traffic['pool_batches'],
                            traffic['batch_per_chip'])
    return family, traffic, pool


@pytest.fixture(scope='module')
def two_pools():
    return [packed_pool(seed) for seed in (3, 2147483659)]


def test_layout_is_the_traffics_and_the_ids_are_the_seeds(two_pools):
    (family, _, a), (_, _, b) = two_pools
    assert len(a) == len(b) == 32
    for (xa, _), (xb, _) in zip(a, b):
        np.testing.assert_array_equal(xa[1], xb[1])         # seg
    assert any((xa[0] != xb[0]).any() for (xa, _), (xb, _) in zip(a, b))
    assert family.layout_digest(a) == family.layout_digest(b)
    assert family.layout_digest(a) == family.layout_digest(a[::-1])
    assert family.layout_digest(a) != family.layout_digest(a[:-1])


def test_the_joyai_family_takes_the_same_layout(two_pools):
    run = _tiny.harness_run
    joyai = run.load_module('families', 'joyai_llm_flash')
    _, traffic, pool = two_pools[0]
    mine = joyai.make_pool({'vocab_size': 64}, traffic, 99, 32, 2)
    assert joyai.layout_digest(mine) == two_pools[0][0].layout_digest(pool)


def test_layout_holds_the_pairs_the_operation_count_expects(two_pools):
    """The pool's own mean of causal pairs a token inside documents,
    recomputed here, is within 1% of what `flops_per_sample` counts, equals
    the figure the traffic file states, and `layout_seed` is the first
    integer from 0 that does so."""
    family, traffic, pool = two_pools[0]

    def pairs(seg):
        total = 0
        for row in seg:
            n = np.bincount(row).astype(np.int64)
            total += int((n * (n + 1) // 2).sum())
        return total / seg.size
    seg = np.concatenate([b[0][1] for b in pool])
    assert seg.shape == (64, traffic['seq_len'])
    assert pairs(seg) == pytest.approx(traffic['layout_pairs_per_token'],
                                       rel=1e-12)
    want = traffic['expected_pairs_per_token']
    assert abs(pairs(seg) / want - 1) < 0.01
    for earlier in range(traffic['layout_seed']):
        other = family.make_pool({'vocab_size': 64},
                                 dict(traffic, layout_seed=earlier), 0,
                                 32, 2)
        other = pairs(np.concatenate([b[0][1] for b in other]))
        assert abs(other / want - 1) >= 0.01, earlier
    # the layout keeps its spread: not sorted, balanced or padded
    each = [pairs(b[0][1]) for b in pool]
    assert max(each) > 3 * min(each) and each != sorted(each)


# ------------------------------------------- the kernels' required counts

def kernel_ctx(config_name):
    """A reader's context at a test size: two rows of 64 tokens a step on
    one chip (128 tokens), the family's own description of its kernels."""
    run = _tiny.harness_run
    config = _tiny.load(config_name)
    return {'config': config, 'traffic': _tiny.load('train-pack-tiny'),
            'rows': 2, 'chips': 1,
            'family': run.load_module('families', config['family'])}


def causal_pairs(traffic, window=None):
    """Mean pairs a row of the pool, from the pool's own segment ids."""
    family = _tiny.harness_run.load_module('families', 'kimi_linear')
    pool = family.make_pool({'vocab_size': 64}, traffic, 3,
                            traffic['pool_batches'], 2)
    total = 0
    for row in np.concatenate([b[0][1] for b in pool]):
        for n in np.bincount(row):
            w = n if window is None else min(n, window)
            total += w * (w + 1) // 2 + (n - w) * w
    return total / (2 * traffic['pool_batches'])


# (reader, test configuration, held rows or None, operations, bytes): each
# count written out by hand from the configuration's numbers; float32 cells,
# so an element of the compute type is 4 bytes
REQUIRED = [
    # 2 KDA layers x 2 heads x 128 tokens; 18 x 16 x 16 operations; q, k, v,
    # 16 decays and beta are 65 elements, o 16: (81 + 81 + 65) x 4 bytes
    ('kda.scan_roofline', 'kimi-linear-tiny', None,
     512 * 18 * 16 * 16, 512 * 227 * 4),
    # 2 KDA layers x 3 calls of 32 channels, 4 taps: 24 operations and 20
    # bytes an element, 3 x 4 taps x 32 x 4 bytes of taps a call
    ('short_conv_roofline', 'kimi-linear-tiny', None,
     6 * 128 * 32 * 24, 6 * (128 * 32 * 20 + 1536)),
    # 3 M layers x (256 + 32 + 32) channels with a bias (5 rows of taps)
    ('short_conv_roofline', 'nemotron-h-tiny', None,
     3 * 128 * 320 * 24, 3 * (128 * 320 * 20 + 3 * 5 * 320 * 4)),
    # 6 linear layers x 3 heads x (96 + 96 + 192) channels
    ('short_conv_roofline', 'olmo-hybrid-tiny', None,
     6 * 128 * 1152 * 24, 6 * (128 * 1152 * 20 + 3 * 4 * 1152 * 4)),
    # 100 held rows, gate + up + down x 3 passes, 32 x 16; 2 expert layers
    # x 4 held experts of 32 x 16 a matrix; 32 + 16 elements a row and pass
    ('moe.experts_roofline', 'kimi-linear-tiny', 100,
     100 * 9 * 2 * 32 * 16, 9 * (2 * 4 * 32 * 16 + 100 * 48) * 4),
    # ungated: up + down x 3 passes at the odd width 13; 3 E layers
    ('moe.experts_roofline', 'nemotron-h-tiny', 100,
     100 * 6 * 2 * 32 * 13, 6 * (3 * 4 * 32 * 13 + 100 * 45) * 4),
    # 100 rows of 32: read and written by four moves, 4 + 4 + 4 + 4 bytes
    # each way; a multiply-add each way in the two weighted moves
    ('moe.permute_roofline', 'kimi-linear-tiny', 100,
     100 * 32 * 4, 100 * 32 * 2 * 16),
    # 8 layers x (4 + 2) heads of 8, all turned; 12 operations, 4 x 4 bytes
    ('rope_roofline', 'mellum-tiny', None,
     8 * 48 * 128 * 12, 8 * 48 * 128 * 16),
    # 3 blocks (2 layers and the module) x (2 heads + the shared key) x 8
    ('rope_roofline', 'joyai-flash-tiny', None,
     3 * 24 * 128 * 12, 3 * 24 * 128 * 16),
]


@pytest.mark.parametrize('name,config,held,flops,bytes_', REQUIRED)
def test_a_kernels_required_count(name, config, held, flops, bytes_):
    reader = _tiny.harness_run.load_module('layer_metrics', name)
    ctx = kernel_ctx(config)
    got = reader.required(ctx) if held is None \
        else reader.required(ctx, held)
    assert got == (flops, bytes_)


@pytest.mark.parametrize('config,layers', [
    # 3 latent blocks: 2 heads on 2, 24 / 16
    ('joyai-flash-tiny', [(None, 2, 2, 24, 16)] * 3),
    # a window of 6 keys, 4 query heads on 2 K/V heads of 8
    ('mellum-tiny', [(6, 4, 2, 8, 8)] * 3 + [(None, 4, 2, 8, 8)]
     + [(6, 4, 2, 8, 8)] * 3 + [(None, 4, 2, 8, 8)]),
    # a share by heads: 3 of 6, in the two full layers of eight
    ('olmo-hybrid-tiny', [(None, 3, 3, 128, 128)] * 2),
    # 4 query heads on 2 K/V heads in the two * layers
    ('nemotron-h-tiny', [(None, 4, 2, 8, 8)] * 2),
    # the one latent layer of three
    ('kimi-linear-tiny', [(None, 2, 2, 24, 16)]),
])
def test_packed_attentions_required_count(config, layers):
    reader = _tiny.harness_run.load_module(
        'layer_metrics', 'flash_attention_packed_roofline')
    ctx = kernel_ctx(config)
    flops = elements = 0
    for window, heads, kv, qk, v in layers:
        pairs = causal_pairs(ctx['traffic'], window)
        flops += 2 * heads * pairs * (6 * qk + 6 * v)
        elements += 128 * (heads + kv) * (3 * qk + 3 * v)
    got = reader.required(ctx)
    assert got == (pytest.approx(flops, rel=1e-12), elements * 4)


def roofline_ctx(config, scope, seconds, **more):
    chip = {'steps': 5, 'busy_s': 1.0, 'window_s': 1.0,
            'scopes': {scope: {'seconds': seconds, 'events': 10}}}
    return dict(kernel_ctx(config), trace={0: chip}, peaks={
        'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}, **more)


def test_a_share_is_the_least_time_over_the_time_paid(monkeypatch):
    """5 whole steps, 50 us under the scope: 10 us a step; the count above
    needs 464896 bytes at 1e11 a second, 4.65 us, more than its operations
    at 1e12: 46.5%. On the slowest chip; nothing where no event ran, or
    where the family has no such mechanism."""
    reader = _tiny.harness_run.load_module('layer_metrics',
                                           'kda.scan_roofline')
    ctx = roofline_ctx('kimi-linear-tiny', 'delta_rule.pallas', 50e-6)
    assert reader.read(ctx) == pytest.approx(46.4896)
    slower = {'steps': 5, 'scopes': {'delta_rule.pallas': {
        'seconds': 100e-6, 'events': 10}}}
    ctx['trace'][1] = slower
    assert reader.read(ctx) == pytest.approx(46.4896 / 2)
    ctx['trace'] = {0: {'steps': 5, 'scopes': {'delta_rule.pallas': {
        'seconds': 0.0, 'events': 0}}}}
    assert reader.read(ctx) is None
    assert reader.read(roofline_ctx('mellum-tiny', 'delta_rule.pallas',
                                    50e-6)) is None


def test_a_routed_share_counts_the_traced_steps_own_rows(monkeypatch):
    """The held assignments are those of the WHOLE TRACED steps (calls 2 to
    6 of the 7 under the trace), not the window's: 100 a step there, 40 in
    the window before. The bytes bound the count above at the test's peaks:
    320256 over 1e11 a second, 3.2 us of the 10 us a step paid."""
    from harness import program
    calls = [(t, t + 8) for t in range(100, 170, 10)]
    records = [{'name': 'engine.step_counters', 'ph': 'X', 't0_ns': t,
                't1_ns': t, 'args': {'moe.assignments_held': v}}
               for t, v in [(15, 40.0), (25, 40.0), (105, 70.0)]
               + [(t + 4, 100.0) for t, _ in calls[1:6]] + [(164, 130.0)]]
    fake = type('o', (), {
        'trace_events': staticmethod(lambda: records),
        'step_counters': type('c', (), {
            'SPAN': 'engine.step_counters',
            'drain': staticmethod(lambda wait=False: 0)})})
    monkeypatch.setattr(program, 'enable', lambda: fake)
    ctx = roofline_ctx('kimi-linear-tiny', 'grouped_matmul.pallas', 50e-6,
                       traced_calls_ns=calls)
    assert program.traced_mean(ctx, 5, 'moe.assignments_held') == 100.0
    reader = _tiny.harness_run.load_module('layer_metrics',
                                           'moe.experts_roofline')
    assert reader.read(ctx) == pytest.approx(32.0256)
    ctx['trace'][0]['scopes']['row_permute.pallas'] = {'seconds': 50e-6,
                                                       'events': 10}
    assert _tiny.harness_run.load_module(
        'layer_metrics', 'moe.permute_roofline').read(ctx) \
        == pytest.approx(10.24)
    monkeypatch.setattr(program, 'enable', lambda: None)    # no program
    assert reader.read(ctx) is None


def test_step_mfu_is_the_rows_operations_over_the_traced_period():
    reader = _tiny.harness_run.load_module('layer_metrics', 'step_mfu')
    chip = {'steps': 5, 'busy_s': 0.99, 'window_s': 1.0}
    ctx = {'trace': {0: chip}, 'traced_step_ms': [300.0] * 7, 'rows': 4,
           'chips': 2, 'flops_per_sample': 1e9,
           'peaks': {'bf16_flops_per_s': 1e11}}
    assert reader.read(ctx) == pytest.approx(100 * 4e9 / (2e11 * 0.2))
    chip['busy_s'] = 0.5        # the host did not keep up: the window's
    assert reader.read(ctx) == pytest.approx(100 * 4e9 / (2e11 * 0.3))
    assert reader.read(dict(ctx, trace={})) is None


def test_a_scopes_shared_part_is_said():
    """Two steps: a kernel's custom call under a layer's scope and its own
    (they nest: nothing shared), a fusion that holds the norm's backward
    AND the next layer's weight gradient (shared by both), a fusion of the
    norm alone."""
    from harness import scopes
    names = {'k': '%k.pallas.1 = f32[8] custom-call(f32[8] %p)',
             'mix': '%fusion.2 = f32[8] fusion(f32[8] %p), kind=kOutput, '
                    'calls=%fused_computation.2',
             'own': '%fusion.3 = f32[8] fusion(f32[8] %p), kind=kLoop, '
                    'calls=%fused_computation.3'}
    step = [('k', 0, 30), ('mix', 30, 50), ('own', 50, 60)]
    dev = {'modules': [('jit_step(1)', 100 * i, 100 * i + 60)
                       for i in range(4)],
           'ops': [(names[n], 100 * i + s, 100 * i + e)
                   for i in range(4) for n, s, e in step]}
    maps = {'engine.train_step0': {
        'k.pallas.1': ('k.pallas', 'layer.scan'),
        'fusion.2': ('layer.proj', 'norm.pallas'),
        'fusion.3': ('norm.pallas',)}}
    out = scopes.reduce({'devices': {0: dev}, 'host': []}, maps)
    assert out['per_step_ms'] == {
        'k.pallas': pytest.approx(30e-6), 'layer.scan': pytest.approx(30e-6),
        'layer.proj': pytest.approx(20e-6),
        'norm.pallas': pytest.approx(30e-6)}
    assert out['shared_ms'] == {
        'k.pallas': 0.0, 'layer.scan': 0.0,
        'layer.proj': pytest.approx(20e-6),
        'norm.pallas': pytest.approx(20e-6)}


# ------------------------------------------------------- the window's rule

class FakeRun:
    """`window`'s call, step and clock: call k takes `ms[k]` on a device
    that is never idle, and the host notices completion k `late[k]` ms
    late."""

    def __init__(self, ms, late=None):
        self.ms, self.late = ms, late or {}
        self.calls, self.now, self.device_free = 0, 0.0, 0.0

    def perf_counter(self):
        return self.now

    def __call__(self, step, state):
        k = self.calls
        self.calls += 1
        self.now += 1e-4                                    # the dispatch
        done = max(self.device_free, self.now) + self.ms(k) / 1e3
        self.device_free = done
        return state, FakeLoss(self, done + self.late.get(k, 0.0) / 1e3)


class FakeLoss:
    def __init__(self, run, seen):
        self.run, self.seen = run, seen

    def block_until_ready(self):
        self.run.now = max(self.run.now, self.seen)


@pytest.fixture
def job():
    return _tiny.harness_run.load_module('jobs', 'train')


@pytest.mark.parametrize('step_ms,seconds,pool_batches,steps', [
    (100.0, 3.0, 8, 32),        # 30 steps reach 3 s: on to the pass's end
    (100.0, 3.25, 8, 40),       # 32 steps end a pass just short of it
    (100.0, 3.15, 32, 32),      # never before `--seconds`
    (850.0, 36.0, 32, 64),      # the packed cells: two passes
    (125.9, 36.0, 64, 320),     # seq128
    (122.0, 36.0, 8, 296),      # ResNet-50
    (100.0, 0.0, 4, 4),         # a window is one pass at the least
])
def test_window_is_whole_passes(job, monkeypatch, step_ms, seconds,
                                pool_batches, steps):
    fake = FakeRun(lambda k: step_ms)
    monkeypatch.setattr(job, 'time', fake)
    fake.calls = job.LEAD_STEPS - 1         # set-up's calls
    _, t0, completions, losses, dispatched, first = job.window(
        fake, None, None, seconds, pool_batches)
    assert first == job.LEAD_STEPS
    assert len(completions) == len(losses) == len(dispatched) == steps
    assert steps % pool_batches == 0
    assert completions[-1] - t0 >= seconds
    # no earlier pass boundary lay at or after `--seconds`
    assert steps == pool_batches \
        or completions[steps - pool_batches - 1] - t0 < seconds
    assert np.diff([t0] + completions) * 1e3 == pytest.approx(step_ms)


def test_feed_begins_a_pass_at_the_first_counted_step(job):
    """The lead-in is the first batches of the old seeded walk, not run out
    to a pass's end; behind it every `pool` batches are a permutation."""
    family = _tiny.harness_run.load_module('families', 'kimi_linear')
    pool = list(range(5))
    for lead in (7, 3, 0):
        feed = job.Feed(family, {}, pool, 11, lead=lead, remember=3)
        given = [b for _, b in zip(range(lead + 15), iter(feed))]
        assert feed.order == given and feed.first == given[:3]
        old = np.random.default_rng([11, 0xFEED])
        walk = np.concatenate([old.permutation(5), old.permutation(5)])
        assert given[:lead] == list(walk[:lead])
        for p in range(3):
            assert sorted(given[lead + 5 * p:lead + 5 * p + 5]) == pool


def pool_times(passes, seed=0):
    """(ms of interval i, pool batch of interval i) over `passes` seeded
    orders of a pool of 32 whose batches take 715-950 ms."""
    rs = np.random.default_rng(seed)
    cost = rs.uniform(715.0, 950.0, 32)
    order = np.concatenate([rs.permutation(32) for _ in range(passes)])
    noise = rs.normal(0.0, 0.2, len(order))             # the device's own
    return cost[order] + noise, [int(b) for b in order]


@pytest.mark.parametrize('passes', [2, 5])
def test_late_notice_is_repaired_and_a_stall_is_not(job, passes):
    ms, batches = pool_times(passes)
    clean, found = job.late_notices(ms, batches)
    assert found == [] and (clean == ms).all()
    planted = ms.copy()
    planted[10] += 114.0            # noticed late ...
    planted[11] -= 114.0            # ... and the next one short by as much
    planted[40] += 300.0            # a stall nobody pays back
    repaired, found = job.late_notices(planted, batches)
    assert found == [10]
    assert repaired[10:12] == pytest.approx(ms[10:12], abs=1.0)
    assert repaired[40] == planted[40] == max(repaired)
    rest = [i for i in range(len(ms)) if i not in (10, 11)]
    assert (repaired[rest] == planted[rest]).all()
    # the tail: the late notice is out of it, the stall is in it
    assert np.quantile(planted, 0.95) > np.quantile(repaired, 0.95)
    assert np.quantile(repaired, 0.95) >= np.quantile(ms, 0.95)


@pytest.mark.parametrize('what,change', [
    ('long by under 1%', {10: +5.0, 11: -5.0}),
    ('paid back by half', {10: +100.0, 11: -50.0}),
    ('short, then long', {10: -100.0, 11: +100.0}),
    ('the window ends in it', {63: +100.0}),
])
def test_what_is_no_late_notice(job, what, change):
    ms, batches = pool_times(2)
    for i, d in change.items():
        ms[i] += d
    repaired, found = job.late_notices(ms, batches)
    assert found == [] and (repaired == ms).all()


def test_one_pass_has_nothing_to_hold_an_interval_against(job):
    ms, batches = pool_times(1)
    ms[10] += 114.0
    ms[11] -= 114.0
    repaired, found = job.late_notices(ms, batches)
    assert found == [] and (repaired == ms).all()


def test_run_reports_whole_passes_and_late_notices(monkeypatch, capsys):
    """A whole run at the test size: `steps` a multiple of the pool, the
    facts on the `window` and `slowest_step` lines."""
    from test_reference import LIMITS
    result = _tiny.drive('bert', limits=LIMITS['bert'], seconds=0.3,
                         monkeypatch=monkeypatch)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{')]
    window = [l for l in lines if l.get('phase') == 'window'][0]
    slowest = [l for l in lines if l.get('phase') == 'slowest_step'][0]
    assert result['attempted'] == window['steps']
    pool = _tiny.load(_tiny.SIZES['bert'][1])['pool_batches']
    assert window['steps'] == window['passes'] * pool
    assert window['late_notices'] == len(window['late_ms'])
    assert window['window_s'] >= 0.3
    assert slowest['late_notice'] in (True, False)
    assert 0 <= slowest['pool_batch'] < pool
