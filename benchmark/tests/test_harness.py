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
    slow host stretched; the untraced window's steps came every 122.0055 ms,
    whatever their batch."""
    reader = _tiny.harness_run.load_module('layer_metrics', 'device.idle_pct')
    ctx = {'traced_step_ms': [122.0055] * 7,
           'trace': {0: {'steps': 6, 'busy_s': 0.731716132,
                         'window_s': 2.863382462}}}
    assert reader.read(ctx) == pytest.approx(0.043, abs=0.001)
    assert reader.read({'traced_step_ms': [1.0] * 7, 'trace': {}}) is None


def test_idle_share_takes_the_period_of_the_batches_that_were_traced():
    """A packed cell: the window's steps run 715-950 ms by their batch
    (median 822) and the six whole traced steps are the pool's longest,
    the device busy 99.94% of each. Over six MEDIAN periods that read -11;
    over the window's readings of the same batches it is what the trace
    says. A trace that caught five whole steps takes the five before its
    last."""
    reader = _tiny.harness_run.load_module('layer_metrics', 'device.idle_pct')
    ms = [905.0, 949.0, 921.0, 934.0, 940.0, 917.0, 715.0]
    chip = {'steps': 6, 'busy_s': 0.9994 * sum(ms[:6]) / 1e3}
    got = reader.read({'traced_step_ms': ms, 'trace': {0: chip}})
    assert got == pytest.approx(0.06, abs=1e-6)
    assert 100.0 * (1.0 - chip['busy_s'] / (6 * 0.822)) < -10
    chip = {'steps': 5, 'busy_s': 0.9994 * sum(ms[1:6]) / 1e3}
    assert reader.read({'traced_step_ms': ms, 'trace': {0: chip}}) \
        == pytest.approx(0.06, abs=1e-6)


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
