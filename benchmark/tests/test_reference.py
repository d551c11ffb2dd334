"""The plain references against the system, at a test size on the CPU, and
the controls that have to come out as not correct.

The test sizes compute in float32 on both sides, so what is held here is
that the reference and the program do the same mathematics (on the chip, at
the published widths, every run holds the stated bf16 precision to the
limits in `benchmark/limits/`).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json

import pytest

import _tiny
from harness import check, params

# The numbers under the names the cells' limits files use. Read on seeds 7,
# 8 (program) and 11-13 (controls), my CPU runs, PR 24:
#   bert    program: loss_gap 0, first_gradient_gap 5e-6,
#           first_gradient_difference 2e-6, change_gap 0.0015;
#           float8 control: first_gradient_gap 0.010-0.014,
#           first_gradient_difference 0.035-0.041 (bfloat16 operands: 0.002-0.003)
#   resnet  program: 0.0003-0.0021, 0.0009-0.0011, 0.005-0.008, 0.024-0.025;
#           float8 control: first_gradient_gap 0.04-0.14,
#           first_gradient_difference 0.40-0.42 (bfloat16 operands: 0.09-0.10)
LIMITS = {'bert': {'loss_gap': 1e-4, 'first_gradient_gap': 1e-3,
                   'first_gradient_difference': 1e-3, 'change_gap': 1e-2,
                   'loss_fall': -1e9},
          'resnet': {'loss_gap': 6e-3, 'first_gradient_gap': 5e-3,
                     'first_gradient_difference': 0.05, 'change_gap': 0.06,
                     'loss_fall': -1e9}}


def compare_rows(captured):
    rows = [json.loads(line) for line in captured.splitlines()
            if line.startswith('{')]
    return [r for r in rows if r.get('phase') == 'compare']


@pytest.mark.parametrize('family_name', ['bert', 'resnet'])
def test_program_follows_the_reference(family_name, monkeypatch, capsys):
    result = _tiny.drive(family_name, limits=LIMITS[family_name],
                         monkeypatch=monkeypatch)
    rows = compare_rows(capsys.readouterr().out)
    assert result['correct'], [r for r in rows if not r['ok']]
    assert {r['number'] for r in rows} >= {
        'loss_gap', 'first_gradient_gap', 'first_gradient_difference',
        'change_gap', 'finite_losses', 'compiles_in_window', 'loss_fall'}
    assert result['failed'] == 0 and result['attempted'] > 1
    assert set(result['metrics']) == {'samples_per_s', 'step_ms_p95',
                                      'mfu_pct', 'setup_s'}


@pytest.mark.parametrize('family_name', ['bert', 'resnet'])
def test_lower_precision_control_is_not_correct(family_name):
    """The reference computed in float8, put in the program's place."""
    config, traffic = (_tiny.load(n) for n in _tiny.SIZES[family_name])
    family = _tiny.harness_run.load_module('families', config['family'])
    reference = _tiny.harness_run.load_module('families', family.REFERENCE)
    spec = family.param_spec(config)
    for seed in (11, 12, 13):
        batches = family.make_pool(config, traffic, seed, 3,
                                   traffic['batch_per_chip'])
        sound = reference.follow_steps(config, config['optimizer'],
                                       params.make(spec, seed), batches)
        control = reference.follow_steps(config, config['optimizer'],
                                         params.make(spec, seed), batches,
                                         precision='float8')
        rows, ok = check.compare(control, sound, LIMITS[family_name])
        assert not ok, rows
        failed = {r[0] for r in rows if not r[3]}
        assert failed >= {'first_gradient_gap',
                          'first_gradient_difference'}, rows


def test_the_cells_hold_numbers_these_tests_plant_faults_under():
    """Every number a committed cell holds is one of LIMITS': the controls
    here and the broken steps of test_broken_path.py fail numbers the cells
    hold, under the cells' own names."""
    import glob
    import os
    files = glob.glob(os.path.join(_tiny.BENCH, 'limits', '*.json'))
    assert files
    for path in files:
        with open(path) as f:
            held = set(json.load(f)) - {'readings'}
        assert held <= set(LIMITS['bert']), (path, held)
        assert {'loss_gap', 'first_gradient_gap', 'change_gap',
                'loss_fall'} <= held, (path, held)


def test_one_wrong_leaf_fails_the_worst_leaf_and_not_the_median():
    """A gradient that is wrong in one leaf of many (a kernel's backward, a
    head): the median leaf's difference does not see it, the worst leaf's
    norm does."""
    import numpy as np
    rs = np.random.default_rng(5)
    sound = {'losses': [1.0], 'change_norms': {},
             'first_gradient': {'leaf%d' % i: rs.normal(size=64)
                                for i in range(9)}}
    program = dict(sound, first_gradient=dict(sound['first_gradient']))
    program['first_gradient']['leaf3'] = 1.5 * sound['first_gradient']['leaf3']
    rows, ok = check.compare(program, sound, {
        'first_gradient_gap': 0.1, 'first_gradient_difference': 0.1})
    assert [(r[0], r[3]) for r in rows] == [
        ('first_gradient_gap', False), ('first_gradient_difference', True)]
    with pytest.raises(ValueError):
        check.compare(program, sound, {'first_gradient_gapp': 0.1})
