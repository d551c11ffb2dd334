"""The readings a cell's limits are set from, on the chip, at the cell's own
size, in one process (not a pytest test: it needs the chip;
`test_reference.py` keeps the same control at a test size).

    python3 benchmark/tests/control_on_chip.py --workload <cell> \
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 1 2 3 4

For each seed: the batches the cell's run would be fed first and the plain
reference's readings from them. Beside them, `--seeds`: the PROGRAM's
readings, its checked step driven as a run's set-up drives it (the sound
runs: each has to pass the cell's limits); `--control-seeds`: the readings
of the reference computed in float8 and put in the program's place (the
control: each has to fail them). Prints every number beside the cell's
limit; the last line says whether every sound run passed and every control
failed.
"""
import argparse
import os
import json
import sys

import _tiny
from harness import check, params
from harness.spans import Spans


def worst_rows(table):
    worst = {}
    for name, value, limit, good, note in table:
        if value >= worst.get(name, (-1,))[0]:
            worst[name] = (value, limit, good, note)
    return worst


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, nargs='*', default=[])
    ap.add_argument('--control-seeds', type=int, nargs='*', default=[])
    args = ap.parse_args(argv)
    run = _tiny.harness_run
    with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
        cell = run.find_cell(json.load(f), args.workload)
    config = run.load_json('configs', cell['config'] + '.json')
    traffic = run.load_json('traffic', cell['traffic'] + '.json')
    limits = run.load_json('limits', cell['name'] + '.json')
    run.place_compile_cache()
    import jax
    job = run.load_module('jobs', traffic['job'])
    family = run.load_module('families', config['family'])
    reference = run.load_module('families', family.REFERENCE)
    spec = family.param_spec(config)
    devices = jax.devices()[:cell['chips']]
    rows = traffic['batch_per_chip'] * cell['chips']
    print(json.dumps({'device': devices[0].device_kind,
                      'cell': cell['name'], 'rows': rows}), flush=True)
    if args.seeds:      # the object a run checks: the twin where there is one
        step, make_state, _ = job.build_step(
            family, config, traffic, devices,
            deterministic=family.stochastic(config))
    sound_passed, control_failed = [], []
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        feed = job.Feed(family, traffic,
                        family.make_pool(config, traffic, seed,
                                         traffic['pool_batches'], rows),
                        seed, remember=job.CHECK_STEPS)
        readings = None
        if seed in args.seeds:
            feed_iter = job.prefetcher(step, feed)
            try:
                state, readings = job.checked_steps(
                    job.Caller(feed_iter, Spans(), config['compute_dtype']),
                    step, make_state(seed), family, config,
                    lambda: params.make(spec, seed))
            finally:
                feed_iter.close()
            del state
            batches = feed.first
        else:
            batches = [b for b, _ in zip(iter(feed), range(job.CHECK_STEPS))]
        sound = reference.follow_steps(config, config['optimizer'],
                                       params.make(spec, seed), batches)
        if readings is not None:
            table, ok = check.compare(readings, sound, limits)
            sound_passed.append(ok)
            print(json.dumps({'seed': seed, 'what': 'program', 'correct': ok,
                              'numbers': worst_rows(table)}), flush=True)
        if seed in args.control_seeds:
            lower = reference.follow_steps(
                config, config['optimizer'], params.make(spec, seed),
                batches, precision='float8')
            table, ok = check.compare(lower, sound, limits)
            control_failed.append(not ok)
            print(json.dumps({'seed': seed, 'what': 'float8 control',
                              'correct': ok, 'numbers': worst_rows(table)}),
                  flush=True)
    good = all(sound_passed) and all(control_failed)
    print(json.dumps({'every_sound_run_passed': all(sound_passed),
                      'every_control_failed': all(control_failed)}))
    return 0 if good else 1


if __name__ == '__main__':
    sys.exit(main())
