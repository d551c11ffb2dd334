"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data the harness finds by the names
in BENCHMARK.json: `configs/<config>.json`, `traffic/<traffic>.json`,
`families/<family>.py` (named by the configuration), `jobs/<job>.py` (named
by the traffic), `layer_metrics/<metric>.py`, `limits/<cell>.json`. The last
line of standard output is the result; a run that finds no TPU, or fewer
chips than the cell asks for, prints no result and exits 2.
"""
import time
T_START = time.perf_counter()       # set-up is counted from here

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """`<kind>/<name>.py` under the benchmark's directory, as a module."""
    path = os.path.join(HERE, kind, name + '.py')
    spec = importlib.util.spec_from_file_location(
        'benchmark_%s_%s' % (kind, name.replace('.', '_').replace('-', '_')),
        path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def find_cell(manifest, name):
    for cell in manifest['workloads']:
        if cell['name'] == name:
            return cell
    raise SystemExit('run.py: BENCHMARK.json has no workload %r (it has %s)'
                     % (name, [c['name'] for c in manifest['workloads']]))


def metrics_of(manifest, section, cell_name):
    """The metrics of `section` this cell reports."""
    return [m for m in manifest[section]
            if cell_name in m.get('workloads', [cell_name])]


def place_compile_cache():
    """JAX's persistent compile cache at a fixed place inside the checkout,
    whatever the environment says; the program's one cache switch takes the
    directory the environment names. Small programs stay out of it (PR 23:
    hundreds of them evicted the two that matter)."""
    cache = os.path.join(ROOT, '.jax_cache', 'benchmark')
    os.makedirs(cache, exist_ok=True)
    os.environ['JAX_COMPILATION_CACHE_DIR'] = cache
    import jax
    jax.config.update('jax_compilation_cache_dir', cache)
    jax.config.update('jax_compilation_cache_max_size', -1)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.3)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return cache


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    cell = find_cell(manifest, args.workload)
    config = load_json('configs', cell['config'] + '.json')
    traffic = load_json('traffic', cell['traffic'] + '.json')
    limits = load_json('limits', cell['name'] + '.json')

    sys.path.insert(0, ROOT)        # the program under test: paddle_tpu
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    cache = place_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < cell['chips']:
        print('run.py: %s needs %d TPU chip(s); JAX found %d x %s'
              % (cell['name'], cell['chips'], len(devices),
                 devices[0].platform), file=sys.stderr)
        return 2

    family = load_module('families', config['family'])
    reference = load_module('families', family.REFERENCE)
    job = load_module('jobs', traffic['job'])
    wanted = metrics_of(manifest, 'per_layer' if args.trace else 'end_to_end',
                        cell['name'])
    readers = {m['name']: load_module('layer_metrics', m['name'])
               for m in wanted} if args.trace else {}
    result = job.run(cell=cell, config=config, traffic=traffic, limits=limits,
                     family=family, reference=reference, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     wanted=wanted, readers=readers, t_start=T_START,
                     devices=devices[:cell['chips']],
                     scratch=os.path.join(ROOT, '.bench_scratch'),
                     facts={'compile_cache_dir': cache})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
