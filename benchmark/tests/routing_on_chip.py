"""How often rounding moves a token to another expert, on the chip, at the
cell's own size (not a pytest test: it needs the chip).

    python3 benchmark/tests/routing_on_chip.py \\
        --workload kimi-linear-48b-a3b.train-pack8k --seeds 1 2

Top-k is discontinuous: a score that rounding moves past its neighbour's
sends the token elsewhere, and the reference is not handed the program's
routing. For each seed, on the first batch a run would be fed: the experts
the plain reference selects in float32, those the PROGRAM selects (its own
forward pass under its autocast), and those of the float8 control. Printed: the share of
(token, expert layer) pairs whose selected SET differs from the
reference's, per layer, and the share of single picks that differ.
"""
import argparse
import json
import os
import sys

import numpy as np

import _tiny
from harness import params


def disagreement(ours, theirs):
    """ours, theirs: per layer (B, T, k) sorted picks -> per layer the share
    of tokens whose set differs, and the share of picks not in common."""
    out = []
    for a, b in zip(ours, theirs):
        a, b = np.asarray(a), np.asarray(b)
        common = (a[..., :, None] == b[..., None, :]).any(-1).sum(-1)
        out.append({'tokens': float(np.mean(np.any(a != b, axis=-1))),
                    'picks': float(1.0 - common.mean() / a.shape[-1])})
    return out


def program_picks(family, config, spec, seed, batch):
    import jax
    from paddle_tpu import amp
    from paddle_tpu.nn.layer_base import functional_call
    holder = {}

    def abstract():
        holder['net'] = family.build(config)[0]
        return 0
    jax.eval_shape(abstract)
    net = holder['net']

    @jax.jit
    def picks(state, ids, seg, labels):
        from paddle_tpu.core.tensor import Tensor
        selected = []
        with amp.auto_cast(dtype=config['compute_dtype']):
            functional_call(net, state, Tensor(ids), Tensor(seg),
                            Tensor(labels), selected=selected)
        return [s._value for s in selected]

    state = dict(params.make(spec, seed))
    state.update(params.make(family.buffer_spec(config), seed, salt=1))
    (ids, seg, labels), _ = batch
    return jax.device_get(picks(state, ids, seg, labels))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    args = ap.parse_args(argv)
    run = _tiny.harness_run
    with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
        cell = run.find_cell(json.load(f), args.workload)
    config = run.load_json('configs', cell['config'] + '.json')
    traffic = run.load_json('traffic', cell['traffic'] + '.json')
    run.place_compile_cache()
    family = run.load_module('families', config['family'])
    reference = run.load_module('families', family.REFERENCE)
    spec = family.param_spec(config)
    rows = traffic['batch_per_chip'] * cell['chips']
    for seed in args.seeds:
        batch = family.make_pool(config, traffic, seed, 1, rows)[0]
        sound, control = [], []
        reference.follow_steps(config, config['optimizer'],
                               params.make(spec, seed), [batch],
                               routing=sound)
        reference.follow_steps(config, config['optimizer'],
                               params.make(spec, seed), [batch],
                               precision='float8', routing=control)
        ours = program_picks(family, config, spec, seed, batch)
        print(json.dumps({'seed': seed,
                          'program': disagreement(ours, sound),
                          'float8 control': disagreement(control, sound)}),
              flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
