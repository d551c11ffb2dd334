"""What the tests under benchmark/tests share: the test sizes, and one way
to drive `jobs/train.py` on the CPU past the harness's look for a chip."""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))      # the program
sys.path.insert(0, BENCH)

import run as harness_run  # noqa: E402
from harness import peaks  # noqa: E402

SIZES = {'bert': ('bert-tiny', 'pretrain-tiny'),
         'resnet': ('resnet-tiny', 'train-tiny')}


def load(name):
    with open(os.path.join(HERE, 'data', name + '.json')) as f:
        return json.load(f)


def drive(family_name, limits, seconds=1.0, seed=7, wrap_step=None,
          monkeypatch=None):
    """A whole run of the train job at the test size -> the result object."""
    import jax
    if monkeypatch is not None:     # the CPU has no published peak
        monkeypatch.setattr(peaks, 'peaks_of', lambda kind: {
            'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11})
    config, traffic = (load(n) for n in SIZES[family_name])
    family = harness_run.load_module('families', config['family'])
    reference = harness_run.load_module('families', family.REFERENCE)
    job = harness_run.load_module('jobs', traffic['job'])
    wanted = [{'name': n, 'unit': 'x'} for n in
              ('samples_per_s', 'step_ms_p95', 'mfu_pct', 'setup_s')]
    return job.run(
        cell={'name': family_name + '.tiny', 'chips': 1}, config=config,
        traffic=traffic, limits=limits, family=family, reference=reference,
        seed=seed, seconds=seconds, trace=False, wanted=wanted, readers={},
        t_start=time.perf_counter(), devices=jax.devices()[:1],
        scratch=os.path.join(HERE, '.scratch'), facts={},
        wrap_step=wrap_step)
