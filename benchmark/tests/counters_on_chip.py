"""A run of one cell with the program's step counters printed after it (not
a pytest test: it needs the chip).

    python3 benchmark/tests/counters_on_chip.py --workload <cell> --seed <n> \
        --seconds 36 --trace 1

The arguments are `run.py`'s, and so is everything printed before the last
line. A traced run turns the program's telemetry on, and a net that names
counters (`step_counter_names`: the sparse decoders' `moe.*`, the prediction
module's `loss.main` / `loss.mtp`) then leaves one record a step; no
per-layer metric reads most of them. The last line, after the run's result:
`{"phase": "step_counters", "steps": n, "first": {...}, "last": {...},
"mean": {...}, "max": {...}}` over the steps of the whole run.
"""
import json
import sys

import _tiny
from harness import program


def main(argv=None):
    code = _tiny.harness_run.main(argv)
    obs = program.enable()
    counters = getattr(obs, 'step_counters', None)
    if code != 0 or counters is None:
        return code
    counters.drain(wait=True)
    found = [ev['args'] for ev in obs.trace_events()
             if ev.get('name') == counters.SPAN and ev.get('args')]
    if found:
        print(json.dumps({
            'phase': 'step_counters', 'steps': len(found),
            'first': found[0], 'last': found[-1],
            'mean': {k: sum(a[k] for a in found) / len(found)
                     for k in found[0]},
            'max': {k: max(a[k] for a in found) for k in found[0]}},
            sort_keys=True), flush=True)
    return code


if __name__ == '__main__':
    sys.exit(main())
