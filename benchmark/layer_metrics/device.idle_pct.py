"""Device: share of a steady step in which no operation ran, on the chip
that idled most: 1 - (the device's busy time in the trace's whole steps) /
(the time those steps took).

The time those steps took is `harness/period.read`'s, and a reading takes
one of its two forms (the run's `idle_period` line says which, chip by
chip):

`trace`   the span of the whole traced steps on the trace's own device
          timeline, where the traced host kept up (the device busy for more
          than 95% of it, no traced get starved). New in PR 47: until then
          every reading took the form below, and since PR 38, when a routed
          step began to cost what its routing costs at that moment, the
          routed cells read below nought (-1.54 / -1.15 / -0.15 / -0.11 on
          Kimi / Mellum / Nemotron / JoyAI, ledger PR 46): the window's
          reading of "the same pool batch", taken tens of steps earlier
          under another routing, is not the traced step's period. The five
          decoder cells and the three BERT cells read this form.
`window`  what the untraced window read for the same pool batches
          (`ctx['traced_step_ms']`, PR 36; before that the window's median,
          PR 24), where the profiler slowed the host: ResNet-50, whose
          traced steps wait seconds for the host-side layout conversion of
          their 38.5 MB batches while the untraced window's come every
          122.0 ms. What the device does in a step is the same under the
          profiler (BERT: both forms agree to 0.01%).
"""
import json

from harness import period


def read(ctx):
    chips = {k: c for k, c in ctx['trace'].items() if c['steps']}
    if not chips:
        return None
    found = {}
    for k, c in chips.items():
        seconds, form = period.read(ctx, c)
        found[str(k)] = {'form': form, 'steps': c['steps'],
                         'period_ms': 1e3 * seconds / c['steps'],
                         'idle_pct': 100.0 * (1.0 - c['busy_s'] / seconds)}
    print(json.dumps({'phase': 'idle_period', 'per_chip': found},
                     sort_keys=True), flush=True)
    return max(c['idle_pct'] for c in found.values())
