"""Device: share of a steady step in which no operation ran, on the chip
that idled most: 1 - (the device's busy time a step, from the trace's whole
steps) / (the median time between step completions in the untraced window).

The step's period comes from the window and not from the trace, because the
profiler slows the host: under it the host-side layout conversion of
ResNet-50's 38.5 MB uint8 batches took 2.5 s a batch and the traced steps
waited seconds for their input, where the untraced window's steps come
every 122.0 ms (PERF.md section 6). What the device does in a step is the
same under the profiler (BERT: both ways of reading it agree to 0.01%)."""


def read(ctx):
    chips = [c for c in ctx['trace'].values() if c['steps']]
    if not chips:
        return None
    period_s = ctx['step_ms_median'] / 1e3
    return max(100.0 * (1.0 - c['busy_s'] / c['steps'] / period_s)
               for c in chips)
