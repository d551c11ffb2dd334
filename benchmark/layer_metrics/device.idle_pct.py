"""Device: share of a steady step in which no operation ran, on the chip
that idled most: 1 - (the device's busy time in the trace's whole steps) /
(the time the untraced window read for the same pool batches).

The steps' period comes from the window and not from the trace, because the
profiler slows the host: under it the host-side layout conversion of
ResNet-50's 38.5 MB uint8 batches took 2.5 s a batch and the traced steps
waited seconds for their input, where the untraced window's steps come
every 122.0 ms (PERF.md section 6). What the device does in a step is the
same under the profiler (BERT: both ways of reading it agree to 0.01%).

It is the period of the batches that were TRACED, not the window's median:
where a step's time follows its batch (the packed cells since PR 32) six
traced steps of the pool's longer batches over six median periods read
below nought (-1.8, ledger PR 32). `ctx['traced_step_ms']` holds, for each
call the job made under the trace in order, what the window read for that
call's pool batch (the mean of its intervals, a late notice repaired). The
trace ends in the last of those calls and `harness/trace.steady_window`
leaves that run out, so a chip's `steps` whole steps are the ones before
it."""


def read(ctx):
    chips = [c for c in ctx['trace'].values() if c['steps']]
    if not chips:
        return None
    ms = ctx['traced_step_ms']
    return max(100.0 * (1.0 - c['busy_s']
                        / (sum(ms[-1 - c['steps']:-1]) / 1e3))
               for c in chips)
