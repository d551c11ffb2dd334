"""The period of the steps a trace holds whole: what their busy time and
their operations are held against (`device.idle_pct`, `step_mfu`).

Two forms, and `read` says which one a chip's reading took:

`trace`   the trace's own device timeline: from the start of the first
          whole execution of the step to the end of the last
          (`harness/trace.steady_window`). Taken where the traced host kept
          up: the device was busy for more than 95% of that span and no get
          of the traced calls found the prefetcher's queue empty. It is the
          time the TRACED steps took. Since PR 38 a routed step costs what
          its routing costs at that moment, and the routing drifts inside a
          run, so what the untraced window read for "the same pool batch"
          some tens of steps earlier is not the traced step's period: the
          routed cells read -1.54 / -1.15 / -0.15 / -0.11 (ledger, PR 46)
          where the trace itself was 99.9% busy.
`window`  what the untraced window read for the same pool batches
          (`ctx['traced_step_ms']`: for each call made under the trace, in
          order, the mean of its batch's intervals, a late notice repaired),
          the form of PR 24 and PR 36. Taken where the profiler slowed the
          host: under it the host-side layout conversion of ResNet-50's
          38.5 MB batches took 2.5 s a batch and the traced steps waited
          seconds for their input, where the untraced window's steps come
          every 122.0 ms. What the device does in a step is the same under
          the profiler.

The trace ends in the last of the calls made under it and `steady_window`
leaves that run out, so a chip's `steps` whole steps are the calls before
it."""
from harness import program

KEPT_UP = 0.95      # busy share of the traced span from which it is trusted


def starved(ctx, steps):
    """Whether a get of the whole traced steps' calls found the prefetcher's
    queue empty, by the program's own `prefetch.get_wait` records (each
    carries the `starved` count as it stood after its get, and the depth it
    found). False where the program keeps no such record: the busy share
    alone then decides."""
    calls = program.traced_calls(ctx, steps)
    if not calls:
        return False
    found = [ev.get('args') or {} for ev in program.records_between(
        'prefetch.get_wait', calls[0][0], calls[-1][1])]
    found = [a for a in found if 'starved' in a]
    return bool(found) and (found[-1]['starved'] > found[0]['starved']
                            or found[0].get('depth') == 0)


def read(ctx, chip):
    """(seconds the chip's `steps` whole traced steps took, which form)."""
    if chip['window_s'] > 0 \
            and chip['busy_s'] / chip['window_s'] > KEPT_UP \
            and not starved(ctx, chip['steps']):
        return chip['window_s'], 'trace'
    ms = ctx['traced_step_ms']
    return sum(ms[-1 - chip['steps']:-1]) / 1e3, 'window'
