"""The program's own records, read from inside it: the spans and counters
`paddle_tpu.observability` keeps at the train path's layer boundaries
(`prefetch.*` in `io.DevicePrefetcher`, `engine.dispatch` in
`engine.TrainStep.__call__`), cut to the measured window by time.

`run.py` loads a cell's readers before the job runs and only with
`--trace 1`; a reader of the program's records calls `enable()` as it is
imported, so the program's telemetry is on in traced runs and off in the
end-to-end ones. Turning it on sets a flag and registers one
`jax.monitoring` listener: no thread starts and no file is written.

The program's records and the benchmark's own spans (`harness/spans.py`)
are both stamped with `time.perf_counter_ns()`, so the window is the
interval from the start of the benchmark's first span in it to the end of
its last. A program that keeps no such record (an older commit) gives
nothing to read: every function here then returns None and does not raise.
"""


def enable():
    """Turn the program's telemetry on -> its observability module, or None
    where the program has none."""
    try:
        from paddle_tpu import observability
        observability.enable()
    except Exception:           # an older program: nothing to read from
        return None
    return observability


def window_ns(ctx):
    """(start_ns, end_ns) of the measured window, from the benchmark's own
    spans: `ctx['window']` marks its first and one past its last record."""
    records, (lo, hi) = ctx['spans'].records, ctx['window']
    if hi <= lo:
        return None
    return records[lo][1], records[hi - 1][2]


def records(ctx, name):
    """The program's span records called `name` that lie inside the measured
    window, in order, or [] where there are none."""
    obs, bounds = enable(), window_ns(ctx)
    if obs is None or bounds is None:
        return []
    lo, hi = bounds
    return [ev for ev in obs.trace_events()
            if ev.get('name') == name and ev.get('ph') == 'X'
            and 't0_ns' in ev and lo <= ev['t0_ns'] and ev['t1_ns'] <= hi]


def mean_ms(ctx, name):
    """Mean duration of the program's `name` spans in the window, or None."""
    found = records(ctx, name)
    if not found:
        return None
    return sum(ev['t1_ns'] - ev['t0_ns'] for ev in found) / len(found) / 1e6
