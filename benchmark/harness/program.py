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
    bounds = window_ns(ctx)
    return [] if bounds is None else records_between(name, *bounds)


def records_between(name, lo, hi):
    """The program's span records called `name` that lie inside [lo, hi] on
    `time.perf_counter_ns`, in order, or [] where there are none."""
    obs = enable()
    if obs is None:
        return []
    return [ev for ev in obs.trace_events()
            if ev.get('name') == name and ev.get('ph') == 'X'
            and 't0_ns' in ev and lo <= ev['t0_ns'] and ev['t1_ns'] <= hi]


def traced_calls(ctx, steps):
    """(start_ns, end_ns) of each of the job's calls whose steps a chip's
    trace holds WHOLE, in order, from the start of the call's `input.wait`
    span to the end of its `step.dispatch`: of the calls the job made under
    the profiler (`ctx['traced_calls_ns']`) the `steps` before the last,
    which is the run the trace ends in and `harness/trace.steady_window`
    leaves out."""
    calls = ctx.get('traced_calls_ns') or []
    return calls[-1 - steps:-1] if len(calls) > steps else []


def traced_counters(ctx, steps):
    """The step counters' records (`observability.step_counters`: values of
    the compiled step, stamped inside the dispatch that returned them) of
    the `steps` whole traced steps: a list of {name: value}, or [] where
    the program keeps none. A routed step's work follows its routing at
    that moment (Kimi: 15312 held assignments at a window's start, 38634
    at its end), so a count that is held against the TRACED steps' device
    time has to be theirs and not the window's mean."""
    obs = enable()
    counters = getattr(obs, 'step_counters', None)
    calls = traced_calls(ctx, steps)
    if counters is None or not calls:
        return []
    counters.drain(wait=True)
    # (a record is stamped inside a dispatch: between the first of these
    # calls' start and the last one's end there are only theirs)
    return [ev['args'] for ev in records_between(
        counters.SPAN, calls[0][0], calls[-1][1]) if ev.get('args')]


def mean_ms(ctx, name):
    """Mean duration of the program's `name` spans in the window, or None."""
    found = records(ctx, name)
    if not found:
        return None
    return sum(ev['t1_ns'] - ev['t0_ns'] for ev in found) / len(found) / 1e6


def traced_mean(ctx, steps, name):
    """The mean of the step counter `name` over the `steps` whole traced
    steps, or None where no record carries it."""
    found = [a[name] for a in traced_counters(ctx, steps) if name in a]
    return sum(found) / len(found) if found else None
