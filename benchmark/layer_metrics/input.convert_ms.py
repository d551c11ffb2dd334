"""Input pipeline: mean time per batch the prefetcher's worker thread spent
in the prefetcher's `convert` (the program's span `prefetch.convert`), over
the measured window, which no profiler slows. `convert` returns when the
transfer is ENQUEUED: the runtime's host-side layout conversion and the
copy run after it on the runtime's own threads, and no span of the program
sees them (40 ms for ResNet-50's 38.5 MB with no profiler session, 2 s
under one at host tracer level 1 or 2: my chip run, PR 25, PERF.md)."""
from harness import program

program.enable()


def read(ctx):
    return program.mean_ms(ctx, 'prefetch.convert')
