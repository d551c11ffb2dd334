"""Input pipeline: mean host time per step spent waiting in `next()` on the
DevicePrefetcher, over the measured window (the benchmark's own span)."""


def read(ctx):
    return ctx['spans'].mean_ms('input.wait', *ctx['window'])
