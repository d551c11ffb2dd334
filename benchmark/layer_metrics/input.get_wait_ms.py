"""Input pipeline: mean host time per step the consumer spent in the
prefetcher's queue `get` (the program's span `prefetch.get_wait`, in
`io.DevicePrefetcher.__iter__`), over the measured window. The inside twin
of `input.wait_ms`, without the generator's own Python around the get."""
from harness import program

program.enable()


def read(ctx):
    return program.mean_ms(ctx, 'prefetch.get_wait')
