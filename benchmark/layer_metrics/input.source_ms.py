"""Input pipeline: mean time per batch the prefetcher's worker thread spent
in `next()` on its source (the program's span `prefetch.source`): host batch
assembly and augmentation, over the measured window. With
`input.convert_ms` it is what the producer needs a batch, to hold against
the step's period."""
from harness import program

program.enable()


def read(ctx):
    return program.mean_ms(ctx, 'prefetch.source')
