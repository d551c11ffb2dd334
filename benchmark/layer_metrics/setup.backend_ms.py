"""Set-up path: time in JAX's backend phase before the window opened: the
union of the program's `jax.backend` records (JAX's
`backend_compile_duration` events: a compile, or a load from the persistent
cache, which the record's `cache` says), without what a `costs.capture`
caused. `harness/compiles.py` sums the same events from outside."""
from harness import program, setup

program.enable()


def read(ctx):
    return setup.read(ctx, 'backend_ms')
