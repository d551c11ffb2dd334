"""Set-up path: programs compiled before the window opened that the
persistent cache did not hold: the program's `jax.backend` records with
`cache: 'miss'`. JAX fires the miss only for a compile it then wrote to the
cache (past the cache's admission time and size), so an eager one-op
program never counts. 0 in a warm run."""
from harness import program, setup

program.enable()


def read(ctx):
    return setup.read(ctx, 'cache_misses')
