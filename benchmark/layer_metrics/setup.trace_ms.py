"""Set-up path: host time spent tracing Python into jaxprs before the window
opened: the union of the program's `jax.trace` records (JAX's
`jaxpr_trace_duration` events), without what a `costs.capture` caused. Most
of it is the steps' own traces, under `engine.dispatch` with `first`."""
from harness import program, setup

program.enable()


def read(ctx):
    return setup.read(ctx, 'trace_ms')
