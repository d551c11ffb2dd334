"""Set-up path: host time spent lowering jaxprs to MLIR modules before the
window opened: the union of the program's `jax.lower` records (JAX's
`jaxpr_to_mlir_module_duration` events), without what a `costs.capture`
caused."""
from harness import program, setup

program.enable()


def read(ctx):
    return setup.read(ctx, 'lower_ms')
