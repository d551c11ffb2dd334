"""Train step program: device time per step under the forward pass (the
compiled instructions traced under `jvp(forward)`), on the chip where it
takes longest: the union of the leaf ops of the whole traced steps that
`observability.costs` puts under that phase (`harness/phases.py`)."""
from harness import phases, program

program.enable()


def read(ctx):
    return phases.read(ctx, 'forward')

