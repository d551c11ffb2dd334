"""Train step program: device time per step under fusions that hold more
than one phase (`backward+update`: XLA fuses a weight gradient's matmul
with the optimizer's update; `forward+backward`: a forward value
recomputed inside a backward fusion), on the chip where it takes longest.
Said as it is, never split or guessed: `harness/phases.py` prints each
mix on its own line of the run's `step_phases` fact."""
from harness import phases, program

program.enable()


def read(ctx):
    return phases.read(ctx, 'mixed')

