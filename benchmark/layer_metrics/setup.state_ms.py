"""Set-up path: making the train state and placing it: the self time of the
program's `engine.init_state` records (around `TrainStep.init_state`: the
optimizer's slots, the guard's and the scaler's counters, and for a sharded
step `engine.place_state`), summed over the run's steps, without the `jax.*`
records inside them (the one-op programs that creation compiles or
loads)."""
from harness import program, setup

program.enable()


def read(ctx):
    return setup.read(ctx, 'state_ms')
