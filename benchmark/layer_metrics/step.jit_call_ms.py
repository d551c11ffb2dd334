"""Train step program: mean host time per call of the step's compiled
program alone (the program's span `engine.dispatch` around `self._jit(...)`
in `engine.TrainStep.__call__`), over the measured window. The inside twin
of `step.dispatch_ms`, without `amp.auto_cast` and `__call__`'s own Python."""
from harness import program

program.enable()


def read(ctx):
    return program.mean_ms(ctx, 'engine.dispatch')
