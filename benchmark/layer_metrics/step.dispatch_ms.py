"""Train step program: mean host time per call of `step(state, batch, key)`
over the measured window (the benchmark's own span). Hidden behind the
device until it nears the step time."""


def read(ctx):
    return ctx['spans'].mean_ms('step.dispatch', *ctx['window'])
