"""Train step program: backend compiles (a program built or loaded) while
the window was open, from JAX's compile events. Anything but 0 is a fault,
and the run is then not `correct`."""


def read(ctx):
    return ctx['compiles_in_window']
