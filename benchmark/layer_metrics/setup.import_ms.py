"""Set-up path: how long `import paddle_tpu` took (the program's record
`paddle_tpu.import`: stamps at the first and last statement of the package's
`__init__.py`; JAX's import is inside it where the process had not imported
JAX before, as `run.py` has). What precedes it (the interpreter, `run.py`'s
own imports, `jax.devices()`) is outside the program: `setup_spans` says how
long it was."""
from harness import program, setup

program.enable()


def read(ctx):
    return setup.read(ctx, 'import_ms')
