"""Text model layers: device time per step under the delta rule's chunk-wise
scan (the scope `kda.scan` of `nn.KimiDeltaAttention`: the scores inside
chunks, the triangular solves, the scan over the chunks' states, the outputs),
forward, recomputation and backward together, on the chip where it takes
longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'kda.scan')
