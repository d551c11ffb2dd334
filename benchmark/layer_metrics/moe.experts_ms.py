"""Text model layers: device time per step under the routed experts this chip
holds (the scope `moe.experts` of `nn.SparseMoE`: sorting the assignments,
gathering rows and weights by block, the grouped products, the scatter back),
forward, recomputation and backward together, on the chip where it takes
longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'moe.experts')
