"""Text model layers: device time per step under the router (the scope
`moe.route` of `nn.SparseMoE`: float32 scores over all experts, top-k,
weights), forward, recomputation and backward together, on the chip where it
takes longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'moe.route')
