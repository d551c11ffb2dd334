"""Text model layers: device time per step under the Gated DeltaNet layers'
delta rule (the scope `gdn.scan` of `nn.GatedDeltaNet`: the delta-rule
kernels, with the XLA ops that lay the heads of 96 / 192 on whole lanes, hand
the scalar decay to them as channels and drop the zero channels again),
forward, recomputation and backward together, on the chip where it takes
longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'gdn.scan')
