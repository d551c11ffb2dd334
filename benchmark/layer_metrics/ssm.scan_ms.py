"""Text model layers: device time per step under the Mamba-2 layers'
state-space rule (the scope `ssm.scan` of `nn.Mamba2`: the chunk-scan
kernels `ssd.pallas`, with the XLA ops that make the chunks' decays from dt
and A and lay them out by group, and their gradient back to dt and A),
forward, recomputation and backward together, on the chip where it takes
longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'ssm.scan')
