"""Text model layers: device time per step under the Mamba-2 layers'
projections (the scope `ssm.proj` of `nn.Mamba2`: the products with W_in's
columns and W_out, the three short convolutions with their bias and SiLU
(`ssm.conv`, inside it), dt's softplus chain, the gate and the grouped RMS
norm), forward, recomputation and backward together, on the chip where it
takes longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'ssm.proj')
