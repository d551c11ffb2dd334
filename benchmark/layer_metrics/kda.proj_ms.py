"""Text model layers: device time per step under the KDA layers' projections
(the scope `kda.proj` of `nn.KimiDeltaAttention`: the q/k/v, decay, beta and
gate products, the short convolutions with their SiLU and l2norm, the output
norm, gate and `o_proj`), forward, recomputation and backward together, on
the chip where it takes longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'kda.proj')
