"""Text model layers: device time per step under the Gated DeltaNet layers'
projections (the scope `gdn.proj` of `nn.GatedDeltaNet`: the q, k, v, a, b
and gate products, the short convolutions with SiLU and the l2norm, the
decay's and beta's activations, the gated per-head RMS norm and `o_proj`),
forward, recomputation and backward together, on the chip where it takes
longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'gdn.proj')
