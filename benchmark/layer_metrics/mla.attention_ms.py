"""Text model layers: device time per step under the latent-attention layer
(the scope `mla.attention` of `nn.LatentAttention`: its projections and the
flash-attention kernels with 192-wide q/k, 128-wide v and the document mask),
forward, recomputation and backward together, on the chip where it takes
longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'mla.attention')
