"""Text model layers: device time per step under the full-attention layers
(the scope `attn.full` of `nn.CausalSelfAttention`: the q, k and v products,
the two RMS norms over the whole projections, the flash kernels with the
document bounds, `o_proj`), forward, recomputation and backward together, on
the chip where it takes longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'attn.full')
