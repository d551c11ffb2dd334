"""Text model layers: device time per step under the rotary position
encoding of the latent-attention layers (the scope `mla.rope` inside
`mla.attention` of `nn.LatentAttention`: cos and sin of the positions inside
documents, the float32 rotation of every head's 64-wide query slice and of
the one shared key slice), forward, recomputation and backward together, on
the chip where it takes longest. A program without the scope reports
nothing."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'mla.rope')
