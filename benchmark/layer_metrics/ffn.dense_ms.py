"""Text model layers: device time per step under the dense SwiGLU of the
OLMo block (the scope `ffn.dense`, opened by `text/decoder_block.
PostNormDecoderBlock`: the gate, up and down products and the gate's
activation; the norm behind it is not under the scope), forward,
recomputation and backward together, on the chip where it takes longest."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'ffn.dense')
