"""Text model layers: device time per step under the rotary position encoding
of the grouped-query attention layers, window and full alike (the scope
`attn.rope` inside `attn.window` / `attn.full` of `nn.GroupedQueryAttention`:
cos and sin of the positions inside documents times the layer's table, the
float32 half-split rotation of every query head and every K/V head over the
whole head), forward, recomputation and backward together, on the chip where
it takes longest. A program without the scope reports nothing."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'attn.rope')
