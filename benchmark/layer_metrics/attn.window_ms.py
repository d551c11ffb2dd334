"""Text model layers: device time per step under the window-attention layers
(the scope `attn.window` of `nn.GroupedQueryAttention` with a window: the q
product at the query heads and the k and v products at the K/V heads, the
rotation of q and k, the flash kernels with the per-row first visible key
that carries document and window, `o_proj`), forward, recomputation and
backward together, on the chip where it takes longest. A program without
the scope reports nothing."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'attn.window')
