"""Text model layers: device time per step under the attention layers' output
gate (the scope `attn.gate` inside `attn.full` / `attn.window` of
`nn.GroupedQueryAttention(gate='per_head')`: the gate's projection of the
layer's normed input to one scalar a query head, its sigmoid, and the
product with the heads on their way into `o_proj`, which also takes them
from the flash kernels' layout to the projection's), forward, recomputation
and backward together, on the chip where it takes longest. A fusion that
holds the gate's product AND a neighbour's work (the `o_proj` product's
operand) counts whole: read it beside its `shared_ms` on the `layer_scopes`
line. A program without the scope reports nothing."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'attn.gate')
