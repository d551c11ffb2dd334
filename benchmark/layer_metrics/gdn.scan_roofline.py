"""Kernels: the delta-rule kernels' share of their roofline in a Gated
DeltaNet cell, forward and backward together, from the device trace.

Time: the union of the events under the scope `delta_rule.pallas`, per
step, on the slowest chip (the recomputation's forward kernel is in it: the
share is of what the step pays). Operations and bytes one step REQUIRES of
the rule at the PUBLISHED head sizes, whatever implements it and on whatever
lanes: per token and head, the state form's three products of d_k d_v
multiply-adds (decay and read, the rank-one write, the read for the output),
forward and the backward's two, 18 d_k d_v operations; bytes in the type
the kernel reads (float32): q, k, v, one decay, one beta read and o written
forward; those and do read and the five gradients written backward. At 96 /
192 the bytes bound it (7.5 ns a token and head against 1.7 ns of
operations), so the share says how far the kernels are from streaming their
operands once. A step whose rule took the XLA form has no such event and
reports nothing."""

from harness import roofline

SCOPES = ('delta_rule.pallas',)
BYTES_PER_ELEMENT = 4


def required(ctx):
    cfg, traffic = ctx['config'], ctx['traffic']
    rows = ctx['rows'] / ctx['chips']          # per chip
    layers = cfg['layer_types'][:cfg['num_hidden_layers']] \
        .count('linear_attention')
    k, v = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    token_heads = layers * rows * traffic['seq_len'] * cfg['heads_held'][1]
    operands = 2 * k + v + 2                   # q, k, v, g, beta
    elements = (operands + v) + (operands + v) + operands
    return (token_heads * 18 * k * v,
            token_heads * elements * BYTES_PER_ELEMENT)


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required)
