"""Kernels: the delta-rule kernels' share of their roofline in a Kimi Delta
Attention cell (a decay for every CHANNEL of the key), forward and backward
together, from the device trace (`harness/roofline.py` says what the time
is).

Operations and bytes one step REQUIRES of the rule at the published head
sizes, whatever implements it: per token and head, the state form's three
products of d_k d_v multiply-adds (decay and read, the rank-one write, the
read for the output), forward and the backward's two, 18 d_k d_v
operations, the count `flops_per_sample` makes; bytes in the type the
kernels read (float32): q, k, v, the decays and one beta read and o written
forward; those and do read and the five gradients written backward. At 128
/ 128 with 128 decays the bytes bound it (a token and head: 7.2 KB against
0.29 MFLOP, 8.8 ns against 1.5 ns), so the share says how far the kernels
are from streaming their operands once. `gdn.scan_roofline` is the same
count for the rule with one decay a head."""
from harness import roofline

SCOPES = ('delta_rule.pallas',)
BYTES_PER_ELEMENT = 4


def required(ctx):
    rule = roofline.shapes(ctx, 'delta_rule')
    k, v = rule['key_dim'], rule['value_dim']
    token_heads = rule['layers'] * rule['heads'] * roofline.tokens(ctx)
    operands = 2 * k + v + rule['decays'] + 1          # q, k, v, g, beta
    elements = (operands + v) + (operands + v) + operands
    return (token_heads * 18 * k * v,
            token_heads * elements * BYTES_PER_ELEMENT)


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required, 'delta_rule')
