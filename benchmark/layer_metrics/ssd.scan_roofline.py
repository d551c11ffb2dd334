"""Kernels: the chunk-scan kernels' share of their roofline in a Mamba-2
cell, forward and backward together, from the device trace.

Time: the union of the events under the scope `ssd.pallas`, per step, on the
slowest chip (the recomputation's forward kernel is in it: the share is of
what the step pays). Operations and bytes one step REQUIRES of the rule at
the PUBLISHED sizes, whatever implements it: per token and head, the state
form's three products of P N multiply-adds (the decay, the rank-one write
dt x B^T, the read S C), forward and the backward's two, 18 P N operations;
bytes in the type the kernels read (float32): x, dt, B and C read and y
written forward; those and dy read and the gradients of x, dt, B and C
written backward, B and C counted once a GROUP (its heads share them). At
64 / 128 with 8 heads a group the bytes bound it (a token and layer: 107 KB
against 9.4 MFLOP, 131 ns against 48 ns), so the share says how far the
kernels are from streaming their operands once. A step whose rule took the
XLA form has no such event and reports nothing."""

from harness import roofline

SCOPES = ('ssd.pallas',)
BYTES_PER_ELEMENT = 4


def required(ctx):
    cfg, traffic = ctx['config'], ctx['traffic']
    rows = ctx['rows'] / ctx['chips']          # per chip
    layers = cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']] \
        .count('M')
    H, P = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    G, N = cfg['n_groups'], cfg['ssm_state_size']
    tokens = layers * rows * traffic['seq_len']
    forward = 2 * H * P + H + 2 * G * N         # x, y; dt; B, C
    backward = 3 * H * P + 2 * H + 4 * G * N    # x, dy, dx; dt, ddt; B, C, dB, dC
    return (tokens * H * 18 * P * N,
            tokens * (forward + backward) * BYTES_PER_ELEMENT)


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required)
