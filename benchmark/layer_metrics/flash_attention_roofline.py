"""Kernels: the flash-attention kernels' share of their roofline, forward
and backward together, from the device trace.

Time: the union of the events under the scope `flash_attention.pallas`,
per step, on the slowest chip. Operations and bytes one step REQUIRES of
them, from the cell's shapes: per layer and per head, forward QK^T and PV
(2 products of 2 L^2 d), backward dV, dP, dQ, dK (4 of them); the
backward's recomputation of the scores does not count. Bytes: Q, K, V read
and O written forward; Q, K, V, O, dO read and dQ, dK, dV written backward,
in the configuration's compute type; at seq 512 the operations bound it
(6.3 ms against 5.9 ms of bytes a step), which also keeps the share honest
where the compiler holds operands in on-chip memory. A step whose attention
took the XLA path has no such event and reports nothing."""

from harness import roofline

SCOPES = ('flash_attention.pallas',)


def required(ctx):
    cfg, traffic = ctx['config'], ctx['traffic']
    rows = ctx['rows'] / ctx['chips']          # per chip
    L, H = traffic['seq_len'], cfg['hidden_size']
    layers = cfg['num_hidden_layers']
    flops = layers * rows * 6 * (2 * L * L * H)
    bytes_ = layers * rows * 12 * L * H * 2
    return flops, bytes_


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required)
