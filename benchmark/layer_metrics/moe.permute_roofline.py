"""Kernels: the row-permutation kernels' share of their roofline (the held
rows' way from token order into the experts' row buffer and back,
`row_permute.pallas`: `gather_rows`, `combine_rows` and each as the other's
backward), from the device trace (`harness/roofline.py` says what the time
is).

Bytes one step REQUIRES of the four moves, whatever implements them: every
assignment this chip HOLDS on the traced steps (`moe.assignments_held`,
`moe.experts_roofline`'s count: a tile's padding moves nothing) is a row of
`hidden` channels read once and written once a move, in the type moved: the
gather and its backward in the compute type, the combine and its backward
in float32 (the products' float32 results, the float32 cotangent).
Operations: the combine's weight, one multiply-add a channel each way. The
bytes bound it, so the share says how far the kernels are from moving each
held row once at the HBM's rate. A program that keeps no such counter
reports nothing."""
from harness import program, roofline

program.enable()

SCOPES = ('row_permute.pallas',)


def required(ctx, rows):
    item = roofline.ITEM[ctx['config']['compute_dtype']]
    elements = rows * roofline.shapes(ctx, 'experts')['hidden']
    return elements * 2 * 2, elements * 2 * (2 * item + 2 * 4)


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required, 'experts',
                         counter='moe.assignments_held')
