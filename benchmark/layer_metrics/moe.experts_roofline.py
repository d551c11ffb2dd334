"""Kernels: the grouped-product kernels' share of their roofline (the routed
experts' products over the row buffer, `grouped_matmul.pallas`), forward
and backward together, from the device trace (`harness/roofline.py` says
what the time is).

Operations and bytes one step REQUIRES of the held experts, whatever
implements them: the assignments this chip HOLDS on the traced steps
(`moe.assignments_held`, summed over the expert layers: a value of the
compiled step which the program records beside each dispatch,
`harness/program.traced_counters`; never `moe.rows_computed`, the buffer's
rows: a tile's padding is not required work) times the products of the
family's experts (`kernel_shapes`' `experts`: gate, up and down, or up and
down ungated, at the PUBLISHED width), each 2 x hidden x width operations a
row, forward and the backward's two. Bytes in the compute type: each
product's matrices read (or, for their gradient, written) once a pass and
layer; each product's row operand read and its result written a pass. At an
even router's load the operations bound it (Kimi: 4096 rows a layer, 0.88 ms
against 0.41 ms of bytes). The count is the mean over the whole traced
steps, the steps the time is of: a routed step's load follows its routing at
that moment. A program that keeps no such counter reports nothing."""
from harness import program, roofline

program.enable()

SCOPES = ('grouped_matmul.pallas',)


def required(ctx, rows):
    experts = roofline.shapes(ctx, 'experts')
    item = roofline.ITEM[ctx['config']['compute_dtype']]
    H, F = experts['hidden'], experts['width']
    passes = 3 * experts['products']
    matrices = experts['layers'] * experts['held'] * H * F
    return (rows * passes * 2 * H * F,
            passes * (matrices + rows * (H + F)) * item)


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required, 'experts',
                         counter='moe.assignments_held')
