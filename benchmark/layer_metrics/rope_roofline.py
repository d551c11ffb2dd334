"""Kernels: the rotary kernel's share of its roofline, forward and backward
together, from the device trace (`harness/roofline.py` says what the time
is).

Bytes one step REQUIRES of the rotation, whatever implements it: the
channels the rotation TURNS (`kernel_shapes`' `rotary`: q's and k's a token
and layer), each read once and written once forward and once more each way
backward (the transpose of a rotation is a rotation), in the compute type.
Six operations a channel and pass (two products, a sum; the partner's too)
do not come near the bytes. The tables (cos and sin of the positions, which
a kernel could work out itself) and whatever else a pass carries do not
count: the kernel of PR 44 also writes the flash kernels' layout, so where a
head turns only a part of its channels (the latent layers: 64 of 192) it
moves three times what the rotation needs and the share says so; where the
whole head turns (grouped-query attention) the share is the pass's own."""
from harness import roofline

SCOPES = ('rotary.pallas',)


def required(ctx):
    item = roofline.ITEM[ctx['config']['compute_dtype']]
    turned = sum(roofline.shapes(ctx, 'rotary')) * roofline.tokens(ctx)
    return turned * 6 * 2, turned * 4 * item


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required, 'rotary')
