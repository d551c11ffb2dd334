"""Kernels: the short-convolution kernels' share of their roofline (per
tensor `l2norm_per_head(silu(causal_conv(y, w, seg)))`, the norm and a bias
where the layer has them), forward and backward together, from the device
trace (`harness/roofline.py` says what the time is).

Operations and bytes one step REQUIRES of the convolutions the family lists
(`kernel_shapes`: one entry a call, its channels at the PUBLISHED head
sizes, never the lanes a kernel lays them on), whatever implements them:
per token and channel, forward the projection's output read in the compute
type and the result written in float32 (what the rule behind it reads);
backward the float32 cotangent and the input read, the input's gradient
written in the compute type; the taps (and the bias) read twice and their
float32 gradient written once a call. One multiply-add a tap and element
forward, two backward (the input's and the taps' gradients): 6 x taps
operations; SiLU and the norm do not count. The bytes bound it by far (14
bytes an element against 24 operations: 17 ps against 0.12 ps), so the
share says how far the kernels are from streaming their operands once."""
from harness import roofline

SCOPES = ('short_conv.pallas',)


def required(ctx):
    item = roofline.ITEM[ctx['config']['compute_dtype']]
    tokens = roofline.tokens(ctx)
    flops = bytes_ = 0
    for call in roofline.shapes(ctx, 'short_conv'):
        width, rows = call['channels'], call['taps'] + bool(call['bias'])
        flops += tokens * width * 6 * call['taps']
        bytes_ += tokens * width * (item + 4 + 4 + item + item) \
            + 3 * rows * width * 4
    return flops, bytes_


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required, 'short_conv')
