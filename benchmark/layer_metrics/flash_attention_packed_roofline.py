"""Kernels: the flash-attention kernels' share of their roofline on PACKED
rows, whatever the layers' heads (grouped-query, latent at 192 / 128, a
share by heads) and masks (causal, a window), forward and backward
together, from the device trace (`harness/roofline.py` says what the time
is: the recomputation's forward kernel is in it, the share is of what the
step pays).

Operations and bytes one step REQUIRES of attention, whatever implements
it, over the layers the cell's family lists (`kernel_shapes`' `attention`:
one entry a layer the step runs, a prediction module's among them, with its
window, its query and K/V heads and the sizes of a query/key head and a
value head; since PR 47: until then the reader read Mellum's own keys and
served that cell alone). The (query, key) pairs a layer's mask admits are
counted from the pool's LAYOUT (the traffic's `layout_seed`, as
`families/kimi_linear.make_pool` draws the rows' documents): a full layer
the causal pairs inside documents, n (n + 1) / 2 a document of n; a window
layer those inside the window too, w (w + 1) / 2 + (n - w) w where n > w.
Per pair and query head: forward QK^T (2 d_qk) and PV (2 d_v), backward dV
and dP (2 d_v each), dQ and dK (2 d_qk each): 6 d_qk + 6 d_v operations, 12
d where the two are one size; the backward's recomputed scores do not count.
Bytes in the configuration's compute type: q read and o written at the
QUERY heads, k and v read at the K/V heads forward; q, o, dO read and dQ
written at the query heads, k, v read and dK, dV written at the K/V heads
backward.

The pairs are the MEAN over the pool's batches, the time that of the five
or six steps the trace caught: a reading swings a few per cent with which
batches those were (a full layer's pairs differ five-fold between batches,
a window layer's far less). The two sides of a comparison share a seed, and
so the batches. A step whose attention took the XLA path has no such event
and reports nothing."""
import numpy as np

from families import kimi_linear
from harness import roofline

SCOPES = ('flash_attention.pallas',)


def pairs_per_row(traffic, rows, window):
    """Mean over the pool's rows of the pairs one head's mask admits: causal
    inside documents, and inside `window` keys where one is given."""
    layout = np.random.default_rng([int(traffic['layout_seed']), 0x4B1A])
    total, n_rows = 0, traffic['pool_batches'] * rows
    for _ in range(n_rows):
        for n in kimi_linear._lengths(layout, traffic, traffic['seq_len']):
            w = n if window is None else min(n, window)
            total += w * (w + 1) // 2 + (n - w) * w
    return total / n_rows


def required(ctx):
    traffic = ctx['traffic']
    rows = ctx['rows'] // ctx['chips']          # per chip
    item = roofline.ITEM[ctx['config']['compute_dtype']]
    layers = roofline.shapes(ctx, 'attention')
    pairs = {w: pairs_per_row(traffic, rows, w)
             for w in {layer['window'] for layer in layers}}
    flops = elements = 0
    for layer in layers:
        sizes = layer['qk_dim'] + layer['v_dim']
        flops += rows * layer['heads'] * pairs[layer['window']] * 6 * sizes
        elements += rows * traffic['seq_len'] * 3 * sizes \
            * (layer['heads'] + layer['kv_heads'])
    return flops, elements * item


def read(ctx):
    return roofline.read(ctx, SCOPES[0], required, 'attention')
