"""Kernels: the flash-attention kernels' share of their roofline on PACKED
rows with grouped-query heads and window layers, forward and backward
together, from the device trace.

Time: the union of the events under the scope `flash_attention.pallas`, per
step, on the slowest chip (the recomputation's forward kernel is in it: the
share is of what the step pays). Operations and bytes one step REQUIRES of
attention, whatever implements it: the (query, key) pairs a layer's mask
admits, counted from the pool's LAYOUT (the traffic's `layout_seed`, as
`families/kimi_linear.make_pool` draws the rows' documents): a full layer
the causal pairs inside documents, n (n + 1) / 2 a document of n; a window
layer those inside the window too, w (w + 1) / 2 + (n - w) w where n > w.
Per pair and query head: forward QK^T and PV (2 products of 2 d), backward
dV, dP, dQ, dK (4 of them), 12 d operations; the backward's recomputed
scores do not count. Bytes in the configuration's compute type: q read and o
written at the QUERY heads, k and v read at the K/V heads forward; q, o, dO
read and dQ written at the query heads, k, v read and dK, dV written at the
K/V heads backward.

The pairs are the MEAN over the pool's batches, the time that of the five
or six steps the trace caught: a reading swings a few per cent with which
batches those were (a full layer's pairs differ five-fold between batches,
a window layer's far less). The two sides of a comparison share a seed, and
so the batches. A step whose attention took the XLA path has no such event
and reports nothing."""
import numpy as np

from families import kimi_linear

SCOPES = ('flash_attention.pallas',)
BYTES_PER_ELEMENT = 2


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
    cfg, traffic = ctx['config'], ctx['traffic']
    rows = ctx['rows'] // ctx['chips']          # per chip
    L, d = traffic['seq_len'], cfg['head_dim']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    kinds = cfg['layer_types'][:cfg['num_hidden_layers']]
    pairs = {'full_attention': pairs_per_row(traffic, rows, None),
             'sliding_attention': pairs_per_row(traffic, rows,
                                                cfg['sliding_window'])}
    flops = rows * heads * 12 * d * sum(pairs[kind] for kind in kinds)
    bytes_ = len(kinds) * rows * L * d * (6 * heads + 6 * kv) \
        * BYTES_PER_ELEMENT
    return flops, bytes_


def read(ctx):
    chips = [c for c in ctx['trace'].values()
             if c['steps'] and c['scopes'][SCOPES[0]]['events']]
    if not chips:
        return None
    seconds = max(c['scopes'][SCOPES[0]]['seconds'] / c['steps']
                  for c in chips)
    flops, bytes_ = required(ctx)
    least = max(flops / ctx['peaks']['bf16_flops_per_s'],
                bytes_ / ctx['peaks']['hbm_bytes_per_s'])
    return 100.0 * least / seconds
