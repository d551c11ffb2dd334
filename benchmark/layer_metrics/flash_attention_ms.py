"""Kernels: device time per step under the flash-attention kernels' scope
(`flash_attention.pallas`: the forward kernel, the backward pass's
recomputation of it where a layer recomputes, and the one backward kernel),
on the slowest chip, from the device trace.

A time and not a share of a roofline: on packed rows the work the kernels
need follows the documents of each batch (they sweep the tile pairs a row's
documents reach, not every pair under the diagonal), so a constant count
of operations would read the traffic, not the kernel. The time is what the
latent-attention cells pay. A step whose attention took the XLA path has no
such event and reports nothing."""

SCOPES = ('flash_attention.pallas',)


def read(ctx):
    chips = [c for c in ctx['trace'].values()
             if c['steps'] and c['scopes'][SCOPES[0]]['events']]
    if not chips:
        return None
    return max(1e3 * c['scopes'][SCOPES[0]]['seconds'] / c['steps']
               for c in chips)
