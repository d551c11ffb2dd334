"""Kernels: device time per step under the fused norm kernels' scopes
(`fused_dropout_norm.pallas`, `fused_layer_norm.pallas`: the forward kernels
and the backward's dropout-mask kernel; the rest of LayerNorm's backward is
XLA fusions outside the scope), on the slowest chip, from the device trace.

A time and not a share of a roofline: the operands of these kernels are
16.8 MB each and the compiler keeps most of them in on-chip memory (`S(1)`
in the traced instructions' layouts), so bytes counted from shapes against
the HBM peak read 196-202% (PERF.md section 6). A cell without these
kernels reports nothing."""

SCOPES = ('fused_dropout_norm.pallas', 'fused_layer_norm.pallas')


def read(ctx):
    chips = [c for c in ctx['trace'].values()
             if c['steps'] and any(c['scopes'][s]['events'] for s in SCOPES)]
    if not chips:
        return None
    return max(1e3 * sum(c['scopes'][s]['seconds'] for s in SCOPES)
               / c['steps'] for c in chips)
