"""Kernels: device time per step under the RMS norm's forward kernel
(`fused_rms_norm.pallas`: its custom calls ONLY, the op events named after
the scope), a recomputation's second forward among them, on the slowest
chip, from the device trace.

A time and not a share of a roofline, for `fused_norms_ms`'s reason: the
compiler keeps much of these kernels' operands in on-chip memory, and bytes
counted from shapes against the HBM peak read 196-202% there (PERF.md
section 6). And the custom calls only, because the rest of what lies under
the scope is not the kernel's: the closed-form backward's XLA fusions, and
fusions that hold a neighbouring layer's work as well (weight-gradient
products, the router's), which the `layer_scopes` line lists whole under
every scope they touch (a Kimi step: 51.7 ms under `fused_rms_norm.pallas`
on that line, 23.9 of them shared, the custom calls 11.9: my chip runs, PR
47). A step whose norms took the XLA path has no such event and reports
nothing."""
from harness import roofline

SCOPES = ('fused_rms_norm.pallas',)


def read(ctx):
    found = roofline.slowest(ctx, SCOPES[0])
    return None if found is None else 1e3 * found[0]
