"""Device, across chips: time per step in which a collective runs and no
compute does, on the chip where that is longest. A cell on one chip has no
collective and reports nothing.

A collective is an op whose instruction is named as one (`%all-gather.3`)
or, since PR 47, a fusion whose called computation is one (`%fusion.14 =
... fusion(...), kind=kCustom, calls=%all-reduce-scatter.2`:
`harness/trace.is_collective`). Until then only the names counted, and the
four-chip cell read 3.43 (ledger, PR 46) while the gradients'
reduce-scatters, 86 such fusions that run on the compute stream one after
another with the matmuls, held ~10.9 ms a step more (Findings PR 45): a
fused collective overlaps nothing, so all of it is exposed."""


def read(ctx):
    chips = [c for c in ctx['trace'].values()
             if c['steps'] and c['collective_s'] > 0]
    if not chips:
        return None
    return max(1e3 * c['exposed_collective_s'] / c['steps'] for c in chips)
