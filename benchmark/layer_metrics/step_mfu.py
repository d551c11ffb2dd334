"""Train step program: the whole step's share of the chip's peak on the
traced steps: the operations their rows REQUIRE
(`families/<family>.flops_per_sample`, the count `mfu_pct` uses) over the
time those steps took (`harness/period.read`, the form `device.idle_pct`
takes on the same chip) and the chips' published bf16 peak
(`harness/peaks.json`), on the slowest chip.

It stands beside the kernels' `*_roofline` shares: a change that takes a
kernel off the path leaves that kernel's share silent, and its gain is then
bounded by this one. It is `mfu_pct` read on five or six steps: on the
packed cells it moves a few per cent with which batches the trace caught
(the operations are the traffic's expectation, the time that of the traced
batches)."""
from harness import period


def read(ctx):
    chips = [c for c in ctx['trace'].values() if c['steps']]
    if not chips or not ctx.get('flops_per_sample'):
        return None
    per_step = max(period.read(ctx, c)[0] / c['steps'] for c in chips)
    return 100.0 * ctx['rows'] * ctx['flops_per_sample'] / (
        ctx['chips'] * ctx['peaks']['bf16_flops_per_s'] * per_step)
