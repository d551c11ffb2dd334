"""A kernel family's share of its roofline on the traced steps: the least
time the chip could take for the operations and bytes a step REQUIRES of
the family's mathematics (a reader's `required(ctx)`: the larger of
operations over the published bf16 peak and bytes over the published HBM
peak, `harness/peaks.json`) over the time the step paid for it.

The time paid is the union of the op events named after the kernels' scope
(`flash_attention.pallas`: the compiler names a custom call after its
innermost jax scope, `harness/trace.reduce`), per whole traced step, on the
chip where it is longest: the kernels' own events, a recomputation's second
forward among them, and none of the XLA fusions around them (those the
`layer_scopes` line lists under the same scope, with their shared part).
A step without such an event took another path and reports nothing, never a
0. A share over 100 is a wrong count, not a fast kernel."""
from harness import program

ITEM = {'bfloat16': 2, 'float16': 2, 'float32': 4}    # bytes an element


def slowest(ctx, scope):
    """The chip whose whole traced steps spent longest under `scope`'s
    events -> (seconds a step, its whole steps), or None."""
    chips = [c for c in ctx['trace'].values()
             if c['steps'] and c['scopes'][scope]['events']]
    if not chips:
        return None
    chip = max(chips, key=lambda c: c['scopes'][scope]['seconds'] / c['steps'])
    return chip['scopes'][scope]['seconds'] / chip['steps'], chip['steps']


def share(ctx, seconds, flops, bytes_):
    least = max(flops / ctx['peaks']['bf16_flops_per_s'],
                bytes_ / ctx['peaks']['hbm_bytes_per_s'])
    return 100.0 * least / seconds


def read(ctx, scope, required, shapes_key=None, counter=None):
    """The share of `scope`'s kernels, or None where a step ran none of
    them, where the cell's family says nothing under `shapes_key`, or where
    the program kept no `counter`. `required(ctx)` -> (operations, bytes) a
    step; with `counter` (a step counter's name) `required(ctx, mean)`, the
    counter's mean over the chip's whole traced steps: the steps the time is
    of."""
    found = slowest(ctx, scope)
    if found is None or (shapes_key and not shapes(ctx, shapes_key)):
        return None
    seconds, steps = found
    if counter is None:
        return share(ctx, seconds, *required(ctx))
    mean = program.traced_mean(ctx, steps, counter)
    if mean is None:
        return None
    return share(ctx, seconds, *required(ctx, mean))


def shapes(ctx, key):
    """What the cell's family says its model asks of a kernel family
    (`families/<family>.kernel_shapes`), or None where it says nothing."""
    describe = getattr(ctx.get('family'), 'kernel_shapes', None)
    return describe(ctx['config']).get(key) if describe else None


def tokens(ctx):
    """Tokens a step gives one chip."""
    return ctx['rows'] // ctx['chips'] * ctx['traffic']['seq_len']
