"""The comparison that decides `correct`: the program's first optimizer
steps against the plain reference's, number by number, each beside its
limit."""
import math
import statistics

import numpy as np

ZERO_GRADIENT = 1e-4    # of the median leaf's gradient norm
NUMBERS = {'loss_gap', 'first_gradient_gap', 'first_gradient_difference',
           'change_gap'}
NOT_COMPARED_HERE = {'loss_fall', 'readings'}   # the job's, and a pointer


def norm(x):
    x = np.asarray(x)
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


def positive_median(values):
    """The median of the values above 0 (a residual block that starts as
    the identity leaves most of its leaves with no gradient at all)."""
    positive = [float(v) for v in values if float(v) > 0]
    return statistics.median(positive) if positive else 0.0


def worst_leaf_gap(program, reference, skip=()):
    """The largest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf (of those with any gradient), whichever is larger (some
    gradients are all but zero). Leaves in `skip` are left out.
    -> (gap, leaf)"""
    floor = positive_median(reference.values())
    worst, where = 0.0, None
    for leaf, ref in reference.items():
        if leaf in skip:
            continue
        gap = abs(float(program[leaf]) - float(ref)) \
            / max(float(ref), floor, 1e-30)
        if gap != gap:              # a NaN gap is the worst there is
            return gap, leaf
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def compare(program, reference, limits):
    """-> (rows, ok). A row is (name, value, limit, ok, note). The cell's
    limits file names the numbers that are held, each with its limit; a
    number it does not name is not read. One that is not finite fails.

    `first_gradient_gap` and `change_gap` are gaps between NORMS, by the
    worst leaf: they see a leaf whose gradient is wrong or missing, and a
    state that did not move. A norm hides rounding that is not systematic
    (it enters squared), so where the norms do not tell the stated
    precision from the one below it, `first_gradient_difference` does: the
    norm of the DIFFERENCE between the program's first gradient and the
    reference's, leaf by leaf against the same floor, the median leaf
    reported. It is first order in rounding error and steady from seed to
    seed (PERF.md section 6 has the readings)."""
    unknown = set(limits) - NUMBERS - NOT_COMPARED_HERE
    if unknown:
        raise ValueError('limits name no number of the comparison: %s'
                         % sorted(unknown))
    rows = []

    def row(name, value, note=''):
        ok = math.isfinite(value) and value <= limits[name]
        rows.append((name, value, limits[name], ok, note))

    if 'loss_gap' in limits:
        for i, (p, r) in enumerate(zip(program['losses'],
                                       reference['losses'])):
            row('loss_gap', abs(p - r) / abs(r),
                'step %d: program %.6f reference %.6f' % (i, p, r))
    theirs, ours = program['first_gradient'], reference['first_gradient']
    grads = {k: norm(v) for k, v in ours.items()}
    floor = positive_median(grads.values())
    nonzero = {k for k, v in grads.items() if v >= ZERO_GRADIENT * floor}
    if 'first_gradient_gap' in limits:
        gap, leaf = worst_leaf_gap({k: norm(v) for k, v in theirs.items()},
                                   grads)
        row('first_gradient_gap', gap, 'worst leaf %s' % leaf)
    if 'first_gradient_difference' in limits:
        apart = sorted(norm(np.asarray(theirs[k]) - np.asarray(ours[k]))
                       / max(grads[k], floor, 1e-30) for k in nonzero)
        row('first_gradient_difference', apart[len(apart) // 2],
            'the median leaf of the %d with any gradient' % len(apart))
    if 'change_gap' in limits:
        # a leaf whose gradient is all but zero (a key projection's bias:
        # the softmax does not see it) moves by rounding noise alone under
        # an optimizer that normalises its step: its change compares nothing
        still = set(grads) - nonzero
        gap, leaf = worst_leaf_gap(program['change_norms'],
                                   reference['change_norms'], skip=still)
        row('change_gap', gap, 'worst leaf %s; %d leaves of all but zero '
            'gradient left out' % (leaf, len(still)))
    return rows, all(r[3] for r in rows)
