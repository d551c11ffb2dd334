"""Input pipeline: share of the consumer's gets in the measured window that
found the prefetcher's queue empty: 100 x `prefetch.starved` /
`prefetch.gets`. Each `prefetch.get_wait` record carries both counters as
they stood after its own get, so the window's counts are the difference of
the readings at its two ends."""
from harness import program

program.enable()


def read(ctx):
    found = [ev.get('args') or {}
             for ev in program.records(ctx, 'prefetch.get_wait')]
    found = [a for a in found if 'gets' in a and 'starved' in a]
    if not found:
        return None
    first, last = found[0], found[-1]
    gets = last['gets'] - first['gets'] + 1
    starved = last['starved'] - first['starved'] \
        + (1 if first.get('depth') == 0 else 0)
    return 100.0 * starved / gets
