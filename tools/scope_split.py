"""A traced run of one benchmark cell, then the device time under ONE layer
scope split by op family and by instruction (needs the chip).

    python3 tools/scope_split.py moe.experts --workload <cell> --seed <n> \
        --seconds 36 --trace 1

The arguments behind the scope are `benchmark/run.py`'s, and so is
everything printed before the last lines. Several scopes, separated by
commas (`attn.rope,attn.window`), are split from the one run, a line each.
`moe.experts_ms` and its like say what a scope costs a step; this says what
it is made of: the leaf ops of the whole traced steps whose instruction the
program's map puts under the scope (`observability.costs.scopes`), summed by
`harness/trace.op_family` and, for the heaviest, one by one with the head of the instruction's text
(its shape says which gather, product or scatter it is). Last lines:
`{"phase": "scope_split", ...}` and the step counters of the run
(`{"phase": "step_counters", ...}`, as `benchmark/tests/counters_on_chip.py`
prints them).
"""
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'benchmark'))

HEAVIEST = 24


def split(trace, maps, scope, trace_mod, phases):
    """-> {'steps', 'ms_a_step', 'families': [[family, ms a step, events a
    step]], 'heaviest': [[ms a step, events a step, instruction]]} on the
    first chip whose ops a map names."""
    for _, dev in sorted(trace['devices'].items()):
        lo, hi, steps = trace_mod.steady_window(dev)
        if not steps:
            continue
        ops = trace_mod.leaves(trace_mod.clip(dev['ops'], lo, hi))
        label, scope_map, _ = phases.pick_map(ops, maps)
        if label is None:
            continue
        under = [o for o in ops
                 if scope in scope_map.get(trace_mod.op_head(o[0]), ())]
        families, events = collections.Counter(), collections.Counter()
        single, calls, text = collections.Counter(), collections.Counter(), {}
        for name, s, e in under:
            family = trace_mod.op_family(name)
            families[family] += e - s
            events[family] += 1
            head = trace_mod.op_head(name)
            single[head] += e - s
            calls[head] += 1
            text[head] = name[:260]
        per = 1e6 * steps
        return {
            'scope': scope, 'program': label, 'steps': steps,
            'ms_a_step': trace_mod.length(trace_mod.union(
                (o[1], o[2]) for o in under)) / per,
            'families': [[f, t / per, events[f] / steps]
                         for f, t in families.most_common()],
            'heaviest': [[t / per, calls[h] / steps, text[h]]
                         for h, t in single.most_common(HEAVIEST)]}
    return None


def main(argv):
    scope, argv = argv[0], argv[1:]
    import run as harness_run
    from harness import phases, program, trace as trace_mod
    code = harness_run.main(argv)
    obs = program.enable()
    path = phases.find_xplane()
    if code != 0 or obs is None or path is None:
        return code or 1
    maps = {e['program']: obs.costs.scopes(e['program'])
            for e in obs.costs.ledger()}
    trace = trace_mod.read_xplane(path)
    maps = {k: v for k, v in maps.items() if v}
    for one in scope.split(','):
        found = split(trace, maps, one, trace_mod, phases)
        print(json.dumps({'phase': 'scope_split', **(found or {})}),
              flush=True)
    counters = obs.step_counters
    counters.drain(wait=True)
    seen = [ev['args'] for ev in obs.trace_events()
            if ev.get('name') == counters.SPAN and ev.get('args')]
    if seen:
        print(json.dumps({
            'phase': 'step_counters', 'steps': len(seen),
            'first': seen[0], 'last': seen[-1],
            'mean': {k: sum(a[k] for a in seen) / len(seen)
                     for k in seen[0]},
            'max': {k: max(a[k] for a in seen) for k in seen[0]}},
            sort_keys=True), flush=True)
    return code


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
