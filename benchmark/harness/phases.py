"""A step's device time by phase: forward, backward, optimizer update, and
the fusions that mix them.

The program traces its step under named scopes (`forward`, `update`,
`guard` in `engine/builder.py`), so every HLO instruction's `op_name` says
its phase, and `observability.costs` keeps, for each train step it captured,
the phase of every instruction of the COMPILED module, looking inside each
fusion (`costs.phases(label)`: `forward` / `backward` / `update` / `guard`
/ `other`, or a mixed `backward+update` where XLA fused the weight
gradient's matmul with the optimizer's update). A TPU op event is named by
its instruction's whole text, `%fusion.808 = ...`, so the instruction's own
name joins the two. An event whose instruction the map lacks takes the
phase of its own `op_name="..."` where its text carries one, else `other`.

`read(ctx)` finds the trace where `jobs/train.py` leaves it
(`<checkout>/.bench_scratch/trace/**/*.xplane.pb`, one file), reads it once
and keeps the result on `ctx` for the four readers that share it. The
reduction (`reduce`) works on `harness/trace.read_xplane`'s plain lists, so
the test beside the harness runs it on the recorded trace without a chip.
"""
import collections
import glob
import json
import os
import re

from harness import program, trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PURE = ('forward', 'backward', 'update')
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def find_xplane(scratch=None):
    """The one `.xplane.pb` of this run's traced steps, or None."""
    scratch = scratch or os.path.join(ROOT, '.bench_scratch')
    found = glob.glob(os.path.join(scratch, 'trace', '**', '*.xplane.pb'),
                      recursive=True)
    return found[0] if len(found) == 1 else None


def pick_map(ops, maps):
    """Of the captured steps' phase maps, the one of the program these ops
    ran: the one that names the largest share of their time (the trace's
    module events carry a fingerprint, not the ledger's label). ->
    (label, map, share of op time it names), or (None, {}, 0.0)."""
    total = sum(e - s for _, s, e in ops)
    best = (None, {}, 0.0)
    for label, phase_map in sorted(maps.items()):
        named = sum(e - s for name, s, e in ops
                    if trace_mod.op_head(name) in phase_map)
        share = named / total if total else 0.0
        if share > best[2]:
            best = (label, phase_map, share)
    return best


def reduce(trace, maps, phase_of_op_name=None):
    """-> {'program', 'coverage', 'steps', 'per_step_ms': {phase: ms},
    'busy_ms'} on the chip that was busiest, each phase on the chip where it
    took longest: the union of the leaf ops of the whole traced steps under
    that phase, per step. None where the trace holds no whole step or no
    map names its ops' phases (a compile cache filled before the scopes
    existed serves executables without them: every instruction reads
    `other`)."""
    chips = []
    for chip, dev in sorted(trace['devices'].items()):
        lo, hi, steps = trace_mod.steady_window(dev)
        if not steps:
            continue
        ops = trace_mod.leaves(trace_mod.clip(dev['ops'], lo, hi))
        label, phase_map, coverage = pick_map(ops, maps)
        if not any(p == 'forward' for p in phase_map.values()):
            continue
        by_phase = collections.defaultdict(list)
        for name, s, e in ops:
            phase = phase_map.get(trace_mod.op_head(name))
            if phase is None:
                own = OP_NAME.search(name) if phase_of_op_name else None
                phase = phase_of_op_name(own.group(1)) if own else 'other'
            by_phase[phase].append((s, e))
        chips.append({
            'program': label, 'coverage': coverage, 'steps': steps,
            'busy_ms': trace_mod.length(trace_mod.union(
                (o[1], o[2]) for o in ops)) / 1e6 / steps,
            'per_step_ms': {
                phase: trace_mod.length(trace_mod.union(iv)) / 1e6 / steps
                for phase, iv in by_phase.items()}})
    if not chips:
        return None
    out = dict(max(chips, key=lambda c: c['busy_ms']))
    out['per_step_ms'] = {
        phase: max(c['per_step_ms'].get(phase, 0.0) for c in chips)
        for phase in sorted({p for c in chips for p in c['per_step_ms']})}
    return out


def phase_ms(reduced, phase):
    """ms a step under `forward` / `backward` / `update`, or under `mixed`:
    every phase that names more than one of them."""
    per = reduced['per_step_ms']
    if phase == 'mixed':
        return sum(v for k, v in per.items() if '+' in k)
    return per.get(phase, 0.0)


def read(ctx, phase):
    """ms a step of device time under `phase` in this run's trace, or None
    where there is nothing to read it from."""
    if 'step_phases' not in ctx:
        ctx['step_phases'] = _read_once()
    reduced = ctx['step_phases']
    return None if reduced is None else phase_ms(reduced, phase)


def _read_once():
    obs, path = program.enable(), find_xplane()
    costs = getattr(obs, 'costs', None)
    if path is None or not hasattr(costs, 'phases'):
        return None
    maps = {e['program']: costs.phases(e['program'])
            for e in costs.ledger()}
    maps = {k: v for k, v in maps.items() if v}
    reduced = reduce(trace_mod.read_xplane(path), maps,
                     costs.phase_of_op_name) if maps else None
    if reduced is not None:
        print(json.dumps({
            'phase': 'step_phases', 'program': reduced['program'],
            'map_coverage': reduced['coverage'], 'steps': reduced['steps'],
            'busy_ms': reduced['busy_ms'],
            'per_step_ms': reduced['per_step_ms'],
            'spans_dropped': obs.spans.dropped()}, sort_keys=True),
            flush=True)
    return reduced
