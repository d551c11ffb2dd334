"""A step's device time by layer scope: the instructions the program traced
under a layer's `jax.named_scope` (`kda.scan`, `moe.experts`, ...), XLA
fusions included.

A Pallas kernel's custom call is named after its scope, which
`harness/trace.reduce` finds in the instruction's name; an XLA fusion is
`%fusion.123`, and only its `op_name` metadata, and that of the instructions
fused into it, says where it came from. The program keeps that map for each
train step it captured (`observability.costs.scopes(label)`: instruction ->
the registered scopes it lies under), so a traced op is put under a scope by
its instruction's own name. A fusion that mixes two scopes counts under
both, whole, and so a scope's time is no more its own than its fusions are:
the `layer_scopes` line therefore gives, beside each scope's `per_step_ms`,
its `shared_ms`: the part of that time whose instructions ALSO lie under a
registered scope that does not nest with this one (a weight gradient's
product fused with the norm's backward in front of it). Two scopes nest
where one instruction that calls no computation (a kernel's custom call, a
copy) lies under both: `kda.scan` and the `delta_rule.pallas` inside it
share nothing. Read a scope as at most `per_step_ms` and at least
`per_step_ms - shared_ms`; size nothing from the first alone (my chip runs,
PR 47: three decoders' `fused_rms_norm.pallas` read 51-52 ms, 22-31 of them
shared, their custom calls 12-17). The
map names an instruction's scopes, not which of them each fused instruction
came from, so the shared part cannot be split further here. A program
without the map (an older commit) gives nothing to read: `read` returns
None and does not raise.
"""
import json

from harness import phases, program, trace as trace_mod


def _calls(name):
    """Whether the instruction behind an op event calls a computation (a
    fusion, a reduce): what it lies under is then the union over what it
    holds, and says nothing of which scopes nest."""
    return 'calls=' in name or 'to_apply=' in name


def reduce(trace, maps):
    """-> {'program', 'coverage', 'per_step_ms': {scope: ms}, 'shared_ms':
    {scope: ms}}: each scope on the chip where it took longest, the union of
    the leaf ops of the whole traced steps under it, per step, and the part
    of it under ops that lie under another scope as well, one it does not
    nest with. None where the trace holds no whole step or no map names any
    of its ops."""
    chips = []
    for _, dev in sorted(trace['devices'].items()):
        lo, hi, steps = trace_mod.steady_window(dev)
        if not steps:
            continue
        ops = trace_mod.leaves(trace_mod.clip(dev['ops'], lo, hi))
        label, scope_map, coverage = phases.pick_map(ops, maps)
        if label is None:
            continue
        under = [(scope_map.get(trace_mod.op_head(name), ()), name, s, e)
                 for name, s, e in ops]
        nested = {frozenset((a, b)) for found, name, _, _ in under
                  if not _calls(name) for a in found for b in found}
        by_scope, shared = {}, {}
        for found, name, s, e in under:
            for scope in found:
                by_scope.setdefault(scope, []).append((s, e))
                if any(frozenset((scope, other)) not in nested
                       for other in found if other != scope):
                    shared.setdefault(scope, []).append((s, e))

        def per_step(intervals):
            return trace_mod.length(trace_mod.union(intervals)) / 1e6 / steps
        chips.append({
            'program': label, 'coverage': coverage,
            'per_step_ms': {k: per_step(v) for k, v in by_scope.items()},
            'shared_ms': {k: per_step(shared.get(k, ())) for k in by_scope}})
    if not chips:
        return None
    out = dict(chips[0])
    for key in ('per_step_ms', 'shared_ms'):
        out[key] = {
            scope: max(c[key].get(scope, 0.0) for c in chips)
            for scope in sorted({s for c in chips for s in c[key]})}
    return out


def read(ctx, scope):
    """ms a step of device time under `scope` in this run's trace, or None
    where there is nothing to read it from."""
    if 'layer_scopes' not in ctx:
        ctx['layer_scopes'] = _read_once()
    reduced = ctx['layer_scopes']
    if reduced is None:
        return None
    return reduced['per_step_ms'].get(scope)


def _read_once():
    obs, path = program.enable(), phases.find_xplane()
    costs = getattr(obs, 'costs', None)
    if path is None or not hasattr(costs, 'scopes'):
        return None
    maps = {e['program']: costs.scopes(e['program']) for e in costs.ledger()}
    maps = {k: v for k, v in maps.items() if v}
    if not maps:
        return None
    reduced = reduce(trace_mod.read_xplane(path), maps)
    if reduced is not None:
        print(json.dumps({'phase': 'layer_scopes', **reduced},
                         sort_keys=True), flush=True)
    return reduced
