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
both. A program without the map (an older commit) gives nothing to read:
`read` returns None and does not raise.
"""
import json

from harness import phases, program, trace as trace_mod


def reduce(trace, maps):
    """-> {'program', 'coverage', 'per_step_ms': {scope: ms}}: each scope on
    the chip where it took longest, the union of the leaf ops of the whole
    traced steps under it, per step. None where the trace holds no whole
    step or no map names any of its ops."""
    chips = []
    for _, dev in sorted(trace['devices'].items()):
        lo, hi, steps = trace_mod.steady_window(dev)
        if not steps:
            continue
        ops = trace_mod.leaves(trace_mod.clip(dev['ops'], lo, hi))
        label, scope_map, coverage = phases.pick_map(ops, maps)
        if label is None:
            continue
        by_scope = {}
        for name, s, e in ops:
            for scope in scope_map.get(trace_mod.op_head(name), ()):
                by_scope.setdefault(scope, []).append((s, e))
        chips.append({'program': label, 'coverage': coverage, 'per_step_ms': {
            scope: trace_mod.length(trace_mod.union(iv)) / 1e6 / steps
            for scope, iv in by_scope.items()}})
    if not chips:
        return None
    out = dict(chips[0])
    out['per_step_ms'] = {
        scope: max(c['per_step_ms'].get(scope, 0.0) for c in chips)
        for scope in sorted({s for c in chips for s in c['per_step_ms']})}
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
