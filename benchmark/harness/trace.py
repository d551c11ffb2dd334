"""From a profiler trace to the device's numbers.

`read_xplane(path)` turns the `.xplane.pb` JAX's profiler writes into plain
lists (needs nothing but `jax.profiler.ProfileData`); `reduce(trace, ...)`
turns those into busy and idle time, the time under a named scope, the
exposed time of collectives, the heaviest operations and the idle gaps by
what the host was doing. The reduction works on the plain lists, so the
test beside it runs on a small recorded trace without a chip.

What a TPU v5e trace holds (looked at by hand, PERF.md section 3): one plane
per chip, `/device:TPU:<n>`; its line `XLA Modules` has one event per
execution of a compiled program (`jit_step(<id>)`), its line `XLA Ops` one
event per HLO operation (12,700 a step of BERT-large), named by the
instruction's whole text, with no statistic but its device time. The host's
plane `/host:CPU` carries the benchmark's `TraceAnnotation` spans, on the
line of the thread that made them, on the same clock.
"""
import collections
import json
import re
import sys

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
# (`async-collective-start` / `-done`: the fusions that issue and await a
# sharded parameter's use-time gather, named so by the compiler)
COLLECTIVE = re.compile(
    r'^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|'
    r'collective-broadcast|async-collective-(start|done))')
# a collective the compiler runs as a FUSION on the compute stream, named
# like any other (`%fusion.14 = f32[30522,256] fusion(f32[30522,1024]),
# kind=kCustom, calls=%all-reduce-scatter.2`): only its called computation
# says what it is. A sharded step's gradient reduction is 86 of them, ~10.9
# ms a step of the four-chip cell, which the instruction's NAME never showed
# (PERF.md, Findings PR 45 and PR 47)
FUSED_COLLECTIVE = re.compile(
    r'\bcalls=%?(all-gather|all-reduce|reduce-scatter|all-to-all|'
    r'collective-permute|collective-broadcast)')


def read_xplane(path, span_names=()):
    """-> {'devices': {chip: {'ops': [...], 'modules': [...]}},
            'host': [(name, start_ns, end_ns)]}.
    An op is (name, start_ns, end_ns). Its name is the HLO instruction's
    whole text, and the instruction is named after the jax scope it was
    traced under: the forward flash kernel is `%jvp_flash_attention.pallas_.42
    = ... custom-call(...)`, its backward `%transpose_jvp_flash_attention.
    pallas__.49 = ...`, so a scope is looked for in the name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    wanted = set(span_names)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {'ops': [], 'modules': []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        dev['ops'].append(
                            (ev.name, int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev['modules'].append(
                            (ev.name, int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)))
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    return {'devices': devices, 'host': sorted(host, key=lambda e: e[1])}


# ------------------------------------------------------------- intervals

def union(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(disjoint):
    return sum(e - s for s, e in disjoint)


def subtract(a, b):
    """a minus b, both sorted disjoint unions."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(ops, lo, hi):
    """Ops cut to [lo, hi]; those outside are dropped."""
    out = []
    for op in ops:
        s, e = max(op[1], lo), min(op[2], hi)
        if e > s:
            out.append((op[0], s, e))
    return out


def leaves(ops):
    """The ops that enclose no other op (control-flow parents go)."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, op in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < op[2] and nxt[2] <= op[2] \
                and (nxt[1], nxt[2]) != (op[1], op[2]):
            continue
        out.append(op)
    return out


def steady_window(dev):
    """(lo, hi, steps): the span of the whole executions of the program that
    takes most of the device's time, and how many there are (the first and
    the last in the trace left out: the trace began and ended inside them).
    Falls back to the span of all ops where the trace has no module line."""
    by_name = collections.defaultdict(list)
    for name, s, e in dev['modules']:
        by_name[re.sub(r'\(\d+\)$', '', name)].append((s, e))
    if by_name:
        runs = max(by_name.values(), key=lambda v: sum(e - s for s, e in v))
        runs.sort()
        if len(runs) >= 3:      # the trace may have cut the two at its ends
            runs = runs[1:-1]
        return runs[0][0], runs[-1][1], len(runs)
    if not dev['ops']:
        return 0, 0, 0
    return (min(o[1] for o in dev['ops']), max(o[2] for o in dev['ops']), 0)


def op_head(name):
    """The instruction's own name: `%all-gather.3 = ...` -> `all-gather.3`."""
    return name.split(' = ', 1)[0].lstrip('%')


def op_family(name):
    """One row per kind of HLO instruction. A TPU op event is named by the
    instruction's whole text, `%fusion.123 = bf16[...] fusion(...),
    kind=kOutput, calls=...`: -> `fusion kOutput`. A plain name loses its
    trailing number: `fusion.123` -> `fusion`."""
    head = op_head(name)
    head = re.sub(r'[.\d]+$', '', head) or head
    kind = re.search(r'\bkind=(\w+)', name)
    return '%s %s' % (head, kind.group(1)) if kind else head


def is_collective(name):
    """Whether an op event is a collective: by its instruction's own name,
    or by the computation a fusion calls."""
    return bool(COLLECTIVE.match(op_head(name))
                or FUSED_COLLECTIVE.search(name))


def reduce(trace, scopes=()):
    """Per chip: window, busy time, time under each scope, exposed time of
    collectives, operations by time, idle gaps by host span."""
    out = {}
    for chip, dev in sorted(trace['devices'].items()):
        lo, hi, steps = steady_window(dev)
        ops = leaves(clip(dev['ops'], lo, hi))
        busy = union((o[1], o[2]) for o in ops)
        coll = union((o[1], o[2]) for o in ops if is_collective(o[0]))
        compute = union((o[1], o[2]) for o in ops if not is_collective(o[0]))
        by_scope = {}
        for scope in scopes:
            hit = [o for o in ops if scope in op_head(o[0])]
            by_scope[scope] = {
                'seconds': length(union((o[1], o[2]) for o in hit)) / 1e9,
                'events': len(hit)}
        heavy = collections.Counter()
        for o in ops:
            label = op_family(o[0])
            for scope in scopes:
                if scope in op_head(o[0]):
                    label = scope
            heavy[label] += o[2] - o[1]
        gaps = subtract([[lo, hi]], busy) if hi > lo else []
        by_span = collections.Counter()
        host = trace['host']
        for s, e in gaps:
            best, best_overlap = 'no benchmark span', 0
            for name, hs, he in host:
                if he <= s:
                    continue
                if hs >= e:
                    break
                overlap = min(e, he) - max(s, hs)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
            by_span[best] += e - s
        out[chip] = {
            'window_s': (hi - lo) / 1e9, 'steps': steps,
            'busy_s': length(busy) / 1e9,
            'collective_s': length(coll) / 1e9,
            'exposed_collective_s': length(subtract(coll, compute)) / 1e9,
            'scopes': by_scope,
            'device_ops': [[k, v / 1e9] for k, v in heavy.most_common(10)],
            'idle_gaps': [[k, v / 1e9] for k, v in by_span.most_common(10)],
            'longest_gap_s': max([e - s for s, e in gaps], default=0) / 1e9,
        }
    return out


def export(path, out, steps=2):
    """A small recording of a trace for the test beside this file: the
    first `steps` whole steps of chip 0, op names cut to 160 characters."""
    trace = read_xplane(path, span_names=('input.wait', 'step.key',
                                          'step.dispatch'))
    dev = trace['devices'][min(trace['devices'])]
    by_name = collections.defaultdict(list)
    for name, s, e in dev['modules']:
        by_name[re.sub(r'\(\d+\)$', '', name)].append((name, s, e))
    runs = sorted(max(by_name.values(),
                      key=lambda v: sum(e - s for _, s, e in v)),
                  key=lambda m: m[1])[:steps]
    lo, hi = runs[0][1], runs[-1][2]
    small = {'devices': {'0': {
        'modules': [list(m) for m in dev['modules'] if lo <= m[1] and m[2] <= hi],
        'ops': [[o[0][:160], o[1], o[2]]
                for o in dev['ops'] if lo <= o[1] and o[2] <= hi]}},
        'host': [list(h) for h in trace['host'] if h[2] >= lo and h[1] <= hi]}
    with open(out, 'w') as f:
        json.dump(small, f, separators=(',', ':'))


if __name__ == '__main__':
    export(sys.argv[1], sys.argv[2])
