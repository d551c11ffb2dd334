"""Set-up, read from inside the program: the span records it keeps of what
happens before the first timed step.

The program writes one record for its own import (`paddle_tpu.import`), one
around each piece of set-up it owns (`engine.build`, `engine.init_state`
with `engine.place_state` inside it, `engine.dispatch` with `first` on a
step's first call, `costs.capture`) and one for every phase of JAX's compile
path that JAX reports (`jax.trace`, `jax.lower`, `jax.backend` with
`cache: 'hit' | 'miss'` and a `jax.cache_load` child), each with the span
that caused it as its `parent`. All are stamped on `time.perf_counter_ns`,
the clock of the benchmark's own spans, so "before the window" is a
comparison with the start of the window's first span.

Traces nest (a `jit` traced inside the step's trace has a record of its
own), so a phase is the UNION of its records' intervals, never their sum.
What lies under a `costs.capture` exists only in traced runs and is left
out of the phases. What the benchmark does itself during set-up (the pool,
the seeded weights, the first gradient's read-back, its waits for the
checked steps) has no record: the `setup_spans` line lists it as the time
`outside` the program's spans, and no metric claims it.

A program that keeps none of these records (an older commit) gives nothing
to read: every metric is None and no line is printed. A ring that has
dropped records cannot say what set-up held: that raises.
"""
import collections
import json
import re
import sys

from harness import program
from harness.trace import length, subtract, union

PHASES = ('jax.trace', 'jax.lower', 'jax.backend')
LISTED = 12         # programs in the line's table; the rest are summed
SHORT_NS = 1e6      # a top-level span shorter than this is only counted
GAP_NS = 5e7        # time outside the program's spans is listed from here


def read(ctx, metric):
    """One of the six `setup.*` metrics, or None where the program keeps
    nothing to read it from."""
    if 'setup_spans' not in ctx:
        ctx['setup_spans'] = _read_once(ctx)
    found = ctx['setup_spans']
    return None if found is None else found['metrics'][metric]


def _read_once(ctx):
    obs, bounds = program.enable(), program.window_ns(ctx)
    if obs is None or bounds is None:
        return None
    dropped = getattr(getattr(obs, 'spans', None), 'dropped', None)
    if dropped is not None and dropped():
        raise RuntimeError(
            "the program's span ring dropped %d records: set-up's are its "
            "oldest, so what is left cannot be read as set-up" % dropped())
    opens = bounds[0]
    before = [ev for ev in obs.trace_events()
              if ev.get('ph') == 'X' and 't0_ns' in ev
              and ev['t1_ns'] <= opens]
    # the process's start is `run.py`'s own stamp (the job is not handed it
    # for its readers): without it the line lacks two of its fields
    started = getattr(sys.modules.get('__main__'), 'T_START', None)
    outside = [r for r in ctx['spans'].records if r[2] <= opens]
    found = reduce(before, opens, outside,
                   None if started is None else int(started * 1e9))
    if found['line'] is not None:
        print(json.dumps({'phase': 'setup_spans', **found['line']},
                         sort_keys=True), flush=True)
    return found


def _iv(records):
    return [(ev['t0_ns'], ev['t1_ns']) for ev in records]


def _union_ns(records):
    return length(union(_iv(records)))


def _dur(ev):
    return ev['t1_ns'] - ev['t0_ns']


def _s(ns):
    return round(ns / 1e9, 4)


def _clip(disjoint, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in disjoint
            if min(e, hi) > max(s, lo)]


class Records:
    """The records by name and by id, and who lies under whom."""

    def __init__(self, records):
        self.all = records
        self.by_id = {ev['span_id']: ev for ev in records
                      if ev.get('span_id') is not None}
        self.named = collections.defaultdict(list)
        for ev in records:
            self.named[ev['name']].append(ev)

    def parent(self, ev):
        return self.by_id.get(ev.get('parent'))

    def ancestors(self, ev):
        seen = set()
        ev = self.parent(ev)
        while ev is not None and ev['span_id'] not in seen:
            seen.add(ev['span_id'])
            yield ev
            ev = self.parent(ev)

    def under(self, ev, name):
        return any(a['name'] == name for a in self.ancestors(ev))

    def phase(self, name):
        """A compile-path phase's records that no `costs.capture` caused."""
        return [ev for ev in self.named[name]
                if not self.under(ev, 'costs.capture')]


def metrics_of(recs):
    def phase_ms(name):
        if not recs.named[name]:
            return None
        return _union_ns(recs.phase(name)) / 1e6

    def state_ms():
        if not recs.named['engine.init_state']:
            return None
        total = 0
        for ev in recs.named['engine.init_state']:
            compiles = [r for r in recs.all if r['name'].startswith('jax.')
                        and any(a is ev for a in recs.ancestors(r))]
            total += length(subtract([[ev['t0_ns'], ev['t1_ns']]],
                                     union(_iv(compiles))))
        return total / 1e6

    imports = recs.named['paddle_tpu.import']
    backends = recs.named['jax.backend']
    return {
        'import_ms': _dur(imports[0]) / 1e6 if imports else None,
        'trace_ms': phase_ms('jax.trace'),
        'lower_ms': phase_ms('jax.lower'),
        'backend_ms': phase_ms('jax.backend'),
        'state_ms': state_ms(),
        'cache_misses': sum((ev.get('args') or {}).get('cache') == 'miss'
                            for ev in backends) if backends else None,
    }


def reduce(records, opens, outside=(), started=None):
    """`records`: the program's `ph: 'X'` records that ended before `opens`
    (ns); `outside`: the benchmark's own (name, start, end) spans before it;
    `started`: the process's start on the same clock, where known ->
    {'metrics': the six values (None where nothing says), 'line': the
    `setup_spans` line's body, or None without a `paddle_tpu.import`}."""
    recs = Records(records)
    imports = recs.named['paddle_tpu.import']
    line = None
    if imports:
        line = line_of(recs, imports[0], opens, outside)
        if started is not None:
            line['before_import_s'] = _s(imports[0]['t0_ns'] - started)
            line['window_t'] = _s(opens - started)
    return {'metrics': metrics_of(recs), 'line': line}


def line_of(recs, imported, opens, outside):
    """The importing thread from the import's start to the window's opening:
    its top-level program spans, the stretches between them, the programs
    JAX lowered and compiled or loaded."""
    begins, thread = imported['t0_ns'], imported.get('tid')
    mine = [ev for ev in recs.all
            if ev.get('tid') == thread and ev['t0_ns'] >= begins]
    top = sorted((ev for ev in mine if recs.parent(ev) is None
                  and not ev['name'].startswith('jax.')),
                 key=lambda ev: ev['t0_ns'])
    children = collections.defaultdict(list)
    for ev in mine:
        if recs.parent(ev) is not None:
            children[ev['parent']].append(ev)

    listed, short = [], collections.defaultdict(lambda: [0, 0])
    for ev in top:
        if _dur(ev) < SHORT_NS:
            short[ev['name']][0] += 1
            short[ev['name']][1] += _dur(ev)
            continue
        kids = collections.defaultdict(list)
        for child in children[ev['span_id']]:
            kids[child['name']].append(child)
        row = {'name': ev['name'], 'start_s': _s(ev['t0_ns'] - opens),
               'dur_s': _s(_dur(ev)),
               'self_s': _s(_dur(ev) - _union_ns(children[ev['span_id']])),
               'children': {name: _s(_union_ns(found))
                            for name, found in sorted(kids.items())}}
        if ev.get('step') is not None:
            row['step'] = ev['step']
        row.update({k: v for k, v in (ev.get('args') or {}).items()
                    if k in ('first', 'bytes', 'sharded', 'program')})
        listed.append(row)

    gaps = gaps_of(recs, top, thread, begins, opens, outside)
    whole = opens - begins
    covered = length(_clip(union(_iv(mine)), begins, opens))
    captures = recs.named['costs.capture']
    own = {name: recs.phase(name) for name in PHASES}
    return {
        'interval_s': _s(whole),
        'named_s': _s(covered),
        'named_share': round(covered / whole, 4),
        'outside_s': _s(sum(g['ns'] for g in gaps)),
        'outside_unnamed_s': _s(sum(g['ns'] - g['jax_ns'] for g in gaps)),
        'spans': listed,
        'short_spans': {name: [n, _s(ns)]
                        for name, (n, ns) in sorted(short.items())},
        'outside': [g['row'] for g in gaps],
        'phases': {name: {'n': len(own[name]),
                          'union_s': _s(_union_ns(own[name]))}
                   for name in PHASES},
        **programs_of(recs),
        'costs_capture': {
            'n': len(captures),
            'total_s': _s(sum(_dur(ev) for ev in captures)),
            'jax_s': _s(_union_ns(
                ev for name in PHASES for ev in recs.named[name]
                if recs.under(ev, 'costs.capture')))},
    }


def gaps_of(recs, top, thread, begins, opens, outside):
    """The stretches of `thread` between its top-level program spans: the
    benchmark's own work, with the `jax.*` records under no span that fell
    in each and the benchmark's own spans that overlap it."""
    loose = union(_iv(ev for name in PHASES + ('jax.cache_load',)
                      for ev in recs.named[name]
                      if ev.get('tid') == thread
                      and recs.parent(ev) is None))
    gaps, reached, after = [], begins, None
    for ahead, start, end in [(ev['name'], ev['t0_ns'], ev['t1_ns'])
                              for ev in top] + [('window', opens, opens)]:
        if start - reached >= GAP_NS:
            held = collections.defaultdict(int)
            for name, s, e in outside:
                held[name] += max(0, min(e, start) - max(s, reached))
            jax_ns = length(_clip(loose, reached, start))
            gaps.append({'ns': start - reached, 'jax_ns': jax_ns, 'row': {
                'after': after, 'before': ahead,
                'start_s': _s(reached - opens), 'dur_s': _s(start - reached),
                'jax_s': _s(jax_ns),
                'benchmark_spans': {n: _s(v) for n, v in sorted(held.items())
                                    if v}}})
        if end > reached:
            reached, after = end, ahead
    return gaps


def programs_of(recs):
    """`jax.lower` and `jax.backend` by `fun_name` (`jit(step)` -> `step`):
    which of a set-up's compiles are the steps and which are eager one-op
    programs, who caused each, what the persistent cache said."""
    programs = collections.defaultdict(collections.Counter)
    causes = collections.defaultdict(set)
    for name in ('jax.lower', 'jax.backend'):
        for ev in recs.named[name]:
            args = ev.get('args') or {}
            fun = re.sub(r'^\w+\((.*)\)$', r'\1', str(args.get('fun_name')))
            programs[fun][name[4:] + '_ns'] += _dur(ev)
            if name == 'jax.backend':
                programs[fun]['n'] += 1
                programs[fun][args.get('cache', 'uncached')] += 1
                cause = recs.parent(ev)
                causes[fun].add(cause['name'] if cause else 'none')
    table = sorted(programs.items(),
                   key=lambda kv: -(kv[1]['lower_ns'] + kv[1]['backend_ns']))

    def row_of(rows):
        total = sum(rows, collections.Counter())
        out = {'n': total['n'], 'lower_s': _s(total['lower_ns']),
               'backend_s': _s(total['backend_ns'])}
        out.update({k: total[k] for k in ('hit', 'miss', 'uncached')
                    if total[k]})
        return out

    return {'programs': [dict(row_of([row]), fun=fun,
                              under=sorted(causes[fun]))
                         for fun, row in table[:LISTED]],
            'other_programs': dict(row_of([r for _, r in table[LISTED:]]),
                                   funs=len(table[LISTED:]))}
