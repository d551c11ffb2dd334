"""Cost explorer: compiled-program cost attribution for the whole runtime.

Every program the runtime compiles — Executor program-cache entries, the
unified ``engine.build_train_step`` step, and the serving runners' closed
program sets — is captured ONCE at build/warmup time into a process-wide
**cost ledger** keyed by program label:

- ``flops`` / ``bytes_accessed`` from XLA's ``Compiled.cost_analysis()``;
- ``argument`` / ``output`` / ``temp`` / ``generated_code`` bytes (and
  their sum, ``peak_bytes``) from ``Compiled.memory_analysis()`` — all
  available on CPU, so the numbers are provable without a chip;
- an **analytic roofline** estimate: arithmetic intensity (flops per byte
  accessed) against the device's published peaks (``DEVICE_PEAKS``, keyed
  by ``device_kind``) names whether the program is compute- or
  memory-bound and what its floor step time would be. The estimate is a
  bound, not a measurement; a device that is not in the table has no
  roofline (``device_peaks`` raises, the ledger entry's ``roofline`` is
  None) — see docs/OBSERVABILITY.md, "Cost explorer" for caveats.

An engine train step's entry also keeps the **phase of every instruction**
of its compiled module (``instruction_phases``, ``phases(program)``):
``forward`` / ``backward`` / ``update`` / ``guard`` / ``other`` from the
``jax.named_scope``s ``engine.builder`` traces the step under, and a
**mixed** phase, named by what it holds (``backward+update``), for a fusion
that holds more than one of the first three (XLA fuses the weight-gradient
matmul with the optimizer's update: that is said, never split or guessed).
A device-trace event is named by its instruction, so this map is what puts
``%fusion.808`` under a phase.

Capture is an AOT ``fn.lower(*args).compile()`` under the span
``costs.capture`` — one extra backend compile per program, paid once while
the program is being built/warmed anyway (the engine's train step asks
AFTER its first dispatch, with the arguments' shapes and shardings: jit
then hands back the lowering and the executable that dispatch made, and
nothing is lowered or compiled twice); repeat requests are ledger hits
(``jax.compiles`` flatness gates stay flat after warmup). Everything is
off until telemetry is enabled.

Surfaces: ``cost.flops{program=}`` / ``cost.peak_bytes{program=}`` gauges,
one ``cost.program`` event per capture (what ``tools/telemetry_dump.py
--costs`` tabulates), the ``/costs`` endpoint slice, the per-rank flush
head, and BENCH ``extras.costs``.

Env knobs:

- ``PADDLE_TPU_HBM_BUDGET``            device memory budget in bytes (the
                                       doctor's ``memory_pressure`` detector
                                       compares ledger ``peak_bytes`` to it)

Stdlib-only at import (jax is imported lazily inside ``capture``).
"""
import collections
import os
import re
import threading

from . import events, registry, spans, state

__all__ = ['capture', 'record_compiled', 'mark_hit', 'ledger', 'entry',
           'summary', 'reset', 'DEVICE_PEAKS', 'device_peaks', 'roofline',
           'hbm_budget', 'instruction_phases', 'phase_of_op_name', 'phases',
           'register_scopes', 'instruction_scopes', 'scopes']

_lock = threading.Lock()
_ledger = {}         # program label -> entry dict
_phases = {}         # program label -> {instruction name: phase}
_scopes = {}         # program label -> {instruction name: layer scopes}


# published peak (bf16 FLOP/s, HBM bytes/s) of one chip, keyed by
# ``jax.devices()[0].device_kind``. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM) — on-chip-measurement guide §4.
# A device that is not here is an error, not a default.
DEVICE_PEAKS = {
    'TPU v5 lite': (197e12, 819e9),
}


def device_peaks(device_kind=None):
    """(peak_flops_per_s, peak_bytes_per_s) of ``device_kind`` (default:
    this process's first device). Raises ``KeyError`` for a kind that is
    not in ``DEVICE_PEAKS``."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            "no published peaks for device kind %r in "
            "observability.costs.DEVICE_PEAKS (have %s): a roofline against "
            "another device's peaks is not a roofline"
            % (device_kind, sorted(DEVICE_PEAKS)))
    return DEVICE_PEAKS[device_kind]


def hbm_budget():
    """Device-memory budget in bytes for memory-pressure accounting:
    ``PADDLE_TPU_HBM_BUDGET`` when set, else the device's reported limit
    (TPU/GPU ``memory_stats``; CPU reports none), else None."""
    raw = os.environ.get('PADDLE_TPU_HBM_BUDGET', '')
    if raw:
        try:
            return int(float(raw))
        except ValueError:
            pass
    import jax
    limit = (jax.devices()[0].memory_stats() or {}).get('bytes_limit')
    return int(limit) if limit else None


def roofline(flops, bytes_accessed, device_kind=None):
    """Analytic roofline for one program: arithmetic intensity vs the
    device ridge point -> binding resource + floor time estimate. Raises
    ``KeyError`` for a device without published peaks."""
    peak_flops, peak_bps = device_peaks(device_kind)
    ai = (flops / bytes_accessed) if bytes_accessed else 0.0
    ridge = peak_flops / peak_bps
    est_s = max(flops / peak_flops if peak_flops else 0.0,
                bytes_accessed / peak_bps if peak_bps else 0.0)
    return {
        'arithmetic_intensity': round(ai, 4),
        'ridge': round(ridge, 4),
        'bound': 'compute' if ai >= ridge else 'memory',
        'est_ms': round(est_s * 1e3, 6),
        'peak_flops': peak_flops,
        'peak_bytes_per_s': peak_bps,
    }


def _cost_scalars(cost):
    """flops / bytes accessed from a ``cost_analysis()`` result (a dict in
    newer jax, a one-element list of dicts in older)."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return 0.0, 0.0
    return (float(cost.get('flops', 0.0) or 0.0),
            float(cost.get('bytes accessed', 0.0) or 0.0))


def _memory_scalars(mem):
    """argument/output/temp/generated-code bytes from ``memory_analysis()``
    (a CompiledMemoryStats-like object; absent fields read 0)."""
    def grab(attr):
        try:
            return int(getattr(mem, attr, 0) or 0)
        except (TypeError, ValueError):
            return 0
    return {
        'argument_bytes': grab('argument_size_in_bytes'),
        'output_bytes': grab('output_size_in_bytes'),
        'temp_bytes': grab('temp_size_in_bytes'),
        'alias_bytes': grab('alias_size_in_bytes'),
        'generated_code_bytes': grab('generated_code_size_in_bytes'),
    }


# -- phases of a compiled module's instructions ------------------------------

# more than one of these in an instruction makes it mixed: `backward+update`
_MAIN = ('forward', 'backward', 'update')

_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$')
_INSTRUCTION = re.compile(r'^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r'\b(?:calls|to_apply)=%?([\w.\-]+)')
# a path component that is the `forward` scope, bare or under transforms:
# `forward`, `jvp(forward)`, `transpose(jvp(forward))`, `vmap(jvp(forward))`
_FORWARD = re.compile(r'^(?:\w+\()*forward\)*$')


def phase_of_op_name(op_name):
    """The phase an instruction's ``op_name`` (its jax name stack) puts it
    under. ``engine.builder`` traces the differentiated loss under
    ``named_scope('forward')``: jax names the forward pass's instructions
    ``.../jvp(forward)/...`` and its transpose's — the backward —
    ``.../transpose(jvp(forward))/...``."""
    for part in op_name.split('/'):
        if _FORWARD.match(part):
            return 'backward' if 'transpose(' in part else 'forward'
        if part in ('update', 'guard'):
            return part
    return 'other'


def _instructions(hlo_text):
    """(own, members) of a compiled module's text: instruction ->
    (its ``op_name`` or None, the computations it calls), computation -> its
    instructions."""
    own = {}
    members = collections.defaultdict(list)
    current = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                current = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = (op.group(1) if op else None, _CALLED.findall(line))
        members[current].append(name)
    return own, members


def _held(own, members, of):
    """instruction -> the union of ``of(op_name)`` (a set) over its own
    ``op_name`` and those of ALL instructions of the computations it calls
    (a fusion's fused computation, a reduce's reducer)."""
    closed = {}                     # computation -> what is inside it

    def inside(computation):
        if computation not in closed:
            closed[computation] = set().union(
                *(held(name) for name in members.get(computation, ())))
        return closed[computation]

    def held(name):             # (HLO computations do not recurse)
        op_name, calls = own[name]
        return (of(op_name) if op_name else set()).union(
            *(inside(computation) for computation in calls))

    return {name: held(name) for name in own}


def instruction_phases(hlo_text):
    """``{instruction name: phase}`` for every instruction of a compiled
    module's text (``Compiled.as_text()``). An instruction's phase is that
    of its own ``op_name`` together with those of ALL instructions of the
    computations it calls (a fusion's fused computation, a reduce's
    reducer): one of ``forward`` / ``backward`` / ``update`` holds it alone,
    or it is mixed and named by what it holds, in that order
    (``backward+update``, ``forward+backward``: a ``+`` marks it); with none
    of the three, ``guard`` if any instruction is the guard's, else
    ``other``."""
    own, members = _instructions(hlo_text)
    out = {}
    for name, found in _held(
            own, members, lambda op: {phase_of_op_name(op)}).items():
        main = [p for p in _MAIN if p in found]
        if main:
            out[name] = '+'.join(main)
        else:
            out[name] = 'guard' if 'guard' in found else 'other'
    return out


# -- layer scopes of a compiled module's instructions --------------------------

_scope_names = set()    # the named scopes whose instructions are kept apart


def register_scopes(*names):
    """Name the ``jax.named_scope``s of a layer whose device time is read on
    its own (``kda.scan``, ``moe.experts``): a step captured after this keeps,
    for each of its instructions, which of them it lies under
    (``scopes(program)``). A layer's module registers its scopes as it is
    imported."""
    with _lock:
        _scope_names.update(names)


def instruction_scopes(hlo_text, names=None):
    """``{instruction name: sorted tuple of the scopes it lies under}`` for
    the instructions of a compiled module's text that lie under any of
    ``names`` (default: the registered ones): a scope is a component of the
    instruction's ``op_name`` path, its own or that of an instruction of a
    computation it calls (a fusion that mixes two layers lies under both)."""
    with _lock:
        wanted = frozenset(_scope_names if names is None else names)
    own, members = _instructions(hlo_text)
    found = _held(own, members, lambda op: wanted.intersection(op.split('/')))
    return {name: tuple(sorted(f)) for name, f in found.items() if f}


def scopes(program):
    """The ``{instruction name: scopes}`` map kept for ``program`` (an engine
    train step captured with ``phases=True``), or None."""
    with _lock:
        return _scopes.get(program)


def phases(program):
    """The ``{instruction name: phase}`` map kept for ``program`` (an
    engine train step captured with ``phases=True``), or None."""
    with _lock:
        return _phases.get(program)


def capture(program, fn, *args, kind='jit', meta=None, phases=False):
    """AOT-lower+compile ``fn`` at ``args``' shapes and ledger the result
    under ``program``. Returns the (possibly pre-existing) entry, or None
    when telemetry is off or the capture failed — a failed capture must
    never fail the program it describes. Idempotent per label: a second
    call is a ledger **hit** (no recompile), so cost numbers are stable
    across program-cache hits. ``phases=True`` also keeps the phase of
    every instruction of the compiled module (``phases(program)``)."""
    if not state.enabled():
        return None
    with _lock:
        ent = _ledger.get(program)
    if ent is not None:
        mark_hit(program)
        return ent
    with spans.span('costs.capture', program=str(program)):
        try:
            compiled = fn.lower(*args).compile()
        except Exception as e:
            events.emit('cost.capture_error', program=str(program),
                        error=repr(e))
            return None
        return record_compiled(program, compiled, kind=kind, meta=meta,
                               phases=phases)


def record_compiled(program, compiled, kind='jit', meta=None, phases=False):
    """Ledger an already-compiled ``jax.stages.Compiled`` (the AOT-export
    path, or a capture that happened elsewhere)."""
    if not state.enabled():
        return None
    if phases:
        try:
            text = compiled.as_text()
            found = instruction_phases(text)
            under = instruction_scopes(text)
        except Exception as e:
            events.emit('cost.capture_error', program=str(program),
                        error=repr(e))
        else:
            with _lock:
                _phases[program] = found
                _scopes[program] = under
            meta = dict(meta or {}, phase_instructions=dict(
                collections.Counter(found.values())))
    try:
        flops, bytes_accessed = _cost_scalars(compiled.cost_analysis())
    except Exception:
        flops = bytes_accessed = 0.0
    mem = {}
    try:
        mem = _memory_scalars(compiled.memory_analysis())
    except Exception:
        pass
    return record_costs(program, flops, bytes_accessed, mem,
                        kind=kind, meta=meta)


def record_costs(program, flops, bytes_accessed, mem=None, kind='jit',
                 meta=None):
    """Ledger raw numbers (the seam record_compiled/capture feed; also lets
    tests and external analyzers inject entries)."""
    if not state.enabled():
        return None
    mem = dict(mem or {})
    peak = (mem.get('argument_bytes', 0) + mem.get('output_bytes', 0) +
            mem.get('temp_bytes', 0) + mem.get('generated_code_bytes', 0))
    entry = {
        'program': str(program),
        'kind': str(kind),
        'flops': float(flops),
        'bytes_accessed': float(bytes_accessed),
        'peak_bytes': int(peak),
        'captured_ts': round(events.wall_ts(), 6),
        'hits': 0,
    }
    entry.update(mem)
    import jax
    device_kind = jax.devices()[0].device_kind
    # flops/bytes are recorded on any device; the roofline only where the
    # device's peaks are published
    roof = (roofline(entry['flops'], entry['bytes_accessed'], device_kind)
            if device_kind in DEVICE_PEAKS else None)
    entry['roofline'] = roof
    if meta:
        entry['meta'] = dict(meta)
    with _lock:
        fresh = program not in _ledger
        _ledger[program] = entry
    lbl = {'program': str(program)}
    registry.gauge('cost.flops', labels=lbl).set(entry['flops'])
    registry.gauge('cost.bytes_accessed', labels=lbl).set(
        entry['bytes_accessed'])
    registry.gauge('cost.peak_bytes', labels=lbl).set(entry['peak_bytes'])
    registry.counter('cost.captures').inc()
    if fresh:
        registry.counter('cost.programs').inc()
    events.emit('cost.program', program=entry['program'],
                program_kind=entry['kind'],
                flops=entry['flops'], bytes_accessed=entry['bytes_accessed'],
                peak_bytes=entry['peak_bytes'],
                argument_bytes=entry.get('argument_bytes', 0),
                output_bytes=entry.get('output_bytes', 0),
                temp_bytes=entry.get('temp_bytes', 0),
                **({'arithmetic_intensity': roof['arithmetic_intensity'],
                    'bound': roof['bound'], 'est_ms': roof['est_ms']}
                   if roof else {}))
    return entry


def mark_hit(program):
    """Count one reuse of a ledgered program (a program-cache hit)."""
    with _lock:
        ent = _ledger.get(program)
        if ent is not None:
            ent['hits'] += 1
    if state.enabled():
        registry.counter('cost.hits').inc()
    return ent


def entry(program):
    with _lock:
        ent = _ledger.get(program)
    return dict(ent) if ent is not None else None


def ledger():
    """Snapshot of every entry, sorted by descending flops."""
    with _lock:
        entries = [dict(e) for e in _ledger.values()]
    entries.sort(key=lambda e: (-e['flops'], e['program']))
    return entries


def summary():
    """Headline ledger aggregates (BENCH ``extras.costs``, flight dumps,
    the flush head)."""
    entries = ledger()
    peak_prog = max(entries, key=lambda e: e['peak_bytes'], default=None)
    by_kind = {}
    for e in entries:
        by_kind[e['kind']] = by_kind.get(e['kind'], 0) + 1
    out = {
        'programs': len(entries),
        'total_flops': round(sum(e['flops'] for e in entries), 1),
        'total_bytes_accessed': round(
            sum(e['bytes_accessed'] for e in entries), 1),
        'max_peak_bytes': peak_prog['peak_bytes'] if peak_prog else 0,
        'max_peak_program': peak_prog['program'] if peak_prog else None,
        'hits': sum(e['hits'] for e in entries),
        'by_kind': by_kind,
    }
    budget = hbm_budget()
    if budget:
        out['hbm_budget'] = budget
        out['peak_budget_ratio'] = round(
            out['max_peak_bytes'] / budget, 4)
    return out


def reset():
    with _lock:
        _ledger.clear()
        _phases.clear()
        _scopes.clear()
