"""Persistent compilation cache: AOT-serialized executables across boots.

The warm program tier every process rebuilds at boot — Executor program-cache
entries, the serving runners' per-bucket warmup sets, Predictor exports —
dies with the process; a fleet relaunch re-pays the whole compile storm on
the recovery path. This module adds the missing durable tier: compiled XLA
executables serialized with ``jax.experimental.serialize_executable`` and
committed under a CRC manifest with ``resilience.atomic_io`` (the PR 14
checkpoint commit protocol), so a **second boot compiles zero programs** —
deserializing an executable skips tracing AND backend compilation, which is
exactly what the ``jax.compiles`` counter certifies.

Layout of a cache/artifact directory::

    <dir>/manifest.json      {"version": 1, "entries": {key: {...}}}
    <dir>/<key>.exe          pickled serialize_executable payload

Every entry is keyed by ``sha1(label + input shapes/dtypes + sharding tag +
backend + jax version + device count)`` — the labels are the cost-ledger
program labels (``executor.p<fp>[...]``, ``serving.<model>.prefill<b>``,
...), so the cost ledger doubles as the cache inventory. The manifest
records the producing jax/backend/device-count and a CRC32 per entry;
*any* load-side disagreement (version skew, torn bytes, deserialize error)
is counted as ``compilecache.incompat`` and falls back to live
compilation — a poisoned cache can cost a compile, never a request.

Surfaces:

- ``enable(dir)`` / ``disable()`` / ``active()`` / ``use(dir)`` — process
  cache binding; the ``PADDLE_TPU_COMPILE_CACHE`` env var binds it at
  first use without a code change.
- ``CachedJit`` — the jit-shaped waist the serving runners and the
  Predictor compile through: ``warm(label, *args)`` loads-or-compiles the
  executable for that exact shape set and installs it for ``__call__``.
- ``fetch_or_compile(label, jitted, args)`` — the raw hook the Executor's
  program cache uses behind its in-memory tier.
- counters ``compilecache.hits/misses/bypass/incompat`` (+ always-on
  ``stats()`` tallies so tests and the bench can assert ``hit_rate``
  without telemetry), ``compilecache.load/store/incompat`` events, and
  ``compilecache.entries/bytes`` gauges.

The CLI view (list/verify/gc) is ``tools/compilecache.py`` — stdlib-only,
it reads the manifest directly.
"""
import contextlib
import hashlib
import json
import os
import pickle
import threading

from .. import observability as _obs
from ..resilience.atomic_io import atomic_write, crc32_bytes, crc32_file

__all__ = ['CompileCache', 'CachedJit', 'enable', 'disable', 'active',
           'use', 'cache_dir', 'fetch_or_compile', 'note_bypass',
           'note_incompat', 'signature', 'make_key', 'stats', 'hit_rate',
           'reset_stats', 'executable_device_count', 'ENV_VAR',
           'MANIFEST_NAME', 'ENTRY_SUFFIX']

ENV_VAR = 'PADDLE_TPU_COMPILE_CACHE'
MANIFEST_NAME = 'manifest.json'
ENTRY_SUFFIX = '.exe'
MANIFEST_VERSION = 1

# always-on tallies (telemetry mirrors them when enabled): tests and the
# cold-start bench assert hit_rate in processes that never enable telemetry
_tally_lock = threading.Lock()
_tally = {'hits': 0, 'misses': 0, 'bypass': 0, 'incompat': 0, 'stores': 0}


def _note(kind, label, reason=None):
    with _tally_lock:
        _tally[kind] = _tally.get(kind, 0) + 1
    if _obs.enabled():
        _obs.counter('compilecache.%s' % kind).inc()
        ev = {'hits': 'compilecache.load', 'stores': 'compilecache.store'}
        payload = {'label': str(label)}
        if reason:
            payload['reason'] = reason
        _obs.event(ev.get(kind, 'compilecache.%s' % kind), **payload)


def stats():
    """Snapshot of the process tallies (+ derived hit rate)."""
    with _tally_lock:
        out = dict(_tally)
    out['hit_rate'] = hit_rate(out)
    return out


def hit_rate(snapshot=None):
    """hits / (hits + misses + incompat): the fraction of persistent-tier
    lookups that produced a ready executable. 0.0 before any lookup."""
    if snapshot is None:
        with _tally_lock:
            snapshot = dict(_tally)
    lookups = (snapshot['hits'] + snapshot['misses']
               + snapshot['incompat'])
    return round(snapshot['hits'] / lookups, 4) if lookups else 0.0


def reset_stats():
    with _tally_lock:
        for k in _tally:
            _tally[k] = 0


def signature(args):
    """Closed-world shape/dtype signature of a call's flattened pytree
    leaves — the per-program half of the cache key (the serving shape
    sets and Executor feed signatures are closed, so exact match is the
    contract, not a limitation)."""
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_leaves(args)
    parts = []
    for leaf in leaves:
        shape = 'x'.join(str(d) for d in np.shape(leaf)) or '()'
        dtype = getattr(leaf, 'dtype', None)
        parts.append('%s:%s' % (shape, dtype if dtype is not None
                                else np.asarray(leaf).dtype))
    return '|'.join(parts)


def _backend_tag():
    import jax
    return (jax.default_backend(), jax.__version__, len(jax.devices()))


def executable_device_count(compiled):
    """Devices the executable itself spans (from its input and output
    shardings) — NOT the process's device count: a one-device program
    compiled on a four-chip host must reload as a one-device program."""
    import jax
    shardings = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings))
    devices = set()
    for s in shardings:
        devices |= s.device_set
    return len(devices) or 1


def make_key(label, sig, sharding=''):
    """Content key for one executable: program label + input signature +
    sharding tag + backend identity. Stable across processes; any
    component changing (new jax, different topology, resharded config)
    keys a different entry instead of poisoning an old one."""
    backend, jax_version, n_devices = _backend_tag()
    raw = '\x1f'.join((str(label), sig, str(sharding), backend,
                       jax_version, str(n_devices)))
    return hashlib.sha1(raw.encode()).hexdigest()


class CompileCache:
    """One on-disk executable cache directory (see module docstring).

    Concurrent writers are safe-by-construction rather than coordinated:
    entry files are content-keyed and committed atomically, and the
    manifest is rewritten atomically — a lost race drops a manifest row
    (a future miss), never a torn file.
    """

    def __init__(self, root):
        self.root = os.fspath(root)
        self._lock = threading.Lock()
        self._manifest = None          # lazy; re-read per boot, not per hit

    # -- manifest -------------------------------------------------------
    @property
    def manifest_path(self):
        return os.path.join(self.root, MANIFEST_NAME)

    def _read_manifest(self):
        try:
            with open(self.manifest_path, 'rb') as f:
                doc = json.loads(f.read().decode('utf-8'))
            entries = doc.get('entries', {})
            return entries if isinstance(entries, dict) else {}
        except FileNotFoundError:
            return {}
        except Exception:
            # a torn/corrupt manifest disables the hit path, never a boot
            _note('incompat', MANIFEST_NAME, reason='manifest_unreadable')
            return {}

    def entries(self):
        """{key: entry} view of the manifest (read-through cached)."""
        with self._lock:
            if self._manifest is None:
                self._manifest = self._read_manifest()
            return dict(self._manifest)

    def total_bytes(self):
        return sum(int(e.get('bytes', 0)) for e in self.entries().values())

    def _commit_manifest(self, entries):
        doc = {'version': MANIFEST_VERSION, 'entries': entries}
        atomic_write(self.manifest_path,
                     json.dumps(doc, indent=1, sort_keys=True).encode())
        self._manifest = entries

    # -- load side ------------------------------------------------------
    def fetch(self, key, label):
        """Deserialize the executable under ``key``, or None. Every
        failure mode — absent, version-skewed, torn, undeserializable —
        is a counted fallback to live compilation, never an exception."""
        entries = self.entries()
        if _obs.enabled():
            # inventory gauge on the LOAD side too: the doctor's
            # cold_compile_storm detector distinguishes "missing against
            # a populated dir" from the first populate pass with it
            _obs.gauge('compilecache.entries').set(len(entries))
        ent = entries.get(key)
        if ent is None:
            _note('misses', label)
            return None
        import jax
        backend, jax_version, n_devices = _backend_tag()
        if ent.get('jax') != jax_version or ent.get('backend') != backend:
            _note('incompat', label, reason='version_skew')
            return None
        if int(ent.get('n_devices', 1)) > n_devices:
            _note('incompat', label, reason='topology')
            return None
        path = os.path.join(self.root, ent.get('file', ''))
        try:
            if crc32_file(path) != int(ent.get('crc32', -1)):
                _note('incompat', label, reason='crc_mismatch')
                return None
            with open(path, 'rb') as f:
                blob = pickle.load(f)
            serialized, in_tree, out_tree = blob['payload']
            from jax.experimental import serialize_executable as se
            # onto exactly the executable's own device count: the default
            # maps onto every local device and then rejects the args
            compiled = se.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=jax.devices()[
                    :int(ent.get('n_devices', 1))])
        except Exception as e:
            _note('incompat', label, reason=repr(e)[:200])
            return None
        _note('hits', label)
        try:
            os.utime(path)             # LRU clock for tools/compilecache.py
        except OSError:
            pass
        return compiled

    # -- store side -----------------------------------------------------
    def store(self, key, compiled, label, sig='', kind='jit'):
        """Serialize + commit one executable under the CRC manifest.
        Best-effort: a cache that cannot be written must never fail the
        program it would have cached."""
        try:
            from jax.experimental import serialize_executable as se
            payload = se.serialize(compiled)
            blob = pickle.dumps({'payload': payload}, protocol=4)
        except Exception as e:
            _note('bypass', label, reason='unserializable: %r' % (e,))
            return False
        backend, jax_version, _ = _backend_tag()
        n_devices = executable_device_count(compiled)
        fname = key + ENTRY_SUFFIX
        try:
            atomic_write(os.path.join(self.root, fname), blob)
            with self._lock:
                entries = self._read_manifest()
                entries[key] = {
                    'label': str(label), 'file': fname, 'sig': sig,
                    'kind': str(kind), 'bytes': len(blob),
                    'crc32': crc32_bytes(blob), 'jax': jax_version,
                    'backend': backend, 'n_devices': n_devices,
                    'created': round(_obs.wall_ts(), 3),
                }
                self._commit_manifest(entries)
        except Exception as e:
            _note('bypass', label, reason='store_failed: %r' % (e,))
            return False
        _note('stores', label)
        if _obs.enabled():
            _obs.gauge('compilecache.entries').set(len(self._manifest))
            _obs.gauge('compilecache.bytes').set(self.total_bytes())
        return True


# -- process binding --------------------------------------------------------

_state_lock = threading.Lock()
_active = None
_env_checked = False


def enable(root):
    """Bind the process persistent compile tier to ``root`` (created on
    first store). Returns the ``CompileCache``."""
    global _active, _env_checked
    with _state_lock:
        _active = CompileCache(root)
        _env_checked = True
        return _active


def disable():
    """Unbind (and stop consulting ``PADDLE_TPU_COMPILE_CACHE``)."""
    global _active, _env_checked
    with _state_lock:
        _active = None
        _env_checked = True


def active():
    """The bound ``CompileCache`` or None. The env knob is consulted once,
    lazily, so processes opt in without a code change."""
    global _active, _env_checked
    with _state_lock:
        if not _env_checked:
            _env_checked = True
            root = os.environ.get(ENV_VAR, '').strip()
            if root:
                _active = CompileCache(root)
        return _active


def cache_dir():
    cc = active()
    return cc.root if cc is not None else None


@contextlib.contextmanager
def use(root):
    """Scope the bound cache to ``root`` (None = leave the binding alone):
    the artifact-dir plumbing for serving registration, fleet relaunch and
    the train→serve handoff."""
    if root is None:
        yield active()
        return
    global _active, _env_checked
    with _state_lock:
        prev, prev_checked = _active, _env_checked
        _active = root if isinstance(root, CompileCache) \
            else CompileCache(root)
        _env_checked = True
        cur = _active
    try:
        yield cur
    finally:
        with _state_lock:
            _active, _env_checked = prev, prev_checked


def note_bypass(label, reason=None):
    """Count a compile that deliberately skipped the persistent tier while
    one is bound (donated train steps, sharded feeds)."""
    if active() is not None:
        _note('bypass', label, reason=reason)


def note_incompat(label, reason=None):
    """Count a cache-loaded executable rejected after install (call-time
    failure the manifest checks could not predict)."""
    _note('incompat', label, reason=reason)


# -- the compile waist ------------------------------------------------------

def fetch_or_compile(label, jitted, args, kind='jit', meta=None,
                     sharding='', cache=None):
    """Load-or-build the executable for ``jitted`` at ``args``' shapes.

    Returns ``(compiled, source)`` with source ``'hit'`` (deserialized —
    zero compiles), ``'miss'`` (AOT-compiled once + committed), or
    ``(None, 'off'|'error')``. Either way the program lands in the cost
    ledger under ``label`` (``record_compiled`` — no extra compile), so
    the ledger doubles as the cache inventory.
    """
    cache = cache if cache is not None else active()
    if cache is None:
        return None, 'off'
    sig = signature(args)
    key = make_key(label, sig, sharding)
    compiled = cache.fetch(key, label)
    source = 'hit'
    if compiled is None:
        try:
            compiled = jitted.lower(*args).compile()
        except Exception as e:
            if _obs.enabled():
                _obs.event('compilecache.compile_error', label=str(label),
                           error=repr(e)[:200])
            return None, 'error'
        cache.store(key, compiled, label, sig=sig, kind=kind)
        source = 'miss'
    if _obs.enabled():
        from ..observability import costs as _costs
        _costs.record_compiled(label, compiled, kind=kind,
                               meta=dict(meta or {}, cache=source))
    return compiled, source


class _Installed:
    """One executable slotted into a ``CachedJit``: calls it directly; a
    cache-loaded one that fails at call time (topology drift the manifest
    checks could not see) is evicted and counted, and the call re-runs
    through the live jit — graceful, never fatal."""

    __slots__ = ('compiled', 'from_cache')

    def __init__(self, compiled, from_cache):
        self.compiled = compiled
        self.from_cache = from_cache


class CachedJit:
    """``jax.jit`` with the persistent executable cache behind it.

    ``warm(label, *args)`` is the compile point: a keyed hit deserializes
    (zero compiles), a miss AOT-compiles exactly once and commits; either
    way the executable is installed for ``__call__`` at that signature and
    ledgered under ``label``. With no cache bound, ``warm`` degrades to
    the plain jit call + cost capture (the pre-cache behavior). Steady-
    state calls dispatch the installed executable; unknown signatures fall
    through to the live jit.

    ``auto_label=`` turns on warm-on-first-call: a new signature arriving
    through ``__call__`` while a cache is bound is warmed under
    ``auto_label + '[' + signature + ']'`` (the Predictor's open-shape
    path)."""

    def __init__(self, fn, auto_label=None, kind='jit', meta=None):
        import jax
        self._jit = jax.jit(fn)
        self._auto_label = auto_label
        self._kind = kind
        self._meta = meta
        self._exe = {}                 # signature -> _Installed

    @property
    def jitted(self):
        return self._jit

    def warm(self, label, *args, kind=None, meta=None):
        """Load-or-compile at ``args``' exact shapes, install, run once,
        return the outputs (warmup call sites use them to thread cache
        pytrees through, exactly like the plain jit call did)."""
        kind = kind or self._kind
        meta = meta if meta is not None else self._meta
        compiled, source = fetch_or_compile(label, self._jit, args,
                                            kind=kind, meta=meta)
        if compiled is not None:
            self._exe[signature(args)] = _Installed(compiled,
                                                    source == 'hit')
            return compiled(*args)
        if source == 'off' and _obs.enabled():
            from ..observability import costs as _costs
            out = self._jit(*args)
            _costs.capture(label, self._jit, *args, kind=kind, meta=meta)
            return out
        return self._jit(*args)

    def __call__(self, *args):
        if not self._exe and self._auto_label is None:
            return self._jit(*args)
        sig = signature(args)
        ent = self._exe.get(sig)
        if ent is not None:
            if not ent.from_cache:
                return ent.compiled(*args)
            try:
                return ent.compiled(*args)
            except Exception as e:
                # a manifest-valid executable the runtime still rejects:
                # evict, count, recover through the live jit
                del self._exe[sig]
                _note('incompat', self._auto_label or 'cachedjit',
                      reason='call_failed: %r' % (e,))
                return self._jit(*args)
        if self._auto_label is not None and active() is not None:
            return self.warm('%s[%s]' % (self._auto_label, sig), *args)
        return self._jit(*args)
