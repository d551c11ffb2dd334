"""Inference engine: AOT-compiled executable caching + Predictor.

Parity: the reference's inference/ stack (AnalysisPredictor + its
serialized program/optimization caches; paddle/fluid/inference/api). On
TPU the expensive artifact is not an optimized subgraph but the XLA
executable, so the cache layer works at that level:

- ``enable_compilation_cache(dir=None)`` — turns on XLA's persistent
  compilation cache (every jit in the process, keyed by HLO fingerprint;
  survives process restarts, the analogue of the reference's
  serialized-program cache directory). ``JAX_COMPILATION_CACHE_DIR``
  places it from outside; unset, it defaults to ``<checkout>/.jax_cache``.
- ``AOTCompiledFunction`` — explicit ahead-of-time lower+compile of one
  function for fixed shapes, serializable to a single file with
  ``jax.experimental.serialize_executable`` (the analogue of shipping a
  compiled inference engine; reloading skips tracing AND compilation).
- ``Predictor`` — save_inference_model dir -> ready-to-run engine with
  feed/fetch names (AnalysisPredictor analogue), jit-cached per feed
  shape, optionally backed by the persistent cache.
- ``load_inference_model(dirname)`` — THE documented load path: one call
  that turns a ``save_inference_model`` directory into a ready
  ``Predictor``. The serving engine (``paddle_tpu.serving``) and direct
  users share it, so an export that loads here is guaranteed to serve.
"""
import os
import pickle
import warnings

import numpy as np
import jax

from ..core.tensor import Tensor

__all__ = ['enable_compilation_cache', 'DEFAULT_CACHE_DIR',
           'AOTCompiledFunction', 'Predictor', 'load_inference_model']


# the fixed default: inside the checkout (ignored by git), never a temporary
# name — the directory is part of the cache key's world, one that moves
# never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')
_CACHE_ENV = 'JAX_COMPILATION_CACHE_DIR'
_env_override_said = [False]


def enable_compilation_cache(cache_dir=None):
    """Turn on JAX's persistent compilation cache; returns its directory.

    THE one place this repo decides where that cache lives. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and no other
    directory is set here (a ``cache_dir`` argument is then left alone, and
    that is said once). Where it is not set, the cache goes to ``cache_dir``
    or, by default, to ``DEFAULT_CACHE_DIR`` inside the checkout.
    """
    placed = os.environ.get(_CACHE_ENV)
    if placed:
        # whoever placed the cache owns its policy too (size limit, entry
        # thresholds: JAX's own env variables) — nothing is set here
        if cache_dir and not _env_override_said[0]:
            _env_override_said[0] = True
            warnings.warn(
                "%s=%s is set: the compile cache stays there; cache_dir=%r "
                "is left alone" % (_CACHE_ENV, placed, os.fspath(cache_dir)))
        return placed
    from jax.experimental.compilation_cache import compilation_cache as _cc
    chosen = os.fspath(cache_dir) if cache_dir else DEFAULT_CACHE_DIR
    os.makedirs(chosen, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', chosen)
    # cache every computation, however small/fast to compile
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    # jax initializes the persistent cache AT MOST ONCE, on the first
    # compile, and importing paddle_tpu jit-compiles helpers before any
    # user code runs: without the reset the cache stays as it was then
    _cc.reset_cache()
    return chosen


def _unwrap(a):
    if isinstance(a, Tensor):
        return a._value
    return a


class AOTCompiledFunction:
    """One function, one set of input shapes, compiled ahead of time.

    ``trace(fn, *example_args)`` lowers + compiles now;
    ``save(path)``/``load(path)`` serialize the compiled executable so a
    serving process runs without tracing or compiling (same
    backend/topology required, as with any native executable).
    """

    def __init__(self, compiled):
        self._compiled = compiled

    @classmethod
    def trace(cls, fn, *example_args):
        vals = tuple(_unwrap(a) for a in example_args)
        lowered = jax.jit(fn).lower(*vals)
        return cls(lowered.compile())

    def __call__(self, *args):
        vals = tuple(_unwrap(a) for a in args)
        # a deserialized executable requires inputs already placed per its
        # compiled shardings (a fresh-traced one commits them itself)
        shardings = getattr(self._compiled, 'input_shardings', None)
        if shardings is not None:
            vals = tuple(jax.device_put(v, s)
                         for v, s in zip(vals, shardings[0]))
        out = self._compiled(*vals)
        if isinstance(out, (tuple, list)):
            return type(out)(Tensor(o) for o in out)
        return Tensor(out)

    @property
    def in_avals(self):
        return self._compiled.in_avals

    def cost_analysis(self):
        return self._compiled.cost_analysis()

    def save(self, path):
        from jax.experimental import serialize_executable as se
        payload = se.serialize(self._compiled)   # (bytes, in_tree, out_tree)
        from ..compilecache import executable_device_count
        n_devices = executable_device_count(self._compiled)
        from ..resilience.atomic_io import atomic_pickle_dump
        atomic_pickle_dump({'backend': jax.default_backend(),
                            'n_devices': n_devices,
                            'payload': payload}, path)
        return path

    @classmethod
    def load(cls, path):
        from jax.experimental import serialize_executable as se
        with open(path, 'rb') as f:
            blob = pickle.load(f)
        if blob['backend'] != jax.default_backend():
            raise RuntimeError(
                "AOT executable was compiled for backend %r but this "
                "process runs %r — recompile with trace()"
                % (blob['backend'], jax.default_backend()))
        serialized, in_tree, out_tree = blob['payload']
        n = blob.get('n_devices') or 1
        if n > len(jax.devices()):
            raise RuntimeError(
                "AOT executable needs %d device(s); %d available"
                % (n, len(jax.devices())))
        # onto exactly the compiled device count — the default maps onto
        # every local device and then rejects the args
        return cls(se.deserialize_and_load(
            serialized, in_tree, out_tree,
            execution_devices=jax.devices()[:n]))


class Predictor:
    """Inference engine over a save_inference_model directory.

    run(feed_dict) -> list of fetch arrays. The whole fetch subgraph runs
    as one jit computation per feed-shape signature; pass
    ``cache_dir`` to persist compiled executables across processes.
    """

    def __init__(self, dirname, model_filename=None, params_filename=None,
                 cache_dir=None):
        if cache_dir:
            enable_compilation_cache(cache_dir)
        with open(os.path.join(dirname, model_filename or '__model__'),
                  'rb') as f:
            meta = pickle.load(f)
        with open(os.path.join(dirname, params_filename or '__params__'),
                  'rb') as f:
            params = pickle.load(f)
        self._feed_names = list(meta['feed_names'])
        self._fetch_names = list(meta['fetch_names'])
        if 'exported' not in meta:
            raise RuntimeError(
                "model dir has no portable export (save_inference_model "
                "recorded: %s) — re-export it"
                % meta.get('export_error', 'unknown reason'))
        import jax.export  # noqa: F401 — lazy submodule: a bare
        # `import jax` does not bind the attribute
        self._exported = jax.export.deserialize(
            bytearray(meta['exported']['blob']))
        self._param_vals = [np.asarray(params[n])
                            for n in meta['exported']['param_names']]
        self._feed_dtypes = [np.dtype(d) for d in
                             meta['exported'].get(
                                 'feed_dtypes',
                                 ['float32'] * len(self._feed_names))]
        # run through the persistent compile tier: in-memory jit caching
        # per feed signature always; against a bound compilecache dir the
        # executable is AOT-deserialized/committed per signature, so a
        # fresh process (or a serving replica registering this predictor
        # with artifact_dir=) replays it with zero compiles
        from .. import compilecache as _cc
        self._call = _cc.CachedJit(
            lambda feed_vals, param_vals:
                self._exported.call(feed_vals, param_vals),
            auto_label='predictor.%s' % os.path.basename(
                os.path.abspath(dirname)),
            kind='predictor', meta={'dir': os.path.basename(
                os.path.abspath(dirname))})

    @property
    def feed_names(self):
        return list(self._feed_names)

    @property
    def fetch_names(self):
        return list(self._fetch_names)

    def run(self, feed):
        """feed: dict name -> array (numpy/Tensor). Returns numpy arrays
        in fetch order. Each new feed-shape signature compiles once (use
        cache_dir to persist those compilations across processes)."""
        feed = {k: (v.numpy() if isinstance(v, Tensor) else np.asarray(v))
                for k, v in feed.items()}
        missing = [n for n in self._feed_names if n not in feed]
        if missing:
            raise ValueError("Predictor.run: missing feeds %s" % missing)
        # cast to the exported dtypes (numpy defaults to float64/int64,
        # which the export was not built for) — same as Executor.run
        feed_vals = [np.asarray(feed[n], dtype=dt)
                     for n, dt in zip(self._feed_names, self._feed_dtypes)]
        outs = self._call(feed_vals, self._param_vals)
        fetched = [np.asarray(o) for o in outs]
        from .. import observability as _obs
        if _obs.enabled():
            _obs.record_host_transfer(sum(a.nbytes for a in fetched),
                                      kind='predictor.fetch')
        return fetched


def load_inference_model(dirname, model_filename=None, params_filename=None,
                         cache_dir=None):
    """Load a ``save_inference_model`` directory into a ready ``Predictor``.

    The standalone-process analogue of ``static.io.load_inference_model``
    (which rebinds params into the *current* Program and therefore only
    works in the process that built the graph — the save/load asymmetry
    this entry point closes). Use this one everywhere a fresh process
    serves an exported model; ``paddle_tpu.serving`` registers its models
    through the same call::

        predictor = inference.load_inference_model('model_dir')
        engine.register('m', predictor=predictor,
                        example={'x': np.zeros((16,), np.float32)})
    """
    return Predictor(dirname, model_filename=model_filename,
                     params_filename=params_filename, cache_dir=cache_dir)
