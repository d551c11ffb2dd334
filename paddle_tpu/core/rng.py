"""Random state management.

Reference parity: python/paddle/fluid/generator.py + paddle/fluid/framework/generator.cc
(global 64-bit Philox-style engines per device). TPU-first: JAX keys; a
stateful Generator derives keys for eager ops, and ``key_scope`` threads an
explicit key through jit-traced regions so compiled functions stay pure.

PRNG implementation: paddle_tpu's own generators use jax's 'rbg' impl by
default — the threefry PRNG costs real step time when dropout runs every
layer (measured ~45% train-step overhead on BERT-large), while 'rbg' maps to
the hardware RNG. This is scoped to OUR keys via PRNGKey(impl=...); the
process-global jax default and the host application's own jax.random calls
are untouched. Override with PADDLE_TPU_PRNG=threefry2x32 if counter-based
reproducibility across backends matters more than speed.

XLA's partitioner cannot split an 'rbg' draw: at a shape that is sharded over
a mesh it is made WHOLE on every device and sliced. The dropout masks that
XLA ops draw therefore go through ``kernels._common.keep_mask``, which draws
each device's part on that device.
"""
import contextlib
import os
import threading
import warnings

import jax
import numpy as np

_PRNG_IMPL = os.environ.get('PADDLE_TPU_PRNG', 'rbg')


def _make_key(seed):
    # new-style typed key: carries its impl, so fold_in/bernoulli on it work
    # regardless of the process-global jax_default_prng_impl
    try:
        return jax.random.key(seed, impl=_PRNG_IMPL)
    except (ValueError, KeyError, TypeError) as e:
        warnings.warn(f"PRNG impl '{_PRNG_IMPL}' unavailable ({e}); "
                      f"falling back to the jax default")
        return jax.random.key(seed)


def _key_data(key):
    try:
        return np.asarray(jax.random.key_data(key))
    except Exception:
        return np.asarray(key)


class Generator:
    """Stateful key source whose STATE is pure Python (base key + counter).

    next_key() derives fold_in(base, counter) instead of split-and-store: a
    split inside a jit/grad trace returns a tracer, and storing that into the
    generator leaks it into later calls (UnexpectedTracerError). With the
    counter design the mutable state never holds a traced value, so drawing
    keys inside traced regions is safe (the drawn key becomes a trace
    constant, as documented for key_scope).
    """

    def __init__(self, seed=0):
        self.manual_seed(seed)

    def manual_seed(self, seed):
        self._seed = int(seed)
        self._base = _make_key(self._seed)
        self._count = 0
        return self

    def seed(self):
        return self._seed

    def initial_seed(self):
        return self._seed

    def next_key(self):
        self._count += 1
        return jax.random.fold_in(self._base, self._count)

    def get_state(self):
        return {'base': _key_data(self._base), 'count': self._count,
                'seed': self._seed}

    def _adopt_key_words(self, arr):
        """Restore a base key from raw uint32 words; if the width doesn't
        match the current impl (state saved under another impl), reseed
        deterministically from the words instead."""
        arr = np.asarray(arr, np.uint32).ravel()
        own = _key_data(self._base).ravel()
        if arr.shape == own.shape:
            try:
                self._base = jax.random.wrap_key_data(
                    jax.numpy.asarray(arr), impl=_PRNG_IMPL)
                return
            except Exception:
                pass
        self.manual_seed(int(arr[-1]) ^ (int(arr[0]) << 1))

    def set_state(self, state):
        if isinstance(state, dict):
            if 'seed' in state:
                self.manual_seed(int(state['seed']))
                if _key_data(self._base).ravel().shape != \
                        np.asarray(state['base'], np.uint32).ravel().shape:
                    # saved under a different impl: the reseed above is the
                    # deterministic restore
                    self._count = int(state['count'])
                    return
            self._adopt_key_words(state['base'])
            self._count = int(state['count'])
            self._seed = int(state.get('seed', -1))
        else:  # legacy raw-key format
            self._adopt_key_words(state)
            self._count = 0


default_generator = Generator(0)

_tls = threading.local()


def _scoped_gen():
    return getattr(_tls, 'gen_stack', None)


def current_generator():
    stack = _scoped_gen()
    if stack:
        return stack[-1]
    return default_generator


def next_key():
    return current_generator().next_key()


@contextlib.contextmanager
def key_scope(key):
    """Run a region with RNG derived from an explicit key (pure under jit)."""
    gen = Generator.__new__(Generator)
    gen._seed = -1
    gen._base = key
    gen._count = 0
    if not hasattr(_tls, 'gen_stack'):
        _tls.gen_stack = []
    _tls.gen_stack.append(gen)
    try:
        yield gen
    finally:
        _tls.gen_stack.pop()


def seed(s):
    """Parity: paddle.seed / fluid.Program.random_seed."""
    default_generator.manual_seed(s)
    return default_generator


def get_rng_state():
    return default_generator.get_state()


def set_rng_state(state):
    default_generator.set_state(state)
