"""Parameters from a seed: one jitted call makes every leaf on the device,
in float32 (the master weights' type). Leaves of one shape and one
distribution are drawn as one block."""
import functools

import jax
import jax.numpy as jnp


def key_of(seed, salt=0):
    """A key from any non-negative whole number (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), salt)


@functools.partial(jax.jit, static_argnums=0)
def _make(items, key):
    groups = {}
    for name, shape, init in items:
        groups.setdefault((shape, init), []).append(name)
    out = {}
    for i, ((shape, init), names) in enumerate(sorted(groups.items())):
        if init.startswith('normal:'):
            block = float(init[7:]) * jax.random.normal(
                jax.random.fold_in(key, i), (len(names),) + shape,
                jnp.float32)
            for j, name in enumerate(names):
                out[name] = block[j]
        elif init in ('zeros', 'ones'):
            for name in names:
                out[name] = jnp.full(shape, float(init == 'ones'), jnp.float32)
        else:
            raise ValueError('unknown init %r of %s' % (init, names))
    return out


def make(spec, seed, salt=0):
    """spec: name -> (shape, init) -> dict name -> float32 device array."""
    items = tuple((n, tuple(s), i) for n, (s, i) in spec.items())
    if not items:
        return {}
    return _make(items, key_of(seed, salt))
