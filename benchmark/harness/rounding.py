"""What the plain references share: rounding to the lower precision of the
control, and the per-leaf norms the comparison reads."""
import jax
import jax.numpy as jnp


def round_to(x, precision):
    """A matmul or convolution operand in `precision`, invisible to the
    gradient (straight through). 'float32' leaves it; 'float8' keeps 4
    exponent and 3 mantissa bits (e4m3) under a per-tensor scale that puts
    the largest magnitude at the format's, the usual fp8 recipe.
    `reduce_precision` states the rounding as an operation of its own: a
    cast down and up again is one the TPU compiler may drop. (Rounding the
    cotangents to e5m2 as well was tried on the chip and separated worse:
    PERF.md section 6.)"""
    if precision == 'float32':
        return x
    if precision == 'float8':
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
        q = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale
    else:
        raise ValueError('unknown precision %r' % (precision,))
    return x + jax.lax.stop_gradient(q - x)


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}
