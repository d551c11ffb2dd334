"""Normalization functionals. Parity: python/paddle/nn/functional/norm.py.

batch_norm takes/returns running stats explicitly in functional form so the
stateful layer can collect updates (see layer_base.functional_call).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, apply_op
from ...tensor._helpers import _t

__all__ = ['normalize', 'batch_norm', 'layer_norm', 'fused_dropout_add_layer_norm',
           'instance_norm', 'group_norm',
           'local_response_norm', 'rms_norm', 'rms_norm_values']


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def fn(v):
        if p == 2:
            nrm = jnp.sqrt(jnp.sum(v * v, axis=axis, keepdims=True))
        else:
            nrm = jnp.sum(jnp.abs(v) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return v / jnp.maximum(nrm, epsilon)
    return apply_op(fn, (_t(x),))


def _channel_shape(v_ndim, c, data_format):
    shp = [1] * v_ndim
    ch_axis = v_ndim - 1 if not data_format.startswith('NC') else 1
    shp[ch_axis] = c
    return shp, ch_axis


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05, data_format="NCHW",
               use_global_stats=None, name=None):
    """Returns normalized output; updates running stats in-place on the
    provided tensors when training (collected by functional_call)."""
    x = _t(x)
    rm, rv = _t(running_mean), _t(running_var)
    use_batch_stats = training and not use_global_stats

    tensors = [x]
    has_affine = weight is not None
    if has_affine:
        tensors += [_t(weight), _t(bias)]

    c = rm.shape[0]
    shp, ch_axis = _channel_shape(x.ndim, c, data_format)
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)

    if use_batch_stats:
        n = int(np.prod([x.shape[i] for i in reduce_axes]))
        unbias = n / max(n - 1, 1)
        # running stats are apply_op INPUTS and the new stats are computed
        # inside the pure fn — this keeps the whole update visible to traces
        # (jit.to_static capture watch) so no tracer ever leaks into buffers.
        tensors += [rm, rv]

        def fn(v, *rest):
            wb, (m0, v0) = rest[:-2], rest[-2:]
            # shifted single-pass stats in fp32: one fused sweep computes
            # E[x-s] and E[(x-s)^2] with s = running mean, so the
            # var = E[(x-s)^2] - E[x-s]^2 subtraction cancels only when
            # |batch mean - s| >> std — which the running mean prevents —
            # instead of whenever |mean| >> std (the naive E[x^2]-E[x]^2).
            vf = v.astype(jnp.float32)
            s = jax.lax.stop_gradient(m0.astype(jnp.float32)).reshape(shp)
            vc = vf - s
            mean_c = jnp.mean(vc, axis=reduce_axes)
            m2 = jnp.mean(vc * vc, axis=reduce_axes)
            var = jnp.maximum(m2 - mean_c * mean_c, 0.0)
            mean = mean_c + s.reshape(mean_c.shape)
            inv = jax.lax.rsqrt(var.reshape(shp) + epsilon)
            out = ((vf - mean.reshape(shp)) * inv).astype(v.dtype)
            if wb:
                out = out * wb[0].reshape(shp) + wb[1].reshape(shp)
            new_rm = momentum * m0 + (1 - momentum) * mean.astype(m0.dtype)
            new_rv = momentum * v0 + (1 - momentum) * (var * unbias).astype(v0.dtype)
            return out, new_rm, new_rv

        def eval_fn(v, *rest):
            # test-mode variant (Program.clone(for_test=True)): normalize
            # with the running stats, leave them unchanged
            wb, (m0, v0) = rest[:-2], rest[-2:]
            inv = 1.0 / jnp.sqrt(v0.astype(jnp.float32).reshape(shp) +
                                 epsilon)
            out = ((v.astype(jnp.float32) -
                    m0.astype(jnp.float32).reshape(shp)) * inv) \
                .astype(v.dtype)
            if wb:
                out = out * wb[0].reshape(shp) + wb[1].reshape(shp)
            return out, m0, v0

        out, new_rm, new_rv = apply_op(fn, tuple(tensors), n_outputs=3,
                                       eval_fn=eval_fn)
        if not getattr(new_rm, '_symbolic', False):
            with _no_grad():
                rm._inplace_value(new_rm._value)
                rv._inplace_value(new_rv._value)
        # static capture: the buffers keep their concrete payloads (writing
        # a symbolic aval into them would poison every later read);
        # running-stat advancement across Executor.run calls is a
        # documented divergence of the static path
        return out

    tensors += [rm, rv]
    def fn(v, *rest):
        if has_affine:
            w, b, m, var = rest
        else:
            (m, var) = rest
            w = b = None
        inv = 1.0 / jnp.sqrt(var.reshape(shp) + epsilon)
        out = (v - m.reshape(shp)) * inv
        if w is not None:
            out = out * w.reshape(shp) + b.reshape(shp)
        return out
    return apply_op(fn, tuple(tensors))


def _no_grad():
    from ...core.autograd import no_grad
    return no_grad()


def _affine(wb, has_w, has_b):
    """(weight, bias) of an op whose inputs carry those that are present."""
    return (wb[0] if has_w else None, wb[int(has_w)] if has_b else None)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    from ...kernels.fused_norm import fused_layer_norm
    x = _t(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(list(normalized_shape))
    tensors = [x]
    if weight is not None:
        tensors.append(_t(weight))
    if bias is not None:
        tensors.append(_t(bias))
    has_w = weight is not None
    has_b = bias is not None

    def fn(v, *wb):
        return fused_layer_norm(v, *_affine(wb, has_w, has_b), epsilon,
                                n_axes)
    return apply_op(fn, tuple(tensors))


def rms_norm_values(v, w=None, epsilon=1e-6, mean_square=None):
    """`rms_norm` over jax values: for a layer that norms inside its own
    traced function (one that is recomputed in the backward pass).
    `mean_square` (v's shape with a last axis of 1), where the caller has
    it, stands for v's own: a layer that holds a share of the channels norms
    by the mean square over all of them (docs/HEAD_SHARE.md)."""
    if mean_square is not None:
        y = v.astype(jnp.float32) * jax.lax.rsqrt(mean_square + epsilon)
        return (y if w is None else y * w).astype(v.dtype)
    from ...kernels.fused_norm import fused_rms_norm
    return fused_rms_norm(v, w, epsilon)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm (modern LLM stacks; pallas-fused variant in kernels/)."""
    x = _t(x)
    tensors = [x] + ([_t(weight)] if weight is not None else [])
    return apply_op(lambda v, *w: rms_norm_values(v, w[0] if w else None,
                                                  epsilon), tuple(tensors))


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    x = _t(x)
    ch_axis = 1 if data_format.startswith('NC') else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i not in (0, ch_axis))
    tensors = [x]
    has_affine = weight is not None
    if has_affine:
        tensors += [_t(weight), _t(bias)]
    def fn(v, *wb):
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) / jnp.sqrt(var + eps)
        if wb:
            shp = [1] * v.ndim
            shp[ch_axis] = wb[0].size
            out = out * wb[0].reshape(shp) + wb[1].reshape(shp)
        return out
    return apply_op(fn, tuple(tensors))


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = _t(x)
    ch_axis = 1 if data_format.startswith('NC') else x.ndim - 1
    tensors = [x]
    has_affine = weight is not None
    if has_affine:
        tensors += [_t(weight), _t(bias)]
    def fn(v, *wb):
        if ch_axis != 1:
            v = jnp.moveaxis(v, ch_axis, 1)
        n, c = v.shape[0], v.shape[1]
        rest = v.shape[2:]
        g = v.reshape(n, num_groups, c // num_groups, *rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) / jnp.sqrt(var + epsilon)).reshape(v.shape)
        if wb:
            shp = [1] * v.ndim
            shp[1] = c
            out = out * wb[0].reshape(shp) + wb[1].reshape(shp)
        if ch_axis != 1:
            out = jnp.moveaxis(out, 1, ch_axis)
        return out
    return apply_op(fn, tuple(tensors))


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = _t(x)
    ch_axis = 1 if data_format.startswith('NC') else x.ndim - 1
    def fn(v):
        sq = v * v
        half = size // 2
        pad_spec = [(0, 0)] * v.ndim
        pad_spec[ch_axis] = (half, size - 1 - half)
        padded = jnp.pad(sq, pad_spec)
        # sliding sum over channel axis
        acc = jnp.zeros_like(v)
        for i in range(size):
            sl = [slice(None)] * v.ndim
            sl[ch_axis] = slice(i, i + v.shape[ch_axis])
            acc = acc + padded[tuple(sl)]
        div = (k + alpha * acc) ** beta
        return v / div
    return apply_op(fn, (x,))


def fused_dropout_add_layer_norm(x, residual, weight=None, bias=None,
                                 dropout_p=0.0, epsilon=1e-5, training=True,
                                 name=None):
    """y = LayerNorm(residual + dropout(x)): the transformer sublayer's
    epilogue as one op (kernels/fused_dropout_norm.py decides between one
    Pallas pass and the composed XLA ops, with identical semantics)."""
    from ...core import rng as _rng
    from ...kernels.fused_dropout_norm import dropout_add_layer_norm
    p_eff = float(dropout_p) if training else 0.0
    tensors = [_t(x), _t(residual)]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        tensors.append(_t(weight))
    if has_b:
        tensors.append(_t(bias))
    key = _rng.next_key() if 0.0 < p_eff < 1.0 else None

    def fn(p, v, r, *wb):
        return dropout_add_layer_norm(v, r, *_affine(wb, has_w, has_b), p,
                                      epsilon, key)
    # eval_fn: the test-mode variant for Program.clone(for_test=True)
    return apply_op(functools.partial(fn, p_eff), tuple(tensors),
                    eval_fn=functools.partial(fn, 0.0))
