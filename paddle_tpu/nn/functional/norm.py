"""Normalization functionals. Parity: python/paddle/nn/functional/norm.py.

batch_norm takes/returns running stats explicitly in functional form so the
stateful layer can collect updates (see layer_base.functional_call).
"""
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


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    x = _t(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_norm = len(list(normalized_shape))
    axes = tuple(range(x.ndim - n_norm, x.ndim))
    tensors = [x]
    if weight is not None:
        tensors.append(_t(weight))
    if bias is not None:
        tensors.append(_t(bias))
    has_w = weight is not None
    has_b = bias is not None

    if (n_norm == 1 and jax.default_backend() == 'tpu'
            and x.shape[-1] % 128 == 0):
        from ...kernels.fused_norm import fused_layer_norm

        def fused(v, *wb):
            i = 0
            w = wb[i] if has_w else None
            i += has_w
            b = wb[i] if has_b else None
            return fused_layer_norm(v, w, b, eps=epsilon)
        return apply_op(fused, tuple(tensors))

    def fn(v, *wb):
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) / jnp.sqrt(var + epsilon)
        i = 0
        if has_w:
            out = out * wb[i]
            i += 1
        if has_b:
            out = out + wb[i]
        return out
    return apply_op(fn, tuple(tensors))


def rms_norm_values(v, w=None, epsilon=1e-6):
    """`rms_norm` over jax values: for a layer that norms inside its own
    traced function (one that is recomputed in the backward pass)."""
    if jax.default_backend() == 'tpu' and v.shape[-1] % 128 == 0:
        from ...kernels.fused_norm import fused_rms_norm
        return fused_rms_norm(v, w, eps=epsilon)
    ms = jnp.mean(v * v, axis=-1, keepdims=True)
    out = v / jnp.sqrt(ms + epsilon)
    return out if w is None else out * w


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


_USE_FUSED_DROPOUT_NORM = [True]
_FUSED_DROPOUT_NORM_MIN_ROWS = 4096  # measured on v5e: below this the pallas
# pass (extra pre-norm-sum write) loses to XLA's own dropout+add fusion


def set_fused_dropout_norm(enabled):
    _USE_FUSED_DROPOUT_NORM[0] = bool(enabled)


def fused_dropout_add_layer_norm(x, residual, weight=None, bias=None,
                                 dropout_p=0.0, epsilon=1e-5, training=True,
                                 name=None):
    """y = LayerNorm(residual + dropout(x)) — single pallas pass on TPU.

    Replaces the three separate HBM passes (rng mask, dropout select,
    residual add) + norm read of the unfused transformer sublayer epilogue
    (kernels/fused_dropout_norm.py). Off-TPU falls back to composed ops with
    identical semantics.
    """
    from ...core import rng as _rng
    x, residual = _t(x), _t(residual)
    p_eff = float(dropout_p) if training else 0.0
    tensors = [x, residual]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        tensors.append(_t(weight))
    if has_b:
        tensors.append(_t(bias))
    n_rows = 1
    for s in x.shape[:-1]:
        n_rows *= s
    if (_USE_FUSED_DROPOUT_NORM[0] and n_rows >= _FUSED_DROPOUT_NORM_MIN_ROWS
            and jax.default_backend() == 'tpu' and x.shape[-1] % 128 == 0):
        from ...kernels.fused_dropout_norm import \
            fused_dropout_add_layer_norm as _kernel
        seed = None
        if p_eff > 0.0:
            seed = jax.random.randint(_rng.next_key(), (1, 1), 0,
                                      2**31 - 1).astype(jnp.int32)

        def fused(v, r, *wb):
            i = 0
            w = wb[i] if has_w else None
            i += has_w
            b = wb[i] if has_b else None
            return _kernel(v, r, w, b, dropout_p=p_eff, epsilon=epsilon,
                           dropout_seed=seed)
        return apply_op(fused, tuple(tensors))

    # composed fallback (identical math, separate passes)
    from .common import dropout as _dropout
    y = _dropout(x, p=p_eff, training=True) if p_eff > 0.0 else x
    s = apply_op(lambda a, b: a + b, (y, residual))
    return layer_norm(s, x.shape[-1], weight, bias, epsilon)
