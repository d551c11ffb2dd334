"""Fused layer/rms norm: Pallas TPU forward kernels + closed-form backward.

Replaces the reference's fused LayerNorm CUDA kernels
(paddle/fluid/operators/layer_norm_op.cu) with a TPU-native design: one VMEM
pass computes mean/rstd and the normalized output per row tile (no separate
moment kernels), saving only the (N, 1) row statistics for the backward. The
backward is the closed-form layer-norm gradient evaluated in plain XLA from
(x, mean, rstd) — elementwise + row reductions, which XLA fuses into one pass,
so no extra memory traffic is saved by hand-writing it.

Testable on CPU via interpret=True (tests/test_fused_norm.py).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import (pallas_runs, row_block as _shared_row_block,
                      spmd_kernel, took)


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps,
                   has_w, has_b):
    x = x_ref[...].astype(jnp.float32)                  # (block_n, D)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd
    if has_w:
        y = y * w_ref[...].astype(jnp.float32)
    if has_b:
        y = y + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _rms_fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps, has_w):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = x * rstd
    if has_w:
        y = y * w_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    rstd_ref[...] = rstd


def _row_block(n, d):
    # one row tile per grid step; 8-row multiples satisfy TPU sublane tiling
    return _shared_row_block(n)


def _ln_forward(x2, w, b, eps, interpret):
    d = x2.shape[1]
    has_w, has_b = w is not None, b is not None
    w_arg = w if has_w else jnp.zeros((d,), x2.dtype)
    b_arg = b if has_b else jnp.zeros((d,), x2.dtype)
    kernel = functools.partial(_ln_fwd_kernel, eps=eps, has_w=has_w,
                               has_b=has_b)

    def call(x2, w_arg, b_arg, shard):
        n = x2.shape[0]                 # this device's rows
        bn = _row_block(n, d)
        return pl.pallas_call(
            kernel,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                      pl.BlockSpec((d,), lambda i: (0,)),
                      pl.BlockSpec((d,), lambda i: (0,))],
            out_specs=(pl.BlockSpec((bn, d), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))),
            out_shape=(jax.ShapeDtypeStruct((n, d), x2.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)),
            interpret=interpret,
        )(x2, w_arg, b_arg)

    return spmd_kernel(call, [('n', 'd'), ('d',), ('d',)],
                       [('n', 'd'), ('n', None), ('n', None)],
                       {'n': 'batch'}, granule=8,
                       scope='fused_layer_norm.pallas')(x2, w_arg, b_arg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_layer_norm2d(x2, w, b, eps, interpret):
    y, _, _ = _ln_forward(x2, w, b, eps, interpret)
    return y


def _ln_fwd_rule(x2, w, b, eps, interpret):
    y, mean, rstd = _ln_forward(x2, w, b, eps, interpret)
    return y, (x2, w, b, mean, rstd)


def _ln_bwd_rule(eps, interpret, res, g):
    x2, w, b, mean, rstd = res
    x = x2.astype(jnp.float32)
    g = g.astype(jnp.float32)
    xhat = (x - mean) * rstd
    gw = g * (w.astype(jnp.float32) if w is not None else 1.0)
    # closed-form LN input grad
    mean_g = jnp.mean(gw, axis=-1, keepdims=True)
    mean_gx = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (rstd * (gw - mean_g - xhat * mean_gx)).astype(x2.dtype)
    dw = jnp.sum(g * xhat, axis=0).astype(w.dtype) if w is not None else None
    db = jnp.sum(g, axis=0).astype(b.dtype) if b is not None else None
    return dx, dw, db


_fused_layer_norm2d.defvjp(_ln_fwd_rule, _ln_bwd_rule)


def _rms_forward(x2, w, eps, interpret):
    d = x2.shape[1]
    has_w = w is not None
    w_arg = w if has_w else jnp.zeros((d,), x2.dtype)
    kernel = functools.partial(_rms_fwd_kernel, eps=eps, has_w=has_w)

    def call(x2, w_arg, shard):
        n = x2.shape[0]                 # this device's rows
        bn = _row_block(n, d)
        return pl.pallas_call(
            kernel,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                      pl.BlockSpec((d,), lambda i: (0,))],
            out_specs=(pl.BlockSpec((bn, d), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))),
            out_shape=(jax.ShapeDtypeStruct((n, d), x2.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)),
            interpret=interpret,
        )(x2, w_arg)

    return spmd_kernel(call, [('n', 'd'), ('d',)],
                       [('n', 'd'), ('n', None)],
                       {'n': 'batch'}, granule=8,
                       scope='fused_rms_norm.pallas')(x2, w_arg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_rms_norm2d(x2, w, eps, interpret):
    y, _ = _rms_forward(x2, w, eps, interpret)
    return y


def _rms_fwd_rule(x2, w, eps, interpret):
    y, rstd = _rms_forward(x2, w, eps, interpret)
    return y, (x2, w, rstd)


def _rms_bwd_rule(eps, interpret, res, g):
    x2, w, rstd = res
    x = x2.astype(jnp.float32)
    g = g.astype(jnp.float32)
    xhat = x * rstd
    gw = g * (w.astype(jnp.float32) if w is not None else 1.0)
    mean_gx = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (rstd * (gw - xhat * mean_gx)).astype(x2.dtype)
    dw = jnp.sum(g * xhat, axis=0).astype(w.dtype) if w is not None else None
    return dx, dw


_fused_rms_norm2d.defvjp(_rms_fwd_rule, _rms_bwd_rule)


def fused_layer_norm(x, weight=None, bias=None, eps=1e-5, n_axes=1,
                     interpret=False):
    """Layer norm over the last ``n_axes`` axes of x (any leading shape).

    The kernel norms the last axis alone, rows of whole 128-lane registers
    in tiles of 8 rows or more, on the TPU (or in interpret mode);
    everything else is plain XLA. Either way under
    ``fused_layer_norm.pallas`` / ``fused_layer_norm.xla``."""
    shape = x.shape
    lanes = (n_axes == 1 and pallas_runs(interpret)
             and shape[-1] % 128 == 0)
    if lanes and _row_block(math.prod(shape[:-1]), shape[-1]) is not None:
        with took('fused_layer_norm', 'pallas'):
            y = _fused_layer_norm2d(x.reshape(-1, shape[-1]), weight, bias,
                                    float(eps), interpret)
        return y.reshape(shape)
    with took('fused_layer_norm', 'xla'):
        axes = tuple(range(x.ndim - n_axes, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        # where only the row count keeps the kernel away, its stand-in keeps
        # its arithmetic and its contract (the result in x's dtype): a
        # step's dtypes do not turn on its batch size. Elsewhere the result
        # promotes as jax.numpy's does
        y = ((x - mean) * jax.lax.rsqrt(var + eps) if lanes
             else (x - mean) / jnp.sqrt(var + eps))
        if weight is not None:
            y = y * weight
        if bias is not None:
            y = y + bias
        return y.astype(x.dtype) if lanes else y


def fused_rms_norm(x, weight=None, eps=1e-6, interpret=False):
    """RMS norm over the LAST axis of x (any leading shape): the kernel for
    rows of whole 128-lane registers in tiles of 8 rows or more on the TPU
    (or in interpret mode), else plain XLA; under ``fused_rms_norm.pallas``
    / ``fused_rms_norm.xla``."""
    shape = x.shape
    if (pallas_runs(interpret) and shape[-1] % 128 == 0
            and _row_block(math.prod(shape[:-1]), shape[-1]) is not None):
        with took('fused_rms_norm', 'pallas'):
            y = _fused_rms_norm2d(x.reshape(-1, shape[-1]), weight,
                                  float(eps), interpret)
        return y.reshape(shape)
    with took('fused_rms_norm', 'xla'):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        y = x / jnp.sqrt(ms + eps)
        return y if weight is None else y * weight
