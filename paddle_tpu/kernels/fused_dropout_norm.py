"""Fused dropout + residual-add + LayerNorm: Pallas TPU kernel.

Replaces the reference's fused_dropout_add / layer_norm CUDA stack
(paddle/fluid/operators/fused/fused_dropout_helper.h,
layer_norm_op.cu) with a TPU-native single-pass design. Profiled on v5e
(BERT-large seq512): the unfused path costs three full HBM passes per
sublayer (rng-bits materialization, dropout select, add) before the norm
kernel reads the sum again — ~30 ms/step across 48 sublayer sites. This
kernel reads x and residual once, generates the keep mask from the TPU
hardware PRNG in-register (seeded by tile id, exactly like
flash_attention.py's in-kernel dropout), and writes the normalized output
plus the pre-norm sum in one pass.

Backward: LayerNorm's closed-form gradient runs in plain XLA from the saved
pre-norm sum + row stats (one fused pass); the dropout mask is REGENERATED
from the same (seed, tile) PRNG stream by a small Pallas kernel — the
(N, D) mask is never stored.

Interpret-mode caveat: prng_random_bits is a zero-stub on CPU interpret, so
dropout_p > 0 parity is TPU-only (the p == 0 fused add+norm path is fully
testable on CPU; see tests/test_fused_dropout_norm.py).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import (keep_mask, pallas_runs, row_block as _row_block,
                      rows_first, spmd_kernel,
                      tile_keep_scale as _keep_scale, took)
from .fused_norm import fused_layer_norm

# nn.functional's epilogue under this many rows stays composed ops: the
# kernel's extra write of the pre-norm sum loses to XLA's own fusion of
# dropout and add there
_MIN_ROWS = 4096


def _fwd_kernel(*refs, eps, p, has_w, has_b):
    refs = list(refs)
    x_ref, res_ref = refs[:2]
    idx = 2
    w_ref = b_ref = seed_ref = None
    if has_w:
        w_ref = refs[idx]; idx += 1
    if has_b:
        b_ref = refs[idx]; idx += 1
    if p > 0.0:
        seed_ref = refs[idx]; idx += 1
    y_ref, yin_ref, mean_ref, rstd_ref = refs[idx:idx + 4]

    x = x_ref[...].astype(jnp.float32)                  # (bn, D)
    res = res_ref[...].astype(jnp.float32)
    if p > 0.0:
        x = x * _keep_scale(seed_ref, seed_ref[0, 1] + pl.program_id(0),
                            x.shape, p)
    yin = res + x
    mean = jnp.mean(yin, axis=-1, keepdims=True)
    xc = yin - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd
    if has_w:
        y = y * w_ref[...].astype(jnp.float32)
    if has_b:
        y = y + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    yin_ref[...] = yin.astype(yin_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _dmask_kernel(g_ref, seed_ref, out_ref, *, p):
    """dx = d_yin * keep/(1-p) with the regenerated tile mask."""
    g = g_ref[...].astype(jnp.float32)
    out = g * _keep_scale(seed_ref, seed_ref[0, 1] + pl.program_id(0),
                          g.shape, p)
    out_ref[...] = out.astype(out_ref.dtype)


def _seed_and_tile(seed, shard, bn):
    """(1, 2) int32 [seed, id of this shard's first row tile]: tile ids
    count rows of the WHOLE array, so the mask a row gets does not depend
    on how the rows were partitioned."""
    return jnp.concatenate(
        [seed.astype(jnp.int32).reshape(1, 1),
         (shard['n'][0] // bn).astype(jnp.int32).reshape(1, 1)], axis=1)


def _fused_fwd(x, res, w, b, seed, eps, p, interpret):
    d = x.shape[1]
    has_w, has_b = w is not None, b is not None
    kernel = functools.partial(_fwd_kernel, eps=eps, p=p, has_w=has_w,
                               has_b=has_b)
    args, dims = [x, res], [('n', 'd'), ('n', 'd')]
    if has_w:
        args.append(w); dims.append(('d',))
    if has_b:
        args.append(b); dims.append(('d',))
    if p > 0.0:
        args.append(seed); dims.append((None, None))

    def call(*args, shard):
        n = args[0].shape[0]            # this device's rows
        bn = _row_block(n)
        args = list(args)
        in_specs = [pl.BlockSpec((bn, d), lambda i: (i, 0)),
                    pl.BlockSpec((bn, d), lambda i: (i, 0))]
        in_specs += [pl.BlockSpec((d,), lambda i: (0,))] * (has_w + has_b)
        if p > 0.0:
            in_specs.append(pl.BlockSpec((1, 2), lambda i: (0, 0)))
            args[-1] = _seed_and_tile(args[-1], shard, bn)
        return pl.pallas_call(
            kernel,
            grid=(n // bn,),
            in_specs=in_specs,
            out_specs=(pl.BlockSpec((bn, d), lambda i: (i, 0)),
                       pl.BlockSpec((bn, d), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))),
            out_shape=(jax.ShapeDtypeStruct((n, d), x.dtype),
                       jax.ShapeDtypeStruct((n, d), x.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)),
            interpret=interpret,
        )(*args)

    return spmd_kernel(
        call, dims, [('n', 'd'), ('n', 'd'), ('n', None), ('n', None)],
        {'n': 'batch'}, granule=8,
        scope='fused_dropout_norm.pallas')(*args)


def _apply_dropout_grad(d_yin, seed, p, interpret):
    d = d_yin.shape[1]

    def call(g, seed, shard):
        n = g.shape[0]
        bn = _row_block(n)
        return pl.pallas_call(
            functools.partial(_dmask_kernel, p=p),
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                      pl.BlockSpec((1, 2), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, d), g.dtype),
            interpret=interpret,
        )(g, _seed_and_tile(seed, shard, bn))

    return spmd_kernel(call, [('n', 'd'), (None, None)], [('n', 'd')],
                       {'n': 'batch'}, granule=8,
                       scope='fused_dropout_norm.pallas')(d_yin, seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fdln(x, res, w, b, seed, eps, p, interpret):
    y, _, _, _ = _fused_fwd(x, res, w, b, seed, eps, p, interpret)
    return y


def _fdln_fwd(x, res, w, b, seed, eps, p, interpret):
    y, yin, mean, rstd = _fused_fwd(x, res, w, b, seed, eps, p, interpret)
    # under amp the residual stream can be fp32 while x is bf16: its
    # cotangent must come back in ITS dtype (a 0-d marker carries it)
    return y, (yin, mean, rstd, w, b, seed, jnp.zeros((), res.dtype))


def _fdln_bwd(eps, p, interpret, saved, g):
    yin, mean, rstd, w, b, seed, res_like = saved
    d = yin.shape[-1]
    gf = g.astype(jnp.float32)
    yin_f = yin.astype(jnp.float32)
    xhat = (yin_f - mean) * rstd
    dw = jnp.sum(gf * xhat, axis=0).astype(w.dtype) if w is not None else None
    db = jnp.sum(gf, axis=0).astype(b.dtype) if b is not None else None
    gy = gf * w.astype(jnp.float32) if w is not None else gf
    # closed-form LN input gradient
    m1 = jnp.mean(gy, axis=-1, keepdims=True)
    m2 = jnp.mean(gy * xhat, axis=-1, keepdims=True)
    d_yin = (gy - m1 - xhat * m2) * rstd
    dx = d_yin.astype(yin.dtype)
    if p > 0.0:
        dx = _apply_dropout_grad(dx, seed, p, interpret)
    return dx, d_yin.astype(res_like.dtype), dw, db, None


_fdln.defvjp(_fdln_fwd, _fdln_bwd)


def fused_dropout_add_layer_norm(x, residual, weight=None, bias=None,
                                 dropout_p=0.0, epsilon=1e-5,
                                 dropout_seed=None, interpret=False):
    """y = LayerNorm(residual + dropout(x)) in one TPU pass.

    x/residual: (..., D) — flattened internally to (N, D) row tiles.
    dropout_seed: int32 (1, 1) array, required when dropout_p > 0.
    Takes the plain XLA composition off-TPU or when the rows do not tile
    (marked ``fused_dropout_norm.xla`` in the HLO).
    """
    p = float(dropout_p)
    shape = x.shape
    d = shape[-1]
    n = 1
    for s in shape[:-1]:
        n *= s
    usable = pallas_runs(interpret) and _row_block(n) is not None
    if p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    if not usable:
        return _xla_reference(x, residual, weight, bias, p, epsilon,
                              dropout_seed)
    seed = (dropout_seed if dropout_seed is not None
            else jnp.zeros((1, 1), jnp.int32))
    with took('fused_dropout_norm', 'pallas'):
        y = _fdln(x.reshape(n, d), residual.reshape(n, d), weight, bias,
                  seed, float(epsilon), p, interpret)
    return y.reshape(shape)


def _xla_reference(x, residual, weight, bias, p, epsilon, dropout_seed):
    with took('fused_dropout_norm', 'xla'):
        xx = x
        if p > 0.0:
            key = jax.random.fold_in(
                jax.random.PRNGKey(0),
                dropout_seed.reshape(()).astype(jnp.uint32))
            keep = keep_mask(key, 1.0 - p, x.shape, rows_first(x.ndim))
            xx = jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))
        yin = residual + xx
        mean = jnp.mean(yin.astype(jnp.float32), axis=-1, keepdims=True)
        xc = yin.astype(jnp.float32) - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + epsilon)
        if weight is not None:
            y = y * weight.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(x.dtype)


def dropout_add_layer_norm(x, residual, weight=None, bias=None, dropout_p=0.0,
                           epsilon=1e-5, dropout_key=None):
    """y = LayerNorm(residual + dropout(x)) as
    ``nn.functional.fused_dropout_add_layer_norm`` defines it; dropout_key:
    a jax key, required when 0 < dropout_p < 1.

    The one-pass kernel on the TPU for at least ``_MIN_ROWS`` rows of whole
    128-lane registers that tile; otherwise dropout and add as XLA ops and
    the layer norm by its own rule (``fused_layer_norm``: under the size rule
    it is still the norm kernel). Under ``fused_dropout_norm.pallas`` /
    ``fused_dropout_norm.xla``."""
    p = float(dropout_p)
    n = math.prod(x.shape[:-1])
    if (pallas_runs(False) and n >= _MIN_ROWS and x.shape[-1] % 128 == 0
            and _row_block(n) is not None and p < 1.0):
        seed = None
        if p > 0.0:
            seed = jax.random.randint(dropout_key, (1, 1), 0, 2**31 - 1
                                      ).astype(jnp.int32)
        return fused_dropout_add_layer_norm(x, residual, weight, bias, p,
                                            epsilon, seed)
    with took('fused_dropout_norm', 'xla'):
        y = x
        if p >= 1.0:
            y = jnp.zeros_like(x)
        elif p > 0.0:
            keep = keep_mask(dropout_key, 1.0 - p, x.shape, rows_first(x.ndim))
            y = jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))
        return fused_layer_norm(y + residual, weight, bias, epsilon)
