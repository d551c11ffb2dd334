"""The Mamba-2 layer (arXiv:2405.21060, as the Nemotron-H decoders have it,
arXiv:2504.03624): a selective state-space layer whose heads share B and C
by group (docs/STATE_SPACE.md). It takes a packed row's document numbers:
the convolution's taps and the state stop at document boundaries.
"""
import itertools
import math

import jax
import jax.numpy as jnp

from ...core.tensor import apply_op
from ...kernels.short_conv import short_conv
from ...kernels.ssd import ssd
from ..functional.norm import rms_norm_values
from ..initializer import Constant, Initializer, Normal, ParamAttr
from ..layer_base import Layer
from .linear_attention import _mm, compute_dtype

__all__ = ['Mamba2', 'gated_group_norm']


class _LogUniform(Initializer):
    """log(u), u uniform in [low, high): the rates A = exp(A_log) are drawn
    evenly over the range."""

    def __init__(self, low, high):
        self.low, self.high = low, high

    def generate(self, key, shape, dtype):
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, self.low, self.high)).astype(dtype)


class _StepBias(Initializer):
    """softplus^-1(dt), dt log-uniform in [low, high] and at least `floor`:
    the bias at which a zero projection gives such a step."""

    def __init__(self, low, high, floor):
        self.low, self.high, self.floor = low, high, floor

    def generate(self, key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(self.low), math.log(self.high)))
        dt = jnp.maximum(dt, self.floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def gated_group_norm(y, z, scale, groups, eps):
    """RMSNorm_groups(y * silu(z)) * scale: the gate BEFORE the norm, the
    mean of squares over each of the `groups` groups of channels. y, z
    (B, T, W) float32, scale (W,)."""
    B, T, W = y.shape
    y = (y * jax.nn.silu(z)).reshape(B, T, groups, W // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return y.reshape(B, T, W) * scale


class Mamba2(Layer):
    """`num_heads` heads of `head_dim` channels over `num_groups` groups
    with a state of `state_size`:

        [z | x | B | C | dt] = u W_in               (no bias)
        x, B, C = silu(causal_conv(.) + b_conv)     (depthwise, `conv_kernel`
                                                     taps, one tensor)
        dt = softplus(dt + dt_bias),  A = -exp(A_log)      (a head)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t
        out = (RMSNorm_groups(y * silu(z)) * w) W_out

    head h reads B and C of group h // (num_heads / num_groups); the gate is
    applied BEFORE the norm, whose mean of squares runs over each group's
    channels. The rule runs chunk-wise (`kernels.ssd`: the Pallas kernels on
    the TPU, off it the XLA form of `functional.ssd`); the convolution is
    `kernels.short_conv` with its bias, taken a part (x, B, C) at a time on
    that part's columns of W_in's product and of the taps, which is the same
    depthwise convolution and leaves nothing to cut out of a (B, T, 6144)
    array afterwards. Scopes: `ssm.proj` (both projections, dt's chain, the
    gated norm) with `ssm.conv` inside it, and `ssm.scan`."""

    def __init__(self, hidden_size, num_heads, head_dim, num_groups,
                 state_size, conv_kernel=4, conv_bias=True, chunk=128,
                 epsilon=1e-5, initializer_range=0.02, dt_min=0.001,
                 dt_max=0.1, dt_floor=1e-4):
        super().__init__()
        if num_heads % num_groups:
            raise ValueError('%d heads do not divide into %d groups'
                             % (num_heads, num_groups))
        self.sizes = (num_heads, head_dim, num_groups, state_size)
        self.chunk, self.epsilon = chunk, epsilon
        inner, bc = num_heads * head_dim, num_groups * state_size

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.in_proj = weight(hidden_size, 2 * inner + 2 * bc + num_heads)
        self.conv_weight = weight(conv_kernel, inner + 2 * bc)
        self.conv_bias = self.create_parameter(
            [inner + 2 * bc], default_initializer=Constant(0.0)) \
            if conv_bias else None
        self.dt_bias = self.create_parameter(
            [num_heads], default_initializer=_StepBias(dt_min, dt_max,
                                                       dt_floor))
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=_LogUniform(1.0, 16.0))
        self.D = self.create_parameter(
            [num_heads], default_initializer=Constant(1.0))
        self.norm = self.create_parameter(
            [inner], default_initializer=Constant(1.0))
        self.out_proj = weight(inner, hidden_size)

    def forward(self, x, segment_ids, pre_norm=None, recompute=False):
        """`pre_norm`: the block's `nn.RMSNorm`, applied to x first and
        inside whatever is recomputed. More rows than one are taken one at a
        time and recomputed in the backward pass (as `KimiDeltaAttention`
        takes them: the layer keeps a dozen arrays the size of x in
        float32); `recompute` does the same for a single row."""
        H, P, G, S = self.sizes
        inner, bc = H * P, G * S
        eps, chunk, biased = self.epsilon, self.chunk, \
            self.conv_bias is not None
        dtype = compute_dtype()
        norm_eps = pre_norm._epsilon if pre_norm is not None else None
        # the columns of W_in's product, and of the taps behind the gate's
        cuts = list(itertools.accumulate((0, inner, inner, bc, bc, H)))
        z_at, x_at, b_at, c_at, dt_at = (
            slice(lo, hi) for lo, hi in zip(cuts, cuts[1:]))

        def fn(x, seg, w_in, taps, dt_bias, a_log, skip, norm, w_out, *rest):
            f32 = jnp.float32
            bias, pre = (rest[0], rest[1:]) if biased else (None, rest)

            def rows(x, seg):
                B, T, _ = x.shape
                if pre:
                    x = rms_norm_values(x, pre[0], norm_eps)
                with jax.named_scope('ssm.proj'):
                    def short(at):
                        part = slice(at.start - inner, at.stop - inner)
                        with jax.named_scope('ssm.conv'):
                            return short_conv(
                                _mm(x, w_in[:, at], dtype), taps[:, part],
                                seg, bias=None if bias is None
                                else bias[part])
                    z = _mm(x, w_in[:, z_at], dtype).astype(f32)
                    xs, Bm, Cm = short(x_at), short(b_at), short(c_at)
                    dt = jax.nn.softplus(
                        _mm(x, w_in[:, dt_at], dtype).astype(f32) + dt_bias)
                    A = -jnp.exp(a_log.astype(f32))
                with jax.named_scope('ssm.scan'):
                    y = ssd(xs.reshape(B, T, H, P), dt, A,
                            Bm.reshape(B, T, G, S), Cm.reshape(B, T, G, S),
                            skip, seg, chunk=min(chunk, T), dtype=dtype)
                with jax.named_scope('ssm.proj'):
                    y = gated_group_norm(y.reshape(B, T, inner), z, norm, G,
                                         eps)
                    return _mm(y, w_out, dtype)

            if x.shape[0] == 1:
                return (jax.checkpoint(rows) if recompute else rows)(x, seg)
            one = jax.checkpoint(lambda xs: rows(xs[0][None], xs[1][None])[0])
            return jax.lax.map(one, (x, seg))

        return apply_op(fn, (x, segment_ids, self.in_proj, self.conv_weight,
                             self.dt_bias, self.A_log, self.D, self.norm,
                             self.out_proj)
                        + ((self.conv_bias,) if biased else ())
                        + ((pre_norm.weight,) if pre_norm is not None
                           else ()))
