"""The short convolution in front of the delta rule, as a Pallas kernel pair
under one `jax.custom_vjp`: per tensor,

    l2norm_per_head(silu(causal_conv(y, w, seg)))

(the l2norm where the caller asks for it: Kimi Delta Attention's q and k,
not its v) in one pass over the projection's output `y`. XLA cannot fuse
across the shift of a tap, across the lane reduction of the norm or across
the reduction over `T` of the taps' gradient, and so streams each through
HBM; here a tile of rows stays in VMEM from the first tap to the norm.

Contract. `y` (B, T, W) in the dtype the projection wrote (bfloat16 under
amp), `w` (n, W) with the last tap on the current token, `seg` (B, T)
document numbers -> (B, T, W) float32, the view the delta rule reads. All
arithmetic is float32, a tap formed in `causal_conv`'s order.

- Tiles. The grid is (row, group of columns, tile of `T`); a block is a tile
  of rows by whole heads, worked through one head (one strip of 128-lane
  columns) at a time, so the norm is a lane reduction of registers.
- Halo. The taps of a tile's first rows reach the `n - 1` rows before it:
  the kernels read the 16 rows in front of the tile as a block of their own
  (16: one bfloat16 register tile; for the first tile any rows, dropped by
  the marks). The backward's transposed convolution reaches the rows AFTER
  the tile: it walks a row's tiles in reverse and carries each tap's masked
  gradient of the later tile's first rows in VMEM.
- Document marks. One int32 per position (B, T, 1), made by XLA from `seg`:
  bit `i - 1` says that the position `i` back exists and is of the same
  document. A tap whose bit is clear is dropped, as `causal_conv` drops it.
- Backward. Keeps nothing but its operands: it rebuilds a tile's
  convolution, SiLU and norm, writes the cotangent of `y` in `y`'s dtype and
  accumulates the taps' gradient over a row's tiles in a float32 block.

`short_conv` takes the kernels on the TPU (or in interpret mode) when `T`
tiles (a multiple of 16), `W` is whole 128-lane columns, a normed head is
whole columns too and the taps reach no further than 8 rows. A caller that
names its heads' size gets heads a third short of whole columns (a Gated
DeltaNet layer's 96 and 192) laid on them behind zero channels, which is
exact; heads shorter than that, and every other shape, take
today's XLA ops (`nn.functional.delta_rule.causal_conv`, which is also what
the tests hold the kernels to). Which one a trace took is marked in the HLO
(`_common.took`): `short_conv.pallas` / `short_conv.xla`.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import head_lanes, on_lanes, pallas_runs, spmd_kernel, took

__all__ = ['short_conv']

_F32 = jnp.float32
_EPS = 1e-6         # the l2norm's, as `nn.KimiDeltaAttention` had it
_HALO = 16          # rows of the block in front of a tile
_EDGE = 8           # of which the taps can reach the last 8: one register


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _rows_back(x, before, i):
    """Row r of the result is row r - i of `x`; its first i rows are the
    last i of `before` (8 rows)."""
    rolled = pltpu.roll(x, i, 0)
    top = jnp.where(_iota(before.shape, 0) < i, pltpu.roll(before, i, 0),
                    rolled[:_EDGE])
    return jnp.concatenate([top, rolled[_EDGE:]], axis=0)


def _rows_ahead(x, after, i):
    """Row r of the result is row r + i of `x`; its last i rows are the
    first i of `after` (8 rows)."""
    n = x.shape[0]
    rolled = pltpu.roll(x, n - i, 0)
    bottom = jnp.where(_iota(after.shape, 0) >= _EDGE - i,
                       pltpu.roll(after, _EDGE - i, 0), rolled[n - _EDGE:])
    return jnp.concatenate([rolled[:n - _EDGE], bottom], axis=0)


def _keeps(bits_ref, taps, strip):
    """The marks of a tile, per tap back an int32 array as wide as a strip:
    not zero where the tap is kept."""
    bits = jnp.broadcast_to(bits_ref[...], (bits_ref.shape[0], strip))
    return [bits & (1 << (i - 1)) for i in range(1, taps)]


def _strip(y_ref, halo_ref, w_ref, cols):
    """The columns `cols` of a block in float32: the tile, the 8 rows in
    front of it, the taps."""
    return (y_ref[:, cols].astype(_F32),
            halo_ref[:, cols].astype(_F32)[_HALO - _EDGE:],
            w_ref[:, cols].astype(_F32))


def _convolved(x, before, w, keep, biased=False):
    """-> the convolution of a strip, and the taps it summed: tap i is the
    rows i back, zero where the marks drop it. `causal_conv`'s order. Where
    `biased`, the last row of `w` is no tap but the bias, added last."""
    n = w.shape[0] - int(biased)
    taps = [x] + [jnp.where(keep[i - 1] != 0, _rows_back(x, before, i), 0.0)
                  for i in range(1, n)]
    u = taps[0] * w[n - 1:n]
    for i in range(1, n):
        u = u + taps[i] * w[n - 1 - i:n - i]
    if biased:
        u = u + w[n:n + 1]
    return u, taps


def _fwd_kernel(y_ref, halo_ref, w_ref, bits_ref, o_ref, *, strip, l2norm,
                biased=False):
    keep = _keeps(bits_ref, w_ref.shape[0] - int(biased), strip)
    for lo in range(0, y_ref.shape[1], strip):
        cols = slice(lo, lo + strip)
        s = jax.nn.silu(_convolved(*_strip(y_ref, halo_ref, w_ref, cols),
                                   keep, biased)[0])
        if l2norm:
            s = s * jax.lax.rsqrt(jnp.sum(s * s, -1, keepdims=True) + _EPS)
        o_ref[:, cols] = s


def _bwd_kernel(y_ref, halo_ref, w_ref, bits_ref, do_ref, dy_ref, dw_ref,
                carry_ref, *, strip, l2norm, biased=False):
    n = w_ref.shape[0] - int(biased)

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    keep = _keeps(bits_ref, n, strip)

    def one(x, before, w, do, after):
        """-> dy, the n rows of dw, and what the tile before this one needs
        of this one: each tap's masked gradient, first 8 rows."""
        u, taps = _convolved(x, before, w, keep, biased)
        sig = jax.nn.sigmoid(u)
        ds = do
        if l2norm:
            s = u * sig
            r = jax.lax.rsqrt(jnp.sum(s * s, -1, keepdims=True) + _EPS)
            ds = r * do - s * (r * r * r
                               * jnp.sum(do * s, -1, keepdims=True))
        du = ds * (sig * (1.0 + u * (1.0 - sig)))
        dy = du * w[n - 1:n]
        dw = [jnp.sum(du * taps[n - 1 - j], 0, keepdims=True)
              for j in range(n)]
        if biased:
            dw.append(jnp.sum(du, 0, keepdims=True))
        first = []
        for i in range(1, n):
            z = jnp.where(keep[i - 1] != 0, du, 0.0)
            dy = dy + _rows_ahead(z, after[i - 1], i) * w[n - 1 - i:n - i]
            first.append(z[:_EDGE])
        return dy, jnp.concatenate(dw, 0), jnp.stack(first)

    for lo in range(0, y_ref.shape[1], strip):
        cols = slice(lo, lo + strip)
        dy, dw, first = one(*_strip(y_ref, halo_ref, w_ref, cols),
                            do_ref[:, cols], carry_ref[:, :, cols])
        dy_ref[:, cols] = dy.astype(dy_ref.dtype)
        dw_ref[:, cols] += dw
        carry_ref[:, :, cols] = first


def _row_tile(T):
    """The tile of `T`: 256 rows of bfloat16 by 2048 columns are 1 MB in and
    2 MB out a step, and the backward's blocks, double-buffered, 9 MB: inside
    the 16 MiB a kernel gets unasked. None where `T` has no tile of whole
    register tiles."""
    return next((t for t in (256, 128, 64, 32, 16) if T % t == 0), None)


def _col_block(W, strip):
    strips = W // strip
    return strip * max(k for k in range(1, strips + 1)
                       if strips % k == 0 and (k == 1 or strip * k <= 2048))


def _specs(tt, wb, taps, tile_of):
    """BlockSpecs over the grid (row, column group, tile step); `tile_of`
    maps the step to the tile (the backward runs them in reverse)."""
    def rows(n, width):
        return pl.BlockSpec((None, n, width),
                            lambda b, g, t: (b, tile_of(t), g))
    halo = pl.BlockSpec(
        (None, _HALO, wb), lambda b, g, t: (
            b, jnp.maximum(tile_of(t) * (tt // _HALO) - 1, 0), g))
    marks = pl.BlockSpec((None, tt, 1), lambda b, g, t: (b, tile_of(t), 0))
    return (rows(tt, wb), halo, pl.BlockSpec((taps, wb),
                                             lambda b, g, t: (0, g)), marks)


# `_forward` and `_backward` are jits of their own: a net's KDA layers make
# the same calls for q, k and v, and a jit inside the step's trace is traced
# and lowered once for all of them (as `kernels/delta_rule.py`'s are). The
# scope is entered again inside: the compiler names a custom call after its
# innermost scope.
_STATIC = ('strip', 'l2norm', 'biased', 'interpret')


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(y, w, bits, *, strip, l2norm, biased, interpret):
    B, T, W = y.shape
    tt, wb = _row_tile(T), _col_block(W, strip)
    tile, halo, taps, marks = _specs(tt, wb, w.shape[0], lambda t: t)
    with jax.named_scope('short_conv.pallas'):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, strip=strip, l2norm=l2norm,
                              biased=biased),
            grid=(B, W // wb, T // tt),
            in_specs=[tile, halo, taps, marks], out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(y.shape, _F32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'parallel', 'parallel')),
            interpret=interpret,
        )(y, y, w, bits)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(y, w, bits, do, *, strip, l2norm, biased, interpret):
    """-> dy in y's dtype, dw (B, n, W) float32: a row's share of it (n:
    the rows of `w`, the bias's among them where there is one)."""
    B, T, W = y.shape
    n, tt, wb = w.shape[0], _row_tile(T), _col_block(W, strip)
    N = T // tt
    tile, halo, taps, marks = _specs(tt, wb, n, lambda t: N - 1 - t)
    per_row = pl.BlockSpec((None, n, wb), lambda b, g, t: (b, 0, g))
    with jax.named_scope('short_conv.pallas'):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, strip=strip, l2norm=l2norm,
                              biased=biased),
            grid=(B, W // wb, N),
            in_specs=[tile, halo, taps, marks, tile],
            out_specs=[tile, per_row],
            out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                       jax.ShapeDtypeStruct((B, n, W), _F32)],
            scratch_shapes=[pltpu.VMEM((n - 1 - biased, _EDGE, wb), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'parallel', 'arbitrary')),
            interpret=interpret,
        )(y, y, w, bits, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv(y, w, bits, static):
    return _forward(y, w, bits, **dict(static))


def _conv_fwd(y, w, bits, static):
    return _forward(y, w, bits, **dict(static)), (y, w, bits)


def _conv_bwd(static, res, do):
    y, w, bits = res
    dy, dw = _backward(y, w, bits, do, **dict(static))
    return dy, jnp.sum(dw, axis=0).astype(w.dtype), None


_conv.defvjp(_conv_fwd, _conv_bwd)


def _marks(seg, taps):
    """(B, T) document numbers -> (B, T, 1) int32: bit i - 1 is set where
    the position i back exists and is of the same document."""
    at = jnp.arange(seg.shape[1], dtype=jnp.int32)
    bits = jnp.zeros(seg.shape, jnp.int32)
    for back in range(1, taps):
        same = (jnp.pad(seg, ((0, 0), (back, 0)))[:, :-back] == seg) \
            & (at >= back)
        bits = bits | (same.astype(jnp.int32) << (back - 1))
    return bits[..., None]


def _xla(y, w, seg, head_dim, bias=None):
    from ..nn.functional.delta_rule import causal_conv
    B, T, W = y.shape
    x = causal_conv(y.astype(_F32), w, seg)
    x = jax.nn.silu(x if bias is None else x + bias)
    if head_dim is not None:
        x = x.reshape(B, T, W // head_dim, head_dim)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _EPS)
    return x.reshape(B, T, W)


def short_conv(y, w, seg, head_dim=None, interpret=False, norm=True,
               bias=None):
    """silu(causal_conv(y, w, seg)), with `bias` (W,) added before the SiLU
    where one is given (the kernels take it as one more row of `w`, and its
    gradient as that row's), and, where `head_dim` is given and
    `norm` is left on, each head of `head_dim` channels scaled to unit
    length (`x * rsqrt(sum(x*x) + 1e-6)`): y (B, T, W) of any float dtype,
    w (n, W), seg (B, T) -> (B, T, W) float32. A `head_dim` with `norm` off
    only says how the width divides into heads. Heads a third short of whole
    128-lane registers (`_common.head_lanes`: 96, 192) are laid on them
    behind zero channels, which the convolution, SiLU and the norm's sum of
    squares leave zero, and dropped from the result. The kernels or the XLA
    form as the module's docstring says; either way under a
    `short_conv.pallas` / `.xla` scope."""
    B, T, W = y.shape
    taps = w.shape[0]
    normed = head_dim is not None and norm
    lanes = 128 if head_dim is None else head_lanes(head_dim)
    strip = lanes if normed else 128
    if not (pallas_runs(interpret) and _row_tile(T) is not None
            and lanes is not None and W % (head_dim or 128) == 0
            and 2 <= taps <= _EDGE + 1):
        with took('short_conv', 'xla'):
            return _xla(y, w, seg, head_dim if normed else None, bias)
    if bias is not None:
        w = jnp.concatenate([w, bias[None].astype(w.dtype)], axis=0)
    rows = w.shape[0]               # the taps, and the bias behind them
    heads = W // (head_dim or 128)
    wide = heads * lanes            # the width with every head on its lanes

    def laid(x):                    # (..., W) -> (..., wide / strip, strip)
        x = on_lanes(x.reshape(x.shape[:-1] + (heads, -1)), lanes)
        return x.reshape(x.shape[:-2] + (wide // strip, strip))

    def call(y, w, bits, shard):
        b, _, h, _ = y.shape                # this device's rows and strips
        return _conv(y.reshape(b, T, h * strip), w.reshape(rows, h * strip),
                     bits, tuple(zip(_STATIC, (
                         strip, normed, bias is not None, interpret)))
                     ).reshape(b, T, h, strip)

    dims = ('b', None, 'h', None)
    with took('short_conv', 'pallas'):
        out = spmd_kernel(
            call, [dims, (None, 'h', None), ('b', None, None)], [dims],
            {'b': 'batch', 'h': 'heads'}, scope='short_conv.pallas')(
                laid(y), laid(w), _marks(seg, taps))
        if wide != W:
            out = out.reshape(B, T, heads, lanes)[..., :head_dim]
        return out.reshape(B, T, W)
