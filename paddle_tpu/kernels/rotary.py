"""The rotary position encoding as one pass over a projection's output: a
Pallas kernel under a `jax.custom_vjp` that reads q (or k) once in the
layout the projection wrote, `(B, T, H * d)` in the compute dtype, turns a
tile in float32 in VMEM and writes it once, rounded once, in the layout the
flash kernels read, `(B, H, T, d)`.

A rotation is linear and elementwise with one partner lane,

    y = x * cos + partner(x) * sin

with the sign of the sine folded into the table. Which lane the partner is
comes from the model's mathematics and is static:

- `halves` (`nn.GroupedQueryAttention`; `rotate_halves`): the lane d / 2
  away, one roll of the head's lanes; or, where only the first `turned`
  channels of a head turn (a `partial_rotary_factor`), the half-split form
  INSIDE them: lane j < turned / 2 pairs with j + turned / 2, rolls by
  turned / 2 either way and a select on the lane index. No table of the
  whole head says this: the pairing differs, not the angle;
- `pairs` (`nn.LatentAttention`; `rotate_pairs`): the other lane of the pair
  (2j, 2j + 1), rolls by one lane either way and a select on lane parity.

- Tables. `cos` and `sin` with its sign, float32 `(B, T, P)`, are made by XLA
  once a call from the positions (which restart at each document of a packed
  row) and the layer's frequencies; `jnp.cos` does not belong in the kernel.
  P, the period, is the fewest whole heads that fill whole 128-lane
  registers (128 for heads of 128; 384, two heads, for heads of 192). Lanes
  that are not turned carry cos 1 and sin 0, so a head of 128 + 64 of which
  the last 64 turn is one uniform tile.
- Grid (row, token tile, block of periods), the periods innermost: a token
  tile's tables are fetched once for all its heads.
- Backward. The transpose of the rotation is the rotation by the negated
  sine (both partners share an angle and their signs are opposite): the SAME
  kernel with the two BlockSpecs exchanged, reading the `(B, H, T, d)`
  cotangent and writing `(B, T, H * d)`. Nothing is kept but the tables.

`rotary_halves` / `rotary_pairs` take x as `(B, T, H, d)`, the projection's
output seen by head, and take the kernel on the TPU (or in interpret mode)
when T tiles (a multiple of 16) and the heads fill whole registers;
off the TPU, in a step sharded over a mesh (`_common.sharded_step`) and for
every other shape they take the XLA form, `rotate_halves` / `rotate_pairs`
below and a `swapaxes`, which is also what the tests hold the kernel to.
Which one a trace took is marked in the HLO (`_common.took`):
`rotary.pallas` / `rotary.xla`.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_runs, sharded_step, took

__all__ = ['rotary_halves', 'rotary_pairs', 'rotate_halves', 'rotate_pairs',
           'rope_inv_freq', 'yarn_inv_freq']

_F32 = jnp.float32
# a block is at most 512 tokens by 1024 lanes, 1 MB of bfloat16 in and 1 MB
# out a step; on the chip 256 to 1024 tokens by 512 to 2048 lanes all ran the
# same to 1% (PERF.md, Findings PR 44): the pass is bound by its bytes
_BLOCK_LANES = 1024


def rotate_pairs(x, positions, theta):
    """Rotary position encoding of the last axis of x (B, T, ..., d): each
    adjacent pair (2j, 2j + 1) turned by the angle p * theta^(-2j / d), p the
    position `positions` (B, T) gives, in float32 -> float32."""
    d = x.shape[-1]
    x = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    rate = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * rate      # (B, T, d/2)
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 4)
                          + angle.shape[2:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0], x[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape[:-2] + (d,))


def rope_inv_freq(theta, dim):
    """(dim / 2,) float64: the plain rotary table's inverse frequencies,
    theta^(-2j / dim)."""
    return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


def yarn_inv_freq(theta, dim, factor, original_max_position_embeddings,
                  beta_fast=32.0, beta_slow=1.0):
    """-> ((dim / 2,) float64 inverse frequencies, low, high): YaRN
    (arXiv:2309.00071 section 3.2, as the public `rope_type: yarn`
    computes it). Dimension j turns c(b) = dim ln(original / (2 pi b)) /
    (2 ln theta) at b rotations over the original context; the dimensions
    under low = floor(c(beta_fast)) keep their rate, those over high =
    ceil(c(beta_slow)) are slowed `factor` times, a linear ramp between."""
    def turns(b):
        return dim * math.log(original_max_position_embeddings
                              / (2 * math.pi * b)) / (2 * math.log(theta))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    plain = rope_inv_freq(theta, dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp), low, high


def rotate_halves(x, cos, sin, turned=None):
    """Rotary position encoding of the last axis of x (B, T, H, d) in the
    half-split form: x * cos + [-x2, x1] * sin with x = [x1, x2] the two
    halves; cos, sin (B, T, 1, d) float32, each of its d / 2 angles repeated
    over the halves. x keeps its layout (the half turn is a roll of the
    lanes, not a reshape to pairs); float32 -> float32. With `turned` < d
    the two halves are those of the first `turned` channels (x1 = x[:turned
    / 2], x2 = x[turned / 2:turned]) and the channels behind them, whose
    cos is 1 and sin 0, pass."""
    d = x.shape[-1]
    half = (turned or d) // 2
    x = x.astype(jnp.float32)
    lane = jnp.arange(d)
    sign = jnp.where(lane < half, -1.0, 1.0)
    partner = jnp.roll(x, half, axis=-1)
    if 2 * half != d:
        partner = jnp.where(lane < half, jnp.roll(x, -half, axis=-1), partner)
    return x * cos + partner * (sin * sign)


def _turned(x, cos, sin, form, d, turned):
    """One period (tt, P) float32, turned."""
    lanes = x.shape[1]
    if form == 'halves' and turned == d:
        partner = pltpu.roll(x, lanes // 2, 1)
    elif form == 'halves':
        low = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) % d \
            < turned // 2
        partner = jnp.where(low, pltpu.roll(x, lanes - turned // 2, 1),
                            pltpu.roll(x, turned // 2, 1))
    else:
        even = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) % 2 == 0
        partner = jnp.where(even, pltpu.roll(x, lanes - 1, 1),
                            pltpu.roll(x, 1, 1))
    return x * cos + partner * sin


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, form, d, turned):
    """One of the two refs is a tile of tokens by whole periods (tt, n * P),
    the other the same heads one by one (n * P / d, tt, d); either may be
    the input."""
    cos, sin = cos_ref[...], sin_ref[...]
    P = cos.shape[1]
    by_head = len(x_ref.shape) == 3
    flat = o_ref if by_head else x_ref
    for j in range(flat.shape[1] // P):
        heads = range(j * (P // d), (j + 1) * (P // d))
        if by_head:
            x = jnp.concatenate([x_ref[h] for h in heads], axis=1)
        else:
            x = x_ref[:, j * P:(j + 1) * P]
        y = _turned(x.astype(_F32), cos, sin, form, d,
                    turned).astype(o_ref.dtype)
        if by_head:
            o_ref[:, j * P:(j + 1) * P] = y
        else:
            for g, h in enumerate(heads):
                o_ref[h] = y[:, g * d:(g + 1) * d]


def _period(d):
    """The lanes of the fewest whole heads of `d` that fill whole
    registers."""
    return d * 128 // math.gcd(d, 128)


def _tiles(T, width, d):
    """-> (token tile, periods a block), or None where the shape does not
    tile: T in whole bfloat16 register tiles, the width in whole periods."""
    P = _period(d)
    tt = next((t for t in (512, 256, 128, 64, 32, 16) if T % t == 0), None)
    if tt is None or width % P:
        return None
    periods = width // P
    return tt, max(n for n in range(1, periods + 1)
                   if periods % n == 0 and (n == 1 or n * P <= _BLOCK_LANES))


# a jit of its own: a net's layers make the same call, and a jit inside the
# step's trace is traced and lowered once for all of them (as
# `kernels/short_conv.py`'s are). The scope is entered again inside: the
# compiler names a custom call after its innermost scope.
@functools.partial(jax.jit, static_argnames=('form', 'heads', 'interpret',
                                             'turned'))
def _call(x, cos, sin, *, form, heads, interpret, turned=None):
    """x (B, T, heads * d) -> (B, heads, T, d), or back where x has four
    axes. `turned`: the channels in front of a head that the `halves` form
    turns (None: the whole head)."""
    to_heads = x.ndim == 3
    if to_heads:
        B, T, width = x.shape
        d = width // heads
    else:
        B, _, T, d = x.shape
        width = heads * d
    P = cos.shape[2]
    tt, n = _tiles(T, width, d)
    flat = pl.BlockSpec((None, tt, n * P), lambda b, t, j: (b, t, j))
    by_head = pl.BlockSpec((None, n * P // d, tt, d),
                           lambda b, t, j: (b, j, t, 0))
    table = pl.BlockSpec((None, tt, P), lambda b, t, j: (b, t, 0))
    with jax.named_scope('rotary.pallas'):
        return pl.pallas_call(
            functools.partial(_kernel, form=form, d=d, turned=turned or d),
            grid=(B, T // tt, width // (n * P)),
            in_specs=[flat if to_heads else by_head, table, table],
            out_specs=by_head if to_heads else flat,
            out_shape=jax.ShapeDtypeStruct(
                (B, heads, T, d) if to_heads else (B, T, width), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'parallel', 'parallel')),
            interpret=interpret,
        )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotary(x, cos, sin, static):
    return _call(x, cos, sin, **dict(static))


def _rotary_fwd(x, cos, sin, static):
    return _call(x, cos, sin, **dict(static)), (cos, sin)


def _rotary_bwd(static, tables, dy):
    cos, sin = tables
    return _call(dy, cos, -sin, **dict(static)), None, None


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def _site(x, form, tables, xla, interpret, turned=None):
    """The one decision, for x (B, T, H, d): the kernel on `tables()`'s cos
    and sin of one head (B, T, d), the sign folded into the sine here, or
    `xla(x)` and a `swapaxes`. `turned`: the channels in front of a head
    that the `halves` form turns."""
    B, T, heads, d = x.shape
    # (a half turn rolls a head's own lanes: heads of whole registers)
    if not (pallas_runs(interpret) and not sharded_step()
            and (form == 'pairs' or d % 128 == 0)
            and _tiles(T, heads * d, d) is not None):
        with took('rotary', 'xla'):
            return jnp.swapaxes(xla(x), 1, 2)
    with took('rotary', 'pallas'):
        cos, sin = tables()
        lane = jnp.arange(d)
        sin = jnp.where(lane < turned // 2 if form == 'halves'
                        else lane % 2 == 0, -sin, sin)
        cos, sin = (jnp.tile(t, (1, 1, _period(d) // d)) for t in (cos, sin))
        return _rotary(x.reshape(B, T, heads * d), cos, sin, (
            ('form', form), ('heads', heads), ('interpret', interpret),
            ('turned', turned)))


def rotary_halves(x, positions, inv_freq, factor=1.0, interpret=False):
    """`rotate_halves` of x (B, T, H, d) at the positions (B, T) and the
    table `inv_freq`, cos and sin times `factor` -> (B, H, T, d) in x's
    dtype, the turn in float32. A table of d / 2 rates turns the whole head;
    a shorter one, of n, the head's first 2 n channels in the half-split
    form inside them, and the channels behind pass (no factor on them)."""
    d = x.shape[-1]
    turned = 2 * len(inv_freq)
    if turned > d:
        raise ValueError('a table of %d rates turns no %d-wide head'
                         % (len(inv_freq), d))

    def tables():
        angle = positions.astype(_F32)[..., None] * inv_freq
        angle = jnp.concatenate([angle, angle], -1)
        cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)
        if turned == d:
            return cos, sin
        behind = [(0, 0), (0, 0), (0, d - turned)]
        return (jnp.pad(cos, behind, constant_values=1.0),
                jnp.pad(sin, behind))

    def xla(x):
        cos, sin = tables()
        return rotate_halves(x, cos[:, :, None], sin[:, :, None],
                             turned).astype(x.dtype)

    return _site(x, 'halves', tables, xla, interpret, turned)


def rotary_pairs(x, positions, theta, turned, interpret=False):
    """`rotate_pairs` at base `theta` over the last `turned` channels of
    every head of x (B, T, H, d), at the positions (B, T); the channels in
    front are carried as they are -> (B, H, T, d) in x's dtype, the turn in
    float32."""
    kept = x.shape[-1] - turned

    def tables():
        # `rotate_pairs`' own angles, each on both lanes of its pair
        rate = theta ** (-jnp.arange(0, turned, 2, dtype=_F32) / turned)
        angle = jnp.repeat(positions.astype(_F32)[..., None] * rate, 2, -1)
        front = [(0, 0), (0, 0), (kept, 0)]
        return (jnp.pad(jnp.cos(angle), front, constant_values=1.0),
                jnp.pad(jnp.sin(angle), front))

    def xla(x):
        return jnp.concatenate([
            x[..., :kept],
            rotate_pairs(x[..., kept:], positions, theta).astype(x.dtype)],
            axis=-1)

    return _site(x, 'pairs', tables, xla, interpret)
