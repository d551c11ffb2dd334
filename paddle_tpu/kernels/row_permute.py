"""The routed experts' rows between token order and expert order: the
permutation around the grouped products (`nn/functional/moe.py::_round`), as
two Pallas kernels that are each other's transposes.

    gather_rows:   rows[r] = x[tok[r]]                      r a row held
    combine_rows:  y[t]    = sum over rows r held, tok[r] = t, of w[r] out[r]

The row buffer `(R, H)` is `tiles` tiles of `tile` rows; tile i holds
`held[i]` rows, its first ones, and the tiles that hold any come first
(`held` is 0 from there on). `tok (R,)` names the token of every row held.
Both kernels walk the tiles that hold rows and touch one row of the token
side for each row HELD: a tile's padding rows and the tiles behind the last
one that holds rows cost no row move (the gather writes zeros there, the
combine reads nothing), so the time follows the rows, not the buffer.

How a row moves. A Mosaic kernel cannot ask for one row of a `(T, H)` array
in HBM by DMA: the array is tiled `(8, 128)` (bfloat16: two rows to a 32-bit
word), a row is `H / 128` pieces 4 KB apart, and the compiler refuses the
slice. So the token side is held WHOLE in VMEM, a column chunk at a time
(`_chunks`: 16384 tokens x 1152 float32 are 72 MiB of the 128), and a row
moves by one vector load and one vector store at a dynamic sublane, a few
cycles for a row of a chunk:
- `gather_rows`: grid (chunks, tiles); chunk c of `x` is copied into VMEM
  once, at its first tile; for each row held the loop copies row `tok[r]` of
  the chunk to row `r` of a staging block, and the tile's epilogue masks the
  padding rows. Rows move as 32-bit words: bfloat16 rows are packed two
  columns to a word by XLA (one pass over `(T, H)`, never the buffer) and
  unpacked a tile at a time. The same kernel is the combine's backward:
  given the rows' weights and the buffer `out` beside the float32 rows of `d
  y`, the epilogue writes `d out[r] = w[r] d y[tok[r]]` and the dots `d
  w[r] = <d y[tok[r]], out[r]>`.
- `combine_rows`: the same grid; a float32 `(T, chunk)` sum stays in VMEM
  over the chunk's tiles and is written once; for each row held the loop
  adds row `r` of the tile's block (times its weight, a tile at a time) to
  row `tok[r]` of the sum. No read-modify-write reaches HBM, and the order
  of the additions is the buffer's. The loop loads `_GROUP` rows of the sum
  before it stores them: a tile's rows held belong to DIFFERENT tokens (a
  tile is one expert's, and a token picks an expert once), which is what
  the caller promises.
`combine_rows` without weights is the backward of `gather_rows` (`d x[t]`:
the sum in float32, rounded once).

Off the TPU (unless a test asks for interpret mode), for shapes that do not
tile (whole 128-lane registers a chunk, whole sublane tiles a tile, a chunk
that fits VMEM: `_RESIDENT` is sized for a v5e's 128 MiB) and in a step
whose operands are sharded over a mesh, both take the XLA expressions they
replace, `where(valid, x[tok], 0)` and
`zeros.at[tok].add(out * w)`, which follow the whole buffer and are what the
tests hold the kernels to. Which one a trace took is marked in the HLO
(`_common.took('row_permute', ...)`).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_runs, sharded_step, took
from .grouped_matmul import _last_active

__all__ = ['gather_rows', 'combine_rows', 'rows_valid']

_F32 = jnp.float32
_RESIDENT = 72 << 20      # the token side's chunk that stays in VMEM
_VMEM_LIMIT = 110 << 20
_GROUP = 8                # rows a trip of the row loops moves


def rows_valid(held, tile):
    """(R,) bool: the rows of the buffer that hold a token."""
    return (jnp.arange(tile, dtype=jnp.int32)[None, :]
            < held[:, None]).reshape(-1)


def _chunks(tokens, width, dtype):
    """Column chunks of a `(tokens, width)` token side of `dtype` whose
    32-bit words (bfloat16: two columns to a word) fit `_RESIDENT`, each
    whole 128-lane registers; None where none does."""
    row = width * np.dtype(dtype).itemsize       # bytes; 512 a lane register
    return min((chunks for chunks in range(1, row // 512 + 1)
                if row % (512 * chunks) == 0
                and tokens * row // chunks <= _RESIDENT), default=None)


def _in_kernels(tokens, width, tile, dtype, interpret):
    """The rule of `grouped_matmul` (the buffer's tiles whole sublane tiles
    of its type), and a token side that fits."""
    dtype = jnp.dtype(dtype)
    return pallas_runs(interpret) and not sharded_step() \
        and dtype in (jnp.bfloat16, _F32) \
        and tile % (32 // dtype.itemsize) == 0 and tokens % 8 == 0 \
        and _chunks(tokens, width, dtype) is not None


def _pack(x, chunks):
    """(T, H) bfloat16 -> (T, H / 2) uint32: inside each of the `chunks`
    column chunks, column j in the low half of word j and column j + half a
    chunk in the high half (both halves whole lane registers)."""
    half = x.shape[1] // chunks // 2

    def halves(at):
        # (lane-aligned slices: a reshape would be a copy of its own)
        return jax.lax.bitcast_convert_type(jnp.concatenate(
            [x[:, (2 * c + at) * half:(2 * c + at + 1) * half]
             for c in range(chunks)], axis=1), jnp.uint16).astype(jnp.uint32)
    return halves(0) | (halves(1) << 16)


def _row_loop(count, move):
    """move(first row, rows) for the rows [0, count): whole groups, then the
    rest one by one."""
    whole = count // _GROUP

    def group(g, carry):
        move(g * _GROUP, _GROUP)
        return carry
    jax.lax.fori_loop(0, whole, group, 0)

    def one(r, carry):
        move(r, 1)
        return carry
    jax.lax.fori_loop(whole * _GROUP, count, one, 0)


def _gather_kernel(tok_ref, held_ref, x_hbm, *refs, tile, words, packed,
                   weighted):
    if weighted:
        scale_ref, other_ref, out_ref, dot_ref, chunk, stage, sem = refs
    else:
        out_ref, chunk, stage, sem = refs
    c, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        copy = pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(pl.multiple_of(c * words, 128), words)],
            chunk, sem.at[0])
        copy.start()
        copy.wait()
    count = held_ref[i]

    @pl.when(count == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        if weighted:
            dot_ref[...] = jnp.zeros_like(dot_ref)

    @pl.when(count > 0)
    def _():
        def move(first, rows):
            for u in range(rows):
                t = tok_ref[i * tile + first + u]
                stage[pl.ds(first + u, 1), :] = chunk[pl.ds(t, 1), :]
        _row_loop(count, move)
        valid = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < count
        got = stage[...]
        if packed:
            for at, bits in enumerate((got << 16,
                                       got & jnp.uint32(0xFFFF0000))):
                half = jax.lax.bitcast_convert_type(bits, _F32)
                out_ref[:, at * words:(at + 1) * words] = jnp.where(
                    valid, half, 0.0).astype(out_ref.dtype)
        elif weighted:
            dot = jnp.sum(got * other_ref[...], axis=1, keepdims=True)
            dot_ref[0] = jnp.where(valid, dot, 0.0)
            out_ref[...] = jnp.where(valid, got * scale_ref[...], 0.0)
        else:
            out_ref[...] = jnp.where(valid, got, 0.0)


# `_gather` and `_combine` are jits of their own, as `grouped_matmul`'s are:
# a step's expert layers make the same calls at the same shapes, and a jit
# inside the step's trace is lowered to a Mosaic kernel once for all of
# them. The scope is entered inside: the compiler names a custom call after
# its innermost scope.
@functools.partial(jax.jit, static_argnames=('interpret',))
def _gather(x, tok, held, weights=None, *, interpret):
    """x (T, H) -> rows (R, H) in x's type. `weights` = (scale (R,), other
    (R, H)), for float32 rows: the rows times `scale`, and beside them the
    rows' dots with `other`, (R,) float32, taken before `scale` goes in."""
    T, H = x.shape
    R, tiles = tok.shape[0], held.shape[0]
    tile = R // tiles
    packed, weighted = x.dtype == jnp.bfloat16, weights is not None
    chunks = _chunks(T, H, x.dtype)
    source = _pack(x, chunks) if packed else x
    words, wide = source.shape[1] // chunks, H // chunks
    block = pl.BlockSpec((tile, wide), lambda c, i, t, h: (i, c))
    operands, in_specs = [source], [pl.BlockSpec(memory_space=pl.ANY)]
    out_shape, out_specs = [jax.ShapeDtypeStruct((R, H), x.dtype)], [block]
    if weighted:
        scale, other = weights
        operands += [scale.reshape(R, 1).astype(_F32), other]
        in_specs += [pl.BlockSpec((tile, 1), lambda c, i, t, h: (i, 0)),
                     block]
        out_shape.append(jax.ShapeDtypeStruct((chunks, R, 1), _F32))
        out_specs.append(pl.BlockSpec((1, tile, 1),
                                      lambda c, i, t, h: (c, i, 0)))
    with jax.named_scope('row_permute.pallas'):
        got = pl.pallas_call(
            functools.partial(_gather_kernel, tile=tile, words=words,
                              packed=packed, weighted=weighted),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(chunks, tiles),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((T, words), source.dtype),
                                pltpu.VMEM((tile, words), source.dtype),
                                pltpu.SemaphoreType.DMA((1,))]),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('arbitrary', 'arbitrary'),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(tok, held, *operands)
    if weighted:
        return got[0], jnp.sum(got[1], axis=(0, 2))
    return got[0]


def _combine_kernel(tok_ref, held_ref, active_ref, *refs, tile, wide,
                    scaled):
    if scaled:
        out_ref, scale_ref, y_hbm, total, stage, sem = refs
    else:
        out_ref, y_hbm, total, stage, sem = refs
    c, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        total[...] = jnp.zeros_like(total)
    count = held_ref[i]

    @pl.when(count > 0)
    def _():
        block = out_ref[...].astype(_F32)
        stage[...] = block * scale_ref[...] if scaled else block

        def move(first, rows):
            at = [tok_ref[i * tile + first + u] for u in range(rows)]
            # every load before any store: the group's tokens differ
            sums = [total[pl.ds(t, 1), :] + stage[pl.ds(first + u, 1), :]
                    for u, t in enumerate(at)]
            for t, s in zip(at, sums):
                total[pl.ds(t, 1), :] = s
        _row_loop(count, move)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        copy = pltpu.make_async_copy(
            total, y_hbm.at[:, pl.ds(pl.multiple_of(c * wide, 128), wide)],
            sem.at[0])
        copy.start()
        copy.wait()


@functools.partial(jax.jit, static_argnames=('tokens', 'interpret'))
def _combine(out, tok, held, scale, *, tokens, interpret):
    """out (R, H), scale (R,) or None -> (tokens, H) float32."""
    R, H = out.shape
    tiles = held.shape[0]
    tile = R // tiles
    chunks = _chunks(tokens, H, _F32)
    wide = H // chunks
    scaled = scale is not None
    active = jnp.sum(held > 0, dtype=jnp.int32).reshape(1)
    operands = [out]
    in_specs = [pl.BlockSpec(
        (tile, wide), lambda c, i, t, h, a: (_last_active(i, a), c))]
    if scaled:
        operands.append(scale.reshape(R, 1).astype(_F32))
        in_specs.append(pl.BlockSpec(
            (tile, 1), lambda c, i, t, h, a: (_last_active(i, a), 0)))
    with jax.named_scope('row_permute.pallas'):
        return pl.pallas_call(
            functools.partial(_combine_kernel, tile=tile, wide=wide,
                              scaled=scaled),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(chunks, tiles),
                in_specs=in_specs,
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.VMEM((tokens, wide), _F32),
                                pltpu.VMEM((tile, wide), _F32),
                                pltpu.SemaphoreType.DMA((1,))]),
            out_shape=jax.ShapeDtypeStruct((tokens, H), _F32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('arbitrary', 'arbitrary'),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(tok, held, active, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gathered(x, tok, held, interpret):
    return _gather(x, tok, held, interpret=interpret)


def _gathered_fwd(x, tok, held, interpret):
    # (an empty array remembers the shape and type the gradient goes back in)
    return (_gathered(x, tok, held, interpret),
            (jnp.zeros((x.shape[0], 0), x.dtype), tok, held))


def _gathered_bwd(interpret, res, drows):
    like, tok, held = res
    dx = _combine(drows, tok, held, None, tokens=like.shape[0],
                  interpret=interpret)
    return dx.astype(like.dtype), None, None


_gathered.defvjp(_gathered_fwd, _gathered_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combined(out, scale, tok, held, tokens, interpret):
    return _combine(out, tok, held, scale, tokens=tokens,
                    interpret=interpret)


def _combined_fwd(out, scale, tok, held, tokens, interpret):
    return (_combined(out, scale, tok, held, tokens, interpret),
            (out, scale, tok, held))


def _combined_bwd(tokens, interpret, res, dy):
    out, scale, tok, held = res
    dout, dscale = _gather(dy.astype(_F32), tok, held, (scale, out),
                           interpret=interpret)
    return dout.astype(out.dtype), dscale.astype(scale.dtype), None, None


_combined.defvjp(_combined_fwd, _combined_bwd)


def gather_rows(x, tok, held, interpret=False):
    """x (T, H); tok (R,) int32: the token of every row held; held (tiles,)
    int32: the rows each tile of R / tiles rows holds, its first ones, 0
    from the first tile that holds none -> rows (R, H) in x's type, `x[tok]`
    where a row is held and zeros elsewhere. Differentiable in x."""
    tile = tok.shape[0] // held.shape[0]
    if _in_kernels(x.shape[0], x.shape[1], tile, x.dtype, interpret):
        with took('row_permute', 'pallas'):
            return _gathered(x, tok, held, interpret)
    if x.dtype == jnp.bfloat16 \
            and _in_kernels(x.shape[0], x.shape[1], tile, _F32, interpret):
        # a width whose bfloat16 pairs fill no whole registers (2688: 10.5
        # of them): the rows move as float32 words and are cast behind
        with took('row_permute', 'pallas'):
            return _gathered(x.astype(_F32), tok, held,
                             interpret).astype(x.dtype)
    with took('row_permute', 'xla'):
        return jnp.where(rows_valid(held, tile)[:, None], x[tok], 0)


def combine_rows(out, scale, tok, held, tokens, interpret=False):
    """out (R, H); scale (R,) float32: the weight of every row; tok, held as
    `gather_rows` takes them, and a tile's rows held belong to different
    tokens -> y (tokens, H) float32, `y[t]` the sum of `scale[r] out[r]`
    over the rows held with `tok[r] = t`. Differentiable in out and scale."""
    tile = tok.shape[0] // held.shape[0]
    if _in_kernels(tokens, out.shape[1], tile, out.dtype, interpret):
        with took('row_permute', 'pallas'):
            return _combined(out, scale, tok, held, tokens, interpret)
    with took('row_permute', 'xla'):
        w = jnp.where(rows_valid(held, tile), scale, 0.0)
        return jnp.zeros((tokens, out.shape[1]), _F32).at[tok].add(
            out * w[:, None])
