"""Grouped matrix product over a row buffer laid out in tiles: the routed
experts' product (`nn/functional/moe.py::expert_share`), as a Pallas kernel
pair under one `jax.custom_vjp`.

    out[rows of tile i] = lhs[rows of tile i] @ rhs[tile_group[i]]    i < active

`lhs` is `(R, K)`: `R = tiles x tm` rows, every tile of `tm` rows one
group's (the caller pads a group's rows to whole tiles with zero rows);
`rhs` is `(G, K, N)`, one matrix a group; `tile_group (tiles,)` says whose
rows a tile holds and `active (1,)` how many tiles hold rows at all. Tiles
from `active` on cost no product and no operand read: their rows of `out`
are written as zeros. So the time follows the rows given, not the buffer.

The forward kernel walks the tiles in order with `tile_group` and `active`
as scalar prefetch: a tile's `rhs` block is its group's whole matrix, which
stays in VMEM over the group's tiles (one read of the weights a group), the
product one `(tm, K) x (K, N)` pass with float32 accumulation. The backward
is the same kernel on the transposed matrices (`d lhs = d out @ rhs^T`: the
contraction over `rhs`'s last axis, nothing is transposed in memory) and one
more kernel for `d rhs[g] = sum over g's tiles of lhs_tile^T @ d out_tile`,
which keeps a group's float32 `(K, N)` sum in VMEM over the group's tiles
and writes it once; a group with no tile among the active ones keeps the
zeros the result starts from.
Operands are cast to `lhs`'s type (the weights arrive in float32 and take
their gradient in float32, summed in float32 over ALL of a group's rows).

Off the TPU (unless a test asks for interpret mode), for a shape that does
not tile (`K`, `N` whole 128-lane registers, `tm` whole sublane tiles of
`lhs`'s type) and in a step whose operands are sharded over a mesh (a Mosaic
kernel cannot be partitioned, and these rows belong to no one device),
`grouped_matmul` takes `jax.lax.ragged_dot` with the tiles' rows as group
sizes: the XLA form, which is also what the tests hold the kernels to. Which
one a trace took is marked in the HLO (`_common.took`). On the TPU the
compiler makes a kernel of its own of `ragged_dot`, at a row tile of 512 it
picks itself and with custom calls that lose the `op_name` of the layer they
came from (a device trace could not put them under `moe.experts`), which is
why the path of the chip is this file's.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_runs, sharded_step, took

__all__ = ['grouped_matmul']

_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_VMEM_LIMIT = 96 << 20    # a group's (K, N) matrix twice, float32 in `d rhs`


def _last_active(i, active_ref):
    """Tile i, or the last tile that holds rows: a tile past it names the
    blocks already in VMEM, so nothing is fetched for it."""
    return jnp.minimum(i, jnp.maximum(active_ref[0] - 1, 0))


def _product_kernel(group_ref, active_ref, lhs_ref, rhs_ref, out_ref, *,
                    dims):
    i = pl.program_id(0)

    @pl.when(i < active_ref[0])
    def _():
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], (dims, ((), ())),
            preferred_element_type=_F32).astype(out_ref.dtype)

    @pl.when(i >= active_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _weight_grad_kernel(group_ref, active_ref, lhs_ref, dout_ref, zeros_ref,
                        out_ref):
    i = pl.program_id(0)
    first = (i == 0) | (group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])

    @pl.when(i < active_ref[0])
    def _():
        part = jax.lax.dot_general(lhs_ref[...], dout_ref[...],
                                   (_TN, ((), ())),
                                   preferred_element_type=_F32)

        @pl.when(first)
        def _():
            out_ref[0] = part

        @pl.when(jnp.logical_not(first))
        def _():
            out_ref[0] += part


# `_product` and `_weight_grad` are jits of their own: a step's expert layers
# make the same calls many times over (every layer, both buffer sizes, each
# round forward and again in its vjp), and a jit inside the step's trace is
# traced and lowered to a Mosaic kernel once for all of them. The scope is
# entered inside: the compiler names a custom call after its innermost
# scope.
@functools.partial(jax.jit, static_argnames=('dims', 'out_dtype',
                                             'interpret'))
def _product(lhs, rhs, tile_group, active, *, dims, out_dtype, interpret):
    """lhs (R, K) @ rhs[g] (K, N) (`dims` _NN), or @ rhs[g] (N, K)^T
    (`dims` _NT: the contraction over the matrices' last axis)."""
    tiles = tile_group.shape[0]
    tm, K = lhs.shape[0] // tiles, lhs.shape[1]
    N = rhs.shape[2 - dims[1][0]]
    with jax.named_scope('grouped_matmul.pallas'):
        return pl.pallas_call(
            functools.partial(_product_kernel, dims=dims),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(tiles,),
                in_specs=[
                    pl.BlockSpec((tm, K),
                                 lambda i, g, a: (_last_active(i, a), 0)),
                    pl.BlockSpec((1,) + rhs.shape[1:],
                                 lambda i, g, a: (g[_last_active(i, a)],
                                                  0, 0))],
                out_specs=pl.BlockSpec((tm, N), lambda i, g, a: (i, 0))),
            out_shape=jax.ShapeDtypeStruct((lhs.shape[0], N), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('arbitrary',),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(tile_group, active, lhs, rhs)


@functools.partial(jax.jit, static_argnames=('groups', 'interpret'))
def _weight_grad(lhs, dout, tile_group, active, *, groups, interpret):
    """-> (groups, K, N) float32: each group's lhs^T @ dout over its tiles;
    zeros for a group none of the active tiles belongs to (the walk never
    writes its block: the result starts as an array of zeros)."""
    tiles = tile_group.shape[0]
    tm, K, N = lhs.shape[0] // tiles, lhs.shape[1], dout.shape[1]

    def row_tile(width):
        return pl.BlockSpec((tm, width),
                            lambda i, g, a: (_last_active(i, a), 0))
    with jax.named_scope('grouped_matmul.pallas'):
        return pl.pallas_call(
            _weight_grad_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(tiles,),
                in_specs=[row_tile(K), row_tile(N),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(
                    (1, K, N),
                    lambda i, g, a: (g[_last_active(i, a)], 0, 0))),
            out_shape=jax.ShapeDtypeStruct((groups, K, N), _F32),
            input_output_aliases={4: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('arbitrary',),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(tile_group, active, lhs, dout, jnp.zeros((groups, K, N), _F32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(lhs, rhs, tile_group, active, out_dtype, interpret):
    return _grouped_fwd(lhs, rhs, tile_group, active, out_dtype,
                        interpret)[0]


def _grouped_fwd(lhs, rhs, tile_group, active, out_dtype, interpret):
    cast = rhs.astype(lhs.dtype)
    out = _product(lhs, cast, tile_group, active, dims=_NN,
                   out_dtype=out_dtype, interpret=interpret)
    # (an empty array remembers the type the gradient is handed over in)
    return out, (lhs, cast, jnp.zeros((0,), rhs.dtype), tile_group, active)


def _grouped_bwd(out_dtype, interpret, res, dout):
    lhs, cast, as_rhs, tile_group, active = res
    dout = dout.astype(lhs.dtype)
    dlhs = _product(dout, cast, tile_group, active, dims=_NT,
                    out_dtype=lhs.dtype, interpret=interpret)
    drhs = _weight_grad(lhs, dout, tile_group, active,
                        groups=cast.shape[0], interpret=interpret)
    return dlhs, drhs.astype(as_rhs.dtype), None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, tile_group, active, out_dtype=None,
                   interpret=False):
    """lhs (R, K) in `tile_group.shape[0]` tiles of equal rows, tile i the
    rows of group `tile_group[i]` (sorted, int32); rhs (G, K, N); active
    (1,) int32: the tiles that hold rows -> (R, N) in `out_dtype` (default
    lhs's), zeros from tile `active` on. Differentiable in lhs and rhs."""
    tiles = tile_group.shape[0]
    tm, sublanes = lhs.shape[0] // tiles, 32 // lhs.dtype.itemsize
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    if pallas_runs(interpret) and not sharded_step() \
            and tm % sublanes == 0 and rhs.shape[1] % 128 == 0 \
            and rhs.shape[2] % 128 == 0:
        with took('grouped_matmul', 'pallas'):
            return _grouped(lhs, rhs, tile_group, active, out_dtype,
                            interpret)
    with took('grouped_matmul', 'xla'):
        rows = jnp.zeros((rhs.shape[0],), jnp.int32).at[tile_group].add(
            jnp.where(jnp.arange(tiles) < active[0], tm, 0))
        return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), rows,
                                  preferred_element_type=out_dtype)
