"""Sparse mixture of experts, one chip's share of it: pure functions over
jax arrays.

An expert layer of E experts is spread over the chips of an expert-parallel
group; this chip holds the experts `held = (lo, hi)`. The router scores a
token over ALL E experts (by a sigmoid with a correction bias and a scaling
factor, or by a plain softmax: the two published routers of the decoders
here) and picks its top k among all of them, so the
routing is the same on every chip of the group; this chip then computes, for
each token, the part of the sum that runs over the experts it holds:

    y_t = sum over e in top_k(t), lo <= e < hi of w_te * Expert_e(x_t)

and nothing that stands in for the other chips. Summed over the shares that
tile [0, E) the parts give the whole layer (`tests/test_kimi_linear.py` holds
that). No token is dropped whatever the imbalance, and the work follows the
rows held: the held assignments are sorted by expert into one row buffer,
each expert's rows padded to whole tiles of `row_tile` rows, and ONE grouped
product a weight stack (`kernels.grouped_matmul`) runs over the tiles that
hold rows. The rows move between token order and the buffer's expert order
inside two kernels that touch one row of the token side for each row HELD
(`kernels.row_permute`: `gather_rows` in, `combine_rows` back, each the
other's backward); of the XLA ops around the kernels only `silu(gate) * up`,
the casts and the sum of the two `d rows` still run over the whole buffer.
The buffer is static, at one of two sizes (`buffer_tiles`: twice
and four times an even router's share, the smaller where the step's rows fit
it); rows past the larger are the next round of the same product, in a loop
that runs once unless the routing sends this chip more than that.
"""
import collections
import functools

import jax
import jax.numpy as jnp

from ...kernels.grouped_matmul import grouped_matmul
from ...kernels.row_permute import combine_rows, gather_rows

__all__ = ['route_sigmoid_topk', 'route_softmax_topk', 'swiglu',
           'relu2_mlp', 'expert_share', 'row_tile', 'buffer_tiles', 'COUNTERS']

# what `expert_share` counts, in this order (`rows_moved`: the rows that
# `gather_rows` brought into the buffer, the valid rows of the tiles the
# rounds took: `assignments_held - dropped`, counted where the rows move)
COUNTERS = ('assignments_held', 'assignments', 'expert_rows_max',
            'expert_rows_mean', 'dropped', 'rows_computed', 'rounds',
            'rows_moved')


def route_sigmoid_topk(x, w_router, correction_bias, top_k, scaling):
    """Scores s = sigmoid(x W_r) over all experts, in float32; the top k by
    s + correction_bias; weights s_sel / sum(s_sel) * scaling.
    x (T, H) -> idx (T, k) int32, weights (T, k) float32."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + correction_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scaling
    return idx.astype(jnp.int32), weights


def route_softmax_topk(x, w_router, top_k):
    """Scores s = softmax(x W_r) over all experts, in float32; the top k by
    s; weights s_sel / sum(s_sel) (the picks' probabilities renormalised).
    No bias and no scaling. x (T, H) -> idx (T, k) int32, weights (T, k)
    float32."""
    s = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, idx = jax.lax.top_k(s, top_k)
    return idx.astype(jnp.int32), \
        picked / jnp.sum(picked, axis=-1, keepdims=True)


def swiglu(x, gate, up, down, dtype=None):
    """down(silu(gate x) * up x); weights (in, out). `dtype`: the products'
    operand type (None: as they come)."""
    if dtype is not None:
        x, gate, up, down = (t.astype(dtype) for t in (x, gate, up, down))
    h = jax.nn.silu(jnp.matmul(x, gate)) * jnp.matmul(x, up)
    return jnp.matmul(h, down)


def relu2_mlp(x, up, down, dtype=None):
    """down(relu(up x)^2): the ungated feed-forward; weights (in, out)."""
    if dtype is not None:
        x, up, down = (t.astype(dtype) for t in (x, up, down))
    return jnp.matmul(jnp.square(jax.nn.relu(jnp.matmul(x, up))), down)


def row_tile(tokens, top_k, experts):
    """Rows of a tile of the row buffer: half the rows an even router sends
    one expert, as a power of two from 8 to 256. An expert's last tile is
    then half empty on average: a tenth to a quarter more rows than held,
    whatever the shapes; 256 rows fill the MXU's passes."""
    even = max(tokens * top_k // experts, 1)
    return min(256, max(8, 1 << max(even.bit_length() - 2, 0)))


def buffer_tiles(tokens, top_k, held, experts, tile):
    """(small, large): tiles of the two static sizes of the row buffer,
    twice and four times the rows an even router sends the `held` experts
    (the rows and every expert's partly filled last tile); neither more
    than the worst routing fills: all tokens, each to as many held experts
    as it can pick. A step takes the smaller one that holds its padded rows
    in one round."""
    even = tokens * top_k * held / experts
    worst = -(-tokens * min(top_k, held) // tile) + held
    return tuple(min(-int(-room * even // tile), worst) for room in (2, 4))


# what a round of the routed product is built from, beside its operands
_Layout = collections.namedtuple(
    '_Layout', 'top_k tile sizes dtype interpret')


@functools.partial(jax.jit, static_argnums=(0, 1))
def _round(layout, tiles, r, order, counts, x, weights, *experts):
    """Round r of the routed product in a buffer of `tiles` tiles: tiles
    [r tiles, (r + 1) tiles) of the sorted, padded rows -> (their part of y
    (T, H) float32, the held assignments among them, float32). `order`: the
    assignments sorted by local expert, held first; `counts` (G,): rows of
    each held expert. The rows come into the buffer and go back to the
    tokens by `kernels.row_permute` (`tok`, `held`: the token of every row
    and the rows each tile holds, its first ones). `experts`: the held
    experts' matrices, (gate, up, down) of `down(silu(gate x) * up x)` or
    (up, down) of the ungated `down(relu(up x)^2)`; a width that is not
    whole 128-lane registers (1856) is laid on them behind zero columns of
    `gate` / `up` and zero rows of `down`, which is exact (silu(0) * 0 and
    relu(0)^2 are 0 and meet zero rows) and keeps the product on the
    kernels. A jit of its own: a step's expert layers make the same call at
    the same shapes (every layer, forward and in the backward rule), and it
    is traced once for all of them."""
    k, tile = layout.top_k, layout.tile
    T, G = x.shape[0], counts.shape[0]
    tiles_of = -(-counts // tile)
    ends = jnp.cumsum(tiles_of)
    starts = jnp.cumsum(counts) - counts
    t = r * tiles + jnp.arange(tiles, dtype=jnp.int32)
    active = jnp.clip(ends[-1] - r * tiles, 0, tiles)
    of = jnp.clip(jnp.searchsorted(ends, t, side='right'), 0, G - 1)
    rank = ((t - (ends - tiles_of)[of]) * tile)[:, None] \
        + jnp.arange(tile, dtype=jnp.int32)[None, :]
    valid = ((rank < counts[of][:, None])
             & (t < ends[-1])[:, None]).reshape(-1)
    a = order[jnp.clip(starts[of][:, None] + rank, 0, T * k - 1)] \
        .reshape(-1)
    tok = a // k
    # the rows a tile holds are its first ones: their count says which
    held = jnp.sum(valid.reshape(tiles, tile), axis=1, dtype=jnp.int32)
    if layout.dtype is not None:
        x = x.astype(layout.dtype)
    rows = gather_rows(x, tok, held, interpret=layout.interpret)
    product = functools.partial(
        grouped_matmul, tile_group=of, active=active.reshape(1),
        interpret=layout.interpret)
    *into, down = experts
    short = -down.shape[1] % 128
    if short:
        into = [jnp.pad(w, ((0, 0), (0, 0), (0, short))) for w in into]
        down = jnp.pad(down, ((0, 0), (0, short), (0, 0)))
    # graftlint: disable=GL006 — the number of matrices handed over (a
    # Python tuple's length, never a tracer): the gated form or the ungated
    if len(into) == 2:
        h = jax.nn.silu(product(rows, into[0])) * product(rows, into[1])
    else:
        h = jnp.square(jax.nn.relu(product(rows, into[0])))
    out = product(h, down, out_dtype=jnp.float32)
    y = combine_rows(out, weights.reshape(-1)[a], tok, held, T,
                     interpret=layout.interpret)
    return y, jnp.sum(held).astype(jnp.float32)


def _tiles_needed(layout, counts):
    return jnp.sum(-(-counts // layout.tile))


def _with_room(layout, counts, rounds):
    """`rounds(tiles, trips)` in the smaller buffer where the padded rows
    fit it (one round, by that very test), in the larger one for
    every heavier load (round 0 and, while rows are left, the next ones):
    the same product at two static sizes, chosen by what the step's routing
    needs (`silu(gate) * up`, the casts and the backward's sum of the two
    `d rows` still run over the whole buffer, so room costs time)."""
    small, large = layout.sizes
    needed = _tiles_needed(layout, counts)

    def all_of(tiles):
        return rounds(tiles, -(-needed // tiles))
    if small == large:
        return all_of(small)
    return jax.lax.cond(needed <= small, lambda: rounds(small, 1),
                        lambda: all_of(large))


def _rounds(layout, counts):
    """Rounds of the buffer the step takes: 1 unless the padded rows pass
    the larger one too."""
    small, large = layout.sizes
    needed = _tiles_needed(layout, counts)
    return jnp.maximum(
        -(-needed // jnp.where(needed <= small, small, large)), 1)


def _sum_of_rounds(one, trips):
    """one(0) + ... + one(trips - 1), at least one(0); `trips` a value (a
    loop) or the number 1 (no loop is traced). Round 0's number is an array
    as the loop's are, so that both are one call of `_round`'s jit."""
    first = one(jnp.int32(0))
    if isinstance(trips, int):
        return first
    return jax.lax.fori_loop(
        1, trips, lambda r, total: jax.tree.map(jnp.add, total, one(r)),
        first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(layout, order, counts, x, weights, *experts):
    """Every round's part of y, summed: round 0 always, the next ones while
    rows are left -> (y, the held assignments computed). jax cannot
    differentiate a loop whose trip count is a value, hence the rule below:
    it keeps the operands and nothing else, and the backward runs the same
    rounds, each by the vjp of `_round` (which computes the round again: a
    block that recomputes, as the cells' do, drops this function's forward
    from its second pass, so the count of products is the same)."""
    return _with_room(layout, counts, lambda tiles, trips: _sum_of_rounds(
        lambda r: _round(layout, tiles, r, order, counts, x, weights,
                         *experts), trips))


def _routed_fwd(layout, order, counts, *operands):
    return (_routed(layout, order, counts, *operands),
            (order, counts, operands))


def _routed_bwd(layout, res, cotangent):
    order, counts, operands = res

    def back(tiles, r):
        return jax.vjp(functools.partial(_round, layout, tiles, r, order,
                                         counts), *operands)[1](cotangent)
    return (None, None) + tuple(_with_room(
        layout, counts, lambda tiles, trips: _sum_of_rounds(
            functools.partial(back, tiles), trips)))


_routed.defvjp(_routed_fwd, _routed_bwd)


def expert_share(x, idx, weights, gate, up, down, held, experts, *,
                 tile=None, dtype=None, interpret=False):
    """This chip's part of the routed sum.

    x (T, H); idx, weights (T, k) from the router (global expert numbers);
    gate, up (G, H, F), down (G, F, H): the G = hi - lo experts held,
    `down(silu(gate x) * up x)`, or with `gate=None` the ungated
    `down(relu(up x)^2)`; held = (lo, hi) of the `experts` the router
    scores.
    -> (y (T, H) float32, counters (len(COUNTERS),) float32).
    `tile`: rows of a tile of the row buffer (None: `row_tile`); the buffer
    is one of `buffer_tiles`' two sizes. `dtype`: the products' operand type
    (None: as they come); `interpret`: the kernels' interpret mode, for
    tests.
    """
    T, k = idx.shape
    lo, hi = held
    G = hi - lo
    if up.shape[0] != G:
        raise ValueError('expert_share: holds %d experts, was told %r'
                         % (up.shape[0], held))
    tile = tile or row_tile(T, k, experts)
    layout = _Layout(k, tile, buffer_tiles(T, k, G, experts, tile), dtype,
                     interpret)
    is_held = (idx >= lo) & (idx < hi)
    local = jnp.where(is_held, idx - lo, G).reshape(-1)        # (T k,)
    counts = jnp.sum(local[:, None] == jnp.arange(G)[None, :], axis=0,
                     dtype=jnp.int32)                          # (G,)
    order = jnp.argsort(local, stable=True)           # held first, by expert
    y, computed = _routed(layout, order, counts, x, weights,
                          *((up, down) if gate is None else (gate, up, down)))
    f32 = jnp.float32
    n_held = jnp.sum(counts).astype(f32)
    counters = jnp.stack([
        n_held, jnp.asarray(T * k, f32), jnp.max(counts).astype(f32),
        n_held / G, n_held - computed,
        (_tiles_needed(layout, counts) * tile).astype(f32),
        _rounds(layout, counts).astype(f32), computed])
    return y, counters
