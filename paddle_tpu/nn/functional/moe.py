"""Sparse mixture of experts, one chip's share of it: pure functions over
jax arrays.

An expert layer of E experts is spread over the chips of an expert-parallel
group; this chip holds the experts `held = (lo, hi)`. The router scores a
token over ALL E experts and picks its top k among all of them, so the
routing is the same on every chip of the group; this chip then computes, for
each token, the part of the sum that runs over the experts it holds:

    y_t = sum over e in top_k(t), lo <= e < hi of w_te * Expert_e(x_t)

and nothing that stands in for the other chips. Summed over the shares that
tile [0, E) the parts give the whole layer (`tests/test_kimi_linear.py` holds
that). No token is dropped whatever the imbalance: the assignments are sorted
by expert and laid out in blocks of `block` rows, each block one expert's
(grouped matrix products: one batched product over the blocks); where the
blocks a batch needs pass the static `blocks`, the step takes the dense form
instead (every held expert over every token, masked), which is exact at any
imbalance and G times the work.
"""
import math

import jax
import jax.numpy as jnp

__all__ = ['route_sigmoid_topk', 'swiglu', 'expert_share', 'share_blocks',
           'COUNTERS']

# what `expert_share` counts, in this order
COUNTERS = ('assignments_held', 'assignments', 'expert_rows_max',
            'expert_rows_mean', 'dropped')


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


def swiglu(x, gate, up, down, dtype=None):
    """down(silu(gate x) * up x); weights (in, out). `dtype`: the products'
    operand type (None: as they come)."""
    if dtype is not None:
        x, gate, up, down = (t.astype(dtype) for t in (x, gate, up, down))
    h = jax.nn.silu(jnp.matmul(x, gate)) * jnp.matmul(x, up)
    return jnp.matmul(h, down)


def share_blocks(tokens, top_k, held, experts, block, capacity_factor):
    """The static number of row blocks of the grouped form: room for
    `capacity_factor` times the rows an even router sends, and one partly
    filled block for each held expert."""
    expected = tokens * top_k * held / experts
    return int(math.ceil(capacity_factor * expected / block)) + held


def expert_share(x, idx, weights, gate, up, down, held, experts, *,
                 block=512, capacity_factor=4.0, dtype=None):
    """This chip's part of the routed sum.

    x (T, H); idx, weights (T, k) from the router (global expert numbers);
    gate, up (G, H, F), down (G, F, H): the G = hi - lo experts held;
    held = (lo, hi) of the `experts` the router scores.
    -> (y (T, H) float32, counters (len(COUNTERS),) float32).
    `capacity_factor` is the tests' (a small one forces the dense form); the
    layer has no option for it.
    """
    T, H = x.shape
    k = idx.shape[1]
    lo, hi = held
    G = hi - lo
    if gate.shape[0] != G:
        raise ValueError('expert_share: holds %d experts, was told %r'
                         % (gate.shape[0], held))
    is_held = (idx >= lo) & (idx < hi)
    local = jnp.where(is_held, idx - lo, G).reshape(-1)        # (T k,)
    counts = jnp.sum(local[:, None] == jnp.arange(G)[None, :], axis=0,
                     dtype=jnp.int32)                          # (G,)
    n_held = jnp.sum(counts)
    block = min(block, T)
    # never more blocks than the worst routing fills: all tokens, each to
    # as many held experts as it can pick
    nb = min(share_blocks(T, k, G, experts, block, capacity_factor),
             -(-T * min(k, G) // block) + G)
    padded = -(-counts // block) * block
    ends = jnp.cumsum(padded)
    needed = ends[-1] // block

    def grouped():
        order = jnp.argsort(local, stable=True)       # held first, by expert
        starts = jnp.cumsum(counts) - counts
        first = jnp.arange(nb, dtype=jnp.int32) * block
        of = jnp.clip(jnp.searchsorted(ends, first, side='right'), 0, G - 1)
        rank = first[:, None] + jnp.arange(block)[None, :] \
            - (ends - padded)[of][:, None]
        valid = (rank < counts[of][:, None]) \
            & (jnp.arange(nb)[:, None] < needed)
        a = order[jnp.clip(starts[of][:, None] + rank, 0, T * k - 1)]
        tok = a // k                                           # (nb, block)
        w = jnp.where(valid, weights.reshape(-1)[a], 0.0)
        cast = (lambda t: t) if dtype is None else (lambda t: t.astype(dtype))
        rows = jnp.where(valid[..., None], cast(x)[tok], 0)
        h = jax.nn.silu(jnp.einsum('bmh,bhf->bmf', rows, cast(gate)[of])) \
            * jnp.einsum('bmh,bhf->bmf', rows, cast(up)[of])
        out = jnp.einsum('bmf,bfh->bmh', h, cast(down)[of],
                         preferred_element_type=jnp.float32)
        y = jnp.zeros((T, H), jnp.float32).at[tok.reshape(-1)].add(
            (out * w[..., None]).reshape(-1, H))
        return y, jnp.sum(valid, dtype=jnp.int32)

    def dense():
        y = jnp.zeros((T, H), jnp.float32)
        one = jax.checkpoint(
            lambda x, g, u, d, m: m[:, None] * swiglu(x, g, u, d, dtype)
            .astype(jnp.float32))
        for e in range(G):
            m = jnp.sum(jnp.where(idx == lo + e, weights, 0.0), axis=1)
            y = y + one(x, gate[e], up[e], down[e], m)
        return y, n_held

    y, computed = jax.lax.cond(needed <= nb, grouped, dense)
    f32 = jnp.float32
    counters = jnp.stack([
        n_held.astype(f32), jnp.asarray(T * k, f32),
        jnp.max(counts).astype(f32), n_held.astype(f32) / G,
        (n_held - computed).astype(f32)])
    return y, counters
