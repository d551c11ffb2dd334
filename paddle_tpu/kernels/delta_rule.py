"""The chunk-wise gated delta rule (`nn/functional/delta_rule.py` has the
mathematics) as a Pallas kernel pair under one `jax.custom_vjp`.

q, k, v, g arrive as the projections leave them, `(B, T, H*K)` seen as a
2-D array per row in which a `(C, K)` block at column `h*K` is one head's
chunk: no transpose to a chunk layout, each operand read once, `o` written
once in the layout the output norm reads. The grid is (row, heads, chunks);
the chunks of a head run in order and carry the state in a VMEM scratch
(transposed, `(V, K)`: the decay then scales lanes and every product with the
state is of the `a @ b.T` form). Everything chunk-local — the running sum of
g, the sub-block references, the scores, the unit-triangular inverse, the
corrections `U` — lives in VMEM.

Per chunk, with `S` the state it starts from:

    U  = (I + Diag(beta) A)^-1 Diag(beta) (V - k_in S)
    o  = scale (q_in S + B U)
    S' = Diag(exp(G_C)) S + k_out^T U

which is the XLA form's `U0 - W S` without `W` ever made. The inverse is
forward substitution in blocks (`_unit_lower_inverse`): the `sub x sub`
diagonal blocks by rank-one updates on the VPU, the rest by products; those
products, the scores, the running sum and `k_in S` run in float32 at the
highest precision, as the XLA form's scores, solve and state recurrence do. `B U`, `q_in S` and `k_out^T U` take `dtype`
operands, as there.

The backward kernel sweeps a head's chunks in reverse carrying dS and works
a chunk's gradient out by hand (`_chunk_backward`), from the chunk's own
equations and from what the forward kept of the chunk when it ran with
`save`: the state it started from, the inverse, `U` and the two score
matrices `A` and `B`. Of the chunk's forward it forms again only the running
sum of g, the decays to and from the chunk's edges and `k_in S`; the scores'
gradient goes back to q, k and G through the sub-block references the
forward uses. `_chunk` is the one definition of the forward, and `jax.vjp`
of it is what the tests hold `_chunk_backward` to.

K and V need not be equal (a Gated DeltaNet head has keys of 96 and values
of 192), and a head a third short of whole 128-lane registers is laid on
them behind zero channels by `delta_rule` (96 -> 128, 192 -> 256; exact, its
docstring says why). A decay per head, one scalar a token, arrives as K
equal channels.

Off the TPU (unless a test asks for interpret mode), or for a shape that
does not tile, `delta_rule` takes `delta_rule_chunked`, the XLA form, which
is also what the tests hold the kernels to; which one a trace took is marked
in the HLO (`_common.took`).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import head_lanes, on_lanes, pallas_runs, spmd_kernel, took

__all__ = ['delta_rule']

_HIGH = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_MARKS = 8      # rows of a chunk's marks block: seg, cont, tail, padding
_ROWS = 8       # of a float32 register


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _block_of(index, size, blocks):
    """index // size for 0 <= index < size * blocks, without a division."""
    out = jnp.zeros_like(index)
    for i in range(1, blocks):
        out = out + (index >= i * size).astype(jnp.int32)
    return out


@functools.lru_cache(maxsize=None)
def _unit_lower_inverse(sub):
    """-> inv(N): (I + N)^-1 of a strictly lower triangular N (C, C), in
    float32, with the closed form of its derivative (what `jax.vjp` of
    `_chunk`, the tests' reference for the backward kernel, takes).

    Forward substitution, blocked. A `sub`-block of the diagonal is
    I + sum_j n_j e_j^T = prod_j (I + n_j e_j^T) (n_j, column j, is zero down
    to row j), so its inverse is the factors' inverses I - n_j e_j^T applied
    to I in turn: `sub - 1` rank-one updates on the VPU, exact float32
    multiply-adds. Then block row i of the whole inverse is
    D_i (I - N[i, :i] X[:i]): two products of `sub` rows at the highest
    precision."""

    def inverse(n_):
        C = n_.shape[0]
        row, lane = _iota((sub, C), 0), _iota((sub, C), 1)
        diag = []                       # D_i, in its place among C lanes
        for lo in range(0, C, sub):
            x = (lane == row + lo).astype(_F32)
            for j in range(sub - 1):
                x = x - n_[lo:lo + sub, lo + j:lo + j + 1] * x[j:j + 1]
            diag.append(x)
        rows = diag[:1]
        for i in range(1, C // sub):
            above = _dot(diag[i], n_, _NN, _HIGH)       # D_i N[i, :]
            above = jnp.where(lane < i * sub, above, 0.0)
            rows.append(diag[i] - _dot(
                above, jnp.concatenate(rows + diag[i:], axis=0), _NN, _HIGH))
        return jnp.concatenate(rows, axis=0)

    @jax.custom_vjp
    def inv(n_):
        return inverse(n_)

    def fwd(n_):
        out = inv(n_)
        return out, out

    def bwd(out, ct):
        # d(X^-1) = -X^-1 dX X^-1
        return (-_dot(_dot(out, ct, _TN, _HIGH), out, _NT, _HIGH),)

    inv.defvjp(fwd, bwd)
    return inv


def _scores_between(G, k, q, sub):
    """sum_c x_t[c] k_j[c] exp(G_t[c] - G_j[c]) for x = k and x = q, j in a
    sub-block before t's: both factors are taken from the start of t's
    sub-block (G at the row before it), where each is at most 1; the rows
    of sub-block i against the keys as sub-block i sees them. -> two
    (C, C); entries that are not between sub-blocks are not to be read."""
    C = G.shape[0]
    a_rows = [jnp.zeros((sub, C), _F32)]
    b_rows = [jnp.zeros((sub, C), _F32)]
    for lo in range(sub, C, sub):
        ref = G[lo - 1:lo]
        row = jnp.exp(G[lo:lo + sub] - ref)
        k_col = k * jnp.exp(jnp.minimum(ref - G, 0.0))
        ab = _dot(jnp.concatenate([k[lo:lo + sub] * row,
                                   q[lo:lo + sub] * row], axis=0),
                  k_col, _NT, _HIGH)
        a_rows.append(ab[:sub])
        b_rows.append(ab[sub:])
    return jnp.concatenate(a_rows, 0), jnp.concatenate(b_rows, 0)


@jax.jit
def _scores_in_block(G, k, q, local):
    """One sub-block of `_scores_inside`: G, k, q (sub, K); `local` (sub, C),
    each lane's index counted from the block's first key. A jit, so that the
    unrolled loop is traced once for every sub-block, head and layer."""
    a = b = jnp.zeros(local.shape, _F32)
    for j in range(G.shape[0]):
        kk = k[j:j + 1] * jnp.exp(jnp.minimum(G - G[j:j + 1], 0.0))
        hit = local == j
        a = jnp.where(hit, jnp.sum(kk * k, -1, keepdims=True), a)
        b = jnp.where(hit, jnp.sum(kk * q, -1, keepdims=True), b)
    return a, b


def _scores_inside(G, k, q, sub):
    """The same scores inside each sub-block, the exponent formed before
    the exponential: one key a step, its column of scores put in place by
    a mask. -> two (C, C), filled on the sub-blocks of the diagonal."""
    C = G.shape[0]
    lane = _iota((sub, C), 1)
    blocks = [_scores_in_block(G[lo:lo + sub], k[lo:lo + sub],
                               q[lo:lo + sub], lane - lo)
              for lo in range(0, C, sub)]
    return (jnp.concatenate([a for a, _ in blocks], 0),
            jnp.concatenate([b for _, b in blocks], 0))


def _mm(a, b, dims, dtype):
    """One of the chunk's three large products, or a transpose of one: its
    operands in `dtype` where one is given, float32 out."""
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return _dot(a, b, dims)


def _chunk(q, k, v, g, beta, state, seg_c, seg_r, cont, tail, *, scale, sub,
           dtype):
    """One head's chunk. q, k, g (C, K); v (C, V); beta, seg_c, cont, tail
    (C, 1); seg_r (1, C); state (V, K), the transposed state the chunk
    starts from -> o (C, V), the state the chunk ends with, and what the
    backward reads of the chunk beside its operands: the inverse
    (I + Diag(beta) A)^-1, U, A and B. All float32."""
    C = q.shape[0]
    blocks = C // sub
    t, j = _iota((C, C), 0), _iota((C, C), 1)
    block_t, block_j = _block_of(t, sub, blocks), _block_of(j, sub, blocks)
    same = seg_c == seg_r
    lower, strict = (t >= j) & same, (t > j) & same
    earlier = block_t > block_j

    G = _dot((j <= t).astype(_F32), g, _NN, _HIGH)    # the running sum of g
    a_far, b_far = _scores_between(G, k, q, sub)
    a_near, b_near = _scores_inside(G, k, q, sub)
    A = jnp.where(strict, jnp.where(earlier, a_far, a_near), 0.0)
    Bm = jnp.where(lower, jnp.where(earlier, b_far, b_near), 0.0)

    inverse = _unit_lower_inverse(sub)(beta * A)
    from_start = jnp.exp(G) * cont
    k_in, q_in = k * from_start, q * from_start
    U = _dot(inverse, beta * (v - _dot(k_in, state, _NT, _HIGH)), _NN, _HIGH)
    o = scale * (_mm(q_in, state, _NT, dtype) + _mm(Bm, U, _NN, dtype))
    G_end = G[C - 1:C]
    k_out = k * jnp.exp(G_end - G) * tail
    keep = jnp.exp(G_end) * cont[C - 1:C]
    return (o, keep * state + _mm(U, k_out, _TN, dtype),
            (inverse, U, A, Bm))


def _running_sum(x, reverse=False):
    """The sum of x's rows up to each row (from each row on where `reverse`),
    by log2(rows) shifted adds: float32 adds where the forward takes a
    product of `HIGHEST` passes."""
    C = x.shape[0]
    at = _iota(x.shape, 0)
    shift = 1
    while shift < C:
        if reverse:
            x = x + jnp.where(at < C - shift, pltpu.roll(x, C - shift, 0), 0.0)
        else:
            x = x + jnp.where(at >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _scores_between_backward(G, k, q, dA, dBm, sub):
    """The transposes of `_scores_between`'s products. dA, dBm (C, C): the
    scores' cotangents, zero where a score is not read -> what they give
    q's rows, k's rows and k as a key, each (C, K). The decays' own
    gradient follows from these three (`_chunk_backward`)."""
    C = G.shape[0]
    zero = jnp.zeros((sub, G.shape[1]), _F32)
    dq_rows, dk_rows, dk_keys = [zero], [zero], 0.0
    for lo in range(sub, C, sub):
        ref = G[lo - 1:lo]
        row = jnp.exp(G[lo:lo + sub] - ref)
        to_ref = jnp.exp(jnp.minimum(ref - G[:lo], 0.0))
        rows = jnp.concatenate([k[lo:lo + sub] * row, q[lo:lo + sub] * row],
                               axis=0)
        ct = jnp.concatenate([dA[lo:lo + sub, :lo], dBm[lo:lo + sub, :lo]],
                             axis=0)
        d_rows = _dot(ct, k[:lo] * to_ref, _NN, _HIGH)
        dk_rows.append(d_rows[:sub] * row)
        dq_rows.append(d_rows[sub:] * row)
        dk_keys = dk_keys + jnp.concatenate(
            [_dot(ct, rows, _TN, _HIGH) * to_ref,
             jnp.zeros((C - lo, G.shape[1]), _F32)], axis=0)
    return (jnp.concatenate(dq_rows, 0), jnp.concatenate(dk_rows, 0),
            dk_keys)


@jax.jit
def _scores_in_block_backward(G, k, q, dA, dBm):
    """The same for one sub-block of `_scores_inside`: G, k, q (sub, K);
    dA, dBm (sub, sub), the cotangents of the block's scores. One key a
    step: its column of cotangents spread over the lanes, the rows'
    gradients by multiply-adds, the key's by a sum over the sublanes."""
    sub = G.shape[0]
    registers = [slice(lo, lo + _ROWS) for lo in range(0, sub, _ROWS)]
    zero = jnp.zeros((_ROWS, G.shape[1]), _F32)
    dq_rows, dk_rows = [zero] * len(registers), [zero] * len(registers)
    at = _iota(G.shape, 0)
    dk_keys = jnp.zeros(G.shape, _F32)
    for j in range(sub):
        # a key's scores are read from its own row on: the registers of
        # rows before it hold none
        key = 0.0
        for i in range(j // _ROWS, len(registers)):
            rows = registers[i]
            decay = jnp.exp(jnp.minimum(G[rows] - G[j:j + 1], 0.0))
            kk = k[j:j + 1] * decay
            da, db = dA[rows, j:j + 1], dBm[rows, j:j + 1]
            dk_rows[i] = dk_rows[i] + da * kk
            dq_rows[i] = dq_rows[i] + db * kk
            key = key + (da * k[rows] + db * q[rows]) * decay
        dk_keys = jnp.where(at == j, jnp.sum(key, axis=0, keepdims=True),
                            dk_keys)
    return (jnp.concatenate(dq_rows, 0), jnp.concatenate(dk_rows, 0),
            dk_keys)


def _chunk_backward(q, k, v, g, beta, state, seg_c, seg_r, cont, tail,
                    inverse, U, A, Bm, do, dstate, *, scale, sub, dtype):
    """`_chunk`'s gradient from its equations. Operands as `_chunk` takes
    them; inverse, U, A, Bm as it returned them; do (C, V) and dstate
    (V, K), the cotangents of `o` and of the state the chunk ends with
    -> dq, dk, dv, dg, dbeta (C, 1), and the cotangent of the state the
    chunk starts from.

    With X the inverse and R = beta (v - k_in S): U = X R, so dR = X^T dU
    and d(beta A) = -X^T (dU R^T) X^T = -dR U^T. A product takes the
    precision of the forward's product it transposes."""
    C = q.shape[0]
    t, j = _iota((C, C), 0), _iota((C, C), 1)
    same = seg_c == seg_r
    lower, strict = (t >= j) & same, (t > j) & same

    G = _running_sum(g)
    from_start = jnp.exp(G) * cont
    k_in, q_in = k * from_start, q * from_start
    G_end = G[C - 1:C]
    to_end = jnp.exp(G_end - G) * tail
    k_out = k * to_end
    keep = jnp.exp(G_end) * cont[C - 1:C]
    seen = v - _dot(k_in, state, _NT, _HIGH)

    do = scale * do
    dU = _mm(Bm, do, _TN, dtype) + _mm(k_out, dstate, _NT, dtype)
    dBm = jnp.where(lower, _mm(do, U, _NT, dtype), 0.0)
    dq_in = _mm(do, state, _NN, dtype)
    dk_out = _mm(U, dstate, _NN, dtype)
    dR = _dot(inverse, dU, _TN, _HIGH)
    dN = jnp.where(strict, -_dot(dR, U, _NT, _HIGH), 0.0)
    dv = beta * dR
    dk_in = -_dot(dv, state, _NN, _HIGH)
    dstart = (keep * dstate + _mm(do, q_in, _TN, dtype)
              - _dot(dv, k_in, _TN, _HIGH))
    dbeta = (jnp.sum(dR * seen, -1, keepdims=True)
             + jnp.sum(dN * A, -1, keepdims=True))

    dA = beta * dN
    dq_rows, dk_rows, dk_keys = _scores_between_backward(G, k, q, dA, dBm,
                                                         sub)
    near = [_scores_in_block_backward(
        G[lo:lo + sub], k[lo:lo + sub], q[lo:lo + sub],
        dA[lo:lo + sub, lo:lo + sub], dBm[lo:lo + sub, lo:lo + sub])
        for lo in range(0, C, sub)]
    dq_rows, dk_rows, dk_keys = (
        far + jnp.concatenate(blocks, 0)
        for far, blocks in zip((dq_rows, dk_rows, dk_keys), zip(*near)))

    # a score reads G as G_t - G_j alone: what it gives G follows from what
    # it gives its row and its key; so for the decays to the chunk's edges
    dq = dq_rows + dq_in * from_start
    dk = dk_rows + dk_keys + dk_in * from_start + dk_out * to_end
    to_the_end = dk_out * k_out
    dG = (q * dq_rows + k * (dk_rows - dk_keys) + dq_in * q_in
          + dk_in * k_in - to_the_end)
    dG_end = (jnp.sum(to_the_end, 0, keepdims=True)
              + keep * jnp.sum(dstate * state, 0, keepdims=True))
    # G_end is in every row's sum
    dg = _running_sum(dG, reverse=True) + dG_end
    return dq, dk, dv, dg, dbeta, dstart


def _columns(marks_ref):
    """A chunk's marks block (8, C) -> seg as a row, and seg, cont, tail as
    columns; with them the function that turns any (1, C) row into a (C, 1)
    column and its inverse (a masked reduction: no transpose)."""
    C = marks_ref.shape[-1]
    eye = _iota((C, C), 0) == _iota((C, C), 1)

    def column(r):
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    def as_row(c):
        return jnp.sum(jnp.where(eye, c, 0.0), axis=0, keepdims=True)

    marks = marks_ref[...]
    return (marks[0:1], column(marks[0:1]), column(marks[1:2]),
            column(marks[2:3]), column, as_row)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, marks_ref, o_ref,
                *rest, heads, K, V, save, **chunk):
    kept_refs, state_ref = rest[:-1], rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    seg_r, seg_c, cont, tail, column, _ = _columns(marks_ref)
    one_head = jax.jit(functools.partial(_chunk, **chunk))  # traced once
    for h in range(heads):
        ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        start = state_ref[h]
        o, state, (inverse, U, A, Bm) = one_head(
            q_ref[:, ks], k_ref[:, ks], v_ref[:, vs], g_ref[:, ks],
            column(beta_ref[h]), start, seg_c, seg_r, cont, tail)
        o_ref[:, vs] = o
        state_ref[h] = state
        if save:
            start_ref, inverse_ref, u_ref, scores_ref = kept_refs
            start_ref[h], inverse_ref[h] = start, inverse
            u_ref[:, vs] = U
            scores_ref[h] = jnp.concatenate([A, Bm], axis=1)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, marks_ref, start_ref,
                inverse_ref, u_ref, scores_ref, do_ref, dq_ref, dk_ref,
                dv_ref, dg_ref, dbeta_ref, dstate_ref, *, heads, K, V,
                **chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    seg_r, seg_c, cont, tail, column, as_row = _columns(marks_ref)
    C = q_ref.shape[0]
    # traced once for all the heads of a step
    one_head = jax.jit(functools.partial(_chunk_backward, **chunk))
    for h in range(heads):
        ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        scores = scores_ref[h]
        dq, dk, dv, dg, dbeta, dstate = one_head(
            q_ref[:, ks], k_ref[:, ks], v_ref[:, vs], g_ref[:, ks],
            column(beta_ref[h]), start_ref[h], seg_c, seg_r, cont, tail,
            inverse_ref[h], u_ref[:, vs], scores[:, :C], scores[:, C:],
            do_ref[:, vs], dstate_ref[h])
        dq_ref[:, ks], dk_ref[:, ks], dg_ref[:, ks] = dq, dk, dg
        dv_ref[:, vs] = dv
        dbeta_ref[h] = as_row(dbeta)
        dstate_ref[h] = dstate


_VMEM_LIMIT = 64 << 20    # the backward holds a chunk's whole gradient per head


def _specs(heads, C, K, V, chunk_of):
    """BlockSpecs over the grid (row, head group, chunk step); `chunk_of`
    maps the step to the chunk (the backward runs them in reverse)."""
    def wide(width):        # (B, T, H*width): a (C, heads*width) block
        return pl.BlockSpec((None, C, heads * width),
                            lambda b, h, n: (b, chunk_of(n), h))
    per_head = pl.BlockSpec((None, heads, None, 1, C),      # (B, H, N, 1, C)
                            lambda b, h, n: (b, h, chunk_of(n), 0, 0))
    marks = pl.BlockSpec((None, None, _MARKS, C),           # (B, N, 8, C)
                         lambda b, h, n: (b, chunk_of(n), 0, 0))
    def kept(rows, cols):   # (B, H, N, rows, cols): a matrix a chunk-head
        return pl.BlockSpec((None, heads, None, rows, cols),
                            lambda b, h, n: (b, h, chunk_of(n), 0, 0))
    return (wide(K), wide(V), per_head, marks, kept(V, K), kept(C, C),
            kept(C, 2 * C))


# `_forward` and `_backward` are jits of their own: a net's KDA layers make
# the same calls, and a jit inside the step's trace is traced (the unrolled
# chunk, and its gradient, are thousands of equations) and lowered once for
# all of them. The scope is entered again inside: the compiler names a custom
# call after its innermost scope.
_STATIC = ('scale', 'chunk', 'sub', 'dtype', 'heads', 'interpret')


@functools.partial(jax.jit, static_argnames=_STATIC + ('save',))
def _forward(q, k, v, g, beta, marks, *, save, scale, chunk, sub, dtype,
             heads, interpret):
    """-> o, and where `save` what the backward reads of every chunk: the
    state it starts from (B, H, N, V, K), its inverse (B, H, N, C, C), its U
    (laid as v is) and its scores A and B side by side (B, H, N, C, 2C)."""
    B, T, HK = q.shape
    H, C = beta.shape[1], chunk
    K, V, N = HK // H, v.shape[2] // H, T // C
    wide_k, wide_v, per_head, marks_spec, states, inverses, scores = _specs(
        heads, C, K, V, lambda n: n)
    out_specs, out_shape = [wide_v], [jax.ShapeDtypeStruct(v.shape, _F32)]
    # graftlint: disable=GL006 — `save` is a static argument of this jit
    # (never a tracer): one trace with the kept outputs, one without
    if save:
        out_specs += [states, inverses, wide_v, scores]
        out_shape += [jax.ShapeDtypeStruct((B, H, N, V, K), _F32),
                      jax.ShapeDtypeStruct((B, H, N, C, C), _F32),
                      jax.ShapeDtypeStruct(v.shape, _F32),
                      jax.ShapeDtypeStruct((B, H, N, C, 2 * C), _F32)]
    with jax.named_scope('delta_rule.pallas'):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, heads=heads, K=K, V=V, save=save,
                              scale=scale, sub=sub, dtype=dtype),
            grid=(B, H // heads, N),
            in_specs=[wide_k, wide_k, wide_v, wide_k, per_head, marks_spec],
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((heads, V, K), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'parallel', 'arbitrary'),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(q, k, v, g, beta, marks)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(q, k, v, g, beta, marks, starts, inverses, us, scores, do, *,
              scale, chunk, sub, dtype, heads, interpret):
    B, T, HK = q.shape
    H, C = beta.shape[1], chunk
    K, V, N = HK // H, v.shape[2] // H, T // C
    (wide_k, wide_v, per_head, marks_spec, states, kept_inverses,
     kept_scores) = _specs(heads, C, K, V, lambda n: N - 1 - n)
    with jax.named_scope('delta_rule.pallas'):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, heads=heads, K=K, V=V, scale=scale,
                              sub=sub, dtype=dtype),
            grid=(B, H // heads, N),
            in_specs=[wide_k, wide_k, wide_v, wide_k, per_head, marks_spec,
                      states, kept_inverses, wide_v, kept_scores, wide_v],
            out_specs=[wide_k, wide_k, wide_v, wide_k, per_head],
            out_shape=[jax.ShapeDtypeStruct(x.shape, _F32)
                       for x in (q, k, v, g, beta)],
            scratch_shapes=[pltpu.VMEM((heads, V, K), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'parallel', 'arbitrary'),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(q, k, v, g, beta, marks, starts, inverses, us, scores, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _delta(q, k, v, g, beta, marks, static):
    return _forward(q, k, v, g, beta, marks, save=False, **dict(static))[0]


def _delta_fwd(q, k, v, g, beta, marks, static):
    o, *kept = _forward(q, k, v, g, beta, marks, save=True, **dict(static))
    return o, (q, k, v, g, beta, marks, *kept)


def _delta_bwd(static, res, do):
    return (*_backward(*res, do, **dict(static)), None)


_delta.defvjp(_delta_fwd, _delta_bwd)


def _marks(seg, chunk):
    """(B, T) document numbers -> (B, N, 8, C) float32: per chunk the
    document number of each position, whether it sees the state the chunk
    starts from (`cont`), whether its update outlives the chunk (`tail`)."""
    B, T = seg.shape
    sc = seg.reshape(B, T // chunk, chunk)
    before = jnp.concatenate([jnp.full((B, 1), -1, sc.dtype), sc[:, :-1, -1]],
                             axis=1)
    rows = [sc, sc == before[:, :, None], sc == sc[:, :, -1:]]
    rows += [jnp.zeros_like(sc)] * (_MARKS - len(rows))
    return jnp.stack([r.astype(_F32) for r in rows], axis=2)


def _heads_a_step(H):
    """Heads taken in one grid step: their chunks are independent chains
    (the inverse's rank-one updates above all), which the scheduler
    interleaves. One row-layer of the cell, forward + backward: 27.7 ms at
    1, 26.1 at 2 (my chip run, PR 28); each head more is lowered again."""
    return 2 if H % 2 == 0 else 1


def delta_rule(q, k, v, g, beta, seg, scale, chunk=64, sub=16, dtype=None,
               interpret=False):
    """The gated delta rule chunk-wise, as `delta_rule_chunked` defines it:
    q, k (B, T, H, K); v (B, T, H, V); g (B, T, H, K), a log-decay per
    channel, or (B, T, H), one per head (handed to the kernels as K equal
    channels); beta (B, T, H); seg (B, T) -> o (B, T, H, V), float32.

    On the TPU (or in interpret mode), with T a multiple of `chunk`, `chunk`
    of `sub` and `sub` of the 8 sublanes, the Pallas kernels: as they are
    where K and V are whole 128-lane registers, and for heads a third short
    of them (`_common.head_lanes`: keys of 96, values of 192) with each head
    laid on whole registers behind zero channels, which is exact: a zero key
    channel adds nothing to a score or to a read of the state, the state's
    rows and columns of zero channels stay zero, and the zero value channels'
    outputs are dropped. Otherwise the XLA form. Either way the ops sit
    under a `delta_rule.pallas` / `delta_rule.xla` scope."""
    T, K, V = q.shape[1], q.shape[3], v.shape[-1]
    lanes_k, lanes_v = head_lanes(K), head_lanes(V)
    if not (pallas_runs(interpret) and T % chunk == 0 and chunk % sub == 0
            and sub % 8 == 0 and lanes_k and lanes_v):
        from ..nn.functional.delta_rule import delta_rule_chunked
        with took('delta_rule', 'xla'):
            return delta_rule_chunked(q, k, v, g, beta, seg, scale,
                                      chunk=chunk, sub=sub, dtype=dtype)
    if dtype is not None:
        dtype = jnp.dtype(dtype).name       # hashable, for the custom_vjp
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], g.shape + (lanes_k,))
    q, k, g = (on_lanes(x, lanes_k) for x in (q, k, g))
    v = on_lanes(v, lanes_v)

    def call(q, k, v, g, beta, marks, shard):
        b, _, h, _ = q.shape                # this device's rows and heads
        wide = [x.astype(_F32).reshape(b, T, -1) for x in (q, k, v, g)]
        per_head = jnp.moveaxis(beta.astype(_F32), 1, 2).reshape(
            b, h, T // chunk, 1, chunk)
        o = _delta(*wide, per_head, marks, tuple(zip(_STATIC, (
            scale, chunk, sub, dtype, _heads_a_step(h), interpret))))
        return o.reshape(b, T, h, lanes_v)

    dims = ('b', None, 'h', None)
    with took('delta_rule', 'pallas'):
        o = spmd_kernel(
            call, [dims] * 4 + [('b', None, 'h'), ('b', None, None, None)],
            [dims], {'b': 'batch', 'h': 'heads'},
            scope='delta_rule.pallas')(q, k, v, g, beta, _marks(seg, chunk))
        return o if lanes_v == V else o[..., :V]
