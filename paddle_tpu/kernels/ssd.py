"""Mamba-2's state-space rule chunk-wise (`nn/functional/ssd.py` has the
mathematics) as a Pallas kernel pair under one `jax.custom_vjp`.

x, B and C arrive as the convolutions leave them, `(B, T, H*P)` and
`(B, T, G*N)` seen as 2-D arrays per row: a `(C, R*P)` block at column
`g*R*P` is the chunk of the R heads of group g, a `(C, N)` block at column
`g*N` the group's B or C. No transpose to a chunk layout, each operand read
once, `y` written once in the layout the gated norm reads. The grid is (row,
group, chunk): a GROUP of heads a step, because its heads share
`G = C B^T`, the read `C S^T` of all their states is one product `(C, N) x
(N, R*P)` and their writes one `(R*P, C) x (C, N)`; the chunks of a group
run in order and carry its heads' states, stacked `(R*P, N)`, in a VMEM
scratch.

What a chunk reads of dt, A and the documents is small (a number a token
and head) and is made by XLA (`_packs`: `functional.ssd.chunk_decays`'
numbers, laid out by group with the chunk's tokens along the lanes): ROWS
`(W, C)` a chunk, the running sum a, dt, `from_start` and `to_end` of the
group's heads and the document number. The decay tile `exp(a_t - a_s)` needs
a down the sublanes too: the kernel turns the pack over itself, one product
of the identity with the rows at `HIGHEST` (`I rows^T`: a 0/1 product,
which leaves a float32 as it is), so that XLA never makes an array 8 or 32
numbers wide (on the TPU such an array is stored 128 lanes wide and every
op on it moves the padding). A column is spread over its head's P lanes by
another 0/1 product. The kernels differentiate with respect to the pack's
entries (the columns' gradient is turned back the same way); XLA's own rule
takes that back to dt and A.

Heads of P = 64 lie two to a 128-lane register. The one product that is a
head's own, `M_h x_h` with its `(C, C)` tile, takes a REGISTER of heads at
a time: `[M_1 | M_2] @ [[x_1, 0], [0, x_2]]`, a `(C, 2C) x (2C, 128)`
product whose operands and result are whole registers; half its
multiply-adds meet zeros, on an MXU the kernel leaves idle nine tenths of
the time (the rule's bytes bound it, not its operations). No lane is shifted
and no 64-wide slice is made.

The backward kernel sweeps a group's chunks in reverse carrying dS. The
forward, when it runs with `save`, keeps the state every chunk starts from;
the backward forms the decay tiles, G and the reads again from the operands
(cheaper than keeping R tiles of (C, C) a chunk) and works the gradient out
by hand from the chunk's equations: every product the transpose of one of
the forward's, on operands of the same type.

Off the TPU (unless a test asks for interpret mode), or for a shape that
does not tile (T in chunks of whole 128-lane registers, P a divisor of 128,
a group's heads and the state whole registers), `ssd` takes `ssd_chunked`,
the XLA form, which is also what the tests hold the kernels to; which one a
trace took is marked in the HLO (`_common.took`).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_runs, spmd_kernel, took
# (the other chunk-wise rule's helpers: a product with float32 out, one on
# `dtype` operands, an index array)
from .delta_rule import _F32, _HIGH, _NN, _NT, _TN, _dot, _iota, _mm

__all__ = ['ssd']

_LANES = 128
_MASKED = -1e30     # an exponent whose exponential is 0


def _width(R):
    """Rows of a chunk's pack for R heads a group: a, dt, `from_start`,
    `to_end` a head, the document number, zeros up to whole sublane
    tiles."""
    return -(-(4 * R + 1) // 8) * 8


def _spread(n, R, P, first, heads_on=0):
    """A 0/1 matrix that spreads entry `first + h` over the P lanes of head
    h (`heads_on` 0: (n, R*P), the entries are rows) or gathers them back
    (`heads_on` 1: (R*P, n))."""
    shape = (n, R * P) if heads_on == 0 else (R * P, n)
    lo = (_iota(shape, heads_on) - first) * P
    lane = _iota(shape, 1 - heads_on)
    return ((lane >= lo) & (lane < lo + P)).astype(_F32)


def _identity(C):
    return (_iota((C, C), 0) == _iota((C, C), 1)).astype(_F32)


def _chunk_parts(x_ref, b_ref, c_ref, rows_ref, keep_ref, *, R, P, dtype):
    """What both kernels form of a chunk: its operands, the pack as rows
    (W, C) and turned over as columns (C, W), the (C, C) mask and scores,
    `from_start` and `to_end` spread over the heads' lanes, `keep` over the
    state's rows."""
    X, Bg, Cg, rows = x_ref[...], b_ref[...], c_ref[...], rows_ref[...]
    W, C = rows.shape
    cols = _dot(_identity(C), rows, _NT, _HIGH)
    # (the document numbers are whole; half a number of room, should the
    # product with the identity ever round one)
    sees = (jnp.abs(cols[:, 4 * R:4 * R + 1] - rows[4 * R:4 * R + 1]) < 0.5) \
        & (_iota((C, C), 0) >= _iota((C, C), 1))
    scores = _mm(Cg, Bg, _NT, dtype)
    from_start = _dot(cols, _spread(W, R, P, 2 * R), _NN, _HIGH)
    to_end = _dot(cols, _spread(W, R, P, 3 * R), _NN, _HIGH)
    keep = _dot(_spread(R, R, P, 0, heads_on=1), keep_ref[...], _NN, _HIGH)
    return X, Bg, Cg, cols, rows, sees, scores, from_start, to_end, keep


def _decay_tile(cols, rows, sees, h):
    """exp(a_t - a_s) of head h where t sees s, else 0."""
    return jnp.exp(jnp.where(sees, cols[:, h:h + 1] - rows[h:h + 1],
                             _MASKED))


def _lanes_of(j, P):
    """The lanes of the j-th head of a register, as a (1, 128) mask."""
    lane = _iota((1, _LANES), 1)
    return (lane >= j * P) & (lane < (j + 1) * P)


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, keep_ref, d_ref, y_ref, *rest,
                R, P, dtype, save):
    state_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    (X, Bg, Cg, cols, rows, sees, scores, from_start, to_end,
     keep) = _chunk_parts(x_ref, b_ref, c_ref, rows_ref, keep_ref, R=R, P=P,
                          dtype=dtype)
    start = state_ref[...]
    if save:
        rest[0][...] = start
    per = _LANES // P
    inside = []
    for i in range(R * P // _LANES):
        reg = X[:, i * _LANES:(i + 1) * _LANES]
        tiles = [_decay_tile(cols, rows, sees, h) * scores
                 * rows[R + h:R + h + 1]
                 for h in range(i * per, (i + 1) * per)]
        blocks = [jnp.where(_lanes_of(j, P), reg, 0.0) for j in range(per)]
        inside.append(_mm(jnp.concatenate(tiles, axis=1),
                          jnp.concatenate(blocks, axis=0), _NN, dtype))
    y_ref[...] = (jnp.concatenate(inside, axis=1)
                  + from_start * _mm(Cg, start, _NT, dtype)
                  + d_ref[...] * X)
    state_ref[...] = keep * start + _mm(X * to_end, Bg, _TN, dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, keep_ref, d_ref, start_ref,
                dy_ref, dx_ref, db_ref, dc_ref, drows_ref, dkeep_ref, dd_ref,
                dstate_ref, *, R, P, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    (X, Bg, Cg, cols, rows, sees, scores, from_start, to_end,
     keep) = _chunk_parts(x_ref, b_ref, c_ref, rows_ref, keep_ref, R=R, P=P,
                          dtype=dtype)
    C, W = cols.shape
    start, dY, dend = start_ref[...], dy_ref[...], dstate_ref[...]

    # y = ... + from_start * (C S^T) + D x;  S' = keep S + (x to_end)^T B
    reads = _mm(Cg, start, _NT, dtype)
    dreads = from_start * dY
    dwrites = _mm(Bg, dend, _NT, dtype)
    dC = _mm(dreads, start, _NN, dtype)
    dB = _mm(X * to_end, dend, _NN, dtype)
    dstate_ref[...] = keep * dend + _mm(dreads, Cg, _TN, dtype)
    dkeep_ref[...] = _dot(_spread(R, R, P, 0), dend * start, _NN, _HIGH)
    dd_ref[...] += jnp.sum(dY * X, axis=0, keepdims=True)
    dcols = (_dot(dY * reads, _spread(W, R, P, 2 * R, heads_on=1), _NN,
                  _HIGH)
             + _dot(dwrites * X, _spread(W, R, P, 3 * R, heads_on=1), _NN,
                    _HIGH))
    drows = jnp.zeros((W, C), _F32)
    dscores = jnp.zeros((C, C), _F32)
    col_at, row_at = _iota((C, W), 1), _iota((W, C), 0)

    # y_h = M_h x_h, M_h = exp(a_t - a_s) G dt_s: d(exponent) = dM M
    per = _LANES // P
    inside = []
    for i in range(R * P // _LANES):
        lanes = slice(i * _LANES, (i + 1) * _LANES)
        reg, dreg = X[:, lanes], dY[:, lanes]
        dx = jnp.zeros_like(reg)
        for j in range(per):
            h, own = i * per + j, _lanes_of(j, P)
            step = rows[R + h:R + h + 1]
            decay = _decay_tile(cols, rows, sees, h)
            weighed = decay * scores
            M = weighed * step
            dM = _mm(jnp.where(own, dreg, 0.0), reg, _NT, dtype)
            moved = dM * M
            dcols = dcols + jnp.where(
                col_at == h, jnp.sum(moved, axis=1, keepdims=True), 0.0)
            drows = drows + jnp.where(
                row_at == h, -jnp.sum(moved, axis=0, keepdims=True), 0.0) \
                + jnp.where(row_at == R + h,
                            jnp.sum(dM * weighed, axis=0, keepdims=True), 0.0)
            dscores = dscores + dM * decay * step
            dx = dx + jnp.where(own, _mm(M, dreg, _TN, dtype), 0.0)
        inside.append(dx)
    dx_ref[...] = (jnp.concatenate(inside, axis=1) + d_ref[...] * dY
                   + to_end * dwrites)
    db_ref[...] = dB + _mm(dscores, Cg, _TN, dtype)
    dc_ref[...] = dC + _mm(dscores, Bg, _NN, dtype)
    drows_ref[...] = drows + _dot(dcols, _identity(C), _TN, _HIGH)


def _specs(C, R, P, S, chunk_of):
    """BlockSpecs over the grid (row, group, chunk step); `chunk_of` maps
    the step to the chunk (the backward runs them in reverse)."""
    def wide(width):        # (B, T, G*width): a (C, width) block
        return pl.BlockSpec((None, C, width),
                            lambda b, g, n: (b, chunk_of(n), g))

    def per_chunk(rows, cols):      # (B, G, N, rows, cols)
        return pl.BlockSpec((None, None, None, rows, cols),
                            lambda b, g, n: (b, g, chunk_of(n), 0, 0))
    skip = pl.BlockSpec((1, R * P), lambda b, g, n: (0, g))     # (1, G*R*P)
    return (wide(R * P), wide(S), per_chunk(_width(R), C), per_chunk(R, S),
            skip, per_chunk(R * P, S))


# `_forward` and `_backward` are jits of their own: a net's Mamba-2 layers
# make the same calls, and a jit inside the step's trace is traced and
# lowered once for all of them. The scope is entered again inside: the
# compiler names a custom call after its innermost scope.
_STATIC = ('R', 'P', 'chunk', 'dtype', 'interpret')
_PARAMS = dict(dimension_semantics=('parallel', 'parallel', 'arbitrary'))


@functools.partial(jax.jit, static_argnames=_STATIC + ('save',))
def _forward(x, Bm, Cm, rows, keep, skip, *, save, R, P, chunk, dtype,
             interpret):
    """-> y, and where `save` the state every chunk starts from
    (B, G, N, R*P, S)."""
    B, T, _ = x.shape
    G, N, S = keep.shape[1], T // chunk, keep.shape[-1]
    heads, group, pack, kept, per_lane, states = _specs(
        chunk, R, P, S, lambda n: n)
    out_specs, out_shape = [heads], [jax.ShapeDtypeStruct(x.shape, _F32)]
    # graftlint: disable=GL006 — `save` is a static argument of this jit
    # (never a tracer): one trace with the kept states, one without
    if save:
        out_specs.append(states)
        out_shape.append(jax.ShapeDtypeStruct((B, G, N, R * P, S), _F32))
    with jax.named_scope('ssd.pallas'):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, R=R, P=P, dtype=dtype, save=save),
            grid=(B, G, N),
            in_specs=[heads, group, group, pack, kept, per_lane],
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((R * P, S), _F32)],
            compiler_params=pltpu.CompilerParams(**_PARAMS),
            interpret=interpret,
        )(x, Bm, Cm, rows, keep, skip)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(x, Bm, Cm, rows, keep, skip, starts, dy, *, R, P, chunk, dtype,
              interpret):
    B, T, _ = x.shape
    N, S = T // chunk, keep.shape[-1]
    heads, group, pack, kept, per_lane, states = _specs(
        chunk, R, P, S, lambda n: N - 1 - n)
    summed = pl.BlockSpec((None, 1, R * P), lambda b, g, n: (b, 0, g))
    with jax.named_scope('ssd.pallas'):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, R=R, P=P, dtype=dtype),
            grid=(B, keep.shape[1], N),
            in_specs=[heads, group, group, pack, kept, per_lane, states,
                      heads],
            out_specs=[heads, group, group, pack, kept, summed],
            out_shape=[jax.ShapeDtypeStruct(t.shape, _F32)
                       for t in (x, Bm, Cm, rows, keep)]
            + [jax.ShapeDtypeStruct((B, 1, x.shape[2]), _F32)],
            scratch_shapes=[pltpu.VMEM((R * P, S), _F32)],
            compiler_params=pltpu.CompilerParams(**_PARAMS),
            interpret=interpret,
        )(x, Bm, Cm, rows, keep, skip, starts, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, Bm, Cm, rows, keep, skip, static):
    return _forward(x, Bm, Cm, rows, keep, skip, save=False,
                    **dict(static))[0]


def _scan_fwd(x, Bm, Cm, rows, keep, skip, static):
    y, starts = _forward(x, Bm, Cm, rows, keep, skip, save=True,
                         **dict(static))
    return y, (x, Bm, Cm, rows, keep, skip, starts)


def _scan_bwd(static, res, dy):
    *grads, dskip = _backward(*res, dy, **dict(static))
    return (*grads, jnp.sum(dskip, axis=0))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _packs(dt, A, seg, chunk, G, S):
    """What a chunk's equations read of dt, A and the documents
    (`functional.ssd.chunk_decays`' numbers), by group and with the chunk's
    tokens along the lanes from the first op on: rows (B, G, N, W, C), per
    chunk the running sum a, dt, `from_start` and `to_end` of the group's R
    heads and the document number; keep (B, G, N, R, S), one number a head
    and chunk over the state's lanes."""
    B, T, H = dt.shape
    R, N, C = H // G, T // chunk, chunk
    dt = jnp.transpose(dt.astype(_F32).reshape(B, N, C, G, R),
                       (0, 3, 1, 4, 2))                     # (B, G, N, R, C)
    # the running sum along the lanes as a product with a triangle of ones
    # (a cumulative sum over the minor axis is a `reduce_window` of its own
    # on the TPU, 0.75 ms a call where this is a few microseconds)
    upper = (jnp.arange(C)[:, None] <= jnp.arange(C)[None, :]).astype(_F32)
    a = jnp.matmul(dt * A.astype(_F32).reshape(G, 1, R, 1), upper,
                   precision=_HIGH)
    sc = seg.reshape(B, 1, N, 1, C)
    before = jnp.concatenate([jnp.full((B, 1, 1, 1, 1), -1, sc.dtype),
                              sc[:, :, :-1, :, -1:]], axis=2)
    cont = (sc == before).astype(_F32)
    tail = (sc == sc[..., -1:]).astype(_F32)
    end = a[..., -1:]
    keep = jnp.exp(end) * cont[..., -1:]
    rows = jnp.concatenate(
        [a, dt, jnp.exp(a) * cont, jnp.exp(end - a) * tail * dt,
         jnp.broadcast_to(sc.astype(_F32), (B, G, N, 1, C)),
         jnp.zeros((B, G, N, _width(R) - 4 * R - 1, C), _F32)], axis=-2)
    return rows, jnp.broadcast_to(keep, keep.shape[:-1] + (S,))


def ssd(x, dt, A, Bm, Cm, D, seg, chunk=128, dtype=None, interpret=False):
    """Mamba-2's rule chunk-wise, as `ssd_chunked` defines it: x (B, T, H,
    P); dt (B, T, H), after its softplus; A, D (H,); Bm, Cm (B, T, G, N),
    head h reading group h // (H / G); seg (B, T) -> y (B, T, H, P),
    float32.

    On the TPU (or in interpret mode) the Pallas kernels, where T is whole
    chunks of whole 128-lane registers, a head's P channels divide a
    register, and a group's heads (R P lanes) and the state (N) are whole
    registers. Otherwise the XLA form. Either way the ops sit under an
    `ssd.pallas` / `ssd.xla` scope."""
    # (`nn` imports this module: its functions are looked up at the call)
    from ..nn.functional.ssd import ssd_chunked
    B, T, H, P = x.shape
    G, S = Bm.shape[2:]
    R = H // G
    if not (pallas_runs(interpret) and T % chunk == 0
            and chunk % _LANES == 0 and _LANES % P == 0
            and (R * P) % _LANES == 0 and S % _LANES == 0):
        with took('ssd', 'xla'):
            return ssd_chunked(x, dt, A, Bm, Cm, D, seg, chunk=chunk,
                               dtype=dtype)
    if dtype is not None:
        dtype = jnp.dtype(dtype).name       # hashable, for the custom_vjp
    rows, keep = _packs(dt, A, seg, chunk, G, S)
    skip = jnp.broadcast_to(D.astype(_F32).reshape(G, R, 1), (G, R, P))

    def call(x, Bm, Cm, rows, keep, skip, shard):
        b, g = x.shape[0], x.shape[2]       # this device's rows and groups
        y = _scan(x.astype(_F32).reshape(b, T, g * R * P),
                  Bm.astype(_F32).reshape(b, T, g * S),
                  Cm.astype(_F32).reshape(b, T, g * S), rows, keep,
                  skip.reshape(1, g * R * P),
                  tuple(zip(_STATIC, (R, P, chunk, dtype, interpret))))
        return y.reshape(b, T, g, R * P)

    by_token, by_chunk = ('b', None, 'h', None), ('b', 'h', None, None, None)
    with took('ssd', 'pallas'):
        y = spmd_kernel(
            call, [by_token] * 3 + [by_chunk, by_chunk, ('h', None, None)],
            [by_token], {'b': 'batch', 'h': 'heads'}, scope='ssd.pallas')(
                x.reshape(B, T, G, R * P), Bm, Cm, rows, keep, skip)
        return y.reshape(B, T, H, P)
