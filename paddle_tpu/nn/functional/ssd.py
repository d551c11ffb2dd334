"""Mamba-2's state-space rule (the state-space duality form of
arXiv:2405.21060), for training: pure functions over jax arrays.

Per head of P channels, with a state S in R^{P x N}, one decay a head and
token, and B_t, C_t in R^N shared by the heads of a group:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

dt_t > 0 is the step (it scales the input as well as the decay), A < 0 the
head's rate. A row holds packed documents (`seg`, a document number per
position): the state a document starts from is zero.

`ssd_chunked` is the training path off the TPU (the tests hold it to the
recurrence as it stands, one token at a time, and the kernels of
`kernels/ssd.py` to it): the row is cut into chunks of C tokens. With a_t the
running sum of dt A from the chunk's start (a_t <= 0, falling),

    G    = C B^T                                   (one a group)
    M_ts = exp(a_t - a_s) G_ts dt_s                (s <= t, same document)
    y    = M x + (exp(a) cont) (C S_0^T) + D x
    S_C  = keep S_0 + (x * (exp(a_C - a) tail dt))^T B

`cont`: the position sees the state the chunk starts from (it lies in the
document of the token before the chunk); `tail`: its write outlives the chunk
(it lies in the document of the chunk's last token); keep = exp(a_C) cont_C.
exp(a_t - a_s) does not factor into a safe product (exp(-a_s) overflows
under a strong decay): the exponent is formed before the exponential, on
the (C, C) tile, and every exponential is of a number that is not positive.
"""
import jax
import jax.numpy as jnp

__all__ = ['chunk_decays', 'ssd_chunked']

_F32 = jnp.float32


def chunk_decays(dt, A, seg, chunk):
    """What a chunk's equations read of dt, A and the documents, all
    (B, N, C, H) float32 but `keep` (B, N, H) and `seg` (B, N, C): the
    running sum `a` of dt A inside the chunk, `from_start` = exp(a) cont,
    `to_end` = exp(a_C - a) tail dt, `keep` = exp(a_C) cont_C, and dt and
    seg by chunk. dt (B, T, H) float32, A (H,), seg (B, T)."""
    B, T, H = dt.shape
    N = T // chunk
    dt = dt.astype(_F32).reshape(B, N, chunk, H)
    a = jnp.cumsum(dt * A.astype(_F32), axis=2)
    sc = seg.reshape(B, N, chunk)
    before = jnp.concatenate([jnp.full((B, 1), -1, sc.dtype), sc[:, :-1, -1]],
                             axis=1)
    cont = (sc == before[:, :, None]).astype(_F32)[..., None]
    tail = (sc == sc[:, :, -1:]).astype(_F32)[..., None]
    end = a[:, :, -1:]
    return {'a': a, 'dt': dt, 'seg': sc, 'from_start': jnp.exp(a) * cont,
            'to_end': jnp.exp(end - a) * tail * dt,
            'keep': (jnp.exp(end) * cont[:, :, -1:])[:, :, 0]}


def ssd_chunked(x, dt, A, Bm, Cm, D, seg, chunk=128, dtype=None):
    """The recurrence chunk-wise (module docstring). x (B, T, H, P);
    dt (B, T, H), after its softplus; A, D (H,); Bm, Cm (B, T, G, N), H a
    multiple of G (head h reads group h // (H / G)); seg (B, T)
    -> y (B, T, H, P), float32. T is a multiple of `chunk`.
    `dtype`: the type of the matrix products' operands (None: float32); the
    decays, the running sum and the state's own recurrence stay in
    float32."""
    B, T, H, P = x.shape
    G, S = Bm.shape[2:]
    R, C = H // G, chunk
    N = T // C
    d = chunk_decays(dt, A, seg, C)

    def cast(t):
        return t if dtype is None else t.astype(dtype)

    def mm(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b),
                          preferred_element_type=_F32)
    x = x.astype(_F32)
    xs = x.reshape(B, N, C, G, R, P)
    Bs, Cs = (t.astype(_F32).reshape(B, N, C, G, S) for t in (Bm, Cm))
    t = jnp.arange(C)
    sees = (d['seg'][:, :, :, None] == d['seg'][:, :, None, :]) \
        & (t[:, None] >= t[None, :])                         # (B, N, C, C)
    a = jnp.moveaxis(d['a'], 3, 2)                           # (B, N, H, C)
    L = jnp.exp(jnp.where(sees[:, :, None], a[..., :, None] - a[..., None, :],
                          -1e30))
    scores = mm('bntgs,bnugs->bngtu', Cs, Bs)
    M = L.reshape(B, N, G, R, C, C) * scores[:, :, :, None] \
        * jnp.moveaxis(d['dt'], 3, 2).reshape(B, N, G, R, 1, C)
    inside = mm('bngrtu,bnugrp->bntgrp', M, xs)
    writes = xs * d['to_end'].reshape(B, N, C, G, R, 1)
    from_start = d['from_start'].reshape(B, N, C, G, R, 1)
    keep = d['keep'].reshape(B, N, G, R, 1, 1)

    def step(state, n):
        read = from_start[:, n] * mm('btgs,bgrps->btgrp', Cs[:, n], state)
        return (keep[:, n] * state
                + mm('btgrp,btgs->bgrps', writes[:, n], Bs[:, n])), read

    _, reads = jax.lax.scan(step, jnp.zeros((B, G, R, P, S), _F32),
                            jnp.arange(N))
    y = inside + jnp.moveaxis(reads, 0, 1)
    return y.reshape(B, T, H, P) + D.astype(_F32)[:, None] * x
