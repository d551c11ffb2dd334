"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692), for training: pure functions over jax arrays.

Per head, with keys and queries of size K and values of size V, a state
S in R^{K x V} follows

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

where g_t <= 0 is the log-decay of each of the K channels. A row holds
packed documents (`seg`, a document number per position): the state a
document starts from is zero.

`delta_rule_chunked` is the training path (the tests hold it to the
recurrence as it stands, one token at a time): the row is cut into chunks of
C tokens; inside a chunk the C rank-one corrections are solved for at once
(a unit lower-triangular system, the WY form of a product of Householder-
like factors), and a scan over the chunks carries the state. Per chunk,
with G_t the running sum of g from the chunk's start:

    A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])      (j <  t)
    B_tj = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c])      (j <= t)
    (I + Diag(beta) A) U = Diag(beta) (V - (k * exp(G)) S_0)
    o = scale * ((q * exp(G)) S_0 + B U)
    S_C = Diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T U

exp(G_t - G_j) does not factor into a safe product over a whole chunk
(exp(-G_j) overflows under a strong decay), so the chunk is cut again into
sub-blocks of `sub` tokens: between sub-blocks both factors are taken from
the start of the later one, where each is at most 1; inside a sub-block the
exponent is formed before the exponential.
"""
import jax
import jax.numpy as jnp

__all__ = ['causal_conv', 'delta_rule_chunked']

_HIGH = jax.lax.Precision.HIGHEST


def causal_conv(x, w, seg):
    """Depthwise causal convolution over time: y_t = sum_i w[i] x_{t-n+1+i},
    taps that would reach into another document dropped.
    x (B, T, D); w (n, D), the last tap on the current token; seg (B, T)."""
    n = w.shape[0]
    y = x * w[n - 1]
    for back in range(1, n):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :-back]
        same = jnp.pad(seg, ((0, 0), (back, 0)),
                       constant_values=-1)[:, :-back] == seg
        y = y + jnp.where(same[..., None], shifted, 0) * w[n - 1 - back]
    return y


def _inside_sub_blocks(G, k, q):
    """Exact scores inside each sub-block. G, k, q (..., s, K) -> two
    (..., s, s): sum_c x_t[c] k_j[c] exp(G_t[c] - G_j[c]) for x = k and
    x = q, on and below the diagonal (above it the entries are not read)."""
    decay = jnp.exp(jnp.minimum(G[..., :, None, :] - G[..., None, :, :], 0.0))
    kk = decay * k[..., None, :, :]
    return (jnp.sum(kk * k[..., :, None, :], axis=-1),
            jnp.sum(kk * q[..., :, None, :], axis=-1))


def delta_rule_chunked(q, k, v, g, beta, seg, scale, chunk=64, sub=16,
                       dtype=None):
    """The recurrence chunk-wise (module docstring). q, k, g (B, T, H, K);
    v (B, T, H, V); beta (B, T, H); seg (B, T) -> o (B, T, H, V), float32.
    A `g` of (B, T, H) is one log-decay a head: every channel's alike.
    T is a multiple of `chunk`, `chunk` of `sub`.
    `dtype`: the type of the large matrix products' operands (None:
    float32); the scores inside a chunk, the triangular solve and the
    state's own recurrence stay in float32 at the highest precision."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], q.shape)
    C, s = chunk, sub
    N, n = T // C, C // s
    f32 = jnp.float32

    def chunks(x):          # (B, T, H, X) -> (B, H, N, C, X)
        return jnp.moveaxis(x.astype(f32).reshape(B, N, C, H, -1), 3, 1)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = jnp.moveaxis(beta.astype(f32).reshape(B, N, C, H), 3, 1)
    G = jnp.cumsum(gc, axis=3)                       # (B, H, N, C, K)

    # ---- documents: who sees whom, who sees the state the chunk starts
    # from (`cont`), whose update outlives the chunk (`tail`)
    sc = seg.reshape(B, N, C)
    before = jnp.concatenate([jnp.full((B, 1), -1, sc.dtype), sc[:, :-1, -1]],
                             axis=1)
    cont = (sc == before[:, :, None])[:, None]       # (B, 1, N, C)
    tail = (sc == sc[:, :, -1:])[:, None]
    same = (sc[:, :, :, None] == sc[:, :, None, :])[:, None]  # (B,1,N,C,C)
    t_ = jnp.arange(C)
    lower = (t_[:, None] >= t_[None, :]) & same
    strict = (t_[:, None] > t_[None, :]) & same

    # ---- the scores inside a chunk, from sub-block references
    Gs = G.reshape(B, H, N, n, s, K)
    ref = jnp.concatenate([jnp.zeros_like(Gs[..., :1, -1, :]),
                           Gs[..., :-1, -1, :]], axis=3)   # (B,H,N,n,K)
    row = jnp.exp(Gs - ref[..., None, :])            # <= 1
    k_row = kc.reshape(B, H, N, n, s, K) * row
    q_row = qc.reshape(B, H, N, n, s, K) * row
    # the keys as sub-block I sees them: exp(ref_I - G_j), j before I
    k_col = kc[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., :, None, :] - G[..., None, :, :], 0.0))   # (B,H,N,n,C,K)
    A = jnp.einsum('...isk,...ijk->...isj', k_row, k_col,
                   precision=_HIGH).reshape(B, H, N, C, C)
    Bm = jnp.einsum('...isk,...ijk->...isj', q_row, k_col,
                    precision=_HIGH).reshape(B, H, N, C, C)
    earlier = (t_[:, None] // s) > (t_[None, :] // s)       # j's block < t's
    a_in, b_in = jax.checkpoint(_inside_sub_blocks)(
        Gs, kc.reshape(B, H, N, n, s, K), qc.reshape(B, H, N, n, s, K))
    eye = jnp.eye(n, dtype=bool)[:, None, :, None]          # (n,1,n,1)

    def spread(x):      # (..., n, s, s) -> (..., C, C), on the block diagonal
        full = jnp.where(eye, x[..., :, :, None, :], 0.0)   # (...,n,s,n,s)
        return full.reshape(x.shape[:-3] + (C, C))

    A = jnp.where(strict, jnp.where(earlier, A, spread(a_in)), 0.0)
    Bm = jnp.where(lower, jnp.where(earlier, Bm, spread(b_in)), 0.0)

    # ---- the chunk's corrections as functions of the state it starts from
    k_in = kc * jnp.exp(G) * cont[..., None]         # reads S_0
    system = jnp.eye(C, dtype=f32) + bc[..., None] * A
    rhs = bc[..., None] * jnp.concatenate([k_in, vc], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(system, rhs, lower=True,
                                               unit_diagonal=True)
    W, U0 = solved[..., :K], solved[..., K:]         # U = U0 - W S_0

    def mm(spec, a, b):
        if dtype is not None:
            a, b = a.astype(dtype), b.astype(dtype)
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    G_end = G[..., -1:, :]
    k_out = kc * jnp.exp(G_end - G) * tail[..., None]
    keep = jnp.exp(G_end[..., 0, :]) * cont[..., -1, None]   # (B,H,N,K)
    M = keep[..., None] * jnp.eye(K, dtype=f32) \
        - mm('...ck,...cl->...kl', k_out, W)          # (B,H,N,K,K)
    Nn = mm('...ck,...cv->...kv', k_out, U0)          # (B,H,N,K,V)

    def carry(S, xs):
        m, nn = xs
        return jnp.einsum('bhkl,bhlv->bhkv', m, S, precision=_HIGH) + nn, S

    _, S0 = jax.lax.scan(carry, jnp.zeros((B, H, K, V), f32),
                         (jnp.moveaxis(M, 2, 0), jnp.moveaxis(Nn, 2, 0)))
    S0 = jnp.moveaxis(S0, 0, 2)                       # (B,H,N,K,V)

    q_in = qc * jnp.exp(G) * cont[..., None] - mm('...cj,...jk->...ck', Bm, W)
    o = mm('...ck,...kv->...cv', q_in, S0) + mm('...cj,...jv->...cv', Bm, U0)
    return jnp.moveaxis(o, 1, 3).reshape(B, T, H, V) * scale
